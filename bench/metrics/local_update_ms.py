"""Device milliseconds per round of the ops under the `LocalUpdate` name
scope (local SGD, the PSO displacement and the D_g scoring of workers)."""


def read(r: dict):
    red = r["reduced"]
    s = red.scope_s.get("LocalUpdate")
    if not s or not red.rounds:
        return None
    return 1e3 * s / red.rounds
