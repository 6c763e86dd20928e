"""Device milliseconds per round of the ops under the `MLA` name scope
(multi-head latent attention: projections, RoPE, the causal attention
and the output projection, forward, rematerialised and backward)."""
import scope_time


def read(r: dict):
    red = r["reduced"]
    s = scope_time.scope_seconds(red, "MLA")
    if not s or not red.rounds:
        return None
    return 1e3 * s / red.rounds
