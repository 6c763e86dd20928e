"""Device milliseconds per round of the ops under the wire's name scopes:
`Uplink`, `Aggregate` and `Downlink` (compression with error feedback,
Eq. 7, the broadcast)."""

SCOPES = ("Uplink", "Aggregate", "Downlink")


def read(r: dict):
    red = r["reduced"]
    s = sum(red.scope_s.get(k, 0.0) for k in SCOPES)
    if not s or not red.rounds:
        return None
    return 1e3 * s / red.rounds
