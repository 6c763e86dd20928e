"""Seconds of set-up spent making the fleet's data: the program's host
spans `setup.data` (label draws, Dirichlet partition, image synthesis)
and `setup.eta` (the Eq.-2 non-iid degrees), each ending on the device
work it started. Read from the program's span counters
(`repro.obs.counters`); a program without them gives nothing."""

SPANS = ("setup.data", "setup.eta")


def read(r: dict):
    try:
        from repro.obs.counters import COUNTERS
    except ImportError:
        return None
    seconds = COUNTERS.span_seconds()
    if not any(s in seconds for s in SPANS):
        return None
    return sum(seconds.get(s, 0.0) for s in SPANS)
