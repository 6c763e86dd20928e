"""Seconds of set-up spent compiling: tracing, lowering and XLA's
compile with its persistent-cache look-ups, as the program's compile
counters (`repro.obs.counters`, fed by `jax.monitoring`) count them,
the union of those spans. Read after the window, in which nothing may
compile (a run that compiles there is not correct), so every second
counted is set-up's; that includes the output check's two small
reductions, compiled during the checked rounds. A program without the
counters gives nothing."""


def read(r: dict):
    try:
        from repro.obs.counters import COUNTERS
    except ImportError:
        return None
    return COUNTERS.snapshot()["compile_s"]
