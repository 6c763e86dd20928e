"""The set-up readers (`setup_data_s`, `setup_compile_s`) on the CPU:
what they read from the program's spans and counters, and that a
program without those gives nothing instead of raising."""
import sys

import numpy as np
import pytest

import run
from conftest import tiny

CELL = "paper-cnn5-x8.c50-dense"


def reader(name: str):
    return run.load_module(run.BENCH / "metrics" / f"{name}.py",
                           f"bench_metric_{name}")


def test_setup_data_s_reads_the_set_up_data_spans():
    from repro.experiments.runner import build
    from repro.obs import recording
    read = reader("setup_data_s").read
    ctx, _, overrides = tiny(CELL)
    before = read({}) or 0.0
    with recording() as rec:
        build(run.resolve(ctx, 7, overrides))
    spans = {s.name: s.dur_s for s in rec}
    assert {"setup.data", "setup.eta", "setup.init"} <= set(spans)
    assert read({}) - before == pytest.approx(
        spans["setup.data"] + spans["setup.eta"])


def test_setup_compile_s_counts_a_compile():
    import jax
    read = reader("setup_compile_s").read
    before = read({})
    jax.jit(lambda v: v * 5.0 - 2.0)(np.ones(3, np.float32))
    assert read({}) > before


@pytest.mark.parametrize("name", ["setup_data_s", "setup_compile_s"])
def test_program_without_counters_gives_nothing(name, monkeypatch):
    monkeypatch.setitem(sys.modules, "repro.obs.counters", None)
    assert reader(name).read({}) is None
