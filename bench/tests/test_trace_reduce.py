"""trace_reduce on a hand-made trace and on a small trace recorded on the
chip (one traced round of paper-cnn5-x8.c50-int4 on a TPU v5 lite)."""
from pathlib import Path

import pytest

import trace_reduce as tr

DATA = Path(__file__).resolve().parent / "data"
RECORDED = DATA / "paper-cnn5-x8.c50-int4.trace.json.gz"


def _doc():
    us = 1.0   # Perfetto timestamps are in microseconds
    meta = [
        {"ph": "M", "pid": 3, "name": "process_name",
         "args": {"name": "/device:TPU:0"}},
        {"ph": "M", "pid": 3, "tid": 3, "name": "thread_name",
         "args": {"name": "XLA Ops"}},
        {"ph": "M", "pid": 9, "name": "process_name",
         "args": {"name": "/host:CPU"}},
    ]

    def op(ts, dur, name, cat, scope):
        return {"ph": "X", "pid": 3, "tid": 3, "ts": ts * us, "dur": dur * us,
                "name": name, "args": {"hlo_category": cat, "tf_op": scope}}

    def span(ts, dur, name):
        return {"ph": "X", "pid": 9, "tid": 1, "ts": ts, "dur": dur,
                "name": name}

    return {"traceEvents": meta + [
        span(0, 40, "bench.step"), span(40, 60, "bench.host_read"),
        # a while loop holding two body ops, then a kernel, then a gap
        op(10, 30, "while.1", "while", "jit(f)/LocalUpdate/while"),
        op(10, 10, "fusion.1", "loop fusion", "jit(f)/LocalUpdate/while/body"),
        op(25, 15, "fusion.2", "loop fusion", "jit(f)/LocalUpdate/while/body"),
        op(50, 5, "wire_agg_2d.3", "custom-call", "jit(f)/Aggregate/x"),
        op(80, 10, "copy.7", "data formatting", "state.params:"),
        span(200, 5, "other"),      # not a benchmark span
    ]}


def test_hand_made_trace():
    red = tr.reduce_trace(tr.parse(_doc()))
    assert red.window_s == pytest.approx(100e-6)
    # union: [10, 40] + [50, 55] + [80, 90] = 45 us
    assert red.busy_s == pytest.approx(45e-6)
    assert red.idle_share == pytest.approx(0.55)
    # the while container is not summed; its body ops are
    assert red.scope_s["LocalUpdate"] == pytest.approx(25e-6)
    assert red.scope_s["Aggregate"] == pytest.approx(5e-6)
    assert red.kernel_s == {"wire_agg_2d": pytest.approx(5e-6)}
    assert red.rounds == 1
    assert red.top_ops[0] == ["LocalUpdate:fusion", pytest.approx(25e-6)]
    # gaps: [0,10] step, [40,50] and [55,80] and [90,100] host_read
    assert [g[0] for g in red.idle_gaps] == ["host_read", "step", "host_read",
                                             "host_read"]
    assert red.idle_gaps[0][1] == pytest.approx(25e-6)


def test_trace_without_spans_is_refused():
    doc = _doc()
    doc["traceEvents"] = [e for e in doc["traceEvents"]
                          if not e.get("name", "").startswith("bench.")]
    with pytest.raises(ValueError):
        tr.reduce_trace(tr.parse(doc))


def test_recorded_chip_trace():
    red = tr.reduce_trace(tr.load(RECORDED))
    assert red.devices == 1 and red.rounds == 1
    assert 0.0 < red.busy_s <= red.window_s
    assert 0.0 <= red.idle_share < 1.0
    # every stage scope of the round is found
    for scope in ("LocalUpdate", "ScoreSelect", "Uplink", "Aggregate",
                  "Downlink", "BestTracking"):
        assert red.scope_s.get(scope, 0.0) > 0.0, scope
    stages = sum(red.scope_s[s] for s in ("LocalUpdate", "ScoreSelect",
                                          "Uplink", "Aggregate", "Downlink",
                                          "BestTracking"))
    assert stages <= red.busy_s
    # the four Pallas wire kernels, by name
    for k in ("quant_pack_ef", "wire_agg", "quant_pack_2d", "dequant_unpack"):
        assert any(k in name for name in red.kernel_s), k
    assert len(red.top_ops) <= 10 and len(red.idle_gaps) <= 10
