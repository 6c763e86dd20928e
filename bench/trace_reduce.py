"""From a profiler trace to the numbers the per-layer metrics read.

Reads the Perfetto JSON that `jax.profiler` writes beside its xplane
(`create_perfetto_trace=True`). On a TPU the device process
(`/device:TPU:<n>`) has an "XLA Ops" thread whose events are the
operations that ran, each with its HLO category and `tf_op`, the op's
name-scope path (`jit(train_step)/LocalUpdate/...`). The host process
holds the benchmark's own `jax.profiler.TraceAnnotation` spans, named
`bench.<what>`, on the same clock.

`reduce_trace` gives:
  * busy seconds per device: the union of its op intervals inside the
    window, and the idle share 1 - busy / window;
  * device seconds per name scope (ops inside a `while` or `conditional`
    are counted, the container events themselves are not);
  * device seconds per custom-call kernel name;
  * the costliest ops, and the longest idle gaps labelled by the host
    span they fall in.
"""
from __future__ import annotations

import gzip
import json
import re
from collections import defaultdict
from typing import NamedTuple

# events that contain other ops of the same line: counted in the busy
# union, never summed as time of their own
CONTAINERS = ("while", "conditional", "call")
HOST_PREFIX = "bench."


class Op(NamedTuple):
    start: float          # seconds on the trace clock
    dur: float            # seconds
    name: str
    category: str
    scope: str            # tf_op name-scope path ("" if none)


class Span(NamedTuple):
    start: float
    dur: float
    name: str


class Trace(NamedTuple):
    ops: dict             # device name -> [Op]
    spans: list           # [Span] of the benchmark's host annotations


def load(path: str) -> Trace:
    """Parse a (gzipped) Perfetto JSON trace written by jax.profiler."""
    opener = gzip.open if str(path).endswith(".gz") else open
    with opener(path, "rt") as f:
        doc = json.load(f)
    return parse(doc)


def parse(doc: dict) -> Trace:
    events = doc["traceEvents"] if isinstance(doc, dict) else doc
    procs, threads = {}, {}
    for e in events:
        if e.get("ph") != "M":
            continue
        if e.get("name") == "process_name":
            procs[e["pid"]] = e["args"]["name"]
        elif e.get("name") == "thread_name":
            threads[(e["pid"], e.get("tid"))] = e["args"]["name"]
    devices = {pid: n for pid, n in procs.items()
               if n.startswith("/device:") and "CUSTOM" not in n}
    ops: dict = defaultdict(list)
    spans = []
    for e in events:
        if e.get("ph") != "X":
            continue
        pid = e.get("pid")
        if pid in devices and threads.get((pid, e.get("tid"))) == "XLA Ops":
            a = e.get("args") or {}
            ops[devices[pid]].append(Op(
                start=e["ts"] * 1e-6, dur=e.get("dur", 0.0) * 1e-6,
                name=e.get("name", ""), category=a.get("hlo_category", ""),
                scope=a.get("tf_op", "")))
        elif pid not in devices and e.get("name", "").startswith(HOST_PREFIX):
            spans.append(Span(e["ts"] * 1e-6, e.get("dur", 0.0) * 1e-6,
                              e["name"][len(HOST_PREFIX):]))
    spans.sort()
    return Trace(ops=dict(ops), spans=spans)


def _union(intervals: list) -> list:
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _base(name: str) -> str:
    """`fusion.123` -> `fusion`: one name for every instance of an op."""
    return re.sub(r"(\.\d+)+$", "", name)


def scope_parts(scope: str) -> list:
    return [p for p in scope.split("/") if p]


class Reduced(NamedTuple):
    window_s: float
    busy_s: float                  # mean over devices
    idle_share: float
    rounds: int                    # `bench.step` spans in the window
    scope_s: dict                  # scope name -> device seconds
    kernel_s: dict                 # custom-call kernel name -> seconds
    top_ops: list                  # [[name, seconds]] costliest, <= 10
    idle_gaps: list                # [[host span, seconds]] longest, <= 10
    devices: int


def reduce_trace(trace: Trace, step_span: str = "step") -> Reduced:
    """Reduce a trace over the window from the first to the last of the
    benchmark's host spans."""
    if not trace.spans:
        raise ValueError("trace holds no benchmark host span")
    if not trace.ops:
        raise ValueError("trace holds no device operation")
    t0 = trace.spans[0].start
    t1 = max(s.start + s.dur for s in trace.spans)
    window = t1 - t0
    busy_all = []
    scope_s, kernel_s, per_op = (defaultdict(float), defaultdict(float),
                                 defaultdict(float))
    first = None
    for dev, ops in sorted(trace.ops.items()):
        ivs = []
        for op in ops:
            s, e = max(op.start, t0), min(op.start + op.dur, t1)
            if e <= s:
                continue
            ivs.append((s, e))
            if op.category in CONTAINERS:
                continue
            d = e - s
            parts = scope_parts(op.scope)
            for p in set(parts[1:]):
                scope_s[p] += d
            if op.category == "custom-call":
                kernel_s[_base(op.name)] += d
            stage = next((p for p in parts[1:] if p[:1].isupper()), "-")
            per_op[f"{stage}:{_base(op.name)}"] += d
        busy = _union(ivs)
        busy_all.append(sum(e - s for s, e in busy))
        if first is None:
            first = busy
    busy_s = sum(busy_all) / len(busy_all)

    gaps = []
    prev = t0
    for s, e in (first or []) + [[t1, t1]]:
        if s > prev:
            mid = 0.5 * (s + prev)
            label = next((sp.name for sp in trace.spans
                          if sp.start <= mid <= sp.start + sp.dur), "harness")
            gaps.append([label, s - prev])
        prev = max(prev, e)
    gaps.sort(key=lambda g: -g[1])
    top = sorted(([k, v] for k, v in per_op.items()), key=lambda kv: -kv[1])
    rounds = sum(1 for s in trace.spans if s.name == step_span)
    return Reduced(window_s=window, busy_s=busy_s,
                   idle_share=1.0 - busy_s / window if window > 0 else 0.0,
                   rounds=rounds, scope_s=dict(scope_s),
                   kernel_s=dict(kernel_s), top_ops=top[:10],
                   idle_gaps=gaps[:10], devices=len(busy_all))
