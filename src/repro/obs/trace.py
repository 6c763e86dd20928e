"""Per-stage span tracing + jax.profiler integration.

`stage_span(name)` is the single instrumentation point the round
pipeline and both engines call around their stages (LocalUpdate /
ScoreSelect / Uplink / Aggregate / Downlink / BestTracking). With no
tracer installed it returns a shared `nullcontext` — one module-global
load and an identity context manager, so the disabled path adds no
measurable work and, critically, no host sync inside jit.

With a `StageTracer` installed (the runner does this for obs-enabled
runs, BEFORE the first step so the spans fire during the round-0 jit
trace), each span:

  * records host-side wall-time and emits a StageEvent. Stages inside a
    jitted round body execute once, at trace time — those spans are
    tagged phase="trace" (per-stage tracing/compile cost breakdown);
    per-round steady-state timings come from the runner's phase="host"
    spans (Step = dispatch + device sync, Eval = accuracy fetch).
  * enters `jax.named_scope(name)`, so device-side profiler traces
    (`--profile-dir`) carry the stage names into TensorBoard.

`RoundProfiler` owns the `jax.profiler.start_trace`/`stop_trace` window
(`--profile-dir` captures `profile_rounds` rounds starting after the
round-0 compile) and wraps each captured round in a
`StepTraceAnnotation`, the marker TensorBoard's step view groups by.

`note_kernel` is the KernelEvent hook kernels call at dispatch-decision
time (pallas vs interpret/ref) — see `repro.kernels.runtime`.
"""
from __future__ import annotations

import contextlib
import time
from typing import Iterator, Optional

import jax

from repro.obs.events import Emitter

_NOOP = contextlib.nullcontext()
_ACTIVE: Optional["StageTracer"] = None


class StageTracer:
    """Emits StageEvents for `stage_span` blocks while installed."""

    def __init__(self, emitter: Emitter, phase: str = "trace"):
        self.emitter = emitter
        self.phase = phase

    @contextlib.contextmanager
    def span(self, stage: str) -> Iterator[None]:
        t0 = time.perf_counter()
        with jax.named_scope(stage):
            try:
                yield
            finally:
                self.emitter.stage(stage, time.perf_counter() - t0,
                                   phase=self.phase)

    def kernel(self, name: str, *, backend: str, interpret: bool,
               **info) -> None:
        self.emitter.kernel(name, backend=backend, interpret=interpret,
                            **info)


def install(tracer: StageTracer) -> None:
    global _ACTIVE
    _ACTIVE = tracer


def uninstall() -> None:
    global _ACTIVE
    _ACTIVE = None


def current() -> Optional[StageTracer]:
    return _ACTIVE


@contextlib.contextmanager
def activated(tracer: Optional[StageTracer]) -> Iterator[None]:
    """Install `tracer` for the duration (None = leave as-is)."""
    if tracer is None:
        yield
        return
    prev = _ACTIVE
    install(tracer)
    try:
        yield
    finally:
        install(prev) if prev is not None else uninstall()


def stage_span(name: str):
    """The pipeline/engine instrumentation point. No tracer -> a shared
    nullcontext (near-zero disabled overhead, nothing added inside
    jit); tracer -> timed span + jax.named_scope."""
    t = _ACTIVE
    if t is None:
        return _NOOP
    return t.span(name)


def note_kernel(name: str, *, backend: str, interpret: bool,
                **info) -> None:
    """Kernel dispatch hook: emits a KernelEvent when tracing is on."""
    t = _ACTIVE
    if t is not None:
        t.kernel(name, backend=backend, interpret=interpret, **info)


# ---------------------------------------------------------------------------
# jax.profiler round windows
# ---------------------------------------------------------------------------

class RoundProfiler:
    """Capture a TensorBoard-loadable device trace for a round window.

    `round(t)` wraps the runner's per-round step: the trace starts when
    `t == start` (default 1 — past the round-0 compile), every captured
    round is a `StepTraceAnnotation`, and the trace stops after `count`
    rounds. A trace that was asked for and cannot start or stop raises:
    the run fails instead of finishing without the trace it was run
    for."""

    def __init__(self, profile_dir: str, start: int = 1, count: int = 3,
                 emitter: Emitter = None):
        self.dir = str(profile_dir)
        self.start = max(0, start)
        self.last = self.start + max(1, count) - 1
        self.emitter = emitter
        self.running = False

    def _log(self, msg: str) -> None:
        if self.emitter is not None:
            self.emitter.log(msg, echo=True)
        else:
            print(msg, flush=True)

    @contextlib.contextmanager
    def round(self, t: int) -> Iterator[None]:
        if not self.running and t == self.start:
            jax.profiler.start_trace(self.dir)
            self.running = True
            self._log(f"[obs] profiler trace started -> {self.dir} "
                      f"(rounds {self.start}..{self.last})")
        if not self.running:
            yield
            return
        try:
            with jax.profiler.StepTraceAnnotation("round", step_num=t):
                yield
        finally:
            if t >= self.last:
                self.stop()

    def stop(self) -> None:
        if self.running:
            self.running = False     # a failed stop is not retried
            jax.profiler.stop_trace()
            self._log(f"[obs] profiler trace written -> {self.dir}")
