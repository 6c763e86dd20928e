"""Host spans on the profiler clock, stage scopes, and profiler windows.

`span(name)` is the program's one tracing primitive, placed at the
layer boundaries of set-up (`setup.data`, `setup.eta`, `setup.init`)
and of the round's host side (`round.key`, `round.dispatch`, ...; the
runner's `round`, `Step` and `Eval`). Every span:

  * enters a `jax.profiler.TraceAnnotation` named `repro.<name>`: a
    no-op unless a profiler trace runs, when the span lands in the same
    trace as the device ops, on one clock;
  * adds its seconds under its name to `counters.COUNTERS`;
  * with a recorder installed (`recording()`), is kept in memory as a
    `Span` (name, parent, start, end on `time.perf_counter`);
  * with a `StageTracer` installed whose emitter is active, emits a
    `StageEvent` (phase="host", its start on the run clock, its parent).

A span adds no host sync and nothing inside jit. Spans open and close
on the thread that drives the rounds.

`stage_span(name)` is the instrumentation point the round pipeline and
both engines call around their stages inside jit (LocalUpdate /
ScoreSelect / Uplink / Aggregate / Downlink / BestTracking). It always
enters `jax.named_scope(name)`, which costs only at trace time, so every
run compiles the same programs and device traces carry the stage names.
With a `StageTracer` installed it is also a span, of phase "trace": the
stages run once, while jax traces the round, so those events are the
per-stage tracing cost, not per-round time.

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

from repro.obs.counters import COUNTERS
from repro.obs.events import Emitter

_ACTIVE: Optional["StageTracer"] = None
_RECORDER: Optional[list] = None
_OPEN: list = []                 # names of the spans open now, outermost first


class Span:
    """One host span: `name`, the name of the span it opened inside
    (`parent`, None at the top), and `start`/`end` on
    `time.perf_counter`. `span()` makes one; use it as a context
    manager, which yields the span itself."""
    __slots__ = ("name", "parent", "start", "end", "round", "phase",
                 "_annotation")

    def __init__(self, name: str, round_idx: Optional[int] = None,
                 phase: str = "host"):
        self.name = name
        self.round = round_idx
        self.phase = phase
        self.parent = self.start = self.end = None

    @property
    def dur_s(self) -> float:
        return self.end - self.start

    def __enter__(self) -> "Span":
        self.parent = _OPEN[-1] if _OPEN else None
        _OPEN.append(self.name)
        self._annotation = jax.profiler.TraceAnnotation("repro." + self.name)
        self._annotation.__enter__()
        self.start = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        self.end = time.perf_counter()
        self._annotation.__exit__(*exc)
        self._annotation = None
        _OPEN.pop()
        COUNTERS.add_span(self.name, self.end - self.start)
        if _RECORDER is not None:
            _RECORDER.append(self)
        t = _ACTIVE
        if t is not None and t.emitter.active:
            t.emitter.stage(self.name, self.end - self.start,
                            phase=self.phase, round_idx=self.round,
                            start_s=t.emitter.clock.at(self.start),
                            parent=self.parent)


def span(name: str, *, round_idx: Optional[int] = None) -> Span:
    """A host span named `name` (`round_idx`: the round it belongs to,
    carried onto its StageEvent)."""
    return Span(name, round_idx)


@contextlib.contextmanager
def recording() -> Iterator[list]:
    """Keep every span that ends inside the block, in the order they
    end, in the list this yields."""
    global _RECORDER
    prev, _RECORDER = _RECORDER, []
    try:
        yield _RECORDER
    finally:
        _RECORDER = prev


class StageTracer:
    """While installed, sends spans to `emitter` as StageEvents: host
    spans as phase "host" and, when `stages`, the `stage_span` stages as
    `phase` (timed while jax traces the jitted round body)."""

    def __init__(self, emitter: Emitter, phase: str = "trace",
                 stages: bool = True):
        self.emitter = emitter
        self.phase = phase
        self.stages = stages

    @contextlib.contextmanager
    def span(self, stage: str) -> Iterator[None]:
        with jax.named_scope(stage), Span(stage, phase=self.phase):
            yield

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
    """The pipeline/engine instrumentation point: `jax.named_scope(name)`
    always, and a timed span of the installed tracer's phase when it
    traces stages."""
    t = _ACTIVE
    if t is None or not t.stages:
        return jax.named_scope(name)
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
