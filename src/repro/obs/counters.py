"""Process-wide counters: the compile work JAX reports, and host span
seconds by name.

Compile work comes from `jax.monitoring`, whose listeners are global to
the process, so `COUNTERS` registers them once, when this module is
first imported (before the program's first compile). It counts, on
the host clock:

  * `trace`: tracing a function to a jaxpr. A jit traced while another
    is being traced reports a span inside the outer one, so the seconds
    are the union of the spans, not their sum;
  * `lower`: lowering a jaxpr to an MLIR module;
  * `backend_compile`: XLA's compile, which holds the look-up in the
    persistent compilation cache (`cache_retrieval_s` on a hit);
  * `cache_hits` / `cache_misses`: persistent-cache reads that found an
    executable, and executables written to the cache after a miss.

`compile_s` is the union of the three phases: the seconds the process
spent compiling. `trace.span` adds each host span's seconds under its
name (`span_seconds`); a span opened many times sums.
"""
from __future__ import annotations

import threading

import jax

PHASES = {"/jax/core/compile/jaxpr_trace_duration": "trace",
          "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
          "/jax/core/compile/backend_compile_duration": "backend_compile"}
CACHE_EVENTS = {"/jax/compilation_cache/cache_hits": "cache_hits",
                "/jax/compilation_cache/cache_misses": "cache_misses"}
CACHE_RETRIEVAL = "/jax/compilation_cache/cache_retrieval_time_sec"


class _Union:
    """Seconds covered by intervals that arrive in the order they end
    and either nest or follow one another, as `with` blocks on one
    thread do: an interval that starts before the latest ones swallows
    them."""

    def __init__(self) -> None:
        self.seconds = 0.0
        self._tops: list = []          # disjoint (start, end), by start

    def add(self, start: float, end: float) -> None:
        while self._tops and self._tops[-1][0] >= start:
            s, e = self._tops.pop()
            self.seconds -= e - s
        self._tops.append((start, end))
        self.seconds += end - start


class Counters:
    """Compile and span counters; `snapshot()` reads them as one flat
    dict of numbers, `since(before)` as the change since a snapshot."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._phase = {p: _Union() for p in PHASES.values()}
        self._count = dict.fromkeys(PHASES.values(), 0)
        self._all = _Union()
        self._cache = dict.fromkeys(CACHE_EVENTS.values(), 0)
        self._cache_retrieval_s = 0.0
        self._spans: dict = {}

    # -- jax.monitoring listeners ------------------------------------------
    def on_time_span(self, event: str, start: float, end: float,
                     **kw) -> None:
        phase = PHASES.get(event)
        if phase is not None:
            with self._lock:
                self._phase[phase].add(start, end)
                self._count[phase] += 1
                self._all.add(start, end)

    def on_event(self, event: str, **kw) -> None:
        name = CACHE_EVENTS.get(event)
        if name is not None:
            with self._lock:
                self._cache[name] += 1

    def on_duration(self, event: str, duration: float, **kw) -> None:
        if event == CACHE_RETRIEVAL:
            with self._lock:
                self._cache_retrieval_s += duration

    def register(self) -> None:
        jax.monitoring.register_event_time_span_listener(self.on_time_span)
        jax.monitoring.register_event_listener(self.on_event)
        jax.monitoring.register_event_duration_secs_listener(
            self.on_duration)

    # -- spans -----------------------------------------------------------
    def add_span(self, name: str, seconds: float) -> None:
        with self._lock:
            self._spans[name] = self._spans.get(name, 0.0) + seconds

    def span_seconds(self) -> dict:
        """Host seconds per span name since the process started."""
        with self._lock:
            return dict(self._spans)

    # -- reading ---------------------------------------------------------
    def snapshot(self) -> dict:
        with self._lock:
            out = {"compile_s": self._all.seconds}
            for p, u in self._phase.items():
                out[f"{p}_s"] = u.seconds
                out[f"{p}s"] = self._count[p]
            out.update(self._cache)
            out["cache_retrieval_s"] = self._cache_retrieval_s
            return out

    def since(self, before: dict) -> dict:
        return {k: v - before.get(k, 0) for k, v in self.snapshot().items()}


COUNTERS = Counters()
COUNTERS.register()
