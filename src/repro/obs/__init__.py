"""repro.obs — structured telemetry bus.

Typed events (events), composable sinks + stream readers (sinks),
host spans on the profiler clock, stage scopes and jax.profiler windows
(trace), compile and span counters (counters), and the terminal run
monitor (monitor). See docs/obs.md for the event schema.
"""
from repro.obs.events import (EVENT_SCHEMA, EVENT_TYPES, Emitter, Event,
                              KernelEvent, LogEvent, NULL, NullEmitter,
                              RoundEvent, RunClock, RunEnd, RunStart,
                              StageEvent, SweepEvent, new_run_id, parse,
                              parse_line)
from repro.obs.sinks import (CsvSink, FanoutSink, JsonlSink,
                             RingBufferSink, Sink, default_obs_dir,
                             follow_jsonl, merge_streams, read_events)
from repro.obs.counters import COUNTERS
from repro.obs.trace import (RoundProfiler, Span, StageTracer, activated,
                             current, install, note_kernel, recording,
                             span, stage_span, uninstall)

__all__ = [
    "EVENT_SCHEMA", "EVENT_TYPES", "Emitter", "Event", "KernelEvent",
    "LogEvent", "NULL", "NullEmitter", "RoundEvent", "RunClock",
    "RunEnd", "RunStart", "StageEvent", "SweepEvent", "new_run_id",
    "parse", "parse_line",
    "CsvSink", "FanoutSink", "JsonlSink", "RingBufferSink", "Sink",
    "default_obs_dir", "follow_jsonl", "merge_streams", "read_events",
    "COUNTERS", "RoundProfiler", "Span", "StageTracer", "activated",
    "current", "install", "note_kernel", "recording", "span",
    "stage_span", "uninstall",
]
