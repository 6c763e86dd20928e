"""repro.obs: event model round-trips, sink behavior, stage tracing,
and the stream/artifact bit-equality contract.

The load-bearing guarantee is tested end-to-end on both engines: an
obs-enabled 3-round run's RoundEvents must carry exactly the artifact's
per-round metric history, bit-equal after one JSON round trip (the
runner builds ONE row dict and feeds both) — and turning obs on must
not perturb the numerics relative to an obs-off run of the same seed.
"""
import json
from pathlib import Path

import hypothesis as hp
import hypothesis.strategies as st
import pytest

from repro.experiments import (SCHEMA_VERSION, get_scenario, load_result,
                               override, run, sweep, to_dict)
from repro.obs import (EVENT_TYPES, NULL, CsvSink, Emitter, FanoutSink,
                       JsonlSink, KernelEvent, RingBufferSink, RoundEvent,
                       RunEnd, RunStart, StageEvent, StageTracer, SweepEvent,
                       follow_jsonl, merge_streams, new_run_id, parse,
                       parse_line, read_events)
from repro.obs import COUNTERS, recording, span
from repro.obs import monitor as obs_monitor
from repro.obs import trace as obs_trace

TINY_PAPER = ("data.num_workers=4", "data.n_local=64", "run.rounds=3",
              "model.width_mult=2", "algo.local_epochs=1")
TINY_MESH = ("data.num_workers=2", "model.seq_len=16",
             "model.per_worker_batch=1", "run.rounds=3")

# the RoundPipeline stages whose spans must appear on every obs stream
PIPELINE_STAGES = {"LocalUpdate", "ScoreSelect", "Uplink", "Aggregate",
                   "Downlink", "BestTracking"}


def _obs_spec(scenario: str, obs_dir: Path, *extra: str):
    spec = get_scenario(scenario)
    ovr = TINY_PAPER if spec.model.kind == "paper" else TINY_MESH
    return override(spec, *ovr, "run.obs.enabled=true",
                    f"run.obs.dir={obs_dir}", *extra)


@pytest.fixture(scope="module")
def paper_obs(tmp_path_factory):
    """One obs-enabled 3-round paper run, shared across tests."""
    obs_dir = tmp_path_factory.mktemp("paper_obs")
    res = run(_obs_spec("quickstart", obs_dir, "run.obs.csv=true"),
              verbose=False)
    return res, read_events(res.events_path)


@pytest.fixture(scope="module")
def mesh_obs(tmp_path_factory):
    obs_dir = tmp_path_factory.mktemp("mesh_obs")
    res = run(_obs_spec("mesh/smollm-smoke", obs_dir), verbose=False)
    return res, read_events(res.events_path)


class TestEventModel:
    @pytest.mark.parametrize("cls", sorted(EVENT_TYPES.values(),
                                           key=lambda c: c.kind))
    def test_default_round_trip(self, cls):
        ev = cls(run_id="r", t_s=1.5)
        assert parse_line(ev.to_json()) == ev

    def test_populated_round_trip(self):
        ev = RoundEvent(run_id="r", t_s=0.25, round=7,
                        metrics={"acc": 0.125, "selected": 3.0})
        back = parse(json.loads(ev.to_json()))
        assert back == ev
        assert back.metrics["acc"] == 0.125

    def test_unknown_kind_rejected(self):
        with pytest.raises(ValueError, match="unknown event kind"):
            parse({"kind": "telemetry", "run_id": "r"})

    def test_unknown_field_rejected(self):
        with pytest.raises(ValueError, match="gpu_watts"):
            parse({"kind": "round", "run_id": "r", "t_s": 0.0,
                   "round": 0, "metrics": {}, "gpu_watts": 42})

    @hp.given(st.lists(st.floats(min_value=-1e9, max_value=1e9),
                       min_size=1, max_size=12))
    def test_metric_floats_survive_stream_bit_equal(self, vals):
        """Any float payload must cross the JSONL boundary bit-equal —
        the property the artifact/stream equality contract rests on."""
        metrics = {f"m{i}": v for i, v in enumerate(vals)}
        back = parse_line(RoundEvent(run_id="r", metrics=metrics).to_json())
        assert back.metrics == metrics

    def test_new_run_id_distinct_and_greppable(self):
        a, b = new_run_id("quickstart"), new_run_id("quickstart")
        assert a != b
        assert a.startswith("quickstart__")
        assert "/" not in new_run_id("mesh/smollm-smoke")


class TestSinks:
    def test_jsonl_round_trip(self, tmp_path):
        p = tmp_path / "s.jsonl"
        em = Emitter("rid", JsonlSink(p))
        em.run_start(scenario="q", seed=0)
        em.round(0, {"acc": 0.5})
        em.run_end(rounds=1, totals={"acc": 0.5})
        em.close()
        evs = read_events(p)
        assert [e.kind for e in evs] == ["run_start", "round", "run_end"]
        assert all(e.run_id == "rid" for e in evs)
        assert [e.t_s for e in evs] == sorted(e.t_s for e in evs)

    def test_jsonl_rotation(self, tmp_path):
        p = tmp_path / "s.jsonl"
        sink = JsonlSink(p, rotate_bytes=200)
        em = Emitter("rid", sink)
        for t in range(20):
            em.round(t, {"acc": 0.1})
        em.close()
        assert p.with_name("s.jsonl.1").exists()
        # the live file may have just rotated away; if present it's capped
        if p.exists():
            assert p.stat().st_size <= 400

    def test_csv_rounds_only_fixed_columns(self, tmp_path):
        p = tmp_path / "s.csv"
        em = Emitter("rid", CsvSink(p))
        em.run_start(scenario="q")          # ignored by the CSV view
        em.round(0, {"acc": 0.5, "loss": 2.0})
        em.round(1, {"acc": 0.6, "loss": 1.5, "extra": 9.0})
        em.close()
        lines = p.read_text().strip().splitlines()
        assert lines[0] == "run_id,round,t_s,acc,loss"
        assert len(lines) == 3
        assert lines[1].startswith("rid,0,")

    def test_ring_buffer_caps(self):
        sink = RingBufferSink(capacity=3)
        em = Emitter("rid", sink)
        for t in range(10):
            em.round(t, {})
        assert [e.round for e in sink.events] == [7, 8, 9]

    def test_fanout_tees_and_proxies_path(self, tmp_path):
        ring = RingBufferSink()
        jsonl = JsonlSink(tmp_path / "s.jsonl")
        em = Emitter("rid", FanoutSink(ring, jsonl))
        em.round(0, {"acc": 0.5})
        em.close()
        assert em.path == str(tmp_path / "s.jsonl")
        assert len(ring.events) == len(read_events(em.path)) == 1

    def test_merge_streams_regroups_by_run_id(self, tmp_path):
        # two interleaved producers, one file each (the sweep-pool shape)
        for rid in ("a", "b"):
            em = Emitter(rid, JsonlSink(tmp_path / f"{rid}.jsonl"))
            em.round(0, {})
            em.round(1, {})
            em.close()
        runs = merge_streams(sorted(tmp_path.glob("*.jsonl")))
        assert set(runs) == {"a", "b"}
        for evs in runs.values():
            assert [e.round for e in evs] == [0, 1]
            assert [e.t_s for e in evs] == sorted(e.t_s for e in evs)

    def test_follow_jsonl_stops_on_run_end(self, tmp_path):
        p = tmp_path / "s.jsonl"
        em = Emitter("rid", JsonlSink(p))
        em.round(0, {})
        em.run_end(rounds=1)
        em.close()
        evs = list(follow_jsonl(p, poll_s=0.01, timeout_s=2.0))
        assert [e.kind for e in evs] == ["round", "run_end"]

    def test_follow_jsonl_times_out_without_growth(self, tmp_path):
        p = tmp_path / "s.jsonl"
        em = Emitter("rid", JsonlSink(p))
        em.round(0, {})
        em.close()
        evs = list(follow_jsonl(p, poll_s=0.01, timeout_s=0.1))
        assert [e.kind for e in evs] == ["round"]


class TestTracing:
    def test_stage_span_is_shared_nullcontext_when_uninstalled(self):
        """No tracer: a stage is only its named scope. It emits nothing
        and records nothing, but the compiled program carries the stage
        name, as a traced run's does."""
        import jax
        import numpy as np

        def f(x):
            with obs_trace.stage_span("Uplink"):
                return jax.numpy.sin(x) * 2

        assert obs_trace.current() is None
        with obs_trace.recording() as rec:
            text = jax.jit(f).lower(np.ones(3, np.float32)).compile() \
                .as_text()
        assert "Uplink" in text
        assert rec == []

    def test_spans_emit_stage_events(self):
        ring = RingBufferSink()
        tracer = StageTracer(Emitter("rid", ring), phase="trace")
        with obs_trace.activated(tracer):
            with obs_trace.stage_span("Uplink"):
                pass
            obs_trace.note_kernel("quant_pack", backend="cpu",
                                  interpret=True, bits=4)
        assert obs_trace.current() is None
        stage, kernel = ring.events
        assert isinstance(stage, StageEvent)
        assert (stage.stage, stage.phase) == ("Uplink", "trace")
        assert stage.dur_s >= 0.0
        assert isinstance(kernel, KernelEvent)
        assert kernel.info == {"bits": 4}

    def test_activated_restores_previous_tracer(self):
        outer = StageTracer(Emitter("o", RingBufferSink()))
        inner = StageTracer(Emitter("i", RingBufferSink()))
        with obs_trace.activated(outer):
            with obs_trace.activated(inner):
                assert obs_trace.current() is inner
            assert obs_trace.current() is outer
        assert obs_trace.current() is None

    def test_null_emitter_span_is_reusable(self):
        """With no tracer and no recorder a span nests in itself and
        leaves nothing behind but its counter."""
        assert obs_trace.current() is None
        before = COUNTERS.span_seconds().get("Step", 0.0)
        with obs_trace.span("Step") as outer:
            with obs_trace.span("Step") as inner:
                pass
        assert inner.parent == "Step" and outer.parent is None
        assert obs_trace._RECORDER is None and obs_trace._OPEN == []
        assert COUNTERS.span_seconds()["Step"] == pytest.approx(
            before + outer.dur_s + inner.dur_s)
        assert NULL.path is None and not NULL.active

    @pytest.mark.parametrize("failing", ["start_trace", "stop_trace"])
    def test_profiler_failure_fails_the_run(self, failing, tmp_path,
                                            monkeypatch):
        """A requested trace that cannot start or stop is an error: the
        run raises and its stream ends with status=error, instead of
        exiting 0 without the trace."""
        import jax

        def boom(*args, **kwargs):
            raise RuntimeError(f"{failing} refused")

        monkeypatch.setattr(jax.profiler, "start_trace", lambda *a, **k: None)
        monkeypatch.setattr(jax.profiler, "stop_trace", lambda: None)
        monkeypatch.setattr(jax.profiler, failing, boom)
        spec = _obs_spec("quickstart", tmp_path, "run.rounds=2",
                         f"run.obs.profile_dir={tmp_path / 'trace'}")
        with pytest.raises(RuntimeError, match=f"{failing} refused"):
            run(spec, verbose=False)
        [stream] = tmp_path.glob("*.jsonl")
        end = read_events(stream)[-1]
        assert isinstance(end, RunEnd) and end.status == "error"


class TestRunStreamIntegrity:
    """The acceptance contract: stream == artifact, bit-equal, and obs
    must not perturb the run."""

    @pytest.mark.parametrize("fixture", ["paper_obs", "mesh_obs"])
    def test_round_events_bit_equal_to_artifact(self, fixture, request):
        res, evs = request.getfixturevalue(fixture)
        art = json.loads(json.dumps(res.to_dict()))   # the saved form
        rounds = [e for e in evs if isinstance(e, RoundEvent)]
        assert [e.round for e in rounds] == [0, 1, 2]
        hist = art["metrics"]
        # per-round histories are the length-`rounds` lists; the rest of
        # the artifact is post-run summary scalars (final_acc, totals...)
        per_round = {k for k, v in hist.items()
                     if isinstance(v, list) and len(v) == len(rounds)}
        assert per_round == set(rounds[0].metrics)
        for ev in rounds:
            for k, v in ev.metrics.items():
                if k.endswith("_time_s"):
                    continue  # wall-clock, not part of the contract
                assert hist[k][ev.round] == v, (ev.round, k)

    @pytest.mark.parametrize("fixture", ["paper_obs", "mesh_obs"])
    def test_stream_shape_and_stage_coverage(self, fixture, request):
        res, evs = request.getfixturevalue(fixture)
        assert isinstance(evs[0], RunStart)
        assert isinstance(evs[-1], RunEnd)
        assert evs[-1].status == "ok" and evs[-1].rounds == 3
        assert evs[0].rounds == 3 and evs[0].n_params > 0
        assert evs[0].spec == json.loads(json.dumps(to_dict(res.spec)))
        traced = {e.stage for e in evs
                  if isinstance(e, StageEvent) and e.phase == "trace"}
        assert PIPELINE_STAGES <= traced
        host = {e.stage for e in evs
                if isinstance(e, StageEvent) and e.phase == "host"}
        assert "Step" in host
        assert all(e.run_id == evs[0].run_id for e in evs)
        assert [e.t_s for e in evs] == sorted(e.t_s for e in evs)

    def test_obs_does_not_perturb_metrics(self, paper_obs, tmp_path):
        res_on, _ = paper_obs
        spec_off = override(res_on.spec, "run.obs.enabled=false")
        res_off = run(spec_off, verbose=False)
        on, off = res_on.record, res_off.record
        assert set(on) == set(off)
        for k in on:
            if k.endswith("_time_s"):
                continue
            assert on[k] == off[k], k

    def test_csv_mirror_matches_stream(self, paper_obs):
        res, evs = paper_obs
        csv_path = Path(res.events_path).with_suffix(".csv")
        lines = csv_path.read_text().strip().splitlines()
        rounds = [e for e in evs if isinstance(e, RoundEvent)]
        assert len(lines) == 1 + len(rounds)
        assert lines[0].split(",")[:3] == ["run_id", "round", "t_s"]
        assert set(lines[0].split(",")[3:]) == set(rounds[0].metrics)


class TestArtifactSchema:
    def test_saved_artifact_declares_schema(self, paper_obs, tmp_path):
        res, _ = paper_obs
        d = res.to_dict()
        assert d["schema"] == SCHEMA_VERSION == 2
        assert d["events"] == res.events_path
        p = tmp_path / "r.json"
        p.write_text(json.dumps(d))
        assert load_result(p)["metrics"] == d["metrics"]

    def test_loader_defaults_missing_schema_to_v1(self, tmp_path):
        p = tmp_path / "v1.json"
        p.write_text(json.dumps({"spec": {}, "metrics": {"acc": [0.1]}}))
        loaded = load_result(p)
        assert loaded["schema"] == 1

    def test_loader_fails_loudly_on_unknown_schema(self, tmp_path):
        p = tmp_path / "v9.json"
        p.write_text(json.dumps({"schema": 9, "spec": {}, "metrics": {}}))
        with pytest.raises(ValueError, match="schema"):
            load_result(p)

    def test_loader_rejects_non_artifact(self, tmp_path):
        p = tmp_path / "bad.json"
        p.write_text(json.dumps({"schema": 2, "hello": "world"}))
        with pytest.raises(ValueError):
            load_result(p)


class TestMonitor:
    def test_render_finished_run(self, paper_obs):
        res, evs = paper_obs
        out = obs_monitor.render(evs)
        assert "quickstart" in out
        assert "rounds 3/3" in out
        for stage in PIPELINE_STAGES:
            assert stage in out
        assert "end: status=ok" in out

    def test_render_empty_stream(self):
        assert "no run_start" in obs_monitor.render([])

    def test_resolve_stream_picks_newest_in_dir(self, tmp_path):
        old, new = tmp_path / "a.jsonl", tmp_path / "b.jsonl"
        old.write_text("")
        new.write_text("")
        import os
        os.utime(old, (1, 1))
        assert obs_monitor.resolve_stream(tmp_path) == new
        assert obs_monitor.resolve_stream(new) == new

    def test_main_renders_non_follow(self, paper_obs, capsys):
        res, _ = paper_obs
        obs_monitor.main([res.events_path])
        out = capsys.readouterr().out
        assert "quickstart" in out and "rounds 3/3" in out


class TestSweepObs:
    def test_sweep_stderr_reports_wall_and_events(self, tmp_path, capsys):
        spec = _obs_spec("quickstart", tmp_path / "obs", "run.rounds=1")
        results = sweep([spec], seeds=(0,), out_dir=tmp_path / "art")
        err = capsys.readouterr().err
        assert "[sweep] quickstart s0:" in err
        assert "wall=" in err
        assert "events=" in err
        # sweep-level stream: one SweepEvent per cell + a run_end
        streams = [p for p in (tmp_path / "obs").glob("*.jsonl")
                   if "sweep__" in p.name]
        assert len(streams) == 1
        evs = read_events(streams[0])
        cells = [e for e in evs if isinstance(e, SweepEvent)]
        assert len(cells) == 1 and cells[0].cell == "quickstart"
        assert cells[0].status == "ok" and cells[0].wall_s > 0
        assert cells[0].events == results[0].events_path
        assert isinstance(evs[-1], RunEnd)
        assert "cells (1):" in obs_monitor.render(evs)

    def test_sweep_obs_off_emits_no_streams(self, tmp_path, capsys):
        spec = override(get_scenario("quickstart"), *TINY_PAPER,
                        "run.rounds=1")
        sweep([spec], seeds=(0,), out_dir=tmp_path / "art")
        err = capsys.readouterr().err
        assert "[sweep] quickstart s0:" in err and "wall=" in err
        assert "events=" not in err


class TestSpans:
    """The one span primitive: names, nesting, the recorder, the obs
    emitter and the profiler trace."""

    def test_recorder_keeps_names_parents_and_order(self):
        with recording() as rec:
            with span("setup.data") as outer:
                with span("setup.eta") as inner:
                    pass
            with span("round.key"):
                pass
        assert [(s.name, s.parent) for s in rec] == [
            ("setup.eta", "setup.data"), ("setup.data", None),
            ("round.key", None)]
        assert outer.start <= inner.start <= inner.end <= outer.end
        assert rec[2].start >= outer.end
        assert outer.dur_s == outer.end - outer.start >= 0.0

    def test_recorder_sees_only_its_own_block(self):
        with span("before"):
            pass
        with recording() as rec:
            pass
        with span("after"):
            pass
        assert rec == [] and obs_trace._RECORDER is None

    def test_span_closes_on_error(self):
        with recording() as rec:
            with pytest.raises(RuntimeError):
                with span("round.dispatch"):
                    raise RuntimeError("boom")
        assert [s.name for s in rec] == ["round.dispatch"]
        assert obs_trace._OPEN == []

    def test_host_span_emits_stage_event_that_round_trips(self):
        ring = RingBufferSink()
        em = Emitter("rid", ring)
        with obs_trace.activated(StageTracer(em, stages=False)):
            with span("Step", round_idx=4):
                with span("round.dispatch") as sp:
                    pass
        inner, outer = ring.events
        assert (inner.stage, inner.phase, inner.parent, inner.round) == (
            "round.dispatch", "host", "Step", None)
        assert (outer.stage, outer.parent, outer.round) == ("Step", None, 4)
        assert inner.dur_s == pytest.approx(sp.dur_s)
        assert inner.start_s == pytest.approx(em.clock.at(sp.start))
        assert 0.0 <= outer.start_s <= inner.start_s <= inner.t_s
        for ev in ring.events:
            assert parse_line(ev.to_json()) == ev

    def test_tracer_without_stages_leaves_stage_spans_silent(self):
        ring = RingBufferSink()
        with obs_trace.activated(StageTracer(Emitter("rid", ring),
                                             stages=False)):
            with obs_trace.stage_span("Uplink"):
                pass
        assert ring.events == []

    def test_span_lands_in_profiler_trace(self, tmp_path):
        """The span is a TraceAnnotation named repro.<name>, on the
        trace that holds the device ops."""
        import gzip

        import jax
        import numpy as np
        f = jax.jit(lambda x: x * 2.0)
        x = np.ones(8, np.float32)
        f(x).block_until_ready()
        with jax.profiler.trace(str(tmp_path), create_perfetto_trace=True):
            with span("round.dispatch"):
                f(x).block_until_ready()
        [path] = tmp_path.glob("**/perfetto_trace.json.gz")
        with gzip.open(path, "rt") as fh:
            names = {e.get("name") for e in json.load(fh)["traceEvents"]}
        assert "repro.round.dispatch" in names


class TestCompileCounters:
    def test_fresh_jit_counts_one_compile(self):
        import jax
        import numpy as np
        x = np.arange(5, dtype=np.float32)
        f = jax.jit(lambda v: v * 3.0 + 1.0)
        before = COUNTERS.snapshot()
        f(x).block_until_ready()
        first = COUNTERS.since(before)
        assert first["backend_compiles"] == 1 and first["lowers"] == 1
        assert first["traces"] >= 1
        assert first["compile_s"] > 0.0
        assert first["compile_s"] <= (first["trace_s"] + first["lower_s"]
                                      + first["backend_compile_s"]) + 1e-6
        again = COUNTERS.snapshot()
        f(x).block_until_ready()
        assert COUNTERS.since(again)["backend_compiles"] == 0

    def test_nested_trace_seconds_are_not_summed_twice(self):
        from repro.obs.counters import _Union
        u = _Union()
        u.add(2.0, 3.0)          # an inner jit traced inside...
        u.add(4.0, 4.5)          # ...two of them...
        u.add(1.0, 6.0)          # ...the outer one, which ends last
        u.add(7.0, 8.0)          # then a later, separate trace
        assert u.seconds == pytest.approx(6.0)

    def test_second_process_hits_the_persistent_cache(self, tmp_path):
        """JAX's persistent cache works on the CPU backend here: the
        first process writes the executable (a miss), the second reads
        it back (a hit)."""
        import os
        import subprocess
        import sys
        code = (
            "import json, jax, numpy as np\n"
            "jax.config.update('jax_persistent_cache_min_compile_time_secs',"
            " 0.0)\n"
            "from repro.obs.counters import COUNTERS\n"
            "b = COUNTERS.snapshot()\n"
            "jax.jit(lambda v: v * 3.0 + 1.0)(np.ones(7, np.float32))"
            ".block_until_ready()\n"
            "print(json.dumps(COUNTERS.since(b)))\n")
        env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=str(tmp_path))
        src = str(Path(__file__).resolve().parents[1] / "src")
        env["PYTHONPATH"] = os.pathsep.join(
            p for p in (src, env.get("PYTHONPATH")) if p)
        runs = [json.loads(subprocess.run(
            [sys.executable, "-c", code], env=env, capture_output=True,
            text=True, check=True, timeout=120).stdout.splitlines()[-1])
            for _ in range(2)]
        assert runs[0]["cache_misses"] == 1 and runs[0]["cache_hits"] == 0
        assert runs[1]["cache_hits"] == 1 and runs[1]["cache_misses"] == 0
        assert runs[1]["cache_retrieval_s"] > 0.0


class TestRunSpans:
    """The runner's spans on an obs stream."""

    @pytest.mark.parametrize("fixture,key", [("paper_obs", "round_time_s"),
                                             ("mesh_obs", "step_time_s")])
    def test_round_time_is_the_round_span(self, fixture, key, request):
        res, evs = request.getfixturevalue(fixture)
        rounds = {e.round: e.dur_s for e in evs
                  if isinstance(e, StageEvent) and e.stage == "round"}
        assert sorted(rounds) == [0, 1, 2]
        assert res.record[key] == [rounds[t] for t in range(3)]

    @pytest.mark.parametrize("fixture,setup", [
        ("paper_obs", ["setup.data", "setup.eta", "setup.init"]),
        ("mesh_obs", ["setup.init"])])
    def test_setup_and_round_spans_on_the_stream(self, fixture, setup,
                                                 request):
        _, evs = request.getfixturevalue(fixture)
        host = [e for e in evs
                if isinstance(e, StageEvent) and e.phase == "host"]
        # set-up follows run_start, in the order it ran
        assert [e.stage for e in evs[1:1 + len(setup)]] == setup
        starts = [e.start_s for e in evs[1:1 + len(setup)]]
        assert starts == sorted(starts) and starts[0] >= 0.0
        parents = {(e.stage, e.parent) for e in host}
        assert {("round.key", "Step"), ("round.dispatch", "Step"),
                ("Step", "round"), ("round", None)} <= parents
        # the jitted stages are traced inside the first dispatch
        traced = {e.parent for e in evs
                  if isinstance(e, StageEvent) and e.phase == "trace"}
        assert traced == {"round.dispatch"}

    def test_run_end_carries_compile_counters(self, paper_obs):
        _, evs = paper_obs
        totals = evs[-1].totals
        assert totals["backend_compiles"] >= 1 and totals["compile_s"] > 0
        for k in ("trace_s", "lower_s", "backend_compile_s", "cache_hits",
                  "cache_misses", "cache_retrieval_s"):
            assert k in totals

    def test_population_step_spans(self):
        from repro.experiments import build
        spec = override(get_scenario("quickstart"), *TINY_PAPER,
                        "fleet.population=16", "fleet.cohort_size=4")
        prep = build(spec)
        with recording() as rec:
            prep.step(prep.state, prep.key)
        assert [(s.name, s.parent) for s in rec] == [
            ("round.schedule", None), ("round.reseat", None),
            ("round.key", None), ("round.dispatch", None),
            ("round.scatter", None)]
