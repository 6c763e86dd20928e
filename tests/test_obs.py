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
        assert obs_trace.current() is None
        assert obs_trace.stage_span("Uplink") is obs_trace._NOOP
        assert obs_trace.stage_span("Downlink") is obs_trace._NOOP

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
        with NULL.span("Step"):
            with NULL.span("Step"):   # nullcontext must be reentrant
                pass
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
