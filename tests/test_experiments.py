"""repro.experiments: spec round-trip, registry completeness, override
parsing, and the golden-pinned legacy shims.

The golden file (tests/golden/paper_default_mdsl.json) was captured
from `run_paper_experiment` *before* the runner refactor (commit
51e0a69's code) at a small deterministic config; the shim must keep
emitting identical metrics (modulo timing) on the default path.
"""
import json
from pathlib import Path

import hypothesis as hp
import hypothesis.strategies as st
import pytest

from repro.experiments import (ExperimentSpec, build, from_dict,
                               get_scenario, list_scenarios, override,
                               run, sweep, to_dict)

GOLDEN = Path(__file__).parent / "golden" / "paper_default_mdsl.json"

# shrink overrides so registry specs build/run in test time
TINY_PAPER = ("data.num_workers=4", "data.n_local=64", "run.rounds=1",
              "model.width_mult=2", "algo.local_epochs=1")
TINY_MESH = ("data.num_workers=2", "model.seq_len=16",
             "model.per_worker_batch=1", "run.rounds=1")


def tiny(spec: ExperimentSpec) -> ExperimentSpec:
    ovr = TINY_PAPER if spec.model.kind == "paper" else TINY_MESH
    spec = override(spec, *ovr)
    # keep byzantine fleets consistent with the shrunk worker count
    # (validate() bounds byzantine and floor(trim_ratio*K) against the
    # shrunk per-round cohort K=4)
    if spec.comm.byzantine:
        spec = override(spec, "comm.byzantine=1")
        if spec.comm.aggregator == "trimmed_mean":
            spec = override(spec, "comm.trim_ratio=0.3")
    # shrink fleet presets with the cohort: P=64 registered, K=4 active
    if spec.fleet.population:
        spec = override(spec, "fleet.population=64", "fleet.cohort_size=4")
    # clamp quorum with the cohort (validate() rejects quorum > K)
    if spec.comm.quorum and spec.comm.quorum > spec.data.num_workers:
        spec = override(spec, f"comm.quorum={spec.data.num_workers}")
    return spec


class TestSpecRoundTrip:
    @pytest.mark.parametrize("name", list_scenarios())
    def test_json_round_trip(self, name):
        spec = get_scenario(name)
        wire = json.loads(json.dumps(to_dict(spec)))
        assert from_dict(wire) == spec

    def test_round_trip_preserves_tuples(self):
        spec = override(ExperimentSpec(), "data.eta_coeffs=0.1,0.2,0.3")
        back = from_dict(json.loads(json.dumps(to_dict(spec))))
        assert back.data.eta_coeffs == (0.1, 0.2, 0.3)
        assert back == spec

    def test_unknown_field_rejected(self):
        d = to_dict(ExperimentSpec())
        d["data"]["num_gpus"] = 8
        with pytest.raises(ValueError, match="num_gpus"):
            from_dict(d)

    @hp.given(st.sampled_from(list_scenarios()),
              st.integers(min_value=0, max_value=999),
              st.sampled_from(["identity", "topk", "int8", "int4"]),
              st.floats(min_value=1e-3, max_value=1.0))
    @hp.settings(max_examples=25, deadline=None)
    def test_round_trip_under_random_overrides(self, name, seed, comp,
                                               ratio):
        spec = override(get_scenario(name), f"run.seed={seed}",
                        f"comm.compressor={comp}",
                        f"comm.topk_ratio={ratio}")
        assert from_dict(json.loads(json.dumps(to_dict(spec)))) == spec


class TestRegistry:
    def test_expected_presets_present(self):
        names = list_scenarios()
        for required in ["paper/fig3-iid", "paper/fig3-noniid1",
                         "paper/fig3-noniid2", "byzantine-median",
                         "low-bandwidth-int4", "lossy-uplink-erasure",
                         "adaptive-tiers", "mesh/smollm-smoke",
                         "quickstart"]:
            assert required in names

    @pytest.mark.parametrize("name", list_scenarios())
    def test_every_preset_validates(self, name):
        spec = get_scenario(name)
        assert spec.validate() is spec
        assert spec.name == name

    def test_unknown_scenario_lists_available(self):
        with pytest.raises(ValueError, match="paper/fig3-noniid1"):
            get_scenario("nope")

    @pytest.mark.parametrize(
        "name", [n for n in list_scenarios() if "mesh" not in n])
    def test_paper_presets_build_runnable_step(self, name):
        prep = build(tiny(get_scenario(name)))
        assert prep.n_params > 0
        state, telemetry, key = prep.step(prep.state, prep.key)
        assert int(telemetry.selected_count) >= 1

    def test_mesh_preset_builds_runnable_step(self):
        prep = build(tiny(get_scenario("mesh/smollm-smoke")))
        assert prep.n_params > 0
        state, info, key = prep.step(prep.state, prep.key)
        assert float(info.global_loss) > 0


class TestOverride:
    def test_type_coercion(self):
        s = override(ExperimentSpec(), "run.rounds=3", "algo.tau=0.5",
                     "comm.adaptive_bits=true", "model.name=resnet",
                     "algo.hp.learning_rate=0.2", "run.out=none")
        assert s.run.rounds == 3 and s.algo.tau == 0.5
        assert s.comm.adaptive_bits is True
        assert s.model.name == "resnet"
        assert s.algo.hp.learning_rate == 0.2
        assert s.run.out is None

    def test_original_spec_unchanged(self):
        base = ExperimentSpec()
        override(base, "run.rounds=99")
        assert base.run.rounds == 20

    @pytest.mark.parametrize("bad", [
        "comm.warp_drive=1",          # unknown leaf
        "nope.rounds=1",              # unknown group
        "run.rounds.deeper=1",        # path through a scalar
        "run.rounds",                 # no assignment
        "run.rounds=three",           # uncoercible int
        "run.rounds=none",            # None into a non-Optional field
        "comm.adaptive_bits=maybe",   # uncoercible bool
        "=5",                         # empty path
    ])
    def test_rejects_bad_overrides(self, bad):
        with pytest.raises(ValueError):
            override(ExperimentSpec(), bad)

    def test_validate_catches_bad_enums(self):
        with pytest.raises(ValueError, match="compressor"):
            override(ExperimentSpec(), "comm.compressor=zip").validate()
        with pytest.raises(ValueError, match="algorithm"):
            override(ExperimentSpec(), "algo.algorithm=sgd").validate()
        with pytest.raises(ValueError, match="rounds"):
            override(ExperimentSpec(), "run.rounds=0").validate()

    def test_alpha_only_valid_on_dirichlet_case(self):
        # alpha shapes only the noniid1 partition; silently ignoring it
        # elsewhere would fake a sweep axis
        override(ExperimentSpec(), "data.alpha=0.1").validate()
        with pytest.raises(ValueError, match="alpha"):
            override(ExperimentSpec(), "data.case=noniid2",
                     "data.alpha=0.1").validate()
        with pytest.raises(ValueError, match="alpha"):
            override(ExperimentSpec(), "data.alpha=-1.0").validate()

    def test_none_allowed_into_optional_fields(self):
        s = override(ExperimentSpec(), "data.alpha=0.5")
        assert override(s, "data.alpha=none").data.alpha is None
        assert override(s, "run.ckpt_dir=none").run.ckpt_dir is None

    def test_validate_rejects_fully_byzantine_fleet(self):
        with pytest.raises(ValueError, match="byzantine"):
            override(ExperimentSpec(), "data.num_workers=3",
                     "comm.byzantine=3").validate()
        with pytest.raises(ValueError, match="byzantine"):
            override(ExperimentSpec(), "comm.byzantine=-1").validate()
        # a minority attack is a legitimate experiment
        override(ExperimentSpec(), "data.num_workers=4",
                 "comm.byzantine=3").validate()


class _Captured(Exception):
    pass


class TestCliMapping:
    def _spec_for(self, monkeypatch, argv):
        import sys

        import repro.launch.train as train
        monkeypatch.setattr(sys, "argv", ["train.py"] + argv)
        seen = {}

        def fake_run(spec, verbose=True):
            seen["spec"] = spec
            raise _Captured

        monkeypatch.setattr(train, "run", fake_run)
        with pytest.raises(_Captured):
            train.main()
        return seen["spec"]

    def test_scenario_plus_set_and_legacy_flag(self, monkeypatch):
        spec = self._spec_for(monkeypatch, [
            "--scenario", "paper/fig3-noniid1", "--set", "run.rounds=2",
            "--rounds", "7", "--compressor", "int8"])
        # --set wins over the legacy flag; comm flag mapped through
        assert spec.run.rounds == 2
        assert spec.comm.compressor == "int8"
        assert spec.data.case == "noniid1"

    def test_pure_legacy_flags_build_a_spec(self, monkeypatch):
        spec = self._spec_for(monkeypatch, [
            "--mode", "paper", "--algorithm", "fedavg", "--case", "noniid2",
            "--rounds", "3", "--workers", "6", "--aggregator", "median",
            "--adaptive-bits"])
        assert spec.algo.algorithm == "fedavg"
        assert spec.data.case == "noniid2" and spec.data.num_workers == 6
        assert spec.run.rounds == 3
        assert spec.comm.aggregator == "median"
        assert spec.comm.adaptive_bits is True

    def test_mesh_mode_maps_arch_and_steps(self, monkeypatch):
        spec = self._spec_for(monkeypatch, [
            "--mode", "mesh", "--arch", "xlstm-350m", "--steps", "2"])
        assert spec.model.kind == "mesh"
        assert spec.model.name == "xlstm-350m"
        assert spec.run.rounds == 2

    def test_algorithm_flag_applies_to_mesh(self, monkeypatch):
        spec = self._spec_for(monkeypatch, [
            "--mode", "mesh", "--algorithm", "fedavg", "--steps", "1"])
        assert spec.algo.algorithm == "fedavg"

    def test_wrong_kind_flags_fail_fast(self, monkeypatch):
        import sys

        import repro.launch.train as train
        # --rounds on a mesh scenario must error, not silently run the
        # preset's step count
        monkeypatch.setattr(sys, "argv", [
            "train.py", "--scenario", "mesh/smollm-smoke",
            "--rounds", "10"])
        with pytest.raises(SystemExit):
            train.main()
        monkeypatch.setattr(sys, "argv", [
            "train.py", "--mode", "paper", "--steps", "3"])
        with pytest.raises(SystemExit):
            train.main()


class TestGoldenShims:
    def test_paper_shim_matches_pre_refactor_golden(self):
        from repro.launch.train import run_paper_experiment
        rec = run_paper_experiment(
            algorithm="mdsl", case="noniid1", dataset="mnist_like",
            rounds=2, num_workers=4, width_mult=2, local_epochs=1,
            n_local=128, verbose=False)
        rec.pop("round_time_s")
        golden = json.loads(GOLDEN.read_text())
        # the record may only grow by the comm.phy telemetry columns;
        # every pre-refactor field must still be present and bit-equal
        phy_fields = {"airtime_s", "energy_j", "mean_snr_db",
                      "total_airtime_s", "total_energy_j"}
        assert set(rec) - set(golden) <= phy_fields
        assert set(golden) <= set(rec)
        rec = json.loads(json.dumps(rec))  # same float serialization
        for k in golden:
            if k == "comm":
                # CommConfig grew the phy axes; the pre-phy wire fields
                # must keep their exact values
                for ck, cv in golden[k].items():
                    assert rec[k][ck] == cv, f"comm.{ck} drifted"
                continue
            assert rec[k] == golden[k], f"field {k!r} drifted"

    def test_mesh_shim_structure(self):
        from repro.launch.train import run_mesh_training
        rec = run_mesh_training("smollm-360m", steps=1, num_spatial=2,
                                seq_len=16, per_worker_batch=1,
                                verbose=False)
        assert rec["steps"] == 1
        assert rec["bytes_up"][0] == rec["selected"][0] * \
            rec["payload_bytes_per_worker"]


class TestRunnerFacade:
    def test_run_embeds_spec_in_result(self, tmp_path):
        spec = tiny(get_scenario("quickstart"))
        res = run(spec, verbose=False)
        assert res.spec == spec
        p = res.save(tmp_path / "r.json")
        saved = json.loads(p.read_text())
        assert from_dict(saved["spec"]) == spec
        assert saved["metrics"]["final_acc"] == res.record["final_acc"]

    def test_sweep_names_artifacts_by_scenario_and_seed(self, tmp_path):
        spec = tiny(get_scenario("quickstart"))
        results = sweep([spec], seeds=(0, 1), out_dir=tmp_path)
        assert len(results) == 2
        files = sorted(p.name for p in tmp_path.glob("*.json"))
        assert files == ["quickstart__s0.json", "quickstart__s1.json"]
        for p in tmp_path.glob("*.json"):
            saved = json.loads(p.read_text())
            assert saved["spec"]["run"]["seed"] in (0, 1)

    def test_parallel_sweep_matches_serial(self, tmp_path):
        """jobs=2 fans the grid over a process pool: same artifacts,
        same grid-order results, identical metrics (runs are seeded)."""
        spec = tiny(get_scenario("quickstart"))
        serial = sweep([spec], seeds=(0, 1), out_dir=tmp_path / "ser")
        par = sweep([spec], seeds=(0, 1), out_dir=tmp_path / "par",
                    jobs=2)
        assert [r.spec for r in par] == [r.spec for r in serial]
        for a, b in zip(par, serial):
            assert a.record["final_acc"] == b.record["final_acc"]
            assert a.record["bytes_up"] == b.record["bytes_up"]
        assert (sorted(p.name for p in (tmp_path / "par").glob("*.json"))
                == sorted(p.name for p in (tmp_path / "ser").glob("*.json")))

    @pytest.mark.parametrize("platforms", ["", "tpu", "cpu,tpu"])
    def test_parallel_sweep_refused_unless_pinned_to_cpu(self, tmp_path,
                                                         platforms):
        """One process per chip: a jobs > 1 pool whose children could
        each open the accelerator is refused before any cell starts."""
        import jax
        prev = jax.config.jax_platforms
        jax.config.update("jax_platforms", platforms)
        try:
            with pytest.raises(ValueError, match="JAX_PLATFORMS=cpu"):
                sweep([tiny(get_scenario("quickstart"))], seeds=(0, 1),
                      out_dir=tmp_path, jobs=2)
        finally:
            jax.config.update("jax_platforms", prev)
        assert not list(tmp_path.glob("*.json"))

    def test_build_sweep_specs_crosses_axes(self):
        """--sweep x --sweep-axis x --set builds the full grid (the
        paper's 4-algo x 3-case grid is one CLI command)."""
        import argparse

        from repro.launch.train import build_sweep_specs
        args = argparse.Namespace(
            sweep="paper/fig3-iid,paper/fig3-noniid1",
            sweep_axis=["algo.algorithm=fedavg,mdsl"],
            overrides=["run.rounds=1"])
        specs = build_sweep_specs(args)
        assert len(specs) == 4
        assert {(s.data.case, s.algo.algorithm) for s in specs} == {
            ("iid", "fedavg"), ("iid", "mdsl"),
            ("noniid1", "fedavg"), ("noniid1", "mdsl")}
        assert all(s.run.rounds == 1 for s in specs)
        with pytest.raises(ValueError):
            build_sweep_specs(argparse.Namespace(
                sweep="paper/fig3-iid", sweep_axis=["algo.algorithm"],
                overrides=[]))

    def test_sweep_cli_rejects_stray_per_axis_flags(self, capsys):
        """--sweep must fail fast on legacy per-axis flags it would
        otherwise silently drop (same contract as single runs)."""
        import sys
        from unittest import mock

        from repro.launch import train
        argv = ["train", "--sweep", "paper/fig3-iid",
                "--channel", "erasure"]
        with mock.patch.object(sys, "argv", argv):
            with pytest.raises(SystemExit):
                train.main()
        assert "--channel" in capsys.readouterr().err
