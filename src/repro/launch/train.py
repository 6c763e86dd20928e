"""Training driver — a thin CLI over `repro.experiments`.

The scenario registry is the front door:

  python -m repro.launch.train --list-scenarios
  python -m repro.launch.train --scenario paper/fig3-noniid1 \\
      --set run.rounds=2 --set data.num_workers=8
  python -m repro.launch.train --scenario mesh/smollm-smoke --steps 3

Legacy flags still work and are mapped through the same spec (so every
flag combination is expressible — and serializable — as an
`ExperimentSpec`):

  python -m repro.launch.train --mode paper --algorithm mdsl --case noniid2 \\
      --dataset cifar_like --rounds 40
  python -m repro.launch.train --mode paper --byzantine 3 \\
      --aggregator median --downlink-compressor int8
  python -m repro.launch.train --mode mesh --arch smollm-360m --steps 5

Precedence: scenario preset < explicit legacy flags < --set overrides.
The spec is validated at arg-parse time so bad flags fail fast; the
metrics JSON artifact embeds the full spec next to the metrics.

`run_paper_experiment` / `run_mesh_training` remain as deprecated shims
over `experiments.run` — golden-pinned (tests/test_experiments.py) to
emit identical metrics on the default path.
"""
from __future__ import annotations

import argparse
from typing import Optional

from repro.comm import (AGGREGATORS, BYZANTINE_MODES, CHANNELS, COMPRESSORS,
                        FADING_MODELS, TIER_RANKS, CommConfig)
from repro.experiments import (ExperimentSpec, default_out, get_scenario,
                               describe_scenarios, override, run, sweep)
from repro.experiments.runner import (ARTIFACTS, CASES, IMAGE_SPECS,
                                      _noniid2_groups, make_case_data,
                                      spec_from_mesh_kwargs,
                                      spec_from_paper_kwargs)
from repro.experiments.spec import PARTITION_CASES, PAPER_DATASETS
from repro.launch import compile_cache

# legacy alias (pre-registry callers imported the case/spec tables here)
SPECS = IMAGE_SPECS

__all__ = ["ARTIFACTS", "CASES", "SPECS", "run_paper_experiment",
           "run_mesh_training", "make_case_data", "build_spec_from_args",
           "build_sweep_specs", "main", "_noniid2_groups"]


def run_paper_experiment(algorithm: str = "mdsl", case: str = "noniid1",
                         dataset: str = "mnist_like", rounds: int = 20,
                         num_workers: int = 50, model: str = "cnn",
                         width_mult: int = 8, tau: float = 0.9,
                         local_epochs: int = 4, batch_size: int = 64,
                         lr: float = 0.01, velocity_clip: float = 0.1,
                         seed: int = 0, eta_coeffs: Optional[tuple] = None,
                         n_local: int = 512, log_every: int = 1,
                         comm: Optional[CommConfig] = None,
                         verbose: bool = True) -> dict:
    """Deprecated: build an `ExperimentSpec` and call
    `repro.experiments.run` instead. Kept as a golden-pinned shim —
    identical metrics record on every legacy call path."""
    spec = spec_from_paper_kwargs(
        algorithm=algorithm, case=case, dataset=dataset, rounds=rounds,
        num_workers=num_workers, model=model, width_mult=width_mult,
        tau=tau, local_epochs=local_epochs, batch_size=batch_size, lr=lr,
        velocity_clip=velocity_clip, seed=seed, eta_coeffs=eta_coeffs,
        n_local=n_local, log_every=log_every, comm=comm)
    return run(spec, verbose=verbose).record


def run_mesh_training(arch: str, steps: int = 5, reduced: bool = True,
                      seq_len: int = 128, per_worker_batch: int = 2,
                      num_spatial: int = 2, ckpt_dir: Optional[str] = None,
                      seed: int = 0, comm: Optional[CommConfig] = None,
                      verbose: bool = True) -> dict:
    """Deprecated: build an `ExperimentSpec` and call
    `repro.experiments.run` instead (golden-pinned shim)."""
    spec = spec_from_mesh_kwargs(
        arch=arch, steps=steps, reduced=reduced, seq_len=seq_len,
        per_worker_batch=per_worker_batch, num_spatial=num_spatial,
        ckpt_dir=ckpt_dir, seed=seed, comm=comm)
    return run(spec, verbose=verbose).record


# (flag attribute, dotted spec path) — None-defaulted flags are applied
# only when the user passed them, so scenario presets keep their values
_COMMON_FLAGS = [
    ("algorithm", "algo.algorithm"), ("workers", "data.num_workers"),
    ("seed", "run.seed"), ("tau", "algo.tau"), ("out", "run.out"),
    ("compressor", "comm.compressor"), ("topk_ratio", "comm.topk_ratio"),
    ("channel", "comm.channel"), ("drop_prob", "comm.drop_prob"),
    ("snr_db", "comm.snr_db"), ("byzantine", "comm.byzantine"),
    ("byzantine_mode", "comm.byzantine_mode"),
    ("byzantine_scale", "comm.byzantine_scale"),
    ("aggregator", "comm.aggregator"), ("trim_ratio", "comm.trim_ratio"),
    ("downlink_compressor", "comm.downlink_compressor"),
    ("fading", "comm.fading"), ("doppler_rho", "comm.doppler_rho"),
    ("pathloss_spread_db", "comm.pathloss_spread_db"),
    ("outage_snr_db", "comm.outage_snr_db"),
    ("num_tiers", "comm.num_tiers"), ("tier_rank", "comm.tier_rank"),
    ("round_deadline_s", "comm.round_deadline_s"),
    ("staleness_gamma", "comm.staleness_gamma"), ("quorum", "comm.quorum"),
    ("fault_prob", "comm.fault_prob"), ("fault_rounds", "comm.fault_rounds"),
    ("fault_seed", "comm.fault_seed"),
]
_PAPER_FLAGS = [
    ("case", "data.case"), ("dataset", "data.dataset"),
    ("rounds", "run.rounds"), ("model", "model.name"),
    ("width_mult", "model.width_mult"),
]
_MESH_FLAGS = [
    ("arch", "model.name"), ("steps", "run.rounds"),
    ("ckpt_dir", "run.ckpt_dir"),
]


def _obs_overrides(args: argparse.Namespace) -> list[str]:
    """--obs / --obs-dir / --profile-dir -> run.obs.* overrides (any of
    them switches the telemetry bus on). getattr-safe so programmatic
    Namespace callers without the flags keep working."""
    ovr = []
    obs_dir = getattr(args, "obs_dir", None)
    profile_dir = getattr(args, "profile_dir", None)
    if getattr(args, "obs", False) or obs_dir or profile_dir:
        ovr.append("run.obs.enabled=true")
    if obs_dir:
        ovr.append(f"run.obs.dir={obs_dir}")
    if profile_dir:
        ovr.append(f"run.obs.profile_dir={profile_dir}")
    return ovr


def build_spec_from_args(args: argparse.Namespace) -> ExperimentSpec:
    """scenario preset (or mode default) -> legacy flags -> --set."""
    if args.scenario:
        spec = get_scenario(args.scenario)
    elif args.mode == "mesh":
        spec = spec_from_mesh_kwargs(arch=args.arch or "smollm-360m")
    else:
        spec = ExperimentSpec()
    paper = spec.model.kind == "paper"
    # fail fast on explicitly-passed flags the spec kind cannot honor
    # (silently dropping --rounds on a mesh scenario fakes a longer run)
    wrong_kind = [attr for attr, _ in (_MESH_FLAGS if paper
                                       else _PAPER_FLAGS)
                  if getattr(args, attr) is not None]
    if wrong_kind:
        names = ", ".join("--" + a.replace("_", "-") for a in wrong_kind)
        raise ValueError(
            f"{names} does not apply to a {spec.model.kind!r} spec "
            f"({'use --steps/--arch' if not paper else 'use --rounds'} "
            f"or a --set override instead)")
    for attr, path in _COMMON_FLAGS + (_PAPER_FLAGS if paper
                                       else _MESH_FLAGS):
        v = getattr(args, attr)
        if v is not None:
            spec = override(spec, f"{path}={v}")
    if args.no_error_feedback:
        spec = override(spec, "comm.error_feedback=false")
    if args.adaptive_bits:
        spec = override(spec, "comm.adaptive_bits=true")
    for assignment in _obs_overrides(args):
        spec = override(spec, assignment)
    for assignment in args.overrides:
        spec = override(spec, assignment)
    return spec.validate()


def build_sweep_specs(args: argparse.Namespace) -> list[ExperimentSpec]:
    """--sweep grid: scenario presets x --sweep-axis value lists, with
    any --set overrides applied to every cell. The full paper grid is
    one command:

        python -m repro.launch.train --sweep \\
            paper/fig3-iid,paper/fig3-noniid1,paper/fig3-noniid2 \\
            --sweep-axis algo.algorithm=fedavg,dsl,multi_dsl,mdsl \\
            --seeds 0,1,2,3,4 --jobs 8
    """
    names = [n.strip() for n in args.sweep.split(",") if n.strip()]
    if not names:
        raise ValueError("--sweep needs at least one scenario name")
    specs = [get_scenario(n) for n in names]
    for assignment in args.overrides:
        specs = [override(s, assignment) for s in specs]
    for axis in args.sweep_axis:
        path, eq, raw = axis.partition("=")
        values = [v.strip() for v in raw.split(",") if v.strip()]
        if not eq or not values:
            raise ValueError(f"--sweep-axis must look like "
                             f"key=v1,v2,..., got {axis!r}")
        specs = [override(s, f"{path}={v}") for s in specs for v in values]
    for assignment in _obs_overrides(args):
        specs = [override(s, assignment) for s in specs]
    return [s.validate() for s in specs]


def main() -> None:
    ap = argparse.ArgumentParser(
        description="Run one experiment: --scenario NAME [--set k=v ...], "
                    "or the legacy per-axis flags.")
    ap.add_argument("--scenario", default=None,
                    help="named preset from repro.experiments.registry")
    ap.add_argument("--set", dest="overrides", action="append", default=[],
                    metavar="KEY=VALUE",
                    help="dotted spec override, e.g. comm.compressor=topk "
                         "(repeatable)")
    ap.add_argument("--list-scenarios", action="store_true")
    ap.add_argument("--mode", default="paper", choices=["paper", "mesh"],
                    help="default spec kind when no --scenario is given")
    # paper mode
    ap.add_argument("--algorithm", default=None,
                    choices=["fedavg", "dsl", "multi_dsl", "mdsl"])
    ap.add_argument("--case", default=None, choices=list(PARTITION_CASES))
    ap.add_argument("--dataset", default=None, choices=list(PAPER_DATASETS))
    ap.add_argument("--rounds", type=int, default=None)
    ap.add_argument("--workers", type=int, default=None)
    ap.add_argument("--model", default=None, choices=["cnn", "resnet"])
    ap.add_argument("--width-mult", type=int, default=None)
    ap.add_argument("--tau", type=float, default=None)
    ap.add_argument("--seed", type=int, default=None)
    ap.add_argument("--out", default=None)
    # comm (both modes)
    ap.add_argument("--compressor", default=None, choices=list(COMPRESSORS))
    ap.add_argument("--topk-ratio", type=float, default=None)
    ap.add_argument("--no-error-feedback", action="store_true")
    ap.add_argument("--channel", default=None, choices=list(CHANNELS))
    ap.add_argument("--drop-prob", type=float, default=None)
    ap.add_argument("--snr-db", type=float, default=None)
    ap.add_argument("--byzantine", type=int, default=None)
    ap.add_argument("--byzantine-mode", default=None,
                    choices=list(BYZANTINE_MODES))
    ap.add_argument("--byzantine-scale", type=float, default=None)
    ap.add_argument("--aggregator", default=None, choices=list(AGGREGATORS))
    ap.add_argument("--trim-ratio", type=float, default=None)
    ap.add_argument("--downlink-compressor", default=None,
                    choices=list(COMPRESSORS))
    ap.add_argument("--adaptive-bits", action="store_true")
    # physical layer (comm.phy)
    ap.add_argument("--fading", default=None, choices=list(FADING_MODELS))
    ap.add_argument("--doppler-rho", type=float, default=None)
    ap.add_argument("--pathloss-spread-db", type=float, default=None)
    ap.add_argument("--outage-snr-db", type=float, default=None)
    ap.add_argument("--num-tiers", type=int, default=None)
    ap.add_argument("--tier-rank", default=None, choices=list(TIER_RANKS))
    # straggler / deadline engine + fault injection (comm.straggler)
    ap.add_argument("--round-deadline-s", type=float, default=None)
    ap.add_argument("--staleness-gamma", type=float, default=None)
    ap.add_argument("--quorum", type=int, default=None)
    ap.add_argument("--fault-prob", type=float, default=None)
    ap.add_argument("--fault-rounds", type=int, default=None)
    ap.add_argument("--fault-seed", type=int, default=None)
    # mesh mode
    ap.add_argument("--arch", default=None)
    ap.add_argument("--steps", type=int, default=None)
    ap.add_argument("--ckpt-dir", default=None)
    # observability (repro.obs; any of these enables the event stream)
    ap.add_argument("--obs", action="store_true",
                    help="stream typed telemetry events to a JSONL file "
                         "under artifacts/obs/ (tail it with "
                         "python -m repro.launch.monitor --follow)")
    ap.add_argument("--obs-dir", default=None,
                    help="event stream directory (implies --obs)")
    ap.add_argument("--profile-dir", default=None,
                    help="capture a jax.profiler trace for a window of "
                         "rounds into this dir (implies --obs; load in "
                         "TensorBoard)")
    # sweep mode: --sweep S1,S2 [--sweep-axis k=v1,v2]... [--seeds ..]
    ap.add_argument("--sweep", default=None, metavar="SCENARIOS",
                    help="comma-separated scenario names to sweep "
                         "(each crossed with --sweep-axis values, "
                         "--seeds, and any --set overrides)")
    ap.add_argument("--sweep-axis", action="append", default=[],
                    metavar="KEY=V1,V2,...",
                    help="cross-product axis over a dotted spec path, "
                         "e.g. algo.algorithm=fedavg,dsl,multi_dsl,mdsl "
                         "(repeatable)")
    ap.add_argument("--seeds", default=None,
                    help="comma-separated seeds for --sweep (default 0)")
    ap.add_argument("--jobs", type=int, default=1,
                    help="process-pool fan-out for --sweep (1 = serial)")
    args = ap.parse_args()

    if args.list_scenarios:
        width = max(len(n) for n, _ in describe_scenarios())
        for name, what in describe_scenarios():
            print(f"{name.ljust(width)}  {what}")
        return

    if args.sweep:
        # same fail-fast contract as single runs: a per-axis flag that
        # --sweep would silently drop fakes results for a config the
        # user never ran — demand the --set / --sweep-axis spelling
        stray = [attr for attr, _ in
                 _COMMON_FLAGS + _PAPER_FLAGS + _MESH_FLAGS
                 if getattr(args, attr) is not None]
        stray += [f for f in ("no_error_feedback", "adaptive_bits")
                  if getattr(args, f)]
        if stray:
            names = ", ".join("--" + a.replace("_", "-") for a in stray)
            ap.error(f"{names} does not combine with --sweep — spell "
                     f"shared values as --set key=value and swept values "
                     f"as --sweep-axis key=v1,v2")
        try:
            specs = build_sweep_specs(args)
            seeds = ([int(s) for s in args.seeds.split(",") if s.strip()]
                     if args.seeds else [0])
        except ValueError as e:
            ap.error(str(e))
        results = sweep(specs, seeds=seeds, jobs=args.jobs)
        print(f"swept {len(results)} runs "
              f"({len(specs)} specs x {len(seeds)} seeds, "
              f"jobs={args.jobs})")
        return

    try:
        # fail fast at the CLI, not deep inside the first jitted round
        spec = build_spec_from_args(args)
    except ValueError as e:
        ap.error(str(e))

    result = run(spec)
    out = default_out(spec)
    result.save(out)
    print(f"wrote {out}")
    if result.events_path:
        print(f"events {result.events_path}\n"
              f"  view: python -m repro.launch.monitor "
              f"{result.events_path}")


if __name__ == "__main__":
    compile_cache.enable()
    main()
