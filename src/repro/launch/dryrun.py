import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=512").strip()

"""Multi-pod dry-run: AOT lower + compile every (arch x shape x mesh)
combination on placeholder devices, record memory/cost/collective
analysis as JSON artifacts (artifacts/dryrun/<arch>__<shape>__<mesh>.json).

The XLA_FLAGS line above MUST run before any other import (jax locks the
device count at first init) — which is why it is the first statement of
this module and why this flag is never set globally (smoke tests and
benchmarks see 1 device).

Usage:
  python -m repro.launch.dryrun --arch smollm-360m --shape train_4k --mesh single
  python -m repro.launch.dryrun --all [--mesh both] [--skip-done]
  python -m repro.launch.dryrun --arch smollm-360m --shape train_4k \
      --scenario rayleigh-uplink   # CommConfig from the registry
"""
import argparse
import gzip
import json
import time
import traceback
from pathlib import Path

import jax

from repro.configs.base import INPUT_SHAPES, get_arch, list_archs
from repro.launch import hlo_analysis, hlo_costmodel
from repro.launch.mesh import make_production_mesh
from repro.launch.steps import build_step

ARTIFACT_DIR = Path(__file__).resolve().parents[3] / "artifacts" / "dryrun"


def model_flops_per_device(cfg, shape, n_dev: int) -> float:
    """Analytic MODEL_FLOPS (6*N_active*D train / 2*N_active*D fwd) for
    the tokens this step processes, per device."""
    tok = shape.global_batch * (shape.seq_len
                                if shape.kind != "decode" else 1)
    mult = 3 if shape.kind == "train" else 1  # fwd+bwd vs fwd
    if shape.kind == "train":
        tok += 16 * 4 * shape.seq_len  # W * EVAL_BATCH scoring fwd (approx)
    return 2 * cfg.active_param_count() * tok * mult / n_dev


def analyze_hlo(hlo: str, cfg, shape, n_dev: int) -> dict:
    """While-multiplicity-aware roofline record from the HLO text
    (hlo_costmodel corrects cost_analysis()'s scan-body undercount)."""
    cm = hlo_costmodel.analyze(hlo)
    mf = model_flops_per_device(cfg, shape, n_dev)
    return {
        "flops_per_device": cm["flops"],
        "hbm_bytes_per_device": cm["hbm_bytes"],
        "collectives": cm["collectives"],
        "max_while_trip": cm["max_while_trip"],
        "roofline": hlo_analysis.roofline(
            cm["flops"], cm["hbm_bytes"],
            cm["collectives"]["total_bytes"], mf, fma_counted=False),
    }


def pair_is_applicable(arch_name: str, shape_name: str) -> tuple[bool, str]:
    cfg = get_arch(arch_name)
    if shape_name == "long_500k" and not cfg.subquadratic:
        return False, ("full-attention arch: long_500k skipped per brief "
                       "(DESIGN.md §4)")
    return True, ""


def run_one(arch_name: str, shape_name: str, mesh_kind: str,
            algorithm: str = "mdsl", save_hlo: bool = True,
            tag: str = "", comm=None) -> dict:
    """`comm` (a repro.comm.CommConfig, default wire when None) threads
    compression/robust-aggregation/downlink configs into the lowered
    step, so comm scenarios cost out on the 512-device model."""
    cfg = get_arch(arch_name)
    shape = INPUT_SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    rec = {"arch": arch_name, "shape": shape_name, "mesh": mesh_kind,
           "algorithm": algorithm, "devices": int(
               len(jax.devices())), "ok": False, "tag": tag}
    if comm is not None:
        rec["comm"] = comm._asdict()
    t0 = time.time()
    try:
        with jax.set_mesh(mesh):
            built = build_step(cfg, shape, mesh, algorithm=algorithm,
                               comm=comm)
            lowered = built.fn.lower(*built.args)
            t_lower = time.time() - t0
            compiled = lowered.compile()
            t_compile = time.time() - t0 - t_lower

            mem = compiled.memory_analysis()
            cost = compiled.cost_analysis()
            hlo = compiled.as_text()
            n_dev = len(jax.devices())

            rec.update(
                ok=True,
                lower_s=round(t_lower, 1), compile_s=round(t_compile, 1),
                memory={
                    "argument_bytes": getattr(mem, "argument_size_in_bytes", 0),
                    "output_bytes": getattr(mem, "output_size_in_bytes", 0),
                    "temp_bytes": getattr(mem, "temp_size_in_bytes", 0),
                    "generated_code_bytes": getattr(
                        mem, "generated_code_size_in_bytes", 0),
                },
                # raw XLA numbers (while/scan bodies counted ONCE — see
                # hlo_costmodel; kept for reference only)
                xla_cost={
                    "flops": float(cost.get("flops", 0.0)) if cost else 0.0,
                    "bytes_accessed": float(cost.get("bytes accessed", 0.0))
                    if cost else 0.0,
                },
                **analyze_hlo(hlo, built.cfg, shape, n_dev),
                meta={k: (list(v) if isinstance(v, tuple) else v)
                      for k, v in built.meta.items()},
            )
            if save_hlo:
                ARTIFACT_DIR.mkdir(parents=True, exist_ok=True)
                hpath = ARTIFACT_DIR / f"{arch_name}__{shape_name}__{mesh_kind}{tag}.hlo.gz"
                with gzip.open(hpath, "wt") as f:
                    f.write(hlo)
                rec["hlo_path"] = str(hpath)
    except Exception as e:  # noqa: BLE001 — record the failure, keep sweeping
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-3000:]
    rec["total_s"] = round(time.time() - t0, 1)
    return rec


def artifact_path(arch: str, shape: str, mesh_kind: str, tag: str = "") -> Path:
    return ARTIFACT_DIR / f"{arch}__{shape}__{mesh_kind}{tag}.json"


def reanalyze_all(tag: str = "") -> None:
    """Recompute the roofline record of every artifact from its saved
    .hlo.gz (no recompilation) — used after cost-model improvements."""
    n_dev_by_mesh = {"single": 256, "multi": 512}
    for jpath in sorted(ARTIFACT_DIR.glob(f"*{tag}.json")):
        rec = json.loads(jpath.read_text())
        if not rec.get("ok"):
            continue
        hpath = Path(str(jpath)[: -len(".json")] + ".hlo.gz")
        if not hpath.exists():
            print(f"no HLO for {jpath.name}, skipping")
            continue
        with gzip.open(hpath, "rt") as f:
            hlo = f.read()
        cfg = get_arch(rec["arch"])
        shape = INPUT_SHAPES[rec["shape"]]
        rec.update(analyze_hlo(hlo, cfg, shape,
                               n_dev_by_mesh[rec["mesh"]]))
        jpath.write_text(json.dumps(rec, indent=1))
        print(f"reanalyzed {jpath.name}: "
              f"dominant={rec['roofline']['dominant']} "
              f"useful={rec['roofline']['useful_flops_ratio']:.2f}")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="single", choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--algorithm", default="mdsl")
    ap.add_argument("--skip-done", action="store_true")
    ap.add_argument("--tag", default="", help="artifact suffix for perf variants")
    ap.add_argument("--scenario", default=None,
                    help="resolve the CommConfig from this registry "
                         "scenario (one flag surface for comm pricing — "
                         "fading/outage/tier scenarios included)")
    ap.add_argument("--reanalyze", action="store_true",
                    help="recompute rooflines from saved HLO (no compile)")
    args = ap.parse_args()

    if args.reanalyze:
        reanalyze_all(args.tag)
        return

    comm = None
    if args.scenario:
        from repro.experiments.registry import get_scenario
        comm = get_scenario(args.scenario).comm
        if not args.tag:
            args.tag = "__" + args.scenario.replace("/", "-")

    archs = ([a for a in list_archs()] if args.all or not args.arch
             else [args.arch])
    shapes = (list(INPUT_SHAPES) if args.all or not args.shape
              else [args.shape])
    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]

    ARTIFACT_DIR.mkdir(parents=True, exist_ok=True)
    for mesh_kind in meshes:
        for arch in archs:
            for shape in shapes:
                ok, why = pair_is_applicable(arch, shape)
                path = artifact_path(arch, shape, mesh_kind, args.tag)
                if not ok:
                    rec = {"arch": arch, "shape": shape, "mesh": mesh_kind,
                           "skipped": True, "reason": why}
                    path.write_text(json.dumps(rec, indent=1))
                    print(f"SKIP {arch} {shape} {mesh_kind}: {why}")
                    continue
                if args.skip_done and path.exists():
                    prev = json.loads(path.read_text())
                    if prev.get("ok") or prev.get("skipped"):
                        print(f"DONE {arch} {shape} {mesh_kind} (cached)")
                        continue
                print(f"RUN  {arch} {shape} {mesh_kind} ...", flush=True)
                rec = run_one(arch, shape, mesh_kind, algorithm=args.algorithm,
                              tag=args.tag, comm=comm)
                path.write_text(json.dumps(rec, indent=1))
                status = "ok" if rec.get("ok") else f"FAIL {rec.get('error')}"
                print(f"     -> {status} ({rec['total_s']}s)", flush=True)


if __name__ == "__main__":
    main()
