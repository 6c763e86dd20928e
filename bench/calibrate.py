#!/usr/bin/env python3
"""Readings that the output check's limits are set from (not part of a
benchmark run).

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,3 \
        [--control-seeds 3] [--out FILE]

In one process, for each seed: builds the cell's program, drives its
checked rounds exactly as `run.py` does, frees it, and compares it with
the reference (the lower readings). For the first `--control-seeds`
seeds it then puts the reference itself in the program's place, on the
same starting states and keys: once in the precision below the one the
configuration states (the control), and once with each of the check's
faults planted (`reference.FAULTS`), each compared with the reference
at full precision (the upper readings). Every reading is one JSON line
on stdout; `--out` also writes them all to a file.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

import run as R  # noqa: E402


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True)
    p.add_argument("--control-seeds", type=int, default=3)
    p.add_argument("--out")
    args = p.parse_args(argv)
    ctx = R.load_cell(args.workload)
    rows = calibrate(ctx, [int(s) for s in args.seeds.split(",")],
                     args.control_seeds)
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out).write_text(json.dumps(rows, indent=1))
    return 0


def calibrate(ctx: dict, seeds: list, control_seeds: int, *,
              require_chip: bool = True, extra_overrides=(), cfg=None) -> list:
    R.import_program()
    import jax
    if require_chip:
        R.require_devices(ctx["entry"]["chips"])
    R.enable_compile_cache()
    from repro.experiments.runner import build
    from repro.experiments.spec import to_dict
    from repro.obs import trace as obs_trace
    from repro.obs.events import NULL

    cfg = ctx["cfg"] if cfg is None else cfg
    engine = R.load_module(R.BENCH / "engines" / f"{cfg['engine']}.py",
                           "bench_engine")
    reference = R.load_module(R.BENCH / "reference" / f"{cfg['reference']}.py",
                              "bench_reference")
    import check
    obs_trace.install(obs_trace.StageTracer(NULL))
    band = ctx["cell"]["check"]["limits"]["loss"]
    modes = [("control", {"dtype": cfg["control_dtype"]})] + [
        (f, {"fault": f}) for f in reference.FAULTS]
    rows = []
    for i, seed in enumerate(seeds):
        t = time.perf_counter()
        spec = R.resolve(ctx, seed, extra_overrides)
        spec_dict = to_dict(spec)
        prep = build(spec)
        mismatches = (R.check_spec(spec_dict, cfg) if not extra_overrides
                      else []) + (engine.check_config(prep, cfg)
                                  if not extra_overrides else [])
        for m in mismatches:
            print(f"config mismatch: {m}", file=sys.stderr)
        state, _, rounds_in, prog, _ = R.checked_rounds(
            engine, reference, prep, ctx["cell"]["check"]["rounds"])
        feed = engine.feed(prep)
        jax.block_until_ready(state)
        del prep, state
        gc.collect()
        ref = R.reference_rounds(reference, rounds_in, prog, feed, cfg,
                                 spec_dict, seed, band)
        emit(rows, seed, "program",
             dict(check.compare(prog, ref), config_mismatches=len(mismatches)),
             prog, ref, time.perf_counter() - t)
        if i >= control_seeds:
            continue
        for name, mode in modes:
            t = time.perf_counter()
            side = R.reference_rounds(reference, rounds_in, None, feed, cfg,
                                      spec_dict, seed, band, **mode)
            ref = R.reference_rounds(reference, rounds_in, side, feed, cfg,
                                     spec_dict, seed, band)
            emit(rows, seed, name, check.compare(side, ref), side, ref,
                 time.perf_counter() - t)
    return rows


def emit(rows, seed, side, numbers, prog, ref, seconds):
    row = {"seed": seed, "side": side, "numbers": numbers,
           "seconds": seconds,
           "global_loss": [[p["global_loss"], r["global_loss"]]
                           for p, r in zip(prog, ref)],
           "losses": [[p["losses"].tolist(), r["losses"].tolist()]
                      for p, r in zip(prog, ref)],
           "velocity_norms": [[p["velocity_norms"], r["velocity_norms"]]
                              for p, r in zip(prog, ref)],
           "changes": [[p["changes"], r["changes"]]
                       for p, r in zip(prog, ref)],
           "adopted": [[r["adopted"], r["adopted_gap"]] for r in ref]}
    rows.append(row)
    print(json.dumps({"seed": seed, "side": side, **numbers,
                      "adopted": row["adopted"],
                      "seconds": round(seconds, 2)}), flush=True)


if __name__ == "__main__":
    sys.exit(main())
