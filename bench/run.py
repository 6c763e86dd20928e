#!/usr/bin/env python3
"""Run one benchmark cell once on the accelerator this process holds.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is found by name in BENCHMARK.json; its configuration and
traffic are files under bench/ (configs/<config>.json,
workloads/<cell>.json), and so are the engine adapter, the reference,
the work counts and the per-layer readers they name. Nothing here is
specific to one cell.

A run:
  1. set-up: builds the system under test through
     `repro.experiments.runner.build` from the registry scenario, the
     configuration's and the cell's overrides and the seed, then drives
     the checked rounds (the first rounds of the run, which also compile
     every program the window uses) through the runner's own `step`,
     keeping what the output check needs;
  2. the window: calls `step` round after round for --seconds, each
     round ending on the host read the runner's loop does (test accuracy
     on the paper path, the global loss on the mesh path). No program
     may compile inside it. With --trace 1 a few seconds of it run under
     the profiler and the per-layer metrics are read from that trace;
  3. the check: after reading the peak device memory and freeing the
     program's state, the plain reference recomputes each checked round
     from the same starting state and key (check.py decides `correct`).

The last line of stdout is the result as one JSON object; the last lines
of stderr are the compared numbers beside their limits. Without an
accelerator, or with fewer chips than the cell asks for, it exits
non-zero and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import functools  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
SEED_MOD = 2**32 - 2        # the runner keys PRNGKey(seed + 1): keep < 2^32
TRACE_SECONDS = 3.0         # length of the traced part of a --trace 1 run
MIN_TRACED_ROUNDS = 3


class Refused(Exception):
    """The run cannot be made here (no chip, missing files)."""


def load_module(path: Path, name: str):
    if not path.is_file():
        raise Refused(f"missing {path.relative_to(ROOT)}")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_cell(name: str) -> dict:
    """The cell's BENCHMARK.json entry, configuration and traffic files."""
    bm_path = ROOT / "BENCHMARK.json"
    if not bm_path.is_file():
        raise Refused("BENCHMARK.json not found")
    bm = json.loads(bm_path.read_text())
    entry = next((w for w in bm["workloads"] if w["name"] == name), None)
    if entry is None:
        raise Refused(f"no workload {name!r} in BENCHMARK.json")
    conf = next(c for c in bm["configs"] if c["name"] == entry["config"])
    cfg = json.loads((ROOT / conf["file"]).read_text())
    cell_path = BENCH / "workloads" / f"{name}.json"
    if not cell_path.is_file():
        raise Refused(f"missing {cell_path.relative_to(ROOT)}")
    cell = json.loads(cell_path.read_text())
    if cell["config"] != entry["config"]:
        raise Refused(f"{cell_path.name} names config {cell['config']}")
    metrics = {"end_to_end": [m for m in bm["end_to_end"]
                              if name in m.get("workloads", [name])],
               "per_layer": [m for m in bm["per_layer"]
                             if name in m.get("workloads", [name])]}
    return {"name": name, "entry": entry, "cfg": cfg, "cell": cell,
            "metrics": metrics}


def import_program():
    src = ROOT / "src"
    if not (src / "repro" / "experiments" / "runner.py").is_file():
        raise Refused("the system under test (src/repro) is not in this "
                      "checkout")
    if str(src) not in sys.path:
        sys.path.insert(0, str(src))


def require_devices(chips: int):
    import jax
    devs = jax.devices()
    if devs[0].platform == "cpu":
        raise Refused("no accelerator: JAX found only the CPU")
    if len(devs) < chips:
        raise Refused(f"the cell needs {chips} chips, JAX found {len(devs)}")
    return devs


def enable_compile_cache():
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        from repro.launch import compile_cache
        compile_cache.enable()
    # cache every program, so a second run of a cell compiles nothing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


class CompileCounter:
    """Counts traces and compiles while `armed` (none may happen inside
    the measured window)."""
    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax
        self.armed = False
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if self.armed and event in self.EVENTS:
            self.count += 1


@functools.lru_cache(maxsize=None)
def _jitted():
    """Fused per-leaf reductions: no full-size temporary on the device,
    so the check's readings do not raise the program's memory peak."""
    import jax
    import jax.numpy as jnp
    f32 = jnp.float32
    return (jax.jit(lambda x: jnp.sum(jnp.square(x.astype(f32)))),
            jax.jit(lambda a, b: jnp.sum(jnp.square(a.astype(f32)
                                                    - b.astype(f32)))))


def host_snapshot(tree):
    """Copy a pytree to the host, leaf by leaf."""
    import jax
    import numpy as np
    return jax.tree.map(np.asarray, tree)


# change readings of the carried state: reading key -> the engines' view key
CARRIED = {"global": "global", "best": "best_params", "gbest": "gbest",
           "residual": "residual", "ps_residual": "ps_residual"}


def program_readings(view_out, start, tel, best_loss_in, gbest_loss_in,
                     pre_losses) -> dict:
    """The program's side of a checked round, read on the device leaf by
    leaf (`start`: host copies of the carried state at the round's
    start; `pre_losses`: the program's F_{i,t} of the models the round
    starts from, its previous round's F_{i,t+1}, or None)."""
    import jax
    import numpy as np
    sqnorm, sqdiff = _jitted()
    vel = [float(np.sqrt(sqnorm(x)))
           for x in jax.tree.leaves(view_out["velocity"])]
    changes = {k: [float(np.sqrt(sqdiff(n, np.asarray(o))))
                   for n, o in zip(jax.tree.leaves(view_out[CARRIED[k]]),
                                   jax.tree.leaves(old))]
               for k, old in start.items()}
    best_out = np.asarray(view_out["best_loss"])
    return {"losses": np.asarray(tel.losses, np.float32),
            "theta": np.asarray(tel.theta, np.float32),
            "pre_losses": pre_losses,
            "global_loss": float(tel.global_loss),
            "mask": np.asarray(tel.mask, np.float32),
            "local_improved": best_out != np.asarray(best_loss_in),
            "global_improved": (float(view_out["gbest_loss"])
                                != float(gbest_loss_in)),
            "velocity_norms": vel, "changes": changes}


def checked_rounds(engine, reference, prep, n: int):
    """Drive the program's first `n` rounds through its own `step`,
    keeping for each the key, the starting state the reference needs
    (round 1: none, the reference builds its own) and the program's
    readings. Returns (state, key, rounds_in, prog_rounds, seconds spent
    on the check's copies and readings)."""
    import numpy as np
    reads = getattr(reference, "READS", None)
    state, key = prep.state, prep.key
    check_s = 0.0
    rounds_in, prog_rounds = [], []
    for r in range(n):
        t = time.perf_counter()
        view = engine.view(state)
        keep = host_snapshot({k: v for k, v in view.items()
                              if r > 0 and (reads is None or k in reads)})
        start = {k: keep[CARRIED[k]] if CARRIED[k] in keep
                 else host_snapshot(view[CARRIED[k]])
                 for k in reference.CHANGES}
        rounds_in.append({
            "key": np.asarray(key), "view": keep if r > 0 else None,
            "start": start,
            "best_loss": np.asarray(view["best_loss"]),
            "gbest_loss": float(view["gbest_loss"])})
        del view
        check_s += time.perf_counter() - t
        state, tel, key = prep.step(state, key)
        engine.host_read(prep, state, tel)
        t = time.perf_counter()
        ri = rounds_in[-1]
        prog_rounds.append(program_readings(
            engine.view(state), start, tel, ri["best_loss"],
            ri["gbest_loss"],
            prog_rounds[-1]["losses"] if prog_rounds else None))
        check_s += time.perf_counter() - t
    return state, key, rounds_in, prog_rounds, check_s


def reference_rounds(reference, rounds_in, prog_rounds, feed, cfg,
                     spec_dict, seed, band, **mode):
    """The reference's readings of each checked round, from the same
    starting state and key; `prog_rounds` holds the other side's
    readings, whose decisions it adopts on near ties (None: its own).
    `mode` passes dtype= / fault=."""
    import jax.numpy as jnp
    out = []
    for r, ri in enumerate(rounds_in):
        view = (reference.init(cfg, spec_dict, seed % SEED_MOD, feed)
                if r == 0 else ri["view"])
        out.append(reference.run_round(
            view, jnp.asarray(ri["key"]), feed, cfg, spec_dict,
            prog=None if prog_rounds is None else prog_rounds[r],
            band=band, **mode))
        del view
    return out


def resolve(ctx: dict, seed: int, extra_overrides=()):
    """The ExperimentSpec of this cell and seed."""
    from repro.experiments import get_scenario, override
    cfg, cell = ctx["cfg"], ctx["cell"]
    spec = override(get_scenario(cfg["scenario"]), *cfg["overrides"],
                    *cell["overrides"], f"run.seed={seed % SEED_MOD}",
                    *extra_overrides)
    return spec


def check_spec(spec_dict: dict, cfg: dict) -> list:
    out = []
    for path, want in cfg.get("expect", {}).items():
        have = spec_dict
        for k in path.split("."):
            have = have[k]
        if have != want:
            out.append(f"{path}: configuration {want!r}, program {have!r}")
    return out


def run(ctx: dict, seed: int, seconds: float, trace: bool, *,
        require_chip: bool = True, extra_overrides=(), cfg=None,
        wrap_step=None) -> dict:
    """One run of a cell. Returns the result dict (the JSON line).

    Tests call this directly: `require_chip=False` skips the look for a
    chip, `extra_overrides`/`cfg` shrink the cell, and `wrap_step` breaks
    the program's step underneath."""
    import_program()
    import jax
    if require_chip:
        devs = require_devices(ctx["entry"]["chips"])
    else:
        devs = jax.devices()
    enable_compile_cache()
    from repro.experiments.runner import build
    from repro.experiments.spec import to_dict
    from repro.obs import trace as obs_trace
    from repro.obs.events import NULL

    cfg = ctx["cfg"] if cfg is None else cfg
    engine = load_module(BENCH / "engines" / f"{cfg['engine']}.py",
                         f"bench_engine_{cfg['engine']}")
    reference = load_module(BENCH / "reference" / f"{cfg['reference']}.py",
                            "bench_reference")
    work = load_module(BENCH / "work" / f"{cfg['work']}.py", "bench_work")
    import check
    counter = CompileCounter()

    # named scopes on every stage, traced or not, so both runs execute
    # the same programs
    obs_trace.install(obs_trace.StageTracer(NULL))
    spec = resolve(ctx, seed, extra_overrides)
    spec_dict = to_dict(spec)
    mismatches = check_spec(spec_dict, cfg) if not extra_overrides else []
    prep = build(spec)
    if wrap_step is not None:
        prep = wrap_step(prep)
    if not extra_overrides:
        mismatches += engine.check_config(prep, cfg)

    # -- checked rounds (set-up: they compile what the window runs) ----
    state, key, rounds_in, prog_rounds, check_s = checked_rounds(
        engine, reference, prep, ctx["cell"]["check"]["rounds"])
    feed = engine.feed(prep)
    jax.block_until_ready(state)

    # -- the window ------------------------------------------------------
    result = {"correct": False, "attempted": 0, "failed": 0, "metrics": {}}
    t_window = time.perf_counter()
    setup_s = t_window - T_START - check_s
    counter.armed = True
    times, failed = [], 0
    tdir = None
    if trace:
        tdir = tempfile.mkdtemp(prefix="bench_trace_")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(tdir, create_perfetto_trace=True,
                                 profiler_options=opts)
        limit = min(seconds, TRACE_SECONDS)
    else:
        limit = seconds
    try:
        t0 = time.perf_counter()
        while True:
            t = time.perf_counter()
            with jax.profiler.TraceAnnotation("bench.step"):
                state, tel, key = prep.step(state, key)
            with jax.profiler.TraceAnnotation("bench." + engine.SPAN_READ):
                v = engine.host_read(prep, state, tel)
            end = time.perf_counter()
            times.append(end - t)
            failed += 0 if math.isfinite(v) else 1
            if end - t0 >= limit and (not trace
                                      or len(times) >= MIN_TRACED_ROUNDS):
                break
        window_s = end - t0
    finally:
        if trace:
            jax.profiler.stop_trace()
    counter.armed = False
    compiles = counter.count
    peak = (devs[0].memory_stats() or {}).get("peak_bytes_in_use")

    result["attempted"] = len(times)
    result["failed"] = failed
    if trace:
        metrics, device_extra, breakdown = read_trace(
            tdir, ctx, cfg, spec_dict, work, devs)
        shutil.rmtree(tdir, ignore_errors=True)
        result["metrics"] = metrics
    else:
        device_extra, breakdown = {}, None
        values = {
            "round_s": window_s / len(times),
            "round_p90_s": (statistics.quantiles(times, n=10)[-1]
                            if len(times) >= 10 else None),
            "peak_hbm_gib": None if peak is None else peak / 2**30,
            "setup_s": setup_s}
        for m in ctx["metrics"]["end_to_end"]:
            val = values.get(m["name"])
            if val is not None:
                result["metrics"][m["name"]] = {"value": val, "unit": m["unit"]}

    # -- the check -------------------------------------------------------
    del state, tel, prep
    gc.collect()
    t = time.perf_counter()
    limits = ctx["cell"]["check"]["limits"]
    ref_rounds = reference_rounds(reference, rounds_in, prog_rounds, feed,
                                  cfg, spec_dict, seed, limits["loss"])
    numbers = check.compare(prog_rounds, ref_rounds)
    numbers["window_compiles"] = float(compiles)
    numbers["config_mismatches"] = float(len(mismatches))
    ok, checks = check.judge(numbers, dict(limits, window_compiles=0,
                                           config_mismatches=0))
    result["correct"] = bool(ok and failed == 0)
    reference_s = time.perf_counter() - t

    d0 = devs[0]
    result["device"] = {"platform": d0.platform, "kind": d0.device_kind,
                        "count": ctx["entry"]["chips"],
                        "memory_peak_bytes": peak, **device_extra}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["timing"] = {"setup_s": setup_s, "check_in_setup_s": check_s,
                        "reference_s": reference_s, "window_s": window_s,
                        "rounds": len(times)}
    for m in mismatches:
        print(f"config mismatch: {m}", file=sys.stderr)
    result["checks"] = checks
    return result


def read_trace(tdir, ctx, cfg, spec_dict, work, devs):
    """Per-layer metrics, device busy time and the breakdown of a trace."""
    import glob
    import trace_reduce as tr
    files = glob.glob(os.path.join(tdir, "**", "perfetto_trace.json.gz"),
                      recursive=True)
    if not files:
        raise RuntimeError("the profiler wrote no trace")
    red = tr.reduce_trace(tr.load(files[0]))
    peaks = json.loads((BENCH / "peaks.json").read_text())["devices"]
    kind = devs[0].device_kind
    if kind not in peaks:
        raise RuntimeError(f"no peaks for device kind {kind!r} in peaks.json")
    reading = {"reduced": red, "peaks": peaks[kind], "cfg": cfg,
               "spec": spec_dict, "work": work}
    metrics = {}
    for m in ctx["metrics"]["per_layer"]:
        reader = load_module(BENCH / "metrics" / f"{m['name']}.py",
                             f"bench_metric_{m['name'].replace('.', '_')}")
        val = reader.read(reading)
        if val is not None:
            metrics[m["name"]] = {"value": val, "unit": m["unit"]}
    device = {"busy_s": red.busy_s, "window_s": red.window_s}
    breakdown = {"device_ops": red.top_ops, "idle_gaps": red.idle_gaps}
    return metrics, device, breakdown


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = p.parse_args(argv)
    try:
        ctx = load_cell(args.workload)
        result = run(ctx, args.seed, args.seconds, bool(args.trace))
    except Refused as e:
        print(f"refused: {e}", file=sys.stderr)
        return 2
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
