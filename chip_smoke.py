#!/usr/bin/env python3
"""Chip smoke test: drive the swarm trainer's main path on a TPU.

    python3 chip_smoke.py             # one chip: phases a, b, c
    python3 chip_smoke.py --chips 4   # four chips: the sharded mesh round

Phases on one chip, each through `repro.experiments.run` (the path the
training CLI takes), at the registered sizes:

  a  paper/fig3-noniid1: C=50 workers, CNN x8, n_local 512, 4 local
     epochs, batch 64; 3 rounds
  b  low-bandwidth-int4 with obs on; 3 rounds. The obs stream must show
     the fused wire kernels compiled on the TPU (KernelEvents with
     backend="tpu", interpret=False); the kernels are also compared
     with their jnp references on one smollm-360m FFN leaf
  c  mesh/smollm-smoke at smollm-360m's published widths
     (model.reduced=false), W=2 workers; 2 steps

`--chips 4` runs only launch/steps.build_train_step on a (4, 1)
("data", "model") mesh, one worker per chip: 2 rounds of smollm-360m at
published widths, then one round at the reduced width compared with the
one-device vmap route (worker_axes=()) on the same state, batch and key.

Every phase prints one line of results; any failed check raises. The
last line of stdout is {"ok": true, "device": {...}} with the device as
JAX reports it. Without a TPU, or without the repository's sources next
to this file, the script exits non-zero and prints no result.
"""
from __future__ import annotations

import argparse
import json
import math
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.experiments import get_scenario, override, run  # noqa: E402
from repro.kernels import runtime  # noqa: E402
from repro.launch import compile_cache  # noqa: E402
from repro.obs.events import KernelEvent  # noqa: E402
from repro.obs.sinks import read_events  # noqa: E402

OUT_DIR = ROOT / "artifacts" / "chip_smoke"
FFN_LEAF_ROWS = 960 * 2560 // 128     # one smollm-360m FFN weight
# sharded vs one-device round at the reduced width: the two programs
# reduce in different orders over workers and devices (bf16 model)
SHARDED_RTOL = SHARDED_ATOL = 1e-3


class SmokeFailure(RuntimeError):
    pass


def _check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def _finite(xs) -> bool:
    return all(math.isfinite(v) for v in np.ravel(np.asarray(xs, float)))


def _peak_bytes() -> int | None:
    """Peak device memory of this process so far (None where the backend
    keeps no statistics, as the CPU does)."""
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def _report(tag: str, **fields) -> dict:
    fields["peak_bytes_in_use"] = _peak_bytes()
    print(f"[{tag}] " + json.dumps(fields), flush=True)
    return fields


def _timed_run(spec):
    t0 = time.perf_counter()
    res = run(spec, verbose=False)
    return res, time.perf_counter() - t0


def _round_times(times: list) -> dict:
    """Round 0 carries the compile; the rest are steady rounds."""
    rest = times[1:]
    return {"round0_compile_and_run_s": times[0],
            "steady_round_s": (sum(rest) / len(rest)) if rest else None}


def phase_paper(*overrides: str, rounds: int = 3) -> dict:
    """(a) the paper's Fig.-3 operating point, as registered."""
    spec = override(get_scenario("paper/fig3-noniid1"),
                    f"run.rounds={rounds}", *overrides)
    res, wall = _timed_run(spec)
    rec = res.record
    _check(len(rec["acc"]) == rounds, f"{len(rec['acc'])} rounds recorded")
    _check(_finite(rec["acc"]) and _finite(rec["global_loss"]),
           f"non-finite metrics: {rec['acc']} {rec['global_loss']}")
    _check(0.0 <= rec["final_acc"] <= 1.0, f"accuracy {rec['final_acc']}")
    return _report("a paper/fig3-noniid1", rounds=rounds,
                   workers=spec.data.num_workers, wall_s=wall,
                   **_round_times(rec["round_time_s"]),
                   final_acc=rec["final_acc"],
                   final_loss=rec["global_loss"][-1], finite=True)


def _kernels_vs_ref(rows: int, workers: int) -> dict:
    """The wire kernels against their jnp references on one leaf of
    `rows` x 128 f32 per worker, vmapped over `workers` as the engine
    calls them. Kernels run compiled on a TPU (interpret mode on CPU);
    the references run as ordinary XLA programs on the same device."""
    from repro.kernels.quant_pack import quant_pack_ef_2d, quant_pack_ef_ref
    from repro.kernels.wire_agg import wire_agg_2d, wire_agg_ref
    interpret = runtime.interpret_default()
    k = jax.random.PRNGKey(11)
    x = jax.random.normal(k, (workers, rows, 128), jnp.float32)
    r = 0.1 * jax.random.normal(jax.random.fold_in(k, 1), x.shape)
    seeds = jnp.arange(workers, dtype=jnp.int32) * 7919 + 3
    kern = jax.jit(jax.vmap(lambda a, b, s: quant_pack_ef_2d(
        a, b, s, bits=4, interpret=interpret)))(x, r, seeds)
    ref = jax.jit(jax.vmap(lambda a, b, s: quant_pack_ef_ref(
        a, b, s, bits=4)))(x, r, seeds)
    pk, sk, rk = (np.asarray(a) for a in kern)
    pr, sr, rr = (np.asarray(a) for a in ref)
    _check(pk.shape == pr.shape == (workers, rows // 2, 128),
           f"packed shape {pk.shape}")
    # the wire spec: payload, scales and residual bit-identical to the
    # reference on every backend (measured so on a v5e as well)
    for name, a, b in (("packed", pk, pr), ("scales", sk, sr),
                       ("residual", rk, rr)):
        _check(np.array_equal(a, b), f"quant_pack_ef {name} differs from "
               f"ref on {np.mean(a != b):.2e} of elements")

    mask = (jnp.arange(workers) % 3 != 0).astype(jnp.float32)[:, None]
    ones = jnp.ones((workers, 1), jnp.float32)
    packed, scales = jnp.asarray(pr), jnp.asarray(sr)
    agg_k = np.asarray(jax.jit(lambda p, s, m, w: wire_agg_2d(
        p, s, m, w, bits=4, interpret=interpret))(packed, scales, mask, ones))
    agg_r = np.asarray(jax.jit(lambda p, s, m, w: wire_agg_ref(
        p, s, m, w, bits=4))(packed, scales, mask, ones))
    _check(agg_k.shape == (rows, 128) and _finite(agg_k),
           "wire_agg output shape or values")
    # Mosaic and XLA may sum the C workers in different orders
    agg_err = float(np.max(np.abs(agg_k - agg_r)) / np.max(np.abs(agg_r)))
    _check(agg_err < 1e-5, f"wire_agg relative error {agg_err}")
    return {"quant_pack_ef_bit_identical": True,
            "wire_agg_max_rel_diff": agg_err}


def phase_wire(*overrides: str, rounds: int = 3,
               kernel_rows: int = FFN_LEAF_ROWS,
               obs_dir: Path = OUT_DIR / "obs") -> dict:
    """(b) the int4 uplink + int8 downlink regime on the fused wire
    route, with the obs stream proving which code ran."""
    spec = override(get_scenario("low-bandwidth-int4"),
                    f"run.rounds={rounds}", "run.obs.enabled=true",
                    f"run.obs.dir={obs_dir}", *overrides)
    res, wall = _timed_run(spec)
    rec = res.record
    _check(len(rec["acc"]) == rounds and _finite(rec["acc"])
           and _finite(rec["global_loss"]), "non-finite or missing rounds")
    kernels = [e for e in read_events(res.events_path)
               if isinstance(e, KernelEvent)]
    backend = jax.default_backend()
    compiled = backend == "tpu"
    for e in kernels:
        _check(e.backend == backend and e.interpret is not compiled,
               f"{e.name} dispatched backend={e.backend} "
               f"interpret={e.interpret} on a {backend} run")
    names = {e.name for e in kernels}
    for need in ("quant_pack_ef", "wire_agg", "quant_pack",
                 "dequant_unpack"):
        _check(need in names, f"no {need} KernelEvent (saw {names})")
    _check(any(e.name == "quant_pack_ef" and e.info.get("bits") == 4
               for e in kernels), "uplink did not pack int4")
    C = spec.data.num_workers
    _check(any(e.name == "wire_agg" and e.info.get("workers") == C
               for e in kernels), f"wire_agg never saw the {C} workers")
    agree = _kernels_vs_ref(kernel_rows, C)
    return _report("b low-bandwidth-int4", rounds=rounds, workers=C,
                   wall_s=wall, **_round_times(rec["round_time_s"]),
                   final_acc=rec["final_acc"],
                   final_loss=rec["global_loss"][-1], finite=True,
                   kernel_events=sorted(names), backend=backend,
                   interpret=not compiled, kernels_vs_ref=agree)


def phase_mesh(*overrides: str, rounds: int = 2) -> dict:
    """(c) the mesh engine at smollm-360m's published widths."""
    spec = override(get_scenario("mesh/smollm-smoke"),
                    "model.reduced=false", f"run.rounds={rounds}",
                    *overrides)
    res, wall = _timed_run(spec)
    rec = res.record
    W = spec.data.num_workers
    _check(len(rec["global_loss"]) == rounds, "missing steps")
    _check(all(len(w) == W for w in rec["worker_losses"]),
           "worker_losses shape")
    _check(_finite(rec["global_loss"]) and _finite(rec["worker_losses"]),
           f"non-finite losses {rec['global_loss']}")
    return _report("c mesh/smollm-smoke", reduced=spec.model.reduced,
                   workers=W, steps=rounds, wall_s=wall,
                   **_round_times(rec["step_time_s"]),
                   final_loss=rec["global_loss"][-1],
                   worker_losses=rec["worker_losses"][-1], finite=True)


def phase_sharded(n_dev: int, *, full_width: bool = True,
                  seq_len: int = 128, rounds: int = 2) -> dict:
    """The mesh path's sharded round: W = n_dev workers, one per device
    on a (n_dev, 1) ("data", "model") mesh. Runs `rounds` rounds at
    smollm-360m's published widths (when `full_width`), then compares
    one reduced-width round with the one-device vmap route."""
    from repro.comm.budget import CommConfig
    from repro.configs.base import InputShape, get_arch
    from repro.core import swarm_dist
    from repro.core.swarm_dist import DistSwarmConfig
    from repro.launch.mesh import make_mesh
    from repro.launch.steps import EVAL_BATCH, build_train_step
    from repro.models.transformer import Transformer

    _check(len(jax.devices()) >= n_dev,
           f"{n_dev} devices wanted, {len(jax.devices())} found")
    mesh = make_mesh((n_dev, 1), ("data", "model"),
                     devices=jax.devices()[:n_dev])
    shape = InputShape("smoke", seq_len, 2 * n_dev, "train")

    def setup(cfg, seed=0):
        built = build_train_step(cfg, shape, mesh)
        _check(built.meta["W"] == n_dev, f"W={built.meta['W']}")
        one_dev = DistSwarmConfig(
            worker_axes=(), num_spatial=n_dev, local_steps=1, tau=0.9,
            microbatches=built.meta["microbatches"],
            comm=CommConfig().validate())
        model = Transformer(built.cfg)
        params = model.init(jax.random.PRNGKey(seed))
        init = lambda p: swarm_dist.init_state(p, one_dev)  # noqa: E731
        k = jax.random.PRNGKey(seed + 1)
        toks = jax.random.randint(k, (n_dev, 2, seq_len), 0,
                                  built.cfg.vocab_size)
        etoks = jax.random.randint(jax.random.fold_in(k, 1),
                                   (EVAL_BATCH, seq_len), 0,
                                   built.cfg.vocab_size)
        batch = {"tokens": toks, "labels": jnp.roll(toks, -1, axis=-1)}
        ebatch = {"tokens": etoks, "labels": jnp.roll(etoks, -1, axis=-1)}
        return built, one_dev, model, params, init, batch, ebatch

    def sharded_rounds(built, init, params, batch, ebatch, n):
        t0 = time.perf_counter()
        compiled = built.fn.lower(*built.args).compile()
        compile_s = time.perf_counter() - t0
        state_sh, batch_sh, ebatch_sh, key_sh = compiled.input_shardings[0]
        # the state is built in place, sharded: at published widths the
        # whole W-worker state does not fit on one chip
        state = jax.jit(init, out_shardings=state_sh)(params)
        batch, ebatch = jax.device_put((batch, ebatch), (batch_sh, ebatch_sh))
        infos, times = [], []
        for t in range(n):
            key = jax.device_put(jax.random.PRNGKey(100 + t), key_sh)
            t0 = time.perf_counter()
            state, info = compiled(state, batch, ebatch, key)
            jax.block_until_ready(info)
            times.append(time.perf_counter() - t0)
            infos.append(jax.device_get(info))
        return infos, compile_s, times

    out = {"devices": n_dev}
    if full_width:
        cfg = get_arch("smollm-360m")
        built, _, _, params, init, batch, ebatch = setup(cfg)
        infos, compile_s, times = sharded_rounds(built, init, params, batch,
                                                 ebatch, rounds)
        del params
        for info in infos:
            _check(_finite(info.losses) and _finite(info.global_loss),
                   f"non-finite sharded losses {info.losses}")
            _check(np.shape(info.losses) == (n_dev,), "losses shape")
        out["full_width"] = {
            "arch": cfg.name, "d_model": cfg.d_model, "layers":
            cfg.num_layers, "rounds": rounds, "compile_s": compile_s,
            "round_s": times,
            "global_loss": [float(i.global_loss) for i in infos],
            "worker_losses": np.asarray(infos[-1].losses).tolist()}
        out["full_width"]["peak_bytes_in_use_per_device"] = [
            (d.memory_stats() or {}).get("peak_bytes_in_use")
            for d in jax.devices()[:n_dev]]

    cfg = get_arch("smollm-360m").reduced()
    built, one_dev, model, params, init, batch, ebatch = setup(cfg, seed=1)
    [sharded], _, _ = sharded_rounds(built, init, params, batch, ebatch, 1)
    step = jax.jit(swarm_dist.build_train_step(model.loss, one_dev))
    _, single = step(jax.jit(init)(params), batch, ebatch,
                     jax.random.PRNGKey(100))
    single = jax.device_get(single)
    np.testing.assert_array_equal(sharded.mask, single.mask)
    for name in ("losses", "global_loss"):
        np.testing.assert_allclose(
            np.asarray(getattr(sharded, name)),
            np.asarray(getattr(single, name)),
            rtol=SHARDED_RTOL, atol=SHARDED_ATOL,
            err_msg=f"sharded vs one-device {name}")
    out["reduced_vs_one_device"] = {
        "d_model": cfg.d_model, "mask": np.asarray(sharded.mask).tolist(),
        "losses_max_abs_diff": float(np.max(np.abs(
            np.asarray(sharded.losses) - np.asarray(single.losses)))),
        "global_loss_abs_diff": float(abs(
            sharded.global_loss - single.global_loss)),
        "rtol": SHARDED_RTOL, "atol": SHARDED_ATOL}
    return _report(f"sharded x{n_dev}", **out)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the sharded mesh round on four chips")
    args = ap.parse_args(argv)

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {devices[0].platform}); "
              f"this script runs only on the chip", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but {len(devices)} "
              f"device(s)", file=sys.stderr)
        return 2
    compile_cache.enable()
    if args.chips == 4:
        phase_sharded(4)
    else:
        phase_paper()
        phase_wire()
        phase_mesh()
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
