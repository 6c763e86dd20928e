"""Builds the jitted (train / prefill / decode) step for an
(architecture x input-shape x mesh) combination, with full in/out
shardings, ready for `.lower(...).compile()` (dry-run) or execution.

This is the single place where the mapping decisions live:
  * swarm layout per arch (`cfg.swarm_mode`, DESIGN.md §3),
  * sharding rules per mode,
  * input_specs() — ShapeDtypeStruct stand-ins for every model input.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.comm.budget import CommConfig
from repro.comm.phy import PhyState
from repro.comm.straggler import StragglerBuffer
from repro.configs.base import ArchConfig, InputShape
from repro.core import swarm_dist
from repro.core.swarm_dist import DistSwarmConfig, DistSwarmState
from repro.models.transformer import Transformer
from repro.sharding import rules as rules_mod
from repro.sharding.param_specs import tree_shardings
from repro.sharding.rules import ShardingRules, use_rules

Array = jax.Array
PyTree = Any

EVAL_BATCH = 4          # D_g scoring batch (selection), per worker


def _prep_cfg(cfg: ArchConfig) -> ArchConfig:
    """Mesh-run config tweaks: pad vocab to a 16-multiple (seamless)."""
    if cfg.vocab_size % 16:
        cfg = dataclasses.replace(cfg, vocab_size=cfg.padded_vocab(16))
    return cfg


def swarm_layout(cfg: ArchConfig, mesh: Mesh) -> tuple[tuple[str, ...], int]:
    """(worker_axes, num_spatial_workers) per DESIGN.md §3."""
    multi = "pod" in mesh.axis_names
    if cfg.swarm_mode == "tp":
        axes = ("pod", "data") if multi else ("data",)
    else:  # fsdp
        axes = ("pod",) if multi else ()
    W = 1
    for a in axes:
        W *= mesh.shape[a]
    return axes, W


def train_rules(cfg: ArchConfig, mesh: Mesh) -> ShardingRules:
    multi = "pod" in mesh.axis_names
    if cfg.swarm_mode == "tp":
        return rules_mod.MULTI_POD_TP if multi else rules_mod.SINGLE_POD_TP
    return (rules_mod.MULTI_POD_FSDP_TP if multi
            else rules_mod.SINGLE_POD_FSDP_TP)


def serve_rules(cfg: ArchConfig, mesh: Mesh, long_context: bool
                ) -> ShardingRules:
    multi = "pod" in mesh.axis_names
    batch_axes = ("pod", "data") if multi else ("data",)
    # KV-cache head sharding only works when kv_heads divides the model
    # axis; otherwise shard the cache SEQUENCE over "model" instead
    # (flash-decode style: GSPMD inserts the partial-softmax collectives).
    # Without this, archs with kv=8 on a 16-way model axis replicate a
    # ~47 GiB cache per device (EXPERIMENTS.md §Perf iteration 3).
    kv_shardable = cfg.num_kv_heads % mesh.shape["model"] == 0
    r = ShardingRules(
        batch=None, seq=None,
        embed=None,
        # big archs keep FSDP-sharded weights at serving too (memory),
        # small archs are pure-TP (no per-layer all-gathers)
        embed_fsdp="data" if cfg.swarm_mode == "fsdp" else None,
        heads="model", kv_heads="model", q_per_kv=None, head_dim=None,
        # activation heads follow the weights only when the cache stays
        # head-sharded; with a seq-sharded cache the act heads replicate
        act_heads="model" if kv_shardable else None,
        act_kv_heads="model" if kv_shardable else None,
        residual_seq=None,
        mlp="model", vocab="model",
        expert="data" if cfg.num_experts >= 64 else "model",
        expert_mlp="model" if cfg.num_experts >= 64 else None,
        worker=None,
        cache_batch=batch_axes,
        cache_seq=None if kv_shardable else "model",
        # shard_map EP dispatch at serving too (no vmap wrapper there)
        moe_ep=cfg.num_experts >= 64,
    )
    if long_context:
        # batch=1: context-parallel KV cache over the data axis
        r = ShardingRules(r, cache_batch=None, cache_seq="data")
        r["batch"] = None
    else:
        r["batch"] = batch_axes
    return r


# ---------------------------------------------------------------------------
# input specs
# ---------------------------------------------------------------------------

def _token_batch_specs(cfg: ArchConfig, batch: int, seq: int,
                       lead: tuple[int, ...] = ()) -> dict:
    """ShapeDtypeStructs of one model batch (tokens + labels + frontends)."""
    i32 = jnp.int32
    sds = jax.ShapeDtypeStruct
    out = {"tokens": sds(lead + (batch, seq), i32),
           "labels": sds(lead + (batch, seq), i32)}
    if cfg.input_mode == "tokens+prefix":
        out["prefix"] = sds(lead + (batch, cfg.prefix_len, cfg.d_model),
                            jnp.dtype(cfg.dtype))
    if cfg.encoder_layers:
        out["frames"] = sds(lead + (batch, cfg.encoder_memory_len,
                                    cfg.d_model), jnp.dtype(cfg.dtype))
    return out


def input_specs(cfg: ArchConfig, shape: InputShape, mesh: Mesh,
                ) -> dict:
    """ShapeDtypeStruct stand-ins for every input of the lowered step
    (weak-type-correct, shardable, no device allocation)."""
    cfg = _prep_cfg(cfg)
    if shape.kind == "train":
        axes, W = swarm_layout(cfg, mesh)
        per_worker = shape.global_batch // max(W, 1)
        return {
            "batch": _token_batch_specs(cfg, per_worker, shape.seq_len,
                                        lead=(W,)),
            "eval_batch": _token_batch_specs(cfg, EVAL_BATCH, shape.seq_len),
            "key": jax.ShapeDtypeStruct((2,), jnp.uint32),
        }
    if shape.kind == "prefill":
        return {"batch": _token_batch_specs(cfg, shape.global_batch,
                                            shape.seq_len)}
    # decode: one new token against a cache of seq_len
    return {"tokens": jax.ShapeDtypeStruct((shape.global_batch, 1),
                                           jnp.int32)}


# ---------------------------------------------------------------------------
# step builders
# ---------------------------------------------------------------------------

class BuiltStep(NamedTuple):
    fn: Any                  # jitted function
    args: tuple              # ShapeDtypeStruct args matching fn signature
    rules: ShardingRules
    cfg: ArchConfig
    meta: dict


def _shard_batch_specs(batch: dict, rules: ShardingRules, mesh: Mesh,
                       worker_axes: Optional[tuple] = None) -> dict:
    """NamedShardings for a token batch dict (optionally worker-stacked)."""
    def leaf(name, x):
        if worker_axes is not None:
            wspec = worker_axes if len(worker_axes) != 1 else worker_axes[0]
            body = (rules.get("batch"),) + (None,) * (x.ndim - 2)
            spec = P(wspec if worker_axes else None, *body)
        else:
            spec = P(rules.get("batch"), *(None,) * (x.ndim - 1))
        # drop non-divisible axes
        fixed = []
        for dim, ax in zip(x.shape, spec):
            if ax is None:
                fixed.append(None)
                continue
            axt = (ax,) if isinstance(ax, str) else tuple(ax)
            size = 1
            for a in axt:
                size *= mesh.shape[a]
            fixed.append(ax if dim % size == 0 else None)
        return NamedSharding(mesh, P(*fixed))

    return {k: leaf(k, v) for k, v in batch.items()}


def build_train_step(cfg: ArchConfig, shape: InputShape, mesh: Mesh,
                     algorithm: str = "mdsl",
                     comm: Optional[CommConfig] = None,
                     population: int = 0) -> BuiltStep:
    """The M-DSL communication round as one jitted SPMD program. `comm`
    threads the wire config (compression / channel / aggregator /
    downlink) into the mesh round, so comm scenarios lower and cost out
    at 512-device scale exactly like the defaults. `population > 0`
    prices a P-device registry next to the step (population_specs) and
    reports its sharded footprint in the meta."""
    cfg = _prep_cfg(cfg)
    rules = train_rules(cfg, mesh)
    worker_axes, W = swarm_layout(cfg, mesh)
    model = Transformer(cfg)
    # auto microbatching: bound the per-local-step activation footprint
    # at ~8 sequences per device batch (grad accumulation over chunks)
    per_worker = shape.global_batch // max(W, 1)
    micro = cfg.train_microbatches or min(8, max(1, per_worker // 8))
    dcfg = DistSwarmConfig(worker_axes=worker_axes, num_spatial=W,
                           local_steps=1, tau=0.9, microbatches=micro,
                           comm=(comm or CommConfig()).validate())

    loss_fn = model.loss
    step = (swarm_dist.build_train_step(loss_fn, dcfg) if algorithm == "mdsl"
            else swarm_dist.fedavg_train_step(loss_fn, dcfg))

    specs = input_specs(cfg, shape, mesh)
    key = jax.random.PRNGKey(0)
    param_shapes = jax.eval_shape(model.init, key)
    state_shapes = jax.eval_shape(
        functools.partial(swarm_dist.init_state, cfg=dcfg), param_shapes)

    wspec = (tuple(worker_axes) if len(worker_axes) != 1 else worker_axes[0]
             ) if worker_axes else None
    pshard = lambda t, w: tree_shardings(
        t, rules, mesh, prefix_axes=1 if w else 0,
        prefix_spec=(wspec,) if w else None)
    scalar = NamedSharding(mesh, P())
    wvec = NamedSharding(mesh, P(wspec))
    state_shardings = DistSwarmState(
        params=pshard(state_shapes.params, True),
        velocity=pshard(state_shapes.velocity, True),
        best_params=pshard(state_shapes.best_params, True),
        best_loss=wvec,
        global_params=pshard(state_shapes.global_params, False),
        gbest_params=pshard(state_shapes.gbest_params, False),
        gbest_loss=scalar, prev_theta_mean=scalar, eta=wvec,
        round_idx=scalar,
        residual=pshard(state_shapes.residual, True),
        ps_residual=pshard(state_shapes.ps_residual, False),
        phy=PhyState(h_re=wvec, h_im=wvec, pathloss_db=wvec, snr_db=wvec,
                     age=wvec),
        # parked late deltas shard like the uplink residual (worker-
        # stacked model tree); ages are a (W,) vector like phy columns
        buffer=(StragglerBuffer(
                    delta=pshard(state_shapes.buffer.delta, True),
                    age=wvec)
                if state_shapes.buffer is not None else None))

    batch_sh = _shard_batch_specs(specs["batch"], rules, mesh,
                                  worker_axes=worker_axes)
    eval_sh = _shard_batch_specs(specs["eval_batch"],
                                 ShardingRules(rules, batch=None), mesh)
    in_sh = (state_shardings, batch_sh, eval_sh, scalar)
    info_sh = swarm_dist.RoundInfo(losses=wvec, theta=wvec, mask=wvec,
                                   global_loss=scalar, selected_count=scalar,
                                   uploaded_params=scalar, bytes_up=scalar,
                                   bytes_down=scalar, delivered=scalar,
                                   compression_ratio=scalar,
                                   airtime_s=scalar, energy_j=scalar,
                                   mean_snr_db=scalar)
    if dcfg.comm.round_deadline_s is not None:
        info_sh = info_sh._replace(late=scalar, drained=scalar,
                                   buffered=scalar, held=scalar)
    if dcfg.comm.fault_prob:
        info_sh = info_sh._replace(transmitted=scalar)

    def wrapped(state, batch, eval_batch, key):
        with use_rules(rules, mesh):
            return step(state, batch, eval_batch, key)

    # donate the swarm state: the round updates it in place, halving the
    # state footprint vs double-buffering
    fn = jax.jit(wrapped, in_shardings=in_sh,
                 out_shardings=(state_shardings, info_sh),
                 donate_argnums=(0,))
    args = (state_shapes, specs["batch"], specs["eval_batch"], specs["key"])
    meta = {"W": W, "worker_axes": worker_axes, "algorithm": algorithm,
            "microbatches": micro}
    if population:
        _, _, pop_meta = population_specs(dcfg.comm, population, mesh,
                                          worker_axes)
        meta["population"] = population
        meta["population_table_bytes"] = pop_meta["table_bytes"]
        meta["population_bytes_per_shard"] = pop_meta["bytes_per_shard"]
    return BuiltStep(fn=fn, args=args, rules=rules, cfg=cfg, meta=meta)


def population_specs(comm: CommConfig, population: int, mesh: Mesh,
                     worker_axes: tuple[str, ...]
                     ) -> tuple[Any, Any, dict]:
    """Dry-run shapes + shardings for a P-device population table on a
    mesh (core/population.py). The table is nine (P,) scalar columns, so
    it shards 1-D over the worker axes like the cohort's phy/eta vectors
    — 36 bytes/device split W ways, never an O(P) model pytree. Returns
    (ShapeDtypeStruct tree, NamedSharding tree, meta) where meta prices
    the footprint per host."""
    from repro.core import population as pop
    specs = pop.table_specs(population)
    wspec = (tuple(worker_axes) if len(worker_axes) != 1 else worker_axes[0]
             ) if worker_axes else None
    vec = NamedSharding(mesh, P(wspec))
    shardings = jax.tree.map(lambda _: vec, specs)
    total = sum(s.size * s.dtype.itemsize for s in jax.tree.leaves(specs))
    W = 1
    for a in worker_axes:
        W *= mesh.shape[a]
    return specs, shardings, {
        "population": population, "table_bytes": total,
        "bytes_per_shard": total // max(W, 1), "worker_axes": worker_axes}


def _serve_cache_shapes(model: Transformer, cfg: ArchConfig, batch: int,
                        cache_len: int) -> PyTree:
    memory = None
    params = None
    if cfg.cross_attention:
        memory = jax.ShapeDtypeStruct(
            (batch, cfg.encoder_memory_len, cfg.d_model),
            jnp.dtype(cfg.dtype))
        params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
        return jax.eval_shape(
            lambda p, m: model.init_cache(batch, cache_len, memory=m,
                                          params=p), params, memory)
    return jax.eval_shape(lambda: model.init_cache(batch, cache_len))


def build_serve_step(cfg: ArchConfig, shape: InputShape, mesh: Mesh
                     ) -> BuiltStep:
    """prefill_32k -> prefill step; decode_32k / long_500k -> decode step
    (one token against a seq_len cache)."""
    cfg = _prep_cfg(cfg)
    long_ctx = shape.seq_len > 100_000
    rules = serve_rules(cfg, mesh, long_ctx)
    model = Transformer(cfg)
    specs = input_specs(cfg, shape, mesh)
    param_shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    param_sh = tree_shardings(param_shapes, rules, mesh)

    if shape.kind == "prefill":
        cache_shapes = _serve_cache_shapes(model, cfg, shape.global_batch,
                                           shape.seq_len)
        cache_sh = tree_shardings(cache_shapes, rules, mesh, table="cache")
        batch_sh = _shard_batch_specs(specs["batch"], rules, mesh)

        def prefill(params, batch, cache):
            with use_rules(rules, mesh):
                if cfg.cross_attention:
                    memory = model.encode(params, batch["frames"])
                    cache = model.init_cache(batch["tokens"].shape[0],
                                             shape.seq_len, memory=memory,
                                             params=params)
                return model.prefill(params, batch, cache)

        fn = jax.jit(prefill, in_shardings=(param_sh, batch_sh, cache_sh),
                     out_shardings=(NamedSharding(mesh, P()), cache_sh),
                     donate_argnums=(2,))
        args = (param_shapes, specs["batch"], cache_shapes)
        return BuiltStep(fn=fn, args=args, rules=rules, cfg=cfg,
                         meta={"mode": "prefill"})

    # decode
    cache_shapes = _serve_cache_shapes(model, cfg, shape.global_batch,
                                       shape.seq_len)
    cache_sh = tree_shardings(cache_shapes, rules, mesh, table="cache")
    tok_sh = _shard_batch_specs({"tokens": specs["tokens"]}, rules,
                                mesh)["tokens"]

    def decode(params, tokens, cache):
        with use_rules(rules, mesh):
            return model.decode_step(params, tokens, cache)

    logits_sh = NamedSharding(mesh, P(rules.get("batch"), None, None))
    # donate the KV cache: the functional update aliases in place
    fn = jax.jit(decode, in_shardings=(param_sh, tok_sh, cache_sh),
                 out_shardings=(logits_sh, cache_sh),
                 donate_argnums=(2,))
    args = (param_shapes, specs["tokens"], cache_shapes)
    return BuiltStep(fn=fn, args=args, rules=rules, cfg=cfg,
                     meta={"mode": "decode", "long": long_ctx})


def build_step(cfg: ArchConfig, shape: InputShape, mesh: Mesh,
               algorithm: str = "mdsl",
               comm: Optional[CommConfig] = None) -> BuiltStep:
    if shape.kind == "train":
        return build_train_step(cfg, shape, mesh, algorithm, comm=comm)
    return build_serve_step(cfg, shape, mesh)
