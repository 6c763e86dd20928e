"""Mixture-of-Experts channel mixer (top-k routing, sort-based dispatch).

TPU-native dispatch (DESIGN.md §5): tokens are argsorted by expert
assignment, packed into per-expert capacity buffers, run through a single
vmapped expert FFN einsum (MXU-friendly (E, cap, d) x (E, d, f)), and
scatter-combined back weighted by the router gate. Capacity-overflow
tokens are dropped (standard GShard semantics, capacity_factor
configurable). With the expert dim sharded over the mesh "expert" axis
(rules table: the data axis in FSDP mode) the pack/unpack gathers lower
to all-to-all-style collectives — the communication pattern the roofline
tracks for the MoE architectures.

Router load-balancing: the auxiliary loss of Shazeer et al. (mean gate
fraction x mean dispatch fraction per expert) is returned alongside the
output so the trainer can add it to the task loss.
"""
from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp

from repro.models.layers import cdtype, dense_init, rmsnorm, rmsnorm_init
from repro.sharding import shard

Array = jax.Array
PyTree = Any


def moe_init(key: Array, cfg) -> PyTree:
    d, f, E = cfg.d_model, cfg.d_ff, cfg.num_experts
    dt = cdtype(cfg)
    ks = jax.random.split(key, 5)
    p = {
        "norm": rmsnorm_init(d),
        "router": dense_init(ks[0], (d, E), dtype=jnp.float32),
        "wi": dense_init(ks[1], (E, d, f), in_axis=1, dtype=dt),
        "wu": dense_init(ks[2], (E, d, f), in_axis=1, dtype=dt),
        "wo": dense_init(ks[3], (E, f, d), in_axis=1, dtype=dt),
    }
    if cfg.dense_residual:  # arctic: parallel dense MLP
        kd = jax.random.split(ks[4], 3)
        p["dense"] = {
            "wi": dense_init(kd[0], (d, f), dtype=dt),
            "wu": dense_init(kd[1], (d, f), dtype=dt),
            "wo": dense_init(kd[2], (f, d), dtype=dt),
        }
    return p


def moe_apply(params: PyTree, x: Array, cfg) -> tuple[Array, Array]:
    """Returns (y, aux_loss). x: (B, S, D)."""
    capacity_factor = cfg.moe_capacity_factor
    B, S, D = x.shape
    E, K = cfg.num_experts, cfg.experts_per_token
    h = rmsnorm(params["norm"], x, cfg.norm_eps)

    # expert-parallel all-to-all dispatch (shard_map) when the rules
    # enable it — see moe_ep.py; falls through to the GSPMD sort-based
    # dispatch otherwise (CPU tests / vmapped tp-mode swarm)
    from repro.models import moe_ep
    from repro.sharding.rules import get_rules
    rules, mesh = get_rules()
    ep_axis = moe_ep.ep_applicable(cfg, mesh, rules)
    if ep_axis is not None and B % mesh.shape[ep_axis] == 0:
        y, aux_loss = moe_ep.moe_apply_ep(params, h, cfg, mesh, ep_axis)
        if "dense" in params:  # arctic dense residual
            dp = params["dense"]
            a = jax.nn.silu(jnp.einsum("bsd,df->bsf", h, dp["wi"]))
            u = jnp.einsum("bsd,df->bsf", h, dp["wu"])
            y = y + jnp.einsum("bsf,fd->bsd", a * u, dp["wo"])
        return shard(y, ("batch", "seq", "embed")), aux_loss
    hf = h.reshape(B * S, D)
    T = B * S

    logits = (hf.astype(jnp.float32) @ params["router"])         # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, expert_idx = jax.lax.top_k(probs, K)              # (T, K)
    gate_vals = gate_vals / jnp.maximum(
        gate_vals.sum(-1, keepdims=True), 1e-9)                  # renorm

    # auxiliary load-balance loss (Shazeer): E * sum_e f_e * p_e
    dispatch_frac = jnp.zeros((E,), jnp.float32).at[
        expert_idx.reshape(-1)].add(1.0) / (T * K)
    gate_frac = probs.mean(axis=0)
    aux_loss = E * jnp.sum(dispatch_frac * gate_frac)

    cap = int(math.ceil(T * K / E * capacity_factor))
    cap = max(cap, 1)

    # --- pack: sort (token, k) pairs by expert, take first `cap` each ---
    flat_e = expert_idx.reshape(T * K)                           # (TK,)
    sort_idx = jnp.argsort(flat_e)                               # (TK,)
    sorted_e = flat_e[sort_idx]
    counts = jnp.zeros((E,), jnp.int32).at[flat_e].add(1)
    starts = jnp.cumsum(counts) - counts
    pos = jnp.arange(T * K) - starts[sorted_e]                   # rank in expert
    keep = pos < cap
    dest = jnp.where(keep, sorted_e * cap + pos, E * cap)        # drop slot
    src_token = sort_idx // K

    buf = jnp.zeros((E * cap + 1, D), h.dtype)
    buf = buf.at[dest].set(hf[src_token])
    # pin the scatter itself to a replicated layout: XLA's SPMD scatter
    # partitioning miscompiles when the expert axis of `buf` is sharded
    # over a mesh dim (observed on the (data, model) host mesh); the
    # reshard to the expert-sharded FFN layout happens on `xs` below
    buf = shard(buf, (None, "embed"))
    xs = buf[: E * cap].reshape(E, cap, D)
    xs = shard(xs, ("expert", None, "embed"))

    # --- expert FFN (gated) ---
    wi = shard(params["wi"], ("expert", "embed_fsdp", "expert_mlp"))
    wu = shard(params["wu"], ("expert", "embed_fsdp", "expert_mlp"))
    wo = shard(params["wo"], ("expert", "expert_mlp", "embed_fsdp"))
    a = jax.nn.silu(jnp.einsum("ecd,edf->ecf", xs, wi))
    u = jnp.einsum("ecd,edf->ecf", xs, wu)
    ys = jnp.einsum("ecf,efd->ecd", a * u, wo)                   # (E,cap,D)
    ys = shard(ys, ("expert", None, "embed"))

    # --- combine: gather back, weight by gate, sum over k ---
    ys_flat = jnp.concatenate(
        [ys.reshape(E * cap, D), jnp.zeros((1, D), ys.dtype)], axis=0)
    # replicated gather for the same partitioner reason as the scatter
    ys_flat = shard(ys_flat, (None, "embed"))
    slot_of_sorted = jnp.where(keep, dest, E * cap)
    # invert the sort: slot of flat (token,k) pair j is slot_of_sorted[rank_j]
    inv = jnp.zeros((T * K,), jnp.int32).at[sort_idx].set(
        jnp.arange(T * K, dtype=jnp.int32))
    slot = slot_of_sorted[inv].reshape(T, K)
    contrib = ys_flat[slot]                                      # (T,K,D)
    yf = jnp.einsum("tkd,tk->td", contrib.astype(jnp.float32),
                    gate_vals).astype(x.dtype)
    y = yf.reshape(B, S, D)

    if "dense" in params:  # arctic dense residual
        dp = params["dense"]
        a = jax.nn.silu(jnp.einsum("bsd,df->bsf", h, dp["wi"]))
        u = jnp.einsum("bsd,df->bsf", h, dp["wu"])
        y = y + jnp.einsum("bsf,fd->bsd", a * u, dp["wo"])

    return shard(y, ("batch", "seq", "embed")), aux_loss


# ---------------------------------------------------------------------------
# DeepSeekMoE (DeepSeek-V3 routing, cfg.router == "sigmoid")
# ---------------------------------------------------------------------------

def deepseek_moe_init(key: Array, cfg) -> PyTree:
    """Router over all cfg.num_experts (f32) with its selection-only
    correction bias (zeros: no load-driven update is run), the held
    experts' gated FFNs, and the shared experts as one gated MLP."""
    d, f, E, G = cfg.d_model, cfg.d_ff, cfg.num_experts, cfg.held_experts
    fs = cfg.shared_experts * f
    dt = cdtype(cfg)
    ks = jax.random.split(key, 7)
    p = {
        "norm": rmsnorm_init(d),
        "router": dense_init(ks[0], (d, E), dtype=jnp.float32),
        "bias": jnp.zeros((E,), jnp.float32),
        "wi": dense_init(ks[1], (G, d, f), in_axis=1, dtype=dt),
        "wu": dense_init(ks[2], (G, d, f), in_axis=1, dtype=dt),
        "wo": dense_init(ks[3], (G, f, d), in_axis=1, dtype=dt),
    }
    if fs:
        p["shared"] = {"wi": dense_init(ks[4], (d, fs), dtype=dt),
                       "wu": dense_init(ks[5], (d, fs), dtype=dt),
                       "wo": dense_init(ks[6], (fs, d), dtype=dt)}
    return p


def route_sigmoid(h: Array, params: PyTree, cfg) -> tuple[Array, Array, Array]:
    """DeepSeek-V3 `noaux_tc` routing with one group. h: (T, D). Returns
    (scores (T, E) f32, expert ids (T, K), weights (T, K) f32): the top K
    of sigmoid(h W_r) + bias are selected, weighted by their scores
    without the bias, normalised to sum 1 and scaled."""
    s = jax.nn.sigmoid(jnp.dot(h.astype(jnp.float32), params["router"],
                               precision=jax.lax.Precision.HIGHEST))
    _, idx = jax.lax.top_k(s + params["bias"], cfg.experts_per_token)
    w = jnp.take_along_axis(s, idx, axis=-1)
    w = w / (w.sum(-1, keepdims=True) + 1e-20) * cfg.routed_scaling_factor
    return s, idx, w


def sequence_balance_loss(s: Array, idx: Array, E: int) -> Array:
    """DeepSeek-V3's sequence-wise balance loss sum_i f_i P_i, the mean
    over sequences. s: (B, S, E) scores; idx: (B, S, K) selected ids."""
    _, S, K = idx.shape
    counts = jax.nn.one_hot(idx, E, dtype=jnp.float32).sum((1, 2))  # (B, E)
    f = counts * E / (K * S)
    p = (s / s.sum(-1, keepdims=True)).mean(1)
    return jnp.mean(jnp.sum(f * p, -1))


def deepseek_moe_apply(params: PyTree, x: Array, cfg) -> tuple[Array, Array]:
    """Returns (y, balance loss). x: (B, S, D).

    Routes every token over all experts and returns the part of the
    output that the experts held here give (plus the shared experts),
    with no pair dropped: the (token, pick) pairs are sorted by held
    expert, the pairs of experts held elsewhere last, and run through
    grouped products (`ragged_dot`) over the held experts' rows only.
    Rows outside every group are zeroed on the way in and out, as the
    grouped product leaves them undefined."""
    B, S, D = x.shape
    E, K, G = cfg.num_experts, cfg.experts_per_token, cfg.held_experts
    T = B * S
    h = rmsnorm(params["norm"], x, cfg.norm_eps)
    hf = h.reshape(T, D)
    s, idx, w = route_sigmoid(hf, params, cfg)
    aux = sequence_balance_loss(s.reshape(B, S, E), idx.reshape(B, S, K), E)

    local = idx - cfg.expert_offset
    held = (local >= 0) & (local < G)                            # (T, K)
    group = jnp.where(held, local, G).reshape(T * K)
    order = jnp.argsort(group, stable=True)
    sizes = jnp.zeros((G + 1,), jnp.int32).at[group].add(1)[:G]
    in_group = held.reshape(T * K)[order][:, None]
    xs = jnp.where(in_group, hf[order // K], 0)
    a = jax.nn.silu(jax.lax.ragged_dot(xs, params["wi"], sizes))
    u = jax.lax.ragged_dot(xs, params["wu"], sizes)
    ys = jnp.where(in_group, jax.lax.ragged_dot(a * u, params["wo"], sizes), 0)
    inv = jnp.zeros((T * K,), jnp.int32).at[order].set(
        jnp.arange(T * K, dtype=jnp.int32))
    y = jnp.einsum("tkd,tk->td", ys[inv].reshape(T, K, D).astype(jnp.float32),
                   jnp.where(held, w, 0.0))
    y = y.astype(x.dtype).reshape(B, S, D)
    if "shared" in params:
        sp = params["shared"]
        g = jax.nn.silu(jnp.einsum("bsd,df->bsf", h, sp["wi"]))
        y = y + jnp.einsum("bsf,fd->bsd",
                           g * jnp.einsum("bsd,df->bsf", h, sp["wu"]), sp["wo"])
    return y, aux
