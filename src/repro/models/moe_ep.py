"""Expert-parallel MoE dispatch via shard_map + all-to-all.

GSPMD lowers the sort-based dispatch of `moe.moe_apply` (a cross-shard
scatter) as "replicate + combine-all-reduce": per-device u32/f32 buffers
of shape (T·K, d_model) and an all-reduce of the same size per MoE layer
— 7–8.75 GiB each for arctic-480b train_4k (EXPERIMENTS.md §Perf
iteration 5). The textbook expert-parallel pattern exchanges only
capacity-bounded buffers:

  1. per token-shard: route, pack tokens by destination expert shard
     into (n_shards, cap_send, D),
  2. `jax.lax.all_to_all` over the expert axis,
  3. local pack by local expert id -> (E_local, cap_local, D), run the
     expert FFN, un-pack,
  4. all-to-all back, combine with router gates at the origin.

Per-device traffic: Θ(T·K·cf·D / n) instead of Θ(T·K·D).

Everything is shape-static (GShard capacity semantics, overflow drops at
both the send and the local stage); `auto` axes (model / pod) remain
under GSPMD, so the expert-FFN f dim stays tensor-parallel inside the
manual region.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

Array = jax.Array
PyTree = Any


def _pack(ids: Array, n_bins: int, cap: int, payload: PyTree,
          valid: Array | None = None) -> tuple[PyTree, Array]:
    """Pack M items into (n_bins, cap, ...) capacity buffers.

    ids: (M,) int bin per item; payload: pytree of (M, ...) arrays.
    Returns (buffers, slot) where slot[m] = flat index bin*cap+pos of
    item m, or the sentinel n_bins*cap if dropped (overflow / ~valid).
    One argsort serves every payload leaf.
    """
    M = ids.shape[0]
    if valid is not None:
        ids = jnp.where(valid, ids, n_bins)  # sentinel bin
    sort_idx = jnp.argsort(ids)
    sorted_ids = ids[sort_idx]
    counts = jnp.zeros((n_bins + 1,), jnp.int32).at[ids].add(1)
    starts = jnp.cumsum(counts) - counts
    pos = jnp.arange(M) - starts[sorted_ids]
    keep = (pos < cap) & (sorted_ids < n_bins)
    dest_slot = jnp.where(keep, sorted_ids * cap + pos, n_bins * cap)

    def pack_leaf(x):
        buf = jnp.zeros((n_bins * cap + 1,) + x.shape[1:], x.dtype)
        buf = buf.at[dest_slot].set(x[sort_idx])
        return buf[: n_bins * cap].reshape((n_bins, cap) + x.shape[1:])

    bufs = jax.tree.map(pack_leaf, payload)
    # slot per ORIGINAL item: invert the sort
    inv = jnp.zeros((M,), jnp.int32).at[sort_idx].set(
        jnp.arange(M, dtype=jnp.int32))
    slot = dest_slot[inv]
    return bufs, slot


def moe_apply_ep(params: PyTree, h: Array, cfg, mesh,
                 axis_name: str) -> tuple[Array, Array]:
    """Expert-parallel MoE over `axis_name`. h: (B, S, D) pre-normed.
    Requires E % n_shards == 0 and B % n_shards == 0."""
    B, S, D = h.shape
    E, K = cfg.num_experts, cfg.experts_per_token
    cf = cfg.moe_capacity_factor
    n = mesh.shape[axis_name]
    E_local = E // n
    T = B * S                       # global tokens
    Tl = T // n                     # per shard
    cap_send = max(int(math.ceil(Tl * K / n * cf)), 1)
    cap_local = max(int(math.ceil(T * K / E * cf)), 1)

    def body(hb, router, wi, wu, wo):
        # hb: (B/n, S, D) local; wi/wu/wo: (E_local, d, f); router (d, E)
        hf = hb.reshape(-1, D)                                   # (Tl, D)
        logits = hf.astype(jnp.float32) @ router                 # (Tl, E)
        probs = jax.nn.softmax(logits, axis=-1)
        gate_vals, expert_idx = jax.lax.top_k(probs, K)          # (Tl, K)
        gate_vals = gate_vals / jnp.maximum(
            gate_vals.sum(-1, keepdims=True), 1e-9)

        # load-balance aux (global stats via psum)
        local_counts = jnp.zeros((E,), jnp.float32).at[
            expert_idx.reshape(-1)].add(1.0)
        dispatch_frac = jax.lax.psum(local_counts, axis_name) / (T * K)
        gate_frac = jax.lax.psum(probs.sum(axis=0), axis_name) / T
        aux = E * jnp.sum(dispatch_frac * gate_frac)

        # ---- stage 1: pack by destination expert shard ----
        flat_e = expert_idx.reshape(Tl * K)
        dest_shard = flat_e // E_local
        tok = jnp.arange(Tl * K) // K
        send, slot_send = _pack(
            dest_shard, n, cap_send,
            {"x": hf[tok], "e": flat_e.astype(jnp.int32)})
        # empty slots carry e=0 -> mark invalid with a sentinel payload
        ones, _ = _pack(dest_shard, n, cap_send,
                        {"v": jnp.ones((Tl * K,), jnp.int8)})

        # ---- all-to-all to expert owners ----
        a2a = partial(jax.lax.all_to_all, axis_name=axis_name,
                      split_axis=0, concat_axis=0, tiled=True)
        recv_x = a2a(send["x"])                 # (n*cap_send, D) tiled
        recv_e = a2a(send["e"])
        recv_v = a2a(ones["v"])
        rf = recv_x.reshape(n * cap_send, D)
        re = recv_e.reshape(n * cap_send)
        rv = recv_v.reshape(n * cap_send) > 0

        # ---- stage 2: pack by LOCAL expert id ----
        my_shard = jax.lax.axis_index(axis_name)
        local_e = re - my_shard * E_local
        xs, slot_recv = _pack(local_e, E_local, cap_local, {"x": rf},
                              valid=rv & (local_e >= 0)
                              & (local_e < E_local))
        xs = xs["x"]                                            # (El,c,D)

        # ---- expert FFN (f dim stays GSPMD-auto over "model") ----
        act = jax.nn.silu(jnp.einsum("ecd,edf->ecf", xs, wi))
        up = jnp.einsum("ecd,edf->ecf", xs, wu)
        ys = jnp.einsum("ecf,efd->ecd", act * up, wo)           # (El,c,D)

        # ---- inverse: local unpack, all-to-all back, combine ----
        ys_flat = jnp.concatenate(
            [ys.reshape(E_local * cap_local, D),
             jnp.zeros((1, D), ys.dtype)], axis=0)
        back = ys_flat[slot_recv].reshape(n * cap_send, D)
        origin = a2a(back).reshape(n * cap_send, D)
        origin = jnp.concatenate(
            [origin, jnp.zeros((1, D), origin.dtype)], axis=0)
        contrib = origin[slot_send].reshape(Tl, K, D)
        yf = jnp.einsum("tkd,tk->td", contrib.astype(jnp.float32),
                        gate_vals).astype(hb.dtype)
        return yf.reshape(hb.shape), aux

    fn = shard_map(
        body, mesh=mesh,
        in_specs=(P(axis_name, None, None),   # h: batch over expert axis
                  P(None, None),              # router replicated
                  P(axis_name, None, None),   # wi: experts over axis
                  P(axis_name, None, None),
                  P(axis_name, None, None)),
        out_specs=(P(axis_name, None, None), P()),
        # manual ONLY over the expert axis; model/pod stay GSPMD-auto
        axis_names={axis_name}, check_vma=False)
    return fn(h, params["router"],
              params["wi"], params["wu"], params["wo"])


def ep_applicable(cfg, mesh, rules) -> str | None:
    """Return the EP axis name if the shard_map dispatch applies."""
    if mesh is None or rules is None:
        return None
    if not rules.get("moe_ep", False):
        return None
    axis = rules.get("expert")
    if not isinstance(axis, str) or axis not in mesh.axis_names:
        return None
    n = mesh.shape[axis]
    if n <= 1 or cfg.num_experts % n:
        return None
    return axis
