"""Shared transformer layers, pure JAX (init/apply pairs over dict params).

Conventions
-----------
* params are nested dicts of jnp arrays; init fns take (key, cfg) and
  return params; apply fns take (params, x, ...) and are shape-polymorphic.
* activations: x is (B, S, D). Attention internals are (B, S, H, hd).
* logical sharding axes are annotated via repro.sharding.shard (no-op
  without an active mesh).
* dtype policy: matmuls run in the config dtype (bf16 on TPU), softmax,
  norms and recurrent states in fp32.
"""
from __future__ import annotations

import math
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.sharding import shard

Array = jax.Array
PyTree = Any


def cdtype(cfg) -> jnp.dtype:
    return jnp.dtype(cfg.dtype)


def dense_init(key: Array, shape: tuple[int, ...], in_axis: int = 0,
               dtype=jnp.float32) -> Array:
    fan_in = shape[in_axis]
    return (jax.random.normal(key, shape) / math.sqrt(fan_in)).astype(dtype)


# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------

def rmsnorm_init(d: int, dtype=jnp.float32) -> PyTree:
    return {"scale": jnp.ones((d,), dtype)}


def rmsnorm(params: PyTree, x: Array, eps: float = 1e-6) -> Array:
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps)
    return (y * params["scale"].astype(jnp.float32)).astype(x.dtype)


# ---------------------------------------------------------------------------
# embeddings
# ---------------------------------------------------------------------------

def embedding_init(key: Array, vocab: int, d: int, dtype=jnp.float32) -> PyTree:
    tbl = jax.random.normal(key, (vocab, d)) * 0.01
    return {"table": tbl.astype(dtype)}


def embed(params: PyTree, tokens: Array) -> Array:
    tbl = shard(params["table"], ("vocab", "embed"))
    out = jnp.take(tbl, tokens, axis=0)
    return shard(out, ("batch", "seq", "embed"))


def unembed(params: PyTree, x: Array) -> Array:
    """Tied output head: (B,S,D) @ (V,D)^T -> (B,S,V)."""
    tbl = shard(params["table"], ("vocab", "embed"))
    logits = jnp.einsum("bsd,vd->bsv", x, tbl)
    return shard(logits, ("batch", "seq", "vocab"))


# ---------------------------------------------------------------------------
# rotary position embedding
# ---------------------------------------------------------------------------

def rope(x: Array, positions: Array, theta: float) -> Array:
    """x: (B, S, H, hd); positions: (B, S) or (S,). Applies RoPE in fp32."""
    hd = x.shape[-1]
    half = hd // 2
    freqs = jnp.exp(-jnp.arange(0, half, dtype=jnp.float32) *
                    (math.log(theta) / half))
    if positions.ndim == 1:
        positions = positions[None, :]
    angles = positions[..., None].astype(jnp.float32) * freqs  # (B,S,half)
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = x[..., :half].astype(jnp.float32), x[..., half:].astype(jnp.float32)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# attention (GQA; full-causal / sliding-window; train, prefill, decode)
# ---------------------------------------------------------------------------

def attention_init(key: Array, cfg, cross: bool = False) -> PyTree:
    d, hd = cfg.d_model, cfg.resolved_head_dim
    h, k = cfg.num_heads, cfg.num_kv_heads
    dt = cdtype(cfg)
    ks = jax.random.split(key, 5)
    return {
        "norm": rmsnorm_init(d),
        "wq": dense_init(ks[0], (d, h, hd), dtype=dt),
        "wk": dense_init(ks[1], (d, k, hd), dtype=dt),
        "wv": dense_init(ks[2], (d, k, hd), dtype=dt),
        "wo": dense_init(ks[3], (h, hd, d), dtype=dt),
    }


def _shard_qkv(q, k, v):
    # act_* names: activation head sharding is decoupled from the WEIGHT
    # head sharding so serving can seq-shard the KV cache (act heads
    # replicated) while keeping projection weights TP-sharded
    q = shard(q, ("batch", "seq", "act_heads", "head_dim"))
    k = shard(k, ("batch", "seq", "act_kv_heads", "head_dim"))
    v = shard(v, ("batch", "seq", "act_kv_heads", "head_dim"))
    return q, k, v


def _repeat_kv(k: Array, q_per_kv: int) -> Array:
    """(B,S,K,hd) -> (B,S,K*q_per_kv,hd) by repetition (GQA)."""
    if q_per_kv == 1:
        return k
    return jnp.repeat(k, q_per_kv, axis=2)


def chunked_attention(q: Array, k: Array, v: Array, *, causal: bool,
                      window: int = 0, q_offset: Array | int = 0,
                      kv_len: Optional[Array] = None,
                      q_chunk: int = 512, kv_chunk: int = 1024) -> Array:
    """Memory-bounded multi-head attention (flash-style, pure JAX).

    q/k: (B, Sq|Sk, H, hd); v: (B, Sk, H, hd_v) (kv already GQA-repeated;
    the value width may differ from the query/key width, as in MLA).
    The scores are scaled by hd ** -0.5.
    Scans over kv chunks with an online softmax so the (Sq, Sk) score
    matrix is never materialized beyond (q_chunk, kv_chunk) tiles. This is
    the XLA-lowered twin of kernels/flash_attention (same tiling), used
    whenever we need a CPU-lowerable path (dry-run) — see DESIGN.md §5.

    window > 0 restricts to a sliding causal window. kv_len masks out
    cache positions >= kv_len (decode with a partially filled cache).
    """
    B, Sq, H, hd = q.shape
    Sk, hd_v = k.shape[1], v.shape[-1]
    scale = 1.0 / math.sqrt(hd)
    orig_dtype = q.dtype

    q_chunk = min(q_chunk, Sq)
    kv_chunk = min(kv_chunk, Sk)
    nq = -(-Sq // q_chunk)
    nk = -(-Sk // kv_chunk)
    # pad to chunk multiples
    q = jnp.pad(q, ((0, 0), (0, nq * q_chunk - Sq), (0, 0), (0, 0)))
    k = jnp.pad(k, ((0, 0), (0, nk * kv_chunk - Sk), (0, 0), (0, 0)))
    v = jnp.pad(v, ((0, 0), (0, nk * kv_chunk - Sk), (0, 0), (0, 0)))

    q = q.reshape(B, nq, q_chunk, H, hd)
    k = k.reshape(B, nk, kv_chunk, H, hd)
    v = v.reshape(B, nk, kv_chunk, H, hd_v)

    q_pos = (jnp.arange(nq * q_chunk).reshape(nq, q_chunk) +
             jnp.asarray(q_offset))
    k_pos = jnp.arange(nk * kv_chunk).reshape(nk, kv_chunk)
    k_valid = k_pos < (Sk if kv_len is None else kv_len)

    def q_block(qi_and_pos):
        qi, qpos = qi_and_pos  # (B,qc,H,hd), (qc,)

        def kv_block(carry, kj):
            acc, m, l = carry
            kjv, vjv, kpos, kval = kj
            s = jnp.einsum("bqhd,bkhd->bhqk", qi, kjv,
                           preferred_element_type=jnp.float32) * scale
            mask = kval[None, :]
            if causal:
                mask = mask & (kpos[None, :] <= qpos[:, None])
            if window:
                mask = mask & (kpos[None, :] > qpos[:, None] - window)
            s = jnp.where(mask[None, None], s, -jnp.inf)
            m_new = jnp.maximum(m, s.max(axis=-1))
            # guard fully-masked rows
            m_safe = jnp.where(jnp.isneginf(m_new), 0.0, m_new)
            p = jnp.exp(s - m_safe[..., None])
            p = jnp.where(mask[None, None], p, 0.0)
            alpha = jnp.where(jnp.isneginf(m), 0.0, jnp.exp(m - m_safe))
            l_new = alpha * l + p.sum(axis=-1)
            acc_new = (acc * alpha[..., None] +
                       jnp.einsum("bhqk,bkhd->bhqd", p,
                                  vjv.astype(jnp.float32)))
            return (acc_new, m_new, l_new), None

        init = (jnp.zeros((B, H, q_chunk, hd_v), jnp.float32),
                jnp.full((B, H, q_chunk), -jnp.inf),
                jnp.zeros((B, H, q_chunk), jnp.float32))
        (acc, m, l), _ = jax.lax.scan(
            kv_block, init,
            (k.swapaxes(0, 1), v.swapaxes(0, 1), k_pos, k_valid))
        out = acc / jnp.maximum(l[..., None], 1e-30)
        return out.swapaxes(1, 2)  # (B,qc,H,hd_v)

    # rematerialised per q chunk: the backward pass holds one chunk's
    # (q_chunk, Sk) tiles at a time, never the whole (Sq, Sk) scores
    out = jax.lax.map(jax.checkpoint(q_block),
                      (q.swapaxes(0, 1), q_pos))  # (nq,B,qc,H,hd_v)
    out = out.swapaxes(0, 1).reshape(B, nq * q_chunk, H, hd_v)[:, :Sq]
    return out.astype(orig_dtype)


def attention_apply(params: PyTree, x: Array, cfg, *, mode: str,
                    layer_cache: Optional[PyTree] = None,
                    positions: Optional[Array] = None,
                    window: int = 0,
                    memory_kv: Optional[tuple[Array, Array]] = None,
                    attn_impl: str = "chunked",
                    ) -> tuple[Array, Optional[PyTree]]:
    """One attention sub-block (pre-norm, residual added by caller).

    mode: "train" | "prefill" | "decode" | "encode" (bidirectional).
    For cross-attention pass memory_kv=(k_mem, v_mem) and mode="train"/
    "decode"; q comes from x, no cache update.

    layer_cache (self-attn decode/prefill): dict with
      k, v: (B, S_cache, K, hd)   (S_cache = window for swa ring buffer)
      pos:  () int32 — number of tokens already written.
    Returns (out, new_cache).
    """
    B, S, D = x.shape
    h = rmsnorm(params["norm"], x, cfg.norm_eps)
    q = jnp.einsum("bsd,dhk->bshk", h, params["wq"])
    if memory_kv is None:
        k = jnp.einsum("bsd,dhk->bshk", h, params["wk"])
        v = jnp.einsum("bsd,dhk->bshk", h, params["wv"])
        q, k, v = _shard_qkv(q, k, v)
    else:
        k, v = memory_kv

    if positions is None:
        base = 0 if layer_cache is None else layer_cache["pos"]
        positions = base + jnp.arange(S)[None, :]

    if memory_kv is None:
        q = rope(q, positions, cfg.rope_theta)
        k = rope(k, positions, cfg.rope_theta)

    new_cache = None
    if mode in ("train", "encode") or (mode == "prefill" and layer_cache is None):
        pass  # use k, v as computed
    elif mode == "prefill":
        # write the whole sequence into the cache (ring for swa)
        cache_len = layer_cache["k"].shape[1]
        if window and cache_len < S:
            # keep the last `cache_len` tokens
            kk, vv = k[:, -cache_len:], v[:, -cache_len:]
            idx = (positions[0, -cache_len:]) % cache_len
        else:
            kk, vv = k, v
            idx = positions[0, :] % cache_len
        ck = layer_cache["k"].at[:, idx].set(kk.astype(layer_cache["k"].dtype))
        cv = layer_cache["v"].at[:, idx].set(vv.astype(layer_cache["v"].dtype))
        new_cache = {"k": ck, "v": cv, "pos": layer_cache["pos"] + S}
    elif mode == "decode" and memory_kv is None:
        cache_len = layer_cache["k"].shape[1]
        idx = positions[0, :] % cache_len
        ck = layer_cache["k"].at[:, idx].set(k.astype(layer_cache["k"].dtype))
        cv = layer_cache["v"].at[:, idx].set(v.astype(layer_cache["v"].dtype))
        ck = shard(ck, ("cache_batch", "cache_seq", "act_kv_heads",
                        "head_dim"))
        cv = shard(cv, ("cache_batch", "cache_seq", "act_kv_heads",
                        "head_dim"))
        new_cache = {"k": ck, "v": cv, "pos": layer_cache["pos"] + S}
        k, v = ck, cv  # same dtype as q (bf16) — no cast, nothing to hoist

    qkv_ratio = cfg.num_heads // k.shape[2]

    if mode == "decode" and memory_kv is None:
        # One-token attention over the cache. Grouped-GQA einsum: no
        # _repeat_kv (which materializes q_per_kv copies of the cache)
        # and no f32 cast of v (XLA hoists that cast out of the layer
        # scan into an f32 copy of the WHOLE stacked cache — +12 GiB on
        # deepseek-67b decode_32k, EXPERIMENTS.md §Perf iteration 4).
        cache_len = k.shape[1]
        kv_pos = jnp.arange(cache_len)
        cur = layer_cache["pos"] + S - 1  # position of the new token
        if window and cache_len <= window:
            # ring buffer: entry j holds absolute position p iff p % len == j
            # valid if written (p<=cur) and within window
            valid = kv_pos <= (cur % cache_len)
            wrapped = cur >= cache_len
            valid = valid | wrapped  # after wrap, all slots hold valid entries
            scores_mask = valid
        else:
            scores_mask = kv_pos <= cur
            if window:
                abs_pos = kv_pos
                scores_mask = scores_mask & (abs_pos > cur - window)
        B, _, H, hd = q.shape
        K = k.shape[2]
        qg = q.reshape(B, S, K, H // K, hd)
        s = jnp.einsum("bskgh,btkh->bkgst", qg, k,
                       preferred_element_type=jnp.float32)
        s = s / math.sqrt(hd)
        s = jnp.where(scores_mask[None, None, None, None, :], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        out = jnp.einsum("bkgst,btkh->bskgh", p.astype(v.dtype), v,
                         preferred_element_type=jnp.float32)
        out = out.reshape(B, S, H, hd).astype(x.dtype)
    else:
        causal = mode != "encode" and memory_kv is None
        q_off = 0
        if mode == "decode" and memory_kv is not None:
            q_off = 0  # cross-attn: no causal mask anyway
        out = chunked_attention(q, _repeat_kv(k, qkv_ratio),
                                _repeat_kv(v, qkv_ratio), causal=causal,
                                window=window, q_offset=q_off)

    out = shard(out, ("batch", "seq", "act_heads", "head_dim"))
    y = jnp.einsum("bshk,hkd->bsd", out, params["wo"])
    return shard(y, ("batch", "seq", "embed")), new_cache


# ---------------------------------------------------------------------------
# multi-head latent attention (DeepSeek-V3 MLA, no query compression)
# ---------------------------------------------------------------------------

def mla_init(key: Array, cfg) -> PyTree:
    """MLA weights for the heads held here (cfg.num_heads): per head a
    query of qk_nope + qk_rope, and from the shared compressed KV
    (kv_lora_rank, normed) a key part and a value; the KV down-projection
    (with the one RoPE key shared by all heads) and its norm are whole."""
    d, h, r = cfg.d_model, cfg.num_heads, cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    dt = cdtype(cfg)
    ks = jax.random.split(key, 4)
    return {
        "norm": rmsnorm_init(d),
        "wq": dense_init(ks[0], (d, h, dn + dr), dtype=dt),
        "wkva": dense_init(ks[1], (d, r + dr), dtype=dt),
        "kv_norm": rmsnorm_init(r),
        "wkvb": dense_init(ks[2], (r, h, dn + dv), dtype=dt),
        "wo": dense_init(ks[3], (h * dv, d), dtype=dt).reshape(h, dv, d),
    }


def rope_interleaved(x: Array, positions: Array, theta: float) -> Array:
    """RoPE on adjacent pairs (x[2i], x[2i+1]) (DeepSeek-V3's
    `rope_interleave`): the pairs are gathered into halves, as the
    published code does, and rotated as `rope` rotates halves."""
    return rope(jnp.concatenate([x[..., 0::2], x[..., 1::2]], axis=-1),
                positions, theta)


def mla_apply(params: PyTree, x: Array, cfg) -> Array:
    """One causal MLA sub-block over the whole sequence (pre-norm,
    residual added by caller); the heads held here give their part of
    the output projection."""
    B, S, _ = x.shape
    h_, r = cfg.num_heads, cfg.kv_lora_rank
    dn, dr = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    h = rmsnorm(params["norm"], x, cfg.norm_eps)
    q = jnp.einsum("bsd,dhk->bshk", h, params["wq"])
    kva = jnp.einsum("bsd,dk->bsk", h, params["wkva"])
    c = rmsnorm(params["kv_norm"], kva[..., :r], cfg.norm_eps)
    kv = jnp.einsum("bsr,rhk->bshk", c, params["wkvb"])
    positions = jnp.arange(S)
    q_pe = rope_interleaved(q[..., dn:], positions, cfg.rope_theta)
    k_pe = rope_interleaved(kva[..., None, r:], positions, cfg.rope_theta)
    q = jnp.concatenate([q[..., :dn], q_pe], axis=-1)
    k = jnp.concatenate([kv[..., :dn],
                         jnp.broadcast_to(k_pe, (B, S, h_, dr))], axis=-1)
    out = chunked_attention(q, k, kv[..., dn:], causal=True)
    return jnp.einsum("bshk,hkd->bsd", out, params["wo"])


def init_attention_cache(cfg, batch: int, cache_len: int, window: int,
                         dtype) -> PyTree:
    k_heads, hd = cfg.num_kv_heads, cfg.resolved_head_dim
    size = min(cache_len, window) if window else cache_len
    return {
        "k": jnp.zeros((batch, size, k_heads, hd), dtype),
        "v": jnp.zeros((batch, size, k_heads, hd), dtype),
        "pos": jnp.zeros((), jnp.int32),
    }


# ---------------------------------------------------------------------------
# gated MLP
# ---------------------------------------------------------------------------

def mlp_init(key: Array, d: int, d_ff: int, cfg) -> PyTree:
    dt = cdtype(cfg)
    ks = jax.random.split(key, 3)
    return {
        "norm": rmsnorm_init(d),
        "wi": dense_init(ks[0], (d, d_ff), dtype=dt),    # gate
        "wu": dense_init(ks[1], (d, d_ff), dtype=dt),    # up
        "wo": dense_init(ks[2], (d_ff, d), dtype=dt),    # down
    }


def mlp_apply(params: PyTree, x: Array, cfg) -> Array:
    h = rmsnorm(params["norm"], x, cfg.norm_eps)
    wi = shard(params["wi"], ("embed_fsdp", "mlp"))
    wu = shard(params["wu"], ("embed_fsdp", "mlp"))
    wo = shard(params["wo"], ("mlp", "embed_fsdp"))
    a = jax.nn.silu(jnp.einsum("bsd,df->bsf", h, wi))
    b = jnp.einsum("bsd,df->bsf", h, wu)
    y = jnp.einsum("bsf,fd->bsd", a * b, wo)
    return shard(y, ("batch", "seq", "embed"))
