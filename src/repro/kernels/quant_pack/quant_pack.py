"""Fused stochastic quantize-and-pack kernels (uplink wire format).

The int8/int4 uplink compressors (`repro/comm/compress.py`) reduce a
worker's round delta to b-bit integers plus one f32 scale per block.
Unfused, XLA materializes |x|, the block max, the scaled tensor, the
random field, and the rounded tensor as separate HBM round-trips; the
payload is produced in one pass here: each grid step reads one
(BLOCK_ROWS, 128) f32 tile from VMEM and emits the packed integer tile
plus its scale (read N f32 words, write N*b/32 + 1).

Three kernels share the block math:

  quant_pack_2d     quantize + pack              (x -> packed, scales)
  quant_pack_ef_2d  quantize + pack + error-feedback update in ONE pass
                    (delta, residual -> packed, scales, new residual =
                    acc - dequant(q)) — the uplink hot loop, no dense
                    f32 round-trip between compression and EF
  dequant_unpack_2d packed, scales -> dense f32  (the decode half; the
                    PS-side aggregate fuses this further, see
                    kernels/wire_agg)

Layout: the flattened parameter vector is tiled to (rows, 128) like
`pso_update`. int8 packs 1:1 into an int8 tile; int4 packs two rows per
byte — row r of the output holds rows r (low nibble) and r + B/2 (high
nibble) of the block — keeping the 128-lane minor dim intact for TPU
tiling (nibble-within-lane packing would shrink the minor dim to 64).

Stochastic rounding uses a counter-based integer hash (`block_uniform`)
seeded per call: pure uint32 jnp arithmetic, so the same bits are
produced by the compiled Mosaic kernel, interpret mode, and the ref.py
oracle — exact-equality tests and bit-identical CPU/TPU simulation.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BLOCK_ROWS = 256          # (256, 128) f32 tile = 128 KiB VMEM per operand
_LANES = 128

QMAX = {8: 127.0, 4: 7.0}


def block_uniform(seed: jax.Array, block_idx: jax.Array,
                  shape: tuple[int, int]) -> jax.Array:
    """U[0,1) field for one block: a splitmix-style uint32 hash of
    (seed, block, row, lane). Part of the wire spec — ref.py reuses it so
    packed payloads are bit-identical across backends."""
    r = jax.lax.broadcasted_iota(jnp.uint32, shape, 0)
    c = jax.lax.broadcasted_iota(jnp.uint32, shape, 1)
    h = (seed.astype(jnp.uint32) * jnp.uint32(2654435761)
         + block_idx.astype(jnp.uint32) * jnp.uint32(976686449)
         + r * jnp.uint32(1664525) + c * jnp.uint32(22695477))
    h = h ^ (h >> 16)
    h = h * jnp.uint32(0x7FEB352D)
    h = h ^ (h >> 15)
    h = h * jnp.uint32(0x846CA68B)
    h = h ^ (h >> 16)
    # via int32: Mosaic has no uint32 -> f32 cast; exact below 2^24
    return ((h >> 8).astype(jnp.int32).astype(jnp.float32)
            * jnp.float32(1.0 / (1 << 24)))


def _quantize_block(x: jax.Array, seed: jax.Array, block_idx: jax.Array,
                    qmax: float) -> tuple[jax.Array, jax.Array]:
    """Shared math: per-block scale + unbiased stochastic rounding.
    Returns (q f32 in [-qmax, qmax], scale f32).

    scale is amax * (1/qmax), NOT amax / qmax: XLA strength-reduces a
    divide-by-constant to a reciprocal multiply but interpret mode does
    not, and the 1-ulp drift would break kernel/ref bit-equality."""
    amax = jnp.max(jnp.abs(x))
    scale = jnp.where(amax > 0.0, amax * jnp.float32(1.0 / qmax), 1.0)
    u = block_uniform(seed, block_idx, x.shape)
    q = jnp.clip(jnp.floor(x / scale + u), -qmax, qmax)
    return q, scale


def _pack_nibbles(q: jax.Array) -> jax.Array:
    """(..., B, 128) integral f32 in [-7, 7] -> (..., B/2, 128) uint8.
    Output row r holds rows r (low nibble) and r + B/2 (high nibble).

    The bit ops run in int32 and cast to uint8 only at the end: Mosaic
    has no uint8 shift/or lowering (sub-word vectors only support
    widen/narrow), so the original uint8 formulation ran in interpret
    mode only. Values are exact small ints, so the int32 route is
    bit-identical."""
    half = q.shape[-2] // 2
    biased = (q + 8.0).astype(jnp.int32)         # [-7,7] -> [1,15]
    packed = biased[..., :half, :] | (biased[..., half:, :] << 4)
    return packed.astype(jnp.uint8)


def _unpack_nibbles(packed: jax.Array) -> jax.Array:
    """Inverse of _pack_nibbles: (..., B/2, 128) uint8 -> (..., B, 128)
    f32 in [-7, 7]. Same int32 discipline (widen first, then bit ops)."""
    p = packed.astype(jnp.int32)
    lo = ((p & 0xF) - 8).astype(jnp.float32)
    hi = ((p >> 4) - 8).astype(jnp.float32)
    return jnp.concatenate([lo, hi], axis=-2)


# Seed and scales live in SMEM as (1, 1) and (1, nb) arrays: Mosaic
# stores no scalars to VMEM, and a rank-1 block cannot be tiled once
# vmap over workers prepends a squeezed dim. With a leading (1, ...)
# the batched block's last two dims still equal the array's, so the
# engines' vmap compiles as one extra grid axis. The scales block stays
# resident across the row grid and is written back once per call.
_SEED_SPEC = pl.BlockSpec((1, 1), lambda i: (0, 0), memory_space=pltpu.SMEM)


def _scales_spec(nb: int) -> pl.BlockSpec:
    return pl.BlockSpec((1, nb), lambda i: (0, 0), memory_space=pltpu.SMEM)


def _seed_arg(seed: jax.Array) -> jax.Array:
    return jnp.asarray(seed, jnp.int32).reshape(1, 1)


def _kernel_int8(seed_ref, x_ref, q_ref, scale_ref):
    i = pl.program_id(0)
    q, scale = _quantize_block(x_ref[...], seed_ref[0, 0], i, QMAX[8])
    q_ref[...] = q.astype(jnp.int8)
    scale_ref[0, i] = scale


def _kernel_int4(seed_ref, x_ref, q_ref, scale_ref):
    i = pl.program_id(0)
    q, scale = _quantize_block(x_ref[...], seed_ref[0, 0], i, QMAX[4])
    q_ref[...] = _pack_nibbles(q)
    scale_ref[0, i] = scale


@functools.partial(jax.jit,
                   static_argnames=("bits", "interpret", "block_rows"))
def quant_pack_2d(x: jax.Array, seed: jax.Array, *, bits: int = 8,
                  interpret: bool = True,
                  block_rows: int = BLOCK_ROWS
                  ) -> tuple[jax.Array, jax.Array]:
    """Core pallas_call on a (rows, 128) f32 layout.

    Returns (packed, scales): packed is int8 (rows, 128) for bits=8 or
    uint8 (rows//2, 128) for bits=4; scales is (rows // block_rows,) f32.
    """
    rows, lanes = x.shape
    assert lanes == _LANES and rows % block_rows == 0, (rows, lanes)
    assert bits in (8, 4), bits
    nb = rows // block_rows
    tile = pl.BlockSpec((block_rows, lanes), lambda i: (i, 0))
    if bits == 8:
        kernel = _kernel_int8
        q_spec = tile
        q_shape = jax.ShapeDtypeStruct((rows, lanes), jnp.int8)
    else:
        kernel = _kernel_int4
        q_spec = pl.BlockSpec((block_rows // 2, lanes), lambda i: (i, 0))
        q_shape = jax.ShapeDtypeStruct((rows // 2, lanes), jnp.uint8)
    packed, scales = pl.pallas_call(
        kernel,
        grid=(nb,),
        in_specs=[_SEED_SPEC, tile],
        out_specs=(q_spec, _scales_spec(nb)),
        out_shape=(q_shape, jax.ShapeDtypeStruct((1, nb), jnp.float32)),
        interpret=interpret,
    )(_seed_arg(seed), x)
    return packed, scales.reshape(nb)


def _make_ef_kernel(bits: int):
    qmax = QMAX[bits]

    def kernel(seed_ref, x_ref, r_ref, q_ref, scale_ref, res_ref):
        i = pl.program_id(0)
        acc = x_ref[...] + r_ref[...]            # EF carry folded in VMEM
        q, scale = _quantize_block(acc, seed_ref[0, 0], i, qmax)
        q_ref[...] = q.astype(jnp.int8) if bits == 8 else _pack_nibbles(q)
        scale_ref[0, i] = scale
        # q is exactly what the receiver unpacks (the int round trip is
        # lossless), so acc - q*scale IS acc - dequant(packed) bit-for-bit
        res_ref[...] = acc - q * scale

    return kernel


@functools.partial(jax.jit,
                   static_argnames=("bits", "interpret", "block_rows"))
def quant_pack_ef_2d(x: jax.Array, residual: jax.Array, seed: jax.Array, *,
                     bits: int = 8, interpret: bool = True,
                     block_rows: int = BLOCK_ROWS
                     ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Fused uplink pass on (rows, 128) f32 layouts: one grid step reads
    a delta tile + its error-feedback residual tile and emits the packed
    wire tile, the block scale, and the NEW residual tile — the legacy
    compress -> dequant -> subtract chain without the dense f32
    round-trip (reads 8 bytes/elem, writes 4 + b/8 instead of the
    unfused ~36 + b/4; see docs/kernels.md).

    Returns (packed, scales, new_residual); packed/scales exactly as
    `quant_pack_2d(x + residual, seed)`, new_residual f32 like x."""
    rows, lanes = x.shape
    assert x.shape == residual.shape, (x.shape, residual.shape)
    assert lanes == _LANES and rows % block_rows == 0, (rows, lanes)
    assert bits in (8, 4), bits
    nb = rows // block_rows
    tile = pl.BlockSpec((block_rows, lanes), lambda i: (i, 0))
    if bits == 8:
        q_spec = tile
        q_shape = jax.ShapeDtypeStruct((rows, lanes), jnp.int8)
    else:
        q_spec = pl.BlockSpec((block_rows // 2, lanes), lambda i: (i, 0))
        q_shape = jax.ShapeDtypeStruct((rows // 2, lanes), jnp.uint8)
    packed, scales, res = pl.pallas_call(
        _make_ef_kernel(bits),
        grid=(nb,),
        in_specs=[_SEED_SPEC, tile, tile],
        out_specs=(q_spec, _scales_spec(nb), tile),
        out_shape=(q_shape,
                   jax.ShapeDtypeStruct((1, nb), jnp.float32),
                   jax.ShapeDtypeStruct((rows, lanes), jnp.float32)),
        interpret=interpret,
    )(_seed_arg(seed), x, residual)
    return packed, scales.reshape(nb), res


def _make_dequant_kernel(bits: int):
    def kernel(scale_ref, q_ref, x_ref):
        q = (q_ref[...].astype(jnp.float32) if bits == 8
             else _unpack_nibbles(q_ref[...]))
        x_ref[...] = q * scale_ref[0, pl.program_id(0)]

    return kernel


@functools.partial(jax.jit,
                   static_argnames=("bits", "interpret", "block_rows"))
def dequant_unpack_2d(packed: jax.Array, scales: jax.Array, *,
                      bits: int = 8, interpret: bool = True,
                      block_rows: int = BLOCK_ROWS) -> jax.Array:
    """Decode kernel: packed (rows, 128) int8 / (rows/2, 128) uint8 plus
    per-block scales -> dense (rows, 128) f32. Inverse of the pack half
    of quant_pack_2d / quant_pack_ef_2d."""
    lanes = packed.shape[1]
    rows = packed.shape[0] * (2 if bits == 4 else 1)
    assert lanes == _LANES and rows % block_rows == 0, packed.shape
    assert bits in (8, 4), bits
    nb = rows // block_rows
    pb = block_rows // (2 if bits == 4 else 1)
    return pl.pallas_call(
        _make_dequant_kernel(bits),
        grid=(nb,),
        in_specs=[_scales_spec(nb),
                  pl.BlockSpec((pb, lanes), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((block_rows, lanes), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, lanes), jnp.float32),
        interpret=interpret,
    )(scales.reshape(1, nb), packed)
