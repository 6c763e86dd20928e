"""Fused dequant + masked-aggregate kernel (PS-side Eq. 7 decode).

The parameter server receives C packed b-bit payloads (one per worker)
plus per-block scales, a delivery mask, and per-worker weights. The
legacy path dequantizes every payload to a dense f32 reconstruction and
then aggregates — C extra (rows, 128) f32 HBM round-trips per leaf. One
grid step here reads the C packed tiles for one (BLOCK_ROWS, 128) block
straight into VMEM, dequantizes, and folds the masked aggregate (mean /
coordinate-wise median / trimmed mean — the exact `channel.receive`
math) into a single f32 output tile: reads C*b/8 bytes per element,
writes 4.

Layouts: packed is the stacked quant_pack wire format (C, rows, 128)
int8 or (C, rows/2, 128) uint8; scales (C, nb) f32; mask/weights (C, 1)
f32. The dequantized block is a (C, BLOCK_ROWS, 128) f32 VMEM value —
128 KiB per worker — so C <~ 64 fits v5e VMEM at the default block
(int4 cannot shrink the block: nibble pairing spans the 256-row quant
block). Robust aggregators additionally unroll an odd-even
transposition sorting network over the worker axis (lax.sort has no
Mosaic lowering; jnp.minimum/maximum do), so prefer C <~ 32 there.

Aggregate semantics (bit-matching comm/channel.receive at weights=1):
mean divides the (mask*weight)-weighted sum by max(sum(mask*weight),1);
median/trimmed sort the weighted values with non-delivered workers at
+inf and pick order statistics from the traced survivor count k =
mask.sum(). All-lost rounds aggregate to 0 (w_t unchanged). Order
statistics are picked by an iota mask-sum instead of dynamic indexing
(Mosaic-safe), which is value-exact: the sum adds one selected row to
zeros.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.quant_pack.quant_pack import (BLOCK_ROWS,
                                                 _unpack_nibbles)

_LANES = 128

AGGREGATORS = ("mean", "median", "trimmed_mean")

# internal two-stage mode (ops.wire_aggregate tree route): the masked
# weighted partial SUM of one worker chunk, no divide — chunk partials
# add associatively, the caller divides once by the fleet-wide weight
_TREE_MODES = AGGREGATORS + ("sum",)


def _dequant_stack(packed: jax.Array, scales: jax.Array,
                   bits: int) -> jax.Array:
    """(C, B[/2], 128) packed + (C, 1) scales -> (C, B, 128) f32.
    Identical per-element math to ref.dequant_unpack_ref (q * scale on
    the same operands), so decoded values are bit-equal to the legacy
    per-worker decode."""
    q = packed.astype(jnp.float32) if bits == 8 else _unpack_nibbles(packed)
    return q * scales[:, :, None]


def _sort_workers(vals: jax.Array) -> jax.Array:
    """Ascending sort along axis 0 (static C): odd-even transposition
    network of fully unrolled jnp.minimum/maximum compare-exchanges.
    Value-equal to jnp.sort(axis=0) — ties among equal floats are
    interchangeable (only ±0.0 ordering can differ, which no consumer
    distinguishes)."""
    rows = [vals[i] for i in range(vals.shape[0])]
    C = len(rows)
    for phase in range(C):
        for i in range(phase % 2, C - 1, 2):
            lo = jnp.minimum(rows[i], rows[i + 1])
            hi = jnp.maximum(rows[i], rows[i + 1])
            rows[i], rows[i + 1] = lo, hi
    return jnp.stack(rows, axis=0)


def _aggregate_block(d: jax.Array, mask: jax.Array, weights: jax.Array,
                     aggregator: str, trim_ratio: float,
                     sort_fn=_sort_workers) -> jax.Array:
    """Shared Eq.-7 block math: d (C, B, 128) f32 dequantized deltas,
    mask/weights (C, 1) f32 -> (B, 128) f32 aggregate. Mirrors
    channel.receive / channel._robust_receive operation-for-operation so
    outputs are bit-identical at weights=1 (the engine route)."""
    if aggregator in ("mean", "sum"):
        mw = mask * weights
        s = (mw[:, :, None] * d).sum(axis=0)
        if aggregator == "sum":     # tree partial: divide deferred
            return s
        return s / jnp.maximum(mw.sum(), 1.0)

    k = mask.sum().astype(jnp.int32)
    dw = d * weights[:, :, None]
    svals = sort_fn(jnp.where(mask[:, :, None] > 0, dw, jnp.inf))
    cidx = jax.lax.broadcasted_iota(jnp.int32, svals.shape, 0)

    def pick(j):  # order statistic j: exact (one row summed with zeros)
        return jnp.where(cidx == j, svals, 0.0).sum(axis=0)

    if aggregator == "median":
        lo = jnp.maximum(k - 1, 0) // 2
        hi = jnp.maximum(k - 1, 0) - lo
        agg = 0.5 * (pick(lo) + pick(hi))
    else:  # trimmed_mean: cut t of the k survivors from each end
        t = (trim_ratio * k.astype(jnp.float32)).astype(jnp.int32)
        t = jnp.minimum(t, jnp.maximum(k - 1, 0) // 2)
        keep = (cidx >= t) & (cidx < k - t)
        cnt = jnp.maximum((k - 2 * t).astype(jnp.float32), 1.0)
        agg = jnp.where(keep, svals, 0.0).sum(axis=0) / cnt
    return jnp.where(k > 0, agg, 0.0)    # all-lost round: w_t unchanged


def _make_agg_kernel(bits: int, aggregator: str, trim_ratio: float):
    def kernel(mask_ref, w_ref, scales_ref, packed_ref, out_ref):
        d = _dequant_stack(packed_ref[...], scales_ref[...], bits)
        out_ref[...] = _aggregate_block(d, mask_ref[...], w_ref[...],
                                        aggregator, trim_ratio)

    return kernel


@functools.partial(jax.jit, static_argnames=("bits", "aggregator",
                                             "trim_ratio", "interpret",
                                             "block_rows"))
def wire_agg_2d(packed: jax.Array, scales: jax.Array, mask: jax.Array,
                weights: jax.Array, *, bits: int = 8,
                aggregator: str = "mean", trim_ratio: float = 0.1,
                interpret: bool = True,
                block_rows: int = BLOCK_ROWS) -> jax.Array:
    """Core pallas_call on stacked wire payloads.

    packed: (C, rows, 128) int8 or (C, rows/2, 128) uint8;
    scales: (C, rows/block_rows) f32; mask, weights: (C, 1) f32.
    Returns the (rows, 128) f32 aggregate delta.
    """
    C = packed.shape[0]
    lanes = packed.shape[2]
    rows = packed.shape[1] * (2 if bits == 4 else 1)
    assert lanes == _LANES and rows % block_rows == 0, packed.shape
    assert bits in (8, 4), bits
    assert aggregator in _TREE_MODES, aggregator
    nb = rows // block_rows
    assert scales.shape == (C, nb), (scales.shape, C, nb)
    assert mask.shape == weights.shape == (C, 1), (mask.shape,
                                                   weights.shape)
    pb = block_rows // (2 if bits == 4 else 1)
    # scales go in block-major as (nb, C, 1): block i's (C, 1) column then
    # spans the array's full last two dims, which Mosaic tiles for any nb
    # (a (C, 1) block of the (C, nb) array only compiles when nb == 1)
    return pl.pallas_call(
        _make_agg_kernel(bits, aggregator, trim_ratio),
        grid=(nb,),
        in_specs=[pl.BlockSpec((C, 1), lambda i: (0, 0)),      # mask
                  pl.BlockSpec((C, 1), lambda i: (0, 0)),      # weights
                  pl.BlockSpec((None, C, 1), lambda i: (i, 0, 0)),
                  pl.BlockSpec((C, pb, lanes), lambda i: (0, i, 0))],
        out_specs=pl.BlockSpec((block_rows, lanes), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, lanes), jnp.float32),
        interpret=interpret,
    )(mask, weights, scales.T.reshape(nb, C, 1), packed)
