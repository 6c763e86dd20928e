"""The wire-path kernels compile for a TPU v5e chip, as the engines call
them: vmapped over a C-worker fleet, at one paper-CNN leaf (256 rows)
and at one smollm-360m FFN leaf (960 x 2560 = 19200 rows).

Nothing runs: the chip is described, not attached, and the TPU compiler
installed with jaxlib refuses what Mosaic cannot tile or lower —
failures that interpret mode on the CPU never shows. Every compile is
checked to contain the Mosaic kernel (`tpu_custom_call`), so a silent
fallback to the jnp reference would fail here too.

The topology is described only inside the module fixture: only one
process may load libtpu at a time, and describing it at import would
make test collection differ between pytest-xdist workers.
"""
import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.quant_pack import (dequantize_unpack, quantize_pack,
                                      quantize_pack_ef)
from repro.kernels.wire_agg import wire_aggregate

C = 50                       # paper fleet (registry paper/* presets)
LEAVES = {256: (256, 128),   # one 256-row block: the nb == 1 case
          19200: (960, 2560)}  # smollm-360m FFN weight, nb == 75


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the
    # persistent cache without the chip: keep the cache out of it
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compiled_hlo(fn, *args) -> str:
    hlo = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in hlo, "no Mosaic kernel in the program"
    return hlo


@pytest.mark.parametrize("rows", sorted(LEAVES))
@pytest.mark.parametrize("bits", [8, 4])
def test_quant_pack_ef_vmapped_over_fleet(one_chip, bits, rows):
    """The uplink hot loop: rounds.uplink_packed vmaps the fused
    quantize + pack + EF pass over the C workers."""
    leaf = LEAVES[rows]
    f = jax.vmap(functools.partial(quantize_pack_ef, bits=bits,
                                   interpret=False))
    x = _sds((C, *leaf), jnp.float32, one_chip)
    _compiled_hlo(f, x, x, _sds((C,), jnp.int32, one_chip))


@pytest.mark.parametrize("rows", sorted(LEAVES))
@pytest.mark.parametrize("bits", [8, 4])
def test_quant_pack_dequant_vmapped_over_fleet(one_chip, bits, rows):
    """The dense route (compress.compress -> quant_dequant) packs and
    unpacks each worker's leaf; both kernels must be in the program."""
    leaf = LEAVES[rows]

    def round_trip(x, seed):
        packed, scales = quantize_pack(x, seed, bits=bits, interpret=False)
        return dequantize_unpack(packed, scales, x.shape, bits=bits,
                                 interpret=False)

    hlo = _compiled_hlo(jax.vmap(round_trip),
                        _sds((C, *leaf), jnp.float32, one_chip),
                        _sds((C,), jnp.int32, one_chip))
    assert hlo.count("tpu_custom_call") >= 2, "pack or unpack missing"


@pytest.mark.parametrize("rows", sorted(LEAVES))
@pytest.mark.parametrize("bits", [8, 4])
@pytest.mark.parametrize("aggregator,workers",
                         [("mean", C), ("median", 16)])
def test_wire_agg(one_chip, aggregator, workers, bits, rows):
    """The PS-side fused decode + Eq.-7 aggregate over the stacked
    payloads of one leaf (channel.receive_packed)."""
    leaf = LEAVES[rows]
    n = leaf[0] * leaf[1]
    prows = (n // 128) // (2 if bits == 4 else 1)
    f = functools.partial(wire_aggregate, shape=leaf, bits=bits,
                          aggregator=aggregator, interpret=False)
    _compiled_hlo(
        f,
        _sds((workers, prows, 128), jnp.int8 if bits == 8 else jnp.uint8,
             one_chip),
        _sds((workers, n // (256 * 128)), jnp.float32, one_chip),
        _sds((workers,), jnp.float32, one_chip))
