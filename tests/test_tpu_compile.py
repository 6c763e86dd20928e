"""The wire-path kernels compile for a TPU v5e chip, as the engines call
them: vmapped over a C-worker fleet, at one paper-CNN leaf (256 rows)
and at one smollm-360m FFN leaf (960 x 2560 = 19200 rows). The paper
fleet's local SGD compiles to a gather of whole image rows, and on a
chip those rows are the host's, bit for bit.

Without a chip nothing runs: the chip is described, not attached, and
the TPU compiler installed with jaxlib refuses what Mosaic cannot tile
or lower — failures that interpret mode on the CPU never shows. Every
kernel compile is checked to contain the Mosaic kernel
(`tpu_custom_call`), so a silent fallback to the jnp reference would
fail here too. The one test that runs (`test_minibatch_rows_on_chip`)
is skipped unless JAX's backend is a TPU.

The topology is described only inside the module fixture: only one
process may load libtpu at a time, and describing it at import would
make test collection differ between pytest-xdist workers.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import mdsl
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


LOCAL_SET = (512, 28, 28, 1)  # paper fleet: n_local 512 MNIST-like images
BATCH = 64


def test_local_sgd_gathers_whole_rows(one_chip):
    """The paper fleet's local SGD (C = 50 workers, 4 epochs of batch 64
    on the x8 CNN) draws each step's minibatch as whole 784-wide image
    rows (and single labels), and makes no shuffled copy of the epoch: a
    gather of the set as laid out for the conv moves single elements."""
    from repro.configs.paper_cnn import paper_cnn
    from repro.core import losses
    from repro.data.synthetic import MNIST_LIKE
    model = paper_cnn(MNIST_LIKE, 8)
    loss_fn = lambda p, x, y: losses.cross_entropy_loss(
        model.apply(p, x), y, MNIST_LIKE.num_classes)
    cfg = mdsl.MdslConfig(local_epochs=4, batch_size=BATCH)
    params = jax.tree.map(
        lambda a: _sds(a.shape, a.dtype, one_chip),
        jax.eval_shape(jax.vmap(model.init),
                       jax.random.split(jax.random.PRNGKey(0), C)))
    sgd = jax.vmap(lambda p, x, y, k: mdsl._local_sgd_epochs(
        p, x, y, loss_fn, 0.01, cfg, k))
    hlo = jax.jit(sgd).lower(
        params, _sds((C, *LOCAL_SET), jnp.float32, one_chip),
        _sds((C, LOCAL_SET[0]), jnp.int32, one_chip),
        _sds((C, 2), jnp.uint32, one_chip)).compile().as_text()
    slices = sorted(l.split("slice_sizes=")[1].split("}")[0] + "}"
                    for l in hlo.splitlines() if " gather(" in l)
    assert slices == ["{1,1,784}", "{1,1}"]


def test_minibatch_rows_on_chip():
    """On an attached chip, the minibatch rows of C = 50 local sets of
    512 f32 28x28x1 images are those of indexing on the host, bit for
    bit."""
    if jax.default_backend() != "tpu":
        pytest.skip("no TPU chip attached")
    kx, ky, ki = jax.random.split(jax.random.PRNGKey(0), 3)
    x = jax.random.normal(kx, (C, *LOCAL_SET), jnp.float32)
    y = jax.random.randint(ky, (C, LOCAL_SET[0]), 0, 10)
    idx = jax.vmap(lambda k: jax.random.permutation(k, LOCAL_SET[0])[:BATCH])(
        jax.random.split(ki, C))
    got = jax.jit(jax.vmap(lambda i, xw, yw: mdsl.minibatch_rows(xw, yw)(i)))(
        idx, x, y)
    x, y, idx = np.asarray(x), np.asarray(y), np.asarray(idx)
    want = (np.stack([x[c][idx[c]] for c in range(C)]),
            np.stack([y[c][idx[c]] for c in range(C)]))
    np.testing.assert_array_equal(np.asarray(got[0]).view(np.uint32),
                                  want[0].view(np.uint32))
    np.testing.assert_array_equal(np.asarray(got[1]), want[1])
