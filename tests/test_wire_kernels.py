"""Fused wire-path kernels (kernels/quant_pack EF pass + kernels/
wire_agg): bit-equality against the jnp oracles (also under vmap over
the stacked-worker axis), error-feedback telescoping through the fused
path, receive_packed == receive under erasure masks for every
aggregator, and the wire_round packed-route gate — including that every
golden-pinned engine config stays on the legacy route."""
import functools

import hypothesis as hp
import hypothesis.strategies as st
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.comm import channel, compress
from repro.comm.budget import CommConfig
from repro.core import rounds
from repro.kernels import runtime
from repro.kernels.quant_pack import (dequant_unpack_2d, dequant_unpack_ref,
                                      dequantize_unpack, quant_pack_ef_2d,
                                      quant_pack_ef_ref, quantize_pack,
                                      quantize_pack_ef)
from repro.kernels.wire_agg import wire_agg_2d, wire_agg_ref, wire_aggregate

KEY = jax.random.PRNGKey(0)


def _xr(seed: int, shape=(256, 128)):
    k = jax.random.fold_in(KEY, seed)
    x = jax.random.normal(k, shape)
    r = 0.05 * jax.random.normal(jax.random.fold_in(k, 1), shape)
    return x, r


class TestFusedQuantPackEF:
    @hp.given(st.integers(0, 2**31 - 1), st.sampled_from([8, 4]))
    @hp.settings(max_examples=8, deadline=None)
    def test_kernel_matches_ref(self, seed, bits):
        x, r = _xr(seed % 1000)
        s = jnp.int32(seed)
        pk, sk, rk = quant_pack_ef_2d(x, r, s, bits=bits, interpret=True)
        pr, sr, rr = quant_pack_ef_ref(x, r, s, bits=bits)
        np.testing.assert_array_equal(np.asarray(pk), np.asarray(pr))
        np.testing.assert_array_equal(np.asarray(sk), np.asarray(sr))
        np.testing.assert_array_equal(np.asarray(rk), np.asarray(rr))

    @hp.given(st.integers(1, 5), st.sampled_from([8, 4]),
              st.integers(0, 2**20))
    @hp.settings(max_examples=6, deadline=None)
    def test_vmap_over_worker_axis_bit_equal(self, C, bits, seed):
        # the engines' calling convention: vmap over stacked workers;
        # two blocks per worker, so the batched grid's block index (and
        # the scale it writes) is checked as well
        k = jax.random.fold_in(KEY, seed)
        xs = jax.random.normal(k, (C, 512, 128))
        rs = 0.1 * jax.random.normal(jax.random.fold_in(k, 1),
                                     (C, 512, 128))
        seeds = jnp.arange(C, dtype=jnp.int32) + seed % 97
        kern = jax.jit(jax.vmap(lambda x, r, s: quant_pack_ef_2d(
            x, r, s, bits=bits, interpret=True)))
        ref = jax.jit(jax.vmap(lambda x, r, s: quant_pack_ef_ref(
            x, r, s, bits=bits)))
        for a, b in zip(kern(xs, rs, seeds), ref(xs, rs, seeds)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    @pytest.mark.parametrize("bits", [8, 4])
    def test_matches_legacy_compose(self, bits):
        """packed/scales/residual == quantize + decode + subtract, run
        in ONE jit (the engines' regime — XLA fuses the residual's
        multiply-subtract identically on both routes)."""
        x, r = _xr(3, (300, 7))
        s = jnp.int32(11)

        @jax.jit
        def legacy(x, r, s):
            p, sc = quantize_pack(x + r, s, bits=bits)
            wire = dequantize_unpack(p, sc, x.shape, bits=bits)
            return p, sc, (x + r) - wire

        fused = quantize_pack_ef(x, r, s, bits=bits)
        for a, b in zip(fused, legacy(x, r, s)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    @pytest.mark.parametrize("bits", [8, 4])
    def test_dequant_kernel_matches_ref(self, bits):
        x, _ = _xr(4, (512, 128))
        p, s = quantize_pack(x, jnp.int32(5), bits=bits)
        dk = dequant_unpack_2d(p, s, bits=bits, interpret=True)
        dr = dequant_unpack_ref(p, s, bits=bits)
        np.testing.assert_array_equal(np.asarray(dk), np.asarray(dr))


class TestDispatch:
    def test_wire_ops_report_dispatch(self, monkeypatch):
        """Every wire-path wrapper notes its kernel/ref decision —
        including dequantize_unpack, which used to run the ref
        unconditionally without reporting."""
        seen = []
        monkeypatch.setattr(
            runtime, "note_dispatch",
            lambda name, interpret, **info: seen.append((name, interpret)))
        x, r = _xr(5, (300, 7))
        p, s, _ = quantize_pack_ef(x, r, jnp.int32(1), bits=8)
        dequantize_unpack(p, s, x.shape, bits=8)
        wire_aggregate(jnp.stack([p, p]), jnp.stack([s, s]), jnp.ones(2),
                       shape=x.shape, bits=8)
        names = {n for n, _ in seen}
        assert {"quant_pack_ef", "dequant_unpack", "wire_agg"} <= names, seen
        # CPU container: everything dispatches to the interpret/ref path
        assert all(interp for _, interp in seen), seen


class TestErrorFeedbackFused:
    @pytest.mark.parametrize("bits", [8, 4])
    def test_fused_step_tracks_legacy(self, bits):
        """Per round, on identical (delta, residual, key) inputs, the
        fused step emits the SAME payload bits and scales as
        compress_with_ef — so the decoded wire is bit-identical — while
        the new residual agrees up to XLA's FMA contraction of the
        final subtract (the legacy route subtracts at leaf shape after
        the dequant slice, the fused pass at the padded block shape;
        XLA is free to contract either).

        delta enters the jit as an INPUT, matching wire_round's regime
        (the engines' delta is a params subtract, not a raw multiply):
        if a caller's multiply fed the EF accumulate inside the same
        trace, XLA could FMA-contract it on one route only, shifting
        amax -> scale -> every decoded element by 1 ulp."""
        cfg = CommConfig(compressor=f"int{bits}")
        t = jnp.asarray([1.0, -2.0, 0.5, 3.0, -0.7, 0.1, 2.2, -1.4])

        @jax.jit
        def step_legacy(delta, res, key):
            wire, res = compress.compress_with_ef(cfg, {"x": delta}, res,
                                                  key)
            return wire["x"], res

        @jax.jit
        def step_packed(delta, res, key):
            pw, res = compress.compress_with_ef_packed(cfg, {"x": delta},
                                                       res, key)
            wire = dequantize_unpack(pw.packed[0], pw.scales[0], t.shape,
                                     bits=bits)
            return wire, res

        x, key = jnp.zeros(8), KEY
        res = compress.init_residual({"x": x})
        for _ in range(25):
            key, k = jax.random.split(key)
            delta = -0.2 * 2.0 * (x - t)
            wl, res_l = step_legacy(delta, res, k)
            wp, res_p = step_packed(delta, res, k)   # same inputs
            np.testing.assert_array_equal(np.asarray(wl), np.asarray(wp))
            np.testing.assert_allclose(np.asarray(res_p["x"]),
                                       np.asarray(res_l["x"]),
                                       rtol=0, atol=1e-6)
            res = res_l
            x = x + delta

    @pytest.mark.parametrize("bits", [8, 4])
    def test_telescoping_through_fused_path(self, bits):
        """EF telescoping (Seide et al.) survives the fused pass: the
        sum of decoded uploads tracks the sum of true deltas to within
        the final residual, exactly (within one jit the fused residual
        IS acc - wire, so the telescoping sum collapses)."""
        cfg = CommConfig(compressor=f"int{bits}")
        t = jnp.asarray([1.0, -2.0, 0.5, 3.0, -0.7, 0.1, 2.2, -1.4])

        @jax.jit
        def step(x, res, key):
            delta = -0.2 * 2.0 * (x - t)
            pw, res = compress.compress_with_ef_packed(
                cfg, {"x": delta}, res, key)
            wire = dequantize_unpack(pw.packed[0], pw.scales[0], t.shape,
                                     bits=bits)
            return wire, res, delta

        x, key = jnp.zeros(8), KEY
        res = compress.init_residual({"x": x})
        srv, sum_d = jnp.zeros(8), jnp.zeros(8)
        for _ in range(30):
            key, k = jax.random.split(key)
            wire, res, delta = step(x, res, k)
            srv, sum_d, x = srv + wire, sum_d + delta, x + delta
        np.testing.assert_allclose(np.asarray(srv + res["x"]),
                                   np.asarray(sum_d), rtol=0, atol=1e-5)
        # and the wire actually moved the server toward the delta sum
        assert np.abs(np.asarray(srv - sum_d)).max() < 0.05


class TestReceivePacked:
    @pytest.mark.parametrize("bits", [8, 4])
    @pytest.mark.parametrize("agg", ["mean", "median", "trimmed_mean"])
    def test_equals_legacy_receive_under_erasure(self, bits, agg):
        cfg = CommConfig(compressor=f"int{bits}", channel="erasure",
                         drop_prob=0.4, aggregator=agg)
        C = 6
        gp = {"w": jax.random.normal(KEY, (90, 11)),
              "b": jax.random.normal(jax.random.fold_in(KEY, 1), (13,))}
        delta = jax.tree.map(
            lambda x: 0.1 * jax.random.normal(jax.random.fold_in(KEY, 2),
                                              (C,) + x.shape), gp)
        residual = jax.tree.map(
            lambda x: jnp.zeros((C,) + x.shape, jnp.float32), gp)
        mask = jnp.array([1., 1., 0., 1., 1., 1.])
        qkey, wkey = jax.random.split(jax.random.fold_in(KEY, 3))

        @jax.jit
        def both(delta, residual, gp, qkey, wkey):
            keys = jax.random.split(qkey, C)
            wire, _ = jax.vmap(functools.partial(
                compress.compress_with_ef, cfg))(delta, residual, keys)
            agg_l, me_l = channel.receive(cfg, gp, wire, mask, wkey)
            pw, _ = jax.vmap(functools.partial(
                compress.compress_with_ef_packed, cfg))(delta, residual,
                                                        keys)
            agg_p, me_p = channel.receive_packed(cfg, gp, pw, mask, wkey)
            return agg_l, me_l, agg_p, me_p

        agg_l, me_l, agg_p, me_p = both(delta, residual, gp, qkey, wkey)
        np.testing.assert_array_equal(np.asarray(me_l), np.asarray(me_p))
        for k in gp:
            np.testing.assert_array_equal(np.asarray(agg_l[k]),
                                          np.asarray(agg_p[k]))

    @hp.given(st.integers(1, 6), st.sampled_from([8, 4]),
              st.sampled_from(["mean", "median", "trimmed_mean"]),
              st.integers(0, 2**20))
    @hp.settings(max_examples=8, deadline=None)
    def test_wire_agg_kernel_matches_ref_masked(self, C, bits, agg, seed):
        from repro.kernels.quant_pack import quant_pack_ref
        k = jax.random.fold_in(KEY, seed)
        xs = jax.random.normal(k, (C, 512, 128))       # two blocks
        pcs = [quant_pack_ref(xs[c], jnp.int32(c + seed % 53), bits=bits)
               for c in range(C)]
        packed = jnp.stack([p for p, _ in pcs])
        scales = jnp.stack([s for _, s in pcs])
        mask = jax.random.bernoulli(jax.random.fold_in(k, 1), 0.6,
                                    (C, 1)).astype(jnp.float32)
        w1 = jnp.ones((C, 1), jnp.float32)
        a_k = wire_agg_2d(packed, scales, mask, w1, bits=bits,
                          aggregator=agg, interpret=True)
        a_r = jax.jit(functools.partial(wire_agg_ref, bits=bits,
                                        aggregator=agg))(packed, scales,
                                                         mask, w1)
        np.testing.assert_array_equal(np.asarray(a_k), np.asarray(a_r))


class TestWireRoundRoute:
    def _run(self, cfg, aggregate_fn=None):
        C = 6
        gp = {"w": jax.random.normal(KEY, (90, 11)),
              "b": jax.random.normal(jax.random.fold_in(KEY, 1), (13,))}
        delta = jax.tree.map(
            lambda x: 0.1 * jax.random.normal(jax.random.fold_in(KEY, 2),
                                              (C,) + x.shape), gp)
        residual = jax.tree.map(
            lambda x: jnp.zeros((C,) + x.shape, jnp.float32), gp)
        kw = {} if aggregate_fn is None else {"aggregate_fn": aggregate_fn}
        run = jax.jit(functools.partial(rounds.wire_round, cfg,
                                        num_workers=C, **kw))
        qkey, wkey = jax.random.split(jax.random.fold_in(KEY, 3))
        return run(delta=delta, theta=jnp.linspace(0.1, 1.0, C),
                   mask=jnp.array([1., 1., 0., 1., 1., 1.]),
                   global_params=gp, residual=residual,
                   ps_residual=compress.init_residual(gp),
                   qkey=qkey, wkey=wkey)

    @pytest.mark.parametrize("comp,agg", [("int8", "mean"),
                                          ("int8", "median"),
                                          ("int4", "trimmed_mean")])
    def test_packed_route_bit_identical_to_legacy(self, comp, agg):
        cfg = CommConfig(compressor=comp, channel="erasure", drop_prob=0.3,
                         aggregator=agg)
        out = self._run(cfg)  # defaults -> packed route engages
        # wrapping the default aggregate_fn defeats the `is` gate ->
        # the identical config runs the legacy dense route
        leg = self._run(cfg, aggregate_fn=lambda *a, **k:
                        channel.receive(*a, **k))
        for k in ("w", "b"):
            np.testing.assert_array_equal(np.asarray(out.global_params[k]),
                                          np.asarray(leg.global_params[k]))
            # EF residual: equal up to XLA FMA contraction of the final
            # subtract (routes subtract at different shapes)
            np.testing.assert_allclose(np.asarray(out.residual[k]),
                                       np.asarray(leg.residual[k]),
                                       rtol=0, atol=1e-6)
        np.testing.assert_array_equal(np.asarray(out.mask_eff),
                                      np.asarray(leg.mask_eff))
        assert float(out.record.bytes_up) == float(leg.record.bytes_up)

    def test_gate(self):
        tree = {"w": jnp.zeros((4, 3), jnp.float32)}
        ok = CommConfig(compressor="int8", channel="erasure")
        assert compress.packed_wire_eligible(ok, tree)
        assert compress.packed_wire_eligible(
            CommConfig(compressor="int4"), tree)
        for bad in (CommConfig(),                                # identity
                    CommConfig(compressor="topk"),
                    CommConfig(compressor="int8", channel="awgn"),
                    CommConfig(compressor="int8", channel="composite"),
                    CommConfig(compressor="int8", adaptive_bits=True)):
            assert not compress.packed_wire_eligible(bad, tree)
        # mixed precision keeps the dense route's astype semantics
        assert not compress.packed_wire_eligible(
            ok, {"w": jnp.zeros((4, 3), jnp.bfloat16)})

    def test_golden_configs_stay_on_legacy_route(self):
        """Structural safety for tests/test_rounds.py pins: none of the
        golden-pinned configs qualifies for the packed route."""
        tree = {"w": jnp.zeros((4, 3), jnp.float32)}
        goldens = [CommConfig(),                                 # A/B/F
                   CommConfig(channel="erasure", drop_prob=0.35),   # ERA
                   CommConfig(channel="awgn", snr_db=10.0),         # AWGN
                   CommConfig(compressor="int8", adaptive_bits=True,
                              error_feedback=True)]                 # ADA
        assert not any(compress.packed_wire_eligible(g, tree)
                       for g in goldens)


class TestTreeAggregate:
    """Two-stage tree mean for fleets past the kernel's VMEM worker cap
    (ops.MEAN_WORKER_CAP): per-chunk masked weighted partial sums, one
    fleet-wide divide."""

    def _fleet(self, C, seed=0, rows=256):
        from repro.kernels.quant_pack import quant_pack_ref
        k = jax.random.fold_in(KEY, seed)
        xs = jax.random.normal(k, (C, rows, 128))
        pcs = [quant_pack_ref(xs[c], jnp.int32(c), bits=8) for c in range(C)]
        packed = jnp.stack([p for p, _ in pcs])
        scales = jnp.stack([s for _, s in pcs])
        mask = jax.random.bernoulli(jax.random.fold_in(k, 1), 0.7,
                                    (C,)).astype(jnp.float32)
        return packed, scales, mask, (rows, 128)

    def test_chunked_matches_flat_mean(self):
        """C=96 > cap routes through the tree; the result matches the
        flat single-stage mean up to f32 re-association."""
        packed, scales, mask, shape = self._fleet(96)
        out = wire_aggregate(packed, scales, mask, shape=shape,
                             interpret=True)
        C = packed.shape[0]
        flat = wire_agg_ref(packed, scales, mask.reshape(C, 1),
                            jnp.ones((C, 1), jnp.float32))
        np.testing.assert_allclose(np.asarray(out), np.asarray(flat),
                                   rtol=1e-5, atol=1e-5)

    def test_chunk_sum_kernel_matches_ref_bitwise(self):
        """The per-chunk 'sum' partial is bit-identical between the
        pallas kernel (interpret) and the jnp ref — the invariant that
        keeps kernel-vs-ref bitwise at every C under the tree."""
        packed, scales, mask, _ = self._fleet(96, seed=1)
        C = packed.shape[0]
        m2 = mask.reshape(C, 1)
        w2 = jnp.ones((C, 1), jnp.float32)
        from repro.kernels.wire_agg.ops import MEAN_WORKER_CAP as CAP
        for g0 in range(0, C, CAP):
            sl = slice(g0, g0 + CAP)
            a_k = wire_agg_2d(packed[sl], scales[sl], m2[sl], w2[sl],
                              aggregator="sum", interpret=True)
            a_r = wire_agg_ref(packed[sl], scales[sl], m2[sl], w2[sl],
                               aggregator="sum")
            np.testing.assert_array_equal(np.asarray(a_k), np.asarray(a_r))

    def test_small_fleet_single_stage_bitwise(self):
        """C <= cap keeps the legacy single-stage call bit-identical to
        the flat ref — existing pins never see the tree."""
        packed, scales, mask, shape = self._fleet(8, seed=2)
        out = wire_aggregate(packed, scales, mask, shape=shape,
                             interpret=True)
        flat = wire_agg_ref(packed, scales, mask.reshape(8, 1),
                            jnp.ones((8, 1), jnp.float32))
        np.testing.assert_array_equal(np.asarray(out), np.asarray(flat))

    def test_forced_cap_uneven_chunks_bitwise(self):
        """worker_cap=3 on C=8 (uneven tail chunk) reproduces the manual
        two-stage computation bit-for-bit."""
        packed, scales, mask, shape = self._fleet(8, seed=3)
        out = wire_aggregate(packed, scales, mask, shape=shape,
                             interpret=True, worker_cap=3)
        m2 = mask.reshape(8, 1)
        w2 = jnp.ones((8, 1), jnp.float32)
        parts = [wire_agg_ref(packed[g:g + 3], scales[g:g + 3],
                              m2[g:g + 3], w2[g:g + 3], aggregator="sum")
                 for g in range(0, 8, 3)]
        man = sum(parts[1:], parts[0]) / jnp.maximum((m2 * w2).sum(), 1.0)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(man))

    def test_threshold_boundary(self):
        """C == cap stays single-stage (no chunks reported); C == cap+1
        trees into two chunks."""
        from repro.kernels.wire_agg import ops as wire_ops
        seen = []
        orig = runtime.note_dispatch
        try:
            runtime.note_dispatch = lambda n, i, **kw: seen.append(kw)
            for C in (4, 5):
                packed, scales, mask, shape = self._fleet(C, seed=4)
                wire_aggregate(packed, scales, mask, shape=shape,
                               interpret=True, worker_cap=4)
        finally:
            runtime.note_dispatch = orig
        assert "chunks" not in seen[0] and seen[0]["workers"] == 4, seen
        assert seen[1].get("chunks") == 2 and seen[1]["workers"] == 5, seen
        assert wire_ops.MEAN_WORKER_CAP == 64
