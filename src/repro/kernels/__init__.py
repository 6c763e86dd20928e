"""Pallas TPU kernels for the framework's compute hot spots.

Each kernel module trio provides:
  <name>.py — pl.pallas_call with explicit BlockSpec VMEM tiling (TPU target)
  ops.py    — jit'd public wrapper (padding, layout, GQA plumbing)
  ref.py    — pure-jnp oracle used by the test sweeps

Kernels: pso_update (the paper's Eq.-8 fused pointwise swarm update),
flash_attention (blockwise causal/sliding attention), rglru_scan
(streaming linear-recurrence scan), and the wire-path pair that fuses
the Eq.-7 uplink hot loop end to end (docs/kernels.md):

  quant_pack  stochastic int8/int4 quantize-and-pack, plus the fused
              quantize+pack+error-feedback-update pass
              (`quantize_pack_ef`: delta + residual -> packed payload,
              block scales, new residual in one read) and the decode
              kernel (`dequantize_unpack`); the shared hash-RNG makes
              the ref.py oracles bit-identical to the kernels
  wire_agg    fused dequant + masked-aggregate: the PS folds C packed
              payloads straight into the Eq.-7 mean / coordinate-wise
              median / trimmed mean without materializing C dense
              reconstructions

On a TPU they compile through Mosaic; on the CPU they run in
interpret mode (`repro.kernels.runtime.interpret_default`), and the
wire-path wrappers take their bit-identical jnp refs instead, which is
cheaper under the engines' vmap. Every dispatch decision is reported
to the obs bus (`runtime.note_dispatch`). tests/test_tpu_compile.py
compiles the wire-path kernels for a described v5e chip.
"""
