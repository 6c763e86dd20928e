"""Serving driver: batched prefill + greedy/temperature decode with a KV
cache, over any assigned architecture (`reduced=True` by default; the
full configs are compiled by the AOT dry-run, launch/dryrun.py).

The M-DSL technique is train-time; serving always runs the *global*
model. This driver is the (b)-deliverable inference example and the
harness behind examples/serve_decode.py.

Usage:
  python -m repro.launch.serve --arch smollm-360m --batch 4 \\
      --prompt-len 32 --gen-len 16
"""
from __future__ import annotations

import argparse
import json
import time
from pathlib import Path

import jax
import jax.numpy as jnp

from repro.configs.base import get_arch
from repro.launch import compile_cache
from repro.models.transformer import Transformer

ARTIFACTS = Path(__file__).resolve().parents[3] / "artifacts"


def make_request_batch(key: jax.Array, cfg, batch: int,
                       prompt_len: int) -> dict:
    """Synthetic batched requests (precomputed frontend embeddings for
    vlm/audio per the carve-out)."""
    k1, k2 = jax.random.split(key)
    out = {"tokens": jax.random.randint(k1, (batch, prompt_len), 0,
                                        cfg.vocab_size)}
    out["labels"] = out["tokens"]  # unused at serve time; keeps batch shape
    if cfg.input_mode == "tokens+prefix":
        out["prefix"] = 0.02 * jax.random.normal(
            k2, (batch, cfg.prefix_len, cfg.d_model), jnp.dtype(cfg.dtype))
    if cfg.encoder_layers:
        out["frames"] = 0.02 * jax.random.normal(
            k2, (batch, cfg.encoder_memory_len, cfg.d_model),
            jnp.dtype(cfg.dtype))
    return out


def serve(arch: str, batch: int = 4, prompt_len: int = 32, gen_len: int = 16,
          reduced: bool = True, temperature: float = 0.0, seed: int = 0,
          params=None, verbose: bool = True) -> dict:
    cfg = get_arch(arch)
    if reduced:
        cfg = cfg.reduced()
    model = Transformer(cfg)
    key = jax.random.PRNGKey(seed)
    k_init, k_req, k_samp = jax.random.split(key, 3)
    if params is None:
        params = model.init(k_init)

    cache_len = prompt_len + gen_len + (
        cfg.prefix_len if cfg.input_mode == "tokens+prefix" else 0)
    req = make_request_batch(k_req, cfg, batch, prompt_len)

    @jax.jit
    def prefill_fn(params, req):
        memory = None
        if cfg.cross_attention:
            memory = model.encode(params, req["frames"])
        cache = model.init_cache(batch, cache_len, memory=memory,
                                 params=params)
        return model.prefill(params, req, cache)

    @jax.jit
    def decode_fn(params, tokens, cache):
        return model.decode_step(params, tokens, cache)

    def sample(logits, k):
        if temperature <= 0.0:
            return jnp.argmax(logits[:, -1], axis=-1)[:, None]
        return jax.random.categorical(
            k, logits[:, -1] / temperature, axis=-1)[:, None]

    t0 = time.time()
    logits, cache = prefill_fn(params, req)
    logits.block_until_ready()
    t_prefill = time.time() - t0

    tokens = sample(logits, k_samp)
    generated = [tokens]
    t0 = time.time()
    for i in range(gen_len - 1):
        k_samp = jax.random.fold_in(k_samp, i)
        logits, cache = decode_fn(params, tokens, cache)
        tokens = sample(logits, k_samp)
        generated.append(tokens)
    tokens.block_until_ready()
    t_decode = time.time() - t0

    out_tokens = jnp.concatenate(generated, axis=1)
    rec = {
        "arch": arch, "reduced": reduced, "batch": batch,
        "prompt_len": prompt_len, "gen_len": gen_len,
        "prefill_s": round(t_prefill, 3), "decode_s": round(t_decode, 3),
        "prefill_tok_per_s": round(batch * prompt_len / max(t_prefill, 1e-9)),
        "decode_tok_per_s": round(
            batch * max(gen_len - 1, 1) / max(t_decode, 1e-9)),
        "output_shape": list(out_tokens.shape),
        "output_sample": out_tokens[0, :8].tolist(),
    }
    if verbose:
        print(f"[serve/{arch}] prefill {rec['prefill_tok_per_s']} tok/s, "
              f"decode {rec['decode_tok_per_s']} tok/s, "
              f"out {rec['output_shape']}", flush=True)
    return rec


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-360m")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen-len", type=int, default=16)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default=None)
    args = ap.parse_args()
    rec = serve(args.arch, batch=args.batch, prompt_len=args.prompt_len,
                gen_len=args.gen_len, temperature=args.temperature,
                seed=args.seed)
    out = Path(args.out or ARTIFACTS / "serve" / f"{args.arch}.json")
    out.parent.mkdir(parents=True, exist_ok=True)
    out.write_text(json.dumps(rec, indent=1))
    print(f"wrote {out}")


if __name__ == "__main__":
    compile_cache.enable()
    main()
