"""Required work of one swarm round of SmolLM-360M on the mesh path.

FLOPs count multiply-adds of the matmuls as two operations. Per token the
forward needs 2 x (matmul parameters): q/k/v/o and the gated MLP of every
layer plus the tied output head (the embedding lookup is no matmul). The
causal attention scores and values need 2 x S^2 x (heads x head size)
per layer and sequence (the causal half of the two S x S products).

A round requires, per the round's semantics:

  * training: forward + backward (3x forward) of each worker's batch for
    every local step;
  * one evaluation forward of the eval batch per worker after its update
    (F_{i,t+1});
  * one evaluation forward of the aggregated global model (Eq. 10).

Recomputation under activation checkpointing is not required work.
"""
from __future__ import annotations


def matmul_params(cfg: dict) -> int:
    d, ff = cfg["hidden_size"], cfg["intermediate_size"]
    h, kv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    attn = d * hd * (h + 2 * kv) + h * hd * d
    mlp = 3 * d * ff
    head = cfg["vocab_size"] * d                      # tied output head
    return cfg["num_hidden_layers"] * (attn + mlp) + head


def attention_flops_per_sequence(cfg: dict, seq: int) -> int:
    width = cfg["num_attention_heads"] * cfg["head_dim"]
    return cfg["num_hidden_layers"] * 2 * seq * seq * width


def forward_flops(cfg: dict, batch: int, seq: int) -> int:
    return (2 * matmul_params(cfg) * batch * seq
            + batch * attention_flops_per_sequence(cfg, seq))


def round_flops(cfg: dict, spec: dict) -> float:
    m, a = spec["model"], spec["algo"]
    W = spec["data"]["num_workers"]
    fwd = forward_flops(cfg, m["per_worker_batch"], m["seq_len"])
    train = 3 * fwd * W * a["local_steps"]
    evals = fwd * (W + 1)
    return float(train + evals)


def wire_bytes(cfg: dict, spec: dict) -> float | None:
    """The mesh cells run the identity wire: no wire kernel."""
    if spec["comm"]["compressor"] in ("int4", "int8"):
        raise NotImplementedError("no quantized-wire cell on this config")
    return None
