"""Required work of one swarm round of Kanana-2-30B-A3B on the mesh path,
at the chip's share of a 16-chip layer, from shapes.

FLOPs count multiply-adds of the matmuls as two operations. Per token the
forward needs 2 x (matmul parameters it uses):

  * MLA, every layer: W_q, W_kva, W_kvb and W_o of the heads held here;
  * layer 0's dense gated MLP;
  * every MoE layer: the router, the shared experts' gated MLP, and the
    routed pairs that reach the experts held here: K picks a token, of
    which a held/E share lands here (K x 8 / 128 at the cell's share;
    uniform random tokens route near uniformly), each a gated MLP;
  * the untied output head over the vocabulary slice (the embedding
    lookup is no matmul).

Causal attention needs 2 x S^2 x H x (qk + v) / 2 per layer and sequence
(the causal half of the two S x S products; qk = 128 + 64, v = 128).

A round requires, per the round's semantics:

  * training: forward + backward (3x forward) of each worker's batch for
    every local step;
  * one evaluation forward of the eval batch per worker after its update
    (F_{i,t+1});
  * one evaluation forward of the aggregated global model (Eq. 10).

Recomputation under activation checkpointing, the masked half of the
attention tiles and the rows of the grouped products outside the held
experts are not required work.
"""
from __future__ import annotations


def mla_params(cfg: dict) -> int:
    d, h, r = (cfg["hidden_size"], cfg["num_attention_heads"],
               cfg["kv_lora_rank"])
    qk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    return (d * h * qk + d * (r + cfg["qk_rope_head_dim"])
            + r * h * (cfg["qk_nope_head_dim"] + cfg["v_head_dim"])
            + h * cfg["v_head_dim"] * d)


def routed_pairs_per_token(cfg: dict) -> float:
    return (cfg["num_experts_per_tok"] * cfg["n_routed_experts"]
            / cfg["router_experts"])


def moe_params_per_token(cfg: dict) -> float:
    d, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    return (d * cfg["router_experts"]
            + 3 * d * f * cfg["n_shared_experts"]
            + 3 * d * f * routed_pairs_per_token(cfg))


def matmul_params_per_token(cfg: dict) -> float:
    d, L, k = (cfg["hidden_size"], cfg["num_hidden_layers"],
               cfg["first_k_dense_replace"])
    return (L * mla_params(cfg) + k * 3 * d * cfg["intermediate_size"]
            + (L - k) * moe_params_per_token(cfg)
            + cfg["vocab_size"] * d)


def attention_flops_per_sequence(cfg: dict, seq: int) -> int:
    h = cfg["num_attention_heads"]
    width = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"] + cfg["v_head_dim"]
    return cfg["num_hidden_layers"] * seq * seq * h * width


def forward_flops(cfg: dict, batch: int, seq: int) -> float:
    return (2 * matmul_params_per_token(cfg) * batch * seq
            + batch * attention_flops_per_sequence(cfg, seq))


def round_flops(cfg: dict, spec: dict) -> float:
    m, a = spec["model"], spec["algo"]
    W = spec["data"]["num_workers"]
    fwd = forward_flops(cfg, m["per_worker_batch"], m["seq_len"])
    train = 3 * fwd * W * a["local_steps"]
    evals = fwd * (W + 1)
    return float(train + evals)


def wire_bytes(cfg: dict, spec: dict) -> float | None:
    """The cell runs the identity wire: no wire kernel."""
    if spec["comm"]["compressor"] in ("int4", "int8"):
        raise NotImplementedError("no quantized-wire cell on this config")
    return None
