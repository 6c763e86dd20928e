"""Kanana-2-30B-A3B [hf:kakaocorp/kanana-2-30b-a3b-instruct-2601,
model_type deepseek_v3]: the chip's share of a 16-chip layer.

Published: 48 layers (layer 0 dense with a gated MLP of 6144, then
DeepSeekMoE), hidden 2048, MLA with 32 heads and no query compression
(kv_lora_rank 512, query/key 128 + 64 RoPE, value 128, rope_theta 1e6,
interleaved RoPE), 128 routed experts of width 768 with top-6 sigmoid
routing (noaux_tc, n_group 1, normalised, scaled by 2.448), 2 shared
experts, vocabulary 128256, untied head, RMSNorm eps 1e-6.

The deployment this configuration stands for shares every layer over 16
chips: experts 16-way (8 of 128 held here; the router keeps its 128
outputs and 6 experts a token), attention heads 2-way (16 of 32 held;
the KV down-projection and its norm are whole on every chip), the
vocabulary 8-way (16,032 rows). The dense MLP and the shared experts are
whole on every chip. The layers left out would lie on further chips as
pipeline stages: the dense layer and 4 MoE layers are kept. No width is
cut.
"""
from repro.configs.base import MLA, ArchConfig

CONFIG = ArchConfig(
    name="kanana-2-30b-a3b", family="moe",
    source="hf:kakaocorp/kanana-2-30b-a3b-instruct-2601",
    num_layers=5, d_model=2048, num_heads=16, num_kv_heads=16,
    d_ff=768, vocab_size=16_032,
    block_pattern=(MLA,),
    kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64,
    v_head_dim=128,
    num_experts=128, experts_per_token=6, experts_held=8, expert_offset=0,
    router="sigmoid", routed_scaling_factor=2.448, shared_experts=2,
    router_aux_weight=1e-4,
    first_k_dense=1, dense_d_ff=6144,
    tie_embeddings=False,
    rope_theta=1_000_000.0, norm_eps=1e-6,
    subquadratic=False,
)
