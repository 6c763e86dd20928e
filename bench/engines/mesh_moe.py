"""The mesh engine (`core/swarm_dist.py` through `runner._prepare_mesh`)
for a DeepSeek-V3 model (MLA + DeepSeekMoE) at the chip's share of a
layer: how the harness reads a round of it.

`view` names the swarm state's arrays as the reference names them.
`host_read` is what the runner's loop reads after every round: the
global loss. The feed is empty: batches come from the step's key.
`check_config` compares every attention, expert, routing and share key
of the configuration file with the built program.
"""
from __future__ import annotations

SPAN_READ = "host_read"

# configuration-file key -> the program's ArchConfig field
ARCH_FIELDS = {"hidden_size": "d_model",
               "intermediate_size": "dense_d_ff",
               "moe_intermediate_size": "d_ff",
               "num_hidden_layers": "num_layers",
               "first_k_dense_replace": "first_k_dense",
               "num_attention_heads": "num_heads",
               "num_key_value_heads": "num_kv_heads",
               "kv_lora_rank": "kv_lora_rank",
               "qk_nope_head_dim": "qk_nope_head_dim",
               "qk_rope_head_dim": "qk_rope_head_dim",
               "v_head_dim": "v_head_dim",
               "router_experts": "num_experts",
               "n_routed_experts": "held_experts",
               "expert_offset": "expert_offset",
               "num_experts_per_tok": "experts_per_token",
               "n_shared_experts": "shared_experts",
               "routed_scaling_factor": "routed_scaling_factor",
               "balance_loss_alpha": "router_aux_weight",
               "vocab_size": "vocab_size",
               "rope_theta": "rope_theta",
               "rms_norm_eps": "norm_eps",
               "torch_dtype": "dtype"}
# configuration-file key -> the value the program's one V3 path implements
FIXED = {"scoring_func": "sigmoid", "topk_method": "noaux_tc",
         "n_group": 1, "topk_group": 1, "norm_topk_prob": True,
         "rope_interleave": True, "rope_scaling": None, "q_lora_rank": None,
         "moe_layer_freq": 1, "hidden_act": "silu", "attention_bias": False,
         "tie_word_embeddings": False}


def view(state) -> dict:
    return {"params": state.params, "velocity": state.velocity,
            "best_params": state.best_params, "best_loss": state.best_loss,
            "global": state.global_params, "gbest": state.gbest_params,
            "gbest_loss": state.gbest_loss,
            "prev_theta_mean": state.prev_theta_mean,
            "round_idx": state.round_idx, "eta": state.eta,
            "residual": state.residual, "ps_residual": state.ps_residual}


def host_read(prep, state, tel) -> float:
    return float(tel.global_loss)


def feed(prep) -> dict:
    return {}


def check_config(prep, cfg: dict) -> list:
    """Differences between the built program and the configuration file."""
    arch = prep.aux["arch_cfg"]
    out = []
    for key, field in ARCH_FIELDS.items():
        have = getattr(arch, field)
        if have != cfg[key]:
            out.append(f"{key}: configuration {cfg[key]!r}, program {have!r}")
    for key, want in FIXED.items():
        if cfg[key] != want:
            out.append(f"{key}: configuration {cfg[key]!r}, program {want!r}")
    if cfg["qk_head_dim"] != arch.qk_nope_head_dim + arch.qk_rope_head_dim:
        out.append(f"qk_head_dim: configuration {cfg['qk_head_dim']!r}")
    if (tuple(arch.block_pattern) != ("mla",) or arch.router != "sigmoid"
            or arch.tie_embeddings):
        out.append("program is not an MLA + DeepSeekMoE decoder with an "
                   "untied head")
    if prep.n_params != cfg["params_held"]:
        out.append(f"params_held: configuration {cfg['params_held']}, "
                   f"program {prep.n_params}")
    return out
