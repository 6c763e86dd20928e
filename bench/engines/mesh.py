"""The mesh engine (`core/swarm_dist.py` through `runner._prepare_mesh`):
how the harness reads a round of it.

`view` names the swarm state's arrays as the reference names them.
`host_read` is what the runner's loop reads after every round: the
global loss. The feed is empty: batches come from the step's key.
"""
from __future__ import annotations

SPAN_READ = "host_read"

# configuration-file key -> the program's ArchConfig field
ARCH_FIELDS = {"hidden_size": "d_model", "intermediate_size": "d_ff",
               "num_hidden_layers": "num_layers",
               "num_attention_heads": "num_heads",
               "num_key_value_heads": "num_kv_heads",
               "head_dim": "resolved_head_dim", "vocab_size": "vocab_size",
               "rope_theta": "rope_theta", "rms_norm_eps": "norm_eps",
               "torch_dtype": "dtype"}


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
    if tuple(arch.block_pattern) != ("attn",) or arch.num_experts:
        out.append("program is not a dense full-attention decoder")
    return out
