"""The paper engine (`core/mdsl.py` through `runner._prepare_paper`): how
the harness reads a round of it.

`view` names the swarm state's arrays as the reference names them.
`host_read` is what the runner's loop reads after every round: the
global model's test accuracy. `feed` is the fleet's data, the input the
reference is run on.
"""
from __future__ import annotations

import numpy as np

SPAN_READ = "host_read"


def view(state) -> dict:
    w = state.workers
    return {"params": w.params, "velocity": w.velocity,
            "best_params": w.best_params, "best_loss": w.best_loss,
            "global": state.global_params, "gbest": state.gbest.params,
            "gbest_loss": state.gbest.loss,
            "prev_theta_mean": state.sel.prev_theta_mean,
            "round_idx": state.round_idx, "eta": state.eta,
            "residual": state.residual, "ps_residual": state.ps_residual}


def host_read(prep, state, tel) -> float:
    return float(prep.aux["test_accuracy"](state.global_params))


def feed(prep) -> dict:
    data = prep.aux["data"]
    return {"x": np.asarray(data.x), "y": np.asarray(data.y),
            "gx": np.asarray(data.global_x), "gy": np.asarray(data.global_y),
            "eta": np.asarray(prep.aux["eta"])}


def check_config(prep, cfg: dict) -> list:
    """Differences between the built program and the configuration file."""
    out = []
    data = prep.aux["data"]
    want = {"image": list(cfg["model"]["image"]),
            "n_global": cfg["n_global"], "n_test": cfg["n_test"]}
    have = {"image": list(data.x.shape[2:]),
            "n_global": int(data.global_x.shape[0]),
            "n_test": int(data.test_x.shape[0])}
    for k in want:
        if want[k] != have[k]:
            out.append(f"{k}: configuration {want[k]}, program {have[k]}")
    if prep.n_params != cfg["model"]["params"]:
        out.append(f"params: configuration {cfg['model']['params']}, "
                   f"program {prep.n_params}")
    return out
