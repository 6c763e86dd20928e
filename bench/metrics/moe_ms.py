"""Device milliseconds per round of the MoE layers: the ops under the
`MoE` name scope (the router, the balance loss, the sort of the pairs,
the shared experts, forward, rematerialised and backward) plus XLA's
`ragged-dot` kernels, the held experts' grouped products, which the TPU
compiler emits as custom calls named `ragged-dot-*` with no scope path
(no other layer of the program calls `ragged_dot`)."""
import scope_time


def read(r: dict):
    red = r["reduced"]
    s = scope_time.scope_seconds(red, "MoE") + sum(
        t for name, t in red.kernel_s.items() if name.startswith("ragged-dot"))
    if not s or not red.rounds:
        return None
    return 1e3 * s / red.rounds
