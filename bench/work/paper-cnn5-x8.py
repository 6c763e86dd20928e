"""Required work of one M-DSL round of the paper CNN fleet, from shapes.

FLOPs count multiply-adds of the convolutions and dense layers as two
operations; pooling, activations and the optimizer's elementwise work are
not counted. A round requires, per the round's semantics:

  * training: forward + backward (3x forward) of every local sample in
    every epoch that the minibatch loop visits;
  * one D_g evaluation forward per worker after its update (F_{i,t+1});
  * one D_g evaluation of the aggregated global model (Eq. 10);
  * the host's test-accuracy forward of the global model.

The D_g pass over the pre-update worker models that the engine repeats
(F_{i,t}, already known from the previous round) is not required work,
nor is recomputation, so removing either raises the utilization.

Wire bytes are the least the fused int4 uplink, the mean aggregate and
the int8 downlink must move, per leaf of the real (unpadded) model.
"""
from __future__ import annotations

QUANT_BLOCK_ELEMS = 256 * 128       # one f32 scale per block (wire spec)


def cnn_layers(width_mult: int, height: int, width: int, channels: int,
               num_classes: int) -> list[tuple[str, tuple, int]]:
    """(name, weight shape, output positions) of the 5-layer CNN."""
    c1, c2, c3 = width_mult, 2 * width_mult, 2 * width_mult
    hidden = 4 * width_mult
    feat = (height // 4) * (width // 4) * c3
    return [
        ("conv1", (3, 3, channels, c1), height * width),
        ("conv2", (3, 3, c1, c2), (height // 2) * (width // 2)),
        ("conv3", (3, 3, c2, c3), (height // 4) * (width // 4)),
        ("fc1", (feat, hidden), 1),
        ("fc2", (hidden, num_classes), 1),
    ]


def _prod(shape) -> int:
    n = 1
    for s in shape:
        n *= s
    return n


def forward_flops_per_sample(cfg: dict) -> int:
    h, w, ch = cfg["model"]["image"]
    return sum(2 * _prod(shape) * positions for _, shape, positions in
               cnn_layers(cfg["model"]["width_mult"], h, w, ch,
                          cfg["model"]["num_classes"]))


def param_leaf_sizes(cfg: dict) -> list[int]:
    h, w, ch = cfg["model"]["image"]
    out = []
    for _, shape, _ in cnn_layers(cfg["model"]["width_mult"], h, w, ch,
                                  cfg["model"]["num_classes"]):
        out += [_prod(shape), shape[-1]]          # weight, bias
    return out


def round_flops(cfg: dict, spec: dict) -> float:
    d, a = spec["data"], spec["algo"]
    C, n = d["num_workers"], d["n_local"]
    bs = min(a["batch_size"], n)
    visited = (n // bs) * bs * a["local_epochs"]          # samples per worker
    fwd = forward_flops_per_sample(cfg)
    train = 3 * fwd * C * visited
    worker_eval = fwd * C * cfg["n_global"]
    global_eval = fwd * cfg["n_global"]
    test = fwd * cfg["n_test"]
    return float(train + worker_eval + global_eval + test)


def _blocks(n: int) -> int:
    return -(-n // QUANT_BLOCK_ELEMS)


def wire_bytes(cfg: dict, spec: dict) -> float | None:
    """Least HBM bytes of the wire kernels per round, or None where the
    round runs no wire kernel (a dense wire)."""
    comm = spec["comm"]
    bits = {"int4": 4, "int8": 8}.get(comm["compressor"])
    if bits is None:
        return None
    C = spec["data"]["num_workers"]
    total = 0.0
    for n in param_leaf_sizes(cfg):
        scales = 4 * _blocks(n)
        payload = n * bits / 8
        # uplink quantize+pack+EF: read delta and residual, write the
        # payload, its scales and the new residual
        total += C * (4 * n + 4 * n + payload + scales + 4 * n)
        # mean aggregate: read every worker's payload and scales, write
        # the f32 aggregate
        total += C * (payload + scales) + 4 * n
        dbits = {"int4": 4, "int8": 8}.get(comm["downlink_compressor"])
        if dbits is not None:
            dpay = n * dbits / 8
            # downlink quantize (read f32, write payload + scales) and
            # decode (read payload + scales, write f32)
            total += (4 * n + dpay + scales) + (dpay + scales + 4 * n)
    return total
