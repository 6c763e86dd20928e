"""The mesh round's share of the chip's bf16 peak: the FLOPs a round
requires (the configuration's work count, from shapes: routed pairs at
the held experts' share, the causal half of attention, no
recomputation) times the rounds traced, over the traced window, over
the peak; in percent."""


def read(r: dict):
    red = r["reduced"]
    if not red.rounds or red.window_s <= 0:
        return None
    flops = r["work"].round_flops(r["cfg"], r["spec"]) * red.rounds
    return 100.0 * flops / red.window_s / (
        red.devices * r["peaks"]["bf16_flops_per_s"])
