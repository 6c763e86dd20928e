"""Roofline share of the fused wire kernels (int4 uplink quantize + pack
+ error feedback, the packed mean aggregate, the int8 downlink quantize
and decode): the least HBM bytes they must move per round (the
configuration's work count, from leaf shapes) times the rounds traced,
over their summed device time, over the HBM bandwidth; in percent. The
kernels are bound by bytes, not FLOPs."""

KERNELS = ("quant_pack", "wire_agg", "dequant_unpack")


def read(r: dict):
    red = r["reduced"]
    t = sum(s for name, s in red.kernel_s.items()
            if any(k in name for k in KERNELS))
    nbytes = r["work"].wire_bytes(r["cfg"], r["spec"])
    if not t or not nbytes or not red.rounds:
        return None
    return 100.0 * nbytes * red.rounds / t / r["peaks"]["hbm_bytes_per_s"]
