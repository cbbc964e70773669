"""Roofline share, in %, of the Pallas int8 quantize kernel at the tier
boundary: the least time its bytes need at the HBM peak (bf16 in; int8
and one float32 scale per 128 lanes out, for every boundary element of
every step in the window) over the device time of its events, found by the
name the kernel carries in a TPU trace (its jitted wrapper's name). It is
bound by memory: it does no matrix work."""
from chipbench import xplane

KERNEL = "quantize_int8_pallas"
NOT = "dequantize"


def read(ctx):
    if not ctx.get("quantize_bytes_per_step"):
        return None
    n, secs = xplane.matching(ctx["trace"], ctx["lo"], ctx["hi"], (KERNEL,), (NOT,))
    if n == 0 or secs <= 0:
        return None
    least = ctx["quantize_bytes_per_step"] * ctx["steps"] / ctx["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least / secs
