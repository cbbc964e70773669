"""Roofline share, in %, of the Pallas int8 dequantize kernel at the
tier boundary: the least time its bytes need at the HBM peak (int8 and
one float32 scale per 128 lanes in, bf16 out, for every boundary element
of every step in the window) over the device time of its events, found by the
name the kernel carries in a TPU trace (its jitted wrapper's name)."""
from chipbench import xplane

KERNEL = "dequantize_int8_pallas"


def read(ctx):
    if not ctx.get("dequantize_bytes_per_step"):
        return None
    n, secs = xplane.matching(ctx["trace"], ctx["lo"], ctx["hi"], (KERNEL,))
    if n == 0 or secs <= 0:
        return None
    least = ctx["dequantize_bytes_per_step"] * ctx["steps"] / ctx["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least / secs
