"""Model FLOP/s utilization of the split fine-tune step over the traced
window, in % of the chip's bf16 peak: model FLOPs per step (``counts``,
from shapes; recompute not counted) times the steps completed in the
window, over the window's length times the peak."""


def read(ctx):
    if not ctx["steps"] or ctx["window_s"] <= 0:
        return None
    flops = ctx["flops_per_step"] * ctx["steps"]
    return 100.0 * flops / (ctx["window_s"] * ctx["peaks"]["bf16_flops_per_s"])
