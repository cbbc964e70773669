"""Device self time per step, in ms, of the trainable suffix: the ops
whose innermost named phase is ``hapi.tune`` (suffix forward and
backward, head, loss, and the gradient accumulation; the int8
dequantize, a phase of its own, is left out). Read from the trace's op
metadata (``chipbench/scopes.py``); nothing where the program names no
phases."""
from chipbench import scopes


def read(ctx):
    return scopes.phase_ms_per_step(ctx, "hapi.tune")
