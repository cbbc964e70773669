"""Device self time per step, in ms, of the optimizer: the ops whose
innermost named phase is ``hapi.adamw`` (the gradient averaging, global
norm, clip and the moment and parameter update). Read from the trace's
op metadata (``chipbench/scopes.py``); nothing where the program names
no phases."""
from chipbench import scopes


def read(ctx):
    return scopes.phase_ms_per_step(ctx, "hapi.adamw")
