"""Device self time per step, in ms, of the frozen prefix run at the COS
batch: the ops whose innermost named phase is ``hapi.extract`` (the
microbatch scan and its loop machinery; the int8 quantize, a phase of
its own, is left out). This is the part HAPI pushes down to storage.
Read from the trace's op metadata (``chipbench/scopes.py``); nothing
where the program names no phases."""
from chipbench import scopes


def read(ctx):
    return scopes.phase_ms_per_step(ctx, "hapi.extract")
