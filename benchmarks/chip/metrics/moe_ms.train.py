"""Device self time per step, in ms, of the expert layer: the ops under
its layer scopes ``moe.route`` (router, dispatch to the held experts,
combine), ``moe.experts`` (the grouped matmuls of the held experts) and
``moe.shared`` (the shared expert), forward and backward. The same time
is part of ``extract_ms.train`` and ``tune_ms.train``. Read from the
trace's op metadata (``chipbench/layer_scopes.py``); nothing where the
program has no expert layer or names no such scope."""
from chipbench import layer_scopes

SCOPES = ("moe.route", "moe.experts", "moe.shared")


def read(ctx):
    secs = layer_scopes.layer_seconds_per_step(ctx, SCOPES)
    return None if secs is None else 1e3 * secs
