"""Single layers inside the phases, in a profiler trace: the program's
layer scopes (``repro.obs.schema.LAYER_SCOPES``, such as ``moe.route``,
``moe.experts`` and ``moe.shared`` of the expert layer) are
``jax.named_scope``s nested in the ``hapi.*`` phases, kept in each op's
``op_name`` (the ``tf_op`` stat of its event metadata) as the phases are
(``chipbench/scopes.py``):

- ``layer_of(op_name, prefix)``: the innermost component of an
  ``op_name`` that starts with ``prefix``, the autodiff wrappers taken
  off; ``None`` outside every such scope;
- ``trace_op_names(path)``: instruction -> ``op_name`` from the event
  metadata of a trace file's device planes;
- ``layer_seconds_per_step(ctx, names)``: device self time per step in
  the traced window of the ops whose innermost layer scope is one of
  ``names``, summed over those names and averaged over the devices;
  ``None`` where the trace file does not name every op, or no such op
  ran.

The scope names are matched as strings and not imported from the
program, so a program without them reads as no layer at all.
"""
from __future__ import annotations

import os
from typing import Dict, Optional

from chipbench import xplane
from chipbench.scopes import _WRAPPED, TF_OP, _fields, _map_value


def layer_of(op_name: Optional[str], prefix: str) -> Optional[str]:
    """``".../transpose(jvp(moe.experts))/dot"`` -> ``"moe.experts"``."""
    found = None
    for comp in (op_name or "").split("/"):
        m = _WRAPPED.match(comp)
        comp = (m.group(1) if m else comp).split(":")[0]
        if comp.startswith(prefix):
            found = comp
    return found


def trace_op_names(path: str) -> Dict[str, Optional[str]]:
    """Instruction -> ``op_name`` for every op named in the device planes'
    event metadata of the trace at ``path``."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    out = {}
    for field, plane in _fields(space):
        if field != 1:
            continue
        fields = list(_fields(plane))
        name = next((bytes(v).decode() for f, v in fields if f == 2), "")
        if not name.startswith("/device:"):
            continue
        stat_names = {}
        for f, entry in fields:
            if f == 5:
                meta = dict(_fields(_map_value(entry)))
                stat_names[meta.get(1, 0)] = bytes(meta.get(2, b"")).decode()
        tf_op = {k for k, v in stat_names.items() if v == TF_OP}
        for f, entry in fields:
            if f != 4:
                continue
            ev_name, op_name = "", None
            for g, v in _fields(_map_value(entry)):
                if g == 2:
                    ev_name = bytes(v).decode()
                elif g == 5:
                    stat = dict(_fields(v))
                    if stat.get(1) in tf_op:
                        op_name = (bytes(stat[5]).decode() if 5 in stat
                                   else stat_names.get(stat.get(7)))
            out[xplane.short_name(ev_name).split(" ")[0]] = op_name
    return out


def layer_seconds_per_step(ctx: dict, names, prefix: str = "moe.") -> Optional[float]:
    """Device self seconds per step of the ops under the layer scopes
    ``names`` (innermost scope with ``prefix``), per device."""
    trace, steps, path = ctx["trace"], ctx["steps"], ctx.get("trace_path")
    if not steps or not trace.n_devices or not path or not os.path.exists(path):
        return None
    ops = trace_op_names(path)
    heads = {xplane.short_name(o.name).split(" ")[0] for o in trace.ops}
    if not heads or not heads <= ops.keys():
        return None
    secs = sum(t for short, t in xplane.op_totals(trace, ctx["lo"], ctx["hi"]).items()
               if layer_of(ops.get(short.split(" ")[0]), prefix) in names)
    if secs <= 0:
        return None
    return secs / trace.n_devices / steps
