"""The phases of the split fine-tune step in a profiler trace.

The program names its phases with ``jax.named_scope``: ``hapi.extract``,
``hapi.quantize``, ``hapi.dequantize``, ``hapi.tune`` and ``hapi.adamw``
(``repro.obs.schema.DEVICE_SCOPES``). A scope is compile-time metadata
of each HLO instruction (``metadata={op_name=...}``), and a TPU trace
keeps that ``op_name`` as the ``tf_op`` stat of each op's event
metadata. So the phase of every device op is read exactly, by its
instruction name, with no guess from fusion names:

- ``phase_of(op_name)``: the innermost ``hapi.*`` component of an
  ``op_name``, with the autodiff wrappers (``jvp(...)``,
  ``transpose(...)``) taken off; ``None`` outside every phase;
- ``op_scopes(hlo_text)``: instruction -> phase, from a compiled
  program's text;
- ``trace_op_scopes(path)``: instruction -> phase, from the event
  metadata of a trace file's device planes;
- ``window_scopes(trace, path)``: the same map for the trace a
  per-layer reader is handed, from the file it was read from (the
  reader context's ``trace_path``);
- ``phase_seconds`` and ``gaps_by_scope``: device self time by phase,
  and idle time by the phases on either side of each gap.

The phase names are matched by their ``hapi.`` prefix and not imported
from the program, so a program without the scopes reads as all
unscoped and the readers report nothing for it.
"""
from __future__ import annotations

import bisect
import os
import re
from collections import defaultdict
from typing import Dict, Optional

from chipbench import xplane

PREFIX = "hapi."
UNSCOPED = "<unscoped>"      # an op the trace knows, under no phase
UNMAPPED = "<unmapped>"      # an op missing from the instruction map
EDGE = "<edge>"              # the window's start or end, beside a gap
TF_OP = "tf_op"

_WRAPPED = re.compile(r"^(?:[A-Za-z_]\w*\()+(.*?)\)*$")
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?(%?[\w.\-]+) = ")
_OP_NAME = re.compile(r'metadata=\{[^}]*?op_name="([^"]*)"')


def phase_of(op_name: Optional[str]) -> Optional[str]:
    """``"jit(f)/while/body/transpose(jvp(hapi.tune))/mul"`` ->
    ``"hapi.tune"``: the last component that names a phase."""
    phase = None
    for comp in (op_name or "").split("/"):
        m = _WRAPPED.match(comp)
        comp = (m.group(1) if m else comp).split(":")[0]
        if comp.startswith(PREFIX):
            phase = comp
    return phase


def op_scopes(hlo_text: str) -> Dict[str, Optional[str]]:
    """Every instruction of a compiled program's text (``%fusion.12``),
    fused computations' bodies included, with its phase."""
    out = {}
    for line in hlo_text.splitlines():
        m = _INSTR.match(line)
        if m:
            name = m.group(1) if m.group(1).startswith("%") else "%" + m.group(1)
            op = _OP_NAME.search(line)
            out[name] = phase_of(op.group(1) if op else None)
    return out


# -- the trace file's event metadata (an XSpace protobuf, read by hand:
#    XSpace.planes 1; XPlane.name 2, .event_metadata 4, .stat_metadata 5;
#    XEventMetadata.name 2, .stats 5; XStatMetadata.name 2;
#    XStat.metadata_id 1, .str_value 5, .ref_value 7) --------------------
def _varint(buf, i):
    shift = result = 0
    while True:
        b = buf[i]
        i += 1
        result |= (b & 0x7F) << shift
        if b < 0x80:
            return result, i
        shift += 7


def _fields(buf):
    """``(field, value)`` of one message: ints for varints, memoryviews
    for length-delimited fields; fixed-width fields are skipped."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            i += 8 if wire == 1 else 4
            continue
        else:
            raise ValueError(f"unsupported protobuf wire type {wire}")
        yield key >> 3, value


def _map_value(entry):
    return next((v for f, v in _fields(entry) if f == 2), b"")


def trace_op_scopes(path: str) -> Dict[str, Optional[str]]:
    """Instruction -> phase for every op named in the device planes'
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
            out[xplane.short_name(ev_name).split(" ")[0]] = phase_of(op_name)
    return out


def window_scopes(trace: xplane.Trace, path: Optional[str]) -> Dict[str, Optional[str]]:
    """The instruction map of the trace file at ``path``, which ``trace``
    was read from; empty where there is no such file or it does not
    name every op of ``trace``."""
    if not path or not os.path.exists(path):
        return {}
    heads = {xplane.short_name(o.name).split(" ")[0] for o in trace.ops}
    scopes = trace_op_scopes(path)
    return scopes if heads and heads <= scopes.keys() else {}


# -- time by phase ----------------------------------------------------------
def _label(short: str, scopes) -> str:
    head = short.split(" ")[0]
    if head not in scopes:
        return UNMAPPED
    return scopes[head] or UNSCOPED


def phase_seconds(trace: xplane.Trace, lo: float, hi: float, scopes) -> Dict[str, float]:
    """Device self seconds in [lo, hi] by innermost phase, summed over
    the devices (the self-time rule of ``xplane.op_totals``); ops under
    no phase under ``UNSCOPED``, ops the map lacks under ``UNMAPPED``."""
    out = defaultdict(float)
    for short, secs in xplane.op_totals(trace, lo, hi).items():
        out[_label(short, scopes)] += secs
    return dict(out)


def gaps_by_scope(trace: xplane.Trace, lo: float, hi: float, scopes,
                  device: Optional[int] = None) -> Dict[str, float]:
    """Idle seconds of one device (the first by default) in [lo, hi],
    by ``"<phase before>|<phase after>"``: the phases of the innermost op
    that ends where the gap starts and of the one that starts where it
    ends (``EDGE`` at the window's ends)."""
    devs = sorted({o.device for o in trace.ops})
    if not devs:
        return {}
    dev = devs[0] if device is None else device
    ops = [o for o in trace.ops if o.device == dev]
    by_end = sorted(ops, key=lambda o: (o.end, o.start))
    ends = [o.end for o in by_end]
    by_start = sorted(ops, key=lambda o: (o.start, o.end))
    starts = [o.start for o in by_start]
    out = defaultdict(float)
    for a, b in xplane.idle_gaps(trace, dev, lo, hi):
        i = bisect.bisect_right(ends, a) - 1
        j = bisect.bisect_left(starts, b)
        before = (_label(xplane.short_name(by_end[i].name), scopes)
                  if i >= 0 and ends[i] > lo else EDGE)
        after = (_label(xplane.short_name(by_start[j].name), scopes)
                 if j < len(starts) and starts[j] < hi else EDGE)
        out[f"{before}|{after}"] += (b - a) * 1e-9
    return dict(out)


def phase_ms_per_step(ctx: dict, phase: str) -> Optional[float]:
    """A per-layer reading: device self time of ``phase`` per step in
    the traced window, in ms, averaged over the devices; ``None`` where
    the program names no phase or this one ran for no time."""
    trace, steps = ctx["trace"], ctx["steps"]
    if not steps or not trace.n_devices:
        return None
    scopes = window_scopes(trace, ctx.get("trace_path"))
    if not any(scopes.values()):
        return None
    secs = phase_seconds(trace, ctx["lo"], ctx["hi"], scopes).get(phase, 0.0)
    if secs <= 0:
        return None
    return 1e3 * secs / trace.n_devices / steps
