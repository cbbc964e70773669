"""Reduce a profiler trace (``*.xplane.pb``) to what the metrics read.

- Device operations: the events of the ``XLA Ops`` line of each
  ``/device:TPU:<n>`` plane. A TPU trace names each by its HLO text
  (``%fusion.12 = bf16[...] fusion(...)``); the breakdown shortens that
  to the instruction and its opcode (``%fusion.12 fusion``) and counts
  each op's self time, since a ``while`` event spans the ops of its body.
- Host spans: the benchmark's own ``jax.profiler.TraceAnnotation``
  events (names starting ``bench.``) on the host plane's threads.
- The window: from the first host span's start to the last one's end.
- Busy time: the union of the device operations' intervals inside the
  window, averaged over the devices. Idle gaps: the complement, each
  labelled by the host span that overlaps it most.
"""
from __future__ import annotations

import glob
import os
import re
from collections import defaultdict
from typing import List, NamedTuple, Tuple

SPAN_PREFIX = "bench."
OPS_LINE = "XLA Ops"


class Op(NamedTuple):
    name: str
    start: float     # ns
    end: float
    device: int


class Span(NamedTuple):
    name: str
    start: float
    end: float


class Trace(NamedTuple):
    ops: List[Op]
    spans: List[Span]
    n_devices: int


def find_xplane(trace_dir: str) -> str:
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
    if len(paths) != 1:
        raise FileNotFoundError(f"expected one .xplane.pb under {trace_dir}, found {paths}")
    return paths[0]


def load(path: str) -> Trace:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    ops, spans, devices = [], [], []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            dev = int(plane.name.rsplit(":", 1)[1])
            devices.append(dev)
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops += [Op(e.name, e.start_ns, e.start_ns + e.duration_ns, dev)
                            for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans += [Span(e.name, e.start_ns, e.start_ns + e.duration_ns)
                          for e in line.events if e.name.startswith(SPAN_PREFIX)]
    return Trace(ops, spans, len(devices))


def window(trace: Trace) -> Tuple[float, float]:
    if not trace.spans:
        raise ValueError("no benchmark spans in the trace")
    return min(s.start for s in trace.spans), max(s.end for s in trace.spans)


def _merged(intervals):
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def busy_intervals(trace: Trace, device: int, lo: float, hi: float):
    return _merged((max(o.start, lo), min(o.end, hi)) for o in trace.ops
                   if o.device == device and o.end > lo and o.start < hi)


def busy_ns(trace: Trace, lo: float, hi: float) -> float:
    """Union of device-op time in [lo, hi], averaged over the devices."""
    devs = sorted({o.device for o in trace.ops})
    if not devs:
        return 0.0
    return sum(sum(b - a for a, b in busy_intervals(trace, d, lo, hi))
               for d in devs) / len(devs)


def idle_gaps(trace: Trace, device: int, lo: float, hi: float):
    gaps, t = [], lo
    for a, b in busy_intervals(trace, device, lo, hi):
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    if hi > t:
        gaps.append((t, hi))
    return gaps


def label(gap, spans: List[Span]) -> str:
    """The host span overlapping ``gap`` the most (``"none"`` if none)."""
    best, name = 0.0, "none"
    for s in spans:
        ov = min(gap[1], s.end) - max(gap[0], s.start)
        if ov > best:
            best, name = ov, s.name
    return name


_OPCODE = re.compile(r" ([a-z][a-z0-9_\-]*)\(")


def short_name(name: str) -> str:
    """``%fusion.12 = bf16[8]{0} fusion(...)`` -> ``%fusion.12 fusion``."""
    head, sep, rest = name.partition(" = ")
    if not sep:
        return name
    m = _OPCODE.search(" " + rest)
    return f"{head} {m.group(1)}" if m else head


def op_totals(trace: Trace, lo: float, hi: float) -> dict:
    """Device self-seconds by short op name inside [lo, hi], over all
    devices: an event's time less the time of the events nested in it."""
    tot = defaultdict(float)
    for dev in sorted({o.device for o in trace.ops}):
        evs = sorted(((max(o.start, lo), min(o.end, hi), o.name) for o in trace.ops
                      if o.device == dev and o.end > lo and o.start < hi),
                     key=lambda e: (e[0], -e[1]))
        stack = []                                  # [end, name, child time]
        for a, b, name in evs:
            while stack and stack[-1][0] <= a:
                _self_close(stack, tot)
            if stack:
                stack[-1][2] += b - a
            stack.append([b, name, 0.0, a])
        while stack:
            _self_close(stack, tot)
    return dict(tot)


def _self_close(stack, tot):
    end, name, child, start = stack.pop()
    tot[short_name(name)] += max(end - start - child, 0.0) * 1e-9


def matching(trace: Trace, lo: float, hi: float, needles, exclude=()) -> Tuple[int, float]:
    """(events, device seconds) of ops whose name contains a needle and
    no excluded string."""
    n, s = 0, 0.0
    for o in trace.ops:
        if (o.end > lo and o.start < hi and any(k in o.name for k in needles)
                and not any(k in o.name for k in exclude)):
            n += 1
            s += (min(o.end, hi) - max(o.start, lo)) * 1e-9
    return n, s


def breakdown(trace: Trace, lo: float, hi: float, top: int = 10) -> dict:
    ops = sorted(op_totals(trace, lo, hi).items(), key=lambda kv: -kv[1])[:top]
    devs = sorted({o.device for o in trace.ops})
    by_label = defaultdict(float)
    for d in devs[:1]:
        for g in idle_gaps(trace, d, lo, hi):
            by_label[label(g, trace.spans)] += (g[1] - g[0]) * 1e-9
    gaps = sorted(by_label.items(), key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[k, v] for k, v in ops],
            "idle_gaps": [[k, v] for k, v in gaps]}
