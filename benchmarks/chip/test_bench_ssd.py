"""``ssd_ms.train`` on synthetic op lists: the device time and the event
count of the Pallas SSD scan kernels per step, read by the name the
kernels carry in a TPU trace."""
import importlib.util

import pytest

from chipbench import cells
from chipbench.xplane import Op, Span, Trace

READER = cells.BENCH_DIR / "metrics" / "ssd_ms.train.py"


def _module():
    spec = importlib.util.spec_from_file_location("chipbench_metric_ssd", READER)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _ssd_ops(steps: int, frozen: int = 36, trainable: int = 12):
    """Each step's ops as the mamba2 job runs them with the Pallas scan:
    one forward in each frozen layer, two forwards (the first pass and the
    rematerialized one) and one backward in each trainable layer, each
    followed by an XLA fusion of the layer's other work."""
    names = ([("%ssd_scan_pallas.37", 10)] * frozen
             + [("%ssd_scan_pallas.36", 10)] * trainable
             + [("%ssd_scan_pallas.38", 10), ("%ssd_scan_pallas.39", 30)] * trainable)
    ops, t = [], 0
    for _ in range(steps):
        for name, dur in names:
            ops.append(Op(f"{name} = (f32[8]) custom-call(bf16[8])", t, t + dur, 0))
            ops.append(Op("%fusion.7 = f32[8] fusion(f32[8])", t + dur, t + dur + 5, 0))
            t += dur + 5
    return ops, t


def test_ssd_kernels_read_by_hand():
    """Two steps: 72 kernel events a step (36 + 12 x 2 forwards, 12
    backwards) and their summed time; the fusions between them and the
    int8 kernel are not counted."""
    ops, end = _ssd_ops(2)
    ops.append(Op("%quantize_int8_pallas.3 = (s8[8]) custom-call(bf16[8])", end, end + 7, 0))
    tr = Trace(ops, [Span("bench.step_wait", 0, end + 10)], 1)
    ctx = dict(trace=tr, lo=0, hi=end + 10, steps=2)
    ns = 36 * 10 + 12 * 10 + 12 * (10 + 30)
    assert _module().per_step(ctx) == (72, pytest.approx(ns * 1e-9))
    assert cells.metric_reader("ssd_ms.train")(ctx) == pytest.approx(ns * 1e-6)


def test_ssd_kernels_clipped_to_the_window():
    """A kernel event that starts before the window counts only its part
    inside it."""
    ops, end = _ssd_ops(1, frozen=2, trainable=0)      # [0, 10] and [15, 25]
    ctx = dict(trace=Trace(ops, [], 1), lo=5, hi=end, steps=1)
    assert _module().per_step(ctx) == (2, pytest.approx(15e-9))


@pytest.mark.parametrize("steps, ops", [
    (1, [Op("%fusion.1 = f32[8] fusion(f32[8])", 0, 100, 0)]),   # XLA's scan
    (0, _ssd_ops(1)[0]),                                         # no steps
])
def test_ssd_kernels_absent_read_nothing(steps, ops):
    ctx = dict(trace=Trace(ops, [], 1), lo=0, hi=10_000, steps=steps)
    assert cells.metric_reader("ssd_ms.train")(ctx) is None
