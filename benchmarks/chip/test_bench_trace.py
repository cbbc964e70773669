"""The trace reduction: by hand on a small synthetic trace, and on a small
trace recorded on a TPU v5e (``testdata/tiny.xplane.pb``: the mamba2
fine-tune job at a small width, a few steps under the profiler)."""
import numpy as np
import pytest

from chipbench import cells, xplane
from chipbench.xplane import Op, Span, Trace

RECORDED = cells.BENCH_DIR / "testdata" / "tiny.xplane.pb"


def _synthetic():
    ops = [Op("%fusion.1 = f32[8] fusion(f32[8])", 100, 200, 0),
           Op("%fusion.2 = f32[8] fusion(f32[8])", 200, 260, 0),
           Op("%while.3 = (s32[]) while((s32[]) %t)", 300, 440, 0),
           Op("%quantize_int8_pallas.3 = (s8[8]) custom-call(bf16[8])", 300, 340, 0),
           Op("%jvp_jit_dequantize_int8_pallas__.3 = bf16[8] custom-call(s8[8])", 400, 430, 0),
           Op("%fusion.1 = f32[8] fusion(f32[8])", 600, 650, 0)]
    spans = [Span("bench.data_next", 50, 90), Span("bench.step_dispatch", 90, 110),
             Span("bench.step_wait", 110, 700)]
    return Trace(ops, spans, 1)


def test_window_busy_and_gaps_by_hand():
    tr = _synthetic()
    lo, hi = xplane.window(tr)
    assert (lo, hi) == (50, 700)
    # union: [100, 260] + [300, 440] + [600, 650] = 160 + 140 + 50
    assert xplane.busy_ns(tr, lo, hi) == 350
    assert xplane.idle_gaps(tr, 0, lo, hi) == [(50, 100), (260, 300), (440, 600), (650, 700)]
    assert xplane.label((50, 100), tr.spans) == "bench.data_next"
    assert xplane.label((440, 600), tr.spans) == "bench.step_wait"
    # clipping to a window
    assert xplane.busy_ns(tr, 120, 320) == (260 - 120) + (320 - 300)


def test_self_times_and_kernel_matching_by_hand():
    tr = _synthetic()
    tot = xplane.op_totals(tr, 50, 700)
    assert tot == pytest.approx({"%fusion.1 fusion": 150e-9, "%fusion.2 fusion": 60e-9,
                                 "%while.3 while": 70e-9,      # 140 less its body
                                 "%quantize_int8_pallas.3 custom-call": 40e-9,
                                 "%jvp_jit_dequantize_int8_pallas__.3 custom-call": 30e-9})
    assert sum(tot.values()) == pytest.approx(xplane.busy_ns(tr, 50, 700) * 1e-9)
    assert xplane.matching(tr, 50, 700, ("quantize_int8_pallas",), ("dequantize",)) \
        == (1, pytest.approx(40e-9))
    assert xplane.matching(tr, 50, 700, ("dequantize_int8_pallas",)) \
        == (1, pytest.approx(30e-9))
    b = xplane.breakdown(tr, 50, 700)
    assert b["device_ops"][0] == ["%fusion.1 fusion", pytest.approx(150e-9)]
    assert b["idle_gaps"][0] == ["bench.step_wait", pytest.approx(250e-9)]


@pytest.fixture(scope="module")
def recorded():
    if not RECORDED.exists():
        pytest.fail(f"missing {RECORDED}")
    return xplane.load(str(RECORDED))


def test_recorded_trace_has_device_ops_and_spans(recorded):
    assert recorded.n_devices == 1
    assert recorded.ops and recorded.spans
    assert {s.name for s in recorded.spans} == {"bench.data_next", "bench.step_dispatch",
                                                "bench.step_wait"}


def test_recorded_busy_is_the_union(recorded):
    """The merged-interval union against a timeline marked at 10 ns."""
    lo, hi = xplane.window(recorded)
    busy = xplane.busy_ns(recorded, lo, hi)
    grid = np.zeros(int((hi - lo) // 10) + 1, bool)
    for o in recorded.ops:
        a, b = max(o.start, lo), min(o.end, hi)
        if b > a:
            grid[int((a - lo) // 10):int((b - lo) // 10)] = True
    assert busy == pytest.approx(grid.sum() * 10, rel=0.02)
    gaps = xplane.idle_gaps(recorded, 0, lo, hi)
    assert sum(b - a for a, b in gaps) + busy == pytest.approx(hi - lo)


def test_recorded_kernels_are_found(recorded):
    lo, hi = xplane.window(recorded)
    nq, sq = xplane.matching(recorded, lo, hi, ("quantize_int8_pallas",), ("dequantize",))
    nd, sd = xplane.matching(recorded, lo, hi, ("dequantize_int8_pallas",))
    steps = sum(s.name == "bench.step_dispatch" for s in recorded.spans)
    assert nq == steps > 0 and nd >= steps
    assert 0 < sq < (hi - lo) * 1e-9 and 0 < sd < (hi - lo) * 1e-9
