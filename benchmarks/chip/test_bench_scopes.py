"""The phases of a step in a trace (``chipbench/scopes.py``): the op_name
parse on a hand-written compiled-program text, time by phase and idle
gaps by phase on a synthetic trace worked out by hand, the event
metadata of a trace file written from a text proto, the three readers,
and two small traces recorded on a TPU v5e as ``record_trace.py``
records them: the program before it named its phases
(``testdata/tiny.xplane.pb``) and after (``tiny-scoped.xplane.pb``)."""
import pytest

from chipbench import cells, scopes, xplane
from chipbench.xplane import Op, Span, Trace

RECORDED = cells.BENCH_DIR / "testdata" / "tiny.xplane.pb"
SCOPED = cells.BENCH_DIR / "testdata" / "tiny-scoped.xplane.pb"
PHASES = {"hapi.extract", "hapi.quantize", "hapi.dequantize", "hapi.tune", "hapi.adamw"}

HLO = """\
HloModule jit_train_step, entry_computation_layout={()->f32[]}

%fused_computation.7 (param_0.1: bf16[8,128]) -> bf16[8,128] {
  %param_0.1 = bf16[8,128]{1,0} parameter(0)
  ROOT %dot.3 = bf16[8,128]{1,0} dot(%param_0.1, %param_0.1), lhs_contracting_dims={1}, rhs_contracting_dims={0}, metadata={op_name="jit(train_step)/while/body/closed_call/hapi.extract/while/body/closed_call/dot_general" source_file="m.py" source_line=3}
}

%body.9 (p.2: (s32[], bf16[8,128])) -> (s32[], bf16[8,128]) {
  %p.2 = (s32[], bf16[8,128]{1,0}) parameter(0)
  %fusion.12 = bf16[8,128]{1,0} fusion(%p.2), kind=kOutput, calls=%fused_computation.7, metadata={op_name="jit(train_step)/while/body/closed_call/hapi.extract/while/body/closed_call/dot_general" source_file="m.py" source_line=3}
  %custom-call.4 = (s8[8,128]{1,0}, f32[8,1]{1,0}) custom-call(%fusion.12), custom_call_target="tpu_custom_call", metadata={op_name="jit(train_step)/while/body/closed_call/hapi.extract/while/body/closed_call/hapi.quantize/jit(quantize_int8_pallas)/pallas_call"}
  ROOT %tuple.5 = (s32[], bf16[8,128]{1,0}) tuple(%p.2, %fusion.12)
}

ENTRY %main.20 () -> f32[] {
  %convolution.6 = f32[8,8]{1,0} convolution(%a, %b), dim_labels=bf_io->bf, metadata={op_name="jit(train_step)/while/body/closed_call/transpose(jvp(hapi.tune))/while/body/closed_call/checkpoint/dot_general"}
  %multiply_subtract_fusion = f32[8]{0} fusion(%c), kind=kLoop, calls=%fc.2, metadata={op_name="jit(train_step)/hapi.adamw/sub"}
  %copy.488 = f32[8]{0} copy(%c)
  ROOT %while.13 = (s32[], bf16[8,128]{1,0}) while(%t), condition=%cond.1, body=%body.9, metadata={op_name="jit(train_step)/while"}
}
"""


def test_op_scopes_parse_by_hand():
    assert scopes.op_scopes(HLO) == {
        "%param_0.1": None,
        "%dot.3": "hapi.extract",                 # inside a fused computation
        "%p.2": None,
        "%fusion.12": "hapi.extract",             # in a while body
        "%custom-call.4": "hapi.quantize",        # innermost of two phases
        "%tuple.5": None,
        "%convolution.6": "hapi.tune",            # the backward pass
        "%multiply_subtract_fusion": "hapi.adamw",
        "%copy.488": None,                        # no metadata
        "%while.13": None,
    }


@pytest.mark.parametrize("op_name, phase", [
    ("jit(f)/hapi.extract/dot_general", "hapi.extract"),
    ("jit(f)/transpose(jvp(hapi.tune))/mul", "hapi.tune"),
    ("jit(f)/while/body/jvp(hapi.tune)/while/body/closed_call/add", "hapi.tune"),
    ("jit(f)/hapi.extract/while/body/hapi.quantize/jit(quantize_int8)/round", "hapi.quantize"),
    ("jit(f)/hapi.tune/hapi.dequantize/jit(dequantize_int8_pallas):", "hapi.dequantize"),
    ("jit(f)/hapi.adamw:", "hapi.adamw"),
    ("jit(f)/transpose(jvp(jit(log_softmax)))/reduce_sum:", None),
    ("jit(f)/while/body/closed_call/hapi_extract/mul", None),
    ("", None),
    (None, None),
])
def test_phase_of(op_name, phase):
    assert scopes.phase_of(op_name) == phase


SCOPES = {"%fusion.1": "hapi.extract", "%while.2": "hapi.extract",
          "%quantize_int8_pallas.3": "hapi.quantize", "%fusion.4": "hapi.tune",
          "%jvp_dequantize.5": "hapi.dequantize", "%fusion.6": "hapi.adamw",
          "%copy.7": None}


def _synthetic():
    ops = [Op("%fusion.1 = f32[8] fusion(f32[8])", 100, 200, 0),
           Op("%while.2 = (s32[]) while((s32[]) %t)", 200, 300, 0),
           Op("%quantize_int8_pallas.3 = (s8[8]) custom-call(bf16[8])", 220, 260, 0),
           Op("%jvp_dequantize.5 = bf16[8] custom-call(s8[8])", 320, 330, 0),
           Op("%fusion.4 = f32[8] fusion(f32[8])", 330, 400, 0),
           Op("%copy.7 = f32[8] copy(f32[8])", 400, 420, 0),
           Op("%fusion.6 = f32[8] fusion(f32[8])", 450, 500, 0),
           Op("%mystery.8 = f32[8] add(f32[8])", 520, 530, 0)]
    spans = [Span("bench.step_dispatch", 50, 60), Span("bench.step_wait", 60, 600)]
    return Trace(ops, spans, 1)


def test_phase_seconds_by_hand():
    tr = _synthetic()
    got = scopes.phase_seconds(tr, 50, 600, SCOPES)
    # the while's 100 less its nested quantize's 40; quantize out of extract
    assert got == pytest.approx({"hapi.extract": (100 + 60) * 1e-9,
                                 "hapi.quantize": 40e-9, "hapi.dequantize": 10e-9,
                                 "hapi.tune": 70e-9, "hapi.adamw": 50e-9,
                                 scopes.UNSCOPED: 20e-9, scopes.UNMAPPED: 10e-9})
    assert sum(got.values()) == pytest.approx(xplane.busy_ns(tr, 50, 600) * 1e-9)


def test_gaps_by_scope_by_hand():
    got = scopes.gaps_by_scope(_synthetic(), 50, 600, SCOPES)
    assert got == pytest.approx({
        "<edge>|hapi.extract": 50e-9,             # [50, 100]
        "hapi.extract|hapi.dequantize": 20e-9,    # [300, 320]: the while ends at 300
        "<unscoped>|hapi.adamw": 30e-9,           # [420, 450]
        "hapi.adamw|<unmapped>": 20e-9,           # [500, 520]
        "<unmapped>|<edge>": 70e-9})              # [530, 600]
    assert sum(got.values()) == pytest.approx(
        sum(b - a for a, b in xplane.idle_gaps(_synthetic(), 0, 50, 600)) * 1e-9)


def _event(meta_id, start_ns, end_ns):
    return (f"events {{ metadata_id: {meta_id} offset_ps: {int(start_ns * 1000)} "
            f"duration_ps: {int((end_ns - start_ns) * 1000)} }}")


def _write_trace(path, ops, op_names):
    """A trace file holding ``ops`` on one TPU and the benchmark's host
    spans of ``_synthetic``; ``op_names[i]`` is op i's ``tf_op`` (a
    string, or ``("ref", s)`` for one held as a ref value)."""
    from jax.profiler import ProfileData

    metas, stats = [], ['stat_metadata { key: 7 value { id: 7 name: "tf_op" } }']
    for i, (op, op_name) in enumerate(zip(ops, op_names), start=1):
        stat = ""
        if isinstance(op_name, tuple):
            stats.append(f'stat_metadata {{ key: {100 + i} value {{ id: {100 + i} '
                         f'name: "{op_name[1]}" }} }}')
            stat = f"stats {{ metadata_id: 7 ref_value: {100 + i} }}"
        elif op_name is not None:
            stat = f'stats {{ metadata_id: 7 str_value: "{op_name}" }}'
        metas.append(f'event_metadata {{ key: {i} value {{ id: {i} name: "{op.name}" '
                     f'{stat} }} }}')
    events = " ".join(_event(i, o.start, o.end) for i, o in enumerate(ops, start=1))
    host = [Span("bench.step_dispatch", 50, 60), Span("bench.step_wait", 60, 600)]
    host_events = " ".join(_event(1000 + i, s.start, s.end) for i, s in enumerate(host))
    host_metas = " ".join(f'event_metadata {{ key: {1000 + i} value {{ id: {1000 + i} '
                          f'name: "{s.name}" }} }}' for i, s in enumerate(host))
    text = f"""
planes {{ id: 1 name: "/device:TPU:0"
  lines {{ id: 1 name: "XLA Ops" timestamp_ns: 0 {events} }}
  {" ".join(metas)} {" ".join(stats)} }}
planes {{ id: 2 name: "/host:CPU"
  lines {{ id: 2 name: "python3" timestamp_ns: 0 {host_events} }}
  {host_metas} }}
"""
    path.parent.mkdir(parents=True)
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(text))


OP_NAMES = ["jit(train_step)/while/body/hapi.extract/while/body/dot_general:",
            ("ref", "jit(train_step)/while/body/hapi.extract/while:"),
            "jit(train_step)/hapi.extract/while/body/hapi.quantize/pallas_call:",
            "jit(train_step)/hapi.tune/hapi.dequantize/pallas_call:",
            "jit(train_step)/while/body/transpose(jvp(hapi.tune))/dot_general:",
            None,
            "jit(train_step)/hapi.adamw/sub:",
            "jit(train_step)/add:"]


@pytest.fixture
def trace_dir(tmp_path):
    """A run's trace directory as the profiler lays it out under the one
    ``run.py`` makes."""
    return tmp_path / "chipbench-trace-abc" / "plugins" / "profile" / "t"


def test_trace_file_metadata_and_readers(trace_dir):
    path = trace_dir / "host.xplane.pb"
    _write_trace(path, _synthetic().ops, OP_NAMES)
    tr = xplane.load(str(path))
    assert scopes.trace_op_scopes(str(path)) == dict(SCOPES, **{"%mystery.8": None})
    assert scopes.window_scopes(tr, str(path)) == scopes.trace_op_scopes(str(path))
    lo, hi = xplane.window(tr)
    ctx = dict(trace=tr, trace_path=str(path), lo=lo, hi=hi, steps=2)
    got = {m: cells.metric_reader(m)(ctx)
           for m in ("extract_ms.train", "tune_ms.train", "adamw_ms.train")}
    assert got == pytest.approx({"extract_ms.train": 160e-6 / 2,
                                 "tune_ms.train": 70e-6 / 2,
                                 "adamw_ms.train": 50e-6 / 2})


def test_readers_read_nothing_without_phases(trace_dir):
    """A program that names no phases, a trace whose file is gone, or a
    reader context without the file's path."""
    path = trace_dir / "host.xplane.pb"
    _write_trace(path, _synthetic().ops, [None] * 8)
    tr = xplane.load(str(path))
    lo, hi = xplane.window(tr)
    ctx = dict(trace=tr, trace_path=str(path), lo=lo, hi=hi, steps=2)
    assert scopes.phase_ms_per_step(ctx, "hapi.extract") is None
    _write_trace(trace_dir.parent / "u" / "host.xplane.pb", _synthetic().ops, OP_NAMES)
    ctx["trace_path"] = str(trace_dir.parent / "u" / "host.xplane.pb")
    assert cells.metric_reader("tune_ms.train")(ctx) == pytest.approx(70e-6 / 2)
    assert cells.metric_reader("tune_ms.train")(dict(ctx, trace_path=None)) is None
    path.unlink()
    assert scopes.window_scopes(tr, str(path)) == {}
    assert cells.metric_reader("tune_ms.train")(dict(ctx, trace_path=str(path))) is None


def test_another_runs_trace_is_not_read(trace_dir):
    """A trace file that does not hold this window's ops."""
    path = trace_dir / "host.xplane.pb"
    _write_trace(path, _synthetic().ops[:3], OP_NAMES[:3])
    assert scopes.window_scopes(_synthetic(), str(path)) == {}


def test_recorded_trace_maps_every_op_and_names_no_phase():
    """The small TPU v5e trace, recorded from the program before it named
    its phases: every op is in the metadata, under no phase."""
    if not RECORDED.exists():
        pytest.fail(f"missing {RECORDED}")
    tr = xplane.load(str(RECORDED))
    m = scopes.window_scopes(tr, str(RECORDED))
    assert m and not any(m.values())
    lo, hi = xplane.window(tr)
    got = scopes.phase_seconds(tr, lo, hi, m)
    assert set(got) == {scopes.UNSCOPED}
    assert got[scopes.UNSCOPED] == pytest.approx(xplane.busy_ns(tr, lo, hi) * 1e-9)
    assert cells.metric_reader("extract_ms.train")(dict(
        trace=tr, trace_path=str(RECORDED), lo=lo, hi=hi, steps=2)) is None


def test_recorded_scoped_trace_reads_every_phase():
    """The small TPU v5e trace of the program with its phases named: each
    phase holds time, the phases and the unscoped rest add up to the busy
    time, and the readers agree with ``phase_seconds``."""
    if not SCOPED.exists():
        pytest.fail(f"missing {SCOPED}")
    tr = xplane.load(str(SCOPED))
    m = scopes.window_scopes(tr, str(SCOPED))
    assert set(m.values()) == PHASES | {None}
    lo, hi = xplane.window(tr)
    got = scopes.phase_seconds(tr, lo, hi, m)
    assert set(got) == PHASES | {scopes.UNSCOPED}
    assert all(v > 0 for v in got.values())
    busy = xplane.busy_ns(tr, lo, hi) * 1e-9
    assert sum(got.values()) == pytest.approx(busy)
    assert got[scopes.UNSCOPED] < 0.5 * busy
    steps = sum(s.name == "bench.step_dispatch" for s in tr.spans)
    ctx = dict(trace=tr, trace_path=str(SCOPED), lo=lo, hi=hi, steps=steps)
    for metric, phase in (("extract_ms.train", "hapi.extract"), ("tune_ms.train", "hapi.tune"),
                          ("adamw_ms.train", "hapi.adamw")):
        assert cells.metric_reader(metric)(ctx) == pytest.approx(1e3 * got[phase] / steps)
