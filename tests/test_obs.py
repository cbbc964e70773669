"""Observability subsystem tests (repro.obs).

* schema stability, both directions: every span name emitted anywhere in
  ``src/repro`` (grep for the ``tr.emit(``/``tr.begin(`` convention) is
  in :data:`repro.obs.SPAN_NAMES` and vice versa; same for metric keys
  (``mx.inc``/``mx.observe``/``mx.gauge_set``) vs
  :data:`repro.obs.METRIC_KEYS`, and ``device_scope("...")`` sites vs
  :data:`repro.obs.DEVICE_SCOPES`;
* determinism: same seed => identical span digest; tracing on vs off
  leaves the event-log digest byte-identical (the golden hashes in
  tests/test_scheduler.py run with tracing on, so this is the only
  missing direction);
* span trees: a burst request's children cover storage read, admission
  and pushdown compute, causally linked to the root;
* Perfetto export: the chrome-trace doc validates, maps tiers->pids and
  tracks->tids via metadata, spans >= 3 tiers, and consecutive
  iterations overlap (the paper's Fig. 9 picture);
* metrics registry: counter/gauge/histogram families, label-cardinality
  bound, family-mixing guard, deterministic dump, and the dual-write
  invariant vs the legacy scheduler attributes;
* percentiles: shared nearest-rank math (the historical floor-biased
  ``int(q*n)`` regression) and ReplayVerdict agreement.
"""
import json
import os
import re
import subprocess
import sys

import pytest

from repro.api import HapiCluster, TenantSpec
from repro.obs import (
    DEVICE_SCOPES,
    LAYER_SCOPES,
    METRIC_KEYS,
    SPAN_NAMES,
    MetricsRegistry,
    Tracer,
    chrome_trace,
    percentile,
    validate_chrome_trace,
    write_trace,
)
from repro.replay import TraceReplayer, WorkloadSpec, generate

SRC_ROOT = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "src", "repro")

SPAN_PAT = re.compile(
    r"\btr\.(?:emit_fast|emit|begin)\(\s*[\"']([a-z][a-z0-9_.-]{1,30})[\"']")
METRIC_PAT = re.compile(
    r"\bmx\.(?:inc|observe|gauge_set)\(\s*[\"']([a-z][a-z0-9_.-]{1,40})[\"']")
SCOPE_PAT = re.compile(
    r"\bdevice_scope\(\s*[\"']([a-z][a-z0-9_.-]{1,40})[\"']")


def _grep_src(pat):
    hits = set()
    for dirpath, _, files in os.walk(SRC_ROOT):
        for fn in files:
            if fn.endswith(".py"):
                with open(os.path.join(dirpath, fn)) as f:
                    hits.update(pat.findall(f.read()))
    return hits


def _burst_cluster(seed=11, *, tracing=True):
    c = (HapiCluster(seed=seed)
         .with_servers(2)
         .with_storage(n_nodes=4, replication=2)
         .with_dataset("ds", n_samples=2000, object_size=500, n_classes=100)
         .with_tracing(tracing))
    c.submit_burst("ds", "alexnet", tenant=0, n_classes=100)
    c.submit_burst("ds", "alexnet", tenant=1, n_classes=100)
    return c


# ---------------------------------------------------------------------------
# Schema stability (both directions, mirroring the event-kind tests)
# ---------------------------------------------------------------------------
def test_every_emitted_span_name_is_in_schema():
    emitted = _grep_src(SPAN_PAT)
    assert emitted, "grep found no tr.emit/tr.begin sites at all"
    missing = emitted - SPAN_NAMES
    assert not missing, (
        f"span names emitted in src/repro but absent from "
        f"repro.obs.schema.SPAN_NAMES: {sorted(missing)}")


def test_schema_has_no_phantom_span_names():
    phantom = SPAN_NAMES - _grep_src(SPAN_PAT)
    assert not phantom, (
        f"schema span names no longer emitted anywhere: {sorted(phantom)}")


def test_every_emitted_metric_key_is_in_schema():
    emitted = _grep_src(METRIC_PAT)
    assert emitted, "grep found no mx.inc/observe/gauge_set sites at all"
    missing = emitted - METRIC_KEYS
    assert not missing, (
        f"metric keys emitted in src/repro but absent from "
        f"repro.obs.schema.METRIC_KEYS: {sorted(missing)}")


def test_schema_has_no_phantom_metric_keys():
    phantom = METRIC_KEYS - _grep_src(METRIC_PAT)
    assert not phantom, (
        f"schema metric keys no longer emitted anywhere: {sorted(phantom)}")


def test_every_device_scope_site_is_in_schema():
    used = _grep_src(SCOPE_PAT)
    assert used, "grep found no device_scope sites at all"
    missing = used - DEVICE_SCOPES - LAYER_SCOPES
    assert not missing, (
        f"device_scope names not registered in "
        f"repro.obs.schema.DEVICE_SCOPES or LAYER_SCOPES: {sorted(missing)}")


def test_schema_has_no_phantom_device_scopes():
    phantom = (DEVICE_SCOPES | LAYER_SCOPES) - _grep_src(SCOPE_PAT)
    assert not phantom, (
        f"schema device scopes no longer used anywhere: {sorted(phantom)}")


def test_obs_import_leaves_jax_unloaded():
    """Simulator-only users of repro.obs do not pay for importing jax."""
    code = "import sys, repro.obs; sys.exit('jax' in sys.modules)"
    env = dict(os.environ, PYTHONPATH=os.path.dirname(SRC_ROOT))
    assert subprocess.run([sys.executable, "-c", code], env=env,
                          timeout=120).returncode == 0


def test_unknown_names_rejected():
    tr = Tracer()
    with pytest.raises(ValueError, match="SPAN_NAMES"):
        tr.emit("made-up", 0.0, 1.0, tier="compute", track="x")
    with pytest.raises(ValueError, match="TIERS"):
        tr.emit("request", 0.0, 1.0, tier="made-up", track="x")
    mx = MetricsRegistry()
    with pytest.raises(ValueError, match="METRIC_KEYS"):
        mx.inc("made_up_total")


# ---------------------------------------------------------------------------
# Determinism
# ---------------------------------------------------------------------------
def test_span_digest_deterministic_per_seed():
    a = _burst_cluster()
    a.drain()
    b = _burst_cluster()
    b.drain()
    assert len(a.tracer) > 0
    assert a.tracer.digest() == b.tracer.digest()
    c = _burst_cluster(seed=12)
    c.drain()
    assert c.tracer.digest() != a.tracer.digest()


def test_event_log_byte_identical_with_tracing_off():
    on = _burst_cluster(tracing=True)
    on.drain()
    off = _burst_cluster(tracing=False)
    off.drain()
    assert on.event_digest() == off.event_digest()
    assert len(on.tracer) > 0
    assert len(off.tracer) == 0          # disabled tracer collects nothing
    # metrics stay on regardless of the tracing toggle
    assert off.metrics().total("requests_total") == \
        on.metrics().total("requests_total") > 0


# ---------------------------------------------------------------------------
# Span trees
# ---------------------------------------------------------------------------
def test_burst_request_span_tree_causality():
    c = _burst_cluster()
    c.drain()
    tr = c.tracer
    roots = [s for s in tr.roots() if s.name == "request"]
    assert roots, "no request root spans emitted"
    # every served request's tree covers the cross-tier pipeline
    child_names = {s.name for r in roots for s in tr.children(r.span_id)}
    assert {"storage.read", "cos.compute"} <= child_names
    assert tr.by_name("admission"), "no admission spans emitted"
    for r in roots[:50]:
        for ch in tr.children(r.span_id):
            assert ch.t0 >= r.t0
            assert ch.t1 <= r.t1 + 1e-9   # root extended to completion
    # tracks() groups by tier/resource; compute accelerators are rows
    assert any(k.startswith("compute/") for k in tr.tracks())
    assert any(k.startswith("storage/") for k in tr.tracks())


def test_tracer_begin_extend_and_disabled_noop():
    tr = Tracer()
    sid = tr.begin("request", 1.0, tier="control", track="tenant0")
    assert tr.spans[sid].duration == 0.0
    tr.extend(sid, 3.0)
    tr.extend(sid, 2.0)                   # monotonic: max-update only
    assert tr.spans[sid].t1 == 3.0
    off = Tracer(enabled=False)
    assert off.emit("request", 0.0, 1.0, tier="control", track="x") == -1
    off.extend(-1, 5.0)                   # no-op, no raise
    assert len(off) == 0


# ---------------------------------------------------------------------------
# Perfetto export
# ---------------------------------------------------------------------------
def _epoch_cluster():
    from repro.core.profiler import profile_layered
    from repro.models.vision import alexnet

    prof = profile_layered(alexnet(100))
    c = (HapiCluster(seed=7)
         .with_servers(2, n_accelerators=2, flops_per_accel=65e12)
         .with_dataset("ds", n_samples=2000, object_size=500, n_classes=100))
    t0 = c.tenant(TenantSpec(model="alexnet", profile=prof,
                             bandwidth=1e9 / 8, client_flops=65e12))
    t1 = c.tenant(TenantSpec(model="alexnet", profile=prof,
                             bandwidth=1e9 / 8, client_flops=65e12))
    c.run_epochs([(t0, "ds", 1000), (t1, "ds", 1000)], max_iterations=3)
    return c


def test_chrome_trace_valid_and_spans_three_tiers(tmp_path):
    c = _epoch_cluster()
    path = str(tmp_path / "trace.json")
    doc = write_trace(c.tracer, path)
    validate_chrome_trace(doc)
    with open(path) as f:
        reloaded = json.load(f)
    validate_chrome_trace(reloaded)

    meta = [e for e in doc["traceEvents"] if e["ph"] == "M"]
    xs = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    tiers = {e["args"]["name"] for e in meta if e["name"] == "process_name"}
    assert len(tiers) >= 3
    assert {"storage", "compute", "client"} <= tiers
    # pid->tier mapping is honest: every X event's pid names its span tier
    pid_tier = {e["pid"]: e["args"]["name"] for e in meta
                if e["name"] == "process_name"}
    by_id = {s.span_id: s for s in c.tracer.spans}
    for e in xs:
        assert pid_tier[e["pid"]] == by_id[e["args"]["span_id"]].tier
    assert len(xs) == len(c.tracer)


def test_consecutive_iterations_overlap_in_trace():
    # the paper's Fig. 9 picture: iteration i+1's prefetch overlaps
    # iteration i (and the two tenants' epochs overlap each other)
    c = _epoch_cluster()
    its = sorted(c.tracer.by_name("iteration"), key=lambda s: s.t0)
    assert len(its) >= 4
    assert any(a.t1 > b.t0 for a, b in zip(its, its[1:])), (
        "no two consecutive iteration spans overlap — the pipeline "
        "parallelism the split exists for is not visible in the trace")


# ---------------------------------------------------------------------------
# Metrics registry
# ---------------------------------------------------------------------------
def test_counters_gauges_histograms_and_dump_deterministic():
    def fill(mx):
        mx.inc("requests_total", tenant=1)
        mx.inc("requests_total", 2.0, tenant=0)
        mx.gauge_set("trunk_utilization", 0.5, link="wan")
        mx.observe("queue_delay_seconds", 0.25, tenant=0)
        mx.observe("queue_delay_seconds", 0.75, tenant=1)

    a, b = MetricsRegistry(), MetricsRegistry()
    fill(a)
    fill(b)
    assert a.total("requests_total") == 3.0
    assert a.counter_value("requests_total", tenant=0) == 2.0
    assert a.gauge_value("trunk_utilization", link="wan") == 0.5
    # label-less histogram query merges every series of the key
    assert a.histogram("queue_delay_seconds").count == 2
    assert a.percentile("queue_delay_seconds", 0.99) == 0.75
    assert a.dump() == b.dump()
    assert a.snapshot() == b.snapshot()
    snap = a.snapshot()
    assert snap["counters"]["requests_total{tenant=0}"] == 2.0
    assert "queue_delay_seconds{tenant=1}" in snap["histograms"]


def test_label_cardinality_bound():
    mx = MetricsRegistry(max_label_sets=4)
    for i in range(4):
        mx.inc("requests_total", tenant=i)
    mx.inc("requests_total", tenant=0)    # existing set: fine
    with pytest.raises(ValueError, match="label-cardinality bound"):
        mx.inc("requests_total", tenant=99)
    assert mx.label_set_count("requests_total") == 4


def test_family_mixing_rejected():
    mx = MetricsRegistry()
    mx.inc("requests_total")
    with pytest.raises(ValueError, match="different .* family"):
        mx.observe("requests_total", 1.0)
    with pytest.raises(ValueError, match="different .* family"):
        mx.gauge_set("requests_total", 1.0)


def test_fleet_metrics_match_legacy_scheduler_attrs():
    # the dual-write invariant benchmarks/qos_compute.py relies on:
    # registry counters are incremented at the same scheduler sites with
    # the same values as the legacy attributes
    c = (HapiCluster(seed=3)
         .with_servers(2, n_accelerators=1, flops_per_accel=65e12)
         .with_dataset("ds", n_samples=1500, object_size=500, n_classes=100)
         .with_scheduler(coalescing=True))
    for t in (0, 1):
        c.submit_burst("ds", "alexnet", tenant=t, n_classes=100)
    responses = c.drain()
    mx = c.metrics()
    sched = c.fleet.scheduler
    assert mx.total("reload_bytes_total") == sched.reload_bytes
    assert mx.total("reload_saved_bytes_total") == sched.reload_saved_bytes
    assert mx.total("coalesce_total") == sched.coalesced
    assert mx.total("responses_total") == len(responses)
    assert mx.total("requests_total") == len(responses)
    assert mx.histogram("queue_delay_seconds").count == len(responses)
    assert mx.total("events_total") == len(c.sim.log.events)


# ---------------------------------------------------------------------------
# Percentiles (shared nearest-rank math)
# ---------------------------------------------------------------------------
def test_percentile_nearest_rank():
    vals = [float(i) for i in range(1, 101)]
    assert percentile(vals, 0.50) == 50.0
    assert percentile(vals, 0.95) == 95.0
    assert percentile(vals, 0.99) == 99.0
    assert percentile(vals, 1.00) == 100.0
    assert percentile([], 0.99) == 0.0
    # the historical floor-biased int(q*n) indexing returned 6.0 here
    assert percentile([float(i) for i in range(1, 11)], 0.50) == 5.0


def test_replay_verdict_uses_shared_percentile():
    # the regression this PR fixed: ReplayVerdict's local int(q*n)
    # indexing was floor-biased by one rank; it must now be the exact
    # nearest-rank implementation the metrics histograms use
    from repro.obs import hist
    from repro.replay import replayer

    assert replayer._percentile is hist.percentile


def test_replay_tracer_opt_in_and_sampled():
    trace = generate(WorkloadSpec(n_requests=5_000, duration=300.0, seed=2))
    full = Tracer()
    v = TraceReplayer(trace, tracer=full, trace_sample=1).run()
    assert len(full.by_name("replay.request")) == v.n_executed > 0
    assert v.queue_delay_p50 <= v.queue_delay_p95 <= v.queue_delay_p99 \
        <= v.queue_delay_max
    # default sampling: deterministically every 8th executed request
    sampled = Tracer()
    vs = TraceReplayer(trace, tracer=sampled).run()
    assert len(sampled.by_name("replay.request")) == vs.n_executed // 8 > 0
    # tracing never perturbs the decision path, sampled or not
    v2 = TraceReplayer(trace).run()
    assert v2.decision_hash == v.decision_hash == vs.decision_hash
    assert v2.queue_delay_p99 == v.queue_delay_p99
    # and the span trace exports like any other
    validate_chrome_trace(chrome_trace(full))
