"""Registered enumerations of the observability layer.

One module is the single source of truth for what the tracer and the
metrics registry may emit, mirroring how
:data:`repro.replay.schema.EVENT_KINDS` pins the event-log vocabulary:

* :data:`SPAN_NAMES` — every structured-span name the runtime emits
  (``tr.emit(...)`` / ``tr.begin(...)`` sites in ``src/repro``). The
  schema-stability tests grep the source both ways: a span name emitted
  anywhere must be registered here, and a registered name must still be
  emitted somewhere.
* :data:`METRIC_KEYS` — every metric key the runtime touches
  (``mx.inc`` / ``mx.observe`` / ``mx.gauge_set`` sites), same
  both-direction guarantee.
* :data:`TIERS` — the process-level grouping of the Perfetto export:
  one ``pid`` per tier, one ``tid`` per resource track within it.
* :data:`DEVICE_SCOPES` — the phases of the split fine-tune program,
  named on the device ops themselves (``device_scope`` sites). Unlike
  the three sets above these are not virtual time: each is a
  ``jax.named_scope``, compile-time metadata (``op_name``) that a
  profiler trace of the compiled program carries on every op.
* :data:`LAYER_SCOPES` — device scopes of single layers inside those
  phases (the expert layer's routing, grouped matmuls and shared
  expert). They carry no ``hapi.`` prefix, so a phase reader, which
  keeps the innermost ``hapi.*`` component, still counts their ops in
  the phase around them.

The tracer and the registry validate against these sets at emission
time, so an unregistered name fails the emitting run loudly instead of
silently producing an unqueryable trace.
"""
from __future__ import annotations

#: Causal-tree span names (request lifecycle across the tiers).
SPAN_NAMES = frozenset({
    # request lifecycle (fleet intake -> served -> pulled)
    "request",
    # compute-tier admission + execution (scheduler/server)
    "admission", "model.load", "cos.compute", "quantize",
    # storage tier
    "storage.read",
    # wire + client training loop
    "wire.transfer", "client.compute", "iteration",
    # decision-path replay (one lightweight span per replayed request)
    "replay.request",
})

#: Perfetto process groups: every span carries exactly one tier.
TIERS = frozenset({"control", "storage", "compute", "network", "client"})

#: Metric keys (counters, gauges and histograms with label sets).
METRIC_KEYS = frozenset({
    # simulator core
    "events_total",
    # request lifecycle
    "requests_total", "responses_total", "queue_delay_seconds",
    "stage_seconds", "slo_miss_total",
    # compute-tier scheduler / coalescer
    "reload_bytes_total", "reload_saved_bytes_total", "warm_hit_total",
    "coalesce_total",
    # warm-weight cache
    "evict_total", "cache_resident_bytes",
    # elasticity
    "scale_events_total",
    # network fabric
    "trunk_bytes_total", "trunk_utilization",
    # scaling signals
    "accel_utilization",
})


#: Named scopes of the split fine-tune program's phases on the device.
DEVICE_SCOPES = frozenset({
    "hapi.extract",      # frozen prefix at the COS batch (storage side)
    "hapi.quantize",     # int8 boundary, storage side
    "hapi.dequantize",   # int8 boundary, compute side
    "hapi.tune",         # suffix forward and backward, head, loss, accumulation
    "hapi.adamw",        # gradient averaging, clip and the AdamW update
})


#: Named scopes of single layers, nested inside the phases above.
LAYER_SCOPES = frozenset({
    "moe.route",         # expert layer: router, dispatch to the held experts, combine
    "moe.experts",       # expert layer: grouped matmuls of the held experts
    "moe.shared",        # expert layer: the shared expert
})


def device_scope(name: str):
    """``jax.named_scope(name)`` for a registered phase or layer scope.

    The scope costs nothing at run time: it only names the ops traced
    inside it, in their ``op_name`` metadata (the backward pass keeps it
    as ``transpose(jvp(<name>))``)."""
    if name not in DEVICE_SCOPES | LAYER_SCOPES:
        raise ValueError(
            f"device scope {name!r} is not in repro.obs.schema.DEVICE_SCOPES or "
            f"LAYER_SCOPES; register it there so trace readers can find it")
    import jax

    return jax.named_scope(name)


def validate_span_name(name: str) -> str:
    """Refuse to emit a span name the schema does not know."""
    if name not in SPAN_NAMES:
        raise ValueError(
            f"span name {name!r} is not in repro.obs.schema.SPAN_NAMES; "
            f"register it there so traces stay queryable")
    return name


def validate_tier(tier: str) -> str:
    if tier not in TIERS:
        raise ValueError(
            f"span tier {tier!r} is not in repro.obs.schema.TIERS")
    return tier


def validate_metric_key(key: str) -> str:
    """Refuse to touch a metric key the schema does not know."""
    if key not in METRIC_KEYS:
        raise ValueError(
            f"metric key {key!r} is not in repro.obs.schema.METRIC_KEYS; "
            f"register it there so dashboards stay stable")
    return key
