"""Cross-tier observability: structured spans, metrics, timeline export,
and the names of the program's phases on the device.

Three pieces run on the simulator's virtual clock, all deterministic and
all strictly additive next to the golden-hashed :class:`EventLog`:

* :mod:`repro.obs.span` — ``Span``/``Tracer`` causal request trees
  (storage read -> admission -> pushdown compute -> wire -> client).
* :mod:`repro.obs.metrics` — ``MetricsRegistry`` counters / gauges /
  histograms with label sets and a deterministic text dump.
* :mod:`repro.obs.export` — Chrome-trace / Perfetto JSON rendering
  (one process per tier, one thread per resource track).

The fourth is not virtual time: :func:`device_scope` names the split
fine-tune program's phases (:data:`DEVICE_SCOPES`) and single layers
inside them (:data:`LAYER_SCOPES`) on its device ops, so a profiler
trace of the real program on the chip splits a step into extract,
quantize, dequantize, tune and AdamW, and the expert layer out of them.

Vocabulary is pinned by :mod:`repro.obs.schema`; shared percentile math
lives in :mod:`repro.obs.hist`.
"""
from repro.obs.export import chrome_trace, validate_chrome_trace, write_trace
from repro.obs.hist import DEFAULT_TIME_BUCKETS, bucket_counts, percentile
from repro.obs.metrics import Histogram, MetricsRegistry
from repro.obs.schema import (DEVICE_SCOPES, LAYER_SCOPES, METRIC_KEYS, SPAN_NAMES, TIERS,
                              device_scope)
from repro.obs.span import Span, Tracer

__all__ = [
    "Span", "Tracer", "Histogram", "MetricsRegistry",
    "chrome_trace", "validate_chrome_trace", "write_trace",
    "percentile", "bucket_counts", "DEFAULT_TIME_BUCKETS",
    "SPAN_NAMES", "METRIC_KEYS", "TIERS", "DEVICE_SCOPES", "LAYER_SCOPES", "device_scope",
]
