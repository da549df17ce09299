"""repro.obs — the repo-wide observability subsystem.

Three pillars (see ISSUE 8 / README "Observability"):

* :mod:`repro.obs.funnel` — in-graph :class:`FunnelStats`: per-query
  candidate counts through the PLAID stage funnel, computed as cheap
  traced reductions inside ``core.pipeline`` and merged across every
  partitioned execution layer.
* :mod:`repro.obs.trace` — ring-buffered span :class:`Tracer` with
  Chrome trace-event JSON export (Perfetto-loadable), span ids and
  parents, profiler annotations on the device trace's clock, and a
  ``jax.profiler.trace`` wrapper for device captures.
* :mod:`repro.obs.metrics` — counters / gauges / latency windows behind a
  :class:`MetricsRegistry` with JSON-snapshot and Prometheus-text
  exporters.
"""
from repro.obs.funnel import FunnelStats
from repro.obs.metrics import (
    Counter,
    Counters,
    Gauge,
    LatencyWindow,
    MetricsRegistry,
    get_registry,
)
from repro.obs.trace import Span, Tracer, get_tracer

__all__ = [
    "FunnelStats",
    "Counter",
    "Counters",
    "Gauge",
    "LatencyWindow",
    "MetricsRegistry",
    "get_registry",
    "Span",
    "Tracer",
    "get_tracer",
]
