"""Compatibility shim: the serving stats primitives moved to ``repro.obs``.

``LatencyWindow`` (the exact-percentile ring buffer) and ``Counters`` (the
named-counter bag, now STRICT by default — incrementing a name the bag was
not constructed with raises instead of silently creating an unread
counter) live in :mod:`repro.obs.metrics` alongside the rest of the
metrics substrate (gauges, the process-wide registry and its
Prometheus/JSON exporters).  Import from ``repro.obs`` in new code; this
module keeps the historical ``repro.serving.stats`` names working.
"""
from __future__ import annotations

from repro.obs.metrics import Counters, LatencyWindow

__all__ = ["Counters", "LatencyWindow"]
