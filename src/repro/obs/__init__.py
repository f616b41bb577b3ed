"""Observability: traced on-device metrics, engine span tracing, export.

Three layers (ISSUE 9 / ROADMAP item 5 sensor substrate):

* ``obs.metrics`` — ``MetricsState``, a pytree of int32 counters and
  per-layer expert-load histograms that rides INSIDE the jitted decode
  cache (zero host syncs, traced leaves so value churn never retraces).
* ``obs.tracing`` — ``SpanTracer``: engine spans as ``engine_*`` profiler
  annotations, with an opt-in buffer exportable as Chrome-trace JSON.
* ``obs.export`` — ``MetricsSnapshot`` + Prometheus text exposition,
  structured JSON log lines, and a scrape server for the serve CLI.
"""
from .metrics import MetricsState, ObsCache, metrics_spec
from .tracing import SpanTracer
from .export import (MetricsSnapshot, MetricsServer, parse_prometheus,
                     render_prometheus, snapshot_json_line)

__all__ = [
    "MetricsState", "ObsCache", "metrics_spec",
    "SpanTracer",
    "MetricsSnapshot", "MetricsServer", "render_prometheus",
    "parse_prometheus", "snapshot_json_line",
]
