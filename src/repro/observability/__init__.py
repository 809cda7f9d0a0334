"""repro.observability — serving telemetry: metrics, tracing, export.

Stdlib only, apart from ``profile_span``, which imports jax's profiler
when it is first called. Three pieces, consumed by every serving tier:

* ``metrics`` — ``Counter``/``Gauge``/``Histogram`` behind a
  ``MetricsRegistry``; deterministic fixed-log-bucket histograms with
  interpolated p50/p95/p99, exact cross-host merging
  (``merge_snapshots``), Prometheus text exposition
  (``to_prometheus``).
* ``trace`` — ``TraceRecorder`` bounded ring of per-request lifecycle
  events (submit -> route -> steal -> dispatch -> settle) with JSONL
  export; ``NULL_RECORDER`` is the allocation-free disabled path.
* ``export`` — ``format_stats_line`` (the ONE stats-line formatter all
  serve.py modes share), ``StatsPrinter`` (periodic line), and
  ``MetricsServer`` (``/metrics`` + ``/metrics.json`` over stdlib
  http.server).

``profile_span(name)`` wraps device-dispatch legs in a
``jax.profiler.TraceAnnotation``, so gateway dispatches show up named in
a profiler trace; the registry itself never imports jax.
"""
from __future__ import annotations

from repro.observability.export import (
    MetricsServer,
    StatsPrinter,
    format_stats_line,
)
from repro.observability.metrics import (
    DEFAULT_MS_BOUNDS,
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    bucket_bounds_at,
    merge_snapshots,
    percentile_from_buckets,
    to_prometheus,
)
from repro.observability.trace import (
    NULL_RECORDER,
    NullRecorder,
    TraceRecorder,
    read_jsonl,
)

def profile_span(name: str):
    """Context manager naming a dispatch leg in a jax profiler trace."""
    from jax.profiler import TraceAnnotation

    return TraceAnnotation(name)


__all__ = ["Counter", "Gauge", "Histogram", "MetricsRegistry",
           "DEFAULT_MS_BOUNDS", "merge_snapshots",
           "percentile_from_buckets", "bucket_bounds_at", "to_prometheus",
           "TraceRecorder", "NullRecorder", "NULL_RECORDER", "read_jsonl",
           "MetricsServer", "StatsPrinter", "format_stats_line",
           "profile_span"]
