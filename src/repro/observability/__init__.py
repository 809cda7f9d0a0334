"""repro.observability — serving telemetry: metrics, tracing, export.

Stdlib only, apart from ``profile_span`` and ``compile_count``, which
import jax's profiler and monitoring when they are first called. Three
pieces, consumed by every serving tier:

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

``profile_span(name, **args)`` names one phase of a serving thread's
tick in a ``jax.profiler.TraceAnnotation``: the spans share the device
trace's clock, and ``args`` (row counts) land as the event's stats while
its name stays stable. ``compile_count`` reads one process-wide listener
on XLA backend compilations. The registry itself never imports jax.
"""
from __future__ import annotations

import threading

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

_TraceAnnotation = None


def profile_span(name: str, **args):
    """Context manager naming one phase of the serving thread in a jax
    profiler trace; ``args`` become the event's stats. About a
    microsecond with the profiler off."""
    global _TraceAnnotation
    if _TraceAnnotation is None:
        from jax.profiler import TraceAnnotation

        _TraceAnnotation = TraceAnnotation
    return _TraceAnnotation(name, **args)


_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_compiles = 0
_compile_lock = threading.Lock()
_listening = False


def _on_compile_event(name: str, _secs: float, **_kw) -> None:
    global _compiles
    if name == _COMPILE_EVENT:
        with _compile_lock:
            _compiles += 1


def compile_count() -> int:
    """XLA backend compilations in this process since the first call.
    The first call registers ONE ``jax.monitoring`` listener for the
    whole process; every later call (every gateway's ``compilations``
    gauge) reads the same count."""
    global _listening
    with _compile_lock:
        if not _listening:
            import jax.monitoring

            jax.monitoring.register_event_duration_secs_listener(
                _on_compile_event)
            _listening = True
        return _compiles


__all__ = ["Counter", "Gauge", "Histogram", "MetricsRegistry",
           "DEFAULT_MS_BOUNDS", "merge_snapshots",
           "percentile_from_buckets", "bucket_bounds_at", "to_prometheus",
           "TraceRecorder", "NullRecorder", "NULL_RECORDER", "read_jsonl",
           "MetricsServer", "StatsPrinter", "format_stats_line",
           "profile_span", "compile_count"]
