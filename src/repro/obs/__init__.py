"""Observatory: the unified telemetry plane.

Three layers (docs/OBSERVABILITY.md):

* :mod:`repro.obs.metrics` — the typed metrics registry with the fixed
  label taxonomy, the :class:`RingLog` bounded evidence container, and
  the pull-based ``publish_*`` adapters that scrape every subsystem's
  existing ad-hoc counters into one ``snapshot()``.
* :mod:`repro.obs.trace` — ring-buffered nested span tracing over the
  serving plane, mirrored into the ``jax.profiler`` trace as
  ``repro.<kind>`` annotations, Chrome-trace/Perfetto export, zero
  overhead when disabled.
* :mod:`repro.obs.baseline` — the perf-regression gate comparing two
  ``BENCH_*.json`` artifacts with per-metric tolerance bands (CLI:
  ``benchmarks/bench_diff.py``).

The tracing gate is re-exported here so instrumentation sites and
entrypoints write ``obs.enable()`` / ``obs.enabled()`` / ``obs.span``
without caring which layer owns the global.
"""
from repro.obs.trace import (KINDS, Span, TraceRecorder, begin, capture,
                             complete, containing, disable, enable,
                             enabled, end, recorder, span, well_formed)
from repro.obs.metrics import (LABEL_KEYS, Counter, Gauge, Histogram,
                               MetricsRegistry, RingLog, collect,
                               publish_chaos, publish_emission_stats,
                               publish_group, publish_pipeline,
                               publish_poll_stats, publish_supervisor)
from repro.obs.baseline import (Delta, DiffReport, Tolerance,
                                default_tolerance, diff, diff_files,
                                load_rows, row_key)

__all__ = [
    # trace
    "KINDS", "Span", "TraceRecorder", "begin", "capture", "complete",
    "containing", "disable", "enable", "enabled", "end", "recorder",
    "span", "well_formed",
    # metrics
    "LABEL_KEYS", "Counter", "Gauge", "Histogram", "MetricsRegistry",
    "RingLog", "collect", "publish_chaos", "publish_emission_stats",
    "publish_group", "publish_pipeline", "publish_poll_stats",
    "publish_supervisor",
    # baseline
    "Delta", "DiffReport", "Tolerance", "default_tolerance", "diff",
    "diff_files", "load_rows", "row_key",
]
