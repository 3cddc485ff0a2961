"""Query-profile layer: typed leveled metrics, device-time attribution,
structured trace events, and metric-annotated EXPLAIN.

Three pieces (docs/monitoring.md):

* :mod:`.registry` — ``TpuMetric``/``MetricsRegistry``: the GpuMetric
  analog; NANO_TIMING/SUM/PEAK/AVERAGE kinds, ESSENTIAL/MODERATE/DEBUG
  levels gated by ``spark.rapids.tpu.metrics.level``, and the standard
  taxonomy every layer reports into.
* :mod:`.profile` — ``QueryProfile``/``QueryProfiler``: the per-query
  operator-tree snapshot with engine counters folded in, rendered by
  ``df.explain(metrics=True)`` and diffed by
  ``tools/profile_bench.py --compare``.
* :mod:`.eventlog` — crash-safe JSON-lines event log
  (``spark.rapids.tpu.metrics.eventLog.dir``), one line per query, with
  size-capped rotation for long-lived serving processes.
* :mod:`.trace` — the one span API (``span``: always a profiler
  ``TraceAnnotation``, and under ``spark.rapids.tpu.trace.enabled`` the
  per-query span tree too), Chrome trace-event export with the clock
  pair that maps it onto the profiler's timeline, wire-propagated trace
  context, and the flight-recorder ring. Not re-exported here (call
  sites import the module directly); ``tools/trace_report.py`` is the
  analyzer.
"""

from .eventlog import EventLog
from .profile import (QueryProfile, QueryProfiler, compare_profiles,
                      dump_profiles, load_profiles)
from .registry import (DEBUG, ESSENTIAL, MODERATE, NONE, TAXONOMY,
                       MetricKind, MetricsRegistry, MetricSpec, TpuMetric,
                       level_name, parse_level, taxonomy_markdown)

__all__ = [
    "DEBUG", "ESSENTIAL", "MODERATE", "NONE", "TAXONOMY", "MetricKind",
    "MetricsRegistry", "MetricSpec", "TpuMetric", "level_name",
    "parse_level", "taxonomy_markdown", "QueryProfile", "QueryProfiler",
    "compare_profiles", "dump_profiles", "load_profiles", "EventLog",
]
