"""Typed, leveled operator metrics — the ``GpuMetric`` analog.

The reference attaches leveled SQL metrics to every operator
(``GpuMetric.scala``: ESSENTIAL/MODERATE/DEBUG levels gated by
``spark.rapids.sql.metrics.level``; NANO_TIMING/SUM/PEAK/AVERAGE kinds) and
couples timing metrics with profiler ranges (``NvtxWithMetrics.scala`` —
SURVEY.md §5). This module is the TPU port: a per-query
:class:`MetricsRegistry` holding :class:`TpuMetric` accumulators keyed by
(node name, metric name), with a standard taxonomy (:data:`TAXONOMY`) shared
by every layer of the engine — exec, shuffle, io, memory, compile — so one
``QueryProfile`` (:mod:`.profile`) can read them all coherently.

Level gating happens at record time: a metric above the configured level
(``spark.rapids.tpu.metrics.level``) is dropped without allocation, and at
level NONE the registry is inert — ``ExecContext.metric`` becomes a no-op
and no timing fences are ever inserted (asserted by tests/test_metrics.py).

Timing metrics are NANO_TIMING kind; :meth:`MetricsRegistry.timer`
couples each one with a :func:`..metrics.trace.span`, so every timed
region doubles as an XProf/TraceAnnotation range (the NvtxWithMetrics
coupling).
"""

from __future__ import annotations

import contextlib
import time
from typing import Dict, Optional

from ..utils import lockdep
from .trace import span

# ---------------------------------------------------------------------------
# Levels (GpuMetric.scala: ESSENTIAL/MODERATE/DEBUG) and kinds.
# ---------------------------------------------------------------------------

NONE = 0
ESSENTIAL = 1
MODERATE = 2
DEBUG = 3

_LEVEL_NAMES = {"NONE": NONE, "ESSENTIAL": ESSENTIAL,
                "MODERATE": MODERATE, "DEBUG": DEBUG}
_LEVEL_STRS = {v: k for k, v in _LEVEL_NAMES.items()}


def parse_level(s: Optional[str]) -> int:
    """Parse a metrics level name; unknown values default to MODERATE (the
    reference's default for spark.rapids.sql.metrics.level)."""
    return _LEVEL_NAMES.get(str(s or "").strip().upper(), MODERATE)


def level_name(level: int) -> str:
    return _LEVEL_STRS.get(level, "MODERATE")


class MetricKind:
    SUM = "SUM"
    NANO_TIMING = "NANO_TIMING"
    PEAK = "PEAK"
    AVERAGE = "AVERAGE"


class MetricSpec:
    """Static description of one metric name: kind + level + doc. Frozen
    (shared across registries); accumulation state lives in TpuMetric."""

    __slots__ = ("name", "kind", "level", "doc")

    def __init__(self, name: str, kind: str, level: int, doc: str):
        self.name = name
        self.kind = kind
        self.level = level
        self.doc = doc


def _spec(name, kind, level, doc):
    return MetricSpec(name, kind, level, doc)


#: The standard metric taxonomy — the names every instrumented layer uses,
#: so profiles are comparable across operators and across runs. The table in
#: docs/monitoring.md is generated from this dict (taxonomy_markdown()).
TAXONOMY: Dict[str, MetricSpec] = {s.name: s for s in [
    _spec("opTime", MetricKind.NANO_TIMING, ESSENTIAL,
          "Host-side wall time spent in the operator's dispatch path "
          "(device execution is async; see deviceTime for fenced time)."),
    _spec("deviceTime", MetricKind.NANO_TIMING, ESSENTIAL,
          "Dispatch-to-ready device time, measured with an explicit "
          "block-until-ready fence. Only recorded under "
          "spark.rapids.tpu.metrics.deviceTiming=true — the fence "
          "serializes the pipeline, so it never runs on the default path."),
    _spec("uploadBytes", MetricKind.SUM, ESSENTIAL,
          "Host->device bytes transferred (Arrow buffer footprint at the "
          "HostToDevice boundary; in a device parquet scan the padded "
          "page bytes, run tables and dictionaries of every column "
          "chunk)."),
    _spec("downloadBytes", MetricKind.SUM, ESSENTIAL,
          "Device->host bytes transferred (result downloads, including the "
          "fused head transfer)."),
    _spec("numOutputRows", MetricKind.SUM, ESSENTIAL,
          "Rows produced, recorded only where the count is host-known "
          "(downloads, scans) — never via an extra device sync."),
    _spec("numOutputBatches", MetricKind.SUM, ESSENTIAL,
          "Batches produced by the operator."),
    _spec("numInputRows", MetricKind.SUM, MODERATE,
          "Rows consumed, where host-known."),
    _spec("numInputBatches", MetricKind.SUM, MODERATE,
          "Batches consumed."),
    _spec("aggMaskedSlotBatches", MetricKind.SUM, ESSENTIAL,
          "Hash aggregate: input batches whose partial aggregation took "
          "the masked-slot form — every grouping key a sorted-dictionary "
          "column and at most ops/kernels/groupby.py _MASKED_SLOT_LIMIT "
          "packed slots, so each slot's sum, min and max is one masked "
          "reduction over the batch and nothing scatters (scope "
          "masked_slot_reduce in agg_partial). Counted on the host from "
          "the key columns' shapes as the batch is handed to the kernel; "
          "batches with other keys or more slots are not counted."),
    _spec("spillBytes", MetricKind.SUM, ESSENTIAL,
          "Bytes pushed out of the device tier by the spill framework "
          "during the query (host + disk)."),
    _spec("semaphoreWaitNs", MetricKind.NANO_TIMING, MODERATE,
          "Time blocked acquiring the task-admission semaphore "
          "(spark.rapids.sql.concurrentTpuTasks)."),
    _spec("shuffleBytesWritten", MetricKind.SUM, ESSENTIAL,
          "Serialized shuffle bytes written to the block catalog."),
    _spec("shuffleBytesRead", MetricKind.SUM, ESSENTIAL,
          "Serialized shuffle bytes read back on the reduce side."),
    _spec("buildTime", MetricKind.NANO_TIMING, MODERATE,
          "Join build-side accumulation wall time."),
    _spec("sortTime", MetricKind.NANO_TIMING, MODERATE,
          "Sort/top-k dispatch wall time."),
    _spec("concatTime", MetricKind.NANO_TIMING, DEBUG,
          "Batch-coalesce concat dispatch wall time."),
    _spec("serializationTime", MetricKind.NANO_TIMING, DEBUG,
          "Shuffle block serialization wall time."),
    _spec("deserializationTime", MetricKind.NANO_TIMING, DEBUG,
          "Shuffle block deserialization wall time."),
    _spec("writeTime", MetricKind.NANO_TIMING, MODERATE,
          "File-writer wall time (encode + filesystem)."),
    _spec("bytesWritten", MetricKind.SUM, ESSENTIAL,
          "Bytes written by the file writer."),
    _spec("numFiles", MetricKind.SUM, MODERATE,
          "Files produced by the file writer."),
    _spec("deviceDecodedRowGroups", MetricKind.SUM, ESSENTIAL,
          "Parquet row groups decoded on the device "
          "(io/parquet_device.py)."),
    _spec("hostFallbackRowGroups", MetricKind.SUM, ESSENTIAL,
          "Parquet row groups the device decoder could not read and the "
          "host reader served instead. Zero when the scan ran on the "
          "device; under spark.rapids.sql.test.enabled such a row group "
          "raises instead."),
    _spec("scanParseNs", MetricKind.NANO_TIMING, ESSENTIAL,
          "Device parquet scan, host side of a column chunk: file read, "
          "page headers, decompression, run tables "
          "(io/parquet_device.py plan_column_chunk). Thread-seconds "
          "summed over the decode producers."),
    _spec("scanUploadNs", MetricKind.NANO_TIMING, ESSENTIAL,
          "Device parquet scan: the host->device copies of a column "
          "chunk's packed bytes, run tables and dictionaries (the "
          "jnp.asarray calls of decode_chunk), summed over the "
          "producers; the bytes are in uploadBytes."),
    _spec("scanLaunchNs", MetricKind.NANO_TIMING, ESSENTIAL,
          "Device parquet scan: the call that enqueues a column chunk's "
          "decode program (trace and compile on its first visit). Grows "
          "when a producer stands behind the device's queue."),
    _spec("scanColumnChunksDecoded", MetricKind.SUM, ESSENTIAL,
          "Column chunks a file scan decoded, per run of the plan: row "
          "groups (parquet), stripes (ORC), file slices (CSV) or record "
          "batches (host scan) x columns of the scan's schema, which is "
          "what the plan references (plan/optimizer.py)."),
    _spec("scanChunksPlain", MetricKind.SUM, ESSENTIAL,
          "Device parquet scan: column chunks decoded from PLAIN data "
          "pages alone (io/parquet_device.py decode_chunk; with the two "
          "below it adds up to the scan's scanColumnChunksDecoded)."),
    _spec("scanChunksDictionary", MetricKind.SUM, ESSENTIAL,
          "Device parquet scan: column chunks decoded from "
          "dictionary-encoded data pages alone, fixed-width or string."),
    _spec("scanChunksDictionaryThenPlain", MetricKind.SUM, ESSENTIAL,
          "Device parquet scan: column chunks, fixed-width or string, "
          "whose writer fell back mid-chunk (the dictionary page passed "
          "its size limit, 1 MiB by default): dictionary-encoded pages, "
          "then PLAIN pages, decoded by one "
          "parquet_decode_*_dictplain[_nn] program."),
    _spec("scanChunksByteArrayPlain", MetricKind.SUM, ESSENTIAL,
          "Device parquet scan: string column chunks whose values came "
          "wholly or partly from PLAIN byte-array pages ([u32 length]"
          "[bytes] a value) and were decoded to a flat string column "
          "(parquet_decode_string_plain[_nn] / _string_dictplain[_nn], "
          "then parquet_decode_string_plain_place). Each is counted under "
          "scanChunksPlain or scanChunksDictionaryThenPlain as well. "
          "scanLaunchNs of such a chunk holds one wait for the device: "
          "the text's bytes and the longest value size the column."),
    _spec("scanChunksNoNulls", MetricKind.SUM, ESSENTIAL,
          "Device parquet scan: column chunks, of whichever kind "
          "(scanChunksPlain, scanChunksDictionary and "
          "scanChunksDictionaryThenPlain count them too), in which no "
          "page held a null: read from the pages' definition levels, "
          "always so for a REQUIRED column. They decode without the "
          "definition-level table, the prefix sum and the gather through "
          "slots (parquet_decode_*_nn programs); a chunk with a null "
          "takes the nullable program of its kind."),
    _spec("planRuns", MetricKind.SUM, ESSENTIAL,
          "Runs of the plan behind one collect()/cache(): 1, plus "
          "join-capacity re-runs and dispatch retries (TpuSession node; "
          "session.py _run_with_retries)."),
    _spec("xlaCompileNs", MetricKind.NANO_TIMING, ESSENTIAL,
          "Seconds in JAX's backend_compile_duration events over the "
          "query: XLA compiles, or the persistent cache's lookups and "
          "loads (compile/xla_events.py; a delta of process totals, on "
          "the TpuSession node and in engine.compile)."),
    _spec("xlaCompiles", MetricKind.SUM, ESSENTIAL,
          "Programs JAX handed to the backend over the query "
          "(backend_compile_duration events), compiled or loaded."),
    _spec("persistentCacheHits", MetricKind.SUM, ESSENTIAL,
          "Programs loaded from JAX's persistent compilation cache over "
          "the query."),
    _spec("persistentCacheMisses", MetricKind.SUM, ESSENTIAL,
          "Programs compiled over the query because the persistent "
          "cache did not hold them, and written to it."),
    _spec("peakDeviceBytes", MetricKind.PEAK, MODERATE,
          "Peak device bytes observed (HBM watermark where the backend "
          "reports it)."),
    _spec("avgBatchRows", MetricKind.AVERAGE, DEBUG,
          "Average host-known rows per batch."),
    _spec("retryCount", MetricKind.SUM, ESSENTIAL,
          "Attempts re-run at the operator's retry sites after a "
          "classified OOM or transient fault (memory/retry.py; "
          "docs/fault-tolerance.md). Zero on a healthy run."),
    _spec("splitAndRetryCount", MetricKind.SUM, ESSENTIAL,
          "Input batches split in half by rows because retries alone "
          "could not fit the operator in device memory (the reference's "
          "splitSpillableInHalfByRows escalation)."),
    _spec("retryBlockTimeNs", MetricKind.NANO_TIMING, MODERATE,
          "Wall time spent blocked in retry backoff sleeps "
          "(spark.rapids.tpu.retry.backoffBaseMs ladder)."),
    _spec("retryWastedComputeNs", MetricKind.NANO_TIMING, MODERATE,
          "Wall time of failed attempts whose work was thrown away and "
          "re-run — the price of surviving the fault."),
    _spec("prefetchProducerStallNs", MetricKind.NANO_TIMING, ESSENTIAL,
          "Pipeline occupancy: time producers (prefetch workers, decode "
          "tasks) spent blocked on a full bounded prefetch queue — the "
          "consumer side is the bottleneck "
          "(spark.rapids.tpu.pipeline.prefetchDepth)."),
    _spec("prefetchConsumerStallNs", MetricKind.NANO_TIMING, ESSENTIAL,
          "Pipeline occupancy: time consumers spent blocked waiting for "
          "a prefetched batch or in-flight decode result — the producer "
          "side is the bottleneck."),
    _spec("decodeThreadBusyNs", MetricKind.NANO_TIMING, ESSENTIAL,
          "Total busy time of shared-pool decode tasks (file/row-group "
          "decode the pipeline layer overlapped with device work)."),
    _spec("boundaryOverlapNs", MetricKind.NANO_TIMING, ESSENTIAL,
          "Wall time saved by materializing independent fusion-boundary "
          "subtrees concurrently: the sum of per-boundary times minus "
          "elapsed time, 0 when the workers' spans did not overlap; "
          "reported whenever the boundaries ran on workers "
          "(spark.rapids.tpu.pipeline.boundaryParallelism)."),
    _spec("checksumFailures", MetricKind.SUM, ESSENTIAL,
          "Shuffle-block / spill-range CRC32C verifications that FAILED "
          "(utils/checksum.py; docs/fault-tolerance.md). Every failure "
          "was recovered by refetch or map recompute, or surfaced as a "
          "typed error — never as data. Zero on a healthy run."),
    _spec("shuffleBlocksRefetched", MetricKind.SUM, ESSENTIAL,
          "Shuffle blocks fetched again after a transport failure or "
          "checksum mismatch (only blocks not yet yielded re-fetch; "
          "shuffle/net.py). Zero on a healthy run."),
    _spec("mapTasksRecomputed", MetricKind.SUM, ESSENTIAL,
          "Map tasks deterministically re-executed from lineage because "
          "their shuffle blocks were lost or corrupt past refetch (the "
          "Spark stage-retry analog; shuffle/exchange.py "
          "MapOutputTracker). Zero on a healthy run."),
    _spec("deadlineCancels", MetricKind.SUM, ESSENTIAL,
          "Cooperative cancellations raised by the query deadline "
          "(spark.rapids.tpu.query.deadlineSecs): in-flight fetches, "
          "pipeline waits, and retry loops that observed an expired "
          "deadline and raised QueryDeadlineExceeded."),
    _spec("peersBlacklisted", MetricKind.SUM, ESSENTIAL,
          "Shuffle peers excluded for the session after repeated fetch "
          "failures (spark.rapids.tpu.shuffle.net.maxPeerFailures)."),
    _spec("hedgedFetches", MetricKind.SUM, ESSENTIAL,
          "Shuffle block fetches that exceeded the straggler threshold "
          "(spark.rapids.tpu.shuffle.hedge.quantileFactor x the peer's "
          "observed p50) and launched a duplicate request against a "
          "replica or the local recompute closure (shuffle/net.py). "
          "Zero on a healthy run."),
    _spec("hedgeWins", MetricKind.SUM, ESSENTIAL,
          "Hedged fetches where the DUPLICATE delivered first — the "
          "straggling primary was cancelled and the partition was "
          "served without waiting out its stall. Always <= "
          "hedgedFetches; the difference is hedge losses (wasted "
          "duplicate work)."),
    _spec("replicaReads", MetricKind.SUM, ESSENTIAL,
          "Shuffle blocks served by a replica "
          "(spark.rapids.tpu.shuffle.replication.factor) because the "
          "primary was dead, stalled, or blacklisted — each one a "
          "lineage recompute avoided. Zero on a healthy run."),
    _spec("meshFailovers", MetricKind.SUM, ESSENTIAL,
          "Mesh SPMD dispatches abandoned to the single-chip path after "
          "a device/host loss (MeshDegradedError) or a failed health "
          "probe (spark.rapids.tpu.mesh.health.probeEnabled): the query "
          "re-ran degraded instead of failing (exec/mesh.py, "
          "session.py). Zero on a healthy run."),
]}

#: Metrics recorded under names outside the taxonomy (operator-specific
#: counters like aqeOutputPartitions, stripeHostFallback) default to
#: SUM/MODERATE.
_AD_HOC_LEVEL = MODERATE


def taxonomy_markdown() -> str:
    """The docs/monitoring.md taxonomy table (kept in sync by
    tests/test_metrics.py)."""
    lines = ["Name | Kind | Level | Description",
             "-----|------|-------|------------"]
    for name in sorted(TAXONOMY):
        s = TAXONOMY[name]
        lines.append(f"`{name}`|{s.kind}|{level_name(s.level)}|{s.doc}")
    return "\n".join(lines) + "\n"


class TpuMetric:
    """One accumulator (the GpuMetric analog). Kind decides the merge:
    SUM/NANO_TIMING add, PEAK keeps the max, AVERAGE tracks (sum, count).
    Mutation is guarded by the owning registry's lock."""

    __slots__ = ("spec", "_sum", "_count", "_peak")

    def __init__(self, spec: MetricSpec):
        self.spec = spec
        self._sum = 0
        self._count = 0
        self._peak = 0

    def update(self, value) -> None:
        value = int(value) if not isinstance(value, float) else value
        if self.spec.kind == MetricKind.PEAK:
            self._peak = max(self._peak, value)
        elif self.spec.kind == MetricKind.AVERAGE:
            self._sum += value
            self._count += 1
        else:
            if not isinstance(self._sum, (int, float)) \
                    or isinstance(self._sum, bool):
                # set() takes whatever a legacy dict writer assigned; a
                # non-numeric leftover counts as 0 rather than raising
                # mid-metric.
                self._sum = 0
            self._sum += value

    def set(self, value) -> None:
        """Overwrite (legacy direct-dict-assignment semantics)."""
        if self.spec.kind == MetricKind.PEAK:
            self._peak = value
        else:
            self._sum = value
            self._count = 1

    @property
    def value(self):
        if self.spec.kind == MetricKind.PEAK:
            return self._peak
        if self.spec.kind == MetricKind.AVERAGE:
            return self._sum / self._count if self._count else 0
        return self._sum


class MetricsRegistry:
    """Per-query metric store: (node name, metric name) -> TpuMetric.

    Thread-safe — warm-up workers and shuffle transport threads report
    concurrently (tests/test_metrics.py hammer test). Node keying follows
    the engine's existing convention: metrics are keyed by the exec's
    node_name(), so two instances of the same exec type in one plan share
    accumulators (noted in docs/monitoring.md)."""

    def __init__(self, level: int = MODERATE, device_timing: bool = False):
        self.level = level
        self.device_timing = device_timing and level > NONE
        self._lock = lockdep.lock("MetricsRegistry._lock")
        self._nodes: Dict[str, Dict[str, TpuMetric]] = {}

    @classmethod
    def for_conf(cls, conf) -> "MetricsRegistry":
        """Build from a TpuConf (duck-typed: anything with the metrics
        properties; bare test contexts without them get the defaults)."""
        level = parse_level(getattr(conf, "metrics_level", None))
        return cls(level, bool(getattr(conf, "metrics_device_timing", False)))

    @property
    def enabled(self) -> bool:
        return self.level > NONE

    def _spec_for(self, name: str) -> MetricSpec:
        spec = TAXONOMY.get(name)
        if spec is None:
            spec = MetricSpec(name, MetricKind.SUM, _AD_HOC_LEVEL,
                              "operator-specific counter")
        return spec

    def records(self, name: str) -> bool:
        """Would a metric of this name be recorded at the current level?"""
        return self.level >= self._spec_for(name).level

    def _metric_locked(self, node: str, name: str) -> Optional[TpuMetric]:
        """The accumulator for (node, name), or None when gated. Caller
        holds the lock (one critical section per observation — this is the
        per-batch hot path)."""
        spec = self._spec_for(name)
        if self.level < spec.level:
            return None
        metrics = self._nodes.setdefault(node, {})
        m = metrics.get(name)
        if m is None:
            m = metrics[name] = TpuMetric(spec)
        return m

    def add(self, node: str, name: str, value) -> None:
        with self._lock:
            m = self._metric_locked(node, name)
            if m is not None:
                m.update(value)

    def set_value(self, node: str, name: str, value) -> None:
        with self._lock:
            m = self._metric_locked(node, name)
            if m is not None:
                m.set(value)

    def timer(self, node: str, name: str, trace: Optional[str] = None,
              owner=None):
        """Exception-safe NANO_TIMING context manager, coupled with a span
        (the NvtxWithMetrics analog; ``owner`` is the query's tracer, as
        for :func:`..metrics.trace.span`). The span is opened regardless
        of the metrics level — profiler visibility must not depend on
        metric gating — but the clock reads and accumulation are skipped
        when the metric is gated."""
        sp = span(owner, trace or f"{node}.{name}")
        if not self.records(name):
            return sp
        return self._timed(node, name, sp)

    @contextlib.contextmanager
    def _timed(self, node: str, name: str, sp):
        # Accumulates in a finally, so a body that raises still records
        # the time it spent before the raise.
        start = time.perf_counter_ns()
        try:
            with sp:
                yield
        finally:
            self.add(node, name, time.perf_counter_ns() - start)

    # -- read side ----------------------------------------------------------
    def node_metrics(self, node: str) -> Dict[str, object]:
        with self._lock:
            return {n: m.value for n, m in self._nodes.get(node, {}).items()}

    def node_names(self):
        with self._lock:
            return list(self._nodes)

    def snapshot(self) -> Dict[str, Dict[str, object]]:
        with self._lock:
            return {node: {n: m.value for n, m in metrics.items()}
                    for node, metrics in self._nodes.items()}

    def legacy_view(self) -> "_LegacyMetricsView":
        return _LegacyMetricsView(self)


def _deprecated(what: str) -> None:
    import warnings
    warnings.warn(
        f"direct mutation of ExecContext.metrics ({what}) is deprecated; "
        "use ExecContext.metric(node, name, value) or "
        "ExecContext.registry — the dict shim is kept for one release",
        DeprecationWarning, stacklevel=3)


class _LegacyNodeView:
    """Read/write shim for one node's metrics: reads return plain numbers
    (what the old ad-hoc dict held); writes warn and route into the
    registry."""

    def __init__(self, registry: MetricsRegistry, node: str):
        self._registry = registry
        self._node = node

    def _values(self):
        return self._registry.node_metrics(self._node)

    def __getitem__(self, name):
        return self._values()[name]

    def get(self, name, default=None):
        return self._values().get(name, default)

    def __contains__(self, name):
        return name in self._values()

    def __iter__(self):
        return iter(self._values())

    def __len__(self):
        return len(self._values())

    def items(self):
        return self._values().items()

    def keys(self):
        return self._values().keys()

    def values(self):
        return self._values().values()

    def __setitem__(self, name, value):
        _deprecated(f"metrics[{self._node!r}][{name!r}] = ...")
        self._registry.set_value(self._node, name, value)

    def setdefault(self, name, default=0):
        cur = self._values().get(name)
        if cur is not None:
            return cur
        _deprecated(f"metrics[{self._node!r}].setdefault({name!r})")
        self._registry.set_value(self._node, name, default)
        return default

    def __repr__(self):
        return repr(self._values())

    def __eq__(self, other):
        return self._values() == other


class _LegacyMetricsView:
    """The ``ExecContext.metrics`` dict shim: node -> name -> value, backed
    by the registry. Reads are silent (tests and diagnostics iterate it);
    mutation warns with DeprecationWarning and keeps working for one
    release."""

    def __init__(self, registry: MetricsRegistry):
        self._registry = registry

    def __getitem__(self, node):
        return _LegacyNodeView(self._registry, node)

    def get(self, node, default=None):
        if node not in self._registry.node_names():
            return default
        return _LegacyNodeView(self._registry, node)

    def setdefault(self, node, default=None):
        return _LegacyNodeView(self._registry, node)

    def __contains__(self, node):
        return node in self._registry.node_names()

    def __iter__(self):
        return iter(self._registry.node_names())

    def __len__(self):
        return len(self._registry.node_names())

    def items(self):
        return [(n, _LegacyNodeView(self._registry, n))
                for n in self._registry.node_names()]

    def keys(self):
        return self._registry.node_names()

    def values(self):
        return [_LegacyNodeView(self._registry, n)
                for n in self._registry.node_names()]

    def __repr__(self):
        return repr(self._registry.snapshot())
