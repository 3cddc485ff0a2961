"""Typed configuration registry — the analog of ``RapidsConf``.

The reference builds every config key through a typed builder DSL that records
the key, type, default, and doc string in one registry, then auto-generates
``docs/configs.md`` from it (reference: ``RapidsConf.scala:100-170`` for the
builders, ``:641`` for the doc generator). Per-operator enable keys are
synthesized from class names (``GpuOverrides.scala:126-131``).

We keep the same architecture: ``ConfEntry`` descriptors registered at import
time, a ``TpuConf`` snapshot object with typed accessors, and
``TpuConf.help_markdown()`` regenerating the user docs. Key namespace follows
the reference (``spark.rapids.sql.*``) with TPU-specific keys under
``spark.rapids.tpu.*``.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, List, Optional

from .utils import lockdep as _lockdep

_REGISTRY: Dict[str, "ConfEntry"] = {}
#: registrations normally happen at module import, but extension points
#: (and the serving layer's worker-reachable call graph) make the write
#: path formally concurrent — the registry mutates under a lock.
_REGISTRY_LOCK = _lockdep.lock("config._REGISTRY_LOCK")


@dataclasses.dataclass(frozen=True)
class ConfEntry:
    key: str
    default: Any
    doc: str
    conv: Callable[[str], Any]
    internal: bool = False

    def get(self, conf: Dict[str, Any]) -> Any:
        if self.key in conf:
            v = conf[self.key]
            return self.conv(v) if isinstance(v, str) else v
        return self.default


def _to_bool(s: str) -> bool:
    return s.strip().lower() in ("true", "1", "yes")


def _register(key, default, doc, conv, internal=False) -> ConfEntry:
    with _REGISTRY_LOCK:
        if key in _REGISTRY:
            raise ValueError(f"duplicate conf key {key}")
        e = ConfEntry(key, default, doc, conv, internal)
        _REGISTRY[key] = e
    return e


def conf_bool(key: str, default: bool, doc: str, internal: bool = False) -> ConfEntry:
    return _register(key, default, doc, _to_bool, internal)


def conf_int(key: str, default: int, doc: str, internal: bool = False) -> ConfEntry:
    return _register(key, default, doc, int, internal)


def conf_float(key: str, default: float, doc: str, internal: bool = False) -> ConfEntry:
    return _register(key, default, doc, float, internal)


def conf_str(key: str, default: Optional[str], doc: str, internal: bool = False) -> ConfEntry:
    return _register(key, default, doc, str, internal)


# ---------------------------------------------------------------------------
# Core feature gates (reference RapidsConf.scala:329-478)
# ---------------------------------------------------------------------------

SQL_ENABLED = conf_bool(
    "spark.rapids.sql.enabled", True,
    "Enable or disable the TPU columnar execution of SQL plans entirely.")

EXPLAIN = conf_str(
    "spark.rapids.sql.explain", "NONE",
    "Explain why parts of a query were or were not placed on the TPU. "
    "Options: NONE, NOT_ON_TPU, ALL.")

TEST_ENABLED = conf_bool(
    "spark.rapids.sql.test.enabled", False,
    "Intended for internal tests only: fail if any operator in an executed plan "
    "fell back to the CPU instead of running on the TPU.")

TEST_ALLOWED_NON_TPU = conf_str(
    "spark.rapids.sql.test.allowedNonTpu", "",
    "Comma-separated operator class names allowed to stay on CPU when "
    "spark.rapids.sql.test.enabled is on.")

INCOMPATIBLE_OPS = conf_bool(
    "spark.rapids.sql.incompatibleOps.enabled", False,
    "Enable operators that produce results that differ from Spark in corner "
    "cases (e.g. float-to-string formatting).")

HAS_NANS = conf_bool(
    "spark.rapids.sql.hasNans", True,
    "Assume floating point data may contain NaNs; disables some device "
    "aggregations/joins on float keys unless set to false.")

VARIABLE_FLOAT_AGG = conf_bool(
    "spark.rapids.sql.variableFloatAgg.enabled", False,
    "Allow float/double aggregations whose result can differ from CPU Spark "
    "because parallel reduction order is not fixed.")

IMPROVED_FLOAT_OPS = conf_bool(
    "spark.rapids.sql.improvedFloatOps.enabled", False,
    "Enable float ops (e.g. float->string cast) that do not match Spark exactly.")

CAST_FLOAT_TO_STRING = conf_bool(
    "spark.rapids.sql.castFloatToString.enabled", False,
    "Enable float/double to string casts; formatting can differ from Spark.")

CAST_STRING_TO_FLOAT = conf_bool(
    "spark.rapids.sql.castStringToFloat.enabled", False,
    "Enable string to float casts; some edge-case strings parse differently.")

CAST_STRING_TO_TIMESTAMP = conf_bool(
    "spark.rapids.sql.castStringToTimestamp.enabled", False,
    "Enable string to timestamp casts; only fixed formats are supported.")

REPLACE_SORT_MERGE_JOIN = conf_bool(
    "spark.rapids.sql.replaceSortMergeJoin.enabled", True,
    "Replace sort-merge joins with hash joins on the device "
    "(reference RapidsConf.scala:384).")

EXPORT_COLUMNAR_RDD = conf_bool(
    "spark.rapids.sql.exportColumnarRdd", False,
    "Allow exporting device-resident columnar batches to ML frameworks "
    "zero-copy (reference RapidsConf.scala:329).")

UDF_COMPILER_ENABLED = conf_bool(
    "spark.rapids.sql.udfCompiler.enabled", True,
    "Compile Python UDF bytecode into the expression IR, so a UDF is "
    "planned, fused and compiled by XLA with the built-in expressions "
    "around it instead of falling back to the CPU.")

# ---------------------------------------------------------------------------
# Batch sizing (reference RapidsConf.scala:306-325)
# ---------------------------------------------------------------------------

BATCH_SIZE_ROWS = conf_int(
    "spark.rapids.sql.batchSizeRows", 1 << 20,
    "Target number of rows for device batches produced by coalescing and reads.")

MAX_READ_BATCH_SIZE_ROWS = conf_int(
    "spark.rapids.sql.reader.batchSizeRows", 1 << 19,
    "Soft limit on rows per batch produced by file readers.")

MAX_READ_BATCH_SIZE_BYTES = conf_int(
    "spark.rapids.sql.reader.batchSizeBytes", 512 * 1024 * 1024,
    "Soft limit on bytes per batch produced by file readers.")

# ---------------------------------------------------------------------------
# Memory & admission (reference RapidsConf.scala:241-301)
# ---------------------------------------------------------------------------

SORT_EXTERNAL_THRESHOLD = conf_int(
    "spark.rapids.sql.sort.externalThresholdBytes", 0,
    "Accumulated input bytes above which a global sort switches to the "
    "external merge-sort path (sorted runs through the spill store, "
    "bounded device residency). 0 = auto: a quarter of the device spill "
    "budget. The reference bounds sorts with RequireSingleBatch + the "
    "spill store (GpuSortExec.scala:50); the external path removes the "
    "single-batch ceiling.")

CONCURRENT_TPU_TASKS = conf_int(
    "spark.rapids.sql.concurrentTpuTasks", 2,
    "Number of tasks that may hold the TPU concurrently "
    "(reference spark.rapids.sql.concurrentGpuTasks).")

CONCURRENT_ACQUIRE_TIMEOUT = conf_float(
    "spark.rapids.tpu.concurrentTpuTasks.acquireTimeout", 0.0,
    "Seconds a task may block acquiring the admission semaphore before "
    "failing with a diagnostic error naming the holding threads and their "
    "held counts (a silent deadlock becomes an actionable failure). 0 "
    "waits forever. See docs/fault-tolerance.md.")

RETRY_MAX_RETRIES = conf_int(
    "spark.rapids.tpu.retry.maxRetries", 3,
    "In-place retries of an operator attempt after a classified OOM or "
    "transient fault (memory/retry.py) before escalating: OOMs escalate "
    "to splitting the input batch in half by rows (SplitAndRetryOOM at "
    "unsplittable sites), transients re-raise. Each OOM retry first "
    "synchronizes the device and spills every spillable buffer below "
    "on-deck priority. See docs/fault-tolerance.md.")

RETRY_BACKOFF_BASE_MS = conf_float(
    "spark.rapids.tpu.retry.backoffBaseMs", 10.0,
    "Base delay for the capped exponential retry backoff (doubles per "
    "attempt, deterministic jitter derived from the site name). 0 "
    "disables sleeping between retries (test hook).")

RETRY_BACKOFF_MAX_MS = conf_float(
    "spark.rapids.tpu.retry.backoffMaxMs", 1000.0,
    "Ceiling on one retry backoff sleep, milliseconds.")

FAULT_INJECTION_SITES = conf_str(
    "spark.rapids.tpu.test.faultInjection.sites", "",
    "Intended for tests: comma-separated retry-site names (or prefixes; "
    "'*' matches every site) where the deterministic fault injector "
    "raises synthetic faults (utils/fault_injection.py). Empty disables "
    "injection. Site names are listed in docs/fault-tolerance.md.")

FAULT_INJECTION_SEED = conf_int(
    "spark.rapids.tpu.test.faultInjection.seed", 0,
    "Phase/flavor seed for the fault injector: shifts WHICH visit of a "
    "site faults and which transient flavor (remote-compile race vs "
    "spill-disk OSError) is raised. Same seed = same fault schedule.")

FAULT_INJECTION_OOM_EVERY_N = conf_int(
    "spark.rapids.tpu.test.faultInjection.oomEveryN", 0,
    "Raise a synthetic RESOURCE_EXHAUSTED at every Nth visit of each "
    "matched injection site; negative N faults the FIRST |N| visits and "
    "then heals (the schedule that exhausts a site's retries into a "
    "split while still letting the query finish). 0 disables OOM "
    "injection; N=1 faults every visit (drives sites to "
    "SplitAndRetryOOM).")

FAULT_INJECTION_TRANSIENT_EVERY_N = conf_int(
    "spark.rapids.tpu.test.faultInjection.transientEveryN", 0,
    "Raise a synthetic transient fault (remote-compile helper race or "
    "spill-disk OSError, flavor chosen deterministically from the seed) "
    "at every Nth visit of each matched injection site; negative N "
    "faults the first |N| visits then heals. 0 disables.")

FAULT_INJECTION_NET_EVERY_N = conf_int(
    "spark.rapids.tpu.test.faultInjection.netEveryN", 0,
    "Apply a deterministic NETWORK fault at every Nth visit of the "
    "matched shuffle-transport site (shuffle.fetchBlock — one visit per "
    "block fetch; the 'sites' patterns gate it). Negative N faults the "
    "first |N| "
    "visits then heals — the schedule that exercises refetch and "
    "recompute while letting the query finish. The fault class per "
    "visit is chosen deterministically from the seed among "
    "faultInjection.netFaults. 0 disables.")

FAULT_INJECTION_NET_FAULTS = conf_str(
    "spark.rapids.tpu.test.faultInjection.netFaults",
    "peerDeath,torn,bitFlip,stall",
    "Comma-separated network fault classes the injector may apply: "
    "peerDeath (connection dies mid-fetch), torn (payload truncated "
    "mid-block), bitFlip (one payload bit corrupted — caught by CRC32C), "
    "stall (peer stops sending past "
    "spark.rapids.tpu.shuffle.net.requestTimeout), replicaLoss (the "
    "replication push at the shuffle.replicate seam is silently "
    "dropped, so a later primary failure must fall through to lineage "
    "recompute — not in the default set, preserving pre-replication "
    "fault schedules). A single name pins every injected fault to that "
    "class.")

FAULT_INJECTION_NET_STALL_SECS = conf_float(
    "spark.rapids.tpu.test.faultInjection.netStallSecs", 0.05,
    "How long an injected 'stall' fault blocks before surfacing as the "
    "request-timeout failure the real stalled peer would produce (kept "
    "small so CI fault matrices stay fast).")

FAULT_INJECTION_MESH_EVERY_N = conf_int(
    "spark.rapids.tpu.test.faultInjection.meshEveryN", 0,
    "Raise a synthetic MeshDegradedError (a mid-query device loss) at "
    "every Nth visit of the matched mesh site (mesh.collect — one visit "
    "per SPMD dispatch; the 'sites' patterns gate it). Negative N "
    "faults the first |N| visits then heals. The session records the "
    "failover (meshFailovers metric, flight-recorder dump) and re-runs "
    "the query on the single-chip path — the degraded-mesh drill real "
    "device loss cannot provide in CI. 0 disables.")

HBM_ALLOC_FRACTION = conf_float(
    "spark.rapids.memory.tpu.allocFraction", 0.9,
    "Fraction of HBM the arena allocator may use "
    "(reference spark.rapids.memory.gpu.allocFraction).")

HOST_SPILL_STORAGE_SIZE = conf_int(
    "spark.rapids.memory.host.spillStorageSize", 1 << 30,
    "Bytes of host memory used to hold spilled device buffers before "
    "overflowing to disk (reference RapidsConf.scala:274).")

MEMORY_DEBUG = conf_bool(
    "spark.rapids.memory.tpu.debug", False,
    "Log every device allocation/free for leak hunting "
    "(reference spark.rapids.memory.gpu.debug).")

SPILL_DIR = conf_str(
    "spark.rapids.memory.tpu.spillDir", None,
    "Directory for the disk spill tier; defaults to a fresh temp directory "
    "(reference uses Spark's disk block manager directories).")

DEVICE_SPILL_BUDGET = conf_int(
    "spark.rapids.memory.tpu.spillBudgetBytes", 0,
    "Explicit device-store byte budget for spillable buffers; 0 derives it "
    "from allocFraction of detected HBM (test hook for forcing spills).")

SPILL_IO_THREADS = conf_int(
    "spark.rapids.tpu.spill.ioThreads", 2,
    "Concurrency of the dedicated spill-IO lane on the shared pipeline "
    "pool: device<->host copies, spill-file appends/reads, and disk-tier "
    "shuffle-block I/O run OFF the catalog lock with up to this many "
    "units in flight, so concurrent spills overlap and no thread ever "
    "waits on a catalog lock held across I/O. 0 runs spill I/O inline on "
    "the requesting thread (still off-lock, just without overlap). See "
    "docs/fault-tolerance.md#async-spill and docs/tuning-guide.md.")

TENANT_ID = conf_str(
    "spark.rapids.tpu.tenantId", "",
    "Session/tenant identity for memory QoS: spill victim selection "
    "prefers the requesting query's own buffers, then same-tenant "
    "buffers, then other tenants ordered by query-deadline slack — so "
    "one tenant's OOM-retry ladder stops evicting a neighbor's hot "
    "build tables (docs/fault-tolerance.md#async-spill). Empty = the "
    "default shared tenant.")

AUTO_BROADCAST_JOIN_ROWS = conf_int(
    "spark.rapids.sql.autoBroadcastJoinRows", 100_000,
    "Equi joins whose build side is estimated at or below this many rows "
    "plan as broadcast hash joins; -1 disables (row-count analog of "
    "spark.sql.autoBroadcastJoinThreshold).")

ORC_DEVICE_DECODE = conf_bool(
    "spark.rapids.sql.orc.deviceDecode.enabled", True,
    "Decode ORC stripes ON DEVICE: the host parses the protobuf tail, "
    "stripe footers, and RLEv2 run headers into compact run tables; "
    "traced kernels expand runs to rows, scatter non-null slots through "
    "the PRESENT bitmask, and gather dictionary codes (the GpuOrcScan "
    "stripe-reassembly split, GpuOrcScan.scala:65,211). Stripes outside "
    "the decoder's scope fall back to the host reader per stripe.")

PARQUET_DEVICE_DECODE = conf_bool(
    "spark.rapids.sql.parquet.deviceDecode.enabled", True,
    "Decode parquet pages ON DEVICE: the host parses footers/page headers "
    "and uploads raw page bytes + RLE run tables; traced kernels expand "
    "definition levels and dictionary indices (the GpuParquetScan -> "
    "Table.readParquet split, GpuParquetScan.scala:365-388). Row groups "
    "outside the decoder's scope fall back to the host reader per unit.")

PARQUET_REBASE_READ = conf_str(
    "spark.sql.legacy.parquet.datetimeRebaseModeInRead", "EXCEPTION",
    "Spark's own rebase-mode key, honored by the device parquet reader "
    "(the RebaseHelper.scala:60 guard): EXCEPTION raises on "
    "legacy-calendar files whose date/timestamp statistics reach below "
    "the 1582-10-15 / 1900-01-01 switchover (this reader never "
    "rebases), CORRECTED reads raw proleptic values, LEGACY is "
    "unsupported.")

CSV_DEVICE_DECODE = conf_bool(
    "spark.rapids.sql.csv.deviceDecode.enabled", True,
    "Parse CSV ON DEVICE (the GpuBatchScanExec.scala:87 cudf-csv role): "
    "the host finds line/field boundaries in one vectorized pass, the "
    "raw bytes upload once, and a traced digit-DP kernel converts "
    "int/double/bool columns while string columns gather their char "
    "matrix from the same buffer. Files with quoted fields, custom null "
    "tokens, or values beyond the DP's exact range fall back to the "
    "host reader per file.")

PARQUET_DEVICE_ENCODE = conf_bool(
    "spark.rapids.sql.parquet.deviceEncode.enabled", True,
    "Encode parquet ON DEVICE (the Table.writeParquetChunked split, "
    "GpuParquetFileFormat.scala:243): a traced kernel compacts def-level "
    "and value lanes in encoding order; the host RLE-frames pages and "
    "writes the thrift footer. Columns outside the encoder's scope fall "
    "back to the host Arrow writer per file.")

ADAPTIVE_ENABLED = conf_bool(
    "spark.rapids.sql.adaptive.enabled", False,
    "Re-plan shuffle reads with OBSERVED map-output sizes: coalesce "
    "adjacent small reduce partitions toward the target size, and split "
    "skewed partitions by map ranges where co-partitioning is not required "
    "(GpuCustomShuffleReaderExec.scala:38 / ShuffledBatchRDD.scala:31-105 "
    "analog). Off by default because every exchange here carries a "
    "user-specified partition count, which Spark's AQE also respects.")

ADAPTIVE_TARGET_SIZE = conf_int(
    "spark.rapids.sql.adaptive.targetPartitionSizeBytes", 64 << 20,
    "Advisory serialized size per post-shuffle partition for adaptive "
    "coalescing/splitting (spark.sql.adaptive.advisoryPartitionSizeInBytes "
    "analog).")

WINDOW_EXTERNAL_THRESHOLD = conf_int(
    "spark.rapids.sql.window.externalThresholdBytes", 0,
    "Window inputs above this many device bytes evaluate in bounded "
    "chunks: the input external-sorts by the (shared) partition-by keys "
    "through the spill catalog and complete key groups stream one chunk "
    "at a time (GpuWindowExec + spill store interplay). 0 = a quarter "
    "of the device spill budget. Chunked output rows arrive partition-"
    "sorted rather than in input order.")

ADAPTIVE_BROADCAST_THRESHOLD = conf_int(
    "spark.rapids.sql.adaptive.autoBroadcastThresholdBytes", 10 << 20,
    "Re-plan a shuffled exchange whose OBSERVED output is at most this "
    "many serialized bytes into a broadcast-style mapper-local read "
    "(PartialMapper specs, ShuffledBatchRDD.scala:31-105): reduce-side "
    "routing is skipped and downstream joins build from the whole "
    "(small) output. Range exchanges never convert (order contract).")

ADAPTIVE_SKEW_FACTOR = conf_float(
    "spark.rapids.sql.adaptive.skewedPartitionFactor", 5.0,
    "A reduce partition is skewed when its size exceeds this multiple of "
    "the median partition size (and the threshold below).")

ADAPTIVE_SKEW_THRESHOLD = conf_int(
    "spark.rapids.sql.adaptive.skewedPartitionThresholdBytes", 256 << 20,
    "Minimum serialized size before a partition can be considered skewed.")

# ---------------------------------------------------------------------------
# Shuffle (reference RapidsConf.scala:522-618)
# ---------------------------------------------------------------------------

SHUFFLE_COMPRESSION_CODEC = conf_str(
    "spark.rapids.shuffle.compression.codec", "none",
    "Codec for shuffle payloads: none, lz4, zstd.")

SHUFFLE_PARTITIONS = conf_int(
    "spark.sql.shuffle.partitions", 16,
    "Number of partitions used for exchanges (Spark's own key, honored here).")

SHUFFLE_ICI_ENABLED = conf_bool(
    "spark.rapids.shuffle.ici.enabled", True,
    "Exchange partitions between chips with XLA all_to_all collectives over "
    "ICI instead of host round-trips (the UCX-transport analog).")

SHUFFLE_MAX_INFLIGHT_BYTES = conf_int(
    "spark.rapids.shuffle.maxReceiveInflightBytes", 1 << 30,
    "Throttle on bytes being fetched concurrently by the shuffle client "
    "(reference RapidsShuffleTransport.scala:418-425).")

SHUFFLE_NET_CONNECT_TIMEOUT = conf_float(
    "spark.rapids.tpu.shuffle.net.connectTimeout", 5.0,
    "Seconds the shuffle wire client waits to establish a TCP connection "
    "to a peer's NetShuffleServer before the attempt counts as a fetch "
    "failure (retried by RetryingBlockIterator, then escalated to "
    "recompute/blacklist). See docs/fault-tolerance.md.")

SHUFFLE_NET_REQUEST_TIMEOUT = conf_float(
    "spark.rapids.tpu.shuffle.net.requestTimeout", 30.0,
    "Seconds the shuffle wire client waits on any single socket "
    "read/write once connected — the slow-peer stall bound: a peer that "
    "stops sending mid-block fails this fetch attempt instead of "
    "wedging the query. See docs/fault-tolerance.md.")

SHUFFLE_NET_ENABLED = conf_bool(
    "spark.rapids.tpu.shuffle.net.enabled", False,
    "Route reduce-side shuffle reads through the TCP wire plane: the "
    "exchange serves its block catalog from a NetShuffleServer and "
    "fetches every block back through the full protocol-v3 client "
    "(handshake, CRC32C verification, timeouts, retry/refetch, "
    "recompute escalation) over a real loopback socket — the same code "
    "path a remote peer exercises, used to harden and CI-gate the "
    "distributed plane. Off by default: in-process reads skip the wire.")

SHUFFLE_NET_MAX_PEER_FAILURES = conf_int(
    "spark.rapids.tpu.shuffle.net.maxPeerFailures", 3,
    "Exhausted fetch attempts (full retry ladders, not individual "
    "refetches) against one peer before the MapOutputTracker "
    "blacklists it for the session: later reads stop dialing it and go "
    "straight to lineage recompute. 0 disables blacklisting.")

SHUFFLE_REPLICATION_FACTOR = conf_int(
    "spark.rapids.tpu.shuffle.replication.factor", 0,
    "Replica peers each map output is pushed to (through the wire "
    "protocol's PUT op, CRC32C-verified at the replica) after the "
    "exchange's write phase. A dead, stalled, or blacklisted primary "
    "then answers from a replica instead of paying a lineage recompute, "
    "and hedged fetches have somewhere to race. Costs factor x the "
    "shuffle's serialized bytes in replica host/disk storage. 0 "
    "(default) disables replication. See docs/fault-tolerance.md.")

SHUFFLE_HEDGE_ENABLED = conf_bool(
    "spark.rapids.tpu.shuffle.hedge.enabled", True,
    "Hedge straggling shuffle fetches: when one block fetch exceeds "
    "hedge.quantileFactor x the peer's observed p50 latency (EWMA, "
    "shuffle/net.py PeerLatencyStats), launch a duplicate request "
    "against a replica (or the local recompute closure) on the shared "
    "pipeline pool — first verified result wins, the loser is "
    "cancelled. Only fires when a hedge source exists (replication "
    "factor > 0 or a recompute closure), so it is free otherwise. "
    "See docs/fault-tolerance.md#hedged-fetches.")

SHUFFLE_HEDGE_QUANTILE_FACTOR = conf_float(
    "spark.rapids.tpu.shuffle.hedge.quantileFactor", 3.0,
    "Straggler threshold: a block fetch is hedged once it has been "
    "outstanding longer than this factor x the peer's observed p50 "
    "fetch latency (never below hedge.minDelayMs). Lower values hedge "
    "more aggressively (more duplicate work, tighter tail); raise it "
    "if hedges fire on healthy jitter.")

SHUFFLE_HEDGE_MIN_DELAY_MS = conf_float(
    "spark.rapids.tpu.shuffle.hedge.minDelayMs", 20.0,
    "Floor on the hedge delay, milliseconds. Keeps sub-millisecond "
    "p50s from hedging every fetch; a COLD peer (no observed latency "
    "yet) is never hedged — the model warms on its first fetch.")

QUERY_DEADLINE_SECS = conf_float(
    "spark.rapids.tpu.query.deadlineSecs", 0.0,
    "Wall-clock budget for one query, seconds. Cooperatively cancels "
    "in-flight shuffle fetches, pipeline waits, and retry/backoff loops "
    "once exceeded, raising QueryDeadlineExceeded naming the slowest "
    "site (classified fatal — deadlines are a contract, not a fault to "
    "retry). The per-tenant time-budget primitive of the multi-tenant "
    "serving roadmap. 0 (default) disables. See docs/fault-tolerance.md.")

LOCKDEP_ENABLED = conf_bool(
    "spark.rapids.tpu.lockdep.enabled", False,
    "Instrument engine locks constructed AFTER session init with runtime "
    "lockdep (utils/lockdep.py): named locks, an observed lock-order "
    "graph, and recorded lock-order-inversion / self-deadlock / "
    "hold-across-blocking violations. Module-level locks are built at "
    "import time, so full coverage needs the TPU_LOCKDEP=1 environment "
    "variable before the engine is imported (tier-1 CI sets it). "
    "Near-zero cost when off: lock factories return raw threading "
    "primitives. See docs/concurrency.md.")

SHUFFLE_CHECKSUM_ENABLED = conf_bool(
    "spark.rapids.tpu.shuffle.checksum.enabled", True,
    "Compute and verify CRC32C checksums on every shuffle block "
    "(catalog registration, wire protocol v3 fetches, local reads) and "
    "every spill range, so corruption surfaces as a typed transient "
    "error — recovered by refetch or map recompute — never as a wrong "
    "answer. Disabling skips verification across every SHUFFLE catalog "
    "tier including its disk spill file (kill switch; the wire protocol "
    "still carries checksums, and the OOM spill catalog always "
    "verifies).")

# ---------------------------------------------------------------------------
# TPU-specific knobs (no reference analog; new hardware, new keys)
# ---------------------------------------------------------------------------

TOPK_THRESHOLD = conf_int(
    "spark.rapids.tpu.sort.topKThreshold", 16384,
    "ORDER BY ... LIMIT n with n at or below this collapses to the "
    "streaming top-k exec (lax.top_k, O(n log k)) instead of a global "
    "sort. 0 disables limit-into-sort.")

TPU_UPLOAD_CACHE_BYTES = conf_int(
    "spark.rapids.tpu.uploadCache.maxBytes", 1 << 30,
    "Byte budget for the host->device upload memo: conversions are keyed "
    "on the immutable arrow buffers, so re-collecting over the same host "
    "data skips dictionary encoding, padding, and the transfer. 0 "
    "disables.")

TPU_CAPACITY_BUCKETING = conf_bool(
    "spark.rapids.tpu.capacityBucketing.enabled", True,
    "Pad device batches to bucket-ladder capacities so XLA compiles one "
    "program per rung instead of one per row count (compile/ladder.py). "
    "Disabling degrades to bare 128-lane alignment — debugging only.")

TPU_MIN_CAPACITY = conf_int(
    "spark.rapids.tpu.minCapacity", 128,
    "Smallest device batch capacity (the bucket ladder's bottom rung); "
    "aligns with the 8x128 VPU lane layout. Deployments that never see "
    "small batches can raise this to skip compiling the tiny rungs.")

TPU_LADDER_GROWTH = conf_float(
    "spark.rapids.tpu.bucketLadder.growth", 2.0,
    "Geometric spacing between capacity-ladder rungs. 2.0 is the classic "
    "power-of-two ladder; 4.0 quarters the number of programs XLA ever "
    "compiles at the price of up to 4x padding (attractive where "
    "compiles are slow); values toward 1.5 trade more programs for "
    "less padded HBM. Rungs stay 128-lane aligned. See "
    "docs/compile-cache.md.")

TPU_LADDER_MAX_CAPACITY = conf_int(
    "spark.rapids.tpu.bucketLadder.maxCapacity", 0,
    "Ladder top: batches above this capacity get an exact lane-aligned "
    "fit instead of the next geometric rung, bounding padded HBM waste "
    "for huge batches. 0 = unbounded.")

POLYMORPHIC_ENABLED = conf_bool(
    "spark.rapids.tpu.polymorphic.enabled", True,
    "Shape-polymorphic fused executables: pad a fused program's boundary "
    "inputs up to coarse capacity TIERS (see polymorphic.tierGrowth) "
    "before dispatch, so ONE compiled XLA executable serves every "
    "bucket-ladder rung inside a tier instead of re-specializing per "
    "rung — O(kernels) compiles instead of O(rungs x kernels). Row "
    "counts stay dynamic scalar operands (the live-mask invariant makes "
    "padded rows dead), so results are bit-identical to the per-rung "
    "path, which remains available as the oracle by disabling this key. "
    "See docs/compile-cache.md.")

POLYMORPHIC_TIER_GROWTH = conf_float(
    "spark.rapids.tpu.polymorphic.tierGrowth", 4.0,
    "Geometric spacing of the polymorphic capacity tiers, anchored at "
    "the bucket-ladder base. 4.0 bounds padded HBM/compute waste at 4x "
    "while merging ~2 power-of-two rungs per executable; 16.0 merges 4 "
    "rungs per executable (one compile per 16x of data growth — right "
    "where compile time dominates) at "
    "up to 16x padding. Tiers always land on bucket-ladder rungs. See "
    "docs/tuning-guide.md for the padding-waste vs compile-count "
    "tradeoff.")

FUSION_COMPILE_BUDGET_SECS = conf_float(
    "spark.rapids.tpu.fusion.compileBudgetSecs", 120.0,
    "Compile-cost budget for one fused region: when compiling a fused "
    "program takes longer than this (measured at first dispatch, "
    "recorded per plan in the compile manifest), future builds of the "
    "same plan SPLIT the fusion region at its most expensive boundary — "
    "first the largest inlined join, then every join — trading one "
    "giant compile for smaller cacheable ones (the q3/bb_q01 class of "
    "compile blowups). 0 disables splitting. See docs/compile-cache.md.")

COMPILE_CACHE_ENABLED = conf_bool(
    "spark.rapids.tpu.compileCache.enabled", False,
    "Keep a manifest of (plan, capacity-rung) shapes beside JAX's "
    "persistent compilation cache, so a restarted process can warm up "
    "what it served before, and apply compileCache.dir / "
    "compileCache.minCompileSecs. The executable cache itself is on "
    "whenever the package is imported, under JAX_COMPILATION_CACHE_DIR "
    "or else <checkout>/.jax_cache (docs/compile-cache.md). The "
    "JAX_ENABLE_COMPILATION_CACHE=false environment kill-switch always "
    "wins.")

COMPILE_CACHE_DIR = conf_str(
    "spark.rapids.tpu.compileCache.dir", None,
    "Directory for the compile manifest, and for the executable cache "
    "unless JAX_COMPILATION_CACHE_DIR already placed that. Default: the "
    "package's cache directory (JAX_COMPILATION_CACHE_DIR, else "
    "<checkout>/.jax_cache).")

COMPILE_CACHE_MIN_COMPILE_SECS = conf_float(
    "spark.rapids.tpu.compileCache.minCompileSecs", 0.0,
    "Only persist executables whose compile took at least this long "
    "(jax_persistent_cache_min_compile_time_secs). 0 persists "
    "everything.")

WARMUP_AUTO = conf_bool(
    "spark.rapids.tpu.warmup.auto", False,
    "After each fused query runs at some capacity rung, AOT-compile the "
    "same program at neighboring ladder rungs (and any rung recorded in "
    "the compile manifest) in a background thread, so growing data never "
    "stalls at a rung boundary. Off by default: it multiplies compile "
    "work, which only pays off for long-lived serving sessions.")

WARMUP_RUNGS_AHEAD = conf_int(
    "spark.rapids.tpu.warmup.rungsAhead", 1,
    "How many ladder rungs ABOVE the observed capacity the auto warm-up "
    "pre-compiles (growing datasets climb the ladder upward).")

WARMUP_RUNGS_BEHIND = conf_int(
    "spark.rapids.tpu.warmup.rungsBehind", 0,
    "How many ladder rungs BELOW the observed capacity the auto warm-up "
    "pre-compiles.")

TPU_JOIN_OUTPUT_GROWTH = conf_float(
    "spark.rapids.tpu.join.outputGrowthFactor", 1.0,
    "Initial output-capacity estimate for joins as a multiple of the probe "
    "side; joins re-execute with a larger bucket on overflow.")

TPU_COLLECT_GUESS_ROWS = conf_int(
    "spark.rapids.tpu.collect.guessRows", 1024,
    "Row-capacity guess for the single-round-trip result download of a fused "
    "query: results at most this large come back in ONE device->host "
    "transfer; larger results pay a second, bandwidth-bound transfer. "
    "A larger guess downloads more padding with every collect; typical "
    "analytic results (aggregates, top-N) fit in 1024.")

TPU_FUSION_ENABLED = conf_bool(
    "spark.rapids.tpu.fusion.enabled", True,
    "Trace an entire device plan into one compiled XLA program (whole-stage "
    "fusion): one dispatch and one device->host transfer per query.")

TPU_FUSION_INLINE_JOINS = conf_bool(
    "spark.rapids.tpu.fusion.inlineJoins", True,
    "Inline hash joins into the fused whole-stage program instead of "
    "running each join as an eager boundary: removes per-join dispatches "
    "and intermediate materialization. Disable when many-sort fused programs are too "
    "expensive to compile.")

TPU_MESH_ENABLED = conf_bool(
    "spark.rapids.tpu.mesh.enabled", False,
    "Run mesh-capable queries as ONE SPMD program over all devices "
    "(jax.sharding.Mesh): sources shard row-wise, aggregate/join "
    "boundaries exchange over ICI via all_to_all (exec/mesh.py). The "
    "engine-integrated form of the reference's GPU-resident shuffle "
    "manager.")

MESH_HEALTH_PROBE_ENABLED = conf_bool(
    "spark.rapids.tpu.mesh.health.probeEnabled", False,
    "Probe every mesh device (a tiny put + block_until_ready) before "
    "dispatching a mesh-capable query as an SPMD program: a device that "
    "fails the probe degrades the session to the single-chip path "
    "up front (meshFailovers metric, flight-recorder dump) instead of "
    "failing mid-collect. Off by default — the probe costs one device "
    "round-trip per dispatch.")

MESH_HEALTH_REPROBE_SECS = conf_float(
    "spark.rapids.tpu.mesh.health.reprobeSecs", 0.0,
    "Seconds after a mesh degradation before the session re-probes the "
    "mesh and, if every device answers, restores SPMD dispatch. 0 "
    "(default): a degraded session stays on the single-chip path for "
    "its lifetime (probe_mesh() re-probes on demand).")

PIPELINE_ENABLED = conf_bool(
    "spark.rapids.tpu.pipeline.enabled", True,
    "Overlap the host-side execution pipeline (exec/pipeline.py): "
    "independent fusion-boundary subtrees materialize concurrently on a "
    "shared worker pool, file readers decode ahead with bounded prefetch, "
    "the streaming download path starts the next batch's dispatch before "
    "downloading the previous one, and shuffle serialization overlaps "
    "device work. Results are bit-identical with the pipeline on or off; "
    "a session with fault injection active always runs the serial path so "
    "per-site fault schedules stay deterministic. See docs/tuning-guide.md.")

PIPELINE_DECODE_THREADS = conf_int(
    "spark.rapids.tpu.pipeline.decodeThreads", 0,
    "Concurrent file/row-group decode tasks the pipeline layer runs on "
    "the shared pool (scan decode + upload assembly). 0 = auto "
    "(min(4, cpu count), at least 2). Raising it helps many-file scans on "
    "hosts with spare cores; each in-flight decode holds one host batch "
    "plus its upload buffers.")

PIPELINE_PREFETCH_DEPTH = conf_int(
    "spark.rapids.tpu.pipeline.prefetchDepth", 2,
    "Bounded look-ahead of every pipeline stage: batches a prefetch "
    "worker keeps ready ahead of its consumer, and decode tasks in "
    "flight ahead of the scan cursor. Deeper prefetch hides more "
    "producer latency at the price of that many extra live batches in "
    "host memory and HBM (see docs/tuning-guide.md for sizing against "
    "HBM pressure).")

PIPELINE_BOUNDARY_PARALLELISM = conf_int(
    "spark.rapids.tpu.pipeline.boundaryParallelism", 0,
    "Independent fusion-boundary subtrees materialized concurrently "
    "before a fused dispatch (exec/fusion.py). 0 = auto (min(4, cpu "
    "count), at least 2); 1 forces serial boundary materialization. "
    "Device admission of the concurrent workers is still bounded by "
    "spark.rapids.sql.concurrentTpuTasks — the dispatching thread "
    "releases its own slot while it waits, the reference's "
    "release-during-shuffle discipline.")

METRICS_LEVEL = conf_str(
    "spark.rapids.tpu.metrics.level", "MODERATE",
    "Operator metrics level: NONE disables the whole query-profile layer "
    "(no metric recording, no QueryProfile, no timing fences — asserted "
    "bit-identical to metrics-free execution by tests), ESSENTIAL records "
    "the core taxonomy (rows/batches/bytes/opTime/spill), MODERATE adds "
    "build/semaphore/compile timings, DEBUG adds serialization and concat "
    "internals. The GpuMetric-level analog "
    "(spark.rapids.sql.metrics.level). See docs/monitoring.md.")

METRICS_DEVICE_TIMING = conf_bool(
    "spark.rapids.tpu.metrics.deviceTiming", False,
    "Attribute DEVICE time per query: insert a block-until-ready fence "
    "after the fused dispatch and record dispatch-to-ready nanoseconds as "
    "the deviceTime metric. Off by default because the fence serializes "
    "the dispatch pipeline — the default path runs with zero fences (the "
    "tests assert none are inserted). See docs/monitoring.md.")

METRICS_EVENT_LOG_DIR = conf_str(
    "spark.rapids.tpu.metrics.eventLog.dir", None,
    "Directory for the structured query event log: every executed query "
    "appends its QueryProfile as one JSON line to query_profiles.jsonl "
    "(crash-safe append; torn lines are skipped on read — same stance as "
    "the compile manifest). Unset disables the log. See "
    "docs/monitoring.md for the record schema.")

METRICS_EVENT_LOG_MAX_BYTES = conf_int(
    "spark.rapids.tpu.metrics.eventLog.maxBytes", 64 << 20,
    "Size-capped rotation for the event log in a long-lived serving "
    "process: when an append would push query_profiles.jsonl past this "
    "many bytes, the file atomically rotates to query_profiles.jsonl.1 "
    "(one prior generation kept) and the append starts a fresh file — "
    "crash-safe (os.replace) and torn-line tolerant like the append "
    "itself. 0 disables rotation (unbounded growth). See "
    "docs/monitoring.md.")

TRACE_ENABLED = conf_bool(
    "spark.rapids.tpu.trace.enabled", False,
    "Per-query distributed tracing (metrics/trace.py): a span tree "
    "spanning serve admission/queue wait, session dispatch, the retry "
    "ladder, pipeline workers, the spill-IO lane, compile/warmup "
    "events, and shuffle map/fetch/recompute — with trace context "
    "propagated over both wire protocols (the SRTQS serve field and the "
    "shuffle net protocol-v4 header) so multi-peer fetches stitch into "
    "one trace. Each query exports Chrome trace-event JSON "
    "(Perfetto-loadable) beside the event log; tools/trace_report.py "
    "computes the critical path. Off by default: the disabled path is "
    "no-op spans, no fences, bit-identical results (asserted by tests). "
    "Read per session. See docs/monitoring.md#distributed-tracing.")

TRACE_DIR = conf_str(
    "spark.rapids.tpu.trace.dir", None,
    "Directory for exported per-query trace files "
    "(trace_<trace_id>.json). Unset: traces land beside the event log "
    "(spark.rapids.tpu.metrics.eventLog.dir); with neither set, spans "
    "still feed the in-memory flight recorder but no per-query file is "
    "written.")

TRACE_MAX_FILES = conf_int(
    "spark.rapids.tpu.trace.maxFiles", 256,
    "Retention bound on exported trace files: after each export the "
    "oldest trace_*.json beyond this count are pruned from the trace "
    "directory, so a long-lived traced serving process cannot fill the "
    "disk (the eventLog.maxBytes stance applied to traces). 0 disables "
    "pruning.")

TRACE_FLIGHT_SPANS = conf_int(
    "spark.rapids.tpu.trace.flightRecorder.spans", 4096,
    "Bound on the in-memory flight recorder: the ring buffer keeps this "
    "many recent finished spans + engine events across all queries, "
    "dumped to JSON on QueryDeadlineExceeded, circuit-breaker "
    "quarantine trips, SessionCrashError, and SIGTERM. See "
    "docs/monitoring.md#flight-recorder.")

TRACE_FLIGHT_DIR = conf_str(
    "spark.rapids.tpu.trace.flightRecorder.dir", "artifacts",
    "Directory flight-recorder dumps are written to "
    "(flight_<reason>_<pid>_<n>.json; bounded per reason so a crash "
    "loop cannot flood it).")

# ---------------------------------------------------------------------------
# ML scenario subsystem (ml/, exec/ml_score.py, docs/ml-integration.md)
# ---------------------------------------------------------------------------

TPU_ML_ENABLED = conf_bool(
    "spark.rapids.tpu.ml.enabled", True,
    "Run ModelScore (df.with_model_score — batch inference over a "
    "registered model INSIDE the query plan) on the device: features "
    "gather straight from the device batch and the prediction kernel "
    "rides the kernel cache and fused-dispatch machinery. false keeps "
    "the operator on the CPU oracle path, which evaluates the SAME "
    "predict function on host-assembled features — the bit-identity "
    "twin the differential tests compare against. See "
    "docs/ml-integration.md.")

TPU_ML_MAX_MODELS = conf_int(
    "spark.rapids.tpu.ml.maxRegisteredModels", 64,
    "Bound on models a session's ModelRegistry holds at once "
    "(re-registering an existing name replaces it in place and does not "
    "count). Registered models are spillable device buffers, so the "
    "bound caps registry HBM/host residency the way the result cache "
    "caps serving memory; exceeding it raises instead of silently "
    "evicting a model a running query may score with. See "
    "docs/ml-integration.md.")

PLAN_LINT_ENABLED = conf_bool(
    "spark.rapids.tpu.planLint.enabled", True,
    "Statically verify every physical plan after planning and again after "
    "the TPU rewrite (analysis/plan_lint.py): per-node schema consistency "
    "against child schemas, cast-lattice legality, host<->device "
    "transition correctness, shuffle partitioning contracts at joins, and "
    "parquet writer physical-type widths. Error-severity violations raise "
    "PlanLintError with the offending node path; warn-severity violations "
    "log and fall the query back to the CPU plan. See docs/plan-lint.md.")

PLAN_LINT_FAIL_ON_WARN = conf_bool(
    "spark.rapids.tpu.planLint.failOnWarn", False,
    "Promote warn-severity plan-lint violations (which normally log and "
    "fall back to the CPU plan) to hard PlanLintError failures. Intended "
    "for CI and tests. See docs/plan-lint.md.")

# ---------------------------------------------------------------------------
# Multi-tenant query service (serve/, docs/serving.md)
# ---------------------------------------------------------------------------

SERVE_SESSIONS = conf_int(
    "spark.rapids.tpu.serve.sessions", 2,
    "Warm TpuSessions the query service (serve/) pools. Each pooled "
    "session loads the registered tables once and serves one query at a "
    "time; a session that dies mid-query is torn down and replaced "
    "without disturbing its neighbors. See docs/serving.md.")

SERVE_MAX_CONCURRENT = conf_int(
    "spark.rapids.tpu.serve.maxConcurrentQueries", 0,
    "Queries the service admits concurrently (the fair-share gate's slot "
    "count, layered in FRONT of spark.rapids.sql.concurrentTpuTasks). "
    "0 = one per pooled session. See docs/serving.md.")

SERVE_MAX_QUEUE_DEPTH = conf_int(
    "spark.rapids.tpu.serve.maxQueueDepth", 16,
    "Bound on each tenant's admission queue: a submit arriving when the "
    "tenant already has this many queries waiting is SHED with a typed "
    "ServiceOverloadedError carrying a retry-after hint — overload "
    "answers as fast typed backpressure, never as unbounded queueing. "
    "See docs/serving.md.")

SERVE_TENANT_WEIGHTS = conf_str(
    "spark.rapids.tpu.serve.tenantWeights", "",
    "Comma-separated 'tenant:weight' fair-share weights for the "
    "admission gate (stride scheduling: a weight-2 tenant is admitted "
    "twice as often under contention). Unlisted tenants weigh 1. "
    "See docs/serving.md.")

SERVE_TENANT_TIME_BUDGET = conf_str(
    "spark.rapids.tpu.serve.tenantTimeBudgetSecs", "",
    "Comma-separated 'tenant:seconds' per-query wall-clock budgets, "
    "enforced through the PR-7 cooperative Deadline spanning queue wait "
    "AND execution (including the retry ladder). 'default:N' applies to "
    "unlisted tenants; 0/absent = unbounded. Exceeding the budget "
    "raises the typed QueryDeadlineExceeded. See docs/serving.md.")

SERVE_TENANT_MEMORY_BUDGET = conf_str(
    "spark.rapids.tpu.serve.tenantMemoryBudgetBytes", "",
    "Comma-separated 'tenant:bytes' device-memory budgets: before each "
    "of a tenant's queries runs, its device-resident spillable bytes "
    "above budget are spilled via the QoS victim order (its OWN buffers "
    "— an over-budget tenant pays with its own residency, never a "
    "neighbor's). 'default:N' applies to unlisted tenants; 0/absent = "
    "unbounded. See docs/serving.md.")

SERVE_QUARANTINE_FAILURES = conf_int(
    "spark.rapids.tpu.serve.quarantine.maxFailures", 2,
    "Retry-ladder exhaustions (OOM-classified failures that escaped the "
    "whole memory/retry.py ladder, or repeated session crashes) of one "
    "plan hash before the circuit breaker quarantines it: further "
    "submits of that plan are rejected with the typed "
    "QueryQuarantinedError instead of re-admitted to burn the pool. "
    "0 disables the breaker. See docs/serving.md.")

SERVE_QUARANTINE_SECS = conf_float(
    "spark.rapids.tpu.serve.quarantine.secs", 300.0,
    "How long a quarantined plan hash stays rejected before one probe "
    "execution is allowed again (half-open breaker).")

SERVE_RESULT_CACHE_ENTRIES = conf_int(
    "spark.rapids.tpu.serve.resultCache.maxEntries", 64,
    "LRU capacity of the serving result cache, keyed by (tenant, PR-2 "
    "plan hash). Entries store the CRC32C-verified serialized result, so "
    "a poisoned entry is detected on hit and recomputed, never served. "
    "Invalidation is tenant-scoped (QueryService.invalidate). 0 "
    "disables. See docs/serving.md.")

SERVE_SHED_RETRY_AFTER_SECS = conf_float(
    "spark.rapids.tpu.serve.shedRetryAfterSecs", 0.25,
    "Base of the retry-after hint a shed (ServiceOverloadedError) "
    "carries; scaled by how loaded the admission gate is when the shed "
    "happens.")

FAULT_INJECTION_SERVE_EVERY_N = conf_int(
    "spark.rapids.tpu.test.faultInjection.serveEveryN", 0,
    "Apply a deterministic SERVING-SEAM fault at every Nth visit of the "
    "matched serve.* site (serve.admission / serve.execute / "
    "serve.cache; the 'sites' patterns gate it). Negative N faults the "
    "first |N| visits then heals. The fault class per visit is chosen "
    "deterministically from the seed among faultInjection.serveFaults "
    "(restricted to the classes valid at that seam). 0 disables.")

FAULT_INJECTION_SERVE_FAULTS = conf_str(
    "spark.rapids.tpu.test.faultInjection.serveFaults",
    "tenantKill,sessionCrash,cachePoison,admissionStall",
    "Comma-separated serving fault classes the injector may apply: "
    "tenantKill (the victim query is cancelled mid-flight — typed "
    "QueryCancelledError, neighbors unaffected), sessionCrash (the "
    "pooled session dies — torn down, replaced, read-only query re-run "
    "once), cachePoison (the stored result-cache entry is corrupted — "
    "CRC32C catches it on hit and the query recomputes), admissionStall "
    "(a delay inside the admission queue — drives shed paths). A single "
    "name pins every injected fault to that class.")

DEVICE_BACKEND = conf_str(
    "spark.rapids.tpu.backend", None,
    "Force a jax backend for device execution (tpu/cpu). Default: jax default.",
    internal=True)


class TpuConf:
    """Immutable snapshot of configuration, with typed accessors.

    Mirrors the accessor layer of ``RapidsConf`` (reference
    RapidsConf.scala:700-885).
    """

    def __init__(self, conf: Optional[Dict[str, Any]] = None):
        self._conf = dict(conf or {})
        for k in self._conf:
            if k.startswith("spark.rapids.") and k not in _REGISTRY:
                raise KeyError(f"unknown rapids conf key: {k}")

    def get(self, entry: ConfEntry) -> Any:
        return entry.get(self._conf)

    def with_overrides(self, **kv: Any) -> "TpuConf":
        merged = dict(self._conf)
        merged.update(kv)
        return TpuConf(merged)

    def raw(self, key: str, default: Optional[str] = None) -> Optional[str]:
        return self._conf.get(key, default)

    # Typed shortcuts used widely.
    @property
    def sql_enabled(self) -> bool:
        return self.get(SQL_ENABLED)

    @property
    def explain(self) -> str:
        return str(self.get(EXPLAIN)).upper()

    @property
    def test_enabled(self) -> bool:
        return self.get(TEST_ENABLED)

    @property
    def allowed_non_tpu(self) -> List[str]:
        raw = self.get(TEST_ALLOWED_NON_TPU)
        return [s.strip() for s in raw.split(",") if s.strip()]

    @property
    def batch_size_rows(self) -> int:
        return self.get(BATCH_SIZE_ROWS)

    @property
    def shuffle_partitions(self) -> int:
        return self.get(SHUFFLE_PARTITIONS)

    @property
    def collect_guess_rows(self) -> int:
        return self.get(TPU_COLLECT_GUESS_ROWS)

    @property
    def fusion_enabled(self) -> bool:
        return self.get(TPU_FUSION_ENABLED)

    @property
    def fusion_inline_joins(self) -> bool:
        return self.get(TPU_FUSION_INLINE_JOINS)

    @property
    def polymorphic_enabled(self) -> bool:
        return self.get(POLYMORPHIC_ENABLED)

    @property
    def fusion_compile_budget_secs(self) -> float:
        return self.get(FUSION_COMPILE_BUDGET_SECS)

    @property
    def mesh_enabled(self) -> bool:
        return self.get(TPU_MESH_ENABLED)

    @property
    def pipeline_enabled(self) -> bool:
        return self.get(PIPELINE_ENABLED)

    @property
    def pipeline_decode_threads(self) -> int:
        return self.get(PIPELINE_DECODE_THREADS)

    @property
    def pipeline_prefetch_depth(self) -> int:
        return self.get(PIPELINE_PREFETCH_DEPTH)

    @property
    def pipeline_boundary_parallelism(self) -> int:
        return self.get(PIPELINE_BOUNDARY_PARALLELISM)

    @property
    def metrics_level(self) -> str:
        return str(self.get(METRICS_LEVEL)).upper()

    @property
    def metrics_device_timing(self) -> bool:
        return self.get(METRICS_DEVICE_TIMING)

    @property
    def metrics_event_log_dir(self) -> Optional[str]:
        return self.get(METRICS_EVENT_LOG_DIR)

    def is_operator_enabled(self, conf_key: str, incompat: bool, disabled_by_default: bool) -> bool:
        """Three-state per-operator gating (reference RapidsMeta.tagForGpu:195-210)."""
        raw = self._conf.get(conf_key)
        if raw is not None:
            return raw if isinstance(raw, bool) else _to_bool(raw)
        if incompat:
            return self.get(INCOMPATIBLE_OPS)
        return not disabled_by_default

    @staticmethod
    def operator_conf_key(kind: str, name: str) -> str:
        """Synthesized per-op enable key (reference GpuOverrides.scala:126-131)."""
        return f"spark.rapids.sql.{kind}.{name}"

    @staticmethod
    def register_operator_key(kind: str, name: str, incompat: bool,
                              disabled_by_default: bool, doc: str) -> str:
        key = TpuConf.operator_conf_key(kind, name)
        if key not in _REGISTRY:
            default = not disabled_by_default and not incompat
            conf_bool(key, default, doc)
        return key

    @staticmethod
    def help_markdown() -> str:
        """Generate docs/configs.md, like ``RapidsConf.help`` (RapidsConf.scala:641)."""
        lines = [
            "# TPU Accelerator for Apache Spark Configuration",
            "",
            "The following configs control the TPU-native execution backend. They can be",
            "set at session creation or per query. Generated by "
            "`TpuConf.help_markdown()` — do not edit by hand.",
            "",
            "Name | Description | Default Value",
            "-----|-------------|--------------",
        ]
        for key in sorted(_REGISTRY):
            e = _REGISTRY[key]
            if e.internal:
                continue
            lines.append(f"{e.key}|{e.doc}|{e.default}")
        return "\n".join(lines) + "\n"


DEFAULT_CONF = TpuConf()
