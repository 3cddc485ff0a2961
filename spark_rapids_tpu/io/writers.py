"""Columnar file writers — the ``GpuFileFormatWriter`` stack analog.

The reference clones Spark's whole writer framework columnar-side (SURVEY.md
§2.5): ``ColumnarOutputWriter[Factory]`` streams cudf-encoded buffers to the
filesystem (ColumnarOutputWriter.scala:37), ``GpuFileFormatWriter.scala:338``
orchestrates the job, ``GpuFileFormatDataWriter.scala:417`` implements the
single-directory and dynamic-partition (hive-layout) writers — the dynamic
writer sorts by partition keys and switches output files on key change — and
write-stats trackers count files/partitions/rows/bytes
(BasicColumnarWriteStatsTracker.scala:168).

Same architecture here. Encoding happens host-side via Arrow (the device
parquet/ORC *encode* kernel is a later milestone, like the reference's device
decode); the TPU writer's device-side work is the dynamic-partition split:
one device sort by partition keys, then contiguous runs slice out per
partition directory — the same sort-based strategy the reference's dynamic
writer uses, but as one XLA program.
"""

from __future__ import annotations

import dataclasses
import os
import uuid
from typing import Dict, List

import numpy as np
import pyarrow as pa
import pyarrow.compute as pc

from .. import types as T
from ..data.batch import HostBatch
from ..plan.physical import ExecContext, PhysicalPlan
from ..metrics.trace import span

#: Spark-compatible save modes.
MODES = ("error", "overwrite", "append", "ignore")

_EXT = {"parquet": "parquet", "orc": "orc", "csv": "csv"}


@dataclasses.dataclass
class WriteStats:
    """BasicColumnarWriteStatsTracker analog."""

    files: int = 0
    partitions: int = 0
    rows: int = 0
    bytes: int = 0

    def to_batch(self) -> HostBatch:
        schema = pa.schema([("files", pa.int64()), ("partitions", pa.int64()),
                            ("rows", pa.int64()), ("bytes", pa.int64())])
        return HostBatch(pa.RecordBatch.from_arrays(
            [pa.array([self.files]), pa.array([self.partitions]),
             pa.array([self.rows]), pa.array([self.bytes])], schema=schema))


STATS_SCHEMA = T.Schema([T.StructField("files", T.LONG, False),
                         T.StructField("partitions", T.LONG, False),
                         T.StructField("rows", T.LONG, False),
                         T.StructField("bytes", T.LONG, False)])


def _write_one(data, fmt: str, path: str, options: Dict) -> int:
    """Encode one file; returns bytes written (ColumnarOutputWriter analog)."""
    table = data if isinstance(data, pa.Table) else pa.Table.from_batches(
        [data])
    compression = options.get("compression")
    with span(None, f"write.{fmt}"):
        if fmt == "parquet":
            import pyarrow.parquet as pq
            pq.write_table(table, path,
                           compression=compression or "snappy")
        elif fmt == "orc":
            import pyarrow.orc as orc
            orc.write_table(table, path)
        elif fmt == "csv":
            import pyarrow.csv as pacsv
            opts = pacsv.WriteOptions(
                include_header=bool(options.get("header", True)),
                delimiter=options.get("delimiter", ","))
            pacsv.write_csv(table, path, opts)
        else:
            raise ValueError(f"unknown write format {fmt}")
    return os.path.getsize(path)


#: Characters Spark escapes in partition directory names
#: (ExternalCatalogUtils.escapePathName): controls + these ASCII specials.
_ESCAPE_CHARS = set('"#%\'*/:=?\\{[]^\x7f') | {chr(c) for c in range(0x20)}


def _escape_path_name(s: str) -> str:
    return "".join(f"%{ord(c):02X}" if c in _ESCAPE_CHARS else c for c in s)


def _partition_dir_value(v) -> str:
    if v is None:
        return "__HIVE_DEFAULT_PARTITION__"
    if isinstance(v, bool):
        return str(v).lower()
    return _escape_path_name(str(v))


def prepare_target(path: str, mode: str) -> bool:
    """Apply the save mode; returns False when the write should be skipped
    (mode=ignore on existing target)."""
    assert mode in MODES, mode
    exists = os.path.exists(path) and (not os.path.isdir(path)
                                       or bool(os.listdir(path)))
    if exists:
        if mode == "error":
            raise FileExistsError(
                f"path {path} already exists (SaveMode.ErrorIfExists)")
        if mode == "ignore":
            return False
        if mode == "overwrite":
            if os.path.isdir(path):
                import shutil
                shutil.rmtree(path, ignore_errors=True)
            else:
                os.remove(path)
    os.makedirs(path, exist_ok=True)
    return True


def run_boundaries(key_cols: List[pa.ChunkedArray], n: int) -> List[int]:
    """Indices where any sorted partition-key column changes (vectorized
    shifted comparison; two nulls compare equal)."""
    if n == 0:
        return [0]
    neq = None
    for c in key_cols:
        c = c.combine_chunks() if isinstance(c, pa.ChunkedArray) else c
        a, b = c.slice(1), c.slice(0, n - 1)
        d = pc.fill_null(pc.not_equal(a, b), False)
        d = pc.or_(d, pc.xor(pc.is_null(a), pc.is_null(b)))
        neq = d if neq is None else pc.or_(neq, d)
    if neq is None:
        return [0, n]
    changed = np.nonzero(neq.to_numpy(zero_copy_only=False))[0]
    return [0] + [int(i) + 1 for i in changed] + [n]


class _WriteFilesBase(PhysicalPlan):
    """Shared writer-job skeleton (GpuFileFormatWriter analog): target prep,
    per-file encode + stats, hive subdirs, job-commit marker. Subclasses
    supply the batch stream and the dynamic-partition grouping strategy."""

    def __init__(self, child: PhysicalPlan, fmt: str, path: str,
                 options: Dict, partition_by: List[str], mode: str):
        self.children = [child]
        self.fmt = fmt
        self.path = path
        self.options = options
        self.partition_by = partition_by
        self.mode = mode
        # Unique per job so append mode never collides with the files of an
        # earlier write (Spark embeds the job UUID the same way).
        self._job_id = uuid.uuid4().hex[:8]
        self._prepare_result: bool = None
        self._emitted: set = set()

    def _prepare_once(self) -> bool:
        """Apply the save mode exactly once per plan instance: a
        dispatch-level transient retry (session._run_with_retries)
        re-executes the plan, and re-applying the mode would rmtree fresh
        output (overwrite), raise (error), or silently skip (ignore).
        A re-execution instead deletes the previous attempt's own files
        (task ids can shift when a batch split-and-retried, so
        name-overwrite alone is not a sound cleanup)."""
        if self._prepare_result is None:
            self._prepare_result = prepare_target(self.path, self.mode)
        elif self._prepare_result and self._emitted:
            for p in self._emitted:
                try:
                    os.remove(p)
                except OSError:
                    pass
            self._emitted.clear()
        return self._prepare_result

    @property
    def schema(self):
        return STATS_SCHEMA

    def describe(self):
        extra = f" partitionBy={self.partition_by}" if self.partition_by \
            else ""
        return f"{self.node_name()} {self.fmt} {self.path}{extra}"

    def _data_arrow(self) -> pa.Schema:
        fields = [f for f in self.children[0].schema
                  if f.name not in self.partition_by]
        return pa.schema([pa.field(f.name, T.to_arrow_type(f.data_type),
                                   f.nullable) for f in fields])

    def _file_name(self, task_id: int, file_no: int) -> str:
        return f"part-{task_id:05d}-{self._job_id}-{file_no:03d}" \
               f".{_EXT[self.fmt]}"

    def _emit(self, data, target_dir: str, task_id: int, file_no: int,
              stats: WriteStats, n_rows: int):
        os.makedirs(target_dir, exist_ok=True)
        target = os.path.join(target_dir, self._file_name(task_id, file_no))
        stats.bytes += _write_one(data, self.fmt, target, self.options)
        self._emitted.add(target)
        stats.files += 1
        stats.rows += n_rows

    def _emit_partition(self, table: pa.Table, key_values: tuple,
                        task_id: int, file_no: int, stats: WriteStats,
                        seen_dirs: set, data_arrow: pa.Schema):
        subdir = os.path.join(self.path, *(
            f"{c}={_partition_dir_value(v)}"
            for c, v in zip(self.partition_by, key_values)))
        seen_dirs.add(subdir)
        out = pa.Table.from_arrays(
            [table.column(nm).combine_chunks() for nm in data_arrow.names],
            schema=data_arrow)
        self._emit(out, subdir, task_id, file_no, stats, table.num_rows)

    def _finish(self, stats: WriteStats, seen_dirs: set):
        stats.partitions = len(seen_dirs)
        # Job-commit marker, like Spark's Hadoop committer.
        open(os.path.join(self.path, "_SUCCESS"), "w").close()
        return [iter([stats.to_batch()])]


class CpuWriteFilesExec(_WriteFilesBase):
    """Host-side writer job: one output file per input batch, group-by based
    dynamic partitioning."""

    def execute(self, ctx: ExecContext):
        stats = WriteStats()
        if not self._prepare_once():
            return [iter([stats.to_batch()])]
        data_arrow = self._data_arrow()
        seen_dirs: set = set()
        task_id = 0
        for part in self.children[0].execute(ctx):
            for hb in part:
                if hb.num_rows == 0:
                    continue
                self._write_batch(hb.rb, task_id, stats, seen_dirs,
                                  data_arrow)
                task_id += 1
        return self._finish(stats, seen_dirs)

    def _write_batch(self, rb: pa.RecordBatch, task_id: int,
                     stats: WriteStats, seen_dirs: set,
                     data_arrow: pa.Schema):
        if not self.partition_by:
            self._emit(rb, self.path, task_id, 0, stats, rb.num_rows)
            return
        table = pa.Table.from_batches([rb])
        key_rows = list(zip(*[table.column(c).to_pylist()
                              for c in self.partition_by]))
        groups: Dict[tuple, List[int]] = {}
        for i, kr in enumerate(key_rows):
            groups.setdefault(kr, []).append(i)
        for file_no, (kr, idxs) in enumerate(sorted(
                groups.items(), key=lambda kv: tuple(map(repr, kv[0])))):
            sub = table.take(pa.array(idxs, pa.int64()))
            self._emit_partition(sub, kr, task_id, file_no, stats, seen_dirs,
                                 data_arrow)


class TpuWriteFilesExec(_WriteFilesBase):
    """Device-side writer (GpuDataWritingCommandExec + dynamic
    GpuFileFormatDataWriter analog): batches arrive on device; the
    dynamic-partition path sorts by partition keys on device so each output
    file's rows are one contiguous run (the reference's dynamic writer relies
    on the same sorted order), then the host encoder streams each run."""

    columnar = False        # emits the host stats row...
    children_columnar = True  # ...but consumes device batches
    children_coalesce_goals = ["target"]

    def execute(self, ctx: ExecContext):
        import time as _time
        from ..config import PARQUET_DEVICE_ENCODE
        from ..memory import retry as R
        from ..ops.kernels import rowops as KR
        name = self.node_name()
        t_start = _time.perf_counter_ns()
        stats = WriteStats()
        if not self._prepare_once():
            return [iter([stats.to_batch()])]
        child_schema = self.children[0].schema
        part_ordinals = [child_schema.index_of(c) for c in self.partition_by]
        data_arrow = self._data_arrow()
        seen_dirs: set = set()
        device_encode = (self.fmt == "parquet" and not part_ordinals
                         and ctx.conf.get(PARQUET_DEVICE_ENCODE))

        def device_sort(b):
            """The writer's device-side memory hazard (dynamic-partition
            sort). File emission stays OUTSIDE the retry: a retried
            attempt must never re-write a committed file."""
            if part_ordinals:
                with span(ctx.trace, "write.device_partition_sort"):
                    b = KR.sort_batch(b, part_ordinals,
                                      [True] * len(part_ordinals),
                                      [True] * len(part_ordinals))
            return b

        task_id = 0
        for part in self.children[0].execute(ctx):
            for db in part:
                if int(db.n_rows) == 0:
                    continue
                # A split input emits two (smaller) files — content is
                # identical; only the file count changes.
                for piece in R.with_retry(ctx, f"{name}.deviceWrite", db,
                                          device_sort,
                                          split=R.halve_by_rows, node=name):
                    if device_encode and self._emit_device(piece, task_id,
                                                           stats):
                        task_id += 1
                        continue
                    rb = piece.to_arrow()
                    if not part_ordinals:
                        self._emit(rb, self.path, task_id, 0, stats,
                                   rb.num_rows)
                    else:
                        self._write_sorted_runs(rb, task_id, stats,
                                                seen_dirs, data_arrow)
                    task_id += 1
        # Writer metrics mirror WriteStats (BasicColumnarWriteStatsTracker):
        # the stats row is the query result, the metrics feed the profile.
        ctx.metric(name, "numOutputRows", stats.rows)
        ctx.metric(name, "bytesWritten", stats.bytes)
        ctx.metric(name, "numFiles", stats.files)
        ctx.metric(name, "writeTime", _time.perf_counter_ns() - t_start)
        return self._finish(stats, seen_dirs)

    def _emit_device(self, db, task_id: int, stats: WriteStats) -> bool:
        """Device-encode one batch as one parquet file; False when out of
        the encoder's scope (caller falls back to the host Arrow path)."""
        from .parquet_encode import NotDeviceEncodable, write_device_batch
        target = os.path.join(self.path, self._file_name(task_id, 0))
        with span(None, "write.parquet_device_encode"):
            try:
                # `or "snappy"`: an explicit compression=None means snappy
                # on the host path too (_write_one) — keep one codec per job.
                n = write_device_batch(
                    db, target,
                    compression=self.options.get("compression") or "snappy")
            except NotDeviceEncodable:
                return False
        self._emitted.add(target)
        stats.bytes += n
        stats.files += 1
        stats.rows += int(db.n_rows)
        return True

    def _write_sorted_runs(self, rb: pa.RecordBatch, task_id: int,
                           stats: WriteStats, seen_dirs: set,
                           data_arrow: pa.Schema):
        """Slice contiguous partition-key runs out of the device-sorted
        batch; run boundaries come from one vectorized shifted comparison."""
        table = pa.Table.from_batches([rb])
        key_cols = [table.column(c) for c in self.partition_by]
        bounds = run_boundaries(key_cols, rb.num_rows)
        for file_no in range(len(bounds) - 1):
            lo, hi = bounds[file_no], bounds[file_no + 1]
            kr = tuple(kc[lo].as_py() for kc in key_cols)
            self._emit_partition(table.slice(lo, hi - lo), kr, task_id,
                                 file_no, stats, seen_dirs, data_arrow)
