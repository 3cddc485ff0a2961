"""TPU physical operators — the ``Gpu*Exec`` analogs.

Each exec consumes/produces device :class:`ColumnarBatch` streams. Per-batch
work is a jitted function over the batch pytree: XLA compiles one program per
(schema, capacity bucket) and fuses the whole operator expression tree
(project chains, filter masks, aggregation updates) into a handful of fused
kernels — the TPU answer to cudf's pre-compiled kernel library.

Operator parity map (reference locations in SURVEY.md §2.3):
* TpuProjectExec / TpuFilterExec  <- basicPhysicalOperators.scala:66,127
* TpuHashAggregateExec            <- aggregate.scala:227 (partial/merge loop)
* TpuSortExec                     <- GpuSortExec.scala:50 (RequireSingleBatch)
* TpuShuffledHashJoinExec         <- GpuShuffledHashJoinExec.scala:76 +
                                     GpuHashJoin.doJoin:113-166
* TpuRangeExec / TpuUnionExec / TpuLimitExec / TpuExpandExec
                                  <- basicPhysicalOperators.scala:182,301 /
                                     limit.scala:115 / GpuExpandExec.scala:66
* HostToDeviceExec / DeviceToHostExec <- HostColumnarToGpu.scala:222 /
                                     GpuColumnarToRowExec.scala:35
"""

from __future__ import annotations

import functools
from typing import Iterator, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
import pyarrow as pa

from .. import types as T
from ..data.batch import ColumnarBatch, HostBatch
from ..data.column import DeviceColumn, bucket_capacity
from ..ops import aggregates as AGG
from ..ops.expression import BoundReference, Expression, make_column
from ..ops.kernels import concat as KC
from ..ops.kernels import groupby as KG
from ..ops.kernels import join as KJ
from ..ops.kernels import rowops as KR
from ..plan.logical import SortOrder
from ..plan.physical import ExecContext, PhysicalPlan
from ..utils.kernel_cache import cached_kernel, kernel_key
from ..metrics.trace import span


def _bind_all(exprs: List[Expression], schema: T.Schema) -> List[Expression]:
    return [e.bind(schema) for e in exprs]


def _tick(ctx, name: str, t0: int) -> int:
    """Record one output batch + host-side dispatch time for an exec
    (GpuExec.scala:25-52's NUM_OUTPUT_BATCHES / OP_TIME analog — dispatch
    wall time only: device execution is async and row counts would cost a
    blocking device->host read). Times are nanoseconds (the taxonomy's NANO_TIMING
    opTime; metrics/registry.py)."""
    import time as _time
    now = _time.perf_counter_ns()
    ctx.metric(name, "numOutputBatches", 1)
    ctx.metric(name, "opTime", now - t0)
    return now


def _counted_stream(ctx, name: str, batches):
    """Pass-through generator recording numOutputBatches per batch — the
    minimum ESSENTIAL instrumentation for execs whose per-batch work is too
    cheap to time (union, limits, replays)."""
    for db in batches:
        ctx.metric(name, "numOutputBatches", 1)
        yield db


def _scoped(name: str, part):
    """``part`` with every pull under ``jax.named_scope(name)``. The scope
    is entered around each ``next`` and never held across a ``yield``:
    the name stack is thread-local, and a suspended generator must not
    leave its scope on the consumer's stack."""
    it = iter(part)
    while True:
        with jax.named_scope(name):
            try:
                batch = next(it)
            except StopIteration:
                return
        yield batch


def _scoped_in_fusion(execute):
    """Inside a fused program's trace, run an operator's ``execute`` and
    every pull of its partitions under a named scope of the operator's
    node name: a parent pulls its child inside its own pull, so an HLO
    op's metadata reads as its path in the plan
    (``jit(fused_1a2b3c4d)/TpuHashAggregateExec/TpuFilterExec/...``).
    Trace-time only; outside fusion the operator's own programs carry
    their names (utils/kernel_cache.py:program_name)."""
    @functools.wraps(execute)
    def scoped(self, ctx):
        if not getattr(ctx, "in_fusion", False):
            return execute(self, ctx)
        name = self.node_name()
        with jax.named_scope(name):
            parts = execute(self, ctx)
        return [_scoped(name, p) for p in parts]
    return scoped


class TpuExec(PhysicalPlan):
    columnar = True

    #: Per-child coalesce goal ("single" | "target" | None), consumed by
    #: exec.coalesce.insert_coalesce (CoalesceGoal declaration analog,
    #: reference GpuExec.childrenCoalesceGoal).
    children_coalesce_goals = None

    def __init_subclass__(cls, **kwargs):
        super().__init_subclass__(**kwargs)
        if "execute" in cls.__dict__:
            cls.execute = _scoped_in_fusion(cls.__dict__["execute"])

    def describe(self):
        return self.node_name()


# ---------------------------------------------------------------------------
# Transitions
# ---------------------------------------------------------------------------


class HostToDeviceExec(TpuExec):
    """Upload host batches, coalescing toward the batch-size goal
    (HostColumnarToGpu + CoalesceGoal, reference HostColumnarToGpu.scala:222)."""

    def __init__(self, child: PhysicalPlan, goal_rows: int = 1 << 20):
        self.children = [child]
        self.goal_rows = goal_rows

    @property
    def schema(self):
        return self.children[0].schema

    def execute(self, ctx):
        arrow = T.schema_to_arrow(self.schema)

        def run(part):
            pending: List[pa.RecordBatch] = []
            pending_rows = 0
            for hb in part:
                rb = hb.rb
                if rb.num_rows == 0:
                    continue
                pending.append(rb.cast(arrow))
                pending_rows += rb.num_rows
                if pending_rows >= self.goal_rows:
                    yield self._upload(pending, ctx)
                    pending, pending_rows = [], 0
            if pending:
                yield self._upload(pending, ctx)
        from ..utils.prefetch import prefetch_iter
        from . import pipeline
        depth = pipeline.prefetch_depth(ctx.conf)
        name = self.node_name()
        return [prefetch_iter(run(p), depth=depth, ctx=ctx, node=name)
                for p in self.children[0].execute(ctx)]

    def _upload(self, rbs: List[pa.RecordBatch],
                ctx=None) -> ColumnarBatch:
        import time as _time
        t0 = _time.perf_counter_ns()
        with span(getattr(ctx, "trace", None), "HostToDevice.upload"):
            if len(rbs) == 1:
                combined = rbs[0]
            else:
                combined = pa.Table.from_batches(rbs).combine_chunks() \
                    .to_batches()[0]
            batch = ColumnarBatch.from_arrow(combined)
        if ctx is not None:
            # uploadBytes = the Arrow buffer footprint crossing the link
            # (the transfer itself is async; opTime is host dispatch wall).
            name = self.node_name()
            ctx.metric(name, "uploadBytes", combined.nbytes)
            ctx.metric(name, "numInputRows", combined.num_rows)
            ctx.metric(name, "numOutputBatches", 1)
            ctx.metric(name, "opTime", _time.perf_counter_ns() - t0)
        return batch


class DeviceToHostExec(PhysicalPlan):
    """Download device batches to host (GpuColumnarToRowExec analog)."""

    columnar = False

    def __init__(self, child: PhysicalPlan):
        self.children = [child]

    @property
    def schema(self):
        return self.children[0].schema

    def execute(self, ctx):
        name = self.node_name()

        def emit(ctx, hb, t0):
            # The download already synced the row count — the one place
            # row metrics are free (GpuExec.NUM_OUTPUT_ROWS analog).
            import time as _time
            ctx.metric(name, "numOutputRows", hb.num_rows)
            ctx.metric(name, "numOutputBatches", 1)
            ctx.metric(name, "downloadBytes", hb.rb.nbytes)
            ctx.metric(name, "opTime", _time.perf_counter_ns() - t0)
            return hb

        def run(part):
            import time as _time
            for db in part:
                t0 = _time.perf_counter_ns()
                with span(ctx.trace, "DeviceToHost.download"):
                    hb = HostBatch.from_device(db)
                yield emit(ctx, hb, t0)

        def run_overlapped(part):
            # Pipelined streaming download: pulling the NEXT device batch
            # (which dispatches its device work) and starting its async
            # copy-to-host happen BEFORE blocking on the PREVIOUS batch's
            # bytes — transfer and compute stay concurrent (the tentpole
            # overlap; to_arrow_begin/finish split in data/batch.py).
            # opTime carries only this batch's begin+finish spans, NOT the
            # overlapped consumer/upstream time in between — overlapped
            # profiles must stay comparable to serial ones.
            import time as _time
            pending = None  # (begin ns, batch, download handle)
            for db in part:
                t0 = _time.perf_counter_ns()
                with span(ctx.trace, "DeviceToHost.download_begin"):
                    handle = db.to_arrow_begin()
                begin_ns = _time.perf_counter_ns() - t0
                if pending is not None:
                    yield self._finish_download(ctx, emit, pending)
                pending = (begin_ns, db, handle)
            if pending is not None:
                yield self._finish_download(ctx, emit, pending)

        from . import pipeline
        parts = self.children[0].execute(ctx)
        if not pipeline.parallel_active(ctx):
            return [run(p) for p in parts]
        from ..utils.prefetch import prefetch_iter
        depth = pipeline.prefetch_depth(ctx.conf)
        return [prefetch_iter(run_overlapped(p), depth=depth, ctx=ctx,
                              node=name)
                for p in parts]

    @staticmethod
    def _finish_download(ctx, emit, pending):
        import time as _time
        begin_ns, db, handle = pending
        t0 = _time.perf_counter_ns()
        with span(ctx.trace, "DeviceToHost.download"):
            hb = HostBatch(db.to_arrow_finish(handle))
        # emit() computes opTime as now - t0; shift t0 back by the begin
        # span so both download phases (and nothing else) are counted.
        return emit(ctx, hb, t0 - begin_ns)


class DeviceSourceExec(TpuExec):
    """Source over device-resident cached partitions (df.cache() analog):
    batches were pinned in HBM by ``TpuSession.materialize`` and replay with
    zero upload cost."""

    def __init__(self, partitions, schema: T.Schema):
        self.children = []
        self.partitions = partitions  # List[List[ColumnarBatch]]
        self._schema = schema

    @property
    def schema(self):
        return self._schema

    def describe(self):
        return f"DeviceSource parts={len(self.partitions)}"

    def execute(self, ctx):
        return [iter(list(p)) for p in self.partitions]


# ---------------------------------------------------------------------------
# Narrow operators
# ---------------------------------------------------------------------------


class TpuProjectExec(TpuExec):
    def __init__(self, child: PhysicalPlan, exprs: List[Expression]):
        self.children = [child]
        self.exprs = exprs

    @property
    def schema(self):
        return T.Schema([T.StructField(e.name, e.data_type, e.nullable)
                         for e in self.exprs])

    def describe(self):
        return "TpuProject [" + ", ".join(e.name for e in self.exprs) + "]"

    def execute(self, ctx):
        from ..ops import nondeterministic as ND
        bound = _bind_all(self.exprs, self.children[0].schema)
        out_schema = self.schema
        nondet = any(ND.has_nondeterministic(e) for e in bound)

        if nondet:
            # Partition id and the running row offset enter the kernel as
            # TRACED arguments so one compile serves every partition/batch
            # (the reference's GpuSparkPartitionID reads TaskContext; here
            # the exec threads the same facts through eval_context).
            def build_nd():
                def project_nd(batch: ColumnarBatch, row_base, pid
                               ) -> ColumnarBatch:
                    # Positional expressions (monotonic id, rand stream)
                    # number LOGICAL rows — scattered lazy rows must
                    # compact first to match the oracle's numbering.
                    batch = KR.physical(batch)
                    with ND.eval_context(pid, row_base):
                        cols = tuple(e.eval_device(batch) for e in bound)
                    return batch.with_columns(cols, out_schema)
                return project_nd
            project_nd = cached_kernel(
                "project_nd", kernel_key(bound, out_schema), build_nd)

            def run_nd(part, pidx):
                row_base = jnp.asarray(0, jnp.int64)
                pid = jnp.asarray(pidx, jnp.int32)
                for db in part:
                    yield project_nd(db, row_base, pid)
                    row_base = row_base + db.n_rows.astype(jnp.int64)
            return [run_nd(p, i)
                    for i, p in enumerate(self.children[0].execute(ctx))]

        def build():
            def project(batch: ColumnarBatch) -> ColumnarBatch:
                cols = tuple(e.eval_device(batch) for e in bound)
                return batch.with_columns(cols, out_schema)
            return project
        project = cached_kernel("project", kernel_key(bound, out_schema),
                                build)

        name = self.node_name()

        def run(part):
            import time as _time
            t0 = _time.perf_counter_ns()
            for db in part:
                out = project(db)
                t0 = _tick(ctx, name, t0)
                yield out
        return [run(p) for p in self.children[0].execute(ctx)]


class TpuFilterExec(TpuExec):
    def __init__(self, child: PhysicalPlan, condition: Expression):
        self.children = [child]
        self.condition = condition

    @property
    def schema(self):
        return self.children[0].schema

    def describe(self):
        return f"TpuFilter ({self.condition})"

    def execute(self, ctx):
        bound = self.condition.bind(self.children[0].schema)

        def build():
            def filt(batch: ColumnarBatch) -> ColumnarBatch:
                mask_col = bound.eval_device(batch)
                keep = mask_col.data & mask_col.validity
                return KR.compact(batch, keep)
            return filt
        filt = cached_kernel("filter", kernel_key(bound), build)

        name = self.node_name()

        def run(part):
            import time as _time
            t0 = _time.perf_counter_ns()
            for db in part:
                out = filt(db)
                t0 = _tick(ctx, name, t0)
                yield out
        return [run(p) for p in self.children[0].execute(ctx)]


class TpuRangeExec(TpuExec):
    def __init__(self, start: int, end: int, step: int,
                 batch_rows: int = 1 << 20):
        self.start, self.end, self.step = start, end, step
        self.batch_rows = batch_rows

    @property
    def schema(self):
        return T.Schema([T.StructField("id", T.LONG, False)])

    def execute(self, ctx):
        name = self.node_name()

        def gen():
            n_total = max(0, -(-(self.end - self.start) // self.step))
            done = 0
            while done < n_total:
                n = min(self.batch_rows, n_total - done)
                cap = bucket_capacity(n)
                start = self.start + done * self.step
                data = start + jnp.arange(cap, dtype=jnp.int64) * self.step
                valid = jnp.arange(cap, dtype=jnp.int32) < n
                col = DeviceColumn(data=jnp.where(valid, data, 0),
                                   validity=valid, dtype=T.LONG)
                ctx.metric(name, "numOutputRows", n)
                ctx.metric(name, "numOutputBatches", 1)
                yield ColumnarBatch((col,), jnp.asarray(n, jnp.int32),
                                    self.schema)
                done += n
        return [gen()]


class TpuUnionExec(TpuExec):
    def __init__(self, children: List[PhysicalPlan], schema: T.Schema):
        self.children = list(children)
        self._schema = schema

    @property
    def schema(self):
        return self._schema

    def execute(self, ctx):
        name = self.node_name()
        parts = []
        for c in self.children:
            def relabel(p):
                for db in p:
                    ctx.metric(name, "numOutputBatches", 1)
                    yield ColumnarBatch(db.columns, db.n_rows,
                                        self._schema, live=db.live)
            parts.extend(relabel(p) for p in c.execute(ctx))
        return parts


def _limit_stream(batches, n: int, in_fusion: bool):
    """Truncate a device-batch stream to a running limit of n rows.

    Traced (fusion) path: the running remainder is a device scalar so no
    host sync interrupts the fused program — loses the early-exit, which
    fusion (a materialized, finite batch list) does not need. Streaming
    path: one host sync per batch with early-exit, the reference's
    per-batch row slicing (limit.scala:115)."""
    if in_fusion:
        remaining = jnp.asarray(n, jnp.int32)
        for db in batches:
            db = KR.physical(db)  # truncation is positional
            take = jnp.minimum(db.n_rows, remaining)
            yield _truncate(db, take)
            remaining = remaining - take
        return
    remaining = n
    for db in batches:
        if remaining <= 0:
            return
        rows = int(db.n_rows)
        take = min(rows, remaining)
        remaining -= take
        if take == rows:
            yield db
        else:
            yield _truncate(KR.physical_jit(db), take)


class TpuLocalLimitExec(TpuExec):
    """Per-partition limit (GpuLocalLimitExec, limit.scala:115): each
    partition truncates independently, preserving the partitioning — the
    cheap first phase of a collect-limit."""

    def __init__(self, child: PhysicalPlan, n: int):
        self.children = [child]
        self.n = n

    @property
    def schema(self):
        return self.children[0].schema

    def execute(self, ctx):
        name = self.node_name()
        return [_counted_stream(ctx, name,
                                _limit_stream(p, self.n, ctx.in_fusion))
                for p in self.children[0].execute(ctx)]


class TpuLimitExec(TpuExec):
    """Global limit: one running limit over the flattened partition stream
    (GpuGlobalLimitExec, limit.scala:120)."""

    def __init__(self, child: PhysicalPlan, n: int):
        self.children = [child]
        self.n = n

    @property
    def schema(self):
        return self.children[0].schema

    def execute(self, ctx):
        def flat():
            for part in self.children[0].execute(ctx):
                yield from part
        return [_counted_stream(ctx, self.node_name(),
                                _limit_stream(flat(), self.n,
                                              ctx.in_fusion))]


@jax.jit
def _truncate(db: ColumnarBatch, take) -> ColumnarBatch:
    take = jnp.asarray(take, jnp.int32)
    live = jnp.arange(db.capacity, dtype=jnp.int32) < take
    cols = []
    for c in db.columns:
        v = c.validity & live
        if c.is_string:
            cols.append(c.replace_rows(v))
        else:
            cols.append(DeviceColumn(
                jnp.where(v, c.data, jnp.zeros((), c.data.dtype)), v, c.dtype))
    return ColumnarBatch(tuple(cols), take, db.schema)


class TpuExpandExec(TpuExec):
    def __init__(self, child: PhysicalPlan, projections, schema: T.Schema):
        self.children = [child]
        self.projections = projections
        self._schema = schema

    @property
    def schema(self):
        return self._schema

    def execute(self, ctx):
        child_schema = self.children[0].schema
        bound = [
            _bind_all(proj, child_schema) for proj in self.projections]
        out_schema = self._schema

        def make_projection(proj):
            def project(batch):
                cols = []
                for e, f in zip(proj, out_schema):
                    c = e.eval_device(batch)
                    if c.dtype.name != f.data_type.name:
                        from ..ops.cast import _jnp_cast
                        data = _jnp_cast(c.data, c.dtype, f.data_type)
                        c = make_column(data, c.validity, f.data_type)
                    cols.append(c)
                return batch.with_columns(tuple(cols), out_schema)
            return project

        fns = [cached_kernel("expand", kernel_key(p, out_schema),
                             lambda p=p: make_projection(p))
               for p in bound]
        name = self.node_name()

        def run(part):
            import time as _time
            t0 = _time.perf_counter_ns()
            for db in part:
                for fn in fns:
                    out = fn(db)
                    t0 = _tick(ctx, name, t0)
                    yield out
        return [run(p) for p in self.children[0].execute(ctx)]


class TpuGenerateExec(TpuExec):
    """Explode / posexplode over the padded-ragged array layout
    (GpuGenerateExec.scala:101 does the same with a cudf gather).

    Traced kernels: flatten the ``[rows, max_len]`` element matrix to
    ``rows * max_len`` output lanes, repeat parent rows by a single 1D
    gather (``row = lane // max_len``), then compact on the element-liveness
    mask. When ``capacity * max_len`` exceeds :attr:`TILE_LANES`, the batch
    explodes in row tiles so no single invocation allocates more than
    ``TILE_LANES`` lanes per output column (the reference chunks similarly
    through its iterator); each tile yields its own output batch."""

    #: Lane bound per explode invocation: a coalesced 1M-row batch with a
    #: 64-wide array bucket would otherwise allocate 64M lanes per output
    #: column in one program — an HBM blow-up at exactly the batch sizes
    #: coalescing produces.
    TILE_LANES = 1 << 22

    def __init__(self, child: PhysicalPlan, generator: Expression,
                 outer: bool, pos: bool, schema: T.Schema):
        self.children = [child]
        self.generator = generator
        self.outer = outer
        self.pos = pos
        self._schema = schema

    @property
    def schema(self):
        return self._schema

    def describe(self):
        return f"TpuGenerate [{self.generator}]"

    def execute(self, ctx):
        bound = self.generator.bind(self.children[0].schema)
        out_schema = self._schema
        outer, pos = self.outer, self.pos
        elem_dt = out_schema[len(out_schema) - 1].data_type

        eval_arr = cached_kernel(
            "generate_arr", kernel_key(bound, out_schema),
            lambda: lambda db: bound.eval_device(db))

        def make_explode(tile_rows: int):
            """Explode rows [offset, offset+tile_rows) of the evaluated
            array column. Row indices past the live count read clamped
            garbage that the keep mask then drops."""
            def explode(db: ColumnarBatch, arr,
                        offset: jnp.ndarray) -> ColumnarBatch:
                w = arr.data.shape[1]
                rows_sel = offset + jnp.arange(tile_rows, dtype=jnp.int32)
                data = arr.data[rows_sel]
                elem_validity = arr.elem_validity[rows_sel]
                lengths = arr.lengths[rows_sel]
                validity = arr.validity[rows_sel]
                out_cap = tile_rows * w
                lane = jnp.arange(out_cap, dtype=jnp.int32)
                local_r = lane // w
                flat_r = offset + local_r
                flat_j = lane % w
                live = flat_r < db.n_rows
                lens = lengths[local_r]
                valid = validity[local_r]
                keep_elem = live & (flat_j < lens)
                if outer:
                    extra = live & (flat_j == 0) & (~valid | (lens == 0))
                    keep = keep_elem | extra
                else:
                    keep = keep_elem
                parent = KR.gather_batch(
                    db, flat_r, jnp.asarray(out_cap, jnp.int32),
                    index_valid=None)
                cols = list(parent.columns)
                if pos:
                    cols.append(make_column(flat_j, keep_elem, T.INT))
                cols.append(make_column(
                    data.reshape(-1),
                    elem_validity.reshape(-1) & keep_elem, elem_dt))
                expanded = ColumnarBatch(
                    tuple(cols), jnp.asarray(out_cap, jnp.int32), out_schema)
                return KR.compact(expanded, keep)
            return explode

        def run(part):
            import time as _time
            from ..data.column import bucket_capacity
            t0 = _time.perf_counter_ns()
            for db in part:
                # Explode liveness is positional (flat_r < n_rows).
                db = KR.physical(db) if ctx.in_fusion \
                    else KR.physical_jit(db)
                arr = eval_arr(db)
                cap, w = arr.data.shape
                tile_rows = cap if cap * w <= self.TILE_LANES else \
                    bucket_capacity(max(self.TILE_LANES // w, 128))
                fn = cached_kernel(
                    "generate",
                    kernel_key(bound, outer, pos, out_schema, tile_rows),
                    lambda: make_explode(tile_rows))
                # When tiling, bound the loop by live rows, not bucket
                # capacity — a filtered batch in a large bucket would
                # otherwise run dead kernels past n_rows. The device sync
                # is paid only on the (large-batch) tiled path.
                live_rows = cap if tile_rows == cap else \
                    max(int(jax.device_get(db.n_rows)), 1)
                for off in range(0, live_rows, tile_rows):
                    out = fn(db, arr, jnp.asarray(off, jnp.int32))
                    t0 = _tick(ctx, self.node_name(), t0)
                    yield out
        return [run(p) for p in self.children[0].execute(ctx)]


# ---------------------------------------------------------------------------
# Sort
# ---------------------------------------------------------------------------


class TpuSortExec(TpuExec):
    """Global sort. Small inputs coalesce to a single batch and sort once
    (RequireSingleBatch, reference GpuSortExec.scala:54); inputs above the
    external threshold run the bounded-memory external merge sort
    (exec/external_sort.py): per-batch sorted runs through the spill
    catalog, pairwise chunked merges, a stream of globally ordered chunks
    out — the device never holds more than a few chunks."""

    children_coalesce_goals = ["target"]

    def __init__(self, child: PhysicalPlan, orders: List[SortOrder]):
        self.children = [child]
        self.orders = orders

    @property
    def schema(self):
        return self.children[0].schema

    def execute(self, ctx):
        schema = self.schema
        key_exprs = [o.child.bind(schema) for o in self.orders]
        asc = [o.ascending for o in self.orders]
        nf = [o.effective_nulls_first for o in self.orders]

        def build():
            def do_sort(b):
                keys = [e.eval_device(b) for e in key_exprs]
                return KR.sort_batch_by_columns(b, keys, asc, nf)
            return do_sort
        do_sort = cached_kernel(
            "sort", kernel_key(key_exprs, asc, nf), build)

        def gen():
            from ..config import SORT_EXTERNAL_THRESHOLD
            from ..memory import retry as R
            name = self.node_name()
            catalog = getattr(ctx, "catalog", None)
            if ctx.in_fusion or catalog is None:
                merged = _accumulate_spillable(self.children[0], ctx, "sort")
                if merged is None:
                    return
                ctx.metric(name, "numOutputBatches", 1)
                with ctx.registry.timer(name, "sortTime"):
                    out = do_sort(merged)
                yield out
                return
            from ..memory import spill as SP_MOD
            threshold = ctx.conf.get(SORT_EXTERNAL_THRESHOLD) or \
                catalog.device_budget // 4
            ids, total, sorter = [], 0, None
            try:
                for part in self.children[0].execute(ctx):
                    for db in part:
                        ids.append(catalog.register_batch(
                            db, SP_MOD.ACTIVE_BATCHING_PRIORITY,
                            owner=getattr(ctx, "qos", None)))
                        total += db.device_size_bytes
                if not ids:
                    return
                if total <= threshold:
                    def assemble_and_sort(id_list):
                        merged = _pinned_concat(catalog, id_list)
                        with ctx.registry.timer(name, "sortTime"):
                            return do_sort(merged)
                    # Single-batch sorts cannot split (two sorted halves
                    # are not a global sort): spill + retry only.
                    out = R.with_retry(ctx, f"{name}.sort", ids,
                                       assemble_and_sort, node=name)[0]
                    ctx.metric(name, "numOutputBatches", 1)
                    yield out
                    return
                from .external_sort import ExternalSorter
                sorter = ExternalSorter(self.orders, schema, catalog,
                                        key_exprs, ctx=ctx)
                for b in ids:
                    # The reload itself can OOM (the batch may have
                    # spilled), so acquisition runs under retry too; the
                    # sort step then splits in half by rows when it cannot
                    # fit — each half becomes its own sorted run, which
                    # the merge tree absorbs naturally.
                    batch = R.with_retry(ctx, f"{name}.runGeneration", b,
                                         catalog.acquire_batch,
                                         node=name)[0]
                    R.with_retry(ctx, f"{name}.runGeneration", batch,
                                 sorter.add_batch,
                                 split=R.halve_by_rows, node=name)
                    catalog.free(b)
                ids = []
                n_out = 0
                for chunk in sorter.sorted_chunks():
                    n_out += 1
                    yield chunk
                ctx.metric(self.node_name(), "numOutputBatches", n_out)
                ctx.metric(self.node_name(), "externalSort", 1)
            finally:
                for b in ids:
                    catalog.free(b)
                if sorter is not None:
                    # An abandoned chunk stream (limit above an external
                    # sort) must not leak the un-merged runs' registrations.
                    sorter.release()
        return [gen()]


class TpuTopKExec(TpuExec):
    """Limit-into-sort: ORDER BY ... LIMIT n keeps a running top-k batch
    instead of globally sorting the input (the reference gets the same
    shape from cudf partial sorts under GpuSortExec.scala:50 +
    GpuCollectLimitExec; planned by the CpuLimitExec rule when n is
    under spark.rapids.tpu.sort.topKThreshold).

    Streaming: each incoming batch reduces to its top-k (single-key
    keys ride one int64 lane through ``lax.top_k``, O(n log k)); the
    running best merges pairwise, so the device never holds more than
    (batch + 2k) rows for the sort tail."""

    children_coalesce_goals = ["target"]

    def __init__(self, child: PhysicalPlan, orders: List[SortOrder],
                 n: int):
        self.children = [child]
        self.orders = orders
        self.n = n

    @property
    def schema(self):
        return self.children[0].schema

    def describe(self):
        return f"TpuTopK n={self.n}"

    def execute(self, ctx):
        schema = self.schema
        key_exprs = [o.child.bind(schema) for o in self.orders]
        asc = [o.ascending for o in self.orders]
        nf = [o.effective_nulls_first for o in self.orders]

        def build(fast):
            def do_topk(b):
                keys = [e.eval_device(b) for e in key_exprs]
                top, ok = KR.topk_batch_by_columns(
                    b, keys, asc, nf, self.n, allow_data_fallback=fast)
                # literal True would jit-box into a device array; None
                # survives jit so the static-exact case stays sync-free
                return top, (None if ok is True else ok)
            return do_topk

        def gen():
            # The float64-lane fast path is optimistic for float/int64
            # keys (exactness is data-dependent); its deferred fail flag
            # rides the same session dense-mode retry as the dense
            # joins/aggs — no per-batch host syncs, fusion-safe.
            site = ctx.next_join_site()
            fast = not ctx.eager_overflow \
                and ctx.dense_modes.get(site, 0) == 0
            do_topk = cached_kernel(
                "topk", kernel_key(key_exprs, asc, nf) + (self.n, fast),
                lambda: build(fast))

            def reduce_one(b):
                top, ok = do_topk(b)
                if ok is not None:
                    fail = ~ok
                    ctx.overflow_flags.append(fail)
                    ctx.dense_fails.append((site, fail))
                return top

            best = None
            for part in self.children[0].execute(ctx):
                for db in part:
                    top = reduce_one(db)
                    best = top if best is None else \
                        reduce_one(_coalesce_device([best, top]))
            if best is not None:
                ctx.metric(self.node_name(), "numOutputBatches", 1)
                yield best
        return [gen()]


def _accumulate_spillable(child: PhysicalPlan, ctx, label: str,
                          node: Optional[str] = None
                          ) -> Optional[ColumnarBatch]:
    """Collect ALL of a child's batches into one, registering each with the
    spill catalog while accumulating so memory pressure can push earlier
    batches to host/disk (the reference makes join build sides and sort
    inputs spillable the same way, RapidsBufferStore.scala:40). Under
    whole-stage fusion tracing the catalog is bypassed (tracers cannot move
    hosts).

    The assembly (unspill + concat) runs under the OOM-retry combinator
    without a split: the consumer's contract is ONE batch, so exhausted
    retries surface SplitAndRetryOOM naming the site."""
    from ..memory import retry as R
    from ..memory import spill as SP
    catalog = getattr(ctx, "catalog", None)
    use_catalog = catalog is not None and not ctx.in_fusion
    if not use_catalog:
        batches = [b for part in child.execute(ctx) for b in part]
        return _coalesce_device(batches) if batches else None
    ids = []
    try:
        for part in child.execute(ctx):
            for db in part:
                ids.append(catalog.register_batch(
                    db, SP.ACTIVE_BATCHING_PRIORITY,
                    owner=getattr(ctx, "qos", None)))
        if not ids:
            return None

        with span(getattr(ctx, "trace", None), f"{label}.assemble"):
            out = R.with_retry(ctx, f"{node or label}.assemble", ids,
                               lambda id_list: _pinned_concat(catalog,
                                                              id_list),
                               node=node)[0]
    finally:
        # Free even when the child raises mid-stream (e.g. a transient
        # remote-compile failure that session._run_with_retries retries) —
        # leaked registrations would shrink the spill budget for the whole
        # session.
        for b in ids:
            catalog.free(b)
    return out


def _pinned_concat(catalog, ids):
    """Acquire + concat a set of catalog buffers with on-deck pinning
    (pin first so acquiring one buffer can't evict another of the same
    set); unpins in finally so a failed — and retried — attempt leaves
    them spillable for the retry's spill-down. The one assembly routine
    behind every with_retry'd concat site (coalesce flush, join build,
    single-batch sort)."""
    for b in ids:
        catalog.pin(b)
    try:
        return _coalesce_device([catalog.acquire_batch(b) for b in ids])
    finally:
        for b in ids:
            catalog.unpin(b)


_concat_jit = jax.jit(KC.concat_batches, static_argnums=(1,))


def _coalesce_device(batches: List[ColumnarBatch]) -> ColumnarBatch:
    """Concat device batches, sizing output by the (static) sum of input
    capacities. Live rows <= capacity, so the bound is safe, and unlike the
    true row total it needs no device->host sync — which keeps concat
    non-blocking and traceable under whole-stage fusion.
    The output is at most one capacity bucket larger than a row-exact concat.
    """
    if len(batches) == 1:
        # Stays lazy: mask-native consumers (agg, join, sort, filter)
        # read row_mask(); positional consumers materialize themselves.
        return batches[0]
    total = sum(b.capacity for b in batches)
    cap = bucket_capacity(max(total, 1))
    return _concat_jit(batches, cap)


# ---------------------------------------------------------------------------
# Hash aggregate
# ---------------------------------------------------------------------------


class TpuHashAggregateExec(TpuExec):
    """Partial-per-batch aggregation with a device merge loop, mirroring the
    reference's concat + re-aggregate accumulation (aggregate.scala:330-400),
    then a final buffer-evaluation projection."""

    children_coalesce_goals = ["target"]

    def __init__(self, child: PhysicalPlan, groupings: List[Expression],
                 aggregates: List[AGG.AggregateExpression]):
        self.children = [child]
        self.groupings = groupings
        self.aggregates = aggregates

    @property
    def schema(self):
        fields = [T.StructField(g.name, g.data_type, g.nullable)
                  for g in self.groupings]
        fields += [T.StructField(a.name, a.func.data_type, a.func.nullable)
                   for a in self.aggregates]
        return T.Schema(fields)

    def describe(self):
        return ("TpuHashAggregate [" + ", ".join(g.name for g in self.groupings)
                + "] [" + ", ".join(a.name for a in self.aggregates) + "]")

    # Buffer schema: groupings then per-agg buffers.
    def _buffer_schema(self) -> T.Schema:
        fields = [T.StructField(g.name, g.data_type, g.nullable)
                  for g in self.groupings]
        for i, a in enumerate(self.aggregates):
            for spec in a.func.buffers():
                fields.append(T.StructField(f"_buf{i}_{spec.suffix}",
                                            spec.dtype, True))
        return T.Schema(fields)

    def execute(self, ctx):
        child_schema = self.children[0].schema
        groupings = _bind_all(self.groupings, child_schema)
        aggs = [AGG.AggregateExpression(a.func.bind(child_schema), a.name)
                for a in self.aggregates]
        buf_schema = self._buffer_schema()
        n_keys = len(groupings)
        agg_key = kernel_key(groupings, [(a.name, a.func) for a in aggs],
                             buf_schema)

        def build_partial(dense_mode):
            def partial(batch: ColumnarBatch):
                return _aggregate_batch(batch, groupings, aggs, buf_schema,
                                        n_keys, update_mode=True,
                                        dense_mode=dense_mode)
            return partial

        def build_merge(dense_mode):
            def merge(batch: ColumnarBatch):
                key_refs = [BoundReference(i, f.data_type, f.nullable)
                            for i, f in enumerate(buf_schema)][:n_keys]
                return _aggregate_batch(batch, key_refs, aggs, buf_schema,
                                        n_keys, update_mode=False,
                                        dense_mode=dense_mode)
            return merge

        def gen():
            # Dense/hash grouping is optimistic like the dense joins:
            # a deferred fail flag (key span or collision sidecar
            # overflow) escalates this site to the sort path via the
            # session's dense-mode retry.
            site = ctx.next_join_site()
            dense_mode = 1 if ctx.eager_overflow else \
                min(ctx.dense_modes.get(site, 0), 1)
            pkey = agg_key + (dense_mode,)
            partial_k = cached_kernel(
                "agg_partial", pkey,
                lambda: build_partial(dense_mode))
            merge_k = cached_kernel(
                "agg_merge", pkey,
                lambda: build_merge(dense_mode))

            def run_k(k, b):
                out, fail = k(b)
                if fail is not None:
                    ctx.overflow_flags.append(fail)
                    ctx.dense_fails.append((site, fail))
                return out

            def key_columns(b: ColumnarBatch):
                return [e.eval_device(b) for e in groupings]

            # Which form the kernel takes is static in the keys' shapes:
            # ask of them what grouped_aggregate will, once a batch
            # structure (an abstract evaluation costs 0.8 ms of host).
            masked_forms = {}

            def takes_masked_form(b: ColumnarBatch) -> bool:
                leaves, treedef = jax.tree_util.tree_flatten(b)
                sig = (treedef, tuple(x.shape for x in leaves))
                if sig not in masked_forms:
                    masked_forms[sig] = KG.masked_slot_form(
                        jax.eval_shape(key_columns, b))
                return masked_forms[sig]

            def partial(b):
                # Inlined in a fused program the host hands over no batch.
                if groupings and not ctx.in_fusion and takes_masked_form(b):
                    ctx.metric(self.node_name(), "aggMaskedSlotBatches", 1)
                return run_k(partial_k, b)

            def merge(b):
                return run_k(merge_k, b)
            # Merge-sort-style reduction stack: merge two partials only when
            # the newer one has caught up in capacity. With capacity-sum
            # concat sizing (no row-count syncs), a linear state-accumulator
            # would re-sort the whole accumulated capacity per batch —
            # O(N^2); the tree keeps total merge work O(N log N).
            stack: List[ColumnarBatch] = []

            def push(b: ColumnarBatch):
                stack.append(b)
                while len(stack) >= 2 and \
                        stack[-1].capacity >= stack[-2].capacity:
                    b2, b1 = stack.pop(), stack.pop()
                    stack.append(merge(_coalesce_device([b1, b2])))

            for part in self.children[0].execute(ctx):
                for db in part:
                    push(partial(db))
            state: Optional[ColumnarBatch] = None
            if stack:
                state = stack.pop()
                while stack:
                    state = merge(_coalesce_device([stack.pop(), state]))
            if state is None:
                # No input batches at all — statically known, no sync.
                # Grouped agg of nothing is nothing; global agg is the
                # count-0 row. With >=1 input batch the global-agg kernel
                # itself always emits exactly one group (even for zero live
                # rows), so no row-count sync is ever needed here.
                if self.groupings:
                    return
                ctx.metric(self.node_name(), "numOutputBatches", 1)
                yield self._empty_result()
                return
            ctx.metric(self.node_name(), "numOutputBatches", 1)
            yield self._finalize(state, buf_schema)
        return [gen()]

    def _finalize(self, state: ColumnarBatch, buf_schema: T.Schema
                  ) -> ColumnarBatch:
        final = finalize_agg_kernel(len(self.groupings), self.aggregates,
                                    buf_schema, self.schema)
        return final(state)

    def _empty_result(self) -> ColumnarBatch:
        """Global aggregation of empty input: one row (count=0, rest null)."""
        arrays = []
        for a in self.aggregates:
            if isinstance(a.func, AGG.Count):
                arrays.append(pa.array([0], pa.int64()))
            else:
                arrays.append(pa.nulls(1, T.to_arrow_type(a.func.data_type)))
        rb = pa.RecordBatch.from_arrays(
            arrays, schema=T.schema_to_arrow(self.schema))
        return ColumnarBatch.from_arrow(rb)


def finalize_agg_kernel(n_keys: int, aggregates: List[AGG.AggregateExpression],
                        buf_schema: T.Schema, out_schema: T.Schema):
    """Cached buffer-evaluation projection (agg result-expression pass);
    shared by the streaming exec and the SPMD mesh path."""
    def build():
        def final(b: ColumnarBatch) -> ColumnarBatch:
            cols = list(b.columns[:n_keys])
            bi = n_keys
            for a in aggregates:
                specs = a.func.buffers()
                refs = [BoundReference(bi + j, s.dtype, True)
                        for j, s in enumerate(specs)]
                bi += len(specs)
                result_expr = a.func.evaluate(refs)
                cols.append(result_expr.eval_device(b))
            return ColumnarBatch(tuple(cols), b.n_rows, out_schema,
                                 live=b.live)
        return final
    return cached_kernel(
        "agg_final",
        kernel_key(n_keys, [(a.name, a.func) for a in aggregates],
                   buf_schema, out_schema),
        build)


def _aggregate_batch(batch: ColumnarBatch, key_exprs: List[Expression],
                     aggs: List[AGG.AggregateExpression],
                     buf_schema: T.Schema, n_keys: int,
                     update_mode: bool, dense_mode: int = 1):
    """One grouping pass. update_mode: inputs are raw rows (evaluate agg
    children, apply update ops). merge mode: inputs are buffer columns.

    Grouped path: KG.grouped_aggregate, which picks by the keys: packed
    dictionary codes as slot ids (masked reductions for few slots,
    ``segment_*`` scatters for many), a dense slot table for int-like
    keys, or one grouping sort with ``segment_*`` scatters (its doc has
    the chip's seconds). Global path: plain fused masked
    reductions, always emitting exactly one group so emptiness never needs
    a host sync."""
    capacity = batch.capacity
    live = batch.row_mask()
    keys = [e.eval_device(batch) for e in key_exprs]
    inputs = []  # (values, validity, op, spec)
    bi = n_keys
    for a in aggs:
        specs = a.func.buffers()
        for j, spec in enumerate(specs):
            if update_mode:
                if a.func.child is None:  # count(*)
                    values = jnp.ones(capacity, dtype=jnp.int64)
                    validity = jnp.ones(capacity, dtype=jnp.bool_)
                else:
                    c = a.func.child.eval_device(batch)
                    from ..ops.cast import _jnp_cast
                    values = _jnp_cast(c.data, c.dtype, spec.dtype) \
                        if c.dtype.name != spec.dtype.name else c.data
                    validity = c.validity
                op = spec.update_op
            else:
                c = batch.columns[bi + j]
                values = c.data
                validity = c.validity
                op = spec.merge_op
            inputs.append((values, validity, op, spec))
        bi += len(specs)
    triples = [(v, val, op) for v, val, op, _ in inputs]
    fail = None
    if keys:
        key_cols, results, n_groups, group_live, fail = \
            KG.grouped_aggregate(keys, live, triples,
                                 dense_mode=dense_mode)
        if fail is False:
            fail = None  # statically exact path: nothing to observe
    else:
        key_cols, results, n_groups, group_live = KG.global_aggregate(
            capacity, live, triples)
    out_cols = list(key_cols)
    for (_, _, op, spec), (result, counts) in zip(inputs, results):
        if spec.from_count:
            data = counts if op == "count" else result
            validity_out = group_live
        else:
            data = result
            validity_out = (counts > 0) & group_live
        out_cols.append(make_column(data.astype(spec.dtype.np_dtype),
                                    validity_out, spec.dtype))
    return ColumnarBatch(tuple(out_cols), n_groups, buf_schema), fail


# ---------------------------------------------------------------------------
# Joins
# ---------------------------------------------------------------------------


def hash_join_kernel(jt: str, lkeys: List[Expression],
                     rkeys: List[Expression], out_schema: T.Schema):
    """Process-cached local equi-join kernel ``(probe, build, out_cap)``.

    Shared by the streaming exec and the SPMD mesh path (exec/mesh.py):
    both are, per shard, exactly this local join. Semantics per join type:
    semi/anti return a compacted probe; left/full expand unmatched probe
    rows with nulls; full also returns the build-side hit mask for the
    caller's unmatched-build pass."""

    def kernel_impl(probe, build, out_cap, dense=0):
        pk = [e.eval_device(probe) for e in lkeys]
        bk = [e.eval_device(build) for e in rkeys]
        if dense == 1:
            # Direct-address fast path (unique int build keys; semi/anti
            # tolerate duplicates): returns a lazy probe-capacity batch +
            # a dense-fail flag the retry machinery consumes; no overflow
            # possible.
            return KJ.dense_join(jt, probe, build, pk[0], bk[0],
                                 out_schema)
        if dense == 2:
            # Swapped mode (inner only): the table builds over the
            # UNIQUE-keyed probe side — the dim.join(fact) shape.
            return KJ.dense_join_swapped(probe, build, pk[0], bk[0],
                                         out_schema)
        hits = None
        if jt != "full" and len(bk) == 1 \
                and KJ.single_key_joinable(bk[0]) \
                and KJ.single_key_joinable(pk[0]):
            # Fact-to-dimension shape: the build side sorted once, every
            # probe key ranked among it (full joins need the build hit
            # mask, which this path does not produce).
            lo, counts, build_at_rank = KJ.join_match_sorted_build(
                bk[0], pk[0], build.row_mask(), probe.row_mask())
        else:
            lo, counts, build_at_rank, hits = KJ.join_match(
                bk, pk, build.row_mask(), probe.row_mask(),
                need_build_hits=(jt == "full"))
        live_p = probe.row_mask()
        counts = jnp.where(live_p, counts, 0)
        matched = counts > 0
        if jt in ("left_semi", "left_anti"):
            keep = matched if jt == "left_semi" else (~matched & live_p)
            return KR.compact(probe, keep), hits
        exp_counts = counts
        if jt in ("left", "full"):
            exp_counts = KJ.left_outer_counts(counts, live_p)
        p_idx, b_idx, n_out, total = KJ.expand_matches_binsearch(
            lo, exp_counts, build_at_rank, out_cap)
        real = matched[p_idx]
        out_live = jnp.arange(out_cap, dtype=jnp.int32) < n_out
        pcols = KR.gather_columns(probe.columns, p_idx, out_live)
        bcols = KR.gather_columns(build.columns, b_idx, out_live & real)
        out = ColumnarBatch(tuple(pcols) + tuple(bcols), n_out, out_schema)
        return (out, hits), total

    return cached_kernel(
        "hash_join",
        kernel_key(jt, lkeys, rkeys, out_schema),
        lambda: kernel_impl, static_argnums=(2, 3))


def join_post_filter(condition: Optional[Expression], out_schema: T.Schema):
    """Cached residual-condition filter applied to join output rows."""
    if condition is None:
        return None
    cond = condition.bind(out_schema)

    def build_post():
        def post_filter(b):
            mask = cond.eval_device(b)
            return KR.compact(b, mask.data & mask.validity)
        return post_filter
    return cached_kernel("join_post_filter", kernel_key(cond), build_post)


def unmatched_build_kernel(left_schema: T.Schema, out_schema: T.Schema):
    """Cached full-outer tail: unmatched build rows null-extended on the
    left (shared by the streaming exec and the mesh path)."""
    def builder():
        def kernel(build, hits):
            live_b = build.row_mask()
            keep = live_b & ~hits if hits is not None else live_b
            compacted = KR.compact(build, keep)
            null_left = [_null_col(f.data_type, build.capacity)
                         for f in left_schema]
            cols = tuple(null_left) + compacted.columns
            return ColumnarBatch(cols, compacted.n_rows, out_schema,
                                 live=compacted.live)
        return kernel
    return cached_kernel("join_unmatched_build",
                         kernel_key(left_schema, out_schema), builder)


class TpuShuffledHashJoinExec(TpuExec):
    """Equi-join: build side fully concatenated on device, probe side
    streamed (GpuShuffledHashJoinExec/GpuHashJoin analog). Also covers the
    broadcast-join shape in single-process mode."""

    def __init__(self, left: PhysicalPlan, right: PhysicalPlan,
                 join_type: str, left_keys: List[Expression],
                 right_keys: List[Expression], schema: T.Schema,
                 condition: Optional[Expression] = None,
                 growth: float = 1.0):
        self.children = [left, right]
        self.join_type = join_type
        self.left_keys = left_keys
        self.right_keys = right_keys
        self._schema = schema
        self.condition = condition
        self.growth = growth

    @property
    def schema(self):
        return self._schema

    def describe(self):
        return f"TpuShuffledHashJoin {self.join_type}"

    def execute(self, ctx):
        left, right = self.children
        if self.join_type == "right":
            # Mirror: right outer = left outer with sides swapped.
            inner = TpuShuffledHashJoinExec(
                right, left, "left", self.right_keys, self.left_keys,
                _swap_schema(self._schema, len(left.schema)),
                self.condition, self.growth)
            parts = inner.execute(ctx)
            n_right = len(right.schema)
            out_schema = self._schema

            def reorder(p):
                for db in p:
                    cols = db.columns[n_right:] + db.columns[:n_right]
                    yield ColumnarBatch(cols, db.n_rows, out_schema,
                                        live=db.live)
            return [reorder(p) for p in parts]

        lkeys = _bind_all(self.left_keys, left.schema)
        rkeys = _bind_all(self.right_keys, right.schema)
        jt = self.join_type
        out_schema = self._schema
        kernel = hash_join_kernel(jt, lkeys, rkeys, out_schema)
        post_filter = join_post_filter(self.condition, out_schema)

        dense_eligible = KJ.dense_joinable(jt, _bind_all(
            self.right_keys, right.schema)) and self.condition is None

        def join_batch(probe, build, site, learn=True):
            # Optimistic output sizing: allocate from the learned exact
            # capacity for this join site when a previous run of this plan
            # observed it (ctx.join_caps, filled by the session's
            # overflow-learning retry), else from the probe capacity. The
            # real match count stays a deferred device-side observation the
            # session reads ONCE per query — no per-batch host syncs.
            # ``site`` is taken by the CALLER, outside the retry wrapper:
            # a retried/split attempt must not consume extra ordinals or
            # every later join's learned capacity would key-shift.
            # ``learn=False`` on split halves: a half's match total would
            # teach the session an UNDER-estimate of the full batch and
            # the cached capacity would overflow on every later run.
            mode = 1 + ctx.dense_modes.get(site, 0)
            if mode == 2 and jt != "inner":
                mode = 3  # swapped mode only exists for inner joins
            if dense_eligible and not ctx.eager_overflow and mode <= 2:
                # Direct-address path: optimistic like the capacity
                # guess — a dense-fail flag (out-of-range keys; duplicate
                # build keys for inner/left) escalates this site's mode
                # (1 = build-side table, 2 = swapped probe-side table,
                # then the general kernel).
                out, fail = kernel(probe, build, 0, mode)
                ctx.overflow_flags.append(fail)
                ctx.dense_fails.append((site, fail))
                if not ctx.in_fusion and out.capacity >= 4 * 128:
                    # Streaming mode: shrink sparse lazy outputs to their
                    # live bucket — downstream capacity-proportional ops
                    # (the group-by argsort, sorts) would otherwise pay
                    # the full probe/build capacity for a few live rows.
                    # One row-count sync per probe batch, same cadence as
                    # the reference's per-batch sizing.
                    total = int(jax.device_get(out.n_rows))
                    cap = bucket_capacity(max(total, 128))
                    if cap * 4 <= out.capacity:
                        from ..data.batch import _shrink_batch
                        out = _shrink_batch(KR.physical_jit(out), cap)
                return out, None
            if jt in ("left_semi", "left_anti"):
                out, hits = kernel(probe, build, probe.capacity)
                return ColumnarBatch(out.columns, out.n_rows, out_schema,
                                     live=out.live), hits
            out_cap = ctx.join_caps.get(site) or bucket_capacity(
                max(int(probe.capacity * self.growth * ctx.join_growth), 128))
            (out, hits), total = kernel(probe, build, out_cap)
            if ctx.eager_overflow:
                # Exact resize with a per-batch sync: for side-effecting
                # plans (writes) and the guaranteed last retry rung.
                t = int(total)
                if t > out_cap:
                    (out, hits), _ = kernel(probe, build, bucket_capacity(t))
            else:
                ctx.overflow_flags.append(total > out_cap)
                if learn:
                    ctx.join_totals.append((site, total))
            if post_filter is not None:
                out = post_filter(out)
            return out, hits

        name = self.node_name()

        def gen():
            import time as _time
            from ..memory import retry as R
            with ctx.registry.timer(name, "buildTime"):
                build = _accumulate_spillable(right, ctx, "join.build",
                                              node=name)
            hit_acc = None
            t0 = _time.perf_counter_ns()
            for part in left.execute(ctx):
                for probe in part:
                    if build is None:
                        if jt in ("left", "full"):
                            yield _null_extend_right(probe, out_schema,
                                                     len(right.schema))
                        elif jt == "left_anti":
                            yield ColumnarBatch(probe.columns, probe.n_rows,
                                                out_schema, live=probe.live)
                        continue
                    # Probe batches split in half by rows when retries
                    # alone cannot fit the gather's output allocation —
                    # each half joins against the same build table and
                    # streams out as its own batch.
                    site = ctx.next_join_site()
                    tracker = R.SplitTracker(R.halve_by_rows)
                    results = R.with_retry(
                        ctx, f"{name}.probe", probe,
                        lambda p: join_batch(p, build, site,
                                             learn=not
                                             tracker.split_happened),
                        split=tracker, node=name)
                    t0 = _tick(ctx, name, t0)
                    for out, hits in results:
                        if hit_acc is None:
                            hit_acc = hits
                        elif hits is not None:
                            hit_acc = hit_acc | hits
                        yield out
            if jt == "full" and build is not None:
                ctx.metric(name, "numOutputBatches", 1)
                yield self._unmatched_build(build, hit_acc)
        return [gen()]

    def _unmatched_build(self, build: ColumnarBatch, hit_acc) -> ColumnarBatch:
        kernel = unmatched_build_kernel(self.children[0].schema, self._schema)
        return kernel(build, hit_acc)


def _null_col(dtype: T.DataType, capacity: int) -> DeviceColumn:
    from ..data.column import null_column
    return null_column(dtype, capacity)


def _null_extend_right(probe: ColumnarBatch, schema: T.Schema,
                       n_right: int) -> ColumnarBatch:
    null_cols = tuple(_null_col(schema[len(probe.columns) + i].data_type,
                                probe.capacity)
                      for i in range(n_right))
    return ColumnarBatch(probe.columns + null_cols, probe.n_rows, schema,
                         live=probe.live)


def _swap_schema(schema: T.Schema, n_first: int) -> T.Schema:
    fields = list(schema)
    return T.Schema(fields[n_first:] + fields[:n_first])
