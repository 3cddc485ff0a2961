"""Physical plan: base classes and the CPU (oracle / fallback) operators.

The reference rewrites Spark physical plans; CPU execution of any node is
"whatever Spark does". Standalone, we supply both sides: every logical node
plans to a Cpu*Exec here (pyarrow-based, row-correct, deliberately independent
of the device kernels), and :mod:`.overrides` replaces eligible nodes with
Tpu*Execs. Differential testing = run the same plan with overrides off/on.

Execution model: ``execute(ctx)`` returns a list of partitions, each a
generator of batches — ``HostBatch`` for CPU nodes, device ``ColumnarBatch``
for TPU nodes (``columnar`` flags which, mirroring Spark's
``supportsColumnar``)."""

from __future__ import annotations

import dataclasses
from typing import Callable, Iterator, List, Optional

import numpy as np
import pyarrow as pa
import pyarrow.compute as pc

from .. import types as T
from ..config import TpuConf
from ..data.batch import HostBatch, concat_host
from ..ops import aggregates as AGG
from ..ops.expression import Expression, host_to_array
from .logical import SortOrder


@dataclasses.dataclass
class ExecContext:
    conf: TpuConf
    #: Typed metrics registry (metrics/registry.py): per-query, leveled
    #: (spark.rapids.tpu.metrics.level), thread-safe. Built from conf by
    #: __post_init__ unless injected. The old free-form ``metrics`` dict
    #: is now a deprecation shim over it (see the ``metrics`` property).
    registry: object = None
    #: spill BufferCatalog (memory/spill.py); None in bare unit tests
    catalog: object = None
    #: end-of-query callbacks (shuffle unregister etc.); run by close()
    cleanups: list = dataclasses.field(default_factory=list)
    #: Multiplier applied to optimistic join output capacities. Joins size
    #: their output from the probe capacity WITHOUT syncing the real match
    #: count (the device->host round trip is the expensive resource); when
    #: a query's deferred overflow check trips, the session re-runs it with
    #: a larger growth (TpuSession.execute retry loop).
    join_growth: float = 1.0
    #: Deferred device-side overflow checks (bool scalars) appended by joins.
    #: Checked ONCE per query after execution — no per-batch host syncs.
    overflow_flags: list = dataclasses.field(default_factory=list)
    #: True = joins sync the exact match count per probe batch and resize
    #: exactly (one round trip per batch, can never overflow). Used for
    #: side-effecting plans (writes) and as the guaranteed last rung of the
    #: session's deferred-overflow retry ladder.
    eager_overflow: bool = False
    #: Whole-stage fusion input override: FusedInputExec index -> partitions.
    fused_inputs: Optional[list] = None
    #: True while executing under a whole-stage fusion trace: execs must not
    #: force host syncs (int(n_rows)) or touch the spill catalog.
    in_fusion: bool = False
    #: Exact join output capacities learned from a previous run of the same
    #: plan (site ordinal -> static capacity). Joins consult this before
    #: falling back to the optimistic probe-capacity guess; the session
    #: fills it from observed match totals and caches it per plan signature
    #: so steady-state queries execute exactly once.
    join_caps: dict = dataclasses.field(default_factory=dict)
    #: (site ordinal, traced total-match-count scalar) per deferred join
    #: batch — the observations join_caps learns from.
    join_totals: list = dataclasses.field(default_factory=list)
    #: Per-site dense-join mode escalation (site -> fail count): 0 = try
    #: the build-side direct-address table, 1 = try the swapped probe-side
    #: table (inner joins), 2+ = the general sort-based kernel. Learned
    #: through dense_fails exactly like join_caps.
    dense_modes: dict = dataclasses.field(default_factory=dict)
    #: (site ordinal, traced dense-ineligible flag) observations feeding
    #: no_dense, mirroring join_totals.
    dense_fails: list = dataclasses.field(default_factory=list)
    #: Deterministic fault injector (utils/fault_injection.py): None in
    #: production (injection conf unset). TpuSession passes its
    #: session-scoped injector so fault schedules survive dispatch
    #: retries; bare contexts build one from conf.
    fault_injector: object = None
    #: Task-admission semaphore of the owning session's DeviceManager
    #: (None in bare unit-test contexts). Pipeline boundary workers
    #: acquire it so concurrent device allocation stays serialized through
    #: the existing semaphore (exec/pipeline.py); the dispatching thread
    #: releases its slot while waiting on them.
    semaphore: object = None
    #: Query wall-clock budget (utils/deadline.py): None unless
    #: spark.rapids.tpu.query.deadlineSecs is set. Cooperative sites
    #: (retry loops, shuffle fetches, pipeline waits) call
    #: deadline.check() and raise QueryDeadlineExceeded once expired.
    deadline: object = None
    #: Session-scoped shuffle MapOutputTracker (shuffle/exchange.py):
    #: lineage recompute + peer blacklist state that must survive
    #: per-query context rebuilds. Lazily created for bare contexts.
    shuffle_tracker: object = None
    #: QoS identity of this query for spill victim selection
    #: (memory/spill.py QosTag): the session's tenant id
    #: (spark.rapids.tpu.tenantId) plus this query's deadline. Built by
    #: __post_init__; boundary forks SHARE it (dataclasses.replace keeps
    #: the reference), so "own buffer" in the victim order means "same
    #: query" across every worker of one execution.
    qos: object = None
    #: Per-query span tracer (metrics/trace.py), or None (the default —
    #: every span site pays one None check and records nothing). Shared
    #: by boundary forks like the registry; worker threads parent their
    #: spans through trace.fork()/SpanCtx or the trace root fallback.
    trace: object = None
    #: Per-batch live-row counts recorded by the ModelScore operators
    #: (exec/ml_score.py): traced scalars on the device path, plain ints
    #: on the CPU oracle — summed by ONE deferred device read into the
    #: QueryProfile ``engine.ml.scoreRows`` counter (metrics/profile.py),
    #: so the hot scoring path never pays a host sync.
    ml_score_rows: list = dataclasses.field(default_factory=list)
    _join_site: int = 0
    #: Base offset for next_join_site ordinals: pipeline boundary forks
    #: get disjoint deterministic namespaces so concurrent materialization
    #: cannot interleave ordinal assignment (capacity learning keys must
    #: be stable across runs of the same plan).
    _site_namespace: int = 0

    def __post_init__(self):
        if self.registry is None:
            from ..metrics.registry import MetricsRegistry
            self.registry = MetricsRegistry.for_conf(self.conf)
        if self.fault_injector is None:
            from ..utils.fault_injection import FaultInjector
            self.fault_injector = FaultInjector.maybe(self.conf)
        if self.qos is None:
            from ..config import TENANT_ID
            from ..memory.spill import QosTag
            try:
                tenant = self.conf.get(TENANT_ID) or ""
            except (AttributeError, TypeError):
                tenant = ""  # bare test doubles without a TpuConf
            self.qos = QosTag(tenant=tenant, deadline=self.deadline,
                              trace=self.trace)

    def next_join_site(self) -> int:
        """Deterministic per-execution ordinal for a join probe batch
        (execution order is deterministic, so ordinals are stable across
        runs of the same plan)."""
        s = self._join_site
        self._join_site += 1
        return self._site_namespace + s

    def fork_for_boundary(self, ordinal: int) -> "ExecContext":
        """A child context for one concurrently-materialized fusion
        boundary (exec/pipeline.py): shares the conf, registry, catalog,
        caps/modes dicts, and fault injector (all thread-safe or
        read-only during execution) but gets PRIVATE accumulator lists —
        merged back in boundary order by :meth:`absorb_boundary`, so
        their contents never depend on worker interleaving — and a
        disjoint join-site namespace keyed by the boundary ordinal, which
        is plan-determined and therefore stable across runs."""
        return dataclasses.replace(
            self, cleanups=[], overflow_flags=[], join_totals=[],
            dense_fails=[], ml_score_rows=[], _join_site=0,
            _site_namespace=(ordinal + 1) << 20)

    def absorb_boundary(self, child: "ExecContext") -> None:
        """Merge a boundary fork's accumulators back (called in boundary
        order, single-threaded, after every worker finished)."""
        self.overflow_flags.extend(child.overflow_flags)
        self.join_totals.extend(child.join_totals)
        self.dense_fails.extend(child.dense_fails)
        self.ml_score_rows.extend(child.ml_score_rows)
        self.cleanups.extend(child.cleanups)
        child.cleanups = []

    def metric(self, node: str, name: str, value):
        """Accumulate one metric observation. Thread-safe (warm-up and
        shuffle transport threads report concurrently); kind/level come
        from the taxonomy (metrics/registry.py). A no-op at metrics level
        NONE."""
        self.registry.add(node, name, value)

    @property
    def metrics(self):
        """Deprecated dict view of the registry (node -> name -> value).
        Reads keep working unchanged; direct mutation warns with
        DeprecationWarning and is removed next release — use
        :meth:`metric` or :attr:`registry`."""
        return self.registry.legacy_view()

    def add_cleanup(self, fn: Callable[[], None]):
        self.cleanups.append(fn)

    def close(self):
        """Run deferred cleanups (query end; TpuSession.execute's finally)."""
        cleanups, self.cleanups = self.cleanups, []
        for fn in reversed(cleanups):
            fn()


class PhysicalPlan:
    """Base physical operator."""

    children: List["PhysicalPlan"] = ()
    #: True when execute() yields device ColumnarBatch (Spark supportsColumnar)
    columnar = False

    @property
    def schema(self) -> T.Schema:
        raise NotImplementedError

    def execute(self, ctx: ExecContext) -> List[Iterator]:
        raise NotImplementedError

    def node_name(self) -> str:
        return type(self).__name__

    def tree_string(self, indent: int = 0) -> str:
        out = "  " * indent + self.describe() + "\n"
        for c in self.children:
            out += c.tree_string(indent + 1)
        return out

    def describe(self) -> str:
        return self.node_name()

    def with_children(self, children: List["PhysicalPlan"]) -> "PhysicalPlan":
        clone = dataclasses.replace(self) if dataclasses.is_dataclass(self) \
            else self._clone()
        clone.children = list(children)
        return clone

    def _clone(self):
        import copy
        return copy.copy(self)

    def transform_up(self, fn) -> "PhysicalPlan":
        new_children = [c.transform_up(fn) for c in self.children]
        node = self
        if list(new_children) != list(self.children):
            node = self.with_children(new_children)
        replaced = fn(node)
        return replaced if replaced is not None else node


def _arrow_schema(schema: T.Schema):
    return T.schema_to_arrow(schema)


def _empty_batch(schema: T.Schema) -> HostBatch:
    arrow = _arrow_schema(schema)
    return HostBatch(pa.RecordBatch.from_pydict(
        {f.name: pa.array([], type=f.type) for f in arrow}, schema=arrow))


def collect_partitions(plan: PhysicalPlan, ctx: ExecContext) -> pa.Table:
    """Run a host-side plan and assemble a pyarrow Table."""
    assert not plan.columnar, "root must be host-side (insert DeviceToHost)"
    batches = []
    for part in plan.execute(ctx):
        for hb in part:
            if hb.num_rows:
                batches.append(hb.rb)
    arrow = _arrow_schema(plan.schema)
    if not batches:
        return pa.Table.from_batches([], schema=arrow)
    return pa.Table.from_batches(batches).cast(arrow)


# ---------------------------------------------------------------------------
# CPU operators
# ---------------------------------------------------------------------------


class CpuLocalScanExec(PhysicalPlan):
    def __init__(self, batches: List[pa.RecordBatch], schema: T.Schema,
                 n_partitions: int = 1):
        self.batches = batches
        self._schema = schema
        self.n_partitions = max(1, min(n_partitions, max(len(batches), 1)))

    @property
    def schema(self):
        return self._schema

    def execute(self, ctx):
        parts = [[] for _ in range(self.n_partitions)]
        for i, rb in enumerate(self.batches):
            parts[i % self.n_partitions].append(rb)
        return [iter([HostBatch(rb) for rb in p]) for p in parts]


class CpuRangeExec(PhysicalPlan):
    def __init__(self, start: int, end: int, step: int, batch_rows: int = 1 << 20):
        self.start, self.end, self.step = start, end, step
        self.batch_rows = batch_rows

    @property
    def schema(self):
        return T.Schema([T.StructField("id", T.LONG, False)])

    def execute(self, ctx):
        def gen():
            vals = np.arange(self.start, self.end, self.step, dtype=np.int64)
            for i in range(0, len(vals), self.batch_rows):
                chunk = vals[i: i + self.batch_rows]
                yield HostBatch(pa.RecordBatch.from_arrays(
                    [pa.array(chunk)], names=["id"]))
        return [gen()]


class CpuProjectExec(PhysicalPlan):
    def __init__(self, child: PhysicalPlan, exprs: List[Expression]):
        self.children = [child]
        self.exprs = exprs

    @property
    def schema(self):
        return T.Schema([T.StructField(e.name, e.data_type, e.nullable)
                         for e in self.exprs])

    def describe(self):
        return "CpuProject [" + ", ".join(e.name for e in self.exprs) + "]"

    def execute(self, ctx):
        arrow = _arrow_schema(self.schema)
        from ..ops import nondeterministic as ND
        nondet = any(ND.has_nondeterministic(e) for e in self.exprs)

        def run(part, pidx):
            row_base = 0
            for hb in part:
                with ND.eval_context(pidx, row_base):
                    arrays = [
                        host_to_array(e.eval_host(hb),
                                      hb.num_rows).cast(f.type)
                        for e, f in zip(self.exprs, arrow)]
                row_base += hb.num_rows
                yield HostBatch(pa.RecordBatch.from_arrays(arrays,
                                                           schema=arrow))

        def run_plain(part):
            for hb in part:
                arrays = [
                    host_to_array(e.eval_host(hb), hb.num_rows).cast(f.type)
                    for e, f in zip(self.exprs, arrow)]
                yield HostBatch(pa.RecordBatch.from_arrays(arrays, schema=arrow))
        parts = self.children[0].execute(ctx)
        if nondet:
            return [run(p, i) for i, p in enumerate(parts)]
        return [run_plain(p) for p in parts]


class CpuFilterExec(PhysicalPlan):
    def __init__(self, child: PhysicalPlan, condition: Expression):
        self.children = [child]
        self.condition = condition

    @property
    def schema(self):
        return self.children[0].schema

    def describe(self):
        return f"CpuFilter ({self.condition})"

    def execute(self, ctx):
        def run(part):
            for hb in part:
                mask = host_to_array(self.condition.eval_host(hb), hb.num_rows)
                mask = pc.fill_null(mask, False)
                yield HostBatch(hb.rb.filter(mask))
        return [run(p) for p in self.children[0].execute(ctx)]


class CpuHashAggregateExec(PhysicalPlan):
    """Complete-mode aggregation via pyarrow group_by (the oracle)."""

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
        return ("CpuHashAggregate [" + ", ".join(g.name for g in self.groupings)
                + "] [" + ", ".join(a.name for a in self.aggregates) + "]")

    def execute(self, ctx):
        # Materialize all input (oracle path; perf is not the point here).
        rows = []
        child = self.children[0]
        for part in child.execute(ctx):
            for hb in part:
                cols, names = [], []
                for i, g in enumerate(self.groupings):
                    cols.append(host_to_array(g.eval_host(hb), hb.num_rows))
                    names.append(f"_g{i}")
                for i, a in enumerate(self.aggregates):
                    fn = a.func
                    if fn.child is None:
                        cols.append(pa.array([1] * hb.num_rows, pa.int64()))
                    else:
                        cols.append(host_to_array(fn.child.eval_host(hb),
                                                  hb.num_rows))
                    names.append(f"_a{i}")
                for i, a in enumerate(self.aggregates):
                    # Spark float min/max semantics need a NaN-presence
                    # indicator per group (NaN orders GREATEST: max is NaN
                    # when any contribution is, min only when all are) —
                    # pyarrow's min_max silently skips NaN.
                    if self._nan_minmax(a):
                        gi = len(self.groupings) + i
                        cols.append(pc.is_nan(cols[gi]))
                        names.append(f"_n{i}")
                        # Non-NaN valid presence: distinguishes an all-NaN
                        # group (Spark min = NaN) from one where pyarrow's
                        # NaN-skipping min found a real value. Needed
                        # because pyarrow's empty-after-skip identity is
                        # version-dependent (null in older builds, +/-inf
                        # in pyarrow >= 22).
                        cols.append(pc.fill_null(
                            pc.invert(pc.is_nan(cols[gi])), False))
                        names.append(f"_f{i}")
                if hb.num_rows:
                    rows.append(pa.RecordBatch.from_arrays(cols, names=names))

        out_arrow = _arrow_schema(self.schema)
        if not rows:
            if self.groupings:
                return [iter([_empty_batch(self.schema)])]
            # Global aggregation over empty input still yields one row.
            vals = []
            for a in self.aggregates:
                if isinstance(a.func, AGG.Count):
                    vals.append(pa.array([0], pa.int64()))
                else:
                    vals.append(pa.nulls(1, T.to_arrow_type(a.func.data_type)))
            rb = pa.RecordBatch.from_arrays(vals, schema=out_arrow)
            return [iter([HostBatch(rb)])]

        table = pa.Table.from_batches(rows)
        keys = [f"_g{i}" for i in range(len(self.groupings))]
        aggs = []
        for i, a in enumerate(self.aggregates):
            pa_agg = a.func.pa_agg
            if isinstance(a.func, AGG.Count) and a.func.child is None:
                pa_agg = "sum"  # count(*) over the synthesized ones column
            aggs.append((f"_a{i}", pa_agg))
        n_base = len(aggs)
        for i, a in enumerate(self.aggregates):
            if self._nan_minmax(a):
                aggs.append((f"_n{i}", "max"))
                aggs.append((f"_f{i}", "max"))
        if not aggs:
            aggs = [(keys[0], "count")] if keys else []
        grouped = table.group_by(keys, use_threads=False).aggregate(aggs)
        arrays = []
        for i, g in enumerate(self.groupings):
            arrays.append(grouped.column(f"_g{i}").combine_chunks()
                          .cast(T.to_arrow_type(g.data_type)))
        for i, a in enumerate(self.aggregates):
            pa_agg = aggs[i][1] if i < n_base else a.func.pa_agg
            cname = f"_a{i}_{pa_agg}"
            arr = grouped.column(cname).combine_chunks()
            if isinstance(a.func, AGG.Count) and a.func.child is None:
                arr = pc.fill_null(arr, 0)
            if self._nan_minmax(a):
                has_nan = pc.fill_null(
                    grouped.column(f"_n{i}_max").combine_chunks(), False)
                nan = pa.scalar(float("nan"), arr.type)
                if isinstance(a.func, AGG.Max):
                    # Any NaN contribution: the max IS NaN.
                    arr = pc.if_else(has_nan, nan, arr)
                else:
                    # All-NaN group: pyarrow skipped every value (yielding
                    # its empty identity — null, or +/-inf on pyarrow>=22);
                    # Spark's answer is NaN. A group with any non-NaN value
                    # keeps pyarrow's NaN-skipping min, which IS Spark's
                    # (NaN orders greatest).
                    has_real = pc.fill_null(
                        grouped.column(f"_f{i}_max").combine_chunks(),
                        False)
                    arr = pc.if_else(
                        pc.and_(pc.invert(has_real), has_nan), nan, arr)
            arrays.append(arr.cast(T.to_arrow_type(a.func.data_type)))
        rb_out = pa.RecordBatch.from_arrays(arrays, schema=out_arrow)
        return [iter([HostBatch(rb_out)])]

    @staticmethod
    def _nan_minmax(a) -> bool:
        fn = a.func
        return isinstance(fn, (AGG.Min, AGG.Max)) and fn.child is not None \
            and fn.data_type.is_floating


class CpuJoinExec(PhysicalPlan):
    def __init__(self, left: PhysicalPlan, right: PhysicalPlan,
                 join_type: str, left_keys: List[Expression],
                 right_keys: List[Expression], schema: T.Schema,
                 condition=None):
        self.children = [left, right]
        self.join_type = join_type
        self.left_keys = left_keys
        self.right_keys = right_keys
        self._schema = schema
        self.condition = condition  # residual non-equi predicate (inner only)

    @property
    def schema(self):
        return self._schema

    def describe(self):
        return f"CpuJoin {self.join_type}"

    def _materialize(self, plan, ctx, keys, prefix) -> pa.Table:
        """Collect a side as a Table with collision-proof prefixed names and
        evaluated key columns appended."""
        arrow = pa.schema(
            [pa.field(f"{prefix}c{i}", T.to_arrow_type(f.data_type))
             for i, f in enumerate(plan.schema)] +
            [pa.field(f"{prefix}k{i}", T.to_arrow_type(k.data_type))
             for i, k in enumerate(keys)])
        batches = []
        for part in plan.execute(ctx):
            for hb in part:
                cols = list(hb.rb.columns) + [
                    host_to_array(k.eval_host(hb), hb.num_rows) for k in keys]
                batches.append(pa.RecordBatch.from_arrays(
                    [c.cast(f.type) for c, f in zip(cols, arrow)],
                    schema=arrow))
        return pa.Table.from_batches(batches, schema=arrow)

    def execute(self, ctx):
        left, right = self.children
        lt = self._materialize(left, ctx, self.left_keys, "__l")
        rt = self._materialize(right, ctx, self.right_keys, "__r")
        out_arrow = _arrow_schema(self.schema)
        lk = [f"__lk{i}" for i in range(len(self.left_keys))]
        rk = [f"__rk{i}" for i in range(len(self.right_keys))]
        pa_type = {"inner": "inner", "left": "left outer",
                   "right": "right outer", "full": "full outer",
                   "left_semi": "left semi", "left_anti": "left anti"}[
            self.join_type]
        joined = lt.join(rt, keys=lk, right_keys=rk, join_type=pa_type,
                         coalesce_keys=False, use_threads=False)
        raw_names = [f"__lc{i}" for i in range(len(left.schema))]
        if self.join_type not in ("left_semi", "left_anti"):
            raw_names += [f"__rc{i}" for i in range(len(right.schema))]
        arrays = [joined.column(rn).combine_chunks().cast(f.type)
                  for rn, f in zip(raw_names, out_arrow)]
        rb = pa.RecordBatch.from_arrays(arrays, schema=out_arrow)
        hb = HostBatch(rb)
        if self.condition is not None:
            mask = host_to_array(self.condition.eval_host(hb), hb.num_rows)
            hb = HostBatch(rb.filter(pc.fill_null(mask, False)))
        return [iter([hb])]


class CpuSortExec(PhysicalPlan):
    def __init__(self, child: PhysicalPlan, orders: List[SortOrder]):
        self.children = [child]
        self.orders = orders

    @property
    def schema(self):
        return self.children[0].schema

    def execute(self, ctx):
        child = self.children[0]
        batches = []
        for part in child.execute(ctx):
            for hb in part:
                cols = [host_to_array(o.child.eval_host(hb), hb.num_rows)
                        for o in self.orders]
                extra, enames = [], []
                for i, (c, o) in enumerate(zip(cols, self.orders)):
                    extra.append(c)
                    enames.append(f"_s{i}")
                    if pa.types.is_floating(c.type):
                        # Spark: NaN is GREATEST (first in desc, last in
                        # asc) — pyarrow always sorts NaN last, so carry a
                        # bucket column: null placement rides it too.
                        nan_b = 1 if o.ascending else -1
                        null_b = -2 if o.effective_nulls_first else 2
                        isn = pc.if_else(pc.is_nan(c), pa.scalar(nan_b,
                                                                 pa.int8()),
                                         pa.scalar(0, pa.int8()))
                        bucket = pc.if_else(
                            pc.is_null(c, nan_is_null=False),
                            pa.scalar(null_b, pa.int8()), isn)
                        extra.append(bucket)
                        enames.append(f"_b{i}")
                names = list(hb.rb.schema.names) + enames
                batches.append(pa.RecordBatch.from_arrays(
                    list(hb.rb.columns) + extra, names=names))
        if not batches:
            return [iter([_empty_batch(self.schema)])]
        table = pa.Table.from_batches(batches)
        # pyarrow sort_by has one global null_placement; emulate per-key
        # placement (and per-key NaN buckets) via successive stable sorts
        # (last key first; within a key, value first then bucket).
        current = table
        for i in reversed(range(len(self.orders))):
            o = self.orders[i]
            order = "ascending" if o.ascending else "descending"
            placement = "at_start" if o.effective_nulls_first else "at_end"
            idx = pc.sort_indices(
                current, sort_keys=[(f"_s{i}", order)],
                null_placement=placement)
            current = current.take(idx)
            if f"_b{i}" in current.column_names:
                idx = pc.sort_indices(
                    current, sort_keys=[(f"_b{i}", "ascending")],
                    null_placement="at_end")
                current = current.take(idx)
        out_arrow = _arrow_schema(self.schema)
        arrays = [current.column(f.name).combine_chunks().cast(f.type)
                  for f in out_arrow]
        rb = pa.RecordBatch.from_arrays(arrays, schema=out_arrow)
        return [iter([HostBatch(rb)])]


def _limit_host_stream(batches, n: int):
    remaining = n
    for hb in batches:
        if remaining <= 0:
            return
        take = min(remaining, hb.num_rows)
        remaining -= take
        yield hb if take == hb.num_rows else HostBatch(hb.rb.slice(0, take))


class CpuLocalLimitExec(PhysicalPlan):
    """Per-partition limit (GpuLocalLimitExec, limit.scala:115): caps each
    partition at n WITHOUT cross-partition coordination, so upstream work
    short-circuits before the global merge."""

    def __init__(self, child: PhysicalPlan, n: int):
        self.children = [child]
        self.n = n

    @property
    def schema(self):
        return self.children[0].schema

    def describe(self):
        return f"CpuLocalLimit {self.n}"

    def execute(self, ctx):
        return [_limit_host_stream(p, self.n)
                for p in self.children[0].execute(ctx)]


class CpuLimitExec(PhysicalPlan):
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
        return [_limit_host_stream(flat(), self.n)]


class CpuUnionExec(PhysicalPlan):
    def __init__(self, children: List[PhysicalPlan], schema: T.Schema):
        self.children = list(children)
        self._schema = schema

    @property
    def schema(self):
        return self._schema

    def execute(self, ctx):
        arrow = _arrow_schema(self.schema)
        parts = []
        for c in self.children:
            def run(p, arrow=arrow):
                for hb in p:
                    arrays = [c.cast(f.type)
                              for c, f in zip(hb.rb.columns, arrow)]
                    yield HostBatch(pa.RecordBatch.from_arrays(
                        arrays, schema=arrow))
            parts.extend(run(p) for p in c.execute(ctx))
        return parts


class CpuExpandExec(PhysicalPlan):
    def __init__(self, child: PhysicalPlan, projections, schema: T.Schema):
        self.children = [child]
        self.projections = projections
        self._schema = schema

    @property
    def schema(self):
        return self._schema

    def execute(self, ctx):
        arrow = _arrow_schema(self.schema)

        def run(part):
            for hb in part:
                for proj in self.projections:
                    arrays = []
                    for e, f in zip(proj, arrow):
                        arr = host_to_array(e.eval_host(hb), hb.num_rows)
                        arrays.append(arr.cast(f.type))
                    yield HostBatch(pa.RecordBatch.from_arrays(
                        arrays, schema=arrow))
        return [run(p) for p in self.children[0].execute(ctx)]


class CpuGenerateExec(PhysicalPlan):
    """Explode oracle: per-row Python over the array column (the trusted
    side of the Generate differential tests; GpuGenerateExec.scala:101)."""

    def __init__(self, child: PhysicalPlan, generator, outer: bool,
                 pos: bool, schema: T.Schema):
        self.children = [child]
        self.generator = generator
        self.outer = outer
        self.pos = pos
        self._schema = schema

    @property
    def schema(self):
        return self._schema

    def describe(self):
        return f"CpuGenerate [{self.generator}]"

    def execute(self, ctx):
        import pyarrow.compute as pc
        arrow = _arrow_schema(self.schema)
        elem_type = arrow.field(len(arrow) - 1).type

        def run(part):
            for hb in part:
                gen = host_to_array(self.generator.eval_host(hb),
                                    hb.num_rows)
                idx, poss, elems = [], [], []
                for i, lst in enumerate(gen.to_pylist()):
                    if not lst:
                        if self.outer:
                            idx.append(i)
                            poss.append(None)
                            elems.append(None)
                    else:
                        for j, v in enumerate(lst):
                            idx.append(i)
                            poss.append(j)
                            elems.append(v)
                take = pa.array(idx, pa.int64())
                arrays = [pc.take(c, take) for c in hb.rb.columns]
                if self.pos:
                    arrays.append(pa.array(poss, pa.int32()))
                arrays.append(pa.array(elems, type=elem_type))
                arrays = [a.cast(f.type) for a, f in zip(arrays, arrow)]
                yield HostBatch(pa.RecordBatch.from_arrays(
                    arrays, schema=arrow))
        return [run(p) for p in self.children[0].execute(ctx)]


class CpuWindowExec(PhysicalPlan):
    """Window oracle: comparator-sorted partitions, per-row frame scans.

    Deliberately naive (O(rows * frame) Python) and fully independent of the
    device kernels — the differential harness's trusted side, playing the
    role CPU Spark's WindowExec plays for the reference's window suites
    (WindowFunctionSuite, window_function_test.py)."""

    def __init__(self, child: PhysicalPlan, window_exprs, schema: T.Schema):
        self.children = [child]
        self.window_exprs = window_exprs  # List[Tuple[name, WindowExpression]]
        self._schema = schema

    @property
    def schema(self):
        return self._schema

    def describe(self):
        return "CpuWindow [" + ", ".join(n for n, _ in self.window_exprs) + "]"

    def execute(self, ctx):
        arrow = _arrow_schema(self.schema)

        def run(parts):
            # Collect ALL child partitions: window partitions must not be
            # split across physical partitions (same contract as TpuWindowExec).
            batches = [hb for part in parts for hb in part]
            if not batches:
                return
            hb = concat_host(batches)
            n = hb.num_rows
            new_arrays = [self._eval(hb, we) for _, we in self.window_exprs]
            arrays = list(hb.rb.columns) + new_arrays
            arrays = [a.cast(f.type) for a, f in zip(arrays, arrow)]
            yield HostBatch(pa.RecordBatch.from_arrays(arrays, schema=arrow))
        return [run(self.children[0].execute(ctx))]

    def _eval(self, hb: HostBatch, we) -> pa.Array:
        import functools
        import math

        from ..ops import windows as W

        n = hb.num_rows
        spec = we.spec
        part_vals = [host_to_array(e.eval_host(hb), n).to_pylist()
                     for e in spec.partition_by]
        order_meta = [(host_to_array(o.child.eval_host(hb), n).to_pylist(),
                       o.ascending, o.effective_nulls_first)
                      for o in spec.order_by]
        child = we.func.children[0] if we.func.children else None
        vals = host_to_array(child.eval_host(hb), n).to_pylist() \
            if child is not None else None

        def cmp_scalar(a, b):
            # NaN sorts greatest (Spark semantics)
            a_nan = isinstance(a, float) and math.isnan(a)
            b_nan = isinstance(b, float) and math.isnan(b)
            if a_nan and b_nan:
                return 0
            if a_nan:
                return 1
            if b_nan:
                return -1
            if a == b:
                return 0
            return -1 if a < b else 1

        def cmp_rows(i, j):
            for pv in part_vals:
                a, b = pv[i], pv[j]
                if (a is None) != (b is None):
                    return -1 if a is None else 1
                if a is not None:
                    c = cmp_scalar(a, b)
                    if c:
                        return c
            for ov, asc, nf in order_meta:
                a, b = ov[i], ov[j]
                if (a is None) != (b is None):
                    null_cmp = -1 if nf else 1
                    return null_cmp if a is None else -null_cmp
                if a is not None:
                    c = cmp_scalar(a, b)
                    if c:
                        return c if asc else -c
            return 0

        idx = sorted(range(n), key=functools.cmp_to_key(cmp_rows))

        frame = spec.effective_frame()
        out = [None] * n
        s = 0
        while s < n:
            e = s + 1
            while e < n and cmp_part(idx[s], idx[e], part_vals) == 0:
                e += 1
            self._eval_segment(idx, s, e, order_meta, frame, we, vals, out)
            s = e
        return pa.array(out, type=T.to_arrow_type(we.data_type))

    def _eval_segment(self, idx, s, e, order_meta, frame, we, vals, out):
        import math

        from ..ops import aggregates as AGG
        from ..ops import windows as W

        def order_tuple(p):
            # Canonicalize NaN so peer equality matches Spark (NaN == NaN).
            return tuple(
                ("NaN",) if isinstance(ov[idx[p]], float)
                and math.isnan(ov[idx[p]]) else ov[idx[p]]
                for ov, _, _ in order_meta)

        def peers(p):
            lo = p
            while lo > s and order_tuple(lo - 1) == order_tuple(p):
                lo -= 1
            hi = p + 1
            while hi < e and order_tuple(hi) == order_tuple(p):
                hi += 1
            return lo, hi

        peer_group_no = []
        g = 0
        for p in range(s, e):
            if p > s and order_tuple(p) != order_tuple(p - 1):
                g += 1
            peer_group_no.append(g)

        for p in range(s, e):
            i = idx[p]
            f = we.func
            if isinstance(f, W.RowNumber):
                out[i] = p - s + 1
                continue
            if isinstance(f, W.Rank):
                out[i] = peers(p)[0] - s + 1
                continue
            if isinstance(f, W.DenseRank):
                out[i] = peer_group_no[p - s] + 1
                continue
            lo, hi = self._frame(p, s, e, frame, order_meta, idx, peers)
            rows = [idx[q] for q in range(lo, hi)]
            if isinstance(f, AGG.Count):
                if vals is None:
                    out[i] = len(rows)
                else:
                    out[i] = sum(1 for r in rows if vals[r] is not None)
                continue
            fv = [vals[r] for r in rows if vals[r] is not None]
            if not fv:
                out[i] = None
            elif isinstance(f, AGG.Sum):
                total = sum(fv)
                out[i] = float(total) if f.data_type is T.DOUBLE else int(total)
            elif isinstance(f, AGG.Average):
                out[i] = float(sum(fv)) / len(fv)
            elif isinstance(f, AGG.Min):
                # NaN ranks greatest (Spark float total order).
                out[i] = min(fv, key=_nan_great_key)
            elif isinstance(f, AGG.Max):
                out[i] = max(fv, key=_nan_great_key)
            else:
                raise NotImplementedError(type(f).__name__)

    def _frame(self, p, s, e, frame, order_meta, idx, peers):
        if frame.frame_type == "rows":
            lo = s if frame.lower.kind == "unbounded" else \
                max(s, min(e, p + (frame.lower.offset
                                   if frame.lower.kind == "offset" else 0)))
            hi = e if frame.upper.kind == "unbounded" else \
                max(s, min(e, p + (frame.upper.offset
                                   if frame.upper.kind == "offset" else 0) + 1))
            return lo, max(hi, lo)
        # RANGE
        need_peers = frame.lower.kind == "current" or \
            frame.upper.kind == "current"
        plo, phi = peers(p) if need_peers else (None, None)
        lo = s if frame.lower.kind == "unbounded" else plo
        hi = e if frame.upper.kind == "unbounded" else phi
        if frame.lower.kind == "offset" or frame.upper.kind == "offset":
            ov, asc, _ = order_meta[0]
            v = ov[idx[p]]
            if v is None:
                lo, hi = peers(p)
            else:
                def in_frame(q):
                    vt = ov[idx[q]]
                    if vt is None:
                        return False
                    if asc:
                        lo_v = None if frame.lower.kind == "unbounded" else \
                            (v if frame.lower.kind == "current"
                             else v + frame.lower.offset)
                        hi_v = None if frame.upper.kind == "unbounded" else \
                            (v if frame.upper.kind == "current"
                             else v + frame.upper.offset)
                        if lo_v is not None and vt < lo_v:
                            return False
                        if hi_v is not None and vt > hi_v:
                            return False
                        return True
                    lo_v = None if frame.upper.kind == "unbounded" else \
                        (v if frame.upper.kind == "current"
                         else v - frame.upper.offset)
                    hi_v = None if frame.lower.kind == "unbounded" else \
                        (v if frame.lower.kind == "current"
                         else v - frame.lower.offset)
                    if lo_v is not None and vt < lo_v:
                        return False
                    if hi_v is not None and vt > hi_v:
                        return False
                    return True
                members = [q for q in range(s, e) if in_frame(q)]
                if not members:
                    # empty frame
                    return s, s
                lo, hi = members[0], members[-1] + 1
        return lo, max(hi, lo)


def _nan_great_key(v):
    import math
    return (1, 0.0) if isinstance(v, float) and math.isnan(v) else (0, v)


def cmp_part(i, j, part_vals):
    import math
    for pv in part_vals:
        a, b = pv[i], pv[j]
        if (a is None) != (b is None):
            return -1 if a is None else 1
        if a is None:
            continue
        a_nan = isinstance(a, float) and math.isnan(a)
        b_nan = isinstance(b, float) and math.isnan(b)
        if a_nan and b_nan:
            continue
        if a_nan or b_nan:
            return 1 if a_nan else -1
        if a != b:
            return -1 if a < b else 1
    return 0


class CpuBroadcastHashJoinExec(CpuJoinExec):
    """Equi-join planned with a broadcast (small) build side — the CPU
    compute is identical to CpuJoinExec; the distinct node lets the TPU
    rewrite insert a broadcast exchange (BroadcastHashJoinExec analog)."""

    def describe(self):
        return f"CpuBroadcastHashJoin {self.join_type}"


class CpuNestedLoopJoinExec(PhysicalPlan):
    """Cross / conditional join oracle: expand the full pair grid with
    pyarrow takes, evaluate the condition once, filter
    (BroadcastNestedLoopJoinExec / CartesianProductExec analog)."""

    def __init__(self, left: PhysicalPlan, right: PhysicalPlan,
                 join_type: str, condition, schema: T.Schema):
        self.children = [left, right]
        self.join_type = join_type
        self.condition = condition
        self._schema = schema

    @property
    def schema(self):
        return self._schema

    def describe(self):
        return f"CpuNestedLoopJoin {self.join_type}"

    def _collect(self, plan, ctx) -> pa.Table:
        batches = []
        arrow = _arrow_schema(plan.schema)
        for part in plan.execute(ctx):
            for hb in part:
                batches.append(hb.rb.cast(arrow))
        return pa.Table.from_batches(batches, schema=arrow).combine_chunks()

    def execute(self, ctx):
        import numpy as np
        left, right = self.children
        lt = self._collect(left, ctx)
        rt = self._collect(right, ctx)
        out_arrow = _arrow_schema(self.schema)
        ln, rn = lt.num_rows, rt.num_rows
        jt = self.join_type

        p_idx = np.repeat(np.arange(ln, dtype=np.int64), max(rn, 1)) \
            if rn else np.zeros(0, np.int64)
        b_idx = np.tile(np.arange(rn, dtype=np.int64), ln) if rn else \
            np.zeros(0, np.int64)
        if self.condition is not None and len(p_idx):
            pair_arrays = [lt.column(i).take(pa.array(p_idx))
                           for i in range(lt.num_columns)]
            pair_arrays += [rt.column(i).take(pa.array(b_idx))
                            for i in range(rt.num_columns)]
            pair_schema = pa.schema(
                [pa.field(f.name, T.to_arrow_type(f.data_type))
                 for f in left.schema] +
                [pa.field(f.name, T.to_arrow_type(f.data_type))
                 for f in right.schema])
            pair_rb = pa.RecordBatch.from_arrays(
                [a.combine_chunks() for a in pair_arrays], schema=pair_schema)
            mask = host_to_array(self.condition.eval_host(HostBatch(pair_rb)),
                                 pair_rb.num_rows)
            mask = pc.fill_null(mask, False).to_numpy(zero_copy_only=False)
        else:
            mask = np.ones(len(p_idx), dtype=bool)

        if jt in ("left_semi", "left_anti", "left"):
            matched = np.zeros(ln, dtype=bool)
            if len(p_idx):
                np.logical_or.at(matched, p_idx, mask)
        if jt in ("left_semi", "left_anti"):
            keep = matched if jt == "left_semi" else ~matched
            rb = lt.filter(pa.array(keep)).combine_chunks()
            out = pa.RecordBatch.from_arrays(
                [rb.column(i).combine_chunks().cast(f.type)
                 for i, f in enumerate(out_arrow)], schema=out_arrow)
            return [iter([HostBatch(out)])]

        def chunkless(a):
            return a.combine_chunks() if isinstance(a, pa.ChunkedArray) else a

        sel = np.nonzero(mask)[0]
        arrays = [chunkless(lt.column(i).take(pa.array(p_idx[sel])))
                  for i in range(lt.num_columns)]
        arrays += [chunkless(rt.column(i).take(pa.array(b_idx[sel])))
                   for i in range(rt.num_columns)]
        if jt == "left":
            # Unmatched probe rows pad the right side with nulls.
            un = np.nonzero(~matched)[0]
            if len(un):
                tails = [chunkless(lt.column(i).take(pa.array(un)))
                         for i in range(lt.num_columns)]
                tails += [pa.nulls(len(un), out_arrow.field(
                    lt.num_columns + i).type) for i in range(rt.num_columns)]
                arrays = [pa.concat_arrays([a.cast(f.type), t.cast(f.type)])
                          for a, t, f in zip(arrays, tails, out_arrow)]
        arrays = [a.cast(f.type) for a, f in zip(arrays, out_arrow)]
        rb = pa.RecordBatch.from_arrays(arrays, schema=out_arrow)
        return [iter([HostBatch(rb)])]
