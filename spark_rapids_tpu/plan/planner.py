"""Logical -> CPU physical planning.

Standalone analog of Spark's query planner: every logical node plans to its
Cpu*Exec. The TPU rewrite then happens as a separate pass over the physical
plan (:mod:`.overrides`), mirroring how the reference intercepts Spark's
already-planned physical plan rather than planning itself.

Join strategy selection plays Spark's role too: equi joins with a small
(row-estimated) build side plan as broadcast hash joins, other equi joins as
shuffled hash joins, keyless joins as nested-loop/cartesian — so the rewrite
layer sees the same exec shapes the reference sees from Catalyst.
"""

from __future__ import annotations

from typing import Optional

from ..config import AUTO_BROADCAST_JOIN_ROWS, DEFAULT_CONF, TpuConf
from . import logical as L
from . import physical as P


def estimate_rows(plan: L.LogicalPlan) -> Optional[int]:
    """Row-count upper bound for join-strategy selection (the stand-in for
    Spark's logical statistics)."""
    if isinstance(plan, L.LocalRelation):
        return sum(rb.num_rows for rb in plan.batches)
    if isinstance(plan, L.CachedRelation):
        return plan.n_rows
    if isinstance(plan, L.Range):
        return max(0, -(-(plan.end - plan.start) // plan.step))
    if isinstance(plan, L.Limit):
        child = estimate_rows(plan.children[0])
        return plan.n if child is None else min(plan.n, child)
    if isinstance(plan, (L.Project, L.Filter, L.Sort, L.WindowOp,
                         L.Aggregate, L.ModelScore)):
        return estimate_rows(plan.children[0])
    if isinstance(plan, L.Union):
        ests = [estimate_rows(c) for c in plan.children]
        return None if any(e is None for e in ests) else sum(ests)
    if isinstance(plan, L.Expand):
        child = estimate_rows(plan.children[0])
        return None if child is None else child * len(plan.projections)
    return None  # scans, joins: unknown


def _plan_join(plan: L.Join, conf: TpuConf) -> P.PhysicalPlan:
    left = plan_physical(plan.children[0], conf)
    right = plan_physical(plan.children[1], conf)
    if not plan.left_keys or (plan.condition is not None
                              and plan.join_type != "inner"):
        # Keyless joins, and any non-inner join with a residual condition:
        # the condition must apply during matching (a post-filter after an
        # outer/semi join is wrong), which only the nested-loop path does.
        if plan.join_type in ("right", "full"):
            raise NotImplementedError(
                f"non-equi {plan.join_type} outer joins are not supported")
        # Pre-bind side-aware: equi keys bind against their own side (right
        # ordinals shift past the left columns), the residual binds with
        # duplicate-name detection — name-only binding against the combined
        # schema would silently send both sides of `id = id` to the left.
        lsch = plan.children[0].schema
        rsch = plan.children[1].schema
        condition = None
        if plan.condition is not None:
            condition = L.bind_join_condition(plan.condition, lsch, rsch)
        from ..ops.predicates import And, EqualTo
        for l, r in zip(plan.left_keys, plan.right_keys):
            eq = EqualTo(l.bind(lsch),
                         L.shift_bound_ordinals(r.bind(rsch), len(lsch)))
            condition = eq if condition is None else And(eq, condition)
        return P.CpuNestedLoopJoinExec(left, right, plan.join_type,
                                       condition, plan.schema)
    threshold = conf.get(AUTO_BROADCAST_JOIN_ROWS)
    build_est = estimate_rows(plan.children[1])
    cls = P.CpuJoinExec
    if threshold >= 0 and build_est is not None and build_est <= threshold:
        cls = P.CpuBroadcastHashJoinExec
    return cls(left, right, plan.join_type, plan.left_keys, plan.right_keys,
               plan.schema, plan.condition)


def plan_and_verify(plan: L.LogicalPlan,
                    conf: TpuConf = DEFAULT_CONF) -> P.PhysicalPlan:
    """Plan to the CPU physical tree and statically verify the result —
    the planner-side plan-lint hook (the session re-verifies after the
    TPU rewrite; see analysis/plan_lint.py and docs/plan-lint.md)."""
    physical = plan_physical(plan, conf)
    from ..analysis.plan_lint import verify_plan
    warns = verify_plan(physical, conf, stage="planned")
    if warns:
        # No rewritten plan exists yet to fall back from; surface the
        # warns so direct callers of this hook don't lose them (the
        # session's post-overrides pass owns the fallback decision).
        import warnings
        for w in warns:
            warnings.warn(f"plan-lint: {w}", stacklevel=2)
    return physical


def plan_physical(plan: L.LogicalPlan,
                  conf: TpuConf = DEFAULT_CONF) -> P.PhysicalPlan:
    if isinstance(plan, L.LocalRelation):
        return P.CpuLocalScanExec(plan.batches, plan.schema)
    if isinstance(plan, L.CachedRelation):
        if plan.device_parts is not None:
            from ..exec.execs import DeviceSourceExec
            return DeviceSourceExec(plan.device_parts, plan.schema)
        return P.CpuLocalScanExec(plan.host_batches, plan.schema)
    if isinstance(plan, L.Range):
        return P.CpuRangeExec(plan.start, plan.end, plan.step)
    if isinstance(plan, L.Scan):
        from ..io.files import CpuFileScanExec
        return CpuFileScanExec(plan.fmt, plan.paths, plan.schema,
                               plan.options, plan.pushed_filters,
                               plan._schema,
                               emit_file_meta=getattr(
                                   plan, "emit_file_meta", False))
    if isinstance(plan, L.Project):
        return P.CpuProjectExec(plan_physical(plan.children[0], conf),
                                plan.exprs)
    if isinstance(plan, L.Filter):
        return P.CpuFilterExec(plan_physical(plan.children[0], conf),
                               plan.condition)
    if isinstance(plan, L.Aggregate):
        return P.CpuHashAggregateExec(plan_physical(plan.children[0], conf),
                                      plan.groupings, plan.aggregates)
    if isinstance(plan, L.Join):
        return _plan_join(plan, conf)
    if isinstance(plan, L.Sort):
        return P.CpuSortExec(plan_physical(plan.children[0], conf),
                             plan.orders)
    if isinstance(plan, L.Limit):
        # CollectLimit shape (limit.scala:115 + GpuOverrides:1688-1704):
        # per-partition LocalLimit caps work early, GlobalLimit merges.
        child = plan_physical(plan.children[0], conf)
        return P.CpuLimitExec(P.CpuLocalLimitExec(child, plan.n), plan.n)
    if isinstance(plan, L.Union):
        return P.CpuUnionExec([plan_physical(c, conf) for c in plan.children],
                              plan.schema)
    if isinstance(plan, L.Repartition):
        from ..shuffle.exchange import CpuShuffleExchangeExec
        from ..shuffle.partitioners import partitioner_factory
        factory = partitioner_factory(plan.mode, plan.n_parts,
                                      keys=plan.keys, orders=plan.orders)
        return CpuShuffleExchangeExec(plan_physical(plan.children[0], conf),
                                      factory, plan.n_parts)
    if isinstance(plan, L.WriteOp):
        from ..io.writers import CpuWriteFilesExec
        return CpuWriteFilesExec(plan_physical(plan.children[0], conf),
                                 plan.fmt, plan.path, plan.options,
                                 plan.partition_by, plan.mode)
    if isinstance(plan, L.WindowOp):
        return P.CpuWindowExec(plan_physical(plan.children[0], conf),
                               plan.window_exprs, plan.schema)
    if isinstance(plan, L.Expand):
        return P.CpuExpandExec(plan_physical(plan.children[0], conf),
                               plan.projections, plan.schema)
    if isinstance(plan, L.ModelScore):
        from ..exec.ml_score import CpuModelScoreExec
        # Version resolved at PLAN time (not DataFrame construction), so
        # a retrain-then-rescore of the same DataFrame always plans the
        # CURRENT model — and the version stamp keys every downstream
        # plan-signature cache (fused programs, join-capacity learning).
        meta = plan.registry.meta(plan.model_name)
        return CpuModelScoreExec(plan_physical(plan.children[0], conf),
                                 plan.registry, plan.model_name,
                                 meta.version, plan.feature_exprs,
                                 plan.output_col, plan.schema)
    if isinstance(plan, L.Generate):
        return P.CpuGenerateExec(plan_physical(plan.children[0], conf),
                                 plan.generator, plan.outer, plan.pos,
                                 plan.schema)
    raise NotImplementedError(f"no physical plan for {type(plan).__name__}")
