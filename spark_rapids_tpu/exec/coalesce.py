"""Batch coalescing — the ``GpuCoalesceBatches`` analog.

The reference concatenates small batches toward a size goal before ops that
want large inputs, with a goal algebra deciding where the planner must insert
coalesce nodes (CoalesceGoal:91-113, exec GpuCoalesceBatches.scala:502,
insertion GpuTransitionOverrides.scala:103). Same architecture here; the
device concat is the traced scatter kernel (ops/kernels/concat.py), and
accumulated batches are registered with the spill catalog so memory pressure
can push them to host/disk while they wait (the reference makes its
coalesce inputs spillable the same way)."""

from __future__ import annotations

from typing import List, Optional

from .. import types as T
from ..data.batch import ColumnarBatch
from ..memory import spill as SP
from ..plan.physical import PhysicalPlan
from .execs import TpuExec, _coalesce_device


class CoalesceGoal:
    def satisfied_by(self, other: "CoalesceGoal") -> bool:
        """True when batches produced under ``other`` also meet this goal."""
        raise NotImplementedError


class TargetSize(CoalesceGoal):
    def __init__(self, rows: int):
        self.rows = rows

    def satisfied_by(self, other):
        if isinstance(other, RequireSingleBatch):
            return True
        return isinstance(other, TargetSize) and other.rows >= self.rows

    def __repr__(self):
        return f"TargetSize({self.rows})"


class RequireSingleBatch(CoalesceGoal):
    def satisfied_by(self, other):
        return isinstance(other, RequireSingleBatch)

    def __repr__(self):
        return "RequireSingleBatch"


class TpuCoalesceBatchesExec(TpuExec):
    def __init__(self, child: PhysicalPlan, goal: CoalesceGoal):
        self.children = [child]
        self.goal = goal

    @property
    def schema(self):
        return self.children[0].schema

    def describe(self):
        return f"TpuCoalesceBatches ({self.goal!r})"

    def execute(self, ctx):
        from ..memory import retry as R
        catalog: Optional[SP.BufferCatalog] = getattr(ctx, "catalog", None)
        single = isinstance(self.goal, RequireSingleBatch)
        target = None if single else self.goal.rows
        name = self.node_name()

        def run(part):
            # Accumulation is accounted by CAPACITY, not live rows: capacity
            # is static (known without a device->host sync), and rows <=
            # capacity so the goal is still met. The old int(n_rows) read
            # here cost one blocking device->host read per batch and made
            # the exec untraceable under whole-stage fusion.
            pending: List[int] = []    # catalog buffer ids
            direct: List[ColumnarBatch] = []  # no-catalog fallback
            pending_cap = 0

            def concat_ids(ids):
                from .execs import _pinned_concat
                with ctx.registry.timer(name, "concatTime",
                                        trace="coalesce.concat"):
                    return _pinned_concat(catalog, ids)

            def concat_direct(batches):
                with ctx.registry.timer(name, "concatTime",
                                        trace="coalesce.concat"):
                    return _coalesce_device(list(batches))

            def flush():
                nonlocal pending_cap
                if pending:
                    # On OOM the accumulated ids split in half: each half
                    # concatenates separately, so the goal degrades to two
                    # smaller output batches instead of the query dying.
                    outs = R.with_retry(ctx, f"{name}.concat",
                                        list(pending), concat_ids,
                                        split=R.halve_list, node=name)
                    for b in pending:
                        catalog.free(b)
                elif direct:
                    outs = R.with_retry(ctx, f"{name}.concat",
                                        list(direct), concat_direct,
                                        split=R.halve_list, node=name)
                else:
                    return []
                ctx.metric(name, "numInputBatches",
                           len(pending) + len(direct))
                ctx.metric(name, "numOutputBatches", len(outs))
                pending.clear()
                direct.clear()
                pending_cap = 0
                return outs

            for db in part:
                if db.capacity == 0:
                    continue
                if catalog is not None and not ctx.in_fusion:
                    pending.append(catalog.register_batch(
                        db, SP.ACTIVE_BATCHING_PRIORITY,
                        owner=getattr(ctx, "qos", None)))
                else:
                    direct.append(db)
                pending_cap += db.capacity
                if not single and pending_cap >= target:
                    yield from flush()
            yield from flush()
        return [run(p) for p in self.children[0].execute(ctx)]


def insert_coalesce(plan: PhysicalPlan, default_target_rows: int
                    ) -> PhysicalPlan:
    """Insert coalesce nodes per operators' declared child goals, skipping
    where the child already satisfies the goal
    (GpuTransitionOverrides.optimizeCoalesce analog)."""

    def fix(node: PhysicalPlan) -> PhysicalPlan:
        new_children = [fix(c) for c in node.children]
        goals = getattr(node, "children_coalesce_goals", None)
        if goals:
            assert len(goals) == len(new_children), \
                (node.node_name(), goals, len(new_children))
            wrapped = []
            for c, goal in zip(new_children, goals):
                if goal is None or not getattr(c, "columnar", False):
                    wrapped.append(c)
                    continue
                # Execs declare goals as strings to avoid import cycles.
                if goal == "single":
                    goal = RequireSingleBatch()
                elif goal == "target":
                    goal = TargetSize(default_target_rows)
                from .execs import HostToDeviceExec
                if isinstance(c, TpuCoalesceBatchesExec):
                    produced = c.goal
                elif isinstance(c, HostToDeviceExec):
                    # Uploads already accumulate to their goal
                    # (optimizeCoalesce recognizes HostColumnarToGpu goals).
                    produced = TargetSize(c.goal_rows)
                else:
                    produced = None
                if produced is not None and goal.satisfied_by(produced):
                    wrapped.append(c)
                elif isinstance(c, TpuCoalesceBatchesExec):
                    # Replace a weaker coalesce instead of stacking two.
                    wrapped.append(TpuCoalesceBatchesExec(c.children[0], goal))
                else:
                    wrapped.append(TpuCoalesceBatchesExec(c, goal))
            new_children = wrapped
        if list(new_children) != list(node.children):
            node = node.with_children(new_children)
        return node

    return fix(plan)
