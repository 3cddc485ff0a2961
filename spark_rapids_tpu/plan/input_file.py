"""Plan-time rewrite of the input_file_name()/block family.

The reference evaluates these on the GPU by reading the task context's
InputFileBlockHolder (GpuInputFileBlock.scala:114). A jitted TPU kernel
cannot read host task state, and threading a per-file string through the
pytree would recompile per file — so the TPU-native design moves the
information into the DATA instead: the file scan emits three hidden
metadata columns (constant per fragment; the string dict-encodes to a
single dictionary entry, one int32 lane on device), and every
``InputFileName()``-family expression in the plan becomes a column
reference to them. Plans with no file scan below substitute Spark's
no-file constants ('' / -1).

Runs on the logical plan before column pruning, for BOTH the oracle and
the device session — keeping the paths differentially comparable.
"""

from __future__ import annotations

from typing import List, Tuple

from .. import types as T
from ..ops.expression import Alias, Literal, col
from ..ops.nondeterministic import (InputFileBlockLength,
                                    InputFileBlockStart, InputFileName)
from . import logical as L

#: hidden column name per expression class
FILE_NAME_COL = "__input_file_name"
FILE_START_COL = "__input_file_block_start"
FILE_LENGTH_COL = "__input_file_block_length"

META_FIELDS = [T.StructField(FILE_NAME_COL, T.STRING, False),
               T.StructField(FILE_START_COL, T.LONG, False),
               T.StructField(FILE_LENGTH_COL, T.LONG, False)]
#: their names: synthesized per fragment, never read from a file
META_NAMES = frozenset(f.name for f in META_FIELDS)

_COL_OF = {InputFileName: FILE_NAME_COL,
           InputFileBlockStart: FILE_START_COL,
           InputFileBlockLength: FILE_LENGTH_COL}


def _contains_input_file(e) -> bool:
    if isinstance(e, tuple(_COL_OF)):
        return True
    return any(_contains_input_file(c) for c in getattr(e, "children", []))


def _has_any(plan: L.LogicalPlan) -> bool:
    exprs = _node_exprs(plan)
    if any(_contains_input_file(e) for e in exprs):
        return True
    return any(_has_any(c) for c in plan.children)


def _node_exprs(plan: L.LogicalPlan) -> List:
    if isinstance(plan, L.Project):
        return plan.exprs
    if isinstance(plan, L.Filter):
        return [plan.condition]
    return []


def _has_scan(plan: L.LogicalPlan) -> bool:
    if isinstance(plan, L.Scan):
        return True
    return any(_has_scan(c) for c in plan.children)


def _scan_count(plan: L.LogicalPlan) -> int:
    n = 1 if isinstance(plan, L.Scan) else 0
    return n + sum(_scan_count(c) for c in plan.children)


def _substitute(e, use_cols: bool):
    cls = type(e)
    if cls in _COL_OF:
        if use_cols:
            return col(_COL_OF[cls])
        return Literal(e.NO_FILE, e.data_type)
    kids = getattr(e, "children", [])
    if not kids or not _contains_input_file(e):
        return e
    return e.with_children([_substitute(c, use_cols) for c in kids])


def _rewrite(plan: L.LogicalPlan) -> L.LogicalPlan:
    use_cols = _has_scan(plan)
    children = [_rewrite(c) for c in plan.children]
    if isinstance(plan, L.Scan):
        if plan.projected is not None:
            # Pruning hasn't run yet; projected is None at this point.
            raise AssertionError("input-file rewrite must run pre-pruning")
        schema = T.Schema(list(plan._schema) + META_FIELDS)
        new = L.Scan(plan.fmt, plan.paths, schema, plan.options,
                     plan.pushed_filters, plan.projected)
        new.emit_file_meta = True
        return new
    if isinstance(plan, L.Project):
        exprs = []
        for e in plan.exprs:
            s = _substitute(e, use_cols)
            if s is not e and not isinstance(s, Alias) \
                    and getattr(e, "name", None):
                s = Alias(s, e.name)
            exprs.append(s)
        if use_cols and _has_scan(plan):
            # Chained projections prune by name; hidden metadata columns
            # must flow through every Project between the scan and their
            # use sites (the root re-projection drops them at the end).
            have = {getattr(e, "name", None) for e in exprs}
            exprs += [col(f.name) for f in META_FIELDS
                      if f.name not in have]
        return L.Project(children[0], exprs)
    if isinstance(plan, L.Filter):
        return L.Filter(children[0], _substitute(plan.condition, use_cols))
    if children == list(plan.children):
        return plan
    import copy
    new = copy.copy(plan)
    new.children = children
    return new


def _rewrite_no_file(plan: L.LogicalPlan) -> L.LogicalPlan:
    """Replace input_file exprs with the no-file constants everywhere,
    leaving scans untouched (multi-scan fallback)."""
    children = [_rewrite_no_file(c) for c in plan.children]
    if isinstance(plan, L.Project):
        return L.Project(children[0],
                         [_keep_name(e, _substitute(e, False))
                          for e in plan.exprs])
    if isinstance(plan, L.Filter):
        return L.Filter(children[0], _substitute(plan.condition, False))
    if children == list(plan.children):
        return plan
    import copy
    new = copy.copy(plan)
    new.children = children
    return new


def _keep_name(orig, sub):
    if sub is not orig and not isinstance(sub, Alias) \
            and getattr(orig, "name", None):
        return Alias(sub, orig.name)
    return sub


def rewrite_input_file_exprs(plan: L.LogicalPlan) -> L.LogicalPlan:
    """No-op unless the plan uses the input_file family; otherwise rewrite
    and re-project to the original output schema (hidden metadata columns
    must not leak into results of projection-free plans)."""
    if not _has_any(plan):
        return plan
    if _scan_count(plan) > 1:
        # A join of two file scans would give BOTH sides the same hidden
        # column names -> ambiguous resolution above the join. Spark keeps
        # per-task file context; we only model the single-scan case, so
        # substitute the no-file constants and stay unambiguous.
        original_names = plan.schema.names
        new = _rewrite_no_file(plan)
        if new.schema.names != original_names:
            new = L.Project(new, [col(n) for n in original_names])
        return new
    original_names = plan.schema.names
    new = _rewrite(plan)
    if new.schema.names != original_names:
        new = L.Project(new, [col(n) for n in original_names])
    return new
