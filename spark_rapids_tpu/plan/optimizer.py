"""Logical optimizations — the Catalyst passes the reference inherits.

The reference plugs into Spark AFTER Catalyst has optimized the logical
plan, so it gets column pruning, filter placement, etc. for free.
Standalone, this engine must supply the load-bearing ones itself. Column
pruning matters disproportionately on TPU: every operator pass carries its
batch's full payload through sorts/gathers at capacity granularity, so an
unpruned 13-column fact table costs ~4x a pruned 3-column one through a
join — and string columns cost far more.

The pass threads a required-column NAME set top-down and inserts narrowing
``Project`` nodes under joins (the expensive boundary). ``None`` means
"all columns required" (the root, and anything under nodes we don't model).
Nodes whose schemas contain duplicate names are left untouched — name-based
narrowing would be ambiguous.

The set ends in the file scan: a ``Scan`` of which fewer columns are
required than its files hold is replaced by a copy whose ``projected`` lists
the required names in file order, so the physical scans (which read by
their schema) parse, upload and decode those columns alone — the
``readDataSchema`` Catalyst hands ``GpuParquetScan``. A requirement of NO
column (``count(*)``) keeps one, the narrowest fixed-width one, for the row
count. ``None`` reaches a scan unchanged: a bare ``read.parquet(...)``
collected, cached or written reads every column. The scan's line of
``explain`` shows it (``columns=4/16``), and over parquet the
``scanColumnChunksDecoded`` counter reads row groups x referenced columns
(``scan_decoded_per_referenced`` 1.0 in the benchmark).
"""

from __future__ import annotations

from typing import FrozenSet, List, Optional

from ..ops.expression import col
from . import logical as L
from .input_file import META_NAMES

_Req = Optional[FrozenSet[str]]


def _refs(exprs) -> FrozenSet[str]:
    out = set()
    for e in exprs:
        out.update(e.references())
    return frozenset(out)


def _has_dup_names(schema) -> bool:
    names = schema.names
    return len(set(names)) != len(names)


def _narrow(plan: L.LogicalPlan, req: _Req) -> L.LogicalPlan:
    """Insert Project(keep-only-req) above ``plan`` when strictly narrower."""
    if req is None or _has_dup_names(plan.schema):
        return plan
    names = plan.schema.names
    keep = [n for n in names if n in req]
    if not keep or len(keep) == len(names):
        return plan
    return L.Project(plan, [col(n) for n in keep])


def _row_count_column(fields) -> str:
    """The column a scan keeps when no column is required: the first of
    the narrowest fixed-width type (any one gives the row count), else the
    first."""
    fixed = [f for f in fields if f.data_type.is_fixed_width]
    if not fixed:
        return fields[0].name
    return min(fixed, key=lambda f: f.data_type.np_dtype.itemsize).name


def _project_scan(scan: L.Scan, req: FrozenSet[str]) -> L.Scan:
    """``scan`` reading only ``req``, as a NEW node: the DataFrame that
    owns ``scan`` is optimized again on its next collect()."""
    schema = scan.schema
    if _has_dup_names(schema):
        return scan
    keep = [n for n in schema.names if n in req]
    if not keep:
        # The hidden __input_file_* columns are synthesized, not read: a
        # row count comes from a column of the file.
        data = [f for f in schema if f.name not in META_NAMES]
        if not data:
            return scan
        keep = [_row_count_column(data)]
    if len(keep) == len(schema.names):
        return scan
    new = L.Scan(scan.fmt, scan.paths, scan._schema, scan.options,
                 scan.pushed_filters, keep)
    if getattr(scan, "emit_file_meta", False):
        new.emit_file_meta = True
    return new


def prune_columns(plan: L.LogicalPlan) -> L.LogicalPlan:
    return _prune(plan, None)


def _prune(plan: L.LogicalPlan, req: _Req) -> L.LogicalPlan:
    if isinstance(plan, L.Scan):
        return plan if req is None else _project_scan(plan, req)

    if isinstance(plan, L.Project):
        exprs = plan.exprs
        if req is not None:
            kept = [e for e in exprs if e.name in req]
            exprs = kept or exprs[:1]  # never project to zero columns
        child = _prune(plan.children[0], _refs(exprs))
        return L.Project(child, exprs)

    if isinstance(plan, L.Filter):
        creq = None if req is None else req | _refs([plan.condition])
        child = _narrow(_prune(plan.children[0], creq), creq)
        return L.Filter(child, plan.condition)

    if isinstance(plan, L.Aggregate):
        needed = _refs(plan.groupings
                       + [a.func for a in plan.aggregates])
        child = _prune(plan.children[0], needed)
        return L.Aggregate(_narrow(child, needed), plan.groupings,
                           plan.aggregates)

    if isinstance(plan, L.Sort):
        creq = None if req is None else req | _refs(
            [o.child for o in plan.orders])
        child = _narrow(_prune(plan.children[0], creq), creq)
        return L.Sort(child, plan.orders, plan.global_sort)

    if isinstance(plan, L.Limit):
        return L.Limit(_prune(plan.children[0], req), plan.n)

    if isinstance(plan, L.Join):
        left, right = plan.children
        lnames = set(left.schema.names)
        rnames = set(right.schema.names)
        key_l = _refs(plan.left_keys)
        key_r = _refs(plan.right_keys)
        cond = _refs([plan.condition]) if plan.condition is not None \
            else frozenset()
        if req is None:
            lreq = rreq = None
        else:
            lreq = frozenset((req | cond) & lnames) | key_l
            rreq = frozenset((req | cond) & rnames) | key_r
        lp = _narrow(_prune(left, lreq), lreq)
        rp = _narrow(_prune(right, rreq), rreq)
        return L.Join(lp, rp, plan.join_type, plan.left_keys,
                      plan.right_keys, plan.condition)

    if isinstance(plan, L.Union):
        if req is None or _has_dup_names(plan.schema):
            kids = [_prune(c, None) for c in plan.children]
            return L.Union(kids)
        out_names = plan.schema.names
        idxs = [i for i, n in enumerate(out_names) if n in req]
        kids = []
        for c in plan.children:
            cnames = c.schema.names
            creq = frozenset(cnames[i] for i in idxs)
            kids.append(_narrow(_prune(c, creq), creq))
        return L.Union(kids)

    # Unmodeled nodes (windows, expand, writes, sources, ...):
    # require everything below, rebuild children conservatively. With a
    # None requirement child schemas are unchanged, so a shallow copy with
    # swapped children keeps any state the node derived from them valid.
    if plan.children:
        new_children = [_prune(c, None) for c in plan.children]
        if list(new_children) != list(plan.children):
            import copy
            plan = copy.copy(plan)
            plan.children = new_children
    return plan
