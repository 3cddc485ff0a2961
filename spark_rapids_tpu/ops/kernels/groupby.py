"""Sort-based group-by kernel — the libcudf ``groupby`` replacement.

cuDF hash-aggregates with device hash tables (reached via JNI from
``aggregate.scala:728`` in the reference). Hash tables are a poor fit for
XLA's static-shape model, so the TPU-native design is sort-based:

1. lexicographic ``lax.sort`` of the key columns (validity participates so
   null forms its own group, like Spark),
2. segment boundaries where adjacent sorted keys differ,
3. ``jax.ops.segment_*`` reductions with ``num_segments = capacity``,
4. group keys gathered from each segment's first row.

The output batch has one live row per distinct key; its capacity equals the
input capacity (worst case all-distinct), carried as the usual traced
``n_rows``. Partial->final merge reuses the same kernel with merge
aggregations (sum-of-partial-sums etc.), mirroring the reference's
partial/final mode split (``aggregate.scala:259-450``).
"""

from __future__ import annotations

import dataclasses
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from ... import types as T
from ...data.column import DeviceColumn
from ..strings_util import char_matrix
from .rowops import (gather_column, orderable_key, orderable_values,
                     sort_permutation, string_sort_keys)


def _equal_adjacent(col: DeviceColumn, perm: jnp.ndarray) -> jnp.ndarray:
    """bool[capacity]: row i (sorted order) has the same key as row i-1.

    The flat-string branch compares W-wide char rows."""
    sorted_validity = col.validity[perm]
    vprev = jnp.concatenate([sorted_validity[:1], sorted_validity[:-1]])
    if col.is_string:
        m = char_matrix(col)[perm]
        prev = jnp.concatenate([m[:1], m[:-1]], axis=0)
        data_eq = jnp.all(m == prev, axis=1)
    else:
        # (bucket, key) pair equality: NaN rides the bucket with a zeroed
        # key and -0.0 canonicalizes, so this is Spark grouping equality.
        key, nb = orderable_key(col)
        k = key[perm]
        b = nb[perm]
        kprev = jnp.concatenate([k[:1], k[:-1]])
        bprev = jnp.concatenate([b[:1], b[:-1]])
        data_eq = (k == kprev) & (b == bprev)
    both_null = ~sorted_validity & ~vprev
    return (data_eq & sorted_validity & vprev) | both_null


def group_ids(keys: Sequence[DeviceColumn], n_rows: jnp.ndarray
              ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Compute (segment_id_per_original_row, n_groups, first_row_index_per_group).

    segment ids are dense [0, n_groups); dead rows get id capacity-1 is NOT
    safe, so they get id = capacity (dropped by segment reductions bounded to
    capacity via clamping at use sites); here they receive the last live
    group's id but contribute nothing because callers mask their inputs.
    """
    capacity = keys[0].capacity
    perm = sort_permutation(keys, n_rows)
    eq = jnp.ones(capacity, dtype=jnp.bool_)
    for k in keys:
        eq = eq & _equal_adjacent(k, perm)
    live_sorted = (jnp.arange(capacity, dtype=jnp.int32) < n_rows)
    # First row of the sorted array starts a segment by definition.
    is_boundary = (~eq | (jnp.arange(capacity) == 0)) & live_sorted
    seg_sorted = jnp.cumsum(is_boundary.astype(jnp.int32)) - 1
    seg_sorted = jnp.maximum(seg_sorted, 0)
    n_groups = jnp.sum(is_boundary.astype(jnp.int32))
    # Scatter segment ids back to original row order.
    seg = jnp.zeros(capacity, dtype=jnp.int32).at[perm].set(seg_sorted)
    # First original-row index of each segment (for gathering key values).
    firsts = jnp.zeros(capacity, dtype=jnp.int32).at[seg_sorted].max(
        jnp.where(is_boundary, perm, 0))
    return seg, n_groups, firsts


# ---------------------------------------------------------------------------
# Sorted-space grouped aggregation (scatter-free)
# ---------------------------------------------------------------------------


def _minmax_strip_nan(values: jnp.ndarray, op: str) -> jnp.ndarray:
    """Spark float semantics prep for min/max (FloatUtils.scala:84): NaN
    orders greatest and -0.0 == 0.0. Replace NaN with the op's neutral so a
    plain min/max reduction sees through it; :func:`_minmax_reinstate_nan`
    puts NaN back where it is the true answer."""
    repl = jnp.asarray(-jnp.inf if op == "max" else jnp.inf, values.dtype)
    v = jnp.where(jnp.isnan(values), repl, values)
    return jnp.where(v == 0, jnp.zeros((), v.dtype), v)


def _minmax_reinstate_nan(res: jnp.ndarray, nan_cnt: jnp.ndarray,
                          cnt: jnp.ndarray, op: str) -> jnp.ndarray:
    """max is NaN when ANY contribution was NaN (NaN is greatest); min is
    NaN only when ALL contributions were."""
    has_nan = (nan_cnt > 0) if op == "max" else (nan_cnt == cnt)
    return jnp.where(has_nan & (cnt > 0), jnp.asarray(jnp.nan, res.dtype),
                     res)


#: Max packed-code group count for the direct-indexed path: the slot
#: tables stay a few KB a lane and no grouping sort runs. (What the
#: reductions into them cost is under ``_MASKED_SLOT_LIMIT``.)
_DICT_GROUP_LIMIT = 4096

#: Up to this many packed slots ``_dict_grouped_aggregate`` takes each
#: slot's sum, min and max by one masked reduction over the batch; above
#: it, by ``jax.ops.segment_*`` (a scatter). A scatter costs rows x lanes
#: whatever the slot count, a masked reduction rows x lanes x slots. On a
#: v5e, one 1 Mi-row batch of q1's 18 lanes (PR 36's microbench, PERF.md
#: section 6): scatter 0.186-0.190 s at every count; masked 0.0035 s at 13
#: segments, 0.0097 at 128, 0.032 at 512, 0.061 at 1,024, 0.119 at 2,048,
#: 0.178 at 3,072, 0.238 at 4,096 — the two cross near 3,250. The cut
#: sits at the last measured count with room to spare (37% under the
#: scatter): a lane mix heavier in 64-bit min/max would cross earlier.
_MASKED_SLOT_LIMIT = 2048


#: Slot-table width for the dense grouping path. 2^21 slots of f64 are
#: 16MB per reduction lane; the path trades the grouping ``lax.sort`` for
#: O(n) segment scatters. On the v5e a scatter-add runs serially, 10 ns
#: an update and lane (0.19 s for 1 Mi rows x 18 lanes, PR 36's
#: microbench; q3's whole aggregate through this path reads 0.063 s a
#: query, PR 34); the sort it replaces was not timed beside it.
_DENSE_AGG_SLOTS = 1 << 21


def _dict_slots(keys: Sequence[DeviceColumn]) -> Optional[int]:
    """The packed slot count of the direct-indexed path — each key's
    dictionary size plus its null slot, multiplied — when every key is a
    sorted-dictionary column and the product fits ``_DICT_GROUP_LIMIT``;
    else None. Static: shapes and pytree aux data only."""
    if not all(k.is_dict and k.dict_sorted for k in keys):
        return None
    n_slots = 1
    for k in keys:
        n_slots *= k.dict_size + 1  # slot 0 = null
    return n_slots if n_slots <= _DICT_GROUP_LIMIT else None


def masked_slot_form(keys: Sequence[DeviceColumn]) -> bool:
    """True when :func:`grouped_aggregate` reduces a batch with these keys
    slot by slot with masked reductions and no scatter. It reads nothing
    but shapes, so the executor asks it of a batch's abstract key columns
    to count ``aggMaskedSlotBatches`` on the host."""
    n_slots = _dict_slots(keys)
    return n_slots is not None and n_slots <= _MASKED_SLOT_LIMIT


def _dense_eligible(keys, inputs) -> bool:
    """True when the packed direct-offset path applies: every key
    int-like (ints/date/bool/dict codes — not floats, whose value span
    is meaningless as an address space) and plain numeric reduction
    lanes. Multi-key groupings pack mixed-radix; the data-dependent
    span-product check is the kernel's fail flag.

    (A hashed multi-key variant with an exact collision sidecar was
    measured (round 5) to LOSE to the grouping sort at realistic
    capacities; exact packing has none of its fixed costs.)"""
    if not keys or len(keys) > 6:  # radix product hopeless beyond a few
        return False
    for k in keys:
        if k.is_complex or (k.dtype.is_floating and not k.is_dict):
            return False
        if k.is_string and not (k.is_dict and k.dict_sorted):
            return False
    for v, val, _ in inputs:
        if v.ndim != 1 or not (jnp.issubdtype(v.dtype, jnp.number)
                               or v.dtype == jnp.bool_):
            return False
    return True


def _key_lane(k: DeviceColumn) -> jnp.ndarray:
    """Validity-normalized int64 value lane for hashing/equality."""
    v64 = k.codes.astype(jnp.int64) if k.is_dict else \
        orderable_values(k.data, k.dtype.is_floating)
    return jnp.where(k.validity, v64, 0)


def _compact_slots(occupied: jnp.ndarray, capacity: int
                   ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """(n_groups, slot_of_group[capacity], group_live) — compaction of
    occupied slots to the front, preserving slot order, via cumsum +
    scatter (O(S); a slot-space lax.sort would reintroduce the sort
    tax)."""
    n_slots = occupied.shape[0]
    n_groups = jnp.sum(occupied.astype(jnp.int32))
    pos = jnp.cumsum(occupied.astype(jnp.int32)) - 1
    idx = jnp.where(occupied, pos, capacity)
    slot_of_group = jnp.zeros(capacity, jnp.int32).at[idx].set(
        jnp.arange(n_slots, dtype=jnp.int32), mode="drop")
    group_live = jnp.arange(capacity, dtype=jnp.int32) < n_groups
    return n_groups, slot_of_group, group_live


def _apply_many(pre_many, lanes):
    """Apply a row-space map (e.g. the sort permutation gather) to many
    lanes as dtype-grouped 2D batches — ONE gather kernel per dtype
    instead of one per lane."""
    out = [None] * len(lanes)
    groups = {}
    for i, lane in enumerate(lanes):
        groups.setdefault(lane.dtype.name, []).append(i)
    for idxs in groups.values():
        stacked = jnp.stack([lanes[i] for i in idxs], axis=1)
        mapped = pre_many(stacked)
        for j, i in enumerate(idxs):
            out[i] = mapped[:, j]
    return out


def _segment_reduce_inputs(inputs, seg, iota, capacity, live,
                           pre=None, post=None, seg_many=None,
                           pre_many=None):
    """THE per-op aggregate dispatch: one copy of the count/sum/min/max/
    first/last semantics (Spark NaN handling included) shared by every
    grouping strategy — sort, packed-dict, and dense-slot paths inject
    their mechanics and reuse these semantics, so an op fix lands
    everywhere at once. ``pre`` maps row-space lanes (the sort path's
    permutation gather), ``seg(x, op)`` reduces a row lane into dense
    group rows, ``iota`` positions first/last in pre-space, ``post``
    masks dead group lanes. (global_aggregate is the no-segment variant
    and keeps its whole-array reductions.)

    BATCHED execution: every unfusable kernel launch has a fixed cost
    (not measured on a locally attached chip yet), and a q1-shaped
    aggregation used to issue ~30 of them (one segment scatter per
    buffer, one permutation gather per lane). With ``seg_many``/
    ``pre_many`` the lanes stack by (op kind, dtype) and each group runs
    as ONE 2D kernel — a 10-buffer aggregation now costs ~3 segment
    scatters and ~2 gathers total."""
    pre = pre or (lambda x: x)
    post = post or (lambda x: x)

    # -- phase 0: row-space pre-map, dtype-batched -------------------------
    if pre_many is not None and inputs:
        pvals = _apply_many(pre_many, [v for v, _, _ in inputs])
        pvalid = _apply_many(pre_many, [val for _, val, _ in inputs])
    else:
        pvals = [pre(v) for v, _, _ in inputs]
        pvalid = [pre(val) for _, val, _ in inputs]

    # -- phase 1: collect reduction requests -------------------------------
    reqs: list = []     # (lane, kind)

    def want(lane, kind):
        reqs.append((lane, kind))
        return len(reqs) - 1

    plan = []
    for (v, val, op), v_p, val_p in zip(inputs, pvals, pvalid):
        contrib = val_p & live
        item = {"op": op, "v_p": v_p}
        item["cnt"] = want(contrib.astype(jnp.int64), "sum")
        if op == "sum":
            item["res"] = want(
                jnp.where(contrib, v_p, jnp.zeros((), v_p.dtype)), "sum")
        elif op in ("min", "max"):
            floating = jnp.issubdtype(v_p.dtype, jnp.floating)
            vv = _minmax_strip_nan(v_p, op) if floating else v_p
            neutral = _max_value(vv.dtype) if op == "min" \
                else _min_value(vv.dtype)
            item["res"] = want(jnp.where(contrib, vv, neutral), op)
            if floating:
                item["nan"] = want(
                    (jnp.isnan(v_p) & contrib).astype(jnp.int64), "sum")
        elif op == "first":
            item["pos"] = want(jnp.where(contrib, iota, capacity), "min")
        elif op == "last":
            item["pos"] = want(jnp.where(contrib, iota, -1), "max")
        elif op != "count":
            raise ValueError(op)
        plan.append(item)

    # -- phase 2: one segment reduction per (kind, dtype) ------------------
    out: list = [None] * len(reqs)
    if seg_many is not None:
        groups = {}
        for i, (lane, kind) in enumerate(reqs):
            groups.setdefault((kind, lane.dtype.name), []).append(i)
        for (kind, _), idxs in groups.items():
            if len(idxs) == 1:
                i = idxs[0]
                out[i] = seg(reqs[i][0], kind)
                continue
            stacked = jnp.stack([reqs[i][0] for i in idxs], axis=1)
            red = seg_many(stacked, kind)
            for j, i in enumerate(idxs):
                out[i] = red[:, j]
    else:
        for i, (lane, kind) in enumerate(reqs):
            out[i] = seg(lane, kind)

    # -- phase 3: finalize per op ------------------------------------------
    results = []
    for item in plan:
        op = item["op"]
        cnt = out[item["cnt"]]
        if op == "count":
            res = cnt
        elif op == "sum":
            res = out[item["res"]]
        elif op in ("min", "max"):
            res = out[item["res"]]
            if "nan" in item:
                res = _minmax_reinstate_nan(res, out[item["nan"]], cnt, op)
        else:  # first / last
            pos = out[item["pos"]]
            res = item["v_p"][jnp.clip(pos, 0, capacity - 1)]
        results.append((post(res), post(cnt)))
    return results


def _dense_int_aggregate(keys, live, inputs):
    """Direct-offset grouping for int-like keys packed mixed-radix into
    one slot id: per key, lane = value - min + 1 (0 = null); the packed
    id is exact by construction (injective while the span product fits
    the slot table), so unlike a hashed scheme there are no collisions
    to detect and no sidecar. O(n) scatters replace the grouping sort
    entirely; packed order == the sort path's nulls-first ascending
    group order. The fail flag trips when the observed span product
    exceeds the slot table — the session's dense-mode escalation
    re-runs on the sort path (same learning loop as the dense joins)."""
    S = _DENSE_AGG_SLOTS
    capacity = keys[0].capacity
    big = jnp.int64(2**62)
    packed = jnp.zeros(capacity, jnp.int64)
    prod = jnp.int64(1)
    fail = jnp.bool_(False)
    for key in keys:
        v64 = _key_lane(key)
        lv = live & key.validity
        any_valid = lv.any()
        vmin = jnp.where(any_valid, jnp.min(jnp.where(lv, v64, big)), 0)
        vmax = jnp.where(any_valid, jnp.max(jnp.where(lv, v64, -big)), 0)
        diff = vmax - vmin  # wraps negative when the span overflows int64
        fail = fail | (diff < 0) | (diff >= jnp.int64(S - 1))
        span = jnp.clip(diff, 0, S - 1) + 2  # +1 bias, +1 null lane
        lane = jnp.where(key.validity,
                         jnp.clip(v64 - vmin + 1, 0, S - 1), 0)
        packed = packed * span + lane
        prod = jnp.minimum(prod * span, jnp.int64(S) + 1)
    fail = fail | (prod > jnp.int64(S))
    slot = jnp.clip(packed, 0, S - 1).astype(jnp.int32)
    slot = jnp.where(live, slot, S)  # dead rows -> spare slot
    rows_per_slot = jax.ops.segment_sum(live.astype(jnp.int32), slot,
                                        num_segments=S + 1)[:S]
    n_groups, slot_of_group, group_live = _compact_slots(
        rows_per_slot > 0, capacity)
    iota = jnp.arange(capacity, dtype=jnp.int32)
    rep = jax.ops.segment_min(jnp.where(live, iota, capacity), slot,
                              num_segments=S + 1)[:S]
    rep_g = jnp.clip(rep[slot_of_group], 0, capacity - 1)
    key_cols = [gather_column(key, rep_g, group_live) for key in keys]

    def seg(x, op="sum"):
        f = {"sum": jax.ops.segment_sum, "min": jax.ops.segment_min,
             "max": jax.ops.segment_max}[op]
        full = f(x, slot, num_segments=S + 1)[:S]
        return jnp.where(group_live, full[slot_of_group],
                         jnp.zeros((), full.dtype))

    def seg_many(m, op="sum"):
        f = {"sum": jax.ops.segment_sum, "min": jax.ops.segment_min,
             "max": jax.ops.segment_max}[op]
        full = f(m, slot, num_segments=S + 1)[:S]
        return jnp.where(group_live[:, None], full[slot_of_group],
                         jnp.zeros((), full.dtype))
    results = _segment_reduce_inputs(inputs, seg, iota, capacity, live,
                                     seg_many=seg_many)
    return key_cols, results, n_groups, group_live, fail


def grouped_aggregate(keys: Sequence[DeviceColumn], live: jnp.ndarray,
                      inputs: Sequence[Tuple[jnp.ndarray, jnp.ndarray, str]],
                      dense_mode: int = 0
                      ) -> Tuple[List[DeviceColumn],
                                 List[Tuple[jnp.ndarray, jnp.ndarray]],
                                 jnp.ndarray, jnp.ndarray, object]:
    """Whole grouped aggregation. Returns (key_cols, results, n_groups,
    group_live, fail): ``fail`` is the literal False for the always-exact
    paths, or a deferred device bool the caller must feed the session's
    dense-mode retry (mirrors the dense-join escalation).

    Path choice: packed-dict direct indexing (small static code spaces)
    -> dense/hash slot tables (``dense_mode == 0``: O(n) scatters instead
    of the grouping sort; data-dependent fail -> escalate) -> the sort
    path below.

    DIRECT-INDEXED PATH: when every key is a sorted-dictionary string
    column and the packed code space is small (<= _DICT_GROUP_LIMIT), the
    group id IS the packed code — no sort, no permutation. Up to
    ``_MASKED_SLOT_LIMIT`` slots each slot's sum, min and max is one masked
    reduction over the batch (TPC-H q1: 12 slots, 18 lanes, 1 Mi rows in
    0.0046 s on a v5e against 0.186 s for the ``segment_*`` scatters it
    replaced, PR 36); above it the reductions are ``segment_*`` at
    dictionary width.

    Design constraints, in tension, both from this TPU toolchain:
    * RUNTIME: sorts/gathers are full memory passes; a scatter-add is a
      serial loop, 10-13 ns an update and lane on a v5e whatever the
      number of targets (ledger, PR 34; PR 36's microbench), 66 ns for a
      64-bit ``.at[].set`` (PR 33).
    * COMPILE TIME: every ``lax.sort``/``associative_scan`` unrolls into
      hundreds of HLO stages; compile cost grows superlinearly with sort
      OPERAND COUNT (a 2-operand 1M sort compiles in ~20s, an 18-operand
      one in ~15min on the remote helper). So: ONE argsort with the fewest
      possible operands (dict-encoded string keys ride as one int32 code
      lane), payload moved by gathers, and segment reductions via global
      cumsum + prefix-range differences or single-op segment scatters —
      never unrolled scans, never payload-carrying sorts.

    ``inputs`` is a list of (values[cap], validity[cap], op). Returns
    (key_columns, [(result[cap], counts[cap])], n_groups, group_live) as
    DENSE group rows (row g = group g).
    """
    n_slots = _dict_slots(keys)
    if n_slots is not None:
        return _dict_grouped_aggregate(keys, live, inputs, n_slots) \
            + (False,)
    if dense_mode == 0 and _dense_eligible(keys, inputs):
        return _dense_int_aggregate(keys, live, inputs)
    return _sort_grouped_aggregate(keys, live, inputs) + (False,)


def _sort_grouped_aggregate(keys: Sequence[DeviceColumn],
                            live: jnp.ndarray,
                            inputs: Sequence[Tuple[jnp.ndarray, jnp.ndarray,
                                                   str]]
                            ) -> Tuple[List[DeviceColumn],
                                       List[Tuple[jnp.ndarray, jnp.ndarray]],
                                       jnp.ndarray, jnp.ndarray]:
    """The always-exact sort path (see grouped_aggregate doc)."""
    capacity = keys[0].capacity
    iota = jnp.arange(capacity, dtype=jnp.int32)
    # -- ONE narrow grouping argsort --------------------------------------
    # Grouping needs equal keys ADJACENT and dead rows at the end — any
    # total order does. So every per-key null bucket folds into ONE leading
    # bucket operand (equality is preserved: the bucket encodes the full
    # null pattern): sort operand count = n_keys + 2, and TPU compile cost
    # grows superlinearly with operand count.
    # The dead-row marker must dominate any live bucket sum: live buckets
    # reach at most 6 * sum(7^i) < 7^n_keys, so 7^n_keys is a safe marker
    # (int64 holds it up to 22 keys; more grouping keys than that would be
    # pathological, so fall back to an unpacked bucket per key).
    packed = len(keys) <= 20
    dead_marker = 7 ** len(keys) if packed else 1
    bucket = jnp.where(live, 0, dead_marker).astype(jnp.int64)
    key_operands: List[jnp.ndarray] = []
    for i, k in enumerate(keys):
        if k.is_string:
            ops = string_sort_keys(k)
            nb = ops[0]
            per_key = list(ops[1:])
        else:
            key, nb = orderable_key(k)
            per_key = [key]
        if packed:
            bucket = bucket + (nb.astype(jnp.int64) + 3) * (7 ** i)
        else:
            key_operands.append(nb.astype(jnp.int8))
        key_operands.extend(per_key)
    operands = [bucket] + key_operands
    sorted_all = jax.lax.sort(tuple(operands) + (iota,),
                              num_keys=len(operands), is_stable=True)
    key_ops_sorted = sorted_all[:-1]  # bucket participates in equality
    perm = sorted_all[-1]
    # -- segment structure (compare + cumsum: single-op HLO) --------------
    eq = jnp.ones(capacity, dtype=jnp.bool_)
    for o in key_ops_sorted:
        prev = jnp.concatenate([o[:1], o[:-1]])
        eq = eq & (o == prev)
    # Dead rows sank to the end under the live bucket; the mask itself
    # must still be permuted (a lazy-filter mask is scattered pre-sort).
    live_sorted = live[perm]
    boundary = (~eq | (iota == 0)) & live_sorted
    n_groups = jnp.sum(boundary.astype(jnp.int32))
    group_live = iota < n_groups
    gid = jnp.maximum(jnp.cumsum(boundary.astype(jnp.int32)) - 1, 0)
    # Dense group start/end positions: one scatter-min, cheap to compile.
    starts = jax.ops.segment_min(jnp.where(boundary, iota, capacity),
                                 gid, num_segments=capacity)
    starts = jnp.where(group_live, jnp.minimum(starts, capacity - 1), 0)

    # -- group key output columns (gather at segment starts) --------------
    orig_starts = perm[starts]
    key_cols = [gather_column(k, orig_starts, group_live) for k in keys]

    # -- per-input reductions (shared dispatch; segment scatters are
    # single-op HLO, cheap to compile; at run time the chip's serial
    # loop: q13's two aggregates at 2 Mi rows 0.31 s each, PR 34). --------
    def seg(x, op="sum"):
        # One body serves both the 1-D and the lane-stacked 2-D case
        # (segment_* is rank-agnostic here).
        f = {"sum": jax.ops.segment_sum, "min": jax.ops.segment_min,
             "max": jax.ops.segment_max}[op]
        return f(x, gid, num_segments=capacity)

    seg_many = seg

    def post(x):
        return jnp.where(group_live, x, jnp.zeros((), x.dtype))

    results = _segment_reduce_inputs(
        inputs, seg, iota, capacity, live_sorted,
        pre=lambda x: x[perm], post=post,
        seg_many=seg_many, pre_many=lambda m: m[perm])
    return key_cols, results, n_groups, group_live


def _dict_grouped_aggregate(keys: Sequence[DeviceColumn],
                            live: jnp.ndarray,
                            inputs: Sequence[Tuple[jnp.ndarray, jnp.ndarray,
                                                   str]],
                            n_slots: int
                            ) -> Tuple[List[DeviceColumn],
                                       List[Tuple[jnp.ndarray, jnp.ndarray]],
                                       jnp.ndarray, jnp.ndarray]:
    """Direct-indexed grouping for sorted-dictionary keys (see
    grouped_aggregate doc). Group id = mixed-radix packed (code + 1 | 0 for
    null) per key; packed ascending order == the sort path's lexicographic
    nulls-first order, so output group order matches the slow path."""
    from ...data.column import bucket_capacity
    capacity = keys[0].capacity
    iota = jnp.arange(capacity, dtype=jnp.int32)
    gid = jnp.zeros(capacity, dtype=jnp.int32)
    for k in keys:
        slot = jnp.where(k.validity, k.codes + 1, 0)
        gid = gid * (k.dict_size + 1) + slot
    gid = jnp.where(live, gid, n_slots)  # dead rows land in a spare slot

    masked = n_slots <= _MASKED_SLOT_LIMIT
    slot_ids = jnp.arange(n_slots, dtype=jnp.int32)

    def slot_reduce(x, op):
        """Row lanes to slot rows ``[n_slots, ...]``; an empty slot reads
        the op's neutral element in either form (0, the dtype's greatest,
        its least). The masked form takes one lane ``[rows]``: XLA fuses
        the ``[slots, rows]`` compare into the reduction and never writes
        it; a dead row's spare slot hits none."""
        if not masked:
            f = {"sum": jax.ops.segment_sum, "min": jax.ops.segment_min,
                 "max": jax.ops.segment_max}[op]
            return f(x, gid, num_segments=n_slots + 1)[:n_slots]
        neutral = {"sum": jnp.zeros((), x.dtype), "min": _max_value(x.dtype),
                   "max": _min_value(x.dtype)}[op]
        with jax.named_scope("masked_slot_reduce"):
            hit = gid[None, :] == slot_ids[:, None]
            lanes = jnp.where(hit, x[None, :], neutral)
            if op == "sum":
                return jnp.sum(lanes, axis=1, dtype=x.dtype)
            return (jnp.min if op == "min" else jnp.max)(lanes, axis=1)

    rows_per_slot = slot_reduce(live.astype(jnp.int32), "sum")
    occupied = rows_per_slot > 0
    n_groups = jnp.sum(occupied.astype(jnp.int32))
    # Compact occupied slots to the front, preserving packed (= sorted key)
    # order: one tiny sort over n_slots lanes.
    slot_iota = jnp.arange(n_slots, dtype=jnp.int32)
    _, slot_of_group = jax.lax.sort(
        ((~occupied).astype(jnp.int8), slot_iota), num_keys=1,
        is_stable=True)
    out_cap = bucket_capacity(n_slots)
    pad = out_cap - n_slots
    slot_of_group = jnp.pad(slot_of_group, (0, pad))
    group_live = jnp.arange(out_cap, dtype=jnp.int32) < n_groups

    # Key columns: recover per-key slots from the packed id; dictionary
    # buffers are shared with the inputs (codes move, entries don't).
    key_cols: List[DeviceColumn] = []
    strides = []
    s = 1
    for k in reversed(keys):
        strides.append(s)
        s *= k.dict_size + 1
    strides.reverse()
    for k, stride in zip(keys, strides):
        slot = (slot_of_group // stride) % (k.dict_size + 1)
        validity = (slot > 0) & group_live
        codes = jnp.where(validity, slot - 1, 0).astype(jnp.int32)
        key_cols.append(DeviceColumn(
            data=k.data, validity=validity, dtype=k.dtype,
            offsets=k.offsets, max_bytes=k.max_bytes, codes=codes,
            dict_sorted=k.dict_sorted))

    def seg(x, op="sum"):
        return jnp.pad(slot_reduce(x, op), (0, pad))[slot_of_group]

    def seg_many(m, op="sum"):
        if masked:
            # A reduction a lane (XLA reads the lanes out of the stack's
            # operands and builds neither it nor the compare: 0 bytes of
            # temporaries, and 0.0035 s for 0.0049 s stacked at q1's
            # shape, PR 36); one pad and one gather for them all.
            full = jnp.stack([slot_reduce(m[:, j], op)
                              for j in range(m.shape[1])], axis=1)
        else:
            full = slot_reduce(m, op)
        return jnp.pad(full, ((0, pad), (0, 0)))[slot_of_group]

    def post(x):
        return jnp.where(group_live, x, jnp.zeros((), x.dtype))

    results = _segment_reduce_inputs(
        inputs, seg, iota, capacity, live, post=post,
        seg_many=seg_many)
    return key_cols, results, n_groups, group_live


def global_aggregate(capacity: int, live: jnp.ndarray,
                     inputs: Sequence[Tuple[jnp.ndarray, jnp.ndarray, str]]
                     ) -> Tuple[List[DeviceColumn],
                                List[Tuple[jnp.ndarray, jnp.ndarray]],
                                jnp.ndarray, jnp.ndarray]:
    """Global (no keys) aggregation: plain masked whole-array reductions,
    fully fused by XLA — no sorts at all. Always emits exactly ONE group
    (count 0 / null values over empty input), so callers never need a
    row-count sync to special-case emptiness."""
    iota = jnp.arange(capacity, dtype=jnp.int32)
    results = []
    for v, val, op in inputs:
        contrib = val & live
        cnt = jnp.sum(contrib.astype(jnp.int64))
        if op == "count":
            res = cnt
        elif op == "sum":
            res = jnp.sum(jnp.where(contrib, v, jnp.zeros((), v.dtype)))
        elif op in ("min", "max"):
            floating = jnp.issubdtype(v.dtype, jnp.floating)
            vv = _minmax_strip_nan(v, op) if floating else v
            neutral = _max_value(vv.dtype) if op == "min" \
                else _min_value(vv.dtype)
            masked = jnp.where(contrib, vv, neutral)
            res = jnp.min(masked) if op == "min" else jnp.max(masked)
            if floating:
                nan_cnt = jnp.sum((jnp.isnan(v) & contrib).astype(jnp.int64))
                res = _minmax_reinstate_nan(res, nan_cnt, cnt, op)
        elif op == "first":
            idx = jnp.argmax(contrib).astype(jnp.int32)
            res = v[idx]
        elif op == "last":
            idx = capacity - 1 - jnp.argmax(contrib[::-1]).astype(jnp.int32)
            res = v[jnp.clip(idx, 0, capacity - 1)]
        else:
            raise ValueError(op)
        dense_res = jnp.where(iota == 0, res,
                              jnp.zeros((), res.dtype)).astype(v.dtype) \
            if op != "count" else jnp.where(iota == 0, res, 0)
        dense_cnt = jnp.where(iota == 0, cnt, 0)
        results.append((dense_res, dense_cnt))
    return [], results, jnp.asarray(1, jnp.int32), iota < 1


def segment_reduce(values: jnp.ndarray, validity: jnp.ndarray,
                   seg: jnp.ndarray, capacity: int, op: str,
                   live: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Reduce ``values`` per segment. Returns (result[capacity], non_empty
    count[capacity] of valid contributions)."""
    contrib = validity & live
    counts = jax.ops.segment_sum(contrib.astype(jnp.int64), seg,
                                 num_segments=capacity)
    if op == "sum":
        masked = jnp.where(contrib, values, 0)
        out = jax.ops.segment_sum(masked, seg, num_segments=capacity)
    elif op == "min":
        neutral = _max_value(values.dtype)
        masked = jnp.where(contrib, values, neutral)
        out = jax.ops.segment_min(masked, seg, num_segments=capacity)
    elif op == "max":
        neutral = _min_value(values.dtype)
        masked = jnp.where(contrib, values, neutral)
        out = jax.ops.segment_max(masked, seg, num_segments=capacity)
    elif op == "count":
        out = counts
    elif op == "first":
        idx = jnp.arange(values.shape[0], dtype=jnp.int32)
        first_idx = jax.ops.segment_min(
            jnp.where(contrib, idx, values.shape[0]), seg,
            num_segments=capacity)
        safe = jnp.clip(first_idx, 0, values.shape[0] - 1)
        out = values[safe]
    elif op == "last":
        idx = jnp.arange(values.shape[0], dtype=jnp.int32)
        last_idx = jax.ops.segment_max(jnp.where(contrib, idx, -1), seg,
                                       num_segments=capacity)
        safe = jnp.clip(last_idx, 0, values.shape[0] - 1)
        out = values[safe]
    else:
        raise ValueError(op)
    return out, counts


def _max_value(dtype):
    if jnp.issubdtype(dtype, jnp.floating):
        return jnp.asarray(jnp.inf, dtype)
    return jnp.asarray(jnp.iinfo(dtype).max, dtype)


def _min_value(dtype):
    if jnp.issubdtype(dtype, jnp.floating):
        return jnp.asarray(-jnp.inf, dtype)
    return jnp.asarray(jnp.iinfo(dtype).min, dtype)


def gather_group_keys(keys: Sequence[DeviceColumn], firsts: jnp.ndarray,
                      n_groups: jnp.ndarray) -> List[DeviceColumn]:
    """Group-key output columns: each group's key from its first member row."""
    capacity = keys[0].capacity
    live = jnp.arange(capacity, dtype=jnp.int32) < n_groups
    return [gather_column(k, firsts, live) for k in keys]
