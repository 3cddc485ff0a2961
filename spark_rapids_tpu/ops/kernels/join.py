"""Equi-join kernels — the libcudf hash-join replacement.

The reference's joins concat the build side and call cudf's hash join
(``GpuHashJoin.scala:113-166``). Hash tables don't map to XLA, so the
TPU-native algorithm is rank-based:

1. **Dense key ids**: concatenate build and probe key columns, lexicographic
   ``lax.sort``, assign each distinct key tuple a dense id, scatter ids back.
   This reduces any multi-column / string / float key to ONE int32 key with
   exact equality (no collision handling, unlike hashing).
2. **Sorted search**: sort build ids, ``searchsorted`` each probe id for its
   [lo, hi) match range; ``counts = hi - lo`` (null keys never match, Spark
   semantics).
3. **Expansion**: output slot k maps back to its probe row by searchsorted
   over the cumulative counts; the build row is recovered from the offset
   within the range. Static output capacity with an overflow count returned;
   callers re-execute with a bigger bucket when it overflows (the dynamic
   part of join output sizing happens at batch granularity, not row).

Inner/left/right/full/semi/anti all derive from (lo, hi, counts).
"""

from __future__ import annotations

from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from ...data.batch import ColumnarBatch
from ...data.column import DeviceColumn
from ..strings_util import char_matrix
from .rowops import orderable_key, string_sort_keys


def dense_key_ids(build_keys: Sequence[DeviceColumn],
                  probe_keys: Sequence[DeviceColumn],
                  n_build: jnp.ndarray, n_probe: jnp.ndarray
                  ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Assign dense ids to distinct key tuples across both sides.

    Returns (build_ids[cap_b], probe_ids[cap_p]); dead rows and null-keyed
    rows get id -1 (never match; Spark equi-join null semantics).
    """
    cap_b = build_keys[0].capacity
    cap_p = probe_keys[0].capacity
    total = cap_b + cap_p

    operands: List[jnp.ndarray] = []
    null_key = jnp.zeros(total, dtype=jnp.bool_)
    live = jnp.concatenate([
        jnp.arange(cap_b, dtype=jnp.int32) < n_build,
        jnp.arange(cap_p, dtype=jnp.int32) < n_probe])
    for b, p in zip(build_keys, probe_keys):
        null_key = null_key | ~jnp.concatenate([b.validity, p.validity])
        if b.is_string:
            # Both sides must expand to the same char width.
            w = max(b.max_bytes, p.max_bytes, 1)
            mb, mp = char_matrix(b, w), char_matrix(p, w)
            m = jnp.concatenate([mb, mp], axis=0)
            operands.extend(m[:, i] for i in range(w))
        else:
            kb, nbb = orderable_key(b)
            kp, nbp = orderable_key(p)
            # The bucket rides along so NaN keys (zeroed, bucket 2) stay
            # distinct from real 0.0 while NaN == NaN joins (Spark
            # normalizes NaN for join keys).
            operands.append(jnp.concatenate([nbb, nbp]))
            operands.append(jnp.concatenate([kb, kp]))
    usable = live & ~null_key
    # Unusable rows sort to the end and never start/join a group.
    operands.insert(0, jnp.where(usable, 0, 1).astype(jnp.int8))
    iota = jnp.arange(total, dtype=jnp.int32)
    sorted_ops = jax.lax.sort(tuple(operands) + (iota,),
                              num_keys=len(operands), is_stable=True)
    perm = sorted_ops[-1]
    # The sort already returns every key operand in sorted order — no
    # post-sort gathers needed.
    keys_sorted = sorted_ops[:-1]
    eq = jnp.ones(total, dtype=jnp.bool_)
    for o in keys_sorted:
        prev = jnp.concatenate([o[:1], o[:-1]])
        eq = eq & (o == prev)
    usable_sorted = keys_sorted[0] == 0
    boundary = (~eq | (iota == 0)) & usable_sorted
    ids_sorted = jnp.cumsum(boundary.astype(jnp.int32)) - 1
    ids_sorted = jnp.where(usable_sorted, jnp.maximum(ids_sorted, 0), -1)
    # Invert the permutation with a second sort instead of a scatter —
    # scatters are the slow ops on TPU, sorts are cheap.
    _, ids = jax.lax.sort((perm, ids_sorted), num_keys=1, is_stable=True)
    return ids[:cap_b], ids[cap_b:]


def join_match(build_keys: Sequence[DeviceColumn],
               probe_keys: Sequence[DeviceColumn],
               live_build: jnp.ndarray, live_probe: jnp.ndarray,
               need_build_hits: bool = False
               ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray,
                          Optional[jnp.ndarray]]:
    """Fused equi-join matching in TWO sorts (vs the ~6 the
    dense_key_ids -> match_ranges -> merge_rank composition costs — sorts
    are the dominant cost of a join program on both TPU and CPU XLA).

    One forward lexicographic sort of both sides with a side flag ordered
    build-before-probe inside each equal-key run; every per-probe match
    range then falls out of segmented prefix scans (elementwise + cumsum,
    bandwidth-speed on TPU): a probe row's build matches are exactly the
    build rows of its run, which all precede it, so
    ``hi = builds_at_or_before(pos)`` and ``lo = builds_before(run_start)``.
    One route-back sort returns results to original row order for both
    sides at once.

    Returns ``(lo, counts, build_at_rank, hits)``:

    * ``lo[cap_p]``   — each probe row's first match, as a *global build
      rank* (position among build rows in sorted-key order),
    * ``counts[cap_p]`` — match count (0 for dead/null-keyed probe rows),
    * ``build_at_rank[cap_b]`` — original build row index at each rank
      (the gather target for expansion),
    * ``hits[cap_b]`` — per-original-build-row matched flag (full joins),
      or None unless ``need_build_hits``.
    """
    cap_b = build_keys[0].capacity
    cap_p = probe_keys[0].capacity
    total = cap_b + cap_p

    operands: List[jnp.ndarray] = []
    null_key = jnp.zeros(total, dtype=jnp.bool_)
    is_build = jnp.arange(total, dtype=jnp.int32) < cap_b
    live = jnp.concatenate([live_build, live_probe])
    for b, p in zip(build_keys, probe_keys):
        null_key = null_key | ~jnp.concatenate([b.validity, p.validity])
        if b.is_string:
            w = max(b.max_bytes, p.max_bytes, 1)
            mb, mp = char_matrix(b, w), char_matrix(p, w)
            m = jnp.concatenate([mb, mp], axis=0)
            operands.extend(m[:, i] for i in range(w))
        else:
            kb, nbb = orderable_key(b)
            kp, nbp = orderable_key(p)
            operands.append(jnp.concatenate([nbb, nbp]))
            operands.append(jnp.concatenate([kb, kp]))
    usable = live & ~null_key
    # Sort order: usable first, then by key, builds before probes in a run.
    operands.insert(0, jnp.where(usable, 0, 1).astype(jnp.int8))
    operands.append(jnp.where(is_build, 0, 1).astype(jnp.int8))
    iota = jnp.arange(total, dtype=jnp.int32)
    sorted_ops = jax.lax.sort(tuple(operands) + (iota,),
                              num_keys=len(operands), is_stable=True)
    perm = sorted_ops[-1]
    # Runs break on key change OR the usable->unusable junction (flag is
    # operand 0); the side flag must NOT break runs.
    keys_sorted = sorted_ops[:-2]
    usable_sorted = sorted_ops[0] == 0
    eq = jnp.ones(total, dtype=jnp.bool_)
    for o in keys_sorted:
        prev = jnp.concatenate([o[:1], o[:-1]])
        eq = eq & (o == prev)
    run_start = ~eq | (iota == 0)

    s_isbuild = perm < cap_b
    b_incl = jnp.cumsum(s_isbuild.astype(jnp.int32))  # builds at-or-before
    # builds strictly before this run, broadcast across the run (b_excl is
    # globally nondecreasing, so a cummax over start-marked values works).
    b_excl = b_incl - s_isbuild.astype(jnp.int32)
    lo_run = jax.lax.cummax(jnp.where(run_start, b_excl, -1))
    # Per sorted position (probe rows): matches = builds in this run.
    hi_s = jnp.where(usable_sorted, b_incl, 0)
    lo_s = jnp.where(usable_sorted, lo_run, 0)
    count_s = jnp.where(usable_sorted & ~s_isbuild, hi_s - lo_s, 0)

    hit_pack = jnp.zeros(total, dtype=jnp.int64)
    if need_build_hits:
        # A build row matched iff its run contains >= 1 usable probe row.
        is_p = (usable_sorted & ~s_isbuild).astype(jnp.int32)
        p_incl = jnp.cumsum(is_p)
        is_last = jnp.concatenate([run_start[1:],
                                   jnp.ones(1, dtype=jnp.bool_)])
        rev = lambda x: jnp.flip(x, 0)  # noqa: E731
        # Probe count at run end / before run start, broadcast across the
        # run. p_incl is globally nondecreasing, so the nearest PRECEDING
        # run start is a forward cummax and the nearest FOLLOWING run end
        # is a reverse CUMMIN (a reverse cummax would smear the LAST run's
        # end over every earlier run).
        big = jnp.iinfo(jnp.int32).max
        p_at_end = rev(jax.lax.cummin(rev(jnp.where(is_last, p_incl, big))))
        p_at_lo = jax.lax.cummax(jnp.where(run_start, p_incl - is_p, -1))
        hit_s = usable_sorted & s_isbuild & (p_at_end > p_at_lo)
        hit_pack = hit_s.astype(jnp.int64)

    # Route back, both sides in ONE sort: build rows keyed by their global
    # rank (b_incl - 1), probe rows keyed by cap_b + original probe index.
    rank = b_incl - 1
    back_key = jnp.where(s_isbuild, rank.astype(jnp.int64),
                         perm.astype(jnp.int64))  # probe perm >= cap_b
    back_pay = jnp.where(
        s_isbuild,
        perm.astype(jnp.int64) * 2 + hit_pack,
        lo_s.astype(jnp.int64) * (1 << 32) + count_s.astype(jnp.int64))
    _, routed = jax.lax.sort((back_key, back_pay), num_keys=1,
                             is_stable=True)
    build_routed = routed[:cap_b]
    probe_routed = routed[cap_b:]
    build_at_rank = (build_routed >> 1).astype(jnp.int32)
    lo = (probe_routed >> 32).astype(jnp.int32)
    counts = (probe_routed & 0xFFFFFFFF).astype(jnp.int32)
    hits = None
    if need_build_hits:
        hit_by_rank = (build_routed & 1).astype(jnp.bool_)
        hits = jnp.zeros(cap_b, dtype=jnp.bool_).at[build_at_rank].set(
            hit_by_rank, mode="drop")
    return lo, counts, build_at_rank, hits


def sorted_rank_pair(reference: jnp.ndarray, queries: jnp.ndarray
                     ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """For each query q (any order): (count of refs < q, count of refs <= q)
    — ``searchsorted`` left and right — over the whole int64 range, by ONE
    stable merge sort and one route-back sort. ``reference`` must be sorted
    ascending.

    A sort's time on the TPU follows its shape alone. The binary searches
    this replaces gather from the reference log2(n_ref) times a query, and
    a gather out of HBM (a reference too large for VMEM) is both the slowest
    thing the chip does (23-32 ns an element on the v5e against 7 from
    VMEM) and the one whose time differs from process to process with where
    the buffer lies: q13's join read 0.93-1.24 s over four runs of one
    program (PERF.md, PR 34)."""
    n_ref, n_q = reference.shape[0], queries.shape[0]
    total = n_ref + n_q
    key = jnp.concatenate([reference.astype(jnp.int64),
                           queries.astype(jnp.int64)])
    iota = jnp.arange(total, dtype=jnp.int32)
    # Stable: in a run of equal keys the refs (concatenated first) stay
    # ahead of the queries, so a query's inclusive ref prefix counts every
    # ref <= it, and the run's first position every ref < it.
    s_key, s_idx = jax.lax.sort((key, iota), num_keys=1, is_stable=True)
    s_isref = (s_idx < n_ref).astype(jnp.int32)
    ref_incl = jnp.cumsum(s_isref)
    prev = jnp.concatenate([s_key[:1], s_key[:-1]])
    run_start = (s_key != prev) | (iota == 0)
    # refs before the run, broadcast across it (nondecreasing: a cummax
    # over the start-marked values carries each to its run's end)
    lo_run = jax.lax.cummax(jnp.where(run_start, ref_incl - s_isref, -1))
    _, lo, hi = jax.lax.sort((s_idx, lo_run, ref_incl), num_keys=1,
                             is_stable=False)
    return lo[n_ref:], hi[n_ref:]


def join_match_sorted_build(build_key: DeviceColumn, probe_key: DeviceColumn,
                            live_b: jnp.ndarray, live_p: jnp.ndarray
                            ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Single non-string, non-float equi-key path: sort the build side with
    its row numbers, then rank every probe key among the sorted build keys
    (:func:`sorted_rank_pair`) for its [lo, hi) match range. Three sorts
    with two or three operands each, no gather out of the build side.

    Returns (lo, counts, build_at_rank) with the same contract as
    :func:`join_match`. Null/dead build rows carry an INT64_MAX sentinel
    and sort to the tail; ranks clamp to the usable-build count so a real
    INT64_MAX probe key cannot match them.
    """
    cap_b = build_key.capacity
    kb, _ = orderable_key(build_key)
    kp, _ = orderable_key(probe_key)
    usable_b = live_b & build_key.validity
    sentinel = jnp.iinfo(jnp.int64).max
    kb = jnp.where(usable_b, kb.astype(jnp.int64), sentinel)
    n_usable = jnp.sum(usable_b.astype(jnp.int32))
    # A genuine Long.MaxValue key collides with the sentinel; the usable
    # flag as a SECONDARY sort key puts real MAX-keyed rows before every
    # unusable row, which the n_usable clamp below then relies on.
    sorted_kb, _, build_at_rank = jax.lax.sort(
        (kb, jnp.where(usable_b, 0, 1).astype(jnp.int8),
         jnp.arange(cap_b, dtype=jnp.int32)), num_keys=2,
        is_stable=True)
    lo, hi = sorted_rank_pair(sorted_kb, kp.astype(jnp.int64))
    lo = jnp.minimum(lo, n_usable)
    hi = jnp.minimum(hi, n_usable)
    usable_p = live_p & probe_key.validity
    counts = jnp.where(usable_p, hi - lo, 0).astype(jnp.int32)
    return lo, counts, build_at_rank


#: Direct-address table size = build capacity x this factor. Dimension
#: surrogate keys are dense 0..n-1, so 4x covers filtered builds whose key
#: range exceeds their live count.
_DENSE_TABLE_FACTOR = 4


def _table_build_probe(slot: jnp.ndarray, pslot: jnp.ndarray, tbl: int,
                       cap_b: int) -> Tuple[jnp.ndarray, jnp.ndarray,
                                            jnp.ndarray]:
    """The direct-address table inner path shared by :func:`dense_join`
    and :func:`dense_join_swapped`: build the (count, first-row) table
    over ``slot`` (pre-sentineled to ``tbl`` for unusable rows) and probe
    it at ``pslot``. Returns ``(cnt_at_probe, row_at_probe, dup)`` where
    ``dup`` is the duplicate-build-key flag ``any(cnt_tbl > 1)``.

    Two XLA segment scatters + two full HBM gathers."""
    ok = slot < tbl
    cnt_tbl = jax.ops.segment_sum(ok.astype(jnp.int32), slot,
                                  num_segments=tbl + 1)[:tbl]
    iota_b = jnp.arange(slot.shape[0], dtype=jnp.int32)
    row_tbl = jax.ops.segment_min(jnp.where(ok, iota_b, cap_b), slot,
                                  num_segments=tbl + 1)[:tbl]
    return cnt_tbl[pslot], row_tbl[pslot], jnp.any(cnt_tbl > 1)


def dense_joinable(jt: str, keys) -> bool:
    """Static eligibility for the direct-address join: probe-preserving
    join type + a single fixed-width integer equi key (``keys`` are bound
    EXPRESSIONS — this check runs before any column exists). Runtime
    conditions (unique usable build keys inside the table range) are
    checked on device and reported through the dense-fail flag."""
    from ... import types as T
    if jt not in ("inner", "left", "left_semi", "left_anti") \
            or len(keys) != 1:
        return False
    dt = keys[0].data_type
    return dt is not T.STRING and not dt.is_floating \
        and not isinstance(dt, (T.ArrayType, T.StructType))


def dense_join_swapped(probe, build, pk: DeviceColumn, bk: DeviceColumn,
                       out_schema):
    """INNER-join dense mode 2: the PROBE side's keys are unique, so the
    table builds over the probe and every BUILD row gathers its (single)
    probe match — the dim.join(fact) shape where the huge fact sits on
    the build side. Output at BUILD capacity, lazy, probe columns first
    (schema order preserved). The table inner path (build + probe) runs
    through :func:`_table_build_probe`."""
    from ...data.batch import ColumnarBatch
    cap_p = pk.capacity
    tbl = cap_p * _DENSE_TABLE_FACTOR
    live_p = probe.row_mask()
    usable_p = live_p & pk.validity
    kp = pk.data.astype(jnp.int64)
    in_range_p = (kp >= 0) & (kp < tbl)
    ok_p = usable_p & in_range_p
    slot = jnp.where(ok_p, kp, tbl).astype(jnp.int32)

    live_b = build.row_mask()
    usable_b = live_b & bk.validity
    kb = bk.data.astype(jnp.int64)
    in_range_b = usable_b & (kb >= 0) & (kb < tbl)
    bslot = jnp.where(in_range_b, kb, 0).astype(jnp.int32)

    cnt_b, row_b, dup = _table_build_probe(slot, bslot, tbl, cap_p)
    fail = jnp.any(usable_p & ~in_range_p) | dup
    matched = in_range_b & (cnt_b > 0)
    probe_row = jnp.clip(row_b, 0, cap_p - 1)
    from .rowops import gather_columns
    pcols = gather_columns(probe.columns, probe_row, matched)
    return ColumnarBatch(pcols + tuple(build.columns),
                         jnp.sum(matched.astype(jnp.int32)), out_schema,
                         live=matched), fail


def dense_join(jt: str, probe, build, pk: DeviceColumn, bk: DeviceColumn,
               out_schema):
    """Direct-address (perfect-hash) equi join for UNIQUE integer build
    keys — the fact-to-dimension shape that dominates TPC-H/DS/xBB.

    Scatter build row ids into a table indexed by key value, then every
    probe row's match is two gathers — no ``lax.sort`` and no
    ``searchsorted``, both of which are order-of-magnitude slower than a
    memory pass on XLA (CPU: a 1M-row sort ~850ms, searchsorted ~450ms,
    vs ~20ms per gather). The output stays LAZY at probe capacity (live =
    match mask), so no compaction pass is paid either; with unique build
    keys the output can never exceed the probe row count, so this path
    cannot overflow. The table build + probe gathers run through
    :func:`_table_build_probe`.

    Returns ``(out_batch, fail)`` where ``fail`` is a traced bool: build
    keys were duplicated or out of table range — the caller's retry
    machinery re-runs the site with the general kernel (ctx.no_dense).
    """
    from ...data.batch import ColumnarBatch
    cap_b = bk.capacity
    tbl = cap_b * _DENSE_TABLE_FACTOR
    live_b = build.row_mask()
    usable_b = live_b & bk.validity
    kb = bk.data.astype(jnp.int64)
    in_range_b = (kb >= 0) & (kb < tbl)
    ok_b = usable_b & in_range_b
    slot = jnp.where(ok_b, kb, tbl).astype(jnp.int32)

    live_p = probe.row_mask()
    usable_p = live_p & pk.validity
    kp = pk.data.astype(jnp.int64)
    in_range_p = usable_p & (kp >= 0) & (kp < tbl)
    pslot = jnp.where(in_range_p, kp, 0).astype(jnp.int32)

    cnt_p, row_p, dup = _table_build_probe(slot, pslot, tbl, cap_b)
    # semi/anti only test MEMBERSHIP — duplicate build keys are fine
    # there (the fact-side build of an EXISTS), and only out-of-range
    # keys disqualify the table.
    fail = jnp.any(usable_b & ~in_range_b)
    if jt in ("inner", "left"):
        fail = fail | dup
    matched = in_range_p & (cnt_p > 0)

    if jt == "left_semi":
        keep = matched
        return ColumnarBatch(probe.columns,
                             jnp.sum(keep.astype(jnp.int32)), out_schema,
                             live=keep), fail
    if jt == "left_anti":
        keep = live_p & ~matched
        return ColumnarBatch(probe.columns,
                             jnp.sum(keep.astype(jnp.int32)), out_schema,
                             live=keep), fail
    build_row = jnp.clip(row_p, 0, cap_b - 1)
    bvalid = matched
    from .rowops import gather_columns
    bcols = gather_columns(build.columns, build_row, bvalid)
    keep = matched if jt == "inner" else live_p
    return ColumnarBatch(tuple(probe.columns) + bcols,
                         jnp.sum(keep.astype(jnp.int32)), out_schema,
                         live=keep), fail


def single_key_joinable(key: DeviceColumn) -> bool:
    """True when a key column qualifies for the single-key sorted-build
    join path: fixed-width, non-string (dictionary codes are not comparable
    across two independently-built dictionaries), non-float (NaN
    normalization needs the bucket operand the packed path can't carry)."""
    return (not key.is_string) and not key.dtype.is_floating


def expand_matches_binsearch(lo: jnp.ndarray, counts: jnp.ndarray,
                             build_at_rank: jnp.ndarray, out_capacity: int
                             ) -> Tuple[jnp.ndarray, jnp.ndarray,
                                        jnp.ndarray, jnp.ndarray]:
    """Materialize (probe_idx, build_idx) pairs for all matches via binary
    search over the cumulative counts (no sort: ``offsets`` is already
    sorted, so slot->probe routing is a searchsorted, log2(cap_p) gather
    rounds instead of two more full sorts).

    Returns (probe_idx[out_cap], build_idx[out_cap], n_out, total); total
    may exceed out_capacity — caller re-runs bigger."""
    offsets = jnp.cumsum(counts)
    total = offsets[-1]
    starts = offsets - counts
    k = jnp.arange(out_capacity, dtype=jnp.int32)
    probe_idx = jnp.searchsorted(offsets, k, side="right").astype(jnp.int32)
    safe_probe = jnp.clip(probe_idx, 0, counts.shape[0] - 1)
    within = k - starts[safe_probe]
    build_rank = lo[safe_probe] + within
    build_idx = build_at_rank[
        jnp.clip(build_rank, 0, build_at_rank.shape[0] - 1)]
    n_out = jnp.minimum(total, out_capacity)
    return safe_probe, build_idx, n_out.astype(jnp.int32), total


def merge_rank_pair(reference: jnp.ndarray, queries: jnp.ndarray
                    ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """For each query q: (count of refs < q, count of refs <= q) in ONE
    merge. ``reference`` must be sorted ascending.

    Two ``lax.sort`` passes total (merge + route-back) instead of the four a
    pair of :func:`merge_rank` calls costs; the within-run bookkeeping is
    segmented prefix scans, which are effectively free on TPU (bandwidth
    bound, no reordering)."""
    n_ref, n_q = reference.shape[0], queries.shape[0]
    total = n_ref + n_q
    ids = jnp.concatenate([reference, queries]).astype(jnp.int64)
    is_ref = jnp.concatenate([jnp.ones(n_ref, jnp.int32),
                              jnp.zeros(n_q, jnp.int32)])
    qidx = jnp.concatenate([jnp.zeros(n_ref, jnp.int32),
                            jnp.arange(n_q, dtype=jnp.int32)])
    # Operands PACK into two int64 lanes: TPU compile cost explodes with
    # sort operand count, and (id, side) ordering == (2*id + side)
    # ordering. refs sort before queries within an equal-value run.
    side = 1 - is_ref
    key = ids * 2 + side.astype(jnp.int64)
    pay = qidx.astype(jnp.int64) * 2 + is_ref.astype(jnp.int64)
    s_key, s_pay = jax.lax.sort((key, pay), num_keys=1, is_stable=True)
    s_isref = (s_pay & 1).astype(jnp.int32)
    s_qidx = (s_pay >> 1).astype(jnp.int32)
    s_id = s_key >> 1
    iota = jnp.arange(total, dtype=jnp.int32)
    ref_incl = jnp.cumsum(s_isref)  # refs at-or-before pos
    # Because refs precede queries in a run, a query position's inclusive
    # ref prefix already counts every equal ref: hi = ref_incl.
    # lo = refs strictly before the run = (exclusive ref prefix) at run
    # start, broadcast across the run by a cummax over start-marked values.
    prev = jnp.concatenate([s_id[:1], s_id[:-1]])
    run_start = (s_id != prev) | (iota == 0)
    lo_at = ref_incl - s_isref
    # Within a run lo_at is constant at the run start and can only grow as
    # refs accumulate; broadcasting the run-start value = running max of
    # (value where start else -1) ... but lo_at is nondecreasing globally,
    # so the run-start broadcast is simply a cummax of masked values.
    lo_run = jax.lax.cummax(jnp.where(run_start, lo_at, -1))
    # route back: queries (isref=0) first by index, carrying (lo, hi) packed.
    back_key = s_isref.astype(jnp.int64) * (1 << 32) \
        + s_qidx.astype(jnp.int64)
    back_pay = lo_run.astype(jnp.int64) * (1 << 32) + ref_incl.astype(jnp.int64)
    _, got = jax.lax.sort((back_key, back_pay), num_keys=1, is_stable=True)
    lo_q = (got[:n_q] >> 32).astype(jnp.int32)
    hi_q = (got[:n_q] & 0xFFFFFFFF).astype(jnp.int32)
    return lo_q, hi_q


def merge_rank(reference: jnp.ndarray, queries: jnp.ndarray,
               inclusive: bool) -> jnp.ndarray:
    """For each query value q (any order), the count of reference elements
    with r < q (or r <= q when ``inclusive``). ``reference`` must be sorted.
    Computed by the packed two-sort merge of :func:`merge_rank_pair`."""
    lo, hi = merge_rank_pair(reference, queries)
    return hi if inclusive else lo


def match_ranges(build_ids: jnp.ndarray, probe_ids: jnp.ndarray,
                 ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Sort build ids; for each probe row return (lo, hi) in the sorted build
    order plus the sorted->original build permutation."""
    cap_b = build_ids.shape[0]
    iota = jnp.arange(cap_b, dtype=jnp.int32)
    sorted_ids, build_perm = jax.lax.sort(
        (jnp.where(build_ids < 0, jnp.int32(2 ** 31 - 1), build_ids), iota),
        num_keys=1, is_stable=True)
    valid_probe = probe_ids >= 0
    lo, hi = merge_rank_pair(sorted_ids, probe_ids)
    counts = jnp.where(valid_probe, hi - lo, 0).astype(jnp.int32)
    return lo.astype(jnp.int32), counts, build_perm, sorted_ids


def expand_matches(lo: jnp.ndarray, counts: jnp.ndarray,
                   build_perm: jnp.ndarray, out_capacity: int
                   ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Materialize (probe_idx, build_idx) pairs for all matches.

    Returns (probe_idx[out_cap], build_idx[out_cap], n_out, total) where
    ``total`` may exceed out_capacity — caller must check and re-run bigger.
    """
    offsets = jnp.cumsum(counts)
    total = offsets[-1]
    starts = offsets - counts
    k = jnp.arange(out_capacity, dtype=jnp.int32)
    probe_idx = merge_rank(offsets, k, inclusive=True).astype(jnp.int32)
    safe_probe = jnp.clip(probe_idx, 0, counts.shape[0] - 1)
    within = k - starts[safe_probe]
    build_sorted_pos = lo[safe_probe] + within
    build_idx = build_perm[jnp.clip(build_sorted_pos, 0, build_perm.shape[0] - 1)]
    n_out = jnp.minimum(total, out_capacity)
    return safe_probe, build_idx, n_out.astype(jnp.int32), total


def left_outer_counts(counts: jnp.ndarray, valid_probe_live: jnp.ndarray
                      ) -> jnp.ndarray:
    """Left join: unmatched live probe rows still emit one (null-build) row."""
    return jnp.where(valid_probe_live & (counts == 0), 1, counts)


def build_hit_mask(build_ids: jnp.ndarray, sorted_ids: jnp.ndarray,
                   probe_ids: jnp.ndarray, n_probe: jnp.ndarray) -> jnp.ndarray:
    """For full-outer/right joins: which build rows matched >=1 probe row."""
    cap_p = probe_ids.shape[0]
    live_probe = jnp.arange(cap_p, dtype=jnp.int32) < n_probe
    usable = (probe_ids >= 0) & live_probe
    # A build row matched iff its id appears among usable probe ids.
    sorted_pids, _ = jax.lax.sort(
        (jnp.where(usable, probe_ids, jnp.int32(2 ** 31 - 1)),
         jnp.arange(cap_p, dtype=jnp.int32)), num_keys=1, is_stable=True)
    pos = jnp.searchsorted(sorted_pids, build_ids, side="left")
    found = sorted_pids[jnp.clip(pos, 0, cap_p - 1)] == build_ids
    return found & (build_ids >= 0)
