"""Row-rearrangement kernels: gather, compact (filter), multi-key sort.

These replace libcudf's ``Table.filter`` / ``gather`` / ``Table.sort`` (the
reference reaches them through the cudf JNI, e.g.
``basicPhysicalOperators.scala:127`` for filter) with XLA-native equivalents:

* **compact**: a stable argsort of the drop-mask moves kept rows to the
  front — no dynamic shapes; the live-row count shrinks instead.
* **multi-key sort**: ``lax.sort`` with one operand per key. Float keys are
  transformed to order-preserving int bit patterns so NaN ordering and
  -0.0 == 0.0 match Spark; nulls order via an explicit validity key.
* **string gather** rebuilds offsets+payload through the char matrix.

Everything here is traced (jit-safe): static capacities, dynamic row counts.
"""

from __future__ import annotations

from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ... import types as T
from ...data.batch import ColumnarBatch
from ...data.column import (DeviceColumn, bucket_byte_capacity,
                            bucket_capacity)
from ..strings_util import PAD, char_matrix


def orderable_values(data: jnp.ndarray, is_floating: bool) -> jnp.ndarray:
    """Monotone int64 transform of a raw value array: ascending int order of
    the result equals SQL ascending order of the values (NaN last, -0 == 0)."""
    if is_floating:
        if data.dtype == jnp.float32:
            bits = data.view(jnp.int32).astype(jnp.int64)
        else:
            bits = data.view(jnp.int64)
        # Canonicalize NaN and -0.0 so grouping equality matches Spark
        # (FloatUtils.scala:84 does the same normalization on GPU).
        canon_nan = jnp.int64(0x7FF8000000000000 if data.dtype == jnp.float64
                              else 0x7FC00000)
        bits = jnp.where(jnp.isnan(data), canon_nan, bits)
        bits = jnp.where(data == 0, jnp.int64(0), bits)
        # IEEE total-order trick: negatives map (order-reversed) below zero,
        # positives keep their bit order. Wrapping int64 add is intended.
        int64_min = jnp.int64(-0x8000000000000000)
        return jnp.where(bits < 0, ~bits + int64_min, bits)
    return data.astype(jnp.int64)


def orderable_key(col: DeviceColumn, ascending: bool = True,
                  nulls_first: bool = True) -> jnp.ndarray:
    """(key, bucket) whose lexicographic (bucket, key) ascending order is
    the requested SQL order.

    Floats stay FLOAT: feeding a float->int bitcast into ``lax.sort``
    crashes this TPU toolchain's compiler, so NaN ordering (greatest, per
    Spark) and null placement ride the BUCKET instead: nulls are +/-3, NaN
    +/-2 (descending puts NaN first), plain values 0. -0.0 canonicalizes
    to 0.0 and NaN keys zero so (bucket, key) equality == Spark grouping
    equality. Callers MUST use the bucket as a more-significant sort
    operand than the key."""
    assert not col.is_string, "string sort keys expand via string_sort_keys"
    if col.dtype.is_floating:
        v = col.data
        nan = jnp.isnan(v)
        v = jnp.where(nan, jnp.zeros((), v.dtype), v)
        v = jnp.where(v == 0, jnp.zeros((), v.dtype), v)
        key = v if ascending else -v
        bucket = jnp.where(nan, 2 if ascending else -2, 0)
        bucket = jnp.where(col.validity, bucket, -3 if nulls_first else 3)
        return key, bucket.astype(jnp.int8)
    key = col.data
    if not ascending:
        key = ~key  # bitwise NOT reverses order with no overflow
    null_bucket = jnp.where(col.validity, 0, -3 if nulls_first else 3)
    return key, null_bucket.astype(jnp.int8)


def string_sort_keys(col: DeviceColumn, ascending: bool = True,
                     nulls_first: bool = True) -> List[jnp.ndarray]:
    """Sort operands for a string column.

    Sorted-dictionary columns sort by their int32 CODES (code order ==
    byte order by construction) — one narrow operand. Anything else
    expands to per-char int16 operands."""
    null_bucket = jnp.where(col.validity, 0, -1 if nulls_first else 1)
    if col.is_dict and col.dict_sorted:
        key = jnp.where(col.validity, col.codes, 0)
        if not ascending:
            key = -key - 1
        return [null_bucket.astype(jnp.int8), key]
    m = char_matrix(col)
    cols = [m[:, i] for i in range(m.shape[1])]
    if not ascending:
        cols = [-(c.astype(jnp.int32) + 1) for c in cols]
    return [null_bucket.astype(jnp.int8)] + cols


def sort_permutation(keys: Sequence[DeviceColumn], n_rows: jnp.ndarray,
                     ascending: Optional[Sequence[bool]] = None,
                     nulls_first: Optional[Sequence[bool]] = None) -> jnp.ndarray:
    """Stable permutation ordering live rows by the given keys; dead rows sink
    to the end. Returns int32[capacity] indices."""
    capacity = keys[0].capacity
    asc = ascending or [True] * len(keys)
    nf = nulls_first or [True] * len(keys)
    operands: List[jnp.ndarray] = []
    live = jnp.arange(capacity, dtype=jnp.int32) < n_rows
    # Dead rows order after everything.
    operands.append(jnp.where(live, 0, 1).astype(jnp.int8))
    for k, a, n in zip(keys, asc, nf):
        if k.is_string:
            operands.extend(string_sort_keys(k, a, n))
        else:
            key, null_bucket = orderable_key(k, a, n)
            operands.append(null_bucket)
            operands.append(key)
    iota = jnp.arange(capacity, dtype=jnp.int32)
    out = jax.lax.sort(tuple(operands) + (iota,), num_keys=len(operands),
                       is_stable=True)
    return out[-1]


def gather_column(col: DeviceColumn, indices: jnp.ndarray,
                  index_valid: Optional[jnp.ndarray] = None) -> DeviceColumn:
    """Gather rows of ``col`` at ``indices`` (int32[out_capacity]).

    Flat-string rows move through the char matrix."""
    out_cap = indices.shape[0]
    safe = jnp.clip(indices, 0, col.capacity - 1)
    validity = col.validity[safe]
    if index_valid is not None:
        validity = validity & index_valid
    if col.is_struct:
        kids = tuple(gather_column(c, indices, index_valid)
                     for c in col.children)
        return DeviceColumn(data=None, validity=validity, dtype=col.dtype,
                            children=kids)
    if col.is_array:
        # Padded-ragged layout: a 2D row gather moves whole arrays.
        emask = col.elem_validity[safe] & validity[:, None]
        data = jnp.where(emask, col.data[safe],
                         jnp.zeros((), col.data.dtype))
        lengths = jnp.where(validity, col.lengths[safe], 0)
        return DeviceColumn(data=data, validity=validity, dtype=col.dtype,
                            elem_validity=emask, lengths=lengths)
    if not col.is_string:
        data = jnp.where(validity, col.data[safe], jnp.zeros((), col.data.dtype))
        return DeviceColumn(data=data, validity=validity, dtype=col.dtype)
    if col.is_dict:
        # Move one int32 lane; the dictionary rides along untouched.
        codes = jnp.where(validity, col.codes[safe], 0)
        return col.replace_rows(validity, codes=codes)
    # Flat strings: gather rows of the char matrix, rebuild offsets+payload.
    m = char_matrix(col)[safe]  # [out_cap, W]
    m = jnp.where(validity[:, None], m, PAD)
    return strings_from_matrix(m, validity, col.max_bytes)


def strings_from_matrix(m: jnp.ndarray, validity: jnp.ndarray,
                        max_bytes: int) -> DeviceColumn:
    """Rebuild (offsets, payload) from a char matrix (PAD-terminated rows).

    Kept chars in row-major order ARE the payload (offsets are cumulative in
    row order, chars in-row are ordered), so one stable sort compacting
    non-PAD chars to the front replaces the scatter this used to do — XLA
    scatters at [capacity x W] scale cost seconds on TPU, sorts tens of ms.
    """
    out_cap, w = m.shape
    flat = m.reshape(-1)
    lens = jnp.sum((flat != PAD).reshape(out_cap, w).astype(jnp.int32),
                   axis=1)
    offsets = jnp.concatenate([jnp.zeros(1, jnp.int32),
                               jnp.cumsum(lens).astype(jnp.int32)])
    total_bytes = offsets[-1]
    byte_cap = bucket_byte_capacity(out_cap * w)
    drop = (flat == PAD).astype(jnp.int8)
    _, sorted_chars = jax.lax.sort((drop, flat), num_keys=1, is_stable=True)
    kept = jnp.pad(sorted_chars, (0, byte_cap - sorted_chars.shape[0]))
    live_byte = jnp.arange(byte_cap, dtype=jnp.int32) < total_bytes
    payload = jnp.where(live_byte, kept, 0).astype(jnp.uint8)
    return DeviceColumn(data=payload, validity=validity, dtype=T.STRING,
                        offsets=offsets, max_bytes=max_bytes)


def gather_columns(columns, indices: jnp.ndarray,
                   index_valid: Optional[jnp.ndarray] = None) -> tuple:
    """Gather rows of MANY columns at once: fixed-width/dict lanes stack
    by dtype and move with ONE 2D gather per dtype (plus one for the bool
    validity lanes) instead of one kernel launch per column — the TPU
    runtime charges ~7ms per launch at 1M rows, which dominated wide join
    outputs and compactions. Complex columns (structs, arrays, flat
    strings) keep the per-column path."""
    out: list = [None] * len(columns)
    simple = [i for i, c in enumerate(columns)
              if not (c.is_struct or c.is_array
                      or (c.is_string and not c.is_dict))]
    if len(simple) >= 2:
        cap = columns[simple[0]].capacity
        safe = jnp.clip(indices, 0, cap - 1)
        vstack = jnp.stack([columns[i].validity for i in simple], axis=1)
        gv = vstack[safe]
        if index_valid is not None:
            gv = gv & index_valid[:, None]
        by_dt: dict = {}
        for j, i in enumerate(simple):
            c = columns[i]
            lane = c.codes if c.is_dict else c.data
            by_dt.setdefault(lane.dtype.name, []).append((j, i, lane))
        for entries in by_dt.values():
            if len(entries) == 1:
                j, i, lane = entries[0]
                g = lane[safe]
                gs = [g]
            else:
                st = jnp.stack([lane for _, _, lane in entries], axis=1)
                g2 = st[safe]
                gs = [g2[:, k] for k in range(len(entries))]
            for (j, i, _), g in zip(entries, gs):
                c = columns[i]
                v = gv[:, j]
                d = jnp.where(v, g, jnp.zeros((), g.dtype))
                if c.is_dict:
                    out[i] = c.replace_rows(v, codes=d)
                else:
                    out[i] = DeviceColumn(data=d, validity=v, dtype=c.dtype)
    for i, c in enumerate(columns):
        if out[i] is None:
            out[i] = gather_column(c, indices, index_valid)
    return tuple(out)


def gather_batch(batch: ColumnarBatch, indices: jnp.ndarray,
                 new_n_rows: jnp.ndarray,
                 index_valid: Optional[jnp.ndarray] = None) -> ColumnarBatch:
    out_cap = indices.shape[0]
    live = jnp.arange(out_cap, dtype=jnp.int32) < new_n_rows
    iv = live if index_valid is None else (index_valid & live)
    cols = gather_columns(batch.columns, indices, iv)
    return ColumnarBatch(cols, new_n_rows.astype(jnp.int32), batch.schema)


#: Max extra sort operands before switching from payload-carrying to
#: argsort + gathers. Carrying saves a full gather pass per column at run
#: time, but TPU compile cost grows superlinearly with sort operand count
#: (2-operand 1M sort ~20s, 18-operand ~15min on the remote helper).
_CARRY_LIMIT = 4


def _permute_by_sort(batch: ColumnarBatch, key_operands: List[jnp.ndarray],
                     new_n_rows: jnp.ndarray) -> ColumnarBatch:
    """Reorder a batch by sorting on ``key_operands``. Narrow batches carry
    their buffers through the sort (zero extra passes); wide ones sort a
    permutation and gather (bounded compile cost — see _CARRY_LIMIT)."""
    cap = batch.capacity
    live_out = jnp.arange(cap, dtype=jnp.int32) < new_n_rows
    payload: List[jnp.ndarray] = []
    carried = []  # (col index, is_dict)
    has_flat_strings = any((c.is_string and not c.is_dict) or c.is_complex
                           for c in batch.columns)
    for i, c in enumerate(batch.columns):
        if c.is_complex:
            pass  # complex columns always go through the gather path
        elif not c.is_string:
            payload.append(c.data)
            payload.append(c.validity)
            carried.append((i, False))
        elif c.is_dict:
            # Dict strings ride the sort as their int32 code lane.
            payload.append(c.codes)
            payload.append(c.validity)
            carried.append((i, True))
    if has_flat_strings or len(payload) > _CARRY_LIMIT:
        # Wide batch: permutation sort + per-column gathers.
        sorted_all = jax.lax.sort(
            tuple(key_operands) + (jnp.arange(cap, dtype=jnp.int32),),
            num_keys=len(key_operands), is_stable=True)
        perm = sorted_all[-1]
        cols = gather_columns(batch.columns, perm, live_out)
        return ColumnarBatch(cols, new_n_rows.astype(jnp.int32),
                             batch.schema)
    sorted_all = jax.lax.sort(tuple(key_operands) + tuple(payload),
                              num_keys=len(key_operands), is_stable=True)
    out = list(sorted_all[len(key_operands):])
    cols: List[Optional[DeviceColumn]] = [None] * len(batch.columns)
    for j, (i, is_dict) in enumerate(carried):
        data, validity = out[2 * j], out[2 * j + 1]
        validity = validity & live_out
        data = jnp.where(validity, data, jnp.zeros((), data.dtype))
        if is_dict:
            cols[i] = batch.columns[i].replace_rows(validity, codes=data)
        else:
            cols[i] = DeviceColumn(data=data, validity=validity,
                                   dtype=batch.columns[i].dtype)
    return ColumnarBatch(tuple(cols), new_n_rows.astype(jnp.int32),
                         batch.schema)


def compact(batch: ColumnarBatch, keep: jnp.ndarray) -> ColumnarBatch:
    """Filter: LAZY — record the kept-row mask instead of physically
    moving rows (a full sort-based compaction, the dominant cost of
    filter-heavy plans). ``n_rows`` becomes the traced live COUNT;
    mask-native consumers read ``row_mask()``, positional ones call
    :func:`physical` first."""
    keep = keep & batch.row_mask()
    n_kept = jnp.sum(keep.astype(jnp.int32))
    return ColumnarBatch(batch.columns, n_kept, batch.schema, live=keep)


def physical(batch: ColumnarBatch) -> ColumnarBatch:
    """Materialize a lazily-filtered batch: live rows move to the front,
    ``live`` clears. No-op when already physical.

    Scatter-compact, NOT a sort: ``pos = cumsum(live) - 1`` gives each
    live row its output slot, one int scatter builds the gather map, and
    every column moves with one gather — a few memory passes instead of
    an O(n log n) ``lax.sort`` (~10x cheaper at 1M rows on CPU XLA; the
    same ratio holds on TPU). Relative order of live rows is preserved
    (pos is monotone)."""
    if batch.live is None:
        return batch
    cap = batch.capacity
    live = batch.live
    pos = jnp.cumsum(live.astype(jnp.int32)) - 1
    iota = jnp.arange(cap, dtype=jnp.int32)
    scatter_idx = jnp.where(live, pos, cap)
    src_idx = jnp.zeros(cap, jnp.int32).at[scatter_idx].set(
        iota, mode="drop")
    live_out = iota < batch.n_rows
    cols = gather_columns(batch.columns, src_idx, live_out)
    return ColumnarBatch(cols, batch.n_rows.astype(jnp.int32),
                         batch.schema)


@jax.jit
def _physical_kernel(batch: ColumnarBatch) -> ColumnarBatch:
    return physical(batch)


def physical_jit(batch: ColumnarBatch) -> ColumnarBatch:
    """Eager-context physical(): jitted (cached per treedef/avals) so host
    callers like ``to_arrow`` don't pay op-by-op dispatch."""
    if batch.live is None:
        return batch
    return _physical_kernel(batch)


def sort_batch_by_columns(batch: ColumnarBatch,
                          keys: Sequence[DeviceColumn],
                          ascending: Sequence[bool],
                          nulls_first: Sequence[bool]) -> ColumnarBatch:
    """Sort a batch by evaluated key columns, carrying payload through the
    one sort (see :func:`_permute_by_sort`). Lazy-filtered inputs are
    handled natively: their scattered dead rows sink to the tail through
    the same dead-row operand, so no separate compaction pass is paid."""
    capacity = batch.capacity
    live = batch.row_mask()
    operands: List[jnp.ndarray] = [jnp.where(live, 0, 1).astype(jnp.int8)]
    for k, a, n in zip(keys, ascending, nulls_first):
        if k.is_string:
            operands.extend(string_sort_keys(k, a, n))
        else:
            key, null_bucket = orderable_key(k, a, n)
            operands.append(null_bucket)
            operands.append(key)
    return _permute_by_sort(batch, operands, batch.n_rows)


def sort_batch(batch: ColumnarBatch, key_ordinals: Sequence[int],
               ascending: Sequence[bool], nulls_first: Sequence[bool]) -> ColumnarBatch:
    keys = [batch.columns[i] for i in key_ordinals]
    return sort_batch_by_columns(batch, keys, ascending, nulls_first)


def _topk_single_lane(key: DeviceColumn, ascending: bool,
                      nulls_first: bool, live: jnp.ndarray
                      ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """(enc, ok) for the single-key top-k path: one FLOAT64 lane whose
    DESCENDING order equals the requested SQL order.

    float64, not int64, because ``lax.top_k`` on f64 runs at memory
    bandwidth while int64 falls off a cliff on XLA (measured 2ms vs
    444ms at 1M). Rank layers, strictly separated finite sentinels:
    dead -1e308 < nulls-last -1e307 < NaN-last -1e306 < values (|v| <=
    1e305 guarded) < NaN-first +1e306 < nulls-first +1e307. ``ok`` is
    the Python literal True when the encoding is statically exact
    (<=32-bit ints, dates, bools, dict codes); otherwise a device bool
    that is False when a live value can't ride the lane exactly —
    floats at |v| > 1e305 or +/-inf (would collide with the NaN/null
    layers), 64-bit ints beyond f64's exact-integer range — and the
    caller must take the always-exact sort path."""
    valid = key.validity
    if key.is_dict:
        vf = key.codes.astype(jnp.float64)
        ok = True  # int32 codes are always f64-exact
        nan = None
    elif key.dtype.is_floating:
        v = key.data.astype(jnp.float64)
        nan = jnp.isnan(v)
        ok = ~(live & valid & ~nan
               & (jnp.abs(v) > 1e305)).any()
        vf = jnp.where(nan, 0.0, v)
    else:
        vf = key.data.astype(jnp.float64)
        nan = None
        if key.data.dtype in (jnp.int64, jnp.uint64):
            exact = vf.astype(key.data.dtype) == key.data
            ok = ~(live & valid & ~exact).any()
        else:
            ok = True  # static: callers skip the host sync entirely
    enc = -vf if ascending else vf
    if nan is not None:
        # Spark: NaN orders greatest — desc puts it first (below nulls
        # when nulls_first), asc puts it last (above nulls when
        # nulls_last)
        enc = jnp.where(nan, -1e306 if ascending else 1e306, enc)
    enc = jnp.where(valid, enc, 1e307 if nulls_first else -1e307)
    enc = jnp.where(live, enc, -1e308)
    return enc, ok


def topk_batch_by_columns(batch: ColumnarBatch,
                          keys: Sequence[DeviceColumn],
                          ascending: Sequence[bool],
                          nulls_first: Sequence[bool],
                          k: int,
                          allow_data_fallback: bool = True
                          ) -> Tuple[ColumnarBatch, jnp.ndarray]:
    """First ``k`` rows of the batch in sort order, in a k-sized capacity
    bucket — the limit-into-sort fast path (the reference reaches the
    same shape via cudf's partial-sort behind GpuSortExec.scala:50 +
    GpuCollectLimitExec).

    Two tiers, both exact and stable (``lax.top_k`` prefers lower
    indices on ties):

    * single orderable key (numeric/date/bool/sorted-dict string): one
      int64 encoding + ``lax.top_k`` — O(n log k), no payload carriage;
    * otherwise: keys-only ``lax.sort`` of (dead, key operands, iota),
      slice the first k positions, gather — still skips carrying the
      payload through the sort.

    Returns ``(batch, ok)``; ``ok=False`` (single-key path only, 64-bit
    int sentinel collision) means the result is unusable and the caller
    must take the full-sort path.
    """
    cap = batch.capacity
    kcap = bucket_capacity(max(k, 1))
    live = batch.row_mask()
    n_out = jnp.minimum(batch.n_rows, jnp.int32(k))
    live_out = jnp.arange(kcap, dtype=jnp.int32) < n_out
    k_take = min(kcap, cap)
    single = len(keys) == 1 and not keys[0].is_complex and (
        not keys[0].is_string or (keys[0].is_dict and keys[0].dict_sorted))
    if single and not allow_data_fallback and not keys[0].is_string and (
            keys[0].dtype.is_floating
            or keys[0].data.dtype in (jnp.int64, jnp.uint64)):
        # float/64-bit-int keys have a data-dependent exactness flag;
        # when the caller can't host-check it (fusion tracing), take the
        # sort path instead.
        single = False
    if single:
        enc, ok = _topk_single_lane(keys[0], ascending[0], nulls_first[0],
                                    live)
        _, idx = jax.lax.top_k(enc, k_take)
    else:
        operands: List[jnp.ndarray] = [
            jnp.where(live, 0, 1).astype(jnp.int8)]
        for key, a, n in zip(keys, ascending, nulls_first):
            if key.is_string:
                operands.extend(string_sort_keys(key, a, n))
            else:
                kv, bucket = orderable_key(key, a, n)
                operands.append(bucket)
                operands.append(kv)
        sorted_all = jax.lax.sort(
            tuple(operands) + (jnp.arange(cap, dtype=jnp.int32),),
            num_keys=len(operands), is_stable=True)
        idx = sorted_all[-1][:k_take]
        ok = True  # sort path is always exact
    if k_take < kcap:  # tiny inputs: pad indices up to the output bucket
        idx = jnp.concatenate(
            [idx, jnp.zeros(kcap - k_take, dtype=idx.dtype)])
    cols = gather_columns(batch.columns, idx.astype(jnp.int32), live_out)
    return ColumnarBatch(cols, n_out.astype(jnp.int32), batch.schema), ok
