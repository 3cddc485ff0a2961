"""Device batch concatenation — the ``Table.concatenate`` replacement used by
coalescing (reference GpuCoalesceBatches.scala:21,502) and build-side assembly.

Traced implementation: every input batch is physical (live rows are a
prefix), so each of its row-indexed lanes is *placed* whole, by one
``lax.dynamic_update_slice`` at the batch's dynamic cumulative row offset,
batches in order: the next batch overwrites the masked dead tail of the one
before. A fixed list of input capacities compiles to one program regardless
of live counts, and the copy runs at memory speed (a scatter of 64-bit lanes
runs serially on the TPU). The start of a slice never clamps because the
output holds at least the sum of the input capacities. Strings route through
the char matrix and rebuild offsets."""

from __future__ import annotations

from typing import List

import jax
import jax.numpy as jnp

from ... import types as T
from ...data.batch import ColumnarBatch
from ...data.column import DeviceColumn, bucket_capacity
from ..strings_util import PAD, char_matrix
from .rowops import strings_from_matrix


def _place(out: jnp.ndarray, lanes, n_rows_list) -> jnp.ndarray:
    """Write each lane (rows along axis 0, its tail past ``n`` rows already
    ``out``'s fill value) into ``out`` at the running sum of the ``n``."""
    if out.shape[0] < sum(lane.shape[0] for lane in lanes):
        raise ValueError(
            f"concat output of {out.shape[0]} rows is smaller than the sum "
            f"of the input capacities {[lane.shape[0] for lane in lanes]}")
    zero = offset = jnp.zeros((), jnp.int32)
    tail = (zero,) * (out.ndim - 1)
    for lane, n in zip(lanes, n_rows_list):
        out = jax.lax.dynamic_update_slice(out, lane.astype(out.dtype),
                                           (offset,) + tail)
        offset = offset + n
    return out


def concat_columns(cols: List[DeviceColumn], n_rows_list, out_capacity: int,
                   total_rows) -> DeviceColumn:
    dtype = cols[0].dtype
    live_out = jnp.arange(out_capacity, dtype=jnp.int32) < total_rows
    lives = [jnp.arange(c.capacity, dtype=jnp.int32) < n
             for c, n in zip(cols, n_rows_list)]
    out_valid = _place(jnp.zeros(out_capacity, dtype=jnp.bool_),
                       [c.validity & live for c, live in zip(cols, lives)],
                       n_rows_list) & live_out
    if cols[0].is_struct:
        kids = tuple(
            concat_columns([c.children[k] for c in cols], n_rows_list,
                           out_capacity, total_rows)
            for k in range(len(cols[0].children)))
        return DeviceColumn(data=None, validity=out_valid, dtype=dtype,
                            children=kids)
    if cols[0].is_array:
        w = max(c.max_len for c in cols)
        pads = [((0, 0), (0, w - c.max_len)) for c in cols]
        out_data = _place(
            jnp.zeros((out_capacity, w), dtype=dtype.np_dtype),
            [jnp.pad(c.data, pad) for c, pad in zip(cols, pads)],
            n_rows_list)
        out_emask = _place(
            jnp.zeros((out_capacity, w), dtype=jnp.bool_),
            [jnp.pad(c.elem_validity, pad) & live[:, None]
             for c, pad, live in zip(cols, pads, lives)], n_rows_list)
        out_lens = _place(
            jnp.zeros(out_capacity, dtype=jnp.int32),
            [jnp.where(live & c.validity, c.lengths, 0)
             for c, live in zip(cols, lives)], n_rows_list)
        out_emask = out_emask & out_valid[:, None]
        return DeviceColumn(
            data=jnp.where(out_emask, out_data, jnp.zeros((), out_data.dtype)),
            validity=out_valid, dtype=dtype, elem_validity=out_emask,
            lengths=jnp.where(out_valid, out_lens, 0))
    if cols[0].is_string and all(c.is_dict for c in cols):
        return _concat_dict_columns(cols, lives, n_rows_list, out_capacity,
                                    out_valid)
    if cols[0].is_string:
        w = max(max(c.max_bytes for c in cols), 1)
        out_m = _place(
            jnp.full((out_capacity, w), PAD, dtype=jnp.int16),
            [jnp.where(live[:, None], char_matrix(c, w), PAD)
             for c, live in zip(cols, lives)], n_rows_list)
        return strings_from_matrix(
            jnp.where(out_valid[:, None], out_m, PAD), out_valid, w)
    out_data = _place(
        jnp.zeros(out_capacity, dtype=dtype.np_dtype),
        [jnp.where(live & c.validity, c.data, jnp.zeros((), c.data.dtype))
         for c, live in zip(cols, lives)], n_rows_list)
    return DeviceColumn(data=jnp.where(out_valid, out_data, jnp.zeros((), out_data.dtype)),
                        validity=out_valid, dtype=dtype)


def _concat_dict_columns(cols: List[DeviceColumn], lives, n_rows_list,
                         out_capacity: int, out_valid) -> DeviceColumn:
    """Concat dictionary-encoded string columns: place the int32 code
    lanes like fixed-width data and append the dictionaries side by side
    (each dict entry keeps its exact offsets; entries of dict i shift by
    the STATIC byte-capacity prefix, codes by the static dict-size prefix).
    No dedupe — the merged dictionary loses the sorted/unique property, so
    downstream falls back to char-matrix comparisons (still correct)."""
    code_lanes = []
    code_base = 0
    for c, live in zip(cols, lives):
        code_lanes.append(
            jnp.where(live & c.validity, c.codes + code_base, 0))
        code_base += c.dict_size
    out_codes = _place(jnp.zeros(out_capacity, dtype=jnp.int32), code_lanes,
                       n_rows_list)
    out_codes = jnp.where(out_valid, out_codes, 0)
    # Dictionary payloads pack contiguously at their running valid-byte
    # offset (traced): each write's zero-padding tail is overwritten by the
    # next dict's payload, keeping every entry's [offset, next) span exact.
    ends = [c.offsets[-1] for c in cols]
    payload = _place(
        jnp.zeros(sum(c.byte_capacity for c in cols), jnp.uint8),
        [c.data for c in cols], ends)
    pos = jnp.zeros((), jnp.int32)
    offs = []
    for c, end in zip(cols, ends):
        offs.append(c.offsets[:-1] + pos)
        pos = pos + end
    offs.append(pos.reshape(1))
    return DeviceColumn(
        data=payload, validity=out_valid, dtype=cols[0].dtype,
        offsets=jnp.concatenate(offs),
        max_bytes=max(c.max_bytes for c in cols),
        codes=out_codes, dict_sorted=False)


def concat_batches(batches: List[ColumnarBatch],
                   out_capacity: int) -> ColumnarBatch:
    """Concatenate device batches (same schema) into one of ``out_capacity``:
    live rows in batch order form the output's prefix, every lane past their
    total is invalid and zero. Caller sizes ``out_capacity >= sum of the
    input capacities`` (static, so no device->host sync; ``_place`` raises
    otherwise): a batch is placed whole, dead tail included."""
    assert batches
    from .rowops import physical
    batches = [physical(b) for b in batches]
    if len(batches) == 1 and batches[0].capacity == out_capacity:
        return batches[0]
    schema = batches[0].schema
    n_list = [b.n_rows for b in batches]
    total = sum(n_list[1:], n_list[0])
    cols = []
    for ci in range(batches[0].num_columns):
        cols.append(concat_columns([b.columns[ci] for b in batches],
                                   n_list, out_capacity, total))
    return ColumnarBatch(tuple(cols), total.astype(jnp.int32), schema)


def worst_case_capacity(batches: List[ColumnarBatch]) -> int:
    return bucket_capacity(sum(b.capacity for b in batches))
