"""Partitioning strategies — GpuHashPartitioning / GpuRangePartitioning /
GpuRoundRobinPartitioning / GpuSinglePartitioning analogs (SURVEY.md §2.6).

Each partitioner produces int32 partition ids for every row; the exchange
turns ids into contiguous per-partition slices. Device ids are computed as
one fused XLA program (the reference calls cudf murmur3/partition kernels,
GpuHashPartitioning.scala:141); range bounds come from deterministic
reservoir sampling like ``GpuRangePartitioner`` + ``SamplingUtils``
(GpuRangePartitioner.scala:237, SamplingUtils.scala:120).
"""

from __future__ import annotations

import dataclasses
from typing import List, Optional, Sequence, Tuple

import jax.numpy as jnp
import numpy as np

from .. import types as T
from ..data.batch import ColumnarBatch, HostBatch
from ..ops.expression import Expression, host_to_array
from ..ops.kernels.rowops import orderable_values
from .partitioning import (pmod_partition, spark_hash_columns_device,
                           spark_hash_columns_host)


class Partitioner:
    """Produces per-row partition ids on device and host."""

    n_parts: int

    def device_ids(self, batch: ColumnarBatch) -> jnp.ndarray:
        raise NotImplementedError

    def host_ids(self, hb: HostBatch) -> np.ndarray:
        raise NotImplementedError


class SinglePartitioner(Partitioner):
    """Everything to partition 0 (GpuSinglePartitioning.scala:61)."""

    def __init__(self):
        self.n_parts = 1

    def device_ids(self, batch):
        return jnp.zeros(batch.capacity, jnp.int32)

    def host_ids(self, hb):
        return np.zeros(hb.num_rows, np.int32)


class RoundRobinPartitioner(Partitioner):
    """Cycle rows over partitions (GpuRoundRobinPartitioning.scala:98).
    ``start`` plays the role of Spark's random per-task start position but is
    deterministic here so CPU/TPU runs distribute identically."""

    def __init__(self, n_parts: int, start: int = 0):
        self.n_parts = n_parts
        self.start = start % n_parts

    def device_ids(self, batch):
        return (jnp.arange(batch.capacity, dtype=jnp.int32) + self.start) \
            % self.n_parts

    def host_ids(self, hb):
        return (np.arange(hb.num_rows, dtype=np.int32) + self.start) \
            % self.n_parts


class HashPartitioner(Partitioner):
    """Spark murmur3 hash pmod n (GpuHashPartitioning.scala:141)."""

    def __init__(self, keys: List[Expression], n_parts: int,
                 child_schema: T.Schema):
        self.n_parts = n_parts
        self._bound = [k.bind(child_schema) for k in keys]

    def device_ids(self, batch):
        cols = [e.eval_device(batch) for e in self._bound]
        h = spark_hash_columns_device(cols)
        return pmod_partition(h, self.n_parts)

    def host_ids(self, hb):
        arrays, dtypes = [], []
        for e in self._bound:
            arr = host_to_array(e.eval_host(hb), hb.num_rows)
            arrays.append(arr)
            dtypes.append(e.data_type)
        h = spark_hash_columns_host(arrays, dtypes)
        return np.asarray(pmod_partition(h, self.n_parts, xp=np))


@dataclasses.dataclass
class RangeBounds:
    """Sampled split points: one tuple of key values per boundary, plus the
    per-key (ascending, nulls_first) directions."""

    rows: List[tuple]  # n_parts - 1 boundary tuples (raw values, None=null)
    ascending: List[bool]
    nulls_first: List[bool]
    dtypes: List[T.DataType]


def sample_range_bounds(sample_rows: List[tuple], n_parts: int,
                        ascending: List[bool], nulls_first: List[bool],
                        dtypes: List[T.DataType]) -> RangeBounds:
    """Pick n_parts-1 evenly spaced boundaries from sorted sample rows
    (the weighted-bounds step of GpuRangePartitioner.createRangeBounds)."""
    import functools

    def cmp_rows(a, b):
        for x, y, asc, nf in zip(a, b, ascending, nulls_first):
            if (x is None) != (y is None):
                c = -1 if (x is None) == nf else 1
            elif x is None or x == y:
                continue
            else:
                c = -1 if x < y else 1
                if not asc:
                    c = -c
            if c:
                return c
        return 0

    ordered = sorted(sample_rows, key=functools.cmp_to_key(cmp_rows))
    bounds = []
    if ordered:
        step = len(ordered) / n_parts
        prev = None
        for i in range(1, n_parts):
            cand = ordered[min(int(step * i), len(ordered) - 1)]
            if prev is None or cmp_rows(cand, prev) != 0:
                bounds.append(cand)
                prev = cand
    return RangeBounds(bounds, ascending, nulls_first, dtypes)


class RangePartitioner(Partitioner):
    """Rows -> partitions by sorted key ranges. Device ids come from one
    vectorized lexicographic [rows x bounds] comparison (bounds are few), the
    TPU replacement for cudf's upper_bound kernel."""

    def __init__(self, keys: List[Expression], bounds: RangeBounds,
                 n_parts: int, child_schema: T.Schema):
        self.n_parts = n_parts
        self.bounds = bounds
        self._bound_exprs = [k.bind(child_schema) for k in keys]

    # -- shared ordering transform ------------------------------------------
    def _key_arrays(self, raw_vals, validity, dtype: T.DataType,
                    ascending: bool, nulls_first: bool, xp):
        if xp is jnp:
            key = orderable_values(raw_vals, dtype.is_floating)
        else:
            key = _np_orderable(raw_vals, dtype)
        if not ascending:
            key = ~key
        bucket = xp.where(validity, 0, -1 if nulls_first else 1)
        return bucket.astype(xp.int8), key

    def _bound_scalars(self, ki: int, xp):
        """(bucket, key) arrays for boundary values of key column ki."""
        dtype = self.bounds.dtypes[ki]
        asc = self.bounds.ascending[ki]
        nf = self.bounds.nulls_first[ki]
        vals = [row[ki] for row in self.bounds.rows]
        validity = np.array([v is not None for v in vals])
        np_dt = dtype.np_dtype
        raw = np.array([0 if v is None else v for v in vals], dtype=np_dt)
        if xp is jnp:
            key = orderable_values(jnp.asarray(raw), dtype.is_floating)
            bucket = jnp.where(jnp.asarray(validity), 0,
                               -1 if nf else 1).astype(jnp.int8)
        else:
            key = _np_orderable(raw, dtype)
            bucket = np.where(validity, 0, -1 if nf else 1).astype(np.int8)
        if not asc:
            key = ~key
        return bucket, key

    def _ids(self, col_cmps, xp, n_rows_cap: int):
        """Combine per-key (gt, eq) [rows x bounds] matrices
        lexicographically into partition ids."""
        nb = len(self.bounds.rows)
        if nb == 0:
            return xp.zeros(n_rows_cap, xp.int32)
        gt = xp.zeros((n_rows_cap, nb), bool)
        eq = xp.ones((n_rows_cap, nb), bool)
        for col_gt, col_eq in col_cmps:
            gt = gt | (eq & col_gt)
            eq = eq & col_eq
        # Rows equal to a boundary go to the right partition (upper bound
        # is exclusive: id = count of bounds the row is > or == ).
        beyond = gt | eq
        return xp.sum(beyond.astype(xp.int32), axis=1)

    def _fixed_cmp(self, ki, rb, rk, xp):
        bb, bk = self._bound_scalars(ki, xp)
        col_gt = (rb[:, None] > bb[None, :]) | \
            ((rb[:, None] == bb[None, :]) & (rk[:, None] > bk[None, :]))
        col_eq = (rb[:, None] == bb[None, :]) & \
            (rk[:, None] == bk[None, :])
        return col_gt, col_eq

    # -- string keys --------------------------------------------------------
    def _string_bound_bytes(self, ki: int):
        """Boundary values of key ki as (validity, list[bytes])."""
        vals = [row[ki] for row in self.bounds.rows]
        validity = np.array([v is not None for v in vals])
        enc = [(v.encode("utf-8") if isinstance(v, str) else (v or b""))
               for v in vals]
        return validity, enc

    def _string_cmp_device(self, ki: int, c, asc: bool, nf: bool):
        """Byte-lexicographic (gt, eq) of every row vs every boundary —
        the GpuRangePartitioner string path (GpuRangePartitioner.scala:237
        range-partitions strings on device; here the comparison is one
        vectorized [rows x bounds x W] byte walk, W = the column's byte
        bucket)."""
        from ..ops.strings_util import char_matrix
        validity_b, enc = self._string_bound_bytes(ki)
        w = max(c.max_bytes, max((len(e) for e in enc), default=1), 1)
        m = char_matrix(c, w)  # [cap, W] int16, PAD(-1) past end
        bm = np.full((len(enc), w), -1, np.int16)
        for i, e in enumerate(enc):
            arr = np.frombuffer(e[:w], np.uint8)
            bm[i, : len(arr)] = arr
        bmat = jnp.asarray(bm)
        # lexicographic compare row vs bound over W byte lanes
        r = m[:, None, :].astype(jnp.int16)
        b = bmat[None, :, :]
        byte_eq = r == b
        byte_gt = r > b
        prefix_eq = jnp.cumprod(byte_eq.astype(jnp.int8), axis=2) > 0
        eq_all = prefix_eq[:, :, -1]
        shifted = jnp.concatenate(
            [jnp.ones(prefix_eq.shape[:2] + (1,), bool),
             prefix_eq[:, :, :-1]], axis=2)
        gt_str = jnp.any(shifted & byte_gt, axis=2)
        row_valid = c.validity
        bval = jnp.asarray(validity_b)
        null_lt = bool(nf)  # nulls_first: null sorts before every value
        rv = row_valid[:, None]
        bv = bval[None, :]
        both = rv & bv
        col_eq = (both & eq_all) | (~rv & ~bv)
        mixed_gt = ((rv & ~bv) & null_lt) | ((~rv & bv) & (not null_lt))
        col_gt = jnp.where(both, gt_str, mixed_gt)
        if not asc:
            col_gt = ~col_gt & ~col_eq
        return col_gt, col_eq

    def _string_cmp_host(self, ki: int, arr, asc: bool, nf: bool,
                         n_rows: int):
        validity_b, enc = self._string_bound_bytes(ki)
        vals = arr.to_pylist()
        rv = np.array([v is not None for v in vals])
        raw = np.array([(v or "").encode("utf-8") for v in vals],
                       dtype=object)
        nb = len(enc)
        gt = np.zeros((n_rows, nb), bool)
        eq = np.zeros((n_rows, nb), bool)
        benc = np.array(enc, dtype=object)
        for j in range(nb):
            if validity_b[j]:
                gt[:, j] = rv & (raw > benc[j])
                eq[:, j] = rv & (raw == benc[j])
                if nf:
                    pass  # null row < valid bound -> neither gt nor eq
                else:
                    gt[:, j] |= ~rv  # nulls last: null row > valid bound
            else:
                if nf:
                    gt[:, j] = rv  # valid row > null bound (nulls first)
                eq[:, j] = ~rv
        if not asc:
            ngt = ~gt & ~eq
            gt = ngt
        return gt, eq

    def device_ids(self, batch):
        cmps = []
        for ki, (e, asc, nf) in enumerate(zip(self._bound_exprs,
                                              self.bounds.ascending,
                                              self.bounds.nulls_first)):
            c = e.eval_device(batch)
            if c.is_string:
                cmps.append(self._string_cmp_device(ki, c, asc, nf))
            else:
                rb, rk = self._key_arrays(c.data, c.validity, c.dtype, asc,
                                          nf, jnp)
                cmps.append(self._fixed_cmp(ki, rb, rk, jnp))
        return self._ids(cmps, jnp, batch.capacity)

    def host_ids(self, hb):
        cmps = []
        for ki, (e, asc, nf, dt) in enumerate(zip(
                self._bound_exprs, self.bounds.ascending,
                self.bounds.nulls_first, self.bounds.dtypes)):
            arr = host_to_array(e.eval_host(hb), hb.num_rows)
            if dt is T.STRING:
                cmps.append(self._string_cmp_host(ki, arr, asc, nf,
                                                  hb.num_rows))
                continue
            validity = np.array([v is not None for v in arr.to_pylist()])
            np_dt = dt.np_dtype
            raw = np.array([0 if v is None else v for v in arr.to_pylist()],
                           dtype=np_dt)
            rb, rk = self._key_arrays(raw, validity, dt, asc, nf, np)
            cmps.append(self._fixed_cmp(ki, rb, rk, np))
        return self._ids(cmps, np, hb.num_rows)


def _np_orderable(data: np.ndarray, dtype: T.DataType) -> np.ndarray:
    """Host mirror of rowops.orderable_values."""
    if dtype.is_floating:
        if data.dtype == np.float32:
            bits = data.view(np.int32).astype(np.int64)
        else:
            bits = data.astype(np.float64).view(np.int64)
        canon = np.int64(0x7FF8000000000000 if data.dtype != np.float32
                         else 0x7FC00000)
        bits = np.where(np.isnan(data), canon, bits)
        bits = np.where(data == 0, np.int64(0), bits)
        int64_min = np.int64(-0x8000000000000000)
        return np.where(bits < 0, (~bits + int64_min).astype(np.int64), bits)
    return data.astype(np.int64)


def _sample_key_rows(child_plan, ctx, columnar: bool,
                     key_exprs: List[Expression], max_samples: int
                     ) -> List[tuple]:
    """Deterministic sample of key tuples from the child stream (the
    SamplingUtils reservoir analog; deterministic so the CPU oracle and TPU
    runs derive identical bounds)."""
    rows: List[tuple] = []
    bound = None
    for part in child_plan.execute(ctx):
        for b in part:
            hb = HostBatch(b.to_arrow()) if columnar else b
            if bound is None:
                bound = [k.bind(hb.schema) for k in key_exprs]
            cols = [host_to_array(e.eval_host(hb), hb.num_rows).to_pylist()
                    for e in bound]
            rows.extend(zip(*cols))
            if len(rows) >= max_samples * 4:
                break
    if len(rows) > max_samples:
        stride = len(rows) / max_samples
        rows = [rows[int(i * stride)] for i in range(max_samples)]
    return rows


def partitioner_factory(mode: str, n_parts: int, keys=None, orders=None,
                        start: int = 0):
    """Factory closure handed to the exchange execs; called with the exec's
    actual child + context so range partitioning can sample it."""

    def make(child_plan, ctx, columnar: bool) -> Partitioner:
        schema = child_plan.schema
        if mode == "single":
            return SinglePartitioner()
        if mode == "round_robin":
            return RoundRobinPartitioner(n_parts, start)
        if mode == "hash":
            return HashPartitioner(list(keys), n_parts, schema)
        assert mode == "range", mode
        key_exprs = [o.child for o in orders]
        asc = [o.ascending for o in orders]
        nf = [o.effective_nulls_first for o in orders]
        dtypes = [k.data_type for k in key_exprs]
        sample = _sample_key_rows(child_plan, ctx, columnar, key_exprs,
                                  max_samples=max(100 * n_parts, 1000))
        bounds = sample_range_bounds(sample, n_parts, asc, nf, dtypes)
        return RangePartitioner(key_exprs, bounds, n_parts, schema)
    make.mode = mode
    make.n_parts = n_parts
    make.keys = keys
    make.orders = orders
    return make
