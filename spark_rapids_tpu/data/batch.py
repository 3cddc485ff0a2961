"""Device columnar batches — the ``ColumnarBatch``/``Table`` analog.

A :class:`ColumnarBatch` is a pytree of :class:`DeviceColumn` plus a traced
``n_rows`` scalar; its capacity and schema are static treedef data. This is
the unit that flows between device operators, exactly as cudf-backed
``ColumnarBatch`` objects flow between GPU execs in the reference
(``GpuColumnVector.java:40``, ``GpuExec`` iterators) — but shaped for XLA:
one compiled program per (schema, capacity-bucket), row count fully dynamic.

``HostBatch`` wraps a pyarrow ``RecordBatch`` and is the currency of the CPU
(oracle / fallback) execution path, standing in for Spark's host
``ColumnarBatch`` of rows.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import List, Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np
import pyarrow as pa

from .. import types as T
from .column import DeviceColumn, bucket_capacity


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class ColumnarBatch:
    """A device-resident table slice with a dynamic live-row count.

    Liveness has two representations:

    * **physical** (``live is None``): rows ``[0, n_rows)`` are live — the
      compacted form every positional consumer (concat, slice, download,
      serialize) requires.
    * **lazy** (``live`` is a ``bool[capacity]`` mask): live rows sit
      scattered at their original positions and ``n_rows`` is their traced
      COUNT. A filter then costs one mask AND instead of a full sort-based
      compaction (the dominant cost of filter-heavy plans); mask-native
      consumers (aggregate, join, sort, further filters) read
      :meth:`row_mask` and never pay the compaction. Positional consumers
      call :func:`..ops.kernels.rowops.physical` first.
    """

    columns: tuple  # tuple[DeviceColumn]
    n_rows: jax.Array  # int32 scalar, traced — COUNT of live rows
    schema: T.Schema  # static
    live: Optional[jax.Array] = None  # bool[capacity]; None = physical

    def tree_flatten(self):
        if self.live is None:
            return (self.columns, self.n_rows), (self.schema, False)
        return (self.columns, self.n_rows, self.live), (self.schema, True)

    @classmethod
    def tree_unflatten(cls, aux, children):
        schema, has_live = aux
        if has_live:
            columns, n_rows, live = children
            return cls(columns=tuple(columns), n_rows=n_rows, schema=schema,
                       live=live)
        columns, n_rows = children
        return cls(columns=tuple(columns), n_rows=n_rows, schema=schema)

    @property
    def num_columns(self) -> int:
        return len(self.columns)

    @property
    def capacity(self) -> int:
        if self.columns:
            return self.columns[0].capacity
        return 0

    def column(self, key: Union[int, str]) -> DeviceColumn:
        if isinstance(key, str):
            key = self.schema.index_of(key)
        return self.columns[key]

    def with_columns(self, columns: Sequence[DeviceColumn],
                     schema: T.Schema) -> "ColumnarBatch":
        return ColumnarBatch(tuple(columns), self.n_rows, schema,
                             live=self.live)

    def row_mask(self) -> jax.Array:
        """bool[capacity] — True for live rows."""
        if self.live is not None:
            return self.live
        return jnp.arange(self.capacity, dtype=jnp.int32) < self.n_rows

    # -- host interchange ---------------------------------------------------
    @staticmethod
    def from_arrow(rb: pa.RecordBatch, min_capacity: int = 128,
                   capacity: Optional[int] = None) -> "ColumnarBatch":
        schema = T.schema_from_arrow(rb.schema)
        cap = capacity or bucket_capacity(rb.num_rows, min_capacity)
        cols = tuple(DeviceColumn.from_arrow(rb.column(i), cap)
                     for i in range(rb.num_columns))
        return ColumnarBatch(cols, jnp.asarray(rb.num_rows, dtype=jnp.int32), schema)

    def to_arrow(self) -> pa.RecordBatch:
        """Download to host. Syncs ``n_rows`` — only call at stage boundaries.

        Transfer discipline (every blocking read is a device round trip):
        one scalar sync for the row count, one cached shrink kernel
        when live rows occupy a smaller capacity bucket, then ONE batched
        ``jax.device_get`` for every buffer of every column.
        """
        return self.to_arrow_finish(self.to_arrow_begin(async_copy=False))

    def to_arrow_begin(self, async_copy: bool = True):
        """Start a download without blocking on the data: materialize +
        shrink, sync only the row-count scalar, and (where the backend
        supports it) start an async device->host copy of every buffer.
        Returns an opaque handle for :meth:`to_arrow_finish`. The split
        lets the pipelined DeviceToHost path dispatch the NEXT batch's
        device work while this batch's bytes are still in flight
        (exec/pipeline.py; the reference's overlapped-download stance)."""
        from ..ops.kernels.rowops import physical_jit
        batch = physical_jit(self)
        n = int(batch.n_rows)
        cap = bucket_capacity(max(n, 1))
        batch = _shrink_batch(batch, cap) if cap < batch.capacity else batch
        bufs = [c.device_buffers() for c in batch.columns]
        if async_copy:
            for leaf in jax.tree_util.tree_leaves(bufs):
                start = getattr(leaf, "copy_to_host_async", None)
                if callable(start):
                    start()
        return batch, n, bufs

    def to_arrow_finish(self, handle) -> pa.RecordBatch:
        """Block on a download started by :meth:`to_arrow_begin` and
        assemble the host RecordBatch (one batched ``jax.device_get``;
        a completed async copy makes it a cache read)."""
        batch, n, bufs = handle
        host = jax.device_get(bufs)
        arrays = [c.arrow_from_host(hb, n)
                  for c, hb in zip(batch.columns, host)]
        fields = [pa.field(f.name, T.to_arrow_type(f.data_type), f.nullable)
                  for f in self.schema]
        return pa.RecordBatch.from_arrays(arrays, schema=pa.schema(fields))

    @property
    def device_size_bytes(self) -> int:
        return sum(c.size_bytes for c in self.columns)


@functools.partial(jax.jit, static_argnums=(1,))
def _shrink_batch(batch: ColumnarBatch, cap: int) -> ColumnarBatch:
    """Copy a batch into a smaller capacity bucket (>= its live rows), so
    downloads move O(live) bytes instead of O(capacity). Rows past n_rows
    are dead by invariant, so a front slice is sufficient."""
    return ColumnarBatch(tuple(c.head(cap) for c in batch.columns),
                         batch.n_rows, batch.schema)


@functools.partial(jax.jit, static_argnums=(1,))
def _grow_batch(batch: ColumnarBatch, cap: int) -> ColumnarBatch:
    """Copy a batch into a LARGER capacity bucket, padding every column
    with dead rows (validity False, zero data — the padding-never-
    changes-results invariant) and extending any lazy live mask with
    False. The shape-polymorphic fused path (exec/fusion.py) pads
    boundary inputs onto coarse capacity tiers with this, so one
    compiled executable serves every bucket-ladder rung in a tier."""
    live = None if batch.live is None else \
        jnp.pad(batch.live, (0, cap - batch.live.shape[0]))
    return ColumnarBatch(tuple(c.grow(cap) for c in batch.columns),
                         batch.n_rows, batch.schema, live=live)


@dataclasses.dataclass
class HostBatch:
    """Host-side batch: the CPU oracle / fallback path currency."""

    rb: pa.RecordBatch

    @property
    def num_rows(self) -> int:
        return self.rb.num_rows

    @property
    def schema(self) -> T.Schema:
        return T.schema_from_arrow(self.rb.schema)

    def to_device(self, min_capacity: int = 128) -> ColumnarBatch:
        return ColumnarBatch.from_arrow(self.rb, min_capacity)

    @staticmethod
    def from_device(batch: ColumnarBatch) -> "HostBatch":
        return HostBatch(batch.to_arrow())

    @staticmethod
    def from_pydict(data: dict, schema: Optional[T.Schema] = None) -> "HostBatch":
        if schema is not None:
            rb = pa.RecordBatch.from_pydict(data, schema=T.schema_to_arrow(schema))
        else:
            rb = pa.RecordBatch.from_pydict(data)
        return HostBatch(rb)


def concat_host(batches: List[HostBatch]) -> HostBatch:
    tables = pa.Table.from_batches([b.rb for b in batches])
    combined = tables.combine_chunks()
    if combined.num_rows == 0:
        return HostBatch(pa.RecordBatch.from_pydict(
            {n: [] for n in combined.schema.names}, schema=combined.schema))
    return HostBatch(combined.to_batches()[0])
