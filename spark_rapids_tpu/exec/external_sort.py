"""External merge sort — bounded-memory global sort over the spill catalog.

The reference bounds sort memory with ``RequireSingleBatch`` + the spill
store (GpuSortExec.scala:50-54 with RapidsBufferStore behind it): the
single concatenated input can spill, but the sort itself still needs the
whole dataset on the device. This module removes that ceiling the TPU way:

1. **Run generation** — each input batch is sorted on-device (one
   ``lax.sort`` program) and registered with the spill catalog, so runs
   migrate device->host->disk under pressure. A run is a FIFO of sorted
   chunks; its head key rides along host-side (downloaded once per chunk,
   a few scalars).
2. **Binary merge tree** — runs merge pairwise. A merge step holds at most
   THREE chunks on device (carry + one chunk + the emitted prefix): the
   two-chunk union is sorted together with a 1-row SENTINEL carrying the
   other run's next head; rows sorting strictly before the sentinel are
   exactly the elements ``<= every future element of both runs`` and are
   emitted as a final sorted chunk (re-bucketed to its live size), the
   rest carry over. No data-dependent shapes: the live split point is the
   batch's traced ``n_rows``.
3. The final run is a stream of globally ordered chunks — downstream
   consumers (limits, windows, downloads) never see a single oversized
   batch.

Host coordination (which run to pull, re-bucketing) happens between
device programs, exactly like the reference's iterator-driven execution.
"""

from __future__ import annotations

import math
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import types as T
from ..data.batch import ColumnarBatch, _shrink_batch
from ..data.column import bucket_capacity
from ..memory import spill as SP
from ..ops.kernels import concat as KC
from ..ops.kernels import rowops as KR
from ..utils.kernel_cache import cached_kernel, kernel_key
from ..metrics.trace import span


def _head_key_values(batch: ColumnarBatch, key_exprs) -> tuple:
    """Download row 0's key values as a host tuple (None for null)."""
    out = []
    for e in key_exprs:
        c = e.eval_device(batch)
        if c.is_string:
            # Compare dictionary strings by their decoded bytes.
            from ..ops.strings_util import char_matrix
            m = char_matrix(c)
            row = np.asarray(jax.device_get(m[:1]))[0]
            valid = bool(jax.device_get(c.validity[0]))
            out.append(bytes(int(x) for x in row if x >= 0)
                       if valid else None)
        else:
            valid = bool(jax.device_get(c.validity[0]))
            out.append(jax.device_get(c.data[0]).item() if valid else None)
    return tuple(out)


def _key_less(a: tuple, b: tuple, orders) -> bool:
    """Host comparator for head tuples, honoring asc / nulls_first."""
    for av, bv, o in zip(a, b, orders):
        nf = o.effective_nulls_first
        if av is None or bv is None:
            if av is None and bv is None:
                continue
            return nf if av is None else not nf
        if av == bv or (isinstance(av, float) and isinstance(bv, float)
                        and math.isnan(av) and math.isnan(bv)):
            continue
        if isinstance(av, float) and math.isnan(av):
            return not o.ascending  # NaN sorts greatest
        if isinstance(bv, float) and math.isnan(bv):
            return o.ascending
        return (av < bv) == o.ascending
    return False


class _Run:
    """FIFO of sorted spill-registered chunks with host-side head keys."""

    def __init__(self):
        self.chunks: List[Tuple[int, tuple, int]] = []  # (id, head, cap)

    def head(self) -> Optional[tuple]:
        return self.chunks[0][1] if self.chunks else None

    def max_cap(self) -> int:
        return max((c for _, _, c in self.chunks), default=128)

    def pop(self, catalog) -> ColumnarBatch:
        """Acquire the next chunk and release its catalog entry — the
        returned batch keeps the device arrays alive by reference, and a
        consumed chunk must not stay registered (it would sit unspillable
        in the device store for the rest of the merge)."""
        bid, _, _ = self.chunks.pop(0)
        batch = catalog.acquire_batch(bid)
        catalog.free(bid)
        return batch

    def peek_head_row(self, catalog, slice_k) -> ColumnarBatch:
        """1-row batch holding the next chunk's first row (the merge
        sentinel). Acquires without consuming."""
        import jax.numpy as _jnp
        bid, _, _ = self.chunks[0]
        src = catalog.acquire_batch(bid)
        return slice_k(src, _jnp.asarray(0, _jnp.int32),
                       _jnp.asarray(1, _jnp.int32), 128)


def _merge_step_kernel(key_exprs, asc, nf, schema, with_sentinel: bool):
    """(carry, chunk[, sentinel_row]) -> (merged_sorted, n_emit).

    The union is sorted once; with a sentinel, n_emit = count of data rows
    sorting strictly before the sentinel row (stable sort with a source
    tag ordering the sentinel after equal keys), else every live row."""
    def build():
        def step(a: ColumnarBatch, b: ColumnarBatch,
                 sent: Optional[ColumnarBatch] = None):
            parts = [a, b] + ([sent] if sent is not None else [])
            total = sum(p.capacity for p in parts)
            merged = KC.concat_batches(tuple(parts), total)
            keys = [e.eval_device(merged) for e in key_exprs]
            iota = jnp.arange(total, dtype=jnp.int32)
            if sent is not None:
                # concat_batches compacts live rows to a prefix, so the
                # sentinel's rows start at the live-row count, not at the
                # capacity offset.
                n_data = a.n_rows + b.n_rows
                is_sent = (iota >= n_data) & (iota < n_data + sent.n_rows)
            operands = []
            for k, kasc, knf in zip(keys, asc, nf):
                if k.is_string:
                    operands.extend(KR.string_sort_keys(k, kasc, knf))
                else:
                    key, null_bucket = KR.orderable_key(k, kasc, knf)
                    operands.append(null_bucket)
                    operands.append(key)
            live = merged.row_mask()
            # dead rows sink to the end
            operands.insert(0, jnp.where(live, 0, 1).astype(jnp.int8))
            if sent is not None:
                # sentinel sorts AFTER equal keys
                operands.append(is_sent.astype(jnp.int8))
            sorted_ops = jax.lax.sort(tuple(operands) + (iota,),
                                      num_keys=len(operands),
                                      is_stable=True)
            perm = sorted_ops[-1]
            out = KR.gather_batch(merged, perm,
                                  jnp.asarray(total, jnp.int32),
                                  index_valid=None)
            if sent is not None:
                sent_sorted = is_sent[perm]
                sent_pos = jnp.argmax(sent_sorted).astype(jnp.int32)
                n_emit = jnp.minimum(sent_pos, n_data)
                # drop the sentinel row from the ordered stream: rows after
                # it shift left by one
                shift_idx = iota + (iota >= sent_pos).astype(jnp.int32)
                out = KR.gather_batch(
                    out, jnp.clip(shift_idx, 0, total - 1),
                    jnp.asarray(total, jnp.int32), index_valid=None)
                out = ColumnarBatch(out.columns, n_data, schema)
            else:
                n_data = a.n_rows + b.n_rows
                out = ColumnarBatch(out.columns, n_data, schema)
                n_emit = n_data
            return out, n_emit
        return step
    return cached_kernel(
        "extsort_merge", kernel_key(key_exprs, tuple(asc), tuple(nf),
                                    schema, with_sentinel), build)


def _slice_kernel(schema):
    """(batch, start, count, out_cap static) -> rows [start, start+count)."""
    def build():
        def do_slice(batch: ColumnarBatch, start, count, out_cap: int):
            idx = start + jnp.arange(out_cap, dtype=jnp.int32)
            live = jnp.arange(out_cap, dtype=jnp.int32) < count
            out = KR.gather_batch(batch, jnp.clip(idx, 0, batch.capacity - 1),
                                  jnp.asarray(out_cap, jnp.int32),
                                  index_valid=None)
            return ColumnarBatch(out.columns, count.astype(jnp.int32),
                                 schema)
        return do_slice
    return cached_kernel("extsort_slice", kernel_key(schema), build,
                         static_argnums=(3,))


class _TrackingCatalog:
    """Thin catalog proxy recording which chunk ids this sorter still owns,
    so an abandoned chunk stream (e.g. a limit closing the generator early)
    can free every outstanding registration instead of leaking them into
    the session-lifetime spill budget."""

    def __init__(self, catalog, owner=None):
        self._c = catalog
        #: QoS identity stamped on every chunk registration (ISSUE 11):
        #: the spill victim order drains this query's own chunks first.
        self._owner = owner
        self.live = set()

    def register_batch(self, batch, priority):
        bid = self._c.register_batch(batch, priority, owner=self._owner)
        self.live.add(bid)
        return bid

    def free(self, bid):
        self.live.discard(bid)
        self._c.free(bid)

    def acquire_batch(self, bid):
        return self._c.acquire_batch(bid)

    def release_all(self):
        for bid in list(self.live):
            self._c.free(bid)
        self.live.clear()


class ExternalSorter:
    """Streaming global sort: feed batches, then iterate sorted chunks."""

    def __init__(self, orders, schema: T.Schema, catalog,
                 key_exprs=None, ctx=None):
        self.orders = orders
        self.schema = schema
        self.catalog = _TrackingCatalog(catalog,
                                        owner=getattr(ctx, "qos", None))
        self.key_exprs = key_exprs or [o.child.bind(schema) for o in orders]
        self.asc = [o.ascending for o in orders]
        self.nf = [o.effective_nulls_first for o in orders]
        self._runs: List[_Run] = []
        #: ExecContext for the OOM-retry combinator around merge steps
        #: (spill + retry only — a merge step cannot split); None keeps
        #: the bare-unit-test construction unchanged.
        self._ctx = ctx
        self._sort_one = self._make_sort_one()

    def _retry_step(self, tag: str, fn):
        """One merge-tree device step under the retry combinator."""
        if self._ctx is None:
            return fn(None)
        from ..memory import retry as R
        return R.with_retry(self._ctx, f"ExternalSorter.{tag}", None, fn,
                            node="ExternalSorter")[0]

    def release(self):
        """Free every chunk this sorter still has registered (safe to call
        after normal completion — it is then a no-op)."""
        self._runs = []
        self.catalog.release_all()

    def _make_sort_one(self):
        key_exprs, asc, nf = self.key_exprs, self.asc, self.nf

        def build():
            def do_sort(b):
                keys = [e.eval_device(b) for e in key_exprs]
                return KR.sort_batch_by_columns(b, keys, asc, nf)
            return do_sort
        return cached_kernel("sort", kernel_key(key_exprs, tuple(asc),
                                                tuple(nf)), build)

    def add_batch(self, batch: ColumnarBatch):
        sdb = self._sort_one(batch)
        run = _Run()
        run.chunks.append((self.catalog.register_batch(
            sdb, SP.ACTIVE_BATCHING_PRIORITY),
            _head_key_values(sdb, self.key_exprs), sdb.capacity))
        self._runs.append(run)

    # -- merging ------------------------------------------------------------
    def _merge_two(self, r1: _Run, r2: _Run) -> _Run:
        """Streaming two-run merge with bounded device residency.

        Per step the device holds the carry (typically <= one chunk), one
        pulled chunk, the merged union, and a 1-row sentinel. Emission is
        bounded by the MINIMUM of BOTH runs' next heads — the carry can
        hold elements larger than the pulled run's own next chunk, so the
        other run's head alone is not a valid bound. Emitted prefixes
        re-chunk to the base chunk capacity so chunk sizes stay constant
        up the whole merge tree."""
        out = _Run()
        merge_s = _merge_step_kernel(self.key_exprs, self.asc, self.nf,
                                     self.schema, True)
        merge_ns = _merge_step_kernel(self.key_exprs, self.asc, self.nf,
                                      self.schema, False)
        slice_k = _slice_kernel(self.schema)
        catalog = self.catalog
        base_cap = max(r1.max_cap(), r2.max_cap())

        def emit(batch, start, n_emit_host):
            off = start
            end = start + n_emit_host
            while off < end:
                take = min(base_cap, end - off)
                cap = base_cap if take == base_cap else \
                    bucket_capacity(max(take, 128))
                chunk = slice_k(batch, jnp.asarray(off, jnp.int32),
                                jnp.asarray(take, jnp.int32), cap)
                out.chunks.append((catalog.register_batch(
                    chunk, SP.ACTIVE_BATCHING_PRIORITY),
                    _head_key_values(chunk, self.key_exprs), cap))
                off += take

        def smaller_head_run():
            h1, h2 = r1.head(), r2.head()
            if h1 is None:
                return r2
            if h2 is None:
                return r1
            return r1 if _key_less(h1, h2, self.orders) else r2

        carry = None
        while r1.chunks or r2.chunks or carry is not None:
            if carry is None:
                if not (r1.chunks or r2.chunks):
                    break
                carry = smaller_head_run().pop(catalog)
                continue
            if not (r1.chunks or r2.chunks):
                emit(carry, 0, int(jax.device_get(carry.n_rows)))
                carry = None
                continue
            src = smaller_head_run()
            chunk = src.pop(catalog)
            # Emission bound: the smaller of the two runs' NEXT heads.
            bound_run = smaller_head_run() \
                if r1.chunks and r2.chunks else \
                (r1 if r1.chunks else (r2 if r2.chunks else None))
            if bound_run is None or not bound_run.chunks:
                merged, n_emit = self._retry_step(
                    "mergeStep", lambda _: merge_ns(carry, chunk))
                n = int(jax.device_get(n_emit))
                emit(merged, 0, n)
                carry = None
                continue
            sent = bound_run.peek_head_row(catalog, slice_k)
            merged, n_emit = self._retry_step(
                "mergeStep", lambda _: merge_s(carry, chunk, sent))
            n = int(jax.device_get(n_emit))
            total_live = int(jax.device_get(merged.n_rows))
            emit(merged, 0, n)
            rest = total_live - n
            if rest > 0:
                cap = bucket_capacity(max(rest, 128))
                carry = slice_k(merged, jnp.asarray(n, jnp.int32),
                                jnp.asarray(rest, jnp.int32), cap)
            else:
                carry = None
        return out

    def sorted_chunks(self):
        """Merge all runs; yield the final run's chunks in order (each
        acquired from the catalog, freed after the caller consumes it)."""
        with span(None, "extsort.merge"):
            runs = self._runs
            while len(runs) > 1:
                nxt = []
                for i in range(0, len(runs) - 1, 2):
                    nxt.append(self._merge_two(runs[i], runs[i + 1]))
                if len(runs) % 2:
                    nxt.append(runs[-1])
                runs = nxt
            self._runs = runs
        if not runs:
            return
        for bid, _, _ in runs[0].chunks:
            batch = self.catalog.acquire_batch(bid)
            self.catalog.free(bid)
            yield batch
        runs[0].chunks = []
