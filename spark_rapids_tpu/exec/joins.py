"""Broadcast exchange and non-equi joins — the ``GpuBroadcastExchangeExec`` /
``GpuBroadcastHashJoinExec`` / ``GpuBroadcastNestedLoopJoinExec`` /
``GpuCartesianProductExec`` analogs.

Reference shapes (SURVEY.md §2.3): broadcast exchange collects device batches
into serialized host buffers, ships them via Spark broadcast, and lazily
re-uploads on each executor (GpuBroadcastExchangeExec.scala:242,
SerializeConcatHostBuffersDeserializeBatch:47). Broadcast hash join feeds the
broadcast as the hash-join build side (GpuBroadcastHashJoinExec.scala:91);
nested-loop join covers cross joins and inner joins with arbitrary conditions
(GpuBroadcastNestedLoopJoinExec.scala:135); cartesian product is the
no-broadcast cross (GpuCartesianProductExec.scala:226).

TPU-native: the exchange caches one coalesced device batch plus its Arrow IPC
host serialization (the single-process stand-in for the torrent broadcast),
so many joins can reuse it without re-upload. The nested-loop join evaluates
the condition on all (probe, build) pairs at once — a gather-expanded pair
batch that XLA fuses with the condition expression — instead of looping rows.
"""

from __future__ import annotations

from typing import List, Optional

import jax
import jax.numpy as jnp

from .. import types as T
from ..data.batch import ColumnarBatch
from ..data.column import bucket_capacity
from ..ops.expression import Expression
from ..ops.kernels import rowops as KR
from ..plan.physical import PhysicalPlan
from ..utils.kernel_cache import cached_kernel, kernel_key
from ..metrics.trace import span
from .execs import (TpuExec, TpuShuffledHashJoinExec, _bind_all,
                    _coalesce_device, _null_col, _null_extend_right)


class TpuBroadcastExchangeExec(TpuExec):
    """Materialize the child once: coalesced device batch + host IPC bytes.

    The host serialization is the broadcast payload (what the reference ships
    through TorrentBroadcast); the device batch is the lazily re-uploaded
    executor-side copy. Both are cached so N consumers pay once."""

    def __init__(self, child: PhysicalPlan):
        self.children = [child]
        self._device_batch: Optional[ColumnarBatch] = None
        self._buffer_id: Optional[int] = None
        self._payload_bytes = 0
        self._empty = False

    @property
    def schema(self):
        return self.children[0].schema

    def broadcast_batch(self, ctx) -> Optional[ColumnarBatch]:
        if self._empty:
            return None
        catalog = getattr(ctx, "catalog", None)
        if self._buffer_id is not None and catalog is not None:
            # Cached in the spill catalog: may restore from host/disk if
            # memory pressure pushed it out between consumers.
            return catalog.acquire_batch(self._buffer_id)
        if self._device_batch is not None:
            return self._device_batch
        batches = []
        for part in self.children[0].execute(ctx):
            batches.extend(part)
        if not batches:
            self._empty = True
            return None
        with span(getattr(ctx, "trace", None), "broadcast.collect"):
            from ..memory import retry as R
            # The broadcast payload must be ONE batch (every consumer
            # builds from it): spill + retry only, no split.
            name = self.node_name()
            merged = R.with_retry(ctx, f"{name}.collect", batches,
                                  _coalesce_device, node=name)[0]
            # Payload size from the device buffer footprint; the IPC bytes
            # are only materialized if a multi-process transport needs them
            # — in-process, consumers share the device batch directly.
            self._payload_bytes = merged.device_size_bytes
        ctx.metric(self.node_name(), "dataSize", self._payload_bytes)
        if catalog is not None and not ctx.in_fusion:
            from ..memory import spill as SP
            bid = catalog.register_batch(merged, SP.ACTIVE_ON_DECK_PRIORITY,
                                         owner=getattr(ctx, "qos", None))
            self._buffer_id = bid

            def _release():
                # The exchange node dies with the query; free its catalog
                # entry at query end or the session-lifetime catalog leaks
                # one build table per broadcast query.
                catalog.free(bid)
                # Cleanups run on the query thread at query end, never
                # on pipeline workers.
                self._buffer_id = None  # concurrency: ignore
            ctx.add_cleanup(_release)
            return catalog.acquire_batch(bid)
        self._device_batch = merged
        return merged

    @property
    def payload_bytes(self) -> int:
        return self._payload_bytes

    def execute(self, ctx):
        b = self.broadcast_batch(ctx)
        if b is not None:
            ctx.metric(self.node_name(), "numOutputBatches", 1)
        return [iter([b] if b is not None else [])]


class TpuBroadcastHashJoinExec(TpuShuffledHashJoinExec):
    """Equi-join whose build side is a broadcast exchange: identical device
    join core (GpuHashJoin.doJoin analog), build batch shared across
    consumers via the exchange cache."""

    def describe(self):
        return f"TpuBroadcastHashJoin {self.join_type}"


class TpuBroadcastNestedLoopJoinExec(TpuExec):
    """Cross / conditional join without equi keys.

    Evaluates the condition over the full (probe x build-chunk) pair grid:
    pair index vectors gather both sides into one wide batch, the bound
    condition evaluates on it (fused by XLA), and matches compact out.
    Supported types mirror the reference's BNLJ: cross, inner (condition),
    left outer, left_semi, left_anti."""

    def __init__(self, left: PhysicalPlan, right: PhysicalPlan,
                 join_type: str, condition: Optional[Expression],
                 schema: T.Schema):
        self.children = [left, right]
        self.join_type = join_type
        self.condition = condition
        self._schema = schema

    @property
    def schema(self):
        return self._schema

    def describe(self):
        return f"TpuBroadcastNestedLoopJoin {self.join_type}"

    def execute(self, ctx):
        left, right = self.children
        jt = self.join_type
        out_schema = self._schema
        pair_schema = T.Schema(
            list(left.schema) + [
                T.StructField(f"__b_{f.name}", f.data_type, f.nullable)
                for f in right.schema])
        cond = None
        if self.condition is not None:
            # The condition references output-position columns; rebind it to
            # the pair schema by ordinal identity (left cols then right cols).
            cond = self.condition.bind(
                T.Schema(list(left.schema) + list(right.schema)))

        def kernel_impl(probe: ColumnarBatch, build: ColumnarBatch,
                        out_cap: int):
            pcap, bcap = probe.capacity, build.capacity
            n_pairs = pcap * bcap
            p_idx = jnp.repeat(jnp.arange(pcap, dtype=jnp.int32), bcap)
            b_idx = jnp.tile(jnp.arange(bcap, dtype=jnp.int32), pcap)
            live = probe.row_mask()[p_idx] & build.row_mask()[b_idx]
            pcols = KR.gather_columns(probe.columns, p_idx, live)
            bcols = KR.gather_columns(build.columns, b_idx, live)
            pairs = ColumnarBatch(tuple(pcols) + tuple(bcols),
                                  jnp.asarray(n_pairs, jnp.int32), pair_schema)
            if cond is not None:
                m = cond.eval_device(pairs)
                match = live & m.data & m.validity
            else:
                match = live
            match_count_per_probe = jax.ops.segment_sum(
                match.astype(jnp.int32), p_idx, num_segments=pcap)
            if jt in ("left_semi", "left_anti"):
                keep = match_count_per_probe > 0
                if jt == "left_anti":
                    keep = ~keep & probe.row_mask()
                return KR.compact(probe, keep), None
            # Compact matching pairs to the front of out_cap rows.
            n_match = jnp.sum(match.astype(jnp.int32))
            order = jnp.where(match, jnp.int8(0), jnp.int8(1))
            iota = jnp.arange(n_pairs, dtype=jnp.int32)
            _, perm = jax.lax.sort((order, iota), num_keys=1, is_stable=True)
            sel = perm[:out_cap] if out_cap <= n_pairs else jnp.concatenate(
                [perm, jnp.full(out_cap - n_pairs, n_pairs - 1, jnp.int32)])
            out_live = jnp.arange(out_cap, dtype=jnp.int32) < n_match
            sp_idx = p_idx[sel]
            sb_idx = b_idx[sel]
            ocols = KR.gather_columns(probe.columns, sp_idx, out_live) \
                + KR.gather_columns(build.columns, sb_idx, out_live)
            out = ColumnarBatch(tuple(ocols),
                                jnp.minimum(n_match, out_cap).astype(jnp.int32),
                                out_schema)
            if jt == "left":
                unmatched = (match_count_per_probe == 0) & probe.row_mask()
                extra = KR.compact(probe, unmatched)
                return (out, extra), n_match
            return (out, None), n_match

        kernel = cached_kernel(
            "nested_loop_join",
            kernel_key(jt, cond, pair_schema, out_schema),
            lambda: kernel_impl, static_argnums=(2,))

        name = self.node_name()

        def counted(db):
            ctx.metric(name, "numOutputBatches", 1)
            return db

        def gen():
            from ..memory import retry as R
            with ctx.registry.timer(name, "buildTime"):
                build_batches = []
                for part in right.execute(ctx):
                    build_batches.extend(part)
                build = _coalesce_device(build_batches) if build_batches \
                    else None
            n_right = len(right.schema)

            for part in left.execute(ctx):
                for probe in part:
                    if build is None:
                        if jt in ("left", "left_anti"):
                            if jt == "left":
                                yield counted(_null_extend_right(
                                    probe, out_schema, n_right))
                            else:
                                yield counted(ColumnarBatch(
                                    probe.columns, probe.n_rows, out_schema,
                                    live=probe.live))
                        continue
                    if jt in ("left_semi", "left_anti"):
                        # The pair grid is the memory hazard (probe cap x
                        # build cap): a probe half quarters it.
                        for out in R.with_retry(
                                ctx, f"{name}.pairGrid", probe,
                                lambda p: kernel(p, build, 0)[0],
                                split=R.halve_by_rows, node=name):
                            yield counted(ColumnarBatch(
                                out.columns, out.n_rows, out_schema,
                                live=out.live))
                        continue
                    # Optimistic sizing + deferred overflow flag — same
                    # no-sync discipline as TpuShuffledHashJoinExec; the
                    # session retries with the learned exact capacity when
                    # the pair count exceeded the allocation.
                    site = ctx.next_join_site()
                    tracker = R.SplitTracker(R.halve_by_rows)

                    def sized_join(p):
                        out_cap = ctx.join_caps.get(site) or \
                            bucket_capacity(
                                max(int(p.capacity * ctx.join_growth), 128))
                        (out, extra), n_match = kernel(p, build, out_cap)
                        if ctx.eager_overflow:
                            t = int(n_match)
                            if t > out_cap:
                                (out, extra), _ = kernel(p, build,
                                                         bucket_capacity(t))
                        else:
                            ctx.overflow_flags.append(n_match > out_cap)
                            if not tracker.split_happened:
                                ctx.join_totals.append((site, n_match))
                        return out, extra
                    for out, extra in R.with_retry(
                            ctx, f"{name}.pairGrid", probe, sized_join,
                            split=tracker, node=name):
                        yield counted(out)
                        if extra is not None:
                            yield counted(_null_extend_right(
                                extra, out_schema, n_right))
        return [gen()]


class TpuCartesianProductExec(TpuBroadcastNestedLoopJoinExec):
    """Cross product of two non-broadcast sides (GpuCartesianProductExec);
    the pairwise device kernel is shared with the nested-loop join."""

    def __init__(self, left: PhysicalPlan, right: PhysicalPlan,
                 schema: T.Schema, condition: Optional[Expression] = None):
        super().__init__(left, right, "cross", condition, schema)

    def describe(self):
        return "TpuCartesianProduct"
