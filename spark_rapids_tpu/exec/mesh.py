"""SPMD mesh query execution — the engine-integrated ICI shuffle.

The reference integrates its GPU-resident shuffle by BEING the shuffle
manager (``RapidsShuffleInternalManager.scala:73-149``): stages stay on the
GPU and exchange over UCX. The TPU-native integration is stronger: a whole
query compiles to ONE SPMD program over a ``jax.sharding.Mesh``. Sources
shard row-wise across chips; narrow operators run on the local shard with
the SAME kernels as single-chip execution; aggregation and join boundaries
insert a hash-partition + ``all_to_all`` exchange over ICI
(:mod:`..shuffle.ici`) so co-keyed rows land on one chip, where the
ordinary local kernel finishes the job. No host round-trips anywhere in
the stage — the property the reference's bounce-buffer/progress-thread
machinery (UCX.scala:84-190) only approximates.

Topology of one mesh query:

    per-chip: filter -> project -> partial agg      (local, XLA-fused)
    exchange: murmur3 partition -> all_to_all       (ICI collective)
    per-chip: merge agg / local join -> finalize    (local)
    collect : one sharded device_get

Plans whose operators are all mesh-capable run here when
``spark.rapids.tpu.mesh.enabled`` is set; anything else falls back to the
single-chip fused/streaming paths.

**Strings over the mesh** ride the dictionary encoding: a source batch is
materialized centrally, so its dictionary is global — the int32 CODES
shard and exchange like any fixed-width lane while the dictionary buffers
REPLICATE across chips (passed as unsharded shard_map inputs). Group-bys
keep the sorted-dict fast path per shard, joins and hash partitioning read
strings through the shared dictionaries, and the collect downloads one
dictionary plus per-shard code lanes. Only dictionary-encoded strings
qualify; expressions that produce FLAT strings (per-row payloads would
need a variable-width exchange) keep the single-chip fallback.

Exchange buckets are capacity-bounded with the deferred-overflow contract:
a ``psum``-reduced flag rides back with the result and the session retries
with a larger bucket growth, exactly like the join ladder.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import pyarrow as pa
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from .. import types as T
from ..data.batch import ColumnarBatch
from ..data.column import DeviceColumn, bucket_capacity
from ..ops.expression import BoundReference, Expression
from ..ops.kernels import rowops as KR
from ..parallel.mesh import (PART_AXIS, MeshDegradedError, is_device_loss,
                             make_mesh, shard_map)
from ..plan.physical import ExecContext
from ..shuffle import ici
from ..shuffle.partitioning import pmod_partition, spark_hash_columns_device
from ..utils.kernel_cache import cached_kernel, kernel_key, \
    plan_signature as _plan_sig
from .coalesce import TpuCoalesceBatchesExec
from .execs import (DeviceSourceExec, DeviceToHostExec, TpuFilterExec,
                    TpuHashAggregateExec, TpuProjectExec,
                    TpuShuffledHashJoinExec, TpuSortExec, _aggregate_batch,
                    _bind_all, _coalesce_device, _swap_schema,
                    finalize_agg_kernel, hash_join_kernel, join_post_filter,
                    unmatched_build_kernel)


class NotMeshCapable(Exception):
    pass


def _require(cond: bool, why: str):
    if not cond:
        raise NotMeshCapable(why)


# ---------------------------------------------------------------------------
# Exchange: hash-partition a local batch and all_to_all it over the mesh
# ---------------------------------------------------------------------------


def _exchange_by_key(batch: ColumnarBatch, key_exprs: List[Expression],
                     n_parts: int, bucket_cap: int, flags: List
                     ) -> ColumnarBatch:
    """Repartition a local shard batch by Spark-murmur3 of the keys: rows
    whose keys hash to chip p land on chip p. One scatter into
    [n_parts, bucket_cap] send buffers, one XLA all_to_all, one compaction.
    Appends a bucket-overflow flag (psum-reduced) to ``flags``."""
    keys = [e.eval_device(batch) for e in key_exprs]
    h = spark_hash_columns_device(keys)
    pid = pmod_partition(h, n_parts)
    return _exchange_by_pid(batch, pid, n_parts, bucket_cap, flags)


def _exchange_by_pid(batch: ColumnarBatch, pid, n_parts: int,
                     bucket_cap: int, flags: List) -> ColumnarBatch:
    """Exchange rows to the chip named by per-row ``pid`` (hash exchange
    for aggs/joins, RANGE exchange for the distributed sort)."""
    live = batch.row_mask()
    payload = {}
    for i, c in enumerate(batch.columns):
        # Dict strings move as their int32 code lane; the dictionary
        # buffers are replicated (identical on every chip), so codes stay
        # meaningful after the exchange.
        payload[f"d{i}"] = c.codes if c.is_dict else c.data
        payload[f"v{i}"] = c.validity
    send, send_valid, overflow = ici.build_send_buffers(
        payload, jnp.ones(batch.capacity, jnp.bool_), pid, live,
        n_parts, bucket_cap)
    recv, recv_valid = ici.exchange(send, send_valid)
    flat, flat_valid, n_live = ici.flatten_received(recv, recv_valid)
    flags.append(jax.lax.psum(overflow, PART_AXIS) > 0)
    cols = []
    for i, c in enumerate(batch.columns):
        validity = flat[f"v{i}"] & flat_valid
        lane = jnp.where(validity, flat[f"d{i}"],
                         jnp.zeros((), flat[f"d{i}"].dtype))
        if c.is_dict:
            cols.append(DeviceColumn(
                data=c.data, validity=validity, dtype=c.dtype,
                offsets=c.offsets, max_bytes=c.max_bytes, codes=lane,
                dict_sorted=c.dict_sorted))
        else:
            cols.append(DeviceColumn(data=lane, validity=validity,
                                     dtype=c.dtype))
    return ColumnarBatch(tuple(cols), n_live.astype(jnp.int32), batch.schema)


# ---------------------------------------------------------------------------
# Plan -> per-shard program
# ---------------------------------------------------------------------------


_NARROW = (TpuProjectExec, TpuFilterExec, TpuCoalesceBatchesExec)


def _compile(node, sources: List, n_parts: int, bucket_growth: float,
             conf) -> "callable":
    """Translate a plan subtree into fn(env, flags) -> local ColumnarBatch,
    where env maps source index -> the local shard batch. Raises
    NotMeshCapable for anything without a mesh story yet."""
    if isinstance(node, DeviceSourceExec):
        # String columns qualify only dictionary-encoded (codes shard, the
        # dictionary replicates); the source batches exist at plan time so
        # this is checkable here.
        for p in node.partitions:
            for b in p:
                for c, f in zip(b.columns, node.schema):
                    if f.data_type is T.STRING:
                        _require(c.is_dict,
                                 "flat (non-dictionary) string column in "
                                 "mesh source")
        sources.append(node)
        idx = len(sources) - 1
        return lambda env, flags: env[idx]

    if _is_scan_source(node):
        # File scans (and any host subtree behind an upload) are mesh
        # sources too: the scan materializes at execution time, uploads
        # (strings dict-encode on upload, so the dictionary is global),
        # and shards row-wise across the chips — row groups land on chips
        # the way the reference's resident shuffle serves arbitrary stages
        # (RapidsShuffleInternalManager.scala:73-149). Decode happens once
        # host-side in this single-host runtime; a multi-host deployment
        # would decode per-host before the same sharding step.
        for f in node.schema:
            _require(T.device_supported(f.data_type),
                     f"scan column type {f.data_type} over the mesh")
        sources.append(node)
        idx = len(sources) - 1
        return lambda env, flags: env[idx]

    if isinstance(node, TpuProjectExec):
        from ..ops.expression import Alias, AttributeReference, \
            BoundReference
        for e in node.exprs:
            if e.data_type is T.STRING:
                inner = e.children[0] if isinstance(e, Alias) else e
                _require(isinstance(inner, (AttributeReference,
                                            BoundReference)),
                         "string-PRODUCING expression over the mesh "
                         "(could yield flat per-shard payloads)")
        child = _compile(node.children[0], sources, n_parts, bucket_growth,
                         conf)
        bound = _bind_all(node.exprs, node.children[0].schema)
        out_schema = node.schema

        def project(env, flags):
            b = child(env, flags)
            cols = tuple(e.eval_device(b) for e in bound)
            return b.with_columns(cols, out_schema)
        return project

    if isinstance(node, TpuFilterExec):
        child = _compile(node.children[0], sources, n_parts, bucket_growth,
                         conf)
        bound = node.condition.bind(node.children[0].schema)

        def filt(env, flags):
            b = child(env, flags)
            mask = bound.eval_device(b)
            return KR.compact(b, mask.data & mask.validity)
        return filt

    if isinstance(node, TpuCoalesceBatchesExec):
        return _compile(node.children[0], sources, n_parts, bucket_growth,
                        conf)

    if isinstance(node, TpuHashAggregateExec):
        child = _compile(node.children[0], sources, n_parts, bucket_growth,
                         conf)
        child_schema = node.children[0].schema
        if not node.groupings:
            return _compile_global_agg(node, child, child_schema)
        from ..ops.expression import Alias, AttributeReference, \
            BoundReference
        for g in node.groupings:
            if g.data_type is T.STRING:
                inner = g.children[0] if isinstance(g, Alias) else g
                _require(isinstance(inner, (AttributeReference,
                                            BoundReference)),
                         "computed string grouping key over the mesh")
        groupings = _bind_all(node.groupings, child_schema)
        from ..ops import aggregates as AGG
        aggs = [AGG.AggregateExpression(a.func.bind(child_schema), a.name)
                for a in node.aggregates]
        buf_schema = node._buffer_schema()
        n_keys = len(groupings)
        key_refs = [BoundReference(i, f.data_type, f.nullable)
                    for i, f in enumerate(buf_schema)][:n_keys]
        final = finalize_agg_kernel(n_keys, node.aggregates, buf_schema,
                                    node.schema)

        def agg(env, flags):
            local = child(env, flags)
            # Mesh stays on the always-exact sort path (dense_mode=1):
            # its growth-escalation retry cannot learn dense-mode flags.
            part, _ = _aggregate_batch(local, groupings, aggs, buf_schema,
                                       n_keys, update_mode=True,
                                       dense_mode=1)
            cap = max(part.capacity // n_parts, 128)
            shuffled = _exchange_by_key(
                part, key_refs, n_parts,
                bucket_capacity(int(cap * bucket_growth)), flags)
            merged, _ = _aggregate_batch(shuffled, key_refs, aggs,
                                         buf_schema, n_keys,
                                         update_mode=False, dense_mode=1)
            return final(merged)
        return agg

    if isinstance(node, TpuShuffledHashJoinExec):
        if node.join_type == "right":
            # Mirror through the left-outer path, reordering columns.
            mirrored = TpuShuffledHashJoinExec(
                node.children[1], node.children[0], "left",
                node.right_keys, node.left_keys,
                _swap_schema(node.schema, len(node.children[0].schema)),
                node.condition, node.growth)
            inner = _compile(mirrored, sources, n_parts, bucket_growth, conf)
            n_right = len(node.children[1].schema)
            out_schema = node.schema

            def reorder(env, flags):
                b = inner(env, flags)
                cols = b.columns[n_right:] + b.columns[:n_right]
                return ColumnarBatch(cols, b.n_rows, out_schema,
                                     live=b.live)
            return reorder

        from .joins import TpuBroadcastExchangeExec
        left, right = node.children
        jt = node.join_type
        # A broadcast build side replicates via all_gather (no keyed
        # exchange needed); correctness holds for the probe-preserving
        # types. Full outer over a broadcast would duplicate the
        # unmatched-build pass per chip, so it co-partitions instead.
        build_is_bcast = isinstance(right, TpuBroadcastExchangeExec) \
            and jt in ("inner", "left", "left_semi", "left_anti")
        right_src = right.children[0] if isinstance(
            right, TpuBroadcastExchangeExec) else right
        # A mirrored right-broadcast join leaves the exchange on the probe
        # side; the wrapper is just a caching layer, so co-partition its
        # child directly.
        left = left.children[0] if isinstance(
            left, TpuBroadcastExchangeExec) else left
        lfn = _compile(left, sources, n_parts, bucket_growth, conf)
        rfn = _compile(right_src, sources, n_parts, bucket_growth, conf)
        lkeys = _bind_all(node.left_keys, left.schema)
        rkeys = _bind_all(node.right_keys, right_src.schema)
        out_schema = node.schema
        kernel = hash_join_kernel(jt, lkeys, rkeys, out_schema)
        post = join_post_filter(node.condition, out_schema)
        unmatched = unmatched_build_kernel(left.schema, out_schema) \
            if jt == "full" else None

        def join(env, flags):
            probe = lfn(env, flags)
            build = rfn(env, flags)
            if build_is_bcast:
                build = _replicate(build)
            else:
                # Co-partition both sides: equal keys meet on one chip, so
                # the ordinary local join kernel is globally correct for
                # every join type (each unmatched row exists on exactly
                # one chip).
                pcap = bucket_capacity(
                    max(int(probe.capacity * bucket_growth) // n_parts, 128))
                bcap = bucket_capacity(
                    max(int(build.capacity * bucket_growth) // n_parts, 128))
                probe = _exchange_by_key(probe, lkeys, n_parts, pcap, flags)
                build = _exchange_by_key(build, rkeys, n_parts, bcap, flags)
            out_cap = bucket_capacity(
                max(int(probe.capacity * node.growth * bucket_growth), 128))
            if jt in ("left_semi", "left_anti"):
                out, _ = kernel(probe, build, out_cap)
                out = ColumnarBatch(out.columns, out.n_rows, out_schema,
                                    live=out.live)
            else:
                (out, hits), total = kernel(probe, build, out_cap)
                flags.append(jax.lax.psum(
                    (total > out_cap).astype(jnp.int32), PART_AXIS) > 0)
                if post is not None:
                    out = post(out)
                if jt == "full":
                    tail = unmatched(build, hits)
                    out = _coalesce_device([out, tail])
            return out
        return join

    if isinstance(node, TpuSortExec):
        return _compile_sort(node, sources, n_parts, bucket_growth, conf)

    raise NotMeshCapable(type(node).__name__)


#: samples per shard for the range-partition bounds; P*64 candidates give
#: boundary error O(1/64) of a shard, well inside the 2x bucket slack.
_SORT_SAMPLES = 64


def _compile_sort(node, sources: List, n_parts: int, bucket_growth: float,
                  conf):
    """Distributed ORDER BY — range-exchange + per-chip sort, never a
    collect-then-sort: each shard samples its first sort key, the samples
    all_gather into global range bounds, rows exchange to the chip owning
    their key range (ties share one chip because bounds are VALUES), and
    the ordinary local sort kernel finishes each shard. Shard s then holds
    global range s, so the collect's in-order concatenation IS the total
    order — the reference's GpuRangePartitioner + per-partition
    GpuSortExec stage shape, as one SPMD program."""
    child = _compile(node.children[0], sources, n_parts, bucket_growth,
                     conf)
    schema = node.schema
    orders = node.orders
    from ..ops.expression import Alias, AttributeReference, BoundReference
    for o in orders:
        if o.child.data_type is T.STRING:
            inner = o.child.children[0] if isinstance(o.child, Alias) \
                else o.child
            _require(isinstance(inner, (AttributeReference, BoundReference)),
                     "computed string sort key over the mesh")
    key_exprs = _bind_all([o.child for o in orders], schema)
    asc = [o.ascending for o in orders]
    nfirst = [o.effective_nulls_first for o in orders]

    def rank_lane(col):
        """Orderable per-row lane in ASCENDING rank space for the first
        key: dict codes for (sorted-dict) strings, raw data otherwise.
        Descending flips with bitwise NOT for integers (order-reversing
        with no overflow at INT_MIN, where negation wraps) and negation
        for floats."""
        lane = col.codes if col.is_dict else col.data
        if col.is_dict:
            # Engine invariant: mesh strings are upload-dictionary-encoded,
            # whose dictionaries are unique+sorted (codes order == string
            # order). Exchanges preserve the flag.
            assert col.dict_sorted, "unsorted dict reached the mesh sort"
        if not asc[0]:
            lane = jnp.negative(lane) \
                if jnp.issubdtype(lane.dtype, jnp.floating) else ~lane
        return lane

    def sortfn(env, flags):
        b = child(env, flags)
        b = KR.physical(b)              # sampling reads the [0, n) prefix
        keys = [e.eval_device(b) for e in key_exprs]
        k0 = keys[0]
        lane = rank_lane(k0)
        n = b.n_rows
        # -- sampled global bounds ---------------------------------------
        pos = (jnp.arange(_SORT_SAMPLES, dtype=jnp.int32) * n) \
            // _SORT_SAMPLES
        samp = lane[jnp.clip(pos, 0, lane.shape[0] - 1)]
        sflag = (jnp.arange(_SORT_SAMPLES, dtype=jnp.int32) < n) \
            & k0.validity[jnp.clip(pos, 0, lane.shape[0] - 1)]
        if jnp.issubdtype(lane.dtype, jnp.floating):
            # NaN keys route explicitly (below), never into the bounds.
            sflag = sflag & ~jnp.isnan(samp)
        all_s = jax.lax.all_gather(samp, PART_AXIS).reshape(-1)
        all_f = jax.lax.all_gather(sflag, PART_AXIS).reshape(-1)
        if all_s.dtype == jnp.bool_:
            all_s = all_s.astype(jnp.int32)
            lane = lane.astype(jnp.int32)
        hi = jnp.asarray(jnp.finfo(all_s.dtype).max
                         if jnp.issubdtype(all_s.dtype, jnp.floating)
                         else jnp.iinfo(all_s.dtype).max, all_s.dtype)
        ordered = jnp.sort(jnp.where(all_f, all_s, hi))
        total = all_f.sum()
        b_idx = (jnp.arange(1, n_parts) * total) // n_parts
        bounds = jnp.where(
            total > 0,
            ordered[jnp.clip(b_idx, 0, ordered.shape[0] - 1)], hi)
        # -- per-row destination -----------------------------------------
        pid = jnp.zeros(lane.shape[0], jnp.int32)
        for j in range(n_parts - 1):
            pid = pid + (lane > bounds[j]).astype(jnp.int32)
        if jnp.issubdtype(lane.dtype, jnp.floating):
            # Spark: NaN is the LARGEST value — last shard ascending,
            # shard 0 descending (rank space already folds direction for
            # finite values, but every NaN comparison is False).
            nan_dest = n_parts - 1 if asc[0] else 0
            pid = jnp.where(jnp.isnan(lane), nan_dest, pid)
        # nulls-first (w.r.t. the ORDER BY direction) puts nulls on shard
        # 0; the asc/desc direction is already folded into rank space.
        null_dest = 0 if nfirst[0] else n_parts - 1
        pid = jnp.where(k0.validity, pid, null_dest)
        # -- range exchange + local sort ---------------------------------
        bucket = bucket_capacity(
            max(int(2 * b.capacity * bucket_growth) // n_parts, 128))
        shuffled = _exchange_by_pid(b, pid, n_parts, bucket, flags)
        keys2 = [e.eval_device(shuffled) for e in key_exprs]
        return KR.sort_batch_by_columns(shuffled, keys2, asc, nfirst)
    return sortfn


def _compile_global_agg(node, child, child_schema):
    """Global (no-key) aggregate over the mesh: local partial buffers per
    shard, then ONE cross-chip collective per buffer (psum/pmin/pmax over
    ICI — no keyed exchange needed), finalize, and emit the single row on
    chip 0 only."""
    from ..ops import aggregates as AGG
    from ..ops.kernels.groupby import _max_value, _min_value
    aggs = [AGG.AggregateExpression(a.func.bind(child_schema), a.name)
            for a in node.aggregates]
    buf_schema = node._buffer_schema()
    merge_ops = [s.merge_op for a in aggs for s in a.func.buffers()]
    for op in merge_ops:
        _require(op in ("sum", "count", "min", "max"),
                 f"global-agg merge op {op!r} over the mesh")
    final = finalize_agg_kernel(0, node.aggregates, buf_schema,
                                node.schema)

    def gagg(env, flags):
        local = child(env, flags)
        part, _ = _aggregate_batch(local, [], aggs, buf_schema, 0,
                                   update_mode=True, dense_mode=1)
        row0 = jnp.arange(part.capacity, dtype=jnp.int32) == 0
        cols = []
        for c, op in zip(part.columns, merge_ops):
            valid = c.validity & row0
            any_valid = jax.lax.pmax(valid.astype(jnp.int32),
                                     PART_AXIS) > 0
            if op in ("sum", "count"):
                data = jax.lax.psum(
                    jnp.where(valid, c.data, jnp.zeros((), c.data.dtype)),
                    PART_AXIS)
            elif op == "min":
                data = jax.lax.pmin(
                    jnp.where(valid, c.data, _max_value(c.data.dtype)),
                    PART_AXIS)
            else:
                data = jax.lax.pmax(
                    jnp.where(valid, c.data, _min_value(c.data.dtype)),
                    PART_AXIS)
            v = any_valid & row0
            cols.append(DeviceColumn(
                data=jnp.where(v, data, jnp.zeros((), data.dtype)),
                validity=v, dtype=c.dtype))
        mine = jax.lax.axis_index(PART_AXIS) == 0
        n = jnp.where(mine, 1, 0).astype(jnp.int32)
        merged = ColumnarBatch(tuple(cols), n, buf_schema)
        return final(merged)
    return gagg


def _replicate(batch: ColumnarBatch) -> ColumnarBatch:
    """all_gather every chip's shard and compact: the mesh broadcast —
    every chip ends up with the full (small) table resident locally.
    Dict strings gather their code lane; the dictionary is already
    replicated."""
    def ag(x):
        return jax.lax.all_gather(x, PART_AXIS, axis=0, tiled=True)
    live_g = ag(batch.row_mask())
    cols = []
    for c in batch.columns:
        if c.is_dict:
            cols.append(DeviceColumn(
                data=c.data, validity=ag(c.validity), dtype=c.dtype,
                offsets=c.offsets, max_bytes=c.max_bytes,
                codes=ag(c.codes), dict_sorted=c.dict_sorted))
        else:
            cols.append(DeviceColumn(data=ag(c.data),
                                     validity=ag(c.validity),
                                     dtype=c.dtype))
    total_cap = live_g.shape[0]
    gb = ColumnarBatch(tuple(cols), jnp.asarray(total_cap, jnp.int32),
                       batch.schema)
    return KR.compact(gb, live_g)


def _encoding_fingerprint(node) -> tuple:
    """Per-source string-encoding layout (dict vs flat), which lives in the
    DATA (DeviceSourceExec.partitions — excluded from plan signatures), so
    mesh cache keys must carry it explicitly: capability and the compiled
    program both depend on it."""
    out = []

    def walk(n):
        if isinstance(n, DeviceSourceExec):
            per_col = []
            for ci, f in enumerate(n.schema):
                if f.data_type is T.STRING:
                    per_col.append(all(
                        b.columns[ci].is_dict
                        for p in n.partitions for b in p))
                else:
                    per_col.append(None)
            out.append(tuple(per_col))
            return
        kids = list(n.children)
        if isinstance(n, TpuShuffledHashJoinExec) and n.join_type == "right":
            kids = [n.children[1], n.children[0]]
        for c in kids:
            walk(c)
    walk(node)
    return tuple(out)


def _sort_mesh_ok(node) -> bool:
    """Static twin of _compile_sort's gates: the in-mesh range sort needs
    every STRING sort key to be a direct column reference (computed string
    keys could yield flat per-shard payloads)."""
    from ..ops.expression import Alias, AttributeReference, BoundReference
    for o in node.orders:
        if o.child.data_type is T.STRING:
            inner = o.child.children[0] if isinstance(o.child, Alias) \
                else o.child
            if not isinstance(inner, (AttributeReference, BoundReference)):
                return False
    return True


def _split_tail(plan):
    """Split trailing single-chip finishers (limit / top-k / project /
    coalesce above the last wide op) off the mesh core: a LIMIT's result
    is tiny by contract, so it finishes on the collected output through
    the ordinary streaming path — the reference likewise finishes LIMIT
    driver-side after its accelerated stages. ORDER BY is NOT peeled when
    _compile_sort can take it: TpuSortExec compiles in-mesh as a
    range-exchange + per-chip sort, so sort tails stay distributed; a
    sort OUTSIDE that scope (computed string key) peels like a limit
    rather than disqualifying the whole plan from the mesh."""
    from .execs import TpuLimitExec, TpuLocalLimitExec, TpuTopKExec
    always_peel = (TpuTopKExec, TpuLimitExec, TpuLocalLimitExec)
    narrow = (TpuProjectExec, TpuCoalesceBatchesExec)

    def peelable(n):
        if isinstance(n, always_peel) or isinstance(n, narrow):
            return True
        return isinstance(n, TpuSortExec) and not _sort_mesh_ok(n)

    def prefix_has_ordered(n):
        while peelable(n):
            if isinstance(n, always_peel) or isinstance(n, TpuSortExec):
                return True
            n = n.children[0]
        return False

    tail = []
    node = plan
    while peelable(node) and prefix_has_ordered(node):
        tail.append(node)
        node = node.children[0]
    return tail, node


def mesh_capable(root, conf) -> bool:
    if not isinstance(root, DeviceToHostExec):
        return False
    sig = ("mesh_capable", _plan_sig(root.children[0]),
           _encoding_fingerprint(root.children[0]))
    cached = _MESH_CACHE.get(sig)
    if cached is None:
        try:
            _, core = _split_tail(root.children[0])
            _compile(core, [], 2, 1.0, conf)
            cached = True
        except NotMeshCapable:
            cached = False
        _MESH_CACHE[sig] = cached  # GIL-atomic last-wins probe cache; concurrency: ignore
    return cached


_MESH_CACHE: Dict[tuple, object] = {}


def clear_mesh_cache() -> None:
    _MESH_CACHE.clear()


def _is_scan_source(node) -> bool:
    """Upload-at-execution source nodes: a host scan behind its upload
    transition, or the device parquet decoder."""
    from ..io.orc_device import TpuOrcScanExec
    from ..io.parquet_device import TpuParquetScanExec
    from .execs import HostToDeviceExec
    return isinstance(node, (HostToDeviceExec, TpuParquetScanExec,
                             TpuOrcScanExec))


def _collect_sources(node, out: List) -> None:
    """Source nodes in the exact order _compile visits them (a mirrored
    right join compiles its children swapped)."""
    if isinstance(node, DeviceSourceExec) or _is_scan_source(node):
        out.append(node)
        return
    kids = list(node.children)
    if isinstance(node, TpuShuffledHashJoinExec) \
            and node.join_type == "right":
        kids = [node.children[1], node.children[0]]
    for c in kids:
        _collect_sources(c, out)


def _shard_source(batch: ColumnarBatch, mesh: Mesh, n_parts: int):
    """Lay a source batch out across the mesh: shard s owns rows
    [s*shard_cap, (s+1)*shard_cap); per-shard live counts derive from the
    traced n_rows with no host sync.

    Per column, the sharded LANE is (data, validity) for fixed-width and
    (codes, validity) for dict strings, whose (payload, offsets) ride
    separately as REPLICATED arrays. Returns (lanes, counts, shard_cap,
    kinds, sides): ``kinds`` is the static per-column descriptor the
    traced program specializes on."""
    shard_cap = bucket_capacity(max(-(-batch.capacity // n_parts), 128))
    global_cap = shard_cap * n_parts
    sharding = NamedSharding(mesh, PartitionSpec(PART_AXIS))
    kinds = tuple(
        ("dict", c.max_bytes, c.dict_sorted) if c.is_dict else ("fixed",)
        for c in batch.columns)

    def build_pad():
        def pad(batch):
            cols = []
            for c in batch.columns:
                lane = c.codes if c.is_dict else c.data
                pad_n = global_cap - c.capacity
                cols.append((jnp.pad(lane, (0, pad_n)),
                             jnp.pad(c.validity, (0, pad_n))))
            counts = jnp.clip(
                batch.n_rows
                - jnp.arange(n_parts, dtype=jnp.int32) * shard_cap,
                0, shard_cap).astype(jnp.int32)
            return cols, counts
        return pad

    pad = cached_kernel(
        "mesh_shard_pad",
        kernel_key(n_parts, shard_cap, batch.schema, batch.capacity, kinds),
        build_pad)
    cols, counts = pad(batch)
    cols = [(jax.device_put(d, sharding), jax.device_put(v, sharding))
            for d, v in cols]
    counts = jax.device_put(counts, sharding)
    repl = NamedSharding(mesh, PartitionSpec())
    sides = tuple(
        (jax.device_put(c.data, repl), jax.device_put(c.offsets, repl))
        if c.is_dict else ()
        for c in batch.columns)
    return cols, counts, shard_cap, kinds, sides


def _mesh_fault_check(ctx) -> None:
    """Deterministic device-loss seam (ISSUE 19). The injector's
    ``mesh.collect`` site stands in for a chip/host dying mid-dispatch:
    a scheduled ``deviceLoss`` raises the typed
    :class:`~..parallel.mesh.MeshDegradedError` BEFORE the SPMD program
    launches, so the failover travels the exact path a real loss takes —
    TRANSIENT classification, session failover record, single-chip
    re-run (docs/fault-tolerance.md#degraded-mesh-fallback)."""
    from ..utils.fault_injection import register_site
    register_site("mesh.collect")
    injector = getattr(ctx, "fault_injector", None)
    if injector is None:
        return
    flavor = injector.check_mesh("mesh.collect")
    if flavor == "deviceLoss":
        raise MeshDegradedError(
            "injected device loss at mesh.collect (mesh.deviceLoss)")


def mesh_collect(root: DeviceToHostExec, ctx: ExecContext,
                 mesh: Optional[Mesh] = None
                 ) -> Tuple[Optional[pa.Table], bool]:
    """Run a mesh-capable plan as one SPMD program over the device mesh.
    Returns (table, overflowed).

    A backend error that reads as device loss (runtime disconnect /
    device-health markers, :func:`~..parallel.mesh.is_device_loss`) is
    re-raised as the typed :class:`~..parallel.mesh.MeshDegradedError`
    so the session fails over to the single-chip path instead of
    surfacing an opaque XlaRuntimeError."""
    _mesh_fault_check(ctx)
    try:
        tail, core = _split_tail(root.children[0])
        if tail:
            table, overflowed = _mesh_core_collect(core, ctx, mesh)
            if overflowed or table is None:
                return None, True
            # Finish sort/limit/project on the (small) collected result
            # via the ordinary streaming path.
            from ..plan.physical import collect_partitions
            src = DeviceSourceExec(
                [[ColumnarBatch.from_arrow(rb)
                  for rb in table.combine_chunks().to_batches()]],
                core.schema)
            plan = src
            for op in reversed(tail):
                plan = op.with_children([plan])
            out = collect_partitions(DeviceToHostExec(plan), ctx)
            return out, False
        return _mesh_core_collect(core, ctx, mesh)
    except MeshDegradedError:
        raise
    except Exception as e:  # tpu-lint: ignore — re-raised unless device loss; XLA surfaces DATA_LOSS as varying exception types
        if is_device_loss(e):
            raise MeshDegradedError(
                f"device loss during mesh dispatch: {e}") from e
        raise


def _mesh_core_collect(device_plan, ctx: ExecContext,
                       mesh: Optional[Mesh] = None
                       ) -> Tuple[Optional[pa.Table], bool]:
    mesh = mesh or make_mesh()
    n_parts = mesh.devices.size
    bucket_growth = float(ctx.join_growth)
    sig = (_plan_sig(device_plan), _encoding_fingerprint(device_plan),
           n_parts, bucket_growth, ctx.conf.collect_guess_rows)
    entry = _MESH_CACHE.get(sig)
    if entry is None:
        sources: List = []
        fn = _compile(device_plan, sources, n_parts, bucket_growth, ctx.conf)
        entry = {"fn": fn, "n_sources": len(sources), "jit": {}}
        _MESH_CACHE[sig] = entry  # GIL-atomic last-wins compile cache; concurrency: ignore
    # The CURRENT plan's source batches, in _compile's traversal order.
    cur_sources: List = []
    _collect_sources(device_plan, cur_sources)
    assert len(cur_sources) == entry["n_sources"]

    sharded = []
    for s in cur_sources:
        if isinstance(s, DeviceSourceExec):
            batches = [b for p in s.partitions for b in p]
        else:  # scan source: execute now (host decode + upload)
            batches = [b for p in s.execute(ctx) for b in p]
        if batches:
            # _shard_source lays rows out positionally — materialize any
            # lazily-filtered cached batch first.
            batch = KR.physical_jit(_coalesce_device(batches))
        else:
            import pyarrow as _pa
            rb = _pa.RecordBatch.from_arrays(
                [_pa.array([], type=f.type)
                 for f in T.schema_to_arrow(s.schema)],
                schema=T.schema_to_arrow(s.schema))
            batch = ColumnarBatch.from_arrow(rb, 128)
        sharded.append(_shard_source(batch, mesh, n_parts))
    shard_caps = tuple(sc for _, _, sc, _, _ in sharded)
    src_kinds = tuple(k for _, _, _, k, _ in sharded)
    schemas = tuple(s.schema for s in cur_sources)

    run = entry["jit"].get((shard_caps, src_kinds))
    if run is None:
        fn = entry["fn"]

        def spmd(source_cols, source_counts, source_sides):
            env = {}
            for i, (cols, counts, sides) in enumerate(
                    zip(source_cols, source_counts, source_sides)):
                n = counts[0]
                cap = cols[0][0].shape[0]
                live = jnp.arange(cap, dtype=jnp.int32) < n
                dcs = []
                for (lane, validity), side, kind, f in zip(
                        cols, sides, src_kinds[i], schemas[i]):
                    validity = validity & live
                    lane = jnp.where(validity, lane,
                                     jnp.zeros((), lane.dtype))
                    if kind[0] == "dict":
                        payload, offsets = side
                        dcs.append(DeviceColumn(
                            data=payload, validity=validity,
                            dtype=f.data_type, offsets=offsets,
                            max_bytes=kind[1], codes=lane,
                            dict_sorted=kind[2]))
                    else:
                        dcs.append(DeviceColumn(data=lane,
                                                validity=validity,
                                                dtype=f.data_type))
                env[i] = ColumnarBatch(tuple(dcs), n.astype(jnp.int32),
                                       schemas[i])
            flags: List = []
            out = fn(env, flags)
            # Host assembly slices each shard's [0, n) prefix — a lazy
            # (mask-live) root must materialize inside the SPMD program.
            out = KR.physical(out)
            flag = jnp.any(jnp.stack(flags)) if flags else \
                jnp.zeros((), jnp.bool_)
            # Dict output columns: the code lane shards; the dictionary
            # buffers are shard-invariant, returned TILED (host slices
            # shard 0's copy — replicated out_specs would need invariance
            # proofs through the collectives).
            out_bufs = tuple(
                (c.codes, c.validity, c.data, c.offsets) if c.is_dict
                else (c.data, c.validity)
                for c in out.columns)
            return out_bufs, out.n_rows.reshape(1), flag.reshape(1)

        spec = PartitionSpec(PART_AXIS)
        run = jax.jit(shard_map(
            spmd, mesh=mesh,
            in_specs=(spec, spec, PartitionSpec()),
            out_specs=(spec, spec, spec)))
        entry["jit"][(shard_caps, src_kinds)] = run

    source_cols = tuple(tuple(cols) for cols, _, _, _, _ in sharded)
    source_counts = tuple(counts for _, counts, _, _, _ in sharded)
    source_sides = tuple(sides for _, _, _, _, sides in sharded)
    out_bufs, out_counts, out_flags = run(source_cols, source_counts,
                                          source_sides)
    got_bufs, counts_np, flags_np = jax.device_get(
        (out_bufs, out_counts, out_flags))
    if bool(np.any(flags_np)):
        return None, True
    out_schema = device_plan.schema
    arrow_schema = T.schema_to_arrow(out_schema)
    shard_out_cap = got_bufs[0][0].shape[0] // n_parts if got_bufs else 0
    batches = []
    for s in range(n_parts):
        n = int(counts_np[s])
        if n == 0:
            continue
        arrays = []
        for bufs, f in zip(got_bufs, out_schema):
            lo = s * shard_out_cap
            if len(bufs) == 4:  # dict string: codes shard, dict tiled
                codes, validity, payload_t, offsets_t = bufs
                n_dict = offsets_t.shape[0] // n_parts - 1
                payload = payload_t[: payload_t.shape[0] // n_parts]
                offsets = offsets_t[: n_dict + 1]
                col = DeviceColumn(
                    data=payload,
                    validity=validity[lo: lo + shard_out_cap],
                    dtype=f.data_type, offsets=offsets,
                    codes=codes[lo: lo + shard_out_cap])
                arrays.append(col.arrow_from_host(
                    (payload, col.validity, offsets, col.codes), n))
            else:
                data, validity = bufs
                col = DeviceColumn(data=data[lo: lo + shard_out_cap],
                                   validity=validity[lo: lo + shard_out_cap],
                                   dtype=f.data_type)
                arrays.append(col.arrow_from_host(
                    (col.data, col.validity), n))
        batches.append(pa.RecordBatch.from_arrays(arrays,
                                                  schema=arrow_schema))
    if not batches:
        return pa.Table.from_batches([], schema=arrow_schema), False
    return pa.Table.from_batches(batches).cast(arrow_schema), False
