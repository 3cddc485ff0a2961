"""SPMD distributed query execution over a device mesh.

This is the multi-chip "training step" of the framework: the analog of a
Spark stage boundary with a GPU-resident shuffle (SURVEY.md §3.4), recast as
one jitted SPMD program:

    per-chip:  filter -> project -> partial aggregate       (local, fused)
    exchange:  hash-partition groups -> all_to_all over ICI (the shuffle)
    per-chip:  merge aggregate of received partials         (final mode)

The whole step is one ``shard_map``-ped function under ``jit`` — XLA overlaps
the collective with compute and there is no host round-trip anywhere in the
stage, which is precisely what the reference's UCX shuffle tries to
approximate with bounce buffers and progress threads (UCX.scala:84-190).
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from .mesh import shard_map

from .. import types as T
from ..data.column import DeviceColumn
from ..ops.kernels import groupby as KG
from ..shuffle import ici
from ..shuffle.partitioning import pmod_partition, spark_hash_columns_device
from .mesh import PART_AXIS


def _col(data, valid, dtype):
    return DeviceColumn(data=data, validity=valid, dtype=dtype)


def _groupby_sum_count(key, key_valid, val, val_valid, live, n_rows,
                       key_dtype, val_dtype):
    """Local sort-based groupby: returns (gkey, gkey_valid, gsum, gcount,
    n_groups). Works on raw arrays so it composes inside shard_map."""
    cap = key.shape[0]
    kcol = _col(jnp.where(live, key, jnp.zeros((), key.dtype)),
                key_valid & live, key_dtype)
    seg, n_groups, firsts = KG.group_ids([kcol], n_rows)
    gsum, counts = KG.segment_reduce(val, val_valid & live, seg, cap, "sum",
                                     live)
    gkeys = KG.gather_group_keys([kcol], firsts, n_groups)[0]
    group_live = jnp.arange(cap, dtype=jnp.int32) < n_groups
    return (gkeys.data, gkeys.validity, gsum, counts.astype(jnp.int64),
            n_groups, group_live)


def distributed_sum_by_key(mesh: Mesh, key, key_valid, val, val_valid,
                           n_rows_per_shard,
                           key_dtype=T.LONG, val_dtype=T.LONG,
                           bucket_cap: int = None):
    """The full distributed aggregation step, jitted over the mesh.

    Inputs are globally-sharded arrays: leading dim = total capacity,
    sharded on the ``part`` axis; ``n_rows_per_shard`` is an int32[n_parts]
    array (one live count per shard). Output: per-shard group keys/sums
    (sharded the same way) plus per-shard group counts.
    """
    n_parts = mesh.devices.size
    shard_cap = key.shape[0] // n_parts
    bucket_cap = bucket_cap or shard_cap

    spec_rows = PartitionSpec(PART_AXIS)

    @functools.partial(
        shard_map, mesh=mesh,
        in_specs=(spec_rows, spec_rows, spec_rows, spec_rows, spec_rows),
        out_specs=(spec_rows, spec_rows, spec_rows, spec_rows, spec_rows),
    )
    def step(key, key_valid, val, val_valid, n_rows):
        n = n_rows[0]
        cap = key.shape[0]
        live = jnp.arange(cap, dtype=jnp.int32) < n

        # ---- local partial aggregation (update mode) ----
        gk, gkv, gs, gc, n_groups, group_live = _groupby_sum_count(
            key, key_valid, val, val_valid, live, n, key_dtype, val_dtype)

        # ---- hash partition the groups (Spark murmur3 placement) ----
        h = spark_hash_columns_device(
            [_col(gk, gkv & group_live, key_dtype)])
        pid = pmod_partition(h, n_parts)

        # ---- ICI all_to_all exchange ----
        payload = {"k": gk, "kv": gkv & group_live, "s": gs, "c": gc}
        send, send_valid, _overflow = ici.build_send_buffers(
            payload, jnp.ones(cap, jnp.bool_), pid, group_live,
            n_parts, bucket_cap)
        recv, recv_valid = ici.exchange(send, send_valid)
        flat, flat_valid, n_recv = ici.flatten_received(recv, recv_valid)

        # ---- merge aggregation of received partials ----
        rcap = flat["k"].shape[0]
        rlive = jnp.arange(rcap, dtype=jnp.int32) < n_recv
        kcol = _col(flat["k"], flat["kv"] & rlive, key_dtype)
        seg, out_groups, firsts = KG.group_ids([kcol], n_recv)
        fsum, fvalid_cnt = KG.segment_reduce(flat["s"], rlive, seg, rcap,
                                             "sum", rlive)
        fcnt, _ = KG.segment_reduce(flat["c"], rlive, seg, rcap, "sum", rlive)
        out_keys = KG.gather_group_keys([kcol], firsts, out_groups)[0]
        out_live = jnp.arange(rcap, dtype=jnp.int32) < out_groups
        # Pad/trim to the shard capacity so out shape matches in shape.
        def fit(x):
            return x[:shard_cap] if x.shape[0] >= shard_cap else jnp.pad(
                x, (0, shard_cap - x.shape[0]))
        return (fit(out_keys.data), fit(out_keys.validity & out_live),
                fit(fsum), fit(fcnt),
                jnp.full(1, out_groups, jnp.int32))

    return jax.jit(step)(key, key_valid, val, val_valid, n_rows_per_shard)
