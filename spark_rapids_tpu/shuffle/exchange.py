"""Shuffle exchange — ``GpuShuffleExchangeExecBase`` + shuffle storage.

The reference's default (serializer) shuffle path evaluates a
``GpuPartitioning`` on device, contiguous-splits the batch, and hands
``(partitionId, batch)`` pairs to Spark's shuffle with the columnar
serializer (GpuShuffleExchangeExec.scala:134-233); the opt-in GPU-resident
path caches partition tables in the device store under ``ShuffleBufferId``s
(RapidsCachingWriter, RapidsShuffleInternalManager.scala:73-149) tracked by
``ShuffleBufferCatalog`` (ShuffleBufferCatalog.scala:50).

TPU-native single-host equivalents:

* partition ids are one fused device program (partitioners.py);
* contiguousSplit = one stable device sort by partition id, then run
  boundaries slice the downloaded batch;
* the write side serializes each slice (Arrow IPC + codec, serializer.py)
  into :class:`ShuffleBufferCatalog`, which keeps payloads in host memory
  up to a budget and overflows to a spill file — the host/disk tiers of the
  reference's store chain (the device tier belongs to the multi-chip ICI
  path, shuffle/ici.py, where the exchange is an ``all_to_all`` collective
  and nothing ever leaves HBM);
* reduce-side partitions lazily deserialize + re-upload, like
  ``HostColumnarToGpu`` after Spark's shuffle.
"""

from __future__ import annotations

import os
import tempfile
from typing import Dict, List, Optional, Tuple

import numpy as np
import pyarrow as pa

from .. import types as T
from ..config import SHUFFLE_COMPRESSION_CODEC
from ..data.batch import ColumnarBatch, HostBatch
from ..memory.spill import SpillFileClosedError
from ..plan.physical import ExecContext, PhysicalPlan, _arrow_schema
from ..utils import lockdep
from ..utils.kernel_cache import cached_kernel, kernel_key
from .codec import get_codec
from .serializer import deserialize_batch, serialize_batch


class ShuffleBufferCatalog:
    """Maps (shuffle_id, map_id, reduce_id) -> serialized shuffle blocks;
    lifecycle mirrors ShuffleBufferCatalog.scala:50 (register on write, free
    on shuffle unregister). Payloads overflow from host memory to a spill
    file beyond ``host_budget_bytes``.

    Durability (ISSUE 7): every block records its CRC32C at registration
    and every payload read verifies it — across all three storage tiers
    (arena, plain bytes, disk) and across the wire (the stored checksum
    rides protocol-v3 META/FETCH). Verification failures raise the typed
    :class:`~.transport.ShuffleBlockCorruptError`, which the read path
    recovers from via lineage recompute (:class:`MapOutputTracker`) —
    corrupt bytes never deserialize into an answer.

    Async-spill discipline (ISSUE 11, mirroring ``BufferCatalog``): the
    catalog lock brackets only bookkeeping — disk-tier appends, reads,
    and compaction rewrites all run OFF the lock (bounded by the
    ``spark.rapids.tpu.spill.ioThreads`` lane slots), so one reduce
    task's disk read never stalls every writer and reader of the
    catalog. Disk reads snapshot the block's range under the lock, read
    atomically under the SpillFile's own io_ok lock, and re-validate the
    range afterward; while a compaction is claimed, disk readers stand
    aside on the catalog's state condition."""

    def __init__(self, host_budget_bytes: int = 1 << 30,
                 spill_dir: Optional[str] = None,
                 verify_checksums: bool = True,
                 io_threads: int = 2):
        self.host_budget = host_budget_bytes
        self.verify_checksums = verify_checksums
        self._blocks: Dict[Tuple[int, int, int], object] = {}
        self._crcs: Dict[Tuple[int, int, int], int] = {}
        self._host_bytes = 0
        # Reentrant: the off-lock disk protocol double-checks the lazy
        # SpillFile init from paths that may already hold the lock.
        self._lock = lockdep.rlock("ShuffleBufferCatalog._lock")
        #: compaction exclusion channel (shares the catalog lock — waits
        #: release it, exactly like BufferCatalog's per-buffer conds)
        self._state_cond = lockdep.condition_on(self._lock)
        self._compacting = False
        #: set by close(): late off-lock disk appends/reads stand down
        #: instead of lazily resurrecting a fresh SpillFile (stray temp
        #: dir leak) or re-installing blocks into the cleared catalog
        #: (mirrors BufferCatalog._closed)
        self._closed = False
        #: disk appends in flight (range not yet published): a compaction
        #: snapshot would miss those bytes and the rewrite would drop them
        #: — _claim_compact refuses while > 0 (mirrors BufferCatalog)
        self._disk_appends = 0
        self._spill_dir = spill_dir
        self._spill_file = None
        import threading
        self._io_slots = threading.BoundedSemaphore(max(1, int(io_threads))) \
            if int(io_threads) > 0 else None
        # Host tier storage: serialized blocks go into ONE native arena
        # region (native/arena.cpp, the AddressSpaceAllocator analog)
        # instead of per-block Python bytes; arena-full or no-native falls
        # back to bytes, over-budget falls through to disk.
        from ..native.arena import HostArena
        self._arena = HostArena(host_budget_bytes)
        self.metrics = {"blocks": 0, "bytes_written": 0, "spilled_blocks": 0,
                        "checksum_failures": 0}

    def _disk(self):
        # Double-checked under the (reentrant) catalog lock so off-lock
        # readers/writers can resolve it without racing the lazy init.
        f = self._spill_file
        if f is None:
            with self._lock:
                if self._closed:
                    # Backstop: never lazily recreate a SpillFile after
                    # close() removed it (mirrors BufferCatalog._disk).
                    raise SpillFileClosedError("shuffle catalog is closed")
                if self._spill_file is None:
                    from ..memory.spill import SpillFile
                    self._spill_file = SpillFile(
                        self._spill_dir, verify=self.verify_checksums)
                f = self._spill_file
        return f

    def _io_lane(self):
        """Bounds concurrent disk-tier I/O to the spill-IO lane width."""
        import contextlib
        return self._io_slots if self._io_slots is not None \
            else contextlib.nullcontext()

    def add_block(self, shuffle_id: int, map_id: int, reduce_id: int,
                  payload: bytes):
        from ..utils import checksum as CK
        crc = CK.crc32c(payload)  # checksummed OFF the catalog lock
        key = (shuffle_id, map_id, reduce_id)
        with self._lock:
            if self._closed:
                # Same silent-drop contract as the disk-tier close-race
                # interleavings below: a post-close add must not
                # resurrect blocks (or byte accounting) into the
                # cleared catalog — its consumers are gone.
                return
            to_disk = self._host_bytes + len(payload) > self.host_budget
            if not to_disk:
                self._crcs[key] = crc
                self.metrics["blocks"] += 1
                self.metrics["bytes_written"] += len(payload)
                if self._arena.available:
                    off = self._arena.put(payload)
                    if off is not None:
                        self._blocks[key] = ("arena", off, len(payload))
                        self._host_bytes += len(payload)
                        return
                self._blocks[key] = payload
                self._host_bytes += len(payload)
                return
        # Disk tier: the append (file open + write) runs off-lock on the
        # IO lane; the block publishes under the lock afterward — a
        # reader never sees a half-written range, and writers of OTHER
        # blocks never queue behind this one's disk write. Appends
        # exclude compaction both ways (mirrors BufferCatalog's
        # _spill_host_job): stand aside while a claimed rewrite runs,
        # and hold _disk_appends so no claim's live snapshot can miss
        # this appended-but-unpublished range (the rewrite would drop
        # the bytes and this publish would install a stale offset).
        with self._lock:
            while self._compacting and not self._closed:
                self._state_cond.wait(timeout=1.0)
            if self._closed:
                # close() already removed the spill file: drop the block
                # (the catalog's consumers are gone) rather than
                # resurrect a fresh file for it.
                return
            self._disk_appends += 1
        try:
            with self._io_lane():
                offset, length = self._disk().append(payload)
        except SpillFileClosedError:
            # close() landed between the pre-gate and the append (the
            # typed error covers both the _disk() backstop and the
            # closed-aware SpillFile refusing open('ab') re-creation):
            # settle as the same silent drop every neighboring
            # interleaving of this race gets, instead of failing the
            # writer task during an otherwise-clean shutdown.
            with self._lock:
                self._disk_appends -= 1
                self._state_cond.notify_all()
            return
        except BaseException:  # tpu-lint: ignore — undo the append hold
            with self._lock:
                self._disk_appends -= 1
            raise
        compact_ready = False
        with self._lock:
            self._disk_appends -= 1
            if self._closed:
                # close() raced the off-lock append — the range died
                # with the closed spill file; do not re-install the
                # block into the cleared catalog.
                self._state_cond.notify_all()
                return
            self._crcs[key] = crc
            self._blocks[key] = ("disk", offset, length)
            self.metrics["blocks"] += 1
            self.metrics["bytes_written"] += len(payload)
            self.metrics["spilled_blocks"] += 1
            # Pick up a compaction our in-flight append deferred.
            compact_ready = self._claim_compact()
        if compact_ready:
            self._compact_now()

    def _read_block(self, v) -> bytes:
        """Host-tier payload copy (caller holds _lock); disk tiers go
        through :meth:`_snapshot_block`'s off-lock protocol instead."""
        if isinstance(v, tuple):
            return self._arena.get(v[1], v[2])
        return v

    def _snapshot_block(self, key: Tuple[int, int, int]
                        ) -> Tuple[bytes, Optional[int]]:
        """(payload, crc-to-verify-or-None) for one block. Host tiers
        (arena, bytes) copy under the lock — host memcpy, no I/O. The
        disk tier reads OFF the lock: snapshot the range under the lock,
        read it atomically under the SpillFile's own io_ok lock, then
        re-validate that no compaction moved it (retrying with the
        installed range if one did). NO verification happens here — the
        CRC pass runs in :meth:`_verify_payload` outside the lock."""
        while True:
            with self._lock:
                while self._compacting:
                    self._state_cond.wait(timeout=1.0)
                v = self._blocks[key]
                crc = self._crcs.get(key) if self.verify_checksums else None
                if not (isinstance(v, tuple) and v[0] == "disk"):
                    return self._read_block(v), crc
            with self._io_lane():
                payload = self._disk().read_with_crc(v[1], v[2])[0]
            with self._lock:
                if not self._compacting and self._blocks.get(key) == v:
                    return payload, crc

    def _verify_payload(self, key: Tuple[int, int, int], payload: bytes,
                        crc: Optional[int]) -> bytes:
        """Verify OUTSIDE the catalog lock (the payload is a private
        copy; a full-payload CRC pass must not serialize every other
        reader and writer on the catalog-wide lock)."""
        if crc is None:
            return payload
        from ..utils import checksum as CK
        from .transport import ShuffleBlockCorruptError
        try:
            CK.verify(payload, crc, f"shuffle block {key}")
        except CK.ChecksumError as e:
            with self._lock:
                self.metrics["checksum_failures"] += 1
            raise ShuffleBlockCorruptError(key, crc, e.actual,
                                           source="catalog") from None
        return payload

    def _keys_for_reduce(self, shuffle_id: int, reduce_id: int,
                         map_range: Optional[Tuple[int, int]]
                         ) -> List[Tuple[int, int, int]]:
        """Sorted block keys of one reduce partition; callers hold _lock.
        The single source of block addressing — META and payload reads must
        agree on it."""
        return sorted(k for k in self._blocks
                      if k[0] == shuffle_id and k[2] == reduce_id
                      and (map_range is None
                           or map_range[0] <= k[1] < map_range[1]))

    def blocks_for_reduce(self, shuffle_id: int, reduce_id: int,
                          map_range: Optional[Tuple[int, int]] = None
                          ) -> List[bytes]:
        return [p for _mid, p in self.blocks_with_ids_for_reduce(
            shuffle_id, reduce_id, map_range)]

    def blocks_with_ids_for_reduce(self, shuffle_id: int, reduce_id: int,
                                   map_range: Optional[Tuple[int, int]]
                                   = None):
        """Lazily yield (map_id, payload) per block of the reduce
        partition, verified, in map order — the streaming read the
        recovery path needs (it must know WHICH map outputs were already
        delivered before a corruption surfaced). Keys snapshot under the
        lock; each payload snapshots at yield time (position-independent
        keying makes that safe against concurrent registration; disk
        payloads read off-lock) and verifies outside the lock."""
        with self._lock:
            keys = self._keys_for_reduce(shuffle_id, reduce_id, map_range)
        for k in keys:
            payload, crc = self._snapshot_block(k)
            yield k[1], self._verify_payload(k, payload, crc)

    def block_metas_for_reduce(self, shuffle_id: int, reduce_id: int,
                               map_range: Optional[Tuple[int, int]] = None
                               ) -> List[Tuple[int, int, int]]:
        """(map_id, size_bytes, crc32c) per block of the reduce
        partition, sorted by map_id — metadata only. Serving META must
        not materialize payloads (arena copies / disk reads); a k-block
        fetch then reads each payload exactly once via
        :meth:`read_block`."""
        with self._lock:
            keys = self._keys_for_reduce(shuffle_id, reduce_id, map_range)
            return [(k[1], self._blocks[k][2]
                     if isinstance(self._blocks[k], tuple)
                     else len(self._blocks[k]),
                     self._crcs.get(k, 0)) for k in keys]

    def read_block(self, shuffle_id: int, map_id: int,
                   reduce_id: int) -> bytes:
        """One block payload by its stable (shuffle, map, reduce) key — the
        reference's tag scheme. Position-independent, so blocks added
        between a client's META and FETCH can't shift addressing."""
        key = (shuffle_id, map_id, reduce_id)
        payload, crc = self._snapshot_block(key)
        return self._verify_payload(key, payload, crc)

    def read_block_with_crc(self, shuffle_id: int, map_id: int,
                            reduce_id: int) -> Tuple[bytes, int]:
        """(payload, crc32c) for the wire server: the payload is verified
        at rest before serving, and the registration checksum travels
        with it so the peer verifies end-to-end."""
        key = (shuffle_id, map_id, reduce_id)
        payload, crc = self._snapshot_block(key)
        with self._lock:
            stored = self._crcs.get(key, 0)
        self._verify_payload(key, payload, crc)
        return payload, stored

    def sizes_for_shuffle(self, shuffle_id: int
                          ) -> Dict[Tuple[int, int], int]:
        """(map_id, reduce_id) -> serialized bytes: the observed statistics
        adaptive re-planning runs on (MapStatus sizes analog)."""
        with self._lock:
            return {(m, r): (v[2] if isinstance(v, tuple) else len(v))
                    for (s, m, r), v in self._blocks.items()
                    if s == shuffle_id}

    def unregister_shuffle(self, shuffle_id: int):
        with self._lock:
            for k in [k for k in self._blocks if k[0] == shuffle_id]:
                v = self._blocks.pop(k)
                self._crcs.pop(k, None)
                if isinstance(v, tuple):
                    if v[0] == "arena":
                        self._arena.free(v[1])
                        self._host_bytes -= v[2]
                    elif v[0] == "disk" and self._spill_file is not None \
                            and not self._compacting:
                        # While a claimed rewrite runs, the offsets are
                        # about to be remapped — the install loop frees
                        # the relocated bytes of popped keys instead.
                        self._spill_file.free_range(v[1], v[2])
                else:
                    self._host_bytes -= len(v)
            compact_ready = self._claim_compact()
        if compact_ready:
            self._compact_now()

    def _claim_compact(self) -> bool:
        """True when half the spill file is dead AND this caller claimed
        the single compaction slot (caller holds _lock; must then call
        :meth:`_compact_now` after releasing it)."""
        from ..memory.spill import DISK_COMPACT_FRACTION
        f = self._spill_file
        if f is None or self._compacting or self._disk_appends > 0 \
                or f.freed_bytes == 0 \
                or f.freed_fraction() < DISK_COMPACT_FRACTION:
            # _disk_appends > 0: an unpublished append would be invisible
            # to the live snapshot; the appender's publish re-claims.
            return False
        self._compacting = True
        return True

    def _compact_now(self):
        """Rewrite the surviving disk blocks contiguously — OFF the
        catalog lock (mirrors BufferCatalog._compact_now): snapshot and
        install bracket the rewrite under the lock, the rewrite holds
        only the SpillFile's own io_ok lock, and disk readers stand
        aside on the claimed ``_compacting`` flag."""
        f = self._spill_file
        with self._lock:
            if self._closed or f is None:
                # close() raced the claimed rewrite: the file and every
                # range died with it — release the claim and stand down
                # instead of dereferencing the nulled file (mirrors
                # BufferCatalog._compact_now).
                self._compacting = False
                self._state_cond.notify_all()
                return
            live = {k: (v[1], v[2]) for k, v in self._blocks.items()
                    if isinstance(v, tuple) and v[0] == "disk"}
        try:
            new_ranges = f.compact(live)
        except SpillFileClosedError:
            # close() landed between the snapshot and the rewrite (the
            # closed-aware SpillFile refused): same stand-down.
            with self._lock:
                self._compacting = False
                self._state_cond.notify_all()
            return
        # Release the claim and re-raise: classification-neutral.
        except BaseException:  # tpu-lint: ignore
            with self._lock:
                self._compacting = False
                self._state_cond.notify_all()
            raise
        with self._lock:
            for k, (off, length) in new_ranges.items():
                if k in self._blocks:
                    self._blocks[k] = ("disk", off, length)
                else:
                    # unregistered while the rewrite ran: release the
                    # relocated bytes instead of resurrecting them
                    f.free_range(off, length)
            self._compacting = False
            self._state_cond.notify_all()

    def close(self):
        with self._lock:
            # Flag first: any off-lock disk append/read still in flight
            # stands down at its next lock bracket instead of touching
            # the cleared catalog or recreating the spill file.
            self._closed = True
            self._blocks.clear()
            self._crcs.clear()
            self._arena.close()
            if self._spill_file is not None:
                self._spill_file.close()
                self._spill_file = None
            self._state_cond.notify_all()


class MapOutputTracker:
    """Map-output lineage registry + peer health — the driver-side
    ``MapOutputTracker`` / stage-retry analog, session-scoped so
    blacklists and recompute budgets survive per-query context rebuilds.

    Two recovery roles (ISSUE 7):

    * **Lineage recompute.** Each live shuffle registers a deterministic
      closure that re-runs its map side for ONE reduce partition and
      returns ``[(map_id, payload)]``. When the fetch plane exhausts
      retries (:class:`~.net.ShuffleFetchFailedError`) or a block fails
      checksum past refetch
      (:class:`~.transport.ShuffleBlockCorruptError`), the read path asks
      the tracker to regenerate the partition instead of failing the
      query — only map outputs not already delivered are re-yielded, and
      the regenerated bytes of already-delivered outputs must match their
      recorded checksums (a diverged recompute raises rather than mixing
      generations: never a wrong answer).
    * **Peer health.** Exhausted fetch ladders against a peer count
      toward ``spark.rapids.tpu.shuffle.net.maxPeerFailures``; a peer
      over the limit is blacklisted for the session — later reads skip
      the dial and go straight to lineage (``peersBlacklisted`` metric).

    For multi-process topologies the driver/harness can register a
    **peer lineage** callback (``set_peer_lineage``) that regenerates a
    DEAD peer's map outputs locally from its input-shard assignment —
    the Spark semantics of rescheduling a lost executor's map tasks."""

    #: recompute attempts allowed per (shuffle, reduce) before the
    #: original error propagates — repeated corruption of regenerated
    #: data means the fault is not in the stored bytes.
    MAX_RECOMPUTES = 2

    def __init__(self, conf=None):
        from ..config import SHUFFLE_NET_MAX_PEER_FAILURES
        try:
            self.max_peer_failures = int(
                conf.get(SHUFFLE_NET_MAX_PEER_FAILURES))
        except (AttributeError, TypeError):
            self.max_peer_failures = SHUFFLE_NET_MAX_PEER_FAILURES.default
        self._lineage: Dict[int, object] = {}
        self._peer_lineage = None
        self._peer_failures: Dict[Tuple[str, int], int] = {}
        self._blacklist: set = set()
        self._recomputes: Dict[Tuple[int, int], int] = {}
        #: shuffle_id -> peers holding a replication-pushed copy of every
        #: map output (ISSUE 19) — the fetch plane's hedge targets and
        #: the recovery ladder's cheaper-than-recompute rung.
        self._replicas: Dict[int, List[Tuple[str, int]]] = {}
        self._lock = lockdep.lock("MapOutputTracker._lock")
        from .net import PeerLatencyStats
        #: session-scoped per-peer fetch-latency EWMA driving the
        #: straggler hedge threshold (net.py HedgePolicy).
        self.latency = PeerLatencyStats()
        self.metrics = {"map_tasks_recomputed": 0, "recomputes": 0,
                        "peers_blacklisted": 0, "hedged_fetches": 0,
                        "hedge_wins": 0, "replica_reads": 0,
                        "recomputes_avoided_by_replica": 0}

    # -- lineage ------------------------------------------------------------
    def register_shuffle(self, shuffle_id: int, lineage) -> None:
        """``lineage(reduce_id) -> [(map_id, payload)]`` re-runs the map
        side of ``shuffle_id`` for one reduce partition (registered by
        the exchange after its write phase)."""
        with self._lock:
            self._lineage[shuffle_id] = lineage

    def unregister_shuffle(self, shuffle_id: int) -> None:
        with self._lock:
            self._lineage.pop(shuffle_id, None)
            self._replicas.pop(shuffle_id, None)
            for k in [k for k in self._recomputes if k[0] == shuffle_id]:
                del self._recomputes[k]

    # -- replication (ISSUE 19) ---------------------------------------------
    def register_replicas(self, shuffle_id: int, peers) -> None:
        """Record the peers that successfully received a FULL replication
        push of ``shuffle_id`` (net.py replicate_shuffle) — the fetch
        plane hedges against them and the recovery ladder reads them
        before paying a lineage recompute."""
        with self._lock:
            self._replicas[shuffle_id] = [tuple(p) for p in peers]

    def replicas_for(self, shuffle_id: int) -> List[Tuple[str, int]]:
        with self._lock:
            return list(self._replicas.get(shuffle_id, ()))

    def tally(self, name: str, n: int = 1) -> None:
        """Bump one self-healing counter (hedged_fetches / hedge_wins /
        replica_reads / recomputes_avoided_by_replica) — the serving
        layer's health view aggregates these across pooled sessions."""
        with self._lock:
            self.metrics[name] = self.metrics.get(name, 0) + n

    def has_lineage(self, shuffle_id: int) -> bool:
        with self._lock:
            return shuffle_id in self._lineage

    def recompute(self, shuffle_id: int, reduce_id: int, ctx=None,
                  node: str = "TpuShuffleExchangeExec"):
        """Regenerate one reduce partition's blocks from lineage, or None
        when no lineage is registered / the recompute budget for this
        partition is spent. Returns ``[(map_id, payload)]``."""
        with self._lock:
            fn = self._lineage.get(shuffle_id)
            if fn is None:
                return None
            key = (shuffle_id, reduce_id)
            if self._recomputes.get(key, 0) >= self.MAX_RECOMPUTES:
                return None
            self._recomputes[key] = self._recomputes.get(key, 0) + 1
        from ..metrics import trace as TR
        with TR.span(getattr(ctx, "trace", None), "shuffle.recompute",
                     cat="shuffle", shuffle=shuffle_id, reduce=reduce_id):
            out = fn(reduce_id)
        with self._lock:
            self.metrics["recomputes"] += 1
            self.metrics["map_tasks_recomputed"] += len(out)
        if ctx is not None and hasattr(ctx, "metric"):
            ctx.metric(node, "mapTasksRecomputed", len(out))
        return out

    # -- peer health --------------------------------------------------------
    def set_peer_lineage(self, fn) -> None:
        """``fn(peer, shuffle_id, reduce_id) -> [(map_id, payload)] |
        None`` regenerates a remote peer's map outputs locally (the
        driver knows every rank's input-shard assignment)."""
        with self._lock:
            self._peer_lineage = fn

    def recompute_peer(self, peer, shuffle_id: int, reduce_id: int,
                       ctx=None, node: str = "ShuffleFetch"):
        with self._lock:
            fn = self._peer_lineage
        if fn is None:
            return None
        from ..metrics import trace as TR
        with TR.span(getattr(ctx, "trace", None), "shuffle.recompute",
                     cat="shuffle", peer=str(tuple(peer)),
                     shuffle=shuffle_id, reduce=reduce_id):
            out = fn(peer, shuffle_id, reduce_id)
        if out is None:
            return None
        with self._lock:
            self.metrics["recomputes"] += 1
            self.metrics["map_tasks_recomputed"] += len(out)
        if ctx is not None and hasattr(ctx, "metric"):
            ctx.metric(node, "mapTasksRecomputed", len(out))
        return out

    def record_peer_failure(self, peer, ctx=None,
                            node: str = "ShuffleFetch") -> bool:
        """Count one exhausted fetch ladder against ``peer``; True when
        this failure crossed the blacklist threshold."""
        peer = tuple(peer)
        with self._lock:
            n = self._peer_failures.get(peer, 0) + 1
            self._peer_failures[peer] = n
            if self.max_peer_failures <= 0 or peer in self._blacklist \
                    or n < self.max_peer_failures:
                return False
            self._blacklist.add(peer)
            self.metrics["peers_blacklisted"] += 1
        if ctx is not None and hasattr(ctx, "metric"):
            ctx.metric(node, "peersBlacklisted", 1)
        return True

    def is_blacklisted(self, peer) -> bool:
        with self._lock:
            return tuple(peer) in self._blacklist

    def peer_failures(self, peer) -> int:
        with self._lock:
            return self._peer_failures.get(tuple(peer), 0)


def _tracker_of(ctx) -> MapOutputTracker:
    """The context's session-scoped tracker (TpuSession passes its own so
    blacklists persist across queries); bare contexts lazily get one."""
    tracker = getattr(ctx, "shuffle_tracker", None)
    if tracker is None:
        tracker = MapOutputTracker(getattr(ctx, "conf", None))
        try:
            ctx.shuffle_tracker = tracker
        except AttributeError:  # frozen test doubles
            pass
    return tracker


def _missing_from_lineage(regen, delivered, map_range, peer,
                          shuffle_id: int, reduce_id: int):
    """The ONE generation-mixing guard both recovery paths share
    (:func:`fetch_with_recovery` and the exchange's internal
    ``recovered_payloads``): given a lineage recompute of a whole reduce
    partition and the blocks already delivered downstream
    (``{map_id: crc32c-of-delivered-payload}``), return the
    ``[(map_id, payload)]`` still missing — after checking that the
    regenerated bytes of every delivered map id match what was delivered
    (serialization is deterministic, so equal content means equal
    bytes). A recompute whose segmentation diverged — possible only when
    the ORIGINAL map run OOM-split a batch that the recompute did not,
    or vice versa — fails CLOSED with a typed error naming the peer
    rather than mixing shuffle generations; with nothing delivered yet
    (the common case: corruption detected on a partition's first read)
    any segmentation is safe."""
    from ..utils import checksum as CK
    from .net import ShuffleFetchFailedError
    if map_range is not None:
        # Honor the caller's map range like the fetch did, or a
        # range-split read would see rows outside its slice twice.
        regen = [(mid, p) for mid, p in regen
                 if map_range[0] <= mid < map_range[1]]
    regen_ids = {mid for mid, _ in regen}
    diverged = not set(delivered) <= regen_ids or any(
        mid in delivered and delivered[mid] is not None
        and CK.crc32c(payload) != delivered[mid]
        for mid, payload in regen)
    if diverged:
        raise ShuffleFetchFailedError(
            tuple(peer), shuffle_id, reduce_id,
            "lineage recompute diverged from the already-delivered map "
            f"outputs {sorted(delivered)} — refusing to mix shuffle "
            "generations")
    return [(mid, p) for mid, p in regen if mid not in delivered]


def fetch_with_recovery(peer, shuffle_id: int, reduce_id: int,
                        tracker: MapOutputTracker, ctx=None,
                        node: str = "ShuffleFetch",
                        expected_map_ids=None, **iterator_kw):
    """Fetch one reduce partition from a REMOTE peer with the full
    recovery ladder (the reduce-task entry point for multi-process
    shuffle): stream-fetch with per-block verify, refetch and straggler
    hedging (:class:`~.net.RetryingBlockIterator`) -> on exhaustion or
    corruption, count the peer failure (blacklisting it past
    maxPeerFailures) and read the missing blocks from a REPLICA
    (``replicas`` kwarg or the tracker's registration — each served
    block is a lineage recompute avoided) -> then regenerate from peer
    lineage (delivered blocks are checked against the regenerated bytes
    — see :func:`_missing_from_lineage`) -> only when no rung answers,
    re-raise the typed error naming the peer. Yields payload bytes in
    map order; a blacklisted peer skips the dial entirely.

    ``expected_map_ids`` (when the caller knows the partition's full map
    set) gates the replica rung on COMPLETENESS: a replica with a hole
    (a lost replication push) is rejected rather than silently
    under-delivering the partition. Without it the replica's own
    metadata is trusted — safe for tracker-registered replicas, which
    only register after a full push."""
    from .net import RetryingBlockIterator, ShuffleFetchFailedError
    from .transport import ShuffleBlockCorruptError
    map_range = iterator_kw.get("map_range")
    replicas = [tuple(r) for r in
                (iterator_kw.pop("replicas", None)
                 or tracker.replicas_for(shuffle_id))]
    if replicas:
        iterator_kw["replicas"] = replicas  # arm the straggler hedge

    def _regenerated(delivered):
        regen = tracker.recompute_peer(peer, shuffle_id, reduce_id, ctx,
                                       node)
        if regen is None:
            return None
        return _missing_from_lineage(regen, delivered, map_range, peer,
                                     shuffle_id, reduce_id)

    def _from_replicas(delivered):
        """The missing ``[(map_id, payload)]`` from the first replica
        that answers COMPLETELY, or None — the recovery rung that costs
        a re-fetch instead of a recompute."""
        for rp in replicas:
            if rp == tuple(peer) or tracker.is_blacklisted(rp):
                continue
            rep_it = RetryingBlockIterator(
                rp, shuffle_id, reduce_id, ctx=ctx, node=node,
                with_map_ids=True, skip_map_ids=set(delivered),
                map_range=map_range)
            try:
                got = list(rep_it)
            except (OSError, ShuffleFetchFailedError):  # next rung
                tracker.record_peer_failure(rp, ctx, node)
                continue
            if expected_map_ids is not None and not (
                    set(expected_map_ids)
                    <= set(delivered) | {m for m, _ in got}):
                continue  # replica hole: not a complete answer
            if ctx is not None and hasattr(ctx, "metric"):
                ctx.metric(node, "replicaReads", len(got))
            tracker.tally("replica_reads", len(got))
            tracker.tally("recomputes_avoided_by_replica")
            return got
        return None

    if tracker.is_blacklisted(peer):
        out = _from_replicas({})
        if out is None:
            out = _regenerated({})
        if out is None:
            raise ShuffleFetchFailedError(
                tuple(peer), shuffle_id, reduce_id,
                f"peer blacklisted after {tracker.peer_failures(peer)} "
                "fetch failures and no replica or peer lineage is "
                "registered")
        for _mid, payload in out:
            yield payload
        return
    it = RetryingBlockIterator(
        tuple(peer), shuffle_id, reduce_id, ctx=ctx, node=node,
        with_map_ids=True, **iterator_kw)
    try:
        for _mid, payload in it:
            yield payload
        return
    except (ShuffleFetchFailedError, ShuffleBlockCorruptError) as e:
        tracker.record_peer_failure(peer, ctx, node)
        # The iterator already verified every delivered payload against
        # its descriptor checksum — reuse those crcs for the generation
        # guard instead of re-hashing on the healthy path.
        out = _from_replicas(dict(it.delivered_crcs))
        if out is None:
            out = _regenerated(dict(it.delivered_crcs))
        if out is None:
            raise e
    for _mid, payload in out:
        yield payload


_next_shuffle_id = [0]
#: Guards the id counter: exchanges in SIBLING fusion boundaries execute
#: concurrently on pipeline workers (exec/pipeline.py), and the previous
#: unsynchronized `+= 1; return [0]` could hand two exchanges the SAME
#: shuffle id (increment and read are separate bytecodes — another
#: worker's increment between them makes both reads return its value),
#: silently mixing two exchanges' blocks in the catalog. Found by the
#: unguarded-shared-write pass (analysis/concurrency.py); regression:
#: tests/test_lockdep.py::TestShuffleIdAllocation.
_SHUFFLE_ID_LOCK = lockdep.lock("exchange._SHUFFLE_ID_LOCK")


def _new_shuffle_id() -> int:
    with _SHUFFLE_ID_LOCK:
        _next_shuffle_id[0] += 1
        return _next_shuffle_id[0]


class _DrainLatch:
    """Runs ``action`` exactly once after ``arrive()`` has been called
    ``n`` times — the read side's early block release (every reduce
    partition drained -> unregister the shuffle before query end).

    Replaces an unsynchronized ``drained["n"] += 1`` closure counter:
    with reduce-side prefetch on, the drain bookkeeping runs on pipeline
    WORKER threads, and concurrent unlocked ``+=`` loses updates — the
    count then never reaches ``n`` and the shuffle's blocks stay pinned
    in host memory until query-end cleanup. Found by the
    unguarded-shared-write pass (analysis/concurrency.py); regression:
    tests/test_lockdep.py::TestDrainLatch."""

    def __init__(self, n: int, action):
        self._lock = lockdep.lock("exchange._DrainLatch._lock")
        self._n = n
        self._count = 0
        self._fired = False
        self._action = action

    def arrive(self) -> None:
        with self._lock:
            self._count += 1
            fire = not self._fired and self._count >= self._n
            if fire:
                self._fired = True
        if fire:
            # Outside the latch lock: the action takes the catalog lock,
            # and lock-order discipline wants no nesting here.
            self._action()


class CpuShuffleExchangeExec(PhysicalPlan):
    """Host repartitioning oracle: numpy mask split per partition."""

    def __init__(self, child: PhysicalPlan, partitioner_factory,
                 n_parts: int):
        self.children = [child]
        self.partitioner_factory = partitioner_factory
        self.n_parts = n_parts

    @property
    def schema(self):
        return self.children[0].schema

    def describe(self):
        return f"CpuShuffleExchange n={self.n_parts}"

    def execute(self, ctx: ExecContext):
        partitioner = self.partitioner_factory(
            self.children[0], ctx, columnar=False)
        outputs: List[List[HostBatch]] = [[] for _ in range(self.n_parts)]
        arrow = _arrow_schema(self.schema)
        for part in self.children[0].execute(ctx):
            for hb in part:
                if hb.num_rows == 0:
                    continue
                ids = partitioner.host_ids(hb)
                for p in range(self.n_parts):
                    mask = ids == p
                    if mask.any():
                        outputs[p].append(HostBatch(
                            hb.rb.filter(pa.array(mask)).cast(arrow)))
        return [iter(batches) for batches in outputs]


class TpuShuffleExchangeExec(PhysicalPlan):
    """Device repartitioning through the serializer path (see module doc)."""

    columnar = True
    children_columnar = True

    def __init__(self, child: PhysicalPlan, partitioner_factory,
                 n_parts: int):
        self.children = [child]
        self.partitioner_factory = partitioner_factory
        self.n_parts = n_parts

    @property
    def schema(self):
        return self.children[0].schema

    def describe(self):
        return f"TpuShuffleExchange n={self.n_parts}"

    def execute(self, ctx: ExecContext):
        import jax
        import jax.numpy as jnp
        from ..ops.kernels import rowops as KR

        partitioner = self.partitioner_factory(
            self.children[0], ctx, columnar=True)
        codec = get_codec(ctx.conf.get(SHUFFLE_COMPRESSION_CODEC) or "none")
        catalog = _shuffle_env(ctx)
        shuffle_id = _new_shuffle_id()
        n_parts = self.n_parts

        def build():
            from .partitioners import RoundRobinPartitioner

            def partition_sort(batch: ColumnarBatch):
                if isinstance(partitioner, RoundRobinPartitioner):
                    # Round-robin ids are POSITIONAL — a lazy batch must
                    # compact first so device assignment matches the host
                    # oracle's row-order assignment.
                    batch = KR.physical(batch)
                ids = partitioner.device_ids(batch)
                live = batch.row_mask()
                ids = jnp.where(live, ids, n_parts)
                iota = jnp.arange(batch.capacity, dtype=jnp.int32)
                sorted_ids, perm = jax.lax.sort((ids, iota), num_keys=1,
                                                is_stable=True)
                return KR.gather_batch(batch, perm, batch.n_rows), sorted_ids
            return partition_sort
        partition_sort = cached_kernel(
            "shuffle_partition_sort",
            kernel_key(type(partitioner).__qualname__, partitioner.__dict__,
                       n_parts),
            build)

        # WRITE side (RapidsCachingWriter analog, host-serialized payloads).
        from ..memory import retry as R
        name = self.node_name()

        def partition_split(b):
            """Device partition sort + result download for one input batch
            — the exchange's memory hazard. Block serialization and
            catalog writes stay OUTSIDE the retry: they are side-effecting
            (a retried attempt must never double-add blocks)."""
            with ctx.registry.timer(name, "opTime",
                                    trace="shuffle.partition_split"):
                sorted_batch, sorted_ids = partition_sort(b)
                rb = sorted_batch.to_arrow()
                ids_np = np.asarray(sorted_ids)[: rb.num_rows]
            return rb, ids_np

        def write_map(rb, ids_np, this_map_id):
            """Serialize one map task's partition slices into the catalog
            (host-only work — blocks are keyed by map_id, so completion
            order never affects reduce-side contents). Runs on a shared-
            pool worker under overlap, so its span parents through the
            trace-root fallback like every other worker lane."""
            from ..metrics import trace as TR
            with TR.span(getattr(ctx, "trace", None), "shuffle.map",
                         cat="shuffle", shuffle=shuffle_id,
                         map=this_map_id):
                # Contiguous runs per partition id (ids are sorted).
                starts = np.searchsorted(ids_np, np.arange(n_parts),
                                         side="left")
                ends = np.searchsorted(ids_np, np.arange(n_parts),
                                       side="right")
                for p in range(n_parts):
                    if ends[p] > starts[p]:
                        piece = rb.slice(starts[p], ends[p] - starts[p])
                        with ctx.registry.timer(
                                name, "serializationTime",
                                trace="shuffle.serialize"):
                            payload = serialize_batch(piece, codec)
                        ctx.metric(name, "shuffleBytesWritten",
                                   len(payload))
                        catalog.add_block(shuffle_id, this_map_id, p,
                                          payload)

        # Pipeline overlap: map-task serialization runs on the shared
        # pool while the NEXT batch's partition sort dispatches on the
        # device — ser/deser and device work stay concurrent. The device
        # split + its retry site stay on this thread (deterministic
        # injection schedules); catalog writes are lock-protected and
        # keyed, so completion order is irrelevant.
        from ..exec import pipeline
        import collections
        overlap = pipeline.parallel_active(ctx)
        ser_pool = pipeline.get_pool() if overlap else None
        ser_depth = pipeline.prefetch_depth(ctx.conf)
        ser_futs = collections.deque()
        map_id = 0
        try:
            for part in self.children[0].execute(ctx):
                for db in part:
                    if int(db.n_rows) == 0:
                        continue
                    # A split input batch serializes as two map tasks:
                    # row-to-partition routing is per-row, so reduce-side
                    # contents are unchanged.
                    for rb, ids_np in R.with_retry(
                            ctx, f"{name}.partitionSplit", db,
                            partition_split, split=R.halve_by_rows,
                            node=name):
                        if overlap:
                            ser_futs.append(ser_pool.submit(
                                write_map, rb, ids_np, map_id))
                            if len(ser_futs) >= max(ser_depth, 1):
                                ser_futs.popleft().result()
                        else:
                            write_map(rb, ids_np, map_id)
                        map_id += 1
        finally:
            # Every block must be in the catalog before the read side
            # plans against observed sizes (and serializer failures must
            # surface here, on the exchange, not at some later result()).
            while ser_futs:
                ser_futs.popleft().result()

        # Lineage registration (ISSUE 7, the stage-retry analog): a
        # deterministic closure that re-runs THIS exchange's map side for
        # one reduce partition — re-executing the child subtree through
        # the same cached partition kernel and serializer — so a block
        # lost to corruption or a dead transport recomputes instead of
        # failing the query. Registered with the session-scoped
        # MapOutputTracker; recovery consumers verify regenerated bytes
        # against the original checksums before trusting partial mixes.
        # Known limit: map ids count with_retry pieces, so a recompute
        # whose OOM-split schedule differs from the original write's
        # segments differently — the shared guard then fails CLOSED
        # (typed error, never mixed generations); with nothing delivered
        # yet (the common case) any segmentation recovers fine.
        tracker = _tracker_of(ctx)

        def recompute_reduce(target_p: int):
            out = []
            mid = 0
            for part in self.children[0].execute(ctx):
                for db in part:
                    if int(db.n_rows) == 0:
                        continue
                    for rb, ids_np in R.with_retry(
                            ctx, f"{name}.partitionSplit", db,
                            partition_split, split=R.halve_by_rows,
                            node=name):
                        lo = int(np.searchsorted(ids_np, target_p, "left"))
                        hi = int(np.searchsorted(ids_np, target_p,
                                                 "right"))
                        if hi > lo:
                            piece = rb.slice(lo, hi - lo)
                            out.append((mid,
                                        serialize_batch(piece, codec)))
                        mid += 1
            return out

        tracker.register_shuffle(shuffle_id, recompute_reduce)
        ctx.add_cleanup(lambda: tracker.unregister_shuffle(shuffle_id))

        # Wire plane (spark.rapids.tpu.shuffle.net.enabled): serve this
        # catalog over TCP and fetch every reduce-side block back through
        # the full protocol-v3 client — handshake, CRC32C verification,
        # conf timeouts, streaming refetch — over a real loopback socket.
        # The identical code path a remote peer takes, so the distributed
        # plane is exercised (and fault-injected) by ordinary queries.
        from ..config import SHUFFLE_NET_ENABLED, SHUFFLE_REPLICATION_FACTOR
        net_server = _net_serve(ctx, catalog) \
            if ctx.conf.get(SHUFFLE_NET_ENABLED) else None

        # Replication push (ISSUE 19): register this exchange's map
        # outputs on `replication.factor` replica peers through the
        # protocol-v5 PUT wire, CRC-verified at each replica. A dead or
        # straggling primary then answers from a replica (hedged fetch /
        # recovery rung) instead of paying a lineage recompute. Push
        # failure is DEGRADED replication — the replica is simply not
        # registered — never a query failure.
        replicas: List[Tuple[str, int]] = []
        repl_factor = int(ctx.conf.get(SHUFFLE_REPLICATION_FACTOR)) \
            if net_server is not None else 0
        if repl_factor > 0:
            from .net import replicate_shuffle
            from ..utils.deadline import QueryDeadlineExceeded
            for rsrv in _replica_env(ctx, repl_factor):
                try:
                    replicate_shuffle(rsrv.address, catalog, shuffle_id,
                                      ctx=ctx, node=name)
                except QueryDeadlineExceeded:
                    raise
                except OSError:  # degraded replication, not a failure
                    continue
                replicas.append(rsrv.address)
            if replicas:
                tracker.register_replicas(shuffle_id, replicas)

        # READ side (RapidsCachingReader analog): lazy fetch + re-upload.
        # Blocks free once every reduce partition is drained — or at query
        # end via the context cleanup (a limit may never start some
        # partitions) — the unregisterShuffle lifecycle
        # (ShuffleBufferCatalog.scala:50).
        ctx.add_cleanup(lambda: catalog.unregister_shuffle(shuffle_id))

        # Adaptive read planning with the OBSERVED block sizes
        # (GpuCustomShuffleReaderExec analog; see shuffle/aqe.py). Skew
        # split only for round-robin exchanges, which carry no
        # co-partitioning guarantee downstream.
        from ..config import (ADAPTIVE_BROADCAST_THRESHOLD,
                              ADAPTIVE_ENABLED, ADAPTIVE_SKEW_FACTOR,
                              ADAPTIVE_SKEW_THRESHOLD, ADAPTIVE_TARGET_SIZE)
        from . import aqe
        if ctx.conf.get(ADAPTIVE_ENABLED) and n_parts > 1:
            sizes = catalog.sizes_for_shuffle(shuffle_id)
            total_bytes = sum(sizes.values())
            from .partitioners import RangePartitioner
            # Range partitioning carries an ORDER contract downstream
            # (partition p's keys < partition p+1's) — never convert it.
            convertible = not isinstance(partitioner, RangePartitioner)
            if convertible and total_bytes <= ctx.conf.get(
                    ADAPTIVE_BROADCAST_THRESHOLD):
                # Re-plan shuffled -> broadcast-style: the observed output
                # is small enough to replicate, so skip reduce-side
                # routing entirely and read mapper-local (PartialMapper,
                # ShuffledBatchRDD.scala:31-105). Downstream joins
                # accumulate the whole build side regardless, so dropping
                # co-partitioning is safe in this single-process engine.
                specs = aqe.plan_mapper_specs(map_id)
                ctx.metric(name, "aqeBroadcastConverted", 1)
            else:
                specs = aqe.plan_specs(
                    sizes, n_parts, map_id,
                    ctx.conf.get(ADAPTIVE_TARGET_SIZE),
                    ctx.conf.get(ADAPTIVE_SKEW_FACTOR),
                    ctx.conf.get(ADAPTIVE_SKEW_THRESHOLD),
                    allow_skew_split=getattr(self.partitioner_factory,
                                             "mode", None) == "round_robin")
            ctx.metric(name, "aqeOutputPartitions", len(specs))
        else:
            specs = [aqe.CoalescedSpec(p, p + 1) for p in range(n_parts)]
        drained = _DrainLatch(
            len(specs), lambda: catalog.unregister_shuffle(shuffle_id))

        def hedge_fallback_for(p):
            """map_id -> payload recompute closure the straggler hedge
            races against a stalled primary (ISSUE 19): regenerates the
            whole reduce partition ONCE from lineage (through the
            tracker's recompute budget and metrics) and serves blocks
            out of it."""
            cache: Dict[int, bytes] = {}

            def fallback(map_id: int) -> bytes:
                if not cache:
                    regen = tracker.recompute(shuffle_id, p, ctx=ctx,
                                              node=name)
                    if regen is None:
                        raise IOError(
                            f"no lineage / recompute budget for hedge "
                            f"fallback of shuffle {shuffle_id} reduce {p}")
                    cache.update(dict(regen))
                return cache[map_id]
            return fallback

        def recovered_payloads(p, map_range):
            """One reduce partition's verified payloads, in map order,
            surviving corruption, transport failure and stragglers:
            stream from the wire plane (or the verified local catalog)
            with the replica-backed hedge armed, and on a typed
            durability error read the missing blocks from a REPLICA
            (recompute avoided), falling back to lineage regeneration —
            through the shared :func:`_missing_from_lineage` guard, so a
            diverged recompute raises instead of mixing generations."""
            from ..utils import checksum as CK
            from ..utils.deadline import QueryDeadlineExceeded
            from .net import RetryingBlockIterator, ShuffleFetchFailedError
            from .transport import ShuffleBlockCorruptError
            delivered_ids: set = set()
            try:
                if net_server is not None:
                    src = RetryingBlockIterator(
                        net_server.address, shuffle_id, p, ctx=ctx,
                        node=name, map_range=map_range, with_map_ids=True,
                        replicas=replicas,
                        local_fallback=(hedge_fallback_for(p)
                                        if replicas else None))
                else:
                    src = catalog.blocks_with_ids_for_reduce(
                        shuffle_id, p, map_range)
                for mid, payload in src:
                    delivered_ids.add(mid)
                    yield payload
                return
            except (ShuffleFetchFailedError, ShuffleBlockCorruptError,
                    CK.ChecksumError):
                # No peer-failure accounting here: the wire plane's
                # server is this query's own ephemeral loopback (nothing
                # would ever dial it again); blacklisting belongs to the
                # real remote path (fetch_with_recovery).
                peer = net_server.address if net_server is not None \
                    else ("local", 0)
                # Delivered payloads passed verification, so their crcs
                # ARE the catalog's stored registration crcs — no extra
                # hashing on the healthy path.
                metas = catalog.block_metas_for_reduce(shuffle_id, p)
                stored = {m: c for m, _l, c in metas}
                expected = {m for m, _l, _c in metas
                            if map_range is None
                            or map_range[0] <= m < map_range[1]}
                missing = None
                # Replica rung first (ISSUE 19): the local catalog knows
                # the partition's FULL map set, so a replica with a hole
                # (a lost replication push) is rejected outright — it
                # can never silently under-deliver.
                for rp in replicas:
                    rep_it = RetryingBlockIterator(
                        rp, shuffle_id, p, ctx=ctx, node=name,
                        map_range=map_range, with_map_ids=True,
                        skip_map_ids=set(delivered_ids))
                    try:
                        got = list(rep_it)
                    except (QueryDeadlineExceeded, GeneratorExit):
                        raise
                    except (OSError, ShuffleFetchFailedError):  # next rung
                        continue
                    got_ids = delivered_ids | {m for m, _ in got}
                    if not expected <= got_ids or any(
                            stored.get(m) is not None
                            and rep_it.delivered_crcs.get(m) is not None
                            and rep_it.delivered_crcs[m] != stored[m]
                            for m, _ in got):
                        continue  # hole or diverged copy: not an answer
                    ctx.metric(name, "replicaReads", len(got))
                    tracker.tally("replica_reads", len(got))
                    tracker.tally("recomputes_avoided_by_replica")
                    missing = got
                    break
                if missing is None:
                    regen = tracker.recompute(shuffle_id, p, ctx=ctx,
                                              node=name)
                    if regen is None:
                        raise
                    missing = _missing_from_lineage(
                        regen,
                        {mid: stored.get(mid) for mid in delivered_ids},
                        map_range, peer, shuffle_id, p)
            for _mid, payload in missing:
                yield payload

        def read_spec(spec):
            try:
                if isinstance(spec, aqe.PartialReducerSpec):
                    pieces = [(spec.reduce_id,
                               (spec.map_start, spec.map_end))]
                elif isinstance(spec, aqe.PartialMapperSpec):
                    # mapper-local: every reduce id of this map range
                    pieces = [(p, (spec.map_start, spec.map_end))
                              for p in range(n_parts)]
                else:
                    pieces = [(p, None)
                              for p in range(spec.start, spec.end)]
                for p, map_range in pieces:
                    for payload in recovered_payloads(p, map_range):
                        ctx.metric(name, "shuffleBytesRead", len(payload))
                        with ctx.registry.timer(
                                name, "deserializationTime",
                                trace="shuffle.deserialize"):
                            _, rb = deserialize_batch(payload)
                        ctx.metric(name, "numOutputBatches", 1)
                        yield ColumnarBatch.from_arrow(rb)
            finally:
                drained.arrive()
        if not overlap:
            return [read_spec(s) for s in specs]
        # Reduce-side overlap: a prefetch worker deserializes + re-uploads
        # the next block while the consumer computes over the previous one.
        from ..utils.prefetch import prefetch_iter
        return [prefetch_iter(read_spec(s), depth=ser_depth, ctx=ctx,
                              node=name)
                for s in specs]


def _shuffle_env(ctx: ExecContext) -> ShuffleBufferCatalog:
    """Per-context shuffle storage (GpuShuffleEnv.initStorage analog)."""
    env = getattr(ctx, "_shuffle_catalog", None)
    if env is None:
        from ..config import (HOST_SPILL_STORAGE_SIZE,
                              SHUFFLE_CHECKSUM_ENABLED, SPILL_DIR,
                              SPILL_IO_THREADS)
        env = ShuffleBufferCatalog(
            ctx.conf.get(HOST_SPILL_STORAGE_SIZE),
            ctx.conf.get(SPILL_DIR),
            verify_checksums=ctx.conf.get(SHUFFLE_CHECKSUM_ENABLED),
            io_threads=ctx.conf.get(SPILL_IO_THREADS))
        ctx._shuffle_catalog = env
        # Query-end teardown: free any still-pinned blocks and delete the
        # spill file so long sessions don't accumulate host memory/disk.
        ctx.add_cleanup(env.close)
    return env


def _net_serve(ctx: ExecContext, catalog: ShuffleBufferCatalog):
    """One loopback NetShuffleServer per context catalog (the wire plane
    of spark.rapids.tpu.shuffle.net.enabled), closed at query end."""
    server = getattr(ctx, "_shuffle_net_server", None)
    if server is None:
        from .net import NetShuffleServer
        server = NetShuffleServer(catalog)
        ctx._shuffle_net_server = server
        ctx.add_cleanup(server.close)
    return server


def _replica_env(ctx: ExecContext, factor: int):
    """Per-context replica shuffle servers (ISSUE 19) — stand-ins for
    ``replication.factor`` distinct peer processes, shared by every
    exchange in the query (like production peers serve many shuffles).
    Each replica holds its OWN ShuffleBufferCatalog fed exclusively by
    protocol-v5 PUT pushes and serves it back over the same wire a real
    remote replica would; all are closed at query end."""
    servers = getattr(ctx, "_shuffle_replica_servers", None)
    if servers is None:
        servers = []
        ctx._shuffle_replica_servers = servers
    if len(servers) < factor:
        from ..config import (HOST_SPILL_STORAGE_SIZE,
                              SHUFFLE_CHECKSUM_ENABLED, SPILL_DIR,
                              SPILL_IO_THREADS)
        from .net import NetShuffleServer
        while len(servers) < factor:
            rcat = ShuffleBufferCatalog(
                ctx.conf.get(HOST_SPILL_STORAGE_SIZE),
                ctx.conf.get(SPILL_DIR),
                verify_checksums=ctx.conf.get(SHUFFLE_CHECKSUM_ENABLED),
                io_threads=ctx.conf.get(SPILL_IO_THREADS))
            rsrv = NetShuffleServer(rcat)
            servers.append(rsrv)
            ctx.add_cleanup(rsrv.close)
            ctx.add_cleanup(rcat.close)
    return servers[:factor]
