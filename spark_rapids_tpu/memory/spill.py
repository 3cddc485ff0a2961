"""Spillable buffer framework — async device -> host -> disk tiers.

Architectural port of the reference's spill subsystem (SURVEY.md §2.1):
``RapidsBufferCatalog`` (RapidsBufferCatalog.scala:30) maps buffer ids to
tiered buffers; ``RapidsBufferStore`` (RapidsBufferStore.scala:40) owns one
tier and spills to the next via ``synchronousSpill:137-149`` in
spill-priority order (SpillPriorities.scala:26); the device store's pressure
callback is ``DeviceMemoryEventHandler.onAllocFailure:35-59``.

TPU-native differences: XLA owns the HBM allocator and exposes no
alloc-failure callback, so the device store enforces a *byte budget*
(fraction of HBM, GpuDeviceManager-style) and spills when a registration
would exceed it — pressure is handled before allocation rather than on
allocation failure. Host interchange is Arrow IPC (the reference uses
JCudfSerialization host buffers); the disk tier appends IPC-serialized
batches to a shared spill file, like the reference's disk block manager
files.

Async spill engine (ISSUE 11). Every buffer is an explicit state machine

    DEVICE -> SPILLING -> HOST/DISK -> RESTORING -> DEVICE

and the catalog lock is held only to *reserve* a transition (pick victims,
mark state) and to *publish* its result (install the copied payload,
update byte accounting, wake waiters). The actual device<->host copy,
CRC32C checksum, and :class:`SpillFile` append/read run OFF the lock, on
a dedicated spill-IO lane of the shared pipeline pool
(:func:`~..exec.pipeline.submit_spill_io`, bounded by
``spark.rapids.tpu.spill.ioThreads``), so

* a spill never stalls threads touching OTHER buffers — the PR-9
  lock-order debt (catalog lock held across transfers and file opens,
  ``tools/lock_order_baseline.json``) is gone, and the static gate keeps
  it gone (the baseline is EMPTY and ratcheted);
* concurrent spills overlap on the lane instead of convoying;
* readers of an in-flight buffer wait on the buffer's own condition
  (:func:`~..utils.lockdep.condition_on` — the wait releases the catalog
  lock), never on the catalog.

Victim selection is QoS-aware (memory QoS for the multi-tenant roadmap
item): within each spill-priority band, candidates order by (requesting
query's own buffers first, then same tenant, then other tenants by
descending query-deadline slack, then descending size), so one query's
OOM ladder drains its own and the most-slack neighbors' buffers before a
deadline-constrained neighbor's hot build tables. See
docs/fault-tolerance.md#async-spill.
"""

from __future__ import annotations

import dataclasses
import io
import math
import os
import tempfile
import time
from typing import Dict, List, Optional, Tuple

import pyarrow as pa

from .. import types as T
from ..data.batch import ColumnarBatch
from ..utils import lockdep
from ..metrics.trace import span


# ---------------------------------------------------------------------------
# Spill priorities (SpillPriorities.scala:26): LOWER values spill FIRST.
# ---------------------------------------------------------------------------

#: Shuffle outputs spill before anything else: they are re-fetchable and
#: typically long-lived.
OUTPUT_FOR_SHUFFLE_PRIORITY = -10_000_000
#: Buffers parked by operators between batches (coalesce accumulation).
ACTIVE_BATCHING_PRIORITY = 0
#: Buffers an operator is actively using; spill only under extreme pressure.
ACTIVE_ON_DECK_PRIORITY = 10_000_000


class StorageTier:
    DEVICE = "device"
    HOST = "host"
    DISK = "disk"
    #: transitional: a device->host (or host->disk) copy is in flight on
    #: the spill-IO lane; readers wait on the entry's condition
    SPILLING = "spilling"
    #: transitional: a host/disk->device restore is in flight
    RESTORING = "restoring"


#: states during which an entry's payload is owned by an IO-lane worker
TRANSITIONAL_TIERS = (StorageTier.SPILLING, StorageTier.RESTORING)


@dataclasses.dataclass
class QosTag:
    """Identity of one executing query for spill victim selection: the
    session's tenant id (``spark.rapids.tpu.tenantId``) plus the query's
    deadline (PR-7 :class:`~..utils.deadline.Deadline`, None when the
    query has no wall-clock contract). One instance per
    :class:`~..plan.physical.ExecContext`; boundary forks share it, so
    "own buffer" means "same query"."""

    tenant: str = ""
    deadline: object = None
    #: the requesting query's span tracer (metrics/trace.py, ISSUE 13):
    #: spill-IO lane units opened on this query's behalf record their
    #: device<->host/disk transitions as spans in ITS trace — None (the
    #: default) records nothing
    trace: object = None

    def slack(self) -> float:
        """Seconds of deadline headroom; +inf without a deadline. A
        neighbor with more slack is the safer victim — it can afford the
        reload round trip."""
        if self.deadline is None:
            return math.inf
        try:
            return float(self.deadline.remaining())
        except Exception:  # tpu-lint: ignore - accounting only: a
            return math.inf  # broken deadline must not poison selection


@dataclasses.dataclass
class TableMeta:
    """What's needed to faithfully restore a batch on device (the flatbuffer
    TableMeta analog, MetaUtils.scala:41)."""

    schema: T.Schema
    capacity: int
    size_bytes: int


@dataclasses.dataclass
class _Entry:
    buffer_id: int
    priority: int
    meta: TableMeta
    tier: str
    device_batch: Optional[ColumnarBatch] = None
    host_batch: Optional[pa.RecordBatch] = None
    disk_range: Optional[Tuple[int, int]] = None  # (offset, length)
    freed: bool = False
    #: QoS identity of the registering query (None in bare tests)
    owner: Optional[QosTag] = None
    #: which settled tier a SPILLING/RESTORING transition left from
    moving_from: str = ""
    #: per-buffer wait channel for in-flight transitions; shares the
    #: catalog lock (lockdep.condition_on) — created at first transition
    cond: object = None
    #: catalog _compact_gen at free() time for a freed-while-RESTORING
    #: entry: the restore worker honors the deferred free_range only if
    #: no compaction rewrote the file since (stale offsets would skew
    #: freed accounting and can delete a live range's CRC record)
    freed_gen: int = -1


#: Compact the shared spill file once this fraction of its bytes is dead
#: (freed ranges of a still-open catalog previously leaked until close).
DISK_COMPACT_FRACTION = 0.5


class SpillFileClosedError(RuntimeError):
    """A SpillFile operation (or a catalog ``_disk()`` resolve) raced
    close(): the file is gone. Typed so straggler publish paths can
    settle as a stand-down instead of treating it like a transient I/O
    failure — an untyped append would silently RE-CREATE the removed
    path via ``open(path, 'ab')`` and leak it."""


class SpillFile:
    """Shared spill file (RapidsDiskStore's block-manager file): appends
    serialized payloads, tracks freed ranges, and compacts itself when the
    owner asks — so freed disk space reclaims during the catalog's
    lifetime instead of leaking until close.

    Durability (ISSUE 7): every appended range records its CRC32C and
    every read verifies it, so disk bit rot (or a concurrent writer
    scribbling over the file) surfaces as a typed
    :class:`~..utils.checksum.ChecksumError` — classified transient by
    the retry taxonomy — instead of deserializing garbage into a query
    answer.

    Concurrency contract (ISSUE 11): every operation is atomic under the
    file's own ``io_ok`` lock, so an off-catalog-lock read can never see
    a half-compacted file. Range STALENESS (the catalog's offset for a
    buffer moving during a concurrent :meth:`compact`) is the OWNER's
    problem: catalogs snapshot ranges under their lock, exclude readers
    while a compaction is claimed, and re-validate the range after the
    read (see ``BufferCatalog._read_disk_payload``)."""

    def __init__(self, spill_dir: Optional[str] = None,
                 verify: bool = True):
        self._owns_dir = spill_dir is None
        self.dir = spill_dir or tempfile.mkdtemp(prefix="tpu_spill_")
        os.makedirs(self.dir, exist_ok=True)
        # Unique per catalog so concurrent catalogs (or a reused spillDir
        # from a previous process) never interleave offsets.
        fd, self.path = tempfile.mkstemp(prefix="spill_", suffix=".bin",
                                         dir=self.dir)
        os.close(fd)
        self._offset = 0
        self._freed = 0
        #: offset -> (length, crc32c) of every live appended range
        self._crcs: Dict[int, Tuple[int, int]] = {}
        #: False = record checksums but skip verification (the shuffle
        #: catalog threads spark.rapids.tpu.shuffle.checksum.enabled here
        #: so the kill switch covers its disk tier too)
        self.verify = verify
        self._closed = False
        self._lock = lockdep.lock("SpillFile._lock", io_ok=True)

    def close(self):
        import shutil
        with self._lock:
            # Flag BEFORE removing: an append serialized behind this
            # lock would otherwise re-create the removed path ('ab'
            # creates) and leak a stray file nothing ever deletes.
            self._closed = True
            try:
                os.remove(self.path)
            except OSError:
                pass
        if self._owns_dir:
            shutil.rmtree(self.dir, ignore_errors=True)

    def append(self, payload: bytes) -> Tuple[int, int]:
        from ..utils import checksum as CK
        crc = CK.crc32c(payload)
        with self._lock:
            if self._closed:
                raise SpillFileClosedError(self.path)
            offset = self._offset
            with open(self.path, "ab") as f:
                f.write(payload)
            self._offset += len(payload)
            self._crcs[offset] = (len(payload), crc)
            return offset, len(payload)

    def read_with_crc(self, offset: int, length: int
                      ) -> Tuple[bytes, Optional[int]]:
        """(payload, recorded crc32c or None) WITHOUT verification — for
        callers that must verify outside their own wider lock (the
        shuffle catalog's disk tier). None when the range has no
        recorded checksum or verification is disabled."""
        # Under the lock: compact() rewrites the file and its checksum
        # table atomically, so payload+crc are always a consistent pair.
        with self._lock:
            if self._closed:
                raise SpillFileClosedError(self.path)
            with open(self.path, "rb") as f:
                f.seek(offset)
                payload = f.read(length)
            rec = self._crcs.get(offset)
        if self.verify and rec is not None and rec[0] == length:
            return payload, rec[1]
        return payload, None

    def read(self, offset: int, length: int) -> bytes:
        from ..utils import checksum as CK
        # Verification runs OUTSIDE the lock — the payload is a private
        # copy, and a full-payload CRC pass must not serialize readers.
        payload, crc = self.read_with_crc(offset, length)
        if crc is not None:
            CK.verify(payload, crc,
                      f"spill range [{offset}:{offset + length}) of "
                      f"{self.path}")
        return payload

    # -- space reclaim ------------------------------------------------------
    @property
    def size_bytes(self) -> int:
        with self._lock:
            return self._offset

    @property
    def freed_bytes(self) -> int:
        with self._lock:
            return self._freed

    @property
    def live_bytes(self) -> int:
        """Bytes still referenced by live ranges (file size minus freed
        ranges not yet reclaimed by compact()) — what the
        diskSpillFileBytes metric reports."""
        with self._lock:
            return self._offset - self._freed

    def free_range(self, offset: int, length: int) -> None:
        """Mark [offset, offset+length) dead; the space reclaims at the
        owner's next :meth:`compact` call."""
        with self._lock:
            self._freed += length
            rec = self._crcs.get(offset)
            if rec is not None and rec[0] == length:
                del self._crcs[offset]

    def freed_fraction(self) -> float:
        with self._lock:
            return self._freed / self._offset if self._offset else 0.0

    def compact(self, live_ranges: Dict) -> Dict:
        """Rewrite the file keeping only ``live_ranges`` ({key: (offset,
        length)}); returns the keys' new ranges. The owner must hold its
        own entry bookkeeping consistent (it passes every live range and
        installs every returned one) and keep readers out while a
        compaction is claimed (the owner's ``_compacting`` flag)."""
        from ..utils import checksum as CK
        with self._lock:
            if self._closed:
                raise SpillFileClosedError(self.path)
            fd, tmp = tempfile.mkstemp(prefix="spill_compact_",
                                       suffix=".bin", dir=self.dir)
            try:
                new_ranges: Dict = {}
                new_crcs: Dict[int, Tuple[int, int]] = {}
                pos = 0
                with os.fdopen(fd, "wb") as out, \
                        open(self.path, "rb") as src:
                    for key, (offset, length) in sorted(
                            live_ranges.items(), key=lambda kv: kv[1][0]):
                        src.seek(offset)
                        payload = src.read(length)
                        # Verify while relocating: compaction must not
                        # launder rotted bytes into a fresh file with a
                        # fresh crc.
                        rec = self._crcs.get(offset)
                        if not self.verify:
                            new_crcs[pos] = rec if rec is not None \
                                and rec[0] == length \
                                else (length, CK.crc32c(payload))
                        elif rec is not None and rec[0] == length:
                            CK.verify(payload, rec[1],
                                      f"spill range [{offset}:"
                                      f"{offset + length}) of {self.path} "
                                      "during compaction")
                            new_crcs[pos] = (length, rec[1])
                        else:
                            new_crcs[pos] = (length, CK.crc32c(payload))
                        out.write(payload)
                        new_ranges[key] = (pos, length)
                        pos += length
                os.replace(tmp, self.path)
            # A failed rewrite (rot surfacing as ChecksumError, disk
            # full, the path removed) must not leak the mkstemp temp —
            # the exact stray-file class the closed-aware guards exist
            # to prevent. os.replace consumed it on success.
            except BaseException:  # tpu-lint: ignore
                try:
                    os.unlink(tmp)
                except OSError:
                    pass
                raise
            self._offset = pos
            self._freed = 0
            self._crcs = new_crcs
            return new_ranges


def _ipc_serialize(rb: pa.RecordBatch) -> bytes:
    sink = io.BytesIO()
    with pa.ipc.new_stream(sink, rb.schema) as w:
        w.write_batch(rb)
    return sink.getvalue()


def _ipc_deserialize(payload: bytes) -> pa.RecordBatch:
    with pa.ipc.open_stream(io.BytesIO(payload)) as r:
        return next(iter(r))


#: bounded wait tick for transition/compaction waiters — workers always
#: notify, the timeout only guards against a worker dying mid-publish
_WAIT_TICK_S = 1.0

#: how long close() waits for in-flight spill IO before giving up and
#: marking the catalog closed (stragglers then stand down at publish)
_CLOSE_DRAIN_DEADLINE_S = 10.0


class BufferCatalog:
    """id -> tiered buffer, with budget-driven spill through the per-buffer
    state machine (module doc).

    The three tiers live inside one catalog (the reference splits catalog
    and three store objects; the chain wiring is identical —
    GpuShuffleEnv.initStorage, GpuShuffleEnv.scala:52-69). The public API
    keeps the synchronous CONTRACT of the reference — ``register_batch``
    returns within budget, ``spill_below`` returns with the bytes moved —
    but the waiting happens with the catalog lock RELEASED and the copies
    overlapped on the spill-IO lane."""

    def __init__(self, device_budget_bytes,
                 host_budget_bytes: int,
                 spill_dir: Optional[str] = None,
                 io_threads: int = 2):
        # int, or a 0-arg callable resolved on first budget check (lets the
        # device manager defer accelerator-backend init until device buffers
        # actually exist — see DeviceManager).
        self._device_budget = device_budget_bytes
        self.host_budget = host_budget_bytes
        self._entries: Dict[int, _Entry] = {}
        self.device_bytes = 0
        self.host_bytes = 0
        #: bytes reserved for in-flight device->host / host->disk copies
        #: (still counted in device_bytes/host_bytes until publish);
        #: budget loops subtract these so one drain never over-reserves
        self._spilling_device_bytes = 0
        self._spilling_host_bytes = 0
        self._next_id = 0
        self._lock = lockdep.rlock("BufferCatalog._lock")
        #: catalog-wide wait channel: compaction exclusion + IO-pending
        #: drain at close (shares the catalog lock, like the entry conds)
        self._state_cond = lockdep.condition_on(self._lock)
        self._compacting = False
        #: set by close() (even when its IO drain times out): late lane
        #: workers check it at publish time and stand down instead of
        #: resurrecting accounting — and _disk() refuses to lazily
        #: recreate a fresh SpillFile post-close (stray temp dir leak)
        self._closed = False
        #: disk appends in flight (range not yet published): a compaction
        #: snapshot taken now would MISS those bytes and the rewrite would
        #: silently drop them — _claim_compact refuses while > 0, and
        #: appenders stand aside while a claimed rewrite runs.
        self._disk_appends = 0
        #: bumped when a compaction installs relocated ranges; guards
        #: deferred free_range calls against stale pre-compaction offsets
        self._compact_gen = 0
        self._spill_dir = spill_dir
        self._spill_file: Optional[SpillFile] = None  # lazy: first disk spill
        self._pinned: set = set()
        # Spill-IO lane (spark.rapids.tpu.spill.ioThreads): up to
        # io_threads copies in flight on the shared pipeline pool; 0 =
        # inline on the requesting thread (still off-lock).
        self._io_threads = max(0, int(io_threads))
        import threading
        self._io_slots = threading.BoundedSemaphore(self._io_threads) \
            if self._io_threads > 0 else None
        self._io_pending = 0
        self._io_running = 0
        self.metrics = {"spilled_to_host": 0, "spilled_to_disk": 0,
                        "reloaded_from_host": 0, "reloaded_from_disk": 0,
                        # byte counters feed the query profile's spillBytes
                        # (metrics/profile.py takes per-query deltas)
                        "spill_bytes_to_host": 0, "spill_bytes_to_disk": 0,
                        # live size of the shared disk spill file (the
                        # diskSpillFileBytes profile metric) + compactions
                        "disk_spill_file_bytes": 0,
                        "disk_spill_file_compactions": 0,
                        # async-engine counters (ISSUE 11): wall ns and
                        # bytes of off-lock IO (spillThroughputBytesPerSec),
                        # submitted-not-finished watermark (spillQueueDepth),
                        # simultaneous-IO watermark (the overlap proof the
                        # spill-storm test asserts), and ns spent WAITING
                        # to acquire the catalog lock (spillLockWaitNs —
                        # the convoy detector).
                        "spill_io_ns": 0, "spill_io_bytes": 0,
                        "spill_queue_peak": 0, "spill_concurrent_peak": 0,
                        "spill_lock_wait_ns": 0}

    @property
    def device_budget(self) -> int:
        # Resolve through a LOCAL so two first readers racing here can
        # never interleave check-then-call with the other's just-assigned
        # int (TypeError: 'int' object is not callable); a double resolve
        # of the idempotent callable is harmless. The resolve itself runs
        # OFF-lock (it may probe the device for HBM size); the install is
        # identity-guarded under the (reentrant) lock so it can never
        # clobber a budget the setter assigned mid-resolve — the lost
        # update would silently disable a forced drain.
        b = self._device_budget
        if callable(b):
            val = b()
            with self._lock:
                if self._device_budget is b:
                    self._device_budget = val
                b = self._device_budget
            if callable(b):  # a different lazy callable was installed
                b = val
        return b

    @device_budget.setter
    def device_budget(self, value: int):
        with self._lock:
            self._device_budget = value

    def _disk(self) -> SpillFile:
        # Double-checked under the catalog lock (reentrant) so IO-lane
        # workers can resolve it off-lock without racing the lazy init.
        f = self._spill_file
        if f is None:
            with self._lock:
                if self._closed:
                    # Backstop: never lazily recreate a SpillFile after
                    # close() removed it — a straggler past the close
                    # drain deadline would leak a fresh temp file/dir.
                    raise SpillFileClosedError("spill catalog is closed")
                if self._spill_file is None:
                    self._spill_file = SpillFile(self._spill_dir)
                f = self._spill_file
        return f

    def _note_lock_wait(self, t0_ns: int) -> None:
        """First statement inside a public entry point's ``with
        self._lock:`` — the elapsed time since ``t0_ns`` (taken just
        before the ``with``) is dominated by the acquisition wait, which
        is exactly what spillLockWaitNs exists to expose: under the old
        synchronous design this was the convoy (threads queued behind a
        lock held across device copies); under the async engine it should
        stay near zero, because the lock now brackets only bookkeeping."""
        self.metrics["spill_lock_wait_ns"] += time.perf_counter_ns() - t0_ns

    def _entry_cond(self, entry: _Entry):
        if entry.cond is None:
            entry.cond = lockdep.condition_on(self._lock)
        return entry.cond

    # -- registration -------------------------------------------------------
    def register_batch(self, batch: ColumnarBatch,
                       priority: int = ACTIVE_BATCHING_PRIORITY,
                       owner: Optional[QosTag] = None) -> int:
        """Track a device batch as spillable; may spill lower-priority
        buffers (QoS order, module doc) to stay within the device budget.
        Returns with the budget satisfied, but the copies ran off-lock on
        the spill-IO lane — concurrent registrations overlap."""
        size = batch.device_size_bytes
        meta = TableMeta(batch.schema, batch.capacity, size)
        t0 = time.perf_counter_ns()
        with self._lock:
            self._note_lock_wait(t0)
            bid = self._next_id
            self._next_id += 1
            self._entries[bid] = _Entry(bid, priority, meta,
                                        StorageTier.DEVICE,
                                        device_batch=batch, owner=owner)
            self.device_bytes += size
        self._enforce_budgets(requester=owner)
        return bid

    # -- access -------------------------------------------------------------
    def acquire_batch(self, buffer_id: int) -> ColumnarBatch:
        """Return the batch on device, unspilling through the tiers if
        needed (RapidsBufferStore.getDeviceMemoryBuffer's tier climb).
        The restore copy runs off-lock; a buffer mid-transition is waited
        out on ITS OWN condition (the wait releases the catalog lock, so
        other threads proceed)."""
        while True:
            reserved = False
            t0 = time.perf_counter_ns()
            with self._lock:
                self._note_lock_wait(t0)
                entry = self._entries[buffer_id]
                assert not entry.freed, f"buffer {buffer_id} already freed"
                tier = entry.tier
                if tier == StorageTier.DEVICE:
                    return entry.device_batch
                if tier in TRANSITIONAL_TIERS:
                    # Wait out the in-flight transition on the BUFFER's
                    # condition — the wait releases the catalog lock, so
                    # threads touching other buffers proceed. A closed
                    # catalog also ends the wait: the stand-down publish
                    # paths never settle the tier, so a waiter would
                    # otherwise tick here forever (the re-entered loop
                    # then raises KeyError on the cleared _entries).
                    cond = self._entry_cond(entry)
                    while entry.tier in TRANSITIONAL_TIERS \
                            and not entry.freed and not self._closed:
                        cond.wait(timeout=_WAIT_TICK_S)
                else:
                    # settled off-device: reserve the restore
                    src = tier  # HOST or DISK
                    entry.tier = StorageTier.RESTORING
                    entry.moving_from = src
                    self._entry_cond(entry)
                    host_rb = entry.host_batch
                    reserved = True
            if reserved:
                return self._restore_entry(entry, src, host_rb)

    def _release_freed_restore_range(self, entry: _Entry, src: str) -> bool:
        """Deferred ``free_range`` for a freed-while-RESTORING disk entry
        (caller holds the lock): free() popped the entry and left the
        range to the restore worker, which may still have been reading
        it. Generation-guarded — a compaction since free() (gen moved, or
        a claimed rewrite running) already dropped/relocated the bytes,
        so these offsets are stale. Returns whether this thread claimed
        the follow-up compaction."""
        if src == StorageTier.DISK \
                and entry.disk_range is not None \
                and self._spill_file is not None \
                and not self._compacting \
                and entry.freed_gen == self._compact_gen:
            self._spill_file.free_range(*entry.disk_range)
            entry.disk_range = None
            return self._claim_compact()
        return False

    def _restore_entry(self, entry: _Entry, src: str,
                       host_rb) -> ColumnarBatch:
        """Off-lock restore of a RESTORING-reserved entry: disk read +
        IPC decode + host->device upload, then publish under the lock."""
        size = entry.meta.size_bytes
        t0 = time.perf_counter_ns()
        try:
            if src == StorageTier.DISK:
                payload = self._read_disk_payload(entry)
                host_rb = _ipc_deserialize(payload)
            with span(None, "spill.reload_to_device"):
                batch = ColumnarBatch.from_arrow(
                    host_rb, capacity=entry.meta.capacity)
        # Revert-and-re-raise: classification-neutral (the exception
        # reaches the retry taxonomy verbatim at the acquiring site).
        except BaseException:  # tpu-lint: ignore
            compact_ready = False
            with self._lock:
                if entry.freed:
                    # free() raced the restore and deferred the disk
                    # range to this worker — the same contract as the
                    # successful-publish freed path below.
                    compact_ready = \
                        self._release_freed_restore_range(entry, src)
                else:
                    entry.tier = src  # revert the reservation
                    entry.moving_from = ""
                entry.cond.notify_all()
            if compact_ready:
                try:
                    self._compact_now()
                except Exception:  # tpu-lint: ignore - the ORIGINAL
                    # restore error is the one the retry taxonomy must
                    # classify (the classification-neutral contract
                    # above); a failed opportunistic rewrite must not
                    # replace it.
                    import logging
                    logging.getLogger(__name__).warning(
                        "spill-file compaction failed during restore "
                        "revert; deferring reclaim", exc_info=True)
            raise
        io_ns = time.perf_counter_ns() - t0
        compact_ready = False
        closed = False
        with self._lock:
            self.metrics["spill_io_ns"] += io_ns
            self.metrics["spill_io_bytes"] += size
            freed = entry.freed
            if not freed and self._closed:
                # close() raced this restore (restores run on the
                # acquiring thread, outside close()'s IO drain): the
                # catalog is cleared and its spill file gone — hand the
                # restored batch to the acquirer without resurrecting
                # byte accounting, tier state, or disk bookkeeping.
                closed = True
                entry.cond.notify_all()
            elif freed:
                # free() deferred the disk range to this worker (the
                # read may have been in flight then): release it NOW or
                # the dead bytes sit in the shared spill file — invisible
                # to freed_fraction, so compaction might never trigger.
                compact_ready = self._release_freed_restore_range(entry,
                                                                  src)
                entry.cond.notify_all()
            else:
                if src == StorageTier.DISK:
                    # While a claimed rewrite runs the offsets are about
                    # to be remapped; disk_range=None makes the install
                    # loop free the relocated bytes instead. When no
                    # rewrite is in flight, disk_range is current (the
                    # install loop keeps live entries' ranges fresh).
                    if entry.disk_range is not None \
                            and self._spill_file is not None \
                            and not self._compacting:
                        self._spill_file.free_range(*entry.disk_range)
                    entry.disk_range = None
                    self.metrics["reloaded_from_disk"] += 1
                    compact_ready = self._claim_compact()
                else:
                    self.host_bytes -= size
                entry.host_batch = None
                entry.device_batch = batch
                entry.tier = StorageTier.DEVICE
                entry.moving_from = ""
                self.device_bytes += size
                self.metrics["reloaded_from_host"] += 1
                self.metrics["disk_spill_file_bytes"] = \
                    self._spill_file.live_bytes if self._spill_file else 0
                entry.cond.notify_all()
        if compact_ready:
            self._compact_now()
        if freed:
            raise KeyError(entry.buffer_id)
        if closed:
            return batch  # no budget pass against the closed catalog
        self._enforce_budgets(requester=entry.owner,
                              exclude=entry.buffer_id)
        return batch

    def _read_disk_payload(self, entry: _Entry) -> bytes:
        """Read one RESTORING entry's disk payload off the catalog lock,
        safely against concurrent compaction: readers stand aside while a
        compaction is claimed, the SpillFile read itself is atomic under
        the file's own lock, and the range is re-validated afterward — a
        relocated range simply retries with the installed offsets."""
        while True:
            with self._lock:
                while self._compacting:
                    self._state_cond.wait(timeout=_WAIT_TICK_S)
                rng = entry.disk_range
            payload = self._disk().read(*rng)
            with self._lock:
                if not self._compacting and entry.disk_range == rng:
                    return payload

    def tier_of(self, buffer_id: int) -> str:
        with self._lock:
            return self._entries[buffer_id].tier

    def free(self, buffer_id: int):
        compact_ready = False
        t0 = time.perf_counter_ns()
        with self._lock:
            self._note_lock_wait(t0)
            entry = self._entries.pop(buffer_id, None)
            self._pinned.discard(buffer_id)
            if entry is None or entry.freed:
                return
            entry.freed = True
            size = entry.meta.size_bytes
            tier = entry.tier
            if tier == StorageTier.DEVICE:
                self.device_bytes -= size
                entry.device_batch = None
            elif tier == StorageTier.HOST:
                self.host_bytes -= size
                entry.host_batch = None
            elif tier == StorageTier.DISK:
                if entry.disk_range is not None \
                        and self._spill_file is not None \
                        and not self._compacting:
                    # While a claimed rewrite runs, the offsets are about
                    # to be remapped — the install loop frees the
                    # relocated bytes of popped entries instead.
                    self._spill_file.free_range(*entry.disk_range)
                    entry.disk_range = None
                    compact_ready = self._claim_compact()
            elif tier == StorageTier.SPILLING:
                # The IO-lane worker owns the payload refs; account the
                # source tier now, the worker skips it on publish.
                if entry.moving_from == StorageTier.DEVICE:
                    self.device_bytes -= size
                else:
                    self.host_bytes -= size
            elif tier == StorageTier.RESTORING:
                # device_bytes was never re-added; release the source
                # side the worker is copying FROM (the disk range is
                # freed by the worker — it may still be reading it;
                # freed_gen lets it detect a compaction intervening
                # before its publish, which makes the offsets stale).
                entry.freed_gen = self._compact_gen
                if entry.moving_from == StorageTier.HOST:
                    self.host_bytes -= size
            if entry.cond is not None:
                entry.cond.notify_all()
        if compact_ready:
            self._compact_now()

    def pin(self, buffer_id: int):
        """Exclude a buffer from spilling while an operator actively uses it
        (the reference's on-deck priority bump)."""
        with self._lock:
            self._pinned.add(buffer_id)

    def unpin(self, buffer_id: int):
        with self._lock:
            self._pinned.discard(buffer_id)

    def leak_report(self) -> list:
        """Buffers registered but never freed — the cudf ref-count
        leak-warning role (SURVEY.md §5 race/leak tracking; reference
        `noWarnLeakExpected`). Returns [(buffer_id, tier, bytes)]."""
        with self._lock:
            return [(bid, e.tier, e.meta.size_bytes)
                    for bid, e in self._entries.items() if not e.freed]

    def close(self):
        with self._lock:
            # Drain in-flight IO first: a worker publishing into a
            # cleared catalog would resurrect accounting. Bounded — the
            # lane's units are short, and public callers drain their own
            # futures before returning.
            deadline = time.monotonic() + _CLOSE_DRAIN_DEADLINE_S
            while self._io_pending > 0 and time.monotonic() < deadline:
                self._state_cond.wait(timeout=_WAIT_TICK_S)
            # Even if the drain timed out, mark closed FIRST: any lane
            # worker still running sees the flag at publish time and
            # stands down instead of touching the cleared catalog or
            # lazily recreating the spill file (stray temp dir).
            self._closed = True
            # Wake every per-buffer waiter: stand-down publishes never
            # settle the tier, so a waiter mid acquire_batch would
            # otherwise tick against SPILLING/RESTORING forever (its
            # wait loop also checks _closed).
            for e in self._entries.values():
                if e.cond is not None:
                    e.cond.notify_all()
            import logging
            if self._io_pending > 0:
                logging.getLogger(__name__).warning(
                    "spill catalog closed with %d IO unit(s) still in "
                    "flight after the drain deadline; they will stand "
                    "down at publish time", self._io_pending)
            leaks = self.leak_report()
            if leaks:
                total = sum(b for _, _, b in leaks)
                logging.getLogger(__name__).warning(
                    "spill catalog closed with %d leaked buffer(s), "
                    "%d bytes: %s", len(leaks), total,
                    [(bid, t) for bid, t, _ in leaks[:8]])
            self._entries.clear()
            self._pinned.clear()
            if self._spill_file is not None:
                self._spill_file.close()
                self._spill_file = None

    # -- spilling -----------------------------------------------------------
    def synchronous_spill(self, target_device_bytes: int,
                          requester: Optional[QosTag] = None):
        """Spill device buffers (QoS victim order) until usage <= target
        (RapidsBufferStore.synchronousSpill:137-149). Returns once the
        copies have landed; they ran off-lock, overlapped on the lane."""
        jobs = self._reserve_for_target(target_device_bytes, requester)
        self._run_spill_jobs(jobs, requester)

    def spill_below(self, priority_ceiling: int,
                    requester: Optional[QosTag] = None) -> int:
        """Spill every unpinned device buffer whose priority is below
        ``priority_ceiling`` off the device (cascading to disk via the
        host budget) — the OOM-retry drain (memory/retry.py): everything
        except on-deck buffers leaves the device before the attempt
        re-runs. Victims drain in QoS order (``requester``'s own buffers
        first — an OOM ladder must not evict its neighbors' hot tables
        while its own spillable state suffices). Returns device bytes
        moved. Concurrent drains are safe without any outer lock: the
        state machine reserves each victim exactly once."""
        t0 = time.perf_counter_ns()
        with self._lock:
            self._note_lock_wait(t0)
            jobs = self._reserve_device_victims(
                target=0, requester=requester, ceiling=priority_ceiling)
        moved = sum(e.meta.size_bytes for e in jobs)
        self._run_spill_jobs(jobs, requester)
        return moved

    def _tenant_device_bytes_locked(self, tenant: str) -> int:
        """Settled DEVICE bytes owned by ``tenant`` (caller holds the
        lock) — the ONE tenant-residency meter shared by the public
        accessor and the budget victim reservation."""
        return sum(e.meta.size_bytes for e in self._entries.values()
                   if e.tier == StorageTier.DEVICE and not e.freed
                   and e.owner is not None
                   and e.owner.tenant == tenant)

    def tenant_device_bytes(self, tenant: str) -> int:
        """Settled DEVICE bytes owned by ``tenant``'s queries (QosTag
        owners stamped at registration) — the usage the serving layer's
        per-tenant memory budget meters (docs/serving.md)."""
        with self._lock:
            return self._tenant_device_bytes_locked(tenant)

    def spill_tenant_over_budget(self, tenant: str, budget: int,
                                 requester: Optional[QosTag] = None) -> int:
        """Spill ``tenant``'s own device buffers (QoS victim order among
        them) until its device residency fits ``budget`` — the serving
        layer's budget enforcement (docs/serving.md): an over-budget
        tenant pays with its OWN spillable residency before its next
        query runs; neighbors' buffers are never candidates, so
        enforcement can neither crash nor starve them. Returns device
        bytes moved."""
        t0 = time.perf_counter_ns()
        with self._lock:
            self._note_lock_wait(t0)
            jobs = self._reserve_device_victims(target=int(budget),
                                                requester=requester,
                                                tenant=tenant)
        moved = sum(e.meta.size_bytes for e in jobs)
        self._run_spill_jobs(jobs, requester)
        return moved

    def _reserve_for_target(self, target: int,
                            requester: Optional[QosTag]) -> List[_Entry]:
        t0 = time.perf_counter_ns()
        with self._lock:
            self._note_lock_wait(t0)
            return self._reserve_device_victims(target=target,
                                                requester=requester)

    def _enforce_budgets(self, requester: Optional[QosTag] = None,
                         exclude: Optional[int] = None) -> None:
        """Bring device AND host usage back under budget: reserve victims
        under the lock, copy off-lock on the lane, wait for the publishes
        (with no lock held). The upload memo's device bytes count against
        the budget too; as a pure cache it is the cheapest thing to evict
        (LRU) before any real buffer spills."""
        budget = self.device_budget  # resolves the lazy callable off-lock
        from ..data import upload_cache
        over = self.device_bytes + upload_cache.cache_bytes() - budget
        if over > 0:
            upload_cache.shrink_by(over)
        t0 = time.perf_counter_ns()
        with self._lock:
            self._note_lock_wait(t0)
            jobs = self._reserve_device_victims(
                target=budget, requester=requester, exclude=exclude)
            jobs += self._reserve_host_victims(requester)
        self._run_spill_jobs(jobs, requester)

    def _victim_key(self, entry: _Entry, requester: Optional[QosTag]):
        """QoS victim order (module doc). Spill PRIORITY stays the
        primary band — shuffle outputs are refetchable and always go
        before anyone's active batches, and on-deck buffers go last no
        matter who owns them (the reference's semantics, preserved).
        WITHIN a band: the requester's own query first (its OOM ladder
        drains its own state before touching a neighbor), then its
        tenant, then other tenants by DESCENDING deadline slack (a query
        far from its deadline can afford the reload round trip), then
        descending size (fewest evictions relieve the most pressure),
        then registration order (deterministic tie-break)."""
        owner = entry.owner
        if requester is None:
            return (entry.priority, 0, 0.0, 0, entry.buffer_id)
        if owner is requester:
            cls = 0
        elif owner is not None and owner.tenant == requester.tenant:
            cls = 1
        else:
            cls = 2
        slack = owner.slack() if owner is not None else math.inf
        return (entry.priority, cls, -slack, -entry.meta.size_bytes,
                entry.buffer_id)

    def _reserve_device_victims(self, target: int,
                                requester: Optional[QosTag],
                                exclude: Optional[int] = None,
                                ceiling: Optional[int] = None,
                                tenant: Optional[str] = None
                                ) -> List[_Entry]:
        """Reserve DEVICE->SPILLING transitions (caller holds the lock)
        until settled-plus-inflight device usage fits ``target``.
        ``ceiling`` bounds eligible priorities (spill_below); ``tenant``
        restricts BOTH the usage meter and the candidates to buffers
        owned by that tenant (the serving layer's per-tenant memory
        budget — neighbors' buffers are never candidates)."""
        if tenant is None:
            usage = self.device_bytes - self._spilling_device_bytes
        else:
            usage = self._tenant_device_bytes_locked(tenant)
        if usage <= target:
            return []
        cands = [e for e in self._entries.values()
                 if e.tier == StorageTier.DEVICE and not e.freed
                 and e.buffer_id != exclude
                 and e.buffer_id not in self._pinned
                 and (ceiling is None or e.priority < ceiling)
                 and (tenant is None or (e.owner is not None
                                         and e.owner.tenant == tenant))]
        cands.sort(key=lambda e: self._victim_key(e, requester))
        jobs: List[_Entry] = []
        for e in cands:
            if usage <= target:
                break
            e.tier = StorageTier.SPILLING
            e.moving_from = StorageTier.DEVICE
            self._entry_cond(e)
            self._spilling_device_bytes += e.meta.size_bytes
            usage -= e.meta.size_bytes
            jobs.append(e)
        return jobs

    def _reserve_host_victims(self, requester: Optional[QosTag]
                              ) -> List[_Entry]:
        """Reserve HOST->SPILLING (to disk) transitions (caller holds the
        lock) until settled-plus-inflight host usage fits the budget."""
        if self.host_bytes - self._spilling_host_bytes <= self.host_budget:
            return []
        cands = [e for e in self._entries.values()
                 if e.tier == StorageTier.HOST and not e.freed
                 and e.buffer_id not in self._pinned]
        cands.sort(key=lambda e: self._victim_key(e, requester))
        jobs: List[_Entry] = []
        for e in cands:
            if self.host_bytes - self._spilling_host_bytes \
                    <= self.host_budget:
                break
            e.tier = StorageTier.SPILLING
            e.moving_from = StorageTier.HOST
            self._entry_cond(e)
            self._spilling_host_bytes += e.meta.size_bytes
            jobs.append(e)
        return jobs

    # -- the spill-IO lane --------------------------------------------------
    def _run_spill_jobs(self, jobs: List[_Entry],
                        requester: Optional[QosTag]) -> None:
        """Run reserved spill transitions off-lock: on the lane when
        ioThreads > 0 (overlapped; bounded by the slot semaphore inside
        each unit), inline otherwise. Always waits for every publish —
        the public API's synchronous contract — but with NO lock held, so
        waiters of other buffers and other registrations proceed."""
        if not jobs:
            return
        if self._io_slots is None or len(jobs) == 1:
            # Inline path (ioThreads=0, or a single job): same collect-
            # and-re-raise contract as the submitted path below — every
            # reservation must settle (publish or revert) before the
            # first failure propagates; aborting mid-list would leave
            # the rest SPILLING forever.
            err0: Optional[BaseException] = None
            for e in jobs:
                try:
                    self._spill_job(e, requester)
                except BaseException as exc:  # tpu-lint: ignore
                    err0 = err0 or exc
            if err0 is not None:
                raise err0
            return
        from ..exec import pipeline
        submitted = []
        for e in jobs:
            with self._lock:
                self._io_pending += 1
                if self._io_pending > self.metrics["spill_queue_peak"]:
                    self.metrics["spill_queue_peak"] = self._io_pending
            f = pipeline.submit_spill_io(self._io_task, e, requester)
            if f is None:  # pool torn down: run inline
                self._io_finished()
                self._spill_job(e, requester)
            else:
                submitted.append((f, e))
        err: Optional[BaseException] = None
        for f, e in submitted:
            try:
                with span(
                        requester.trace if requester is not None else None,
                        "spill.io_wait", cat="spill"), \
                        lockdep.blocking("spill.io_wait"):
                    f.result()
            except BaseException as exc:  # tpu-lint: ignore - collect-
                # re-raise: every job must settle (publish or revert)
                # before the first failure propagates to the retry
                # taxonomy; a cancelled unit (pool shutdown race) runs
                # inline so the reservation never leaks.
                if _is_cancelled(exc):
                    # _io_task never started, so its finally never
                    # decremented the pending count — undo it here or
                    # every later close() spins its full drain deadline.
                    self._io_finished()
                    self._spill_job(e, requester)
                else:
                    err = err or exc
        if err is not None:
            raise err

    def _io_task(self, entry: _Entry, requester: Optional[QosTag]) -> None:
        """One lane unit: bounded by the ioThreads slot semaphore."""
        with self._io_slots:
            try:
                self._spill_job(entry, requester)
            finally:
                self._io_finished()

    def _io_finished(self) -> None:
        with self._lock:
            self._io_pending -= 1
            self._state_cond.notify_all()

    def _spill_job(self, entry: _Entry,
                   requester: Optional[QosTag]) -> None:
        """Run one reserved SPILLING transition to completion (off-lock
        copy + locked publish), cascading host->disk pressure on the same
        worker so a waiter observes full settlement. Tracks simultaneous
        spill I/O — spill_concurrent_peak >= 2 is the machine-checkable
        proof that spills overlap instead of convoying (the spill-storm
        test asserts it)."""
        with self._lock:
            self._io_running += 1
            if self._io_running > self.metrics["spill_concurrent_peak"]:
                self.metrics["spill_concurrent_peak"] = self._io_running
        # Lane-transition span (ISSUE 13): runs on the IO-lane worker, so
        # it parents under the requesting query's trace root — concurrent
        # lane units show as overlapping spans, the proof the PR-11
        # off-lock engine actually overlaps.
        try:
            with span(
                    requester.trace if requester is not None else None,
                    "spill.io", cat="spill",
                    tier=entry.moving_from or entry.tier,
                    bytes=entry.meta.size_bytes):
                if entry.moving_from == StorageTier.DEVICE:
                    self._spill_device_job(entry, requester)
                else:
                    self._spill_host_job(entry)
        finally:
            with self._lock:
                self._io_running -= 1

    def _spill_device_job(self, entry: _Entry,
                          requester: Optional[QosTag]) -> None:
        size = entry.meta.size_bytes
        t0 = time.perf_counter_ns()
        try:
            with span(None, "spill.device_to_host"):
                rb = entry.device_batch.to_arrow()
        # Revert-and-re-raise: classification-neutral (the waiter's
        # retry site classifies the propagated exception).
        except BaseException:  # tpu-lint: ignore
            with self._lock:
                self._spilling_device_bytes -= size
                if not entry.freed:
                    entry.tier = StorageTier.DEVICE  # revert
                    entry.moving_from = ""
                entry.cond.notify_all()
            raise
        io_ns = time.perf_counter_ns() - t0
        cascade: List[_Entry] = []
        with self._lock:
            self._spilling_device_bytes -= size
            self.metrics["spill_io_ns"] += io_ns
            self.metrics["spill_io_bytes"] += size
            if self._closed:
                # Late publish after close() gave up its drain: the
                # catalog (and byte accounting) is gone — drop the refs
                # and stand down; no host-budget cascade either.
                entry.device_batch = None
                entry.host_batch = None
                entry.cond.notify_all()
                self._state_cond.notify_all()
                return
            if entry.freed:
                entry.device_batch = None
                entry.cond.notify_all()
            else:
                entry.host_batch = rb
                entry.device_batch = None
                entry.tier = StorageTier.HOST
                entry.moving_from = ""
                self.device_bytes -= size
                self.host_bytes += size
                self.metrics["spilled_to_host"] += 1
                self.metrics["spill_bytes_to_host"] += size
                entry.cond.notify_all()
                cascade = self._reserve_host_victims(requester)
        # Host-budget cascade runs on THIS worker (sequential, still
        # off-lock): the submitter's wait then covers the whole chain.
        # Collect-and-re-raise (same contract as _run_spill_jobs): every
        # reserved victim must settle — publish or revert — before the
        # first failure propagates, or the survivors sit SPILLING forever
        # with _spilling_host_bytes inflated and any later acquire of
        # them hangs.
        err: Optional[BaseException] = None
        for victim in cascade:
            try:
                self._spill_host_job(victim)
            except BaseException as exc:  # tpu-lint: ignore
                err = err or exc
        if err is not None:
            raise err

    def _spill_host_job(self, entry: _Entry) -> None:
        size = entry.meta.size_bytes
        t0 = time.perf_counter_ns()
        # Appends exclude compaction both ways: stand aside while a
        # claimed rewrite runs (it would os.replace the file under us),
        # and hold _disk_appends so no claim's live snapshot can miss the
        # appended-but-not-yet-published range (the rewrite would drop
        # those bytes and this publish would install a stale offset —
        # permanent data loss on a later restore).
        with self._lock:
            while self._compacting:
                self._state_cond.wait(timeout=_WAIT_TICK_S)
            if self._closed:
                # close() gave up its IO drain and already removed the
                # spill file: abandon the transition (the catalog is
                # gone; appending would resurrect a fresh SpillFile).
                self._spilling_host_bytes -= size
                entry.host_batch = None
                if entry.cond is not None:
                    entry.cond.notify_all()
                return
            self._disk_appends += 1
        try:
            with span(None, "spill.host_to_disk"):
                payload = _ipc_serialize(entry.host_batch)
                rng = self._disk().append(payload)
        except SpillFileClosedError:
            # close() raced between the pre-gate and the append (the
            # closed-aware SpillFile refused rather than re-create the
            # removed path via open('ab')): settle as the closed
            # stand-down — reverting to HOST would resurrect tier state
            # in the cleared catalog.
            with self._lock:
                self._disk_appends -= 1
                self._spilling_host_bytes -= size
                entry.host_batch = None
                entry.cond.notify_all()
                self._state_cond.notify_all()
            return
        # Revert-and-re-raise: classification-neutral (see above).
        except BaseException:  # tpu-lint: ignore
            with self._lock:
                self._disk_appends -= 1
                self._spilling_host_bytes -= size
                if not entry.freed:
                    entry.tier = StorageTier.HOST  # revert
                    entry.moving_from = ""
                entry.cond.notify_all()
            raise
        io_ns = time.perf_counter_ns() - t0
        compact_ready = False
        with self._lock:
            self._disk_appends -= 1
            self._spilling_host_bytes -= size
            self.metrics["spill_io_ns"] += io_ns
            self.metrics["spill_io_bytes"] += len(payload)
            if self._closed:
                # close() gave up its IO drain while the append was in
                # flight and already removed the spill file (the range
                # died with it): settle without touching _disk() — it
                # must not resurrect a fresh file post-close.
                entry.host_batch = None
                entry.cond.notify_all()
                self._state_cond.notify_all()
                return
            if entry.freed:
                self._disk().free_range(*rng)
                entry.host_batch = None
                compact_ready = self._claim_compact()
            else:
                entry.disk_range = rng
                entry.host_batch = None
                entry.tier = StorageTier.DISK
                entry.moving_from = ""
                self.host_bytes -= size
                self.metrics["spilled_to_disk"] += 1
                self.metrics["spill_bytes_to_disk"] += len(payload)
                # Pick up a compaction our in-flight append deferred.
                compact_ready = self._claim_compact()
            self.metrics["disk_spill_file_bytes"] = self._disk().live_bytes
            entry.cond.notify_all()
        if compact_ready:
            self._compact_now()

    # -- disk compaction ----------------------------------------------------
    def _claim_compact(self) -> bool:
        """True when the shared spill file crossed DISK_COMPACT_FRACTION
        dead bytes AND this caller claimed the (single) compaction slot
        (caller holds the lock; must then call :meth:`_compact_now` after
        releasing it). The claim excludes disk readers until cleared."""
        f = self._spill_file
        if f is None or self._compacting or self._disk_appends > 0:
            # _disk_appends > 0: an appended-but-unpublished range would
            # be invisible to the live snapshot — the rewrite would drop
            # its bytes. The appender's publish re-claims if still due.
            if f is not None:
                self.metrics["disk_spill_file_bytes"] = f.live_bytes
            return False
        if f.freed_bytes == 0 \
                or f.freed_fraction() < DISK_COMPACT_FRACTION:
            self.metrics["disk_spill_file_bytes"] = f.live_bytes
            return False
        self._compacting = True
        return True

    def _compact_now(self) -> None:
        """Rewrite the spill file keeping only live ranges — OFF the
        catalog lock (the PR-9 debt had this under it): the live-range
        snapshot and the new-range install bracket the rewrite under the
        lock, the rewrite itself holds only the file's own io_ok lock,
        and disk readers stand aside on the claimed ``_compacting`` flag
        (re-validating their range after every read)."""
        f = self._spill_file
        with self._lock:
            if self._closed or f is None:
                # close() raced the claimed rewrite (an inline job's
                # claim runs outside close()'s IO drain): the file and
                # every range died with it — release the claim and
                # stand down instead of dereferencing the nulled file.
                self._compacting = False
                self._state_cond.notify_all()
                return
            live = {bid: e.disk_range for bid, e in self._entries.items()
                    if e.disk_range is not None and not e.freed}
        try:
            with span(None, "spill.compact_disk"):
                new_ranges = f.compact(live)
        except SpillFileClosedError:
            # close() landed between the snapshot and the rewrite (the
            # closed-aware SpillFile refused): same stand-down — an
            # opportunistic reclaim of a dead file is not an error.
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
            for bid, rng in new_ranges.items():
                e = self._entries.get(bid)
                if e is None or e.freed or e.disk_range is None:
                    # freed (or restored) while the rewrite ran: release
                    # the relocated bytes instead of resurrecting them
                    f.free_range(*rng)
                else:
                    e.disk_range = rng
            self._compacting = False
            self._compact_gen += 1
            self.metrics["disk_spill_file_compactions"] += 1
            self.metrics["disk_spill_file_bytes"] = f.live_bytes
            self._state_cond.notify_all()


def _is_cancelled(exc: BaseException) -> bool:
    from concurrent.futures import CancelledError
    return isinstance(exc, CancelledError)
