"""TCP wire transport for the shuffle fetch plane — the UCX module analog.

The reference's opt-in shuffle transport is UCX tag-matching with a TCP
management port for the handshake (UCX.scala:53, startManagementPort:192,
handleSocket:423); fetch failures surface as
``RapidsShuffleFetchFailedException`` so the engine can retry
(RapidsShuffleIterator.scala:28,70-80). On TPU the intra-slice exchange is
an XLA collective (shuffle/ici.py) — this wire is the HOST-coordinated
cross-process / cross-slice (DCN) plane: one process serves its
:class:`~.exchange.ShuffleBufferCatalog` blocks over TCP, peers fetch them
through the same :class:`~.transport.ShuffleClient` state machine
(bounce buffers + inflight throttle) that the in-process
:class:`~.transport.LocalTransport` feeds.

Protocol v3 (length-prefixed binary, little-endian) — v3 adds end-to-end
CRC32C integrity (ISSUE 7):

* handshake: server greets ``b"SRTPU" + version`` on accept; a client that
  sees anything else disconnects (the management-port validation role).
* ``META  (op=1, shuffle_id, reduce_id)`` ->
  ``ok, n, n * (u32 map_id, u64 length, u32 crc32c)`` — metadata only;
  the server never materializes payloads to answer META. The per-block
  CRC32C recorded at registration rides the metadata so the client can
  verify every payload independently of the connection that carried it.
  ``crc32c=0`` is reserved as "no checksum recorded" (a serving catalog
  without checksum support); clients skip verification for such blocks.
* ``FETCH (op=2, shuffle_id, reduce_id, map_id)`` -> ``ok, u64 len,
  u32 crc32c, bytes`` — keyed by the stable (shuffle, map, reduce) block
  id (the reference's tag scheme), not by position in a catalog snapshot,
  so blocks registered between META and FETCH cannot shift addressing.
  The server verifies the block against its stored checksum BEFORE
  sending — corruption at rest on the serving side answers as a protocol
  error, not as bytes.
* errors -> ``ok=1, u32 msg_len, msg`` and the connection stays usable.

Timeouts are conf-driven (``spark.rapids.tpu.shuffle.net.connectTimeout``
/ ``requestTimeout``) — a dead or stalled peer fails the attempt instead
of wedging the query, and the query deadline (utils/deadline.py) bounds
them further.

:class:`RetryingBlockIterator` is the task-facing
``RapidsShuffleIterator`` analog: it STREAMS blocks as they arrive and
verify, retries transient failures with backoff — refetching only the
blocks not yet yielded — and raises :class:`ShuffleFetchFailedError`
(naming the peer and carrying exactly which map outputs are missing)
when retries exhaust: the signal the exchange's
:class:`~.exchange.MapOutputTracker` uses to recompute the missing map
tasks from lineage, exactly the role ``FetchFailedException`` plays for
Spark's stage retry.
"""

from __future__ import annotations

import socket
import socketserver
import struct
import threading
import time

from ..utils import lockdep
from typing import Callable, Dict, Iterator, List, Optional, Tuple

from ..utils import checksum as CK
from ..utils.deadline import QueryDeadlineExceeded
from .transport import (BlockDescriptor, BounceBufferPool,
                        ShuffleBlockCorruptError, ShuffleClient, Throttle,
                        Transport)

MAGIC = b"SRTPU"
#: v3 added CRC32C in META entries and FETCH responses (ISSUE 7); v4
#: adds a trace-context header — (trace64, span64) — on every request
#: (ISSUE 13): the serving side's work stitches into the REQUESTING
#: query's distributed trace (same-process peers join the live tracer;
#: cross-process peers record under the same trace id). (0, 0) means
#: "no trace context" and costs nothing. v5 adds ``PUT`` (ISSUE 19):
#: the replication push — ``op=3, shuffle_id, reduce_id, map_id`` then
#: ``u64 len, u32 crc32c, bytes``; the replica verifies the payload
#: against the wire CRC BEFORE registering it in its catalog (a torn or
#: flipped push answers as a protocol error, never as a silently bad
#: replica) and replies ``ok``.
VERSION = 5

_OP_META = 1
_OP_FETCH = 2
_OP_PUT = 3

#: op, shuffle_id, reduce_id, map_id, trace64, parent span64 (v4)
_REQ = struct.Struct("<BIIIQQ")
_META_ENTRY = struct.Struct("<IQI")  # map_id, length, crc32c
_FETCH_HEAD = struct.Struct("<QI")  # length, crc32c (after the ok byte)


def _wire_trace(tracer) -> Tuple[int, int]:
    """(trace64, span64) of the caller's current span, or (0, 0)."""
    if tracer is None:
        return 0, 0
    try:
        return tracer.wire_context()
    except (AttributeError, TypeError):
        return 0, 0  # tracing must never fail a fetch


def _serve_span(trace64: int, span64: int, name: str, **args):
    """Server-side span stitched under the requesting client's span —
    the live-trace registry resolves same-process peers to the ONE
    tracer; an unknown trace id (cross-process peer whose tracer lives
    elsewhere) records a flight-recorder event instead and opens the
    bare profiler annotation."""
    from ..metrics import trace as TR
    tracer = TR.live_tracer(trace64) if trace64 else None
    if trace64 and tracer is None:
        TR.record_event(name, **args)
    owner = TR.SpanCtx(tracer, span64) if tracer is not None else None
    return TR.span(owner, name, cat="shuffle", **args)


class ShuffleFetchFailedError(Exception):
    """Fetch retries exhausted against a peer
    (RapidsShuffleFetchFailedException analog): carries the peer address,
    the (shuffle, reduce) that must be recovered, and which map outputs
    were already delivered — the recompute path regenerates only the
    rest."""

    def __init__(self, peer: Tuple[str, int], shuffle_id: int,
                 reduce_id: int, cause: str,
                 yielded_map_ids: Optional[frozenset] = None):
        super().__init__(
            f"shuffle {shuffle_id} reduce {reduce_id} fetch from "
            f"{peer[0]}:{peer[1]} failed: {cause}")
        self.peer = peer
        self.shuffle_id = shuffle_id
        self.reduce_id = reduce_id
        self.yielded_map_ids = frozenset(yielded_map_ids or ())


def _recv_exact(sock: socket.socket, n: int) -> bytes:
    out = bytearray()
    while len(out) < n:
        chunk = sock.recv(n - len(out))
        if not chunk:
            raise ConnectionError("peer closed")
        out.extend(chunk)
    return bytes(out)


def _block_payload_crc(catalog, shuffle_id: int, map_id: int,
                       reduce_id: int) -> Tuple[bytes, int]:
    """One (payload, crc32c) from any catalog: durability-aware catalogs
    verify at rest and return their stored crc; plain ones get a fresh
    computation (the wire is still covered end-to-end)."""
    reader = getattr(catalog, "read_block_with_crc", None)
    if reader is not None:
        return reader(shuffle_id, map_id, reduce_id)
    payload = catalog.read_block(shuffle_id, map_id, reduce_id)
    return payload, CK.crc32c(payload)


class _Handler(socketserver.BaseRequestHandler):
    def handle(self):
        self.request.sendall(MAGIC + bytes([VERSION]))
        catalog = self.server.catalog  # type: ignore[attr-defined]
        while True:
            try:
                req = _recv_exact(self.request, _REQ.size)
            except (ConnectionError, OSError):
                return
            op, shuffle_id, reduce_id, map_id, trace64, span64 = \
                _REQ.unpack(req)
            try:
                if op == _OP_META:
                    with _serve_span(trace64, span64, "shuffle.serve.meta",
                                     shuffle=shuffle_id, reduce=reduce_id):
                        metas = catalog.block_metas_for_reduce(shuffle_id,
                                                               reduce_id)
                        resp = bytearray(struct.pack("<BI", 0, len(metas)))
                        for entry in metas:
                            mid, length = entry[0], entry[1]
                            crc = entry[2] if len(entry) > 2 else 0
                            resp += _META_ENTRY.pack(mid, length, crc)
                        self.request.sendall(bytes(resp))
                elif op == _OP_FETCH:
                    with _serve_span(trace64, span64, "shuffle.serve.fetch",
                                     shuffle=shuffle_id, reduce=reduce_id,
                                     map=map_id):
                        try:
                            payload, crc = _block_payload_crc(
                                catalog, shuffle_id, map_id, reduce_id)
                        except KeyError:
                            raise KeyError(
                                f"no block map {map_id} for shuffle "
                                f"{shuffle_id} reduce {reduce_id}") from None
                        self.request.sendall(
                            struct.pack("<B", 0)
                            + _FETCH_HEAD.pack(len(payload), crc))
                        self.request.sendall(payload)
                elif op == _OP_PUT:
                    # Replication push: the payload is ALWAYS drained off
                    # the socket (even if verification will fail) so the
                    # connection stays framed for the error reply.
                    head = _recv_exact(self.request, _FETCH_HEAD.size)
                    length, crc = _FETCH_HEAD.unpack(head)
                    payload = _recv_exact(self.request, length)
                    with _serve_span(trace64, span64, "shuffle.serve.put",
                                     shuffle=shuffle_id, reduce=reduce_id,
                                     map=map_id):
                        if crc:
                            CK.verify(payload, crc,
                                      f"replica put ({shuffle_id}, "
                                      f"{map_id}, {reduce_id})")
                        catalog.add_block(shuffle_id, map_id, reduce_id,
                                          payload)
                        self.request.sendall(struct.pack("<B", 0))
                else:
                    raise ValueError(f"bad opcode {op}")
            except (ConnectionError, OSError) as e:
                # Socket-plane failure: connection is gone. EXCEPT the
                # catalog's own typed corruption signal (an IOError so the
                # retry taxonomy buckets it transient): that must answer
                # as a protocol error so the peer can escalate to
                # recompute instead of seeing a silent disconnect.
                if not isinstance(e, (ShuffleBlockCorruptError,
                                      CK.ChecksumError)):
                    return
                msg = str(e).encode()
                try:
                    self.request.sendall(
                        struct.pack("<BI", 1, len(msg)) + msg)
                except OSError:
                    return
            except Exception as e:  # noqa: BLE001 - protocol error reply
                msg = str(e).encode()
                try:
                    self.request.sendall(
                        struct.pack("<BI", 1, len(msg)) + msg)
                except OSError:
                    return


class NetShuffleServer:
    """Serves one process's shuffle catalog over TCP (RapidsShuffleServer +
    management port). ``port=0`` picks a free port; ``address`` is what
    peers dial — the MapStatus-topology-string role."""

    def __init__(self, catalog, host: str = "127.0.0.1", port: int = 0):
        self._srv = socketserver.ThreadingTCPServer(
            (host, port), _Handler, bind_and_activate=True)
        self._srv.daemon_threads = True
        self._srv.catalog = catalog  # type: ignore[attr-defined]
        self._thread = threading.Thread(target=self._srv.serve_forever,
                                        daemon=True)
        self._thread.start()

    @property
    def address(self) -> Tuple[str, int]:
        return self._srv.server_address[:2]

    def close(self):
        self._srv.shutdown()
        self._srv.server_close()


class NetTransport(Transport):
    """TCP client side of the wire (one connection, request/response).
    Raises ConnectionError on handshake mismatch. Timeouts come from the
    shuffle.net confs via the callers (RetryingBlockIterator /
    exchange)."""

    def __init__(self, peer: Tuple[str, int], connect_timeout: float = 5.0,
                 request_timeout: float = 30.0, trace=None, deadline=None):
        self.peer = peer
        #: the requesting query's Tracer (or None): each request stamps
        #: the v4 (trace64, span64) header from its CURRENT span so the
        #: serving side stitches into this query's trace (ISSUE 13)
        self.trace = trace
        # The query deadline bounds the DIAL too (ISSUE 19 satellite): a
        # stalled connect or handshake against a black-holed peer must
        # not overshoot query.deadlineSecs by the full connect-timeout
        # ladder. The floor keeps a just-expired deadline from turning
        # the socket non-blocking (timeout=0) — the expiry itself is
        # raised by the caller's deadline.check, with full attribution.
        def _bound(t: float) -> float:
            return t if deadline is None else max(deadline.bound(t), 0.001)
        self._sock = socket.create_connection(
            peer, timeout=_bound(connect_timeout))
        self._sock.settimeout(_bound(connect_timeout))
        greeting = _recv_exact(self._sock, len(MAGIC) + 1)
        if greeting[:len(MAGIC)] != MAGIC or greeting[-1] != VERSION:
            self._sock.close()
            raise ConnectionError(f"bad handshake from {peer}: {greeting!r}")
        self._sock.settimeout(_bound(request_timeout))
        self._lock = lockdep.lock("NetTransport._lock", io_ok=True)

    def close(self):
        try:
            self._sock.close()
        except OSError:
            pass

    def _check_error(self, status: int) -> None:
        if status:
            (msg_len,) = struct.unpack("<I", _recv_exact(self._sock, 4))
            raise IOError(_recv_exact(self._sock, msg_len).decode())

    def request_metadata(self, shuffle_id: int,
                         reduce_id: int) -> List[BlockDescriptor]:
        t64, s64 = _wire_trace(self.trace)
        with self._lock:
            self._sock.sendall(_REQ.pack(_OP_META, shuffle_id, reduce_id, 0,
                                         t64, s64))
            status = _recv_exact(self._sock, 1)[0]
            self._check_error(status)
            (n,) = struct.unpack("<I", _recv_exact(self._sock, 4))
            out = []
            for _ in range(n):
                mid, length, crc = _META_ENTRY.unpack(
                    _recv_exact(self._sock, _META_ENTRY.size))
                # crc=0 is the wire encoding of "no checksum recorded"
                # (a crc-less serving catalog): verification must skip,
                # not fail every healthy block against zero.
                out.append(BlockDescriptor((shuffle_id, mid, reduce_id),
                                           length, block_no=mid,
                                           crc=crc or None))
            return out

    def put_block(self, shuffle_id: int, map_id: int, reduce_id: int,
                  payload: bytes, crc: int) -> None:
        """Replication push (protocol v5 PUT): register one block in the
        peer's catalog. The peer verifies ``payload`` against ``crc``
        before accepting — a corrupt push raises here (IOError carrying
        the replica's checksum complaint), it never poisons the
        replica."""
        t64, s64 = _wire_trace(self.trace)
        with self._lock:
            self._sock.sendall(
                _REQ.pack(_OP_PUT, shuffle_id, reduce_id, map_id, t64, s64)
                + _FETCH_HEAD.pack(len(payload), crc))
            self._sock.sendall(payload)
            status = _recv_exact(self._sock, 1)[0]
            self._check_error(status)

    def fetch_block_chunks(self, desc: BlockDescriptor, chunk_size: int):
        sid, mid, rid = desc.tag
        t64, s64 = _wire_trace(self.trace)
        with self._lock:
            self._sock.sendall(_REQ.pack(_OP_FETCH, sid, rid, mid,
                                         t64, s64))
            status = _recv_exact(self._sock, 1)[0]
            self._check_error(status)
            length, crc = _FETCH_HEAD.unpack(
                _recv_exact(self._sock, _FETCH_HEAD.size))
            if desc.crc is None and crc:
                # Fetch without a prior META (direct addressing): adopt
                # the wire-carried checksum so the client still verifies
                # (0 = the serving side has no checksum for this block).
                desc.crc = crc
            remaining = length
            try:
                while remaining > 0:
                    chunk = _recv_exact(self._sock,
                                        min(chunk_size, remaining))
                    remaining -= len(chunk)
                    yield chunk
            finally:
                # A consumer abandoning the generator early must not leave
                # payload bytes on the socket — the next request on this
                # transport would parse them as a status byte.
                try:
                    while remaining > 0:
                        remaining -= len(_recv_exact(
                            self._sock, min(chunk_size, remaining)))
                except (ConnectionError, OSError):
                    self.close()


def _net_timeouts(ctx) -> Tuple[float, float]:
    """(connect, request) timeouts from the context's conf, else the conf
    defaults — satellite of ISSUE 7 (previously hardcoded 5.0/30.0)."""
    from ..config import (SHUFFLE_NET_CONNECT_TIMEOUT,
                          SHUFFLE_NET_REQUEST_TIMEOUT)
    conf = getattr(ctx, "conf", None)
    try:
        return (float(conf.get(SHUFFLE_NET_CONNECT_TIMEOUT)),
                float(conf.get(SHUFFLE_NET_REQUEST_TIMEOUT)))
    except (AttributeError, TypeError):
        return (SHUFFLE_NET_CONNECT_TIMEOUT.default,
                SHUFFLE_NET_REQUEST_TIMEOUT.default)


class PeerLatencyStats:
    """Per-peer fetch-latency EWMA — the straggler detector's model of
    "normal" (ISSUE 19). One scalar per peer updated on every successful
    primary fetch; :meth:`p50` is the EWMA read back as the p50 proxy the
    hedge threshold multiplies (an EWMA of individual latencies tracks
    the central tendency without keeping a histogram per peer — the
    trade the hedge knob's quantileFactor absorbs). Session-scoped when
    reached through ``MapOutputTracker.latency`` (the normal path), with
    a process-global fallback for bare iterators."""

    def __init__(self, alpha: float = 0.2):
        self.alpha = float(alpha)
        self._ewma: Dict[Tuple[str, int], float] = {}
        self._lock = lockdep.lock("PeerLatencyStats._lock")

    def record(self, peer: Tuple[str, int], seconds: float) -> None:
        with self._lock:
            prev = self._ewma.get(peer)
            self._ewma[peer] = seconds if prev is None \
                else prev + self.alpha * (seconds - prev)

    def p50(self, peer: Tuple[str, int]) -> Optional[float]:
        """Observed typical fetch latency for ``peer`` in SECONDS, or
        None for a peer never successfully fetched from (cold)."""
        with self._lock:
            return self._ewma.get(peer)


#: Fallback latency model for iterators built without a session context
#: (bare tests, tools). Session-owned stats live on MapOutputTracker.
_GLOBAL_LATENCY = PeerLatencyStats()


class HedgePolicy:
    """When to launch a duplicate fetch (snapshotted from conf). The
    hedge delay is ``max(minDelayMs, quantileFactor * p50(peer))``; a
    COLD peer (no successful fetch yet, so no p50) is never hedged —
    the model warms on the first fetch, like every production hedging
    implementation, so a healthy run reports hedgedFetches == 0.
    Hedging only arms when a hedge SOURCE exists (a replica or the
    local recompute closure), so un-replicated deployments never pay
    the pool dispatch."""

    def __init__(self, enabled: bool = True, quantile_factor: float = 3.0,
                 min_delay_s: float = 0.02):
        self.enabled = bool(enabled)
        self.quantile_factor = float(quantile_factor)
        self.min_delay_s = float(min_delay_s)

    @classmethod
    def from_ctx(cls, ctx) -> "HedgePolicy":
        from ..config import (SHUFFLE_HEDGE_ENABLED,
                              SHUFFLE_HEDGE_MIN_DELAY_MS,
                              SHUFFLE_HEDGE_QUANTILE_FACTOR)
        conf = getattr(ctx, "conf", None)
        try:
            return cls(bool(conf.get(SHUFFLE_HEDGE_ENABLED)),
                       float(conf.get(SHUFFLE_HEDGE_QUANTILE_FACTOR)),
                       float(conf.get(SHUFFLE_HEDGE_MIN_DELAY_MS)) / 1e3)
        except (AttributeError, TypeError):
            return cls(SHUFFLE_HEDGE_ENABLED.default,
                       SHUFFLE_HEDGE_QUANTILE_FACTOR.default,
                       SHUFFLE_HEDGE_MIN_DELAY_MS.default / 1e3)

    def delay_s(self, p50: Optional[float]) -> Optional[float]:
        """Seconds to wait before hedging, or None (= never) for a cold
        peer with no latency model yet."""
        if p50 is None:
            return None
        return max(self.min_delay_s, self.quantile_factor * p50)


class _HedgeSource:
    """Where a won hedge came from — and how to keep using it for the
    REST of the partition (after a hedge win the straggling primary's
    connection is closed; remaining blocks read from the winner)."""

    def __init__(self, label: str, fetch: Callable, close: Callable):
        self.label = label
        self.fetch = fetch  # BlockDescriptor -> verified payload bytes
        self.close = close
        self.is_replica = label.startswith("replica:")


def _discard_hedge_result(future) -> None:
    """Done-callback for the LOSER of a hedge race: swallow its error
    (the winner already delivered) and close any replica connection it
    opened — losers must not leak sockets or poison the pool."""
    try:
        res = future.result()
    except BaseException:  # noqa: BLE001 - loser errors are expected
        return
    if isinstance(res, tuple) and len(res) == 3 \
            and isinstance(res[2], _HedgeSource):
        try:
            res[2].close()
        except OSError:  # best-effort cleanup
            pass


def replicate_shuffle(peer: Tuple[str, int], catalog, shuffle_id: int,
                      ctx=None, node: str = "ShuffleReplicate") -> int:
    """Push every registered block of ``shuffle_id`` to the replica
    serving at ``peer`` (protocol v5 PUT, CRC-verified at the replica).
    Returns the number of blocks pushed. Raises on a dead replica — the
    CALLER treats that as degraded replication (skip registering this
    replica), never as a query failure. The ``shuffle.replicate``
    injection seam applies ``peerDeath`` (push fails, replica not
    registered) and ``replicaLoss`` (one block silently never arrives —
    the replica registers with a hole, so a later primary failure must
    fall through the replica ladder to lineage recompute)."""
    from ..utils.fault_injection import register_site
    register_site("shuffle.replicate")
    injector = getattr(ctx, "fault_injector", None)
    deadline = getattr(ctx, "deadline", None)
    connect_t, request_t = _net_timeouts(ctx)
    from ..metrics import trace as TR
    tracer = TR.tracer_of(getattr(ctx, "trace", None))
    transport = NetTransport(peer, connect_t, request_t, trace=tracer,
                             deadline=deadline)
    pushed = 0
    try:
        for map_id, reduce_id in sorted(
                catalog.sizes_for_shuffle(shuffle_id)):
            if deadline is not None:
                deadline.check("shuffle.replicate", ctx, node)
            fault = injector.check_net(
                "shuffle.replicate", classes=("peerDeath", "replicaLoss")
            ) if injector is not None else None
            if fault == "replicaLoss":
                continue
            if fault == "peerDeath":
                raise ConnectionError(
                    f"injected replica death during replication push of "
                    f"shuffle {shuffle_id}")
            payload, crc = _block_payload_crc(catalog, shuffle_id, map_id,
                                              reduce_id)
            with TR.span(tracer, "shuffle.replicate", cat="shuffle",
                         peer=f"{peer[0]}:{peer[1]}", map=map_id,
                         reduce=reduce_id), \
                    lockdep.blocking("shuffle.replicate_push"):
                transport.put_block(shuffle_id, map_id, reduce_id,
                                    payload, crc)
            pushed += 1
    finally:
        transport.close()
    return pushed


class RetryingBlockIterator:
    """Task-facing STREAMING fetch iterator with retry
    (RapidsShuffleIterator:46).

    Pulls every block of (shuffle_id, reduce_id) from ``peer``, yielding
    each block as soon as it arrives and passes CRC32C verification —
    blocks are never buffered for the whole partition (the pre-ISSUE-7
    iterator held every block in memory before yielding the first).
    Transient failures (connection resets, short reads, checksum
    mismatches, timeouts) reconnect and retry up to ``max_retries`` with
    exponential backoff, REFETCHING ONLY the blocks not yet yielded;
    exhaustion raises :class:`ShuffleFetchFailedError` carrying the
    already-yielded map ids for the recompute path. An optional ``ctx``
    threads in conf timeouts, the query deadline, the network fault
    injector, and metric attribution (``shuffleBlocksRefetched``).

    ISSUE 19 adds STRAGGLER HEDGING: with ``replicas`` (peers holding a
    replication-pushed copy) and/or a ``local_fallback`` recompute
    closure, a primary fetch exceeding the :class:`HedgePolicy`
    threshold (quantileFactor x the peer's :class:`PeerLatencyStats`
    p50) races a duplicate request on the shared pipeline pool — first
    VERIFIED payload wins, the loser is cancelled (its connection
    closed, its error swallowed), and after a hedge win the remaining
    blocks stream from the winner. Every delivered block still passes
    the same CRC32C gate regardless of source, so hedging can reorder
    who answers but never what arrives."""

    def __init__(self, peer: Tuple[str, int], shuffle_id: int,
                 reduce_id: int, bounce: Optional[BounceBufferPool] = None,
                 throttle: Optional[Throttle] = None, max_retries: int = 3,
                 backoff_s: float = 0.05,
                 transport_factory: Optional[Callable[[], Transport]] = None,
                 ctx=None, node: str = "ShuffleFetch",
                 map_range: Optional[Tuple[int, int]] = None,
                 with_map_ids: bool = False,
                 replicas: Optional[List[Tuple[str, int]]] = None,
                 local_fallback: Optional[Callable[[int], bytes]] = None,
                 skip_map_ids=None,
                 latency: Optional[PeerLatencyStats] = None,
                 hedge: Optional[HedgePolicy] = None):
        self.peer = peer
        self.shuffle_id = shuffle_id
        self.reduce_id = reduce_id
        self.bounce = bounce or BounceBufferPool(1 << 20, 4)
        self.throttle = throttle or Throttle(64 << 20)
        self.max_retries = max_retries
        self.backoff_s = backoff_s
        self.ctx = ctx
        self.node = node
        self.map_range = map_range
        self.with_map_ids = with_map_ids
        #: peers holding replication-pushed copies of this shuffle's
        #: blocks (MapOutputTracker.replicas_for) — hedge targets.
        self.replicas = [tuple(r) for r in (replicas or ())]
        #: map_id -> payload closure regenerating one block from lineage
        #: locally — the hedge target of last resort.
        self.local_fallback = local_fallback
        #: map ids ALREADY delivered by an earlier source (a failed
        #: primary's partial stream) — never refetched, never re-yielded.
        self.skip_map_ids = frozenset(skip_map_ids or ())
        tracker = getattr(ctx, "shuffle_tracker", None)
        self._tracker = tracker
        self.latency = latency \
            or getattr(tracker, "latency", None) or _GLOBAL_LATENCY
        self.hedge = hedge or HedgePolicy.from_ctx(ctx)
        if self.replicas or self.local_fallback is not None:
            from ..utils.fault_injection import register_site
            register_site("shuffle.hedgeFetch")
        self.connect_timeout, self.request_timeout = _net_timeouts(ctx)
        from ..metrics import trace as TR
        self._trace = TR.tracer_of(getattr(ctx, "trace", None))
        self._deadline = getattr(ctx, "deadline", None)
        self._factory = transport_factory or (
            lambda: NetTransport(peer, self.connect_timeout,
                                 self.request_timeout, trace=self._trace,
                                 deadline=self._deadline))
        #: map_id -> verified crc32c (or None for crc-less blocks) of
        #: every block yielded so far — recovery consumers
        #: (fetch_with_recovery) read this instead of re-hashing payloads
        #: the client already verified. Reset at each __iter__.
        self.delivered_crcs: dict = {}

    def _metric(self, name: str, value: int) -> None:
        if self.ctx is not None and hasattr(self.ctx, "metric"):
            self.ctx.metric(self.node, name, value)

    def _tally(self, name: str) -> None:
        """Session-level self-healing tally (serve health view) — rides
        on the MapOutputTracker when the context carries one."""
        if self._tracker is not None and hasattr(self._tracker, "tally"):
            self._tracker.tally(name)

    # -- hedged fetch (ISSUE 19) --------------------------------------

    def _hedge_sources_armed(self) -> bool:
        return self.hedge.enabled and bool(
            self.replicas or self.local_fallback is not None)

    def _verify_fallback(self, desc: BlockDescriptor) -> bytes:
        """Regenerate one block from lineage and hold it to the same
        CRC gate a fetched payload passes (generation mixing shows up
        here as a checksum mismatch, which fails the hedge)."""
        payload = self.local_fallback(desc.tag[1])
        if desc.crc is not None:
            CK.verify(payload, desc.crc,
                      f"hedge recompute block {desc.tag}", self.ctx,
                      self.node)
        return payload

    def _replica_source(self, rp: Tuple[str, int]) -> _HedgeSource:
        """Open a verified fetch path to one replica. Hedge fetches
        count against their OWN injection site (shuffle.hedgeFetch) so
        arming a hedge never perturbs the primary path's deterministic
        fault schedule."""
        transport = NetTransport(rp, self.connect_timeout,
                                 self.request_timeout, trace=self._trace,
                                 deadline=self._deadline)
        client = ShuffleClient(transport, self.bounce, self.throttle,
                               ctx=self.ctx, node=self.node,
                               injection_site="shuffle.hedgeFetch")
        return _HedgeSource(f"replica:{rp[0]}:{rp[1]}", client.fetch_one,
                            transport.close)

    def _hedge_attempt(self, desc: BlockDescriptor):
        """Runs ON THE POOL as the duplicate request: try each replica,
        then the local recompute closure; first verified payload wins.
        Returns (payload, label, reusable _HedgeSource or None)."""
        last_error: Optional[BaseException] = None
        for rp in self.replicas:
            source = None
            try:
                source = self._replica_source(rp)
                return source.fetch(desc), source.label, source
            except (OSError, ShuffleFetchFailedError) as e:  # next source
                if source is not None:
                    source.close()
                last_error = e
        if self.local_fallback is not None:
            payload = self._verify_fallback(desc)
            return payload, "recompute", _HedgeSource(
                "recompute", self._verify_fallback, lambda: None)
        raise last_error if last_error is not None else IOError(
            f"no hedge source for block {desc.tag}")

    def _fetch_hedged(self, client: ShuffleClient, desc: BlockDescriptor,
                      attempt: int):
        """One block through the hedge race. Returns (payload, source
        label, takeover _HedgeSource or None). A primary failure with no
        hedge in flight raises verbatim (the normal retry ladder); once
        a hedge IS in flight, whichever side verifies first wins and the
        other side's error is irrelevant."""
        from concurrent.futures import FIRST_COMPLETED
        from concurrent.futures import wait as cf_wait
        from ..exec.pipeline import get_pool
        from ..metrics import trace as TR
        t0 = time.monotonic()
        delay = self.hedge.delay_s(self.latency.p50(self.peer))
        if delay is None:
            # Cold peer: no latency model to call it a straggler against.
            payload = client.fetch_one(desc)
            self.latency.record(self.peer, time.monotonic() - t0)
            return payload, "primary", None
        try:
            pool = get_pool()
            primary_f = pool.submit(client.fetch_one, desc)
        except RuntimeError:
            # Pool tearing down under a concurrent session close:
            # hedging is a luxury, the fetch is not.
            payload = client.fetch_one(desc)
            self.latency.record(self.peer, time.monotonic() - t0)
            return payload, "primary", None
        if self._deadline is not None:
            delay = self._deadline.bound(delay)
        with TR.span(self._trace, "shuffle.hedge_wait", cat="shuffle",
                     peer=f"{self.peer[0]}:{self.peer[1]}",
                     map=desc.tag[1]), \
                lockdep.blocking("shuffle.hedge_wait"):
            done, _ = cf_wait([primary_f], timeout=delay)
        if done:
            payload = primary_f.result()  # raises into the retry ladder
            self.latency.record(self.peer, time.monotonic() - t0)
            return payload, "primary", None
        # The primary is a straggler: launch the duplicate.
        self._metric("hedgedFetches", 1)
        self._tally("hedged_fetches")
        try:
            hedge_f = pool.submit(self._hedge_attempt, desc)
        except RuntimeError:
            payload = primary_f.result()
            self.latency.record(self.peer, time.monotonic() - t0)
            return payload, "primary", None
        pending = {primary_f, hedge_f}
        errors: dict = {}
        with TR.span(self._trace, "shuffle.hedge_race", cat="shuffle",
                     peer=f"{self.peer[0]}:{self.peer[1]}",
                     map=desc.tag[1]), \
                lockdep.blocking("shuffle.hedge_wait"):
            while pending:
                if self._deadline is not None:
                    self._deadline.check(
                        f"shuffle.hedge {self.peer[0]}:{self.peer[1]}",
                        self.ctx, self.node)
                done, _ = cf_wait(list(pending), timeout=0.05,
                                  return_when=FIRST_COMPLETED)
                for f in done:
                    pending.discard(f)
                    try:
                        res = f.result()
                    except Exception as e:  # tpu-lint: ignore — either side of the race may lose with ANY error; the winner's payload (or the primary's error, below) is the outcome
                        errors[f] = e
                        continue
                    if f is primary_f:
                        # Primary answered before the hedge: hedge loss.
                        hedge_f.add_done_callback(_discard_hedge_result)
                        self.latency.record(self.peer,
                                            time.monotonic() - t0)
                        return res, "primary", None
                    # Hedge win: cancel the straggling primary by
                    # closing its connection (unblocks the pool worker;
                    # its error is swallowed below) and keep the winning
                    # source for the REST of the partition.
                    payload, label, source = res
                    self._metric("hedgeWins", 1)
                    self._tally("hedge_wins")
                    try:
                        client.transport.close()
                    except OSError:  # already dead
                        pass
                    primary_f.add_done_callback(
                        lambda f: f.exception())  # observe, don't raise
                    return payload, label, source
        # Both sides failed: surface the PRIMARY error so the retry
        # ladder sees the same failure it would have without hedging.
        raise errors.get(primary_f) or errors.get(hedge_f) \
            or IOError(f"hedged fetch of {desc.tag} failed")

    def __iter__(self) -> Iterator:
        deadline = getattr(self.ctx, "deadline", None)
        self.delivered_crcs = {}
        yielded: set = set(self.skip_map_ids)
        attempted: set = set()
        last_error = "unknown"
        hedging = self._hedge_sources_armed()
        for attempt in range(self.max_retries + 1):
            prev_attempted = frozenset(attempted)
            transport = None
            takeover: Optional[_HedgeSource] = None
            try:
                if deadline is not None:
                    # Bound the DIAL by the deadline too (the transport
                    # clamps its connect/handshake timeouts, this check
                    # attributes an already-expired deadline before we
                    # spend a socket on it).
                    deadline.check(
                        f"shuffle.dial {self.peer[0]}:{self.peer[1]}",
                        self.ctx, self.node)
                transport = self._factory()
                client = ShuffleClient(transport, self.bounce,
                                       self.throttle, ctx=self.ctx,
                                       node=self.node)
                descs = transport.request_metadata(self.shuffle_id,
                                                   self.reduce_id)
                if self.map_range is not None:
                    lo, hi = self.map_range
                    descs = [d for d in descs if lo <= d.tag[1] < hi]
                pending = [d for d in descs if d.tag[1] not in yielded]
                for desc in pending:
                    if deadline is not None:
                        deadline.check(
                            f"shuffle.fetch {self.peer[0]}:{self.peer[1]}",
                            self.ctx, self.node)
                    # Count ONLY blocks a previous attempt actually
                    # started fetching — a block never tried before is a
                    # first fetch, not a refetch (keeps the recovery
                    # counters honest about work redone).
                    if desc.tag[1] in prev_attempted:
                        self._metric("shuffleBlocksRefetched", 1)
                    attempted.add(desc.tag[1])
                    from ..metrics import trace as TR
                    with TR.span(self._trace, "shuffle.fetch",
                                 cat="shuffle",
                                 peer=f"{self.peer[0]}:{self.peer[1]}",
                                 map=desc.tag[1], attempt=attempt,
                                 refetch=desc.tag[1] in prev_attempted), \
                            lockdep.blocking("shuffle.fetch_wait"):
                        if takeover is not None:
                            payload = takeover.fetch(desc)
                            source_label = takeover.label
                        elif hedging:
                            payload, source_label, takeover = \
                                self._fetch_hedged(client, desc, attempt)
                        else:
                            t0 = time.monotonic()
                            payload = client.fetch_one(desc)
                            self.latency.record(
                                self.peer, time.monotonic() - t0)
                            source_label = "primary"
                    if source_label.startswith("replica:"):
                        self._metric("replicaReads", 1)
                        self._tally("replica_reads")
                    yielded.add(desc.tag[1])
                    self.delivered_crcs[desc.tag[1]] = desc.crc
                    yield (desc.tag[1], payload) if self.with_map_ids \
                        else payload
                return
            except QueryDeadlineExceeded:
                raise
            except GeneratorExit:
                raise
            except Exception as e:  # noqa: BLE001 - wire faults retried
                from ..memory.retry import Classification, classify
                if not isinstance(e, (OSError, ShuffleFetchFailedError)) \
                        and classify(e) is Classification.FATAL:
                    # A bug is not a wire fault: don't launder it into
                    # the refetch ladder's typed error.
                    raise
                last_error = f"{type(e).__name__}: {e}"
            finally:
                if transport is not None and hasattr(transport, "close"):
                    transport.close()
                if takeover is not None:
                    try:
                        takeover.close()
                    except OSError:  # best-effort
                        pass
            if attempt < self.max_retries:
                delay = self.backoff_s * (2 ** attempt)
                if deadline is not None:
                    deadline.check(
                        f"shuffle.fetch {self.peer[0]}:{self.peer[1]}",
                        self.ctx, self.node)
                    delay = deadline.bound(delay)
                from ..metrics import trace as TR
                with TR.span(self._trace, "shuffle.backoff", cat="shuffle",
                             attempt=attempt), \
                        lockdep.blocking("shuffle.fetch_backoff"):
                    time.sleep(delay)
        raise ShuffleFetchFailedError(self.peer, self.shuffle_id,
                                      self.reduce_id, last_error,
                                      yielded_map_ids=yielded)
