"""Wire-transport tests: real TCP sockets, handshake, chunked fetch through
the client state machine, fetch-failure retry, and a true cross-process
fetch (the reference tests these layers with mocked transactions,
RapidsShuffleTestHelper.scala:33-120; the wire itself deserves real
sockets)."""

import os
import socket
import struct
import subprocess
import sys
import threading
import time

import pytest

from spark_rapids_tpu.shuffle.exchange import ShuffleBufferCatalog
from spark_rapids_tpu.shuffle.net import (MAGIC, VERSION, NetShuffleServer,
                                          NetTransport,
                                          RetryingBlockIterator,
                                          ShuffleFetchFailedError)
from spark_rapids_tpu.shuffle.serializer import serialize_batch
from spark_rapids_tpu.shuffle.codec import get_codec


def _payload(tag: int) -> bytes:
    import pyarrow as pa
    rb = pa.RecordBatch.from_pydict({"v": list(range(tag, tag + 10))})
    return serialize_batch(rb, get_codec("none"))


@pytest.fixture
def served_catalog():
    cat = ShuffleBufferCatalog()
    blocks = {}
    for m in range(3):
        for r in range(2):
            p = _payload(m * 10 + r)
            blocks[(m, r)] = p
            cat.add_block(5, m, r, p)
    srv = NetShuffleServer(cat)
    yield srv, blocks
    srv.close()
    cat.close()


class TestWire:
    def test_handshake_and_metadata(self, served_catalog):
        srv, blocks = served_catalog
        t = NetTransport(srv.address)
        descs = t.request_metadata(5, 0)
        assert [d.length for d in descs] == \
            [len(blocks[(m, 0)]) for m in range(3)]
        t.close()

    def test_fetch_roundtrip_chunked(self, served_catalog):
        srv, blocks = served_catalog
        t = NetTransport(srv.address)
        descs = t.request_metadata(5, 1)
        got = [b"".join(t.fetch_block_chunks(d, 16)) for d in descs]
        assert got == [blocks[(m, 1)] for m in range(3)]
        t.close()

    def test_unknown_block_is_protocol_error_not_disconnect(
            self, served_catalog):
        srv, _ = served_catalog
        t = NetTransport(srv.address)
        from spark_rapids_tpu.shuffle.transport import BlockDescriptor
        # FETCH is keyed by the stable (shuffle, map, reduce) tag; an
        # unknown map_id is a protocol-level error reply.
        with pytest.raises(IOError):
            list(t.fetch_block_chunks(
                BlockDescriptor((5, 99, 0), 10, block_no=99), 16))
        # connection still usable after an error reply
        assert len(t.request_metadata(5, 0)) == 3
        t.close()

    def test_abandoned_fetch_does_not_desync_protocol(self, served_catalog):
        # Abandoning the chunk generator mid-payload must drain the socket:
        # the next request on the same transport still parses correctly.
        srv, blocks = served_catalog
        t = NetTransport(srv.address)
        descs = t.request_metadata(5, 0)
        gen = t.fetch_block_chunks(descs[0], 8)
        next(gen)  # read one chunk, leave the rest unread
        gen.close()
        assert len(t.request_metadata(5, 1)) == 3
        got = b"".join(t.fetch_block_chunks(descs[1], 16))
        assert got == blocks[(descs[1].tag[1], 0)]
        t.close()

    def test_meta_is_metadata_only(self, served_catalog):
        # META must not materialize payloads server-side: register a block
        # whose payload lives on disk via a catalog with a zero host
        # budget, then answer META without touching the spill file.
        cat = ShuffleBufferCatalog(host_budget_bytes=0)
        p = _payload(1)
        cat.add_block(9, 0, 0, p)
        from spark_rapids_tpu.utils.checksum import crc32c
        metas = cat.block_metas_for_reduce(9, 0)
        assert metas == [(0, len(p), crc32c(p))]
        assert cat._spill_file is not None  # block went to disk
        assert cat.read_block(9, 0, 0) == p
        cat.close()

    def test_bad_handshake_rejected(self):
        srv = socket.socket()
        srv.bind(("127.0.0.1", 0))
        srv.listen(1)

        def fake():
            conn, _ = srv.accept()
            conn.sendall(b"NOTSR" + bytes([9]))
            conn.close()
        threading.Thread(target=fake, daemon=True).start()
        with pytest.raises(ConnectionError):
            NetTransport(srv.getsockname())
        srv.close()

    def test_iterator_drains_all_blocks(self, served_catalog):
        srv, blocks = served_catalog
        got = list(RetryingBlockIterator(srv.address, 5, 0))
        assert got == [blocks[(m, 0)] for m in range(3)]

    def test_fetch_failed_after_retries(self):
        # nobody listening on this port
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        addr = s.getsockname()
        s.close()
        it = RetryingBlockIterator(addr, 1, 0, max_retries=2,
                                   backoff_s=0.01)
        with pytest.raises(ShuffleFetchFailedError) as ei:
            list(it)
        assert ei.value.peer == addr
        assert ei.value.reduce_id == 0

    def test_retry_recovers_from_flaky_server(self, served_catalog):
        srv, blocks = served_catalog
        attempts = {"n": 0}
        real_addr = srv.address

        class FlakyFirst:
            """Transport factory whose first connection dies mid-flight."""

            def __call__(self):
                attempts["n"] += 1
                t = NetTransport(real_addr)
                if attempts["n"] == 1:
                    t._sock.close()  # simulate connection reset
                return t
        got = list(RetryingBlockIterator(
            real_addr, 5, 1, max_retries=3, backoff_s=0.01,
            transport_factory=FlakyFirst()))
        assert got == [blocks[(m, 1)] for m in range(3)]
        assert attempts["n"] >= 2


CHILD = r"""
import os, sys, struct, time
sys.path.insert(0, os.getcwd())
os.environ.setdefault("JAX_PLATFORMS", "cpu")
from spark_rapids_tpu.shuffle.exchange import ShuffleBufferCatalog
from spark_rapids_tpu.shuffle.net import NetShuffleServer
cat = ShuffleBufferCatalog()
for m in range(2):
    for r in range(2):
        cat.add_block(9, m, r, bytes([m * 4 + r]) * 1000)
srv = NetShuffleServer(cat)
print(srv.address[1], flush=True)
time.sleep(30)
"""


MAP_CHILD = r"""
import os, sys, time
sys.path.insert(0, os.getcwd())
os.environ["JAX_PLATFORM_NAME"] = "cpu"
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"
import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np
import pyarrow as pa
from spark_rapids_tpu.session import TpuSession
from spark_rapids_tpu.ops.expression import col
from spark_rapids_tpu.ops import aggregates as A
from spark_rapids_tpu.shuffle.exchange import ShuffleBufferCatalog
from spark_rapids_tpu.shuffle.net import NetShuffleServer
from spark_rapids_tpu.shuffle.serializer import serialize_batch
from spark_rapids_tpu.shuffle.codec import get_codec

# MAP side of a two-stage aggregate: each input split runs a device
# partial aggregate, hash-partitions its group rows into reduce blocks,
# and serves them over the wire (RapidsCachingWriter role).
rng = np.random.default_rng(77)
k = rng.integers(0, 40, 4000)
v = rng.normal(0, 10, 4000)
s = TpuSession({"spark.rapids.sql.enabled": True})
cat = ShuffleBufferCatalog()
N_REDUCE = 2
for m, sl in enumerate((slice(0, 1500), slice(1500, 4000))):
    part = pa.table({"k": k[sl], "v": v[sl]})
    partial = (s.create_dataframe(part).group_by(col("k"))
               .agg(A.AggregateExpression(A.Sum(col("v")), "sv"),
                    A.AggregateExpression(A.Count(), "c"))
               .collect())
    kk = np.asarray(partial.column("k"))
    for r in range(N_REDUCE):
        piece = partial.filter(pa.array(kk % N_REDUCE == r))
        if piece.num_rows == 0:
            continue
        rb = piece.combine_chunks().to_batches()[0]
        cat.add_block(3, m, r, serialize_batch(rb, get_codec("lz4")))
srv = NetShuffleServer(cat)
print(srv.address[1], flush=True)
time.sleep(60)
"""


class TestCrossProcess:
    def test_two_process_aggregate_query(self):
        """End-to-end query across two processes: process A maps (partial
        aggregate + hash partition + serve), this process reduces (fetch,
        merge aggregate) — and the result matches a single-process oracle
        (reference read path role, RapidsCachingReader.scala:49)."""
        import numpy as np
        import pyarrow as pa

        from spark_rapids_tpu.session import TpuSession
        from spark_rapids_tpu.ops.expression import col
        from spark_rapids_tpu.ops import aggregates as A
        from spark_rapids_tpu.shuffle.serializer import deserialize_batch

        proc = subprocess.Popen(
            [sys.executable, "-c", MAP_CHILD], stdout=subprocess.PIPE,
            cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            text=True)
        try:
            port = int(proc.stdout.readline())
            s = TpuSession({"spark.rapids.sql.enabled": True})
            outs = []
            for r in range(2):
                payloads = list(RetryingBlockIterator(
                    ("127.0.0.1", port), 3, r))
                rbs = [deserialize_batch(p)[1] for p in payloads]
                merged = pa.Table.from_batches(rbs)
                outs.append(
                    (s.create_dataframe(merged.combine_chunks()
                                        .to_batches()[0])
                     .group_by(col("k"))
                     .agg(A.AggregateExpression(A.Sum(col("sv")), "sv"),
                          A.AggregateExpression(A.Sum(col("c")), "c"))
                     .collect()))
            got = pa.concat_tables(outs).sort_by("k").to_pydict()
            # Oracle: same data, one process, one aggregate.
            rng = np.random.default_rng(77)
            k = rng.integers(0, 40, 4000)
            v = rng.normal(0, 10, 4000)
            cpu = TpuSession({"spark.rapids.sql.enabled": False})
            exp = (cpu.create_dataframe(pa.table({"k": k, "v": v}))
                   .group_by(col("k"))
                   .agg(A.AggregateExpression(A.Sum(col("v")), "sv"),
                        A.AggregateExpression(A.Count(), "c"))
                   .collect().sort_by("k").to_pydict())
            assert got["k"] == exp["k"]
            assert got["c"] == exp["c"]
            assert np.allclose(got["sv"], exp["sv"], rtol=1e-9)
        finally:
            proc.kill()
            proc.wait()

    def test_fetch_from_another_process(self):
        proc = subprocess.Popen(
            [sys.executable, "-c", CHILD], stdout=subprocess.PIPE,
            cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            text=True)
        try:
            port = int(proc.stdout.readline())
            got = list(RetryingBlockIterator(("127.0.0.1", port), 9, 1))
            assert got == [bytes([r]) * 1000 for r in (1, 5)]
        finally:
            proc.kill()
            proc.wait()


_MATRIX_BLOCKS = [bytes([m + 1]) * 1000 for m in range(3)]

DYING_CHILD = r"""
import os, sys, time
sys.path.insert(0, os.getcwd())
os.environ.setdefault("JAX_PLATFORMS", "cpu")
from spark_rapids_tpu.shuffle.exchange import ShuffleBufferCatalog
from spark_rapids_tpu.shuffle.net import NetShuffleServer
cat = ShuffleBufferCatalog()
for m in range(3):
    cat.add_block(9, m, 0, bytes([m + 1]) * 1000)
real = cat.read_block_with_crc
served = [0]
def dying(sid, mid, rid):
    served[0] += 1
    if served[0] > 1:
        os._exit(1)  # the peer dies mid-fetch, after serving one block
    return real(sid, mid, rid)
cat.read_block_with_crc = dying
srv = NetShuffleServer(cat)
print(srv.address[1], flush=True)
time.sleep(30)
"""

CORRUPT_CHILD = r"""
import os, sys, time
sys.path.insert(0, os.getcwd())
os.environ.setdefault("JAX_PLATFORMS", "cpu")
from spark_rapids_tpu.shuffle.exchange import ShuffleBufferCatalog
from spark_rapids_tpu.shuffle.net import NetShuffleServer
cat = ShuffleBufferCatalog()
for m in range(3):
    cat.add_block(9, m, 0, bytes([m + 1]) * 1000)
# Bit rot on the serving side: map 1's stored bytes no longer match the
# checksum recorded at registration.
cat._crcs[(9, 1, 0)] ^= 0xFFFF
srv = NetShuffleServer(cat)
print(srv.address[1], flush=True)
time.sleep(30)
"""


def _spawn(child_src):
    proc = subprocess.Popen(
        [sys.executable, "-c", child_src], stdout=subprocess.PIPE,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        text=True)
    port = int(proc.stdout.readline())
    return proc, ("127.0.0.1", port)


def _recovery_env():
    """(ctx, tracker-with-lineage): the driver-side knowledge a real
    scheduler has — every rank's map outputs are deterministically
    regenerable from its input-shard assignment."""
    import types

    from spark_rapids_tpu.config import TpuConf
    from spark_rapids_tpu.shuffle.exchange import MapOutputTracker
    conf = TpuConf({
        "spark.rapids.tpu.shuffle.net.connectTimeout": 0.5,
        "spark.rapids.tpu.shuffle.net.requestTimeout": 0.3,
        "spark.rapids.tpu.shuffle.net.maxPeerFailures": 1,
    })
    ctx = types.SimpleNamespace(conf=conf, deadline=None,
                                fault_injector=None)
    tracker = MapOutputTracker(conf)
    tracker.set_peer_lineage(
        lambda peer, sid, rid: [(m, _MATRIX_BLOCKS[m]) for m in range(3)])
    return ctx, tracker


class TestTwoProcessRecoveryMatrix:
    """The ISSUE-7 recovery matrix against a REAL second process: a peer
    killed mid-fetch, a block corrupted at rest on the peer, and a peer
    stalled past requestTimeout must each recover bit-identically via
    refetch/recompute — or raise the typed error naming the peer."""

    def test_peer_killed_mid_fetch_recomputes(self):
        from spark_rapids_tpu.shuffle.exchange import fetch_with_recovery
        proc, peer = _spawn(DYING_CHILD)
        ctx, tracker = _recovery_env()
        try:
            got = list(fetch_with_recovery(
                peer, 9, 0, tracker, ctx=ctx, max_retries=1,
                backoff_s=0.01))
            # Bit-identical: one block arrived over the wire before the
            # peer died; lineage regenerated exactly the missing two.
            assert got == _MATRIX_BLOCKS
            assert tracker.metrics["map_tasks_recomputed"] > 0
            assert tracker.is_blacklisted(peer)
        finally:
            proc.kill()
            proc.wait()

    def test_corrupt_block_on_peer_recomputes(self):
        from spark_rapids_tpu.shuffle.exchange import fetch_with_recovery
        proc, peer = _spawn(CORRUPT_CHILD)
        ctx, tracker = _recovery_env()
        try:
            got = list(fetch_with_recovery(
                peer, 9, 0, tracker, ctx=ctx, max_retries=1,
                backoff_s=0.01))
            assert got == _MATRIX_BLOCKS
            assert tracker.metrics["map_tasks_recomputed"] > 0
        finally:
            proc.kill()
            proc.wait()

    def test_corrupt_block_without_lineage_is_typed(self):
        proc, peer = _spawn(CORRUPT_CHILD)
        ctx, _ = _recovery_env()
        try:
            it = RetryingBlockIterator(peer, 9, 0, ctx=ctx, max_retries=1,
                                       backoff_s=0.01)
            with pytest.raises(ShuffleFetchFailedError) as ei:
                list(it)
            # The typed error names the peer and carries what arrived.
            assert ei.value.peer == peer
            assert ei.value.yielded_map_ids == frozenset({0})
            assert "checksum" in str(ei.value)
        finally:
            proc.kill()
            proc.wait()

    def _stall_server(self):
        """A handshaking server that then goes silent — the slow-peer
        stall the requestTimeout exists for."""
        srv = socket.socket()
        srv.bind(("127.0.0.1", 0))
        srv.listen(4)
        stop = threading.Event()

        def run():
            while not stop.is_set():
                try:
                    conn, _ = srv.accept()
                except OSError:
                    return
                try:
                    conn.sendall(MAGIC + bytes([VERSION]))
                except OSError:
                    pass
                # ...and never answer another byte.
        t = threading.Thread(target=run, daemon=True)
        t.start()
        return srv, stop

    def test_stalled_peer_times_out_and_recomputes(self):
        from spark_rapids_tpu.shuffle.exchange import fetch_with_recovery
        srv, stop = self._stall_server()
        ctx, tracker = _recovery_env()
        try:
            t0 = time.monotonic()
            got = list(fetch_with_recovery(
                srv.getsockname(), 9, 0, tracker, ctx=ctx, max_retries=1,
                backoff_s=0.01))
            assert got == _MATRIX_BLOCKS
            assert tracker.metrics["map_tasks_recomputed"] == 3
            # The stall was bounded by requestTimeout (0.3s x 2 attempts),
            # not by any 30s default.
            assert time.monotonic() - t0 < 5.0
        finally:
            stop.set()
            srv.close()

    def test_stalled_peer_without_lineage_names_peer(self):
        srv, stop = self._stall_server()
        ctx, _ = _recovery_env()
        peer = srv.getsockname()
        try:
            with pytest.raises(ShuffleFetchFailedError) as ei:
                list(RetryingBlockIterator(peer, 9, 0, ctx=ctx,
                                           max_retries=1, backoff_s=0.01))
            assert ei.value.peer == tuple(peer)
            assert "timed out" in str(ei.value).lower()
        finally:
            stop.set()
            srv.close()
