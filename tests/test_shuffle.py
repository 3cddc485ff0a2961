"""Shuffle subsystem tests: partitioners, codecs, serializer protocol,
exchange, and transport state machines (GpuPartitioningSuite /
RapidsShuffleClientSuite / RapidsShuffleIteratorSuite analogs)."""

import numpy as np
import pyarrow as pa
import pytest

from spark_rapids_tpu import types as T
from spark_rapids_tpu.config import TpuConf
from spark_rapids_tpu.data.batch import HostBatch
from spark_rapids_tpu.ops import aggregates as AGG
from spark_rapids_tpu.ops.expression import col
from spark_rapids_tpu.plan.logical import SortOrder
from spark_rapids_tpu.shuffle import partitioners as PT
from spark_rapids_tpu.shuffle.codec import get_codec
from spark_rapids_tpu.shuffle.exchange import ShuffleBufferCatalog
from spark_rapids_tpu.shuffle.serializer import (ShuffleTableMeta,
                                                 deserialize_batch,
                                                 serialize_batch)
from spark_rapids_tpu.shuffle.transport import (BounceBufferPool,
                                                LocalTransport, ShuffleClient,
                                                ShuffleServer, Throttle,
                                                TransactionStatus, Transport)

from harness import assert_tpu_and_cpu_are_equal, tpu_session


def _hb(n=100, seed=0):
    rng = np.random.default_rng(seed)
    return HostBatch.from_pydict({
        "k": [None if rng.random() < 0.1 else int(x)
              for x in rng.integers(0, 50, n)],
        "v": rng.integers(-100, 100, n).astype(np.int64).tolist(),
        "s": [f"s{int(x)}" for x in rng.integers(0, 9, n)],
    })


class TestPartitioners:
    def test_hash_device_matches_host(self):
        hb = _hb()
        schema = hb.schema
        p = PT.HashPartitioner([col("k"), col("s")], 8, schema)
        host = p.host_ids(hb)
        dev = np.asarray(p.device_ids(hb.to_device()))[: hb.num_rows]
        assert (host == dev).all()

    def test_round_robin_balanced(self):
        hb = _hb(n=97)
        p = PT.RoundRobinPartitioner(4)
        ids = p.host_ids(hb)
        counts = np.bincount(ids, minlength=4)
        assert counts.max() - counts.min() <= 1

    def test_range_partitioner_device_matches_host(self):
        hb = _hb(n=200)
        schema = hb.schema
        orders = [SortOrder(col("v").bind(schema))]
        sample = [(v,) for v in hb.rb.column("v").to_pylist()]
        bounds = PT.sample_range_bounds(sample, 4, [True], [True], [T.LONG])
        p = PT.RangePartitioner([col("v")], bounds, 4, schema)
        host = p.host_ids(hb)
        dev = np.asarray(p.device_ids(hb.to_device()))[: hb.num_rows]
        assert (host == dev).all()
        # Ranges actually partition the ordered domain.
        vals = hb.rb.column("v").to_pylist()
        per_part = {}
        for v, pid in zip(vals, host):
            per_part.setdefault(pid, []).append(v)
        pids = sorted(per_part)
        for a, b in zip(pids, pids[1:]):
            assert max(per_part[a]) <= min(per_part[b])


class TestSerializer:
    @pytest.mark.parametrize("codec", ["none", "copy", "lz4", "zstd"])
    def test_round_trip(self, codec):
        rb = _hb().rb
        payload = serialize_batch(rb, get_codec(codec))
        meta, back = deserialize_batch(payload)
        assert back.equals(rb)
        assert meta.n_rows == rb.num_rows
        assert meta.field_names == ["k", "v", "s"]

    def test_compression_shrinks(self):
        rb = HostBatch.from_pydict(
            {"x": [7] * 10000}).rb
        raw = serialize_batch(rb, get_codec("none"))
        z = serialize_batch(rb, get_codec("zstd"))
        assert len(z) < len(raw) / 4

    def test_meta_decode_standalone(self):
        rb = _hb(5).rb
        payload = serialize_batch(rb, get_codec("zstd"))
        meta, off = ShuffleTableMeta.decode(payload)
        assert meta.codec == "zstd"
        assert off + meta.compressed_size == len(payload)


class TestCatalog:
    def test_register_fetch_unregister(self):
        cat = ShuffleBufferCatalog()
        cat.add_block(1, 0, 0, b"a" * 10)
        cat.add_block(1, 1, 0, b"b" * 10)
        cat.add_block(1, 0, 1, b"c" * 10)
        cat.add_block(2, 0, 0, b"d" * 10)
        assert cat.blocks_for_reduce(1, 0) == [b"a" * 10, b"b" * 10]
        cat.unregister_shuffle(1)
        assert cat.blocks_for_reduce(1, 0) == []
        assert cat.blocks_for_reduce(2, 0) == [b"d" * 10]
        cat.close()

    def test_overflow_to_disk(self, tmp_path):
        cat = ShuffleBufferCatalog(host_budget_bytes=15,
                                   spill_dir=str(tmp_path))
        cat.add_block(1, 0, 0, b"x" * 10)
        cat.add_block(1, 1, 0, b"y" * 10)  # over budget -> disk
        assert cat.metrics["spilled_blocks"] == 1
        assert cat.blocks_for_reduce(1, 0) == [b"x" * 10, b"y" * 10]
        cat.close()

    def test_close_racing_disk_append_stands_down(self, monkeypatch,
                                                  tmp_path):
        """A disk append whose off-lock write loses the race to close()
        must drop the block — not re-install it into the cleared catalog
        or lazily resurrect a fresh SpillFile (stray temp dir); mirrors
        BufferCatalog's straggler-publish guard."""
        import threading
        from spark_rapids_tpu.memory import spill as SP
        cat = ShuffleBufferCatalog(host_budget_bytes=0,
                                   spill_dir=str(tmp_path))
        gate_in, gate_out = threading.Event(), threading.Event()

        def blocking_append(self, payload):
            gate_in.set()
            assert gate_out.wait(10)
            return (0, len(payload))  # file is closed by now: fake range

        monkeypatch.setattr(SP.SpillFile, "append", blocking_append)
        t = threading.Thread(
            target=lambda: cat.add_block(1, 0, 0, b"x" * 10))
        t.start()
        assert gate_in.wait(10)  # mid-append, off-lock
        cat.close()
        gate_out.set()
        t.join(30)
        assert not t.is_alive()
        assert cat._spill_file is None       # never resurrected
        assert cat.blocks_for_reduce(1, 0) == []
        assert cat.metrics["blocks"] == 0
        # And a to-disk add AFTER close is dropped before the append.
        cat.add_block(1, 0, 1, b"y" * 10)
        assert cat._spill_file is None
        assert cat.blocks_for_reduce(1, 0) == []

    def test_closed_spill_file_append_drops_silently(self, monkeypatch,
                                                     tmp_path):
        """The REAL closed-SpillFile race (no faked append): the append
        that loses to close() hits the typed SpillFileClosedError —
        either from the closed-aware SpillFile refusing the open('ab')
        re-creation of its removed path, or from the _disk() backstop
        when the lazy file never existed — and add_block settles as the
        same silent drop every neighboring interleaving gets, leaving
        no stray .bin behind."""
        import contextlib
        import threading
        from spark_rapids_tpu.memory import spill as SP

        def racing_add(cat, key, gate_in, gate_out):
            errs = []

            def add():
                try:
                    cat.add_block(*key, b"y" * 10)
                except BaseException as exc:  # noqa: BLE001 - capture
                    errs.append(exc)

            t = threading.Thread(target=add)
            t.start()
            assert gate_in.wait(10)  # off-lock, past the closed pre-gate
            cat.close()
            gate_out.set()
            t.join(10)
            assert not t.is_alive()
            return errs

        # Case 1: the spill file exists on disk; the gated append runs
        # its REAL body only after close() removed the path.
        cat = ShuffleBufferCatalog(host_budget_bytes=0,
                                   spill_dir=str(tmp_path))
        cat.add_block(1, 0, 0, b"x" * 10)  # creates the real file
        assert list(tmp_path.glob("spill_*.bin"))
        gate_in, gate_out = threading.Event(), threading.Event()
        real_append = SP.SpillFile.append

        def gated_append(self, payload):
            gate_in.set()
            assert gate_out.wait(10)
            return real_append(self, payload)

        monkeypatch.setattr(SP.SpillFile, "append", gated_append)
        assert racing_add(cat, (1, 0, 1), gate_in, gate_out) == []
        monkeypatch.undo()
        assert not list(tmp_path.glob("spill_*.bin"))  # no 'ab' revival
        assert cat._disk_appends == 0

        # Case 2: close() lands BEFORE the lazy SpillFile ever exists —
        # the _disk() backstop raises the same typed error; same drop.
        cat2 = ShuffleBufferCatalog(host_budget_bytes=0,
                                    spill_dir=str(tmp_path))
        gate_in2, gate_out2 = threading.Event(), threading.Event()

        @contextlib.contextmanager
        def gated_lane():
            gate_in2.set()
            assert gate_out2.wait(10)
            yield

        cat2._io_lane = gated_lane
        assert racing_add(cat2, (2, 0, 0), gate_in2, gate_out2) == []
        assert cat2._spill_file is None
        assert not list(tmp_path.glob("spill_*.bin"))
        assert cat2._disk_appends == 0

    def test_post_close_host_add_drops_silently(self):
        """The HOST-tier path of add_block honors the same post-close
        silent-drop contract as the disk tier: no block, no byte
        accounting, no metrics resurrected into the cleared catalog."""
        cat = ShuffleBufferCatalog(host_budget_bytes=1 << 20)
        cat.close()
        cat.add_block(1, 0, 0, b"x" * 10)
        assert cat.blocks_for_reduce(1, 0) == []
        assert cat._host_bytes == 0
        assert cat.metrics["blocks"] == 0

    def test_claimed_compaction_racing_close_stands_down(self, tmp_path):
        """A compaction claimed pre-close but executed post-close must
        release the claim and stand down — not dereference the nulled
        spill file (mirrors BufferCatalog)."""
        cat = ShuffleBufferCatalog(host_budget_bytes=0,
                                   spill_dir=str(tmp_path))
        cat.add_block(1, 0, 0, b"x" * 32)
        with cat._lock:
            cat._compacting = True  # the claim, as if taken pre-close
        cat.close()
        cat._compact_now()
        assert not cat._compacting


class TestExchange:
    @pytest.mark.parametrize("call", [
        lambda df: df.repartition(4, "k"),
        lambda df: df.repartition(3),
        lambda df: df.repartition_by_range(4, "v"),
        # string keys through the hash exchange: the murmur3 row hash of
        # char-matrix rows places each group, empty string included
        lambda df: df.repartition(4, "s").group_by(col("s")).agg(
            AGG.AggregateExpression(AGG.Sum(col("v")), "sv")),
    ], ids=["hash", "round-robin", "range", "string-key-aggregate"])
    def test_repartition_differential(self, call):
        data = {"k": [i % 11 for i in range(300)],
                "v": list(range(300)),
                "s": [["apple", "pear", "fig", "kiwi", "dragonfruit",
                       ""][i % 6] for i in range(300)]}
        assert_tpu_and_cpu_are_equal(
            lambda s: call(s.create_dataframe(data)))

    def test_partition_count_and_grouping(self):
        s = tpu_session()
        df = s.create_dataframe(
            {"k": [i % 7 for i in range(200)], "v": list(range(200))})
        plan = s.plan(df.repartition(5, "k")._plan)
        assert "TpuShuffleExchange" in plan.tree_string()
        from spark_rapids_tpu.plan.physical import ExecContext
        ctx = ExecContext(s.conf, catalog=s.device_manager.catalog)
        parts = plan.children[0].execute(ctx) if not plan.columnar else None
        # Execute via the exchange directly: same key never splits across
        # partitions (co-partitioning invariant).
        exchange = plan.children[0] if not hasattr(plan, "partitioner_factory") \
            else plan
        while not hasattr(exchange, "partitioner_factory"):
            exchange = exchange.children[0]
        outs = exchange.execute(ctx)
        key_to_part = {}
        for pid, it in enumerate(outs):
            for db in it:
                for kv in db.to_arrow().column("k").to_pylist():
                    assert key_to_part.setdefault(kv, pid) == pid

    def test_codec_conf_applies(self):
        s = tpu_session(**{"spark.rapids.shuffle.compression.codec": "zstd"})
        df = s.create_dataframe({"k": [1, 2, 3] * 50, "v": list(range(150))})
        out = df.repartition(2, "k").collect()
        assert out.num_rows == 150

    def test_range_repartition_plus_sort_is_globally_ordered(self):
        # rangepartition + per-partition sort = total order across partition
        # ids (what Spark's global sort does).
        s = tpu_session()
        rng = np.random.default_rng(5)
        df = s.create_dataframe(
            {"v": [int(x) for x in rng.integers(0, 1000, 400)]})
        plan = s.plan(df.repartition_by_range(4, "v")._plan)
        from spark_rapids_tpu.plan.physical import ExecContext
        ctx = ExecContext(s.conf, catalog=s.device_manager.catalog)
        exchange = plan
        while not hasattr(exchange, "partitioner_factory"):
            exchange = exchange.children[0]
        outs = exchange.execute(ctx)
        prev_max = None
        for it in outs:
            vals = []
            for db in it:
                vals.extend(db.to_arrow().column("v").to_pylist())
            if not vals:
                continue
            if prev_max is not None:
                assert min(vals) >= prev_max
            prev_max = max(vals)


class _ScriptedTransport(Transport):
    """Mock transport with scripted failures (RapidsShuffleTestHelper's
    mocked Transaction behavior)."""

    def __init__(self, inner, fail_metadata=False, truncate_block=False):
        self.inner = inner
        self.fail_metadata = fail_metadata
        self.truncate_block = truncate_block

    def request_metadata(self, shuffle_id, reduce_id):
        if self.fail_metadata:
            raise IOError("peer unreachable")
        return self.inner.request_metadata(shuffle_id, reduce_id)

    def fetch_block_chunks(self, desc, chunk_size):
        chunks = list(self.inner.fetch_block_chunks(desc, chunk_size))
        if self.truncate_block:
            chunks = chunks[:-1]
        yield from chunks


def _payload(n=20, seed=0, codec="none"):
    return serialize_batch(_hb(n, seed).rb, get_codec(codec))


class TestTransport:
    def _setup(self, payloads, bounce_size=16, **script):
        cat = ShuffleBufferCatalog()
        for i, p in enumerate(payloads):
            cat.add_block(1, i, 0, p)
        server = ShuffleServer(cat)
        transport = _ScriptedTransport(LocalTransport(server), **script)
        client = ShuffleClient(transport, BounceBufferPool(bounce_size, 2),
                               Throttle(1 << 20))
        return client

    def test_fetch_success_chunked(self):
        payloads = [_payload(seed=1), _payload(seed=2)]
        client = self._setup(payloads, bounce_size=64)
        got, errs = [], []
        txn = client.fetch(1, 0, got.append, errs.append)
        assert txn.status == TransactionStatus.SUCCESS
        assert got == payloads
        assert not errs
        expected_chunks = sum(-(-len(p) // 64) for p in payloads)
        assert client.metrics["chunks"] == expected_chunks

    def test_metadata_failure_surfaces_error(self):
        client = self._setup([_payload()], fail_metadata=True)
        got, errs = [], []
        txn = client.fetch(1, 0, got.append, errs.append)
        assert txn.status == TransactionStatus.ERROR
        assert errs and "unreachable" in errs[0]
        assert not got

    def test_truncated_transfer_is_error_not_corruption(self):
        client = self._setup([_payload()], truncate_block=True)
        got, errs = [], []
        txn = client.fetch(1, 0, got.append, errs.append)
        assert txn.status == TransactionStatus.ERROR
        assert "short read" in txn.error_message
        assert not got

    def test_throttle_released_after_fetch(self):
        client = self._setup([_payload()])
        client.fetch(1, 0, lambda b: None, lambda e: None)
        assert client.throttle.inflight == 0

    def test_end_to_end_fetch_deserializes(self):
        rb = _hb(20).rb
        payload = serialize_batch(rb, get_codec("lz4"))
        client = self._setup([payload])
        got = []
        txn = client.fetch(1, 0, got.append, lambda e: None)
        assert txn.status == TransactionStatus.SUCCESS
        _, back = deserialize_batch(got[0])
        assert back.equals(rb)
