"""ORC device decode: stripe run tables expand on device and match both
the writer's data and the host-read oracle (GpuOrcScan.scala:65,211
parity; mirrors test_parquet_device.py's strategy)."""

import os

import numpy as np
import pyarrow as pa
import pyarrow.orc as orc
import pytest

from spark_rapids_tpu import types as T
from spark_rapids_tpu.io import orc_device as OD
from spark_rapids_tpu.session import TpuSession

try:
    import zstandard  # noqa: F401
    _HAS_ZSTANDARD = True
except ImportError:
    _HAS_ZSTANDARD = False


def _write(tmp_path, table, name="t.orc", **kw):
    p = os.path.join(str(tmp_path), name)
    orc.write_table(table, p, **kw)
    return p


def _table(n=20_000, seed=3):
    rng = np.random.default_rng(seed)
    return pa.table({
        "i64": rng.integers(-10**12, 10**12, n),
        "seq": np.arange(n, dtype=np.int64),
        "const": np.full(n, 7, dtype=np.int64),
        "f64": pa.array(rng.normal(size=n), mask=rng.random(n) < 0.07),
        "s": pa.array(np.array(["red", "green", "blue", "lime", "x"])[
            rng.integers(0, 5, n)]),
        "ni": pa.array(rng.integers(0, 50, n), mask=rng.random(n) < 0.15),
    })


def _check_stripes(path, table):
    tail = OD.read_tail(path)
    schema = T.schema_from_arrow(table.schema)
    assert OD.device_decodable(path, schema, tail)
    rows = 0
    for si in tail.stripes:
        got = OD.decode_stripe(path, tail, si, schema).to_arrow()
        want = table.slice(rows, si.n_rows).combine_chunks().to_batches()[0]
        rows += si.n_rows
        for name in table.column_names:
            g = got.column(got.schema.get_field_index(name)).to_pylist()
            w = want.column(want.schema.get_field_index(name)).to_pylist()
            assert len(g) == len(w)
            for a, b in zip(g, w):
                if isinstance(a, float) and isinstance(b, float):
                    assert abs(a - b) < 1e-12
                else:
                    assert a == b, (name, a, b)
    assert rows == table.num_rows


class TestOrcDeviceDecode:
    def test_uncompressed_single_stripe(self, tmp_path):
        t = _table(5000)
        _check_stripes(_write(tmp_path, t), t)

    @pytest.mark.parametrize("comp", [
        "zlib", "snappy",
        pytest.param("zstd", marks=pytest.mark.skipif(
            not _HAS_ZSTANDARD,
            reason="zstandard module not installed (ORC zstd stripes need "
                   "it: pyarrow's zstd codec requires the exact "
                   "decompressed size, which ORC chunk headers omit)"))])
    def test_compressed_multi_stripe(self, tmp_path, comp):
        t = _table(30_000, seed=9)
        p = _write(tmp_path, t, compression=comp, stripe_size=64 * 1024)
        tail = OD.read_tail(p)
        assert len(tail.stripes) > 1, "test needs multiple stripes"
        _check_stripes(p, t)

    def test_all_null_and_empty_strings(self, tmp_path):
        t = pa.table({
            "x": pa.array([None] * 64, type=pa.int64()),
            "s": pa.array((["", "a", None, "bb"] * 16)),
        })
        _check_stripes(_write(tmp_path, t), t)

    def test_session_scan_uses_device_decoder(self, tmp_path):
        from spark_rapids_tpu.ops import predicates as P
        from spark_rapids_tpu.ops.expression import col, lit
        t = _table(8000, seed=11)
        p = _write(tmp_path, t, compression="zlib")
        tpu = TpuSession({"spark.rapids.sql.enabled": True})

        def q(s):
            # the swap-in rides the host->device transition, so the scan
            # must sit under a device subtree (same contract as parquet)
            return s.read.orc(p).where(P.GreaterThanOrEqual(
                col("seq"), lit(0)))
        plan = tpu.plan(q(tpu)._plan)

        def find(pl):
            if type(pl).__name__ == "TpuOrcScanExec":
                return True
            return any(find(c) for c in pl.children)
        assert find(plan), "ORC scan must swap in the device decoder"
        got = q(tpu).collect().sort_by("seq")
        cpu = TpuSession({"spark.rapids.sql.enabled": False})
        want = q(cpu).collect().sort_by("seq")
        assert got.equals(want)

    def test_unsupported_type_falls_back_whole_scan(self, tmp_path):
        t = pa.table({"b": pa.array([True, False, None] * 10),
                      "v": pa.array(range(30), type=pa.int64())})
        p = _write(tmp_path, t)
        tail = OD.read_tail(p)
        assert not OD.device_decodable(
            p, T.schema_from_arrow(t.schema), tail)
        # the session still reads it (host path)
        tpu = TpuSession({"spark.rapids.sql.enabled": True})
        cpu = TpuSession({"spark.rapids.sql.enabled": False})
        assert tpu.read.orc(p).collect().sort_by("v").equals(
            cpu.read.orc(p).collect().sort_by("v"))

    def test_direct_v2_strings_with_duplicates(self, tmp_path):
        # dictionary_key_size_threshold=0 forces DIRECT_V2 string
        # encoding; repeated values must dedupe in the decoder's
        # dictionary build or the dict_sorted contract breaks (round-5
        # advisor high finding: GROUP BY returned duplicate groups)
        rng = np.random.default_rng(5)
        t = pa.table({
            "s": pa.array(np.array(["aa", "bb", "aa", "cc", "bb", "aa"])[
                rng.integers(0, 6, 4000)]),
            "v": rng.integers(0, 100, 4000),
        })
        p = _write(tmp_path, t, dictionary_key_size_threshold=0.0)
        _check_stripes(p, t)
        # end-to-end GROUP BY on the direct-encoded column
        from spark_rapids_tpu.ops import aggregates as A
        from spark_rapids_tpu.ops.expression import col

        def q(s):
            return (s.read.orc(p).group_by(col("s"))
                    .agg(A.AggregateExpression(A.Count(), "c"),
                         A.AggregateExpression(A.Sum(col("v")), "sv"))
                    .sort("s"))
        tpu = TpuSession({"spark.rapids.sql.enabled": True})
        cpu = TpuSession({"spark.rapids.sql.enabled": False})
        assert q(tpu).collect().equals(q(cpu).collect())

    def test_patched_base_outliers(self, tmp_path):
        # mostly-small values with huge outliers steer the writer toward
        # PATCHED_BASE; the patch list packs at closestFixedBits(pgw+pw)
        # (round-5 advisor medium finding)
        rng = np.random.default_rng(13)
        vals = rng.integers(0, 512, 50_000)
        out_idx = rng.choice(50_000, 600, replace=False)
        vals[out_idx] = rng.integers(2**40, 2**45, 600)
        t = pa.table({"v": vals, "seq": np.arange(50_000, dtype=np.int64)})
        before = OD.decode_stats["patched_base_runs"]
        _check_stripes(_write(tmp_path, t), t)
        assert OD.decode_stats["patched_base_runs"] > before, \
            "data shape failed to trigger PATCHED_BASE; test is vacuous"

    def test_orc_query_differential(self, tmp_path):
        from spark_rapids_tpu.ops import aggregates as A
        from spark_rapids_tpu.ops import predicates as P
        from spark_rapids_tpu.ops.expression import col, lit
        t = _table(20_000, seed=21)
        p = _write(tmp_path, t, compression="zlib", stripe_size=128 * 1024)

        def q(s):
            return (s.read.orc(p)
                    .where(P.GreaterThan(col("i64"), lit(0)))
                    .group_by(col("s"))
                    .agg(A.AggregateExpression(A.Count(), "c"),
                         A.AggregateExpression(A.Min(col("ni")), "mn"))
                    .sort("s"))
        tpu = TpuSession({"spark.rapids.sql.enabled": True})
        cpu = TpuSession({"spark.rapids.sql.enabled": False})
        assert q(tpu).collect().equals(q(cpu).collect())


def test_scan_decodes_only_referenced_columns(tmp_path, monkeypatch):
    """The projection reaches the scan (plan/optimizer.py)."""
    from harness import (assert_scan_reads_only_referenced, tpu_session,
                         wide_table)
    path = _write(tmp_path, wide_table(rows=30_000), "wide.orc",
                  stripe_size=64 << 10)
    stripes = len(OD.read_tail(path).stripes)
    assert stripes > 1
    s = tpu_session()
    assert_scan_reads_only_referenced(s, s.read.orc(path), stripes,
                                      "TpuOrcScan", monkeypatch)
    assert s.last_query_profile().totals().get("stripeHostFallback", 0) == 0
