"""CSV device-parse differentials — the GpuBatchScanExec.scala:87 analog.

Contract: the device digit-DP parse must match the host pyarrow reader
bit-for-bit on its supported range, and anything outside that range must
fall back PER FILE (quotes, exponent notation, >15-digit doubles), never
mis-parse."""

import os

import numpy as np
import pytest

from harness import cpu_session, tpu_session

from spark_rapids_tpu import types as T
from spark_rapids_tpu.io import csv_device as CD
from spark_rapids_tpu.ops import aggregates as AGG
from spark_rapids_tpu.ops import predicates as P
from spark_rapids_tpu.ops.expression import col, lit


def _write_csv(tmp_path, data, name="t"):
    cpu = cpu_session()
    path = str(tmp_path / name)
    cpu.create_dataframe(data).write.csv(path)
    return path


def _plan_has_device_scan(s, df) -> bool:
    plan = s.plan(df._plan)
    found = []

    def walk(p):
        found.append(type(p).__name__)
        for c in getattr(p, "children", []):
            walk(c)
    walk(plan)
    return "TpuCsvScanExec" in found


def _read_both(tmp_path, data, sort_keys):
    path = _write_csv(tmp_path, data)
    cpu, tpu = cpu_session(), tpu_session()
    df = tpu.read.csv(path).where(P.IsNotNull(col(sort_keys[0][0])))
    assert _plan_has_device_scan(tpu, df)
    got = df.collect().sort_by(sort_keys)
    want = cpu.read.csv(path).where(
        P.IsNotNull(col(sort_keys[0][0]))).collect().sort_by(sort_keys)
    assert got.to_pydict() == want.to_pydict()


class TestDeviceParse:
    def test_int_double_string_bool_fuzz(self, tmp_path):
        rng = np.random.default_rng(5)
        n = 5000
        data = {
            "a": [None if rng.random() < 0.1 else int(v)
                  for v in rng.integers(-10**12, 10**12, n)],
            "b": [None if rng.random() < 0.1 else round(float(v), 6)
                  for v in rng.normal(scale=1000, size=n)],
            "s": [f"tag_{int(v)}" for v in rng.integers(0, 30, n)],
            "f": [bool(v) for v in rng.integers(0, 2, n)],
        }
        _read_both(tmp_path, data, [("a", "ascending"), ("b", "ascending")])

    def test_edge_numerals(self, tmp_path):
        data = {"x": [0, -1, 1, None, 999999999999999999,
                      -999999999999999999, 42],
                "y": [0.0, -0.5, 0.125, 123456.789012, None, 1.0, -7.0]}
        _read_both(tmp_path, data, [("x", "ascending")])

    def test_mortgage_numeric_columns(self, tmp_path):
        """The VERDICT's named target: the mortgage workload's numeric
        columns device-parse under a differential."""
        from spark_rapids_tpu.workloads import mortgage
        tables = mortgage.gen_tables(perf_rows=1 << 11, seed=3)
        cpu, tpu = cpu_session(), tpu_session()
        path = str(tmp_path / "perf")
        cpu.create_dataframe(tables["performance"]).write.csv(path)
        df = tpu.read.csv(path)
        dff = df.where(P.IsNotNull(col(df.schema.names[0])))
        assert _plan_has_device_scan(tpu, dff)
        keys = [(n, "ascending") for n in df.schema.names[:3]]
        got = dff.collect().sort_by(keys)
        want_df = cpu.read.csv(path)
        want = want_df.where(
            P.IsNotNull(col(want_df.schema.names[0]))).collect().sort_by(keys)
        assert got.to_pydict() == want.to_pydict()

    def test_crlf_and_no_header(self, tmp_path):
        path = str(tmp_path / "crlf.csv")
        with open(path, "wb") as f:
            f.write(b"1,2.5\r\n3,4.25\r\n5,\r\n")
        tpu, cpu = tpu_session(), cpu_session()
        opts = {"header": False}
        got = tpu.read.option("header", False).csv(path) \
            .where(P.IsNotNull(col("f0"))).collect()
        want = cpu.read.option("header", False).csv(path) \
            .where(P.IsNotNull(col("f0"))).collect()
        assert got.to_pydict() == want.to_pydict()


class TestFallbacks:
    def _decode_all(self, path, schema, options):
        return list(CD.decode_file(path, schema, options))

    def test_quoted_fields_fall_back(self, tmp_path):
        path = str(tmp_path / "q.csv")
        with open(path, "w") as f:
            f.write('s,v\n"hello, world",1\nplain,2\n')
        schema = T.Schema([T.StructField("s", T.STRING, True),
                           T.StructField("v", T.LONG, True)])
        with pytest.raises(CD.NotCsvDecodable):
            self._decode_all(path, schema, {"header": True})
        # ...and through the engine the query still answers correctly.
        tpu, cpu = tpu_session(), cpu_session()
        q = lambda s: s.read.csv(path).where(
            P.GreaterThan(col("v"), lit(0))).collect().sort_by(
                [("v", "ascending")])
        assert q(tpu).to_pydict() == q(cpu).to_pydict()

    def test_exponent_notation_falls_back(self, tmp_path):
        path = str(tmp_path / "e.csv")
        with open(path, "w") as f:
            f.write("x\n1e10\n2.5\n")
        schema = T.Schema([T.StructField("x", T.DOUBLE, True)])
        with pytest.raises(CD.NotCsvDecodable):
            self._decode_all(path, schema, {"header": True})

    def test_wide_mantissa_falls_back(self, tmp_path):
        path = str(tmp_path / "w.csv")
        with open(path, "w") as f:
            f.write("x\n0.12345678901234567890\n")
        schema = T.Schema([T.StructField("x", T.DOUBLE, True)])
        with pytest.raises(CD.NotCsvDecodable):
            self._decode_all(path, schema, {"header": True})

    def test_null_value_option_stays_host(self, tmp_path):
        assert not CD.device_decodable(
            T.Schema([T.StructField("x", T.LONG, True)]),
            {"nullValue": "NA"})

    def test_hive_partitioned_dir_stays_host(self, tmp_path):
        """Read-back of a partitionBy CSV write must restore the partition
        columns — the per-file device parse can't see them, so the plan
        keeps the host dataset reader."""
        cpu, tpu = cpu_session(), tpu_session()
        path = str(tmp_path / "hive")
        cpu.create_dataframe({"k": [0, 1, 0, 1], "v": [1, 2, 3, 4]}) \
            .write.partition_by("k").csv(path)
        df = tpu.read.csv(path).where(P.IsNotNull(col("v")))
        assert not _plan_has_device_scan(tpu, df)
        key = [("v", "ascending")]
        got = df.collect().sort_by(key)
        want = cpu.read.csv(path).where(
            P.IsNotNull(col("v"))).collect().sort_by(key)
        assert got.to_pydict() == want.to_pydict()

    def test_blank_crlf_line_skipped(self, tmp_path):
        path = str(tmp_path / "blank.csv")
        with open(path, "wb") as f:
            f.write(b"x\r\n1\r\n\r\n2\r\n")
        schema = T.Schema([T.StructField("x", T.LONG, True)])
        out = self._decode_all(path, schema, {"header": True})
        import numpy as np
        n = int(out[0].n_rows)
        assert n == 2
        assert list(np.asarray(out[0].columns[0].data)[:n]) == [1, 2]

    def test_quote_false_option(self, tmp_path):
        path = str(tmp_path / "nq.csv")
        with open(path, "w") as f:
            f.write("x\n1\n2\n")
        schema = T.Schema([T.StructField("x", T.LONG, True)])
        out = self._decode_all(path, schema, {"header": True,
                                              "quote": False})
        assert int(out[0].n_rows) == 2

    def test_ragged_rows_fall_back(self, tmp_path):
        path = str(tmp_path / "r.csv")
        with open(path, "w") as f:
            f.write("a,b\n1,2\n3\n")
        schema = T.Schema([T.StructField("a", T.LONG, True),
                           T.StructField("b", T.LONG, True)])
        with pytest.raises(CD.NotCsvDecodable):
            self._decode_all(path, schema, {"header": True})


def test_scan_parses_only_referenced_fields(tmp_path, monkeypatch):
    """The projection reaches the scan (plan/optimizer.py): the boundary
    pass still walks all 16 fields of a line, the device parses 4."""
    from harness import assert_scan_reads_only_referenced, wide_table
    path = str(tmp_path / "wide")
    os.makedirs(path)
    table = wide_table()
    rows = list(zip(*[c.to_pylist() for c in table.columns]))
    for i in range(3):   # unquoted: quoted fields go to the host reader
        with open(os.path.join(path, f"part-{i}.csv"), "w") as f:
            f.write(",".join(table.column_names) + "\n")
            f.writelines(",".join(map(str, r)) + "\n"
                         for r in rows[i * 1000:(i + 1) * 1000])
    files = CD.scan_files([path])
    assert len(files) == 3
    s = tpu_session()
    assert_scan_reads_only_referenced(s, s.read.csv(path), len(files),
                                      "TpuCsvScan", monkeypatch)
    assert s.last_query_profile().totals().get("fileHostFallback", 0) == 0
