"""Device parquet decode tests (GpuParquetScan.scala:365-388 split analog):
run tables + device expansion produce bit-identical columns vs pyarrow,
and the planner swaps the host scan for the device decoder end-to-end."""

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from spark_rapids_tpu import types as T
from spark_rapids_tpu.io import parquet_device as PD
from spark_rapids_tpu.ops.expression import col

from harness import assert_tpu_and_cpu_are_equal, cpu_session, tpu_session


def _table(n=4000, seed=0):
    rng = np.random.default_rng(seed)
    return pa.table({
        "i": pa.array([int(x) if x % 7 else None
                       for x in rng.integers(0, 1000, n)], pa.int64()),
        "i32": pa.array(rng.integers(-100, 100, n), pa.int32()),
        "f": pa.array([float(x) if x % 5 else None
                       for x in rng.integers(0, 100, n)], pa.float64()),
        "s": pa.array([f"cat{x % 29}" if x % 11 else None
                       for x in rng.integers(0, 10 ** 6, n)]),
    })


@pytest.mark.parametrize("compression", ["snappy", "zstd", "none"])
def test_row_group_decode_bit_exact(tmp_path, compression):
    tbl = _table()
    path = str(tmp_path / "t.parquet")
    pq.write_table(tbl, path, compression=compression)
    schema = T.schema_from_arrow(tbl.schema)
    batch = PD.decode_row_group(path, 0, schema)
    out = batch.to_arrow()
    for name in tbl.column_names:
        assert out.column(name).to_pylist() == \
            tbl.column(name).to_pylist(), name


def test_decoded_strings_are_sorted_dict(tmp_path):
    tbl = _table()
    path = str(tmp_path / "t.parquet")
    pq.write_table(tbl, path)
    schema = T.schema_from_arrow(tbl.schema)
    batch = PD.decode_row_group(path, 0, schema)
    c = batch.column("s")
    assert c.is_dict and c.dict_sorted


def test_multiple_row_groups(tmp_path):
    tbl = _table(n=3000)
    path = str(tmp_path / "t.parquet")
    pq.write_table(tbl, path, row_group_size=700)
    schema = T.schema_from_arrow(tbl.schema)
    got = []
    for rg in range(pq.ParquetFile(path).metadata.num_row_groups):
        got.extend(PD.decode_row_group(path, rg, schema)
                   .to_arrow().column("i").to_pylist())
    assert got == tbl.column("i").to_pylist()


def test_all_null_and_empty_columns(tmp_path):
    tbl = pa.table({
        "a": pa.array([None] * 50, pa.int64()),
        "b": pa.array([1.5] * 50, pa.float64()),
    })
    path = str(tmp_path / "t.parquet")
    pq.write_table(tbl, path)
    schema = T.schema_from_arrow(tbl.schema)
    out = PD.decode_row_group(path, 0, schema).to_arrow()
    assert out.column("a").to_pylist() == [None] * 50
    assert out.column("b").to_pylist() == [1.5] * 50


def test_multipage_nullable_dict_chunk(tmp_path):
    # Review repro: nullable dict chunk spanning many data pages — index
    # run tables must align per page's NON-NULL count, not num_values.
    rng = np.random.default_rng(5)
    n = 20000
    tbl = pa.table({"x": pa.array(
        [int(v) if v % 3 else None for v in rng.integers(0, 50, n)],
        pa.int64())})
    path = str(tmp_path / "t.parquet")
    pq.write_table(tbl, path, data_page_size=2000)
    schema = T.schema_from_arrow(tbl.schema)
    out = PD.decode_row_group(path, 0, schema).to_arrow()
    assert out.column("x").to_pylist() == tbl.column("x").to_pylist()


def test_multipage_growing_dictionary_width(tmp_path):
    # Review repro: sequential distinct values make the dictionary (and
    # its index bit width) grow across pages; runs carry per-run widths.
    n = 20000
    tbl = pa.table({"x": pa.array(np.arange(n), pa.int64())})
    path = str(tmp_path / "t.parquet")
    pq.write_table(tbl, path, data_page_size=1000,
                   dictionary_pagesize_limit=1 << 20)
    schema = T.schema_from_arrow(tbl.schema)
    out = PD.decode_row_group(path, 0, schema).to_arrow()
    assert out.column("x").to_pylist() == list(range(n))


def test_multipage_strings_with_nulls(tmp_path):
    rng = np.random.default_rng(6)
    n = 15000
    tbl = pa.table({"s": pa.array(
        [f"v{int(v) % 211}" if v % 5 else None
         for v in rng.integers(0, 10 ** 9, n)])})
    path = str(tmp_path / "t.parquet")
    pq.write_table(tbl, path, data_page_size=1500)
    schema = T.schema_from_arrow(tbl.schema)
    out = PD.decode_row_group(path, 0, schema).to_arrow()
    assert out.column("s").to_pylist() == tbl.column("s").to_pylist()


def test_planner_swaps_in_device_scan(tmp_path):
    tbl = _table(n=500)
    path = str(tmp_path / "t.parquet")
    pq.write_table(tbl, path)
    s = tpu_session()
    df = s.read.parquet(path).where(col("i32") > 0).select(col("i"), col("s"))
    plan = s.plan(df._plan)
    assert "TpuParquetScan" in plan.tree_string(), plan.tree_string()


def test_device_scan_differential(tmp_path):
    tbl = _table(n=2000, seed=3)
    path = str(tmp_path / "t.parquet")
    pq.write_table(tbl, path, row_group_size=512)

    from spark_rapids_tpu.ops import aggregates as A
    assert_tpu_and_cpu_are_equal(
        lambda s: s.read.parquet(path)
        .where(col("i32") > -50)
        .group_by(col("s"))
        .agg(A.AggregateExpression(A.Sum(col("i")), "si"),
             A.AggregateExpression(A.Count(), "c")))


def test_conf_gate_off_uses_host_scan(tmp_path):
    tbl = _table(n=100)
    path = str(tmp_path / "t.parquet")
    pq.write_table(tbl, path)
    s = tpu_session(**{
        "spark.rapids.sql.parquet.deviceDecode.enabled": False})
    plan = s.plan(s.read.parquet(path).select(col("i"))._plan)
    assert "TpuParquetScan" not in plan.tree_string()


def test_hive_partitioned_falls_back(tmp_path):
    s = cpu_session()
    df = s.create_dataframe(pa.RecordBatch.from_pydict(
        {"k": [1, 1, 2], "v": [10, 20, 30]}))
    out = str(tmp_path / "hive")
    df.write.partition_by("k").parquet(out)
    ts = tpu_session()
    plan = ts.plan(ts.read.parquet(out).select(col("v"))._plan)
    assert "TpuParquetScan" not in plan.tree_string()
    # still correct through the host path
    assert sorted(ts.read.parquet(out).select(col("v")).collect()
                  .column("v").to_pylist()) == [10, 20, 30]


@pytest.mark.parametrize("test_enabled", [False, True])
def test_plain_fallback_pages(tmp_path, test_enabled):
    # use_dictionary=False forces PLAIN data pages: fixed-width columns
    # decode on device via the plain path, and since ISSUE 34 byte-array
    # chunks do too (a flat string column): with or without
    # spark.rapids.sql.test.enabled no row group is read on the host.
    tbl = _table(n=300)
    path = str(tmp_path / "t.parquet")
    pq.write_table(tbl, path, use_dictionary=False)

    def query(s):
        return s.read.parquet(path).select(col("i"), col("f"), col("s"))
    conf = {"spark.rapids.sql.test.enabled": test_enabled}
    assert_tpu_and_cpu_are_equal(query, conf=conf)
    s = tpu_session(**conf)
    query(s).collect()
    totals = s.last_query_profile().totals()
    assert totals.get("hostFallbackRowGroups", 0) == 0
    assert totals["scanChunksByteArrayPlain"] == 1


class TestRebaseGuard:
    """RebaseHelper.scala:60 analog: legacy-calendar files with ancient
    datetimes must raise under EXCEPTION mode, read raw under CORRECTED,
    and reject LEGACY — never silently mis-read."""

    def _legacy_file(self, tmp_path, dates):
        import pyarrow as pa
        import pyarrow.parquet as pq
        t = pa.table({"d": pa.array(dates, pa.date32()),
                      "v": pa.array(list(range(len(dates))), pa.int64())})
        t = t.replace_schema_metadata(
            {b"org.apache.spark.legacyDateTime": b""})
        path = str(tmp_path / "legacy.parquet")
        pq.write_table(t, path)
        return path

    def _scan(self, session, path):
        from spark_rapids_tpu.ops import predicates as P
        from spark_rapids_tpu.ops.expression import col
        return session.read.parquet(path).where(P.IsNotNull(col("v")))

    def test_ancient_dates_raise_by_default(self, tmp_path):
        import datetime
        from harness import tpu_session
        from spark_rapids_tpu.io.parquet_device import SparkUpgradeError
        path = self._legacy_file(
            tmp_path, [datetime.date(1500, 1, 1), datetime.date(2020, 1, 1)])
        s = tpu_session()
        with pytest.raises(SparkUpgradeError, match="1582"):
            self._scan(s, path).collect()

    def test_corrected_mode_reads_raw(self, tmp_path):
        import datetime
        from harness import cpu_session, tpu_session
        path = self._legacy_file(
            tmp_path, [datetime.date(1500, 1, 1), datetime.date(2020, 1, 1)])
        s = tpu_session(**{
            "spark.sql.legacy.parquet.datetimeRebaseModeInRead": "CORRECTED"})
        got = self._scan(s, path).collect().sort_by([("v", "ascending")])
        want = self._scan(cpu_session(), path).collect().sort_by(
            [("v", "ascending")])
        assert got.to_pydict() == want.to_pydict()

    def test_modern_legacy_file_passes(self, tmp_path):
        import datetime
        from harness import tpu_session
        path = self._legacy_file(
            tmp_path, [datetime.date(1990, 5, 4), datetime.date(2020, 1, 1)])
        s = tpu_session()
        out = self._scan(s, path).collect()
        assert out.num_rows == 2

    def test_unmarked_file_never_raises(self, tmp_path):
        import datetime
        import pyarrow as pa
        import pyarrow.parquet as pq
        from harness import tpu_session
        t = pa.table({"d": pa.array([datetime.date(1500, 1, 1)],
                                    pa.date32()),
                      "v": pa.array([1], pa.int64())})
        path = str(tmp_path / "modern.parquet")
        pq.write_table(t, path)
        out = self._scan(tpu_session(), path).collect()
        assert out.num_rows == 1

    def test_legacy_mode_rejected(self, tmp_path):
        import datetime
        from harness import tpu_session
        from spark_rapids_tpu.io.parquet_device import SparkUpgradeError
        path = self._legacy_file(tmp_path, [datetime.date(2020, 1, 1)])
        s = tpu_session(**{
            "spark.sql.legacy.parquet.datetimeRebaseModeInRead": "LEGACY"})
        with pytest.raises(SparkUpgradeError, match="LEGACY"):
            self._scan(s, path).collect()


# -- the projection reaches the scan (plan/optimizer.py) --------------------

def test_scan_decodes_only_referenced_columns(tmp_path, monkeypatch):
    from harness import assert_scan_reads_only_referenced, wide_table
    path = str(tmp_path / "wide.parquet")
    pq.write_table(wide_table(), path, row_group_size=1000)
    s = tpu_session()
    assert_scan_reads_only_referenced(s, s.read.parquet(path), 3,
                                      "TpuParquetScan", monkeypatch)


def test_unreferenced_plain_string_column_stays_on_device(tmp_path):
    """A PLAIN byte-array column (as Spark writes a near-unique comment),
    unreferenced, is not the scan's business; referenced, it decodes on
    the device too (ISSUE 34): test.enabled would raise if a row group
    went to the host."""
    from harness import wide_query, wide_table
    tbl = wide_table()
    tbl = tbl.append_column("comment", pa.array(
        [f"text {i}" for i in range(tbl.num_rows)]))
    path = str(tmp_path / "plain.parquet")
    pq.write_table(tbl, path, row_group_size=1000,
                   use_dictionary=[n for n in tbl.column_names
                                   if n != "comment"])
    s = tpu_session()
    df = s.read.parquet(path)
    got = wide_query(df).collect()
    totals = s.last_query_profile().totals()
    assert totals["deviceDecodedRowGroups"] == 3
    assert totals.get("hostFallbackRowGroups", 0) == 0
    want = wide_query(cpu_session().read.parquet(path)).collect()
    assert got.equals(want)
    # referenced, the same column is a flat string column on the device
    got = df.where(col("c01") >= 250).select(col("comment")).collect()
    totals = s.last_query_profile().totals()
    assert totals.get("hostFallbackRowGroups", 0) == 0
    assert totals["scanChunksByteArrayPlain"] == 3
    want = cpu_session().read.parquet(path).where(col("c01") >= 250) \
        .select(col("comment")).collect()
    assert sorted(got.column("comment").to_pylist()) \
        == sorted(want.column("comment").to_pylist())


def test_two_projections_of_one_file_hash_apart(tmp_path):
    """The served path's result cache and breaker key on the plan hash:
    the scan's schema is in the signature, so the projection is."""
    from harness import wide_table
    from spark_rapids_tpu.metrics.profile import plan_profile_hash
    from spark_rapids_tpu.utils.kernel_cache import plan_signature
    path = str(tmp_path / "wide.parquet")
    pq.write_table(wide_table(), path, row_group_size=1000)
    s = tpu_session()
    df = s.read.parquet(path)

    def hashes(name):
        """(the plan's hash, its scan's) for a sum over one column."""
        from spark_rapids_tpu.ops import aggregates as A
        plan = s.plan(df.group_by().agg(
            A.AggregateExpression(A.Sum(col(name)), "total"))._plan)
        scan = plan
        while scan.children:
            scan = scan.children[0]
        assert scan.schema.names == [name], plan.tree_string()
        return tuple(plan_profile_hash(plan_signature(p))
                     for p in (plan, scan))
    assert hashes("c01") == hashes("c01")
    # both bigint, both columns=1/16: only the name under the scan differs
    plan_a, scan_a = hashes("c01")
    plan_b, scan_b = hashes("c05")
    assert plan_a != plan_b and scan_a != scan_b


# -- chunks that fall back from their dictionary to PLAIN pages -------------

_FALLBACK_ROWS = 6000      # a row group
_FALLBACK_GROUPS = 3


def _fallback_column(dtype, nulls, every_group, seed=0):
    """Three row groups of a fixed-width column. Distinct values overflow
    a 4 KiB dictionary page in the first row group (``every_group``: in
    all three); the others hold 50 values and stay on their dictionary."""
    rng = np.random.default_rng(seed)
    n = _FALLBACK_ROWS * _FALLBACK_GROUPS
    many = rng.permutation(n * 4)[:n]
    few = rng.integers(0, 50, n)
    values = many if every_group else np.where(
        np.arange(n) < _FALLBACK_ROWS, many, few)
    if dtype == "float64":
        arr = pa.array(values / 100.0, pa.float64())
    elif dtype == "date32":
        arr = pa.array(values.astype(np.int32), pa.date32())
    else:
        arr = pa.array(values, getattr(pa, dtype)())
    if nulls:
        arr = pa.array(arr.to_pylist(), arr.type,
                       mask=rng.integers(0, 5, n) == 0)
    return arr


def _scan_all(path, keep):
    """(answer, profile totals) of every row through the session's device
    scan (a filter that keeps all: a bare scan stays on the host), under
    ``test.enabled``: a row group the device refused would raise."""
    s = tpu_session()
    got = s.read.parquet(path).where(keep).collect()
    return got, s.last_query_profile().totals()


def _assert_decoded_as_pyarrow_reads(path, got, totals, fell_back):
    want = pq.read_table(path)
    for name in want.column_names:
        assert got.column(name).combine_chunks().equals(
            want.column(name).combine_chunks()), name
    assert totals.get("hostFallbackRowGroups", 0) == 0
    assert totals["scanChunksDictionaryThenPlain"] == fell_back
    assert sum(totals.get(k, 0) for k in (
        "scanChunksPlain", "scanChunksDictionary",
        "scanChunksDictionaryThenPlain")) == totals["scanColumnChunksDecoded"]


@pytest.mark.parametrize("every_group", [False, True],
                         ids=["first_group", "every_group"])
@pytest.mark.parametrize("nulls", [False, True], ids=["no_nulls", "nulls"])
@pytest.mark.parametrize("dtype", ["int32", "int64", "float64", "date32"])
def test_dictionary_then_plain_chunk_decodes_on_device(tmp_path, dtype,
                                                       nulls, every_group):
    """What parquet-mr and parquet-cpp write once a chunk's dictionary
    page passes its limit: dictionary-encoded pages, then PLAIN pages.
    Bit for bit what pyarrow's own reader gives, every row group on the
    device, each chunk counted by what its pages hold."""
    tbl = pa.table({"v": _fallback_column(dtype, nulls, every_group),
                    "few": pa.array(np.arange(
                        _FALLBACK_ROWS * _FALLBACK_GROUPS) % 7, pa.int64())})
    path = str(tmp_path / "fallback.parquet")
    pq.write_table(tbl, path, row_group_size=_FALLBACK_ROWS,
                   dictionary_pagesize_limit=4096, data_page_size=2048,
                   write_batch_size=256)
    # the file is what the case says: the dictionary, then PLAIN pages
    md = pq.ParquetFile(path).metadata
    assert md.num_row_groups == _FALLBACK_GROUPS
    with open(path, "rb") as f:
        plans = [PD.plan_column_chunk(
            f, md.row_group(rg).column(0),
            T.schema_from_arrow(tbl.schema).fields[0], 1)
            for rg in range(md.num_row_groups)]
    mixed = [p.idx_runs is not None and p.plain_values is not None
             for p in plans]
    assert mixed == [True, every_group, every_group]
    assert all(0 < p.dict_count < p.n_rows for p, m in zip(plans, mixed)
               if m)
    got, totals = _scan_all(path, col("few") >= 0)
    _assert_decoded_as_pyarrow_reads(path, got, totals, sum(mixed))
    assert totals["deviceDecodedRowGroups"] == _FALLBACK_GROUPS


def test_default_written_file_crosses_the_real_dictionary_limit(tmp_path):
    """``pq.write_table(t, path)`` with no option: 300,000 distinct doubles
    pass the writer's own 1 MiB dictionary page (131,072 values)."""
    rng = np.random.default_rng(3)
    n = 300_000
    tbl = pa.table({
        "price": pa.array(rng.permutation(n * 8)[:n] / 100.0, pa.float64()),
        "qty": pa.array(rng.integers(1, 51, n).astype(np.float64)),
        "day": pa.array(rng.integers(8035, 10591, n).astype(np.int32),
                        pa.date32())})
    path = str(tmp_path / "default.parquet")
    pq.write_table(tbl, path)
    got, totals = _scan_all(path, col("qty") > 0)
    _assert_decoded_as_pyarrow_reads(path, got, totals, 1)
    assert totals["scanChunksDictionary"] == 2
    from spark_rapids_tpu.utils import kernel_cache
    assert "parquet_decode_double_dictplain_nn" in {
        fn.__name__ for fn in kernel_cache._CACHE.values()}


def test_dictionary_page_after_plain_pages_is_refused(tmp_path,
                                                      monkeypatch):
    """The writers' fallback is one-way; a chunk in any other order is
    outside the decoder (a slot's source is told by its position)."""
    tbl = pa.table({"v": _fallback_column("int64", False, True)})
    path = str(tmp_path / "fallback.parquet")
    pq.write_table(tbl, path, dictionary_pagesize_limit=4096,
                   data_page_size=2048, write_batch_size=256)
    real = PD._parse_page_header
    seen = []

    def reversed_encodings(buf, pos):
        ph = real(buf, pos)
        if ph.page_type == 0:       # PLAIN first, then the dictionary's
            seen.append(ph.encoding)
            ph.encoding = PD.PLAIN if len(seen) == 1 else PD.RLE_DICTIONARY
        return ph
    monkeypatch.setattr(PD, "_parse_page_header", reversed_encodings)
    with pytest.raises(NotImplementedError,
                       match="dictionary pages after PLAIN"):
        PD.decode_row_group(path, 0, T.schema_from_arrow(tbl.schema))


def _spy_on_programs(monkeypatch, with_hlo=False):
    """Every decode program ``decode_chunk`` calls from here on: (name,
    cache key, operands, [first 16 hex of the sha256 of its lowered
    text]), in call order."""
    import hashlib
    from spark_rapids_tpu.utils.kernel_cache import program_name
    asked = []
    real = PD.cached_kernel

    def spy(kind, key, builder, static_argnums=None, suffix=""):
        fn = real(kind, key, builder, static_argnums, suffix)

        def call(*operands):
            entry = (program_name(kind, suffix), key, len(operands))
            if with_hlo:
                text = fn.lower(*operands).as_text()
                entry += (hashlib.sha256(text.encode()).hexdigest()[:16],)
            asked.append(entry)
            return fn(*operands)
        return call
    monkeypatch.setattr(PD, "cached_kernel", spy)
    return asked


def _four_columns(n, holes=None):
    rng = np.random.default_rng(1)
    return pa.table({
        "x": pa.array(rng.integers(0, 10 ** 9, n) / 100.0, pa.float64(),
                      mask=holes),
        "d": pa.array(rng.integers(0, 2500, n).astype(np.int32),
                      pa.date32(), mask=holes),
        "k": pa.array(rng.integers(0, 10 ** 9, n), pa.int64(), mask=holes),
        "s": pa.array([f"s{i % 13}" for i in range(n)], pa.string(),
                      mask=holes)})


def _decode_three_ways(tbl, path):
    """The table written PLAIN (strings on their dictionary), by the
    writer's defaults, and its first column with a 4 KiB dictionary page
    that overflows: each read back through the device decoder."""
    schema = T.schema_from_arrow(tbl.schema)
    pq.write_table(tbl, path, use_dictionary=["s"])
    PD.decode_row_group(path, 0, schema)
    pq.write_table(tbl, path)
    PD.decode_row_group(path, 0, schema)
    pq.write_table(tbl.select(["x"]), path, dictionary_pagesize_limit=4096)
    PD.decode_row_group(path, 0, T.Schema(schema.fields[:1]))


def test_pure_chunks_keep_their_programs(tmp_path, monkeypatch):
    """A chunk WITH nulls asks ``cached_kernel`` for the key, the name and
    the operands it asked for before chunks without nulls got programs of
    their own, and lowers to the same text (the digests are of the commit
    before that change, from this table, on this backend; a JAX upgrade
    that moves them moves all of them: re-pin from a checkout that still
    passes): a file with nulls compiles and runs what it ran."""
    n = 5000
    holes = np.zeros(n, bool)
    holes[n - 1] = True         # one null a column, in its last page
    asked = _spy_on_programs(monkeypatch, with_hlo=True)
    _decode_three_ways(_four_columns(n, holes), str(tmp_path / "t.parquet"))
    assert asked == [
        ("parquet_decode_double_bw0_plain",
         ("double", 8192, 0, False, False, True, 128), 6,
         "f05d49efbe52a95e"),
        ("parquet_decode_date_bw0_plain",
         ("date", 8192, 0, False, False, True, 128), 6,
         "c2d7779a6efc2662"),
        ("parquet_decode_bigint_bw0_plain",
         ("bigint", 8192, 0, False, False, True, 128), 6,
         "2964ad0b865f31d4"),
        ("parquet_decode_string_bw4_dictstr",
         ("string", 8192, 4, True, True, False, 128), 6,
         "35219163f6826281"),
        ("parquet_decode_double_bw13_dict",
         ("double", 8192, 13, True, False, False, 128), 6,
         "da0c047f25bc16ca"),
        ("parquet_decode_date_bw12_dict",
         ("date", 8192, 12, True, False, False, 128), 6,
         "22bd95769912ce1d"),
        ("parquet_decode_bigint_bw13_dict",
         ("bigint", 8192, 13, True, False, False, 128), 6,
         "bad169d070ea0787"),
        ("parquet_decode_string_bw4_dictstr",
         ("string", 8192, 4, True, True, False, 128), 6,
         "35219163f6826281"),
        ("parquet_decode_double_bw10_dictplain",
         ("double", 8192, 10, True, False, True, 128), 7,
         "cb1c44bfdc36d15d"),
    ]


def test_chunks_without_nulls_have_programs_of_their_own(tmp_path,
                                                         monkeypatch):
    """The same table without a null: names of their own, keyed by what
    the kernel reads (no index bit width), no definition-level table
    among the operands; a PLAIN chunk launches the validity alone."""
    asked = _spy_on_programs(monkeypatch)
    _decode_three_ways(_four_columns(5000), str(tmp_path / "t.parquet"))
    assert asked == [
        ("parquet_decode_plain_nn", ("plain_nn", 8192), 1),
        ("parquet_decode_plain_nn", ("plain_nn", 8192), 1),
        ("parquet_decode_plain_nn", ("plain_nn", 8192), 1),
        ("parquet_decode_string_dictstr_nn",
         ("string", 8192, "dictstr_nn", 128), 4),
        ("parquet_decode_double_dict_nn",
         ("double", 8192, "dict_nn", 128), 4),
        ("parquet_decode_date_dict_nn", ("date", 8192, "dict_nn", 128), 4),
        ("parquet_decode_bigint_dict_nn",
         ("bigint", 8192, "dict_nn", 128), 4),
        ("parquet_decode_string_dictstr_nn",
         ("string", 8192, "dictstr_nn", 128), 4),
        ("parquet_decode_double_dictplain_nn",
         ("double", 8192, "dictplain_nn", 128), 6),
    ]


def test_index_bit_widths_share_one_program(tmp_path, monkeypatch):
    """l_quantity (6 bits), l_discount and l_tax (4 bits) of a
    default-written lineitem: the width is per run, in the run table, and
    in no key — one ``double`` dictionary program for all three."""
    n = 3000
    rng = np.random.default_rng(2)
    tbl = pa.table({
        "quantity": pa.array(rng.integers(1, 51, n).astype(np.float64)),
        "discount": pa.array(rng.integers(0, 11, n) / 100.0),
        "tax": pa.array(rng.integers(0, 9, n) / 100.0)})
    path = str(tmp_path / "t.parquet")
    pq.write_table(tbl, path)
    schema = T.schema_from_arrow(tbl.schema)
    md = pq.ParquetFile(path).metadata.row_group(0)
    with open(path, "rb") as f:
        widths = [PD.plan_column_chunk(f, md.column(i), schema.fields[i],
                                       1).idx_bit_width for i in range(3)]
    assert widths == [6, 4, 4]
    asked = _spy_on_programs(monkeypatch)
    out = PD.decode_row_group(path, 0, schema).to_arrow()
    assert pa.Table.from_batches([out]).equals(tbl)
    assert asked == [("parquet_decode_double_dict_nn",
                      ("double", 4096, "dict_nn", 128), 4)] * 3
    from spark_rapids_tpu.utils import kernel_cache
    assert sum(key == ("parquet_decode", asked[0][1])
               for key in kernel_cache._CACHE) == 1


# -- kind x type x definition levels ----------------------------------------

_NN_ROWS = 6000
_ARROW = {"int32": ("int", pa.int32()), "int64": ("bigint", pa.int64()),
          "float32": ("float", pa.float32()),
          "float64": ("double", pa.float64()),
          "date32": ("date", pa.date32()), "string": ("string", pa.string())}
_LEVELS = ("required", "optional_no_null", "one_null_in_last_page",
           "all_null")
_KIND_CASES = [
    (kind, dtype, levels)
    for kind, dtypes in (
        ("plain", ("int32", "int64", "float32", "float64", "date32")),
        ("dict", ("int32", "int64", "float32", "float64", "date32")),
        ("dictstr", ("string",)),
        ("dictplain", ("int32", "int64", "float32", "float64", "date32")))
    for dtype in dtypes for levels in _LEVELS
    # a chunk of nulls alone holds no value to fall back over
    if not (kind == "dictplain" and levels == "all_null")]


def _kind_column(kind, dtype, levels):
    """6,000 rows of one column whose chunk the writer options of
    ``_write_kind`` turn into ``kind``: 50 distinct values stay on their
    dictionary, 6,000 distinct ones overflow a 4 KiB dictionary page."""
    rng = np.random.default_rng(7)
    n = _NN_ROWS
    values = rng.permutation(n * 4)[:n] if kind == "dictplain" \
        else rng.integers(0, 50, n) if kind in ("dict", "dictstr") \
        else rng.integers(0, 10 ** 6, n)
    arrow_type = _ARROW[dtype][1]
    if dtype == "string":
        values = [f"word{v}" for v in values]
    elif dtype in ("float32", "float64"):
        values = values / 4.0
    elif dtype == "date32":
        values = values.astype(np.int32)
    mask = None
    if levels == "one_null_in_last_page":
        mask = np.zeros(n, bool)
        mask[n - 1] = True
    elif levels == "all_null":
        mask = np.ones(n, bool)
    arr = pa.array(values, arrow_type, mask=mask)
    field = pa.field("v", arrow_type, nullable=levels != "required")
    return pa.Table.from_arrays([arr], schema=pa.schema([field]))


def _write_kind(tbl, path, kind):
    pq.write_table(tbl, path, use_dictionary=kind != "plain",
                   dictionary_pagesize_limit=4096 if kind == "dictplain"
                   else 1 << 20,
                   data_page_size=2048, write_batch_size=256)


@pytest.mark.parametrize("kind,dtype,levels", _KIND_CASES)
def test_chunk_decodes_by_what_its_pages_hold(tmp_path, monkeypatch, kind,
                                              dtype, levels):
    """Each kind of chunk, over each fixed-width type, under each shape of
    definition levels: bit for bit what pyarrow reads; the nullable
    program exactly when a page holds a null (one null in the last of
    several pages is enough), else the program without the null
    machinery; ``scanChunksNoNulls`` counts exactly the latter."""
    tbl = _kind_column(kind, dtype, levels)
    path = str(tmp_path / "t.parquet")
    _write_kind(tbl, path, kind)
    schema = T.schema_from_arrow(tbl.schema)
    pf = pq.ParquetFile(path)
    max_def = pf.schema.column(0).max_definition_level
    assert max_def == (0 if levels == "required" else 1)
    pages = []
    real = PD._parse_page_header

    def counting(buf, pos):
        ph = real(buf, pos)
        pages.append(ph.page_type)
        return ph
    monkeypatch.setattr(PD, "_parse_page_header", counting)
    with open(path, "rb") as f:
        plan = PD.plan_column_chunk(f, pf.metadata.row_group(0).column(0),
                                    schema.fields[0], max_def)
    monkeypatch.setattr(PD, "_parse_page_header", real)
    # (a chunk of nulls alone holds no bytes to split: one page)
    assert pages.count(0) > 1 or levels == "all_null", pages
    has_nulls = levels in ("one_null_in_last_page", "all_null")
    assert plan.has_nulls == has_nulls
    # the file is what the case says
    holds = ("dictstr" if plan.dict_rank is not None
             else "dictplain" if plan.idx_runs is not None
             and plan.plain_values is not None
             else "dict" if plan.idx_runs is not None else "plain")
    assert holds == kind

    asked = _spy_on_programs(monkeypatch)
    counters = {}
    out = PD.decode_row_group(path, 0, schema, counters=counters).to_arrow()
    want = pq.read_table(path).column("v")
    assert out.column("v").to_pylist() == want.to_pylist()
    assert out.column("v").null_count == want.null_count
    name = _ARROW[dtype][0]
    (program, key, operands), = asked
    if has_nulls:
        assert program == \
            f"parquet_decode_{name}_bw{plan.idx_bit_width}_{kind}"
        assert len(key) == 7 and operands == (7 if kind == "dictplain"
                                              else 6)
    elif kind == "plain":
        assert (program, key, operands) == (
            "parquet_decode_plain_nn", ("plain_nn", 8192), 1)
    else:
        assert program == f"parquet_decode_{name}_{kind}_nn"
        assert key[:3] == (name, 8192, f"{kind}_nn") and len(key) == 4
        assert operands == (6 if kind == "dictplain" else 4)
    assert counters["scanChunksNoNulls"] == int(not has_nulls)
    assert counters["scanColumnChunksDecoded"] == 1
    assert counters[{"plain": "scanChunksPlain",
                     "dictplain": "scanChunksDictionaryThenPlain"}.get(
                         kind, "scanChunksDictionary")] == 1


def test_dictionary_typed_arrow_field_reads_as_its_values(tmp_path):
    """A table written from dictionary-typed columns with the writer's
    defaults stores its Arrow schema: ``dictionary<values=V>`` opens as
    ``V``, on the device scan and on the host scan."""
    n = 2000
    words = pa.array(["AIR", "MAIL", "RAIL", "SHIP", "TRUCK"])
    tbl = pa.table({
        "mode": pa.DictionaryArray.from_arrays(
            pa.array(np.arange(n) % 5, pa.int32()), words),
        "bucket": pa.DictionaryArray.from_arrays(
            pa.array(np.arange(n) % 3, pa.int8()),
            pa.array([10, 20, 30], pa.int64())),
        "v": pa.array(np.arange(n), pa.int64())})
    path = str(tmp_path / "dict_typed.parquet")
    pq.write_table(tbl, path)
    assert pa.types.is_dictionary(pq.read_schema(path).field("mode").type)
    assert T.from_arrow_type(tbl.schema.field("mode").type) == T.STRING
    assert T.from_arrow_type(tbl.schema.field("bucket").type) == T.LONG
    want = tbl.cast(pa.schema([("mode", pa.string()), ("bucket", pa.int64()),
                               ("v", pa.int64())]))
    s = tpu_session()
    df = s.read.parquet(path)
    assert [f.data_type for f in df.schema] == [T.STRING, T.LONG, T.LONG]
    got = df.where(col("v") >= 0).collect()
    assert s.last_query_profile().totals()["deviceDecodedRowGroups"] == 1
    host = cpu_session().read.parquet(path).collect()
    for name in want.column_names:
        assert got.column(name).to_pylist() == \
            want.column(name).to_pylist(), name
        assert host.column(name).to_pylist() == \
            want.column(name).to_pylist(), name


# -- PLAIN byte arrays: a flat string column (ISSUE 34) -----------------------

_TEXT_WIDTH = 79      # o_comment's varchar(79)
_TEXT_KINDS = {
    # writer options that make a string chunk all PLAIN, dictionary pages
    # then PLAIN (the default writers' fallback, at a 4 KiB limit), or all
    # dictionary; ``distinct``: how many texts the column draws from
    "plain": (dict(use_dictionary=False), None),
    "dictplain": (dict(dictionary_pagesize_limit=4096), None),
    "dict": (dict(), 40),
}
_TEXT_CASES = [(kind, nulls, pages)
               for kind in _TEXT_KINDS for nulls in ("no_nulls", "nulls",
                                                     "all_null")
               for pages in ("one_page", "many_pages")
               if not (kind == "dictplain" and nulls == "all_null")]


def _texts(n, nulls, distinct=None, seed=0):
    """n strings of 0.._TEXT_WIDTH bytes: empty ones, ones of the full
    width, ASCII and multi-byte UTF-8, near-unique unless ``distinct``."""
    rng = np.random.default_rng(seed)
    alphabet = list("abcdefghij klmnop,.") + ["é", "ß", "€", "日"]
    pool = []
    for i in range(distinct or n):
        chars, size = [], 0
        want = (0, _TEXT_WIDTH)[i % 2] if i % 17 < 2 \
            else int(rng.integers(0, _TEXT_WIDTH + 1))
        while True:
            c = alphabet[int(rng.integers(0, len(alphabet)))]
            if size + len(c.encode()) > want:
                break
            chars.append(c)
            size += len(c.encode())
        pool.append("".join(chars) + "x" * (want - size))
    vals = [pool[i % len(pool)] for i in rng.permutation(n)]
    if nulls == "nulls":
        vals = [None if rng.integers(0, 4) == 0 else v for v in vals]
    elif nulls == "all_null":
        vals = [None] * n
    return pa.array(vals, pa.string())


def _decode_text_groups(path, counters=None):
    """[(device column, rows, what pyarrow's own reader gives)] a row
    group of the file's one column ``s``."""
    schema = T.Schema([T.StructField("s", T.STRING, True)])
    pf = pq.ParquetFile(path)
    out = []
    for rg in range(pf.metadata.num_row_groups):
        batch = PD.decode_row_group(path, rg, schema, pf=pf,
                                    counters=counters)
        want = pf.read_row_group(rg).column("s").combine_chunks()
        out.append((batch.columns[0], int(batch.n_rows), want))
    return out


@pytest.mark.parametrize("kind,nulls,pages", _TEXT_CASES)
def test_byte_array_chunk_decodes_as_pyarrow_reads(tmp_path, kind, nulls,
                                                   pages):
    """Payload, offsets and validity of a string chunk, byte for byte,
    whatever its v1 pages hold; 2,500 rows in row groups of 1,000, so the
    last one is short."""
    options, distinct = _TEXT_KINDS[kind]
    arr = _texts(2500, nulls, distinct)
    path = str(tmp_path / "text.parquet")
    pq.write_table(pa.table({"s": arr}), path, row_group_size=1000,
                   data_page_size=(1 << 20) if pages == "one_page" else 512,
                   write_batch_size=64, **options)
    counters = {}
    groups = _decode_text_groups(path, counters)
    assert [n for _, n, _ in groups] == [1000, 1000, 500]
    for column, n, want in groups:
        got = column.to_arrow(n)
        assert got.equals(want)
        assert column.is_dict == (kind == "dict")
        if kind == "dict":
            continue
        # the flat layout IS Arrow's: offsets and payload as pyarrow's
        # reader lays them out (nulls take no byte)
        w_off = np.frombuffer(want.buffers()[1], np.int32)[:n + 1]
        w_off = w_off - w_off[0]
        offsets = np.asarray(column.offsets)
        assert np.array_equal(offsets[:n + 1], w_off)
        assert (offsets[n:] == w_off[-1]).all()         # dead rows clamp
        w_data = np.frombuffer(want.buffers()[2], np.uint8) \
            if want.buffers()[2] is not None else np.zeros(0, np.uint8)
        first = np.frombuffer(want.buffers()[1], np.int32)[0]
        payload = np.asarray(column.data)
        assert np.array_equal(payload[:w_off[-1]],
                              w_data[first:first + w_off[-1]])
        assert not payload[w_off[-1]:].any()            # and zero past it
        validity = np.asarray(column.validity)
        assert np.array_equal(validity[:n], np.asarray(want.is_valid()))
        assert not validity[n:].any()
        longest = int(np.diff(w_off).max()) if n else 0
        assert column.max_bytes >= max(longest, 1)
    flat = 0 if kind == "dict" else 3
    assert counters.get("scanChunksByteArrayPlain", 0) == flat
    assert counters["scanColumnChunksDecoded"] == 3
    assert sum(counters.get(k, 0) for k in (
        "scanChunksPlain", "scanChunksDictionary",
        "scanChunksDictionaryThenPlain")) == 3
    if kind == "dictplain":
        assert counters["scanChunksDictionaryThenPlain"] == 3
    assert counters["scanChunksNoNulls"] == (3 if nulls == "no_nulls" else 0)


def test_text_chunks_are_named_by_what_they_decode(tmp_path, monkeypatch):
    asked = _spy_on_programs(monkeypatch)
    for kind, nulls in (("plain", "no_nulls"), ("plain", "nulls"),
                        ("dictplain", "no_nulls"), ("dictplain", "nulls")):
        path = str(tmp_path / f"{kind}_{nulls}.parquet")
        pq.write_table(pa.table({"s": _texts(900, nulls)}), path,
                       write_batch_size=64, **_TEXT_KINDS[kind][0])
        _decode_text_groups(path)
    assert [name for name, _, _ in asked] == [
        "parquet_decode_string_plain_nn",
        "parquet_decode_string_plain_place",
        "parquet_decode_string_plain",
        "parquet_decode_string_plain_place",
        "parquet_decode_string_dictplain_nn",
        "parquet_decode_string_plain_place",
        "parquet_decode_string_dictplain",
        "parquet_decode_string_plain_place"]


def test_dictionaries_of_different_lengths_share_their_programs(tmp_path):
    """Two files whose string chunks start on dictionaries of different
    lengths run the same compiled programs: every shape is a bucket, the
    true counts operands. Counted as JAX counts compiles."""
    import jax
    compiles = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda event, secs, **kw: compiles.append(event)
        if event == "/jax/core/compile/backend_compile_duration" else None)
    seen = []
    for seed, limit in ((1, 6000), (2, 7000)):
        path = str(tmp_path / f"seed{seed}.parquet")
        pq.write_table(pa.table({"s": _texts(3000, "no_nulls", seed=seed)}),
                       path, dictionary_pagesize_limit=limit,
                       write_batch_size=64)
        with open(path, "rb") as f:
            md = pq.ParquetFile(path).metadata.row_group(0).column(0)
            plan = PD.plan_column_chunk(
                f, md, T.StructField("s", T.STRING, True), 1)
        seen.append((plan.page_counts[0], plan.dict_count))
        before = len(compiles)
        (column, n, want), = _decode_text_groups(path)
        assert column.to_arrow(n).equals(want)
        if seed == 2:
            assert len(compiles) == before, compiles[before:]
    # the dictionaries differ in length (184 and 179 entries)
    assert seen[0][0] != seen[1][0]


def test_string_dictionary_page_after_plain_pages_is_refused(tmp_path,
                                                             monkeypatch):
    path = str(tmp_path / "text.parquet")
    pq.write_table(pa.table({"s": _texts(2000, "no_nulls")}), path,
                   dictionary_pagesize_limit=4096, data_page_size=512,
                   write_batch_size=64)
    real = PD._parse_page_header
    seen = []

    def reversed_encodings(buf, pos):
        ph = real(buf, pos)
        if ph.page_type == 0:
            seen.append(ph.encoding)
            ph.encoding = PD.PLAIN if len(seen) == 1 else PD.RLE_DICTIONARY
        return ph
    monkeypatch.setattr(PD, "_parse_page_header", reversed_encodings)
    with pytest.raises(NotImplementedError,
                       match="dictionary pages after PLAIN"):
        _decode_text_groups(path)
