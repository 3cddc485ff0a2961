"""The configuration ``tpcds_sf1_parquet`` on the CPU backend: TPC-DS Q43
over files written by ``pq.write_table(table, path)`` with no option, whose
store_sales keys and prices hold nulls, under the configuration's ``conf``
(``test.enabled``), at 1/32 of its rows — 90,013 store_sales rows in one
row group. ``run_cell`` holds the answer to the plain reference and every
row group to the device; the per-layer readers of the cell read the run;
a hand-made star with nulls in known rows holds the null semantics. No
time read here is a device metric."""

import importlib.util
import json
import os
import sys

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmarks")
CONFIG = "tpcds_sf1_parquet"
CELL = CONFIG + ".q43"
SCALE = 1 / 32
SEED = 2147483743
TABLES = ("store_sales", "date_dim", "store")
READERS = ("scan_nullable_chunks_per_query",
           "scan_def_level_parse_s_per_query", "null_decode_roofline")


def _load(kind, name):
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name}", os.path.join(BENCH, kind, name + ".py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.fixture(scope="module")
def bench_run():
    sys.path[:0] = [BENCH, ROOT]
    try:
        import run
        yield run
    finally:
        sys.path.remove(BENCH)
        sys.path.remove(ROOT)


@pytest.fixture(scope="module")
def cell(bench_run):
    return bench_run.load_cell(CELL)


@pytest.fixture(scope="module")
def paths(bench_run, cell):
    return cell["generator"].ensure(bench_run.DATA_DIR, cell["config"],
                                    TABLES, SEED, SCALE)[0]


@pytest.fixture(scope="module")
def q43_run(bench_run, cell, paths):
    return bench_run.run_cell(cell, SEED, 0.1, False, scale=SCALE)


def _collect(cell, paths, query="q43", **params):
    from spark_rapids_tpu.session import TpuSession
    session = TpuSession(dict(cell["config"]["conf"]))
    try:
        tables = cell["loader"].load(session, paths)
        answer = cell["queries"][query].build(tables, **params).collect()
        totals = session.last_query_profile().totals()
    finally:
        session.close()
    return answer, totals


def _same(got: dict, want: dict):
    """Row for row: strings exactly, sums to float64's rounding, a null
    where the reference has one."""
    assert list(got) == list(want)
    for name, w in want.items():
        g = got[name]
        assert len(g) == len(w), name
        if w.dtype.kind == "f":
            assert np.array_equal(np.isnan(g), np.isnan(w)), name
            ok = ~np.isnan(w)
            np.testing.assert_allclose(g[ok], w[ok], rtol=1e-12,
                                       err_msg=name)
        else:
            assert g.tolist() == w.tolist(), name


def test_q43_with_nulls_is_correct(q43_run):
    run = q43_run
    assert run["correct"], run["compared"]
    assert run["failed"] == 0 and run["completed"] >= 1
    compared = run["compared"]
    assert compared["wrong_cells"]["value"] == 0
    assert compared["rel_gap.q43"]["value"] <= 1e-13
    assert compared["host_row_groups"]["value"] == 0
    assert compared["undecoded_row_groups"]["value"] == 0


def test_every_store_sales_chunk_takes_the_nullable_program(q43_run):
    counters = q43_run["counters"]
    runs_of_plan = counters["planRuns"]
    # store_sales: 3 columns with nulls; date_dim 3 and store 4 without
    assert counters["scanColumnChunksDecoded"] == 10 * runs_of_plan
    assert counters["scanColumnChunksDecoded"] \
        - counters["scanChunksNoNulls"] == 3 * runs_of_plan
    assert counters["scanDefLevelNs"] > 0
    assert counters["scanNullableUploadBytes"] > 0
    assert counters["scanNullableUploadBytes"] < counters["uploadBytes"]
    assert counters["scanDefLevelNs"] <= counters["scanParseNs"]


def test_engine_answer_equals_the_reference_row_for_row(bench_run, cell,
                                                        paths):
    import compare
    answer, totals = _collect(cell, paths)
    want = bench_run.reference_answers(cell, paths)["q43"]
    assert 0 < answer.num_rows <= 100
    _same(compare.answer_columns(answer), want)
    assert totals.get("hostFallbackRowGroups", 0) == 0


def test_the_float32_control_is_not_correct(bench_run, cell, paths):
    import control
    correct, compared = control.control_reading(cell, SEED, SCALE)
    assert correct is False
    assert compared["rel_gap.q43"]["value"] > 1e3 * 1e-10


@pytest.mark.parametrize("fault", ["price_moved", "row_dropped",
                                   "name_changed", "host_row_group"])
def test_a_faulty_answer_is_not_correct(bench_run, cell, paths, fault):
    import compare
    want = bench_run.reference_answers(cell, paths)["q43"]
    held = {"failed": (0, 0), "host_row_groups": (0, 0),
            "undecoded_row_groups": (0, 0)}
    ok, _ = compare.judge([("q43", dict(want))], {"q43": want}, cell, held)
    assert ok
    got = {name: values.copy() for name, values in want.items()}
    if fault == "price_moved":
        got["sun_sales"][0] *= 1 + 1e-6
    elif fault == "row_dropped":
        got = {name: values[:-1] for name, values in got.items()}
    elif fault == "name_changed":
        got["s_store_name"] = np.array(["x"] + got["s_store_name"][1:]
                                       .tolist(), np.str_)
    else:
        held["host_row_groups"] = (1, 0)
    ok, _ = compare.judge([("q43", got)], {"q43": want}, cell, held)
    assert not ok


@pytest.mark.parametrize("name", READERS)
def test_reader_reads_the_run_and_none_without_its_source(q43_run, name):
    run = dict(q43_run, peaks={"hbm_bytes_per_s": 819e9})
    read = _load("metrics", name).read
    counter = {"scan_nullable_chunks_per_query": "scanChunksNoNulls",
               "scan_def_level_parse_s_per_query": "scanDefLevelNs",
               "null_decode_roofline": "scanNullableUploadBytes"}[name]
    if name == "null_decode_roofline":
        assert read(run) is None                    # no trace on the CPU
        run = dict(run, traced_queries=["q43"], trace={
            "busy_s": 1.0, "device_ops": [
                ["jit_parquet_decode_bigint_bw11_dict/fusion.3", 0.25],
                ["jit_parquet_decode_double_bw15_dict/fusion.3", 0.25],
                ["jit_parquet_decode_bigint_dict_nn/fusion.2", 9.0],
                ["jit_fused_07f46e2d/fusion.18", 1.0]]})
    value = read(run)
    assert isinstance(value, float) and value > 0
    if name == "scan_nullable_chunks_per_query":
        assert value == 3.0           # one row group at this scale
    without = dict(run, counters={k: v for k, v in run["counters"].items()
                                  if k != counter})
    assert read(without) is None
    assert read(dict(run, completed=0)) is None


def test_null_roofline_counts_read_and_written_bytes(q43_run):
    reader = _load("metrics", "null_decode_roofline")
    rows = q43_run["row_counts"]["store_sales"]
    run = dict(q43_run, peaks={"hbm_bytes_per_s": 819e9},
               traced_queries=["q43", "q43"], trace={
                   "busy_s": 2.0, "device_ops": [
                       ["jit_parquet_decode_bigint_bw4_dict/fusion.1", 0.5],
                       ["jit_agg_partial/fusion.9", 3.0]]})
    uploaded = run["counters"]["scanNullableUploadBytes"] \
        / run["completed"] * 2
    # three nullable columns of 8 bytes, each with a validity byte
    assert reader.null_bytes(run) == uploaded + 2 * rows * 3 * 9
    assert reader.read(run) == pytest.approx(
        100 * reader.null_bytes(run) / 819e9 / 0.5)
    run["trace"]["device_ops"] = [["jit_agg_partial/fusion.9", 3.0]]
    assert reader.read(run) is None


def _write(table: pa.Table, path: str) -> str:
    pq.write_table(table, path)
    return path


def test_nulls_in_known_rows(tmp_path, bench_run, cell):
    """A null key joins nothing, a null price adds nothing, and a store's
    day whose prices are all null sums to null — in the engine as in the
    reference."""
    import compare
    import columns
    i64 = pa.int64()
    # 2000-01-02 (a Sunday) .. 2000-01-08, and one day of 1999
    date_sk = [2451546 + k for k in range(7)] + [2451500]
    days = ["Sunday", "Monday", "Tuesday", "Wednesday", "Thursday",
            "Friday", "Saturday", "Friday"]
    date_dim = pa.table({
        "d_date_sk": pa.array(date_sk, i64),
        "d_year": pa.array([2000] * 7 + [1999], pa.int32()),
        "d_day_name": pa.array(days, pa.string())})
    store = pa.table({
        "s_store_sk": pa.array([1, 2, 3], i64),
        "s_store_name": pa.array(["able", "bar", "ought"], pa.string()),
        "s_store_id": pa.array(["AAAAAAAABAAAAAAA", "AAAAAAAACAAAAAAA",
                                "AAAAAAAADAAAAAAA"], pa.string()),
        "s_gmt_offset": pa.array([-5.0, -5.0, -6.0], pa.float64())})
    # (date, store, price): Sunday at store 1 has a null date and a null
    # store beside its sale; Monday at store 1 holds only a null price;
    # store 3 is in the wrong zone and 1999 is the wrong year
    sold = [(2451546, 1, 10.25), (None, 1, 99.0), (2451546, None, 77.0),
            (2451547, 1, None), (2451547, 1, None), (2451546, 2, 1.5),
            (2451548, 2, 2.0), (2451548, 2, None), (2451548, 3, 50.0),
            (2451500, 1, 40.0), (2451552, 2, 3.25)] * 5
    store_sales = pa.table({
        "ss_sold_date_sk": pa.array([d for d, _, _ in sold], i64),
        "ss_store_sk": pa.array([s for _, s, _ in sold], i64),
        "ss_sales_price": pa.array([p for _, _, p in sold], pa.float64())})
    paths = {"store_sales": _write(store_sales,
                                   str(tmp_path / "store_sales.parquet")),
             "date_dim": _write(date_dim, str(tmp_path / "date_dim.parquet")),
             "store": _write(store, str(tmp_path / "store.parquet"))}
    answer, totals = _collect(cell, paths)
    # store_sales' three chunks hold nulls and take the nullable program
    assert totals["scanColumnChunksDecoded"] \
        - totals["scanChunksNoNulls"] == 3
    got = compare.answer_columns(answer)
    query = cell["queries"]["q43"]
    want = query.reference({t: columns.load_columns(paths[t], cols)
                            for t, cols in query.COLUMNS.items()})
    _same(got, want)
    assert got["s_store_name"].tolist() == ["able", "bar"]
    assert got["sun_sales"].tolist() == [5 * 10.25, 5 * 1.5]
    assert np.isnan(got["mon_sales"][0])             # only null prices
    assert got["tue_sales"][1] == 5 * 2.0            # the null skipped
    assert got["sat_sales"][1] == 5 * 3.25
    for day in ("wed_sales", "thu_sales", "fri_sales"):
        assert np.isnan(got[day]).all(), day


def test_the_configuration_entry_and_file_agree(bench_run, cell):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = next(c for c in bench["configs"] if c["name"] == CONFIG)
    config = cell["config"]
    assert entry["source"] == config["source"]
    assert entry["reduced"] == config["reduced"] == ["scale_factor"]
    assert set(config["reduced_why"]) == {"scale_factor"}
    assert config["tables"] == {"store_sales": 2880404, "date_dim": 73049,
                                "store": 12}
    assert config["generator"] == "tpcds" and config["loader"] == "files"
    sibling = bench_run.load_cell(
        "tpch_sf1_parquet_writer_defaults.q6")["config"]
    assert config["conf"] == sibling["conf"]
    assert sibling["guarantees"] == config["guarantees"][:3]
    assert cell["chips"] == 1 and list(cell["queries"]) == ["q43"]
    names = {m["name"] for m in cell["per_layer"]}
    assert set(READERS) | {"launches_per_query", "query_roofline",
                           "device_idle_pct", "compiles_in_window"} <= names
    q1 = bench_run.load_cell("tpch_sf1_parquet_writer_defaults.q1")
    assert q1["chips"] == 1 and list(q1["queries"]) == ["q1"]


def test_the_generator_writes_the_same_bytes_for_a_seed(tmp_path, cell):
    gen = cell["generator"]
    first, n = gen.ensure(str(tmp_path / "a"), cell["config"], TABLES, 11,
                          1 / 256)
    again, _ = gen.ensure(str(tmp_path / "b"), cell["config"], TABLES, 11,
                          1 / 256)
    other, _ = gen.ensure(str(tmp_path / "c"), cell["config"],
                          ["store_sales"], 12, 1 / 256)
    for table in TABLES:
        with open(first[table], "rb") as a, open(again[table], "rb") as b:
            assert a.read() == b.read(), table
    with open(first["store_sales"], "rb") as a, \
            open(other["store_sales"], "rb") as c:
        assert a.read() != c.read()
    assert n == {"store_sales": 11252, "date_dim": 73049, "store": 12}


def test_the_tables_have_the_specifications_columns(paths):
    """23 / 28 / 29 columns; the fact table's primary key never null and
    every other column null on about NULL_RATE of its rows; keys in their
    ranges; the calendar's Julian days."""
    width = {"store_sales": 23, "date_dim": 28, "store": 29}
    files = {t: pq.read_table(p) for t, p in paths.items()}
    for name, table in files.items():
        assert table.num_columns == width[name], name
    sales = files["store_sales"]
    assert sales.num_rows == 90013
    assert pq.ParquetFile(paths["store_sales"]).metadata.num_row_groups == 1
    for column in sales.column_names:
        share = sales.column(column).null_count / sales.num_rows
        if column in ("ss_item_sk", "ss_ticket_number"):
            assert share == 0, column
        else:
            assert 0.03 < share < 0.05, (column, share)
    pairs = set(zip(sales.column("ss_item_sk").to_pylist(),
                    sales.column("ss_ticket_number").to_pylist()))
    assert len(pairs) == sales.num_rows              # the primary key
    sold = sales.column("ss_sold_date_sk").drop_null().to_numpy()
    assert sold.min() >= 2450816 and sold.max() <= 2452642
    dates = files["date_dim"]
    assert dates.column("d_date_sk")[0].as_py() == 2415022
    day = dates.column("d_date").to_pylist()
    assert str(day[0]) == "1900-01-02" and str(day[-1]) == "2100-01-01"
    row = dates.column("d_date_sk").to_pylist().index(2451546)
    assert str(day[row]) == "2000-01-02"
    assert dates.column("d_day_name")[row].as_py() == "Sunday"
    assert dates.column("d_year")[row].as_py() == 2000
    store = files["store"]
    assert len(set(store.column("s_store_id").to_pylist())) == 6
    assert sorted(set(store.column("s_gmt_offset").to_pylist())) \
        == [-6.0, -5.0]
