"""The configuration ``tpch_sf1_parquet_dbgen_text`` on the CPU backend:
Q13 over files written by ``pq.write_table(table, path)`` with no option,
whose comment columns are near-unique, under the configuration's ``conf``
(``test.enabled``), at 1/8 of its rows — 187,500 orders, whose o_comment
chunk starts on a dictionary of about 20,000 texts and falls back to PLAIN
byte-array pages. ``run_cell`` holds the answer to the plain reference and
every row group to the device; the per-layer readers of ISSUE 34 read the
run. ``benchmarks/selfcheck.py`` cannot hold this cell yet (its float32
control needs a floating-point column; Q13's answer holds counts only),
so this file does. No time read here is a device metric."""

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
CONFIG = "tpch_sf1_parquet_dbgen_text"
CELL = CONFIG + ".q13"
SCALE = 1 / 8
SEED = 2147483659
KINDS = ("scanChunksPlain", "scanChunksDictionary",
         "scanChunksDictionaryThenPlain")


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
def q13_run(bench_run, cell):
    return bench_run.run_cell(cell, SEED, 0.1, False, scale=SCALE)


def test_q13_over_dbgen_text_is_correct(q13_run):
    run = q13_run
    assert run["correct"], run["compared"]
    assert run["failed"] == 0 and run["completed"] >= 1
    compared = run["compared"]
    assert compared["wrong_cells"]["value"] == 0
    assert compared["rel_gap.q13"] == {"value": 0.0, "limit": 0.0}
    assert compared["host_row_groups"]["value"] == 0
    assert compared["undecoded_row_groups"]["value"] == 0
    counters = run["counters"]
    runs_of_plan = counters["planRuns"]
    # orders: o_comment falls back to PLAIN byte arrays, o_orderkey to
    # PLAIN int64s, o_custkey stays on its dictionary; customer: c_custkey
    assert counters["scanColumnChunksDecoded"] == 4 * runs_of_plan
    assert counters["scanChunksByteArrayPlain"] == 1 * runs_of_plan
    assert counters["scanChunksDictionaryThenPlain"] == 2 * runs_of_plan
    assert sum(counters.get(k, 0) for k in KINDS) \
        == counters["scanColumnChunksDecoded"]
    assert counters["scanChunksNoNulls"] \
        == counters["scanColumnChunksDecoded"]


def test_the_files_are_as_the_configuration_says(q13_run, bench_run, cell):
    """o_comment is written dictionary-then-PLAIN, as the counters say."""
    paths, _ = cell["generator"].ensure(
        bench_run.DATA_DIR, cell["config"], ["orders"], SEED, SCALE)
    md = pq.ParquetFile(paths["orders"]).metadata
    assert md.num_row_groups == 1 and md.num_rows == 187500
    row_group = md.row_group(0)
    comment = next(row_group.column(i) for i in range(row_group.num_columns)
                   if row_group.column(i).path_in_schema == "o_comment")
    assert comment.physical_type == "BYTE_ARRAY"
    assert {"PLAIN", "RLE_DICTIONARY"} <= set(comment.encodings)
    # 187,500 x 48.5 bytes: the chunk is nowhere near a 1 MiB dictionary
    assert comment.total_uncompressed_size > 8 * (1 << 20)


def test_the_cell_is_in_the_benchmark_with_its_metrics(bench_run, cell):
    assert cell["chips"] == 1
    assert cell["config"]["generator"] == "tpch_dbgen_text"
    names = {m["name"] for m in cell["per_layer"]}
    assert {"scan_bytearray_chunks_per_query", "text_decode_roofline",
            "launches_per_query", "query_roofline", "device_idle_pct",
            "compiles_in_window", "first_query_s"} <= names
    assert {m["name"] for m in cell["end_to_end"]} \
        == {"query_s", "query_p95_s", "setup_s"}
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = next(c for c in bench["configs"] if c["name"] == CONFIG)
    assert entry["source"] == cell["config"]["source"]
    assert entry["reduced"] == cell["config"]["reduced"] == ["scale_factor"]
    # same tables, conf, loader and guarantees as the writer-defaults
    # sibling: the two differ in the text of the comment columns alone
    sibling = bench_run.load_cell(
        "tpch_sf1_parquet_writer_defaults.q6")["config"]
    for key in ("tables", "conf", "guarantees", "loader", "scale_factor"):
        assert cell["config"][key] == sibling[key], key
    for key, value in sibling["storage"].items():
        if key != "encoding":
            assert cell["config"]["storage"][key] == value, key
    # the waiting cell came with it
    q1 = bench_run.load_cell("tpch_sf1_parquet.q1")
    assert q1["chips"] == 1 and list(q1["queries"]) == ["q1"]


def test_bytearray_reader_reads_its_counter(q13_run):
    run = q13_run
    read = _load("metrics", "scan_bytearray_chunks_per_query").read
    assert read(run) == 1.0          # one row group of orders at this scale
    # a program that does not count such chunks (the parent): nothing
    parent = dict(run, counters={k: v for k, v in run["counters"].items()
                                 if k != "scanChunksByteArrayPlain"})
    assert read(parent) is None
    assert read(dict(run, completed=0)) is None


def test_text_roofline_reads_the_listed_text_programs(q13_run):
    run = q13_run
    reader = _load("metrics", "text_decode_roofline")
    peaks = {"hbm_bytes_per_s": 819e9}
    assert reader.read(dict(run, peaks=peaks)) is None      # no trace here
    orders = run["row_counts"]["orders"]
    traced = dict(run, peaks=peaks, traced_queries=["q13", "q13"], trace={
        "busy_s": 3.0, "device_ops": [
            ["jit_parquet_decode_string_dictplain_nn/while.1", 0.5],
            ["jit_parquet_decode_string_plain_place/fusion.3", 0.25],
            ["jit_fused_07f46e2d/fusion.18", 1.0],
            ["jit_parquet_decode_string_dictstr_nn/fusion.2", 9.0],
            ["jit_parquet_decode_bigint_dictplain_nn/fusion.2", 9.0]]})
    # o_comment alone is text: 53 bytes a row, read once, written once
    assert reader.text_bytes(traced) == 2 * orders * 53
    least_s = 2 * (2 * orders * 53) / 819e9
    assert reader.read(traced) == pytest.approx(100 * least_s / 0.75)
    # a trace that lists none of these programs: nothing to read
    traced["trace"]["device_ops"] = [["jit_fused_07f46e2d/fusion.18", 1.0]]
    assert reader.read(traced) is None


@pytest.mark.parametrize("fault", ["count_changed", "row_dropped",
                                   "host_row_group"])
def test_a_faulty_run_is_not_correct(bench_run, cell, q13_run, fault):
    """``correct`` is exact for this cell: one count off, one row of the
    answer gone, or one row group read on the host, and it is false."""
    sys.path.insert(0, BENCH)
    import compare
    paths, _ = cell["generator"].ensure(
        bench_run.DATA_DIR, cell["config"], ["orders", "customer"], SEED,
        SCALE)
    want = bench_run.reference_answers(cell, paths)["q13"]
    held = {"failed": (0, 0), "host_row_groups": (0, 0),
            "undecoded_row_groups": (0, 0)}
    ok, _ = compare.judge([("q13", dict(want))], {"q13": want}, cell, held)
    assert ok
    got = {name: values.copy() for name, values in want.items()}
    if fault == "count_changed":
        got["custdist"][0] += 1
    elif fault == "row_dropped":
        got = {name: values[:-1] for name, values in got.items()}
    else:
        held["host_row_groups"] = (1, 0)
    ok, compared = compare.judge([("q13", got)], {"q13": want}, cell, held)
    assert not ok
    if fault != "host_row_group":
        assert compared["wrong_cells"]["value"] > 0


def test_the_text_never_meets_a_compaction(bench_run, cell, monkeypatch):
    """o_comment is read by the filter and dropped by the select behind
    it: no ``physical()``, gather or concat rebuilds a flat string column
    (``strings_from_matrix`` sorts rows x width characters)."""
    from spark_rapids_tpu.exec import fusion
    from spark_rapids_tpu.ops import strings
    from spark_rapids_tpu.ops.kernels import concat, rowops
    from spark_rapids_tpu.session import TpuSession

    def never(*a, **kw):
        raise AssertionError("a flat string column was rebuilt")
    for module in (rowops, strings, concat):
        if hasattr(module, "strings_from_matrix"):
            monkeypatch.setattr(module, "strings_from_matrix", never)
    fusion.clear_fused_cache()
    paths, _ = cell["generator"].ensure(
        bench_run.DATA_DIR, cell["config"], ["orders", "customer"], SEED,
        1 / 64)
    session = TpuSession(dict(cell["config"]["conf"]))
    try:
        tables = cell["loader"].load(session, paths)
        answer = cell["queries"]["q13"].build(tables).collect()
    finally:
        session.close()
        fusion.clear_fused_cache()
    assert answer.num_rows > 0


def test_without_test_enabled_no_row_group_is_read_on_the_host(bench_run,
                                                               cell):
    """The host fallback is not what makes the cell pass."""
    from spark_rapids_tpu.session import TpuSession
    paths, _ = cell["generator"].ensure(
        bench_run.DATA_DIR, cell["config"], ["orders", "customer"], SEED,
        1 / 64)
    conf = dict(cell["config"]["conf"])
    conf["spark.rapids.sql.test.enabled"] = False
    session = TpuSession(conf)
    try:
        tables = cell["loader"].load(session, paths)
        cell["queries"]["q13"].build(tables).collect()
        totals = session.last_query_profile().totals()
    finally:
        session.close()
    assert totals.get("hostFallbackRowGroups", 0) == 0
    assert totals["deviceDecodedRowGroups"] == 2
    assert totals["scanChunksByteArrayPlain"] == 1


def test_generator_keeps_the_siblings_rows_and_the_clauses_lengths():
    """Under one seed every non-comment column is the writer-defaults
    sibling's; comments are near-unique and within clause 4.2.2.10's
    lengths."""
    gen = _load("generators", "tpch_dbgen_text")
    counts = gen.tpch.row_counts(
        {"lineitem": 6001215, "orders": 1500000, "customer": 150000,
         "supplier": 10000, "part": 200000, "partsupp": 800000,
         "nation": 25, "region": 5}, 1 / 64)
    sales = gen.tpch.gen_sales(counts, 11, ["orders"], 1 << 20)
    tables = {"orders": sales["orders"],
              "customer": gen.tpch.gen_table("customer", counts, 11,
                                             1 << 20)}
    for name, table in tables.items():
        column, lo, hi = gen.COMMENTS[name]
        cut = gen.with_dbgen_text(name, table, 11)
        assert cut.column_names == table.column_names
        for other in table.column_names:
            if other != column:
                assert cut.column(other).equals(table.column(other)), other
        text = cut.column(column).combine_chunks()
        assert pa.types.is_string(text.type) and text.null_count == 0
        lengths = np.diff(np.frombuffer(text.buffers()[1], np.int32)
                          [:len(text) + 1])
        assert lengths.min() >= lo and lengths.max() <= hi
        assert len(set(text.to_pylist())) > 0.97 * len(text)
        # another seed, other text
        again = gen.with_dbgen_text(name, table, 12).column(column)
        assert not again.combine_chunks().equals(text)
