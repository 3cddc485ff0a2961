"""The configuration ``tpch_sf1_parquet_writer_defaults`` on the CPU
backend: Q6, Q1 and Q3 over files written by ``pq.write_table(table,
path)`` with no option, under the configuration's ``conf``
(``test.enabled``), at 1/8 of its rows — 750,152 of lineitem, where the
dictionaries of l_extendedprice, l_orderkey and o_orderkey pass the
writer's 1 MiB (131,072 values) and their chunks fall back to PLAIN. ``run_cell`` holds each answer to the plain reference and
every row group to the device; the per-layer readers of ISSUE 30 read the
run's counters. No time read here is a device metric."""

import importlib.util
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmarks")
CONFIG = "tpch_sf1_parquet_writer_defaults"
CELL = CONFIG + ".q6"
SCALE = 1 / 8
KINDS = ("scanChunksPlain", "scanChunksDictionary",
         "scanChunksDictionaryThenPlain")
#: query -> (chunks a run of its plan decodes, of them those that fall
#: back): referenced columns x row groups (one a table at this scale);
#: l_extendedprice (q6, q1, q3), l_orderkey and o_orderkey (q3) have over
#: 131,072 distinct values
CHUNKS = {"q6": (4, 1), "q1": (7, 1), "q3": (10, 3)}


def _reader(name):
    spec = importlib.util.spec_from_file_location(
        "bench_reader_" + name, os.path.join(BENCH, "metrics", name + ".py"))
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
def runs(bench_run):
    done = {}

    def run_of(query):
        if query not in done:
            bench = bench_run.load_json(os.path.join(ROOT, "BENCHMARK.json"))
            cell = bench_run.build_cell(bench, f"{CONFIG}.{query}", CONFIG,
                                        query, 1)
            done[query] = bench_run.run_cell(cell, 2147483659, 0.1, False,
                                             scale=SCALE)
        return done[query]
    return run_of


@pytest.mark.parametrize("query", sorted(CHUNKS))
def test_query_over_default_written_files_is_correct(runs, query):
    run = runs(query)
    assert run["correct"], run["compared"]
    assert run["failed"] == 0 and run["completed"] >= 1
    assert run["compared"]["host_row_groups"]["value"] == 0
    assert run["compared"]["undecoded_row_groups"]["value"] == 0
    counters = run["counters"]
    decoded, fell_back = CHUNKS[query]
    runs_of_plan = counters["planRuns"]
    assert counters["scanColumnChunksDecoded"] == decoded * runs_of_plan
    assert counters["scanChunksDictionaryThenPlain"] \
        == fell_back * runs_of_plan
    assert sum(counters.get(k, 0) for k in KINDS) \
        == counters["scanColumnChunksDecoded"]
    # nothing is written PLAIN by the writer's defaults
    assert "scanChunksPlain" not in counters
    # and TPC-H holds no null: no chunk takes a nullable program
    assert counters["scanChunksNoNulls"] \
        == counters["scanColumnChunksDecoded"]


def test_the_cell_is_in_the_benchmark_with_its_metrics(bench_run):
    cell = bench_run.load_cell(CELL)
    assert cell["chips"] == 1 and cell["config"]["generator"] \
        == "tpch_writer_defaults"
    names = {m["name"] for m in cell["per_layer"]}
    assert {"scan_fallback_chunks_per_query", "scan_dict_chunks_per_query",
            "scan_decode_roofline", "scan_nonnull_chunks_per_query",
            "launches_per_query", "query_roofline",
            "device_idle_pct", "compiles_in_window"} <= names
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = next(c for c in bench["configs"] if c["name"] == CONFIG)
    assert entry["source"] == cell["config"]["source"]
    assert entry["reduced"] == cell["config"]["reduced"] == ["scale_factor"]
    # same tables, conf and guarantees as the PLAIN sibling: the two cells
    # differ in the bytes of the files alone
    sibling = bench_run.load_cell("tpch_sf1_parquet.q6")["config"]
    for key in ("tables", "conf", "guarantees", "loader", "scale_factor"):
        assert cell["config"][key] == sibling[key], key


@pytest.mark.parametrize("name,counter,want", [
    ("scan_fallback_chunks_per_query", "scanChunksDictionaryThenPlain", 1.0),
    ("scan_dict_chunks_per_query", "scanChunksDictionary", 3.0)])
def test_chunk_readers_read_their_counters(runs, name, counter, want):
    run = runs("q6")
    read = _reader(name).read
    assert read(run) == want
    # a program that counts no chunk by kind (the parent): nothing to read
    parent = dict(run, counters={k: v for k, v in run["counters"].items()
                                 if k not in KINDS})
    assert read(parent) is None
    # counted by kind, none of this kind: a reading of 0, not a silence
    others = dict(run, counters={k: v for k, v in run["counters"].items()
                                 if k != counter})
    assert read(others) == 0.0
    assert read(dict(run, completed=0)) is None


def test_nonnull_reader_reads_its_counter(runs):
    run = runs("q6")
    read = _reader("scan_nonnull_chunks_per_query").read
    assert read(run) == 4.0
    # a program that does not count such chunks (the parent), though it
    # counts chunks by kind: nothing to read
    parent = dict(run, counters={k: v for k, v in run["counters"].items()
                                 if k != "scanChunksNoNulls"})
    assert read(parent) is None
    # counted, and every chunk held a null: a reading of 0, not a silence
    assert read(dict(run, counters=dict(run["counters"],
                                        scanChunksNoNulls=0))) == 0.0
    assert read(dict(run, completed=0)) is None


def test_decode_roofline_reads_the_listed_decode_operations(runs):
    run = runs("q6")
    reader = _reader("scan_decode_roofline")
    peaks = {"hbm_bytes_per_s": 819e9}
    assert reader.read(dict(run, peaks=peaks)) is None      # no trace here
    rows = run["row_counts"]["lineitem"]
    per_query = run["counters"]["uploadBytes"] / run["completed"]
    traced = dict(run, peaks=peaks, traced_queries=["q6", "q6"], trace={
        "busy_s": 3.0, "device_ops": [
            ["jit_parquet_decode_double_dictplain_nn/fusion.1", 0.5],
            ["jit_fused_07f46e2d/fusion.18", 1.0],
            ["jit_parquet_decode_date_dict_nn/fusion.2", 0.25]]})
    assert reader.bytes_read(traced) == 2 * per_query
    assert reader.bytes_written(traced) == 2 * rows * 28
    least_s = (2 * per_query + 2 * rows * 28) / 819e9
    assert reader.read(traced) == pytest.approx(100 * least_s / 0.75)
    # a trace that lists no decode operation: nothing to read
    traced["trace"]["device_ops"] = [["jit_fused_07f46e2d/fusion.18", 1.0]]
    assert reader.read(traced) is None
