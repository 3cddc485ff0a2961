"""The per-layer readers of ISSUE 28 against the program's counters: each
gives a number where ``run_cell`` ran (at 1/90 of the rows, on the CPU
backend, as ``benchmarks/selfcheck.py`` does) and ``None`` without its
counter; and a second seed adds no program name. No time read here is a
device metric."""

import importlib.util
import os
import re
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmarks")
SEEDS = (41, 2147483747)          # PR 27's seeds study
CELLS = ("tpch_sf1_parquet.q6", "tpch_sf1_cached.q1")
#: reader -> (its counter, where run_cell keeps it)
READERS = {
    "plan_runs_per_query": ("planRuns", "counters"),
    "scan_decoded_per_referenced": ("scanColumnChunksDecoded", "counters"),
    "scan_parse_s_per_query": ("scanParseNs", "counters"),
    "scan_upload_s_per_query": ("scanUploadNs", "counters"),
    "scan_launch_s_per_query": ("scanLaunchNs", "counters"),
    "cold_programs_in_setup": ("persistentCacheMisses", "setup_counters"),
    "scan_nonnull_chunks_per_query": ("scanChunksNoNulls", "counters"),
}


def _reader(name):
    spec = importlib.util.spec_from_file_location(
        "bench_reader_" + name, os.path.join(BENCH, "metrics", name + ".py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


def _program_names():
    from spark_rapids_tpu.exec import fusion
    from spark_rapids_tpu.utils import kernel_cache
    return {fn.__name__ for fn in kernel_cache._CACHE.values()} \
        | {p.fn.__name__ for p in fusion._FUSED_CACHE.values()}


@pytest.fixture(scope="module")
def runs():
    """{cell: run_cell's result for the first seed}, and the program
    names each (cell, seed) had brought into the process when it ended."""
    sys.path[:0] = [BENCH, ROOT]
    try:
        import run as bench_run
        out, names = {}, {}
        for cell_name in CELLS:
            for seed in SEEDS:
                cell = bench_run.load_cell(cell_name)
                result = bench_run.run_cell(cell, seed, 0.5, False,
                                            scale=1 / 90)
                assert result["correct"], result["compared"]
                out.setdefault(cell_name, result)
                names[cell_name, seed] = _program_names()
        yield out, names
    finally:
        sys.path.remove(BENCH)
        sys.path.remove(ROOT)


def _metrics_of(cell_name):
    import json
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    return {m["name"]: m for m in bench["per_layer"]
            if cell_name in m.get("workloads", [cell_name])}


@pytest.mark.parametrize("name", sorted(READERS))
def test_reader_reads_its_counter_and_none_without_it(runs, name):
    results, _ = runs
    counter, where = READERS[name]
    read = _reader(name)
    cells = [c for c in CELLS if name in _metrics_of(c)]
    assert cells, f"{name}: in no cell's per_layer list"
    for cell_name in cells:
        run = results[cell_name]
        value = read(run)
        assert isinstance(value, (int, float)), (cell_name, value)
        without = dict(run, **{where: {k: v for k, v in run[where].items()
                                       if k != counter}})
        assert read(without) is None


def test_readings_at_a_ninetieth(runs):
    results, _ = runs
    q6, q1 = (results[c] for c in CELLS)
    assert _reader("plan_runs_per_query")(q6) == 1.0
    assert _reader("plan_runs_per_query")(q1) == 1.0
    # the projection reaches the scan: of lineitem's 16 columns the 4 that
    # Q6 references are decoded, and no other
    assert _reader("scan_decoded_per_referenced")(q6) == 1.0
    # TPC-H holds no null: each of the 4 chunks a query (one row group at
    # this scale) decodes without the null machinery
    assert _reader("scan_nonnull_chunks_per_query")(q6) == 4.0
    assert q1["counters"].get("scanColumnChunksDecoded") is None
    spent = sum(_reader(f"scan_{part}_s_per_query")(q6)
                for part in ("parse", "upload", "launch"))
    assert 0 < spent
    assert _reader("cold_programs_in_setup")(q6) >= 0


def test_a_second_seed_adds_no_program_name(runs):
    _, names = runs
    for cell_name in CELLS:
        first, second = (names[cell_name, seed] for seed in SEEDS)
        assert second == first, sorted(second - first)
    every = set().union(*names.values())
    assert any(n.startswith("parquet_decode_") for n in every)
    assert any(n.startswith("fused_") for n in every)
    for name in every:
        assert re.fullmatch(r"[a-z0-9_]+", name), name
        assert name not in ("kern", "run", "partial", "build", "program")
