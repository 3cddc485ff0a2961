"""One span API on the device trace's clock; counters where the work
happens; device programs named for what they are (ISSUE 28,
docs/monitoring.md#one-span-api).

* clock: a tree span, mapped through the ``clock`` pair of its Chrome
  export, lands on its ``TraceAnnotation`` twin in the profiler's trace;
* names: programs are named from their cache key, in ``[a-z0-9_]``;
* counters: the scan's chunks, parse/upload/launch time, runs of the plan,
  what JAX compiled;
* the load's profile has a slot of its own;
* ``QueryProfile.totals`` counts a shared node name once.
"""

import glob
import json
import os
import re

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from spark_rapids_tpu.metrics import trace as TR
from spark_rapids_tpu.metrics.profile import QueryProfile
from spark_rapids_tpu.ops import aggregates as A
from spark_rapids_tpu.ops.expression import col, lit
from spark_rapids_tpu.session import TpuSession

DEVICE = {"spark.rapids.sql.enabled": True,
          "spark.rapids.sql.test.enabled": True,
          "spark.rapids.sql.variableFloatAgg.enabled": True}


def _lineitem(tmp_path, rows=3000, row_group=1000):
    """A small lineitem of six columns in three row groups, numbers PLAIN
    and the string dictionary-encoded, as the benchmark writes them."""
    rng = np.random.default_rng(7)
    table = pa.table({
        "l_orderkey": np.arange(rows, dtype=np.int64),
        "l_quantity": rng.integers(1, 51, rows).astype(np.float64),
        "l_extendedprice": rng.integers(100, 10000, rows).astype(np.float64),
        "l_discount": rng.integers(0, 11, rows).astype(np.float64) / 100,
        "l_shipdate": rng.integers(8000, 10500, rows).astype(np.int32),
        "l_returnflag": pa.array(rng.choice(["A", "N", "R"], rows)),
    })
    path = str(tmp_path / "lineitem.parquet")
    pq.write_table(table, path, row_group_size=row_group,
                   use_dictionary=["l_returnflag"])
    return path, table


def _q6(df, quantity=24.0):
    return (df.where((col("l_shipdate") >= lit(8766))
                     & (col("l_shipdate") < lit(9131))
                     & (col("l_quantity") < lit(quantity)))
            .group_by().agg(A.AggregateExpression(
                A.Sum(col("l_extendedprice") * col("l_discount")),
                "revenue")))


# ---------------------------------------------------------------------------
# (a) one clock
# ---------------------------------------------------------------------------


def test_tree_spans_land_on_their_annotation_twins(tmp_path):
    import jax
    path, _ = _lineitem(tmp_path)
    trace_dir = tmp_path / "chrome"
    session = TpuSession(dict(DEVICE, **{
        "spark.rapids.tpu.trace.enabled": True,
        "spark.rapids.tpu.trace.dir": str(trace_dir)}))
    query = _q6(session.read.parquet(path))
    query.collect()                       # compile outside the trace
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp_path / "xplane"),
                             profiler_options=options)
    try:
        query.collect()
    finally:
        jax.profiler.stop_trace()
    tracer = session.last_trace()
    exported = os.path.join(
        str(trace_dir), f"trace_{tracer.trace_id}.json")
    with open(exported) as f:
        chrome = json.load(f)
    clock = chrome["otherData"]["clock"]
    assert set(clock) == {"perf_counter_ns", "unix_ns"}

    found = glob.glob(str(tmp_path / "xplane" / "**" / "*.xplane.pb"),
                      recursive=True)
    profile = jax.profiler.ProfileData.from_file(found[0])
    start = next(int(dict(p.stats)["profile_start_time"])
                 for p in profile.planes
                 if "profile_start_time" in dict(p.stats))
    twins = {}
    for plane in profile.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for event in line.events:
                    twins.setdefault(event.name, []).append(
                        (start + event.start_ns, event.duration_ns))
    spans = [e for e in chrome["traceEvents"] if e["ph"] == "X"]
    assert {"session.query", "session.dispatch", "parquet.device_decode",
            "pipeline.decode"} <= {e["name"] for e in spans}
    for event in spans:
        unix_ns = clock["unix_ns"] + event["ts"] * 1e3
        assert event["name"] in twins, f"{event['name']}: no annotation"
        gap, twin_dur = min((abs(unix_ns - t), d)
                            for t, d in twins[event["name"]])
        assert gap < 1e6, f"{event['name']}: {gap} ns off its twin"
        assert abs(twin_dur - event["dur"] * 1e3) < 1e6


def test_untraced_span_is_the_bare_annotation_and_keeps_no_tracer():
    import jax
    session = TpuSession({"spark.rapids.sql.enabled": True})
    df = session.create_dataframe({"a": [1, 2, 3]}).where(col("a") > lit(1))
    assert df.collect().num_rows == 2
    assert session.last_trace() is None
    assert type(TR.span(None, "x")) is jax.profiler.TraceAnnotation


# ---------------------------------------------------------------------------
# (b) names
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("kind,suffix,want", [
    ("parquet_decode", "int_bw17_dict", "parquet_decode_int_bw17_dict"),
    ("agg_partial", "", "agg_partial"),
    ("csv_device.parse", "", "csv_device_parse"),
    ("Project", "A-b C", "project_a_b_c"),
])
def test_program_name_is_readable_and_plain(kind, suffix, want):
    from spark_rapids_tpu.utils.kernel_cache import program_name
    assert program_name(kind, suffix) == want
    assert re.fullmatch(r"[a-z0-9_]+", program_name(kind, suffix))


def test_cached_kernel_lowers_to_a_module_named_from_its_key():
    import jax.numpy as jnp
    from spark_rapids_tpu.utils.kernel_cache import cached_kernel

    def build():
        def kern(x):
            return x + 1
        return kern
    key = ("int", 8, 17, True)
    fn = cached_kernel("parquet_decode", key, build,
                       suffix="int_bw17_dict")
    text = fn.lower(jnp.ones(8, jnp.int32)).as_text()
    assert "module @jit_parquet_decode_int_bw17_dict" in text
    assert "kern" not in text.split("\n", 1)[0]
    # equal keys: the same program under the same name
    again = cached_kernel("parquet_decode", key, build,
                          suffix="int_bw17_dict")
    assert again is fn and again.__name__ == "parquet_decode_int_bw17_dict"
    assert int(fn(jnp.ones(8, jnp.int32))[0]) == 2


def test_decode_and_fused_programs_carry_their_names(tmp_path):
    from spark_rapids_tpu.exec import fusion
    from spark_rapids_tpu.utils import kernel_cache
    path, _ = _lineitem(tmp_path)
    session = TpuSession(DEVICE)
    # a filter that returns whole rows decodes every column of the file,
    # Q6 only the ones it references (plan/optimizer.py): one program a
    # (type, encoding)
    session.read.parquet(path).where(col("l_orderkey") >= lit(0)).collect()
    _q6(session.read.parquet(path)).collect()
    names = {fn.__name__ for fn in kernel_cache._CACHE.values()}
    # (no null in this file: a PLAIN chunk is its uploaded buffer, and one
    # validity program serves every type)
    assert {"parquet_decode_plain_nn",
            "parquet_decode_string_dictstr_nn"} <= names
    fused = {p.fn.__name__ for p in fusion._FUSED_CACHE.values()}
    assert fused and all(re.fullmatch(r"fused_[0-9a-f]{8}", n)
                         for n in fused)


def test_decode_phases_are_named_scopes(tmp_path):
    import jax.numpy as jnp
    from spark_rapids_tpu import types as T
    from spark_rapids_tpu.io import parquet_device as PD
    runs = tuple(jnp.zeros(8, jnp.int32) for _ in range(5))
    packed = jnp.zeros(16, jnp.uint8)
    dictionary = jnp.zeros(8, jnp.int32)

    def decode(def_table, idx_table, packed, dictionary, n):
        return PD._decode_chunk_device(def_table, idx_table, packed, None,
                                       dictionary, n, 128, 3, T.INT, False)
    import jax
    text = jax.jit(decode).lower(
        runs, runs, packed, dictionary,
        jnp.asarray(100, jnp.int32)).as_text(debug_info=True)
    for scope in ("def_levels/expand_hybrid", "def_levels/unpack",
                  "dict_gather"):
        assert scope in text, scope


def test_a_chunk_without_nulls_has_no_definition_level_phase():
    import jax
    import jax.numpy as jnp
    from spark_rapids_tpu.io import parquet_device as PD
    runs = tuple(jnp.zeros(8, jnp.int32) for _ in range(5))
    packed = jnp.zeros(16, jnp.uint8)
    dictionary = jnp.zeros(8, jnp.int32)

    def decode(idx_table, packed, dictionary, n):
        return PD._decode_chunk_no_nulls(idx_table, packed, None,
                                         dictionary, n, 128)
    text = jax.jit(decode).lower(
        runs, packed, dictionary,
        jnp.asarray(100, jnp.int32)).as_text(debug_info=True)
    for scope in ("live_rows", "expand_hybrid", "unpack", "dict_gather"):
        assert scope in text, scope
    # the one run-table expansion is the index stream's
    assert "def_levels" not in text


def test_fused_operators_are_named_scopes():
    import jax
    from spark_rapids_tpu.exec import fusion
    session = TpuSession({"spark.rapids.sql.enabled": True})
    df = session.create_dataframe({"a": list(range(64))}) \
        .where(col("a") > lit(3)).select((col("a") + lit(1)).alias("b"))
    assert df.collect().num_rows == 60
    # the scopes are in the lowered text's locations: lower every fused
    # program of the process again at the shapes it ran with
    texts = []
    for program in fusion._FUSED_CACHE.values():
        for treedef, leaves in program._jit_seen:
            args = jax.tree_util.tree_unflatten(
                treedef, [jax.ShapeDtypeStruct(s, d) for s, d in leaves])
            texts.append(program.fn.lower(*args).as_text(debug_info=True))
    assert any("TpuProjectExec/TpuFilterExec" in t for t in texts)


# ---------------------------------------------------------------------------
# (c) counters
# ---------------------------------------------------------------------------


def test_scan_counters_of_a_small_q6(tmp_path):
    path, table = _lineitem(tmp_path)
    session = TpuSession(DEVICE)
    # a literal no other test uses: this process compiles the program here
    got = _q6(session.read.parquet(path), quantity=23.0).collect()
    keep = ((table["l_shipdate"].to_numpy() >= 8766)
            & (table["l_shipdate"].to_numpy() < 9131)
            & (table["l_quantity"].to_numpy() < 23.0))
    want = float((table["l_extendedprice"].to_numpy()[keep]
                  * table["l_discount"].to_numpy()[keep]).sum())
    assert got.column("revenue")[0].as_py() == pytest.approx(want, rel=1e-12)
    profile = session.last_query_profile()
    totals = profile.totals()
    assert totals["deviceDecodedRowGroups"] == 3
    # the projection reaches the scan: the 4 columns Q6 references of 6
    assert totals["scanColumnChunksDecoded"] == 3 * 4
    assert totals["planRuns"] == 1
    # no null in the file: every chunk decoded without the null machinery
    assert totals["scanChunksNoNulls"] == totals["scanChunksPlain"] == 3 * 4
    referenced = table.select(["l_quantity", "l_extendedprice",
                               "l_discount", "l_shipdate"])
    assert totals["uploadBytes"] > referenced.nbytes // 2
    for name in ("scanParseNs", "scanUploadNs", "scanLaunchNs"):
        assert totals[name] > 0, name
    assert totals["scanParseNs"] + totals["scanUploadNs"] \
        + totals["scanLaunchNs"] <= totals["decodeThreadBusyNs"]
    compile_ = profile.engine["compile"]
    assert compile_["xlaCompiles"] >= 1 and compile_["xlaCompileNs"] > 0
    assert compile_["kernelBuildNs"] >= 0 and "compileNs" not in compile_
    for name in ("xlaCompileNs", "xlaCompiles", "persistentCacheHits",
                 "persistentCacheMisses"):
        assert profile.extras["TpuSession"][name] == compile_[name]
    # the second run compiles nothing
    _q6(session.read.parquet(path), quantity=23.0).collect()
    again = session.last_query_profile()
    assert again.engine["compile"]["xlaCompiles"] == 0
    assert again.totals()["planRuns"] == 1
    assert {"hbmBytesInUse", "hbmPeakBytesInUse"} <= set(again.engine)


@pytest.mark.parametrize("key,sort,want", [
    ("l_returnflag", True, 3), ("l_orderkey", True, 0),
    ("l_returnflag", False, 0)],
    ids=["dictionary-key", "integer-key", "inlined-in-fused-program"])
def test_masked_slot_batches_counted_where_the_host_hands_them_over(
        tmp_path, key, sort, want):
    """``aggMaskedSlotBatches``: one per batch the host hands to
    ``agg_partial`` with sorted-dictionary keys of few slots (a sort above
    the aggregate is a fusion boundary, so its subtree streams); none for
    an integer key, none where the aggregate is inlined in a fused
    program."""
    path, table = _lineitem(tmp_path)
    session = TpuSession(DEVICE)
    df = session.read.parquet(path).group_by(col(key)).agg(
        A.AggregateExpression(A.Sum(col("l_quantity")), "q"),
        A.AggregateExpression(A.Count(), "n"))
    got = (df.sort(col(key)) if sort else df).collect()
    assert sum(got.column("n").to_pylist()) == table.num_rows
    assert got.column("q").to_numpy().sum() == pytest.approx(
        table["l_quantity"].to_numpy().sum(), rel=1e-12)
    totals = session.last_query_profile().totals()
    assert totals.get("aggMaskedSlotBatches", 0) == want


def test_join_over_its_capacity_runs_the_plan_twice():
    n, dup = 600, 4
    session = TpuSession(dict(DEVICE, **{
        "spark.rapids.tpu.trace.enabled": True}))
    left = session.create_dataframe({
        "k": np.arange(n, dtype=np.int64),
        "a": np.arange(n, dtype=np.int64)})
    right = session.create_dataframe({
        "k": (np.arange(n * dup) % n).astype(np.int64),
        "b": np.arange(n * dup, dtype=np.int64)})
    joined = left.join(right, on="k")
    assert joined.collect().num_rows == n * dup
    totals = session.last_query_profile().totals()
    assert totals["planRuns"] == 2
    dispatches = [s for s in session.last_trace().spans
                  if s["name"] == "session.dispatch"]
    assert len(dispatches) == 2
    # the learned capacity is kept: the same query again runs once
    assert joined.collect().num_rows == n * dup
    assert session.last_query_profile().totals()["planRuns"] == 1


# ---------------------------------------------------------------------------
# (d) the load's profile
# ---------------------------------------------------------------------------


def test_cache_fills_the_load_slot_and_leaves_the_query_slot(tmp_path):
    path, table = _lineitem(tmp_path)
    log_dir = tmp_path / "events"
    session = TpuSession(dict(DEVICE, **{
        "spark.rapids.tpu.metrics.eventLog.dir": str(log_dir)}))
    assert session.last_load_profile() is None
    df = session.read.parquet(path)
    _q6(df).collect()
    before = session.last_query_profile()
    cached = df.cache()
    assert session.last_query_profile() is before
    load = session.last_load_profile()
    assert isinstance(load, QueryProfile) and load is not before
    assert load.totals()["planRuns"] == 1
    assert load.query_id == before.query_id + 1
    assert session.query_profile(load.query_id) is load
    _q6(cached).collect()
    after = session.last_query_profile()
    assert after is not before and session.last_load_profile() is load
    assert after.totals().get("deviceDecodedRowGroups", 0) == 0
    from spark_rapids_tpu.metrics import eventlog
    logged = [r["query_id"] for r in eventlog.read_all(str(log_dir))]
    assert load.query_id in logged


# ---------------------------------------------------------------------------
# QueryProfile.totals
# ---------------------------------------------------------------------------


def test_totals_counts_a_shared_node_name_once():
    scan = {"name": "TpuParquetScanExec", "describe": "scan",
            "metrics": {"deviceDecodedRowGroups": 9, "opTime": 5,
                        "flag": True},
            "children": []}
    join = {"name": "Join", "describe": "join", "metrics": {"opTime": 2},
            "children": [scan, dict(scan), dict(scan)]}
    profile = QueryProfile(
        query_id=1, plan_hash="h", wall_ns=1, level="ESSENTIAL", tree=join,
        extras={"TpuSession": {"planRuns": 1, "opTime": 1}},
        engine={"compile": {"xlaCompiles": 3}})
    assert profile.totals() == {"deviceDecodedRowGroups": 9, "opTime": 8,
                                "planRuns": 1}


# ---------------------------------------------------------------------------
# The scan's host side, stage by stage
# ---------------------------------------------------------------------------

#: a column chunk's stages: span -> the counter that times it
STAGES = {"parquet.read": "scanReadNs",
          "parquet.decompress": "scanDecompressNs",
          "parquet.def_levels": "scanDefLevelNs",
          "parquet.index_runs": "scanIndexRunNs",
          "parquet.values": "scanValuesNs",
          "parquet.stage": "scanStageNs",
          "parquet.upload": "scanUploadNs",
          "parquet.launch": "scanLaunchNs"}
#: the benchmark's readers of the stages' counters
STAGE_READERS = {"scan_read_s_per_query": "scanReadNs",
                 "scan_decompress_s_per_query": "scanDecompressNs",
                 "scan_index_run_parse_s_per_query": "scanIndexRunNs",
                 "scan_values_s_per_query": "scanValuesNs",
                 "scan_stage_s_per_query": "scanStageNs"}


def _metric(name):
    import importlib.util
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmarks", "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location("metric_" + name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


def test_scan_stages_are_children_of_the_row_groups_span(tmp_path):
    """Under ``trace.enabled`` each stage of a column chunk is one tree
    span, a child of its row group's ``parquet.device_decode`` on the
    producer thread that decoded it, and lands on its annotation twin in
    the profiler's trace."""
    import jax
    rng = np.random.default_rng(5)
    rows = 4000
    table = pa.table({
        "k": pa.array(rng.integers(0, 50, rows), pa.int64(),
                      mask=rng.random(rows) < 0.05),
        "x": rng.random(rows),
        "s": pa.array(rng.choice(["a", "b", "c"], rows))})
    path = str(tmp_path / "stages.parquet")
    pq.write_table(table, path, row_group_size=2000,
                   use_dictionary=["k", "s"])
    session = TpuSession(dict(DEVICE, **{
        "spark.rapids.tpu.trace.enabled": True,
        "spark.rapids.tpu.trace.dir": str(tmp_path / "chrome")}))
    query = session.read.parquet(path).where(col("x") >= lit(0.0))
    query.collect()                       # compile outside the trace
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp_path / "xplane"),
                             profiler_options=options)
    try:
        assert query.collect().num_rows == rows
    finally:
        jax.profiler.stop_trace()
    tracer = session.last_trace()
    by_id = {s["id"]: s for s in tracer.spans}
    decodes = [s for s in tracer.spans
               if s["name"] == "parquet.device_decode"]
    assert len(decodes) == 2                   # one a row group
    stages = [s for s in tracer.spans if s["name"] in STAGES]
    # one span a stage and chunk: 3 columns (each nullable in the file, two
    # of them dictionary-encoded) in each of 2 row groups
    want = {name: 6 for name in STAGES}
    want["parquet.index_runs"] = 4
    assert {name: sum(s["name"] == name for s in stages)
            for name in STAGES} == want
    for s in stages:
        parent = by_id[s["parent"]]
        assert parent["name"] == "parquet.device_decode", s
        assert parent["tid"] == s["tid"]
        assert parent["t0_ns"] <= s["t0_ns"] <= s["t1_ns"] \
            <= parent["t1_ns"]

    found = glob.glob(str(tmp_path / "xplane" / "**" / "*.xplane.pb"),
                      recursive=True)
    profile = jax.profiler.ProfileData.from_file(found[0])
    start = next(int(dict(p.stats)["profile_start_time"])
                 for p in profile.planes
                 if "profile_start_time" in dict(p.stats))
    twins = {}
    for plane in profile.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for event in line.events:
                    if event.name in STAGES:
                        twins.setdefault(event.name, []).append(
                            start + event.start_ns)
    for s in stages:
        unix_ns = tracer.unix_ns + s["t0_ns"] - tracer.t0_ns
        gap = min(abs(unix_ns - t) for t in twins[s["name"]])
        assert gap < 1e6, f"{s['name']}: {gap} ns off its twin"


@pytest.mark.parametrize("name", sorted(STAGE_READERS))
def test_stage_reader_reads_its_counter(name):
    """Each stage's reader: its counter's thread-seconds a completed
    query, nothing without the counter or a completed query."""
    read = _metric(name)
    counter = STAGE_READERS[name]
    run = {"counters": {counter: 3_000_000_000, "scanParseNs": 7},
           "completed": 2}
    assert read(run) == 1.5
    assert read(dict(run, counters={"scanParseNs": 7})) is None
    assert read(dict(run, completed=0)) is None
    # the benchmark's file-scanning cells list it, the cached cell not
    with open(os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "BENCHMARK.json")) as f:
        entry = next(m for m in json.load(f)["per_layer"]
                     if m["name"] == name)
    assert entry["source"] == "program_counter"
    assert "tpch_sf1_cached.q1" not in entry["workloads"]
    assert len(entry["workloads"]) == 7


def test_scan_host_idle_reads_the_scan_spans_of_the_reduced_trace():
    """The idle seconds a traced query that the reduced trace puts under a
    ``parquet.*`` span or ``pipeline.wait``; no other span's, and none
    from the pooled short gaps."""
    read = _metric("scan_host_idle_s_per_query")
    trace = {"queries": 4, "window_s": 10.0, "busy_s": 3.0, "idle_gaps": [
        ["pipeline.wait", 2.5], ["parquet.decompress", 1.75],
        ["(shorter gaps)", 0.6], ["parquet.device_decode", 0.5],
        ["parquet.index_runs", 0.25], ["session.dispatch", 0.2],
        ["DevicePutWithSharding", 0.1], ["parquet.stage", 0.05]]}
    assert read({"trace": trace}) == pytest.approx(
        (2.5 + 1.75 + 0.5 + 0.25 + 0.05) / 4)
    assert read({"trace": dict(trace, idle_gaps=[
        ["(shorter gaps)", 5.0]])}) == 0.0
    assert read({"trace": None}) is None
