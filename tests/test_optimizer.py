"""Column pruning ends in the file scan (plan/optimizer.py, ISSUE 29): the
required-column set that ``_prune`` threads top-down is set on a NEW
``L.Scan`` as ``projected`` instead of becoming a Project above the scan."""

import pyarrow.parquet as pq
import pytest

from harness import (assert_scan_reads_only_referenced, tpu_session,
                     wide_query, wide_table)

from spark_rapids_tpu import types as T
from spark_rapids_tpu.ops import aggregates as A
from spark_rapids_tpu.ops.arithmetic import Add
from spark_rapids_tpu.ops.expression import col, lit
from spark_rapids_tpu.ops.nondeterministic import (InputFileBlockStart,
                                                   InputFileName)
from spark_rapids_tpu.plan.logical import SortOrder
from spark_rapids_tpu.plan import logical as L
from spark_rapids_tpu.plan.input_file import (FILE_NAME_COL, FILE_START_COL,
                                              rewrite_input_file_exprs)
from spark_rapids_tpu.plan.optimizer import prune_columns

FIELDS = [("a", T.LONG), ("b", T.DOUBLE), ("s", T.STRING), ("d", T.DATE),
          ("e", T.INT), ("f", T.DOUBLE), ("g", T.SHORT), ("t", T.STRING)]


def _scan(prefix="", fmt="parquet"):
    schema = T.Schema([T.StructField(prefix + n, t, True)
                       for n, t in FIELDS])
    return L.Scan(fmt, [f"/nowhere/{prefix or 'x'}"], schema,
                  {"header": True})


def _scans(plan):
    if isinstance(plan, L.Scan):
        return [plan]
    return [s for c in plan.children for s in _scans(c)]


def _nodes(plan):
    return [plan] + [n for c in plan.children for n in _nodes(c)]


def _sum(name, out="total"):
    return A.AggregateExpression(A.Sum(col(name)), out)


def _under_filter():
    return L.Project(L.Filter(_scan(), col("a") > lit(3)), [col("b")])


def _under_aggregate():
    return L.Aggregate(_scan(), [col("s")], [_sum("b")])


def _under_sort():
    return L.Project(L.Sort(_scan(), [SortOrder(col("d"))]), [col("b")])


def _under_limit():
    return L.Project(L.Limit(_scan(), 5), [col("e"), col("a")])


def _under_chained_projects():
    inner = L.Project(_scan(), [col("a"), col("b"), col("f"),
                                Add(col("e"), lit(1)).alias("e1")])
    return L.Project(L.Project(inner, [col("a"), col("e1")]), [col("e1")])


def _under_filter_aggregate_sort_limit():
    """Q6's shape with a sort and a limit on top."""
    plan = L.Filter(_scan(), col("d") > lit(0))
    plan = L.Aggregate(plan, [col("s")], [_sum("f")])
    return L.Limit(L.Sort(plan, [SortOrder(col("total"))]), 3)


@pytest.mark.parametrize("build,want", [
    (_under_filter, ["a", "b"]),
    (_under_aggregate, ["b", "s"]),
    (_under_sort, ["b", "d"]),
    (_under_limit, ["a", "e"]),
    (_under_chained_projects, ["e"]),
    (_under_filter_aggregate_sort_limit, ["s", "d", "f"]),
], ids=["filter", "aggregate", "sort", "limit", "chained_projects",
        "filter_aggregate_sort_limit"])
def test_projected_is_set_under(build, want):
    plan = build()
    out = prune_columns(plan)
    scan, = _scans(out)
    # the required names in FILE order, whatever order the plan uses
    assert scan.projected == sorted(want, key=[n for n, _ in FIELDS].index)
    assert scan.schema.names == scan.projected
    assert out.schema.names == plan.schema.names
    # nothing is left to narrow: no Project sits directly above the scan
    # that only repeats its columns
    for node in _nodes(out):
        if isinstance(node, L.Project) and node.children[0] is scan:
            assert [e.name for e in node.exprs] != scan.projected


def test_join_projects_each_side_to_its_own_set_plus_keys_and_condition():
    join = L.Join(_scan("l_"), _scan("r_"), "inner",
                  [col("l_a")], [col("r_a")],
                  condition=col("l_e") < col("r_g"))
    out = prune_columns(L.Project(join, [col("l_b"), col("r_s")]))
    left, right = _scans(out)
    assert left.projected == ["l_a", "l_b", "l_e"]
    assert right.projected == ["r_a", "r_s", "r_g"]
    joined = [n for n in _nodes(out) if isinstance(n, L.Join)][0]
    assert joined.children == [left, right]


def test_union_projects_every_child_by_position():
    union = L.Union([_scan("x_"), _scan("y_")])
    out = prune_columns(L.Aggregate(union, [], [_sum("x_f")]))
    first, second = _scans(out)
    assert first.projected == ["x_f"] and second.projected == ["y_f"]


def _bare():
    return _scan()


def _write():
    return L.WriteOp(_scan(), "parquet", "/nowhere/out", {}, [], "error")


def _whole_rows_filtered():
    return L.Sort(L.Filter(_scan(), col("a") > lit(3)),
                  [SortOrder(col("d"))])


def _duplicate_names():
    schema = T.Schema([T.StructField("a", T.LONG, True),
                       T.StructField("a", T.DOUBLE, True),
                       T.StructField("b", T.INT, True)])
    scan = L.Scan("csv", ["/nowhere/dup"], schema)
    return L.Aggregate(scan, [], [_sum("b")])


def _every_column_referenced():
    return L.Project(_scan(), [col(n) for n, _ in reversed(FIELDS)])


@pytest.mark.parametrize("build", [
    _bare, _write, _whole_rows_filtered, _duplicate_names,
    _every_column_referenced])
def test_projected_stays_none_for(build):
    plan = build()
    before, = _scans(plan)
    out = prune_columns(plan)
    scan, = _scans(out)
    assert scan.projected is None
    assert scan is before   # nothing to say: the very node


@pytest.mark.parametrize("fields,want", [
    (FIELDS, "g"),                                   # smallint, 2 bytes
    ([("s", T.STRING), ("x", T.LONG), ("y", T.INT), ("z", T.DATE)], "y"),
    ([("s", T.STRING), ("t", T.STRING)], "s"),       # no fixed width: first
])
def test_no_column_aggregate_keeps_exactly_one_column(fields, want):
    schema = T.Schema([T.StructField(n, t, True) for n, t in fields])
    scan = L.Scan("parquet", ["/nowhere/x"], schema)
    count = A.AggregateExpression(A.Count(), "n")
    out = prune_columns(L.Aggregate(scan, [], [count]))
    pruned, = _scans(out)
    assert pruned.projected == [want]
    assert isinstance(out, L.Aggregate) and out.children == [pruned]


def test_input_file_columns_survive_and_are_never_the_row_count_column():
    named = L.Project(L.Filter(_scan(), col("a") > lit(3)),
                      [col("b"), InputFileName().alias("file"),
                       InputFileBlockStart().alias("start")])
    scan, = _scans(prune_columns(rewrite_input_file_exprs(named)))
    assert scan.projected == ["a", "b", FILE_NAME_COL, FILE_START_COL]
    assert scan.emit_file_meta is True
    # count(*) over a scan that emits them reads a column of the FILE
    hidden, = _scans(rewrite_input_file_exprs(named))
    assert hidden.emit_file_meta and len(hidden.schema) == len(FIELDS) + 3
    count = L.Aggregate(hidden, [], [A.AggregateExpression(A.Count(), "n")])
    scan, = _scans(prune_columns(count))
    assert scan.projected == ["g"] and scan.emit_file_meta is True


def test_scan_copy_carries_options_and_filters_and_leaves_the_original():
    original = _scan(fmt="csv")
    original.pushed_filters = [col("a") > lit(3)]
    plan = L.Aggregate(original, [], [_sum("b")])
    out = prune_columns(plan)
    scan, = _scans(out)
    assert scan is not original and original.projected is None
    assert (scan.fmt, scan.paths) == (original.fmt, original.paths)
    assert scan.options is original.options
    assert scan.pushed_filters == original.pushed_filters
    assert scan._schema is original._schema
    assert not hasattr(scan, "emit_file_meta")
    assert plan.children == [original]   # the input tree is untouched
    # a scan somebody already projected is narrowed within its projection
    half = L.Scan("parquet", ["/nowhere/x"], original._schema,
                  projected=["a", "b", "s"])
    scan, = _scans(prune_columns(L.Aggregate(half, [], [_sum("b")])))
    assert scan.projected == ["b"] and half.projected == ["a", "b", "s"]


# -- through the session, over real files -----------------------------------

@pytest.fixture()
def wide_parquet(tmp_path):
    path = str(tmp_path / "wide.parquet")
    pq.write_table(wide_table(), path, row_group_size=1000)
    return path


def test_dataframe_keeps_its_scan_over_two_different_collects(wide_parquet):
    s = tpu_session()
    df = s.read.parquet(wide_parquet)
    own = df._plan
    first = df.group_by().agg(_sum("c01")).collect()
    assert s.last_query_profile().totals()["scanColumnChunksDecoded"] == 3
    second = wide_query(df).collect()
    assert s.last_query_profile().totals()["scanColumnChunksDecoded"] == 12
    assert df._plan is own and own.projected is None
    assert len(own.schema) == 16
    table = wide_table()
    assert first.column("total")[0].as_py() == sum(
        table.column("c01").to_pylist())
    assert second.num_rows == sum(
        v >= 250 for v in table.column("c01").to_pylist())
    # and a third collect of whole rows still reads every column
    assert df.where(col("c01") >= lit(0)).collect().equals(table)
    assert s.last_query_profile().totals()["scanColumnChunksDecoded"] == 48


def test_count_star_decodes_one_column_a_row_group(wide_parquet):
    s = tpu_session()
    got = s.read.parquet(wide_parquet).group_by().agg(
        A.AggregateExpression(A.Count(), "n")).collect()
    assert got.column("n").to_pylist() == [3000]
    assert s.last_query_profile().totals()["scanColumnChunksDecoded"] == 3


def test_host_scan_reads_only_referenced_columns(wide_parquet, monkeypatch):
    s = tpu_session(**{"spark.rapids.sql.parquet.deviceDecode.enabled": False})
    assert_scan_reads_only_referenced(s, s.read.parquet(wide_parquet), 3,
                                      "CpuFileScan", monkeypatch)


def test_cache_of_a_derived_frame_holds_what_it_selected(wide_parquet):
    s = tpu_session()
    df = s.read.parquet(wide_parquet)
    cached = wide_query(df).cache()
    load = s.last_load_profile().totals()
    assert load["scanColumnChunksDecoded"] == 12
    assert cached.collect().equals(wide_query(df).collect())
    # cache() of the bare scan: every column, as before
    whole = df.cache()
    assert s.last_load_profile().totals()["scanColumnChunksDecoded"] == 48
    assert whole.collect().equals(wide_table())
