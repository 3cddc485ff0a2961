"""Differential query harness — the SparkQueryCompareTestSuite /
assert_gpu_and_cpu_are_equal_collect analog (reference
SparkQueryCompareTestSuite.scala:54, asserts.py:28).

Every test builds a DataFrame via a lambda and runs it twice: once with
``spark.rapids.sql.enabled=false`` (pure CPU oracle) and once with ``=true``
plus ``spark.rapids.sql.test.enabled=true`` so any unexpected CPU fallback is
a hard failure. Results compare as row multisets (optionally ordered), with
NaN/null awareness and optional float tolerance.
"""

from __future__ import annotations

import math
from typing import Callable, Optional

import numpy as np
import pyarrow as pa

from spark_rapids_tpu.session import TpuSession

#: Device-tier run (pytest --tpu): the real TPU emulates f64 with ~1 ulp
#: of upload error, so float comparisons get a default tolerance — the
#: reference documents the same float-compare stance for its GPU runs
#: (docs/compatibility.md:31-66).
import os

ON_TPU = os.environ.get("SRTPU_TEST_TPU") == "1"
DEVICE_FLOAT_TOL = 1e-6

_CPU = None
_TPU_BASE = None


def cpu_session() -> TpuSession:
    global _CPU
    if _CPU is None:
        _CPU = TpuSession({"spark.rapids.sql.enabled": False})
    return _CPU


def tpu_session(**conf) -> TpuSession:
    global _TPU_BASE
    if _TPU_BASE is None:
        _TPU_BASE = TpuSession({
            "spark.rapids.sql.enabled": True,
            "spark.rapids.sql.test.enabled": True,
        })
    if conf:
        return _TPU_BASE.with_conf(**conf)
    return _TPU_BASE


def _canonical_rows(table: pa.Table):
    rows = []
    for row in zip(*[table.column(i).to_pylist()
                     for i in range(table.num_columns)]):
        rows.append(tuple(_canon(v) for v in row))
    return rows


def _canon(v):
    if isinstance(v, float):
        if math.isnan(v):
            return ("NaN",)
        if v == 0.0:
            return 0.0  # -0.0 == 0.0
        return v
    if isinstance(v, list):  # array column values
        return tuple(_canon(x) for x in v)
    if isinstance(v, dict):  # struct column values
        return tuple((k, _canon(x)) for k, x in sorted(v.items()))
    return v


def _rows_equal(a, b, approx: Optional[float]) -> bool:
    if len(a) != len(b):
        return False
    for x, y in zip(a, b):
        if x == y:
            continue
        if approx is not None and isinstance(x, float) and isinstance(y, float):
            if math.isclose(x, y, rel_tol=approx, abs_tol=1e-12):
                continue
        return False
    return True


def assert_tpu_and_cpu_are_equal(
        df_fn: Callable[[TpuSession], "object"],
        ignore_order: bool = True,
        approx: Optional[float] = None,
        conf: Optional[dict] = None,
        allowed_non_tpu: Optional[list] = None):
    """Run df_fn under both sessions and compare collected results."""
    if approx is None and ON_TPU:
        approx = DEVICE_FLOAT_TOL
    extra = dict(conf or {})
    if allowed_non_tpu:
        extra["spark.rapids.sql.test.allowedNonTpu"] = ",".join(allowed_non_tpu)
    cpu_result = df_fn(cpu_session()).collect()
    tpu_result = df_fn(tpu_session(**extra)).collect()
    assert cpu_result.schema.equals(tpu_result.schema), \
        f"schema mismatch:\nCPU: {cpu_result.schema}\nTPU: {tpu_result.schema}"
    cpu_rows = _canonical_rows(cpu_result)
    tpu_rows = _canonical_rows(tpu_result)
    if ignore_order:
        key = lambda r: tuple((x is None, ("NaN",) == x if isinstance(x, tuple)
                               else False, str(x)) for x in r)
        cpu_rows = sorted(cpu_rows, key=key)
        tpu_rows = sorted(tpu_rows, key=key)
    assert len(cpu_rows) == len(tpu_rows), \
        f"row count: CPU {len(cpu_rows)} vs TPU {len(tpu_rows)}"
    for i, (c, t) in enumerate(zip(cpu_rows, tpu_rows)):
        if not _rows_equal(c, t, approx):
            raise AssertionError(
                f"row {i} differs:\nCPU: {c}\nTPU: {t}")


# -- the projection reaches the scan (plan/optimizer.py) --------------------

WIDE_COLUMNS = 16
#: the four columns `wide_query` references, in file order
WIDE_REFERENCED = ["c01", "c06", "c10", "c15"]


def wide_table(rows: int = 3000, seed: int = 11) -> pa.Table:
    """A table of 16 columns, c00..c15: bigint, double, double, string in
    turn, so a query for four of them leaves every type unread too."""
    rng = np.random.default_rng(seed)
    words = np.array(["ash", "birch", "cedar", "elm", "fir", "oak"])
    cols = {}
    for i in range(WIDE_COLUMNS):
        name = f"c{i:02d}"
        if i % 4 == 0:
            cols[name] = rng.integers(-10**9, 10**9, rows)
        elif i % 4 == 3:
            cols[name] = pa.array(words[rng.integers(0, len(words), rows)])
        elif i % 4 == 1:
            cols[name] = rng.integers(0, 1000, rows).astype(np.int64)
        else:
            cols[name] = np.round(rng.normal(scale=100.0, size=rows), 3)
    return pa.table(cols)


def wide_query(df):
    """Whole rows of 4 of the 16 columns, in file order of the rows."""
    from spark_rapids_tpu.ops.expression import col, lit
    return df.where(col("c01") >= lit(250)).select(
        *[col(n) for n in WIDE_REFERENCED])


def assert_scan_reads_only_referenced(session, df, units: int, scan: str,
                                      monkeypatch):
    """``wide_query`` over ``df`` (a 16-column file of ``units`` row
    groups, stripes, slices or batches) plans ``scan``, decodes 4 columns a
    unit, and returns the very table the unpruned plan — a Project above a
    scan of all 16, as before the projection reached the scan — returns."""
    from spark_rapids_tpu.plan import optimizer
    query = wide_query(df)
    line = [ln for ln in session.explain(query._plan).splitlines()
            if scan in ln]
    assert line and "columns=4/16" in line[0], session.explain(query._plan)
    got = query.collect()
    totals = session.last_query_profile().totals()
    assert totals["scanColumnChunksDecoded"] == units * 4
    monkeypatch.setattr(optimizer, "_project_scan", lambda scan, req: scan)
    assert "columns=16/16" in session.explain(query._plan)
    want = query.collect()
    totals = session.last_query_profile().totals()
    assert totals["scanColumnChunksDecoded"] == units * WIDE_COLUMNS
    assert got.num_rows and got.equals(want)
    assert df._plan.projected is None
