"""Shared result comparison for benchmarks, dryruns, and workload tests:
full-row multiset compare with float tolerance (XLA reduction order and the
TPU's emulated float64 legitimately differ from sequential pyarrow)."""

from __future__ import annotations

import math

import pyarrow as pa


def rows(table: pa.Table) -> list:
    out = []
    for row in zip(*[table.column(i).to_pylist()
                     for i in range(table.num_columns)]):
        out.append(tuple(row))
    return sorted(out, key=str)


def values_close(a, b, rel_tol: float, abs_tol: float) -> bool:
    if isinstance(a, float) and isinstance(b, float):
        if math.isnan(a) and math.isnan(b):
            return True
        return math.isclose(a, b, rel_tol=rel_tol, abs_tol=abs_tol)
    return a == b


def rows_match(a: list, b: list, rel_tol: float = 1e-6,
               abs_tol: float = 1e-6) -> bool:
    if len(a) != len(b):
        return False
    for ra, rb in zip(a, b):
        if len(ra) != len(rb):
            return False
        for va, vb in zip(ra, rb):
            if not values_close(va, vb, rel_tol, abs_tol):
                return False
    return True


def tables_match(got: pa.Table, want: pa.Table, rel_tol: float = 1e-6,
                 abs_tol: float = 1e-6) -> bool:
    return rows_match(rows(got), rows(want), rel_tol, abs_tol)
