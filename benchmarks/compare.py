"""The comparison that decides ``correct``: every answer of the window
against the plain reference's answer for that query.

Numbers compared, each with a limit of its own (``LIMITS``; where the
readings they were set from are kept: PERF.md, section 2):

``wrong_cells``  rows missing or extra, and cells of exact type (keys,
                 counts, dates, strings) that differ, over all answers.
``rel_gap.<q>``  per query of the cell, the widest gap of a floating-point
                 cell of its answers from the reference's, as a share of
                 the reference's magnitude; its limit is the query file's
                 ``REL_GAP_LIMIT``.
``failed``       queries that raised (a planned CPU operator or a row group
                 the device could not decode raises under ``test.enabled``).
``host_row_groups``  row groups read on the host, in set-up and window.
others           what the configuration's loader holds its residence to
                 (``loaders/<l>.py:compared``), each with its limit.
"""

import numpy as np
import pyarrow as pa

import columns


def answer_columns(table: pa.Table) -> dict:
    """An engine answer as {column: numpy array}, converted like the
    reference's inputs (``columns.to_numpy``)."""
    return {name: columns.to_numpy(table.column(name))
            for name in table.column_names}


def _is_real(a) -> bool:
    return a.dtype.kind == "f"


def _by_keys(cols: dict) -> dict:
    """Rows sorted by their exact-typed columns (an unordered answer)."""
    keys = [cols[n] for n in reversed(list(cols)) if not _is_real(cols[n])]
    if not keys:
        return cols
    order = np.lexsort(keys)
    return {n: a[order] for n, a in cols.items()}


def gap(got: dict, want: dict, ordered: bool):
    """(wrong_cells, rel_gap) of one answer against the reference's."""
    if list(got) != list(want):
        return max(len(got), len(want)), 0.0
    n_got = len(next(iter(got.values()))) if got else 0
    n_want = len(next(iter(want.values()))) if want else 0
    if n_got != n_want:
        return abs(n_got - n_want) * max(len(want), 1), 0.0
    if not ordered:
        got, want = _by_keys(got), _by_keys(want)
    wrong, rel = 0, 0.0
    for name, w in want.items():
        g = got[name]
        if _is_real(w):
            if not _is_real(g):
                wrong += len(w)
                continue
            w64, g64 = w.astype(np.float64), g.astype(np.float64)
            bad = ~np.isfinite(g64)
            wrong += int(bad.sum())
            scale = np.maximum(np.abs(w64), np.finfo(np.float64).tiny)
            gaps = (np.abs(g64 - w64) / scale)[~bad]
            if len(gaps):
                rel = max(rel, float(gaps.max()))
        else:
            wrong += int(np.sum(g != w)) if g.dtype.kind == w.dtype.kind \
                else len(w)
    return wrong, rel


def judge(answers, references: dict, cell: dict, held: dict):
    """(correct, compared): ``answers`` is [(entry of the mix, columns)] for
    every query the window finished, ``references`` {entry: columns},
    ``held`` {name: (value, limit)} of the exact counts. ``compared`` holds
    each number beside its limit."""
    module = {e["name"]: cell["queries"][e["query"]] for e in cell["mix"]}
    wrong, rel = 0, {name: 0.0 for name in module}
    for name, cols in answers:
        w, r = gap(cols, references[name], module[name].ORDERED)
        wrong, rel[name] = wrong + w, max(rel[name], r)
    compared = {"wrong_cells": {"value": wrong, "limit": 0}}
    for name, value in rel.items():
        compared[f"rel_gap.{name}"] = {
            "value": value, "limit": module[name].REL_GAP_LIMIT}
    for name, (value, limit) in held.items():
        compared[name] = {"value": value, "limit": limit}
    correct = bool(answers) and all(
        c["value"] <= c["limit"] for c in compared.values())
    return correct, compared
