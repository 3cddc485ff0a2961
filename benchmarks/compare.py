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

Nulls as SQL has them: a null cell of an answer is right only where the
reference's is null, and a non-null cell only where the reference's is
not. A reference marks a null as NaN in a column of floats (a nullable
integer or date column comes from ``columns.load_columns`` as float64
with NaN) and as None in a column of strings; an engine answer carries
arrow's validity (``answer_columns``), so a NaN or an infinity that the
engine gives as a value is wrong whatever the reference holds.
"""

import numpy as np
import pyarrow as pa

import columns


class Answer(dict):
    """An engine answer: {column: numpy array} as ``columns.to_numpy``
    gives it, with what that conversion loses, read from arrow before it:
    ``null``, {column: bool array} of the null cells of every column that
    holds one, and ``exact``, the columns whose type is not floating point
    (a nullable integer or date column comes back as float64)."""

    null: dict
    exact: set


def answer_columns(table: pa.Table) -> Answer:
    """An engine answer as {column: numpy array}, converted like the
    reference's inputs (``columns.to_numpy``), with each column's
    validity."""
    out = Answer((name, columns.to_numpy(table.column(name)))
                 for name in table.column_names)
    out.null = {name: np.asarray(table.column(name).is_null())
                for name in table.column_names
                if table.column(name).null_count}
    out.exact = {f.name for f in table.schema
                 if not pa.types.is_floating(f.type)}
    return out


def _is_real(a) -> bool:
    return a.dtype.kind == "f"


def _null(cols: dict, name: str) -> np.ndarray:
    """The null cells of a column: arrow's validity in an engine answer;
    in an answer made by a reference (or by the control), NaN in a column
    of floats and None in a column of objects."""
    a = cols[name]
    if isinstance(cols, Answer):
        return cols.null.get(name, np.zeros(len(a), bool))
    if a.dtype.kind == "f":
        return np.isnan(a)
    if a.dtype.kind == "O":
        return np.array([v is None for v in a], bool)
    return np.zeros(len(a), bool)


def _filled(a: np.ndarray, null: np.ndarray) -> np.ndarray:
    """An exact column with every null made alike (0, or '' in a column of
    objects, which holds strings), so that nulls compare equal and sort
    together."""
    if a.dtype.kind == "O":
        return np.array(["" if n else v for v, n in zip(a, null)], np.str_)
    return np.where(null, 0, a) if null.any() else a


def _by_keys(cols: dict, null: dict, keys: list):
    """(columns, nulls) with the rows sorted by the exact-typed columns
    ``keys``, nulls first as Spark's ascending order has them (an
    unordered answer)."""
    order_by = []
    for name in reversed(keys):     # np.lexsort: the last key leads
        order_by.append(_filled(cols[name], null[name]))
        if null[name].any():
            order_by.append(~null[name])
    if not order_by:
        return cols, null
    order = np.lexsort(order_by)
    return ({n: a[order] for n, a in cols.items()},
            {n: m[order] for n, m in null.items()})


def gap(got: dict, want: dict, ordered: bool):
    """(wrong_cells, rel_gap) of one answer against the reference's.

    A null cell is right only against a null; a non-null NaN or infinity
    in an engine answer is wrong whatever the reference holds (no query
    here yields one); null cells add nothing to ``rel_gap``."""
    if list(got) != list(want):
        return max(len(got), len(want)), 0.0
    n_got = len(next(iter(got.values()))) if got else 0
    n_want = len(next(iter(want.values()))) if want else 0
    if n_got != n_want:
        return abs(n_got - n_want) * max(len(want), 1), 0.0
    typed = got.exact if isinstance(got, Answer) else ()
    exact = [n for n in want
             if n in typed or not _is_real(got[n]) or not _is_real(want[n])]
    got_null = {n: _null(got, n) for n in got}
    want_null = {n: _null(want, n) for n in want}
    if not ordered:
        got, got_null = _by_keys(got, got_null, exact)
        want, want_null = _by_keys(want, want_null, exact)
    wrong, rel = 0, 0.0
    for name, w in want.items():
        g, g_null, w_null = got[name], got_null[name], want_null[name]
        if name not in exact:
            w64, g64 = w.astype(np.float64), g.astype(np.float64)
            bad = (g_null != w_null) | (~g_null & ~np.isfinite(g64))
            wrong += int(bad.sum())
            both = ~bad & ~w_null
            w64, g64 = w64[both], g64[both]
            scale = np.maximum(np.abs(w64), np.finfo(np.float64).tiny)
            gaps = np.abs(g64 - w64) / scale
            if len(gaps):
                rel = max(rel, float(gaps.max()))
        else:
            g, w = _filled(g, g_null), _filled(w, w_null)
            wrong += int(np.sum((g_null != w_null) | (g != w))) \
                if g.dtype.kind == w.dtype.kind else len(w)
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
