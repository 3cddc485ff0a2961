"""Nulls in answers, as SQL has them (CPU, no engine): `pytest benchmarks/`.

A null cell of an engine answer (arrow validity false) is right only against
a null of the reference, a non-null one only against a non-null; a NaN or an
infinity that the engine gives as a value is never right. A reference marks
a null as NaN in a column of floats (a nullable integer or date comes back
from ``columns.load_columns`` as float64) and as None in a column of
strings."""

import datetime
import types

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

import columns
import compare

NAN = float("nan")


def old_to_numpy(column) -> np.ndarray:
    """``columns.to_numpy`` as it was before it read strings with nulls."""
    arr = column.combine_chunks() if isinstance(column, pa.ChunkedArray) \
        else column
    if pa.types.is_string(arr.type) or pa.types.is_large_string(arr.type):
        enc = arr.dictionary_encode()
        words = np.asarray(enc.dictionary.to_pylist(), dtype=np.str_)
        return words[enc.indices.to_numpy(zero_copy_only=False)]
    if pa.types.is_date32(arr.type):
        arr = arr.cast(pa.int32())
    return arr.to_numpy(zero_copy_only=False)


# kind -> (arrow column of three rows, the second null; the reference's
# column for the same rows; a value that is not null, for the second row,
# in the engine's form and in the reference's)
KINDS = {
    "float64": (pa.array([1.5, None, 2.25], pa.float64()),
                np.array([1.5, NAN, 2.25]), 7.0, 7.0),
    "int64": (pa.array([3, None, 5], pa.int64()),
              np.array([3.0, NAN, 5.0]), 4, 4.0),
    "date32": (pa.array([9204, None, 9300], pa.int32()).cast(pa.date32()),
               np.array([9204.0, NAN, 9300.0]),
               datetime.date(1995, 3, 16), 9205.0),
    "string": (pa.array(["a", None, "b"]),
               np.array(["a", None, "b"], object), "c", "c"),
}


def _answer(column: pa.Array) -> compare.Answer:
    return compare.answer_columns(pa.table(
        {"k": pa.array([1, 2, 3], pa.int64()), "v": column}))


def _reference(values: np.ndarray, ordered: bool) -> dict:
    """The reference's rows for keys 1, 2, 3; reversed where the answer is
    unordered, so that only a sort by the keys lines them up."""
    want = {"k": np.array([1, 2, 3], np.int64), "v": values}
    return want if ordered else {n: a[::-1] for n, a in want.items()}


def _with_second(column: pa.Array, value) -> pa.Array:
    values = column.to_pylist()
    values[1] = value
    return pa.array(values, column.type)


@pytest.mark.parametrize("ordered", [True, False])
@pytest.mark.parametrize("kind", KINDS)
def test_null_against_null_is_right(kind, ordered):
    column, want, _, _ = KINDS[kind]
    assert compare.gap(_answer(column), _reference(want, ordered),
                       ordered) == (0, 0.0)


@pytest.mark.parametrize("ordered", [True, False])
@pytest.mark.parametrize("kind", KINDS)
def test_null_against_a_value_is_wrong(kind, ordered):
    column, want, _, reference_value = KINDS[kind]
    valued = want.copy()
    valued[1] = reference_value
    wrong, _ = compare.gap(_answer(column), _reference(valued, ordered),
                           ordered)
    assert wrong >= 1


@pytest.mark.parametrize("ordered", [True, False])
@pytest.mark.parametrize("kind", KINDS)
def test_a_value_against_null_is_wrong(kind, ordered):
    column, want, engine_value, _ = KINDS[kind]
    got = _answer(_with_second(column, engine_value))
    assert "v" not in got.null
    wrong, _ = compare.gap(got, _reference(want, ordered), ordered)
    assert wrong >= 1


@pytest.mark.parametrize("ordered", [True, False])
@pytest.mark.parametrize("value", [NAN, float("inf"), -float("inf")])
def test_an_engine_nan_or_infinity_is_never_right(value, ordered):
    """A non-null NaN against the reference's null, and against a number."""
    column, want, _, _ = KINDS["float64"]
    got = _answer(_with_second(column, value))
    assert compare.gap(got, _reference(want, ordered), ordered)[0] == 1
    valued = want.copy()
    valued[1] = value if np.isinf(value) else 7.0
    assert compare.gap(got, _reference(valued, ordered), ordered)[0] == 1


@pytest.mark.parametrize("chunked", [False, True])
def test_a_string_column_with_nulls_reads_back(tmp_path, chunked):
    column = pa.array(["x", None, "y", "x", None])
    if chunked:
        column = pa.chunked_array([column[:2], column[2:]])
    got = columns.to_numpy(column)
    assert got.dtype == object
    assert got.tolist() == ["x", None, "y", "x", None]
    assert columns.to_numpy(pa.array([None, None], pa.string())).tolist() \
        == [None, None]
    path = str(tmp_path / "t.parquet")
    pq.write_table(pa.table({"s": column, "n": pa.array(
        [1, None, 3, 4, 5], pa.int64())}), path)
    read = columns.load_columns(path, ["s", "n"])
    assert read["s"].tolist() == ["x", None, "y", "x", None]
    assert np.array_equal(read["n"], [1, NAN, 3, 4, 5], equal_nan=True)


def test_an_unordered_answer_is_sorted_by_a_nullable_integer_key():
    got = compare.answer_columns(pa.table({
        "k": pa.array([3, None, 1], pa.int64()),
        "v": pa.array([30.0, 0.5, 10.0])}))
    assert "k" in got.exact and got["k"].dtype.kind == "f"
    want = {"k": np.array([NAN, 1.0, 3.0]), "v": np.array([0.5, 10.0, 30.0])}
    assert compare.gap(got, want, False) == (0, 0.0)
    swapped = {"k": want["k"], "v": np.array([10.0, 0.5, 30.0])}
    assert compare.gap(got, swapped, False) == (0, 19.0)   # 9.5 / 0.5
    # nulls first, as Spark's ascending order has them
    cols, null = compare._by_keys(dict(got), {"k": got.null["k"],
                                              "v": np.zeros(3, bool)}, ["k"])
    assert null["k"].tolist() == [True, False, False]
    assert cols["v"].tolist() == [0.5, 10.0, 30.0]


@pytest.mark.parametrize("column", [
    pa.array([1.5, NAN, -0.0], pa.float64()),
    pa.array([1.5, 2.5], pa.float32()),
    pa.array([7, -1, 2 ** 40], pa.int64()),
    pa.array([7, -1], pa.int32()),
    pa.array([9204, 0], pa.int32()).cast(pa.date32()),
    pa.array(["R", "A", "N", "A"]),
    pa.array(["R", "A"], pa.large_string()),
    pa.array([True, False]),
    pa.chunked_array([pa.array([1.0, 2.0]), pa.array([3.0])]),
    pa.chunked_array([pa.array(["b", "a"]), pa.array(["a"])]),
], ids=lambda c: str(c.type) + ("_chunked" if isinstance(
    c, pa.ChunkedArray) else ""))
def test_a_column_without_nulls_converts_as_before(column):
    got, old = columns.to_numpy(column), old_to_numpy(column)
    assert got.dtype == old.dtype
    assert got.tobytes() == old.tobytes()


def _q7_cell():
    query = types.SimpleNamespace(ORDERED=True, REL_GAP_LIMIT=1e-10)
    return {"queries": {"q7": query},
            "mix": [{"name": "q7", "query": "q7", "params": {}}]}


def _q7(agg1, agg4) -> pa.Table:
    return pa.table({"i_item_id": ["AAAA", "AAAB", "AAAC"],
                     "agg1": pa.array(agg1, pa.float64()),
                     "agg2": [2.0, 3.0, 4.0], "agg3": [5.5, 6.5, 7.5],
                     "agg4": pa.array(agg4, pa.float64())})


Q7_REFERENCE = {"i_item_id": np.array(["AAAA", "AAAB", "AAAC"]),
                "agg1": np.array([1.0, NAN, 3.0]),
                "agg2": np.array([2.0, 3.0, 4.0]),
                "agg3": np.array([5.5, 6.5, 7.5]),
                "agg4": np.array([NAN, 12.25, NAN])}


@pytest.mark.parametrize("agg1,agg4,correct", [
    ([1.0, None, 3.0], [None, 12.25, None], True),
    ([1.0, 2.0, 3.0], [None, 12.25, None], False),     # a number for null
    ([1.0, None, 3.0], [NAN, 12.25, None], False),     # a NaN for null
    ([1.0, None, NAN], [None, 12.25, None], False),    # a NaN for 3.0
], ids=["nulls_match", "number_for_null", "nan_for_null", "nan_for_number"])
def test_a_q7_shaped_answer(agg1, agg4, correct):
    """(i_item_id, agg1..agg4), ordered: an avg over no non-null value is
    null in SQL, NaN in the reference."""
    got = compare.answer_columns(_q7(agg1, agg4))
    ok, compared = compare.judge([("q7", got)], {"q7": Q7_REFERENCE},
                                 _q7_cell(), {})
    assert ok is correct, compared
    assert compared["rel_gap.q7"]["value"] == 0.0
