"""``LIKE`` over a FLAT string column at a comment's width (ISSUE 34):
TPC-H Q13's ``'%special%requests%'`` as the parquet decoder's flat columns
meet it, against ``pyarrow.compute.match_like``, and the window compares
against the wildcard walk they stand in for."""

import numpy as np
import pyarrow as pa
import pyarrow.compute as pc
import pytest

from spark_rapids_tpu import types as T
from spark_rapids_tpu.ops import strings as S
from spark_rapids_tpu.ops.expression import col

def _flat_strings(values, capacity=None):
    """A FLAT device string column (payload + offsets, no dictionary), as
    the parquet decoder makes one of PLAIN byte-array pages."""
    from spark_rapids_tpu.data.batch import ColumnarBatch
    from spark_rapids_tpu.data.column import (DeviceColumn,
                                              bucket_capacity)
    import jax.numpy as jnp
    arr = pa.array(values, pa.string())
    filled = arr.fill_null("")
    offsets = np.frombuffer(filled.buffers()[1], np.int32)[:len(arr) + 1]
    data = np.frombuffer(filled.buffers()[2], np.uint8)
    column = DeviceColumn.string_from_host(
        offsets, data, np.asarray(arr.is_valid()),
        capacity or bucket_capacity(len(arr)))
    schema = T.Schema([T.StructField("t", T.STRING, True)])
    return arr, ColumnarBatch((column,), jnp.asarray(len(arr), jnp.int32),
                              schema)


def _q13_comments():
    rng = np.random.default_rng(13)
    words = ["special", "requests", "packages", "pending", "spec", "ial",
             "request", "s", " ", ". ", "furiously ", "special requests"]
    texts = ["".join(rng.choice(words, rng.integers(0, 12)))[:79]
             for _ in range(3000)]
    return texts + [
        "special requests", "requests special",     # the other order
        "specialrequests", "speciarequests", "specialequests",
        "special" + "x" * 64 + "requests",          # both ends, 79 bytes
        "requests" + "y" * 64 + "special",
        "special" * 11, "requests" * 9,
        "a special deposits sleep requests.", None, "",
        "spécial requests", "special rëquests special requests"]


@pytest.mark.parametrize("pattern", [
    "%special%requests%", "special%requests", "%special%requests",
    "special%requests%", "%s%s%s%", "%special requests%special%",
    "a\\%b", "%é%requests%"])
def test_like_over_a_flat_column_matches_pyarrow(pattern):
    """Q13's predicate over a flat column at varchar(79): the two literals
    in order, not overlapping; a null stays null."""
    arr, batch = _flat_strings(_q13_comments())
    column = batch.columns[0]
    assert not column.is_dict and column.max_bytes >= 79
    out = S.Like(col("t"), pattern).bind(batch.schema).eval_device(batch)
    n = len(arr)
    want = pc.match_like(arr, pattern)
    assert np.array_equal(np.asarray(out.validity)[:n],
                          np.asarray(want.is_valid()))
    got = np.asarray(out.data)[:n] & np.asarray(out.validity)[:n]
    assert np.array_equal(
        got, want.fill_null(False).to_numpy(zero_copy_only=False))
    if pattern == "%special%requests%":
        by_text = dict(zip(arr.to_pylist(), got.tolist()))
        assert by_text["special requests"] and by_text["specialrequests"]
        assert not by_text["requests special"]
        assert not by_text["specialequests"]        # the words overlap
        assert by_text["special" + "x" * 64 + "requests"]
        assert not by_text["requests" + "y" * 64 + "special"]


@pytest.mark.parametrize("pattern", ["%special%requests%", "sp%ial%",
                                     "%req%sts", "%s% %s%"])
def test_like_literals_is_the_wildcard_walk(pattern):
    """The window compares over the bytes as they lie give the answer of
    the W x P walk over the char matrix, for every row."""
    from spark_rapids_tpu.ops.strings_util import _matrix_from_offsets
    arr, batch = _flat_strings(_q13_comments())
    column = batch.columns[0]
    toks = S.Like(col("t"), pattern).tokens()
    w = column.max_bytes
    offsets = column.offsets
    walked = S._like_dp(_matrix_from_offsets(column.data, offsets, w), toks)
    windows = S._like_literals(column.data, offsets, toks, w)
    assert np.array_equal(np.asarray(walked), np.asarray(windows))
