"""Unit tests for the device row kernels (sort/compact/gather/groupby/join),
validated against numpy/pandas oracles — the analog of the reference's
runtime-internals suites (GpuPartitioningSuite, HashAggregatesSuite internals).
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pandas as pd
import pyarrow as pa
import pytest

from spark_rapids_tpu import types as T
from spark_rapids_tpu.data.batch import ColumnarBatch, HostBatch
from spark_rapids_tpu.data.column import DeviceColumn, bucket_capacity
from spark_rapids_tpu.ops.kernels import groupby as G
from spark_rapids_tpu.ops.kernels import join as J
from spark_rapids_tpu.ops.kernels import rowops as R
from spark_rapids_tpu.ops.kernels.concat import concat_batches
from spark_rapids_tpu.ops.strings_util import char_matrix, lengths
from spark_rapids_tpu.shuffle import partitioning as SP

from datagen import FloatGen, IntGen, StringGen, gen_batch


def make_device(data: dict) -> ColumnarBatch:
    return HostBatch.from_pydict(data).to_device()


class TestCompact:
    def test_compact_basic(self):
        db = make_device({"a": [1, 2, 3, 4, 5], "b": list("vwxyz")})
        keep = jnp.asarray([True, False, True, False, True] + [False] * (db.capacity - 5))
        out = R.compact(db, keep)
        rb = out.to_arrow()
        assert rb.column(0).to_pylist() == [1, 3, 5]
        assert rb.column(1).to_pylist() == ["v", "x", "z"]

    def test_compact_keeps_nulls(self):
        db = make_device({"a": [1, None, 3, None]})
        keep = jnp.asarray([True, True, False, True] + [False] * (db.capacity - 4))
        out = R.compact(db, keep)
        assert out.to_arrow().column(0).to_pylist() == [1, None, None]


class TestSort:
    @pytest.mark.parametrize("asc", [True, False])
    @pytest.mark.parametrize("nf", [True, False])
    def test_sort_ints_with_nulls(self, asc, nf):
        vals = [5, None, 3, 8, None, 1, -7]
        db = make_device({"a": vals})
        out = R.sort_batch(db, [0], [asc], [nf])
        got = out.to_arrow().column(0).to_pylist()
        nn = sorted([v for v in vals if v is not None], reverse=not asc)
        nulls = [None, None]
        assert got == (nulls + nn if nf else nn + nulls)

    def test_sort_floats_total_order(self):
        vals = [1.5, float("nan"), -0.0, 0.0, float("-inf"), float("inf"), -2.25]
        db = make_device({"a": vals})
        out = R.sort_batch(db, [0], [True], [True])
        got = out.to_arrow().column(0).to_pylist()
        # Spark float order: -inf < ... < inf < NaN; -0.0/0.0 stable-equal.
        assert got[0] == float("-inf")
        assert np.isnan(got[-1])
        assert got[1:6] == [-2.25, -0.0, 0.0, 1.5, float("inf")]

    def test_sort_strings(self):
        vals = ["pear", "", None, "apple", "apples", "b"]
        db = make_device({"s": vals})
        out = R.sort_batch(db, [0], [True], [True])
        assert out.to_arrow().column(0).to_pylist() == \
            [None, "", "apple", "apples", "b", "pear"]

    def test_multikey_stable(self):
        db = make_device({"k": [1, 2, 1, 2, 1], "v": [9, 8, 7, 6, 5]})
        out = R.sort_batch(db, [0, 1], [True, False], [True, True])
        rb = out.to_arrow()
        assert rb.column(0).to_pylist() == [1, 1, 1, 2, 2]
        assert rb.column(1).to_pylist() == [9, 7, 5, 8, 6]


class TestGroupBy:
    def _group_sum(self, data, keys, val):
        db = make_device(data)
        key_cols = [db.column(k) for k in keys]
        seg, n_groups, firsts = G.group_ids(key_cols, db.n_rows)
        vcol = db.column(val)
        out, counts = G.segment_reduce(vcol.data, vcol.validity, seg,
                                       db.capacity, "sum", db.row_mask())
        kcols = G.gather_group_keys(key_cols, firsts, n_groups)
        n = int(n_groups)
        result = {}
        for i in range(n):
            kv = tuple(c.to_arrow(n).to_pylist()[i] for c in kcols)
            result[kv] = np.asarray(out)[i]
        return result

    def test_single_key(self):
        res = self._group_sum({"k": [1, 2, 1, 3, 2, 1], "v": [10, 20, 30, 40, 50, 60]},
                              ["k"], "v")
        assert res == {(1,): 100, (2,): 70, (3,): 40}

    def test_null_key_group(self):
        res = self._group_sum({"k": [1, None, 1, None], "v": [1, 2, 3, 4]},
                              ["k"], "v")
        assert res == {(1,): 4, (None,): 6}

    def test_string_key(self):
        res = self._group_sum({"k": ["a", "bb", "a", None, "bb"],
                               "v": [1, 2, 3, 4, 5]}, ["k"], "v")
        assert res == {("a",): 4, ("bb",): 7, (None,): 4}

    def test_multi_key(self):
        res = self._group_sum(
            {"k1": [1, 1, 2, 2], "k2": ["x", "y", "x", "x"], "v": [1, 2, 3, 4]},
            ["k1", "k2"], "v")
        assert res == {(1, "x"): 1, (1, "y"): 2, (2, "x"): 7}

    def test_null_values_skipped(self):
        db = make_device({"k": [1, 1, 2], "v": [5, None, 7]})
        seg, n_groups, firsts = G.group_ids([db.column("k")], db.n_rows)
        vcol = db.column("v")
        s, counts = G.segment_reduce(vcol.data, vcol.validity, seg,
                                     db.capacity, "sum", db.row_mask())
        assert np.asarray(s)[:2].tolist() == [5, 7]
        assert np.asarray(counts)[:2].tolist() == [1, 1]

    @pytest.mark.parametrize("op,expect", [
        ("min", {(1,): 3, (2,): 2}), ("max", {(1,): 9, (2,): 6}),
        ("count", {(1,): 3, (2,): 2}), ("first", {(1,): 9, (2,): 2}),
        ("last", {(1,): 3, (2,): 6})])
    def test_reduce_ops(self, op, expect):
        db = make_device({"k": [1, 2, 1, 2, 1], "v": [9, 2, 4, 6, 3]})
        key_cols = [db.column("k")]
        seg, n_groups, firsts = G.group_ids(key_cols, db.n_rows)
        vcol = db.column("v")
        out, _ = G.segment_reduce(vcol.data, vcol.validity, seg, db.capacity,
                                  op, db.row_mask())
        kcols = G.gather_group_keys(key_cols, firsts, n_groups)
        n = int(n_groups)
        keys = kcols[0].to_arrow(n).to_pylist()
        got = {(keys[i],): int(np.asarray(out)[i]) for i in range(n)}
        assert got == expect

    def test_fuzz_vs_pandas(self):
        rb = gen_batch({"k1": IntGen(T.INT, lo=0, hi=8),
                        "k2": StringGen(max_len=2),
                        "v": IntGen(T.LONG, lo=-1000, hi=1000)}, n=300, seed=11)
        db = HostBatch(rb).to_device()
        key_cols = [db.column(0), db.column(1)]
        seg, n_groups, firsts = G.group_ids(key_cols, db.n_rows)
        vcol = db.column(2)
        out, counts = G.segment_reduce(vcol.data, vcol.validity, seg,
                                       db.capacity, "sum", db.row_mask())
        kcols = G.gather_group_keys(key_cols, firsts, n_groups)
        n = int(n_groups)
        got = {}
        k1 = kcols[0].to_arrow(n).to_pylist()
        k2 = kcols[1].to_arrow(n).to_pylist()
        for i in range(n):
            cnt = int(np.asarray(counts)[i])
            got[(k1[i], k2[i])] = (int(np.asarray(out)[i]), cnt)
        df = rb.to_pandas()
        exp = {}
        for (a, b), g in df.groupby(["k1", "k2"], dropna=False):
            a = None if pd.isna(a) else int(a)
            b = None if (not isinstance(b, str) and pd.isna(b)) else b
            exp[(a, b)] = (int(g["v"].sum()), int(g["v"].notna().sum()))
        assert got == exp


def run_inner_join(build, probe, n_build, n_probe, out_cap):
    bids, pids = J.dense_key_ids(build, probe, n_build, n_probe)
    lo, counts, perm, sorted_ids = J.match_ranges(bids, pids)
    live_p = jnp.arange(pids.shape[0], dtype=jnp.int32) < n_probe
    counts = jnp.where(live_p, counts, 0)
    p_idx, b_idx, n_out, total = J.expand_matches(lo, counts, perm, out_cap)
    return p_idx, b_idx, int(n_out), int(total)


class TestJoin:
    def test_inner_basic(self):
        b = make_device({"k": [1, 2, 3, 2]})
        p = make_device({"k": [2, 4, 1, 2]})
        p_idx, b_idx, n_out, total = run_inner_join(
            [b.column(0)], [p.column(0)], b.n_rows, p.n_rows, 128)
        pairs = set()
        pk = np.asarray(p.column(0).data)
        bk = np.asarray(b.column(0).data)
        for i in range(n_out):
            pairs.add((int(np.asarray(p_idx)[i]), int(np.asarray(b_idx)[i])))
        # probe row 0 (k=2) matches build rows 1,3; probe row 2 (k=1) matches
        # build 0; probe row 3 (k=2) matches build 1,3.
        assert pairs == {(0, 1), (0, 3), (2, 0), (3, 1), (3, 3)}
        assert total == 5

    def test_null_keys_never_match(self):
        b = make_device({"k": [1, None]})
        p = make_device({"k": [None, 1]})
        p_idx, b_idx, n_out, total = run_inner_join(
            [b.column(0)], [p.column(0)], b.n_rows, p.n_rows, 64)
        assert total == 1
        assert int(np.asarray(p_idx)[0]) == 1 and int(np.asarray(b_idx)[0]) == 0

    def test_string_and_multi_key(self):
        b = make_device({"k1": ["a", "b", "a"], "k2": [1, 1, 2]})
        p = make_device({"k1": ["a", "a", "zz"], "k2": [2, 1, 1]})
        p_idx, b_idx, n_out, total = run_inner_join(
            [b.column(0), b.column(1)], [p.column(0), p.column(1)],
            b.n_rows, p.n_rows, 64)
        pairs = {(int(np.asarray(p_idx)[i]), int(np.asarray(b_idx)[i]))
                 for i in range(n_out)}
        assert pairs == {(0, 2), (1, 0)}

    def test_overflow_reported(self):
        b = make_device({"k": [7, 7, 7, 7]})
        p = make_device({"k": [7, 7]})
        _, _, n_out, total = run_inner_join(
            [b.column(0)], [p.column(0)], b.n_rows, p.n_rows, 4)
        assert total == 8
        assert n_out == 4

    def test_fuzz_vs_pandas(self):
        rb_b = gen_batch({"k": IntGen(T.INT, lo=0, hi=20)}, n=150, seed=5)
        rb_p = gen_batch({"k": IntGen(T.INT, lo=0, hi=20)}, n=100, seed=6)
        b = HostBatch(rb_b).to_device()
        p = HostBatch(rb_p).to_device()
        p_idx, b_idx, n_out, total = run_inner_join(
            [b.column(0)], [p.column(0)], b.n_rows, p.n_rows, 8192)
        got = sorted((int(np.asarray(p_idx)[i]), int(np.asarray(b_idx)[i]))
                     for i in range(n_out))
        # pandas merge matches NaN==NaN; SQL join semantics drop null keys.
        dfb = rb_b.to_pandas().reset_index().rename(columns={"index": "bi"}).dropna()
        dfp = rb_p.to_pandas().reset_index().rename(columns={"index": "pi"}).dropna()
        m = dfp.merge(dfb, on="k")
        exp = sorted((int(r.pi), int(r.bi)) for r in m.itertuples())
        assert got == exp
        assert total == len(exp)

    def test_build_hit_mask(self):
        b = make_device({"k": [1, 2, 3, None]})
        p = make_device({"k": [2, 2, 5]})
        bids, pids = J.dense_key_ids([b.column(0)], [p.column(0)],
                                     b.n_rows, p.n_rows)
        hits = J.build_hit_mask(bids, None, pids, p.n_rows)
        assert np.asarray(hits)[:4].tolist() == [False, True, False, False]


# ---------------------------------------------------------------------------
# The kernels the chip runs, against plain numpy on seeded inputs. The
# shapes are the matrix these kernels were once only checked on through a
# second implementation's comparison with them.
# ---------------------------------------------------------------------------


def _keyed_batch(keys, valid, live, prefix, key_type=T.INT):
    """A lazy two-column batch: int key (nullable), int64 payload that
    names its row."""
    cap = len(keys)
    kcol = DeviceColumn.from_numpy(keys, valid, key_type, cap)
    pay = DeviceColumn.from_numpy(np.arange(cap) * 10 + 1, None, T.LONG, cap)
    schema = T.Schema([T.StructField(prefix + "k", key_type, True),
                       T.StructField(prefix + "v", T.LONG, False)])
    return ColumnarBatch((kcol, pay), jnp.asarray(int(live.sum()), jnp.int32),
                         schema, live=jnp.asarray(live))


def _dense_case(case):
    """(table keys/valid/live, scan keys/valid/live): the table side is
    the one the direct-address table builds over."""
    if case == "one_live_row":
        cap_t = cap_s = 128
        kt = np.zeros(cap_t, np.int64)
        kt[0] = 7
        live_t = np.zeros(cap_t, bool)
        live_t[0] = True
        ks = np.zeros(cap_s, np.int64)
        ks[3] = 7
        return (kt, np.ones(cap_t, bool), live_t,
                ks, np.ones(cap_s, bool), np.ones(cap_s, bool))
    cap_t, cap_s, dead_frac, dup = case
    rng = np.random.default_rng(cap_t * cap_s)
    tbl = cap_t * 4
    if dup:
        kt = rng.integers(0, tbl // 2, cap_t)
        kt[1] = kt[0]                       # one collision at least
    else:
        kt = rng.permutation(tbl)[:cap_t]   # unique, spread over the table
    live_t = rng.random(cap_t) >= dead_frac
    valid_t = rng.random(cap_t) >= dead_frac / 4
    if dup:
        live_t[:2] = valid_t[:2] = True
    # scan keys: mostly in range, some past either end of the table
    ks = rng.integers(-tbl // 8, tbl + tbl // 8, cap_s)
    live_s = rng.random(cap_s) >= dead_frac / 2
    valid_s = rng.random(cap_s) >= 0.05
    return kt, valid_t, live_t, ks, valid_s, live_s


@pytest.mark.parametrize("case", [
    (128, 128, 0.0, False),      # minimal table
    (256, 1024, 0.3, False),     # dead and null rows kept out of the table
    (384, 896, 0.1, True),       # duplicate table keys raise the flag
    (128, 256, 1.0, False),      # no usable table row at all
    "one_live_row",
], ids=lambda c: c if isinstance(c, str) else "-".join(map(str, c)))
@pytest.mark.parametrize("swapped", [False, True],
                         ids=["dense_join", "dense_join_swapped"])
def test_direct_address_join_matches_numpy(swapped, case):
    """``_table_build_probe`` through both joins that use it: which scan
    rows match, the table row each gathers (the first of duplicates) and
    the duplicate flag, against a dictionary built in Python."""
    kt, valid_t, live_t, ks, valid_s, live_s = _dense_case(case)
    table = _keyed_batch(kt, valid_t, live_t, "t_")
    scan = _keyed_batch(ks, valid_s, live_s, "s_")
    first, seen_twice = {}, False
    for i in np.flatnonzero(live_t & valid_t):
        seen_twice |= int(kt[i]) in first
        first.setdefault(int(kt[i]), i)
    want_row = np.asarray([first.get(int(k), -1) for k in ks])
    want_match = live_s & valid_s & (want_row >= 0)
    if swapped:
        out_schema = T.Schema(list(table.schema) + list(scan.schema))
        out, fail = J.dense_join_swapped(table, scan, table.column(0),
                                         scan.column(0), out_schema)
        t_cols, s_cols = out.columns[:2], out.columns[2:]
    else:
        out_schema = T.Schema(list(scan.schema) + list(table.schema))
        out, fail = J.dense_join("inner", scan, table, scan.column(0),
                                 table.column(0), out_schema)
        s_cols, t_cols = out.columns[:2], out.columns[2:]
    assert bool(fail) == seen_twice
    assert (np.asarray(out.live) == want_match).all()
    assert int(out.n_rows) == int(want_match.sum())
    m = want_match
    assert (np.asarray(t_cols[1].data)[m] == want_row[m] * 10 + 1).all()
    assert (np.asarray(t_cols[0].data)[m] == ks[m]).all()
    assert (np.asarray(s_cols[0].data)[m] == ks[m]).all()
    assert (np.asarray(s_cols[1].data)[m]
            == (np.arange(len(ks)) * 10 + 1)[m]).all()
    for c in t_cols:            # an unmatched row gathers nulls
        assert not np.asarray(c.validity)[~m].any()


_I64 = np.iinfo(np.int64)


@pytest.mark.parametrize("n_ref,n_q,span", [
    (0, 5, 10), (7, 0, 10), (1, 1, 1),
    (1000, 300, 40),             # long runs of equal keys on both sides
    (300, 5000, 10 ** 6),        # more queries than references
    (4096, 1024, _I64.max),      # the whole int64 range, both ends present
], ids=lambda v: str(v))
def test_sorted_rank_pair_is_searchsorted(n_ref, n_q, span):
    """The merge that stands where two binary searches stood: left and
    right ranks of every query, against numpy's ``searchsorted``."""
    rng = np.random.default_rng(n_ref + n_q)
    low = _I64.min if span == _I64.max else -span
    ref = np.sort(rng.integers(low, span, n_ref, dtype=np.int64,
                               endpoint=True))
    q = rng.integers(low, span, n_q, dtype=np.int64, endpoint=True)
    if n_ref > 3:
        ref[0], ref[-2:] = _I64.min, _I64.max
    if n_q > 3:
        q[:2] = [_I64.max, _I64.min]
        q[2:4] = ref[len(ref) // 2: len(ref) // 2 + 2][:2] if n_ref > 3 else 0
    lo, hi = jax.jit(J.sorted_rank_pair)(jnp.asarray(ref), jnp.asarray(q))
    assert lo.dtype == hi.dtype == jnp.int32
    assert (np.asarray(lo) == np.searchsorted(ref, q, "left")).all()
    assert (np.asarray(hi) == np.searchsorted(ref, q, "right")).all()


@pytest.mark.parametrize("case", [
    (256, 1024, 0.3, True), (384, 896, 0.1, True), (128, 256, 1.0, False),
    "max_key",
], ids=lambda c: c if isinstance(c, str) else "-".join(map(str, c)))
def test_join_match_sorted_build_ranges(case):
    """Every probe row's [lo, lo + count) names, through ``build_at_rank``,
    exactly the usable build rows of its key; a dead or null row on either
    side matches nothing, and a real Long.MaxValue key does not match the
    sentinel that dead build rows carry."""
    if case == "max_key":
        kt = np.asarray([5, _I64.max, 7, _I64.max, 5, 1, 2, 3] * 16)
        valid_t = np.ones(128, bool)
        live_t = np.arange(128) % 8 != 3       # every second MAX row dead
        ks = np.asarray([_I64.max, 5, 9, _I64.min] * 32)
        valid_s = live_s = np.ones(128, bool)
    else:
        kt, valid_t, live_t, ks, valid_s, live_s = _dense_case(case)
    table = _keyed_batch(kt, valid_t, live_t, "t_", T.LONG)
    scan = _keyed_batch(ks, valid_s, live_s, "s_", T.LONG)
    lo, counts, build_at_rank = jax.jit(J.join_match_sorted_build)(
        table.column(0), scan.column(0), table.row_mask(), scan.row_mask())
    lo, counts, build_at_rank = map(np.asarray, (lo, counts, build_at_rank))
    rows_of = {}
    for i in np.flatnonzero(live_t & valid_t):
        rows_of.setdefault(int(kt[i]), []).append(i)
    for j, k in enumerate(ks):
        want = rows_of.get(int(k), []) if live_s[j] and valid_s[j] else []
        got = build_at_rank[lo[j]: lo[j] + counts[j]]
        assert sorted(got.tolist()) == want, (j, k)


# -- segment reductions through the sort path and the packed-dictionary path -

#: Sorted-dictionary key pairs by path. ``dict13`` is q1's shape: (3 + null)
#: x (2 + null) = 12 slots and the spare one dead rows land in, 13 segments,
#: reduced slot by slot with masked reductions, as are ``dict512``'s (63 +
#: null) x (7 + null) = 512. ``dict3072`` is (63 + null) x (47 + null) =
#: 3,072 slots: above ``_MASKED_SLOT_LIMIT``, the scatter form.
_DICT_KEYS = {
    "dict13": (["A", "N", "R"], ["F", "O"]),
    "dict512": ([f"k{i:02d}" for i in range(63)],
                [f"s{i}" for i in range(7)]),
    "dict3072": ([f"k{i:02d}" for i in range(63)],
                 [f"s{i:02d}" for i in range(47)]),
}


def _dict_slots(path):
    first, second = _DICT_KEYS[path]
    return (len(first) + 1) * (len(second) + 1)


def _dict_key_parts(path, slots):
    """Per key, each row's entry number + 1 (0 = null) out of its packed
    slot (slot = part1 * (len(entries2) + 1) + part2)."""
    radix = len(_DICT_KEYS[path][1]) + 1
    return slots // radix, slots % radix


def _dict_key_columns(path, slots):
    """Two sorted-dictionary string columns whose packed slot is ``slots``."""
    cols = []
    for part, entries in zip(_dict_key_parts(path, slots), _DICT_KEYS[path]):
        vals = [None if p == 0 else entries[p - 1] for p in part]
        col = DeviceColumn.dict_string_from_arrow(
            pa.array(vals + entries, pa.string()), len(vals) + len(entries))
        # the appended entries only pin the dictionary; drop their rows
        cols.append(col.head(len(vals)))
    return cols


def _grouping(path, spec, n, rng):
    """(key columns, group label per row, n) for one path: labels order
    as the path's output groups do (nulls first, then ascending)."""
    if path != "sort":
        n_slots = _dict_slots(path)
        radix = len(_DICT_KEYS[path][1]) + 1
        if spec == "own":
            n = n_slots
            slots = rng.permutation(n_slots)
        elif spec == "one":
            slots = np.full(n, 7)
        elif spec == "null-keys":       # every row null in one key or both
            slots = np.where(rng.random(n) < 0.5,
                             rng.integers(0, radix, n),
                             rng.integers(0, n_slots // radix, n) * radix)
        else:
            slots = rng.integers(0, n_slots, n)
        return _dict_key_columns(path, slots), slots.astype(np.int64), n
    if spec == "own":
        keys, valid = rng.permutation(n).astype(np.int64), np.ones(n, bool)
    elif spec == "one":
        keys, valid = np.full(n, 5, np.int64), np.ones(n, bool)
    else:
        keys = rng.integers(-n // 20, n // 20, n)
        valid = rng.random(n) >= (0.5 if spec == "null-keys" else 0.05)
    col = DeviceColumn.from_numpy(keys, valid, T.INT, n)
    return [col], np.where(valid, keys, np.iinfo(np.int64).min), n


_REDUCE_CASES = (
    [pytest.param(("random", 1024, dt, op, 1), id=f"{dt}-{op}")
     for dt in ("int32", "int64") for op in ("sum", "min", "max")]
    + [pytest.param(("random", 512, "float64", op, 1), id=f"float64-{op}")
       for op in ("min", "max", "sum")]
    + [pytest.param(("own", 256, "int64", "sum", 5), id="2d-lanes-own-group"),
       pytest.param(("one", 512, "int64", "sum", 1), id="one-group"),
       pytest.param(("one", 1, "int64", "sum", 1), id="one-row"),
       pytest.param(("random", 128, "int64", "sum", 1, True), id="empty")]
    # first / last read positions out of the segment min / max
    + [pytest.param(("random", 1024, dt, op, 2), id=f"{dt}-{op}")
       for dt in ("int64", "float64") for op in ("first", "last")]
    # Spark's NaN: greatest in a max, a min's answer only when alone
    + [pytest.param(("random", 512, "float64nan", op, 2), id=f"nan-{op}")
       for op in ("min", "max")]
    + [pytest.param(("null-keys", 1024, "int64", "sum", 2), id="null-keys")]
    + [pytest.param(("dead-slot", 1024, "int64", op, 1), id=f"dead-slot-{op}")
       for op in ("sum", "min")])


def _reduce_reference(op, v):
    """One group's answer over its contributing values, as Spark has it."""
    if op == "first":
        return v[0]
    if op == "last":
        return v[-1]
    if op == "max" or not np.isnan(v).any():        # np.max carries a NaN
        return {"sum": np.sum, "min": np.min, "max": np.max}[op](v)
    return np.nan if np.isnan(v).all() else np.nanmin(v)


@pytest.mark.parametrize("case", _REDUCE_CASES)
@pytest.mark.parametrize("path", ["sort", "dict13", "dict512", "dict3072"])
def test_grouped_reductions_match_numpy(path, case):
    """The grouping paths' slot reductions — the sort path's and the wide
    dictionary's ``jax.ops.segment_{sum,min,max}``, the narrow dictionary's
    masked reductions; 1-D lanes and the (kind, dtype)-stacked 2-D lanes —
    against a loop over the groups in numpy: group order, keys, counts,
    results."""
    spec, n, dtype, op, lanes = case[:5]
    empty = len(case) > 5
    rng = np.random.default_rng(
        sum(map(ord, f"{path}{spec}{n}{dtype}{op}")))
    keys, labels, n = _grouping(path, spec, n, rng)
    live = np.zeros(n, bool) if empty else rng.random(n) >= 0.1
    if spec in ("own", "one"):
        live[:] = True
    elif spec == "dead-slot":           # one group's rows are all dead
        live &= labels != labels[0]
    vals, valids = [], []
    for _ in range(lanes):
        if dtype.startswith("float64"):
            v = rng.standard_normal(n)
            if dtype == "float64nan":
                v[rng.random(n) < 0.3] = np.nan
                v[labels == labels[1]] = np.nan     # a group of NaN alone
            vals.append(v)
        else:
            vals.append(rng.integers(-10**6, 10**6, n).astype(dtype))
        valids.append(rng.random(n) >= (0.0 if spec in ("own", "one")
                                        else 0.1))
    inputs = [(jnp.asarray(v), jnp.asarray(ok), op)
              for v, ok in zip(vals, valids)]
    if path == "sort":
        key_cols, results, n_groups, group_live = \
            G._sort_grouped_aggregate(keys, jnp.asarray(live), inputs)
    else:
        assert G.masked_slot_form(keys) == (path != "dict3072")
        key_cols, results, n_groups, group_live, fail = \
            G.grouped_aggregate(keys, jnp.asarray(live), inputs)
        assert fail is False
    groups = sorted(set(labels[live].tolist()))
    g = len(groups)
    assert int(n_groups) == g
    assert spec != "dead-slot" or labels[0] not in groups
    assert np.asarray(group_live).tolist() == \
        [True] * g + [False] * (len(np.asarray(group_live)) - g)
    # the keys of each output group
    if path == "sort":
        null = np.iinfo(np.int64).min
        assert key_cols[0].to_arrow(g).to_pylist() == \
            [None if k == null else k for k in groups]
    else:
        want = [[None if p == 0 else e[p - 1] for p in part]
                for part, e in zip(
                    _dict_key_parts(path, np.asarray(groups, np.int64)),
                    _DICT_KEYS[path])]
        assert [c.to_arrow(g).to_pylist() for c in key_cols] == want
        assert spec != "null-keys" or all(None in pair
                                          for pair in zip(*want))
    for (res, cnt), v, ok in zip(results, vals, valids):
        res, cnt = np.asarray(res), np.asarray(cnt)
        assert res.dtype == v.dtype
        for i, label in enumerate(groups):
            rows = live & ok & (labels == label)
            assert cnt[i] == rows.sum()
            if not rows.any():
                continue
            want = np.asarray(_reduce_reference(op, v[rows])).astype(v.dtype)
            if dtype == "float64" and op == "sum":
                np.testing.assert_allclose(res[i], want,
                                           rtol=1e-12, atol=1e-12)
            elif np.isnan(want):
                assert np.isnan(res[i])
            else:                           # bit for bit, floats too
                assert res[i].tobytes() == want.tobytes()
        assert not cnt[g:].any() and not res[g:].any()


@pytest.mark.parametrize("shape", ["q1_13_slots", "above_the_cut"])
def test_dict_grouped_aggregate_lowers_without_scatter(shape):
    """The mechanism's counter, as ``concat_batches`` has its own below: at
    q1's shape — 13 segments, float64 sums, int64 counts, a min and a max,
    1 Mi rows — no scatter is lowered, the slot rows come out of ``reduce``
    operations under the ``masked_slot_reduce`` scope; a dictionary above
    ``_MASKED_SLOT_LIMIT`` still scatters. Lowered from shapes, nothing
    runs."""
    cap = 1 << 20
    sizes = (3, 2) if shape == "q1_13_slots" else (G._MASKED_SLOT_LIMIT,)
    lane = lambda dt, n=cap: jax.ShapeDtypeStruct((n,), dt)
    keys = [DeviceColumn(lane(np.uint8, size), lane(np.bool_), T.STRING,
                         offsets=lane(np.int32, size + 1), max_bytes=1,
                         codes=lane(np.int32), dict_sorted=True)
            for size in sizes]
    assert G.masked_slot_form(keys) == (shape == "q1_13_slots")

    def aggregate(keys, live, doubles, longs, oks):
        inputs = [(v, ok, "sum") for v, ok in zip(doubles, oks)] \
            + [(v, ok, op) for v, ok, op in zip(longs, oks, ("count", "min",
                                                              "max"))]
        return G.grouped_aggregate(keys, live, inputs)[:4]
    lowered = jax.jit(aggregate).lower(
        keys, lane(np.bool_), [lane(np.float64)] * 7, [lane(np.int64)] * 3,
        [lane(np.bool_)] * 7)
    text = lowered.as_text(dialect="hlo")
    scatters = re.findall(r"= (\w+\[[\d,]*\])\S* scatter\(", text)
    reduces = re.findall(r"= (\w+\[12\])\S* reduce\(", text)
    scoped = "masked_slot_reduce" in lowered.as_text(debug_info=True)
    if shape == "q1_13_slots":
        # a reduction a lane: rows_per_slot; the doubles' sums; ten
        # counts, a min and a max
        assert sorted(reduces) == ["f64[12]"] * 7 + ["s32[12]"] \
            + ["s64[12]"] * 12
        assert scatters == [] and scoped
    else:
        assert len(scatters) == 5 and not scoped


# -- the stable sort ----------------------------------------------------------

@pytest.mark.parametrize("n,equal", [(1, False), (7, False), (128, False),
                                     (777, False), (1024, False),
                                     (640, True), (0, False)],
                         ids=["1", "7", "128", "777", "1024", "all-equal",
                              "empty"])
def test_sort_is_numpy_stable_argsort(n, equal):
    """One int key over the whole int32 range carrying a row-id payload:
    the payload comes out as ``np.argsort(kind="stable")``, so equal keys
    keep their input order; an empty batch stays empty."""
    rng = np.random.default_rng(n)
    cap = max(n, 128) if n in (0, 1, 7) else n
    keys = np.zeros(cap, np.int64) if equal else \
        rng.integers(-2**31, 2**31, cap)
    if not equal and n > 7:
        keys[rng.integers(0, n, n // 4)] = keys[0]     # ties to keep in order
    kcol = DeviceColumn.from_numpy(keys, None, T.INT, cap)
    ids = DeviceColumn.from_numpy(np.arange(cap), None, T.INT, cap)
    schema = T.Schema([T.StructField("k", T.INT, False),
                       T.StructField("id", T.INT, False)])
    batch = ColumnarBatch((kcol, ids), jnp.asarray(n, jnp.int32), schema)
    out = R.sort_batch_by_columns(batch, [kcol], [True], [True])
    assert int(out.n_rows) == n
    want = np.argsort(keys[:n], kind="stable")
    assert (np.asarray(out.columns[1].data)[:n] == want).all()
    assert (np.asarray(out.columns[0].data)[:n] == keys[:n][want]).all()


# -- flat strings: row gather and adjacent-row equality ----------------------

def _flat_strings(rng, n, w, null_frac=0.1):
    """A flat (not dictionary-encoded) string column of ``n`` rows of at
    most ``w`` bytes, and the Python values it holds."""
    alphabet = list("abcxyz019 _") + ["é", "語"]
    vals = []
    for _ in range(n):
        if rng.random() < null_frac:
            vals.append(None)
            continue
        s = ""
        for ch in rng.choice(alphabet, rng.integers(0, w + 1)):
            if len((s + ch).encode()) > w:
                break
            s += ch
        vals.append(s)
    arr = pa.array(vals, pa.string())
    offsets = np.frombuffer(arr.buffers()[1], np.int32, n + 1)
    data = np.frombuffer(arr.buffers()[2] or b"", np.uint8)
    col = DeviceColumn.string_from_host(
        offsets, data, np.asarray([v is not None for v in vals]), n)
    return col, vals


@pytest.mark.parametrize("n,m,w", [(128, 128, 1), (300, 512, 24),
                                   (64, 1024, 48), (128, 256, 8)],
                         ids=["128x128x1", "300x512x24", "64x1024x48",
                              "empty"])
def test_flat_string_gather_matches_numpy(n, m, w):
    """``gather_column`` of char-matrix rows: indices past either end
    clip, a row the index mask drops is null, and no index kept at all
    leaves an empty payload."""
    rng = np.random.default_rng(n * m)
    col, vals = _flat_strings(rng, n, w)
    assert not col.is_dict
    idx = rng.integers(-5, n + 5, m)
    keep = np.zeros(m, bool) if (n, m, w) == (128, 256, 8) \
        else rng.random(m) < 0.8
    out = R.gather_column(col, jnp.asarray(idx, jnp.int32),
                          jnp.asarray(keep))
    want = [vals[int(np.clip(i, 0, n - 1))] if k else None
            for i, k in zip(idx, keep)]
    assert out.to_arrow(m).to_pylist() == want
    assert int(out.offsets[-1]) == \
        sum(len(v.encode()) for v in want if v is not None)


def test_flat_string_adjacent_equality_matches_numpy():
    """``_equal_adjacent`` on flat strings in a given row order: equal
    bytes and both valid, or both null."""
    rng = np.random.default_rng(9)
    n = 512
    col, vals = _flat_strings(rng, n, 2, null_frac=0.2)   # many repeats
    perm = rng.permutation(n)
    got = np.asarray(G._equal_adjacent(col, jnp.asarray(perm, jnp.int32)))
    s = [vals[i] for i in perm]
    want = [True] + [s[i] == s[i - 1] for i in range(1, n)]
    assert got.tolist() == want
    assert 0.05 < np.mean(want[1:]) < 0.95


# -- the string row hash -------------------------------------------------------

@pytest.mark.parametrize("n,w,columns,all_empty", [
    (128, 8, 1, False), (512, 24, 1, False), (300, 7, 1, False),
    (1024, 64, 1, False), (256, 16, 3, False), (128, 8, 1, True)],
    ids=["128x8", "512x24", "300x7", "1024x64", "chained-seeds",
         "empty-strings"])
def test_string_row_hash_matches_host_hash(n, w, columns, all_empty):
    """``murmur3_bytes_rows`` on the device's char matrix against the host
    hash the CPU oracle partitions by (``spark_hash_columns_host``); with
    several columns each row's hash is the next column's seed."""
    rng = np.random.default_rng(n * w)
    h = jnp.full(n, np.uint32(SP.SPARK_SEED), jnp.uint32)
    arrays = []
    for _ in range(columns):
        col, vals = _flat_strings(rng, n, 0 if all_empty else w,
                                  null_frac=0.0)
        h = SP.murmur3_bytes_rows(jnp, char_matrix(col, w), lengths(col), h)
        arrays.append(pa.array(vals, pa.string()))
    want = SP.spark_hash_columns_host(arrays, [T.STRING] * columns)
    assert (np.asarray(h).astype(np.int32) == want).all()
    assert all_empty or len(set(want.tolist())) > n // 4


# -- concat_batches: placement against plain numpy ---------------------------

def _concat_column(kind, rng, cap, salt):
    """(pyarrow array of ``cap`` rows with nulls, its DeviceColumn): every
    row holds data, so a batch's dead rows carry values the kernel must
    mask."""
    null = rng.random(cap) < 0.2
    if kind == "plain_string":
        col, vals = _flat_strings(rng, cap, 3 + 4 * (salt % 3), 0.2)
        return pa.array(vals, pa.string()), col
    if kind == "dict_string":
        vals = [None if z else f"b{salt}-{int(v)}"
                for z, v in zip(null, rng.integers(0, 9 + salt, cap))]
        arr = pa.array(vals, pa.string())
    elif kind == "array":
        # widths differ by batch (1, 2, 4 elements), so narrower inputs pad
        arr = pa.array(
            [None if z else
             [None if rng.random() < 0.2 else int(v) for v in
              rng.integers(-9, 9, rng.integers(0, 2 ** (salt % 3) + 1))]
             for z in null], pa.list_(pa.int32()))
    elif kind == "struct":
        arr = pa.array(
            [None if z else
             {"a": None if rng.random() < 0.2 else int(rng.integers(-99, 99)),
              "b": None if rng.random() < 0.2 else float(rng.random())}
             for z in null],
            pa.struct([("a", pa.int32()), ("b", pa.float64())]))
    else:
        typ, values = {
            "int32": (pa.int32(), rng.integers(-2 ** 31, 2 ** 31, cap)),
            "int64": (pa.int64(), rng.integers(-2 ** 62, 2 ** 62, cap)),
            "float64": (pa.float64(), rng.standard_normal(cap) * 1e9),
            "date": (pa.date32(),
                     rng.integers(0, 20000, cap).astype(np.int32)),
            "bool": (pa.bool_(), rng.random(cap) < 0.5)}[kind]
        arr = pa.array(values, mask=null).cast(typ)
    return arr, DeviceColumn.from_arrow(arr, cap)


def _concat_input(kinds, rng, cap, mode, salt):
    """One input batch and the rows (per column) a concat must take from
    it, in order."""
    arrays, cols = zip(*[_concat_column(k, rng, cap, salt) for k in kinds])
    if mode == "lazy":                       # a live mask with holes
        keep = rng.random(cap) < 0.6
        keep[0], keep[-1] = False, True
    else:
        n = {"empty": 0, "one": 1, "full": cap}.get(
            mode, int(rng.integers(1, cap)))
        keep = np.arange(cap) < n
    schema = T.schema_from_arrow(pa.schema(
        [pa.field(f"c{i}", a.type) for i, a in enumerate(arrays)]))
    batch = ColumnarBatch(
        tuple(cols), jnp.asarray(keep.sum(), jnp.int32), schema,
        live=jnp.asarray(keep) if mode == "lazy" else None)
    rows = [[v for v, k in zip(a.to_pylist(), keep) if k] for a in arrays]
    return batch, rows


def _assert_dead_past(col, total):
    """Every lane of ``col`` past row ``total`` is invalid and zero."""
    assert not np.asarray(col.validity)[total:].any()
    if col.is_struct:
        for kid in col.children:
            _assert_dead_past(kid, total)
    elif col.is_array:
        assert not np.asarray(col.data)[total:].any()
        assert not np.asarray(col.elem_validity)[total:].any()
        assert not np.asarray(col.lengths)[total:].any()
    elif col.is_dict:
        assert not np.asarray(col.codes)[total:].any()
    elif col.is_string:
        offsets = np.asarray(col.offsets)
        assert (offsets[total:] == offsets[total]).all()
    else:
        assert not np.asarray(col.data)[total:].any()


_CONCAT_KINDS = {
    "fixed": ("int32", "int64", "float64", "date", "bool"),
    "dict_string": ("dict_string", "int64"),
    "plain_string": ("plain_string", "float64"),
    "array": ("array",),
    "struct": ("struct", "int32"),
}
_CONCAT_LAYOUTS = {
    "2": [(128, "lazy"), (256, "partial")],
    "3": [(256, "full"), (128, "empty"), (512, "lazy")],
    "6": [(128, "lazy"), (256, "partial"), (128, "empty"), (512, "full"),
          (128, "partial"), (256, "lazy")],
    # external_sort._merge_step_kernel: carry, chunk, one-row sentinel
    "merge_step": [(256, "partial"), (512, "full"), (128, "one")],
}


@pytest.mark.parametrize("room", ["exact", "bucket_above"])
@pytest.mark.parametrize("layout", list(_CONCAT_LAYOUTS))
@pytest.mark.parametrize("kinds", list(_CONCAT_KINDS))
def test_concat_batches_matches_numpy(kinds, layout, room):
    """``concat_batches`` against plain Python: the live rows of every input
    in batch order, nulls kept, and nothing but dead zero lanes after."""
    rng = np.random.default_rng(len(kinds) * 31 + len(layout))
    inputs = [_concat_input(_CONCAT_KINDS[kinds], rng, cap, mode, i)
              for i, (cap, mode) in enumerate(_CONCAT_LAYOUTS[layout])]
    caps = sum(cap for cap, _ in _CONCAT_LAYOUTS[layout])
    out_capacity = caps if room == "exact" else 2 * bucket_capacity(caps)
    out = concat_batches([b for b, _ in inputs], out_capacity)
    assert out.live is None and out.capacity == out_capacity
    total = sum(len(rows[0]) for _, rows in inputs)
    assert int(out.n_rows) == total
    got = out.to_arrow()
    for ci in range(len(_CONCAT_KINDS[kinds])):
        want = [v for _, rows in inputs for v in rows[ci]]
        assert got.column(ci).to_pylist() == want
        _assert_dead_past(out.columns[ci], total)


def test_concat_batches_refuses_output_under_sum_of_capacities():
    """A batch is placed whole, so the output has to hold every input's
    capacity even when the live rows would fit."""
    rng = np.random.default_rng(5)
    batches = [_concat_input(("int64",), rng, cap, "one", i)[0]
               for i, cap in enumerate((128, 256))]
    with pytest.raises(ValueError, match="sum of the input capacities"):
        concat_batches(batches, 256)


@pytest.mark.parametrize("shape", ["q3_coalesce", "q6_merge_tree"])
def test_concat_batches_lowers_without_wide_scatter(shape):
    """The mechanism's counter: at the benchmark's shapes no scatter writes
    a lane of the output's width; the ones left are ``physical()``'s
    ``s32[capacity]`` index maps. Lowered from shapes, nothing runs."""
    if shape == "q3_coalesce":      # lineitem's six filtered row groups
        n, cap, out, lazy = 6, 1 << 20, 8 << 20, True
        types = (T.LONG, T.DOUBLE, T.DOUBLE)
    else:                           # two one-row global-aggregate states
        n, cap, out, lazy = 2, 2 << 20, 4 << 20, False
        types = (T.DOUBLE,)
    schema = T.Schema([T.StructField(f"c{i}", t)
                       for i, t in enumerate(types)])
    lane = lambda dt: jax.ShapeDtypeStruct((cap,), dt)
    batch = ColumnarBatch(
        tuple(DeviceColumn(lane(t.np_dtype), lane(np.bool_), t)
              for t in types),
        jax.ShapeDtypeStruct((), np.int32), schema,
        live=lane(np.bool_) if lazy else None)
    text = jax.jit(concat_batches, static_argnums=(1,)).lower(
        [batch] * n, out).as_text(dialect="hlo")
    scatters = re.findall(r"= (\w+\[[\d,]*\])\S* scatter\(", text)
    assert scatters == ([f"s32[{cap}]"] * n if lazy else [])
    assert len(re.findall(r"\[%d\]\S* dynamic-update-slice\(" % out, text)) \
        == 2 * n * len(types)
