"""TPC-DS query 43, the store sales pivot: store_sales joined to date_dim
and store, the sales price summed per day name in seven conditional sums,
grouped by store name and id, ordered by the name, the id and the seven
sums, the first 100 (qualification substitutions: YEAR 2000, GMT -5).
The joins follow the FROM clause's order, date_dim, store_sales, store:
the filtered calendar probes the fact table, whose three row groups the
join gathers into one build side, so the group-by meets the store's own
sorted dictionaries.

Nulls as SQL has them: a null ss_sold_date_sk or ss_store_sk joins
nothing; ``CASE WHEN ... THEN ss_sales_price ELSE NULL END`` is null on
the other days and where the price is null, and SUM skips nulls, so a
store's day without a non-null price sums to null. ``reference`` reads the
columns as ``columns.load_columns`` gives them: a nullable integer column
with a null comes back as float64 with NaN there, a price as NaN.

``REL_GAP_LIMIT`` 1e-10: the engine sums in float64 (a tree of adds a
slot, the reference serial adds); a cell of the answer sums 10^4 to 10^5
prices at SF1, so float64's rounding leaves 1e-14 at most, while the same
sums in float32 (the control) are off by 1e-8 or more — the limit sits
between the two (PERF.md section 2)."""

import numpy as np

DAYS = ("Sunday", "Monday", "Tuesday", "Wednesday", "Thursday", "Friday",
        "Saturday")
SUMS = tuple(f"{d[:3].lower()}_sales" for d in DAYS)

COLUMNS = {"store_sales": {"ss_sold_date_sk": 8, "ss_store_sk": 8,
                           "ss_sales_price": 8},
           "date_dim": {"d_date_sk": 8, "d_year": 4, "d_day_name": 1},
           "store": {"s_store_sk": 8, "s_store_name": 1, "s_store_id": 1,
                     "s_gmt_offset": 8}}
REL_GAP_LIMIT = 1e-10
ORDERED = True


def build(t, year=2000, gmt=-5.0):
    from spark_rapids_tpu.ops import aggregates as A
    from spark_rapids_tpu.ops import predicates as P
    from spark_rapids_tpu.ops.conditional import CaseWhen
    from spark_rapids_tpu.ops.expression import col, lit
    from spark_rapids_tpu.plan.logical import SortOrder

    def day_sum(day, name):
        return A.AggregateExpression(A.Sum(CaseWhen(
            [(P.EqualTo(col("d_day_name"), lit(day)),
              col("ss_sales_price"))])), name)

    dates = t["date_dim"].where(P.EqualTo(col("d_year"), lit(year)))
    stores = t["store"].where(P.EqualTo(col("s_gmt_offset"), lit(gmt)))
    return (dates
            .join(t["store_sales"], on=P.EqualTo(col("d_date_sk"),
                                                 col("ss_sold_date_sk")),
                  how="inner")
            .join(stores, on=P.EqualTo(col("ss_store_sk"),
                                       col("s_store_sk")), how="inner")
            .group_by(col("s_store_name"), col("s_store_id"))
            .agg(*[day_sum(day, name) for day, name in zip(DAYS, SUMS)])
            .sort(*[SortOrder(col(name)) for name in
                    ("s_store_name", "s_store_id") + SUMS])
            .limit(100))


def _key(values):
    """(keys as int64, valid) of a key column as read back: a column that
    held a null comes back as float64 with NaN there."""
    values = np.asarray(values)
    valid = ~np.isnan(values) if values.dtype.kind == "f" \
        else np.ones(len(values), bool)
    return np.where(valid, values, 0).astype(np.int64), valid


def _lookup(keys, valid, table_keys):
    """(row of the matching dimension row, found) for each fact key."""
    order = np.argsort(table_keys, kind="stable")
    sorted_keys = table_keys[order]
    pos = np.minimum(np.searchsorted(sorted_keys, keys), len(order) - 1)
    found = valid & (sorted_keys[pos] == keys)
    return order[pos], found


def reference(t, real=np.float64, year=2000, gmt=-5.0):
    sales, dates, stores = t["store_sales"], t["date_dim"], t["store"]
    date_sk, date_ok = _key(sales["ss_sold_date_sk"])
    store_sk, store_ok = _key(sales["ss_store_sk"])
    date_row, date_ok = _lookup(date_sk, date_ok, dates["d_date_sk"])
    store_row, store_ok = _lookup(store_sk, store_ok, stores["s_store_sk"])
    keep = (date_ok & store_ok
            & (dates["d_year"][date_row] == year)
            & (stores["s_gmt_offset"][store_row] == gmt))
    day = dates["d_day_name"][date_row[keep]]
    price = sales["ss_sales_price"][keep]
    names = stores["s_store_name"][store_row[keep]]
    ids = stores["s_store_id"][store_row[keep]]
    # groups in (name, id) order: each string's rank, then the pair's
    name_of, name_code = np.unique(names, return_inverse=True)
    id_of, id_code = np.unique(ids, return_inverse=True)
    pairs, group = np.unique(name_code * len(id_of) + id_code,
                             return_inverse=True)
    out = {"s_store_name": name_of[pairs // max(len(id_of), 1)],
           "s_store_id": id_of[pairs % max(len(id_of), 1)]}
    for name, day_name in zip(SUMS, DAYS):
        take = (day == day_name) & ~np.isnan(price)
        g, p = group[take], price[take].astype(real)
        by_group = np.argsort(g, kind="stable")
        g, p = g[by_group], p[by_group]
        sums = np.full(len(pairs), np.nan, real)
        if len(g):
            starts = np.flatnonzero(np.r_[True, g[1:] != g[:-1]])
            sums[g[starts]] = np.add.reduceat(p, starts).astype(real)
        out[name] = sums
    # ordered by name and id, which are unique per group: the sums after
    # them never decide; the first 100
    return {k: v[:100] for k, v in out.items()}
