"""TPC-H Q3, shipping priority (clause 2.4.3): customer x orders x lineitem,
revenue grouped by order, date and ship priority, ordered by revenue
descending and order date, the first ten (validation parameters: SEGMENT
BUILDING, DATE 1995-03-15)."""

import numpy as np

D_1995_03_15 = 9204

COLUMNS = {"customer": {"c_custkey": 8, "c_mktsegment": 1},
           "orders": {"o_orderkey": 8, "o_custkey": 8, "o_orderdate": 4,
                      "o_shippriority": 4},
           "lineitem": {"l_orderkey": 8, "l_shipdate": 4,
                        "l_extendedprice": 8, "l_discount": 8}}
REL_GAP_LIMIT = 1e-10   # PERF.md section 2: the readings it sits between
ORDERED = True


def build(t, segment="BUILDING", date=D_1995_03_15):
    from spark_rapids_tpu import types as T
    from spark_rapids_tpu.ops import aggregates as A
    from spark_rapids_tpu.ops import predicates as P
    from spark_rapids_tpu.ops.arithmetic import Multiply, Subtract
    from spark_rapids_tpu.ops.expression import col, lit
    from spark_rapids_tpu.plan.logical import SortOrder
    cust = t["customer"].where(P.EqualTo(col("c_mktsegment"), lit(segment)))
    orders = t["orders"].where(
        P.LessThan(col("o_orderdate"), lit(date, T.DATE)))
    li = t["lineitem"].where(
        P.GreaterThan(col("l_shipdate"), lit(date, T.DATE)))
    return (cust
            .join(orders, on=P.EqualTo(col("c_custkey"), col("o_custkey")),
                  how="inner")
            .join(li, on=P.EqualTo(col("l_orderkey"), col("o_orderkey")),
                  how="inner")
            .with_column("rev",
                         Multiply(col("l_extendedprice"),
                                  Subtract(lit(1.0), col("l_discount"))))
            .group_by(col("l_orderkey"), col("o_orderdate"),
                      col("o_shippriority"))
            .agg(A.AggregateExpression(A.Sum(col("rev")), "revenue"))
            .sort(SortOrder(col("revenue"), ascending=False),
                  SortOrder(col("o_orderdate")))
            .limit(10)
            .select(col("l_orderkey"), col("revenue"), col("o_orderdate"),
                    col("o_shippriority")))


def reference(t, real=np.float64, segment="BUILDING", date=D_1995_03_15):
    cust, orders, li = t["customer"], t["orders"], t["lineitem"]
    buyers = cust["c_custkey"][cust["c_mktsegment"] == segment]
    keep_o = ((orders["o_orderdate"] < date)
              & np.isin(orders["o_custkey"], buyers))
    okey = orders["o_orderkey"][keep_o]       # the table's primary key
    odate = orders["o_orderdate"][keep_o]
    oprio = orders["o_shippriority"][keep_o]
    by_key = np.argsort(okey, kind="stable")
    okey, odate, oprio = okey[by_key], odate[by_key], oprio[by_key]
    keep_l = li["l_shipdate"] > date
    lkey = li["l_orderkey"][keep_l]
    rev = (li["l_extendedprice"].astype(real)[keep_l]
           * (real(1.0) - li["l_discount"].astype(real)[keep_l]))
    if not len(okey) or not len(lkey):
        return {"l_orderkey": np.array([], np.int64),
                "revenue": np.array([], real),
                "o_orderdate": np.array([], np.int32),
                "o_shippriority": np.array([], np.int32)}
    pos = np.minimum(np.searchsorted(okey, lkey), len(okey) - 1)
    hit = okey[pos] == lkey
    group, rev = pos[hit], rev[hit]
    by_group = np.argsort(group, kind="stable")
    group, rev = group[by_group], rev[by_group]
    starts = np.flatnonzero(np.r_[True, group[1:] != group[:-1]])
    sums = np.add.reduceat(rev, starts).astype(real)
    group = group[starts]
    top = np.lexsort((odate[group], -sums))[:10]   # revenue desc, then date
    return {"l_orderkey": okey[group[top]], "revenue": sums[top],
            "o_orderdate": odate[group[top]],
            "o_shippriority": oprio[group[top]]}
