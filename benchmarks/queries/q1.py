"""TPC-H Q1, pricing summary report (clause 2.4.1): a group-by on two
dictionary strings with eight aggregates over seven lineitem columns,
ordered by its keys (validation parameter: DELTA = 90 days before
1998-12-01)."""

import numpy as np

D_1998_12_01 = 10561

COLUMNS = {"lineitem": {"l_shipdate": 4, "l_returnflag": 1,
                        "l_linestatus": 1, "l_quantity": 8,
                        "l_extendedprice": 8, "l_discount": 8, "l_tax": 8}}
REL_GAP_LIMIT = 1e-9   # PERF.md section 2: the readings it sits between
ORDERED = True


def build(t, delta=90):
    from spark_rapids_tpu import types as T
    from spark_rapids_tpu.ops import aggregates as A
    from spark_rapids_tpu.ops import predicates as P
    from spark_rapids_tpu.ops.arithmetic import Add, Multiply, Subtract
    from spark_rapids_tpu.ops.expression import col, lit

    def disc_price():
        return Multiply(col("l_extendedprice"),
                        Subtract(lit(1.0), col("l_discount")))

    def agg(fn, name):
        return A.AggregateExpression(fn, name)

    return (t["lineitem"]
            .where(P.LessThanOrEqual(col("l_shipdate"),
                                     lit(D_1998_12_01 - delta, T.DATE)))
            .with_column("disc_price", disc_price())
            .with_column("charge", Multiply(disc_price(),
                                            Add(lit(1.0), col("l_tax"))))
            .group_by(col("l_returnflag"), col("l_linestatus"))
            .agg(agg(A.Sum(col("l_quantity")), "sum_qty"),
                 agg(A.Sum(col("l_extendedprice")), "sum_base_price"),
                 agg(A.Sum(col("disc_price")), "sum_disc_price"),
                 agg(A.Sum(col("charge")), "sum_charge"),
                 agg(A.Average(col("l_quantity")), "avg_qty"),
                 agg(A.Average(col("l_extendedprice")), "avg_price"),
                 agg(A.Average(col("l_discount")), "avg_disc"),
                 agg(A.Count(), "count_order"))
            .sort(col("l_returnflag"), col("l_linestatus")))


def reference(t, real=np.float64, delta=90):
    li = t["lineitem"]
    keep = li["l_shipdate"] <= D_1998_12_01 - delta
    flag, status = li["l_returnflag"][keep], li["l_linestatus"][keep]
    qty = li["l_quantity"].astype(real)[keep]
    price = li["l_extendedprice"].astype(real)[keep]
    disc = li["l_discount"].astype(real)[keep]
    tax = li["l_tax"].astype(real)[keep]
    disc_price = price * (real(1.0) - disc)
    charge = disc_price * (real(1.0) + tax)
    out = {k: [] for k in ("l_returnflag", "l_linestatus", "sum_qty",
                           "sum_base_price", "sum_disc_price", "sum_charge",
                           "avg_qty", "avg_price", "avg_disc",
                           "count_order")}
    for f in np.unique(flag):               # sorted: the ORDER BY
        for s in np.unique(status):
            g = (flag == f) & (status == s)
            count = int(g.sum())
            if not count:
                continue
            out["l_returnflag"].append(f)
            out["l_linestatus"].append(s)
            sum_qty = qty[g].sum(dtype=real)
            out["sum_qty"].append(sum_qty)
            sum_price = price[g].sum(dtype=real)
            out["sum_base_price"].append(sum_price)
            out["sum_disc_price"].append(disc_price[g].sum(dtype=real))
            out["sum_charge"].append(charge[g].sum(dtype=real))
            out["avg_qty"].append(sum_qty / real(count))
            out["avg_price"].append(sum_price / real(count))
            out["avg_disc"].append(disc[g].sum(dtype=real) / real(count))
            out["count_order"].append(count)
    return {k: np.array(v) for k, v in out.items()}
