"""TPC-H Q6, forecasting revenue change: a selective filter and one global
sum over four lineitem columns (clause 2.4.6; validation parameters: DATE
1994, DISCOUNT 0.06, QUANTITY 24; a traffic mix may give others)."""

import datetime

import numpy as np

# referenced columns and their logical width in bytes per row: the least
# any implementation has to read to answer the query
COLUMNS = {"lineitem": {"l_shipdate": 4, "l_discount": 8, "l_quantity": 8,
                        "l_extendedprice": 8}}
REL_GAP_LIMIT = 1e-11   # PERF.md section 2: the readings it sits between
ORDERED = False


def _bounds(year, discount, quantity):
    """Day numbers of the year's ends, and discount -+ 0.01 in whole cents
    (0.06 - 0.01 in binary floating point is not 0.05)."""
    epoch = datetime.date(1970, 1, 1)
    cents = round(discount * 100)
    return ((datetime.date(year, 1, 1) - epoch).days,
            (datetime.date(year + 1, 1, 1) - epoch).days,
            (cents - 1) / 100.0, (cents + 1) / 100.0, float(quantity))


def build(t, year=1994, discount=0.06, quantity=24):
    """The query through the engine's public DataFrame API."""
    from spark_rapids_tpu import types as T
    from spark_rapids_tpu.ops import aggregates as A
    from spark_rapids_tpu.ops import predicates as P
    from spark_rapids_tpu.ops.arithmetic import Multiply
    from spark_rapids_tpu.ops.expression import col, lit
    first, after, low, high, below = _bounds(year, discount, quantity)
    li = t["lineitem"].where(P.And(P.And(P.And(
        P.GreaterThanOrEqual(col("l_shipdate"), lit(first, T.DATE)),
        P.LessThan(col("l_shipdate"), lit(after, T.DATE))),
        P.And(P.GreaterThanOrEqual(col("l_discount"), lit(low)),
              P.LessThanOrEqual(col("l_discount"), lit(high)))),
        P.LessThan(col("l_quantity"), lit(below))))
    return (li.with_column("rev", Multiply(col("l_extendedprice"),
                                           col("l_discount")))
            .group_by()
            .agg(A.AggregateExpression(A.Sum(col("rev")), "revenue")))


def reference(t, real=np.float64, year=1994, discount=0.06, quantity=24):
    """Plain numpy over {table: {column: array}}; money arithmetic and its
    sums in ``real`` (float64 as the configuration states; float32 is the
    lower-precision control)."""
    first, after, low, high, below = _bounds(year, discount, quantity)
    li = t["lineitem"]
    disc = li["l_discount"].astype(real)
    keep = ((li["l_shipdate"] >= first) & (li["l_shipdate"] < after)
            & (disc >= real(low)) & (disc <= real(high))
            & (li["l_quantity"].astype(real) < real(below)))
    rev = li["l_extendedprice"].astype(real)[keep] * disc[keep]
    return {"revenue": np.array([rev.sum(dtype=real)])}
