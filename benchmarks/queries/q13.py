"""TPC-H Q13, customer distribution (clause 2.4.13): customers left outer
joined to their orders whose comment is NOT LIKE '%special%requests%',
orders counted per customer, then customers counted per count, ordered by
that count of customers descending and the count of orders descending
(validation parameters: WORD1 special, WORD2 requests).

The specification's NOT LIKE stands in the outer join's ON clause; on the
null-supplying side that is a filter on orders ahead of the join, which is
how ``build`` writes it and ``reference`` computes it. The pattern is one
ordered pattern: "requests ... special" does not match.

``COLUMNS``: o_comment is 19 to 78 bytes of text, 48.5 in the mean, and a
PLAIN byte-array value carries a 4-byte length: 53 bytes a row. The answer
holds counts only, so ``correct`` is exact: ``REL_GAP_LIMIT`` 0.0 and no
floating-point cell to compare."""

import numpy as np

COLUMNS = {"orders": {"o_comment": 53, "o_custkey": 8, "o_orderkey": 8},
           "customer": {"c_custkey": 8}}
REL_GAP_LIMIT = 0.0
ORDERED = True


def build(t, word1="special", word2="requests"):
    from spark_rapids_tpu.ops import aggregates as A
    from spark_rapids_tpu.ops import predicates as P
    from spark_rapids_tpu.ops.expression import col
    from spark_rapids_tpu.ops.strings import Like
    from spark_rapids_tpu.plan.logical import SortOrder
    orders = (t["orders"]
              .where(P.Not(Like(col("o_comment"), f"%{word1}%{word2}%")))
              .select(col("o_custkey"), col("o_orderkey")))
    per_customer = (t["customer"].select(col("c_custkey"))
                    .join(orders,
                          on=P.EqualTo(col("c_custkey"), col("o_custkey")),
                          how="left")
                    .group_by(col("c_custkey"))
                    .agg(A.AggregateExpression(A.Count(col("o_orderkey")),
                                               "c_count")))
    return (per_customer.group_by(col("c_count"))
            .agg(A.AggregateExpression(A.Count(), "custdist"))
            .sort(SortOrder(col("custdist"), ascending=False),
                  SortOrder(col("c_count"), ascending=False)))


def reference(t, real=np.float64, word1="special", word2="requests"):
    """``real`` is taken and not used: no cell of the answer is a
    floating-point number."""
    orders, customer = t["orders"], t["customer"]
    text = orders["o_comment"]
    at = np.char.find(text, word1)
    after = np.where(at >= 0, at + len(word1), 0)
    like = (at >= 0) & (np.char.find(text, word2, after) >= 0)
    buyers = orders["o_custkey"][~like]
    custkey = np.sort(customer["c_custkey"])          # the primary key
    pos = np.minimum(np.searchsorted(custkey, buyers), len(custkey) - 1)
    pos = pos[custkey[pos] == buyers]
    c_count = np.bincount(pos, minlength=len(custkey)).astype(np.int64)
    counts, custdist = np.unique(c_count, return_counts=True)
    order = np.lexsort((-counts, -custdist))   # custdist desc, c_count desc
    return {"c_count": counts[order].astype(np.int64),
            "custdist": custdist[order].astype(np.int64)}
