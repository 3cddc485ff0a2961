"""TPC-H-like suite as differential tests: every query must produce the
same rows on the TPU path (fused and streaming) as on the CPU oracle —
the reference's TpchLikeSpark suite discipline (TpchLikeSpark.scala:290+)
applied through the differential harness."""

import math

import pytest

from spark_rapids_tpu.session import TpuSession
from spark_rapids_tpu.workloads import tpch

N_LI = 1 << 12


@pytest.fixture(scope="module")
def tables():
    return tpch.gen_tables(N_LI, seed=7)


@pytest.fixture(scope="module")
def sessions():
    return (TpuSession({"spark.rapids.sql.enabled": False}),
            TpuSession({"spark.rapids.sql.enabled": True,
                        "spark.rapids.sql.variableFloatAgg.enabled": True}))


#: Default-tier subset covering the operator families (scan/filter/
#: project/agg q1/q6, top-k-over-join q3, band/disjunctive join q19,
#: float scoring xbb_score, the six-table join tree with dense and
#: swapped direct-address joins q5); deeper join trees, semi/anti, and
#: the rest of the 22 run under ``-m "slow or not slow"``.
FAST = {"q1", "q3", "q5", "q6", "q19", "xbb_score"}


@pytest.mark.parametrize(
    "name",
    [n if n in FAST else pytest.param(n, marks=pytest.mark.slow)
     for n in sorted(tpch.QUERIES)])
def test_query_differential(tables, sessions, name):
    cpu, tpu = sessions
    q = tpch.QUERIES[name]
    from spark_rapids_tpu.workloads.compare import tables_match
    cpu_result = q(tpch.load(cpu, tables)).collect()
    tpu_result = q(tpch.load(tpu, tables)).collect()
    # Multiset compare (q3's top-10 float-sum ties can legitimately
    # reorder) with float tolerance for XLA reduction-order differences.
    assert tables_match(tpu_result, cpu_result, rel_tol=1e-9, abs_tol=1e-9)
