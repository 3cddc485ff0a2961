"""Ratcheted compile-count gate (ISSUE 6 CI satellite): the TPC-H smoke
suite must stay within a baselined compile budget
(tools/compile_budget_baseline.json — the tpu_lint ratchet discipline
applied to compiles). Each query runs at TWO ladder rungs inside one
polymorphic tier, so any return of per-rung re-specialization doubles
the fused-compile count and fails the gate long before a benchmark run
would notice the regression.

The assertions are deltas, so running after other test modules (which
may have pre-compiled some kernels) can only LOWER the observed counts —
the gate never flakes from test ordering; the true numbers come from a
standalone run, which is how the baseline was measured."""

import json
import os

from spark_rapids_tpu.compile import executables
from spark_rapids_tpu.exec import fusion
from spark_rapids_tpu.session import TpuSession
from spark_rapids_tpu.utils import kernel_cache as KC
from spark_rapids_tpu.workloads import tpch

BASELINE = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "tools", "compile_budget_baseline.json")

SMOKE = ("q1", "q3", "q6")


def test_tpch_smoke_stays_within_compile_budget():
    with open(BASELINE, encoding="utf-8") as f:
        budget = json.load(f)
    tables = tpch.gen_tables(1 << 10, seed=3)     # rung 1024
    big = tpch.gen_tables(1 << 11, seed=3)        # rung 2048, same tier
    tpu = TpuSession({"spark.rapids.sql.enabled": True,
                      "spark.rapids.sql.variableFloatAgg.enabled": True})
    kc0, exe0 = KC.cache_stats(), executables.stats()
    pad0 = fusion.pad_program_count()
    for name in SMOKE:
        q = tpch.QUERIES[name]
        q(tpch.load(tpu, tables)).collect()
        q(tpch.load(tpu, big)).collect()
    kc1, exe1 = KC.cache_stats(), executables.stats()
    kernels = kc1["misses"] - kc0["misses"]
    fused = exe1["jit_compiles"] - exe0["jit_compiles"]
    pads = fusion.pad_program_count() - pad0
    assert kernels <= budget["kernels_compiled_budget"], (
        f"TPC-H smoke compiled {kernels} kernels, budget "
        f"{budget['kernels_compiled_budget']} — per-rung specialization "
        f"crept back? Lower counts ratchet the baseline down; raising it "
        f"needs a review note ({BASELINE}).")
    assert fused <= budget["fused_compiles_budget"], (
        f"TPC-H smoke compiled {fused} fused executables, budget "
        f"{budget['fused_compiles_budget']} — a second rung inside one "
        f"polymorphic tier must reuse the tier executable "
        f"({BASELINE}).")
    assert pads <= budget["pad_programs_budget"], (
        f"TPC-H smoke dispatched {pads} distinct tier-pad kernels, "
        f"budget {budget['pad_programs_budget']} — these tiny per-rung "
        f"_grow_batch compiles bypass the kernel cache, so this is the "
        f"only counter that can catch them growing ({BASELINE}).")
