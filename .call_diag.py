"""Scratch diagnostic (not committed): where a warm q6 spends its host time."""
import time, jax
import chip_smoke
from spark_rapids_tpu.session import TpuSession
from spark_rapids_tpu.workloads import tpch
import sys
paths = chip_smoke.ensure_parquet(int(sys.argv[1]) if len(sys.argv) > 1 else 6_001_215, 1)
base = {"spark.rapids.sql.enabled": True, "spark.rapids.sql.test.enabled": True,
        "spark.rapids.sql.variableFloatAgg.enabled": True}
for level, extra in (("ESSENTIAL", {}), ("DEBUG", {"spark.rapids.tpu.metrics.deviceTiming": True})):
    s = TpuSession({**base, "spark.rapids.tpu.metrics.level": level, **extra})
    t = {n: s.read.parquet(p) for n, p in paths.items()}
    for i in range(2):
        t0 = time.perf_counter(); tpch.q6(t).collect(); print(level, i, time.perf_counter() - t0, flush=True)
    print(s.last_query_profile().render(), flush=True)
print(jax.devices()[0].memory_stats())
