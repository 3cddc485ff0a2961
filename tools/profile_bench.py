"""Per-query perf breakdown on the CPU XLA backend — where does the time go?

Reports, for each query: oracle (pyarrow) time, device time, and kernel-
cache stats so compile counts are visible; every profiled query's
QueryProfile (docs/monitoring.md) is bundled into ``BENCH_profiles.json``
next to the other BENCH artifacts.

Run:  JAX_PLATFORMS=cpu python tools/profile_bench.py [q1 q6 q5 ...]

Compare two profile bundles (this run vs an older baseline) and flag >20%
per-operator timing regressions::

    python tools/profile_bench.py --compare OLD_profiles.json NEW_profiles.json

Exit code 1 when any regression is flagged — wire it into CI as a perf
ratchet alongside the tier-1 tests.
"""
import os
import sys


def compare_main(old_path: str, new_path: str, threshold: float = 0.20
                 ) -> int:
    """Diff two profile bundles ({query: QueryProfile dict}); print and
    count >threshold per-operator timing regressions."""
    # Import inside so --compare works without touching jax/backends.
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    from spark_rapids_tpu.metrics.profile import (compare_profiles,
                                                  load_profiles)
    old = load_profiles(old_path)
    new = load_profiles(new_path)
    n_regressions = 0
    for name in sorted(set(old) & set(new)):
        if not isinstance(old[name], dict) or not isinstance(new[name], dict):
            continue
        regs = compare_profiles(old[name], new[name], threshold=threshold)
        for r in regs:
            n_regressions += 1
            print(f"REGRESSION {name} {r['path']} {r['metric']}: "
                  f"{r['old'] / 1e6:.1f}ms -> {r['new'] / 1e6:.1f}ms "
                  f"({r['ratio']:.2f}x)")
    only = sorted(set(old) ^ set(new))
    if only:
        print(f"note: queries present in only one bundle (not compared): "
              f"{', '.join(only)}")
    if n_regressions:
        print(f"{n_regressions} per-operator regression(s) above "
              f"{threshold:.0%}", file=sys.stderr)
        return 1
    print(f"no per-operator timing regressions above {threshold:.0%} "
          f"across {len(set(old) & set(new))} shared query/ies")
    return 0


def main():
    os.environ["JAX_PLATFORM_NAME"] = "cpu"
    os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    import jax

    jax.config.update("jax_platforms", "cpu")

    import time

    import numpy as np
    from spark_rapids_tpu.metrics.profile import dump_profiles
    from spark_rapids_tpu.session import TpuSession
    from spark_rapids_tpu.utils import kernel_cache as KC
    from spark_rapids_tpu.workloads import tpch

    names = sys.argv[1:] or ["q1", "q6", "q3", "q5"]
    n_li = 1 << 20
    tables = tpch.gen_tables(n_li, seed=42)
    cpu = TpuSession({"spark.rapids.sql.enabled": False})
    tpu = TpuSession({"spark.rapids.sql.enabled": True,
                      "spark.rapids.sql.variableFloatAgg.enabled": True,
                      "spark.rapids.tpu.metrics.level": "MODERATE"})
    cpu_t = tpch.load(cpu, tables)
    tpu_t = tpch.load(tpu, tables)

    def timed(fn, reps=5):
        ts = []
        for _ in range(reps):
            t0 = time.perf_counter()
            fn()
            ts.append(time.perf_counter() - t0)
        return float(np.median(ts)) * 1e3

    profiles = {}
    for name in names:
        q = tpch.QUERIES[name]
        q(cpu_t).collect()
        q(tpu_t).collect()  # warmup/compile
        stats0 = KC.cache_stats()
        cpu_ms = timed(lambda: q(cpu_t).collect())
        tpu_ms = timed(lambda: q(tpu_t).collect())
        stats1 = KC.cache_stats()
        profiles[name] = tpu.last_query_profile()
        print(f"{name}: cpu={cpu_ms:.1f}ms tpu={tpu_ms:.1f}ms "
              f"ratio={cpu_ms / tpu_ms:.2f} "
              f"kernel_lookups/run~{(stats1['hits'] - stats0['hits']) / 5:.0f}"
              )

    prof_path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "BENCH_profiles.json")
    dump_profiles(prof_path, profiles)
    print(f"wrote {len(profiles)} query profiles to {prof_path} "
          f"(diff runs with: python tools/profile_bench.py --compare "
          f"OLD.json {os.path.basename(prof_path)})")

    # cProfile one device run of the slowest query for host-side hotspots
    import cProfile
    import pstats
    name = names[-1]
    q = tpch.QUERIES[name]
    pr = cProfile.Profile()
    pr.enable()
    for _ in range(3):
        q(tpu_t).collect()
    pr.disable()
    st = pstats.Stats(pr)
    st.sort_stats("cumulative")
    print(f"\n== cProfile {name} (3 device runs) ==")
    st.print_stats(28)


if __name__ == "__main__":
    if len(sys.argv) >= 2 and sys.argv[1] == "--compare":
        if len(sys.argv) != 4:
            print("usage: python tools/profile_bench.py --compare "
                  "OLD_profiles.json NEW_profiles.json", file=sys.stderr)
            sys.exit(2)
        sys.exit(compare_main(sys.argv[2], sys.argv[3]))
    main()
