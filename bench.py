"""Benchmark: TPC-H-like suite + TPCxBB-like scoring query, device vs the
CPU oracle — BASELINE.md configs 1-3 (the reference's own harnesses are
TpchLikeSpark / TpcxbbLikeSpark; its headline chart is the TPCxBB-like
suite). The metric is the suite GEOMEAN, matching BASELINE.md's stated
"geomean query time" metric.

Prints one cumulative JSON line after EVERY query plus the final line;
the driver takes stdout's LAST parsed line:
  {"metric": ..., "value": ..., "unit": ..., "vs_baseline": ...}

Resilience contract: this script ALWAYS leaves a valid JSON line behind
— the per-query checkpoint lines mean even a SIGKILL mid-suite yields
the cumulative totals up to the last completed query (the BENCH_r05
rc=124 parsed:null failure class), and a SIGTERM/normal-exit mid-suite
additionally dumps a final partial line via the installed handlers. If
jax finds no accelerator the script exits non-zero before it prints
anything: a number from the CPU backend is never printed under a device
metric's name (the reference likewise fails fast on executor init,
Plugin.scala:130-137).

Methodology (TPC practice + the reference's CPU-vs-accelerator compare):
generated tables are written to PARQUET once per run and every timed run
SCANS them — the device parquet decoder is inside the headline number
(ISSUE 11 / ROADMAP item 1; BASELINE's configs say "SF=N parquet").
Headline scale is 4M lineitem rows (--rows), where the CPU oracle's
compute grows past the device's fixed per-query floor. Each query runs
once for compile warmup WITH a full-row correctness gate against the
oracle, then is timed end-to-end (scan -> plan -> execute -> result
download), median of 3. value = geomean TPU time; vs_baseline =
geomean(CPU time / TPU time), >1 = TPU wins; cold_vs_baseline clears the
upload memo first so host prep + transfer are fully timed too.
"""

import argparse
import atexit
import contextlib
import json
import math
import os
import signal
import sys
import tempfile
import time

#: Default headline scale: 4M lineitem rows — at 1M the engine's fixed
#: per-query cost (plan, dispatch, download) dwarfs compute and the CPU
#: oracle finishes under it.
DEFAULT_ROWS = 1 << 22

# -- cumulative checkpointing (VERDICT round-5 ask) -------------------------
#: The last cumulative payload emitted; the SIGTERM/atexit dumpers re-emit
#: it with an error note so an external kill can never yield parsed:null.
_CHECKPOINT = {"payload": None, "done": False}

#: cleanups the signal-exit path must run itself: os._exit skips atexit,
#: so anything registered only there (the parquet staging dir rmtree)
#: would leak on every external SIGTERM/timeout kill — the exact rc=124
#: class the kill-dump exists for.
_KILL_CLEANUPS: list = []


def emit_checkpoint(payload: dict) -> None:
    """Print one cumulative JSON line NOW (the driver takes the last
    parsed line, so each checkpoint supersedes the previous one)."""
    payload = dict(payload)
    payload["partial"] = True
    _CHECKPOINT["payload"] = payload
    print(json.dumps(payload), flush=True)


def emit_final(payload: dict) -> None:
    _CHECKPOINT["done"] = True
    print(json.dumps(payload), flush=True)


def install_kill_dump() -> None:
    """SIGTERM/SIGINT + atexit dumpers: re-emit the last cumulative
    checkpoint with an error note, flush, and (for signals) exit — the
    always-emit-JSON contract survives external timeouts."""
    def dump(note: str) -> None:
        if not _CHECKPOINT["done"]:
            # Before the first per-query checkpoint (table gen + parquet
            # write + first warmup can take minutes at 4M rows) there is
            # no cumulative payload yet — a kill there must still leave a
            # parseable line, not rc=0 with no JSON.
            p = dict(_CHECKPOINT["payload"] or
                     {"metric": "tpchlike_geomean_device_time",
                      "value": 0.0, "unit": "ms", "vs_baseline": 0.0,
                      "partial": True})
            p["error"] = note
            print(json.dumps(p), flush=True)
        sys.stdout.flush()

    def on_signal(signum, frame):
        dump(f"killed by signal {signum} mid-suite; cumulative totals up "
             "to the last completed query")
        for fn in list(_KILL_CLEANUPS):  # os._exit skips atexit
            try:
                fn()
            except Exception:
                pass
        os._exit(0)  # exit-0 contract: the JSON just printed is valid
    try:
        signal.signal(signal.SIGTERM, on_signal)
        signal.signal(signal.SIGINT, on_signal)
    except (ValueError, OSError):
        pass  # not the main thread / restricted platform
    atexit.register(
        lambda: dump("process exited mid-suite; cumulative totals up to "
                     "the last completed query"))

#: Suite wall-clock budget (seconds) when --budget is not given: BENCH_r05
#: was killed by an external timeout (rc=124, bb_q01 spent 646s in
#: warmup+compile); the budget makes the suite skip over-budget work and
#: ALWAYS emit its JSON instead.
DEFAULT_BUDGET_S = 2400.0
#: Per-query ceiling (seconds) on warmup+correctness+timing for one query.
DEFAULT_QUERY_BUDGET_S = 600.0


class QueryBudgetExceeded(Exception):
    """Raised by the SIGALRM guard when one query overruns its budget."""


@contextlib.contextmanager
def query_budget(seconds):
    """Bound one query's warmup+timing with a SIGALRM (main thread only;
    no-op where unavailable). A query that overruns raises
    QueryBudgetExceeded at the next Python bytecode, is recorded as
    skipped, and the suite moves on — the always-complete contract."""
    if seconds is None or seconds <= 0 or not hasattr(signal, "SIGALRM") \
            or threading_main() is False:
        yield
        return

    def on_alarm(signum, frame):
        raise QueryBudgetExceeded(f"query budget {seconds:.0f}s exceeded")
    old = signal.signal(signal.SIGALRM, on_alarm)
    signal.setitimer(signal.ITIMER_REAL, seconds)
    try:
        yield
    finally:
        signal.setitimer(signal.ITIMER_REAL, 0)
        signal.signal(signal.SIGALRM, old)


def threading_main() -> bool:
    import threading
    return threading.current_thread() is threading.main_thread()


def timed(fn, reps=3):
    import numpy as np
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        fn()
        times.append(time.perf_counter() - t0)
    return float(np.median(times))


def require_accelerator() -> dict:
    """The device this run measures, as jax reports it. No accelerator
    => non-zero exit: there is no probe child, no retry and no re-run on
    the CPU backend."""
    import jax
    devs = jax.devices()
    if devs[0].platform == "cpu":
        sys.exit("bench.py: jax found no accelerator (platform 'cpu'); "
                 "this benchmark measures the device and does not fall "
                 "back to the CPU backend")
    return {"backend": devs[0].platform,
            "device_kind": devs[0].device_kind,
            "device_count": len(devs)}


def _geo(xs):
    return math.exp(sum(math.log(x) for x in xs) / len(xs))


def write_parquet_tables(tables: dict, out_dir: str) -> dict:
    """Write generated tables to parquet ONCE per run (ISSUE 11 /
    ROADMAP item 1: the timed region must include the device parquet
    decoder, which had never appeared in a headline number). Returns
    {table name: file path}."""
    import pyarrow as pa
    import pyarrow.parquet as pq
    os.makedirs(out_dir, exist_ok=True)
    paths = {}
    t0 = time.perf_counter()
    total = 0
    for name, rb in tables.items():
        path = os.path.join(out_dir, f"{name}.parquet")
        pq.write_table(pa.Table.from_batches([rb]), path)
        total += os.path.getsize(path)
        paths[name] = path
    print(f"[bench] wrote {len(paths)} parquet tables "
          f"({total / 1e6:.0f} MB) to {out_dir} "
          f"in {time.perf_counter() - t0:.1f}s", file=sys.stderr)
    return paths


def parquet_frames(session, paths: dict) -> dict:
    """Per-engine DataFrames that SCAN the parquet files — every collect
    re-reads them, so scan+decode are inside the timed region."""
    return {name: session.read.parquet(path)
            for name, path in paths.items()}


def measure_pipeline_overlap(tpch, tables, timed_fn):
    """ISSUE-5 acceptance probe: cold uncached wall time of the
    multi-boundary join queries q3/q5 with the pipeline layer enabled
    (default) vs spark.rapids.tpu.pipeline.enabled=false, on this bench
    backend. >1 = the pipeline wins; the overlap pays where uploads are
    mostly waits on the host-to-device copy, not where the host itself is
    saturated."""
    from spark_rapids_tpu.data import upload_cache
    from spark_rapids_tpu.session import TpuSession
    out = {}
    on = TpuSession({"spark.rapids.sql.enabled": True,
                     "spark.rapids.sql.variableFloatAgg.enabled": True})
    off = on.with_conf(**{"spark.rapids.tpu.pipeline.enabled": False})
    t_on = tpch.load(on, tables, cache=False)
    t_off = tpch.load(off, tables, cache=False)
    for name in ("q3", "q5"):
        q = tpch.QUERIES[name]
        q(t_on).collect()  # shared warmup (same plan shape both modes)
        q(t_off).collect()

        def cold(t):
            upload_cache.clear()
            return q(t).collect()
        t_pipe = timed_fn(lambda: cold(t_on))
        t_serial = timed_fn(lambda: cold(t_off))
        out[f"pipeline_cold_speedup_{name}"] = round(t_serial / t_pipe, 3)
        print(f"[bench] pipeline A/B {name}: on={t_pipe*1e3:.1f}ms "
              f"off={t_serial*1e3:.1f}ms "
              f"speedup={t_serial/t_pipe:.2f}", file=sys.stderr)
    return out


def run_suite(budget_s=DEFAULT_BUDGET_S,
              query_budget_s=DEFAULT_QUERY_BUDGET_S,
              n_rows=DEFAULT_ROWS):
    from spark_rapids_tpu.session import TpuSession
    from spark_rapids_tpu.utils import kernel_cache as KC
    from spark_rapids_tpu.workloads import tpch
    from spark_rapids_tpu.workloads.compare import tables_match
    suite_t0 = time.perf_counter()
    diag = require_accelerator()
    print(f"[bench] backend={diag['backend']} "
          f"kind={diag['device_kind']} count={diag['device_count']}",
          file=sys.stderr)

    tables = tpch.gen_tables(n_rows, seed=42)

    cpu = TpuSession({"spark.rapids.sql.enabled": False})
    # variableFloatAgg: same stance as the reference's benchmarks — float
    # aggregation order differs from CPU (documented incompat,
    # docs/compatibility.md); the correctness gate compares with tolerance.
    # ESSENTIAL metrics so every timed query leaves a QueryProfile
    # (emitted next to the BENCH_*.json artifacts; docs/monitoring.md).
    tpu = TpuSession({"spark.rapids.sql.enabled": True,
                      "spark.rapids.sql.variableFloatAgg.enabled": True,
                      "spark.rapids.tpu.metrics.level": "ESSENTIAL"})
    # PARQUET-INCLUSIVE timed region (ISSUE 11 / ROADMAP item 1): the
    # generated tables land in parquet once, and every timed collect
    # SCANS them — the device parquet decoder finally shows up in the
    # headline number instead of only in its unit tests.
    pq_dir = tempfile.mkdtemp(prefix="bench_parquet_")
    # The staged tables are hundreds of MB at 4M rows; repeated runs must
    # not accumulate them until /tmp fills.
    import functools
    import shutil
    cleanup = functools.partial(shutil.rmtree, pq_dir, ignore_errors=True)
    atexit.register(cleanup)
    # The signal kill path exits via os._exit (skipping atexit), so it
    # runs the same callable itself before exiting.
    _KILL_CLEANUPS.append(cleanup)
    cpu_t = parquet_frames(cpu, write_parquet_tables(tables, pq_dir))
    tpu_t = parquet_frames(
        tpu, {n: os.path.join(pq_dir, f"{n}.parquet") for n in tables})

    from spark_rapids_tpu.data import upload_cache

    ratios, tpu_times, cold_ratios = [], [], []
    # Subset: every operator shape (scan/filter/project/agg, 1-4 joins,
    # semi join, disjunctive band join, conditional sums, float scoring)
    # without double-paying remote-compile time for shapes q5/q3 already
    # cover (q10/q18 re-run under pytest, tests/test_tpch.py).
    bench_queries = ["q1", "q3", "q4", "q5", "q6", "q12", "q14", "q19",
                     "xbb_score"]
    # TPCxBB suite entries (the reference's headline chart is TPCxBB;
    # round-5 adds the basket self-join, ML feature build, and
    # clickstream sessionization shapes from workloads/tpcxbb.py)
    from spark_rapids_tpu.workloads import tpcxbb
    xbb_tables = tpcxbb.gen_tables(1 << 17, seed=42)
    xbb_dir = os.path.join(pq_dir, "xbb")
    bb_cpu = parquet_frames(cpu, write_parquet_tables(xbb_tables, xbb_dir))
    bb_tpu = parquet_frames(
        tpu, {n: os.path.join(xbb_dir, f"{n}.parquet") for n in xbb_tables})
    xbb_specs = [("bb_q01", tpcxbb.q01), ("bb_q05", tpcxbb.q05),
                 ("bb_q30", tpcxbb.q30)]
    runs = [(name, tpch.QUERIES[name], cpu_t, tpu_t)
            for name in bench_queries]
    runs += [(name, q, bb_cpu, bb_tpu) for name, q in xbb_specs]
    from spark_rapids_tpu.compile import executables as _executables
    from spark_rapids_tpu.exec import fusion
    profiles = {}
    skipped = {}
    # Per-query compile breakdown (ISSUE 6): compile_seconds,
    # kernels_compiled, executables_reused, cold_vs_cached_ratio land in
    # the BENCH JSON so the win curve is machine-readable (the ROADMAP
    # success metric is cold within 2x of cached, per query).
    query_compile = {}

    def cumulative(extra_error=None):
        """The cumulative BENCH payload over queries completed SO FAR —
        emitted as a checkpoint line after every query, so an external
        kill at any point leaves machine-readable totals behind."""
        out = {
            "metric": f"tpch_tpcxbb_{len(tpu_times)}q_{n_rows}row_"
                      "parquet_geomean_device_time",
            "value": round(_geo(tpu_times) * 1000, 2) if tpu_times else 0.0,
            "unit": "ms",
            "vs_baseline": round(_geo(ratios), 3) if ratios else 0.0,
            "cold_vs_baseline": round(_geo(cold_ratios), 3)
            if cold_ratios else 0.0,
            "completed": len(tpu_times),
            "queries": query_compile,
            **diag,
        }
        if skipped:
            out["skipped"] = skipped
        if extra_error:
            out["error"] = extra_error
        return out

    for name, q, cpu_frames, tpu_frames in runs:
        elapsed = time.perf_counter() - suite_t0
        if budget_s and elapsed > budget_s:
            # Wall-clock budget exhausted (rc=124 class of failure in
            # BENCH_r05): record the skip and keep the JSON contract.
            skipped[name] = (f"suite budget {budget_s:.0f}s exhausted "
                             f"after {elapsed:.0f}s; warmup skipped")
            print(f"[bench] SKIP {name}: {skipped[name]}", file=sys.stderr)
            emit_checkpoint(cumulative())
            continue
        per_query = query_budget_s
        if budget_s:
            per_query = min(per_query or budget_s, budget_s - elapsed)
        t0 = time.perf_counter()
        try:
            with query_budget(per_query):
                stats0 = KC.cache_stats()
                exe0 = _executables.stats()
                cpu_result = q(cpu_frames).collect()  # oracle
                tpu_result = q(tpu_frames).collect()  # warmup + compile
                assert tables_match(tpu_result, cpu_result), \
                    f"{name}: TPU result != CPU oracle result"
                stats1 = KC.cache_stats()
                exe1 = _executables.stats()
                # Headline: parquet scan + decode INSIDE the timed region
                # for both engines (executables and upload memo warm).
                cpu_time = timed(lambda: q(cpu_frames).collect())
                tpu_time = timed(lambda: q(tpu_frames).collect())
                # Per-query QueryProfile of the last timed device run,
                # emitted next to BENCH_*.json (tools/profile_bench.py
                # --compare diffs two bundles for >20% regressions).
                profiles[name] = tpu.last_query_profile()
                # cold: upload memo dropped first, so host-side prep +
                # transfer land fully inside the timed region too

                def cold_run():
                    upload_cache.clear()
                    return q(tpu_frames).collect()
                ctpu = timed(cold_run, reps=1)
        except QueryBudgetExceeded as e:
            skipped[name] = f"{e} (started at {t0 - suite_t0:.0f}s)"
            print(f"[bench] SKIP {name}: {skipped[name]}", file=sys.stderr)
            emit_checkpoint(cumulative())
            continue
        ratios.append(cpu_time / tpu_time)
        cold_ratios.append(cpu_time / ctpu)
        tpu_times.append(tpu_time)
        reused0 = exe0["aot_hits"] + exe0["jit_calls"] - exe0["jit_compiles"]
        reused1 = exe1["aot_hits"] + exe1["jit_calls"] - exe1["jit_compiles"]
        query_compile[name] = {
            # Fused-program compile time plus host kernel-build time paid
            # by this query's warmup run.
            "compile_seconds": round(
                exe1["compile_seconds"] - exe0["compile_seconds"]
                + (stats1["build_ns"] - stats0["build_ns"]) / 1e9, 3),
            "kernels_compiled": stats1["misses"] - stats0["misses"],
            "fused_compiles": exe1["jit_compiles"] - exe0["jit_compiles"],
            "executables_reused": reused1 - reused0,
            "ratio": round(cpu_time / tpu_time, 3),
            # ROADMAP success metric: cold within 2x of cached (<= 2.0).
            "cold_vs_cached_ratio": round(ctpu / tpu_time, 3),
        }
        # Perf evidence (VERDICT r3 item 1b): kernels compiled for this
        # query's warmup, fused-program count, and steady-state dispatch
        # counts — "compiles and matches" AND "how it runs".
        print(f"[bench] {name}: cpu={cpu_time*1e3:.1f}ms "
              f"tpu={tpu_time*1e3:.1f}ms ratio={cpu_time/tpu_time:.2f} "
              f"cold_ratio={cpu_time/ctpu:.2f} "
              f"kernels_compiled={stats1['misses'] - stats0['misses']} "
              f"compile_s={query_compile[name]['compile_seconds']:.1f} "
              f"cold_vs_cached={ctpu/tpu_time:.2f} "
              f"fused_programs={len(fusion._FUSED_CACHE)} "
              f"(warmup+compile {time.perf_counter()-t0:.0f}s)",
              file=sys.stderr)
        # Cumulative checkpoint: the rc=124 insurance — every completed
        # query updates the JSON the driver would parse after a kill.
        emit_checkpoint(cumulative())

    # Per-query QueryProfile bundle next to the BENCH_*.json artifacts
    # (best-effort: profiles must never fail the bench contract).
    try:
        from spark_rapids_tpu.metrics.profile import dump_profiles
        prof_path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                 "BENCH_profiles.json")
        dump_profiles(prof_path, profiles)
        print(f"[bench] wrote {len([p for p in profiles.values() if p])} "
              f"query profiles to {prof_path}", file=sys.stderr)
    except Exception as e:  # noqa: BLE001 - observability is best-effort
        print(f"[bench] profile dump failed: {e}", file=sys.stderr)

    # Compile-once layer counters (docs/compile-cache.md): how many fused
    # programs exist, how many AOT executables warm-up built, and how the
    # steady-state dispatches split between the AOT table and jit.
    from spark_rapids_tpu.compile import budget as _compile_budget
    from spark_rapids_tpu.compile import warmup as _compile_warmup
    _aot = _executables.stats()
    print(f"[bench] compile-once: programs={_aot['programs']} "
          f"aot_executables={_aot['aot_executables']} "
          f"aot_hits={_aot['aot_hits']} jit_calls={_aot['jit_calls']} "
          f"fused_compiles={_aot['jit_compiles']} "
          f"compile_seconds={_aot['compile_seconds']:.1f} "
          f"budget={_compile_budget.stats()} "
          f"warmup={_compile_warmup.stats()}", file=sys.stderr)

    if not tpu_times:
        return cumulative(
            extra_error="every query skipped by the wall-clock budget")
    geo_r = _geo(ratios)
    print(f"[bench] geomean ratio warm={geo_r:.3f} "
          f"cold={_geo(cold_ratios):.3f} "
          f"(>1 = device wins; both scan the parquet tables inside the "
          f"timed region — warm keeps the upload memo, cold clears it so "
          f"prep+transfer are fully timed too)",
          file=sys.stderr)
    out = {
        **cumulative(),
        # Per-query compile breakdown + suite compile totals (ISSUE 6):
        # the machine-readable compile win curve.
        "compile": {
            "fused_programs": _aot["programs"],
            "fused_compiles": _aot["jit_compiles"],
            "compile_seconds": round(_aot["compile_seconds"], 1),
            "executables_reused": _aot["aot_hits"] + _aot["jit_calls"]
            - _aot["jit_compiles"],
            "cold_vs_cached_geomean": round(_geo(
                [q["cold_vs_cached_ratio"] for q in query_compile.values()
                 if q.get("cold_vs_cached_ratio", 0) > 0] or [1.0]), 3),
        },
        # Durability evidence (ISSUE 7, docs/fault-tolerance.md): the
        # per-query recovery counters from the QueryProfiles. All-zero
        # totals PROVE the run was clean (no silent corruption was
        # retried through); non-zero counters under fault injection prove
        # the recovery machinery actually ran.
        "faults": _fault_section(profiles),
    }
    # Pipelined-execution A/B (ISSUE-5 acceptance): cold q3/q5 with the
    # pipeline on vs off, budget-guarded like everything else. Runs at a
    # reduced scale — the A/B isolates overlap, not throughput.
    if not budget_s or time.perf_counter() - suite_t0 < budget_s:
        try:
            with query_budget(query_budget_s):
                ab_tables = tables if n_rows <= (1 << 20) \
                    else tpch.gen_tables(1 << 20, seed=42)
                out.update(measure_pipeline_overlap(tpch, ab_tables, timed))
        except Exception as e:  # noqa: BLE001 — incl. QueryBudgetExceeded
            print(f"[bench] pipeline A/B skipped: {e}", file=sys.stderr)
    # Critical-path attribution (ISSUE 13): ONE traced q3 rerun OUTSIDE
    # every timed region (tracing adds spans, so it must never touch the
    # headline numbers), summarized by tools/trace_report.py into the
    # BENCH JSON — the "where did the time go" artifact the hardware win
    # curve round needs (ROADMAP item 1: per-kernel/per-stage
    # device-time attribution populated).
    if not budget_s or time.perf_counter() - suite_t0 < budget_s:
        try:
            with query_budget(query_budget_s):
                out["trace_report"] = _traced_query_report(
                    tpu, tpu_t, tpch.QUERIES["q3"])
        except Exception as e:  # noqa: BLE001 — best-effort attribution
            print(f"[bench] trace report skipped: {e}", file=sys.stderr)
    return out


def _traced_query_report(tpu, frames, q) -> dict:
    """Re-run one query with tracing on and summarize its critical path
    (tools/trace_report.py). The traced session shares the warm engine
    state, so the trace shows the STEADY-STATE timeline."""
    import functools
    import shutil

    import tools.trace_report as trace_report
    trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
    # Same accumulation guard as the parquet staging dir above: repeated
    # runs must not pile temp dirs up in /tmp (atexit + kill path).
    cleanup = functools.partial(shutil.rmtree, trace_dir,
                                ignore_errors=True)
    atexit.register(cleanup)
    _KILL_CLEANUPS.append(cleanup)
    traced = tpu.with_conf(**{
        "spark.rapids.tpu.trace.enabled": True,
        "spark.rapids.tpu.trace.dir": trace_dir,
    })
    traced.execute(q(frames)._plan)
    rep = trace_report.summarize_dir(trace_dir)
    return rep["worst"] if rep else {}


def _fault_section(profiles) -> dict:
    """The BENCH JSON ``faults`` section: suite totals + per-query
    durability counters (only queries with any non-zero counter are
    listed — the common all-clean case stays one small totals dict)."""
    totals = {"checksumFailures": 0, "shuffleBlocksRefetched": 0,
              "mapTasksRecomputed": 0, "deadlineCancels": 0,
              "peersBlacklisted": 0}
    per_query = {}
    for qname, p in profiles.items():
        engine = getattr(p, "engine", None) or {}
        dur = engine.get("durability")
        if not dur:
            continue
        counters = {k: int(dur.get(k, 0)) for k in totals}
        for k, v in counters.items():
            totals[k] += v
        if any(counters.values()):
            per_query[qname] = counters
    out = {"totals": totals}
    if per_query:
        out["queries"] = per_query
    return out


def parse_args(argv=None):
    ap = argparse.ArgumentParser(
        description="TPC-H/TPCxBB-like bench (always emits one JSON line, "
                    "always exits 0)")
    ap.add_argument(
        "--budget", type=float,
        default=float(os.environ.get("SPARK_RAPIDS_TPU_BENCH_BUDGET",
                                     DEFAULT_BUDGET_S)),
        help="suite wall-clock budget in seconds; queries whose warmup "
             "would start past it are skipped (recorded per query in the "
             "output JSON). 0 disables.")
    ap.add_argument(
        "--query-budget", type=float,
        default=float(os.environ.get("SPARK_RAPIDS_TPU_BENCH_QUERY_BUDGET",
                                     DEFAULT_QUERY_BUDGET_S)),
        help="per-query ceiling in seconds (SIGALRM-guarded warmup+timing; "
             "an over-budget query is recorded as skipped and the suite "
             "continues). 0 disables.")
    ap.add_argument(
        "--rows", type=int,
        default=int(os.environ.get("SPARK_RAPIDS_TPU_BENCH_ROWS",
                                   DEFAULT_ROWS)),
        help="lineitem row count for the parquet-inclusive headline "
             f"(default {DEFAULT_ROWS} = 4M; at 1M the CPU oracle "
             "finishes under the engine's fixed per-query cost).")
    return ap.parse_args(argv)


def main():
    args = parse_args()
    require_accelerator()   # before any JSON can be printed
    install_kill_dump()
    try:
        result = run_suite(budget_s=args.budget,
                           query_budget_s=args.query_budget,
                           n_rows=args.rows)
    except Exception as e:  # noqa: BLE001 — the JSON line must always land
        import traceback
        traceback.print_exc()
        # Keep the cumulative per-query totals gathered before the crash
        # (if any) so a late failure doesn't zero the whole artifact.
        result = dict(_CHECKPOINT["payload"] or
                      {"metric": "tpchlike_geomean_device_time",
                       "value": 0.0, "unit": "ms", "vs_baseline": 0.0})
        result.pop("partial", None)
        result["error"] = f"{type(e).__name__}: {e}"
    emit_final(result)


if __name__ == "__main__":
    main()
