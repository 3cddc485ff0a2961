"""Quickest proof that the engine still starts on the chip.

One process, one TPU: TPC-H SF1 tables made from ``--seed``, written to
parquet once, and q6 and q1 (q3 through ``--queries``) run through
``TpuSession`` from those files — device parquet decode, upload, the fused
executor and the download are all on the path — each twice (cold, then
warm), each answer checked against the CPU oracle session outside the
timed call. Any planned CPU operator,
any row group read on the host, any mismatch or any exception ends the run
non-zero with no result line. There is no path that passes on a CPU.

    python chip_smoke.py                 # on the chip machine; see README
    python chip_smoke.py --queries q6,q1,q3   # q3: 748 s cold on a v5e

Last line of stdout on success, and only then:
    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}
"""

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
DATA_DIR = os.path.join(HERE, ".chip_smoke_data")   # in .gitignore
SF1_LINEITEM_ROWS = 6_001_215   # TPC-H SF1: 1.5 M orders, 150 k customers
TABLES_OF = {"q6": ("lineitem",), "q1": ("lineitem",),
             "q3": ("customer", "orders", "lineitem")}
# The longest prefix of q6,q1,q3 whose COLD run fits the 1200 s a sealed
# machine with an empty compile cache gets: q3 alone compiled for 748 s on
# the v5e (CHANGES.md, PR 25), q6 + q1 take ~455 s with everything.
DEFAULT_QUERIES = "q6,q1"


def say(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


def ensure_parquet(rows: int, seed: int) -> dict:
    """{table: parquet path}; generated and written once per (rows, seed)."""
    import pyarrow as pa
    import pyarrow.parquet as pq
    from spark_rapids_tpu.workloads import tpch
    out = os.path.join(DATA_DIR, f"rows{rows}_seed{seed}")
    done = os.path.join(out, "_DONE")
    if not os.path.exists(done):
        t0 = time.perf_counter()
        tables = tpch.gen_tables(rows, seed=seed)
        os.makedirs(out, exist_ok=True)
        for name, rb in tables.items():
            # Strings dictionary-encoded, numbers PLAIN: the files the
            # smoke's recorded timings were taken on. The decoder reads
            # pyarrow's default too since PR 30 (numbers that start on a
            # dictionary and fall back to PLAIN mid-chunk); the benchmark's
            # tpch_sf1_parquet_writer_defaults cell holds that path.
            strings = [f.name for f in rb.schema if pa.types.is_string(f.type)]
            pq.write_table(pa.Table.from_batches([rb]),
                           os.path.join(out, f"{name}.parquet"),
                           use_dictionary=strings)
        open(done, "w").close()
        say(f"generated {rows} lineitem rows (seed {seed}) to {out} "
            f"in {time.perf_counter() - t0:.1f}s")
    return {f[:-len(".parquet")]: os.path.join(out, f)
            for f in sorted(os.listdir(out)) if f.endswith(".parquet")}


def run(queries, rows: int, seed: int) -> None:
    """The smoke's body: raises (or exits non-zero) on any failure."""
    import pyarrow.parquet as pq
    import spark_rapids_tpu
    from spark_rapids_tpu.session import TpuSession
    from spark_rapids_tpu.workloads import tpch
    from spark_rapids_tpu.workloads.compare import tables_match
    say(f"compile cache: {spark_rapids_tpu.COMPILE_CACHE_DIR} "
        f"(JAX_COMPILATION_CACHE_DIR "
        f"{'set' if os.environ.get('JAX_COMPILATION_CACHE_DIR') else 'unset'})")
    paths = ensure_parquet(rows, seed)
    row_groups = {name: pq.ParquetFile(path).metadata.num_row_groups
                  for name, path in paths.items()}
    tpu = TpuSession({"spark.rapids.sql.enabled": True,
                      # hard error on any planned CPU operator, and on a
                      # row group the device decoder could not read
                      "spark.rapids.sql.test.enabled": True,
                      # without it sum(double) stays on the host
                      "spark.rapids.sql.variableFloatAgg.enabled": True,
                      "spark.rapids.tpu.metrics.level": "ESSENTIAL"})
    cpu = TpuSession({"spark.rapids.sql.enabled": False})
    tpu_t = {name: tpu.read.parquet(path) for name, path in paths.items()}
    cpu_t = {name: cpu.read.parquet(path) for name, path in paths.items()}
    for name in queries:
        query = tpch.QUERIES[name]
        want = query(cpu_t).collect()
        scanned = sum(row_groups[t] for t in TABLES_OF[name])
        for phase in ("cold", "warm"):
            t0 = time.perf_counter()
            got = query(tpu_t).collect()
            secs = time.perf_counter() - t0
            prof = tpu.last_query_profile()
            comp = prof.engine["compile"]
            totals = prof.totals()
            decoded = totals.get("deviceDecodedRowGroups", 0)
            fallback = totals.get("hostFallbackRowGroups", 0)
            say(f"{name} {phase} seconds={secs:.3f} rows={got.num_rows} "
                f"compiles={comp['xlaCompiles']} "
                f"cache_misses={comp['persistentCacheMisses']} "
                f"compile_seconds={comp['xlaCompileNs'] / 1e9:.3f} "
                f"planRuns={totals.get('planRuns', 0)} "
                f"deviceDecodedRowGroups={decoded} scannedRowGroups={scanned} "
                f"hostFallbackRowGroups={fallback} "
                f"hbmPeakBytesInUse={prof.engine['hbmPeakBytesInUse']}")
            # a re-run inside collect (join capacity learning) decodes the
            # files again: decoded is scanned x planRuns
            if fallback or decoded < scanned:
                sys.exit(f"chip_smoke: {name} {phase}: {fallback} row groups "
                         f"read on the host, {decoded} decoded on the device "
                         f"of {scanned} in the scanned files")
            if not tables_match(got, want):
                sys.exit(f"chip_smoke: {name} {phase}: answer differs from "
                         "the CPU oracle's")
    tpu.close()
    cpu.close()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--queries", default=DEFAULT_QUERIES,
                    help="comma-separated, run in this order "
                         f"(default {DEFAULT_QUERIES})")
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--rows", type=int, default=SF1_LINEITEM_ROWS,
                    help="lineitem rows; for the CPU rehearsal of run() "
                         "only (default: TPC-H SF1)")
    args = ap.parse_args(argv)
    queries = [q for q in args.queries.split(",") if q]
    unknown = [q for q in queries if q not in TABLES_OF]
    if unknown or not queries:
        ap.error(f"--queries takes a subset of {sorted(TABLES_OF)}")
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: jax found no TPU (platform "
              f"{devices[0].platform!r}); this script only runs on the chip",
              file=sys.stderr)
        return 2
    say(f"device {devices[0].device_kind} x{len(devices)}")
    run(queries, args.rows, args.seed)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
