"""One run of one benchmark cell on the chip this process is started on.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

Everything about a cell is data found by name: the cell, its configuration
and its metrics in ``BENCHMARK.json``; the configuration's file there, which
names its generator (``benchmarks/generators/<g>.py``) and its loader
(``benchmarks/loaders/<l>.py``); the traffic mix in
``benchmarks/traffic/<traffic>.json``; each query in
``benchmarks/queries/<q>.py``; each metric's reader in
``benchmarks/metrics/<name>.py``. See ``benchmarks/README.md``.

Set-up (``setup_s``, process start to window start): imports, the tables
made from ``--seed`` by the generator and written to parquet, one
``TpuSession``, the loader (files as they are, or ``cache()``), and every
query of the mix once. Window: one client in a closed loop, the mix's
queries in turn through ``DataFrame.collect()``; a new query starts while
less than ``--seconds`` have passed. The host's counters are read at its
two ends. After it: the device's peak memory, the session closed, the plain
reference over the same files and the comparison that decides ``correct``.
The last line of stdout is the result, printed only on a TPU that
``peaks.json`` knows.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import glob  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import random  # noqa: E402
import resource  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
DATA_DIR = os.path.join(HERE, ".data")     # listed in benchmarks/.gitignore
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
QUERY_SPAN = "bench.query"
sys.path.insert(0, HERE)

import columns  # noqa: E402
import compare  # noqa: E402


def say(msg: str) -> None:
    print(f"[bench] {msg}", flush=True)


def load_module(path: str):
    spec = importlib.util.spec_from_file_location(
        "bench_" + os.path.basename(path)[:-3].replace(".", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def load_json(path: str):
    with open(path) as f:
        return json.load(f)


def load_cell(name: str) -> dict:
    """The cell of ``BENCHMARK.json`` with that name."""
    bench = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"run.py: no cell {name!r}; have {sorted(cells)}")
    cell = cells[name]
    return build_cell(bench, name, cell["config"], cell["traffic"],
                      cell["chips"])


def build_cell(bench: dict, name: str, config_name: str, traffic_name: str,
               chips: int) -> dict:
    """A cell with its configuration, traffic mix, queries and the metrics
    that report in it, all found by name."""
    entry = next(c for c in bench["configs"] if c["name"] == config_name)
    traffic = load_json(os.path.join(HERE, "traffic", traffic_name + ".json"))
    config = load_json(os.path.join(ROOT, entry["file"]))
    # an entry of the mix: a query's name, or {"query", "name", "params"}
    mix = [{"name": e, "query": e, "params": {}} if isinstance(e, str)
           else {"name": e.get("name", e["query"]), "query": e["query"],
                 "params": e.get("params", {})}
           for e in traffic["queries"]]

    def here(kind):
        return [m for m in bench[kind]
                if name in m.get("workloads", [name])]

    def module(kind, which):
        return load_module(os.path.join(HERE, kind, which + ".py"))
    return {"name": name, "chips": chips,
            "config_name": config_name, "config": config,
            "generator": module("generators", config["generator"]),
            "loader": module("loaders", config["loader"]),
            "traffic": traffic, "mix": mix,
            "queries": {q: module("queries", q)
                        for q in dict.fromkeys(e["query"] for e in mix)},
            "end_to_end": here("end_to_end"), "per_layer": here("per_layer")}


def schedule(mix: list, order: str, seed: int):
    """The mix's entries for ever: in turn, or (``shuffled``) every round in
    an order of its own drawn from the seed, so that every seed sends the
    same set of queries."""
    rng = random.Random(seed)
    while True:
        round_ = list(mix)
        if order == "shuffled":
            rng.shuffle(round_)
        yield from round_


def host_reading() -> dict:
    """What the host's kernel counts, read at one end of the window: the
    load averages, the jiffies of all CPUs by state (``/proc/stat``) and
    this process's own CPU seconds and context switches."""
    with open("/proc/loadavg") as f:
        load = [float(x) for x in f.read().split()[:3]]
    with open("/proc/stat") as f:
        jiffies = [int(x) for x in f.readline().split()[1:]]
    usage = resource.getrusage(resource.RUSAGE_SELF)
    return {"loadavg": load, "jiffies": jiffies,
            "cpu_s": usage.ru_utime + usage.ru_stime,
            "nvcsw": usage.ru_nvcsw, "nivcsw": usage.ru_nivcsw}


def host_of(before: dict, after: dict) -> dict:
    """The result line's ``host`` object, from the readings at the window's
    two ends: what else the machine was doing while the window ran."""
    # /proc/stat: user nice system idle iowait irq softirq steal [guest ...]
    spent = [b - a for a, b in zip(before["jiffies"], after["jiffies"])][:8]
    spent += [0] * (8 - len(spent))
    total = sum(spent) or 1
    return {"cpu_count": os.cpu_count(),
            "affinity": len(os.sched_getaffinity(0)),
            "loadavg_before": before["loadavg"],
            "loadavg_after": after["loadavg"],
            "steal_share": spent[7] / total,
            "busy_share": (total - spent[3] - spent[4]) / total,
            "process_cpu_s": after["cpu_s"] - before["cpu_s"],
            "nvcsw": after["nvcsw"] - before["nvcsw"],
            "nivcsw": after["nivcsw"] - before["nivcsw"]}


def counters_of(profile) -> dict:
    """Every numeric metric of a QueryProfile summed by name. Operators of
    one node name share one metrics entry, so each node name counts once."""
    total, seen = {}, set()

    def add(metrics):
        for key, value in metrics.items():
            if isinstance(value, (int, float)) and not isinstance(value, bool):
                total[key] = total.get(key, 0) + value

    def walk(node):
        if node["name"] not in seen:
            seen.add(node["name"])
            add(node["metrics"])
        for child in node["children"]:
            walk(child)
    if profile is not None:
        walk(profile.tree)
        for metrics in profile.extras.values():
            add(metrics)
    return total


def tables_of(queries: dict) -> list:
    """The tables a cell's queries read, each once."""
    return list(dict.fromkeys(t for q in queries.values() for t in q.COLUMNS))


def reference_answers(cell: dict, paths: dict, real=None) -> dict:
    """{entry of the mix: the plain reference's answer} over the parquet
    files, in float64, or in ``real`` for the control."""
    out, loaded = {}, {}
    for entry in cell["mix"]:
        query = cell["queries"][entry["query"]]
        if entry["query"] not in loaded:
            loaded[entry["query"]] = {
                t: columns.load_columns(paths[t], cols)
                for t, cols in query.COLUMNS.items()}
        kw = dict(entry["params"], **({} if real is None else {"real": real}))
        out[entry["name"]] = query.reference(loaded[entry["query"]], **kw)
    return out


def run_cell(cell: dict, seed: int, seconds: float, trace: bool,
             scale: float = 1.0, keep_trace=None) -> dict:
    """Set-up, window, reference and comparison of one run. Returns what
    the readers and the result line are made from; looks for no chip."""
    import jax
    import pyarrow.parquet as pq
    from spark_rapids_tpu.session import TpuSession

    compiles = []   # (perf_counter at the event's end, seconds)
    jax_seconds = {}   # every duration event of JAX, summed by name

    def on_duration(event, secs, **kw):
        jax_seconds[event] = jax_seconds.get(event, 0.0) + secs
        if event == COMPILE_EVENT:
            compiles.append((time.perf_counter(), secs))
    jax.monitoring.register_event_duration_secs_listener(on_duration)
    config, traffic, queries = cell["config"], cell["traffic"], cell["queries"]
    phases = {"import": time.perf_counter() - T_START}

    t0 = time.perf_counter()
    paths, row_counts = cell["generator"].ensure(
        DATA_DIR, config, tables_of(queries), seed, scale)
    row_groups = {t: pq.ParquetFile(p).metadata.num_row_groups
                  for t, p in paths.items()}
    phases["data"] = time.perf_counter() - t0

    setup_counters, noted = {}, [None]

    def note(into: dict) -> None:
        """Add the last query's profile counters, if it is a new one."""
        profile = session.last_query_profile()
        if profile is not noted[0]:
            noted[0] = profile
            for key, value in counters_of(profile).items():
                into[key] = into.get(key, 0) + value

    t0 = time.perf_counter()
    session = TpuSession(dict(config["conf"]))
    tables = cell["loader"].load(session, paths)
    note(setup_counters)
    phases["load"] = time.perf_counter() - t0

    t0 = time.perf_counter()
    for entry in cell["mix"]:                # compiles, or loads the cache
        t1 = time.perf_counter()
        queries[entry["query"]].build(tables, **entry["params"]).collect()
        note(setup_counters)
        say(f"first {entry['name']} {time.perf_counter() - t1:.3f} s")
    phases["first_queries"] = time.perf_counter() - t0

    trace_dir = os.path.join(DATA_DIR, "trace")
    tracing = False
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0      # a 20 s query: too many events
        options.host_tracer_level = 2        # TraceAnnotation spans
        jax.profiler.start_trace(trace_dir, profiler_options=options)
        tracing = True
    say("phases " + " ".join(f"{k}={v:.3f}" for k, v in phases.items()))
    say("jax seconds in set-up " + " ".join(
        f"{k.rsplit('/', 1)[-1]}={v:.3f}" for k, v in jax_seconds.items()))

    turns = schedule(cell["mix"], traffic.get("order", "round_robin"), seed)
    answers, done, latencies, counters, traced = [], [], [], {}, []
    attempted = failed = 0
    host_before = host_reading()
    w0 = time.perf_counter()
    setup_s = w0 - T_START
    while True:
        entry = next(turns)
        build = queries[entry["query"]].build
        attempted += 1
        t1 = time.perf_counter()
        try:
            if tracing:
                with jax.profiler.TraceAnnotation(QUERY_SPAN):
                    answer = build(tables, **entry["params"]).collect()
                traced.append(entry["query"])
            else:
                answer = build(tables, **entry["params"]).collect()
            answers.append((entry["name"], answer))
            done.append(entry["query"])
            note(counters)
        except Exception as e:  # noqa: BLE001 - a failed query is counted
            failed += 1
            say(f"query {attempted} ({entry['name']}) raised "
                f"{type(e).__name__}: {e}")
        t2 = time.perf_counter()
        latencies.append(t2 - t1)
        if tracing and t2 - w0 >= traffic["trace_seconds"]:
            jax.profiler.stop_trace()
            tracing = False
        if time.perf_counter() - w0 >= seconds:
            break
    w1 = t2
    host = host_of(host_before, host_reading())
    if tracing:
        jax.profiler.stop_trace()
    say(f"setup_s {setup_s:.3f} latencies "
        + " ".join(f"{x:.4f}" for x in latencies))

    device = jax.devices()[0]
    memory_peak = (device.memory_stats() or {}).get("peak_bytes_in_use", 0)
    del tables
    session.close()

    reduced = None
    if trace:
        import trace_reduce
        found = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                          recursive=True)
        if found:
            t0 = time.perf_counter()
            reduced = trace_reduce.reduce(found[0], QUERY_SPAN)
            say(f"trace {os.path.getsize(found[0])} bytes reduced in "
                f"{time.perf_counter() - t0:.1f} s")
            if keep_trace:
                os.makedirs(os.path.dirname(keep_trace) or ".", exist_ok=True)
                shutil.copy(found[0], keep_trace)
        shutil.rmtree(trace_dir, ignore_errors=True)

    run = {
        "cell": cell, "seed": seed, "seconds": seconds, "scale": scale,
        "queries": queries, "row_counts": row_counts,
        "row_groups": row_groups, "setup_s": setup_s, "phases": phases,
        "attempted": attempted, "failed": failed,
        "completed": attempted - failed, "latencies": latencies,
        "window_s": w1 - w0, "host": host,
        "first": [e["query"] for e in cell["mix"]],
        "done": done, "setup_counters": setup_counters,
        "counters": counters, "traced_queries": traced, "trace": reduced,
        "compile_events": [(t - w0, secs) for t, secs in compiles],
        "memory_peak_bytes": int(memory_peak),
    }
    t0 = time.perf_counter()
    held = {"failed": (failed, 0),
            "host_row_groups": (
                int(setup_counters.get("hostFallbackRowGroups", 0)
                    + counters.get("hostFallbackRowGroups", 0)), 0)}
    held.update(cell["loader"].compared(run))
    run["correct"], run["compared"] = compare.judge(
        [(n, compare.answer_columns(a)) for n, a in answers],
        reference_answers(cell, paths), cell, held)
    say(f"reference {time.perf_counter() - t0:.3f} s")
    return run


def read_metrics(run: dict, kind: str, peaks: dict) -> dict:
    """{name: {"value", "unit"}} from each metric's own reader; a reader
    that finds nothing to read returns None and the metric is left out."""
    ctx = dict(run, peaks=peaks)
    out = {}
    for metric in run["cell"][kind]:
        reader = load_module(os.path.join(HERE, "metrics",
                                          metric["name"] + ".py"))
        value = reader.read(ctx)
        if value is not None:
            out[metric["name"]] = {"value": float(value),
                                   "unit": metric["unit"]}
    return out


def result_line(run: dict, trace: bool, device: dict, peaks: dict) -> dict:
    device = dict(device, memory_peak_bytes=run["memory_peak_bytes"])
    line = {"correct": run["correct"], "attempted": run["attempted"],
            "failed": run["failed"],
            "metrics": read_metrics(
                run, "per_layer" if trace else "end_to_end", peaks),
            "device": device}
    if trace and run["trace"]:
        device["busy_s"] = run["trace"]["busy_s"]
        device["window_s"] = run["trace"]["window_s"]
        line["breakdown"] = {"device_ops": run["trace"]["device_ops"],
                             "idle_gaps": run["trace"]["idle_gaps"]}
    line["host"] = run["host"]
    line["scale"] = run["scale"]    # 1.0: the cell's own size
    line["compared"] = run["compared"]
    return line


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--scale", type=float, default=1.0,
                    help="share of the configuration's rows, for a trial "
                         "below the cell's size; it is in the result line")
    ap.add_argument("--keep-trace", default=None,
                    help="copy the run's .xplane.pb here (for a look by hand)")
    args = ap.parse_args(argv)
    if not os.path.isdir(os.path.join(ROOT, "spark_rapids_tpu")):
        print(f"run.py: no engine beside the benchmark in {ROOT}",
              file=sys.stderr)
        return 3
    sys.path.insert(0, ROOT)
    cell = load_cell(args.workload)
    import jax
    devices = jax.devices()
    all_peaks = load_json(os.path.join(HERE, "peaks.json"))
    kind = devices[0].device_kind
    if (devices[0].platform != "tpu" or kind not in all_peaks
            or len(devices) < cell["chips"]):
        print(f"run.py: needs {cell['chips']} TPU chip(s) of a kind in "
              f"peaks.json; found {len(devices)} x {devices[0].platform} "
              f"{kind!r}", file=sys.stderr)
        return 2
    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(devices)}
    say(f"cell {args.workload} seed {args.seed} on {kind} x{len(devices)}")
    run = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                   scale=args.scale, keep_trace=args.keep_trace)
    line = result_line(run, bool(args.trace), device, all_peaks[kind])
    if args.trace and "busy_s" not in line["device"]:
        print("run.py: the trace holds no device operation", file=sys.stderr)
        return 4
    for name, c in line["compared"].items():
        print(f"compared {name} = {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    print(f"correct = {line['correct']}", file=sys.stderr, flush=True)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
