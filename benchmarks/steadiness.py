"""How steady a cell reads: N runs of one cell on one tree, reduced as the
driver reduces a set, beside the bounds of ``BENCHMARK.json``.

    python3 benchmarks/steadiness.py [--cut 30,60,90,120] <run.out> ...

Each file is the standard output of one ``run.py`` (its ``[bench]`` lines
and the result line last); a ``.jsonl`` file holds one result a line, bare
or under a ``line`` key beside ``seed``, ``cell`` and ``latencies``. Pure
Python over numbers that came from a chip: it needs none and measures
nothing itself.

For every end-to-end metric of the runs: the median, the range over the
median, the range less the run farthest from the median (where that narrows
it) over the median, which is the driver's spread, and the distance of the
quartiles (``statistics.quantiles``) over the median, which is the spread
the bounds are set from, less the farthest run too. For every run: its seed,
its metrics, the medians of its window's four quarters, the queries over
three times the window's median, and its ``host`` readings. ``--cut`` takes
each run's queries as consecutive windows of that many seconds and reads
``query_s`` in each: how far a longer window narrows the spread.
"""

import argparse
import importlib.util
import json
import os
import re
import statistics
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
HOST_KEYS = ("steal_share", "busy_share", "process_cpu_s", "nvcsw", "nivcsw")

_spec = importlib.util.spec_from_file_location(
    "stalled_queries_in_window",
    os.path.join(HERE, "metrics", "stalled_queries_in_window.py"))
_STALLED = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_STALLED)


def less_farthest(values: list) -> list:
    """The values without the one farthest from their median."""
    mid = statistics.median(values)
    rest = sorted(values, key=lambda v: abs(v - mid))
    return rest[:-1]


def spreads(values: list) -> dict:
    """median, range, range less the farthest run, and the quartiles'
    distance with and without it, each spread as a share of the median."""
    mid = statistics.median(values)
    out = {"n": len(values), "median": mid,
           "range": (max(values) - min(values)) / mid}
    rest = less_farthest(values) if len(values) > 2 else values
    out["less_farthest"] = min(out["range"], (max(rest) - min(rest)) / mid)

    def iqr(v):
        if len(v) < 2:
            return 0.0
        q = statistics.quantiles(v, n=4)
        return (q[2] - q[0]) / statistics.median(v)
    out["iqr"] = iqr(values)
    out["iqr_less_farthest"] = min(out["iqr"], iqr(rest))
    return out


def quarters(latencies: list) -> list:
    """Medians of the four quarters of a window, a query in the quarter of
    the elapsed time that it starts in."""
    total, t, parts = sum(latencies), 0.0, [[], [], [], []]
    for x in latencies:
        parts[min(int(4 * t / total), 3)].append(x)
        t += x
    return [statistics.median(p) for p in parts if p]


def cuts(latencies: list, seconds: float) -> list:
    """Consecutive windows of ``seconds`` over one run's queries, as run.py
    closes a window: a query belongs to the window it starts in, and a
    window's ``query_s`` is its first start to its last answer over its
    queries. A last window that the run did not fill is left out."""
    out, start, t, inside = [], 0.0, 0.0, []
    for x in latencies:
        if t - start >= seconds and inside:
            out.append(inside)
            start, inside = t, []
        inside.append(x)
        t += x
    if inside and t - start >= seconds * (1 - 1e-9):
        out.append(inside)
    return [{"queries": len(w), "query_s": sum(w) / len(w),
             "median": statistics.median(w)} for w in out]


def stalled(latencies: list) -> int:
    """Queries over three times the window's median, as the metric
    ``stalled_queries_in_window`` counts them (its reader's definition)."""
    return _STALLED.read({"latencies": latencies})


def parse_out(text: str, source: str) -> dict:
    """One run from run.py's standard output."""
    run = {"source": source, "seed": None, "cell": None, "latencies": None}
    last = None
    for line in text.splitlines():
        m = re.match(r"\[bench\] cell (\S+) seed (\d+)", line)
        if m:
            run["cell"], run["seed"] = m.group(1), int(m.group(2))
        m = re.match(r"\[bench\] setup_s \S+ latencies (.*)", line)
        if m:
            run["latencies"] = [float(x) for x in m.group(1).split()]
        if line.startswith("{"):
            last = line
    if last is None:
        raise ValueError(f"{source}: no result line")
    run["line"] = json.loads(last)
    run["latencies"] = run["latencies"] or run["line"].get("latencies")
    return run


def load_runs(paths: list) -> list:
    runs = []
    for path in paths:
        with open(path) as f:
            text = f.read()
        if path.endswith(".jsonl"):
            for i, row in enumerate(filter(None, text.splitlines())):
                row = json.loads(row)
                line = row.get("line", row)
                runs.append({"source": f"{path}:{i + 1}",
                             "seed": row.get("seed"),
                             "cell": row.get("cell"), "line": line,
                             "latencies": row.get("latencies")
                             or line.get("latencies")})
        else:
            runs.append(parse_out(text, os.path.basename(path)))
    return runs


def metric_values(runs: list) -> dict:
    """{metric: [value of each run that reports it]}."""
    out = {}
    for run in runs:
        for name, m in run["line"]["metrics"].items():
            out.setdefault(name, []).append(m["value"])
    return out


def report(runs: list, bounds: dict, cut: list) -> list:
    """The lines of the table."""
    lines = []
    cells = sorted({r["cell"] for r in runs if r["cell"]})
    lines.append(f"{len(runs)} runs" + (f" of {', '.join(cells)}"
                                        if cells else ""))
    lines.append("metric        n  median      range   less_farthest  "
                 "iqr     iqr_less_farthest  bound")
    metrics = metric_values(runs)
    for name, values in metrics.items():
        s = spreads(values)
        bound = bounds.get(name)
        lines.append(
            f"{name:12s} {s['n']:2d}  {s['median']:<10.5f}  {s['range']:.4f}"
            f"  {s['less_farthest']:.4f}         {s['iqr']:.4f}  "
            f"{s['iqr_less_farthest']:.4f}             "
            + ("-" if bound is None else f"{bound}"))
    lines.append("run: seed correct queries " + " ".join(metrics)
                 + " | quarters' medians | stalled | host")
    for run in runs:
        line, lat = run["line"], run["latencies"]
        text = (f"  {run['seed']} {line['correct']} "
                f"{line['attempted'] - line['failed']} "
                + " ".join(f"{m['value']:.5f}"
                           for m in line["metrics"].values()))
        if lat:
            text += (" | " + " ".join(f"{q:.4f}" for q in quarters(lat))
                     + f" | {stalled(lat)}")
        host = line.get("host")
        if host:
            text += (" | load " + "/".join(
                f"{host[k][0]:.2f}" for k in ("loadavg_before",
                                              "loadavg_after"))
                + " " + " ".join(
                    f"{k.split('_')[0]} {host[k]:.4g}" for k in HOST_KEYS)
                + f" cpus {host['affinity']}/{host['cpu_count']}")
        lines.append(text)
    for seconds in cut:
        per_run = [cuts(r["latencies"], seconds) for r in runs
                   if r["latencies"]]
        values = [w["query_s"] for windows in per_run for w in windows]
        if len(values) < 2:
            lines.append(f"cut {seconds:g} s: fewer than two windows")
            continue
        s = spreads(values)
        lines.append(
            f"cut {seconds:g} s: {s['n']} windows, query_s median "
            f"{s['median']:.5f} range {s['range']:.4f} less_farthest "
            f"{s['less_farthest']:.4f} iqr {s['iqr']:.4f}; by run: "
            + " ; ".join(" ".join(f"{w['query_s']:.5f}" for w in windows)
                         for windows in per_run))
    return lines


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("files", nargs="+")
    ap.add_argument("--cut", default="",
                    help="comma-separated window lengths in seconds")
    args = ap.parse_args(argv)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bounds = {m["name"]: m["bound"] for m in json.load(f)["end_to_end"]}
    cut = [float(x) for x in args.cut.split(",") if x]
    runs = load_runs(args.files)
    print("\n".join(report(runs, bounds, cut)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
