"""From a profiler trace (``*.xplane.pb``) to the numbers the per-layer
metrics read: device busy seconds, launches, the operations that took most
device time, and the longest idle gaps by the host span open during them.

Reads with ``jax.profiler.ProfileData`` alone. A TPU trace has one plane
per chip, ``/device:TPU:<n>``, whose line ``XLA Modules`` holds one event
per program execution and ``XLA Ops`` one per HLO operation (nested where
an operation, such as a ``while``, contains others), and a ``/host:CPU``
plane with one line per thread that holds the ``TraceAnnotation`` spans.
All planes share one clock. A trace without a device plane (a CPU run)
reduces to ``None``: there is nothing to read, and no metric is made up.
"""

import bisect

import numpy as np

DEVICE_PLANE = "/device:TPU:"
HOST_PLANE = "/host:CPU"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
TOP = 10
NAME_CHARS = 96         # of an operation's HLO text, after its program's name
GAPS_ATTRIBUTED = 200   # the longest gaps are attributed, the rest pooled


def _events(line, lo, hi):
    """(start, end, name) of a line's events, clipped to [lo, hi]."""
    out = []
    for e in line.events:
        start, end = e.start_ns, e.start_ns + e.duration_ns
        if end <= lo or start >= hi:
            continue
        out.append((max(start, lo), min(end, hi), e.name))
    out.sort()
    return out


def name_ops(ops, modules):
    """Each operation named ``<program>/<start of its HLO text>``: the
    program is the XLA Modules event it started in."""
    starts = [m[0] for m in modules]
    named = []
    for start, end, name in ops:
        i = bisect.bisect_right(starts, start) - 1
        program = modules[i][2] if i >= 0 and start < modules[i][1] else "?"
        named.append((start, end, f"{program}/{name[:NAME_CHARS]}"))
    return named


def union(intervals):
    """Merged, sorted [start, end] intervals."""
    merged = []
    for start, end in sorted(intervals):
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], end)
        else:
            merged.append([start, end])
    return merged


def self_times(events):
    """{name: ns} with each nested event's time taken off its parent's."""
    total, stack = {}, []   # stack of [end, name, self_ns]

    def close(upto):
        while stack and stack[-1][0] <= upto:
            end, name, own = stack.pop()
            total[name] = total.get(name, 0) + own
    for start, end, name in events:
        close(start)
        if stack:
            stack[-1][2] -= min(end, stack[-1][0]) - start
        stack.append([end, name, end - start])
    close(float("inf"))
    return total


def attribute(gap, starts, ends, names, fallback):
    """The innermost host span open over most of a gap: the shortest of
    those that cover at least half of it, else the one that overlaps it
    most, else ``fallback``. ``starts``/``ends`` are numpy arrays."""
    lo, hi = gap
    overlap = np.minimum(ends, hi) - np.maximum(starts, lo)
    if not len(overlap) or overlap.max() <= 0:
        return fallback
    half = overlap * 2 >= hi - lo
    if half.any():
        length = np.where(half, ends - starts, np.inf)
        return names[int(length.argmin())]
    return names[int(overlap.argmax())]


def reduce(path, query_span="bench.query"):
    """The reduced trace, or None where no device plane is in it.

    The traced window runs from the start of the first ``query_span`` host
    span to the end of the last: the harness wraps each traced query in
    one. Seconds are averaged over the chips in the trace."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    devices = [p for p in data.planes if p.name.startswith(DEVICE_PLANE)
               and p.name[len(DEVICE_PLANE):].isdigit()]
    if not devices:
        return None
    host_lines = [line for p in data.planes if p.name == HOST_PLANE
                  for line in p.lines]
    inf = float("inf")
    queries, spans = [], []
    for line in host_lines:
        for start, end, name in _events(line, -inf, inf):
            if name == query_span:
                queries.append((start, end))
            elif end > start:
                spans.append((start, end, name))
    if not queries:
        return None
    lo, hi = min(q[0] for q in queries), max(q[1] for q in queries)
    spans = [s for s in spans if s[1] > lo and s[0] < hi]
    starts = np.array([s[0] for s in spans], np.float64)
    ends = np.array([s[1] for s in spans], np.float64)
    names = [s[2] for s in spans]
    busy_ns = launches = 0
    op_ns, gap_ns = {}, {}
    for plane in devices:
        lines = {line.name: line for line in plane.lines}
        ops = _events(lines[OPS_LINE], lo, hi) if OPS_LINE in lines else []
        mods = (_events(lines[MODULES_LINE], lo, hi)
                if MODULES_LINE in lines else [])
        launches += len(mods)
        busy = union([(s, e) for s, e, _ in (ops or mods)])
        busy_ns += sum(e - s for s, e in busy)
        for name, ns in self_times(name_ops(ops, mods)).items():
            op_ns[name] = op_ns.get(name, 0) + ns
        edges = [lo] + [t for iv in busy for t in iv] + [hi]
        gaps = sorted(((edges[i], edges[i + 1])
                       for i in range(0, len(edges), 2)
                       if edges[i + 1] > edges[i]),
                      key=lambda g: g[0] - g[1])
        for gap in gaps[:GAPS_ATTRIBUTED]:
            name = attribute(gap, starts, ends, names, query_span)
            gap_ns[name] = gap_ns.get(name, 0) + gap[1] - gap[0]
        rest = sum(g[1] - g[0] for g in gaps[GAPS_ATTRIBUTED:])
        if rest:
            gap_ns["(shorter gaps)"] = gap_ns.get("(shorter gaps)", 0) + rest
    n = len(devices)

    def top(table):
        ranked = sorted(table.items(), key=lambda kv: -kv[1])[:TOP]
        return [[name, ns / n / 1e9] for name, ns in ranked]
    return {"chips": n, "queries": len(queries),
            "window_s": (hi - lo) / 1e9, "busy_s": busy_ns / n / 1e9,
            "launches": launches / n,
            "device_ops": top(op_ns), "idle_gaps": top(gap_ns)}
