"""Queries of the window whose seconds exceed three times the window's
median: the rare stalls of one query (PERF.md, section 7) that `query_p95_s`
and `query_s` hold without saying so. Reads 0 in most runs."""

import statistics


def read(run):
    latencies = run["latencies"]
    if not latencies:
        return None
    limit = 3 * statistics.median(latencies)
    return sum(1 for x in latencies if x > limit)
