"""Seconds per query the scan's producers spent reading column chunks' bytes
from their files (``scanReadNs`` of the window over its queries:
``f.seek`` and ``f.read`` in ``plan_column_chunk``; thread-seconds, summed
over the producers, and a part of what ``scan_parse_s_per_query`` reads).
Nothing to read where the program does not count it."""


def read(run):
    spent = run["counters"].get("scanReadNs")
    if spent is None or not run["completed"]:
        return None
    return spent / 1e9 / run["completed"]
