"""Seconds per query the scan's producers spent slicing the dictionary
index streams of column chunks into run tables (``scanIndexRunNs`` of the
window over its queries: ``_parse_hybrid`` over the indices; thread-seconds,
summed over the producers, and a part of what ``scan_parse_s_per_query``
reads; zero in a cell whose chunks hold no dictionary-encoded page).
Nothing to read where the program does not count it."""


def read(run):
    spent = run["counters"].get("scanIndexRunNs")
    if spent is None or not run["completed"]:
        return None
    return spent / 1e9 / run["completed"]
