"""Seconds per query the scan's producers spent on the host parsing the
definition levels of column chunks whose column is nullable in the file
and counting their non-null values (``scanDefLevelNs`` of the window over
its queries; thread-seconds, summed over the producers, and a part of what
``scan_parse_s_per_query`` reads). Nothing to read where the program does
not count it."""


def read(run):
    spent = run["counters"].get("scanDefLevelNs")
    if spent is None or not run["completed"]:
        return None
    return spent / 1e9 / run["completed"]
