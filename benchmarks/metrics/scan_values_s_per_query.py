"""Seconds per query the scan's producers spent turning column chunks'
PLAIN values and dictionary pages into the arrays the decode takes
(``scanValuesNs`` of the window over its queries: ``np.frombuffer``, the
concatenations, a string dictionary's ranks and offsets, a byte-array
chunk's text buffer; thread-seconds, summed over the producers, and a part
of what ``scan_parse_s_per_query`` reads). Nothing to read where the
program does not count it."""


def read(run):
    spent = run["counters"].get("scanValuesNs")
    if spent is None or not run["completed"]:
        return None
    return spent / 1e9 / run["completed"]
