"""Seconds per query the scan's producers spent decompressing the pages of
column chunks, each page's copy out of the codec's buffer included
(``scanDecompressNs`` of the window over its queries; thread-seconds,
summed over the producers, and a part of what ``scan_parse_s_per_query``
reads). Nothing to read where the program does not count it."""


def read(run):
    spent = run["counters"].get("scanDecompressNs")
    if spent is None or not run["completed"]:
        return None
    return spent / 1e9 / run["completed"]
