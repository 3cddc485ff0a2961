"""Seconds per query the scan's producers spent reading and parsing column
chunks on the host: file read, page headers, decompression, run tables
(scanParseNs of the window over its queries; thread-seconds, summed over
the producers)."""


def read(run):
    spent = run["counters"].get("scanParseNs")
    if spent is None or not run["completed"]:
        return None
    return spent / 1e9 / run["completed"]
