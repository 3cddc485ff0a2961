"""Seconds per query the scan's producers spent staging column chunks' host
operands for their upload (``scanStageNs`` of the window over its queries:
the decode program's lookup, run tables and dictionaries padded to their
buckets, the zero-padded copies; thread-seconds, summed over the
producers; not a part of ``scan_parse_s_per_query``, nor of the upload's
or the launch's). Nothing to read where the program does not count it."""


def read(run):
    spent = run["counters"].get("scanStageNs")
    if spent is None or not run["completed"]:
        return None
    return spent / 1e9 / run["completed"]
