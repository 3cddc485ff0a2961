"""Seconds per query the consumer of a scan waited for a decoded batch
(prefetchConsumerStallNs of the window over its queries)."""


def read(run):
    stall = run["counters"].get("prefetchConsumerStallNs")
    if stall is None or not run["completed"]:
        return None
    return stall / 1e9 / run["completed"]
