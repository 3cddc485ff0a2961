"""Seconds per query the scan's producers spent in the calls that enqueue
the decode programs; grows when a producer stands behind the device's queue
(scanLaunchNs of the window over its queries; thread-seconds, summed over
the producers)."""


def read(run):
    spent = run["counters"].get("scanLaunchNs")
    if spent is None or not run["completed"]:
        return None
    return spent / 1e9 / run["completed"]
