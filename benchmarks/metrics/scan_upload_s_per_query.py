"""Seconds per query the scan's producers spent in the host-to-device copies
of packed bytes, run tables and dictionaries (scanUploadNs of the window
over its queries; thread-seconds, summed over the producers)."""


def read(run):
    spent = run["counters"].get("scanUploadNs")
    if spent is None or not run["completed"]:
        return None
    return spent / 1e9 / run["completed"]
