"""Runs of the plan per collect() of the window (planRuns of the TpuSession
node over the queries completed): 1, plus join-capacity re-runs and
dispatch retries."""


def read(run):
    runs = run["counters"].get("planRuns")
    if runs is None or not run["completed"]:
        return None
    return runs / run["completed"]
