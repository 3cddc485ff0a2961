"""Seconds per query: the whole window, first start to last answer, over the
queries completed in it (a stall, a re-run or a compile in the window is in
it)."""


def read(run):
    return run["window_s"] / run["completed"] if run["completed"] else None
