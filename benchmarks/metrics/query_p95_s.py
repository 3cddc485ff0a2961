"""Nearest-rank 95th percentile of the seconds of every query of the window;
the slowest one while fewer than 20 complete."""

import math


def read(run):
    ranked = sorted(run["latencies"])
    if not ranked:
        return None
    return ranked[max(math.ceil(0.95 * len(ranked)), 1) - 1]
