"""Device program executions (events of the trace's XLA Modules line) per
traced query."""


def read(run):
    trace = run["trace"]
    return trace["launches"] / trace["queries"] if trace else None
