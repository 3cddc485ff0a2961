"""JAX backend-compile events inside the window; should read 0."""


def read(run):
    return sum(1 for t, _ in run["compile_events"]
               if 0 <= t <= run["window_s"])
