"""Seconds in JAX's backend-compile events (compile, or the persistent
cache's lookup and load) during set-up."""


def read(run):
    return sum(secs for t, secs in run["compile_events"] if t < 0)
