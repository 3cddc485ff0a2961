"""Programs that set-up's queries compiled because JAX's persistent cache did
not hold them (persistentCacheMisses of their profiles): 0 on a warm run."""


def read(run):
    return run["setup_counters"].get("persistentCacheMisses")
