"""Process start to window start: imports, data, session, cache(), and each
query of the cell once (the cold compile bill on a first run)."""


def read(run):
    return run["setup_s"]
