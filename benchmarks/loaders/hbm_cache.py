"""Tables loaded from their parquet files once, in set-up, and pinned in
HBM with DataFrame.cache() (Spark's CACHE TABLE): the window reads no file.

How set-up loads them is the engine's own ``cache()``: today a host read
and an upload, of which no counter tells (``materialize()`` keeps no
profile), so nothing is held about the load but that it raised nothing."""


def load(session, paths: dict) -> dict:
    return {t: session.read.parquet(p).cache() for t, p in paths.items()}


def compared(run: dict) -> dict:
    """{name: (value, limit)}: no row group is decoded once the tables are
    pinned, neither by the window nor by set-up's first queries."""
    decoded = (run["setup_counters"].get("deviceDecodedRowGroups", 0)
               + run["counters"].get("deviceDecodedRowGroups", 0))
    return {"decoded_after_load_row_groups": (int(decoded), 0)}
