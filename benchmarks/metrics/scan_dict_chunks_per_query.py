"""Column chunks per completed query that the device scan decoded from
dictionary-encoded pages alone: ``scanChunksDictionary`` of the window over
its queries. 18 in ``tpch_sf1_parquet_writer_defaults.q6`` (l_shipdate,
l_discount and l_quantity in each of 6 row groups); with
``scan_fallback_chunks_per_query`` it shows that the dictionary decode
programs, not a host path, did the work. A fact of the files. Nothing to
read where the program does not count its chunks by kind (before PR 30)."""

KINDS = ("scanChunksPlain", "scanChunksDictionary",
         "scanChunksDictionaryThenPlain")


def read(run):
    counters = run["counters"]
    if not run["completed"] or not any(k in counters for k in KINDS):
        return None
    return counters.get("scanChunksDictionary", 0) / run["completed"]
