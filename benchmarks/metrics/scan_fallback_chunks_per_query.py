"""Column chunks per completed query that the device scan decoded from
dictionary-encoded pages followed by PLAIN pages (the writer fell back
when the chunk's dictionary page passed its size limit):
``scanChunksDictionaryThenPlain`` of the window over its queries.

A fact of the files, not a cost to lower (the schema wants a ``better``):
6 in ``tpch_sf1_parquet_writer_defaults.q6``, l_extendedprice in each of
lineitem's 6 row groups. A reading under 6 with ``correct`` false means
chunks left the device. Nothing to read where the program does not count
its chunks by kind (before PR 30)."""

KINDS = ("scanChunksPlain", "scanChunksDictionary",
         "scanChunksDictionaryThenPlain")


def read(run):
    counters = run["counters"]
    if not run["completed"] or not any(k in counters for k in KINDS):
        return None
    return counters.get("scanChunksDictionaryThenPlain", 0) / run["completed"]
