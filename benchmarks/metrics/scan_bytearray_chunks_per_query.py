"""Column chunks per completed query whose string values the device scan
took wholly or partly from PLAIN byte-array pages (``[u32 length][bytes]``
a value: a text column written without a dictionary, or one whose
dictionary passed its size limit and fell back):
``scanChunksByteArrayPlain`` of the window over its queries. Such a chunk
is a flat string column on the device, and is counted under
``scanChunksPlain`` or ``scanChunksDictionaryThenPlain`` as well.

A fact of the files, not a cost to lower (the schema wants a ``better``):
2 in ``tpch_sf1_parquet_dbgen_text.q13``, o_comment in each of orders' 2
row groups. A reading under 2 with ``correct`` false means chunks left the
device. Nothing to read where the program does not count such chunks
(before PR 34, which also refuses them)."""


def read(run):
    counters = run["counters"]
    if not run["completed"] or "scanChunksByteArrayPlain" not in counters:
        return None
    return counters["scanChunksByteArrayPlain"] / run["completed"]
