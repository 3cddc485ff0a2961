"""Column chunks per completed query that the device scan decoded without
the null machinery — no definition-level expansion, no prefix sum, no
gather through slots — because no page of the chunk held a null:
``scanChunksNoNulls`` of the window over its queries. 24 in both q6 cells
(4 referenced columns x 6 row groups) and 34 in ``tpch_sf1_parquet.q3``:
TPC-H holds no null, so every chunk. A fact of the files, like
``scan_dict_chunks_per_query`` beside it; under that count with ``correct``
true, chunks without nulls took the nullable programs. Nothing to read
where the program does not count such chunks (before PR 31)."""


def read(run):
    counters = run["counters"]
    if not run["completed"] or "scanChunksNoNulls" not in counters:
        return None
    return counters["scanChunksNoNulls"] / run["completed"]
