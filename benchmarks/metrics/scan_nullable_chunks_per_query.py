"""Column chunks per completed query that the device scan decoded through
the null machinery — definition-level expansion, prefix sum to slots, the
slot or dictionary gather — because a page of the chunk held a null:
``scanColumnChunksDecoded`` less ``scanChunksNoNulls`` of the window, over
its queries. 9 in ``tpcds_sf1_parquet.q43`` (ss_sold_date_sk, ss_store_sk
and ss_sales_price in each of store_sales' 3 row groups). A fact of the
files; under that count with ``correct`` true, chunks with nulls left the
device or were not read. Nothing to read where either counter is absent."""


def read(run):
    counters = run["counters"]
    if not run["completed"] or "scanColumnChunksDecoded" not in counters \
            or "scanChunksNoNulls" not in counters:
        return None
    return (counters["scanColumnChunksDecoded"]
            - counters["scanChunksNoNulls"]) / run["completed"]
