"""The nullable decode programs' share of their roofline: the least time the
chip needs to read what the decode of the chunks with nulls reads and to
write what it writes, over the device seconds of the
``jit_parquet_decode_*_bw*`` operations (the programs of a chunk with
nulls) that the reduced trace lists.

Bandwidth bounds the decode (a gather and a select a value, no arithmetic
to speak of), so the least time is bytes over ``hbm_bytes_per_s`` of
``peaks.json``. The function that counts the bytes lives here and reads
the same whatever implements the decode: ``null_bytes`` is what the
programs were given — the window's ``scanNullableUploadBytes`` (page
bytes, run tables, dictionaries of the chunks that held a null, as padded
and uploaded) per completed query, times the traced queries — and what
they must write: for each traced query and each column of
``queries/<q>.py:COLUMNS`` that the files hold with nulls (store_sales'
columns but its primary key), rows x (its width + 1 validity byte).

As in ``scan_decode_roofline``, the denominator is the listed operations
only (``run["trace"]["device_ops"]``, the ten longest of the traced
interval), so the share is overstated by what is unlisted. ``None`` where
the trace lists no such operation or the program does not count the
bytes."""

PROGRAMS = "jit_parquet_decode_"
PRIMARY_KEY = ("ss_item_sk", "ss_ticket_number")


def nullable(column: str) -> bool:
    """A column the files hold with nulls: store_sales' but its key."""
    return column.startswith("ss_") and column not in PRIMARY_KEY


def null_bytes(run) -> float:
    """Bytes read and written by the nullable decode of the traced
    queries."""
    traced = run["traced_queries"]
    read_ = run["counters"]["scanNullableUploadBytes"] / run["completed"] \
        * len(traced)
    queries = run["cell"]["queries"]
    written = sum(run["row_counts"][table] * (width + 1)
                  for q in traced
                  for table, widths in queries[q].COLUMNS.items()
                  for column, width in widths.items() if nullable(column))
    return float(read_ + written)


def read(run):
    trace = run["trace"]
    if not trace or not run["completed"] or not run["traced_queries"] \
            or "scanNullableUploadBytes" not in run["counters"]:
        return None
    decode_s = sum(seconds for name, seconds in trace["device_ops"]
                   if name.startswith(PROGRAMS)
                   and "_bw" in name.split("/", 1)[0])
    if not decode_s:
        return None
    least_s = null_bytes(run) / run["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least_s / decode_s
