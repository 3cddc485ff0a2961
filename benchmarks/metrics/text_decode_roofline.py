"""The byte-array decode programs' share of their roofline: the least time
the chip needs to read the text of the traced queries once and write it
once, over the device seconds of the ``jit_parquet_decode_string_*plain*``
operations that the reduced trace lists.

Bandwidth bounds the decode (a copy a byte; the walk over the length
prefixes reads the same bytes), so the least time is bytes over
``hbm_bytes_per_s`` of ``peaks.json``. The function that counts the bytes
lives here and reads the same whatever implements the decode:
``text_bytes`` is, per traced query, rows x the width that
``queries/<q>.py:COLUMNS`` states for each text column the query reads
(``TEXT``: the columns the dbgen-text files hold as PLAIN byte arrays),
once read and once written.

As in ``scan_decode_roofline``, the denominator is the listed operations
only (``run["trace"]["device_ops"]``, the ten longest of the traced
interval), so the share is overstated by what is unlisted. ``None`` where
the trace lists no such operation (a program without these programs, or a
cell that reads no text)."""

PROGRAMS = "jit_parquet_decode_string_"
TEXT = ("l_comment", "o_comment", "c_comment", "s_comment", "p_comment",
        "ps_comment", "n_comment", "r_comment", "c_name", "c_address",
        "c_phone", "s_name", "s_address", "s_phone", "p_name")


def text_bytes(run) -> float:
    queries = run["cell"]["queries"]
    return float(sum(run["row_counts"][table] * width
                     for q in run["traced_queries"]
                     for table, widths in queries[q].COLUMNS.items()
                     for column, width in widths.items() if column in TEXT))


def read(run):
    trace = run["trace"]
    if not trace or not run["traced_queries"]:
        return None
    decode_s = sum(seconds for name, seconds in trace["device_ops"]
                   if name.startswith(PROGRAMS)
                   and "plain" in name.split("/", 1)[0])
    if not decode_s:
        return None
    least_s = 2.0 * text_bytes(run) / run["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least_s / decode_s
