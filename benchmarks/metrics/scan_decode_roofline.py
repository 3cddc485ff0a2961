"""The parquet decode programs' share of their roofline: the least time the
chip needs to move the bytes a decode must move, over the device seconds of
the ``jit_parquet_decode_*`` operations that the reduced trace lists.

Bandwidth bounds a decode (a gather and a select a value, no arithmetic to
speak of), so the least time is bytes over ``hbm_bytes_per_s`` of
``peaks.json``. The functions that count the bytes live here:
``bytes_read`` is what the programs were given — the window's
``uploadBytes`` (packed page bytes, run tables, dictionaries, as padded and
uploaded) per completed query, times the traced queries; ``bytes_written``
is the decoded columns, rows x the referenced columns' logical widths
(``queries/<q>.py:COLUMNS``) of each traced query.

The denominator is the listed operations only: ``run["trace"]["device_ops"]``
holds the ten longest of the traced interval, so whatever decode operation
is not among them is left out and the share is overstated by that much (it
reads near 0.001%, nowhere near a limit). The ``benchmark`` PR that brings
device seconds by program name (ROADMAP.md, D2a) points it at the whole sum.
"""

PROGRAMS = "jit_parquet_decode_"


def bytes_read(run) -> float:
    per_query = run["counters"].get("uploadBytes", 0) / run["completed"]
    return per_query * len(run["traced_queries"])


def bytes_written(run) -> float:
    queries = run["cell"]["queries"]
    return float(sum(run["row_counts"][table] * sum(widths.values())
                     for q in run["traced_queries"]
                     for table, widths in queries[q].COLUMNS.items()))


def read(run):
    trace = run["trace"]
    if not trace or not run["completed"] or not run["traced_queries"]:
        return None
    decode_s = sum(seconds for name, seconds in trace["device_ops"]
                   if name.startswith(PROGRAMS))
    if not decode_s:
        return None
    least_s = (bytes_read(run) + bytes_written(run)) \
        / run["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least_s / decode_s
