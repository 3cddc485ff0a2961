"""The least time the chip needs for the traced queries over the seconds it
was busy with them. Least time: the logical bytes of the columns each query
references (rows x width, from the query's file) at the HBM bandwidth of
peaks.json; bandwidth bounds it, these queries do ~1 operation per byte."""


def read(run):
    trace = run["trace"]
    if not trace or not trace["busy_s"]:
        return None
    queries = run["cell"]["queries"]
    logical = sum(run["row_counts"][table] * sum(widths.values())
                  for q in run["traced_queries"]
                  for table, widths in queries[q].COLUMNS.items())
    least_s = logical / run["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least_s / trace["busy_s"]
