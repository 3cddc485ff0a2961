"""Column chunks the device scan decoded in the window over the chunks of
the columns its queries reference (row groups x referenced columns of every
table each completed query reads): 1 where the projection reaches the scan."""


def read(run):
    decoded = run["counters"].get("scanColumnChunksDecoded")
    if not decoded:
        return None
    queries = run["cell"]["queries"]
    referenced = sum(run["row_groups"][t] * len(cols)
                     for q in run["done"]
                     for t, cols in queries[q].COLUMNS.items())
    return decoded / referenced if referenced else None
