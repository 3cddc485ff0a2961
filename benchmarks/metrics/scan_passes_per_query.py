"""Row groups decoded on the device in the window over the row groups in
the files its queries read: 1 where a collect() scans its files once."""


def read(run):
    decoded = run["counters"].get("deviceDecodedRowGroups")
    if not decoded:
        return None
    queries = run["cell"]["queries"]
    in_files = sum(run["row_groups"][t]
                   for q in run["done"] for t in queries[q].COLUMNS)
    return decoded / in_files if in_files else None
