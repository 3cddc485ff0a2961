"""Tables queried from their parquet files each time: every collect()
scans the files it reads, on the device."""


def load(session, paths: dict) -> dict:
    return {t: session.read.parquet(p) for t, p in paths.items()}


def compared(run: dict) -> dict:
    """{name: (value, limit)} that hold this residence to its guarantee:
    every row group of every file a query reads is decoded on the device,
    in each collect of set-up and of the window."""
    scans = sum(run["row_groups"][t]
                for q in run["first"] + run["done"]
                for t in run["queries"][q].COLUMNS)
    decoded = (run["setup_counters"].get("deviceDecodedRowGroups", 0)
               + run["counters"].get("deviceDecodedRowGroups", 0))
    return {"undecoded_row_groups": (max(0, scans - int(decoded)), 0)}
