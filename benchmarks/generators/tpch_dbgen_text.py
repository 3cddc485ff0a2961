"""The tables of ``generators/tpch.py`` — same seed, same rows in every
column that is not a comment — with the comment columns as clause 4.2.2.10
and dbgen make them: every row its own cut of the grammar's text pool, so
a comment column is near-unique. Written as a user's writer writes them:
``pyarrow.parquet.write_table(table, path)`` with no option.

A near-unique string chunk starts on a dictionary like every other; its
dictionary page reaches 1 MiB after about 20,000 texts (1 MiB / (49 + 4)
bytes for o_comment) and the rest of the chunk, 98% of a full row group,
is PLAIN byte-array pages: ``[u32 length][bytes]`` a value.

A comment is ``pool[start : start + length]``: ``length`` uniform over the
clause's range for that column, ``start`` uniform over the seeded pool of
``tpch.py`` (1 MiB of the grammar's words), both drawn per row from a
stream of their own, so no other column moves. No Python loop over rows.
The files go to a directory of their own. ``ensure`` is the entry the
harness calls.
"""

import importlib.util
import os

import numpy as np
import pyarrow as pa

_spec = importlib.util.spec_from_file_location(
    "bench_tpch_writer_defaults",
    os.path.join(os.path.dirname(os.path.abspath(__file__)),
                 "tpch_writer_defaults.py"))
writer_defaults = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(writer_defaults)
tpch = writer_defaults.tpch     # the tables, drawn as the siblings draw them

# clause 4.2.3: a comment is a text string of 40% to 100% of its column's
# varchar width (clause 4.2.2.10); the same ranges ``tpch.py`` draws from
COMMENTS = {"lineitem": ("l_comment", 10, 43),
            "orders": ("o_comment", 19, 78),
            "customer": ("c_comment", 29, 116),
            "supplier": ("s_comment", 25, 100),
            "part": ("p_comment", 5, 22),
            "partsupp": ("ps_comment", 49, 198),
            "nation": ("n_comment", 31, 114),
            "region": ("r_comment", 31, 115)}
ROWS_A_PIECE = 1 << 18     # rows cut from the pool at a time


def cut_text(rng, pool: np.ndarray, n: int, lo: int, hi: int) -> pa.Array:
    """n texts of lo..hi bytes, each cut from the pool at a place of its
    own; a piece of the rows at a time, so the byte index stays small."""
    lengths = rng.integers(lo, hi + 1, n)
    starts = rng.integers(0, len(pool) - hi, n)
    offsets = np.concatenate([[0], np.cumsum(lengths)])
    data = np.empty(int(offsets[-1]), np.uint8)
    for a in range(0, n, ROWS_A_PIECE):
        b = min(a + ROWS_A_PIECE, n)
        first = offsets[a:b] - offsets[a]
        source = np.repeat(starts[a:b] - first, lengths[a:b]) \
            + np.arange(offsets[b] - offsets[a])
        data[offsets[a]:offsets[b]] = pool[source]
    return pa.Array.from_buffers(
        pa.string(), n,
        [None, pa.py_buffer(offsets.astype(np.int32)), pa.py_buffer(data)])


def with_dbgen_text(name: str, table: pa.Table, seed: int) -> pa.Table:
    """The table with its comment column cut anew, row by row."""
    column, lo, hi = COMMENTS[name]
    rng = tpch._rng(seed, 100 + tpch.TABLES.index(name))
    text = cut_text(rng, tpch._text_pool(rng), table.num_rows, lo, hi)
    at = table.schema.get_field_index(column)
    if name == "supplier":
        # clause 4.2.3's marked suppliers keep their marks (Q16 reads them)
        old = table.column(column).to_pylist()
        new = text.to_pylist()
        for row, was in enumerate(old):
            if was.startswith("Customer "):
                new[row] = was[:20] + new[row][20:]
        text = pa.array(new, pa.string())
    return table.set_column(at, column, text)


def ensure(data_dir: str, config: dict, tables, seed: int,
           scale: float = 1.0):
    """({table: parquet path}, {table: rows}) for the tables asked for,
    under ``data_dir/tpch_dbgen_text_<rows>_seed<S>/``."""
    n = tpch.row_counts(config["tables"], scale)
    out = os.path.join(data_dir,
                       f"tpch_dbgen_text_{n['lineitem']}_seed{seed}")
    os.makedirs(out, exist_ok=True)
    paths = {t: os.path.join(out, f"{t}.parquet") for t in tables}
    missing = [t for t, p in paths.items() if not os.path.exists(p)]
    group_rows = config["storage"]["row_group_rows"]
    made = tpch.gen_sales(n, seed, missing, group_rows) \
        if {"orders", "lineitem"} & set(missing) else {}
    for name in missing:
        table = made[name] if name in made \
            else tpch.gen_table(name, n, seed, group_rows)
        writer_defaults.write_default(with_dbgen_text(name, table, seed),
                                      paths[name])
    return paths, n
