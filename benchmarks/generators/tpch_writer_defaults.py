"""The tables of ``generators/tpch.py`` — same seed, same rows — written as
a user's writer writes them: ``pyarrow.parquet.write_table(table, path)``
with no option. Every column chunk starts on a dictionary; one whose
dictionary page passes 1 MiB (131,072 doubles or int64s) finishes in PLAIN
pages (parquet-format ``Encodings.md``, "Dictionary Encoding"), as
parquet-mr writes it for Spark. At SF1 that is l_extendedprice,
l_orderkey, o_orderkey and o_totalprice in every full row group, and the
names, addresses and phones of customer and supplier, which no cell reads.

The generator's dictionary-typed columns are cast to plain strings first,
as a user's table holds them; the files go to a directory of their own, so
they never meet ``tpch.py``'s. ``ensure`` is the entry the harness calls.
"""

import importlib.util
import os

import pyarrow as pa
import pyarrow.parquet as pq

_spec = importlib.util.spec_from_file_location(
    "bench_tpch", os.path.join(os.path.dirname(os.path.abspath(__file__)),
                               "tpch.py"))
tpch = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(tpch)


def write_default(table: pa.Table, path: str) -> None:
    """One table to one parquet file by the installed writer's defaults."""
    plain = pa.table({
        f.name: (table.column(f.name).cast(f.type.value_type)
                 if pa.types.is_dictionary(f.type) else table.column(f.name))
        for f in table.schema})
    tmp = path + ".part"
    pq.write_table(plain, tmp)
    os.replace(tmp, path)


def ensure(data_dir: str, config: dict, tables, seed: int,
           scale: float = 1.0):
    """({table: parquet path}, {table: rows}) for the tables asked for,
    under ``data_dir/tpch_writer_defaults_<rows>_seed<S>/``."""
    n = tpch.row_counts(config["tables"], scale)
    out = os.path.join(data_dir,
                       f"tpch_writer_defaults_{n['lineitem']}_seed{seed}")
    os.makedirs(out, exist_ok=True)
    paths = {t: os.path.join(out, f"{t}.parquet") for t in tables}
    missing = [t for t, p in paths.items() if not os.path.exists(p)]
    # the comments' distinct texts are laid out per row group of the
    # writer's default size (storage.row_group_rows records it)
    group_rows = config["storage"]["row_group_rows"]
    made = tpch.gen_sales(n, seed, missing, group_rows) \
        if {"orders", "lineitem"} & set(missing) else {}
    for name in missing:
        table = made[name] if name in made \
            else tpch.gen_table(name, n, seed, group_rows)
        write_default(table, paths[name])
    return paths, n
