"""Columns between pyarrow, parquet and numpy: what every generator, the
plain references and the comparison share. Nothing here knows a schema."""

import os

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq


def write_parquet(table: pa.Table, path: str, storage: dict) -> None:
    """One table to one parquet file by the configuration's ``storage``:
    strings dictionary-encoded, whatever their cardinality (the dictionary
    page may grow to ``dictionary_page_bytes``), numbers and dates PLAIN.
    pyarrow's default starts every column on a dictionary and falls back
    to PLAIN mid-chunk once it passes 1 MiB ("mixed PLAIN + dictionary
    pages"), which the device decoder refuses, as it does PLAIN strings.
    A column that comes as a dictionary is written as it is, and no Arrow
    schema is stored, so every reader sees plain strings."""
    strings = [f.name for f in table.schema
               if pa.types.is_string(f.type) or pa.types.is_dictionary(f.type)]
    tmp = path + ".part"
    pq.write_table(table, tmp, use_dictionary=strings, store_schema=False,
                   dictionary_pagesize_limit=storage["dictionary_page_bytes"],
                   compression=storage["compression"],
                   row_group_size=storage["row_group_rows"])
    os.replace(tmp, path)


def to_numpy(column) -> np.ndarray:
    """A pyarrow column as numpy: dates as int32 days, strings as numpy
    unicode (through their dictionary, so 6 M flags cost no 6 M objects).
    A null is NaN in a column of numbers or dates (which then comes as
    float64) and None in a column of strings (which then comes as an
    object array)."""
    arr = column.combine_chunks() if isinstance(column, pa.ChunkedArray) \
        else column
    if pa.types.is_string(arr.type) or pa.types.is_large_string(arr.type):
        enc = arr.dictionary_encode()
        if arr.null_count:
            words = np.array(enc.dictionary.to_pylist() + [None], object)
            return words[enc.indices.fill_null(len(words) - 1)
                         .to_numpy(zero_copy_only=False)]
        words = np.asarray(enc.dictionary.to_pylist(), dtype=np.str_)
        return words[enc.indices.to_numpy(zero_copy_only=False)]
    if pa.types.is_date32(arr.type):
        arr = arr.cast(pa.int32())
    return arr.to_numpy(zero_copy_only=False)


def load_columns(path: str, columns) -> dict:
    """{column: numpy array} read back with pyarrow alone, for the plain
    reference."""
    table = pq.read_table(path, columns=list(columns))
    return {name: to_numpy(table.column(name)) for name in columns}
