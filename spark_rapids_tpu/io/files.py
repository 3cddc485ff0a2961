"""File scans — Parquet/ORC/CSV readers (host decode milestone).

The reference reads files in two stages: CPU-side footer/stripe selection and
byte assembly, then device-side decode via cudf (GpuParquetScan.scala:314 —
readPartFile rebuilds a mini parquet file in host memory, then
Table.readParquet decodes on GPU). The TPU analog of stage two (device decode
kernels for RLE/dictionary/bitpack leaves) is a later milestone (SURVEY.md §7
hard parts); this module implements stage one with pyarrow: predicate
pushdown, column pruning, and row-group-granular chunked reads honoring
``spark.rapids.sql.reader.batchSizeRows/Bytes``.
"""

from __future__ import annotations

import os
from typing import List, Optional

import pyarrow as pa
import pyarrow.dataset as ds

from .. import types as T
from ..config import MAX_READ_BATCH_SIZE_BYTES, MAX_READ_BATCH_SIZE_ROWS
from ..data.batch import HostBatch
from ..ops import predicates as PRED
from ..ops.expression import AttributeReference, Expression, Literal
from ..plan.physical import PhysicalPlan


def infer_schema(fmt: str, paths: List[str], options: dict) -> T.Schema:
    dataset = _dataset(fmt, paths, options)
    return T.schema_from_arrow(dataset.schema)


def _dataset(fmt: str, paths: List[str], options: dict) -> ds.Dataset:
    # A single directory (a write target) must pass as a bare string;
    # pyarrow rejects directories inside path lists. Default ignore_prefixes
    # skip _SUCCESS and hidden files, like Spark's readers. hive partitioning
    # restores partitionBy columns from key=value directory names.
    src = paths[0] if len(paths) == 1 else paths
    hive = "hive" if len(paths) == 1 and os.path.isdir(paths[0]) else None
    if fmt == "parquet":
        return ds.dataset(src, format="parquet", partitioning=hive)
    if fmt == "orc":
        return ds.dataset(src, format="orc", partitioning=hive)
    if fmt == "csv":
        import pyarrow.csv as pacsv
        _validate_csv_options(options)
        parse = pacsv.ParseOptions(
            delimiter=options.get("delimiter", ","),
            quote_char=options.get("quote", '"'),
            escape_char=options.get("escape", False) or False)
        read = pacsv.ReadOptions()
        # Spark treats empty fields as null ALWAYS, plus the custom
        # nullValue when given (which nulls string cells too — pyarrow
        # needs the explicit opt-in for that).
        convert = pacsv.ConvertOptions(
            null_values=["", options["nullValue"]]
            if "nullValue" in options else [""],
            strings_can_be_null="nullValue" in options)
        if not options.get("header", True):
            read = pacsv.ReadOptions(autogenerate_column_names=True)
        fmt_obj = ds.CsvFileFormat(parse_options=parse,
                                   read_options=read,
                                   convert_options=convert)
        # hive partitioning here too: a partitionBy CSV write read back
        # through this reader must restore the partition columns rather
        # than silently dropping them.
        return ds.dataset(src, format=fmt_obj, partitioning=hive)
    raise ValueError(f"unknown format {fmt}")


def _validate_csv_options(options: dict) -> None:
    """CSV option gates (GpuCSVScan object:87 validates the same surface:
    single-char delimiter distinct from quote/newline, no multiLine, UTF-8
    only; unsupported combinations fail loudly instead of misparsing)."""
    delim = str(options.get("delimiter", ","))
    if len(delim) != 1:
        raise ValueError(f"CSV delimiter must be a single character, "
                         f"got {delim!r}")
    if delim in ("\n", "\r", '"'):
        raise ValueError(f"unsupported CSV delimiter {delim!r}")
    quote = str(options.get("quote", '"'))
    if len(quote) != 1:
        raise ValueError(f"CSV quote must be a single character, "
                         f"got {quote!r}")
    if quote == delim:
        raise ValueError("CSV quote and delimiter must differ")
    if str(options.get("multiLine", "false")).lower() == "true":
        raise ValueError("multiLine CSV is not supported "
                         "(reference GpuCSVScan rejects it too)")
    charset = str(options.get("charset", options.get("encoding", "UTF-8")))
    if charset.upper().replace("-", "") not in ("UTF8",):
        raise ValueError(f"unsupported CSV charset {charset} (UTF-8 only)")
    esc = options.get("escape")
    if esc is not None and len(str(esc)) != 1:
        raise ValueError(f"CSV escape must be a single character, got {esc!r}")


def to_arrow_filter(expr: Expression) -> Optional[ds.Expression]:
    """Best-effort conversion of a pushed filter to a pyarrow dataset filter
    (the ParquetFilters predicate-pushdown analog, GpuParquetScan.scala:290)."""
    import pyarrow.compute as pc
    try:
        if isinstance(expr, PRED.And):
            l = to_arrow_filter(expr.children[0])
            r = to_arrow_filter(expr.children[1])
            if l is not None and r is not None:
                return l & r
            return l if r is None else r
        if isinstance(expr, PRED.Or):
            l = to_arrow_filter(expr.children[0])
            r = to_arrow_filter(expr.children[1])
            return (l | r) if l is not None and r is not None else None
        if isinstance(expr, PRED.Comparison):
            left, right = expr.children
            if isinstance(left, AttributeReference) and isinstance(right, Literal):
                f = pc.field(left._name)
                v = right.value
                op = {"equal": f.__eq__, "not_equal": f.__ne__,
                      "less": f.__lt__, "less_equal": f.__le__,
                      "greater": f.__gt__, "greater_equal": f.__ge__}[expr.op]
                return op(v)
        if isinstance(expr, PRED.IsNotNull) and isinstance(
                expr.children[0], AttributeReference):
            return ~pc.field(expr.children[0]._name).is_null()
        if isinstance(expr, PRED.IsNull) and isinstance(
                expr.children[0], AttributeReference):
            return pc.field(expr.children[0]._name).is_null()
    except Exception:
        return None
    return None


def columns_read(schema: T.Schema, file_schema: T.Schema) -> str:
    """``columns=4/16`` for a scan's line of explain: how many of the
    files' columns it reads (the hidden __input_file_* columns are
    synthesized, so they count on neither side)."""
    from ..plan.input_file import META_NAMES
    read = sum(n not in META_NAMES for n in schema.names)
    held = sum(n not in META_NAMES for n in file_schema.names)
    return f"columns={read}/{held}"


class CpuFileScanExec(PhysicalPlan):
    """Host file scan; one partition per input fragment (file/row-group
    cluster), chunked by reader batch-size limits."""

    def __init__(self, fmt: str, paths: List[str], schema: T.Schema,
                 options: dict, pushed_filters: List[Expression],
                 file_schema: T.Schema, emit_file_meta: bool = False):
        self.fmt = fmt
        self.paths = paths
        self._schema = schema
        #: every column of the files; ``schema`` is the part of it the plan
        #: references (plan/optimizer.py) and the only part read.
        self._file_schema = file_schema
        self.options = options
        self.pushed_filters = pushed_filters
        #: emit the hidden __input_file_* metadata columns (set by the
        #: input_file_name() rewrite, plan/input_file.py); the columns are
        #: part of ``schema`` but synthesized per fragment, not read.
        self.emit_file_meta = emit_file_meta

    @property
    def schema(self):
        return self._schema

    def describe(self):
        return (f"CpuFileScan {self.fmt} {self.paths} "
                f"{columns_read(self._schema, self._file_schema)}")

    def execute(self, ctx):
        import pyarrow as pa_mod
        dataset = _dataset(self.fmt, self.paths, self.options)
        arrow_schema = T.schema_to_arrow(self._schema)
        meta_names = ()
        if self.emit_file_meta:
            from ..plan.input_file import (FILE_LENGTH_COL, FILE_NAME_COL,
                                           FILE_START_COL)
            meta_names = (FILE_NAME_COL, FILE_START_COL, FILE_LENGTH_COL)
        names = [f.name for f in arrow_schema if f.name not in meta_names]
        filt = None
        for f in self.pushed_filters:
            af = to_arrow_filter(f)
            if af is not None:
                filt = af if filt is None else (filt & af)
        max_rows = ctx.conf.get(MAX_READ_BATCH_SIZE_ROWS)
        fragments = list(dataset.get_fragments())

        def read_fragment(frag):
            # dataset.schema carries hive partition fields; passing it lets
            # the fragment materialize partition columns from its
            # partition_expression.
            scanner = ds.Scanner.from_fragment(
                frag, schema=dataset.schema, columns=names, filter=filt,
                batch_size=max_rows)
            meta_present = [f.name for f in arrow_schema
                            if f.name in meta_names]
            if meta_present:
                # Whole-file fragments: the split is the file, so block
                # start is 0 and block length the file size (the reference
                # reports the Hadoop split, GpuInputFileBlock.scala:114).
                path = getattr(frag, "path", "") or ""
                try:
                    import os
                    size = os.path.getsize(path)
                except OSError:
                    size = -1
                meta_value = {meta_names[0]: (path, pa_mod.string()),
                              meta_names[1]: (0, pa_mod.int64()),
                              meta_names[2]: (size, pa_mod.int64())}
            data_schema = pa_mod.schema(
                [f for f in arrow_schema if f.name not in meta_names])
            for rb in scanner.to_batches():
                if not rb.num_rows:
                    continue
                ctx.metric(self.node_name(), "scanColumnChunksDecoded",
                           len(names))
                rb = rb.cast(data_schema)
                if meta_present:
                    n = rb.num_rows
                    by_name = {f.name: c for f, c in zip(data_schema,
                                                         rb.columns)}
                    arrays = []
                    for f in arrow_schema:
                        if f.name in meta_value:
                            v, t = meta_value[f.name]
                            arrays.append(pa_mod.array([v] * n, t))
                        else:
                            arrays.append(by_name[f.name])
                    rb = pa_mod.RecordBatch.from_arrays(
                        arrays, schema=arrow_schema)
                yield HostBatch(rb)
        if not fragments:
            return [iter([])]
        return [read_fragment(f) for f in fragments]
