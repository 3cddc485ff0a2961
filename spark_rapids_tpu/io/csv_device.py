"""Device-side CSV parse — the ``GpuBatchScanExec`` CSV analog.

The reference parses CSV on the GPU (``GpuBatchScanExec.scala:87`` ->
cudf's csv reader). The TPU-native split mirrors the parquet/ORC
decoders' contract:

* HOST (structure-sized work): one vectorized numpy pass finds line and
  field boundaries — newline/delimiter positions via ``np.where``, the
  k-th delimiter of each line via ``searchsorted`` — WITHOUT converting
  a single value.
* DEVICE (data-sized work): the raw file bytes upload ONCE; one traced
  kernel gathers each column's byte matrix from the boundary tables and
  runs the digit DP — sign fold, mantissa accumulation, decimal-point
  split — producing value + validity lanes. String columns gather their
  char matrix from the same buffer (no second host pass).

Correct-rounding note: doubles parse as integer mantissa m and decimal
exponent f, finished as ``m / 10^f`` in float64. That division is
correctly rounded whenever both operands are exact (m <= 15 digits,
f <= 22), which makes it bit-identical to strtod/pyarrow on that range;
anything wider trips the kernel's ``bad`` flag and the FILE falls back
to the host pyarrow reader (per-file graceful degradation, like the
per-stripe/rowgroup fallback of the other decoders). The same flag
catches malformed digits, exponent notation, inf/nan spellings, and
int64 overflow risk (>18 digits) — the device never guesses.

Out of scope (host fallback): quoted fields (quote char anywhere in the
file), custom nullValue tokens, escape chars, non-UTF-8, types beyond
int8/16/32/64, float/double, boolean, string.
"""

from __future__ import annotations

import os
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import types as T
from ..data.batch import ColumnarBatch
from ..data.column import (DeviceColumn, bucket_byte_capacity,
                           bucket_capacity)
from ..utils.kernel_cache import cached_kernel
from ..metrics.trace import span


class NotCsvDecodable(Exception):
    """File outside the device parser's scope; caller reads it host-side."""


_INT_TYPES = ("bigint", "int", "smallint", "tinyint")
_SUPPORTED = set(_INT_TYPES) | {"double", "float", "boolean", "string"}


def scan_files(paths: List[str]) -> List[str]:
    out = []
    for p in paths:
        if os.path.isdir(p):
            for root, _, files in os.walk(p):
                out.extend(os.path.join(root, fn) for fn in sorted(files)
                           if fn.endswith(".csv"))
        elif p.endswith(".csv"):
            out.append(p)
        else:
            return []
    return sorted(out)


def device_decodable(schema: T.Schema, options: dict) -> bool:
    """Static (pre-data) scope check; data-dependent hazards (quotes,
    overlong numbers) fall back per file at decode time."""
    if any(f.data_type.name not in _SUPPORTED for f in schema):
        return False
    if "nullValue" in options or options.get("escape"):
        return False
    return True


# ---------------------------------------------------------------------------
# Host: vectorized boundary finding
# ---------------------------------------------------------------------------


def _boundaries(buf: np.ndarray, delim: int, n_cols: int,
                header: bool) -> Tuple[np.ndarray, np.ndarray]:
    """(field_starts [n, C], field_ends [n, C]) — one vectorized pass;
    raises NotCsvDecodable on ragged lines."""
    n_bytes = len(buf)
    if n_bytes == 0:
        return (np.zeros((0, n_cols), np.int64),
                np.zeros((0, n_cols), np.int64))
    nl = np.nonzero(buf == 10)[0]
    line_starts = np.concatenate(([0], nl + 1))
    line_ends = np.concatenate((nl, [n_bytes]))
    # CRLF: trim the \r BEFORE the empty-line filter, so a blank "\r\n"
    # line is recognized as empty (pyarrow skips it; a post-trim check
    # would let it through as a spurious null row).
    crlf = (line_ends > line_starts) \
        & (buf[np.maximum(line_ends - 1, 0)] == 13)
    line_ends = line_ends - crlf.astype(np.int64)
    # Drop the phantom line after a trailing newline (and any empty lines
    # — Spark/pyarrow skip fully empty lines).
    live = line_starts < line_ends
    line_starts = line_starts[live]
    line_ends = line_ends[live]
    if header:
        line_starts, line_ends = line_starts[1:], line_ends[1:]
    n = len(line_starts)
    if n == 0:
        return (np.zeros((0, n_cols), np.int64),
                np.zeros((0, n_cols), np.int64))
    dpos = np.nonzero(buf == delim)[0]
    first = np.searchsorted(dpos, line_starts)
    after = np.searchsorted(dpos, line_ends)
    if not ((after - first) == (n_cols - 1)).all():
        raise NotCsvDecodable("ragged rows (field count != schema)")
    starts = np.empty((n, n_cols), np.int64)
    ends = np.empty((n, n_cols), np.int64)
    starts[:, 0] = line_starts
    for j in range(1, n_cols):
        d = dpos[first + (j - 1)]
        ends[:, j - 1] = d
        starts[:, j] = d + 1
    ends[:, n_cols - 1] = line_ends
    return starts, ends


# ---------------------------------------------------------------------------
# Device: the digit DP
# ---------------------------------------------------------------------------


def _build_parse_kernel(dtypes: Tuple[str, ...], widths: Tuple[int, ...],
                        cap: int):
    def parse_int(mat, lens, w):
        neg = mat[:, 0] == 45
        plus = mat[:, 0] == 43
        skip = (neg | plus).astype(jnp.int32)
        col_idx = jnp.arange(w, dtype=jnp.int32)[None, :]
        in_field = col_idx < lens[:, None]
        digit_pos = in_field & (col_idx >= skip[:, None])
        d = mat - 48
        bad_char = digit_pos & ((d < 0) | (d > 9))
        ndig = lens - skip
        has = lens > 0
        bad = (bad_char.any(axis=1) | (has & (ndig <= 0))
               | (has & (ndig > 18)))
        v = jnp.zeros(mat.shape[0], jnp.int64)
        for k in range(w):
            v = jnp.where(digit_pos[:, k], v * 10 + d[:, k].astype(jnp.int64),
                          v)
        v = jnp.where(neg, -v, v)
        return v, has, bad, ndig

    def parse_double(mat, lens, w):
        neg = mat[:, 0] == 45
        plus = mat[:, 0] == 43
        skip = (neg | plus).astype(jnp.int32)
        col_idx = jnp.arange(w, dtype=jnp.int32)[None, :]
        in_field = col_idx < lens[:, None]
        body = in_field & (col_idx >= skip[:, None])
        is_dot = body & (mat == 46)
        d = mat - 48
        is_digit = body & (d >= 0) & (d <= 9)
        bad_char = body & ~is_digit & ~is_dot
        ndots = is_dot.sum(axis=1)
        has = lens > 0
        ndig = is_digit.sum(axis=1)
        # f = digits after the dot
        dot_rel = jnp.where(is_dot.any(axis=1),
                            jnp.argmax(is_dot, axis=1), 0)
        frac = jnp.where(is_dot.any(axis=1),
                         (lens - 1 - dot_rel).astype(jnp.int32), 0)
        bad = (bad_char.any(axis=1) | (ndots > 1) | (has & (ndig <= 0))
               | (ndig > 15) | (frac > 22) | (frac < 0))
        m = jnp.zeros(mat.shape[0], jnp.int64)
        for k in range(w):
            m = jnp.where(is_digit[:, k], m * 10 + d[:, k].astype(jnp.int64),
                          m)
        pow10 = jnp.asarray([10.0 ** i for i in range(23)], jnp.float64)
        v = m.astype(jnp.float64) / pow10[jnp.clip(frac, 0, 22)]
        v = jnp.where(neg, -v, v)
        return v, has, bad.any()

    def parse_bool(mat, lens, w):
        """Exactly pyarrow's accepted spellings: true/True/TRUE,
        false/False/FALSE, 1, 0 — anything else trips ``bad`` so the file
        falls back instead of guessing ('tree' is not true)."""
        has = lens > 0

        def word(token: bytes):
            tl = len(token)
            if w < tl:
                return jnp.zeros(mat.shape[0], jnp.bool_)
            folded_ok = jnp.ones(mat.shape[0], jnp.bool_)
            all_lower = jnp.ones(mat.shape[0], jnp.bool_)
            all_upper = jnp.ones(mat.shape[0], jnp.bool_)
            title = jnp.ones(mat.shape[0], jnp.bool_)
            for k, ch in enumerate(token):
                b = mat[:, k]
                folded_ok &= (b | 0x20) == ch
                all_lower &= b == ch
                all_upper &= b == (ch - 32)
                title &= b == (ch - 32 if k == 0 else ch)
            case_ok = all_lower | all_upper | title
            return (lens == tl) & folded_ok & case_ok

        t = word(b"true") | ((lens == 1) & (mat[:, 0] == 49))    # '1'
        f = word(b"false") | ((lens == 1) & (mat[:, 0] == 48))   # '0'
        bad = (has & ~(t | f)).any()
        return t, has, bad

    def run(buf, starts, ends, n_rows):
        live = jnp.arange(cap, dtype=jnp.int32) < n_rows
        out = []
        bads = []
        nb = buf.shape[0]
        for j, (tn, w) in enumerate(zip(dtypes, widths)):
            s = starts[:, j]
            lens = jnp.where(live, (ends[:, j] - s).astype(jnp.int32), 0)
            pos = s[:, None] + jnp.arange(w, dtype=jnp.int64)[None, :]
            in_field = jnp.arange(w, dtype=jnp.int32)[None, :] < lens[:, None]
            mat = jnp.where(
                in_field,
                buf[jnp.clip(pos, 0, nb - 1)].astype(jnp.int32), -1)
            if tn in _INT_TYPES:
                v, has, badv, _ = parse_int(mat, lens, w)
                if tn != "bigint":
                    info = jnp.iinfo(T.type_by_name(tn).np_dtype)
                    badv = badv | (has & ((v > info.max) | (v < info.min)))
                bad = badv.any()
            elif tn in ("double", "float"):
                v, has, bad = parse_double(mat, lens, w)
            elif tn == "boolean":
                v, has, bad = parse_bool(mat, lens, w)
            else:                               # string: char matrix
                out.append((jnp.where(in_field, mat, -1).astype(jnp.int16),
                            lens, live))
                bads.append(jnp.asarray(False))
                continue
            validity = live & has
            out.append((jnp.where(validity, v, 0), validity, None))
            bads.append(bad)
        return tuple(out), jnp.stack(bads).any()

    return lambda: run


def decode_file(path: str, schema: T.Schema, options: dict,
                max_rows: int = 1 << 20,
                file_schema: Optional[T.Schema] = None):
    """Yield ColumnarBatches parsed on device; NotCsvDecodable when the
    file's DATA is out of scope (quotes, overlong numbers, ragged rows).
    A line holds every field of ``file_schema``; only the fields of
    ``schema`` (a subset, by name) are parsed on the device."""
    file_schema = file_schema if file_schema is not None else schema
    buf = np.fromfile(path, dtype=np.uint8)
    q_opt = options.get("quote", '"')
    if q_opt not in (False, None, ""):
        # Quoting disabled (quote=False, pyarrow-style) needs no check.
        quote = ord(str(q_opt))
        if len(buf) and (buf == quote).any():
            raise NotCsvDecodable("quoted fields")
    delim = ord(str(options.get("delimiter", ",")))
    header = bool(options.get("header", True))
    starts, ends = _boundaries(buf, delim, len(file_schema), header)
    if len(schema) < len(file_schema):
        at = [file_schema.names.index(n) for n in schema.names]
        starts, ends = starts[:, at], ends[:, at]
    n = len(starts)
    dev_buf = jax.device_put(buf if len(buf) else np.zeros(1, np.uint8))
    if n == 0:
        yield _decode_slice(dev_buf, starts, ends, schema)
        return
    for lo in range(0, n, max_rows):
        hi = min(lo + max_rows, n)
        yield _decode_slice(dev_buf, starts[lo:hi], ends[lo:hi], schema)


def _decode_slice(dev_buf, starts: np.ndarray, ends: np.ndarray,
                  schema: T.Schema) -> ColumnarBatch:
    n = len(starts)
    cap = bucket_capacity(n)
    widths = tuple(
        int(bucket_byte_capacity(int((ends[:, j] - starts[:, j]).max())
                            if n else 1, 8))
        for j in range(len(schema)))
    dtypes = tuple(f.data_type.name for f in schema)
    s_pad = np.zeros((cap, len(schema)), np.int64)
    e_pad = np.zeros((cap, len(schema)), np.int64)
    s_pad[:n] = starts
    e_pad[:n] = ends
    kern = cached_kernel("csv_device.parse", (dtypes, widths, cap),
                         _build_parse_kernel(dtypes, widths, cap))
    with span(None, "csv.device_parse"):
        outs, bad = kern(dev_buf, jnp.asarray(s_pad), jnp.asarray(e_pad),
                         jnp.asarray(n, jnp.int32))
    if bool(bad):   # one scalar sync per batch
        raise NotCsvDecodable("value outside the digit DP's exact range")
    cols = []
    for f, payload in zip(schema, outs):
        if f.data_type is T.STRING:
            from ..ops.kernels.rowops import strings_from_matrix
            mat, lens, live = payload
            col = strings_from_matrix(mat, live, mat.shape[1])
            cols.append(col)
        else:
            v, validity, _ = payload
            np_dt = f.data_type.np_dtype
            cols.append(DeviceColumn(
                data=jnp.asarray(v).astype(np_dt),
                validity=validity, dtype=f.data_type))
    return ColumnarBatch(tuple(cols), jnp.asarray(n, jnp.int32), schema)


class TpuCsvScanExec:
    """Device CSV scan; per-FILE fallback to the host pyarrow reader."""

    columnar = True
    children = ()
    children_coalesce_goals = None

    def __init__(self, files: List[str], schema: T.Schema,
                 file_schema: T.Schema, options: dict):
        self.files = list(files)
        #: the fields the plan references (plan/optimizer.py), parsed on
        #: the device; ``_file_schema`` is every field of a line, which the
        #: host-side boundary pass needs to find them.
        self._schema = schema
        self._file_schema = file_schema
        self.options = dict(options)

    @property
    def schema(self):
        return self._schema

    def node_name(self):
        return "TpuCsvScanExec"

    def describe(self):
        from .files import columns_read
        return (f"TpuCsvScan files={len(self.files)} "
                f"{columns_read(self._schema, self._file_schema)}")

    def tree_string(self, indent: int = 0) -> str:
        return "  " * indent + self.describe() + "\n"

    def with_children(self, children):
        assert not children
        return self

    def execute(self, ctx):
        name = self.node_name()

        def read_file(path):
            from ..memory.retry import Classification, classify
            from ..utils.fault_injection import maybe_inject
            try:
                maybe_inject(ctx, "io.csv.file")
                with ctx.registry.timer(name, "opTime",
                                        trace="csv.decode_file",
                                        owner=getattr(ctx, "trace", None)):
                    batches = list(decode_file(
                        path, self._schema, self.options,
                        file_schema=self._file_schema))
                ctx.metric(name, "scanColumnChunksDecoded",
                           len(batches) * len(self._schema))
                return batches
            except Exception as e:  # noqa: BLE001 - classify-narrowed
                # Out-of-scope files (NotCsvDecodable) and classified
                # device faults fall back to the host reader per file;
                # parser-logic bugs still fail loudly.
                if not isinstance(e, NotCsvDecodable) \
                        and classify(e) == Classification.FATAL:
                    raise
                ctx.metric(name, "fileHostFallback", 1)
                return self._host_file(path)

        # Files decode ahead on the shared pipeline pool (bounded by
        # decodeThreads/prefetchDepth), yielding in file order; with the
        # pipeline off, the serial stream keeps its depth-2 prefetch
        # worker (pre-pipeline behavior).
        from ..exec import pipeline

        def gen():
            for batches in pipeline.ordered_map_iter(
                    read_file, self.files, ctx, name):
                for b in batches:
                    ctx.metric(name, "numOutputBatches", 1)
                    yield b
        if pipeline.parallel_active(ctx):
            return [gen()]
        from ..utils.prefetch import prefetch_iter
        return [prefetch_iter(gen(), ctx=ctx, node=name)]

    def _host_file(self, path: str) -> List[ColumnarBatch]:
        import pyarrow as pa
        from .files import _dataset
        table = _dataset("csv", [path], self.options).to_table()
        arrow_schema = T.schema_to_arrow(self._schema)
        table = table.select([f.name for f in self._schema]) \
            .cast(arrow_schema)
        if table.num_rows == 0:
            rb = pa.RecordBatch.from_arrays(
                [pa.array([], type=f.type) for f in arrow_schema],
                schema=arrow_schema)
            return [ColumnarBatch.from_arrow(rb)]
        return [ColumnarBatch.from_arrow(rb)
                for rb in table.combine_chunks().to_batches()]
