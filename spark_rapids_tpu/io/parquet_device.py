"""Device-side parquet decode — the ``Table.readParquet`` stage analog.

The reference's scan splits work exactly this way: the CPU parses the footer
and reassembles the selected row-group bytes in host memory, then cuDF
decodes ON DEVICE (GpuParquetScan.scala:365-388 -> Table.readParquet). The
TPU-native split here:

* HOST (metadata-sized work): pyarrow reads the footer; a minimal
  thrift-compact parser walks page headers; page payloads decompress; the
  RLE/bit-packed hybrid streams (definition levels + dictionary indices)
  are sliced into RUN TABLES — (kind, count, value | bit offset) per run —
  without expanding a single value.
* DEVICE (data-sized work): one traced kernel expands the run tables —
  ``searchsorted`` over run ends finds each output's run, RLE runs
  broadcast their value, bit-packed runs gather+shift+mask straight from
  the uploaded page bytes — then definition levels become the validity
  mask and dictionary indices scatter into row order. Everything is
  vectorized; no per-value host loop anywhere. A chunk in which no page
  holds a null (the host sees that in the pages) skips the definition
  levels, the row -> slot prefix sum and the slot gather: row i is value
  i, and a PLAIN chunk is its uploaded buffer.

Parquet dictionaries pair perfectly with this engine's dict-encoded string
columns: the page dictionary IS the column dictionary. The host sorts the
(small) dictionary and uploads a rank table; the device remaps codes, so
decoded string columns arrive ``dict_sorted`` and every downstream sort /
group-by / join uses the fast code paths.

Scope (falls back to the host scan otherwise, reference-style graceful
degradation): v1 data pages, PLAIN + PLAIN_DICTIONARY/RLE_DICTIONARY
encodings, flat schemas, dictionary bit widths <= 24. A chunk may start on
its dictionary and finish in PLAIN pages, as parquet-mr and parquet-cpp
write one whose dictionary page passes its size limit (1 MiB by default);
PLAIN boolean pages stay outside.

A BYTE_ARRAY chunk with PLAIN values (``[u32 length][bytes]`` a value; all
of it, or what follows its dictionary pages) becomes a FLAT string column.
The host concatenates the page payloads and notes where each page's values
start and how many it holds; the device walks the length prefixes (serial
within a page, one lane a page: ``length_walk``), gives every row its
source and length, and copies the text into the Arrow layout
(``text_place``). A dictionary page is one more page of that walk, so
nothing is compiled for a dictionary's length.
"""

from __future__ import annotations

import dataclasses
import datetime as _dt
import struct as _struct
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import types as T
from ..data.batch import ColumnarBatch
from ..data.column import (DeviceColumn, bucket_byte_capacity,
                           bucket_capacity)
from ..utils.kernel_cache import cached_kernel

# -- minimal thrift compact protocol reader ---------------------------------


class _Thrift:
    """Just enough of the thrift compact protocol for parquet PageHeader."""

    def __init__(self, buf: bytes, pos: int = 0):
        self.buf = buf
        self.pos = pos

    def _byte(self) -> int:
        b = self.buf[self.pos]
        self.pos += 1
        return b

    def varint(self) -> int:
        out = shift = 0
        while True:
            b = self._byte()
            out |= (b & 0x7F) << shift
            if not b & 0x80:
                return out
            shift += 7

    def zigzag(self) -> int:
        v = self.varint()
        return (v >> 1) ^ -(v & 1)

    def read_struct(self) -> Dict[int, object]:
        """Field id -> value; nested structs become dicts, unneeded types
        are skipped structurally."""
        out: Dict[int, object] = {}
        field_id = 0
        while True:
            header = self._byte()
            if header == 0:
                return out
            delta = header >> 4
            ftype = header & 0x0F
            field_id = field_id + delta if delta else self.zigzag()
            out[field_id] = self._read_value(ftype)

    def _read_value(self, ftype: int):
        if ftype in (1, 2):  # bool true/false encoded in the type nibble
            return ftype == 1
        if ftype == 3:
            return self._byte()
        if ftype in (4, 5, 6):  # i16/i32/i64
            return self.zigzag()
        if ftype == 7:
            v = _struct.unpack_from("<d", self.buf, self.pos)[0]
            self.pos += 8
            return v
        if ftype == 8:  # binary
            n = self.varint()
            v = self.buf[self.pos: self.pos + n]
            self.pos += n
            return v
        if ftype == 9:  # list
            head = self._byte()
            size = head >> 4
            etype = head & 0x0F
            if size == 15:
                size = self.varint()
            return [self._read_value(etype) for _ in range(size)]
        if ftype == 12:
            return self.read_struct()
        raise NotImplementedError(f"thrift compact type {ftype}")


@dataclasses.dataclass
class _PageHeader:
    page_type: int            # 0 data v1, 2 dictionary, 3 data v2
    compressed_size: int
    uncompressed_size: int
    num_values: int = 0
    encoding: int = 0
    def_encoding: int = 3     # RLE
    header_len: int = 0


def _parse_page_header(buf: bytes, pos: int) -> _PageHeader:
    t = _Thrift(buf, pos)
    d = t.read_struct()
    ph = _PageHeader(page_type=d[1], uncompressed_size=d[2],
                     compressed_size=d[3], header_len=t.pos - pos)
    if ph.page_type == 0:
        dph = d[5]
        ph.num_values = dph[1]
        ph.encoding = dph[2]
        ph.def_encoding = dph[3]
    elif ph.page_type == 2:
        ph.num_values = d[7][1]
        ph.encoding = d[7][2]
    return ph


# -- host page walk: bytes -> run tables ------------------------------------

PLAIN, PLAIN_DICTIONARY, RLE, RLE_DICTIONARY = 0, 2, 3, 8


@dataclasses.dataclass
class _HybridRuns:
    """Run table for one RLE/bit-packed hybrid stream, offsets relative to
    ONE shared packed-bytes buffer uploaded to the device."""

    kinds: List[int]          # 1 = RLE, 0 = bit-packed
    counts: List[int]
    values: List[int]         # RLE value (0 for bit-packed runs)
    bit_starts: List[int]     # absolute bit offset into the packed buffer
    widths: List[int]         # per-run bit width (dict width grows as the
    #                           dictionary fills across pages)

    def __init__(self):
        self.kinds, self.counts, self.values = [], [], []
        self.bit_starts, self.widths = [], []

    def non_null_count(self, start_run: int, packed: bytearray) -> int:
        """Popcount of a bit-width-1 (definition level) run suffix: the
        number of NON-NULL values — which is exactly how many entries the
        page's index stream stores."""
        total = 0
        for i in range(start_run, len(self.kinds)):
            if self.kinds[i] == 1:
                total += self.counts[i] * (self.values[i] & 1)
            else:
                b0 = self.bit_starts[i]
                count = self.counts[i]
                chunk = np.frombuffer(
                    packed, np.uint8,
                    count=(b0 % 8 + count + 7) // 8, offset=b0 // 8)
                bits = np.unpackbits(chunk, bitorder="little")
                total += int(bits[b0 % 8: b0 % 8 + count].sum())
        return total


def _parse_hybrid(buf: bytes, pos: int, end: int, bit_width: int,
                  n_values: int, runs: _HybridRuns, packed: bytearray,
                  pad_tail: bool = True) -> None:
    """Slice one hybrid stream into runs; bit-packed payloads append to
    ``packed``. Never expands values. Counts CAP at the page's n_values so
    the (multiple-of-8 padded) last bit-packed group never leaks phantom
    positions into the next page's runs."""
    produced = 0
    t = _Thrift(buf, pos)
    byte_w = (bit_width + 7) // 8
    while produced < n_values and t.pos < end:
        header = t.varint()
        if header & 1:  # bit-packed: (header>>1) groups of 8 values
            groups = header >> 1
            count = min(groups * 8, n_values - produced)
            nbytes = groups * bit_width  # groups * 8 * bw / 8
            runs.kinds.append(0)
            runs.counts.append(count)
            runs.values.append(0)
            runs.bit_starts.append(len(packed) * 8)
            runs.widths.append(bit_width)
            packed.extend(buf[t.pos: t.pos + nbytes])
            t.pos += nbytes
        else:
            count = min(header >> 1, n_values - produced)
            raw = buf[t.pos: t.pos + byte_w]
            t.pos += byte_w
            runs.kinds.append(1)
            runs.counts.append(count)
            runs.values.append(int.from_bytes(raw, "little"))
            runs.bit_starts.append(0)
            runs.widths.append(bit_width)
        produced += count
    if pad_tail and produced < n_values:
        # Implicit trailing zeros (writers may omit the final RLE run).
        runs.kinds.append(1)
        runs.counts.append(n_values - produced)
        runs.values.append(0)
        runs.bit_starts.append(0)
        runs.widths.append(bit_width)


_PHYS_NP = {"INT32": np.int32, "INT64": np.int64, "FLOAT": np.float32,
            "DOUBLE": np.float64, "BOOLEAN": np.bool_}


@dataclasses.dataclass
class ColumnChunkPlan:
    """Everything the device kernel needs for one column chunk, prepared
    host-side from page bytes."""

    dtype: T.DataType
    n_rows: int
    nullable: bool
    # whether any page held a null, read from the pages' definition levels
    # (never for a REQUIRED column): a chunk without one decodes without
    # the definition-level table and without slots
    has_nulls: bool
    # definition-level hybrid (bw=1): validity
    def_runs: Optional[_HybridRuns]
    # value source: dictionary indices (hybrid) + dictionary, or PLAIN
    # values uploaded directly, or (a chunk whose writer fell back) the
    # first ``dict_count`` non-null values from the one, the rest from the
    # other
    idx_runs: Optional[_HybridRuns]
    idx_bit_width: int
    packed: bytes             # shared packed buffer (def + idx bitpacks)
    plain_values: Optional[np.ndarray]
    dict_count: int           # non-null values of the dictionary pages
    # dictionary: fixed-width values, or sorted string dict + rank remap
    dict_values: Optional[np.ndarray]
    dict_rank: Optional[np.ndarray]
    dict_offsets: Optional[np.ndarray]
    dict_payload: Optional[np.ndarray]
    # a BYTE_ARRAY chunk with PLAIN values: the pages' value bytes back to
    # back (the dictionary page first, where there is one), zero-padded to
    # their byte bucket, and per page where its values start in them and
    # how many it holds
    text_src: Optional[np.ndarray] = None
    page_starts: Optional[List[int]] = None
    page_counts: Optional[List[int]] = None


def _decompress(codec: str, payload: bytes, uncompressed_size: int) -> bytes:
    if codec == "UNCOMPRESSED":
        return payload
    import pyarrow as pa
    return pa.Codec(codec.lower()).decompress(
        payload, decompressed_size=uncompressed_size).to_pybytes()


def plan_column_chunk(f, col_md, field: T.StructField,
                      max_def_level: int) -> ColumnChunkPlan:
    """Host phase for one column chunk: page headers -> run tables.

    ``f`` is an open file object; ``col_md`` a pyarrow ColumnChunkMetaData;
    ``max_def_level`` comes from the FILE's schema (a REQUIRED column has
    no definition-level stream regardless of what the engine schema says
    about nullability — trusting the engine schema here mis-frames the
    page payload). Raises NotImplementedError for shapes outside scope
    (caller falls back to the host scan)."""
    if max_def_level > 1:
        raise NotImplementedError("nested columns (max_def_level > 1)")
    phys = col_md.physical_type
    if phys not in _PHYS_NP and phys != "BYTE_ARRAY":
        raise NotImplementedError(f"physical type {phys}")
    start = col_md.data_page_offset
    if col_md.has_dictionary_page:
        start = min(start, col_md.dictionary_page_offset)
    f.seek(start)
    chunk = f.read(col_md.total_compressed_size)
    codec = col_md.compression

    pos = 0
    dict_vals_raw: Optional[bytes] = None
    def_runs = _HybridRuns()
    idx_runs = _HybridRuns()
    packed = bytearray()
    plain_parts: List[bytes] = []
    plain_counts: List[int] = []
    n_dict = 0
    idx_bw = 0
    n_rows = 0
    dict_count = 0
    has_nulls = False
    uses_dict = False
    uses_plain = False
    while pos < len(chunk):
        ph = _parse_page_header(chunk, pos)
        pos += ph.header_len
        payload = _decompress(codec, chunk[pos: pos + ph.compressed_size],
                              ph.uncompressed_size)
        pos += ph.compressed_size
        if ph.page_type == 2:  # dictionary page (PLAIN-encoded)
            dict_vals_raw = payload
            n_dict = ph.num_values
            continue
        if ph.page_type != 0:
            raise NotImplementedError(f"page type {ph.page_type} (v2?)")
        p = 0
        page_def_start = len(def_runs.kinds)
        if max_def_level > 0:
            if ph.def_encoding != RLE:
                raise NotImplementedError("non-RLE definition levels")
            (def_len,) = _struct.unpack_from("<I", payload, p)
            p += 4
            _parse_hybrid(payload, p, p + def_len, 1, ph.num_values,
                          def_runs, packed)
            p += def_len
            non_null = def_runs.non_null_count(page_def_start, packed)
        else:
            def_runs.kinds.append(1)
            def_runs.counts.append(ph.num_values)
            def_runs.values.append(1)
            def_runs.bit_starts.append(0)
            def_runs.widths.append(1)
            non_null = ph.num_values
        has_nulls = has_nulls or non_null < ph.num_values
        if ph.encoding in (PLAIN_DICTIONARY, RLE_DICTIONARY):
            if uses_plain:
                # the writers' fallback is one-way: a slot's source is
                # told by its position alone only in that order
                raise NotImplementedError(
                    "dictionary pages after PLAIN pages")
            uses_dict = True
            dict_count += non_null
            bw = payload[p]
            p += 1
            if bw > 24:
                raise NotImplementedError(f"dictionary bit width {bw}")
            idx_bw = max(idx_bw, bw)
            # The page stores exactly non_null indices (indices exist only
            # for non-null slots; the def mask scatters them into row order
            # on device). Capping at the EXACT count keeps multi-page run
            # tables positionally aligned; per-run widths let later pages
            # use wider codes as the dictionary fills.
            _parse_hybrid(payload, p, len(payload), bw, non_null,
                          idx_runs, packed)
        elif ph.encoding == PLAIN:
            uses_plain = True
            plain_parts.append(payload[p:])
            plain_counts.append(non_null)
        else:
            raise NotImplementedError(f"encoding {ph.encoding}")
        n_rows += ph.num_values
    text_plain = phys == "BYTE_ARRAY" and (uses_plain or not uses_dict)

    plan = ColumnChunkPlan(
        dtype=field.data_type, n_rows=n_rows, nullable=field.nullable,
        has_nulls=has_nulls, def_runs=def_runs,
        idx_runs=idx_runs if uses_dict else None,
        idx_bit_width=idx_bw, packed=bytes(packed),
        plain_values=None, dict_count=dict_count, dict_values=None,
        dict_rank=None, dict_offsets=None, dict_payload=None)

    if text_plain:
        # the values' bytes as the pages hold them; finding each value in
        # them is the device's work (no per-value host loop)
        parts = ([dict_vals_raw] if uses_dict else []) + plain_parts
        plan.page_counts = ([n_dict] if uses_dict else []) + plain_counts
        ends = np.cumsum([len(part) for part in parts], dtype=np.int64)
        plan.page_starts = [int(end) - len(part)
                            for end, part in zip(ends, parts)]
        plan.text_src = np.zeros(bucket_byte_capacity(
            max(int(ends[-1]) if parts else 0, 4)), np.uint8)
        for start, part in zip(plan.page_starts, parts):
            plan.text_src[start: start + len(part)] = np.frombuffer(
                part, np.uint8)
        return plan
    if uses_plain:
        raw = b"".join(plain_parts)
        if phys == "BOOLEAN":
            raise NotImplementedError("PLAIN boolean pages")
        plan.plain_values = np.frombuffer(
            raw, dtype=_PHYS_NP[phys]).astype(
                field.data_type.np_dtype, copy=False)
    if uses_dict:
        assert dict_vals_raw is not None, "dict pages missing"
        if phys == "BYTE_ARRAY":
            # PLAIN byte-array dictionary: [u32 len][bytes]... Host-parse
            # (dictionary-sized, small), sort, build the rank remap so the
            # device column lands dict_sorted.
            vals: List[bytes] = []
            q = 0
            while q < len(dict_vals_raw):
                (ln,) = _struct.unpack_from("<I", dict_vals_raw, q)
                q += 4
                vals.append(dict_vals_raw[q: q + ln])
                q += ln
            order = np.argsort(np.asarray(vals, dtype=object), kind="stable")
            rank = np.empty(len(vals), dtype=np.int32)
            rank[order] = np.arange(len(vals), dtype=np.int32)
            sorted_vals = [vals[i] for i in order] or [b""]
            lens = np.asarray([len(b) for b in sorted_vals], np.int32)
            plan.dict_offsets = np.concatenate(
                [[0], np.cumsum(lens)]).astype(np.int32)
            plan.dict_payload = np.frombuffer(
                b"".join(sorted_vals) or b"\0", dtype=np.uint8)
            plan.dict_rank = rank
        else:
            plan.dict_values = np.frombuffer(
                dict_vals_raw, dtype=_PHYS_NP[phys]).astype(
                    field.data_type.np_dtype, copy=False)
    return plan


# -- device expansion kernels -----------------------------------------------


def _expand_hybrid(kinds, counts, values, bit_starts, widths, packed,
                   capacity):
    """Expand a hybrid run table to ``capacity`` int32 values (traced).

    For output i: its run via searchsorted over cumulative counts; RLE runs
    broadcast, bit-packed runs gather 4 bytes around the value's bit
    position and shift/mask. Widths are per RUN (a dictionary's bit width
    grows across pages as it fills; <= 24, so shift <= 7 + width <= 24
    keeps every value inside the 4 gathered bytes)."""
    with jax.named_scope("expand_hybrid"):
        ends = jnp.cumsum(counts)
        starts = ends - counts
        i = jnp.arange(capacity, dtype=jnp.int32)
        r = jnp.searchsorted(ends, i, side="right")
        r = jnp.clip(r, 0, kinds.shape[0] - 1)
        within = i - starts[r]
        w = widths[r]
        bit0 = bit_starts[r] + within * w
    with jax.named_scope("unpack"):
        byte0 = bit0 >> 3
        shift = (bit0 & 7).astype(jnp.uint32)
        nb = packed.shape[0]
        b = [packed[jnp.clip(byte0 + k, 0, nb - 1)].astype(jnp.uint32)
             for k in range(4)]
        word = b[0] | (b[1] << 8) | (b[2] << 16) | (b[3] << 24)
        mask = (jnp.uint32(1) << jnp.clip(w, 0, 31).astype(jnp.uint32)) \
            - jnp.uint32(1)
        packed_val = ((word >> shift) & mask).astype(jnp.int32)
        return jnp.where(kinds[r] == 1, values[r], packed_val)


def _decode_chunk_device(def_table, idx_table, packed, plain, dict_table,
                         n_rows, capacity, idx_bw, dtype,
                         dict_string: bool, dict_count=None):
    """Traced device decode of one column chunk (see module doc). Each
    phase runs under a ``jax.named_scope`` (trace-time only), so XProf
    and the HLO metadata tell them apart inside the one program.
    ``dict_count`` (a traced scalar, never a shape) comes with a chunk
    that holds both sources: non-null slot ``s`` reads the dictionary
    below it and ``plain[s - dict_count]`` from it on."""
    with jax.named_scope("def_levels"):
        live = jnp.arange(capacity, dtype=jnp.int32) < n_rows
        dk, dc, dv, db, dw = def_table
        levels = _expand_hybrid(dk, dc, dv, db, dw, packed, capacity)
        validity = (levels == 1) & live
        # Indices/values are stored for NON-NULL slots only, compacted:
        # row -> slot via an exclusive cumsum of the validity mask.
        slot = jnp.cumsum(validity.astype(jnp.int32)) - 1
        slot = jnp.clip(slot, 0, capacity - 1)
    if idx_table is not None:
        ik, ic, iv, ib, iw = idx_table
        raw_idx = _expand_hybrid(ik, ic, iv, ib, iw, packed, capacity)
        if plain is not None:
            with jax.named_scope("dict_or_plain"):
                # one gather over [dictionary | PLAIN values]: a slot's
                # source is told by its position among the non-null values
                n_dict = dict_table.shape[0]
                source = jnp.where(
                    slot < dict_count,
                    jnp.clip(raw_idx[slot], 0, n_dict - 1),
                    n_dict + jnp.clip(slot - dict_count, 0, capacity - 1))
                vals = jnp.concatenate([dict_table, plain])[source]
                data = jnp.where(validity, vals, jnp.zeros((), vals.dtype))
                return data, validity
        with jax.named_scope("dict_gather"):
            codes = jnp.where(validity, raw_idx[slot], 0)
            if dict_string:
                rank = dict_table
                codes = jnp.where(
                    validity, rank[jnp.clip(codes, 0, rank.shape[0] - 1)], 0)
                return codes, validity
            vals = dict_table[jnp.clip(codes, 0, dict_table.shape[0] - 1)]
            data = jnp.where(validity, vals, jnp.zeros((), vals.dtype))
            return data, validity
    with jax.named_scope("plain_scatter"):
        data = jnp.where(validity, plain[slot], jnp.zeros((), plain.dtype))
        return data, validity


def _pad_packed(packed: bytes) -> np.ndarray:
    raw = np.frombuffer(packed or b"\0\0\0\0", dtype=np.uint8)
    cap = bucket_byte_capacity(max(len(raw), 4), 8)
    buf = np.zeros(cap, np.uint8)
    buf[: len(raw)] = raw
    return buf


def _runs_arrays(runs: _HybridRuns, pad_to: int):
    def arr(xs, fill):
        a = np.full(pad_to, fill, np.int32)
        a[: len(xs)] = xs
        return a
    # Padding runs have count 0 -> they own no output positions.
    return (arr(runs.kinds, 1), arr(runs.counts, 0), arr(runs.values, 0),
            arr(runs.bit_starts, 0), arr(runs.widths, 1))


def _bucketed(arr, dtype) -> np.ndarray:
    """Pad to a power-of-two length: unbucketed shapes would retrace
    the jitted kernel per row group (kernel_cache discipline). Also
    keeps (masked-out) gathers in range for empty dictionaries."""
    cap = bucket_byte_capacity(max(len(arr), 1), 8)
    buf = np.zeros(cap, dtype)
    buf[: len(arr)] = arr
    return buf


def _count(counters: Optional[dict], name: str, value: int) -> None:
    if counters is not None:
        counters[name] = counters.get(name, 0) + value


def _count_chunk(counters: Optional[dict], plan: "ColumnChunkPlan",
                 kinds, host, upload_ns: int, launch_ns: int) -> None:
    """One decoded chunk into the scan's counters: the upload's and the
    launch's host nanoseconds, the bytes uploaded, and the chunk under
    each of ``kinds`` (what its pages hold)."""
    _count(counters, "scanUploadNs", upload_ns)
    _count(counters, "uploadBytes",
           sum(a.nbytes for a in jax.tree_util.tree_leaves(host)))
    _count(counters, "scanLaunchNs", launch_ns)
    _count(counters, "scanColumnChunksDecoded", 1)
    for kind in kinds:
        _count(counters, kind, 1)
    _count(counters, "scanChunksNoNulls", int(not plan.has_nulls))


def _live_rows(n_rows, capacity):
    """(row numbers, validity) of a chunk without nulls: all there is to
    compute for a PLAIN one, whose uploaded, zero-padded buffer is the
    column."""
    with jax.named_scope("live_rows"):
        row = jnp.arange(capacity, dtype=jnp.int32)
        return row, row < n_rows


def _decode_chunk_no_nulls(idx_table, packed, plain, dict_table, n_rows,
                           capacity, dict_count=None):
    """Traced device decode of a dictionary-encoded chunk whose pages hold
    no null: row ``i`` is non-null value ``i``, so there is no
    definition-level table, no prefix sum and no gather through slots. A
    dictionary-string chunk gathers from its rank table as a number does
    from its dictionary; ``plain`` and ``dict_count`` come with a chunk
    that fell back."""
    row, validity = _live_rows(n_rows, capacity)
    ik, ic, iv, ib, iw = idx_table
    idx = _expand_hybrid(ik, ic, iv, ib, iw, packed, capacity)
    n_dict = dict_table.shape[0]
    if plain is not None:
        with jax.named_scope("dict_or_plain"):
            source = jnp.where(
                row < dict_count,
                jnp.clip(idx, 0, n_dict - 1),
                n_dict + jnp.clip(row - dict_count, 0, capacity - 1))
            vals = jnp.concatenate([dict_table, plain])[source]
    else:
        with jax.named_scope("dict_gather"):
            vals = dict_table[jnp.clip(idx, 0, n_dict - 1)]
    return jnp.where(validity, vals, jnp.zeros((), vals.dtype)), validity


def _run_table_bucket(*tables: Optional[_HybridRuns]) -> int:
    return bucket_byte_capacity(
        max([len(t.kinds) for t in tables if t is not None] + [1]), 8)


def _nullable_program(plan: ColumnChunkPlan, capacity: int, kind: str):
    """(program, host operands, their order) of a chunk with nulls: the
    definition levels expand to the validity, its prefix sum gives each
    row its slot among the non-null values."""
    idx_bw, dtype = plan.idx_bit_width, plan.dtype
    dict_string = plan.dict_rank is not None
    has_idx = plan.idx_runs is not None
    has_plain = plan.plain_values is not None
    pad = _run_table_bucket(plan.def_runs, plan.idx_runs)

    def build():
        def kern(dt, it, pk, pl, dtab, n, dict_count=None):
            return _decode_chunk_device(dt, it, pk, pl, dtab, n, capacity,
                                        idx_bw, dtype, dict_string,
                                        dict_count)
        return kern
    kern = cached_kernel(
        "parquet_decode",
        (dtype.name, capacity, idx_bw, has_idx, dict_string, has_plain,
         pad),
        build, suffix=f"{dtype.name}_bw{idx_bw}_{kind}")
    host = {"def": _runs_arrays(plan.def_runs, pad),
            "idx": _runs_arrays(plan.idx_runs, pad) if has_idx else None,
            "packed": _pad_packed(plan.packed)}
    return kern, host, (["def", "idx", "packed", "plain", "dict", "n_rows"]
                        + ["dict_count"] * (has_idx and has_plain))


def _no_nulls_program(plan: ColumnChunkPlan, capacity: int, kind: str):
    """(program, host operands, their order) of a chunk without nulls,
    keyed by what :func:`_decode_chunk_no_nulls` reads: the definition
    levels are neither staged nor uploaded, the run-table bucket is the
    index stream's alone, and the index bit width (per run, in the table)
    is in no key — one program serves every width."""
    if plan.idx_runs is None:
        def build():
            return lambda n: _live_rows(n, capacity)[1]
        kern = cached_kernel("parquet_decode", ("plain_nn", capacity),
                             build, suffix="plain_nn")
        return kern, {}, ["n_rows"]
    has_plain = plan.plain_values is not None
    pad = _run_table_bucket(plan.idx_runs)

    def build():
        if has_plain:
            return lambda it, pk, pl, dtab, n, dict_count: \
                _decode_chunk_no_nulls(it, pk, pl, dtab, n, capacity,
                                       dict_count)
        return lambda it, pk, dtab, n: _decode_chunk_no_nulls(
            it, pk, None, dtab, n, capacity)
    kern = cached_kernel(
        "parquet_decode", (plan.dtype.name, capacity, f"{kind}_nn", pad),
        build, suffix=f"{plan.dtype.name}_{kind}_nn")
    host = {"idx": _runs_arrays(plan.idx_runs, pad),
            "packed": _pad_packed(plan.packed)}
    return kern, host, ["idx", "packed"] + (
        ["plain", "dict", "n_rows", "dict_count"] if has_plain
        else ["dict", "n_rows"])


# -- PLAIN byte arrays: a flat string column --------------------------------


def _width_bucket(n: int) -> int:
    """A flat column's ``max_bytes`` from its longest value: sixteens up
    to 128 (a char matrix is rows x this, and TPC-H's comments are 43 to
    198 bytes wide), the byte ladder above."""
    n = max(int(n), 1)
    return -(-n // 16) * 16 if n <= 128 else bucket_byte_capacity(n, 8)


def _length_walk(src, page_starts, page_counts, walk_steps, steps_cap):
    """(starts, lengths), each ``int32[steps_cap * pages]`` with value
    ``k`` of page ``p`` at ``k * pages + p``: where its bytes start in
    ``src`` and how many they are. A value's place follows from the length
    before it, so the walk is serial within a page; the pages walk side by
    side, one lane each, ``walk_steps`` (a traced scalar: the most values
    any page holds) steps in all."""
    with jax.named_scope("length_walk"):
        n_pages = page_starts.shape[0]
        last = src.shape[0] - 1
        # The buffer as little-endian 32-bit words: a prefix at any byte is
        # two words shifted together, two gathers a step and not one a
        # byte (a gather out of HBM is the walk's whole cost).
        words = jax.lax.bitcast_convert_type(
            jnp.pad(src, (0, -src.shape[0] % 4)).reshape(-1, 4), jnp.uint32)

        def step(k, state):
            pos, table = state
            word = pos >> 2
            shift = ((pos & 3) << 3).astype(jnp.uint32)
            low = words.at[word].get(mode="clip") >> shift
            high = jnp.where(shift == 0, jnp.uint32(0),
                             words.at[word + 1].get(mode="clip")
                             << (jnp.uint32(32) - shift))
            length = (low | high).astype(jnp.int32)
            live = k < page_counts
            # a corrupt prefix cannot walk out of the buffer
            length = jnp.where(live, jnp.clip(length, 0, last - pos), 0)
            table = jax.lax.dynamic_update_slice(
                table, jnp.stack([pos + 4, length])[None],
                (k, jnp.zeros((), jnp.int32), jnp.zeros((), jnp.int32)))
            return jnp.where(live, pos + 4 + length, pos), table

        _, table = jax.lax.fori_loop(
            0, walk_steps, step,
            (page_starts, jnp.zeros((steps_cap, 2, n_pages), jnp.int32)))
        return table[:, 0].reshape(-1), table[:, 1].reshape(-1)


def _decode_text_rows(src, page_starts, page_counts, walk_steps, n_rows,
                      def_table, idx_table, packed, dict_count,
                      capacity, steps_cap, idx_cap):
    """Traced: every row's source in ``src``, its length and its validity,
    and ``[total bytes, longest value]`` for the host to size the column
    by. ``def_table`` comes with a chunk that holds nulls, ``idx_table``
    (expanded over ``idx_cap`` slots only) and ``dict_count`` with one
    that starts on its dictionary: page 0 of the walk is then the
    dictionary page, non-null value ``s`` below ``dict_count`` is the entry
    its index names and value ``s`` from there on is PLAIN value
    ``s - dict_count``."""
    row, validity = _live_rows(n_rows, capacity)
    slot = row
    if def_table is not None:
        with jax.named_scope("def_levels"):
            dk, dc, dv, db, dw = def_table
            levels = _expand_hybrid(dk, dc, dv, db, dw, packed, capacity)
            validity = (levels == 1) & validity
            slot = jnp.clip(jnp.cumsum(validity.astype(jnp.int32)) - 1,
                            0, capacity - 1)
    starts, lens = _length_walk(src, page_starts, page_counts, walk_steps,
                                steps_cap)
    with jax.named_scope("row_source"):
        n_pages = page_starts.shape[0]
        plain_counts = page_counts
        plain_slot = slot
        if idx_table is not None:
            plain_counts = page_counts.at[0].set(0)
            plain_slot = slot - dict_count
        ends = jnp.cumsum(plain_counts)
        page = jnp.clip(jnp.searchsorted(ends, plain_slot, side="right",
                                         method="compare_all"),
                        0, n_pages - 1)
        value = plain_slot - (ends - plain_counts)[page]
        if idx_table is not None:
            ik, ic, iv, ib, iw = idx_table
            idx = _expand_hybrid(ik, ic, iv, ib, iw, packed, idx_cap)
            entry = jnp.clip(idx[jnp.clip(slot, 0, idx_cap - 1)], 0,
                             jnp.maximum(page_counts[0] - 1, 0))
            from_dict = slot < dict_count
            page = jnp.where(from_dict, 0, page)
            value = jnp.where(from_dict, entry, value)
        at = jnp.clip(value, 0, steps_cap - 1) * n_pages + page
        row_start = jnp.where(validity, starts[at], 0)
        row_len = jnp.where(validity, lens[at], 0)
        stats = jnp.stack([jnp.sum(row_len), jnp.max(row_len)])
    return row_start, row_len, validity, stats


def _place_text(src, row_start, row_len, out_cap):
    """Traced: (payload ``uint8[out_cap]``, offsets ``int32[rows + 1]``) of
    the flat column: the rows' bytes back to back in row order. Output byte
    ``j`` of row ``r`` is ``src[j + delta[r]]`` with ``delta[r] =
    row_start[r] - offsets[r]``; the steps of ``delta`` are scattered to
    the rows' first bytes (a million updates, not a search a byte) and a
    prefix sum carries each to the row's other bytes. Rows without bytes
    (null, empty, dead) share their successor's first byte, where the
    steps add up to the successor's ``delta``."""
    with jax.named_scope("text_place"):
        offsets = jnp.concatenate([jnp.zeros(1, jnp.int32),
                                   jnp.cumsum(row_len).astype(jnp.int32)])
        delta = row_start - offsets[:-1]
        step = delta - jnp.concatenate([jnp.zeros(1, jnp.int32), delta[:-1]])
        marks = jnp.zeros(out_cap, jnp.int32).at[offsets[:-1]].add(
            step, mode="drop", indices_are_sorted=True)
        byte = jnp.arange(out_cap, dtype=jnp.int32)
        source = jnp.clip(byte + jnp.cumsum(marks), 0, src.shape[0] - 1)
        payload = jnp.where(byte < offsets[-1], src[source],
                            jnp.zeros((), jnp.uint8))
        return payload, offsets


def _text_programs(plan: ColumnChunkPlan, capacity: int, kind: str):
    """(rows program, host operands, their order) of a BYTE_ARRAY chunk
    with PLAIN values. Every shape is a bucket: rows, source bytes, pages,
    the most values a page holds, and (a chunk that starts on its
    dictionary) the slots its index stream is expanded over and its run
    table; the true counts are operands."""
    has_idx = plan.idx_runs is not None
    n_pages = bucket_byte_capacity(max(len(plan.page_counts), 1), 8)
    steps_cap = bucket_capacity(max(plan.page_counts + [1]))
    idx_cap = bucket_capacity(max(plan.dict_count, 1)) if has_idx else 0
    pad = _run_table_bucket(plan.def_runs if plan.has_nulls else None,
                            plan.idx_runs)
    nulls = plan.has_nulls

    def build():
        def kern(src, starts, counts, steps, n, *rest):
            rest = list(rest)
            dt = rest.pop(0) if nulls else None
            it = rest.pop(0) if has_idx else None
            pk = rest.pop(0) if nulls or has_idx else None
            count = rest.pop(0) if has_idx else None
            return _decode_text_rows(src, starts, counts, steps, n, dt, it,
                                     pk, count, capacity, steps_cap,
                                     idx_cap)
        return kern
    name = f"string_{kind}" + ("" if nulls else "_nn")
    kern = cached_kernel(
        "parquet_decode", (name, capacity, steps_cap, idx_cap, pad), build,
        suffix=name)

    def padded(xs):
        a = np.zeros(n_pages, np.int32)
        a[: len(xs)] = xs
        return a
    host = {"src": plan.text_src, "page_starts": padded(plan.page_starts),
            "page_counts": padded(plan.page_counts),
            "walk_steps": np.asarray(max(plan.page_counts + [0]), np.int32),
            "n_rows": np.asarray(plan.n_rows, np.int32)}
    order = ["src", "page_starts", "page_counts", "walk_steps", "n_rows"]
    if nulls:
        host["def"] = _runs_arrays(plan.def_runs, pad)
        order.append("def")
    if has_idx:
        host["idx"] = _runs_arrays(plan.idx_runs, pad)
        order.append("idx")
    if nulls or has_idx:
        host["packed"] = _pad_packed(plan.packed)
        order.append("packed")
    if has_idx:
        host["dict_count"] = np.asarray(plan.dict_count, np.int32)
        order.append("dict_count")
    return kern, host, order


def _decode_text_chunk(plan: ColumnChunkPlan, capacity: int,
                       counters: Optional[dict]) -> DeviceColumn:
    """A BYTE_ARRAY chunk with PLAIN values to a flat string column: the
    rows program, then — the one wait of a chunk's decode, for two numbers
    — the text's bytes and the longest value back on the host, which size
    the payload's bucket and the column's ``max_bytes``, then the copy."""
    import time
    has_idx = plan.idx_runs is not None
    kind, counter = (("dictplain", "scanChunksDictionaryThenPlain")
                     if has_idx else ("plain", "scanChunksPlain"))
    kern, host, order = _text_programs(plan, capacity, kind)
    t0 = time.perf_counter_ns()
    dev = jax.tree_util.tree_map(jnp.asarray, host)
    t1 = time.perf_counter_ns()
    row_start, row_len, validity, stats = kern(*[dev[n] for n in order])
    total, longest = (int(x) for x in jax.device_get(stats))
    out_cap = bucket_byte_capacity(max(total, 1))
    place = cached_kernel(
        "parquet_decode", ("string_plain_place", out_cap),
        lambda: lambda src, start, length: _place_text(
            src, start, length, out_cap),
        suffix="string_plain_place")
    payload, offsets = place(dev["src"], row_start, row_len)
    t2 = time.perf_counter_ns()
    _count_chunk(counters, plan, (counter, "scanChunksByteArrayPlain"),
                 host, t1 - t0, t2 - t1)
    return DeviceColumn(data=payload, validity=validity, dtype=T.STRING,
                        offsets=offsets, max_bytes=_width_bucket(longest))


def decode_chunk(plan: ColumnChunkPlan, capacity: int,
                 counters: Optional[dict] = None) -> DeviceColumn:
    """Upload one chunk's page bytes + run tables and decode on device.
    ``counters`` (the scan's, one dict per row group) takes the upload's
    and the launch's host nanoseconds, the bytes uploaded and the chunk
    itself: three clock reads a chunk, nothing per row."""
    import time
    if plan.text_src is not None:
        return _decode_text_chunk(plan, capacity, counters)
    dict_string = plan.dict_rank is not None
    has_idx = plan.idx_runs is not None
    has_plain = plan.plain_values is not None

    # what the chunk's pages hold, read from them: the program's kind and
    # the counter it is counted under
    kind, counter = (
        ("dictstr", "scanChunksDictionary") if dict_string
        else ("dictplain", "scanChunksDictionaryThenPlain")
        if has_idx and has_plain
        else ("dict", "scanChunksDictionary") if has_idx
        else ("plain", "scanChunksPlain"))
    kern, host, order = (_nullable_program if plan.has_nulls
                         else _no_nulls_program)(plan, capacity, kind)

    # Host staging (pad to the bucketed shapes), then every host->device
    # copy of the chunk in one timed stretch.
    host["n_rows"] = np.asarray(plan.n_rows, np.int32)
    if dict_string:
        host["dict"] = _bucketed(plan.dict_rank, np.int32)
        byte_cap = bucket_byte_capacity(max(int(plan.dict_offsets[-1]), 1))
        payload = np.zeros(byte_cap, np.uint8)
        payload[: len(plan.dict_payload)] = plan.dict_payload
        host["payload"] = payload
        host["offsets"] = plan.dict_offsets
    elif plan.dict_values is not None:
        host["dict"] = _bucketed(plan.dict_values, plan.dict_values.dtype)
    if has_plain:
        buf = np.zeros(capacity, plan.plain_values.dtype)
        buf[: len(plan.plain_values)] = plan.plain_values
        host["plain"] = buf
    if kind == "dictplain":
        # where the chunk fell back is data: an operand, in no shape or key
        host["dict_count"] = np.asarray(plan.dict_count, np.int32)
    t0 = time.perf_counter_ns()
    dev = jax.tree_util.tree_map(jnp.asarray, host)
    t1 = time.perf_counter_ns()
    out = kern(*[dev.get(name) for name in order])
    # a PLAIN chunk without nulls is the uploaded buffer as it is
    data, validity = out if has_idx or plan.has_nulls \
        else (dev["plain"], out)
    t2 = time.perf_counter_ns()
    _count_chunk(counters, plan, (counter,), host, t1 - t0, t2 - t1)
    if dict_string:
        max_bytes = 8
        if plan.dict_offsets is not None and len(plan.dict_offsets) > 1:
            max_bytes = bucket_byte_capacity(
                int(np.diff(plan.dict_offsets).max() or 1), 8)
        return DeviceColumn(
            data=dev["payload"], validity=validity, dtype=T.STRING,
            offsets=dev["offsets"], max_bytes=max_bytes,
            codes=data, dict_sorted=True)
    return DeviceColumn(data=data, validity=validity, dtype=plan.dtype)


def decode_row_group(path: str, row_group: int, schema: T.Schema,
                     pf=None, meta=None, pq_schema=None,
                     counters: Optional[dict] = None) -> ColumnarBatch:
    """Decode one row group of a parquet file into a device batch.
    Pass either an open ``pyarrow.parquet.ParquetFile`` or its parsed
    ``(meta, pq_schema)`` to amortize the footer parse across a file's
    row groups (metadata objects hold no file descriptor). ``counters``
    is added to in place: ``scanParseNs`` here (file read, page headers,
    decompression, run tables), the rest in :func:`decode_chunk`."""
    import time
    import pyarrow.parquet as pq
    if meta is None:
        if pf is None:
            pf = pq.ParquetFile(path)
        meta, pq_schema = pf.metadata, pf.schema
    md = meta.row_group(row_group)
    name_to_idx = {md.column(i).path_in_schema: i
                   for i in range(md.num_columns)}
    cols = []
    n_rows = md.num_rows
    capacity = bucket_capacity(max(n_rows, 1))
    with open(path, "rb") as f:
        for field in schema:
            ci = name_to_idx[field.name]
            t0 = time.perf_counter_ns()
            plan = plan_column_chunk(
                f, md.column(ci), field,
                pq_schema.column(ci).max_definition_level)
            _count(counters, "scanParseNs", time.perf_counter_ns() - t0)
            cols.append(decode_chunk(plan, capacity, counters))
    return ColumnarBatch(tuple(cols), jnp.asarray(n_rows, jnp.int32),
                         schema)


class SparkUpgradeError(RuntimeError):
    """Ambiguous legacy-calendar datetimes (the SparkUpgradeException the
    reference raises via RebaseHelper.newRebaseExceptionInRead)."""


#: Proleptic/Julian switchover bounds (RebaseDateTime.lastSwitchJulianDay/
#: Ts): dates before 1582-10-15 and timestamps before 1900-01-01 differ
#: between the hybrid and proleptic Gregorian calendars.
_JULIAN_SWITCH_DATE = _dt.date(1582, 10, 15)
_JULIAN_SWITCH_TS = _dt.datetime(1900, 1, 1)
_LEGACY_MARKER = b"org.apache.spark.legacyDateTime"


def rebase_guard(meta, schema: T.Schema, mode: str, path: str) -> None:
    """The RebaseHelper.isDateTimeRebaseNeededRead analog
    (reference RebaseHelper.scala:60,82): files written by Spark 2.x /
    legacy Hive carry the legacyDateTime marker and a hybrid-calendar
    encoding for ancient datetimes. This reader never rebases, so under
    the default EXCEPTION mode a marked file whose date/timestamp
    statistics reach (or may reach — stats absent) below the 1582-10-15 /
    1900-01-01 switchover raises instead of silently mis-reading;
    CORRECTED reads raw values as proleptic, LEGACY is unsupported."""
    mode = (mode or "EXCEPTION").upper()
    if mode == "CORRECTED":
        return
    kv = meta.metadata or {}
    if _LEGACY_MARKER not in kv:
        return      # proleptic writer: nothing ambiguous
    if mode == "LEGACY":
        raise SparkUpgradeError(
            f"{path}: LEGACY datetime rebase is not supported on the TPU "
            "parquet reader (reference raises the same; "
            "RebaseHelper.scala:66). Set "
            "spark.sql.legacy.parquet.datetimeRebaseModeInRead=CORRECTED "
            "to read raw proleptic values.")
    dt_names = {f.name for f in schema
                if f.data_type in (T.DATE, T.TIMESTAMP)}
    if not dt_names:
        return
    for rg in range(meta.num_row_groups):
        md = meta.row_group(rg)
        for ci in range(md.num_columns):
            c = md.column(ci)
            if c.path_in_schema not in dt_names:
                continue
            st = c.statistics
            ancient = True      # stats absent: conservative
            if st is not None and st.has_min_max:
                mn = st.min
                if isinstance(mn, _dt.datetime):
                    ancient = mn.replace(tzinfo=None) < _JULIAN_SWITCH_TS
                elif isinstance(mn, _dt.date):
                    ancient = mn < _JULIAN_SWITCH_DATE
            if ancient:
                raise SparkUpgradeError(
                    f"{path}: reading dates before 1582-10-15 or "
                    "timestamps before 1900-01-01T00:00:00Z from parquet "
                    "files written with the legacy hybrid calendar is "
                    "ambiguous (SPARK-31404); this reader does not rebase. "
                    "Set spark.sql.legacy.parquet."
                    "datetimeRebaseModeInRead=CORRECTED to read the raw "
                    "values as-is.")


class TpuParquetScanExec:
    """Device parquet scan: one partition per (file, row group); each batch
    decodes ON DEVICE from uploaded page bytes (the GpuParquetScan +
    Table.readParquet split). A row group outside the decoder's scope
    falls back to a host pyarrow read + upload for JUST that row group —
    the reference's graceful per-unit degradation."""

    columnar = True
    children = ()
    children_coalesce_goals = None

    def __init__(self, files: List[str], schema: T.Schema,
                 file_schema: T.Schema, pf_cache=None):
        self.files = list(files)
        #: the columns the plan references (plan/optimizer.py): the only
        #: chunks parsed, uploaded and decoded. ``_file_schema`` is every
        #: column of the files, for explain.
        self._schema = schema
        self._file_schema = file_schema
        # Parsed footers carried from the planning-time gate so each one
        # parses ONCE: {path: (FileMetaData, ParquetSchema)} — metadata
        # objects only, NOT open file handles (a thousand-file scan must
        # not pin a thousand descriptors from plan time). Excluded from
        # plan signatures via PLAN_SIG_SKIP_ATTRS.
        self._pf_cache = dict(pf_cache or {})

    @property
    def schema(self):
        return self._schema

    def node_name(self):
        return "TpuParquetScanExec"

    def describe(self):
        from .files import columns_read
        return (f"TpuParquetScan files={len(self.files)} "
                f"{columns_read(self._schema, self._file_schema)}")

    def tree_string(self, indent: int = 0) -> str:
        return "  " * indent + self.describe() + "\n"

    def with_children(self, children):
        assert not children
        return self

    def execute(self, ctx):
        import pyarrow.parquet as pq
        from ..config import PARQUET_REBASE_READ
        rebase_mode = ctx.conf.get(PARQUET_REBASE_READ)
        units = []
        for path in self.files:
            cached = self._pf_cache.get(path)
            if cached is None:
                with pq.ParquetFile(path) as pf:
                    cached = (pf.metadata, pf.schema)
            meta, pq_schema = cached
            # Raised HERE, outside the per-row-group fallback, so the
            # ambiguity error cannot be swallowed by the host-read path.
            rebase_guard(meta, self._schema, rebase_mode, path)
            units.extend((path, meta, pq_schema, rg)
                         for rg in range(meta.num_row_groups))

        name = self.node_name()

        def read_unit(unit):
            path, meta, pq_schema, rg = unit
            from ..metrics.trace import span
            from ..utils.fault_injection import maybe_inject
            n_rows = meta.row_group(rg).num_rows
            io: Dict[str, int] = {}
            try:
                maybe_inject(ctx, "io.parquet.rowGroup")
                with ctx.registry.timer(name, "opTime",
                                        trace="parquet.device_decode",
                                        owner=ctx.trace):
                    batch = decode_row_group(path, rg, self._schema,
                                             meta=meta, pq_schema=pq_schema,
                                             counters=io)
                ctx.metric(name, "deviceDecodedRowGroups", 1)
            # ANY decode failure (unsupported shape, decompression codec
            # mismatch, corrupt/truncated page metadata) degrades to the
            # host reader for just this row group — the host result is the
            # correctness baseline, so falling back is always safe. Under
            # spark.rapids.sql.test.enabled nothing may leave the device
            # quietly (a compile refusal lands here too): re-raise.
            except Exception:  # noqa: BLE001 - graceful per-unit fallback
                if ctx.conf.test_enabled:
                    raise
                with span(ctx.trace, "parquet.host_fallback"), \
                        pq.ParquetFile(path) as pf:
                    tbl = pf.read_row_group(
                        rg, columns=self._schema.names)
                    rb = tbl.combine_chunks().to_batches()[0] \
                        if tbl.num_rows else None
                    import pyarrow as pa
                    if rb is None:
                        rb = pa.RecordBatch.from_pydict(
                            {n: [] for n in self._schema.names},
                            schema=T.schema_to_arrow(self._schema))
                    batch = ColumnarBatch.from_arrow(
                        rb.cast(T.schema_to_arrow(self._schema)))
                ctx.metric(name, "hostFallbackRowGroups", 1)
            finally:
                # what a failed device attempt spent is still the scan's
                for key, value in io.items():
                    ctx.metric(name, key, value)
            ctx.metric(name, "numOutputRows", n_rows)
            ctx.metric(name, "numOutputBatches", 1)
            return batch
        # One partition per row group (the scan partition contract), but
        # with the pipeline active the next `prefetchDepth` units decode
        # on the shared pool while the consumer uploads/dispatches the
        # current one — the reference's overlapped readPartFile stance.
        from ..exec.pipeline import unit_partitions
        return unit_partitions(read_unit, units, ctx, name)


def scan_files(paths: List[str]) -> Optional[List[str]]:
    """Concrete parquet files behind a scan's paths (None when the layout
    is unsupported, e.g. hive-partitioned directories)."""
    import os
    import pyarrow.dataset as pads
    try:
        src = paths[0] if len(paths) == 1 else paths
        if len(paths) == 1 and os.path.isdir(paths[0]):
            # Hive layouts carry partition columns in directory names that
            # the file-level decoder cannot restore — host path handles it.
            d = pads.dataset(src, format="parquet", partitioning="hive")
            if any("=" in os.path.basename(os.path.dirname(f))
                   for f in d.files):
                return None
            return list(d.files)
        return list(pads.dataset(src, format="parquet").files)
    except Exception:
        return None


def device_decodable(path: str, schema: T.Schema, pf=None) -> bool:
    """Cheap metadata-only check: can every SELECTED column of every row
    group go through the device decoder? (The graceful-fallback gate.)"""
    import pyarrow.parquet as pq
    try:
        if pf is None:
            pf = pq.ParquetFile(path)
    except Exception:
        return False
    for field in schema:
        if isinstance(field.data_type, (T.ArrayType, T.StructType)):
            return False
    file_cols = set(pf.schema_arrow.names)
    if not set(schema.names) <= file_cols:
        return False
    md = pf.metadata
    wanted = set(schema.names)
    for rg in range(md.num_row_groups):
        g = md.row_group(rg)
        for ci in range(g.num_columns):
            cm = g.column(ci)
            if cm.path_in_schema not in wanted:
                continue  # pruned away; its shape is irrelevant
            if cm.physical_type not in _PHYS_NP and \
                    cm.physical_type != "BYTE_ARRAY":
                return False
            encs = set(cm.encodings)
            # NOTE: "PLAIN" always appears (the dictionary page itself is
            # PLAIN-encoded), so a chunk that actually fell back to PLAIN
            # data pages is indistinguishable here; it decodes on the
            # device either way. What the footer cannot show (a PLAIN
            # boolean page, a dictionary page after PLAIN pages) is
            # refused by plan_column_chunk at scan time, which the scan
            # catches to fall back to the host path.
            if not encs <= {"PLAIN", "PLAIN_DICTIONARY", "RLE_DICTIONARY",
                            "RLE", "BIT_PACKED"}:
                return False
            # No "LZ4": parquet's legacy LZ4 is Hadoop-block-framed, which
            # pa.Codec("lz4") (frame format) cannot decode.
            if cm.compression not in ("UNCOMPRESSED", "SNAPPY", "ZSTD",
                                      "GZIP"):
                return False
    return True
