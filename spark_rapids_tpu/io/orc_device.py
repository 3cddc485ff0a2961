"""ORC device scan — stripe streams decoded through device run tables.

The reference reassembles ORC stripes host-side and device-decodes them
with cudf (``GpuOrcScan.scala:65,211``). The TPU-native split mirrors the
parquet decoder (:mod:`.parquet_device`): the host parses the protobuf
tail + stripe footers and the RLEv2 RUN HEADERS into compact run tables
(a few ints per run), and a jitted device kernel expands runs to row
space, scatters non-null slots through the PRESENT bitmask, and gathers
dictionary codes — the memory-proportional work stays on the device.

Scope (everything else falls back per stripe to a host pyarrow read, the
reference's graceful degradation):

* flat struct schemas,
* SHORT/INT/LONG/DATE via RLEv2 (short-repeat, direct, delta,
  patched-base), decoded as run tables: ``const``/``linear`` runs expand
  arithmetically on device, ``direct`` runs gather host-unpacked values,
* FLOAT/DOUBLE plain streams (uploaded, slot-scattered on device),
* STRING in DIRECT_V2 (lengths RLEv2 + blob -> host dictionary build,
  codes upload) and DICTIONARY_V2 (codes RLEv2 expand ON DEVICE against
  the uploaded dictionary),
* PRESENT byte-RLE (host-decoded to a packed bitmask; bits expand on
  device),
* NONE / ZLIB / SNAPPY / ZSTD block compression.
"""

from __future__ import annotations

import dataclasses
import functools
import os
import struct
import zlib
from typing import Dict, List, Optional, Tuple

import jax.numpy as jnp
import numpy as np
import pyarrow as pa

from ..utils import lockdep

from .. import types as T
from ..data.batch import ColumnarBatch
from ..data.column import (DeviceColumn, bucket_byte_capacity,
                           bucket_capacity)
from ..utils.kernel_cache import cached_kernel
from ..metrics.trace import span

MAGIC = b"ORC"

#: ORC type kinds (Types.proto)
_K_BOOL, _K_BYTE, _K_SHORT, _K_INT, _K_LONG = 0, 1, 2, 3, 4
_K_FLOAT, _K_DOUBLE, _K_STRING, _K_DATE, _K_STRUCT = 5, 6, 7, 15, 12
#: stream kinds
_S_PRESENT, _S_DATA, _S_LENGTH, _S_DICT = 0, 1, 2, 3
#: column encodings
_E_DIRECT, _E_DICT, _E_DIRECT_V2, _E_DICT_V2 = 0, 1, 2, 3

#: decode-path observability (tests assert rare encodings were exercised).
#: Incremented from DECODE WORKERS (the readers run stripes through
#: ordered_map_iter, exec/pipeline.py), so the bump must hold the lock —
#: an unlocked `+=` from concurrent workers loses updates (found by the
#: unguarded-shared-write pass, analysis/concurrency.py; regression:
#: tests/test_lockdep.py::TestOrcDecodeStats).
decode_stats = {"patched_base_runs": 0}
_STATS_LOCK = lockdep.lock("orc_device._STATS_LOCK")

#: RLEv2 5-bit width-code table (ORC spec "Closest fixed bit sizes").
_WIDTH_TABLE = [1, 2, 3, 4, 5, 6, 7, 8, 9, 10, 11, 12, 13, 14, 15, 16,
                17, 18, 19, 20, 21, 22, 23, 24, 26, 28, 30, 32, 40, 48,
                56, 64]


class NotOrcDecodable(Exception):
    pass


def _parse_boundary(fn):
    """Malformed/truncated input makes the hand-rolled parsers raise bare
    IndexError/ValueError/KeyError; translate those to NotOrcDecodable at
    the parser boundary so decode_stripe's fallback catch can stay
    narrow (decoder-logic regressions elsewhere still fail loudly)."""
    @functools.wraps(fn)
    def wrap(*a, **kw):
        try:
            return fn(*a, **kw)
        except (IndexError, ValueError, KeyError, struct.error) as e:
            raise NotOrcDecodable(f"{fn.__name__}: {e!r}") from e
    return wrap


# ---------------------------------------------------------------------------
# protobuf + file tail
# ---------------------------------------------------------------------------


@_parse_boundary
def _proto_fields(b: bytes) -> List[Tuple[int, int, object]]:
    out, i, n = [], 0, len(b)
    while i < n:
        tag = b[i]
        i += 1
        f, wt = tag >> 3, tag & 7
        if wt == 0:
            v, s = 0, 0
            while True:
                x = b[i]
                i += 1
                v |= (x & 0x7F) << s
                s += 7
                if not x & 0x80:
                    break
            out.append((f, wt, v))
        elif wt == 2:
            ln, s = 0, 0
            while True:
                x = b[i]
                i += 1
                ln |= (x & 0x7F) << s
                s += 7
                if not x & 0x80:
                    break
            out.append((f, wt, b[i:i + ln]))
            i += ln
        else:
            raise NotOrcDecodable(f"protobuf wire type {wt}")
    return out


@dataclasses.dataclass
class StripeInfo:
    offset: int
    index_length: int
    data_length: int
    footer_length: int
    n_rows: int


@dataclasses.dataclass
class OrcTail:
    compression: int  # 0 none, 1 zlib, 2 snappy, 5 zstd
    block_size: int
    stripes: List[StripeInfo]
    kinds: List[int]        # per column id (0 = root struct)
    names: List[str]        # root field names (column ids 1..n)


def read_tail(path: str) -> OrcTail:
    size = os.path.getsize(path)
    with open(path, "rb") as f:
        f.seek(max(0, size - (1 << 14)))
        tail = f.read()
        ps_len = tail[-1]
        ps = _proto_fields(tail[-1 - ps_len:-1])
        pd = {fl: v for fl, _, v in ps}
        footer_len = pd.get(1, 0)
        compression = pd.get(2, 0)
        block_size = pd.get(3, 1 << 18)
        foot_raw = tail[-1 - ps_len - footer_len:-1 - ps_len]
        if len(foot_raw) < footer_len:
            f.seek(size - 1 - ps_len - footer_len)
            foot_raw = f.read(footer_len)
    foot = _decompress_all(compression, foot_raw)
    stripes, kinds, names = [], [], []
    for fl, wt, v in _proto_fields(foot):
        if fl == 3:
            sv = {a: c for a, _, c in _proto_fields(v)}
            stripes.append(StripeInfo(sv.get(1, 0), sv.get(2, 0),
                                      sv.get(3, 0), sv.get(4, 0),
                                      sv.get(5, 0)))
        elif fl == 4:
            tf = _proto_fields(v)
            kinds.append(next((c for a, _, c in tf if a == 1), 0))
            if len(kinds) == 1:
                names = [c.decode() for a, _, c in tf if a == 3]
    return OrcTail(compression, block_size, stripes, kinds, names)


@_parse_boundary
def _decompress_all(compression: int, raw: bytes) -> bytes:
    """Undo ORC's block framing: 3-byte little-endian header per block,
    (length << 1) | is_original."""
    if compression == 0:
        return raw
    out, i = [], 0
    while i + 3 <= len(raw):
        hdr = raw[i] | (raw[i + 1] << 8) | (raw[i + 2] << 16)
        i += 3
        ln, orig = hdr >> 1, hdr & 1
        chunk = raw[i:i + ln]
        i += ln
        if orig:
            out.append(chunk)
        elif compression == 1:  # zlib (raw deflate)
            out.append(zlib.decompress(chunk, wbits=-15))
        elif compression == 2:  # snappy (raw block; leading varint = size)
            usize, s, j = 0, 0, 0
            while True:
                x = chunk[j]
                j += 1
                usize |= (x & 0x7F) << s
                s += 7
                if not x & 0x80:
                    break
            buf = pa.Codec("snappy").decompress(chunk,
                                                decompressed_size=usize)
            out.append(buf.to_pybytes() if hasattr(buf, "to_pybytes")
                       else bytes(buf))
        elif compression == 5:  # zstd
            import zstandard
            out.append(zstandard.ZstdDecompressor().decompress(
                chunk, max_output_size=1 << 26))
        else:
            raise NotOrcDecodable(f"compression kind {compression}")
    return b"".join(out)


# ---------------------------------------------------------------------------
# RLEv2 -> run tables (host header parse, device expansion)
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class _Runs:
    """Run table: kind 0 = linear (base + delta * within), 1 = direct
    (values[vstart + within])."""

    kinds: List[int]
    counts: List[int]
    bases: List[int]
    deltas: List[int]
    vstarts: List[int]
    values: List[int]

    def __init__(self):
        self.kinds, self.counts, self.bases = [], [], []
        self.deltas, self.vstarts, self.values = [], [], []

    def add_linear(self, count, base, delta=0):
        self.kinds.append(0)
        self.counts.append(count)
        self.bases.append(base)
        self.deltas.append(delta)
        self.vstarts.append(0)

    def add_direct(self, vals):
        self.kinds.append(1)
        self.counts.append(len(vals))
        self.bases.append(0)
        self.deltas.append(0)
        self.vstarts.append(len(self.values))
        self.values.extend(int(v) for v in vals)


def _varint(b: bytes, i: int) -> Tuple[int, int]:
    v, s = 0, 0
    while True:
        x = b[i]
        i += 1
        v |= (x & 0x7F) << s
        s += 7
        if not x & 0x80:
            return v, i


def _zigzag(v: int) -> int:
    return (v >> 1) ^ -(v & 1)


def _unpack_be(b: bytes, i: int, count: int, width: int
               ) -> Tuple[np.ndarray, int]:
    """Unpack ``count`` big-endian ``width``-bit values starting at byte
    ``i`` (vectorized via numpy bit arithmetic)."""
    total_bits = count * width
    nbytes = (total_bits + 7) // 8
    raw = np.frombuffer(b, np.uint8, count=nbytes, offset=i)
    bits = np.unpackbits(raw)
    bits = bits[: count * width].reshape(count, width).astype(np.uint64)
    weights = (np.uint64(1) << np.arange(width - 1, -1, -1,
                                         dtype=np.uint64))
    vals = (bits * weights).sum(axis=1)
    return vals, i + nbytes


@_parse_boundary
def parse_rlev2(b: bytes, signed: bool, expected: int) -> _Runs:
    """Parse an RLEv2 byte stream into a run table; values count must
    reach ``expected``."""
    runs = _Runs()
    i, produced = 0, 0
    while produced < expected:
        if i >= len(b):
            raise NotOrcDecodable("rlev2 stream truncated")
        hdr = b[i]
        enc = hdr >> 6
        if enc == 0:  # SHORT_REPEAT
            width = ((hdr >> 3) & 7) + 1
            count = (hdr & 7) + 3
            i += 1
            v = int.from_bytes(b[i:i + width], "big")
            i += width
            if signed:
                v = _zigzag(v)
            runs.add_linear(count, v)
            produced += count
        elif enc == 1:  # DIRECT
            wcode = (hdr >> 1) & 0x1F
            width = _WIDTH_TABLE[wcode]
            count = ((hdr & 1) << 8 | b[i + 1]) + 1
            i += 2
            vals, i = _unpack_be(b, i, count, width)
            vals = vals.astype(np.int64)
            if signed:
                vals = (vals >> 1) ^ -(vals & 1)
            runs.add_direct(vals)
            produced += count
        elif enc == 3:  # DELTA
            wcode = (hdr >> 1) & 0x1F
            width = _WIDTH_TABLE[wcode] if wcode else 0
            count = ((hdr & 1) << 8 | b[i + 1]) + 1
            i += 2
            raw_base, i = _varint(b, i)
            base = _zigzag(raw_base) if signed else raw_base
            raw_db, i = _varint(b, i)
            delta_base = _zigzag(raw_db)
            if width == 0:
                runs.add_linear(count, base, delta_base)
            else:
                # variable deltas: first two values then |count-2| deltas
                # whose sign follows delta_base — materialize host-side.
                deltas, i = _unpack_be(b, i, count - 2, width)
                sign = 1 if delta_base >= 0 else -1
                vals = np.empty(count, np.int64)
                vals[0] = base
                vals[1] = base + delta_base
                np.cumsum(deltas.astype(np.int64) * sign, out=vals[2:],
                          dtype=np.int64)
                vals[2:] += vals[1]
                runs.add_direct(vals)
            produced += count
        else:  # enc == 2, PATCHED_BASE — materialize host-side
            with _STATS_LOCK:
                decode_stats["patched_base_runs"] += 1
            wcode = (hdr >> 1) & 0x1F
            width = _WIDTH_TABLE[wcode]
            count = ((hdr & 1) << 8 | b[i + 1]) + 1
            third, fourth = b[i + 2], b[i + 3]
            bw = ((third >> 5) & 7) + 1          # base bytes
            pw = _WIDTH_TABLE[third & 0x1F]      # patch width
            pgw = ((fourth >> 5) & 7) + 1        # patch gap width (bits)
            pll = fourth & 0x1F                  # patch list length
            i += 4
            base = int.from_bytes(b[i:i + bw], "big")
            i += bw
            msb = 1 << (bw * 8 - 1)
            if base & msb:
                base = -(base & (msb - 1))
            vals, i = _unpack_be(b, i, count, width)
            vals = vals.astype(np.int64)
            # writers pack patch entries with getClosestFixedBits(pgw+pw),
            # not the raw sum (e.g. 25 -> 26)
            pe_width = next((w for w in _WIDTH_TABLE if w >= pgw + pw), 64)
            pcombined, i = _unpack_be(b, i, pll, pe_width)
            gap_pos = 0
            for pc in pcombined:
                gap_pos += int(pc) >> pw
                patch = int(pc) & ((1 << pw) - 1)
                vals[gap_pos] |= patch << width
            runs.add_direct(vals + base)
            produced += count
    if produced != expected:
        raise NotOrcDecodable("rlev2 produced wrong count")
    return runs


@_parse_boundary
def parse_byte_rle_bits(b: bytes, n_rows: int) -> np.ndarray:
    """PRESENT stream: byte-RLE over MSB-first bit-packed bytes ->
    packed uint8 bitmask of n_rows bits."""
    out = bytearray()
    need = (n_rows + 7) // 8
    i = 0
    while len(out) < need and i < len(b):
        ctrl = b[i]
        i += 1
        if ctrl < 128:  # run of ctrl+3 copies
            out.extend(b[i:i + 1] * (ctrl + 3))
            i += 1
        else:  # 256-ctrl literals
            lit = 256 - ctrl
            out.extend(b[i:i + lit])
            i += lit
    if len(out) < need:
        raise NotOrcDecodable("present stream truncated")
    return np.frombuffer(bytes(out[:need]), np.uint8)


# ---------------------------------------------------------------------------
# device expansion
# ---------------------------------------------------------------------------


def _runs_arrays(runs: _Runs, pad: int):
    def arr(xs, fill, dt=np.int64):
        a = np.full(pad, fill, dt)
        a[: len(xs)] = xs
        return jnp.asarray(a)
    vals = np.asarray(runs.values or [0], np.int64)
    vcap = bucket_byte_capacity(max(len(vals), 1), 8)
    vbuf = np.zeros(vcap, np.int64)
    vbuf[: len(vals)] = vals
    return (arr(runs.kinds, 0, np.int32), arr(runs.counts, 0, np.int32),
            arr(runs.bases, 0), arr(runs.deltas, 0),
            arr(runs.vstarts, 0, np.int32), jnp.asarray(vbuf))


def _expand_runs(table, capacity: int) -> jnp.ndarray:
    kinds, counts, bases, deltas, vstarts, values = table
    ends = jnp.cumsum(counts)
    starts = ends - counts
    i = jnp.arange(capacity, dtype=jnp.int32)
    r = jnp.searchsorted(ends, i, side="right")
    r = jnp.clip(r, 0, kinds.shape[0] - 1)
    within = (i - starts[r]).astype(jnp.int64)
    linear = bases[r] + deltas[r] * within
    nv = values.shape[0]
    direct = values[jnp.clip(vstarts[r].astype(jnp.int64) + within, 0,
                             nv - 1)]
    return jnp.where(kinds[r] == 1, direct, linear)


def _expand_present(packed: jnp.ndarray, capacity: int) -> jnp.ndarray:
    i = jnp.arange(capacity, dtype=jnp.int32)
    byte = packed[jnp.clip(i >> 3, 0, packed.shape[0] - 1)]
    return ((byte >> (7 - (i & 7).astype(jnp.uint8))) & 1).astype(jnp.bool_)


def _pad_bits(bits: Optional[np.ndarray], capacity: int) -> jnp.ndarray:
    cap = bucket_byte_capacity(max(capacity // 8 + 1, 8), 8)
    buf = np.full(cap, 0xFF, np.uint8)
    if bits is not None:
        buf[: len(bits)] = bits
    return jnp.asarray(buf)


# ---------------------------------------------------------------------------
# column decode
# ---------------------------------------------------------------------------

_INT_KINDS = {_K_SHORT: T.SHORT, _K_INT: T.INT, _K_LONG: T.LONG,
              _K_DATE: T.DATE}


def _decode_int_column(runs: _Runs, bits, n_rows: int, capacity: int,
                       dtype: T.DataType) -> DeviceColumn:
    pad = bucket_byte_capacity(max(len(runs.kinds), 1), 8)
    table = _runs_arrays(runs, pad)
    packed = _pad_bits(bits, capacity)

    def build():
        def kern(table, packed, n):
            live = jnp.arange(capacity, dtype=jnp.int32) < n
            validity = _expand_present(packed, capacity) & live
            slot = jnp.clip(jnp.cumsum(validity.astype(jnp.int32)) - 1, 0,
                            capacity - 1)
            vals = _expand_runs(table, capacity)
            data = jnp.where(validity, vals[slot], 0)
            return data.astype(dtype.np_dtype), validity
        return kern
    kern = cached_kernel(
        "orc_int_decode",
        (dtype.name, capacity, pad, int(table[5].shape[0]),
         int(packed.shape[0])), build)
    data, validity = kern(table, packed, jnp.asarray(n_rows, jnp.int32))
    return DeviceColumn(data=data, validity=validity, dtype=dtype)


def _decode_float_column(vals: np.ndarray, bits, n_rows: int,
                         capacity: int, dtype: T.DataType) -> DeviceColumn:
    buf = np.zeros(capacity, vals.dtype)
    buf[: len(vals)] = vals
    plain = jnp.asarray(buf)
    packed = _pad_bits(bits, capacity)

    def build():
        def kern(plain, packed, n):
            live = jnp.arange(capacity, dtype=jnp.int32) < n
            validity = _expand_present(packed, capacity) & live
            slot = jnp.clip(jnp.cumsum(validity.astype(jnp.int32)) - 1, 0,
                            capacity - 1)
            data = jnp.where(validity, plain[slot],
                             jnp.zeros((), plain.dtype))
            return data, validity
        return kern
    kern = cached_kernel("orc_float_decode",
                         (dtype.name, capacity, int(packed.shape[0])),
                         build)
    data, validity = kern(plain, packed, jnp.asarray(n_rows, jnp.int32))
    return DeviceColumn(data=data.astype(dtype.np_dtype), validity=validity,
                        dtype=dtype)


def _dict_from_blob(blob: bytes, lengths: np.ndarray
                    ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """(sorted unique payload, offsets, code remap old->sorted).

    Entries are deduped: the dict_sorted contract (data/column.py) needs
    code equality == string equality, and DIRECT_V2 feeds every row's
    value through here (duplicates guaranteed)."""
    offs = np.zeros(len(lengths) + 1, np.int64)
    np.cumsum(lengths, out=offs[1:])
    entries = [blob[offs[k]:offs[k + 1]] for k in range(len(lengths))]
    uniq = sorted(set(entries))
    rank = {e: r for r, e in enumerate(uniq)}
    remap = np.fromiter((rank[e] for e in entries), np.int32,
                        count=len(entries)) if entries else \
        np.zeros(0, np.int32)
    payload = b"".join(uniq)
    soffs = np.zeros(len(uniq) + 1, np.int32)
    np.cumsum([len(e) for e in uniq], out=soffs[1:])
    return (np.frombuffer(payload, np.uint8) if payload else
            np.zeros(0, np.uint8), soffs, remap)


def _string_column_from_codes(codes_dev, validity, payload: np.ndarray,
                              offsets: np.ndarray) -> DeviceColumn:
    max_bytes = bucket_byte_capacity(
        max(int(np.diff(offsets).max()) if len(offsets) > 1 else 1, 1), 8)
    byte_cap = bucket_byte_capacity(max(int(offsets[-1]), 1))
    buf = np.zeros(byte_cap, np.uint8)
    buf[: len(payload)] = payload
    return DeviceColumn(data=jnp.asarray(buf), validity=validity,
                        dtype=T.STRING, offsets=jnp.asarray(offsets),
                        max_bytes=max_bytes, codes=codes_dev,
                        dict_sorted=True)


# ---------------------------------------------------------------------------
# stripe decode
# ---------------------------------------------------------------------------


def decode_stripe(path: str, tail: OrcTail, si: StripeInfo,
                  schema: T.Schema) -> ColumnarBatch:
    with open(path, "rb") as f:
        f.seek(si.offset)
        raw = f.read(si.index_length + si.data_length + si.footer_length)
    sf = _proto_fields(_decompress_all(
        tail.compression,
        raw[si.index_length + si.data_length:]))
    streams, encodings = [], []
    for fl, _, v in sf:
        if fl == 1:
            sv = {a: c for a, _, c in _proto_fields(v)}
            streams.append((sv.get(1, 0), sv.get(2, 0), sv.get(3, 0)))
        elif fl == 2:
            ev = {a: c for a, _, c in _proto_fields(v)}
            encodings.append(ev.get(1, 0))
    # stream payloads laid out in order from the stripe start
    payloads: Dict[Tuple[int, int], bytes] = {}
    pos = 0
    for kind, col, ln in streams:
        payloads[(kind, col)] = raw[pos:pos + ln]
        pos += ln

    def stream(kind, col) -> bytes:
        p = payloads.get((kind, col))
        if p is None:
            return b""
        return _decompress_all(tail.compression, p)

    n_rows = si.n_rows
    capacity = bucket_capacity(max(n_rows, 1))
    name_to_col = {nm: ci + 1 for ci, nm in enumerate(tail.names)}
    cols = []
    for field in schema:
        cid = name_to_col[field.name]
        kind = tail.kinds[cid]
        enc = encodings[cid] if cid < len(encodings) else _E_DIRECT
        present = stream(_S_PRESENT, cid)
        bits = parse_byte_rle_bits(present, n_rows) if present else None
        n_valid = n_rows if bits is None else int(
            np.unpackbits(bits)[:n_rows].sum())
        with span(None, "orc.decode_column"):
            if kind in _INT_KINDS:
                if enc not in (_E_DIRECT_V2,):
                    raise NotOrcDecodable(f"int encoding {enc}")
                runs = parse_rlev2(stream(_S_DATA, cid), True, n_valid)
                cols.append(_decode_int_column(runs, bits, n_rows,
                                               capacity,
                                               _INT_KINDS[kind]))
            elif kind in (_K_FLOAT, _K_DOUBLE):
                dt = np.float32 if kind == _K_FLOAT else np.float64
                vals = np.frombuffer(stream(_S_DATA, cid), dt,
                                     count=n_valid)
                cols.append(_decode_float_column(
                    vals, bits, n_rows, capacity,
                    T.FLOAT if kind == _K_FLOAT else T.DOUBLE))
            elif kind == _K_STRING and enc == _E_DICT_V2:
                dict_blob = stream(_S_DICT, cid)
                # dictionarySize lives in the encoding proto (field 2)
                ev = [dict({a: c for a, _, c in _proto_fields(v)})
                      for fl, _, v in sf if fl == 2]
                dsize = ev[cid].get(2, 0)
                lr = parse_rlev2(stream(_S_LENGTH, cid), False, dsize)
                lengths = _expand_runs_host(lr, dsize)
                payload, soffs, remap = _dict_from_blob(dict_blob, lengths)
                cruns = parse_rlev2(stream(_S_DATA, cid), False, n_valid)
                codes = _decode_int_column(cruns, bits, n_rows, capacity,
                                           T.INT)
                remap_pad = np.zeros(
                    bucket_byte_capacity(max(len(remap), 1), 8), np.int32)
                remap_pad[: len(remap)] = remap
                rdev = jnp.asarray(remap_pad)
                code_vals = rdev[jnp.clip(codes.data.astype(jnp.int32), 0,
                                          rdev.shape[0] - 1)]
                code_vals = jnp.where(codes.validity, code_vals, 0)
                cols.append(_string_column_from_codes(
                    code_vals, codes.validity, payload, soffs))
            elif kind == _K_STRING and enc == _E_DIRECT_V2:
                lr = parse_rlev2(stream(_S_LENGTH, cid), False, n_valid)
                lengths = _expand_runs_host(lr, n_valid)
                blob = stream(_S_DATA, cid)
                payload, soffs, remap = _dict_from_blob(blob, lengths)
                # codes per non-null slot (host: the dictionary build is
                # host-side anyway), scattered to rows on device
                cruns = _Runs()
                cruns.add_direct(remap)
                codes = _decode_int_column(cruns, bits, n_rows, capacity,
                                           T.INT)
                cols.append(_string_column_from_codes(
                    codes.data.astype(jnp.int32), codes.validity, payload,
                    soffs))
            else:
                raise NotOrcDecodable(
                    f"column kind {kind} encoding {enc}")
    return ColumnarBatch(tuple(cols), jnp.asarray(n_rows, jnp.int32),
                         T.Schema(list(schema)))


def _expand_runs_host(runs: _Runs, n: int) -> np.ndarray:
    out = np.empty(n, np.int64)
    pos = 0
    vals = np.asarray(runs.values, np.int64)
    for k, c, b, d, vs in zip(runs.kinds, runs.counts, runs.bases,
                              runs.deltas, runs.vstarts):
        if k == 0:
            out[pos:pos + c] = b + d * np.arange(c, dtype=np.int64)
        else:
            out[pos:pos + c] = vals[vs:vs + c]
        pos += c
    return out


# ---------------------------------------------------------------------------
# scan exec + gating
# ---------------------------------------------------------------------------


def scan_files(paths: List[str]) -> List[str]:
    out = []
    for p in paths:
        if os.path.isdir(p):
            for root, _, files in os.walk(p):
                out.extend(os.path.join(root, fn) for fn in sorted(files)
                           if fn.endswith(".orc"))
        elif p.endswith(".orc"):
            out.append(p)
        else:
            return []
    return sorted(out)


_SUPPORTED_KINDS = set(_INT_KINDS) | {_K_FLOAT, _K_DOUBLE, _K_STRING}


def device_decodable(path: str, schema: T.Schema,
                     tail: Optional[OrcTail] = None) -> bool:
    try:
        tail = tail or read_tail(path)
    except Exception:
        return False
    if tail.compression not in (0, 1, 2, 5):
        return False
    if not tail.kinds or tail.kinds[0] != _K_STRUCT:
        return False
    name_to_col = {nm: ci + 1 for ci, nm in enumerate(tail.names)}
    for f in schema:
        cid = name_to_col.get(f.name)
        if cid is None or cid >= len(tail.kinds):
            return False
        if tail.kinds[cid] not in _SUPPORTED_KINDS:
            return False
    return True


class TpuOrcScanExec:
    """Device ORC scan: one partition per (file, stripe); per-stripe
    fallback to a host pyarrow read keeps out-of-scope stripes working
    (GpuOrcScan.scala:65,211 role)."""

    columnar = True
    children = ()
    children_coalesce_goals = None

    def __init__(self, files: List[str], schema: T.Schema,
                 file_schema: T.Schema, tails: Optional[dict] = None):
        self.files = list(files)
        self._schema = schema
        self._file_schema = file_schema
        self._tails = dict(tails or {})

    @property
    def schema(self):
        return self._schema

    def node_name(self):
        return "TpuOrcScanExec"

    def describe(self):
        from .files import columns_read
        return (f"TpuOrcScan files={len(self.files)} "
                f"{columns_read(self._schema, self._file_schema)}")

    def tree_string(self, indent: int = 0) -> str:
        return "  " * indent + self.describe() + "\n"

    def with_children(self, children):
        assert not children
        return self

    def execute(self, ctx):
        units = []
        for path in self.files:
            tail = self._tails.get(path) or read_tail(path)
            units.extend((path, tail, si) for si in tail.stripes)

        name = self.node_name()

        def read(unit):
            path, tail, si = unit
            from ..memory.retry import Classification, classify
            from ..utils.fault_injection import maybe_inject
            try:
                maybe_inject(ctx, "io.orc.stripe")
                with ctx.registry.timer(name, "opTime",
                                        trace="orc.device_decode_stripe",
                                        owner=getattr(ctx, "trace", None)):
                    batch = decode_stripe(path, tail, si, self._schema)
                ctx.metric(name, "scanColumnChunksDecoded",
                           len(self._schema))
                return batch
            except Exception as e:  # noqa: BLE001 - classify-narrowed
                # parsers translate malformed-input errors to
                # NotOrcDecodable at their boundary (_parse_boundary), and
                # classified device faults (OOM/transient) degrade to the
                # host reader per stripe — the correctness baseline;
                # decoder-logic bugs elsewhere still fail loudly.
                if not isinstance(e, NotOrcDecodable) \
                        and classify(e) == Classification.FATAL:
                    raise
                ctx.metric(name, "stripeHostFallback", 1)
                return self._host_stripe(path, tail, si)

        # Stripes decode ahead on the shared pipeline pool (bounded by
        # decodeThreads/prefetchDepth), yielding in stripe order; with
        # the pipeline off, the serial stream keeps its depth-2 prefetch
        # worker (pre-pipeline behavior).
        from ..exec import pipeline

        def gen():
            for u, b in zip(units, pipeline.ordered_map_iter(
                    read, units, ctx, name)):
                ctx.metric(name, "numOutputBatches", 1)
                ctx.metric(name, "numOutputRows", u[2].n_rows)
                yield b
        if pipeline.parallel_active(ctx):
            return [gen()]
        from ..utils.prefetch import prefetch_iter
        return [prefetch_iter(gen(), ctx=ctx, node=name)]

    def _host_stripe(self, path, tail, si) -> ColumnarBatch:
        import pyarrow.orc as orc
        f = orc.ORCFile(path)
        idx = tail.stripes.index(si)
        rb = f.read_stripe(idx, columns=[f_.name for f_ in self._schema])
        table = pa.Table.from_batches([rb]) if isinstance(
            rb, pa.RecordBatch) else rb
        rb = table.combine_chunks().to_batches()[0] if table.num_rows else \
            pa.RecordBatch.from_arrays(
                [pa.array([], type=fld.type)
                 for fld in T.schema_to_arrow(self._schema)],
                schema=T.schema_to_arrow(self._schema))
        return ColumnarBatch.from_arrow(
            rb.cast(T.schema_to_arrow(self._schema)))
