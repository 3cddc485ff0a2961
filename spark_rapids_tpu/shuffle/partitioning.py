"""Partitioning strategies — the GpuPartitioning family.

Reference: GpuHashPartitioning.scala:141 (cudf murmur3 partition),
GpuRoundRobinPartitioning.scala:98, GpuSinglePartitioning.scala:61,
GpuRangePartitioning.scala:166. Hash partitioning reimplements **Spark's
Murmur3** row hash bit-for-bit (seed 42, per-column chaining, nulls skipped)
so partition placement matches CPU Spark — the same property cudf's
murmur3-partition gives the reference.

The hash kernels are written against an array-namespace parameter so one
implementation serves both the device path (jnp, fused by XLA) and the host
oracle (numpy).
"""

from __future__ import annotations

from typing import List, Sequence

import jax.numpy as jnp
import numpy as np

from .. import types as T
from ..data.column import DeviceColumn
from ..ops.strings_util import char_matrix

_C1 = 0xCC9E2D51
_C2 = 0x1B873593
SPARK_SEED = 42


def _u32(xp, v):
    return xp.asarray(v, dtype=xp.uint32)


def _rotl32(xp, x, r):
    return (x << _u32(xp, r)) | (x >> _u32(xp, 32 - r))


def _mix_k1(xp, k1):
    k1 = k1 * _u32(xp, _C1)
    k1 = _rotl32(xp, k1, 15)
    return k1 * _u32(xp, _C2)


def _mix_h1(xp, h1, k1):
    h1 = h1 ^ k1
    h1 = _rotl32(xp, h1, 13)
    return h1 * _u32(xp, 5) + _u32(xp, 0xE6546B64)


def _fmix(xp, h1, length):
    h1 = h1 ^ _u32(xp, length)
    h1 = h1 ^ (h1 >> _u32(xp, 16))
    h1 = h1 * _u32(xp, 0x85EBCA6B)
    h1 = h1 ^ (h1 >> _u32(xp, 13))
    h1 = h1 * _u32(xp, 0xC2B2AE35)
    return h1 ^ (h1 >> _u32(xp, 16))


def murmur3_int32(xp, values, seed):
    """Spark Murmur3Hash of an int-like 4-byte value."""
    k1 = _mix_k1(xp, values.astype(xp.uint32))
    h1 = _mix_h1(xp, seed.astype(xp.uint32), k1)
    return _fmix(xp, h1, 4)


def murmur3_int64(xp, values, seed):
    v = values.astype(xp.uint64)
    lo = (v & xp.asarray(0xFFFFFFFF, xp.uint64)).astype(xp.uint32)
    hi = (v >> xp.asarray(32, xp.uint64)).astype(xp.uint32)
    h1 = seed.astype(xp.uint32)
    h1 = _mix_h1(xp, h1, _mix_k1(xp, lo))
    h1 = _mix_h1(xp, h1, _mix_k1(xp, hi))
    return _fmix(xp, h1, 8)


def _spark_normalize_float(xp, data):
    """Spark hashes the raw IEEE bits but normalizes NaN to a canonical NaN
    and -0.0 to 0.0."""
    if data.dtype in (xp.float32, np.float32):
        bits = data.view(np.int32) if xp is np else data.view(jnp.int32)
        bits = xp.where(xp.isnan(data), xp.asarray(0x7FC00000, bits.dtype), bits)
        bits = xp.where(data == 0, xp.zeros((), bits.dtype), bits)
        return bits, 32
    bits = data.view(np.int64) if xp is np else data.view(jnp.int64)
    canon = xp.asarray(0x7FF8000000000000, bits.dtype)
    bits = xp.where(xp.isnan(data), canon, bits)
    bits = xp.where(data == 0, xp.zeros((), bits.dtype), bits)
    return bits, 64


def hash_column(xp, data, validity, dtype: T.DataType, seed):
    """One column's contribution: h = murmur3(value, seed); null rows keep
    the incoming seed (Spark skips null columns in row hashes)."""
    if dtype.is_floating:
        bits, width = _spark_normalize_float(xp, data)
        h = murmur3_int32(xp, bits, seed) if width == 32 \
            else murmur3_int64(xp, bits, seed)
    elif dtype in (T.LONG, T.TIMESTAMP):
        h = murmur3_int64(xp, data, seed)
    elif dtype is T.BOOLEAN:
        h = murmur3_int32(xp, data.astype(np.int32 if xp is np else jnp.int32),
                          seed)
    else:  # byte/short/int/date hash as int (Spark widens to int)
        h = murmur3_int32(xp, data.astype(np.int32 if xp is np else jnp.int32),
                          seed)
    return xp.where(validity, h, seed)


def murmur3_bytes_rows(xp, mat, lengths, seed):
    """Spark Murmur3 of UTF-8 byte rows given a [n, W] char matrix (PAD -1
    past end) and per-row byte lengths. Processes 4-byte little-endian blocks
    then the 1-3 byte tail, exactly like Murmur3_x86_32.hashUnsafeBytes."""
    n, w = mat.shape
    h1 = seed.astype(xp.uint32) * xp.ones(n, dtype=xp.uint32)
    blocks = w // 4
    valid_char = mat != -1
    chars = xp.where(valid_char, mat, 0).astype(xp.uint32)
    for b in range(blocks):
        i = b * 4
        k1 = (chars[:, i]
              | (chars[:, i + 1] << _u32(xp, 8))
              | (chars[:, i + 2] << _u32(xp, 16))
              | (chars[:, i + 3] << _u32(xp, 24)))
        full_block = lengths >= (i + 4)
        nh = _mix_h1(xp, h1, _mix_k1(xp, k1))
        h1 = xp.where(full_block, nh, h1)
    # Tail: Spark's hashUnsafeBytes processes trailing bytes one at a time as
    # SIGNED ints through the full mix (Murmur3_x86_32.hashUnsafeBytes).
    signed = xp.where(valid_char, mat, 0).astype(xp.int32)
    signed = xp.where(signed > 127, signed - 256, signed)
    for pos in range(w):
        in_tail = (pos >= (lengths // 4) * 4) & (pos < lengths)
        k1 = _mix_k1(xp, signed[:, pos].astype(xp.uint32))
        nh = _mix_h1(xp, h1, k1)
        h1 = xp.where(in_tail, nh, h1)
    return _fmix_len(xp, h1, lengths)


def _fmix_len(xp, h1, lengths):
    h1 = h1 ^ lengths.astype(xp.uint32)
    h1 = h1 ^ (h1 >> _u32(xp, 16))
    h1 = h1 * _u32(xp, 0x85EBCA6B)
    h1 = h1 ^ (h1 >> _u32(xp, 13))
    h1 = h1 * _u32(xp, 0xC2B2AE35)
    return h1 ^ (h1 >> _u32(xp, 16))


def spark_hash_columns_device(cols: Sequence[DeviceColumn],
                              seed: int = SPARK_SEED) -> jnp.ndarray:
    """Row hash over device columns (int32, Spark-compatible)."""
    n = cols[0].capacity
    h = jnp.full(n, jnp.uint32(seed & 0xFFFFFFFF), dtype=jnp.uint32)
    for c in cols:
        h = _hash_device_column(c, h)
    return h.astype(jnp.int32)


def _hash_device_column(c: DeviceColumn, h: jnp.ndarray) -> jnp.ndarray:
    """Fold one column into the running row hash, Spark semantics: null
    values (and null elements/fields) leave the hash unchanged; arrays and
    structs fold element-by-element / field-by-field
    (Spark HashExpression.computeHash on ArrayType/StructType)."""
    if c.is_struct:
        hh = h
        for kid in c.children:
            hh = _hash_device_column(kid, hh)
        return jnp.where(c.validity, hh, h)
    if c.is_array:
        # Sequential fold over the padded element lanes; masked lanes keep
        # the running hash, exactly like Spark's per-element loop.
        hh = h
        in_len = jnp.arange(c.max_len, dtype=jnp.int32)[None, :] \
            < c.lengths[:, None]
        for j in range(c.max_len):
            live = in_len[:, j] & c.elem_validity[:, j]
            nh = hash_column(jnp, c.data[:, j], live,
                             c.dtype.element_type, hh)
            hh = jnp.where(live, nh, hh)
        return jnp.where(c.validity, hh, h)
    if c.is_string:
        from ..ops.strings_util import lengths as str_lengths
        m = char_matrix(c)
        nh = murmur3_bytes_rows(jnp, m, str_lengths(c), h)
        return jnp.where(c.validity, nh, h)
    return hash_column(jnp, c.data, c.validity, c.dtype, h)


def spark_hash_columns_host(arrays, dtypes: List[T.DataType],
                            seed: int = SPARK_SEED) -> np.ndarray:
    """Same row hash on host numpy (pa.Array inputs)."""
    import pyarrow as pa
    n = len(arrays[0])
    h = np.full(n, np.uint32(seed & 0xFFFFFFFF), dtype=np.uint32)
    old = np.seterr(over="ignore")
    try:
        for arr, dt in zip(arrays, dtypes):
            h = _hash_host_column(arr, dt, h)
    finally:
        np.seterr(**old)
    return h.astype(np.int32)


def _hash_host_column(arr, dt: T.DataType, h: np.ndarray) -> np.ndarray:
    """Host fold of one pyarrow column into the running row hash (same
    semantics as _hash_device_column)."""
    import pyarrow as pa
    n = len(arr)
    validity = np.asarray(arr.is_valid()) if arr.null_count \
        else np.ones(n, dtype=bool)
    if isinstance(dt, T.StructType):
        hh = h
        for i, f in enumerate(dt.fields):
            hh = _hash_host_column(arr.field(i), f.data_type, hh)
        return np.where(validity, hh, h)
    if isinstance(dt, T.ArrayType):
        # Oracle path: per-row element fold in Python.
        et = dt.element_type
        out = h.copy()
        for i, lst in enumerate(arr.to_pylist()):
            if lst is None:
                continue
            hh = out[i: i + 1].copy()
            for v in lst:
                if v is None:
                    continue
                one = pa.array([v], type=T.to_arrow_type(et))
                hh = _hash_host_column(one, et, hh)
            out[i] = hh[0]
        return np.where(validity, out, h)
    if dt is T.STRING:
        nh = _native_hash_strings(arr, validity, h)
        if nh is not None:
            return nh
        lengths = np.zeros(n, dtype=np.int32)
        vals = arr.to_pylist()
        w = max([len(v.encode()) if v else 0 for v in vals] + [4])
        w = ((w + 3) // 4) * 4
        mat = np.full((n, w), -1, dtype=np.int16)
        for i, v in enumerate(vals):
            if v is not None:
                raw = np.frombuffer(v.encode(), dtype=np.uint8)
                lengths[i] = len(raw)
                mat[i, : len(raw)] = raw
        nh = murmur3_bytes_rows(np, mat, lengths, h)
        return np.where(validity, nh, h)
    filled = arr.fill_null(False if dt is T.BOOLEAN else 0) \
        if arr.null_count else arr
    vals = filled.to_numpy(zero_copy_only=False)
    if vals.dtype.kind == "M":
        unit = "D" if dt is T.DATE else "us"
        vals = vals.astype(f"datetime64[{unit}]").view(np.int64)
    vals = vals.astype(dt.np_dtype, copy=False)
    nh = _native_hash_fixed(vals, validity, dt, h)
    if nh is not None:
        return nh
    return hash_column(np, vals, validity, dt, h)


def _native_hash_fixed(vals: np.ndarray, validity: np.ndarray,
                       dt: T.DataType, h: np.ndarray):
    """Fold one fixed-width column via the native kernels (hostkern.cpp);
    None when the native library is unavailable."""
    import ctypes
    from ..native import lib
    L = lib()
    if L is None:
        return None
    if dt.is_floating:
        fn, cast = (L.sr_hash_col_f32, np.float32) if dt is T.FLOAT \
            else (L.sr_hash_col_f64, np.float64)
    elif dt in (T.LONG, T.TIMESTAMP):
        fn, cast = L.sr_hash_col_i64, np.int64
    else:  # bool/byte/short/int/date widen to int (Spark semantics)
        fn, cast = L.sr_hash_col_i32, np.int32
    v = np.ascontiguousarray(vals.astype(cast, copy=False))
    val8 = np.ascontiguousarray(validity, dtype=np.uint8)
    out = np.ascontiguousarray(h, dtype=np.uint32).copy()
    fn(v.ctypes.data_as(ctypes.c_void_p),
       val8.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
       len(v), out.ctypes.data_as(ctypes.POINTER(ctypes.c_uint32)))
    return out


def _native_hash_strings(arr, validity: np.ndarray, h: np.ndarray):
    import ctypes
    import pyarrow as pa
    from ..native import lib
    L = lib()
    if L is None:
        return None
    arr = arr.cast(pa.string())
    bufs = arr.buffers()
    raw_off = np.frombuffer(bufs[1], dtype=np.int32)
    offsets = np.ascontiguousarray(
        raw_off[arr.offset: arr.offset + len(arr) + 1])
    payload = np.frombuffer(bufs[2], dtype=np.uint8) if bufs[2] \
        else np.zeros(0, np.uint8)
    val8 = np.ascontiguousarray(validity, dtype=np.uint8)
    out = np.ascontiguousarray(h, dtype=np.uint32).copy()
    L.sr_hash_col_str(
        offsets.ctypes.data_as(ctypes.c_void_p),
        payload.ctypes.data_as(ctypes.c_void_p),
        val8.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
        len(arr), out.ctypes.data_as(ctypes.POINTER(ctypes.c_uint32)))
    return out


def pmod_partition(hash32, n_parts: int, xp=jnp):
    """partition = pmod(hash, n) like Spark's HashPartitioning."""
    m = hash32.astype(xp.int32) % xp.asarray(n_parts, xp.int32)
    return xp.where(m < 0, m + n_parts, m)
