"""Device-resident columnar vectors — the ``GpuColumnVector`` analog.

The reference wraps cudf device columns in Spark ``ColumnVector`` objects
(reference: ``sql-plugin/src/main/java/.../GpuColumnVector.java:40``). cuDF's
model is eager and dynamically shaped: every kernel allocates an exactly-sized
output. That model is hostile to XLA, which wants static shapes and traced
programs.

The TPU-native model here is different by design:

* A :class:`DeviceColumn` owns a **fixed-capacity** buffer (power-of-two
  bucketed, lane-aligned) plus a validity mask. The number of live rows is
  tracked by the enclosing batch as a *traced* scalar, so data-dependent row
  counts (filters, joins) flow through a compiled program without host syncs
  or recompilation.
* Invariant: rows at index >= n_rows always have ``validity == False`` and
  deterministic (zero) data, so masked reductions never need the row count and
  padding never changes results.
* Strings use the Arrow layout — ``offsets: int32[capacity+1]`` into a
  ``uint8[byte_capacity]`` payload — the same layout cudf uses on GPU, which is
  also the right layout for TPU gather/scatter kernels.

Columns are registered as jax pytrees, so whole batches can be passed straight
through ``jax.jit`` boundaries; the dtype/capacity live in the static treedef,
giving one compiled program per capacity bucket.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import pyarrow as pa

from .. import types as T

#: Lane width of the VPU — the minimum sensible capacity granularity.
#: Canonical definition (and the bucket policy itself) live in
#: compile/ladder.py; re-exported here because every exec imports them
#: from this module since the seed.
from ..compile.ladder import (LANE, bucket_byte_capacity,  # noqa: E402,F401
                              bucket_capacity)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class DeviceColumn:
    """One column of one device batch.

    For fixed-width types, ``data`` has shape ``[capacity]``. Strings come
    in two layouts:

    * **flat**: ``data`` is the ``uint8`` byte payload, ``offsets`` is
      ``int32[capacity+1]`` (Arrow layout); offsets past the live row count
      clamp to the last valid offset.
    * **dictionary-encoded** (``codes is not None``): ``codes`` is
      ``int32[capacity]`` indexing a small dictionary whose entries live in
      ``data``/``offsets`` (``int32[n_dict+1]``). This is the TPU-native
      string representation: row rearrangement (filters, sorts, joins,
      shuffles) moves ONE int32 lane instead of a char matrix, and when
      ``dict_sorted`` (entries unique + bytewise ascending — the upload
      default) code ORDER and EQUALITY coincide with string order and
      equality, so sorts and group-bys use codes directly. cudf gets the
      same wins from its dictionary category type; here it also keeps XLA
      programs narrow.
    """

    data: jax.Array
    validity: jax.Array  # bool[capacity]
    dtype: T.DataType
    offsets: Optional[jax.Array] = None  # int32 offsets (see class doc)
    #: Static upper bound on any single string's byte length (strings only).
    #: Host-known at upload; device string kernels use it to bound the padded
    #: char-matrix width. Propagates through string ops (substr keeps it,
    #: concat sums it).
    max_bytes: int = 0
    #: int32[capacity] dictionary codes (dict-encoded strings only).
    codes: Optional[jax.Array] = None
    #: True when the dictionary is unique + sorted ascending (static).
    dict_sorted: bool = False
    #: ARRAY columns (padded-ragged layout, see types.ArrayType): ``data``
    #: is ``[capacity, max_len]`` element values, ``elem_validity`` the
    #: matching element mask, ``lengths`` int32[capacity] live lengths.
    elem_validity: Optional[jax.Array] = None
    lengths: Optional[jax.Array] = None
    #: STRUCT columns (column-shredded, see types.StructType): one child
    #: DeviceColumn per field; ``data`` is unused, ``validity`` is the
    #: struct-level null lane.
    children: tuple = ()

    # -- pytree protocol ----------------------------------------------------
    def tree_flatten(self):
        if self.children:
            return ((self.validity, self.children), (self.dtype, 5, 0))
        if self.lengths is not None:
            return ((self.data, self.validity, self.elem_validity,
                     self.lengths), (self.dtype, 4, 0))
        if self.offsets is None:
            return (self.data, self.validity), (self.dtype, 0, 0)
        if self.codes is None:
            return ((self.data, self.validity, self.offsets),
                    (self.dtype, 1, self.max_bytes))
        return ((self.data, self.validity, self.offsets, self.codes),
                (self.dtype, 3 if self.dict_sorted else 2, self.max_bytes))

    @classmethod
    def tree_unflatten(cls, aux, children):
        dtype, kind, max_bytes = aux
        if kind == 5:
            validity, kids = children
            return cls(data=None, validity=validity, dtype=dtype,
                       children=tuple(kids))
        if kind == 4:
            data, validity, elem_validity, lengths = children
            return cls(data=data, validity=validity, dtype=dtype,
                       elem_validity=elem_validity, lengths=lengths)
        if kind == 0:
            data, validity = children
            return cls(data=data, validity=validity, dtype=dtype)
        if kind == 1:
            data, validity, offsets = children
            return cls(data=data, validity=validity, dtype=dtype,
                       offsets=offsets, max_bytes=max_bytes)
        data, validity, offsets, codes = children
        return cls(data=data, validity=validity, dtype=dtype, offsets=offsets,
                   max_bytes=max_bytes, codes=codes, dict_sorted=kind == 3)

    # -- properties ---------------------------------------------------------
    @property
    def is_string(self) -> bool:
        return self.offsets is not None

    @property
    def is_dict(self) -> bool:
        return self.codes is not None

    @property
    def is_array(self) -> bool:
        return self.lengths is not None

    @property
    def is_struct(self) -> bool:
        return bool(self.children)

    @property
    def is_complex(self) -> bool:
        return self.is_array or self.is_struct

    @property
    def max_len(self) -> int:
        assert self.is_array
        return int(self.data.shape[1])

    @property
    def capacity(self) -> int:
        if self.children:
            return int(self.validity.shape[0])
        if self.codes is not None:
            return int(self.codes.shape[0])
        if self.is_string:
            return int(self.offsets.shape[0]) - 1
        return int(self.data.shape[0])

    @property
    def dict_size(self) -> int:
        assert self.is_dict
        return int(self.offsets.shape[0]) - 1

    @property
    def byte_capacity(self) -> int:
        assert self.is_string
        return int(self.data.shape[0])

    @property
    def size_bytes(self) -> int:
        total = self.validity.size
        if self.data is not None:
            total += self.data.size * self.data.dtype.itemsize
        if self.offsets is not None:
            total += self.offsets.size * 4
        if self.codes is not None:
            total += self.codes.size * 4
        if self.elem_validity is not None:
            total += self.elem_validity.size
        if self.lengths is not None:
            total += self.lengths.size * 4
        for c in self.children:
            total += c.size_bytes
        return total

    # -- constructors -------------------------------------------------------
    @staticmethod
    def from_numpy(values: np.ndarray, validity: Optional[np.ndarray],
                   dtype: T.DataType, capacity: int) -> "DeviceColumn":
        """Upload a host fixed-width array, padding to ``capacity``."""
        n = len(values)
        assert n <= capacity, (n, capacity)
        np_dt = dtype.np_dtype
        buf = np.zeros(capacity, dtype=np_dt)
        buf[:n] = values.astype(np_dt, copy=False)
        mask = np.zeros(capacity, dtype=np.bool_)
        if validity is None:
            mask[:n] = True
        else:
            mask[:n] = validity
            buf[:n] = np.where(validity, buf[:n], np.zeros((), np_dt))
        return DeviceColumn(jnp.asarray(buf), jnp.asarray(mask), dtype)

    @staticmethod
    def string_from_host(offsets: np.ndarray, data: np.ndarray,
                         validity: Optional[np.ndarray], capacity: int,
                         byte_capacity: Optional[int] = None) -> "DeviceColumn":
        """Upload Arrow string buffers, padding offsets by clamping to the end."""
        n = len(offsets) - 1
        assert n <= capacity
        nbytes = int(offsets[-1])
        byte_capacity = byte_capacity or bucket_byte_capacity(max(nbytes, 1))
        off = np.full(capacity + 1, nbytes, dtype=np.int32)
        off[: n + 1] = offsets.astype(np.int32, copy=False)
        payload = np.zeros(byte_capacity, dtype=np.uint8)
        payload[:nbytes] = data[:nbytes]
        mask = np.zeros(capacity, dtype=np.bool_)
        if validity is None:
            mask[:n] = True
        else:
            mask[:n] = validity
        item_lens = np.diff(offsets)
        max_bytes = bucket_byte_capacity(int(item_lens.max()) if n else 1, 8)
        return DeviceColumn(jnp.asarray(payload), jnp.asarray(mask), T.STRING,
                            offsets=jnp.asarray(off), max_bytes=max_bytes)

    @staticmethod
    def from_arrow(arr: pa.Array, capacity: int) -> "DeviceColumn":
        """Upload a pyarrow array (the host interchange format, like
        JCudfSerialization host buffers in the reference). Conversions are
        memoized on the immutable arrow buffers (see data/upload_cache.py)
        so re-uploading data the device has already seen skips both the
        host-side prep and the transfer."""
        from . import upload_cache
        arr = arr.combine_chunks() if isinstance(arr, pa.ChunkedArray) else arr
        hit = upload_cache.lookup(arr, capacity)
        if hit is not None:
            return hit
        col = DeviceColumn._from_arrow_uncached(arr, capacity)
        upload_cache.insert(arr, capacity, col)
        return col

    @staticmethod
    def _from_arrow_uncached(arr: pa.Array, capacity: int) -> "DeviceColumn":
        dtype = T.from_arrow_type(arr.type)
        if isinstance(dtype, T.ArrayType):
            return DeviceColumn.array_from_arrow(arr, dtype, capacity)
        if isinstance(dtype, T.StructType):
            validity = _arrow_validity(arr)
            mask = np.zeros(capacity, dtype=np.bool_)
            mask[: len(arr)] = True if validity is None else validity
            kids = tuple(DeviceColumn.from_arrow(arr.field(i), capacity)
                         for i in range(arr.type.num_fields))
            return DeviceColumn(data=None, validity=jnp.asarray(mask),
                                dtype=dtype, children=kids)
        if dtype is T.STRING:
            return DeviceColumn.dict_string_from_arrow(arr, capacity)
        if dtype is T.NULL:
            return DeviceColumn.from_numpy(
                np.zeros(len(arr), dtype=np.int8),
                np.zeros(len(arr), dtype=np.bool_), T.NULL, capacity)
        values, validity = _fixed_np_from_arrow(arr, dtype)
        return DeviceColumn.from_numpy(values, validity, dtype, capacity)

    @staticmethod
    def array_from_arrow(arr: pa.Array, dtype: "T.ArrayType",
                         capacity: int) -> "DeviceColumn":
        """Upload a pyarrow list array in the padded-ragged device layout:
        ``[capacity, max_len]`` element matrix + element mask + length lane
        (see types.ArrayType). max_len buckets to a power of two so jit
        programs are shared across close array sizes."""
        if pa.types.is_large_list(arr.type):
            arr = arr.cast(pa.list_(arr.type.value_type))
        n = len(arr)
        validity = _arrow_validity(arr)
        offs = np.asarray(arr.offsets.to_numpy(zero_copy_only=False),
                          dtype=np.int64)
        lens = np.diff(offs)
        if validity is not None:
            lens = np.where(validity, lens, 0)
        max_len = _pow2(int(lens.max()) if n and lens.size else 1)
        child_vals, child_valid = _fixed_np_from_arrow(
            arr.values, dtype.element_type)
        if child_valid is None:
            child_valid = np.ones(len(child_vals), dtype=np.bool_)
        # Pad the flat child by one zero slot so out-of-range gathers are safe.
        child_vals = np.concatenate(
            [child_vals, np.zeros(1, child_vals.dtype)])
        child_valid = np.concatenate([child_valid, np.zeros(1, np.bool_)])
        j = np.arange(max_len, dtype=np.int64)[None, :]
        idx = offs[:n, None] + j                     # [n, max_len]
        in_row = j < lens[:, None]
        idx = np.where(in_row, idx, len(child_vals) - 1)
        data = np.zeros((capacity, max_len), dtype=child_vals.dtype)
        emask = np.zeros((capacity, max_len), dtype=np.bool_)
        data[:n] = np.where(in_row, child_vals[idx],
                            np.zeros((), child_vals.dtype))
        emask[:n] = in_row & child_valid[idx]
        data[:n] = np.where(emask[:n], data[:n],
                            np.zeros((), child_vals.dtype))
        lengths = np.zeros(capacity, dtype=np.int32)
        lengths[:n] = lens.astype(np.int32)
        mask = np.zeros(capacity, dtype=np.bool_)
        mask[:n] = True if validity is None else validity
        return DeviceColumn(
            data=jnp.asarray(data), validity=jnp.asarray(mask), dtype=dtype,
            elem_validity=jnp.asarray(emask), lengths=jnp.asarray(lengths))

    @staticmethod
    def dict_string_from_arrow(arr: pa.Array, capacity: int
                               ) -> "DeviceColumn":
        """Upload a string array dictionary-encoded: codes[capacity] into a
        SORTED unique dictionary, so code order/equality match string
        order/equality on device."""
        import pyarrow.compute as pc
        arr = arr.cast(pa.string())
        validity = _arrow_validity(arr)
        d = pc.dictionary_encode(arr)
        entries = d.dictionary  # unique, appearance order
        codes = d.indices.fill_null(0).to_numpy(zero_copy_only=False) \
            .astype(np.int32)
        vals = entries.to_pylist()
        order = np.argsort(np.asarray(
            [v.encode() for v in vals], dtype=object), kind="stable") \
            if vals else np.zeros(0, np.int64)
        rank = np.empty(len(vals), dtype=np.int32)
        rank[order] = np.arange(len(vals), dtype=np.int32)
        codes = rank[codes] if len(vals) else codes
        sorted_vals = [vals[i] for i in order]
        raw = [v.encode() for v in sorted_vals] or [b""]
        n_dict = len(raw)
        lens = np.asarray([len(b) for b in raw], dtype=np.int32)
        offsets = np.concatenate([[0], np.cumsum(lens)]).astype(np.int32)
        payload = np.frombuffer(b"".join(raw), dtype=np.uint8) \
            if offsets[-1] else np.zeros(0, np.uint8)
        byte_cap = bucket_byte_capacity(max(int(offsets[-1]), 1))
        buf = np.zeros(byte_cap, np.uint8)
        buf[: offsets[-1]] = payload
        code_buf = np.zeros(capacity, np.int32)
        code_buf[: len(codes)] = codes
        mask = np.zeros(capacity, np.bool_)
        if validity is None:
            mask[: len(arr)] = True
        else:
            mask[: len(arr)] = validity
            code_buf[: len(codes)] = np.where(validity, codes, 0)
        max_bytes = bucket_byte_capacity(int(lens.max()) if n_dict else 1, 8)
        return DeviceColumn(
            data=jnp.asarray(buf), validity=jnp.asarray(mask),
            dtype=T.STRING, offsets=jnp.asarray(offsets),
            max_bytes=max_bytes, codes=jnp.asarray(code_buf),
            dict_sorted=True)

    def head(self, cap: int) -> "DeviceColumn":
        """Front-slice to a smaller capacity (rows past n_rows are dead by
        invariant, so a plain slice is sufficient)."""
        if self.is_struct:
            return DeviceColumn(
                data=None, validity=self.validity[:cap], dtype=self.dtype,
                children=tuple(c.head(cap) for c in self.children))
        if self.is_array:
            return DeviceColumn(
                data=self.data[:cap], validity=self.validity[:cap],
                dtype=self.dtype, elem_validity=self.elem_validity[:cap],
                lengths=self.lengths[:cap])
        if self.is_dict:
            return self.replace_rows(self.validity[:cap],
                                     codes=self.codes[:cap])
        if self.is_string:
            return DeviceColumn(self.data, self.validity[:cap], self.dtype,
                                self.offsets[: cap + 1], self.max_bytes)
        return DeviceColumn(self.data[:cap], self.validity[:cap], self.dtype)

    def grow(self, cap: int) -> "DeviceColumn":
        """Pad to a LARGER capacity with dead rows — the inverse of
        :meth:`head`. Padding preserves the core invariant (rows at
        index >= n_rows have validity False and zero data; flat-string
        offsets clamp to the end), so growing a batch never changes
        results. The shape-polymorphic fused path (exec/fusion.py) uses
        this to canonicalize boundary inputs onto coarse capacity tiers.
        Traceable: safe inside jit."""
        old = self.capacity
        if cap == old:
            return self
        assert cap > old, (cap, old)
        pad = cap - old
        validity = jnp.pad(self.validity, (0, pad))
        if self.is_struct:
            return DeviceColumn(
                data=None, validity=validity, dtype=self.dtype,
                children=tuple(c.grow(cap) for c in self.children))
        if self.is_array:
            return DeviceColumn(
                data=jnp.pad(self.data, ((0, pad), (0, 0))),
                validity=validity, dtype=self.dtype,
                elem_validity=jnp.pad(self.elem_validity, ((0, pad), (0, 0))),
                lengths=jnp.pad(self.lengths, (0, pad)))
        if self.is_dict:
            return self.replace_rows(validity,
                                     codes=jnp.pad(self.codes, (0, pad)))
        if self.is_string:
            return DeviceColumn(self.data, validity, self.dtype,
                                jnp.pad(self.offsets, (0, pad), mode="edge"),
                                self.max_bytes)
        return DeviceColumn(jnp.pad(self.data, (0, pad)), validity,
                            self.dtype)

    def replace_rows(self, validity, data=None, codes=None) -> "DeviceColumn":
        """Same column with row-level arrays swapped (dict buffers kept)."""
        return DeviceColumn(
            data=self.data if data is None else data,
            validity=validity, dtype=self.dtype, offsets=self.offsets,
            max_bytes=self.max_bytes,
            codes=self.codes if codes is None else codes,
            dict_sorted=self.dict_sorted)

    # -- download -----------------------------------------------------------
    def device_buffers(self) -> tuple:
        """The device arrays to download for host reassembly (batch these
        through one ``jax.device_get``: each blocking read is a round trip).
        Struct columns nest their children's buffers (device_get treats the
        whole thing as one pytree)."""
        if self.is_struct:
            return (self.validity,
                    tuple(c.device_buffers() for c in self.children))
        if self.is_array:
            return (self.data, self.validity, self.elem_validity,
                    self.lengths)
        if self.is_dict:
            return (self.data, self.validity, self.offsets, self.codes)
        if self.is_string:
            return (self.data, self.validity, self.offsets)
        return (self.data, self.validity)

    def arrow_from_host(self, bufs: tuple, n_rows: int) -> pa.Array:
        """Reassemble a pyarrow array from downloaded buffers (see
        :meth:`device_buffers`). Zero-copy: the device layout IS the Arrow
        layout (offsets + bytes, values + validity); no per-row Python."""
        if self.dtype is T.NULL:
            return pa.nulls(n_rows)
        if self.is_struct:
            validity = np.ascontiguousarray(bufs[0][:n_rows])
            all_valid = bool(validity.all())
            mask_buf = None if all_valid else \
                pa.py_buffer(np.packbits(validity, bitorder="little"))
            kids = [c.arrow_from_host(b, n_rows)
                    for c, b in zip(self.children, bufs[1])]
            return pa.Array.from_buffers(
                T.to_arrow_type(self.dtype), n_rows, [mask_buf],
                0 if all_valid else int(n_rows - validity.sum()),
                children=kids)
        if self.is_array:
            data, validity, emask, lengths = bufs
            validity = np.ascontiguousarray(validity[:n_rows])
            all_valid = bool(validity.all())
            mask_buf = None if all_valid else \
                pa.py_buffer(np.packbits(validity, bitorder="little"))
            lens = np.where(validity, lengths[:n_rows], 0).astype(np.int64)
            offsets = np.concatenate([[0], np.cumsum(lens)]).astype(np.int32)
            keep = np.arange(data.shape[1])[None, :] < lens[:, None]
            flat_vals = np.ascontiguousarray(data[:n_rows][keep])
            flat_valid = np.ascontiguousarray(emask[:n_rows][keep])
            et = self.dtype.element_type
            child = _np_values_to_arrow(flat_vals, flat_valid, et)
            return pa.Array.from_buffers(
                T.to_arrow_type(self.dtype), n_rows,
                [mask_buf, pa.py_buffer(offsets)],
                0 if all_valid else int(n_rows - validity.sum()),
                children=[child])
        validity = np.ascontiguousarray(bufs[1][:n_rows])
        all_valid = bool(validity.all())
        null_count = 0 if all_valid else int(n_rows - validity.sum())
        mask_buf = None if all_valid else \
            pa.py_buffer(np.packbits(validity, bitorder="little"))
        if self.is_dict:
            payload, _, offsets, codes = bufs
            n_dict = len(offsets) - 1
            entries = pa.StringArray.from_buffers(
                n_dict, pa.py_buffer(np.ascontiguousarray(offsets)),
                pa.py_buffer(np.ascontiguousarray(
                    payload[: offsets[-1]])), None, 0)
            idx = pa.Array.from_buffers(
                pa.int32(), n_rows,
                [mask_buf, pa.py_buffer(np.ascontiguousarray(
                    np.clip(codes[:n_rows], 0, max(n_dict - 1, 0))))],
                null_count)
            return pa.DictionaryArray.from_arrays(idx, entries) \
                .cast(pa.string())
        if self.is_string:
            offsets = np.ascontiguousarray(bufs[2][: n_rows + 1])
            payload = np.ascontiguousarray(bufs[0])
            return pa.StringArray.from_buffers(
                n_rows, pa.py_buffer(offsets), pa.py_buffer(payload),
                mask_buf, null_count)
        values = np.ascontiguousarray(bufs[0][:n_rows])
        arrow_type = T.to_arrow_type(self.dtype)
        if self.dtype is T.BOOLEAN:
            values_buf = pa.py_buffer(np.packbits(values, bitorder="little"))
        else:
            values_buf = pa.py_buffer(values)
        return pa.Array.from_buffers(
            arrow_type, n_rows, [mask_buf, values_buf], null_count)

    def to_arrow(self, n_rows: int) -> pa.Array:
        """Download the first ``n_rows`` live rows as a pyarrow array."""
        return self.arrow_from_host(
            jax.device_get(self.device_buffers()), n_rows)


def _arrow_validity(arr: pa.Array) -> Optional[np.ndarray]:
    if arr.null_count == 0:
        return None
    return np.asarray(arr.is_valid())


def _pow2(n: int, lo: int = 1) -> int:
    cap = max(lo, 1)
    while cap < n:
        cap <<= 1
    return cap


def _np_values_to_arrow(values: np.ndarray, validity: Optional[np.ndarray],
                        dtype: T.DataType) -> pa.Array:
    """Fixed-width numpy values (+ optional bool validity) -> arrow array."""
    n = len(values)
    if validity is None or bool(np.asarray(validity).all()):
        mask_buf, null_count = None, 0
    else:
        mask_buf = pa.py_buffer(np.packbits(validity, bitorder="little"))
        null_count = int(n - validity.sum())
    if dtype is T.BOOLEAN:
        values_buf = pa.py_buffer(np.packbits(values, bitorder="little"))
    else:
        values_buf = pa.py_buffer(np.ascontiguousarray(values))
    return pa.Array.from_buffers(
        T.to_arrow_type(dtype), n, [mask_buf, values_buf], null_count)


def _fixed_np_from_arrow(arr: pa.Array, dtype: T.DataType):
    """(values, validity) numpy pair for a fixed-width arrow array, nulls
    zero-filled (the null-data-is-zero invariant)."""
    if dtype is T.TIMESTAMP:
        arr = arr.cast(pa.timestamp("us"))
    validity = _arrow_validity(arr)
    filled = arr.fill_null(False if dtype is T.BOOLEAN else 0) \
        if arr.null_count else arr
    values = filled.to_numpy(zero_copy_only=False)
    if values.dtype.kind == "M":  # datetime64 from date32/timestamp
        unit = "D" if dtype is T.DATE else "us"
        values = values.astype(f"datetime64[{unit}]").view(np.int64)
    return values.astype(dtype.np_dtype, copy=False), validity


def null_column(dtype: T.DataType, capacity: int) -> DeviceColumn:
    """An all-null column of the given type (used for outer-join padding)."""
    if isinstance(dtype, T.ArrayType):
        return DeviceColumn(
            data=jnp.zeros((capacity, 1), dtype=dtype.element_type.np_dtype),
            validity=jnp.zeros(capacity, dtype=jnp.bool_), dtype=dtype,
            elem_validity=jnp.zeros((capacity, 1), dtype=jnp.bool_),
            lengths=jnp.zeros(capacity, dtype=jnp.int32))
    if isinstance(dtype, T.StructType):
        return DeviceColumn(
            data=None, validity=jnp.zeros(capacity, dtype=jnp.bool_),
            dtype=dtype,
            children=tuple(null_column(f.data_type, capacity)
                           for f in dtype.fields))
    if dtype is T.STRING:
        # Dict-encoded: one empty dictionary entry, all codes 0, all null.
        return DeviceColumn(
            data=jnp.zeros(8, dtype=jnp.uint8),
            validity=jnp.zeros(capacity, dtype=jnp.bool_),
            dtype=T.STRING,
            offsets=jnp.zeros(2, dtype=jnp.int32),
            max_bytes=8,
            codes=jnp.zeros(capacity, dtype=jnp.int32),
            dict_sorted=True)
    return DeviceColumn(
        data=jnp.zeros(capacity, dtype=dtype.np_dtype),
        validity=jnp.zeros(capacity, dtype=jnp.bool_),
        dtype=dtype)


def scalar_column(value, dtype: T.DataType, capacity: int,
                  live) -> DeviceColumn:
    """Broadcast a literal into a column (GpuLiteral expansion,
    reference literals.scala:128). ``live`` is the batch's row MASK —
    lazy-filtered batches have scattered live rows, so a prefix
    (iota < n_rows) would mark the wrong lanes valid."""
    import jax.numpy as _jnp
    if value is None:
        return null_column(dtype, capacity)
    live = _jnp.asarray(live)
    if dtype is T.STRING:
        # Dict-encoded: ONE dictionary entry, every live row points at it —
        # O(1) payload instead of a capacity-wide tiled buffer.
        raw = np.frombuffer(str(value).encode("utf-8"), dtype=np.uint8)
        ln = len(raw)
        byte_cap = bucket_byte_capacity(max(ln, 1), 8)
        payload = np.zeros(byte_cap, dtype=np.uint8)
        payload[:ln] = raw
        valid = live
        return DeviceColumn(
            data=jnp.asarray(payload),
            validity=valid,
            dtype=T.STRING,
            offsets=jnp.asarray(np.asarray([0, ln], np.int32)),
            max_bytes=bucket_byte_capacity(max(ln, 1), 8),
            codes=jnp.zeros(capacity, dtype=jnp.int32),
            dict_sorted=True)
    valid = live
    data = jnp.where(valid, jnp.asarray(value, dtype=dtype.np_dtype), 0)
    return DeviceColumn(data=data.astype(dtype.np_dtype), validity=valid, dtype=dtype)
