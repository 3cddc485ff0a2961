"""Device string primitives: the padded char-matrix trick.

Variable-width data in a vector ISA is the classic TPU-hostile case (SURVEY.md
§7 "Strings on TPU"). The kernel strategy: materialize, inside the traced
program, a ``[capacity, W]`` int16 character matrix from the Arrow
offsets+payload layout, where ``W`` is the column's static ``max_bytes`` bound
and positions past each string's end hold ``-1`` (sorts before every real
byte). Gathers of this shape vectorize cleanly on the VPU, and XLA fuses the
downstream compare/reduce.

cudf solves the same problems with specialized CUDA kernels over the raw
offsets (reference relies on libcudf's strings support via the
``ai.rapids.cudf`` JNI, SURVEY.md §2.10); the char-matrix is the XLA-native
equivalent for bounded-width columns.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ..data.column import DeviceColumn

#: Character value used for "past end of string" — sorts before every byte.
PAD = -1


def char_matrix(col: DeviceColumn, width: int = None) -> jnp.ndarray:
    """[capacity, W] int16; row i holds string i's bytes, PAD past its end.

    Dictionary-encoded columns build the small [n_dict, W] matrix once and
    gather rows by code — O(dict) char work instead of O(capacity)."""
    assert col.is_string
    w = width or max(col.max_bytes, 1)
    if col.is_dict:
        dm = _matrix_from_offsets(col.data, col.offsets, w)
        safe = jnp.clip(col.codes, 0, dm.shape[0] - 1)
        return dm[safe]
    with jax.named_scope("char_matrix"):
        return _matrix_from_offsets(col.data, col.offsets, w)


def _matrix_from_offsets(payload: jnp.ndarray, offsets: jnp.ndarray,
                         w: int) -> jnp.ndarray:
    starts = offsets[:-1]
    ends = offsets[1:]
    pos = starts[:, None] + jnp.arange(w, dtype=jnp.int32)[None, :]
    in_range = pos < ends[:, None]
    byte_cap = payload.shape[0]
    chars = payload[jnp.clip(pos, 0, byte_cap - 1)].astype(jnp.int16)
    return jnp.where(in_range, chars, PAD)


def map_string_column(col: DeviceColumn, fn) -> DeviceColumn:
    """Apply a string->string transform ``fn(flat_col) -> flat_col``.

    Dictionary-encoded inputs transform their (small) DICTIONARY once and
    keep the codes — a 1M-row replace/pad/initcap costs O(dict). The
    result dictionary loses the sorted/unique property (fn may collide or
    reorder entries), so downstream falls back to char comparisons."""
    import jax.numpy as _jnp
    if col.is_dict:
        dcol = DeviceColumn(
            data=col.data,
            validity=_jnp.ones(col.dict_size, _jnp.bool_),
            dtype=col.dtype, offsets=col.offsets, max_bytes=col.max_bytes)
        out = fn(dcol)
        return DeviceColumn(
            data=out.data, validity=col.validity, dtype=col.dtype,
            offsets=out.offsets, max_bytes=out.max_bytes,
            codes=col.codes, dict_sorted=False)
    return fn(col)


def lengths(col: DeviceColumn) -> jnp.ndarray:
    """Byte length per row, int32[capacity]."""
    per = col.offsets[1:] - col.offsets[:-1]
    if col.is_dict:
        return per[jnp.clip(col.codes, 0, per.shape[0] - 1)]
    return per


def lift_dict(col: DeviceColumn, fn, width: int = None) -> jnp.ndarray:
    """Apply ``fn(char_matrix, byte_lengths) -> per-row values`` through the
    dictionary: dict-encoded columns evaluate fn once per ENTRY and gather
    by code — O(dict * W) char work instead of O(capacity * W), the same
    win cudf's category type gives the reference's string predicates."""
    w = width or max(col.max_bytes, 1)
    if col.is_dict:
        dm = _matrix_from_offsets(col.data, col.offsets, w)
        dlen = (col.offsets[1:] - col.offsets[:-1]).astype(jnp.int32)
        vals = fn(dm, dlen)
        return vals[jnp.clip(col.codes, 0, dm.shape[0] - 1)]
    return fn(char_matrix(col, w), lengths(col))


def device_string_compare(op: str, l: DeviceColumn, r: DeviceColumn) -> jnp.ndarray:
    """Lexicographic byte comparison of two string columns.

    ``op`` uses pyarrow.compute naming so predicate classes can share it:
    equal/not_equal/less/less_equal/greater/greater_equal.

    Two dictionary-encoded inputs with a small entry-pair product compare
    per (entry, entry) PAIR and gather by codes — the common literal
    comparison (a 1-entry dictionary) costs O(dict * W + capacity)."""
    w = max(max(l.max_bytes, r.max_bytes), 1)
    if l.is_dict and r.is_dict \
            and l.dict_size * r.dict_size <= (1 << 16):
        lm = _matrix_from_offsets(l.data, l.offsets, w)  # [n1, w]
        rm = _matrix_from_offsets(r.data, r.offsets, w)  # [n2, w]
        le, re_ = lm[:, None, :], rm[None, :, :]
        if op == "equal":
            mat = jnp.all(le == re_, axis=2)
        elif op == "not_equal":
            mat = jnp.any(le != re_, axis=2)
        else:
            diff = le != re_
            any_diff = jnp.any(diff, axis=2)
            first = jnp.argmax(diff, axis=2)
            lv = jnp.take_along_axis(lm[:, None, :].repeat(rm.shape[0], 1),
                                     first[:, :, None], axis=2)[:, :, 0]
            rv = jnp.take_along_axis(rm[None, :, :].repeat(lm.shape[0], 0),
                                     first[:, :, None], axis=2)[:, :, 0]
            cmp = jnp.where(any_diff,
                            jnp.sign(lv - rv).astype(jnp.int32), 0)
            mat = {"less": cmp < 0, "less_equal": cmp <= 0,
                   "greater": cmp > 0, "greater_equal": cmp >= 0}[op]
        li = jnp.clip(l.codes, 0, lm.shape[0] - 1)
        ri = jnp.clip(r.codes, 0, rm.shape[0] - 1)
        return mat[li, ri]
    lm = char_matrix(l, w)
    rm = char_matrix(r, w)
    if op == "equal":
        return jnp.all(lm == rm, axis=1)
    if op == "not_equal":
        return jnp.any(lm != rm, axis=1)
    cmp = _lex_cmp(lm, rm)
    if op == "less":
        return cmp < 0
    if op == "less_equal":
        return cmp <= 0
    if op == "greater":
        return cmp > 0
    if op == "greater_equal":
        return cmp >= 0
    raise ValueError(op)


def _lex_cmp(lm: jnp.ndarray, rm: jnp.ndarray) -> jnp.ndarray:
    """-1/0/+1 per row comparing char matrices; PAD (-1) makes shorter-prefix
    strings compare less, matching byte-wise UTF-8 ordering."""
    diff = lm != rm
    any_diff = jnp.any(diff, axis=1)
    first = jnp.argmax(diff, axis=1)
    rows = jnp.arange(lm.shape[0])
    lv = lm[rows, first]
    rv = rm[rows, first]
    sign = jnp.sign(lv - rv).astype(jnp.int32)
    return jnp.where(any_diff, sign, 0)


def sort_keys_for_strings(col: DeviceColumn) -> list:
    """Decompose a string column into a list of int16 columns usable as
    lexicographic sort keys for ``lax.sort`` (one operand per char position)."""
    m = char_matrix(col)
    return [m[:, i] for i in range(m.shape[1])]


def string_hash(col: DeviceColumn, seed: int = 42) -> jnp.ndarray:
    """FNV-1a over the char matrix — used for hash partitioning of string
    keys. Deterministic across hosts/chips."""
    m = char_matrix(col)
    valid = m != PAD
    mu = jnp.where(valid, m, 0).astype(jnp.uint32)
    h = jnp.full(m.shape[0], jnp.uint32(2166136261 ^ seed), dtype=jnp.uint32)
    for i in range(m.shape[1]):
        nh = (h ^ mu[:, i]) * jnp.uint32(16777619)
        h = jnp.where(valid[:, i], nh, h)
    return h
