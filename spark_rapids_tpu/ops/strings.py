"""String expression family — the ``stringFunctions.scala`` analog (862 LoC,
SURVEY.md §2.4): Upper/Lower/Length/Substring/StartsWith/EndsWith/Contains/
Like/Concat/Trim family/InitCap.

Device strategy: every kernel runs on the padded char matrix
(:mod:`.strings_util`) — ASCII case mapping is vector arithmetic, substring
is a bounded gather, contains/like are shifted-window compares. Non-ASCII
case mapping and regex fall back to CPU (tagged in overrides), matching the
reference's posture (RegExpReplace literal-pattern-only, compatibility.md).

Semantics note: Spark's length()/substring() are CHARACTER-based (UTF-8
aware). The device kernels operate on bytes; overrides tag non-ASCII-safe
columns... in this snapshot we implement byte semantics and the oracle uses
pyarrow's *binary* (byte) kernels to match — documented divergence from
Spark for multi-byte UTF-8, gated behind the incompatibleOps conf like the
reference gates its divergent string ops.
"""

from __future__ import annotations

from typing import List, Optional

import jax
import jax.numpy as jnp
import pyarrow as pa
import pyarrow.compute as pc

from .. import types as T
from ..data.batch import ColumnarBatch, HostBatch
from ..data.column import DeviceColumn, bucket_byte_capacity
from .expression import (Expression, UnaryExpression, host_to_array,
                         make_column)
from .kernels.rowops import strings_from_matrix
from .strings_util import (PAD, _matrix_from_offsets, char_matrix,
                           lengths)


class StringUnary(Expression):
    """Base: one string child, string/int result."""

    def __init__(self, child: Expression):
        self.children = [child]

    @property
    def child(self):
        return self.children[0]

    def with_children(self, children):
        return type(self)(children[0])


class Length(StringUnary):
    """Byte length (see module semantics note)."""

    @property
    def data_type(self):
        return T.INT

    def eval_host(self, batch: HostBatch) -> pa.Array:
        v = host_to_array(self.child.eval_host(batch), batch.num_rows)
        return pc.binary_length(v.cast(pa.binary())).cast(pa.int32())

    def eval_device(self, batch: ColumnarBatch) -> DeviceColumn:
        c = self.child.eval_device(batch)
        return make_column(lengths(c), c.validity, T.INT)


class _CaseMap(StringUnary):
    lo, hi, delta = 0, 0, 0

    @property
    def data_type(self):
        return T.STRING

    def eval_device(self, batch: ColumnarBatch) -> DeviceColumn:
        c = self.child.eval_device(batch)
        m = char_matrix(c)
        shift = ((m >= self.lo) & (m <= self.hi)) * jnp.int16(self.delta)
        return strings_from_matrix(m + shift, c.validity, c.max_bytes)


class Upper(_CaseMap):
    lo, hi, delta = ord("a"), ord("z"), -32

    def eval_host(self, batch: HostBatch) -> pa.Array:
        v = host_to_array(self.child.eval_host(batch), batch.num_rows)
        return pc.ascii_upper(v)


class Lower(_CaseMap):
    lo, hi, delta = ord("A"), ord("Z"), 32

    def eval_host(self, batch: HostBatch) -> pa.Array:
        v = host_to_array(self.child.eval_host(batch), batch.num_rows)
        return pc.ascii_lower(v)


class Substring(Expression):
    """substring(str, pos, len) — Spark 1-based positions, negative pos
    counts from the end (byte semantics on device)."""

    def __init__(self, child: Expression, pos: Expression, length: Expression):
        self.children = [child, pos, length]

    @property
    def data_type(self):
        return T.STRING

    def with_children(self, children):
        return Substring(*children)

    def eval_host(self, batch: HostBatch) -> pa.Array:
        n = batch.num_rows
        v = host_to_array(self.children[0].eval_host(batch), n)
        # pos/len evaluate per-row on host (the device path requires literals
        # and tags non-literals to fall back here, overrides._substring_tag).
        poss = host_to_array(self.children[1].eval_host(batch), n).to_pylist()
        lens = host_to_array(self.children[2].eval_host(batch), n).to_pylist()
        # Spark: pos 1-based; pos 0 behaves like 1; negative from end.
        out = []
        for s, p, ln in zip(v.to_pylist(), poss, lens):
            if s is None or p is None or ln is None:
                out.append(None)
                continue
            b = s.encode()
            if p > 0:
                start = p - 1
            elif p == 0:
                start = 0
            else:
                start = max(len(b) + p, 0)
            out.append(b[start: start + max(ln, 0)].decode("utf-8",
                                                           errors="replace"))
        return pa.array(out, pa.string())

    def eval_device(self, batch: ColumnarBatch) -> DeviceColumn:
        c = self.children[0].eval_device(batch)
        pos = self.children[1].value
        ln = max(self.children[2].value, 0)
        m = char_matrix(c)
        n, w = m.shape
        slen = lengths(c)
        if pos > 0:
            start = jnp.full(n, pos - 1, jnp.int32)
        elif pos == 0:
            start = jnp.zeros(n, jnp.int32)
        else:
            start = jnp.maximum(slen + pos, 0)
        out_w = min(ln, w) if ln else 1
        out_w = max(out_w, 1)
        cols_idx = start[:, None] + jnp.arange(out_w, dtype=jnp.int32)[None, :]
        in_range = (cols_idx < jnp.minimum(start + ln, slen)[:, None])
        gathered = jnp.take_along_axis(m, jnp.clip(cols_idx, 0, w - 1), axis=1)
        out_m = jnp.where(in_range, gathered, PAD)
        return strings_from_matrix(out_m, c.validity,
                                   bucket_byte_capacity(out_w, 8))


class _FixMatch(Expression):
    """startswith/endswith/contains with a literal needle."""

    def __init__(self, child: Expression, needle: str):
        self.children = [child]
        self.needle = needle

    @property
    def data_type(self):
        return T.BOOLEAN

    def with_children(self, children):
        return type(self)(children[0], self.needle)

    def _needle_arr(self):
        raw = self.needle.encode()
        return jnp.asarray(list(raw), dtype=jnp.int16), len(raw)

    def eval_device(self, batch: ColumnarBatch) -> DeviceColumn:
        from .strings_util import lift_dict
        c = self.children[0].eval_device(batch)
        needle, k = self._needle_arr()
        data = lift_dict(c, lambda m, ln: self.match(m, ln, needle, k))
        return make_column(data, c.validity, T.BOOLEAN)


class StartsWith(_FixMatch):
    def eval_host(self, batch: HostBatch) -> pa.Array:
        v = host_to_array(self.children[0].eval_host(batch), batch.num_rows)
        return pc.starts_with(v, pattern=self.needle)

    def match(self, m, slen, needle, k):
        if k == 0:
            return jnp.ones(m.shape[0], jnp.bool_)
        if k > m.shape[1]:
            return jnp.zeros(m.shape[0], jnp.bool_)
        return jnp.all(m[:, :k] == needle[None, :], axis=1)


class EndsWith(_FixMatch):
    def eval_host(self, batch: HostBatch) -> pa.Array:
        v = host_to_array(self.children[0].eval_host(batch), batch.num_rows)
        return pc.ends_with(v, pattern=self.needle)

    def match(self, m, slen, needle, k):
        if k == 0:
            return jnp.ones(m.shape[0], jnp.bool_)
        w = m.shape[1]
        if k > w:
            return jnp.zeros(m.shape[0], jnp.bool_)
        start = slen - k
        idx = start[:, None] + jnp.arange(k, dtype=jnp.int32)[None, :]
        gathered = jnp.take_along_axis(m, jnp.clip(idx, 0, w - 1), axis=1)
        return (start >= 0) & jnp.all(gathered == needle[None, :], axis=1)


class Contains(_FixMatch):
    def eval_host(self, batch: HostBatch) -> pa.Array:
        v = host_to_array(self.children[0].eval_host(batch), batch.num_rows)
        return pc.match_substring(v, pattern=self.needle)

    def match(self, m, slen, needle, k):
        if k == 0:
            return jnp.ones(m.shape[0], jnp.bool_)
        w = m.shape[1]
        if k > w:
            return jnp.zeros(m.shape[0], jnp.bool_)
        # Shifted-window compare: position p matches if m[:, p:p+k] == needle.
        hits = jnp.zeros(m.shape[0], jnp.bool_)
        for p in range(w - k + 1):
            hits = hits | jnp.all(m[:, p: p + k] == needle[None, :], axis=1)
        return hits


def _like_dp(m: jnp.ndarray, toks) -> jnp.ndarray:
    """Vectorized SQL-LIKE wildcard DP over a [N, W] byte matrix (PAD past
    each string's end). One boolean lane per pattern position; W x P
    unrolled vector ops — every lane stays batch-wide, XLA fuses the whole
    walk into a few kernels.

    '_' is character-aware: it consumes one UTF-8 lead byte and then any
    continuation bytes extend the same state, so multi-byte characters
    match Spark's one-character semantics. '%' needs no special casing —
    a literal following '%' starts with a lead byte and can never match
    at a mid-character (continuation-byte) position."""
    n, w = m.shape
    p = len(toks)
    dp = [jnp.ones(n, jnp.bool_)]
    for i in range(1, p + 1):
        dp.append(dp[i - 1] & (toks[i - 1][0] == 2))
    for j in range(w):
        c = m[:, j]
        valid = c >= 0
        cont = (c & 0xC0) == 0x80  # UTF-8 continuation byte
        ndp = [jnp.zeros(n, jnp.bool_)]
        for i in range(1, p + 1):
            kind, lit = toks[i - 1]
            if kind == 2:
                nd = ndp[i - 1] | dp[i] | dp[i - 1]
            elif kind == 1:
                nd = (dp[i - 1] & ~cont) | (dp[i] & cont)
            else:
                nd = dp[i - 1] & (c == lit)
            ndp.append(nd)
        dp = [jnp.where(valid, a, b) for a, b in zip(ndp, dp)]
    return dp[p]


def _like_literals(payload: jnp.ndarray, offsets: jnp.ndarray, toks,
                   width: int) -> jnp.ndarray:
    """SQL LIKE of a pattern made of literals and '%' alone, over the
    strings' bytes as they lie (the Arrow payload and its offsets; rows of
    a flat column or entries of a dictionary): the same answer as
    :func:`_like_dp` for every byte string, with no char matrix.

    ``windows``: a literal of ``k`` bytes matches at byte ``j`` of the
    payload where ``k`` shifted compares of the whole payload agree.
    ``next_hit``: a running minimum over the next ``width`` bytes (no string
    is longer: the column's ``max_bytes``), log2(width) shifted minima,
    turns those marks into "the first match at or after ``j``", so a row
    reads its leftmost match with one gather at the byte it has reached;
    the match counts if it ends inside the row (a first match that runs
    past the row's end leaves none that does not). Greedy leftmost is
    exact where only '%' separates the literals. A literal at the
    pattern's start must match at the row's first byte, one at its end at
    ``end - k``."""
    n_bytes = payload.shape[0]
    starts, ends = offsets[:-1], offsets[1:]
    segs, run = [], []
    for kind, lit in toks:
        if kind == 2:
            if run:
                segs.append(run)
            run = []
        else:
            run.append(lit)
    if run:
        segs.append(run)
    lead, trail = toks[0][0] == 2, toks[-1][0] == 2
    none = jnp.int32(n_bytes)
    byte = jnp.arange(n_bytes, dtype=jnp.int32)

    def windows(lit):
        with jax.named_scope("windows"):
            hit = byte <= n_bytes - len(lit)
            for i, b in enumerate(lit):
                shifted = payload if i == 0 else jnp.concatenate(
                    [payload[i:], jnp.zeros(i, payload.dtype)])
                hit = hit & (shifted == b)
            return hit

    def at(marks, where):
        return marks[jnp.clip(where, 0, n_bytes - 1)] & (where >= 0) \
            & (where < n_bytes)

    def next_hit(hit):
        with jax.named_scope("next_hit"):
            nxt, reach = jnp.where(hit, byte, none), 1
            while reach < min(width, n_bytes):
                nxt = jnp.minimum(nxt, jnp.concatenate(
                    [nxt[reach:], jnp.full(reach, none)]))
                reach *= 2
            return nxt

    if len(segs) == 1 and not lead and not trail:
        lit = segs[0]           # no '%' at all (escaped ones): the whole
        return at(windows(lit), starts) & (ends - starts == len(lit))
    ok = jnp.ones(starts.shape[0], jnp.bool_)
    reached = starts
    first = None if lead else segs.pop(0)
    last = None if trail else (segs.pop() if segs else None)
    if first is not None:
        ok = ok & at(windows(first), starts) \
            & (starts + len(first) <= ends)
        reached = starts + len(first)
    for lit in segs:
        nxt = next_hit(windows(lit))
        found = jnp.where(reached < n_bytes,
                          nxt[jnp.clip(reached, 0, n_bytes - 1)], none)
        ok = ok & (found <= ends - len(lit))
        reached = jnp.where(ok, found + len(lit), reached)
    if last is not None:
        where = ends - len(last)
        ok = ok & (where >= reached) & at(windows(last), where)
    return ok


class Like(Expression):
    """SQL LIKE with %/_ wildcards. Device support: patterns reducible to
    prefix/suffix/contains/exact; general patterns tagged to CPU."""

    def __init__(self, child: Expression, pattern: str, escape: str = "\\"):
        self.children = [child]
        self.pattern = pattern
        self.escape = escape

    @property
    def data_type(self):
        return T.BOOLEAN

    def with_children(self, children):
        return Like(children[0], self.pattern, self.escape)

    def simple_form(self) -> Optional[tuple]:
        """(kind, literal) when the pattern is a simple form, else None."""
        p = self.pattern
        if "_" in p or self.escape in p:
            return None
        inner = p.strip("%")
        if "%" in inner:
            return None
        if p.startswith("%") and p.endswith("%") and len(p) >= 2:
            return ("contains", inner)
        if p.endswith("%") and not p.startswith("%"):
            return ("prefix", inner)
        if p.startswith("%"):
            return ("suffix", inner)
        return ("exact", inner)

    def eval_host(self, batch: HostBatch) -> pa.Array:
        v = host_to_array(self.children[0].eval_host(batch), batch.num_rows)
        return pc.match_like(v, pattern=self.pattern)

    def tokens(self):
        """Pattern as byte-level tokens: (kind, byte) with kind 0=literal,
        1=_ (any one byte), 2=% (any run); escape makes the next byte
        literal. Consecutive % collapse."""
        pb = self.pattern.encode("utf-8")
        esc = self.escape.encode("utf-8")[0] if self.escape else None
        toks = []
        i = 0
        while i < len(pb):
            b = pb[i]
            if esc is not None and b == esc and i + 1 < len(pb):
                toks.append((0, pb[i + 1]))
                i += 2
                continue
            if b == 0x25:  # %
                if not toks or toks[-1] != (2, 0):
                    toks.append((2, 0))
            elif b == 0x5F:  # _
                toks.append((1, 0))
            else:
                toks.append((0, b))
            i += 1
        return toks

    def eval_device(self, batch: ColumnarBatch) -> DeviceColumn:
        form = self.simple_form()
        if form is not None:
            kind, literal = form
            impl = {"contains": Contains, "prefix": StartsWith,
                    "suffix": EndsWith}.get(kind)
            if impl is not None:
                return impl(self.children[0], literal).eval_device(batch)
            # exact
            from .predicates import EqualTo
            from .expression import Literal
            return EqualTo(self.children[0],
                           Literal(literal, T.STRING)).eval_device(batch)
        # General %/_ pattern: vectorized wildcard DP over the byte matrix
        # (the GpuLike role, stringFunctions.scala:862 — cudf's kernel is
        # this same NFA walk). Dictionary columns run the DP once over the
        # (small) dictionary and gather by code. '_' is UTF-8
        # character-aware (continuation bytes extend the state).
        toks = self.tokens()
        col = self.children[0].eval_device(batch)
        from .expression import make_column
        w = max(col.max_bytes, 1)
        if all(kind != 1 for kind, _ in toks):
            # literals and '%' alone (Q13's '%special%requests%'): window
            # compares and a running minimum over the bytes as they lie,
            # entries of a dictionary or rows of a flat column alike
            with jax.named_scope("like_literals"):
                hit = _like_literals(col.data, col.offsets, toks, w)
        else:
            with jax.named_scope("like_dp"):
                hit = _like_dp(
                    _matrix_from_offsets(col.data, col.offsets, w)
                    if col.is_dict else char_matrix(col), toks)
        res = hit[jnp.clip(col.codes, 0, hit.shape[0] - 1)] \
            if col.is_dict else hit
        res = res & col.validity
        return make_column(res, col.validity, T.BOOLEAN)


class ConcatStrings(Expression):
    """concat(s1, s2, ...) — null if any input is null (Spark concat)."""

    def __init__(self, *children: Expression):
        self.children = list(children)

    @property
    def data_type(self):
        return T.STRING

    def with_children(self, children):
        return ConcatStrings(*children)

    def eval_host(self, batch: HostBatch) -> pa.Array:
        args = [host_to_array(c.eval_host(batch), batch.num_rows)
                for c in self.children]
        return pc.binary_join_element_wise(
            *args, "", null_handling="emit_null")

    def eval_device(self, batch: ColumnarBatch) -> DeviceColumn:
        cols = [c.eval_device(batch) for c in self.children]
        mats = [char_matrix(c) for c in cols]
        lens = [lengths(c) for c in cols]
        n = mats[0].shape[0]
        total_w = sum(m.shape[1] for m in mats)
        out = jnp.full((n, total_w), PAD, dtype=jnp.int16)
        col_idx = jnp.zeros(n, jnp.int32)
        pos_base = jnp.arange(total_w, dtype=jnp.int32)
        offset = jnp.zeros(n, jnp.int32)
        for m, ln in zip(mats, lens):
            w = m.shape[1]
            # Scatter this piece at per-row offset via take_along_axis trick:
            # build target positions then one-hot place with where over a
            # shifted gather (gather out positions back from piece).
            rel = pos_base[None, :] - offset[:, None]  # [n, total_w]
            in_piece = (rel >= 0) & (rel < ln[:, None])
            gathered = jnp.take_along_axis(
                m, jnp.clip(rel, 0, w - 1), axis=1) if w else m
            out = jnp.where(in_piece, gathered, out)
            offset = offset + ln
        validity = cols[0].validity
        for c in cols[1:]:
            validity = validity & c.validity
        out = jnp.where(validity[:, None], out, PAD)
        return strings_from_matrix(out, validity,
                                   bucket_byte_capacity(sum(c.max_bytes
                                                       for c in cols), 8))


class _Trim(StringUnary):
    """trim/ltrim/rtrim of spaces (Spark String2TrimExpression family)."""

    trim_left = True
    trim_right = True

    @property
    def data_type(self):
        return T.STRING

    def eval_device(self, batch: ColumnarBatch) -> DeviceColumn:
        c = self.child.eval_device(batch)
        m = char_matrix(c)
        n, w = m.shape
        slen = lengths(c)
        is_space = m == 32
        idx = jnp.arange(w, dtype=jnp.int32)[None, :]
        if self.trim_left:
            # first non-space position
            non_space = ~is_space & (m != PAD)
            has = jnp.any(non_space, axis=1)
            first = jnp.where(has, jnp.argmax(non_space, axis=1), slen)
        else:
            first = jnp.zeros(n, jnp.int32)
        if self.trim_right:
            non_space = ~is_space & (m != PAD)
            has = jnp.any(non_space, axis=1)
            last = jnp.where(
                has, w - 1 - jnp.argmax(non_space[:, ::-1], axis=1), -1)
            end = jnp.where(has, last + 1, first)
        else:
            end = slen
        rel = idx + first[:, None]
        in_range = (idx < (end - first)[:, None])
        gathered = jnp.take_along_axis(m, jnp.clip(rel, 0, w - 1), axis=1)
        out = jnp.where(in_range, gathered, PAD)
        return strings_from_matrix(out, c.validity, c.max_bytes)


class StringTrim(_Trim):
    def eval_host(self, batch: HostBatch) -> pa.Array:
        v = host_to_array(self.child.eval_host(batch), batch.num_rows)
        return pc.utf8_trim(v, characters=" ")


class StringTrimLeft(_Trim):
    trim_right = False

    def eval_host(self, batch: HostBatch) -> pa.Array:
        v = host_to_array(self.child.eval_host(batch), batch.num_rows)
        return pc.utf8_ltrim(v, characters=" ")


class StringTrimRight(_Trim):
    trim_left = False

    def eval_host(self, batch: HostBatch) -> pa.Array:
        v = host_to_array(self.child.eval_host(batch), batch.num_rows)
        return pc.utf8_rtrim(v, characters=" ")
