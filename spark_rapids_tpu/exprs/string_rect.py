"""Device string transforms over byte rectangles (VERDICT r3 #4).

High-cardinality STRING columns live in HBM as `StrVal` rectangles
(columnar/strrect.py). The transforms here are the vectorized axis-1
kernels the reference gets from cudf's string kernels
(stringFunctions.scala:1-2377): every op is elementwise/static-shift work
over `bytes_[P, W]` + `lengths[P]` — no ragged buffers, no per-row code,
everything fuses into ONE projection kernel.

ASCII gate: the device path only runs when the batch was proven
all-ASCII at ingest (ByteRectColumn.ascii_only); case mapping and char
semantics beyond ASCII fall back to the host path honestly rather than
being silently wrong.

Supported chain ops (STRING -> STRING): Upper, Lower, StringTrim(L/R)
(space-only, Spark semantics), Substring (pos >= 0, fixed length), StringReplace,
Lpad/Rpad, SubstringIndex, Reverse; terminals: Length (STRING -> INT),
StringLocate/StringInstr (STRING -> INT),
Contains/StartsWith/EndsWith/Like (STRING -> BOOL).

All kernels are scatter/gather + unrolled static shifts — no lax.sort
(a sort's compile time multiplies with its module on this backend,
docs/performance.md r4) and no per-row host work.
"""
from __future__ import annotations

from typing import Optional

import jax.numpy as jnp
import numpy as np

from ..types import BOOL, INT32, STRING, Schema
from .base import ColumnRef, DVal, Expression, StrVal

__all__ = ["rect_chain_leaf", "eval_rect_expr", "rect_supported_op",
           "RectUnsupported"]


class RectUnsupported(Exception):
    """Raised at kernel-trace time when a rect op cannot run for THIS
    batch's concrete widths (e.g. a growing replace past the width
    cap): the caller falls back to host evaluation for the batch."""


def _live(sv: StrVal):
    w = sv.bytes_.shape[1]
    return (jnp.arange(w, dtype=jnp.int32)[None, :]
            < sv.lengths[:, None])


def _is_space(b):
    return jnp.logical_or(b == 32, jnp.logical_and(b >= 9, b <= 13))


def _realign(bytes_, start):
    """Shift each row left by its (traced, per-row) start offset: the sum
    of W static shifts masked by (start == s) — compile-friendly, no
    per-row gather."""
    w = bytes_.shape[1]
    out = jnp.zeros_like(bytes_)
    for s in range(w):
        shifted = (bytes_ if s == 0
                   else jnp.pad(bytes_[:, s:], ((0, 0), (0, s))))
        out = jnp.where((start == s)[:, None], shifted, out)
    return out


def _zero_tail(bytes_, lengths):
    w = bytes_.shape[1]
    live = jnp.arange(w, dtype=jnp.int32)[None, :] < lengths[:, None]
    return jnp.where(live, bytes_, jnp.uint8(0))


def _upper(sv: StrVal) -> StrVal:
    b = sv.bytes_
    low = jnp.logical_and(b >= 97, b <= 122)
    return StrVal(jnp.where(low, b - 32, b), sv.lengths)


def _lower(sv: StrVal) -> StrVal:
    b = sv.bytes_
    up = jnp.logical_and(b >= 65, b <= 90)
    return StrVal(jnp.where(up, b + 32, b), sv.lengths)


def _trim(sv: StrVal, left: bool, right: bool) -> StrVal:
    b, ln = sv.bytes_, sv.lengths
    live = _live(sv)
    # Spark TRIM removes ONLY the space character 0x20 (SPARK-17299)
    sp = jnp.logical_and(b == 32, live)
    lead = jnp.zeros_like(ln)
    if left:
        # leading-space count: cumprod zeroes after the first non-space
        run = jnp.cumprod(jnp.where(live, sp.astype(jnp.int32), 0),
                          axis=1)
        lead = jnp.sum(run, axis=1).astype(jnp.int32)
    trail = jnp.zeros_like(ln)
    if right:
        # trailing run: reverse cumprod; positions past the length keep 1
        # so they don't break the run
        rev = jnp.cumprod(jnp.where(live, sp.astype(jnp.int32), 1)[:, ::-1],
                          axis=1)[:, ::-1]
        trail = jnp.sum(jnp.where(live, rev, 0), axis=1).astype(jnp.int32)
    new_len = jnp.maximum(ln - lead - trail, 0)
    # all-space strings: lead+trail may double-count; clamp start too
    start = jnp.minimum(lead, ln)
    out = _realign(b, start) if left else b
    return StrVal(_zero_tail(out, new_len), new_len)


def _substring(sv: StrVal, pos: int, length: Optional[int]) -> StrVal:
    b, ln = sv.bytes_, sv.lengths
    start = pos - 1 if pos > 0 else 0       # SQL 1-based; 0 acts like 1
    w = b.shape[1]
    if start > 0:
        b = (jnp.pad(b[:, start:], ((0, 0), (0, min(start, w))))
             if start < w else jnp.zeros_like(b))
    new_len = jnp.maximum(ln - start, 0)
    if length is not None:
        if length <= 0:
            new_len = jnp.zeros_like(new_len)
        else:
            new_len = jnp.minimum(new_len, length)
        from ..columnar.strrect import rect_width_bucket
        wb = rect_width_bucket(max(length, 1), w)
        if wb is not None and wb < b.shape[1]:
            b = b[:, :wb]
    return StrVal(_zero_tail(b, new_len), new_len)


def _match_at(b, live, pat: np.ndarray, offset):
    """all_j b[:, offset+j] == pat[j], offset static."""
    w = b.shape[1]
    L = len(pat)
    if offset + L > w:
        return jnp.zeros(b.shape[0], bool)
    m = jnp.ones(b.shape[0], bool)
    for j, ch in enumerate(pat):
        m = jnp.logical_and(m, b[:, offset + j] == np.uint8(ch))
    return m


def _startswith(sv: StrVal, pat: bytes):
    p = np.frombuffer(pat, np.uint8)
    ok_len = sv.lengths >= len(p)
    return jnp.logical_and(ok_len,
                           _match_at(sv.bytes_, None, p, 0))


def _endswith(sv: StrVal, pat: bytes):
    p = np.frombuffer(pat, np.uint8)
    L = len(p)
    b, ln = sv.bytes_, sv.lengths
    w = b.shape[1]
    if L == 0:
        return jnp.ones(b.shape[0], bool)
    out = jnp.zeros(b.shape[0], bool)
    for s in range(w - L + 1):           # match where length-L == s
        out = jnp.where(ln - L == s, _match_at(b, None, p, s), out)
    return jnp.logical_and(ln >= L, out)


def _contains(sv: StrVal, pat: bytes):
    p = np.frombuffer(pat, np.uint8)
    L = len(p)
    b, ln = sv.bytes_, sv.lengths
    w = b.shape[1]
    if L == 0:
        return jnp.ones(b.shape[0], bool)
    out = jnp.zeros(b.shape[0], bool)
    for s in range(w - L + 1):
        out = jnp.logical_or(
            out, jnp.logical_and(_match_at(b, None, p, s),
                                 ln - L >= s))
    return out


def _take_shift(b, start):
    """Gather-based left shift by a per-row (traced) start offset; reads
    past the width land on a zero column."""
    w = b.shape[1]
    j = jnp.arange(w, dtype=jnp.int32)[None, :]
    src = j + start[:, None]
    bx = jnp.pad(b, ((0, 0), (0, 1)))
    return jnp.take_along_axis(bx, jnp.clip(src, 0, w), axis=1)


def _select_nonoverlap(b, ln, pat: np.ndarray):
    """Greedy left-to-right NON-OVERLAPPING occurrences of ``pat``
    (java String semantics shared by replace/split): sel[p, j] marks
    occurrence starts, cum[p, j] counts occurrences at positions <= j.
    Sequential in j but unrolled over the static width — vector ops
    only, no per-row code."""
    w = b.shape[1]
    rows = b.shape[0]
    L = len(pat)
    match = []
    for j in range(w):
        if j + L <= w:
            match.append(jnp.logical_and(_match_at(b, None, pat, j),
                                         ln >= j + L))
        else:
            match.append(jnp.zeros(rows, bool))
    next_free = jnp.zeros(rows, jnp.int32)
    sels = []
    for j in range(w):
        s = jnp.logical_and(match[j], next_free <= j)
        next_free = jnp.where(s, j + L, next_free)
        sels.append(s)
    sel = jnp.stack(sels, axis=1)
    cum = jnp.cumsum(sel.astype(jnp.int32), axis=1)
    return sel, cum


#: replacement-literal length cap: each replacement byte is one scatter
#: in the fused kernel
_REPLACE_MAX = 32
#: static pad-target cap (HBM is rows*width)
_PAD_MAX = 256


def _replace(sv: StrVal, search: bytes, replace: bytes,
             width_cap: int = 1 << 20) -> StrVal:
    """replace(str, search, replace): non-overlapping left-to-right, may
    grow the rectangle (bounded by W//len(search) occurrences) up to the
    configured width cap — past it the batch falls back to host."""
    b, ln = sv.bytes_, sv.lengths
    rows, w = b.shape
    s = np.frombuffer(search, np.uint8)
    r = np.frombuffer(replace, np.uint8)
    l1, l2 = len(s), len(r)
    if l1 == 0:
        return sv                       # Spark: empty search is identity
    sel, _ = _select_nonoverlap(b, ln, s)
    # covered: inside a selected occurrence, not at its start
    cov = jnp.zeros_like(sel)
    for k in range(1, min(l1, w)):
        cov = jnp.logical_or(cov, jnp.pad(sel[:, :-k], ((0, 0), (k, 0))))
    live = _live(sv)
    emit = jnp.where(sel, l2,
                     jnp.where(jnp.logical_or(cov, ~live), 0, 1)) \
        .astype(jnp.int32)
    outpos = jnp.cumsum(emit, axis=1) - emit        # exclusive
    new_len = outpos[:, -1] + emit[:, -1]
    w_need = w + max(0, l2 - l1) * (w // l1)
    from ..columnar.strrect import rect_width_bucket
    # growth allowance: the conf cap governs ingest width; an op may
    # grow to the cap (or the input width when already above it)
    wo = rect_width_bucket(max(w_need, 1), max(width_cap, w))
    if wo is None:      # grown width past the cap: host handles it
        raise RectUnsupported(f"replace output width {w_need}")
    rowix = jnp.arange(rows, dtype=jnp.int32)[:, None]
    out = jnp.zeros((rows, wo + 1), jnp.uint8)      # col wo = dump slot
    copy_idx = jnp.where(
        jnp.logical_or(sel, jnp.logical_or(cov, ~live)), wo, outpos)
    out = out.at[rowix, copy_idx].set(b, mode="drop")
    for k in range(l2):
        rep_idx = jnp.where(sel, outpos + k, wo)
        out = out.at[rowix, rep_idx].set(jnp.uint8(r[k]), mode="drop")
    return StrVal(_zero_tail(out[:, :wo], new_len), new_len)


def _pad(sv: StrVal, valid, length: int, pad: bytes, left: bool) -> StrVal:
    """lpad/rpad to a STATIC length with a cyclic pad pattern; longer
    inputs keep their prefix (Spark semantics). Invalid rows stay
    all-zero (the rectangle convention grouping relies on)."""
    b, ln = sv.bytes_, sv.lengths
    rows, w = b.shape
    p = np.frombuffer(pad, np.uint8)
    lp = len(p)
    from ..columnar.strrect import rect_width_bucket
    wo = rect_width_bucket(max(length, 1), 1 << 20)
    bx = b if wo <= w else jnp.pad(b, ((0, 0), (0, wo - w)))
    bx = bx[:, :wo]
    j = jnp.arange(wo, dtype=jnp.int32)[None, :]
    pad_full = jnp.asarray(np.resize(p, wo))        # pad[j % lp] table
    if left:
        shift = jnp.maximum(length - ln, 0)[:, None]
        src = j - shift
        bpad = jnp.pad(bx, ((0, 0), (0, 1)))
        orig = jnp.take_along_axis(bpad, jnp.clip(src, 0, wo), axis=1)
        out = jnp.where(src >= 0, orig, pad_full[None, :])
    else:
        out = jnp.where(j < ln[:, None], bx,
                        pad_full[jnp.clip(j - ln[:, None], 0, wo - 1)])
    new_len = jnp.where(valid, jnp.int32(length), 0)
    return StrVal(_zero_tail(out, new_len), new_len)


def _locate(sv: StrVal, sub: bytes):
    """1-based first occurrence, 0 when absent (byte == char: ASCII)."""
    b, ln = sv.bytes_, sv.lengths
    rows, w = b.shape
    p = np.frombuffer(sub, np.uint8)
    L = len(p)
    if L == 0:
        return jnp.ones(rows, jnp.int32)   # Spark: locate('', s) == 1
    pos = jnp.zeros(rows, jnp.int32)
    found = jnp.zeros(rows, bool)
    for s in range(0, max(w - L + 1, 0)):
        m = jnp.logical_and(_match_at(b, None, p, s), ln >= s + L)
        pos = jnp.where(jnp.logical_and(~found, m), s + 1, pos)
        found = jnp.logical_or(found, m)
    return pos


_REGEX_META = set(".^$*+?{}[]\\|()")


def _rlike_literal_parts(pattern: str):
    """(mode, literal) when a Java-regex RLIKE pattern is really an
    (optionally anchored) LITERAL — the common grep-style case cudf also
    fast-paths (ref RegexParser literal detection): no metacharacters
    besides the ^/$ anchors at the edges. None otherwise."""
    if not pattern:
        return None
    lead = pattern.startswith("^")
    trail = pattern.endswith("$")
    body = pattern[1 if lead else 0: len(pattern) - (1 if trail else 0)]
    if any(c in _REGEX_META for c in body):
        return None
    if _ascii(body) is None:
        return None
    if lead and trail:
        return ("equals", body)
    if lead:
        return ("startswith", body)
    if trail:
        return ("endswith", body)
    return ("contains", body)    # RLIKE is an unanchored search


def _like_parts(pattern: str):
    """(form, literal) for rectangle-supported LIKE patterns: leading/
    trailing %% around one literal (prefix/suffix/contains/exact).
    None for '_', escapes, interior %%, or non-ASCII."""
    if "_" in pattern or "\\" in pattern:
        return None
    try:
        pattern.encode("ascii")
    except UnicodeEncodeError:
        return None
    lead = pattern.startswith("%")
    trail = pattern.endswith("%")
    mid = pattern.strip("%")
    if "%" in mid:
        return None
    if lead and trail:
        return ("contains", mid)
    if lead:
        return ("endswith", mid)
    if trail:
        return ("startswith", mid)
    return ("equals", mid)


def _equals(sv: StrVal, pat: bytes):
    p = np.frombuffer(pat, np.uint8)
    return jnp.logical_and(sv.lengths == len(p),
                           _match_at(sv.bytes_, None, p, 0))


def _substring_index(sv: StrVal, delim: bytes, count: int) -> StrVal:
    """substring_index: prefix before the count-th delimiter (count>0)
    or suffix after the |count|-th-from-last (count<0); whole string
    when there are fewer delimiters."""
    b, ln = sv.bytes_, sv.lengths
    rows, w = b.shape
    d = np.frombuffer(delim, np.uint8)
    L = len(d)
    if count == 0:
        z = jnp.zeros_like(ln)
        return StrVal(jnp.zeros_like(b), z)
    sel, cum = _select_nonoverlap(b, ln, d)
    j = jnp.arange(w, dtype=jnp.int32)[None, :]
    if count > 0:
        mask = jnp.logical_and(sel, cum == count)
        cut = jnp.where(mask, j, w).min(axis=1)
        new_len = jnp.minimum(ln, cut)
        return StrVal(_zero_tail(b, new_len), new_len)
    target = cum[:, -1] + count + 1     # 1-based boundary occurrence
    mask = jnp.logical_and(sel, cum == target[:, None])
    start = jnp.where(mask, j, 0).max(axis=1) + L
    start = jnp.where(target >= 1, start, 0)
    new_len = jnp.maximum(ln - start, 0)
    return StrVal(_zero_tail(_take_shift(b, start), new_len), new_len)


def _reverse(sv: StrVal) -> StrVal:
    b, ln = sv.bytes_, sv.lengths
    w = b.shape[1]
    j = jnp.arange(w, dtype=jnp.int32)[None, :]
    src = ln[:, None] - 1 - j
    bx = jnp.pad(b, ((0, 0), (0, 1)))
    out = jnp.take_along_axis(bx, jnp.clip(src, 0, w), axis=1)
    return StrVal(_zero_tail(out, ln), ln)


# ---------------------------------------------------------------------------
# expression bridge
# ---------------------------------------------------------------------------

def _ascii(s: str) -> Optional[bytes]:
    try:
        return s.encode("ascii")
    except UnicodeEncodeError:
        return None


def rect_supported_op(e: Expression) -> bool:
    from .base import Literal
    from .string_fns import (Contains, EndsWith, Length, Like, Lower, Lpad,
                             Reverse, RLike, StartsWith, StringInstr,
                             StringLocate, StringReplace, StringTrim,
                             StringTrimLeft, StringTrimRight,
                             SubstringIndex, Substring, Upper)
    if isinstance(e, (Upper, Lower, Length, Reverse)):
        return True
    if isinstance(e, (StringTrim, StringTrimLeft, StringTrimRight)):
        return e.chars is None           # default (space-only) trim
    if isinstance(e, Substring):
        return e.pos >= 0                # negative pos: from-end (host)
    if isinstance(e, Like):
        # _like_parts rejects any '\\' in the pattern, so the default
        # escape can never fire on an accepted pattern; a CUSTOM escape
        # char would change the parse -> host
        return e.escape == "\\" and _like_parts(e.pattern) is not None
    if isinstance(e, RLike):
        return _rlike_literal_parts(e.pattern) is not None
    if isinstance(e, (Contains, StartsWith, EndsWith)):
        return _ascii(e.pattern) is not None
    if isinstance(e, StringReplace):
        return (_ascii(e.search) is not None and len(e.search) >= 1
                and _ascii(e.replace) is not None
                and len(e.replace) <= _REPLACE_MAX)
    if isinstance(e, (Lpad,)):           # covers Rpad subclass
        return (0 < e.length <= _PAD_MAX and len(e.pad) >= 1
                and _ascii(e.pad) is not None)
    if isinstance(e, StringLocate):
        return _ascii(e.substr) is not None
    if isinstance(e, StringInstr):
        sub = e.children[1]
        return (isinstance(sub, Literal) and isinstance(sub.value, str)
                and _ascii(sub.value) is not None)
    if isinstance(e, SubstringIndex):
        return len(e.delim) >= 1 and _ascii(e.delim) is not None
    return False


def rect_chain_leaf(e: Expression, schema: Schema) -> Optional[str]:
    """Leaf column name when ``e`` is a chain of rect-supported ops over
    one STRING ColumnRef, else None. StringInstr carries its substring
    as a Literal second child — the chain continues through child 0."""
    cur = e
    hops = 0
    while rect_supported_op(cur) and len(cur.children) >= 1:
        cur = cur.children[0]
        hops += 1
    if hops and isinstance(cur, ColumnRef) \
            and cur.name in schema.names() \
            and schema[cur.name].dtype == STRING:
        return cur.name
    return None


def eval_rect_expr(e: Expression, child: DVal,
                   width_cap: int = 1 << 20) -> DVal:
    """Evaluate one rect-supported op over a StrVal-typed DVal (traced)."""
    from .string_fns import (Contains, EndsWith, Length, Like, Lower, Lpad,
                             Reverse, RLike, Rpad, StartsWith, StringInstr,
                             StringLocate, StringReplace, StringTrim,
                             StringTrimLeft, StringTrimRight,
                             SubstringIndex, Substring, Upper)
    sv: StrVal = child.data
    v = child.validity
    if isinstance(e, Upper):
        return DVal(_upper(sv), v, STRING)
    if isinstance(e, Lower):
        return DVal(_lower(sv), v, STRING)
    if isinstance(e, StringTrim):
        return DVal(_trim(sv, True, True), v, STRING)
    if isinstance(e, StringTrimLeft):
        return DVal(_trim(sv, True, False), v, STRING)
    if isinstance(e, StringTrimRight):
        return DVal(_trim(sv, False, True), v, STRING)
    if isinstance(e, Substring):
        return DVal(_substring(sv, e.pos, e.length), v, STRING)
    if isinstance(e, Length):
        return DVal(jnp.where(v, sv.lengths, 0).astype(jnp.int32), v,
                    INT32)
    if isinstance(e, StartsWith):
        return DVal(_startswith(sv, e.pattern.encode()), v, BOOL)
    if isinstance(e, EndsWith):
        return DVal(_endswith(sv, e.pattern.encode()), v, BOOL)
    if isinstance(e, Contains):
        return DVal(_contains(sv, e.pattern.encode()), v, BOOL)
    if isinstance(e, (Like, RLike)):
        form, lit = (_like_parts(e.pattern) if isinstance(e, Like)
                     else _rlike_literal_parts(e.pattern))
        fn = {"contains": _contains, "startswith": _startswith,
              "endswith": _endswith, "equals": _equals}[form]
        return DVal(fn(sv, lit.encode()), v, BOOL)
    if isinstance(e, StringReplace):
        return DVal(_replace(sv, e.search.encode(), e.replace.encode(),
                             width_cap), v, STRING)
    if isinstance(e, Rpad):
        return DVal(_pad(sv, v, e.length, e.pad.encode(), False), v,
                    STRING)
    if isinstance(e, Lpad):
        return DVal(_pad(sv, v, e.length, e.pad.encode(), True), v,
                    STRING)
    if isinstance(e, StringLocate):
        return DVal(_locate(sv, e.substr.encode()), v, INT32)
    if isinstance(e, StringInstr):
        return DVal(_locate(sv, e.children[1].value.encode()), v, INT32)
    if isinstance(e, SubstringIndex):
        return DVal(_substring_index(sv, e.delim.encode(), e.count), v,
                    STRING)
    if isinstance(e, Reverse):
        return DVal(_reverse(sv), v, STRING)
    raise NotImplementedError(type(e).__name__)


def eval_rect_chain(e: Expression, leaf_val: DVal,
                    width_cap: int = 1 << 20) -> DVal:
    """Evaluate a rect_chain (validated by rect_chain_leaf) bottom-up."""
    if isinstance(e, ColumnRef):
        return leaf_val
    child = eval_rect_chain(e.children[0], leaf_val, width_cap)
    return eval_rect_expr(e, child, width_cap)
