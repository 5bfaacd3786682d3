"""Spark's decimal rules on unscaled int64 lanes.

Reference analog: Catalyst ``DecimalPrecision`` (result types), the
plugin's ``GpuDecimalMultiply`` / ``GpuDecimalDivide`` (arithmetic.scala)
and the ``DecimalUtils`` JNI (SURVEY.md 2.12). What is here:

* **result types** as Spark derives them: add / subtract
  ``max(p1-s1, p2-s2) + max(s1, s2) + 1`` at scale ``max(s1, s2)``,
  multiply ``p1 + p2 + 1`` at ``s1 + s2``, divide scale
  ``max(6, s1 + p2 + 1)``, each through ``adjustPrecisionScale`` beyond
  38 digits; an integer as ``decimal(3|5|10|20, 0)`` and an integer or
  decimal LITERAL by its own digits (``DecimalType.fromLiteral``);
* **the arithmetic** on unscaled values, written once over ``xp`` (numpy
  on the host, jax.numpy in a kernel): operands brought to one scale,
  HALF_UP wherever a scale is cut, and every step that could leave 63
  bits CHECKED. A checked step returns the row's overflow flag beside
  the value; an operation whose declared result precision is at most 18
  cannot leave 63 bits and is not checked at all;
* **the host's exact form**: the same arithmetic on Python ints in
  object arrays (``wide=True``), which the host engine falls back to for
  the rows int64 cannot hold and for decimal division;
* **where the flags go**: an overflowed lane is NULL, never a wrapped
  number. A kernel that can carry a count opens ``collecting()`` around
  its traced body, and the count comes back with the kernel's own result
  (the aggregate packs it into its one fetch); a kernel that cannot
  leaves ``defer()``-ed device scalars for the query's sink, which
  fetches them once and raises ``DecimalOverflow``.
"""
from __future__ import annotations

import contextlib
import decimal
import threading
from typing import List, Optional

import numpy as np

from ..types import DataType, DecimalType

__all__ = ["DecimalOverflow", "checked_ops", "checked_ops_of_stages",
           "add_type", "multiply_type", "divide_type",
           "remainder_type", "wider_type", "operand_type", "literal_type",
           "sum_type", "avg_types", "collecting", "masked",
           "note_overflow", "defer", "settle_pending", "clear_pending"]

MAX_PRECISION = 38
MIN_ADJUSTED_SCALE = 6
INT64_MAX = (1 << 63) - 1
#: a declared precision up to this cannot leave the lane (10^18 < 2^63)
LANE_DIGITS = 18
#: Spark's DecimalType.LongDecimal, what avg casts its count to
LONG_DECIMAL = DecimalType(20, 0)
_INTEGRAL_DIGITS = {"tinyint": 3, "smallint": 5, "int": 10, "bigint": 20}


class DecimalOverflow(ArithmeticError, ValueError):
    """A decimal value left the device's 64-bit unscaled lane where Spark
    would still hold a number (the engine's loud error; ingest raises it
    too, columnar/batch.py)."""


def overflow_error(rows: int, where: str) -> DecimalOverflow:
    return DecimalOverflow(
        f"decimal overflow in {where}: {rows} row(s) exceed the device's "
        f"64-bit unscaled range (|unscaled| >= 2^63) where Spark's "
        f"decimal(38) would hold a number; this magnitude needs host "
        f"execution")


# ---------------------------------------------------------------------------
# result types (Catalyst DecimalPrecision / DecimalType)
# ---------------------------------------------------------------------------

def bounded(precision: int, scale: int) -> DecimalType:
    return DecimalType(min(precision, MAX_PRECISION),
                       min(scale, MAX_PRECISION))


def adjust(precision: int, scale: int) -> DecimalType:
    """``DecimalType.adjustPrecisionScale``: beyond 38 digits keep the
    integral digits and cut the scale, but not below min(scale, 6)."""
    if precision <= MAX_PRECISION:
        return DecimalType(precision, scale)
    int_digits = precision - scale
    min_scale = min(scale, MIN_ADJUSTED_SCALE)
    return DecimalType(MAX_PRECISION,
                       max(MAX_PRECISION - int_digits, min_scale))


def add_precision(l: DecimalType, r: DecimalType) -> int:
    s = max(l.scale, r.scale)
    return max(l.precision - l.scale, r.precision - r.scale) + s + 1


def add_type(l: DecimalType, r: DecimalType) -> DecimalType:
    return adjust(add_precision(l, r), max(l.scale, r.scale))


def multiply_type(l: DecimalType, r: DecimalType) -> DecimalType:
    return adjust(l.precision + r.precision + 1, l.scale + r.scale)


def divide_type(l: DecimalType, r: DecimalType) -> DecimalType:
    s = max(MIN_ADJUSTED_SCALE, l.scale + r.precision + 1)
    return adjust(l.precision - l.scale + r.scale + s, s)


def remainder_type(l: DecimalType, r: DecimalType) -> DecimalType:
    s = max(l.scale, r.scale)
    return adjust(min(l.precision - l.scale, r.precision - r.scale) + s, s)


def wider_type(l: DecimalType, r: DecimalType) -> DecimalType:
    """The type two decimals are compared (or unioned) in."""
    s = max(l.scale, r.scale)
    return bounded(max(l.precision - l.scale, r.precision - r.scale) + s, s)


def operand_type(dt: DataType) -> Optional[DecimalType]:
    """A decimal or integral type as the decimal it enters decimal
    arithmetic in (``DecimalType.forType``); None for anything else (a
    double beside a decimal makes the operation a double one)."""
    if isinstance(dt, DecimalType):
        return dt
    digits = _INTEGRAL_DIGITS.get(dt.name)
    return DecimalType(digits, 0) if digits else None


def literal_type(value) -> DecimalType:
    """``DecimalType.fromLiteral`` / ``fromDecimal``: a literal's own
    digits (1 is decimal(1,0), 0.05 is decimal(2,2))."""
    sign, digits, exp = decimal.Decimal(value).as_tuple()
    if exp > 0:
        return DecimalType(len(digits) + exp, 0)
    return DecimalType(max(len(digits), -exp, 1), -exp)


def sum_type(dt: DecimalType) -> DecimalType:
    return bounded(dt.precision + 10, dt.scale)


def avg_types(dt: DecimalType):
    """(sum type, the divide's own type, the average's type): Spark
    evaluates ``(sum / cast(count as decimal(20,0))).cast(decimal(p+4,
    s+4))``, so an average is rounded HALF_UP twice, to the divide's
    scale and then to its own."""
    st = sum_type(dt)
    return st, divide_type(st, LONG_DECIMAL), \
        bounded(dt.precision + 4, dt.scale + 4)


def unscaled(value, dtype: DecimalType) -> int:
    """A Python number as the unscaled int of ``dtype`` (exact; the
    value has to fit the scale)."""
    with decimal.localcontext() as c:
        c.prec = 2 * MAX_PRECISION + 4
        return int(decimal.Decimal(value).scaleb(dtype.scale)
                   .to_integral_exact(rounding=decimal.ROUND_HALF_UP))


def from_unscaled(value: int, dtype: DecimalType) -> Optional[decimal.Decimal]:
    """The ``decimal.Decimal`` of an unscaled int at ``dtype``'s scale;
    None where it has more digits than the type declares (Spark: NULL)."""
    if abs(value) >= 10 ** dtype.precision:
        return None
    with decimal.localcontext() as c:
        c.prec = 2 * MAX_PRECISION + 4
        return decimal.Decimal(value).scaleb(-dtype.scale)


# ---------------------------------------------------------------------------
# arithmetic on unscaled values: xp is numpy or jax.numpy; wide=True means
# numpy object arrays of Python ints (exact, nothing to check)
# ---------------------------------------------------------------------------

def checked_mul(xp, a, b, wide: bool = False):
    """(a * b, overflow flag or None)."""
    if wide:
        return a * b, None
    if xp is np:
        with np.errstate(all="ignore"):
            p = a * b
            safe = np.where(a == 0, 1, a)
            over = (a != 0) & ((p // safe != b)
                               | ((a == -1) & (b == np.iinfo(np.int64).min)))
        return p, over
    # device: the product of two magnitudes is below 2^64 iff their
    # leading zeros add up to 64 or more; it then is the wrapping unsigned
    # product, and fits the signed lane iff that is below 2^63
    from jax.lax import clz
    ua = xp.abs(a).astype(xp.uint64)
    ub = xp.abs(b).astype(xp.uint64)
    lz = clz(ua).astype(xp.int32) + clz(ub).astype(xp.int32)
    top = (ua * ub) >> xp.uint64(63)
    over = xp.logical_or(lz < 64, top != 0)
    return a * b, over


def checked_add(xp, a, b, wide: bool = False):
    if wide:
        return a + b, None
    with np.errstate(all="ignore"):
        s = a + b
    return s, ((a ^ s) & (b ^ s)) < 0


def checked_sub(xp, a, b, wide: bool = False):
    if wide:
        return a - b, None
    with np.errstate(all="ignore"):
        d = a - b
    return d, ((a ^ b) & (a ^ d)) < 0


def scale_up(xp, x, k: int, wide: bool = False):
    """(x * 10^k, overflow flag or None)."""
    if k == 0:
        return x, None
    if wide:
        return x * (10 ** k), None
    if k > LANE_DIGITS:
        return xp.zeros_like(x), x != 0
    lim = INT64_MAX // 10 ** k
    over = xp.abs(x) > lim
    return xp.where(over, 0, x) * (10 ** k), over


def div_half_up(xp, n, d):
    """n / d rounded HALF_UP (ties away from zero); d > 0."""
    a = abs(n)
    q = a // d
    q = q + ((a % d) * 2 >= d)
    return xp.where(n < 0, -q, q)


def scale_down(xp, x, k: int, wide: bool = False):
    """x / 10^k rounded HALF_UP."""
    if k == 0:
        return x
    if wide or k <= LANE_DIGITS:
        return div_half_up(xp, x, 10 ** k)
    if k == LANE_DIGITS + 1:          # |x| < 2^63 < 10^19
        return xp.where(xp.abs(x) >= 5 * 10 ** LANE_DIGITS,
                        xp.sign(x), xp.zeros_like(x))
    return xp.zeros_like(x)


def rescale(xp, x, frm: int, to: int, wide: bool = False):
    """(x at scale ``to``, overflow flag or None)."""
    if to >= frm:
        return scale_up(xp, x, to - frm, wide)
    return scale_down(xp, x, frm - to, wide), None


def any_flag(xp, *flags):
    out = None
    for f in flags:
        if f is not None:
            out = f if out is None else xp.logical_or(out, f)
    return out


def add_values(xp, x, y, l, r, res, wide=False, subtract=False):
    """x (+|-) y of types l, r as the unscaled value of ``res``."""
    s = max(l.scale, r.scale)
    checked = not wide and add_precision(l, r) > LANE_DIGITS
    x, fx = scale_up(xp, x, s - l.scale, wide or not checked)
    y, fy = scale_up(xp, y, s - r.scale, wide or not checked)
    op = checked_sub if subtract else checked_add
    z, fz = op(xp, x, y, wide or not checked)
    return scale_down(xp, z, s - res.scale, wide), any_flag(xp, fx, fy, fz)


def multiply_values(xp, x, y, l, r, res, wide=False):
    checked = not wide and l.precision + r.precision + 1 > LANE_DIGITS
    z, f = checked_mul(xp, x, y, wide or not checked)
    return scale_down(xp, z, l.scale + r.scale - res.scale, wide), f


def compare_values(xp, x, y, l, r, wide=False):
    """Both sides at the wider scale, and the flag of that rescale."""
    s = max(l.scale, r.scale)
    checked = not wide and wider_type(l, r).precision > LANE_DIGITS
    x, fx = scale_up(xp, x, s - l.scale, wide or not checked)
    y, fy = scale_up(xp, y, s - r.scale, wide or not checked)
    return x, y, any_flag(xp, fx, fy)


def exceeds(xp, x, dtype: DecimalType):
    """|x| has more digits than ``dtype`` declares (Spark: NULL)."""
    if dtype.precision > LANE_DIGITS and hasattr(x, "dtype") \
            and x.dtype != object:
        return None
    return abs(x) >= 10 ** dtype.precision


# ---- exact scalar forms (Python ints): the host aggregate's finish -------

def half_up_int(n: int, d: int) -> int:
    q, r = divmod(abs(n), d)
    q += 2 * r >= d
    return -q if n < 0 else q


def rescale_int(x: int, frm: int, to: int) -> int:
    return x * 10 ** (to - frm) if to >= frm \
        else half_up_int(x, 10 ** (frm - to))


def average_int(total: int, count: int, dt: DecimalType) -> Optional[int]:
    """avg over ``dt`` from the exact unscaled total: Spark's two
    roundings. None where the value has more digits than the type."""
    st, dv, res = avg_types(dt)
    q = half_up_int(total * 10 ** max(dv.scale - st.scale, 0),
                    count * 10 ** max(st.scale - dv.scale, 0))
    if abs(q) >= 10 ** dv.precision:
        return None
    q = rescale_int(q, dv.scale, res.scale)
    return None if abs(q) >= 10 ** res.precision else q


# ---------------------------------------------------------------------------
# Arrow <-> unscaled lanes on the host
# ---------------------------------------------------------------------------

def arrow_to_unscaled(arr):
    """decimal128 / integral Arrow array -> (unscaled values, valid).
    int64 where every valid value fits the lane, else an object array of
    Python ints."""
    import pyarrow as pa
    if isinstance(arr, pa.ChunkedArray):
        arr = arr.combine_chunks()
    valid = ~np.asarray(arr.is_null()) if arr.null_count \
        else np.ones(len(arr), dtype=bool)
    if not pa.types.is_decimal(arr.type):
        vals = arr.fill_null(0).to_numpy(zero_copy_only=False) \
            if arr.null_count else arr.to_numpy(zero_copy_only=False)
        return vals.astype(np.int64), valid
    if not pa.types.is_decimal128(arr.type):
        arr = arr.cast(pa.decimal128(38, arr.type.scale))
    words = np.frombuffer(arr.buffers()[1], dtype=np.int64)
    lo = words[2 * arr.offset::2][:len(arr)]
    hi = words[2 * arr.offset + 1::2][:len(arr)]
    if (hi[valid] == (lo[valid] >> 63)).all():
        return np.where(valid, lo, 0), valid
    wide = np.array([((int(h) << 64) | (int(w) & ((1 << 64) - 1))) if ok
                     else 0 for w, h, ok in zip(lo, hi, valid)],
                    dtype=object)
    return wide, valid


def unscaled_to_arrow(vals, valid, dtype: DecimalType):
    """Unscaled values (int64 lanes, or Python ints in an object array)
    -> Arrow ``decimal128`` of the declared type: the 16-byte buffers are
    built by numpy (low word, sign word), no Python object per value."""
    import pyarrow as pa
    at = pa.decimal128(dtype.precision, dtype.scale)
    vals = np.asarray(vals)
    valid = np.asarray(valid, dtype=bool)
    n = len(vals)
    if vals.dtype == object:
        py = [decimal.Decimal(int(x)).scaleb(-dtype.scale) if ok else None
              for x, ok in zip(vals.tolist(), valid.tolist())]
        with decimal.localcontext() as c:
            c.prec = 2 * MAX_PRECISION
            return pa.array(py, type=at)
    words = np.empty((n, 2), dtype=np.int64)
    words[:, 0] = vals
    words[:, 1] = vals.astype(np.int64) >> 63
    bitmap = None if valid.all() else pa.py_buffer(
        np.packbits(valid, bitorder="little").tobytes())
    return pa.Array.from_buffers(at, n, [bitmap, pa.py_buffer(words.data)],
                                 null_count=int(n - valid.sum()))


# ---------------------------------------------------------------------------
# where the flags go
# ---------------------------------------------------------------------------

_TLS = threading.local()


class _Collector:
    """The overflow flags of the checked operations traced inside one
    kernel body (``bool[P]`` each) and how many operations that were."""

    def __init__(self):
        self.flags: List = []
        self.ops = 0

    def rows(self, xp):
        """int32 scalar: rows with any flag set (0 if nothing was
        checked)."""
        if not self.flags:
            return xp.zeros((), dtype=xp.int32)
        return xp.sum(any_flag(xp, *self.flags)).astype(xp.int32)


@contextlib.contextmanager
def collecting(enabled: bool = True):
    """Collect the flags that ``note_overflow`` gets while the body is
    traced (innermost collector wins). ``enabled`` false: nothing is
    collected and None is yielded (a kernel that checks no decimal)."""
    if not enabled:
        yield None
        return
    prev = getattr(_TLS, "collector", None)
    col = _TLS.collector = _Collector()
    try:
        yield col
    finally:
        _TLS.collector = prev


@contextlib.contextmanager
def masked(xp, keep):
    """Flags noted in the body count only for rows where ``keep()`` holds
    (a row a filter dropped BEFORE the operation is nobody's overflow).
    ``keep`` is called only if something was flagged; where no collector
    is open this traces nothing at all."""
    outer = getattr(_TLS, "collector", None)
    if outer is None:
        yield
        return
    inner = _TLS.collector = _Collector()
    try:
        yield
    finally:
        _TLS.collector = outer
        outer.ops += inner.ops
        if inner.flags:
            outer.flags.append(xp.logical_and(
                any_flag(xp, *inner.flags), keep()))


def checked_ops(e, schema) -> int:
    """How many checked decimal operations the tree ``e`` traces against
    ``schema`` (each node's ``decimal_checks``): what decides, before a
    kernel is built, whether it carries an overflow count."""
    if e is None:
        return 0
    try:
        n = int(e.decimal_checks(schema))
    except Exception:  # noqa: BLE001 - not typeable against this schema
        n = 0
    for c in getattr(e, "children", None) or ():
        if hasattr(c, "decimal_checks"):
            n += checked_ops(c, schema)
    return n


def checked_ops_of_stages(stages, schema):
    """(checked operations of a chain of fused ``("filter", cond)`` /
    ``("project", exprs, out_schema)`` stages over ``schema``, the schema
    the chain ends in)."""
    n = 0
    for st in stages:
        if st[0] == "filter":
            n += checked_ops(st[1], schema)
        else:
            n += sum(checked_ops(e, schema) for e in st[1])
            schema = st[2]
    return n, schema


def note_overflow(flag) -> None:
    """One checked operation's flag (already ANDed with its validity).
    Dropped where no collector is open: the lane is NULL all the same."""
    col = getattr(_TLS, "collector", None)
    if col is not None:
        col.ops += 1
        col.flags.append(flag)


def defer(count, ops: int) -> None:
    """A device scalar counting a kernel's overflowed rows, for the
    query's sink to fetch (kernels whose result is not fetched by their
    own operator: projections, fused stages, a flushed carry)."""
    pend = getattr(_TLS, "pending", None)
    if pend is None:
        pend = _TLS.pending = []
    pend.append((count, ops))


def clear_pending() -> None:
    _TLS.pending = []


def settle_pending() -> None:
    """The sink's end of ``defer``: ONE fetch of every deferred count of
    this query (none where nothing was deferred), the ``decimal.checked``
    counter, and the loud error."""
    pend = getattr(_TLS, "pending", None)
    if not pend:
        return
    _TLS.pending = []
    from ..columnar.transfer import traced_device_get
    counts = traced_device_get([c for c, _ in pend], "d2h.decimal")
    rows = int(sum(int(c) for c in counts))
    count_checked(sum(ops for _, ops in pend), rows)
    if rows:
        raise overflow_error(rows, "a projection or filter")


def count_checked(ops: int, overflow_rows: int) -> None:
    """Tracer counter ``decimal.checked``: checked decimal operations in
    the kernels of one execution and the rows they flagged
    (docs/profiling.md)."""
    from ..trace import core as trace_core
    tr = trace_core.TRACER
    if tr is not None:
        tr.counter("decimal.checked",
                   {"ops": ops, "overflow_rows": overflow_rows},
                   cat="exec")
