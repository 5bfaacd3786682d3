"""Aggregate functions (ref aggregate/aggregateFunctions.scala, 2,158 LoC;
GpuAggregateFunction trait aggregateBase.scala:79).

TPU-first design: groupby is segmented reduction, not hash tables (cudf's
hash groupby relies on device atomics, which have no XLA analog). Two
regimes, both scatter-free (columnar/segmented.py): dense one-hot
broadcast+reduce when the group-id space is small (dictionary-coded keys),
and sort + Hillis-Steele segmented scans for the general case — every
aggregate's seg_* call dispatches on the context it is handed. Each
aggregate declares:
  update   : per-row values  -> per-group partials      (first pass, per batch)
  merge    : per-group partials -> per-group partials   (combining batches or
             shuffle partitions — identical maths to the reference's
             GpuMergeAggregateIterator pass, GpuAggregateExec.scala:718)
  finalize : partials -> result column
Spark null semantics: sum/min/max/avg ignore nulls, empty group -> null;
count is never null. Float NaN: NaN is greatest for min/max.
"""
from __future__ import annotations

from typing import List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..types import (BOOL, DataType, DecimalType, FLOAT64, INT64, Schema,
                     TypeEnum, numeric, tpuNative)
from . import decimal_rules as D
from .base import DVal, Expression, Literal
from ..columnar.segmented import SortedSegments, seg_max, seg_min, seg_sum

__all__ = ["AggregateExpression", "Sum", "Count", "CountStar", "Min", "Max",
           "Average", "First", "Last", "StddevSamp", "StddevPop",
           "VarianceSamp", "VariancePop", "CollectList", "CollectSet",
           "MinBy", "MaxBy", "Percentile", "ApproximatePercentile"]


def _seg_sum(data, valid, gid, num_segments):
    masked = jnp.where(valid, data, jnp.zeros_like(data))
    s = seg_sum(masked, gid, num_segments=num_segments)
    cnt = seg_sum(valid.astype(jnp.int64), gid,
                              num_segments=num_segments)
    return s, cnt


def _seg_min(data, valid, gid, num_segments):
    if jnp.issubdtype(data.dtype, jnp.floating):
        big = jnp.array(jnp.inf, dtype=data.dtype)
        masked = jnp.where(valid & ~jnp.isnan(data), data, big)
        has_nan = seg_max(
            (valid & jnp.isnan(data)).astype(jnp.int32), gid,
            num_segments=num_segments) > 0
        non_nan_cnt = seg_sum(
            (valid & ~jnp.isnan(data)).astype(jnp.int64), gid,
            num_segments=num_segments)
        m = seg_min(masked, gid, num_segments=num_segments)
        # all-NaN group: min is NaN (NaN is greatest but it's all there is)
        m = jnp.where((non_nan_cnt == 0) & has_nan,
                      jnp.array(jnp.nan, dtype=data.dtype), m)
        cnt = seg_sum(valid.astype(jnp.int64), gid,
                                  num_segments=num_segments)
        return m, cnt
    info = jnp.iinfo(data.dtype) if jnp.issubdtype(data.dtype, jnp.integer) \
        else None
    big = jnp.array(info.max, dtype=data.dtype) if info is not None else True
    masked = jnp.where(valid, data, big)
    m = seg_min(masked, gid, num_segments=num_segments)
    cnt = seg_sum(valid.astype(jnp.int64), gid,
                              num_segments=num_segments)
    return m, cnt


def _seg_max(data, valid, gid, num_segments):
    if jnp.issubdtype(data.dtype, jnp.floating):
        small = jnp.array(-jnp.inf, dtype=data.dtype)
        masked = jnp.where(valid & ~jnp.isnan(data), data, small)
        has_nan = seg_max(
            (valid & jnp.isnan(data)).astype(jnp.int32), gid,
            num_segments=num_segments) > 0
        m = seg_max(masked, gid, num_segments=num_segments)
        # Spark: NaN is greatest, so any NaN -> max is NaN
        m = jnp.where(has_nan, jnp.array(jnp.nan, dtype=data.dtype), m)
        cnt = seg_sum(valid.astype(jnp.int64), gid,
                                  num_segments=num_segments)
        return m, cnt
    info = jnp.iinfo(data.dtype) if jnp.issubdtype(data.dtype, jnp.integer) \
        else None
    small = jnp.array(info.min, dtype=data.dtype) if info is not None else False
    masked = jnp.where(valid, data, small)
    m = seg_max(masked, gid, num_segments=num_segments)
    cnt = seg_sum(valid.astype(jnp.int64), gid,
                              num_segments=num_segments)
    return m, cnt


class AggregateExpression:
    """Base: not an Expression (cannot appear mid-row-expression); planner
    handles it in Aggregate nodes only (ref GpuAggregateExpression:219)."""

    #: DISTINCT modifier (agg(DISTINCT e)); the TPU path rewrites the plan
    #: into a two-level aggregation (plan/rewrites.py), the host aggregate
    #: dedups natively
    distinct: bool = False

    def __init__(self, child: Optional[Expression], name: Optional[str] = None):
        self.child = child
        self._name = name

    def as_distinct(self) -> "AggregateExpression":
        self.distinct = True
        return self

    # ---- naming / typing -------------------------------------------------
    @property
    def name_hint(self) -> str:
        if self._name:
            return self._name
        cn = self.child.name_hint if self.child is not None else "*"
        return f"{type(self).__name__.lower()}({cn})"

    def with_name(self, name: str) -> "AggregateExpression":
        self._name = name
        return self

    def data_type(self, schema: Schema) -> DataType:
        raise NotImplementedError

    def device_unsupported_reason(self, schema: Schema) -> Optional[str]:
        from .base import expression_disabled_reason
        r = expression_disabled_reason(type(self))
        if r:
            return r
        if self.child is None:
            return None
        r = self.child.fully_device_supported(schema)
        if r:
            return r
        dt = self.child.data_type(schema)
        if not dt.device_backed:
            return f"{self.name_hint}: input type {dt.name} is host-only"
        return None

    def decimal_checks(self, schema: Schema) -> int:
        """Checked decimal operations of this aggregate's ``finalize``
        (a total or an average that may leave the lane)."""
        return 0

    # ---- device pipeline -------------------------------------------------
    def input_exprs(self) -> List[Expression]:
        return [self.child] if self.child is not None else []

    def partial_types(self, schema: Schema) -> List[DataType]:
        raise NotImplementedError

    def update(self, vals: List[DVal], gid, num_segments, row_mask):
        """per-row DVals -> list of per-group (data, validity) partials."""
        raise NotImplementedError

    def merge(self, partials: List[DVal], gid, num_segments):
        raise NotImplementedError

    def finalize(self, partials: List[DVal]) -> DVal:
        raise NotImplementedError

    # ---- host (CPU fallback + oracle) -----------------------------------
    #: pandas groupby aggregation name used by the host aggregate exec
    pandas_agg: str = "?"

    def key(self) -> str:
        c = self.child.key() if self.child is not None else "*"
        d = "DISTINCT " if self.distinct else ""
        return f"{type(self).__name__}({d}{c})"


#: decimal SUM limb base: 3 limbs of 10^12 cover 36+ digits of running
#: total, and a <=2^20-row segment of limb values stays inside int64
_DEC_LIMB = 10 ** 12
_DEC_LIMB2 = _DEC_LIMB * _DEC_LIMB


def _divmod_est(x, k, rounds: int = 2):
    """Floor ``divmod(x, k)`` of int64 lanes, k > 0 (a Python int or
    lanes), WITHOUT a 64-bit division. This backend emulates int64, and
    one 64-bit division costs its compiler about a second
    (described-v5e compile, PR 28: the decimal ``tail`` with some 240 of
    them did not compile in 300 s) and its device a long emulated
    sequence per lane; float32 is native. Each round estimates what is
    left of the quotient in float32 (the remainder as a float32, times
    the constant's reciprocal or over the lanes' divisor, floored) and
    takes it off the remainder with an integer multiply and subtract
    (wrapping arithmetic: the true remainder fits the lane, so it comes
    out right); one whole step each way then finishes.

    A round's estimate is off by at most |what is left| x 2^-21 (the
    int64 -> float32 conversion, the constant, the multiply or divide:
    five float32 roundings, with room for a divide that is a few ulps
    off) plus the floor. So ``rounds=1`` is exact where |x / k| < 2^21
    (the estimate is then within one of the quotient), and ``rounds=2``
    where |x / k| < 2^42: every int64 x over a limb or a digit."""
    inv = jnp.float32(1.0 / k) if isinstance(k, int) else None
    q = jnp.zeros_like(x)
    r = x
    for _ in range(rounds):
        rf = r.astype(jnp.float32)
        est = jnp.floor(rf * inv if inv is not None
                        else rf / k.astype(jnp.float32)).astype(jnp.int64)
        q = q + est
        r = r - est * k
    low = r < 0
    q = jnp.where(low, q - 1, q)
    r = jnp.where(low, r + k, r)
    high = r >= k
    q = jnp.where(high, q + 1, q)
    r = jnp.where(high, r - k, r)
    return q, r


def _dec_normalize(l0, l1, l2):
    """Carry-propagate limb sums back into canonical form
    (l0, l1 in [0, base); sign carried by l2)."""
    c0, l0 = _divmod_est(l0, _DEC_LIMB)
    c1, l1 = _divmod_est(l1 + c0, _DEC_LIMB)
    return l0, l1, l2 + c1


def _dec_limb_sums(v: DVal, gid, num_segments):
    """Exact 128-bit-wide accumulation of a decimal column in 10^12-base
    limbs: every per-segment limb sum fits int64 (ref DecimalUtils JNI
    128-bit sums; TPU has no int128, limbs are the XLA shape). Returns the
    normalized limbs and the count of valid rows."""
    x = v.data.astype(jnp.int64)
    # up to 18 digits the quotient is below 10^6: one round; any int64
    # over 10^12 is below 2^24: two
    xd, x0 = _divmod_est(x, _DEC_LIMB,
                         1 if v.dtype.precision <= D.LANE_DIGITS else 2)
    # |xd| < 2^63 / 10^12 < 10^12: its own split is a compare
    neg = xd < 0
    l0, c = _seg_sum(x0, v.validity, gid, num_segments)
    l1, _ = _seg_sum(jnp.where(neg, xd + _DEC_LIMB, xd), v.validity, gid,
                     num_segments)
    l2, _ = _seg_sum(jnp.where(neg, -1, 0).astype(jnp.int64), v.validity,
                     gid, num_segments)
    return _dec_normalize(l0, l1, l2), c


def _dec_limb_merge(partials, gid, num_segments):
    sums = [_seg_sum(p.data, p.validity, gid, num_segments)[0]
            for p in partials]
    return _dec_normalize(*sums)


def _dec_total(l0, l1, l2):
    """(total, fits): the limbs as ONE int64 where the exact total fits
    the lane. The f64 magnitude test is exact to ~1e3 at the boundary,
    erring to "does not fit" inside the last few thousand ulps; the
    nested form keeps every constant and (when it fits) every
    intermediate inside int64."""
    est = (l2.astype(jnp.float64) * float(_DEC_LIMB2)
           + l1.astype(jnp.float64) * float(_DEC_LIMB)
           + l0.astype(jnp.float64))
    fits = jnp.abs(est) < 9.223372e18
    total = (l2 * _DEC_LIMB + l1) * _DEC_LIMB + l0
    return jnp.where(fits, total, 0), fits


_DIGIT = 10 ** 6


def _dec_collapse(digits):
    """Base-10^6 digits (most significant first) as ONE int64, and the
    flag of the lanes it does not fit."""
    acc = jnp.zeros_like(digits[0])
    over = jnp.zeros(digits[0].shape, jnp.bool_)
    for d in digits:
        over = jnp.logical_or(over, acc > (D.INT64_MAX - _DIGIT) // _DIGIT)
        acc = jnp.where(over, 0, acc) * _DIGIT + d
    return acc, over


def _dec_average(l0, l1, l2, count, in_type: DecimalType):
    """avg over ``in_type`` from the limb total and the count, on int64
    lanes, as Spark evaluates it: ``sum / cast(count as decimal(20,0))``
    HALF_UP at the divide's own scale, then cast HALF_UP to the
    average's (two roundings; exprs/decimal_rules.py:avg_types). Neither
    the numerator (total x 10^k, up to ~40 digits) nor the first quotient
    (Q1's avg_price is 3.8e19 at the divide's scale 15) is ever formed:
    schoolbook long division over base-10^6 digits keeps ``remainder x
    10^6 + digit`` inside int64 for any count below 2^42, the first
    rounding is a carry through the quotient's digits, and the second
    splits them at the average's scale. Every quotient on the way is a
    digit, so no 64-bit division is traced (_divmod_est). Returns
    (value, overflow flag): the flag where the count or the final
    quotient leaves the lane."""
    st, dv, res = D.avg_types(in_type)
    k = dv.scale - st.scale
    B = _DEC_LIMB
    # the magnitude, limb by limb (l0, l1 in [0, B): a borrow, no division)
    neg = l2 < 0
    b0 = l0 > 0
    t1 = l1 + b0
    a0 = jnp.where(neg, jnp.where(b0, B - l0, 0), l0)
    a1 = jnp.where(neg, jnp.where(t1 > 0, B - t1, 0), l1)
    a2 = jnp.where(neg, -l2 - (t1 > 0), l2)
    # its digits, most significant first (a2 < 2^63 < 10^24)
    d7, rest = _divmod_est(a2, _DIGIT ** 3)
    d6, rest = _divmod_est(rest, _DIGIT ** 2)
    d5, d4 = _divmod_est(rest, _DIGIT)
    d3, d2 = _divmod_est(a1, _DIGIT)
    d1, d0 = _divmod_est(a0, _DIGIT)
    digits = [d7, d6, d5, d4, d3, d2, d1, d0]
    k6, kr = divmod(max(k, 0), 6)
    if kr:
        carry = jnp.zeros_like(a0)
        scaled = []
        for d in reversed(digits):
            carry, low = _divmod_est(d * 10 ** kr + carry, _DIGIT)
            scaled.insert(0, low)
        digits = [carry] + scaled
    digits = digits + [jnp.zeros_like(a0)] * k6
    c = jnp.where(count > 0, count, 1) * 10 ** max(-k, 0)
    over = c >= 1 << 42
    c = jnp.where(over, 1, c)
    quot = []
    r = jnp.zeros_like(a0)
    for d in digits:
        q, r = _divmod_est(r * _DIGIT + d, c)
        quot.append(q)
    # HALF_UP at the divide's scale: one more in the last digit, carried
    carry = 2 * r >= c
    for i in reversed(range(len(quot))):
        t = quot[i] + carry
        carry = t >= _DIGIT
        quot[i] = jnp.where(carry, t - _DIGIT, t)
    quot = [carry.astype(jnp.int64)] + quot
    m = dv.scale - res.scale
    if m < 0:
        q, o1 = _dec_collapse(quot)
        q, o2 = D.scale_up(jnp, q, -m)
        return jnp.where(neg, -q, q), D.any_flag(jnp, over, o1, o2)
    # HALF_UP again at the average's scale: the digits split at 10^m
    # (m <= 17: the dropped part fits a lane); the kept part is formed
    # at ITS scale, never at the divide's
    m6, mr = divmod(m, 6)
    cut = len(quot) - m6 - 1            # the digit the split runs through
    high, o1 = _dec_collapse(quot[:cut])
    high, o2 = D.scale_up(jnp, high, 6 - mr)
    o3 = high > D.INT64_MAX - 2 * _DIGIT
    kept, low = _divmod_est(quot[cut], 10 ** mr)
    for d in quot[cut + 1:]:
        low = low * _DIGIT + d
    q = jnp.where(o3, 0, high) + kept + (2 * low >= 10 ** m)
    return jnp.where(neg, -q, q), D.any_flag(jnp, over, o1, o2, o3)


class Sum(AggregateExpression):
    pandas_agg = "sum"
    device_type_sig = tpuNative.with_psnote(
        TypeEnum.DECIMAL,
        "summed exactly in three 10^12-base limbs; a total whose |unscaled "
        "value| >= 2^63 raises DecimalOverflow on the device (lanes are "
        "int64; Spark non-ANSI holds up to 38 digits, and so does the "
        "host engine)")

    def data_type(self, schema):
        dt = self.child.data_type(schema)
        if dt.name in ("tinyint", "smallint", "int", "bigint"):
            return INT64
        if isinstance(dt, DecimalType):
            # Spark: sum(decimal(p,s)) -> decimal(min(p+10, 38), s). The
            # limb accumulation is exact; only the finalized total has to
            # fit a lane. One that does not is NULL in its lane AND
            # flagged (exprs/decimal_rules.py), which the aggregate's own
            # fetch turns into the loud error, as ingest does
            # (columnar/batch.py).
            return D.sum_type(dt)
        return FLOAT64 if dt.name in ("float", "double") else dt

    def _is_decimal(self, schema) -> bool:
        return isinstance(self.child.data_type(schema), DecimalType)

    def decimal_checks(self, schema):
        return int(self._is_decimal(schema))

    def partial_types(self, schema):
        if self._is_decimal(schema):
            return [INT64, INT64, INT64]
        return [self.data_type(schema)]

    def update(self, vals, gid, num_segments, row_mask):
        v = vals[0]
        if isinstance(v.dtype, DecimalType):
            (l0, l1, l2), c = _dec_limb_sums(v, gid, num_segments)
            ok = c > 0
            return [(l0, ok), (l1, ok), (l2, ok)]
        # promote to the accumulator type before summing
        acc_dt = jnp.int64 if jnp.issubdtype(v.data.dtype, jnp.integer) \
            else jnp.float64
        s, cnt = _seg_sum(v.data.astype(acc_dt), v.validity, gid, num_segments)
        return [(s, cnt > 0)]

    def merge(self, partials, gid, num_segments):
        if len(partials) == 3:         # decimal limbs
            l0, l1, l2 = _dec_limb_merge(partials, gid, num_segments)
            ok = seg_sum(partials[0].validity.astype(jnp.int64), gid,
                         num_segments=num_segments) > 0
            return [(l0, ok), (l1, ok), (l2, ok)]
        p = partials[0]
        s, cnt = _seg_sum(p.data, p.validity, gid, num_segments)
        return [(s, cnt > 0)]

    def finalize(self, partials):
        if len(partials) == 3:
            l0, l1, l2 = (p.data for p in partials)
            ok = partials[0].validity
            # representable on device iff the exact total fits int64; a
            # total that does not is NULL in its lane and flagged
            total, fits = _dec_total(l0, l1, l2)
            D.note_overflow(jnp.logical_and(ok, jnp.logical_not(fits)))
            return DVal(total, jnp.logical_and(ok, fits), INT64)
        return partials[0]


class Count(AggregateExpression):
    pandas_agg = "count"

    def data_type(self, schema):
        return INT64

    def partial_types(self, schema):
        return [INT64]

    def update(self, vals, gid, num_segments, row_mask):
        v = vals[0]
        cnt = seg_sum(v.validity.astype(jnp.int64), gid,
                                  num_segments=num_segments)
        return [(cnt, jnp.ones_like(cnt, dtype=jnp.bool_))]

    def merge(self, partials, gid, num_segments):
        p = partials[0]
        s, _ = _seg_sum(p.data, p.validity, gid, num_segments)
        return [(s, jnp.ones_like(s, dtype=jnp.bool_))]

    def finalize(self, partials):
        p = partials[0]
        # count is never null: empty merge slots become 0
        return DVal(jnp.where(p.validity, p.data, jnp.zeros_like(p.data)),
                    jnp.ones_like(p.validity), INT64)


class CountStar(Count):
    def __init__(self, name: Optional[str] = None):
        super().__init__(None, name)

    @property
    def name_hint(self):
        return self._name or "count(1)"

    def input_exprs(self):
        return [Literal(1)]

    def update(self, vals, gid, num_segments, row_mask):
        ones = row_mask.astype(jnp.int64)
        cnt = seg_sum(ones, gid, num_segments=num_segments)
        return [(cnt, jnp.ones_like(cnt, dtype=jnp.bool_))]


class Min(AggregateExpression):
    pandas_agg = "min"

    def data_type(self, schema):
        return self.child.data_type(schema)

    def partial_types(self, schema):
        return [self.data_type(schema)]

    def update(self, vals, gid, num_segments, row_mask):
        v = vals[0]
        m, cnt = _seg_min(v.data, v.validity, gid, num_segments)
        return [(m, cnt > 0)]

    def merge(self, partials, gid, num_segments):
        p = partials[0]
        m, cnt = _seg_min(p.data, p.validity, gid, num_segments)
        return [(m, cnt > 0)]

    def finalize(self, partials):
        return partials[0]


class Max(AggregateExpression):
    pandas_agg = "max"

    def data_type(self, schema):
        return self.child.data_type(schema)

    def partial_types(self, schema):
        return [self.data_type(schema)]

    def update(self, vals, gid, num_segments, row_mask):
        v = vals[0]
        m, cnt = _seg_max(v.data, v.validity, gid, num_segments)
        return [(m, cnt > 0)]

    def merge(self, partials, gid, num_segments):
        p = partials[0]
        m, cnt = _seg_max(p.data, p.validity, gid, num_segments)
        return [(m, cnt > 0)]

    def finalize(self, partials):
        return partials[0]


class Average(AggregateExpression):
    """avg: double for every input but a decimal; avg(decimal(p,s)) is
    Spark's decimal(p+4, s+4): the sum in three limbs beside the count,
    divided and rounded HALF_UP on the device (_dec_average)."""
    pandas_agg = "mean"

    def _decimal_in(self, schema):
        dt = self.child.data_type(schema)
        #: finalize sees partials only: the input type is kept from the
        #: typing calls every exec makes before it builds a kernel (same
        #: key -> same type, so a cached kernel's copy agrees)
        self._dec_in = dt if isinstance(dt, DecimalType) else None
        return self._dec_in

    def data_type(self, schema):
        dt = self._decimal_in(schema)
        return FLOAT64 if dt is None else D.avg_types(dt)[2]

    def decimal_checks(self, schema):
        return int(self._decimal_in(schema) is not None)

    def partial_types(self, schema):
        if self._decimal_in(schema) is not None:
            return [INT64, INT64, INT64, INT64]  # three limbs, count
        return [FLOAT64, INT64]  # sum, count

    def update(self, vals, gid, num_segments, row_mask):
        v = vals[0]
        if isinstance(v.dtype, DecimalType):
            (l0, l1, l2), c = _dec_limb_sums(v, gid, num_segments)
            ok = c > 0
            return [(l0, ok), (l1, ok), (l2, ok), (c, jnp.ones_like(ok))]
        s, cnt = _seg_sum(v.data.astype(jnp.float64), v.validity, gid,
                          num_segments)
        ok = cnt > 0
        return [(s, ok), (cnt, jnp.ones_like(ok))]

    def merge(self, partials, gid, num_segments):
        if len(partials) == 4:         # decimal limbs + count
            l0, l1, l2 = _dec_limb_merge(partials[:3], gid, num_segments)
            c, _ = _seg_sum(partials[3].data, partials[3].validity, gid,
                            num_segments)
            ok = c > 0
            return [(l0, ok), (l1, ok), (l2, ok),
                    (c, jnp.ones_like(c, dtype=jnp.bool_))]
        s, _ = _seg_sum(partials[0].data, partials[0].validity, gid,
                        num_segments)
        c, _ = _seg_sum(partials[1].data, partials[1].validity, gid,
                        num_segments)
        return [(s, c > 0), (c, jnp.ones_like(c, dtype=jnp.bool_))]

    def finalize(self, partials):
        if len(partials) == 4:
            l0, l1, l2, c = (p.data for p in partials)
            ok = jnp.logical_and(partials[0].validity, c > 0)
            q, over = _dec_average(l0, l1, l2, c, self._dec_in)
            over = jnp.logical_and(over, ok)
            D.note_overflow(over)
            ok = jnp.logical_and(ok, jnp.logical_not(over))
            return DVal(jnp.where(ok, q, 0), ok, INT64)
        s, c = partials[0], partials[1]
        ok = jnp.logical_and(s.validity, c.data > 0)
        denom = jnp.where(c.data > 0, c.data, jnp.ones_like(c.data))
        return DVal(s.data / denom.astype(jnp.float64), ok, FLOAT64)


class First(AggregateExpression):
    """first(x, ignoreNulls=True) — within-batch order; cross-batch order
    follows batch arrival like the reference's first agg."""
    pandas_agg = "first"

    def data_type(self, schema):
        return self.child.data_type(schema)

    def partial_types(self, schema):
        return [self.data_type(schema), INT64]  # value, first-row-index

    def update(self, vals, gid, num_segments, row_mask):
        v = vals[0]
        n = v.data.shape[0]
        big = jnp.array(np.iinfo(np.int64).max, dtype=jnp.int64)
        if isinstance(gid, SortedSegments):
            idx = gid.orig_index.astype(jnp.int64)
            (val,), fi, ok = gid.select_by_rank([v.data], idx, v.validity,
                                                "min")
            return [(val, ok), (jnp.where(ok, fi, big), jnp.ones_like(ok))]
        idx = jnp.arange(n, dtype=jnp.int64)
        first_idx = seg_min(jnp.where(v.validity, idx, big), gid,
                                        num_segments=num_segments)
        ok = first_idx < big
        safe = jnp.where(ok, first_idx, 0)
        val = jnp.take(v.data, safe, mode="clip")
        return [(val, ok), (jnp.where(ok, first_idx, big), jnp.ones_like(ok))]

    def merge(self, partials, gid, num_segments):
        val, pos = partials[0], partials[1]
        big = jnp.array(np.iinfo(np.int64).max, dtype=jnp.int64)
        eff = jnp.where(val.validity, pos.data, big)
        if isinstance(gid, SortedSegments):
            (out,), fp, ok = gid.select_by_rank([val.data], eff,
                                                val.validity, "min")
            return [(out, ok), (jnp.where(ok, fp, big), jnp.ones_like(ok))]
        first_pos = seg_min(eff, gid, num_segments=num_segments)
        ok = first_pos < big
        # gather the value whose pos equals first_pos within the segment
        is_first = jnp.logical_and(eff == jnp.take(first_pos, gid, mode="clip"),
                                   val.validity)
        out = jnp.zeros((num_segments,), dtype=val.data.dtype) \
            .at[jnp.where(is_first, gid, num_segments)] \
            .set(val.data, mode="drop")
        return [(out, ok), (jnp.where(ok, first_pos, big), jnp.ones_like(ok))]

    def finalize(self, partials):
        return partials[0]


class Last(AggregateExpression):
    pandas_agg = "last"

    def data_type(self, schema):
        return self.child.data_type(schema)

    def partial_types(self, schema):
        return [self.data_type(schema), INT64]

    def update(self, vals, gid, num_segments, row_mask):
        v = vals[0]
        n = v.data.shape[0]
        small = jnp.array(-1, dtype=jnp.int64)
        if isinstance(gid, SortedSegments):
            idx = gid.orig_index.astype(jnp.int64)
            (val,), li, ok = gid.select_by_rank([v.data], idx, v.validity,
                                                "max")
            return [(val, ok), (jnp.where(ok, li, small),
                                jnp.ones_like(ok))]
        idx = jnp.arange(n, dtype=jnp.int64)
        last_idx = seg_max(jnp.where(v.validity, idx, small), gid,
                                       num_segments=num_segments)
        ok = last_idx >= 0
        safe = jnp.where(ok, last_idx, 0)
        val = jnp.take(v.data, safe, mode="clip")
        return [(val, ok), (jnp.where(ok, last_idx, small), jnp.ones_like(ok))]

    def merge(self, partials, gid, num_segments):
        val, pos = partials[0], partials[1]
        small = jnp.array(-1, dtype=jnp.int64)
        eff = jnp.where(val.validity, pos.data, small)
        if isinstance(gid, SortedSegments):
            (out,), lp, ok = gid.select_by_rank([val.data], eff,
                                                val.validity, "max")
            return [(out, ok), (jnp.where(ok, lp, small),
                                jnp.ones_like(ok))]
        last_pos = seg_max(eff, gid, num_segments=num_segments)
        ok = last_pos >= 0
        is_last = jnp.logical_and(eff == jnp.take(last_pos, gid, mode="clip"),
                                  val.validity)
        out = jnp.zeros((num_segments,), dtype=val.data.dtype) \
            .at[jnp.where(is_last, gid, num_segments)] \
            .set(val.data, mode="drop")
        return [(out, ok), (jnp.where(ok, last_pos, small), jnp.ones_like(ok))]

    def finalize(self, partials):
        return partials[0]


class _MomentAgg(AggregateExpression):
    """Shared machinery for variance/stddev: partials (count, sum, sum_sq)."""
    ddof = 1

    def data_type(self, schema):
        return FLOAT64

    def partial_types(self, schema):
        return [INT64, FLOAT64, FLOAT64]

    def update(self, vals, gid, num_segments, row_mask):
        v = vals[0]
        d = v.data.astype(jnp.float64)
        s, cnt = _seg_sum(d, v.validity, gid, num_segments)
        s2, _ = _seg_sum(d * d, v.validity, gid, num_segments)
        ones = jnp.ones_like(cnt, dtype=jnp.bool_)
        return [(cnt, ones), (s, ones), (s2, ones)]

    def merge(self, partials, gid, num_segments):
        outs = []
        for p in partials:
            s, _ = _seg_sum(p.data, p.validity, gid, num_segments)
            outs.append((s, jnp.ones_like(s, dtype=jnp.bool_)))
        return outs

    def _moments(self, partials):
        n = partials[0].data.astype(jnp.float64)
        s = partials[1].data
        s2 = partials[2].data
        denom = jnp.where(n > 0, n, 1.0)
        mean = s / denom
        m2 = s2 - n * mean * mean
        return n, m2


class VariancePop(_MomentAgg):
    pandas_agg = "var_pop"
    ddof = 0

    def finalize(self, partials):
        n, m2 = self._moments(partials)
        ok = n > 0
        out = m2 / jnp.where(ok, n, 1.0)
        return DVal(jnp.maximum(out, 0.0), ok, FLOAT64)


class VarianceSamp(_MomentAgg):
    pandas_agg = "var"

    def finalize(self, partials):
        n, m2 = self._moments(partials)
        ok = n > 1
        out = m2 / jnp.where(ok, n - 1.0, 1.0)
        # n <= 1 -> NULL (Spark 3.1+ divide-by-zero semantics,
        # SPARK-33726; the legacy NaN behavior is gone)
        return DVal(jnp.maximum(out, 0.0), ok, FLOAT64)


class StddevPop(VariancePop):
    pandas_agg = "std_pop"

    def finalize(self, partials):
        v = super().finalize(partials)
        return DVal(jnp.sqrt(v.data), v.validity, FLOAT64)


class StddevSamp(VarianceSamp):
    pandas_agg = "std"

    def finalize(self, partials):
        # reuse the sample-variance finalize (incl. its FP-cancellation
        # clamp to >= 0 — sqrt of a tiny negative m2 would be NaN)
        v = VarianceSamp.finalize(self, partials)
        return DVal(jnp.sqrt(v.data), v.validity, FLOAT64)


class _HostOnlyAgg(AggregateExpression):
    """Aggregates without a device update/merge pipeline: the planner
    reverts the whole aggregation to the CPU twin, whose per-group
    evaluation lives in exec/aggregate.CpuAggregateExec (honest whole-exec
    fallback, ref the reference's TypeSig rejections)."""

    def device_unsupported_reason(self, schema: Schema) -> Optional[str]:
        from .base import expression_disabled_reason
        return (expression_disabled_reason(type(self))
                or f"{type(self).__name__} evaluates on host")


class CollectList(_HostOnlyAgg):
    """collect_list(e): non-null values per group in arrival order
    (ref GpuCollectList in aggregateFunctions.scala)."""

    def data_type(self, schema: Schema):
        from ..types import ArrayType
        return ArrayType(self.child.data_type(schema))

    def nullable(self, schema):
        return False


class CollectSet(CollectList):
    """collect_set(e): distinct non-null values (ref GpuCollectSet)."""


class MinBy(_HostOnlyAgg):
    """min_by(value, ordering) (ref GpuMinBy)."""

    _pick_min = True

    def __init__(self, child, ordering, name=None):
        super().__init__(child, name)
        self.ordering = ordering

    def data_type(self, schema: Schema):
        return self.child.data_type(schema)

    def input_exprs(self):
        return [self.child, self.ordering]

    def key(self):
        return (f"{type(self).__name__}({self.child.key()},"
                f"{self.ordering.key()})")


class MaxBy(MinBy):
    _pick_min = False


class Percentile(_HostOnlyAgg):
    """percentile(e, p): exact percentile with linear interpolation
    between closest ranks (Spark's Percentile; ref GpuPercentileDefault)."""

    def __init__(self, child, percentage: float, name=None):
        super().__init__(child, name)
        self.percentage = float(percentage)

    def data_type(self, schema: Schema):
        from ..types import FLOAT64
        return FLOAT64

    def key(self):
        return f"percentile({self.child.key()},{self.percentage})"

class ApproximatePercentile(Percentile):
    """approx_percentile(e, p[, accuracy]): Spark's t-digest sketch is
    an ACCURACY/memory trade; this engine computes the EXACT percentile
    instead (a strictly tighter answer — the accuracy argument is
    accepted and ignored). Ref GpuApproximatePercentile /
    ApproxPercentileFromTDigestExpr."""

    def __init__(self, child, percentage: float, accuracy: int = 10000,
                 name=None):
        super().__init__(child, percentage, name)
        self.accuracy = int(accuracy)

