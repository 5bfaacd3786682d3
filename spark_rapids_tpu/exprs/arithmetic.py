"""Arithmetic expressions with Spark semantics.

Reference analog: sql-plugin arithmetic.scala (GpuAdd, GpuSubtract, ...,
1,282 LoC). Spark (non-ANSI) semantics implemented:
  * division / modulo by zero -> NULL (not inf/exception)
  * `/` always produces double for integral inputs; `div` is integral division
  * `%` takes the sign of the dividend (Java remainder)
Device path is traced jax.numpy (fused by XLA); host path is masked numpy.
"""
from __future__ import annotations

import numpy as np
import jax.numpy as jnp

from ..types import (BOOL, DataType, DecimalType, FLOAT32, FLOAT64, INT64,
                     Schema, integral, numeric, TypeSig)
from . import decimal_rules as D
from .base import DVal, EvalContext, Expression, null_and, promote_types

__all__ = ["Add", "Subtract", "Multiply", "Divide", "IntegralDivide",
           "Remainder", "Pmod", "UnaryMinus", "UnaryPositive", "Abs",
           "BitwiseAnd", "BitwiseOr", "BitwiseXor", "BitwiseNot",
           "ShiftLeft", "ShiftRight", "ShiftRightUnsigned",
           "host_binary_numpy", "arrow_to_masked_numpy",
           "masked_numpy_to_arrow"]


def arrow_to_masked_numpy(arr):
    """pyarrow.Array -> (values ndarray, valid bool ndarray). A decimal
    array comes back as its UNSCALED values (int64 lanes; Python ints in
    an object array where a value does not fit one)."""
    import pyarrow as pa
    if pa.types.is_decimal(arr.type):
        return D.arrow_to_unscaled(arr)
    valid = ~np.asarray(arr.is_null())
    if arr.null_count:
        if pa.types.is_boolean(arr.type):
            fill = False
        elif pa.types.is_string(arr.type) or pa.types.is_large_string(
                arr.type):
            fill = ""
        elif pa.types.is_binary(arr.type):
            fill = b""
        else:
            fill = 0
        vals = arr.fill_null(fill).to_numpy(zero_copy_only=False)
    else:
        vals = arr.to_numpy(zero_copy_only=False)
    return vals, valid


def masked_numpy_to_arrow(vals, valid, dtype: DataType):
    import pyarrow as pa
    from ..types import to_arrow
    vals = np.asarray(vals)
    if isinstance(dtype, DecimalType):
        return D.unscaled_to_arrow(vals, valid, dtype)
    if dtype.np_dtype is not None and vals.dtype != dtype.np_dtype:
        vals = vals.astype(dtype.np_dtype)
    return pa.Array.from_pandas(vals, mask=~np.asarray(valid), type=to_arrow(dtype))


def decimal_as_double(xp, data, dtype: DataType):
    """A decimal operand's lanes as doubles (a double beside a decimal
    makes the operation a double one); other lanes as they are."""
    if isinstance(dtype, DecimalType):
        return data.astype(xp.float64) / float(10 ** dtype.scale)
    return data


def host_binary_numpy(expr, batch, fn, out_dtype: DataType,
                      cast_to=None, null_on_zero_rhs=False):
    l, lv = arrow_to_masked_numpy(expr.children[0].eval_host(batch))
    r, rv = arrow_to_masked_numpy(expr.children[1].eval_host(batch))
    l = decimal_as_double(np, l, expr.children[0].data_type(batch.schema))
    r = decimal_as_double(np, r, expr.children[1].data_type(batch.schema))
    if cast_to is not None:
        l = l.astype(cast_to)
        r = r.astype(cast_to)
    valid = lv & rv
    if null_on_zero_rhs:
        valid = valid & (r != 0)
        r = np.where(r == 0, np.ones_like(r), r)
    with np.errstate(all="ignore"):
        vals = fn(l, r)
    return masked_numpy_to_arrow(vals, valid, out_dtype)


class BinaryArithmetic(Expression):
    """Binary arithmetic. Over decimals (a decimal beside a decimal or an
    integer) the result type is Spark's for the operator and the value is
    computed on the unscaled int64 lanes by exprs/decimal_rules.py:
    exact while it fits 63 bits, and a row that does not is flagged and
    NULL, never wrapped. A double beside a decimal makes it a double
    operation."""
    device_type_sig: TypeSig = TypeSig(numeric.types)
    symbol = "?"
    #: an integer or SQL-decimal literal beside a decimal operand becomes
    #: a decimal literal (exprs/base.py:coerce_decimal_literals)
    decimal_literal_operands = True
    #: (result type of two decimal operands, values from two lanes) for
    #: the operators that have a decimal form on the lanes
    decimal_type = None
    decimal_values = None

    def __init__(self, left: Expression, right: Expression):
        self.children = [left, right]

    def _decimal_types_of(self, lt: DataType, rt: DataType):
        if not (isinstance(lt, DecimalType) or isinstance(rt, DecimalType)):
            return None
        l, r = D.operand_type(lt), D.operand_type(rt)
        if l is None or r is None or type(self).decimal_type is None:
            return None
        return l, r, type(self).decimal_type(l, r)

    def decimal_types(self, schema: Schema):
        """(left, right, result) when this is decimal arithmetic: both
        operands decimal or integral and at least one decimal."""
        return self._decimal_types_of(self.children[0].data_type(schema),
                                      self.children[1].data_type(schema))

    def data_type(self, schema: Schema) -> DataType:
        # each child typed ONCE: typing is recursive, and a second call a
        # level would double the work with every level of nesting
        lt = self.children[0].data_type(schema)
        rt = self.children[1].data_type(schema)
        dec = self._decimal_types_of(lt, rt)
        return dec[2] if dec is not None else promote_types(lt, rt)

    def device_unsupported_reason(self, schema):
        if self.decimal_types(schema) is not None \
                and type(self).decimal_values is None:
            return (f"{type(self).__name__}: decimal {self.symbol} needs "
                    f"more than the 63 bits of a device lane (host)")
        return super().device_unsupported_reason(schema)

    def _promoted_device_operands(self, ctx: EvalContext):
        dt = self.data_type(ctx.schema)
        l = self.children[0].eval_device(ctx)
        r = self.children[1].eval_device(ctx)
        np_dt = dt.np_dtype
        ld = decimal_as_double(jnp, l.data, l.dtype)
        rd = decimal_as_double(jnp, r.data, r.dtype)
        ld = ld.astype(np_dt) if ld.dtype != np_dt else ld
        rd = rd.astype(np_dt) if rd.dtype != np_dt else rd
        return ld, rd, null_and(l.validity, r.validity), dt

    def _eval_decimal_device(self, ctx: EvalContext, dec):
        l = self.children[0].eval_device(ctx)
        r = self.children[1].eval_device(ctx)
        valid = null_and(l.validity, r.validity)
        out, over = type(self).decimal_values(
            jnp, l.data.astype(jnp.int64), r.data.astype(jnp.int64), *dec)
        if over is not None:
            over = jnp.logical_and(over, valid)
            D.note_overflow(over)
            valid = jnp.logical_and(valid, jnp.logical_not(over))
            out = jnp.where(over, 0, out)
        return DVal(out, valid, dec[2])

    def _eval_decimal_host(self, batch, dec, wide_values):
        """The host twin: the same arithmetic in numpy on int64 lanes,
        and the rows (or operands) they cannot hold again on Python ints,
        which is exact: NULL only where Spark's decimal(38) overflows."""
        x, xv = arrow_to_masked_numpy(self.children[0].eval_host(batch))
        y, yv = arrow_to_masked_numpy(self.children[1].eval_host(batch))
        valid = xv & yv
        fast = type(self).decimal_values
        if fast is not None and x.dtype != object and y.dtype != object:
            out, over = fast(np, x.astype(np.int64), y.astype(np.int64),
                             *dec)
            if over is None or not (over & valid).any():
                return masked_numpy_to_arrow(out, valid, dec[2])
        out, null = wide_values(x.astype(object), y.astype(object), *dec)
        if null is not None:
            valid = valid & ~np.asarray(null, dtype=bool)
        out = np.where(valid, out, 0)
        big = np.asarray(abs(out) >= 10 ** dec[2].precision, dtype=bool)
        valid = valid & ~big
        return masked_numpy_to_arrow(_narrow(np.where(valid, out, 0)),
                                     valid, dec[2])

    def key(self):
        return f"{type(self).__name__}({self.children[0].key()},{self.children[1].key()})"

    @property
    def name_hint(self):
        return (f"({self.children[0].name_hint} {self.symbol} "
                f"{self.children[1].name_hint})")


def _narrow(vals):
    """An object array of Python ints as int64 lanes where all fit."""
    if vals.dtype == object and all(
            -(1 << 63) <= int(v) < (1 << 63) for v in vals.tolist()):
        return vals.astype(np.int64)
    return vals


def _wide(fn, **kw):
    """A decimal_rules value function as the host's exact form."""
    def run(x, y, l, r, res):
        out, _ = fn(np, x, y, l, r, res, wide=True, **kw)
        return out, None
    return run


class _DecimalLanes(BinaryArithmetic):
    """Add / Subtract / Multiply: a decimal form on the lanes."""
    np_fn = None

    def decimal_checks(self, schema):
        dec = self.decimal_types(schema)
        if dec is None:
            return 0
        l, r, _ = dec
        digits = (l.precision + r.precision + 1
                  if type(self).decimal_type is D.multiply_type
                  else D.add_precision(l, r))
        return int(digits > D.LANE_DIGITS)

    def eval_device(self, ctx):
        dec = self.decimal_types(ctx.schema)
        if dec is not None:
            return self._eval_decimal_device(ctx, dec)
        ld, rd, v, dt = self._promoted_device_operands(ctx)
        return DVal(type(self).np_fn(ld, rd), v, dt)

    def eval_host(self, batch):
        dec = self.decimal_types(batch.schema)
        if dec is not None:
            return self._eval_decimal_host(
                batch, dec, _wide(type(self).decimal_values))
        return host_binary_numpy(self, batch, type(self).np_fn,
                                 self.data_type(batch.schema))


class Add(_DecimalLanes):
    symbol = "+"
    np_fn = staticmethod(lambda l, r: l + r)
    decimal_type = staticmethod(D.add_type)
    decimal_values = staticmethod(D.add_values)


def _subtract_values(xp, x, y, l, r, res, wide=False):
    return D.add_values(xp, x, y, l, r, res, wide=wide, subtract=True)


class Subtract(_DecimalLanes):
    symbol = "-"
    np_fn = staticmethod(lambda l, r: l - r)
    decimal_type = staticmethod(D.add_type)
    decimal_values = staticmethod(_subtract_values)


class Multiply(_DecimalLanes):
    symbol = "*"
    np_fn = staticmethod(lambda l, r: l * r)
    decimal_type = staticmethod(D.multiply_type)
    decimal_values = staticmethod(D.multiply_values)


def _divide_wide(x, y, l, r, res):
    """Spark's decimal divide on Python ints: HALF_UP at the result's
    scale, a zero divisor NULL."""
    k = res.scale - l.scale + r.scale
    zero = np.asarray(y == 0, dtype=bool)
    y = np.where(zero, 1, y)
    num = x * 10 ** max(k, 0)
    den = abs(y) * 10 ** max(-k, 0)
    q = D.div_half_up(np, num, den)
    return np.where(np.asarray(y < 0, dtype=bool), -q, q), zero


def _remainder_wide(x, y, l, r, res):
    """Java's remainder (the dividend's sign) at the wider scale."""
    s = max(l.scale, r.scale)
    x, y = x * 10 ** (s - l.scale), y * 10 ** (s - r.scale)
    zero = np.asarray(y == 0, dtype=bool)
    y = np.where(zero, 1, y)
    m = abs(x) % abs(y)
    return np.where(np.asarray(x < 0, dtype=bool), -m, m), zero


class Divide(BinaryArithmetic):
    """Spark `/`: result is double for non-decimal inputs; 0 divisor -> NULL
    (ref arithmetic.scala GpuDivide)."""
    symbol = "/"
    decimal_type = staticmethod(D.divide_type)

    def data_type(self, schema: Schema) -> DataType:
        base = super().data_type(schema)
        if isinstance(base, DecimalType):
            return base
        return FLOAT32 if base == FLOAT32 else FLOAT64

    def eval_device(self, ctx):
        dt = self.data_type(ctx.schema)
        l = self.children[0].eval_device(ctx)
        r = self.children[1].eval_device(ctx)
        ld = decimal_as_double(jnp, l.data, l.dtype).astype(dt.np_dtype)
        rd = decimal_as_double(jnp, r.data, r.dtype).astype(dt.np_dtype)
        zero = rd == 0
        v = null_and(l.validity, r.validity, jnp.logical_not(zero))
        safe = jnp.where(zero, jnp.ones_like(rd), rd)
        return DVal(ld / safe, v, dt)

    def eval_host(self, batch):
        dec = self.decimal_types(batch.schema)
        if dec is not None:
            return self._eval_decimal_host(batch, dec, _divide_wide)
        dt = self.data_type(batch.schema)
        return host_binary_numpy(self, batch, np.divide, dt,
                                 cast_to=dt.np_dtype, null_on_zero_rhs=True)


class IntegralDivide(BinaryArithmetic):
    """Spark `div`: integral division -> long; 0 divisor -> NULL."""
    symbol = "div"
    decimal_literal_operands = False

    def decimal_types(self, schema):
        return None

    def data_type(self, schema: Schema) -> DataType:
        return INT64

    def eval_device(self, ctx):
        l = self.children[0].eval_device(ctx)
        r = self.children[1].eval_device(ctx)
        ld = l.data.astype(jnp.int64)
        rd = r.data.astype(jnp.int64)
        zero = rd == 0
        v = null_and(l.validity, r.validity, jnp.logical_not(zero))
        safe = jnp.where(zero, jnp.ones_like(rd), rd)
        # C-style truncation toward zero (Spark/Java), not Python floor
        q = (jnp.abs(ld) // jnp.abs(safe)) * jnp.sign(ld) * jnp.sign(safe)
        return DVal(q.astype(jnp.int64), v, INT64)

    def eval_host(self, batch):
        def f(l, r):
            return (np.abs(l) // np.abs(r)) * np.sign(l) * np.sign(r)
        return host_binary_numpy(self, batch, f, INT64, cast_to=np.int64,
                                 null_on_zero_rhs=True)


class Remainder(BinaryArithmetic):
    """Spark `%`: sign of the dividend (Java); 0 divisor -> NULL."""
    symbol = "%"
    decimal_type = staticmethod(D.remainder_type)

    def eval_device(self, ctx):
        ld, rd, v, dt = self._promoted_device_operands(ctx)
        zero = rd == 0
        v = null_and(v, jnp.logical_not(zero))
        safe = jnp.where(zero, jnp.ones_like(rd), rd)
        return DVal(jnp.fmod(ld, safe), v, dt)

    def eval_host(self, batch):
        dec = self.decimal_types(batch.schema)
        if dec is not None:
            return self._eval_decimal_host(batch, dec, _remainder_wide)
        return host_binary_numpy(self, batch, np.fmod,
                                 self.data_type(batch.schema),
                                 null_on_zero_rhs=True)


class Pmod(BinaryArithmetic):
    """Positive modulo (ref GpuPmod)."""
    symbol = "pmod"
    decimal_type = staticmethod(D.remainder_type)

    def eval_device(self, ctx):
        ld, rd, v, dt = self._promoted_device_operands(ctx)
        zero = rd == 0
        v = null_and(v, jnp.logical_not(zero))
        safe = jnp.where(zero, jnp.ones_like(rd), rd)
        m = jnp.fmod(ld, safe)
        m = jnp.where(m < 0, jnp.fmod(m + safe, safe), m)
        return DVal(m, v, dt)

    def eval_host(self, batch):
        dec = self.decimal_types(batch.schema)
        if dec is not None:
            def pmod(x, y, l, r, res):
                m, zero = _remainder_wide(x, y, l, r, res)
                y = abs(y) * 10 ** (res.scale - r.scale)
                neg = np.asarray(m < 0, dtype=bool)
                return np.where(neg, (m + y) % np.where(zero, 1, y), m), zero
            return self._eval_decimal_host(batch, dec, pmod)

        def f(l, r):
            m = np.fmod(l, r)
            return np.where(m < 0, np.fmod(m + r, r), m)
        return host_binary_numpy(self, batch, f, self.data_type(batch.schema),
                                 null_on_zero_rhs=True)


class UnaryMinus(Expression):
    device_type_sig = TypeSig(numeric.types)

    def __init__(self, child: Expression):
        self.children = [child]

    def data_type(self, schema):
        return self.children[0].data_type(schema)

    def eval_device(self, ctx):
        c = self.children[0].eval_device(ctx)
        return DVal(-c.data, c.validity, c.dtype)

    def eval_host(self, batch):
        v, ok = arrow_to_masked_numpy(self.children[0].eval_host(batch))
        return masked_numpy_to_arrow(-v, ok, self.data_type(batch.schema))

    def key(self):
        return f"neg({self.children[0].key()})"


class Abs(Expression):
    device_type_sig = TypeSig(numeric.types)

    def __init__(self, child: Expression):
        self.children = [child]

    def data_type(self, schema):
        return self.children[0].data_type(schema)

    def eval_device(self, ctx):
        c = self.children[0].eval_device(ctx)
        return DVal(jnp.abs(c.data), c.validity, c.dtype)

    def eval_host(self, batch):
        v, ok = arrow_to_masked_numpy(self.children[0].eval_host(batch))
        return masked_numpy_to_arrow(np.abs(v), ok, self.data_type(batch.schema))

    def key(self):
        return f"abs({self.children[0].key()})"


class UnaryPositive(Expression):
    """`+x`: identity on numerics (ref GpuOverrides UnaryPositive rule)."""

    device_type_sig = TypeSig(numeric.types)

    def __init__(self, child: Expression):
        self.children = [child]

    def data_type(self, schema):
        return self.children[0].data_type(schema)

    def eval_device(self, ctx):
        return self.children[0].eval_device(ctx)

    def eval_host(self, batch):
        return self.children[0].eval_host(batch)

    def key(self):
        return f"pos({self.children[0].key()})"


# ---------------------------------------------------------------------------
# bitwise (ref bitwise.scala — cudf bitwise kernels; here plain VPU int ops)
# ---------------------------------------------------------------------------

class _BitwiseBinary(BinaryArithmetic):
    device_type_sig = integral
    decimal_literal_operands = False
    jnp_fn = None
    np_fn = None

    def eval_device(self, ctx):
        ld, rd, v, dt = self._promoted_device_operands(ctx)
        return DVal(type(self).jnp_fn(ld, rd), v, dt)

    def eval_host(self, batch):
        return host_binary_numpy(self, batch, type(self).np_fn,
                                 self.data_type(batch.schema))


class BitwiseAnd(_BitwiseBinary):
    symbol = "&"
    jnp_fn = staticmethod(jnp.bitwise_and)
    np_fn = staticmethod(np.bitwise_and)


class BitwiseOr(_BitwiseBinary):
    symbol = "|"
    jnp_fn = staticmethod(jnp.bitwise_or)
    np_fn = staticmethod(np.bitwise_or)


class BitwiseXor(_BitwiseBinary):
    symbol = "^"
    jnp_fn = staticmethod(jnp.bitwise_xor)
    np_fn = staticmethod(np.bitwise_xor)


class BitwiseNot(Expression):
    device_type_sig = integral

    def __init__(self, child: Expression):
        self.children = [child]

    def data_type(self, schema):
        return self.children[0].data_type(schema)

    def eval_device(self, ctx):
        c = self.children[0].eval_device(ctx)
        return DVal(jnp.bitwise_not(c.data), c.validity, c.dtype)

    def eval_host(self, batch):
        v, ok = arrow_to_masked_numpy(self.children[0].eval_host(batch))
        return masked_numpy_to_arrow(np.bitwise_not(v), ok,
                                     self.data_type(batch.schema))

    def key(self):
        return f"~({self.children[0].key()})"


class _Shift(Expression):
    """shiftleft/shiftright/shiftrightunsigned(x, n): Java semantics —
    byte/short values promote to INT (like Java's << on sub-int types)
    and the shift amount uses only the low 5 (int) or 6 (long) bits
    (ref GpuShiftLeft/Right in arithmetic.scala)."""

    device_type_sig = integral

    def __init__(self, value: Expression, amount: Expression):
        self.children = [value, amount]

    def data_type(self, schema):
        from ..types import INT32
        dt = self.children[0].data_type(schema)
        return dt if dt.np_dtype.itemsize >= 4 else INT32

    def _mask(self, dt) -> int:
        return 63 if dt.np_dtype.itemsize == 8 else 31

    def _shift_np(self, v, n, dt):
        raise NotImplementedError

    def eval_device(self, ctx):
        import jax.numpy as jnp
        c = self.children[0].eval_device(ctx)
        a = self.children[1].eval_device(ctx)
        dt = self.data_type(ctx.schema)
        n = a.data.astype(jnp.int32) & self._mask(dt)
        out = self._shift_jnp(c.data.astype(dt.np_dtype), n, dt)
        from .base import null_and
        return DVal(out, null_and(c.validity, a.validity), dt)

    def eval_host(self, batch):
        v, vok = arrow_to_masked_numpy(self.children[0].eval_host(batch))
        n, nok = arrow_to_masked_numpy(self.children[1].eval_host(batch))
        dt = self.data_type(batch.schema)
        v = v.astype(dt.np_dtype, copy=False)
        n = n.astype(np.int64) & self._mask(dt)
        out = self._shift_np(v, n, dt)
        return masked_numpy_to_arrow(out, vok & nok, dt)

    def key(self):
        return (f"{type(self).__name__}({self.children[0].key()},"
                f"{self.children[1].key()})")


class ShiftLeft(_Shift):
    def _shift_jnp(self, v, n, dt):
        return jnp.left_shift(v, n.astype(v.dtype))

    def _shift_np(self, v, n, dt):
        return np.left_shift(v, n.astype(v.dtype))


class ShiftRight(_Shift):
    """Arithmetic (sign-propagating) right shift, Java >>."""

    def _shift_jnp(self, v, n, dt):
        return jnp.right_shift(v, n.astype(v.dtype))

    def _shift_np(self, v, n, dt):
        return np.right_shift(v, n.astype(v.dtype))


class ShiftRightUnsigned(_Shift):
    """Logical right shift, Java >>>: shift the UNSIGNED bit pattern."""

    def _shift_jnp(self, v, n, dt):
        u = jnp.asarray(v).view(
            jnp.uint64 if dt.np_dtype.itemsize == 8 else jnp.uint32)
        return jnp.right_shift(u, n.astype(u.dtype)).view(v.dtype)

    def _shift_np(self, v, n, dt):
        udt = np.uint64 if dt.np_dtype.itemsize == 8 else np.uint32
        u = v.astype(dt.np_dtype, copy=False).view(udt)
        return np.right_shift(u, n.astype(udt)).view(dt.np_dtype)
