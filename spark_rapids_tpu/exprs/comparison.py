"""Comparison predicates (ref sql-plugin predicates.scala GpuEqualTo etc.).

Numeric comparisons promote operands; NaN handling follows Spark: NaN == NaN
is true and NaN is largest for ordering (ref GpuGreaterThan docs / cudf NaN
config spark.rapids.sql.hasNans).
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from ..types import (BOOL, DataType, DecimalType, Schema, TypeEnum,
                     comparable, tpuNative, STRING)
from . import decimal_rules as D
from .base import DVal, EvalContext, Expression, null_and, promote_types
from .arithmetic import (arrow_to_masked_numpy, decimal_as_double,
                         masked_numpy_to_arrow)

__all__ = ["EqualTo", "EqualNullSafe", "NotEqual", "LessThan",
           "LessThanOrEqual", "GreaterThan", "GreaterThanOrEqual",
           "IsNull", "IsNotNull", "IsNaN", "In", "InSet"]


def _nan_eq(l, r):
    base = l == r
    if jnp.issubdtype(l.dtype, jnp.floating):
        both_nan = jnp.logical_and(jnp.isnan(l), jnp.isnan(r))
        return jnp.logical_or(base, both_nan)
    return base


def _nan_lt(l, r):
    # Spark ordering: NaN is greater than everything
    if jnp.issubdtype(l.dtype, jnp.floating):
        ln, rn = jnp.isnan(l), jnp.isnan(r)
        return jnp.where(rn, jnp.logical_not(ln), jnp.logical_and(
            jnp.logical_not(ln), l < r))
    return l < r


#: supported_ops.md's note on STRING for =, <>, IN
_DICT_NOTE = ("a plain STRING column against string literal(s) in a filter "
              "is evaluated over the column's sorted dictionary, once per "
              "distinct value, and the batch stays on the device (placement "
              "code EXPR_DICT_EVAL); an IN list holding NULL, and a "
              "comparison of two string columns or of a computed string, "
              "run on the host")


def _plain_string_column(e, schema: Schema):
    """``e`` if it is a bare reference to a STRING column of ``schema``."""
    from .base import ColumnRef
    if isinstance(e, ColumnRef) and e.name in schema.names() \
            and schema[e.name].dtype == STRING:
        return e
    return None


class BinaryComparison(Expression):
    device_type_sig = comparable
    symbol = "?"
    #: how a comparison of a STRING column with a string literal is
    #: evaluated over the column's dictionary (exprs/compiler.py
    #: build_dict_filter): None = not at all; "range" = the matching codes
    #: of a sorted dictionary are one contiguous span; "mask" = any set
    dict_form = None

    def dict_column(self, schema: Schema):
        """The plain STRING column this predicate compares with a non-NULL
        string literal (either order), or None: then the match can be
        computed ONCE per distinct value (``host_mask``) and broadcast
        through the codes on the device."""
        from .base import Literal
        if self.dict_form is None:
            return None
        for col, lit in (self.children, self.children[::-1]):
            if isinstance(lit, Literal) and isinstance(lit.value, str):
                return _plain_string_column(col, schema)
        return None

    def _dict_literal(self):
        import pyarrow as pa
        from .base import Literal
        lit = next(c for c in self.children if isinstance(c, Literal))
        return pa.scalar(lit.value, type=pa.string())
    #: an integer or SQL-decimal literal beside a decimal operand becomes
    #: a decimal literal (exprs/base.py:coerce_decimal_literals)
    decimal_literal_operands = True

    def __init__(self, left: Expression, right: Expression):
        self.children = [left, right]

    def data_type(self, schema: Schema) -> DataType:
        return BOOL

    def _decimal_pair(self, schema: Schema):
        """(left, right) decimal types where a decimal is compared with a
        decimal or an integer: both sides go to the wider scale first."""
        ldt = self.children[0].data_type(schema)
        rdt = self.children[1].data_type(schema)
        if not (isinstance(ldt, DecimalType) or isinstance(rdt, DecimalType)):
            return None
        l, r = D.operand_type(ldt), D.operand_type(rdt)
        return None if l is None or r is None else (l, r)

    def decimal_checks(self, schema):
        dec = self._decimal_pair(schema)
        return int(dec is not None and dec[0].scale != dec[1].scale
                   and D.wider_type(*dec).precision > D.LANE_DIGITS)

    def _operands(self, ctx: EvalContext):
        l = self.children[0].eval_device(ctx)
        r = self.children[1].eval_device(ctx)
        ldt = self.children[0].data_type(ctx.schema)
        rdt = self.children[1].data_type(ctx.schema)
        dec = self._decimal_pair(ctx.schema)
        if dec is not None:
            valid = null_and(l.validity, r.validity)
            x, y, over = D.compare_values(jnp, l.data.astype(jnp.int64),
                                          r.data.astype(jnp.int64), *dec)
            if over is not None:
                over = jnp.logical_and(over, valid)
                D.note_overflow(over)
                valid = jnp.logical_and(valid, jnp.logical_not(over))
            return x, y, valid
        # (operands first, validity after: the traced order is part of a
        # float plan's executable-cache key)
        if ldt != rdt:
            wide = promote_types(ldt, rdt)
            return (decimal_as_double(jnp, l.data, ldt).astype(wide.np_dtype),
                    decimal_as_double(jnp, r.data, rdt).astype(wide.np_dtype),
                    null_and(l.validity, r.validity))
        return l.data, r.data, null_and(l.validity, r.validity)

    def _host_operands(self, batch):
        from .base import Literal

        from ..types import DATE, TIMESTAMP, DecimalType

        def is_lit(e):
            if not (isinstance(e, Literal) and e.value is not None
                    and e.dtype.np_dtype is not None):
                return False
            # DATE/TIMESTAMP/decimal columns materialize as datetime64 /
            # object arrays on host — their literals must keep the arrow
            # path so dtypes line up
            other = self.children[1] if e is self.children[0] \
                else self.children[0]
            odt = other.data_type(batch.schema)
            if odt in (DATE, TIMESTAMP) or isinstance(odt, DecimalType) \
                    or e.dtype in (DATE, TIMESTAMP) \
                    or isinstance(e.dtype, DecimalType):
                return False
            return True

        def side(e, as_scalar):
            # literal operands ride as numpy scalars (broadcast is free;
            # materializing a constant column costs ~30 ms per 1M rows)
            if as_scalar:
                import numpy as _np
                return (_np.asarray(e.value, dtype=e.dtype.np_dtype),
                        True)
            return arrow_to_masked_numpy(e.eval_host(batch))

        lit0, lit1 = is_lit(self.children[0]), is_lit(self.children[1])
        # at most one side stays scalar so the result keeps batch length
        l, lv = side(self.children[0], lit0 and not lit1)
        r, rv = side(self.children[1], lit1)
        ldt = self.children[0].data_type(batch.schema)
        rdt = self.children[1].data_type(batch.schema)
        dec = self._decimal_pair(batch.schema)
        if dec is not None:
            # unscaled lanes at the wider scale; Python ints where the
            # lanes cannot hold that (exact)
            wide_ints = l.dtype == object or r.dtype == object
            if not wide_ints:
                x, y, over = D.compare_values(np, l.astype(np.int64),
                                              r.astype(np.int64), *dec)
                wide_ints = over is not None and (over & lv & rv).any()
            if wide_ints:
                x, y, _ = D.compare_values(np, l.astype(object),
                                           r.astype(object), *dec, wide=True)
            return x, y, lv & rv
        if ldt != rdt and ldt.device_backed and rdt.device_backed:
            wide = promote_types(ldt, rdt).np_dtype
            l = decimal_as_double(np, l, ldt).astype(wide)
            r = decimal_as_double(np, r, rdt).astype(wide)
        return l, r, lv & rv

    def key(self):
        return f"{type(self).__name__}({self.children[0].key()},{self.children[1].key()})"

    @property
    def name_hint(self):
        return (f"({self.children[0].name_hint} {self.symbol} "
                f"{self.children[1].name_hint})")


class EqualTo(BinaryComparison):
    symbol = "="
    device_type_sig = comparable.with_psnote(TypeEnum.STRING, _DICT_NOTE)
    dict_form = "range"     # one value = one code of a sorted dictionary

    def host_mask(self, arr):
        import pyarrow.compute as pc
        return pc.equal(arr, self._dict_literal())

    def eval_device(self, ctx):
        l, r, v = self._operands(ctx)
        return DVal(_nan_eq(l, r), v, BOOL)

    def eval_host(self, batch):
        l, r, v = self._host_operands(batch)
        with np.errstate(all="ignore"):
            eq = l == r
            if np.issubdtype(np.asarray(l).dtype, np.floating):
                eq = eq | (np.isnan(l) & np.isnan(r))
        return masked_numpy_to_arrow(eq, v, BOOL)


class EqualNullSafe(BinaryComparison):
    """<=> : never null; null <=> null is true."""
    symbol = "<=>"

    def eval_device(self, ctx):
        l = self.children[0].eval_device(ctx)
        r = self.children[1].eval_device(ctx)
        eq = _nan_eq(l.data, r.data)
        both_null = jnp.logical_and(~l.validity, ~r.validity)
        both_valid = jnp.logical_and(l.validity, r.validity)
        out = jnp.logical_or(both_null, jnp.logical_and(both_valid, eq))
        return DVal(out, jnp.ones_like(out, dtype=jnp.bool_), BOOL)

    def eval_host(self, batch):
        l, lv = arrow_to_masked_numpy(self.children[0].eval_host(batch))
        r, rv = arrow_to_masked_numpy(self.children[1].eval_host(batch))
        with np.errstate(all="ignore"):
            eq = l == r
        out = (~lv & ~rv) | (lv & rv & eq)
        return masked_numpy_to_arrow(out, np.ones_like(out, dtype=bool), BOOL)


class NotEqual(BinaryComparison):
    symbol = "!="
    device_type_sig = comparable.with_psnote(TypeEnum.STRING, _DICT_NOTE)
    dict_form = "mask"

    def host_mask(self, arr):
        import pyarrow.compute as pc
        return pc.not_equal(arr, self._dict_literal())

    def eval_device(self, ctx):
        l, r, v = self._operands(ctx)
        return DVal(jnp.logical_not(_nan_eq(l, r)), v, BOOL)

    def eval_host(self, batch):
        l, r, v = self._host_operands(batch)
        with np.errstate(all="ignore"):
            eq = l == r
            if np.issubdtype(np.asarray(l).dtype, np.floating):
                eq = eq | (np.isnan(l) & np.isnan(r))
        return masked_numpy_to_arrow(~eq, v, BOOL)


def _host_cmp(op):
    def f(self, batch):
        l, r, v = self._host_operands(batch)
        fl = np.issubdtype(np.asarray(l).dtype, np.floating)
        with np.errstate(all="ignore"):
            if fl:
                # Spark float ordering: NaN compares greater than everything
                ln, rn = np.isnan(l), np.isnan(r)
                l2 = np.where(ln, 0, l)
                r2 = np.where(rn, 0, r)
                lt = np.where(rn, ~ln, ~ln & (l2 < r2))
                eq = np.where(ln & rn, True, (~ln & ~rn) & (l2 == r2))
                out = {"lt": lt, "le": lt | eq, "gt": ~(lt | eq), "ge": ~lt}[op]
            else:
                out = {"lt": l < r, "le": l <= r, "gt": l > r, "ge": l >= r}[op]
        return masked_numpy_to_arrow(out, v, BOOL)
    return f


class LessThan(BinaryComparison):
    symbol = "<"

    def eval_device(self, ctx):
        l, r, v = self._operands(ctx)
        return DVal(_nan_lt(l, r), v, BOOL)

    eval_host = _host_cmp("lt")


class LessThanOrEqual(BinaryComparison):
    symbol = "<="

    def eval_device(self, ctx):
        l, r, v = self._operands(ctx)
        return DVal(jnp.logical_or(_nan_lt(l, r), _nan_eq(l, r)), v, BOOL)

    eval_host = _host_cmp("le")


class GreaterThan(BinaryComparison):
    symbol = ">"

    def eval_device(self, ctx):
        l, r, v = self._operands(ctx)
        return DVal(jnp.logical_not(
            jnp.logical_or(_nan_lt(l, r), _nan_eq(l, r))), v, BOOL)

    eval_host = _host_cmp("gt")


class GreaterThanOrEqual(BinaryComparison):
    symbol = ">="

    def eval_device(self, ctx):
        l, r, v = self._operands(ctx)
        return DVal(jnp.logical_not(_nan_lt(l, r)), v, BOOL)

    eval_host = _host_cmp("ge")


class IsNull(Expression):
    def __init__(self, child: Expression):
        self.children = [child]

    def data_type(self, schema):
        return BOOL

    def nullable(self, schema):
        return False

    def device_unsupported_reason(self, schema):
        return None  # works for any child whose column is device-backed

    def eval_device(self, ctx):
        c = self.children[0].eval_device(ctx)
        out = jnp.logical_not(c.validity)
        # padding rows must not count as "null rows"
        out = jnp.logical_and(out, ctx.row_mask())
        return DVal(out, jnp.ones_like(out), BOOL)

    def eval_host(self, batch):
        import pyarrow.compute as pc
        return pc.is_null(self.children[0].eval_host(batch))

    def key(self):
        return f"isnull({self.children[0].key()})"


class IsNotNull(Expression):
    def __init__(self, child: Expression):
        self.children = [child]

    def data_type(self, schema):
        return BOOL

    def nullable(self, schema):
        return False

    def device_unsupported_reason(self, schema):
        return None

    def eval_device(self, ctx):
        c = self.children[0].eval_device(ctx)
        return DVal(c.validity, jnp.ones_like(c.validity), BOOL)

    def eval_host(self, batch):
        import pyarrow.compute as pc
        return pc.is_valid(self.children[0].eval_host(batch))

    def key(self):
        return f"isnotnull({self.children[0].key()})"


class IsNaN(Expression):
    def __init__(self, child: Expression):
        self.children = [child]

    def data_type(self, schema):
        return BOOL

    def eval_device(self, ctx):
        c = self.children[0].eval_device(ctx)
        if jnp.issubdtype(c.data.dtype, jnp.floating):
            out = jnp.isnan(c.data)
        else:
            out = jnp.zeros_like(c.validity)
        return DVal(out, c.validity, BOOL)

    def eval_host(self, batch):
        v, ok = arrow_to_masked_numpy(self.children[0].eval_host(batch))
        out = np.isnan(v) if np.issubdtype(v.dtype, np.floating) \
            else np.zeros(len(v), dtype=bool)
        return masked_numpy_to_arrow(out, ok, BOOL)

    def key(self):
        return f"isnan({self.children[0].key()})"


class In(Expression):
    """value IN (literals...) (ref GpuInSet)."""

    device_type_sig = tpuNative.with_psnote(TypeEnum.STRING, _DICT_NOTE)
    dict_form = "mask"

    def __init__(self, child: Expression, values):
        self.children = [child]
        self.values = tuple(values)

    def data_type(self, schema):
        return BOOL

    def dict_column(self, schema: Schema):
        """As ``BinaryComparison.dict_column``: a plain STRING column IN a
        list of non-NULL string literals. (A NULL in the list makes a
        non-match NULL, not false, which a mask cannot say.)"""
        if self.values and all(isinstance(v, str) for v in self.values):
            return _plain_string_column(self.children[0], schema)
        return None

    def host_mask(self, arr):
        import pyarrow as pa
        import pyarrow.compute as pc
        return pc.is_in(arr, value_set=pa.array(self.values, pa.string()))

    def eval_device(self, ctx):
        c = self.children[0].eval_device(ctx)
        out = jnp.zeros(ctx.padded_len, dtype=jnp.bool_)
        fl = jnp.issubdtype(c.data.dtype, jnp.floating)
        for v in self.values:
            if v is None:
                continue
            if fl and isinstance(v, (int, float, np.floating, np.integer)):
                # same NaN-eq semantics as EqualTo (ADVICE r5): Spark's
                # double('NaN') IN (NaN) is true — a bare == would miss it
                out = jnp.logical_or(
                    out, _nan_eq(c.data, jnp.asarray(v, c.data.dtype)))
            else:
                out = jnp.logical_or(out, c.data == v)
        valid = c.validity
        if any(v is None for v in self.values):
            # SQL three-valued IN: x IN (..., NULL) is NULL unless a
            # listed value matches (x = NULL is unknown, not false)
            valid = jnp.logical_and(valid, out)
        return DVal(out, valid, BOOL)

    def eval_host(self, batch):
        import pyarrow as pa
        import pyarrow.compute as pc
        arr = self.children[0].eval_host(batch)
        nan_listed = any(isinstance(v, float) and np.isnan(v)
                        for v in self.values)
        vals = pa.array([v for v in self.values if v is not None
                         and not (isinstance(v, float) and np.isnan(v))],
                        type=arr.type)
        res = pc.is_in(arr, value_set=vals)
        if nan_listed and pa.types.is_floating(arr.type):
            # Spark NaN semantics (as EqualTo/_nan_eq): NaN IN (NaN) is
            # true; arrow's is_in must not decide NaN membership
            res = pc.or_(res, pc.is_nan(arr))
        # Spark: null IN (...) -> NULL (pc.is_in yields false for nulls)
        out = pc.if_else(pc.is_valid(arr), res,
                         pa.nulls(len(arr), pa.bool_()))
        if any(v is None for v in self.values):
            # non-match against a list containing NULL is NULL too
            out = pc.if_else(pc.fill_null(out, False), out,
                             pa.nulls(len(arr), pa.bool_()))
        return out

    def key(self):
        return f"in({self.children[0].key()},{self.values!r})"


class InSet(In):
    """Optimizer-produced literal-set IN (ref GpuInSet): identical
    evaluation to In — Spark splits them only because InSet carries a
    pre-built set; here the literal tuple already is one."""
