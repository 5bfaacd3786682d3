"""Expression core: trees that compile into fused XLA kernels.

Reference analog: GpuExpression (GpuExpressions.scala) + the ~224 expression
rules in GpuOverrides.scala:3935. Key TPU-first divergence: the reference
interprets expression trees node-by-node, each node a cudf JNI kernel launch;
here an operator's whole expression list is traced into ONE jitted XLA
computation per shape bucket, so XLA fuses the elementwise work (HBM-bandwidth
friendly) and there is exactly one dispatch per batch.

Null semantics follow Spark: values travel as (data, validity) pairs; most
expressions are null-propagating (validity = AND of child validities);
AND/OR use Kleene logic (see logical.py).
"""
from __future__ import annotations

from typing import List, NamedTuple, Optional, Sequence

import jax.numpy as jnp
import numpy as np

from ..types import (BOOL, DATE, DataType, DecimalType, FLOAT32, FLOAT64,
                     INT8, INT16, INT32, INT64, NULLTYPE, STRING, Schema,
                     TIMESTAMP, TypeSig, tpuNative, from_numpy_dtype)

__all__ = ["DVal", "EvalContext", "Expression", "ColumnRef", "BoundReference",
           "Literal", "Unsupported", "promote_types", "Alias"]


class Unsupported(Exception):
    """Raised when an expression cannot run on the device; the tagging pass
    converts this into a fallback reason (ref RapidsMeta willNotWorkOnGpu)."""


#: expression class names disabled by `spark.rapids.tpu.sql.expression.<Name>`
#: confs (ref GpuOverrides.scala:3935 — every ExprRule gets an enable conf;
#: disabling it forces the expression off the accelerator). Thread-local:
#: plan/op_confs.install_from_conf installs the set from the query's conf at
#: BOTH plan time (tagging) and execution time (the dataframe sink
#: re-installs before running), so interleaved sessions on other threads
#: cannot contaminate this query's fallback decisions. Consulted by the SAME
#: fully_device_supported checks the execs use at run time, so a disabled
#: expression falls back to host evaluation end to end.
import threading as _thr

_DISABLED = _thr.local()


def set_disabled_expressions(names) -> None:
    _DISABLED.sets = frozenset(names)


def expression_disabled_reason(cls) -> Optional[str]:
    name = cls.__name__
    if name in getattr(_DISABLED, "sets", ()):
        return (f"{name} disabled by "
                f"spark.rapids.tpu.sql.expression.{name}=false")
    return None


class ListVal(NamedTuple):
    """Traced device LIST value in the rectangular layout
    (columnar/nested.py): rides in DVal.data for ArrayType-typed values.
    values[P, W] element data, elem_valid[P, W], lengths[P]."""
    values: jnp.ndarray
    elem_valid: jnp.ndarray
    lengths: jnp.ndarray


class StrVal(NamedTuple):
    """Traced device STRING value as a dense byte rectangle
    (columnar/strrect.py): rides in DVal.data for STRING-typed values
    when the column is rectangle-backed (high cardinality — dictionary
    codes stay the low-cardinality representation).
    bytes_[P, W] uint8 (zero-padded past each row's length),
    lengths[P] int32 (byte == char: the device path is ASCII-gated)."""
    bytes_: jnp.ndarray
    lengths: jnp.ndarray


class DVal(NamedTuple):
    """A traced device value: padded data + validity mask (+static dtype).
    For ArrayType values, ``data`` is a ListVal rectangle and ``validity``
    remains the per-row mask."""
    data: jnp.ndarray
    validity: jnp.ndarray
    dtype: DataType


class EvalContext:
    """Trace-time context handed to Expression.eval_device.

    columns: per-input-ordinal DVal (traced jnp arrays)
    num_rows: traced int32 scalar — the true (unpadded) row count
    padded_len: static int — the shape bucket
    scalars/literal_slots: traced literal values (see parameterized_keys) —
    numeric literals ride into the kernel as scalar operands so queries
    differing only in constants share ONE compiled executable
    """

    def __init__(self, schema: Schema, columns: Sequence[DVal], num_rows,
                 padded_len: int, scalars=None, literal_slots=None):
        self.schema = schema
        self.columns = list(columns)
        self.num_rows = num_rows
        self.padded_len = padded_len
        self.scalars = scalars
        self.literal_slots = literal_slots

    def row_mask(self):
        """bool[P]: True for real rows, False for padding."""
        return jnp.arange(self.padded_len, dtype=jnp.int32) < self.num_rows


import contextlib as _contextlib
import threading as _threading

_PARAM_KEYS = _threading.local()


def _param_keys_on() -> bool:
    return getattr(_PARAM_KEYS, "on", False)


@_contextlib.contextmanager
def parameterized_keys():
    """Within this context, Literal.key() renders parameterizable values
    as a type-only placeholder. Kernel caches compute their keys under it,
    so queries that differ only in numeric constants (TPC parameter
    sweeps) resolve to the SAME compiled kernel; the actual values ride in
    as traced scalar operands collected by collect_param_literals."""
    prev = getattr(_PARAM_KEYS, "on", False)
    prev_map = getattr(_PARAM_KEYS, "slots", None)
    _PARAM_KEYS.on = True
    _PARAM_KEYS.slots = {}
    try:
        yield
    finally:
        _PARAM_KEYS.on = prev
        _PARAM_KEYS.slots = prev_map


def collect_param_literals(exprs) -> list:
    """Deterministic DFS over expression trees -> parameterizable Literal
    nodes (deduped by identity), the slot order shared by kernel build
    and call sites."""
    out, seen = [], set()

    def walk(e):
        if e is None:
            return
        if isinstance(e, Literal):
            if e.parameterizable() and id(e) not in seen:
                seen.add(id(e))
                out.append(e)
            return
        for c in getattr(e, "children", []):
            walk(c)

    for e in exprs:
        walk(e)
    return out


def literal_slot_map(exprs) -> dict:
    """id(Literal) -> slot index in the shared DFS order; kernel builders
    derive slots and call sites derive values from the SAME traversal."""
    return {id(l): i for i, l in enumerate(collect_param_literals(exprs))}


def literal_scalars(lits) -> tuple:
    """Call-time traced operand tuple for the collected literals."""
    return tuple(jnp.asarray(np.asarray(l.value, dtype=l.dtype.np_dtype))
                 for l in lits)


class Expression:
    children: List["Expression"] = []

    # --- analysis ---------------------------------------------------------
    def data_type(self, schema: Schema) -> DataType:
        raise NotImplementedError

    def nullable(self, schema: Schema) -> bool:
        return True

    @property
    def name_hint(self) -> str:
        return str(self)

    def references(self) -> List[str]:
        out: List[str] = []
        for c in self.children:
            out.extend(c.references())
        return out

    # --- planner tagging (ref BaseExprMeta.tagExprForGpu) ----------------
    #: types this expression supports on device; planner checks child+output
    device_type_sig: TypeSig = tpuNative

    def device_unsupported_reason(self, schema: Schema) -> Optional[str]:
        """None if the expression (this node only) can run on device."""
        dt = self.data_type(schema)
        r = self.device_type_sig.reason_not_supported(dt)
        if r is not None:
            return f"{type(self).__name__}: output {r}"
        for c in self.children:
            cdt = c.data_type(schema)
            if cdt == NULLTYPE:
                # an untyped NULL literal adapts to the consumer's output
                # type (all-invalid lanes) — e.g. CASE WHEN ... ELSE NULL
                continue
            cr = self.device_type_sig.reason_not_supported(cdt)
            if cr is not None:
                return f"{type(self).__name__}: input {cr}"
        return None

    def fully_device_supported(self, schema: Schema) -> Optional[str]:
        r = expression_disabled_reason(type(self))
        if r:
            return r
        r = self.device_unsupported_reason(schema)
        if r:
            return r
        for c in self.children:
            r = c.fully_device_supported(schema)
            if r:
                return r
        return None

    def decimal_checks(self, schema: Schema) -> int:
        """Checked decimal operations THIS node traces on the device
        (exprs/decimal_rules.py): each notes one overflow flag."""
        return 0

    # --- evaluation -------------------------------------------------------
    def eval_device(self, ctx: EvalContext) -> DVal:
        raise Unsupported(f"{type(self).__name__} has no device implementation")

    def eval_host(self, batch) -> "object":
        """Vectorized host (Arrow) evaluation — the CPU-fallback interpreter.
        Returns a pyarrow.Array of length batch.num_rows."""
        raise Unsupported(f"{type(self).__name__} has no host implementation")

    # --- identity (kernel-cache key) -------------------------------------
    def key(self) -> str:
        kids = ",".join(c.key() for c in self.children)
        return f"{type(self).__name__}({kids})"

    def __repr__(self):
        return self.key()


def coerce_decimal_literals(e, schema: Schema):
    """Spark's ``DecimalPrecision`` for literals, applied where a logical
    node takes its expressions (plan/logical.py): in arithmetic and
    comparisons, an integer literal or a SQL ``0.05`` that meets a decimal
    operand becomes a decimal literal of its own digits. Returns ``e`` or
    a copy with the literals replaced; never touches a tree without such
    a pair, so plans over floats keep their keys."""
    import copy
    from .aggregates import AggregateExpression
    if isinstance(e, AggregateExpression):
        if e.child is None:
            return e
        child = coerce_decimal_literals(e.child, schema)
        if child is e.child:
            return e
        e = copy.copy(e)
        e.child = child
        return e
    kids = getattr(e, "children", None)
    if not kids or not all(isinstance(c, Expression) for c in kids):
        return e
    new = [coerce_decimal_literals(c, schema) for c in kids]
    if getattr(e, "decimal_literal_operands", False) and len(new) == 2:
        for i in (0, 1):
            if not isinstance(new[i], Literal):
                continue
            try:
                other = new[1 - i].data_type(schema)
            except Exception:  # noqa: BLE001 - not typeable here: leave
                continue
            if isinstance(other, DecimalType):
                new[i] = new[i].as_decimal() or new[i]
    if all(a is b for a, b in zip(new, kids)):
        return e
    e = copy.copy(e)
    e.children = new
    return e


class ColumnRef(Expression):
    """Named attribute reference; resolved to an ordinal at bind time."""

    def __init__(self, name: str):
        self.name = name
        self.children = []

    def data_type(self, schema: Schema) -> DataType:
        return schema[self.name].dtype

    def references(self):
        return [self.name]

    def device_unsupported_reason(self, schema: Schema) -> Optional[str]:
        dt = schema[self.name].dtype
        if dt.device_backed:
            return None
        from ..columnar.nested import device_list_ok
        if device_list_ok(dt):
            # list-of-primitive rides the dense rectangle (nested.py);
            # width-capped batches demote to host per batch at run time
            return None
        return f"column {self.name}: {dt.name} is host-only"

    def eval_device(self, ctx: EvalContext) -> DVal:
        return ctx.columns[ctx.schema.index_of(self.name)]

    def eval_host(self, batch):
        return batch.column_by_name(self.name).to_arrow(batch.num_rows)

    def key(self):
        return f"col({self.name})"

    @property
    def name_hint(self):
        return self.name


class BoundReference(Expression):
    """Ordinal reference (post-binding), ref BoundReference in Catalyst."""

    def __init__(self, ordinal: int, dtype: DataType):
        self.ordinal = ordinal
        self._dtype = dtype
        self.children = []

    def data_type(self, schema: Schema) -> DataType:
        return self._dtype

    def eval_device(self, ctx: EvalContext) -> DVal:
        return ctx.columns[self.ordinal]

    def eval_host(self, batch):
        return batch.column(self.ordinal).to_arrow(batch.num_rows)

    def key(self):
        return f"bound({self.ordinal}:{self._dtype.name})"


def _literal_type(value) -> DataType:
    import datetime
    import decimal
    if value is None:
        return NULLTYPE
    if isinstance(value, decimal.Decimal):
        from .decimal_rules import literal_type
        return literal_type(value)
    if isinstance(value, bool):
        return BOOL
    if isinstance(value, int):
        return INT32 if -(2**31) <= value < 2**31 else INT64
    if isinstance(value, float):
        return FLOAT64
    if isinstance(value, str):
        return STRING
    if isinstance(value, np.datetime64):
        unit = np.datetime_data(value.dtype)[0]
        return DATE if unit in ("D", "W", "M", "Y") else TIMESTAMP
    if isinstance(value, datetime.datetime):
        return TIMESTAMP
    if isinstance(value, datetime.date):
        return DATE
    if isinstance(value, np.generic):
        return from_numpy_dtype(value.dtype)
    raise TypeError(f"cannot infer literal type for {value!r}")


def _canonical_literal(value, dtype: DataType):
    """Store date/timestamp literals as their device representation
    (DATE: int32 days since epoch, TIMESTAMP: int64 microseconds) so both
    the device kernel (jnp.full) and the host path (pa.array with the
    arrow logical type) consume the same value."""
    if value is None:
        return None
    if dtype == DATE and not isinstance(value, (int, np.integer)):
        return int(np.datetime64(value, "D").astype(np.int64))
    if dtype == TIMESTAMP and not isinstance(value, (int, np.integer)):
        return int(np.datetime64(value, "us").astype(np.int64))
    if isinstance(dtype, DecimalType):
        # the value a decimal literal holds IS a decimal.Decimal at the
        # type's scale (the device lane takes its unscaled int)
        import decimal
        return decimal.Decimal(value).quantize(
            decimal.Decimal(1).scaleb(-dtype.scale),
            context=decimal.Context(prec=76))
    return value


class Literal(Expression):
    def __init__(self, value, dtype: Optional[DataType] = None):
        self.dtype = dtype if dtype is not None else _literal_type(value)
        self.value = _canonical_literal(value, self.dtype)
        self.children = []

    def data_type(self, schema: Schema) -> DataType:
        return self.dtype

    def nullable(self, schema: Schema) -> bool:
        return self.value is None

    def device_unsupported_reason(self, schema: Schema) -> Optional[str]:
        if self.value is None:
            return None  # typed null literal is fine on device
        if not self.dtype.device_backed:
            return f"literal of host-only type {self.dtype.name}"
        return None

    def eval_device(self, ctx: EvalContext) -> DVal:
        p = ctx.padded_len
        if self.value is None:
            np_dt = self.dtype.np_dtype or np.dtype(np.int32)
            return DVal(jnp.zeros(p, dtype=np_dt),
                        jnp.zeros(p, dtype=jnp.bool_), self.dtype)
        slots = ctx.literal_slots
        if slots is not None and id(self) in slots \
                and ctx.scalars is not None:
            v = ctx.scalars[slots[id(self)]]
            return DVal(jnp.broadcast_to(v, (p,)),
                        jnp.ones(p, dtype=jnp.bool_), self.dtype)
        value = self.value
        if isinstance(self.dtype, DecimalType):
            from .decimal_rules import unscaled
            value = unscaled(value, self.dtype)
        data = jnp.full((p,), value, dtype=self.dtype.np_dtype)
        return DVal(data, jnp.ones(p, dtype=jnp.bool_), self.dtype)

    def as_decimal(self) -> Optional["Literal"]:
        """This literal as Spark types it beside a decimal operand: an
        integer by its own digits (1 is decimal(1,0)), a SQL literal
        written with a point and no exponent by its text (0.05 is
        decimal(2,2), types.DecimalText); None for any other (a double
        beside a decimal makes the operation a double one). The decimal
        literal's value is part of its key, never a traced scalar: its
        scale is a type."""
        import decimal
        from ..types import DecimalText
        if isinstance(self.value, DecimalText):
            return Literal(decimal.Decimal(self.value.text))
        if (isinstance(self.value, (int, np.integer))
                and not isinstance(self.value, (bool, np.bool_))
                and self.dtype in (INT8, INT16, INT32, INT64)):
            return Literal(decimal.Decimal(int(self.value)))
        return None

    def eval_host(self, batch):
        import pyarrow as pa
        from ..types import to_arrow
        at = to_arrow(self.dtype) if self.dtype != NULLTYPE else pa.null()
        if self.value is None:
            return pa.nulls(batch.num_rows, type=at)
        # C-level broadcast: a python-list literal column costs ~30 ms per
        # 1M rows and was the host engine's single biggest line
        return pa.repeat(pa.scalar(self.value, type=at), batch.num_rows)

    def key(self):
        if _param_keys_on() and self.parameterizable():
            # slot index in the key: two queries whose literal-object
            # SHARING differs must not collide on one compiled kernel
            slots = _PARAM_KEYS.slots
            slot = slots.setdefault(id(self), len(slots))
            return f"lit(?{slot}:{self.dtype.name})"
        return f"lit({self.value!r}:{self.dtype.name})"

    def parameterizable(self) -> bool:
        """True when the value can ride into a kernel as a traced scalar
        operand (numeric/bool/date/timestamp; not strings/decimals/NULL)."""
        from ..types import DecimalType, STRING
        return (self.value is not None
                and self.dtype.np_dtype is not None
                and self.dtype != STRING
                and not isinstance(self.dtype, DecimalType))

    @property
    def name_hint(self):
        return repr(self.value)


class Alias(Expression):
    def __init__(self, child: Expression, name: str):
        self.children = [child]
        self.name = name

    def data_type(self, schema: Schema) -> DataType:
        return self.children[0].data_type(schema)

    def device_unsupported_reason(self, schema):
        return None

    def eval_device(self, ctx: EvalContext) -> DVal:
        return self.children[0].eval_device(ctx)

    def eval_host(self, batch):
        return self.children[0].eval_host(batch)

    def key(self):
        return self.children[0].key()

    @property
    def name_hint(self):
        return self.name


# ---------------------------------------------------------------------------
# numeric type promotion (Catalyst TypeCoercion's common type of two
# operands; the result type of decimal ARITHMETIC is the operator's own,
# exprs/decimal_rules.py)
# ---------------------------------------------------------------------------

_NUMERIC_ORDER = [INT8, INT16, INT32, INT64, FLOAT32, FLOAT64]


def promote_types(l: DataType, r: DataType) -> DataType:
    if l == r:
        return l
    if isinstance(l, DecimalType) or isinstance(r, DecimalType):
        # two decimals, or a decimal and an integer (as decimal(3|5|10|
        # 20, 0)): the wider decimal, which holds both; a float or double
        # beside a decimal: double
        from .decimal_rules import operand_type, wider_type
        ld, rd = operand_type(l), operand_type(r)
        if ld is not None and rd is not None:
            return wider_type(ld, rd)
        if l in (FLOAT32, FLOAT64) or r in (FLOAT32, FLOAT64):
            return FLOAT64
        raise TypeError(f"cannot promote {l} and {r}")
    try:
        li, ri = _NUMERIC_ORDER.index(l), _NUMERIC_ORDER.index(r)
    except ValueError:
        raise TypeError(f"cannot promote {l} and {r}")
    return _NUMERIC_ORDER[max(li, ri)]


def null_and(*validities):
    out = validities[0]
    for v in validities[1:]:
        out = jnp.logical_and(out, v)
    return out
