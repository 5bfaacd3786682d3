"""Cast expression (ref GpuCast.scala, 1,795 LoC of compat-matrix dispatch).

Implemented semantics (non-ANSI Spark):
  * numeric -> numeric: Java narrowing; float->int truncates toward zero,
    NaN -> 0, out-of-range clamps to the target min/max (Java (int)/(long)).
  * numeric <-> boolean: 0=false else true; bool -> 0/1.
  * date -> timestamp (midnight UTC) and timestamp -> date (floor).
  * string casts run on the host path (Arrow), tagged host-only.
"""
from __future__ import annotations

import numpy as np
import jax.numpy as jnp

from ..types import (BOOL, DATE, DataType, DecimalType, Schema, STRING,
                     TIMESTAMP, all_types)
from . import decimal_rules as D
from .base import DVal, Expression
from .arithmetic import arrow_to_masked_numpy, masked_numpy_to_arrow

__all__ = ["Cast"]

_MICROS_PER_DAY = 86_400_000_000


def _int_bounds(np_dt):
    info = np.iinfo(np_dt)
    return info.min, info.max


def _float_to_int_java(d, np_dt, xp):
    """Java (int)/(long) cast semantics: NaN -> 0, truncate toward zero,
    out-of-range saturates to min/max (ref GpuCast float->int handling).
    `xp` is numpy or jax.numpy so device and host paths share one definition."""
    lo, hi = _int_bounds(np_dt)
    bits = np.iinfo(np_dt).bits
    t_hi = 2.0 ** (bits - 1)               # first value that overflows
    max_safe = np.nextafter(t_hi, 0.0)     # largest representable below 2^(b-1)
    clean = xp.where(xp.isnan(d), xp.zeros_like(d), d)
    safe = xp.clip(clean, float(lo), max_safe)
    out = xp.trunc(safe).astype(np_dt)
    out = xp.where(clean >= t_hi, xp.asarray(hi, dtype=np_dt), out)
    return out.astype(np_dt)


def _decimal_cast(xp, d, src: DataType, dst: DataType, wide: bool = False):
    """A cast with a decimal on either side, on unscaled lanes: (values,
    NULL flag or None, lane-overflow flag or None). Spark (non-ANSI): a
    scale that is cut rounds HALF_UP; a value with more digits than the
    target declares is NULL; decimal -> integral truncates toward zero.
    ``wide``: Python ints in object arrays (the host's exact form)."""
    if isinstance(dst, DecimalType):
        if isinstance(src, DecimalType) or D.operand_type(src) is not None:
            frm = src.scale if isinstance(src, DecimalType) else 0
            if not wide:
                d = d.astype(xp.int64)
            out, over = D.rescale(xp, d, frm, dst.scale, wide)
        else:                                   # float / double
            x = d.astype(xp.float64) * float(10 ** dst.scale)
            out = (xp.sign(x) * xp.floor(xp.abs(x) + 0.5))
            over = xp.logical_or(xp.isnan(x), xp.abs(out) >= 2.0 ** 63)
            out = xp.where(over, 0, out).astype(xp.int64)
            # a double that leaves the lane has left decimal(38) or is
            # unrepresentable alike: Spark's answer there is NULL
            return out, over, None
        return out, D.exceeds(xp, out, dst), over
    # decimal -> numeric / boolean
    if dst == BOOL:
        return d != 0, None, None
    if np.issubdtype(dst.np_dtype, np.floating):
        return (d.astype(xp.float64) / float(10 ** src.scale)) \
            .astype(dst.np_dtype), None, None
    whole = xp.where(d < 0, -(abs(d) // 10 ** src.scale),
                     abs(d) // 10 ** src.scale)
    return whole.astype(dst.np_dtype), None, None


class Cast(Expression):
    device_type_sig = all_types  # per-pair support decided in reason check

    def __init__(self, child: Expression, dtype: DataType):
        self.children = [child]
        self.dtype = dtype

    def data_type(self, schema: Schema) -> DataType:
        return self.dtype

    def device_unsupported_reason(self, schema):
        src = self.children[0].data_type(schema)
        if not src.device_backed or not self.dtype.device_backed:
            return (f"cast {src.name} -> {self.dtype.name} runs on host "
                    f"(string/nested path)")
        return None

    def decimal_checks(self, schema):
        src = self.children[0].data_type(schema)
        frm = D.operand_type(src)
        return int(isinstance(self.dtype, DecimalType) and frm is not None
                   and self.dtype.scale > frm.scale)

    def eval_device(self, ctx):
        src = self.children[0].data_type(ctx.schema)
        c = self.children[0].eval_device(ctx)
        dst = self.dtype
        d = c.data
        if src == dst:
            return c
        if isinstance(src, DecimalType) or isinstance(dst, DecimalType):
            out, null, over = _decimal_cast(jnp, d, src, dst)
            valid = c.validity
            if over is not None:
                # Spark would hold the number: flagged, the engine's
                # loud error
                over = jnp.logical_and(over, valid)
                D.note_overflow(over)
                null = over if null is None else jnp.logical_or(null, over)
            if null is not None:
                valid = jnp.logical_and(valid, jnp.logical_not(null))
                out = jnp.where(null, jnp.zeros_like(out), out)
            return DVal(out, valid, dst)
        if dst == BOOL:
            out = d != 0
        elif src == BOOL:
            out = d.astype(dst.np_dtype)
        elif src == DATE and dst == TIMESTAMP:
            out = d.astype(jnp.int64) * _MICROS_PER_DAY
        elif src == TIMESTAMP and dst == DATE:
            out = jnp.floor_divide(d, _MICROS_PER_DAY).astype(jnp.int32)
        elif (jnp.issubdtype(d.dtype, jnp.floating)
              and np.issubdtype(dst.np_dtype, np.integer)):
            out = _float_to_int_java(d, dst.np_dtype, jnp)
        else:
            out = d.astype(dst.np_dtype)
        return DVal(out, c.validity, dst)

    def eval_host(self, batch):
        import pyarrow as pa
        import pyarrow.compute as pc
        from ..types import to_arrow
        src = self.children[0].data_type(batch.schema)
        arr = self.children[0].eval_host(batch)
        dst = self.dtype
        if src == dst:
            return arr
        if src.device_backed and dst.device_backed:
            # mirror the device semantics exactly with numpy
            v, ok = arrow_to_masked_numpy(arr)
            if isinstance(src, DecimalType) or isinstance(dst, DecimalType):
                wide = v.dtype == object
                if not wide:
                    out, null, over = _decimal_cast(np, v, src, dst)
                    wide = over is not None and (over & ok).any()
                if wide:
                    out, null, _ = _decimal_cast(np, v.astype(object), src,
                                                 dst, wide=True)
                if null is not None:
                    ok = ok & ~np.asarray(null, dtype=bool)
                    out = np.where(ok, out, 0)
                return masked_numpy_to_arrow(out, ok, dst)
            if dst == BOOL:
                out = v != 0
            elif src == BOOL:
                out = v.astype(dst.np_dtype)
            elif src == DATE and dst == TIMESTAMP:
                out = v.astype("datetime64[D]").astype("datetime64[us]") \
                    if v.dtype.kind == "M" else v.astype(np.int64) * _MICROS_PER_DAY
            elif src == TIMESTAMP and dst == DATE:
                iv = v.astype(np.int64) if v.dtype.kind != "M" else \
                    v.astype("datetime64[us]").astype(np.int64)
                out = np.floor_divide(iv, _MICROS_PER_DAY).astype(np.int32)
            elif (np.issubdtype(v.dtype, np.floating)
                  and np.issubdtype(dst.np_dtype, np.integer)):
                out = _float_to_int_java(v, dst.np_dtype, np)
            else:
                out = v.astype(dst.np_dtype)
            return masked_numpy_to_arrow(out, ok, dst)
        # string/nested paths via Arrow cast (best-effort Spark compat)
        if dst == STRING:
            if pa.types.is_floating(arr.type):
                # Spark formats doubles with trailing .0; arrow matches closely
                return pc.cast(arr, pa.string())
            return pc.cast(arr, pa.string())
        try:
            return pc.cast(arr, to_arrow(dst), safe=False)
        except pa.ArrowInvalid:
            # Spark non-ANSI: unparseable -> null
            py = arr.to_pylist()
            out = []
            for x in py:
                try:
                    out.append(None if x is None else
                               _py_cast(x, dst))
                except (ValueError, TypeError):
                    out.append(None)
            return pa.array(out, type=to_arrow(dst))

    def key(self):
        return f"cast({self.children[0].key()} as {self.dtype.name})"

    @property
    def name_hint(self):
        return f"CAST({self.children[0].name_hint} AS {self.dtype.name})"


def _py_cast(x, dst: DataType):
    if dst.np_dtype is not None and np.issubdtype(dst.np_dtype, np.integer):
        return int(float(x))
    if dst.np_dtype is not None and np.issubdtype(dst.np_dtype, np.floating):
        return float(x)
    if dst == BOOL:
        s = str(x).strip().lower()
        if s in ("t", "true", "y", "yes", "1"):
            return True
        if s in ("f", "false", "n", "no", "0"):
            return False
        raise ValueError(s)
    return str(x)
