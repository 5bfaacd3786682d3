"""Columnar vectors: HBM-resident (jax.Array) and host (Arrow) columns.

TPU-native re-design of the reference's columnar data layer
(GpuColumnVector.java:40 device vector over cudf; RapidsHostColumnVector for
host side). On TPU a column is:

  * ``DeviceColumn`` — a dense ``jax.Array`` ``data`` padded to a shape bucket
    plus a ``validity`` bool mask (False for nulls AND for padding rows).
    Registered as a pytree so whole batches flow through ``jax.jit``.
  * ``HostColumn``  — a pyarrow Array for types XLA cannot hold densely
    (strings, binary, nested). The planner's TypeSig tagging routes
    expressions over these to vectorized host kernels (honest CPU fallback,
    the analog of the reference's per-type fallback tagging).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..types import (DataType, DecimalType, STRING, TIMESTAMP, DATE,
                     from_arrow, to_arrow)
from .transfer import traced_device_get

__all__ = ["DeviceColumn", "HostColumn", "Column"]


class DeviceColumn:
    """A typed device vector: ``data`` + ``validity`` jax arrays of equal
    (padded) length. Slots where validity is False hold the dtype's default
    value so arithmetic never sees garbage (NaN-free padding)."""

    __slots__ = ("data", "validity", "dtype", "host_mirror")

    def __init__(self, data, validity, dtype: DataType, host_mirror=None):
        self.data = data
        self.validity = validity
        self.dtype = dtype
        #: the SOURCE arrow array this column was ingested from, when the
        #: device content is a verbatim padded copy of it. Materialization
        #: serves a prefix slice of the mirror instead of a D2H fetch.
        #: Any transform that
        #: rearranges rows goes through with_arrays(), which drops it.
        self.host_mirror = host_mirror

    # -- constructors ------------------------------------------------------
    @staticmethod
    def host_prepare(values: np.ndarray, dtype: DataType,
                     mask: Optional[np.ndarray] = None,
                     padded_len: Optional[int] = None):
        """Build the padded host (data, validity) numpy pair for a column —
        split from the device transfer so callers can batch many columns
        into ONE device_put (each blocking transfer pays its own
        latency)."""
        n = len(values)
        p = padded_len if padded_len is not None else n
        if p < n:
            raise ValueError("padded_len < len(values)")
        np_dt = dtype.np_dtype
        assert np_dt is not None, f"{dtype} is not device-backed"
        out = np.zeros(p, dtype=np_dt)
        vals = np.asarray(values).astype(np_dt, copy=False)
        valid = np.zeros(p, dtype=np.bool_)
        if mask is None:
            out[:n] = vals
            valid[:n] = True
        else:
            m = np.asarray(mask, dtype=np.bool_)
            out[:n] = np.where(m, vals, np_dt.type(0))
            valid[:n] = m
        return out, valid

    @staticmethod
    def from_numpy(values: np.ndarray, dtype: DataType,
                   mask: Optional[np.ndarray] = None,
                   padded_len: Optional[int] = None) -> "DeviceColumn":
        out, valid = DeviceColumn.host_prepare(values, dtype, mask,
                                               padded_len)
        return DeviceColumn(jnp.asarray(out), jnp.asarray(valid), dtype)

    @staticmethod
    def all_valid(data, dtype: DataType) -> "DeviceColumn":
        return DeviceColumn(data, jnp.ones(data.shape, dtype=jnp.bool_), dtype)

    def with_arrays(self, data, validity) -> "DeviceColumn":
        """Rebuild this column around row-rearranged arrays (gather /
        compact / concat) — subclasses carry their extra state across."""
        return DeviceColumn(data, validity, self.dtype)

    # -- properties --------------------------------------------------------
    @property
    def padded_len(self) -> int:
        return int(self.data.shape[0])

    @property
    def device_backed(self) -> bool:
        return True

    def nbytes(self) -> int:
        return int(self.data.size * self.data.dtype.itemsize + self.validity.size)

    # -- host materialization ---------------------------------------------
    def to_numpy(self, num_rows: int):
        """Return (values, validity) host arrays truncated to num_rows."""
        d, v = traced_device_get((self.data, self.validity))
        return d[:num_rows], v[:num_rows]

    def arrow_from_host(self, d: np.ndarray, v: np.ndarray):
        """Assemble the arrow array from already-fetched host (data,
        validity) — the fetch itself is batched at the ColumnarBatch level
        (one device_get round trip for the whole batch)."""
        return arrow_from_numpy(d, v, self.dtype)

    def to_arrow(self, num_rows: int):
        if self.host_mirror is not None:
            # serve the exact source bits: besides skipping the D2H
            # fetch, this is a CORRECTNESS requirement for f64 — the
            # backend's emulated float64 carries ~48 mantissa bits, so a
            # device round trip of an untouched column would hand host
            # expressions values 1 ulp off (q6's `discount >= 0.05`
            # silently dropped every boundary row on the host engine)
            return self.host_mirror.slice(0, num_rows)
        d, v = self.to_numpy(num_rows)
        return self.arrow_from_host(d, v)

    def __repr__(self):
        return f"DeviceColumn({self.dtype.name}, padded={self.padded_len})"


def arrow_from_numpy(d: np.ndarray, v: np.ndarray, dtype: DataType):
    """Host (data, validity) numpy pair -> arrow array of the declared
    logical type (shared by every D2H materialization path)."""
    import pyarrow as pa
    at = to_arrow(dtype)
    if dtype == TIMESTAMP:
        return pa.Array.from_pandas(d, mask=~v).cast(pa.int64()).cast(at)
    if dtype == DATE:
        return pa.Array.from_pandas(d, mask=~v).cast(pa.int32()).cast(at)
    if isinstance(dtype, DecimalType):
        # int64 lanes -> decimal128 of the declared type: host work the
        # float path does not have, so it is a span of its own
        # (d2h.decimal.finish: billed as fetch time; docs/profiling.md)
        from ..exprs.decimal_rules import unscaled_to_arrow
        from ..trace import core as trace_core
        tr = trace_core.TRACER
        if tr is None:
            return unscaled_to_arrow(d, v, dtype)
        with tr.span("d2h.decimal.finish", cat="transfer",
                     args={"rows": int(len(d))}):
            return unscaled_to_arrow(d, v, dtype)
    return pa.Array.from_pandas(d, mask=~v, type=at)


def _flatten_device_column(c: DeviceColumn):
    return (c.data, c.validity), c.dtype


def _unflatten_device_column(dtype, children):
    data, validity = children
    return DeviceColumn(data, validity, dtype)


jax.tree_util.register_pytree_node(
    DeviceColumn, _flatten_device_column, _unflatten_device_column)


class DictColumn(DeviceColumn):
    """A STRING column living in HBM as dictionary codes.

    TPU-first design for SURVEY.md hard-part #2 (strings in HBM without
    cudf): ``data`` holds int32 codes into a SORTED host-side dictionary,
    so equality AND relative order of codes match the string semantics
    (UTF-8 byte order == codepoint order). Row-rearranging device kernels
    (filter compaction, join gathers, partition scatter) move the codes
    like any fixed-width column — strings never round-trip through the
    host on the hot path; only final materialization decodes.

    The reference holds strings in device memory via cudf's offset+char
    layout; codes+dictionary is the XLA-friendly equivalent (static
    widths, MXU/VPU-amenable, no ragged buffers)."""

    __slots__ = ("dictionary",)

    def __init__(self, data, validity, dtype: DataType,
                 dictionary: np.ndarray, host_mirror=None):
        super().__init__(data, validity, dtype, host_mirror=host_mirror)
        self.dictionary = dictionary     # np object/str array, sorted

    def with_arrays(self, data, validity) -> "DictColumn":
        return DictColumn(data, validity, self.dtype, self.dictionary)

    def to_numpy(self, num_rows: int):
        codes, v = super().to_numpy(num_rows)
        vals = self.dictionary[np.clip(codes, 0, len(self.dictionary) - 1)] \
            if len(self.dictionary) else np.full(len(codes), "", object)
        return vals, v

    def arrow_from_host(self, d: np.ndarray, v: np.ndarray):
        """``d`` holds CODES here (what lives on device), not strings."""
        import pyarrow as pa
        if not len(self.dictionary):
            return pa.nulls(len(d), type=pa.string())
        idx = pa.array(np.clip(d, 0, len(self.dictionary) - 1)
                       .astype(np.int64), mask=~v)
        return pa.array(self.dictionary, type=pa.string()).take(idx)

    def to_arrow(self, num_rows: int):
        if self.host_mirror is not None:
            return self.host_mirror.slice(0, num_rows)
        codes, v = traced_device_get((self.data, self.validity))
        return self.arrow_from_host(codes[:num_rows], v[:num_rows])

    def __repr__(self):
        return (f"DictColumn(card={len(self.dictionary)}, "
                f"padded={self.padded_len})")


def _flatten_dict_column(c: DictColumn):
    return (c.data, c.validity), (c.dtype, c.dictionary)


def _unflatten_dict_column(aux, children):
    dtype, dictionary = aux
    data, validity = children
    return DictColumn(data, validity, dtype, dictionary)


jax.tree_util.register_pytree_node(
    DictColumn, _flatten_dict_column, _unflatten_dict_column)


class HostColumn:
    """Arrow-backed host column for types without a dense device layout.

    Reference analog: RapidsHostColumnVector + the per-type CPU fallback the
    TypeSig machinery makes cheap to express (SURVEY.md section 7 hard part #2).
    """

    __slots__ = ("array", "dtype")

    def __init__(self, array, dtype: Optional[DataType] = None):
        import pyarrow as pa
        if isinstance(array, pa.ChunkedArray):
            array = array.combine_chunks()
        self.array = array
        self.dtype = dtype if dtype is not None else from_arrow(array.type)

    @staticmethod
    def from_pylist(values, dtype: DataType = STRING) -> "HostColumn":
        import pyarrow as pa
        return HostColumn(pa.array(values, type=to_arrow(dtype)), dtype)

    @property
    def device_backed(self) -> bool:
        return False

    @property
    def padded_len(self) -> int:
        return len(self.array)

    def nbytes(self) -> int:
        return self.array.nbytes

    def to_arrow(self, num_rows: int):
        return self.array.slice(0, num_rows)

    def to_numpy(self, num_rows: int):
        a = self.array.slice(0, num_rows)
        v = ~np.asarray(a.is_null())
        return a.to_numpy(zero_copy_only=False), v

    def __repr__(self):
        return f"HostColumn({self.dtype.name}, len={len(self.array)})"


Column = (DeviceColumn, HostColumn)
