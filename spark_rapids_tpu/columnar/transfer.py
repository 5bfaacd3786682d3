"""Host->device transfer compression for batch ingest.

Ingest bytes are a first-order cost of every query that scans (H2D
bandwidth of the attached chip: not measured). TPC-shaped data is
massively narrowable: dates span ~2.5k days (int32 -> uint16+offset),
quantities/discounts are small ints or 2-decimal fixed-point doubles
(float64 -> int8/int16/int32 + scale), dictionary codes have tiny
cardinality (int32 -> uint8), and validity is usually all-true (dropped)
or bitpackable 8:1.

Encodings are chosen per column ONLY when a host-side check proves the
device decode reproduces identical bits (the decode formula is evaluated
on the host with the same IEEE ops). The decode runs as ONE fused XLA
kernel right after the single device_put, costing one extra dispatch —
worth it only above a size threshold, so small batches keep the raw path.

Reference analog: the GPU parquet reader ships compressed pages to the
device and decodes there (GpuParquetScan.scala Table.readParquet); this is
the same move for in-memory ingest, with XLA as the decoder.
"""
from __future__ import annotations

import functools
from typing import List, Optional, Tuple

import numpy as np

from ..trace import core as trace_core

__all__ = ["encode_columns", "decode_with_len", "worthwhile", "RAW",
           "traced_device_put", "traced_device_get"]

RAW = ("raw",)

#: encoded batch must be at most this fraction of raw bytes to pay for
#: the extra decode dispatch
_WORTH_RATIO = 0.6
#: and the raw batch at least this big (small batches: dispatch dominates)
MIN_RAW_BYTES = 4 << 20

#: f64 columns NEVER narrow: the TPU backend's emulated f64 is not
#: bit-exact for division (5/100.0 < 0.05) NOR for int->f64 conversion
#: (measured wrong bits even in int32 range), so any value-recomputing
#: decode would shift comparison results at band edges. The host-side
#: exactness proof only covers ops the device computes identically —
#: for floats that is the raw bit-copy alone. Integer ops (add, astype
#: between int widths) are exact on device (verified), so ints, dates,
#: bools, dict codes, and validity still narrow.
_F64_INV_SCALES = ()


def _narrow_int(rng: int):
    if rng < (1 << 8):
        return np.uint8
    if rng < (1 << 16):
        return np.uint16
    if rng < (1 << 31):
        return np.int32
    return None


def encode_column(data: np.ndarray, valid: np.ndarray):
    """(padded data, padded validity) -> (host arrays, spec, params).

    spec is a STATIC tuple (kernel cache key); params are per-batch traced
    scalars (offset, scale) so varying data never recompiles. Returns
    (arrays=[data_enc] or [], spec, params, vspec, varrays) with validity
    handled separately."""
    # -- validity ----------------------------------------------------------
    if valid.all():
        vspec, varrays = ("valid_all",), []
    elif not valid.any():
        vspec, varrays = ("valid_none",), []
    else:
        vspec, varrays = ("valid_bits",), [np.packbits(valid)]

    n_valid = int(valid.sum())
    if n_valid == 0:
        return [], ("zero", data.dtype.str), (), vspec, varrays

    dt = data.dtype
    if dt == np.bool_:
        return ([np.packbits(data & valid)], ("bool_bits",), (),
                vspec, varrays)

    if np.issubdtype(dt, np.integer):
        vmin = int(data[valid].min())
        vmax = int(data[valid].max())
        enc_dt = _narrow_int(vmax - vmin)
        if enc_dt is None or np.dtype(enc_dt).itemsize >= dt.itemsize:
            return [data], RAW, (), vspec, varrays
        enc = np.zeros(data.shape, enc_dt)
        enc[valid] = (data[valid].astype(np.int64)
                      - vmin).astype(enc_dt)
        return ([enc], ("int_off", dt.str, enc_dt().dtype.str),
                (np.int64(vmin),), vspec, varrays)

    if dt == np.float64:
        v = data[valid]
        if not np.isfinite(v).all():
            return [data], RAW, (), vspec, varrays
        for inv in _F64_INV_SCALES:
            s = v * inv
            r = np.round(s)
            if not (np.abs(r) < (1 << 62)).all():
                continue
            ri = r.astype(np.int64)
            vmin = int(ri.min())
            rng = int(ri.max()) - vmin
            enc_dt = _narrow_int(rng)
            if enc_dt is None:
                continue
            # exactness proof: the DEVICE decode formula evaluated on the
            # host must reproduce the input bit-for-bit
            back = (ri - vmin + vmin).astype(np.float64) / inv
            if not np.array_equal(back, v):
                continue
            enc = np.zeros(data.shape, enc_dt)
            enc[valid] = (ri - vmin).astype(enc_dt)
            return ([enc], ("f64_scaled", enc_dt().dtype.str),
                    (np.int64(vmin), np.float64(inv)), vspec, varrays)
        return [data], RAW, (), vspec, varrays

    return [data], RAW, (), vspec, varrays


def encode_columns(pairs: List[Tuple[np.ndarray, np.ndarray]]):
    """[(padded data, padded validity)] -> (flat host arrays, specs,
    flat params, saved_ratio). specs is the static kernel key."""
    flat: List[np.ndarray] = []
    params: List = []
    specs: List = []
    raw_bytes = enc_bytes = 0
    for d, v in pairs:
        arrays, spec, ps, vspec, varrays = encode_column(d, v)
        raw_bytes += d.nbytes + v.nbytes
        enc_bytes += sum(a.nbytes for a in arrays + varrays)
        specs.append((spec, vspec, len(arrays), len(varrays), len(ps)))
        flat.extend(arrays)
        flat.extend(varrays)
        params.extend(ps)
    ratio = enc_bytes / max(raw_bytes, 1)
    return flat, tuple(specs), params, ratio, raw_bytes


def worthwhile(ratio: float, raw_bytes: int) -> bool:
    return raw_bytes >= MIN_RAW_BYTES and ratio <= _WORTH_RATIO


@functools.lru_cache(maxsize=256)
def _decode_kernel(specs, padded_len: int):
    import jax
    import jax.numpy as jnp

    def unpack_bits(bits, p):
        # bits: uint8[ceil(p/8)] -> bool[p] (elementwise, no gather)
        b = bits[:, None] >> (7 - jnp.arange(8, dtype=jnp.uint8))
        return (b & 1).astype(jnp.bool_).reshape(-1)[:p]

    @jax.jit
    def decode(arrays, params):
        ai = pi = 0
        out = []
        for spec, vspec, n_a, n_v, n_p in specs:
            a = arrays[ai:ai + n_a]
            va = arrays[ai + n_a:ai + n_a + n_v]
            ps = params[pi:pi + n_p]
            ai += n_a + n_v
            pi += n_p
            if vspec == ("valid_all",):
                valid = jnp.ones(padded_len, jnp.bool_)
            elif vspec == ("valid_none",):
                valid = jnp.zeros(padded_len, jnp.bool_)
            else:
                valid = unpack_bits(va[0], padded_len)
            kind = spec[0]
            if kind == "raw":
                data = a[0]
            elif kind == "zero":
                data = jnp.zeros(padded_len, dtype=np.dtype(spec[1]))
            elif kind == "bool_bits":
                data = unpack_bits(a[0], padded_len)
            elif kind == "int_off":
                tgt = np.dtype(spec[1])
                off = ps[0]
                data = (a[0].astype(jnp.int64) + off).astype(tgt)
                data = jnp.where(valid, data, jnp.zeros((), tgt))
            elif kind == "f64_scaled":
                off, inv = ps
                data = ((a[0].astype(jnp.int64) + off)
                        .astype(jnp.float64) / inv)
                data = jnp.where(valid, data, 0.0)
            else:  # pragma: no cover
                raise ValueError(spec)
            out.append((data, valid))
        return out

    return decode


def decode_with_len(dev_arrays, specs, params, padded_len: int):
    """One fused decode dispatch over the already-transferred arrays."""
    import jax.numpy as jnp
    return _decode_kernel(specs, padded_len)(
        tuple(dev_arrays), tuple(jnp.asarray(p) for p in params))


# ---------------------------------------------------------------------------
# traced transfers (trace/core.py): H2D/D2H time + bytes attribution
# ---------------------------------------------------------------------------

def traced_device_put(host_arrays, label: str = "h2d"):
    """``jax.device_put`` with H2D attribution when tracing is on: a
    ``<label>.dispatch`` span around the ENQUEUE (what the query thread
    pays; the put stays asynchronous, the transfer itself is on the
    device trace) and the ``h2d.bytes`` counter. When tracing is off
    this is exactly one branch around a plain device_put."""
    import jax
    tr = trace_core.TRACER
    if tr is None:
        return jax.device_put(host_arrays)
    nbytes = sum(getattr(a, "nbytes", 0) for a in host_arrays)
    with tr.span(f"{label}.dispatch", cat="transfer",
                 args={"bytes": nbytes, "arrays": len(host_arrays)}):
        out = jax.device_put(host_arrays)
    tr.counter("h2d.bytes", {"bytes": nbytes}, cat="transfer")
    return out


def traced_device_get(arrays, label: str = "d2h"):
    """``jax.device_get`` — THE blocking fetch of the query path: the
    host waits for the chip to finish what ``arrays`` (an array or a
    pytree of arrays) depend on, then copies them. With tracing on, a
    ``<label>.transfer`` span covers that wait and the ``d2h.bytes``
    counter counts what came back; off, one branch around the get."""
    import jax
    tr = trace_core.TRACER
    if tr is None:
        return jax.device_get(arrays)
    nbytes = sum(getattr(a, "nbytes", 0)
                 for a in jax.tree_util.tree_leaves(arrays))
    with tr.span(f"{label}.transfer", cat="transfer",
                 args={"bytes": nbytes}):
        out = jax.device_get(arrays)
    tr.counter("d2h.bytes", {"bytes": nbytes}, cat="transfer")
    return out
