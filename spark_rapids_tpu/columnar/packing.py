"""Batched device->host fetch in (at most) two transfers.

Every array fetched pays per-transfer latency, and `jax.device_get` of a
list waits leaf by leaf. This packs results
into TWO device buffers — a uint32 stream (32-bit types bitcast, bools
bit-packed 32:1, int64 split into lo/hi words by arithmetic shifts) and
one concatenated float64 buffer (this backend's X64-removal pass cannot
bitcast 64-bit element types at all, so f64 bits are unreachable in-graph;
a plain f64 fetch is still a single transfer).

The reference ships query results through JCudfSerialization host buffers
(GpuColumnarBatchSerializer.scala) — one contiguous buffer per table — for
the same reason.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["fetch_packed", "pack_traced", "unpack_streams"]


def _u32_words(dt: np.dtype, shape) -> int:
    count = int(np.prod(shape)) if shape else 1
    if dt == np.bool_:
        return (count + 31) // 32
    if dt.itemsize == 8:
        return count * 2
    return count


def pack_traced(flat):
    """Traceable packing — call INSIDE an operator kernel so results leave
    the device as two buffers with no extra dispatch.
    -> (u32 stream, f64 stream); f64 arrays contribute only to the
    second, everything else only to the first."""
    words = []
    f64s = []
    for a in flat:
        if a.ndim == 0:
            a = a[None]
        if a.dtype == jnp.float64:
            f64s.append(a)
            continue
        if a.dtype == jnp.bool_:
            n = a.shape[0]
            k = (n + 31) // 32
            bits = jnp.zeros((k * 32,), jnp.uint32).at[:n].set(
                a.astype(jnp.uint32))
            w = bits.reshape(k, 32) << jnp.arange(32, dtype=jnp.uint32)
            words.append(jnp.sum(w, axis=1, dtype=jnp.uint32))
        elif a.dtype.itemsize == 8:      # i64/u64: arithmetic split
            ai = a.astype(jnp.int64)
            lo = (ai & 0xFFFFFFFF).astype(jnp.uint32)
            hi = ((ai >> 32) & 0xFFFFFFFF).astype(jnp.uint32)
            words.append(jnp.stack([lo, hi], axis=1).reshape(-1))
        elif a.dtype.itemsize == 4:
            words.append(jax.lax.bitcast_convert_type(a, jnp.uint32))
        elif jnp.issubdtype(a.dtype, jnp.floating):
            # f16/bf16: value-cast would drop fraction bits — carry the
            # raw 16-bit pattern instead
            words.append(jax.lax.bitcast_convert_type(a, jnp.uint16)
                         .astype(jnp.uint32))
        else:                            # 1/2-byte ints: widen (rare)
            words.append(a.astype(jnp.uint32))
    u32 = (jnp.concatenate(words) if words
           else jnp.zeros((0,), jnp.uint32))
    f64 = (jnp.concatenate(f64s) if f64s
           else jnp.zeros((0,), jnp.float64))
    return u32, f64


#: lazily resolved through the executable cache (exec_cache is the
#: blessed jit owner); the module-global memo keeps the per-fetch hit
#: path one attribute read — the compiler-front-memo idiom
_PACK = None


def _clear_pack() -> None:
    global _PACK
    _PACK = None


def _pack(flat):
    global _PACK
    # bind to a local: a concurrent exec_cache.clear() may null the
    # memo between the check and the call
    fn = _PACK
    if fn is None:
        from ..plan import exec_cache
        # front-memo contract: exec_cache.clear() must release THIS
        # strong reference too, or the dropped tier keeps serving
        exec_cache.register_clear_hook(_clear_pack)
        fn = _PACK = exec_cache.get_or_build_jit("columnar.pack_traced",
                                                 pack_traced)
    return fn(flat)


def unpack_streams(u32, f64, specs):
    """Host-side inverse of pack_traced; specs = [(np dtype, shape)]."""
    u32 = np.asarray(u32)
    f64 = np.asarray(f64)
    out = []
    woff = foff = 0
    for dt, shape in specs:
        count = int(np.prod(shape)) if shape else 1
        if dt == np.float64:
            arr = f64[foff:foff + count]
            foff += count
        else:
            w = _u32_words(dt, shape)
            raw = u32[woff:woff + w]
            woff += w
            if dt == np.bool_:
                bits = (raw[:, None] >> np.arange(32, dtype=np.uint32)) & 1
                arr = bits.reshape(-1)[:count].astype(bool)
            elif dt.itemsize == 8:
                pair = raw.reshape(-1, 2).astype(np.uint64)
                arr = ((pair[:, 1] << np.uint64(32)) | pair[:, 0]).view(dt)
            elif dt.itemsize == 4:
                arr = raw.view(dt)
            elif np.issubdtype(dt, np.floating) or dt.kind == 'V':
                arr = raw.astype(np.uint16).view(dt)
            else:
                arr = raw.astype(dt)
        out.append(arr.reshape(shape) if shape else arr[0])
    return out


def sum_counts(groups, label: str = "d2h"):
    """The sum of each group of row counts, a count a host int or a scalar
    still on the device: the device's all come in ONE packed transfer (none
    where every count is on the host). What a tracer counter that reports
    rows is built from."""
    groups = [list(g) if isinstance(g, (list, tuple)) else [g]
              for g in groups]
    lazy = [c for g in groups for c in g
            if not isinstance(c, (int, np.integer))]
    got = iter(fetch_packed(lazy, label)) if lazy else iter(())
    return [sum(int(c) if isinstance(c, (int, np.integer))
                else int(next(got)) for c in g) for g in groups]


def fetch_packed(arrays, label: str = "d2h"):
    """Fetch a list of device arrays in at most two transfers (span
    ``<label>.transfer``); returns numpy arrays with the original
    dtypes/shapes."""
    from ..trace import core as trace_core
    from .transfer import traced_device_get
    flat = tuple(arrays)
    specs = [(np.dtype(a.dtype), tuple(a.shape)) for a in flat]
    tr = trace_core.TRACER           # single branch when tracing is off
    if tr is None:
        packed = _pack(flat)
    else:
        with tr.span("d2h.dispatch", cat="transfer"):
            packed = _pack(flat)     # pack-kernel dispatch (async)
    u32, f64 = traced_device_get(packed, label)
    return unpack_streams(u32, f64, specs)
