"""Device strings as dense byte rectangles — the HIGH-cardinality string
representation (VERDICT r3 #4; ref stringFunctions.scala:1-2377, where
cudf holds strings device-side in an offset+chars layout).

Low-cardinality strings stay dictionary codes (DictColumn — transforms
evaluate once per distinct value). Past the dictionary crossover the r3
design collapsed, so rectangle columns carry EVERY row's bytes in HBM:

  bytes_[P, W] uint8   zero-padded past each row's length
  lengths[P]   int32   byte length per row (ASCII-gated: byte == char)
  validity[P]  bool

The XLA-friendly choices:
  * W is a small static bucket (8/16/32/64/... up to rect.maxBytes) —
    transforms are axis-1 vectorized ops over [P, W], no ragged buffers;
  * grouping/sorting packs each 8 bytes into one order-preserving int64
    word (big-endian, sign bit flipped), so a W-byte key is W/8 sort
    operands and the existing sort-based groupby machinery applies;
  * non-ASCII batches fall back to the host path honestly (case mapping
    and char semantics beyond ASCII need real Unicode tables — the
    reference leans on cudf's; a bad fast path would be silently wrong).
"""
from __future__ import annotations

from typing import Optional, Tuple

import numpy as np

from ..config import register
from ..types import STRING
from .column import DeviceColumn
from .transfer import traced_device_get

__all__ = ["ByteRectColumn", "encode_string_rect", "RECT_MAX_BYTES",
           "rect_width_bucket", "pack_words", "unpack_words",
           "decode_rect_numpy"]

_LANE_JIT = {}

RECT_MAX_BYTES = register(
    "spark.rapids.tpu.sql.string.rect.maxBytes", 64,
    "Width cap for the device byte-rectangle string layout: columns "
    "whose longest value exceeds this stay host-resident (HBM cost is "
    "rows*width; cudf's ragged layout has no such cap but also no XLA "
    "static shapes). Power of two.")

def rect_width_bucket(max_len: int, cap: int) -> Optional[int]:
    """Smallest power-of-two width >= max_len (floor 8), or None past the
    cap. The ladder is unbounded below the CALLER's cap — merge-path
    re-encodes pass a huge cap because grouping consistency beats HBM
    economy there."""
    w = 8
    while w < max_len:
        w <<= 1
    return w if w <= cap else None


_WIDTH_BUCKETS = (8, 16, 32, 64, 128, 256)   # first-ingest ladder (docs)


def encode_string_rect(col, n: int, padded: int, cap: int):
    """pa.StringArray -> (rect uint8[P, W], lengths int32[P],
    valid bool[P], ascii_only) or None when too wide. Vectorized host
    encode: one flat byte copy, no per-row Python."""
    import pyarrow as pa
    if n == 0:
        w = _WIDTH_BUCKETS[0]
        return (np.zeros((padded, w), np.uint8),
                np.zeros(padded, np.int32), np.zeros(padded, bool), True)
    arr = col
    if isinstance(arr, pa.ChunkedArray):
        arr = arr.combine_chunks()
    valid_n = ~np.asarray(arr.is_null())
    arr = arr.fill_null("")
    # offsets/data straight from the arrow buffers (large_string widened)
    if pa.types.is_large_string(arr.type):
        arr = arr.cast(pa.string())
    bufs = arr.buffers()
    offsets = np.frombuffer(bufs[1], np.int32,
                            count=len(arr) + 1 + arr.offset)[arr.offset:]
    data = np.frombuffer(bufs[2], np.uint8) if bufs[2] is not None \
        else np.zeros(0, np.uint8)
    lens = (offsets[1:] - offsets[:-1]).astype(np.int32)
    max_len = int(lens.max()) if len(lens) else 0
    w = rect_width_bucket(max_len, cap)
    if w is None:
        return None
    rect = np.zeros((padded, w), np.uint8)
    # flat scatter: target positions row*W + col for every source byte
    total = int(offsets[-1] - offsets[0])
    if total:
        rows = np.repeat(np.arange(n, dtype=np.int64), lens)
        within = (np.arange(total, dtype=np.int64)
                  - np.repeat((offsets[:-1] - offsets[0]).astype(np.int64),
                              lens))
        rect.reshape(-1)[rows * w + within] = \
            data[offsets[0]:offsets[0] + total]
    lengths = np.zeros(padded, np.int32)
    lengths[:n] = lens
    valid = np.zeros(padded, bool)
    valid[:n] = valid_n
    ascii_only = bool((rect < 0x80).all())
    return rect, lengths, valid, ascii_only


def decode_rect_numpy(rect: np.ndarray, lengths: np.ndarray,
                      valid: np.ndarray, num_rows: int):
    """Host rect -> pa.StringArray (one pass through arrow's builder)."""
    import pyarrow as pa
    r = rect[:num_rows]
    ln = lengths[:num_rows].astype(np.int64)
    v = valid[:num_rows]
    ln = np.where(v, ln, 0)
    w = r.shape[1] if r.ndim == 2 else 0
    mask = np.arange(w, dtype=np.int64)[None, :] < ln[:, None]
    flat = r[mask]                       # concatenated live bytes
    offsets = np.zeros(num_rows + 1, np.int32)
    np.cumsum(ln, out=offsets[1:])
    nulls = int((~v).sum())
    return pa.StringArray.from_buffers(
        num_rows, pa.py_buffer(offsets.tobytes()),
        pa.py_buffer(flat.tobytes()),
        (pa.py_buffer(np.packbits(v, bitorder="little").tobytes())
         if nulls else None),
        nulls)


def pack_words(bytes_, lengths):
    """uint8[P, W] -> order-preserving int64 words [P, W/8]: big-endian
    byte packing so integer comparison equals bytewise (UTF-8/codepoint)
    comparison; the sign bit is flipped so the SIGNED sort order matches
    the unsigned byte order. Bytes past each row's length are zero in the
    rectangle, which compares below every real byte — so shorter strings
    sort before their extensions, exactly the string order."""
    import jax.numpy as jnp
    p, w = bytes_.shape
    nw = max(w // 8, 1)
    words = []
    for k in range(nw):
        word = jnp.zeros(bytes_.shape[:1], jnp.int64)
        for j in range(8):
            word = (word << 8) | bytes_[:, k * 8 + j].astype(jnp.int64)
        # flip the sign bit: unsigned byte order in the signed domain
        words.append(word ^ jnp.int64(np.int64(-0x8000000000000000)))
    return words


def unpack_words(words, width: int):
    """Inverse of pack_words -> uint8[P, W]."""
    import jax.numpy as jnp
    cols = []
    for k, word in enumerate(words):
        u = word ^ jnp.int64(np.int64(-0x8000000000000000))
        for j in range(8):
            shift = 8 * (7 - j)
            cols.append(((u >> shift) & 0xFF).astype(jnp.uint8))
    return jnp.stack(cols[:width], axis=1)


def one_width(cols):
    """One column's rectangles out of several batches, each padded to the
    widest's width and all marked ASCII only where every one is: they then
    have the same word lanes, and any of them rebuilds the others' rows."""
    import jax.numpy as jnp
    width = max(c.width for c in cols)
    asc = all(c.ascii_only for c in cols)
    return [ByteRectColumn(
        jnp.pad(c.data, ((0, 0), (0, width - c.width))), c.validity,
        c.lengths, ascii_only=asc) for c in cols]


class ByteRectColumn(DeviceColumn):
    """STRING column living in HBM as a byte rectangle (module doc)."""

    __slots__ = ("lengths", "ascii_only")

    def __init__(self, data, validity, lengths, ascii_only: bool = True,
                 host_mirror=None):
        super().__init__(data, validity, STRING, host_mirror=host_mirror)
        self.lengths = lengths
        self.ascii_only = ascii_only

    @property
    def width(self) -> int:
        return int(self.data.shape[1])

    @property
    def padded_len(self) -> int:
        return int(self.data.shape[0])

    def nbytes(self) -> int:
        return int(self.data.size + self.validity.size + 4 * self.lengths.size)

    def with_arrays(self, data, validity) -> "DeviceColumn":
        # row-rearranging kernels move (bytes, lengths) together via
        # kernel_lanes()/from_lanes(); a caller handing back only 1-D
        # data is moving some DERIVED column, not this rectangle
        raise TypeError("ByteRectColumn rows move via kernel_lanes")

    # -- rearranging-kernel interop (the ListColumn lane protocol:
    # exprs/compiler._lane_pairs): the rectangle rides variadic 1-D row
    # kernels as W/8 order-preserving int64 word lanes + the length lane
    def kernel_lanes(self):
        import jax
        key = ("lanes", self.width)
        fn = _LANE_JIT.get(key)
        if fn is None:
            def mk(bytes_, lengths):
                return tuple(pack_words(bytes_, lengths))
            fn = _LANE_JIT[key] = jax.jit(mk)
        words = fn(self.data, self.lengths)
        return ([(w, self.validity) for w in words]
                + [(self.lengths, self.validity)])

    def from_lanes(self, outs):
        import jax
        words = tuple(d for d, _ in outs[:-1])
        lengths, validity = outs[-1]
        key = ("unlanes", self.width, len(words))
        fn = _LANE_JIT.get(key)
        if fn is None:
            w = self.width

            def mk(ws, ln):
                return unpack_words(list(ws), w), ln.astype("int32")
            fn = _LANE_JIT[key] = jax.jit(mk)
        bytes_, ln = fn(words, lengths)
        return ByteRectColumn(bytes_, validity, ln,
                              ascii_only=self.ascii_only)

    def strval(self):
        from ..exprs.base import DVal, StrVal
        return DVal(StrVal(self.data, self.lengths), self.validity, STRING)

    def to_numpy(self, num_rows: int):
        rect, ln, v = (a[:num_rows] for a in traced_device_get(
            (self.data, self.lengths, self.validity)))
        w = rect.shape[1]
        mask = np.arange(w)[None, :] < np.where(v, ln, 0)[:, None]
        vals = np.empty(num_rows, object)
        # bulk decode: join on the flat live bytes with per-row splits
        flat = rect[mask].tobytes()
        offs = np.zeros(num_rows + 1, np.int64)
        np.cumsum(np.where(v, ln, 0), out=offs[1:])
        for i in range(num_rows):
            vals[i] = flat[offs[i]:offs[i + 1]].decode("utf-8",
                                                       "replace")
        return vals, v

    def to_arrow(self, num_rows: int):
        if self.host_mirror is not None:
            return self.host_mirror.slice(0, num_rows)
        rect, ln, v = traced_device_get(
            (self.data, self.lengths, self.validity))
        return decode_rect_numpy(rect, ln, v, num_rows)

    def arrow_from_host(self, d, v):
        # d arrives as the fetched rectangle rows when the batched sink
        # fetch resolved this column (packing flattens 2-D arrays)
        if isinstance(d, np.ndarray) and d.ndim == 2:
            ln = traced_device_get(self.lengths)[:len(d)]
            return decode_rect_numpy(d, ln, np.asarray(v), len(d))
        return super().arrow_from_host(d, v)

    def __repr__(self):
        return (f"ByteRectColumn(w={self.width}, "
                f"padded={self.padded_len}, ascii={self.ascii_only})")
