"""TPU-native segmented reductions (the engine's groupby/join/window core).

Measured on TPU v5e: XLA lowers `jax.ops.segment_*` to scatter, and 1M-row
scatters serialize on the scalar core at ~15-77 ns/element — 72-155 ms per
segment-sum (emulated-64-bit tuple combiners are worst). Row-sized gathers
(`jnp.take` with 1M indices) cost ~15-45 ms for the same reason. Dense
one-hot masked reductions instead run on the vector units at HBM bandwidth:
~15 us per segment over 1M rows (0.3 ms for 12 groups, 15 ms for 1024).

Strategy implemented here:
  * ``num_segments <= DENSE_MAX``: one-hot broadcast + reduce. The
    ``gid[None, :] == iota[:, None]`` mask fuses into the reduction loop, so
    the [G, n] intermediate never materializes.
  * larger: scatter fallback (cheap when the row count is small, e.g. the
    merge pass over already-grouped partials; the 1M-row big-G case is
    handled by the sorted-segment scan pipeline in groupby_core).

Group-sized (output-sized) gathers and scatters stay: G <= 4096 elements on
the scalar core is ~60 us, which is noise.

The reference gets segmented reductions from cudf's hash-based groupby
(CUDA hash tables + atomics); there is no XLA analog of device atomics, and
emulating one via scatter is exactly the wrong shape for this hardware.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["DENSE_MAX", "SortedSegments", "GlobalSegments",
           "bucket_segments", "seg_sum", "seg_min", "seg_max",
           "seg_count", "onehot_gather"]

#: largest static segment count handled by the dense one-hot strategy
DENSE_MAX = 4096

#: static bucket sizes: kernels recompile only when the group-count estimate
#: crosses a bucket boundary (5 variants max), never per dictionary growth
_BUCKETS = (16, 64, 256, 1024, 4096)


def bucket_segments(n: int) -> int:
    """Smallest static bucket >= n (for jit static num_segments args)."""
    for b in _BUCKETS:
        if n <= b:
            return b
    return n




#: Hillis-Steele scans are UNROLLED with static shift distances. The
#: rolled form (lax.fori_loop whose body dynamic-slices by a traced
#: 1<<i) compiles pathologically on this backend WHEN COMPOSED WITH the
#: sort pipeline around it: sort+scan+sort measured 65-95 s to compile at
#: 262k rows (vs 24 s for the two sorts alone), multiplying per key and
#: per aggregate until the q28 merge kernel took >20 minutes. The same
#: pipeline with static-shift unrolled scans compiles in 15-17 s total.
#: (jnp.cumsum and lax.associative_scan are still worse: 191 s / 63 s at
#: 1M rows — see docs/performance.md.)


def prefix_sum(x, dtype=None):
    """Inclusive prefix sum via log2(n) static-shift/add passes."""
    v = x if dtype is None else x.astype(dtype)
    n = v.shape[0]
    zero = jnp.zeros((), v.dtype)
    d = 1
    while d < n:
        v = v + shift_static(v, d, zero)
        d <<= 1
    return v


def last_valid_scan(values, present):
    """Per row: the ``values`` entry at the most recent row (itself
    included) where ``present`` is True; rows before any present row keep
    their own value with present=False propagated. The vector-native way
    to broadcast a per-segment value (e.g. at segment starts) to every row
    without the group-table gather (~15-45 ms per 1M rows on TPU)."""
    v, p = values, present
    n = v.shape[0]
    zero = jnp.zeros((), v.dtype)
    d = 1
    while d < n:
        pv = shift_static(v, d, zero)
        pp = shift_static(p, d, False)
        v = jnp.where(p, v, pv)
        p = jnp.logical_or(p, pp)
        d <<= 1
    return v, p


def reverse_last_valid_scan(values, present):
    """last_valid_scan scanning right-to-left (broadcast from segment
    ENDS backward)."""
    v, p = last_valid_scan(jnp.flip(values), jnp.flip(present))
    return jnp.flip(v), jnp.flip(p)


def shift_static(arr, d: int, fill):
    """arr shifted by a STATIC distance (positive = right), fill-padded —
    a concatenate, not a gather."""
    if d == 0:
        return arr
    n = arr.shape[0]
    k = min(abs(d), n)
    pad = jnp.full((k,), fill, dtype=arr.dtype)
    if d > 0:
        return jnp.concatenate([pad, arr[:n - k]])
    return jnp.concatenate([arr[k:], pad])


def _dense_mask(gid, num_segments: int):
    """[G, n] one-hot mask; stays fused into the consuming reduction."""
    iota = jax.lax.broadcasted_iota(jnp.int32, (num_segments, gid.shape[0]),
                                    0)
    return gid.astype(jnp.int32)[None, :] == iota


class SortedSegments:
    """Segment context for rows already sorted by group key (groupby_core's
    sort pipeline). Segment reductions become Hillis-Steele segmented scans
    — log2(n) shift/combine passes, all vector ops — and each segment's
    aggregate lands at the segment's LAST row. Callers pass an instance in
    place of the ``gid`` array; every seg_* op dispatches on it and returns
    PER-ROW arrays (value at each row = scan up to that row). groupby_core
    extracts the per-segment results at the end positions with one shared
    compaction sort.

    ``live`` marks real rows (False = padding/filtered); dead rows
    contribute the combine-neutral to every scan and carry no boundary
    flags, so a trailing dead region just extends the last segment without
    changing its total.
    """

    def __init__(self, flags, live, orig_index=None):
        self.flags = flags            # bool[n]: True at segment starts
        self.live = live              # bool[n]
        #: original (pre-sort) row index per row — the rank FIRST/LAST
        #: select by; required when those aggregates run over this context
        self.orig_index = orig_index

    def _scan(self, v, combine, neutral):
        n = v.shape[0]
        neutral = jnp.asarray(neutral, dtype=v.dtype)
        f = self.flags
        d = 1
        while d < n:
            pv = shift_static(v, d, neutral)
            pf = shift_static(f, d, True)
            v = jnp.where(f, v, combine(pv, v))
            f = jnp.logical_or(f, pf)
            d <<= 1
        return v

    def sum(self, data, valid):
        ok = jnp.logical_and(valid, self.live)
        z = jnp.zeros((), dtype=data.dtype)
        masked = jnp.where(ok, data, z)
        return self._scan(masked, lambda a, b: a + b, 0)

    def min(self, data, valid):
        ok = jnp.logical_and(valid, self.live)
        big = _neutral_max(data.dtype)
        return self._scan(jnp.where(ok, data, big), jnp.minimum, big)

    def max(self, data, valid):
        ok = jnp.logical_and(valid, self.live)
        small = _neutral_min(data.dtype)
        return self._scan(jnp.where(ok, data, small), jnp.maximum, small)

    def count(self, pred, dtype=jnp.int64):
        ok = jnp.logical_and(pred, self.live)
        return self._scan(ok.astype(dtype), lambda a, b: a + b, 0)

    def select_by_rank(self, values, rank, valid, mode: str):
        """argmin/argmax scan: per row, the (values..., rank) of the valid
        row with the smallest (mode='min') / largest ('max') rank seen so
        far in the segment. Returns (selected_values list, sel_rank, ok).
        Used for FIRST/LAST (rank = original row index)."""
        ok = jnp.logical_and(valid, self.live)
        if mode == "min":
            neutral_r = _neutral_max(rank.dtype)
            better = lambda a, b: a <= b
        else:
            neutral_r = _neutral_min(rank.dtype)
            better = lambda a, b: a >= b
        r = jnp.where(ok, rank, neutral_r)
        n = r.shape[0]
        neutral_r = jnp.asarray(neutral_r, dtype=r.dtype)
        o, f, vs = ok, self.flags, tuple(values)
        d = 1
        while d < n:
            pr = shift_static(r, d, neutral_r)
            po = shift_static(o, d, False)
            pf = shift_static(f, d, True)
            pvs = tuple(shift_static(v, d, jnp.zeros((), v.dtype))
                        for v in vs)
            # take the predecessor when it is valid and (we're invalid or
            # its rank is better) — standard argmin/argmax monoid
            take_prev = jnp.logical_and(
                jnp.logical_not(f),
                jnp.logical_and(po, jnp.logical_or(jnp.logical_not(o),
                                                   better(pr, r))))
            r, o, f, vs = (
                jnp.where(take_prev, pr, r),
                jnp.where(f, o, jnp.logical_or(o, po)),
                jnp.logical_or(f, pf),
                tuple(jnp.where(take_prev, pv, v)
                      for pv, v in zip(pvs, vs)))
            d <<= 1
        return list(vs), r, o


class GlobalSegments(SortedSegments):
    """Single-segment (key-less aggregation) context: every reduction is
    ONE masked vector reduce instead of a log2(n) Hillis-Steele scan.
    The q9 shape — N conditional aggregates over the whole batch — drops
    from ~2N scans x log2(P) full-array shift/combine passes to N single
    reduces that XLA fuses into a handful of HBM sweeps, all still ONE
    kernel dispatch per batch.

    Results come back as shape-(1,) totals; callers (global_groupby)
    read element [-1] exactly as they do the scan path's last row, so
    every AggregateExpression.update/merge works over either context
    unchanged. Reduction ORDER differs from the scan path for floats
    (both differ from a sequential sum; neither is more exact)."""

    def __init__(self, live, orig_index=None):
        flags = jnp.zeros(live.shape, jnp.bool_).at[0].set(True)
        super().__init__(flags, live, orig_index=orig_index)

    def sum(self, data, valid):
        ok = jnp.logical_and(valid, self.live)
        z = jnp.zeros((), dtype=data.dtype)
        return jnp.sum(jnp.where(ok, data, z), dtype=data.dtype)[None]

    def min(self, data, valid):
        ok = jnp.logical_and(valid, self.live)
        big = _neutral_max(data.dtype)
        return jnp.min(jnp.where(ok, data, big))[None]

    def max(self, data, valid):
        ok = jnp.logical_and(valid, self.live)
        small = _neutral_min(data.dtype)
        return jnp.max(jnp.where(ok, data, small))[None]

    def count(self, pred, dtype=jnp.int64):
        ok = jnp.logical_and(pred, self.live)
        return jnp.sum(ok.astype(dtype), dtype=dtype)[None]

    def select_by_rank(self, values, rank, valid, mode: str):
        """Global argmin/argmax over rank — one reduce + one row gather
        (group-sized, i.e. a single element) instead of the scan."""
        ok = jnp.logical_and(valid, self.live)
        if mode == "min":
            neutral_r = _neutral_max(rank.dtype)
            r = jnp.where(ok, rank, neutral_r)
            i = jnp.argmin(r)
        else:
            neutral_r = _neutral_min(rank.dtype)
            r = jnp.where(ok, rank, neutral_r)
            i = jnp.argmax(r)
        any_ok = jnp.any(ok)[None]
        sel = [v[i][None] for v in values]
        return sel, r[i][None], any_ok


def seg_sum(data, gid, num_segments: int):
    """Sum of data per segment; rows with gid outside [0, G) are dropped.
    Callers pre-mask invalid rows to the neutral. With a SortedSegments
    context, returns the per-row segmented scan."""
    if isinstance(gid, SortedSegments):
        return gid.sum(data, jnp.ones(data.shape, jnp.bool_))
    if num_segments <= DENSE_MAX:
        m = _dense_mask(gid, num_segments)
        return jnp.sum(jnp.where(m, data[None, :], jnp.zeros_like(data[:1])),
                       axis=1)
    return jax.ops.segment_sum(data, gid, num_segments=num_segments)


def seg_count(pred, gid, num_segments: int, dtype=jnp.int64):
    """Count of True rows per segment (pred bool)."""
    if isinstance(gid, SortedSegments):
        return gid.count(pred, dtype)
    return seg_sum(pred.astype(dtype), gid, num_segments)


def seg_min(data, gid, num_segments: int):
    if isinstance(gid, SortedSegments):
        return gid.min(data, jnp.ones(data.shape, jnp.bool_))
    if num_segments <= DENSE_MAX:
        m = _dense_mask(gid, num_segments)
        big = _neutral_max(data.dtype)
        return jnp.min(jnp.where(m, data[None, :], big), axis=1)
    return jax.ops.segment_min(data, gid, num_segments=num_segments)


def seg_max(data, gid, num_segments: int):
    if isinstance(gid, SortedSegments):
        return gid.max(data, jnp.ones(data.shape, jnp.bool_))
    if num_segments <= DENSE_MAX:
        m = _dense_mask(gid, num_segments)
        small = _neutral_min(data.dtype)
        return jnp.max(jnp.where(m, data[None, :], small), axis=1)
    return jax.ops.segment_max(data, gid, num_segments=num_segments)


def _neutral_max(dtype):
    if jnp.issubdtype(dtype, jnp.floating):
        return jnp.array(jnp.inf, dtype=dtype)
    if dtype == jnp.bool_:
        return jnp.array(True)
    return jnp.array(jnp.iinfo(dtype).max, dtype=dtype)


def _neutral_min(dtype):
    if jnp.issubdtype(dtype, jnp.floating):
        return jnp.array(-jnp.inf, dtype=dtype)
    if dtype == jnp.bool_:
        return jnp.array(False)
    return jnp.array(jnp.iinfo(dtype).min, dtype=dtype)


def front_sort(first, rank, lanes, padded_len: int, rank_bound: int = 0):
    """Rows where ``first`` to the front in the order of ``rank``, the
    others behind them in the order of theirs, every lane (1-D, one entry
    a row) carried along: ONE variadic sort, the idiom that replaces
    cumsum+scatter (per-column 1M-row scatters serialize on the scalar
    core, the sort network is bandwidth-bound, ~5 ms).

    ``rank`` is below ``rank_bound`` (``padded_len`` where none is
    given; a rank that leads with a partition number has a wider one) and
    unique among the ``first`` rows
    and among the others. So the key is ONE uint32 a row and unique (bit
    31: not first; then the rank), an UNSTABLE sort orders by it as a
    stable one would, and the bool lanes ride in the bits below the rank
    (what does not fit there, 32 lanes a word). On this backend a sort's
    compile time grows with every operand and doubles with ``is_stable``:
    at 1,048,576 rows three 64-bit columns with their validity compile in
    a third of the time they took as (u8 key, stable, a bool lane a
    column) (PERF.md, PR 32)."""
    flags = [i for i, l in enumerate(lanes) if l.dtype == jnp.bool_]
    spare = 31 - max(1, (max(rank_bound, padded_len) - 1).bit_length())
    assert spare >= 0, (rank_bound, padded_len)
    in_key, rest = flags[:spare], flags[spare:]

    def bits(ids):
        word = jnp.zeros((padded_len,), jnp.uint32)
        for b, i in enumerate(ids):
            word = word | (lanes[i].astype(jnp.uint32) << b)
        return word

    key = jnp.where(first, jnp.uint32(0), jnp.uint32(1 << 31)) \
        | (rank.astype(jnp.uint32) << len(in_key)) | bits(in_key)
    plain = [i for i in range(len(lanes)) if i not in flags]
    words = [rest[i:i + 32] for i in range(0, len(rest), 32)]
    packed = jax.lax.sort(
        tuple([key] + [lanes[i] for i in plain] + [bits(w) for w in words]),
        num_keys=1, is_stable=False)
    out = [None] * len(lanes)
    for i, lane in zip(plain, packed[1:]):
        out[i] = lane
    for word, ids in zip((packed[0],) + packed[1 + len(plain):],
                         [in_key] + words):
        for b, i in enumerate(ids):
            out[i] = (word >> b) & 1 == 1
    return out


def compact_rows(arrays, keep, padded_len: int):
    """Move keep-rows to the front preserving order (``front_sort`` by the
    row's index), validity cleared behind them.

    arrays: [(data, validity), ...]; returns (compacted pairs, count)."""
    count = jnp.sum(keep).astype(jnp.int32)
    live = jnp.arange(padded_len, dtype=jnp.int32) < count
    it = iter(front_sort(keep, jnp.arange(padded_len, dtype=jnp.int32),
                         [lane for pair in arrays for lane in pair],
                         padded_len))
    outs = [(next(it), jnp.logical_and(next(it), live)) for _ in arrays]
    return outs, count


def onehot_gather(table, codes, num_entries: int):
    """table[codes] for a SMALL table (dictionary remap): dense one-hot
    select instead of a row-sized gather (44 ms -> 0.3 ms at 1M rows).
    Codes outside [0, num_entries) map to 0 of the table dtype."""
    if num_entries == 0:
        return jnp.zeros(codes.shape, dtype=table.dtype)
    # crossover vs the serialized row-gather (~30 ms/1M rows) is ~2k entries
    if num_entries > 2048:
        return jnp.take(table, codes, mode="clip")
    iota = jax.lax.broadcasted_iota(jnp.int32,
                                    (num_entries, codes.shape[0]), 0)
    m = codes.astype(jnp.int32)[None, :] == iota
    t = table[:num_entries].astype(table.dtype)[:, None]
    return jnp.sum(jnp.where(m, t, jnp.zeros_like(t[:1])), axis=0)
