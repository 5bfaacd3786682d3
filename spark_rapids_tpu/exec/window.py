"""Window exec (ref window/GpuWindowExec.scala:146 + specializations:
GpuRunningWindowExec scan-based running aggs, GpuBatchedBoundedWindowExec
bounded frames, BasicWindowCalc).

TPU-first, one fused kernel: ONE index-only lax.sort by (partition keys,
order keys), segment ids from boundaries, then every window column is
segment arithmetic on the VPU:
  row_number  = idx - partition_start + 1
  rank        = order-run start - partition_start + 1 (associative max scan)
  dense_rank  = per-partition cumsum of order-run starts
  lag/lead    = shifted gather with partition-boundary nulling
  unbounded aggregate frames = segment reduction broadcast via take(gid)
  running / bounded-rows sum,count,avg frames = partition-local prefix sums
    (prefix[i+hi] - prefix[i+lo-1])
Results scatter back to input row order through the inverse permutation, so
the exec preserves row order like the reference does.
"""
from __future__ import annotations

import functools
from typing import Dict, Iterator, List, Optional, Tuple

import jax
import jax.numpy as jnp

from ..columnar.segmented import (SortedSegments, last_valid_scan,
                                  prefix_sum, reverse_last_valid_scan,
                                  shift_static)
import numpy as np

from ..columnar import (ColumnarBatch, DeviceColumn, DictColumn,
                        concat_batches)
from ..exprs.aggregates import AggregateExpression, Average, Count, CountStar, \
    Max, Min, Sum
from ..exprs.base import DVal, EvalContext
from ..exprs.window_fns import (DenseRank, Lag, Lead, NthValue, NTile,
                                PercentRank, Rank, RowNumber,
                                WindowFunction)
from ..mem import SpillableBatch, with_retry_no_split, wrap_spillables
from ..plan.logical import WindowSpec
from ..types import FLOAT64, INT32, INT64, Schema, StructField
from .base import ExecContext, TpuExec
from .encoding import grouping_operands, operands_equal, order_key_operands

__all__ = ["TpuWindowExec", "CpuWindowExec"]

_WIN_CACHE: Dict[Tuple, object] = {}


def _start_broadcast(values, pflags):
    """values at partition-start rows propagated forward to every row of
    the partition (scan, not a group-table gather)."""
    return last_valid_scan(values, pflags)[0]


def _end_broadcast(values, end_mask):
    """values at partition-end rows propagated backward."""
    return reverse_last_valid_scan(values, end_mask)[0]


def _build_window_kernel(window_exprs, schema: Schema, padded_len_key=None):
    dtypes = [f.dtype for f in schema.fields]

    @functools.partial(jax.jit, static_argnums=(2,))
    def kernel(cols, num_rows, padded_len):
        P = padded_len
        dvals = [None if c is None else DVal(c[0], c[1], dt)
                 for c, dt in zip(cols, dtypes)]
        ctx = EvalContext(schema, dvals, num_rows, P)
        row_mask = ctx.row_mask()
        outs = []
        for fn, spec, _name in window_exprs:
            # --- sort by (partition, order) --------------------------------
            pad_flag = jnp.where(row_mask, jnp.uint8(0), jnp.uint8(1))
            operands = [pad_flag]
            n_part_ops = 1
            for pk in spec.partition_by:
                operands.extend(grouping_operands(pk.eval_device(ctx)))
            n_part_ops = len(operands)
            for o in spec.order_by:
                operands.extend(order_key_operands(
                    o.expr.eval_device(ctx), o.ascending, o.nulls_first))
            perm0 = jnp.arange(P, dtype=jnp.int32)
            # carry the aggregated/lagged value through the sort network
            # instead of gathering it by perm afterwards (row gathers
            # serialize on the TPU scalar core)
            payload = [perm0]
            child = getattr(fn, "child", None)
            if child is not None:
                cv = child.eval_device(ctx)
                payload.extend((cv.data, cv.validity))
            srt = jax.lax.sort(tuple(operands + payload),
                               num_keys=len(operands), is_stable=True)
            perm = srt[len(operands)]
            sorted_child = (DVal(srt[len(operands) + 1],
                                 srt[len(operands) + 2], cv.dtype)
                            if child is not None else None)
            s_ops = srt[:len(operands)]
            idx = jnp.arange(P, dtype=jnp.int32)
            # partition boundaries
            pdiff = jnp.zeros(P, dtype=jnp.bool_)
            for op in s_ops[1:n_part_ops]:
                prev = jnp.roll(op, 1)
                pdiff = jnp.logical_or(
                    pdiff, jnp.logical_not(operands_equal(op, prev)))
            pflags = jnp.logical_and(jnp.logical_or(idx == 0, pdiff), row_mask)
            gid = jnp.where(row_mask,
                            prefix_sum(pflags, jnp.int32) - 1, P)
            part_start = _start_broadcast(idx, pflags)
            nlive = jnp.sum(row_mask.astype(jnp.int32))
            end_mask = jnp.logical_and(
                row_mask,
                jnp.logical_or(
                    jnp.concatenate([pflags[1:],
                                     jnp.ones((1,), jnp.bool_)]),
                    idx + 1 >= nlive))
            pend = _end_broadcast(idx, end_mask)
            # order-value run boundaries (for rank/dense_rank)
            odiff = pdiff
            for op in s_ops[n_part_ops:]:
                prev = jnp.roll(op, 1)
                odiff = jnp.logical_or(
                    odiff, jnp.logical_not(operands_equal(op, prev)))
            oflags = jnp.logical_and(jnp.logical_or(idx == 0, odiff), row_mask)

            val = self_validity = None
            if isinstance(fn, (RowNumber,)):
                out_sorted = (idx - part_start + 1).astype(jnp.int32)
                ov_sorted = row_mask
            elif isinstance(fn, Rank):
                run_start = _start_broadcast(idx, oflags)
                out_sorted = (run_start - part_start + 1).astype(jnp.int32)
                ov_sorted = row_mask
            elif isinstance(fn, PercentRank):
                run_start = _start_broadcast(idx, oflags)
                rank = (run_start - part_start + 1).astype(jnp.float64)
                cnt = (pend - part_start + 1).astype(jnp.float64)
                out_sorted = jnp.where(cnt > 1, (rank - 1.0)
                                       / jnp.maximum(cnt - 1.0, 1.0),
                                       0.0)
                ov_sorted = row_mask
            elif isinstance(fn, DenseRank):
                c = prefix_sum(oflags, jnp.int32)
                c_at_pstart = _start_broadcast(c, pflags)
                out_sorted = (c - c_at_pstart + 1).astype(jnp.int32)
                ov_sorted = row_mask
            elif isinstance(fn, NTile):
                cnt = (pend - part_start + 1).astype(jnp.int32)
                rn = idx - part_start
                n = jnp.int32(fn.n)
                base = cnt // n
                rem = cnt % n
                # Spark NTile: first `rem` buckets get base+1 rows
                big_rows = rem * (base + 1)
                out_sorted = jnp.where(
                    rn < big_rows,
                    rn // jnp.maximum(base + 1, 1),
                    rem + (rn - big_rows) // jnp.maximum(base, 1)
                ).astype(jnp.int32) + 1
                ov_sorted = row_mask
            elif isinstance(fn, (Lag, Lead)):
                sd = sorted_child.data
                sv = sorted_child.validity
                off = fn.signed_offset
                # STATIC shift (a concatenate), not a row gather
                ok = jnp.logical_and(idx - off >= 0, idx - off < P)
                out_sorted = shift_static(sd, off,
                                          jnp.zeros((), sd.dtype))
                ov_sorted = jnp.logical_and(
                    shift_static(sv, off, jnp.array(False)), ok)
                # must stay inside the partition
                same_part = shift_static(
                    gid, off, jnp.full((), P, gid.dtype)) == gid
                ov_sorted = jnp.logical_and(ov_sorted, same_part)
                if fn.default is not None:
                    dflt = jnp.asarray(fn.default, dtype=out_sorted.dtype)
                    fill = jnp.logical_and(jnp.logical_not(
                        jnp.logical_and(ok, same_part)), row_mask)
                    out_sorted = jnp.where(fill, dflt, out_sorted)
                    ov_sorted = jnp.logical_or(ov_sorted, fill)
            elif isinstance(fn, NthValue):
                sd = sorted_child.data
                sv = sorted_child.validity
                rel = idx - part_start
                src_flags = jnp.logical_and(rel == fn.n - 1, row_mask)
                out_sorted = last_valid_scan(sd, src_flags)[0]
                nth_valid = last_valid_scan(sv, src_flags)[0]
                ov_sorted = jnp.logical_and(
                    jnp.logical_and(rel >= fn.n - 1, nth_valid),
                    row_mask)
            elif isinstance(fn, AggregateExpression):
                out_sorted, ov_sorted = _windowed_agg(
                    fn, spec, ctx, sorted_child, part_start, idx,
                    row_mask, P, pflags, end_mask, pend)
            else:
                raise NotImplementedError(type(fn).__name__)

            # restore original order: ONE variadic sort keyed on the
            # carried original index (scatter + inverse gathers serialize
            # on the scalar core)
            _, od, ov = jax.lax.sort((perm, out_sorted, ov_sorted),
                                     num_keys=1, is_stable=True)
            outs.append((od, jnp.logical_and(ov, row_mask)))
        return outs

    return kernel


def _windowed_agg(fn: AggregateExpression, spec: WindowSpec, ctx,
                  sorted_child, part_start, idx, row_mask, P,
                  pflags, end_mask, pend):
    """Aggregate over a window frame. Default frames follow Spark: with
    order_by -> running (unbounded preceding..current row); without ->
    whole partition. All segment maths are scans + STATIC shifts — no
    row-sized gather or scatter anywhere (TPU scalar-core serialization).
    """
    if isinstance(fn, CountStar):
        vd = jnp.ones(P, dtype=jnp.int64)
        vv = row_mask
    else:
        vd = sorted_child.data
        vv = sorted_child.validity
    vv = jnp.logical_and(vv, row_mask)
    seg = SortedSegments(pflags, row_mask)

    frame = spec.frame
    if frame is None:
        frame = ("rows", None, 0) if spec.order_by else ("rows", None, None)
    kind, lo, hi = frame

    whole = lo is None and hi is None
    if whole:
        if isinstance(fn, (Sum, Average, Count, CountStar)):
            acc = vd
            if isinstance(fn, (Count, CountStar)):
                acc = vv.astype(jnp.int64)
            acc = acc.astype(jnp.float64 if isinstance(fn, Average)
                             else acc.dtype)
            tot = _end_broadcast(seg.sum(acc, vv), end_mask)
            cnt = _end_broadcast(seg.count(vv), end_mask)
            if isinstance(fn, (Count, CountStar)):
                return tot, row_mask
            if isinstance(fn, Average):
                ok = cnt > 0
                return (tot / jnp.maximum(cnt, 1).astype(jnp.float64), ok)
            return tot, cnt > 0
        if isinstance(fn, (Min, Max)):
            if jnp.issubdtype(vd.dtype, jnp.floating):
                # Spark: NaN is greatest; all-NaN group -> NaN
                notnan = jnp.logical_and(vv, jnp.logical_not(jnp.isnan(vd)))
                has_nan = _end_broadcast(
                    seg.max(jnp.logical_and(vv, jnp.isnan(vd))
                            .astype(jnp.int32), vv), end_mask) > 0
                red = seg.min if isinstance(fn, Min) else seg.max
                m = _end_broadcast(red(vd, notnan), end_mask)
                n_notnan = _end_broadcast(seg.count(notnan), end_mask)
                nanv = jnp.array(jnp.nan, dtype=vd.dtype)
                if isinstance(fn, Max):
                    m = jnp.where(has_nan, nanv, m)
                else:
                    m = jnp.where(jnp.logical_and(n_notnan == 0, has_nan),
                                  nanv, m)
            else:
                red = seg.min if isinstance(fn, Min) else seg.max
                m = _end_broadcast(red(vd, vv), end_mask)
            cnt = _end_broadcast(seg.count(vv), end_mask)
            return m, cnt > 0
        raise NotImplementedError(type(fn).__name__)

    # frame geometry shared by every bounded/running aggregate
    is_f = jnp.issubdtype(vd.dtype, jnp.floating)
    isnan = (jnp.logical_and(vv, jnp.isnan(vd)) if is_f
             else jnp.zeros(P, jnp.bool_))
    lo_i = part_start if lo is None else jnp.maximum(part_start, idx + lo)
    hi_i = pend if hi is None else jnp.minimum(pend, idx + hi)
    empty = hi_i < lo_i

    def window_sum(prefix):
        z = jnp.zeros((), prefix.dtype)
        # prefix value just BEFORE the partition (0 at the table start)
        before = _start_broadcast(shift_static(prefix, 1, z), pflags)
        at_end = _end_broadcast(prefix, end_mask)
        # upper = prefix[min(pend, idx+hi)] via a STATIC shift + clamp fix
        if hi is None:
            upper = at_end
        else:
            upper = jnp.where(idx + hi > pend, at_end,
                              shift_static(prefix, -hi, z))
        # lower = prefix[max(pstart, idx+lo) - 1]
        if lo is None:
            lower = before
        else:
            lower = jnp.where(idx + lo <= part_start, before,
                              shift_static(prefix, -(lo - 1), z))
        return jnp.where(empty, z, upper - lower)

    if isinstance(fn, (Min, Max)):
        return _bounded_minmax(fn, vd, vv, isnan, lo, hi, part_start,
                               pend, idx, row_mask, P, pflags, end_mask,
                               window_sum, empty)

    if not isinstance(fn, (Sum, Average, Count, CountStar)):
        raise NotImplementedError(
            f"bounded frame for {type(fn).__name__}")
    acc_dt = jnp.float64 if (isinstance(fn, Average)
                             or jnp.issubdtype(vd.dtype, jnp.floating)) \
        else jnp.int64
    # NaN must poison only frames CONTAINING it, not every later prefix:
    # sum finite values in the prefix and track NaN positions separately
    # (a frame whose NaN-count difference is >0 yields NaN)
    finite_ok = jnp.logical_and(vv, jnp.logical_not(isnan))
    acc = jnp.where(finite_ok, vd, jnp.zeros_like(vd)).astype(acc_dt)
    cntv = vv.astype(jnp.int64)
    ps = prefix_sum(acc)          # global prefix (inclusive)
    pc = prefix_sum(cntv)
    pn = prefix_sum(isnan.astype(jnp.int32))

    s = window_sum(ps)
    c = window_sum(pc)
    if isinstance(fn, (Count, CountStar)):
        return c, row_mask
    if is_f:
        frame_nan = window_sum(pn) > 0
        s = jnp.where(frame_nan, jnp.array(jnp.nan, s.dtype), s)
    if isinstance(fn, Average):
        ok = jnp.logical_and(c > 0, row_mask)
        return s.astype(jnp.float64) / jnp.maximum(c, 1).astype(jnp.float64), ok
    ok = jnp.logical_and(c > 0, row_mask)
    if jnp.issubdtype(vd.dtype, jnp.integer):
        s = s.astype(jnp.int64)
    return s, ok


def _numpy_window_one(fn, spec, col_np, n: int):
    """One window expression over host arrays; returns (data, validity)
    in ORIGINAL row order, or None if unsupported. Mirrors the device
    kernel's frame semantics (incl. Spark NaN/NULL rules)."""
    from .sort import _np_total_order_key
    keys = []
    for pk in spec.partition_by:
        got = col_np(pk)
        if got is None:
            return None
        keys.append((got, True, True))
    for o in spec.order_by:
        got = col_np(o.expr)
        if got is None:
            return None
        keys.append((got, o.ascending, o.nulls_first))
    child_pair = None
    child = getattr(fn, "child", None)
    if child is not None:
        child_pair = col_np(child)
        if child_pair is None:
            return None

    # one total-order encoding per key, shared by the sort AND boundary
    # detection (raw-value comparison would merge NULLs with the fill
    # value and split equal NaNs — the device kernel compares encoded
    # operands, so must we)
    encs = []
    for (v, ok), asc, nf in keys:
        enc = _np_total_order_key(np.asarray(v), np.asarray(ok))
        if not asc:
            enc = ~enc
        enc = np.where(ok, enc, np.uint64(0))
        rank = (np.where(ok, 1, 0) if nf else np.where(ok, 0, 1)) \
            .astype(np.uint8)
        encs.append((enc, rank))
    lex = []
    for enc, rank in reversed(encs):
        lex.extend([enc, rank])
    order = (np.lexsort(tuple(lex)) if lex
             else np.arange(n, dtype=np.int64))
    idx = np.arange(n, dtype=np.int64)

    def run_flags(pairs):
        flags = np.zeros(n, dtype=bool)
        if n:
            flags[0] = True
        for enc, rank in pairs:
            se, sr = enc[order], rank[order]
            diff = np.zeros(n, dtype=bool)
            diff[1:] = (se[1:] != se[:-1]) | (sr[1:] != sr[:-1])
            flags |= diff
        return flags

    npart = len(spec.partition_by)
    pflags = run_flags(encs[:npart])
    part_start = np.maximum.accumulate(np.where(pflags, idx, 0))
    # partition end: reverse accumulate of end flags
    endf = np.zeros(n, dtype=bool)
    if n:
        endf[-1] = True
        endf[:-1] = pflags[1:]
    pend = np.minimum.accumulate(np.where(endf, idx, n - 1)[::-1])[::-1]

    oflags = pflags | run_flags(encs[npart:])

    if isinstance(fn, RowNumber):
        out, ov = (idx - part_start + 1).astype(np.int64), \
            np.ones(n, bool)
    elif isinstance(fn, Rank):
        run_start = np.maximum.accumulate(np.where(oflags, idx, 0))
        out = (run_start - part_start + 1).astype(np.int64)
        ov = np.ones(n, bool)
    elif isinstance(fn, PercentRank):
        run_start = np.maximum.accumulate(np.where(oflags, idx, 0))
        rank = (run_start - part_start + 1).astype(np.float64)
        cnt = (pend - part_start + 1).astype(np.float64)
        out = np.where(cnt > 1, (rank - 1.0) / np.maximum(cnt - 1.0, 1.0),
                       0.0)
        ov = np.ones(n, bool)
    elif isinstance(fn, DenseRank):
        c = np.cumsum(oflags)
        c_at = np.maximum.accumulate(np.where(pflags, c, 0))
        out = (c - c_at + 1).astype(np.int64)
        ov = np.ones(n, bool)
    elif isinstance(fn, NthValue):
        vd = np.asarray(child_pair[0])[order]
        vv = np.asarray(child_pair[1])[order]
        rel = idx - part_start
        src = np.clip(part_start + fn.n - 1, 0, n - 1)
        ok = rel >= fn.n - 1
        out = np.where(ok, vd[src], np.zeros((), vd.dtype))
        ov = ok & vv[src]
    elif isinstance(fn, (Lag, Lead)):
        vd = np.asarray(child_pair[0])[order]
        vv = np.asarray(child_pair[1])[order]
        off = fn.signed_offset
        src = idx - off
        inside = (src >= part_start) & (src <= pend)
        srcc = np.clip(src, 0, n - 1)
        out = np.where(inside, vd[srcc], np.zeros((), vd.dtype))
        ov = np.where(inside, vv[srcc], False)
        if getattr(fn, "default", None) is not None:
            fill = ~inside
            out = np.where(fill, np.asarray(fn.default, vd.dtype), out)
            ov = ov | fill
    elif isinstance(fn, AggregateExpression) and isinstance(
            fn, (Sum, Average, Count, CountStar, Min, Max)):
        got = _numpy_frame_agg(fn, spec, child_pair, order, idx,
                               part_start, pend, n)
        if got is None:
            return None
        out, ov = got
    else:
        return None

    inv = np.empty(n, dtype=np.int64)
    inv[order] = idx
    return out[inv], ov[inv]


def _numpy_frame_agg(fn, spec, child_pair, order, idx, part_start, pend,
                     n: int):
    frame = spec.frame
    if frame is None:
        frame = ("rows", None, 0) if spec.order_by else \
            ("rows", None, None)
    kind, lo, hi = frame
    if kind != "rows":
        return None
    if isinstance(fn, CountStar):
        vd = np.ones(n, dtype=np.int64)
        vv = np.ones(n, dtype=bool)
    else:
        vd = np.asarray(child_pair[0])[order]
        vv = np.asarray(child_pair[1])[order]
    is_f = np.issubdtype(vd.dtype, np.floating)
    isnan = (vv & np.isnan(vd)) if is_f else np.zeros(n, bool)
    ok = vv & ~isnan
    lo_i = part_start if lo is None else np.maximum(part_start, idx + lo)
    hi_i = pend if hi is None else np.minimum(pend, idx + hi)
    empty = hi_i < lo_i
    hs = np.clip(hi_i, 0, max(n - 1, 0))
    ls = np.clip(lo_i, 0, max(n - 1, 0))

    def wsum(prefix):
        upper = prefix[hs]
        lower = np.where(ls > 0, prefix[np.maximum(ls - 1, 0)], 0)
        return np.where(empty, 0, upper - lower)

    c_valid = wsum(np.cumsum(vv.astype(np.int64)))
    c_nan = wsum(np.cumsum(isnan.astype(np.int64)))
    if isinstance(fn, (Min, Max)):
        is_min = isinstance(fn, Min)
        from ..columnar.segmented import _neutral_max, _neutral_min
        neutral = np.asarray(_neutral_max(vd.dtype) if is_min
                             else _neutral_min(vd.dtype), vd.dtype)
        masked = np.where(ok, vd, neutral)
        combine = np.minimum if is_min else np.maximum
        # sparse table over clamped per-row spans (log2 passes)
        span = (hs - ls + 1).astype(np.int64)
        span = np.where(empty, 1, span)
        K = int(max(span.max(), 1)).bit_length() - 1 if n else 0
        tables = [masked]
        for k in range(K):
            t = tables[-1]
            shifted = np.concatenate(
                [t[1 << k:], np.full(min(1 << k, n), neutral, vd.dtype)])
            tables.append(combine(t, shifted))
        k_i = np.maximum(
            np.int64(np.log2(np.maximum(span, 1))), 0).astype(np.int64) \
            if n else np.zeros(0, np.int64)
        # per-row table pick via np.select over log-many tables
        out = np.full(n, neutral, vd.dtype)
        for k in range(K + 1):
            sel = k_i == k
            if not sel.any():
                continue
            t = tables[k]
            a = ls[sel]
            b = hs[sel] - (1 << k) + 1
            out[sel] = combine(t[a], t[np.maximum(b, 0)])
        if is_f:
            n_ok = c_valid - c_nan
            if is_min:
                out = np.where((n_ok == 0) & (c_nan > 0), np.nan, out)
            else:
                out = np.where(c_nan > 0, np.nan, out)
        return out, (~empty) & (c_valid > 0)
    # sum / avg / count
    acc_dt = np.float64 if (isinstance(fn, Average) or is_f) else np.int64
    acc = np.where(ok, vd, 0).astype(acc_dt)
    s = wsum(np.cumsum(acc))
    if isinstance(fn, (Count, CountStar)):
        return wsum(np.cumsum(vv.astype(np.int64))), np.ones(n, bool)
    if is_f:
        s = np.where(c_nan > 0, np.nan, s)
    c_ok = wsum(np.cumsum(vv.astype(np.int64)))
    if isinstance(fn, Average):
        out = s.astype(np.float64) / np.maximum(c_ok, 1)
        return out, (c_ok > 0)
    if np.issubdtype(vd.dtype, np.integer):
        s = s.astype(np.int64)
    return s, (c_ok > 0)


def _seg_combine_scan(vals, flags, combine, neutral):
    """Segmented inclusive forward scan (Hillis-Steele: log2(P) STATIC
    shift+combine passes, unrolled — the rolled traced-shift form
    composes pathologically with surrounding sorts at compile time; see
    columnar/segmented.py)."""
    from ..columnar.segmented import shift_static
    v, f = vals, flags
    n = v.shape[0]
    neutral = jnp.asarray(neutral, dtype=v.dtype)
    d = 1
    while d < n:
        pv = shift_static(v, d, neutral)
        pf = shift_static(f, d, True)
        v = jnp.where(f, v, combine(pv, v))
        f = jnp.logical_or(f, pf)
        d <<= 1
    return v


def _bounded_minmax(fn, vd, vv, isnan, lo, hi, part_start, pend, idx,
                    row_mask, P, pflags, end_mask, window_sum, empty):
    """Bounded-frame MIN/MAX (removes the r1 limitation; ref
    GpuBatchedBoundedWindowExec). Sliding extrema without gathers:

      * interior rows (frame fully inside the partition) query a sparse
        table: T_k[i] = extremum over [i, i+2^k); the frame [a, a+W-1] is
        combine(T_K[a], T_K[a+W-2^K]) with K = floor(log2(W)) — both
        reads are STATIC shifts because a = i+lo;
      * start-clamped rows read the partition-running scan at i+hi;
      * end-clamped rows read the reverse (suffix) scan at i+lo;
      * doubly-clamped rows take the whole-partition extremum.

    All four candidates are elementwise selects over scans and static
    shifts — the same no-gather discipline as the rest of the kernel.
    Spark NaN semantics: max -> NaN if the frame contains any NaN; min ->
    NaN only when the frame has NaNs and no other valid values."""
    from ..columnar.segmented import _neutral_max, _neutral_min
    is_min = isinstance(fn, Min)
    combine = jnp.minimum if is_min else jnp.maximum
    neutral = _neutral_max(vd.dtype) if is_min else _neutral_min(vd.dtype)
    ok = jnp.logical_and(vv, jnp.logical_not(isnan))
    masked = jnp.where(ok, vd, jnp.asarray(neutral, vd.dtype))

    z = jnp.asarray(neutral, vd.dtype)
    run_fwd = _seg_combine_scan(masked, pflags, combine, neutral)
    # suffix scan = forward scan of the flipped array with flipped
    # segment-start flags (= end flags)
    run_rev = jnp.flip(_seg_combine_scan(
        jnp.flip(masked), jnp.flip(end_mask), combine, neutral))
    whole_part = _end_broadcast(run_fwd, end_mask)

    cands = []
    if lo is not None and hi is not None and hi >= lo:
        W = hi - lo + 1
        K = max(W.bit_length() - 1, 0)      # floor(log2(W))
        T = masked
        for k in range(K):
            T = combine(T, shift_static(T, -(1 << k), z))
        interior_val = combine(shift_static(T, -lo, z),
                               shift_static(T, -(hi - (1 << K) + 1), z))
        interior = jnp.logical_and(idx + lo >= part_start,
                                   idx + hi <= pend)
        cands.append((interior, interior_val))
    if hi is not None:
        start_clamped = shift_static(run_fwd, -hi, z)
        cands.append((jnp.logical_and(
            (idx + lo < part_start) if lo is not None
            else jnp.ones(P, jnp.bool_),
            idx + hi <= pend), start_clamped))
    if lo is not None:
        end_clamped = shift_static(run_rev, -lo, z)
        cands.append((jnp.logical_and(
            idx + lo >= part_start,
            (idx + hi > pend) if hi is not None
            else jnp.ones(P, jnp.bool_)), end_clamped))
    out = whole_part
    for mask, val in cands:
        out = jnp.where(mask, val, out)

    # null / NaN semantics from frame counts (prefix-sum machinery)
    c_valid = window_sum(prefix_sum(vv.astype(jnp.int64)))
    c_nan = window_sum(prefix_sum(isnan.astype(jnp.int32)))
    c_ok = window_sum(prefix_sum(ok.astype(jnp.int64)))
    has_val = jnp.logical_and(jnp.logical_not(empty), c_valid > 0)
    if jnp.issubdtype(vd.dtype, jnp.floating):
        nanv = jnp.array(jnp.nan, dtype=vd.dtype)
        if is_min:
            out = jnp.where(jnp.logical_and(c_ok == 0, c_nan > 0),
                            nanv, out)
        else:
            out = jnp.where(c_nan > 0, nanv, out)
    return out, jnp.logical_and(has_val, row_mask)


class TpuWindowExec(TpuExec):
    def __init__(self, window_exprs, child: TpuExec,
                 host_sink: bool = False):
        super().__init__([child])
        self.window_exprs = list(window_exprs)
        #: True when this window is the query's terminal stage: its
        #: row-sized result goes straight to a host collect, so the D2H
        #: fetch (not the compute) can be the dominant cost — the cost
        #: model may run the SAME kernel on host XLA
        #: (ref CostBasedOptimizer's transition-cost reverts,
        #: RapidsConf.scala:2126)
        self.host_sink = host_sink
        cs = child.output_schema()
        fields = list(cs.fields)
        for e, spec, name in self.window_exprs:
            fields.append(StructField(name, e.data_type(cs), True))
        self._schema = Schema(fields)

    def output_schema(self) -> Schema:
        return self._schema

    def do_execute(self, ctx: ExecContext) -> Iterator[ColumnarBatch]:
        cs = self.children[0].output_schema()
        key = (tuple(f"{type(e).__name__}|{n}" for e, s, n in
                     self.window_exprs),
               tuple((f.name, f.dtype.name) for f in cs.fields), id(self))
        kern = _WIN_CACHE.get(key)
        if kern is None:
            kern = _build_window_kernel(self.window_exprs, cs)
            _WIN_CACHE[key] = kern
        # window needs whole partitions: single-batch goal
        spill = wrap_spillables(
            (b.ensure_device().with_lists_on_host()
             for b in self.children[0].execute(ctx)), ctx.memory)
        if not spill:
            return

        from ..config import WINDOW_HOST_SINK_ROWS
        thr = int(ctx.conf.get(WINDOW_HOST_SINK_ROWS))

        def run():
            with ctx.semaphore.held():
                batch = concat_batches([s.get() for s in spill])
                np_cols = (self._host_inputs(batch)
                           if self.host_sink and thr
                           and batch.num_rows >= thr else None)
                if np_cols is not None:
                    out = self._run_host_numpy(batch, cs, np_cols)
                    if out is not None:
                        return out
                    return self._run_host_xla(kern, batch, cs, np_cols)
                # host columns (e.g. high-cardinality strings) ride
                # through untouched; the kernel must not dereference them
                cols = [(c.data, c.validity)
                        if isinstance(c, DeviceColumn) else None
                        for c in batch.columns]
                outs = kern(cols, jnp.int32(batch.num_rows),
                            batch.padded_len)
                new_cols = list(batch.columns)
                for (d, v), (e, s, name) in zip(outs, self.window_exprs):
                    new_cols.append(DeviceColumn(d, v, e.data_type(cs)))
                return ColumnarBatch(new_cols, batch.num_rows, self._schema)

        try:
            out = with_retry_no_split(run, ctx=ctx, op=self._exec_id)
        finally:
            for s in spill:
                s.close()
        yield out

    # -- host numpy execution (terminal, fetch-bound windows) --------------
    def _run_host_numpy(self, batch, cs, np_cols):
        """Vectorized numpy evaluation of the window — the same
        prefix-sum / segment-broadcast formulas as the device kernel, on
        host-sorted arrays (np.lexsort ~3x faster than XLA-CPU's
        lax.sort). Returns None when an expression falls outside the
        supported set (caller then uses the host-XLA kernel, then the
        device). Differentially tested against BOTH other engines."""
        from ..columnar.column import HostColumn
        from ..exprs.arithmetic import masked_numpy_to_arrow
        n = batch.num_rows
        name_to = {f.name: i for i, f in enumerate(cs.fields)}

        def col_np(e):
            from ..exprs.base import Alias, ColumnRef
            inner = e.children[0] if isinstance(e, Alias) else e
            if not isinstance(inner, ColumnRef) \
                    or inner.name not in name_to:
                return None
            pair = np_cols[name_to[inner.name]]
            if pair is None:
                return None
            return pair[0][:n], pair[1][:n]

        new_cols = list(batch.columns)
        for fn, spec, name in self.window_exprs:
            res = _numpy_window_one(fn, spec, col_np, n)
            if res is None:
                return None
            d, v = res
            dt = fn.data_type(cs)
            new_cols.append(HostColumn(masked_numpy_to_arrow(d, v, dt),
                                       dt))
        return ColumnarBatch(new_cols, n, self._schema)

    # -- host-XLA execution (terminal, fetch-bound windows) ----------------
    def _host_inputs(self, batch):
        """Padded numpy (data, validity) pairs for every device column,
        WITHOUT a device fetch (host mirrors only); None when any needed
        column lacks a mirror (then the device path runs)."""
        from ..columnar.column import HostColumn
        from ..exprs.arithmetic import arrow_to_masked_numpy
        cols = []
        for c in batch.columns:
            if isinstance(c, DictColumn):
                return None          # codes live on device only
            if isinstance(c, DeviceColumn):
                mirror = c.host_mirror
                if mirror is None:
                    return None
                v, ok = arrow_to_masked_numpy(
                    mirror.combine_chunks() if hasattr(mirror,
                                                       "combine_chunks")
                    else mirror)
                d, val = DeviceColumn.host_prepare(
                    v, c.dtype, mask=ok, padded_len=batch.padded_len)
                cols.append((d, val))
            elif isinstance(c, HostColumn):
                cols.append(None)
            else:
                return None
        return cols

    def _run_host_xla(self, kern, batch, cs, np_cols):
        """Run the SAME window kernel compiled for the host XLA backend:
        identical semantics by construction, zero device round trips.
        Output columns are HostColumns — the terminal collect reads them
        without any D2H."""
        import jax
        from ..columnar.column import HostColumn
        from ..exprs.arithmetic import masked_numpy_to_arrow
        cpu = jax.devices("cpu")[0]
        dev_cols = [None if c is None else
                    (jax.device_put(c[0], cpu), jax.device_put(c[1], cpu))
                    for c in np_cols]
        n = jax.device_put(jnp.int32(batch.num_rows), cpu)
        outs = kern(dev_cols, n, batch.padded_len)
        new_cols = list(batch.columns)
        for (d, v), (e, s, name) in zip(outs, self.window_exprs):
            dt = e.data_type(cs)
            dn = np.asarray(d)[:batch.num_rows]
            vn = np.asarray(v)[:batch.num_rows]
            new_cols.append(HostColumn(masked_numpy_to_arrow(dn, vn, dt),
                                       dt))
        return ColumnarBatch(new_cols, batch.num_rows, self._schema)

    def describe(self):
        names = ", ".join(n for _, _, n in self.window_exprs)
        return f"Window[{names}]"


class CpuWindowExec(TpuExec):
    is_tpu = False

    def __init__(self, window_exprs, child: TpuExec):
        super().__init__([child])
        self.window_exprs = list(window_exprs)
        cs = child.output_schema()
        fields = list(cs.fields)
        for e, spec, name in self.window_exprs:
            fields.append(StructField(name, e.data_type(cs), True))
        self._schema = Schema(fields)

    def output_schema(self) -> Schema:
        return self._schema

    def do_execute(self, ctx: ExecContext) -> Iterator[ColumnarBatch]:
        import pandas as pd
        import pyarrow as pa
        tables = [b.to_arrow() for b in self.children[0].execute(ctx)]
        if not tables:
            return
        t = pa.concat_tables(tables)
        df = t.to_pandas()
        batch = ColumnarBatch.from_arrow_host(t)
        for fn, spec, name in self.window_exprs:
            pcols = []
            for i, pk in enumerate(spec.partition_by):
                pc = f"__p{i}"
                df[pc] = pk.eval_host(batch).to_pandas()
                pcols.append(pc)
            ocols = []
            for i, o in enumerate(spec.order_by):
                oc = f"__o{i}"
                df[oc] = o.expr.eval_host(batch).to_pandas()
                ocols.append(oc)
            if pcols or ocols:
                # per-column direction AND null placement must match the
                # device kernel (order_key_operands); pandas sort_values
                # has one global na_position, so encode like CpuSortExec
                import numpy as np
                from ..exprs.arithmetic import arrow_to_masked_numpy
                from .sort import _np_total_order_key
                lex = []
                specs = [(o.expr, o.ascending, o.nulls_first)
                         for o in spec.order_by]
                specs = [(pk, True, True) for pk in spec.partition_by] + specs
                for e, asc_, nf in reversed(specs):
                    v, ok = arrow_to_masked_numpy(e.eval_host(batch))
                    enc = _np_total_order_key(v, ok)
                    if not asc_:
                        enc = ~enc
                    enc = np.where(ok, enc, np.uint64(0))
                    rank = np.where(ok, 1, 0) if nf else np.where(ok, 0, 1)
                    lex.extend([enc, rank.astype(np.uint8)])
                order = np.lexsort(tuple(lex))
                work = df.iloc[order]
            else:
                work = df
            g = work.groupby(pcols, dropna=False, sort=False) if pcols \
                else work.assign(__one=1).groupby("__one")
            if isinstance(fn, RowNumber):
                res = g.cumcount() + 1
            elif isinstance(fn, Rank):
                res = _sorted_rank(work, pcols, ocols, dense=False)
            elif isinstance(fn, DenseRank):
                res = _sorted_rank(work, pcols, ocols, dense=True)
            elif isinstance(fn, PercentRank):
                rk = _sorted_rank(work, pcols, ocols, dense=False)
                cnt = (g[work.columns[0]].transform("size") if pcols
                       else pd.Series(len(work), index=work.index))
                res = ((rk - 1) / (cnt - 1).clip(lower=1)) \
                    .where(cnt > 1, other=0.0)
            elif isinstance(fn, NTile):
                rn = g.cumcount()
                cnt = g[work.columns[0]].transform("size") \
                    if pcols else pd.Series(len(work), index=work.index)
                base, rem = cnt // fn.n, cnt % fn.n
                big = rem * (base + 1)
                res = (rn.where(rn < big, other=None).floordiv(base + 1)
                       .fillna(rem + (rn - big) // base.clip(lower=1))
                       .astype("int64") + 1)
            elif isinstance(fn, NthValue):
                res = _host_nth_value(fn, g, work, batch)
            elif isinstance(fn, (Lag, Lead)):
                # validity-aware shift: out-of-partition slots are SQL
                # NULL (or the default), never NaN — pandas shift's NaN
                # fill is indistinguishable from a real NaN value
                res = _host_shift(fn, g, work, batch)
            elif isinstance(fn, AggregateExpression):
                res = self._host_agg(fn, spec, g, work, batch)
            else:
                raise NotImplementedError(type(fn).__name__)
            df[name] = res.reindex(df.index) if hasattr(res, "reindex") \
                else res
            # drop only the temporaries THIS loop created — input columns
            # may legitimately start with "__" (e.g. SQL-hoisted windows)
            temps = set(pcols + ocols) | {"__v", "__a", "__one"}
            df = df.drop(columns=[c for c in df.columns if c in temps])
        from ..types import to_arrow
        arrays = []
        n_in = len(t.column_names)
        for fi, f in enumerate(self._schema.fields):
            if fi < n_in:
                # passthrough columns come straight from the input table:
                # the pandas round trip turns SQL NULL into NaN and could
                # not restore it (NaN-vs-NULL parity)
                col = t.column(fi).combine_chunks()
                if col.type != to_arrow(f.dtype):
                    col = col.cast(to_arrow(f.dtype))
                arrays.append(col)
                continue
            isf = f.dtype.name in ("float", "double")
            vals = [x if (isf and isinstance(x, float) and np.isnan(x))
                    else (None if pd.isna(x) else x)
                    for x in df[f.name].tolist()]
            arrays.append(pa.array(vals, type=to_arrow(f.dtype)))
        yield ColumnarBatch.from_arrow(
            pa.Table.from_arrays(arrays, names=self._schema.names()))

    def _host_agg(self, fn, spec, g, work, batch):
        """Frame aggregation on the host oracle with Spark semantics:
        SQL NULL (arrow validity) is skipped, NaN is a VALUE that poisons
        any frame containing it; FOLLOWING bounds are honored (pandas
        rolling is trailing-only and skips NaN, so frames are computed
        from per-partition prefix arrays instead)."""
        import numpy as np
        import pandas as pd
        n = len(work)
        if isinstance(fn, CountStar):
            vals = np.ones(n)
            ok = np.ones(n, dtype=bool)
        else:
            import pyarrow as pa
            arr = fn.child.eval_host(batch)
            if isinstance(arr, pa.ChunkedArray):
                arr = arr.combine_chunks()
            ok_full = ~np.asarray(arr.is_null())
            v_full = np.asarray(arr.to_pandas().to_numpy(), dtype=object)
            pos = work.index.to_numpy()
            vals = v_full[pos]
            ok = ok_full[pos]
        import pyarrow as pa
        if isinstance(fn, (Count, CountStar)):
            is_f, is_num, is_dec = False, True, False
        else:
            # decimal SUM/AVG take the float64 path (approximate — exact
            # decimal accumulation is future work); decimal MIN/MAX stay
            # exact via the object path below; int64 stays exact
            is_dec = pa.types.is_decimal(arr.type)
            is_f = pa.types.is_floating(arr.type) or is_dec
            is_num = is_f or pa.types.is_integer(arr.type)
        if is_f:
            fvals = np.asarray([np.nan if x is None else float(x)
                                for x in vals], dtype=np.float64)
        elif is_num:
            # int64 prefix sums stay EXACT (float64 would lose precision
            # past 2^53 and mangle decimals)
            fvals = np.asarray([0 if x is None else int(x)
                                for x in vals], dtype=np.int64)
        else:
            fvals = vals            # strings/dates: min/max only

        frame = spec.frame
        if frame is None:
            frame = ("rows", None, 0) if spec.order_by \
                else ("rows", None, None)
        kind, lo, hi = frame

        out = np.empty(n, dtype=object)
        start = 0
        sizes = (g.size().to_numpy() if hasattr(g, "size") else [n])
        for sz in sizes:
            sl = slice(start, start + int(sz))
            v = fvals[sl]
            k = ok[sl]
            m = int(sz)
            if is_num:
                isn = np.where(k, np.isnan(v), False) if is_f \
                    else np.zeros(m, dtype=bool)
                fin = k & ~isn
                acc = np.where(fin, v, 0).cumsum()
            else:
                isn = fin = np.zeros(m, dtype=bool)
                acc = np.zeros(m)
            nc = isn.astype(np.int64).cumsum()
            cnt = k.astype(np.int64).cumsum()
            i = np.arange(m)
            lo_i = np.zeros(m, np.int64) if lo is None \
                else np.clip(i + lo, 0, m)
            hi_i = np.full(m, m - 1) if hi is None \
                else np.minimum(i + hi, m - 1)
            empty = hi_i < lo_i
            hs = np.clip(hi_i, 0, m - 1)

            def dif(p):
                upper = p[hs]
                lower = np.where(lo_i > 0, p[np.maximum(lo_i - 1, 0)], 0)
                return np.where(empty, 0, upper - lower)

            if isinstance(fn, (Min, Max)) and (lo is not None
                                               or hi is not None):
                # bounded frames: direct per-row slice evaluation — the
                # oracle optimizes for obviousness, not speed
                res = np.empty(m, dtype=object)
                src = vals[sl]
                for j in range(m):
                    a = 0 if lo is None else max(j + lo, 0)
                    b_ = m - 1 if hi is None else min(j + hi, m - 1)
                    if b_ < a:
                        res[j] = None
                        continue
                    win_v = src[a:b_ + 1]
                    win_k = k[a:b_ + 1]
                    sel = [x for x, kk2 in zip(win_v, win_k) if kk2]
                    if not sel:
                        res[j] = None
                        continue
                    if is_f:
                        fs = [float(x) for x in sel]
                        nn = [x for x in fs if not np.isnan(x)]
                        if isinstance(fn, Max):
                            res[j] = np.nan if len(nn) < len(fs) \
                                else max(nn)
                        else:
                            res[j] = min(nn) if nn else np.nan
                    else:
                        res[j] = (min(sel) if isinstance(fn, Min)
                                  else max(sel))
                out[sl] = res
                start += int(sz)
                continue
            if isinstance(fn, (Min, Max)):
                # whole partition; Spark: NaN is greatest, all-NaN -> NaN
                if not k.any():
                    val = None
                elif not is_num or is_dec:  # strings/dates/decimals: exact
                    src = vals[sl] if is_dec else v
                    vv = [x for x, kk in zip(src, k) if kk]
                    val = min(vv) if isinstance(fn, Min) else max(vv)
                elif isinstance(fn, Max):
                    val = np.nan if (is_f and isn.any()) else v[fin].max()
                elif len(v[fin]):
                    val = v[fin].min()
                else:
                    val = np.nan
                out[sl] = np.full(m, val, dtype=object)
                start += int(sz)
                continue
            s_ = dif(acc)
            c_ = dif(cnt)
            has_nan = dif(nc) > 0
            if isinstance(fn, (Count, CountStar)):
                res = c_.astype(object)
            elif isinstance(fn, Average):
                res = np.where(has_nan, np.nan,
                               s_ / np.maximum(c_, 1))
                res = np.asarray(res, dtype=object)
                res[c_ == 0] = None
            else:  # Sum
                if is_f:
                    res = np.where(has_nan, np.nan, s_)
                else:
                    res = s_        # int64: exact, no NaN possible
                res = np.asarray(res, dtype=object)
                res[c_ == 0] = None
                if not is_f:
                    res = np.asarray(
                        [None if x is None else int(x) for x in res],
                        dtype=object)
            out[sl] = res
            start += int(sz)
        return pd.Series(out, index=work.index)

    def describe(self):
        return "CpuWindow[" + ", ".join(n for _, _, n in
                                        self.window_exprs) + "]"


def _host_nth_value(fn, g, work, batch):
    """Running-frame nth value: the partition's n-th row's value for
    rows at position >= n-1, else NULL."""
    import numpy as np
    import pyarrow as pa
    arr = fn.child.eval_host(batch)
    if isinstance(arr, pa.ChunkedArray):
        arr = arr.combine_chunks()
    ok_full = ~np.asarray(arr.is_null())
    v_full = np.asarray(arr.to_pandas().to_numpy(), dtype=object)
    pos = work.index.to_numpy()
    vals, ok = v_full[pos], ok_full[pos]
    out = np.empty(len(work), dtype=object)
    start = 0
    for sz in g.size().to_numpy():
        m = int(sz)
        res = np.full(m, None, dtype=object)
        if m >= fn.n:
            v = vals[start + fn.n - 1] if ok[start + fn.n - 1] else None
            res[fn.n - 1:] = v
        out[start:start + m] = res
        start += m
    import pandas as pd
    return pd.Series(out, index=work.index)


def _host_shift(fn, g, work, batch):
    import numpy as np
    import pandas as pd
    import pyarrow as pa
    arr = fn.child.eval_host(batch)
    if isinstance(arr, pa.ChunkedArray):
        arr = arr.combine_chunks()
    ok_full = ~np.asarray(arr.is_null())
    v_full = np.asarray(arr.to_pandas().to_numpy(), dtype=object)
    pos = work.index.to_numpy()
    vals, ok = v_full[pos], ok_full[pos]
    off = fn.signed_offset
    out = np.empty(len(work), dtype=object)
    start = 0
    for sz in g.size().to_numpy():
        m = int(sz)
        sl_v, sl_k = vals[start:start + m], ok[start:start + m]
        res = np.full(m, fn.default, dtype=object)   # outside partition
        if off >= 0:                                  # lag: shift right
            d = min(off, m)
            src_v, src_k = sl_v[:m - d], sl_k[:m - d]
            res[d:] = np.where(src_k, src_v, None)
        else:                                         # lead: shift left
            d = min(-off, m)
            src_v, src_k = sl_v[d:], sl_k[d:]
            res[:m - d] = np.where(src_k, src_v, None)
        out[start:start + m] = res
        start += m
    return pd.Series(out, index=work.index)


def _sorted_rank(work, pcols, ocols, dense: bool):
    """rank/dense_rank computed POSITIONALLY over the pre-sorted frame:
    the sort already applied each order column's ASC/DESC and null
    placement, so equal-key runs are contiguous and direction never needs
    re-deriving (pandas' value rank() is ascending-only and was wrong for
    DESC orders). Nulls compare EQUAL for ranking (Spark semantics), so
    run detection uses null-safe per-column equality, never tuple !=."""
    import pandas as pd
    grp = [work[c] for c in pcols] if pcols else \
        [pd.Series(0, index=work.index)]
    anchor = work[ocols[0]] if ocols else pd.Series(0, index=work.index)
    rn = anchor.groupby(grp, dropna=False, sort=False).cumcount() + 1
    same = pd.Series(True, index=work.index)
    for c in ocols:
        col, prev = work[c], work[c].shift(1)
        same &= (col == prev) | (col.isna() & prev.isna())
    newrun = (rn == 1) | ~same
    if dense:
        return newrun.groupby(grp, dropna=False, sort=False) \
            .cumsum().astype("int64")
    r = rn.where(newrun)
    return r.groupby(grp, dropna=False, sort=False).ffill().astype("int64")