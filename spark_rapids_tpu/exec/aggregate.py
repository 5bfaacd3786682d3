"""Hash-aggregate exec, TPU style.

Reference: GpuHashAggregateExec (GpuAggregateExec.scala:1776) — a 3-phase
pipeline: per-batch first-pass aggregation, merge passes over partial results
(GpuMergeAggregateIterator:718), finalize projection.

TPU-first divergence: the per-batch groupby avoids scatter/gather entirely
(they serialize on the TPU scalar core). Dictionary-coded keys with a small
cardinality product take the direct-addressing kernel (dense one-hot
broadcast+reduce over a bucketed static group count); everything else takes
the sort pipeline in groupby_core (one variadic lax.sort carrying payloads,
segmented scans, one compaction sort), all static shapes, one fused XLA
kernel per phase per shape bucket. Merge uses the same kernels with each
aggregate's merge semantics — identical maths to the reference's merge pass.

Memory behaviour mirrors the reference: partial batches are Spillable, merge
runs under the retry framework, so injected/real RetryOOM spills and re-runs
(HashAggregateRetrySuite semantics).
"""
from __future__ import annotations

import functools
from typing import Dict, Iterator, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..columnar import ColumnarBatch, DeviceColumn, HostColumn, concat_batches
from ..columnar.bucketing import bucket_for
from ..columnar.transfer import traced_device_get
from ..exprs.aggregates import AggregateExpression
from ..exprs.compiler import _lane_pairs, _lane_rebuild
from ..exprs.base import (BoundReference, DVal, EvalContext, Expression,
                          collect_param_literals, literal_scalars,
                          literal_slot_map, parameterized_keys)
from ..mem import SpillableBatch, with_retry_no_split
from ..trace import core as trace_core
from ..types import (BOOL, FLOAT32, INT32, INT64, STRING, Schema,
                     StructField)
from .base import ESSENTIAL, ExecContext, TpuExec
from ..exprs import decimal_rules as D
from .groupby_core import segmented_groupby

__all__ = ["TpuHashAggregateExec", "CpuAggregateExec"]

_AGG_KERNEL_CACHE: Dict[Tuple, object] = {}
#: last observed group count per kernel shape: the optimistic single-
#: fetch attempt is skipped while the statistic exceeds the bound and
#: refreshed on every execution, so it adapts back when the data changes
#: (the aggregate analog of the joins' _TOTAL_STATS sizing)
_FAST_GROUPS: Dict[Tuple, int] = {}


def _build_groupby_kernel(key_exprs: Sequence[Expression],
                          aggs: Sequence[AggregateExpression],
                          schema: Schema, mode: str,
                          partial_counts: Optional[List[int]] = None,
                          in_schema: Optional[Schema] = None,
                          stages: Optional[list] = None,
                          n_codes: int = 0):
    """mode='update': key_exprs/agg inputs evaluated against ``schema``
    (the eval schema + appended __gk code columns). When ``stages`` is
    given, the kernel first applies the FUSED pre-stages — ("filter",
    cond) / ("project", exprs, out_schema) — starting from ``in_schema``
    (the actual child exec's schema): the scan→filter→project→groupby
    pipeline becomes ONE XLA computation with a row mask instead of a
    separate compaction kernel per stage, eliminating per-stage host
    syncs (each stalls the dispatch pipeline for a device round trip).
    mode='merge': schema is the partial schema [keys..., partials...] and
    aggs merge partial columns (referenced by ordinal; partial_counts gives
    how many partial columns each agg owns)."""
    dtypes = [f.dtype for f in schema.fields]
    num_keys = len(key_exprs)
    base_schema = in_schema if in_schema is not None else None
    base_dtypes = ([f.dtype for f in base_schema.fields]
                   if base_schema is not None else None)

    if mode == "update":
        value_exprs: List[List[Expression]] = [a.input_exprs() for a in aggs]
    else:
        # partial columns start after the keys, in agg order
        value_exprs = []
        ord_ = num_keys
        for a, n in zip(aggs, partial_counts):
            value_exprs.append([BoundReference(o, dtypes[o])
                                for o in range(ord_, ord_ + n)])
            ord_ += n

    from ..types import INT32
    slots = literal_slot_map(_param_exprs(
        key_exprs, aggs, mode, stages,
        value_exprs=value_exprs if mode == "update" else None))

    def prep(cols, num_rows, padded_len, scalars):
        """Shared traced prologue: pre-stages + key/value evaluation."""
        keep = None
        from ..exprs.base import StrVal
        if base_schema is not None:
            n_base = len(base_dtypes)
            base = [None if c is None
                    else (DVal(StrVal(c[0], c[2]), c[1], dt)
                          if len(c) == 3 else DVal(c[0], c[1], dt))
                    for c, dt in zip(cols[:n_base], base_dtypes)]
            codes = [DVal(c[0], c[1], INT32) for c in cols[n_base:]]
            sctx, keep = _apply_pre_stages(stages, base_schema, base,
                                           num_rows, padded_len,
                                           scalars, slots)
            dvals = list(sctx.columns) + codes
            # schema = eval schema + __gk fields; pad dvals to match
            dvals = dvals[:len(dtypes)] + [None] * (len(dtypes) - len(dvals))
            ctx = EvalContext(schema, dvals, num_rows, padded_len,
                              scalars, slots)
        else:
            dvals = [None if c is None
                     else (DVal(StrVal(c[0], c[2]), c[1], dt)
                           if len(c) == 3 else DVal(c[0], c[1], dt))
                     for c, dt in zip(cols, dtypes)]
            ctx = EvalContext(schema, dvals, num_rows, padded_len,
                              scalars, slots)
        with D.masked(jnp, lambda: ctx.row_mask() if keep is None else keep):
            keys = [e.eval_device(ctx) for e in key_exprs]
            vals = [[e.eval_device(ctx) for e in exprs]
                    for exprs in value_exprs]
        return keys, vals, keep

    def raw(cols, num_rows, padded_len, scalars=()):
        keys, vals, keep = prep(cols, num_rows, padded_len, scalars)
        return segmented_groupby(keys, vals, aggs, mode, num_rows,
                                 padded_len, row_mask=keep)

    @functools.partial(jax.jit, static_argnums=(2,))
    def kernel(cols, num_rows, padded_len, scalars=()):
        return raw(cols, num_rows, padded_len, scalars)

    kernel.n_param_slots = len(slots)
    kernel._prep = prep
    #: the untraced body: what a kernel that collects decimal overflow
    #: flags traces in its OWN scope (a nested jit would keep them)
    kernel._raw = raw
    kernel._value_exprs = value_exprs
    kernel.n_dispatches = 1      # one fused module per batch
    return kernel


def _build_groupby_kernel_split(key_exprs, aggs, schema, mode,
                                partial_counts=None, in_schema=None,
                                stages=None, n_codes=0):
    """The same groupby as _build_groupby_kernel but run as THREE
    separately-jitted dispatches (prologue+sort / scans / compaction
    sort). Identical maths — the stages are groupby_core's own pieces —
    but each XLA module is small: on this backend a lax.sort's compile
    time multiplies with surrounding module complexity (the fused two-key
    merge kernel never finished compiling in >20 min; split stages total
    ~1 min). Used on the classic multi-batch/merge path where the extra
    ~2 dispatch round trips are amortized per QUERY, not per batch-row;
    the fused form remains for the single-batch fast path and shard_map
    fragments (dispatch count dominates there)."""
    from .groupby_core import stage_scan
    fused = _build_groupby_kernel(key_exprs, aggs, schema, mode,
                                  partial_counts, in_schema, stages,
                                  n_codes)
    if not key_exprs:
        return fused         # global path has no sort — fused is cheap
    prep = fused._prep
    value_exprs = fused._value_exprs
    key_dtypes = [e.data_type(schema) for e in key_exprs]
    val_dtypes = [[e.data_type(schema) for e in exprs]
                  for exprs in value_exprs]

    from .encoding import grouping_operands

    # Sort operand budget: every operand in the variadic sort costs
    # compile time, so the split path carries the MINIMUM. Keys whose
    # grouping encoding is the standard (null_rank, key) pair are NOT
    # duplicated as payload — k_scan reconstructs (data, validity) from
    # the sorted operands themselves (validity = its flag bit; data =
    # operand cast back, canonicalized for floats — the
    # NormalizeFloatingNumbers semantics grouping already applies). The
    # original row index rides behind the keys as the LAST key: the keys
    # are then unique, and an unstable sort, which compiles in about half
    # a stable one's time, gives the stable order (PERF.md, PR 32).
    from ..exprs.base import StrVal

    def _reconstructible(dt):
        if dt == STRING:
            return True          # rect: words + length operands suffice
        if dt.np_dtype is None:
            return False         # decimal etc.: carried as payload lanes
        import numpy as _np
        shapes = jax.eval_shape(
            lambda d, v: tuple(grouping_operands(DVal(d, v, dt))),
            jax.ShapeDtypeStruct((1,), dt.np_dtype),
            jax.ShapeDtypeStruct((1,), _np.bool_))
        return len(shapes) == 2

    recon = [_reconstructible(dt) for dt in key_dtypes]
    #: keys whose null rank fits the flag operand below the padding's bit
    _FLAG_KEYS = 31

    @functools.partial(jax.jit, static_argnums=(2,))
    def k_prep(cols, num_rows, padded_len, scalars=()):
        """Prologue + key encoding ONLY — no sort. A lax.sort's compile
        time multiplies with everything else in its module (a fused
        filter/CASE prologue pushed the q28 update sort past 15 minutes),
        so the sort gets a module to itself with raw operands. Key ops
        come back as a NESTED per-key tuple (arities vary: scalar keys
        one operand, byte-rectangle strings 1 + W/8). Grouping asks
        equal keys to meet, not Spark's order, so the keys' null ranks
        (a uint8 operand a key) ride as bits of the ONE leading flag
        operand, below the padding's bit: each key operand taken out of
        the comparator is compile time (three keys at 524,288 rows: 357 s
        with the ranks as operands; PERF.md, PR 32)."""
        keys, vals, keep = prep(cols, num_rows, padded_len, scalars)
        if keep is None:
            keep = jnp.arange(padded_len, dtype=jnp.int32) < num_rows
        ops = [grouping_operands(k) for k in keys]
        n_flag = min(len(keys), _FLAG_KEYS)
        flag_t = jnp.uint8 if n_flag < 8 else jnp.uint32
        # the padding's bit on top: dropped rows sort behind every live one
        pad_flag = jnp.where(keep, flag_t(0), flag_t(1 << n_flag))
        for i, o in enumerate(ops[:n_flag]):
            pad_flag = pad_flag | (o[0].astype(flag_t) << i)
        key_ops = tuple(tuple(o[1:] if i < _FLAG_KEYS else o)
                        for i, o in enumerate(ops))
        payload = [jnp.arange(padded_len, dtype=jnp.int32)]
        for k, r in zip(keys, recon):
            if not r:
                payload.extend((k.data, k.validity))
        for vs in vals:
            for v in vs:
                payload.extend((v.data, v.validity))
        live = jnp.sum(keep).astype(jnp.int32)
        return (pad_flag, key_ops, tuple(payload)), live

    _sort_jits = {}

    def k_sort(flat, nk):
        """The bare variadic sort — nothing else in the module; the row
        index (the first payload) is its last key."""
        fn = _sort_jits.get(nk)
        if fn is None:
            def mk(flat, nk=nk):
                return jax.lax.sort(tuple(flat), num_keys=nk + 1,
                                    is_stable=False)
            fn = _sort_jits[nk] = jax.jit(mk)
        return fn(flat)

    @functools.partial(jax.jit, static_argnums=(1, 2))
    def k_scan(flat, arities, padded_len, live):
        it = iter(flat)
        s_ops = [next(it) for _ in range(1 + sum(arities))]
        perm = next(it)
        s_keys = []
        pos = 1
        i = 0                       # the key's bit in the flag operand
        for ar, dt, r in zip(arities, key_dtypes, recon):
            ops = s_ops[pos:pos + ar]
            pos += ar
            if i < _FLAG_KEYS:
                valid = (s_ops[0] >> i) & 1 == 0
            else:
                valid, ops = ops[0] == 0, ops[1:]
            i += 1
            if not r:
                s_keys.append(DVal(next(it), next(it), dt))
            elif dt == STRING:
                from ..columnar.strrect import unpack_words
                words, ln = ops[:-1], ops[-1]
                s_keys.append(DVal(
                    StrVal(unpack_words(list(words), 8 * len(words)),
                           ln.astype(jnp.int32)),
                    valid, dt))
            else:
                s_keys.append(DVal(ops[0].astype(dt.np_dtype), valid, dt))
        sorted_vals = [[DVal(next(it), next(it), dt) for dt in dts]
                       for dts in val_dtypes]
        # a group ends where the flags (a key's nullness) or a key differ
        ckey, carry, num_groups = stage_scan(
            aggs, mode, s_ops[:1] + s_ops, perm, s_keys, sorted_vals, live,
            padded_len)
        return ckey, carry, num_groups

    @functools.partial(jax.jit, static_argnums=(2,))
    def k_pack(ckey, carry, padded_len, num_groups):
        """The compaction sort + nested rebuild (stage_pack), its own
        module."""
        from .groupby_core import stage_pack
        return stage_pack(ckey, carry, num_groups, key_dtypes,
                          padded_len)

    def kernel(cols, num_rows, padded_len, scalars=()):
        (pad_flag, key_ops, payload), live = k_prep(
            cols, num_rows, padded_len, scalars)
        arities = tuple(len(g) for g in key_ops)
        flat = [pad_flag]
        for g in key_ops:
            flat.extend(g)
        flat.extend(payload)
        sorted_all = k_sort(tuple(flat), 1 + sum(arities))
        ckey, carry, ng = k_scan(tuple(sorted_all), arities, padded_len,
                                 live)
        key_outs, partial_outs, _ = k_pack(ckey, carry, padded_len, ng)
        return list(key_outs), list(partial_outs), ng

    kernel.n_param_slots = fused.n_param_slots
    kernel.n_dispatches = 4      # prep + sort + scan + pack modules
    return kernel


def _apply_pre_stages(stages, in_schema, base_dvals, num_rows, padded_len,
                      scalars=None, slots=None):
    """Trace the fused ("filter", cond) / ("project", exprs, schema)
    pre-stages over the base context; returns (final EvalContext over the
    last stage's schema, keep mask). Shared by the sort-based and
    direct-addressing update kernels so the fusion semantics cannot
    diverge between them."""
    ctx = EvalContext(in_schema, base_dvals, num_rows, padded_len,
                      scalars, slots)
    keep = ctx.row_mask()
    for st in stages:
        # a checked decimal operation's overflow counts for the rows that
        # reached the stage (exprs/decimal_rules.py; traces nothing where
        # no kernel collects)
        with D.masked(jnp, lambda k=keep: k):
            if st[0] == "filter":
                pv = st[1].eval_device(ctx)
                keep = jnp.logical_and(
                    keep, jnp.logical_and(pv.data, pv.validity))
            else:
                _, exprs, out_schema = st
                dv = [e.eval_device(ctx)
                      if e.fully_device_supported(ctx.schema) is None
                      else None for e in exprs]
                ctx = EvalContext(out_schema, dv, num_rows, padded_len,
                                  ctx.scalars, ctx.literal_slots)
    return ctx, keep


def _param_exprs(key_exprs, aggs, mode, stages, value_exprs=None):
    """The expression list (deterministic order) whose parameterizable
    literals ride into the kernel as traced scalars — the ONE definition
    of slot order shared by kernel build and call sites. Builders pass
    their already-materialized ``value_exprs`` (the objects the kernel
    traces over); callers omit it and get structurally-aligned fresh
    lists from input_exprs()."""
    exprs = []
    for st in (stages or ()):
        if st[0] == "filter":
            exprs.append(st[1])
        else:
            exprs.extend(st[1])
    exprs.extend(key_exprs)
    if mode == "update":
        if value_exprs is not None:
            for ve in value_exprs:
                exprs.extend(ve)
        else:
            for a in aggs:
                exprs.extend(a.input_exprs())
    return exprs


def _stage_key(stages):
    if not stages:
        return ()
    out = []
    for st in stages:
        if st[0] == "filter":
            out.append(("F", st[1].key()))
        else:
            out.append(("P", tuple(e.key() for e in st[1]),
                        tuple((f.name, f.dtype.name)
                              for f in st[2].fields)))
    return tuple(out)


def _agg_kernel_key(key_exprs, aggs, schema, mode, in_schema=None,
                    stages=None, n_codes=0):
    with parameterized_keys():
        return (tuple(e.key() for e in key_exprs),
                tuple(a.key() for a in aggs),
                tuple((f.name, f.dtype.name) for f in schema.fields), mode,
                tuple((f.name, f.dtype.name) for f in in_schema.fields)
                if in_schema is not None else None,
                _stage_key(stages), n_codes)


def _check_scalar_slots(kernel, scalars):
    """Kernel slot maps and call-site scalars come from SEPARATE
    traversals of the parameterizable-literal set (value_exprs at build
    vs fresh input_exprs() at call); the alignment is an invariant, not a
    given — fail loudly instead of silently misbinding constants."""
    n = getattr(kernel, "n_param_slots", None)
    if n is not None and n != len(scalars):
        raise RuntimeError(
            f"aggregate kernel literal-slot mismatch: kernel built with "
            f"{n} parameter slots, call site collected {len(scalars)}")


def _get_kernel(key_exprs, aggs, schema, mode, partial_counts=None,
                in_schema=None, stages=None, n_codes=0,
                split: bool = False):
    """``split=True`` returns the three-dispatch variant (cheap XLA
    compiles, ~2 extra round trips) — the right form for direct calls
    from the classic multi-batch/merge path. The default fused form is
    required wherever the kernel is traced INSIDE another jit (the fast
    single-batch kernel, shard_map fragments)."""
    key = _agg_kernel_key(key_exprs, aggs, schema, mode, in_schema,
                          stages, n_codes)
    if split:
        key = ("split",) + key
    k = _AGG_KERNEL_CACHE.get(key)
    if k is None:
        build = (_build_groupby_kernel_split if split
                 else _build_groupby_kernel)
        k = build(key_exprs, aggs, schema, mode, partial_counts,
                  in_schema, stages, n_codes)
        _AGG_KERNEL_CACHE[key] = k
    return k


def _count_carry(batches: int, flushes: int) -> None:
    """Tracer counter ``agg.carry``, once per aggregate execution: input
    batches folded into a device-resident carry, and carries that had to
    leave it for the windowed path (docs/profiling.md)."""
    tr = trace_core.TRACER
    if tr is not None:
        tr.counter("agg.carry", {"batches": batches, "flushes": flushes},
                   cat="exec")


def _kernel_cols(batch: ColumnarBatch) -> list:
    """A batch's columns as the direct kernels take them: (data,
    validity) per device column, None for what lives on the host."""
    return [(c.data, c.validity) if isinstance(c, DeviceColumn) else None
            for c in batch.columns]


def _direct_strides(cards, nkeys: int):
    """Per-key strides of the direct-addressed slot number
    gid = sum(code_i * stride_i); every key takes cards[i] + 1 codes (the
    last one is NULL), the first key is the most significant."""
    strides = []
    stride = jnp.int32(1)
    for i in reversed(range(nkeys)):
        strides.insert(0, stride)
        stride = stride * (cards[i] + 1)
    return strides


#: the hash buckets ONE division of the partials sorts their rows into: a
#: SHAPE of the partition kernel (its counts, and the bits the bucket
#: number takes in the sort key above the row index), not a threshold. The
#: partitions are runs of neighbouring buckets, packed under the cap from
#: the counts the operator reads, so a bucket is the grain a partition is
#: filled by (1/64 of the rows); a bucket that alone passes the cap (more
#: than 64 caps' worth of rows, or a skewed hash) is divided again. The
#: kernel's dense count over them is part of ``jit_agg_partition``'s
#: device time (PERF.md section 5)
_HASH_BUCKETS = 64


def _hash_operands(d, v) -> List[DVal]:
    """One lane of a group key as what the partition's hash folds. The
    partition needs a function of the key's VALUE, not Spark's hash of it:
    equal keys share it, and that is all. So a DOUBLE, which this chip
    cannot hash bit for bit (it holds one as two float32, and no bitcast
    reaches them), gives the float32 nearest it and the float32 nearest
    what is left, after the group-by's own normalization (-0.0 is 0.0, one
    NaN); every other lane hashes as its Spark type of the same width."""
    if d.dtype == jnp.float64:
        x = jnp.where(d == 0.0, 0.0, d)
        head = x.astype(jnp.float32)
        rest = jnp.where(jnp.isfinite(head),
                         (x - head.astype(jnp.float64)).astype(jnp.float32),
                         jnp.float32(0.0))
        return [DVal(head, v, FLOAT32), DVal(rest, v, FLOAT32)]
    if d.dtype == jnp.float32:
        return [DVal(d, v, FLOAT32)]
    if d.dtype == jnp.bool_:
        return [DVal(d, v, BOOL)]
    return [DVal(d, v, INT64 if d.dtype.itemsize == 8 else INT32)]


def _partition_kernel(key_lanes: Tuple[int, ...], seed: int, buckets: int):
    """A partial batch's lanes (1-D, a (data, validity) pair each; a
    string rectangle as its word lanes) -> (the lanes with the live rows
    in the order of their key's hash bucket, rows a bucket). The bucket is
    a hash of the key's lanes (``key_lanes``: which they are) modulo
    ``buckets``; the rows move in ONE single-key unstable sort that
    carries every lane (``front_sort``: the bucket number above the row
    index in a unique uint32 key, the validity lanes in its low bits), and
    the counts are a dense compare-and-sum, no scatter."""
    from ..columnar.segmented import front_sort
    from ..exprs.hash_fns import murmur3_fold_device
    from ..plan import exec_cache

    def agg_partition(lanes, num_rows, padded_len):
        h = murmur3_fold_device(
            [w for i in key_lanes for w in _hash_operands(*lanes[i])], seed)
        pid = h % buckets
        pid = jnp.where(pid < 0, pid + buckets, pid)
        live = jnp.arange(padded_len, dtype=jnp.int32) < num_rows
        pid = jnp.where(live, pid, jnp.int32(buckets))
        counts = jnp.sum(
            pid[None, :] == jax.lax.broadcasted_iota(
                jnp.int32, (buckets, padded_len), 0),
            axis=1, dtype=jnp.int32)
        rank = pid * jnp.int32(padded_len) \
            + jnp.arange(padded_len, dtype=jnp.int32)
        it = iter(front_sort(live, rank,
                             [lane for pair in lanes for lane in pair],
                             padded_len, (buckets + 1) * padded_len))
        return [(next(it), jnp.logical_and(next(it), live))
                for _ in lanes], counts

    return exec_cache.get_or_build_jit(
        f"agg.partition:{key_lanes, seed, buckets}", agg_partition,
        static_argnums=(2,))


def _pack_buckets(rows, cap: int) -> List[Tuple[int, int]]:
    """Runs [lo, hi) of neighbouring hash buckets (``rows``: the rows in
    each, over all partials) that hold at most ``cap`` rows together, as
    few as taking them in order gives; a bucket that alone passes the cap
    is a run of its own, and empty runs are left out."""
    parts, lo, acc = [], 0, 0
    for b, n in enumerate(rows):
        if acc and acc + n > cap:
            parts.append((lo, b))
            lo, acc = b, 0
        acc += int(n)
    if acc:
        parts.append((lo, len(rows)))
    return parts


def _one_width(group, nkeys: int):
    """The (batch, start, count) sources of one collect with their string
    key rectangles at one width: the same lanes in every source."""
    from ..columnar.strrect import ByteRectColumn, one_width
    cols = [list(b.columns) for b, _, _ in group]
    for k in range(nkeys):
        if isinstance(cols[0][k], ByteRectColumn):
            for per, c in zip(cols, one_width([per[k] for per in cols])):
                per[k] = c
    return [(ColumnarBatch(per, b.num_rows_raw, b.schema), at, n)
            for per, (b, at, n) in zip(cols, group)]


def _collect_kernel():
    """Runs of rows out of several batches -> one batch, the runs one
    after another: each source gives ``piece`` rows from its start (what
    lies past its count is overwritten by the next source's run, or lies
    past the total), copied with dynamic slices: no gather, no sort."""
    from ..plan import exec_cache

    def agg_collect(sources, starts, counts, piece, out_p):
        offs = jnp.cumsum(counts) - counts
        total = jnp.sum(counts)
        outs = None
        for i, cols in enumerate(sources):
            lanes = [lane for pair in cols for lane in pair]
            if outs is None:
                outs = [jnp.zeros((out_p + piece,), l.dtype) for l in lanes]
            for j, lane in enumerate(lanes):
                short = piece - lane.shape[0]
                if short > 0:
                    lane = jnp.pad(lane, (0, short))
                # a slice that would pass the end starts earlier (the
                # runtime clamps it so anyway) and is turned back
                at = jnp.minimum(starts[i], lane.shape[0] - piece)
                run = jnp.roll(jax.lax.dynamic_slice(lane, (at,), (piece,)),
                               at - starts[i])
                outs[j] = jax.lax.dynamic_update_slice(outs[j], run,
                                                       (offs[i],))
        live = jnp.arange(out_p, dtype=jnp.int32) < total
        it = iter(o[:out_p] for o in outs)
        return [(next(it), jnp.logical_and(next(it), live))
                for _ in sources[0]]

    return exec_cache.get_or_build_jit("agg.collect", agg_collect,
                                       static_argnums=(3, 4))


class TpuHashAggregateExec(TpuExec):
    """Device hash aggregate. String group keys are DICTIONARY-ENCODED at
    the exec boundary (TPU-first design: strings live on the host; the
    grouping machinery wants fixed-width device lanes — so each string key
    expression is evaluated on host, mapped through an exec-local
    string→int32 dictionary that stays consistent across batches, and the
    codes group on device; finalize decodes codes back to strings). The
    reference groups strings natively in cudf; this is the TPU analog."""

    def __init__(self, groupings: Sequence[Expression],
                 aggs: Sequence[AggregateExpression], child: TpuExec,
                 pre_stages: Optional[list] = None,
                 eval_schema: Optional[Schema] = None,
                 many_groups_hint: bool = False,
                 int_key_cards: Optional[Sequence] = None):
        super().__init__([child])
        self.groupings = list(groupings)
        self.aggs = list(aggs)
        #: planner-known high cardinality: never try the optimistic
        #: single-fetch path (its fused kernel compile would be wasted)
        self.many_groups_hint = many_groups_hint
        #: fused pre-stages: ("filter", cond) / ("project", exprs, schema)
        #: applied INSIDE the update kernel, bottom-up from the child's
        #: actual output (the folded scan→filter→project→agg pipeline)
        self.pre_stages = pre_stages or []
        cs = eval_schema if eval_schema is not None else child.output_schema()
        self._eval_schema = cs
        from ..types import INT32, STRING, IntegerType
        #: grouping ordinals that go through the string dictionary
        self._dict_keys = [i for i, g in enumerate(self.groupings)
                           if g.data_type(cs) == STRING]
        #: ordinal -> PROVEN cardinality for planner-constructed small
        #: int keys (values in [0, card), e.g. the union-rewrite branch
        #: id): these group by DIRECT one-hot addressing with no sort
        #: (the cudf hash-groupby trade). The key travels as an int32
        #: CODE in partials on BOTH the direct and split paths, so
        #: per-batch path choices merge consistently.
        cards_in = list(int_key_cards or [])
        self._int_cards = {
            i: int(c) for i, c in enumerate(cards_in)
            if c and isinstance(self.groupings[i].data_type(cs),
                                IntegerType)}
        # the kernel sees an augmented input schema: child columns plus one
        # appended int32 code column per string key; string groupings are
        # rewritten to BoundReferences onto those columns
        self._kernel_schema = cs
        self._kernel_groupings = list(self.groupings)
        if self._int_cards:
            from ..exprs.cast import Cast
            for i in self._int_cards:
                self._kernel_groupings[i] = Cast(self.groupings[i],
                                                 INT32)
        if self._dict_keys:
            extra = [StructField(f"__gk{i}", INT32, True)
                     for i in self._dict_keys]
            self._kernel_schema = Schema(list(cs.fields) + extra)
            for j, i in enumerate(self._dict_keys):
                self._kernel_groupings[i] = BoundReference(
                    len(cs.fields) + j, INT32)
        fields = [StructField(e.name_hint, e.data_type(cs), True)
                  for e in self.groupings]
        fields += [StructField(a.name_hint, a.data_type(cs), True)
                   for a in self.aggs]
        self._schema = Schema(fields)
        if self.pre_stages:
            # the trace contract for fused regions (exec/base._traced_iter
            # reads trace_args): one span per batch showing what the
            # update kernel swallowed — the partial-agg analog of
            # WholeStageExec's fused=[...] annotation
            self.trace_args = {"fused": [
                ("filter" if s[0] == "filter" else "project")
                for s in self.pre_stages] + ["partial-agg"]}
        # partial (intermediate) schema: keys then each agg's partials
        # (string keys travel as their int32 codes)
        pfields = [StructField(f"_k{i}",
                               e.data_type(self._kernel_schema), True)
                   for i, e in enumerate(self._kernel_groupings)]
        self._partial_counts = []
        afields = []
        for ai, a in enumerate(self.aggs):
            pts = a.partial_types(cs)
            self._partial_counts.append(len(pts))
            for pi, pt in enumerate(pts):
                afields.append(StructField(f"_a{ai}_{pi}", pt, True))
        self._partial_schema_dict = Schema(pfields + afields)
        self._partial_schema = self._partial_schema_dict
        # rect-key variant: string keys keep their STRING type (byte
        # rectangles ride the kernels directly, no int32 code columns)
        self._partial_schema_rect = Schema(
            [StructField(f"_k{i}", e.data_type(cs), True)
             for i, e in enumerate(self.groupings)] + afields)
        self._rect_mode = False
        #: checked decimal operations (exprs/decimal_rules.py) traced by
        #: the update kernels (pre-stages, keys, aggregate inputs) and by
        #: every aggregate's finalize: where there are any, the kernels
        #: carry a count of overflowed rows to the operator's own fetch
        self._input_checks = self._count_input_checks()
        self._decimal_checks = self._input_checks + sum(
            a.decimal_checks(cs) for a in self.aggs)

    def _count_input_checks(self) -> int:
        n = D.checked_ops_of_stages(
            self.pre_stages, self.children[0].output_schema())[0] \
            if self.pre_stages else 0
        n += sum(D.checked_ops(g, self._kernel_schema)
                 for g in self._kernel_groupings)
        for a in self.aggs:
            n += sum(D.checked_ops(e, self._kernel_schema)
                     for e in a.input_exprs())
        return n

    def _settle_overflow(self, rows: int) -> None:
        """The end of this execution's decimal checks: the counter, and
        the loud error where a row left the lane."""
        D.count_checked(self._decimal_checks, rows)
        if rows:
            raise D.overflow_error(rows, self.describe())

    def output_schema(self) -> Schema:
        return self._schema

    # ------------------------------------------------------------------
    def _run_kernel_raw(self, kernel, batch: ColumnarBatch,
                        extra_cols=(), scalars=()):
        """Dispatch the agg kernel; NO device sync — returns the raw
        (outs, num_groups device scalar) pair so multi-batch first passes
        can overlap every batch's kernel and resolve all counts in ONE
        stacked fetch (a per-batch ``int(num_groups)`` costs a full device
        round trip each and serializes the pipeline)."""
        cols = self._raw_cols(batch, extra_cols)
        _check_scalar_slots(kernel, scalars)
        key_outs, partial_outs, num_groups = kernel(
            cols, jnp.int32(batch.num_rows_raw), batch.padded_len, scalars)
        return list(key_outs) + list(partial_outs), num_groups

    @staticmethod
    def _raw_cols(batch: ColumnarBatch, extra_cols=()) -> list:
        from ..columnar.strrect import ByteRectColumn
        cols = []
        for c in batch.columns:
            if isinstance(c, ByteRectColumn):
                cols.append((c.data, c.validity, c.lengths))
            elif isinstance(c, DeviceColumn):
                cols.append((c.data, c.validity))
            else:
                cols.append(None)
        for c in extra_cols:
            cols.append((c.data, c.validity))
        return cols

    def _probe_overflow(self, batch: ColumnarBatch, extra_cols) -> None:
        """The sort path's update kernels have no channel for decimal
        overflow flags (their lanes are NULL all the same): where the
        aggregate's inputs hold checked operations, one more small
        dispatch a batch traces the same prologue and counts the flagged
        rows, for the sink to fetch."""
        key = ("ovfprobe",) + self._kernel_key
        probe = _AGG_KERNEL_CACHE.get(key)
        if probe is None:
            prep = _get_kernel(
                self._kernel_groupings, self.aggs, self._kernel_schema,
                "update",
                in_schema=(self.children[0].output_schema()
                           if self.pre_stages else None),
                stages=self.pre_stages or None,
                n_codes=len(self._dict_keys))._prep

            @functools.partial(  # tpulint: disable=adhoc-jit
                jax.jit, static_argnums=(2,))
            def probe(cols, num_rows, padded_len, scalars=()):
                with D.collecting() as col:
                    prep(cols, num_rows, padded_len, scalars)
                return col.rows(jnp)
            _AGG_KERNEL_CACHE[key] = probe
        D.defer(probe(self._raw_cols(batch, extra_cols),
                      jnp.int32(batch.num_rows_raw), batch.padded_len,
                      self._upd_scalars), self._input_checks)

    def _slice_to_count(self, outs, n, out_schema: Schema) -> ColumnarBatch:
        """Re-bucket raw kernel outputs once the group count is known:
        group counts are usually orders of magnitude below the input
        bucket; slicing keeps the merge pass (another sort) tiny."""
        from ..columnar.strrect import ByteRectColumn
        from ..exprs.base import StrVal
        target = bucket_for(int(n))
        out_cols = []
        for (d, v), f in zip(outs, out_schema.fields):
            if isinstance(d, StrVal):
                b, ln = d.bytes_, d.lengths
                if target < b.shape[0]:
                    b, ln, v = b[:target], ln[:target], v[:target]
                out_cols.append(ByteRectColumn(
                    b, v, ln,
                    ascii_only=getattr(self, "_rect_ascii", True)))
                continue
            if target < d.shape[0]:
                d, v = d[:target], v[:target]
            out_cols.append(DeviceColumn(d, v, f.dtype))
        return ColumnarBatch(out_cols, int(n), out_schema)

    def _run_kernel(self, kernel, batch: ColumnarBatch,
                    out_schema: Schema, extra_cols=(),
                    scalars=(), lazy: bool = False) -> ColumnarBatch:
        outs, num_groups = self._run_kernel_raw(kernel, batch, extra_cols,
                                                scalars)
        if lazy:
            # keep the count on device (resolved by the sink fetch); the
            # outputs stay at the input bucket — callers use this when the
            # input is already group-sized (merge passes), where slicing
            # would buy nothing but the sync would cost a round trip
            from ..columnar.strrect import ByteRectColumn
            from ..exprs.base import StrVal
            out_cols = [
                (ByteRectColumn(d.bytes_, v, d.lengths,
                                ascii_only=getattr(self, "_rect_ascii",
                                                   True))
                 if isinstance(d, StrVal) else DeviceColumn(d, v, f.dtype))
                for (d, v), f in zip(outs, out_schema.fields)]
            return ColumnarBatch(out_cols, num_groups, out_schema)
        return self._slice_to_count(outs, int(num_groups), out_schema)

    # -- string-key dictionary encoding --------------------------------
    def _encode_key(self, j: int, i: int, batch: ColumnarBatch):
        """ONE implementation of dictionary-encoding a string group key
        through the exec-local dictionary (consistent global codes across
        batches AND across the fused/classic paths — they must agree when
        the optimistic path bails out mid-query).

        Returns (data, validity, gmap, already_global):
          * DictColumn fast path: device codes in the SOURCE dictionary's
            space + the source->global remap table (applied later, on
            device, fused into the kernel when possible);
          * general path (computed keys, host strings): host-encoded codes
            already in GLOBAL space, gmap=None.
        """
        import pyarrow as pa
        from ..columnar import DictColumn
        from ..exprs.base import Alias, ColumnRef
        p = batch.padded_len
        d = self._dicts[j]
        g = self.groupings[i]
        if isinstance(g, Alias):
            g = g.children[0]
        src = None
        if isinstance(g, ColumnRef) and g.name in batch.schema.names():
            src = batch.column_by_name(g.name)
        if isinstance(src, DictColumn):
            # the exec-local dictionary only appends, so the remap of a
            # source dictionary stays true for every later batch that
            # carries the same one (the scan cache's batches do)
            seen = self._gmaps.get(j)
            if seen is None or seen[0] is not src.dictionary:
                seen = self._gmaps[j] = (src.dictionary, np.asarray(
                    [d.setdefault(s_, len(d)) for s_ in src.dictionary],
                    dtype=np.int32))
            return src.data, src.validity, seen[1], False
        arr = g.eval_host(batch)
        if isinstance(arr, pa.ChunkedArray):
            arr = arr.combine_chunks()
        de = arr.dictionary_encode()
        gmap = np.asarray([d.setdefault(s_, len(d))
                           for s_ in de.dictionary.to_pylist()],
                          dtype=np.int32)
        valid = ~np.asarray(de.indices.is_null())
        idx = np.asarray(de.indices.fill_null(0).to_numpy(
            zero_copy_only=False), dtype=np.int64)
        codes = gmap[idx] if len(gmap) else np.zeros(len(idx), np.int32)
        n = batch.num_rows      # host encode needs the exact count anyway
        data = np.zeros(p, dtype=np.int32)
        vmask = np.zeros(p, dtype=bool)
        data[:n] = codes[:n]
        vmask[:n] = valid[:n]
        return jnp.asarray(data), jnp.asarray(vmask), None, True

    def _augment(self, batch: ColumnarBatch) -> list:
        """One int32 GLOBAL-code device column per string group key (the
        classic/sort path: the remap is applied here with one dispatch)."""
        if not self._dict_keys:
            return []
        from ..columnar.segmented import onehot_gather
        from ..types import INT32
        cols = []
        for j, i in enumerate(self._dict_keys):
            data, validity, gmap, already_global = \
                self._encode_key(j, i, batch)
            if not already_global:
                if len(gmap):
                    data = onehot_gather(jnp.asarray(gmap), data, len(gmap))
                else:
                    data = jnp.zeros(batch.padded_len, jnp.int32)
            cols.append(DeviceColumn(data, validity, INT32))
        return cols

    def _augment_pairs(self, batch: ColumnarBatch):
        """Dict-key operands for the FUSED dense kernel: per key a raw
        (codes, validity) device pair plus its dictionary->global-code
        remap (numpy; identity when codes are already global) — the remap
        is applied INSIDE the kernel, so no extra dispatch per key."""
        if not self._dict_keys:
            return [], []
        pairs, remaps = [], []
        for j, i in enumerate(self._dict_keys):
            data, validity, gmap, already_global = \
                self._encode_key(j, i, batch)
            pairs.append((data, validity))
            if already_global:
                card = max(len(self._dicts[j]), 1)
                remaps.append(np.arange(card, dtype=np.int32))
            else:
                remaps.append(gmap if len(gmap)
                              else np.zeros(1, np.int32))
        return pairs, remaps

    def _inverse_dict(self, j: int) -> list:
        """code -> string list for dictionary key ordinal j."""
        inv = [None] * len(self._dicts[j])
        for s, c in self._dicts[j].items():
            inv[c] = s
        return inv

    def _decode_keys(self, out_cols: List, num_rows: int) -> List:
        """Replace int32 code key columns with device DictColumns whose
        dictionaries are sorted — only a tiny remap table touches the
        wire; the strings materialize lazily at the final sink (one
        batched fetch there instead of one per key here). Int-carded
        keys' codes ARE their values — just widen to the declared
        type."""
        for i in self._int_cards:
            col = out_cols[i]
            dt = self._schema.fields[i].dtype
            out_cols[i] = DeviceColumn(
                col.data.astype(dt.np_dtype), col.validity, dt)
        if not self._dict_keys or self._rect_mode:
            # rect keys pass through as ByteRectColumns: the sink decodes
            # the (group-sized) rectangles directly
            return out_cols
        from ..columnar import DictColumn
        from ..types import STRING
        for j, i in enumerate(self._dict_keys):
            inv = self._inverse_dict(j)
            col = out_cols[i]
            if not inv:
                out_cols[i] = DictColumn(col.data, col.validity, STRING,
                                         np.asarray([], dtype=object))
                continue
            inv = np.asarray(inv, dtype=object)
            order = np.argsort(inv)
            rank = np.empty(len(inv), np.int32)
            rank[order] = np.arange(len(inv), dtype=np.int32)
            codes2 = jnp.take(jnp.asarray(rank), col.data, mode="clip")
            out_cols[i] = DictColumn(codes2, col.validity, STRING,
                                     inv[order])
        return out_cols

    #: optimistic single-fetch group bound: the fused update+finalize
    #: kernel slices outputs to this many rows so num_groups AND the
    #: results come back in ONE device_get; more groups -> slow path
    OPTIMISTIC_GROUPS = 4096     # overridden per query from conf

    def _get_fast_kernel(self, update_k, kernel_key):
        cached = _AGG_KERNEL_CACHE.get(
            ("fast", self.OPTIMISTIC_GROUPS) + kernel_key)
        if cached is not None:
            return cached
        aggs, pcounts = self.aggs, self._partial_counts
        nkeys = len(self._kernel_groupings)
        ptypes = [f.dtype for f in self._partial_schema.fields]
        OPT = self.OPTIMISTIC_GROUPS

        checked = self._decimal_checks > 0

        @functools.partial(jax.jit, static_argnums=(2,))
        def fast(cols, num_rows, padded_len, scalars=()):
            with D.collecting(checked) as col:
                # collecting: the update kernel's body is traced in THIS
                # scope, so that its overflow flags can leave with the
                # packed result
                key_outs, partial_outs, num_groups = (
                    update_k._raw if checked else update_k)(
                        cols, num_rows, padded_len, scalars)
                outs = list(key_outs)
                ord_ = 0
                for ai, a in enumerate(aggs):
                    parts = [DVal(partial_outs[o][0], partial_outs[o][1],
                                  ptypes[nkeys + o])
                             for o in range(ord_, ord_ + pcounts[ai])]
                    ord_ += pcounts[ai]
                    fin = a.finalize(parts)
                    outs.append((fin.data, fin.validity))
            from ..columnar.packing import pack_traced
            flat = [num_groups] + ([col.rows(jnp)] if checked else []) \
                + [x for d, v in outs for x in (d[:OPT], v[:OPT])]
            spec_cell[padded_len] = [(np.dtype(x.dtype), tuple(x.shape))
                                     for x in flat]
            return pack_traced(flat)

        spec_cell = {}
        fast.out_specs = spec_cell
        fast.n_param_slots = getattr(update_k, "n_param_slots", None)
        _AGG_KERNEL_CACHE[("fast", self.OPTIMISTIC_GROUPS)
                          + kernel_key] = fast
        return fast

    def _get_fast_direct_kernel(self, g_bucket: int):
        """Direct-addressing groupby for ALL-dictionary-coded keys with a
        small cardinality product: gid = Σ code_i·stride_i — NO 1M-row
        sort (the sort is the dominant FLOPs of the sort-based path; the
        reference's cudf hash groupby makes the same trade). The static
        segment count is the smallest bucket >= the cardinality product,
        so the dense one-hot reduction (columnar/segmented.py) only pays
        for the groups that can exist; cardinalities themselves still ride
        in traced, so dictionary growth recompiles only on a bucket
        crossing (<=5 variants), never per new dictionary entry."""
        key = ("fastdirect", self.OPTIMISTIC_GROUPS,
               g_bucket) + self._kernel_key
        cached = _AGG_KERNEL_CACHE.get(key)
        if cached is not None:
            return cached
        core = self._build_direct_core(g_bucket)
        spec_cell = {}
        finish = self._build_direct_finish(g_bucket, spec_cell)

        @functools.partial(jax.jit, static_argnums=(2,))
        def fast_direct(cols, num_rows, padded_len, cards, scalars,
                        code_pairs, remaps):
            key_outs, partial_outs, num_groups, overflow = core(
                cols, num_rows, padded_len, cards, scalars, code_pairs,
                remaps)
            return finish(key_outs, partial_outs, num_groups, padded_len,
                          overflow)

        fast_direct.out_specs = spec_cell
        fast_direct.n_param_slots = core.n_param_slots
        _AGG_KERNEL_CACHE[key] = fast_direct
        return fast_direct

    def _build_direct_finish(self, g_bucket: int, spec_cell: dict):
        """The traced TAIL shared by the fused single-batch kernel and the
        carried path's once-a-query kernel: finalize every aggregate over
        the compacted G slots and pack (count, columns cut to the
        optimistic bound) for ONE fetch. The packed layout is recorded in
        ``spec_cell[spec_key]`` while tracing."""
        aggs, pcounts = self.aggs, self._partial_counts
        nkeys = len(self._kernel_groupings)
        ptypes = [f.dtype for f in self._partial_schema.fields]
        OPT = self.OPTIMISTIC_GROUPS
        G = g_bucket
        checked = self._decimal_checks > 0

        def finish(key_outs, partial_outs, num_groups, spec_key,
                   overflow=None):
            """``overflow``: the rows the update kernels flagged (a
            traced int scalar), where the aggregate checks decimals; the
            finalizers' own flags are added and the sum is packed after
            the group count."""
            outs = list(key_outs)
            live = jnp.arange(G, dtype=jnp.int32) < num_groups
            ord_ = 0
            with D.collecting(checked) as col:
                for ai, a in enumerate(aggs):
                    parts = []
                    for o in range(ord_, ord_ + pcounts[ai]):
                        cd, cv = partial_outs[o]
                        parts.append(DVal(cd, jnp.logical_and(cv, live),
                                          ptypes[nkeys + o]))
                    ord_ += pcounts[ai]
                    fin = a.finalize(parts)
                    outs.append((fin.data, fin.validity))
            from ..columnar.packing import pack_traced
            flat = [num_groups] + (
                [overflow.astype(jnp.int32) + col.rows(jnp)]
                if checked else []) \
                + [x for d, v in outs for x in (d[:OPT], v[:OPT])]
            spec_cell[spec_key] = [(np.dtype(x.dtype), tuple(x.shape))
                                   for x in flat]
            return pack_traced(flat)

        return finish

    def _get_carry_kernels(self, g_bucket: int):
        """The multi-batch first pass of the direct-addressed group-by as
        ONE running partial on the device: ``(fold, tail, flush)``.

        ``fold(carry, batch operands) -> carry'`` runs the dense pipeline
        of _build_direct_core over the batch and merges the result into
        the carry with each aggregate's own ``merge``, slot onto slot, so
        a batch costs one dispatch, no fetch and no host-side slicing.
        ``carry`` = the G-sized partials and the occupancy, stacked into
        a few arrays; the cards its slots are laid out by travel beside
        it. The slot of a group depends on the (traced) cards,
        which grow with the exec-local dictionaries, so the carry's slots
        are re-derived under THIS batch's cards inside the same merge: a
        later batch may bring a new key value, a first NULL, or cross
        into a larger bucket (the carry then simply has fewer slots than
        G). ``tail(carry, cards)`` is the once-a-query end: compaction +
        finalize + pack for one fetch. ``flush(carry, cards)`` compacts it
        into the sort path's update contract (key-code rows + partials +
        num_groups) for a query that leaves the direct path midway."""
        key = ("carry", self.OPTIMISTIC_GROUPS,
               g_bucket) + self._kernel_key
        kernels = _AGG_KERNEL_CACHE.get(key)
        if kernels is None:
            kernels = _AGG_KERNEL_CACHE[key] = \
                self._build_carry_kernels(g_bucket)
        return kernels

    def _build_carry_kernels(self, g_bucket: int):
        """(fold, tail, flush), jitted; memoized by _get_carry_kernels in
        _AGG_KERNEL_CACHE beside the other aggregate kernels (hence the
        adhoc-jit waivers)."""
        from ..columnar.segmented import seg_sum
        aggs, pcounts = self.aggs, self._partial_counts
        nkeys = len(self._kernel_groupings)
        pfields = self._partial_schema.fields[nkeys:]
        ptypes = [f.dtype for f in pfields]
        pos_partials = self._position_partials()
        G = g_bucket
        core = self._build_direct_core(G)
        spec_cell = {}
        finish = self._build_direct_finish(G, spec_cell)
        # The carry crosses the jit boundary as FEW arrays: one [n, G]
        # block per distinct partial dtype plus one bool block (every
        # validity, then occupancy). Enqueueing a call costs this chip's
        # runtime about 75 us per OUTPUT buffer (1 output 0.2 ms, 25
        # outputs 2 ms; inputs are nearly free — PERF.md, PR 26), which at
        # one array per partial is more than a batch's device work.
        np_dtypes = [np.dtype(f.dtype.np_dtype) for f in pfields]
        checked = self._decimal_checks > 0
        if checked:
            # the rows the update kernels flagged so far (decimal
            # overflow) ride as one more int64 row of the carry, the
            # count in its slot 0: no array and no fetch of its own
            np_dtypes.append(np.dtype(np.int64))
        blocks = sorted(set(np_dtypes), key=str)
        rows_of = {dt: [o for o, d in enumerate(np_dtypes) if d == dt]
                   for dt in blocks}

        def stack(parts, occ):
            return (tuple(jnp.stack([parts[o][0] for o in rows_of[dt]])
                          for dt in blocks),
                    jnp.stack([v for _, v in parts] + [occ]))

        def unstack(carry):
            data, valid = carry
            parts = [None] * len(np_dtypes)
            for dt, block in zip(blocks, data):
                for r, o in enumerate(rows_of[dt]):
                    parts[o] = (block[r], valid[o])
            return parts, valid[-1]

        @functools.partial(  # tpulint: disable=adhoc-jit
            jax.jit, static_argnums=(5,))
        def fold(carry, c_cards, row_base, cols, num_rows, padded_len,
                 cards, scalars, code_pairs, remaps):
            c_parts, c_occ = unstack(carry)
            dense, occ = core.dense(cols, num_rows, padded_len, cards,
                                    scalars, code_pairs, remaps)
            dense = list(dense)
            for val_o, pos_o in pos_partials:
                # First/Last: batch-local row positions become global
                d, v = dense[pos_o - nkeys]
                dense[pos_o - nkeys] = (
                    jnp.where(dense[val_o - nkeys][1], d + row_base, d), v)
            # the carry's slots under this batch's layout (the NULL code
            # of a key is its cardinality, so it moves as cards grow)
            slot = jnp.arange(c_occ.shape[0], dtype=jnp.int32)
            c_strides = _direct_strides(c_cards, nkeys)
            strides = _direct_strides(cards, nkeys)
            moved = jnp.zeros_like(slot)
            for i in range(nkeys):
                code = (slot // c_strides[i]) % (c_cards[i] + 1)
                code = jnp.where(code == c_cards[i], cards[i], code)
                moved = moved + code * strides[i]
            gid = jnp.concatenate([
                jnp.where(c_occ, moved, G),
                jnp.where(occ, jnp.arange(G, dtype=jnp.int32), G)])
            merged = []
            ord_ = 0
            for a, n in zip(aggs, pcounts):
                parts = [DVal(jnp.concatenate([c_parts[o][0], dense[o][0]]),
                              jnp.concatenate([c_parts[o][1], dense[o][1]]),
                              ptypes[o])
                         for o in range(ord_, ord_ + n)]
                merged.extend(a.merge(parts, gid, G))
                ord_ += n
            occ = seg_sum(jnp.ones(gid.shape, jnp.int32), gid,
                          num_segments=G) > 0
            if checked:
                merged.append((c_parts[-1][0] + dense[-1][0],
                               c_parts[-1][1]))
            return stack(merged, occ)

        @functools.cache
        def empty_carry():
            """No group yet: what the first batch of a query folds into
            (zeros of the partial types; built once per kernel)."""
            return (tuple(jnp.zeros((len(rows_of[dt]), G), dt)
                          for dt in blocks),
                    jnp.zeros((len(np_dtypes) + 1, G), jnp.bool_))

        def unstack_checked(carry):
            """(partials, occupancy, flagged rows or None)."""
            parts, occ = unstack(carry)
            return parts, occ, (parts.pop()[0][0] if checked else None)

        @jax.jit  # tpulint: disable=adhoc-jit
        def tail(carry, cards):
            parts, occ, overflow = unstack_checked(carry)
            return finish(*core.compact(parts, occ, cards), None, overflow)

        @jax.jit  # tpulint: disable=adhoc-jit
        def flush(carry, cards):
            parts, occ, overflow = unstack_checked(carry)
            return core.compact(parts, occ, cards) + (overflow,)

        fold.empty_carry = empty_carry
        fold.n_param_slots = core.n_param_slots
        tail.out_specs = spec_cell
        return fold, tail, flush

    def _build_direct_core(self, g_bucket: int):
        """The direct-addressing groupby pipeline SHARED by the fused
        single-batch kernel and the carried multi-batch kernels (one
        implementation — null-key handling, stride packing, and pre-stage
        fusion cannot diverge between the paths). Returns a traceable
        fn (cols, num_rows, padded_len, cards, scalars, code_pairs,
        remaps) -> (key_outs, partial_outs, num_groups) with compacted
        G-sized outputs; partial validities are ANDed with occupancy but
        NOT with the live prefix (callers needing fetch-stable tails mask
        with ``slot < num_groups`` themselves). Its two halves are
        attributes: ``core.dense`` (same operands -> per-slot update
        partials + occupancy, slots addressed by gid) and
        ``core.compact(partials, occ, cards)`` (occupied slots moved to
        the front, key codes rebuilt from the slot numbers)."""
        aggs = self.aggs
        nkeys = len(self._kernel_groupings)
        value_exprs = [a.input_exprs() for a in aggs]
        schema = self._kernel_schema
        dtypes = [f.dtype for f in schema.fields]
        in_schema = (self.children[0].output_schema()
                     if self.pre_stages else None)
        base_dtypes = ([f.dtype for f in in_schema.fields]
                       if in_schema is not None else None)
        stages = self.pre_stages
        G = g_bucket
        from ..types import INT32
        from ..columnar.segmented import prefix_sum, seg_sum
        slots = literal_slot_map(_param_exprs(
            self._kernel_groupings, aggs, "update", stages,
            value_exprs=value_exprs))

        # only DICTIONARY keys occupy appended kernel-schema slots;
        # int-carded keys' codes feed gid directly and must NOT displace
        # real columns in the eval context (r5: the old tail-replace
        # clobbered the column after the last real one — e.g. the
        # distinct flag — whenever a non-appended key was present)
        dict_ords = tuple(self._dict_keys)

        checked = self._decimal_checks > 0

        def dense(cols, num_rows, padded_len, cards, scalars,
                  code_pairs, remaps):
            if not checked:
                return dense_body(cols, num_rows, padded_len, cards,
                                  scalars, code_pairs, remaps)
            # decimal overflow: the flagged rows of this batch as one
            # more per-slot "partial" (the count in slot 0), which the
            # carry adds up and the finish packs into the one fetch
            with D.collecting() as col:
                partial_dense, occ = dense_body(
                    cols, num_rows, padded_len, cards, scalars,
                    code_pairs, remaps)
            flagged = jnp.zeros(G, jnp.int64).at[0].set(
                col.rows(jnp).astype(jnp.int64))
            return partial_dense + [(flagged, jnp.zeros(G, jnp.bool_))], occ

        def dense_body(cols, num_rows, padded_len, cards, scalars,
                       code_pairs, remaps):
            from ..columnar.segmented import onehot_gather
            # dictionary remap FUSED into the kernel (a standalone remap
            # would be one more dispatch per key)
            code_cols = [(onehot_gather(rm, cd, G), cv)
                         for (cd, cv), rm in zip(code_pairs, remaps)]
            dict_codes = [DVal(code_cols[i][0], code_cols[i][1], INT32)
                          for i in dict_ords]
            if base_dtypes is not None:
                n_base = len(base_dtypes)
                base = [None if c is None else DVal(c[0], c[1], dt)
                        for c, dt in zip(cols[:n_base], base_dtypes)]
                sctx, keep = _apply_pre_stages(stages, in_schema, base,
                                               num_rows, padded_len,
                                               scalars, slots)
                dvals = list(sctx.columns) + dict_codes
                ectx = EvalContext(schema, dvals, num_rows, padded_len,
                                   scalars, slots)
            else:
                n_base = len(dtypes) - len(dict_ords)
                dvals = [None if c is None else DVal(c[0], c[1], dt)
                         for c, dt in zip(cols[:n_base],
                                          dtypes[:n_base])]
                dvals += [None] * (n_base - len(dvals))
                dvals += dict_codes
                ectx = EvalContext(schema, dvals, num_rows, padded_len,
                                   scalars, slots)
                keep = ectx.row_mask()
            # gid from packed codes; null occupies the extra slot per key
            strides = _direct_strides(cards, nkeys)
            gid = jnp.zeros(padded_len, dtype=jnp.int32)
            for i in range(nkeys):
                cd, cv = code_cols[i]
                ceff = jnp.where(cv, cd, cards[i])
                gid = gid + ceff * strides[i]
            gid = jnp.where(keep, gid, G)        # dead rows drop out
            with D.masked(jnp, lambda: keep):
                vals = [[e.eval_device(ectx) for e in exprs]
                        for exprs in value_exprs]
            partial_dense = []
            for a, vs in zip(aggs, vals):
                partial_dense.extend(a.update(vs, gid, G, keep))
            occ = seg_sum(keep.astype(jnp.int32), gid, num_segments=G) > 0
            return partial_dense, occ

        def compact(partial_dense, occ, cards):
            strides = _direct_strides(cards, nkeys)
            num_groups = jnp.sum(occ).astype(jnp.int32)
            pos = jnp.where(occ, prefix_sum(occ, jnp.int32) - 1, G)
            slot = jnp.arange(G, dtype=jnp.int32)
            key_outs = []
            for i in range(nkeys):
                code_i = (slot // strides[i]) % (cards[i] + 1)
                valid_i = jnp.logical_and(code_i < cards[i], occ)
                kd = jnp.zeros(G, jnp.int32).at[pos].set(code_i,
                                                         mode="drop")
                kv = jnp.zeros(G, jnp.bool_).at[pos].set(valid_i,
                                                         mode="drop")
                key_outs.append((kd, kv))
            partial_outs = []
            for d, v in partial_dense:
                cd = jnp.zeros(G, d.dtype).at[pos].set(d, mode="drop")
                cv = jnp.zeros(G, jnp.bool_).at[pos].set(
                    jnp.logical_and(v, occ), mode="drop")
                partial_outs.append((cd, cv))
            return key_outs, partial_outs, num_groups

        def core(cols, num_rows, padded_len, cards, scalars,
                 code_pairs, remaps):
            partial_dense, occ = dense(cols, num_rows, padded_len, cards,
                                       scalars, code_pairs, remaps)
            overflow = partial_dense.pop()[0][0] if checked else None
            return compact(partial_dense, occ, cards) + (overflow,)

        core.dense = dense
        core.compact = compact
        core.n_param_slots = len(slots)
        return core

    def _rect_key_mode(self, batch) -> bool:
        """True when every string group key is a direct reference to a
        byte-rectangle ASCII column of this batch — keys then group on
        device via packed-word operands (exprs/string_rect design)."""
        if not self._dict_keys or batch is None:
            return False
        from ..columnar.strrect import ByteRectColumn
        from ..exprs.base import Alias, ColumnRef
        for i in self._dict_keys:
            g = self.groupings[i]
            if isinstance(g, Alias):
                g = g.children[0]
            if not isinstance(g, ColumnRef):
                return False
            try:
                col = batch.column_by_name(g.name)
            except (KeyError, ValueError):
                return False
            if not (isinstance(col, ByteRectColumn) and col.ascii_only):
                return False
        return True

    def _ensure_rect_cols(self, batch: ColumnarBatch, ordinals) -> ColumnarBatch:
        """Rect-mode invariant: the given STRING columns must be byte
        rectangles. A spill round trip or host-staged concat can re-ingest
        them as dictionary codes (whose code spaces differ per batch —
        grouping on them across batches would be wrong); re-encode those
        back to rectangles (grouping on bytes is exact for ANY UTF-8)."""
        from ..columnar.strrect import ByteRectColumn, encode_string_rect
        import jax
        cols = list(batch.columns)
        changed = False
        for i in ordinals:
            c = cols[i]
            if isinstance(c, ByteRectColumn):
                if not c.ascii_only:
                    self._rect_ascii = False
                continue
            arr = c.to_arrow(batch.num_rows)
            enc = encode_string_rect(arr, len(arr), batch.padded_len,
                                     1 << 30)     # correctness: no cap
            if enc is None:       # cannot happen below the 1<<30 cap,
                raise ValueError(  # but never unpack None silently
                    "string too wide for the rectangle re-encode")
            rect, lens, v, asc = enc
            if not asc:
                # grouping stays byte-exact for any UTF-8; only the
                # downstream case-transform eligibility flag must flip
                self._rect_ascii = False
            cols[i] = ByteRectColumn(jax.device_put(rect),
                                     jax.device_put(v),
                                     jax.device_put(lens),
                                     ascii_only=asc)
            changed = True
        if not changed:
            return batch
        return ColumnarBatch(cols, batch.num_rows_raw, batch.schema,
                             meta=batch.meta)

    def _rect_key_ordinals_for(self, batch: ColumnarBatch):
        """Ordinals of the key-leaf columns in an UPDATE input batch."""
        from ..exprs.base import Alias, ColumnRef
        out = []
        for i in self._dict_keys:
            g = self.groupings[i]
            if isinstance(g, Alias):
                g = g.children[0]
            out.append(batch.schema.index_of(g.name))
        return out

    def _direct_keys_ok(self) -> bool:
        """Every grouping is either a dictionary string key or a
        proven-cardinality int key — the direct core's requirement."""
        if not self.groupings or self._rect_mode:
            return False
        covered = set(self._dict_keys) | set(self._int_cards)
        return len(covered) == len(self.groupings)

    def _mixed_pairs(self, batch: ColumnarBatch):
        """(pairs, remaps, cards) for ALL groupings in grouping order:
        string keys dictionary-encode (global codes), int-carded keys
        pass their device values straight through as codes with an
        identity remap."""
        from ..exprs.base import Alias, ColumnRef
        s_pairs, s_remaps = self._augment_pairs(batch)
        by_dict = {i: j for j, i in enumerate(self._dict_keys)}
        pairs, remaps, cards = [], [], []
        for i in range(len(self.groupings)):
            if i in by_dict:
                j = by_dict[i]
                pairs.append(s_pairs[j])
                remaps.append(s_remaps[j])
                cards.append(max(len(self._dicts[j]), 1))
                continue
            card = self._int_cards[i]
            g = self.groupings[i]
            if isinstance(g, Alias):
                g = g.children[0]
            if not isinstance(g, ColumnRef):
                return None
            try:
                col = batch.column_by_name(g.name)
            except (KeyError, ValueError):
                return None
            if not isinstance(col, DeviceColumn):
                return None
            pairs.append((col.data, col.validity))
            remaps.append(np.arange(card, dtype=np.int32))
            cards.append(card)
        return pairs, remaps, np.asarray(cards, np.int32)

    def _direct_operands(self, batch: ColumnarBatch):
        """(cards_dev, pairs, padded_remaps, Gb) when direct addressing
        applies to this batch, else None — the shared operand builder of
        the fused single-batch and the carried multi-batch call sites."""
        if not self._direct_keys_ok():
            return None
        # current dictionary sizes are a lower bound on post-encode sizes:
        # once the product exceeds the bound it can only grow, so bail out
        # BEFORE paying the host-side dictionary encode a second time
        lower = 1
        for d in self._dicts:
            lower *= max(len(d), 1) + 1
        for c in self._int_cards.values():
            lower *= c + 1
        if lower > self.OPTIMISTIC_GROUPS:
            return None
        mixed = self._mixed_pairs(batch)
        if mixed is None:
            return None
        pairs, remaps, cards = mixed
        prod = int(np.prod(cards.astype(np.int64) + 1))
        if prod > self.OPTIMISTIC_GROUPS:
            return None
        from ..columnar.segmented import bucket_segments
        Gb = bucket_segments(prod)
        if jax.default_backend() == "cpu" \
                and Gb * batch.padded_len > (1 << 28):
            # XLA:CPU MATERIALIZES the dense one-hot (G x P) the TPU
            # backend fuses into its reduction — a 4096-segment bucket
            # over a 1M-row batch would allocate >100 GB on the CPU
            # fallback path (r5 rehearsal OOM). The split sort path
            # handles these shapes there.
            return None
        # the remap tables and cards are uploaded when a value changed
        # since the previous batch, not per batch
        up = self._direct_uploaded
        if up is None or up[0] != Gb or not np.array_equal(up[1], cards) \
                or not all(np.array_equal(a, b)
                           for a, b in zip(up[2], remaps)):
            up = self._direct_uploaded = (
                Gb, cards, remaps, jnp.asarray(cards), tuple(
                    jnp.asarray(np.pad(r, (0, max(Gb - len(r), 0)))[:Gb])
                    for r in remaps))
        return up[3], tuple(pairs), up[4], Gb

    def _fast_single_batch(self, ctx, batch: ColumnarBatch,
                           update_k) -> Optional[ColumnarBatch]:
        """Single-input-batch aggregation: ONE kernel dispatch (fused
        pre-stages + dictionary remap + update + finalize + result
        packing) and ONE fetch produce the final HOST batch — every extra
        dispatch or fetch adds its full latency. Returns None when the
        group count exceeds the optimistic bound (caller takes the
        classic path)."""
        base_cols = _kernel_cols(batch)
        nkeys = len(self.groupings)
        packed = None
        if nkeys > 0:
            ops = self._direct_operands(batch)
            if ops is not None:
                cards, pairs, padded_remaps, Gb = ops
                fast = self._get_fast_direct_kernel(Gb)
                _check_scalar_slots(fast, self._upd_scalars)
                packed = fast(base_cols, jnp.int32(batch.num_rows_raw),
                              batch.padded_len, cards,
                              self._upd_scalars, pairs, padded_remaps)
                specs = fast.out_specs[batch.padded_len]
        if packed is None:
            if nkeys > 0:
                # SORT-based keyed aggregation must not compile the fused
                # update+finalize kernel: a lax.sort's compile time
                # multiplies with everything else in its module, and this
                # exact kernel is the one whose compile has stalled whole
                # bench runs. The classic path runs the SPLIT kernels
                # instead — a couple more dispatches on a single batch,
                # each module small enough to compile quickly.
                return None
            codes = self._augment(batch)
            cols = base_cols + [(c.data, c.validity) for c in codes]
            if self._fast_k is None:
                self._fast_k = self._get_fast_kernel(update_k,
                                                     self._kernel_key)
            _check_scalar_slots(self._fast_k, self._upd_scalars)
            packed = self._fast_k(
                cols, jnp.int32(batch.num_rows_raw), batch.padded_len,
                self._upd_scalars)
            specs = self._fast_k.out_specs[batch.padded_len]
        return self._fetch_result(packed, specs)

    def _fetch_result(self, packed, specs) -> Optional[ColumnarBatch]:
        """The ONE round trip of a query whose kernel finalized and packed
        on the device: fetch, unpack, decode dictionary keys — the final
        HOST batch, or None when the group count passed the optimistic
        bound (the packed columns are cut to it)."""
        from ..columnar.column import arrow_from_numpy
        from ..columnar.packing import unpack_streams
        from ..types import STRING
        u32, f64 = traced_device_get(packed, "d2h.agg")
        got = unpack_streams(u32, f64, specs)
        n = int(got[0])
        if n > self.OPTIMISTIC_GROUPS:
            _FAST_GROUPS[self._kernel_key] = n
            return None
        first = 1
        if self._decimal_checks:
            # the rows a checked decimal operation flagged, packed after
            # the group count: the one fetch carries them
            first = 2
            self._settle_overflow(int(got[1]))
        out_cols = []
        dict_pos = {i: j for j, i in enumerate(self._dict_keys)}
        for o, f in enumerate(self._schema.fields):
            d = np.asarray(got[first + 2 * o])[:n]
            v = np.asarray(got[first + 1 + 2 * o])[:n]
            if o in dict_pos:
                inv = self._inverse_dict(dict_pos[o])
                vals = [inv[int(x)] if ok else None
                        for x, ok in zip(d, v)]
                out_cols.append(HostColumn.from_pylist(vals, STRING))
            else:
                out_cols.append(HostColumn(arrow_from_numpy(d, v, f.dtype),
                                           f.dtype))
        return ColumnarBatch(out_cols, n, self._schema)

    def _position_partials(self) -> List[Tuple[int, int]]:
        """(value ordinal, position ordinal) in the partial schema per
        First/Last aggregate: their within-batch row positions must become
        GLOBAL before partials of different batches merge, or ties between
        different batches' firsts break cross-batch arrival order (caught
        by test_agg_multibatch_first_last_order_dependent)."""
        from ..exprs.aggregates import First, Last
        out = []
        ord_ = len(self.groupings)
        for ai, a in enumerate(self.aggs):
            if isinstance(a, (First, Last)):
                out.append((ord_, ord_ + 1))
            ord_ += self._partial_counts[ai]
        return out

    def _device_count(self, num_rows):
        """A batch's row count as the int32 device scalar the kernels
        take; a host int is uploaded once per distinct value and query
        (every batch of a scan but the last has the same)."""
        if not isinstance(num_rows, int):
            return jnp.int32(num_rows)
        dev = self._counts_dev.get(num_rows)
        if dev is None:
            dev = self._counts_dev[num_rows] = jnp.int32(num_rows)
        return dev

    def do_execute(self, ctx: ExecContext) -> Iterator[ColumnarBatch]:
        from ..config import AGG_OPTIMISTIC_GROUPS
        self.OPTIMISTIC_GROUPS = int(ctx.conf.get(AGG_OPTIMISTIC_GROUPS))
        self._dicts = [dict() for _ in self._dict_keys]
        #: per dictionary key: (source dictionary, its remap) last seen
        self._gmaps = {}
        #: the direct path's operands as last uploaded (_direct_operands)
        self._direct_uploaded = None
        self._counts_dev = {}
        self._fast_k = None
        in_schema = (self.children[0].output_schema()
                     if self.pre_stages else None)
        self._kernel_key = _agg_kernel_key(
            self._kernel_groupings, self.aggs, self._kernel_schema,
            "update", in_schema, self.pre_stages or None,
            len(self._dict_keys))
        # the fused (single-module) update kernel is only ever invoked for
        # GLOBAL aggregations (_fast_single_batch's nkeys==0 branch);
        # keyed aggregations always run the split kernels — the fused
        # sort-based form compiles pathologically on this backend
        update_k = None
        if not self.groupings:
            update_k = _get_kernel(self._kernel_groupings, self.aggs,
                                   self._kernel_schema, "update",
                                   in_schema=in_schema,
                                   stages=self.pre_stages or None,
                                   n_codes=len(self._dict_keys))
        # the multi-batch first pass calls the kernel directly (not traced
        # inside another jit) — the split three-dispatch form compiles in
        # ~1 min where the fused sort pipeline took >20 on this backend
        update_k_split = _get_kernel(self._kernel_groupings, self.aggs,
                                     self._kernel_schema, "update",
                                     in_schema=in_schema,
                                     stages=self.pre_stages or None,
                                     n_codes=len(self._dict_keys),
                                     split=True)
        self._upd_scalars = literal_scalars(collect_param_literals(
            _param_exprs(self._kernel_groupings, self.aggs, "update",
                         self.pre_stages or None)))
        rows_m = ctx.metric(self._exec_id, "numOutputRows", ESSENTIAL)
        #: compiled-module launches of the UPDATE phase, per query: the
        #: fused-partial-agg acceptance metric — a q9-shaped
        #: scan→filter→partial-agg region must cost exactly ONE dispatch
        #: per input batch (fused/direct kernels), vs 4 on the split
        #: sort pipeline and one per operator when fusion is off
        disp_m = ctx.metric(self._exec_id, "updateDispatches")

        it = self.children[0].execute(ctx)
        first = next(it, None)
        second = next(it, None) if first is not None else None
        # byte-rectangle key mode (VERDICT r3 #4): when every string
        # group key is a rectangle-backed ASCII column, the keys group
        # ON DEVICE through packed-word sort operands — no exec-local
        # dictionary, no host encode, no per-distinct-value work
        self._rect_mode = self._rect_key_mode(first)
        self._rect_ascii = True
        self._partial_schema = self._partial_schema_dict
        if self._rect_mode:
            self._kernel_key = ("rect",) + _agg_kernel_key(
                self.groupings, self.aggs, self._eval_schema, "update",
                in_schema, self.pre_stages or None, 0)
            update_k_split = _get_kernel(self.groupings, self.aggs,
                                         self._eval_schema, "update",
                                         in_schema=in_schema,
                                         stages=self.pre_stages or None,
                                         split=True)
            self._upd_scalars = literal_scalars(collect_param_literals(
                _param_exprs(self.groupings, self.aggs, "update",
                             self.pre_stages or None)))
            self._partial_schema = self._partial_schema_rect
        if first is not None and second is None \
                and not self.many_groups_hint \
                and not self._rect_mode \
                and (not self.groupings or self._direct_keys_ok()) \
                and _FAST_GROUPS.get(self._kernel_key, 0) \
                <= self.OPTIMISTIC_GROUPS:
            first = first.ensure_device()

            def run_fast():
                with ctx.semaphore.held():
                    return self._fast_single_batch(ctx, first, update_k)
            out = with_retry_no_split(run_fast, ctx=ctx, op=self._exec_id)
            if out is not None:
                disp_m.add(1)    # fused update+finalize: one module
                _count_carry(0, 0)
                _FAST_GROUPS[self._kernel_key] = out.num_rows
                rows_m.add(out.num_rows)
                yield out
                return

        import itertools
        pending = [b for b in (first, second) if b is not None]
        # phase 1, direct-addressed keys: every batch FOLDS into one
        # running partial on the device (_get_carry_kernels) — a dispatch
        # a batch, then one tail dispatch and one fetch a query.
        # phase 1, everything else: dispatch EVERY batch's update kernel
        # without syncing — the kernels overlap in the device queue (a
        # per-batch int(num_groups) costs one device round trip EACH, pure
        # latency that grows with the batch count).
        # Outputs are sliced immediately to a SPECULATIVE group bucket
        # (stat from previous runs of this kernel) so at most one
        # input-bucket-sized output is live at a time; the stacked count
        # fetch in phase 2 validates every guess and re-runs the (rare,
        # idempotent) overflowed batch at its true bucket.
        spec = bucket_for(max(_FAST_GROUPS.get(self._kernel_key, 0),
                              1 if not self.groupings else 1024))
        #: bound on input batches pinned by pending dispatch closures: the
        #: count fetch resolves per WINDOW, so a long scan never holds
        #: every input batch in HBM at once (one fetch per 8 batches
        #: instead of per batch — latency amortized 8x, memory bounded)
        WINDOW = 8
        partials: List[SpillableBatch] = []
        row_base = 0     # global row offset of the next batch
        # (sliced outs, num_groups dev scalar, dispatch, base, n_disp)
        window = []
        pos_partials = self._position_partials()
        #: (running partial, its cards, its tail and flush kernels) while
        #: the direct path holds; G-sized: never sliced, fetched or spilled
        carry = None
        carried = flushes = 0
        #: each input batch's row count, as it came (a host int, or still
        #: on the device below a filter): the ``agg.highcard`` counter's
        rows_in: list = []

        def flush_window():
            if not window:
                return
            if not self.groupings:
                counts = [1] * len(window)
            elif len(window) == 1:
                counts = [int(traced_device_get(window[0][1],
                                                "d2h.groups"))]
            else:
                def resolve_counts():
                    return [int(x) for x in traced_device_get(
                        jnp.stack([w[1] for w in window]), "d2h.groups")]
                counts = with_retry_no_split(resolve_counts, ctx=ctx,
                                             op=self._exec_id)
            for (outs, _, dispatch, base, n_disp), n in zip(window,
                                                            counts):
                if n > spec:
                    # speculation overflow: re-run this batch's kernel
                    # (pure function of retained inputs) and slice at the
                    # true count — a second real launch, so the dispatch
                    # metric counts it again
                    disp_m.add(n_disp)

                    def redo(d=dispatch):
                        with ctx.semaphore.held():
                            return d()[0]
                    outs = with_retry_no_split(redo, ctx=ctx,
                                               op=self._exec_id)
                pb = self._slice_to_count(outs, n, self._partial_schema)
                for val_o, pos_o in pos_partials:
                    vcol, pcol = pb.columns[val_o], pb.columns[pos_o]
                    pd_ = jnp.where(vcol.validity,
                                    pcol.data + jnp.int64(base),
                                    pcol.data)
                    pb.columns[pos_o] = DeviceColumn(pd_, pcol.validity,
                                                     pcol.dtype)
                partials.append(SpillableBatch(pb, ctx.memory))
            window.clear()

        def _spec_slice(d_, v):
            from ..exprs.base import StrVal
            if isinstance(d_, StrVal):
                if spec < d_.bytes_.shape[0]:
                    return (StrVal(d_.bytes_[:spec],
                                   d_.lengths[:spec]), v[:spec])
                return (d_, v)
            if spec < d_.shape[0]:
                return (d_[:spec], v[:spec])
            return (d_, v)

        def enqueue(dispatch, base, n_disp):
            """One update's modules into the window: ``dispatch`` returns
            (key + partial outputs, group count on the device)."""
            disp_m.add(n_disp)

            def first_pass():
                with ctx.semaphore.held():
                    outs, ng = dispatch()
                    return [_spec_slice(d_, v) for d_, v in outs], ng
            # idempotent over the retained input -> retry-safe
            outs, ng = with_retry_no_split(first_pass, ctx=ctx,
                                           op=self._exec_id)
            window.append((outs, ng, dispatch, base, n_disp))
            if len(window) >= WINDOW:
                flush_window()

        def flush_carry():
            """The direct path stopped applying in mid-query (the slot
            product passed the bound, a key column left the device): the
            carry joins the windowed path as ONE ordinary partial. Its
            First/Last positions are global already, hence base 0."""
            nonlocal carry, flushes
            state, c_cards, _, flush_k = carry
            carry = None
            flushes += 1

            def dispatch():
                key_outs, partial_outs, ng, overflow = flush_k(state,
                                                               c_cards)
                if overflow is not None:
                    # leaves with the sort path: the sink fetches it
                    D.defer(overflow, self._input_checks)
                return list(key_outs) + list(partial_outs), ng
            enqueue(dispatch, 0, 1)

        try:
            for batch in itertools.chain(pending, it):
                batch = batch.ensure_device()
                rows_in.append(batch.num_rows_raw)
                if self._rect_mode:
                    batch = self._ensure_rect_cols(
                        batch, self._rect_key_ordinals_for(batch))
                ops = self._direct_operands(batch)
                if ops is not None:
                    cards, pairs, remaps, Gb = ops
                    fold, tail, flush_k = self._get_carry_kernels(Gb)
                    _check_scalar_slots(fold, self._upd_scalars)
                    state, c_cards = (carry[:2] if carry is not None
                                      else (fold.empty_carry(), cards))
                    base = jnp.int64(row_base) if pos_partials else None

                    def step():
                        with ctx.semaphore.held():
                            return fold(
                                state, c_cards, base, _kernel_cols(batch),
                                self._device_count(batch.num_rows_raw),
                                batch.padded_len, cards, self._upd_scalars,
                                pairs, remaps)
                    # replaced only after the call returned: a retry sees
                    # the same (carry, batch)
                    carry = (with_retry_no_split(step, ctx=ctx,
                                                 op=self._exec_id),
                             cards, tail, flush_k)
                    disp_m.add(1)
                    carried += 1
                else:
                    if carry is not None:
                        flush_carry()
                    codes = [] if self._rect_mode else self._augment(batch)
                    if self._input_checks and not self._rect_mode:
                        self._probe_overflow(batch, codes)

                    def dispatch(b=batch, extra=codes):
                        return self._run_kernel_raw(
                            update_k_split, b, extra_cols=extra,
                            scalars=self._upd_scalars)
                    enqueue(dispatch, row_base,
                            getattr(update_k_split, "n_dispatches", 1))
                row_base += batch.padded_len
            if carry is not None and (partials or window):
                flush_carry()
            flush_window()
        except BaseException:
            # fatal error (or cooperative QueryTimeout) mid-update:
            # accumulated partials would outlive the query and pin
            # pool budget — the zero-leak audit's contract
            for sb in partials:
                sb.close()
            raise
        _count_carry(carried, flushes)

        if carry is not None:
            # every batch went into the one carry: one tail dispatch
            # (compaction + finalize + pack) and the query's ONE fetch
            state, c_cards, tail, _ = carry

            def run_tail():
                with ctx.semaphore.held():
                    return self._fetch_result(tail(state, c_cards),
                                              tail.out_specs[None])
            out = with_retry_no_split(run_tail, ctx=ctx, op=self._exec_id)
            disp_m.add(1)
            _FAST_GROUPS[self._kernel_key] = out.num_rows
            rows_m.add(out.num_rows)
            yield out
            return

        # partials that do not fit ONE bucket together (their group
        # counts, which the windows' fetches brought, say so): no merge
        # kernel is built over all of them; they finish in partitions
        if (self.groupings and len(partials) > 1
                and sum(sb.num_rows for sb in partials)
                > self._merge_cap(ctx, partials)):
            yield from self._repartitioned_merge(ctx, partials, rows_m,
                                                 rows_in)
            return

        if len(partials) == 1:
            # one update output already has unique groups — merge is the
            # identity, skip its kernel (and host sync) entirely
            merged = partials[0].get()
            partials[0].close()
        else:
            merged = self._merge(ctx, partials)
        final = self._finalize(ctx, merged)
        nr = final.num_rows_raw
        if isinstance(nr, int):
            _FAST_GROUPS[self._kernel_key] = nr   # refresh stat
            rows_m.add(nr)
        else:
            # lazy count: refresh the stat when the sink fetch resolves it
            # (never an extra sync — _resolve_count runs the callback)
            kk, fg = self._kernel_key, _FAST_GROUPS

            def _on_groups(n, _kk=kk, _fg=fg, _m=rows_m):
                _fg[_kk] = n
                _m.add(n)
            import weakref
            final.meta = dict(final.meta)
            final.meta["count_cb"] = (_on_groups, weakref.ref(final))
        yield final

    # -- the partitioned finish (ref GpuAggregateExec.scala:718-780: when
    # the merge target cannot fit, hash re-partition the partial batches by
    # key and merge each partition independently — group keys are disjoint
    # across partitions, so per-partition merge+finalize is exact). The
    # reference also skips its merge passes where the first pass barely
    # reduces (skipAggPassReductionRatio); here nothing is merged before
    # the partials are divided, so a partition's merge is the only one ----
    #: distinct seed from shuffle partitioning (42) so a key-partitioned
    #: shuffle stage does not collapse all rows into one sub-partition
    REPARTITION_SEED = 1879048201

    def _merge_kernel(self):
        merge_keys = [BoundReference(i, f.dtype) for i, f in
                      enumerate(self._partial_schema.fields[:len(self.groupings)])]
        return _get_kernel(merge_keys, self.aggs, self._partial_schema,
                           "merge", self._partial_counts, split=True)

    def _merge_cap(self, ctx: ExecContext, partials) -> int:
        """The most rows ONE merge kernel is built for: ``batchSizeRows``,
        or as many rows of these partials as ``batchSizeBytes`` holds
        where that is fewer (wide partials: a string rectangle, many
        aggregates), and never under the largest single partial (whose
        update kernel ran at that shape already)."""
        row_bytes = max(1, sum(sb.device_bytes() for sb in partials)
                        // max(1, sum(sb.padded_len for sb in partials)))
        return max(min(ctx.conf.batch_size_rows,
                       ctx.conf.batch_size_bytes // row_bytes),
                   max(sb.num_rows for sb in partials))

    def _partial(self, sb: SpillableBatch) -> ColumnarBatch:
        """A partial as the kernels take it (the caller holds the
        semaphore): back on the device, string keys as rectangles where
        the aggregate groups on them."""
        b = sb.get()
        return self._ensure_rect_cols(b, range(len(self.groupings))) \
            if self._rect_mode else b

    def _repartitioned_merge(self, ctx: ExecContext, partials, rows_m,
                             rows_in, depth: int = 0, seen=None
                             ) -> Iterator[ColumnarBatch]:
        """The partitioned finish: every partial's rows are put in the
        order of their key's hash bucket (``agg_partition``: ONE
        single-key sort a partial carrying its columns), the buckets'
        row counts of all partials come in one fetch, and neighbouring
        buckets are packed into partitions of at most the cap from those
        counts (what the operator observed, not a guess at how a hash
        spreads). Each partition is then collected (``agg_collect``: a run
        of every partial copied, nothing gathered), merged by the ordinary
        merge kernel at a shape of the bucket ladder, finalized and handed
        on: group keys are disjoint across partitions, so that is exact. A
        bucket that alone passes the cap is divided again under another
        seed, unless the division that made it moved nothing (every row in
        one bucket: keys no hash tells apart)."""
        merge_k = self._merge_kernel()
        nkeys = len(self.groupings)
        cap = self._merge_cap(ctx, partials)
        total = sum(sb.num_rows for sb in partials)
        largest_partial = max(sb.num_rows for sb in partials)
        seen = seen if seen is not None else {
            "partials": len(partials), "partitions": 0, "largest": 0,
            "groups": []}
        cols = list(self._highcard_cols())
        placed: List[SpillableBatch] = []

        def collect(took, at, n):
            """The runs (from ``at``, ``n`` rows: one entry a placed
            partial) of the placed partials ``took``."""
            with ctx.semaphore.held():
                return self._collect(placed, took, at, n)
        try:
            with self.child_span("agg.partition", cols=cols,
                                 partials=len(partials)):
                counts = []
                for sb in partials:
                    def place(sb=sb):
                        with ctx.semaphore.held():
                            b = self._partial(sb)
                            lanes, spans = _lane_pairs(
                                list(enumerate(b.columns)))
                            outs, cnt = _partition_kernel(
                                tuple(range(spans[nkeys - 1][2])),
                                self.REPARTITION_SEED + depth,
                                _HASH_BUCKETS)(
                                    lanes, jnp.int32(b.num_rows),
                                    b.padded_len)
                            moved = [None] * len(b.columns)
                            _lane_rebuild(b, spans, outs, moved)
                            return ColumnarBatch(moved, b.num_rows,
                                                 b.schema), cnt
                    pb, cnt = with_retry_no_split(place, ctx=ctx,
                                                  op=self._exec_id)
                    sb.close()
                    placed.append(SpillableBatch(pb, ctx.memory))
                    counts.append(cnt)
                counts = np.asarray(traced_device_get(
                    jnp.stack(counts), "d2h.agg_parts"), np.int64)
            starts = np.cumsum(counts, axis=1) - counts
            parts = _pack_buckets(counts.sum(axis=0), cap)
            ctx.metric(self._exec_id, "aggRepartitions").set(
                seen["partitions"] + len(parts))
            # every placed partial is a source of every partition, one
            # that holds no row of it too: the collect kernel unrolls over
            # its sources, and their number is then the same whatever the
            # counts (one module a division, not one a partition)
            took = list(range(len(placed)))
            for lo, hi in parts:
                at, n = starts[:, lo], counts[:, lo:hi].sum(axis=1)
                rows_p = int(n.sum())
                if cap < rows_p < total:
                    # ONE bucket over the cap: its runs leave as batches
                    # of at most the cap each and are divided again
                    groups, acc = [[]], 0
                    for i in took:
                        if groups[-1] and acc + n[i] > cap:
                            groups.append([])
                            acc = 0
                        groups[-1].append(i)
                        acc += int(n[i])
                    pieces = [SpillableBatch(with_retry_no_split(
                        lambda g=g: collect(g, at, n), ctx=ctx,
                        op=self._exec_id), ctx.memory) for g in groups]
                    yield from self._repartitioned_merge(
                        ctx, pieces, rows_m, rows_in, depth + 1, seen)
                    continue
                seen["partitions"] += 1
                seen["largest"] = max(seen["largest"], rows_p)
                with self.child_span("agg.merge_part", cols=cols,
                                     rows=rows_p):
                    def merge_part(at=at, n=n):
                        with ctx.semaphore.held():
                            return self._run_kernel(
                                merge_k, collect(took, at, n),
                                self._partial_schema, lazy=True)
                    final = self._finalize(ctx, with_retry_no_split(
                        merge_part, ctx=ctx, op=self._exec_id))
                seen["groups"].append(final.num_rows_raw)
                rows_m.add(final.num_rows_raw)
                yield final
        finally:
            # consumed, failed or abandoned: the placed partials pin pool
            # budget no longer (close() is idempotent)
            for sb in partials + placed:
                sb.close()
        if not depth:
            # the next run's partials keep the bucket of this run's largest
            _FAST_GROUPS[self._kernel_key] = largest_partial
            self._count_highcard(seen, rows_in)

    def _count_highcard(self, seen: dict, rows_in: list) -> None:
        """Tracer counter ``agg.highcard``, once per execution that
        finished in partitions. Counts still on the device (the
        partitions' groups, input rows below a filter) come in ONE packed
        transfer, and only while a tracer records."""
        tr = trace_core.TRACER
        if tr is None or not tr.recording:
            return
        from ..columnar.packing import sum_counts
        groups, rows = sum_counts((seen["groups"], rows_in),
                                  "d2h.agg_highcard")
        tr.counter("agg.highcard", {
            "partials": seen["partials"], "rows_in": rows,
            "partitions": seen["partitions"], "groups": groups,
            "largest_partition_rows": seen["largest"],
            "op": int(self._exec_id.rsplit("@", 1)[-1])}, cat="exec")

    def _highcard_cols(self):
        """The input columns the aggregate reads (its keys' and its
        aggregates' references), by name: the ``cols`` of its spans."""
        from ..plan.rewrites import _agg_refs, _expr_refs
        refs: set = set()
        for g in self.groupings:
            _expr_refs(g, refs)
        for a in self.aggs:
            _agg_refs(a, refs)
        names = self.children[0].output_schema().names()
        return [n for n in names if n in refs] or names

    def _collect(self, placed, took, starts, counts) -> ColumnarBatch:
        """One partition's rows out of the placed partials ``took`` (each
        holds them in one run from ``starts``), as one batch in the bucket
        of their sum. The caller holds the semaphore."""
        group = [(self._partial(placed[i]), int(starts[i]), int(counts[i]))
                 for i in took]
        if self._rect_mode:
            group = _one_width(group, len(self.groupings))
        rows = sum(n for _, _, n in group)
        # a run is copied as a slice of a static length: the power of two
        # that holds the longest
        piece = 1 << (max(n for _, _, n in group) - 1).bit_length()
        lanes = [_lane_pairs(list(enumerate(b.columns)))
                 for b, _, _ in group]
        got = _collect_kernel()(
            [pairs for pairs, _ in lanes],
            np.asarray([a for _, a, _ in group], np.int32),
            np.asarray([n for _, _, n in group], np.int32),
            piece, bucket_for(rows))
        first = group[0][0]
        cols = [None] * len(first.columns)
        _lane_rebuild(first, lanes[0][1], got, cols)
        return ColumnarBatch(cols, rows, first.schema)

    # ------------------------------------------------------------------
    def _merge(self, ctx: ExecContext,
               partials: List[SpillableBatch]) -> ColumnarBatch:
        """Merge partial batches that fit one bucket together (the caller
        saw to that: what passes the cap finishes in partitions,
        ``_repartitioned_merge``): one concat, ONE lazy merge kernel. The
        inputs materialize via ``sb.get()`` INSIDE the retried closure, so
        a RetryOOM spill actually frees HBM and the retry re-materializes
        from the host."""
        merge_k = self._merge_kernel()
        if not partials:
            # empty input: still one row for global agg, zero rows for grouped
            empty = ColumnarBatch.from_arrow(
                _empty_arrow(self._partial_schema))
            with ctx.semaphore.held():
                return self._run_kernel(merge_k, empty, self._partial_schema)

        def do_merge() -> ColumnarBatch:
            with ctx.semaphore.held():
                big = concat_batches([s.get() for s in partials])
                if self._rect_mode:
                    big = self._ensure_rect_cols(
                        big, range(len(self.groupings)))
                # lazy: the merge input is already group-sized, so the
                # output stays at its (small) bucket and the group count
                # rides to the sink fetch instead of syncing here
                return self._run_kernel(merge_k, big, self._partial_schema,
                                        lazy=True)

        try:
            return with_retry_no_split(do_merge, ctx=ctx, op=self._exec_id)
        finally:
            for sb in partials:
                sb.close()

    # ------------------------------------------------------------------
    def _finalize(self, ctx: ExecContext, merged: ColumnarBatch) -> ColumnarBatch:
        nkeys = len(self.groupings)
        out_cols: List[DeviceColumn] = self._decode_keys(
            list(merged.columns[:nkeys]), merged.num_rows_raw)
        ord_ = nkeys
        checks = self._decimal_checks - self._input_checks
        with D.collecting(checks > 0) as col:
            for ai, a in enumerate(self.aggs):
                n = self._partial_counts[ai]
                parts = [DVal(merged.columns[o].data,
                              merged.columns[o].validity,
                              merged.columns[o].dtype)
                         for o in range(ord_, ord_ + n)]
                ord_ += n
                final = a.finalize(parts)
                out_cols.append(DeviceColumn(
                    final.data, final.validity,
                    self._schema.fields[nkeys + ai].dtype))
        if checks:
            # finalized eagerly, batch left on the device: the sink
            # fetches the count of totals that left the lane
            D.defer(col.rows(jnp), checks)
        return ColumnarBatch(out_cols, merged.num_rows_raw, self._schema)

    def describe(self):
        g = ", ".join(e.name_hint for e in self.groupings)
        a = ", ".join(x.name_hint for x in self.aggs)
        fused = ""
        if self.pre_stages:
            parts = [("filter" if s[0] == "filter" else "project")
                     for s in self.pre_stages]
            fused = f" fused=[{'+'.join(parts)}]"
        return f"HashAggregate[keys=[{g}], aggs=[{a}]]{fused}"


def _empty_arrow(schema: Schema):
    import pyarrow as pa
    from ..types import to_arrow
    return pa.table({f.name: pa.array([], type=to_arrow(f.dtype))
                     for f in schema.fields})


class CpuAggregateExec(TpuExec):
    """Host fallback via pandas groupby (the CPU oracle for differential
    tests, playing the role CPU Spark plays for the reference)."""
    is_tpu = False

    def __init__(self, groupings, aggs, child: TpuExec):
        super().__init__([child])
        self.groupings = list(groupings)
        self.aggs = list(aggs)
        cs = child.output_schema()
        fields = [StructField(e.name_hint, e.data_type(cs), True)
                  for e in self.groupings]
        fields += [StructField(a.name_hint, a.data_type(cs), True)
                   for a in self.aggs]
        self._schema = Schema(fields)

    def output_schema(self) -> Schema:
        return self._schema

    def do_execute(self, ctx: ExecContext) -> Iterator[ColumnarBatch]:
        import pandas as pd
        import pyarrow as pa
        from ..exprs.aggregates import (Average, CollectList, CollectSet,
                                        Count, CountStar, First, Last, Max,
                                        MaxBy, Min, MinBy, Percentile,
                                        StddevPop, StddevSamp, Sum,
                                        VariancePop, VarianceSamp)
        from ..types import DecimalType
        child_schema = self.children[0].output_schema()
        tables = [b.to_arrow() for b in self.children[0].execute(ctx)]
        at = (pa.concat_tables(tables) if tables
              else _empty_arrow(self.children[0].output_schema()))
        df = at.to_pandas()

        # evaluate key + input expressions into temp columns; the source
        # batch comes straight from ARROW (from_pandas would turn every
        # NaN into a SQL NULL — Spark distinguishes them: NaN is a value)
        work = pd.DataFrame(index=df.index)
        src = ColumnarBatch.from_arrow_host(at) if len(df) else None
        key_names = []
        for i, g in enumerate(self.groupings):
            col = f"_k{i}"
            work[col] = _host_series(g, df, src)
            key_names.append(col)
        in_names = []
        for i, a in enumerate(self.aggs):
            col = f"_a{i}"
            if isinstance(a, (MinBy, MaxBy)) and src is not None:
                # second input: the ordering column rides alongside
                work[col + "__ord"] = a.ordering.eval_host(src).to_pandas()
            if isinstance(a, CountStar):
                work[col] = 1
                work[col + "__ok"] = True
            else:
                arr = (a.child.eval_host(src) if src is not None else None)
                if arr is None:
                    work[col] = pd.Series([], dtype="float64")
                    work[col + "__ok"] = pd.Series([], dtype="bool")
                else:
                    # keep SQL NULL distinct from NaN: pandas conflates
                    # them, but Spark's sum/avg/max PROPAGATE NaN while
                    # ignoring NULL (NaN is a value, NaN > everything)
                    work[col] = arr.to_pandas()
                    work[col + "__ok"] = ~np.asarray(arr.is_null())
            in_names.append(col)

        def agg_series(a, s: "pd.Series", ok: "pd.Series", sub=None,
                       col=None):
            okm = ok.to_numpy().astype(bool)
            vals = s.to_numpy()[okm]
            if a.distinct and not isinstance(a, CountStar):
                vals = pd.unique(pd.Series(vals))   # NaN == NaN, keep one
            if isinstance(a, CountStar):
                return len(s)
            if isinstance(a, Count):
                return len(vals)
            if isinstance(a, CollectSet):
                return list(pd.unique(pd.Series(vals)))
            if isinstance(a, CollectList):
                return list(vals)
            if isinstance(a, (MinBy, MaxBy)):
                # Spark: pick the VALUE (possibly NULL) at the extreme
                # ordering; only NULL-ordering rows are skipped
                o = sub[col + "__ord"].to_numpy()
                o_ok = ~pd.isna(o)
                if not o_ok.any():
                    return None
                idx = np.nanargmin(o[o_ok]) if a._pick_min \
                    else np.nanargmax(o[o_ok])
                if not okm[o_ok][idx]:
                    return None                     # value is SQL NULL
                return s.to_numpy()[o_ok][idx]
            if len(vals) == 0:
                return None
            if isinstance(a, Percentile):
                # incl. ApproximatePercentile: computed EXACTLY here
                fv = vals.astype(np.float64)
                fv = fv[~np.isnan(fv)]
                if len(fv) == 0:
                    return None
                return float(np.percentile(np.sort(fv),
                                           a.percentage * 100.0,
                                           method="linear"))
            dec_in = a.child.data_type(child_schema)
            if isinstance(dec_in, DecimalType) \
                    and isinstance(a, (Sum, Average)):
                # exact: Python ints of the unscaled values; Spark's
                # result types and its two HALF_UP roundings of an average
                total = sum(D.unscaled(x, dec_in) for x in vals)
                if isinstance(a, Sum):
                    return D.from_unscaled(total, D.sum_type(dec_in))
                q = D.average_int(total, len(vals), dec_in)
                return None if q is None else D.from_unscaled(
                    q, D.avg_types(dec_in)[2])
            if isinstance(a, Sum):
                return np.sum(vals)                 # NaN propagates
            if isinstance(a, Min):
                with np.errstate(invalid="ignore"):
                    m = np.nanmin(vals) if _is_float(vals) else np.min(vals)
                return m                            # all-NaN -> NaN
            if isinstance(a, Max):
                return np.max(vals)                 # NaN is greatest
            if isinstance(a, Average):
                return np.sum(vals) / len(vals)
            if isinstance(a, First):
                return vals[0]
            if isinstance(a, Last):
                return vals[-1]
            n = len(vals)
            if isinstance(a, (StddevSamp, VarianceSamp)) and n < 2:
                return None
            mean = np.sum(vals) / n
            var = np.sum((vals - mean) ** 2) / \
                (n - 1 if isinstance(a, (StddevSamp, VarianceSamp)) else n)
            if isinstance(a, (StddevSamp, StddevPop)):
                return np.sqrt(var)
            if isinstance(a, (VarianceSamp, VariancePop)):
                return var
            raise NotImplementedError(type(a).__name__)

        if self.groupings:
            grouped = work.groupby(key_names, dropna=False, sort=False)
            rows = []
            for key, sub in grouped:
                if not isinstance(key, tuple):
                    key = (key,)
                rows.append(list(key) +
                            [agg_series(a, sub[c], sub[c + "__ok"],
                                        sub, c)
                             for a, c in zip(self.aggs, in_names)])
            out = pd.DataFrame(rows, columns=self._schema.names())
        else:
            vals = [agg_series(a, work[c], work[c + "__ok"], work, c)
                    for a, c in zip(self.aggs, in_names)]
            out = pd.DataFrame([vals], columns=self._schema.names())
        # coerce to declared output types
        from ..types import to_arrow as _toa

        def _cell(x, is_float: bool):
            if x is None:
                return None
            if isinstance(x, (list, np.ndarray)):
                return list(x)         # collect_list/set array cells
            if is_float and isinstance(x, float) and np.isnan(x):
                return x               # NaN is a VALUE, not SQL NULL
            return None if pd.isna(x) else x

        arrays = []
        for f in self._schema.fields:
            isf = f.dtype.name in ("float", "double")
            vals = [_cell(x, isf) for x in out[f.name].tolist()]
            arrays.append(pa.array(vals, type=_toa(f.dtype)))
        table = pa.Table.from_arrays(arrays, names=self._schema.names())
        # host-only output (see CpuFilterExec): no device bounce on the
        # CPU-reverted path; downstream re-materializes if needed
        yield ColumnarBatch.from_arrow_host(table)

    def describe(self):
        g = ", ".join(e.name_hint for e in self.groupings)
        a = ", ".join(x.name_hint for x in self.aggs)
        return f"CpuAggregate[keys=[{g}], aggs=[{a}]]"


def _is_float(vals) -> bool:
    return getattr(vals, "dtype", None) is not None and \
        vals.dtype.kind == "f"


def _host_series(expr: Expression, df, src_batch):
    """Evaluate an expression to a pandas Series on the host."""
    import pandas as pd
    if src_batch is None:
        return pd.Series([], dtype="float64")
    return expr.eval_host(src_batch).to_pandas()
