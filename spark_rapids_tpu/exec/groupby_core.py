"""Core traced groupby: encode keys -> ONE variadic lax.sort (payloads ride
the sort network) -> segmented scans -> one compaction sort. Shared by the
single-chip aggregate exec and the SPMD fragment compiler.

Reference analog: cudf's hash groupby behind GpuHashAggregateExec
(GpuAggregateExec.scala). A hash table is the wrong shape for a TPU (random
scatter/gather serialize on the scalar core); sorting is native (variadic
bitonic sort on the VPU, 4-8 ms per 1M rows measured on v5e). Values are
carried through the key sort as sort payloads, aggregates become segmented
scans over the sorted domain, and results pack to the front with one more
sort keyed on "segment id at end rows, +inf elsewhere".

The pipeline is exposed BOTH as one traceable composition
(``segmented_groupby`` — required inside shard_map SPMD fragments and the
fused single-batch kernels) AND as three separately-traceable stages
(``stage_sort`` / ``stage_scan`` / ``stage_pack``). The split form exists
for COMPILE time: a lax.sort's compile cost multiplies with the complexity
of the surrounding module (a bare variadic sort compiles fastest, the same
sort fed by elementwise prologues slower, and the full fused two-key merge
kernel slowest by far), while the three stages jitted separately each stay
small and add only dispatch latency — the right trade everywhere except
inside shard_map. Compile seconds per module on the attached chip: PERF.md.
"""
from __future__ import annotations

from typing import List, Sequence, Tuple

import jax
import jax.numpy as jnp

from ..columnar.segmented import (GlobalSegments, SortedSegments,
                                  front_sort, prefix_sum)
from ..exprs.base import DVal
from .encoding import grouping_operands, operands_equal

__all__ = ["segmented_groupby", "stage_sort", "stage_scan", "stage_pack",
           "global_groupby"]


def global_groupby(vals: List[List[DVal]], aggs: Sequence, mode: str,
                   num_rows, padded_len: int, row_mask=None):
    """Key-less (global) aggregation: ONE segment, evaluated as plain
    masked reductions (GlobalSegments) — every aggregate's update is a
    single vector pass instead of a log2(n) segmented scan, and ALL N
    aggregates trace into the one kernel: the q9 multi-aggregate shape
    costs one dispatch and ~N fused HBM sweeps per batch."""
    if row_mask is None:
        row_mask = jnp.arange(padded_len, dtype=jnp.int32) < num_rows
    idx = jnp.arange(padded_len, dtype=jnp.int32)
    seg = GlobalSegments(row_mask, orig_index=idx)
    num_groups = jnp.int32(1)
    partial_rows = _run_aggs(aggs, vals, seg, mode, row_mask)
    partial_outs = [(jnp.where(idx == 0, d[-1],
                               jnp.zeros((), dtype=d.dtype)),
                     jnp.where(idx == 0, v[-1], False))
                    for d, v in partial_rows]
    return [], partial_outs, num_groups


def _flatten_key(k: DVal):
    """Key payload lanes: (lanes, rebuild). Byte-rectangle strings ride
    as W/8 packed words + length (the same lanes their sort operands
    use); scalar keys as (data, validity)."""
    from ..exprs.base import StrVal
    if isinstance(k.data, StrVal):
        from ..columnar.strrect import pack_words, unpack_words
        sv: StrVal = k.data
        w = sv.bytes_.shape[1]
        lanes = list(pack_words(sv.bytes_, sv.lengths)) \
            + [sv.lengths, k.validity]

        def rebuild(ls, dtype=k.dtype, w=w):
            words, lengths, validity = ls[:-2], ls[-2], ls[-1]
            return DVal(StrVal(unpack_words(list(words), w),
                               lengths.astype(jnp.int32)),
                        validity, dtype)
        return lanes, rebuild
    lanes = [k.data, k.validity]

    def rebuild(ls, dtype=k.dtype):
        return DVal(ls[0], ls[1], dtype)
    return lanes, rebuild


def stage_sort(keys: List[DVal], vals: List[List[DVal]], num_rows,
               padded_len: int, row_mask=None):
    """Stage 1: encode key operands and run THE sort, values riding as
    payloads. Returns (s_ops, perm, s_keys, sorted_vals, live_count)."""
    if row_mask is None:
        row_mask = jnp.arange(padded_len, dtype=jnp.int32) < num_rows
    idx = jnp.arange(padded_len, dtype=jnp.int32)
    pad_flag = jnp.where(row_mask, jnp.uint8(0), jnp.uint8(1))
    operands = [pad_flag]
    for k in keys:
        operands.extend(grouping_operands(k))
    n_key_ops = len(operands)
    # payloads (carried through the sort network — far cheaper than
    # row-sized gathers): original index, key columns, value columns
    payload: List = [idx]
    rebuilds = []
    spans = []
    for k in keys:
        lanes, rebuild = _flatten_key(k)
        spans.append((len(payload), len(payload) + len(lanes)))
        payload.extend(lanes)
        rebuilds.append(rebuild)
    v_start = len(payload)
    for vs in vals:
        for v in vs:
            payload.extend((v.data, v.validity))
    # the row index (the first payload) as the last key: unique keys, so
    # the unstable sort gives the stable order at half the compile time
    sorted_all = jax.lax.sort(tuple(operands + payload),
                              num_keys=n_key_ops + 1, is_stable=False)
    s_ops = sorted_all[:n_key_ops]
    rest = sorted_all[n_key_ops:]
    perm = rest[0]
    s_keys = [rb(rest[a:b]) for (a, b), rb in zip(spans, rebuilds)]
    it = iter(rest[v_start:])
    sorted_vals = [[DVal(next(it), next(it), v.dtype) for v in vs]
                   for vs in vals]
    live_count = jnp.sum(row_mask).astype(jnp.int32)
    return s_ops, perm, s_keys, sorted_vals, live_count


def stage_scan(aggs: Sequence, mode: str, s_ops, perm, s_keys,
               sorted_vals, live_count, padded_len: int):
    """Stage 2: segment boundaries from adjacent-key comparison, then the
    segmented scans. Returns (ckey, carry, num_groups) where ``carry`` is
    a NESTED (key_lane_groups, partial_pairs) structure the compaction
    sort moves — byte-rectangle string keys contribute a lane group of
    packed words + length + validity, scalar keys (data, validity)."""
    idx = jnp.arange(padded_len, dtype=jnp.int32)
    differs = jnp.zeros(padded_len, dtype=jnp.bool_)
    for op in s_ops[1:]:
        prev = jnp.roll(op, 1)
        differs = jnp.logical_or(
            differs, jnp.logical_not(operands_equal(op, prev)))
    # live rows sort first (pad_flag), so the sorted-domain live mask
    # is a prefix of length live_count — row_mask itself is in the
    # UNSORTED domain and may be arbitrary (fused pre-filter)
    s_live = idx < live_count
    flags = jnp.logical_and(jnp.logical_or(idx == 0, differs), s_live)
    num_groups = jnp.sum(flags).astype(jnp.int32)
    # segment id without live-masking: the trailing dead region simply
    # extends the last segment (its scans see only neutrals there)
    gid_seg = prefix_sum(flags, jnp.int32) - 1

    seg = SortedSegments(flags, s_live, orig_index=perm)
    partial_rows = _run_aggs(aggs, sorted_vals, seg, mode, s_live)

    # extraction: each segment's total sits at its last LIVE row (the
    # scan there covers the whole segment; the raw key payload there is
    # a real row, unlike the trailing dead region); one stable sort
    # packs those rows — already in segment order — to the front
    one_true = jnp.ones((1,), dtype=jnp.bool_)
    nxt_flag = jnp.concatenate([flags[1:], one_true])
    nxt_dead = jnp.concatenate([jnp.logical_not(s_live[1:]), one_true])
    end_mask = jnp.logical_and(
        s_live, jnp.logical_or(nxt_flag, nxt_dead))
    ckey = jnp.where(end_mask, gid_seg, padded_len)
    key_groups = []
    for k in s_keys:
        lanes, _rb = _flatten_key(k)
        key_groups.append(tuple(lanes))
    carry = (tuple(key_groups),
             tuple((d, v) for d, v in partial_rows))
    return ckey, carry, num_groups


def stage_pack(ckey, carry, num_groups, key_dtypes, padded_len: int):
    """Stage 3: the compaction sort over the nested carry. Returns
    (key_outs, partial_outs, num_groups) with group validities masked to
    the live prefix; a byte-rectangle key comes back as
    (StrVal, validity)."""
    from ..exprs.base import StrVal
    key_groups, partial_pairs = carry
    flat: List = []
    for g in key_groups:
        flat.extend(g)
    for d, v in partial_pairs:
        flat.extend((d, v))
    idx = jnp.arange(padded_len, dtype=jnp.int32)
    ends = ckey < padded_len
    it = iter(front_sort(ends, jnp.where(ends, ckey, idx), flat,
                         padded_len))
    group_live = idx < num_groups
    key_outs = []
    for g, dt in zip(key_groups, key_dtypes):
        lanes = [next(it) for _ in g]
        if len(lanes) == 2:
            key_outs.append((lanes[0],
                             jnp.logical_and(lanes[1], group_live)))
        else:                      # rect string: words... + length + valid
            from ..columnar.strrect import unpack_words
            words, lengths, valid = lanes[:-2], lanes[-2], lanes[-1]
            w = 8 * len(words)
            key_outs.append((StrVal(unpack_words(list(words), w),
                                    lengths.astype(jnp.int32)),
                             jnp.logical_and(valid, group_live)))
    partial_outs = [(next(it), jnp.logical_and(next(it), group_live))
                    for _ in partial_pairs]
    return key_outs, partial_outs, num_groups


def segmented_groupby(keys: List[DVal], vals: List[List[DVal]],
                      aggs: Sequence, mode: str, num_rows, padded_len: int,
                      row_mask=None):
    """Returns (key_outs [(data, validity)...], partial_outs, num_groups).

    mode='update' runs agg.update, mode='merge' runs agg.merge. All inputs
    are padded device values; rows >= num_rows are ignored. Output group
    arrays have length padded_len with groups packed at the front.
    ``row_mask`` (bool[P]) overrides the row-count mask so a fused
    pre-filter can drop rows without a separate compaction kernel.

    One traceable composition of the three stages — required inside
    shard_map fragments and the fused single-batch kernels; the aggregate
    exec's classic path jits the stages separately instead (see module
    docstring for why)."""
    if not keys:
        return global_groupby(vals, aggs, mode, num_rows, padded_len,
                              row_mask)
    s_ops, perm, s_keys, sorted_vals, live_count = stage_sort(
        keys, vals, num_rows, padded_len, row_mask)
    ckey, carry, num_groups = stage_scan(
        aggs, mode, s_ops, perm, s_keys, sorted_vals, live_count,
        padded_len)
    return stage_pack(ckey, carry, num_groups,
                      [k.dtype for k in keys], padded_len)


def _run_aggs(aggs, vals, seg, mode, update_mask):
    outs = []
    for a, vs in zip(aggs, vals):
        if mode == "update":
            outs.extend(a.update(vs, seg, None, update_mask))
        else:
            outs.extend(a.merge(vs, seg, None))
    return outs
