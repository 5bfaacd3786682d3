"""Multi-chip SPMD query execution over a device mesh.

This is the ICI/DCN replacement for the reference's shuffle transport
(SURVEY.md section 2.10 "TPU equivalent"): instead of UCX point-to-point RDMA
between executor processes (RapidsShuffleClient.doFetch), the whole exchange
is ONE XLA `all_to_all` collective inside a shard_map'd program — batches
stay in HBM, XLA schedules the ICI transfers, and DCN handles cross-slice
legs automatically for meshes spanning slices.

Distributed aggregation pipeline (per device, lockstep SPMD):
  1. local filter/project + first-pass segmented groupby  (compute, no comm)
  2. route each local group to owner = key_hash % n_devices
  3. all_to_all the routed group partials                 (ICI)
  4. merge-pass groupby over received partials            (compute)
  5. finalize -> each device owns a disjoint set of final groups
This is the same update/merge maths as the single-chip path (shared
exec/groupby_core.py), so distributing cannot change results.
"""
from __future__ import annotations

import functools
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from jax import shard_map

from ..exprs.base import DVal, EvalContext, Expression
from ..exec.groupby_core import segmented_groupby
from ..types import Schema

__all__ = ["build_distributed_agg_step", "distributed_groupby",
           "build_distributed_join_step", "distributed_join"]

# Engine-INTERNAL routing hash for group->owner placement (placement here
# never needs Spark parity — unlike shuffle partitioning, which uses the
# Spark-exact Murmur3 in exprs/hash_fns.py). 32-bit mixing only, so it
# works for every device dtype including f64 (hashed via its f32 image;
# equal keys still hash equal, the only requirement) — TPU has no f64
# bitcast (hash_fns.py device notes).

_M1 = jnp.uint32(0x85EBCA6B)
_M2 = jnp.uint32(0xC2B2AE35)


def _mix32(h):
    h = h ^ (h >> jnp.uint32(16))
    h = h * _M1
    h = h ^ (h >> jnp.uint32(13))
    h = h * _M2
    h = h ^ (h >> jnp.uint32(16))
    return h


def _col_hash_u32(v: DVal):
    d = v.data
    if jnp.issubdtype(d.dtype, jnp.floating):
        f = d.astype(jnp.float32)
        f = jnp.where(f == 0.0, jnp.zeros_like(f), f)
        f = jnp.where(jnp.isnan(f), jnp.full_like(f, jnp.nan), f)
        h = jax.lax.bitcast_convert_type(f, jnp.uint32)
    elif d.dtype == jnp.bool_:
        h = d.astype(jnp.uint32)
    else:
        x = d.astype(jnp.int64)
        lo = (x & jnp.int64(0xFFFFFFFF)).astype(jnp.uint32)
        hi = (x >> jnp.int64(32)).astype(jnp.uint32)
        h = lo ^ _mix32(hi)
    # null contributes a fixed tag so null keys land together
    return jnp.where(v.validity, _mix32(h), jnp.uint32(42))


def _route_to_buffers(arrays, pid, padded_len: int, n_dev: int):
    """Pack rows into (n_dev, padded_len) send buffers by destination.

    Worst case (every row to one destination) still fits because the chunk
    size equals the local padded length; slot = pid*P + rank-within-pid,
    computed via one stable sort by pid (the contiguous-split trick)."""
    order = jnp.argsort(pid, stable=True)
    s_pid = jnp.take(pid, order)
    idx = jnp.arange(padded_len, dtype=jnp.int32)
    first_of_pid = jnp.logical_or(idx == 0, s_pid != jnp.roll(s_pid, 1))
    seg_start = jnp.where(first_of_pid, idx, 0)
    seg_start = jax.lax.associative_scan(jnp.maximum, seg_start)
    intra = idx - seg_start
    slot = jnp.where(s_pid < n_dev, s_pid * padded_len + intra,
                     n_dev * padded_len)
    outs = []
    for d, v in arrays:
        sd = jnp.take(d, order)
        sv = jnp.take(v, order)
        od = jnp.zeros((n_dev * padded_len,), dtype=d.dtype) \
            .at[slot].set(sd, mode="drop")
        ov = jnp.zeros((n_dev * padded_len,), dtype=jnp.bool_) \
            .at[slot].set(jnp.logical_and(sv, s_pid < n_dev), mode="drop")
        outs.append((od.reshape(n_dev, padded_len),
                     ov.reshape(n_dev, padded_len)))
    return outs


def _compact_rows(arrays, keep, length):
    """Move keep-rows to the front; arrays are (data, validity) pairs;
    returns compacted pairs + count. Sort-based (segmented.compact_rows):
    scatter compaction serializes on the TPU scalar core."""
    from ..columnar.segmented import compact_rows
    masked = [(d, jnp.logical_and(v, keep)) for d, v in arrays]
    return compact_rows(masked, keep, length)


def build_distributed_agg_step(mesh: Mesh, schema: Schema,
                               key_exprs: Sequence[Expression],
                               aggs: Sequence,
                               local_padded: int,
                               pre_filter: Optional[Expression] = None,
                               axis: str = "data"):
    """Compile the full distributed query step: returns fn(cols, num_rows)
    where cols are GLOBAL (n_dev*local_padded,) arrays sharded on `axis` and
    num_rows is a (n_dev,) int32 vector of per-shard row counts. Output:
    per-device final group columns (global (n_dev*local_padded,)) and a
    (n_dev,) group-count vector."""
    n_dev = int(np.prod([mesh.shape[a] for a in mesh.axis_names]))
    dtypes = [f.dtype for f in schema.fields]
    partial_counts = [len(a.partial_types(schema)) for a in aggs]

    _compact = _compact_rows

    def local_step(nrows, *cols):
        P_ = local_padded
        nloc = nrows[0]
        dvals = [DVal(d, v, dt)
                 for d, v, dt in zip(cols[0::2], cols[1::2], dtypes)]
        ctx = EvalContext(schema, dvals, nloc, P_)
        # 1. local filter: evaluate predicate, compact surviving rows
        keys = [e.eval_device(ctx) for e in key_exprs]
        vals = [[e.eval_device(ctx) for e in a.input_exprs()] for a in aggs]
        flat = [(k.data, k.validity) for k in keys]
        for vs in vals:
            flat.extend((v.data, v.validity) for v in vs)
        if pre_filter is not None:
            keep = pre_filter.eval_device(ctx)
            keepb = jnp.logical_and(jnp.logical_and(keep.data, keep.validity),
                                    ctx.row_mask())
            flat, nloc = _compact(flat, keepb, P_)
        # rebuild DVals (post-compaction or as-is)
        ai = 0
        keys2, vals2 = [], []
        for k in keys:
            keys2.append(DVal(flat[ai][0], flat[ai][1], k.dtype))
            ai += 1
        for vs in vals:
            cur = []
            for v in vs:
                cur.append(DVal(flat[ai][0], flat[ai][1], v.dtype))
                ai += 1
            vals2.append(cur)
        # 2. first-pass local aggregation
        key_outs, partial_outs, n_groups = segmented_groupby(
            keys2, vals2, aggs, "update", nloc, P_)
        # 3. route groups to owners by key hash
        glive = jnp.arange(P_, dtype=jnp.int32) < n_groups
        if key_exprs:
            h = jnp.full(P_, jnp.uint32(42))
            for (kd, kv), k in zip(key_outs, keys2):
                h = _mix32(h * jnp.uint32(31)
                           + _col_hash_u32(DVal(kd, kv, k.dtype)))
            pid = jnp.where(glive, (h % jnp.uint32(n_dev)).astype(jnp.int32),
                            jnp.int32(n_dev))
        else:
            pid = jnp.where(glive, 0, n_dev)  # global agg -> device 0
        bufs = _route_to_buffers(key_outs + partial_outs, pid, P_, n_dev)
        # 4. ICI all_to_all: every device receives the groups it owns
        recv = []
        for d, v in bufs:
            rd = jax.lax.all_to_all(d, axis, 0, 0, tiled=False)
            rv = jax.lax.all_to_all(v, axis, 0, 0, tiled=False)
            recv.append((rd.reshape(n_dev * P_), rv.reshape(n_dev * P_)))
        # compact received group rows (validity marks real rows; count is
        # never null so every live group row has >=1 valid column)
        live = jnp.zeros(n_dev * P_, dtype=jnp.bool_)
        for _, v in recv:
            live = jnp.logical_or(live, v)
        comp, cnt = _compact(recv, live, n_dev * P_)
        # 5. merge pass over received partials
        rkeys = [DVal(comp[i][0], comp[i][1], k.dtype)
                 for i, k in enumerate(keys2)]
        rvals = []
        ai = len(keys2)
        for a, npart in zip(aggs, partial_counts):
            pts = a.partial_types(schema)
            rvals.append([DVal(comp[ai + j][0], comp[ai + j][1], pts[j])
                          for j in range(npart)])
            ai += npart
        mkey_outs, mpartial_outs, m_groups = segmented_groupby(
            rkeys, rvals, aggs, "merge", cnt, n_dev * P_)
        if not key_exprs:
            # the single global group lives on device 0 only
            m_groups = jnp.where(jax.lax.axis_index(axis) == 0,
                                 m_groups, 0)
        # 6. finalize
        glive2 = jnp.arange(n_dev * P_, dtype=jnp.int32) < m_groups
        outs = []
        for d, v in mkey_outs:
            outs.extend([d, jnp.logical_and(v, glive2)])
        ai = 0
        for a, npart in zip(aggs, partial_counts):
            pts = a.partial_types(schema)
            parts = [DVal(mpartial_outs[ai + j][0], mpartial_outs[ai + j][1],
                          pts[j]) for j in range(npart)]
            ai += npart
            f = a.finalize(parts)
            outs.extend([f.data, jnp.logical_and(f.validity, glive2)])
        return (m_groups.reshape(1),) + tuple(outs)

    in_specs = (P(axis),) + tuple(P(axis) for _ in range(2 * len(dtypes)))
    n_out = 1 + 2 * (len(key_exprs) + len(aggs))
    out_specs = (P(axis),) + tuple(P(axis) for _ in range(n_out - 1))

    fn = shard_map(local_step, mesh=mesh, in_specs=in_specs,
                   out_specs=out_specs, check_vma=False)
    return jax.jit(fn), n_dev


def distributed_groupby(mesh: Mesh, table, key_names: List[str], aggs,
                        pre_filter=None, axis: str = "data"):
    """Host-friendly wrapper: Arrow table -> sharded arrays -> distributed
    step -> Arrow result table. Used by tests and the dryrun."""
    import pyarrow as pa
    from ..columnar import ColumnarBatch
    from ..columnar.bucketing import bucket_for
    from ..exprs.base import ColumnRef
    from ..types import to_arrow

    n_dev = int(np.prod([mesh.shape[a] for a in mesh.axis_names]))
    n = table.num_rows
    per = -(-n // n_dev)
    local_p = bucket_for(max(per, 1))
    schema = ColumnarBatch.from_arrow_host(table).schema
    key_exprs = [ColumnRef(k) for k in key_names]
    step, _ = build_distributed_agg_step(mesh, schema, key_exprs, aggs,
                                         local_p, pre_filter, axis)
    nrows_dev, cols_dev = _shard_table_arrays(mesh, table, schema,
                                              local_p, axis)
    out = step(nrows_dev, *cols_dev)
    m_groups = np.asarray(jax.device_get(out[0]))
    data = [np.asarray(jax.device_get(x)) for x in out[1:]]
    # stitch per-device group slices
    names = key_names + [a.name_hint for a in aggs]
    dtypes = [schema[k].dtype for k in key_names] + \
        [a.data_type(schema) for a in aggs]
    chunk = n_dev * local_p
    arrays = []
    for ci in range(len(names)):
        d_all, v_all = data[2 * ci], data[2 * ci + 1]
        parts_d, parts_v = [], []
        for dev in range(n_dev):
            g = int(m_groups[dev])
            parts_d.append(d_all[dev * chunk: dev * chunk + g])
            parts_v.append(v_all[dev * chunk: dev * chunk + g])
        dv = np.concatenate(parts_d)
        vv = np.concatenate(parts_v)
        from ..columnar.column import DeviceColumn
        col = DeviceColumn(jnp.asarray(dv), jnp.asarray(vv), dtypes[ci])
        arrays.append(col.to_arrow(len(dv)))
    return pa.Table.from_arrays(arrays, names=names)


# ---------------------------------------------------------------------------
# distributed equi-join (the ICI analog of the reference's UCX shuffle join:
# both sides hash-route rows to key owners with ONE all_to_all each, then
# every device runs the local sort-based join kernel on its co-partitioned
# slice — the same kernel as single-chip exec/joins.py, so distribution
# cannot change results)
# ---------------------------------------------------------------------------

def build_distributed_join_step(mesh: Mesh, lschema: Schema,
                                rschema: Schema,
                                lkey_exprs: Sequence[Expression],
                                rkey_exprs: Sequence[Expression],
                                local_padded: int, out_factor: int = 4,
                                axis: str = "data"):
    """Returns fn(nl, nr, *lcols, *rcols) under shard_map. Per device the
    local join output is bounded by ``out_factor * local_padded`` rows
    (static shapes: XLA requirement); the returned per-device `total` lets
    the caller detect overflow and re-run with a larger factor."""
    from ..exec.joins import _build_count_kernel, _gather_index_kernel
    n_dev = int(np.prod([mesh.shape[a] for a in mesh.axis_names]))
    ldtypes = [f.dtype for f in lschema.fields]
    rdtypes = [f.dtype for f in rschema.fields]
    P_ = local_padded
    RP = n_dev * P_                 # received rows bound per device
    OUT = out_factor * P_           # local join output bound
    count_k = _build_count_kernel(lkey_exprs, rkey_exprs, lschema, rschema,
                                  "inner")

    # both sides must hash each key through a COMMON dtype, or equal keys
    # of different widths route to different owners and matches silently
    # vanish (the local count kernel promotes before comparing; routing
    # must promote identically)
    l0, r0 = lschema, rschema
    key_np = [np.promote_types(lk.data_type(l0).np_dtype,
                               rk.data_type(r0).np_dtype)
              for lk, rk in zip(lkey_exprs, rkey_exprs)]

    def route_side(nloc, pairs, dtypes, schema, key_exprs):
        dvals = [DVal(d, v, dt) for (d, v), dt in zip(pairs, dtypes)]
        ctx = EvalContext(schema, dvals, nloc, P_)
        live = ctx.row_mask()
        keys = [e.eval_device(ctx) for e in key_exprs]
        h = jnp.full(P_, jnp.uint32(42))
        for k, npdt in zip(keys, key_np):
            kk = DVal(k.data.astype(npdt), k.validity, k.dtype)
            h = _mix32(h * jnp.uint32(31) + _col_hash_u32(kk))
        pid = jnp.where(live, (h % jnp.uint32(n_dev)).astype(jnp.int32),
                        jnp.int32(n_dev))
        # explicit liveness lane: a routed row may be all-null, so column
        # validities cannot double as the row-live flag
        flat = list(pairs) + [(jnp.ones(P_, jnp.int8), live)]
        bufs = _route_to_buffers(flat, pid, P_, n_dev)
        recv = []
        for d, v in bufs:
            rd = jax.lax.all_to_all(d, axis, 0, 0, tiled=False)
            rv = jax.lax.all_to_all(v, axis, 0, 0, tiled=False)
            recv.append((rd.reshape(RP), rv.reshape(RP)))
        live_recv = recv[-1][1]
        comp, cnt = _compact_rows(recv[:-1], live_recv, RP)
        return comp, cnt

    def local(nl, nr, *cols):
        nL, nR = len(ldtypes), len(rdtypes)
        lpairs = [(cols[2 * i], cols[2 * i + 1]) for i in range(nL)]
        rpairs = [(cols[2 * nL + 2 * i], cols[2 * nL + 2 * i + 1])
                  for i in range(nR)]
        lcomp, nl2 = route_side(nl[0], lpairs, ldtypes, lschema, lkey_exprs)
        rcomp, nr2 = route_side(nr[0], rpairs, rdtypes, rschema, rkey_exprs)
        (s_orig, cnt_l, cnt_r, start_l, start_r, _pairs, offsets, total,
         _ng) = count_k(lcomp, rcomp, nl2, nr2, RP, RP)
        cfg = jnp.zeros(3, dtype=jnp.int32)       # inner join
        l_row, r_row = _gather_index_kernel(
            s_orig, cnt_l, cnt_r, start_l, start_r, offsets, cfg, OUT)
        out_live = jnp.arange(OUT, dtype=jnp.int64) < total
        outs = []
        for d, v in lcomp:
            idx = jnp.clip(l_row, 0, None)
            outs.append(jnp.take(d, idx, mode="clip"))
            outs.append(jnp.logical_and(
                jnp.take(v, idx, mode="clip"),
                jnp.logical_and(out_live, l_row >= 0)))
        for d, v in rcomp:
            idx = jnp.clip(r_row, 0, None)
            outs.append(jnp.take(d, idx, mode="clip"))
            outs.append(jnp.logical_and(
                jnp.take(v, idx, mode="clip"),
                jnp.logical_and(out_live, r_row >= 0)))
        return (total.astype(jnp.int64).reshape(1),
                out_live.reshape(1, OUT)) + tuple(
                    o.reshape(1, OUT) for o in outs)

    n_in = 2 * (len(ldtypes) + len(rdtypes))
    in_specs = (P(axis), P(axis)) + tuple(P(axis) for _ in range(n_in))
    n_out = 2 + n_in
    out_specs = tuple(P(axis) for _ in range(n_out))
    fn = shard_map(local, mesh=mesh, in_specs=in_specs,
                   out_specs=out_specs, check_vma=False)
    return jax.jit(fn), n_dev, OUT


def _shard_table_arrays(mesh, table, schema, local_p, axis):
    """Split an Arrow table row-wise across the mesh into padded, sharded
    global (data, validity) device arrays + per-shard row counts."""
    from ..columnar import ColumnarBatch
    n_dev = int(np.prod([mesh.shape[a] for a in mesh.axis_names]))
    per = -(-table.num_rows // n_dev) if table.num_rows else 1
    shards = [table.slice(i * per, per) for i in range(n_dev)]
    nrows = np.array([s.num_rows for s in shards], dtype=np.int32)
    sharding = NamedSharding(mesh, P(axis))
    cols_dev = []
    for f in schema.fields:
        ds, vs = [], []
        for s in shards:
            b = ColumnarBatch.from_arrow(s.select([f.name]))
            c = b.columns[0]
            d = np.asarray(jax.device_get(c.data))
            v = np.asarray(jax.device_get(c.validity))
            if d.shape[0] < local_p:
                d = np.pad(d, (0, local_p - d.shape[0]))
                v = np.pad(v, (0, local_p - v.shape[0]))
            ds.append(d[:local_p])
            vs.append(v[:local_p])
        cols_dev.append(jax.device_put(jnp.asarray(np.concatenate(ds)),
                                       sharding))
        cols_dev.append(jax.device_put(jnp.asarray(np.concatenate(vs)),
                                       sharding))
    nrows_dev = jax.device_put(jnp.asarray(nrows), sharding)
    return nrows_dev, cols_dev


def distributed_join(mesh: Mesh, ltable, rtable, on, out_factor: int = 4,
                     axis: str = "data"):
    """Host-friendly wrapper: inner equi-join of two Arrow tables over the
    mesh; returns the joined Arrow table (l columns then r columns).
    ``on`` is a list of (left_col, right_col) name pairs."""
    import pyarrow as pa
    from ..columnar import ColumnarBatch
    from ..columnar.bucketing import bucket_for
    from ..columnar.column import DeviceColumn
    from ..exprs.base import ColumnRef

    n_dev = int(np.prod([mesh.shape[a] for a in mesh.axis_names]))
    per = max(-(-max(ltable.num_rows, rtable.num_rows) // n_dev), 1)
    local_p = bucket_for(per)
    lschema = ColumnarBatch.from_arrow_host(ltable).schema
    rschema = ColumnarBatch.from_arrow_host(rtable).schema
    lkeys = [ColumnRef(a) for a, _ in on]
    rkeys = [ColumnRef(b) for _, b in on]
    step, _, OUT = build_distributed_join_step(
        mesh, lschema, rschema, lkeys, rkeys, local_p, out_factor, axis)
    nl, lcols = _shard_table_arrays(mesh, ltable, lschema, local_p, axis)
    nr, rcols = _shard_table_arrays(mesh, rtable, rschema, local_p, axis)
    out = step(nl, nr, *(lcols + rcols))
    totals = np.asarray(jax.device_get(out[0]))
    if (totals > OUT).any():
        raise RuntimeError(
            f"distributed join output overflowed the static bound "
            f"(max {int(totals.max())} > {OUT}); re-run with a larger "
            f"out_factor")
    data = [np.asarray(jax.device_get(x)) for x in out[2:]]
    names = [f.name for f in lschema.fields] + \
        [f.name for f in rschema.fields]
    dtypes = [f.dtype for f in lschema.fields] + \
        [f.dtype for f in rschema.fields]
    arrays = []
    for ci in range(len(names)):
        d_all, v_all = data[2 * ci], data[2 * ci + 1]
        parts_d, parts_v = [], []
        for dev in range(n_dev):
            g = int(totals[dev])
            parts_d.append(d_all[dev][:g])
            parts_v.append(v_all[dev][:g])
        dv = np.concatenate(parts_d)
        vv = np.concatenate(parts_v)
        col = DeviceColumn(jnp.asarray(dv), jnp.asarray(vv), dtypes[ci])
        arrays.append(col.to_arrow(len(dv)))
    return pa.Table.from_arrays(arrays, names=names)
