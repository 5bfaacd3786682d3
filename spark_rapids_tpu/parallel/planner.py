"""Planner-driven distributed execution: planned queries run on the mesh.

This is the analog of the reference's planner inserting shuffle exchanges
(GpuShuffleExchangeExecBase.scala:167, prepareBatchShuffleDependency:277 →
GpuPartitioning.scala:37) so every downstream operator runs distributed.
TPU-first shape: instead of per-task exchanges through a shuffle service,
the planner compiles the WHOLE supported plan fragment — scan → filter →
project → join → aggregate — into ONE SPMD program under ``shard_map`` over
a ``jax.sharding.Mesh``; exchanges become ``all_to_all`` collectives inside
the program (ICI/DCN, batches never leave HBM), exactly the design the
reference approximates with UCX device-to-device shuffle
(RapidsShuffleClient.doFetch).

Lowering contract (maybe_distribute):
  * walks the physical plan for the largest subtree expressible as a
    distributed fragment containing at least one join or aggregation
    (a fragment without comm gains nothing from the mesh);
  * replaces it with DistributedPipelineExec; everything above (final sort,
    limit, write) keeps running on the host driver over the collected
    result — the same division of labour as the reference's CPU-fallback
    boundary, with honest explain() output;
  * unsupported leaves degrade gracefully: any unsupported subtree becomes
    a host-executed SOURCE whose result is sharded onto the mesh (the
    row-to-columnar boundary analog, GpuRowToColumnarExec).

String columns ride the mesh as int32 codes into a per-column GLOBAL sorted
dictionary built at shard time (the multi-chip extension of the engine's
DictColumn design, columnar/column.py): code equality/order equals string
equality/order on every device, and only final materialization decodes.

Static-shape discipline (XLA): every per-device relation has a padded
length fixed at trace time. Join outputs and routed aggregations carry
speculative bounds validated AFTER execution from the fetched counts; an
overflow rebuilds the program with doubled bounds and re-runs (the
mesh-level analog of the engine's speculative join sizing with sink
validation, columnar/batch.py SpeculativeOverflow).
"""
from __future__ import annotations

import logging
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..config import TpuConf, register
from ..exec.base import TpuExec
from ..types import INT32, INT64, STRING, DataType, Schema, StructField

log = logging.getLogger("spark_rapids_tpu.distributed")

__all__ = ["maybe_distribute", "try_distribute", "distribution_gate",
           "DistributedPipelineExec",
           "DISTRIBUTED_ENABLED", "DISTRIBUTED_NUM_DEVICES"]

DISTRIBUTED_ENABLED = register(
    "spark.rapids.tpu.distributed.enabled", True,
    "Lower planned queries onto the session's device mesh: the supported "
    "plan fragment compiles to one SPMD program with all_to_all exchanges "
    "(ref GpuShuffleExchangeExecBase.scala:167 — the planner, not the user, "
    "makes queries distributed). ON by default since r4: a mesh is built "
    "automatically when >1 device is visible, and the planner skips the "
    "mesh for inputs below distributed.minRows (a per-collective dispatch "
    "floor no small input can pay back). An explicitly-supplied session "
    "mesh always distributes.", commonly_used=True)

DISTRIBUTED_MIN_ROWS = register(
    "spark.rapids.tpu.distributed.minRows", 262144,
    "Auto-mesh threshold: a conf-built (non-explicit) mesh is only used "
    "for queries whose in-memory scan inputs reach this many rows — below "
    "it the exchange/dispatch overhead outweighs the parallelism (the "
    "reference's CBO transition-cost revert applied to distribution). "
    "File scans are always considered large enough.")

DISTRIBUTED_NUM_DEVICES = register(
    "spark.rapids.tpu.distributed.numDevices", 0,
    "Mesh size for distributed execution; 0 = all visible devices.")

DISTRIBUTED_MAX_GROUPS = register(
    "spark.rapids.tpu.distributed.maxPartialGroups", 65536,
    "Static per-device bound on first-pass groups routed through the "
    "all_to_all exchange; exceeded bounds double and re-run (speculative "
    "sizing, validated at the sink).")

DISTRIBUTED_OUT_FACTOR = register(
    "spark.rapids.tpu.distributed.joinOutFactor", 2,
    "Initial join-output bound as a multiple of the probe-side shard size; "
    "exceeded bounds double and re-run.")

DISTRIBUTED_MAX_DICT = register(
    "spark.rapids.tpu.distributed.maxDictEntries", 100_000,
    "Cardinality cap for the per-column GLOBAL sorted string dictionary "
    "built at shard time. Above the cap the column rides as 64-bit "
    "string hashes instead (no driver-side string sort — the decode map "
    "sorts only the int64 hashes); hash-coded columns keep equality "
    "(grouping, filters) but not order.")

FUSED_PIPELINE = register(
    "spark.rapids.tpu.sql.fusedPipeline.enabled", True,
    "Single-chip queries whose plan contains a join compile the WHOLE "
    "supported fragment (scans -> filters -> joins -> aggregation) into "
    "ONE kernel via the fragment compiler on a 1-device mesh — one "
    "dispatch and a two-stream packed result fetch instead of several "
    "launches (ref GpuShuffleExchangeExecBase.scala:167: exchanges are "
    "not opt-in). ON by default since r3 (packed sink + compiled-"
    "program cache); fused against operator pipeline is not measured "
    "on the attached chip. Unsupported or oversized plans (a source "
    "above the largest shape bucket) fall back to the operator "
    "pipeline either way.", commonly_used=True)

#: learned speculative bounds per (fragment signature, bound key) —
#: the cross-query statistics that let repeat queries start with tight
#: static shapes (the fragment analog of exec/joins._TOTAL_STATS)
_FRAGMENT_STATS: Dict[Tuple, int] = {}

#: compiled SPMD programs keyed by (signature, n_dev, source layout,
#: resolved bounds): re-running the same query shape must NOT pay the
#: shard_map retrace + lowering again (measured ~5 s/query on the
#: fused q3 fragment — the whole win of one-dispatch execution was
#: being spent re-tracing it). Programs are cached only after their
#: bounds VALIDATE (an overflowed attempt's undersized program could
#: never match again) and the cache is entry-capped LRU — each entry
#: pins a compiled XLA executable.
_PROGRAM_CACHE: Dict[Tuple, List[tuple]] = {}
_PROGRAM_LRU: Dict[Tuple, int] = {}
_PROGRAM_TICK = [0]
_PROGRAM_CACHE_MAX = 64


def _program_cache_put(base_key, variant):
    _PROGRAM_CACHE.setdefault(base_key, []).append(variant)
    _PROGRAM_TICK[0] += 1
    _PROGRAM_LRU[base_key] = _PROGRAM_TICK[0]
    while sum(len(v) for v in _PROGRAM_CACHE.values()) \
            > _PROGRAM_CACHE_MAX:
        coldest = min(_PROGRAM_LRU, key=_PROGRAM_LRU.get)
        del _PROGRAM_CACHE[coldest]
        del _PROGRAM_LRU[coldest]

#: per-source device-array cache (encode + pad + H2D skipped on repeat
#: queries over the same in-memory table). Weak pin + finalizer evict on
#: table GC (the scan-cache pattern, exec/basic.py); byte-capped LRU.
import weakref  # noqa: E402

_SOURCE_PIN: "weakref.WeakValueDictionary" = weakref.WeakValueDictionary()
_SOURCE_ARRAYS: Dict[Tuple, tuple] = {}
_SOURCE_LRU: Dict[Tuple, int] = {}
_SOURCE_TICK = [0]


def _source_cache_limit(conf: TpuConf) -> int:
    # governed by the SAME conf as the operator scan cache: one budget
    # for "device arrays pinned for repeat scans", 0 disables both
    from ..exec.basic import SCAN_CACHE_MAX_BYTES
    return int(conf.get(SCAN_CACHE_MAX_BYTES))


def _source_evict(tid: int):
    for k in [k for k in _SOURCE_ARRAYS if k[0] == tid]:
        del _SOURCE_ARRAYS[k]
        _SOURCE_LRU.pop(k, None)


def _source_bytes(entry) -> int:
    _n, pairs, _d, _p = entry
    return sum(int(d.nbytes) + int(v.nbytes) for d, v in pairs)


def _source_cache_put(key, entry, limit: int):
    new_bytes = _source_bytes(entry)
    if limit <= 0 or new_bytes > limit:
        return
    total = sum(_source_bytes(e) for e in list(_SOURCE_ARRAYS.values()))
    while _SOURCE_ARRAYS and total + new_bytes > limit:
        coldest = min(_SOURCE_LRU, key=_SOURCE_LRU.get)
        total -= _source_bytes(_SOURCE_ARRAYS[coldest])
        del _SOURCE_ARRAYS[coldest]
        del _SOURCE_LRU[coldest]
    _SOURCE_ARRAYS[key] = entry
    _SOURCE_TICK[0] += 1
    _SOURCE_LRU[key] = _SOURCE_TICK[0]


def _source_cache_key(src, replicated: bool, n_dev: int, frag_fields):
    from ..exec.basic import InMemoryScanExec
    if not isinstance(src, InMemoryScanExec) or len(src.tables) != 1:
        return None
    t = src.tables[0]
    tid = id(t)
    if _SOURCE_PIN.get(tid) is not t:
        try:
            _SOURCE_PIN[tid] = t
        except TypeError:
            return None
        _source_evict(tid)          # stale entries under a reused id
        weakref.finalize(t, _source_evict, tid)
    sig = tuple((f.name, f.phys.name, f.dict_id is not None,
                 f.order_required)
                for f in frag_fields)
    return (tid, replicated, n_dev, sig)


# ---------------------------------------------------------------------------
# fragment IR
# ---------------------------------------------------------------------------

class _Field:
    """Physical field riding the mesh: logical dtype + device dtype
    (+ dictionary id for code-carried strings). ``order_required``
    (set during lowering when the field feeds an ORDER-sensitive op)
    forces the sorted-dictionary encode — the hash fallback keeps only
    equality."""

    __slots__ = ("name", "logical", "phys", "dict_id", "order_required")

    def __init__(self, name: str, logical: DataType, phys: DataType,
                 dict_id: Optional[int] = None):
        self.name = name
        self.logical = logical
        self.phys = phys
        self.dict_id = dict_id
        self.order_required = False


def _phys_schema(fields: Sequence[_Field]) -> Schema:
    return Schema([StructField(f.name, f.phys, True) for f in fields])


class _Frag:
    fields: List[_Field]
    replicated: bool = False

    def signature(self) -> str:
        raise NotImplementedError

    def emit(self, env) -> "_Rel":
        raise NotImplementedError


class _Rel:
    """Traced per-device relation inside the SPMD program."""

    __slots__ = ("pairs", "count", "padded", "keep")

    def __init__(self, pairs, count, padded: int, keep=None):
        self.pairs = pairs          # [(data, validity), ...]
        self.count = count          # traced scalar (rows live)
        self.padded = padded        # static per-device length
        self.keep = keep            # optional bool[padded] live mask

    def compacted(self, env):
        """Resolve a pending filter mask into front-packed rows."""
        if self.keep is None:
            return self
        from .collective import _compact_rows
        comp, cnt = _compact_rows(self.pairs, self.keep, self.padded)
        return _Rel(comp, cnt, self.padded)

    def live_mask(self, env):
        import jax.numpy as jnp
        base = jnp.arange(self.padded, dtype=jnp.int32) < self.count
        return base if self.keep is None else jnp.logical_and(base,
                                                              self.keep)


def _probe_low_cardinality(exec_node, name: str,
                           sample: int = 8192) -> bool:
    """Plan-time sample probe: True when the column looks low-cardinality
    (sorted-dictionary territory — int32 codes suffice). Conservative:
    anything unprobable is treated as potentially high-cardinality."""
    from ..exec.basic import InMemoryScanExec
    if not isinstance(exec_node, InMemoryScanExec) or not exec_node.tables:
        return False
    try:
        import pyarrow as pa
        t = exec_node.tables[0]
        n = t.num_rows
        # head + middle + tail slices: value-clustered data (logs sorted
        # by key) would fool a head-only sample into the int32/sorted
        # path and reintroduce the driver string sort the cap prevents
        k = max(sample // 3, 1)
        if n <= 3 * k:
            # small table: probe it whole — overlapping head/middle/tail
            # slices would triple-count rows and misclassify all-distinct
            # columns as low-cardinality
            col = _one_chunk(t.column(name).slice(0, n))
        else:
            parts = [_one_chunk(t.column(name).slice(off, k))
                     for off in (0, (n - k) // 2, n - k)]
            col = pa.concat_arrays(parts)
        de = col.dictionary_encode()
        return len(de.dictionary) <= max(col.length() // 2, 1)
    except Exception:
        return False


class _SourceFrag(_Frag):
    """A host-executed subtree whose collected result is sharded (or
    replicated, for broadcast build sides) onto the mesh."""

    def __init__(self, exec_node, index: int, replicated: bool,
                 planner: "_Planner"):
        self.exec_node = exec_node
        self.index = index
        self.replicated = replicated
        self.fields = []
        for f in exec_node.output_schema().fields:
            if f.dtype == STRING:
                # plan-time cardinality probe picks the code width:
                # int32 for low-cardinality columns (half the HBM and
                # exchange traffic), int64 where the hash fallback may
                # be needed at scale
                phys = (INT32 if _probe_low_cardinality(exec_node, f.name)
                        else INT64)
                fld = _Field(f.name, STRING, phys, planner.new_dict())
                planner.dict_fields[fld.dict_id] = fld
                self.fields.append(fld)
            else:
                self.fields.append(_Field(f.name, f.dtype, f.dtype))

    def signature(self) -> str:
        kinds = ",".join(f"{f.name}:{f.phys.name}" for f in self.fields)
        return f"src{self.index}[{int(self.replicated)};{kinds}]"

    def emit(self, env) -> _Rel:
        pairs, count, padded = env.source(self.index)
        return _Rel(pairs, count, padded)


class _LocalFrag(_Frag):
    """Device-local filter/project stages — no communication."""

    def __init__(self, child: _Frag, stages: List[tuple],
                 fields: List[_Field]):
        self.child = child
        self.stages = stages        # ("filter", cond) | ("project", exprs)
        self.fields = fields
        self.replicated = child.replicated

    def signature(self) -> str:
        ss = []
        for st in self.stages:
            if st[0] == "filter":
                ss.append(f"F({st[1].key()})")
            else:
                ss.append("P(" + ",".join(e.key() for e in st[1]) + ")")
        return f"local[{';'.join(ss)}]({self.child.signature()})"

    def emit(self, env) -> _Rel:
        import jax.numpy as jnp
        from ..exprs.base import DVal, EvalContext
        rel = self.child.emit(env)
        schema = _phys_schema(self.child.fields)
        dvals = [DVal(d, v, f.phys)
                 for (d, v), f in zip(rel.pairs, self.child.fields)]
        ctx = EvalContext(schema, dvals, rel.count, rel.padded)
        keep = rel.live_mask(env)
        fields = self.child.fields
        for st in self.stages:
            if st[0] == "filter":
                c = st[1].eval_device(ctx)
                keep = jnp.logical_and(keep,
                                       jnp.logical_and(c.data, c.validity))
            else:
                exprs = st[1]
                outs = [e.eval_device(ctx) for e in exprs]
                fields = st[2]
                schema = _phys_schema(fields)
                ctx = EvalContext(schema, outs, rel.count, rel.padded)
        pairs = [(dv.data, dv.validity) for dv in ctx.columns]
        return _Rel(pairs, rel.count, rel.padded, keep)


def _key_hash_rel(env, rel: _Rel, fields, key_exprs, key_np):
    import jax.numpy as jnp
    from ..exprs.base import DVal, EvalContext
    from .collective import _col_hash_u32, _mix32
    schema = _phys_schema(fields)
    dvals = [DVal(d, v, f.phys)
             for (d, v), f in zip(rel.pairs, fields)]
    ctx = EvalContext(schema, dvals, rel.count, rel.padded)
    h = jnp.full(rel.padded, jnp.uint32(42))
    for i, e in enumerate(key_exprs):
        k = e.eval_device(ctx)
        npdt = key_np[i] if key_np is not None else k.data.dtype
        kk = DVal(k.data.astype(npdt), k.validity, k.dtype)
        h = _mix32(h * jnp.uint32(31) + _col_hash_u32(kk))
    return h


def _route_rel(env, rel: _Rel, fields, key_exprs, key_np, bound_key):
    """Hash-route live rows to their key-owner device with one
    all_to_all (the exchange shared by routed joins, aggs, and windows —
    ref GpuShuffleExchangeExecBase.prepareBatchShuffleDependency:277)."""
    import jax
    import jax.numpy as jnp
    from .collective import _compact_rows, _route_to_buffers
    n_dev = env.n_dev
    rel = rel.compacted(env)
    if n_dev == 1:
        return rel
    P_ = rel.padded
    h = _key_hash_rel(env, rel, fields, key_exprs, key_np)
    live = rel.live_mask(env)
    pid = jnp.where(live, (h % jnp.uint32(n_dev)).astype(jnp.int32),
                    jnp.int32(n_dev))
    flat = list(rel.pairs) + [(jnp.ones(P_, jnp.int8), live)]
    bufs = _route_to_buffers(flat, pid, P_, n_dev)
    recv = []
    for d, v in bufs:
        rd = jax.lax.all_to_all(d, env.axis, 0, 0, tiled=False)
        rv = jax.lax.all_to_all(v, env.axis, 0, 0, tiled=False)
        recv.append((rd.reshape(n_dev * P_), rv.reshape(n_dev * P_)))
    live_recv = recv[-1][1]
    comp, cnt = _compact_rows(recv[:-1], live_recv, n_dev * P_)
    # received rows are speculatively re-bounded (hash balance makes
    # ~P_ the expectation; worst case n_dev*P_) — validated at the sink
    rb = min(env.bound(bound_key,
                       default=min(n_dev * P_, _bucket(2 * P_))),
             n_dev * P_)
    env.check(cnt, rb)
    comp = [(d[:rb], v[:rb]) for d, v in comp]
    return _Rel(comp, cnt, rb)


class _JoinFrag(_Frag):
    """Equi-join. ``routed``: both sides hash-route rows to key owners with
    one all_to_all each, then each device joins its co-partitioned slice
    (the UCX shuffled-join analog). Non-routed (broadcast): the build side
    is replicated, each device probes its local shard — no collective
    (GpuBroadcastHashJoinExecBase analog)."""

    def __init__(self, frag_id: int, left: _Frag, right: _Frag,
                 lkeys, rkeys, join_type: str, broadcast_build: bool,
                 condition=None):
        self.frag_id = frag_id
        self.left = left
        self.right = right
        self.lkeys = list(lkeys)
        self.rkeys = list(rkeys)
        self.join_type = join_type
        self.broadcast_build = broadcast_build
        #: residual non-equi condition (inner joins only: there it is
        #: exactly a post-join filter — ref GpuHashJoin compiled AST
        #: conditions)
        self.condition = condition
        self.fields = list(left.fields) + list(right.fields)
        self.replicated = left.replicated and right.replicated

    def signature(self) -> str:
        lk = ",".join(e.key() for e in self.lkeys)
        rk = ",".join(e.key() for e in self.rkeys)
        cond = self.condition.key() if self.condition is not None else ""
        return (f"join{self.frag_id}[{self.join_type};{int(self.broadcast_build)};"
                f"{lk};{rk};{cond}]({self.left.signature()},"
                f"{self.right.signature()})")

    def emit(self, env) -> _Rel:
        import jax.numpy as jnp
        from ..exec.joins import _build_count_kernel, _gather_index_kernel
        lrel = self.left.emit(env)
        rrel = self.right.emit(env)
        lschema = _phys_schema(self.left.fields)
        rschema = _phys_schema(self.right.fields)
        key_np = [np.promote_types(lk.data_type(lschema).np_dtype,
                                   rk.data_type(rschema).np_dtype)
                  for lk, rk in zip(self.lkeys, self.rkeys)]
        if self.broadcast_build or env.n_dev == 1 or self.replicated:
            lrel = lrel.compacted(env)
            rrel = rrel.compacted(env)
        else:
            lrel = _route_rel(env, lrel, self.left.fields, self.lkeys,
                              key_np, ("recv", self.frag_id, False))
            rrel = _route_rel(env, rrel, self.right.fields, self.rkeys,
                              key_np, ("recv", self.frag_id, True))
        count_k = _build_count_kernel(self.lkeys, self.rkeys,
                                      lschema, rschema, self.join_type)
        (s_orig, cnt_l, cnt_r, start_l, start_r, _pairs, offsets, total,
         _ng) = count_k(lrel.pairs, rrel.pairs, lrel.count, rrel.count,
                        lrel.padded, rrel.padded)
        out = env.bound(("join", self.frag_id),
                        default=_bucket(env.conf_out_factor
                                        * max(lrel.padded, rrel.padded)))
        env.check(total, out)
        nullable_l = self.join_type in ("right", "full")
        nullable_r = self.join_type in ("left", "full")
        semi_like = self.join_type in ("leftsemi", "leftanti")
        cfg = jnp.array([nullable_l, nullable_r, semi_like], dtype=jnp.int32)
        l_row, r_row = _gather_index_kernel(
            s_orig, cnt_l, cnt_r, start_l, start_r, offsets, cfg, out)
        out_live = jnp.arange(out, dtype=jnp.int64) < total
        pairs = []
        for d, v in lrel.pairs:
            idx = jnp.clip(l_row, 0, None)
            pairs.append((jnp.take(d, idx, mode="clip"),
                          jnp.logical_and(
                              jnp.take(v, idx, mode="clip"),
                              jnp.logical_and(out_live, l_row >= 0))))
        if semi_like:
            return _Rel(pairs, total, out)
        for d, v in rrel.pairs:
            idx = jnp.clip(r_row, 0, None)
            pairs.append((jnp.take(d, idx, mode="clip"),
                          jnp.logical_and(
                              jnp.take(v, idx, mode="clip"),
                              jnp.logical_and(out_live, r_row >= 0))))
        if self.condition is None:
            return _Rel(pairs, total, out)
        # inner-join residual condition == post-join filter: evaluate
        # over the gathered pair columns, pending rows carry a keep mask
        from ..exprs.base import DVal, EvalContext
        schema = _phys_schema(self.fields)
        dvals = [DVal(d, v, f.phys)
                 for (d, v), f in zip(pairs, self.fields)]
        ctx = EvalContext(schema, dvals, total, out)
        c = self.condition.eval_device(ctx)
        # seed with liveness: a condition whose validity is constant-true
        # (e.g. null-safe equality) must not resurrect padding rows
        keep = jnp.logical_and(jnp.logical_and(c.data, c.validity),
                               out_live)
        return _Rel(pairs, total, out, keep)


class _WindowFrag(_Frag):
    """Window functions on the mesh: rows hash-route to the device owning
    their PARTITION (one all_to_all), then each device runs the engine's
    window kernel over its complete partitions — the distributed analog of
    window/GpuWindowExec.scala:146 downstream of a hash exchange."""

    def __init__(self, frag_id: int, child: _Frag, window_exprs,
                 fields: List[_Field]):
        self.frag_id = frag_id
        self.child = child
        self.window_exprs = list(window_exprs)
        self.fields = fields
        self.replicated = child.replicated
        self._kern = None

    def signature(self) -> str:
        ws = ",".join(f"{type(e).__name__}|{n}"
                      for e, _s, n in self.window_exprs)
        return f"win{self.frag_id}[{ws}]({self.child.signature()})"

    def emit(self, env) -> _Rel:
        import jax.numpy as jnp
        from ..exec.window import _build_window_kernel
        rel = self.child.emit(env)
        part_keys = []
        for _fn, spec, _n in self.window_exprs:
            part_keys = list(spec.partition_by)
            break
        if env.n_dev == 1 or self.replicated:
            rel = rel.compacted(env)
        else:
            rel = _route_rel(env, rel, self.child.fields, part_keys,
                             None, ("win", self.frag_id))
        if self._kern is None:
            self._kern = _build_window_kernel(
                self.window_exprs, _phys_schema(self.child.fields))
        cols = [(d, v) for d, v in rel.pairs]
        outs = self._kern(cols, rel.count.astype(jnp.int32), rel.padded)
        pairs = list(rel.pairs) + [(d, v) for d, v in outs]
        return _Rel(pairs, rel.count, rel.padded)


class _AggFrag(_Frag):
    """Grouped/global aggregation: local first pass, groups hash-routed to
    owners via all_to_all, merge pass, finalize — the distributed 3-pass
    pipeline (GpuAggregateExec.scala:718 + exchange), sharing
    segmented_groupby with the single-chip exec so distribution cannot
    change results."""

    def __init__(self, frag_id: int, child: _Frag, groupings, aggs,
                 fields: List[_Field]):
        self.frag_id = frag_id
        self.child = child
        self.groupings = list(groupings)
        self.aggs = list(aggs)
        self.fields = fields
        self.replicated = child.replicated

    def signature(self) -> str:
        g = ",".join(e.key() for e in self.groupings)
        a = ",".join(a.key() for a in self.aggs)
        return (f"agg{self.frag_id}[{g};{a}]({self.child.signature()})")

    def emit(self, env) -> _Rel:
        import jax
        import jax.numpy as jnp
        from ..exec.groupby_core import segmented_groupby
        from ..exprs.base import DVal, EvalContext
        from .collective import (_col_hash_u32, _compact_rows, _mix32,
                                 _route_to_buffers)
        rel = self.child.emit(env)
        schema = _phys_schema(self.child.fields)
        dvals = [DVal(d, v, f.phys)
                 for (d, v), f in zip(rel.pairs, self.child.fields)]
        ctx = EvalContext(schema, dvals, rel.count, rel.padded)
        keys = [e.eval_device(ctx) for e in self.groupings]
        vals = [[e.eval_device(ctx) for e in a.input_exprs()]
                for a in self.aggs]
        key_outs, partial_outs, n_groups = segmented_groupby(
            keys, vals, self.aggs, "update", rel.count, rel.padded,
            row_mask=rel.live_mask(env))
        n_dev = env.n_dev
        ptypes = []
        for a in self.aggs:
            ptypes.extend(a.partial_types(schema))
        if n_dev > 1 and not self.replicated:
            # First/Last carry within-SHARD row positions; the merge after
            # the exchange breaks ties by position, so positions must be
            # GLOBAL (shard index * padded — sources shard row-contiguous,
            # so shard order IS row order). Without this, 88% of groups
            # returned another shard's first (caught by the r4 drive).
            from ..exprs.aggregates import First, Last
            base = (jax.lax.axis_index(env.axis).astype(jnp.int64)
                    * jnp.int64(rel.padded))
            ord_ = 0
            adj = list(partial_outs)
            for a in self.aggs:
                n_p = len(a.partial_types(schema))
                if isinstance(a, (First, Last)):
                    _vd, vv = adj[ord_]
                    pd_, pv = adj[ord_ + 1]
                    adj[ord_ + 1] = (jnp.where(vv, pd_ + base, pd_), pv)
                ord_ += n_p
            partial_outs = adj
        if n_dev == 1 or self.replicated:
            m_key_outs, m_partial_outs, m_groups = key_outs, partial_outs, \
                n_groups
            padded = rel.padded
        else:
            # slice first-pass groups to the speculative exchange bound
            gb = min(env.bound(("agg", self.frag_id),
                               default=min(rel.padded,
                                           env.conf_max_groups)),
                     rel.padded)
            env.check(n_groups, gb)
            s_keys = [(d[:gb], v[:gb]) for d, v in key_outs]
            s_parts = [(d[:gb], v[:gb]) for d, v in partial_outs]
            glive = jnp.arange(gb, dtype=jnp.int32) < n_groups
            if self.groupings:
                h = jnp.full(gb, jnp.uint32(42))
                for (kd, kv), k in zip(s_keys, keys):
                    h = _mix32(h * jnp.uint32(31)
                               + _col_hash_u32(DVal(kd, kv, k.dtype)))
                pid = jnp.where(glive,
                                (h % jnp.uint32(n_dev)).astype(jnp.int32),
                                jnp.int32(n_dev))
            else:
                pid = jnp.where(glive, 0, n_dev)
            flat = list(s_keys) + list(s_parts) + \
                [(jnp.ones(gb, jnp.int8), glive)]
            bufs = _route_to_buffers(flat, pid, gb, n_dev)
            recv = []
            for d, v in bufs:
                rd = jax.lax.all_to_all(d, env.axis, 0, 0, tiled=False)
                rv = jax.lax.all_to_all(v, env.axis, 0, 0, tiled=False)
                recv.append((rd.reshape(n_dev * gb),
                             rv.reshape(n_dev * gb)))
            live_recv = recv[-1][1]
            comp, cnt = _compact_rows(recv[:-1], live_recv, n_dev * gb)
            rkeys = [DVal(comp[i][0], comp[i][1], k.dtype)
                     for i, k in enumerate(keys)]
            rvals = []
            ai = len(keys)
            for a in self.aggs:
                n_p = len(a.partial_types(schema))
                rvals.append([DVal(comp[ai + j][0], comp[ai + j][1],
                                   ptypes[ai - len(keys) + j])
                              for j in range(n_p)])
                ai += n_p
            m_key_outs, m_partial_outs, m_groups = segmented_groupby(
                rkeys, rvals, self.aggs, "merge", cnt, n_dev * gb)
            if not self.groupings:
                m_groups = jnp.where(jax.lax.axis_index(env.axis) == 0,
                                     m_groups, 0)
            padded = n_dev * gb
        glive2 = jnp.arange(padded, dtype=jnp.int32) < m_groups
        pairs = []
        for d, v in m_key_outs:
            pairs.append((d, jnp.logical_and(v, glive2)))
        ai = 0
        for a in self.aggs:
            n_p = len(a.partial_types(schema))
            parts = [DVal(m_partial_outs[ai + j][0],
                          m_partial_outs[ai + j][1], ptypes[ai + j])
                     for j in range(n_p)]
            ai += n_p
            f = a.finalize(parts)
            pairs.append((f.data, jnp.logical_and(f.validity, glive2)))
        return _Rel(pairs, m_groups, padded)


def _bucket(n: int) -> int:
    from ..columnar.bucketing import bucket_for
    return bucket_for(max(int(n), 1))


# ---------------------------------------------------------------------------
# lowering: physical exec tree -> fragment IR
# ---------------------------------------------------------------------------

class _NotLowerable(Exception):
    pass


class _Planner:
    def __init__(self, conf: TpuConf, fused_mode: bool = False):
        self.conf = conf
        #: True for single-chip fused lowering (stricter gates apply:
        #: features living only in the operator path must not be lost)
        self.fused_mode = fused_mode
        self.sources: List[Tuple[object, bool]] = []   # (exec, replicated)
        self.n_dicts = 0
        self.n_frags = 0
        self.has_comm = False
        self.has_join = False
        #: dict_id -> the SOURCE _Field, so order-sensitive consumers
        #: can force the sorted-dictionary encode on it
        self.dict_fields: Dict[int, _Field] = {}
        #: bound-check key -> logical plan signature: the fragment's bound
        #: validation fetches measured sizes anyway — feed them to the
        #: cost model's _RUNTIME_ROWS so re-planning sees real join
        #: outputs even when the whole query ran fused
        self.key_sigs: Dict[Tuple, str] = {}

    def new_dict(self) -> int:
        self.n_dicts += 1
        return self.n_dicts - 1

    def frag_id(self) -> int:
        self.n_frags += 1
        return self.n_frags - 1

    def source(self, exec_node, replicated: bool) -> _SourceFrag:
        for f in exec_node.output_schema().fields:
            if f.dtype != STRING and not f.dtype.device_backed:
                # nested/binary columns have no fragment encoding (list
                # rectangles don't ride the exchange yet) — reject the
                # fragment; the operator pipeline handles these
                raise _NotLowerable(
                    f"source column {f.name}: {f.dtype.name}")
        idx = len(self.sources)
        self.sources.append((exec_node, replicated))
        return _SourceFrag(exec_node, idx, replicated, self)

    # -- helpers -----------------------------------------------------------
    def _expr_ok_f(self, e, fields: Sequence[_Field]) -> bool:
        """Device-supported and independent of dict-coded (string) cols."""
        from ..types import ArrayType
        schema = Schema([StructField(f.name, f.logical, True)
                         for f in fields])
        if e.fully_device_supported(schema) is not None:
            return False
        # list columns (rectangular layout) don't ride fragments yet:
        # their lanes would need the exchange/compaction to be W-aware
        if isinstance(e.data_type(schema), ArrayType) or any(
                isinstance(f.logical, ArrayType)
                for f in fields if f.name in set(e.references())):
            return False
        dict_names = {f.name for f in fields if f.dict_id is not None}
        return not (set(e.references()) & dict_names)

    def _expr_ok(self, e, frag: _Frag) -> bool:
        return self._expr_ok_f(e, frag.fields)

    def _passthrough_f(self, e, fields: Sequence[_Field]) \
            -> Optional[_Field]:
        """ColumnRef / Alias(ColumnRef) -> the referenced field."""
        from ..exprs.base import Alias, ColumnRef
        inner = e.children[0] if isinstance(e, Alias) else e
        if not isinstance(inner, ColumnRef):
            return None
        for f in fields:
            if f.name == inner.name:
                return f
        return None

    def _passthrough_field(self, e, frag: _Frag) -> Optional[_Field]:
        return self._passthrough_f(e, frag.fields)

    # -- node lowering -----------------------------------------------------
    def lower(self, node, replicated: bool = False) -> _Frag:
        from ..exec import basic as B
        from ..exec.aggregate import TpuHashAggregateExec
        from ..exec.joins import TpuBroadcastHashJoinExec, TpuHashJoinExec
        from ..shuffle.broadcast import BroadcastExchangeExec
        from ..shuffle.exchange import ShuffleExchangeExec

        if isinstance(node, (ShuffleExchangeExec, B.CoalesceBatchesExec)):
            # the SPMD program IS the exchange: shuffles lower to the
            # routing inside joins/aggs; a bare repartition is an identity
            # on the mesh, and so is a coalesce (a fragment is a
            # single-batch program)
            return self.lower(node.children[0], replicated)

        if isinstance(node, B.TpuFilterExec):
            if node.condition.fully_device_supported(
                    node.children[0].output_schema()) is not None:
                # a string predicate evaluated over the dictionary
                # (exprs/compiler.py build_dict_filter) has no fragment
                # form: the operator runs ahead of the fragment, as a
                # source, where its host twin used to
                return self.source(node, replicated)
            child = self.lower(node.children[0], replicated)
            if not self._expr_ok(node.condition, child):
                raise _NotLowerable(f"filter {node.condition.name_hint}")
            return _LocalFrag(child, [("filter", node.condition)],
                              child.fields)

        if isinstance(node, B.TpuProjectExec):
            child = self.lower(node.children[0], replicated)
            out_fields = []
            for e, f in zip(node.exprs, node.output_schema().fields):
                pf = self._passthrough_field(e, child)
                if pf is not None:
                    out_fields.append(_Field(f.name, pf.logical, pf.phys,
                                             pf.dict_id))
                elif self._expr_ok(e, child):
                    out_fields.append(_Field(f.name, f.dtype, f.dtype))
                else:
                    raise _NotLowerable(f"project {e.name_hint}")
            return _LocalFrag(child, [("project", list(node.exprs),
                                       out_fields)], out_fields)

        if isinstance(node, TpuBroadcastHashJoinExec):
            if node.join_type not in ("inner", "left", "right", "full",
                                      "leftsemi", "leftanti"):
                raise _NotLowerable(f"join type {node.join_type}")
            lc, rc = node.children
            if isinstance(rc, BroadcastExchangeExec):
                left = self.lower(lc, replicated)
                right = self.lower(rc.children[0], True)
            elif isinstance(lc, BroadcastExchangeExec):
                left = self.lower(lc.children[0], True)
                right = self.lower(rc, replicated)
            else:
                left = self.lower(lc, replicated)
                right = self.lower(rc, True)
            return self._make_join(node, left, right, broadcast=True)

        if isinstance(node, TpuHashJoinExec):
            left = self.lower(node.children[0], replicated)
            right = self.lower(node.children[1], replicated)
            return self._make_join(node, left, right, broadcast=False)

        if isinstance(node, TpuHashAggregateExec):
            return self._lower_agg(node, replicated)

        from ..exec.window import TpuWindowExec
        if isinstance(node, TpuWindowExec):
            return self._lower_window(node, replicated)

        # anything else becomes a host-executed source (scans always do)
        return self.source(node, replicated)

    def _lower_window(self, node, replicated: bool) -> _Frag:
        from ..exprs.window_fns import (DenseRank, Lag, Lead, NthValue,
                                        NTile, PercentRank, Rank,
                                        RowNumber)
        from ..exprs.aggregates import AggregateExpression
        child = self.lower(node.children[0], replicated)
        part_sig = None
        for fn, spec, _name in node.window_exprs:
            if not isinstance(fn, (RowNumber, Rank, DenseRank, NTile,
                                   PercentRank, NthValue, Lag, Lead,
                                   AggregateExpression)):
                raise _NotLowerable(f"window fn {type(fn).__name__}")
            # all exprs must share ONE partitioning: the routing
            # co-locates partitions for exactly one key set
            sig = tuple(k.key() for k in spec.partition_by)
            if part_sig is None:
                part_sig = sig
            elif sig != part_sig:
                raise _NotLowerable("window exprs with mixed partitioning")
            for k in spec.partition_by:
                pf = self._passthrough_field(k, child)
                if pf is None and not self._expr_ok(k, child):
                    raise _NotLowerable("window partition key")
            for o in spec.order_by:
                pf = self._passthrough_field(o.expr, child)
                if pf is None and not self._expr_ok(o.expr, child):
                    raise _NotLowerable("window order key")
                if pf is not None and pf.dict_id is not None:
                    # ordering by a string: only a SORTED dictionary's
                    # codes order like the strings
                    src = self.dict_fields.get(pf.dict_id)
                    if src is not None:
                        src.order_required = True
            fchild = getattr(fn, "child", None)
            if fchild is not None and not self._expr_ok(fchild, child):
                raise _NotLowerable("window value expression")
        cs = node.children[0].output_schema()
        out_fields = list(child.fields)
        for fn, _spec, name in node.window_exprs:
            dt = fn.data_type(cs)
            out_fields.append(_Field(name, dt, dt))
        self.has_comm = True
        return _WindowFrag(self.frag_id(), child, node.window_exprs,
                           out_fields)

    def _make_join(self, node, left: _Frag, right: _Frag,
                   broadcast: bool) -> _Frag:
        if node.join_type not in ("inner", "left", "right", "full",
                                  "leftsemi", "leftanti"):
            raise _NotLowerable(f"join type {node.join_type}")
        condition = getattr(node, "condition", None)
        if condition is not None:
            # only for INNER joins is the ON-condition equivalent to a
            # post-join filter; outer joins would change match semantics
            if node.join_type != "inner":
                raise _NotLowerable(
                    f"join condition on {node.join_type} join")
            if not self._expr_ok_f(condition,
                                   list(left.fields) + list(right.fields)):
                raise _NotLowerable("join condition not device-evaluable")
        from ..config import JOIN_BLOOM_FILTER
        if self.fused_mode and self.conf.get(JOIN_BLOOM_FILTER):
            # the runtime bloom filter is an operator-path optimization;
            # single-chip fusion must not silently drop it (on a REAL
            # mesh the collective exchange replaces it wholesale, so
            # multi-device lowering proceeds regardless)
            raise _NotLowerable("bloom-filtered joins keep the operator "
                               "pipeline")
        for k in node.left_keys:
            if not self._expr_ok(k, left):
                raise _NotLowerable(f"join key {k.name_hint}")
        for k in node.right_keys:
            if not self._expr_ok(k, right):
                raise _NotLowerable(f"join key {k.name_hint}")
        if broadcast and not right.replicated and not left.replicated:
            raise _NotLowerable("broadcast side not replicable")
        # a replicated side must never be on the EMITTING side of the join
        # while the other side is sharded: every device would emit its
        # unmatched/matched replicated rows independently (N-fold dupes)
        if right.replicated and not left.replicated \
                and node.join_type in ("right", "full"):
            raise _NotLowerable(
                f"{node.join_type} join emits replicated build rows")
        if left.replicated and not right.replicated \
                and node.join_type in ("left", "full", "leftsemi",
                                       "leftanti"):
            raise _NotLowerable(
                f"{node.join_type} join emits replicated probe rows")
        # any join benefits from the mesh: routed joins exchange, broadcast
        # joins probe in parallel across shards
        self.has_comm = True
        self.has_join = True
        frag = _JoinFrag(self.frag_id(), left, right, node.left_keys,
                         node.right_keys, node.join_type, broadcast,
                         condition=condition)
        sig = getattr(node, "plan_sig", None)
        if sig is not None:
            self.key_sigs[("join", frag.frag_id)] = sig
        # semi/anti joins emit probe-side fields only
        if node.join_type in ("leftsemi", "leftanti"):
            frag.fields = list(left.fields)
        return frag

    def _lower_agg(self, node, replicated: bool) -> _Frag:
        child = self.lower(node.children[0], replicated)
        # folded pre-stages (filter/project fused below the agg) re-lower
        # as explicit local stages so the SPMD program keeps the fusion
        if node.pre_stages:
            stages = []
            cur_fields = child.fields
            for st in node.pre_stages:
                if st[0] == "filter":
                    if not self._expr_ok_f(st[1], cur_fields):
                        raise _NotLowerable("agg pre-filter")
                    stages.append(("filter", st[1]))
                else:
                    out_fields = []
                    for e, f in zip(st[1], st[2].fields):
                        pf = self._passthrough_f(e, cur_fields)
                        if pf is not None:
                            out_fields.append(_Field(f.name, pf.logical,
                                                     pf.phys, pf.dict_id))
                        elif self._expr_ok_f(e, cur_fields):
                            out_fields.append(_Field(f.name, f.dtype,
                                                     f.dtype))
                        else:
                            raise _NotLowerable("agg pre-project")
                    stages.append(("project", list(st[1]), out_fields))
                    cur_fields = out_fields
            child = _LocalFrag(child, stages, cur_fields)
        out_fields = []
        groupings = []
        for g, f in zip(node.groupings, node._schema.fields):
            pf = self._passthrough_field(g, child)
            if pf is not None and pf.dict_id is not None:
                out_fields.append(_Field(f.name, STRING, pf.phys, pf.dict_id))
                from ..exprs.base import ColumnRef
                groupings.append(ColumnRef(pf.name))
                continue
            if not self._expr_ok(g, child):
                raise _NotLowerable(f"grouping {g.name_hint}")
            out_fields.append(_Field(f.name, f.dtype, f.dtype))
            groupings.append(g)
        schema = _phys_schema(child.fields)
        for a, f in zip(node.aggs, node._schema.fields[len(groupings):]):
            if not hasattr(a, "update") or a.distinct:
                raise _NotLowerable(f"aggregate {a.name_hint}")
            for e in a.input_exprs():
                if not self._expr_ok(e, child):
                    raise _NotLowerable(f"aggregate input {e.name_hint}")
            try:
                a.partial_types(schema)
            except Exception as exc:
                raise _NotLowerable(f"aggregate {a.name_hint}: {exc}")
            out_fields.append(_Field(f.name, f.dtype, f.dtype))
        self.has_comm = True
        return _AggFrag(self.frag_id(), child, groupings, node.aggs,
                        out_fields)


# ---------------------------------------------------------------------------
# the distributed exec
# ---------------------------------------------------------------------------

class _Env:
    """Per-trace environment handed to frag.emit: source arrays, bounds,
    and the overflow-check accumulator."""

    def __init__(self, mesh, axis: str, conf: TpuConf,
                 source_layout, bounds: Dict, sig: str = ""):
        self.mesh = mesh
        self.axis = axis
        self.n_dev = int(np.prod([mesh.shape[a] for a in mesh.axis_names]))
        self.conf_max_groups = int(conf.get(DISTRIBUTED_MAX_GROUPS))
        self.conf_out_factor = int(conf.get(DISTRIBUTED_OUT_FACTOR))
        self._layout = source_layout    # idx -> (padded, n_fields)
        self._bounds = bounds           # key -> int (speculative bounds)
        self.sig = sig                  # fragment signature for stats
        self._inputs = None             # set per trace
        self.checks: List[Tuple] = []   # (traced count, static bound)

    def bound(self, key, default: int) -> int:
        b = self._bounds.get(key)
        if b is None:
            # learned cross-query statistic first (the fragment analog
            # of the joins' _TOTAL_STATS speculative sizing). The stat is
            # keyed by the bucketed DEFAULT too, so the same query shape
            # at a different input scale keeps its input-proportional
            # default instead of a stale too-small bound.
            b = _FRAGMENT_STATS.get(
                (self.sig, self.n_dev, key, _bucket(default)))
            if b is None:
                b = int(default)
            self._bounds[key] = b
        # record on EVERY call: retries rebuild the env with pre-filled
        # bounds, and the success-time stats write needs the default
        self._defaults = getattr(self, "_defaults", {})
        self._defaults[key] = int(default)
        return b

    def check(self, count, bound: int):
        self.checks.append((count, bound))

    def source(self, idx: int):
        padded, nf, off = self._layout[idx]
        nrows = self._inputs[off]
        pairs = [(self._inputs[off + 1 + 2 * i],
                  self._inputs[off + 2 + 2 * i]) for i in range(nf)]
        import jax.numpy as jnp
        return pairs, nrows[0], padded


class _BoundOverflow(Exception):
    def __init__(self, violations):
        self.violations = violations


def _one_chunk(col):
    import pyarrow as pa
    if isinstance(col, pa.ChunkedArray):
        return col.combine_chunks() if col.num_chunks != 1 else col.chunk(0)
    return col


def _encode_plain(col, phys):
    """Arrow column -> (data, validity) numpy pair with the same
    arrow->device casts as ColumnarBatch.from_arrow."""
    import pyarrow as pa
    import pyarrow.compute as pc
    from ..columnar.column import DeviceColumn
    arr = col
    if pa.types.is_date32(arr.type):
        arr = arr.cast(pa.int32())
    elif pa.types.is_timestamp(arr.type):
        arr = arr.cast(pa.int64())
    elif pa.types.is_decimal(arr.type):
        arr = pc.multiply_checked(
            arr.cast(pa.decimal128(38, arr.type.scale)),
            10 ** arr.type.scale).cast(pa.int64())
    mask = ~np.asarray(col.is_null())
    fill = False if pa.types.is_boolean(arr.type) else 0
    vals = arr.fill_null(fill).to_numpy(zero_copy_only=False)
    return DeviceColumn.host_prepare(vals, phys, mask=mask)


def _encode_string_global(cols, cap: int, ordered: bool,
                          code_dtype=np.int64):
    """Global string encoding across shards: ``cols`` = one Arrow
    column per shard. Returns (decode_entry, [(codes, valid)] per
    shard); decode_entry: ("sorted", uniq) | ("hashed", h_uniq, s_by_h).

    The row pass is Arrow ``dictionary_encode`` (O(n) hash table);
    everything after operates on DISTINCTS only. Low cardinality (or
    order-required fields): ONE sorted global dictionary — code order ==
    string order. Above ``cap`` (VERDICT r2 #6: a global string sort is
    a driver bottleneck at scale): codes are 64-bit hashes of the
    distinct values (pandas hash_array — stable across shards and
    processes); the decode map sorts only int64 hashes. Collisions are
    detected exactly and fall back to the sorted dictionary."""
    des, dvals, valids, idxs = [], [], [], []
    for c in cols:
        de = _one_chunk(c).dictionary_encode()
        des.append(de)
        dvals.append(np.asarray(
            de.dictionary.to_numpy(zero_copy_only=False), dtype=object))
        valids.append(~np.asarray(de.indices.is_null()))
        idxs.append(np.asarray(
            de.indices.fill_null(0).to_numpy(zero_copy_only=False),
            dtype=np.int64))

    def emit(rank_per_shard, dt):
        out = []
        for rank, idx, valid in zip(rank_per_shard, idxs, valids):
            codes = rank[idx].astype(dt) if len(rank) \
                else np.zeros(len(idx), dt)
            codes[~valid] = 0
            out.append((codes, valid))
        return out

    def sorted_path(distincts):
        uniq = np.unique(np.concatenate(distincts)) if distincts \
            else np.asarray([], dtype=object)
        if np.dtype(code_dtype).itemsize < 8 and len(uniq) >= (1 << 31):
            raise ValueError(
                "dictionary exceeds int32 code space (mis-probed "
                "cardinality); raise distributed.maxDictEntries or "
                "disable distribution for this query")
        ranks = [np.searchsorted(uniq, d).astype(np.int64)
                 for d in dvals]
        return ("sorted", uniq), emit(ranks, code_dtype)

    nonempty = [d for d in dvals if len(d)]
    bound = sum(len(d) for d in nonempty)     # distinct-count upper bound
    if ordered or bound <= cap \
            or np.dtype(code_dtype).itemsize < 8:
        # (32-bit code space cannot carry the 64-bit hash fallback —
        # the plan-time probe assigns int32 only to low-card columns)
        return sorted_path(nonempty)
    # hash path: hash only the DISTINCT values per shard
    import pandas as pd
    h_per = [pd.util.hash_array(d, categorize=False).view(np.int64)
             if len(d) else np.zeros(0, np.int64) for d in dvals]
    all_h = np.concatenate([h for h in h_per if len(h)])
    all_s = np.concatenate(nonempty)
    order = np.argsort(all_h, kind="stable")
    h_sorted, s_sorted = all_h[order], all_s[order]
    first = np.ones(len(h_sorted), bool)
    first[1:] = h_sorted[1:] != h_sorted[:-1]
    dup = ~first
    if dup.any() and (s_sorted[dup] != s_sorted[
            np.flatnonzero(dup) - 1]).any():
        # genuine 64-bit collision: correctness over speed
        return sorted_path(nonempty)
    h_uniq, s_uniq = h_sorted[first], s_sorted[first]
    if len(h_uniq) <= cap:
        # true cardinality is low: sorting <=cap distincts is cheap and
        # keeps code order == string order
        return sorted_path([s_uniq])
    return ("hashed", h_uniq, s_uniq), emit(h_per, np.int64)


class _ShardedTables:
    """Per-device pre-sharded source tables (row-group-partitioned scan):
    shard i's table goes to device i verbatim — no driver-side concat or
    re-slice."""

    def __init__(self, shards):
        self.shards = list(shards)

    def rows_per_shard(self):
        return [t.num_rows for t in self.shards]


class DistributedPipelineExec(TpuExec):
    """Physical operator executing a plan fragment as ONE SPMD program over
    the session mesh (see module docstring). Appears in explain() where the
    reference would show GpuShuffleExchangeExec-separated stages."""

    def __init__(self, root: _Frag, sources: List[Tuple[object, bool]],
                 mesh, conf: TpuConf, out_schema: Schema,
                 axis: str = "data", fallback=None):
        super().__init__([s for s, _ in sources])
        self.root = root
        self.sources = sources
        self.mesh = mesh
        self.conf = conf
        self.axis = axis
        self._schema = out_schema
        self._bounds: Dict = {}
        self.sig = root.signature()
        #: original operator subtree; runs instead when a source exceeds
        #: the shape-bucket ladder (fragments are single-batch programs)
        self.fallback = fallback
        self.n_dev = int(np.prod([mesh.shape[a] for a in mesh.axis_names]))
        #: (jitted SPMD program, its placed inputs, its raw outputs) of
        #: the last attempt — what chip_smoke.py inspects for device
        #: layout and collectives; lives only as long as this query's plan
        self.last_run = None

    def output_schema(self) -> Schema:
        return self._schema

    def describe(self) -> str:
        return (f"DistributedPipeline[n_dev={self.n_dev}, "
                f"axis={self.axis}, frag={type(self.root).__name__}]")

    # -----------------------------------------------------------------------
    def do_execute(self, ctx):
        import pyarrow as pa
        from ..columnar import ColumnarBatch
        from ..columnar.bucketing import DEFAULT_BUCKETS
        from ..exec.basic import InMemoryScanExec
        max_rows = max(DEFAULT_BUCKETS)
        if self.fallback is not None:
            # fragments are single-batch programs; oversized inputs take
            # the multi-batch operator pipeline. Scan sources expose
            # their row counts WITHOUT executing anything — check them
            # first so fallback never double-runs the sources.
            for s, _ in self.sources:
                if isinstance(s, InMemoryScanExec) and \
                        sum(t.num_rows for t in s.tables) > max_rows:
                    yield from self.fallback.execute(ctx)
                    return
        tables = []
        for s, replicated in self.sources:
            shards = None
            if not replicated:
                from ..io.parquet import ParquetScanExec
                if isinstance(s, ParquetScanExec):
                    # row-group-partitioned scan: each shard reads only
                    # its assigned groups (VERDICT r2 #3; ref
                    # GpuMultiFileReader.scala:295)
                    shards = s.collect_row_group_shards(self.n_dev)
            tables.append(_ShardedTables(shards) if shards is not None
                          else s._collect_tables(ctx))
        if self.fallback is not None and any(
                (max(t.rows_per_shard()) if isinstance(t, _ShardedTables)
                 else t.num_rows) > max_rows for t in tables):
            # non-scan source turned out oversized: the sources ran
            # twice on this rare path — documented cost of the late check
            yield from self.fallback.execute(ctx)
            return
        out = self._run(ctx, tables)
        yield ColumnarBatch.from_arrow(out)

    def _mesh_key(self):
        return (tuple(str(d) for d in np.asarray(self.mesh.devices).flat),
                tuple(self.mesh.axis_names), self.axis)

    def _resolve_bound(self, key, default: int) -> int:
        """Host-side mirror of _Env.bound()'s resolution order, used to
        test whether a cached program's embedded bounds still apply."""
        b = self._bounds.get(key)
        if b is None:
            b = _FRAGMENT_STATS.get(
                (self.sig, self.n_dev, key, _bucket(default)))
        return int(default) if b is None else int(b)

    def _lookup_program(self, layout):
        layout_t = tuple(sorted((i, p, nf)
                                for i, (p, nf, _o) in layout.items()))
        base = (self.sig, self.n_dev, self._mesh_key(), layout_t)
        for variant in _PROGRAM_CACHE.get(base, []):
            (fn, out_specs, check_keys, bounds_flat, bound_items) = variant
            if all(self._resolve_bound(k, d) == r
                   for k, d, r in bound_items):
                _PROGRAM_TICK[0] += 1
                _PROGRAM_LRU[base] = _PROGRAM_TICK[0]
                return base, variant
        return base, None

    def _run(self, ctx, tables):
        import jax
        from ..columnar.packing import unpack_streams
        # deep fragments can surface undersized bounds one layer per
        # attempt (each clamped count hides the next layer's true size)
        for attempt in range(6):
            layout, inputs, dicts = self._shard_inputs(tables)
            base_key, cached = self._lookup_program(layout)
            if cached is not None:
                # repeat query shape: skip the shard_map retrace + XLA
                # lowering entirely (measured ~5 s on the fused q3
                # fragment) — the compiled executable is called directly
                (fn, out_specs, check_keys, bounds_flat,
                 bound_items) = cached
                self._out_specs = out_specs
                self._check_keys = check_keys
                defaults = {k: d for k, d, _ in bound_items}
                for k, _d, r in bound_items:
                    self._bounds[k] = r
                env = None
            else:
                env = _Env(self.mesh, self.axis, self.conf, layout,
                           self._bounds, self.sig)
                fn = self._build_program(env)
            outs = fn(*inputs)
            self.last_run = (fn, inputs, outs)
            variant = None
            if env is not None:
                # trace happened inside the call above: snapshot the
                # program + its embedded bounds (cached below ONLY if
                # this attempt's bounds validate)
                bounds_flat = [b for _, b in env.checks]
                defaults = getattr(env, "_defaults", {})
                bound_items = [(k, defaults.get(k, 0),
                                self._bounds.get(k, defaults.get(k, 0)))
                               for k in self._check_keys
                               if k in defaults or k in self._bounds]
                variant = (fn, self._out_specs, self._check_keys,
                           bounds_flat, bound_items)
            # ONE device_get over the two packed streams (the operator
            # path's fetch_packed discipline, applied to the fragment)
            u32_all, f64_all = jax.device_get(outs)
            u32_all = np.asarray(u32_all)
            f64_all = np.asarray(f64_all)
            per_dev = [unpack_streams(u32_all[i], f64_all[i],
                                      self._out_specs)
                       for i in range(self.n_dev)]
            counts = np.asarray([int(p[0][0]) for p in per_dev])
            # per-device check values -> worst (max) over devices
            check_vals = np.stack([p[1] for p in per_dev]).max(axis=0)
            violations = [(i, int(v), b) for i, (v, b) in
                          enumerate(zip(check_vals, bounds_flat))
                          if v > b]
            if not violations:
                if variant is not None:
                    _program_cache_put(base_key, variant)
                # record observed sizes so the NEXT query of this shape
                # AND input scale starts with tight static bounds; a
                # running max avoids thrash on varying data
                key_sigs = getattr(self, "key_sigs", None) or {}
                for i, (v, b) in enumerate(zip(check_vals, bounds_flat)):
                    ck = self._check_keys[i]
                    sig = key_sigs.get(ck)
                    if sig is not None:
                        # measured fragment sizes -> the cost model, so
                        # re-planning this shape knows real join outputs
                        from ..plan.cost import record_runtime_rows
                        record_runtime_rows(sig, int(v))
                    dflt = defaults.get(ck)
                    if dflt is None:
                        continue
                    k = (self.sig, self.n_dev, ck, _bucket(dflt))
                    _FRAGMENT_STATS[k] = max(
                        _FRAGMENT_STATS.get(k, 0),
                        _bucket(max(int(v) * 3 // 2, 1)))
                return self._stitch_packed(per_dev, counts, dicts)
            # double every violated speculative bound and re-run (the
            # mesh-level SpeculativeOverflow retry)
            for i, v, b in violations:
                k = self._check_keys[i]
                self._bounds[k] = _bucket(max(2 * b, v))
            log.warning("distributed bounds overflowed (%s); retrying",
                        violations)
        raise RuntimeError("distributed pipeline failed to size its "
                           "speculative bounds after 6 attempts")

    # -----------------------------------------------------------------------
    def _shard_inputs(self, tables):
        """Arrow tables -> padded sharded/replicated device arrays.
        Returns (layout, flat_inputs, dicts). Per-source device arrays
        are cached by underlying-table identity, so repeat queries over
        the same in-memory data skip the encode + H2D entirely (the
        fragment analog of the operator scan cache)."""
        layout = {}
        flat = []
        dicts = {}
        off = 0
        for (src, replicated), table, frag_fields in zip(
                self.sources, tables, self._source_fields()):
            key = _source_cache_key(src, replicated, self.n_dev,
                                    frag_fields)
            cached = _SOURCE_ARRAYS.get(key) if key is not None else None
            if cached is not None:
                _SOURCE_TICK[0] += 1
                _SOURCE_LRU[key] = _SOURCE_TICK[0]
            else:
                cached = self._put_source(table, replicated, frag_fields)
                if key is not None:
                    _source_cache_put(key, cached,
                                      _source_cache_limit(self.conf))
            nrows, pairs_dev, pos_dicts, padded = cached
            flat.append(nrows)
            for d, v in pairs_dev:
                flat.append(d)
                flat.append(v)
            for pos, uniq in pos_dicts.items():
                dicts[frag_fields[pos].dict_id] = uniq
            layout[len(layout)] = (padded, len(pairs_dev), off)
            off += 1 + 2 * len(pairs_dev)
        return layout, flat, dicts

    def _put_source(self, table, replicated: bool, frag_fields):
        if isinstance(table, _ShardedTables):
            return self._put_source_shards(table.shards, frag_fields)
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        shard = NamedSharding(self.mesh, P(self.axis))
        repl = NamedSharding(self.mesh, P())
        n_dev = self.n_dev
        n = table.num_rows
        if replicated:
            padded = _bucket(n)
            nrows = jax.device_put(jnp.asarray(np.full(1, n, np.int32)),
                                   repl)
        else:
            per = -(-n // n_dev) if n else 1
            padded = _bucket(per)
            counts = np.asarray(
                [max(min(n - i * per, per), 0) for i in range(n_dev)],
                np.int32)
            nrows = jax.device_put(jnp.asarray(counts), shard)
        dicts: Dict = {}
        arrays = self._encode_columns(table, frag_fields, dicts)
        pos_dicts = {i: dicts[f.dict_id]
                     for i, f in enumerate(frag_fields)
                     if f.dict_id is not None}
        pairs_dev = []
        for d, v in arrays:
            if replicated:
                dp = np.zeros(padded, d.dtype)
                vp = np.zeros(padded, bool)
                dp[:n] = d
                vp[:n] = v
                pairs_dev.append((jax.device_put(jnp.asarray(dp), repl),
                                  jax.device_put(jnp.asarray(vp), repl)))
            else:
                per = -(-n // n_dev) if n else 1
                dp = np.zeros(n_dev * padded, d.dtype)
                vp = np.zeros(n_dev * padded, bool)
                for i in range(n_dev):
                    c = max(min(n - i * per, per), 0)
                    if c:
                        dp[i * padded:i * padded + c] = \
                            d[i * per:i * per + c]
                        vp[i * padded:i * padded + c] = \
                            v[i * per:i * per + c]
                pairs_dev.append((jax.device_put(jnp.asarray(dp), shard),
                                  jax.device_put(jnp.asarray(vp), shard)))
        return nrows, pairs_dev, pos_dicts, padded

    def _source_fields(self):
        out = []

        def walk(frag):
            if isinstance(frag, _SourceFrag):
                out.append((frag.index, frag.fields))
            elif isinstance(frag, _JoinFrag):
                walk(frag.left)
                walk(frag.right)
            elif isinstance(frag, (_LocalFrag, _AggFrag, _WindowFrag)):
                walk(frag.child)
        walk(self.root)
        out.sort()
        return [f for _, f in out]

    def _encode_columns(self, table, fields: List[_Field], dicts):
        """numpy (data, validity) per field; strings -> GLOBAL sorted
        dictionary codes (code order == string order on every device)."""
        cap = int(self.conf.get(DISTRIBUTED_MAX_DICT))
        arrays = []
        for f, col in zip(fields, table.columns):
            col = _one_chunk(col)
            if f.dict_id is not None:
                entry, codes = _encode_string_global(
                    [col], cap, f.order_required, f.phys.np_dtype)
                dicts[f.dict_id] = entry
                arrays.append(codes[0])
            else:
                arrays.append(_encode_plain(col, f.phys))
        return arrays

    def _put_source_shards(self, shards, frag_fields):
        """Pre-sharded (row-group-assigned) tables: shard i's rows land
        on device i directly; string dictionaries are built GLOBALLY
        across shards so codes stay comparable on every device."""
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        shard_sh = NamedSharding(self.mesh, P(self.axis))
        n_dev = self.n_dev
        assert len(shards) == n_dev, (len(shards), n_dev)
        counts = np.asarray([t.num_rows for t in shards], np.int32)
        padded = _bucket(max(int(counts.max()), 1))
        nrows = jax.device_put(jnp.asarray(counts), shard_sh)
        dicts: Dict = {}
        shard_cols: Dict[int, list] = {}   # pos -> [(d, v) per shard]
        cap = int(self.conf.get(DISTRIBUTED_MAX_DICT))
        for pos, f in enumerate(frag_fields):
            if f.dict_id is not None:
                entry, codes = _encode_string_global(
                    [t.columns[pos] for t in shards], cap,
                    f.order_required, f.phys.np_dtype)
                dicts[f.dict_id] = entry
                shard_cols[pos] = codes
            else:
                shard_cols[pos] = [
                    _encode_plain(_one_chunk(t.columns[pos]), f.phys)
                    for t in shards]
        pairs_dev = []
        for pos, f in enumerate(frag_fields):
            cols = shard_cols[pos]
            dt = cols[0][0].dtype
            dp = np.zeros(n_dev * padded, dt)
            vp = np.zeros(n_dev * padded, bool)
            for i, (d, v) in enumerate(cols):
                c = len(d)
                if c:
                    dp[i * padded:i * padded + c] = d
                    vp[i * padded:i * padded + c] = v
            pairs_dev.append((jax.device_put(jnp.asarray(dp), shard_sh),
                              jax.device_put(jnp.asarray(vp), shard_sh)))
        pos_dicts = {i: dicts[f.dict_id]
                     for i, f in enumerate(frag_fields)
                     if f.dict_id is not None}
        return nrows, pairs_dev, pos_dicts, padded

    # -----------------------------------------------------------------------
    def _build_program(self, env: _Env):
        import jax
        from jax.sharding import PartitionSpec as P

        from jax import shard_map
        from ..columnar.packing import pack_traced
        root = self.root
        self._check_keys = None
        self._out_specs = None

        def local(*inputs):
            import jax.numpy as jnp
            env._inputs = inputs
            env.checks = []
            rel = root.emit(env).compacted(env)
            # Sink discipline (r2 verdict #1): the fetch is sized by the
            # RESULT, not the padded program shapes — slice every output
            # column to a learned speculative result bound (validated
            # like every other bound; first run uses the padded size,
            # the recorded stat shrinks repeats), then pack everything
            # into the engine's two-stream format (columnar/packing.py)
            # so the whole result leaves the device in at most two
            # transfers instead of 2×columns×devices padded fetches.
            rb = min(env.bound(("result",), default=rel.padded),
                     rel.padded)
            env.check(rel.count, rb)
            flat = [rel.count.astype(jnp.int64).reshape(1)]
            # env.checks is never empty: the result-bound check above
            # is always present
            flat.append(jnp.concatenate(
                [c.astype(jnp.int64).reshape(1) for c, _ in env.checks]))
            for d, v in rel.pairs:
                flat.append(d[:rb])
                flat.append(v[:rb])
            self._out_specs = [(np.dtype(str(x.dtype)), tuple(x.shape))
                               for x in flat]
            u32, f64 = pack_traced(flat)
            return u32.reshape(1, -1), f64.reshape(1, -1)

        # specs: replicated sources P(), sharded P(axis)
        in_specs = []
        for idx, (src, replicated) in enumerate(self.sources):
            padded, nf, off = env._layout[idx]
            spec = P() if replicated else P(self.axis)
            in_specs.append(spec)
            in_specs.extend([spec] * (2 * nf))
        out_spec = P(self.axis)

        fn = shard_map(local, mesh=self.mesh, in_specs=tuple(in_specs),
                       out_specs=out_spec, check_vma=False)
        jit_fn = jax.jit(fn)
        # bind check keys in emit order: do a lightweight bound-key pass
        self._check_keys = self._collect_check_keys(env)
        return jit_fn

    def _collect_check_keys(self, env: _Env):
        """Deterministic (emit-order) keys for the overflow checks —
        mirrors the env.bound() calls inside emit()."""
        keys = []

        def walk(frag):
            if isinstance(frag, _SourceFrag):
                return
            if isinstance(frag, _LocalFrag):
                walk(frag.child)
                return
            if isinstance(frag, _JoinFrag):
                walk(frag.left)
                walk(frag.right)
                if not (frag.broadcast_build or env.n_dev == 1
                        or frag.replicated):
                    keys.append(("recv", frag.frag_id, False))
                    keys.append(("recv", frag.frag_id, True))
                keys.append(("join", frag.frag_id))
                return
            if isinstance(frag, _WindowFrag):
                walk(frag.child)
                if not (env.n_dev == 1 or frag.replicated):
                    keys.append(("win", frag.frag_id))
                return
            if isinstance(frag, _AggFrag):
                walk(frag.child)
                if not (env.n_dev == 1 or frag.replicated):
                    keys.append(("agg", frag.frag_id))
        walk(self.root)
        keys.append(("result",))    # the sink's result-bound check
        return keys

    # -----------------------------------------------------------------------
    def _stitch_packed(self, per_dev, counts, dicts):
        import pyarrow as pa
        from ..columnar.column import arrow_from_numpy
        n_dev = self.n_dev
        root = self.root
        take_first_only = root.replicated
        arrays = []
        for ci, (f, lf) in enumerate(zip(self._schema.fields, root.fields)):
            parts_d, parts_v = [], []
            devs = [0] if take_first_only else range(n_dev)
            for dev in devs:
                g = int(counts[dev])
                parts_d.append(per_dev[dev][2 + 2 * ci][:g])
                parts_v.append(per_dev[dev][3 + 2 * ci][:g])
            dv = np.concatenate(parts_d) if parts_d \
                else per_dev[0][2 + 2 * ci][:0]
            vv = np.concatenate(parts_v) if parts_v \
                else per_dev[0][3 + 2 * ci][:0]
            if lf.dict_id is not None:
                entry = dicts.get(lf.dict_id, ("sorted",
                                               np.asarray([], object)))
                if entry[0] == "sorted":
                    uniq = entry[1]
                    pos = np.clip(dv, 0, max(len(uniq) - 1, 0))
                else:                   # hash codes -> decode map
                    h_uniq, uniq = entry[1], entry[2]
                    pos = np.clip(np.searchsorted(h_uniq, dv), 0,
                                  max(len(uniq) - 1, 0))
                if len(uniq):
                    idx = pa.array(pos.astype(np.int64), mask=~vv)
                    arr = pa.array(uniq, type=pa.string()).take(idx)
                else:
                    arr = pa.nulls(len(dv), type=pa.string())
                arrays.append(arr)
            else:
                # arrays are already host numpy (device_get above) —
                # convert directly; a DeviceColumn round trip would pay
                # one H2D + one D2H crossing per result column
                arrays.append(arrow_from_numpy(dv, vv, lf.logical))
        names = [f.name for f in self._schema.fields]
        return pa.Table.from_arrays(arrays, names=names)


# ---------------------------------------------------------------------------
# entry point
# ---------------------------------------------------------------------------

def _scan_input_rows(node):
    """Total in-memory scan rows under a physical node; file scans count
    as 'large' (None = unbounded)."""
    from ..exec.basic import InMemoryScanExec
    from ..io.file_scan import FileScanBase
    if isinstance(node, FileScanBase):
        return None
    total = 0
    if isinstance(node, InMemoryScanExec):
        total += sum(t.num_rows for t in node.tables)
    for c in getattr(node, "children", []):
        sub = _scan_input_rows(c)
        if sub is None:
            return None
        total += sub
    return total


def distribution_gate(physical, conf: TpuConf, auto: bool = False) -> bool:
    """Whether a mesh should be used for this plan. An explicitly-supplied
    mesh implies distribution is wanted; an AUTO mesh (built because
    distributed.enabled defaulted on with >1 device) only engages above
    the minRows threshold — the cost-model gate that lets the conf
    default ON without hurting small queries."""
    if not auto:
        return True
    rows = _scan_input_rows(physical)
    return rows is None or rows >= int(conf.get(DISTRIBUTED_MIN_ROWS))


def try_distribute(physical, conf: TpuConf, mesh):
    """Replace the largest lowerable subtree containing communication with
    a DistributedPipelineExec. Returns None when NOTHING lowered, so the
    caller can fall back to the single-chip fused pipeline instead of
    silently losing it."""
    if mesh is None:
        return None
    return _try_replace(physical, conf, mesh)


def maybe_distribute(physical, conf: TpuConf, mesh):
    """try_distribute, keeping the original plan when nothing lowered."""
    replaced = try_distribute(physical, conf, mesh)
    return replaced if replaced is not None else physical


_SINGLE_MESH = [None]


def maybe_fuse_single_chip(physical, conf: TpuConf):
    """Single-chip fused pipelines: a plan fragment containing a JOIN
    compiles to ONE kernel through the fragment compiler over a 1-device
    mesh — one dispatch instead of several per operator, the dominant
    cost on a latency-bound backend. Join-free plans keep the operator
    pipeline (the aggregate exec's fused single-fetch path is already
    one dispatch). Oversized inputs fall back at runtime."""
    if _SINGLE_MESH[0] is None:
        from .mesh import make_mesh
        _SINGLE_MESH[0] = make_mesh(1)
    replaced = _try_replace(physical, conf, _SINGLE_MESH[0],
                            require_join=True, keep_fallback=True)
    return replaced if replaced is not None else physical


def _try_replace(node, conf: TpuConf, mesh, require_join: bool = False,
                 keep_fallback: bool = False):
    new = _lower_node(node, conf, mesh, require_join, keep_fallback)
    if new is not None:
        return new
    changed = False
    new_children = []
    for c in getattr(node, "children", []):
        r = _try_replace(c, conf, mesh, require_join, keep_fallback)
        if r is not None and r is not c:
            changed = True
            new_children.append(r)
        else:
            new_children.append(c)
    if changed:
        node.children = new_children
    return node if changed else None


def _oversized_scan(sources) -> bool:
    """Whether an in-memory scan among the fragment's sources holds more
    rows than the largest shape bucket, read off the plan."""
    from ..columnar.bucketing import DEFAULT_BUCKETS
    from ..exec.basic import InMemoryScanExec
    return any(isinstance(s, InMemoryScanExec)
               and sum(t.num_rows for t in s.tables) > max(DEFAULT_BUCKETS)
               for s, _ in sources)


def _lower_node(node, conf: TpuConf, mesh, require_join: bool = False,
                keep_fallback: bool = False):
    planner = _Planner(conf, fused_mode=require_join)
    try:
        frag = planner.lower(node)
    except _NotLowerable as e:
        log.debug("not lowerable at %s: %s", type(node).__name__, e)
        return None
    if not planner.has_comm:
        return None                 # no join/agg: the mesh gains nothing
    if require_join and not planner.has_join:
        return None
    if keep_fallback and _oversized_scan(planner.sources):
        # the fragment is a single-batch program and would hand such a
        # source to its fallback before running anything
        # (DistributedPipelineExec.do_execute): plan the operator pipeline
        # as what it is, so that explain shows the operators that run
        return None
    ex = DistributedPipelineExec(frag, planner.sources, mesh, conf,
                                 node.output_schema(),
                                 fallback=node if keep_fallback
                                 else None)
    ex.key_sigs = planner.key_sigs
    return ex
