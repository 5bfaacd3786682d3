"""Basic physical operators: scan, project, filter, range, limit, union,
sample, expand, coalesce (ref basicPhysicalOperators.scala: GpuProjectExec:365,
GpuFilterExec:806, GpuRangeExec:1137; GpuCoalesceBatches.scala:112).
"""
from __future__ import annotations

import time
from typing import Dict, Iterator, List, Optional, Sequence

import jax.numpy as jnp
import numpy as np

from ..columnar import (ColumnarBatch, DeviceColumn, HostColumn,
                        concat_batches)
from ..columnar.batch import resolve_counts
from ..columnar.bucketing import bucket_for
from ..exprs.base import Expression
from ..exprs.compiler import compile_projection, filter_batch_device
from ..trace import core as trace_core
from ..types import INT64, Schema, StructField
from .base import DEBUG, ESSENTIAL, ExecContext, TpuExec

__all__ = ["InMemoryScanExec", "TpuProjectExec", "CpuProjectExec",
           "TpuFilterExec", "CpuFilterExec", "TpuRangeExec", "LimitExec",
           "UnionExec", "CoalesceBatchesExec", "TpuSampleExec",
           "TpuExpandExec"]


def _reset_task_state(exprs):
    """Restart task-context counters (monotonically_increasing_id, rand)
    at the start of each plan execution — Spark resets per-task state on
    every task launch."""
    stack = list(exprs)
    while stack:
        e = stack.pop()
        r = getattr(e, "reset_task_state", None)
        if r is not None:
            r()
        stack.extend(e.children)


#: device-batch cache for repeated scans of the same Arrow table (the
#: HostColumnarToGpu analog of keeping broadcast/shuffle data
#: device-resident): weak-keyed on the table so memory frees with it,
#: LRU-bounded so it cannot starve the spillable memory pool (the entries
#: live OUTSIDE the retry framework's reach — eviction here is the only
#: pressure valve)
import weakref

from ..config import register as _register_conf

SCAN_CACHE_MAX_BYTES = _register_conf(
    "spark.rapids.tpu.sql.scanCache.maxBytes", 2 * 1024 * 1024 * 1024,
    "Device-memory budget for cached in-memory-table scan batches; "
    "least-recently-used entries evict first. 0 disables the cache.")

_SCAN_CACHE: "weakref.WeakValueDictionary" = weakref.WeakValueDictionary()
_SCAN_CACHE_BATCHES: Dict[tuple, list] = {}
_SCAN_CACHE_LRU: Dict[tuple, int] = {}
_SCAN_CACHE_TICK = [0]


def _scan_cache_get(t, key):
    if _SCAN_CACHE.get(id(t)) is t:
        k = (id(t),) + key
        got = _SCAN_CACHE_BATCHES.get(k)
        if got is not None:
            _SCAN_CACHE_TICK[0] += 1
            _SCAN_CACHE_LRU[k] = _SCAN_CACHE_TICK[0]
        return got
    return None


def _scan_cache_bytes() -> int:
    # snapshot: weakref finalizers may evict entries mid-iteration (GC can
    # run _scan_cache_evict during any allocation inside the sum)
    return sum(b.device_size_bytes()
               for bs in list(_SCAN_CACHE_BATCHES.values()) for b in bs)


def _scan_cache_put(t, key, batches, limit: int):
    if limit <= 0:
        return
    new_bytes = sum(b.device_size_bytes() for b in batches)
    if new_bytes > limit:
        return
    # LRU-evict until the new entry fits
    while _SCAN_CACHE_BATCHES and _scan_cache_bytes() + new_bytes > limit:
        coldest = min(_SCAN_CACHE_LRU, key=_SCAN_CACHE_LRU.get)
        del _SCAN_CACHE_BATCHES[coldest]
        del _SCAN_CACHE_LRU[coldest]
    tid = id(t)
    if _SCAN_CACHE.get(tid) is not t:
        # new table under a reused id: drop stale entries for that id
        _scan_cache_evict(tid)
        try:
            _SCAN_CACHE[tid] = t
        except TypeError:
            return      # not weak-referenceable: skip caching
        weakref.finalize(t, _scan_cache_evict, tid)
    k = (tid,) + key
    _SCAN_CACHE_BATCHES[k] = batches
    _SCAN_CACHE_TICK[0] += 1
    _SCAN_CACHE_LRU[k] = _SCAN_CACHE_TICK[0]


def _scan_cache_evict(tid):
    for k in [k for k in _SCAN_CACHE_BATCHES if k[0] == tid]:
        del _SCAN_CACHE_BATCHES[k]
        _SCAN_CACHE_LRU.pop(k, None)


class InMemoryScanExec(TpuExec):
    """Scan over pre-partitioned Arrow tables (ref GpuInMemoryTableScanExec).
    Device batches are cached per (table, split) so re-running a query over
    the same in-memory data skips the H2D transfer entirely."""

    def __init__(self, tables, schema: Schema, batch_rows: int = 1 << 20,
                 columns=None):
        super().__init__([])
        self.tables = list(tables)
        self._schema = schema if columns is None else Schema(
            [schema[c] for c in columns])
        self.columns = list(columns) if columns is not None else None
        self.batch_rows = batch_rows

    def output_schema(self) -> Schema:
        return self._schema

    def do_execute(self, ctx: ExecContext) -> Iterator[ColumnarBatch]:
        rows_m = ctx.metric(self._exec_id, "numOutputRows", ESSENTIAL)
        names = tuple(self._schema.names())
        limit = int(ctx.conf.get(SCAN_CACHE_MAX_BYTES))
        for pid, t in enumerate(self.tables):
            key = (self.batch_rows, names)
            cached = _scan_cache_get(t, key)
            if cached is not None:
                for b in cached:
                    rows_m.add(b.num_rows)
                    yield b
                continue
            built = []
            src = t if self.columns is None else t.select(self.columns)
            off = 0
            while off < src.num_rows or (src.num_rows == 0 and off == 0):
                chunk = src.slice(off, self.batch_rows)
                if chunk.num_rows == 0 and off > 0:
                    break
                with ctx.semaphore.held():
                    from ..columnar.strrect import RECT_MAX_BYTES
                    b = ColumnarBatch.from_arrow(
                        chunk, rect_cap=int(ctx.conf.get(RECT_MAX_BYTES)))
                b.meta = {"partition_id": pid}
                rows_m.add(b.num_rows)
                built.append(b)
                yield b
                off += self.batch_rows
                if src.num_rows == 0:
                    break
            _scan_cache_put(t, key, built, limit)

    def describe(self):
        return f"InMemoryScan[{len(self.tables)} partitions]"


class TpuProjectExec(TpuExec):
    """Projection. Device-supported expressions compile into ONE fused XLA
    kernel; host-only expressions (strings etc.) evaluate via Arrow and are
    H2D'd when their output type is device-backed — per-expression fallback,
    finer-grained than the reference's whole-exec fallback."""

    def __init__(self, exprs: Sequence[Expression], child: TpuExec):
        super().__init__([child])
        self.exprs = list(exprs)
        in_schema = child.output_schema()
        self._schema = Schema([
            StructField(e.name_hint, e.data_type(in_schema), True)
            for e in self.exprs])
        self.device_idx = []
        self.host_idx = []
        self.passthrough = {}    # out ordinal -> source column name
        #: out ordinal -> (transform chain root, leaf column name):
        #: value-wise string transforms over ONE string column evaluate
        #: once per distinct dictionary entry and re-encode (VERDICT r2
        #: #4 — row data stays on device; ref stringFunctions.scala)
        self.dict_chain = {}
        #: out ordinal -> (chain root, leaf name): device byte-rectangle
        #: string chains (high cardinality — exprs/string_rect.py)
        self.rect_chain = {}
        from ..exprs.base import Alias, ColumnRef
        for i, e in enumerate(self.exprs):
            inner = e.children[0] if isinstance(e, Alias) else e
            if isinstance(inner, ColumnRef):
                # identity projection: reuse the column object — zero
                # compute AND it preserves runtime column state
                # (DictColumn dictionaries) the planner can't see
                self.passthrough[i] = inner.name
            elif e.fully_device_supported(in_schema) is None:
                self.device_idx.append(i)
            else:
                self.host_idx.append(i)
                leaf = self._dict_chain_leaf(inner, in_schema)
                if leaf is not None:
                    self.dict_chain[i] = (inner, leaf)
                from ..exprs.string_rect import rect_chain_leaf
                rleaf = rect_chain_leaf(inner, in_schema)
                if rleaf is not None:
                    # high-cardinality path: when the source column is a
                    # byte rectangle (ASCII), the chain compiles to ONE
                    # device kernel over [rows, width] (VERDICT r3 #4;
                    # ref stringFunctions.scala device kernels)
                    self.rect_chain[i] = (inner, rleaf)
        #: device exprs referencing ArrayType columns: the batch may carry
        #: them as HostColumns (width cap, columnar/nested.py) — those
        #: exprs drop to host PER BATCH (the dict-filter bail-out pattern)
        from ..types import ArrayType
        self._list_refs = {
            i: [r for r in set(self.exprs[i].references())
                if r in in_schema.names()
                and isinstance(in_schema[r].dtype, ArrayType)]
            for i in self.device_idx}
        self._list_refs = {i: v for i, v in self._list_refs.items() if v}
        self._projector = None
        self._sub_projectors = {}
        self._dict_xform_cache = {}

    @staticmethod
    def _dict_chain_leaf(e, schema):
        """Leaf column name when ``e`` is a chain of dict_transform
        string ops over one STRING ColumnRef, else None."""
        from ..exprs.base import ColumnRef
        from ..types import STRING
        cur = e
        hops = 0
        while getattr(cur, "dict_transform", False) \
                and len(cur.children) == 1:
            cur = cur.children[0]
            hops += 1
        if hops and isinstance(cur, ColumnRef) \
                and cur.name in schema.names() \
                and schema[cur.name].dtype == STRING:
            return cur.name
        return None

    def _dict_transform(self, expr, leaf: str, col):
        """DictColumn -> DictColumn with the TRANSFORMED dictionary;
        None when a transformed entry is NULL (caller takes the per-row
        path). Transforms can merge or reorder entries (upper('a') ==
        upper('A')), so the raw result is deduped + re-SORTED and the
        device codes remapped through one small one-hot gather —
        DictColumn's sorted-unique invariant (code order == string
        order) holds for every downstream consumer (sort, window
        partitioning, range predicates)."""
        import pyarrow as pa
        from ..columnar import ColumnarBatch, DictColumn
        from ..columnar.segmented import onehot_gather
        ck = expr.key()
        cached = self._dict_xform_cache.get(ck)
        if cached is not None and cached[0] is col.dictionary:
            uniq, rank = cached[1]
        else:
            fake = ColumnarBatch.from_arrow_host(
                pa.table({leaf: pa.array(col.dictionary,
                                         type=pa.string())}))
            out = expr.eval_host(fake)
            if pa.compute.any(pa.compute.is_null(out)).as_py():
                return None
            vals = np.asarray(out.to_numpy(zero_copy_only=False),
                              dtype=object)
            uniq, inv = np.unique(vals, return_inverse=True)
            rank = inv.astype(np.int32)
            self._dict_xform_cache[ck] = (col.dictionary, (uniq, rank))
        G = bucket_for(max(len(rank), 1), (64, 1024, 16384, 262144))
        table = np.zeros(G, np.int32)
        table[:len(rank)] = rank
        codes = onehot_gather(jnp.asarray(table), col.data, G)
        return DictColumn(codes, col.validity, col.dtype,
                          np.asarray(uniq, dtype=object))

    def _rect_eval(self, expr, col, ordinal: int, width_cap: int):
        """One jitted kernel for a whole rect string chain (upper/trim/
        substring/... fused), resolved through the PROCESS-wide
        executable cache keyed on (expr, width, padded, cap): a
        per-exec kernel dict re-traced the chain on every query — the
        string_transforms_100k 17.3 s warm cliff (ISSUE 6)."""
        from ..columnar.strrect import ByteRectColumn
        from ..exprs.base import StrVal
        from ..exprs.compiler import compile_rect_chain
        fn = compile_rect_chain(expr, col.width, col.padded_len,
                                width_cap)
        data, valid = fn(col.data, col.lengths, col.validity)
        if isinstance(data, StrVal):
            return ByteRectColumn(data.bytes_, valid, data.lengths,
                                  ascii_only=True)
        from ..columnar import DeviceColumn
        return DeviceColumn(data, valid,
                            self._schema.fields[ordinal].dtype)

    def output_schema(self) -> Schema:
        return self._schema

    def do_execute(self, ctx: ExecContext) -> Iterator[ColumnarBatch]:
        child_schema = self.children[0].output_schema()
        dev_exprs = [self.exprs[i] for i in self.device_idx]
        rows_m = ctx.metric(self._exec_id, "numOutputRows", ESSENTIAL)
        _reset_task_state(self.exprs)
        for batch in self.children[0].execute(ctx):
            batch = batch.ensure_device()
            out: List[Optional[object]] = [None] * len(self.exprs)
            for i, name in self.passthrough.items():
                out[i] = batch.column_by_name(name)
            host_now = []
            dev_now = self.device_idx
            if self._list_refs:
                from ..columnar.nested import ListColumn
                host_now = [
                    i for i, names in self._list_refs.items()
                    if any(not isinstance(batch.column_by_name(nm),
                                          ListColumn) for nm in names)]
                if host_now:
                    dev_now = [i for i in self.device_idx
                               if i not in host_now]
            if dev_now:
                if dev_now is self.device_idx:
                    if self._projector is None:
                        self._projector = compile_projection(dev_exprs,
                                                             child_schema)
                    proj = self._projector
                else:
                    key = tuple(dev_now)
                    proj = self._sub_projectors.get(key)
                    if proj is None:
                        proj = compile_projection(
                            [self.exprs[i] for i in dev_now],
                            child_schema)
                        self._sub_projectors[key] = proj
                with ctx.semaphore.held():
                    dcols = proj.run(batch)
                for i, c in zip(dev_now, dcols):
                    out[i] = c
            for i in host_now:
                arr = self.exprs[i].eval_host(batch)
                dt = self._schema.fields[i].dtype
                if dt.device_backed:
                    import pyarrow as pa
                    hb = ColumnarBatch.from_arrow(pa.table({"c": arr}))
                    out[i] = hb.columns[0]
                else:
                    out[i] = HostColumn(arr, dt)
            for i in self.host_idx:
                chain = self.dict_chain.get(i)
                if chain is not None:
                    from ..columnar import DictColumn
                    expr, leaf = chain
                    src = batch.column_by_name(leaf)
                    if isinstance(src, DictColumn) \
                            and len(src.dictionary):
                        xf = self._dict_transform(expr, leaf, src)
                        if xf is not None:
                            out[i] = xf
                            continue
                rchain = self.rect_chain.get(i)
                if rchain is not None:
                    from ..columnar.strrect import ByteRectColumn
                    from ..exprs.string_rect import RectUnsupported
                    expr, leaf = rchain
                    src = batch.column_by_name(leaf)
                    if isinstance(src, ByteRectColumn) and src.ascii_only:
                        from ..columnar.strrect import RECT_MAX_BYTES
                        cap = int(ctx.conf.get(RECT_MAX_BYTES))
                        try:
                            with ctx.semaphore.held():
                                out[i] = self._rect_eval(expr, src, i, cap)
                            continue
                        except RectUnsupported:
                            # the chain outgrows the width cap: host for
                            # this and (dropping the chain) later batches
                            # — no per-batch re-trace just to re-raise
                            self.rect_chain.pop(i, None)
                arr = self.exprs[i].eval_host(batch)
                dt = self._schema.fields[i].dtype
                if dt.device_backed:
                    import pyarrow as pa
                    hb = ColumnarBatch.from_arrow(
                        pa.table({"c": arr}))
                    out[i] = hb.columns[0]
                else:
                    out[i] = HostColumn(arr, dt)
            rows_m.add(batch.num_rows_raw)
            yield ColumnarBatch(out, batch.num_rows_raw, self._schema,
                                meta=batch.meta)

    def describe(self):
        tags = []
        plain_host = [i for i in self.host_idx
                      if i not in self.dict_chain
                      and i not in self.rect_chain]
        if plain_host:
            tags.append("host_fallback="
                        f"{[self.exprs[i].name_hint for i in plain_host]}")
        if self.dict_chain:
            tags.append("dict_transform="
                        f"{[self.exprs[i].name_hint for i in self.dict_chain]}")
        rect_only = [i for i in self.rect_chain if i not in self.dict_chain]
        if rect_only:
            tags.append("rect_device="
                        f"{[self.exprs[i].name_hint for i in rect_only]}")
        return ("Project[" + ", ".join(e.name_hint for e in self.exprs) + "]"
                + (" " + " ".join(tags) if tags else ""))


class CpuProjectExec(TpuExec):
    """Whole-node host fallback (ref: plan stays on CPU after tagging)."""
    is_tpu = False

    def __init__(self, exprs: Sequence[Expression], child: TpuExec):
        super().__init__([child])
        self.exprs = list(exprs)
        in_schema = child.output_schema()
        self._schema = Schema([
            StructField(e.name_hint, e.data_type(in_schema), True)
            for e in self.exprs])

    def output_schema(self) -> Schema:
        return self._schema

    def do_execute(self, ctx: ExecContext) -> Iterator[ColumnarBatch]:
        _reset_task_state(self.exprs)
        for batch in self.children[0].execute(ctx):
            cols = []
            for e, f in zip(self.exprs, self._schema.fields):
                arr = e.eval_host(batch)
                cols.append(HostColumn(arr, f.dtype))
            yield ColumnarBatch(cols, batch.num_rows_raw, self._schema,
                                meta=batch.meta)

    def describe(self):
        return "CpuProject[" + ", ".join(e.name_hint for e in self.exprs) + "]"


class TpuFilterExec(TpuExec):
    """Device filter with O(n) compaction (ref GpuFilterExec:806)."""

    def __init__(self, condition: Expression, child: TpuExec):
        super().__init__([child])
        self.condition = condition
        self._dict_eval = None
        self._dict_checked = False

    def output_schema(self) -> Schema:
        return self.children[0].output_schema()

    def _dict_evaluator(self, schema):
        if not self._dict_checked:
            self._dict_checked = True
            if self.condition.fully_device_supported(schema) is not None:
                from ..exprs.compiler import build_dict_filter
                self._dict_eval = build_dict_filter(self.condition,
                                                    schema)
        return self._dict_eval

    def do_execute(self, ctx: ExecContext) -> Iterator[ColumnarBatch]:
        from ..exprs.compiler import (DictFilterFallback,
                                      filter_batch_by_mask)
        rows_m = ctx.metric(self._exec_id, "numOutputRows", ESSENTIAL)
        schema = self.children[0].output_schema()
        for batch in self.children[0].execute(ctx):
            batch = batch.ensure_device()
            dict_eval = self._dict_evaluator(schema)
            with ctx.semaphore.held():
                if dict_eval is not None:
                    out = self._filter_dict(ctx, dict_eval, batch)
                elif batch.all_device:
                    out = filter_batch_device(self.condition, batch)
                else:
                    out = self._filter_mixed(batch)
            rows_m.add(out.num_rows_raw)
            yield out    # measured-rows feedback: base execute() records

    def _filter_dict(self, ctx, dict_eval, batch):
        """String predicates evaluated once over the dictionary,
        broadcast through codes on device; per-batch host fallback when a
        string column is not dict-coded (high-cardinality bail-out)."""
        import pyarrow.compute as pc
        from ..exprs.compiler import (DictFilterFallback,
                                      filter_batch_by_mask)
        try:
            keep = dict_eval.keep_mask(batch)
            return filter_batch_by_mask(batch, keep)
        except DictFilterFallback:
            mask = pc.fill_null(self.condition.eval_host(batch), False)
            return ColumnarBatch.from_arrow(
                batch.to_arrow().filter(mask))

    def _filter_mixed(self, batch: ColumnarBatch) -> ColumnarBatch:
        from ..exprs.compiler import filter_mixed_batch
        return filter_mixed_batch(self.condition, batch)

    def describe(self):
        return f"Filter[{self.condition.name_hint}]"


class CpuFilterExec(TpuExec):
    is_tpu = False

    def __init__(self, condition: Expression, child: TpuExec):
        super().__init__([child])
        self.condition = condition

    def output_schema(self) -> Schema:
        return self.children[0].output_schema()

    def do_execute(self, ctx: ExecContext) -> Iterator[ColumnarBatch]:
        import pyarrow.compute as pc
        for batch in self.children[0].execute(ctx):
            mask = self.condition.eval_host(batch)
            t = batch.to_arrow().filter(pc.fill_null(mask, False))
            # host-only output: a CPU-reverted chain must not bounce
            # every batch back through HBM (downstream device execs
            # re-materialize via ensure_device when they need to);
            # measured-rows feedback records in base execute()
            yield ColumnarBatch.from_arrow_host(t)

    def describe(self):
        return f"CpuFilter[{self.condition.name_hint}]"


class TpuRangeExec(TpuExec):
    """range(start, end, step) generated directly in HBM via iota
    (ref GpuRangeExec basicPhysicalOperators.scala:1137)."""

    def __init__(self, start: int, end: int, step: int, name: str = "id",
                 batch_rows: int = 1 << 20):
        super().__init__([])
        self.start, self.end, self.step = start, end, step
        self.name = name
        self.batch_rows = batch_rows
        self._schema = Schema([StructField(name, INT64, False)])

    def output_schema(self) -> Schema:
        return self._schema

    def do_execute(self, ctx: ExecContext) -> Iterator[ColumnarBatch]:
        total = max(0, -(-(self.end - self.start) // self.step)
                    if self.step > 0 else -((self.start - self.end) // -self.step))
        emitted = 0
        while emitted < total or (total == 0 and emitted == 0):
            n = min(self.batch_rows, total - emitted)
            p = bucket_for(max(n, 1))
            with ctx.semaphore.held():
                base = self.start + emitted * self.step
                data = base + jnp.arange(p, dtype=jnp.int64) * self.step
                valid = jnp.arange(p) < n
                col = DeviceColumn(data, valid, INT64)
            yield ColumnarBatch([col], n, self._schema)
            emitted += n
            if total == 0:
                break

    def describe(self):
        return f"Range[{self.start},{self.end},{self.step}]"


class LimitExec(TpuExec):
    engine_neutral = True
    def __init__(self, n: int, child: TpuExec):
        super().__init__([child])
        self.n = n

    def output_schema(self) -> Schema:
        return self.children[0].output_schema()

    def do_execute(self, ctx: ExecContext) -> Iterator[ColumnarBatch]:
        rows_m = ctx.metric(self._exec_id, "numOutputRows", ESSENTIAL)
        remaining = self.n
        for batch in self.children[0].execute(ctx):
            if remaining <= 0:
                break
            if batch.num_rows <= remaining:
                remaining -= batch.num_rows
                rows_m.add(batch.num_rows)
                yield batch
            else:
                rows_m.add(remaining)
                yield batch.slice(0, remaining)
                remaining = 0

    def describe(self):
        return f"Limit[{self.n}]"


class UnionExec(TpuExec):
    engine_neutral = True
    def __init__(self, children: List[TpuExec]):
        super().__init__(children)

    def output_schema(self) -> Schema:
        return self.children[0].output_schema()

    def do_execute(self, ctx: ExecContext) -> Iterator[ColumnarBatch]:
        for c in self.children:
            yield from c.execute(ctx)

    def describe(self):
        return f"Union[{len(self.children)}]"


class BranchAlignExec(TpuExec):
    engine_neutral = True
    """Host assembly of the union-of-aggregates single pass (see
    plan/rewrites.py _rewrite_union_agg): child rows are keyed by a
    branch-id first column; emit exactly n rows in branch order with
    empty-aggregate defaults for missing branches. At most n (tiny) rows
    — host by construction, zero device dispatches."""

    def __init__(self, n: int, fill_zero: List[bool], child: TpuExec):
        super().__init__([child])
        self.n = n
        self.fill_zero = list(fill_zero)
        cs = child.output_schema()
        self._schema = Schema(list(cs.fields)[1:])

    def output_schema(self) -> Schema:
        return self._schema

    def do_execute(self, ctx: ExecContext) -> Iterator[ColumnarBatch]:
        import pyarrow as pa
        from ..types import to_arrow
        t = self.children[0].collect(ctx, validate=False)
        bid = t.column(0).to_pylist()
        row_of = {int(b): i for i, b in enumerate(bid) if b is not None}
        arrays = []
        for ci, f in enumerate(self._schema.fields):
            col = t.column(ci + 1)
            vals = col.to_pylist()
            default = 0 if self.fill_zero[ci] else None
            out = [vals[row_of[i]] if i in row_of else default
                   for i in range(self.n)]
            arrays.append(pa.array(out, type=to_arrow(f.dtype)))
        yield ColumnarBatch.from_arrow_host(
            pa.Table.from_arrays(arrays, names=self._schema.names()))

    def describe(self):
        return f"BranchAlign[n={self.n}]"


class CoalesceBatchesExec(TpuExec):
    """Concatenate small batches up to a target size (ref
    GpuCoalesceBatches.scala CoalesceGoal/TargetSize; RequireSingleBatch via
    target_rows=None meaning 'all'). A TargetSize goal is a ceiling: the
    pending batches leave when the NEXT one would pass it, so the output's
    bucket is ``bucket_for(target_rows)`` at most. A batch alone in its
    group passes through as the same object. The merged batch carries the
    first batch's ``meta`` (``plan/overrides.py`` plans this operator in
    no plan that reads it).

    The goal is applied to TRUE row counts. Where the input's counts are
    host ints (above a scan) each batch is decided as it arrives and
    nothing is read. Where they are on the device (above a streaming
    broadcast join) the input is taken ``COUNT_WINDOW`` batches at a time
    and a window's counts are read in ONE packed transfer (span
    ``d2h.coalesce_count.transfer``), each checked against its batch's
    capacity as ``num_rows`` would (``SpeculativeOverflow``: the plan
    re-runs with exact sizing); so at most ``batchSizeBytes`` of padded
    bytes and one window are held. A group still open after a window with
    ``COUNT_WINDOW`` batches or more is folded into one, so a concat takes
    fewer than twice that many whatever the join let through (the kernel
    compiles per arity). Tracer: a span ``coalesce.concat`` around each
    concat, a counter ``coalesce.batches`` {in, out, fetches, op} once an
    execution (``fetches``: count transfers; ``op``: the number in the
    operator's id, which the spans carry as ``exec``)."""

    #: input batches whose device counts are read in one transfer
    COUNT_WINDOW = 8

    def __init__(self, child: TpuExec, target_rows: Optional[int] = None,
                 target_bytes: Optional[int] = None):
        super().__init__([child])
        self.target_rows = target_rows
        self.target_bytes = target_bytes

    def output_schema(self) -> Schema:
        return self.children[0].output_schema()

    def do_execute(self, ctx: ExecContext) -> Iterator[ColumnarBatch]:
        single = self.target_rows is None and self.target_bytes is None
        max_bytes = self.target_bytes or ctx.conf.batch_size_bytes
        max_rows = self.target_rows or ctx.conf.batch_size_rows
        concat_m = ctx.metric(self._exec_id, "concatTime", DEBUG)
        pending: List[ColumnarBatch] = []
        #: input whose counts are still on the device, in arrival order
        window: List[ColumnarBatch] = []
        rows = nbytes = n_in = n_out = fetches = 0

        def concat() -> ColumnarBatch:
            with ctx.semaphore.held():
                return concat_batches(pending)

        def merged() -> ColumnarBatch:
            if len(pending) == 1:
                return pending[0]
            t0 = time.perf_counter()
            tr = trace_core.TRACER       # single branch when tracing is off
            if tr is None:
                out = concat()
            else:
                with tr.span("coalesce.concat", cat="exec",
                             args={"exec": self._exec_id,
                                   "n": len(pending)}):
                    out = concat()
            out.meta = pending[0].meta
            concat_m.add(time.perf_counter() - t0)
            return out

        def admit(batch: ColumnarBatch) -> Iterator[ColumnarBatch]:
            """The goal, on a batch whose count is on the host."""
            nonlocal pending, rows, nbytes, n_out
            b_rows, b_bytes = batch.num_rows, batch.size_bytes()
            if pending and not single and (rows + b_rows > max_rows
                                           or nbytes + b_bytes > max_bytes):
                n_out += 1
                yield merged()
                pending, rows, nbytes = [], 0, 0
            pending.append(batch)
            rows += b_rows
            nbytes += b_bytes

        def admit_window() -> Iterator[ColumnarBatch]:
            """The window's counts in one transfer, checked; then the goal
            on each of its batches."""
            nonlocal pending, nbytes, fetches
            if window:       # opened by a count that is on the device
                fetches += 1
                resolve_counts(window, label="d2h.coalesce_count")
            for b in window:
                yield from admit(b)
            window.clear()
            if len(pending) >= self.COUNT_WINDOW:
                pending = [merged()]
                nbytes = pending[0].size_bytes()

        try:
            for batch in self.children[0].execute(ctx):
                n_in += 1
                if not window and isinstance(batch.num_rows_raw, int):
                    yield from admit(batch)
                    continue
                window.append(batch)
                if len(window) == self.COUNT_WINDOW:
                    yield from admit_window()
            yield from admit_window()
            if pending:
                n_out += 1
                yield merged()
        finally:
            tr = trace_core.TRACER
            if tr is not None:
                tr.counter("coalesce.batches",
                           {"in": n_in, "out": n_out, "fetches": fetches,
                            "op": int(self._exec_id.rsplit("@", 1)[-1])},
                           cat="exec")

    def describe(self):
        goal = "RequireSingleBatch" if (self.target_rows is None and
                                        self.target_bytes is None) \
            else f"TargetSize(rows={self.target_rows}, bytes={self.target_bytes})"
        return f"CoalesceBatches[{goal}]"


class TpuSampleExec(TpuExec):
    """Bernoulli sample (ref GpuSampleExec)."""

    def __init__(self, fraction: float, seed: int, child: TpuExec):
        super().__init__([child])
        self.fraction = fraction
        self.seed = seed

    def output_schema(self) -> Schema:
        return self.children[0].output_schema()

    def do_execute(self, ctx: ExecContext) -> Iterator[ColumnarBatch]:
        rng = np.random.RandomState(self.seed)
        for batch in self.children[0].execute(ctx):
            mask = rng.random_sample(batch.num_rows) < self.fraction
            import pyarrow as pa
            t = batch.to_arrow().filter(pa.array(mask))
            yield ColumnarBatch.from_arrow(t)


class TpuExpandExec(TpuExec):
    """Each input row emits one output row per projection set
    (ref GpuExpandExec.scala)."""

    def __init__(self, projections, names, child: TpuExec):
        super().__init__([child])
        self.projections = projections
        self.names = names
        cs = child.output_schema()
        self._schema = Schema([StructField(n, e.data_type(cs), True)
                               for n, e in zip(names, projections[0])])

    def output_schema(self) -> Schema:
        return self._schema

    def do_execute(self, ctx: ExecContext) -> Iterator[ColumnarBatch]:
        child_schema = self.children[0].output_schema()
        projectors = [compile_projection(p, child_schema)
                      for p in self.projections]
        for batch in self.children[0].execute(ctx):
            for proj in projectors:
                with ctx.semaphore.held():
                    cols = proj.run(batch)
                yield ColumnarBatch(cols, batch.num_rows, self._schema,
                                meta=batch.meta)
