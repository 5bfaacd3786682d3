"""Whole-stage fusion: one compiled XLA program per operator region.

Reference analog: Spark's whole-stage codegen collapsed onto the XLA
fusion model ("Operator Fusion in XLA", PAPERS.md): the physical plan is
walked for maximal chains of fusible device operators between pipeline
breakers (scan -> filter -> project -> ...; sorts, joins, aggregations,
exchanges and host-fallback execs break the stage), and each chain is
replaced by ONE ``WholeStageExec`` that dispatches a single jitted
kernel per batch (exprs/compiler.FusedStageKernel) instead of one
dispatch + one compaction per operator. Where dispatch latency bounds a
query the dispatch count IS the cost model, so an N-operator region goes
from N separately-priced launches to one.

Aggregations already fuse their input chain into the update kernel
(plan/overrides.AggregateMeta._fold_stages); this pass covers every
region an aggregate does not swallow — join inputs, sort inputs,
filter/project pipelines feeding windows, limits or sinks.

Observability contract:
  * EXPLAIN shows the region as ``WholeStage[fused=[...]]``;
  * the PR-4 trace shows ONE span per batch with a ``fused=[...]`` arg;
  * EXPLAIN ANALYZE still reports per-operator rows and self time
    inside the region: the kernel returns one survivor count per fused
    stage (device scalars, forced only through the metrics view's
    packed fetch) and the fused dispatch wall is apportioned across the
    fused operators (metrics/analyze.py renders them indented under the
    WholeStage row).

Compiled programs resolve through the two-tier executable cache
(plan/exec_cache.py): warm repeats of a plan shape pay zero retrace in
process and zero XLA compile across processes.
"""
from __future__ import annotations

import time
from typing import Iterator, List, Optional, Tuple

from ..columnar import ColumnarBatch, DeviceColumn
from ..config import TpuConf, register
from ..types import Schema
from . import basic as B
from .base import ESSENTIAL, ExecContext, TpuExec

__all__ = ["WholeStageExec", "fuse_whole_stages", "FUSION_ENABLED",
           "AGG_FUSION_ENABLED"]

FUSION_ENABLED = register(
    "spark.rapids.tpu.fusion.enabled", True,
    "Fuse chains of device filter/project operators between pipeline "
    "breakers into one compiled XLA program per region (WholeStageExec):"
    " one kernel dispatch and ONE row compaction per batch instead of "
    "one per operator — the whole-stage-codegen analog on a backend "
    "where dispatch latency is the unit of cost. Fused regions show as "
    "WholeStage[fused=[...]] in EXPLAIN and as one span in the trace; "
    "EXPLAIN ANALYZE still reports per-operator rows/self time inside "
    "them. Executables resolve through the two-tier compile cache "
    "(spark.rapids.tpu.compile.cache.*).", commonly_used=True)

FUSION_MIN_OPS = register(
    "spark.rapids.tpu.fusion.minOperators", 2,
    "Minimum chain length worth fusing: a single operator already is "
    "one dispatch, so wrapping it only adds indirection.", internal=True)

AGG_FUSION_ENABLED = register(
    "spark.rapids.tpu.fusion.aggregate.enabled", True,
    "Fold the chain of device filter/project operators feeding an "
    "aggregation INTO its update kernel (plan/overrides.py "
    "_fold_stages): scan->filter->project->partial-agg runs as ONE "
    "compiled dispatch per batch — the whole-stage fusion extended "
    "through partial aggregation, the tpcds q9/q28 multi-aggregate "
    "shape. EXPLAIN shows the folded region as "
    "HashAggregate[...] fused=[...]; the exec's updateDispatches "
    "metric counts the actual kernel launches per batch. Off = the "
    "per-operator pipeline (byte-identical results, one dispatch and "
    "one compaction per stage).", commonly_used=True)


def _nondeterministic(exprs) -> bool:
    """Expressions carrying per-task state (rand, monotonically
    increasing id) observe row positions: evaluating them row-wise over
    the uncompacted bucket would disagree with the per-operator
    pipeline, so their chains never fuse."""
    stack = list(exprs)
    while stack:
        e = stack.pop()
        if e is None:
            continue
        if getattr(e, "reset_task_state", None) is not None:
            return True
        stack.extend(getattr(e, "children", ()))
    return False


def _fusible(op: TpuExec) -> bool:
    if type(op) is B.TpuFilterExec:
        schema = op.children[0].output_schema()
        return (op.condition.fully_device_supported(schema) is None
                and not _nondeterministic([op.condition]))
    if type(op) is B.TpuProjectExec:
        return (not op.host_idx and not op._list_refs
                and not _nondeterministic(op.exprs))
    return False


def fuse_whole_stages(node: TpuExec, conf: TpuConf) -> TpuExec:
    """Physical-plan pass replacing maximal fusible chains with
    WholeStageExec. The disabled path is one conf read — no tree walk,
    no cache traffic (the trace/metrics off-path contract)."""
    if not conf.get(FUSION_ENABLED):
        return node
    return _fuse(node, max(1, int(conf.get(FUSION_MIN_OPS))))


def _fuse(node: TpuExec, min_ops: int) -> TpuExec:
    chain: List[TpuExec] = []
    cur = node
    while _fusible(cur):
        chain.append(cur)
        cur = cur.children[0]
    if len(chain) >= min_ops:
        return WholeStageExec(list(reversed(chain)), _fuse(cur, min_ops))
    node.children = [_fuse(c, min_ops)
                     for c in getattr(node, "children", [])]
    return node


class WholeStageExec(TpuExec):
    """Executes a fused region of filter/project operators as one
    compiled program per batch (module doc)."""

    def __init__(self, fused_ops: List[TpuExec], child: TpuExec):
        super().__init__([child])
        self.fused_ops = list(fused_ops)          # bottom-up order
        self._schema = self.fused_ops[-1].output_schema()
        in_schema = child.output_schema()
        self.stages: List[Tuple] = []
        for op in self.fused_ops:
            if isinstance(op, B.TpuFilterExec):
                self.stages.append(("filter", op.condition))
            else:
                self.stages.append(("project", op.exprs,
                                    op.output_schema()))
        #: measured-rows feedback rides the TOP op's plan signature —
        #: the region's output rows are exactly that operator's
        self.plan_sig = getattr(self.fused_ops[-1], "plan_sig", None)
        self.trace_args = {
            "fused": [op.describe() for op in self.fused_ops]}
        self._origins = self._trace_origins(in_schema)
        self._kernel = None

    def __getstate__(self):
        # plans ship to shuffle workers by pickle; the compiled kernel
        # is process-local (the receiving process resolves its own from
        # the executable cache)
        state = dict(self.__dict__)
        state["_kernel"] = None
        return state

    def _trace_origins(self, in_schema: Schema) -> List[Optional[str]]:
        """Per output ordinal: the INPUT column name when the output is
        an identity chain from it (dictionary-coded strings must be
        rebuilt around their dictionary after compaction)."""
        from ..exprs.base import Alias, ColumnRef
        mapping = {n: n for n in in_schema.names()}
        for st in self.stages:
            if st[0] == "filter":
                continue
            new = {}
            for e in st[1]:
                inner = e.children[0] if isinstance(e, Alias) else e
                new[e.name_hint] = (mapping.get(inner.name)
                                    if isinstance(inner, ColumnRef)
                                    else None)
            mapping = new
        return [mapping.get(f.name) for f in self._schema.fields]

    def output_schema(self) -> Schema:
        return self._schema

    # ------------------------------------------------------------ execution
    def _fast_ok(self, batch: ColumnarBatch) -> bool:
        """The single-dispatch kernel moves columns as plain
        (data, validity) lanes: every input column must be a plain
        DeviceColumn or a DictColumn (codes are a plain lane; the
        dictionary is rebuilt from the passthrough origin), and every
        output must either be such a passthrough or a numeric-lane
        type. Byte-rectangle / list / host columns take the per-stage
        fallback path — same results, more dispatches."""
        from ..columnar.column import DictColumn
        for c in batch.columns:
            if type(c) is not DeviceColumn and type(c) is not DictColumn:
                return False
        for f, origin in zip(self._schema.fields, self._origins):
            if origin is None and getattr(f.dtype, "np_dtype",
                                          None) is None:
                return False
        return True

    def do_execute(self, ctx: ExecContext) -> Iterator[ColumnarBatch]:
        rows_m = ctx.metric(self._exec_id, "numOutputRows", ESSENTIAL)
        per_op = [(ctx.metric(op._exec_id, "opTime"),
                   ctx.metric(op._exec_id, "numOutputRows", ESSENTIAL),
                   ctx.metric(op._exec_id, "numOutputBatches"))
                  for op in self.fused_ops]
        from ..plan import exec_cache
        cache0 = exec_cache.stats()
        in_rows = 0
        stage_wall = 0.0
        for batch in self.children[0].execute(ctx):
            batch = batch.ensure_device()
            t0 = time.perf_counter()
            with ctx.semaphore.held():
                if self._fast_ok(batch):
                    out, counts = self._run_fused(batch)
                else:
                    out, counts = self._run_stages(batch)
            dt = time.perf_counter() - t0
            stage_wall += dt
            # fused-region attribution: the dispatch wall is one
            # indivisible launch — apportion it evenly so EXPLAIN
            # ANALYZE keeps a per-operator breakdown; rows are exact
            # (one survivor count per stage from the kernel)
            share = dt / len(self.fused_ops)
            for (m_t, m_r, m_b), c in zip(per_op, counts):
                m_t.add(share)
                m_b.add(1)
                if c is not None:
                    m_r.add(c)
            rows_m.add(out.num_rows_raw)
            if isinstance(batch.num_rows_raw, int):
                in_rows += batch.num_rows_raw
            yield out
        if in_rows and stage_wall > 0.0:
            # measured fused-stage device wall -> the cost model: the
            # optimizer learns that fused device regions are cheap
            # instead of pricing them from static per-row guesses.
            # Keyed on exec-cache hit status: a first run whose wall
            # includes jit trace / XLA compile measures the cold start,
            # not the region — only compile-free walls are learned
            # (that keying is what let trusted_engine_wall drop its
            # old >=2-observation workaround to >=1-with-cache-hit)
            compile_free = exec_cache.compile_free_since(cache0)
            from ..plan import cost as plan_cost
            plan_cost.record_op_wall(
                "WholeStageExec", "device", in_rows, stage_wall,
                compile_free=compile_free,
                # under-scale regions measure dispatch floor, not per-row
                # cost — the same sample gate as the analyze.py feed
                # (without it, warm small repeats would accumulate
                # dispatch-dominated quotients past _OP_COST_MIN_ROWS
                # and poison the trusted per-row price)
                min_rows=plan_cost._OP_COST_SAMPLE_MIN_ROWS)

    def _run_fused(self, batch: ColumnarBatch):
        from ..columnar.column import DictColumn
        from ..exprs.compiler import compile_fused_stages
        if self._kernel is None:
            self._kernel = compile_fused_stages(
                self.stages, self.children[0].output_schema())
        outs, count, counts = self._kernel.run(batch)
        cols = []
        for (d, v), f, origin in zip(outs, self._schema.fields,
                                     self._origins):
            src = (batch.column_by_name(origin)
                   if origin is not None else None)
            if isinstance(src, DictColumn):
                cols.append(DictColumn(d, v, f.dtype, src.dictionary))
            else:
                cols.append(DeviceColumn(d, v, f.dtype))
        out = ColumnarBatch(cols, count, self._schema, meta=batch.meta)
        return out, list(counts)

    def _run_stages(self, batch: ColumnarBatch):
        """Per-stage fallback for batches carrying columns the fused
        kernel's plain lanes cannot represent (byte rectangles, lists,
        host columns): the original operators' semantics, one dispatch
        per stage."""
        counts = []
        for st in self.stages:
            if st[0] == "filter":
                batch = self._apply_filter(batch, st[1])
            else:
                batch = self._apply_project(batch, st[1], st[2])
            counts.append(batch.num_rows_raw)
        return batch, counts

    @staticmethod
    def _apply_filter(batch: ColumnarBatch, cond) -> ColumnarBatch:
        from ..exprs.compiler import filter_mixed_batch
        return filter_mixed_batch(cond, batch)

    @staticmethod
    def _apply_project(batch: ColumnarBatch, exprs,
                       out_schema: Schema) -> ColumnarBatch:
        from ..exprs.base import Alias, ColumnRef
        from ..exprs.compiler import compile_projection
        out_cols: List[Optional[object]] = [None] * len(exprs)
        dev_idx = []
        for i, e in enumerate(exprs):
            inner = e.children[0] if isinstance(e, Alias) else e
            if isinstance(inner, ColumnRef):
                out_cols[i] = batch.column_by_name(inner.name)
            else:
                dev_idx.append(i)
        if dev_idx:
            proj = compile_projection([exprs[i] for i in dev_idx],
                                      batch.schema)
            for i, c in zip(dev_idx, proj.run(batch)):
                out_cols[i] = c
        return ColumnarBatch(out_cols, batch.num_rows_raw, out_schema,
                             meta=batch.meta)

    # -------------------------------------------------------------- explain
    def describe(self) -> str:
        return ("WholeStage[fused=["
                + ", ".join(op.describe() for op in self.fused_ops)
                + "]]")
