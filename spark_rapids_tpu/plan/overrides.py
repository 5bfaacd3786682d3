"""The override rule registry: logical plan -> tagged meta -> physical exec.

Reference analog: GpuOverrides.scala (object :438) — wrapAndTagPlan (:4480),
doConvertPlan (:4486), applyOverrides (:4813), and the per-node ExecRule map
(:4121). Explain-only mode honours spark.rapids.tpu.sql.mode
(GpuOverrides.scala:4701).
"""
from __future__ import annotations

import copy
import logging
from typing import Callable, Dict, Optional, Type

from ..config import TpuConf
from ..types import Schema
from ..exec import basic as B
from ..exec import aggregate as A
from ..exec import sort as S
from ..exec.base import TpuExec
from ..trace import core as trace_core
from . import logical as L
from . import tags as T
from .meta import PlanMeta

log = logging.getLogger("spark_rapids_tpu.overrides")

_RULES: Dict[Type, Type[PlanMeta]] = {}


def rule(plan_cls):
    def deco(meta_cls):
        _RULES[plan_cls] = meta_cls
        return meta_cls
    return deco


def wrap_plan(plan: L.LogicalPlan, conf: TpuConf,
              parent=None) -> PlanMeta:
    meta_cls = _RULES.get(type(plan))
    if meta_cls is None:
        meta_cls = _FallbackMeta
    m = meta_cls(plan, conf, parent)
    m.child_metas = [wrap_plan(c, conf, m) for c in plan.children]
    return m


def plan_query(plan: L.LogicalPlan, conf: TpuConf, mesh=None,
               mesh_auto: bool = False) -> TpuExec:
    """tag -> cost-optimize -> (explain) -> convert (ref
    applyOverrides:4813, getOptimizations:4827) -> distribute onto the mesh
    when one is configured (ref GpuShuffleExchangeExecBase: the planner —
    not the user — makes queries distributed)."""
    from .rewrites import prune_columns
    from .op_confs import install_from_conf
    from .cost import OPTIMIZER_ENABLED, plan_signature
    install_from_conf(conf)
    # signature of the plan AS THE USER BUILT IT: the execution sink
    # records measured walls under this same pre-rewrite signature
    # (exec/query.run_query), so lookup and record must agree
    wall_sig = plan_signature(plan)
    digest = None
    if conf.get(OPTIMIZER_ENABLED):
        # structural plan digest (the PR-5 event-log key): the cost
        # model's cache-aware floor asks the executable cache whether
        # this digest's kernels are already compiled — recorded by the
        # sink under the same pre-rewrite digest, so lookup and record
        # must agree. Computed only when the optimizer will consume it
        # (a full-tree hash per planning otherwise buys nothing); the
        # sink reuses it via physical.plan_digest.
        from ..metrics.events import plan_digest
        digest = plan_digest(plan)
    if conf.sql_enabled:
        # TPU-targeted rewrites (distinct-agg expansion, union-of-aggs
        # single-pass) BEFORE pruning: the union rewrite keys on shared
        # scan identity, which pruning's per-branch copies would break.
        # The host oracle path keeps native semantics so differential
        # tests check the rewrites themselves. The sort-free hash
        # distinct applies only off-mesh: the distributed fragment
        # compiler lowers the two-level Aggregate form, not the
        # stateful DistinctFlag operator.
        from .rewrites import (HASH_DISTINCT_ENABLED,
                               push_filters_below_joins, rewrite_plan)
        # one-input conjuncts of a filter above a join go below it first:
        # both engines run the plan in that shape, so a whole-plan host
        # reversion below keeps it
        plan, pushed, above = push_filters_below_joins(plan)
        tr = trace_core.TRACER
        if tr is not None:
            tr.counter("plan.pushdown", {"pushed": pushed,
                                         "above_joins": above}, cat="plan")
        plan0 = plan               # the user's shape, pre-rewrite
        plan = rewrite_plan(
            plan, hash_distinct=(mesh is None
                                 and conf.get(HASH_DISTINCT_ENABLED)))
    else:
        plan0 = plan
    rewritten = plan is not plan0
    plan = prune_columns(plan)
    meta = wrap_plan(plan, conf)
    meta.tag()
    from .cost import apply_cost_optimizer
    decision = None
    if conf.get(OPTIMIZER_ENABLED):
        decision = apply_cost_optimizer(meta, conf, wall_sig=wall_sig,
                                        plan_digest=digest)
        if rewritten and not _any_device_meta(meta):
            # whole-plan host reversion: the TPU-targeted rewrites
            # (distinct expansion/flag, union single-pass) only help
            # the DEVICE engine — their CPU twins are slower than the
            # native host shapes (e.g. a per-row flag pass vs pandas
            # nunique). Re-plan the user's ORIGINAL plan for the host
            # twins; the measured wall still records under wall_sig,
            # so arbitration stays consistent.
            meta = wrap_plan(prune_columns(plan0), conf)
            meta.tag()
            T.revert_to_host(
                meta, "cost-based: whole-plan host placement "
                      "(native shape, no device rewrites)",
                code=T.WHOLE_PLAN_HOST_REVERT)
            decision = ("host (whole-plan host placement: native "
                        "shape, no device rewrites)")
    # coded placement report (plan/tags.py): assembled AFTER tagging and
    # cost optimization so it records the final verdicts; the plan-time
    # INPUT row estimate (summed over scan leaves — the work scale, not
    # the often-tiny aggregate output) rides along for the qualify
    # tool's learned-cost join
    try:
        from .cost import estimate_rows

        def _leaf_rows(p):
            if not p.children:
                return estimate_rows(p)
            return sum(_leaf_rows(c) for c in p.children)

        est_rows = int(_leaf_rows(plan))
    except Exception:  # noqa: BLE001 - diagnostics never fail planning
        est_rows = None
    report = T.build_report(meta, decision=decision, est_rows=est_rows)
    explain = conf.explain
    if explain in ("NOT_ON_TPU", "ALL"):
        out = meta.explain(only_not_on_tpu=(explain == "NOT_ON_TPU"))
        if out:
            log.warning("\n%s", out)
    pexplain = str(conf.get(T.PLACEMENT_EXPLAIN)).upper()
    # NOT_ON_DEVICE is silent for all-device plans (render() always
    # emits at least the verdict line, so gate on recorded tags — the
    # legacy mode's "nothing on host, nothing to say" contract)
    if pexplain == "ALL" or (pexplain == "NOT_ON_DEVICE"
                             and report.counts()):
        log.warning("\n%s", report.render(
            only_not_on_device=(pexplain == "NOT_ON_DEVICE")))
    physical = meta.convert()
    if conf.sql_enabled:
        physical = insert_coalesce(physical, conf)
        from ..parallel.planner import (FUSED_PIPELINE, distribution_gate,
                                        maybe_fuse_single_chip,
                                        try_distribute)
        distributed = None
        if mesh is not None and distribution_gate(physical, conf,
                                                  auto=mesh_auto):
            distributed = try_distribute(physical, conf, mesh)
        if distributed is not None:
            physical = distributed
        elif conf.get(FUSED_PIPELINE):
            # no mesh, auto-mesh below the row threshold, OR nothing in
            # the plan lowered onto the mesh: single-chip fused pipelines
            # still apply (losing them regressed latency-bound joins)
            physical = maybe_fuse_single_chip(physical, conf)
    # whole-stage fusion LAST, over whatever the mesh/fragment lowering
    # left as an operator pipeline: maximal device filter/project chains
    # become one compiled program each (exec/wholestage.py)
    from ..exec.wholestage import fuse_whole_stages
    physical = fuse_whole_stages(physical, conf)
    #: why the cost optimizer placed this plan where it did — EXPLAIN
    #: prints it, so "why is this stage on host" is answerable from the
    #: plan output alone (satellite of ISSUE 6)
    physical.placement_decision = decision
    #: the coded per-operator report (ISSUE 7): explain("placement"),
    #: the fallback metric family, and queryStart event records all
    #: read it off the physical plan
    physical.placement_report = report
    #: pre-rewrite structural digest (None when the optimizer is off):
    #: the sink reuses it to mark the digest warm after a device run
    #: (exec_cache.record_plan_compiled) instead of re-hashing the tree
    physical.plan_digest = digest
    return physical


# ---------------------------------------------------------------------------
# post-conversion: full buckets for the per-batch operators (ref
# GpuTransitionOverrides.insertCoalesce)
# ---------------------------------------------------------------------------

def insert_coalesce(physical: TpuExec, conf: TpuConf) -> TpuExec:
    """Put ``CoalesceBatches[TargetSize]`` where a per-batch operator (the
    stream side of a streaming broadcast join, the input of an aggregate)
    would be handed under-filled batches, through device-only filters and
    projections, by

    - an in-memory scan whose neighbouring batches fit the target
      (``batchSizeRows``) together: the operator goes above the scan;
    - a streaming broadcast join: it hands on a batch a stream batch with
      whatever matched, so a selective one hands on buckets of padding.
      The operator goes above the join and reads the counts, which are
      on the device, a window at a time (exec/basic.py).

    Those operators pay their host and device work per BATCH at the padded
    shape, so two half-empty buckets cost twice what one full one does.
    Not below a sort (a partition-local one answers per partition, which a
    merge would show, and a global one concatenates everything itself),
    and not below whatever else materializes its input itself (the join
    of two big sides, an exchange, the sink). Everything is read off the
    plan: where nothing qualifies the plan is the one it was. No plan
    that reads ``batch.meta`` (``spark_partition_id`` and its kin) gets
    one either: the merged batch carries its first batch's ``meta``."""
    from ..exec.wholestage import _fusible
    target_rows = conf.batch_size_rows
    sites = []      # (parent, child index) of each scan or join to wrap

    def visit(node: TpuExec) -> None:
        fed = None  # index of the child this node consumes batch by batch
        if _streams(node):
            fed = 0 if node.build_side == "right" else 1
        elif isinstance(node, A.TpuHashAggregateExec):
            fed = 0
        if fed is not None:
            parent, i = node, fed
            while _fusible(parent.children[i]):
                parent, i = parent.children[i], 0
            if _fills(parent.children[i], target_rows):
                sites.append((parent, i))
        for c in node.children:
            visit(c)

    visit(physical)
    if not sites or _reads_task_context(physical):
        return physical
    for parent, i in sites:
        parent.children[i] = B.CoalesceBatchesExec(
            parent.children[i], target_rows=target_rows,
            target_bytes=conf.batch_size_bytes)
    return physical


def _streams(node: TpuExec) -> bool:
    """Whether ``node`` is a broadcast join that joins its stream side
    batch by batch."""
    from ..exec.joins import TpuBroadcastHashJoinExec
    return isinstance(node, TpuBroadcastHashJoinExec) and node.streams


def _fills(source: TpuExec, target_rows: int) -> bool:
    """Whether ``source``'s batches are worth filling and concatenate on
    the device: a dictionary, rectangle or host column from a scan would
    send the concat through Arrow and back (each partition has a
    dictionary of its own); a join's build side hands every output ONE
    dictionary, so its strings concatenate as codes."""
    if isinstance(source, B.InMemoryScanExec):
        return (all(f.dtype.device_backed for f in source.output_schema())
                and _scan_underfilled(source, target_rows))
    if not _streams(source) or _stream_batches(source) == 1:
        return False
    from ..types import STRING
    n_left = len(source.children[0].output_schema())
    build = (range(n_left, len(source.output_schema()))
             if source.build_side == "right" else range(n_left))
    return all(f.dtype.device_backed or (f.dtype == STRING and i in build)
               for i, f in enumerate(source.output_schema()))


def _stream_batches(join: TpuExec) -> Optional[int]:
    """The most batches a streaming broadcast join hands on, where the
    plan says: one a batch of its stream side, which filters, projections
    and further such joins pass on one for one from an in-memory scan."""
    from ..exec.wholestage import _fusible
    node = join.children[0 if join.build_side == "right" else 1]
    while _fusible(node):
        node = node.children[0]
    if isinstance(node, B.InMemoryScanExec):
        b = max(int(node.batch_rows), 1)
        return sum(max(-(-t.num_rows // b), 1) for t in node.tables)
    return _stream_batches(node) if _streams(node) else None


def _scan_underfilled(scan, target_rows: int) -> bool:
    """Whether two neighbouring batches of the scan fit ``target_rows``
    together: each table yields ``batch_rows`` rows a batch and then the
    rest, so the first and last batch of each table say it all."""
    b = max(int(scan.batch_rows), 1)
    last = None
    for t in scan.tables:
        full, rest = divmod(t.num_rows, b)
        first = b if full else rest
        if last is not None and last + first <= target_rows:
            return True
        if full and ((full > 1 and 2 * b <= target_rows)
                     or (rest and b + rest <= target_rows)):
            return True
        last = rest if rest or not full else b
    return False


def _reads_task_context(plan: TpuExec) -> bool:
    """Whether anything the plan holds reads the task context off
    ``batch.meta`` (exprs/nondeterministic.py), by the family's one marker,
    ``reset_task_state`` (exec/wholestage.py:_nondeterministic reads the
    same). Fails closed: the walk goes through EVERY attribute of every
    operator and of whatever they hold, containers and objects of any
    class alike, so an expression kept in a holder nobody listed here is
    still found; only what cannot hold an expression is not entered
    (batches, tables and arrays, the conf, modules, classes, functions)."""
    import types
    from ..columnar.batch import ColumnarBatch
    opaque = (ColumnarBatch, TpuConf, type, types.ModuleType,
              types.FunctionType, types.BuiltinFunctionType,
              types.MethodType)
    seen = set()
    stack = [plan]
    while stack:
        v = stack.pop()
        if isinstance(v, opaque) or id(v) in seen:
            continue
        seen.add(id(v))
        if getattr(v, "reset_task_state", None) is not None:
            return True
        if isinstance(v, dict):
            stack.extend(v.values())
        elif isinstance(v, (list, tuple, set, frozenset)):
            stack.extend(v)
        stack.extend(getattr(v, "__dict__", {}).values())
        stack.extend(getattr(v, n, None) for c in type(v).__mro__
                     for n in vars(c).get("__slots__", ()))
    return False


#: logical nodes whose execs are engine-shared pass-throughs: their
#: placement says nothing about which engine runs the real compute
_NEUTRAL_PLANS = (L.LogicalScan, L.ParquetScan, L.Union, L.GlobalLimit,
                  L.BranchAlign)


def _any_device_meta(meta: PlanMeta) -> bool:
    """True when some non-neutral node still plans onto the device
    (scans/unions/limits are engine-shared — they don't count; must
    stay consistent with dataframe._on_device's placement check)."""
    if meta.can_run_on_tpu and not isinstance(meta.plan, _NEUTRAL_PLANS):
        return True
    return any(_any_device_meta(c) for c in meta.child_metas)


def explain_potential_tpu_plan(plan: L.LogicalPlan, conf: TpuConf) -> str:
    """Public ExplainPlan API analog (ref ExplainPlan.scala:28)."""
    from .op_confs import install_from_conf
    install_from_conf(conf)
    meta = wrap_plan(plan, conf)
    meta.tag()
    return meta.explain(only_not_on_tpu=False) or "<entire plan runs on TPU>"


def _list_key_reason(expr, schema):
    """Keys (join/group/partition/window) cannot be list-typed: the key
    hash/compare kernels are 1D. List-typed VALUES are fine in project/
    filter pipelines (columnar/nested.py); Spark allows array keys, so a
    list key converts the exec to its CPU twin."""
    from ..types import ArrayType
    if isinstance(expr.data_type(schema), ArrayType):
        return "list-typed keys compare on host"
    return None


class _FallbackMeta(PlanMeta):
    def tag_self(self):
        self.will_not_work_on_tpu(
            f"no TPU rule registered for {type(self.plan).__name__}",
            code=T.OP_UNSUPPORTED)

    def convert_to_cpu(self, children):
        raise NotImplementedError(
            f"no conversion for {type(self.plan).__name__}")


@rule(L.LogicalScan)
class ScanMeta(PlanMeta):
    def convert_to_tpu(self, children):
        return B.InMemoryScanExec(self.plan.tables, self.plan._schema,
                                  columns=self.plan.columns)

    convert_to_cpu = convert_to_tpu  # scan is shared (host decode either way)


@rule(L.ParquetScan)
class ParquetScanMeta(PlanMeta):
    def convert_to_tpu(self, children):
        from ..io.parquet import ParquetScanExec
        return ParquetScanExec(self.plan.paths, self.plan.schema(),
                               self.plan.columns, self.conf)

    convert_to_cpu = convert_to_tpu


@rule(L.OrcScan)
class OrcScanMeta(PlanMeta):
    def convert_to_tpu(self, children):
        from ..io.orc import OrcScanExec
        return OrcScanExec(self.plan.paths, self.plan.schema(),
                           self.plan.columns, self.conf)

    convert_to_cpu = convert_to_tpu


@rule(L.AvroScan)
class AvroScanMeta(PlanMeta):
    def convert_to_tpu(self, children):
        from ..io.avro import AvroScanExec
        return AvroScanExec(self.plan.paths, self.plan.schema(),
                            self.plan.columns, self.conf)

    convert_to_cpu = convert_to_tpu


@rule(L.Project)
class ProjectMeta(PlanMeta):
    def tag_self(self):
        schema = self.plan.children[0].schema()
        for e in self.plan.exprs:
            r = e.fully_device_supported(schema)
            if r:
                # per-expression fallback stays inside TpuProjectExec;
                # recorded for explain parity with the reference
                self.note_expr_fallback(f"<{e.name_hint}> runs on host: {r}",
                                        code=T.EXPR_UNSUPPORTED,
                                        expr=e.name_hint)

    def convert_to_tpu(self, children):
        return B.TpuProjectExec(self.plan.exprs, children[0])

    def convert_to_cpu(self, children):
        return B.CpuProjectExec(self.plan.exprs, children[0])


@rule(L.Filter)
class FilterMeta(PlanMeta):
    def tag_self(self):
        schema = self.plan.children[0].schema()
        r = self.plan.condition.fully_device_supported(schema)
        if r:
            # string predicates over dict-coded columns still run on the
            # device via dictionary evaluation (compiler.py
            # DictFilterEvaluator; ref stringFunctions.scala families)
            from ..exprs.compiler import build_dict_filter
            if build_dict_filter(self.plan.condition, schema) is not None:
                self.note_expr_fallback(
                    "string predicate evaluated over the dictionary",
                    code=T.EXPR_DICT_EVAL,
                    expr=self.plan.condition.name_hint)
                return
            self.will_not_work_on_tpu(f"filter condition: {r}",
                                      code=T.EXPR_UNSUPPORTED,
                                      expr=self.plan.condition.name_hint)

    def convert_to_tpu(self, children):
        self._push_down_predicate(children[0])
        ex = B.TpuFilterExec(self.plan.condition, children[0])
        from .cost import plan_signature
        ex.plan_sig = plan_signature(self.plan)   # measured-rows feedback
        return ex

    def convert_to_cpu(self, children):
        self._push_down_predicate(children[0])
        ex = B.CpuFilterExec(self.plan.condition, children[0])
        from .cost import plan_signature
        ex.plan_sig = plan_signature(self.plan)
        return ex

    def _push_down_predicate(self, child_exec):
        """Predicate pushdown into file scans for row-group / delta-file
        skipping (ref GpuParquetScan filterBlocks:670 + delta data
        skipping) and into cached scans for batch skipping via the
        embedded parquet statistics (ref ParquetCachedBatchSerializer).
        The filter itself still runs — pruning is conservative, so this
        is purely an IO reduction."""
        from ..exec.cached import ParquetCachedScanExec
        from ..io.file_scan import FileScanBase
        cond = self.plan.condition
        refs = set(cond.references())
        node = child_exec
        # look through projections that pass the referenced columns
        # through unchanged (the exec's own passthrough map, restricted
        # to un-renamed columns)
        while isinstance(node, B.TpuProjectExec):
            same_name = {n for i, n in node.passthrough.items()
                         if node.exprs[i].name_hint == n}
            if not refs <= same_name:
                return
            node = node.children[0]
        if (isinstance(node, (FileScanBase, ParquetCachedScanExec))
                and node.predicate is None):
            names = set(node.output_schema().names())
            if refs <= names:
                node.set_predicate(cond)


@rule(L.Aggregate)
class AggregateMeta(PlanMeta):
    def tag_self(self):
        from ..types import STRING
        schema = self.plan.children[0].schema()
        for g in self.plan.groupings:
            r = g.fully_device_supported(schema)
            lk = None if r else _list_key_reason(g, schema)
            # string group keys stay on the TPU path: the exec
            # dictionary-encodes them to device int32 codes (evaluated on
            # host, grouped on device, decoded at finalize)
            if (r or lk) and g.data_type(schema) != STRING:
                self.will_not_work_on_tpu(
                    f"grouping <{g.name_hint}>: {r or lk}",
                    code=(T.EXPR_UNSUPPORTED if r else T.LIST_KEY_HOST),
                    expr=g.name_hint)
        for a in self.plan.aggs:
            r = a.device_unsupported_reason(schema)
            if r:
                self.will_not_work_on_tpu(f"aggregate <{a.name_hint}>: {r}",
                                          code=T.EXPR_UNSUPPORTED,
                                          expr=a.name_hint)
            if not hasattr(a, "update"):
                self.will_not_work_on_tpu(
                    f"aggregate <{a.name_hint}> has no device implementation",
                    code=T.EXPR_UNSUPPORTED, expr=a.name_hint)
            if a.distinct:
                # reaches here only when rewrites.py could not expand it
                # (multiple distinct columns / non-decomposable mix)
                self.will_not_work_on_tpu(
                    f"aggregate <{a.name_hint}>: DISTINCT form not "
                    "expandable to the two-level device aggregation",
                    code=T.AGG_DISTINCT_HOST, expr=a.name_hint)

    def convert_to_tpu(self, children):
        hint = getattr(self.plan, "many_groups_hint", False)
        cards = getattr(self.plan, "int_key_cards", None)
        from ..exec.wholestage import AGG_FUSION_ENABLED
        if self.conf.get(AGG_FUSION_ENABLED):
            child, stages, eval_schema = self._fold_stages(children[0])
        else:
            # unfused reference path (byte-identical results, one
            # dispatch + one compaction per stage) — the differential
            # oracle for the fused partial-agg kernel
            child, stages, eval_schema = children[0], None, None
        if not self.plan.groupings:
            self._widen_scan_batches(child if stages else children[0])
        if stages:
            return A.TpuHashAggregateExec(self.plan.groupings,
                                          self.plan.aggs, child,
                                          pre_stages=stages,
                                          eval_schema=eval_schema,
                                          many_groups_hint=hint,
                                          int_key_cards=cards)
        return A.TpuHashAggregateExec(self.plan.groupings, self.plan.aggs,
                                      children[0], many_groups_hint=hint,
                                      int_key_cards=cards)

    def _widen_scan_batches(self, node):
        """A GLOBAL aggregation's steady-state cost is per-dispatch
        latency (the update kernel is elementwise + reductions): feed it
        the widest batches the memory runtime allows. A single input
        batch upgrades the whole query to the fused one-dispatch
        one-fetch path (_fast_single_batch). Group-keyed aggregations
        keep the default width — wider batches would inflate their
        per-batch group buckets."""
        from ..config import AGG_WIDE_BATCH_ROWS
        from ..exec.distinct_flag import HashDistinctFlagExec
        wide = int(self.conf.get(AGG_WIDE_BATCH_ROWS))
        while isinstance(node, (B.TpuFilterExec, B.TpuProjectExec,
                                HashDistinctFlagExec)):
            node = node.children[0]
        if isinstance(node, B.InMemoryScanExec):
            if wide <= 0:
                # auto ceiling (ADVICE r5): "whole partition" is only
                # safe while the batch plausibly fits device memory —
                # gate the widening on estimated bytes against half the
                # HBM budget instead of widening unconditionally and
                # leaning on OOM retry/split churn to survive it
                total = max((t.num_rows for t in node.tables), default=0)
                wide = min(total, self._wide_batch_row_cap(node))
            node.batch_rows = max(node.batch_rows, wide, 1)

    def _wide_batch_row_cap(self, scan) -> int:
        """Estimated-byte gate for scan widening: rows such that one
        batch of this scan's schema stays within HALF the device budget.
        Per-row bytes are the LARGER of the schema estimate (fixed-width
        lanes + validity) and the scan's actual Arrow bytes per row, so
        variable-width columns (strings: dict codes or byte rectangles
        on device) are costed from their real data, not a flat guess."""
        import numpy as np
        from ..mem.manager import MemoryManager
        row_bytes = 0
        for f in scan.output_schema():
            np_dt = getattr(f.dtype, "np_dtype", None)
            row_bytes += (np.dtype(np_dt).itemsize if np_dt is not None
                          else 16) + 1     # +1: validity lane
        total_rows = sum(t.num_rows for t in scan.tables)
        if total_rows:
            cols = scan.columns
            data_bytes = sum(
                (t.select(cols) if cols is not None else t).nbytes
                for t in scan.tables)
            row_bytes = max(row_bytes, -(-data_bytes // total_rows))
        budget = MemoryManager.get(self.conf).budget
        return max(1, (budget // 2) // max(1, row_bytes))

    def _fold_stages(self, child):
        """Fold a chain of device-only Filter/Project execs below the
        aggregate INTO its update kernel: scan→filter→project→groupby
        becomes one XLA computation — no per-stage compaction kernels or
        host syncs (the device round trip is the unit of cost on TPU)."""
        from ..exprs.base import ColumnRef
        from ..types import STRING
        eval_schema = child.output_schema()
        stages, node = [], child
        while True:
            if (isinstance(node, B.TpuFilterExec)
                    and node.condition.fully_device_supported(
                        node.children[0].output_schema()) is None):
                stages.append(("filter", node.condition))
                node = node.children[0]
            elif isinstance(node, B.TpuProjectExec) and not node.host_idx:
                stages.append(("project", node.exprs, node.output_schema()))
                node = node.children[0]
            else:
                break
        if not stages:
            return child, None, None
        # string group keys are dictionary-encoded OUTSIDE the kernel from
        # the folded input batch — they must be plain refs (possibly
        # aliased) present there. Int-carded keys (int_key_cards) need
        # the same: their direct-addressing operands read the key COLUMN
        # from the batch, so folding away the projection that produces it
        # would silently demote the plan to the sort path.
        from ..exprs.base import Alias
        in_names = set(node.output_schema().names())
        cards = getattr(self.plan, "int_key_cards",
                        [None] * len(self.plan.groupings))
        for gi, g in enumerate(self.plan.groupings):
            inner = g.children[0] if isinstance(g, Alias) else g
            needs_column = (g.data_type(eval_schema) == STRING
                            or (gi < len(cards) and cards[gi]))
            if needs_column and not (isinstance(inner, ColumnRef)
                                     and inner.name in in_names):
                return child, None, None
        stages.reverse()
        return node, stages, eval_schema

    def convert_to_cpu(self, children):
        return A.CpuAggregateExec(self.plan.groupings, self.plan.aggs,
                                  children[0])


@rule(L.Sort)
class SortMeta(PlanMeta):
    def tag_self(self):
        schema = self.plan.children[0].schema()
        for o in self.plan.orders:
            r = o.expr.fully_device_supported(schema)
            if r:
                self.will_not_work_on_tpu(
                    f"sort key <{o.expr.name_hint}>: {r}",
                    code=T.EXPR_UNSUPPORTED, expr=o.expr.name_hint)
        from ..types import STRING
        for f in schema.fields:
            # the selection kernel of a LIMIT above (exec/sort.py) picks
            # rows by the keys alone and gathers a string payload by the
            # picked rows, in whatever form the column has
            if not f.dtype.device_backed and not (
                    f.dtype == STRING and self.plan.limit is not None):
                self.will_not_work_on_tpu(
                    f"column {f.name}: {f.dtype.name} payload is host-only",
                    code=T.DTYPE_HOST_ONLY)

    def convert_to_tpu(self, children):
        return S.TpuSortExec(self.plan.orders, children[0],
                             self.plan.global_sort, self.plan.limit)

    def convert_to_cpu(self, children):
        return S.CpuSortExec(self.plan.orders, children[0],
                             self.plan.global_sort)


@rule(L.GlobalLimit)
class LimitMeta(PlanMeta):
    def convert_to_tpu(self, children):
        return B.LimitExec(self.plan.n, children[0])

    convert_to_cpu = convert_to_tpu


@rule(L.LocalLimit)
class LocalLimitMeta(LimitMeta):
    pass


@rule(L.Union)
class UnionMeta(PlanMeta):
    def convert_to_tpu(self, children):
        return B.UnionExec(children)

    convert_to_cpu = convert_to_tpu


@rule(L.RangeRel)
class RangeMeta(PlanMeta):
    def convert_to_tpu(self, children):
        p = self.plan
        return B.TpuRangeExec(p.start, p.end, p.step, p.name)

    convert_to_cpu = convert_to_tpu


@rule(L.Sample)
class SampleMeta(PlanMeta):
    def convert_to_tpu(self, children):
        return B.TpuSampleExec(self.plan.fraction, self.plan.seed, children[0])

    convert_to_cpu = convert_to_tpu


@rule(L.Expand)
class ExpandMeta(PlanMeta):
    def tag_self(self):
        schema = self.plan.children[0].schema()
        for p in self.plan.projections:
            for e in p:
                r = e.fully_device_supported(schema)
                if r:
                    self.will_not_work_on_tpu(f"expand <{e.name_hint}>: {r}",
                                              code=T.EXPR_UNSUPPORTED,
                                              expr=e.name_hint)

    def convert_to_tpu(self, children):
        return B.TpuExpandExec(self.plan.projections, self.plan.names,
                               children[0])

    def convert_to_cpu(self, children):
        raise NotImplementedError("CPU expand fallback not implemented")


@rule(L.DistinctFlag)
class DistinctFlagMeta(PlanMeta):
    def tag_self(self):
        schema = self.plan.children[0].schema()
        for e in self.plan.key_exprs + [self.plan.value_expr]:
            r = e.fully_device_supported(schema)
            if r:
                self.will_not_work_on_tpu(
                    f"distinct-flag <{e.name_hint}>: {r}",
                    code=T.EXPR_UNSUPPORTED, expr=e.name_hint)

    def convert_to_tpu(self, children):
        from ..exec.distinct_flag import HashDistinctFlagExec
        p = self.plan
        return HashDistinctFlagExec(p.key_exprs, p.value_expr,
                                    p.flag_name, children[0])

    def convert_to_cpu(self, children):
        from ..exec.distinct_flag import CpuDistinctFlagExec
        p = self.plan
        return CpuDistinctFlagExec(p.key_exprs, p.value_expr,
                                   p.flag_name, children[0])


@rule(L.Generate)
class GenerateMeta(PlanMeta):
    def tag_self(self):
        from ..exprs.base import Unsupported
        schema = self.plan.children[0].schema()
        try:
            self.plan.generator.generator_output(schema)
        except Unsupported as e:
            self.will_not_work_on_tpu(str(e), code=T.EXPR_UNSUPPORTED)

    def convert_to_tpu(self, children):
        from ..exec.generate import TpuGenerateExec
        p = self.plan
        return TpuGenerateExec(p.generator, p.required_cols, children[0],
                               p.output_names)

    convert_to_cpu = convert_to_tpu


@rule(L.Join)
class JoinMeta(PlanMeta):
    def tag_self(self):
        ls = self.plan.children[0].schema()
        rs = self.plan.children[1].schema()
        for side, keys, schema in (("left", self.plan.left_keys, ls),
                                   ("right", self.plan.right_keys, rs)):
            for k in keys:
                r = k.fully_device_supported(schema)
                lk = None if r else _list_key_reason(k, schema)
                if r or lk:
                    self.will_not_work_on_tpu(
                        f"{side} key <{k.name_hint}>: {r or lk}",
                        code=(T.EXPR_UNSUPPORTED if r else T.LIST_KEY_HOST),
                        expr=k.name_hint)
        if self.plan.condition is not None:
            joined = Schema(list(ls.fields) + list(rs.fields))
            r = self.plan.condition.fully_device_supported(joined)
            if r:
                self.will_not_work_on_tpu(
                    f"join condition <{self.plan.condition.name_hint}>: {r}",
                    code=T.EXPR_UNSUPPORTED,
                    expr=self.plan.condition.name_hint)

    def _auto_broadcast(self):
        """Pick a broadcast side from plan-time size estimates when the
        user gave no hint (ref Spark autoBroadcastJoinThreshold + the
        reference's AQE join-strategy switching,
        GpuOverrides.scala:4681)."""
        from ..config import AUTO_BROADCAST_THRESHOLD
        from .cost import plan_signature, runtime_size
        from .rewrites import estimated_size_bytes
        p = self.plan
        thr = int(self.conf.get(AUTO_BROADCAST_THRESHOLD))
        if thr <= 0:
            return None

        def side_size(child):
            # MEASURED size from a previous materialization of this
            # subtree beats any estimate (the AQE stage-stats analog,
            # ref GpuCustomShuffleReaderExec)
            meas = runtime_size(plan_signature(child))
            est = estimated_size_bytes(child)
            return (meas if meas is not None else est), meas, est
        r_ok = p.join_type in ("inner", "left", "leftsemi", "leftanti")
        l_ok = p.join_type in ("inner", "right")
        rs, rm, re_ = side_size(p.children[1]) if r_ok else (None,) * 3
        ls, lm, le = side_size(p.children[0]) if l_ok else (None,) * 3
        cand, est_cand = [], []
        for sz, est, side in ((rs, re_, "right"), (ls, le, "left")):
            if sz is not None and sz <= thr:
                cand.append((sz, side))
            if est is not None and est <= thr:
                est_cand.append((est, side))
        choice = min(cand)[1] if cand else None
        est_choice = min(est_cand)[1] if est_cand else None
        if choice != est_choice:
            self._aqe_broadcast_decision(choice, est_choice, thr,
                                         {"right": rm, "left": lm})
        return choice

    def _aqe_broadcast_decision(self, choice, est_choice, thr, measured):
        """AQE join-strategy switch surfaced as a decision: a MEASURED
        side size flipped the broadcast pick away from what the
        plan-time estimate alone would have chosen."""
        from .. import aqe as aqe_mod
        log = aqe_mod.LOG
        if log is None:
            return
        try:  # tpulint: never-raise
            from ..aqe import AQE_BROADCAST_DEMOTE_ENABLED
            if choice is None:
                # estimate said broadcast, measurement came in over
                if not self.conf.get(AQE_BROADCAST_DEMOTE_ENABLED):
                    return
                log.record(aqe_mod.make_decision(
                    aqe_mod.BROADCAST_DEMOTE,
                    detail=f"{est_choice} side measured "
                           f"{measured.get(est_choice)}B > threshold "
                           f"{thr}B -> shuffled join", parts=1))
            else:
                # estimate said shuffle (or the other side), the
                # measured side came in under the threshold
                log.record(aqe_mod.make_decision(
                    aqe_mod.BROADCAST_PROMOTE,
                    detail=f"{choice} side measured "
                           f"{measured.get(choice)}B <= threshold "
                           f"{thr}B -> broadcast join", parts=1))
        except Exception:
            pass

    def convert_to_tpu(self, children):
        from ..exec.joins import (TpuBroadcastHashJoinExec, TpuHashJoinExec,
                                  TpuNestedLoopJoinExec)
        from ..shuffle.broadcast import BroadcastExchangeExec
        p = self.plan
        if p.join_type == "cross" or not p.left_keys:
            # no equi keys: nested loop (ref GpuBroadcastNestedLoopJoinExec)
            return TpuNestedLoopJoinExec(children[0], children[1],
                                         p.join_type, p.condition)
        if p.broadcast is None:
            p = copy.copy(p)
            p.broadcast = self._auto_broadcast()
        from .cost import plan_signature
        sigs = (plan_signature(p.children[0]),
                plan_signature(p.children[1]))
        if p.broadcast == "right":
            j = TpuBroadcastHashJoinExec(
                children[0], BroadcastExchangeExec(children[1]), p.join_type,
                p.left_keys, p.right_keys, p.condition, build_side="right")
        elif p.broadcast == "left":
            j = TpuBroadcastHashJoinExec(
                BroadcastExchangeExec(children[0]), children[1], p.join_type,
                p.left_keys, p.right_keys, p.condition, build_side="left")
        else:
            j = TpuHashJoinExec(children[0], children[1], p.join_type,
                                p.left_keys, p.right_keys, p.condition)
        # runtime-stats hookup: the exec records each side's MEASURED
        # bytes under these signatures when it materializes them, and the
        # join's OUTPUT rows under its own (the cost model's join-output
        # estimates are the crudest — measured feedback re-plans e.g. a
        # dimension-filtered join at its real, tiny output size)
        j.side_sigs = sigs
        j.plan_sig = plan_signature(self.plan)
        return j

    def convert_to_cpu(self, children):
        from ..exec.joins import CpuJoinExec
        from .cost import plan_signature
        p = self.plan
        ex = CpuJoinExec(children[0], children[1], p.join_type,
                         p.left_keys, p.right_keys, p.condition)
        ex.plan_sig = plan_signature(self.plan)
        return ex


@rule(L.Repartition)
class RepartitionMeta(PlanMeta):
    def tag_self(self):
        schema = self.plan.children[0].schema()
        for k in self.plan.keys:
            r = k.fully_device_supported(schema)
            lk = None if r else _list_key_reason(k, schema)
            if r or lk:
                self.will_not_work_on_tpu(
                    f"partition key <{k.name_hint}>: {r or lk}",
                    code=(T.EXPR_UNSUPPORTED if r else T.LIST_KEY_HOST),
                    expr=k.name_hint)
            if self.plan.mode == "hash":
                # device murmur3 covers fewer types than device storage
                # (e.g. DOUBLE hashes on host only — hash_fns device notes)
                from ..exprs.hash_fns import device_hashable
                hr = device_hashable.reason_not_supported(k.data_type(schema))
                if hr:
                    self.will_not_work_on_tpu(
                        f"hash partition key <{k.name_hint}>: {hr}",
                        code=T.HASH_KEY_HOST, expr=k.name_hint)

    def _num_parts(self):
        from ..config import DEFAULT_SHUFFLE_PARTITIONS
        n = self.plan.num_partitions
        return n if n is not None \
            else int(self.conf.get(DEFAULT_SHUFFLE_PARTITIONS))

    def convert_to_tpu(self, children):
        from ..shuffle.exchange import ShuffleExchangeExec
        p = self.plan
        return ShuffleExchangeExec(
            children[0], self._num_parts(), p.keys, p.mode, self.conf,
            adaptive_ok=p.adaptive_ok)

    def convert_to_cpu(self, children):
        from ..shuffle.exchange import CpuShuffleExchangeExec
        p = self.plan
        return CpuShuffleExchangeExec(children[0], self._num_parts(),
                                      p.keys, p.mode)


@rule(L.BranchAlign)
class BranchAlignMeta(PlanMeta):
    def convert_to_tpu(self, children):
        p = self.plan
        return B.BranchAlignExec(p.n, p.fill_zero, children[0])

    convert_to_cpu = convert_to_tpu


@rule(L.WriteFile)
class WriteMeta(PlanMeta):
    def convert_to_tpu(self, children):
        from ..io.writers import FileWriteExec
        p = self.plan
        return FileWriteExec(children[0], p.path, p.file_format, p.mode,
                             p.partition_by, getattr(p, "options", None))

    convert_to_cpu = convert_to_tpu


@rule(L.Window)
class WindowMeta(PlanMeta):
    def tag_self(self):
        schema = self.plan.children[0].schema()
        from ..types import ArrayType
        for f in schema.fields:
            if isinstance(f.dtype, ArrayType):
                # list payloads don't ride the window kernels (they own
                # their 1D column layout); CPU window handles them
                self.will_not_work_on_tpu(
                    f"column {f.name}: list payload is host-only in windows",
                    code=T.DTYPE_HOST_ONLY)
        for e, spec, name in self.plan.window_exprs:
            for pk in spec.partition_by:
                r = pk.fully_device_supported(schema)
                lk = None if r else _list_key_reason(pk, schema)
                if r or lk:
                    self.will_not_work_on_tpu(
                        f"window partition key: {r or lk}",
                        code=(T.EXPR_UNSUPPORTED if r else T.LIST_KEY_HOST),
                        expr=pk.name_hint)

    def convert_to_tpu(self, children):
        from ..exec.window import TpuWindowExec
        # terminal (root) windows feed a host collect: the cost model may
        # run their kernel on host XLA (see WINDOW_HOST_SINK_ROWS)
        return TpuWindowExec(self.plan.window_exprs, children[0],
                             host_sink=self.parent is None)

    def convert_to_cpu(self, children):
        from ..exec.window import CpuWindowExec
        return CpuWindowExec(self.plan.window_exprs, children[0])


@rule(L.MapInPandas)
class MapInPandasMeta(PlanMeta):
    def convert_to_tpu(self, children):
        from ..exec.python_execs import MapInPandasExec
        return MapInPandasExec(children[0], self.plan.fn, self.plan.schema())

    convert_to_cpu = convert_to_tpu


@rule(L.FlatMapGroupsInPandas)
class FlatMapGroupsInPandasMeta(PlanMeta):
    def convert_to_tpu(self, children):
        from ..exec.python_execs import FlatMapGroupsInPandasExec
        return FlatMapGroupsInPandasExec(children[0], self.plan.keys,
                                         self.plan.fn, self.plan.schema())

    convert_to_cpu = convert_to_tpu
