"""EXPLAIN ANALYZE rendering — the SQL-UI per-operator view.

``df.explain("analyze")`` executes the query, then renders the physical
plan annotated with per-operator output rows, batches, cumulative and
SELF time pulled from ``ExecContext.metrics`` (the GpuMetric registry
analog, GpuExec.scala:54-165). Cumulative time for a pipelined operator
includes the time spent pulling from its children (the iterator chain),
so self time is cumulative minus the children's cumulative, clamped at
zero — the same interval math the trace profiler uses on spans.

Lazy device row counts are forced through the metrics summary view's
single packed fetch, so rendering costs one device round trip total,
not one per operator.
"""
from __future__ import annotations

from typing import Dict

__all__ = ["render_analyzed_plan", "record_learned_op_costs"]


def _fmt_count(v) -> str:
    if v is None:
        return "-"
    try:
        f = float(v)
    except (TypeError, ValueError):
        return str(v)
    if f == int(f):
        return str(int(f))
    return f"{f:.2f}"


def _fmt_ms(seconds: float) -> str:
    return f"{seconds * 1000.0:.1f}ms"


#: physical exec class name -> learned-cost kind (plan/cost.node_kind's
#: key space): device execs and their CPU twins land on the SAME kind so
#: the cost model holds a device AND a host per-row price per operator
#: family. WholeStageExec keeps its own kind (it prices fused regions).
_EXEC_KIND = {
    "TpuFilterExec": "Filter", "CpuFilterExec": "Filter",
    "TpuProjectExec": "Project", "CpuProjectExec": "Project",
    "TpuHashAggregateExec": "Aggregate", "CpuAggregateExec": "Aggregate",
    "TpuHashJoinExec": "Join", "TpuBroadcastHashJoinExec": "Join",
    "TpuNestedLoopJoinExec": "Join", "CpuJoinExec": "Join",
    "TpuSortExec": "Sort", "CpuSortExec": "Sort",
    "TpuWindowExec": "Window", "CpuWindowExec": "Window",
    "TpuExpandExec": "Expand",
    "WholeStageExec": "WholeStageExec",
}


def record_learned_op_costs(physical, ctx, compile_free: bool) -> None:
    """Feed the per-operator SELF times this query measured into the
    cost model's learned per-operator row cost table (plan/cost.py
    _OP_COSTS) — the live feedback loop that replaces the static
    host/device per-row guesses with what this machine measured.

    Self time = cumulative opTime minus the children's cumulative (the
    EXPLAIN ANALYZE interval math); rows = the operator's INPUT rows
    (children's numOutputRows — the rows it processed, matching how the
    cost model charges nodes). Lazy device row counts (jax scalars) are
    SKIPPED rather than forced: this runs on every query and must never
    add a host sync. record_op_wall's per-query sample gate
    (_OP_COST_SAMPLE_MIN_ROWS) drops dispatch-floor-dominated small
    runs; compile-laden runs are dropped wholesale (the exec-cache-hit
    keying).

    What a DEVICE self-time measures — deliberately: device kernels
    dispatch asynchronously (the host-sync-flow lint rule bans mid-pipeline
    forces), so a device operator's metered wall is its dispatch + any
    host-side prep, while the device wait drains in the sink's single
    packed fetch, which the per-query floor already prices. That makes
    the learned device s/row the operator's MARGINAL contribution to
    the query wall — the quantity the per-subtree host-vs-device
    comparison needs — not device occupancy.
    Device-BOUND shapes (where occupancy is the wall) are caught by the
    whole-query engine-wall arbitration and its symmetric exploration
    (plan/cost.py), never by per-node pricing. The distortion left:
    an operator that does sync per batch (the aggregate's speculation
    windows) absorbs its upstream chain's lazy work into its own self
    time — an overestimate, i.e. conservative for device placement."""
    from ..plan.cost import _OP_COST_SAMPLE_MIN_ROWS, record_op_wall

    def raw(node, name):
        m = (ctx.metrics.get(node._exec_id) or {}).get(name)
        v = m.value if m is not None else None
        return v if isinstance(v, (int, float)) else None

    # iterative traversal, deliberately: a recursive closure here would
    # be a function->cell reference cycle pinning ctx (and through it
    # every cached broadcast relation) until the next gc pass — the
    # suite's zero-leak audit relies on refcount-driven cleanup
    try:
        stack = [physical]
        while stack:
            node = stack.pop()
            stack.extend(node.children)
            kind = _EXEC_KIND.get(type(node).__name__)
            # WholeStageExec feeds its own measured dispatch wall from
            # inside execution (exec/wholestage.py) — never double-count
            if kind is None or kind == "WholeStageExec":
                continue
            if kind == "Aggregate" and getattr(node, "pre_stages", None):
                # folded filter/project stages run INSIDE this exec's
                # update kernel, so its self time covers THEIR work too
                # — but the planner still charges the logical Filter /
                # Project nodes their own learned costs on the same
                # rows. Learning "Aggregate" from a folded sample would
                # double-count the folded work in every device estimate
                # for exactly the q9 shapes this feed exists to flip.
                continue
            cum = raw(node, "opTime") or 0.0
            child_cum = sum(raw(c, "opTime") or 0.0
                            for c in node.children)
            self_s = max(0.0, float(cum) - float(child_cum))
            if node.children:
                rows = [raw(c, "numOutputRows") for c in node.children]
                rows_in = (sum(int(r) for r in rows)
                           if all(r is not None for r in rows) else None)
            else:
                r = raw(node, "numOutputRows")
                rows_in = int(r) if r is not None else None
            if rows_in and self_s > 0.0:
                record_op_wall(kind,
                               "device" if node.is_tpu else "host",
                               rows_in, self_s,
                               compile_free=compile_free,
                               min_rows=_OP_COST_SAMPLE_MIN_ROWS)
    except Exception:  # noqa: BLE001 - telemetry must never fail a query
        pass


def render_analyzed_plan(physical, ctx) -> str:
    """Physical tree string with per-operator metric annotations."""
    from ..aux.metrics import metrics_summary
    summary: Dict[str, dict] = dict(metrics_summary(ctx))

    def node_time(node) -> float:
        ms = summary.get(node._exec_id) or {}
        try:
            return float(ms.get("opTime", 0.0) or 0.0)
        except (TypeError, ValueError):
            return 0.0

    def fused_lines(node, indent: int) -> str:
        """Per-operator breakdown INSIDE a fused region
        (exec/wholestage.py): the fused ops are not children in the
        iterator chain, but the region records each one's output rows
        (exact, from the kernel's per-stage survivor counts) and its
        apportioned share of the fused dispatch wall — so EXPLAIN
        ANALYZE keeps per-op rows and self time through fusion."""
        out = ""
        for op in getattr(node, "fused_ops", ()):
            ms = summary.get(op._exec_id) or {}
            t = node_time(op)
            ann = (f"rows={_fmt_count(ms.get('numOutputRows'))} "
                   f"batches={_fmt_count(ms.get('numOutputBatches'))} "
                   f"self={_fmt_ms(t)}")
            out += "  " * (indent + 1) + f"+ {op.describe()} [{ann}]\n"
        return out

    def walk(node, indent: int) -> str:
        ms = summary.get(node._exec_id) or {}
        cum = node_time(node)
        child_cum = sum(node_time(c) for c in node.children)
        self_s = max(0.0, cum - child_cum)
        ann = (f"rows={_fmt_count(ms.get('numOutputRows'))} "
               f"batches={_fmt_count(ms.get('numOutputBatches'))} "
               f"time={_fmt_ms(cum)} self={_fmt_ms(self_s)}")
        marker = "*" if node.is_tpu else "!"
        line = "  " * indent + f"{marker} {node.describe()} [{ann}]\n"
        line += fused_lines(node, indent)
        return line + "".join(walk(c, indent + 1)
                              for c in node.children)

    return walk(physical, 0)
