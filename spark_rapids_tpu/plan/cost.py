"""Cost-based optimizer (ref CostBasedOptimizer.scala; defaults from
RapidsConf.scala:2126-2156 — CPU exec 2.0e-4 s/row, GPU exec 1.0e-4 s/row,
plus row<->columnar transition costs).

After tagging, walk the meta tree bottom-up estimating per-subtree wall cost
under two placements (device vs host). A node that is TPU-capable but whose
device cost — including the transitions its placement would force — exceeds
its host cost is reverted with an explicit "cost-based" reason, exactly the
reference's "it is not worth moving this subtree to the GPU" behavior.

Row estimates are deliberately crude (the reference's are too): scans count
real rows, filters halve, aggregates collapse by ~the group-ratio guess,
joins multiply selectivity. The model's job is to catch egregious cases
(tiny subtree sandwiched between CPU sections), not to be a planner.
"""
from __future__ import annotations

import logging
from typing import Optional

from ..config import (CBO_ENABLED as OPTIMIZER_ENABLED,
                      CPU_EXEC_COST_PER_ROW as CPU_EXEC_COST,
                      TPU_EXEC_COST_PER_ROW as TPU_EXEC_COST,
                      TpuConf, register)
from . import logical as L
from .meta import PlanMeta

log = logging.getLogger(__name__)

TRANSITION_COST = register(
    "spark.rapids.tpu.sql.optimizer.transition.cost", 1.0e-8,
    "Estimated cost per row of a host<->device transition "
    "(row->columnar H2D or columnar->row D2H; ref "
    "spark.rapids.sql.optimizer.cpu.exec.rowToColumnarCost).",
    internal=True)

DEVICE_QUERY_FLOOR = register(
    "spark.rapids.tpu.sql.optimizer.device.queryFloorSeconds", 0.12,
    "Fixed wall cost a COLD device placement pays once per query: jit "
    "trace + (persistent-tier-miss) XLA compile + kernel dispatch + the "
    "D2H result fetch. The default was calibrated on an earlier "
    "development backend and is not measured on the attached chip "
    "(ROADMAP Speed 2 re-derives it per device kind). "
    "Split against dispatchFloorSeconds: a plan digest whose compiled "
    "executables are already warm in the two-tier executable cache "
    "(plan/exec_cache.py) pays only the dispatch component, so warm "
    "repeats — the serving case — are costed without the compile floor. "
    "Queries whose whole-plan host estimate beats device+floor revert to "
    "the host engine — the reference's CostBasedOptimizer transition "
    "revert generalized to the per-query floor that dominates small "
    "inputs.", commonly_used=True)

DEVICE_DISPATCH_FLOOR = register(
    "spark.rapids.tpu.sql.optimizer.device.dispatchFloorSeconds", 0.02,
    "The dispatch-only component of the per-query device floor: kernel "
    "launch + D2H result fetch with every executable already resolved "
    "from the live or persistent compile cache (plan/exec_cache.py). "
    "Charged instead of queryFloorSeconds when the plan digest is known "
    "compiled — the cache-aware re-costing that flips warm repeats of "
    "small queries onto the device. Never charged above "
    "queryFloorSeconds.", commonly_used=True)

#: vectorized per-row host cost by node kind (numpy/pyarrow kernels, NOT
#: the reference's per-row-interpreter 2e-4 — this engine's host twin is
#: columnar). Calibrated against measured 1M-row pandas times
#: (docs/performance.md headline table).
_HOST_ROW_COST = {
    L.LogicalScan: 0.0,          # both engines share the host decode
    L.ParquetScan: 0.0,
    L.Filter: 6.0e-9,
    L.Project: 8.0e-9,
    L.Join: 4.0e-8,              # hash probe per stream row
    L.Sort: 1.5e-7,
    # the CPU twin (CpuWindowExec, pandas per-window apply) measures
    # ~1e-5 s/row — NOT the host-sink numpy path, which belongs to
    # TpuWindowExec and prices itself (WINDOW_HOST_SINK_ROWS); a cheap
    # estimate here would revert windows onto the slow twin
    L.Window: 1.0e-5,
    L.Expand: 2.0e-8,
}
_HOST_ROW_DEFAULT = 2.0e-8


#: logical node type -> learned-cost-table kind name (the key space of
#: record_op_wall / learned_row_cost). One kind per operator family —
#: coarse on purpose: the learned table prices "what a Filter costs per
#: row on this machine", not one entry per query shape (shapes are the
#: engine walls' job).
_KIND_OF = {
    L.Filter: "Filter",
    L.Project: "Project",
    L.Aggregate: "Aggregate",
    L.Join: "Join",
    L.Sort: "Sort",
    L.Window: "Window",
    L.Expand: "Expand",
}


def node_kind(plan) -> Optional[str]:
    """Learned-cost kind for a logical node (None = not learned)."""
    return _KIND_OF.get(type(plan))


def _expr_weight(e) -> int:
    """Expression-tree node count: one vectorized host kernel pass per
    node is the cost unit (a 5-comparison filter costs ~5x one compare)."""
    return 1 + sum(_expr_weight(c) for c in getattr(e, "children", []))


def _host_node_cost(plan, rows_in: float, cpu_scale: float) -> float:
    """Vectorized host cost of one node over its INPUT rows. A TRUSTED
    learned host row cost for the node's kind (fed back from the host
    twin's measured per-operator self-times) replaces the static table —
    what this machine measured beats any calibration constant."""
    kind = node_kind(plan)
    if kind is not None:
        lc = learned_row_cost(kind, "host")
        if lc is not None:
            return lc * rows_in
    per_pass = 3.0e-9       # one numpy/arrow elementwise pass per row
    if isinstance(plan, L.Aggregate):
        if plan.groupings:
            c = 1.2e-7 + 2.0e-8 * len(plan.aggs)   # hash groupby
        else:
            c = 8.0e-9 * max(len(plan.aggs), 1)    # global reductions
        c += per_pass * sum(_expr_weight(a.child)
                            for a in plan.aggs
                            if getattr(a, "child", None) is not None)
        return c * rows_in * cpu_scale
    if isinstance(plan, L.Filter):
        return (per_pass * (1 + _expr_weight(plan.condition))
                * rows_in * cpu_scale)
    if isinstance(plan, L.Project):
        w = sum(_expr_weight(e) for e in plan.exprs)
        return per_pass * w * rows_in * cpu_scale
    return (_HOST_ROW_COST.get(type(plan), _HOST_ROW_DEFAULT)
            * rows_in * cpu_scale)


# ---------------------------------------------------------------------------
# adaptive runtime statistics (ref GpuCustomShuffleReaderExec / the
# reference's AQE stage stats, GpuOverrides.scala:4681-4730): execs record
# the MEASURED size of materialized plan subtrees keyed by a structural
# signature; the planner prefers these over the crude estimates below, so
# a join strategy mis-planned from estimates flips on the next planning
# of the same shape.
# ---------------------------------------------------------------------------

_RUNTIME_SIZES: dict = {}
_RUNTIME_SIZES_MAX = 4096

# In-memory tables are tagged with a CONTENT fingerprint (schema + row
# count + hashed head/tail slices), memoized per object id. Content tags
# are stable across processes — measured walls and row counts persist to
# the on-disk stats store (stats_store.py) and a fresh process plans a
# previously-seen query correctly on its FIRST execution (the cross-
# process analog of the reference's AQE stage statistics,
# GpuOverrides.scala:4691-4730). The id-memo is only a cache: a recycled
# object id can at worst recompute the fingerprint, never serve a stale
# one, because the memo pins the table object itself.
import weakref  # noqa: E402

_SIG_PIN: "weakref.WeakValueDictionary" = weakref.WeakValueDictionary()
_SIG_MEMO: dict = {}


def _drop_memo(tid: int):
    _SIG_MEMO.pop(tid, None)


def _fingerprint_table(t) -> str:
    import hashlib
    h = hashlib.blake2b(digest_size=10)
    h.update(str(t.schema).encode())
    h.update(str(t.num_rows).encode())
    n = t.num_rows
    for sl in (t.slice(0, 128), t.slice(max(n - 128, 0), 128),
               t.slice(n // 2, 64)):
        try:
            # hash VALUES of the sampled rows, never buffers: pyarrow
            # slices are zero-copy views whose .buffers() return the
            # UNTRIMMED parent buffers (hashing the whole table three
            # times, ~1.3 s at 20M rows, measured)
            import pickle
            h.update(pickle.dumps(sl.to_pydict(), protocol=4))
        except Exception:       # unpicklable cell types: length-only tag
            h.update(b"?")
    return h.hexdigest()


def _evict_local_sigs(tag: str):
    """Drop every stat whose signature embeds a process-local '#<id>#'
    tag when that object dies — a recycled id must never serve another
    table's measurements (the content-fingerprint path needs no eviction;
    this guards only the non-Arrow fallback)."""
    for store in (_RUNTIME_SIZES, _RUNTIME_ROWS):
        for k in [k for k in store if tag in k]:
            del store[k]
    for k in [k for k in _ENGINE_WALLS if tag in k[0]]:
        del _ENGINE_WALLS[k]


def _pin_table(t) -> str:
    tid = id(t)
    if _SIG_PIN.get(tid) is t and tid in _SIG_MEMO:
        return _SIG_MEMO[tid]
    try:
        fp = f"#{_fingerprint_table(t)}#"
    except Exception:
        fp = f"#{tid}#"              # non-arrow source: process-local tag
        try:
            _SIG_PIN[tid] = t
            _SIG_MEMO[tid] = fp
            weakref.finalize(t, _drop_memo, tid)
            weakref.finalize(t, _evict_local_sigs, fp)
        except TypeError:
            pass
        return fp
    try:
        _SIG_PIN[tid] = t
        _SIG_MEMO[tid] = fp
        weakref.finalize(t, _drop_memo, tid)
    except TypeError:
        pass
    return fp


def plan_signature(plan: L.LogicalPlan) -> str:
    """Structural signature of a logical subtree (stable across runs of
    the same query shape; scans key on table identity + schema)."""
    kids = ",".join(plan_signature(c) for c in plan.children)
    extra = ""
    if isinstance(plan, L.LogicalScan):
        extra = (f"{[_pin_table(t) for t in plan.tables]};"
                 f"{plan.schema().names()}")
    elif isinstance(plan, L.ParquetScan):
        # key on content fingerprint (mtime+size) and projected columns:
        # an appended file or a wider projection must not inherit a
        # stale measured size. Memo lifetime is a short freshness window,
        # not the node's lifetime — plan_signature runs several times
        # per planning and must not re-stat thousands of files each time,
        # but a node re-planned after its files changed must see them.
        import time
        memo = getattr(plan, "_sig_fingerprint", None)
        now = time.monotonic()
        if memo is not None and now - memo[1] < 2.0:
            fp = memo[0]
        else:
            import os
            parts = []
            for p in plan.paths:
                try:
                    st = os.stat(p)
                    parts.append(f"{p}@{st.st_mtime_ns}:{st.st_size}")
                except OSError:
                    parts.append(p)
            fp = ";".join(parts)
            plan._sig_fingerprint = (fp, now)
        extra = fp + f";{plan.columns}"
    elif isinstance(plan, L.Filter):
        extra = plan.condition.key()
    elif isinstance(plan, L.Project):
        extra = ",".join(e.key() for e in plan.exprs)
    elif isinstance(plan, L.Join):
        cond = plan.condition.key() if plan.condition is not None else ""
        extra = (f"{plan.join_type};"
                 + ",".join(e.key() for e in plan.left_keys) + ";"
                 + ",".join(e.key() for e in plan.right_keys)
                 + f";{cond};{plan.broadcast}")
    elif isinstance(plan, L.Aggregate):
        extra = (",".join(e.key() for e in plan.groupings) + ";"
                 + ",".join(a.key() for a in plan.aggs))
    return f"{type(plan).__name__}[{extra}]({kids})"


def record_runtime_size(sig: str, nbytes: int) -> None:
    if len(_RUNTIME_SIZES) >= _RUNTIME_SIZES_MAX \
            and sig not in _RUNTIME_SIZES:
        _RUNTIME_SIZES.pop(next(iter(_RUNTIME_SIZES)))
    # running max: re-planning must stay safe under varying batch counts
    _RUNTIME_SIZES[sig] = max(_RUNTIME_SIZES.get(sig, 0), int(nbytes))


def runtime_size(sig: str):
    return _RUNTIME_SIZES.get(sig)


#: measured output ROW counts per plan signature (same lifecycle/eviction
#: as _RUNTIME_SIZES): the adaptive feedback that fixes the crude
#: selectivity guesses below — a dimension filter measured at 30 rows
#: re-plans as 30 rows, not input/2 (ref AQE stage statistics,
#: GpuOverrides.scala:4681-4730)
_RUNTIME_ROWS: dict = {}


def record_runtime_rows(sig: str, rows: int) -> None:
    if len(_RUNTIME_ROWS) >= _RUNTIME_SIZES_MAX \
            and sig not in _RUNTIME_ROWS:
        _RUNTIME_ROWS.pop(next(iter(_RUNTIME_ROWS)))
    _RUNTIME_ROWS[sig] = max(_RUNTIME_ROWS.get(sig, 0), int(rows))
    if _persist_enabled():
        from . import stats_store
        stats_store.mark_dirty()


#: measured whole-query wall seconds per (plan signature, placement):
#: the ground truth that overrides the static floor model once an engine
#: has actually been tried — mispriced shapes self-correct on the next
#: planning. Values are (compile-free observations, min seconds).
#: Walls are keyed on executable-cache hit status at record time: only
#: COMPILE-FREE runs (zero in-process cache misses, zero backend-compile
#: seconds during the query) are ingested, so one observation suffices
#: for trust — the old >=2-observation workaround existed solely because
#: first-run walls smuggled their XLA compile into the measurement
_ENGINE_WALLS: dict = {}


def _persist_enabled() -> bool:
    import os
    return os.environ.get("SRTPU_STATS_PERSIST", "1") != "0"


def load_persisted_stats() -> None:
    """Merge the on-disk adaptive stats (stats_store.py) into the live
    dicts — idempotent, called lazily before the first read."""
    if _persist_enabled():
        from . import exec_cache, stats_store
        stats_store.load_into(_ENGINE_WALLS, _RUNTIME_ROWS, _OP_COSTS,
                              exec_cache._PLAN_DIGESTS)


def record_engine_wall(sig: str, placement: str, seconds: float,
                       compile_free: bool = True) -> None:
    """Record a measured whole-query wall. ``compile_free=False`` (the
    caller saw executable-cache misses or backend-compile time during
    the run) drops the sample: a compile-laden wall measures the cold
    start, not the engine, and must never gate the placement choice."""
    if not compile_free:
        return
    if len(_ENGINE_WALLS) >= _RUNTIME_SIZES_MAX \
            and (sig, placement) not in _ENGINE_WALLS:
        _ENGINE_WALLS.pop(next(iter(_ENGINE_WALLS)))
    k = (sig, placement)
    cnt, prev = _ENGINE_WALLS.get(k, (0, None))
    _ENGINE_WALLS[k] = (cnt + 1,
                        seconds if prev is None else min(prev, seconds))
    if _persist_enabled():
        from . import stats_store
        stats_store.mark_dirty()


def trusted_engine_wall(sig: str, placement: str):
    # >=1 observation: every recorded wall is already compile-free
    # (record_engine_wall keys on exec-cache hit status), so the first
    # sample is representative — the >=2 rule this replaces only guarded
    # against compile-poisoned first runs
    got = _ENGINE_WALLS.get((sig, placement))
    if got is None or got[0] < 1:
        return None
    return got[1]


#: learned per-row operator costs from LIVE self-times, keyed
#: (operator kind, placement) -> (rows processed, seconds): the metrics
#: registry already measures every operator's self time — feeding those
#: walls back here (metrics/analyze.record_learned_op_costs, plus the
#: fused-region wall from exec/wholestage.py) replaces the static
#: per-row guesses with what this machine actually measured, for device
#: AND host placements. Persisted with the other adaptive stats
#: (stats_store.py).
_OP_COSTS: dict = {}
#: rows an operator kind must have processed before its learned cost is
#: trusted (tiny samples are all dispatch floor, not per-row cost)
_OP_COST_MIN_ROWS = 65536
#: per-QUERY input-row minimum for the generic self-time feed
#: (record_op_wall min_rows): a query below this is dispatch-floor- and
#: iterator-overhead-dominated, so its per-row quotient would poison the
#: table no matter how many such samples accumulate
_OP_COST_SAMPLE_MIN_ROWS = 262144


def record_op_wall(kind: str, placement: str, rows: int,
                   seconds: float, compile_free: bool = True,
                   min_rows: int = 0) -> None:
    """Accumulate (rows, seconds) into the learned per-operator cost
    table. ``compile_free=False`` drops the sample — a wall that paid
    jit trace or XLA compile measures the cold start, not the operator
    (the executable-cache-hit keying that replaced the old trust-later
    workaround). ``min_rows`` drops under-scale samples (see
    _OP_COST_SAMPLE_MIN_ROWS)."""
    if rows <= 0 or seconds <= 0.0 or not compile_free \
            or rows < min_rows:
        return
    k = (kind, placement)
    r, s = _OP_COSTS.get(k, (0, 0.0))
    _OP_COSTS[k] = (r + int(rows), s + float(seconds))
    if _persist_enabled():
        from . import stats_store
        stats_store.mark_dirty()


def learned_row_cost(kind: str, placement: str):
    """Measured seconds/row for an operator kind, or None before the
    sample is trustworthy."""
    got = _OP_COSTS.get((kind, placement))
    if got is None or got[0] < _OP_COST_MIN_ROWS:
        return None
    return got[1] / got[0]


def _on_device(node) -> bool:
    # scans and engine-neutral pass-throughs (union, limit, branch-align)
    # are shared by both engines; any OTHER device exec means the query
    # actually touched the accelerator
    if node.is_tpu and not node.engine_neutral \
            and "Scan" not in type(node).__name__:
        return True
    return any(_on_device(c) for c in node.children)


def learn_from_query(o, plan: L.LogicalPlan, physical, ctx,
                     cache_before: dict) -> Optional[str]:
    """Everything the cost model learns from one finished query
    (``o``: its exec/query.QueryOutcome; ``cache_before``: the
    ``exec_cache.stats()`` taken before the run). Returns the engine
    that ran it, or None where nothing is learned: a failed query, a
    write (never re-priced), or a degraded run, whose wall mixes failed
    attempts and the emergency host path."""
    if not o.ok or o.degradations or isinstance(plan, L.WriteFile):
        return None
    from . import exec_cache
    from ..metrics.analyze import record_learned_op_costs
    placement = "device" if _on_device(physical) else "host"
    compile_free = exec_cache.compile_free_since(cache_before)
    # measured whole-query wall per (shape, engine placement): the
    # optimizer prefers these over its model, so a mispriced engine
    # choice self-corrects on the next planning of the same shape. The
    # outcome's wall: the observers that ran since are not engine time
    record_engine_wall(plan_signature(plan), placement, o.wall_s,
                       compile_free=compile_free)
    # per-operator self-times -> the learned row costs (device AND host)
    record_learned_op_costs(physical, ctx, compile_free)
    if placement == "device":
        # this plan's kernels now live in the executable cache tiers:
        # the cache-aware floor charges warm repeats dispatch-only. Only
        # the optimizer reads the digest set, and the planner hashes the
        # tree exactly when the optimizer runs: with it off (and no
        # observer that asked for a digest) nothing pays a full-tree hash
        digest = o.digest or getattr(physical, "plan_digest", None)
        if digest is not None:
            exec_cache.record_plan_compiled(digest)
    return placement


class RowsAccum:
    """Per-exec output-row accumulator for measured-rows feedback.

    One accumulator spans ALL batches of one execute() call, so a
    multi-batch exec records its true total (not the largest single
    batch). Lazy device counts add when the sink fetch resolves them —
    exec/base._record_rows tags each lazy batch with (accum, weakref to
    that exact batch); derived batches that copy or share the meta dict
    fail the identity check and never mis-attribute their counts."""

    __slots__ = ("sig", "total", "_lock")

    def __init__(self, sig: str):
        import threading
        self.sig = sig
        self.total = 0
        self._lock = threading.Lock()

    def add(self, n: int) -> None:
        with self._lock:
            self.total += int(n)
            record_runtime_rows(self.sig, self.total)


def estimate_rows(plan: L.LogicalPlan) -> float:
    """Cardinality estimate per logical node: measured (from a previous
    run of the same shape) when available, crude guess otherwise."""
    meas = _RUNTIME_ROWS.get(plan_signature(plan))
    if meas is not None:
        return float(meas)
    kids = [estimate_rows(c) for c in plan.children]
    if isinstance(plan, L.LogicalScan):
        return float(sum(t.num_rows for t in plan.tables))
    if isinstance(plan, L.ParquetScan):
        total = 0
        for p in plan.paths:
            try:
                import pyarrow.parquet as pq
                total += pq.ParquetFile(p).metadata.num_rows
            except Exception:
                total += 1_000_000
        return float(total)
    if isinstance(plan, L.RangeRel):
        return float(max(0, (plan.end - plan.start) // (plan.step or 1)))
    if isinstance(plan, L.Filter):
        return kids[0] * 0.5
    if isinstance(plan, L.Aggregate):
        return max(kids[0] * 0.1, 1.0) if plan.groupings else 1.0
    if isinstance(plan, (L.GlobalLimit, L.LocalLimit)):
        return float(min(plan.n, kids[0]))
    if isinstance(plan, L.Join):
        if plan.join_type in ("leftsemi", "leftanti", "existence"):
            return kids[0]
        if not plan.left_keys:
            return kids[0] * kids[1] * 0.1
        return max(kids[0], kids[1])
    if isinstance(plan, L.Sample):
        return kids[0] * plan.fraction
    if isinstance(plan, L.Expand):
        return kids[0] * len(plan.projections)
    if isinstance(plan, L.Union):
        return float(sum(kids))
    return kids[0] if kids else 1000.0


class _Cost:
    __slots__ = ("device", "host", "device_boundary")

    def __init__(self, device: float, host: float, device_boundary: bool):
        #: cheapest cost of this subtree ending device-resident / host-resident
        self.device = device
        self.host = host
        #: whether the subtree root runs on device in the device plan
        self.device_boundary = device_boundary


def apply_cost_optimizer(meta: PlanMeta, conf: TpuConf,
                         wall_sig: Optional[str] = None,
                         plan_digest: Optional[str] = None) -> str:
    """Revert TPU-capable nodes whose device placement is not worth it.

    Two decisions, both the reference's CostBasedOptimizer idea adapted to
    an accelerator with a per-query floor (RapidsConf.scala:2126-2156):
      * per-subtree: a node whose host cost (incl. transitions) beats its
        device cost reverts (the reference's behavior verbatim);
      * whole-plan: ANY device placement pays the per-query floor ONCE —
        when the entire plan's host estimate beats best-device + floor,
        the whole query runs on the host engine. The floor is
        CACHE-AWARE: a ``plan_digest`` whose executables are already
        warm in the two-tier compile cache (plan/exec_cache.py) pays
        only the dispatch component (DEVICE_DISPATCH_FLOOR), not the
        cold trace+compile floor — warm repeats (the serving case) are
        re-costed without the compile they will not pay. Small inputs
        still lose to the dispatch floor no matter how fast the
        kernels are; measured row feedback (_RUNTIME_ROWS) makes the
        second planning of a shape exact.

    Per-node costs prefer the LEARNED per-operator row costs (device and
    host, record_op_wall) over the static tables once trusted.

    Mutates metas via will_not_work_on_tpu. Returns a one-line placement
    decision ("device (...)" / "host (...)") recording WHY, which
    EXPLAIN prints — a stage staying on host is explained by the plan
    output itself. Every COST_MODEL_HOST tag detail carries the device
    and host cost estimates behind the decision."""
    load_persisted_stats()
    # the registered defaults are per-row costs for the reference's
    # row-interpreter; this engine's host twin is vectorized — treat the
    # conf values as SCALES relative to the registered defaults so
    # existing knobs still steer the model
    cpu_scale = conf.get(CPU_EXEC_COST) / 2.0e-4
    tpu_c = conf.get(TPU_EXEC_COST) / 1.0e-4 * 2.0e-9
    # live per-operator self-times trump the static device guess — but
    # ONLY for the node kinds the measurement covers: fused regions
    # measure filter/project rows (record_op_wall from
    # exec/wholestage.py), so a cheap fused wall must not also discount
    # joins/sorts/aggregates it never timed
    fused_c = learned_row_cost("WholeStageExec", "device")
    trans_c = conf.get(TRANSITION_COST)
    cold_floor = float(conf.get(DEVICE_QUERY_FLOOR))
    # cache-aware floor: plan digest warm in the executable cache (live
    # tier or a previous process via the persistent tier) -> the compile
    # component is already paid, only dispatch+fetch remains
    warm_digest = False
    if plan_digest is not None:
        from . import exec_cache
        warm_digest = exec_cache.plan_digest_cached(plan_digest)
    dispatch_floor = min(float(conf.get(DEVICE_DISPATCH_FLOOR)),
                         cold_floor)
    floor = dispatch_floor if warm_digest else cold_floor

    pending_reverts = []     # (meta, reason): applied only if the
    # measured-wall arbitration below doesn't choose the device wholesale

    def walk(m: PlanMeta) -> _Cost:
        # costs scale with the rows a node PROCESSES (its input); a
        # groupby collapsing 2M rows to 7 groups still hashes 2M rows
        rows_in = (sum(estimate_rows(c.plan) for c in m.child_metas)
                   if m.child_metas else estimate_rows(m.plan))
        kids = [walk(c) for c in m.child_metas]
        host_node = _host_node_cost(m.plan, rows_in, cpu_scale)
        # scans decode on host for BOTH engines (the H2D is the floor's /
        # transition's job) — placement-neutral, never worth reverting
        kind = node_kind(m.plan)
        learned_dev = (learned_row_cost(kind, "device")
                       if kind is not None else None)
        if isinstance(m.plan, (L.LogicalScan, L.ParquetScan)):
            node_tpu_c = 0.0
        elif learned_dev is not None:
            # trusted measured device cost for this operator KIND
            # replaces the static guess outright (the learned cost
            # already includes the kernel's real dispatch wall)
            node_tpu_c = learned_dev
            if fused_c is not None and isinstance(m.plan,
                                                  (L.Filter, L.Project)):
                # fusible chains collapse into ONE dispatch + ONE
                # compaction (exec/wholestage.py): a per-kind cost
                # learned from STANDALONE operators (each paying its
                # own dispatch) overprices the fused execution, so the
                # region's measured per-row wall caps it
                node_tpu_c = min(node_tpu_c, fused_c)
        elif fused_c is not None and isinstance(m.plan,
                                                (L.Filter, L.Project)):
            # fusible node kinds price from the measured fused walls
            node_tpu_c = min(tpu_c, fused_c)
        else:
            node_tpu_c = tpu_c
        if not m.can_run_on_tpu:
            # host-only: children feeding it from device pay a D2H transition
            host = host_node + sum(
                min(k.host, k.device + trans_c * estimate_rows(cm.plan))
                for k, cm in zip(kids, m.child_metas))
            return _Cost(float("inf"), host, False)
        # device placement: children arriving host-side pay H2D
        device = node_tpu_c * rows_in + sum(
            min(k.device, k.host + trans_c * estimate_rows(cm.plan))
            for k, cm in zip(kids, m.child_metas))
        host = host_node + sum(
            min(k.host, k.device + trans_c * estimate_rows(cm.plan))
            for k, cm in zip(kids, m.child_metas))
        if host < device:
            # the COST_MODEL_HOST contract: the detail always carries
            # both estimates, so explain("placement") shows the numbers
            # behind the decision
            pending_reverts.append((m, (
                f"cost-based: device≈{device:.4f}s (incl. transitions) "
                f"exceeds host≈{host:.4f}s")))
            return _Cost(float("inf"), host, False)
        return _Cost(device, host, True)

    root = walk(meta)

    def pure_host(m: PlanMeta) -> float:
        rows_in = (sum(estimate_rows(c.plan) for c in m.child_metas)
                   if m.child_metas else estimate_rows(m.plan))
        return (_host_node_cost(m.plan, rows_in, cpu_scale)
                + sum(pure_host(c) for c in m.child_metas))

    host_only = pure_host(meta)
    best_mixed = min(root.device, root.host)
    host_est = host_only
    # model device estimate WITHOUT the per-node reverts applied: the
    # cost every node would pay if the whole plan ran device-side
    dev_model = root.device if root.device != float("inf") else best_mixed
    dev_est = best_mixed + floor
    how = "estimate"
    hw = dw = None
    if wall_sig is not None:
        # MEASURED whole-query walls trump the model: a shape that has
        # actually run on an engine is priced by what it cost, so
        # marginal mispredictions self-correct on the next planning
        hw = trusted_engine_wall(wall_sig, "host")
        dw = trusted_engine_wall(wall_sig, "device")
        if hw is not None:
            host_est, how = hw, "measured"
        if dw is not None:
            dev_est, how = dw, "measured"

    # whole-plan reversions record a coded wrapping tag on the root AND
    # flip each still-capable node — nodes carrying their own reasons
    # keep them (tags.revert_to_host; the explain("placement") contract)
    from .tags import WHOLE_PLAN_HOST_REVERT, revert_to_host

    def revert_all(m: PlanMeta, reason: str):
        revert_to_host(m, reason, code=WHOLE_PLAN_HOST_REVERT)

    # Bidirectional measured-wall arbitration (the per-node model alone
    # could only flip device->host; a slow host twin would then be chosen
    # forever with the measured walls ignored — caught when the r4 bench
    # kept q9 on a 1.4 s host plan while the device ran it in 0.2 s):
    #   * both walls trusted -> the faster engine wins wholesale;
    #   * only the host wall trusted, and the MODEL thinks the device
    #     could beat it -> run device once to learn its wall;
    #   * otherwise the model decides (per-node reverts + floor check).
    if hw is not None and dw is not None:
        if dw <= hw:
            log.debug("cost optimizer: measured device wall %.4fs beats "
                      "host %.4fs — device wholesale", dw, hw)
            return (f"device (measured device wall {dw:.4f}s beats host "
                    f"{hw:.4f}s)")
        revert_all(meta, (f"cost-based: measured host≈{hw:.4f}s beats "
                          f"device≈{dw:.4f}s"))
        return (f"host (measured host wall {hw:.4f}s beats device "
                f"{dw:.4f}s)")
    if hw is not None and dw is None \
            and dev_model + dispatch_floor < hw:
        # exploration prices the device at its WARM floor even when the
        # digest is cold: the compile is a one-time investment a serving
        # workload amortizes over every repeat, so a shape whose warm
        # repeats would beat the measured host wall is worth one
        # compile-paying run to learn its device wall
        log.debug("cost optimizer: exploring device (model %.4fs + "
                  "dispatch floor < measured host %.4fs)", dev_model, hw)
        return (f"device (exploring: model {dev_model:.4f}s + dispatch "
                f"floor {dispatch_floor:.4f}s < measured host "
                f"{hw:.4f}s)")
    if dw is not None and hw is None and host_only < dw:
        # symmetric: a device-first shape measuring slow must TRY the
        # host twin once, or it stays on the slow engine forever
        revert_all(meta, (f"cost-based: exploring host — model "
                          f"host≈{host_only:.4f}s < measured "
                          f"device≈{dw:.4f}s"))
        log.debug("cost optimizer: exploring host (model %.4fs < "
                  "measured device %.4fs)", host_only, dw)
        return (f"host (exploring: model {host_only:.4f}s < measured "
                f"device {dw:.4f}s)")
    from .tags import COST_MODEL_HOST
    for m, reason in pending_reverts:
        m.will_not_work_on_tpu(reason, code=COST_MODEL_HOST)
        log.debug("cost optimizer reverted %s", type(m.plan).__name__)
    floor_word = "warm dispatch floor" if warm_digest else "cold floor"
    if floor > 0 and host_est < dev_est:
        reason = (f"cost-based: whole-plan host {how} host≈{host_est:.4f}s "
                  f"beats device≈{dev_est:.4f}s (incl. {floor_word} "
                  f"{floor:.4f}s)")
        revert_all(meta, reason)
        log.debug("cost optimizer reverted whole plan to host (%s)", reason)
        return (f"host ({how} {host_est:.4f}s beats device "
                f"{dev_est:.4f}s incl. {floor_word})")
    return (f"device ({how}: device {dev_est:.4f}s incl. {floor_word} vs "
            f"host {host_est:.4f}s"
            + (f"; {len(pending_reverts)} subtree(s) reverted"
               if pending_reverts else "") + ")")
