"""Physical operator base (ref GpuExec.scala:274).

A TpuExec produces an iterator of ColumnarBatch. Metrics mirror the
reference's GpuMetric registry with verbosity levels (GpuExec.scala:54-165);
the device semaphore gates concurrent device work (GpuSemaphore.scala:51).
"""
from __future__ import annotations

import contextlib
import itertools
import threading
import time
from typing import Dict, Iterator, List, Optional

from ..columnar import ColumnarBatch
from ..config import TpuConf
from ..mem.semaphore import QueryTimeout
from ..trace import core as trace_core
from ..types import Schema

__all__ = ["ExecContext", "TpuExec", "Metric", "ESSENTIAL", "MODERATE",
           "DEBUG", "QueryTimeout"]

ESSENTIAL, MODERATE, DEBUG = "ESSENTIAL", "MODERATE", "DEBUG"


class Metric:
    __slots__ = ("name", "level", "value")

    def __init__(self, name: str, level: str = MODERATE):
        self.name = name
        self.level = level
        self.value = 0

    def add(self, v):
        self.value += v

    def set(self, v):
        self.value = v


class ExecContext:
    """Execution context: conf + the runtime services + ONE query's state.

    The services (the ``DeviceSemaphore``, the ``MemoryManager``, the
    installed tracer / metric registry / ops plane / admission / AQE log)
    are process-wide and shared; reference analog: the executor-process
    singletons (GpuSemaphore, RapidsBufferCatalog, GpuTaskMetrics). The
    rest — operator metrics, cleanups, the broadcast cache, speculations,
    OOM degradations, the ladder rung, the deadline — belongs to one
    query. A session holds ONE context for the services' sake; product
    code runs every plan on ``ExecContext(conf, parent=session's)`` and
    closes it when the plan ends (``exec/query.py``)."""

    def __init__(self, conf: Optional[TpuConf] = None, semaphore=None,
                 memory=None, parent: Optional["ExecContext"] = None):
        from ..mem.semaphore import DeviceSemaphore
        from ..mem.manager import MemoryManager
        if parent is not None:
            # one query's context under a session's: the parent's
            # services, and under the parent's own conf nothing to
            # install again (the parent's constructor did)
            conf = conf or parent.conf
            semaphore = semaphore or parent.semaphore
            memory = memory or parent.memory
        self.conf = conf or TpuConf()
        if parent is None or self.conf is not parent.conf:
            self._install_from_conf()
        from ..config import SEMAPHORE_WEDGE_TIMEOUT_MS, TASK_TIMEOUT
        self.memory = memory or MemoryManager.get(self.conf)
        self.semaphore = semaphore or DeviceSemaphore(
            self.conf.concurrent_tpu_tasks,
            timeout_s=float(self.conf.get(TASK_TIMEOUT)),
            wedge_timeout_ms=int(self.conf.get(SEMAPHORE_WEDGE_TIMEOUT_MS)),
            memory=self.memory)
        #: exec id -> {name: Metric}: the operators executed on THIS
        #: context (one query's, where exec/query.py made the context)
        self.metrics: Dict[str, Dict[str, Metric]] = {}
        self._cleanups = []
        #: BroadcastExchangeExec id -> SpillableBatch: relations built
        #: once and held until close() (shuffle/broadcast.py)
        self._broadcast_cache: Dict[str, object] = {}
        #: query-lifecycle cooperative deadline (time.monotonic instant,
        #: None = no timeout); checked per produced batch and polled by
        #: semaphore waits (exec/query.py sets it per query)
        self.deadline: Optional[float] = None
        self._oom_lock = threading.Lock()
        #: runtime OOM_PRESSURE_HOST degradations recorded by the retry
        #: ladder (mem/retry.py): [{"op", "detail"}, ...]; drained at the
        #: query's end by exec/query.run_query
        self.oom_degradations: List[dict] = []  # tpulint: guarded-by _oom_lock
        #: highest OOM-escalation rung any ladder reached this query
        #: (1 retry / 2 split / 3 pressure spill / 4 host degradation);
        #: drained per query next to oom_degradations — the queryEnd
        #: record, /queries and the regression sentinel all read it
        self.max_ladder_rung = 0  # tpulint: guarded-by _oom_lock
        #: speculative output sizing (joins skip the count->host sync and
        #: guess the bucket); the FINAL sink calls check_speculations() once
        self.speculate = self.conf.join_speculative_sizing
        #: [(device total, capacity, join stat key), ...]
        self.speculations = []
        #: join stat key -> the largest output total this context's query
        #: has read of that join shape (exec/joins.py:_note_total)
        self.join_totals: Dict[tuple, int] = {}

    def _install_from_conf(self) -> None:
        """The process-wide installs a conf asks for, each install-once
        and a conf lookup or two when already done."""
        # installs the process tracer iff spark.rapids.tpu.trace.enabled,
        # and the metric registry (+ sampler) iff
        # spark.rapids.tpu.metrics.enabled
        trace_core.ensure_tracer_from_conf(self.conf)
        from ..metrics import registry as metrics_registry
        metrics_registry.ensure_metrics_from_conf(self.conf)
        # persistent executable tier: point jax's compilation cache at
        # the conf'd dir + trim to budget (never per kernel —
        # plan/exec_cache.py)
        from ..plan import exec_cache
        exec_cache.configure_from_conf(self.conf)
        # live ops plane: HTTP endpoint, flight recorder, regression
        # sentinel — same install pattern; with nothing configured this
        # is three conf lookups and no threads (ops/__init__.py)
        from ..ops import ensure_ops_plane_from_conf
        ensure_ops_plane_from_conf(self.conf)
        # multi-tenant admission controller (ISSUE 18): installed iff
        # spark.rapids.tpu.admission.enabled — same one-conf-lookup
        # install-once pattern; disabled it stays None and each query
        # pays one module-global load + branch (sched/admission.py)
        from ..sched.admission import ensure_admission_from_conf
        ensure_admission_from_conf(self.conf)
        # adaptive query execution (ISSUE 19): the closed-taxonomy
        # decision log, installed iff spark.rapids.tpu.aqe.enabled —
        # off, every decision site is one module load + branch
        from ..aqe import ensure_aqe_from_conf
        ensure_aqe_from_conf(self.conf)

    # --------------------------------------------- query-lifecycle control
    def set_query_deadline(self, deadline: Optional[float]) -> None:
        """Install (or with None clear) this query's cooperative
        cancellation deadline; the semaphore polls the same instant
        (per-thread — a shared semaphore must not leak one query's
        deadline into another's wait) so a blocked acquire cancels
        promptly too."""
        self.deadline = deadline
        self.semaphore.set_thread_deadline(deadline)

    def check_cancelled(self) -> None:
        """Cooperative cancellation point: raises QueryTimeout past the
        deadline. Called at every produced batch (TpuExec.execute) and
        from the retry ladder — the exception unwinds through the normal
        cleanup paths, releasing the semaphore and closing spillables."""
        dl = self.deadline
        if dl is not None and time.monotonic() > dl:
            raise QueryTimeout(
                "query exceeded spark.rapids.tpu.query.timeout "
                f"(deadline passed by {time.monotonic() - dl:.3f}s)")

    def record_oom_degradation(self, op: str, detail: str) -> None:
        """The retry ladder's host-degradation rung fired for ``op``:
        remembered for the query's PlacementReport / event-log record
        and counted into the metric families immediately."""
        with self._oom_lock:
            self.oom_degradations.append({"op": op, "detail": detail})
        from ..metrics import registry as metrics_registry
        mr = metrics_registry.REGISTRY
        if mr is not None:
            mr.counter("srtpu_oom_host_fallback_total", op=op).inc()
            mr.counter("srtpu_placement_fallback_total",
                       code="OOM_PRESSURE_HOST", op=op).inc()
        self.note_ladder_rung(4, f"{op}: {detail}")

    def note_ladder_rung(self, rung: int, detail: str = "") -> None:
        """Record the OOM-escalation rung a ladder just reached (the
        per-query max survives to the queryEnd record). Crossing into
        rung >= 3 for the first time this query fires the flight
        recorder's ``oom_ladder`` trigger — the PR-14 anomaly sites
        dumped diagnostics only into exception strings before."""
        with self._oom_lock:
            prev = self.max_ladder_rung
            self.max_ladder_rung = max(prev, int(rung))
        if rung >= 3 and rung > prev and prev < 3:
            from ..ops import flight as flight_mod
            fr = flight_mod.RECORDER
            if fr is not None:
                fr.trigger("oom_ladder",
                           detail=detail
                           or f"OOM escalation reached rung {rung}")

    def take_ladder_rung(self) -> int:
        """Drain the per-query max escalation rung (per-query reset)."""
        with self._oom_lock:
            rung, self.max_ladder_rung = self.max_ladder_rung, 0
        return rung

    def take_oom_degradations(self) -> List[dict]:
        """Drain the recorded degradations (per-query reset)."""
        with self._oom_lock:
            out, self.oom_degradations = self.oom_degradations, []
        return out

    def check_speculations(self) -> None:
        """Validate every speculatively-sized output (ONE batched fetch of
        the tiny totals); raises SpeculativeOverflow if any guess was too
        small. Only the query's final sink may call this — a mid-plan
        validation would consume another join's pending record."""
        if not self.speculations:
            return
        from ..columnar.batch import SpeculativeOverflow
        from ..columnar.packing import fetch_packed
        from .joins import _note_total
        pending, self.speculations = self.speculations, []
        totals = fetch_packed([t for t, _, _, _ in pending])
        over = None
        for n, (_, cap, stat_key, plan_sig) in zip(totals, pending):
            n = int(n)
            if stat_key is not None:
                _note_total(self, stat_key, n)  # keep the statistic fresh
            if plan_sig is not None:
                # measured join-output rows -> the cost model (the crudest
                # estimate it has); rides the same batched totals fetch
                from ..plan.cost import record_runtime_rows
                record_runtime_rows(plan_sig, n)
            if n > cap and over is None:
                over = SpeculativeOverflow(n, cap)
        if over is not None:
            raise over

    def metric(self, exec_id: str, name: str, level: str = MODERATE) -> Metric:
        m = self.metrics.setdefault(exec_id, {})
        if name not in m:
            m[name] = Metric(name, level)
        return m[name]

    def add_cleanup(self, fn) -> None:
        """Register a resource release to run at context close (per-query
        caches like broadcast relations)."""
        self._cleanups.append(fn)

    def close(self) -> None:
        """Run the registered cleanups and drop the broadcast cache; the
        operator metrics stay readable (EXPLAIN ANALYZE renders them
        after the query). Idempotent."""
        fns, self._cleanups = self._cleanups, []
        for fn in fns:
            try:
                fn()
            except Exception:  # pragma: no cover - best-effort teardown
                pass
        self._broadcast_cache.clear()

    def __del__(self):  # pragma: no cover - GC backstop
        try:
            self.close()
        except Exception:
            pass


#: process-wide exec-id source (itertools.count is atomic under the GIL)
_EXEC_ID_COUNTER = itertools.count()


class TpuExec:
    """Base physical operator."""

    #: True if this exec runs its compute on the device
    is_tpu: bool = True
    #: True for pass-through operators shared by BOTH engines (union,
    #: branch-align, limit): they must not make a host-reverted query
    #: look device-placed to the measured-wall arbitration
    engine_neutral: bool = False

    def __init__(self, children: List["TpuExec"]):
        self.children = children
        # monotonic, never-reused id: keying metrics on id(self) lets a
        # freed plan tree's address be reused by a later exec, silently
        # MERGING two operators' metric entries in a shared ExecContext
        self._exec_id = f"{type(self).__name__}@{next(_EXEC_ID_COUNTER)}"

    # -- interface ---------------------------------------------------------
    def output_schema(self) -> Schema:
        raise NotImplementedError

    def execute(self, ctx: ExecContext) -> Iterator[ColumnarBatch]:
        m = ctx.metric(self._exec_id, "opTime")
        t0 = time.perf_counter()
        it = self.do_execute(ctx)
        m.add(time.perf_counter() - t0)
        # per-batch metering: cumulative operator time (includes pulls
        # from children — EXPLAIN ANALYZE derives SELF time by
        # subtracting the children's cumulative) + produced batches
        it = self._metered_iter(
            it, m, ctx.metric(self._exec_id, "numOutputBatches"))
        if ctx.deadline is not None:
            # cooperative cancellation: one deadline check per produced
            # batch at every operator (zero cost with no timeout set)
            it = self._cancel_iter(it, ctx)
        sig = getattr(self, "plan_sig", None)
        if sig is not None:
            it = self._record_rows(it, sig)
        tr = trace_core.TRACER       # single branch when tracing is off
        if tr is not None:
            it = self._traced_iter(it, tr)
        return it

    def child_span(self, name: str, **args):
        """A ``with`` span of the installed tracer for one step inside this
        operator (a join's build, an aggregate's partition), nested under
        the operator's own span and carrying its id as ``exec`` (so that it
        reaches the profiler's clock: trace/core.py), or nothing."""
        tr = trace_core.TRACER
        if tr is None:
            return contextlib.nullcontext()
        return tr.span(name, cat="exec", args=dict(args, exec=self._exec_id))

    @staticmethod
    def _metered_iter(it, m_time: Metric, m_batches: Metric):
        """Time every next() into the operator's cumulative opTime and
        count produced batches (two perf_counter reads per BATCH — noise
        next to batch-scale work, and the price of an always-on SQL-UI
        view; ref GpuMetric.ns around every GPU op)."""
        it = iter(it)
        while True:
            t0 = time.perf_counter()
            try:
                b = next(it)
            except StopIteration:
                m_time.add(time.perf_counter() - t0)
                return
            m_time.add(time.perf_counter() - t0)
            m_batches.add(1)
            yield b

    @staticmethod
    def _cancel_iter(it, ctx):
        """Raise QueryTimeout at the first batch boundary past the
        query deadline (spark.rapids.tpu.query.timeout). The exception
        unwinds through the generator stack: semaphore permits release
        via their with-scopes, spillables close via the operators'
        cleanup handlers — cancellation leaks nothing."""
        for b in it:
            ctx.check_cancelled()
            yield b

    def _traced_iter(self, it, tr):
        """One span per produced batch, named after the operator. Child
        operators' spans nest inside (the contextvar parent chain), so
        the profile analyzer can compute SELF time — where a query's
        wall actually goes, not just cumulative subtree time."""
        name = type(self).__name__
        # fused regions annotate their span with the operators they
        # swallowed (exec/wholestage.py trace_args = {"fused": [...]})
        args = {"exec": self._exec_id,
                **getattr(self, "trace_args", {})}
        it = iter(it)
        while True:
            with tr.span(name, cat="exec", args=args):
                try:
                    b = next(it)
                except StopIteration:
                    return
            yield b

    @staticmethod
    def _record_rows(it, sig):
        """Measured-rows feedback for the cost model (plan/cost.py
        _RUNTIME_ROWS): execs tagged with a plan signature record their
        output row counts — immediately for host ints, deferred to the
        sink fetch for lazy device counts (never an extra sync). One
        accumulator covers all of this exec's batches (true totals);
        the weakref tag pins each deferred count to its exact batch."""
        import weakref
        from ..plan.cost import RowsAccum
        accum = RowsAccum(sig)
        for b in it:
            if isinstance(b.num_rows_raw, int):
                accum.add(b.num_rows_raw)
            else:
                b.meta = dict(b.meta)
                b.meta["rows_accum"] = (accum, weakref.ref(b))
            yield b

    def do_execute(self, ctx: ExecContext) -> Iterator[ColumnarBatch]:
        raise NotImplementedError

    # -- explain -----------------------------------------------------------
    def describe(self) -> str:
        return type(self).__name__

    def tree_string(self, indent: int = 0) -> str:
        marker = "*" if self.is_tpu else "!"
        s = "  " * indent + marker + " " + self.describe() + "\n"
        for c in self.children:
            s += c.tree_string(indent + 1)
        return s

    def collect(self, ctx: Optional[ExecContext] = None,
                validate: bool = True):
        """Materialize to a single Arrow table (drives the whole pipeline).
        ``validate=False`` marks a MID-PLAN materialization (e.g. a join
        building its broadcast side): it must neither consume the context's
        pending speculation records nor retry a subtree on its own — an
        overflow propagates to the final sink, which re-runs the full plan.
        """
        import pyarrow as pa
        from ..columnar.batch import SpeculativeOverflow
        ctx = ctx or ExecContext()
        if not validate:
            return self._collect_tables(ctx)
        try:
            tables = [b.to_arrow() for b in self.execute(ctx)]
            ctx.check_speculations()
        except SpeculativeOverflow:
            # a join's guessed output bucket was too small: re-run the
            # whole plan with exact (synchronous) output sizing
            ctx.speculate = False
            ctx.speculations.clear()
            ctx.metrics.clear()        # don't double-count the failed run
            tables = [b.to_arrow() for b in self.execute(ctx)]
        if not tables:
            return self._empty_table()
        return pa.concat_tables(tables)

    def _collect_tables(self, ctx):
        import pyarrow as pa
        tables = [b.to_arrow() for b in self.execute(ctx)]
        if not tables:
            return self._empty_table()
        return pa.concat_tables(tables)

    def _empty_table(self):
        import pyarrow as pa
        from ..types import to_arrow
        fields = [(f.name, to_arrow(f.dtype)) for f in self.output_schema()]
        return pa.table({n: pa.array([], type=t) for n, t in fields})
