"""One way to run a plan.

``run_query`` is a query's whole life: plan, admit, attempt, settle, tell
the observers, let the cost model learn. ``run_plan`` is for code that
already holds a physical plan. Both execute on an ``ExecContext`` of
their own under the session's and close it when the plan ends, so nothing
under ``spark_rapids_tpu/`` executes on ``session.exec_context()`` itself.

Layering: ``api/`` -> this module -> ``plan/`` and the operators of
``exec/``. What a finished query IS gets decided once, in one
``QueryOutcome``; the metric registry, the event log, the ops tracker,
the flight recorder, the sentinel, the SLO tracker and the cost model
each take it whole and pick their fields in their own module. That list
is fixed here, in the order below; it is not an extension point.
"""
from __future__ import annotations

import threading
import time

from .. import aqe as aqe_mod
from ..aqe.feedback import overlay_conf
from ..aux.fault import DeviceDumpHandler
from ..aux.lore import lore_wrap
from ..aux.metrics import TaskMetrics
from ..columnar.batch import SpeculativeOverflow
from ..config import OOM_HOST_FALLBACK_ENABLED, QUERY_TIMEOUT
from ..exprs import decimal_rules
from ..mem.manager import (MemoryManager, OutOfDeviceMemory, RetryOOM,
                           SplitAndRetryOOM)
from ..mem.semaphore import QueryTimeout
from ..metrics import registry as metrics_registry
from ..metrics.events import plan_digest
from ..ops import flight as flight_mod
from ..ops import sentinel as sentinel_mod
from ..ops import server as ops_server_mod
from ..ops import slo as slo_mod
from ..plan import cost, exec_cache
from ..plan import logical as L
from ..plan.op_confs import install_from_conf
from ..plan.overrides import plan_query
from ..plan.tags import OOM_PRESSURE_HOST, make_tag
from ..sched import admission as adm_mod
from ..trace import core as trace_core
from .base import ExecContext

__all__ = ["QueryOutcome", "run_query", "run_plan", "plan_physical",
           "tenant_of", "audit_leaks"]

_OOMS = (RetryOOM, SplitAndRetryOOM, OutOfDeviceMemory)


class QueryOutcome:
    """What one query was: filled in as it runs, whole at its end. The
    last three slots are the consumers' own notes between a query's
    start and its end."""

    __slots__ = ("query_id", "digest", "root", "tenant", "conf",
                 "placement", "trace_path", "ok", "wall_s", "reason",
                 "degradations", "ladder_rung", "compile_s", "queued_ms",
                 "admission", "aqe", "metrics", "fault_stats",
                 "tracker_token", "was_warm", "bundles_before")

    def __init__(self, query_id, digest, root, tenant, conf, placement,
                 trace_path):
        self.query_id = query_id
        self.digest = digest
        self.root = root
        self.tenant = tenant
        self.conf = conf
        #: coded placement summary ({"verdict", "codes", "ops", ...})
        self.placement = placement
        self.trace_path = trace_path
        self.ok = False
        self.wall_s = self.compile_s = 0.0
        self.ladder_rung = 0
        self.degradations = ()
        self.was_warm = False
        self.reason = self.queued_ms = self.admission = self.aqe = None
        self.metrics = self.fault_stats = None
        self.tracker_token = self.bundles_before = None

    @property
    def verdict(self):
        return (self.placement or {}).get("verdict")

    @property
    def wall_ms(self) -> float:
        return self.wall_s * 1000.0


def tenant_of(conf):
    """The tenant a session's queries run as (None = anonymous)."""
    return str(conf.get(adm_mod.TENANT_ID)) or None


def audit_leaks() -> list:
    """Device buffer registrations alive in ANY memory manager."""
    return MemoryManager.audit_all_leaks()


def plan_physical(session, plan, conf=None):
    """The physical plan of ``plan`` under the session's conf (or an
    overlay of it), inside a ``plan.physical`` span when tracing."""
    tr = trace_core.TRACER       # single branch when tracing is off
    if tr is None:
        return _plan(session, plan, conf)
    with tr.span("plan.physical", cat="plan"):
        return _plan(session, plan, conf)


def _plan(session, plan, conf):
    return plan_query(plan, conf or session.conf,
                      mesh=getattr(session, "mesh", None),
                      mesh_auto=getattr(session, "mesh_is_auto", False))


def run_plan(session, physical):
    """Collect a physical plan somebody already holds (a worker task, the
    driver side of a distributed round) on a context of its own: its
    operator metrics and broadcast relations die with it."""
    ctx = ExecContext(parent=session.exec_context())
    try:
        return physical.collect(ctx)
    finally:
        ctx.close()


def _attempt(conf, consume, ctx, physical):
    """One full run of the plan, with the speculative-sizing overflow
    retry inside (plans with side effects run with speculation off, so
    this retry can never duplicate output files). After the sink, the
    decimal overflow counts its kernels left are settled: a row that
    left the 64-bit lane is the loud error, never a wrapped number."""
    def checked():
        decimal_rules.clear_pending()
        out = consume(physical, ctx)
        decimal_rules.settle_pending()
        return out
    try:
        out = DeviceDumpHandler(conf).wrap(checked, physical)
        ctx.check_speculations()
        return out
    except SpeculativeOverflow:
        ctx.speculate = False
        ctx.speculations.clear()
        ctx.metrics.clear()
        return DeviceDumpHandler(conf).wrap(checked, physical)


def _oom_ladder(session, plan, err, physical, ctx, consume):
    """Query-level OOM escalation: the backstop for an OOM that escaped
    every operator retry frame (a reserve outside any with_retry scope).
    Rung A: spill EVERY live session's spillables and re-run the plan
    once on the device. Rung B
    (``spark.rapids.tpu.oom.hostFallback.enabled``): re-plan the query
    onto the host engine and run it under an unbudgeted pressure grant,
    recorded as a whole-query OOM_PRESSURE_HOST degradation: pressure
    degrades *placement*, never results."""
    ctx.note_ladder_rung(
        3, f"query-level pressure spill after {type(err).__name__} "
           "escaped every operator retry frame")
    MemoryManager.spill_all_sessions()
    ctx.memory.spill_everything()    # explicit managers too
    ctx.metrics.clear()
    ctx.speculations.clear()
    try:
        return _attempt(session.conf, consume, ctx, physical)
    except _OOMS as e2:
        if not bool(session.conf.get(OOM_HOST_FALLBACK_ENABLED)):
            raise
        ctx.record_oom_degradation(
            "Query", "whole-query host degradation after "
            f"{type(e2).__name__}: {e2}")
        host_physical = plan_query(
            plan, session.conf.set("spark.rapids.tpu.sql.enabled", False))
        ctx.metrics.clear()
        ctx.speculations.clear()
        ctx.speculate = False
        with ctx.memory.pressure_host_grant():
            return consume(host_physical, ctx)


def _admit(adm, session, ctx, o, tracker):
    """Queue at the front door, BEFORE any device work: an overloaded or
    pressure-degraded process refuses with a structured
    AdmissionRejected (retry-after hint) instead of piling onto the
    semaphore. Returns the ticket to release."""
    if tracker is not None:
        tracker.admission(o.tracker_token, "queued")
    try:
        ticket = adm.admit(
            tenant=o.tenant,
            priority=int(session.conf.get(adm_mod.TENANT_PRIORITY)),
            deadline=ctx.deadline)
    except adm_mod.AdmissionRejected:
        o.admission = "shed"
        if tracker is not None:
            tracker.admission(o.tracker_token, "shed")
        raise
    o.admission = "admitted"
    o.queued_ms = ticket.queued_ms
    if tracker is not None:
        tracker.admission(o.tracker_token, "admitted", o.queued_ms)
    return ticket


def _note_timeout(o, frec):
    reg = metrics_registry.REGISTRY
    if reg is not None:
        reg.counter("srtpu_query_timeout_total").inc()
    if frec is not None:
        frec.trigger(
            "query_timeout",
            detail=f"query {o.query_id if o.query_id is not None else '?'} "
                   f"(digest {o.digest or '?'}) cancelled by "
                   "spark.rapids.tpu.query.timeout")


def run_query(session, plan, consume, q=None, qargs=None, trace_path=None):
    """Run logical ``plan`` through the whole pipeline and hand
    ``consume(physical, ctx)``'s result back. ``q`` / ``qargs``: the
    ordinal and the args of the open ``query`` span (None when tracing
    is off); ``trace_path``: where the caller writes this query's trace
    afterwards."""
    # a query that RAISES (planning included) must not leave the prior
    # run's telemetry behind, and a non-distributed query must not
    # inherit the last cluster run's fault stats
    session.last_query_metrics = None
    session.last_fault_stats = None
    session.last_placement_report = None
    session.last_aqe_decisions = None
    conf = session.conf
    # the decision log is marked up front, so the end can slice out THIS
    # query's decisions; history feedback may hand back an overlay conf
    aqe_log = aqe_mod.ensure_aqe_from_conf(conf)
    aqe_mark = aqe_log.mark() if aqe_log is not None else 0
    run_conf = overlay_conf(conf, plan, aqe_log)
    physical = plan_physical(session, plan, run_conf)
    report = getattr(physical, "placement_report", None)
    placement = report.summary() if report is not None else None
    session.last_placement_report = placement
    if conf.is_explain_only:
        raise RuntimeError("session is in explainOnly mode")
    # planning by another session in between must not leak its
    # per-expression disables into this execution (thread-local set)
    install_from_conf(conf)
    physical = lore_wrap(physical, run_conf or conf)
    # the query's own context: operator metrics, cleanups, broadcast
    # relations, speculations and OOM bookkeeping start empty and die in
    # the finally below, so a query costs the same on a session's first
    # day and on its thousandth query, and two threads on one session
    # never see each other's. The session's context lends its semaphore
    # and memory manager. An overlay rides the same way: batch targets
    # are consumed at EXEC time through ctx.conf
    ctx = ExecContext(run_conf, parent=session.exec_context())
    # a write runs with speculation OFF: a retry would duplicate files
    side_effects = isinstance(plan, L.WriteFile)
    ctx.speculate = ctx.speculate and not side_effects
    tm = TaskMetrics(ctx)
    session.profiler.maybe_start()
    # the observers: one global load and a None branch each when nothing
    # is configured (the registry is installed by the context above)
    reg = metrics_registry.REGISTRY
    elog = session.event_log
    frec = flight_mod.RECORDER
    sentinel = sentinel_mod.SENTINEL
    slo = slo_mod.TRACKER
    srv = ops_server_mod.SERVER
    tracker = srv.tracker if srv is not None else None
    qid = digest = None
    if (elog is not None or tracker is not None or frec is not None
            or sentinel is not None or slo is not None):
        # a traced query's id is its span's ordinal. The planner hashed
        # the pre-rewrite tree if the optimizer ran: one resolution for
        # every consumer, so lookup and record agree on the digest
        qid = q if q is not None else next(session._query_seq)
        digest = getattr(physical, "plan_digest", None) or plan_digest(plan)
    o = QueryOutcome(qid, digest, type(plan).__name__, session.tenant,
                     conf, placement, trace_path)
    for ob in (reg, elog, tracker, frec):
        if ob is not None:
            ob.query_started(o)
    # zero in-process misses AND zero backend-compile seconds around the
    # run = a COMPILE-FREE run, the only kind the cost model learns from
    cache_before = exec_cache.stats()
    # cooperative deadline: every operator checks it per produced batch
    # and the semaphore polls it, so a timed-out query unwinds through
    # the normal exception path (permits released, batches closed)
    qt = float(conf.get(QUERY_TIMEOUT))
    ctx.set_query_deadline(time.monotonic() + qt if qt > 0 else None)
    adm, ticket = adm_mod.CONTROLLER, None
    if o.tenant is not None:
        # per-tenant HBM quota attribution for every buffer this query
        # retains (mem/manager.py census; cleared in the finally)
        share = float(conf.get(adm_mod.TENANT_HBM_SHARE))
        ctx.memory.set_thread_tenant(
            o.tenant, int(share * ctx.memory.budget) if share > 0 else 0)
    t0 = time.perf_counter()
    try:
        if adm is not None:
            ticket = _admit(adm, session, ctx, o, tracker)
        try:
            try:
                out = _attempt(conf, consume, ctx, physical)
            except _OOMS as e:
                if side_effects:     # must not re-run
                    raise
                out = _oom_ladder(session, plan, e, physical, ctx, consume)
            o.ok = True
            return out
        except QueryTimeout:
            _note_timeout(o, frec)
            raise
    except BaseException as e:
        # a cancelled or failed query records WHY
        o.reason = f"{type(e).__name__}: {e}"
        raise
    finally:
        if ticket is not None:
            adm.release(ticket)      # idempotent; never raises
        if o.tenant is not None:
            ctx.memory.set_thread_tenant(None)
        ctx.set_query_deadline(None)
        o.degradations = degs = ctx.take_oom_degradations()
        o.ladder_rung = ctx.take_ladder_rung()
        # broadcast relations leave the memory manager and cleanups run,
        # returned or raised; the metrics stay readable (EXPLAIN ANALYZE)
        ctx.close()
        session.profiler.maybe_stop()
        o.metrics = session.last_query_metrics = tm.finish()
        if qargs is not None:
            # the trace alone answers "did this query touch the device"
            # and how many operator ids the summary above walked
            qargs["ok"] = o.ok
            qargs["metric_execs"] = len(ctx.metrics)
            if report is not None:
                qargs["placement"] = report.verdict
        if degs and report is not None:
            # runtime pressure degradations join the coded placement
            # report (the only tags recorded AFTER planning)
            for d in degs:
                report.plan_tags.append(make_tag(
                    OOM_PRESSURE_HOST, d["detail"], node=d["op"]))
            o.placement = session.last_placement_report = report.summary()
        o.wall_s = time.perf_counter() - t0
        # a PROCESS-global delta: a concurrent query's compile lands in
        # it too; the sentinel then skips the run as cold (conservative)
        o.compile_s = round(exec_cache.stats()["compile_s"]
                            - cache_before["compile_s"], 4)
        if o.ok and degs:
            o.reason = ("degraded: " + "; ".join(
                f"{d['op']}: {d['detail']}" for d in degs))[:500]
        # this thread drove every decision site of this query
        decs = (aqe_log.since(aqe_mark, thread=threading.get_ident())
                if aqe_log is not None else [])
        o.aqe = aqe_mod.summarize(decs) if decs else None
        session.last_aqe_decisions = \
            [d.summary() for d in decs] if decs else None
        o.fault_stats = session.last_fault_stats
        for ob in (metrics_registry.REGISTRY, elog, frec, sentinel, slo,
                   tracker):
            if ob is not None:
                ob.query_ended(o)
        engine = cost.learn_from_query(o, plan, physical, ctx, cache_before)
        if engine is not None:
            #: which engine actually ran the last materialized query
            session.last_placement = engine
