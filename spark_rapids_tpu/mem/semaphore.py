"""Device admission semaphore (ref GpuSemaphore.scala:51).

Gates how many tasks may have live device work at once
(spark.rapids.tpu.sql.concurrentTpuTasks); tracks wait time the way
GpuTaskMetrics records gpuSemaphoreWait (GpuTaskMetrics.scala:146).

r14 adds the **wedge watchdog**: a waiter blocked past
``spark.rapids.tpu.semaphore.wedgeTimeoutMs`` wakes up, dumps a
holder/waiter/held-bytes diagnostic, and force-releases permits whose
holder THREAD is dead — a worker killed while holding the semaphore can
no longer wedge every later query (counted by
``srtpu_semaphore_wedge_total``). Waits also poll the query-lifecycle
``deadline`` (exec/query.py cooperative cancellation), so a timed-out
query never sits out the full task timeout inside acquire().
"""
from __future__ import annotations

import logging
import threading
import time
import weakref
from contextlib import contextmanager
from typing import Dict, List, Optional

from ..trace import core as trace_core

__all__ = ["DeviceSemaphore", "QueryTimeout", "wedged_census"]

log = logging.getLogger(__name__)

#: live semaphores, observed by the metrics sampler (queue depth / wait
#: totals across every in-flight query context); weak so a finished
#: query's semaphore just drops out of the sums
_SEMAPHORES: "weakref.WeakSet" = weakref.WeakSet()


def wedged_census() -> Dict[str, int]:
    """Dead/overdue holder counts across every live semaphore — the
    cheap process-wide wedge probe shared by the ops ``/healthz``
    semaphore verdict (ops/server.py) and the admission controller's
    shed check (sched/admission.py): a holder whose thread died, or
    one past the wedge horizon, means new low-priority work should be
    refused rather than queued behind a wedge."""
    dead = overdue = 0
    for s in list(_SEMAPHORES):
        d = s.diagnostics()
        horizon_s = (s.wedge_timeout_ms / 1000.0
                     if s.wedge_timeout_ms > 0 else None)
        for h in d["holders"]:
            if h.get("alive") is False:
                dead += 1
            elif horizon_s is not None and h["held_s"] >= horizon_s:
                overdue += 1
    return {"dead": dead, "overdue": overdue}


class QueryTimeout(RuntimeError):
    """The query's cooperative deadline (spark.rapids.tpu.query.timeout)
    expired: raised at batch boundaries and from semaphore waits so the
    query unwinds through the normal exception path — semaphore permits
    release via their ``with`` scopes and spillables close via the
    operators' cleanup handlers (the zero-leak audit holds)."""


class DeviceSemaphore:
    def __init__(self, permits: int, timeout_s: float = 600.0,
                 wedge_timeout_ms: int = 10000, memory=None):
        self._permits = max(1, int(permits))
        self._sem = threading.BoundedSemaphore(self._permits)
        self._timeout = timeout_s
        self.wedge_timeout_ms = int(wedge_timeout_ms)
        #: MemoryManager for held-bytes diagnostics (optional)
        self._memory = memory
        self._lock = threading.Lock()
        self.total_wait_s = 0.0      # tpulint: guarded-by _lock
        self.acquires = 0            # tpulint: guarded-by _lock
        #: tasks currently blocked in acquire() (metrics queue depth)
        self.waiting = 0             # tpulint: guarded-by _lock
        #: dead holders force-released by the wedge watchdog
        self.wedges = 0              # tpulint: guarded-by _lock
        #: thread ident -> {name, thread, since, count} for every live
        #: top-level holder (the watchdog's force-release census)
        self._holders: Dict[int, dict] = {}  # tpulint: guarded-by _lock
        self._held = threading.local()
        #: query-lifecycle deadline (time.monotonic() instant) polled by
        #: this THREAD's waits — thread-local, because sessions may share
        #: one semaphore (multi-tenant ExecContexts): a global attribute
        #: would let query A's timeout cancel query B's wait, and B's
        #: no-timeout reset would strip A's deadline mid-wait
        self._deadline = threading.local()
        _SEMAPHORES.add(self)

    def set_thread_deadline(self, deadline: Optional[float]) -> None:
        """Install (None clears) the calling thread's query deadline;
        acquire() waits on this thread poll it and raise QueryTimeout."""
        self._deadline.value = deadline

    @property
    def deadline(self) -> Optional[float]:
        return getattr(self._deadline, "value", None)

    @property
    def permits(self) -> int:
        return self._permits

    # ------------------------------------------------------------ acquire
    def acquire(self):
        if getattr(self._held, "count", 0) > 0:
            self._held.count += 1  # reentrant per task thread
            with self._lock:
                h = self._holders.get(threading.get_ident())
                if h is not None:
                    h["count"] += 1
            return
        self._maybe_watchdog()
        tr = trace_core.TRACER
        t0 = time.perf_counter()
        with self._lock:
            self.waiting += 1
        try:
            if tr is None:
                acquired = self._wait_acquire()
            else:
                sargs = {"permits": self._permits}
                with tr.span("semaphore.wait", cat="sem", args=sargs):
                    acquired = self._wait_acquire()
                    if not acquired:
                        # the timed-out wait is the WORST contention
                        # case — the profiler must see it, not just
                        # successful acquires
                        sargs["timeout"] = True
        finally:
            with self._lock:
                self.waiting -= 1
        if not acquired:
            raise TimeoutError(
                f"device semaphore not acquired within {self._timeout}s; "
                f"diagnostics: {self.diagnostics()}")
        wait = time.perf_counter() - t0
        me = threading.current_thread()
        stale = None
        with self._lock:
            self.total_wait_s += wait
            self.acquires += 1
            old = self._holders.get(threading.get_ident())
            if old is not None and old.get("thread") is not None \
                    and old["thread"] is not me \
                    and not old["thread"].is_alive():
                # the OS recycled a dead holder's thread ident before
                # the watchdog saw it; overwriting the record would
                # orphan the dead thread's permit forever — reclaim it
                stale = old
                self.wedges += 1
            self._holders[threading.get_ident()] = {
                "name": me.name, "thread": me,
                "since": time.monotonic(), "count": 1}
        if stale is not None:
            try:
                self._sem.release()
            except ValueError:  # pragma: no cover - over-release race
                pass
            log.error("semaphore wedge: reclaimed permit of dead thread "
                      "%r whose ident was recycled", stale["name"])
            from ..metrics import registry as metrics_registry
            mr = metrics_registry.REGISTRY
            if mr is not None:
                mr.counter("srtpu_semaphore_wedge_total").inc()
            from ..ops import flight as flight_mod
            fr = flight_mod.RECORDER
            if fr is not None:
                fr.trigger("semaphore_wedge",
                           detail=f"reclaimed permit of dead thread "
                                  f"{stale['name']!r} (recycled ident); "
                                  f"diagnostics: {self.diagnostics()}")
        self._held.count = 1
        # chaos site: a holder that stalls WITH the permit (the stuck-
        # holder scenario the wedge watchdog diagnoses; aux/fault.py)
        from ..aux.fault import active_chaos
        ctl = active_chaos()
        if ctl is not None and ctl.wants("sem.stall"):
            ctl.maybe_delay("sem.stall")

    def _wait_acquire(self) -> bool:
        """Bounded-step wait loop: wake at the wedge horizon to run the
        watchdog, and at the query deadline to cancel cooperatively.
        With the watchdog off and no deadline this is one plain
        acquire(timeout=task timeout), the pre-r14 behavior."""
        start = time.monotonic()
        wedge_s = (self.wedge_timeout_ms / 1000.0
                   if self.wedge_timeout_ms > 0 else None)
        while True:
            now = time.monotonic()
            remaining = self._timeout - (now - start)
            if remaining <= 0:
                return False
            step = remaining
            if wedge_s is not None:
                step = min(step, wedge_s)
            dl = self.deadline
            if dl is not None:
                dl_rem = dl - now
                if dl_rem <= 0:
                    raise QueryTimeout(
                        "query deadline expired while waiting on the "
                        f"device semaphore; diagnostics: "
                        f"{self.diagnostics()}")
                step = min(step, dl_rem)
            if self._sem.acquire(timeout=max(step, 0.001)):
                return True
            if wedge_s is not None \
                    and (time.monotonic() - start) >= wedge_s:
                self.check_wedged()

    # ----------------------------------------------------------- watchdog
    def _maybe_watchdog(self) -> None:
        """Cheap overdue-holder sweep on every top-level acquire: a dead
        holder of one of N permits silently halves capacity even when
        no single waiter ever starves past the wedge horizon — the
        starving-waiter path alone would never notice. One short
        lock'd scan (<= permits entries) per acquire."""
        if self.wedge_timeout_ms <= 0:
            return
        wedge_s = self.wedge_timeout_ms / 1000.0
        now = time.monotonic()
        with self._lock:
            overdue = any(now - h["since"] >= wedge_s
                          for h in self._holders.values())
        if overdue:
            self.check_wedged()

    def check_wedged(self) -> List[dict]:
        """Wedge watchdog pass: force-release permits whose holder
        thread is DEAD (it can never release; a killed worker must not
        wedge the semaphore forever) and dump holder/waiter diagnostics
        when anything looks stuck. Returns the force-released holder
        records. Safe to call from any thread (the sampler or a waiter);
        live holders are never touched — cooperative cancellation is the
        tool for those."""
        now = time.monotonic()
        released: List[dict] = []
        stuck = False
        wedge_s = self.wedge_timeout_ms / 1000.0 \
            if self.wedge_timeout_ms > 0 else None
        with self._lock:
            for tid, h in list(self._holders.items()):
                th = h.get("thread")
                if th is not None and not th.is_alive():
                    self._holders.pop(tid)
                    released.append(h)
                    self.wedges += 1
                elif wedge_s is not None and now - h["since"] >= wedge_s:
                    stuck = True
        for h in released:
            try:
                self._sem.release()
            except ValueError:  # pragma: no cover - over-release race
                log.error("semaphore force-release raced a real release "
                          "for holder %r", h["name"])
            log.error(
                "semaphore wedge: force-released permit held by DEAD "
                "thread %r (held %.1fs)", h["name"], now - h["since"])
            from ..metrics import registry as metrics_registry
            mr = metrics_registry.REGISTRY
            if mr is not None:
                mr.counter("srtpu_semaphore_wedge_total").inc()
        if released:
            # anomaly hook (ISSUE 15): a force-release previously left
            # its census only in the log — dump a flight bundle while
            # the holder table still shows the wedge
            from ..ops import flight as flight_mod
            fr = flight_mod.RECORDER
            if fr is not None:
                fr.trigger(
                    "semaphore_wedge",
                    detail=f"force-released {len(released)} permit(s) "
                           f"of dead holder(s) "
                           f"{[h['name'] for h in released]}; "
                           f"diagnostics: {self.diagnostics()}")
        if released or stuck:
            log.warning("semaphore diagnostics: %s", self.diagnostics())
        return released

    def diagnostics(self) -> dict:
        """Holder/waiter/held-bytes census for wedge dumps and timeout
        errors (the GpuSemaphore dump analog)."""
        now = time.monotonic()
        with self._lock:
            holders = [{"thread": h["name"], "ident": tid,
                        "alive": (h["thread"].is_alive()
                                  if h.get("thread") is not None else None),
                        "held_s": round(now - h["since"], 3),
                        "reentry": h["count"]}
                       for tid, h in self._holders.items()]
            waiting = self.waiting
            wedges = self.wedges
        out = {"permits": self._permits, "waiting": waiting,
               "holders": holders, "wedges": wedges}
        if self._memory is not None:
            out["memory"] = self._memory.stats()
        return out

    # ------------------------------------------------------------ release
    def release(self):
        c = getattr(self._held, "count", 0)
        if c <= 0:
            return
        if c == 1:
            with self._lock:
                self._holders.pop(threading.get_ident(), None)
            self._sem.release()
        else:
            with self._lock:
                h = self._holders.get(threading.get_ident())
                if h is not None:
                    h["count"] = c - 1
        self._held.count = c - 1

    @contextmanager
    def held(self):
        self.acquire()
        try:
            yield self
        finally:
            self.release()
