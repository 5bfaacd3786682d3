"""HBM budget manager + spill orchestration.

Reference analog: RMM pool + RapidsBufferCatalog + DeviceMemoryEventHandler
(RapidsBufferCatalog.scala:810-851, DeviceMemoryEventHandler.scala:36). On
TPU, XLA owns physical HBM, so the framework performs *logical* accounting:
every long-lived device buffer the runtime retains (shuffle partitions, agg
partials, cached builds, spillable batches) is registered here; ``reserve``
enforces the budget and, on pressure, synchronously spills registered buffers
(device -> host -> disk) in spill-priority order, exactly the role of the
reference's onAllocFailure callback. When spilling cannot satisfy a request,
a RetryOOM/SplitAndRetryOOM is raised for the retry framework (retry.py).

Fault injection (force_retry_oom / force_split_and_retry_oom) mirrors
RmmSpark.forceRetryOOM — the backbone of the reference's OOM test suites
(HashAggregateRetrySuite.scala:121-222).
"""
from __future__ import annotations

import logging
import sys
import threading
import time
from contextlib import contextmanager
from typing import Dict, List, Optional

from ..config import (ALLOC_FRACTION, HBM_LIMIT_BYTES, HOST_SPILL_LIMIT,
                      SPILL_DIR, TpuConf)
from ..trace import core as trace_core

__all__ = ["MemoryManager", "RetryOOM", "SplitAndRetryOOM", "OutOfDeviceMemory"]


log = logging.getLogger(__name__)

class RetryOOM(RuntimeError):
    """Allocation failed but retrying after spill may succeed
    (ref GpuRetryOOM jni)."""


class SplitAndRetryOOM(RuntimeError):
    """Retry alone cannot succeed; caller must split its input
    (ref GpuSplitAndRetryOOM jni)."""


class OutOfDeviceMemory(RuntimeError):
    """Unrecoverable: nothing left to spill and input cannot be split."""


#: budget base on the CPU backend, whose devices report no memory limit
_CPU_ASSUMED_BYTES = 8 * 1024 * 1024 * 1024


def _pinned_or_first_device():
    """The device the engine computes on: an explicitly pinned default
    device (tests pin 'cpu') is honoured, and other backends are NEVER
    initialized just for bookkeeping — touching the TPU client here would
    block if another process holds the chip."""
    import jax
    dd = jax.config.jax_default_device
    if dd is not None:
        return jax.devices(dd)[0] if isinstance(dd, str) else dd
    return jax.local_devices()[0]


def _device_hbm_bytes() -> int:
    """Memory limit of the device the engine computes on. An accelerator
    that does not report its ``bytes_limit`` is an error, never a guess:
    a budget invented for a chip either wastes most of its HBM or plans
    past it."""
    d = _pinned_or_first_device()
    stats = d.memory_stats()
    if stats and "bytes_limit" in stats:
        return int(stats["bytes_limit"])
    if d.platform == "cpu":
        return _CPU_ASSUMED_BYTES
    raise RuntimeError(
        f"device {d} ({d.platform}) reports no bytes_limit in "
        f"memory_stats() ({stats!r}): set "
        "spark.rapids.tpu.memory.hbm.limitBytes explicitly")


class MemoryManager:
    _global_lock = threading.Lock()
    # tpulint: guarded-by _global_lock
    _instances: Dict[int, "MemoryManager"] = {}

    def __init__(self, budget_bytes: int, host_limit_bytes: int,
                 spill_dir: str, use_native: bool = False):
        self.budget = budget_bytes
        self.host_limit = host_limit_bytes
        self.spill_dir = spill_dir
        self._lock = threading.RLock()
        # native accounting + fault machine (mem/native.py -> oom_state.cpp);
        # process-global, so only opted into (the singleton path uses it)
        self._native = None
        if use_native:
            from .native import NativeOomState, load
            if load() is not None:
                self._native = NativeOomState(budget_bytes)
        self._py_device_used = 0     # tpulint: guarded-by _lock
        self.host_used = 0           # tpulint: guarded-by _lock
        self.disk_used = 0           # tpulint: guarded-by _lock
        self._py_max_device_used = 0  # tpulint: guarded-by _lock
        self.spill_to_host_bytes = 0  # tpulint: guarded-by _lock
        self.spill_to_disk_bytes = 0  # tpulint: guarded-by _lock
        # spillables: handle -> SpillableBatch, priority-ordered on demand
        self._spillables: Dict[int, "object"] = {}  # tpulint: guarded-by _lock
        self._next_handle = 0        # tpulint: guarded-by _lock
        # fault injection: thread-ident -> [(kind, remaining_skips, count)]
        self._inject: Dict[int, List] = {}  # tpulint: guarded-by _lock
        #: bytes admitted by the OOM_PRESSURE_HOST degradation rung —
        #: host-backed emergency grants OUTSIDE the device budget
        #: (mem/retry.py ladder; SpillableBatch accounts here while a
        #: pressure grant is active on its creating thread)
        self.pressure_granted = 0    # tpulint: guarded-by _lock
        #: monotonic instant the pressure pool was last seen nonzero —
        #: the /healthz memory verdict clears once the pool has been
        #: empty past a short horizon instead of flapping per grant
        #: (ISSUE 18 satellite); None = never granted
        self._grant_last_nonzero: Optional[float] = None  # tpulint: guarded-by _lock
        #: per-thread pressure-grant depth (threading.local: no lock —
        #: each thread reads/writes only its own slot)
        self._grant = threading.local()
        #: tenant the calling thread's reserves run as (threading.local:
        #: exec/query.run_query sets it per query from
        #: spark.rapids.tpu.tenant.*)
        self._tenant = threading.local()
        #: handle -> owning tenant for registered spillables: tenant
        #: usage is a CENSUS over live registrations, so a spilled or
        #: closed buffer leaves its tenant's account by construction —
        #: cross-tenant leakage is structurally impossible
        self._spillable_tenant: Dict[int, str] = {}  # tpulint: guarded-by _lock
        #: last-declared quota per tenant (bytes; telemetry only — the
        #: enforcing quota is the calling thread's own)
        self._tenant_quota: Dict[str, int] = {}  # tpulint: guarded-by _lock
        #: alloc/free logging (ref spark.rapids.memory.gpu.debug=STDOUT,
        #: RapidsConf.scala:376)
        self.debug_log = False

    # ------------------------------------------------------------------ ctor
    @classmethod
    def get(cls, conf: Optional[TpuConf] = None) -> "MemoryManager":
        conf = conf or TpuConf()
        limit = conf.get(HBM_LIMIT_BYTES)
        if not limit:
            limit = int(_device_hbm_bytes() * conf.get(ALLOC_FRACTION))
        key = limit
        with cls._global_lock:
            if key not in cls._instances:
                # first (largest-budget) singleton owns the native machine
                cls._instances[key] = cls(limit, conf.get(HOST_SPILL_LIMIT),
                                          conf.get(SPILL_DIR),
                                          use_native=not cls._instances)
            inst = cls._instances[key]
            from ..config import MEMORY_DEBUG
            inst.debug_log = bool(conf.get(MEMORY_DEBUG))
            return inst

    # ------------------------------------------------------------ accounting
    @property
    def state_machine(self) -> str:
        """Which OOM accounting twin this manager runs: "native"
        (native/oom_state.cpp through mem/native.py) or "python"."""
        return "native" if self._native is not None else "python"

    @property
    def device_used(self) -> int:
        if self._native is not None:
            return self._native.used
        # tpulint: disable=lock-discipline — lock-free by design: a
        # single int read for logging/telemetry; stats() takes the lock
        return self._py_device_used

    @property
    def max_device_used(self) -> int:
        if self._native is not None:
            return self._native.max_used
        # tpulint: disable=lock-discipline — lock-free by design: a
        # single int read for logging/telemetry; stats() takes the lock
        return self._py_max_device_used

    # ----------------------------------------------------------- registration
    def register_spillable(self, spillable) -> int:
        tenant = getattr(self._tenant, "name", None)
        with self._lock:
            h = self._next_handle
            self._next_handle += 1
            self._spillables[h] = spillable
            if tenant:
                # stamp the owner at registration: quota enforcement and
                # per-tenant telemetry census over this map
                self._spillable_tenant[h] = tenant
            return h

    def unregister_spillable(self, handle: int):
        with self._lock:
            self._spillables.pop(handle, None)
            self._spillable_tenant.pop(handle, None)

    # ------------------------------------------------------------- tenants
    def set_thread_tenant(self, tenant: Optional[str],
                          quota_bytes: int = 0) -> None:
        """Attribute the calling thread's retained buffers to ``tenant``
        (None clears). With ``quota_bytes > 0``, :meth:`reserve`
        enforces the per-tenant HBM share: a breach first spills the
        tenant's OWN spillables, then raises into the tenant's own
        rung-1/2 retry ladder — never a rung-3 cross-session spill on
        other tenants (ISSUE 18)."""
        self._tenant.name = tenant or None
        self._tenant.quota = max(0, int(quota_bytes))
        if tenant and quota_bytes > 0:
            with self._lock:
                self._tenant_quota[tenant] = int(quota_bytes)

    def thread_tenant(self) -> Optional[str]:
        return getattr(self._tenant, "name", None)

    def tenant_device_used(self, tenant: str) -> int:
        """Device-resident bytes retained by ``tenant``'s live
        spillables (the quota census)."""
        with self._lock:
            return self._tenant_used_locked(tenant)

    def _tenant_used_locked(self, tenant: str) -> int:
        return sum(s.device_bytes()
                   for h, s in self._spillables.items()
                   if s.tier == "device"
                   and self._spillable_tenant.get(h) == tenant)

    def _spill_tenant(self, tenant: str, need_bytes: int) -> int:
        """Spill ``tenant``'s OWN device spillables in priority order —
        the quota breach's self-help step, deliberately blind to every
        other tenant's buffers."""
        with self._lock:
            candidates = sorted(
                (s for h, s in self._spillables.items()
                 if s.tier == "device"
                 and self._spillable_tenant.get(h) == tenant),
                key=lambda s: s.spill_priority)
        freed = 0
        for s in candidates:
            if freed >= need_bytes:
                break
            freed += s.spill_to_host()
        return freed

    def _enforce_tenant_quota(self, nbytes: int) -> None:
        """Per-tenant HBM share gate (reserve-time, BEFORE the global
        budget): over quota, spill the tenant's own buffers; still over,
        raise RetryOOM (rung 1) or SplitAndRetryOOM when this single
        allocation alone exceeds the share (rung 2). The raise precedes
        any global-budget pressure, so a quota breach rides the
        breaching tenant's own ladder instead of forcing a cross-session
        spill on everyone else."""
        tenant = getattr(self._tenant, "name", None)
        quota = getattr(self._tenant, "quota", 0)
        if not tenant or quota <= 0:
            return
        with self._lock:
            used = self._tenant_used_locked(tenant)
        if used + nbytes <= quota:
            return
        self._spill_tenant(tenant, used + nbytes - quota)
        with self._lock:
            used = self._tenant_used_locked(tenant)
        if used + nbytes <= quota:
            return
        if nbytes > quota:
            raise SplitAndRetryOOM(
                f"tenant {tenant}: allocation of {nbytes} exceeds the "
                f"whole tenant HBM share {quota}")
        raise RetryOOM(
            f"tenant {tenant}: reserve of {nbytes} would exceed the "
            f"tenant HBM share (used={used}, quota={quota})")

    # ------------------------------------------------------------ accounting
    def reserve(self, nbytes: int, allow_spill: bool = True):
        """Account for nbytes of device memory about to be retained.

        On budget pressure: spill registered buffers; on injected or real
        exhaustion raise RetryOOM / SplitAndRetryOOM
        (ref DeviceMemoryEventHandler.onAllocFailure -> store.spill)."""
        if self.debug_log:
            log.info("alloc %d B (used %d B)", nbytes, self.device_used)
        if self.in_pressure_grant():
            # the degradation rung must never fail a granted thread's
            # reserve — checked FIRST so the native allocator (whose
            # budget enforcement and injections have no grant notion)
            # and the chaos/injection hooks are all bypassed. Bytes land
            # in the unbudgeted pressure pool, with a thread-local
            # ledger so the matching release() inside the grant drains
            # the SAME pool instead of under-counting other buffers'
            # device bytes (SpillableBatch skips reserve() entirely and
            # handles cross-grant-boundary symmetry with its _granted
            # flag).
            self._grant.ledger = getattr(self._grant, "ledger", 0) + nbytes
            self.reserve_granted(nbytes)
            return
        self._maybe_chaos()
        # per-tenant HBM share (ISSUE 18): gated BEFORE the global
        # budget so a breaching tenant self-spills / splits on its own
        # ladder instead of pressuring everyone else's buffers
        self._enforce_tenant_quota(nbytes)
        if self._native is not None:
            rc = self._native.reserve(nbytes, block_ms=0)
            if rc == 0:
                self._trace_alloc(nbytes)
                return
            if rc == 2:
                raise SplitAndRetryOOM(
                    f"native: allocation of {nbytes} cannot ever fit "
                    f"(budget {self.budget}) or split was injected")
            if allow_spill:
                self.spill_device(nbytes)
                # brief native block/wake window lets concurrent releases in
                rc = self._native.reserve(nbytes, block_ms=20)
                if rc == 0:
                    self._trace_alloc(nbytes)
                    return
            raise RetryOOM(f"native: could not reserve {nbytes} "
                           f"(used={self.device_used}, budget={self.budget})")
        self._maybe_inject()
        with self._lock:
            if self._py_device_used + nbytes <= self.budget:
                self._py_device_used += nbytes
                self._py_max_device_used = max(self._py_max_device_used,
                                               self._py_device_used)
                self._trace_alloc(nbytes)
                return
        if allow_spill:
            with self._lock:
                # read the shortfall under the lock: a stale used-count
                # here under-spills and turns a satisfiable reserve
                # into a spurious RetryOOM
                shortfall = nbytes - (self.budget - self._py_device_used)
            self.spill_device(shortfall)
            with self._lock:
                if self._py_device_used + nbytes <= self.budget:
                    self._py_device_used += nbytes
                    self._py_max_device_used = max(self._py_max_device_used,
                                                   self._py_device_used)
                    self._trace_alloc(nbytes)
                    return
        if nbytes > self.budget:
            raise SplitAndRetryOOM(
                f"allocation of {nbytes} exceeds whole budget {self.budget}")
        raise RetryOOM(f"could not reserve {nbytes} "
                       f"(used={self.device_used}, budget={self.budget})")

    def _trace_alloc(self, nbytes: int) -> None:
        tr = trace_core.TRACER       # single branch when tracing is off
        if tr is not None:
            tr.counter("mem.device_used", {"bytes": self.device_used,
                                           "alloc": nbytes}, cat="mem")

    def release(self, nbytes: int):
        if self.debug_log:
            log.info("free  %d B (used %d B)", nbytes,
                     self.device_used - nbytes)
        # symmetric with the grant branch in reserve(): bytes this
        # thread reserved UNDER the grant (ledger) drain the grant
        # pool; anything beyond the ledger is a pre-grant buffer
        # being closed under the grant and falls through to the
        # normal device accounting. The ledger is drained even when
        # the grant scope has already EXITED (ISSUE 18 satellite): a
        # reserve made under the grant whose release lands after the
        # scope closed used to strand its bytes in pressure_granted
        # forever — degrading the /healthz memory verdict with zero
        # live granted bytes — while the normal accounting was
        # under-counted by the same amount.
        led = getattr(self._grant, "ledger", 0)
        if led > 0:
            take = min(nbytes, led)
            self._grant.ledger = led - take
            self.release_granted(take)
            nbytes -= take
            if nbytes <= 0:
                return
        if self._native is not None:
            self._native.release(nbytes)
            return
        with self._lock:
            self._py_device_used = max(0, self._py_device_used - nbytes)

    def reserve_absorbing_retries(self, nbytes: int, attempts: int = 10):
        """``reserve`` that absorbs transient RetryOOMs at the allocation
        site itself: spill-and-retry a bounded number of times before
        letting the OOM escape to the caller's retry frame (ref RMM's
        alloc loop re-entering the spill callback before GpuRetryOOM
        reaches the task thread). SpillableBatch wraps reserve through
        this, so a bare ``[SpillableBatch(b, mm) for b in ...]``
        comprehension survives an injected or transient OOM without every
        call site needing its own retry closure. SplitAndRetryOOM is
        NEVER absorbed — only the caller can split its input."""
        last: Optional[BaseException] = None
        for attempt in range(max(1, attempts)):
            try:
                return self.reserve(nbytes)
            except RetryOOM as e:
                last = e
                tr = trace_core.TRACER
                if tr is not None:
                    tr.instant("oom.retry", cat="mem",
                               args={"attempt": attempt, "site": "reserve"})
                from ..metrics import registry as metrics_registry
                mr = metrics_registry.REGISTRY
                if mr is not None:
                    mr.counter("srtpu_oom_retries_total").inc()
                self.spill_device(nbytes)
                time.sleep(0)        # yield so other tasks can release
        raise last

    # --------------------------------------------------- pressure grants
    def in_pressure_grant(self) -> bool:
        """True while the calling thread runs under the OOM escalation
        ladder's host degradation rung (mem/retry.py)."""
        return getattr(self._grant, "depth", 0) > 0

    @contextmanager
    def pressure_host_grant(self):
        """Admit the calling thread's new spillables OUTSIDE the device
        budget for the duration: the final escalation rung after retries,
        splits and a cross-session pressure spill all failed. Buffers
        created under the grant account into ``pressure_granted`` (their
        own flag keeps release symmetric) and reserve-time fault
        injection is suppressed — the work is off the device path."""
        self._grant.depth = getattr(self._grant, "depth", 0) + 1
        try:
            yield self
        finally:
            self._grant.depth -= 1

    def reserve_granted(self, nbytes: int):
        with self._lock:
            self.pressure_granted += nbytes
            if self.pressure_granted > 0:
                self._grant_last_nonzero = time.monotonic()

    def release_granted(self, nbytes: int):
        with self._lock:
            if self.pressure_granted > 0:
                # stamp the drain instant: pressure_grant_idle_s (and
                # the /healthz clear horizon) measure from the moment
                # the pool was LAST nonzero, not from first grant
                self._grant_last_nonzero = time.monotonic()
            self.pressure_granted = max(0, self.pressure_granted - nbytes)

    def reserve_host(self, nbytes: int):
        with self._lock:
            self.host_used += nbytes

    def release_host(self, nbytes: int):
        with self._lock:
            self.host_used = max(0, self.host_used - nbytes)

    # --------------------------------------------------------------- spilling
    def spill_device(self, need_bytes: int) -> int:
        """Synchronously spill device-tier spillables in priority order until
        need_bytes freed (ref RapidsBufferStore.synchronousSpill)."""
        tr = trace_core.TRACER
        t0 = tr.now() if tr is not None else 0
        with self._lock:
            candidates = sorted(
                (s for s in self._spillables.values()
                 if s.tier == "device"),
                key=lambda s: s.spill_priority)
        freed = 0
        for s in candidates:
            if freed >= need_bytes:
                break
            freed += s.spill_to_host()
        if tr is not None and (need_bytes > 0 or freed > 0):
            # the retry loop's spill_device(0) nudge is a no-op here
            # (freed >= 0 breaks immediately) — a span for it would
            # count phantom spills in the profiler
            tr.complete("spill.device", t0, cat="mem",
                        args={"need_bytes": need_bytes,
                              "freed_bytes": freed})
        # host pressure cascades to disk
        with self._lock:
            over = self.host_used - self.host_limit
        if over > 0:
            self.spill_host(over)
        return freed

    def spill_everything(self) -> int:
        """Spill EVERY device-tier spillable this manager tracks (and
        cascade host pressure to disk): the cross-session pressure rung
        of the OOM escalation ladder — other sessions' builds, broadcast
        relations and parked partials all move off-device so one starving
        operator gets the whole budget (ref synchronousSpill(store, 0))."""
        with self._lock:
            need = sum(s.device_bytes() for s in self._spillables.values()
                       if s.tier == "device")
        return self.spill_device(need) if need > 0 else 0

    @classmethod
    def spill_all_sessions(cls) -> int:
        """``spill_everything`` across every live budget singleton — the
        process-wide pressure valve the retry ladder pulls before the
        host degradation rung. Returns total bytes freed."""
        with cls._global_lock:
            insts = list(cls._instances.values())
        freed = 0
        for mm in insts:
            freed += mm.spill_everything()
        from ..metrics import registry as metrics_registry
        mr = metrics_registry.REGISTRY
        if mr is not None:
            mr.counter("srtpu_oom_pressure_spills_total").inc()
        return freed

    def spill_host(self, need_bytes: int) -> int:
        tr = trace_core.TRACER
        t0 = tr.now() if tr is not None else 0
        with self._lock:
            candidates = sorted(
                (s for s in self._spillables.values() if s.tier == "host"),
                key=lambda s: s.spill_priority)
        freed = 0
        for s in candidates:
            if freed >= need_bytes:
                break
            freed += s.spill_to_disk()
        if tr is not None and (need_bytes > 0 or freed > 0):
            tr.complete("spill.host", t0, cat="mem",
                        args={"need_bytes": need_bytes,
                              "freed_bytes": freed})
        return freed

    # -------------------------------------------------------- fault injection
    def force_retry_oom(self, num_ooms: int = 1, skip: int = 0,
                        thread_id: Optional[int] = None):
        """Next `num_ooms` reserves on the thread raise RetryOOM after
        skipping `skip` (ref RmmSpark.forceRetryOOM)."""
        if self._native is not None:
            self._native.force_retry_oom(num_ooms, skip, thread_id)
            return
        tid = thread_id if thread_id is not None else threading.get_ident()
        with self._lock:
            self._inject.setdefault(tid, []).append(["retry", skip, num_ooms])

    def force_split_and_retry_oom(self, num_ooms: int = 1, skip: int = 0,
                                  thread_id: Optional[int] = None):
        if self._native is not None:
            self._native.force_split_and_retry_oom(num_ooms, skip, thread_id)
            return
        tid = thread_id if thread_id is not None else threading.get_ident()
        with self._lock:
            self._inject.setdefault(tid, []).append(["split", skip, num_ooms])

    def clear_injections(self):
        if self._native is not None:
            self._native.clear_injections()
        with self._lock:
            self._inject.clear()

    def _maybe_chaos(self):
        """Config-armed chaos sites at the reserve entry point (the
        process-global ChaosController, aux/fault.py): ``mem.oom`` raises
        an injected RetryOOM, ``mem.reserve.delay`` stalls the reserve.
        One list-read when chaos is disarmed; suppressed entirely under a
        pressure grant (the thread is already off the device path)."""
        from ..aux.fault import active_chaos
        ctl = active_chaos()
        if ctl is None or self.in_pressure_grant():
            return
        if ctl.wants("mem.reserve.delay"):
            ctl.maybe_delay("mem.reserve.delay")
        if ctl.wants("mem.oom") and ctl.fires("mem.oom"):
            # record the OPERATOR-level reserve site (first frame outside
            # mem/) so the chaos battery can assert injection breadth
            f = sys._getframe(1)
            while f is not None and ("/mem/" in
                                     f.f_code.co_filename.replace("\\", "/")):
                f = f.f_back
            if f is not None:
                import os as _os
                ctl.note_context(
                    "mem.oom",
                    f"{_os.path.basename(f.f_code.co_filename)}:"
                    f"{f.f_code.co_name}")
            raise RetryOOM("chaos: injected mem.oom at reserve()")

    def _maybe_inject(self):
        if self.in_pressure_grant():
            return
        tid = threading.get_ident()
        with self._lock:
            queue = self._inject.get(tid)
            if not queue:
                return
            entry = queue[0]
            kind, skip, count = entry
            if skip > 0:
                entry[1] -= 1
                return
            entry[2] -= 1
            if entry[2] <= 0:
                queue.pop(0)
                if not queue:
                    self._inject.pop(tid, None)
        if kind == "retry":
            raise RetryOOM("injected RetryOOM")
        raise SplitAndRetryOOM("injected SplitAndRetryOOM")

    # ----------------------------------------------------------- leak audit
    def audit_leaks(self) -> List[dict]:
        """Live (unclosed) spillable registrations — the MemoryCleaner
        leak tracker analog (ref Plugin.scala:573-588: cudf MemoryCleaner
        asserts no leaked device buffers at shutdown). Every
        SpillableBatch a query creates must be close()d by the time its
        sink finishes; anything still registered here afterwards is a
        leak. Entries carry the creation site when leak-detection debug
        is on (SpillableBatch records it)."""
        with self._lock:
            return [{"handle": h, "tier": s.tier,
                     "bytes": s.device_bytes(),
                     "created_at": getattr(s, "created_at", None)}
                    for h, s in self._spillables.items()]

    @classmethod
    def audit_all_leaks(cls) -> List[dict]:
        with cls._global_lock:
            insts = list(cls._instances.values())
        out = []
        for mm in insts:
            out.extend(mm.audit_leaks())
        return out

    @classmethod
    def stats_all(cls) -> Dict[str, int]:
        """Aggregate accounting across every live budget singleton — the
        metrics sampler's view (one process may hold several budgets in
        tests; fleet gauges sum them). Each instance is read through
        its own lock'd stats() so a manager mid-spill contributes a
        consistent row, not a torn one."""
        with cls._global_lock:
            insts = list(cls._instances.values())
        out = {"device_used": 0, "host_used": 0, "disk_used": 0,
               "max_device_used": 0, "budget": 0,
               "spill_to_host_bytes": 0, "spill_to_disk_bytes": 0,
               "pressure_granted": 0}
        tenant_used: Dict[str, int] = {}
        tenant_quota: Dict[str, int] = {}
        idle = None
        for mm in insts:
            st = mm.stats()
            for k in out:
                out[k] += st[k]
            for t, v in (st.get("tenant_used") or {}).items():
                tenant_used[t] = tenant_used.get(t, 0) + v
            for t, v in (st.get("tenant_quota") or {}).items():
                tenant_quota[t] = tenant_quota.get(t, 0) + v
            i = st.get("pressure_grant_idle_s")
            if i is not None:
                # MIN across instances: the most recent grant activity
                # anywhere governs the process-wide clear horizon
                idle = i if idle is None else min(idle, i)
        out["tenant_used"] = tenant_used
        out["tenant_quota"] = tenant_quota
        out["pressure_grant_idle_s"] = idle
        return out

    # ------------------------------------------------------------------ stats
    def stats(self) -> Dict[str, int]:
        with self._lock:
            tenants = sorted(set(self._spillable_tenant.values())
                             | set(self._tenant_quota))
            return {"device_used": self.device_used,
                    "host_used": self.host_used,
                    "disk_used": self.disk_used,
                    "max_device_used": self.max_device_used,
                    "budget": self.budget,
                    "spill_to_host_bytes": self.spill_to_host_bytes,
                    "spill_to_disk_bytes": self.spill_to_disk_bytes,
                    "pressure_granted": self.pressure_granted,
                    # seconds since the pressure pool was last nonzero
                    # (0.0 while nonzero; None = never granted): the
                    # /healthz memory verdict's clear horizon and the
                    # admission shed check both read this
                    "pressure_grant_idle_s": (
                        0.0 if self.pressure_granted > 0
                        else (round(time.monotonic()
                                    - self._grant_last_nonzero, 3)
                              if self._grant_last_nonzero is not None
                              else None)),
                    # per-tenant device residency census (ISSUE 18):
                    # live registered spillables per owning tenant
                    "tenant_used": {t: self._tenant_used_locked(t)
                                    for t in tenants},
                    "tenant_quota": dict(self._tenant_quota),
                    "num_spillables": len(self._spillables)}
