"""Anomaly-triggered flight recorder (ISSUE 15).

Reference analog: the diagnostic artifacts the RAPIDS Profiling tool
mines after the fact — except cut *at the moment of the anomaly*, while
the wedged holder table, the pressure-grant pool and the trace ring
still show the failure. The PR-14 watchdogs detect wedges, OOM ladders
and timeouts but dump their diagnostics only into exception strings;
this module turns each of those sites into a trigger hook that writes
ONE self-contained bundle directory.

Trigger taxonomy (closed — :data:`TRIGGERS`; docs/ops.md):

* ``semaphore_wedge``    — the wedge watchdog force-released a dead
  holder's permit (mem/semaphore.py);
* ``oom_ladder``         — an OOM escalation reached rung >= 3 (the
  cross-session pressure spill or the host degradation rung,
  mem/retry.py / the query-level ladder);
* ``query_timeout``      — a query was cancelled by the cooperative
  ``spark.rapids.tpu.query.timeout`` deadline;
* ``worker_evicted``     — the driver evicted a worker that chaos did
  NOT deliberately kill (shuffle/cluster.py);
* ``warm_recompile``     — backend-compile seconds were observed on a
  plan digest in the compiled-plan set (a warm digest paid a compile it
  was vouched never to pay again);
* ``placement_revert``   — a digest whose history says device planned
  host (fired by the regression sentinel's verdict-flip check);
* ``sentinel_regression``— any other sentinel flag (warm-digest
  slowdown, new rung-3+ escalation);
* ``admission_shed``     — a burst of admission rejections past the
  controller's rate threshold (``spark.rapids.tpu.admission.shed.*``):
  the bundle names the pressured section the shed verdict blamed
  (sched/admission.py, docs/serving.md).

Bundle layout — five sections, written atomically (a temp directory
renamed into place, so a reader never sees a partial bundle):

* ``trace.json``     — the tracer ring tail plus the recorder's own
  breadcrumb ring;
* ``metrics.json``   — a metric-registry snapshot (after one
  synchronous sample pass), or null when metrics are off;
* ``state.json``     — semaphore holder/waiter diagnostics, memory-tier
  accounting (pressure-grant pool included) and executable-cache
  counters;
* ``placement.json`` — the trigger, detail, and the current query's
  digest + coded PlacementReport summary when one is in flight;
* ``config.json``    — the conf delta from registered defaults,
  redacted (secret-shaped keys keep their names, lose their values).

Dumps are rate-limited per trigger kind
(``spark.rapids.tpu.flight.rateLimitMs``) and counted by
``srtpu_flight_dumps_total{trigger=...}``. Disabled
(``spark.rapids.tpu.flight.enabled`` off) the recorder is ``None`` and
every trigger site costs one module-global load + branch.
"""
from __future__ import annotations

import json
import logging
import os
import threading
import time
from collections import deque
from typing import Dict, List, Optional

from ..config import register

__all__ = ["FlightRecorder", "TRIGGERS", "install_flight",
           "ensure_flight_from_conf", "active_flight", "FLIGHT_ENABLED",
           "FLIGHT_DIR", "FLIGHT_RATE_LIMIT_MS", "FLIGHT_RING_EVENTS"]

log = logging.getLogger(__name__)

FLIGHT_ENABLED = register(
    "spark.rapids.tpu.flight.enabled", False,
    "Arm the anomaly-triggered flight recorder (ops/flight.py): "
    "semaphore wedges, OOM ladder rungs >= 3, query timeouts, "
    "chaos-free worker evictions, warm-digest recompiles and placement "
    "reverts each atomically dump one redacted diagnostic bundle "
    "directory (trace ring tail, metrics snapshot, semaphore/memory/"
    "exec-cache state, placement report, config delta) under "
    "spark.rapids.tpu.flight.dir, rate-limited per trigger kind "
    "(docs/ops.md). Off by default: every trigger site is a single "
    "branch when disabled.", commonly_used=True)

FLIGHT_DIR = register(
    "spark.rapids.tpu.flight.dir", "/tmp/srtpu_flight",
    "Directory flight-recorder bundles are written under (one "
    "subdirectory per dump, created on first trigger).")

FLIGHT_RATE_LIMIT_MS = register(
    "spark.rapids.tpu.flight.rateLimitMs", 60000,
    "Minimum milliseconds between two bundles of the SAME trigger kind; "
    "suppressed triggers are counted (FlightRecorder.stats) but write "
    "nothing. <= 0 disables rate limiting.")

FLIGHT_RING_EVENTS = register(
    "spark.rapids.tpu.flight.ring.events", 256,
    "Capacity of the recorder's always-on breadcrumb ring (anomaly "
    "notes kept in memory between dumps; the newest tail ships inside "
    "every bundle's trace.json).")

#: closed trigger taxonomy — an unknown kind is a programming error and
#: raises (the plan/tags.py idiom: structurally impossible to ship an
#: undocumented trigger)
TRIGGERS = ("semaphore_wedge", "oom_ladder", "query_timeout",
            "worker_evicted", "warm_recompile", "placement_revert",
            "sentinel_regression", "admission_shed", "slo_burn")

#: the process-global recorder; ``None`` means the flight recorder is
#: OFF and every trigger site costs exactly one attribute load + branch
RECORDER: Optional["FlightRecorder"] = None

#: substrings marking a conf key as secret-bearing: the bundle keeps the
#: key (operators need to know it was set) but redacts the value
_SECRET_TOKENS = ("secret", "password", "passwd", "token", "credential",
                  "apikey", "api.key", "auth")


def redact_conf(raw: dict) -> dict:
    """Copy of a raw conf dict with secret-shaped values replaced."""
    out = {}
    for k in sorted(raw):
        kl = str(k).lower()
        if any(t in kl for t in _SECRET_TOKENS):
            out[str(k)] = "<redacted>"
        else:
            out[str(k)] = str(raw[k])
    return out


class FlightRecorder:
    """Bounded diagnostic ring + atomic bundle writer. Thread-safe;
    triggers never raise into their (already-failing) call sites —
    bundle-write errors are logged and swallowed."""

    def __init__(self, directory: str, rate_limit_ms: int = 60000,
                 ring_events: int = 256, conf=None):
        self.dir = str(directory)
        self.rate_limit_ms = int(rate_limit_ms)
        #: conf the recorder was installed from (the config.json delta)
        self._conf = conf
        self._lock = threading.Lock()
        #: always-on breadcrumb ring, oldest dropped
        self._ring: deque = deque(
            maxlen=max(16, int(ring_events)))  # tpulint: guarded-by _lock
        self._last: Dict[str, float] = {}    # tpulint: guarded-by _lock
        self._seq = 0                        # tpulint: guarded-by _lock
        self.dumps: Dict[str, int] = {}      # tpulint: guarded-by _lock
        self.suppressed: Dict[str, int] = {}  # tpulint: guarded-by _lock
        #: paths of every bundle written, oldest first
        self.bundles: List[str] = []         # tpulint: guarded-by _lock
        #: the in-flight query on THIS thread (set by query_started):
        #: {"queryId", "planDigest", "placement", "startedMonotonic"}
        self._query = threading.local()

    # ------------------------------------------------------------- notes
    # tpulint: never-raise
    def note(self, kind: str, **info) -> None:
        """Append one breadcrumb to the always-on ring (never dumps)."""
        ev = {"ts": round(time.time(), 6), "kind": str(kind)}
        if info:
            ev["info"] = info
        with self._lock:
            self._ring.append(ev)

    def ring_tail(self, n: int = 256) -> List[dict]:
        with self._lock:
            buf = list(self._ring)
        return buf[-n:]

    # ----------------------------------------------------- query context
    def set_query(self, info: Optional[dict]) -> None:
        """Install (None clears) the calling thread's in-flight query
        summary so anomaly dumps fired from this thread carry the
        query's digest and placement report."""
        self._query.info = info

    def query_context(self) -> Optional[dict]:
        return getattr(self._query, "info", None)

    def query_started(self, o) -> None:
        """Anomaly dumps fired from THIS thread (semaphore wedge, OOM
        ladder) carry the in-flight query's digest and coded report."""
        from ..plan import exec_cache
        self.set_query({"queryId": o.query_id, "planDigest": o.digest,
                        "placement": o.placement})
        o.was_warm = (o.digest is not None
                      and exec_cache.plan_digest_cached(o.digest))
        o.bundles_before = len(self.stats()["bundles"])

    def query_ended(self, o) -> None:
        if o.was_warm and o.compile_s > 0:
            # the compiled-plan set vouched for this digest, yet the run
            # paid real XLA compile: a retrace cliff or an evicted tier
            self.trigger(
                "warm_recompile",
                detail=f"digest {o.digest} is in the compiled-"
                       f"plan set but paid {o.compile_s}s "
                       "of backend compile")
        self.set_query(None)

    def bundle_since(self, count: Optional[int]) -> Optional[str]:
        """The newest bundle, if one was written after the census stood
        at ``count`` (a query's ``bundles_before``)."""
        bundles = self.stats()["bundles"]
        newer = count is not None and len(bundles) > count
        return bundles[-1] if newer else None

    # ------------------------------------------------------------ stats
    def stats(self) -> dict:
        with self._lock:
            return {"dumps": dict(self.dumps),
                    "suppressed": dict(self.suppressed),
                    "bundles": list(self.bundles)}

    # ----------------------------------------------------------- trigger
    # tpulint: never-raise
    def trigger(self, kind: str, detail: str = "",
                query: Optional[dict] = None) -> Optional[str]:
        """Fire one trigger: rate-limit per kind, then atomically write
        a bundle directory. Returns the bundle path, or None when
        rate-limited or the write failed (never raises)."""
        if kind not in TRIGGERS:
            # tpulint: disable=never-raise — an unregistered kind is a
            # PROGRAMMING error caught by the taxonomy tests, not a
            # runtime failure of a failing call site; it must be loud
            raise ValueError(
                f"unknown flight trigger {kind!r}; registered kinds: "
                f"{TRIGGERS} (ops/flight.py — add it to the taxonomy "
                "and docs/ops.md first)")
        now = time.monotonic()
        with self._lock:
            last = self._last.get(kind)
            if (self.rate_limit_ms > 0 and last is not None
                    and (now - last) * 1000.0 < self.rate_limit_ms):
                self.suppressed[kind] = self.suppressed.get(kind, 0) + 1
                return None
            self._last[kind] = now
            self._seq += 1
            seq = self._seq
        self.note("flight.trigger", trigger=kind, detail=detail[:200])
        if query is None:
            query = self.query_context()
        try:
            path = self._write_bundle(kind, detail, seq, query)
        except Exception as e:  # noqa: BLE001 - never fail the caller
            log.warning("flight recorder could not write a %s bundle "
                        "under %s: %s", kind, self.dir, e)
            with self._lock:
                # a FAILED write must not consume the rate-limit
                # window: the next real anomaly of this kind (possibly
                # after the disk recovers) still deserves its bundle
                if self._last.get(kind) == now:
                    if last is not None:
                        self._last[kind] = last
                    else:
                        self._last.pop(kind, None)
            return None
        with self._lock:
            self.dumps[kind] = self.dumps.get(kind, 0) + 1
            self.bundles.append(path)
        from ..metrics import registry as metrics_registry
        mr = metrics_registry.REGISTRY
        if mr is not None:
            mr.counter("srtpu_flight_dumps_total", trigger=kind).inc()
        log.warning("flight recorder: %s bundle written to %s (%s)",
                    kind, path, detail[:200])
        return path

    # ----------------------------------------------------- bundle writer
    def _write_bundle(self, kind: str, detail: str, seq: int,
                      query: Optional[dict]) -> str:
        stamp = time.strftime("%Y%m%dT%H%M%S", time.gmtime())
        name = f"flight-{stamp}-{kind}-{seq:04d}"
        final = os.path.join(self.dir, name)
        tmp = os.path.join(self.dir, f".tmp-{name}-{os.getpid()}")
        os.makedirs(tmp)
        try:
            for fname, payload in (
                    ("trace.json", self._trace_section()),
                    ("metrics.json", self._metrics_section()),
                    ("state.json", self._state_section()),
                    ("placement.json", self._placement_section(
                        kind, detail, query)),
                    ("config.json", self._config_section())):
                with open(os.path.join(tmp, fname), "w",
                          encoding="utf-8") as f:
                    json.dump(payload, f, indent=2, sort_keys=True,
                              default=str)
            # the rename is the commit point: a reader listing self.dir
            # either sees the whole bundle or none of it
            os.rename(tmp, final)
        except BaseException:
            import shutil
            shutil.rmtree(tmp, ignore_errors=True)
            raise
        return final

    def _trace_section(self) -> dict:
        from ..trace import core as trace_core
        tr = trace_core.TRACER
        events = tr.tail(512) if tr is not None else []
        return {"traceRingTail": events,
                "breadcrumbs": self.ring_tail()}

    def _metrics_section(self) -> Optional[dict]:
        from ..metrics import registry as metrics_registry
        reg = metrics_registry.REGISTRY
        if reg is None:
            return None
        try:
            from ..metrics.export import registry_snapshot
            return registry_snapshot(reg)
        except Exception:  # noqa: BLE001 - a wedged sampler source must
            return reg.snapshot()  # not lose the bundle

    def _state_section(self) -> dict:
        out: dict = {}
        try:
            from ..mem import semaphore as sem_mod
            out["semaphores"] = [s.diagnostics()
                                 for s in list(sem_mod._SEMAPHORES)]
        except Exception as e:  # noqa: BLE001
            out["semaphores"] = f"<unavailable: {e}>"
        try:
            from ..mem.manager import MemoryManager
            out["memory"] = MemoryManager.stats_all()
        except Exception as e:  # noqa: BLE001
            out["memory"] = f"<unavailable: {e}>"
        try:
            from ..plan import exec_cache
            out["execCache"] = exec_cache.stats()
        except Exception as e:  # noqa: BLE001
            out["execCache"] = f"<unavailable: {e}>"
        return out

    def _placement_section(self, kind: str, detail: str,
                           query: Optional[dict]) -> dict:
        return {"trigger": kind, "detail": detail,
                "tsMs": round(time.time() * 1000.0, 1),
                "query": query}

    def _config_section(self) -> dict:
        raw = dict(getattr(self._conf, "raw", None) or {})
        return {"overridesFromDefaults": redact_conf(raw)}


# ---------------------------------------------------------------------------
# installation (the trace/metrics pattern)
# ---------------------------------------------------------------------------

_INSTALL_LOCK = threading.Lock()


def active_flight() -> Optional[FlightRecorder]:
    # tpulint: disable=lock-discipline — lock-free by design: the
    # disabled-path contract is one unlocked reference read per site
    return RECORDER


def install_flight(rec: Optional[FlightRecorder]) -> \
        Optional[FlightRecorder]:
    """Install (or with ``None`` remove) the process-global recorder."""
    global RECORDER
    with _INSTALL_LOCK:
        RECORDER = rec
    return rec


def ensure_flight_from_conf(conf) -> Optional[FlightRecorder]:
    """Install a recorder iff ``spark.rapids.tpu.flight.enabled`` — one
    conf lookup per ExecContext construction, never per trigger."""
    global RECORDER
    if not conf.get(FLIGHT_ENABLED):
        # tpulint: disable=lock-discipline — lock-free by design:
        # flight-off fast path; installation itself locks below
        return RECORDER
    with _INSTALL_LOCK:
        if RECORDER is None:
            RECORDER = FlightRecorder(
                str(conf.get(FLIGHT_DIR)),
                rate_limit_ms=int(conf.get(FLIGHT_RATE_LIMIT_MS)),
                ring_events=int(conf.get(FLIGHT_RING_EVENTS)),
                conf=conf)
        return RECORDER
