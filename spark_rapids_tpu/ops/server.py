"""Live ops HTTP endpoint (ISSUE 15).

Reference analog: the Spark live UI + Prometheus servlet sink the
reference stack is operated through. One stdlib ``http.server`` daemon
thread, bound to ``127.0.0.1`` only, gated by
``spark.rapids.tpu.ops.port`` (0 = disabled: no thread, no socket):

* ``GET /metrics``  — Prometheus text exposition of the process metric
  registry (after one synchronous sample pass); when a LocalCluster has
  registered itself the merged cluster view is served instead, every
  series carrying a ``worker`` label;
* ``GET /healthz``  — JSON health sections, each with an
  ``ok``/``degraded`` verdict: semaphore holders/waiters (a dead or
  overdue holder degrades), memory tiers + the rung-4 pressure-grant
  pool, executable-cache hit rate, worker heartbeat ages, event-log
  write lag, flight-recorder dumps and sentinel flags. HTTP 200 when
  every section is ok, 503 otherwise (load-balancer-pluggable);
* ``GET /queries``  — in-flight and recent queries: id, plan digest,
  placement verdict, elapsed/wall ms, max OOM-ladder rung, status and
  failure reason (the live analog of ``tools/history``).

The server holds NO references that keep a query alive: clusters
register via weakref, runtime singletons are observed through the same
weak registries the metrics sampler uses.
"""
from __future__ import annotations

import json
import logging
import threading
import time
import weakref
from collections import deque
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Dict, List, Optional

from ..config import register

__all__ = ["OpsServer", "QueryTracker", "install_ops",
           "ensure_ops_from_conf", "shutdown_ops", "active_ops",
           "OPS_PORT", "OPS_RECENT_QUERIES"]

log = logging.getLogger(__name__)

OPS_PORT = register(
    "spark.rapids.tpu.ops.port", 0,
    "Serve the live ops endpoint on 127.0.0.1:<port> — GET /metrics "
    "(Prometheus exposition, cluster-merged when a LocalCluster is "
    "live), /healthz (JSON ok/degraded verdicts over semaphore, "
    "memory, exec cache, worker heartbeats, event-log lag) and "
    "/queries (in-flight + recent queries with digest, placement, "
    "elapsed, OOM-ladder rung). 0 disables: no thread, no socket "
    "(docs/ops.md).", commonly_used=True)

OPS_RECENT_QUERIES = register(
    "spark.rapids.tpu.ops.queries.recent", 64,
    "Finished queries the /queries endpoint keeps in its recency ring.")

#: the process-global server; ``None`` means the ops plane is OFF and
#: every instrumented site costs exactly one attribute load + branch
SERVER: Optional["OpsServer"] = None

#: /healthz exec-cache verdict: below this hit rate (with enough
#: lookups to mean something) the section reads degraded
_CACHE_HIT_RATE_FLOOR = 0.5
_CACHE_MIN_LOOKUPS = 64
#: /healthz memory verdict: device tier fuller than this is degraded
_HBM_DEGRADED_FRACTION = 0.95
#: /healthz memory verdict: seconds the pressure-grant pool must stay
#: EMPTY before the degraded verdict clears (hysteresis keyed off the
#: pool's last-nonzero instant, mem/manager.py): a pool that flickers
#: empty between rung-4 grants must not flap the verdict, and a drained
#: one must clear instead of degrading forever (ISSUE 18 satellite).
#: The admission shed check (sched/admission.py) reads the same horizon.
_GRANT_CLEAR_HORIZON_S = 2.0
#: /healthz worker verdict: a peer older than this fraction of the
#: eviction horizon reads degraded — strictly BELOW 1.0, because
#: _evict (run by every heartbeat/live_peers call) removes the peer at
#: the full horizon: an equal threshold would let a silent worker
#: vanish from the census at the same instant it first read degraded
_WORKER_DEGRADED_FRACTION = 0.5


class QueryTracker:
    """In-flight + recent query table behind /queries. Thread-safe;
    bounded (the recency ring drops oldest)."""

    def __init__(self, recent: int = 64):
        self._lock = threading.Lock()
        self._seq = 0                     # tpulint: guarded-by _lock
        self._inflight: Dict[int, dict] = {}  # tpulint: guarded-by _lock
        self._recent: deque = deque(
            maxlen=max(1, int(recent)))   # tpulint: guarded-by _lock

    def begin(self, query_id, digest: Optional[str],
              verdict: Optional[str], root: Optional[str] = None,
              tenant: Optional[str] = None) -> int:
        rec = {"queryId": query_id, "planDigest": digest,
               "placement": verdict, "root": root,
               "tenant": tenant,
               "startedMs": round(time.time() * 1000.0, 1),
               "_t0": time.monotonic()}
        with self._lock:
            self._seq += 1
            tok = self._seq
            self._inflight[tok] = rec
        return tok

    def admission(self, token: int, status: str,
                  queued_ms: Optional[float] = None) -> None:
        """Record the query's admission-controller outcome (ISSUE 18):
        ``queued`` while it waits at the front door, then ``admitted``
        (with the wait it paid) or ``shed``. /queries renders it live,
        and end() carries it into the recency ring."""
        with self._lock:
            rec = self._inflight.get(token)
            if rec is None:
                return
            rec["admission"] = status
            if queued_ms is not None:
                rec["queuedMs"] = round(float(queued_ms), 3)

    def end(self, token: int, ok: bool, wall_ms: Optional[float] = None,
            rung: int = 0, reason: Optional[str] = None,
            degraded: bool = False,
            aqe: Optional[dict] = None) -> None:
        with self._lock:
            rec = self._inflight.pop(token, None)
            if rec is None:
                return
            rec = dict(rec)
            rec.pop("_t0", None)
            rec["status"] = "ok" if ok else "failed"
            rec["degraded"] = bool(degraded)
            rec["wallMs"] = (round(float(wall_ms), 3)
                             if wall_ms is not None else None)
            rec["ladderRung"] = int(rung or 0)
            if reason:
                rec["reason"] = str(reason)
            if aqe:
                # AQE decision summary (ISSUE 19): kind -> count, the
                # same compact map the queryEnd record carries
                rec["aqe"] = dict(aqe)
            self._recent.append(rec)

    def query_started(self, o) -> None:
        o.tracker_token = self.begin(o.query_id, o.digest, o.verdict,
                                     root=o.root, tenant=o.tenant)

    def query_ended(self, o) -> None:
        self.end(o.tracker_token, ok=o.ok, wall_ms=o.wall_ms,
                 rung=o.ladder_rung, reason=o.reason,
                 degraded=bool(o.degradations), aqe=o.aqe)

    def snapshot(self) -> dict:
        now = time.monotonic()
        with self._lock:
            inflight = []
            for rec in self._inflight.values():
                r = dict(rec)
                r["elapsedMs"] = round((now - r.pop("_t0")) * 1000.0, 1)
                r["status"] = "running"
                inflight.append(r)
            recent = [dict(r) for r in self._recent]
        inflight.sort(key=lambda r: r["startedMs"])
        return {"inflight": inflight, "recent": recent}


class _Handler(BaseHTTPRequestHandler):
    # the ops endpoint must never spam the serving process's stderr
    def log_message(self, fmt, *args):  # noqa: A003 - stdlib signature
        log.debug("ops: " + fmt, *args)

    # tpulint: never-raise
    def do_GET(self):  # noqa: N802 - stdlib naming
        ops: "OpsServer" = self.server.ops  # type: ignore[attr-defined]
        path = self.path.split("?", 1)[0].rstrip("/") or "/"
        try:
            # the request counter is part of the guarded body: a registry
            # error in the fan-out must degrade to a 500, not escape into
            # socketserver's handle_error (stderr traceback + a dropped
            # connection — exactly what this handler promises never to do)
            if path in ("/metrics", "/healthz", "/queries", "/slo"):
                from ..metrics import registry as metrics_registry
                mr = metrics_registry.REGISTRY
                if mr is not None:
                    mr.counter("srtpu_ops_requests_total",
                               endpoint=path).inc()
            if path == "/metrics":
                body = ops.metrics_text().encode("utf-8")
                self._reply(200, body,
                            "text/plain; version=0.0.4; charset=utf-8")
            elif path == "/healthz":
                doc = ops.healthz()
                code = 200 if doc.get("status") == "ok" else 503
                self._reply(code, json.dumps(
                    doc, indent=2, sort_keys=True,
                    default=str).encode("utf-8"), "application/json")
            elif path == "/queries":
                self._reply(200, json.dumps(
                    ops.queries(), indent=2, sort_keys=True,
                    default=str).encode("utf-8"), "application/json")
            elif path == "/slo":
                self._reply(200, json.dumps(
                    ops.slo(), indent=2, sort_keys=True,
                    default=str).encode("utf-8"), "application/json")
            elif path == "/":
                self._reply(200, json.dumps(
                    {"endpoints": ["/metrics", "/healthz", "/queries",
                                   "/slo"]}
                ).encode("utf-8"), "application/json")
            else:
                self._reply(404, b'{"error": "not found"}',
                            "application/json")
        except Exception as e:  # noqa: BLE001 - a probe must never kill
            log.warning("ops endpoint %s failed: %s", path, e)
            try:
                self._reply(500, json.dumps(
                    {"error": str(e)}).encode("utf-8"),
                    "application/json")
            except Exception:  # noqa: BLE001 - client went away
                pass           # mid-reply (or the error body itself
                #                failed to build): nothing left to do

    def _reply(self, code: int, body: bytes, ctype: str) -> None:
        self.send_response(code)
        self.send_header("Content-Type", ctype)
        self.send_header("Content-Length", str(len(body)))
        self.end_headers()
        self.wfile.write(body)


class OpsServer:
    """The live ops plane: one daemon HTTP thread + the query tracker.

    ``port=0`` binds an OS-assigned ephemeral port (tests); the conf
    gate in :func:`ensure_ops_from_conf` only starts a server for
    explicit ports > 0."""

    def __init__(self, port: int = 0, recent_queries: int = 64):
        self.tracker = QueryTracker(recent_queries)
        self._cluster: Optional[weakref.ref] = None
        self._httpd = ThreadingHTTPServer(("127.0.0.1", int(port)),
                                          _Handler)
        self._httpd.daemon_threads = True
        self._httpd.ops = self  # type: ignore[attr-defined]
        self.port = int(self._httpd.server_address[1])
        self._thread = threading.Thread(
            target=self._httpd.serve_forever, kwargs={"poll_interval": 0.2},
            name="srtpu-ops-server", daemon=True)

    def start(self) -> "OpsServer":
        self._thread.start()
        log.info("ops server listening on 127.0.0.1:%d "
                 "(/metrics /healthz /queries)", self.port)
        return self

    def stop(self) -> None:
        self._httpd.shutdown()
        self._httpd.server_close()
        if self._thread.is_alive():
            self._thread.join(timeout=5.0)

    # ------------------------------------------------------------ wiring
    def register_cluster(self, cluster) -> None:
        """Weakly remember a LocalCluster so /metrics serves the merged
        cluster view and /healthz sees worker heartbeat ages. The last
        registered live cluster wins; a GC'd one silently drops."""
        self._cluster = weakref.ref(cluster)

    def _live_cluster(self):
        ref = self._cluster
        return ref() if ref is not None else None

    # --------------------------------------------------------- /metrics
    def metrics_text(self) -> str:
        cl = self._live_cluster()
        if cl is not None:
            try:
                txt = cl.prometheus_snapshot()
                if txt:
                    return txt
            except Exception as e:  # noqa: BLE001 - fall back to local
                log.warning("ops: cluster metrics merge failed: %s", e)
        from ..metrics import registry as metrics_registry
        reg = metrics_registry.REGISTRY
        if reg is None:
            return ("# spark.rapids.tpu.metrics.enabled is off: "
                    "no metric registry installed\n")
        from ..metrics.export import prometheus_text, registry_snapshot
        snap = registry_snapshot(reg)
        from .slo import TRACKER as _slo
        if _slo is not None:
            # OpenMetrics exemplars: each tenant's newest over-target
            # query rides its summary series, linking the quantile line
            # to the on-disk trace/flight artifact (ops/slo.py)
            snap = _slo.decorate_snapshot(snap)
        return prometheus_text(snap)

    # --------------------------------------------------------- /healthz
    def healthz(self) -> dict:
        sections = {"semaphore": self._health_semaphore(),
                    "memory": self._health_memory(),
                    "admission": self._health_admission(),
                    "execCache": self._health_exec_cache(),
                    "workers": self._health_workers(),
                    "eventLog": self._health_event_log(),
                    "flight": self._health_flight(),
                    "sentinel": self._health_sentinel(),
                    "slo": self._health_slo()}
        status = ("ok" if all(s.get("verdict") == "ok"
                              for s in sections.values())
                  else "degraded")
        return {"status": status, "tsMs": round(time.time() * 1000.0, 1),
                **sections}

    def _health_semaphore(self) -> dict:
        from ..mem import semaphore as sem_mod
        sems = list(sem_mod._SEMAPHORES)
        holders: List[dict] = []
        dead = overdue = 0
        permits = waiting = wedges = 0
        for s in sems:
            d = s.diagnostics()
            permits += d["permits"]
            waiting += d["waiting"]
            wedges += d["wedges"]
            horizon_s = (s.wedge_timeout_ms / 1000.0
                         if s.wedge_timeout_ms > 0 else None)
            for h in d["holders"]:
                holders.append(h)
                if h.get("alive") is False:
                    dead += 1
                elif horizon_s is not None and h["held_s"] >= horizon_s:
                    overdue += 1
        verdict = "degraded" if (dead or overdue) else "ok"
        return {"semaphores": len(sems), "permits": permits,
                "waiting": waiting, "holders": holders,
                "deadHolders": dead, "overdueHolders": overdue,
                "wedges": wedges, "verdict": verdict}

    def _health_memory(self) -> dict:
        from ..mem.manager import MemoryManager
        st = MemoryManager.stats_all()
        budget = st.get("budget") or 0
        used = st.get("device_used") or 0
        grant = st.get("pressure_granted") or 0
        # the grant pool degrades while nonzero AND for a short horizon
        # after it drains (last-nonzero hysteresis) — then CLEARS: a
        # pool back to zero live bytes must not read degraded forever
        # (ISSUE 18 satellite; mem/manager.py pressure_grant_idle_s)
        idle = st.get("pressure_grant_idle_s")
        grant_hot = bool(grant) or (
            idle is not None and idle < _GRANT_CLEAR_HORIZON_S)
        degraded = grant_hot or (
            budget > 0 and used > _HBM_DEGRADED_FRACTION * budget)
        out = dict(st)
        out["verdict"] = "degraded" if degraded else "ok"
        return out

    def _health_admission(self) -> dict:
        from ..sched import admission as adm_mod
        ctl = adm_mod.CONTROLLER
        if ctl is None:
            return {"enabled": False, "verdict": "ok"}
        st = ctl.stats()
        shed = adm_mod.shed_reason()
        out = {"enabled": True, "shedActive": shed is not None,
               **st}
        if shed is not None:
            out["shedReason"] = shed
        # shedding mirrors the memory/semaphore pressure verdicts —
        # report it here too so a load balancer reading only this
        # section still sees the front door is refusing work
        out["verdict"] = "degraded" if shed is not None else "ok"
        return out

    def _health_exec_cache(self) -> dict:
        from ..plan import exec_cache
        st = exec_cache.stats()
        lookups = st["hits"] + st["misses"]
        rate = exec_cache.hit_rate()
        degraded = (lookups >= _CACHE_MIN_LOOKUPS and rate is not None
                    and rate < _CACHE_HIT_RATE_FLOOR)
        out = dict(st)
        out["hitRate"] = round(rate, 4) if rate is not None else None
        out["verdict"] = "degraded" if degraded else "ok"
        return out

    def _health_workers(self) -> dict:
        cl = self._live_cluster()
        if cl is None:
            return {"workers": {}, "verdict": "ok",
                    "note": "no LocalCluster registered"}
        try:
            ages = cl.manager.peer_ages()
            stale_after = float(cl.manager.stale_after_s)
        except Exception as e:  # noqa: BLE001 - a mid-shutdown cluster
            return {"workers": {}, "verdict": "ok",
                    "note": f"cluster unreadable: {e}"}
        degraded_at = stale_after * _WORKER_DEGRADED_FRACTION
        workers = {wid: {"heartbeatAgeS": age,
                         "verdict": ("degraded" if age > degraded_at
                                     else "ok")}
                   for wid, age in sorted(ages.items())}
        verdict = ("degraded" if any(w["verdict"] == "degraded"
                                     for w in workers.values())
                   else "ok")
        return {"workers": workers, "staleAfterS": stale_after,
                "verdict": verdict}

    def _health_event_log(self) -> dict:
        from ..metrics.events import writer_health
        writers = writer_health()
        if not writers:
            return {"writers": [], "verdict": "ok",
                    "note": "no event-log writer active"}
        now = time.time()
        degraded = False
        for w in writers:
            wts, ets = w.get("lastWriteTs"), w.get("lastErrorTs")
            if ets is not None and (wts is None or ets >= wts):
                degraded = True      # the newest attempt failed
            if wts is not None:
                # informational only: a long lag just means no queries
                # ran — an idle process is healthy, not degraded
                w["lagS"] = round(now - wts, 3)
        return {"writers": writers,
                "verdict": "degraded" if degraded else "ok"}

    def _health_flight(self) -> dict:
        from .flight import RECORDER
        if RECORDER is None:
            return {"enabled": False, "verdict": "ok"}
        st = RECORDER.stats()
        return {"enabled": True, "dumps": st["dumps"],
                "suppressed": st["suppressed"],
                "lastBundle": (st["bundles"][-1] if st["bundles"]
                               else None), "verdict": "ok"}

    def _health_sentinel(self) -> dict:
        from .sentinel import SENTINEL
        if SENTINEL is None:
            return {"enabled": False, "verdict": "ok"}
        flags = SENTINEL.recent_flags()
        return {"enabled": True, "recentFlags": flags[-8:],
                "flaggedTotal": len(flags), "verdict": "ok"}

    def _health_slo(self) -> dict:
        from .slo import TRACKER
        if TRACKER is None:
            return {"enabled": False, "verdict": "ok"}
        h = TRACKER.healthz()
        return {"enabled": True,
                "burningTenants": h["burningTenants"],
                "alertsFired": h["alertsFired"],
                "shedActive": h["shedActive"],
                "exemplars": h["exemplars"],
                "verdict": ("degraded" if h["status"] == "degraded"
                            else "ok")}

    # --------------------------------------------------------- /queries
    def queries(self) -> dict:
        return self.tracker.snapshot()

    # ------------------------------------------------------------- /slo
    def slo(self) -> dict:
        """The GET /slo report: burn rates, error-budget remaining,
        worst digests by tail contribution, exemplars — or an
        ``enabled: false`` stub when the tracker is off."""
        from .slo import TRACKER
        if TRACKER is None:
            return {"enabled": False}
        return {"enabled": True, **TRACKER.report()}


# ---------------------------------------------------------------------------
# installation (the trace/metrics pattern)
# ---------------------------------------------------------------------------

_INSTALL_LOCK = threading.Lock()


def active_ops() -> Optional[OpsServer]:
    # tpulint: disable=lock-discipline — lock-free by design: the
    # disabled-path contract is one unlocked reference read per site
    return SERVER


def install_ops(srv: Optional[OpsServer]) -> Optional[OpsServer]:
    """Install (or with ``None`` remove) the process-global server; the
    caller owns start/stop."""
    global SERVER
    with _INSTALL_LOCK:
        SERVER = srv
    return srv


def shutdown_ops() -> None:
    """Stop and uninstall the server (per-test reset)."""
    global SERVER
    with _INSTALL_LOCK:
        srv, SERVER = SERVER, None
    if srv is not None:
        try:
            srv.stop()
        except Exception:  # pragma: no cover - teardown best effort
            pass


def ensure_ops_from_conf(conf) -> Optional[OpsServer]:
    """Start the ops server iff ``spark.rapids.tpu.ops.port`` > 0 — one
    conf lookup per ExecContext construction. The first port wins for
    the process lifetime (the install-once registry pattern); a bind
    failure logs and leaves the plane off rather than failing a query."""
    global SERVER
    port = int(conf.get(OPS_PORT))
    if port <= 0:
        # tpulint: disable=lock-discipline — lock-free by design:
        # ops-off fast path; installation itself locks below
        return SERVER
    with _INSTALL_LOCK:
        if SERVER is None:
            try:
                SERVER = OpsServer(
                    port,
                    recent_queries=int(conf.get(OPS_RECENT_QUERIES))
                ).start()
            except OSError as e:
                log.error("ops server could not bind 127.0.0.1:%d: %s "
                          "— ops plane disabled for this process",
                          port, e)
                return None
        return SERVER
