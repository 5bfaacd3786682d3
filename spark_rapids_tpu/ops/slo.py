"""Per-tenant tail-latency SLO tracker (ISSUE 20).

The closed-loop half of the tail-latency layer: every finished query is
folded as a *good* or *bad* event against its tenant's latency target
(bad = over ``spark.rapids.tpu.slo.targetMs`` or failed), and the
good/bad stream drives multi-window **burn rates** — the standard SRE
alerting shape. A burn rate of 1.0 spends the error budget exactly at
the objective's allowance; ``slo.burn.threshold`` x that over BOTH the
short and the long window means the budget is burning fast enough,
persistently enough, to act on:

* the flight recorder's ``slo_burn`` trigger fires (one diagnostic
  bundle, rate-limited),
* the admission controller starts shedding below its priority floor
  (``shed_reason`` consults :meth:`SloTracker.shed_hint`) while the
  alert is live — the same graceful-degradation path memory pressure
  uses (docs/serving.md),
* AQE feedback sees per-digest breach counts and re-plans repeat
  offenders to smaller batches (aqe/feedback.py).

Every over-target observation also records an **exemplar** — a bounded
ring entry linking the outlier to its on-disk evidence (trace path,
flight bundle, queryId, plan digest) — surfaced through OpenMetrics
exemplar syntax on ``/metrics`` and the ``GET /slo`` report, so a p99
spike on a dashboard is one hop from the artifact that explains it.

The fold is **pure** (:func:`fold_slo_event` / :func:`burn_rate` /
:func:`budget_remaining` operate on plain dicts) and shared verbatim
with the offline replay (``tools/history --slo``), the sentinel's
``fold_record`` idiom. Install follows the tracer/flight pattern:
``TRACKER`` is ``None`` when off and every instrumented site costs one
module-global load + branch.
"""
from __future__ import annotations

import json
import logging
import threading
import time
from typing import Dict, List, Optional, Tuple

from ..config import register

__all__ = ["SloTracker", "TRACKER", "install_slo", "active_slo",
           "ensure_slo_from_conf", "fold_slo_event", "burn_rate",
           "budget_remaining", "parse_tenant_overrides", "new_slo_state",
           "SLO_ENABLED", "SLO_TARGET_MS", "SLO_OBJECTIVE",
           "SLO_TENANT_OVERRIDES", "SLO_SHORT_WINDOW_S",
           "SLO_LONG_WINDOW_S", "SLO_BURN_THRESHOLD", "SLO_EXEMPLARS",
           "SLO_SHED_ENABLED", "SLO_DIGESTS"]

log = logging.getLogger(__name__)

SLO_ENABLED = register(
    "spark.rapids.tpu.slo.enabled", False,
    "Fold every finished query into the per-tenant tail-latency SLO "
    "tracker (ops/slo.py): good/bad events against slo.targetMs drive "
    "multi-window error-budget burn rates, exemplars linking p99 "
    "outliers to trace/flight artifacts, the GET /slo report, the "
    "flight recorder's slo_burn trigger and (with slo.shed.enabled) "
    "admission shedding while the budget burns (docs/serving.md).",
    commonly_used=True)

SLO_TARGET_MS = register(
    "spark.rapids.tpu.slo.targetMs", 1000.0,
    "Default per-query latency target in milliseconds: a query slower "
    "than this (or failed) is a bad SLO event for its tenant. "
    "Per-tenant overrides via slo.tenant.overrides.")

SLO_OBJECTIVE = register(
    "spark.rapids.tpu.slo.objective", 0.99,
    "Default SLO objective — the fraction of queries that must meet "
    "the latency target; 1 - objective is the error budget the burn "
    "rates are measured against.")

SLO_TENANT_OVERRIDES = register(
    "spark.rapids.tpu.slo.tenant.overrides", "",
    "Per-tenant target/objective overrides, "
    "'tenant=targetMs[:objective]' comma-separated — e.g. "
    "'alpha=500:0.999,batch=30000:0.9'. Tenants not listed use "
    "slo.targetMs / slo.objective.")

SLO_SHORT_WINDOW_S = register(
    "spark.rapids.tpu.slo.burn.shortWindowS", 60.0,
    "Short burn-rate window in seconds (the fast signal of the "
    "multi-window alert; both windows must exceed slo.burn.threshold "
    "to fire).")

SLO_LONG_WINDOW_S = register(
    "spark.rapids.tpu.slo.burn.longWindowS", 600.0,
    "Long burn-rate window in seconds (the sustained signal; also the "
    "horizon events are retained for and the error-budget-remaining "
    "denominator).")

SLO_BURN_THRESHOLD = register(
    "spark.rapids.tpu.slo.burn.threshold", 2.0,
    "Burn-rate multiple that fires the slo_burn alert when BOTH "
    "windows exceed it: 1.0 spends the budget exactly at the "
    "objective's allowance, 2.0 twice as fast.")

SLO_EXEMPLARS = register(
    "spark.rapids.tpu.slo.exemplars", 64,
    "Bounded ring of over-target exemplars retained (queryId, plan "
    "digest, tenant, trace path, flight-bundle path) — served by "
    "GET /slo and attached to /metrics in OpenMetrics exemplar "
    "syntax.")

SLO_SHED_ENABLED = register(
    "spark.rapids.tpu.slo.shed.enabled", True,
    "Let a live slo_burn alert drive admission shedding (below the "
    "admission priority floor) for the duration of the short window — "
    "the burn->shed half of the closed loop (docs/serving.md).")

SLO_DIGESTS = register(
    "spark.rapids.tpu.slo.digests", 128,
    "Distinct plan digests tracked for tail contribution (worst-digest "
    "ranking, AQE feedback); past the cap new digests collapse into "
    "'other'.")

#: the process-global tracker; ``None`` means SLO tracking is OFF and
#: the query-completion site costs exactly one attribute load + branch
TRACKER: Optional["SloTracker"] = None


# ---------------------------------------------------------------------------
# the pure fold (shared with tools/history --slo replay)
# ---------------------------------------------------------------------------

def parse_tenant_overrides(spec: str) -> Dict[str, Tuple[float, float]]:
    """``'alpha=500:0.999,beta=2000'`` -> {tenant: (target_ms,
    objective-or-None)}. Malformed entries are skipped (a bad conf
    string must not take down the tracker install)."""
    out: Dict[str, Tuple[float, float]] = {}
    for part in (spec or "").split(","):
        part = part.strip()
        if not part or "=" not in part:
            continue
        tenant, _, val = part.partition("=")
        target, _, obj = val.partition(":")
        try:
            out[tenant.strip()] = (float(target),
                                   float(obj) if obj else None)
        except ValueError:
            log.warning("slo: ignoring malformed tenant override %r",
                        part)
    return out


def new_slo_state() -> dict:
    """Empty fold state: {tenant: {"events": [(ts, bad)], "good": n,
    "bad": n}} — events pruned to the long window, good/bad cumulative
    over the process lifetime."""
    return {}


def fold_slo_event(state: dict, *, tenant: str, ts: float, bad: bool,
                   long_window_s: float) -> dict:
    """Fold one good/bad event into ``state`` (mutated in place) and
    return the tenant's sub-state. Pure and deterministic — the single
    code path shared by the live tracker and the ``tools/history
    --slo`` replay."""
    t = state.setdefault(tenant, {"events": [], "good": 0, "bad": 0})
    t["events"].append((round(float(ts), 3), 1 if bad else 0))
    cutoff = float(ts) - float(long_window_s)
    ev = t["events"]
    i = 0
    while i < len(ev) and ev[i][0] < cutoff:
        i += 1
    if i:
        del ev[:i]
    t["bad" if bad else "good"] += 1
    return t


def burn_rate(tenant_state: dict, *, now: float, window_s: float,
              objective: float) -> float:
    """Error-budget burn rate over the trailing window: the observed
    bad fraction divided by the budget fraction (1 - objective). 0.0
    with no events; an objective of 1.0 makes any bad event an
    infinite burn, clamped to a large finite value (JSON-safe)."""
    cutoff = float(now) - float(window_s)
    n = bad = 0
    for ts, isbad in tenant_state.get("events") or []:
        if ts >= cutoff:
            n += 1
            bad += isbad
    if n == 0 or bad == 0:
        return 0.0
    budget = 1.0 - float(objective)
    if budget <= 0.0:
        return 1e9
    return min(1e9, (bad / n) / budget)


def budget_remaining(tenant_state: dict, *, objective: float) -> float:
    """Fraction of the error budget left over the retained horizon:
    1.0 untouched, 0.0 exhausted (clamped)."""
    ev = tenant_state.get("events") or []
    n = len(ev)
    if n == 0:
        return 1.0
    bad = sum(isbad for _, isbad in ev)
    budget = n * (1.0 - float(objective))
    if budget <= 0.0:
        return 0.0 if bad else 1.0
    return min(1.0, max(0.0, 1.0 - bad / budget))


# ---------------------------------------------------------------------------
# the live tracker
# ---------------------------------------------------------------------------

class SloTracker:
    """Thread-safe live fold over the pure SLO state, with exemplar
    ring, per-digest tail attribution, burn alerting and the shed
    hint the admission controller consults."""

    def __init__(self, *, target_ms: float = 1000.0,
                 objective: float = 0.99,
                 tenant_overrides: Optional[
                     Dict[str, Tuple[float, float]]] = None,
                 short_window_s: float = 60.0,
                 long_window_s: float = 600.0,
                 burn_threshold: float = 2.0,
                 exemplar_cap: int = 64,
                 shed_enabled: bool = True,
                 digest_cap: int = 128):
        self.target_ms = float(target_ms)
        self.objective = float(objective)
        self.overrides = dict(tenant_overrides or {})
        self.short_window_s = max(1.0, float(short_window_s))
        self.long_window_s = max(self.short_window_s,
                                 float(long_window_s))
        self.burn_threshold = float(burn_threshold)
        self.exemplar_cap = max(1, int(exemplar_cap))
        self.shed_enabled = bool(shed_enabled)
        self.digest_cap = max(1, int(digest_cap))
        self._lock = threading.Lock()
        self._state = new_slo_state()       # tpulint: guarded-by _lock
        #: newest-last over-target exemplar ring
        self._exemplars: List[dict] = []    # tpulint: guarded-by _lock
        #: digest -> {"n", "over", "excessMs"} tail attribution
        self._digests: Dict[str, dict] = {}  # tpulint: guarded-by _lock
        #: tenant -> last alert wall-clock (alert cooldown = short win)
        self._alerted_at: Dict[str, float] = {}  # tpulint: guarded-by _lock
        #: (tenant, expiry) of the live shed hint
        self._shed_until: Tuple[str, float] = ("", 0.0)  # tpulint: guarded-by _lock
        self.alerts_fired = 0               # tpulint: guarded-by _lock

    # ----------------------------------------------------------- targets
    def target_for(self, tenant: str) -> Tuple[float, float]:
        """(target_ms, objective) for a tenant, overrides applied."""
        ov = self.overrides.get(tenant)
        if ov is None:
            return self.target_ms, self.objective
        target, obj = ov
        return target, (obj if obj is not None else self.objective)

    # -------------------------------------------------------------- fold
    # tpulint: never-raise
    def observe(self, *, tenant: Optional[str], wall_ms: float,
                ok: bool, query_id=None, digest: Optional[str] = None,
                trace_path: Optional[str] = None,
                flight_path: Optional[str] = None,
                ts: Optional[float] = None) -> None:
        """Fold one finished query. Runs on the query-completion path —
        never raises, and fans out (metrics, flight trigger) only
        behind the same guards every other completion hook uses."""
        try:
            alert_tenant = self._fold(
                tenant=tenant or "default", wall_ms=float(wall_ms),
                ok=bool(ok), query_id=query_id,
                digest=str(digest) if digest else None,
                trace_path=trace_path, flight_path=flight_path,
                ts=float(ts) if ts is not None else time.time())
        except Exception as e:  # noqa: BLE001 - observability only
            log.warning("slo fold failed: %s", e)
            return
        if alert_tenant is not None:
            self._fire_alert(alert_tenant)

    def query_ended(self, o) -> None:
        """Runs AFTER the trace write and any flight dump: an over-target
        exemplar links the artifacts this very query produced (its
        trace; the newest bundle if one landed during the run)."""
        from .flight import RECORDER as frec
        self.observe(tenant=o.tenant, wall_ms=o.wall_ms, ok=o.ok,
                     query_id=o.query_id, digest=o.digest,
                     trace_path=o.trace_path,
                     flight_path=(frec.bundle_since(o.bundles_before)
                                  if frec is not None else None))

    def _fold(self, *, tenant: str, wall_ms: float, ok: bool, query_id,
              digest: Optional[str], trace_path: Optional[str],
              flight_path: Optional[str], ts: float) -> Optional[str]:
        """The locked fold; returns the tenant to alert on, if any."""
        target_ms, objective = self.target_for(tenant)
        over = wall_ms > target_ms
        bad = over or not ok
        with self._lock:
            tstate = fold_slo_event(self._state, tenant=tenant, ts=ts,
                                    bad=bad,
                                    long_window_s=self.long_window_s)
            if digest:
                if digest not in self._digests and \
                        len(self._digests) >= self.digest_cap:
                    digest = "other"
                d = self._digests.setdefault(
                    digest, {"n": 0, "over": 0, "excessMs": 0.0})
                d["n"] += 1
                if over:
                    d["over"] += 1
                    d["excessMs"] = round(
                        d["excessMs"] + (wall_ms - target_ms), 3)
            if over:
                self._exemplars.append({
                    "queryId": query_id,
                    "planDigest": digest,
                    "tenant": tenant,
                    "wallMs": round(wall_ms, 3),
                    "targetMs": target_ms,
                    "trace": trace_path,
                    "flight": flight_path,
                    "tsMs": round(ts * 1000.0, 1)})
                del self._exemplars[:-self.exemplar_cap]
            short = burn_rate(tstate, now=ts,
                              window_s=self.short_window_s,
                              objective=objective)
            long_ = burn_rate(tstate, now=ts,
                              window_s=self.long_window_s,
                              objective=objective)
            alerting = (short >= self.burn_threshold
                        and long_ >= self.burn_threshold)
            alert = None
            if alerting:
                if self.shed_enabled:
                    self._shed_until = (tenant,
                                        ts + self.short_window_s)
                last = self._alerted_at.get(tenant, 0.0)
                if ts - last >= self.short_window_s:
                    self._alerted_at[tenant] = ts
                    self.alerts_fired += 1
                    alert = tenant
        # metric fan-out outside the tracker lock (registry locks its
        # own metrics; holding ours across it invites ordering bugs)
        from ..metrics import registry as metrics_registry
        mr = metrics_registry.REGISTRY
        if mr is not None:
            mr.counter("srtpu_slo_events_total", tenant=tenant,
                       status="bad" if bad else "good").inc()
            mr.gauge("srtpu_slo_burn_rate", tenant=tenant,
                     window="short").set(round(short, 4))
            mr.gauge("srtpu_slo_burn_rate", tenant=tenant,
                     window="long").set(round(long_, 4))
            mr.gauge("srtpu_slo_error_budget_remaining",
                     tenant=tenant).set(round(
                         budget_remaining(tstate,
                                          objective=objective), 4))
        return alert

    # tpulint: never-raise
    def _fire_alert(self, tenant: str) -> None:
        """Alert fan-out: counter + flight trigger. Never raises —
        the caller is the query-completion path."""
        try:
            from ..metrics import registry as metrics_registry
            mr = metrics_registry.REGISTRY
            if mr is not None:
                mr.counter("srtpu_slo_burn_alerts_total",
                           tenant=tenant).inc()
            from .flight import RECORDER as _frec
            if _frec is not None:
                with self._lock:
                    detail = {"tenant": tenant,
                              "burnThreshold": self.burn_threshold,
                              "exemplars": list(self._exemplars[-8:])}
                _frec.trigger("slo_burn",
                              detail=json.dumps(detail, sort_keys=True,
                                                default=str))
            log.warning("slo burn alert: tenant=%s burning > %gx over "
                        "both windows", tenant, self.burn_threshold)
        except Exception as e:  # noqa: BLE001 - observability only
            log.warning("slo alert fan-out failed: %s", e)

    # ------------------------------------------------------------- reads
    def shed_hint(self, now: Optional[float] = None) -> Optional[str]:
        """The live burn-driven shed reason, or None. Consulted by
        ``sched.admission.shed_reason`` on every admission attempt —
        cheap (one lock, two compares) and self-expiring."""
        if not self.shed_enabled:
            return None
        t = time.time() if now is None else float(now)
        with self._lock:
            tenant, until = self._shed_until
        if until > t:
            return f"slo_burn:{tenant}"
        return None

    def digest_breaches(self, digest: str) -> int:
        """Over-target observation count for a digest (AQE feedback)."""
        with self._lock:
            d = self._digests.get(str(digest))
            return int(d["over"]) if d else 0

    def exemplars(self) -> List[dict]:
        """Newest-first exemplar ring copy."""
        with self._lock:
            return [dict(e) for e in reversed(self._exemplars)]

    def latest_exemplar(self, tenant: str) -> Optional[dict]:
        with self._lock:
            for e in reversed(self._exemplars):
                if e.get("tenant") == tenant:
                    return dict(e)
        return None

    def report(self, now: Optional[float] = None) -> dict:
        """The GET /slo document: per-tenant burn rates and budget,
        worst digests by tail contribution, exemplars."""
        t = time.time() if now is None else float(now)
        with self._lock:
            tenants = {}
            for tenant in sorted(self._state):
                tstate = self._state[tenant]
                target_ms, objective = self.target_for(tenant)
                tenants[tenant] = {
                    "targetMs": target_ms,
                    "objective": objective,
                    "good": tstate["good"],
                    "bad": tstate["bad"],
                    "burn": {
                        "short": round(burn_rate(
                            tstate, now=t,
                            window_s=self.short_window_s,
                            objective=objective), 4),
                        "long": round(burn_rate(
                            tstate, now=t,
                            window_s=self.long_window_s,
                            objective=objective), 4)},
                    "errorBudgetRemaining": round(budget_remaining(
                        tstate, objective=objective), 4)}
            worst = sorted(
                ((dg, dict(d)) for dg, d in self._digests.items()
                 if d["over"] > 0),
                key=lambda kv: (-kv[1]["excessMs"], kv[0]))[:8]
            shed_tenant, shed_until = self._shed_until
            return {
                "windows": {"shortS": self.short_window_s,
                            "longS": self.long_window_s},
                "burnThreshold": self.burn_threshold,
                "alertsFired": self.alerts_fired,
                "shedActive": shed_until > t,
                "shedTenant": shed_tenant if shed_until > t else None,
                "tenants": tenants,
                "worstDigests": [
                    {"digest": dg, **d} for dg, d in worst],
                "exemplars": [dict(e) for e
                              in reversed(self._exemplars)]}

    def healthz(self, now: Optional[float] = None) -> dict:
        """The /healthz slo section: degraded while a burn alert's
        shed hint is live."""
        t = time.time() if now is None else float(now)
        rep = self.report(t)
        burning = sorted(
            tenant for tenant, d in rep["tenants"].items()
            if d["burn"]["short"] >= self.burn_threshold
            and d["burn"]["long"] >= self.burn_threshold)
        return {"status": "degraded" if burning else "ok",
                "burningTenants": burning,
                "alertsFired": rep["alertsFired"],
                "shedActive": rep["shedActive"],
                "exemplars": len(rep["exemplars"])}

    def export_gauges(self, reg) -> None:
        """Refresh the per-tenant burn/budget gauges from the current
        clock (sampler pass) — burn rates DECAY as bad events age out
        of their windows, and a gauge last set at observe time would
        freeze a stale alarm on /metrics."""
        rep = self.report()
        for tenant, d in rep["tenants"].items():
            reg.gauge("srtpu_slo_burn_rate", tenant=tenant,
                      window="short").set(d["burn"]["short"])
            reg.gauge("srtpu_slo_burn_rate", tenant=tenant,
                      window="long").set(d["burn"]["long"])
            reg.gauge("srtpu_slo_error_budget_remaining",
                      tenant=tenant).set(d["errorBudgetRemaining"])

    def decorate_snapshot(self, snap: dict) -> dict:
        """Attach each tenant's newest exemplar to its
        ``srtpu_query_latency_seconds`` summary series (mutates and
        returns ``snap``) — the OpenMetrics exemplar hop from a
        /metrics quantile line to the on-disk artifact."""
        ent = snap.get("srtpu_query_latency_seconds")
        for s in (ent or {}).get("series") or []:
            tenant = (s.get("labels") or {}).get("tenant")
            ex = self.latest_exemplar(tenant) if tenant else None
            if ex is None:
                continue
            labels = {"query_id": str(ex.get("queryId")),
                      "tenant": tenant}
            if ex.get("trace"):
                labels["trace_path"] = str(ex["trace"])
            if ex.get("flight"):
                labels["flight_path"] = str(ex["flight"])
            if ex.get("planDigest"):
                labels["plan_digest"] = str(ex["planDigest"])
            s["exemplar"] = {"labels": labels,
                             "value": ex["wallMs"] / 1000.0,
                             "ts": ex["tsMs"] / 1000.0}
        return snap


# ---------------------------------------------------------------------------
# installation (the trace/metrics pattern)
# ---------------------------------------------------------------------------

_INSTALL_LOCK = threading.Lock()


def active_slo() -> Optional[SloTracker]:
    # tpulint: disable=lock-discipline — lock-free by design: the
    # disabled-path contract is one unlocked reference read per site
    return TRACKER


def install_slo(tracker: Optional[SloTracker]) -> Optional[SloTracker]:
    """Install (or with ``None`` remove) the process-global tracker."""
    global TRACKER
    with _INSTALL_LOCK:
        TRACKER = tracker
    return tracker


def ensure_slo_from_conf(conf) -> Optional[SloTracker]:
    """Install a tracker iff ``spark.rapids.tpu.slo.enabled`` — one
    conf lookup per ExecContext construction, never per query."""
    global TRACKER
    if not conf.get(SLO_ENABLED):
        # tpulint: disable=lock-discipline — lock-free by design:
        # slo-off fast path; installation itself locks below
        return TRACKER
    with _INSTALL_LOCK:
        if TRACKER is None:
            TRACKER = SloTracker(
                target_ms=float(conf.get(SLO_TARGET_MS)),
                objective=float(conf.get(SLO_OBJECTIVE)),
                tenant_overrides=parse_tenant_overrides(
                    str(conf.get(SLO_TENANT_OVERRIDES) or "")),
                short_window_s=float(conf.get(SLO_SHORT_WINDOW_S)),
                long_window_s=float(conf.get(SLO_LONG_WINDOW_S)),
                burn_threshold=float(conf.get(SLO_BURN_THRESHOLD)),
                exemplar_cap=int(conf.get(SLO_EXEMPLARS)),
                shed_enabled=bool(conf.get(SLO_SHED_ENABLED)),
                digest_cap=int(conf.get(SLO_DIGESTS)))
        return TRACKER
