"""Process-global metric registry: counters, gauges, histograms.

Reference analog: the Spark metrics system (ExecutorMetrics + the
DropwizardReporter sinks) the reference plugin feeds its GPU memory /
spill / semaphore telemetry into — the long-lived, *between*-queries
view the SQL UI and qualification tools consume. Here a single
process-global :class:`MetricRegistry` plays that role, with
Prometheus-text and JSON exporters (export.py) and a background sampler
(sampler.py) snapshotting the runtime singletons.

Design contract (ISSUE 5, same shape as trace/core.py):

* **one branch when off** — instrumentation sites read the module
  global ``REGISTRY`` and skip entirely when it is ``None``; no conf
  lookup, no allocation, no lock on the disabled path;
* **declared inventory** — every shipped metric name is declared at
  import time in ``_INVENTORY`` with its kind and help text; creating
  an undeclared metric raises, so docs/monitoring.md and the
  ``metric-name-drift`` lint rule always check against a closed,
  honest catalog (the RapidsConf-registry pattern applied to metrics);
* **cheap when unread** — counters and gauges are a slot store plus a
  lock'd add; histograms bisect a short bucket ladder. Nothing is
  formatted, aggregated, or exported until somebody asks.
"""
from __future__ import annotations

import bisect
import threading
import time
from typing import Dict, List, Optional, Tuple

from ..config import register

__all__ = ["MetricRegistry", "Counter", "Gauge", "Histogram", "Summary",
           "declare_metric", "metric_inventory", "active_registry",
           "install_metrics", "shutdown_metrics",
           "ensure_metrics_from_conf", "METRICS_ENABLED",
           "METRICS_SAMPLE_INTERVAL_MS"]

METRICS_ENABLED = register(
    "spark.rapids.tpu.metrics.enabled", False,
    "Maintain the process-global MetricRegistry (metrics/registry.py): "
    "always-on counters/gauges/histograms for HBM pressure, spill "
    "totals, semaphore contention, shuffle health and query outcomes, "
    "sampled by a background thread and exported as Prometheus text or "
    "JSON (docs/monitoring.md). Off by default: every instrumentation "
    "site is a single branch when disabled.", commonly_used=True)

METRICS_SAMPLE_INTERVAL_MS = register(
    "spark.rapids.tpu.metrics.sample.intervalMs", 1000,
    "Background sampler period for gauge snapshots (HBM used/budget, "
    "spill-store bytes, semaphore queue depth, shuffle block-store "
    "size). <= 0 disables the sampler thread; instrumented counters "
    "still record, and exporters run one synchronous sample pass so "
    "snapshots are never stale.")

#: Prometheus-style default latency buckets (seconds)
DEFAULT_BUCKETS = (0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5,
                   1.0, 2.5, 5.0, 10.0, 30.0, 60.0)

#: the process-global registry; ``None`` means metrics are OFF and every
#: instrumentation site costs exactly one attribute load + branch
REGISTRY: Optional["MetricRegistry"] = None

#: name -> {"kind", "help"[, "buckets"]}; the closed catalog every
#: registry enforces
_INVENTORY: Dict[str, Dict[str, object]] = {}


def declare_metric(name: str, kind: str, help_text: str,
                   buckets: Optional[Tuple[float, ...]] = None) -> str:
    """Declare a metric name in the process-wide inventory (import
    time). Idempotent for identical declarations; a kind conflict is a
    programming error and raises. ``buckets`` declares a histogram's
    per-metric bucket ladder — the fix for DEFAULT_BUCKETS saturating
    at 60 s while queries run to the 600 s timeout."""
    prev = _INVENTORY.get(name)
    if prev is not None and prev["kind"] != kind:
        raise ValueError(f"metric {name} redeclared as {kind}, "
                         f"was {prev['kind']}")
    ent: Dict[str, object] = {"kind": kind, "help": help_text}
    if buckets is not None:
        ent["buckets"] = tuple(sorted(buckets))
    _INVENTORY[name] = ent
    return name


def metric_inventory() -> Dict[str, Dict[str, str]]:
    """The declared catalog (docs/monitoring.md + metric-name-drift)."""
    return dict(_INVENTORY)


class Counter:
    """Monotone counter. ``set_total`` exists for mirror counters whose
    source of truth is an external cumulative total (e.g. the memory
    manager's spill_to_host_bytes) — the sampler overwrites rather than
    re-adding."""

    __slots__ = ("name", "labels", "value", "_lock")
    kind = "counter"

    def __init__(self, name: str, labels: Tuple[Tuple[str, str], ...]):
        self.name = name
        self.labels = labels
        self.value = 0               # tpulint: guarded-by _lock
        self._lock = threading.Lock()

    def inc(self, n=1) -> None:
        with self._lock:
            self.value += n

    def set_total(self, v) -> None:
        with self._lock:
            self.value = v

    def set_max(self, v) -> None:
        """Monotone mirror for totals summed over WEAKLY-held sources
        (semaphores, block servers): a GC'd source drops out of the
        sum, and a decreasing counter would read as a reset to
        Prometheus rate()/increase() — hold the high-water mark
        instead."""
        with self._lock:
            if v > self.value:
                self.value = v


class Gauge:
    __slots__ = ("name", "labels", "value", "_lock")
    kind = "gauge"

    def __init__(self, name: str, labels: Tuple[Tuple[str, str], ...]):
        self.name = name
        self.labels = labels
        self.value = 0               # tpulint: guarded-by _lock
        self._lock = threading.Lock()

    def set(self, v) -> None:
        with self._lock:
            self.value = v

    def inc(self, n=1) -> None:
        with self._lock:
            self.value += n

    def dec(self, n=1) -> None:
        with self._lock:
            self.value -= n


class Histogram:
    """Cumulative-bucket histogram, Prometheus exposition semantics:
    ``bucket_counts[i]`` counts observations <= ``buckets[i]``; the
    implicit +Inf bucket is ``count``."""

    __slots__ = ("name", "labels", "buckets", "bucket_counts", "sum",
                 "count", "_lock")
    kind = "histogram"

    def __init__(self, name: str, labels: Tuple[Tuple[str, str], ...],
                 buckets=DEFAULT_BUCKETS):
        self.name = name
        self.labels = labels
        self.buckets = tuple(sorted(buckets))
        self.bucket_counts = [0] * len(self.buckets)  # tpulint: guarded-by _lock
        self.sum = 0.0               # tpulint: guarded-by _lock
        self.count = 0               # tpulint: guarded-by _lock
        self._lock = threading.Lock()

    def observe(self, v: float) -> None:
        i = bisect.bisect_left(self.buckets, v)
        with self._lock:
            for j in range(i, len(self.bucket_counts)):
                self.bucket_counts[j] += 1
            self.sum += v
            self.count += 1


class Summary:
    """Quantile summary over a mergeable relative-error sketch
    (metrics/sketch.py). Exposed as Prometheus
    ``name{quantile="0.5|0.95|0.99"}`` lines plus ``_sum``/``_count``;
    snapshots carry the serialized sketch so ``merge_snapshots`` ships
    it worker-labeled like any other series and the driver can fold a
    cluster-wide tail without raw samples."""

    __slots__ = ("name", "labels", "sketch", "_lock")
    kind = "summary"

    #: the exported quantile ladder
    QUANTILES = (0.5, 0.95, 0.99)

    def __init__(self, name: str, labels: Tuple[Tuple[str, str], ...]):
        from .sketch import QuantileSketch
        self.name = name
        self.labels = labels
        self.sketch = QuantileSketch()  # tpulint: guarded-by _lock
        self._lock = threading.Lock()

    def observe(self, v: float) -> None:
        with self._lock:
            self.sketch.observe(v)


class MetricRegistry:
    """Thread-safe store of live metric instances, keyed on
    (name, sorted labels). Snapshots are plain dicts — the interchange
    format task-completion RPCs ship and the exporters consume."""

    def __init__(self):
        # tpulint: guarded-by _lock
        self._metrics: Dict[Tuple[str, Tuple[Tuple[str, str], ...]],
                            object] = {}
        # bounded-cardinality label admission per (metric, label) pair
        self._label_seen: Dict[Tuple[str, str],
                               set] = {}  # tpulint: guarded-by _lock
        self._lock = threading.Lock()

    def _get(self, cls, name: str, labels: dict, **kw):
        if name not in _INVENTORY:
            raise KeyError(
                f"metric {name!r} is not declared in the inventory — "
                "declare_metric() it (and document it in "
                "docs/monitoring.md) before use")
        if _INVENTORY[name]["kind"] != cls.kind:
            raise TypeError(f"metric {name} is declared as "
                            f"{_INVENTORY[name]['kind']}, not {cls.kind}")
        key = (name, tuple(sorted(labels.items())))
        with self._lock:
            m = self._metrics.get(key)
            if m is None:
                m = cls(name, key[1], **kw)
                self._metrics[key] = m
            return m

    def counter(self, name: str, **labels) -> Counter:
        return self._get(Counter, name, labels)

    def gauge(self, name: str, **labels) -> Gauge:
        return self._get(Gauge, name, labels)

    def histogram(self, name: str, buckets=None,
                  **labels) -> Histogram:
        if buckets is None:
            buckets = (_INVENTORY.get(name, {}).get("buckets")
                       or DEFAULT_BUCKETS)
        return self._get(Histogram, name, labels, buckets=buckets)

    def summary(self, name: str, **labels) -> Summary:
        return self._get(Summary, name, labels)

    def bounded_label(self, name: str, label: str, value: str,
                      cap: int = 32) -> str:
        """Admit a label value under a per-(metric, label) cardinality
        cap: the first ``cap`` distinct values keep their identity,
        later ones collapse to ``"other"`` — an unbounded plan-digest
        stream must not mint unbounded series. Deterministic for a
        given observation order; reset with the registry (per-test
        ``shutdown_metrics``)."""
        value = str(value)
        key = (name, label)
        with self._lock:
            seen = self._label_seen.setdefault(key, set())
            if value in seen:
                return value
            if len(seen) < cap:
                seen.add(value)
                return value
        return "other"

    # ------------------------------------------ a query's own accounting
    def query_started(self, o) -> None:
        """Per-query fallback accounting (the qualification feed): one
        increment per (reason code, operator) tag occurrence of the
        plan-time placement summary."""
        if o.placement is not None:
            for op, codes in sorted(o.placement["ops"].items()):
                for code, n in sorted(codes.items()):
                    self.counter("srtpu_placement_fallback_total",
                                 code=code, op=op).inc(n)

    def query_ended(self, o) -> None:
        """Status count, and the wall into the tenant's histogram lane
        and two mergeable quantile sketches: per tenant for SLO burn
        math, per plan digest (bounded: overflow -> "other") so /slo can
        rank digests by tail contribution."""
        self.counter("srtpu_queries_total",
                     status="ok" if o.ok else "failed").inc()
        tenant = o.tenant or "default"
        self.histogram("srtpu_query_seconds",
                       tenant=tenant).observe(o.wall_s)
        self.summary("srtpu_query_latency_seconds",
                     tenant=tenant).observe(o.wall_s)
        if o.digest is not None:
            name = "srtpu_digest_latency_seconds"
            self.summary(name, digest=self.bounded_label(
                name, "digest", o.digest)).observe(o.wall_s)

    # ------------------------------------------------------------- read
    def snapshot(self) -> dict:
        """JSON-able {name: {kind, series: [...]}} snapshot plus a
        wall-clock stamp (the driver keeps the freshest of
        task-completion vs heartbeat snapshots per worker)."""
        with self._lock:
            metrics = list(self._metrics.values())
        out: Dict[str, dict] = {"__ts__": time.time()}
        for m in metrics:
            ent = out.setdefault(m.name, {"kind": m.kind, "series": []})
            s = {"labels": dict(m.labels)}
            if m.kind == "histogram":
                with m._lock:
                    s["buckets"] = [[b, c] for b, c in
                                    zip(m.buckets, m.bucket_counts)]
                    s["sum"] = m.sum
                    s["count"] = m.count
            elif m.kind == "summary":
                with m._lock:
                    s["sketch"] = m.sketch.to_json()
                    # tpulint: disable=lock-discipline — lock-free by
                    # design: the summary's _lock (held here) is the
                    # sketch's guard; the sketch itself is unsynchronized
                    s["sum"] = m.sketch.sum
                    # tpulint: disable=lock-discipline — same guard
                    s["count"] = m.sketch.count
            else:
                with m._lock:
                    # a torn scalar read is survivable, but exporting a
                    # value mid-update while histograms are snapshotted
                    # consistently made the two families disagree
                    s["value"] = m.value
            ent["series"].append(s)
        for ent in out.values():
            if isinstance(ent, dict) and "series" in ent:
                ent["series"].sort(
                    key=lambda s: sorted(s["labels"].items()))
        return out


# ---------------------------------------------------------------------------
# installation (the trace/core.py pattern)
# ---------------------------------------------------------------------------

_INSTALL_LOCK = threading.Lock()


def active_registry() -> Optional[MetricRegistry]:
    # tpulint: disable=lock-discipline — lock-free by design: the
    # disabled-path contract is one unlocked reference read per site
    return REGISTRY


def install_metrics(reg: Optional[MetricRegistry]) -> \
        Optional[MetricRegistry]:
    """Install (or with ``None`` remove) the process-global registry."""
    global REGISTRY
    with _INSTALL_LOCK:
        REGISTRY = reg
    return reg


def shutdown_metrics() -> None:
    """Stop the sampler thread (if any) and uninstall the registry —
    the per-test reset (conftest) and the bench artifact teardown."""
    from .sampler import stop_sampler
    stop_sampler()
    install_metrics(None)


def ensure_metrics_from_conf(conf) -> Optional[MetricRegistry]:
    """Install a registry (and start the sampler) iff
    ``spark.rapids.tpu.metrics.enabled`` — the one conf lookup, paid per
    ExecContext construction, never per metric event."""
    global REGISTRY
    if not conf.get(METRICS_ENABLED):
        # tpulint: disable=lock-discipline — lock-free by design:
        # metrics-off fast path; installation itself locks below
        return REGISTRY
    with _INSTALL_LOCK:
        if REGISTRY is None:
            REGISTRY = MetricRegistry()
        reg = REGISTRY
    interval_ms = int(conf.get(METRICS_SAMPLE_INTERVAL_MS))
    if interval_ms > 0:
        from .sampler import start_sampler
        start_sampler(reg, interval_ms)
    return reg


# ---------------------------------------------------------------------------
# the shipped metric catalog (docs/monitoring.md mirrors this; the
# metric-name-drift lint rule enforces the mirror)
# ---------------------------------------------------------------------------

declare_metric("srtpu_hbm_used_bytes", "gauge",
               "Logical HBM bytes currently accounted by the memory "
               "manager(s), summed across budgets.")
declare_metric("srtpu_hbm_budget_bytes", "gauge",
               "Total HBM budget across memory manager instances.")
declare_metric("srtpu_hbm_max_used_bytes", "gauge",
               "High-water mark of accounted HBM bytes.")
declare_metric("srtpu_spill_store_host_bytes", "gauge",
               "Bytes currently held in the host spill tier.")
declare_metric("srtpu_spill_store_disk_bytes", "gauge",
               "Bytes currently held in the disk spill tier.")
declare_metric("srtpu_spill_to_host_bytes_total", "counter",
               "Cumulative bytes spilled device -> host.")
declare_metric("srtpu_spill_to_disk_bytes_total", "counter",
               "Cumulative bytes spilled host -> disk.")
declare_metric("srtpu_semaphore_queue_depth", "gauge",
               "Tasks currently blocked waiting on the device "
               "semaphore, summed across live semaphores.")
declare_metric("srtpu_semaphore_wait_seconds_total", "counter",
               "Cumulative seconds tasks spent waiting on the device "
               "semaphore.")
declare_metric("srtpu_semaphore_acquires_total", "counter",
               "Cumulative successful device-semaphore acquisitions.")
declare_metric("srtpu_shuffle_block_store_bytes", "gauge",
               "Serialized shuffle block bytes currently resident in "
               "this process's block store(s).")
declare_metric("srtpu_shuffle_block_store_blocks", "gauge",
               "Shuffle blocks currently resident in this process's "
               "block store(s).")
declare_metric("srtpu_shuffle_put_bytes_total", "counter",
               "Cumulative serialized bytes accepted by block-store "
               "puts.")
declare_metric("srtpu_shuffle_fetch_bytes_total", "counter",
               "Cumulative serialized bytes served by block-store "
               "fetches.")
declare_metric("srtpu_shuffle_crc_rejects_total", "counter",
               "Corrupt shuffle blocks rejected by CRC32C verification "
               "(never stored/served).")
declare_metric("srtpu_oom_retries_total", "counter",
               "RetryOOM events absorbed by the retry framework.")
declare_metric("srtpu_oom_splits_total", "counter",
               "SplitAndRetryOOM events (input halved and retried).")
declare_metric("srtpu_oom_pressure_spills_total", "counter",
               "Cross-session pressure spills: the escalation rung that "
               "spills EVERY live session's spillables before the host "
               "degradation rung (mem/retry.py ladder).")
declare_metric("srtpu_oom_host_fallback_total", "counter",
               "Operators (or whole queries, op=Query) degraded to the "
               "host backend by the final OOM escalation rung instead of "
               "failing — labeled op=<operator kind>; each is also "
               "recorded as an OOM_PRESSURE_HOST placement tag.")
declare_metric("srtpu_semaphore_wedge_total", "counter",
               "Dead device-semaphore holders force-released by the "
               "wedge watchdog (spark.rapids.tpu.semaphore."
               "wedgeTimeoutMs): a holder thread died without releasing "
               "and its permit was reclaimed.")
declare_metric("srtpu_query_timeout_total", "counter",
               "Queries cancelled by the spark.rapids.tpu.query.timeout "
               "cooperative deadline.")
declare_metric("srtpu_queries_total", "counter",
               "Materialized queries, labeled status=ok|failed.")
declare_metric("srtpu_query_seconds", "histogram",
               "Whole-query wall time distribution (seconds), labeled "
               "tenant=<id or 'default'>. Per-metric buckets extend to "
               "600 s so queries near spark.rapids.tpu.query.timeout "
               "are not collapsed into +Inf.",
               buckets=DEFAULT_BUCKETS + (120.0, 300.0, 600.0))
declare_metric("srtpu_sampler_ticks_total", "counter",
               "Background sampler passes completed.")
declare_metric("srtpu_compile_cache_hits_total", "counter",
               "In-process executable-cache hits: a kernel request "
               "served by an already-built jitted callable "
               "(plan/exec_cache.py) — zero retrace, zero compile.")
declare_metric("srtpu_compile_cache_misses_total", "counter",
               "In-process executable-cache misses (a new kernel was "
               "built; XLA compile may still be served by the "
               "persistent tier).")
declare_metric("srtpu_compile_persistent_hits_total", "counter",
               "Compiles served by the persistent on-disk executable "
               "tier (JAX compilation-cache deserialization) instead "
               "of a fresh XLA compile.")
declare_metric("srtpu_compile_seconds_total", "counter",
               "Cumulative XLA backend-compile seconds this process "
               "actually paid (persistent-tier hits pay none).")
declare_metric("srtpu_event_log_records_total", "counter",
               "Records appended to the session event log.")
declare_metric("srtpu_hbm_pressure_grant_bytes", "gauge",
               "Bytes currently admitted OUTSIDE the device budget under "
               "the rung-4 pressure host grant (mem/manager.py); any "
               "nonzero value means an emergency host degradation is in "
               "flight and degrades the ops /healthz memory verdict.")
declare_metric("srtpu_worker_last_seen_ms", "gauge",
               "Wall-clock milliseconds of each merged metric lane's "
               "newest snapshot (merge_snapshots stamps one series per "
               "worker label): the exposition itself says how stale a "
               "lane's counters are, and the ops /healthz worker "
               "verdicts read heartbeat age from it.")
declare_metric("srtpu_ops_requests_total", "counter",
               "HTTP requests served by the live ops endpoint, labeled "
               "endpoint=/metrics|/healthz|/queries|/slo "
               "(ops/server.py).")
declare_metric("srtpu_flight_dumps_total", "counter",
               "Flight-recorder bundles written, labeled "
               "trigger=<kind from the ops/flight.py closed taxonomy> "
               "(semaphore_wedge, oom_ladder, query_timeout, "
               "worker_evicted, warm_recompile, placement_revert, "
               "sentinel_regression, admission_shed, slo_burn — "
               "docs/ops.md); rate-limited suppressions are not "
               "counted.")
declare_metric("srtpu_query_regressions_total", "counter",
               "Regressions flagged by the per-digest sentinel, labeled "
               "kind=warm_slowdown|verdict_flip|rung_escalation|"
               "tail_regression (ops/sentinel.py, docs/ops.md).")
declare_metric("srtpu_placement_fallback_total", "counter",
               "Operators/expressions kept off the device at plan time, "
               "labeled code=<reason code from the plan/tags.py closed "
               "registry> and op=<logical operator>; incremented once "
               "per executed query with that query's PlacementReport "
               "tag counts (docs/placement.md).")
declare_metric("srtpu_admission_admitted_total", "counter",
               "Queries admitted through the multi-tenant admission "
               "controller (sched/admission.py), labeled tenant=<id or "
               "'default'>; only counted when spark.rapids.tpu."
               "admission.enabled is on (docs/serving.md).")
declare_metric("srtpu_admission_rejected_total", "counter",
               "Admissions refused with AdmissionRejected, labeled "
               "reason=queue_full|deadline|shed|chaos "
               "(sched/admission.py, docs/serving.md).")
declare_metric("srtpu_admission_wait_seconds", "histogram",
               "Time admitted queries spent queued in the admission "
               "controller before their permit (seconds), labeled "
               "tenant=<id or 'default'>.")
declare_metric("srtpu_admission_queue_depth", "gauge",
               "Queries currently queued in the admission controller "
               "waiting for an in-flight slot (sampler snapshot).")
declare_metric("srtpu_tenant_hbm_used_bytes", "gauge",
               "Device-tier spillable bytes attributed to each tenant "
               "by the memory manager's ownership census, labeled "
               "tenant=<id> (mem/manager.py, docs/serving.md).")
declare_metric("srtpu_tenant_hbm_quota_bytes", "gauge",
               "Per-tenant HBM quota in bytes (spark.rapids.tpu."
               "tenant.hbmShare x the device budget), labeled "
               "tenant=<id>; 0 rows are not exported.")
declare_metric("srtpu_aqe_replans_total", "counter",
               "Adaptive-execution decisions recorded by the AQE log, "
               "labeled kind=<decision kind from the aqe/ closed "
               "taxonomy: coalesce_partitions|skew_split|"
               "broadcast_demote|broadcast_promote|cost_replan|"
               "feedback_replan> (aqe/__init__.py, docs/aqe.md).")
declare_metric("srtpu_aqe_coalesced_partitions_total", "counter",
               "Shuffle partitions merged into larger reduce units by "
               "AQE coalescing (cluster boundary re-planning plus the "
               "single-process adaptive reader).")
declare_metric("srtpu_aqe_skew_splits_total", "counter",
               "Sub-partitions created by AQE skew splits (salted "
               "re-partition of oversized shuffle partitions; for "
               "shuffled joins both sides split co-partitioned).")
declare_metric("srtpu_aqe_broadcast_demotions_total", "counter",
               "Broadcast build sides observed LARGER than the "
               "auto-broadcast threshold at materialization; the "
               "measured size re-plans the next run of the shape to a "
               "shuffled join (exec/joins.py, docs/aqe.md).")
declare_metric("srtpu_query_latency_seconds", "summary",
               "Whole-query wall time quantile summary (relative-error "
               "sketch, metrics/sketch.py), labeled tenant=<id or "
               "'default'>; exported as quantile=0.5|0.95|0.99 lines "
               "and mergeable across workers (docs/monitoring.md).")
declare_metric("srtpu_digest_latency_seconds", "summary",
               "Per-plan-digest wall time quantile summary, labeled "
               "digest=<plan digest, bounded cardinality — past the "
               "cap new digests collapse into digest=\"other\">; the "
               "tail-contribution ranking /slo serves reads it.")
declare_metric("srtpu_admission_wait_latency_seconds", "summary",
               "Admission-queue wait quantile summary, labeled "
               "tenant=<id or 'default'> (sched/admission.py, "
               "docs/serving.md).")
declare_metric("srtpu_worker_task_seconds", "summary",
               "Worker-side task wall time quantile summary, labeled "
               "task=<worker task name> (shuffle/cluster.py); per-lane "
               "sketches merge into the cluster-wide task tail.")
declare_metric("srtpu_slo_events_total", "counter",
               "Queries folded into the SLO tracker (ops/slo.py), "
               "labeled tenant=<id or 'default'> and status=good|bad "
               "(bad = over the tenant's latency target or failed).")
declare_metric("srtpu_slo_burn_rate", "gauge",
               "Error-budget burn rate per tenant and window, labeled "
               "tenant=<id> window=short|long; 1.0 burns the budget "
               "exactly at the objective's allowance, >1 burns faster "
               "(ops/slo.py, docs/serving.md).")
declare_metric("srtpu_slo_error_budget_remaining", "gauge",
               "Fraction of the long-window error budget left per "
               "tenant, labeled tenant=<id>; 1.0 = untouched, 0.0 = "
               "exhausted (ops/slo.py).")
declare_metric("srtpu_slo_burn_alerts_total", "counter",
               "Multi-window SLO burn alerts fired, labeled "
               "tenant=<id>; each also fires the flight recorder's "
               "slo_burn trigger (ops/slo.py, docs/ops.md).")
