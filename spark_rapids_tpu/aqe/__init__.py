"""Closed-loop Adaptive Query Execution (ISSUE 19).

Reference analog: Spark AQE re-optimizes the remaining plan from the
MapOutputStatistics of every materialized shuffle (coalescing small
partitions, splitting skewed ones, demoting broadcasts whose build side
came in oversized) — the reference plugin rides those re-planned stages
through GpuCustomShuffleReaderExec. Our reproduction had every input to
that loop (the PR-4 profiler's per-partition histograms, the PR-8
learned costs, the PR-15 sentinel baselines, PR-3 lineage) but planned
once and executed blind. This package closes the loop:

* at each materialized shuffle boundary the cluster driver snapshots
  actual per-partition rows/bytes (:class:`~.planner.ShuffleStats`) and
  re-plans the not-yet-executed reduce side — runs of small partitions
  below ``spark.rapids.tpu.aqe.coalesce.targetBytes`` merge into one
  reduce unit, partitions above ``spark.rapids.tpu.aqe.skew.threshold``
  x mean are salted-rehashed into sub-partitions (shuffle/cluster.py);
* the single-process exchange's adaptive reader and the broadcast join
  record the same decisions when observed sizes flip a plan-time choice
  (shuffle/exchange.py, exec/joins.py, plan/overrides.py);
* :mod:`~.feedback` consumes sentinel history so a digest that
  repeatedly hit OOM rung >= 3 — or kept flagging warm-slowdown — is
  pre-emptively re-planned at admission (aqe/feedback.py overlay_conf).

Every decision is an :class:`AqeDecision` with a kind from the CLOSED
``DECISION_KINDS`` registry (the plan/tags idiom: unknown kinds raise),
recorded into the process-global :class:`AqeLog` (install pattern of
trace/core.py) and fanned out to the metric inventory
(``srtpu_aqe_*``) and the tracer (one ``aqe.<kind>`` instant per
decision, which tools/profile counts). Surfaced in
``explain("analyze")``, ``GET /queries``, queryEnd / clusterQuery event
records and tools/history (docs/aqe.md).
"""
from __future__ import annotations

import threading
from typing import Dict, List, Optional

from ..config import register

__all__ = [
    "AQE_ENABLED", "AQE_COALESCE_TARGET_BYTES", "AQE_SKEW_THRESHOLD",
    "AQE_SKEW_MIN_BYTES", "AQE_BROADCAST_DEMOTE_ENABLED",
    "AQE_FEEDBACK_ENABLED", "DECISION_KINDS", "COALESCE_PARTITIONS",
    "SKEW_SPLIT", "BROADCAST_DEMOTE", "BROADCAST_PROMOTE", "COST_REPLAN",
    "FEEDBACK_REPLAN", "AqeDecision", "make_decision", "AqeLog",
    "summarize", "LOG", "install_aqe", "ensure_aqe_from_conf"]

AQE_ENABLED = register(
    "spark.rapids.tpu.aqe.enabled", True,
    "Closed-loop adaptive query execution: re-plan at materialized "
    "shuffle boundaries from observed per-partition statistics "
    "(coalesce small partitions, split skewed ones with a salted "
    "rehash, demote oversized broadcasts) and record every decision as "
    "a closed-taxonomy AqeDecision (docs/aqe.md). Off = the pre-AQE "
    "one-shot plan with zero added overhead (ref Spark "
    "spark.sql.adaptive.enabled + GpuCustomShuffleReaderExec).",
    commonly_used=True)
AQE_COALESCE_TARGET_BYTES = register(
    "spark.rapids.tpu.aqe.coalesce.targetBytes", 64 * 1024 * 1024,
    "AQE merges consecutive shuffle partitions whose combined "
    "serialized size stays under this target into one reduce unit "
    "(ref spark.sql.adaptive.advisoryPartitionSizeInBytes).")
AQE_SKEW_THRESHOLD = register(
    "spark.rapids.tpu.aqe.skew.threshold", 2.0,
    "A shuffle partition is skewed when its serialized bytes exceed "
    "this factor times the mean partition size (the tools/profile "
    "SKEW_RATIO condition, now acted on at run time); skewed "
    "partitions are salted-rehashed into sub-partitions before the "
    "reduce (ref spark.sql.adaptive.skewJoin.skewedPartitionFactor).")
AQE_SKEW_MIN_BYTES = register(
    "spark.rapids.tpu.aqe.skew.minBytes", 1 << 20,
    "Partitions below this absolute size are never treated as skewed "
    "regardless of the ratio — splitting tiny partitions only adds "
    "task overhead (the profiler's SKEW_MIN_BYTES floor).")
AQE_BROADCAST_DEMOTE_ENABLED = register(
    "spark.rapids.tpu.aqe.broadcast.demote.enabled", True,
    "Record a broadcast_demote decision — and feed the measured size "
    "to the planner so the next plan of the same shape genuinely "
    "demotes — when a broadcast build side materializes LARGER than "
    "spark.rapids.tpu.sql.autoBroadcastJoinThreshold; the symmetric "
    "broadcast_promote fires when a measured side comes in under a "
    "threshold its estimate exceeded (ref AQE join-strategy "
    "switching, GpuOverrides.scala:4681).")
AQE_FEEDBACK_ENABLED = register(
    "spark.rapids.tpu.aqe.feedback.enabled", True,
    "Sentinel-history feedback: a plan digest whose baseline shows "
    "repeated OOM ladder escalation to rung >= 3 is pre-emptively "
    "re-planned at admission with quartered target batch sizes; one "
    "that keeps flagging warm-slowdown on the device is re-planned "
    "onto the host engine (aqe/feedback.py, docs/aqe.md). Requires "
    "both aqe.enabled and an installed regression sentinel.")

# --------------------------------------------------------------------------
# the closed decision taxonomy (docs/aqe.md mirrors this table)
# --------------------------------------------------------------------------

COALESCE_PARTITIONS = "coalesce_partitions"
SKEW_SPLIT = "skew_split"
BROADCAST_DEMOTE = "broadcast_demote"
BROADCAST_PROMOTE = "broadcast_promote"
COST_REPLAN = "cost_replan"
FEEDBACK_REPLAN = "feedback_replan"

#: kind -> one-line meaning; the single source docs/aqe.md, the
#: explain("analyze") renderer and tools/history share. CLOSED:
#: make_decision raises on anything not listed here (plan/tags.py
#: REASON_CODES pattern), so downstream consumers never see an
#: unclassifiable decision.
DECISION_KINDS: Dict[str, str] = {
    COALESCE_PARTITIONS:
        "a run of small shuffle partitions (each under "
        "aqe.coalesce.targetBytes combined) was merged into one "
        "reduce unit, or the single-process adaptive reader "
        "concatenated sub-target batches",
    SKEW_SPLIT:
        "a shuffle partition above aqe.skew.threshold x mean was "
        "salted-rehashed into sub-partitions before the reduce (for "
        "shuffled joins BOTH sides of the skewed partition are split "
        "with the same salt, keeping them co-partitioned)",
    BROADCAST_DEMOTE:
        "a planned broadcast's build side materialized larger than "
        "the auto-broadcast threshold; the measured size is recorded "
        "so the next plan of this shape uses a shuffled join",
    BROADCAST_PROMOTE:
        "a join side's MEASURED size came in under the auto-broadcast "
        "threshold its plan-time estimate exceeded, flipping the join "
        "to a broadcast build",
    COST_REPLAN:
        "observed row counts at a materialized boundary diverged from "
        "the scan-based estimate by >= 2x; the learned-cost optimizer "
        "re-plans the remaining stages (and future runs of this "
        "shape) with the observed cardinality",
    FEEDBACK_REPLAN:
        "sentinel history showed this digest repeatedly escalating "
        "the OOM ladder or flagging warm-slowdown; it was admitted "
        "with a pre-emptively re-planned configuration (smaller "
        "target batches or host placement)",
}


class AqeDecision:
    """One recorded adaptive decision: a registered ``kind``, free-text
    ``detail``, the shuffle id it acted on (when any) and how many
    partitions/sub-partitions it touched. Strings and ints only —
    decisions cross the event-log JSON boundary."""

    __slots__ = ("kind", "detail", "shuffle", "parts", "seq", "thread")

    def __init__(self, kind: str, detail: str = "",
                 shuffle: Optional[int] = None, parts: int = 0):
        if kind not in DECISION_KINDS:
            raise ValueError(
                f"unregistered AQE decision kind {kind!r} — add it to "
                "aqe.DECISION_KINDS (and docs/aqe.md) first")
        self.kind = kind
        self.detail = detail
        self.shuffle = shuffle
        self.parts = int(parts)
        self.seq = -1         # assigned by AqeLog.record
        self.thread = 0       # recording thread ident (attribution)

    def summary(self) -> dict:
        out = {"kind": self.kind, "detail": self.detail,
               "parts": self.parts}
        if self.shuffle is not None:
            out["shuffle"] = self.shuffle
        return out

    def __repr__(self):
        return (f"AqeDecision({self.kind}, parts={self.parts}, "
                f"shuffle={self.shuffle}, {self.detail!r})")

    def __getstate__(self):
        return {s: getattr(self, s) for s in self.__slots__}

    def __setstate__(self, st):
        for s in self.__slots__:
            setattr(self, s, st[s])


def make_decision(kind: str, detail: str = "",
                  shuffle: Optional[int] = None,
                  parts: int = 0) -> AqeDecision:
    """The one constructor decision sites use (raises on unknown kinds,
    keeping the taxonomy closed at every call site)."""
    return AqeDecision(kind, detail, shuffle=shuffle, parts=parts)


#: the specific per-kind counter next to the labeled replans_total
#: family (metrics/registry.py inventory; kinds without a row only
#: count in replans_total)
_KIND_COUNTER = {
    COALESCE_PARTITIONS: "srtpu_aqe_coalesced_partitions_total",
    SKEW_SPLIT: "srtpu_aqe_skew_splits_total",
    BROADCAST_DEMOTE: "srtpu_aqe_broadcast_demotions_total",
}


class AqeLog:
    """Process-global bounded decision log (install pattern of
    trace/core.py: module global, one attribute load + branch per
    decision site when AQE is off).

    Attribution contract: every decision site runs on the thread
    DRIVING its query (the cluster driver loop, the exchange's
    consuming generator, the broadcast build, the admission hook), so
    ``since(mark, thread=...)`` slices out exactly one query's
    decisions even with concurrent sessions in one process."""

    def __init__(self, max_events: int = 4096):
        self._lock = threading.Lock()
        self._seq = 0                        # tpulint: guarded-by _lock
        self._events: List[AqeDecision] = []  # tpulint: guarded-by _lock
        self._max = int(max_events)

    def mark(self) -> int:
        """Current sequence number — pair with :meth:`since` to scope
        one query's decisions."""
        with self._lock:
            return self._seq

    def record(self, d: AqeDecision) -> AqeDecision:
        """Append a decision and fan it out to the metric registry and
        the tracer (an ``aqe.<kind>`` instant tools/profile counts).
        The fan-out is observability: it must never fail the query
        that decided."""
        with self._lock:
            d.seq = self._seq
            self._seq += 1
            d.thread = threading.get_ident()
            self._events.append(d)
            if len(self._events) > self._max:
                del self._events[:len(self._events) - self._max]
        try:  # tpulint: never-raise
            from ..metrics import registry as metrics_registry
            mr = metrics_registry.REGISTRY
            if mr is not None:
                mr.counter("srtpu_aqe_replans_total", kind=d.kind).inc()
                spec = _KIND_COUNTER.get(d.kind)
                if spec is not None:
                    mr.counter(spec).inc(max(1, d.parts))
            from ..trace import core as trace_core
            tr = trace_core.TRACER
            if tr is not None:
                tr.instant(f"aqe.{d.kind}", cat="aqe", args=d.summary())
        except Exception:  # noqa: BLE001 - observability only
            pass
        return d

    def since(self, mark: int,
              thread: Optional[int] = None) -> List[AqeDecision]:
        """Decisions recorded at/after ``mark`` — optionally only those
        recorded by ``thread`` (per-query attribution under
        concurrency; see class docstring)."""
        with self._lock:
            evs = [d for d in self._events if d.seq >= mark]
        if thread is not None:
            evs = [d for d in evs if d.thread == thread]
        return evs

    def decisions(self) -> List[AqeDecision]:
        with self._lock:
            return list(self._events)


def summarize(decisions: List[AqeDecision]) -> Dict[str, int]:
    """decision kind -> count, the compact form queryEnd records,
    ``GET /queries`` and tools/history carry."""
    out: Dict[str, int] = {}
    for d in decisions:
        out[d.kind] = out.get(d.kind, 0) + 1
    return out


#: the installed log, or None = AQE off (every decision site is one
#: module-attribute load + branch on the disabled path)
LOG: Optional[AqeLog] = None


def install_aqe(log: Optional[AqeLog]) -> Optional[AqeLog]:
    """Install (or with None, tear down) the process AQE log."""
    global LOG
    LOG = log
    return log


def ensure_aqe_from_conf(conf) -> Optional[AqeLog]:
    """One conf lookup per ExecContext / cluster execute: installs the
    process log when ``spark.rapids.tpu.aqe.enabled`` is on and none is
    installed yet (the ensure_tracer_from_conf contract)."""
    if not bool(conf.get(AQE_ENABLED)):
        return None
    if LOG is None:
        install_aqe(AqeLog())
    return LOG
