"""Session-scoped structured event log (JSONL, rotating).

Reference analog: Spark's event log (spark.eventLog.enabled/dir) — the
durable query-history record the History Server and the spark-rapids
qualification/profiling tools replay. Each materializing query appends
a ``queryStart`` record (plan digest + config snapshot) and a
``queryEnd`` record (ok/failed, duration, TaskMetrics, fault stats,
trace-artifact path); ``tools/history`` renders and diffs the logs.

Format: one JSON object per line. The active file is
``events.jsonl``; when it exceeds ``rotate.maxBytes`` after a write it
is renamed to ``events-<seq>.jsonl`` (ascending seq = older). A
crash-truncated trailing line is tolerated by every reader
(tools/history skips undecodable lines and counts them).

Event-log writes must never fail a query: I/O errors are logged and
swallowed, exactly like trace-artifact writes.
"""
from __future__ import annotations

import hashlib
import json
import logging
import os
import threading
import time
import weakref
from typing import List, Optional

from ..config import register

__all__ = ["EventLogWriter", "plan_digest", "writer_health",
           "EVENT_LOG_ENABLED", "EVENT_LOG_DIR", "EVENT_LOG_MAX_BYTES",
           "ACTIVE_NAME"]

log = logging.getLogger(__name__)

#: live writers, observed by the ops /healthz event-log-lag section;
#: weak so a closed session's writer just drops out of the census
_WRITERS: "weakref.WeakSet" = weakref.WeakSet()


def writer_health() -> List[dict]:
    """Per-writer write/error recency for the ops /healthz verdicts
    (ops/server.py): a writer whose newest attempt FAILED — or that has
    not landed a record in far too long — degrades the section."""
    out = []
    for w in list(_WRITERS):
        with w._lock:
            out.append({"dir": w.dir,
                        "lastWriteTs": w.last_write_ts,
                        "lastErrorTs": w.last_error_ts})
    return sorted(out, key=lambda d: d["dir"])

EVENT_LOG_ENABLED = register(
    "spark.rapids.tpu.eventLog.enabled", False,
    "Append a structured JSONL record per materialized query "
    "(queryStart: plan digest + config snapshot; queryEnd: status, "
    "duration, TaskMetrics, fault stats, trace-artifact path) to "
    "spark.rapids.tpu.eventLog.dir — the Spark event-log analog. "
    "Render/diff with python -m spark_rapids_tpu.tools.history "
    "(docs/monitoring.md).", commonly_used=True)

EVENT_LOG_DIR = register(
    "spark.rapids.tpu.eventLog.dir", "/tmp/srtpu_events",
    "Directory for the rotating query event log (created on first "
    "write).")

EVENT_LOG_MAX_BYTES = register(
    "spark.rapids.tpu.eventLog.rotate.maxBytes", 16 * 1024 * 1024,
    "The active events.jsonl rotates to events-<seq>.jsonl once it "
    "exceeds this many bytes (ascending seq = older records); <= 0 "
    "disables rotation.")

ACTIVE_NAME = "events.jsonl"


def plan_digest(plan) -> str:
    """Stable digest of a logical plan's structure — the join key for
    run-over-run regression diffs (tools/history --diff). Uses the
    plan's tree string, which renders structure + expressions but not
    data, so re-running the same query text matches across sessions."""
    return hashlib.sha256(
        plan.tree_string().encode("utf-8")).hexdigest()[:16]


class EventLogWriter:
    """Appends JSONL records with size-based rotation. Thread-safe;
    one writer per session (the session serializes queries anyway, but
    background samplers may interleave)."""

    def __init__(self, directory: str, max_bytes: int = 0):
        self.dir = directory
        self.max_bytes = int(max_bytes)
        self._lock = threading.Lock()
        self._seq = self._next_seq()  # tpulint: guarded-by _lock
        #: wall-clock of the last successful append / failed attempt
        #: (the ops /healthz event-log-lag inputs)
        self.last_write_ts: Optional[float] = None  # tpulint: guarded-by _lock
        self.last_error_ts: Optional[float] = None  # tpulint: guarded-by _lock
        _WRITERS.add(self)

    @classmethod
    def from_conf(cls, conf) -> Optional["EventLogWriter"]:
        if not conf.get(EVENT_LOG_ENABLED):
            return None
        return cls(str(conf.get(EVENT_LOG_DIR)),
                   int(conf.get(EVENT_LOG_MAX_BYTES)))

    @property
    def active_path(self) -> str:
        return os.path.join(self.dir, ACTIVE_NAME)

    def _next_seq(self) -> int:
        try:
            names = os.listdir(self.dir)
        except OSError:
            return 0
        seqs = []
        for n in names:
            if n.startswith("events-") and n.endswith(".jsonl"):
                try:
                    seqs.append(int(n[len("events-"):-len(".jsonl")]))
                except ValueError:
                    continue
        return max(seqs) + 1 if seqs else 0

    # tpulint: never-raise
    def write(self, record: dict) -> bool:
        """Append one record (stamped with a wall-clock ``ts``).
        Returns False — never raises — on I/O failure."""
        rec = dict(record)
        rec.setdefault("ts", round(time.time(), 6))
        try:
            line = json.dumps(rec, sort_keys=True, default=str) + "\n"
            with self._lock:
                os.makedirs(self.dir, exist_ok=True)
                with open(self.active_path, "a", encoding="utf-8") as f:
                    f.write(line)
                    f.flush()
                    size = f.tell()
                self.last_write_ts = time.time()
                if 0 < self.max_bytes < size:
                    self._rotate()
        except Exception as e:  # noqa: BLE001 - never fail a query
            log.warning("event log write to %s failed: %s",
                        self.dir, e)
            with self._lock:
                self.last_error_ts = time.time()
            return False
        from .registry import REGISTRY
        if REGISTRY is not None:
            REGISTRY.counter("srtpu_event_log_records_total").inc()
        return True

    def query_started(self, o) -> None:
        """The ``queryStart`` record of one ``exec/query.QueryOutcome``."""
        self.write({"event": "queryStart", "queryId": o.query_id,
                    "planDigest": o.digest, "root": o.root,
                    # coded placement summary: what tools/qualify mines
                    # across the history (docs/placement.md)
                    "placement": o.placement,
                    "conf": {k: str(v)
                             for k, v in sorted(o.conf.raw.items())}})

    def query_ended(self, o) -> None:
        """The ``queryEnd`` record; the sentinel and tools/history read
        ``degraded`` / ``ladderRung`` / ``reason`` directly."""
        from ..aux.metrics import metrics_to_json
        rec = {"event": "queryEnd", "queryId": o.query_id,
               "planDigest": o.digest, "ok": o.ok,
               "durationMs": round(o.wall_ms, 3),
               "degraded": bool(o.degradations),
               "ladderRung": o.ladder_rung,
               "tenant": o.tenant, "queuedMs": o.queued_ms,
               "compileSeconds": o.compile_s,
               "placementVerdict": o.verdict,
               "metrics": metrics_to_json(o.metrics),
               "faultStats": o.fault_stats, "trace": o.trace_path}
        if o.reason:
            rec["reason"] = o.reason
        if o.admission:
            rec["admission"] = o.admission
        if o.aqe:
            # compact kind -> count map; the per-decision details ride
            # the session attribute and the trace, not every record
            rec["aqe"] = o.aqe
        if o.degradations:
            # queryStart shipped the plan-time summary; degradations are
            # runtime facts, so the END record carries them (and the
            # refreshed summary tools/qualify prefers when present)
            rec["oomDegradations"] = o.degradations
            rec["placement"] = o.placement
        self.write(rec)

    def _rotate(self) -> None:
        # re-scan at rotation time: another writer sharing the
        # directory (two sessions, two processes) may have rotated
        # since construction — never os.replace() onto its records
        self._seq = max(self._seq, self._next_seq())
        dst = os.path.join(self.dir, f"events-{self._seq}.jsonl")
        os.replace(self.active_path, dst)
        self._seq += 1
