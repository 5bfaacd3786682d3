"""Task metrics aggregation (ref GpuTaskMetrics.scala:110-195 — semaphore
wait, spill-to-host/disk time+bytes, max device footprint — merged into
Spark accumulators; here merged into a per-query summary dict exposed as
``TpuSession.last_query_metrics``)."""
from __future__ import annotations

from collections.abc import Mapping
from typing import Dict, Optional

__all__ = ["TaskMetrics", "metrics_summary", "metrics_to_json"]


class TaskMetrics:
    """Point-in-time capture of runtime counters to diff across a query."""

    def __init__(self, ctx):
        self.ctx = ctx
        mm = ctx.memory
        self._before = {
            "semWaitSec": ctx.semaphore.total_wait_s,
            "spillToHostBytes": mm.spill_to_host_bytes,
            **{k: v for k, v in mm.stats().items()},
        }

    def finish(self) -> Dict[str, object]:
        ctx = self.ctx
        mm = ctx.memory
        after = mm.stats()
        out = {
            "semWaitSec": round(
                ctx.semaphore.total_wait_s - self._before["semWaitSec"], 6),
            "spillToHostBytes":
                mm.spill_to_host_bytes - self._before["spillToHostBytes"],
            "spillToDiskBytes":
                after["spill_to_disk_bytes"]
                - self._before["spill_to_disk_bytes"],
            "maxDeviceBytes": after["max_device_used"],
        }
        out["operators"] = metrics_summary(ctx)
        return out


class LazyMetricsView(Mapping):
    """Per-exec metric mapping that defers forcing lazy device-scalar
    values (row counts kept unforced to avoid host syncs) until someone
    READS the metrics — then forces them all in ONE packed fetch instead
    of one device round trip per metric. A query that never inspects
    last_query_metrics pays nothing.

    The VALUES are snapshotted at construction (finish time): jax scalars
    are immutable, so later queries mutating the live Metric objects
    cannot contaminate this view, and forcing never writes back into
    engine state. Mapping (not dict) so every access path — [], get, in,
    iteration, dict(view) — funnels through the forcing accessors."""

    def __init__(self, values):
        #: exec_id -> {name: raw value (host number or jax scalar)}
        self._raw = values
        self._data = None

    def _force(self):
        if self._data is not None:
            return self._data
        lazy = [(eid, name, v) for eid, ms in self._raw.items()
                for name, v in ms.items() if hasattr(v, "item")]
        forced = {}
        if lazy:
            from ..columnar.packing import fetch_packed
            got = fetch_packed([v for _, _, v in lazy])
            for (eid, name, _), v in zip(lazy, got):
                forced[(eid, name)] = v.item() if hasattr(v, "item") else v
        self._data = {
            eid: {name: forced.get((eid, name), v)
                  for name, v in ms.items()}
            for eid, ms in self._raw.items()}
        return self._data

    def __getitem__(self, k):
        return self._force()[k]

    def __iter__(self):
        return iter(self._force())

    def __len__(self):
        return len(self._force())

    def __repr__(self):
        return repr(self._force())


def metrics_to_json(summary: Optional[dict]) -> Optional[dict]:
    """TaskMetrics.finish() output -> plain JSON-able dict (forces the
    lazy operator view — one packed fetch). Used by the event log's
    queryEnd record; NEVER raises: forcing device scalars after a failed
    query can itself fail, and the event-log path must not mask the
    query's real exception — it degrades to operators=None instead."""
    if summary is None:
        return None
    out = {}
    for k, v in summary.items():
        if k != "operators":
            out[k] = v.item() if hasattr(v, "item") else v
            continue
        try:
            ops = {}
            for eid, ms in dict(v).items():
                ops[eid] = {
                    n: (val.item() if hasattr(val, "item") else val)
                    for n, val in ms.items()}
            out[k] = ops
        except Exception:  # noqa: BLE001 - degrade, never mask
            out[k] = None
    return out


def metrics_summary(ctx):
    """Per-exec metric values keyed by exec id (the SQL-UI GpuMetric view,
    GpuExec.scala:54-165). The verbosity conf plays the role of the
    reference's DEBUG/MODERATE/ESSENTIAL metric levels: lower verbosity
    drops the noisier counters from the summary. Lazy: LazyMetricsView."""
    from ..config import METRICS_LEVEL
    level = str(ctx.conf.get(METRICS_LEVEL)).upper()
    lvl_rank = {"ESSENTIAL": 0, "MODERATE": 1, "DEBUG": 2}
    keep = lvl_rank.get(level, 2)
    # ctx is the query's own (exec/query.run_query): this walks
    # the operators of ONE plan. Snapshot the raw VALUES all the same —
    # a caller may hand in a context it goes on executing plans on
    snap = {}
    for exec_id, ms in ctx.metrics.items():
        kept = {name: m.value for name, m in ms.items()
                if lvl_rank.get(m.level, 1) <= keep}
        if kept:
            snap[exec_id] = kept
    return LazyMetricsView(snap)
