"""Query-history tool over the rotating event log (metrics/events.py).

The offline half of the observability subsystem — the role the Spark
History Server + the spark-rapids qualification/profiling tools play
over Spark event logs. Reads a log directory (rotated
``events-<seq>.jsonl`` files oldest-first, then the active
``events.jsonl``), pairs queryStart/queryEnd records, and renders:

* the query history (``python -m spark_rapids_tpu.tools.history DIR``),
* the slowest queries (``--slowest N``),
* a deterministic run-over-run regression diff between two logs
  (``--diff OTHER_DIR``), matching queries by plan digest,
* a metrics-snapshot summary (``--metrics-file snap.json``) over a
  JSON snapshot artifact (metrics/export.py).

Crash tolerance: a crash-truncated (or otherwise undecodable) line is
skipped and counted, never fatal — the log is written line-at-a-time
precisely so everything before the crash stays readable. Stdlib-only
and deterministic: identical logs render identical reports.
"""
from __future__ import annotations

import argparse
import json
import os
from typing import Dict, List, Optional, Tuple

__all__ = ["load_events", "build_history", "format_history",
           "format_slowest", "diff_histories", "format_diff",
           "summarize_metrics_file", "slo_replay", "format_slo",
           "main"]

#: registry series the metrics-snapshot summary surfaces (must exist in
#: the MetricRegistry inventory — enforced by the metric-name-drift
#: lint rule)
KEY_METRICS = [
    "srtpu_hbm_used_bytes",
    "srtpu_hbm_budget_bytes",
    "srtpu_spill_to_host_bytes_total",
    "srtpu_spill_to_disk_bytes_total",
    "srtpu_semaphore_wait_seconds_total",
    "srtpu_shuffle_block_store_bytes",
    "srtpu_oom_retries_total",
    "srtpu_oom_splits_total",
    "srtpu_queries_total",
]


def _log_files(path: str) -> List[str]:
    """Event-log files oldest-first for a directory (rotation order) or
    a single file path."""
    if os.path.isfile(path):
        return [path]
    try:
        names = os.listdir(path)
    except OSError:
        return []
    rotated = []
    for n in names:
        if n.startswith("events-") and n.endswith(".jsonl"):
            try:
                rotated.append((int(n[len("events-"):-len(".jsonl")]), n))
            except ValueError:
                continue
    out = [os.path.join(path, n) for _, n in sorted(rotated)]
    active = os.path.join(path, "events.jsonl")
    if os.path.exists(active):
        out.append(active)
    return out


def load_events(path: str) -> Tuple[List[dict], int]:
    """All decodable records oldest-first plus the count of skipped
    (truncated/corrupt) lines."""
    events: List[dict] = []
    skipped = 0
    for f in _log_files(path):
        with open(f, encoding="utf-8", errors="replace") as fh:
            for line in fh:
                line = line.strip()
                if not line:
                    continue
                try:
                    rec = json.loads(line)
                except (json.JSONDecodeError, ValueError):
                    skipped += 1       # crash-truncated tail, etc.
                    continue
                if isinstance(rec, dict):
                    events.append(rec)
                else:
                    skipped += 1
    return events, skipped


def build_history(events: List[dict]) -> List[dict]:
    """Pair queryStart/queryEnd into one record per query, in start
    order. A start without an end (crash mid-query) renders with
    status ``lost``."""
    starts: Dict[object, dict] = {}
    out: List[dict] = []
    for rec in events:
        kind = rec.get("event")
        if kind == "queryStart":
            q = {"queryId": rec.get("queryId"),
                 "planDigest": rec.get("planDigest"),
                 "root": rec.get("root"),
                 "startTs": rec.get("ts"),
                 "status": "lost", "durationMs": None,
                 "trace": None, "faultStats": None, "metrics": None,
                 "reason": None, "degraded": False,
                 "tenant": None, "queuedMs": None, "admission": None,
                 "aqe": None}
            starts[rec.get("queryId")] = q
            out.append(q)
        elif kind == "queryEnd":
            q = starts.pop(rec.get("queryId"), None)
            if q is None:             # end without a start (rotated away)
                q = {"queryId": rec.get("queryId"),
                     "planDigest": rec.get("planDigest"),
                     "root": None, "startTs": None}
                out.append(q)
            q["status"] = "ok" if rec.get("ok") else "failed"
            q["durationMs"] = rec.get("durationMs")
            q["trace"] = rec.get("trace")
            q["faultStats"] = rec.get("faultStats")
            q["metrics"] = rec.get("metrics")
            # outcome detail (ISSUE 15): why a query failed (timeout,
            # OOM) or ran degraded on the rung-4 host ladder
            q["reason"] = rec.get("reason")
            q["degraded"] = bool(rec.get("degraded"))
            # multi-tenant serving detail (ISSUE 18): which tenant ran
            # the query and how the admission controller treated it
            q["tenant"] = rec.get("tenant")
            q["queuedMs"] = rec.get("queuedMs")
            q["admission"] = rec.get("admission")
            # adaptive execution summary (ISSUE 19): the queryEnd
            # record's kind -> count map of AqeDecisions
            q["aqe"] = rec.get("aqe")
            if q["degraded"] and q["status"] == "ok":
                q["status"] = "degraded"
    return out


def _fmt_ms(v) -> str:
    return "-" if v is None else f"{float(v):10.1f}"


def format_history(history: List[dict], skipped: int = 0,
                   source: str = "") -> str:
    lines = [f"== Query history ({source or 'event log'}) ==",
             f"{'id':>4}  {'status':<8} {'ms':>10}  "
             f"{'digest':<16}  {'tenant':<10}  root  reason"]
    for q in history:
        reason = q.get("reason") or ""
        # admission detail (ISSUE 18): shed queries surface as the
        # admission status; admitted-after-queueing shows the queue wait
        adm = q.get("admission")
        if adm == "shed":
            reason = (f"admission=shed; {reason}" if reason
                      else "admission=shed")
        elif q.get("queuedMs"):
            reason = (f"queued {q['queuedMs']}ms; {reason}" if reason
                      else f"queued {q['queuedMs']}ms")
        if q.get("aqe"):
            # compact AQE summary (ISSUE 19): aqe=kind:count,...
            aqe_txt = "aqe=" + ",".join(
                f"{k}:{q['aqe'][k]}" for k in sorted(q["aqe"]))
            reason = f"{aqe_txt}; {reason}" if reason else aqe_txt
        lines.append(
            f"{str(q.get('queryId') or '?'):>4}  "
            f"{q.get('status') or '?':<8} "
            f"{_fmt_ms(q.get('durationMs'))}  "
            f"{str(q.get('planDigest') or '?'):<16}  "
            f"{str(q.get('tenant') or '-'):<10}  "
            f"{q.get('root') or '?'}"
            + (f"  {reason[:80]}" if reason else ""))
    ok = sum(1 for q in history if q.get("status") == "ok")
    failed = sum(1 for q in history if q.get("status") == "failed")
    lost = sum(1 for q in history if q.get("status") == "lost")
    degraded = sum(1 for q in history if q.get("status") == "degraded")
    tail = (f"{len(history)} queries: {ok} ok, {failed} failed, "
            f"{lost} lost")
    if degraded:
        tail += f", {degraded} degraded"
    lines.append(f"{tail}; {skipped} undecodable line(s) skipped")
    return "\n".join(lines) + "\n"


def format_slowest(history: List[dict], n: int) -> str:
    timed = [q for q in history if q.get("durationMs") is not None]
    timed.sort(key=lambda q: (-float(q["durationMs"]),
                              str(q.get("queryId"))))
    lines = [f"== Slowest {min(n, len(timed))} queries =="]
    for q in timed[:n]:
        lines.append(f"{_fmt_ms(q['durationMs'])} ms  "
                     f"id={q.get('queryId')}  "
                     f"digest={q.get('planDigest')}  "
                     f"{q.get('root') or '?'}")
    return "\n".join(lines) + "\n"


def diff_histories(a: List[dict], b: List[dict]) -> List[dict]:
    """Regression diff: queries matched by plan digest; per digest the
    MIN ok duration of each side is compared (min is the stable
    estimator the bench harness uses). Deterministic: sorted by ratio
    descending then digest."""
    def by_digest(h):
        out: Dict[str, List[float]] = {}
        for q in h:
            if q.get("status") == "ok" and q.get("durationMs") is not None:
                out.setdefault(str(q.get("planDigest")), []).append(
                    float(q["durationMs"]))
        return out

    da, db = by_digest(a), by_digest(b)
    rows = []
    for digest in sorted(set(da) & set(db)):
        base, new = min(da[digest]), min(db[digest])
        rows.append({"digest": digest, "baseMs": round(base, 3),
                     "newMs": round(new, 3),
                     "ratio": round(new / base, 4) if base > 0 else None,
                     "nBase": len(da[digest]), "nNew": len(db[digest])})
    rows.sort(key=lambda r: (-(r["ratio"] or 0.0), r["digest"]))
    only_a = sorted(set(da) - set(db))
    only_b = sorted(set(db) - set(da))
    if only_a:
        rows.append({"digest": None, "onlyBase": only_a})
    if only_b:
        rows.append({"digest": None, "onlyNew": only_b})
    return rows


def format_diff(rows: List[dict], a: str, b: str) -> str:
    lines = [f"== Regression diff: {a} -> {b} ==",
             f"{'digest':<16}  {'base ms':>10}  {'new ms':>10}  "
             f"{'ratio':>7}  n"]
    for r in rows:
        if r.get("digest") is None:
            for k, label in (("onlyBase", "only in base"),
                             ("onlyNew", "only in new")):
                if r.get(k):
                    lines.append(f"{label}: {', '.join(r[k])}")
            continue
        flag = ""
        if r["ratio"] is not None and r["ratio"] >= 1.2:
            flag = "  REGRESSED"
        elif r["ratio"] is not None and r["ratio"] <= 0.8:
            flag = "  improved"
        lines.append(f"{r['digest']:<16}  {r['baseMs']:>10.1f}  "
                     f"{r['newMs']:>10.1f}  "
                     f"{r['ratio'] if r['ratio'] is not None else '-':>7}"
                     f"  {r['nBase']}/{r['nNew']}{flag}")
    return "\n".join(lines) + "\n"


def slo_replay(events: List[dict], *, target_ms: float,
               objective: float = 0.99, short_window_s: float = 60.0,
               long_window_s: float = 600.0) -> dict:
    """Offline SLO report over an event log: replays every queryEnd
    through the SAME pure fold the live ``SloTracker`` runs
    (ops/slo.py ``fold_slo_event``/``burn_rate``/``budget_remaining``)
    and the same quantile sketch the ``Summary`` metric kind uses, so
    a replayed log and the live ``/slo`` endpoint agree by
    construction. Deterministic: identical logs yield identical
    reports."""
    from ...metrics.sketch import QuantileSketch
    from ...ops.slo import (budget_remaining, burn_rate,
                            fold_slo_event, new_slo_state)
    state = new_slo_state()
    sketches: Dict[str, QuantileSketch] = {}
    last_ts = 0.0
    for rec in events:
        if rec.get("event") != "queryEnd":
            continue
        ts = float(rec.get("ts") or 0.0)
        last_ts = max(last_ts, ts)
        tenant = str(rec.get("tenant") or "default")
        wall = rec.get("durationMs")
        bad = (not rec.get("ok")
               or (wall is not None and float(wall) > target_ms))
        fold_slo_event(state, tenant=tenant, ts=ts, bad=bad,
                       long_window_s=long_window_s)
        if wall is not None and float(wall) > 0:
            sketches.setdefault(tenant, QuantileSketch()).observe(
                float(wall))
    tenants = {}
    for tenant in sorted(state):
        t = state[tenant]
        sk = sketches.get(tenant)
        p50, p95, p99 = (sk.quantiles((0.5, 0.95, 0.99))
                         if sk is not None else (0.0, 0.0, 0.0))
        tenants[tenant] = {
            "good": t["good"], "bad": t["bad"],
            "burn": {
                "short": round(burn_rate(
                    t, now=last_ts, window_s=short_window_s,
                    objective=objective), 4),
                "long": round(burn_rate(
                    t, now=last_ts, window_s=long_window_s,
                    objective=objective), 4)},
            "errorBudgetRemaining": round(
                budget_remaining(t, objective=objective), 4),
            "p50Ms": round(p50, 3), "p95Ms": round(p95, 3),
            "p99Ms": round(p99, 3)}
    return {"targetMs": target_ms, "objective": objective,
            "windows": {"shortS": short_window_s,
                        "longS": long_window_s},
            "tenants": tenants}


def format_slo(report: dict, source: str = "") -> str:
    lines = [f"== SLO replay ({source or 'event log'}): "
             f"target {report['targetMs']:g} ms, "
             f"objective {report['objective']:g} ==",
             f"{'tenant':<12} {'good':>6} {'bad':>6} {'burn_s':>8} "
             f"{'burn_l':>8} {'budget':>7} {'p50 ms':>10} "
             f"{'p95 ms':>10} {'p99 ms':>10}"]
    for tenant in sorted(report.get("tenants") or {}):
        t = report["tenants"][tenant]
        lines.append(
            f"{tenant:<12} {t['good']:>6} {t['bad']:>6} "
            f"{t['burn']['short']:>8.2f} {t['burn']['long']:>8.2f} "
            f"{t['errorBudgetRemaining']:>7.3f} {t['p50Ms']:>10.1f} "
            f"{t['p95Ms']:>10.1f} {t['p99Ms']:>10.1f}")
    return "\n".join(lines) + "\n"


def summarize_metrics_file(path: str) -> str:
    """Render the KEY_METRICS series of a JSON snapshot artifact."""
    with open(path, encoding="utf-8") as f:
        doc = json.load(f)
    snap = doc.get("snapshot", doc)
    lines = [f"== Metrics snapshot ({os.path.basename(path)}) =="]
    for name in KEY_METRICS:
        ent = snap.get(name)
        if not ent:
            continue
        for s in ent.get("series", []):
            labels = s.get("labels") or {}
            ltxt = ("{" + ",".join(f"{k}={v}" for k, v
                                   in sorted(labels.items())) + "}"
                    if labels else "")
            if ent.get("kind") == "histogram":
                lines.append(f"{name}{ltxt} count={s.get('count')} "
                             f"sum={s.get('sum')}")
            else:
                lines.append(f"{name}{ltxt} {s.get('value')}")
    return "\n".join(lines) + "\n"


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m spark_rapids_tpu.tools.history",
        description="Render / diff spark-rapids-tpu query event logs "
                    "(docs/monitoring.md).")
    ap.add_argument("log", nargs="?", help="event-log directory or file")
    ap.add_argument("--slowest", type=int, metavar="N",
                    help="top-N slowest queries")
    ap.add_argument("--diff", metavar="OTHER",
                    help="regression diff against OTHER log (this log "
                         "is the baseline)")
    ap.add_argument("--metrics-file", metavar="SNAP",
                    help="summarize a JSON metrics-snapshot artifact")
    ap.add_argument("--slo", type=float, metavar="TARGET_MS",
                    help="replay the log through the SLO fold with this "
                         "latency target (ms) and render per-tenant "
                         "burn rates, budget and p50/p95/p99")
    ap.add_argument("--slo-objective", type=float, default=0.99,
                    metavar="FRAC",
                    help="availability objective for --slo "
                         "(default 0.99)")
    ap.add_argument("--json", action="store_true",
                    help="machine-readable output")
    args = ap.parse_args(argv)
    if args.metrics_file:
        if args.json:
            with open(args.metrics_file, encoding="utf-8") as f:
                print(json.dumps(json.load(f), sort_keys=True))
        else:
            print(summarize_metrics_file(args.metrics_file), end="")
        return 0
    if not args.log:
        ap.error("an event-log path is required (or --metrics-file)")
    events, skipped = load_events(args.log)
    history = build_history(events)
    if args.slo is not None:
        report = slo_replay(events, target_ms=args.slo,
                            objective=args.slo_objective)
        if args.json:
            print(json.dumps(report, sort_keys=True))
        else:
            print(format_slo(report, source=args.log), end="")
        return 0
    if args.diff:
        other_events, _ = load_events(args.diff)
        other = build_history(other_events)
        rows = diff_histories(history, other)
        if args.json:
            print(json.dumps(rows, sort_keys=True))
        else:
            print(format_diff(rows, args.log, args.diff), end="")
        return 0
    if args.slowest:
        if args.json:
            timed = [q for q in history
                     if q.get("durationMs") is not None]
            timed.sort(key=lambda q: (-float(q["durationMs"]),
                                      str(q.get("queryId"))))
            print(json.dumps(timed[:args.slowest], sort_keys=True))
        else:
            print(format_slowest(history, args.slowest), end="")
        return 0
    if args.json:
        print(json.dumps({"history": history, "skipped": skipped},
                         sort_keys=True))
    else:
        print(format_history(history, skipped, source=args.log), end="")
    return 0
