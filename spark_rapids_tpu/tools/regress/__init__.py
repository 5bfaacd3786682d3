"""Regression replay + bench differ (ISSUE 15).

The offline half of the regression sentinel — the role the reference's
qualification/profiling CLIs play over Spark event logs, sharing ONE
code path with the live check:

* ``python -m spark_rapids_tpu.tools.regress LOG_DIR`` replays a query
  event log (metrics/events.py JSONL) through the sentinel's
  :func:`~spark_rapids_tpu.ops.sentinel.fold_record` — the exact fold
  the live sentinel runs per queryEnd — into a deterministic report of
  warm-digest slowdowns, device->host verdict flips and new rung-3+
  escalations, plus the final per-digest baselines;
* ``--bench BASE.json NEW.json`` diffs two summary artifacts of the
  user's own (a ``details`` map of rungs with ``speedup`` and
  ``placement``) into a one-line geomean/placement delta plus per-rung
  regressions. The repo's own speed record is ``perfbench/``.

Stdlib-only and deterministic: identical inputs render identical
bytes. Crash-truncated event-log lines are skipped and counted
(tools/history semantics).
"""
from __future__ import annotations

import argparse
import json
import os
from typing import Dict, List, Optional, Tuple

__all__ = ["replay_events", "format_replay", "load_bench", "diff_bench",
           "format_bench_delta", "main"]

#: per-rung speedup drop flagged by the bench differ
BENCH_REGRESSION_RATIO = 0.8


# ---------------------------------------------------------------------------
# event-log replay (the sentinel's fold, offline)
# ---------------------------------------------------------------------------

def _fold_records(events: List[dict]) -> List[dict]:
    """queryStart/queryEnd pairs -> sentinel fold records, in end
    order. Newer logs carry verdict/rung/compile on the END record;
    older ones fall back to the paired start's placement summary."""
    starts: Dict[Tuple[object, object], dict] = {}
    out: List[dict] = []
    for rec in events:
        kind = rec.get("event")
        if kind == "queryStart":
            starts[(rec.get("queryId"), rec.get("planDigest"))] = rec
        elif kind == "queryEnd":
            digest = rec.get("planDigest")
            if not digest:
                continue
            start = starts.pop((rec.get("queryId"), digest), None)
            verdict = rec.get("placementVerdict")
            if verdict is None:
                placement = ((rec.get("placement")
                              or (start or {}).get("placement")) or {})
                verdict = placement.get("verdict")
            out.append({"digest": digest,
                        "wallMs": rec.get("durationMs"),
                        "verdict": verdict,
                        "rung": rec.get("ladderRung") or 0,
                        "ok": bool(rec.get("ok")),
                        "compileS": rec.get("compileSeconds") or 0.0,
                        "queryId": rec.get("queryId")})
    return out


def replay_events(events: List[dict], *, wall_factor: float = 3.0,
                  min_samples: int = 3, window: int = 32,
                  tail_factor: float = 2.0) -> dict:
    """Replay an event log through the live sentinel's fold. Returns
    ``{"records", "regressions", "baselines"}`` — regressions in log
    order (each stamped with the queryId that tripped it), baselines
    the table a live sentinel would hold after the log."""
    from ...ops.sentinel import fold_record
    baselines: Dict[str, dict] = {}
    regressions: List[dict] = []
    records = _fold_records(events)
    for rec in records:
        regs = fold_record(baselines, rec, wall_factor=wall_factor,
                           min_samples=min_samples, window=window,
                           tail_factor=tail_factor)
        for r in regs:
            r["queryId"] = rec.get("queryId")
        regressions.extend(regs)
    return {"records": len(records), "regressions": regressions,
            "baselines": baselines}


def format_replay(result: dict, source: str = "",
                  skipped: int = 0) -> str:
    lines = [f"== Regression sentinel replay ({source or 'event log'}) ==",
             f"{result['records']} queryEnd record(s) folded, "
             f"{len(result['regressions'])} regression(s); "
             f"{skipped} undecodable line(s) skipped"]
    for r in result["regressions"]:
        kind = r["kind"]
        if kind == "warm_slowdown":
            detail = (f"wall {r['wallMs']:.1f} ms vs median "
                      f"{r['medianMs']:.1f} ms ({r['factor']}x)")
        elif kind == "verdict_flip":
            detail = f"{r['from']} -> {r['to']}"
        elif kind == "tail_regression":
            detail = (f"wall {r['wallMs']:.1f} ms vs p99 "
                      f"{r['p99Ms']:.1f} ms ({r['factor']}x)")
        else:
            detail = (f"rung {r['rung']} (baseline "
                      f"{r['baselineRung']})")
        lines.append(f"{kind.upper():<15} digest={r['digest']}  "
                     f"query={r.get('queryId')}  {detail}")
    lines.append("-- baselines --")
    lines.append(f"{'digest':<16}  {'medianMs':>10}  {'verdict':<7}  "
                 f"{'maxRung':>7}  n")
    from ...ops.sentinel import _median
    for digest in sorted(result["baselines"]):
        b = result["baselines"][digest]
        med = _median(b.get("walls") or [])
        lines.append(f"{digest:<16}  {med:>10.1f}  "
                     f"{b.get('verdict') or '?':<7}  "
                     f"{b.get('maxRung') or 0:>7}  {b.get('n')}")
    return "\n".join(lines) + "\n"


# ---------------------------------------------------------------------------
# bench-artifact differ
# ---------------------------------------------------------------------------

def load_bench(path: str) -> dict:
    """Normalize one BENCH artifact to ``{"geomean", "placement_counts",
    "details": {rung: {"speedup", "placement"}}}``. Accepts the raw
    summary JSON, a driver-captured ``{"parsed": ..., "tail":
    ...}`` wrapper, and (tail-only) the emitted metric lines."""
    with open(path, encoding="utf-8") as f:
        doc = json.load(f)
    return normalize_bench(doc)


def normalize_bench(doc: dict) -> dict:
    parsed = doc.get("parsed") if isinstance(doc.get("parsed"), dict) \
        else doc
    details = {}
    geomean = None
    placement_counts = None
    if isinstance(parsed, dict) and isinstance(parsed.get("details"),
                                               dict):
        for k, d in parsed["details"].items():
            if isinstance(d, dict) and d.get("speedup") is not None:
                details[k] = {"speedup": float(d["speedup"]),
                              "placement": d.get("placement")}
                # serving artifacts (SERVE_r02+) carry sketch-derived
                # per-tenant tail latencies; keep them round-trippable
                for q in ("p50Ms", "p95Ms", "p99Ms"):
                    if d.get(q) is not None:
                        details[k][q] = float(d[q])
        if parsed.get("geomean") is not None:
            geomean = float(parsed["geomean"])
        elif parsed.get("value") is not None:
            geomean = float(parsed["value"])
        if isinstance(parsed.get("placement_counts"), dict):
            placement_counts = {k: int(v) for k, v in
                                parsed["placement_counts"].items()}
    if not details and isinstance(doc.get("tail"), str):
        import re
        for m in re.finditer(
                r'\{"metric": "(\w+)_speedup", "value": ([\d.]+)',
                doc["tail"]):
            details[m.group(1)] = {"speedup": float(m.group(2)),
                                   "placement": None}
        m = re.search(r'"geomean": ([\d.]+)', doc["tail"])
        if m:
            geomean = float(m.group(1))
    if placement_counts is None:
        placement_counts = {}
        for d in details.values():
            p = d.get("placement")
            if p:
                placement_counts[p] = placement_counts.get(p, 0) + 1
    return {"geomean": geomean, "placement_counts": placement_counts,
            "details": details}


def diff_bench(base: dict, cur: dict) -> dict:
    """Deterministic delta between two normalized bench summaries:
    geomean shift, device/host placement tally shift, per-rung
    regressions (speedup below ``BENCH_REGRESSION_RATIO`` x base) and
    placement flips."""
    shared = sorted(set(base["details"]) & set(cur["details"]))
    regressions = []
    flips = []
    for k in shared:
        b, c = base["details"][k], cur["details"][k]
        if c["speedup"] < BENCH_REGRESSION_RATIO * b["speedup"]:
            regressions.append(
                {"rung": k, "base": round(b["speedup"], 3),
                 "now": round(c["speedup"], 3),
                 "ratio": round(c["speedup"] / b["speedup"], 3)
                 if b["speedup"] else None})
        if (b.get("placement") and c.get("placement")
                and b["placement"] != c["placement"]):
            flips.append({"rung": k, "from": b["placement"],
                          "to": c["placement"]})
    return {"geomean": {"base": base["geomean"], "now": cur["geomean"]},
            "placement_counts": {"base": base["placement_counts"],
                                 "now": cur["placement_counts"]},
            "shared_rungs": len(shared),
            "only_base": sorted(set(base["details"])
                                - set(cur["details"])),
            "only_new": sorted(set(cur["details"])
                               - set(base["details"])),
            "regressions": regressions,
            "placement_flips": flips}


def _fmt_geo(v) -> str:
    return "?" if v is None else f"{v:.3f}x"


def _fmt_counts(c: dict) -> str:
    return (f"{c.get('device', 0)}dev/{c.get('host', 0)}host"
            if c else "?")


def format_bench_delta(delta: dict, base_name: str = "base") -> str:
    """The one-line summary of a ``diff_bench`` delta."""
    g = delta["geomean"]
    pc = delta["placement_counts"]
    line = (f"delta vs {base_name}: geomean {_fmt_geo(g['base'])} -> "
            f"{_fmt_geo(g['now'])}, placement "
            f"{_fmt_counts(pc['base'])} -> {_fmt_counts(pc['now'])}, "
            f"{len(delta['regressions'])} regressed rung(s), "
            f"{len(delta['placement_flips'])} placement flip(s) "
            f"over {delta['shared_rungs']} shared rung(s)")
    if delta["regressions"]:
        worst = min(delta["regressions"],
                    key=lambda r: (r["ratio"] if r["ratio"] is not None
                                   else 0.0, r["rung"]))
        line += (f"; worst {worst['rung']} {worst['base']}x -> "
                 f"{worst['now']}x")
    if delta["placement_flips"]:
        f0 = delta["placement_flips"][0]
        line += f"; flip {f0['rung']} {f0['from']}->{f0['to']}"
    return line


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------

def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m spark_rapids_tpu.tools.regress",
        description="Replay a query event log through the regression "
                    "sentinel, or diff two BENCH_r*.json artifacts "
                    "(docs/ops.md).")
    ap.add_argument("log", nargs="?",
                    help="event-log directory or file to replay")
    ap.add_argument("--bench", nargs=2, metavar=("BASE", "NEW"),
                    help="diff two bench artifacts instead")
    ap.add_argument("--wall-factor", type=float, default=3.0,
                    help="warm_slowdown threshold (default 3.0)")
    ap.add_argument("--min-samples", type=int, default=3,
                    help="baselined walls before the slowdown check "
                         "engages (default 3)")
    ap.add_argument("--window", type=int, default=32,
                    help="rolling baseline window (default 32)")
    ap.add_argument("--tail-factor", type=float, default=2.0,
                    help="tail_regression threshold over the baselined "
                         "p99 (default 2.0)")
    ap.add_argument("--json", action="store_true",
                    help="machine-readable output")
    args = ap.parse_args(argv)
    if args.bench:
        base, new = args.bench
        delta = diff_bench(load_bench(base), load_bench(new))
        if args.json:
            print(json.dumps(delta, sort_keys=True))
        else:
            print(format_bench_delta(delta, os.path.basename(base)))
        return 1 if (delta["regressions"]
                     or delta["placement_flips"]) else 0
    if not args.log:
        ap.error("an event-log path is required (or --bench BASE NEW)")
    from ..history import load_events
    events, skipped = load_events(args.log)
    result = replay_events(events, wall_factor=args.wall_factor,
                           min_samples=args.min_samples,
                           window=args.window,
                           tail_factor=args.tail_factor)
    if args.json:
        print(json.dumps({"records": result["records"],
                          "regressions": result["regressions"],
                          "baselines": result["baselines"],
                          "skipped": skipped}, sort_keys=True))
    else:
        print(format_replay(result, source=args.log, skipped=skipped),
              end="")
    return 1 if result["regressions"] else 0
