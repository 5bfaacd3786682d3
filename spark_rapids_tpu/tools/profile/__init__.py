"""Trace profiler: Chrome-trace JSON -> attribution report + tuning hints.

The reference ships a standalone profiling tool that turns Spark event
logs into per-exec time attribution and auto-tuner recommendations
(spark-rapids-tools qualification/profiling); this is its analog over
the engine's own trace artifacts (trace/ subsystem):

    python -m spark_rapids_tpu.tools.profile trace.json

Sections:
  * top operators by SELF time (interval nesting per pid/tid lane — a
    parent operator is not billed for the time its children ran);
  * transfer attribution: H2D/D2H bytes + time, dispatch vs device
    split (a device round trip is the unit of cost of a sync);
  * memory pressure: OOM retries/splits, spill time + bytes, device
    semaphore wait;
  * shuffle partitions: per-shuffle size histogram + skew detection;
  * recommendations in the spirit of the reference's auto-tuner
    (broadcast threshold, batch sizing, partition count).

Pure stdlib; deterministic output for a given trace (golden-tested).
"""
from __future__ import annotations

import json
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

__all__ = ["analyze", "analyze_file", "format_report", "self_times"]


# ---------------------------------------------------------------------------
# span math
# ---------------------------------------------------------------------------

def _spans(events: List[dict]) -> List[dict]:
    return [e for e in events if e.get("ph") == "X"]


def self_times(events: List[dict],
               cat: Optional[str] = "exec") -> Dict[str, dict]:
    """name -> {count, total_us, self_us}. Self time subtracts the time
    of spans nested INSIDE a span on the same (pid, tid) lane — children
    strictly contained in the parent interval — so a pipeline parent is
    not billed for its upstream's work."""
    lanes: Dict[Tuple, List[dict]] = defaultdict(list)
    for e in _spans(events):
        if cat is not None and e.get("cat") != cat:
            continue
        lanes[(e.get("pid"), e.get("tid"))].append(e)
    out: Dict[str, dict] = {}
    for lane in lanes.values():
        # by start asc, then duration desc: a parent sorts before the
        # children it contains even when they share a start timestamp
        lane.sort(key=lambda e: (e["ts"], -e.get("dur", 0)))
        stack: List[dict] = []            # enclosing spans, innermost last
        for e in lane:
            ts, dur = e["ts"], e.get("dur", 0)
            while stack and stack[-1]["ts"] + stack[-1].get("dur", 0) <= ts:
                stack.pop()
            if stack:                     # innermost enclosing span
                parent = stack[-1]
                parent["_child_us"] = parent.get("_child_us", 0.0) + dur
            stack.append(e)
        for e in lane:
            s = out.setdefault(e["name"], {"count": 0, "total_us": 0.0,
                                           "self_us": 0.0})
            s["count"] += 1
            s["total_us"] += e.get("dur", 0)
            s["self_us"] += max(0.0, e.get("dur", 0)
                                - e.pop("_child_us", 0.0))
    return out


def _sum_spans(events: List[dict], name_prefix: str,
               cat: Optional[str] = None) -> Tuple[int, float, int]:
    """(count, total_us, total_bytes) over X events whose name starts
    with ``name_prefix``."""
    n, us, nbytes = 0, 0.0, 0
    for e in _spans(events):
        if cat is not None and e.get("cat") != cat:
            continue
        if not e["name"].startswith(name_prefix):
            continue
        n += 1
        us += e.get("dur", 0)
        nbytes += int((e.get("args") or {}).get("bytes", 0))
    return n, us, nbytes


def _count_instants(events: List[dict], name: str) -> int:
    return sum(1 for e in events
               if e.get("ph") == "i" and e.get("name") == name)


# ---------------------------------------------------------------------------
# the analysis
# ---------------------------------------------------------------------------

def analyze(events: List[dict]) -> dict:
    """Structured analysis of a trace's events (Chrome-trace dicts with
    microsecond ts/dur, as written by trace/export.py)."""
    ops = self_times(events, cat="exec")
    top_ops = sorted(ops.items(),
                     key=lambda kv: (-kv[1]["self_us"], kv[0]))

    # an upload is ONE <label>.dispatch span (its enqueue; older traces
    # add a .device span for a wait the tracer forced); a fetch is ONE
    # d2h.*.transfer span (the blocking get), with a d2h.dispatch span
    # before it only where a pack kernel was enqueued. Count an H2D per
    # .dispatch and a D2H per .transfer; time from every span
    h2d_n = h2d_b = d2h_n = d2h_b = 0
    h2d_us = d2h_us = dispatch_us = device_us = 0.0
    for e in _spans(events):
        if e.get("cat") != "transfer":
            continue
        name, dur = e["name"], e.get("dur", 0)
        nbytes = int((e.get("args") or {}).get("bytes", 0))
        is_dispatch = name.endswith(".dispatch")
        if is_dispatch:
            dispatch_us += dur
        elif name.endswith(".device") or name.endswith(".transfer"):
            device_us += dur
        if name.startswith("h2d"):
            h2d_us += dur
            if is_dispatch:
                h2d_n += 1
                h2d_b += nbytes
        elif name.startswith("d2h"):
            d2h_us += dur
            if name.endswith(".transfer"):
                d2h_n += 1
                d2h_b += nbytes

    # compile attribution (ISSUE 6): cat="compile" spans come from the
    # executable cache — backend-compile walls via jax.monitoring plus
    # fused-kernel build spans. Cold queries are compile-bound; a warm
    # repeat should show ~0 here (srtpu_compile_* metrics agree).
    compile_n, compile_us, _ = _sum_spans(events, "compile.",
                                          cat="compile")

    retries = _count_instants(events, "oom.retry")
    splits = _count_instants(events, "oom.split")
    spill_n, spill_us, _ = _sum_spans(events, "spill.", cat="mem")
    spill_freed = sum(int((e.get("args") or {}).get("freed_bytes", 0))
                      for e in _spans(events)
                      if e["name"].startswith("spill."))
    sem_n, sem_us, _ = _sum_spans(events, "semaphore.wait", cat="sem")

    # shuffle: partition sizes from put spans (local + remote). Spans
    # carry the block id, so a RE-PUT of the same block — a re-executed
    # map task after fault recovery; the receiving store dedupes it —
    # is deduped here too instead of inflating the size histogram.
    parts: Dict[Tuple[int, int], int] = defaultdict(int)
    seen_bids: Dict[Tuple[int, int], set] = defaultdict(set)
    fetch_n, fetch_us, fetch_b = _sum_spans(events, "shuffle.fetch")
    put_n, put_us, put_b = _sum_spans(events, "shuffle.put")
    put_retries = fetch_retries = 0
    for e in _spans(events):
        a = e.get("args") or {}
        if e["name"] == "shuffle.put":
            put_retries += int(a.get("retries", 0))
            key = (a.get("shuffle", -1), a.get("part", -1))
            bid = a.get("bid")
            if bid is not None:
                if bid in seen_bids[key]:
                    continue
                seen_bids[key].add(bid)
            parts[key] += int(a.get("bytes", 0))
        elif e["name"] == "shuffle.fetch":
            fetch_retries += int(a.get("retries", 0))
    crc_rejects = _count_instants(events, "shuffle.crc_reject")

    shuffles: Dict[int, dict] = {}
    for (sid, _p), nbytes in parts.items():
        s = shuffles.setdefault(sid, {"parts": 0, "bytes": 0, "max": 0})
        s["parts"] += 1
        s["bytes"] += nbytes
        s["max"] = max(s["max"], nbytes)
    for s in shuffles.values():
        mean = s["bytes"] / max(1, s["parts"])
        s["mean"] = mean
        s["skew"] = (s["max"] / mean) if mean > 0 else 0.0

    # AQE decisions ride the trace as aqe.<kind> instants (ISSUE 19,
    # aqe/__init__.py AqeLog.record): count them by kind so the report
    # — and the skew recommendation — can tell whether the adaptive
    # layer already acted on what the histogram shows
    aqe: Dict[str, int] = defaultdict(int)
    for e in events:
        if e.get("ph") == "i" and str(e.get("name", "")).startswith("aqe."):
            aqe[e["name"][len("aqe."):]] += 1
    aqe = dict(aqe)

    total_exec_us = sum(v["self_us"] for v in ops.values())
    workers = sorted({(e.get("args") or {}).get("worker")
                      for e in events
                      if e.get("cat") == "task"
                      and (e.get("args") or {}).get("worker")})
    lanes = sorted({(e.get("pid"), e.get("tid")) for e in events
                    if e.get("ph") in ("X", "C", "i")})

    return {"top_ops": top_ops,
            "transfer": {"h2d": {"n": h2d_n, "us": h2d_us, "bytes": h2d_b},
                         "d2h": {"n": d2h_n, "us": d2h_us, "bytes": d2h_b},
                         "dispatch_us": dispatch_us,
                         "device_us": device_us,
                         "compile_n": compile_n,
                         "compile_us": compile_us},
            "memory": {"oom_retries": retries, "oom_splits": splits,
                       "spills": spill_n, "spill_us": spill_us,
                       "spill_freed_bytes": spill_freed,
                       "sem_waits": sem_n, "sem_wait_us": sem_us},
            "shuffle": {"shuffles": shuffles, "puts": put_n,
                        "put_us": put_us, "put_bytes": put_b,
                        "fetches": fetch_n, "fetch_us": fetch_us,
                        "fetch_bytes": fetch_b,
                        "put_retries": put_retries,
                        "fetch_retries": fetch_retries,
                        "crc_rejects": crc_rejects},
            "total_exec_us": total_exec_us,
            "workers": workers, "lanes": lanes,
            "aqe": aqe,
            "recommendations": _recommend(
                shuffles, retries, splits, spill_n, sem_us,
                total_exec_us, h2d_n, h2d_b, h2d_us, d2h_us,
                compile_us, aqe=aqe)}


#: thresholds for the recommendation rules (module-level so tests and
#: operators can see/tune what the advisor considers "pressure")
BROADCAST_THRESHOLD_BYTES = 10 * 1024 * 1024
SKEW_RATIO = 2.0
SKEW_MIN_BYTES = 1 << 20
SMALL_H2D_BYTES = 4 << 20


def _recommend(shuffles, retries, splits, spills, sem_us,
               total_exec_us, h2d_n, h2d_b, h2d_us, d2h_us,
               compile_us: float = 0.0,
               aqe: Optional[Dict[str, int]] = None) -> List[str]:
    recs: List[str] = []
    aqe = aqe or {}
    if total_exec_us > 0 and compile_us > 0.5 * total_exec_us:
        recs.append(
            f"compile time ({_ms(compile_us)}) rivals exec self time: "
            f"this is a COLD run — warm repeats should pay zero "
            f"(persistent executable tier, "
            f"spark.rapids.tpu.compile.cache.dir); if srtpu_compile_* "
            f"metrics show misses on repeats, a kernel key is unstable")
    for sid, s in sorted(shuffles.items()):
        if 0 < s["bytes"] <= BROADCAST_THRESHOLD_BYTES:
            recs.append(
                f"shuffle {sid} moved only {_fmt_bytes(s['bytes'])} "
                f"total: a broadcast join would skip this exchange "
                f"(raise spark.rapids.tpu.sql.autoBroadcastJoinThreshold "
                f"above {s['bytes']})")
        if s["skew"] >= SKEW_RATIO and s["max"] >= SKEW_MIN_BYTES:
            if aqe.get("skew_split"):
                # the adaptive layer already split this run's skewed
                # partitions; the histogram shows the PRE-split sizes
                recs.append(
                    f"shuffle {sid} is skewed: largest partition "
                    f"{_fmt_bytes(s['max'])} vs mean "
                    f"{_fmt_bytes(int(s['mean']))} ({s['skew']:.1f}x) — "
                    f"AQE split it at run time "
                    f"({aqe['skew_split']} skew_split decision(s)); "
                    f"tune spark.rapids.tpu.aqe.skew.threshold if the "
                    f"reduce is still imbalanced")
            else:
                recs.append(
                    f"shuffle {sid} is skewed: largest partition "
                    f"{_fmt_bytes(s['max'])} vs mean "
                    f"{_fmt_bytes(int(s['mean']))} "
                    f"({s['skew']:.1f}x) — enable "
                    f"spark.rapids.tpu.aqe.enabled so the runtime "
                    f"salt-splits it, or raise "
                    f"spark.rapids.tpu.sql.shuffle.partitions / salt "
                    f"the hot key")
    if retries + splits > 0 or spills > 0:
        recs.append(
            f"memory pressure ({retries} OOM retries, {splits} splits, "
            f"{spills} spills): lower "
            f"spark.rapids.tpu.sql.batchSizeBytes (or "
            f"agg.wideBatchRows) so batches fit the HBM budget without "
            f"retry churn")
    if h2d_n >= 8 and h2d_b and (h2d_b / h2d_n) < SMALL_H2D_BYTES:
        recs.append(
            f"{h2d_n} H2D transfers averaged "
            f"{_fmt_bytes(int(h2d_b / h2d_n))}: raise "
            f"spark.rapids.tpu.sql.batchSizeBytes / batchSizeRows to "
            f"amortize per-dispatch latency over wider batches")
    if total_exec_us > 0 and sem_us > 0.10 * total_exec_us:
        recs.append(
            f"device semaphore wait is "
            f"{100.0 * sem_us / total_exec_us:.0f}% of exec self time: "
            f"lower spark.rapids.tpu.sql.concurrentTpuTasks or widen "
            f"batches so fewer tasks contend")
    if (h2d_us + d2h_us) > 0 and total_exec_us > 0 \
            and (h2d_us + d2h_us) > total_exec_us:
        recs.append(
            "transfer time exceeds exec self time: the query is "
            "transfer-bound — prune columns earlier, enable ingest "
            "narrowing (columnar/transfer.py), or keep results on "
            "device (to_device_columns)")
    if not recs:
        recs.append("no pressure detected: the trace shows no OOM "
                    "retries, skewed shuffles, or transfer-bound phases")
    return recs


# ---------------------------------------------------------------------------
# formatting
# ---------------------------------------------------------------------------

def _fmt_bytes(n: int) -> str:
    for unit in ("B", "KiB", "MiB", "GiB"):
        if abs(n) < 1024 or unit == "GiB":
            return (f"{n} {unit}" if unit == "B"
                    else f"{n:.1f} {unit}")
        n /= 1024.0
    return f"{n:.1f} GiB"   # pragma: no cover


def _ms(us: float) -> str:
    return f"{us / 1000.0:.2f} ms"


def format_report(a: dict, source: str = "") -> str:
    L: List[str] = []
    L.append("spark-rapids-tpu profile" + (f" — {source}" if source else ""))
    L.append("=" * max(24, len(L[0])))
    L.append("")
    L.append(f"lanes: {len(a['lanes'])} (pid,tid) across "
             f"{len({p for p, _ in a['lanes']})} process(es)"
             + (f"; workers: {', '.join(map(str, a['workers']))}"
                if a["workers"] else ""))
    L.append("")
    L.append("== Top operators by self time ==")
    if a["top_ops"]:
        L.append(f"{'operator':<32} {'count':>6} {'total':>12} "
                 f"{'self':>12} {'self%':>6}")
        tot = a["total_exec_us"] or 1.0
        for name, s in a["top_ops"][:15]:
            L.append(f"{name:<32} {s['count']:>6} "
                     f"{_ms(s['total_us']):>12} {_ms(s['self_us']):>12} "
                     f"{100.0 * s['self_us'] / tot:>5.1f}%")
    else:
        L.append("(no exec spans in trace)")
    L.append("")
    t = a["transfer"]
    L.append("== Transfer (H2D / D2H) ==")
    L.append(f"H2D: {t['h2d']['n']} transfer(s), "
             f"{_fmt_bytes(t['h2d']['bytes'])}, {_ms(t['h2d']['us'])}")
    L.append(f"D2H: {t['d2h']['n']} transfer(s), "
             f"{_fmt_bytes(t['d2h']['bytes'])}, {_ms(t['d2h']['us'])}")
    L.append(f"host dispatch {_ms(t['dispatch_us'])} vs device/transfer "
             f"{_ms(t['device_us'])} vs compile "
             f"{_ms(t.get('compile_us', 0.0))} "
             f"({t.get('compile_n', 0)} compile span(s))")
    L.append("")
    m = a["memory"]
    L.append("== Memory pressure ==")
    L.append(f"OOM retries: {m['oom_retries']}, splits: {m['oom_splits']}")
    L.append(f"spills: {m['spills']} ({_ms(m['spill_us'])}, freed "
             f"{_fmt_bytes(m['spill_freed_bytes'])})")
    L.append(f"semaphore waits: {m['sem_waits']} ({_ms(m['sem_wait_us'])})")
    L.append("")
    sh = a["shuffle"]
    L.append("== Shuffle partitions ==")
    if sh["shuffles"]:
        L.append(f"{'shuffle':>7} {'parts':>6} {'total':>12} {'max':>12} "
                 f"{'mean':>12} {'skew':>6}")
        for sid in sorted(sh["shuffles"]):
            s = sh["shuffles"][sid]
            flag = "  <-- SKEW" if (s["skew"] >= SKEW_RATIO
                                    and s["max"] >= SKEW_MIN_BYTES) else ""
            L.append(f"{sid:>7} {s['parts']:>6} "
                     f"{_fmt_bytes(s['bytes']):>12} "
                     f"{_fmt_bytes(s['max']):>12} "
                     f"{_fmt_bytes(int(s['mean'])):>12} "
                     f"{s['skew']:>5.1f}x{flag}")
        L.append(f"puts: {sh['puts']} ({_fmt_bytes(sh['put_bytes'])}, "
                 f"{_ms(sh['put_us'])}, {sh['put_retries']} retries); "
                 f"fetches: {sh['fetches']} "
                 f"({_fmt_bytes(sh['fetch_bytes'])}, "
                 f"{_ms(sh['fetch_us'])}, {sh['fetch_retries']} retries); "
                 f"CRC rejects: {sh['crc_rejects']}")
    else:
        L.append("(no shuffle spans in trace)")
    if a.get("aqe"):
        # only when the trace carries aqe.<kind> instants — traces from
        # aqe-off runs (and pre-AQE goldens) render unchanged
        L.append("")
        L.append("== Adaptive execution decisions ==")
        for kind in sorted(a["aqe"]):
            L.append(f"{kind}: {a['aqe'][kind]}")
    L.append("")
    L.append("== Recommendations ==")
    for i, r in enumerate(a["recommendations"], 1):
        L.append(f"{i}. {r}")
    L.append("")
    return "\n".join(L)


def analyze_file(path: str) -> Tuple[dict, str]:
    from ...trace.export import load_chrome_trace
    events = load_chrome_trace(path)
    a = analyze(events)
    return a, format_report(a, source=path)


def main(argv: Optional[List[str]] = None) -> int:
    import argparse
    ap = argparse.ArgumentParser(
        prog="python -m spark_rapids_tpu.tools.profile",
        description="Analyze a spark-rapids-tpu Chrome-trace artifact")
    ap.add_argument("trace", help="trace JSON file (trace/export.py "
                                  "format, loads in Perfetto)")
    ap.add_argument("--json", action="store_true",
                    help="emit the structured analysis as JSON instead "
                         "of the text report")
    args = ap.parse_args(argv)
    a, report = analyze_file(args.trace)
    if args.json:
        print(json.dumps(a, indent=1, sort_keys=True, default=str))
    else:
        print(report)
    return 0
