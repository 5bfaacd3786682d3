"""perfbench/run.py — one cell of BENCHMARK.json, once.

    python3 perfbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process, one cell. It fails before it imports the engine when JAX
reports no TPU or another device count than the cell's ``chips``; there is
no CPU fallback (``--rehearsal-rows N`` asks for a rehearsal by name: tiny
size, any platform, output labelled as such and WITHOUT metrics).

Set-up (``setup_s``: process start to the first query of the window):
data from ``--seed`` in threads while the chip is found, ONE ``TpuSession``
with the configuration's conf, the views, and the cell's query until a
repeat compiles nothing. The window is the traffic mix's loop (a closed
loop of one client issuing ``session.sql(text).collect_arrow()``: no query
starts after ``--seconds`` and the window ends when the last one has
returned). Every result of the run is compared with the plain reference
(computed after the window, in children, from the seed) and every query's
placement is read: a query that left the device counts as failed.
``--control float32`` puts the reference computed in that precision in the
program's place: such a run has to come out as not correct.

Everything that belongs to one configuration, traffic mix, loop, source
kind, query or per-layer metric is a file of its own, found by the name in
BENCHMARK.json or in the cell's files; this file holds none of their names
(README.md).
"""
from __future__ import annotations

import time

_T0 = time.perf_counter()     # process start, as near as Python gives it

import argparse    # noqa: E402
import contextlib  # noqa: E402
import io          # noqa: E402
import json        # noqa: E402
import os          # noqa: E402
import shutil      # noqa: E402
import sys         # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)
sys.path.insert(1, ROOT)

import datagen     # noqa: E402  (no JAX, no engine)

#: placement codes that mean "the plan, or part of it, left the device"
#: (copied from chip_smoke.py)
HOST_REVERT_CODES = ("WHOLE_PLAN_HOST_REVERT", "COST_MODEL_HOST",
                     "OOM_PRESSURE_HOST")
#: runs allowed before a query must repeat compile-free: cold, one repeat
#: that may still compile (a join switches to its fused kernel once the
#: first run has measured its output size), warm
MAX_RUNS_TO_WARM = 3
SCRATCH_TRACE = os.path.join(ROOT, "bench_traces", "perfbench")


def say(*a):
    print(*a, flush=True)


class BenchFailure(Exception):
    """The run cannot produce a result (wrong device, bad manifest, a
    query still compiling after warm-up)."""


# ---------------------------------------------------------------------------
# the manifest and the cell's files
# ---------------------------------------------------------------------------

def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_cell(name: str) -> dict:
    manifest = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cells = {w["name"]: w for w in manifest["workloads"]}
    if name not in cells:
        raise BenchFailure(f"no workload {name!r} in BENCHMARK.json; "
                           f"it has {sorted(cells)}")
    cell = cells[name]
    conf_entry = {c["name"]: c for c in manifest["configs"]}[cell["config"]]
    config = load_json(os.path.join(ROOT, conf_entry["file"]))
    traffic = load_json(os.path.join(HERE, "traffic",
                                     cell["traffic"] + ".json"))
    query = traffic["query"]
    with open(os.path.join(HERE, "queries", query + ".sql")) as f:
        text = f.read().strip()
    check = load_json(os.path.join(HERE, "queries", query + ".json"))

    def reported(metric):
        return "workloads" not in metric or name in metric["workloads"]

    return {"name": name, "chips": int(cell["chips"]), "config": config,
            "traffic": traffic, "query": query, "text": text,
            "check": check,
            "loop": datagen.load_module("loops", traffic["loop"]),
            "source": datagen.load_module("sources", traffic["source"]),
            "end_to_end": [m for m in manifest["end_to_end"] if reported(m)],
            "per_layer": [m for m in manifest["per_layer"] if reported(m)]}


# ---------------------------------------------------------------------------
# the device
# ---------------------------------------------------------------------------

def require_device(chips: int, rehearsal: bool) -> dict:
    import jax
    devs = jax.devices()
    d = {"platform": devs[0].platform, "kind": devs[0].device_kind,
         "count": len(devs)}
    if rehearsal:
        return d
    if d["platform"] != "tpu":
        raise BenchFailure(f"no TPU: jax reports {d}")
    if d["count"] != chips:
        raise BenchFailure(f"the cell needs {chips} chip(s), jax reports {d}")
    return d


def memory_peak_bytes() -> int:
    import jax
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.devices()]
    return int(max(peaks))


# ---------------------------------------------------------------------------
# set-up: data, session, views
# ---------------------------------------------------------------------------

def query_columns(cell: dict, tables: dict) -> dict:
    """table -> the columns of it that the query's text names: what a run
    generates and registers, and what the reference reads."""
    import roofline
    return roofline.query_columns(cell["text"], tables)


def engine_conf(cell: dict) -> dict:
    """What keeps a run inside its checkout and lets placement be read,
    then the configuration's conf, then the traffic mix's overrides: a
    cell's own files have the last word."""
    conf = {
        "spark.rapids.tpu.memory.spillDir":
            os.path.join(ROOT, "bench_metrics", "perfbench_spill"),
        "spark.rapids.tpu.distributed.enabled": cell["chips"] > 1,
        "spark.rapids.tpu.metrics.enabled": True,
        "spark.rapids.tpu.metrics.sample.intervalMs": 0,
    }
    conf.update(cell["config"].get("conf", {}))
    conf.update(cell["traffic"].get("conf", {}))
    return conf


def open_session(cell: dict, tables: dict, seed: int, columns: dict,
                 fact_table):
    from spark_rapids_tpu.api import TpuSession
    config = cell["config"]
    session = TpuSession(engine_conf(cell))
    views = datagen.dimension_tables(config, tables, seed, columns)
    views[config["fact"]] = fact_table
    for name, table in views.items():
        cell["source"].register(session, name, table, tables[name])
    return session


# ---------------------------------------------------------------------------
# one query, and what it left behind
# ---------------------------------------------------------------------------

class Compiles:
    """Compilations seen by the process: the engine's executable-cache
    misses plus the requests jax's persistent cache could not serve
    (``compile_s`` also covers the READ of a persistent hit, so it is
    not used; PERF.md, PR 21)."""

    def __init__(self):
        from jax import monitoring
        self.requests = 0
        monitoring.register_event_listener(self._on_event)

    def _on_event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/compile_requests_use_cache":
            self.requests += 1

    def snapshot(self) -> dict:
        from spark_rapids_tpu.plan import exec_cache
        st = exec_cache.stats()
        return {"misses": st["misses"],
                "persistent_misses": self.requests - st["persistent_hits"]}

    def since(self, before: dict) -> int:
        now = self.snapshot()
        return int(sum(now[k] - before[k] for k in now))


def left_device(session) -> str:
    """Why the last query does not count as run on the device ('' if it
    does)."""
    rep = session.last_placement_report or {}
    if session.last_placement != "device":
        return f"last_placement={session.last_placement!r} report={rep}"
    bad = [c for c in HOST_REVERT_CODES if c in (rep.get("codes") or {})]
    return f"host-revert codes {bad}" if bad else ""


def run_query(session, text: str, records: list, annotate=None) -> float:
    """One client call: ``sql()`` to the Arrow result. Appends (start_ns,
    end_ns, result, why-not-on-device) and returns the seconds."""
    null = contextlib.nullcontext
    t0 = time.perf_counter_ns()
    with (annotate("perfbench.sql") if annotate else null()):
        df = session.sql(text)
    with (annotate("perfbench.collect") if annotate else null()):
        res = df.collect_arrow()
    t1 = time.perf_counter_ns()
    records.append((t0, t1, res, left_device(session)))
    return (t1 - t0) / 1e9


def oom_host_fallbacks() -> int:
    from spark_rapids_tpu.metrics.registry import active_registry
    reg = active_registry()
    if reg is None:
        raise BenchFailure("the engine's metric registry is not installed")
    ent = reg.snapshot().get("srtpu_oom_host_fallback_total")
    return int(sum(s["value"] for s in ent["series"])) if ent else 0


def plan_text(session, text: str) -> str:
    with contextlib.redirect_stdout(io.StringIO()):
        return session.sql(text).explain()


def loop(cell: dict, session, seconds: float, records: list,
         annotate=None) -> float:
    """The traffic mix's loop for ``seconds``; returns the elapsed seconds
    when its last query has returned."""
    return cell["loop"].run(
        lambda: run_query(session, cell["text"], records, annotate),
        seconds, cell["traffic"])


# ---------------------------------------------------------------------------
# the traced parts
# ---------------------------------------------------------------------------

def profiled_part(cell: dict, session, seconds: float, records: list):
    """Part A: engine tracer off, jax.profiler on for whole queries of the
    steady loop."""
    import jax

    import trace_reduce
    shutil.rmtree(SCRATCH_TRACE, ignore_errors=True)
    os.makedirs(SCRATCH_TRACE)
    n0 = len(records)
    jax.profiler.start_trace(
        SCRATCH_TRACE, profiler_options=trace_reduce.profiler_options())
    try:
        with jax.profiler.TraceAnnotation(trace_reduce.WINDOW_ANNOTATION):
            elapsed = loop(cell, session, seconds, records,
                           annotate=jax.profiler.TraceAnnotation)
    finally:
        jax.profiler.stop_trace()
    loaded = trace_reduce.load(trace_reduce.find_xplane(SCRATCH_TRACE))
    say(f"[trace] planes and lines: {json.dumps(loaded['lines'])}")
    prof = trace_reduce.reduce(loaded)
    if prof is not None:
        prof["queries"] = len(records) - n0
        prof["loop_s"] = elapsed
    return prof


def engine_traced_part(session, text: str, queries: int, records: list):
    """Part B: profiler off, the engine's own tracer installed for a few
    queries (it makes every upload block, so it feeds no device metric)."""
    from spark_rapids_tpu import trace
    tracer = trace.Tracer(max_events=1 << 21, proc_name="perfbench")
    n0 = len(records)
    trace.install_tracer(tracer)
    try:
        t0 = time.perf_counter()
        for _ in range(queries):
            run_query(session, text, records)
        elapsed = time.perf_counter() - t0
    finally:
        trace.install_tracer(None)
    events, dropped = tracer.export_events()
    return {"events": events, "dropped": dropped, "loop_s": elapsed,
            "queries": [(r[0], r[1]) for r in records[n0:]]}


# ---------------------------------------------------------------------------
# correct
# ---------------------------------------------------------------------------

def judge(cell: dict, answers: list, want) -> dict:
    """Every answer of the run (pandas frames) against the reference: for
    each number compared, the worst reading and its limit."""
    ref = datagen.load_module("references", cell["check"]["reference"])
    limits = cell["check"]["limits"]
    worst = {}
    for got in answers:
        for name, value in ref.compare(cell["query"], got, want).items():
            if name not in limits:
                raise BenchFailure(f"no limit for {name!r} in the query's "
                                   f"file: {sorted(limits)}")
            if not value <= worst.get(name, -1.0):
                # NaN counts as the worst reading there is
                worst[name] = value if value == value else 1e300
    return {name: {"value": worst[name], "limit": limits[name]}
            for name in sorted(worst)}


# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), required=True)
    ap.add_argument("--rehearsal-rows", type=int, default=0,
                    help="REHEARSAL: this many fact rows, any platform, "
                         "no metrics printed")
    ap.add_argument("--control", default="",
                    help="CONTROL: judge the reference computed in this "
                         "precision (float32) in the program's place")
    args = ap.parse_args(argv)
    rehearsal = args.rehearsal_rows > 0
    try:
        return run(args, rehearsal)
    except BenchFailure as e:
        print(f"perfbench: {e}", file=sys.stderr, flush=True)
        return 2


def run(args, rehearsal: bool) -> int:
    cell = load_cell(args.workload)
    config = cell["config"]
    fact = config["fact"]
    fact_rows = args.rehearsal_rows if rehearsal \
        else int(config["tables"][fact]["rows"])
    tables = datagen.scaled_tables(config, fact_rows)
    # learned walls of an earlier run must never steer this one
    # (plan/stats_store.py keeps them on disk and they overrule the model)
    os.environ["SRTPU_STATS_PERSIST"] = "0"

    if not os.path.isdir(os.path.join(ROOT, "spark_rapids_tpu")):
        raise BenchFailure(f"the program is not in this checkout: {ROOT}")

    # ---- set-up: threads generate while the main thread finds the chip
    t = time.perf_counter()
    columns = query_columns(cell, tables)
    making = datagen.FactTable(config, tables, args.seed, columns)
    session = None
    try:
        device = require_device(cell["chips"], rehearsal)
        say(f"[device] {device} seed={args.seed} cell={cell['name']}"
            + (" REHEARSAL: not a measurement" if rehearsal else ""))
        say(f"[setup] chip found {time.perf_counter() - _T0:.3f}s after "
            f"process start")
        fact_table = making.result()
        say(f"[setup] {fact}: {fact_rows} rows x {columns[fact]} "
            f"({fact_table.nbytes} bytes) ready "
            f"{time.perf_counter() - t:.3f}s after the threads started")
        session = open_session(cell, tables, args.seed, columns, fact_table)
        facts = drive(args, cell, tables, session, device)
        # free the program's state, then the reference and the comparison
        session.close()
        session = None
        del fact_table
        result = conclude(args, cell, tables, columns, facts, rehearsal)
    finally:
        making.close()
        if session is not None:
            session.close()
        if "spark_rapids_tpu.metrics" in sys.modules:
            sys.modules["spark_rapids_tpu.metrics"].shutdown_metrics()
        shutil.rmtree(SCRATCH_TRACE, ignore_errors=True)
    for name, c in result["compared"].items():
        print(f"compared {name}={c['value']!r} limit={c['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)    # "compared" comes last in it
    return 0


def drive(args, cell, tables, session, device) -> dict:
    """Warm-up, the window, the traced parts and every query's placement:
    everything that needs the session."""
    text, traffic = cell["text"], cell["traffic"]
    compiles = Compiles()
    plan = plan_text(session, text)
    say(f"[plan]\n{plan.rstrip()}")

    # ---- warm-up: until a repeat compiles nothing
    records = []
    for i in range(MAX_RUNS_TO_WARM):
        before = compiles.snapshot()
        dt = run_query(session, text, records)
        n = compiles.since(before)
        say(f"[warm-up] run {i}: seconds={dt:.3f} compiles={n} "
            f"placement={session.last_placement}")
        if i > 0 and n == 0:
            break
    else:
        raise BenchFailure(f"still compiling after {MAX_RUNS_TO_WARM} runs")
    n_warm = len(records)
    first_query_s = (records[0][1] - records[0][0]) / 1e9

    # ---- the window
    before = compiles.snapshot()
    setup_s = time.perf_counter() - _T0
    say(f"[setup] setup_s={setup_s:.3f}")
    elapsed = loop(cell, session, args.seconds, records)
    window = records[n_warm:]
    compiles_in_window = compiles.since(before)
    peak = memory_peak_bytes()
    latencies = [(t1 - t0) / 1e9 for t0, t1, _, _ in window]
    rows = tables[cell["config"]["fact"]]["rows"]
    say(f"[window] queries={len(window)} elapsed_s={elapsed:.3f} "
        f"compiles={compiles_in_window} hbm_peak_bytes={peak}")
    ordered = sorted(latencies)
    say(f"[window] seconds per query: min={ordered[0]:.4f} "
        f"median={ordered[len(ordered) // 2]:.4f} max={ordered[-1]:.4f}"
        + (f" all={[round(x, 3) for x in latencies]}"
           if len(latencies) <= 24 else ""))
    from spark_rapids_tpu.config import TpuConf
    from spark_rapids_tpu.mem import MemoryManager
    say(f"[mem] MemoryManager max_device_used="
        f"{MemoryManager.get(TpuConf(engine_conf(cell))).stats()['max_device_used']}")

    # ---- the traced parts (their queries are judged too; they feed no
    # end-to-end metric)
    profile = spans = None
    if args.trace:
        tr = traffic.get("trace", {})
        profile = profiled_part(cell, session,
                                float(tr.get("profiler_seconds", 3)),
                                records)
        spans = engine_traced_part(session, text,
                                   int(tr.get("tracer_queries", 2)), records)
        untraced = elapsed / len(window)
        say(f"[trace] seconds per query: untraced {untraced:.4f}, "
            f"under the profiler "
            f"{profile['loop_s'] / profile['queries'] if profile else None}"
            f", under the engine's tracer "
            f"{spans['loop_s'] / len(spans['queries']):.4f} "
            f"(dropped spans: {spans['dropped']})")

    # ---- placement of every query of the run
    off_device = [why for _, _, _, why in records[n_warm:] if why]
    n_oom = oom_host_fallbacks()
    fallback_in_plan = "host_fallback=" in plan
    failed = len(off_device)
    if (n_oom or fallback_in_plan) and not failed:
        failed = len(records) - n_warm
    for why in sorted(set(off_device)):
        say(f"[placement] left the device: {why}")
    say(f"[placement] oom_host_fallbacks={n_oom} "
        f"host_fallback_in_plan={fallback_in_plan}")

    return {
        "query_text": text, "tables": tables, "device": device,
        "records": records, "n_warm": n_warm, "failed": failed,
        "window": {"queries": len(window), "elapsed_s": elapsed,
                   "compiles": compiles_in_window, "latencies_s": latencies,
                   "fact_rows": rows, "setup_s": setup_s},
        "first_query_s": first_query_s, "hbm_peak_bytes": peak,
        "profile": profile, "spans": spans,
    }


def conclude(args, cell, tables, columns, facts, rehearsal) -> dict:
    """The reference (made again from the seed, after the window), the
    comparison, and the metrics, each by its reader."""
    records, n_warm = facts.pop("records"), facts.pop("n_warm")
    failed, profile, device = facts.pop("failed"), facts["profile"], \
        facts["device"]
    check, query = cell["check"], cell["query"]
    t = time.perf_counter()
    want = datagen.reference_answer(cell["config"], tables, args.seed,
                                    columns, check["reference"], query)
    say(f"[reference] {time.perf_counter() - t:.3f}s")
    answers = [res.to_pandas() for _, _, res, _ in records]
    if args.control:
        # the control in the program's place: the same reference in the
        # precision below the configuration's
        ref = datagen.load_module("references", check["reference"])
        low = datagen.reference_answer(cell["config"], tables, args.seed,
                                       columns, check["reference"], query,
                                       args.control)
        answers = [ref.answer_frame(query, low)]
        say(f"[control] judging the {args.control} reference, not the "
            f"program's {len(records)} answers")
    compared = judge(cell, answers, want)
    correct = failed == 0 and all(c["value"] <= c["limit"]
                                  for c in compared.values())

    def read(name):
        return datagen.load_module("metrics", name).read(facts)

    if args.trace:
        # what the traffic mix requires of a traced run (a resident cell
        # uploads next to nothing), or the run is not correct
        for name, most in cell["traffic"].get("require_at_most",
                                              {}).items():
            got = read(name)
            say(f"[require] {name}={got} (at most {most})")
            correct = correct and got is not None and got <= most
    metrics = {}
    for m in cell["per_layer"] if args.trace else cell["end_to_end"]:
        value = read(m["name"])
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    dev = dict(device, memory_peak_bytes=facts["hbm_peak_bytes"])
    out = {"correct": bool(correct), "attempted": len(records) - n_warm,
           "failed": failed}
    if rehearsal:
        # a rehearsal never prints a number under a device metric's name
        out.update(rehearsal=True, metrics={},
                   counts={"window_queries": facts["window"]["queries"],
                           "compiles_in_window": facts["window"]["compiles"],
                           "metrics_read": sorted(metrics)})
    else:
        out["metrics"] = metrics
        if profile is not None:
            dev.update(busy_s=profile["busy_s"], window_s=profile["window_s"])
            out["breakdown"] = {"device_ops": profile["device_ops"],
                                "idle_gaps": profile["idle_gaps"]}
            say(f"[trace] idle by host state: "
                f"{profile['idle_by_host_state']}")
    out["device"] = dev
    out["queries_in_window"] = facts["window"]["queries"]
    out["compared"] = compared
    return out


if __name__ == "__main__":
    sys.exit(main())
