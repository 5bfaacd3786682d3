"""Continuous-telemetry subsystem (metrics/ + tools/history, ISSUE 5).

Covers the registry core (disabled path is a no-op with a tested
overhead bound, no sampler thread when off), Prometheus exposition
(label escaping, histogram invariants), the 3-worker distributed
snapshot merge, the rotating event log (+ crash-truncated tail
tolerated by tools/history, deterministic regression diff), EXPLAIN
ANALYZE golden output, and the stale last_query_metrics fix."""
import json
import os
import re
import threading

import numpy as np
import pyarrow as pa
import pytest

from harness import OPERATOR_CONF, tpu_session
from spark_rapids_tpu.api import functions as F
from spark_rapids_tpu.metrics import (MetricRegistry, SAMPLER_THREAD_NAME,
                                      active_registry, install_metrics,
                                      merge_snapshots, metric_inventory,
                                      prometheus_text, registry_snapshot,
                                      sampler_thread, shutdown_metrics)

pytestmark = pytest.mark.filterwarnings("ignore::ResourceWarning")

FIXTURES = os.path.join(os.path.dirname(__file__), "fixtures")


def _small_table(n=2000, k=7):
    return pa.table({"k": pa.array(np.arange(n) % k),
                     "v": pa.array(np.arange(n, dtype=np.float64))})


# ---------------------------------------------------------------------------
# disabled path
# ---------------------------------------------------------------------------

def test_disabled_no_registry_no_sampler_thread():
    """With metrics off (the default) a query battery installs no
    registry and starts no sampler thread."""
    assert active_registry() is None
    s = tpu_session()
    df = (s.create_dataframe(_small_table()).group_by("k")
          .agg(F.sum(F.col("v")).with_name("sv")))
    assert df.collect_arrow().num_rows == 7
    assert df.filter(F.col("k") > 2).count() > 0
    assert active_registry() is None
    assert sampler_thread() is None
    assert not [t for t in threading.enumerate()
                if t.name == SAMPLER_THREAD_NAME]


def test_disabled_overhead_is_one_branch():
    """The instrumented-site pattern when disabled is a module-global
    load + branch (same bound style as the tracer's)."""
    import time
    from spark_rapids_tpu.metrics import registry as metrics_registry
    assert metrics_registry.REGISTRY is None
    n = 200_000

    def site_loop():
        acc = 0
        for _ in range(n):
            mr = metrics_registry.REGISTRY   # the instrumented pattern
            if mr is not None:
                mr.counter("srtpu_oom_retries_total").inc()  # pragma: no cover
            acc += 1
        return acc

    def bare_loop():
        acc = 0
        for _ in range(n):
            acc += 1
        return acc

    t0 = time.perf_counter(); site_loop(); site = time.perf_counter() - t0
    t0 = time.perf_counter(); bare_loop(); bare = time.perf_counter() - t0
    assert site < max(10 * bare, bare + 0.5), (site, bare)


def test_undeclared_metric_rejected():
    reg = MetricRegistry()
    with pytest.raises(KeyError):
        reg.counter("srtpu_not_in_the_inventory_total")
    with pytest.raises(TypeError):
        reg.gauge("srtpu_oom_retries_total")   # declared as a counter


# ---------------------------------------------------------------------------
# exposition
# ---------------------------------------------------------------------------

def test_prometheus_exposition_parses():
    reg = MetricRegistry()
    reg.counter("srtpu_queries_total", status="ok").inc(3)
    reg.counter("srtpu_queries_total", status='fa"il\\ed\n').inc()
    reg.gauge("srtpu_hbm_used_bytes").set(12345)
    h = reg.histogram("srtpu_query_seconds", buckets=(0.1, 1.0, 10.0))
    for v in (0.05, 0.5, 0.5, 5.0, 50.0):
        h.observe(v)
    txt = prometheus_text(reg.snapshot())
    lines = txt.splitlines()
    # HELP/TYPE headers present and typed correctly
    assert "# TYPE srtpu_queries_total counter" in lines
    assert "# TYPE srtpu_query_seconds histogram" in lines
    # label escaping: backslash, quote, newline
    esc = [l for l in lines if "fa\\\"il\\\\ed\\n" in l]
    assert esc, txt
    # histogram invariants: cumulative buckets, +Inf == count,
    # sum matches the observations
    def val(sub):
        return [float(l.rsplit(" ", 1)[1]) for l in lines
                if l.startswith(sub)]
    buckets = val("srtpu_query_seconds_bucket")
    assert buckets == sorted(buckets)          # cumulative
    assert buckets == [1.0, 3.0, 4.0, 5.0]     # le=.1,1,10,+Inf
    (count,) = val("srtpu_query_seconds_count")
    assert count == 5.0 == buckets[-1]
    (total,) = val("srtpu_query_seconds_sum")
    assert abs(total - 56.05) < 1e-9
    # every sample line parses as "name{labels} value"
    for l in lines:
        if l.startswith("#") or not l:
            continue
        assert re.match(r"^[a-zA-Z_:][a-zA-Z0-9_:]*(\{.*\})? \S+$", l), l


def test_snapshot_merge_stamps_worker_label():
    a, b = MetricRegistry(), MetricRegistry()
    a.counter("srtpu_oom_retries_total").inc(2)
    b.counter("srtpu_oom_retries_total").inc(5)
    merged = merge_snapshots({"worker-0": a.snapshot(),
                              "worker-1": b.snapshot()})
    series = merged["srtpu_oom_retries_total"]["series"]
    got = {s["labels"]["worker"]: s["value"] for s in series}
    assert got == {"worker-0": 2, "worker-1": 5}


def test_summary_exposition_quantiles_and_exemplar_parse():
    """The Summary kind renders as Prometheus quantile series plus
    _sum/_count, and an attached exemplar survives the wire in
    OpenMetrics syntax (`` # {labels} value ts``)."""
    reg = MetricRegistry()
    sm = reg.summary("srtpu_query_latency_seconds", tenant="a")
    for i in range(1, 101):
        sm.observe(i / 100.0)
    snap = reg.snapshot()
    # decorate the series the way ops/slo.decorate_snapshot does
    snap["srtpu_query_latency_seconds"]["series"][0]["exemplar"] = {
        "labels": {"trace_path": "/tmp/trace.json", "query_id": "7"},
        "value": 1.0, "ts": 1700000000.0}
    txt = prometheus_text(snap)
    lines = txt.splitlines()
    assert "# TYPE srtpu_query_latency_seconds summary" in lines
    for q in ("0.5", "0.95", "0.99"):
        ql = [l for l in lines
              if f'quantile="{q}"' in l and 'tenant="a"' in l]
        assert ql, (q, txt)
    p99 = [l for l in lines if 'quantile="0.99"' in l]
    assert abs(float(p99[0].rsplit(" ", 1)[1]) - 0.99) < 0.05
    count = [l for l in lines
             if l.startswith("srtpu_query_latency_seconds_count")]
    assert count and " # {" in count[0]
    m = re.match(r'^(\S+)\{(.*)\} (\S+) # \{(.*)\} (\S+) (\S+)$',
                 count[0])
    assert m, count[0]
    assert 'trace_path="/tmp/trace.json"' in m.group(4)
    assert float(m.group(3)) == 100.0


def test_summary_merge_is_deterministic_and_exact():
    """Three shard registries merged through merge_snapshots fold to
    EXACTLY the single-process sketch — bucket counts are integers, so
    distribution across workers cannot drift the quantiles."""
    from spark_rapids_tpu.metrics import QuantileSketch, fold_sketches
    rng = np.random.RandomState(5)
    vals = [float(v) for v in rng.lognormal(0.0, 1.0, 3000)]
    whole = MetricRegistry()
    shards = [MetricRegistry() for _ in range(3)]
    for i, v in enumerate(vals):
        whole.summary("srtpu_query_latency_seconds",
                      tenant="a").observe(v)
        shards[i % 3].summary("srtpu_query_latency_seconds",
                              tenant="a").observe(v)
    merged = merge_snapshots({f"worker-{i}": r.snapshot()
                              for i, r in enumerate(shards)})
    series = merged["srtpu_query_latency_seconds"]["series"]
    assert [s["labels"]["worker"] for s in series] == \
        ["worker-0", "worker-1", "worker-2"]
    folded = fold_sketches([s["sketch"] for s in series])
    want = QuantileSketch.from_json(
        whole.snapshot()["srtpu_query_latency_seconds"]
        ["series"][0]["sketch"])
    # integer bucket counts: the shard split cannot drift a quantile
    assert folded.bins == want.bins and folded.count == want.count
    assert folded.quantiles((0.5, 0.95, 0.99)) == \
        want.quantiles((0.5, 0.95, 0.99))


def test_per_metric_buckets_and_600s_ceiling():
    """srtpu_query_seconds carries its own inventory buckets topping at
    600s (the 60s default ceiling saturated on long queries), while
    explicit buckets= still win over the inventory."""
    from spark_rapids_tpu.metrics import metric_inventory
    reg = MetricRegistry()
    h = reg.histogram("srtpu_query_seconds", tenant="a")
    assert h.buckets[-1] == 600.0
    h.observe(300.0)                       # lands in a real bucket now
    snap = reg.snapshot()
    buckets = dict(snap["srtpu_query_seconds"]["series"][0]["buckets"])
    assert buckets[300.0] == 1 and buckets[120.0] == 0
    assert metric_inventory()["srtpu_query_seconds"]["buckets"][-1] \
        == 600.0
    # explicit buckets still beat the inventory (the PR-5 contract)
    reg2 = MetricRegistry()
    h2 = reg2.histogram("srtpu_query_seconds", buckets=(1.0, 2.0))
    assert h2.buckets == (1.0, 2.0)


def test_bounded_label_caps_cardinality():
    reg = MetricRegistry()
    seen = {reg.bounded_label("srtpu_digest_latency_seconds", "digest",
                              f"d{i}", cap=4) for i in range(10)}
    assert seen == {"d0", "d1", "d2", "d3", "other"}
    # identity is sticky for values admitted before the cap
    assert reg.bounded_label("srtpu_digest_latency_seconds", "digest",
                             "d2", cap=4) == "d2"


def test_registry_snapshot_samples_runtime_gauges():
    """One synchronous sample pass populates the hbm/spill/semaphore/
    shuffle gauges even with the sampler thread off."""
    reg = MetricRegistry()
    snap = registry_snapshot(reg)
    for name in ("srtpu_hbm_used_bytes", "srtpu_hbm_budget_bytes",
                 "srtpu_spill_store_host_bytes",
                 "srtpu_semaphore_queue_depth",
                 "srtpu_shuffle_block_store_bytes"):
        assert name in snap, name


# ---------------------------------------------------------------------------
# enabled single-process path
# ---------------------------------------------------------------------------

def test_enabled_query_counters_and_sampler():
    s = tpu_session({"spark.rapids.tpu.metrics.enabled": True,
                     "spark.rapids.tpu.metrics.sample.intervalMs": 50})
    df = (s.create_dataframe(_small_table()).group_by("k")
          .agg(F.sum(F.col("v")).with_name("sv")))
    assert df.collect_arrow().num_rows == 7
    reg = active_registry()
    assert reg is not None
    assert sampler_thread() is not None
    snap = registry_snapshot(reg)
    ok = [se for se in snap["srtpu_queries_total"]["series"]
          if se["labels"].get("status") == "ok"]
    assert ok and ok[0]["value"] >= 1
    hist = snap["srtpu_query_seconds"]["series"][0]
    assert hist["count"] >= 1
    assert snap["srtpu_hbm_budget_bytes"]["series"][0]["value"] > 0
    shutdown_metrics()
    assert sampler_thread() is None
    assert active_registry() is None


# ---------------------------------------------------------------------------
# distributed: 3 workers, merged snapshot
# ---------------------------------------------------------------------------

def test_three_worker_snapshot_merge(tmp_path):
    from spark_rapids_tpu.config import TpuConf
    from spark_rapids_tpu.shuffle.cluster import LocalCluster
    conf = TpuConf({"spark.rapids.tpu.metrics.enabled": True,
                    "spark.rapids.tpu.metrics.sample.intervalMs": 100})
    cl = LocalCluster(3, conf=conf)
    elog_dir = str(tmp_path / "elog")
    try:
        rng = np.random.RandomState(7)
        t = pa.table({"k": pa.array(rng.randint(0, 13, 9000)),
                      "v": pa.array(rng.uniform(0, 100, 9000))})
        s = tpu_session({"spark.rapids.tpu.eventLog.enabled": True,
                         "spark.rapids.tpu.eventLog.dir": elog_dir})
        df = (s.create_dataframe(t).group_by("k")
              .agg(F.sum(F.col("v")).with_name("sv"),
                   F.count_star().with_name("n")))
        got = cl.execute(df).to_pandas().sort_values("k") \
                .reset_index(drop=True)
        # fault_stats surfaced on the session by the cluster run (the
        # oracle collect below clears it again, by design)
        assert isinstance(s.last_fault_stats, dict)
        want = df.collect_arrow().to_pandas().sort_values("k") \
                 .reset_index(drop=True)
        np.testing.assert_allclose(got["sv"], want["sv"], rtol=1e-9)
        assert s.last_fault_stats is None   # driver-local query cleared it
        view = cl.metrics_snapshot()
        lanes = set(view["workers"])
        assert {"worker-0", "worker-1", "worker-2"} <= lanes, lanes
        assert "driver" in lanes
        # the cluster run appended a durable clusterQuery record
        from spark_rapids_tpu.tools.history import load_events
        events, _ = load_events(elog_dir)
        cq = [e for e in events if e.get("event") == "clusterQuery"]
        assert cq and "workers_lost" in cq[0]["faultStats"]
        txt = cl.prometheus_snapshot()
        for series in ("srtpu_hbm_used_bytes",
                       "srtpu_spill_store_host_bytes",
                       "srtpu_semaphore_queue_depth",
                       "srtpu_shuffle_block_store_bytes"):
            for w in ("worker-0", "worker-1", "worker-2"):
                pat = re.compile(
                    rf'^{series}\{{[^}}]*worker="{w}"[^}}]*\}} ',
                    re.M)
                assert pat.search(txt), (series, w)
        # workers actually shuffled: put bytes recorded somewhere
        put = [se["value"] for se in
               view["aggregate"]["srtpu_shuffle_put_bytes_total"]["series"]
               if se["labels"]["worker"].startswith("worker-")]
        assert sum(put) > 0
        # worker task-wall summaries (ISSUE 20): every worker lane
        # ships a serialized quantile sketch that survives the merge
        # and renders as quantile series in the merged exposition
        task_ent = view["aggregate"]["srtpu_worker_task_seconds"]
        assert task_ent["kind"] == "summary"
        task_series = [se for se in task_ent["series"]
                       if se["labels"]["worker"].startswith("worker-")]
        assert task_series, "no worker task summaries merged"
        assert all(se["count"] >= 1 and se["sketch"]["bins"]
                   for se in task_series)
        q_pat = re.compile(
            r'^srtpu_worker_task_seconds\{[^}]*quantile="0\.99"'
            r'[^}]*worker="worker-\d+"[^}]*\} ', re.M)
        assert q_pat.search(txt), "no worker p99 line in exposition"
    finally:
        cl.shutdown()
        shutdown_metrics()


# ---------------------------------------------------------------------------
# event log + history
# ---------------------------------------------------------------------------

def _run_queries(s, n):
    t = _small_table()
    for i in range(n):
        df = (s.create_dataframe(t).filter(F.col("v") > float(i))
              .group_by("k").agg(F.sum(F.col("v")).with_name("sv")))
        assert df.collect_arrow().num_rows == 7


def test_event_log_rotation_and_truncated_tail(tmp_path):
    from spark_rapids_tpu.tools.history import (build_history,
                                                load_events)
    d = str(tmp_path / "elog")
    s = tpu_session({"spark.rapids.tpu.eventLog.enabled": True,
                     "spark.rapids.tpu.eventLog.dir": d,
                     "spark.rapids.tpu.eventLog.rotate.maxBytes": 2048})
    _run_queries(s, 4)
    files = sorted(os.listdir(d))
    assert any(f.startswith("events-") for f in files), files
    # crash-truncate the active file's tail (created if the final write
    # rotated it away — a crash can land at any point in the cycle)
    with open(os.path.join(d, "events.jsonl"), "a") as f:
        f.write('{"event": "queryStart", "queryId": 99, "trunc')
    events, skipped = load_events(d)
    assert skipped == 1
    history = build_history(events)
    ok = [q for q in history if q["status"] == "ok"]
    assert len(ok) == 4
    # the queryEnd schema fields are present
    assert all(q["durationMs"] is not None for q in ok)
    assert all(q["planDigest"] for q in ok)
    assert ok[0]["metrics"] is not None
    assert "maxDeviceBytes" in ok[0]["metrics"]


def test_history_cli_and_diff(tmp_path, capsys):
    from spark_rapids_tpu.tools.history import main
    base, new = str(tmp_path / "a"), str(tmp_path / "b")
    for d, n in ((base, 2), (new, 3)):
        s = tpu_session({"spark.rapids.tpu.eventLog.enabled": True,
                         "spark.rapids.tpu.eventLog.dir": d})
        _run_queries(s, n)
    assert main([base]) == 0
    out = capsys.readouterr().out
    assert "== Query history" in out and "2 ok" in out
    assert main([new, "--slowest", "2"]) == 0
    assert "== Slowest 2 queries" in capsys.readouterr().out
    # diff is deterministic: same invocation twice, identical bytes
    assert main([base, "--diff", new]) == 0
    d1 = capsys.readouterr().out
    assert main([base, "--diff", new]) == 0
    d2 = capsys.readouterr().out
    assert d1 == d2
    assert "== Regression diff" in d1
    # every digest in both logs appears
    assert main([base, "--diff", new, "--json"]) == 0
    rows = json.loads(capsys.readouterr().out)
    matched = [r for r in rows if r.get("digest")]
    assert matched, rows
    assert all(r["baseMs"] > 0 and r["newMs"] > 0 for r in matched)


def test_failed_query_does_not_leave_stale_metrics():
    """Satellite fix: a query that raises BEFORE execution must not
    leave the previous run's last_query_metrics behind."""
    s = tpu_session()
    df = (s.create_dataframe(_small_table()).group_by("k")
          .agg(F.sum(F.col("v")).with_name("sv")))
    # a later driver-local query must not inherit a cluster run's
    # fault stats either (same staleness class)
    s.last_fault_stats = {"workers_lost": 1}
    assert df.collect_arrow().num_rows == 7
    assert s.last_query_metrics is not None
    assert s.last_fault_stats is None
    s.set_conf("spark.rapids.tpu.sql.mode", "explainOnly")
    with pytest.raises(RuntimeError):
        df.collect_arrow()
    assert s.last_query_metrics is None


# ---------------------------------------------------------------------------
# a query's metrics are its own, whatever the session's age (ISSUE 29)
# ---------------------------------------------------------------------------

def _dim_table(k=7):
    return pa.table({"k": pa.array(np.arange(k)),
                     "w": pa.array(np.arange(k, dtype=np.float64) + 1.0)})


def _global_aggregate(s, n):
    return (s.create_dataframe(_small_table(n)).filter(F.col("v") >= 100.0)
            .agg(F.sum(F.col("v")).with_name("sv")))


def _grouped_aggregate(s, n):
    return (s.create_dataframe(_small_table(n)).group_by("k")
            .agg(F.sum(F.col("v")).with_name("sv")))


def _broadcast_join(s, n):
    return (s.create_dataframe(_small_table(n))
            .join(s.create_dataframe(_dim_table()), on="k")
            .group_by("k")
            .agg(F.sum(F.col("v") * F.col("w")).with_name("sv")))


_SHAPES = {"global_aggregate": _global_aggregate,
           "grouped_aggregate": _grouped_aggregate,
           "broadcast_join": _broadcast_join}


def _plan_execs(physical):
    """Every operator of a physical plan, fused ones included."""
    out = [physical, *getattr(physical, "fused_ops", ())]
    for c in physical.children:
        out.extend(_plan_execs(c))
    return out


def _traced_query(df):
    """Run ``df`` under a tracer of its own; the ``query`` span's args."""
    from spark_rapids_tpu.trace import Tracer, install_tracer
    tr = install_tracer(Tracer())
    try:
        out = df.collect_arrow()
    finally:
        install_tracer(None)
    (query,) = [e for e in tr.snapshot()
                if e["ph"] == "X" and e["name"] == "query"]
    return out, query["args"]


@pytest.mark.parametrize("shape", sorted(_SHAPES))
def test_query_metrics_do_not_grow_with_session_age(shape):
    """300 queries in ONE session: the operators of
    ``last_query_metrics`` and the ``query`` span's ``metric_execs`` are
    the plan's own at query 1 and at query 300, and the last query's
    row counts are its own (it runs over another table than the 299
    before it)."""
    s = tpu_session(OPERATOR_CONF)
    make = _SHAPES[shape]
    df = make(s, 2000)
    plan_ids = [type(e).__name__ for e in _plan_execs(df._physical())]
    if shape == "broadcast_join":
        assert "BroadcastExchangeExec" in plan_ids, plan_ids

    def check(frame, scan_rows):
        out, qargs = _traced_query(frame)
        ops = dict(s.last_query_metrics["operators"])
        assert sorted(k.split("@")[0] for k in ops) == sorted(plan_ids)
        assert qargs["metric_execs"] == len(plan_ids) == len(ops)
        scans = sorted(v["numOutputRows"] for k, v in ops.items()
                       if k.startswith("InMemoryScanExec"))
        assert scans[-1] == scan_rows, ops
        assert all(v["numOutputBatches"] >= 1 for v in ops.values()
                   if "numOutputBatches" in v), ops
        return out

    first = check(df, 2000)
    for _ in range(298):
        df.collect_arrow()
    assert len(dict(s.last_query_metrics["operators"])) == len(plan_ids)
    # query 300 reads 3000 rows: a summary that still held an earlier
    # query's operators would show a 2000-row scan
    last = check(make(s, 3000), 3000)
    assert first.num_rows == last.num_rows
    # nothing of any query stayed behind on the session's own context
    ctx = s.exec_context()
    assert not ctx.metrics and not ctx._cleanups
    assert not ctx._broadcast_cache
    s.close()


def test_concurrent_queries_keep_their_own_metrics():
    """Two threads drive queries through ONE session: a query's start
    must not wipe the metrics of the other thread's query in flight —
    every finished query's operators carry non-zero batch counts."""
    s = tpu_session(OPERATOR_CONF)
    frames = [_grouped_aggregate(s, 2000), _broadcast_join(s, 3000)]
    wants = [len(_plan_execs(f._physical())) for f in frames]
    seen, errors = [[], []], []
    start = threading.Barrier(2)

    def drive(i):
        try:
            start.wait(timeout=60)
            for _ in range(40):
                frames[i].collect_arrow()
                # last_query_metrics is the SESSION's, so the other
                # thread may have replaced it: take whichever query it
                # is and hold it to that query's own plan below
                m = s.last_query_metrics
                if m is not None:
                    seen[i].append(dict(m["operators"]))
        except BaseException as e:  # noqa: BLE001 - reported below
            errors.append(e)

    threads = [threading.Thread(target=drive, args=(i,)) for i in (0, 1)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=300)
    assert not errors, errors
    assert seen[0] and seen[1]
    for ops in seen[0] + seen[1]:
        assert len(ops) in wants, ops
        batches = [v["numOutputBatches"] for v in ops.values()
                   if "numOutputBatches" in v]
        assert batches and all(b >= 1 for b in batches), ops
    s.close()


# ---------------------------------------------------------------------------
# EXPLAIN ANALYZE
# ---------------------------------------------------------------------------

def test_explain_analyze_golden(capsys):
    s = tpu_session()
    t = _small_table()
    df = (s.create_dataframe(t).filter(F.col("v") > 100.0)
          .with_column("w", F.col("v") * F.lit(2.0))
          .group_by("k").agg(F.sum(F.col("w")).with_name("sw"))
          .order_by("k").limit(5))
    out = df.explain("analyze")
    capsys.readouterr()                      # swallow the print
    norm = re.sub(r"\d+(?:\.\d+)?ms", "_ms", out)
    with open(os.path.join(FIXTURES, "explain_analyze_golden.txt")) as f:
        assert norm == f.read()
    # analyze EXECUTED the query: metrics from the run are live
    assert s.last_query_metrics is not None


def test_explain_analyze_self_time_bounds():
    s = tpu_session()
    df = (s.create_dataframe(_small_table()).group_by("k")
          .agg(F.sum(F.col("v")).with_name("sv")))
    out = df._explain_analyze()
    times = [float(m) for m in re.findall(r"time=(\d+\.\d+)ms", out)]
    selfs = [float(m) for m in re.findall(r"self=(\d+\.\d+)ms", out)]
    assert len(times) == len(selfs) >= 2
    assert all(sf <= tm + 1e-9 for tm, sf in zip(times, selfs))
    # root cumulative bounds every operator's self time sum-ish: the
    # root's time is the largest (children are pulled through it)
    assert times[0] == max(times)


# ---------------------------------------------------------------------------
# catalog / docs coherence
# ---------------------------------------------------------------------------

def test_inventory_covers_history_key_metrics():
    from spark_rapids_tpu.tools.history import KEY_METRICS
    inv = set(metric_inventory())
    missing = [n for n in KEY_METRICS if n not in inv]
    assert not missing, missing


def test_metrics_file_summary(tmp_path, capsys):
    from spark_rapids_tpu.tools.history import main
    reg = MetricRegistry()
    reg.counter("srtpu_oom_retries_total").inc(3)
    snap = registry_snapshot(reg)
    p = str(tmp_path / "m.json")
    with open(p, "w") as f:
        json.dump({"rung": "x", "snapshot": snap}, f, default=float)
    assert main(["--metrics-file", p]) == 0
    out = capsys.readouterr().out
    assert "srtpu_oom_retries_total 3" in out
    assert "srtpu_hbm_used_bytes" in out


def test_install_metrics_roundtrip():
    reg = MetricRegistry()
    assert install_metrics(reg) is reg
    assert active_registry() is reg
    install_metrics(None)
    assert active_registry() is None
