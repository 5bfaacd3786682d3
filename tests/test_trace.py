"""Tracing + profiling subsystem (trace/ + tools/profile, ISSUE 4).

Covers the tracer core (nesting, truncation, the disabled path being a
no-op), single-process query traces, the 3-worker distributed
trace-merge (driver + every worker on one timeline), and golden output
of the profile analyzer over a checked-in fixture trace."""
import json
import os

import numpy as np
import pyarrow as pa
import pytest

from harness import tpu_session
from spark_rapids_tpu.api import functions as F
from spark_rapids_tpu.trace import (Tracer, active_tracer, chrome_trace,
                                    install_tracer, load_chrome_trace,
                                    write_chrome_trace)

pytestmark = pytest.mark.filterwarnings("ignore::ResourceWarning")

FIXTURES = os.path.join(os.path.dirname(__file__), "fixtures")


# ---------------------------------------------------------------------------
# core
# ---------------------------------------------------------------------------

def test_span_nesting_parent_ids():
    tr = Tracer()
    with tr.span("outer"):
        with tr.span("inner"):
            pass
        with tr.span("inner2"):
            pass
    evs = tr.snapshot()
    by_name = {e["name"]: e for e in evs}
    assert by_name["outer"]["parent"] == 0
    assert by_name["inner"]["parent"] == by_name["outer"]["id"]
    assert by_name["inner2"]["parent"] == by_name["outer"]["id"]
    # children's intervals are contained in the parent's
    o = by_name["outer"]
    for c in ("inner", "inner2"):
        assert by_name[c]["ts"] >= o["ts"]
        assert (by_name[c]["ts"] + by_name[c]["dur"]
                <= o["ts"] + o["dur"])


def test_span_records_on_exception():
    tr = Tracer()
    with pytest.raises(ValueError):
        with tr.span("boom"):
            raise ValueError("x")
    assert [e["name"] for e in tr.snapshot()] == ["boom"]


def test_ring_buffer_truncation_counts_drops():
    tr = Tracer(max_events=16)
    for i in range(40):
        tr.instant(f"e{i}")
    evs = tr.snapshot()
    assert len(evs) == 16
    assert tr.dropped == 24
    # OLDEST events were dropped
    assert evs[0]["name"] == "e24" and evs[-1]["name"] == "e39"
    doc = chrome_trace(tr)
    assert doc["otherData"]["dropped_events"] == 24


def test_disabled_path_records_nothing():
    """With tracing off (the default) no tracer exists, instrumented
    sites see None and skip, and a full query leaves no global state."""
    assert active_tracer() is None
    t = pa.table({"k": pa.array(np.arange(500) % 5),
                  "v": pa.array(np.arange(500, dtype=np.float64))})
    s = tpu_session()
    out = (s.create_dataframe(t).group_by("k")
           .agg(F.sum(F.col("v")).with_name("sv"))).collect_arrow()
    assert out.num_rows == 5
    assert active_tracer() is None     # conf off -> never installed


def test_disabled_overhead_is_one_branch():
    """The record path when disabled is a module-global load + branch:
    time a tight loop over the exact site pattern and assert it stays
    within an order of magnitude of a bare loop (a generous bound —
    this guards against accidentally adding allocation/conf lookups to
    the disabled path, not against scheduler noise)."""
    import time
    from spark_rapids_tpu.trace import core as trace_core
    assert trace_core.TRACER is None
    n = 200_000

    def site_loop():
        acc = 0
        for _ in range(n):
            tr = trace_core.TRACER          # the instrumented pattern
            if tr is not None:
                tr.instant("x")             # pragma: no cover
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


def test_ingest_aligns_remote_clock_and_lanes():
    a, b = Tracer(), Tracer()
    b.proc_name = "worker-7"
    b.proc_names[b.pid] = "worker-7"
    b.epoch_ns = a.epoch_ns + 5_000_000_000   # worker clock 5s ahead
    t0 = b.now()
    b.complete("remote", t0, t0 + 1000)
    a.ingest(b.serialize())
    evs = a.snapshot()
    assert len(evs) == 1
    # the remote span was shifted onto A's monotonic timeline
    assert evs[0]["ts"] == t0 + 5_000_000_000
    assert a.proc_names[b.pid] == "worker-7"
    assert len(b.snapshot()) == 0              # serialize() drains


# ---------------------------------------------------------------------------
# single-process query trace
# ---------------------------------------------------------------------------

def test_query_trace_written_and_loadable(tmp_path):
    out_path = str(tmp_path / "q.json")
    t = pa.table({"k": pa.array(np.arange(2000) % 7),
                  "v": pa.array(np.arange(2000, dtype=np.float64))})
    s = tpu_session({"spark.rapids.tpu.trace.enabled": True,
                     "spark.rapids.tpu.trace.output": out_path})
    df = (s.create_dataframe(t).group_by("k")
          .agg(F.sum(F.col("v")).with_name("sv")))
    assert df.collect_arrow().num_rows == 7
    events = load_chrome_trace(out_path)
    phases = {e.get("ph") for e in events}
    assert "X" in phases and "M" in phases
    names = {e["name"] for e in events if e.get("ph") == "X"}
    assert "query" in names
    assert any(n.endswith("Exec") for n in names), names
    assert any(n.startswith("h2d.") for n in names), names
    # valid chrome trace: every X event has the required keys
    for e in events:
        if e.get("ph") == "X":
            assert {"name", "ts", "dur", "pid", "tid"} <= set(e)
    install_tracer(None)


# ---------------------------------------------------------------------------
# one tracer, two sinks: the profiler's clock, spans where the work happens
# ---------------------------------------------------------------------------

#: the operator pipeline (exec/), not the one-program fragment of parallel/
_OPERATOR_CONF = {"spark.rapids.tpu.sql.optimizer.enabled": False,
                  "spark.rapids.tpu.sql.fusedPipeline.enabled": False,
                  "spark.rapids.tpu.distributed.enabled": False}


def _fact(n=3000):
    return pa.table({"k": pa.array(np.arange(n) % 7),
                     "v": pa.array(np.arange(n, dtype=np.float64))})


def _grouped(s):
    return (s.create_dataframe(_fact()).group_by("k")
            .agg(F.sum(F.col("v")).with_name("sv")))


def _xs(tracer):
    return [e for e in tracer.snapshot() if e["ph"] == "X"]


def test_profiler_session_gets_engine_spans(tmp_path):
    """While a jax.profiler session runs — whoever started it — and no
    tracer is installed, a query annotates itself into the profiler's
    trace: its spans sit on the device trace's clock, properly nested,
    and nothing stays installed. Without a session nothing is installed
    at all."""
    import glob

    import jax
    from jax.profiler import ProfileData
    from spark_rapids_tpu.trace import core as trace_core
    s = tpu_session(_OPERATOR_CONF)
    s.create_temp_view("t", s.create_dataframe(_fact()))
    text = "select k, sum(v) as sv from t group by k"
    installs = []
    real = trace_core.Tracer

    class Counted(real):
        def __init__(self, *a, **kw):
            installs.append(kw)
            super().__init__(*a, **kw)

    trace_core.Tracer = Counted
    try:
        assert s.sql(text).collect_arrow().num_rows == 7   # no session
        assert installs == [] and trace_core.TRACER is None
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
        try:
            df = s.sql(text)
            assert trace_core.TRACER is None        # gone after sql() too
            assert df.collect_arrow().num_rows == 7
        finally:
            jax.profiler.stop_trace()
    finally:
        trace_core.Tracer = real
    assert trace_core.TRACER is None
    # one annotate-only tracer for sql(), one for the query
    assert installs == [{"proc_name": "driver", "recording": False}] * 2
    (path,) = glob.glob(str(tmp_path / "plugins" / "profile" / "*"
                            / "*.xplane.pb"))
    spans = [(e.name, e.start_ns, e.start_ns + e.duration_ns,
              dict(e.stats))
             for plane in ProfileData.from_file(path).planes
             if plane.name.startswith("/host:")
             for line in plane.lines for e in line.events
             if e.name.startswith("srtpu/")]
    names = [n for n, *_ in spans]
    assert "srtpu/plan/plan.sql" in names
    assert "srtpu/plan/plan.physical" in names
    assert any(n.startswith("srtpu/transfer/d2h") for n in names), names
    (query,) = [sp for sp in spans if sp[0] == "srtpu/query/query"]
    q = query[3]["q"]
    # every operator of the plan has its own exec annotation, by its id
    ops = {sp[3]["exec"] for sp in spans if sp[0].startswith("srtpu/exec/")}
    assert {o.split("@")[0] for o in ops} >= {"InMemoryScanExec",
                                              "TpuHashAggregateExec"}, ops
    for name, a, b, stats in spans:
        if name == "srtpu/plan/plan.sql":
            assert b <= query[1]                # sql() precedes the query
            continue
        assert query[1] <= a and b <= query[2], (name, a, b, query)
        assert stats["q"] == q, (name, stats)
    # properly nested: two spans are disjoint or one holds the other
    for i, (_, a, b, _) in enumerate(spans):
        for _, c, d, _ in spans[i + 1:]:
            assert b <= c or d <= a or (a <= c and d <= b) \
                or (c <= a and b <= d), (a, b, c, d)


#: four 1000-row partitions against a target of 2000: the planner fills
#: them into two batches (plan/overrides.insert_coalesce)
_COALESCE_CONF = {**_OPERATOR_CONF, "spark.rapids.tpu.sql.batchSizeRows": 2000}


def _coalesced(s):
    t = pa.table({"k": pa.array(np.arange(4000) % 7),
                  "v": pa.array(np.arange(4000, dtype=np.float64))})
    return (s.create_dataframe(t, num_partitions=4).group_by("k")
            .agg(F.sum(F.col("v")).with_name("sv")))


def test_coalesce_span_and_counter_in_the_ring_buffer():
    """Each concat is a ``coalesce.concat`` span, a ``cat="exec"`` child of
    its operator's span (so ``exec_host_s`` bills it once), and
    ``coalesce.batches`` says once an execution how many batches came and
    went."""
    s = tpu_session(_COALESCE_CONF)
    df = _coalesced(s)
    assert "CoalesceBatches[TargetSize(rows=2000" in \
        df._physical().tree_string()
    tr = install_tracer(Tracer())
    try:
        assert df.collect_arrow().num_rows == 7
    finally:
        install_tracer(None)
    counters = [e["args"] for e in tr.snapshot()
                if e["ph"] == "C" and e["name"] == "coalesce.batches"]
    (counter,) = counters
    # the scan's counts are host ints: no count is ever transferred
    assert {k: counter[k] for k in ("in", "out", "fetches")} == \
        {"in": 4, "out": 2, "fetches": 0}
    spans = _xs(tr)
    by_id = {e["id"]: e for e in spans}
    concats = [e for e in spans if e["name"] == "coalesce.concat"]
    assert len(concats) == 2
    for e in concats:
        assert e["cat"] == "exec" and e["args"]["n"] == 2
        parent = by_id[e["parent"]]
        assert parent["name"] == "CoalesceBatchesExec"
        assert parent["args"]["exec"] == e["args"]["exec"]
        assert parent["ts"] <= e["ts"] and \
            e["ts"] + e["dur"] <= parent["ts"] + parent["dur"]
    assert int(concats[0]["args"]["exec"].rsplit("@", 1)[1]) == counter["op"]
    # no fetch inside the operator: the concat stays on the device
    assert not [e for e in spans if e["name"].startswith("d2h")
                and by_id.get(e["parent"], {}).get("name")
                in ("coalesce.concat", "CoalesceBatchesExec")]


def test_coalesce_above_a_join_reads_its_counts_a_window_at_a_time():
    """Above a streaming broadcast join the counts are on the device: the
    operator reads them in ``d2h.coalesce_count`` transfers of its own
    span, at most one per 8 input batches, and ``coalesce.batches`` {in,
    out, fetches, op} tells the plan's operators apart; the scan-site
    operator of the same plan transfers nothing."""
    rng = np.random.RandomState(11)
    n, parts = 21 * 500, 42
    fact = pa.table({"fk": pa.array(rng.randint(0, 400, n)),
                     "v": pa.array(rng.rand(n))})
    dim = pa.table({"dk": pa.array(np.arange(0, 400, 8)),     # keeps 1/8
                    "g": pa.array((np.arange(50) % 3).astype(np.int32))})
    s = tpu_session({**_OPERATOR_CONF,
                     "spark.rapids.tpu.sql.batchSizeRows": 500})
    df = (s.create_dataframe(fact, num_partitions=parts)
          .join(F.broadcast(s.create_dataframe(dim)), on=[("fk", "dk")])
          .group_by("g").agg(F.sum(F.col("v")).with_name("sv")))
    tree = df._physical().tree_string()
    assert tree.count("CoalesceBatches[TargetSize(rows=500") == 2, tree
    want = df.collect_arrow()               # sizes the join's outputs
    tr = install_tracer(Tracer())
    try:
        got = df.collect_arrow()
    finally:
        install_tracer(None)
    assert got.sort_by("g").equals(want.sort_by("g"))
    spans = _xs(tr)
    by_id = {e["id"]: e for e in spans}
    ops = {int(e["args"]["exec"].rsplit("@", 1)[1]): e["name"]
           for e in spans if "exec" in (e.get("args") or {})}
    counters = {c["op"]: c for c in (
        e["args"] for e in tr.snapshot()
        if e["ph"] == "C" and e["name"] == "coalesce.batches")}
    assert len(counters) == 2 and all(
        ops[op] in ("CoalesceBatchesExec", "coalesce.concat")
        for op in counters)
    scan_site, join_site = sorted(counters.values(),
                                  key=lambda c: c["fetches"])
    assert (scan_site["in"], scan_site["out"], scan_site["fetches"]) == \
        (parts, parts // 2, 0)
    # 21 stream batches of 500 rows, an eighth of each kept: 3 windows
    assert join_site["in"] == parts // 2 and join_site["fetches"] == 3
    assert 8 * join_site["fetches"] <= join_site["in"] + 7
    # the goal on TRUE rows: a group leaves when the next batch would pass
    kept = (fact["fk"].to_numpy() % 8 == 0).reshape(21, 500).sum(axis=1)
    groups, rows = 1, 0
    for k in kept:
        if rows and rows + k > 500:
            groups, rows = groups + 1, 0
        rows += k
    assert join_site["out"] == groups < 5
    gets = [e for e in spans if e["name"] == "d2h.coalesce_count.transfer"]
    assert len(gets) == join_site["fetches"]
    for e in gets:
        owner = by_id[e["parent"]]
        assert owner["name"] == "CoalesceBatchesExec" and int(
            owner["args"]["exec"].rsplit("@", 1)[1]) == join_site["op"]
    # whatever else is fetched, no batch's count is fetched on its own
    assert not [e for e in spans if e["name"] == "d2h.num_rows.transfer"]


def test_coalesce_span_under_the_profilers_tracer(tmp_path):
    """Under a ``jax.profiler`` session the same span is an annotation
    ``srtpu/exec/coalesce.concat`` carrying its operator's id, inside that
    operator's annotation; the counter, which cannot be an annotation, is
    skipped and nothing fails."""
    import glob

    import jax
    from jax.profiler import ProfileData
    s = tpu_session(_COALESCE_CONF)
    df = _coalesced(s)
    assert df.collect_arrow().num_rows == 7          # compiles outside
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        assert df.collect_arrow().num_rows == 7
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "plugins" / "profile" / "*"
                            / "*.xplane.pb"))
    spans = [(e.name, e.start_ns, e.start_ns + e.duration_ns, dict(e.stats))
             for plane in ProfileData.from_file(path).planes
             if plane.name.startswith("/host:")
             for line in plane.lines for e in line.events
             if e.name.startswith("srtpu/exec/")]
    concats = [sp for sp in spans if sp[0] == "srtpu/exec/coalesce.concat"]
    ops = [sp for sp in spans if sp[0] == "srtpu/exec/CoalesceBatchesExec"]
    assert len(concats) == 2 and len(ops) == 3       # 2 batches + the end
    for _, a, b, stats in concats:
        assert stats["exec"].startswith("CoalesceBatchesExec@")
        assert any(c <= a and b <= d and st["exec"] == stats["exec"]
                   for _, c, d, st in ops)


def _joined(s, shuffled: bool):
    rng = np.random.default_rng(5)
    fact = pa.table({"k": pa.array(rng.integers(0, 400, 6000)),
                     "v": pa.array(rng.random(6000))})
    dim = pa.table({"dk": pa.array(np.arange(300)),
                    "w": pa.array(np.arange(300) % 9)})
    return (s.create_dataframe(fact, num_partitions=3)
            .join(s.create_dataframe(dim), on=[("k", "dk")], how="inner")
            .filter((F.col("v") > 0.25) & (F.col("w") < 7)))


@pytest.mark.parametrize("shuffled", [False, True])
def test_join_spans_and_counters_in_the_ring_buffer(shuffled):
    """``join.build`` (once a join a query) and ``join.probe`` (a stream
    batch each) are ``cat="exec"`` children of the join operator's span
    carrying its id and the query's ordinal; ``join.rows`` is written once
    an execution and ``plan.pushdown`` once a planned query."""
    conf = dict(_OPERATOR_CONF)
    if shuffled:
        conf["spark.rapids.tpu.sql.autoBroadcastJoinThreshold"] = 0
    s = tpu_session(conf)
    df = _joined(s, shuffled)
    want = df.collect_arrow().num_rows      # sizes the join's outputs
    tr = install_tracer(Tracer())
    try:
        assert df.collect_arrow().num_rows == want
    finally:
        install_tracer(None)
    ev = tr.snapshot()
    counters = {n: [e["args"] for e in ev if e["ph"] == "C"
                    and e["name"] == n]
                for n in ("join.rows", "plan.pushdown")}
    assert counters["plan.pushdown"] == [{"pushed": 2, "above_joins": 0}]
    (rows,) = counters["join.rows"]
    # the planner fills the broadcast join's three stream batches into one
    # (insert_coalesce); the join of two big sides takes them as they come
    parts = 3 if shuffled else 1
    assert (rows["build"], rows["out"], rows["parts"]) == (234, want, parts)
    assert 4000 < rows["stream"] < 5000         # v > 0.25 of 6000
    spans = _xs(tr)
    by_id = {e["id"]: e for e in spans}
    op = "TpuHashJoinExec" if shuffled else "TpuBroadcastHashJoinExec"
    (build,) = [e for e in spans if e["name"] == "join.build"]
    probes = [e for e in spans if e["name"] == "join.probe"]
    assert len(probes) == parts
    for e in [build] + probes:
        parent = by_id[e["parent"]]
        assert e["cat"] == "exec" and parent["name"] == op
        assert e["args"]["exec"] == parent["args"]["exec"]
        assert int(e["args"]["exec"].rsplit("@", 1)[1]) == rows["op"]
        assert e["q"] == parent["q"] and e["q"] is not None
        assert parent["ts"] <= e["ts"] and \
            e["ts"] + e["dur"] <= parent["ts"] + parent["dur"]
    assert build["args"]["cols"] == ["dk", "w"]
    assert probes[0]["args"]["cols"] == ["k", "v"]
    # a probe enqueues and returns: whatever is read, is read once a join
    assert not [e for e in spans if e["name"].startswith("d2h")
                and by_id.get(e["parent"], {}).get("name") == "join.probe"]


def test_join_counters_cost_nothing_with_tracing_off(monkeypatch):
    """No tracer, no work: the counter's one packed fetch of counts that
    are still on the device is not made, and a span is no object at all."""
    import contextlib
    from spark_rapids_tpu.columnar import packing
    from spark_rapids_tpu.exec import joins
    from spark_rapids_tpu.trace import core as trace_core
    assert trace_core.TRACER is None

    def no_fetch(*a, **kw):
        raise AssertionError("a fetch for a counter nobody records")
    monkeypatch.setattr(packing, "fetch_packed", no_fetch)
    joins._count_join_rows("TpuHashJoinExec@7", object(), [object()],
                           object(), 1)
    from spark_rapids_tpu.exec.base import TpuExec
    assert isinstance(TpuExec([]).child_span("join.probe"),
                      contextlib.nullcontext)
    # an annotate-only tracer (a profiler session) records no counter
    # either: spans reach the profiler, counters have nowhere to go
    install_tracer(Tracer(recording=False))
    try:
        joins._count_join_rows("TpuHashJoinExec@7", object(), [object()],
                               object(), 1)
    finally:
        install_tracer(None)


def test_traced_upload_never_blocks(monkeypatch):
    """With a tracer installed an upload stays an asynchronous enqueue:
    no block_until_ready (a tracer must not change what it measures),
    and still the enqueue span and the bytes counter."""
    import jax
    from spark_rapids_tpu.columnar.transfer import traced_device_put
    waits = []
    monkeypatch.setattr(jax, "block_until_ready",
                        lambda x: waits.append(x) or x)
    tr = install_tracer(Tracer())
    host = [np.arange(1000, dtype=np.int64), np.ones(1000, dtype=bool)]
    out = traced_device_put(host, label="h2d.test")
    assert waits == []
    assert [np.asarray(o).tolist() for o in out] == \
        [h.tolist() for h in host]
    evs = tr.snapshot()
    (span,) = [e for e in evs if e["ph"] == "X"]
    assert span["name"] == "h2d.test.dispatch" and span["cat"] == "transfer"
    assert span["args"] == {"bytes": 9000, "arrays": 2}
    (ctr,) = [e for e in evs if e["ph"] == "C"]
    assert ctr["name"] == "h2d.bytes" and ctr["args"] == {"bytes": 9000}


def test_every_fetch_happens_inside_a_d2h_span(monkeypatch):
    """The blocking gets are where a one-client query waits for the chip:
    every jax.device_get of a global aggregate, a grouped aggregate and a
    join + sort goes through traced_device_get, i.e. happens while a
    d2h.*.transfer span is the innermost open one."""
    import jax
    from spark_rapids_tpu.trace import core as trace_core
    seen = []
    real = jax.device_get

    def spying(x):
        seen.append(trace_core._CUR_SPAN.get()[0])
        return real(x)

    monkeypatch.setattr(jax, "device_get", spying)
    s = tpu_session(_OPERATOR_CONF)
    fact = s.create_dataframe(_fact())
    dim = s.create_dataframe(pa.table({
        "k": pa.array(np.arange(7)),
        "w": pa.array(np.arange(7, dtype=np.float64))}))
    queries = {
        "global": fact.agg(F.sum(F.col("v")).with_name("sv")),
        "grouped": _grouped(s),
        "join_sort": fact.join(dim, on="k").sort("v").limit(50),
    }
    for name, df in queries.items():
        tr = install_tracer(Tracer())
        del seen[:]
        assert df.collect_arrow().num_rows > 0
        install_tracer(None)
        by_id = {e["id"]: e["name"] for e in _xs(tr)}
        assert seen, name                      # at least one fetch a query
        for sid in seen:
            span = by_id.get(sid, "")
            assert span.startswith("d2h") and span.endswith(".transfer"), \
                (name, span)


def _agg_trace(table, key, value=None, conf=None):
    """One traced run, in a session of its own, of a two-aggregate
    group-by of ``table`` (6 batches) by ``key``: (spans, counters, the
    aggregate's operator metrics)."""
    s = tpu_session({**_OPERATOR_CONF, **(conf or {})})
    v = F.col("v") if value is None else value
    df = s.create_dataframe(table, num_partitions=6).group_by(key).agg(
        F.sum(v).with_name("sv"), F.avg(v).with_name("av"))
    tr = install_tracer(Tracer())
    try:
        assert df.collect_arrow().num_rows > 0
    finally:
        install_tracer(None)
    counters = [e for e in tr.snapshot() if e["ph"] == "C"]
    (agg_m,) = [m for eid, m in
                dict(s.last_query_metrics["operators"]).items()
                if eid.startswith("TpuHashAggregateExec@")]
    return _xs(tr), counters, agg_m


def test_direct_aggregate_is_one_fetch_a_query():
    """A 6-batch aggregate over direct-addressable keys folds every batch
    into one carry on the device: ONE blocking fetch inside the
    aggregate's span and no group-count fetch, 6 + 1 dispatches, and the
    ``agg.carry`` counter says so. A sort-path aggregate (an int key of
    unproven cardinality) reads batches 0."""
    n = 6000
    t = pa.table({"k": pa.array(np.array(["a", "b", "c"], dtype=object)
                                [np.arange(n) % 3]),
                  "m": pa.array(np.arange(n) % 7),
                  "v": pa.array(np.arange(n, dtype=np.float64))})
    spans, counters, agg_m = _agg_trace(t, "k")
    by_id = {e["id"]: e for e in spans}

    def inside_aggregate(e):
        while e["parent"]:
            e = by_id[e["parent"]]
            if e["name"] == "TpuHashAggregateExec":
                return True
        return False

    fetches = [e["name"] for e in spans if e["name"].startswith("d2h")
               and e["name"].endswith(".transfer") and inside_aggregate(e)]
    assert fetches == ["d2h.agg.transfer"]
    assert not [e for e in spans if e["name"].startswith("d2h.groups")]
    assert [e["args"] for e in counters if e["name"] == "agg.carry"] == \
        [{"batches": 6, "flushes": 0}]
    assert agg_m["updateDispatches"] == 7, agg_m

    # an all-numeric scan: at the default target the planner would fill
    # its six 1000-row batches into one (plan/overrides.insert_coalesce)
    _, counters, agg_m = _agg_trace(
        t, "m", conf={"spark.rapids.tpu.sql.batchSizeRows": 1000})
    assert [e["args"] for e in counters if e["name"] == "agg.carry"] == \
        [{"batches": 0, "flushes": 0}]
    assert agg_m["updateDispatches"] > 7, agg_m


def _disjoint_keys(n=12_000):
    """Keys in runs of four (an order's lines): every batch of a scan in
    row order holds other keys than its neighbours."""
    return pa.table({
        "k": pa.array(np.repeat(np.arange(n // 4, dtype=np.int64), 4) * 7),
        "v": pa.array(np.arange(n, dtype=np.float64) % 50)})


#: six partials of 500 groups against a cap of 1,024 rows: the aggregate
#: finishes in partitions
_HIGHCARD_CONF = {"spark.rapids.tpu.sql.batchSizeRows": 1024}


def test_partitioned_finish_spans_and_counter_in_the_ring_buffer():
    """``agg.partition`` (once a division: every partial sorted by its
    keys' hash bucket, and the ONE fetch of the buckets' counts, under a
    ``d2h.*`` label) and ``agg.merge_part`` (one a partition, an enqueue
    that reads nothing) are ``cat="exec"`` children of the aggregate's own
    span carrying its id and the columns it reads; ``agg.highcard`` is
    written once an execution with what the spans add up to."""
    spans, counters, agg_m = _agg_trace(_disjoint_keys(), "k",
                                        conf=_HIGHCARD_CONF)
    by_id = {e["id"]: e for e in spans}
    (count,) = [e["args"] for e in counters if e["name"] == "agg.highcard"]
    (part,) = [e for e in spans if e["name"] == "agg.partition"]
    merges = [e for e in spans if e["name"] == "agg.merge_part"]
    assert count["partials"] == part["args"]["partials"] >= 6
    assert count["rows_in"] == 12_000 and count["groups"] == 3000
    assert count["partitions"] == len(merges) >= 3
    assert max(e["args"]["rows"] for e in merges) \
        == count["largest_partition_rows"] <= 1024
    assert sum(e["args"]["rows"] for e in merges) == 3000
    assert agg_m["aggRepartitions"] == count["partitions"]
    for e in [part] + merges:
        parent = by_id[e["parent"]]
        assert e["cat"] == "exec" \
            and parent["name"] == "TpuHashAggregateExec"
        assert e["args"]["exec"] == parent["args"]["exec"]
        assert int(e["args"]["exec"].rsplit("@", 1)[1]) == count["op"]
        assert e["args"]["cols"] == ["k", "v"]
        assert e["q"] == parent["q"] and e["q"] is not None
        assert parent["ts"] <= e["ts"] and \
            e["ts"] + e["dur"] <= parent["ts"] + parent["dur"]
    fetches = [e for e in spans if e["name"].startswith("d2h")
               and e["name"].endswith(".transfer")]
    # the buckets' counts of all partials: one transfer, inside the span
    assert [e["name"] for e in fetches
            if by_id.get(e["parent"], {}).get("name") == "agg.partition"] \
        == ["d2h.agg_parts.transfer"]
    assert not [e for e in fetches
                if by_id.get(e["parent"], {}).get("name")
                == "agg.merge_part"]


def test_partitioned_finish_spans_under_the_profilers_tracer(tmp_path):
    """Under a jax.profiler session and no installed tracer the finish's
    spans are annotations on the profiler's clock, nested in the
    aggregate's own and carrying its id as ``exec`` (``cols`` is the ring
    buffer's: an annotation carries ``q`` and ``exec``); the counter,
    which has nowhere to go there, makes no fetch."""
    import glob

    import jax
    from jax.profiler import ProfileData
    from spark_rapids_tpu.columnar import packing
    s = tpu_session({**_OPERATOR_CONF, **_HIGHCARD_CONF})
    df = s.create_dataframe(_disjoint_keys(), num_partitions=6) \
        .group_by("k").agg(F.sum(F.col("v")).with_name("sv"))
    assert df.collect_arrow().num_rows == 3000
    real, fetched = packing.fetch_packed, []

    def counted(arrays, label="d2h"):
        fetched.append(label)
        return real(arrays, label)
    packing.fetch_packed = counted
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        assert df.collect_arrow().num_rows == 3000
    finally:
        jax.profiler.stop_trace()
        packing.fetch_packed = real
    (path,) = glob.glob(str(tmp_path / "plugins" / "profile" / "*"
                            / "*.xplane.pb"))
    spans = [(e.name, e.start_ns, e.start_ns + e.duration_ns,
              dict(e.stats))
             for plane in ProfileData.from_file(path).planes
             if plane.name.startswith("/host:")
             for line in plane.lines for e in line.events
             if e.name.startswith("srtpu/")]
    aggs = [sp for sp in spans
            if sp[0] == "srtpu/exec/TpuHashAggregateExec"]
    (part,) = [sp for sp in spans if sp[0] == "srtpu/exec/agg.partition"]
    merges = [sp for sp in spans if sp[0] == "srtpu/exec/agg.merge_part"]
    assert len(merges) >= 3
    for name, a, b, stats in [part] + merges:
        assert stats["exec"].startswith("TpuHashAggregateExec@")
        assert any(c <= a and b <= d and st["exec"] == stats["exec"]
                   for _, c, d, st in aggs), (name, a, b)
    assert any(n == "srtpu/transfer/d2h.agg_parts.transfer"
               and part[1] <= a and b <= part[2] for n, a, b, _ in spans)
    assert fetched and "d2h.agg_highcard" not in fetched, fetched


def test_highcard_counter_costs_nothing_with_tracing_off(monkeypatch):
    """No recording tracer, no work: the partitions' group counts stay on
    the device and no fetch is made for a counter nobody records."""
    from spark_rapids_tpu.columnar import packing
    from spark_rapids_tpu.exec.aggregate import TpuHashAggregateExec
    from spark_rapids_tpu.trace import core as trace_core
    assert trace_core.TRACER is None

    def no_fetch(*a, **kw):
        raise AssertionError("a fetch for a counter nobody records")
    monkeypatch.setattr(packing, "fetch_packed", no_fetch)
    seen = {"partials": 2, "partitions": 2, "largest": 9,
            "groups": [object(), object()]}
    TpuHashAggregateExec._count_highcard(
        type("A", (), {"_exec_id": "TpuHashAggregateExec@3"})(), seen,
        [object()])
    install_tracer(Tracer(recording=False))
    try:
        TpuHashAggregateExec._count_highcard(
            type("A", (), {"_exec_id": "TpuHashAggregateExec@3"})(), seen,
            [object()])
    finally:
        install_tracer(None)


def test_semi_join_writes_the_joins_spans_and_counter():
    """``expr IN (select ...)`` runs as a left semi join: ``join.build``,
    a ``join.probe`` a stream batch and ``join.rows``, as an inner join
    writes them."""
    s = tpu_session({**_OPERATOR_CONF,
                     "spark.rapids.tpu.sql.autoBroadcastJoinThreshold": 0})
    s.create_dataframe(_disjoint_keys(), num_partitions=3) \
        .create_or_replace_temp_view("t")
    s.create_dataframe(pa.table({"kk": pa.array([7, 7, 14, None, 5])})) \
        .create_or_replace_temp_view("u")
    df = s.sql("select k, v from t where k in (select kk from u)")
    assert df.collect_arrow().num_rows == 8
    tr = install_tracer(Tracer())
    try:
        assert df.collect_arrow().num_rows == 8
    finally:
        install_tracer(None)
    spans = _xs(tr)
    by_id = {e["id"]: e for e in spans}
    (rows,) = [e["args"] for e in tr.snapshot()
               if e["ph"] == "C" and e["name"] == "join.rows"]
    assert (rows["build"], rows["stream"], rows["out"]) == (5, 12_000, 8)
    (build,) = [e for e in spans if e["name"] == "join.build"]
    probes = [e for e in spans if e["name"] == "join.probe"]
    assert len(probes) == rows["parts"] >= 1
    for e in [build] + probes:
        assert by_id[e["parent"]]["name"] == "TpuHashJoinExec"
        assert int(e["args"]["exec"].rsplit("@", 1)[1]) == rows["op"]
    assert probes[0]["args"]["cols"] == ["k", "v"]


def test_decimal_aggregate_is_one_fetch_and_counts_its_checks():
    """The same 6-batch direct aggregate over decimal lanes: still ONE
    fetch a query and 6 + 1 dispatches (the flagged-row count rides in the
    carry and in the packed result), the ``decimal.checked`` counter is
    written once beside ``agg.carry`` with the operations checked (the
    product, traced once for each of the two aggregates that read it, and
    the two finalizers) and no overflowed row, and the int64 ->
    decimal128 build of the result is a ``d2h.decimal.finish`` span, one
    per decimal column, billed as fetch time."""
    import decimal
    n = 6000
    money = pa.array([decimal.Decimal(int(x)).scaleb(-2)
                      for x in np.arange(n) * 37 % 100003],
                     pa.decimal128(15, 2))
    t = pa.table({"k": pa.array(np.array(["a", "b", "c"], dtype=object)
                                [np.arange(n) % 3]),
                  "v": money, "w": money})
    spans, counters, agg_m = _agg_trace(t, "k", F.col("v") * F.col("w"))
    fetches = [e["name"] for e in spans if e["name"].startswith("d2h")
               and e["name"].endswith(".transfer")]
    assert fetches.count("d2h.agg.transfer") == 1
    assert "d2h.decimal.transfer" not in fetches
    assert not [e for e in spans if e["name"].startswith("d2h.groups")]
    assert [e["args"] for e in counters if e["name"] == "agg.carry"] == \
        [{"batches": 6, "flushes": 0}]
    assert agg_m["updateDispatches"] == 7, agg_m
    assert [e["args"] for e in counters if e["name"] == "decimal.checked"] \
        == [{"ops": 4, "overflow_rows": 0}]
    finish = [e for e in spans if e["name"] == "d2h.decimal.finish"]
    assert len(finish) >= 2 and all(e["cat"] == "transfer" for e in finish)

    # a projection's checks reach the sink: one small fetch of its own
    s = tpu_session(_OPERATOR_CONF)
    tr = install_tracer(Tracer())
    try:
        s.create_dataframe(t).select(
            (F.col("v") * F.col("w")).alias("p")).collect_arrow()
    finally:
        install_tracer(None)
    assert [e["args"] for e in tr.snapshot() if e["ph"] == "C"
            and e["name"] == "decimal.checked"] == \
        [{"ops": 1, "overflow_rows": 0}]
    assert [e for e in _xs(tr) if e["name"] == "d2h.decimal.transfer"]


def test_spans_of_a_query_share_its_ordinal():
    """Every span of a query reaches that query's ``query`` span by
    ``parent`` and carries its ``q``; the next query gets another."""
    tr = install_tracer(Tracer())
    s = tpu_session(_OPERATOR_CONF)
    df = _grouped(s)
    df.collect_arrow()
    df.collect_arrow()
    spans = _xs(tr)
    by_id = {e["id"]: e for e in spans}
    queries = [e for e in spans if e["name"] == "query"]
    assert len(queries) == 2
    assert queries[0]["q"] != queries[1]["q"]
    assert all(e["parent"] == 0 and e["cat"] == "query" for e in queries)
    for e in spans:
        root = e
        while root["parent"]:
            root = by_id[root["parent"]]
        assert root["name"] == "query", e
        assert e["q"] == root["q"] is not None, e
    assert {e["q"] for e in spans} == {q["q"] for q in queries}


def test_planning_is_inside_the_query_span():
    """The query span opens before planning: plan.physical lies inside it
    and ends before the first operator span starts."""
    tr = install_tracer(Tracer())
    _grouped(tpu_session(_OPERATOR_CONF)).collect_arrow()
    spans = _xs(tr)
    (query,) = [e for e in spans if e["name"] == "query"]
    (plan,) = [e for e in spans if e["name"] == "plan.physical"]
    assert plan["cat"] == "plan" and plan["parent"] == query["id"]
    assert query["ts"] <= plan["ts"]
    assert plan["ts"] + plan["dur"] <= query["ts"] + query["dur"]
    first_exec = min(e["ts"] for e in spans if e["cat"] == "exec")
    assert plan["ts"] + plan["dur"] <= first_exec
    # the verdict the span is closed with
    assert query["args"]["ok"] is True and "placement" in query["args"]


# ---------------------------------------------------------------------------
# distributed: 3 workers, one merged timeline
# ---------------------------------------------------------------------------

def test_three_worker_trace_merge(tmp_path):
    from spark_rapids_tpu.config import TpuConf
    from spark_rapids_tpu.shuffle.cluster import LocalCluster
    out_path = str(tmp_path / "dist.json")
    conf = TpuConf({"spark.rapids.tpu.trace.enabled": True,
                    "spark.rapids.tpu.trace.output": out_path})
    cl = LocalCluster(3, conf=conf)
    try:
        rng = np.random.RandomState(5)
        t = pa.table({"k": pa.array(rng.randint(0, 13, 9000)),
                      "v": pa.array(rng.uniform(0, 100, 9000))})
        s = tpu_session()
        df = (s.create_dataframe(t).group_by("k")
              .agg(F.sum(F.col("v")).with_name("sv"),
                   F.count_star().with_name("n")))
        got = cl.execute(df).to_pandas().sort_values("k") \
                .reset_index(drop=True)
        want = df.collect_arrow().to_pandas().sort_values("k") \
                 .reset_index(drop=True)
        np.testing.assert_allclose(got["sv"], want["sv"], rtol=1e-9)
    finally:
        cl.shutdown()
        install_tracer(None)
    events = load_chrome_trace(out_path)
    # one coherent timeline: the driver AND every worker have a lane
    lane_names = {e["args"]["name"] for e in events
                  if e.get("ph") == "M"
                  and e.get("name") == "process_name"}
    assert {"worker-0", "worker-1", "worker-2"} <= lane_names, lane_names
    assert "driver" in lane_names
    pids = {e["pid"] for e in events if e.get("ph") == "X"}
    assert len(pids) >= 4          # driver + 3 worker processes
    names = {e["name"] for e in events if e.get("ph") == "X"}
    assert "cluster.execute" in names
    assert any(n.startswith("task:") for n in names), names
    assert any(n.startswith("rpc:") for n in names), names
    assert "shuffle.put" in names
    # worker spans were shifted onto the driver timeline: everything
    # falls inside the cluster.execute umbrella (loose 10s slack for
    # clock-alignment jitter)
    umb = next(e for e in events if e["name"] == "cluster.execute")
    lo, hi = umb["ts"] - 10e6, umb["ts"] + umb["dur"] + 10e6
    for e in events:
        if e.get("ph") == "X":
            assert lo <= e["ts"] <= hi, (e["name"], e["ts"], (lo, hi))
    # the analyzer runs over the merged artifact without error and
    # reports every required section
    from spark_rapids_tpu.tools.profile import analyze_file
    analysis, report = analyze_file(out_path)
    assert "== Top operators by self time ==" in report
    assert "== Memory pressure ==" in report
    assert "== Shuffle partitions ==" in report
    assert analysis["shuffle"]["shuffles"], "no shuffle sizes collected"
    assert {"worker-0", "worker-1", "worker-2"} <= set(analysis["workers"])


# ---------------------------------------------------------------------------
# analyzer golden output
# ---------------------------------------------------------------------------

def test_profile_analyzer_golden():
    fixture = os.path.join(FIXTURES, "trace_fixture.json")
    golden = os.path.join(FIXTURES, "profile_golden.txt")
    from spark_rapids_tpu.tools.profile import analyze, format_report
    events = load_chrome_trace(fixture)
    report = format_report(analyze(events), source="trace_fixture.json")
    with open(golden) as f:
        assert report == f.read()


def test_profile_analyzer_self_time_math():
    from spark_rapids_tpu.tools.profile import self_times
    events = [
        {"ph": "X", "name": "parent", "cat": "exec", "ts": 0,
         "dur": 100, "pid": 1, "tid": 1},
        {"ph": "X", "name": "child", "cat": "exec", "ts": 10,
         "dur": 30, "pid": 1, "tid": 1},
        {"ph": "X", "name": "child", "cat": "exec", "ts": 50,
         "dur": 20, "pid": 1, "tid": 1},
        # different lane: no nesting against pid 1
        {"ph": "X", "name": "parent", "cat": "exec", "ts": 20,
         "dur": 40, "pid": 2, "tid": 1},
    ]
    st = self_times(events)
    assert st["parent"]["count"] == 2
    assert st["parent"]["total_us"] == 140
    assert st["parent"]["self_us"] == 90     # 100 - 30 - 20, + 40
    assert st["child"]["self_us"] == 50


def test_profile_cli_main(tmp_path, capsys):
    from spark_rapids_tpu.tools.profile import main
    fixture = os.path.join(FIXTURES, "trace_fixture.json")
    assert main([fixture]) == 0
    out = capsys.readouterr().out
    assert "Recommendations" in out
    assert main([fixture, "--json"]) == 0
    json.loads(capsys.readouterr().out)      # valid JSON mode


def test_write_and_reload_roundtrip(tmp_path):
    tr = Tracer()
    with tr.span("a", cat="exec", args={"k": 1}):
        tr.counter("c", {"v": 2.0})
    p = write_chrome_trace(str(tmp_path / "t.json"), tr)
    evs = load_chrome_trace(p)
    assert {e["ph"] for e in evs} == {"M", "X", "C"}
    x = next(e for e in evs if e["ph"] == "X")
    assert x["args"] == {"k": 1}
