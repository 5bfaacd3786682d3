"""The seam under ``api/``: ``exec/query.py`` is the one way product code
runs a plan. Every sink, returning or raising, tells every installed
consumer of a query's outcome once, in a fixed order, with ONE record;
nothing stays on the session's own context, on the driver side of a
distributed query and in a worker task too; the event log's records keep
their keys; and ``api/dataframe.py`` no longer knows the subsystems."""
import ast
import os
import pickle

import numpy as np
import pyarrow as pa
import pytest

from harness import OPERATOR_CONF, tpu_session
from spark_rapids_tpu.api import functions as F

PKG = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))), "spark_rapids_tpu")

_RNG = np.random.RandomState(30)
_T = pa.table({"k": pa.array(_RNG.randint(0, 7, 1500)),
               "v": pa.array(_RNG.randint(0, 1000, 1500).astype(np.int64))})
_DIM = pa.table({"k": pa.array(np.arange(7)),
                 "w": pa.array(np.arange(7, dtype=np.int64) + 1)})


def _assert_session_context_empty(s):
    ctx = s.exec_context()
    assert not ctx.metrics and not ctx._cleanups
    assert not ctx._broadcast_cache


def _events(elog_dir):
    from spark_rapids_tpu.tools.history import load_events
    return load_events(elog_dir)[0]


# ---------------------------------------------------------------------------
# (a) sinks x (returns, raises): one start, one end, one record
# ---------------------------------------------------------------------------

_SINKS = {
    "collect_arrow": lambda df, tmp: df.collect_arrow(),
    "count": lambda df, tmp: df.count(),
    "to_device_columns": lambda df, tmp: df.to_device_columns(),
    "write_parquet": lambda df, tmp: df.write_parquet(str(tmp / "out")),
    "explain_analyze": lambda df, tmp: df.explain("analyze"),
}

_STARTS = ["registry", "eventlog", "tracker", "flight"]
_ENDS = ["registry", "eventlog", "flight", "sentinel", "slo", "tracker"]


def _install_consumers(tmp_path, monkeypatch):
    """The real consumers on a tmp dir, each ``query_started`` /
    ``query_ended`` recording its call before running."""
    from spark_rapids_tpu.metrics.events import EventLogWriter
    from spark_rapids_tpu.metrics.registry import MetricRegistry
    from spark_rapids_tpu.ops import flight, sentinel, server, slo
    heard = []
    for name, cls in (("registry", MetricRegistry),
                      ("eventlog", EventLogWriter),
                      ("tracker", server.QueryTracker),
                      ("flight", flight.FlightRecorder),
                      ("sentinel", sentinel.RegressionSentinel),
                      ("slo", slo.SloTracker)):
        for phase in ("query_started", "query_ended"):
            real = getattr(cls, phase, None)
            if real is None:
                continue

            def spy(self, o, _real=real, _name=name, _phase=phase):
                heard.append((_name, _phase, o))
                return _real(self, o)
            monkeypatch.setattr(cls, phase, spy)
    srv = server.install_ops(server.OpsServer(0).start())
    flight.install_flight(flight.FlightRecorder(str(tmp_path / "flight")))
    sen = sentinel.install_sentinel(
        sentinel.RegressionSentinel(str(tmp_path / "baselines.json")))
    trk = slo.install_slo(slo.SloTracker(target_ms=60000.0))
    return heard, srv, sen, trk


@pytest.mark.parametrize("raises", [False, True],
                         ids=["returns", "raises"])
@pytest.mark.parametrize("sink", sorted(_SINKS))
def test_every_consumer_hears_one_start_and_one_end(sink, raises, tmp_path,
                                                    monkeypatch):
    heard, srv, sen, trk = _install_consumers(tmp_path, monkeypatch)
    elog = str(tmp_path / "elog")
    s = tpu_session({**OPERATOR_CONF,
                     "spark.rapids.tpu.eventLog.enabled": True,
                     "spark.rapids.tpu.eventLog.dir": elog,
                     "spark.rapids.tpu.metrics.enabled": True,
                     "spark.rapids.tpu.metrics.sample.intervalMs": 0})
    df = s.create_dataframe(_T)
    if raises:
        def boom(pdf):
            raise ValueError("boom-30")
        df = df.map_in_pandas(boom, _T.schema)
    df = (df.join(s.create_dataframe(_DIM), on="k").group_by("k")
          .agg(F.sum(F.col("v") * F.col("w")).with_name("sv")))
    if raises:
        with pytest.raises(Exception, match="boom-30"):
            _SINKS[sink](df, tmp_path)
    else:
        _SINKS[sink](df, tmp_path)

    # today's order, and ONE record for all of them
    assert [n for n, p, _ in heard if p == "query_started"] == _STARTS
    assert [n for n, p, _ in heard if p == "query_ended"] == _ENDS
    assert len({id(o) for _, _, o in heard}) == 1
    o = heard[0][2]
    assert o.ok is (not raises) and o.wall_s > 0
    assert o.query_id is not None and o.digest

    # what each consumer kept agrees on query id, digest and wall
    start, end = [e for e in _events(elog)
                  if e.get("event") in ("queryStart", "queryEnd")]
    rec = srv.tracker.snapshot()["recent"][-1]
    assert not srv.tracker.snapshot()["inflight"]
    assert start["queryId"] == end["queryId"] == rec["queryId"] \
        == o.query_id
    assert start["planDigest"] == end["planDigest"] == rec["planDigest"] \
        == o.digest
    assert end["durationMs"] == rec["wallMs"] == round(o.wall_s * 1e3, 3)
    assert end["ok"] is (not raises)
    assert rec["status"] == ("failed" if raises else "ok")
    assert ("boom-30" in end["reason"]) if raises else "reason" not in end
    assert o.digest in sen.baselines()
    lane = trk.report()["tenants"]["default"]
    assert lane["good"] + lane["bad"] == 1
    from spark_rapids_tpu.metrics import registry as metrics_registry
    series = metrics_registry.REGISTRY.snapshot()[
        "srtpu_queries_total"]["series"]
    assert [(m["labels"], m["value"]) for m in series] == \
        [({"status": "failed" if raises else "ok"}, 1)]
    from spark_rapids_tpu.ops import flight
    assert flight.RECORDER.query_context() is None

    assert s.last_query_metrics is not None
    _assert_session_context_empty(s)
    s.close()


# ---------------------------------------------------------------------------
# (b) the cluster path: nothing stays on the session's context either
# ---------------------------------------------------------------------------

def test_cluster_query_leaves_the_users_session_context_empty():
    """The driver side of a distributed round runs its final plan on the
    USER's session (``run_plan``): at the parent the operators of that
    plan stayed on the session's context until ``close()``."""
    from spark_rapids_tpu.shuffle.cluster import LocalCluster
    cl = LocalCluster(2)
    try:
        s = tpu_session()
        df = (s.create_dataframe(_T).group_by("k")
              .agg(F.sum(F.col("v")).with_name("sv")))
        for _ in range(2):
            got = cl.execute(df).to_pandas().sort_values("k")
        want = _T.to_pandas().groupby("k", as_index=False).agg(
            sv=("v", "sum"))
        np.testing.assert_array_equal(got["sv"], want["sv"])
        _assert_session_context_empty(s)
        s.close()
    finally:
        cl.shutdown()


def test_worker_task_leaves_its_session_context_empty(monkeypatch):
    """A worker's map task, run in this process with the block store
    stubbed out: the session it makes keeps nothing of the plan."""
    from spark_rapids_tpu.api import dataframe as api_df
    from spark_rapids_tpu.exprs.base import ColumnRef
    from spark_rapids_tpu.shuffle import cluster
    made = []

    class Recorded(api_df.TpuSession):
        def __init__(self, *a, **kw):
            super().__init__(*a, **kw)
            made.append(self)

    monkeypatch.setattr(api_df, "TpuSession", Recorded)
    monkeypatch.setattr(
        cluster, "_put_partitions",
        lambda sid, parts, owners, map_id=None:
            {p: t.num_rows for p, t in parts.items()})
    plan = (tpu_session().create_dataframe(_T)
            .filter(F.col("v") > 10).plan)
    rows = cluster._run_map_task(1, pickle.dumps(plan),
                                 pickle.dumps([ColumnRef("k")]),
                                 ["a", "b"])
    assert sum(rows.values()) == int(np.sum(_T["v"].to_numpy() > 10))
    (session,) = made
    _assert_session_context_empty(session)


# ---------------------------------------------------------------------------
# (c) the event log's records keep their keys
# ---------------------------------------------------------------------------

QUERY_START_KEYS = {"event", "ts", "queryId", "planDigest", "root",
                    "placement", "conf"}
QUERY_END_KEYS = {"event", "ts", "queryId", "planDigest", "ok",
                  "durationMs", "degraded", "ladderRung", "tenant",
                  "queuedMs", "compileSeconds", "placementVerdict",
                  "metrics", "faultStats", "trace"}


def _plain(s, tmp_path):
    return {}, set()


def _failed(s, tmp_path):
    return {"spark.rapids.tpu.query.timeout": 1e-9}, {"reason"}


def _admitted(s, tmp_path):
    return ({"spark.rapids.tpu.admission.enabled": True,
             "spark.rapids.tpu.tenant.id": "team-a"}, {"admission"})


def _degraded(s, tmp_path):
    from spark_rapids_tpu.aux.fault import ChaosController, install_chaos
    install_chaos(ChaosController("mem.oom=*"))
    return {}, {"reason", "oomDegradations", "placement"}


def _replanned(s, tmp_path):
    """Two rung-3 runs in the digest's history: admitted with an overlay,
    which is an AQE decision."""
    from spark_rapids_tpu.metrics.events import plan_digest
    from spark_rapids_tpu.ops.sentinel import (RegressionSentinel,
                                               install_sentinel)
    sen = install_sentinel(RegressionSentinel(str(tmp_path / "b.json")))
    for _ in range(2):
        sen.fold({"digest": plan_digest(_keys_df(s).plan), "wallMs": 50.0,
                  "verdict": "device", "rung": 3, "ok": True})
    return {}, {"aqe"}


def _keys_df(s):
    return (s.create_dataframe(_T, num_partitions=2).group_by("k")
            .agg(F.sum(F.col("v")).with_name("sv")))


@pytest.mark.parametrize("case", [_plain, _failed, _admitted, _degraded,
                                  _replanned],
                         ids=lambda f: f.__name__.strip("_"))
def test_query_records_keep_their_keys(case, tmp_path):
    """``tools/history``, ``tools/qualify`` and ``tools/regress`` read
    these keys: the always-present ones as literals, each optional one
    under its condition and only there."""
    elog = str(tmp_path / "elog")
    s = tpu_session({"spark.rapids.tpu.eventLog.enabled": True,
                     "spark.rapids.tpu.eventLog.dir": elog})
    extra_conf, optional = case(s, tmp_path)
    for k, v in extra_conf.items():
        s.set_conf(k, v)
    try:
        _keys_df(s).collect_arrow()
    except Exception:
        assert case is _failed
    start, end = [e for e in _events(elog)
                  if e.get("event") in ("queryStart", "queryEnd")]
    assert set(start) == QUERY_START_KEYS
    assert set(end) == QUERY_END_KEYS | optional
    assert end["ok"] is (case is not _failed)
    assert end["degraded"] is (case is _degraded)
    s.close()


# ---------------------------------------------------------------------------
# (d) the arrows point one way
# ---------------------------------------------------------------------------

_BELOW_THE_SEAM = ("ops", "sched", "aqe", "mem", "plan.cost",
                   "plan.exec_cache", "exprs.decimal_rules")


def test_dataframe_imports_nothing_below_the_seam():
    tree = ast.parse(open(os.path.join(PKG, "api", "dataframe.py")).read())
    found = []
    for node in ast.walk(tree):
        if isinstance(node, ast.ImportFrom):
            mods = [node.module or ""] + [
                f"{node.module or ''}.{a.name}".strip(".")
                for a in node.names]
        elif isinstance(node, ast.Import):
            mods = [a.name.replace("spark_rapids_tpu.", "")
                    for a in node.names]
        else:
            continue
        found += [(node.lineno, m) for m in mods for b in _BELOW_THE_SEAM
                  if m == b or m.startswith(b + ".")]
    assert not found, found
    names = {n.name for n in ast.walk(tree)
             if isinstance(n, ast.FunctionDef)}
    assert not names & {"_execute_query", "_oom_query_ladder",
                        "_aqe_feedback_conf"}
    assert "_execute_wrapped" in names


def test_no_product_code_executes_on_the_sessions_context():
    hits = []
    for root, _, files in os.walk(PKG):
        for f in files:
            if f.endswith(".py"):
                path = os.path.join(root, f)
                text = "".join(open(path).read().split())
                if ".collect(session.exec_context())" in text \
                        or ".execute(session.exec_context())" in text:
                    hits.append(os.path.relpath(path, PKG))
    assert not hits, hits
