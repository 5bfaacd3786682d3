"""TPC-H Q3 (clause 2.4.3, its text unchanged) through ``session.sql`` on
seeded ``customer`` / ``orders`` / ``lineitem`` tables of some 10^4 orders:
the ten rows against pandas, and the plan: every operator on the device,
each one-table predicate below its join, the string predicate evaluated
over the dictionary, both joins probed by the sort-and-scan kernel."""
import numpy as np
import pandas as pd
import pyarrow as pa
import pytest

from harness import OPERATOR_CONF, tpu_session
from spark_rapids_tpu.trace import Tracer, install_tracer

Q3 = """
select
    l_orderkey,
    sum(l_extendedprice * (1 - l_discount)) as revenue,
    o_orderdate,
    o_shippriority
from
    customer,
    orders,
    lineitem
where
    c_mktsegment = 'BUILDING'
    and c_custkey = o_custkey
    and l_orderkey = o_orderkey
    and o_orderdate < date '1995-03-15'
    and l_shipdate > date '1995-03-15'
group by
    l_orderkey,
    o_orderdate,
    o_shippriority
order by
    revenue desc,
    o_orderdate
limit 10
"""
SEGMENTS = ["AUTOMOBILE", "BUILDING", "FURNITURE", "MACHINERY", "HOUSEHOLD"]
START = np.datetime64("1992-01-01")
DATE = np.datetime64("1995-03-15")


def _tables(seed, n_orders=12_000, n_cust=1_500):
    rng = np.random.default_rng(seed)
    cust = pa.table({
        "c_custkey": np.arange(1, n_cust + 1, dtype=np.int64),
        "c_mktsegment": pa.array(SEGMENTS).take(
            pa.array(rng.integers(0, 5, n_cust))),
        "c_name": pa.array([f"Customer#{i}" for i in range(n_cust)])})
    days = rng.integers(0, 2406, n_orders)
    orders = pa.table({
        "o_orderkey": np.arange(1, n_orders + 1, dtype=np.int64) * 4,
        "o_custkey": rng.integers(1, n_cust + 1, n_orders).astype(np.int64),
        "o_orderdate": (START + days).astype("datetime64[D]"),
        "o_shippriority": np.zeros(n_orders, np.int32)})
    of = np.repeat(np.arange(n_orders), rng.integers(1, 8, n_orders))
    lineitem = pa.table({
        "l_orderkey": (of.astype(np.int64) + 1) * 4,
        "l_extendedprice": rng.integers(90_000, 10_000_000, len(of)) / 100.0,
        "l_discount": rng.integers(0, 11, len(of)) / 100.0,
        "l_shipdate": (START + days[of] + rng.integers(1, 122, len(of)))
        .astype("datetime64[D]")})
    return {"customer": cust, "orders": orders, "lineitem": lineitem}


def _reference(t):
    cu = t["customer"].to_pandas()
    od = t["orders"].to_pandas(date_as_object=False)
    li = t["lineitem"].to_pandas(date_as_object=False)
    j = cu[cu.c_mktsegment == "BUILDING"].merge(
        od[od.o_orderdate < DATE], left_on="c_custkey", right_on="o_custkey")
    j = j.merge(li[li.l_shipdate > DATE], left_on="o_orderkey",
                right_on="l_orderkey")
    j["revenue"] = j.l_extendedprice * (1 - j.l_discount)
    g = j.groupby(["l_orderkey", "o_orderdate", "o_shippriority"],
                  as_index=False).revenue.sum()
    return g.sort_values(["revenue", "o_orderdate"],
                         ascending=[False, True]).head(10)


def _session(t, conf=None):
    s = tpu_session({**OPERATOR_CONF, **(conf or {})})
    for name, table in t.items():
        s.create_dataframe(table, num_partitions=3) \
            .create_or_replace_temp_view(name)
    return s


#: the planner's own join choice at this size (both broadcast), then the
#: join of two big sides forced: build side made ready once, the other
#: side's batches probed against it
CONFS = {"planner": {},
         "shuffled": {"spark.rapids.tpu.sql.autoBroadcastJoinThreshold": 0}}


@pytest.mark.parametrize("joins", sorted(CONFS))
@pytest.mark.parametrize("seed", [1, 2**31 + 11])
def test_q3_answer_and_placement(seed, joins):
    t = _tables(seed)
    s = _session(t, CONFS[joins])
    got = s.sql(Q3).collect_arrow().to_pandas(date_as_object=False)
    want = _reference(t)
    assert list(got.columns) == ["l_orderkey", "revenue", "o_orderdate",
                                 "o_shippriority"]
    assert got.l_orderkey.tolist() == want.l_orderkey.tolist()
    assert (got.o_orderdate.to_numpy().astype("datetime64[D]")
            == want.o_orderdate.to_numpy().astype("datetime64[D]")).all()
    np.testing.assert_allclose(got.revenue.to_numpy(),
                               want.revenue.to_numpy(), rtol=1e-13)
    assert s.last_placement == "device"
    assert s.last_placement_report["codes"] == {"EXPR_DICT_EVAL": 1}


def test_q3_plan_has_every_predicate_below_its_join_and_no_host_operator():
    s = _session(_tables(5), CONFS["shuffled"])
    lines = s.sql(Q3)._physical().tree_string().splitlines()
    assert not [l for l in lines if "Cpu" in l or "!" in l], lines
    depth = {}
    for l in lines:
        name = l.strip().lstrip("* ")
        depth[name.split("[")[0] + "|" + name] = len(l) - len(l.lstrip())
    def at(text):
        hit = [d for k, d in depth.items() if text in k]
        assert len(hit) == 1, (text, lines)
        return hit[0]
    joins = sorted(d for k, d in depth.items() if k.startswith("HashJoin|"))
    assert len(joins) == 2, lines
    # the customer and orders predicates below BOTH joins, the lineitem
    # one below the join lineitem enters; no filter above a join
    assert at("c_mktsegment = 'BUILDING'") > joins[1]
    assert at("o_orderdate < ") > joins[1]
    assert at("l_shipdate > ") > joins[0]
    assert not [k for k, d in depth.items()
                if "Filter" in k and d < joins[0]], lines
    # a join input carries what is read above it, not its filter's column
    assert at("Project[c_custkey]") < at("c_mktsegment = 'BUILDING'")


def test_q3_joins_count_their_rows_and_probe_a_build_side_made_once():
    t = _tables(9)
    s = _session(t, CONFS["shuffled"])
    df = s.sql(Q3)
    df.collect_arrow()              # sizes the joins' outputs
    tr = install_tracer(Tracer())
    try:
        df = s.sql(Q3)
        df.collect_arrow()
    finally:
        install_tracer(None)
    ev = tr.snapshot()
    rows = [e["args"] for e in ev
            if e["ph"] == "C" and e["name"] == "join.rows"]
    cu = t["customer"].to_pandas()
    od = t["orders"].to_pandas(date_as_object=False)
    li = t["lineitem"].to_pandas(date_as_object=False)
    n_c = int((cu.c_mktsegment == "BUILDING").sum())
    n_o = int((od.o_orderdate < DATE).sum())
    n_l = int((li.l_shipdate > DATE).sum())
    first = od[od.o_orderdate < DATE].merge(
        cu[cu.c_mktsegment == "BUILDING"], left_on="o_custkey",
        right_on="c_custkey")
    assert [(r["build"], r["stream"], r["out"]) for r in rows][0] == \
        (min(n_c, n_o), max(n_c, n_o), len(first))
    assert rows[1]["build"] == len(first) and rows[1]["stream"] == n_l
    assert rows[1]["parts"] == 3        # lineitem's three batches
    assert [e["args"] for e in ev if e["ph"] == "C"
            and e["name"] == "plan.pushdown"] == \
        [{"pushed": 3, "above_joins": 0}]
    spans = [e for e in ev if e["ph"] == "X"]
    by_id = {e["id"]: e for e in spans}
    builds = [e for e in spans if e["name"] == "join.build"]
    probes = [e for e in spans if e["name"] == "join.probe"]
    assert len(builds) == 2 and len(probes) == 6
    for e in builds + probes:
        parent = by_id[e["parent"]]
        assert parent["name"] == "TpuHashJoinExec"
        assert parent["args"]["exec"] == e["args"]["exec"]
        assert e["q"] == parent["q"] and e["q"] is not None
        assert e["cat"] == "exec"
    # no read a batch: the build side's one fetch, none inside a probe
    assert not [e for e in spans if e["name"].startswith("d2h")
                and by_id.get(e["parent"], {}).get("name") == "join.probe"]
