"""Distributed bench rung: TPC-DS q3 and a string-key aggregation planned
onto an 8-virtual-device CPU mesh (run as a subprocess of bench.py with
JAX_PLATFORMS=cpu and --xla_force_host_platform_device_count=8).

This measures the SPMD path every round (VERDICT r3 #5: "add a distributed
rung so the SPMD path is measured, not just dryrun-validated") — the same
planner lowering the driver's dryrun_multichip validates, but timed and
differentially checked against pandas. Wall times are CPU-mesh times, for
trend tracking only; they are not comparable to the TPU ladder.

Prints ONE JSON line:
{"q3_s": ..., "agg_s": ..., "platform": "cpu", "n_devices": 8, "ok": true}
"""
from __future__ import annotations

import json
import sys
import time

import numpy as np


def main(iters: int = 3) -> None:
    import jax
    sys.path.insert(0, __file__.rsplit("/", 2)[0])
    from benchmarks import tpcds
    from spark_rapids_tpu.api import TpuSession, functions as F
    from spark_rapids_tpu.parallel import make_mesh

    devs = jax.devices("cpu")
    n_dev = min(8, len(devs))
    mesh = make_mesh(devices=devs[:n_dev])

    n = 1_000_000
    ss = tpcds.gen_store_sales(n)
    dd = tpcds.gen_date_dim()
    it = tpcds.gen_item()

    def session():
        return TpuSession({
            "spark.rapids.tpu.distributed.enabled": True,
            "spark.rapids.tpu.sql.optimizer.enabled": False,
        }, mesh=mesh)

    # --- q3: scan -> filter -> join -> join -> grouped agg, distributed
    def q3():
        s = session()
        q = tpcds.q3(s.create_dataframe(ss), s.create_dataframe(dd),
                     s.create_dataframe(it), F)
        return q, s

    q, s = q3()
    plan = q.explain()
    assert "DistributedPipeline" in plan, plan
    got = None
    best_q3 = float("inf")
    for _ in range(max(iters, 1)):
        t0 = time.perf_counter()
        q, s = q3()
        got = q.collect_arrow().to_pandas()
        best_q3 = min(best_q3, time.perf_counter() - t0)
    # differential check vs pandas
    pss, pdd, pit = ss.to_pandas(), dd.to_pandas(), it.to_pandas()
    pdd = pdd[pdd["d_moy"] == 11]
    pit = pit[pit["i_manufact_id"] == 128]
    j = pss.merge(pdd, left_on="ss_sold_date_sk", right_on="d_date_sk")
    j = j.merge(pit, left_on="ss_item_sk", right_on="i_item_sk")
    want = (j.groupby(["d_year", "i_brand_id", "i_brand"], as_index=False)
            ["ss_ext_sales_price"].sum())
    assert len(got) == len(want), (len(got), len(want))
    np.testing.assert_allclose(
        np.sort(got["sum_agg"].to_numpy()),
        np.sort(want["ss_ext_sales_price"].to_numpy()), rtol=1e-9)

    # --- grouped agg over a string key, distributed
    import pyarrow as pa
    rng = np.random.RandomState(3)
    keys = np.asarray([f"k{i:03d}" for i in range(500)], dtype=object)
    at = pa.table({"k": pa.array(keys[rng.randint(0, 500, n)]),
                   "v": pa.array(rng.uniform(-10, 10, n))})

    def agg():
        s = session()
        df = s.create_dataframe(at)
        return (df.group_by("k")
                .agg(F.sum(F.col("v")).with_name("sv"),
                     F.count_star().with_name("n")), s)

    q, s = agg()
    plan = q.explain()
    assert "DistributedPipeline" in plan, plan
    best_agg = float("inf")
    for _ in range(max(iters, 1)):
        t0 = time.perf_counter()
        q, s = agg()
        got = q.collect_arrow().to_pandas()
        best_agg = min(best_agg, time.perf_counter() - t0)
    want = (at.to_pandas().groupby("k", as_index=False)
            .agg(sv=("v", "sum"), n=("v", "size")))
    got = got.sort_values("k").reset_index(drop=True)
    want = want.sort_values("k").reset_index(drop=True)
    assert len(got) == len(want)
    np.testing.assert_array_equal(got["k"], want["k"])
    np.testing.assert_allclose(got["sv"], want["sv"], rtol=1e-9)
    np.testing.assert_array_equal(got["n"], want["n"])

    # --- cross-PROCESS sort and window through the TCP shuffle cluster
    # (r4 added range-partitioned sorts and hash-partitioned windows to
    # shuffle/cluster.py with differential tests but no timed rung —
    # VERDICT r4 weak #8; smaller row count: every shuffled byte crosses
    # a real socket)
    from spark_rapids_tpu.exprs import ColumnRef
    from spark_rapids_tpu.exprs.aggregates import Sum as AggSum
    from spark_rapids_tpu.shuffle.cluster import LocalCluster
    nc = n // 4
    st = pa.table({"a": pa.array(rng.randint(-10**6, 10**6, nc)),
                   "b": pa.array(rng.uniform(0, 1, nc))})
    wt = pa.table({"p": pa.array(rng.randint(0, 64, nc)),
                   "o": pa.array(rng.permutation(nc)),
                   "v": pa.array(np.round(rng.uniform(-5, 5, nc), 2))})
    cl = LocalCluster(2)
    try:
        s = session()
        best_sort = best_win = float("inf")
        sorted_got = wgot = None
        for _ in range(max(iters, 1)):
            df = s.create_dataframe(st).order_by(F.col("a").asc())
            t0 = time.perf_counter()
            sorted_got = cl.execute(df).to_pandas()
            best_sort = min(best_sort, time.perf_counter() - t0)
        a = sorted_got["a"].to_numpy()
        assert len(a) == nc and (a[:-1] <= a[1:]).all()
        for _ in range(max(iters, 1)):
            dfw = s.create_dataframe(wt).with_window_column(
                "wsum", AggSum(ColumnRef("v")), partition_by=["p"],
                order_by=[F.col("o").asc()], frame=("rows", -2, 0))
            t0 = time.perf_counter()
            wgot = cl.execute(dfw).to_pandas()
            best_win = min(best_win, time.perf_counter() - t0)
        wgot = wgot.sort_values(["p", "o"])
        wp = wt.to_pandas().sort_values(["p", "o"])
        wexp = (wp.groupby("p")["v"].rolling(3, min_periods=1).sum()
                .reset_index(level=0, drop=True))
        np.testing.assert_allclose(wgot["wsum"].to_numpy(),
                                   wexp.to_numpy(), rtol=1e-9, atol=1e-9)
    finally:
        cl.shutdown()

    # --- skewed_join micro-rung (ISSUE 19): a Zipf key column puts most
    # of one join side into a single hash partition, so the AQE
    # read-side re-plan must salt-split it (and coalesce the tiny
    # remainder) — timed with AQE on, byte-identical vs AQE off
    from spark_rapids_tpu.config import TpuConf
    nk = n // 8
    # zipf(2.5) puts ~75% of rows on key 0: with 3 reduce partitions the
    # hot partition clears threshold x mean (2.0 x 1/3). Integer values
    # + a total order keep the differential exact: int sums are
    # associative, so split/coalesced partial aggs cannot drift
    zk = np.minimum(rng.zipf(2.5, nk), 64).astype(np.int64) - 1
    left = pa.table({"k": pa.array(zk),
                     "v": pa.array(rng.randint(0, 1000, nk)
                                   .astype(np.int64))})
    # small multiplicity (~16 matches/key): the rung times the skew
    # re-plan, not a multiplicative join blow-up
    right = pa.table({"k2": pa.array(rng.randint(0, 64, 1024)
                                     .astype(np.int64)),
                      "w": pa.array(rng.randint(0, 100, 1024)
                                    .astype(np.int64))})

    def skew_query(s):
        df = s.create_dataframe(left)
        return (df.join(s.create_dataframe(right),
                        on=[(F.col("k"), F.col("k2"))], how="inner")
                .group_by("k")
                .agg(F.sum(F.col("v")).with_name("sv"),
                     F.count_star().with_name("n"))
                .order_by(F.col("k").asc()))

    def skew_conf(on: bool):
        # skew.minBytes drops so the CPU-rung byte counts clear the
        # don't-bother floor; the decision thresholds themselves stay
        # at their defaults
        return (TpuConf()
                .set("spark.rapids.tpu.aqe.enabled", on)
                .set("spark.rapids.tpu.aqe.skew.minBytes", 64 * 1024))

    best_skew = float("inf")
    aqe_counts: dict = {}
    cl = LocalCluster(3, shuffle_join_min_rows=1024, conf=skew_conf(True))
    try:
        s = session()
        sgot = None
        for _ in range(max(iters, 1)):
            t0 = time.perf_counter()
            sgot = cl.execute(skew_query(s)).to_pandas()
            best_skew = min(best_skew, time.perf_counter() - t0)
        for d in (s.last_aqe_decisions or []):
            aqe_counts[d["kind"]] = aqe_counts.get(d["kind"], 0) + 1
        assert aqe_counts.get("skew_split", 0) >= 1, aqe_counts
        assert aqe_counts.get("coalesce_partitions", 0) >= 1, aqe_counts
    finally:
        cl.shutdown()
    cl = LocalCluster(3, shuffle_join_min_rows=1024, conf=skew_conf(False))
    try:
        s = session()
        soff = cl.execute(skew_query(s)).to_pandas()
        assert not (s.last_aqe_decisions or []), s.last_aqe_decisions
    finally:
        cl.shutdown()
    # byte-identity, not allclose: re-planning may only change the
    # execution shape, never the answer
    import pandas.testing as pdt
    pdt.assert_frame_equal(sgot, soff)

    print(json.dumps({"q3_s": round(best_q3, 3),
                      "agg_s": round(best_agg, 3),
                      "xproc_sort_s": round(best_sort, 3),
                      "xproc_window_s": round(best_win, 3),
                      "xproc_rows": nc,
                      "skewed_join_s": round(best_skew, 3),
                      "skewed_join_rows": nk,
                      "aqe": aqe_counts,
                      "platform": devs[0].platform,
                      "n_devices": n_dev, "rows": n, "ok": True}))


if __name__ == "__main__":
    main(int(sys.argv[1]) if len(sys.argv) > 1 else 3)
