"""Benchmark ladder: TPC-H q1/q6, TPC-DS q3/q9/q28, bounded window, string
transforms — at 1M rows AND 10M rows (q1/q6/q9/q28) — plus a distributed
rung (8-virtual-device CPU mesh) run in a subprocess.

Design (VERDICT r3 #1: "finish the bench — at scale, with placement
honesty"):
  * every workload is timed AND correctness-checked before the next one
    starts, so a timeout can never discard finished results;
  * each workload records which engine actually ran ("device"/"host" from
    session.last_placement) — host-numpy wins are labeled as such;
  * a LADDER budget (SRTPU_BENCH_BUDGET, default 1500 s) gracefully
    skips remaining rungs; the budget clock starts after table
    generation. Every finished rung's metric line is flushed
    IMMEDIATELY, so even an external timeout mid-ladder preserves all
    completed results;
  * a run that finds no TPU exits non-zero before the first rung; only
    SRTPU_BENCH_CPU=1 makes it a (labeled) CPU rehearsal;
  * the summary carries an overall geomean, a DEVICE-ONLY geomean, and a
    regression check against the previous round's BENCH_r*.json.

Baseline = the same queries through pandas on this host's CPU (the role CPU
Spark plays for the reference's speedups, docs/index.md:8-24).

Env: SRTPU_BENCH_CPU=1 forces the JAX CPU backend; SRTPU_BENCH_ROWS
overrides the base row count; SRTPU_BENCH_BIG_ROWS the big-rung count
(0 disables); SRTPU_BENCH_ITERS per-workload iterations;
SRTPU_BENCH_BUDGET the wall budget in seconds; SRTPU_BENCH_DIST=0
disables the distributed rung.
"""
from __future__ import annotations

import glob
import json
import os
import re
import subprocess
import sys
import time

import numpy as np

START = time.perf_counter()


def log(*a):
    print(*a, file=sys.stderr, flush=True)


def _time_min(fn, iters):
    best = float("inf")
    out = None
    for _ in range(iters):
        t0 = time.perf_counter()
        out = fn()
        best = min(best, time.perf_counter() - t0)
    return best, out


def gen_string_table(n: int, seed: int = 13, card: int = 1000):
    import pyarrow as pa
    rng = np.random.RandomState(seed)
    pool = np.asarray([f"  Item-{i:05d}-{'x' * (i % 7)}  "
                       for i in range(card)], dtype=object)
    return pa.table({
        "s": pa.array(pool[rng.randint(0, card, n)]),
        "v": pa.array(rng.uniform(0, 10, n)),
    })


def gen_window_table(n: int, seed: int = 11):
    import pyarrow as pa
    rng = np.random.RandomState(seed)
    return pa.table({
        "p": pa.array(rng.randint(0, 512, n)),
        "o": pa.array(rng.randint(0, 1 << 30, n)),
        "v": pa.array(rng.uniform(-100.0, 100.0, n)),
    })


# ---------------------------------------------------------------------------
# correctness checks (one per workload shape, run IMMEDIATELY after timing)
# ---------------------------------------------------------------------------

def check_q1(res, base):
    got = res.to_pandas().set_index(["l_returnflag", "l_linestatus"]) \
             .sort_index()
    np.testing.assert_allclose(got["sum_disc_price"].to_numpy(),
                               base["sum_disc_price"].to_numpy(), rtol=1e-9)
    np.testing.assert_array_equal(got["count_order"].to_numpy(),
                                  base["count_order"].to_numpy())


def check_q6(res, base):
    np.testing.assert_allclose(res.column("revenue")[0].as_py(), base,
                               rtol=1e-9)


def check_q3(res, base):
    np.testing.assert_allclose(
        np.sort(res.column("sum_agg").to_numpy()),
        np.sort(base["sum_agg"].to_numpy()), rtol=1e-9)
    assert res.num_rows == len(base)


def check_q9(res, base):
    grow = res.to_pylist()[0]
    for k, v in base.items():
        np.testing.assert_allclose(grow[k], v, rtol=1e-9, err_msg=k)


def check_q28(res, base):
    eng_rows = [(r["b_avg"], r["b_cnt"], r["b_cntd"])
                for r in res.to_pylist()]
    for (ea, ec, ed), (ba, bc, bd) in zip(eng_rows, base):
        np.testing.assert_allclose(ea, ba, rtol=1e-9)
        assert (ec, ed) == (bc, bd)


def check_window(res, base):
    eng_sum = float(np.nansum(res.column("wsum").to_numpy(
        zero_copy_only=False)))
    np.testing.assert_allclose(eng_sum, float(base["wsum"].sum()), rtol=1e-6)


def check_strings(res, base):
    got = res.to_pandas().sort_values(["u", "pre"]).reset_index(drop=True)
    base = base.sort_values(["u", "pre"]).reset_index(drop=True)
    assert len(got) == len(base), (len(got), len(base))
    np.testing.assert_array_equal(got["u"], base["u"])
    np.testing.assert_array_equal(got["n"], base["n"])
    np.testing.assert_allclose(got["sv"], base["sv"], rtol=1e-9)


# ---------------------------------------------------------------------------

def _bench_round_no(p):
    m = re.search(r"r(\d+)", os.path.basename(p))
    return int(m.group(1)) if m else -1


def _bench_artifacts():
    """Every BENCH_r*.json beside this script, oldest round first — ONE
    discovery for the regression gate and the regress-delta emitter."""
    return sorted(glob.glob(os.path.join(os.path.dirname(
        os.path.abspath(__file__)), "BENCH_r*.json")),
        key=_bench_round_no)


def previous_bench():
    """Newest BENCH_r*.json with a parsed summary (regression gate)."""
    best = None
    for p in _bench_artifacts():
        try:
            j = json.load(open(p))
        except Exception:
            continue
        tail = j.get("tail", "")
        m = re.findall(r'\{"metric": "(\w+)_speedup", "value": ([\d.]+)',
                       tail)
        if j.get("parsed") and isinstance(j["parsed"], dict) \
                and j["parsed"].get("details"):
            best = (p, {k: d.get("speedup")
                        for k, d in j["parsed"]["details"].items()})
        elif m:
            best = (p, {k: float(v) for k, v in m})
    return best


def run_distributed_rung(iters: int):
    """q3 + a string-key agg on an 8-virtual-device CPU mesh, subprocess
    (XLA device count is fixed at backend init, so it cannot run in this
    process next to the TPU backend). The child is pinned to the CPU —
    this process holds the chip — and its output line says so
    ("platform": "cpu"). Differential vs pandas; wall is reported for
    visibility, not compared to the TPU numbers."""
    here = os.path.dirname(os.path.abspath(__file__))
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=8")
    env["PYTHONPATH"] = here + os.pathsep + env.get("PYTHONPATH", "")
    p = subprocess.run(
        [sys.executable, os.path.join(here, "benchmarks",
                                      "distributed_rung.py"),
         str(iters)],
        capture_output=True, text=True, timeout=600, env=env)
    if p.returncode != 0:
        log("bench: distributed rung FAILED:\n" + p.stderr[-2000:])
        return None
    for line in reversed(p.stdout.strip().splitlines()):
        try:
            return json.loads(line)
        except Exception:
            continue
    return None


def main():
    import jax
    cpu_asked = os.environ.get("SRTPU_BENCH_CPU") == "1"
    if cpu_asked:
        jax.config.update("jax_platforms", "cpu")
    # ONE backend query, in this process (a chip belongs to one process:
    # a probing child would hold it against its own parent). A ladder
    # that finds no TPU fails — it never carries on on the CPU unless
    # the CPU was asked for by name.
    platform = jax.devices()[0].platform
    if platform != "tpu" and not cpu_asked:
        log(f"bench: no TPU (jax reports platform={platform!r}); set "
            "SRTPU_BENCH_CPU=1 for a CPU rehearsal")
        sys.exit(1)

    from spark_rapids_tpu.api import TpuSession, functions as F

    from benchmarks import tpch, tpcds

    n = int(os.environ.get("SRTPU_BENCH_ROWS", 1_000_000))
    nbig = int(os.environ.get("SRTPU_BENCH_BIG_ROWS", 10_000_000))
    iters = int(os.environ.get("SRTPU_BENCH_ITERS", 3))
    budget = float(os.environ.get("SRTPU_BENCH_BUDGET", 1500))
    nw = min(n, 500_000)
    lineitem = tpch.gen_lineitem(n)
    store_sales = tpcds.gen_store_sales(n)
    date_dim = tpcds.gen_date_dim()
    item = tpcds.gen_item()
    wtab = gen_window_table(nw)
    stab = gen_string_table(n)
    stab_hc = gen_string_table(n, card=100_000)   # byte-rectangle regime
    # big tables generate LAZILY right before their rung: eager generation
    # would burn minutes of budget (and >1 GB resident) even when the
    # budget ends up skipping every big rung
    _big = {}

    def lineitem_big():
        if "l" not in _big:
            _big["l"] = tpch.gen_lineitem(nbig)
        return _big["l"]

    def store_sales_big():
        if "s" not in _big:
            _big["s"] = tpcds.gen_store_sales(nbig)
        return _big["s"]

    nhuge = int(os.environ.get("SRTPU_BENCH_HUGE_ROWS", 100_000_000))

    def store_sales_huge():
        # SF100-class rung (BASELINE.md config #3 ladder): generated
        # COLUMN-PRUNED (q9 touches 3 of the 12 columns; the full table
        # would be ~10 GB host RAM for nothing) and only if the budget
        # survives to the last rung
        if "h" not in _big:
            _big.pop("s", None)       # reclaim the 10M table first
            import pyarrow as pa
            rng = np.random.RandomState(7)
            _big["h"] = pa.table({
                "ss_quantity": pa.array(
                    rng.randint(1, 101, nhuge)),
                "ss_ext_sales_price": pa.array(
                    np.round(rng.uniform(1.0, 20000.0, nhuge), 2)),
                "ss_net_paid": pa.array(
                    np.round(rng.uniform(1.0, 20000.0, nhuge), 2)),
            })
        return _big["h"]
    log(f"bench: ladder on {jax.devices()[0].platform}, {n} rows "
        f"(+{nbig} big rungs), {iters} iters, budget {budget:.0f}s")
    # the budget buys LADDER time, not table generation
    ladder_t0 = time.perf_counter()

    last_session = [None]

    def eng(q_builder):
        def run():
            s = TpuSession()
            last_session[0] = s
            return q_builder(s).collect_arrow()
        return run

    # ---------------- engine queries (tables via thunk: big rungs
    # generate lazily) ----------------
    def q1_of(tab):
        return eng(lambda s: tpch.q1(s.create_dataframe(tab()), F))

    def q6_of(tab):
        return eng(lambda s: tpch.q6(s.create_dataframe(tab()), F))

    def q9_of(tab):
        return eng(lambda s: tpcds.q9(s.create_dataframe(tab()), F))

    def q28_of(tab):
        return eng(lambda s: tpcds.q28(s.create_dataframe(tab()), F))

    eng_q3 = eng(lambda s: tpcds.q3(s.create_dataframe(store_sales),
                                    s.create_dataframe(date_dim),
                                    s.create_dataframe(item), F))

    def _window_q(s):
        from spark_rapids_tpu.exprs import ColumnRef
        from spark_rapids_tpu.exprs.aggregates import Sum
        return (s.create_dataframe(wtab)
                .with_window_column("wsum", Sum(ColumnRef("v")),
                                    partition_by=["p"],
                                    order_by=[F.col("o").asc()],
                                    frame=("rows", -2, 0)))
    eng_window = eng(_window_q)

    def _strings_q_of(table):
        def q(s):
            return (s.create_dataframe(table)
                    .select(F.upper(F.trim(F.col("s"))).alias("u"),
                            F.substring(F.col("s"), 3, 4).alias("pre"),
                            F.col("v"))
                    .group_by("u", "pre")
                    .agg(F.sum(F.col("v")).with_name("sv"),
                         F.count_star().with_name("n")))
        return q
    eng_strings = eng(_strings_q_of(stab))
    eng_strings_hc = eng(_strings_q_of(stab_hc))

    # ---------------- pandas baselines ----------------
    def base_q1_of(tab):
        def run():
            pdf = tab().to_pandas(date_as_object=False)
            cutoff = (np.datetime64("1998-12-01")
                      - np.timedelta64(90, "D")).astype("datetime64[ns]")
            f = pdf[pdf["l_shipdate"] <= cutoff].copy()
            f["disc_price"] = f["l_extendedprice"] * (1.0 - f["l_discount"])
            f["charge"] = f["disc_price"] * (1.0 + f["l_tax"])
            return f.groupby(["l_returnflag", "l_linestatus"]).agg(
                sum_qty=("l_quantity", "sum"),
                sum_base_price=("l_extendedprice", "sum"),
                sum_disc_price=("disc_price", "sum"),
                sum_charge=("charge", "sum"),
                avg_qty=("l_quantity", "mean"),
                avg_price=("l_extendedprice", "mean"),
                avg_disc=("l_discount", "mean"),
                count_order=("l_quantity", "size")).sort_index()
        return run

    def base_q6_of(tab):
        def run():
            pdf = tab().to_pandas(date_as_object=False)
            m = ((pdf["l_shipdate"] >= np.datetime64("1994-01-01"))
                 & (pdf["l_shipdate"] < np.datetime64("1995-01-01"))
                 & (pdf["l_discount"] >= 0.05) & (pdf["l_discount"] <= 0.07)
                 & (pdf["l_quantity"] < 24.0))
            f = pdf[m]
            return float((f["l_extendedprice"] * f["l_discount"]).sum())
        return run

    def base_q3():
        ss = store_sales.to_pandas()
        dd = date_dim.to_pandas(date_as_object=False)
        it = item.to_pandas()
        dd = dd[dd["d_moy"] == 11]
        it = it[it["i_manufact_id"] == 128]
        j = ss.merge(dd, left_on="ss_sold_date_sk", right_on="d_date_sk")
        j = j.merge(it, left_on="ss_item_sk", right_on="i_item_sk")
        g = (j.groupby(["d_year", "i_brand_id", "i_brand"], as_index=False)
             ["ss_ext_sales_price"].sum()
             .rename(columns={"ss_ext_sales_price": "sum_agg"}))
        return g.sort_values(["d_year", "sum_agg", "i_brand_id"],
                             ascending=[True, False, True])

    def base_q9_of(tab):
        def run():
            ss = tab().to_pandas()
            out = {}
            for i, (lo, hi) in enumerate(
                    [(1, 20), (21, 40), (41, 60), (61, 80), (81, 100)], 1):
                m = (ss["ss_quantity"] >= lo) & (ss["ss_quantity"] <= hi)
                out[f"cnt{i}"] = int(m.sum())
                out[f"avg_price{i}"] = float(
                    ss.loc[m, "ss_ext_sales_price"].mean())
                out[f"avg_paid{i}"] = float(ss.loc[m, "ss_net_paid"].mean())
            return out
        return run

    def base_q28_of(tab):
        def run():
            ss = tab().to_pandas()
            buckets = [(0, 5, 11, 460, 14930), (6, 10, 91, 1430, 32370),
                       (11, 15, 66, 1480, 3750), (16, 20, 142, 3270, 21910),
                       (21, 25, 135, 2450, 17300), (26, 30, 28, 2340, 33660)]
            rows = []
            for lo, hi, lp, cp, wc in buckets:
                m = ((ss["ss_quantity"] >= lo) & (ss["ss_quantity"] <= hi)
                     & ((ss["ss_list_price"] >= float(lp))
                        | (ss["ss_coupon_amt"] >= float(cp))
                        | (ss["ss_wholesale_cost"] >= float(wc))))
                b = ss.loc[m, "ss_list_price"]
                rows.append((float(b.mean()), int(b.count()),
                             int(b.nunique())))
            return rows
        return run

    def base_strings_of(table):
        def run():
            pdf = table.to_pandas()
            pdf["u"] = pdf["s"].str.strip(" ").str.upper()
            pdf["pre"] = pdf["s"].str.slice(2, 6)
            return (pdf.groupby(["u", "pre"], as_index=False)
                    .agg(sv=("v", "sum"), n=("v", "size")))
        return run
    base_strings = base_strings_of(stab)
    base_strings_hc = base_strings_of(stab_hc)

    def base_window():
        pdf = wtab.to_pandas()
        pdf = pdf.sort_values(["p", "o"], kind="stable")
        pdf["wsum"] = (pdf.groupby("p")["v"]
                       .rolling(3, min_periods=1).sum()
                       .reset_index(level=0, drop=True))
        return pdf

    li = lambda: lineitem          # noqa: E731
    ss_ = lambda: store_sales      # noqa: E731
    workloads = [
        ("tpch_q1", n, q1_of(li), base_q1_of(li), check_q1),
        ("tpch_q6", n, q6_of(li), base_q6_of(li), check_q6),
        ("tpcds_q3", n, eng_q3, base_q3, check_q3),
        ("tpcds_q9", n, q9_of(ss_), base_q9_of(ss_), check_q9),
        ("tpcds_q28", n, q28_of(ss_), base_q28_of(ss_), check_q28),
        ("window_bounded", nw, eng_window, base_window, check_window),
        ("string_transforms", n, eng_strings, base_strings, check_strings),
        ("string_transforms_100k", n, eng_strings_hc, base_strings_hc,
         check_strings),
    ]
    if nbig:
        workloads += [
            ("tpch_q1_10m", nbig, q1_of(lineitem_big),
             base_q1_of(lineitem_big), check_q1),
            ("tpch_q6_10m", nbig, q6_of(lineitem_big),
             base_q6_of(lineitem_big), check_q6),
            ("tpcds_q9_10m", nbig, q9_of(store_sales_big),
             base_q9_of(store_sales_big), check_q9),
            ("tpcds_q28_10m", nbig, q28_of(store_sales_big),
             base_q28_of(store_sales_big), check_q28),
        ]
    if nhuge:
        # SF100-class global-agg rung: the wide-batch path runs the
        # whole 100M-row query as a handful of fused dispatches
        workloads += [
            ("tpcds_q9_100m", nhuge, q9_of(store_sales_huge),
             base_q9_of(store_sales_huge), check_q9),
        ]

    # per-rung trace + metrics artifacts (ISSUE 4 / ISSUE 5): one extra
    # INSTRUMENTED engine run per finished rung — trace AND metric
    # registry enabled together so the rung ships both a Chrome-trace
    # JSON (where the time went) and a final metrics snapshot (HBM /
    # spill / semaphore / shuffle / OOM totals, renderable with
    # python -m spark_rapids_tpu.tools.history --metrics-file). The
    # instrumented run is never the timed run.
    trace_dir = os.environ.get("SRTPU_BENCH_TRACE_DIR",
                               os.path.join(os.getcwd(), "bench_traces"))
    metrics_dir = os.environ.get("SRTPU_BENCH_METRICS_DIR",
                                 os.path.join(os.getcwd(),
                                              "bench_metrics"))
    trace_on = os.environ.get("SRTPU_BENCH_TRACE", "1") != "0"

    def capture_artifacts(name, eng_fn):
        """(trace_path, metrics_path) for one instrumented run; either
        may be None — best effort, a wedged capture never fails the
        rung."""
        if not trace_on:
            return None, None
        tpath = os.path.join(trace_dir, f"trace_{name}.json")
        mpath = os.path.join(metrics_dir, f"metrics_{name}.json")
        saved = {k: os.environ.get(k)
                 for k in ("SPARK_RAPIDS_TPU_TRACE_ENABLED",
                           "SPARK_RAPIDS_TPU_TRACE_OUTPUT",
                           "SPARK_RAPIDS_TPU_METRICS_ENABLED")}
        got_metrics = None
        try:
            os.makedirs(trace_dir, exist_ok=True)
            os.makedirs(metrics_dir, exist_ok=True)
            os.environ["SPARK_RAPIDS_TPU_TRACE_ENABLED"] = "true"
            os.environ["SPARK_RAPIDS_TPU_TRACE_OUTPUT"] = tpath
            os.environ["SPARK_RAPIDS_TPU_METRICS_ENABLED"] = "true"
            eng_fn()
            try:
                from spark_rapids_tpu.metrics import (registry_snapshot,
                                                      active_registry)
                reg = active_registry()
                if reg is not None:
                    with open(mpath, "w") as f:
                        json.dump({"rung": name,
                                   "snapshot": registry_snapshot(reg)},
                                  f, sort_keys=True, default=float)
                    got_metrics = mpath
            except Exception as e:           # noqa: BLE001 - best effort
                log(f"bench: {name} metrics snapshot failed: {e}")
            return tpath, got_metrics
        except Exception as e:               # noqa: BLE001 - best effort
            log(f"bench: {name} trace capture failed: {e}")
            return None, got_metrics
        finally:
            for k, v in saved.items():       # restore, don't clobber
                if v is None:
                    os.environ.pop(k, None)
                else:
                    os.environ[k] = v
            from spark_rapids_tpu.trace import install_tracer
            install_tracer(None)   # drop the buffer between rungs
            from spark_rapids_tpu.metrics import shutdown_metrics
            shutdown_metrics()     # stop the sampler between rungs

    details = {}
    skipped = []
    failed = []
    wrong = []
    for name, rows, eng_fn, base_fn, check_fn in workloads:
        elapsed = time.perf_counter() - ladder_t0
        if elapsed > budget:
            skipped.append(name)
            log(f"bench: {name:18s} SKIPPED (budget {budget:.0f}s "
                f"exhausted at {elapsed:.0f}s)")
            continue
        if name == "tpcds_q9_10m":
            _big.pop("l", None)      # last lineitem rung done: ~1 GB back
        try:
            from spark_rapids_tpu.plan import exec_cache
            cache0 = exec_cache.stats()
            t0 = time.perf_counter()
            eng_res = eng_fn()                # COLD run incl. compile
            warm = time.perf_counter() - t0
            cache_cold = exec_cache.stats()
            eng_s, eng_res = _time_min(eng_fn, iters)
            cache_warm = exec_cache.stats()
            placement = getattr(last_session[0], "last_placement",
                                None) or "?"
            # coded not-on-device summary (ISSUE 7; schema in
            # docs/tuning.md): the artifact itself says WHY a rung
            # stayed on host — {} for all-device rungs
            pl_report = getattr(last_session[0], "last_placement_report",
                                None) or {}
            base_s, base_res = _time_min(base_fn, iters)
        except Exception as e:                # noqa: BLE001
            # INFRA failure (OOM, backend error): must not discard the
            # finished rungs; listed in the summary, and the run exits
            # non-zero after printing it
            failed.append(name)
            log(f"bench: {name:18s} FAILED: {type(e).__name__}: {e}")
            continue
        try:
            check_fn(eng_res, base_res)       # per-workload, immediately
        except AssertionError as e:
            # WRONG ANSWER: a correctness regression always fails the
            # run (rc=1), unlike infra flakes above
            wrong.append(name)
            log(f"bench: {name:18s} WRONG RESULT: {e}")
            continue
        speedup = base_s / eng_s
        # cold-vs-warm compile split (ISSUE 6; schema note in
        # docs/tuning.md): warm_s keeps its historical meaning — the
        # FIRST run of the query in this process (the cold warm-up,
        # including every trace + XLA compile the persistent tier did
        # not serve); engine_s is the warm best-of-iters. The
        # executable-cache counter deltas attribute WHERE the cold cost
        # went and prove the warm iterations recompile nothing.
        details[name] = {
            "engine_s": round(eng_s, 4), "baseline_s": round(base_s, 4),
            "speedup": round(speedup, 3), "placement": placement,
            "rows_per_sec": round(rows / eng_s, 1),
            "warm_s": round(warm, 1), "checked": True,
            "placement_reasons": pl_report.get("codes") or {},
            "compile": {
                "cold": {k: round(cache_cold[k] - cache0[k], 3)
                         for k in cache_cold},
                "warm": {k: round(cache_warm[k] - cache_cold[k], 3)
                         for k in cache_warm},
            },
        }
        # adaptive-execution decisions the LAST engine run made
        # (ISSUE 19; kind -> count, {} when none fired — schema note in
        # docs/tuning.md): the ladder artifact shows WHETHER runtime
        # re-planning touched a rung, not just how fast it went
        aqe_counts = {}
        for d in getattr(last_session[0], "last_aqe_decisions",
                         None) or []:
            aqe_counts[d["kind"]] = aqe_counts.get(d["kind"], 0) + 1
        details[name]["aqe"] = aqe_counts
        # emit the metric line NOW — a later failure or timeout (even a
        # wedged best-effort trace run below) must never discard a
        # finished workload's result
        print(json.dumps({"metric": name + "_speedup", "value": speedup,
                          "unit": "x_vs_pandas", "vs_baseline": speedup,
                          "platform": jax.devices()[0].platform}),
              flush=True)
        cold_compile = details[name]["compile"]["cold"]["compile_s"]
        warm_compile = details[name]["compile"]["warm"]["compile_s"]
        log(f"bench: {name:18s} engine {eng_s:7.3f}s [{placement:6s}] "
            f"pandas {base_s:7.3f}s -> {speedup:5.2f}x "
            f"(cold {warm:.1f}s incl. {cold_compile:.1f}s compile; "
            f"warm recompiled {warm_compile:.1f}s, checked)")
        tr_path, m_path = capture_artifacts(name, eng_fn)
        details[name]["trace"] = tr_path
        details[name]["metrics"] = m_path

    # ---------------- distributed rung (subprocess) ----------------
    dist = None
    if os.environ.get("SRTPU_BENCH_DIST", "1") != "0" \
            and time.perf_counter() - ladder_t0 < budget:
        try:
            dist = run_distributed_rung(iters)
        except Exception as e:                       # noqa: BLE001
            log(f"bench: distributed rung error: {e}")
        if dist:
            log(f"bench: distributed(8dev) {dist}")

    # ---------------- regression gate ----------------
    prev = previous_bench()
    regressions = {}
    if prev:
        prev_path, prev_speeds = prev
        for k, d in details.items():
            p = prev_speeds.get(k)
            if p and d["speedup"] < 0.8 * p:
                regressions[k] = {"prev": p, "now": d["speedup"]}
        if regressions:
            log(f"bench: REGRESSIONS vs {os.path.basename(prev_path)}: "
                f"{regressions}")

    geo = (float(np.exp(np.mean([np.log(d["speedup"])
                                 for d in details.values()])))
           if details else 0.0)     # budget ate everything: valid JSON > NaN
    dev = [d["speedup"] for d in details.values()
           if d["placement"] == "device"]
    geo_dev = (float(np.exp(np.mean(np.log(dev)))) if dev else None)
    # one-line-diffable regression surface (schema note in
    # docs/tuning.md): top-level geomean + device/host rung tally, so
    # BENCH_rXX rounds compare on two keys instead of a details crawl
    placement_counts = {"device": 0, "host": 0}
    for d in details.values():
        placement_counts[d["placement"]] = \
            placement_counts.get(d["placement"], 0) + 1
    print(json.dumps({
        "metric": "ladder_geomean_speedup",
        "value": round(geo, 3),
        "unit": "x_vs_pandas",
        "vs_baseline": round(geo, 3),
        "geomean": round(geo, 3),
        "placement_counts": placement_counts,
        "platform": jax.devices()[0].platform,
        "device_only_geomean": (round(geo_dev, 3)
                                if geo_dev is not None else None),
        "device_workloads": len(dev),
        "skipped": skipped,
        "failed": failed,
        "wrong": wrong,
        "distributed": dist,
        "regressions": regressions,
        "wall_s": round(time.perf_counter() - START, 1),
        "details": details,
    }))
    # one-line machine-checkable delta vs the newest prior BENCH_r*.json
    # (ISSUE 15 satellite): the SAME differ the tools/regress CLI
    # exposes, so ladder rounds land with evidence, not eyeballed
    # geomeans — golden-tested in tests/test_ops.py
    try:
        from spark_rapids_tpu.tools.regress import (
            diff_bench, format_bench_delta, load_bench, normalize_bench)
        priors = _bench_artifacts()
        if priors and details:
            cur = normalize_bench({"geomean": round(geo, 3),
                                   "placement_counts": placement_counts,
                                   "details": details})
            delta = diff_bench(load_bench(priors[-1]), cur)
            log("bench: " + format_bench_delta(
                delta, os.path.basename(priors[-1])))
    except Exception as e:                           # noqa: BLE001
        log(f"bench: regress delta unavailable: {e}")

    if wrong or failed:
        # a wrong answer or a rung that did not run fails the run; the
        # finished rungs' lines and the summary above are already out
        sys.exit(1)


if __name__ == "__main__":
    main()
