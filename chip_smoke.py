"""chip_smoke.py — the quickest proof that the engine still starts on the chip.

Run from the repo root, in ONE process, on a machine with a TPU:

    python chip_smoke.py              # one chip: TPC-H q6/q1 over Parquet + TPC-DS q3
    python chip_smoke.py --chips 4    # four chips: the SPMD path and its comparison, only

It drives the normal query path (``TpuSession.read_parquet`` / temp views /
``session.sql(...).collect_arrow()``) at a real size, pins the device path
and ASSERTS it was taken (a right answer alone proves nothing: the cost
optimizer, the OOM ladder and the host twins can all produce one without
the chip), and checks every result against a pandas reference that shares
no code with the engine. The reference is accumulated from the generated
chunks BEFORE they are written, so the Parquet round trip is under test
too.

The last line of standard output is one JSON object,
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``;
any failed phase exits non-zero and prints no such line. Without a TPU the
script fails before it imports the engine. Everything it writes (Parquet,
spill files) lands in ``chip_smoke_scratch/`` beside this file and is
removed at the end.
"""
from __future__ import annotations

import argparse
import contextlib
import io
import json
import os
import shutil
import sys
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
SCRATCH = os.path.join(HERE, "chip_smoke_scratch")

#: BASELINE.json config 2 is TPC-H SF10: ~60M lineitem rows
LINEITEM_ROWS = 60_000_000
STORE_SALES_ROWS = 10_000_000
#: rows per generated chunk == rows per Parquet row group == the engine's
#: default batchSizeRows, so host memory stays bounded by one chunk
CHUNK_ROWS = 1 << 20

#: q3's batch cut. The operator pipeline's join and sort-based group-by
#: kernels each carry a variadic multi-key lax.sort, and the TPU compiler
#: needs minutes for one at the default 1,048,576-row batch shape (PERF.md,
#: PR 21); q3 has six such modules, and its aggregate merges partials in
#: chunks of up to batchSizeRows rows through two more. So store_sales
#: streams in batches of half a shape bucket — the join sizes its output at
#: 1.5x the last batch's and the bucket ladder steps 8x (8192 -> 65536),
#: so every sort stays in the 8192-row bucket — and sql.batchSizeRows caps
#: the merge fan-in at the same bucket.
Q3_BATCH_ROWS = 4096
Q3_CONF = {"spark.rapids.tpu.sql.batchSizeRows": 8192}
Q3_BATCH_CUT_REASON = (
    "at the default batch shape q3's sort-bearing modules (two joins x "
    "count and fused form, the group-by's and its merge's sort and pack) "
    "need more cold compile than this run's time limit holds; the row "
    "count is not cut")

#: placement codes that mean "the plan (or part of it) left the device"
HOST_REVERT_CODES = ("WHOLE_PLAN_HOST_REVERT", "COST_MODEL_HOST",
                     "OOM_PRESSURE_HOST")

#: conf of the ASSERTED runs: the cost optimizer (ON by default) may send
#: whole plans to the host twin, so it is pinned off; metrics on (no
#: sampler thread) so the OOM-fallback counter exists to be read
PINNED_CONF = {
    "spark.rapids.tpu.sql.optimizer.enabled": False,
    # the mesh is --chips 4's business: on one device this changes
    # nothing (no mesh is built), on a rehearsal host with several
    # virtual devices it keeps the one-chip phases on one device
    "spark.rapids.tpu.distributed.enabled": False,
    "spark.rapids.tpu.metrics.enabled": True,
    "spark.rapids.tpu.metrics.sample.intervalMs": 0,
}


def say(*a):
    print(*a, flush=True)


class SmokeFailure(AssertionError):
    """A phase of the smoke did not hold."""


def require(cond, msg: str):
    if not cond:
        raise SmokeFailure(msg)


# ---------------------------------------------------------------------------
# the device
# ---------------------------------------------------------------------------

def require_tpu(chips: int) -> dict:
    """The first thing the script does: JAX must report a TPU, with the
    device count the chosen path needs. No platform override anywhere."""
    import jax
    devs = jax.devices()
    d = {"platform": devs[0].platform, "kind": devs[0].device_kind,
         "count": len(devs)}
    if d["platform"] != "tpu":
        raise SystemExit(f"chip_smoke: no TPU — jax reports {d}")
    if d["count"] != chips:
        raise SystemExit(
            f"chip_smoke: this path needs {chips} chip(s), jax reports {d}")
    return d


# ---------------------------------------------------------------------------
# data + the plain reference (pandas; no engine code)
# ---------------------------------------------------------------------------

class LineitemReference:
    """TPC-H q1 and q6 over the generated chunks, as plain pandas partial
    sums merged at the end — bounded memory at any row count."""

    Q1_CUTOFF = np.datetime64("1998-12-01") - np.timedelta64(90, "D")

    def __init__(self):
        self.q1 = None
        self.q6 = 0.0
        self.rows = 0

    def add(self, chunk) -> None:
        pdf = chunk.select(
            ["l_quantity", "l_extendedprice", "l_discount", "l_tax",
             "l_returnflag", "l_linestatus", "l_shipdate"]
        ).to_pandas(date_as_object=False)
        self.rows += len(pdf)
        ship = pdf["l_shipdate"].to_numpy().astype("datetime64[D]")
        f = pdf[ship <= self.Q1_CUTOFF].copy()
        f["disc_price"] = f["l_extendedprice"] * (1.0 - f["l_discount"])
        f["charge"] = f["disc_price"] * (1.0 + f["l_tax"])
        part = f.groupby(["l_returnflag", "l_linestatus"]).agg(
            sum_qty=("l_quantity", "sum"),
            sum_base_price=("l_extendedprice", "sum"),
            sum_disc_price=("disc_price", "sum"),
            sum_charge=("charge", "sum"),
            sum_disc=("l_discount", "sum"),
            count_order=("l_quantity", "size"))
        self.q1 = part if self.q1 is None else self.q1.add(part,
                                                           fill_value=0)
        m = ((ship >= np.datetime64("1994-01-01"))
             & (ship < np.datetime64("1995-01-01"))
             & (pdf["l_discount"] >= 0.05) & (pdf["l_discount"] <= 0.07)
             & (pdf["l_quantity"] < 24.0))
        g = pdf[m]
        self.q6 += float((g["l_extendedprice"] * g["l_discount"]).sum())

    def q1_result(self):
        r = self.q1.sort_index().copy()
        n = r["count_order"]
        r["avg_qty"] = r["sum_qty"] / n
        r["avg_price"] = r["sum_base_price"] / n
        r["avg_disc"] = r["sum_disc"] / n
        r["count_order"] = n.astype(np.int64)
        return r.drop(columns=["sum_disc"])


def write_lineitem_parquet(path: str, rows: int, seed: int,
                           chunk_rows: int = CHUNK_ROWS
                           ) -> LineitemReference:
    """Generate ``rows`` lineitem rows chunk by chunk — chunk ``i`` from
    ``(seed, i)`` — writing each as one row group of ONE Parquet file and
    folding it into the pandas reference on the way."""
    import pyarrow.parquet as pq

    from benchmarks import tpch
    ref = LineitemReference()
    writer = None
    try:
        for i, off in enumerate(range(0, rows, chunk_rows)):
            chunk = tpch.gen_lineitem(min(chunk_rows, rows - off),
                                      seed=(seed, i), total_rows=rows)
            if writer is None:
                writer = pq.ParquetWriter(path, chunk.schema)
            writer.write_table(chunk, row_group_size=chunk_rows)
            ref.add(chunk)
    finally:
        if writer is not None:
            writer.close()
    return ref


def gen_tpcds(ss_rows: int, seed: int):
    from benchmarks import tpcds
    return (tpcds.gen_store_sales(ss_rows, seed=seed + 1),
            tpcds.gen_date_dim(), tpcds.gen_item())


def reference_q3(store_sales, date_dim, item):
    """TPC-DS q3 in pandas (the query of benchmarks/queries_sql.TPCDS_Q3)."""
    dd = date_dim.to_pandas(date_as_object=False)
    it = item.to_pandas()
    dd = dd[dd["d_moy"] == 11][["d_date_sk", "d_year"]]
    it = it[it["i_manufact_id"] == 128][["i_item_sk", "i_brand_id",
                                         "i_brand"]]
    ss = store_sales.select(["ss_sold_date_sk", "ss_item_sk",
                             "ss_ext_sales_price"]).to_pandas()
    ss = ss[ss["ss_item_sk"].isin(it["i_item_sk"])
            & ss["ss_sold_date_sk"].isin(dd["d_date_sk"])]
    j = ss.merge(dd, left_on="ss_sold_date_sk", right_on="d_date_sk")
    j = j.merge(it, left_on="ss_item_sk", right_on="i_item_sk")
    g = (j.groupby(["d_year", "i_brand_id", "i_brand"], as_index=False)
         ["ss_ext_sales_price"].sum()
         .rename(columns={"ss_ext_sales_price": "sum_agg"}))
    return g.sort_values(["d_year", "sum_agg", "i_brand_id"],
                         ascending=[True, False, True]
                         ).reset_index(drop=True)


def gen_string_agg_table(rows: int, seed: int):
    """The string-keyed aggregation input of the SPMD phase: 500
    distinct keys, one double."""
    import pyarrow as pa
    rng = np.random.RandomState(seed + 2)
    keys = pa.array([f"k{i:03d}" for i in range(500)])
    return pa.table({"k": keys.take(pa.array(rng.randint(0, 500, rows))),
                     "v": pa.array(rng.uniform(-10, 10, rows))})


STRING_AGG_SQL = ("SELECT k, sum(v) AS sv, count(*) AS n "
                  "FROM string_keyed GROUP BY k")


def reference_string_agg(table):
    return (table.to_pandas().groupby("k", as_index=False)
            .agg(sv=("v", "sum"), n=("v", "size")))


# ---------------------------------------------------------------------------
# comparisons (rtol 1e-9 on sums, counts exact)
# ---------------------------------------------------------------------------

RTOL = 1e-9


def check_q6(res, want: float):
    require(res.num_rows == 1, f"q6 returned {res.num_rows} rows")
    np.testing.assert_allclose(res.column("revenue")[0].as_py(), want,
                               rtol=RTOL)


def check_q1(res, want):
    got = (res.to_pandas().set_index(["l_returnflag", "l_linestatus"]))
    require(list(got.index) == sorted(got.index),
            "q1 result is not ordered by its keys")
    require(list(got.index) == list(want.index),
            f"q1 groups {list(got.index)} != {list(want.index)}")
    for c in ("sum_qty", "sum_base_price", "sum_disc_price", "sum_charge",
              "avg_qty", "avg_price", "avg_disc"):
        np.testing.assert_allclose(got[c].to_numpy(), want[c].to_numpy(),
                                   rtol=RTOL, err_msg=c)
    np.testing.assert_array_equal(got["count_order"].to_numpy(),
                                  want["count_order"].to_numpy())


def check_q3(res, want):
    got = res.to_pandas()
    require(len(got) == len(want), f"q3 rows {len(got)} != {len(want)}")
    keys = ["d_year", "i_brand_id", "i_brand"]
    g = got.sort_values(keys).reset_index(drop=True)
    w = want.sort_values(keys).reset_index(drop=True)
    for k in keys:
        np.testing.assert_array_equal(g[k].to_numpy(), w[k].to_numpy(),
                                      err_msg=k)
    np.testing.assert_allclose(g["sum_agg"].to_numpy(),
                               w["sum_agg"].to_numpy(), rtol=RTOL)
    # ORDER BY d_year, sum_agg DESC, i_brand_id
    order = got.sort_values(["d_year", "sum_agg", "i_brand_id"],
                            ascending=[True, False, True], kind="stable")
    require(list(order.index) == list(got.index),
            "q3 result is not in ORDER BY order")


def check_string_agg(res, want):
    got = res.to_pandas().sort_values("k").reset_index(drop=True)
    want = want.sort_values("k").reset_index(drop=True)
    require(len(got) == len(want), f"agg rows {len(got)} != {len(want)}")
    np.testing.assert_array_equal(got["k"].to_numpy(), want["k"].to_numpy())
    np.testing.assert_allclose(got["sv"].to_numpy(), want["sv"].to_numpy(),
                               rtol=RTOL)
    np.testing.assert_array_equal(got["n"].to_numpy(), want["n"].to_numpy())


# ---------------------------------------------------------------------------
# running one query and asserting where it ran
# ---------------------------------------------------------------------------

def explain_text(df) -> str:
    """``explain()`` prints as well as returns; keep the one copy."""
    with contextlib.redirect_stdout(io.StringIO()):
        return df.explain()


#: compile requests that went through jax's persistent cache (hit or
#: not), counted by a jax.monitoring listener installed on first use
_CACHE_REQUESTS = {"listening": False, "n": 0}


def _count_cache_request(event: str, **_kw) -> None:
    if event == "/jax/compilation_cache/compile_requests_use_cache":
        _CACHE_REQUESTS["n"] += 1


def timed_run(df):
    """(arrow result, seconds, exec-cache counter deltas) of one
    ``collect_arrow()`` — the sink fetches the result, so the device work
    is inside the timed region. ``compile_s`` is jax's backend-compile
    duration, which also covers the READ of a persistent-cache hit;
    ``persistent_misses`` (requests the persistent cache could not serve)
    is what counts real XLA compiles in a process that found a warm
    cache."""
    from jax import monitoring
    from spark_rapids_tpu.plan import exec_cache
    if not _CACHE_REQUESTS["listening"]:
        monitoring.register_event_listener(_count_cache_request)
        _CACHE_REQUESTS["listening"] = True
    c0, r0 = exec_cache.stats(), _CACHE_REQUESTS["n"]
    t0 = time.perf_counter()
    res = df.collect_arrow()
    dt = time.perf_counter() - t0
    c1 = exec_cache.stats()
    delta = {k: round(c1[k] - c0[k], 3) for k in c1}
    delta["persistent_misses"] = (_CACHE_REQUESTS["n"] - r0
                                  - delta["persistent_hits"])
    return res, dt, delta


def assert_on_device(name: str, session, plan: str) -> None:
    """The pinned run took the device path — not trusted from the answer."""
    rep = session.last_placement_report or {}
    require(session.last_placement == "device",
            f"{name}: last_placement={session.last_placement!r}, "
            f"report={rep}")
    bad = [c for c in HOST_REVERT_CODES if c in (rep.get("codes") or {})]
    require(not bad, f"{name}: host-revert codes {bad} in {rep}")
    require("host_fallback=" not in plan,
            f"{name}: explain() shows a host_fallback column:\n{plan}")


#: runs allowed before a query must repeat compile-free: cold; one repeat
#: that may still compile (a join switches to its fused one-dispatch
#: kernel once the first run has measured its output size); warm
MAX_RUNS_TO_WARM = 3


def run_pinned(name: str, session, sql: str, check, memory) -> dict:
    """Cold run, then repeats until one compiles nothing (the warm run);
    every run is checked against the reference and asserted on device."""
    df = session.sql(sql)
    plan = explain_text(df)
    say(f"[{name}] plan (optimizer pinned off):\n{plan.rstrip()}")
    runs = []
    for i in range(MAX_RUNS_TO_WARM):
        res, dt, delta = timed_run(df)
        check(res)
        assert_on_device(name, session, plan)
        runs.append({"seconds": dt, "cache": delta})
        say(f"[{name}] run {i} ({'cold' if i == 0 else 'repeat'}): "
            f"rows={res.num_rows} seconds={dt:.3f} exec_cache={delta} "
            f"placement={session.last_placement} "
            f"report={session.last_placement_report}")
        if i > 0 and delta["compile_s"] == 0 and delta["misses"] == 0:
            break
    warm = runs[-1]
    require(len(runs) > 1 and warm["cache"]["compile_s"] == 0
            and warm["cache"]["misses"] == 0,
            f"{name}: still compiling after {len(runs)} runs: {runs}")
    st = memory.stats()
    say(f"[{name}] cold_s={runs[0]['seconds']:.3f} "
        f"warm_s={warm['seconds']:.3f} runs_to_warm={len(runs)} "
        f"cold_compile_s={runs[0]['cache']['compile_s']} "
        f"persistent_hits={sum(r['cache']['persistent_hits'] for r in runs)} "
        f"persistent_misses="
        f"{sum(r['cache']['persistent_misses'] for r in runs)} "
        f"hbm_budget={st['budget']} hbm_peak={st['max_device_used']} "
        f"checked=pandas")
    return {"cold_s": runs[0]["seconds"], "warm_s": warm["seconds"],
            "runs": runs}


def oom_host_fallbacks() -> int:
    """Sum of ``srtpu_oom_host_fallback_total`` over its series."""
    from spark_rapids_tpu.metrics.registry import active_registry
    reg = active_registry()
    require(reg is not None, "metric registry is not installed")
    ent = reg.snapshot().get("srtpu_oom_host_fallback_total")
    return int(sum(s["value"] for s in ent["series"])) if ent else 0


def assert_arrays_on(platform: str, arrays, what: str) -> None:
    for a in arrays:
        plats = {d.platform for d in a.devices()}
        require(plats == {platform},
                f"{what}: array lives on {plats}, expected {platform}")


# ---------------------------------------------------------------------------
# the one-chip path
# ---------------------------------------------------------------------------

def run_one_chip(platform: str, rows: int, ss_rows: int, seed: int,
                 scratch: str) -> dict:
    """Every phase of the one-chip smoke; raises on the first that fails.
    ``platform`` is what device arrays must live on — the platform
    ``require_tpu`` asserted when run as a script."""
    from benchmarks import queries_sql as Q
    from spark_rapids_tpu.api import TpuSession
    from spark_rapids_tpu.config import TpuConf
    from spark_rapids_tpu.mem import MemoryManager
    from spark_rapids_tpu.mem.native_spill import get_store

    os.makedirs(scratch, exist_ok=True)
    spill_dir = os.path.join(scratch, "spill")
    pinned = dict(PINNED_CONF)
    pinned["spark.rapids.tpu.memory.spillDir"] = spill_dir
    # q3's join runs through the OPERATOR pipeline here. The fused
    # one-device fragment (sql.fusedPipeline.enabled, the default) is a
    # single-batch program: above the largest shape bucket (4,194,304
    # rows) it hands over to exactly this pipeline at run time while
    # explain() still names the fragment, and below it the fragment
    # re-compiles once per fragment layer while it learns its bounds
    # (four compiles for q3). Pinned off, explain() shows what runs.
    pinned["spark.rapids.tpu.sql.fusedPipeline.enabled"] = False

    # ---- data + reference
    t0 = time.perf_counter()
    li_path = os.path.join(scratch, "lineitem.parquet")
    ref = write_lineitem_parquet(li_path, rows, seed)
    require(ref.rows == rows, f"generated {ref.rows} rows, wanted {rows}")
    say(f"[data] lineitem: {rows} rows x 10 columns -> {li_path} "
        f"({os.path.getsize(li_path)} bytes, "
        f"{-(-rows // CHUNK_ROWS)} row groups) in "
        f"{time.perf_counter() - t0:.1f}s")
    t0 = time.perf_counter()
    store_sales, date_dim, item = gen_tpcds(ss_rows, seed)
    want_q3 = reference_q3(store_sales, date_dim, item)
    want_q1 = ref.q1_result()
    say(f"[data] store_sales: {ss_rows} rows x {store_sales.num_columns} "
        f"columns, date_dim {date_dim.num_rows}, item {item.num_rows}; "
        f"pandas references ready in {time.perf_counter() - t0:.1f}s")

    def new_session(conf, cut_q3_batches=False):
        """The smoke's four views. An in-memory scan emits one batch per
        partition (up to 1,048,576 rows each), so q3's batch cut
        partitions store_sales."""
        if cut_q3_batches:
            conf = dict(conf, **Q3_CONF)
        s = TpuSession(conf)
        s.read_parquet(li_path).create_or_replace_temp_view("lineitem")
        parts = -(-ss_rows // Q3_BATCH_ROWS) if cut_q3_batches else 1
        s.create_dataframe(store_sales, num_partitions=parts) \
            .create_or_replace_temp_view("store_sales")
        for name, t in (("date_dim", date_dim), ("item", item)):
            s.create_dataframe(t).create_or_replace_temp_view(name)
        return s

    # ---- the asserted runs: optimizer pinned off
    memory = MemoryManager.get(TpuConf(pinned))
    say(f"[mem] hbm_budget={memory.budget} bytes "
        f"(device bytes_limit x allocFraction), "
        f"oom_state_machine={memory.state_machine}, spill_store="
        f"{'native' if get_store(spill_dir) is not None else 'python'}")
    say(f"[cut] tpcds_q3 streams store_sales in {Q3_BATCH_ROWS}-row "
        f"batches with {Q3_CONF} (default 1048576 for both, which "
        f"tpch_q6 and tpch_q1 keep): {Q3_BATCH_CUT_REASON}")
    queries = (
        ("tpch_q6", Q.TPCH_Q6, lambda r: check_q6(r, ref.q6), False),
        ("tpch_q1", Q.TPCH_Q1, lambda r: check_q1(r, want_q1), False),
        ("tpcds_q3", Q.TPCDS_Q3, lambda r: check_q3(r, want_q3), True),
    )
    report = {}
    for name, sql, check, cut in queries:
        with new_session(pinned, cut) as session:
            report[name] = run_pinned(name, session, sql, check, memory)

    # ---- a device column handed to the user lives on the device
    with new_session(pinned) as session:
        batches = session.sql("SELECT i_item_sk, i_brand_id + 1 AS b "
                              "FROM item").to_device_columns()
    require(sum(b["num_rows"] for b in batches) == item.num_rows,
            "to_device_columns lost rows")
    assert_arrays_on(platform,
                     [a for b in batches for pair in b["columns"].values()
                      for a in pair], "to_device_columns")
    say(f"[device] to_device_columns: {len(batches)} batch(es), "
        f"{item.num_rows} rows, every array on platform={platform}")
    del batches

    # ---- nothing degraded to the host under memory pressure
    n_oom = oom_host_fallbacks()
    require(n_oom == 0, f"srtpu_oom_host_fallback_total={n_oom}")
    say(f"[oom] srtpu_oom_host_fallback_total=0 "
        f"hbm_peak={memory.stats()['max_device_used']}")

    # ---- once more with DEFAULT settings: which engine does the default
    # choose? Printed, not judged (ROADMAP Speed 2 re-derives placement);
    # the answer is still checked. (q3 keeps its batch cut.)
    defaults = {"spark.rapids.tpu.memory.spillDir": spill_dir,
                "spark.rapids.tpu.distributed.enabled":
                    PINNED_CONF["spark.rapids.tpu.distributed.enabled"]}
    for name, sql, check, cut in queries:
        with new_session(defaults, cut) as default:
            res, dt, delta = timed_run(default.sql(sql))
            check(res)
            rep = default.last_placement_report or {}
            say(f"[{name}] default settings: placement="
                f"{default.last_placement} verdict={rep.get('verdict')} "
                f"codes={rep.get('codes')} seconds={dt:.3f} "
                f"exec_cache={delta} checked=pandas")
            report[name]["default_placement"] = default.last_placement
    return report


# ---------------------------------------------------------------------------
# the four-chip path (--chips 4): the SPMD program and what it is compared
# with, nothing else
# ---------------------------------------------------------------------------

def _distributed_exec(physical):
    from spark_rapids_tpu.parallel.planner import DistributedPipelineExec
    if isinstance(physical, DistributedPipelineExec):
        return physical
    for c in physical.children:
        found = _distributed_exec(c)
        if found is not None:
            return found
    return None


def run_distributed(name: str, session, sql: str, n_dev: int):
    """One query on the mesh through the session's normal execution
    wrapper, keeping the physical plan so the SPMD program's own inputs
    and outputs can be inspected: every one of them must be laid out
    over all ``n_dev`` devices, and the compiled program must exchange
    rows with an all-to-all."""
    df = session.sql(sql)
    plan = explain_text(df)
    say(f"[{name}] distributed plan:\n{plan.rstrip()}")
    require("DistributedPipeline" in plan,
            f"{name}: no DistributedPipeline in\n{plan}")
    t0 = time.perf_counter()
    physical, res = df._execute_wrapped(
        lambda p, ctx: (p, p.collect(ctx)))
    dt = time.perf_counter() - t0
    ex = _distributed_exec(physical)
    require(ex is not None and ex.n_dev == n_dev,
            f"{name}: executed plan has no {n_dev}-device pipeline")
    fn, inputs, outs = ex.last_run
    mesh_devs = set(np.asarray(ex.mesh.devices).flat)
    require(len(mesh_devs) == n_dev, f"{name}: mesh is {mesh_devs}")
    for what, arrays in (("input", inputs), ("result", outs)):
        for a in arrays:
            on = {s.device for s in a.addressable_shards}
            require(on == mesh_devs,
                    f"{name}: {what} array {a.shape} occupies {on}, "
                    f"not all of {mesh_devs}")
    hlo = fn.lower(*inputs).compile().as_text()
    require("all-to-all" in hlo,
            f"{name}: compiled SPMD program has no all-to-all")
    say(f"[{name}] distributed: rows={res.num_rows} seconds={dt:.3f} "
        f"n_dev={n_dev} inputs={len(inputs)} arrays and "
        f"{len(outs)} result arrays on all {n_dev} devices, "
        f"all-to-all ops in compiled program="
        f"{hlo.count('all-to-all-start') or hlo.count('all-to-all(')}")
    return res


#: sizes of the mesh phase. An SPMD fragment is ONE module holding every
#: sort of its joins and aggregation, so its compile time is what bounds
#: the phase (PERF.md, PR 21): per device, q3 stays in the 8192-row bucket
#: (the dimension tables already fill it) and the string-keyed
#: aggregation in the 1024-row bucket, whose sorts compile in seconds.
MESH_Q3_ROWS_PER_DEVICE = 8192
MESH_AGG_ROWS_PER_DEVICE = 1024
#: speculative bounds of the mesh fragment, pinned to the shard's own
#: bucket: every store_sales row joins exactly one date and one item, so
#: a join emits no more rows than its shard holds, and no device receives
#: more groups than that. The defaults (2x, 65536) would carry every
#: later sort into the next buckets and their compile times.
MESH_CONF = {
    "spark.rapids.tpu.distributed.enabled": True,
    "spark.rapids.tpu.distributed.joinOutFactor": 1,
    "spark.rapids.tpu.distributed.maxPartialGroups": 8192,
}


def run_four_chips(n_dev: int, seed: int) -> None:
    """TPC-DS q3 and one string-keyed aggregation with
    ``distributed.enabled`` on a mesh of ``n_dev`` devices, against the
    one-device result of the same queries and pandas."""
    from benchmarks import queries_sql as Q
    from spark_rapids_tpu.api import TpuSession
    from spark_rapids_tpu.parallel import make_mesh

    ss_rows = n_dev * MESH_Q3_ROWS_PER_DEVICE
    agg_rows = n_dev * MESH_AGG_ROWS_PER_DEVICE
    store_sales, date_dim, item = gen_tpcds(ss_rows, seed)
    strings = gen_string_agg_table(agg_rows, seed)
    want = {"tpcds_q3": reference_q3(store_sales, date_dim, item),
            "string_agg": reference_string_agg(strings)}
    say(f"[cut] mesh phase sizes: store_sales {ss_rows} rows "
        f"({MESH_Q3_ROWS_PER_DEVICE}/device), string_keyed {agg_rows} rows "
        f"({MESH_AGG_ROWS_PER_DEVICE}/device), bounds {MESH_CONF}: an SPMD "
        f"fragment is one module holding all its sorts, and its compile "
        f"time — not the chips — bounds this phase")

    def new_session(conf, mesh=None, cut_q3_batches=False):
        s = TpuSession(conf, mesh=mesh)
        parts = -(-ss_rows // Q3_BATCH_ROWS) if cut_q3_batches else 1
        s.create_dataframe(store_sales, num_partitions=parts) \
            .create_or_replace_temp_view("store_sales")
        for name, t in (("date_dim", date_dim), ("item", item),
                        ("string_keyed", strings)):
            s.create_dataframe(t).create_or_replace_temp_view(name)
        return s

    dist = new_session(dict(PINNED_CONF, **MESH_CONF),
                       mesh=make_mesh(n_dev))
    # the comparison runs the one-chip smoke's own path: operator
    # pipeline, q3's batch cut
    one = new_session(
        dict(PINNED_CONF, **Q3_CONF,
             **{"spark.rapids.tpu.sql.fusedPipeline.enabled": False}),
        cut_q3_batches=True)
    checks = {"tpcds_q3": check_q3, "string_agg": check_string_agg}
    for name, sql in (("tpcds_q3", Q.TPCDS_Q3),
                      ("string_agg", STRING_AGG_SQL)):
        got = run_distributed(name, dist, sql, n_dev)
        require(got.num_rows > 0, f"{name}: empty result proves nothing")
        checks[name](got, want[name])
        single, dt, _ = timed_run(one.sql(sql))
        require(one.last_placement == "device",
                f"{name}: one-chip comparison ran on {one.last_placement}")
        checks[name](single, want[name])
        # the mesh result against the one-chip result of the same query
        checks[name](got, single.to_pandas())
        say(f"[{name}] one-chip comparison: seconds={dt:.3f}; mesh result "
            f"== one-chip result == pandas (rtol {RTOL})")
    dist.close()
    one.close()


# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=20260926)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs ONLY the distributed (SPMD) phase and "
                         "its comparison, on a four-chip host")
    ap.add_argument("--rows", type=int, default=LINEITEM_ROWS,
                    help="one chip: lineitem rows (default: TPC-H SF10, "
                         "~60M)")
    ap.add_argument("--ss-rows", type=int, default=STORE_SALES_ROWS,
                    help="one chip: store_sales rows")
    args = ap.parse_args(argv)

    device = require_tpu(args.chips)
    # learned walls of an earlier run must never steer this one
    # (plan/stats_store.py keeps them on disk and they overrule the model)
    os.environ["SRTPU_STATS_PERSIST"] = "0"
    say(f"[device] {device}; seed={args.seed}")
    if args.chips == 1 and args.rows != LINEITEM_ROWS:
        say(f"[cut] lineitem rows cut from {LINEITEM_ROWS} (TPC-H SF10) "
            f"to {args.rows}; columns unchanged")
    if args.chips == 1 and args.ss_rows != STORE_SALES_ROWS:
        say(f"[cut] store_sales rows {args.ss_rows} instead of "
            f"{STORE_SALES_ROWS}")
    t0 = time.perf_counter()
    shutil.rmtree(SCRATCH, ignore_errors=True)
    try:
        if args.chips == 4:
            run_four_chips(4, args.seed)
        else:
            run_one_chip(device["platform"], args.rows, args.ss_rows,
                         args.seed, SCRATCH)
    finally:
        shutil.rmtree(SCRATCH, ignore_errors=True)
        from spark_rapids_tpu.metrics import shutdown_metrics
        shutdown_metrics()
    say(f"[done] all phases passed in {time.perf_counter() - t0:.1f}s")
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
