"""Scale-test harness (ref integration_tests/.../scaletest + the datagen
module's ScaleTestDataGen: run a query set against generated data at a
chosen scale, record wall/memory/engine-placement per query, assert
correctness against the independent host oracle).

CLI::

    python -m spark_rapids_tpu.tools.scale_test \
        --rows 10000000 --queries q1,q6,q3,q9,q28 --iters 2 \
        --report scale_report.json

Not the benchmark (that is ``perfbench/``): scale and query set are
parameters, every query is verified against the host oracle (not
pandas), and the report captures the engine placement the cost optimizer
chose plus task metrics — the artifact a CI perf job diffs run-over-run.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from typing import Dict, List


def log(*a):
    print(*a, file=sys.stderr, flush=True)


def _queries(names: List[str], n_rows: int):
    from benchmarks import tpcds, tpch
    lineitem = store_sales = None
    if any(q in names for q in ("q1", "q6")):
        lineitem = tpch.gen_lineitem(n_rows)
    if any(q in names for q in ("q3", "q9", "q28")):
        store_sales = tpcds.gen_store_sales(n_rows)
    dd = tpcds.gen_date_dim() if "q3" in names else None
    it = tpcds.gen_item() if "q3" in names else None

    def build(sess, F, name):
        if name == "q1":
            return tpch.q1(sess.create_dataframe(lineitem), F)
        if name == "q6":
            return tpch.q6(sess.create_dataframe(lineitem), F)
        if name == "q3":
            return tpcds.q3(sess.create_dataframe(store_sales),
                            sess.create_dataframe(dd),
                            sess.create_dataframe(it), F)
        if name == "q9":
            return tpcds.q9(sess.create_dataframe(store_sales), F)
        if name == "q28":
            return tpcds.q28(sess.create_dataframe(store_sales), F)
        raise SystemExit(f"unknown query {name!r}")

    return build


def _placement(df) -> str:
    t = df._physical().tree_string()
    host = any(m in t for m in ("CpuAggregate", "CpuJoin", "CpuFilter",
                                "CpuProject", "CpuWindow"))
    return "host" if host else "device"


def _canon(table):
    """Order-insensitive canonical rows for oracle comparison."""
    rows = sorted(map(tuple, zip(*[c.to_pylist()
                                   for c in table.columns])))
    return rows


def run_scale_test(n_rows: int, names: List[str], iters: int,
                   verify: bool = True) -> Dict:
    from spark_rapids_tpu.api import TpuSession, functions as F
    build = _queries(names, n_rows)
    report = {"rows": n_rows, "queries": {}}
    for name in names:
        sess = TpuSession()
        df = build(sess, F, name)
        t0 = time.perf_counter()
        out = df.collect_arrow()
        warm = time.perf_counter() - t0
        best = warm
        for _ in range(max(iters - 1, 0)):
            # fresh session per iteration: the cost optimizer re-plans
            # from this run's recorded statistics (the adaptive loop a
            # CI perf job should exercise, not bypass)
            sess = TpuSession()
            df = build(sess, F, name)
            t0 = time.perf_counter()
            out = df.collect_arrow()
            best = min(best, time.perf_counter() - t0)
        entry = {
            "warm_s": round(warm, 4),
            "best_s": round(best, 4),
            "rows_per_sec": round(n_rows / best, 1),
            "placement": _placement(df),
            "output_rows": out.num_rows,
        }
        m = sess.last_query_metrics or {}
        if m:
            entry["metrics"] = {k: v for k, v in m.items()
                                if isinstance(v, (int, float))}
        if verify:
            oracle_sess = TpuSession(
                {"spark.rapids.tpu.sql.enabled": "false"})
            expect = build(oracle_sess, F, name).collect_arrow()
            got_rows, exp_rows = _canon(out), _canon(expect)
            if len(got_rows) != len(exp_rows):
                raise AssertionError(
                    f"{name}: {len(got_rows)} rows vs oracle "
                    f"{len(exp_rows)}")
            for g, e in zip(got_rows, exp_rows):
                for gv, ev in zip(g, e):
                    if isinstance(gv, float) and isinstance(ev, float):
                        if abs(gv - ev) > 1e-6 * max(abs(ev), 1.0):
                            raise AssertionError(
                                f"{name}: {gv} != oracle {ev}")
                    elif gv != ev:
                        raise AssertionError(
                            f"{name}: {gv!r} != oracle {ev!r}")
            entry["verified"] = True
        report["queries"][name] = entry
        log(f"scale: {name:4s} rows={n_rows} best={best:.3f}s "
            f"({entry['placement']}) ok")
    return report


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--rows", type=int, default=1_000_000)
    ap.add_argument("--queries", default="q1,q6,q3,q9,q28")
    ap.add_argument("--iters", type=int, default=2)
    ap.add_argument("--no-verify", action="store_true",
                    help="skip the host-oracle comparison (pure timing)")
    ap.add_argument("--report", default="",
                    help="write the JSON report here (default stdout)")
    args = ap.parse_args(argv)
    names = [q.strip() for q in args.queries.split(",") if q.strip()]
    report = run_scale_test(args.rows, names, args.iters,
                            verify=not args.no_verify)
    text = json.dumps(report, indent=2)
    if args.report:
        with open(args.report, "w") as f:
            f.write(text + "\n")
        log(f"scale: report -> {args.report}")
    else:
        print(text)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
