"""The plain reference for TPC-H Q1 and Q6: pandas over the generated
chunks, as partial sums merged at the end (bounded memory at any row
count). A copy of ``chip_smoke.py``'s ``LineitemReference`` and checks
(PR 24; original listed in PERF.md, Open questions). It imports nothing of
the engine and takes nothing the engine made.

``precision`` is the configuration's own (``float64``) for the reference,
and the next one below (``float32``) for the CONTROL: the same arithmetic
on columns rounded to float32, accumulated in float32. The control has to
come out as not correct (perfbench/tests/test_control.py).
"""
from __future__ import annotations

import numpy as np
import pandas as pd

Q1_CUTOFF = np.datetime64("1998-12-01") - np.timedelta64(90, "D")
_FLOATS = ["l_quantity", "l_extendedprice", "l_discount", "l_tax"]
_Q1_SUMS = ["sum_qty", "sum_base_price", "sum_disc_price", "sum_charge"]
_Q1_AVGS = ["avg_qty", "avg_price", "avg_disc"]
#: "cannot be compared" (a shape mismatch); finite, so that the result
#: line stays valid JSON
INF = 1e300


def _frame(chunk, columns, precision: str) -> pd.DataFrame:
    pdf = chunk.select(columns).to_pandas(date_as_object=False)
    if precision != "float64":
        for c in _FLOATS:
            if c in pdf:
                pdf[c] = pdf[c].astype(precision)
    return pdf


def _partial_q6(tables, precision):
    pdf = _frame(tables["lineitem"], ["l_quantity", "l_extendedprice",
                                      "l_discount", "l_shipdate"], precision)
    ship = pdf["l_shipdate"].to_numpy().astype("datetime64[D]")
    one = np.dtype(precision).type
    m = ((ship >= np.datetime64("1994-01-01"))
         & (ship < np.datetime64("1995-01-01"))
         & (pdf["l_discount"] >= one(0.05)) & (pdf["l_discount"] <= one(0.07))
         & (pdf["l_quantity"] < one(24.0)))
    g = pdf[m]
    return (g["l_extendedprice"] * g["l_discount"]).sum()


def _merge_q6(states, precision):
    return float(np.sum(np.asarray(states, dtype=precision)))


def _partial_q1(tables, precision):
    pdf = _frame(tables["lineitem"],
                 _FLOATS + ["l_returnflag", "l_linestatus", "l_shipdate"],
                 precision)
    one = np.dtype(precision).type(1.0)
    ship = pdf["l_shipdate"].to_numpy().astype("datetime64[D]")
    f = pdf[ship <= Q1_CUTOFF].copy()
    f["disc_price"] = f["l_extendedprice"] * (one - f["l_discount"])
    f["charge"] = f["disc_price"] * (one + f["l_tax"])
    return f.groupby(["l_returnflag", "l_linestatus"]).agg(
        sum_qty=("l_quantity", "sum"),
        sum_base_price=("l_extendedprice", "sum"),
        sum_disc_price=("disc_price", "sum"),
        sum_charge=("charge", "sum"),
        sum_disc=("l_discount", "sum"),
        count_order=("l_quantity", "size"))


def _merge_q1(states, precision):
    total = states[0]
    for part in states[1:]:
        counts = total["count_order"].add(part["count_order"], fill_value=0)
        total = total.add(part, fill_value=0).astype(
            {c: precision for c in _Q1_SUMS + ["sum_disc"]})
        total["count_order"] = counts
    r = total.sort_index().copy()
    n = r["count_order"].astype(np.int64)
    nf = n.astype(precision)
    r["avg_qty"] = r["sum_qty"] / nf
    r["avg_price"] = r["sum_base_price"] / nf
    r["avg_disc"] = r["sum_disc"] / nf
    r["count_order"] = n
    return r.reset_index()[["l_returnflag", "l_linestatus"] + _Q1_SUMS
                           + _Q1_AVGS + ["count_order"]]


def _rel_gap(got, want) -> float:
    got = np.asarray(got, dtype=np.float64)
    want = np.asarray(want, dtype=np.float64)
    if got.shape != want.shape:
        return INF
    if got.size == 0:
        return 0.0
    return float(np.max(np.abs(got - want) / np.abs(want)))


def _compare_q6(got: pd.DataFrame, want: float) -> dict:
    if list(got.columns) != ["revenue"] or len(got) != 1:
        return {"shape_mismatch": 1.0, "revenue_rel_gap": INF}
    return {"shape_mismatch": 0.0,
            "revenue_rel_gap": _rel_gap([got["revenue"].iloc[0]], [want])}


def _compare_q1(got: pd.DataFrame, want: pd.DataFrame) -> dict:
    keys = ["l_returnflag", "l_linestatus"]
    out = {"shape_mismatch": 1.0, "count_gap": INF, "sum_rel_gap": INF,
           "avg_rel_gap": INF}
    if list(got.columns) != list(want.columns) or len(got) != len(want):
        return out
    # ORDER BY l_returnflag, l_linestatus: position by position
    if not all((got[k].to_numpy() == want[k].to_numpy()).all()
               for k in keys):
        return out
    out["shape_mismatch"] = 0.0
    out["count_gap"] = float(np.max(np.abs(
        got["count_order"].to_numpy().astype(np.int64)
        - want["count_order"].to_numpy().astype(np.int64))))
    out["sum_rel_gap"] = max(_rel_gap(got[c], want[c]) for c in _Q1_SUMS)
    out["avg_rel_gap"] = max(_rel_gap(got[c], want[c]) for c in _Q1_AVGS)
    return out


_QUERIES = {
    "tpch_q6": (_partial_q6, _merge_q6, _compare_q6),
    "tpch_q1": (_partial_q1, _merge_q1, _compare_q1),
}


def partial(query: str, tables: dict, precision: str = "float64"):
    """The reference's partial state over one chunk of the fact table
    (``tables``: table name -> the Arrow chunk / whole dimension)."""
    return _QUERIES[query][0](tables, precision)


def merge(query: str, states: list, precision: str = "float64"):
    """The expected answer from the chunks' partial states, in chunk
    order."""
    return _QUERIES[query][1](states, precision)


def answer_frame(query: str, want) -> pd.DataFrame:
    """The expected answer in the shape the engine returns it (used when
    the control is put in the program's place)."""
    if query == "tpch_q6":
        return pd.DataFrame({"revenue": [want]})
    return want


def compare(query: str, got: pd.DataFrame, want) -> dict:
    """name -> number compared (each has its limit in
    perfbench/queries/<query>.json)."""
    return _QUERIES[query][2](got, want)
