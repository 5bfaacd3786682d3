"""The plain reference for TPC-DS q3: pandas over the generated tables, the
fact table chunk by chunk. A copy of ``chip_smoke.py``'s ``reference_q3``
and ``check_q3`` (PR 24; original listed in PERF.md, Open questions). It
imports nothing of the engine and takes nothing the engine made.

``precision="float32"`` is the CONTROL (see references/tpch_lineitem.py).
"""
from __future__ import annotations

import numpy as np
import pandas as pd

INF = 1e300      # "cannot be compared"; finite, so the line stays JSON
_KEYS = ["d_year", "i_brand_id", "i_brand"]
LIMIT = 100


def _partial_q3(tables, precision):
    dd = tables["date_dim"].to_pandas(date_as_object=False)
    it = tables["item"].to_pandas()
    dd = dd[dd["d_moy"] == 11][["d_date_sk", "d_year"]]
    it = it[it["i_manufact_id"] == 128][["i_item_sk", "i_brand_id",
                                         "i_brand"]]
    ss = tables["store_sales"].select(
        ["ss_sold_date_sk", "ss_item_sk", "ss_ext_sales_price"]).to_pandas()
    ss["ss_ext_sales_price"] = ss["ss_ext_sales_price"].astype(precision)
    # a NULL key joins nothing (it comes to pandas as NaN)
    ss = ss[ss["ss_item_sk"].isin(it["i_item_sk"])
            & ss["ss_sold_date_sk"].isin(dd["d_date_sk"])]
    ss = ss.astype({"ss_sold_date_sk": np.int64})
    j = ss.merge(dd, left_on="ss_sold_date_sk", right_on="d_date_sk")
    j = j.merge(it, left_on="ss_item_sk", right_on="i_item_sk")
    # sum() skips NULL prices; a group of NULL prices alone sums to NULL
    return j.groupby(_KEYS)["ss_ext_sales_price"].sum(min_count=1)


def _merge_q3(states, precision):
    total = states[0]
    for part in states[1:]:
        # NULL + x = x here, NULL + NULL stays NULL
        total = total.add(part, fill_value=0).astype(precision)
    g = total.rename("sum_agg").reset_index()
    # ORDER BY d_year, sum_agg DESC (NULLS LAST), i_brand_id LIMIT 100
    return g.sort_values(["d_year", "sum_agg", "i_brand_id"],
                         ascending=[True, False, True], na_position="last"
                         ).head(LIMIT).reset_index(drop=True)


def _compare_q3(got: pd.DataFrame, want: pd.DataFrame) -> dict:
    out = {"shape_mismatch": 1.0, "sum_rel_gap": INF, "order_mismatch": 1.0}
    if list(got.columns) != list(want.columns) or len(got) != len(want):
        return out
    g = got.sort_values(_KEYS).reset_index(drop=True)
    w = want.sort_values(_KEYS).reset_index(drop=True)
    if not all((g[k].to_numpy() == w[k].to_numpy()).all() for k in _KEYS):
        return out
    out["shape_mismatch"] = 0.0
    a = g["sum_agg"].to_numpy(dtype=np.float64, na_value=np.nan)
    b = w["sum_agg"].to_numpy(dtype=np.float64, na_value=np.nan)
    if (np.isnan(a) != np.isnan(b)).any():
        return out                           # a NULL sum on one side only
    live = ~np.isnan(b)
    # relative to the sum, or to 1.00 where the sum is smaller (a group of
    # fully discounted sales sums to 0.00)
    out["sum_rel_gap"] = (float(np.max(np.abs(a[live] - b[live])
                                       / np.maximum(np.abs(b[live]), 1.0)))
                          if live.any() else 0.0)
    # ORDER BY d_year, sum_agg DESC, i_brand_id, judged on what was served
    order = got.sort_values(["d_year", "sum_agg", "i_brand_id"],
                            ascending=[True, False, True], kind="stable",
                            na_position="last")
    out["order_mismatch"] = float(list(order.index) != list(got.index))
    return out


_QUERIES = {"tpcds_q3": (_partial_q3, _merge_q3, _compare_q3)}


def partial(query: str, tables: dict, precision: str = "float64"):
    return _QUERIES[query][0](tables, precision)


def merge(query: str, states: list, precision: str = "float64"):
    return _QUERIES[query][1](states, precision)


def answer_frame(query: str, want) -> pd.DataFrame:
    return want


def compare(query: str, got: pd.DataFrame, want) -> dict:
    return _QUERIES[query][2](got, want)
