"""The plain reference for the TPC-H join queries (PR 32): pandas over the
generated tables, ``lineitem`` chunk by chunk, float64. It imports nothing
of the engine and takes nothing the engine made.

Q3 (clause 2.4.3, SEGMENT = BUILDING, DATE = 1995-03-15, first 10 rows).
An order's lines lie in one chunk of ``lineitem`` and a chunk starts a new
order (``generators/tpch_lineitem.py``'s own rule), so a chunk's groups
are whole: its partial state is its own groups' revenue, cut to the
``CANDIDATES`` largest, and the merge is a top-N over the chunks'
candidates. The expected answer keeps ``CANDIDATES`` rows, more than the
query's ten, so that the comparison can see how close the eleventh is.

``precision="float32"`` is the CONTROL (see references/tpch_lineitem.py):
the same query with the money columns and the sums in float32, put in the
program's place by ``run.py --control float32``. It has to fail.
"""
from __future__ import annotations

import numpy as np
import pandas as pd

INF = 1e300      # "cannot be compared"; finite, so the line stays JSON
LIMIT = 10
CANDIDATES = 32
SEGMENT = "BUILDING"
DATE = np.datetime64("1995-03-15")
_KEYS = ["l_orderkey", "o_orderdate", "o_shippriority"]
_COLUMNS = ["l_orderkey", "revenue", "o_orderdate", "o_shippriority"]
#: two groups whose reference revenues differ by less than this share of
#: the larger are interchangeable at the cut and in order: which of them
#: comes first is decided by the last bits of two sums. The same number as
#: ``revenue_rel_gap``'s limit in queries/tpch_q3.json
TIE_REL = 1e-11


def _top(groups: pd.DataFrame, n: int) -> pd.DataFrame:
    """ORDER BY revenue DESC, o_orderdate; then the key, so that the
    reference's own order is total."""
    return groups.sort_values(["revenue", "o_orderdate", "l_orderkey"],
                              ascending=[False, True, True],
                              kind="stable").head(n).reset_index(drop=True)


def _partial_q3(tables, precision):
    import pyarrow.compute as pc
    li = tables["lineitem"].select(
        ["l_orderkey", "l_extendedprice", "l_discount",
         "l_shipdate"]).to_pandas(date_as_object=False)
    li = li[li["l_shipdate"] > DATE]
    # the orders this chunk's lines can belong to (cut out of the whole
    # table before it becomes a frame: a dozen children hold it at once),
    # then the query's own predicates on them
    od = tables["orders"]
    keys = od.column("o_orderkey")
    od = od.filter(pc.and_(
        pc.greater_equal(keys, int(li["l_orderkey"].min())),
        pc.less_equal(keys, int(li["l_orderkey"].max())))) \
        .to_pandas(date_as_object=False)
    od = od[od["o_orderdate"] < DATE]
    cu = tables["customer"]
    cu = cu.filter(pc.equal(cu.column("c_mktsegment"), SEGMENT)) \
        .select(["c_custkey"]).to_pandas()
    od = od.merge(cu, left_on="o_custkey", right_on="c_custkey")
    j = li.merge(od, left_on="l_orderkey", right_on="o_orderkey")
    price = j["l_extendedprice"].astype(precision)
    j["revenue"] = (price * (1 - j["l_discount"].astype(precision))) \
        .astype(precision)
    g = j.groupby(_KEYS, sort=False)["revenue"].sum().astype(precision)
    return _top(g.reset_index(), CANDIDATES)


def _merge_q3(states, precision):
    return _top(pd.concat(states, ignore_index=True), CANDIDATES)


def near_ties(want: pd.DataFrame) -> int:
    """Pairs of neighbouring candidates, down to the eleventh, whose
    revenues lie within ``TIE_REL`` of one another (expected: none)."""
    r = want["revenue"].to_numpy(dtype=np.float64)[:LIMIT + 1]
    return int(np.sum(np.abs(np.diff(r)) <= TIE_REL * np.abs(r[:-1])))


def _compare_q3(got: pd.DataFrame, want: pd.DataFrame) -> dict:
    out = {"shape_mismatch": 1.0, "count_gap": INF, "key_mismatch": INF,
           "revenue_rel_gap": INF}
    if list(got.columns) != _COLUMNS:
        return out
    out["shape_mismatch"] = 0.0
    expect = want.head(LIMIT)
    out["count_gap"] = float(abs(len(got) - len(expect)))
    ref = {tuple(k): (i, r) for i, (k, r) in enumerate(zip(
        want[_KEYS].astype({"o_orderdate": "datetime64[s]"})
        .itertuples(index=False, name=None),
        want["revenue"].to_numpy(dtype=np.float64)))}
    keys = got[_KEYS].astype({"o_orderdate": "datetime64[s]"}) \
        .itertuples(index=False, name=None)
    revenue = got["revenue"].to_numpy(dtype=np.float64)
    wrong, gap = 0, 0.0
    for pos, (key, rev) in enumerate(zip(keys, revenue)):
        at = ref.get(tuple(key))
        if at is None or pos >= len(expect):
            wrong += 1          # not among the candidates at all
            continue
        here = float(expect["revenue"].iloc[pos])
        # in ORDER BY position, or interchangeable with what stands there
        if at[0] != pos and abs(at[1] - here) > TIE_REL * abs(here):
            wrong += 1
        gap = max(gap, abs(rev - at[1]) / max(abs(at[1]), 1.0)
                  if rev == rev else INF)
    out["key_mismatch"] = float(wrong)
    out["revenue_rel_gap"] = float(gap)
    return out


_QUERIES = {"tpch_q3": (_partial_q3, _merge_q3, _compare_q3)}


def partial(query: str, tables: dict, precision: str = "float64"):
    return _QUERIES[query][0](tables, precision)


def merge(query: str, states: list, precision: str = "float64"):
    return _QUERIES[query][1](states, precision)


def answer_frame(query: str, want) -> pd.DataFrame:
    """The expected answer shaped as the program returns it."""
    return want.head(LIMIT)[_COLUMNS].reset_index(drop=True)


def compare(query: str, got: pd.DataFrame, want) -> dict:
    return _QUERIES[query][2](got, want)
