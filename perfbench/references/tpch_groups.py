"""The plain reference for TPC-H Q18 (PR 35): pandas over the generated
tables, ``lineitem`` chunk by chunk, float64. It imports nothing of the
engine and takes nothing the engine made.

Q18 (clause 2.4.18, QUANTITY = 300, first 100 rows). An order's lines lie
in one chunk of ``lineitem`` and a chunk starts a new order
(``generators/tpch_lineitem.py``'s own rule), so a chunk's per-order sums
of ``l_quantity`` are whole: its partial state is its own orders over the
quantity, joined to their ``orders`` and ``customer`` rows and cut to the
``CANDIDATES`` first in ORDER BY order, and the merge is a top-N over the
chunks' candidates. ``l_quantity`` holds whole numbers, so every sum is
exact in any precision that holds 350; ``o_totalprice`` passes through.
The expected answer keeps ``CANDIDATES`` rows, more than the query's 100,
so that the comparison can see what stands at the cut.

``precision="float32"`` is the CONTROL (see references/tpch_lineitem.py):
the same query with its money and its quantities in float32, put in the
program's place by ``run.py --control float32``. ``o_totalprice`` (up to
hundreds of thousands, with cents: 26 bits) loses its cents there, so it
has to fail, by ``totalprice_cents_mismatch`` (rows whose ``o_totalprice`` is
not the generated value to the cent) and by ``totalprice_rel_gap``. The
comparison is to the cent and not bit for bit: this chip holds a float64
as a pair of float32 (48 bits of mantissa), so a double that merely
PASSES THROUGH the device comes back within 2^-48 of itself, not equal to
it (PERF.md section 2: a builder's full-size run read 90 of 100 rows not
bit-equal, every one exact to the cent).
"""
from __future__ import annotations

import numpy as np
import pandas as pd

INF = 1e300      # "cannot be compared"; finite, so the line stays JSON
LIMIT = 100
CANDIDATES = 132
QUANTITY = 300
CENT = 0.01
_KEYS = ["c_name", "c_custkey", "o_orderkey", "o_orderdate"]
_COLUMNS = _KEYS + ["o_totalprice", "sum(l_quantity)"]


def _top(rows: pd.DataFrame, n: int) -> pd.DataFrame:
    """ORDER BY o_totalprice DESC, o_orderdate; then the key, so that the
    reference's own order is total."""
    return rows.sort_values(["o_totalprice", "o_orderdate", "o_orderkey"],
                            ascending=[False, True, True],
                            kind="stable").head(n).reset_index(drop=True)


def _partial_q18(tables, precision):
    import pyarrow as pa
    import pyarrow.compute as pc
    li = tables["lineitem"].select(["l_orderkey", "l_quantity"]).to_pandas()
    qty = li["l_quantity"].astype(precision).groupby(li["l_orderkey"]) \
        .sum().astype(precision)
    qty = qty[qty > QUANTITY]
    # the orders that passed, out of the table before it becomes a frame
    od = tables["orders"]
    od = od.filter(pc.is_in(od.column("o_orderkey"), value_set=pa.array(
        qty.index.to_numpy(), od.schema.field("o_orderkey").type))) \
        .to_pandas(date_as_object=False)
    od["o_totalprice"] = od["o_totalprice"].astype(precision)
    cu = tables["customer"]
    cu = cu.filter(pc.is_in(cu.column("c_custkey"), value_set=pa.array(
        od["o_custkey"].to_numpy(), cu.schema.field("c_custkey").type))) \
        .to_pandas()
    j = od.merge(cu, left_on="o_custkey", right_on="c_custkey")
    j["sum(l_quantity)"] = qty.reindex(j["o_orderkey"]).to_numpy()
    return _top(j[_COLUMNS], CANDIDATES)


def _merge_q18(states, precision):
    return _top(pd.concat(states, ignore_index=True), CANDIDATES)


def _keys_of(frame: pd.DataFrame):
    return list(frame[_KEYS].astype({"o_orderdate": "datetime64[s]"})
                .itertuples(index=False, name=None))


def _compare_q18(got: pd.DataFrame, want: pd.DataFrame) -> dict:
    out = {"shape_mismatch": 1.0, "count_gap": INF, "key_mismatch": INF,
           "totalprice_cents_mismatch": INF, "totalprice_rel_gap": INF,
           "quantity_gap": INF}
    if list(got.columns) != _COLUMNS:
        return out
    out["shape_mismatch"] = 0.0
    expect = want.head(LIMIT)
    out["count_gap"] = float(abs(len(got) - len(expect)))
    price = want["o_totalprice"].to_numpy(dtype=np.float64)
    day = want["o_orderdate"].to_numpy().astype("datetime64[s]")
    ref = {k: i for i, k in enumerate(_keys_of(want))}
    qty = want["sum(l_quantity)"].to_numpy(dtype=np.float64)
    wrong = off = 0
    gap = rel = 0.0
    for pos, (key, total, q) in enumerate(zip(
            _keys_of(got), got["o_totalprice"].to_numpy(dtype=np.float64),
            got["sum(l_quantity)"].to_numpy(dtype=np.float64))):
        at = ref.get(key)
        if at is None or pos >= len(expect):
            wrong += 1          # not among the candidates at all
            continue
        # in ORDER BY position, or interchangeable with what stands
        # there: the same o_totalprice and the same o_orderdate
        if at != pos and not (price[at] == price[pos]
                              and day[at] == day[pos]):
            wrong += 1
        # the generated value to the cent (money is whole cents; a NaN
        # is off); and how far from it, relative to it
        off += not abs(total - price[at]) < CENT / 2
        rel = max(rel, abs(total - price[at]) / max(abs(price[at]), 1.0)
                  if total == total else INF)
        gap = max(gap, abs(q - qty[at]) if q == q else INF)
    out["key_mismatch"] = float(wrong)
    out["totalprice_cents_mismatch"] = float(off)
    out["totalprice_rel_gap"] = float(rel)
    out["quantity_gap"] = float(gap)
    return out


_QUERIES = {"tpch_q18": (_partial_q18, _merge_q18, _compare_q18)}


def partial(query: str, tables: dict, precision: str = "float64"):
    return _QUERIES[query][0](tables, precision)


def merge(query: str, states: list, precision: str = "float64"):
    return _QUERIES[query][1](states, precision)


def answer_frame(query: str, want) -> pd.DataFrame:
    """The expected answer shaped as the program returns it."""
    out = want.head(LIMIT)[_COLUMNS].reset_index(drop=True)
    return out.astype({"o_totalprice": "float64",
                       "sum(l_quantity)": "float64"})


def compare(query: str, got: pd.DataFrame, want) -> dict:
    return _QUERIES[query][2](got, want)
