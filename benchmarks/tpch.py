"""TPC-H lineitem generator + q1/q6 through the DataFrame API.

The generator follows the TPC-H column domains (dbgen's lineitem spec) at a
row-count scale rather than SF so it runs anywhere: SF1 lineitem ~= 6M rows.
Queries are written exactly as their SQL shapes, so they exercise the
engine's hot path: date filter -> project -> (string-keyed) grouped
aggregation.
"""
from __future__ import annotations

from typing import Optional

import numpy as np
import pyarrow as pa


_RETURNFLAGS = ["A", "N", "R"]
_LINESTATUSES = ["O", "F"]
_SHIPMODES = ["AIR", "MAIL", "RAIL", "SHIP", "TRUCK", "REG AIR", "FOB"]


def _pick(rng, names, n_rows: int) -> pa.Array:
    """``pa.array(rng.choice(names, n_rows))`` without the numpy unicode
    array in between (whose conversion dominates generation time): the
    same draws — ``choice`` over a list IS ``randint`` over its indices —
    taken straight from an Arrow dictionary."""
    idx = rng.randint(0, len(names), n_rows)
    return pa.array(names).take(pa.array(idx))


def gen_lineitem(n_rows: int, seed=42,
                 total_rows: Optional[int] = None) -> pa.Table:
    """``n_rows`` lineitem rows from ``seed`` (an int, or a sequence of
    ints such as ``(seed, chunk)`` when a large table is generated chunk
    by chunk to bound host memory). ``total_rows`` is the row count of
    the whole table the rows belong to — it sizes the order-key domain —
    and defaults to ``n_rows``."""
    rng = np.random.RandomState(seed)
    total = n_rows if total_rows is None else total_rows
    base = np.datetime64("1992-01-01")
    shipdate = base + rng.randint(0, 2526, n_rows)  # through 1998-11-28
    receiptdate = shipdate + rng.randint(1, 31, n_rows)
    qty = rng.randint(1, 51, n_rows).astype(np.float64)
    price = np.round(rng.uniform(900.0, 105000.0, n_rows), 2)
    return pa.table({
        "l_orderkey": pa.array(rng.randint(1, total // 4 + 2, n_rows)),
        "l_quantity": pa.array(qty),
        "l_extendedprice": pa.array(price),
        "l_discount": pa.array(np.round(rng.randint(0, 11, n_rows) / 100.0,
                                        2)),
        "l_tax": pa.array(np.round(rng.randint(0, 9, n_rows) / 100.0, 2)),
        "l_returnflag": _pick(rng, _RETURNFLAGS, n_rows),
        "l_linestatus": _pick(rng, _LINESTATUSES, n_rows),
        "l_shipdate": pa.array(shipdate.astype("datetime64[D]")),
        "l_receiptdate": pa.array(receiptdate.astype("datetime64[D]")),
        "l_shipmode": _pick(rng, _SHIPMODES, n_rows),
    })


def q1(df, F):
    """Pricing summary report (TPC-H Q1)."""
    cutoff = np.datetime64("1998-12-01") - np.timedelta64(90, "D")
    disc_price = F.col("l_extendedprice") * (F.lit(1.0) -
                                             F.col("l_discount"))
    charge = disc_price * (F.lit(1.0) + F.col("l_tax"))
    return (df.filter(F.col("l_shipdate") <= F.lit(cutoff))
            .with_column("disc_price", disc_price)
            .with_column("charge", charge)
            .group_by("l_returnflag", "l_linestatus")
            .agg(F.sum(F.col("l_quantity")).with_name("sum_qty"),
                 F.sum(F.col("l_extendedprice")).with_name("sum_base_price"),
                 F.sum(F.col("disc_price")).with_name("sum_disc_price"),
                 F.sum(F.col("charge")).with_name("sum_charge"),
                 F.avg(F.col("l_quantity")).with_name("avg_qty"),
                 F.avg(F.col("l_extendedprice")).with_name("avg_price"),
                 F.avg(F.col("l_discount")).with_name("avg_disc"),
                 F.count_star().with_name("count_order"))
            .order_by("l_returnflag", "l_linestatus"))


def q6(df, F):
    """Forecasting revenue change (TPC-H Q6): pure filter + reduction."""
    lo = np.datetime64("1994-01-01")
    hi = np.datetime64("1995-01-01")
    return (df.filter((F.col("l_shipdate") >= F.lit(lo))
                      & (F.col("l_shipdate") < F.lit(hi))
                      & (F.col("l_discount") >= F.lit(0.05))
                      & (F.col("l_discount") <= F.lit(0.07))
                      & (F.col("l_quantity") < F.lit(24.0)))
            .agg(F.sum(F.col("l_extendedprice") * F.col("l_discount"))
                 .with_name("revenue")))
