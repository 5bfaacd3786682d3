"""TPC-DS ``store_sales`` / ``date_dim`` / ``item`` from a seed, at dsdgen's
SF1 row counts and key domains (PR 24; ``benchmarks/tpcds.py``, which the
bring-up ran, departs from them and is listed in PERF.md, Open questions).

``date_dim`` is dsdgen's calendar: 73,049 days from 1900-01-02, whose
``d_date_sk`` is the Julian day number (2415022 on). ``item`` has 18,000
rows, ``i_item_sk`` 1..18000, ``i_manufact_id`` uniform 1..1000 and a
brand id built as dsdgen builds it, category x 1,000,000 + class x 1,000 +
number, with the brand's name made of the class's and the category's
syllables. ``store_sales`` has a never-NULL uniform ``ss_item_sk``, a
``ss_sold_date_sk`` over dsdgen's five sales years weighted towards the
sales seasons and NULL in 4.5% of rows, and a price built as dsdgen builds
it (wholesale x markup x discount x quantity, cents). What is not dsdgen's
to the letter is in the configuration's ``assumed`` group.

Chunk ``i`` of ``store_sales`` is drawn from ``(seed, i)`` and each column
from a stream of its own, so any subset of columns comes out the same.
``item`` is drawn from ``(seed,)``. Imports nothing of the engine and
nothing of JAX.
"""
from __future__ import annotations

import numpy as np
import pyarrow as pa

JULIAN_1900_01_02 = 2415022          # d_date_sk of date_dim's first row
SALES_FIRST = np.datetime64("1998-01-02")
SALES_LAST = np.datetime64("2003-01-02")
#: per-day weight of a sales date by month: dsdgen's three sales zones
#: (January-July low, August-October medium, November-December high)
ZONE_WEIGHT = np.array([1, 1, 1, 1, 1, 1, 1, 2, 2, 2, 3, 3], np.float64)
NULL_SHARE = 0.045
SYLLABLES = ["amalg", "importo", "edu pack", "exporti", "scholar", "brand",
             "corp", "maxi", "univ", "nameless"]


def _stream(seed: int, chunk: int, column: int):
    return np.random.RandomState((seed, chunk, column))


def _nullable(values: np.ndarray, rng) -> pa.Array:
    return pa.array(values, mask=rng.random_sample(len(values)) < NULL_SHARE)


def _sales_dates():
    days = np.arange(SALES_FIRST, SALES_LAST + 1)
    moy = days.astype("datetime64[M]").astype(int) % 12
    w = ZONE_WEIGHT[moy]
    sk = (days - np.datetime64("1900-01-02")).astype(np.int64) \
        + JULIAN_1900_01_02
    return sk, np.cumsum(w) / w.sum()


def _store_sales(column: str, tables: dict, seed: int, chunk: int,
                 rows: int) -> pa.Array:
    if column == "ss_sold_date_sk":
        rng = _stream(seed, chunk, 1)
        sk, cdf = _sales_dates()
        picked = sk[np.searchsorted(cdf, rng.random_sample(rows))]
        return _nullable(picked, rng)
    if column == "ss_item_sk":
        n_items = int(tables["item"]["rows"])
        return pa.array(_stream(seed, chunk, 2).randint(1, n_items + 1, rows)
                        .astype(np.int64))
    if column == "ss_ext_sales_price":
        rng = _stream(seed, chunk, 3)
        wholesale = rng.randint(100, 10001, rows) / 100.0
        list_price = np.round(wholesale * (1.0 + rng.randint(0, 201, rows)
                                           / 100.0), 2)
        sales_price = np.round(list_price * (1.0 - rng.randint(0, 101, rows)
                                             / 100.0), 2)
        quantity = rng.randint(1, 101, rows)
        return _nullable(np.round(sales_price * quantity, 2), rng)
    raise KeyError(f"tpcds_star makes no column store_sales.{column}")


def generate(table: str, tables: dict, seed: int, chunk: int, rows: int,
             columns=None) -> pa.Table:
    """``rows`` rows of chunk ``chunk`` of ``table`` (``columns``: a subset
    of the configuration's, in its order; all of them if None)."""
    names = [c for c in tables[table]["columns"]
             if columns is None or c in columns]
    if table == "store_sales":
        return pa.table({c: _store_sales(c, tables, seed, chunk, rows)
                         for c in names})
    if table == "date_dim":
        dates = np.datetime64("1900-01-02") + np.arange(rows)
        made = {
            "d_date_sk": pa.array(JULIAN_1900_01_02
                                  + np.arange(rows, dtype=np.int64)),
            "d_date": pa.array(dates.astype("datetime64[D]")),
            "d_year": pa.array((dates.astype("datetime64[Y]").astype(int)
                                + 1970).astype(np.int32)),
            "d_moy": pa.array((dates.astype("datetime64[M]").astype(int)
                               % 12 + 1).astype(np.int32)),
        }
    elif table == "item":
        rng = np.random.RandomState((seed, 0x17E3))
        category = rng.randint(1, 11, rows)
        klass = rng.randint(1, 17, rows)
        number = rng.randint(1, 7, rows)
        made = {
            "i_item_sk": pa.array(np.arange(1, rows + 1, dtype=np.int64)),
            "i_brand_id": pa.array((category * 1_000_000 + klass * 1_000
                                    + number).astype(np.int32)),
            "i_brand": pa.array([f"{SYLLABLES[(k - 1) % 10]}"
                                 f"{SYLLABLES[c - 1]} #{n}"
                                 for c, k, n in zip(category, klass,
                                                    number)]),
            "i_manufact_id": pa.array(rng.randint(1, 1001, rows)
                                      .astype(np.int32)),
        }
    else:
        raise KeyError(f"tpcds_star makes no table {table!r}")
    return pa.table({c: made[c] for c in names})
