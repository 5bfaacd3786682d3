"""TPC-H ``lineitem`` from a seed, chunk by chunk, by dbgen's rules
(TPC-H specification clause 4.2.3; PR 24 — ``benchmarks/tpch.py:
gen_lineitem``, which the bring-up ran, draws the flags and prices
independently and is listed in PERF.md, Open questions).

An order has 1..7 lines and an ``o_orderdate`` uniform over 1992-01-01 ..
1998-08-02; a line ships 1..121 days later, is committed 30..90 days after
the order and received 1..30 days after it shipped. ``l_returnflag`` is R
or A (a coin) where the line was received by 1995-06-17 and N otherwise;
``l_linestatus`` is O where it ships after that day and F otherwise: four
groups of unequal size, as dbgen gives. ``l_extendedprice`` is
``l_quantity`` x the part's retail price, which follows from ``l_partkey``.

Chunk ``i`` is drawn from ``(seed, i)`` and every draw from a stream of its
own, so any subset of columns comes out the same, in any process or
thread. ``l_comment`` is not made (the configuration says so). Imports
nothing of the engine and nothing of JAX.
"""
from __future__ import annotations

import functools

import numpy as np
import pyarrow as pa

START = np.datetime64("1992-01-01")
ORDER_DAYS = 2406                # o_orderdate: START .. 1998-08-02
CURRENT = np.datetime64("1995-06-17")
INSTRUCTS = ["DELIVER IN PERSON", "COLLECT COD", "NONE", "TAKE BACK RETURN"]
MODES = ["REG AIR", "AIR", "RAIL", "SHIP", "TRUCK", "MAIL", "FOB"]


class _Chunk:
    """The draws of one chunk, each made once and only when asked for."""

    def __init__(self, tables: dict, seed: int, chunk: int, rows: int):
        self.rows, self.key = rows, (seed, chunk)
        spec = tables["lineitem"]
        self.first_order = chunk * int(spec.get("chunk_rows", 0))
        self.parts, self.supps = int(spec["parts"]), int(spec["suppliers"])

    def draw(self, stream: int, low: int, high: int, size=None):
        """Uniform whole numbers low..high, from this chunk's stream."""
        rng = np.random.Generator(np.random.PCG64(self.key + (stream,)))
        return rng.integers(low, high + 1, size or self.rows,
                            dtype=np.int32)

    @functools.cached_property
    def order(self):
        """Each line's order, counted from 0 within the chunk."""
        lines = self.draw(1, 1, 7)           # more orders than are needed
        return np.repeat(np.arange(self.rows, dtype=np.int32),
                         lines)[:self.rows]

    @functools.cached_property
    def orderdate(self):
        return self.draw(2, 0, ORDER_DAYS - 1)[self.order]

    @functools.cached_property
    def shipdate(self):
        return START + (self.orderdate + self.draw(3, 1, 121))

    @functools.cached_property
    def receiptdate(self):
        return self.shipdate + self.draw(4, 1, 30)

    @functools.cached_property
    def quantity(self):
        return self.draw(5, 1, 50)

    @functools.cached_property
    def partkey(self):
        return self.draw(6, 1, self.parts)

    def l_orderkey(self):
        # dbgen's sparse keys: 8 of every 32 are used
        n = (self.first_order + self.order).astype(np.int64)
        return (n >> 3 << 5 | n & 7) + 1

    def l_partkey(self):
        return self.partkey.astype(np.int64)

    def l_suppkey(self):
        p = self.partkey.astype(np.int64)
        s = self.supps
        return (p + self.draw(7, 0, 3) * (s // 4 + (p - 1) // s)) % s + 1

    def l_linenumber(self):
        first = np.flatnonzero(np.diff(self.order, prepend=-1))
        return (np.arange(self.rows) - np.repeat(
            first, np.diff(first, append=self.rows)) + 1).astype(np.int32)

    def l_quantity(self):
        return self.quantity.astype(np.float64)

    def l_extendedprice(self):
        p = self.partkey.astype(np.int64)
        cents = 90000 + (p // 10) % 20001 + 100 * (p % 1000)
        return self.quantity * cents / 100.0

    def l_discount(self):
        return self.draw(8, 0, 10) / 100.0

    def l_tax(self):
        return self.draw(9, 0, 8) / 100.0

    def l_returnflag(self):
        code = np.where(self.receiptdate <= CURRENT, self.draw(10, 0, 1), 2)
        return pa.array(["R", "A", "N"]).take(pa.array(code))

    def l_linestatus(self):
        code = (self.shipdate > CURRENT).astype(np.int32)
        return pa.array(["F", "O"]).take(pa.array(code))

    def l_shipdate(self):
        return self.shipdate.astype("datetime64[D]")

    def l_commitdate(self):
        return (START + (self.orderdate + self.draw(11, 30, 90))) \
            .astype("datetime64[D]")

    def l_receiptdate(self):
        return self.receiptdate.astype("datetime64[D]")

    def l_shipinstruct(self):
        return pa.array(INSTRUCTS).take(pa.array(self.draw(12, 0, 3)))

    def l_shipmode(self):
        return pa.array(MODES).take(pa.array(self.draw(13, 0, 6)))


def generate(table: str, tables: dict, seed: int, chunk: int, rows: int,
             columns=None) -> pa.Table:
    """``rows`` rows of chunk ``chunk`` of ``table`` (``columns``: a subset
    of the configuration's, in its order; all that are made if None)."""
    if table != "lineitem":
        raise KeyError(f"tpch_lineitem makes no table {table!r}")
    made = _Chunk(tables, seed, chunk, rows)
    names = [c for c in tables[table]["columns"]
             if hasattr(made, c) and (columns is None or c in columns)]
    cols = {c: getattr(made, c)() for c in names}
    return pa.table({c: v if isinstance(v, pa.Array) else pa.array(v)
                     for c, v in cols.items()})
