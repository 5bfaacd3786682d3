"""TPC-H ``customer``, ``orders`` and ``lineitem`` from a seed, consistent
with one another (PR 32; TPC-H specification clause 4.2.3).

``lineitem`` is ``generators/tpch_lineitem.py``'s table, row for row, from
the same ``(seed, chunk)``: this module imports that one and calls it.
``orders`` is rebuilt from the same per-chunk draws: chunk ``i`` of
``lineitem`` holds whole orders (an order's lines are consecutive rows of
one chunk and a chunk starts a new order), so its orders are the distinct
``l_orderkey`` of the chunk, in order; ``o_orderdate`` is the date the
lines' ship dates were drawn from (stream 2 of the chunk, one value an
order); ``o_shippriority`` is 0 (dbgen's constant); ``o_custkey`` is
uniform over 1..customers skipping the multiples of 3 (dbgen's rule: a
third of the customers have no order), from a stream of the chunk that
``tpch_lineitem`` does not use. ``customer`` has ``c_custkey`` 1..rows and
a ``c_mktsegment`` uniform over dbgen's five segments, from ``(seed,)``.

A whole ``orders`` table is asked for as ``chunk`` 0 (``datagen.
dimension_tables``); its row count follows from the ``lineitem`` rows in
``tables`` (a rehearsal asks for fewer), not from ``rows``: about a
quarter of them, the exact count depends on the seed.

In a reference child (a spawned process of ``datagen.reference_answer``,
which makes ONE chunk of ``lineitem`` and then asks for the dimension
tables to fold that chunk against) ``orders`` is the orders of that chunk
alone: an order's lines lie in one chunk, so no other order can meet them,
and twelve children that each held all 15M orders beside a run's own 20 GB
process passed the machine's 40 GiB (PERF.md, PR 32). The process that
registers the tables (no parent process) always gets the whole table.
Imports nothing of the engine and nothing of JAX.
"""
from __future__ import annotations

import multiprocessing
import sys
import types

import numpy as np
import pyarrow as pa

import datagen  # perfbench/datagen.py: run.py puts perfbench/ on the path

_LINEITEM = datagen.load_module("generators", "tpch_lineitem")
SEGMENTS = ["AUTOMOBILE", "BUILDING", "FURNITURE", "MACHINERY", "HOUSEHOLD"]
CUSTKEY_STREAM = 14          # tpch_lineitem draws streams 1..13 of a chunk
SEGMENT_STREAM = 0xC057
#: (seed, chunk, rows) of the ``lineitem`` chunk this PROCESS made last.
#: Kept outside this module: ``datagen.load_module`` executes the file
#: anew for every ask.
_STATE = sys.modules.setdefault("perfbench_tpch_joins_state",
                                types.SimpleNamespace(last_chunk=None))


def _chunk_draws(tables: dict, seed: int, chunk: int, rows: int) -> dict:
    """What the orders of one ``lineitem`` chunk are made of, as int32 (a
    dozen reference children hold 58 chunks' worth at once): each order's
    index in the key numbering, the draw its customer follows from, the
    day its lines' ship dates were drawn from."""
    made = _LINEITEM._Chunk(tables, seed, chunk, rows)
    n = int(made.order[-1]) + 1
    customers = int(tables["customer"]["rows"])
    return {
        "index": made.first_order + np.arange(n, dtype=np.int32),
        "customer": made.draw(CUSTKEY_STREAM, 0,
                              customers - customers // 3 - 1, n),
        "day": made.draw(2, 0, _LINEITEM.ORDER_DAYS - 1)[:n].copy(),
    }


#: column -> (the draw it is made from, the draw as the column)
_ORDERS = {
    # the sparse numbering of tpch_lineitem.l_orderkey
    "o_orderkey": ("index", lambda n: (n.astype(np.int64) >> 3 << 5
                                       | n & 7) + 1),
    "o_custkey": ("customer",
                  lambda j: 3 * (j.astype(np.int64) // 2) + j % 2 + 1),
    "o_orderdate": ("day", lambda d: (_LINEITEM.START + d)
                    .astype("datetime64[D]")),
    "o_shippriority": ("index", lambda n: np.zeros(len(n), np.int32)),
}


def _orders(seed: int, lineitem_rows: int, chunk_rows: int, parts: int,
            suppliers: int, customers: int, names: tuple) -> pa.Table:
    """The whole table."""
    tables = {"lineitem": {"chunk_rows": chunk_rows, "parts": parts,
                           "suppliers": suppliers},
              "customer": {"rows": customers}}
    size = chunk_rows or lineitem_rows
    made = [_chunk_draws(tables, seed, i, min(size, lineitem_rows - off))
            for i, off in enumerate(range(0, lineitem_rows, size))]
    return _orders_of(made, names)


def _orders_of(made: list, names) -> pa.Table:
    """The columns from the chunks' draws: a draw at a time, its pieces
    dropped as they are joined and the draw itself once its columns are
    made."""
    cols = {}
    for draw in ("index", "customer", "day"):
        whole = np.concatenate([m.pop(draw) for m in made])
        cols.update({c: pa.array(_ORDERS[c][1](whole)) for c in names
                     if _ORDERS[c][0] == draw})
    return pa.table({c: cols[c] for c in names})


def _customer(tables: dict, seed: int, rows: int, names: list) -> pa.Table:
    rng = np.random.Generator(np.random.PCG64((seed, SEGMENT_STREAM)))
    made = {
        "c_custkey": lambda: pa.array(np.arange(1, rows + 1, dtype=np.int64)),
        "c_mktsegment": lambda: pa.array(SEGMENTS).take(
            pa.array(rng.integers(0, len(SEGMENTS), rows, dtype=np.int32))),
    }
    return pa.table({c: made[c]() for c in names})


def generate(table: str, tables: dict, seed: int, chunk: int, rows: int,
             columns=None) -> pa.Table:
    """``rows`` rows of chunk ``chunk`` of ``lineitem``; the whole of
    ``orders`` or ``customer`` (``columns``: a subset of the
    configuration's, in its order; all that are made if None)."""
    if table == "lineitem":
        _STATE.last_chunk = (seed, chunk, rows)
        return _LINEITEM.generate(table, tables, seed, chunk, rows, columns)
    if table not in ("orders", "customer"):
        raise KeyError(f"tpch_joins makes no table {table!r}")
    names = [c for c in tables[table]["columns"]
             if columns is None or c in columns]
    if table == "customer":
        return _customer(tables, seed, rows, names)
    spec = tables["lineitem"]
    last = _STATE.last_chunk
    if multiprocessing.parent_process() is not None and last is not None \
            and last[0] == seed:
        return _orders_of([_chunk_draws(tables, *last)], names)
    return _orders(seed, int(spec["rows"]), int(spec.get("chunk_rows", 0)),
                   int(spec["parts"]), int(spec["suppliers"]),
                   int(tables["customer"]["rows"]), tuple(names))
