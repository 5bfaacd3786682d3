"""TPC-H ``customer``, ``orders`` and ``lineitem`` from a seed for Q18
(PR 35; TPC-H specification clause 4.2.3): ``generators/tpch_joins.py``'s
three tables from the same ``(seed, chunk)`` draws, with two columns more.

``lineitem`` is ``tpch_lineitem``'s table row for row (through
``tpch_joins``, imported and not copied). ``orders`` is ``tpch_joins``'s
(the distinct ``l_orderkey`` of a chunk, the date its lines' ship dates
were drawn from, a customer that is no multiple of 3) plus
``o_totalprice``: the sum over the order's lines of ``l_extendedprice *
(1 + l_tax) * (1 - l_discount)``, rounded to cents (clause 4.2.3), in
float64, from the chunk's own line draws. ``customer`` has ``c_custkey``
1..rows and ``c_name`` = ``Customer#`` followed by the key in nine digits
(dbgen's rule). As in ``tpch_joins`` a whole ``orders`` table is asked for
as chunk 0 and its row count follows from the ``lineitem`` rows, and a
reference child (a spawned process that has just made ONE ``lineitem``
chunk) gets the orders of that chunk alone: an order's lines lie in one
chunk. Imports nothing of the engine and nothing of JAX.
"""
from __future__ import annotations

import multiprocessing

import numpy as np
import pyarrow as pa

import datagen  # perfbench/datagen.py: run.py puts perfbench/ on the path

_JOINS = datagen.load_module("generators", "tpch_joins")
_LINEITEM = _JOINS._LINEITEM
NAME_PREFIX = b"Customer#"
NAME_DIGITS = 9


def _chunk_draws(tables: dict, seed: int, chunk: int, rows: int) -> dict:
    """``tpch_joins``'s draws of one chunk's orders, and each order's
    total price from the chunk's lines."""
    draws = _JOINS._chunk_draws(tables, seed, chunk, rows)
    made = _LINEITEM._Chunk(tables, seed, chunk, rows)
    charge = made.l_extendedprice() * (1.0 + made.l_tax()) \
        * (1.0 - made.l_discount())
    draws["total"] = np.round(np.bincount(
        made.order, weights=charge, minlength=len(draws["index"])) * 100.0
    ) / 100.0
    return draws


#: column -> (the draw it is made from, the draw as the column)
_ORDERS = dict(_JOINS._ORDERS, o_totalprice=("total", lambda t: t))


def _orders_of(made: list, names) -> pa.Table:
    cols = {}
    for draw in ("index", "customer", "day", "total"):
        whole = np.concatenate([m.pop(draw) for m in made])
        cols.update({c: pa.array(_ORDERS[c][1](whole)) for c in names
                     if _ORDERS[c][0] == draw})
    return pa.table({c: cols[c] for c in names})


def customer_names(keys: np.ndarray) -> pa.Array:
    """``Customer#000000001`` ...: the bytes laid out by arithmetic, no
    Python string a row."""
    width = len(NAME_PREFIX) + NAME_DIGITS
    chars = np.empty((len(keys), width), np.uint8)
    chars[:, :len(NAME_PREFIX)] = np.frombuffer(NAME_PREFIX, np.uint8)
    rest = keys.astype(np.int64)
    for d in range(NAME_DIGITS):
        chars[:, width - 1 - d] = 48 + rest % 10
        rest = rest // 10
    offsets = np.arange(len(keys) + 1, dtype=np.int32) * width
    return pa.Array.from_buffers(
        pa.string(), len(keys),
        [None, pa.py_buffer(offsets), pa.py_buffer(chars.tobytes())])


def _customer(rows: int, names: list) -> pa.Table:
    keys = np.arange(1, rows + 1, dtype=np.int64)
    made = {"c_custkey": lambda: pa.array(keys),
            "c_name": lambda: customer_names(keys)}
    return pa.table({c: made[c]() for c in names})


def generate(table: str, tables: dict, seed: int, chunk: int, rows: int,
             columns=None) -> pa.Table:
    """``rows`` rows of chunk ``chunk`` of ``lineitem``; the whole of
    ``orders`` or ``customer`` (``columns``: a subset of the
    configuration's, in its order; all that are made if None)."""
    if table == "lineitem":
        return _JOINS.generate(table, tables, seed, chunk, rows, columns)
    if table not in ("orders", "customer"):
        raise KeyError(f"tpch_groups makes no table {table!r}")
    names = [c for c in tables[table]["columns"]
             if columns is None or c in columns]
    if table == "customer":
        return _customer(rows, names)
    spec = tables["lineitem"]
    last = _JOINS._STATE.last_chunk
    if multiprocessing.parent_process() is not None and last is not None \
            and last[0] == seed:
        return _orders_of([_chunk_draws(tables, *last)], names)
    total, size = int(spec["rows"]), int(spec.get("chunk_rows", 0))
    size = size or total
    return _orders_of(
        [_chunk_draws(tables, seed, i, min(size, total - off))
         for i, off in enumerate(range(0, total, size))], names)
