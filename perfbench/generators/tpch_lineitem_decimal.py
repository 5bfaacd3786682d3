"""TPC-H ``lineitem`` with the money columns in the type the
specification gives them (clause 1.4.1: ``L_QUANTITY``,
``L_EXTENDEDPRICE``, ``L_DISCOUNT``, ``L_TAX`` are decimal; Spark reads
them as ``DecimalType(15,2)``): the draws of ``tpch_lineitem``, row for
row, from the same ``(seed, i)``, as ``decimal128(15,2)``.

The float generator's money values are whole cents divided by 100; here
the same cents go straight into the 16-byte buffers of an Arrow
``decimal128`` array (low word the cents, high word their sign), by
numpy: no float on the way and no Python object per value. Every other
column is the float generator's own. Imports nothing of the engine and
nothing of JAX.
"""
from __future__ import annotations

import importlib.util
import os

import numpy as np
import pyarrow as pa

MONEY = pa.decimal128(15, 2)


def _float_generator():
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "tpch_lineitem.py")
    spec = importlib.util.spec_from_file_location(
        "perfbench_generators_tpch_lineitem", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


_BASE = _float_generator()


def cents_to_decimal(cents: np.ndarray) -> pa.Array:
    """int64 cents -> ``decimal128(15,2)``, the buffers built by numpy."""
    words = np.empty((len(cents), 2), dtype=np.int64)
    words[:, 0] = cents
    words[:, 1] = words[:, 0] >> 63
    return pa.Array.from_buffers(MONEY, len(cents),
                                 [None, pa.py_buffer(words.data)],
                                 null_count=0)


class _Chunk(_BASE._Chunk):
    """The float generator's chunk with the four money columns as their
    cents (``tpch_lineitem`` divides the same integers by 100.0)."""

    def l_quantity(self):
        return cents_to_decimal(self.quantity.astype(np.int64) * 100)

    def l_extendedprice(self):
        p = self.partkey.astype(np.int64)
        cents = 90000 + (p // 10) % 20001 + 100 * (p % 1000)
        return cents_to_decimal(self.quantity * cents)

    def l_discount(self):
        return cents_to_decimal(self.draw(8, 0, 10).astype(np.int64))

    def l_tax(self):
        return cents_to_decimal(self.draw(9, 0, 8).astype(np.int64))


def generate(table: str, tables: dict, seed: int, chunk: int, rows: int,
             columns=None) -> pa.Table:
    """``rows`` rows of chunk ``chunk`` of ``table`` (``columns``: a subset
    of the configuration's, in its order; all that are made if None)."""
    if table != "lineitem":
        raise KeyError(f"tpch_lineitem_decimal makes no table {table!r}")
    made = _Chunk(tables, seed, chunk, rows)
    names = [c for c in tables[table]["columns"]
             if hasattr(made, c) and (columns is None or c in columns)]
    cols = {c: getattr(made, c)() for c in names}
    return pa.table({c: v if isinstance(v, pa.Array) else pa.array(v)
                     for c, v in cols.items()})
