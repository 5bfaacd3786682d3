"""The least work a query needs, reckoned from the cell's files alone.

``query_bytes``: for every table of the configuration that the query's
text names, rows x the ``domain_bytes`` of each of that table's columns
the text names (the narrowest whole-byte width that holds the column's
generated domain, written per column in the configuration's file), read
once. It reads no shape from the program, so it is the same number
whatever kernel, width or fusion the program chooses.

``least_seconds``: those bytes over the chip's HBM bandwidth
(perfbench/peaks.json). These queries do a handful of operations per
byte, so HBM bandwidth — not FLOP/s — is the bound; the function says so
in what it returns.
"""
from __future__ import annotations

import json
import os
import re

HERE = os.path.dirname(os.path.abspath(__file__))


def peaks_for(device_kind: str) -> dict:
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table or device_kind.startswith("_"):
        raise KeyError(f"device kind {device_kind!r} is not in "
                       f"perfbench/peaks.json: add it with its source")
    return table[device_kind]


def query_columns(query_text: str, tables: dict) -> dict:
    """table -> [columns] that the query's text names."""
    words = set(re.findall(r"[A-Za-z_][A-Za-z_0-9]*", query_text))
    return {t: [c for c in spec["columns"] if c in words]
            for t, spec in tables.items() if t in words}


def query_bytes(query_text: str, tables: dict) -> int:
    total = 0
    for t, cols in query_columns(query_text, tables).items():
        per_row = sum(int(tables[t]["columns"][c]["domain_bytes"])
                      for c in cols)
        total += int(tables[t]["rows"]) * per_row
    return total


def least_seconds(query_text: str, tables: dict, device_kind: str) -> dict:
    nbytes = query_bytes(query_text, tables)
    bw = peaks_for(device_kind)["hbm_bytes_per_s"]
    return {"bytes": nbytes, "seconds": nbytes / bw,
            "bound_by": "hbm_bandwidth", "hbm_bytes_per_s": bw}
