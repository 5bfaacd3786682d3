"""Data and reference from the seed.

Chunk ``i`` of a fact table depends on ``(seed, i)`` alone, and only the
columns the cell's query names are made. Two passes:

* set-up (``FactTable``): threads of the parent make the chunks while the
  main thread brings up the chip (numpy's generators and Arrow release the
  interpreter lock), so nothing is pickled or written on its way into the
  one in-memory table;
* the reference (``reference_answer``, after the window has closed):
  spawned children, which import numpy, pandas and pyarrow and NEVER JAX
  or the engine, make the same chunks again and fold the plain
  reference's partial state; only the partials come back. The reference
  therefore sees nothing the engine made, and its time is not set-up time.
"""
from __future__ import annotations

import concurrent.futures
import importlib.util
import multiprocessing
import os

HERE = os.path.dirname(os.path.abspath(__file__))


def load_module(kind: str, name: str):
    """``perfbench/<kind>/<name>.py`` as a module, found by its name."""
    path = os.path.join(HERE, kind, name + ".py")
    if not os.path.isfile(path):
        raise FileNotFoundError(f"no {kind[:-1]} named {name!r}: {path}")
    spec = importlib.util.spec_from_file_location(
        f"perfbench_{kind}_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def chunk_plan(config: dict, fact_rows: int):
    """[(index, rows)] of the fact table's chunks."""
    size = int(config["tables"][config["fact"]].get("chunk_rows", fact_rows))
    return [(i, min(size, fact_rows - off))
            for i, off in enumerate(range(0, fact_rows, size))]


def scaled_tables(config: dict, fact_rows: int) -> dict:
    """The configuration's ``tables`` group with the fact table's row
    count replaced (a rehearsal asks for fewer rows; a run never does)."""
    tables = {k: dict(v) for k, v in config["tables"].items()}
    tables[config["fact"]]["rows"] = fact_rows
    return tables


def dimension_tables(config: dict, tables: dict, seed: int,
                     columns: dict) -> dict:
    """name -> the whole dimension table, the query's columns of it."""
    gen = load_module("generators", config["generator"])
    return {name: gen.generate(name, tables, seed, 0, int(spec["rows"]),
                               columns[name])
            for name, spec in tables.items()
            if name != config["fact"] and name in columns}


def _workers(n_tasks: int) -> int:
    return max(1, min(n_tasks, (os.cpu_count() or 2) - 1))


class FactTable:
    """The set-up pass: start the threads at once; ``result`` is the fact
    table as one Arrow table, chunks in order. ``close`` always stops
    them."""

    def __init__(self, config, tables, seed, columns: dict):
        import pyarrow  # noqa: F401  (in the main thread, before JAX is)
        fact = config["fact"]
        gen = load_module("generators", config["generator"])
        plan = chunk_plan(config, int(tables[fact]["rows"]))
        self._pool = concurrent.futures.ThreadPoolExecutor(
            _workers(len(plan)))
        self._chunks = [self._pool.submit(gen.generate, fact, tables, seed,
                                          i, n, columns[fact])
                        for i, n in plan]

    def result(self):
        import pyarrow as pa
        return pa.concat_tables([c.result() for c in self._chunks])

    def close(self):
        self._pool.shutdown(wait=True, cancel_futures=True)


def _child(task):
    """The reference's partial state over one chunk, in a child."""
    config, tables, seed, index, rows, columns, ref_name, query, \
        precision = task
    gen = load_module("generators", config["generator"])
    fact = config["fact"]
    chunk = gen.generate(fact, tables, seed, index, rows, columns[fact])
    ref = load_module("references", ref_name)
    dims = dimension_tables(config, tables, seed, columns)
    return ref.partial(query, dict(dims, **{fact: chunk}), precision)


def reference_answer(config, tables, seed, columns, ref_name, query,
                     precision="float64"):
    """The reference pass: the expected answer for this seed and size."""
    fact_rows = int(tables[config["fact"]]["rows"])
    tasks = [(config, tables, seed, i, n, columns, ref_name, query,
              precision) for i, n in chunk_plan(config, fact_rows)]
    pool = multiprocessing.get_context("spawn").Pool(_workers(len(tasks)))
    try:
        states = pool.map(_child, tasks)
    finally:
        pool.terminate()
        pool.join()
    ref = load_module("references", ref_name)
    return ref.merge(query, states, precision)
