"""Source kind ``memory_device_strings``: ``memory`` (``sources/memory.py``:
the table registered with ``create_dataframe``, served from HBM by the
scan cache) for a configuration whose guarantees include that a string
equality predicate runs on the device.

Before it registers the first table it asks the session ONE question,
through the public API and without running anything: where does it place
``col = 'literal'`` over a one-row string frame? A program that answers
"on the host" (the parent of PR 32: ``! CpuFilter``) cannot run the
configuration: it also leaves that predicate, and every other one-table
predicate of a join query, in one host filter ABOVE the joins, so TPC-H Q3
would join 1.5M x 15M x 60M unfiltered rows before the first of them is
dropped. The run then ends here, during set-up, with an exit code other
than 0 and one line on standard error, not after minutes of a join
nobody asked for."""
import contextlib
import io

import pyarrow as pa

import datagen

_ASKED = set()      # id(session): the question is asked once a session


def _placement_of_string_equality(session) -> str:
    from spark_rapids_tpu.api import functions as F
    one = session.create_dataframe(pa.table({"s": pa.array(["a"])}))
    with contextlib.redirect_stdout(io.StringIO()):
        return one.filter(F.col("s") == "a").explain()


def register(session, name: str, table, spec: dict) -> None:
    if id(session) not in _ASKED:
        _ASKED.add(id(session))
        plan = _placement_of_string_equality(session)
        if "Cpu" in plan or "!" in plan:
            raise SystemExit(
                "perfbench: the program places a string equality predicate "
                f"on the host ({plan.strip().splitlines()[0].strip()}): it "
                "cannot run a configuration that guarantees every operator "
                "on the device")
    datagen.load_module("sources", "memory").register(session, name, table,
                                                      spec)
