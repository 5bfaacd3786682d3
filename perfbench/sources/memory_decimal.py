"""Source kind ``memory_decimal``: ``memory`` (``sources/memory.py``: the
table registered with ``create_dataframe``, served from HBM by the scan
cache) for a configuration whose guarantees include Spark's decimal result
types.

Before it registers a table that holds decimal columns it asks the
session ONE question, through the public API and without running
anything: which type does it give the product of such a column with
itself? Spark's rule is ``decimal(p,s) * decimal(p,s) = decimal(2p+1,
2s)`` (asked of a column whose product stays within 38 digits). A
program that answers otherwise cannot run the configuration: it would
compute at one scale under another scale's label and return other types
than Spark's (at the parent of PR 28 every run of such a cell reads
``type_mismatch`` 20, its device time spent on arithmetic that is not the
configuration's). The run then ends here, during set-up, with an exit
code other than 0 and one line on standard
error, not after a window of wrong answers."""
import pyarrow as pa

import datagen


def _asked_type(session, table, column: str):
    from spark_rapids_tpu.api import functions as F
    one = session.create_dataframe(table.select([column]).slice(0, 1))
    product = one.select((F.col(column) * F.col(column)).alias("p"))
    return product.schema.fields[0].dtype


def register(session, name: str, table, spec: dict) -> None:
    for field in table.schema:
        if not pa.types.is_decimal(field.type) \
                or 2 * field.type.precision + 1 > 38:
            continue
        want = (2 * field.type.precision + 1, 2 * field.type.scale)
        got = _asked_type(session, table, field.name)
        if (getattr(got, "precision", None),
                getattr(got, "scale", None)) != want:
            raise SystemExit(
                f"perfbench: the program types {field.name} * {field.name} "
                f"({field.type}) as {got}, Spark as decimal{want}: it "
                f"cannot run a configuration that guarantees Spark's "
                f"decimal result types")
        break
    datagen.load_module("sources", "memory").register(session, name, table,
                                                      spec)
