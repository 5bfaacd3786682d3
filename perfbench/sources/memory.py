"""Source kind ``memory``: a table registered with ``create_dataframe``,
so that after the first query ``InMemoryScanExec``'s device scan cache
serves it from HBM. A table's ``partition_rows`` (configuration) cuts it
into that many-row partitions, which are the device batches.

A source kind is ``register(session, name, table, spec)``: ``table`` holds
the columns the cell's query reads, ``spec`` is the table's entry in the
configuration."""


def register(session, name: str, table, spec: dict) -> None:
    part = spec.get("partition_rows")
    parts = -(-table.num_rows // int(part)) if part else 1
    session.create_dataframe(table, num_partitions=parts) \
        .create_or_replace_temp_view(name)
