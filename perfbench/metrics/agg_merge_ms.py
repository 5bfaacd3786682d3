"""kernels: milliseconds per query the host spent inside the partitioned
finish of a high-cardinality aggregate: the spans ``agg.partition``
(``exec/aggregate.py``: every partial put in the order of its keys'
partitions, and the one fetch of the partitions' row counts, which waits
for the update kernels before it) and ``agg.merge_part`` (one a partition:
its rows collected, merged and finalized; enqueues, no read). On the
host's clock. Part B of a traced run. Nothing where the program records
no such span."""

SPANS = ("agg.partition", "agg.merge_part")


def read(run):
    spans = run.get("spans")
    if not spans or not spans["queries"]:
        return None
    took = [e["dur"] for e in spans["events"]
            if e.get("ph") == "X" and e["name"] in SPANS]
    if not took:
        return None
    return sum(took) / 1e6 / len(spans["queries"])
