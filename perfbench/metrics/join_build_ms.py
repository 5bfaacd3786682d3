"""kernels: milliseconds per query the host spent inside the joins'
``join.build`` spans (``exec/joins.py``: the build side of an equi-join
made ready, once a join a query: its batches concatenated, its key
multiplicity read, whatever the stream batches are then probed against).
On the host's clock, so it includes the wait for the device where the
build blocks. Part B of a traced run. Nothing where the program records
no such span."""


def read(run):
    spans = run.get("spans")
    if not spans or not spans["queries"]:
        return None
    builds = [e["dur"] for e in spans["events"]
              if e.get("ph") == "X" and e["name"] == "join.build"]
    if not builds:
        return None
    return sum(builds) / 1e6 / len(spans["queries"])
