"""kernels: rows that a checked decimal operation flagged as leaving the
64-bit unscaled lane, per traced run: the tracer's ``decimal.checked``
counter (``overflow_rows``; written once per aggregate execution beside
``agg.carry``, and by the query's sink for projections). A count. Part B
of a traced run. 0 in a sound run (a traffic file's ``require_at_most``
holds a traced run to that); nothing where the program writes no such
counter (a program without the decimal path, or a query that checks no
decimal)."""


def read(run):
    spans = run.get("spans")
    if not spans or not spans["queries"]:
        return None
    rows = [e["args"]["overflow_rows"] for e in spans["events"]
            if e.get("ph") == "C" and e["name"] == "decimal.checked"]
    return sum(rows) if rows else None
