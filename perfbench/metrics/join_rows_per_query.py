"""kernels: rows that entered the query's equi-joins, per query: the
tracer's ``join.rows`` counter (``exec/joins.py``, written once per
equi-join execution from numbers the operator holds or fetches once at
its end), ``build`` + ``stream`` summed over the query's joins. A count:
what the joins were handed, whatever implements them (Q3 at SF10: about
42M with every one-table predicate below its join, about 92M without).
Part B of a traced run. Nothing where the program writes no such
counter."""


def read(run):
    spans = run.get("spans")
    if not spans or not spans["queries"]:
        return None
    rows = [e["args"]["build"] + e["args"]["stream"]
            for e in spans["events"]
            if e.get("ph") == "C" and e["name"] == "join.rows"]
    return sum(rows) / len(spans["queries"]) if rows else None
