"""kernels: groups that left the query's partitioned aggregates, per
query: the tracer's ``agg.highcard`` counter (``exec/aggregate.py``,
written once per execution of a grouped aggregate whose partials did not
fit one bucket and finished in partitions), ``groups`` summed over the
query's such aggregates. A count: what the aggregate was asked for,
whatever implements it (Q18 at SF10: the 15.0M orders of ``lineitem``).
Part B of a traced run. Nothing where the program writes no such
counter."""


def read(run):
    spans = run.get("spans")
    if not spans or not spans["queries"]:
        return None
    groups = [e["args"]["groups"] for e in spans["events"]
              if e.get("ph") == "C" and e["name"] == "agg.highcard"]
    return sum(groups) / len(spans["queries"]) if groups else None
