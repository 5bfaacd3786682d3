"""entry + plan: milliseconds per query inside the engine's own planning
spans, ``plan.sql`` (``TpuSession.sql``: parse, analyse) and
``plan.physical`` (``plan_query``: overrides, cost, placement), from the
engine's tracer; the mean over the traced queries. Part B of a traced run.
Nothing where the program records no such span."""


def read(run):
    spans = run.get("spans")
    if not spans or not spans["queries"]:
        return None
    plans = [e["dur"] for e in spans["events"]
             if e.get("ph") == "X" and e.get("cat") == "plan"]
    if not plans:
        return None
    return sum(plans) / 1e6 / len(spans["queries"])
