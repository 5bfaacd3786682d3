"""kernels: host seconds per query of the operators that are not scans —
the SELF time of their ``cat="exec"`` spans in the engine's tracer: a span
less what the spans it is the parent of cover (children operators, uploads,
blocking fetches, semaphore waits). What is left is the operator's own host
work: building and dispatching kernels, and the glue between them. Part B
of a traced run. Nothing where spans carry no ``parent``."""


def read(run):
    spans = run.get("spans")
    if not spans or not spans["queries"]:
        return None
    events = [e for e in spans["events"] if e.get("ph") == "X"]
    ops = {e["id"]: e["dur"] for e in events
           if e.get("cat") == "exec" and "Scan" not in e["name"]
           and "id" in e}
    if not ops:
        return None
    for e in events:
        if e.get("parent") in ops:
            ops[e["parent"]] -= e["dur"]
    return sum(ops.values()) / 1e9 / len(spans["queries"])
