"""scan + H2D: the tracer's ``h2d.bytes`` counter over the traced queries,
per query. A count. Part B of a traced run. Reads next to
nothing where every batch came from the device scan cache (a traffic
file's ``require_at_most`` holds a traced run to that, or the run is not
correct)."""


def read(run):
    spans = run.get("spans")
    if not spans or not spans["queries"]:
        return None
    total = sum(e["args"]["bytes"] for e in spans["events"]
                if e.get("ph") == "C" and e["name"] == "h2d.bytes")
    return total / len(spans["queries"])
