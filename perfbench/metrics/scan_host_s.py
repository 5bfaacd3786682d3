"""scan + H2D: host seconds per query inside the scan operators'
``cat="exec"`` spans (pyarrow decode, ingest narrowing and the
``device_put`` enqueue are synchronous host work, so the host clock is
right for them). Scans are leaves, so their self time is their span time
less the ``h2d.device`` waits nested in them, which only the tracer
forces (``columnar/transfer.py:traced_device_put``). Part B of a traced
run.
"""


def read(run):
    spans = run.get("spans")
    if not spans or not spans["queries"]:
        return None
    scans = [e for e in spans["events"]
             if e.get("ph") == "X" and e.get("cat") == "exec"
             and "Scan" in e["name"]]
    if not scans:
        return None
    ids = {e["id"] for e in scans}
    waits = sum(e["dur"] for e in spans["events"]
                if e.get("ph") == "X" and e["name"].endswith(".device")
                and e.get("cat") == "transfer" and e.get("parent") in ids)
    total = sum(e["dur"] for e in scans) - waits
    return total / 1e9 / len(spans["queries"])
