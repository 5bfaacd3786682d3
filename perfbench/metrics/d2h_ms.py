"""result fetch (D2H): milliseconds per query the host spent blocked in
``d2h.*.transfer`` spans (``columnar/transfer.py:traced_device_get``):
waiting for the chip to finish what the fetched arrays depend on, then
copying them. On the host's clock, so it holds device time the host could
not overlap; the part of it during which the device was idle is
``idle_in_fetch_pct``. Part B of a traced run. Nothing where the program
records no such span."""


def read(run):
    spans = run.get("spans")
    if not spans or not spans["queries"]:
        return None
    gets = [e["dur"] for e in spans["events"]
            if e.get("ph") == "X" and e.get("cat") == "transfer"
            and e["name"].startswith("d2h") and e["name"].endswith(".transfer")]
    if not gets:
        return None
    return sum(gets) / 1e6 / len(spans["queries"])
