"""result fetch (D2H): milliseconds per query the host spent turning
fetched int64 lanes into Arrow ``decimal128`` columns of the declared
types (span ``d2h.decimal.finish``, ``columnar/column.py:
arrow_from_numpy``): host work the float path does not have. The final
division and rounding of the averages is done on the device, so this is
all the decimal path adds on the host. On the host's clock. Part B of a
traced run. Nothing where the program records no such span."""


def read(run):
    spans = run.get("spans")
    if not spans or not spans["queries"]:
        return None
    builds = [e["dur"] for e in spans["events"]
              if e.get("ph") == "X" and e["name"] == "d2h.decimal.finish"]
    if not builds:
        return None
    return sum(builds) / 1e6 / len(spans["queries"])
