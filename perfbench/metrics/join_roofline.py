"""kernels: the least time the chip could take for the JOINS' work over the
device seconds per query of the join kernels (XLA modules ``jit_join_build``,
``jit_join_probe`` and ``jit__pairs_gather``, ``exec/joins.py``: the build
side sorted by its key once a join, each stream batch probed against it,
the matched pairs' columns gathered) in the profiler trace. The work: every row that entered an equi-join (counter
``join.rows``: ``build`` and ``stream``), its key and payload columns (the
``cols`` of the operator's ``join.build`` / ``join.probe`` spans) at the
``domain_bytes`` the configuration gives them, read once, over the HBM
bandwidth of perfbench/peaks.json (HBM bandwidth bounds it: a join does a
few operations a byte). Rows and columns come from part B of a traced run
(the engine's tracer), the modules' seconds from part A (the profiler).
Nothing where the program has no such module, counter or span."""
import roofline     # perfbench/roofline.py: run.py puts perfbench/ on the path
import span_reduce
import trace_reduce

MODULES = ("jit_join_build", "jit_join_probe", "jit__pairs_gather")


def join_bytes(events, tables: dict) -> int:
    """Bytes the joins of the traced queries read, at domain widths."""
    width = {c: int(spec["domain_bytes"]) for t in tables.values()
             for c, spec in t["columns"].items()}
    cols = {}       # (operator number, "join.build" | "join.probe") -> cols
    for e in events:
        if e.get("ph") == "X" and e["name"] in ("join.build", "join.probe") \
                and (e.get("args") or {}).get("cols"):
            op = int(e["args"]["exec"].rsplit("@", 1)[-1])
            cols[(op, e["name"])] = e["args"]["cols"]
    total = 0
    for e in events:
        if e.get("ph") == "C" and e["name"] == "join.rows":
            for side, span in (("build", "join.build"),
                               ("stream", "join.probe")):
                names = cols.get((int(e["args"]["op"]), span), [])
                # a column the configuration does not name (a computed
                # one) counts as an 8-byte lane
                total += int(e["args"][side]) * sum(width.get(c, 8)
                                                    for c in names)
    return total


def read(run):
    prof, spans = run.get("profile"), run.get("spans")
    if not prof or not prof.get("queries") or not spans \
            or not spans["queries"]:
        return None
    try:
        got = span_reduce._reduce_file(
            trace_reduce.find_xplane(span_reduce.SCRATCH_TRACE))
    except FileNotFoundError:
        return None
    if got is None:
        return None
    busy = sum(s for name, s in got["device_by_module"] if name in MODULES)
    nbytes = join_bytes(spans["events"], run["tables"])
    if busy <= 0 or nbytes <= 0:
        return None
    bw = roofline.peaks_for(run["device"]["kind"])["hbm_bytes_per_s"]
    least = nbytes / len(spans["queries"]) / bw
    return 100.0 * least / (busy / prof["queries"])
