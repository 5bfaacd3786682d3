"""kernels: the least time the chip could take for the partitioned
AGGREGATE's work over the device seconds per query of the aggregate's
kernels in the profiler trace: the XLA modules of the sort-based group-by
(``jit_k_prep``, ``jit_mk``, ``jit_k_scan``, ``jit_k_pack``:
``exec/aggregate.py``'s split update and merge kernels) and of the
partitioned finish (``jit_agg_partition``, ``jit_agg_collect``). The work:
every row that entered an aggregate which finished in partitions (counter
``agg.highcard``: ``rows_in``), the columns it reads (the ``cols`` of the
operator's ``agg.partition`` span) at the ``domain_bytes`` the
configuration gives them, read once, over the HBM bandwidth of
perfbench/peaks.json (HBM bandwidth bounds it: a group-by does a few
operations a byte). Rows and columns come from part B of a traced run (the
engine's tracer), the modules' seconds from part A (the profiler). The
modules also run the query's other sort-based aggregates (Q18's last one,
a few thousand rows), so the share errs low. Nothing where the program has
no such module, counter or span."""
import roofline     # perfbench/roofline.py: run.py puts perfbench/ on the path
import span_reduce
import trace_reduce

MODULES = ("jit_k_prep", "jit_mk", "jit_k_scan", "jit_k_pack",
           "jit_agg_partition", "jit_agg_collect")


def agg_bytes(events, tables: dict) -> int:
    """Bytes the partitioned aggregates of the traced queries read, at
    domain widths."""
    width = {c: int(spec["domain_bytes"]) for t in tables.values()
             for c, spec in t["columns"].items()}
    cols = {}       # operator number -> the columns it reads
    for e in events:
        if e.get("ph") == "X" and e["name"] == "agg.partition" \
                and (e.get("args") or {}).get("cols"):
            cols[int(e["args"]["exec"].rsplit("@", 1)[-1])] = \
                e["args"]["cols"]
    total = 0
    for e in events:
        if e.get("ph") == "C" and e["name"] == "agg.highcard":
            names = cols.get(int(e["args"]["op"]), [])
            # a column the configuration does not name (a computed one)
            # counts as an 8-byte lane
            total += int(e["args"]["rows_in"]) * sum(width.get(c, 8)
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
    nbytes = agg_bytes(spans["events"], run["tables"])
    if busy <= 0 or nbytes <= 0:
        return None
    bw = roofline.peaks_for(run["device"]["kind"])["hbm_bytes_per_s"]
    least = nbytes / len(spans["queries"]) / bw
    return 100.0 * least / (busy / prof["queries"])
