"""kernels: the least time the chip could take for the QUERY's work
(perfbench/roofline.py: the query's columns at their domain widths, read
once, over the HBM bandwidth of perfbench/peaks.json — HBM bandwidth
bounds it) over the device's busy seconds per query in the profiler
trace. Part A of a traced run. Nothing traced, nothing reported."""
import roofline  # perfbench/roofline.py: run.py puts perfbench/ on the path


def read(run):
    prof = run.get("profile")
    if not prof or not prof.get("queries") or prof["busy_s"] <= 0:
        return None
    least = roofline.least_seconds(run["query_text"], run["tables"],
                                   run["device"]["kind"])
    busy_per_query = prof["busy_s"] / prof["queries"]
    return 100.0 * least["seconds"] / busy_per_query
