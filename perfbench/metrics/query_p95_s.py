"""end to end: nearest-rank 95th percentile of the client-side seconds
(``sql()`` call to Arrow result) of ALL queries of the window."""
import math


def read(run):
    ordered = sorted(run["window"]["latencies_s"])
    return ordered[max(0, math.ceil(0.95 * len(ordered)) - 1)]
