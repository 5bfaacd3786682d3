"""device: the share of part A's traced window in which the device is idle and
the innermost engine span is ``srtpu/transfer/d2h.*``: the exposed part of
a blocking fetch (the round trip after the last kernel has ended).
Read by perfbench/span_reduce.py: the engine's spans on the profiler's
clock, every idle gap intersected with the innermost span open on the
client's thread; the five ``idle_*`` shares add up to ``device_idle_pct``.
Nothing where the program writes no span into the profiler's trace."""
import span_reduce  # perfbench/span_reduce.py: run.py puts perfbench/ on the path


def read(run):
    return span_reduce.idle_pct(run, "fetch")
