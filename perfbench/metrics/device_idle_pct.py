"""device: 1 - union of the device operations' intervals / traced span,
from the profiler trace of a few seconds of the steady loop
(perfbench/trace_reduce.py). Part A of a traced run."""


def read(run):
    prof = run.get("profile")
    return None if not prof else prof["idle_pct"]
