"""entry + plan: per query, from the benchmark's clock at the ``sql()``
call to the start of that query's first ``cat="exec"`` span in the
engine's tracer (the same ``perf_counter_ns`` clock, the same process);
the mean over the traced queries, in milliseconds. Part B of a traced run.
"""
import bisect


def read(run):
    spans = run.get("spans")
    if not spans or not spans["queries"]:
        return None
    starts = sorted(e["ts"] for e in spans["events"]
                    if e.get("ph") == "X" and e.get("cat") == "exec")
    gaps = []
    for t0, t1 in spans["queries"]:
        i = bisect.bisect_left(starts, t0)
        if i < len(starts) and starts[i] <= t1:
            gaps.append((starts[i] - t0) / 1e6)
    return sum(gaps) / len(gaps) if gaps else None
