"""Run by hand: re-derives, through perfbench/trace_reduce.py, the busy and
idle time of the small recorded trace beside this file, and checks
perfbench/roofline.py's bytes for every configuration against the
hand-worked numbers in expected.json. Not a tier-1 test; needs no chip
(reading a trace needs only ``jax.profiler.ProfileData``).

    python3 perfbench/selfcheck/check.py
"""
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
PERFBENCH = os.path.dirname(HERE)
sys.path.insert(0, PERFBENCH)

import roofline       # noqa: E402
import trace_reduce   # noqa: E402


def close(a: float, b: float, rel: float = 1e-9) -> bool:
    return abs(a - b) <= rel * max(abs(a), abs(b))


def main() -> int:
    with open(os.path.join(HERE, "expected.json")) as f:
        expected = json.load(f)
    bad = []
    got = trace_reduce.reduce(trace_reduce.load(
        os.path.join(HERE, expected["trace"]["file"])))
    for key, want in expected["trace"]["reduced"].items():
        ok = got[key] == want if isinstance(want, int) \
            else close(got[key], want)
        print(f"trace {key}: got {got[key]!r} expected {want!r} "
              f"{'ok' if ok else 'MISMATCH'}")
        bad += [] if ok else [key]
    for case in expected["roofline"]:
        with open(os.path.join(PERFBENCH, "configs",
                               case["config"] + ".json")) as f:
            tables = json.load(f)["tables"]
        with open(os.path.join(PERFBENCH, "queries",
                               case["query"] + ".sql")) as f:
            text = f.read()
        nbytes = roofline.query_bytes(text, tables)
        least = roofline.least_seconds(text, tables, case["device_kind"])
        ok = nbytes == case["bytes"] and \
            close(least["seconds"], case["least_seconds"])
        print(f"roofline {case['config']} {case['query']}: bytes {nbytes} "
              f"(expected {case['bytes']}: {case['worked']}), least "
              f"{least['seconds']!r} s bound by {least['bound_by']} "
              f"{'ok' if ok else 'MISMATCH'}")
        bad += [] if ok else [case["query"]]
    print("selfcheck", "FAILED: %s" % bad if bad else "passed")
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
