"""How perfbench/selfcheck/small_trace.xplane.pb was recorded (run by
hand on the chip; PR 24): three small device operations with sleeps
between them, under the annotations run.py writes, traced with the
options run.py uses. Prints what trace_reduce.py makes of it, so that the
numbers in expected.json can be checked against the trace by hand.

    python3 perfbench/selfcheck/record_trace.py <out_dir>
"""
import json
import os
import shutil
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))


def main(out_dir: str) -> int:
    import jax
    import jax.numpy as jnp

    import trace_reduce
    shutil.rmtree(out_dir, ignore_errors=True)
    os.makedirs(out_dir)
    f = jax.jit(lambda x: jnp.sort(x * 2.0).sum())
    x = jnp.arange(1 << 20, dtype=jnp.float32)
    f(x).block_until_ready()
    jax.profiler.start_trace(
        out_dir, profiler_options=trace_reduce.profiler_options())
    with jax.profiler.TraceAnnotation(trace_reduce.WINDOW_ANNOTATION):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("perfbench.sql"):
                time.sleep(0.002)
            with jax.profiler.TraceAnnotation("perfbench.collect"):
                f(x).block_until_ready()
            time.sleep(0.005)
    jax.profiler.stop_trace()
    path = trace_reduce.find_xplane(out_dir)
    loaded = trace_reduce.load(path)
    print(json.dumps({"device": jax.devices()[0].device_kind,
                      "file": path, "bytes": os.path.getsize(path),
                      "lines": loaded["lines"],
                      "reduced": trace_reduce.reduce(loaded)}, indent=1))
    for plane, evs in loaded["devices"].items():
        for e in evs[:40]:
            print(plane, e)
    print(loaded["host"])
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
