"""The closed loop: ``clients`` callers (one here: a caller of
``collect_arrow()`` waits for its answer) issue the query again and again.
No query starts after ``seconds`` (the first always does); the window ends
when the last one started has returned, and that elapsed time is the
divisor of every rate.

A loop is ``run(issue, seconds, traffic) -> elapsed seconds``; ``issue()``
is one client call, timed and recorded by run.py."""
import time


def run(issue, seconds: float, traffic: dict) -> float:
    if int(traffic.get("clients", 1)) != 1:
        raise ValueError("the closed loop drives one client")
    t0 = time.perf_counter()
    while True:
        issue()
        if time.perf_counter() - t0 >= seconds:
            return time.perf_counter() - t0
