"""Chrome-trace-event JSON export (loads in Perfetto / chrome://tracing).

Format: the ``traceEvents`` array flavor of the Trace Event Format —
complete events (``ph: "X"``) for spans, ``C`` for counters, ``i`` for
instants, plus ``M`` metadata events naming each process lane (driver /
worker-N). Timestamps are microseconds as the format requires; the
tracer records nanoseconds internally.
"""
from __future__ import annotations

import json
from typing import List, Optional

from .core import Tracer

__all__ = ["chrome_trace", "write_chrome_trace", "load_chrome_trace"]


def _to_chrome(ev: dict) -> dict:
    out = {"ph": ev["ph"], "name": ev["name"], "cat": ev.get("cat", ""),
           "ts": ev["ts"] / 1000.0, "pid": ev["pid"], "tid": ev["tid"]}
    if ev["ph"] == "X":
        out["dur"] = ev.get("dur", 0) / 1000.0
    if ev["ph"] == "i":
        out["s"] = ev.get("s", "t")
    args = ev.get("args")
    if ev.get("q") is not None:
        # the query ordinal every span of one query shares (trace/core.py)
        args = {**(args or {}), "q": ev["q"]}
    if args:
        out["args"] = args
    return out


def chrome_trace(tracer: Tracer, drain: bool = True) -> dict:
    """Tracer buffer -> Chrome trace dict with stable pid/tid lanes."""
    events, dropped = tracer.export_events(drain=drain)
    out: List[dict] = []
    for pid, name in sorted(tracer.proc_names.items()):
        out.append({"ph": "M", "name": "process_name", "pid": pid,
                    "tid": 0, "args": {"name": name}})
    out.extend(_to_chrome(ev) for ev in events)
    return {"traceEvents": out, "displayTimeUnit": "ms",
            "otherData": {"dropped_events": dropped,
                          "generator": "spark_rapids_tpu.trace"}}


def write_chrome_trace(path: str, tracer: Tracer,
                       drain: bool = True) -> str:
    with open(path, "w") as f:
        json.dump(chrome_trace(tracer, drain=drain), f)
    return path


def load_chrome_trace(path: str) -> List[dict]:
    """Load a Chrome trace file -> its traceEvents list (accepts both
    the object flavor and a bare event array)."""
    with open(path) as f:
        doc = json.load(f)
    if isinstance(doc, list):
        return doc
    return list(doc.get("traceEvents", []))
