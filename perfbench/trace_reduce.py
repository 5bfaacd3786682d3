"""From a profiler trace (``.xplane.pb``) to device busy and idle time.

Read with ``jax.profiler.ProfileData`` and nothing else. A device plane is
one whose name starts with ``/device:`` (``/device:TPU:0``); its line
``XLA Ops`` holds one event per device operation. Busy time is the UNION
of those events' intervals (operations on other lines — modules, steps —
only wrap them and are left out); idle share is 1 - busy / traced window.
The traced window is given by the caller's own host annotations
(``perfbench.window``), falling back to the device events' own extent.

Checked against one small recorded trace: perfbench/selfcheck/.
"""
from __future__ import annotations

import glob
import os

OPS_LINE = "XLA Ops"
WINDOW_ANNOTATION = "perfbench.window"
QUERY_ANNOTATIONS = ("perfbench.sql", "perfbench.collect")


def profiler_options():
    """The options every trace of the benchmark is taken with: the
    caller's annotations and no Python call stacks, so traces stay small."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    return opts


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def _union(intervals):
    """Merged, sorted intervals and their total length."""
    merged = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            if b > merged[-1][1]:
                merged[-1][1] = b
        else:
            merged.append([a, b])
    return merged, sum(b - a for a, b in merged)


def load(path: str) -> dict:
    """{"devices": {plane: [(name, start_ns, end_ns)]},
        "host": [(name, start_ns, end_ns)] of the benchmark's annotations,
        "lines": {plane: {line: n_events}}} — everything the reduction
    needs, as plain lists."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    devices, host, lines = {}, [], {}
    wanted = set(QUERY_ANNOTATIONS) | {WINDOW_ANNOTATION}
    for plane in data.planes:
        per_line = lines.setdefault(plane.name, {})
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                events = [(e.name, float(e.start_ns),
                           float(e.start_ns) + float(e.duration_ns))
                          for e in line.events]
                per_line[line.name] = len(events)
                if line.name == OPS_LINE:
                    devices.setdefault(plane.name, []).extend(events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                n = 0
                for e in line.events:
                    n += 1
                    if e.name in wanted:
                        host.append((e.name, float(e.start_ns),
                                     float(e.start_ns)
                                     + float(e.duration_ns)))
                per_line[line.name] = n
    return {"devices": devices, "host": host, "lines": lines}


def reduce(loaded: dict, top: int = 10) -> dict:
    """busy_s (averaged over the device planes), window_s, idle_pct, the
    device operations that took most time and the longest idle gaps by
    what the host was doing. Returns None where no device operation was
    traced (a reader then reports nothing)."""
    devices = {k: v for k, v in loaded["devices"].items() if v}
    if not devices:
        return None
    host = sorted(loaded["host"], key=lambda e: e[1])
    windows = [e for e in host if e[0] == WINDOW_ANNOTATION]
    if windows:
        w0, w1 = windows[0][1], windows[-1][2]
    else:
        w0 = min(e[1] for evs in devices.values() for e in evs)
        w1 = max(e[2] for evs in devices.values() for e in evs)
    busy, op_time, gaps = [], {}, []
    for evs in devices.values():
        clipped = []
        for name, a, b in evs:
            if b > w0 and a < w1:
                a, b = max(a, w0), min(b, w1)
                clipped.append((a, b))
                op_time[name] = op_time.get(name, 0.0) + (b - a)
        merged, total = _union(clipped)
        busy.append(total)
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        for i in range(0, len(edges), 2):
            if edges[i + 1] > edges[i]:
                gaps.append((edges[i], edges[i + 1]))
    n_dev = len(devices)
    window_s = (w1 - w0) / 1e9
    busy_s = sum(busy) / n_dev / 1e9
    queries = [e for e in host if e[0] in QUERY_ANNOTATIONS]

    def doing(a, b):
        mid = (a + b) / 2
        for name, s, e in queries:
            if s <= mid <= e:
                return "inside " + name
        return "between queries" if queries else "host (no annotation)"

    by_what = {}
    for a, b in gaps:
        what = doing(a, b)
        by_what[what] = by_what.get(what, 0.0) + (b - a)
    longest = sorted(gaps, key=lambda g: g[0] - g[1])[:top]
    return {
        "busy_s": busy_s,
        "window_s": window_s,
        "idle_pct": 100.0 * (1.0 - busy_s / window_s),
        "n_devices": n_dev,
        "n_device_events": sum(len(v) for v in devices.values()),
        "n_queries_annotated": sum(e[0] == "perfbench.collect"
                                   for e in queries),
        "device_ops": [[k, v / 1e9 / n_dev] for k, v in sorted(
            op_time.items(), key=lambda kv: -kv[1])[:top]],
        "idle_by_host_state": [[k, v / 1e9 / n_dev] for k, v in sorted(
            by_what.items(), key=lambda kv: -kv[1])],
        "idle_gaps": [["%s (at +%.6f s)" % (doing(a, b), (a - w0) / 1e9),
                       (b - a) / 1e9] for a, b in longest],
    }
