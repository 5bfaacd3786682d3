"""From a profiler trace to the device's idle time BY WHAT THE HOST WAS
DOING, and its busy time by XLA module.

While a ``jax.profiler`` session runs, the engine writes its own spans into
the profiler's trace as annotations named ``srtpu/<cat>/<name>``
(spark_rapids_tpu/trace/core.py), so part A of a traced run holds them on
the host planes of the same ``.xplane.pb`` that holds the device's
``XLA Ops`` line. Three steps:

1. **The clocks.** Device and host timestamps of one trace differ by about a
   millisecond. The offset (host time = device time + offset) is bounded
   from causality on both sides by the runtime's own events, which carry the
   ``run_id`` of the ``XLA Modules`` event they belong to: a module does not
   start on the device before its ``DoEnqueueProgram`` started on the host
   (the largest such difference bounds the offset from below), and its
   ``CompleteCallbacks`` does not start on the host before the module ended
   on the device (the smallest bounds it from above). The midpoint is
   applied and the interval printed: a gap shorter than the interval may be
   billed to a neighbour of the span it fell in.
2. **The gaps.** Idle gaps are those of trace_reduce.py (the union of the
   ``XLA Ops`` events inside ``perfbench.window``), so what is attributed
   here adds up to ``device_idle_pct``.
3. **The attribution.** Every gap, moved onto the host's clock, is
   intersected with the INNERMOST engine span open at the time on the
   client's thread (the one that holds ``perfbench.collect``) and billed, by
   intersection, to that span's class: ``plan`` (``srtpu/plan/*``), ``exec``
   (an operator's own span and the uploads it enqueues), ``fetch``
   (``srtpu/transfer/d2h.*``: the blocking gets), ``query_other`` (the
   ``query`` span's own time, and whatever else is open inside it) or
   ``unattributed`` (no engine span open: the client's loop).

A trace without ``srtpu/`` events (a program that writes none) reduces to
``None`` and the readers report nothing. The arithmetic is checked on
hand-made intervals by perfbench/tests/test_span_reduce.py.
"""
from __future__ import annotations

import bisect
import functools
import os

import trace_reduce

SPAN_PREFIX = "srtpu/"
MODULES_LINE = "XLA Modules"
ENQUEUE_EVENT = "DoEnqueueProgram"
COMPLETE_EVENT = "CompleteCallbacks"
CLIENT_ANNOTATION = "perfbench.collect"
CLASSES = ("plan", "exec", "fetch", "query_other", "unattributed")
#: where run.py keeps the trace of part A while the readers run
SCRATCH_TRACE = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "bench_traces", "perfbench")


def span_class(name):
    """The class an idle nanosecond is billed to when ``name`` is the
    innermost engine span open (``None``: no span open)."""
    if name is None:
        return "unattributed"
    cat, _, leaf = name[len(SPAN_PREFIX):].partition("/")
    if cat in ("plan", "exec"):
        return cat
    if cat == "transfer":
        return "fetch" if leaf.startswith("d2h") else "exec"
    return "query_other"


def offset_bounds(modules, enqueues, completes):
    """(lowest, highest) offset in ns that keeps every module after its
    enqueue and before its completion callback; a side nothing bounds is
    ``None``. ``modules``: (name, start, end, run_id) on the device's clock;
    ``enqueues`` / ``completes``: run_id -> start on the host's clock."""
    lo = hi = None
    for _name, start, end, run_id in modules:
        if run_id in enqueues:
            d = enqueues[run_id] - start
            lo = d if lo is None else max(lo, d)
        if run_id in completes:
            d = completes[run_id] - end
            hi = d if hi is None else min(hi, d)
    return lo, hi


def idle_gaps(ops, w0, w1):
    """The intervals of [w0, w1] no device operation covers; the rule of
    trace_reduce.reduce."""
    merged, _ = trace_reduce._union(
        (max(a, w0), min(b, w1)) for a, b in ops if b > w0 and a < w1)
    edges = [w0] + [x for iv in merged for x in iv] + [w1]
    return [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]


def innermost(spans):
    """[(t, name)]: from ``t`` on (until the next entry) ``name`` is the
    innermost open span of ``spans`` ((name, start, end), one thread, so
    nested), or None where none is open."""
    cuts, stack = [], []

    def cut(t):
        # a child that outlasts its parent by a clock tick must not turn
        # time backwards
        t = max(t, cuts[-1][0]) if cuts else t
        cuts.append((t, stack[-1][1] if stack else None))

    for name, a, b in sorted(spans, key=lambda s: (s[1], -s[2])):
        while stack and stack[-1][0] <= a:
            end = stack.pop()[0]
            cut(end)
        stack.append((b, name))
        cut(a)
    while stack:
        end = stack.pop()[0]
        cut(end)
    return cuts


def attribute(gaps, spans, offset):
    """Idle ns by the innermost span's name (None: no span open). ``gaps``
    on the device's clock, ``spans`` on the host's."""
    return bill(gaps, innermost(spans), offset)


def bill(gaps, cuts, offset):
    """``attribute`` over the timeline ``innermost`` made of the spans."""
    times = [t for t, _ in cuts]
    by_name = {}
    for a, b in gaps:
        a, b = a + offset, b + offset
        i = bisect.bisect_right(times, a) - 1
        while a < b:
            name = cuts[i][1] if i >= 0 else None
            until = min(b, times[i + 1]) if i + 1 < len(times) else b
            if until > a:
                by_name[name] = by_name.get(name, 0.0) + (until - a)
                a = until
            i += 1
    return by_name


def reduce(loaded, top=5):
    """``loaded`` (see ``load``) -> the offset, idle seconds by class and
    by span name, device seconds by XLA module, all averaged over the
    device planes, and the ``top`` longest gaps each with its own split by
    span; None where there is no device operation, no window or no engine
    span to attribute to."""
    devices = {k: v for k, v in loaded["devices"].items() if v["ops"]}
    if not devices or not loaded["spans"] or loaded["window"] is None:
        return None
    w0, w1 = loaded["window"]
    modules = [m for d in devices.values() for m in d["modules"]]
    lo, hi = offset_bounds(modules, loaded["enqueues"], loaded["completes"])
    known = [x for x in (lo, hi) if x is not None]
    offset = sum(known) / len(known) if known else 0.0
    n_dev = len(devices)
    cuts = innermost(loaded["spans"])
    by_name, by_module, longest = {}, {}, []
    for d in devices.values():
        gaps = idle_gaps(d["ops"], w0, w1)
        for name, ns in bill(gaps, cuts, offset).items():
            by_name[name] = by_name.get(name, 0.0) + ns / n_dev
        longest.extend(sorted(gaps, key=lambda g: g[0] - g[1])[:top])
        for name, a, b, _run in d["modules"]:
            if b > w0 and a < w1:
                name = name.split("(")[0]
                by_module[name] = by_module.get(name, 0.0) \
                    + (min(b, w1) - max(a, w0)) / n_dev
    idle_s = dict.fromkeys(CLASSES, 0.0)
    for name, ns in by_name.items():
        idle_s[span_class(name)] += ns / 1e9
    window_s = (w1 - w0) / 1e9

    def ranked(d):
        return [[k if k is not None else "(no engine span)", v / 1e9]
                for k, v in sorted(d.items(), key=lambda kv: -kv[1])]

    return {"offset_ns": offset, "offset_interval_ns": [lo, hi],
            "window_s": window_s, "idle_s": idle_s,
            "idle_pct": {k: 100.0 * v / window_s for k, v in idle_s.items()},
            "idle_by_span": ranked(by_name),
            "longest_gaps": [
                [(b - a) / 1e9, "+%.6f s" % ((a - w0) / 1e9),
                 ranked(bill([(a, b)], cuts, offset))]
                for a, b in sorted(longest, key=lambda g: g[0] - g[1])[:top]],
            "device_by_module": ranked(by_module)}


def load(path):
    """Everything ``reduce`` needs of an ``.xplane.pb``, as plain lists:
    per device plane the ``XLA Ops`` intervals and the ``XLA Modules``
    events with their run_id; the ``srtpu/`` events of the client's thread;
    the ``perfbench.window`` extent; the runtime's enqueue and completion
    events by run_id."""
    from jax.profiler import ProfileData
    devices, client, enqueues, completes = {}, [], {}, {}
    window = None

    def run_id(e):
        for k, v in e.stats:
            if k == "run_id":
                return int(v)
        return None

    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:"):
            dev = devices.setdefault(plane.name, {"ops": [], "modules": []})
            for line in plane.lines:
                if line.name == trace_reduce.OPS_LINE:
                    dev["ops"].extend(
                        (float(e.start_ns),
                         float(e.start_ns) + float(e.duration_ns))
                        for e in line.events)
                elif line.name == MODULES_LINE:
                    dev["modules"].extend(
                        (e.name, float(e.start_ns),
                         float(e.start_ns) + float(e.duration_ns), run_id(e))
                        for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans, is_client = [], False
                for e in line.events:
                    name = e.name
                    if name.startswith(SPAN_PREFIX):
                        spans.append((name, float(e.start_ns),
                                      float(e.start_ns)
                                      + float(e.duration_ns)))
                    elif name == ENQUEUE_EVENT:
                        enqueues[run_id(e)] = float(e.start_ns)
                    elif name == COMPLETE_EVENT:
                        completes[run_id(e)] = float(e.start_ns)
                    elif name == CLIENT_ANNOTATION:
                        is_client = True
                    elif name == trace_reduce.WINDOW_ANNOTATION:
                        window = (float(e.start_ns), float(e.start_ns)
                                  + float(e.duration_ns))
                if is_client:
                    client.extend(spans)
    return {"devices": devices, "spans": client, "window": window,
            "enqueues": enqueues, "completes": completes}


@functools.lru_cache(maxsize=1)
def _reduce_file(path):
    got = reduce(load(path))
    if got is None:
        print("[spans] no engine span on the profiler's clock in this "
              "trace: nothing attributed", flush=True)
        return None
    lo, hi = got["offset_interval_ns"]
    width = None if lo is None or hi is None else (hi - lo) / 1e6
    print(f"[spans] host = device + offset: interval [{lo}, {hi}] ns "
          f"(width {width} ms), midpoint {got['offset_ns']} ns applied",
          flush=True)
    print(f"[spans] idle seconds by class: {got['idle_s']} of "
          f"window_s={got['window_s']}", flush=True)
    print(f"[spans] idle seconds by innermost span: "
          f"{got['idle_by_span'][:16]}", flush=True)
    print(f"[spans] longest gaps [seconds, at, by innermost span]: "
          f"{got['longest_gaps']}", flush=True)
    print(f"[spans] device seconds by XLA module: "
          f"{got['device_by_module'][:16]}", flush=True)
    return got


def idle_pct(run, cls):
    """The reader of the ``idle_*`` metrics: the share of part A's traced
    window in which the device is idle and the innermost engine span is of
    class ``cls``; None where the run was not traced, the trace is gone or
    the program wrote no span into it."""
    if not run.get("profile"):
        return None
    try:
        path = trace_reduce.find_xplane(SCRATCH_TRACE)
    except FileNotFoundError:
        return None
    got = _reduce_file(path)
    return None if got is None else got["idle_pct"][cls]
