"""Span tracer core: thread-safe, bounded, near-zero when disabled.

Reference analog: the per-exec GpuMetric registry plus NVTX ranges the
reference emits around every GPU op (GpuMetric.ns / NvtxWithMetrics) —
here a single process-global recorder feeding a Chrome-trace exporter
instead of CUPTI.

Design contract (ISSUE 4):

* **one branch when off** — instrumentation sites read the module
  global ``TRACER`` and skip entirely when it is ``None``; no context
  manager, no allocation, no conf lookup on the hot path;
* **monotonic clocks** — timestamps are ``time.perf_counter_ns()``;
  each tracer also records a wall-clock epoch so traces from DIFFERENT
  processes (driver + workers) can be aligned onto one timeline at
  merge time without sacrificing in-process monotonicity;
* **bounded** — events land in a ring buffer of
  ``spark.rapids.tpu.trace.buffer.spans`` slots; overflow drops the
  OLDEST events and counts the drops (a trace must never OOM the
  process it is observing);
* **nested spans** — a contextvar carries the current span id and the
  query ordinal ``q`` of the enclosing ``query`` span, so a child
  operator's span records its parent and its query without any global
  stack (threads and generators interleave safely);
* **two sinks, one recorder** — a span goes to the ring buffer and,
  while a ``jax.profiler`` session runs, also into the profiler's own
  trace as a ``TraceAnnotation`` named ``srtpu/<cat>/<name>``: the
  engine's spans then sit on the clock of the device's ``XLA Ops``
  line. A query that starts while a session runs and no tracer is
  installed gets an ANNOTATE-ONLY tracer (no ring buffer, no dict per
  span) for its own length (:func:`query_tracer`).
"""
from __future__ import annotations

import contextvars
import itertools
import os
import pickle
import threading
import time
from collections import deque
from typing import Dict, List, Optional, Tuple

from jax.profiler import TraceAnnotation as _Annotation

from ..config import register

__all__ = ["Tracer", "active_tracer", "install_tracer",
           "ensure_tracer_from_conf", "query_tracer", "release_query_tracer",
           "TRACE_ENABLED", "TRACE_BUFFER_SPANS", "TRACE_OUTPUT"]

TRACE_ENABLED = register(
    "spark.rapids.tpu.trace.enabled", False,
    "Record per-operator / memory / transfer / shuffle spans into the "
    "query tracer (trace/core.py). Off by default: every instrumentation "
    "site is a single branch when disabled. Export Chrome-trace JSON "
    "via spark.rapids.tpu.trace.output (or LocalCluster.write_trace); "
    "analyze with python -m spark_rapids_tpu.tools.profile "
    "(docs/profiling.md).", commonly_used=True)

TRACE_BUFFER_SPANS = register(
    "spark.rapids.tpu.trace.buffer.spans", 65536,
    "Ring-buffer capacity of the tracer in events; overflow drops the "
    "oldest events and is reported in the exported trace metadata "
    "(a trace must never OOM the process it observes).")

TRACE_OUTPUT = register(
    "spark.rapids.tpu.trace.output", "",
    "When set, every materializing query writes its merged Chrome-trace "
    "JSON here (loads in Perfetto / chrome://tracing). Distributed "
    "queries via LocalCluster.execute() include every worker's spans.")

#: the process-global tracer; ``None`` means tracing is OFF and every
#: instrumentation site costs exactly one attribute load + branch
TRACER: Optional["Tracer"] = None

_SPAN_IDS = itertools.count(1)
#: (id of the innermost open span, query ordinal ``q`` it belongs to)
_CUR_SPAN: contextvars.ContextVar[Tuple[int, Optional[int]]] = \
    contextvars.ContextVar("srtpu_trace_span", default=(0, None))


class _SpanCtx:
    """Reusable span context manager (allocated only when tracing is ON)."""

    __slots__ = ("tracer", "name", "cat", "args", "q", "sid", "t0",
                 "token", "ann")

    def __init__(self, tracer: "Tracer", name: str, cat: str, args, q):
        self.tracer = tracer
        self.name = name
        self.cat = cat
        self.args = args
        self.q = q

    def __enter__(self):
        self.sid = next(_SPAN_IDS)
        if self.q is None:
            self.q = _CUR_SPAN.get()[1]
        self.ann = None
        if _Annotation.is_enabled():
            # the profiler sink: the same span on the device trace's clock
            meta = {} if self.q is None else {"q": self.q}
            if self.args and "exec" in self.args:
                meta["exec"] = self.args["exec"]
            self.ann = _Annotation(f"srtpu/{self.cat}/{self.name}", **meta)
            self.ann.__enter__()
        self.t0 = time.perf_counter_ns()
        self.token = _CUR_SPAN.set((self.sid, self.q))
        return self

    def __exit__(self, exc_type, exc, tb):
        t1 = time.perf_counter_ns()
        if self.ann is not None:
            self.ann.__exit__(exc_type, exc, tb)
        parent = 0
        try:
            _CUR_SPAN.reset(self.token)
            parent = _CUR_SPAN.get()[0]
        except Exception:   # token from another context: best effort
            pass
        tracer = self.tracer
        if tracer.recording:
            tracer._emit({"ph": "X", "name": self.name, "cat": self.cat,
                          "ts": self.t0, "dur": t1 - self.t0,
                          "pid": tracer.pid,
                          "tid": threading.get_ident(),
                          "id": self.sid, "parent": parent, "q": self.q,
                          "args": self.args})
        return False


class Tracer:
    """Bounded, thread-safe event recorder.

    Events are plain dicts in Chrome-trace shape with NANOSECOND
    ``ts``/``dur`` (the exporter converts to microseconds): ``ph`` is
    ``X`` (complete span), ``C`` (counter) or ``i`` (instant). With
    ``recording=False`` the tracer is annotate-only: :meth:`span` still
    enters the profiler's annotation, nothing reaches the buffer, and
    what cannot be an annotation (:meth:`complete`, :meth:`counter`,
    :meth:`instant`) is skipped."""

    def __init__(self, max_events: int = 65536,
                 proc_name: Optional[str] = None, recording: bool = True):
        self.recording = recording
        self.pid = os.getpid()
        self.proc_name = proc_name or f"pid-{self.pid}"
        #: perf_counter -> wall-clock offset, captured once: lets the
        #: driver place THIS process's monotonic timestamps onto the
        #: shared cross-process timeline
        self.epoch_ns = time.time_ns() - time.perf_counter_ns()
        self._lock = threading.Lock()
        self._buf: deque = deque(maxlen=max(16, int(max_events)))  # tpulint: guarded-by _lock
        self.dropped = 0             # tpulint: guarded-by _lock
        #: pid -> process name, for lanes ingested from other processes
        self.proc_names: Dict[int, str] = {self.pid: self.proc_name}  # tpulint: guarded-by _lock

    # ------------------------------------------------------------ record
    def now(self) -> int:
        return time.perf_counter_ns()

    def _emit(self, ev: dict) -> None:
        with self._lock:
            if len(self._buf) == self._buf.maxlen:
                self.dropped += 1
            self._buf.append(ev)

    def span(self, name: str, cat: str = "exec",
             args: Optional[dict] = None,
             q: Optional[int] = None) -> _SpanCtx:
        """Context manager recording one complete span around its body.
        ``q`` is given by the ``query`` span alone (the session's query
        ordinal); every span opened inside it inherits it."""
        return _SpanCtx(self, name, cat, args, q)

    def complete(self, name: str, t0_ns: int, t1_ns: Optional[int] = None,
                 cat: str = "exec", args: Optional[dict] = None) -> None:
        """Record a span that already happened: ``t0_ns`` from
        :meth:`now` before the work, end defaulting to now. Written
        after the fact, so it reaches the ring buffer only, never the
        profiler's trace."""
        if not self.recording:
            return
        if t1_ns is None:
            t1_ns = time.perf_counter_ns()
        parent, q = _CUR_SPAN.get()
        self._emit({"ph": "X", "name": name, "cat": cat, "ts": t0_ns,
                    "dur": t1_ns - t0_ns, "pid": self.pid,
                    "tid": threading.get_ident(),
                    "id": next(_SPAN_IDS), "parent": parent, "q": q,
                    "args": args})

    def counter(self, name: str, values: Dict[str, float],
                cat: str = "counter") -> None:
        if not self.recording:
            return
        self._emit({"ph": "C", "name": name, "cat": cat,
                    "ts": time.perf_counter_ns(), "pid": self.pid,
                    "tid": threading.get_ident(), "args": dict(values)})

    def instant(self, name: str, cat: str = "event",
                args: Optional[dict] = None) -> None:
        if not self.recording:
            return
        self._emit({"ph": "i", "s": "t", "name": name, "cat": cat,
                    "ts": time.perf_counter_ns(), "pid": self.pid,
                    "tid": threading.get_ident(), "args": args})

    # ------------------------------------------------------------- read
    def snapshot(self) -> List[dict]:
        """Copy of the buffered events, oldest first (buffer intact)."""
        with self._lock:
            return list(self._buf)

    def tail(self, n: int = 512) -> List[dict]:
        """Copy of up to the NEWEST ``n`` buffered events (oldest of
        those first) — the flight recorder's trace-ring section
        (ops/flight.py). Never drains: an anomaly dump must not eat the
        events the query's own trace artifact will export."""
        with self._lock:
            buf = list(self._buf)
        return buf[-max(0, int(n)):]

    def drain(self) -> List[dict]:
        """Remove and return every buffered event (drop count intact)."""
        with self._lock:
            out = list(self._buf)
            self._buf.clear()
            return out

    def export_events(self, drain: bool = True):
        """Atomic (events, dropped) read for exporters. Draining also
        RESETS the drop counter: each export/serialize accounts its own
        window's drops — re-reporting a cumulative count would make
        every later artifact (or the driver's ingest of per-task worker
        buffers) re-count earlier windows' drops."""
        with self._lock:
            events = list(self._buf)
            dropped = self.dropped
            if drain:
                self._buf.clear()
                self.dropped = 0
        return events, dropped

    # ----------------------------------------------- cross-process merge
    def serialize(self, drain: bool = True) -> bytes:
        """Buffer -> bytes for attaching to a task-completion RPC.
        The payload carries this process's wall-clock epoch so the
        receiver can align lanes, plus its lane name and this window's
        drop count (see export_events)."""
        events, dropped = self.export_events(drain=drain)
        return pickle.dumps({"pid": self.pid, "proc": self.proc_name,
                             "epoch_ns": self.epoch_ns,
                             "dropped": dropped,
                             "events": events})

    def ingest(self, payload: bytes) -> int:
        """Merge another process's serialized buffer into this one.
        Remote timestamps are shifted from the sender's monotonic clock
        onto THIS tracer's, via both wall-clock epochs — one coherent
        timeline, per-process pid/tid lanes preserved."""
        got = pickle.loads(payload)
        shift = got["epoch_ns"] - self.epoch_ns
        # the driver ingests worker payloads while its own query thread
        # still emits: lane-name/drop bookkeeping shares the buffer lock
        with self._lock:
            self.proc_names[got["pid"]] = got["proc"]
            self.dropped += got.get("dropped", 0)
        evs = got["events"]
        for ev in evs:
            ev["ts"] = ev["ts"] + shift
            self._emit(ev)
        return len(evs)


# ---------------------------------------------------------------------------
# installation
# ---------------------------------------------------------------------------

_INSTALL_LOCK = threading.Lock()


def active_tracer() -> Optional[Tracer]:
    # tpulint: disable=lock-discipline — lock-free by design: the
    # disabled-path contract is one unlocked reference read per site
    return TRACER


def install_tracer(tracer: Optional[Tracer]) -> Optional[Tracer]:
    """Install (or with ``None`` remove) the process-global tracer."""
    global TRACER
    with _INSTALL_LOCK:
        TRACER = tracer
    return tracer


def ensure_tracer_from_conf(conf) -> Optional[Tracer]:
    """Install a tracer iff ``spark.rapids.tpu.trace.enabled`` — the one
    conf lookup, paid per ExecContext construction, never per event."""
    global TRACER
    if not conf.get(TRACE_ENABLED):
        # tpulint: disable=lock-discipline — lock-free by design:
        # tracing-off fast path; installation itself locks below
        return TRACER
    with _INSTALL_LOCK:
        if TRACER is None:
            TRACER = Tracer(max_events=int(conf.get(TRACE_BUFFER_SPANS)),
                            proc_name="driver")
        return TRACER


def query_tracer(conf) -> Tuple[Optional[Tracer], bool]:
    """The tracer a query (or a ``sql()`` call) starts under, and whether
    it was installed HERE for this query alone: the installed one, else
    the conf's, else — while a ``jax.profiler`` session runs, whoever
    started it — a fresh annotate-only tracer, which the caller hands to
    :func:`release_query_tracer` at the query's end. With tracing off
    and no session this is one conf lookup and one ``is_enabled()``."""
    global TRACER
    tr = ensure_tracer_from_conf(conf)
    if tr is not None or not _Annotation.is_enabled():
        return tr, False
    with _INSTALL_LOCK:
        if TRACER is None:
            TRACER = Tracer(proc_name="driver", recording=False)
            return TRACER, True
        return TRACER, False


def release_query_tracer(tracer: Tracer) -> None:
    """Remove the annotate-only tracer :func:`query_tracer` installed,
    unless another one has taken its place since."""
    global TRACER
    with _INSTALL_LOCK:
        if TRACER is tracer:
            TRACER = None
