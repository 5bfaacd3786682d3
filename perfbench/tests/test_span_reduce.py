"""perfbench/span_reduce.py on hand-made intervals, on the recorded trace of
perfbench/selfcheck/, and the three part-B readers through a rehearsal.

The worked example (nanoseconds). The device's clock runs 1,000 ns BEHIND
the host's: host = device + 1000. ``perfbench.window`` is [0, 10000].

Device operations (device clock): [1000, 2000], [2000, 2500], [6000, 7000]:
busy 2,500 of 10,000, so ``device_idle_pct`` is 75. The idle gaps, by
trace_reduce's rule, are [0, 1000], [2500, 6000], [7000, 10000]; on the
host's clock (+1000) they are [1000, 2000], [3500, 7000], [8000, 11000].

Engine spans on the client's thread (host clock):

    srtpu/plan/plan.sql                      [ 500, 1500]
    srtpu/query/query                        [1600, 9000]
      srtpu/plan/plan.physical               [1700, 2200]
      srtpu/exec/TpuHashAggregateExec        [2300, 8500]
        srtpu/exec/InMemoryScanExec          [2400, 3800]
        srtpu/transfer/d2h.agg.transfer      [6500, 8200]

Gap [1000, 2000]: plan.sql until 1500 (plan 500), nothing open until 1600
(unattributed 100), the query's own time until 1700 (query_other 100),
plan.physical until 2000 (plan 300).
Gap [3500, 7000]: the scan until 3800 (exec 300), the aggregate's own time
until 6500 (exec 2700), the blocking get until 7000 (fetch 500).
Gap [8000, 11000]: the get until 8200 (fetch 200), the aggregate until 8500
(exec 300), the query's own time until 9000 (query_other 500), nothing open
until 11000 (unattributed 2000).

    plan 800 = 8%; exec 3300 = 33%; fetch 700 = 7%; query_other 600 = 6%;
    unattributed 2100 = 21%; together 7500 = 75% = device_idle_pct.

The offset's interval: module ``jit_a`` (run 1) runs [1000, 2500] on the
device and was enqueued at 1900 on the host, so offset >= 900; its
completion callback starts at 3600, so offset <= 1100. ``jit_b`` (run 2)
runs [6000, 7000], enqueued at 6950 (>= 950), completed at 8050 (<= 1050).
Interval [950, 1050], midpoint 1000: the offset the example was built with.
"""
import json
import os

import pytest

import run
import span_reduce

OPS = [(1000.0, 2000.0), (2000.0, 2500.0), (6000.0, 7000.0)]
MODULES = [("jit_a(111)", 1000.0, 2500.0, 1), ("jit_b(222)", 6000.0, 7000.0, 2)]
SPANS = [("srtpu/plan/plan.sql", 500.0, 1500.0),
         ("srtpu/query/query", 1600.0, 9000.0),
         ("srtpu/plan/plan.physical", 1700.0, 2200.0),
         ("srtpu/exec/TpuHashAggregateExec", 2300.0, 8500.0),
         ("srtpu/exec/InMemoryScanExec", 2400.0, 3800.0),
         ("srtpu/transfer/d2h.agg.transfer", 6500.0, 8200.0)]
LOADED = {"devices": {"/device:TPU:0": {"ops": OPS, "modules": MODULES}},
          "spans": SPANS, "window": (0.0, 10000.0),
          "enqueues": {1: 1900.0, 2: 6950.0},
          "completes": {1: 3600.0, 2: 8050.0}}


def test_offset_interval_from_causality():
    assert span_reduce.offset_bounds(
        MODULES, LOADED["enqueues"], LOADED["completes"]) == (950.0, 1050.0)
    # a side that nothing bounds stays open
    assert span_reduce.offset_bounds(MODULES, {}, {2: 8050.0}) == (None, 1050.0)


def test_gaps_follow_trace_reduce():
    assert span_reduce.idle_gaps(OPS, 0.0, 10000.0) == [
        (0.0, 1000.0), (2500.0, 6000.0), (7000.0, 10000.0)]


def test_idle_by_innermost_span():
    got = span_reduce.attribute(
        span_reduce.idle_gaps(OPS, 0.0, 10000.0), SPANS, 1000.0)
    assert got == {"srtpu/plan/plan.sql": 500.0, None: 2100.0,
                   "srtpu/query/query": 600.0,
                   "srtpu/plan/plan.physical": 300.0,
                   "srtpu/exec/InMemoryScanExec": 300.0,
                   "srtpu/exec/TpuHashAggregateExec": 3000.0,
                   "srtpu/transfer/d2h.agg.transfer": 700.0}


def test_worked_example_shares():
    got = span_reduce.reduce(LOADED)
    assert got["offset_ns"] == 1000.0
    assert got["offset_interval_ns"] == [950.0, 1050.0]
    assert got["idle_pct"] == pytest.approx(
        {"plan": 8.0, "exec": 33.0, "fetch": 7.0, "query_other": 6.0,
         "unattributed": 21.0})
    assert sum(got["idle_pct"].values()) == pytest.approx(75.0)
    assert got["device_by_module"] == [["jit_a", 1.5e-6], ["jit_b", 1.0e-6]]
    # the longest gap, [2500, 6000] on the device's clock, with its split
    length, at, split = got["longest_gaps"][0]
    assert (length, at) == (3.5e-6, "+0.000003 s")
    assert split == [["srtpu/exec/TpuHashAggregateExec", 2.7e-6],
                     ["srtpu/transfer/d2h.agg.transfer", 5e-7],
                     ["srtpu/exec/InMemoryScanExec", 3e-7]]


def test_classes():
    cls = span_reduce.span_class
    assert cls(None) == "unattributed"
    assert cls("srtpu/transfer/h2d.raw.dispatch") == "exec"
    assert cls("srtpu/transfer/d2h.dispatch") == "fetch"
    assert cls("srtpu/sem/semaphore.wait") == "query_other"


def test_a_trace_without_engine_spans_reduces_to_nothing():
    """The recorded TPU trace of selfcheck/ predates the engine's
    annotations: it loads, its runtime events bound the offset (the first
    device operation starts 0.93 ms before its perfbench.collect
    annotation), and there is nothing to attribute."""
    loaded = span_reduce.load(os.path.join(
        run.HERE, "selfcheck", "small_trace.xplane.pb"))
    assert loaded["spans"] == [] and span_reduce.reduce(loaded) is None
    dev = loaded["devices"]["/device:TPU:0"]
    assert len(dev["ops"]) == 12 and len(dev["modules"]) == 3
    lo, hi = span_reduce.offset_bounds(
        dev["modules"], loaded["enqueues"], loaded["completes"])
    assert 0.9e6 < lo < hi < 2.0e6, (lo, hi)


def test_rehearsal_reads_the_engine_span_metrics(capsys):
    assert run.main(["--workload", "tpch_sf10.q6_resident", "--seed", "7",
                     "--seconds", "1", "--trace", "1",
                     "--rehearsal-rows", "300000"]) == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["correct"] is True, line
    assert {"plan_span_ms", "exec_host_s", "d2h_ms"} \
        <= set(line["counts"]["metrics_read"]), line
