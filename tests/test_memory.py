"""OOM retry / spill / fault-injection suites.

Reference analog: WithRetrySuite.scala, HashAggregateRetrySuite.scala:121-222,
GpuSemaphoreSuite — the fault-injection hooks (force_retry_oom) mirror
RmmSpark.forceRetryOOM / forceSplitAndRetryOOM.
"""
import threading

import pandas as pd
import pytest

from harness import (OPERATOR_CONF, assert_tpu_and_cpu_equal,
                     tpu_session)
from data_gen import DoubleGen, IntGen, gen_df
from spark_rapids_tpu.api import functions as F
from spark_rapids_tpu.columnar import ColumnarBatch
from spark_rapids_tpu.mem import (DeviceSemaphore, MemoryManager,
                                  OutOfDeviceMemory, RetryOOM, SpillableBatch,
                                  SplitAndRetryOOM, with_retry,
                                  with_retry_no_split)


def _mm(budget=10**9):
    return MemoryManager(budget, budget, "/tmp/srtpu_spill_test")


def _batch(n=100):
    return ColumnarBatch.from_pandas(
        pd.DataFrame({"a": range(n), "b": [float(x) for x in range(n)]}))


class TestRetryFramework:
    def test_retry_succeeds_after_injected_oom(self):
        mm = _mm()
        mm.force_retry_oom(2)
        attempts = []

        def work():
            attempts.append(1)
            mm.reserve(10)
            mm.release(10)
            return "ok"

        assert with_retry_no_split(work, mm) == "ok"
        assert len(attempts) == 3  # two injected failures + success

    def test_split_and_retry_without_splitter_degrades_to_host(self):
        """r14 ladder: SplitAndRetryOOM in a no-split frame is no longer
        fatal — it escalates through the pressure spill to the host
        degradation rung and the attempt completes under an unbudgeted
        grant (recorded as a host fallback)."""
        from spark_rapids_tpu.mem.retry import RetryStats
        mm = _mm()
        # one raise per rung: first attempt + the post-pressure retry,
        # so the ladder must reach the degradation rung to succeed
        mm.force_split_and_retry_oom(2)
        stats = RetryStats()
        seen = []

        def work():
            mm.reserve(10)
            mm.release(10)
            seen.append(mm.in_pressure_grant())
            return "ok"

        assert with_retry_no_split(work, mm, stats) == "ok"
        assert stats.pressure_spills == 1
        assert stats.host_fallbacks == 1
        assert seen == [True]          # the attempt ran under the grant

    def test_split_and_retry_fatal_when_host_fallback_disabled(self):
        """spark.rapids.tpu.oom.hostFallback.enabled=false restores the
        pre-r14 contract: the ladder ends in OutOfDeviceMemory."""
        from spark_rapids_tpu.config import TpuConf
        from spark_rapids_tpu.exec.base import ExecContext
        mm = _mm()
        ctx = ExecContext(TpuConf(
            {"spark.rapids.tpu.oom.hostFallback.enabled": False}),
            memory=mm)
        mm.force_split_and_retry_oom(2)
        with pytest.raises(OutOfDeviceMemory):
            with_retry_no_split(lambda: mm.reserve(10), mm, ctx=ctx)
        mm.clear_injections()

    def test_with_retry_splits_input(self):
        mm = _mm()
        sb = SpillableBatch(_batch(100), mm)
        mm.force_split_and_retry_oom(1)
        seen = []

        def fn(item):
            mm.reserve(1)
            mm.release(1)
            b = item.get()
            seen.append(b.num_rows)
            return b.num_rows

        total = sum(with_retry([sb], fn, mm))
        assert total == 100
        assert len(seen) == 2  # split in half
        assert sorted(seen) == [50, 50]

    def test_split_batch_closes_pieces_keeps_input_on_failure(self):
        """If the SECOND piece's wrap blows up mid-split, the
        already-wrapped first piece must close (a half-built split must
        not pin pool budget) but the INPUT stays open — the r14 ladder
        still owns it and can escalate (pressure spill, host
        degradation) with the data intact. RetryOOM is absorbed at the
        allocation site now, so the failure here is a SplitAndRetryOOM
        (never absorbed: only the caller can split)."""
        from spark_rapids_tpu.mem.retry import split_batch_in_half
        mm = _mm()
        sb = SpillableBatch(_batch(100), mm)
        # skip piece 1's reserve, fail piece 2's
        mm.force_split_and_retry_oom(1, skip=1)
        with pytest.raises(SplitAndRetryOOM):
            split_batch_in_half(sb)
        mm.clear_injections()
        assert not sb._closed
        assert len(mm.audit_leaks()) == 1   # the still-open input only
        sb.close()
        assert mm.audit_leaks() == []

    def test_split_batch_uses_public_manager_accessor(self):
        from spark_rapids_tpu.mem.retry import split_batch_in_half
        mm = _mm()
        sb = SpillableBatch(_batch(10), mm)
        assert sb.memory_manager is mm
        pieces = split_batch_in_half(sb)
        assert sb._closed
        assert [p.memory_manager for p in pieces] == [mm, mm]
        for p in pieces:
            p.close()
        assert mm.audit_leaks() == []

    def test_injection_skip(self):
        mm = _mm()
        mm.force_retry_oom(1, skip=2)
        mm.reserve(1)
        mm.reserve(1)
        with pytest.raises(RetryOOM):
            mm.reserve(1)


class TestCheckpointRestore:
    """Satellite regression guard: an operator that MUTATES its input
    state then OOMs must produce byte-identical output after the retry
    (ref Retryable.scala CheckpointRestore)."""

    class _Acc:
        def __init__(self):
            self.rows = []

        def checkpoint(self):
            self._saved = list(self.rows)

        def restore(self):
            self.rows = list(self._saved)

    def test_mutating_operator_retries_byte_identical(self):
        mm = _mm()
        acc = self._Acc()

        def work():
            acc.rows.extend(range(100))   # side effect BEFORE the OOM
            mm.reserve(1)
            mm.release(1)
            return list(acc.rows)

        mm.force_retry_oom(1)
        out = with_retry_no_split(work, mm, retryable=acc)
        # restored between attempts: rows appear ONCE, not twice
        assert out == list(range(100))

    def test_without_checkpoint_the_mutation_doubles(self):
        """The failure mode the contract exists for: no retryable means
        the second attempt re-appends onto mutated state."""
        mm = _mm()
        acc = self._Acc()

        def work():
            acc.rows.extend(range(10))
            mm.reserve(1)
            mm.release(1)
            return list(acc.rows)

        mm.force_retry_oom(1)
        out = with_retry_no_split(work, mm)
        assert len(out) == 20             # doubled — retry was not clean


class TestSplitDepthLadder:
    def test_split_depth_bound_escalates_to_host_rung(self):
        """A piece that still cannot fit at oom.maxSplitDepth escalates:
        pressure spill, then the host degradation rung completes it
        under the grant — all 64 input rows processed, zero leaks."""
        from spark_rapids_tpu.mem.retry import RetryStats
        mm = _mm()
        sb = SpillableBatch(_batch(64), mm)
        stats = RetryStats()
        calls = []

        def fn(item):
            b = item.get()
            if not mm.in_pressure_grant() and b.num_rows > 1:
                raise SplitAndRetryOOM("still too big")
            calls.append(b.num_rows)
            item.close()
            return b.num_rows

        total = sum(with_retry([sb], fn, mm, stats=stats,
                               max_split_depth=2))
        assert total == 64
        # depth cap 2 means no piece smaller than 64/4 was ever split
        assert min(calls) >= 16
        assert stats.splits >= 2
        assert stats.pressure_spills == 1
        assert stats.host_fallbacks >= 1
        assert mm.audit_leaks() == []

    def test_unsplittable_single_row_degrades(self):
        mm = _mm()
        sb = SpillableBatch(_batch(1), mm)
        seen = []

        def fn(item):
            b = item.get()
            if not mm.in_pressure_grant():
                raise SplitAndRetryOOM("cannot ever fit")
            seen.append(b.num_rows)
            item.close()
            return b.num_rows

        assert list(with_retry([sb], fn, mm)) == [1]
        assert seen == [1]
        assert mm.audit_leaks() == []


class TestMemoryChaosSites:
    def test_mem_oom_site_fires_on_exact_nth_reserve(self):
        from spark_rapids_tpu.aux.fault import (ChaosController,
                                                install_chaos)
        mm = _mm()
        install_chaos(ChaosController("mem.oom=2"))
        try:
            mm.reserve(1)                 # hit 1: clean
            with pytest.raises(RetryOOM):
                mm.reserve(1)             # hit 2: injected
            mm.reserve(1)                 # hit 3: clean again
        finally:
            install_chaos(None)
        mm.release(2)

    def test_mem_reserve_delay_site_stalls(self):
        import time
        from spark_rapids_tpu.aux.fault import (ChaosController,
                                                install_chaos)
        mm = _mm()
        install_chaos(ChaosController("mem.reserve.delay=1",
                                      delay_ms=80))
        try:
            t0 = time.perf_counter()
            mm.reserve(1)
            assert time.perf_counter() - t0 >= 0.08
        finally:
            install_chaos(None)
        mm.release(1)

    def test_pressure_grant_suppresses_injection_and_chaos(self):
        from spark_rapids_tpu.aux.fault import (ChaosController,
                                                install_chaos)
        mm = _mm(budget=100)
        install_chaos(ChaosController("mem.oom=*"))
        try:
            with mm.pressure_host_grant():
                assert mm.in_pressure_grant()
                mm.reserve(1000)          # over budget AND chaos-armed
                assert mm.stats()["pressure_granted"] == 1000
                mm.release(1000)          # drains the grant pool, not
                assert mm.stats()["pressure_granted"] == 0  # device_used
        finally:
            install_chaos(None)

    def test_spill_all_sessions_spills_registered_instances(self):
        mm = _mm()
        sb = SpillableBatch(_batch(500), mm)
        assert sb.tier == "device"
        freed = mm.spill_everything()
        assert freed > 0 and sb.tier == "host"
        assert sb.get().num_rows == 500   # unspill round-trips
        sb.close()


class TestSpill:
    def test_spill_to_host_and_back(self):
        mm = _mm()
        sb = SpillableBatch(_batch(1000), mm)
        used = mm.device_used
        assert used > 0
        freed = sb.spill_to_host()
        assert freed > 0 and sb.tier == "host"
        assert mm.device_used == used - freed
        b = sb.get()
        assert sb.tier == "device"
        assert b.num_rows == 1000
        sb.close()
        assert mm.device_used == 0

    def test_spill_to_disk_roundtrip(self):
        mm = _mm()
        sb = SpillableBatch(_batch(500), mm)
        sb.spill_to_host()
        sb.spill_to_disk()
        assert sb.tier == "disk"
        b = sb.get()
        assert b.num_rows == 500
        assert b.to_arrow().column("a").to_pylist()[:3] == [0, 1, 2]
        sb.close()

    def test_budget_pressure_triggers_spill(self):
        b = _batch(1000)
        size = b.device_size_bytes()
        mm = _mm(budget=int(size * 1.5))
        sb = SpillableBatch(b, mm)
        # a second reservation must push the first one out
        mm.reserve(size)
        assert sb.tier == "host"
        mm.release(size)
        sb.close()

    def test_oversized_reserve_raises_split(self):
        mm = _mm(budget=1000)
        with pytest.raises(SplitAndRetryOOM):
            mm.reserve(2000)


class TestSemaphore:
    def test_limits_concurrency(self):
        sem = DeviceSemaphore(2)
        active = []
        peak = []
        lock = threading.Lock()

        def task():
            with sem.held():
                with lock:
                    active.append(1)
                    peak.append(len(active))
                import time
                time.sleep(0.01)
                with lock:
                    active.pop()

        threads = [threading.Thread(target=task) for _ in range(8)]
        [t.start() for t in threads]
        [t.join() for t in threads]
        assert max(peak) <= 2
        assert sem.acquires == 8

    def test_reentrant(self):
        sem = DeviceSemaphore(1)
        with sem.held():
            with sem.held():
                pass
        with sem.held():
            pass

    def test_wedge_watchdog_force_releases_dead_holder(self):
        """A holder thread that dies without releasing (a killed
        worker) must not wedge the semaphore: the watchdog detects the
        dead thread and reclaims its permit within wedgeTimeoutMs."""
        sem = DeviceSemaphore(1, timeout_s=10.0, wedge_timeout_ms=150)
        t = threading.Thread(target=sem.acquire, name="doomed")
        t.start()
        t.join()
        assert len(sem.diagnostics()["holders"]) == 1
        import time
        t0 = time.monotonic()
        with sem.held():                  # recovers via force-release
            pass
        assert time.monotonic() - t0 < 5.0
        assert sem.wedges == 1
        assert sem.diagnostics()["holders"] == []

    def test_wedge_diagnostics_in_timeout_error(self):
        """A LIVE stalled holder is never force-released; the waiter's
        TimeoutError carries the holder/waiter diagnostics dump."""
        import time
        sem = DeviceSemaphore(1, timeout_s=0.4, wedge_timeout_ms=100)
        evt = threading.Event()

        def hog():
            with sem.held():
                evt.wait(5.0)

        t = threading.Thread(target=hog, name="hog")
        t.start()
        time.sleep(0.05)
        try:
            with pytest.raises(TimeoutError, match="holders"):
                sem.acquire()
        finally:
            evt.set()
            t.join(timeout=5)
        assert sem.wedges == 0            # live holders are untouchable

    def test_sem_stall_chaos_site_stalls_holder(self):
        import time
        from spark_rapids_tpu.aux.fault import (ChaosController,
                                                install_chaos)
        ctl = ChaosController("sem.stall=1", delay_ms=80)
        install_chaos(ctl)
        sem = DeviceSemaphore(2)
        try:
            t0 = time.perf_counter()
            with sem.held():
                held_at = time.perf_counter() - t0
            assert held_at >= 0.08        # stalled WHILE holding
            assert ("sem.stall", 1) in ctl.fired()
        finally:
            install_chaos(None)

    def test_diagnostics_carry_memory_stats(self):
        mm = _mm()
        sem = DeviceSemaphore(2, memory=mm)
        d = sem.diagnostics()
        assert d["permits"] == 2
        assert "budget" in d["memory"]


class TestAggregateUnderOOM:
    """ref HashAggregateRetrySuite: inject OOM into the merge pass and assert
    the query still produces correct results."""

    def test_agg_survives_injected_retry_oom(self):
        s = tpu_session()
        df = s.create_dataframe(
            gen_df({"k": IntGen(lo=0, hi=10, nullable=False),
                    "v": IntGen(nullable=False)}, n=4096),
            num_partitions=4)
        q = df.group_by("k").agg(F.sum(F.col("v")).with_name("s"))
        mm = s.exec_context().memory
        mm.force_retry_oom(1)
        try:
            out = q.to_pandas()
        finally:
            mm.clear_injections()
        expect = (df.to_pandas().groupby("k", dropna=False)["v"]
                  .sum().reset_index())
        got = dict(zip(out["k"], out["s"]))
        want = dict(zip(expect["k"], expect["v"]))
        assert got == want


# ---------------------------------------------------------------------------
# a query's broadcast relations live as long as the query (ISSUE 29)
# ---------------------------------------------------------------------------

class TestBroadcastRelationsDieWithTheirQuery:
    def _join(self, s):
        fact = s.create_dataframe(
            gen_df({"k": IntGen(lo=0, hi=10, nullable=False),
                    "v": IntGen(nullable=False)}, n=4096))
        dim = s.create_dataframe(pd.DataFrame(
            {"k": range(10), "w": [float(i) for i in range(10)]}))
        joined = fact.join(dim, on="k")
        tree = joined._physical().tree_string()
        assert "BroadcastExchange" in tree, tree
        return joined

    def test_five_join_queries_hold_no_more_than_one(self):
        s = tpu_session(OPERATOR_CONF)
        mm = s.exec_context().memory
        q = self._join(s).group_by("k").agg(
            F.sum(F.col("w")).with_name("sw"))
        census = []
        for _ in range(5):
            assert q.collect_arrow().num_rows == 10
            census.append((len(mm.audit_leaks()),
                           mm.stats()["device_used"]))
            assert not s.exec_context()._cleanups
            assert not s.exec_context()._broadcast_cache
        assert census[-1] <= census[0], census
        assert census[0][0] == 0, census

    def test_a_query_that_raises_releases_its_relation(self):
        s = tpu_session(OPERATOR_CONF)
        mm = s.exec_context().memory
        held = []

        def boom(pdf):
            # by now the join has built and registered its relation
            held.append(len(mm.audit_leaks()))
            raise ValueError("boom")

        from spark_rapids_tpu.types import INT64
        q = self._join(s).map_in_pandas(boom, {"k": INT64})
        with pytest.raises(Exception, match="boom"):
            q.collect_arrow()
        assert held and held[0] >= 1, held
        assert mm.audit_leaks() == []
        assert not s.exec_context()._cleanups


# ---------------------------------------------------------------------------
# native disk spill store (native/spill_store.cpp — RapidsDiskStore analog)
# ---------------------------------------------------------------------------

def test_native_spill_store_roundtrip(tmp_path):
    from spark_rapids_tpu.mem.native_spill import get_store
    st = get_store(str(tmp_path / "spill"))
    assert st is not None, "g++ is available in this environment"
    ids = [st.write(bytes([i]) * (1000 + i)) for i in range(8)]
    for i, bid in enumerate(ids):
        data = st.read(bid)
        assert data == bytes([i]) * (1000 + i)
    stats = st.stats()
    assert stats["live_blocks"] == 8 and stats["slab_files"] == 1
    for bid in ids[:4]:
        st.free(bid)
    assert st.stats()["live_blocks"] == 4
    import pytest
    with pytest.raises(KeyError):
        st.read(ids[0])


def test_spillable_batch_disk_tier_uses_native_store(tmp_path):
    import numpy as np
    import pyarrow as pa
    from spark_rapids_tpu.mem.manager import MemoryManager
    from spark_rapids_tpu.mem.spillable import SpillableBatch
    from spark_rapids_tpu.columnar import ColumnarBatch
    mm = MemoryManager(1 << 30, 1 << 30, str(tmp_path / "sp"))
    t = pa.table({"a": pa.array(np.arange(5000)),
                  "s": pa.array([f"v{i}" for i in range(5000)])})
    sb = SpillableBatch(ColumnarBatch.from_arrow(t), mm)
    sb.spill_to_host()
    assert sb.spill_to_disk() > 0
    assert sb.tier == "disk" and sb._disk_block is not None
    got = sb.get().to_arrow()
    assert got.equals(t)
    sb.close()
    from spark_rapids_tpu.mem.native_spill import get_store
    assert get_store(str(tmp_path / "sp")).stats()["live_blocks"] == 0


def test_fetch_packed_roundtrip_all_dtypes():
    """Two-stream packed fetch must round-trip every dtype bit-exactly —
    including sub-4-byte floats, whose bit patterns must be carried, not
    value-cast (ADVICE r1: astype would truncate f16/bf16 fractions)."""
    import jax.numpy as jnp
    import numpy as np
    from spark_rapids_tpu.columnar.packing import fetch_packed
    rng = np.random.default_rng(7)
    arrays = [
        np.arange(10, dtype=np.int32),
        rng.standard_normal(7).astype(np.float32),
        np.array([True, False, True] * 20),
        np.arange(-5, 5, dtype=np.int64) * (1 << 40),
        rng.standard_normal(5).astype(np.float64),
        np.array([1.5, -2.25, 3.75, 1e-3], dtype=np.float16),
        np.array([0, 1, 255], dtype=np.uint8),
    ]
    dev = [jnp.asarray(a) for a in arrays]
    got = fetch_packed(dev)
    for orig, back in zip(arrays, got):
        assert back.dtype == orig.dtype, (back.dtype, orig.dtype)
        np.testing.assert_array_equal(np.asarray(back), orig)


@pytest.mark.parametrize("platform,stats,want", [
    ("tpu", {"bytes_limit": 1 << 34}, 1 << 34),
    ("tpu", None, RuntimeError),
    ("tpu", {"bytes_in_use": 0}, RuntimeError),
    ("cpu", None, 8 << 30),
])
def test_hbm_budget_is_read_not_guessed(monkeypatch, platform, stats, want):
    """An accelerator that does not report bytes_limit is an error (the
    old code swallowed every exception and assumed 8 GiB); only the CPU
    backend, which never reports one, gets the assumed size."""
    from spark_rapids_tpu.mem import manager

    class FakeDevice:
        def __init__(self):
            self.platform = platform

        def memory_stats(self):
            return stats
    monkeypatch.setattr(manager, "_pinned_or_first_device", FakeDevice)
    if want is RuntimeError:
        with pytest.raises(RuntimeError, match="bytes_limit"):
            manager._device_hbm_bytes()
    else:
        assert manager._device_hbm_bytes() == want


def test_native_build_failure_is_logged_not_hidden(monkeypatch, caplog):
    """A failed g++ build returns None (the Python twin takes over) and
    says so, with the compiler's words."""
    import logging
    import subprocess
    from spark_rapids_tpu.mem import native

    def failing_run(cmd, **kw):
        raise subprocess.CalledProcessError(1, cmd, stderr=b"no such g++")
    monkeypatch.setattr(native.os.path, "exists", lambda p: False)
    monkeypatch.setattr(native.subprocess, "run", failing_run)
    with caplog.at_level(logging.WARNING, logger=native.log.name):
        assert native.build_shared_lib("oom_state") is None
    assert "no such g++" in caplog.text and "Python twin" in caplog.text


def test_memory_manager_names_its_state_machine():
    mm = MemoryManager(1 << 20, 1 << 20, "/tmp/srtpu_spill_t")
    assert mm.state_machine == "python"      # native is opt-in per ctor
    from spark_rapids_tpu.mem.native import load
    try:
        native_mm = MemoryManager(1 << 20, 1 << 20, "/tmp/srtpu_spill_t",
                                  use_native=True)
        assert native_mm.state_machine == ("native" if load() is not None
                                           else "python")
    finally:
        # the native machine is ONE a process: hand it back to the
        # session manager at that one's budget, or every query a later
        # test of this worker runs meets a 1 MiB device
        for mm in MemoryManager._instances.values():
            if mm._native is not None:
                mm._native.lib.oom_init(mm.budget)
