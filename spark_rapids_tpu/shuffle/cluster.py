"""Multi-process execution: N worker processes + TCP shuffle, with
driver-side fault tolerance.

The cross-process runtime the reference gets from Spark (driver/executor
split + shuffle service) rebuilt TPU-engine-style (ref
RapidsShuffleInternalManagerBase.scala:238 threaded writer, :614 threaded
reader, :1228 manager; heartbeat discovery Plugin.scala:428-439):

  driver                      worker processes (JAX_PLATFORMS=cpu)
  ------                      --------------------------------------
  ShuffleHeartbeatManager <--- ShuffleHeartbeatEndpoint heartbeats
  LocalCluster.execute(df)     each runs a BlockServer (transport.py)
    split plan at the agg      map task: run fragment, hash-partition
    (and, r3, at a shuffled    output, PUT blocks to partition owners
    JOIN below it)             join task: fetch co-partitions of both
    ship typed tasks --------> sides, local join + partial agg, PUT
    collect + finish plan <---- serialized Arrow results

Aggregates are decomposed into update/merge pairs exactly like the
distinct rewrite (plan/rewrites.py): Sum/Min/Max merge with themselves,
Count(+Star) merges by summing, Average splits into sum+count with a
driver-side divide — so distributing cannot change results.

Joins (r3): when BOTH sides of an equi-join are large, the driver
hash-shuffles both sides by their join keys (one map task per worker per
side), each worker joins its co-partitioned slice locally and runs the
partial aggregation, then the existing agg shuffle/merge finishes — the
host-staged analog of GpuShuffledHashJoinExec over
RapidsShuffleInternalManagerBase exchanges (:614). Small sides keep the
replicated (broadcast) path.

Fault tolerance (r6 — the role Spark's scheduler plays for the
reference: FetchFailedException triggers stage re-execution and dead
executors are replaced, Plugin.scala:661-686):

* the driver records the LINEAGE of every shuffle it creates — the
  serialized map-fragment plans plus which earlier shuffles each task
  reads — so lost map outputs are recomputed, not fatal;
* a task whose worker dies (connection refused/reset), stops
  heartbeating (ShuffleHeartbeatManager eviction), or exceeds
  ``spark.rapids.tpu.task.timeout`` is re-dispatched to a live worker,
  bounded by ``spark.rapids.tpu.task.maxFailures`` attempts per task;
* partitions owned by a dead worker are remapped to survivors and their
  blocks regenerated by re-running the recorded map plans — re-puts are
  idempotent because every block carries a deterministic block id the
  receiving store dedupes on (transport.py), which also makes the
  re-executed shuffle concatenate in the SAME order, so recovery cannot
  change results;
* reducers verify a block-count manifest (and per-block CRC32C) before
  merging, so a silently lost or corrupted block surfaces as
  ShuffleFetchFailed and triggers targeted regeneration instead of a
  silently wrong answer.

All control traffic is the typed-task protocol in transport.py, signed
with a per-cluster HMAC token — workers execute only registered task
entry points, never shipped code objects.

This is deliberately the MULTITHREADED-mode analog (host-staged blocks
over TCP). The single-process device-resident path (ShuffleCatalog) and
the SPMD collective path (parallel/planner.py) remain the fast paths; this
runtime is the scale-out seam for multi-host deployments.
"""
from __future__ import annotations

import copy
import os
import pickle
import secrets
import threading
import time
from typing import Dict, List, Optional, Set, Tuple

from .. import aqe as aqe_mod
from ..config import register
from ..exec.query import run_plan
from ..trace import core as trace_core
from .heartbeat import ShuffleHeartbeatEndpoint, ShuffleHeartbeatManager
from .transport import BlockClient, BlockServer, ShuffleFetchFailed

__all__ = ["LocalCluster", "ShuffleFetchFailed"]


FETCH_MAX_RETRIES = register(
    "spark.rapids.tpu.shuffle.fetch.maxRetries", 3,
    "Transient shuffle block transfer failures (connection resets, "
    "timeouts, checksum rejects) are retried against the owner this many "
    "times — with exponential backoff + jitter — before escalating to "
    "ShuffleFetchFailed and lineage recovery (ref "
    "spark.shuffle.io.maxRetries / RapidsShuffleIterator fetch errors).")

FETCH_RETRY_BACKOFF_MS = register(
    "spark.rapids.tpu.shuffle.fetch.retryBackoffMs", 50,
    "Base backoff between shuffle transfer retries; attempt k sleeps "
    "base * 2^k * jitter (ref spark.shuffle.io.retryWait).")

TASK_RPC_TIMEOUT = register(
    "spark.rapids.tpu.task.timeout", 600.0,
    "Max seconds the driver waits on one worker task RPC before "
    "declaring the worker lost and re-dispatching the task to a live "
    "worker (ref spark.network.timeout driving executor loss).")

TASK_MAX_FAILURES = register(
    "spark.rapids.tpu.task.maxFailures", 4,
    "A task may fail (worker death, timeout, lost shuffle blocks) this "
    "many times before the query is failed (ref spark.task.maxFailures).")


class _RemoteManager:
    """Worker-side proxy giving ShuffleHeartbeatEndpoint the manager
    interface over the driver's control socket."""

    def __init__(self, driver_addr, token: Optional[bytes]):
        self._client = BlockClient(driver_addr, token=token)

    def register(self, executor_id: str, address: dict,
                 metrics: Optional[dict] = None):
        return self._client.task("register", executor_id=executor_id,
                                 address=address, metrics=metrics)


# ---------------------------------------------------------------------------
# worker process
# ---------------------------------------------------------------------------

_WORKER: Dict[str, object] = {}

#: transport settings a worker uses for its peer clients; overridden by
#: the driver-provided net dict at spawn (standalone multi-host workers
#: get the registered defaults — the same knobs, never drifting copies)
_DEFAULT_NET = {"timeout": float(TASK_RPC_TIMEOUT.default),
                "max_retries": int(FETCH_MAX_RETRIES.default),
                "backoff_ms": float(FETCH_RETRY_BACKOFF_MS.default),
                "metrics_interval_ms": 0}


def _worker_main(worker_id: int, driver_addr, ready_q, token: bytes,
                 bind_host: str = "127.0.0.1",
                 net: Optional[dict] = None):
    # CPU backend only: worker processes must never grab the TPU the
    # driver session owns (a chip belongs to ONE process — the reference's
    # one-GPU-per-executor assignment, Plugin.scala:536). A spawned worker
    # has already imported jax by the time it gets here, so the platform
    # is pinned through the config, before any backend initializes; the
    # env var covers whatever the worker itself starts.
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ.setdefault("XLA_FLAGS",
                          "--xla_force_host_platform_device_count=1")
    import jax
    jax.config.update("jax_platforms", "cpu")
    if os.environ.get("SRTPU_CLUSTER_DEBUG"):
        import faulthandler
        import sys
        faulthandler.dump_traceback_later(30, repeat=True, file=sys.stderr)
    server = BlockServer(host=bind_host, token=token,
                         tasks={n: _trace_wrap(n, f)
                                for n, f in _WORKER_TASKS.items()})
    _WORKER["server"] = server
    _WORKER["id"] = f"worker-{worker_id}"
    _WORKER["peers"] = {}
    _WORKER["token"] = token
    _WORKER["net"] = dict(_DEFAULT_NET, **(net or {}))
    # conf-armed continuous telemetry: install this worker's registry at
    # spawn (metric RPC markers would install it lazily anyway, but a
    # spawn-armed worker reports via heartbeat even before any task)
    interval_ms = int(_WORKER["net"].get("metrics_interval_ms") or 0)
    if interval_ms:
        _ensure_worker_metrics(interval_ms)

    def on_new_peer(p):
        n = _WORKER["net"]
        _WORKER["peers"][p["id"]] = BlockClient(
            (p["addr"]["host"], p["addr"]["port"]), token=token,
            timeout=n["timeout"], max_retries=n["max_retries"],
            backoff_ms=n["backoff_ms"])

    ep = ShuffleHeartbeatEndpoint(
        _RemoteManager(tuple(driver_addr), token), _WORKER["id"],
        {"host": server.address[0], "port": server.address[1]},
        on_new_peer=on_new_peer,
        metrics_provider=_worker_metrics_snapshot)
    _WORKER["endpoint"] = ep
    ep.heartbeat()
    if ready_q is not None:           # standalone (multi-host) workers
        ready_q.put((worker_id, server.address))
    import threading
    stop = threading.Event()
    _WORKER["stop"] = stop
    while not stop.is_set():           # heartbeat loop; tasks arrive via
        time.sleep(1.0)                # the BlockServer "task" op
        try:
            ep.heartbeat()
        except Exception:
            return                     # driver gone: exit


def _worker_stop():
    _WORKER["stop"].set()              # type: ignore
    return True


def _worker_heartbeat():
    _WORKER["endpoint"].heartbeat()    # type: ignore
    return sorted(_WORKER["peers"])    # type: ignore


def _worker_chaos(spec: str = "", seed: int = 0, delay_ms: int = 100):
    """Arm (or with an empty spec, disarm) fault injection in this
    worker process — the distributed analog of force_retry_oom."""
    from ..aux.fault import ChaosController, install_chaos
    install_chaos(ChaosController(spec, seed=seed, delay_ms=delay_ms)
                  if str(spec).strip() else None)
    return True


def _worker_chaos_stats():
    """Fired-injection log of this worker's controller (test assertions
    that chaos actually hit the sites it was armed for)."""
    from ..aux.fault import active_chaos
    ctl = active_chaos()
    return None if ctl is None else ctl.fired()


def _peer_client(owner_id: str) -> Optional[BlockClient]:
    if owner_id == _WORKER["id"]:
        return None                    # local put goes straight to store
    peers: Dict[str, BlockClient] = _WORKER["peers"]  # type: ignore
    if owner_id not in peers:
        try:
            _WORKER["endpoint"].heartbeat()  # type: ignore
        except Exception:
            pass
    if owner_id not in peers:
        raise ShuffleFetchFailed(
            f"peer {owner_id} is not registered/reachable",
            peer=owner_id)
    return peers[owner_id]


def _hash_partition(table, exprs, n_parts: int, seed: int = 0):
    """Deterministic host hash partitioning of an Arrow table by the
    grouping expressions (same mixing as CpuShuffleExchangeExec so every
    process routes identically). A non-zero ``seed`` salts the initial
    mixing state — the AQE skew-split rehash: all rows of one key still
    land together (correctness for joins/aggs/windows), but the
    key->partition assignment decorrelates from the unsalted shuffle
    that produced the skew."""
    import numpy as np
    import pyarrow as pa
    from ..columnar import ColumnarBatch
    if not exprs or n_parts == 1:
        return {0: table}
    batch = ColumnarBatch.from_arrow_host(table)
    init = (42 ^ (int(seed) * 0x9E3779B97F4A7C15)) % (1 << 64) \
        if seed else 42
    h = np.full(table.num_rows, init, dtype=np.uint64)
    for e in exprs:
        from ..exprs.arithmetic import arrow_to_masked_numpy
        arr = e.eval_host(batch)
        if isinstance(arr, pa.ChunkedArray):
            arr = arr.combine_chunks()
        v, ok = arrow_to_masked_numpy(arr)
        v = np.asarray(v)
        if v.dtype == object:
            # Python's str hash is per-process randomized; routing must be
            # identical in EVERY worker (crc32 is stable everywhere)
            import zlib
            hv = np.asarray([zlib.crc32(str(x).encode()) for x in v],
                            dtype=np.uint64)
        elif np.issubdtype(v.dtype, np.floating):
            # normalize before hashing (advisor r2): -0.0 == 0.0 must
            # route together, and all NaN payloads are one group — raw
            # bit patterns would split them across reduce partitions
            f = v.astype(np.float64) + 0.0          # -0.0 -> +0.0
            f = np.where(np.isnan(f), np.nan, f)    # canonical NaN
            hv = f.view(np.uint64)
        else:
            hv = v.astype(np.int64).view(np.uint64)
        h = h * np.uint64(31) + np.where(ok, hv, np.uint64(7))
    pid = (h % np.uint64(n_parts)).astype(np.int64)
    out = {}
    for p in range(n_parts):
        sub = table.filter(pa.array(pid == p))
        if sub.num_rows:
            out[p] = sub
    return out


def _range_partition(table, key_name: str, ascending: bool,
                     nulls_first: bool, boundaries, n_parts: int):
    """Range partitioning by the FIRST sort key (ref GpuRangePartitioner):
    boundaries arrive ASC-sorted; equal key values always route to one
    partition, so a local sort per partition + ordered concatenation is a
    global sort (ties broken by the remaining keys locally, which all
    live in the same partition). Nulls route to the first/last partition
    per the null ordering."""
    import numpy as np
    import pyarrow as pa
    from ..columnar import ColumnarBatch
    from ..exprs.arithmetic import arrow_to_masked_numpy
    from ..exprs.base import ColumnRef
    if n_parts == 1 or not len(boundaries):
        return {0: table}
    batch = ColumnarBatch.from_arrow_host(table)
    arr = ColumnRef(key_name).eval_host(batch)
    if isinstance(arr, pa.ChunkedArray):
        arr = arr.combine_chunks()
    v, ok = arrow_to_masked_numpy(arr)
    v = np.asarray(v)
    b = np.asarray(boundaries)
    if ascending:
        pid = np.searchsorted(b, v, side="right").astype(np.int64)
    else:
        pid = (len(b) - np.searchsorted(b, v, side="left")).astype(np.int64)
    pid = np.clip(pid, 0, n_parts - 1)
    pid = np.where(ok, pid, 0 if nulls_first else n_parts - 1)
    out = {}
    for p in range(n_parts):
        sub = table.filter(pa.array(pid == p))
        if sub.num_rows:
            out[p] = sub
    return out


def _run_range_map_task(shuffle_id: int, plan_bytes: bytes,
                        key_bytes: bytes, boundaries_bytes: bytes,
                        owners: List[str], map_id: Optional[str] = None):
    """Evaluate the map fragment and RANGE-partition its output by the
    first sort key (the exchange below a distributed global sort; ref
    GpuShuffleExchangeExecBase with GpuRangePartitioner)."""
    from ..api.dataframe import TpuSession
    from ..plan.overrides import plan_query
    plan = pickle.loads(plan_bytes)
    key_name, ascending, nulls_first = pickle.loads(key_bytes)
    boundaries = pickle.loads(boundaries_bytes)
    session = TpuSession()
    physical = plan_query(plan, session.conf)
    table = run_plan(session, physical)
    parts = _range_partition(table, key_name, ascending, nulls_first,
                             boundaries, len(owners))
    return _put_partitions(shuffle_id, parts, owners, map_id=map_id)


def _put_partitions(shuffle_id: int, parts, owners: List[str],
                    map_id: Optional[str] = None):
    """Route partition tables to their owners' stores. Each block carries
    the map task's deterministic block id, so a re-executed task (or a
    retried put) DEDUPES instead of double-storing, and a dead owner
    surfaces as ShuffleFetchFailed naming the peer — the driver's signal
    to remap ownership and regenerate from lineage."""
    from ..columnar.serializer import serialize_table
    server: BlockServer = _WORKER["server"]  # type: ignore
    out = {}
    for p, sub in parts.items():
        data = serialize_table(sub, "lz4")
        out[p] = (sub.num_rows, len(data))
        owner = owners[p]
        try:
            client = _peer_client(owner)
            if client is None:
                # local puts skip the transport client, so its span
                # doesn't fire — record one here or the profiler's
                # partition-size histogram would miss every partition
                # the mapper itself owns
                tr = trace_core.TRACER
                t0 = tr.now() if tr is not None else 0
                server._put(shuffle_id, p, data, bid=map_id)
                if tr is not None:
                    tr.complete("shuffle.put", t0, cat="shuffle",
                                args={"shuffle": shuffle_id, "part": p,
                                      "bytes": len(data), "retries": 0,
                                      "bid": map_id, "local": True})
            else:
                client.put(shuffle_id, p, data, bid=map_id)
        except (ConnectionError, OSError) as e:
            raise ShuffleFetchFailed(
                f"put shuffle={shuffle_id} part={p} to {owner} "
                f"failed: {e}", peer=owner, shuffle=shuffle_id,
                part=p) from e
    # per-partition (rows, serialized bytes): the driver folds these
    # into the ShuffleStats snapshot AQE re-plans from (aqe/planner.py)
    return out


def _fetch_concat(shuffle_id: int, parts: List[int],
                  expected: Optional[Dict[int, int]] = None,
                  remote: Optional[Dict[int, str]] = None):
    """Fetch owned partitions from the local store (writers already
    routed them here), CRC-verifying every block. ``expected`` is the
    driver's block-count manifest: fewer stored blocks than the manifest
    says means a writer's output was lost with a dead owner — raise
    ShuffleFetchFailed so the driver regenerates from lineage instead of
    silently reducing partial data. ``remote`` maps partitions an AQE
    coalesced unit pulls from ANOTHER worker's store to that owner: the
    transport's fetch RPC CRC-verifies and returns blocks in bid order,
    so remote reads concatenate exactly like local ones."""
    import pyarrow as pa
    from ..columnar.serializer import deserialize_table
    from .checksum import ChecksumError
    server: BlockServer = _WORKER["server"]  # type: ignore
    tables = []
    for p in parts:
        owner = remote.get(p) if remote else None
        client = _peer_client(owner) if owner is not None else None
        try:
            if client is not None:
                blocks = client.fetch(shuffle_id, p)
            else:
                blocks = server._fetch(shuffle_id, p, verify=True)
        except ChecksumError as e:
            raise ShuffleFetchFailed(
                f"stored block corrupt: {e}", shuffle=shuffle_id,
                part=p) from e
        except (ConnectionError, OSError) as e:
            raise ShuffleFetchFailed(
                f"fetch shuffle={shuffle_id} part={p} from {owner} "
                f"failed: {e}", peer=owner, shuffle=shuffle_id,
                part=p) from e
        if expected is not None and len(blocks) < expected.get(p, 0):
            raise ShuffleFetchFailed(
                f"shuffle={shuffle_id} part={p} has {len(blocks)} "
                f"block(s), manifest expects {expected.get(p, 0)} — "
                f"map outputs lost", shuffle=shuffle_id, part=p)
        for blk in blocks:
            tables.append(deserialize_table(blk))
    return pa.concat_tables(tables) if tables else None


def _scan_of(table):
    from ..plan import logical as L
    from ..types import Schema, from_arrow, StructField
    schema = Schema([StructField(f.name, from_arrow(f.type), True)
                     for f in table.schema])
    return L.LogicalScan([table], schema)


def _run_map_task(shuffle_id: int, plan_bytes: bytes, group_bytes: bytes,
                  owners: List[str], map_id: Optional[str] = None):
    """Execute the map fragment, hash-partition its output, PUT blocks to
    partition owners (ref RapidsShuffleThreadedWriterBase:238)."""
    from ..api.dataframe import TpuSession
    plan = pickle.loads(plan_bytes)
    groupings = pickle.loads(group_bytes)
    session = TpuSession()
    from ..plan.overrides import plan_query
    physical = plan_query(plan, session.conf)
    table = run_plan(session, physical)
    parts = _hash_partition(table, groupings, len(owners))
    return _put_partitions(shuffle_id, parts, owners, map_id=map_id)


def _run_reduce_task(shuffle_id: int, parts: List[int], plan_bytes: bytes,
                     expected: Optional[Dict[int, int]] = None,
                     remote: Optional[Dict[int, str]] = None):
    """Merge-aggregate the owned partitions — with AQE coalescing, one
    task may read a RUN of partitions, pulling non-local members from
    their owners over the transport (ref
    RapidsShuffleThreadedReaderBase:614)."""
    from ..api.dataframe import TpuSession
    from ..columnar.serializer import serialize_table
    from ..plan.overrides import plan_query
    reduce_plan = pickle.loads(plan_bytes)
    t = _fetch_concat(shuffle_id, parts, expected=expected, remote=remote)
    if t is None:
        return None
    reduce_plan = copy.copy(reduce_plan)
    reduce_plan.children = [_scan_of(t)]
    session = TpuSession()
    physical = plan_query(reduce_plan, session.conf)
    out = run_plan(session, physical)
    return serialize_table(out, "lz4")


def _run_join_side_task(shuffle_id: int, plan_bytes: bytes,
                        key_bytes: bytes, owners: List[str],
                        map_id: Optional[str] = None):
    """Evaluate one side of a shuffled join and hash-partition its rows
    by the JOIN keys (the exchange below GpuShuffledHashJoinExec)."""
    from ..api.dataframe import TpuSession
    from ..plan.overrides import plan_query
    plan = pickle.loads(plan_bytes)
    keys = pickle.loads(key_bytes)
    session = TpuSession()
    physical = plan_query(plan, session.conf)
    table = run_plan(session, physical)
    parts = _hash_partition(table, keys, len(owners))
    return _put_partitions(shuffle_id, parts, owners, map_id=map_id)


def _run_join_local_task(shuffle_l: int, shuffle_r: int, parts: List[int],
                         template_bytes: bytes, group_bytes: bytes,
                         out_shuffle: int, owners: List[str],
                         schemas_bytes: bytes,
                         map_id: Optional[str] = None,
                         expected_l: Optional[Dict[int, int]] = None,
                         expected_r: Optional[Dict[int, int]] = None):
    """Fetch co-partitioned slices of both join sides, run the local
    join + upper fragment + PARTIAL aggregation, hash-partition the
    partials by grouping keys into the next shuffle."""
    from ..api.dataframe import TpuSession
    from ..plan import logical as L
    from ..plan.overrides import plan_query
    template = pickle.loads(template_bytes)
    groupings = pickle.loads(group_bytes)
    lschema, rschema = pickle.loads(schemas_bytes)
    lt = _fetch_concat(shuffle_l, parts, expected=expected_l)
    rt = _fetch_concat(shuffle_r, parts, expected=expected_r)
    if lt is None and rt is None:
        return {}
    lt = lt if lt is not None else _empty_like(lschema)
    rt = rt if rt is not None else _empty_like(rschema)
    join = _find_join(template)
    join.children = [L.LogicalScan([lt], lschema),
                     L.LogicalScan([rt], rschema)]
    session = TpuSession()
    physical = plan_query(template, session.conf)
    table = run_plan(session, physical)
    parts_out = _hash_partition(table, groupings, len(owners))
    return _put_partitions(out_shuffle, parts_out, owners, map_id=map_id)


def _run_split_task(src_shuffle: int, src_part: int, out_shuffle: int,
                    key_bytes: bytes, n_sub: int, seed: int,
                    owners: List[str], map_id: Optional[str] = None,
                    expected: Optional[Dict[int, int]] = None):
    """AQE skew split (ISSUE 19): fetch ONE oversized partition from
    the local store and salted-rehash it by the same keys into
    ``n_sub`` sub-partitions of ``out_shuffle`` (ref Spark AQE
    OptimizeSkewedJoin's skewed-partition sub-reader). Deterministic —
    the salt derives from the source partition, so lineage re-execution
    reproduces identical sub-blocks and the chaos battery's
    byte-identity contract holds with AQE on. For shuffled joins BOTH
    sides split with the SAME salt, keeping sub-partitions
    co-partitioned by join key."""
    keys = pickle.loads(key_bytes)
    t = _fetch_concat(src_shuffle, [src_part], expected=expected)
    if t is None:
        return {}
    parts = _hash_partition(t, keys, n_sub, seed=seed)
    return _put_partitions(out_shuffle, parts, owners, map_id=map_id)


def _ensure_worker_metrics(interval_ms: int = 0):
    """Install (idempotently) this worker process's metric registry —
    the per-worker lane of the distributed collection — and start its
    sampler when an interval is armed."""
    from ..metrics import registry as metrics_registry
    reg = metrics_registry.REGISTRY
    if reg is None:
        reg = metrics_registry.install_metrics(
            metrics_registry.MetricRegistry())
    if interval_ms and interval_ms > 0:
        from ..metrics.sampler import start_sampler
        start_sampler(reg, int(interval_ms))
    return reg


def _worker_metrics_snapshot() -> Optional[dict]:
    """This worker's registry snapshot (after one synchronous sample
    pass, so gauges are current), or None when metrics are off —
    attached to heartbeats and task-completion replies."""
    from ..metrics import registry as metrics_registry
    reg = metrics_registry.REGISTRY
    if reg is None:
        return None
    try:
        from ..metrics.sampler import sample_now
        sample_now(reg)
    except Exception:     # telemetry must never fail the carrier RPC
        pass
    return reg.snapshot()


def _trace_wrap(name: str, fn):
    """Worker-side telemetry shim around a task entry point: when the
    driver marks the RPC with ``__trace__`` (it does iff
    spark.rapids.tpu.trace.enabled), the worker records the task span,
    drains its process tracer — which the instrumented exec/mem/
    transfer/transport layers filled while the task ran — and attaches
    the serialized buffer to the task-completion reply. A
    ``__metrics__`` marker (set iff spark.rapids.tpu.metrics.enabled)
    likewise attaches this worker's registry snapshot. The driver
    ingests both into its merged views. Unmarked calls pass through
    with a single branch."""
    def run(__trace__=None, __metrics__=None, **kw):
        if not __trace__ and not __metrics__:
            return fn(**kw)
        tr = None
        if __trace__:
            tr = trace_core.TRACER
            if tr is None:
                spans = (int(__trace__.get("spans", 65536))
                         if isinstance(__trace__, dict) else 65536)
                tr = trace_core.install_tracer(trace_core.Tracer(
                    max_events=spans,
                    proc_name=str(_WORKER.get("id")
                                  or f"pid-{os.getpid()}")))
        if __metrics__:
            interval = (int(__metrics__.get("intervalMs", 0))
                        if isinstance(__metrics__, dict) else 0)
            _ensure_worker_metrics(interval)
        t0 = tr.now() if tr is not None else 0
        t0_wall = time.perf_counter() if __metrics__ else 0.0
        res = fn(**kw)
        out = {"result": res}
        if tr is not None:
            tr.complete(f"task:{name}", t0, cat="task",
                        args={"worker": _WORKER.get("id")})
            out["__trace__"] = tr.serialize()
        if __metrics__:
            # per-task wall into this worker's quantile sketch (ISSUE
            # 20): the snapshot below carries the sketch to the driver,
            # where merge_snapshots folds it into fleet-wide quantiles
            from ..metrics import registry as _mr
            if _mr.REGISTRY is not None:
                _mr.REGISTRY.summary(
                    "srtpu_worker_task_seconds", task=name).observe(
                        time.perf_counter() - t0_wall)
            out["__metrics__"] = _worker_metrics_snapshot()
        return out
    return run


#: the closed task table workers expose over the transport — the typed
#: protocol's entire executable surface (ref RapidsShuffleTransport's
#: message enum: adding a capability means adding a NAME here, not
#: shipping code)
_WORKER_TASKS = {
    "map_agg": _run_map_task,
    "map_range": _run_range_map_task,
    # fetch owned partitions, apply an arbitrary unary plan over them,
    # return Arrow: the merge-agg reducer, the per-range local sorter,
    # and the per-hash-partition window runner are all this one task
    "reduce_agg": _run_reduce_task,
    "join_side": _run_join_side_task,
    "join_local": _run_join_local_task,
    # AQE skew split: salted re-partition of one oversized shuffle
    # partition into sub-partitions (aqe/planner.py decides when)
    "split_part": _run_split_task,
    "heartbeat": _worker_heartbeat,
    "chaos": _worker_chaos,
    "chaos_stats": _worker_chaos_stats,
    "stop": _worker_stop,
}


# ---------------------------------------------------------------------------
# plan decomposition (map partials / reduce merge / driver finish)
# ---------------------------------------------------------------------------

def _decompose_aggs(groupings, aggs, child_schema):
    """-> (map_aggs, reduce_aggs, final_projections) or None."""
    from ..exprs import aggregates as AG
    from ..exprs.arithmetic import Divide
    from ..exprs.base import Alias, ColumnRef, Literal
    from ..exprs.cast import Cast
    from ..exprs.conditional import Coalesce
    from ..types import FLOAT64, INT64
    map_aggs, reduce_aggs, projections = [], [], []
    for g in groupings:
        projections.append(ColumnRef(g.name_hint))
    for i, a in enumerate(aggs):
        if getattr(a, "distinct", False):
            return None
        out = a.name_hint
        t = f"__mp_t{i}"
        if isinstance(a, AG.Average):
            ps, pc = f"__mp_p{i}_s", f"__mp_p{i}_c"
            map_aggs.append(AG.Sum(Cast(a.child, FLOAT64)).with_name(ps))
            map_aggs.append(AG.Count(a.child).with_name(pc))
            ts, tc = f"__mp_t{i}_s", f"__mp_t{i}_c"
            reduce_aggs.append(AG.Sum(ColumnRef(ps)).with_name(ts))
            reduce_aggs.append(AG.Sum(ColumnRef(pc)).with_name(tc))
            projections.append(Alias(
                Divide(ColumnRef(ts), Cast(ColumnRef(tc), FLOAT64)), out))
        elif isinstance(a, (AG.CountStar, AG.Count)):
            p = f"__mp_p{i}"
            inner = (AG.CountStar() if isinstance(a, AG.CountStar)
                     else AG.Count(a.child))
            map_aggs.append(inner.with_name(p))
            reduce_aggs.append(AG.Sum(ColumnRef(p)).with_name(t))
            projections.append(Alias(
                Coalesce(ColumnRef(t), Literal(0, INT64)), out))
        elif isinstance(a, (AG.Sum, AG.Min, AG.Max)):
            p = f"__mp_p{i}"
            cls = type(a)
            map_aggs.append(cls(a.child).with_name(p))
            reduce_aggs.append(cls(ColumnRef(p)).with_name(t))
            projections.append(Alias(ColumnRef(t), out))
        else:
            return None
    return map_aggs, reduce_aggs, projections


def _find_root(plan, pred, through):
    """Topmost node matching ``pred`` reachable through unary
    driver-finishable nodes of the given types; returns (path, node)
    where path re-applies the upper fragment on the driver."""
    path = []
    node = plan
    while True:
        if pred(node):
            return path, node
        if isinstance(node, through) and len(node.children) == 1:
            path.append(node)
            node = node.children[0]
            continue
        return None, None


def _find_agg(plan):
    from ..plan import logical as L
    return _find_root(plan, lambda n: isinstance(n, L.Aggregate),
                      (L.Sort, L.Project, L.GlobalLimit, L.LocalLimit))


def _find_sort(plan):
    from ..plan import logical as L
    return _find_root(
        plan, lambda n: isinstance(n, L.Sort) and n.global_sort,
        (L.Project, L.GlobalLimit, L.LocalLimit))


def _find_window(plan):
    from ..plan import logical as L
    return _find_root(plan, lambda n: isinstance(n, L.Window),
                      (L.Sort, L.Project, L.GlobalLimit, L.LocalLimit))


def _largest_scan(child):
    scans: List = []
    _scan_sizes(child, scans)
    if not scans:
        return None
    return max(scans, key=lambda s: sum(t.num_rows for t in s.tables))


def _check_row_decomposable(child, stop_at=None, sliced=None) -> None:
    """The map fragment below a distributed agg/sort/window is executed
    on row SLICES of its largest scan, so it must be row-local: slicing
    the input and unioning the outputs has to equal running it whole.
    Project/Filter/Sample/inner-Join qualify; a nested Aggregate, Sort,
    Limit, Window, or an outer/semi/anti join (whose null-extended or
    filtered rows are per-slice artifacts — a dim row unmatched in one
    slice but matched in another would be emitted null-extended anyway)
    would silently compute per-slice results — refuse instead.
    ``stop_at`` marks a join the caller shuffles by key instead of
    slicing (its own subtrees are validated separately)."""
    from ..plan import logical as L
    ok = (L.Project, L.Filter, L.Join, L.LogicalScan, L.Sample,
          L.Union, L.Expand, L.Generate)

    def contains(n, target):
        return n is target or any(contains(c, target) for c in n.children)

    def walk(n):
        if n is stop_at:
            return
        if not isinstance(n, ok):
            raise ValueError(
                f"fragment below the distributed root is not "
                f"row-decomposable: {type(n).__name__} computes a "
                f"cross-row result and would be wrong on row slices")
        if isinstance(n, L.Join) and n.join_type != "inner":
            # a non-inner join slices safely ONLY when the sliced scan
            # feeds its row-preserving side (each output row then derives
            # from exactly one sliced row); a sliced null-producing or
            # filtering side emits per-slice artifacts
            preserving = {"left": 0, "leftsemi": 0, "leftanti": 0,
                          "existence": 0, "right": 1}.get(n.join_type)
            if preserving is None or sliced is None \
                    or not contains(n.children[preserving], sliced):
                raise ValueError(
                    f"{n.join_type} join is not row-decomposable with "
                    f"the sliced input on its non-preserving side")
        for c in n.children:
            walk(c)

    walk(child)


def _find_join(plan):
    """Topmost equi-Join in the subtree (depth-first)."""
    from ..plan import logical as L
    if isinstance(plan, L.Join):
        return plan
    for c in plan.children:
        j = _find_join(c)
        if j is not None:
            return j
    return None


def _scan_sizes(plan, out):
    from ..plan import logical as L
    if isinstance(plan, L.LogicalScan):
        out.append(plan)
    for c in plan.children:
        _scan_sizes(c, out)


def _subtree_rows(plan) -> int:
    scans: List = []
    _scan_sizes(plan, scans)
    return sum(sum(t.num_rows for t in s.tables) for s in scans)


def _replace_node(plan, old, new):
    if plan is old:
        return new
    clone = copy.copy(plan)
    clone.children = [_replace_node(c, old, new) for c in plan.children]
    return clone


# ---------------------------------------------------------------------------
# fault-tolerant dispatch
# ---------------------------------------------------------------------------

class _MapSpec:
    """One recorded map-side task: enough to re-run it on ANY live worker
    (the plan bytes carry the data slice). ``part`` pins the task to the
    owner of that partition (join_local reads its local store). The
    lineage edges recovery walks — which upstream shuffles must be
    present first — live per-shuffle in _FaultContext.reads."""
    __slots__ = ("task", "map_id", "kwargs", "part")

    def __init__(self, task: str, map_id: str, kwargs: dict,
                 part: Optional[int] = None):
        self.task = task
        self.map_id = map_id
        self.kwargs = kwargs
        self.part = part


class _FaultContext:
    """Per-execute() recovery state: partition ownership, lineage, the
    block-count manifests, and per-task failure budgets."""

    def __init__(self, worker_ids: List[str]):
        self.owners: List[str] = list(worker_ids)   # partition -> worker
        self.live: List[str] = list(worker_ids)
        self.lineage: Dict[int, List[_MapSpec]] = {}
        self.reads: Dict[int, Tuple[int, ...]] = {}
        self.manifest: Dict[int, Dict[int, int]] = {}
        #: per-shuffle observed stats {sid: {part: (rows, bytes)}} —
        #: folded at materialization, read by the AQE re-planner
        self.part_stats: Dict[int, Dict[int, Tuple[int, int]]] = {}
        self.clean: Set[int] = set()    # sids materialized under owners
        self.failures: Dict[str, int] = {}

    @property
    def n_parts(self) -> int:
        return len(self.owners)


class _TaskFailed(Exception):
    """Internal: one dispatched task failed in a recoverable way."""

    def __init__(self, key: str, wid: str, suspect: str,
                 cause: BaseException):
        super().__init__(f"{key} on {wid}: {cause}")
        self.key = key
        self.wid = wid
        self.suspect = suspect      # the worker believed dead
        self.cause = cause


# ---------------------------------------------------------------------------
# the cluster
# ---------------------------------------------------------------------------

class LocalCluster:
    """N worker processes shuffling over TCP with a shared HMAC token.

    Multi-host (VERDICT r3 #9): pass ``bind_host`` (a non-loopback
    address) and workers on OTHER hosts join via the standalone entry
    point — no code shipping, only the typed-task protocol:

        # on the driver host
        cl = LocalCluster(n_workers=0, bind_host="10.0.0.1")
        open("/shared/token", "wb").write(cl.token)
        print(cl.control.address)               # e.g. ('10.0.0.1', 41234)
        # on each worker host
        python -m spark_rapids_tpu.shuffle.worker \
            --driver 10.0.0.1:41234 --token-file /shared/token \
            --id 0 --bind 10.0.0.2
        # back on the driver
        cl.wait_for_workers(2)
        cl.execute(df)

    Local workers (``n_workers`` > 0) spawn as processes on this host and
    bind the same ``bind_host`` (ref Plugin.scala:428-439 heartbeat
    discovery; the transport is the RapidsShuffleTransport analog).

    ``conf`` wires the fault-tolerance knobs
    (``spark.rapids.tpu.shuffle.fetch.*``, ``spark.rapids.tpu.task.*``)
    and optionally arms chaos injection (``spark.rapids.tpu.chaos.*``);
    ``stale_after_s`` is the heartbeat-eviction horizon."""

    def __init__(self, n_workers: int = 2, start_timeout_s: float = 60.0,
                 shuffle_join_min_rows: int = 100_000,
                 bind_host: str = "127.0.0.1", conf=None,
                 stale_after_s: float = 30.0):
        import multiprocessing as mp
        from ..config import DEFAULT
        self.conf = conf or DEFAULT
        self.fetch_max_retries = int(self.conf.get(FETCH_MAX_RETRIES))
        self.fetch_backoff_ms = float(self.conf.get(FETCH_RETRY_BACKOFF_MS))
        self.task_timeout_s = float(self.conf.get(TASK_RPC_TIMEOUT))
        self.task_max_failures = max(1, int(self.conf.get(TASK_MAX_FAILURES)))
        self.trace_enabled = bool(self.conf.get(trace_core.TRACE_ENABLED))
        self.trace_spans = int(self.conf.get(trace_core.TRACE_BUFFER_SPANS))
        self.trace_output = str(self.conf.get(trace_core.TRACE_OUTPUT))
        from ..metrics import registry as metrics_registry
        self.metrics_enabled = bool(
            self.conf.get(metrics_registry.METRICS_ENABLED))
        self.metrics_interval_ms = int(
            self.conf.get(metrics_registry.METRICS_SAMPLE_INTERVAL_MS))
        if self.metrics_enabled:
            # driver-side lane of the merged cluster view
            metrics_registry.ensure_metrics_from_conf(self.conf)
        #: freshest registry snapshot per worker, from task-completion
        #: replies (heartbeat-carried ones live on self.manager.metrics)
        self._worker_metrics: Dict[str, dict] = {}
        self.token = secrets.token_bytes(32)
        self.bind_host = bind_host
        self.manager = ShuffleHeartbeatManager(stale_after_s=stale_after_s)
        # the control server binds ITS OWN manager: two live clusters in
        # one driver process must not cross-register workers
        self.control = BlockServer(host=bind_host, token=self.token,
                                   tasks={"register": self.manager.register})
        self.shuffle_join_min_rows = shuffle_join_min_rows
        self.fault_stats = {"workers_lost": 0, "tasks_redispatched": 0,
                            "maps_rerun": 0, "rounds_recovered": 0}
        self._dead: Set[str] = set()
        #: workers the chaos controller DELIBERATELY killed: their
        #: eviction is the experiment working, not an anomaly — the
        #: flight recorder's worker_evicted trigger skips them
        self._chaos_killed: Set[str] = set()
        net = {"timeout": self.task_timeout_s,
               "max_retries": self.fetch_max_retries,
               "backoff_ms": self.fetch_backoff_ms,
               "metrics_interval_ms": (self.metrics_interval_ms
                                       if self.metrics_enabled else 0)}
        ctx = mp.get_context("spawn")
        self._ready = ctx.Queue()
        self.procs = [ctx.Process(target=_worker_main,
                                  args=(i, self.control.address,
                                        self._ready, self.token,
                                        bind_host, net),
                                  daemon=True)
                      for i in range(n_workers)]
        self._proc_by_id = {f"worker-{i}": p
                            for i, p in enumerate(self.procs)}
        for p in self.procs:
            p.start()
        self.workers: Dict[str, Tuple[str, int]] = {}
        deadline = time.monotonic() + start_timeout_s
        while len(self.workers) < n_workers:
            if time.monotonic() > deadline:
                raise TimeoutError("workers failed to start")
            wid, addr = self._ready.get(timeout=start_timeout_s)
            self.workers[f"worker-{wid}"] = tuple(addr)
        self.clients = {wid: self._make_client(addr)
                        for wid, addr in sorted(self.workers.items())}
        # let every worker discover every peer before tasks ship
        for c in self.clients.values():
            c.task("heartbeat")
        self._next_shuffle = [0]
        # conf-armed chaos (spark.rapids.tpu.chaos.*): arm the driver
        # controller AND every worker process
        from ..aux.fault import (CHAOS_DELAY_MS, CHAOS_KILL_TARGET,
                                 CHAOS_SEED, CHAOS_SPEC)
        self.chaos = None
        spec = str(self.conf.get(CHAOS_SPEC))
        if spec.strip():
            self.set_chaos(spec, seed=int(self.conf.get(CHAOS_SEED)),
                           delay_ms=int(self.conf.get(CHAOS_DELAY_MS)),
                           kill_target=str(self.conf.get(CHAOS_KILL_TARGET)))
        # live ops plane: the /metrics endpoint serves the MERGED
        # cluster exposition and /healthz reads worker heartbeat ages
        # while this cluster is alive (weakly held — ops/server.py)
        from ..ops import server as ops_server_mod
        srv = ops_server_mod.SERVER
        if srv is not None:
            srv.register_cluster(self)

    def _make_client(self, addr) -> BlockClient:
        return BlockClient(addr, token=self.token,
                           timeout=self.task_timeout_s,
                           max_retries=self.fetch_max_retries,
                           backoff_ms=self.fetch_backoff_ms)

    def wait_for_workers(self, n: int, timeout_s: float = 120.0) -> None:
        """Block until ``n`` workers (incl. externally-launched ones) have
        registered via heartbeat, then connect task clients to them."""
        deadline = time.monotonic() + timeout_s
        while True:
            peers = {p["id"]: (p["addr"]["host"], p["addr"]["port"])
                     for p in self.manager.peer_details()}
            if len(peers) >= n:
                break
            if time.monotonic() > deadline:
                raise TimeoutError(
                    f"{len(peers)}/{n} workers registered")
            time.sleep(0.2)
        self.workers = dict(sorted(peers.items()))
        self.clients = {wid: self._make_client(addr)
                        for wid, addr in self.workers.items()}
        for c in self.clients.values():
            c.task("heartbeat")

    def _shuffle_id(self, owned: List[int]) -> int:
        sid = self._next_shuffle[0]
        self._next_shuffle[0] += 1
        owned.append(sid)
        return sid

    # ------------------------------------------------------- chaos / fault
    def set_chaos(self, spec: str, seed: int = 0, delay_ms: int = 100,
                  kill_target: str = "",
                  workers: Optional[List[str]] = None):
        """Arm deterministic fault injection: the driver-side controller
        drives worker.kill at task dispatch; every (or only ``workers``)
        worker process is armed with the same spec for the transport
        sites AND the in-process memory/semaphore sites (mem.oom,
        mem.reserve.delay, sem.stall — a worker's MemoryManager.reserve
        and semaphore acquires see the same seeded schedule, so the
        pressure battery runs identically in-process and distributed).
        Empty spec disarms everything."""
        from ..aux.fault import ChaosController
        self.chaos = (ChaosController(spec, seed=seed, delay_ms=delay_ms,
                                      kill_target=kill_target)
                      if str(spec).strip() else None)
        for wid, c in self.clients.items():
            if wid in self._dead:
                continue
            arm = spec if (workers is None or wid in workers) else ""
            try:
                c.task("chaos", spec=arm, seed=seed, delay_ms=delay_ms)
            except (ConnectionError, OSError):
                self._dead.add(wid)
        return self.chaos

    def chaos_stats(self) -> Dict[str, list]:
        """Fired-injection census across the whole cluster: the driver's
        controller (worker.kill) plus every live worker's fired list —
        one call answers 'did the battery actually hit its sites'."""
        out: Dict[str, list] = {}
        if self.chaos is not None:
            out["driver"] = self.chaos.fired()
        for wid, c in sorted(self.clients.items()):
            if wid in self._dead:
                continue
            try:
                fired = c.task("chaos_stats")
            except (ConnectionError, OSError):
                continue
            if fired:
                out[wid] = fired
        return out

    def live_workers(self) -> List[str]:
        return [w for w in sorted(self.clients) if w not in self._dead]

    def set_task_timeout(self, timeout_s: float) -> None:
        """Rebound spark.rapids.tpu.task.timeout at runtime (ops knob:
        tighten after warm-up — first tasks pay one-time import/compile
        cost a steady-state timeout should not be sized for)."""
        self.task_timeout_s = float(timeout_s)
        for c in self.clients.values():
            c.set_timeout(self.task_timeout_s)

    def _kill_worker(self, wid: str) -> None:
        import signal
        p = self._proc_by_id.get(wid)
        if p is not None and p.is_alive() and p.pid:
            os.kill(p.pid, signal.SIGKILL)
            p.join(timeout=5)

    def _maybe_chaos_kill(self, wid: str) -> None:
        c = self.chaos
        if c is None or not c.wants("worker.kill"):
            return
        if c.kill_target and c.kill_target != wid:
            return
        if c.fires("worker.kill"):
            self._chaos_killed.add(wid)
            self._kill_worker(wid)

    def _ping(self, wid: str) -> bool:
        import socket as _socket
        try:
            s = _socket.create_connection(self.workers[wid], timeout=2.0)
            s.close()
            return True
        except OSError:
            return False

    def _mark_dead(self, ctx: _FaultContext, dead: Set[str]) -> None:
        """Remove ``dead`` workers from scheduling, remap their
        partitions to survivors, and invalidate materialized shuffles so
        the next wave regenerates the moved partitions from lineage."""
        dead = {w for w in dead if w not in self._dead}
        if not dead:
            return
        self._dead.update(dead)
        self.fault_stats["workers_lost"] += len(dead)
        evicted = sorted(w for w in dead if w not in self._chaos_killed)
        if evicted:
            # anomaly hook (ISSUE 15): a worker died that chaos did NOT
            # deliberately kill — dump a bundle while the lineage
            # recovery state is still live
            from ..ops import flight as flight_mod
            fr = flight_mod.RECORDER
            if fr is not None:
                fr.trigger("worker_evicted",
                           detail=f"evicted {evicted} (not chaos-"
                                  f"killed); fault_stats="
                                  f"{self.fault_stats}")
        ctx.live = [w for w in ctx.live if w not in dead]
        if not ctx.live:
            raise ShuffleFetchFailed(
                f"no live workers left (lost {sorted(self._dead)})")
        for p, wid in enumerate(ctx.owners):
            if wid in dead:
                ctx.owners[p] = ctx.live[p % len(ctx.live)]
        ctx.clean.clear()
        self.fault_stats["rounds_recovered"] += 1

    def _recover(self, ctx: _FaultContext,
                 failures: List[_TaskFailed]) -> None:
        """Classify a wave's failures: bump per-task budgets (raising
        once a task is out of attempts), then integrate every death
        signal — RPC errors, peers named by ShuffleFetchFailed, TCP
        probes, and ShuffleHeartbeatManager staleness eviction — into
        the ownership map."""
        for f in failures:
            n = ctx.failures[f.key] = ctx.failures.get(f.key, 0) + 1
            if n >= self.task_max_failures:
                raise ShuffleFetchFailed(
                    f"task {f.key} failed {n} time(s), "
                    f"task.maxFailures={self.task_max_failures}: "
                    f"{f.cause}") from f.cause
        suspects = {f.suspect for f in failures}
        hb_live = set(self.manager.live_peers())
        dead = set()
        for wid in ctx.live:
            if wid in suspects:
                dead.add(wid)          # caused a real failure: never trust
            elif not self._ping(wid) or wid not in hb_live:
                dead.add(wid)          # silent death / stopped heartbeating
        self._mark_dead(ctx, dead)

    # ------------------------------------------------------- dispatch core
    def _submit(self, pool, wid: str, task: str, key: str, kwargs: dict):
        self._maybe_chaos_kill(wid)
        if self.trace_enabled or self.metrics_enabled:
            kwargs = dict(kwargs)
            if self.trace_enabled:
                kwargs["__trace__"] = {"spans": self.trace_spans}
            if self.metrics_enabled:
                kwargs["__metrics__"] = {
                    "intervalMs": self.metrics_interval_ms}
            return pool.submit(self._traced_task_call, wid, task, key,
                               kwargs)
        return pool.submit(self.clients[wid].task, task, **kwargs)

    def _traced_task_call(self, wid: str, task: str, key: str,
                          kwargs: dict):
        """RPC with telemetry collection: one driver-side span per
        dispatch (its duration is the full remote round trip); the
        worker's serialized trace buffer — attached to the completion
        reply by _trace_wrap — is ingested into the driver tracer so one
        distributed query yields one coherent timeline, and its metric
        snapshot lands in the per-worker view metrics_snapshot()
        merges."""
        tr = (trace_core.ensure_tracer_from_conf(self.conf)
              if self.trace_enabled else None)
        t0 = tr.now() if tr is not None else 0
        res = self.clients[wid].task(task, **kwargs)
        if isinstance(res, dict) and ("__trace__" in res
                                      or "__metrics__" in res):
            if tr is not None and res.get("__trace__") is not None:
                tr.ingest(res["__trace__"])
            snap = res.get("__metrics__")
            if snap is not None:
                prev = self._worker_metrics.get(wid)
                if (prev is None or prev.get("__ts__", 0)
                        <= snap.get("__ts__", 0)):
                    self._worker_metrics[wid] = snap
            res = res["result"]
        if tr is not None:
            tr.complete(f"rpc:{task}", t0, cat="rpc",
                        args={"worker": wid, "key": key})
        return res

    # ------------------------------------------------------- metrics view
    def metrics_snapshot(self) -> dict:
        """Merged continuous-telemetry view of the cluster (ISSUE 5):
        ``workers`` maps every lane (driver included) to its freshest
        registry snapshot — task-completion snapshots beat
        heartbeat-carried ones by timestamp — and ``aggregate`` is the
        label-merged union (every series stamped worker=<lane>).
        Returns {} when spark.rapids.tpu.metrics.enabled is off."""
        if not self.metrics_enabled:
            return {}
        from ..metrics import registry as metrics_registry
        from ..metrics.export import merge_snapshots, registry_snapshot
        lanes: Dict[str, dict] = {}
        for wid, snap in self.manager.metrics_by_worker().items():
            lanes[wid] = snap
        for wid, snap in self._worker_metrics.items():
            prev = lanes.get(wid)
            if (prev is None or prev.get("__ts__", 0)
                    <= snap.get("__ts__", 0)):
                lanes[wid] = snap
        # a dead worker's last snapshot is history, not a live lane
        for wid in self._dead:
            lanes.pop(wid, None)
        reg = metrics_registry.REGISTRY
        if reg is not None:
            lanes["driver"] = registry_snapshot(reg)
        return {"workers": lanes, "aggregate": merge_snapshots(lanes)}

    def prometheus_snapshot(self) -> str:
        """The merged cluster view as one Prometheus exposition — every
        worker's hbm/spill/semaphore/shuffle series distinguished by a
        ``worker`` label (empty string when metrics are off)."""
        snap = self.metrics_snapshot()
        if not snap:
            return ""
        from ..metrics.export import prometheus_text
        return prometheus_text(snap["aggregate"])

    def _collect(self, entries) -> Tuple[dict, List[_TaskFailed]]:
        """Drain (key, wid, future) entries; recoverable
        failures are collected, real errors raise."""
        results: Dict[str, object] = {}
        failures: List[_TaskFailed] = []
        for key, wid, future in entries:
            try:
                results[key] = future.result()
            except ShuffleFetchFailed as e:
                failures.append(_TaskFailed(key, wid, e.peer or wid, e))
            except (ConnectionError, OSError) as e:
                # includes socket.timeout: a worker exceeding
                # task.timeout is indistinguishable from a dead one
                failures.append(_TaskFailed(key, wid, wid, e))
        return results, failures

    def _materialize(self, ctx: _FaultContext, pool,
                     sids: List[int]) -> None:
        """Run every recorded map task of ``sids`` (deps first) until
        their blocks are present under the CURRENT ownership map.
        Re-puts dedupe server-side, so re-materializing after a remap
        only actually moves the partitions that changed owner."""
        pending = [s for s in sids if s not in ctx.clean]
        if not pending:
            return
        deps = [d for s in pending for d in ctx.reads.get(s, ())]
        if deps:
            self._materialize(ctx, pool, deps)
        specs = [sp for s in pending for sp in ctx.lineage[s]]
        results: Dict[str, object] = {}
        while True:
            todo = [sp for sp in specs if sp.map_id not in results]
            entries = []
            live = ctx.live
            for i, sp in enumerate(todo):
                wid = (ctx.owners[sp.part] if sp.part is not None
                       else live[i % len(live)])
                kwargs = dict(sp.kwargs)
                kwargs["map_id"] = sp.map_id
                kwargs["owners"] = ctx.owners
                if sp.task == "join_local":
                    kwargs["expected_l"] = ctx.manifest.get(
                        kwargs["shuffle_l"])
                    kwargs["expected_r"] = ctx.manifest.get(
                        kwargs["shuffle_r"])
                elif sp.task == "split_part":
                    src = kwargs["src_part"]
                    kwargs["expected"] = {
                        src: ctx.manifest.get(
                            kwargs["src_shuffle"], {}).get(src, 0)}
                entries.append((sp.map_id, wid,
                                self._submit(pool, wid, sp.task,
                                             sp.map_id, kwargs)))
            got, failures = self._collect(entries)
            results.update(got)
            if not failures:
                for s in pending:
                    manifest: Dict[int, int] = {}
                    stats: Dict[int, List[int]] = {}
                    for sp in ctx.lineage[s]:
                        for p, rb in results[sp.map_id].items():
                            manifest[p] = manifest.get(p, 0) + 1
                            st = stats.setdefault(p, [0, 0])
                            st[0] += int(rb[0])
                            st[1] += int(rb[1])
                    ctx.manifest[s] = manifest
                    # observed per-partition (rows, bytes): the
                    # ShuffleStats snapshot the AQE boundary re-plans
                    # from, refreshed on every (re-)materialization
                    ctx.part_stats[s] = {p: (v[0], v[1])
                                         for p, v in stats.items()}
                    ctx.clean.add(s)
                return
            self.fault_stats["tasks_redispatched"] += len(failures)
            dead_before = set(self._dead)
            self._recover(ctx, failures)
            if self._dead != dead_before:
                # ownership moved: every fragment must re-put under the
                # new map (blocks routed to the dead owner are gone;
                # re-puts to unchanged owners dedupe server-side)
                self.fault_stats["maps_rerun"] += len(specs)
                results.clear()
                self._materialize(ctx, pool, deps)  # deps moved too
            else:
                # transient failure, same ownership: only the failed
                # fragments re-run
                self.fault_stats["maps_rerun"] += len(failures)

    def _run_reduces(self, ctx: _FaultContext, pool, sid: int,
                     proto_bytes: bytes, *,
                     owned_sids: Optional[List[int]] = None,
                     split_key_bytes: Optional[bytes] = None,
                     coalesce_ok: bool = True) -> List:
        """One reduce-style task per READ UNIT, on the unit's owner;
        returns deserialized results in PARTITION order (partition w
        holds range/bucket w, so partition order IS result order).

        This is the AQE shuffle boundary (ISSUE 19): after the shuffle
        materializes — and BEFORE any reducer launches — the observed
        per-partition stats re-plan the read side (aqe/planner.py).
        Runs of small partitions merge into one unit whose task
        remote-fetches non-local members over the transport; a skewed
        partition is salted-split into sub-partitions via the
        split_part task, with its own lineage edge so recovery
        regenerates it like any other shuffle. With AQE off every unit
        is exactly one partition — the pre-AQE plan, unchanged. Units
        are planned ONCE per boundary from deterministic stats, so a
        failed wave (remapped ownership, lineage regeneration) re-runs
        the SAME units and reproduces identical results."""
        from ..columnar.serializer import deserialize_table
        done: Dict[str, object] = {}
        units = None
        while True:
            self._materialize(ctx, pool, [sid])
            if units is None:
                units = self._aqe_reduce_units(
                    ctx, sid, owned_sids=owned_sids,
                    split_key_bytes=split_key_bytes,
                    coalesce_ok=coalesce_ok)
            extra = sorted({u.sid for u in units} - {sid})
            if extra:
                self._materialize(ctx, pool, extra)
            entries = []
            for i, u in enumerate(units):
                key = f"reduce:{sid}:{i}"
                if key in done:
                    continue
                wid = ctx.owners[u.owner_part]
                # AQE-coalesced units may span owners: non-local
                # members are fetched from their owner's store
                remote = {p: ctx.owners[p] for p in u.parts
                          if ctx.owners[p] != wid}
                kwargs = {"shuffle_id": u.sid, "parts": list(u.parts),
                          "plan_bytes": proto_bytes,
                          "expected": {p: ctx.manifest[u.sid].get(p, 0)
                                       for p in u.parts}}
                if remote:
                    kwargs["remote"] = remote
                entries.append(
                    (key, wid,
                     self._submit(pool, wid, "reduce_agg", key, kwargs)))
            results, failures = self._collect(entries)
            done.update(results)
            if not failures:
                break
            self.fault_stats["tasks_redispatched"] += len(failures)
            self._recover(ctx, failures)
            # ownership moved: results computed on still-owned
            # partitions remain valid (contents are deterministic)
        out = []
        for i in range(len(units)):
            got = done.get(f"reduce:{sid}:{i}")
            if got is not None:
                out.append(deserialize_table(got))
        return out

    def _aqe_reduce_units(self, ctx: _FaultContext, sid: int, *,
                          owned_sids: Optional[List[int]],
                          split_key_bytes: Optional[bytes],
                          coalesce_ok: bool):
        """Plan this boundary's read units from the materialized
        shuffle's observed stats, recording one AqeDecision per
        coalesced run / skew split. Disabled path (no installed log):
        one plain unit per partition, a single branch of overhead."""
        from ..aqe.planner import ReadUnit, ShuffleStats, \
            plan_reduce_units
        n = ctx.n_parts
        log = aqe_mod.LOG
        if log is None:
            return [ReadUnit(sid, [p], p, (p, 0)) for p in range(n)]
        stats = ShuffleStats(sid, ctx.part_stats.get(sid, {}), n)
        target = int(self.conf.get(aqe_mod.AQE_COALESCE_TARGET_BYTES))
        ratio = float(self.conf.get(aqe_mod.AQE_SKEW_THRESHOLD))
        minb = int(self.conf.get(aqe_mod.AQE_SKEW_MIN_BYTES))
        units, splits, _n_coalesced = plan_reduce_units(
            stats, target_bytes=target, skew_threshold=ratio,
            skew_min_bytes=minb,
            allow_split=(split_key_bytes is not None
                         and owned_sids is not None),
            allow_coalesce=coalesce_ok)
        for p, k in sorted(splits.items()):
            # the salted sub-shuffle: one split_part map spec pinned to
            # the skewed partition's owner, lineage edge back to the
            # source shuffle so recovery regenerates end-to-end
            sub_sid = self._shuffle_id(owned_sids)
            ctx.lineage[sub_sid] = [_MapSpec(
                "split_part", f"m{sub_sid}.0",
                {"src_shuffle": sid, "src_part": p,
                 "out_shuffle": sub_sid, "key_bytes": split_key_bytes,
                 "n_sub": k, "seed": 1 + p}, part=p)]
            ctx.reads[sub_sid] = (sid,)
            for u in units:
                if u.kind == "split" and u.order[0] == p:
                    u.sid = sub_sid
            log.record(aqe_mod.make_decision(
                aqe_mod.SKEW_SPLIT,
                detail=f"shuffle {sid} part {p} "
                       f"({stats.part_bytes(p)}B vs mean "
                       f"{int(stats.mean_bytes)}B) -> {k} salted "
                       f"sub-partitions",
                shuffle=sid, parts=k))
        for u in units:
            if u.kind == "coalesced":
                log.record(aqe_mod.make_decision(
                    aqe_mod.COALESCE_PARTITIONS,
                    detail=f"shuffle {sid} parts {u.parts} "
                           f"({sum(stats.part_bytes(p) for p in u.parts)}"
                           f"B <= target {target}B) -> one reduce unit",
                    shuffle=sid, parts=len(u.parts)))
        return units

    def _new_context(self) -> _FaultContext:
        live = self.live_workers()
        if not live:
            raise ShuffleFetchFailed("no live workers")
        return _FaultContext(live)

    # -------------------------------------------------------------------
    def execute(self, df):
        """Distributed execution of a DataFrame whose plan is
        Sort/Project/Limit* over a decomposable Aggregate: map fragments
        run on workers, the shuffle moves partial-aggregate blocks, the
        reduce merges, the driver finishes the plan. When the aggregate
        sits over an equi-join whose sides are BOTH large, the join is
        itself shuffled (both sides hash-partitioned by join key) before
        the local join + partial agg. Worker failures mid-query are
        recovered via lineage (class docstring). Returns Arrow.

        With ``spark.rapids.tpu.trace.enabled`` the query produces ONE
        merged trace — driver spans plus every worker's, aligned onto a
        single timeline — written to ``spark.rapids.tpu.trace.output``
        when set (or exported later via :meth:`write_trace`)."""
        tr = trace_core.ensure_tracer_from_conf(self.conf)
        # AQE default-on for cluster queries: ensure the decision log
        # (one conf lookup), mark it so only THIS query's decisions —
        # all recorded on this driving thread — land on its records
        aqe_log = aqe_mod.ensure_aqe_from_conf(self.conf)
        aqe_mark = aqe_log.mark() if aqe_log is not None else 0
        try:
            if tr is None:
                return self._execute_impl(df)
            with tr.span("cluster.execute", cat="query"):
                out = self._execute_impl(df)
        finally:
            aqe_decs = (aqe_log.since(aqe_mark,
                                      thread=threading.get_ident())
                        if aqe_log is not None else [])
            session = getattr(df, "session", None)
            if session is not None:
                session.last_fault_stats = dict(self.fault_stats)
                session.last_aqe_decisions = [d.summary()
                                              for d in aqe_decs]
                elog = getattr(session, "event_log", None)
                if elog is not None:
                    # distributed queries do not route through the
                    # session's run_query, so they append their
                    # own durable record carrying the fault counters
                    rec = {"event": "clusterQuery",
                           "faultStats": dict(self.fault_stats),
                           "liveWorkers": self.live_workers()}
                    if aqe_decs:
                        rec["aqe"] = aqe_mod.summarize(aqe_decs)
                    elog.write(rec)
        if self.trace_output:
            try:
                self.write_trace(self.trace_output)
            except Exception as e:  # noqa: BLE001 - never fail a query
                import logging
                logging.getLogger(__name__).warning(
                    "could not write trace to %s: %s",
                    self.trace_output, e)
        return out

    def write_trace(self, path: str, drain: bool = True):
        """Export the merged driver+worker trace as Chrome-trace JSON
        (None when tracing was never enabled)."""
        tr = trace_core.TRACER
        if tr is None:
            return None
        from ..trace.export import write_chrome_trace
        return write_chrome_trace(path, tr, drain=drain)

    def _execute_impl(self, df):
        from ..plan import logical as L
        from ..plan.rewrites import prune_columns
        from ..types import Schema, from_arrow, StructField
        import pyarrow as pa

        plan = prune_columns(df.plan)
        path, agg = _find_agg(plan)
        if agg is None:
            wpath, win = _find_window(plan)
            if win is not None:
                return self._execute_window(df, plan, wpath, win)
            spath, sort = _find_sort(plan)
            if sort is not None:
                return self._execute_sort(df, plan, spath, sort)
            raise ValueError(
                "plan has no distributable aggregate/sort/window root")
        dec = _decompose_aggs(agg.groupings, agg.aggs,
                              agg.children[0].schema())
        if dec is None:
            raise ValueError("aggregates are not merge-decomposable")
        map_aggs, reduce_aggs, projections = dec

        ctx = self._new_context()
        group_bytes = pickle.dumps([self._group_ref(g)
                                    for g in agg.groupings])

        join = _find_join(agg.children[0])
        shuffled_join = (
            join is not None and join.condition is None
            and join.join_type in ("inner", "left", "right", "full")
            and join.left_keys and join.right_keys
            and _subtree_rows(join.children[0]) >= self.shuffle_join_min_rows
            and _subtree_rows(join.children[1]) >= self.shuffle_join_min_rows)
        if shuffled_join:
            # the join itself is key-shuffled (exact for outer types);
            # each SIDE is row-sliced and must be row-local on its own
            _check_row_decomposable(agg.children[0], stop_at=join)
            for side in join.children:
                _check_row_decomposable(side,
                                        sliced=_largest_scan(side))
        else:
            _check_row_decomposable(agg.children[0],
                                    sliced=_largest_scan(agg.children[0]))

        side_shuffles = None
        with self._shuffle_scope() as (pool, owned_sids):
            if shuffled_join:
                agg_shuffle, side_shuffles = self._plan_shuffled_join(
                    ctx, agg, join, map_aggs, group_bytes, owned_sids)
                # AQE join skew pass: materialize the side shuffles and
                # salted-split any skewed partition into co-partitioned
                # sub-joins BEFORE the local joins dispatch
                self._aqe_split_join(ctx, pool, side_shuffles,
                                     agg_shuffle, join, owned_sids)
            else:
                agg_shuffle = self._plan_sliced_map(
                    ctx, agg, map_aggs, group_bytes, owned_sids)

            # reduce: the owner of partition w merges it; the child is
            # patched worker-side with a scan of the fetched blocks
            reduce_proto = L.Aggregate(
                [self._group_ref(g) for g in agg.groupings], reduce_aggs,
                L.RangeRel(0, 1),
                int_key_cards=getattr(agg, "int_key_cards", None))
            proto_bytes = pickle.dumps(reduce_proto)
            results = self._run_reduces(
                ctx, pool, agg_shuffle, proto_bytes,
                owned_sids=owned_sids,
                split_key_bytes=(group_bytes if agg.groupings else None))
            # closed loop (d): observed rows/bytes replace estimates for
            # the driver finish and every future plan of these shapes
            self._aqe_record_observed(
                ctx, agg, results, side_shuffles,
                join if shuffled_join else None)

        merged = pa.concat_tables(results) if results else None
        # driver finish: restore names/avg divides, then the upper path
        from ..api.dataframe import TpuSession
        session = getattr(df, "session", None) or TpuSession()
        if merged is None:
            agg_out_schema = L.Aggregate(agg.groupings, agg.aggs,
                                         agg.children[0]).schema()
            merged = _empty_like(agg_out_schema)
            final = L.LogicalScan([merged], agg_out_schema)
        else:
            schema = Schema([StructField(f.name, from_arrow(f.type), True)
                             for f in merged.schema])
            final = L.Project(projections,
                              L.LogicalScan([merged], schema))
        for node in reversed(path):
            clone = copy.copy(node)
            clone.children = [final]
            final = clone
        from ..plan.overrides import plan_query
        physical = plan_query(final, session.conf)
        return run_plan(session, physical)

    # -------------------------------------------------------------------
    def _shuffle_scope(self):
        """Task pool + shuffle-id ownership with guaranteed cleanup: the
        one lifecycle every distributed round (agg/sort/window) shares."""
        import concurrent.futures as cf
        from contextlib import contextmanager

        @contextmanager
        def scope():
            pool = cf.ThreadPoolExecutor(max_workers=2 * len(self.clients))
            owned: List[int] = []
            try:
                yield pool, owned
            finally:
                # settle in-flight tasks BEFORE dropping, or a late map
                # PUT would recreate blocks for a dropped shuffle id
                pool.shutdown(wait=True)
                for wid, c in self.clients.items():
                    if wid in self._dead:
                        continue
                    for sid in owned:
                        try:
                            c.drop(sid)
                        except Exception:
                            continue
        return scope()

    def _driver_finish(self, df, results, out_schema, path):
        """Concatenate worker results (in partition order) and re-apply
        the driver-finishable upper path."""
        import pyarrow as pa
        from ..api.dataframe import TpuSession
        from ..plan import logical as L
        from ..plan.overrides import plan_query
        session = getattr(df, "session", None) or TpuSession()
        merged = (pa.concat_tables(results) if results
                  else _empty_like(out_schema))
        final = L.LogicalScan([merged], out_schema)
        for node in reversed(path):
            clone = copy.copy(node)
            clone.children = [final]
            final = clone
        physical = plan_query(final, session.conf)
        return run_plan(session, physical)

    def _sliced_fragments(self, child, n: int):
        """Slice the largest in-memory scan of a fragment row-wise into
        ``n`` slices; returns the per-slice fragment plans."""
        from ..plan import logical as L
        import pyarrow as pa
        scans: List = []
        _scan_sizes(child, scans)
        if not scans:
            raise ValueError("no in-memory scans to distribute")
        fact = max(scans, key=lambda s: sum(t.num_rows for t in s.tables))
        fact_table = pa.concat_tables(fact.tables) \
            if len(fact.tables) > 1 else fact.tables[0]
        per = -(-fact_table.num_rows // n)
        plans = []
        for wi in range(n):
            slice_w = fact_table.slice(wi * per, per)
            scan_w = L.LogicalScan([slice_w], fact._schema,
                                   columns=fact.columns)
            plans.append(_replace_node(child, fact, scan_w))
        return plans, fact, fact_table

    def _execute_sort(self, df, plan, path, sort):
        """Distributed global sort (VERDICT r3 #6): sample the first sort
        key for range boundaries, range-shuffle the fragment output, sort
        each range locally, concatenate in range order (ref
        GpuRangePartitioner + GpuSortExec over the shuffle manager,
        RapidsShuffleInternalManagerBase.scala:238-614)."""
        import copy as _copy
        from ..plan import logical as L
        child = sort.children[0]
        _check_row_decomposable(child, sliced=_largest_scan(child))
        order0 = sort.orders[0]
        key_name = order0.expr.name_hint
        if key_name not in child.schema().names():
            raise ValueError("distributed sort keys must be child columns")
        ctx = self._new_context()
        n = ctx.n_parts
        plans, fact, fact_table = self._sliced_fragments(child, n)
        boundaries = self._sample_boundaries(df, child, order0, n,
                                             fact=fact,
                                             fact_table=fact_table)
        with self._shuffle_scope() as (pool, owned_sids):
            sid = self._shuffle_id(owned_sids)
            key_bytes = pickle.dumps((key_name, order0.ascending,
                                      order0.nulls_first))
            boundaries_bytes = pickle.dumps(boundaries)
            ctx.lineage[sid] = [
                _MapSpec("map_range", f"m{sid}.{wi}",
                         {"shuffle_id": sid, "plan_bytes": pickle.dumps(p),
                          "key_bytes": key_bytes,
                          "boundaries_bytes": boundaries_bytes})
                for wi, p in enumerate(plans)]
            proto = _copy.copy(sort)
            proto.children = [L.RangeRel(0, 1)]
            proto_bytes = pickle.dumps(proto)
            # partition w holds range w: descending orders put the
            # LARGEST range in partition 0, so partition order IS sort
            # order. AQE may coalesce small ranges (disjoint + ordered,
            # so a locally sorted merged range keeps the global order);
            # range partitions can NOT be salted-split, so no split
            # keys are passed
            results = self._run_reduces(ctx, pool, sid, proto_bytes,
                                        owned_sids=owned_sids)
        return self._driver_finish(df, results, sort.schema(), path)

    def _sample_boundaries(self, df, child, order0, n_parts: int,
                           sample_rows: int = 20000, fact=None,
                           fact_table=None):
        """Range boundaries from a driver-local sample of the fragment
        output (the RangePartitioner sampling pass, run through the same
        fragment plan the workers will run). ``fact``/``fact_table`` come
        from the caller's _sliced_fragments pass — re-concatenating a
        multi-chunk fact table here would double the driver copy cost."""
        import numpy as np
        import pyarrow as pa
        from ..api.dataframe import TpuSession
        from ..columnar import ColumnarBatch
        from ..exprs.arithmetic import arrow_to_masked_numpy
        from ..exprs.base import ColumnRef
        from ..plan import logical as L
        from ..plan.overrides import plan_query
        if fact is None or fact_table is None:
            scans: List = []
            _scan_sizes(child, scans)
            fact = max(scans,
                       key=lambda s: sum(t.num_rows for t in s.tables))
            fact_table = pa.concat_tables(fact.tables) \
                if len(fact.tables) > 1 else fact.tables[0]
        total = fact_table.num_rows
        if total > sample_rows:
            rng = np.random.RandomState(77)
            idx = np.sort(rng.choice(total, sample_rows, replace=False))
            sample = fact_table.take(pa.array(idx))
        else:
            sample = fact_table
        scan_s = L.LogicalScan([sample], fact._schema, columns=fact.columns)
        plan_s = _replace_node(child, fact, scan_s)
        session = getattr(df, "session", None) or TpuSession()
        out = run_plan(session, plan_query(plan_s, session.conf))
        batch = ColumnarBatch.from_arrow_host(out)
        arr = ColumnRef(order0.expr.name_hint).eval_host(batch)
        if isinstance(arr, pa.ChunkedArray):
            arr = arr.combine_chunks()
        v, ok = arrow_to_masked_numpy(arr)
        v = np.asarray(v)[np.asarray(ok, bool)]
        if not len(v):
            return []
        v = np.sort(v)          # ASC always; routing handles direction
        cuts = [v[int(len(v) * i / n_parts)] for i in range(1, n_parts)]
        return list(cuts)

    def _execute_window(self, df, plan, path, win):
        """Distributed windows (VERDICT r3 #6): hash-shuffle the fragment
        output by the window partition keys (co-locating every window
        partition on one worker), run the full Window node per worker,
        concatenate (ref hash-partitioned GpuWindowExec over the shuffle
        manager)."""
        import copy as _copy
        from ..exprs.base import ColumnRef
        from ..plan import logical as L
        specs = [spec for _e, spec, _n in win.window_exprs]
        keysets = {tuple(e.name_hint for e in s.partition_by)
                   for s in specs}
        if len(keysets) != 1 or not next(iter(keysets)):
            raise ValueError("distributed windows need one shared, "
                             "non-empty partition_by")
        keys = [ColumnRef(k) for k in next(iter(keysets))]
        child = win.children[0]
        _check_row_decomposable(child, sliced=_largest_scan(child))
        cnames = child.schema().names()
        if any(k.name not in cnames for k in keys):
            raise ValueError("window partition keys must be child columns")
        ctx = self._new_context()
        plans, _fact, _ft = self._sliced_fragments(child, ctx.n_parts)
        with self._shuffle_scope() as (pool, owned_sids):
            sid = self._shuffle_id(owned_sids)
            group_bytes = pickle.dumps(keys)
            ctx.lineage[sid] = [
                _MapSpec("map_agg", f"m{sid}.{wi}",
                         {"shuffle_id": sid, "plan_bytes": pickle.dumps(p),
                          "group_bytes": group_bytes})
                for wi, p in enumerate(plans)]
            proto = _copy.copy(win)
            proto.children = [L.RangeRel(0, 1)]
            proto_bytes = pickle.dumps(proto)
            # windows keep the per-partition row order of their input:
            # coalescing would interleave two partitions' outputs, so
            # AQE leaves window boundaries alone (plain units)
            results = self._run_reduces(ctx, pool, sid, proto_bytes,
                                        coalesce_ok=False)
        return self._driver_finish(df, results, win.schema(), path)

    def _plan_sliced_map(self, ctx: _FaultContext, agg, map_aggs,
                         group_bytes, owned_sids: List[int]) -> int:
        """Original single-exchange path: the fact scan sliced row-wise,
        dims ride replicated (broadcast analog); partial agg on top.
        Records lineage only — dispatch happens in _run_reduces'
        materialize pass."""
        from ..plan import logical as L
        shuffle_id = self._shuffle_id(owned_sids)
        plans, _fact, _ft = self._sliced_fragments(agg.children[0],
                                                   ctx.n_parts)
        specs = []
        for wi, child_w in enumerate(plans):
            map_plan = L.Aggregate(
                list(agg.groupings), map_aggs, child_w,
                int_key_cards=getattr(agg, "int_key_cards", None))
            specs.append(_MapSpec(
                "map_agg", f"m{shuffle_id}.{wi}",
                {"shuffle_id": shuffle_id,
                 "plan_bytes": pickle.dumps(map_plan),
                 "group_bytes": group_bytes}))
        ctx.lineage[shuffle_id] = specs
        return shuffle_id

    # -------------------------------------------------------------------
    def _plan_shuffled_join(self, ctx: _FaultContext, agg, join, map_aggs,
                            group_bytes, owned_sids: List[int]
                            ) -> Tuple[int, List[int]]:
        """Two-exchange path: hash-shuffle both join sides by join keys,
        local join + partial agg per worker, then the agg exchange. The
        join_local tasks record lineage edges to BOTH side shuffles, so
        losing a worker regenerates the side partitions before the local
        joins re-run."""
        from ..plan import logical as L
        n = ctx.n_parts
        side_shuffles = []
        for side, keys in ((0, join.left_keys), (1, join.right_keys)):
            subtree = join.children[side]
            shuffle_id = self._shuffle_id(owned_sids)
            side_shuffles.append(shuffle_id)
            plans, _f, _ft = self._sliced_fragments(subtree, n)
            key_bytes = pickle.dumps(list(keys))
            ctx.lineage[shuffle_id] = [
                _MapSpec("join_side", f"m{shuffle_id}.{wi}",
                         {"shuffle_id": shuffle_id,
                          "plan_bytes": pickle.dumps(p),
                          "key_bytes": key_bytes})
                for wi, p in enumerate(plans)]

        # local join + partial agg per worker; output rides the agg
        # exchange. The template is the agg child with the join's inputs
        # to be patched worker-side (located by the same deterministic
        # walk both sides of the wire run).
        lschema = join.children[0].schema()
        rschema = join.children[1].schema()
        template_join = copy.copy(join)
        template_join.children = [L.RangeRel(0, 1), L.RangeRel(0, 1)]
        template_child = _replace_node(agg.children[0], join,
                                       template_join)
        template = L.Aggregate(
            list(agg.groupings), map_aggs, template_child,
            int_key_cards=getattr(agg, "int_key_cards", None))
        agg_shuffle = self._shuffle_id(owned_sids)
        schemas_bytes = pickle.dumps((lschema, rschema))
        template_bytes = pickle.dumps(template)
        ctx.lineage[agg_shuffle] = [
            _MapSpec("join_local", f"m{agg_shuffle}.{p}",
                     {"shuffle_l": side_shuffles[0],
                      "shuffle_r": side_shuffles[1], "parts": [p],
                      "template_bytes": template_bytes,
                      "group_bytes": group_bytes,
                      "out_shuffle": agg_shuffle,
                      "schemas_bytes": schemas_bytes},
                     part=p)
            for p in range(n)]
        ctx.reads[agg_shuffle] = tuple(side_shuffles)
        return agg_shuffle, side_shuffles

    def _aqe_split_join(self, ctx: _FaultContext, pool,
                        side_shuffles: List[int], agg_shuffle: int,
                        join, owned_sids: List[int]) -> None:
        """AQE skewed-join handling (ISSUE 19; ref Spark AQE
        OptimizeSkewedJoin): materialize both side shuffles, and for
        every partition whose combined side bytes are skewed, replace
        its single join_local spec with ``k`` sub-joins over salted
        co-partitioned sub-shuffles of BOTH sides. The same salt on
        both sides keeps equal join keys routed to the same
        sub-partition, so each sub-join is exact; sub-join partials
        ride the same agg exchange, and the rewritten lineage edges
        make recovery regenerate splits like any other shuffle."""
        log = aqe_mod.LOG
        if log is None:
            return
        from ..aqe.planner import is_skewed, split_width
        self._materialize(ctx, pool, list(side_shuffles))
        n = ctx.n_parts
        ratio = float(self.conf.get(aqe_mod.AQE_SKEW_THRESHOLD))
        minb = int(self.conf.get(aqe_mod.AQE_SKEW_MIN_BYTES))
        sl = ctx.part_stats.get(side_shuffles[0]) or {}
        sr = ctx.part_stats.get(side_shuffles[1]) or {}
        tot = [sl.get(p, (0, 0))[1] + sr.get(p, (0, 0))[1]
               for p in range(n)]
        mean = sum(tot) / n if n else 0.0
        key_bytes = (pickle.dumps(list(join.left_keys)),
                     pickle.dumps(list(join.right_keys)))
        for p in range(n):
            if not is_skewed(tot[p], mean, ratio, minb):
                continue
            k = split_width(tot[p], mean, n)
            sub = []
            for side, src in enumerate(side_shuffles):
                s2 = self._shuffle_id(owned_sids)
                ctx.lineage[s2] = [_MapSpec(
                    "split_part", f"m{s2}.0",
                    {"src_shuffle": src, "src_part": p,
                     "out_shuffle": s2, "key_bytes": key_bytes[side],
                     "n_sub": k, "seed": 1 + p}, part=p)]
                ctx.reads[s2] = (src,)
                sub.append(s2)
            old_id = f"m{agg_shuffle}.{p}"
            old = next(sp for sp in ctx.lineage[agg_shuffle]
                       if sp.map_id == old_id)
            new_specs = []
            for j in range(k):
                kw = dict(old.kwargs)
                kw["shuffle_l"], kw["shuffle_r"] = sub
                kw["parts"] = [j]
                new_specs.append(_MapSpec(
                    "join_local", f"{old_id}s{j}", kw, part=j))
            ctx.lineage[agg_shuffle] = \
                [sp for sp in ctx.lineage[agg_shuffle]
                 if sp.map_id != old_id] + new_specs
            ctx.reads[agg_shuffle] = ctx.reads[agg_shuffle] + tuple(sub)
            log.record(aqe_mod.make_decision(
                aqe_mod.SKEW_SPLIT,
                detail=f"shuffled join part {p} ({tot[p]}B vs mean "
                       f"{int(mean)}B across sides) -> {k} salted "
                       f"co-partitioned sub-joins",
                shuffle=agg_shuffle, parts=k))

    def _aqe_record_observed(self, ctx: _FaultContext, agg, results,
                             side_shuffles, join) -> None:
        """Closed-loop cost feedback (the AQE ``cost_replan`` leg):
        record the MEASURED aggregate cardinality and join side sizes
        under their plan signatures, so the driver-finish planning of
        this query — and every later plan of the same shapes — runs the
        learned-cost optimizer over observed numbers instead of scan
        estimates (plan/cost.py runtime tables)."""
        try:
            from ..plan.cost import (estimate_rows, plan_signature,
                                     record_runtime_rows,
                                     record_runtime_size)
            obs = sum(int(t.num_rows) for t in results) if results else 0
            sig = plan_signature(agg)
            est = estimate_rows(agg)
            record_runtime_rows(sig, obs)
            if join is not None and side_shuffles:
                for child, s in zip(join.children, side_shuffles):
                    st = ctx.part_stats.get(s) or {}
                    b = sum(v[1] for v in st.values())
                    if b:
                        record_runtime_size(plan_signature(child), b)
            log = aqe_mod.LOG
            if log is not None and est and obs and (
                    obs >= 2 * est or est >= 2 * obs):
                log.record(aqe_mod.make_decision(
                    aqe_mod.COST_REPLAN,
                    detail=f"aggregate observed {obs} rows vs estimated "
                           f"{int(est)} — remaining stages plan with "
                           "the observed cardinality"))
        except Exception:  # noqa: BLE001 - feedback must never fail a query
            pass

    @staticmethod
    def _group_ref(g):
        from ..exprs.base import ColumnRef
        return ColumnRef(g.name_hint)

    def shutdown(self, join_timeout_s: float = 10.0):
        """Stop workers and reap their processes. Escalates join ->
        terminate (SIGTERM) -> kill (SIGKILL): a hung — or SIGSTOPped —
        worker can never wedge teardown, because SIGKILL is delivered
        even to a stopped process."""
        for wid, c in self.clients.items():
            try:
                if wid not in self._dead:
                    # a wedged worker must not stall shutdown for the
                    # full task timeout
                    c.set_timeout(min(5.0, max(1.0, join_timeout_s)))
                    c.task("stop")
            except Exception:
                pass
            c.close()
        for p in self.procs:
            p.join(timeout=join_timeout_s)
            if p.is_alive():
                p.terminate()
                p.join(timeout=max(1.0, join_timeout_s / 2))
            if p.is_alive():
                p.kill()
                p.join(timeout=max(1.0, join_timeout_s / 2))
        self.control.close()


def _empty_like(schema):
    import pyarrow as pa
    from ..types import to_arrow
    return pa.table({f.name: pa.array([], type=to_arrow(f.dtype))
                     for f in schema.fields})
