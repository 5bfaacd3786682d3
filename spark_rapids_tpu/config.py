"""Typed configuration system.

TPU-native analog of the reference's RapidsConf (sql-plugin/.../RapidsConf.scala:
122-261 ConfEntry/ConfBuilder DSL, registry at 320-328, `help()` doc generation).
Keys live under ``spark.rapids.tpu.*``. The registry is introspectable so
``generate_docs()`` can emit docs/configs.md just like the reference.
"""
from __future__ import annotations

import os
import threading
from typing import Any, Callable, Dict, List, Optional

__all__ = ["ConfEntry", "TpuConf", "register", "all_entries", "generate_docs"]

_LOCK = threading.Lock()
_REGISTRY: Dict[str, "ConfEntry"] = {}  # tpulint: guarded-by _LOCK


class ConfEntry:
    def __init__(self, key: str, default: Any, doc: str, conv: Callable[[str], Any],
                 internal: bool = False, startup_only: bool = False,
                 commonly_used: bool = False):
        self.key = key
        self.default = default
        self.doc = doc
        self.conv = conv
        self.internal = internal
        self.startup_only = startup_only
        self.commonly_used = commonly_used

    def get(self, conf: "TpuConf") -> Any:
        raw = conf.raw.get(self.key)
        if raw is None:
            env_key = self.key.upper().replace(".", "_")
            raw = os.environ.get(env_key)
        if raw is None:
            return self.default
        if isinstance(raw, str):
            return self.conv(raw)
        return raw


def _bool(s: str) -> bool:
    return s.strip().lower() in ("1", "true", "yes", "on")


def _register(key: str, default, doc, conv, **kw) -> ConfEntry:
    with _LOCK:
        if key in _REGISTRY:
            raise ValueError(f"duplicate conf key {key}")
        e = ConfEntry(key, default, doc, conv, **kw)
        _REGISTRY[key] = e
        return e


def register(key: str, default, doc: str, **kw) -> ConfEntry:
    conv: Callable[[str], Any]
    if isinstance(default, bool):
        conv = _bool
    elif isinstance(default, int):
        conv = int
    elif isinstance(default, float):
        conv = float
    else:
        conv = str
    return _register(key, default, doc, conv, **kw)


def all_entries() -> List[ConfEntry]:
    # snapshot under the lock: the docs generator or qualify tool may
    # enumerate while ensure_op_confs() is still registering per-op keys
    with _LOCK:
        entries = list(_REGISTRY.values())
    return sorted(entries, key=lambda e: e.key)


# ---------------------------------------------------------------------------
# Registered configs (counterparts of the reference's key knobs; reference
# file:line cited per entry)
# ---------------------------------------------------------------------------

SQL_ENABLED = register(
    "spark.rapids.tpu.sql.enabled", True,
    "Enable plan replacement onto the TPU (ref RapidsConf spark.rapids.sql.enabled).",
    commonly_used=True)

EXPLAIN = register(
    "spark.rapids.tpu.sql.explain", "NONE",
    "NONE / NOT_ON_TPU / ALL: log why (parts of) a plan did or did not run on the "
    "TPU (ref RapidsConf spark.rapids.sql.explain).", commonly_used=True)

MODE = register(
    "spark.rapids.tpu.sql.mode", "executeOnTPU",
    "executeOnTPU or explainOnly (ref GpuOverrides.scala:4701 explain-only mode).")

CONCURRENT_TPU_TASKS = register(
    "spark.rapids.tpu.sql.concurrentTpuTasks", 2,
    "Number of tasks that may hold the device semaphore concurrently "
    "(ref RapidsConf.scala:545 concurrentGpuTasks / GpuSemaphore.scala:137).",
    commonly_used=True)

BATCH_SIZE_BYTES = register(
    "spark.rapids.tpu.sql.batchSizeBytes", 512 * 1024 * 1024,
    "Target columnar batch size; coalesce goal ceiling "
    "(ref RapidsConf.scala:554 batchSizeBytes).", commonly_used=True)

BATCH_SIZE_ROWS = register(
    "spark.rapids.tpu.sql.batchSizeRows", 1 << 20,
    "Target max rows per columnar batch (shape-bucket ceiling; TPU-specific: "
    "bounds XLA recompilation via the bucket ladder). Also the TargetSize "
    "goal of the CoalesceBatches the planner puts above an in-memory scan "
    "or a streaming broadcast join whose under-filled batches reach a "
    "per-batch operator.")

AGG_WIDE_BATCH_ROWS = register(
    "spark.rapids.tpu.sql.agg.wideBatchRows", 0,
    "Batch-width ceiling for in-memory scans feeding a GLOBAL (no group "
    "key) aggregation: such pipelines have no per-batch group-bucket "
    "risk, and their steady-state cost is per-dispatch latency, so the "
    "scan feeds the widest batches possible — one batch means the whole "
    "query runs as ONE fused kernel dispatch + one fetch (ref "
    "GpuAggregateExec.scala:718 first-pass concatenation). 0 = auto: "
    "widen up to the whole partition ONLY while the estimated batch "
    "bytes fit half the HBM budget (the OOM retry-split machinery "
    "remains the backstop); set a row count to pin the ceiling instead.")

AUTO_BROADCAST_THRESHOLD = register(
    "spark.rapids.tpu.sql.autoBroadcastJoinThreshold", 10 * 1024 * 1024,
    "Equi-joins broadcast a side whose plan-time size estimate (a tenth "
    "of the input for each col = literal conjunct of a filter on it; the "
    "measured size from the second planning on) is at or "
    "below this many bytes (build once, probe per shard — ref Spark's "
    "autoBroadcastJoinThreshold + the reference's AQE join-strategy "
    "switching, GpuOverrides.scala:4681). <=0 disables auto selection.",
    commonly_used=True)

JOIN_BLOOM_FILTER = register(
    "spark.rapids.tpu.sql.join.bloomFilter.enabled", False,
    "Build a device bloom filter from the build side's join keys and "
    "pre-filter the stream side before inner/semi hash joins (ref Spark's "
    "InjectRuntimeFilter + spark-rapids-jni BloomFilter).")

JOIN_SUBPARTITION_SIZE = register(
    "spark.rapids.tpu.sql.join.subPartitionSizeBytes", 256 * 1024 * 1024,
    "When the build side of an equi-join (the smaller input, which every "
    "batch of the other side is joined against whole) exceeds this many bytes the join "
    "hash-partitions both sides and runs N independent sub-joins "
    "(ref GpuSubPartitionHashJoin.scala / GpuShuffledSizedHashJoinExec.scala:1255). "
    "<= 0 disables sub-partitioning.")

JOIN_SPECULATIVE_SIZING = register(
    "spark.rapids.tpu.sql.join.speculativeSizing", True,
    "Size join outputs from the input shape bucket instead of syncing the "
    "exact pair count to the host (each sync is a full device round trip). "
    "Sinks validate the real totals once per query and transparently "
    "re-execute with exact sizing if a guess was too small.")

ALLOC_FRACTION = register(
    "spark.rapids.tpu.memory.hbm.allocFraction", 0.85,
    "Fraction of HBM the pool manager budgets for columnar buffers "
    "(ref RapidsConf spark.rapids.memory.gpu.allocFraction).", startup_only=True)

HBM_LIMIT_BYTES = register(
    "spark.rapids.tpu.memory.hbm.limitBytes", 0,
    "Explicit HBM budget in bytes; 0 = derive from device "
    "(ref GpuDeviceManager.computeRmmPoolSize).", startup_only=True)

HOST_SPILL_LIMIT = register(
    "spark.rapids.tpu.memory.host.spillStorageSize", 4 * 1024 * 1024 * 1024,
    "Bytes of host memory for spilled buffers before going to disk "
    "(ref RapidsHostMemoryStore.scala:41).")

OOM_RETRY_ENABLED = register(
    "spark.rapids.tpu.memory.oomRetry.enabled", True,
    "Enable the per-thread OOM retry/split state machine "
    "(ref RmmRapidsRetryIterator.scala:33).")

OOM_MAX_SPLIT_DEPTH = register(
    "spark.rapids.tpu.oom.maxSplitDepth", 8,
    "How many times a single input batch may be halved by the "
    "SplitAndRetryOOM rung of the retry state machine before the "
    "escalation ladder moves on (cross-session pressure spill, then the "
    "OOM_PRESSURE_HOST degradation rung — mem/retry.py, "
    "docs/fault_tolerance.md). Depth 8 means pieces as small as "
    "1/256th of the original batch.")

OOM_HOST_FALLBACK_ENABLED = register(
    "spark.rapids.tpu.oom.hostFallback.enabled", True,
    "Allow the final rung of the OOM escalation ladder: after retries, "
    "splits and a cross-session pressure spill all fail, run the one "
    "starving operator on the host backend under an unbudgeted memory "
    "grant instead of failing the query (recorded as an "
    "OOM_PRESSURE_HOST placement tag and counted by "
    "srtpu_oom_host_fallback_total). Off = the ladder ends in "
    "OutOfDeviceMemory, the pre-r14 behavior.")

ADAPTIVE_ENABLED = register(
    "spark.rapids.tpu.sql.adaptive.enabled", True,
    "Adaptive execution: post-shuffle partition coalescing by observed "
    "partition sizes (ref Spark AQE + GpuCustomShuffleReaderExec).",
    commonly_used=True)

ADAPTIVE_TARGET_BYTES = register(
    "spark.rapids.tpu.sql.adaptive.targetPostShuffleBytes",
    64 * 1024 * 1024,
    "Adaptive coalescing merges consecutive shuffle partitions until this "
    "many bytes (ref spark.sql.adaptive.advisoryPartitionSizeInBytes).")

DEFAULT_SHUFFLE_PARTITIONS = register(
    "spark.rapids.tpu.sql.shuffle.partitions", 8,
    "Partition count for repartition() without an explicit count "
    "(ref spark.sql.shuffle.partitions).")

SHUFFLE_MODE = register(
    "spark.rapids.tpu.shuffle.mode", "MULTITHREADED",
    "MULTITHREADED (host-staged) / ICI (device-resident collective exchange) / "
    "CACHE_ONLY (single-process testing) "
    "(ref RapidsShuffleInternalManagerBase.scala:1264-1276).", commonly_used=True)

SHUFFLE_CODEC = register(
    "spark.rapids.tpu.shuffle.compression.codec", "lz4",
    "Compression for serialized shuffle blocks: lz4 / zstd / none "
    "(ref spark.rapids.shuffle.compression.codec + TableCompressionCodec).")

SHUFFLE_THREADS = register(
    "spark.rapids.tpu.shuffle.multiThreaded.numThreads", 8,
    "Writer/reader threads for the multithreaded shuffle "
    "(ref RapidsShuffleThreadedWriterBase).")

MULTITHREADED_READ_THREADS = register(
    "spark.rapids.tpu.sql.multiThreadedRead.numThreads", 8,
    "Host read thread-pool size for cloud/coalescing file readers "
    "(ref Plugin.scala:269-281).")

IO_PATH_REPLACEMENT = register(
    "spark.rapids.tpu.io.pathReplacementRules", "",
    "Semicolon-separated 'prefix->replacement' rules applied to scan paths "
    "before opening (ref AlluxioUtils.scala s3://->alluxio:// rewriting); "
    "e.g. 's3://bucket->/mnt/alluxio/bucket'.")

PARQUET_READER_TYPE = register(
    "spark.rapids.tpu.sql.format.parquet.reader.type", "AUTO",
    "PERFILE / COALESCING / MULTITHREADED / AUTO "
    "(ref GpuParquetScan.scala reader factory:1070).")

CBO_ENABLED = register(
    "spark.rapids.tpu.sql.optimizer.enabled", True,
    "Cost-based reversion of device subtrees (and whole small-input "
    "queries, which lose to the per-query dispatch+fetch floor) "
    "to the host engine (ref CostBasedOptimizer.scala; "
    "floor model: plan/cost.py DEVICE_QUERY_FLOOR). ON by default since "
    "r3: the engine picks the faster engine per query; tests pin it off "
    "to keep device-path coverage.", commonly_used=True)

CPU_EXEC_COST_PER_ROW = register(
    "spark.rapids.tpu.sql.optimizer.cpu.exec.defaultRowCost", 2.0e-4,
    "CBO default CPU cost s/row (ref RapidsConf.scala:2133).", internal=True)

TPU_EXEC_COST_PER_ROW = register(
    "spark.rapids.tpu.sql.optimizer.tpu.exec.defaultRowCost", 1.0e-4,
    "CBO default TPU cost s/row (ref RapidsConf.scala:2149).", internal=True)

MEMORY_DEBUG = register(
    "spark.rapids.tpu.memory.debug", False,
    "Log every device allocation/free with the running footprint "
    "(ref spark.rapids.memory.gpu.debug=STDOUT, RapidsConf.scala:376).")

LEAK_DETECTION = register(
    "spark.rapids.tpu.memory.leakDetection", False,
    "Debug-mode allocation auditing: every SpillableBatch records its "
    "creation site, and TpuSession.close() raises if any device buffer "
    "registration is still live (ref cudf MemoryCleaner leak tracking at "
    "shutdown, Plugin.scala:573-588). The test suite runs with this "
    "effectively on via its per-test zero-leak fixture.")

METRICS_LEVEL = register(
    "spark.rapids.tpu.sql.metrics.level", "MODERATE",
    "DEBUG / MODERATE / ESSENTIAL metric verbosity (ref GpuExec.scala:54-165).")

STABLE_SORT = register(
    "spark.rapids.tpu.sql.stableSort.enabled", False,
    "Force stable device sorts (ref RapidsConf stableSort).")

IMPROVED_FLOAT_OPS = register(
    "spark.rapids.tpu.sql.improvedFloatOps.enabled", False,
    "Allow float aggregation orderings that can differ from CPU bit-for-bit.")

HAS_NANS = register(
    "spark.rapids.tpu.sql.hasNans", True,
    "Assume float columns may contain NaN (ref RapidsConf spark.rapids.sql.hasNans).")

UDF_COMPILER_ENABLED = register(
    "spark.rapids.tpu.sql.udfCompiler.enabled", False,
    "Translate Python UDF bytecode into columnar expressions at plan time "
    "(ref udf-compiler/, Plugin.scala:122-128).")

SPILL_DIR = register(
    "spark.rapids.tpu.memory.spillDir", "/tmp/srtpu_spill",
    "Directory for disk-tier spill files (ref RapidsDiskStore.scala:38).")

OOM_INJECTION = register(
    "spark.rapids.tpu.memory.oomInjection.mode", "NONE",
    "Test-only fault injection mode (ref RmmSpark.forceRetryOOM test hooks).",
    internal=True)

LORE_DUMP_PATH = register(
    "spark.rapids.tpu.sql.lore.dumpPath", "",
    "When set, operators tagged by lore ids dump input batches for offline "
    "replay (ref lore/GpuLore.scala).")

LORE_IDS = register(
    "spark.rapids.tpu.sql.lore.idsToDump", "",
    "Comma-separated lore ids to dump (ref GpuLore.tagForLore).")

PROFILE_PATH = register(
    "spark.rapids.tpu.profile.pathPrefix", "",
    "When set, capture XLA/TPU profiler traces to this path "
    "(ref profiler.scala ProfilerOnExecutor).")

DELTA_OPTIMIZE_WRITE_TARGET_ROWS = register(
    "spark.rapids.tpu.delta.optimizeWrite.targetRows", 1 << 20,
    "Target rows per output file when delta.autoOptimize.optimizeWrite is set "
    "on a table (ref GpuOptimizeWriteExchangeExec.scala); also the "
    "auto-compaction target size.")

DELTA_AUTO_COMPACT_MIN_FILES = register(
    "spark.rapids.tpu.delta.autoCompact.minNumFiles", 8,
    "Minimum number of sub-target-size files before post-commit "
    "auto-compaction folds them (ref delta autoCompact.minNumFiles).")

SHAPE_BUCKETS = register(
    "spark.rapids.tpu.sql.shapeBuckets", "1024,8192,65536,262144,1048576,4194304",
    "Row-count bucket ladder; batches pad up to the nearest bucket so each "
    "operator compiles once per bucket (TPU-specific, no reference analog — "
    "cudf is shape-dynamic, XLA is not).")

AGG_OPTIMISTIC_GROUPS = register(
    "spark.rapids.tpu.sql.agg.optimisticGroups", 4096,
    "Single-batch aggregations speculatively fetch final results sized "
    "for at most this many groups in ONE device round trip; more groups "
    "fall back to the classic multi-pass pipeline (every extra fetch "
    "is a device round trip).")

WINDOW_HOST_SINK_ROWS = register(
    "spark.rapids.tpu.window.hostSinkRowThreshold", 65536,
    "A terminal window exec whose input has at least this many rows runs "
    "its kernel on the host XLA backend instead of the device: the result "
    "is row-sized and heading to a host collect, so the D2H fetch — not "
    "compute — is what the device path adds (its cost is not measured "
    "on the attached chip). Identical kernel, identical "
    "semantics; 0 disables (ref CostBasedOptimizer transition-cost "
    "reverts, RapidsConf.scala:2126).")

CPU_FALLBACK_ENABLED = register(
    "spark.rapids.tpu.sql.cpuFallback.enabled", True,
    "Allow per-operator CPU fallback (off = fail when a plan node is unsupported).")

TASK_TIMEOUT = register(
    "spark.rapids.tpu.task.semaphore.timeoutSeconds", 600,
    "Max seconds a task waits on the device semaphore before erroring.")

SEMAPHORE_WEDGE_TIMEOUT_MS = register(
    "spark.rapids.tpu.semaphore.wedgeTimeoutMs", 10000,
    "Wedge-watchdog horizon for the device semaphore: a task blocked in "
    "acquire() for this long wakes up, dumps a holder/waiter/held-bytes "
    "diagnostic, and force-releases permits whose holder THREAD is dead "
    "(a killed worker can no longer wedge every later query; counted by "
    "srtpu_semaphore_wedge_total). <= 0 disables the watchdog — waits "
    "block until task.semaphore.timeoutSeconds as before.")

QUERY_TIMEOUT = register(
    "spark.rapids.tpu.query.timeout", 0.0,
    "Whole-query deadline in seconds, enforced by cooperative "
    "cancellation: every operator checks the deadline at each produced "
    "batch (and semaphore waits poll it), so a timed-out query unwinds "
    "through the normal exception path — the device semaphore is "
    "released and every spillable batch is closed (the zero-leak audit "
    "holds). Raises QueryTimeout; counted by srtpu_query_timeout_total. "
    "0 disables (ref spark.sql.broadcastTimeout / spark.network.timeout "
    "query-level analogs).")


class TpuConf:
    """Immutable snapshot of raw key->string (or typed) settings.

    Reference: RapidsConf wraps SQLConf the same way; the driver serializes the
    conf map to executors (Plugin.scala:472) — here sessions pass TpuConf down
    the plan explicitly.
    """

    def __init__(self, raw: Optional[Dict[str, Any]] = None):
        self.raw = dict(raw or {})

    def with_settings(self, **kv) -> "TpuConf":
        new = dict(self.raw)
        for k, v in kv.items():
            new[k] = v
        return TpuConf(new)

    def set(self, key: str, value) -> "TpuConf":
        new = dict(self.raw)
        new[key] = value
        return TpuConf(new)

    def get(self, entry: ConfEntry):
        return entry.get(self)

    # convenience accessors mirroring RapidsConf's vals
    @property
    def sql_enabled(self) -> bool: return self.get(SQL_ENABLED)
    @property
    def explain(self) -> str: return str(self.get(EXPLAIN)).upper()
    @property
    def mode(self) -> str: return self.get(MODE)
    @property
    def concurrent_tpu_tasks(self) -> int: return self.get(CONCURRENT_TPU_TASKS)
    @property
    def batch_size_bytes(self) -> int: return self.get(BATCH_SIZE_BYTES)
    @property
    def batch_size_rows(self) -> int: return self.get(BATCH_SIZE_ROWS)
    @property
    def join_speculative_sizing(self) -> bool:
        return bool(self.get(JOIN_SPECULATIVE_SIZING))
    @property
    def join_subpartition_size_bytes(self) -> int:
        return self.get(JOIN_SUBPARTITION_SIZE)
    @property
    def shuffle_mode(self) -> str: return str(self.get(SHUFFLE_MODE)).upper()
    @property
    def is_explain_only(self) -> bool: return self.get(MODE) == "explainOnly"
    @property
    def shape_buckets(self):
        return [int(x) for x in str(self.get(SHAPE_BUCKETS)).split(",") if x]
    @property
    def cpu_fallback_enabled(self) -> bool: return self.get(CPU_FALLBACK_ENABLED)


DEFAULT = TpuConf()


def generate_docs() -> str:
    """Emit markdown config docs (ref RapidsConf.help() -> docs/configs.md)."""
    out = ["# spark-rapids-tpu configuration", "",
           "Name | Description | Default", "--- | --- | ---"]
    for e in all_entries():
        if e.internal:
            continue
        out.append(f"{e.key} | {e.doc} | {e.default}")
    return "\n".join(out) + "\n"
