"""Two-tier compiled-executable cache (ISSUE 6).

Every fused kernel the engine builds — whole-stage fusion regions
(exec/wholestage.py), projection/filter kernels (exprs/compiler.py),
string-rectangle chains — resolves through this module instead of
jitting ad hoc:

* **in-process tier** — a bounded LRU of live jitted callables keyed on
  (kernel digest, input dtypes, device kind). A repeat query of the
  same shape (new exec objects, same expressions) reuses the SAME
  callable, so jax's own trace cache serves every shape bucket it has
  already seen — zero retrace, zero recompile. This is the layer the
  r5 bench was missing: per-exec kernel dicts died with their query,
  so "warm" runs re-traced everything (string_transforms_100k: 17.3 s
  warm at 0.03x).
* **persistent tier** — JAX's on-disk compilation cache (serialized
  executables, configured process-wide in ``spark_rapids_tpu/__init__``
  and re-pointable per session via ``spark.rapids.tpu.compile.cache.dir``).
  A fresh process pays trace time but ZERO XLA compile for any module a
  previous process compiled. ``compile.cache.maxBytes`` bounds the tier
  with mtime-LRU eviction.

Observability: ``srtpu_compile_*`` metrics (registry inventory +
docs/monitoring.md) count in-process hits/misses, persistent-tier hits
and cumulative backend-compile seconds; the same events emit
``cat="compile"`` trace spans so ``tools/profile`` can attribute
cold-start time honestly. Both ride jax.monitoring, so they measure the
REAL XLA compile, not the (instant) jit-closure construction.

The blessed-modules contract is enforced by the ``adhoc-jit`` tpulint
rule: a ``jax.jit`` call site outside the compiler/cache modules
bypasses this cache and silently re-introduces per-query recompiles.
"""
from __future__ import annotations

import hashlib
import os
import threading
import time
from collections import OrderedDict
from typing import Callable, Dict, Optional, Tuple

from ..config import register

__all__ = ["COMPILE_CACHE_DIR", "COMPILE_CACHE_MAX_BYTES",
           "get_or_build", "fused_key", "stats", "hit_rate",
           "reset_stats", "clear", "configure_from_conf",
           "trim_persistent", "device_kind", "record_plan_compiled",
           "plan_digest_cached", "compile_free_since"]

COMPILE_CACHE_DIR = register(
    "spark.rapids.tpu.compile.cache.dir", "",
    "Directory for the persistent compiled-executable tier (JAX's "
    "on-disk compilation cache: serialized XLA executables keyed by "
    "module fingerprint). Empty keeps the process default (the fixed "
    ".srtpu_cache/xla directory inside the checkout). Ignored when the "
    "JAX_COMPILATION_CACHE_DIR environment variable is set: the "
    "environment then owns the location and no engine code sets one. "
    "Point every serving process of a fleet at a shared directory so a "
    "repeat query pays zero compile even in a fresh process "
    "(docs/tuning.md).",
    commonly_used=True)

COMPILE_CACHE_MAX_BYTES = register(
    "spark.rapids.tpu.compile.cache.maxBytes", 4 * 1024 * 1024 * 1024,
    "Size budget for the persistent executable tier; when exceeded the "
    "oldest entries (file mtime) are evicted after a compile writes new "
    "ones. <= 0 disables eviction (unbounded).", commonly_used=True)

#: in-process tier bound: distinct fused kernels alive at once. Each
#: entry is one Python callable (the executables behind it are owned by
#: jax's caches, which the test harness clears per module).
_LRU_MAX = 512

_LOCK = threading.Lock()
_LRU: "OrderedDict[Tuple, Callable]" = OrderedDict()  # tpulint: guarded-by _LOCK
# tpulint: guarded-by _LOCK
_STATS: Dict[str, float] = {"hits": 0, "misses": 0,
                            "persistent_hits": 0, "compile_s": 0.0}

#: last persistent-tier trim PER DIRECTORY, debounced (an eviction walk
#: per compile burst, not per kernel; two sessions on different dirs
#: must not consume each other's debounce window)
_LAST_TRIM: Dict[str, float] = {}    # tpulint: guarded-by _LOCK
_TRIM_DEBOUNCE_S = 30.0

#: callbacks invoked by clear(): front memos layered over this cache
#: (exprs/compiler._FRONT) register here so dropping the tier actually
#: releases every strong reference
_CLEAR_HOOKS = []                    # tpulint: guarded-by _LOCK

#: the process-default cache dir, captured before any session override:
#: a session with an EMPTY compile.cache.dir conf must get this default
#: back, not whichever directory the previous session pointed jax at
_PROC_DEFAULT_DIR = [None]           # tpulint: guarded-by _LOCK

#: plan digests (metrics/events.plan_digest) whose device execution
#: completed — every kernel the plan builds now lives in the in-process
#: tier and (serialized) in jax's persistent tier, so a repeat of the
#: digest pays the dispatch floor only, never the compile floor. The
#: set persists with the adaptive stats (plan/stats_store.py "plans"),
#: giving a fresh process the same warm-floor costing the persistent
#: executable tier gives it warm kernels. Keyed per device kind: an
#: executable compiled for one backend says nothing about another.
#: A dict-as-ordered-set (values unused): insertion order is the
#: recency proxy, so the cap evicts the OLDEST digest, never an
#: arbitrary hot one (the _ENGINE_WALLS idiom).
_PLAN_DIGESTS: dict = {}             # tpulint: guarded-by _LOCK
_PLAN_DIGESTS_MAX = 4096


def record_plan_compiled(digest: str) -> None:
    """Mark a plan digest's executables as resident in the cache tiers
    (called after a successful device execution of the plan)."""
    if not digest:
        return
    key = (str(digest), device_kind())
    with _LOCK:
        if key in _PLAN_DIGESTS:
            # refresh recency (move to end): a hot serving plan that
            # re-runs every second must not age into the "oldest" slot
            # just because it was registered first. No mark_dirty — the
            # SET is unchanged, only its order, not worth a save per
            # repeat query.
            _PLAN_DIGESTS.pop(key)
            _PLAN_DIGESTS[key] = None
            return
        # while, not if: a persisted-stats merge (load_into) can leave
        # the set over the cap, and delete-one-insert-one would keep it
        # there forever
        while len(_PLAN_DIGESTS) >= _PLAN_DIGESTS_MAX:
            del _PLAN_DIGESTS[next(iter(_PLAN_DIGESTS))]
        _PLAN_DIGESTS[key] = None
    from .cost import _persist_enabled
    if _persist_enabled():
        from . import stats_store
        stats_store.mark_dirty()


def warm_digests() -> list:
    """Snapshot of the warm (digest, device-kind) pairs, taken under
    the lock — the stats_store persist path must not iterate the live
    dict while record_plan_compiled mutates it."""
    with _LOCK:
        return list(_PLAN_DIGESTS)


def plan_digest_cached(digest: str) -> bool:
    """True when a previous device run of this plan digest (this process
    or, via the persisted stats, an earlier one sharing the cache dirs)
    left its executables warm — the planner's cache-aware floor check."""
    if not digest:
        return False
    from .cost import load_persisted_stats
    load_persisted_stats()
    with _LOCK:
        return (str(digest), device_kind()) in _PLAN_DIGESTS


def _invalidate_plan_digests() -> None:
    """Drop the warm-digest set because the persistent tier changed
    under it (trim eviction, cache-dir re-point): a digest must never
    vouch for executables that are no longer there — the planner would
    charge the dispatch floor to a plan about to pay a full cold
    compile. Conservative by design (clear()'s contract): the cold
    floor re-applies until a device run proves the kernels warm again."""
    with _LOCK:
        if not _PLAN_DIGESTS:
            return
        _PLAN_DIGESTS.clear()
    try:
        from .cost import _persist_enabled
        if _persist_enabled():
            from . import stats_store
            stats_store.mark_dirty()
    except Exception:  # pragma: no cover - persistence is best-effort
        pass


def compile_free_since(snapshot: dict) -> bool:
    """True when zero in-process cache misses AND zero backend-compile
    seconds accrued since ``snapshot`` (an earlier ``stats()`` result) —
    THE definition of a compile-free run, the only kind the learned
    cost model ingests (cost.record_engine_wall / record_op_wall). One
    helper so every feed site keys on the same counters."""
    now = stats()
    return (now["compile_s"] == snapshot["compile_s"]
            and now["misses"] == snapshot["misses"])


def device_kind() -> str:
    """Platform component of every cache key: an executable compiled
    for one backend must never be served to another."""
    try:
        import jax
        return jax.default_backend()
    except Exception:  # pragma: no cover - broken backend environments
        return "unknown"


def fused_key(digest: str, schema_sig: Tuple, extra: Tuple = ()) -> Tuple:
    """Cache key for a compiled region: (plan digest, input dtypes,
    device kind[, extras]). Shape buckets are NOT part of the key — the
    cached callable is a jitted function that re-specializes per static
    shape internally, so one entry serves every bucket."""
    return (digest, schema_sig, device_kind()) + tuple(extra)


def digest_of(*parts: str) -> str:
    """Stable short digest over structural signature strings (the
    PR-5 plan-digest idiom applied to physical kernel signatures)."""
    h = hashlib.sha256()
    for p in parts:
        h.update(p.encode("utf-8"))
        h.update(b"\x00")
    return h.hexdigest()[:16]


def get_or_build(key: Tuple, build: Callable[[], Callable],
                 label: str = "kernel") -> Callable:
    """Resolve ``key`` in the in-process tier; on miss, run ``build``
    (which must return the jitted callable) under a ``cat="compile"``
    trace span and insert it. Thread-safe; a racing duplicate build is
    harmless (last insert wins, both callables are equivalent)."""
    with _LOCK:
        fn = _LRU.get(key)
        if fn is not None:
            _LRU.move_to_end(key)
            _STATS["hits"] += 1
            hit = True
        else:
            _STATS["misses"] += 1
            hit = False
    if hit:
        _registry_inc("srtpu_compile_cache_hits_total")
        return fn
    _registry_inc("srtpu_compile_cache_misses_total")
    from ..trace import core as trace_core
    tr = trace_core.TRACER
    t0 = tr.now() if tr is not None else 0
    fn = build()
    if tr is not None:
        tr.complete(f"compile.build.{label}", t0, cat="compile",
                    args={"key": str(key[0])})
    with _LOCK:
        _LRU[key] = fn
        while len(_LRU) > _LRU_MAX:
            _LRU.popitem(last=False)
    return fn


def get_or_build_jit(name: str, fn: Callable, **jit_kwargs) -> Callable:
    """Blessed ``jax.jit`` wrapper for NAMED module-level kernels: the
    compiled callable resolves through the in-process tier keyed on
    (name, jit options, device kind), so every holder shares one
    callable and the ``srtpu_compile_*`` metrics see the compile.  This
    is the migration target for the grandfathered ad-hoc
    ``jax.jit(module_fn)`` sites the ``adhoc-jit`` rule tracks
    (docs/static_analysis.md)."""
    import jax

    def build():
        return jax.jit(fn, **jit_kwargs)

    # jit options are part of the identity: two sites sharing a name
    # but differing in e.g. donate_argnums must not share a callable
    opts = tuple(sorted((k, repr(v)) for k, v in jit_kwargs.items()))
    return get_or_build(fused_key(name, opts), build, label=name)


def stats() -> Dict[str, float]:
    """Copy of the process-lifetime cache counters (exec/query.py diffs
    these around each query for its compile seconds)."""
    with _LOCK:
        return dict(_STATS)


def hit_rate() -> Optional[float]:
    """In-process tier hit rate over the process lifetime, or None
    before the first lookup — the ops ``/healthz`` exec-cache verdict
    input (a warm serving process living below ~0.5 is recompiling
    kernels it should be reusing)."""
    st = stats()
    lookups = st["hits"] + st["misses"]
    return (st["hits"] / lookups) if lookups else None


def reset_stats() -> None:
    with _LOCK:
        for k in _STATS:
            _STATS[k] = 0 if k != "compile_s" else 0.0


def register_clear_hook(fn: Callable[[], None]) -> None:
    """Register a callback run by clear() — front memos layered over
    this cache use it so clear() releases THEIR references too.
    Idempotent per callback."""
    with _LOCK:
        if fn not in _CLEAR_HOOKS:
            _CLEAR_HOOKS.append(fn)


def clear() -> None:
    """Drop the in-process tier, every registered front memo, and the
    warm-plan-digest set (tests; the persistent tier survives — dropping
    the digests is conservative: the planner re-applies the cold floor
    until a run proves the kernels warm again)."""
    with _LOCK:
        _LRU.clear()
        _PLAN_DIGESTS.clear()
        hooks = list(_CLEAR_HOOKS)
    for fn in hooks:
        fn()


def _registry_inc(name: str, amount=1) -> None:
    from ..metrics.registry import REGISTRY
    if REGISTRY is not None:
        REGISTRY.counter(name).inc(amount)


# ---------------------------------------------------------------------------
# persistent tier: conf hookup + size budget
# ---------------------------------------------------------------------------

def configure_from_conf(conf) -> Optional[str]:
    """Point jax's persistent compilation cache at the conf'd directory
    (when set) and schedule a size trim. One conf lookup per
    ExecContext construction — the metrics/tracer installation pattern.
    Returns the active cache dir (or None when persistence is off)."""
    import jax

    from .. import compile_cache_dir_is_external
    cur = jax.config.jax_compilation_cache_dir
    # check-then-set under the lock: two ExecContexts constructed
    # concurrently must agree on ONE process default, not race to
    # capture each other's override as "the default"
    with _LOCK:
        if _PROC_DEFAULT_DIR[0] is None:
            _PROC_DEFAULT_DIR[0] = cur or ""
        default_dir = _PROC_DEFAULT_DIR[0]
    want = (str(conf.get(COMPILE_CACHE_DIR) or "").strip()
            or default_dir)
    if compile_cache_dir_is_external():
        # JAX_COMPILATION_CACHE_DIR placed the cache from outside: the
        # conf override stands down with the import-time default
        want = cur or ""
    if want != (cur or ""):
        try:
            jax.config.update("jax_compilation_cache_dir", want or None)
            cur = want
            # the persistent tier the warm digests vouch for just moved
            _invalidate_plan_digests()
        except Exception:  # pragma: no cover - cache is an optimization
            pass
    if cur:
        max_bytes = int(conf.get(COMPILE_CACHE_MAX_BYTES))
        now = time.monotonic()
        # the debounce check-then-set is atomic, or two concurrent
        # sessions both pass the window test and stat-walk the (shared,
        # possibly NFS) cache dir twice
        with _LOCK:
            due = max_bytes > 0 and \
                now - _LAST_TRIM.get(cur, 0.0) >= _TRIM_DEBOUNCE_S
            if due:
                _LAST_TRIM[cur] = now
        if due:
            # background thread: the stat walk of a large shared cache
            # dir (possibly NFS) must not block query start — this is
            # called from ExecContext construction
            threading.Thread(target=trim_persistent,
                             args=(cur, max_bytes), daemon=True,
                             name="srtpu-exec-cache-trim").start()
    return cur or None


def trim_persistent(cache_dir: str, max_bytes: int) -> int:
    """Evict oldest-mtime files until the directory fits ``max_bytes``.
    Returns the number of files removed. Tolerates concurrent writers,
    unreadable/corrupt entries and vanished files — eviction is an
    optimization and must never raise into a query."""
    removed = 0
    try:
        entries = []
        for dirpath, _dirs, files in os.walk(cache_dir):
            for fn in files:
                p = os.path.join(dirpath, fn)
                try:
                    st = os.stat(p)
                    entries.append((st.st_mtime, st.st_size, p))
                except OSError:
                    continue
        total = sum(s for _, s, _ in entries)
        if total <= max_bytes:
            return 0
        for _mt, size, p in sorted(entries):
            try:
                os.unlink(p)
                removed += 1
                total -= size
            except OSError:
                continue
            if total <= max_bytes:
                break
    except OSError:  # pragma: no cover - directory races
        pass
    if removed:
        # which plans lost executables is unknowable at file level —
        # drop every warm digest rather than let one vouch for a
        # compile the evicted entries no longer cover
        _invalidate_plan_digests()
    return removed


# ---------------------------------------------------------------------------
# compile-time accounting: jax.monitoring bridge
# ---------------------------------------------------------------------------
# XLA compiles lazily at first dispatch, so build() timing above would
# read ~0. jax emits monitoring events around the REAL work:
#   /jax/core/compile/backend_compile_duration   — seconds of XLA compile
#   /jax/compilation_cache/cache_hits            — persistent-tier reads
# The listeners are registered once at import and cost one dict update
# per COMPILE (never per batch); metric mirroring is one branch when the
# registry is off — the trace/metrics disabled-path contract.

_LISTENERS_ON = [False]


def _on_event(event: str, **kw) -> None:
    if event == "/jax/compilation_cache/cache_hits":
        with _LOCK:
            _STATS["persistent_hits"] += 1
        _registry_inc("srtpu_compile_persistent_hits_total")


def _on_duration(event: str, duration: float, **kw) -> None:
    if event == "/jax/core/compile/backend_compile_duration":
        with _LOCK:
            _STATS["compile_s"] += float(duration)
        _registry_inc("srtpu_compile_seconds_total", float(duration))
        from ..trace import core as trace_core
        tr = trace_core.TRACER
        if tr is not None:
            t1 = tr.now()
            tr.complete("compile.backend", t1 - int(duration * 1e9), t1,
                        cat="compile", args={"seconds": round(duration, 4)})


def _install_listeners() -> None:
    if _LISTENERS_ON[0]:
        return
    try:
        from jax import monitoring
        monitoring.register_event_listener(_on_event)
        monitoring.register_event_duration_secs_listener(_on_duration)
        _LISTENERS_ON[0] = True
    except Exception:  # pragma: no cover - accounting only, never fatal
        pass


_install_listeners()
