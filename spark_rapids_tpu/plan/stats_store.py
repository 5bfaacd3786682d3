"""On-disk persistence for adaptive planning statistics (VERDICT r3 #7).

The cost model learns measured whole-query walls per engine placement
(`_ENGINE_WALLS`) and measured output row counts per plan-subtree signature
(`_RUNTIME_ROWS`) — the reference's AQE stage statistics
(GpuOverrides.scala:4691-4730) generalized across queries. Until r4 those
lived only in process memory, so every cold process re-paid each
misprediction (a 2.2 s device detour on TPC-DS q3 before the measured-wall
flip). Plan signatures are content-addressed (cost._fingerprint_table), so
they mean the same thing in the next process; this module gives them the
same lifetime the XLA compile cache gives kernels.

Format: one JSON file beside the default compile-cache directory
(``spark_rapids_tpu.STATE_DIR``, inside the checkout; ``SRTPU_STATS_PATH``
relocates it) —
  {"version": 2, "walls": [[sig, placement, count, min_s], ...],
   "rows": [[sig, rows], ...],
   "ops": [[op_kind, placement, rows, seconds], ...],
   "plans": [[plan_digest, device_kind], ...]}
("ops" are the learned per-operator row costs, cost.record_op_wall;
"plans" the compiled-plan-digest set behind the cache-aware device
floor, exec_cache.record_plan_compiled; older files without either key
load fine.) Version 2 records COMPILE-FREE observation counts (trusted
at >=1); version-1 files recorded raw counts whose first observation
could embed a full XLA compile, so their counts load as count-1 — a v1
single-observation wall stays untrusted (the old >=2 rule preserved),
a v1 multi-observation wall stays trusted. v1 "ops" quotients (no
compile-free keying, not subtractable) are dropped entirely.
Writes are atomic (tmp + rename) and debounced; entries are capped with
insertion order as the recency proxy. Process-local signatures (the
"#<id>#" fallback for non-Arrow sources) are never persisted.
"""
from __future__ import annotations

import atexit
import json
import os
import re
import threading
import time

_CAP = 2048
_DEBOUNCE_S = 5.0
_LOCAL_TAG = re.compile(r"#\d+#")

_lock = threading.Lock()
#: serializes whole-file writes: two concurrent save()s could otherwise
#: os.replace in snapshot-age order reversed, persisting the STALER one
#: while both clear _dirty (the fresher data then never lands). Always
#: taken BEFORE _lock, never while holding it.
_save_lock = threading.Lock()
_loaded = False      # tpulint: guarded-by _lock
_dirty = False       # tpulint: guarded-by _lock
_last_save = 0.0     # tpulint: guarded-by _lock


def _path() -> str:
    p = os.environ.get("SRTPU_STATS_PATH")
    if p:
        return os.path.expanduser(p)
    from .. import STATE_DIR
    return os.path.join(STATE_DIR, "adaptive_stats.json")


def store_path() -> str:
    """Public location of the adaptive-stats file — siblings (the
    regression sentinel's baseline table, ops/sentinel.py) persist in
    the same directory so one SRTPU_STATS_PATH override relocates the
    whole learned-state family."""
    return _path()


def _persistable(sig: str) -> bool:
    return not _LOCAL_TAG.search(sig)


def load_into(walls: dict, rows: dict, ops: dict = None,
              plans: dict = None) -> None:
    """Merge persisted stats into the live dicts (live entries win).
    Corrupt or truncated files are tolerated — the caller starts with a
    fresh table, never a crash (adaptive stats are an optimization)."""
    global _loaded
    with _lock:
        if _loaded:
            return
        _loaded = True
    try:
        with open(_path()) as f:
            j = json.load(f)
    except (OSError, ValueError):
        return
    version = j.get("version") if isinstance(j, dict) else None
    if version not in (1, 2):
        return
    # v1 wall counts include the (possibly compile-poisoned) first
    # observation — discount it so the lowered >=1 trust threshold can
    # never retroactively trust a stale single-compile-run wall
    discount = 1 if version == 1 else 0
    try:
        for sig, placement, cnt, s in j.get("walls", []):
            k = (sig, placement)
            if k not in walls:
                walls[k] = (max(int(cnt) - discount, 0), float(s))
        for sig, n in j.get("rows", []):
            if sig not in rows:
                rows[sig] = int(n)
        if ops is not None and version >= 2:
            # learned per-operator row costs (cost.record_op_wall): a
            # fresh process prices operators from previously-measured
            # walls, device AND host. v1 "ops" entries are DROPPED, not
            # discounted: unlike walls (count-keyed, so one poisoned
            # observation can be subtracted) they are accumulated
            # (rows, seconds) quotients recorded with no compile-free
            # keying — a cold 17s-compile fused run baked into a v1
            # quotient would load straight into trusted territory
            for kind, placement, r, s in j.get("ops", []):
                k = (kind, placement)
                if k not in ops:
                    ops[k] = (int(r), float(s))
        if plans is not None:
            # compiled plan digests (exec_cache.record_plan_compiled):
            # a fresh process applies the warm dispatch-only floor to
            # every shape whose executables the persistent compile
            # cache already holds
            for ent in j.get("plans", []):
                if isinstance(ent, (list, tuple)) and len(ent) == 2:
                    plans.setdefault((str(ent[0]), str(ent[1])))
    except (TypeError, ValueError):
        # malformed entries mid-file: keep whatever merged cleanly
        return


def mark_dirty() -> None:
    global _dirty
    now = time.monotonic()
    # flag-set and debounce check are atomic: two writers racing here
    # could both read a stale _last_save and double-save (harmless) or
    # interleave with save()'s flag reset and LOSE the dirty mark (a
    # dropped persist)
    with _lock:
        _dirty = True
        due = now - _last_save >= _DEBOUNCE_S
    if due:
        save()


def save() -> None:
    global _dirty, _last_save
    # tpulint: disable=lock-discipline — lock-free by design: racy
    # early-out double-check; re-checked under _save_lock below
    if not _dirty:
        return
    with _save_lock:
        _save_serialized()


def _save_serialized() -> None:
    """The body of save(), holding _save_lock: snapshot, write,
    flag-reset happen as one unit so a staler snapshot can never
    overwrite a fresher file."""
    global _dirty, _last_save
    with _lock:
        if not _dirty:
            return
        # claim the flag BEFORE snapshotting: a record_* that dirties
        # the stats mid-write re-marks and the NEXT save persists it,
        # instead of this save clearing a mark its snapshot missed
        _dirty = False
    from . import cost, exec_cache
    # merge the on-disk state first: a process that never planned (e.g.
    # optimizer disabled) would otherwise TRUNCATE the accumulated store
    # to just its own entries on the first debounced save
    cost.load_persisted_stats()
    # cost's dicts have no lock of their own (their writers are the
    # query threads) and _PLAN_DIGESTS is guarded by exec_cache._LOCK,
    # not ours — so snapshot each under the right regime: the digests
    # through exec_cache's locked accessor, the cost dicts with a
    # bounded retry on the resize-mid-iteration race
    for _attempt in range(4):
        try:
            walls = [[sig, pl, c, s]
                     for (sig, pl), (c, s) in
                     list(cost._ENGINE_WALLS.items())
                     if _persistable(sig)][-_CAP:]
            rows = [[sig, n] for sig, n in
                    list(cost._RUNTIME_ROWS.items())
                    if _persistable(sig)][-_CAP:]
            ops = [[kind, pl, r, s]
                   for (kind, pl), (r, s) in list(cost._OP_COSTS.items())]
            break
        except RuntimeError:     # dict changed size during iteration
            continue
    else:
        with _lock:
            _dirty = True        # keep the claim; try again next time
        return
    # insertion order IS the recency order (record_plan_compiled
    # refreshes repeats to the end), so persist it — sorting would
    # replace recency with lexicographic order on reload — and keep
    # the NEWEST entries when over the cap (the walls idiom)
    plans = [[dig, dk] for dig, dk in
             exec_cache.warm_digests()][-exec_cache._PLAN_DIGESTS_MAX:]
    path = _path()
    tmp = path + f".tmp{os.getpid()}"
    try:
        os.makedirs(os.path.dirname(path), exist_ok=True)
        with open(tmp, "w") as f:
            json.dump({"version": 2, "walls": walls, "rows": rows,
                       "ops": ops, "plans": plans}, f)
        os.replace(tmp, path)
        with _lock:
            _last_save = time.monotonic()
    except OSError:
        with _lock:
            _dirty = True        # nothing landed; keep the data claimed
        try:
            os.unlink(tmp)
        except OSError:
            pass


atexit.register(save)
