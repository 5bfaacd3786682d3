"""Per-digest regression sentinel (ISSUE 15).

Reference analog: the qualification/profiling tools' run-over-run diffs
— promoted from an offline CLI to a live check. Every ``queryEnd``
folds into a rolling per-plan-digest baseline (median warm wall,
cumulative compile seconds, placement verdict, max OOM-ladder rung) and
is compared against it FIRST, so a regression pages on the query that
regressed, not at the next manual diff:

* ``warm_slowdown``   — a compile-free run of a digest with >=
  ``sentinel.minSamples`` baselined walls took more than
  ``sentinel.wallFactor`` x the baseline median;
* ``verdict_flip``    — a digest whose baseline verdict is ``device``
  planned ``host`` (the "nothing silently reverts" check, ROADMAP
  item 1) — fires the flight recorder's ``placement_revert`` trigger;
* ``rung_escalation`` — a digest that never escalated past rung 2
  reached the cross-session pressure spill (rung 3) or the host
  degradation rung (rung 4);
* ``tail_regression``  — a compile-free ok run exceeded
  ``sentinel.tailFactor`` x the digest's baselined p99 (a rolling
  relative-error sketch, metrics/sketch.py). The median-based
  ``warm_slowdown`` is blind to a digest whose typical wall is fine
  but whose tail stretched; this is the per-digest half of the SLO
  layer (ISSUE 20, ops/slo.py).

Each flag increments ``srtpu_query_regressions_total{kind=...}`` and
fires the flight recorder. Baselines persist beside the adaptive stats
store (plan/stats_store.py) so a fresh serving process inherits its
predecessor's notion of normal; ``tools/regress`` replays an event log
through the SAME fold (``fold_record``) into a deterministic report.

Baselines are *rolling*: the flagged run still enters the window, so a
genuine persistent change re-baselines after ~``sentinel.window`` runs
(one page, not a permanent alarm) — the flight rate limiter bounds the
bundle volume in between.
"""
from __future__ import annotations

import json
import logging
import os
import threading
from typing import Dict, List, Optional

from ..config import register

__all__ = ["RegressionSentinel", "fold_record", "REGRESSION_KINDS",
           "install_sentinel", "ensure_sentinel_from_conf",
           "active_sentinel", "default_baselines_path",
           "SENTINEL_ENABLED", "SENTINEL_WALL_FACTOR",
           "SENTINEL_MIN_SAMPLES", "SENTINEL_WINDOW", "SENTINEL_PATH",
           "SENTINEL_TAIL_FACTOR"]

log = logging.getLogger(__name__)

SENTINEL_ENABLED = register(
    "spark.rapids.tpu.sentinel.enabled", False,
    "Fold every queryEnd into per-plan-digest rolling baselines (median "
    "warm wall, compile seconds, placement verdict, OOM-ladder rung; "
    "persisted beside the adaptive stats store) and flag regressions — "
    "warm-digest slowdowns past sentinel.wallFactor, device->host "
    "verdict flips, new rung-3+ escalations — via "
    "srtpu_query_regressions_total and the flight recorder "
    "(ops/sentinel.py, docs/ops.md).", commonly_used=True)

SENTINEL_WALL_FACTOR = register(
    "spark.rapids.tpu.sentinel.wallFactor", 3.0,
    "A compile-free run slower than this multiple of the digest's "
    "baseline median wall is flagged as a warm_slowdown regression.")

SENTINEL_MIN_SAMPLES = register(
    "spark.rapids.tpu.sentinel.minSamples", 3,
    "Baselined walls required before the warm_slowdown check engages "
    "for a digest (fewer and the median is noise).")

SENTINEL_WINDOW = register(
    "spark.rapids.tpu.sentinel.window", 32,
    "Rolling window of per-digest walls the baseline median is computed "
    "over; a genuine persistent change re-baselines after this many "
    "runs.")

SENTINEL_PATH = register(
    "spark.rapids.tpu.sentinel.path", "",
    "Baseline persistence file; empty uses sentinel_baselines.json "
    "beside the adaptive stats store (SRTPU_STATS_PATH directory).")

SENTINEL_TAIL_FACTOR = register(
    "spark.rapids.tpu.sentinel.tailFactor", 2.0,
    "A compile-free run slower than this multiple of the digest's "
    "baselined p99 (rolling quantile sketch) is flagged as a "
    "tail_regression — the tail-latency analog of sentinel.wallFactor "
    "(docs/ops.md).")

#: closed regression taxonomy (docs/ops.md)
REGRESSION_KINDS = ("warm_slowdown", "verdict_flip", "rung_escalation",
                    "tail_regression")

#: persist baselines at most every N clean folds (every regression
#: persists immediately) — durability without a whole-table JSON
#: serialization on every query's completion path
_SAVE_EVERY_FOLDS = 16

#: the process-global sentinel; ``None`` means the sentinel is OFF and
#: the queryEnd site costs exactly one attribute load + branch
SENTINEL: Optional["RegressionSentinel"] = None


def default_baselines_path() -> str:
    from ..plan import stats_store
    return os.path.join(os.path.dirname(stats_store.store_path()),
                        "sentinel_baselines.json")


def _median(xs: List[float]) -> float:
    s = sorted(xs)
    n = len(s)
    if n == 0:
        return 0.0
    mid = n // 2
    return s[mid] if n % 2 else (s[mid - 1] + s[mid]) / 2.0


def fold_record(baselines: Dict[str, dict], rec: dict, *,
                wall_factor: float = 3.0, min_samples: int = 3,
                window: int = 32, tail_factor: float = 2.0) -> List[dict]:
    """Fold ONE query record into ``baselines`` (mutated in place) and
    return the regressions it triggered. Pure and deterministic — the
    single code path shared by the live sentinel and the
    ``tools/regress`` event-log replay.

    ``rec`` keys: ``digest`` (required), ``wallMs``, ``verdict``
    (``device``/``host``), ``rung`` (max OOM-ladder rung reached),
    ``ok``, ``compileS`` (backend-compile seconds paid — a run that
    compiled is cold, so it neither trips nor feeds the warm-wall
    window)."""
    digest = rec.get("digest")
    if not digest:
        return []
    digest = str(digest)
    wall = rec.get("wallMs")
    verdict = rec.get("verdict")
    rung = int(rec.get("rung") or 0)
    ok = bool(rec.get("ok", True))
    compile_free = float(rec.get("compileS") or 0.0) == 0.0
    b = baselines.get(digest)
    regs: List[dict] = []
    if b is not None:
        med = _median(b.get("walls") or [])
        if (ok and compile_free and wall is not None
                and len(b.get("walls") or []) >= min_samples
                and med > 0 and float(wall) > wall_factor * med):
            regs.append({"kind": "warm_slowdown", "digest": digest,
                         "wallMs": round(float(wall), 3),
                         "medianMs": round(med, 3),
                         "factor": round(float(wall) / med, 2)})
        if verdict == "host" and b.get("verdict") == "device":
            regs.append({"kind": "verdict_flip", "digest": digest,
                         "from": "device", "to": "host"})
        if rung >= 3 and int(b.get("maxRung") or 0) < 3:
            regs.append({"kind": "rung_escalation", "digest": digest,
                         "rung": rung,
                         "baselineRung": int(b.get("maxRung") or 0)})
        # per-digest p99 check: the median is blind to a stretched tail
        # (ISSUE 20). The flagged wall still folds into the sketch
        # below, so a persistent shift re-baselines like the median.
        if ok and compile_free and wall is not None and b.get("tail"):
            from ..metrics.sketch import QuantileSketch
            sk = QuantileSketch.from_json(b["tail"])
            p99 = sk.quantile(0.99)
            if (sk.count >= min_samples and p99 > 0
                    and float(wall) > tail_factor * p99):
                regs.append({"kind": "tail_regression", "digest": digest,
                             "wallMs": round(float(wall), 3),
                             "p99Ms": round(p99, 3),
                             "factor": round(float(wall) / p99, 2)})
    if b is None:
        b = baselines[digest] = {"walls": [], "verdict": None,
                                 "maxRung": 0, "compileS": 0.0, "n": 0,
                                 "highRungs": 0, "warmSlowdowns": 0}
    if ok and compile_free and wall is not None:
        b["walls"] = (b.get("walls") or []) + [round(float(wall), 3)]
        b["walls"] = b["walls"][-max(1, int(window)):]
        # rolling tail sketch (JSON-able — rides baseline persistence);
        # .get-defaulted so pre-ISSUE-20 baselines keep folding. A
        # sketch has no eviction, so decay by halving bin counts once
        # it holds 4x the wall window: old observations lose weight
        # deterministically and a persistent tail shift re-baselines
        # within ~2 windows instead of never.
        from ..metrics.sketch import QuantileSketch
        sk = QuantileSketch.from_json(b.get("tail") or {})
        sk.observe(float(wall))
        if sk.count >= 4 * max(1, int(window)):
            sk.bins = {k: c // 2 for k, c in sk.bins.items() if c // 2}
            sk.zero_count //= 2
            sk.count = sk.zero_count + sum(sk.bins.values())
            sk.sum /= 2.0
        b["tail"] = sk.to_json()
    if verdict in ("device", "host"):
        b["verdict"] = verdict
    b["maxRung"] = max(int(b.get("maxRung") or 0), rung)
    # AQE feedback counters (ISSUE 19, aqe/feedback.py): how OFTEN this
    # digest hit the pressure-spill rung or a warm slowdown — maxRung
    # says "ever", the feedback loop needs "repeatedly". .get-defaulted
    # so baselines persisted before these keys existed keep folding.
    if rung >= 3:
        b["highRungs"] = int(b.get("highRungs") or 0) + 1
    if any(r["kind"] == "warm_slowdown" for r in regs):
        b["warmSlowdowns"] = int(b.get("warmSlowdowns") or 0) + 1
    b["compileS"] = round(float(b.get("compileS") or 0.0)
                          + float(rec.get("compileS") or 0.0), 4)
    b["n"] = int(b.get("n") or 0) + 1
    return regs


class RegressionSentinel:
    """Thread-safe live fold over the shared baseline table, with
    best-effort atomic persistence and metric/flight fan-out."""

    def __init__(self, path: str, wall_factor: float = 3.0,
                 min_samples: int = 3, window: int = 32,
                 tail_factor: float = 2.0):
        self.path = str(path)
        self.wall_factor = float(wall_factor)
        self.min_samples = int(min_samples)
        self.window = int(window)
        self.tail_factor = float(tail_factor)
        self._lock = threading.Lock()
        #: serializes whole-file persists: two concurrent save()s share
        #: one pid-derived tmp name, so an unserialized pair could
        #: os.replace a half-written file over the baselines (the
        #: stats_store._save_lock idiom). Taken BEFORE _lock, never
        #: while holding it.
        self._save_lock = threading.Lock()
        self._baselines: Dict[str, dict] = {}  # tpulint: guarded-by _lock
        #: regressions flagged this process, oldest first (ops /healthz)
        self.flagged: List[dict] = []          # tpulint: guarded-by _lock
        self._folds_since_save = 0             # tpulint: guarded-by _lock
        self._load()

    # ------------------------------------------------------- persistence
    def _load(self) -> None:
        try:
            with open(self.path, encoding="utf-8") as f:
                doc = json.load(f)
            if isinstance(doc, dict) and isinstance(
                    doc.get("digests"), dict):
                with self._lock:
                    self._baselines = {str(k): dict(v) for k, v
                                       in doc["digests"].items()
                                       if isinstance(v, dict)}
        except (OSError, ValueError):
            # absent or corrupt baselines: start fresh — the sentinel
            # must never fail a query over its own persistence
            pass

    # tpulint: never-raise
    def save(self) -> bool:
        """Atomic best-effort persist (tmp + replace, serialized by
        ``_save_lock``); returns False on failure, never raises.

        The catch is deliberately ``Exception``, not just ``OSError``: a
        baseline record that picked up a non-JSON value (a numpy scalar
        riding in through a folded query record) makes ``json.dump``
        raise ``TypeError``, and that must degrade to an unsaved
        baseline, not fail the query-completion path that called
        ``fold``."""
        with self._save_lock:
            with self._lock:
                doc = {"digests": {k: dict(v) for k, v
                                   in self._baselines.items()}}
                self._folds_since_save = 0
            tmp = f"{self.path}.tmp.{os.getpid()}"
            try:
                os.makedirs(os.path.dirname(self.path) or ".",
                            exist_ok=True)
                with open(tmp, "w", encoding="utf-8") as f:
                    json.dump(doc, f, sort_keys=True)
                os.replace(tmp, self.path)
                return True
            except Exception as e:  # noqa: BLE001 - never-raise surface
                log.warning("sentinel baselines not persisted to %s: "
                            "%s", self.path, e)
                try:
                    os.unlink(tmp)
                except Exception:  # noqa: BLE001 - best-effort cleanup
                    pass
                return False

    # -------------------------------------------------------------- fold
    # tpulint: never-raise
    def fold(self, rec: dict) -> List[dict]:
        """Fold one live query record; flags fan out to the metric
        registry and the flight recorder. Never raises."""
        try:
            with self._lock:
                regs = fold_record(self._baselines, rec,
                                   wall_factor=self.wall_factor,
                                   min_samples=self.min_samples,
                                   window=self.window,
                                   tail_factor=self.tail_factor)
                self.flagged.extend(regs)
                # /healthz shows recent flags, not unbounded history
                del self.flagged[:-64]
                self._folds_since_save += 1
                save_due = bool(regs) or \
                    self._folds_since_save >= _SAVE_EVERY_FOLDS
        except Exception as e:  # noqa: BLE001 - observability only
            log.warning("sentinel fold failed: %s", e)
            return []
        if regs:
            # the fan-out is fallible too — json.dumps raises TypeError
            # when a flag record carries a non-JSON value (numpy scalars
            # from a folded metric), and nothing here may escape into
            # the query-completion path that called fold
            try:
                from ..metrics import registry as metrics_registry
                mr = metrics_registry.REGISTRY
                from .flight import RECORDER as _frec
                for r in regs:
                    if mr is not None:
                        mr.counter("srtpu_query_regressions_total",
                                   kind=r["kind"]).inc()
                    if _frec is not None:
                        trig = ("placement_revert"
                                if r["kind"] == "verdict_flip"
                                else "sentinel_regression")
                        _frec.trigger(trig, detail=json.dumps(
                            r, sort_keys=True))
                    log.warning("regression sentinel: %s", r)
            except Exception as e:  # noqa: BLE001 - observability only
                log.warning("sentinel flag fan-out failed: %s", e)
        if save_due:
            # debounced persist: re-serializing the whole baseline
            # table per queryEnd would tax the completion path of a
            # short-query serving workload for no added durability
            self.save()
        return regs

    def query_ended(self, o) -> None:
        """Runs AFTER the event record: the sentinel sees exactly what a
        tools/regress replay of this log would see."""
        if o.digest is not None:
            self.fold({"digest": o.digest, "wallMs": round(o.wall_ms, 3),
                       "verdict": o.verdict, "rung": o.ladder_rung,
                       "ok": o.ok, "compileS": o.compile_s})

    # ------------------------------------------------------------- reads
    def baselines(self) -> Dict[str, dict]:
        with self._lock:
            return {k: dict(v) for k, v in self._baselines.items()}

    def recent_flags(self) -> List[dict]:
        with self._lock:
            return list(self.flagged)


# ---------------------------------------------------------------------------
# installation (the trace/metrics pattern)
# ---------------------------------------------------------------------------

_INSTALL_LOCK = threading.Lock()


def active_sentinel() -> Optional[RegressionSentinel]:
    # tpulint: disable=lock-discipline — lock-free by design: the
    # disabled-path contract is one unlocked reference read per site
    return SENTINEL


def install_sentinel(sen: Optional[RegressionSentinel]) -> \
        Optional[RegressionSentinel]:
    """Install (or with ``None`` remove) the process-global sentinel."""
    global SENTINEL
    with _INSTALL_LOCK:
        SENTINEL = sen
    return sen


def ensure_sentinel_from_conf(conf) -> Optional[RegressionSentinel]:
    """Install a sentinel iff ``spark.rapids.tpu.sentinel.enabled`` —
    one conf lookup per ExecContext construction, never per query."""
    global SENTINEL
    if not conf.get(SENTINEL_ENABLED):
        # tpulint: disable=lock-discipline — lock-free by design:
        # sentinel-off fast path; installation itself locks below
        return SENTINEL
    with _INSTALL_LOCK:
        if SENTINEL is None:
            path = str(conf.get(SENTINEL_PATH) or "").strip() \
                or default_baselines_path()
            SENTINEL = RegressionSentinel(
                path,
                wall_factor=float(conf.get(SENTINEL_WALL_FACTOR)),
                min_samples=int(conf.get(SENTINEL_MIN_SAMPLES)),
                window=int(conf.get(SENTINEL_WINDOW)),
                tail_factor=float(conf.get(SENTINEL_TAIL_FACTOR)))
        return SENTINEL
