"""Sentinel-history feedback: self-healing admission re-planning.

The regression sentinel (ops/sentinel.py) folds every queryEnd into a
per-digest baseline; since ISSUE 19 that baseline also counts how often
the digest escalated the OOM ladder to rung >= 3 (``highRungs``) and
how often it flagged warm-slowdown (``warmSlowdowns``). This module
turns those counters into an admission-time overlay: BEFORE the plan is
lowered, a digest with a bad history is re-planned

* with QUARTERED target batch sizes when it repeatedly hit rung >= 3 —
  the ladder's rung-2 split, applied pre-emptively so the query never
  pays the failed full-size attempts again; or
* onto the HOST engine when it repeatedly flagged warm-slowdown on the
  device — the same conf the query-level OOM ladder's final rung uses
  (``spark.rapids.tpu.sql.enabled=false``), chosen up front.

The overlay is a derived conf, not a mutation: the session conf — and
every other digest — is untouched, and the decision is recorded as a
``feedback_replan`` AqeDecision on the query's record (docs/aqe.md).
Thresholds are deliberately sticky: a digest that needed rung 3 twice
keeps its smaller batches even after the re-planned runs come back
healthy — the baseline remembers WHY they are healthy.
"""
from __future__ import annotations

from typing import Optional

from ..metrics.events import plan_digest
from ..ops import sentinel as sentinel_mod
from ..ops import slo as slo_mod
from . import AQE_FEEDBACK_ENABLED, FEEDBACK_REPLAN, make_decision

#: how many rung>=3 folds / warm-slowdown flags a digest's baseline
#: must accumulate before feedback re-plans it (2 = "repeatedly":
#: one bad run can be noise, two is a pattern)
HIGH_RUNG_REPEATS = 2
WARM_SLOWDOWN_REPEATS = 2
#: how many over-SLO-target runs the live SLO tracker must attribute
#: to a digest before feedback shrinks its batches (ISSUE 20): the
#: burn alert sheds at the front door; this is the slower, per-digest
#: repair that removes the cause
SLO_BREACH_REPEATS = 2

#: the smaller-batch overlay divides both batch targets by this
#: (mirrors one SplitAndRetry halving applied twice, the ladder's
#: observed stable point for repeat offenders)
BATCH_SHRINK_FACTOR = 4
MIN_BATCH_BYTES = 1 << 20
MIN_BATCH_ROWS = 4096

__all__ = ["FeedbackPlan", "plan_feedback", "overlay_conf",
           "HIGH_RUNG_REPEATS",
           "WARM_SLOWDOWN_REPEATS", "SLO_BREACH_REPEATS",
           "BATCH_SHRINK_FACTOR"]


class FeedbackPlan:
    """One admission-time re-plan: conf ``settings`` to overlay and the
    human-readable ``reason`` the AqeDecision carries."""

    __slots__ = ("mode", "settings", "reason")

    def __init__(self, mode: str, settings: dict, reason: str):
        self.mode = mode            # smaller_batches | host
        self.settings = settings
        self.reason = reason


def _shrink_overlay(conf):
    """The quartered-batch settings, or None at the floor (shared by
    the rung-history and SLO-tail branches)."""
    from ..config import BATCH_SIZE_BYTES, BATCH_SIZE_ROWS
    cur_b = int(conf.get(BATCH_SIZE_BYTES))
    cur_r = int(conf.get(BATCH_SIZE_ROWS))
    new_b = max(MIN_BATCH_BYTES, cur_b // BATCH_SHRINK_FACTOR)
    new_r = max(MIN_BATCH_ROWS, cur_r // BATCH_SHRINK_FACTOR)
    if new_b >= cur_b and new_r >= cur_r:
        return None             # already at the floor: nothing to shrink
    return ({"spark.rapids.tpu.sql.batchSizeBytes": new_b,
             "spark.rapids.tpu.sql.batchSizeRows": new_r},
            cur_b, new_b, cur_r, new_r)


def plan_feedback(digest: Optional[str], baseline: Optional[dict],
                  conf) -> Optional[FeedbackPlan]:
    """Consult one digest's sentinel baseline and the live SLO
    tracker's per-digest breach counts; returns the overlay to apply
    at admission, or None when history is clean (the common path: two
    dict lookups and one None check)."""
    if not digest:
        return None
    high = int((baseline or {}).get("highRungs") or 0)
    warm = int((baseline or {}).get("warmSlowdowns") or 0)
    if high >= HIGH_RUNG_REPEATS:
        shrunk = _shrink_overlay(conf)
        if shrunk is None:
            return None
        settings, cur_b, new_b, cur_r, new_r = shrunk
        return FeedbackPlan(
            "smaller_batches", settings,
            f"digest {digest} hit OOM ladder rung>=3 {high}x — admitted "
            f"with batchSizeBytes {cur_b}->{new_b}, "
            f"batchSizeRows {cur_r}->{new_r}")
    if warm >= WARM_SLOWDOWN_REPEATS:
        return FeedbackPlan(
            "host",
            {"spark.rapids.tpu.sql.enabled": False},
            f"digest {digest} flagged warm-slowdown {warm}x on the "
            "device — admitted on the host engine")
    # SLO tail coupling (ISSUE 20): a digest the live tracker has
    # repeatedly attributed over-target walls to gets the same
    # pre-emptive batch shrink as a rung offender — smaller batches
    # shorten the longest device occupancy a single query can pin
    slo = slo_mod.TRACKER
    if slo is not None:
        breaches = slo.digest_breaches(digest)
        if breaches >= SLO_BREACH_REPEATS:
            shrunk = _shrink_overlay(conf)
            if shrunk is not None:
                settings, cur_b, new_b, cur_r, new_r = shrunk
                return FeedbackPlan(
                    "smaller_batches", settings,
                    f"digest {digest} exceeded its SLO target "
                    f"{breaches}x — admitted with batchSizeBytes "
                    f"{cur_b}->{new_b}, batchSizeRows {cur_r}->{new_r}")
    return None


def overlay_conf(conf, plan, aqe_log):
    """The conf a query is admitted with BEFORE planning, where its
    digest's history (sentinel baseline, SLO breaches) asks for smaller
    target batches or host placement; None on the common clean-history
    path. The overlay is recorded as a FEEDBACK_REPLAN decision."""
    if aqe_log is None or not bool(conf.get(AQE_FEEDBACK_ENABLED)):
        return None
    sent = sentinel_mod.SENTINEL
    if sent is None and slo_mod.TRACKER is None:
        return None
    digest = plan_digest(plan)
    fb = plan_feedback(
        digest, sent.baselines().get(digest) if sent is not None else None,
        conf)
    if fb is None:
        return None
    for k, v in sorted(fb.settings.items()):
        conf = conf.set(k, v)
    try:  # tpulint: never-raise
        aqe_log.record(make_decision(FEEDBACK_REPLAN, detail=fb.reason,
                                     parts=len(fb.settings)))
    except Exception:  # noqa: BLE001 - observability only
        pass
    return conf
