"""Multi-tenant scheduling layer (ISSUE 18).

The query-serving front door: every ``exec/query.run_query`` query passes
through the :mod:`.admission` controller before it can touch the device
semaphore. The subsystem rations *entry* the way the reference stack's
``GpuSemaphore`` rations concurrent device tasks — but one level up,
where a request can still be cheaply refused instead of wedging the
runtime ("Accelerating Presto with GPUs" is the concurrent-query
admission blueprint):

* priority-queued admission — per-tenant priority classes, FIFO within
  a class, configurable max in-flight and max queued
  (``spark.rapids.tpu.admission.*``);
* deadline-aware queueing — a query whose
  ``spark.rapids.tpu.query.timeout`` budget would expire while queued
  is rejected immediately, not admitted to fail later;
* graceful shedding — while the process is pressure-degraded (the
  ``/healthz`` memory/semaphore verdicts: HBM > 95 %, a live or
  recently-drained pressure-grant pool, a wedged holder) new
  low-priority admissions are refused with a structured
  :class:`~.admission.AdmissionRejected` carrying a retry-after hint.

Contract (the trace/metrics/ops pattern): disabled, the controller is
``None`` and every query pays one module-global load + branch.
"""
from __future__ import annotations

from .admission import (AdmissionController, AdmissionRejected,
                        AdmissionTicket, active_admission,
                        ensure_admission_from_conf, install_admission,
                        shed_reason)

__all__ = ["AdmissionController", "AdmissionRejected", "AdmissionTicket",
           "active_admission", "ensure_admission_from_conf",
           "install_admission", "shed_reason"]
