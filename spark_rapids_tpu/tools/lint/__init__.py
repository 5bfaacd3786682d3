"""tpulint: AST-based static analysis enforcing the accelerator contracts.

The repo's core invariants are documented but were historically unenforced:

* ``mem/retry.py`` — "the attempted function must be idempotent over its
  (spillable) input" (the RmmRapidsRetryIterator.scala:33 contract), and
  state mutation inside an attempt needs a ``CheckpointRestore``;
* ``mem/spillable.py`` — every ``SpillableBatch`` must be closed exactly
  once by exactly one owner (the reference tracks this with RefCount leak
  detection / MemoryCleaner); v3 verifies it interprocedurally on an
  owned/borrowed/moved/closed lattice over the CFG;
* device hot paths must not sync to the host (each sync is a full device
  round trip — the silent perf killer of accelerator pipelines);
* the ops plane's never-raise surfaces (flight triggers, event-log
  writes, sentinel folds) must not let exceptions escape past a logging
  catch, and pressure-grant accounting must stay paired;
* the config / ops registries must stay in sync with ``docs/configs.md``
  and ``docs/supported_ops.md`` (the reference enforces the analog with
  TypeChecks-driven doc generation and custom scalastyle rules).

This package is a self-contained stdlib-``ast`` framework: a rule
registry, per-line / per-file suppression comments
(``# tpulint: disable=<rule>``), a checked-in baseline for grandfathered
findings, a project-wide call graph with per-function ownership/escape
summaries (callgraph.py), and a CLI
(``python -m spark_rapids_tpu.tools.lint``) that exits non-zero on new
violations. See docs/static_analysis.md.
"""
from .framework import (FileContext, FileRule, Finding, LintResult,
                        ProjectRule, Rule, lint_source, load_baseline,
                        prune_baseline, run_lint, write_baseline)
from .rules_retry import RetryIdempotenceRule
from .rules_ownership import OwnershipRule
from .rules_contracts import (GrantPairingRule, NeverRaiseRule,
                              RetryPurityRule)
from .rules_hostsyncflow import HostSyncFlowRule
from .rules_jit import AdHocJitRule
from .rules_lockdiscipline import LockDisciplineRule
from .rules_retrace import RetraceRiskRule
from .rules_drift import (ConfigKeyDriftRule, MetricNameDriftRule,
                          OpsDocDriftRule, ReasonCodeDriftRule)

#: every shipped rule, in reporting order
ALL_RULES = [RetryIdempotenceRule(), RetryPurityRule(), OwnershipRule(),
             NeverRaiseRule(), GrantPairingRule(), HostSyncFlowRule(),
             AdHocJitRule(), RetraceRiskRule(), LockDisciplineRule(),
             ConfigKeyDriftRule(), OpsDocDriftRule(),
             MetricNameDriftRule(), ReasonCodeDriftRule()]

__all__ = ["ALL_RULES", "FileContext", "FileRule", "Finding", "LintResult",
           "ProjectRule", "Rule", "lint_source", "load_baseline",
           "prune_baseline", "run_lint", "write_baseline",
           "RetryIdempotenceRule", "RetryPurityRule", "OwnershipRule",
           "NeverRaiseRule", "GrantPairingRule", "HostSyncFlowRule",
           "AdHocJitRule", "RetraceRiskRule", "LockDisciplineRule",
           "ConfigKeyDriftRule", "OpsDocDriftRule", "MetricNameDriftRule",
           "ReasonCodeDriftRule"]
