"""host-sync-flow: device values must not FLOW into implicit host syncs.

The retired pattern-based ``host-sync`` rule caught the direct shapes —
``np.asarray(x)``, ``.item()``, ``jax.device_get`` — but a device value
that travels through a couple of assignments or a helper before hitting
``float()`` or an ``if`` was invisible to it.  This rule runs the
tools/lint/dataflow.py taint engine over every device hot scope
(``eval_device`` bodies and jit-decorated kernels, the trace-time code
paths of exprs/base.py and the compiled kernels):

* **sources** — parameters of the scope (everything handed to a jitted
  kernel is traced; ``eval_device``'s ctx columns are device
  residents), ``jax.numpy``/``jax.lax`` call results, and
  ``.data``/``.validity``/``.columns`` buffers;
* **propagation** — assignments, tuple unpacking, arithmetic,
  comparisons, conditionals, loops, comprehensions; ``.shape`` /
  ``.ndim`` / ``.dtype`` / ``len()`` / ``is None`` launder taint away
  (they are trace-static host values);
* **same-module helper summaries** — a tainted argument is followed
  through module-level ``def``s: parameters that reach a sink inside
  the helper fire at the call site, parameters that reach the return
  value keep the result tainted;
* **sinks** — ``float()`` / ``int()`` / ``bool()`` conversions,
  truthiness tests (``if``/``while``/``assert`` conditions, ``and`` /
  ``or`` / ``not`` operands, conditional-expression and comprehension
  conditions), and f-string interpolation.  Each is a silent full
  device round trip per batch — or an outright TracerBoolConversion /
  ConcretizationError under trace.

This is the ONE host-sync rule surface (tpulint v3): the direct sync
shapes that need no flow analysis — ``np.asarray(x)`` / ``.item()`` /
``jax.device_get`` on anything inside a hot scope — are folded in here
too (they were a separate ``host-sync`` pattern rule through v2).  The
scalar-conversion heuristic that rule ALSO used to carry (``float()``
of a name that merely *looked* device-ish) stays retired in favor of
the dataflow version.  Intentional sync points carry an inline
suppression with their justification (docs/static_analysis.md).
"""
from __future__ import annotations

import ast
from typing import Dict, Iterable, List, Optional, Tuple

from .astutil import call_name, dotted_name, is_jit_decorated, \
    jit_static_params
from .dataflow import (Summaries, TaintAnalysis, TaintSpec,
                       element_exprs, scan_conditions)
from .framework import FileContext, FileRule, Finding

__all__ = ["HostSyncFlowRule"]

#: call names that ARE a host sync on a device value, no argument
#: analysis needed
_SYNC_CALLS = {"np.asarray", "np.array", "numpy.asarray", "numpy.array",
               "jax.device_get", "device_get", "_np.asarray", "_np.array",
               "onp.asarray", "onp.array"}
#: method names that force a sync on any jax array receiver
_SYNC_METHODS = {"item", "block_until_ready", "tolist", "to_py"}

#: call prefixes whose results live on device (trace-time values)
_DEVICE_CALL_PREFIXES = ("jnp.", "jax.numpy.", "jax.lax.", "lax.",
                         "jax.nn.", "jnn.")
#: attribute names that are device-resident buffers wherever they occur
#: in a hot scope (DVal/DeviceColumn/Batch fields)
_DEVICE_ATTRS = frozenset({"data", "validity", "columns"})
#: scalar-conversion sinks
_SCALAR_SINKS = ("float", "int", "bool")


class _FlowSpec(TaintSpec):
    """Labels: "@src" marks device-derived; helper summaries add int
    parameter indices. Sources keep the underlying labels too, so a
    helper's param lineage survives passing through a device op."""

    #: dtype/metadata predicates yield host values even on traced
    #: arrays — branching on them is trace-static, not a sync
    untaint_calls = TaintSpec.untaint_calls | frozenset(
        {"issubdtype", "data_type", "result_type", "promote_types",
         "can_cast", "bucket_for"})

    def __init__(self, summaries: Optional[Summaries] = None):
        self.summaries = summaries

    #: host-side metadata fields of ctx/DVal objects — reading them off
    #: a traced value yields trace-static host data
    untaint_attrs = TaintSpec.untaint_attrs | frozenset(
        {"schema", "literal_slots", "padded_len", "np_dtype",
         "fields", "device_backed"})

    def source(self, expr, ev):
        if isinstance(expr, ast.Call):
            name = dotted_name(expr.func) or ""
            if name.rsplit(".", 1)[-1] in self.untaint_calls:
                return None          # dtype predicates stay host-static
            if name.startswith(_DEVICE_CALL_PREFIXES):
                out = frozenset(["@src"])
                for a in expr.args:
                    out |= ev(a)
                for k in expr.keywords:
                    out |= ev(k.value)
                return out
        if isinstance(expr, ast.Attribute) and \
                expr.attr in _DEVICE_ATTRS and \
                isinstance(expr.ctx, ast.Load):
            return frozenset(["@src"]) | ev(expr.value)
        return None


class HostSyncFlowRule(FileRule):
    name = "host-sync-flow"
    contract = ("no device->host sync inside eval_device or a jit "
                "kernel: neither a direct one (np.asarray/device_get/"
                ".item()) nor a device-derived value FLOWING (through "
                "assignments or same-module helpers) into float()/int()/"
                "bool(), a truthiness test, or an f-string — each is a "
                "full device round trip per batch or a tracing break")

    def check(self, ctx: FileContext) -> Iterable[Finding]:
        if ctx.tree is None:
            return []
        scopes: List[Tuple[ast.AST, str]] = []
        for node in ast.walk(ctx.tree):
            if not isinstance(node, (ast.FunctionDef,
                                     ast.AsyncFunctionDef)):
                continue
            if node.name == "eval_device":
                scopes.append((node, "eval_device"))
            elif is_jit_decorated(node):
                scopes.append((node, f"jit kernel {node.name}"))
        direct: List[Finding] = []
        for fn, where in scopes:
            direct.extend(self._direct_syncs(ctx, fn, where))
        # nested (non-jit) defs inside a hot scope are trace-time code
        # too — the CFG treats them as opaque, so analyze each as its
        # own scope (params of a helper defined under trace receive
        # traced values)
        seen = {id(fn) for fn, _ in scopes}
        for fn, where in list(scopes):
            for sub in ast.walk(fn):
                if isinstance(sub, (ast.FunctionDef,
                                    ast.AsyncFunctionDef)) and \
                        id(sub) not in seen:
                    seen.add(id(sub))
                    scopes.append((sub, f"{where} (nested def "
                                        f"{sub.name})"))
        if not scopes:
            return []
        summaries = Summaries(ctx.tree, lambda s: _FlowSpec(s),
                              sink_scan=self._summary_sinks)
        findings: List[Finding] = list(direct)
        for fn, where in scopes:
            findings.extend(self._check_scope(ctx, fn, where, summaries))
        return findings

    # ------------------------------------------------- direct sync calls
    def _direct_syncs(self, ctx: FileContext, fn,
                      where: str) -> List[Finding]:
        """The no-flow-analysis shapes absorbed from the retired
        ``host-sync`` pattern rule.  Nested defs inside a hot scope are
        still trace-time code, so walk everything (ast.walk) — this runs
        on the TOP-level scopes only, before nested-def expansion, so
        each call site reports once."""
        out: List[Finding] = []
        fname = getattr(fn, "name", "<lambda>")

        def emit(node, what, key):
            out.append(Finding(
                self.name, ctx.rel, node.lineno,
                f"{what} inside {where} — this synchronizes the device "
                "to the host (a full device round trip per batch) or "
                "breaks XLA tracing", key=f"{fname}:{key}"))

        for node in ast.walk(fn) if fn.body else []:
            if not isinstance(node, ast.Call):
                continue
            name = call_name(node)
            if name in _SYNC_CALLS:
                emit(node, f"{name}() on a traced value", f"{name}")
            elif isinstance(node.func, ast.Attribute) \
                    and node.func.attr in _SYNC_METHODS \
                    and not node.args:
                emit(node, f".{node.func.attr}()",
                     f"method:{node.func.attr}")
        return out

    # ------------------------------------------------------------ scopes
    @staticmethod
    def _seeds(fn) -> Dict[str, frozenset]:
        skip = jit_static_params(fn)
        seeds = {}
        for a in (list(fn.args.posonlyargs) + list(fn.args.args)
                  + list(fn.args.kwonlyargs)):
            if a.arg in ("self", "cls") or a.arg in skip:
                continue
            seeds[a.arg] = frozenset(["@src"])
        return seeds

    def _check_scope(self, ctx: FileContext, fn, where: str,
                     summaries: Summaries) -> List[Finding]:
        analysis = TaintAnalysis(fn, _FlowSpec(summaries),
                                 self._seeds(fn))
        out: List[Finding] = []
        counts: Dict[str, int] = {}
        fname = fn.name

        def emit(node, desc: str):
            n = counts.get(desc, 0)
            counts[desc] = n + 1
            out.append(Finding(
                self.name, ctx.rel, node.lineno,
                f"device-derived value flows into {desc} inside {where}"
                " — an implicit device->host sync (full device round "
                "trip per batch) or a tracing break; hoist the sync "
                "out of the hot path or keep the logic in jnp",
                key=f"{fname}:{desc}:{n}"))

        def on_cond(expr, env):
            if "@src" in analysis.eval(expr, env):
                emit(expr, "a truthiness test")

        def on_value_sink(node, env, desc):
            if "@src" in analysis.eval(node, env):
                emit(node, desc)

        scan_conditions(analysis, on_cond)
        self._scan_value_sinks(analysis, on_value_sink,
                               summaries=summaries, emit=emit)
        return out

    # ------------------------------------------------- value sinks
    def _scan_value_sinks(self, analysis: TaintAnalysis, on_sink,
                          summaries: Optional[Summaries] = None,
                          emit=None) -> None:
        """Scalar-conversion and f-string sinks (plus helper call-site
        reporting when ``summaries``/``emit`` are given)."""

        def visit(node, env):
            if isinstance(node, ast.Call):
                name = dotted_name(node.func)
                if name in _SCALAR_SINKS and node.args:
                    on_sink(node.args[0], env, f"a {name}() conversion")
                elif summaries is not None and \
                        isinstance(node.func, ast.Name):
                    self._call_sink(analysis, summaries, node, env, emit)
            elif isinstance(node, ast.FormattedValue):
                on_sink(node.value, env, "f-string interpolation")

        for elem, env in analysis.walk():
            for e in element_exprs(elem):
                analysis.scan_expr(e, env, visit)

    @staticmethod
    def _call_sink(analysis: TaintAnalysis, summaries: Summaries,
                   node, env, emit) -> None:
        """A tainted argument reaching a sink INSIDE a same-module
        helper fires at the call site."""
        summ = summaries.get(node.func.id)
        if summ is None or not summ.sinks:
            return
        arg_labels = [analysis.eval(a, env) for a in node.args]
        for labels, desc, line in summ.sinks:
            hit = any(isinstance(lbl, int) and lbl < len(arg_labels)
                      and "@src" in arg_labels[lbl] for lbl in labels)
            if hit:
                emit(node, f"{desc} inside helper "
                           f"'{node.func.id}' (line {line})")

    # ---------------------------------------------- helper summaries
    def _summary_sinks(self, analysis: TaintAnalysis) -> List[Tuple]:
        """Sink scan used while summarizing a helper: record sinks
        whose labels include parameter indices."""
        sinks: List[Tuple] = []

        def record(node, env, desc):
            labels = analysis.eval(node, env)
            if any(isinstance(lbl, int) for lbl in labels):
                sinks.append((labels, desc, node.lineno))

        def on_cond(expr, env):
            record(expr, env, "a truthiness test")

        scan_conditions(analysis, on_cond)
        self._scan_value_sinks(analysis, record)
        return sinks
