"""Logical plan rewrites applied before TPU planning.

Distinct aggregates (ref Spark's RewriteDistinctAggregates, which the
reference accelerates post-rewrite: GpuHashAggregateExec only ever sees
the expanded two-level form): an Aggregate containing `agg(DISTINCT e)` is
rewritten into

    Project(restore names/order)
      Aggregate(G, merge partials + distinct aggs over e)   -- outer
        Aggregate(G + [e], partials of non-distinct aggs)   -- inner dedup

which runs entirely on the device groupby pipeline. The rewrite applies
when every distinct agg shares ONE child expression and all aggs are
decomposable (Sum/Count/CountStar/Min/Max/Average); otherwise the plan is
left alone and the host aggregate computes distinct natively (the planner
tags it off-device).

Only applied when planning for the TPU: the host oracle path keeps its
native pandas distinct so differential tests check the rewrite itself.
"""
from __future__ import annotations

import copy
from typing import Optional

from ..exprs import aggregates as AG
from ..exprs.arithmetic import Divide
from ..exprs.base import Alias, ColumnRef, Literal
from ..exprs.cast import Cast
from ..exprs.conditional import Coalesce
from ..types import FLOAT64, INT64
from . import logical as L

__all__ = ["rewrite_plan", "prune_columns", "push_filters_below_joins",
           "HASH_DISTINCT_ENABLED"]

from ..config import register

HASH_DISTINCT_ENABLED = register(
    "spark.rapids.tpu.sql.hashDistinct.enabled", True,
    "Rewrite count/sum/avg(DISTINCT e) over fixed-width types into a "
    "single-level aggregate guarded by a hash-table first-occurrence "
    "flag (exec/distinct_flag.py) instead of the two-level sort "
    "expansion — no lax.sort in any resulting kernel, so modules "
    "compile in seconds and the whole pipeline dispatches without "
    "per-batch syncs (ref: cudf hash-based distinct aggregation). "
    "Applies only when the plan is not lowered onto a device mesh.")


# ---------------------------------------------------------------------------
# column pruning (projection pushdown into scans)
# ---------------------------------------------------------------------------
# The reference gets pruning for free from Catalyst; standalone we push the
# required-column set top-down and trim LogicalScan/file scans. This
# directly cuts H2D bytes.

def _expr_refs(e, out: set):
    if e is None:
        return
    if hasattr(e, "references") and not getattr(e, "children", None):
        for n in e.references():
            out.add(n)
        return
    if isinstance(e, ColumnRef):
        out.add(e.name)
        return
    for c in getattr(e, "children", ()):  # Expression tree
        _expr_refs(c, out)


def _agg_refs(a, out: set):
    # input_exprs() covers multi-input aggregates (min_by's ordering)
    for e in a.input_exprs():
        _expr_refs(e, out)


def _narrowed(child: L.LogicalPlan, required: Optional[set]):
    """A join input under a projection onto what the join and the plan
    above it read, where the input still carries more (a column only its
    own filter reads, a key of a join below): the join sorts, gathers and
    concatenates every column it is handed."""
    if required is None:
        return child
    names = child.schema().names()
    keep = [n for n in names if n in required] or names[:1]
    if len(keep) == len(names):
        return child
    return L.Project([ColumnRef(n) for n in keep], child)


def prune_columns(plan: L.LogicalPlan,
                  required: Optional[set] = None) -> L.LogicalPlan:
    """required = names needed from this node's output; None = all."""
    def rebuilt(node, new_children):
        if all(n is o for n, o in zip(new_children, node.children)):
            return node
        node = copy.copy(node)
        node.children = new_children
        return node

    if isinstance(plan, L.LogicalScan):
        names = plan.schema().names()
        if required is None or set(names) <= required:
            return plan
        keep = [n for n in names if n in required]
        if not keep:        # degenerate count(*)-style: keep one column
            keep = names[:1]
        return L.LogicalScan(plan.tables, plan._schema, columns=keep)
    if isinstance(plan, L.ParquetScan):  # covers Orc/Avro subclasses
        names = plan.schema().names()
        if required is not None and not set(names) <= required:
            keep = [n for n in names if n in required] or names[:1]
            plan = copy.copy(plan)
            plan.columns = keep
        return plan
    if isinstance(plan, L.Project):
        exprs = plan.exprs
        if required is not None:
            kept = [e for e in exprs if e.name_hint in required]
            exprs = kept if kept else exprs[:1]
        child_req: set = set()
        for e in exprs:
            _expr_refs(e, child_req)
        child = prune_columns(plan.children[0], child_req)
        if exprs is not plan.exprs or child is not plan.children[0]:
            return L.Project(exprs, child)
        return plan
    if isinstance(plan, L.Filter):
        child_req = None if required is None else set(required)
        if child_req is not None:
            _expr_refs(plan.condition, child_req)
        return rebuilt(plan, [prune_columns(plan.children[0], child_req)])
    if isinstance(plan, L.Aggregate):
        child_req: set = set()
        for g in plan.groupings:
            _expr_refs(g, child_req)
        for a in plan.aggs:
            _agg_refs(a, child_req)
        return rebuilt(plan, [prune_columns(plan.children[0], child_req)])
    if isinstance(plan, L.Sort):
        child_req = None if required is None else set(required)
        if child_req is not None:
            for o in plan.orders:
                _expr_refs(o.expr, child_req)
        return rebuilt(plan, [prune_columns(plan.children[0], child_req)])
    if isinstance(plan, L.DistinctFlag):
        child_req = None if required is None \
            else set(required) - {plan.flag_name}
        if child_req is not None:
            for e in plan.key_exprs:
                _expr_refs(e, child_req)
            _expr_refs(plan.value_expr, child_req)
        return rebuilt(plan, [prune_columns(plan.children[0], child_req)])
    if isinstance(plan, (L.GlobalLimit, L.LocalLimit, L.Sample)):
        return rebuilt(plan, [prune_columns(plan.children[0], required)])
    if isinstance(plan, L.Repartition):
        child_req = None if required is None else set(required)
        if child_req is not None:
            for k in plan.keys:
                _expr_refs(k, child_req)
        return rebuilt(plan, [prune_columns(plan.children[0], child_req)])
    from ..exec.cached import CachedRelation
    if isinstance(plan, CachedRelation):
        names = plan.schema().names()
        if required is None or set(names) <= required:
            return plan
        keep = [n for n in names if n in required] or names[:1]
        return CachedRelation(plan.blobs, plan._schema, columns=keep)
    if isinstance(plan, L.Union):
        # children share column names positionally only when schemas align;
        # prune identically by name
        return rebuilt(plan, [prune_columns(c, required)
                              for c in plan.children])
    if isinstance(plan, L.Join):
        lnames = set(plan.children[0].schema().names())
        rnames = set(plan.children[1].schema().names())
        if required is None:
            lreq, rreq = None, None
        else:
            lreq = {n for n in required if n in lnames}
            rreq = {n for n in required if n in rnames}
            cond_refs: set = set()
            for k in plan.left_keys:
                _expr_refs(k, cond_refs)
            for k in plan.right_keys:
                _expr_refs(k, cond_refs)
            _expr_refs(plan.condition, cond_refs)
            lreq |= cond_refs & lnames
            rreq |= cond_refs & rnames
        return rebuilt(plan, [_narrowed(prune_columns(c, req), req)
                              for c, req in zip(plan.children,
                                                (lreq, rreq))])
    # Window/Generate/Expand/WriteFile/unknown: conservative — children
    # keep everything
    return rebuilt(plan, [prune_columns(c, None) for c in plan.children])


# ---------------------------------------------------------------------------
# predicate pushdown below joins (ref Spark PushPredicateThroughJoin)
# ---------------------------------------------------------------------------
# The SQL lowering claims the join-key conjuncts of an implicit join and
# leaves every other WHERE conjunct in ONE filter above the last join, and
# the DataFrame API lets a user write the same shape. A conjunct that names
# columns of one join input alone filters that input just as well, and
# there it runs before the join pays for the rows it drops.

#: join type -> the inputs a filter ABOVE the join may move onto: both of
#: an inner or cross join; the preserved side only of an outer, semi or
#: anti join (below the null-producing side it would keep NULL-extended
#: rows the filter above drops); none of a full outer or existence join
_PUSH_SIDES = {"inner": (0, 1), "cross": (0, 1), "left": (0,),
               "right": (1,), "leftsemi": (0,), "leftanti": (0,),
               "full": (), "existence": ()}


def _home(cond, sides) -> Optional[int]:
    """The one join input (0 left, 1 right) whose columns alone the
    conjunct names, by the inputs' column-name sets; None for a conjunct
    over both, over neither, or over a name both inputs have."""
    refs: set = set()
    _expr_refs(cond, refs)
    for i in (0, 1):
        if refs and refs <= sides[i] and not refs & sides[1 - i]:
            return i
    return None


def _and_all(conds):
    from ..exprs.logical import And
    out = None
    for c in conds:
        out = c if out is None else And(out, c)
    return out


def _filtered(conds, plan: L.LogicalPlan) -> L.LogicalPlan:
    """``plan`` under the conjuncts: each moved below the join it can
    cross, what is left in ONE filter directly above ``plan``."""
    if not conds:
        return plan
    if isinstance(plan, L.Filter):
        inner: list = []
        _conjuncts(plan.condition, inner)
        return _filtered(inner + list(conds), plan.children[0])
    if isinstance(plan, L.Join):
        from ..exec.wholestage import _nondeterministic
        sides = [set(c.schema().names()) for c in plan.children]
        moved = ([], [])
        kept = []
        for c in conds:
            i = _home(c, sides)
            # a value with per-task state (exec/wholestage.py's marker)
            # depends on which rows reach it: it stays where it was written
            if i in _PUSH_SIDES[plan.join_type] \
                    and not _nondeterministic([c]):
                moved[i].append(c)
            else:
                kept.append(c)
        if moved[0] or moved[1]:
            plan = copy.copy(plan)
            plan.children = [_filtered(moved[i], plan.children[i])
                             for i in (0, 1)]
        conds = kept
    return L.Filter(_and_all(conds), plan) if conds else plan


def _sink(semi: L.Join, into: L.LogicalPlan, counts: list):
    """``into`` with the semi-join placed below the joins it can cross, or
    None where it crosses none. A left semi or anti join against a
    relation of its own (``expr IN (select ...)``) is a filter of its left
    input's rows: where that input is a join and the semi-join's keys name
    columns of ONE of its inputs, it goes onto that input, by the rule a
    filter's conjunct follows (``_PUSH_SIDES``), and through a filter in
    its way. ``counts`` as in :func:`push_filters_below_joins`: a
    semi-join counts as one conjunct, however far it goes."""
    if isinstance(into, L.Filter):
        below = _sink(semi, into.children[0], counts)
        return None if below is None else L.Filter(into.condition, below)
    if not isinstance(into, L.Join):
        return None
    refs: set = set()
    for k in semi.left_keys:
        _expr_refs(k, refs)
    sides = [set(c.schema().names()) for c in into.children]
    i = next((i for i in (0, 1)
              if refs and refs <= sides[i] and not refs & sides[1 - i]),
             None)
    if i not in _PUSH_SIDES[into.join_type]:
        counts[1] += i is not None
        return None
    counts[0] += 1
    kids = list(into.children)
    kids[i] = _sink(semi, kids[i], [0, 0]) \
        or _with_children(semi, [kids[i], semi.children[1]])
    return _with_children(into, kids)


def _with_children(node, children):
    node = copy.copy(node)
    node.children = list(children)
    return node


def push_filters_below_joins(plan: L.LogicalPlan):
    """(plan', pushed, above_joins): every filter directly above a join
    split into its conjuncts, each deterministic conjunct that names
    columns of ONE join input moved onto that input (recursively: below
    the next join too), the others kept above; a left semi or anti join
    whose keys name columns of one input of a join below it likewise
    (:func:`_sink`). ``pushed`` counts the conjuncts that crossed a join;
    ``above_joins`` the one-input conjuncts that could not (the
    null-producing side of an outer join, a value with per-task state).
    The plan comes back as the same object when nothing moves."""
    counts = [0, 0]

    def walk(node):
        kids = [walk(c) for c in node.children]
        if any(n is not o for n, o in zip(kids, node.children)):
            node = _with_children(node, kids)
        if isinstance(node, L.Join) and node.condition is None \
                and node.join_type in ("leftsemi", "leftanti"):
            return _sink(node, node.children[0], counts) or node
        if not (isinstance(node, L.Filter)
                and isinstance(node.children[0], L.Join)):
            return node
        join = node.children[0]
        conds: list = []
        _conjuncts(node.condition, conds)
        new = _filtered(conds, join)
        stay = []
        if isinstance(new, L.Filter):
            _conjuncts(new.condition, stay)
        counts[0] += len(conds) - len(stay)
        sides = [set(c.schema().names()) for c in join.children]
        counts[1] += sum(_home(c, sides) is not None for c in stay)
        return node if len(stay) == len(conds) else new

    return walk(plan), counts[0], counts[1]


def estimated_size_bytes(plan: L.LogicalPlan) -> Optional[int]:
    """Plan-time size estimate (ref Spark SizeInBytesOnlyStatsPlan /
    the reference's AQE stage statistics): known for in-memory and file
    scans, propagated through size-preserving unary nodes, None where
    unknowable. A filter keeps of the child's estimate what
    :func:`_kept_share` guesses."""
    own = getattr(plan, "estimated_size_bytes", None)
    if own is not None:                # LogicalScan, CachedRelation, ...
        return own()
    if isinstance(plan, L.ParquetScan):
        import os
        try:
            return sum(os.path.getsize(p) for p in plan.paths)
        except OSError:
            return None
    if isinstance(plan, L.Filter):
        est = estimated_size_bytes(plan.children[0])
        return est if est is None else int(est * _kept_share(plan.condition))
    if isinstance(plan, (L.Sort, L.Repartition, L.Sample,
                         L.LocalLimit, L.GlobalLimit, L.Project)):
        return estimated_size_bytes(plan.children[0])
    return None


def _kept_share(cond) -> float:
    """The share of its input a filter is taken to keep before anything
    was measured: a tenth for each conjunct that holds a column to ONE
    literal, k tenths for a list of k (System R's guess without
    statistics: such a predicate names a few values of a domain); every
    other conjunct keeps it all (Spark's default without column
    statistics). A dimension cut to one segment or one month is then
    planned as the broadcast side it will measure as, in its FIRST query
    and not from the second on; a side that measures over the threshold
    is demoted at the next planning (plan/overrides.py:_auto_broadcast)."""
    from ..exprs.base import ColumnRef, Literal
    from ..exprs.comparison import EqualTo, In
    conds: list = []
    _conjuncts(cond, conds)
    share = 1.0
    for c in conds:
        if isinstance(c, EqualTo) and {type(k) for k in c.children} == {
                ColumnRef, Literal}:
            share *= 0.1
        elif isinstance(c, In) and isinstance(c.children[0], ColumnRef):
            share *= min(1.0, 0.1 * len(c.values))
    return share


def rewrite_plan(plan: L.LogicalPlan,
                 hash_distinct: bool = False) -> L.LogicalPlan:
    """``hash_distinct``: prefer the sort-free hash-table distinct flag
    over the two-level sort expansion. The caller enables it only when
    the plan will NOT lower onto a device mesh (the distributed fragment
    compiler understands the two-level Aggregate form, not the stateful
    DistinctFlag operator)."""
    if isinstance(plan, L.Union):
        new = _rewrite_union_agg(plan)
        if new is not None:
            # the single-pass form contains a (possibly distinct) grouped
            # aggregate that still needs the standard rewrites
            return rewrite_plan(new, hash_distinct)
    new_children = [rewrite_plan(c, hash_distinct)
                    for c in plan.children]
    if any(n is not o for n, o in zip(new_children, plan.children)):
        plan = copy.copy(plan)
        plan.children = new_children
    if isinstance(plan, L.Aggregate) and any(
            getattr(a, "distinct", False) for a in plan.aggs):
        new = _rewrite_distinct_hash(plan) if hash_distinct else None
        if new is None:
            new = _rewrite_distinct(plan)
        if new is not None:
            plan = new
    if type(plan) is L.GlobalLimit:
        plan = _limit_into_sort(plan)
    return plan


def _limit_into_sort(limit: L.GlobalLimit) -> L.LogicalPlan:
    """``LIMIT n`` above a global sort, with nothing between them but
    projections (a row in, a row out): the sort learns that only its first
    ``n`` rows are read, and selects them without sorting the rest
    (exec/sort.py:TOP_N_MAX bounds ``n``). The limit stays where it is."""
    from ..exec.sort import TOP_N_MAX
    chain = [limit]
    while isinstance(chain[-1].children[0], L.Project):
        chain.append(chain[-1].children[0])
    sort = chain[-1].children[0]
    if not (isinstance(sort, L.Sort) and sort.global_sort
            and sort.limit is None and 0 < limit.n <= TOP_N_MAX):
        return limit
    node = copy.copy(sort)
    node.limit = limit.n
    for parent in reversed(chain):
        parent = copy.copy(parent)
        parent.children = [node]
        node = parent
    return node


_DECOMPOSABLE = (AG.Sum, AG.Count, AG.CountStar, AG.Min, AG.Max, AG.Average)
_DISTINCT_OK = (AG.Count, AG.Sum, AG.Average)


# ---------------------------------------------------------------------------
# single-pass rewrite for unions of global aggregates over one shared scan
# (the TPC-DS q28 shape: k disjoint-filter branches each computing
# avg/count/count-distinct). Reference analog: RewriteDistinctAggregates'
# Expand-based multi-distinct plan (GpuExpandExec + GpuAggregateExec merge
# machinery, GpuAggregateExec.scala:718). k independent scans+sorts become
# ONE grouped aggregation keyed by a branch id:
#
#   Project(outputs, bid dropped)
#     Sort(bid)                               -- union branch order
#       Join(left: Range(0..k), agg, on bid)  -- rows for EMPTY branches
#         Aggregate([bid], shared aggs)
#           Filter(bid IS NOT NULL)
#           <tag>: Project(+CASE bid) when branch filters are provably
#                  disjoint (1x rows), else Expand (one copy per matching
#                  branch — correct under overlap)
#             <shared child>
# ---------------------------------------------------------------------------

def _flatten_union(plan, out):
    for c in plan.children:
        if isinstance(c, L.Union):
            _flatten_union(c, out)
        else:
            out.append(c)


def _conjuncts(e, out):
    from ..exprs.logical import And
    if isinstance(e, And):
        for c in e.children:
            _conjuncts(c, out)
    else:
        out.append(e)


def _branch_interval(cond):
    """(col, lo, hi) when the condition's top-level conjuncts pin one
    column into a closed interval; None otherwise."""
    from ..exprs.comparison import (GreaterThan, GreaterThanOrEqual,
                                    LessThan, LessThanOrEqual)
    cs: list = []
    _conjuncts(cond, cs)
    lo = hi = col = None
    for c in cs:
        l, r = getattr(c, "children", (None, None))[:2] \
            if len(getattr(c, "children", ())) == 2 else (None, None)
        if not (isinstance(l, ColumnRef) and isinstance(r, Literal)):
            continue
        if isinstance(c, GreaterThanOrEqual):
            b = r.value
        elif isinstance(c, GreaterThan):
            b = r.value  # open bound: treat as lo (conservative for ints)
        elif isinstance(c, (LessThanOrEqual, LessThan)):
            if col is None or col == l.name:
                col = l.name
                hi = r.value if hi is None else min(hi, r.value)
            continue
        else:
            continue
        if col is None or col == l.name:
            col = l.name
            lo = b if lo is None else max(lo, b)
    if col is None or lo is None or hi is None:
        return None
    return (col, lo, hi)


def _branches_disjoint(conds) -> bool:
    ivs = [_branch_interval(c) for c in conds]
    if any(iv is None for iv in ivs):
        return False
    col = ivs[0][0]
    if any(iv[0] != col for iv in ivs):
        return False
    spans = sorted((iv[1], iv[2]) for iv in ivs)
    return all(spans[i][1] < spans[i + 1][0] for i in range(len(spans) - 1))


def _rewrite_union_agg(union: L.Union) -> Optional[L.LogicalPlan]:
    branches: list = []
    _flatten_union(union, branches)
    if len(branches) < 2:
        return None
    conds = []
    shared = None
    for b in branches:
        if not (isinstance(b, L.Aggregate) and not b.groupings
                and len(b.children) == 1
                and isinstance(b.children[0], L.Filter)):
            return None
        f = b.children[0]
        if shared is None:
            shared = f.children[0]
        elif f.children[0] is not shared:
            return None          # branches must scan the SAME relation
        conds.append(f.condition)
    # agg lists must be structurally identical across branches
    a0 = branches[0].aggs
    for b in branches[1:]:
        if len(b.aggs) != len(a0):
            return None
        for x, y in zip(a0, b.aggs):
            if type(x) is not type(y) or x.distinct != y.distinct \
                    or x.name_hint != y.name_hint:
                return None
            cx = getattr(x, "child", None)
            cy = getattr(y, "child", None)
            if (cx is None) != (cy is None):
                return None
            if cx is not None and cx.key() != cy.key():
                return None
    for a in a0:
        if a.distinct and type(a) not in _DISTINCT_OK:
            return None
        if not a.distinct and type(a) not in _DECOMPOSABLE:
            return None
    # a single distinct child expression at most (matches _rewrite_distinct)
    if len({a.child.key() for a in a0 if a.distinct}) > 1:
        return None

    from ..exprs.comparison import IsNotNull
    from ..exprs.conditional import CaseWhen
    k = len(branches)
    bid = "__ua_bid"
    needed: set = set()
    for a in a0:
        _agg_refs(a, needed)
    cs = shared.schema()
    keep = [n for n in cs.names() if n in needed] or cs.names()[:1]
    refs = [ColumnRef(n) for n in keep]
    if _branches_disjoint(conds):
        tag = CaseWhen([(c, Literal(i, INT64)) for i, c in enumerate(conds)])
        tagged = L.Project(refs + [Alias(tag, bid)], shared)
    else:
        projections = [refs + [Alias(CaseWhen([(c, Literal(i, INT64))]),
                                     bid)]
                       for i, c in enumerate(conds)]
        tagged = L.Expand(projections, keep + [bid], shared)
    filtered = L.Filter(IsNotNull(ColumnRef(bid)), tagged)
    # the branch id is OUR construction: literals 0..k-1 (or null) — the
    # exec may group it by direct addressing, no sort
    agg = L.Aggregate([ColumnRef(bid)],
                      [copy.copy(a) for a in a0], filtered,
                      int_key_cards=[k])
    # branch-ordered assembly with empty-branch defaults is a tiny host
    # op (<= k rows) — cheaper than a join+sort tail, which would cost
    # several device dispatches on a latency-bound backend
    fill_zero = [isinstance(a, (AG.Count, AG.CountStar)) for a in a0]
    return L.BranchAlign(k, fill_zero, agg)


#: fixed-width device-backed types whose bit patterns the hash-distinct
#: table stores exactly (strings/decimals/arrays stay on the sort path)
_HASHABLE_TYPE_NAMES = frozenset(
    ["boolean", "tinyint", "smallint", "int", "bigint", "float",
     "double", "date", "timestamp"])


def _rewrite_distinct_hash(agg: L.Aggregate) -> Optional[L.LogicalPlan]:
    """Sort-free distinct: ``count(DISTINCT e) GROUP BY g`` becomes
    ``count(CASE WHEN __hd THEN e END) GROUP BY g`` over a DistinctFlag
    operator marking first (g, e) occurrences via a persistent device
    hash table (exec/distinct_flag.py). One level — non-distinct aggs
    pass through untouched — and no lax.sort in any resulting module
    (a sort's compile time multiplies with everything fused around it,
    docs/performance.md r4). Applies to at most one grouping key and one
    distinct child, both fixed-width numeric."""
    cs = agg.children[0].schema()
    d_keys = {a.child.key() for a in agg.aggs if a.distinct}
    if len(d_keys) != 1 or len(agg.groupings) > 1:
        return None
    for a in agg.aggs:
        if a.distinct and type(a) not in _DISTINCT_OK:
            return None
    d_expr = next(a.child for a in agg.aggs if a.distinct)
    try:
        if d_expr.data_type(cs).name not in _HASHABLE_TYPE_NAMES:
            return None
        for g in agg.groupings:
            if g.data_type(cs).name not in _HASHABLE_TYPE_NAMES:
                return None
    except Exception:
        return None
    from ..exprs.conditional import CaseWhen
    flag = "__hd_flag"
    new_aggs = []
    for a in agg.aggs:
        if not a.distinct:
            new_aggs.append(a)
            continue
        guarded = CaseWhen([(ColumnRef(flag), a.child)])
        new_aggs.append(type(a)(guarded).with_name(a.name_hint))
    flagged = L.DistinctFlag(list(agg.groupings), d_expr, flag,
                             agg.children[0])
    return L.Aggregate(agg.groupings, new_aggs, flagged,
                       many_groups_hint=agg.many_groups_hint,
                       int_key_cards=agg.int_key_cards)


def _rewrite_distinct(agg: L.Aggregate) -> Optional[L.LogicalPlan]:
    cs = agg.children[0].schema()
    d_keys = {a.child.key() for a in agg.aggs if a.distinct}
    if len(d_keys) != 1:
        return None          # multiple distinct columns: host handles it
    for a in agg.aggs:
        if a.distinct and type(a) not in _DISTINCT_OK:
            return None
        if not a.distinct and type(a) not in _DECOMPOSABLE:
            return None
    d_expr = next(a.child for a in agg.aggs if a.distinct)
    dname = "__da_d"

    inner_aggs, outer_aggs, projections = [], [], []
    for g in agg.groupings:
        projections.append(ColumnRef(g.name_hint))
    for i, a in enumerate(agg.aggs):
        out = a.name_hint
        t = f"__da_t{i}"
        if a.distinct:
            # the inner agg dedups (G, e); plain agg over e finishes it
            outer_aggs.append(type(a)(ColumnRef(dname)).with_name(t))
            projections.append(Alias(ColumnRef(t), out))
        elif isinstance(a, AG.Average):
            ps, pc = f"__da_p{i}_s", f"__da_p{i}_c"
            inner_aggs.append(AG.Sum(Cast(a.child, FLOAT64)).with_name(ps))
            inner_aggs.append(AG.Count(a.child).with_name(pc))
            ts, tc = f"__da_t{i}_s", f"__da_t{i}_c"
            outer_aggs.append(AG.Sum(ColumnRef(ps)).with_name(ts))
            outer_aggs.append(AG.Sum(ColumnRef(pc)).with_name(tc))
            projections.append(Alias(
                Divide(ColumnRef(ts), Cast(ColumnRef(tc), FLOAT64)), out))
        elif isinstance(a, (AG.CountStar, AG.Count)):
            p = f"__da_p{i}"
            inner = (AG.CountStar() if isinstance(a, AG.CountStar)
                     else AG.Count(a.child))
            inner_aggs.append(inner.with_name(p))
            outer_aggs.append(AG.Sum(ColumnRef(p)).with_name(t))
            projections.append(Alias(
                Coalesce(ColumnRef(t), Literal(0, INT64)), out))
        else:                  # Sum / Min / Max merge with themselves
            p = f"__da_p{i}"
            cls = type(a)
            inner_aggs.append(cls(a.child).with_name(p))
            outer_aggs.append(cls(ColumnRef(p)).with_name(t))
            projections.append(Alias(ColumnRef(t), out))

    inner_groupings = list(agg.groupings) + [Alias(d_expr, dname)]
    inner = L.Aggregate(inner_groupings, inner_aggs, agg.children[0],
                        many_groups_hint=True,
                        int_key_cards=agg.int_key_cards + [None])
    outer_groupings = [ColumnRef(g.name_hint) for g in agg.groupings]
    outer = L.Aggregate(outer_groupings, outer_aggs, inner,
                        int_key_cards=agg.int_key_cards)
    return L.Project(projections, outer)
