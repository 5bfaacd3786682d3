"""Lower the SQL AST onto the DataFrame API / logical plan.

Aggregation handling mirrors Spark's analyzer: aggregate calls anywhere in
SELECT/HAVING/ORDER BY are hoisted into the Aggregate node under generated
names, and the surrounding expression becomes a Project over the aggregate
output. GROUP BY accepts expressions, select aliases, and 1-based
ordinals.
"""
from __future__ import annotations

from typing import Dict, List, Optional

import numpy as np

from ..api import functions as F
from ..exprs import base as EB
from .parser import (Join, OrderItem, Select, SqlError, SubqueryRef,
                     TableRef)

__all__ = ["lower_statement"]

_AGG_FNS = {
    "sum": F.sum, "count": F.count, "avg": F.avg, "mean": F.avg,
    "min": F.min, "max": F.max, "first": F.first, "last": F.last,
    "stddev": F.stddev, "stddev_samp": F.stddev,
    "stddev_pop": F.stddev_pop, "variance": F.var_samp,
    "var_samp": F.var_samp, "var_pop": F.var_pop,
}

_SCALAR_FNS = {
    "abs": F.abs, "sqrt": F.sqrt, "exp": F.exp, "ln": F.log, "log": F.log,
    "floor": F.floor, "ceil": F.ceil, "ceiling": F.ceil,
    "upper": F.upper, "ucase": F.upper, "lower": F.lower, "lcase": F.lower,
    "length": F.length, "char_length": F.length, "trim": F.trim,
    "ltrim": F.ltrim, "rtrim": F.rtrim, "reverse": F.reverse,
    "initcap": F.initcap, "year": F.year, "month": F.month,
    "day": F.dayofmonth, "dayofmonth": F.dayofmonth, "hour": F.hour,
    "minute": F.minute, "second": F.second, "quarter": F.quarter,
    "dayofweek": F.dayofweek, "dayofyear": F.dayofyear,
    "isnan": F.isnan, "isnull": F.isnull,
}

_VARARG_FNS = {
    "coalesce": F.coalesce, "concat": F.concat,
}


def _ast_key(ast) -> str:
    return repr(ast)


class _Lowerer:
    def __init__(self, session, views: Dict[str, object]):
        self.session = session
        self.views = dict(views)

    # ------------------------------------------------------------------
    def lower(self, sel: Select):
        for name, cte in sel.ctes:
            self.views[name.lower()] = self.lower(cte)
        if sel.union_with is not None:
            left = self._resolve_ref(sel.from_ref)
            op, mode, rhs = sel.union_with
            right = self.lower(rhs)
            if op == "union":
                df = left.union(right)
                if mode == "distinct":
                    df = df.distinct()
            elif op == "intersect":
                df = (left.intersect_all(right) if mode == "all"
                      else left.intersect(right))
            else:                       # EXCEPT / MINUS
                df = (left.except_all(right) if mode == "all"
                      else left.subtract(right))
            return self._order_limit(df, sel.order_by, sel.limit, {},
                                     df.columns)
        return self._lower_select(sel)

    # ------------------------------------------------------------------
    def _resolve_ref(self, ref):
        if ref is None:
            raise SqlError("SELECT without FROM is not supported")
        if isinstance(ref, SubqueryRef):
            return self.lower(ref.select)
        name = ref.name.lower()
        if name not in self.views:
            # catalog fallback: [db.]table names resolve through the
            # session catalog (sql/catalog.py; ref GpuDeltaCatalogBase)
            from .catalog import CatalogError
            try:
                return self.session.catalog.table(name)
            except CatalogError:
                raise SqlError(f"table or view not found: {ref.name}")
        v = self.views[name]
        from ..delta.table import DeltaTable
        if isinstance(v, DeltaTable):
            return v.to_df()     # re-read the log: DML may have run
        return v

    def _lower_select(self, sel: Select):
        df = self._resolve_ref(sel.from_ref)
        # alias -> {original column name -> actual column name}: join
        # inputs whose names collide with columns already in the frame are
        # renamed before the join, and qualified references (t.k / r.k)
        # resolve through this map — otherwise both sides' k collapse to
        # one ambiguous name (Spark keeps attributes distinct by expr id)
        alias_cols = {}
        if isinstance(sel.from_ref, (TableRef, SubqueryRef)) \
                and sel.from_ref.alias:
            alias_cols[sel.from_ref.alias.lower()] = {c: c
                                                      for c in df.columns}
        elif isinstance(sel.from_ref, TableRef):
            alias_cols[sel.from_ref.name.lower()] = {c: c
                                                     for c in df.columns}

        # implicit joins (FROM a, b WHERE a.k = b.k): claim WHERE equality
        # conjuncts as join keys so the plan never materializes a true
        # cartesian product (Spark's planner does the same rewrite)
        conjuncts = _split_conjuncts(sel.where)
        for ji, j in enumerate(sel.joins):
            right = self._resolve_ref(j.ref)
            rname = (j.ref.alias or getattr(j.ref, "name", None))
            rmap = {c: c for c in right.columns}
            if j.using is None:
                taken = set(df.columns)
                collide = [c for c in right.columns if c in taken]
                if collide:
                    rmap = {c: (f"__j{ji}_{c}" if c in collide else c)
                            for c in right.columns}
                    right = right.select(*[
                        F.col(c).alias(rmap[c]) for c in right.columns])
            if rname:
                alias_cols[rname.lower()] = rmap
            if j.kind == "cross" and j.on is None and j.using is None \
                    and conjuncts:
                self._aliases = alias_cols
                pairs, conjuncts = self._claim_eq_pairs(
                    conjuncts, set(df.columns), set(right.columns),
                    alias_cols, rname.lower() if rname else None)
                if pairs:
                    df = df.join(right, on=pairs, how="inner")
                    continue
            df = self._lower_join(df, right, j, alias_cols)

        # expr IN (select ...) conjuncts become semi-joins of the joined
        # frame (the planner moves each below the joins it can cross,
        # plan/rewrites.py); the subqueries lower first: a nested lowering
        # has an alias scope of its own
        in_subs = [c for c in conjuncts if _is_in_subquery(c)]
        keysets = [self._in_subquery_keys(c) for c in in_subs]
        self._aliases = alias_cols
        for n, (c, keys) in enumerate(zip(in_subs, keysets)):
            name = f"__in{n}_{keys.columns[0]}"
            df = df.join(keys.select(F.col(keys.columns[0]).alias(name)),
                         on=[(self._expr(c[1]), F.col(name))],
                         how="leftsemi")
        remaining = _and_all([c for c in conjuncts
                              if not _is_in_subquery(c)])
        if remaining is not None:
            df = df.filter(self._expr(remaining))

        select_has_agg = any(_contains_agg(e) for e, _ in sel.items) \
            or bool(sel.group_by) or _contains_agg(sel.having)
        has_window = (any(_contains_window(e) for e, _ in sel.items)
                      or _contains_window(sel.having)
                      or any(_contains_window(o.expr)
                             for o in sel.order_by))
        if has_window:
            if select_has_agg:
                raise SqlError(
                    "window functions over aggregates need a subquery "
                    "(SELECT ... OVER ... FROM (SELECT ... GROUP BY ...))")
            df, sel = self._hoist_windows(df, sel)

        if select_has_agg:
            df, alias_map, order_handled = self._lower_aggregate(df, sel)
            if sel.distinct:
                df = df.distinct()
            if order_handled:
                if sel.limit is not None:
                    df = df.limit(sel.limit)
                return df
            return self._order_limit(df, sel.order_by, sel.limit,
                                     alias_map, df.columns)
        if sel.distinct:
            df, alias_map = self._lower_projection(df, sel)
            df = df.distinct()
            return self._order_limit(df, sel.order_by, sel.limit,
                                     alias_map, df.columns)
        # non-distinct: ORDER BY resolves against the PRE-projection frame
        # so it can reference hoisted window columns, select aliases, and
        # source columns the projection drops (SQL-legal)
        if sel.order_by:
            items = self._expand_items(df, sel.items)
            alias_ast = {a.lower(): e for e, a in items if a}
            orders = []
            for o in sel.order_by:
                e = o.expr
                if isinstance(e, tuple) and e[0] == "lit" \
                        and isinstance(e[1], int):
                    e, _ = items[_ordinal(e[1], len(items))]
                elif isinstance(e, tuple) and e[0] == "col" \
                        and len(e[1]) == 1 \
                        and e[1][0].lower() in alias_ast:
                    e = alias_ast[e[1][0].lower()]
                c = self._expr(e)
                orders.append(c.asc(o.nulls_first) if o.ascending
                              else c.desc(o.nulls_first))
            df = df.order_by(*orders)
        df, alias_map = self._lower_projection(df, sel)
        if sel.limit is not None:
            df = df.limit(sel.limit)
        return df

    # -- window functions -------------------------------------------------
    def _hoist_windows(self, df, sel: Select):
        """Replace window-call subtrees (in SELECT, HAVING, ORDER BY) with
        refs to computed columns; all hoisted calls land in ONE Window
        plan node (the exec handles a list natively — one spill/concat
        pass instead of a stack of Window nodes)."""
        import copy
        from ..plan.logical import SortOrder, Window, WindowSpec

        def int_lit(ast, what):
            if isinstance(ast, tuple) and ast[0] == "lit" \
                    and isinstance(ast[1], int):
                return ast[1]
            if isinstance(ast, tuple) and ast[0] == "unary" \
                    and ast[1] == "-" and isinstance(ast[2], tuple) \
                    and ast[2][0] == "lit":
                return -ast[2][1]
            raise SqlError(f"{what} must be an integer literal")

        def scalar_lit(ast, what):
            if ast is None:
                return None
            if isinstance(ast, tuple) and ast[0] == "lit":
                return ast[1]
            if isinstance(ast, tuple) and ast[0] == "unary" \
                    and ast[1] == "-" and isinstance(ast[2], tuple) \
                    and ast[2][0] == "lit":
                return -ast[2][1]
            raise SqlError(f"{what} must be a literal")

        wins = []    # (fn, WindowSpec, name)

        def lower_win(ast):
            _, fn_node, parts, orders, frame = ast
            fname, args, distinct = fn_node[1], fn_node[2], fn_node[3]
            if distinct:
                raise SqlError(
                    f"DISTINCT is not supported in window {fname}()")
            if fname == "count" and (not args or args[0] == ("star",)):
                f = F.count_star()
            elif fname in _AGG_FNS:
                f = _AGG_FNS[fname](self._expr(args[0]))
            elif fname == "row_number":
                f = F.row_number()
            elif fname == "rank":
                f = F.rank()
            elif fname == "dense_rank":
                f = F.dense_rank()
            elif fname == "ntile":
                f = F.ntile(int_lit(args[0], "ntile bucket count"))
            elif fname in ("lag", "lead"):
                off = int_lit(args[1], f"{fname} offset") \
                    if len(args) > 1 else 1
                default = scalar_lit(args[2] if len(args) > 2 else None,
                                     f"{fname} default")
                mk = F.lag if fname == "lag" else F.lead
                f = mk(self._expr(args[0]), off, default)
            else:
                raise SqlError(f"{fname}() is not a window function")
            pks = [self._expr(p).expr for p in parts]
            obs = [SortOrder(self._expr(e).expr, asc, nf)
                   for e, asc, nf in orders]
            lframe = None
            if frame is not None:
                kind, lo, hi = frame
                if kind != "rows":
                    raise SqlError("only ROWS frames are supported")
                lframe = ("rows", lo, hi)
            name = f"__win{len(wins)}"
            fn = f.expr if hasattr(f, "expr") else f
            wins.append((fn, WindowSpec(pks, obs, lframe), name))
            return name

        def walk(ast):
            if ast is None or not isinstance(ast, tuple):
                return ast
            if ast[0] == "window":
                return ("col", (lower_win(ast),))
            if ast[0] == "fn":
                return ("fn", ast[1], [walk(a) for a in ast[2]], ast[3])
            if ast[0] == "case":
                return ("case", [(walk(c), walk(v)) for c, v in ast[1]],
                        walk(ast[2]) if ast[2] is not None else None)
            if ast[0] == "in":
                return ("in", walk(ast[1]), [walk(v) for v in ast[2]],
                        ast[3])
            return tuple(walk(x) if isinstance(x, tuple) else x
                         for x in ast)

        new_sel = copy.copy(sel)
        new_sel.items = [(walk(e), a) for e, a in sel.items]
        new_sel.having = walk(sel.having)
        from .parser import OrderItem
        new_sel.order_by = [OrderItem(walk(o.expr), o.ascending,
                                      o.nulls_first)
                            for o in sel.order_by]
        if wins:
            from ..api.dataframe import DataFrame
            df = DataFrame(df.session, Window(wins, df.plan))
        return df, new_sel

    # -- joins ----------------------------------------------------------
    def _in_subquery_keys(self, ast):
        """The frame of ``expr IN (select ...)``'s subquery: uncorrelated,
        one column. IN is a left semi join on it, with Spark's NULL
        semantics (a NULL probe value matches nothing, a NULL among the
        subquery's values matches nothing). NOT IN is null-aware (one NULL
        in the subquery empties the answer) and is refused by name: an
        anti join would answer it wrongly where a NULL is. So is a
        correlated subquery (a column of the outer query inside it)."""
        if ast[3]:
            raise SqlError(
                "NOT IN (select ...) is not supported: it needs the "
                "null-aware anti join; write NOT EXISTS or a LEFT ANTI "
                "JOIN where the subquery's column holds no NULL")
        keys = self.lower(ast[2])
        # the subquery lowers in a scope of its own and columns resolve
        # when a plan is made: a column of the OUTER query in one of its
        # predicates would surface there as a KeyError
        outer = _unresolved_filter_column(keys.plan)
        if outer is not None:
            raise SqlError(
                f"IN (select ...): column '{outer}' does not resolve "
                "inside the subquery; a correlated subquery is not "
                "supported")
        if len(keys.columns) != 1:
            raise SqlError(
                f"IN (select ...) needs a subquery of ONE column, this "
                f"one has {len(keys.columns)}: {keys.columns}")
        return keys

    def _side_of(self, ast, lcols, rcols, alias_cols, ralias=None):
        """Which join side a column AST belongs to, or (None, None).
        ``ralias`` is the alias of the table being joined in (the right
        side): a qualifier equal to it decides RIGHT, any other known
        qualifier decides LEFT — which keeps self-joins (identical column
        sets on both sides) unambiguous."""
        if not (isinstance(ast, tuple) and ast[0] == "col"):
            return None, None
        parts = ast[1]
        if len(parts) == 2:
            q = parts[0].lower()
            nm = alias_cols.get(q, {}).get(parts[1], parts[1])
            if ralias is not None and q == ralias:
                return ("r", nm) if nm in rcols else (None, None)
            if q in alias_cols:
                return ("l", nm) if nm in lcols else (None, None)
        nm = self._col_name(ast)
        if nm in lcols and nm not in rcols:
            return "l", nm
        if nm in rcols and nm not in lcols:
            return "r", nm
        return None, None

    def _claim_eq_pairs(self, conjuncts, lcols, rcols, alias_cols,
                        ralias=None):
        pairs, rest = [], []
        for c in conjuncts:
            if isinstance(c, tuple) and c[0] == "binop" and c[1] == "=":
                s1, n1 = self._side_of(c[2], lcols, rcols, alias_cols,
                                       ralias)
                s2, n2 = self._side_of(c[3], lcols, rcols, alias_cols,
                                       ralias)
                if s1 == "l" and s2 == "r":
                    pairs.append((n1, n2))
                    continue
                if s1 == "r" and s2 == "l":
                    pairs.append((n2, n1))
                    continue
            rest.append(c)
        return pairs, rest

    def _lower_join(self, left, right, j: Join, alias_cols):
        lcols, rcols = set(left.columns), set(right.columns)
        if j.kind == "cross":
            return left.join(right, how="cross")
        if j.using:
            # SQL USING keeps ONE copy of each key column: rename the right
            # side's keys, join, then emit coalesce(l.k, r.k) as the key
            # (for inner/left the left key suffices; right/full need the
            # coalesce so right-only rows keep their key values)
            keys = list(j.using)
            right = right.select(*[
                (F.col(c).alias(f"__using_{c}") if c in keys else F.col(c))
                for c in right.columns])
            out = left.join(right,
                            on=[(c, f"__using_{c}") for c in keys],
                            how=j.kind)
            if j.kind in ("leftsemi", "leftanti"):
                return out
            cols = []
            for c in out.columns:
                if c.startswith("__using_"):
                    continue
                if c in keys and j.kind in ("right", "full"):
                    cols.append(F.coalesce(F.col(c),
                                           F.col(f"__using_{c}")).alias(c))
                else:
                    cols.append(F.col(c))
            return out.select(*cols)
        # split conjunctive equalities into key pairs; rest is residual
        self._aliases = alias_cols
        ralias = (j.ref.alias or getattr(j.ref, "name", None))
        pairs, residual = self._claim_eq_pairs(
            _split_conjuncts(j.on), lcols, rcols, alias_cols,
            ralias.lower() if ralias else None)
        cond = None
        for r in residual:
            c = self._expr(r)
            cond = c if cond is None else (cond & c)
        if not pairs:
            raise SqlError("join requires at least one equality in ON")
        return left.join(right, on=pairs, how=j.kind, condition=cond)

    # -- projection / aggregation ---------------------------------------
    def _expand_items(self, df, items):
        out = []
        rev = {}
        for amap in getattr(self, "_aliases", {}).values():
            for orig, actual in amap.items():
                if actual != orig:
                    rev[actual] = orig
        for e, alias in items:
            if isinstance(e, tuple) and e[0] == "star":
                for c in df.columns:
                    out.append((("col", (c,)), rev.get(c)))
            elif isinstance(e, tuple) and e[0] == "qstar":
                amap = self._aliases.get(e[1].lower())
                if amap is None:
                    raise SqlError(f"unknown alias {e[1]}")
                arev = {actual: orig for orig, actual in amap.items()}
                for c in df.columns:
                    if c in arev:
                        out.append((("col", (c,)),
                                    arev[c] if arev[c] != c else None))
            else:
                out.append((e, alias))
        return out

    def _lower_projection(self, df, sel: Select):
        items = self._expand_items(df, sel.items)
        cols, alias_map = [], {}
        for e, alias in items:
            c = self._expr(e)
            name = alias or self._default_name(e, c)
            cols.append(c.alias(name))
            alias_map[name.lower()] = ("col", (name,))
        return df.select(*cols), alias_map

    def _lower_aggregate(self, df, sel: Select):
        items = self._expand_items(df, sel.items)
        alias_map = {a.lower(): e for e, a in items if a}
        # group keys: expressions, select aliases, or 1-based ordinals
        groupings = []
        for g in sel.group_by:
            if isinstance(g, tuple) and g[0] == "lit" \
                    and isinstance(g[1], int):
                e, alias = items[_ordinal(g[1], len(items))]
            elif isinstance(g, tuple) and g[0] == "col" \
                    and g[1][-1].lower() in alias_map:
                e, alias = alias_map[g[1][-1].lower()], g[1][-1]
            else:
                e, alias = g, None
            groupings.append((e, alias))

        agg_calls: Dict[str, object] = {}    # ast key -> (name, AggExpr)
        # grouping subtrees are available as values under their output
        # name (Spark analyzer semantics); filled after names are chosen
        group_map: Dict[str, str] = {}

        def hoist(ast):
            """Replace aggregate subtrees with refs to generated names."""
            if not isinstance(ast, tuple):
                return ast
            gk = group_map.get(_ast_key(ast))
            if gk is not None:
                return ("col", (gk,))
            if ast[0] == "fn" and ast[1] in _AGG_FNS:
                k = _ast_key(ast)
                if k not in agg_calls:
                    nm = f"__agg{len(agg_calls)}"
                    agg_calls[k] = (nm, self._agg_expr(ast, nm))
                return ("col", (agg_calls[k][0],))
            if ast[0] in ("fn",):
                return (ast[0], ast[1], [hoist(a) for a in ast[2]], ast[3])
            if ast[0] == "case":
                return ("case",
                        [(hoist(c), hoist(v)) for c, v in ast[1]],
                        hoist(ast[2]) if ast[2] is not None else None)
            if ast[0] == "in":
                return ("in", hoist(ast[1]), [hoist(v) for v in ast[2]],
                        ast[3])
            return tuple(hoist(x) if isinstance(x, tuple) else x
                         for x in ast)

        gb_cols = []
        gb_names = []
        for i, (e, alias) in enumerate(groupings):
            c = self._expr(e)
            name = alias or self._default_name(e, c)
            gb_cols.append(c.alias(name))
            gb_names.append(name)
            group_map[_ast_key(e)] = name
            if alias:
                group_map[_ast_key(("col", (alias,)))] = name

        proj_items = []
        for e, alias in items:
            proj_items.append((hoist(e), alias))
        having_ast = hoist(sel.having) if sel.having is not None else None
        order_hoisted = [OrderItem(hoist(o.expr), o.ascending,
                                   o.nulls_first)
                         for o in sel.order_by]
        aggs = [v[1] for v in agg_calls.values()]
        if gb_cols:
            df = df.group_by(*gb_cols).agg(*aggs)
        else:
            df = df.agg(*aggs)

        if having_ast is not None:
            df = df.filter(self._expr(having_ast))

        # ORDER BY runs BEFORE the final projection so it may reference
        # hoisted aggregates / group keys the projection would drop
        # (Spark's analyzer resolves ORDER BY against the pre-projection
        # aggregate output the same way). DISTINCT forces the post-
        # projection path: items must then come from the select list.
        order_handled = False
        if order_hoisted and not sel.distinct:
            sel_alias_map = {al.lower(): e for e, al in proj_items if al}
            orders = []
            for o in order_hoisted:
                e = o.expr
                if isinstance(e, tuple) and e[0] == "lit" \
                        and isinstance(e[1], int):
                    e, _ = proj_items[_ordinal(e[1], len(proj_items))]
                elif isinstance(e, tuple) and e[0] == "col" \
                        and len(e[1]) == 1 \
                        and e[1][0].lower() in sel_alias_map:
                    e = sel_alias_map[e[1][0].lower()]
                c = self._expr(e)
                orders.append(c.asc(o.nulls_first) if o.ascending
                              else c.desc(o.nulls_first))
            df = df.order_by(*orders)
            order_handled = True

        # final projection restores select order/names over agg output
        out_cols, final_alias = [], {}
        for (e, alias), (written, _) in zip(proj_items, items):
            c = self._expr(e)
            name = alias or _agg_call_name(written) \
                or self._default_name(e, c)
            out_cols.append(c.alias(name))
            final_alias[name.lower()] = ("col", (name,))
        df = df.select(*out_cols)
        return df, final_alias, order_handled

    def _agg_expr(self, ast, name):
        fn, args, distinct = ast[1], ast[2], ast[3]
        if fn == "count" and (not args or args[0] == ("star",)):
            return F.count_star().with_name(name)
        a = self._expr(args[0])
        if distinct:
            if fn == "count":
                return F.count_distinct(a).with_name(name)
            if fn == "sum":
                return F.sum_distinct(a).with_name(name)
            if fn in ("avg", "mean"):
                return F.avg_distinct(a).with_name(name)
            raise SqlError(f"DISTINCT not supported for {fn}")
        return _AGG_FNS[fn](a).with_name(name)

    # -- order by / limit ------------------------------------------------
    def _order_limit(self, df, order_by, limit, alias_map, names):
        if order_by:
            orders = []
            for o in order_by:
                e = o.expr
                if isinstance(e, tuple) and e[0] == "lit" \
                        and isinstance(e[1], int):
                    e = ("col", (names[_ordinal(e[1], len(names))],))
                elif isinstance(e, tuple) and e[0] == "col" \
                        and len(e[1]) == 1 \
                        and e[1][0].lower() in alias_map:
                    e = alias_map[e[1][0].lower()]
                c = self._expr(e)
                orders.append(c.asc(o.nulls_first) if o.ascending
                              else c.desc(o.nulls_first))
            df = df.order_by(*orders)
        if limit is not None:
            df = df.limit(limit)
        return df

    # -- scalar expressions ----------------------------------------------
    def _col_name(self, ast) -> str:
        parts = ast[1]
        if len(parts) == 2:
            # qualified ref: resolve through the alias map so t.k / r.k
            # reach the right (possibly collision-renamed) column
            amap = getattr(self, "_aliases", {}).get(parts[0].lower())
            if amap is not None:
                actual = amap.get(parts[1])
                if actual is None:
                    raise SqlError(
                        f"{parts[0]}.{parts[1]}: no such column (columns: "
                        f"{sorted(amap)})")
                return actual
        return parts[-1]

    def _default_name(self, ast, c) -> str:
        if isinstance(ast, tuple) and ast[0] == "col":
            return ast[1][-1]
        return c.expr.name_hint

    def _expr(self, ast) -> "F.Col":
        if not isinstance(ast, tuple):
            raise SqlError(f"bad expression node {ast!r}")
        kind = ast[0]
        if kind == "lit":
            return F.lit(ast[1])
        if kind == "datelit":
            return F.lit(np.datetime64(ast[1], "D"))
        if kind == "tslit":
            return F.lit(np.datetime64(ast[1].replace(" ", "T"), "us"))
        if kind == "col":
            return F.col(self._col_name(ast))
        if kind == "binop":
            op = ast[1]
            if op == "-" and isinstance(ast[3], tuple) \
                    and ast[3][0] == "interval":
                return self._interval_shift(ast[2], ast[3], -1)
            if op == "+" and isinstance(ast[3], tuple) \
                    and ast[3][0] == "interval":
                return self._interval_shift(ast[2], ast[3], +1)
            l, r = self._expr(ast[2]), self._expr(ast[3])
            return {
                "and": lambda: l & r, "or": lambda: l | r,
                "=": lambda: l == r, "<>": lambda: l != r,
                "!=": lambda: l != r, "<": lambda: l < r,
                "<=": lambda: l <= r, ">": lambda: l > r,
                ">=": lambda: l >= r, "+": lambda: l + r,
                "-": lambda: l - r, "*": lambda: l * r,
                "/": lambda: l / r, "%": lambda: l % r,
                "||": lambda: F.concat(l, r),
            }[op]()
        if kind == "unary":
            if ast[1] == "not":
                return ~self._expr(ast[2])
            return -self._expr(ast[2])
        if kind == "isnull":
            c = self._expr(ast[1]).isNull()
            return ~c if ast[2] else c
        if kind == "in":
            vals = []
            for v in ast[2]:
                if isinstance(v, tuple) and v[0] == "unary" \
                        and v[1] == "-" and isinstance(v[2], tuple) \
                        and v[2][0] == "lit":
                    vals.append(-v[2][1])
                    continue
                if not (isinstance(v, tuple) and v[0] == "lit"):
                    raise SqlError("IN list must be literals")
                vals.append(v[1])
            c = self._expr(ast[1]).isin(vals)
            return ~c if ast[3] else c
        if kind == "like":
            c = F.like(self._expr(ast[1]), ast[2])
            return ~c if ast[3] else c
        if kind == "in_subquery":
            raise SqlError(
                "IN (select ...) is supported as a conjunct of WHERE "
                "only (not under OR / NOT, in SELECT, HAVING or ON)")
        if kind == "between":
            e = self._expr(ast[1])
            c = (e >= self._expr(ast[2])) & (e <= self._expr(ast[3]))
            return ~c if ast[4] else c
        if kind == "case":
            branches = [(self._expr(c), self._expr(v)) for c, v in ast[1]]
            els = self._expr(ast[2]) if ast[2] is not None else F.lit(None)
            b = F.when(*branches[0])
            for c, v in branches[1:]:
                b = b.when(c, v)
            return b.otherwise(els)
        if kind == "cast":
            return F.cast(self._expr(ast[1]), _canon_type(ast[2]))
        if kind == "interval":
            raise SqlError("interval literal only valid in +/- with a date")
        if kind == "fn":
            fn, args, distinct = ast[1], ast[2], ast[3]
            if fn in _AGG_FNS:
                raise SqlError(
                    f"aggregate {fn}() not allowed in this context")
            if fn in _VARARG_FNS:
                return _VARARG_FNS[fn](*[self._expr(a) for a in args])
            if fn == "substring" or fn == "substr":
                a = [self._expr(args[0])] + [x[1] for x in args[1:]]
                return F.substring(*a)
            if fn == "round":
                scale = args[1][1] if len(args) > 1 else 0
                return F.round(self._expr(args[0]), scale)
            if fn == "date_add":
                return F.date_add(self._expr(args[0]),
                                  self._expr(args[1]))
            if fn == "date_sub":
                return F.date_sub(self._expr(args[0]),
                                  self._expr(args[1]))
            if fn == "datediff":
                return F.datediff(self._expr(args[0]),
                                  self._expr(args[1]))
            if fn == "nullif":
                if len(args) != 2:
                    raise SqlError("nullif requires (a, b)")
                return F.nullif(self._expr(args[0]),
                                self._expr(args[1]))
            if fn == "parse_url":
                if len(args) < 2:
                    raise SqlError("parse_url requires (url, part[, key])")
                part = _str_lit(args[1], "parse_url part")
                key = _str_lit(args[2], "parse_url key") \
                    if len(args) > 2 else None
                return F.parse_url(self._expr(args[0]), part, key)
            if fn in ("from_utc_timestamp", "to_utc_timestamp"):
                if len(args) != 2:
                    raise SqlError(f"{fn} requires (timestamp, tz)")
                mk = (F.from_utc_timestamp if fn == "from_utc_timestamp"
                      else F.to_utc_timestamp)
                return mk(self._expr(args[0]),
                          _str_lit(args[1], f"{fn} timezone"))
            if fn in _SCALAR_FNS:
                return _SCALAR_FNS[fn](self._expr(args[0]))
            raise SqlError(f"unknown function {fn}()")
        if kind in ("star", "qstar"):
            raise SqlError("* only valid as a top-level select item")
        raise SqlError(f"unsupported expression {kind}")

    def _interval_shift(self, base_ast, interval, sign):
        n, unit = interval[1], interval[2]
        days = {"day": 1, "week": 7}.get(unit)
        if days is None:
            raise SqlError(f"unsupported interval unit {unit}")
        b = self._expr(base_ast)
        return (F.date_add(b, n * days * sign) if sign > 0
                else F.date_sub(b, n * days))


def _unresolved_filter_column(plan) -> Optional[str]:
    """A column that a filter of ``plan`` reads and its input does not
    have, or None."""
    from ..plan import logical as L
    from ..plan.rewrites import _expr_refs
    if isinstance(plan, L.Filter):
        refs: set = set()
        _expr_refs(plan.condition, refs)
        missing = sorted(refs - set(plan.children[0].schema().names()))
        if missing:
            return missing[0]
    for c in plan.children:
        found = _unresolved_filter_column(c)
        if found is not None:
            return found
    return None


def _is_in_subquery(ast) -> bool:
    return isinstance(ast, tuple) and ast[0] == "in_subquery"


def _agg_call_name(ast) -> Optional[str]:
    """Spark's name for an unaliased select item that is one aggregate
    call over a column: ``sum(l_quantity)``; None for anything else."""
    if isinstance(ast, tuple) and ast[0] == "fn" and ast[1] in _AGG_FNS \
            and not ast[3] and len(ast[2]) == 1 \
            and isinstance(ast[2][0], tuple) and ast[2][0][0] == "col":
        return f"{ast[1]}({ast[2][0][1][-1]})"
    return None


def _str_lit(ast, what) -> str:
    if isinstance(ast, tuple) and ast[0] == "lit" \
            and isinstance(ast[1], str):
        return ast[1]
    raise SqlError(f"{what} must be a string literal")


def _ordinal(n: int, count: int) -> int:
    """1-based SQL ordinal -> 0-based index, range-checked."""
    if not 1 <= n <= count:
        raise SqlError(f"ordinal {n} out of range (1..{count})")
    return n - 1


def _split_conjuncts(ast) -> list:
    if ast is None:
        return []
    if isinstance(ast, tuple) and ast[0] == "binop" and ast[1] == "and":
        return _split_conjuncts(ast[2]) + _split_conjuncts(ast[3])
    return [ast]


def _and_all(conjuncts):
    out = None
    for c in conjuncts:
        out = c if out is None else ("binop", "and", out, c)
    return out


def _contains_window(ast) -> bool:
    if ast is None or not isinstance(ast, tuple):
        return False
    if ast[0] == "window":
        return True
    if ast[0] == "fn":
        return any(_contains_window(a) for a in ast[2])
    if ast[0] == "case":
        return any(_contains_window(c) or _contains_window(v)
                   for c, v in ast[1]) or _contains_window(ast[2])
    if ast[0] == "in":
        return _contains_window(ast[1]) or any(_contains_window(v)
                                               for v in ast[2])
    return any(_contains_window(x) for x in ast[1:]
               if isinstance(x, tuple))


def _contains_agg(ast) -> bool:
    if ast is None or not isinstance(ast, tuple):
        return False
    if ast[0] == "window":
        return False      # agg inside OVER() is a window fn, not a groupby
    if ast[0] == "fn":
        if ast[1] in _AGG_FNS:
            return True
        return any(_contains_agg(a) for a in ast[2])
    if ast[0] == "case":
        return any(_contains_agg(c) or _contains_agg(v)
                   for c, v in ast[1]) or _contains_agg(ast[2])
    if ast[0] == "in":
        return _contains_agg(ast[1]) or any(_contains_agg(v)
                                            for v in ast[2])
    return any(_contains_agg(x) for x in ast[1:] if isinstance(x, tuple))


def _canon_type(ty: str) -> str:
    t = ty.lower()
    return {"integer": "int", "long": "bigint", "varchar": "string",
            "char": "string", "real": "float", "numeric": "double",
            "decimal": "decimal(10,0)"}.get(t, t)


def _resolve_delta(session, ref, views, what):
    from ..delta.table import DeltaTable
    from .parser import TableRef
    if not isinstance(ref, TableRef):
        raise SqlError(f"{what} requires a registered Delta table name")
    dt = views.get(ref.name.lower())
    if dt is None:
        from .catalog import CatalogError
        try:
            return session.catalog.delta(ref.name)
        except CatalogError as e:
            raise SqlError(str(e))
    if not isinstance(dt, DeltaTable):
        raise SqlError(
            f"{ref.name} is not a registered Delta table (use "
            "session.register_delta_table(name, path))")
    return dt


def _metrics_df(session, metrics: dict):
    import pyarrow as pa
    return session.create_dataframe(
        pa.table({k: [v] for k, v in metrics.items()} or {"ok": [1]}))


def _dup_check(pairs, what, kind="SET"):
    seen = set()
    for c, _ in pairs:
        if c.lower() in seen:
            raise SqlError(f"duplicate {kind} column {c!r} in {what}")
        seen.add(c.lower())


def _lower_dml(session, stmt, views):
    from .parser import DeleteStmt, MergeStmt, UpdateStmt
    lw = _Lowerer(session, views)
    lw._aliases = {}
    if isinstance(stmt, DeleteStmt):
        dt = _resolve_delta(session, stmt.table, views, "DELETE")
        cond = lw._expr(stmt.where).expr if stmt.where is not None else None
        return _metrics_df(session, dt.delete(cond))
    if isinstance(stmt, UpdateStmt):
        dt = _resolve_delta(session, stmt.table, views, "UPDATE")
        _dup_check(stmt.assignments, "UPDATE")
        _target_col_check((c for c, _ in stmt.assignments),
                          dt.to_df().columns, "UPDATE SET")
        cond = lw._expr(stmt.where).expr if stmt.where is not None else None
        sets = {c: lw._expr(e).expr for c, e in stmt.assignments}
        return _metrics_df(session, dt.update(cond, sets))
    if isinstance(stmt, MergeStmt):
        return _lower_merge(session, stmt, views, lw)
    raise SqlError(f"unsupported statement {type(stmt).__name__}")


def _target_col_check(cols, target_cols, what):
    """Unknown SET/INSERT target columns are an analysis error (Spark
    raises too); the DeltaTable builders silently drop unmatched names."""
    known = set(target_cols)
    for c in cols:
        if c not in known:
            raise SqlError(f"{what}: column {c!r} does not exist in the "
                           f"target table (columns: {sorted(known)})")


def _lower_merge(session, stmt, views, lw):
    """MERGE lowering with qualifier resolution: source columns whose
    names collide with target columns are renamed before the merge, and
    t.col / s.col references resolve through the alias — an unqualified
    colliding name is an error (the engine's pair batch could otherwise
    silently bind it to the target side)."""
    dt = _resolve_delta(session, stmt.target, views, "MERGE INTO")
    src = lw._resolve_ref(stmt.source)
    talias = (stmt.target.alias or stmt.target.name).lower()
    salias = ((stmt.source.alias
               or getattr(stmt.source, "name", None)) or "__src").lower()
    tdf = dt.to_df()
    tcols = set(tdf.columns)
    scols = list(src.columns)
    colliding = {c for c in scols if c in tcols}
    rename = {c: f"__src_{c}" for c in colliding}
    if rename:
        src = src.select(*[
            (F.col(c).alias(rename[c]) if c in rename else F.col(c))
            for c in scols])

    def resolve(ast):
        """AST -> AST with qualified refs bound to a side and colliding
        names renamed on the source side."""
        if not isinstance(ast, tuple):
            return ast
        if ast[0] == "col":
            parts = ast[1]
            if len(parts) == 2:
                q, n = parts[0].lower(), parts[1]
                if q == salias:
                    return ("col", (rename.get(n, n),))
                if q == talias:
                    if n not in tcols:
                        raise SqlError(
                            f"{parts[0]}.{n}: no such target column")
                    return ("col", (n,))
                raise SqlError(f"unknown qualifier {parts[0]!r} in MERGE")
            n = parts[0]
            if n in colliding:
                raise SqlError(
                    f"ambiguous column {n!r} in MERGE (qualify with "
                    f"{talias}. or {salias}.)")
            return ast
        return tuple(resolve(x) if isinstance(x, tuple)
                     else ([resolve(y) if isinstance(y, tuple) else y
                            for y in x] if isinstance(x, list) else x)
                     for x in ast)

    mb = dt.merge(src, lw._expr(resolve(stmt.on)).expr)
    kinds = [c[0] for c in stmt.clauses]
    for kind in ("update", "delete"):
        if kinds.count(kind) > 1:
            raise SqlError(f"duplicate WHEN MATCHED THEN {kind.upper()} "
                           "clause")
    if "update" in kinds and "delete" in kinds:
        raise SqlError("MERGE with both WHEN MATCHED UPDATE and DELETE "
                       "clauses is not supported (conditional clauses "
                       "are unimplemented)")
    if kinds.count("insert") + kinds.count("insert_star") > 1:
        raise SqlError("duplicate WHEN NOT MATCHED THEN INSERT clause")
    for clause in stmt.clauses:
        if clause[0] == "update":
            _dup_check(clause[1], "MERGE UPDATE")
            _target_col_check((c for c, _ in clause[1]), tcols,
                              "MERGE UPDATE SET")
            mb = mb.when_matched_update(
                {c: lw._expr(resolve(e)).expr for c, e in clause[1]})
        elif clause[0] == "delete":
            mb = mb.when_matched_delete()
        elif clause[0] == "insert":
            if len(clause[1]) != len(clause[2]):
                raise SqlError(
                    f"MERGE INSERT: {len(clause[1])} columns but "
                    f"{len(clause[2])} values")
            _dup_check([(c, None) for c in clause[1]], "MERGE INSERT",
                       kind="INSERT")
            _target_col_check(clause[1], tcols, "MERGE INSERT")
            mb = mb.when_not_matched_insert(
                {c: lw._expr(resolve(e)).expr
                 for c, e in zip(clause[1], clause[2])})
        else:
            # insert_star: map source columns onto same-named target
            # columns (through any collision renames) with the target's
            # dtype cast — same contract as the builder's fallback
            from ..exprs.base import ColumnRef
            from ..exprs.cast import Cast
            tschema = tdf.schema
            mb = mb.when_not_matched_insert(
                {c: Cast(ColumnRef(rename.get(c, c)), tschema[c].dtype)
                 for c in scols if c in tcols})
    return _metrics_df(session, mb.execute())


def lower_statement(session, text: str, views: Dict[str, object]):
    from .parser import (CreateTableStmt, DeleteStmt, DropTableStmt,
                         MergeStmt, Select, ShowTablesStmt, UpdateStmt,
                         parse)
    stmt = parse(text)
    if isinstance(stmt, (DeleteStmt, MergeStmt, UpdateStmt)):
        return _lower_dml(session, stmt, views)
    if isinstance(stmt, (CreateTableStmt, DropTableStmt, ShowTablesStmt)):
        return _lower_catalog(session, stmt, views)
    return _Lowerer(session, views).lower(stmt)


def _lower_catalog(session, stmt, views):
    """Catalog DDL (ref GpuDeltaCatalogBase StagedTable /
    GpuDropTable): CREATE/DROP/SHOW over the session catalog."""
    import pyarrow as pa
    from .parser import CreateTableStmt, DropTableStmt
    from .catalog import CatalogError, TableExistsError
    cat = session.catalog
    if isinstance(stmt, CreateTableStmt):
        df = (_Lowerer(session, views).lower(stmt.select)
              if stmt.select is not None else None)
        try:
            if df is None and stmt.location is not None:
                try:
                    cat.register_table(stmt.name, stmt.location,
                                       stmt.format,
                                       partition_by=stmt.partition_by)
                except TableExistsError:
                    # IF NOT EXISTS suppresses ONLY the name collision
                    if not stmt.if_not_exists:
                        raise
            else:
                cat.create_table(stmt.name, df, format=stmt.format,
                                 partition_by=stmt.partition_by,
                                 path=stmt.location,
                                 if_not_exists=stmt.if_not_exists)
        except CatalogError as e:
            raise SqlError(str(e))
        return _metrics_df(session, {"created": 1})
    if isinstance(stmt, DropTableStmt):
        try:
            cat.drop_table(stmt.name, if_exists=stmt.if_exists)
        except CatalogError as e:
            raise SqlError(str(e))
        return _metrics_df(session, {"dropped": 1})
    rows = cat.list_tables(stmt.db)
    return session.create_dataframe(pa.table({
        "database": [r["database"] for r in rows],
        "tableName": [r["table"] for r in rows],
        "format": [r["format"] for r in rows],
        "path": [r["path"] for r in rows],
    }) if rows else pa.table({"database": pa.array([], pa.string()),
                              "tableName": pa.array([], pa.string()),
                              "format": pa.array([], pa.string()),
                              "path": pa.array([], pa.string())}))
