"""Logical plan nodes.

The reference is a plugin over Spark Catalyst and consumes Catalyst plans
(GpuOverrides.scala:4480 wrapAndTagPlan). Standalone on TPU we own the plan
representation: a small Catalyst-shaped logical algebra produced by the
DataFrame API (api/dataframe.py), tagged and converted by plan/overrides.py.
"""
from __future__ import annotations

from typing import List, Optional, Sequence, Tuple

from ..types import (BOOL, INT64, DataType, DecimalType, Schema,
                     StructField)
from ..exprs.base import Alias, ColumnRef, Expression

__all__ = ["LogicalPlan", "LogicalScan", "ParquetScan", "Project", "Filter",
           "Aggregate", "Sort", "SortOrder", "GlobalLimit", "LocalLimit",
           "Join", "Union", "RangeRel", "Sample", "Expand", "Window",
           "WindowSpec", "Repartition", "WriteFile"]


class LogicalPlan:
    children: List["LogicalPlan"] = []

    def schema(self) -> Schema:
        raise NotImplementedError

    def node_name(self) -> str:
        return type(self).__name__

    def tree_string(self, indent: int = 0) -> str:
        s = "  " * indent + self.describe() + "\n"
        for c in self.children:
            s += c.tree_string(indent + 1)
        return s

    def describe(self) -> str:
        return self.node_name()


class LogicalScan(LogicalPlan):
    """In-memory source: a list of Arrow tables (one per partition).
    ``columns`` (set by the pruning pass) narrows the scan without
    replacing the tables, so the exec's device cache keys on the original
    table object."""

    def __init__(self, tables, schema: Schema,
                 columns: Optional[List[str]] = None):
        self.tables = list(tables)
        self._schema = schema
        self.columns = columns
        self.children = []

    def schema(self) -> Schema:
        if self.columns is None:
            return self._schema
        return Schema([self._schema[c] for c in self.columns])

    def estimated_size_bytes(self) -> int:
        return sum(t.nbytes for t in self.tables)

    def describe(self):
        return f"LogicalScan[{len(self.tables)} partitions]({self.schema()})"


class ParquetScan(LogicalPlan):
    """File source (ref GpuParquetScan.scala). Partitioning into tasks is
    decided at physical planning (io/parquet.py)."""

    def __init__(self, paths: Sequence[str], schema: Schema,
                 columns: Optional[List[str]] = None):
        self.paths = list(paths)
        self._schema = schema
        self.columns = columns
        self.children = []

    def schema(self) -> Schema:
        if self.columns is None:
            return self._schema
        return Schema([self._schema[c] for c in self.columns])


    def describe(self):
        return f"{type(self).__name__}[{len(self.paths)} files]"


class OrcScan(ParquetScan):
    """ORC file source (ref GpuOrcScan.scala)."""


class AvroScan(ParquetScan):
    """Avro file source (ref GpuAvroScan.scala)."""


def _coerced(exprs, child: LogicalPlan) -> list:
    """Expressions as a node takes them: literals that meet a decimal
    operand typed as Spark types them (exprs/base.py)."""
    from ..exprs.base import coerce_decimal_literals
    schema = child.schema()
    if not any(isinstance(f.dtype, DecimalType) for f in schema.fields):
        return list(exprs)
    return [coerce_decimal_literals(e, schema) for e in exprs]


class Project(LogicalPlan):
    def __init__(self, exprs: Sequence[Expression], child: LogicalPlan):
        self.exprs = _coerced(exprs, child)
        self.children = [child]

    def schema(self) -> Schema:
        cs = self.children[0].schema()
        return Schema([StructField(e.name_hint, e.data_type(cs), True)
                       for e in self.exprs])

    def describe(self):
        return "Project[" + ", ".join(e.name_hint for e in self.exprs) + "]"


class Filter(LogicalPlan):
    def __init__(self, condition: Expression, child: LogicalPlan):
        self.condition = _coerced([condition], child)[0]
        self.children = [child]

    def schema(self) -> Schema:
        return self.children[0].schema()

    def describe(self):
        return f"Filter[{self.condition.name_hint}]"


class Aggregate(LogicalPlan):
    """groupings: list of (expr, name); aggs: list of AggregateExpression
    (exprs/aggregates.py) each with an output name."""

    def __init__(self, groupings, aggs, child: LogicalPlan,
                 many_groups_hint: bool = False,
                 int_key_cards=None):
        self.groupings = _coerced(groupings, child)
        self.aggs = _coerced(aggs, child)
        #: planner knows this aggregate is high-cardinality (e.g. the
        #: inner dedup pass of a DISTINCT expansion groups by the distinct
        #: value): the exec skips its optimistic single-fetch fast path,
        #: whose kernel compile + fetch would be wasted
        self.many_groups_hint = many_groups_hint
        #: per-grouping PROVEN cardinality: entry k (an int) promises the
        #: key's values lie in [0, k) — set only by rewrites that
        #: construct the key themselves (the union-of-aggregates branch
        #: id). Lets the exec use direct one-hot addressing with NO sort
        #: (the cudf hash-groupby trade; exec/aggregate.py direct core).
        self.int_key_cards = (list(int_key_cards)
                              if int_key_cards is not None
                              else [None] * len(self.groupings))
        self.children = [child]

    def schema(self) -> Schema:
        cs = self.children[0].schema()
        fields = [StructField(e.name_hint, e.data_type(cs), True)
                  for e in self.groupings]
        fields += [StructField(a.name_hint, a.data_type(cs), True)
                   for a in self.aggs]
        return Schema(fields)

    def describe(self):
        g = ", ".join(e.name_hint for e in self.groupings)
        a = ", ".join(a.name_hint for a in self.aggs)
        return f"Aggregate[keys=[{g}], aggs=[{a}]]"


class SortOrder:
    def __init__(self, expr: Expression, ascending: bool = True,
                 nulls_first: Optional[bool] = None):
        self.expr = expr
        self.ascending = ascending
        # Spark default: nulls first for asc, nulls last for desc
        self.nulls_first = nulls_first if nulls_first is not None else ascending

    def __repr__(self):
        d = "ASC" if self.ascending else "DESC"
        n = "NULLS FIRST" if self.nulls_first else "NULLS LAST"
        return f"{self.expr.name_hint} {d} {n}"


class Sort(LogicalPlan):
    def __init__(self, orders: Sequence[SortOrder], child: LogicalPlan,
                 global_sort: bool = True, limit: Optional[int] = None):
        self.orders = list(orders)
        self.global_sort = global_sort
        #: the rows a GlobalLimit above keeps (rewrites.limit_into_sort)
        self.limit = limit
        self.children = [child]

    def schema(self) -> Schema:
        return self.children[0].schema()

    def describe(self):
        top = "" if self.limit is None else f"; first {self.limit}"
        return f"Sort[{', '.join(map(repr, self.orders))}{top}]"


class GlobalLimit(LogicalPlan):
    def __init__(self, n: int, child: LogicalPlan):
        self.n = n
        self.children = [child]

    def schema(self):
        return self.children[0].schema()

    def describe(self):
        return f"GlobalLimit[{self.n}]"


class LocalLimit(GlobalLimit):
    def describe(self):
        return f"LocalLimit[{self.n}]"


class Join(LogicalPlan):
    JOIN_TYPES = ("inner", "left", "right", "full", "leftsemi", "leftanti",
                  "cross", "existence")

    def __init__(self, left: LogicalPlan, right: LogicalPlan,
                 join_type: str, left_keys: Sequence[Expression] = (),
                 right_keys: Sequence[Expression] = (),
                 condition: Optional[Expression] = None,
                 broadcast: Optional[str] = None):
        jt = join_type.lower().replace("_", "")
        if jt == "leftouter":
            jt = "left"
        if jt == "rightouter":
            jt = "right"
        if jt in ("fullouter", "outer"):
            jt = "full"
        if jt == "semi":
            jt = "leftsemi"
        if jt == "anti":
            jt = "leftanti"
        assert jt in self.JOIN_TYPES, join_type
        assert broadcast in (None, "left", "right"), broadcast
        self.join_type = jt
        self.left_keys = list(left_keys)
        self.right_keys = list(right_keys)
        self.condition = condition
        self.broadcast = broadcast
        self.children = [left, right]

    def schema(self) -> Schema:
        l, r = self.children[0].schema(), self.children[1].schema()
        if self.join_type in ("leftsemi", "leftanti"):
            return l
        if self.join_type == "existence":
            return Schema(list(l.fields) +
                          [StructField("exists", BOOL, nullable=False)])
        # outer sides become nullable
        return Schema(list(l.fields) + list(r.fields))

    def describe(self):
        k = ", ".join(f"{a.name_hint}={b.name_hint}"
                      for a, b in zip(self.left_keys, self.right_keys))
        return f"Join[{self.join_type}, keys=({k})]"


class Union(LogicalPlan):
    def __init__(self, children: Sequence[LogicalPlan]):
        self.children = list(children)

    def schema(self):
        return self.children[0].schema()

    def describe(self):
        return f"Union[{len(self.children)}]"


class RangeRel(LogicalPlan):
    """ref GpuRangeExec (basicPhysicalOperators.scala:1137)."""

    def __init__(self, start: int, end: int, step: int = 1,
                 num_partitions: int = 1, name: str = "id"):
        self.start, self.end, self.step = start, end, step
        self.num_partitions = num_partitions
        self.name = name
        self.children = []

    def schema(self):
        return Schema([StructField(self.name, INT64, False)])

    def describe(self):
        return f"Range[{self.start},{self.end},{self.step}]"


class Sample(LogicalPlan):
    def __init__(self, fraction: float, seed: int, child: LogicalPlan):
        self.fraction = fraction
        self.seed = seed
        self.children = [child]

    def schema(self):
        return self.children[0].schema()


class Expand(LogicalPlan):
    """ref GpuExpandExec: each input row emits one row per projection set."""

    def __init__(self, projections: Sequence[Sequence[Expression]],
                 names: Sequence[str], child: LogicalPlan):
        self.projections = [list(p) for p in projections]
        self.names = list(names)
        self.children = [child]

    def schema(self):
        cs = self.children[0].schema()
        return Schema([StructField(n, e.data_type(cs), True)
                       for n, e in zip(self.names, self.projections[0])])


class BranchAlign(LogicalPlan):
    """Assemble the union-of-aggregates result: the child is a grouped
    aggregate keyed by a branch-id column (first field); output has
    exactly ``n`` rows in branch order, with empty branches filled by
    empty-aggregate defaults (count -> 0, everything else -> NULL). Rows
    are tiny (one per branch): a host op by construction."""

    def __init__(self, n: int, fill_zero: Sequence[bool],
                 child: LogicalPlan):
        self.n = n
        self.fill_zero = list(fill_zero)
        self.children = [child]

    def schema(self) -> Schema:
        cs = self.children[0].schema()
        return Schema(list(cs.fields)[1:])       # drop the bid key

    def describe(self):
        return f"BranchAlign[n={self.n}]"


class DistinctFlag(LogicalPlan):
    """Appends a boolean column that is True on the stream-global FIRST
    occurrence of each (key_exprs, value_expr) combination and False
    elsewhere (NULL values never flag). Produced by the hash-distinct
    rewrite (rewrites.py _rewrite_distinct_hash); executed by the
    sort-free persistent-hash-table operator (exec/distinct_flag.py).
    Reference analog: cudf's hash-based distinct aggregation that the
    reference lowers count-distinct onto."""

    def __init__(self, key_exprs: Sequence[Expression],
                 value_expr: Expression, flag_name: str,
                 child: LogicalPlan):
        self.key_exprs = list(key_exprs)
        self.value_expr = value_expr
        self.flag_name = flag_name
        self.children = [child]

    def schema(self) -> Schema:
        from ..types import BOOL
        cs = self.children[0].schema()
        return Schema(list(cs.fields)
                      + [StructField(self.flag_name, BOOL, True)])

    def describe(self):
        k = ", ".join(e.name_hint for e in self.key_exprs)
        return (f"DistinctFlag[keys=[{k}], "
                f"value={self.value_expr.name_hint}]")


class Generate(LogicalPlan):
    """Generator application: explode/posexplode/stack (ref GpuGenerateExec).

    required_cols: child column names passed through alongside the generator
    output (ref requiredChildOutput)."""

    def __init__(self, generator, required_cols: Sequence[str],
                 child: LogicalPlan, output_names: Optional[Sequence[str]] = None):
        self.generator = generator
        self.required_cols = list(required_cols)
        self.output_names = list(output_names) if output_names else None
        self.children = [child]

    def schema(self):
        cs = self.children[0].schema()
        gen_fields = self.generator.generator_output(cs)
        if self.output_names:
            gen_fields = [StructField(n, f.dtype, f.nullable)
                          for n, f in zip(self.output_names, gen_fields)]
        return Schema([cs.fields[cs.index_of(c)] for c in self.required_cols]
                      + gen_fields)


class WindowSpec:
    def __init__(self, partition_by: Sequence[Expression] = (),
                 order_by: Sequence[SortOrder] = (),
                 frame: Optional[Tuple] = None):
        self.partition_by = list(partition_by)
        self.order_by = list(order_by)
        self.frame = frame  # (kind, lower, upper) or None


class Window(LogicalPlan):
    """ref window/GpuWindowExec.scala:146."""

    def __init__(self, window_exprs, child: LogicalPlan):
        # window_exprs: list of (agg_or_rank_expr, WindowSpec, out_name)
        self.window_exprs = list(window_exprs)
        self.children = [child]

    def schema(self):
        cs = self.children[0].schema()
        fields = list(cs.fields)
        for e, spec, name in self.window_exprs:
            fields.append(StructField(name, e.data_type(cs), True))
        return Schema(fields)


class Repartition(LogicalPlan):
    """Exchange request (ref GpuShuffleExchangeExecBase).

    ``num_partitions`` None means "use the conf default"; only then may
    adaptive execution coalesce the output (``adaptive_ok``)."""

    def __init__(self, num_partitions: Optional[int],
                 keys: Sequence[Expression], child: LogicalPlan,
                 mode: str = "hash", adaptive_ok: bool = False):
        if num_partitions is not None and num_partitions <= 0:
            raise ValueError(
                f"repartition count must be positive, got {num_partitions}")
        self.num_partitions = num_partitions
        self.adaptive_ok = adaptive_ok
        self.keys = list(keys)
        self.mode = mode  # hash / roundrobin / range / single
        self.children = [child]

    def schema(self):
        return self.children[0].schema()

    def describe(self):
        return f"Repartition[{self.mode}, n={self.num_partitions}]"


class WriteFile(LogicalPlan):
    def __init__(self, path: str, file_format: str, child: LogicalPlan,
                 mode: str = "overwrite", partition_by: Sequence[str] = (),
                 options: Optional[dict] = None):
        self.path = path
        self.file_format = file_format
        self.mode = mode
        self.partition_by = list(partition_by)
        #: format-specific writer options (e.g. hive text field_delim /
        #: null_value) so reads and writes can round-trip non-defaults
        self.options = dict(options or {})
        self.children = [child]

    def schema(self):
        return self.children[0].schema()


class MapInPandas(LogicalPlan):
    """ref GpuMapInPandasExec (execution/python/)."""

    def __init__(self, fn, out_schema: Schema, child: LogicalPlan):
        self.fn = fn
        self._out = out_schema
        self.children = [child]

    def schema(self) -> Schema:
        return self._out

    def describe(self):
        return f"MapInPandas[{getattr(self.fn, '__name__', 'fn')}]"


class FlatMapGroupsInPandas(LogicalPlan):
    """ref GpuFlatMapGroupsInPandasExec."""

    def __init__(self, keys, fn, out_schema: Schema, child: LogicalPlan):
        self.keys = list(keys)
        self.fn = fn
        self._out = out_schema
        self.children = [child]

    def schema(self) -> Schema:
        return self._out

    def describe(self):
        return f"FlatMapGroupsInPandas[keys={self.keys}]"
