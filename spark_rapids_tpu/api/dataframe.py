"""DataFrame + session API (PySpark-shaped front-end over the TPU planner).

The reference plugs into Spark's session (SQLExecPlugin.scala:26); standalone
we provide the session. `TpuSession.conf` toggles behave like RapidsConf —
notably setting spark.rapids.tpu.sql.enabled=False runs the identical plan
through the host (CPU-oracle) path, which is how the differential test
harness mirrors the reference's with_cpu_session/with_gpu_session pattern
(integration_tests spark_session.py:145-151).
"""
from __future__ import annotations

from typing import List, Optional, Sequence, Union

from ..config import TpuConf
from ..exec.base import ExecContext
from ..exec.query import audit_leaks, plan_physical, run_query, tenant_of
from ..exprs.aggregates import AggregateExpression
from ..exprs.base import Alias, ColumnRef, Expression
from ..plan import logical as L
from ..plan.overrides import explain_potential_tpu_plan
from ..types import Schema, from_arrow
from .functions import Col, _to_expr, col as _col


def _as_schema(schema) -> Schema:
    """Schema | {name: DataType} | pyarrow.Schema -> Schema."""
    if isinstance(schema, Schema):
        return schema
    if isinstance(schema, dict):
        return Schema.of(**schema)
    import pyarrow as pa
    if isinstance(schema, pa.Schema):
        from ..types import StructField
        return Schema([StructField(f.name, from_arrow(f.type), f.nullable)
                       for f in schema])
    raise TypeError(f"cannot interpret schema {schema!r}")

__all__ = ["TpuSession", "DataFrame", "GroupedData"]


def _rename_refs(e: Expression, mapping: dict) -> Expression:
    """Deep-copied expression with ColumnRef names remapped (set-op
    right-side rename)."""
    import copy as _copy
    e = _copy.deepcopy(e)

    def walk(x):
        if isinstance(x, ColumnRef) and x.name in mapping:
            x.name = mapping[x.name]
        for c in getattr(x, "children", ()):
            walk(c)
    walk(e)
    return e


def _as_expr(c, alias_ok=True) -> Expression:
    if isinstance(c, str):
        return ColumnRef(c)
    return _to_expr(c)


class TpuSession:
    def __init__(self, conf: Optional[TpuConf] = None, mesh=None):
        if isinstance(conf, dict):
            conf = TpuConf(conf)
        self.conf = conf or TpuConf()
        self._ctx: Optional[ExecContext] = None
        #: temp-view registry consumed by session.sql()
        self._views: dict = {}
        from ..aux.profiler import Profiler
        self.profiler = Profiler(self.conf)
        #: the last query's runtime summary (ref GpuTaskMetrics
        #: accumulators); "operators" holds that query's operators only
        self.last_query_metrics = None
        #: rotating query-history log (ref spark.eventLog.*), None when
        #: spark.rapids.tpu.eventLog.enabled is off
        from ..metrics.events import EventLogWriter
        self.event_log = EventLogWriter.from_conf(self.conf)
        import itertools as _it
        self._query_seq = _it.count(1)
        #: tenant id this session's queries run as — the admission
        #: controller's priority/fairness unit and the memory manager's
        #: quota unit (sched/admission.py; empty conf = anonymous None)
        self.tenant = tenant_of(self.conf)
        #: fault_stats of the last LocalCluster.execute on this session
        #: (the event log's queryEnd picks it up)
        self.last_fault_stats = None
        #: AqeDecision summaries of the last query (aqe/__init__.py):
        #: a list of {"kind", "detail", "parts", "shuffle"?} dicts for
        #: every adaptive re-planning decision the run recorded —
        #: explain("analyze") renders them, queryEnd/clusterQuery
        #: records carry the kind->count
        self.last_aqe_decisions = None
        #: engine that ran the last materialized query: "device"/"host"
        self.last_placement = None
        #: coded PlacementReport summary of the last planned query
        #: ({"verdict", "codes", "ops", "estRows"} — plan/tags.py)
        self.last_placement_report = None
        #: device mesh for distributed execution: explicit, or built from
        #: spark.rapids.tpu.distributed.* conf (the planner lowers
        #: supported fragments onto it — parallel/planner.py)
        self.mesh = mesh
        #: True when the mesh was built from conf defaults rather than
        #: supplied explicitly: the planner only uses an auto mesh above
        #: the distributed.minRows threshold (distribution_gate)
        self.mesh_is_auto = False
        from ..bootstrap import STARTUP_CHECK
        if self.conf.get(STARTUP_CHECK):
            # BEFORE the auto-mesh device query: in the broken-backend
            # environments this diagnoses, jax.devices() below would
            # raise first and eat the diagnostic
            import logging
            from ..bootstrap import check_environment, engine_banner
            lg = logging.getLogger("spark_rapids_tpu.bootstrap")
            lg.info("%s", engine_banner())
            for r in check_environment(self.conf):
                lvl = (lg.info if r["level"] == "ok"
                       else lg.error if r["level"] == "fatal"
                       else lg.warning)
                lvl("startup check %s [%s]: %s", r["check"], r["level"],
                    r["detail"])
        if self.mesh is None:
            from ..parallel.planner import (DISTRIBUTED_ENABLED,
                                            DISTRIBUTED_NUM_DEVICES)
            if self.conf.get(DISTRIBUTED_ENABLED):
                import jax
                n = int(self.conf.get(DISTRIBUTED_NUM_DEVICES)) or None
                avail = len(jax.devices())
                # a 1-device mesh adds shard_map overhead for nothing —
                # distributed-by-default only engages with real devices
                if (n or avail) > 1 and avail > 1:
                    from ..parallel.mesh import make_mesh
                    self.mesh = make_mesh(n)
                    self.mesh_is_auto = True

    # ------------------------------------------------------------ lifecycle
    def close(self) -> None:
        """Release session resources. With
        spark.rapids.tpu.memory.leakDetection on, assert that no device
        buffer registration outlived its query — the MemoryCleaner
        shutdown leak check analog (ref Plugin.scala:573-588). Like the
        reference's shutdown hook, the audit is PROCESS-wide (buffer
        registries are per-memory-budget, not per-session): run it from
        single-session debug harnesses, not while other sessions have
        queries in flight."""
        if self._ctx is not None:
            self._ctx.close()
            self._ctx = None
        from ..config import LEAK_DETECTION
        if self.conf.get(LEAK_DETECTION):
            leaks = audit_leaks()
            if leaks:
                raise AssertionError(
                    f"{len(leaks)} leaked device buffer registration(s) "
                    f"at session close: {leaks[:5]} "
                    f"(set SRTPU_LEAK_DEBUG=1 for creation sites)")

    def __enter__(self) -> "TpuSession":
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        if exc_type is not None:
            # never mask the in-flight exception with a leak assertion
            # (leaks ARE likely mid-exception — batches were abandoned)
            if self._ctx is not None:
                self._ctx.close()
                self._ctx = None
            return
        self.close()

    # ------------------------------------------------------------- config
    def set_conf(self, key: str, value) -> "TpuSession":
        self.conf = self.conf.set(key, value)
        self._ctx = None
        from ..aux.profiler import Profiler
        self.profiler = Profiler(self.conf)
        from ..metrics.events import EventLogWriter
        self.event_log = EventLogWriter.from_conf(self.conf)
        self.tenant = tenant_of(self.conf)
        return self

    def exec_context(self) -> ExecContext:
        """The session's services (semaphore, memory manager). Plans run
        on contexts of their own under it: ``exec/query.py``."""
        if self._ctx is None:
            self._ctx = ExecContext(self.conf)
        return self._ctx

    # ------------------------------------------------------------- sources
    def create_dataframe(self, data, num_partitions: int = 1) -> "DataFrame":
        import pandas as pd
        import pyarrow as pa
        if isinstance(data, pd.DataFrame):
            table = pa.Table.from_pandas(data, preserve_index=False)
        elif isinstance(data, pa.Table):
            table = data
        elif isinstance(data, dict):
            table = pa.table(data)
        else:  # list of dicts / rows
            table = pa.Table.from_pylist(list(data))
        schema = Schema.of(**{f.name: from_arrow(f.type)
                              for f in table.schema})
        if num_partitions <= 1:
            parts = [table]
        else:
            n = table.num_rows
            step = -(-n // num_partitions)
            parts = [table.slice(i * step, step)
                     for i in range(num_partitions)]
        return DataFrame(self, L.LogicalScan(parts, schema))

    def range(self, start: int, end: Optional[int] = None, step: int = 1,
              num_partitions: int = 1) -> "DataFrame":
        if end is None:
            start, end = 0, start
        return DataFrame(self, L.RangeRel(start, end, step, num_partitions))

    def read_parquet(self, *paths: str,
                     columns: Optional[List[str]] = None) -> "DataFrame":
        from ..io.file_scan import apply_path_rules
        from ..io.parquet import parquet_schema, expand_paths
        files = expand_paths(apply_path_rules(self.conf, paths))
        schema = parquet_schema(files[0])
        return DataFrame(self, L.ParquetScan(files, schema, columns))

    def read_orc(self, *paths: str,
                 columns: Optional[List[str]] = None) -> "DataFrame":
        from ..io.file_scan import apply_path_rules
        from ..io.orc import expand_orc_paths, orc_schema
        files = expand_orc_paths(apply_path_rules(self.conf, paths))
        return DataFrame(self, L.OrcScan(files, orc_schema(files[0]),
                                         columns))

    def read_avro(self, *paths: str,
                  columns: Optional[List[str]] = None) -> "DataFrame":
        from ..io.avro import avro_schema, expand_avro_paths
        from ..io.file_scan import apply_path_rules
        files = expand_avro_paths(apply_path_rules(self.conf, paths))
        return DataFrame(self, L.AvroScan(files, avro_schema(files[0]),
                                          columns))

    def read_iceberg(self, path: str, columns: Optional[List[str]] = None,
                     snapshot_id: Optional[int] = None) -> "DataFrame":
        from ..iceberg import IcebergTable
        from ..io.file_scan import apply_path_rules
        path = apply_path_rules(self.conf, [path])[0]
        return IcebergTable(path).to_df(self, columns, snapshot_id)

    def read_delta(self, path: str, columns: Optional[List[str]] = None,
                   version: Optional[int] = None) -> "DataFrame":
        from ..delta import DeltaTable
        from ..io.file_scan import apply_path_rules
        path = apply_path_rules(self.conf, [path])[0]
        return DeltaTable(self, path).to_df(columns, version)

    def delta_table(self, path: str):
        from ..delta import DeltaTable
        return DeltaTable(self, path)

    def sql(self, text: str) -> "DataFrame":
        """Run a SQL query over registered temp views (ANSI analytics
        subset — see spark_rapids_tpu.sql)."""
        from ..sql import lower_statement
        from ..trace import core as trace_core
        tracer, mine = trace_core.query_tracer(self.conf)
        if tracer is None:
            return lower_statement(self, text, self._views)
        try:
            with tracer.span("plan.sql", cat="plan"):
                return lower_statement(self, text, self._views)
        finally:
            if mine:
                trace_core.release_query_tracer(tracer)

    def create_temp_view(self, name: str, df: "DataFrame") -> None:
        self._views[name.lower()] = df

    def drop_temp_view(self, name: str) -> None:
        self._views.pop(name.lower(), None)

    def register_delta_table(self, name: str, path: str) -> None:
        """Expose a Delta table to SQL, both as a readable view (always
        reading the CURRENT version) and as the target of UPDATE / DELETE
        / MERGE INTO statements. One registry: replacing the name with a
        temp view later redirects BOTH reads and DML resolution."""
        self._views[name.lower()] = self.delta_table(path)

    @property
    def catalog(self):
        """Named-table catalog over the conf'd warehouse directory (ref
        GpuDeltaCatalogBase / IcebergProviderImpl — see sql/catalog.py)."""
        from ..sql.catalog import Catalog
        return Catalog(self)

    def table(self, name: str) -> "DataFrame":
        """Resolve a table by name: temp views first, then the catalog
        ([db.]table). The SQL FROM clause resolves identically."""
        v = self._views.get(name.lower())
        if v is not None:
            from ..delta.table import DeltaTable
            return v.to_df() if isinstance(v, DeltaTable) else v
        return self.catalog.table(name)

    def read_csv(self, *paths: str, schema=None, header=True) -> "DataFrame":
        from ..io.file_scan import apply_path_rules
        from ..io.text import csv_to_tables
        tables, sch = csv_to_tables(apply_path_rules(self.conf, paths),
                                    schema, header)
        return DataFrame(self, L.LogicalScan(tables, sch))

    def read_hive_text(self, *paths: str, schema,
                       field_delim: str = "\x01",
                       null_value: str = "\\N") -> "DataFrame":
        """Hive text tables (LazySimpleSerDe ^A-delimited, \\N nulls —
        ref GpuHiveTextFileFormat / hive text scans)."""
        from ..io.file_scan import apply_path_rules
        from ..io.text import hive_text_to_tables
        tables, sch = hive_text_to_tables(
            apply_path_rules(self.conf, paths), schema,
            field_delim=field_delim, null_value=null_value)
        return DataFrame(self, L.LogicalScan(tables, sch))

    def read_json(self, *paths: str, schema=None) -> "DataFrame":
        from ..io.file_scan import apply_path_rules
        from ..io.text import json_to_tables
        tables, sch = json_to_tables(apply_path_rules(self.conf, paths),
                                     schema)
        return DataFrame(self, L.LogicalScan(tables, sch))


class DataFrame:
    def __init__(self, session: TpuSession, plan: L.LogicalPlan):
        self.session = session
        self.plan = plan

    # ------------------------------------------------------------ plan ops
    def select(self, *cols) -> "DataFrame":
        exprs = [_as_expr(c) for c in cols]
        gen = self._extract_generator(exprs)
        if gen is not None:
            return gen
        return DataFrame(self.session, L.Project(exprs, self.plan))

    def _extract_generator(self, exprs) -> Optional["DataFrame"]:
        """Spark's ExtractGenerator analyzer rule: a select list containing
        explode/posexplode/stack plans a Generate node, with the other
        expressions evaluated on top of its pass-through columns."""
        from ..exprs.base import Alias
        from ..exprs.generators import Generator
        gen_idx = [i for i, e in enumerate(exprs)
                   if isinstance(e, Generator)
                   or (isinstance(e, Alias) and isinstance(e.children[0],
                                                           Generator))]
        if not gen_idx:
            return None
        if len(gen_idx) > 1:
            raise ValueError("only one generator allowed per select clause")
        i = gen_idx[0]
        e = exprs[i]
        alias = e.name if isinstance(e, Alias) else None
        generator = e.children[0] if isinstance(e, Alias) else e
        others = [x for j, x in enumerate(exprs) if j != i]
        child_schema = self.plan.schema()
        needed, seen = [], set()
        for o in others:
            for r in o.references():
                if r not in seen:
                    seen.add(r)
                    needed.append(r)
        gen_fields = generator.generator_output(child_schema)
        out_names = None
        if alias is not None:
            if len(gen_fields) != 1:
                raise ValueError(
                    "single alias on a multi-column generator; use the "
                    "default names instead")
            out_names = [alias]
        plan = L.Generate(generator, needed, self.plan, out_names)
        gen_names = [f.name for f in (plan.schema().fields[len(needed):])]
        top = (others[:i] + [ColumnRef(n) for n in gen_names] + others[i:])
        return DataFrame(self.session, L.Project(top, plan))

    def with_column(self, name: str, c) -> "DataFrame":
        schema = self.plan.schema()
        exprs: List[Expression] = []
        replaced = False
        for f in schema.fields:
            if f.name == name:
                exprs.append(Alias(_as_expr(c), name))
                replaced = True
            else:
                exprs.append(ColumnRef(f.name))
        if not replaced:
            exprs.append(Alias(_as_expr(c), name))
        return DataFrame(self.session, L.Project(exprs, self.plan))

    withColumn = with_column

    def filter(self, cond) -> "DataFrame":
        return DataFrame(self.session, L.Filter(_as_expr(cond), self.plan))

    where = filter

    def group_by(self, *cols) -> "GroupedData":
        return GroupedData(self, [_as_expr(c) for c in cols])

    groupBy = group_by

    def agg(self, *aggs) -> "DataFrame":
        return GroupedData(self, []).agg(*aggs)

    def order_by(self, *orders) -> "DataFrame":
        from ..plan.logical import SortOrder
        os = []
        for o in orders:
            if isinstance(o, SortOrder):
                os.append(o)
            elif isinstance(o, str):
                os.append(SortOrder(ColumnRef(o), True))
            elif isinstance(o, Col):
                os.append(SortOrder(o.expr, True))
            else:
                os.append(o)
        return DataFrame(self.session, L.Sort(os, self.plan))

    orderBy = sort = order_by

    def sort_within_partitions(self, *orders) -> "DataFrame":
        df = self.order_by(*orders)
        df.plan.global_sort = False
        return df

    def with_window_column(self, name: str, fn, partition_by=(),
                           order_by=(), frame=None) -> "DataFrame":
        """Add a window-function column (ref GpuWindowExec). `fn` is a
        WindowFunction or AggregateExpression; frame is None (Spark default)
        or ('rows', lo, hi) with None = unbounded."""
        from ..plan.logical import SortOrder, Window, WindowSpec
        pks = [_as_expr(c) for c in partition_by]
        obs = []
        for o in order_by:
            if isinstance(o, SortOrder):
                obs.append(o)
            elif isinstance(o, str):
                obs.append(SortOrder(ColumnRef(o), True))
            else:
                obs.append(SortOrder(_to_expr(o), True))
        spec = WindowSpec(pks, obs, frame)
        return DataFrame(self.session,
                         Window([(fn, spec, name)], self.plan))

    def limit(self, n: int) -> "DataFrame":
        return DataFrame(self.session, L.GlobalLimit(n, self.plan))

    def union(self, other: "DataFrame") -> "DataFrame":
        return DataFrame(self.session, L.Union([self.plan, other.plan]))

    unionAll = union

    # ------------------------------------------------------- set operations
    def _nullsafe_key_pairs(self, other):
        """Per-column join-key expression pairs implementing SQL set-op
        equality over standard equi-joins: each column contributes an
        is-null flag plus a default-filled value, so NULLs match NULLs
        and never a real default. NaN == NaN and -0.0 == 0.0 come from
        the join key encoding itself (exec/encoding.py float
        canonicalization). Columns pair POSITIONALLY (SQL set-op
        semantics — names may differ between the sides); the output
        keeps the left side's names. Ref: Spark plans set ops as joins
        with EqualNullSafe keys (ReplaceOperators)."""
        from ..exprs import Coalesce, IsNull, Literal
        sch = self.plan.schema()
        osch = other.plan.schema()
        if len(sch.fields) != len(osch.fields):
            raise ValueError(
                "set operations require the same number of columns "
                f"({len(sch.fields)} vs {len(osch.fields)})")
        defaults = {"string": "", "boolean": False, "float": 0.0,
                    "double": 0.0}
        pairs = []
        for lf_, rf_ in zip(sch.fields, osch.fields):
            d = defaults.get(lf_.dtype.name, 0)
            l, r = ColumnRef(lf_.name), ColumnRef(rf_.name)
            pairs.append((IsNull(l), IsNull(r)))
            pairs.append((Coalesce(l, Literal(d, lf_.dtype)),
                          Coalesce(r, Literal(d, rf_.dtype))))
        return pairs

    def intersect(self, other: "DataFrame") -> "DataFrame":
        """Distinct rows present in BOTH frames (SQL INTERSECT; ref
        Spark ReplaceIntersectWithSemiJoin -> GpuShuffledHashJoin)."""
        return self.distinct().join(other,
                                    on=self._nullsafe_key_pairs(other),
                                    how="leftsemi")

    def subtract(self, other: "DataFrame") -> "DataFrame":
        """Distinct rows of this frame absent from ``other`` (SQL
        EXCEPT; ref ReplaceExceptWithAntiJoin)."""
        return self.distinct().join(other,
                                    on=self._nullsafe_key_pairs(other),
                                    how="leftanti")

    def _counted_setop(self, other, all_kind: str) -> "DataFrame":
        from . import functions as F
        from ..exprs import Coalesce, Literal
        from ..exprs.aggregates import CountStar
        from ..exprs.conditional import Least
        from ..exprs.arithmetic import Subtract
        names = [f.name for f in self.plan.schema().fields]
        rnames = [f.name for f in other.plan.schema().fields]
        lc = GroupedData(self, [ColumnRef(n) for n in names]).agg(
            CountStar().with_name("__so_l"))
        rc = GroupedData(other, [ColumnRef(n) for n in rnames]).agg(
            CountStar().with_name("__so_r"))
        # rename the right side wholesale: positional pairing, and the
        # joined frame must not carry duplicate names
        rmap = {rn: f"__so_r_{i}" for i, rn in enumerate(rnames)}
        rc = rc.select(*([F.col(rn).alias(rmap[rn]) for rn in rnames]
                         + [F.col("__so_r")]))
        lk = self._nullsafe_key_pairs(other)
        pairs = [(le, _rename_refs(re, rmap)) for le, re in lk]
        if all_kind == "intersect":
            j = lc.join(rc, on=pairs, how="inner")
            m = Least(ColumnRef("__so_l"), ColumnRef("__so_r"))
        else:                           # exceptAll
            j = lc.join(rc, on=pairs, how="left")
            m = Subtract(ColumnRef("__so_l"),
                         Coalesce(ColumnRef("__so_r"), Literal(0)))
        j = j.with_column("__so_m", Col(m)) \
             .filter(F.col("__so_m") > F.lit(0))
        # multiset semantics: replicate each row m times via an exploded
        # 1..m sequence (the ReplicateRows analog)
        j = j.select(*(names
                       + [F.explode(F.sequence(F.lit(1),
                                               F.col("__so_m")))
                          .alias("__so_i")]))
        return j.select(*names)

    def intersect_all(self, other: "DataFrame") -> "DataFrame":
        """Multiset INTERSECT ALL (ref ReplaceIntersectAll +
        GpuReplicateRowsExec)."""
        return self._counted_setop(other, "intersect")

    intersectAll = intersect_all

    def except_all(self, other: "DataFrame") -> "DataFrame":
        """Multiset EXCEPT ALL (ref ReplaceExceptAll +
        GpuReplicateRowsExec)."""
        return self._counted_setop(other, "except")

    exceptAll = except_all

    def join(self, other: "DataFrame", on=None, how: str = "inner",
             condition=None) -> "DataFrame":
        lk, rk = [], []
        on_list = [on] if isinstance(on, str) else (on or [])
        all_named = bool(on_list) and all(isinstance(k, str)
                                          for k in on_list)
        bc = "right" if getattr(other, "_broadcast_hint", False) else (
            "left" if getattr(self, "_broadcast_hint", False) else None)
        cond = _as_expr(condition) if condition is not None else None
        if not all_named:
            for k in on_list:
                if isinstance(k, str):
                    lk.append(ColumnRef(k))
                    rk.append(ColumnRef(k))
                else:  # (left_col, right_col) pair
                    lk.append(_as_expr(k[0]))
                    rk.append(_as_expr(k[1]))
            return DataFrame(self.session,
                             L.Join(self.plan, other.plan, how, lk, rk,
                                    cond, broadcast=bc))
        # USING-style join (shared key NAMES): PySpark emits ONE key
        # column, not both sides' duplicates — otherwise a later
        # col("k") can silently resolve to the right side's null-filled
        # copy, and the device/host twins disagree on duplicate-name
        # layouts (r5 ground-truth finding). Rename the right side's
        # columns before the join so both execs see distinct names,
        # then project: keys FIRST (PySpark order), one column per key
        # (left's values; right's for RIGHT joins; coalesced for FULL).
        # Colliding NON-key names keep both sides' data, the right one
        # under a "<name>_r" suffix (this engine's schemas are
        # name-addressed, so true duplicate names cannot be kept).
        named_keys = list(on_list)
        keyset = set(named_keys)
        lnames = [f.name for f in self.plan.schema().fields]
        rcols = [f.name for f in other.plan.schema().fields]
        taken = set(lnames) | set(rcols)
        rmap = {}
        for i, k in enumerate(named_keys):
            rmap[k] = f"__ju_{i}"
        for c in rcols:
            if c in keyset or c not in lnames:
                continue
            alt = f"{c}_r"
            while alt in taken:
                alt += "_"
            taken.add(alt)
            rmap[c] = alt
        right2 = other.select(*[_col(c).alias(rmap.get(c, c))
                                for c in rcols])
        lk = [ColumnRef(k) for k in named_keys]
        rk = [ColumnRef(rmap[k]) for k in named_keys]
        joined = DataFrame(self.session,
                           L.Join(self.plan, right2.plan, how, lk, rk,
                                  cond, broadcast=bc))
        jt = joined.plan.join_type
        if jt in ("leftsemi", "leftanti", "existence"):
            return joined          # left-only output: nothing to drop
        from ..exprs import Coalesce
        exprs = []
        for k in named_keys:       # keys first, PySpark column order
            if jt == "right":
                exprs.append(Alias(ColumnRef(rmap[k]), k))
            elif jt == "full":
                exprs.append(Alias(Coalesce(ColumnRef(k),
                                            ColumnRef(rmap[k])), k))
            else:
                exprs.append(ColumnRef(k))
        for c in lnames:
            if c not in keyset:
                exprs.append(ColumnRef(c))
        for c in rcols:
            if c in keyset:
                continue
            out_name = rmap.get(c, c)
            exprs.append(ColumnRef(out_name))
        return DataFrame(self.session, L.Project(exprs, joined.plan))

    def hint(self, name: str) -> "DataFrame":
        """Spark-style plan hint; only "broadcast" is meaningful (ref
        Spark's broadcast() function / GpuBroadcastHashJoinExec selection)."""
        df = DataFrame(self.session, self.plan)
        if name.lower() == "broadcast":
            df._broadcast_hint = True
        return df

    def map_in_pandas(self, fn, schema) -> "DataFrame":
        """Per-batch pandas transform (ref GpuMapInPandasExec)."""
        return DataFrame(self.session,
                         L.MapInPandas(fn, _as_schema(schema), self.plan))

    def create_or_replace_temp_view(self, name: str) -> None:
        self.session.create_temp_view(name, self)

    createOrReplaceTempView = create_or_replace_temp_view

    def cache(self) -> "DataFrame":
        """Materialize once into in-memory parquet-encoded batches
        (ref ParquetCachedBatchSerializer)."""
        from ..exec.cached import CACHE_CODEC, CachedRelation, \
            encode_batches
        codec = str(self.session.conf.get(CACHE_CODEC))
        blobs = self._execute_wrapped(
            lambda p, ctx: encode_batches(p.execute(ctx), codec))
        return DataFrame(self.session,
                         CachedRelation(blobs, self.schema))

    def sample(self, fraction: float, seed: int = 42) -> "DataFrame":
        return DataFrame(self.session, L.Sample(fraction, seed, self.plan))

    def repartition(self, n: Optional[int] = None, *cols) -> "DataFrame":
        """Shuffle into n partitions (hash by cols when given). With no
        explicit n, the count comes from
        spark.rapids.tpu.sql.shuffle.partitions and adaptive execution
        may coalesce small output partitions (Spark AQE semantics: an
        explicit n is a hard contract, an implicit one is advisory)."""
        import numpy as _np
        if n is not None and not isinstance(n, (int, _np.integer)):
            cols = (n,) + cols      # repartition(col, ...) form
            n = None
        keys = [_as_expr(c) for c in cols]
        mode = "hash" if keys else "roundrobin"
        plan = L.Repartition(int(n) if n is not None else None, keys,
                             self.plan, mode, adaptive_ok=n is None)
        return DataFrame(self.session, plan)

    def drop(self, *names: str) -> "DataFrame":
        keep = [f.name for f in self.plan.schema().fields
                if f.name not in names]
        return self.select(*keep)

    def distinct(self) -> "DataFrame":
        names = [f.name for f in self.plan.schema().fields]
        return GroupedData(self, [ColumnRef(n) for n in names]).agg()

    # ------------------------------------------------------------- actions
    @property
    def schema(self) -> Schema:
        return self.plan.schema()

    @property
    def columns(self) -> List[str]:
        return self.plan.schema().names()

    def _physical(self, conf=None):
        return plan_physical(self.session, self.plan, conf)

    def _execute_wrapped(self, consume):
        """Every materializing sink goes through here: the query under
        its ``query`` span. The span opens BEFORE planning and closes
        after the event log and the metrics, so every span of the query
        has it as an ancestor and carries its ordinal ``q``; while a
        ``jax.profiler`` session runs and no tracer is installed, the
        query gets an annotate-only tracer for its own length
        (trace/core.py). Tracing off: one conf lookup + one
        ``is_enabled()`` a query."""
        from ..trace import core as trace_core
        tracer, mine = trace_core.query_tracer(self.session.conf)
        if tracer is None:
            return run_query(self.session, self.plan, consume)
        q = next(self.session._query_seq)
        qargs = {}
        out_path = (str(self.session.conf.get(trace_core.TRACE_OUTPUT))
                    if tracer.recording else "")
        try:
            with tracer.span("query", cat="query", args=qargs, q=q):
                return run_query(self.session, self.plan, consume, q,
                                 qargs, out_path or None)
        finally:
            if mine:
                trace_core.release_query_tracer(tracer)
            if out_path:
                # written once the query span has closed, so that the
                # artifact holds it
                from ..trace.export import write_chrome_trace
                try:
                    write_chrome_trace(out_path, tracer)
                except Exception as e:  # noqa: BLE001
                    # tracing must never fail a query — but a silently
                    # missing artifact after paying the recording
                    # overhead must at least be loud
                    import logging
                    logging.getLogger(__name__).warning(
                        "could not write trace to %s: %s", out_path, e)

    def collect_arrow(self):
        return self._execute_wrapped(lambda p, ctx: p.collect(ctx))

    def to_pandas(self):
        return self.collect_arrow().to_pandas()

    def to_device_columns(self):
        """Zero-copy export of the result as device column batches for ML
        interop (ref ColumnarRdd.scala:42 convert(df): RDD[Table] used by
        XGBoost): a list of batches, each a dict name -> (data jax.Array,
        validity jax.Array), plus ``num_rows``. The arrays stay in HBM —
        no host round trip.

        The arrays keep their shape-bucket padded length: rows at index
        >= ``num_rows`` are padding whose data values are arbitrary (their
        validity lanes are False). Mask with ``validity`` or slice to
        ``num_rows`` before any reduction over the array."""
        def consume(physical, ctx):
            out = []
            for b in physical.execute(ctx):
                cols = {}
                for f, c in zip(b.schema.fields, b.columns):
                    if not hasattr(c, "data"):
                        raise ValueError(
                            f"column {f.name} is host-only "
                            f"({f.dtype.name}); device export requires "
                            "device-backed types")
                    cols[f.name] = (c.data, c.validity)
                out.append({"columns": cols, "num_rows": b.num_rows})
            return out
        return self._execute_wrapped(consume)

    toPandas = to_pandas

    def collect(self):
        return self.collect_arrow().to_pylist()

    def count(self) -> int:
        # count(*) as an aggregation: column pruning trims the scan to one
        # column and the aggregate's single-fetch path makes the whole
        # count one device round trip
        from .functions import count_star
        t = self.agg(count_star().with_name("n")).collect_arrow()
        return t.column("n")[0].as_py()

    def write_parquet(self, path: str, mode: str = "overwrite",
                      partition_by: Sequence[str] = ()):
        df = DataFrame(self.session,
                       L.WriteFile(path, "parquet", self.plan, mode,
                                   partition_by))
        return df.collect_arrow()

    def write_delta(self, path: str, mode: str = "overwrite",
                    partition_by: Sequence[str] = ()):
        from ..delta.table import write_delta
        write_delta(self.session, self, path, mode, partition_by)

    def write_orc(self, path: str, mode: str = "overwrite",
                  partition_by: Sequence[str] = ()):
        df = DataFrame(self.session,
                       L.WriteFile(path, "orc", self.plan, mode,
                                   partition_by))
        return df.collect_arrow()

    def write_csv(self, path: str, mode: str = "overwrite",
                  partition_by: Sequence[str] = ()):
        df = DataFrame(self.session,
                       L.WriteFile(path, "csv", self.plan, mode,
                                   partition_by))
        return df.collect_arrow()

    def write_hive_text(self, path: str, mode: str = "overwrite",
                        partition_by: Sequence[str] = (),
                        field_delim: Optional[str] = None,
                        null_value: Optional[str] = None):
        opts = {k: v for k, v in (("field_delim", field_delim),
                                  ("null_value", null_value))
                if v is not None}
        df = DataFrame(self.session,
                       L.WriteFile(path, "hive_text", self.plan, mode,
                                   partition_by, opts))
        return df.collect_arrow()

    def explain(self, mode: str = "physical") -> str:
        if mode == "logical":
            s = self.plan.tree_string()
        elif mode == "potential":
            s = explain_potential_tpu_plan(self.plan, self.session.conf)
        elif mode == "analyze":
            s = self._explain_analyze()
        elif mode == "placement":
            # the coded placement report (plan/tags.py): per-operator
            # device/host verdicts with reason codes — plans only,
            # never executes (docs/placement.md)
            physical = self._physical()
            rep = getattr(physical, "placement_report", None)
            s = (rep.render() if rep is not None
                 else "<no placement report>")
            decision = getattr(physical, "placement_decision", None)
            if decision:
                s = f"placement: {decision}\n" + s
        else:
            physical = self._physical()
            s = physical.tree_string()
            decision = getattr(physical, "placement_decision", None)
            if decision:
                # the cost optimizer's recorded WHY: a plan staying on
                # host explains itself from the EXPLAIN output alone
                s = f"placement: {decision}\n" + s
        print(s)
        return s

    def _explain_analyze(self) -> str:
        """EXPLAIN ANALYZE (the SQL-UI analog): EXECUTE the query
        through the full pipeline, then render the physical plan
        annotated with each operator's output rows, batches, cumulative
        and self time from ``ExecContext.metrics``
        (metrics/analyze.py)."""
        from ..metrics.analyze import render_analyzed_plan
        holder = {}

        def consume(physical, ctx):
            holder["physical"] = physical
            holder["ctx"] = ctx
            return physical.collect(ctx)

        self._execute_wrapped(consume)
        out = render_analyzed_plan(holder["physical"], holder["ctx"])
        rep = getattr(holder["physical"], "placement_report", None)
        if rep is not None and rep.counts():
            # the report's top-level verdict: ANALYZE output alone says
            # why (and how much of) the plan stayed on host
            out = (f"placement fallbacks [{rep.verdict}]: "
                   f"{rep.format_counts()}\n" + out)
        decision = getattr(holder["physical"], "placement_decision", None)
        if decision:
            out = f"placement: {decision}\n" + out
        if self.session.last_aqe_decisions:
            # the run's closed-taxonomy AQE decisions (ISSUE 19,
            # docs/aqe.md): ANALYZE output alone shows what the
            # adaptive layer changed about the plan it just executed
            lines = "".join(
                f"  {d['kind']}: {d['detail']}\n"
                for d in self.session.last_aqe_decisions)
            out += "adaptive execution decisions:\n" + lines
        return out


class GroupedData:
    def __init__(self, df: DataFrame, keys: List[Expression]):
        self.df = df
        self.keys = keys

    def agg(self, *aggs) -> DataFrame:
        parsed: List[AggregateExpression] = []
        for a in aggs:
            assert isinstance(a, AggregateExpression), \
                f"expected aggregate function, got {a!r}"
            parsed.append(a)
        return DataFrame(self.df.session,
                         L.Aggregate(self.keys, parsed, self.df.plan))

    def apply_in_pandas(self, fn, schema) -> DataFrame:
        """Per-group pandas transform (ref GpuFlatMapGroupsInPandasExec)."""
        names = []
        for k in self.keys:
            assert isinstance(k, ColumnRef), \
                "apply_in_pandas requires plain column keys"
            names.append(k.name)
        return DataFrame(self.df.session,
                         L.FlatMapGroupsInPandas(names, fn,
                                                 _as_schema(schema),
                                                 self.df.plan))

    # pyspark-style helpers
    def count(self) -> DataFrame:
        from ..exprs.aggregates import CountStar
        return self.agg(CountStar("count"))

    def sum(self, *names: str) -> DataFrame:
        from ..exprs.aggregates import Sum
        return self.agg(*[Sum(ColumnRef(n)) for n in names])

    def avg(self, *names: str) -> DataFrame:
        from ..exprs.aggregates import Average
        return self.agg(*[Average(ColumnRef(n)) for n in names])

    def min(self, *names: str) -> DataFrame:
        from ..exprs.aggregates import Min
        return self.agg(*[Min(ColumnRef(n)) for n in names])

    def max(self, *names: str) -> DataFrame:
        from ..exprs.aggregates import Max
        return self.agg(*[Max(ColumnRef(n)) for n in names])
