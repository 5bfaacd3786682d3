"""The post-conversion coalesce rule (plan/overrides.py:insert_coalesce):
where a scan or a streaming broadcast join hands under-filled batches to a
per-batch operator, the scan fills its own batches (and caches them
filled) and the join gets ``CoalesceBatches[TargetSize]`` above it; where
neither does the plan is the one it was."""
import numpy as np
import pyarrow as pa
import pytest

from harness import OPERATOR_CONF, tpu_session
from spark_rapids_tpu.api import functions as F
from spark_rapids_tpu.exec.aggregate import TpuHashAggregateExec
from spark_rapids_tpu.exec.base import ExecContext
from spark_rapids_tpu.exec.basic import (CoalesceBatchesExec,
                                         InMemoryScanExec, TpuProjectExec)
from spark_rapids_tpu.exec.joins import TpuBroadcastHashJoinExec
from spark_rapids_tpu.exec.wholestage import WholeStageExec
from spark_rapids_tpu.plan import overrides

#: q3's pins (perfbench/configs/tpcds_sf1_star.json): the operator
#: pipeline, 4096-row partitions against a target of 8192
Q3_CONF = {**OPERATOR_CONF, "spark.rapids.tpu.sql.batchSizeRows": 8192}
PART = 4096
GOAL = "TargetSize(rows=8192, bytes=536870912)"


def _star(s, rows: int, parts: int, seed: int = 7):
    """A q3-shaped star: a fact with NULL keys in ``parts`` partitions and
    two small dimensions with unique keys."""
    rng = np.random.RandomState(seed)
    fact = pa.table({
        "f_date": pa.array(rng.randint(0, 60, rows), mask=rng.rand(rows) < .05),
        "f_item": pa.array(rng.randint(0, 40, rows)),
        "f_price": pa.array(np.round(rng.rand(rows) * 100, 2),
                            mask=rng.rand(rows) < .05)})
    dates = pa.table({"d_sk": pa.array(np.arange(60)),
                      "d_year": pa.array((1998 + np.arange(60) // 12)
                                         .astype(np.int32)),
                      "d_moy": pa.array((np.arange(60) % 12 + 1)
                                        .astype(np.int32))})
    items = pa.table({"i_sk": pa.array(np.arange(40)),
                      "i_brand": pa.array((np.arange(40) % 7)
                                          .astype(np.int32))})
    s.create_dataframe(fact, num_partitions=parts) \
        .create_or_replace_temp_view("fact")
    s.create_dataframe(dates).create_or_replace_temp_view("dates")
    s.create_dataframe(items).create_or_replace_temp_view("items")
    return s.sql(
        "SELECT d_year, i_brand, sum(f_price) AS total FROM dates, fact, "
        "items WHERE d_sk = f_date AND f_item = i_sk AND d_moy = 11 "
        "GROUP BY d_year, i_brand ORDER BY d_year, i_brand")


def _nodes(node, cls):
    out = [node] if isinstance(node, cls) else []
    for c in node.children:
        out += _nodes(c, cls)
    return out


def _without_rule(monkeypatch):
    monkeypatch.setattr(overrides, "insert_coalesce", lambda p, conf: p)


# (rows, partitions): even; odd; odd with a short last partition; a
# last partition of one row
@pytest.mark.parametrize("rows,parts", [
    (4 * PART, 4), (5 * PART, 5), (5 * PART - 1500, 5), (2 * PART + 1, 3)])
def test_star_plan_fills_the_lowest_joins_stream(rows, parts):
    """The fact scan on the stream side of the lowest join fills its own
    batches, with no operator between them; that join sees half as many
    batches (rounded up), each in the 8,192-row bucket with at most 8,192
    live rows, no row lost or reordered against the unfilled scan."""
    s = tpu_session(Q3_CONF)
    physical = _star(s, rows, parts)._physical()
    joins = _nodes(physical, TpuBroadcastHashJoinExec)
    lowest = joins[-1]
    assert not _nodes(lowest, CoalesceBatchesExec)
    (scan,) = [c for c in lowest.children if isinstance(c, InMemoryScanExec)]
    assert scan.describe() == \
        f"InMemoryScan[{parts} partitions; filled to {GOAL}]"
    ctx = ExecContext(parent=s.exec_context())
    try:
        merged = list(scan.execute(ctx))
        scan.fill = None
        scanned = list(scan.execute(ctx))
    finally:
        ctx.close()
    assert len(scanned) == parts and len(merged) == -(-parts // 2)
    assert all(b.padded_len == 8192 and b.num_rows <= 8192 for b in merged)
    assert [b.num_rows for b in merged] == [
        sum(b.num_rows for b in scanned[i:i + 2])
        for i in range(0, parts, 2)]
    assert pa.concat_tables([b.to_arrow() for b in merged]).equals(
        pa.concat_tables([b.to_arrow() for b in scanned]))
    assert [b.meta for b in merged] == [b.meta for b in scanned[::2]]
    # an odd one out is uploaded alone: the batch the scan made
    if parts % 2:
        one, alone = merged[-1], scanned[-1]
        assert (one.num_rows, one.padded_len, one.meta) == \
            (alone.num_rows, alone.padded_len, alone.meta)
        assert one.to_arrow().equals(alone.to_arrow())


def test_full_partitions_fill_the_joins_and_not_the_scan():
    """Two partitions of exactly the target: nothing to merge above the
    scan, and each join still hands on what it kept of a batch."""
    physical = _star(tpu_session(Q3_CONF), 2 * 8192, 2)._physical()
    filled = [co.children[0] for co in _nodes(physical, CoalesceBatchesExec)]
    assert filled == _nodes(physical, TpuBroadcastHashJoinExec)
    assert all(scan.fill is None
               for scan in _nodes(physical, InMemoryScanExec))


def test_star_plan_fills_above_both_joins():
    """Each streaming broadcast join of the star hands its output to
    another per-batch operator (the next join's stream side through a
    projection, the aggregate), so each gets the operator directly above
    it, and ``explain`` shows both and the filling scan."""
    physical = _star(tpu_session(Q3_CONF), 4 * PART, 4)._physical()
    upper, lowest = _nodes(physical, TpuBroadcastHashJoinExec)
    agg = _nodes(physical, TpuHashAggregateExec)[-1]
    above_upper = agg.children[0]
    assert isinstance(above_upper, CoalesceBatchesExec) \
        and above_upper.children == [upper]
    # through the projection between the joins, on the stream side
    fed = upper.children[0]
    while not isinstance(fed, CoalesceBatchesExec):
        assert isinstance(fed, (TpuProjectExec, WholeStageExec)), fed
        (fed,) = fed.children
    assert fed.children == [lowest]
    assert len(_nodes(physical, CoalesceBatchesExec)) == 2
    tree = physical.tree_string()
    assert tree.count(f"CoalesceBatches[{GOAL}]") == 2
    assert tree.count(f"InMemoryScan[4 partitions; filled to {GOAL}]") == 1


def _join_into(s, above: str):
    """A streaming broadcast join whose output reaches ``above``."""
    rng = np.random.RandomState(3)
    fact = pa.table({"f_k": pa.array(rng.randint(0, 50, 6000)),
                     "f_v": pa.array(rng.rand(6000))})
    dim = pa.table({"d_k": pa.array(np.arange(50)),
                    "d_g": pa.array((np.arange(50) % 5).astype(np.int32))})
    big = pa.table({"b_g": pa.array(rng.randint(0, 5, 7000).astype(np.int32)),
                    "b_v": pa.array(rng.rand(7000))})
    joined = s.create_dataframe(fact, num_partitions=3).join(
        F.broadcast(s.create_dataframe(dim)), on=[("f_k", "d_k")])
    if above == "sink":
        return joined
    if above == "sort":
        return joined.sort("f_v")
    # a join of two big sides: the threshold keeps both off the broadcast
    return joined.join(s.create_dataframe(big, num_partitions=2),
                       on=[("d_g", "b_g")])


@pytest.mark.parametrize("above", ["hash-join", "sort", "sink"])
def test_a_join_feeding_a_materializing_operator_gets_none(above,
                                                           monkeypatch):
    """``HashJoin``, a sort and the sink take their whole input themselves
    (the join reads its sides' counts in one fetch): no operator above the
    broadcast join, and the plan is the one the rule's absence gives."""
    # two of the scan's 2,000-row batches do not fit 3,000: nothing to
    # fill below the join either
    conf = {**Q3_CONF, "spark.rapids.tpu.sql.batchSizeRows": 3000,
            "spark.rapids.tpu.sql.autoBroadcastJoinThreshold": 0}
    physical = _join_into(tpu_session(conf), above)._physical()
    tree = physical.tree_string()
    assert "BroadcastHashJoin" in tree and "CoalesceBatches" not in tree
    if above == "hash-join":
        assert "\n* HashJoin[" in "\n" + tree.replace("  ", "")
    _without_rule(monkeypatch)
    assert _join_into(tpu_session(conf), above)._physical().tree_string() \
        == tree


def test_strings_from_the_stream_side_stop_the_join_site():
    """A string column of the stream side has a dictionary per stream
    batch: its concat would go through Arrow, so no operator; the same
    column from the build side shares ONE dictionary and gets it."""
    # two of the scan's 2,000-row batches pass 3,000: the join sees three
    s = tpu_session({**Q3_CONF, "spark.rapids.tpu.sql.batchSizeRows": 3000})
    fact = pa.table({"f_k": pa.array(np.arange(6000) % 50),
                     "f_s": pa.array([f"s{i % 9}" for i in range(6000)])})
    dim = pa.table({"d_k": pa.array(np.arange(50)),
                    "d_s": pa.array([f"d{i % 4}" for i in range(50)])})
    f = s.create_dataframe(fact, num_partitions=3)
    d = F.broadcast(s.create_dataframe(dim))
    joined = f.join(d, on=[("f_k", "d_k")])
    stream_s = joined.group_by("f_s").agg(F.count(F.col("f_k")).with_name("n"))
    assert "CoalesceBatches" not in stream_s._physical().tree_string()
    build_s = joined.select("f_k", "d_s").group_by("d_s").agg(
        F.count(F.col("f_k")).with_name("n"))
    tree = build_s._physical().tree_string()
    assert "CoalesceBatches[TargetSize" in tree, tree
    assert sorted((r["d_s"], r["n"]) for r in build_s.collect()) == \
        [(f"d{i}", sum(1 for k in np.arange(6000) % 50 if k % 4 == i))
         for i in range(4)]


def test_star_answers_equal_the_host_engines(monkeypatch):
    """With the three operators, without the rule, and on the host engine:
    the same keys in the same order, the sums to float64's rounding."""
    rows, parts = 5 * PART - 900, 5
    got = _star(tpu_session(Q3_CONF), rows, parts).collect_arrow()
    host = _star(tpu_session({"spark.rapids.tpu.sql.enabled": False}),
                 rows, parts).collect_arrow()
    _without_rule(monkeypatch)
    plain = _star(tpu_session(Q3_CONF), rows, parts).collect_arrow()
    assert got.num_rows == host.num_rows == plain.num_rows > 0
    for want in (host, plain):
        assert got.drop(["total"]).equals(want.drop(["total"]))
        np.testing.assert_allclose(got["total"].to_numpy(),
                                   want["total"].to_numpy(), rtol=1e-12)


@pytest.mark.parametrize("conf", [
    Q3_CONF,
    # the defaults' one-program fragment keeps the operator plan, with its
    # coalesce, as the fallback for inputs beyond the bucket ladder
    {"spark.rapids.tpu.sql.optimizer.enabled": False,
     "spark.rapids.tpu.sql.batchSizeRows": 8192}],
    ids=["operator-pipeline", "fused-pipeline"])
def test_answers_equal_with_and_without_the_operator(conf, monkeypatch):
    rows, parts = 3 * PART - 700, 3
    with_op = _star(tpu_session(conf), rows, parts)
    tree = with_op._physical().tree_string()
    if conf is Q3_CONF:
        assert "CoalesceBatches[TargetSize" in tree
    else:
        assert "DistributedPipeline" in tree
    got = with_op.collect_arrow()
    _without_rule(monkeypatch)
    plain = _star(tpu_session(conf), rows, parts)
    assert "CoalesceBatches" not in plain._physical().tree_string()
    want = plain.collect_arrow()
    assert got.num_rows == want.num_rows > 0
    assert got.drop(["total"]).equals(want.drop(["total"]))
    np.testing.assert_allclose(got["total"].to_numpy(),
                               want["total"].to_numpy(), rtol=1e-12)


def _full_partitions(s):
    # 2 partitions of exactly the target feeding an aggregate: nothing to
    # merge
    t = pa.table({"k": pa.array(np.arange(2 * 8192) % 5),
                  "v": pa.array(np.arange(2 * 8192, dtype=np.float64))})
    return s.create_dataframe(t, num_partitions=2).group_by("k").agg(
        F.sum(F.col("v")).with_name("sv"))


def _global_aggregate(s):
    # a global aggregate widens its single-partition scan to one batch
    t = pa.table({"x": pa.array(np.arange(20000, dtype=np.float64))})
    return s.create_dataframe(t).agg(F.sum(F.col("x")).with_name("sx"))


def _one_partition_join(s):
    # one stream batch: each join hands on one batch, whatever it keeps
    return _star(s, 3000, 1)


def _string_fact(s):
    # a dictionary column would take the concat through Arrow and back
    t = pa.table({"k": pa.array(["a", "b"] * 2048), "v": pa.array(
        np.arange(4096, dtype=np.float64))})
    return s.create_dataframe(t, num_partitions=4).group_by("k").agg(
        F.sum(F.col("v")).with_name("sv"))


@pytest.mark.parametrize("query", [
    _full_partitions, _global_aggregate, _one_partition_join, _string_fact])
def test_plans_with_nothing_to_fill_are_unchanged(query, monkeypatch):
    tree = query(tpu_session(Q3_CONF))._physical().tree_string()
    assert "CoalesceBatches" not in tree
    _without_rule(monkeypatch)
    assert query(tpu_session(Q3_CONF))._physical().tree_string() == tree


@pytest.mark.parametrize("col,distinct", [
    (F.spark_partition_id, 4), (F.monotonically_increasing_id, 4 * 1000),
    (F.input_file_name, 1)])
def test_task_context_readers_keep_their_batches(col, distinct):
    """A plan that reads ``batch.meta`` gets no coalesce anywhere (the
    merged batch would carry its first batch's partition id) and answers
    as before: one id per partition, ids unique per row."""
    s = tpu_session(Q3_CONF)
    t = pa.table({"k": pa.array(np.arange(4000) % 5),
                  "v": pa.array(np.arange(4000, dtype=np.float64))})
    df = s.create_dataframe(t, num_partitions=4)
    tagged = df.select(F.col("k"), F.col("v"), col().alias("tag"))
    agg = tagged.group_by("tag").agg(F.sum(F.col("v")).with_name("sv"))
    tree = agg._physical().tree_string()
    assert "CoalesceBatches" not in tree and "filled to" not in tree
    out = agg.collect_arrow()
    assert out.num_rows == distinct
    assert abs(sum(out["sv"].to_pylist()) - float(np.arange(4000).sum())) \
        < 1e-6
    # the same aggregate without the tag is filled
    filled = df.group_by("k").agg(F.sum(F.col("v")).with_name("sv"))
    assert f"filled to {GOAL}" in filled._physical().tree_string()
    assert sorted(r["sv"] for r in filled.collect()) == sorted(
        float(np.arange(4000)[np.arange(4000) % 5 == k].sum())
        for k in range(5))


@pytest.mark.parametrize("col", [
    F.spark_partition_id, F.monotonically_increasing_id, F.input_file_name,
    F.rand])
def test_a_reader_anywhere_in_the_plan_stops_the_rule(col):
    """Above the aggregate the reader sees the aggregate's batches, not
    the scan's; the rule does not reason about that and plans nothing."""
    s = tpu_session(Q3_CONF)
    t = pa.table({"k": pa.array(np.arange(4000) % 5),
                  "v": pa.array(np.arange(4000, dtype=np.float64))})
    df = s.create_dataframe(t, num_partitions=4).group_by("k").agg(
        F.sum(F.col("v")).with_name("sv"))
    assert f"filled to {GOAL}" in df._physical().tree_string()
    tagged = df.select(F.col("k"), F.col("sv"), col().alias("tag"))
    assert "filled to" not in tagged._physical().tree_string()
    assert sorted(r["sv"] for r in tagged.collect()) == \
        sorted(r["sv"] for r in df.collect())


class _ForeignSpec:
    """An exec-side holder this rule has never heard of."""

    def __init__(self, expr):
        self.inner = {"exprs": [expr]}


class _SlottedSpec:
    __slots__ = ("expr",)

    def __init__(self, expr):
        self.expr = expr


@pytest.mark.parametrize("hold", [
    _ForeignSpec, _SlottedSpec,
    lambda e: __import__("collections").namedtuple("Spec", "a b")(1, (e,)),
    lambda e: {"deep": [{"er": _ForeignSpec(e)}]}],
    ids=["foreign-object", "slots", "namedtuple", "nested-containers"])
def test_the_reader_check_fails_closed(hold):
    """A task-context expression is found in whatever holds it on an
    operator, a class the rule does not know included; the same holder
    around a plain column stops nothing."""
    s = tpu_session(Q3_CONF)
    physical = _star(s, 4 * PART, 4)._physical()
    assert not overrides._reads_task_context(physical)
    join = _nodes(physical, TpuBroadcastHashJoinExec)[-1]
    join.some_spec = hold(F.col("f_item").expr)
    assert not overrides._reads_task_context(physical)
    join.some_spec = hold(F.spark_partition_id().expr)
    assert overrides._reads_task_context(physical)


@pytest.mark.parametrize("local", [True, False],
                         ids=["within-partitions", "global"])
def test_sorts_keep_their_batches(local):
    """A sort gets no operator: the partition-local one answers one sorted
    run per partition, the global one concatenates its input itself."""
    s = tpu_session(Q3_CONF)
    t = pa.table({"v": pa.array(np.arange(3000)[::-1].copy())})
    df = s.create_dataframe(t, num_partitions=3)
    df = df.sort_within_partitions("v") if local else df.sort("v")
    physical = df._physical()
    assert "CoalesceBatches" not in physical.tree_string()
    ctx = ExecContext(parent=s.exec_context())
    runs = [b.to_arrow()["v"].to_pylist() for b in physical.execute(ctx)]
    assert all(r == sorted(r) for r in runs)
    if local:
        assert [len(r) for r in runs] == [1000, 1000, 1000]
    assert sorted(v for r in runs for v in r) == list(range(3000))


# -- the filling scan and its cache entry (exec/basic.py InMemoryScanExec) --

def _parts(n_parts=6, rows=1000, seed=0):
    rng = np.random.RandomState(seed)
    return [pa.table({"k": pa.array(rng.randint(0, 5, rows)),
                      "v": pa.array(rng.rand(rows))})
            for _ in range(n_parts)]


def _filling_scan(tables, goal):
    from spark_rapids_tpu.types import Schema, from_arrow
    scan = InMemoryScanExec(tables, Schema.of(
        **{f.name: from_arrow(f.type) for f in tables[0].schema}))
    scan.fill = goal
    return scan


#: (k int64, v float64): 9 bytes a lane and a validity byte each
ROW_BYTES = 18


def _run(s, scan):
    from spark_rapids_tpu.trace import Tracer, install_tracer
    ctx = ExecContext(parent=s.exec_context())
    tr = install_tracer(Tracer())
    try:
        out = list(scan.execute(ctx))
    finally:
        install_tracer(None)
        ctx.close()
    (fill,) = [e["args"] for e in tr.snapshot()
               if e["ph"] == "C" and e["name"] == "scan.fill"]
    return out, fill


def _count_uploads_and_concats(monkeypatch):
    from spark_rapids_tpu.columnar import batch as batch_mod
    calls = {"upload": 0, "concat": 0}
    upload = batch_mod.ColumnarBatch.from_arrow
    concat = batch_mod.concat_batches_device

    def counted_upload(*a, **kw):
        calls["upload"] += 1
        return upload(*a, **kw)

    def counted_concat(*a, **kw):
        calls["concat"] += 1
        return concat(*a, **kw)

    monkeypatch.setattr(batch_mod.ColumnarBatch, "from_arrow",
                        staticmethod(counted_upload))
    monkeypatch.setattr(batch_mod, "concat_batches_device", counted_concat)
    return calls


def test_a_repeated_plan_serves_the_filled_batches_from_the_cache(
        monkeypatch):
    """The second execution of the same plan (a streaming broadcast join
    into the sink) takes the scan's filled batches from the cache: the
    same objects, no upload and no concat anywhere in the query, and
    ``scan.fill`` says ``cached`` 1."""
    from spark_rapids_tpu.trace import Tracer, install_tracer
    s = tpu_session({**Q3_CONF, "spark.rapids.tpu.sql.batchSizeRows": 2000})
    dim = pa.table({"dk": pa.array(np.arange(5)),
                    "w": pa.array(np.arange(5, dtype=np.float64) / 2)})
    df = s.create_dataframe(pa.concat_tables(_parts()), num_partitions=6) \
        .join(F.broadcast(s.create_dataframe(dim)), on=[("k", "dk")])
    physical = df._physical()
    assert "CoalesceBatches" not in physical.tree_string()
    (scan,) = [n for n in _nodes(physical, InMemoryScanExec) if n.fill]
    assert scan.fill == (2000, 536870912)

    def execute():
        ctx = ExecContext(parent=s.exec_context())
        tr = install_tracer(Tracer())
        try:
            out = pa.concat_tables(b.to_arrow()
                                   for b in physical.execute(ctx))
        finally:
            install_tracer(None)
            ctx.close()
        (fill,) = [e["args"] for e in tr.snapshot()
                   if e["ph"] == "C" and e["name"] == "scan.fill"]
        assert fill["op"] == int(scan._exec_id.rsplit("@", 1)[1])
        return out, (fill["slices"], fill["batches"], fill["cached"])

    want, fill = execute()
    assert fill == (6, 3, 0)
    first, _ = _run(s, scan)
    calls = _count_uploads_and_concats(monkeypatch)
    got, fill = execute()
    assert calls == {"upload": 0, "concat": 0}
    assert fill == (6, 3, 1)
    assert got.equals(want) and got.num_rows == 6000
    again, _ = _run(s, scan)
    assert len(again) == 3 and all(a is b for a, b in zip(again, first))


def test_a_freed_or_replaced_table_evicts_the_filled_entry():
    """The entry belongs to every table of the scan: freeing one of them
    drops it, and a new table, under that id if the allocator hands it
    out again, is read anew and never answered with the old rows."""
    import gc

    from spark_rapids_tpu.exec import basic as B
    s = tpu_session(Q3_CONF)
    goal = (2000, 1 << 29)
    tables = _parts()
    before, _ = _run(s, _filling_scan(tables, goal))
    _, fill = _run(s, _filling_scan(tables, goal))
    assert fill["cached"] == 1
    old_id = id(tables[1])
    assert [k for k in B._SCAN_CACHE_BATCHES if old_id in k[0]]
    tables[1] = None
    gc.collect()
    assert not [k for k in B._SCAN_CACHE_BATCHES if old_id in k[0]]
    for attempt in range(200):       # try for the freed table's id
        fresh = _parts(1, seed=100 + attempt)[0]
        if id(fresh) == old_id:
            break
    tables[1] = fresh
    got, fill = _run(s, _filling_scan(tables, goal))
    assert fill["cached"] == 0
    assert got[0].to_arrow().equals(pa.concat_tables(tables[:2]))
    assert not got[0].to_arrow().equals(before[0].to_arrow())
    assert all(a.to_arrow().equals(b.to_arrow())
               for a, b in zip(got[1:], before[1:]))


@pytest.mark.parametrize("slices_a_batch", [1, 3])
def test_a_byte_goal_splits_the_groups(slices_a_batch):
    """The goal's bytes bind as its rows do, each slice counted at the
    padded bytes of the batch it would be alone (what the operator above
    a scan counted): under two slices' bytes every slice is a batch of its
    own, at three slices' bytes the rows' goal of four is cut to three."""
    one_slice = 1024 * ROW_BYTES          # 1,000 rows in the 1,024 bucket
    s = tpu_session({**Q3_CONF, "spark.rapids.tpu.sql.batchSizeRows": 4000,
                     "spark.rapids.tpu.sql.batchSizeBytes":
                     one_slice * slices_a_batch + one_slice - 1})
    df = s.create_dataframe(pa.concat_tables(_parts()), num_partitions=6) \
        .group_by("k").agg(F.sum(F.col("v")).with_name("sv"))
    (scan,) = _nodes(df._physical(), InMemoryScanExec)
    assert scan.fill is not None
    out, fill = _run(s, scan)
    assert fill["batches"] == len(out) == 6 // slices_a_batch
    assert [b.num_rows for b in out] == [1000 * slices_a_batch] * len(out)
    scan.fill = None
    ctx = ExecContext(parent=s.exec_context())
    try:
        alone = list(scan.execute(ctx))
    finally:
        ctx.close()
    assert alone[0].size_bytes() == one_slice
    assert pa.concat_tables(b.to_arrow() for b in out).equals(
        pa.concat_tables(b.to_arrow() for b in alone))


def test_the_filled_entry_counts_against_the_cache_budget(monkeypatch):
    """The filled batches are ONE entry of ``scanCache.maxBytes``: a byte
    under their size and none is cached (every run uploads every group);
    at their size they are, and another scan's entry that needs the room
    evicts them whole."""
    from spark_rapids_tpu.exec import basic as B
    goal = (2000, 1 << 29)
    entry = 3 * 8192 * ROW_BYTES          # three groups of 2,000 rows
    tables, others = _parts(seed=1), _parts(seed=2)
    key = tuple(map(id, tables))
    calls = _count_uploads_and_concats(monkeypatch)
    tight = tpu_session({**Q3_CONF,
                         "spark.rapids.tpu.sql.scanCache.maxBytes": entry - 1})
    for _ in range(2):
        out, fill = _run(tight, _filling_scan(tables, goal))
        assert fill["cached"] == 0
    assert sum(b.device_size_bytes() for b in out) == entry
    assert calls["upload"] == 6 and calls["concat"] == 0
    assert not [k for k in B._SCAN_CACHE_BATCHES if k[0] == key]
    fits = tpu_session({**Q3_CONF,
                        "spark.rapids.tpu.sql.scanCache.maxBytes": entry})
    _run(fits, _filling_scan(tables, goal))
    _, fill = _run(fits, _filling_scan(tables, goal))
    assert fill["cached"] == 1 and calls["upload"] == 9
    _run(fits, _filling_scan(others, goal))
    assert not [k for k in B._SCAN_CACHE_BATCHES if k[0] == key]
    _, fill = _run(fits, _filling_scan(tables, goal))
    assert fill["cached"] == 0 and calls["upload"] == 15


@pytest.mark.parametrize("rows,parts", [(5 * PART - 1500, 5),
                                        (2 * PART + 1, 3)])
def test_star_answers_equal_with_and_without_the_fill(rows, parts,
                                                      monkeypatch):
    """The q3-shaped star (NULL keys, a short last partition) answers the
    same whether the scan fills its batches or the operator above it
    concatenates them, as the plans before the filling scan did: the same
    batches reach the joins, so the same sums to the last bit."""
    got = _star(tpu_session(Q3_CONF), rows, parts).collect_arrow()
    rule = overrides.insert_coalesce

    def concat_above_the_scan(physical, conf):
        physical = rule(physical, conf)
        for node in _nodes(physical, TpuBroadcastHashJoinExec):
            for i, c in enumerate(node.children):
                if isinstance(c, InMemoryScanExec) and c.fill is not None:
                    c.fill = None
                    node.children[i] = CoalesceBatchesExec(
                        c, target_rows=conf.batch_size_rows,
                        target_bytes=conf.batch_size_bytes)
        return physical

    monkeypatch.setattr(overrides, "insert_coalesce", concat_above_the_scan)
    df = _star(tpu_session(Q3_CONF), rows, parts)
    tree = df._physical().tree_string()
    assert "filled to" not in tree and tree.count("CoalesceBatches") == 3
    want = df.collect_arrow()
    assert got.num_rows == want.num_rows > 0
    assert got.equals(want)


def _lineitem_like(s, query):
    """A table cut into partitions of about the goal's rows, as the TPC-H
    configurations register ``lineitem``: no neighbouring pair fits."""
    rng = np.random.RandomState(4)
    n = 5 * 8192 - 3000
    t = pa.table({"l_k": pa.array(rng.randint(0, 900, n)),
                  "l_flag": pa.array(rng.randint(0, 4, n).astype(np.int32)),
                  "l_v": pa.array(rng.rand(n))})
    li = s.create_dataframe(t, num_partitions=-(-n // 8192))
    if query == "q6":
        return li.filter(F.col("l_v") < 0.5).agg(
            F.sum(F.col("l_v")).with_name("s"))
    if query == "q1":
        return li.filter(F.col("l_v") < 0.9).group_by("l_flag").agg(
            F.sum(F.col("l_v")).with_name("s"))
    orders = pa.table({"o_k": pa.array(np.arange(900)),
                       "o_d": pa.array(rng.randint(0, 9, 900))})
    return li.join(s.create_dataframe(orders), on=[("l_k", "o_k")]) \
        .group_by("o_d").agg(F.sum(F.col("l_v")).with_name("s"))


@pytest.mark.parametrize("query", ["q6", "q1", "q3"])
def test_full_batches_get_no_fill_target(query, monkeypatch):
    """Partitions that already fill ``batchSizeRows`` (the TPC-H cells'):
    no scan gets a fill target and the plan is the one without the rule
    (Q3's join of two sides too large to broadcast materializes both)."""
    conf = Q3_CONF if query != "q3" else {
        **Q3_CONF, "spark.rapids.tpu.sql.autoBroadcastJoinThreshold": 0}
    physical = _lineitem_like(tpu_session(conf), query)._physical()
    assert all(scan.fill is None
               for scan in _nodes(physical, InMemoryScanExec))
    tree = physical.tree_string()
    assert "filled to" not in tree
    _without_rule(monkeypatch)
    assert _lineitem_like(tpu_session(conf), query)._physical() \
        .tree_string() == tree
