"""The post-conversion coalesce rule (plan/overrides.py:insert_coalesce):
where a scan or a streaming broadcast join hands under-filled batches to a
per-batch operator the plan gets ``CoalesceBatches[TargetSize]`` above it,
and where it does not the plan is the one it was."""
import numpy as np
import pyarrow as pa
import pytest

from harness import OPERATOR_CONF, tpu_session
from spark_rapids_tpu.api import functions as F
from spark_rapids_tpu.exec.aggregate import TpuHashAggregateExec
from spark_rapids_tpu.exec.base import ExecContext
from spark_rapids_tpu.exec.basic import CoalesceBatchesExec, TpuProjectExec
from spark_rapids_tpu.exec.joins import TpuBroadcastHashJoinExec
from spark_rapids_tpu.exec.wholestage import WholeStageExec
from spark_rapids_tpu.plan import overrides

#: q3's pins (perfbench/configs/tpcds_sf1_star.json): the operator
#: pipeline, 4096-row partitions against a target of 8192
Q3_CONF = {**OPERATOR_CONF, "spark.rapids.tpu.sql.batchSizeRows": 8192}
PART = 4096


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
    """The operator sits on the stream side of the lowest join, directly
    above the fact scan; that join sees half as many batches (rounded up),
    each in the 8,192-row bucket with at most 8,192 live rows, no row lost
    or reordered."""
    s = tpu_session(Q3_CONF)
    physical = _star(s, rows, parts)._physical()
    joins = _nodes(physical, TpuBroadcastHashJoinExec)
    lowest = joins[-1]
    (co,) = _nodes(lowest, CoalesceBatchesExec)
    assert co.describe() == \
        "CoalesceBatches[TargetSize(rows=8192, bytes=536870912)]"
    assert co in lowest.children
    (scan,) = co.children
    assert scan.describe() == f"InMemoryScan[{parts} partitions]"
    ctx = ExecContext(parent=s.exec_context())
    try:
        scanned = list(scan.execute(ctx))
        merged = list(co.execute(ctx))
    finally:
        ctx.close()
    assert len(scanned) == parts and len(merged) == -(-parts // 2)
    assert all(b.padded_len == 8192 and b.num_rows <= 8192 for b in merged)
    assert [b.num_rows for b in merged] == [
        sum(b.num_rows for b in scanned[i:i + 2])
        for i in range(0, parts, 2)]
    assert pa.concat_tables([b.to_arrow() for b in merged]).equals(
        pa.concat_tables([b.to_arrow() for b in scanned]))
    # an odd one out passes through as the object the scan made
    if parts % 2:
        assert merged[-1] is scanned[-1]


def test_full_partitions_fill_the_joins_and_not_the_scan():
    """Two partitions of exactly the target: nothing to merge above the
    scan, and each join still hands on what it kept of a batch."""
    physical = _star(tpu_session(Q3_CONF), 2 * 8192, 2)._physical()
    filled = [co.children[0] for co in _nodes(physical, CoalesceBatchesExec)]
    assert filled == _nodes(physical, TpuBroadcastHashJoinExec)


def test_star_plan_fills_above_both_joins():
    """Each streaming broadcast join of the star hands its output to
    another per-batch operator (the next join's stream side through a
    projection, the aggregate), so each gets the operator directly above
    it, and ``explain`` shows all three."""
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
    assert len(_nodes(physical, CoalesceBatchesExec)) == 3
    assert physical.tree_string().count(
        "CoalesceBatches[TargetSize(rows=8192, bytes=536870912)]") == 3


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
    s = tpu_session(Q3_CONF)
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
    assert "CoalesceBatches" not in agg._physical().tree_string()
    out = agg.collect_arrow()
    assert out.num_rows == distinct
    assert abs(sum(out["sv"].to_pylist()) - float(np.arange(4000).sum())) \
        < 1e-6
    # the same aggregate without the tag is filled
    filled = df.group_by("k").agg(F.sum(F.col("v")).with_name("sv"))
    assert "CoalesceBatches[TargetSize" in filled._physical().tree_string()
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
    assert "CoalesceBatches[TargetSize" in df._physical().tree_string()
    tagged = df.select(F.col("k"), F.col("sv"), col().alias("tag"))
    assert "CoalesceBatches" not in tagged._physical().tree_string()
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
