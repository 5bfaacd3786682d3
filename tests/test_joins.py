"""Differential join tests (ref join_test.py)."""
import contextlib
import pandas as pd
import pytest

from harness import assert_tpu_and_cpu_equal, tpu_session
from data_gen import DoubleGen, IntGen, gen_df
from spark_rapids_tpu.api import functions as F


def _sides(s, n_l=512, n_r=256, key_hi=40, nullable=True, seed=0):
    l = s.create_dataframe(gen_df(
        {"lk": IntGen(lo=0, hi=key_hi, nullable=nullable),
         "lv": IntGen(nullable=False)}, n=n_l, seed=seed))
    r = s.create_dataframe(gen_df(
        {"rk": IntGen(lo=0, hi=key_hi, nullable=nullable),
         "rv": IntGen(nullable=False)}, n=n_r, seed=seed + 1))
    return l, r


@pytest.mark.parametrize("how", ["inner", "left", "right", "full",
                                 "leftsemi", "leftanti"])
def test_equi_join(how):
    def q(s):
        l, r = _sides(s)
        return l.join(r, on=[("lk", "rk")], how=how)
    assert_tpu_and_cpu_equal(q)


@pytest.mark.parametrize("how", ["inner", "left", "full"])
def test_join_null_keys_never_match(how):
    def q(s):
        l, r = _sides(s, key_hi=3, nullable=True)
        return l.join(r, on=[("lk", "rk")], how=how)
    assert_tpu_and_cpu_equal(q)


def test_join_duplicate_keys_product():
    def q(s):
        l, r = _sides(s, n_l=64, n_r=64, key_hi=4, nullable=False)
        return l.join(r, on=[("lk", "rk")], how="inner")
    assert_tpu_and_cpu_equal(q)


def test_multi_key_join():
    def q(s):
        l = s.create_dataframe(gen_df(
            {"a": IntGen(lo=0, hi=5), "b": IntGen(lo=0, hi=5),
             "lv": IntGen(nullable=False)}, n=256))
        r = s.create_dataframe(gen_df(
            {"c": IntGen(lo=0, hi=5), "d": IntGen(lo=0, hi=5),
             "rv": IntGen(nullable=False)}, n=256, seed=7))
        return l.join(r, on=[("a", "c"), ("b", "d")], how="inner")
    assert_tpu_and_cpu_equal(q)


def test_join_empty_side():
    def q(s):
        l, r = _sides(s)
        return l.filter(F.col("lv") > 10**10).join(
            r, on=[("lk", "rk")], how="inner")
    assert_tpu_and_cpu_equal(q)


def test_join_with_condition_inner():
    def q(s):
        l, r = _sides(s, nullable=False)
        return l.join(r, on=[("lk", "rk")], how="inner",
                      condition=F.col("lv") > F.col("rv"))
    assert_tpu_and_cpu_equal(q)


def test_cross_join():
    def q(s):
        l = s.create_dataframe(pd.DataFrame({"a": [1, 2, 3]}))
        r = s.create_dataframe(pd.DataFrame({"b": [10, 20]}))
        return l.join(r, how="cross")
    assert_tpu_and_cpu_equal(q)


def test_join_then_agg():
    def q(s):
        l, r = _sides(s, nullable=False)
        return (l.join(r, on=[("lk", "rk")], how="inner")
                .group_by("lk")
                .agg(F.sum(F.col("lv")).with_name("sl"),
                     F.count_star().with_name("n")))
    assert_tpu_and_cpu_equal(q)


def test_float_keys_nan_matches_nan():
    # Spark semantics: NaN joins NaN, -0.0 joins 0.0 (NormalizeFloatingNumbers).
    # Arrow's join does NOT follow this, so pin the expected rows explicitly.
    from harness import tpu_session

    import pyarrow as pa
    s = tpu_session()
    # NB: build via pyarrow — pandas conversion would turn NaN into null
    l = s.create_dataframe(pa.table(
        {"lk": pa.array([1.0, float("nan"), 0.0, -0.0], pa.float64()),
         "lv": pa.array([1, 2, 3, 4], pa.int64())}))
    r = s.create_dataframe(pa.table(
        {"rk": pa.array([float("nan"), 0.0, 2.0], pa.float64()),
         "rv": pa.array([10, 20, 30], pa.int64())}))
    out = l.join(r, on=[("lk", "rk")], how="inner").to_pandas()
    got = sorted(zip(out["lv"], out["rv"]))
    assert got == [(2, 10), (3, 20), (4, 20)]


# ---------------------------------------------------------------------------
# Conditional (residual-condition) joins of every type
# (ref GpuBroadcastNestedLoopJoinExecBase / conditional JoinGatherer)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("how", ["inner", "left", "right", "full",
                                 "leftsemi", "leftanti"])
def test_conditional_equi_join(how):
    def q(s):
        l, r = _sides(s, n_l=128, n_r=96, key_hi=10)
        return l.join(r, on=[("lk", "rk")], how=how,
                      condition=F.col("lv") > F.col("rv"))
    assert_tpu_and_cpu_equal(q)


def test_conditional_left_join_hand_oracle():
    """Condition decides matched-ness — NOT a post-filter (a left row whose
    key matches but whose condition never passes must still appear,
    null-extended)."""
    import pyarrow as pa
    from harness import tpu_session
    s = tpu_session()
    l = s.create_dataframe(pa.table({"lk": [1, 1, 2], "lv": [10, 1, 5]}))
    r = s.create_dataframe(pa.table({"rk": [1, 1, 3], "rv": [5, 20, 0]}))
    out = l.join(r, on=[("lk", "rk")], how="left",
                 condition=F.col("lv") > F.col("rv")).to_pandas()
    out = out.sort_values(["lk", "lv"], na_position="first")
    # lv=10 matches rv=5 only; lv=1 matches nothing -> null-extended;
    # lk=2 has no key match -> null-extended
    assert len(out) == 3
    matched = out[out["rv"].notna()]
    assert matched[["lv", "rv"]].values.tolist() == [[10, 5]]
    assert out["rv"].isna().sum() == 2


@pytest.mark.parametrize("how", ["existence"])
def test_existence_join(how):
    def q(s):
        l, r = _sides(s, n_l=256, n_r=64, key_hi=20)
        return l.join(r, on=[("lk", "rk")], how=how)
    assert_tpu_and_cpu_equal(q)


def test_existence_join_with_condition():
    def q(s):
        l, r = _sides(s, n_l=128, n_r=64, key_hi=8)
        return l.join(r, on=[("lk", "rk")], how="existence",
                      condition=F.col("lv") > F.col("rv"))
    assert_tpu_and_cpu_equal(q)


# ---------------------------------------------------------------------------
# Nested-loop joins (no equi keys; ref GpuBroadcastNestedLoopJoinExecBase,
# GpuCartesianProductExec)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("how", ["inner", "left", "right", "full",
                                 "leftsemi", "leftanti"])
def test_nested_loop_join(how):
    def q(s):
        l, r = _sides(s, n_l=64, n_r=48, key_hi=100)
        return l.join(r, how=how, condition=F.col("lk") < F.col("rk"))
    assert_tpu_and_cpu_equal(q)


def test_nested_loop_join_plan():
    from harness import tpu_session
    l, r = _sides(tpu_session())
    plan = l.join(r, how="inner",
                  condition=F.col("lk") < F.col("rk"))._physical()
    assert "NestedLoopJoin" in plan.tree_string()


def test_cartesian_product_with_condition():
    def q(s):
        l, r = _sides(s, n_l=32, n_r=32)
        return l.join(r, how="cross",
                      condition=F.col("lv") % 2 == F.col("rv") % 2)
    assert_tpu_and_cpu_equal(q)


def test_nested_loop_empty_right():
    def q(s):
        l, r = _sides(s, n_l=32)
        return l.join(r.filter(F.col("rv") > 10**10), how="left",
                      condition=F.col("lk") < F.col("rk"))
    assert_tpu_and_cpu_equal(q)


# ---------------------------------------------------------------------------
# Broadcast hash join (ref GpuBroadcastHashJoinExecBase +
# GpuBroadcastExchangeExec)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("how", ["inner", "left", "leftsemi", "leftanti",
                                 "right", "full", "existence"])
def test_broadcast_hash_join(how):
    def q(s):
        l, r = _sides(s, n_l=512, n_r=64, key_hi=30)
        return l.join(F.broadcast(r), on=[("lk", "rk")], how=how)
    assert_tpu_and_cpu_equal(q)


def test_broadcast_join_plan_has_exchange():
    # operator-plan shape: disable single-chip fusion, which would
    # otherwise compile the whole fragment into one pipeline node
    from harness import tpu_session
    s = tpu_session({"spark.rapids.tpu.sql.fusedPipeline.enabled": False})
    l, r = _sides(s)
    plan = l.join(F.broadcast(r), on=[("lk", "rk")], how="inner")._physical()
    t = plan.tree_string()
    assert "BroadcastExchange" in t and "BroadcastHashJoin" in t


# ---------------------------------------------------------------------------
# Sub-partitioned big-input join (ref GpuSubPartitionHashJoin.scala)
# ---------------------------------------------------------------------------

_SUBPART_CONF = {"spark.rapids.tpu.sql.join.subPartitionSizeBytes": 1024}


@pytest.mark.parametrize("how", ["inner", "left", "right", "full",
                                 "leftsemi", "leftanti"])
def test_subpartitioned_join(how):
    def q(s):
        l, r = _sides(s, n_l=1024, n_r=512, key_hi=50)
        return l.join(r, on=[("lk", "rk")], how=how)
    assert_tpu_and_cpu_equal(q, conf=_SUBPART_CONF)


def test_subpartitioned_join_matches_unpartitioned():
    from harness import tpu_session
    def q(s):
        l, r = _sides(s, n_l=777, n_r=333, key_hi=25)
        return l.join(r, on=[("lk", "rk")], how="inner")
    a = q(tpu_session(_SUBPART_CONF)).to_pandas()
    b = q(tpu_session()).to_pandas()
    key = ["lk", "lv", "rk", "rv"]
    a = a.sort_values(key, na_position="first").reset_index(drop=True)
    b = b.sort_values(key, na_position="first").reset_index(drop=True)
    pd.testing.assert_frame_equal(a, b)


@pytest.mark.parametrize("how", ["inner", "right", "left", "full"])
def test_broadcast_left_build_side(how):
    def q(s):
        l, r = _sides(s, n_l=64, n_r=512, key_hi=30)
        return F.broadcast(l).join(r, on=[("lk", "rk")], how=how)
    assert_tpu_and_cpu_equal(q)


def test_broadcast_join_empty_stream():
    def q(s):
        l, r = _sides(s, n_l=64, n_r=64, key_hi=10)
        return l.filter(F.col("lv") > 10**10).join(
            F.broadcast(r), on=[("lk", "rk")], how="left")
    assert_tpu_and_cpu_equal(q)


def test_auto_broadcast_small_side():
    """Plan-time size estimates pick the broadcast side without a hint
    (ref Spark autoBroadcastJoinThreshold / reference AQE switching)."""
    import numpy as np
    import pyarrow as pa
    from harness import tpu_session
    rng = np.random.RandomState(0)
    big = pa.table({"k": pa.array(rng.randint(0, 50, 50000)),
                    "v": pa.array(rng.standard_normal(50000))})
    dim = pa.table({"k2": pa.array(np.arange(50)),
                    "w": pa.array(np.arange(50) * 2.0)})
    s = tpu_session({"spark.rapids.tpu.sql.fusedPipeline.enabled": False})
    df = s.create_dataframe(big).join(s.create_dataframe(dim),
                                      on=[("k", "k2")])
    tree = df._physical().tree_string()
    assert "BroadcastHashJoin" in tree and "build=right" in tree, tree
    # correctness unchanged
    out = df.agg(F.sum(F.col("w")).with_name("sw")).collect()
    pdf = big.to_pandas().merge(dim.to_pandas(), left_on="k",
                                right_on="k2")
    np.testing.assert_allclose(out[0]["sw"], pdf["w"].sum(), rtol=1e-9)


def test_auto_broadcast_disabled_by_conf():
    import numpy as np
    import pyarrow as pa
    from harness import tpu_session
    big = pa.table({"k": pa.array(np.arange(1000))})
    dim = pa.table({"k2": pa.array(np.arange(10))})
    s = tpu_session({"spark.rapids.tpu.sql.autoBroadcastJoinThreshold": 0})
    df = s.create_dataframe(big).join(s.create_dataframe(dim),
                                      on=[("k", "k2")])
    assert "BroadcastHashJoin" not in df._physical().tree_string()


def test_aqe_broadcast_flips_on_measured_size():
    """AQE analog (VERDICT r2 #7): the first run measures the filtered
    side's TRUE size; re-planning the same query shape then broadcasts a
    side the plan-time estimate had called too big."""
    import numpy as np
    import pyarrow as pa
    rng = np.random.RandomState(4)
    n = 60000
    left = pa.table({"k": pa.array(rng.randint(0, 1000, n)),
                     "v": pa.array(rng.uniform(0, 1, n))})
    # big scan whose filter keeps almost nothing: plan-time estimate
    # (conservative: filters keep the child size) exceeds the broadcast
    # threshold, the MEASURED size is tiny
    right = pa.table({"k2": pa.array(rng.randint(0, 1000, n)),
                      "w": pa.array(rng.randint(0, 3, n))})
    thr = 64 * 1024
    s = tpu_session({"spark.rapids.tpu.sql.autoBroadcastJoinThreshold": thr,
                     # operator pipeline: the fused fragment's explain
                     # would hide the join strategy under one node
                     "spark.rapids.tpu.sql.fusedPipeline.enabled": False})

    def build():
        r = s.create_dataframe(right).filter(F.col("w") == F.lit(0)) \
             .filter(F.col("k2") < F.lit(20))
        return (s.create_dataframe(left)
                .join(r, on=[(F.col("k"), F.col("k2"))], how="inner")
                .group_by("k").agg(F.count_star().with_name("n")))

    q1 = build()
    p1 = q1.explain()
    assert "BroadcastHashJoin" not in p1, p1   # estimate said too big
    r1 = q1.collect_arrow()
    q2 = build()
    p2 = q2.explain()
    assert "BroadcastHashJoin" in p2, p2       # measured size flipped it
    r2 = q2.collect_arrow()
    g1 = r1.to_pandas().sort_values("k").reset_index(drop=True)
    g2 = r2.to_pandas().sort_values("k").reset_index(drop=True)
    np.testing.assert_array_equal(g1["k"], g2["k"])
    np.testing.assert_array_equal(g1["n"], g2["n"])


def test_using_join_single_key_column():
    """r5 ground-truth finding: join(on='k') must emit ONE k column
    (PySpark USING semantics) — previously both sides' k survived and
    col('k') could resolve to the right side's null-filled copy."""
    import pyarrow as pa
    s = tpu_session()
    l = s.create_dataframe(pa.table({"k": pa.array([1, 2], pa.int64()),
                                     "v": pa.array([10, 20], pa.int64())}))
    r = s.create_dataframe(pa.table({"k": pa.array([1], pa.int64()),
                                     "w": pa.array([5], pa.int64())}))
    j = l.join(r, on="k", how="left")
    assert j.columns == ["k", "v", "w"], j.columns
    out = j.order_by(F.col("k").asc()).to_pandas()
    assert list(out["k"]) == [1, 2]
    assert list(out["v"]) == [10, 20]
    assert out["w"][0] == 5 and pd.isna(out["w"][1])
    # right join: key values come from the right side
    jr = l.join(r, on="k", how="right").to_pandas()
    assert list(jr["k"]) == [1] and list(jr["w"]) == [5]
    # full outer: key coalesces across sides
    r2 = s.create_dataframe(pa.table({"k": pa.array([3], pa.int64()),
                                      "w": pa.array([7], pa.int64())}))
    jf = (l.join(r2, on="k", how="full")
          .order_by(F.col("k").asc()).to_pandas())
    assert list(jf["k"]) == [1, 2, 3], jf


# -- the streaming broadcast join's output bound (exec/joins.py _OutBound):
# a build side with unique keys keeps each output in its stream batch's
# bucket; a duplicated build key leaves the speculation as it was ---------

#: the operator pipeline, a stream of three 1024-row batches the planner
#: leaves apart (two of them do not fit the target together)
_BOUND_CONF = {"spark.rapids.tpu.sql.optimizer.enabled": False,
               "spark.rapids.tpu.sql.fusedPipeline.enabled": False,
               "spark.rapids.tpu.distributed.enabled": False,
               "spark.rapids.tpu.sql.batchSizeRows": 1024}


def _stream_and_build(s, stream_keys, build_keys):
    import numpy as np
    import pyarrow as pa
    n = len(stream_keys)
    stream = s.create_dataframe(
        pa.table({"sk": stream_keys,
                  "sv": pa.array(np.arange(n, dtype=np.float64))}),
        num_partitions=n // 1024)
    build = s.create_dataframe(
        pa.table({"bk": build_keys,
                  "bv": pa.array(np.arange(len(build_keys)))}))
    return stream, build


def _run_join(s, df):
    """Execute the plan's one broadcast join on a context of the test's
    own, under the engine's tracer: (output batches, what was left in
    ``ctx.speculations``, the ``join.out_bound`` counters)."""
    from spark_rapids_tpu.exec.base import ExecContext
    from spark_rapids_tpu.exec.joins import TpuBroadcastHashJoinExec
    from spark_rapids_tpu.trace import Tracer, install_tracer

    def find(node):
        if isinstance(node, TpuBroadcastHashJoinExec):
            return node
        return next(filter(None, map(find, node.children)), None)

    join = find(df._physical())
    assert join is not None, df._physical().tree_string()
    ctx = ExecContext(parent=s.exec_context())
    tr = install_tracer(Tracer())
    try:
        outs = list(join.execute(ctx))
        left = list(ctx.speculations)
        ctx.check_speculations()
    finally:
        install_tracer(None)
        ctx.close()
    bounds = [e["args"] for e in tr.snapshot()
              if e["ph"] == "C" and e["name"] == "join.out_bound"]
    return outs, left, bounds


@pytest.mark.parametrize("how,stream_first", [
    ("inner", True), ("left", True), ("inner", False), ("right", False)])
def test_unique_build_keys_bound_the_output_hard(how, stream_first):
    """Unique build keys: every output batch sits in its stream batch's
    bucket (1024), first batch included, although 3 of 4 stream rows
    match and a guess of 1.5 times the last total would take the next
    bucket; nothing is registered for the sink to validate; the counter
    says hard. NULL and absent keys null-extend in the outer joins and
    still fit."""
    import numpy as np
    import pyarrow as pa
    rng = np.random.RandomState(3)
    keys = rng.randint(0, 400, 3 * 1024)       # 0..299 match, the rest not
    sk = pa.array(keys, mask=rng.rand(len(keys)) < 0.05)
    bk = pa.array(np.arange(300), mask=np.arange(300) == 7)   # a NULL key

    def q(s):
        stream, build = _stream_and_build(s, sk, bk)
        if stream_first:
            return stream.join(build, on=[("sk", "bk")], how=how)
        return build.join(stream, on=[("bk", "sk")], how=how)

    s = tpu_session(_BOUND_CONF)
    df = q(s)
    assert "CoalesceBatches" not in df._physical().tree_string()
    outs, left, bounds = _run_join(s, df)
    assert len(outs) == 3
    assert [b.padded_len for b in outs] == [1024] * 3
    assert left == []
    assert bounds == [{"hard": 1, "speculative": 0}]
    n_out = sum(b.num_rows for b in outs)
    t = assert_tpu_and_cpu_equal(q, conf=_BOUND_CONF)
    assert len(t) == n_out
    if how == "inner":
        assert 0 < n_out < len(keys)
    else:
        assert n_out == len(keys)


def test_duplicated_build_key_keeps_the_speculation_and_its_rerun():
    """One duplicated build key: the first batch is sized exactly and
    measures the multiplicity, the rest are sized from the last total
    with 1.5x headroom and registered for the sink, as before; when the
    second batch then emits 50 rows a row the sink's check overflows and
    the exact re-run gives every row."""
    import numpy as np
    import pyarrow as pa
    from spark_rapids_tpu.columnar.batch import SpeculativeOverflow
    # batch 0 meets the unique key 0, batches 1 and 2 the key 1 x 50
    sk = pa.array(np.repeat([0, 1, 1], 1024))
    bk = pa.array(np.array([0] + [1] * 50))

    def q(s):
        stream, build = _stream_and_build(s, sk, bk)
        return stream.join(build, on=[("sk", "bk")], how="inner")

    s = tpu_session(_BOUND_CONF)
    with pytest.raises(SpeculativeOverflow):
        _run_join(s, q(s))
    s = tpu_session(_BOUND_CONF)
    df = q(s)
    got = df.collect_arrow()
    assert got.num_rows == 1024 + 2 * 1024 * 50
    assert_tpu_and_cpu_equal(q, conf=_BOUND_CONF)

    # duplicates that stay inside the guess: speculative, validated, kept
    sk2 = pa.array(np.tile(np.arange(8), 3 * 128))
    bk2 = pa.array(np.array([0, 0, 1, 2, 3]))

    def q2(s):
        stream, build = _stream_and_build(s, sk2, bk2)
        return stream.join(build, on=[("sk", "bk")], how="inner")

    s = tpu_session(_BOUND_CONF)
    outs, left, bounds = _run_join(s, q2(s))
    assert len(left) == 2                      # all but the measured first
    assert bounds == [{"hard": 0, "speculative": 1}]
    assert sum(b.num_rows for b in outs) == 3 * 128 * 5
    assert_tpu_and_cpu_equal(q2, conf=_BOUND_CONF)


# -- the streaming broadcast join under CoalesceBatches (PR 33): its outputs
# are filled before the next per-batch operator, their counts read a window
# at a time (exec/basic.py CoalesceBatchesExec) -----------------------------

def _coalesce_above_join(physical):
    from spark_rapids_tpu.exec.basic import CoalesceBatchesExec
    from spark_rapids_tpu.exec.joins import TpuBroadcastHashJoinExec
    if isinstance(physical, CoalesceBatchesExec) and isinstance(
            physical.children[0], TpuBroadcastHashJoinExec):
        return physical
    return next(filter(None, map(_coalesce_above_join, physical.children)),
                None)


def test_overflow_inside_a_coalesce_window_still_raises_and_reruns():
    """A duplicated build key: batch 0 measures, batches 1 and 2 are sized
    from its total and then emit 50 rows a row. The operator above the
    join reads their counts in its window, finds them over their buckets
    and raises what ``num_rows`` would; the query re-runs with exact
    sizing and gives every row."""
    import numpy as np
    import pyarrow as pa
    from spark_rapids_tpu.columnar.batch import SpeculativeOverflow
    from spark_rapids_tpu.exec.base import ExecContext
    sk = pa.array(np.repeat([0, 1, 1], 1024))
    bk = pa.array(np.array([0] + [1] * 50))

    def q(s):
        stream, build = _stream_and_build(s, sk, bk)
        return (stream.join(build, on=[("sk", "bk")], how="inner")
                .group_by("bv").agg(F.count(F.col("sv")).with_name("n"),
                                    F.sum(F.col("sv")).with_name("t")))

    s = tpu_session(_BOUND_CONF)
    with _fresh_total_stats():
        op = _coalesce_above_join(q(s)._physical())
        assert op is not None, q(s)._physical().tree_string()
        ctx = ExecContext(parent=s.exec_context())
        try:
            with pytest.raises(SpeculativeOverflow) as e:
                list(op.execute(ctx))
            assert e.value.needed == 1024 * 50
            assert e.value.needed > e.value.capacity
        finally:
            ctx.close()
    with _fresh_total_stats():
        s = tpu_session(_BOUND_CONF)
        got = q(s).to_pandas().sort_values("bv").reset_index(drop=True)
    assert list(got["n"]) == [1024] + [2048] * 50
    assert_tpu_and_cpu_equal(q, conf=_BOUND_CONF)


def test_a_repeated_star_query_compiles_nothing_from_its_third_run_on():
    """Two selective broadcast joins under an aggregate, each filled by
    its own operator: run 0 measures (a first batch ever leaves in its
    stream batch's bucket), run 1 may still meet a new shape, and from
    run 2 on the concats' arities and buckets repeat: no executable-cache
    miss and no backend compile, the same rows every time."""
    import numpy as np
    import pyarrow as pa
    from spark_rapids_tpu.plan import exec_cache
    rng = np.random.default_rng(8)
    n, parts = 24 * 512, 24
    fact = pa.table({"f_d": pa.array(rng.integers(0, 120, n)),
                     "f_i": pa.array(rng.integers(0, 300, n)),
                     "f_v": pa.array(np.round(rng.random(n) * 100, 2))})
    dates = pa.table({"d_k": pa.array(np.arange(120)),
                      "d_y": pa.array((1998 + np.arange(120) // 12)
                                      .astype(np.int32)),
                      "d_m": pa.array((np.arange(120) % 12 + 1)
                                      .astype(np.int32))})
    items = pa.table({"i_k": pa.array(np.arange(300)),
                      "i_m": pa.array((np.arange(300) % 10).astype(np.int32)),
                      "i_b": pa.array([f"brand #{i % 13}"
                                       for i in range(300)])})
    conf = {**_BOUND_CONF}

    def q(s):
        for name, t, p in (("fact", fact, parts), ("dates", dates, 1),
                           ("items", items, 1)):
            s.create_dataframe(t, num_partitions=p) \
                .create_or_replace_temp_view(name)
        return s.sql(
            "SELECT d_y, i_b, sum(f_v) AS t FROM dates, fact, items "
            "WHERE d_k = f_d AND f_i = i_k AND d_m = 11 AND i_m = 3 "
            "GROUP BY d_y, i_b ORDER BY d_y, i_b")

    s = tpu_session(conf)
    with _fresh_total_stats():
        tree = q(s)._physical().tree_string()
        assert tree.count("CoalesceBatches[TargetSize(rows=1024") == 3, tree
        answers, misses, compile_s = [], [], []
        for _ in range(4):
            before = exec_cache.stats()
            answers.append(q(s).to_pandas())
            after = exec_cache.stats()
            misses.append(after["misses"] - before["misses"])
            compile_s.append(after["compile_s"] - before["compile_s"])
    assert misses[2:] == [0, 0] and compile_s[2:] == [0.0, 0.0], \
        (misses, compile_s)
    assert len(answers[0]) > 5
    for a in answers[1:]:
        pd.testing.assert_frame_equal(a, answers[0])
    host = q(tpu_session({"spark.rapids.tpu.sql.enabled": False})).to_pandas()
    pd.testing.assert_frame_equal(answers[0], host, check_exact=False,
                                  rtol=1e-12)


# ---------------------------------------------------------------------------
# The join of two sides that are both too large to broadcast (PR 32): the
# smaller side made ready once, the other side's batches joined against it,
# their outputs leaving as one batch; sub-partitions where the build side
# alone passes join.subPartitionSizeBytes. Against CpuJoinExec.
# ---------------------------------------------------------------------------

_SHUFFLED = {"spark.rapids.tpu.sql.autoBroadcastJoinThreshold": 0,
             "spark.rapids.tpu.sql.fusedPipeline.enabled": False}
_HOW = ["inner", "left", "right", "full", "leftsemi", "leftanti"]


def _big_sides(s, unique_build=False, key_hi=60, parts=4):
    import numpy as np
    import pyarrow as pa
    rng = np.random.default_rng(11)
    n = 3000
    l = s.create_dataframe(pa.table({
        "lk": pa.array(rng.integers(0, key_hi, n), mask=rng.random(n) < .05),
        "lv": pa.array(rng.integers(0, 1000, n))}), num_partitions=parts)
    rk = (rng.permutation(key_hi * 2)[:key_hi] if unique_build
          else rng.integers(0, key_hi, 700))
    r = s.create_dataframe(pa.table({
        "rk": pa.array(rk, mask=rng.random(len(rk)) < .05),
        "rv": pa.array(rng.integers(0, 1000, len(rk)))}), num_partitions=2)
    return l, r


@pytest.mark.parametrize("unique_build", [False, True])
@pytest.mark.parametrize("how", _HOW)
def test_shuffled_join_streams_the_larger_side(how, unique_build):
    def q(s):
        l, r = _big_sides(s, unique_build)
        return l.filter(F.col("lv") > 100).join(r, on=[("lk", "rk")],
                                                 how=how)
    from harness import tpu_session
    tree = q(tpu_session(_SHUFFLED))._physical().tree_string()
    assert "* HashJoin[" in tree and "Broadcast" not in tree, tree
    assert_tpu_and_cpu_equal(q, conf=_SHUFFLED)


@pytest.mark.parametrize("how", _HOW)
def test_shuffled_join_sub_partitions_a_large_build_side(how):
    def q(s):
        l, r = _big_sides(s)
        return l.join(r, on=[("lk", "rk")], how=how)
    assert_tpu_and_cpu_equal(q, conf=dict(_SHUFFLED, **_SUBPART_CONF))


def test_shuffled_join_build_side_is_the_smaller_one_either_way_round():
    """The same join written both ways round gives the same rows, and a
    second run (its outputs now sized from the first's totals) too."""
    from harness import tpu_session

    def q(s, flip):
        l, r = _big_sides(s, unique_build=True)
        j = r.join(l, on=[("rk", "lk")], how="inner") if flip \
            else l.join(r, on=[("lk", "rk")], how="inner")
        return j.select("lk", "lv", "rk", "rv")
    s = tpu_session(_SHUFFLED)
    frames = [q(s, flip).to_pandas() for flip in (False, True, False)]
    key = ["lk", "lv", "rk", "rv"]
    want = frames[0].sort_values(key).reset_index(drop=True)
    assert len(want)
    for f in frames[1:]:
        pd.testing.assert_frame_equal(
            f.sort_values(key).reset_index(drop=True), want)


@pytest.mark.parametrize("build", ["dates", "max_key", "duplicate",
                                   "all_null", "empty"])
def test_unique_key_probe_edge_cases(build):
    """The sort-and-scan probe takes an inner join on one integer-lane key
    whose build side holds each key once; anything else (a key twice, a
    live key equal to its padding value) keeps the general kernel. Either
    way the rows are CpuJoinExec's."""
    import numpy as np
    import pyarrow as pa
    big = np.iinfo(np.int64).max
    keys = {"dates": pa.array(np.arange(40).astype("datetime64[D]")),
            "max_key": pa.array([big, 5, 7, big - 1]),
            "duplicate": pa.array([1, 2, 2, 3]),
            "all_null": pa.array([None, None], pa.int64()),
            "empty": pa.array([], pa.int64())}[build]
    stream = {"dates": pa.array((np.arange(300) % 60)
                                .astype("datetime64[D]")),
              }.get(build, pa.array([big, 1, 2, 3, 5, None, big - 1] * 40))

    def q(s):
        l = s.create_dataframe(pa.table({
            "lk": stream, "lv": pa.array(np.arange(len(stream)))}),
            num_partitions=3)
        r = s.create_dataframe(pa.table({
            "rk": keys, "rv": pa.array(np.arange(len(keys)))}))
        return l.join(r, on=[("lk", "rk")], how="inner")
    for conf in (_SHUFFLED, {"spark.rapids.tpu.sql.fusedPipeline.enabled":
                             False}):
        assert_tpu_and_cpu_equal(q, conf=conf)


def test_speculation_statistic_is_the_largest_total_of_the_query():
    """A streaming join registers one speculated total a stream batch and
    its last batch is a partial one: the next run sizes its outputs from
    the LARGEST total seen, or its full batches would overflow and the
    plan re-run with exact sizing (and the general kernel) every time."""
    import jax.numpy as jnp
    from harness import tpu_session
    from spark_rapids_tpu.exec import joins
    ctx = tpu_session().exec_context()
    key = ("test", "speculation", "key")
    try:
        ctx.speculations.extend(
            (jnp.int32(n), 1024, key, None) for n in (700, 900, 30))
        ctx.check_speculations()
        assert joins._TOTAL_STATS[key] == 900 and not ctx.speculations
    finally:
        joins._TOTAL_STATS.pop(key, None)


def test_a_repeated_join_query_settles_after_its_second_run():
    """Run 0 joins two big sides; its measured sizes make the planner
    broadcast the small one from run 1 on (AQE); from then on a repeat
    re-plans the same operators, registers no speculation that overflows
    and compiles nothing new."""
    import numpy as np
    import pyarrow as pa
    from harness import tpu_session
    from spark_rapids_tpu.plan import exec_cache
    rng = np.random.default_rng(2)
    dim = pa.table({"dk": pa.array(np.arange(40_000)),
                    "tag": pa.array(rng.integers(0, 50, 40_000))})
    fact = pa.table({"k": pa.array(rng.integers(0, 40_000, 9_000)),
                     "v": pa.array(rng.random(9_000))})
    s = tpu_session({"spark.rapids.tpu.sql.optimizer.enabled": False,
                     "spark.rapids.tpu.sql.fusedPipeline.enabled": False,
                     "spark.rapids.tpu.sql.batchSizeRows": 4096,
                     # the filtered dim is guessed at a tenth of its
                     # 640,000 bytes: over this, so run 0 joins big sides
                     "spark.rapids.tpu.sql.autoBroadcastJoinThreshold":
                     50_000})

    def q():
        return (s.create_dataframe(fact, num_partitions=3)
                .join(s.create_dataframe(dim).filter(F.col("tag") == 7),
                      on=[("k", "dk")], how="inner")
                .group_by("tag").agg(F.sum(F.col("v")).with_name("sv")))
    plans, answers, misses = [], [], []
    for _ in range(4):
        df = q()
        plans.append(df._physical().tree_string())
        before = exec_cache.stats()["misses"]
        answers.append(df.to_pandas())
        misses.append(exec_cache.stats()["misses"] - before)
    assert "* HashJoin[" in plans[0] and "BroadcastHashJoin" in plans[1]
    assert plans[1] == plans[2] == plans[3]
    assert misses[2:] == [0, 0], misses
    for a in answers[1:]:
        pd.testing.assert_frame_equal(a, answers[0])


def _conditional_big_sides(s, matches=(5_000, 5_000, 500)):
    """Unique build keys; stream batches of 20,000 rows of which
    ``matches`` meet a build key; a condition over both sides that keeps
    about one pair in twenty."""
    import numpy as np
    import pyarrow as pa
    rng = np.random.default_rng(5)
    n_build, per = 20_000, 20_000
    lk = np.concatenate([
        np.concatenate([rng.permutation(n_build)[:m],
                        n_build + rng.integers(0, 1000, per - m)])
        for m in matches])
    l = s.create_dataframe(pa.table({
        "lk": pa.array(lk), "lv": pa.array(rng.integers(0, 1000, len(lk)))}),
        num_partitions=len(matches))
    r = s.create_dataframe(pa.table({
        "rk": pa.array(rng.permutation(n_build)),
        "rv": pa.array(rng.integers(0, 50, n_build))}))
    return l, r


@contextlib.contextmanager
def _fresh_total_stats():
    """The joins' output statistics as a new process has them: empty."""
    from spark_rapids_tpu.exec import joins
    known = dict(joins._TOTAL_STATS)
    joins._TOTAL_STATS.clear()
    try:
        yield
    finally:
        joins._TOTAL_STATS.clear()
        joins._TOTAL_STATS.update(known)


def test_conditional_join_of_big_sides_checks_the_pairs_before_its_condition():
    """5,000 pairs a batch (guessed into the 8,192-row bucket, under the
    stream batch's own 65,536), a condition that keeps one in forty: the
    speculation is on the PAIRS (what the gather cut to its guessed
    bucket), not on what the condition left, and so is the statistic the
    second run sizes from (from 125 rows it would guess 1,024 and lose
    four pairs in five). Both runs give CpuJoinExec's rows."""
    from spark_rapids_tpu.exec import joins

    def q(s):
        l, r = _conditional_big_sides(s)
        return l.join(r, on=[("lk", "rk")], how="inner",
                      condition=F.col("lv") < F.col("rv"))
    with _fresh_total_stats():
        first = assert_tpu_and_cpu_equal(q, conf=_SHUFFLED)
        assert 0 < len(first) < 1024
        assert list(joins._TOTAL_STATS.values()) == [5_000]
        second = assert_tpu_and_cpu_equal(q, conf=_SHUFFLED)
        assert len(second) == len(first)


def test_exact_rerun_after_an_overflow_keeps_the_largest_total():
    """A guess that was too small re-runs the plan with exact sizing, a
    read a stream batch; the statistic it leaves is the largest of them,
    not the last (partial) batch's, so the next run does not overflow
    again: it compiles nothing and gives the same rows."""
    from harness import tpu_session
    from spark_rapids_tpu.exec import joins
    from spark_rapids_tpu.plan import exec_cache
    s = tpu_session(_SHUFFLED)

    def run():
        l, r = _conditional_big_sides(s)
        df = l.join(r, on=[("lk", "rk")], how="inner")
        before = exec_cache.stats()["misses"]
        got = df.to_pandas()
        return got, exec_cache.stats()["misses"] - before
    with _fresh_total_stats():
        want, _ = run()
        (key,) = joins._TOTAL_STATS
        assert joins._TOTAL_STATS[key] == 5_000 and len(want) == 10_500
        joins._TOTAL_STATS[key] = 10       # the next guess: 1,024 rows
        forced, _ = run()
        assert joins._TOTAL_STATS[key] == 5_000
        settled, misses = run()
        assert misses == 0 and joins._TOTAL_STATS[key] == 5_000
    cols = ["lk", "lv", "rk", "rv"]
    for got in (forced, settled):
        pd.testing.assert_frame_equal(
            got.sort_values(cols).reset_index(drop=True),
            want.sort_values(cols).reset_index(drop=True))


@pytest.mark.parametrize("pred,share", [
    (lambda: F.col("tag") == 7, 0.1),
    (lambda: F.lit(7) == F.col("tag"), 0.1),
    (lambda: F.col("tag").isin(1, 2, 3), 0.3),
    (lambda: (F.col("tag") == 7) & (F.col("dk") > 10), 0.1),
    (lambda: F.col("dk") > 10, 1.0),
    (lambda: F.col("tag") == F.col("dk"), 1.0)])
def test_filtered_side_is_sized_by_what_its_filter_is_taken_to_keep(
        pred, share):
    """The plan-time size of a join side under a filter: a tenth for each
    conjunct that holds a column to a literal, k tenths for a list of k,
    all of it otherwise; it decides the broadcast in the FIRST planning
    (a measured size decides from the second on)."""
    import numpy as np
    import pyarrow as pa
    from spark_rapids_tpu.plan.rewrites import estimated_size_bytes
    dim = pa.table({"dk": pa.array(np.arange(5_000)),
                    "tag": pa.array(np.arange(5_000) % 50)})
    fact = pa.table({"k": pa.array(np.arange(20_000) % 5_000)})
    assert dim.nbytes == 80_000
    s = tpu_session({
        "spark.rapids.tpu.sql.fusedPipeline.enabled": False,
        "spark.rapids.tpu.sql.autoBroadcastJoinThreshold": 30_000})
    side = s.create_dataframe(dim).filter(pred())
    assert estimated_size_bytes(side.plan) == int(80_000 * share)
    tree = s.create_dataframe(fact, num_partitions=2).join(
        side, on=[("k", "dk")], how="inner")._physical().tree_string()
    assert ("BroadcastHashJoin" in tree) == (80_000 * share <= 30_000), tree


# ---------------------------------------------------------------------------
# PR 35: the left semi join through the sort-and-scan probe, string
# rectangles through the probe's gather, and the stream side's keys as the
# unique ones where the build side holds a key twice
# ---------------------------------------------------------------------------

def _probe_calls(monkeypatch):
    """The stream batches the sort-and-scan probe joined, by join type
    (``swapped``: with the stream batch's keys as the sorted side)."""
    from spark_rapids_tpu.exec.joins import TpuHashJoinExec
    calls = []
    real = TpuHashJoinExec._join_probe
    real_swapped = TpuHashJoinExec._join_swapped

    def counted(self, ctx, sb, bound, ck):
        calls.append(self.join_type)
        return real(self, ctx, sb, bound, ck)

    def swapped(self, ctx, sb, bb, bound):
        out = real_swapped(self, ctx, sb, bb, bound)
        calls.append("swapped" if out is not None else "refused")
        return out
    monkeypatch.setattr(TpuHashJoinExec, "_join_probe", counted)
    monkeypatch.setattr(TpuHashJoinExec, "_join_swapped", swapped)
    return calls


@pytest.mark.parametrize("broadcast", [False, True])
@pytest.mark.parametrize("unique_build", [True, False])
def test_left_semi_join_through_the_probe_is_the_general_kernels(
        unique_build, broadcast, monkeypatch):
    """A left semi join on one integer key takes ``join_build`` /
    ``join_probe`` (no pair is gathered from the build side), whether its
    build side holds each key once or some of them many times over (a
    semi join asks whether a key is there). The rows are CpuJoinExec's,
    NULL keys on both sides included."""
    conf = dict(_SHUFFLED) if not broadcast else {
        "spark.rapids.tpu.sql.fusedPipeline.enabled": False}
    calls = _probe_calls(monkeypatch)

    def q(s):
        l, r = _big_sides(s, unique_build)
        return l.join(r, on=[("lk", "rk")], how="leftsemi")
    assert_tpu_and_cpu_equal(q, conf=conf)
    assert set(calls) == {"leftsemi"}, calls
    if not broadcast:
        assert calls.count("leftsemi") == 4       # one a stream batch


def test_a_string_rectangle_rides_the_probe_on_the_device(monkeypatch):
    """A high-cardinality string column (a byte rectangle on the device)
    of either side goes through the unique-key probe as its word lanes:
    it leaves the join as a rectangle, not by way of the host."""
    import numpy as np
    import pyarrow as pa
    from harness import OPERATOR_CONF, tpu_session
    from spark_rapids_tpu.columnar.strrect import ByteRectColumn
    calls = _probe_calls(monkeypatch)
    n = 200_000
    rng = np.random.default_rng(2)
    cust = pa.table({"ck": np.arange(1, n + 1),
                     "name": pa.array([f"Customer#{i:09d}"
                                       for i in range(1, n + 1)])})
    orders = pa.table({"ok": np.arange(500) * 3,
                       "oc": rng.permutation(n)[:500] + 1})
    lines = pa.table({"lo": np.repeat(np.arange(1500), 2),
                      "q": np.arange(3000) % 50})

    def q(s):
        c = s.create_dataframe(cust, num_partitions=2)
        o = s.create_dataframe(orders)
        li = s.create_dataframe(lines, num_partitions=3)
        return (c.join(o, on=[("ck", "oc")], how="inner")
                .join(li, on=[("ok", "lo")], how="inner")
                .select("name", "ck", "ok", "q"))
    s = tpu_session({**OPERATOR_CONF, **_SHUFFLED})
    out = list(q(s)._physical().execute(s.exec_context()))
    assert all(isinstance(b.columns[0], ByteRectColumn) for b in out)
    assert calls.count("inner") == 2 + 3          # the stream batches
    assert_tpu_and_cpu_equal(q, conf={**OPERATOR_CONF, **_SHUFFLED})


@pytest.mark.parametrize("broadcast", [False, True])
def test_a_duplicated_build_key_makes_the_stream_side_the_sorted_one(
        broadcast, monkeypatch):
    """A foreign key joined to its primary key with the referencing side
    the smaller one, so the build side holds a key twice and thrice: the
    probe needs ONE side with unique keys, and each stream batch's are
    (a primary key), so the batch is sorted and the build side's rows are
    probed against it. A high-cardinality string column (a rectangle on
    the device) rides along on the stream side."""
    import numpy as np
    import pyarrow as pa
    from harness import OPERATOR_CONF
    calls = _probe_calls(monkeypatch)
    big = pa.table({"pk": np.arange(5000), "pv": np.arange(5000) * 2,
                    "name": pa.array([f"Customer#{i:09d}"
                                      for i in range(5000)])})
    small = pa.table({"fk": pa.array([7, 7, 9, 4000, 4999, None, 9, 9]),
                      "fv": np.arange(8)})

    def q(s):
        return s.create_dataframe(small).join(
            s.create_dataframe(big, num_partitions=3),
            on=[("fk", "pk")], how="inner")
    conf = {**OPERATOR_CONF, **(
        {} if broadcast else _SHUFFLED)}
    got = assert_tpu_and_cpu_equal(q, conf=conf)
    assert len(got) == 7
    assert calls == ["swapped"] * 3       # the big side's three batches


@pytest.mark.parametrize("stream_left", [False, True])
def test_keys_twice_on_both_sides_keep_the_general_kernel(stream_left,
                                                          monkeypatch):
    """Both sides hold a key twice: no side is the probe's sorted one, a
    stream batch whose keys repeat is refused by the swapped probe (its
    one fetch says so) and the general kernel joins it; a batch of the
    same join whose keys are unique still takes the probe."""
    import numpy as np
    import pyarrow as pa
    from harness import OPERATOR_CONF
    calls = _probe_calls(monkeypatch)
    pk = np.arange(4000)
    pk[10] = 11                     # the first batch holds 11 twice
    # (the string column keeps the scan's two batches apart)
    big = pa.table({"pk": pk, "pv": np.arange(4000) * 2,
                    "name": pa.array([f"Customer#{i:09d}"
                                      for i in range(4000)])})
    small = pa.table({"fk": pa.array([11, 7, 7, 3999, None, 2500]),
                      "fv": np.arange(6)})

    def q(s):
        b = s.create_dataframe(big, num_partitions=2)
        sm = s.create_dataframe(small)
        return (b.join(sm, on=[("pk", "fk")], how="inner") if stream_left
                else sm.join(b, on=[("fk", "pk")], how="inner"))
    got = assert_tpu_and_cpu_equal(q, conf=OPERATOR_CONF)
    assert len(got) == 6
    assert calls == ["refused", "swapped"], calls
