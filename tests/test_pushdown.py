"""Predicate pushdown below joins (``plan/rewrites.py:
push_filters_below_joins``; ref Spark PushPredicateThroughJoin) and the
projection a join input gets onto what is read above it: where each
conjunct of a filter above a join lands, by join type, and that the
answer is the un-pushed host engine's."""
import numpy as np
import pyarrow as pa
import pytest

from harness import assert_tpu_and_cpu_equal, tpu_session
from spark_rapids_tpu.api import functions as F
from spark_rapids_tpu.plan import logical as L
from spark_rapids_tpu.plan.rewrites import (_conjuncts,
                                            push_filters_below_joins)
from spark_rapids_tpu.trace import Tracer, install_tracer

CONF = {"spark.rapids.tpu.sql.fusedPipeline.enabled": False}


def _sides(s, n=400):
    rng = np.random.default_rng(3)
    l = s.create_dataframe(pa.table({
        "lk": pa.array(rng.integers(0, 50, n), mask=rng.random(n) < 0.1),
        "lv": pa.array(rng.integers(0, 100, n))}))
    r = s.create_dataframe(pa.table({
        "rk": pa.array(rng.integers(0, 50, n // 2),
                       mask=rng.random(n // 2) < 0.1),
        "rv": pa.array(rng.integers(0, 100, n // 2))}))
    return l, r


def _filters(plan, out=None):
    """[(condition text, the node the filter sits on)] of a logical plan."""
    out = [] if out is None else out
    if isinstance(plan, L.Filter):
        conds = []
        _conjuncts(plan.condition, conds)
        out.extend((c.name_hint, type(plan.children[0]).__name__)
                   for c in conds)
    for c in plan.children:
        _filters(c, out)
    return out


#: join type -> (the conjunct over the left input moves, the one over the
#: right input moves): never below the side the join NULL-extends
CASES = {"inner": (True, True), "left": (True, False),
         "right": (False, True), "full": (False, False),
         "leftsemi": (True, None), "leftanti": (True, None)}


@pytest.mark.parametrize("how", sorted(CASES))
def test_one_input_conjuncts_move_below_the_join_by_its_type(how):
    moves_l, moves_r = CASES[how]

    def q(s):
        l, r = _sides(s)
        j = l.join(r, on=[("lk", "rk")], how=how)
        cond = F.col("lv") > 30
        if moves_r is not None:         # semi/anti joins emit no right column
            cond = cond & (F.col("rv") < 70)
        return j.filter(cond)

    plan, pushed, above = push_filters_below_joins(q(tpu_session()).plan)
    where = dict(_filters(plan))
    assert where["(lv > 30)"] == ("LogicalScan" if moves_l else "Join")
    if moves_r is not None:
        assert where["(rv < 70)"] == ("LogicalScan" if moves_r else "Join")
    moved = int(moves_l) + int(bool(moves_r))
    assert (pushed, above) == (moved, (1 if moves_r is None else 2) - moved)
    assert_tpu_and_cpu_equal(q, conf=CONF)


def test_a_conjunct_over_both_inputs_stays_and_the_others_go():
    def q(s):
        l, r = _sides(s)
        return l.join(r, on=[("lk", "rk")], how="inner").filter(
            (F.col("lv") > 30) & (F.col("lv") + F.col("rv") < 120)
            & (F.col("rv") < 70))

    plan, pushed, above = push_filters_below_joins(q(tpu_session()).plan)
    assert sorted(_filters(plan)) == [
        ("((lv + rv) < 120)", "Join"), ("(lv > 30)", "LogicalScan"),
        ("(rv < 70)", "LogicalScan")]
    assert (pushed, above) == (2, 0)
    assert_tpu_and_cpu_equal(q, conf=CONF)


def test_a_conjunct_goes_below_every_join_it_can_cross():
    def q(s):
        l, r = _sides(s)
        t = s.create_dataframe(pa.table({
            "tk": pa.array(np.arange(50)), "tv": pa.array(np.arange(50))}))
        return (l.join(r, on=[("lk", "rk")], how="inner")
                .join(t, on=[("rk", "tk")], how="left")
                .filter((F.col("lv") > 30) & (F.col("rv") < 70)
                        & (F.col("tv") > 5)))

    plan, pushed, above = push_filters_below_joins(q(tpu_session()).plan)
    # below the left join AND the inner one; tv is of the NULL-extended side
    assert sorted(_filters(plan)) == [
        ("(lv > 30)", "LogicalScan"), ("(rv < 70)", "LogicalScan"),
        ("(tv > 5)", "Join")]
    assert (pushed, above) == (2, 1)
    assert_tpu_and_cpu_equal(q, conf=CONF)


def test_a_value_with_per_task_state_stays_where_it_was_written():
    s = tpu_session()
    l, r = _sides(s)
    df = l.join(r, on=[("lk", "rk")], how="inner").filter(
        (F.col("lv") > 30) & (F.monotonically_increasing_id() % 2 == 0))
    plan, pushed, above = push_filters_below_joins(df.plan)
    where = dict(_filters(plan))
    assert where["(lv > 30)"] == "LogicalScan" and pushed == 1
    assert [n for c, n in where.items() if "lv" not in c] == ["Join"]


def test_a_plan_with_nothing_to_move_comes_back_as_it_was():
    s = tpu_session()
    l, r = _sides(s)
    for df in (l.filter(F.col("lv") > 3),
               l.join(r, on=[("lk", "rk")], how="full")
               .filter(F.col("lv") > F.col("rv"))):
        plan, pushed, above = push_filters_below_joins(df.plan)
        assert plan is df.plan and (pushed, above) == (0, 0)


def test_a_join_input_is_projected_onto_what_is_read_above_it():
    """The column only the pushed filter reads does not enter the join."""
    s = tpu_session(CONF)
    l, r = _sides(s)
    df = (l.join(r, on=[("lk", "rk")], how="inner")
          .filter(F.col("rv") < 70).group_by("lk")
          .agg(F.sum(F.col("lv")).with_name("s")))
    tree = df._physical().tree_string()
    # one fused stage below the join: the filter, then the projection
    assert "fused=[Filter[(rv < 70)], Project[rk]]" in tree
    assert_tpu_and_cpu_equal(lambda s: (
        _sides(s)[0].join(_sides(s)[1], on=[("lk", "rk")], how="inner")
        .filter(F.col("rv") < 70).group_by("lk")
        .agg(F.sum(F.col("lv")).with_name("s"))), conf=CONF)


def test_plan_pushdown_counter_once_a_planned_query():
    s = tpu_session(CONF)
    l, r = _sides(s)
    df = l.join(r, on=[("lk", "rk")], how="left").filter(
        (F.col("lv") > 30) & (F.col("rv") < 70))
    tr = install_tracer(Tracer())
    try:
        df.collect_arrow()
    finally:
        install_tracer(None)
    counters = [e["args"] for e in tr.snapshot()
                if e["ph"] == "C" and e["name"] == "plan.pushdown"]
    assert counters == [{"pushed": 1, "above_joins": 1}]


@pytest.mark.parametrize("how,side,moves", [
    ("inner", "l", True), ("inner", "r", True), ("left", "l", True),
    ("left", "r", False), ("full", "l", False)])
@pytest.mark.parametrize("kind", ["leftsemi", "leftanti"])
def test_a_semi_join_goes_below_the_join_whose_one_input_its_keys_name(
        how, side, moves, kind):
    """``expr IN (select ...)`` is a left semi join of the joined frame
    against a relation of its own (PR 35): a filter of the rows of ONE
    input where its keys name that input's columns alone, so it moves as a
    conjunct would (and counts as one), and the answer is the host
    engine's un-pushed one."""
    def q(s):
        l, r = _sides(s)
        keys = s.create_dataframe(pa.table({
            "kk": pa.array([3, 5, 5, None, 8, 40, 41])}))
        col = {"l": "lv", "r": "rv"}[side]
        return (l.join(r, on=[("lk", "rk")], how=how)
                .filter(F.col("lv") > 10)
                .join(keys, on=[(F.col(col) % 50, F.col("kk"))], how=kind))

    plan, pushed, above = push_filters_below_joins(q(tpu_session()).plan)

    def joins_in(node):
        return isinstance(node, L.Join) + sum(map(joins_in, node.children))

    def semi(node):
        if isinstance(node, L.Join) and node.join_type == kind:
            return node
        return next((w for w in map(semi, node.children) if w), None)
    # through the filter in its way and below the join, or where it was
    assert joins_in(semi(plan).children[0]) == int(not moves)
    lv_pushed = how in ("inner", "left")
    assert (pushed, above) == (int(moves) + int(lv_pushed),
                               int(not moves) + int(not lv_pushed))
    assert_tpu_and_cpu_equal(q, conf=CONF)
