"""Hash-distinct (sort-free count-distinct) tests: the DistinctFlag
rewrite + persistent-hash-table operator (exec/distinct_flag.py) against
the independent host engine (ref integration_tests hash_aggregate_test
count-distinct cases)."""
import numpy as np
import pyarrow as pa
import pyarrow.compute as pc
import pytest

from data_gen import DoubleGen, IntGen, gen_df
from harness import assert_tpu_and_cpu_equal, tpu_session
from spark_rapids_tpu.api import functions as F

#: hash distinct only applies off-mesh (the distributed fragment
#: compiler lowers the two-level sort form instead)
_CONF = {"spark.rapids.tpu.distributed.enabled": False}


def _flagged_tree(q):
    tree = q._physical().tree_string()
    assert "DistinctFlag" in tree, tree
    return tree


def test_hash_distinct_grouped_differential():
    def q(s):
        df = s.create_dataframe(
            gen_df({"k": IntGen(lo=0, hi=7),
                    "v": IntGen(lo=0, hi=200),
                    "w": IntGen(nullable=False)}, n=6000, seed=5))
        return (df.group_by("k")
                .agg(F.count_distinct(F.col("v")).with_name("cd"),
                     F.sum(F.col("w")).with_name("sw"),
                     F.count_star().with_name("n")))
    _flagged_tree(q(tpu_session(_CONF)))
    assert_tpu_and_cpu_equal(q, conf=_CONF)


def test_hash_distinct_global_and_sum_avg():
    def q(s):
        df = s.create_dataframe(
            gen_df({"v": IntGen(lo=0, hi=50)}, n=3000, seed=6))
        return df.agg(F.count_distinct(F.col("v")).with_name("cd"),
                      F.sum_distinct(F.col("v")).with_name("sd"),
                      F.avg_distinct(F.col("v")).with_name("ad"))
    _flagged_tree(q(tpu_session(_CONF)))
    assert_tpu_and_cpu_equal(q, conf=_CONF, approximate_float=True)


def test_hash_distinct_float_nan_negzero_null():
    """SQL distinct semantics: NULL ignored, NaN is ONE value,
    -0.0 == 0.0 (the kernel canonicalizes bit patterns)."""
    t = pa.table({
        "k": pa.array([0, 0, 0, 0, 0, 1, 1, 1] * 64, pa.int64()),
        "v": pa.array([1.5, float("nan"), float("nan"), -0.0, 0.0,
                       None, float("nan"), 2.0] * 64),
    })

    def q(s):
        return (s.create_dataframe(t).group_by("k")
                .agg(F.count_distinct(F.col("v")).with_name("cd")))
    got = {r["k"]: r["cd"] for r in q(tpu_session(_CONF)).collect()}
    assert got == {0: 3, 1: 2}, got     # {1.5, nan, 0.0} / {nan, 2.0}
    assert_tpu_and_cpu_equal(q, conf=_CONF)


def test_hash_distinct_null_group_is_a_group():
    def q(s):
        df = s.create_dataframe(
            gen_df({"k": IntGen(lo=0, hi=3, nullable=True),
                    "v": IntGen(lo=0, hi=40)}, n=4000, seed=7))
        return (df.group_by("k")
                .agg(F.count_distinct(F.col("v")).with_name("cd")))
    assert_tpu_and_cpu_equal(q, conf=_CONF)


def test_hash_distinct_multi_batch_and_growth(monkeypatch):
    """Cross-batch dedup through the persistent table, including the
    grow/rebuild path (tiny initial table forces doubling)."""
    from spark_rapids_tpu.exec.distinct_flag import HashDistinctFlagExec
    monkeypatch.setattr(HashDistinctFlagExec, "_MIN_SLOTS", 1 << 10)

    def q(s):
        df = s.create_dataframe(
            gen_df({"k": IntGen(lo=0, hi=5),
                    "v": IntGen(lo=0, hi=100000)}, n=20000, seed=8),
            num_partitions=8)
        return (df.group_by("k")
                .agg(F.count_distinct(F.col("v")).with_name("cd"),
                     F.count(F.col("v")).with_name("c")))
    assert_tpu_and_cpu_equal(
        q, conf={**_CONF, "spark.rapids.tpu.sql.batchSizeRows": 4096})


def test_hash_distinct_matches_sort_path():
    """The hash rewrite and the two-level sort expansion must agree."""
    df_t = gen_df({"k": IntGen(lo=0, hi=9),
                   "v": DoubleGen(),
                   "w": IntGen(nullable=False)}, n=8000, seed=9)
    t = pa.Table.from_pandas(df_t)

    def run(extra):
        s = tpu_session({**_CONF, **extra})
        return (s.create_dataframe(t).group_by("k")
                .agg(F.count_distinct(F.col("v")).with_name("cd"),
                     F.avg(F.col("w")).with_name("aw"))
                .to_pandas().sort_values("k").reset_index(drop=True))
    import pandas as pd
    h = run({})
    s_ = run({"spark.rapids.tpu.sql.hashDistinct.enabled": False})
    pd.testing.assert_frame_equal(h, s_)


def test_hash_distinct_string_value_stays_on_sort_path():
    """Variable-width values can't live in the fixed-width hash table:
    the rewrite must leave string distinct on the two-level path."""
    t = pa.table({"k": pa.array([1, 1, 2] * 100, pa.int64()),
                  "v": pa.array(["a", "b", "a"] * 100)})

    def q(s):
        return (s.create_dataframe(t).group_by("k")
                .agg(F.count_distinct(F.col("v")).with_name("cd")))
    tree = q(tpu_session(_CONF))._physical().tree_string()
    assert "DistinctFlag" not in tree, tree
    assert_tpu_and_cpu_equal(q, conf=_CONF)


def test_hash_distinct_q28_shape():
    """The union-of-aggregates + hash-distinct composition (TPC-DS q28):
    six disjoint branches, one flag pass, no sort anywhere."""
    import sys, os
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
    from benchmarks import tpcds
    tab = tpcds.gen_store_sales(30000, seed=11)

    def q(s):
        return tpcds.q28(s.create_dataframe(tab), F)
    tree = q(tpu_session(_CONF))._physical().tree_string()
    assert "DistinctFlag" in tree, tree
    assert "BranchAlign" in tree, tree
    assert_tpu_and_cpu_equal(q, conf=_CONF, approximate_float=True,
                             ignore_order=False)


def test_union_agg_int_key_direct_addressing():
    """r5: the union-rewrite branch id carries a proven cardinality, so
    the aggregate groups it by direct one-hot addressing — no sort
    kernel — on both the single-batch and multi-batch paths."""
    import sys, os
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
    from benchmarks import tpcds
    from spark_rapids_tpu.exec import aggregate as AG
    tab = tpcds.gen_store_sales(40000, seed=13)

    def q(s):
        return tpcds.q28(s.create_dataframe(tab), F)

    def q_parts(s):
        # multiple in-memory partitions -> multiple batches into the agg
        return tpcds.q28(s.create_dataframe(tab, num_partitions=5), F)

    def drop_direct():
        for k in [k for k in AG._AGG_KERNEL_CACHE
                  if k[0] in ("fastdirect", "carry")]:
            AG._AGG_KERNEL_CACHE.pop(k)

    def direct_kinds():
        return {k[0] for k in AG._AGG_KERNEL_CACHE
                if k[0] in ("fastdirect", "carry")}

    drop_direct()
    assert_tpu_and_cpu_equal(q, conf=_CONF, approximate_float=True,
                             ignore_order=False)
    assert "fastdirect" in direct_kinds(), \
        "single-batch int-key query missed the fused direct path"
    # multi-batch: every batch folds into the direct path's carry
    drop_direct()
    assert_tpu_and_cpu_equal(q_parts, conf=_CONF,
                             approximate_float=True, ignore_order=False)
    assert "carry" in direct_kinds(), \
        "multi-batch int-key query missed the carried direct path"


def test_cpu_twin_nan_cross_batch_and_big_ints():
    """r5 review scenarios: the vectorized CPU twin must not overcount
    NaN across batches (nan != nan in python tuples) nor lose int64
    precision above 2**53 when a null forces a float conversion."""
    conf = {**_CONF, "spark.rapids.tpu.sql.exec.HashAggregateExec": False}
    t = pa.table({"v": pa.array([1.0, float("nan")] * 100
                                + [float("nan")] * 100)})
    s = tpu_session(conf)
    out = (s.create_dataframe(t, num_partitions=4)
           .agg(F.count_distinct(F.col("v")).with_name("cd")).collect())
    assert out[0]["cd"] == 2, out
    big = 2 ** 53
    t2 = pa.table({"v": pa.array([big, big + 1, None, big, big + 1],
                                 pa.int64())})
    out2 = (s.create_dataframe(t2, num_partitions=2)
            .agg(F.count_distinct(F.col("v")).with_name("cd")).collect())
    assert out2[0]["cd"] == 2, out2


def test_cpu_twin_packed_byte_keys_strings_and_specials():
    """ADVICE r5: the CPU twin's cross-batch seen-set stores packed
    bytes of the normalized int64 lanes (incl. first-seen string codes
    and null-mask lanes), not python tuples. Drive the exec directly
    over a multi-batch scan and check SQL distinct semantics survive:
    NaN is ONE value, -0.0 == 0.0, NULL values never flag, NULL group
    is a real group, and string codes stay stable across batches."""
    from spark_rapids_tpu.exec.base import ExecContext
    from spark_rapids_tpu.exec.basic import InMemoryScanExec
    from spark_rapids_tpu.exec.distinct_flag import CpuDistinctFlagExec
    from spark_rapids_tpu.exprs.base import ColumnRef
    from spark_rapids_tpu.types import Schema, StructField, from_arrow

    g = (["a", "b", None] * 40)[:100]
    v = ([1.0, float("nan"), -0.0, 0.0, None] * 20)[:100]
    t = pa.table({"g": pa.array(g), "v": pa.array(v, pa.float64())})
    schema = Schema([StructField(f.name, from_arrow(f.type), True)
                     for f in t.schema])
    scan = InMemoryScanExec([t], schema, batch_rows=17)  # many batches
    ex = CpuDistinctFlagExec([ColumnRef("g")], ColumnRef("v"), "__hd",
                             scan)
    out = pa.concat_tables(b.to_arrow()
                           for b in ex.execute(ExecContext()))
    df = out.to_pandas()
    counts = {}
    for gg, sub in df.groupby("g", dropna=False):
        counts[None if gg is None or (isinstance(gg, float)
                                      and np.isnan(gg)) else gg] = \
            int(sub["__hd"].sum())
    want = {}
    for gg, vv in zip(g, v):
        if vv is None:
            continue
        key = vv
        if isinstance(vv, float):
            if np.isnan(vv):
                key = "nan"
            elif vv == 0.0:
                key = 0.0          # -0.0 == 0.0 for SQL distinct
        want.setdefault(gg, set()).add(key)
    assert counts == {k: len(s) for k, s in want.items()}, counts
    # the flags across ALL batches count each distinct pair ONCE
    assert int(df["__hd"].sum()) == sum(len(s) for s in want.values())
