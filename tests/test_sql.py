"""SQL front-end tests: text -> logical plan -> differential vs the
DataFrame formulation and vs the host oracle (the reference consumes SQL
through Spark's parser; this framework ships its own ANSI analytics
subset — spark_rapids_tpu/sql/)."""
import os
import sys

import numpy as np
import pandas as pd
import pyarrow as pa
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from benchmarks import tpcds, tpch
from harness import tpu_session
from spark_rapids_tpu.api import functions as F
from spark_rapids_tpu.sql.parser import SqlError


def _sess():
    s = tpu_session()
    s.create_dataframe(tpch.gen_lineitem(10_000)) \
        .create_or_replace_temp_view("lineitem")
    s.create_dataframe(tpcds.gen_store_sales(8_000)) \
        .create_or_replace_temp_view("store_sales")
    s.create_dataframe(tpcds.gen_date_dim()) \
        .create_or_replace_temp_view("date_dim")
    s.create_dataframe(tpcds.gen_item()) \
        .create_or_replace_temp_view("item")
    return s


def test_sql_tpch_q1_matches_dataframe():
    s = _sess()
    got = s.sql("""
        SELECT l_returnflag, l_linestatus,
               sum(l_quantity) AS sum_qty,
               sum(l_extendedprice * (1 - l_discount)) AS sum_disc_price,
               avg(l_discount) AS avg_disc,
               count(*) AS count_order
        FROM lineitem
        WHERE l_shipdate <= date '1998-12-01' - interval '90' day
        GROUP BY l_returnflag, l_linestatus
        ORDER BY l_returnflag, l_linestatus""").to_pandas()
    assert len(got) == 6
    exp = tpch.q1(s.create_dataframe(tpch.gen_lineitem(10_000)), F) \
        .to_pandas()
    np.testing.assert_allclose(got["sum_disc_price"],
                               exp["sum_disc_price"], rtol=1e-12)


def test_sql_tpcds_q3_join():
    s = _sess()
    got = s.sql("""
        SELECT d_year, i_brand_id, i_brand,
               sum(ss_ext_sales_price) AS sum_agg
        FROM date_dim, store_sales, item
        WHERE d_date_sk = ss_sold_date_sk AND ss_item_sk = i_item_sk
          AND i_manufact_id = 128 AND d_moy = 11
        GROUP BY d_year, i_brand_id, i_brand
        ORDER BY d_year, sum_agg DESC, i_brand_id""").to_pandas()
    exp = tpcds.q3(s.create_dataframe(tpcds.gen_store_sales(8_000)),
                   s.create_dataframe(tpcds.gen_date_dim()),
                   s.create_dataframe(tpcds.gen_item()), F).to_pandas()
    assert len(got) == len(exp)
    np.testing.assert_allclose(sorted(got["sum_agg"]),
                               sorted(exp["sum_agg"]), rtol=1e-12)


def test_sql_explicit_join_on_and_using():
    s = _sess()
    a = s.sql("""SELECT d_year, count(*) AS n
                 FROM store_sales JOIN date_dim
                      ON ss_sold_date_sk = d_date_sk
                 GROUP BY d_year ORDER BY d_year""").to_pandas()
    assert a["n"].sum() == 8000
    s.create_dataframe(pa.table({"k": [1, 2, 3], "x": [10, 20, 30]})) \
        .create_or_replace_temp_view("t1")
    s.create_dataframe(pa.table({"k": [2, 3, 4], "y": [5, 6, 7]})) \
        .create_or_replace_temp_view("t2")
    u = s.sql("SELECT k, x, y FROM t1 JOIN t2 USING (k) ORDER BY k") \
        .to_pandas()
    assert list(u["k"]) == [2, 3] and list(u.columns) == ["k", "x", "y"]
    lo = s.sql("SELECT k, x, y FROM t1 LEFT JOIN t2 USING (k) ORDER BY k") \
        .to_pandas()
    assert len(lo) == 3 and lo["y"].isna().sum() == 1


def test_sql_case_when_and_conditional_agg():
    s = _sess()
    got = s.sql("""
        SELECT count(CASE WHEN ss_quantity BETWEEN 1 AND 20
                          THEN 1 ELSE NULL END) AS b1,
               avg(CASE WHEN ss_quantity BETWEEN 1 AND 20
                        THEN ss_ext_sales_price ELSE NULL END) AS a1
        FROM store_sales""").to_pandas()
    raw = tpcds.gen_store_sales(8_000).to_pandas()
    m = (raw["ss_quantity"] >= 1) & (raw["ss_quantity"] <= 20)
    assert int(got["b1"][0]) == int(m.sum())
    np.testing.assert_allclose(got["a1"][0],
                               raw.loc[m, "ss_ext_sales_price"].mean(),
                               rtol=1e-9)


def test_sql_cte_having_union_limit():
    s = _sess()
    got = s.sql("""
        WITH big AS (
            SELECT l_orderkey, sum(l_quantity) AS q
            FROM lineitem GROUP BY l_orderkey HAVING sum(l_quantity) > 60
        )
        SELECT l_orderkey, q FROM big ORDER BY q DESC, l_orderkey
        LIMIT 10""").to_pandas()
    assert len(got) == 10 and (got["q"] > 60).all()
    assert list(got["q"]) == sorted(got["q"], reverse=True)

    u = s.sql("""
        SELECT 1 AS v FROM (SELECT l_orderkey FROM lineitem LIMIT 1) x
        UNION ALL
        SELECT 2 AS v FROM (SELECT l_orderkey FROM lineitem LIMIT 1) y
        ORDER BY v""").to_pandas()
    assert list(u["v"]) == [1, 2]


def test_sql_count_distinct_and_aliases():
    s = _sess()
    got = s.sql("""
        SELECT count(DISTINCT ss_item_sk) AS items,
               count(*) AS n, sum(ss_quantity) / count(*) AS avg_q
        FROM store_sales""").to_pandas()
    raw = tpcds.gen_store_sales(8_000).to_pandas()
    assert int(got["items"][0]) == raw["ss_item_sk"].nunique()
    assert int(got["n"][0]) == 8000
    np.testing.assert_allclose(got["avg_q"][0], raw["ss_quantity"].mean(),
                               rtol=1e-9)


def test_sql_scalar_fns_in_like_strings():
    s = _sess()
    t = pa.table({"name": ["Alice", "bob", "CAROL", None],
                  "v": [1.5, -2.5, 3.25, 4.0]})
    s.create_dataframe(t).create_or_replace_temp_view("people")
    got = s.sql("""
        SELECT upper(name) AS u, abs(v) AS av
        FROM people
        WHERE name IS NOT NULL AND lower(name) LIKE '%o%'
        ORDER BY u""").to_pandas()
    assert list(got["u"]) == ["BOB", "CAROL"]
    got2 = s.sql("SELECT v FROM people WHERE v IN (1.5, 4) ORDER BY v") \
        .to_pandas()
    assert list(got2["v"]) == [1.5, 4.0]
    n = s.sql("SELECT count(*) AS n FROM people "
              "WHERE name NOT LIKE '%o%' AND name IS NOT NULL").to_pandas()
    # LIKE is case-sensitive: 'bob' matches '%o%'; 'Alice' and 'CAROL'
    # (uppercase O) do not
    assert int(n["n"][0]) == 2


def test_sql_group_by_ordinal_and_alias():
    s = _sess()
    a = s.sql("""SELECT l_returnflag AS rf, count(*) AS n
                 FROM lineitem GROUP BY 1 ORDER BY 1""").to_pandas()
    b = s.sql("""SELECT l_returnflag AS rf, count(*) AS n
                 FROM lineitem GROUP BY rf ORDER BY rf""").to_pandas()
    pd.testing.assert_frame_equal(a, b)
    assert list(a["rf"]) == ["A", "N", "R"]


def test_sql_errors_are_actionable():
    s = _sess()
    with pytest.raises(SqlError, match="not found"):
        s.sql("SELECT * FROM nope")
    with pytest.raises(SqlError):
        s.sql("SELECT FROM lineitem")
    with pytest.raises(SqlError, match="unknown function"):
        s.sql("SELECT frobnicate(l_quantity) FROM lineitem")


def test_sql_order_by_agg_and_hidden_columns():
    s = _sess()
    got = s.sql("""SELECT l_returnflag FROM lineitem
                   GROUP BY l_returnflag ORDER BY count(*) DESC""") \
        .to_pandas()
    raw = tpch.gen_lineitem(10_000).to_pandas()
    exp = raw.groupby("l_returnflag").size().sort_values(ascending=False)
    assert list(got["l_returnflag"]) == list(exp.index)
    # aliased group key ordered by its source name
    got2 = s.sql("""SELECT l_returnflag AS rf, count(*) AS n FROM lineitem
                    GROUP BY l_returnflag ORDER BY l_returnflag""") \
        .to_pandas()
    assert list(got2["rf"]) == ["A", "N", "R"]


def test_sql_self_join_with_aliases():
    s = _sess()
    import pyarrow as pa
    s.create_dataframe(pa.table({"k": [1, 2, 3], "x": [10, 20, 30]})) \
        .create_or_replace_temp_view("t1")
    got = s.sql("""SELECT count(*) AS n FROM t1 a JOIN t1 b ON a.k = b.k""") \
        .to_pandas()
    assert int(got["n"][0]) == 3


def test_sql_using_right_and_full_outer_keys():
    s = _sess()
    import pyarrow as pa
    s.create_dataframe(pa.table({"k": [1, 2, 3], "x": [10, 20, 30]})) \
        .create_or_replace_temp_view("t1")
    s.create_dataframe(pa.table({"k": [2, 3, 4], "y": [5, 6, 7]})) \
        .create_or_replace_temp_view("t2")
    r = s.sql("SELECT k, y FROM t1 RIGHT JOIN t2 USING (k) ORDER BY k") \
        .to_pandas()
    assert list(r["k"]) == [2, 3, 4]
    f = s.sql("SELECT k FROM t1 FULL JOIN t2 USING (k) ORDER BY k") \
        .to_pandas()
    assert list(f["k"]) == [1, 2, 3, 4]


def test_sql_negative_in_semicolon_and_bad_ordinal():
    s = _sess()
    import pyarrow as pa
    s.create_dataframe(pa.table({"x": [-1, 2, 5]})) \
        .create_or_replace_temp_view("t")
    got = s.sql("SELECT x FROM t WHERE x IN (-1, 2) ORDER BY x;") \
        .to_pandas()
    assert list(got["x"]) == [-1, 2]
    with pytest.raises(SqlError, match="ordinal"):
        s.sql("SELECT x FROM t GROUP BY 0")
    with pytest.raises(SqlError, match="ordinal"):
        s.sql("SELECT x FROM t ORDER BY 5")


def test_sql_window_functions():
    s = _sess()
    t = pa.table({"g": ["a", "a", "a", "b", "b"],
                  "v": [3.0, 1.0, 2.0, 5.0, 4.0]})
    s.create_dataframe(t).create_or_replace_temp_view("w")
    got = s.sql("""
        SELECT g, v,
               row_number() OVER (PARTITION BY g ORDER BY v) AS rn,
               sum(v) OVER (PARTITION BY g) AS gs,
               lag(v, 1) OVER (PARTITION BY g ORDER BY v) AS pv
        FROM w ORDER BY g, v""").to_pandas()
    assert list(got["rn"]) == [1, 2, 3, 1, 2]
    assert list(got["gs"]) == [6.0, 6.0, 6.0, 9.0, 9.0]
    assert got["pv"].isna().sum() == 2    # first row of each partition
    assert list(got["pv"].dropna()) == [1.0, 2.0, 4.0]


def test_sql_window_running_sum_frame():
    s = _sess()
    t = pa.table({"v": [1.0, 2.0, 3.0, 4.0]})
    s.create_dataframe(t).create_or_replace_temp_view("w2")
    got = s.sql("""
        SELECT v, sum(v) OVER (ORDER BY v
            ROWS BETWEEN UNBOUNDED PRECEDING AND CURRENT ROW) AS rs
        FROM w2 ORDER BY v""").to_pandas()
    assert list(got["rs"]) == [1.0, 3.0, 6.0, 10.0]


def test_sql_window_over_aggregate_requires_subquery():
    s = _sess()
    with pytest.raises(SqlError, match="subquery"):
        s.sql("""SELECT l_returnflag, rank() OVER (ORDER BY sum(l_quantity))
                 FROM lineitem GROUP BY l_returnflag""")
    # the subquery formulation works
    got = s.sql("""
        SELECT rf, rank() OVER (ORDER BY sq DESC) AS r FROM
          (SELECT l_returnflag AS rf, sum(l_quantity) AS sq
           FROM lineitem GROUP BY l_returnflag) t
        ORDER BY r""").to_pandas()
    assert list(got["r"]) == [1, 2, 3]


def test_sql_window_extras():
    s = _sess()
    t = pa.table({"g": ["a", "a", "b", "b", None],
                  "v": pa.array([3.0, None, 2.0, 5.0, 4.0])})
    s.create_dataframe(t).create_or_replace_temp_view("wx")
    # ntile + negative lag default + trailing ';' + soft keyword column
    got = s.sql("""
        SELECT g, v, ntile(2) OVER (ORDER BY v NULLS FIRST) AS nt,
               lag(v, 1, -1) OVER (PARTITION BY g ORDER BY v) AS pv
        FROM wx ORDER BY g NULLS FIRST, v;""").to_pandas()
    assert set(got["nt"]) == {1, 2}
    assert (got["pv"].dropna() >= -1).all()
    # window in ORDER BY only
    r = s.sql("""SELECT v FROM wx
                 ORDER BY row_number() OVER (ORDER BY v DESC)""") \
        .to_pandas()
    assert list(r["v"].dropna()) == [5.0, 4.0, 3.0, 2.0]
    # DISTINCT inside a window is rejected loudly
    with pytest.raises(SqlError, match="DISTINCT"):
        s.sql("SELECT sum(DISTINCT v) OVER () FROM wx")
    # soft keywords usable as column names
    s.create_dataframe(pa.table({"rows": [1, 2], "current": [3, 4]})) \
        .create_or_replace_temp_view("soft")
    assert s.sql("SELECT rows, current FROM soft ORDER BY rows") \
        .count() == 2


def test_sql_rank_null_order_keys_tie():
    s = _sess()
    t = pa.table({"v": pa.array([None, None, 1.0, 2.0])})
    s.create_dataframe(t).create_or_replace_temp_view("nt")
    got = s.sql("""SELECT v, rank() OVER (ORDER BY v) AS r,
                          dense_rank() OVER (ORDER BY v) AS dr
                   FROM nt ORDER BY r, v""").to_pandas()
    assert list(got["r"]) == [1, 1, 3, 4]
    assert list(got["dr"]) == [1, 1, 2, 3]


def test_sql_tpc_query_texts_match_dataframe():
    """The canonical SQL texts (benchmarks/queries_sql.py) agree with the
    DataFrame formulations."""
    from benchmarks import queries_sql as Q
    s = tpu_session()
    Q.register_tpch(s, 10_000)
    Q.register_tpcds(s, 8_000)
    q1 = s.sql(Q.TPCH_Q1).to_pandas()
    e1 = tpch.q1(s.create_dataframe(tpch.gen_lineitem(10_000)), F) \
        .to_pandas()
    pd.testing.assert_frame_equal(q1, e1, check_exact=False, rtol=1e-12)
    q6 = s.sql(Q.TPCH_Q6).to_pandas()
    e6 = tpch.q6(s.create_dataframe(tpch.gen_lineitem(10_000)), F) \
        .to_pandas()
    np.testing.assert_allclose(q6["revenue"], e6["revenue"], rtol=1e-12)
    q3 = s.sql(Q.TPCDS_Q3).to_pandas()
    e3 = tpcds.q3(s.create_dataframe(tpcds.gen_store_sales(8_000)),
                  s.create_dataframe(tpcds.gen_date_dim()),
                  s.create_dataframe(tpcds.gen_item()), F).to_pandas()
    np.testing.assert_allclose(sorted(q3["sum_agg"]),
                               sorted(e3["sum_agg"]), rtol=1e-12)


def test_io_path_replacement(tmp_path):
    import pyarrow.parquet as pq
    real = tmp_path / "data"
    real.mkdir()
    pq.write_table(pa.table({"a": [1, 2, 3]}), str(real / "t.parquet"))
    s = tpu_session({"spark.rapids.tpu.io.pathReplacementRules":
                     f"s3://fake-bucket->{real}"})
    df = s.read_parquet("s3://fake-bucket/t.parquet")
    assert df.count() == 3


def test_shuffle_codec_conf():
    from harness import tpu_session
    import numpy as np
    t = pa.table({"k": pa.array(np.arange(5000) % 7),
                  "v": pa.array(np.ones(5000))})
    for codec in ("lz4", "zstd", "none"):
        s = tpu_session({"spark.rapids.tpu.shuffle.compression.codec": codec})
        out = s.create_dataframe(t).repartition(4, F.col("k")).count()
        assert out == 5000


def test_path_rules_and_codec_validation():
    import pytest
    from spark_rapids_tpu.io.file_scan import apply_path_rules
    from spark_rapids_tpu.config import TpuConf
    conf = TpuConf({"spark.rapids.tpu.io.pathReplacementRules": "s3://b"})
    with pytest.raises(ValueError, match="malformed"):
        apply_path_rules(conf, ["s3://b/x"])
    s = tpu_session({"spark.rapids.tpu.shuffle.compression.codec": "snappy"})
    t = pa.table({"k": [1, 2, 3]})
    with pytest.raises(ValueError, match="unsupported shuffle codec"):
        s.create_dataframe(t).repartition(2, F.col("k")).count()


def test_qualified_refs_with_colliding_join_columns():
    """t.k and r.k must stay distinct after a join (Spark keeps attributes
    by expression id; the lowerer renames collisions internally)."""
    t = pa.table({"k": [1, 2, 2], "v": [1.0, 2.0, 3.0]})
    r = pa.table({"k": [2, 3], "name": ["a", "b"]})
    for enabled in (True, False):
        s = tpu_session({"spark.rapids.tpu.sql.enabled": enabled})
        s.create_dataframe(t).create_or_replace_temp_view("t")
        s.create_dataframe(r).create_or_replace_temp_view("r")
        got = s.sql("""SELECT t.k, count(name) c FROM t LEFT JOIN r
                       ON t.k = r.k GROUP BY t.k ORDER BY t.k""").collect()
        assert got == [{"k": 1, "c": 0}, {"k": 2, "c": 2}]
        both = s.sql("SELECT t.k, r.k FROM t JOIN r ON t.k = r.k") \
            .collect_arrow()
        assert both.column_names == ["k", "k"]
        star = s.sql("SELECT r.* FROM t JOIN r ON t.k = r.k").collect()
        assert star == [{"k": 2, "name": "a"}, {"k": 2, "name": "a"}]


# ---------------------------------------------------------------------------
# expr [NOT] IN (select ...) in a WHERE (PR 35): a left semi join
# ---------------------------------------------------------------------------

def _in_subquery_tables():
    import numpy as np
    rng = np.random.default_rng(5)
    n = 600
    t = pa.table({
        "k": pa.array(rng.integers(0, 40, n), mask=rng.random(n) < 0.15),
        "v": pa.array(np.arange(n))})
    # duplicates and NULLs among the subquery's values
    u = pa.table({
        "k2": pa.array(rng.integers(0, 80, 90), mask=rng.random(90) < 0.2),
        "w": pa.array(rng.integers(0, 10, 90))})
    return t, u


@pytest.mark.parametrize("where,keep", [
    # NULLs in the probe column never match; duplicates and NULLs among
    # the subquery's values change nothing
    ("k in (select k2 from u)",
     lambda t, u: t.k.isin(u.k2.dropna())),
    # an expression on the probe side, an aggregate in the subquery
    ("k + 1 in (select k2 from u group by k2 having sum(w) > 6)",
     lambda t, u: (t.k + 1).isin(
         u.groupby("k2").w.sum().loc[lambda s: s > 6].index)),
    # an empty subquery keeps nothing
    ("k in (select k2 from u where w > 99)",
     lambda t, u: t.k.isin([])),
    # beside other conjuncts
    ("v > 100 and k in (select k2 from u where w < 5) and v < 500",
     lambda t, u: (t.v > 100) & (t.v < 500)
     & t.k.isin(u[u.w < 5].k2.dropna())),
])
def test_in_subquery_is_a_semi_join_with_sparks_null_semantics(where, keep):
    t, u = _in_subquery_tables()
    tp, up = t.to_pandas(), u.to_pandas()
    want = tp[keep(tp, up)].v.tolist()
    for enabled in (True, False):
        s = tpu_session({"spark.rapids.tpu.sql.enabled": enabled,
                         "spark.rapids.tpu.sql.fusedPipeline.enabled":
                         False})
        s.create_dataframe(t).create_or_replace_temp_view("t")
        s.create_dataframe(u).create_or_replace_temp_view("u")
        df = s.sql(f"select v from t where {where} order by v")
        if enabled:
            tree = df._physical().tree_string()
            assert "Join[leftsemi" in tree and "Cpu" not in tree, tree
        assert df.collect_arrow().column("v").to_pylist() == want


def test_in_subquery_over_a_join_runs_below_it():
    """The semi-join names columns of ONE input of the implicit join, so
    it filters that input before the join (plan/rewrites.py)."""
    t, u = _in_subquery_tables()
    from harness import OPERATOR_CONF
    s = tpu_session({**OPERATOR_CONF,
                     "spark.rapids.tpu.sql.autoBroadcastJoinThreshold": 0})
    s.create_dataframe(t).create_or_replace_temp_view("t")
    s.create_dataframe(u).create_or_replace_temp_view("u")
    df = s.sql("""select v, w from t, u
                  where k = k2 and w in (select k2 from u) order by v, w""")
    lines = [ln.strip() for ln in df._physical().tree_string().splitlines()]
    semi = next(i for i, ln in enumerate(lines) if "Join[leftsemi" in ln)
    assert "Cpu" not in "".join(lines), lines
    assert any("Join[inner" in ln for ln in lines[:semi]), lines
    assert not any("Join[inner" in ln for ln in lines[semi:]), lines
    tp, up = t.to_pandas(), u.to_pandas()
    # (pandas joins NaN to NaN; SQL's NULL keys match nothing)
    want = tp.dropna(subset=["k"]).merge(
        up[up.w.isin(up.k2.dropna())].dropna(subset=["k2"]), left_on="k",
        right_on="k2").sort_values(["v", "w"])
    got = df.collect_arrow().to_pandas()
    assert got.v.tolist() == want.v.tolist()
    assert got.w.tolist() == want.w.tolist()


@pytest.mark.parametrize("text,says", [
    ("select v from t where k not in (select k2 from u)",
     "NOT IN (select ...) is not supported"),
    ("select v from t where k in (select k2, w from u)", "ONE column"),
    ("select v from t where v > 3 or k in (select k2 from u)",
     "conjunct of WHERE"),
    ("select k in (select k2 from u) from t", "conjunct of WHERE"),
    ("select v from t where k in (select k2 from u where w = v)",
     "correlated subquery is not supported"),
    ("select v from t where exists (select k2 from u where w > 3)",
     "EXISTS (select ...)"),
    ("select v from t where not exists (select k2 from u)",
     "EXISTS (select ...)"),
    ("select v from t where k = (select max(k2) from u)",
     "scalar subquery (select ...)"),
    ("select v, (select max(k2) from u) m from t",
     "scalar subquery (select ...)"),
])
def test_subqueries_that_are_not_supported_are_refused_by_name(text, says):
    from spark_rapids_tpu.sql.parser import SqlError
    t, u = _in_subquery_tables()
    s = tpu_session()
    s.create_dataframe(t).create_or_replace_temp_view("t")
    s.create_dataframe(u).create_or_replace_temp_view("u")
    with pytest.raises(SqlError, match=says.replace("(", r"\(")
                       .replace(")", r"\)").replace(".", r"\.")):
        s.sql(text)


def test_an_unaliased_aggregate_is_named_as_spark_names_it():
    s = tpu_session()
    s.create_dataframe(pa.table({"k": [1, 1, 2], "v": [1.0, 2.0, 3.0]})) \
        .create_or_replace_temp_view("t")
    got = s.sql("select k, sum(v), max(v) m from t group by k order by k") \
        .collect_arrow()
    assert got.column_names == ["k", "sum(v)", "m"]
