"""Differential tests for sort (ref sort_test.py). Spark ordering semantics:
NaN greatest, nulls first/last per order, -0.0 == 0.0."""
import pytest

from harness import assert_tpu_and_cpu_equal
from data_gen import BoolGen, DoubleGen, IntGen, LongGen, gen_df
from spark_rapids_tpu.api import functions as F


@pytest.mark.parametrize("gen", [IntGen(lo=-100, hi=100), LongGen(),
                                 DoubleGen(with_special=False)],
                         ids=["int", "long", "double"])
@pytest.mark.parametrize("asc", [True, False], ids=["asc", "desc"])
def test_single_key_sort(gen, asc):
    def q(s):
        df = s.create_dataframe(gen_df({"a": gen, "b": IntGen()}))
        o = F.col("a").asc() if asc else F.col("a").desc()
        return df.order_by(o, F.col("b").asc())
    assert_tpu_and_cpu_equal(q, ignore_order=False)


def test_multi_key_mixed_direction():
    def q(s):
        df = s.create_dataframe(gen_df({"a": IntGen(lo=0, hi=10),
                                        "b": IntGen(lo=0, hi=10),
                                        "c": IntGen()}))
        return df.order_by(F.col("a").asc(), F.col("b").desc(),
                           F.col("c").asc())
    assert_tpu_and_cpu_equal(q, ignore_order=False)


@pytest.mark.parametrize("asc,nulls_first", [(True, True), (True, False),
                                             (False, True), (False, False)])
def test_null_ordering(asc, nulls_first):
    def q(s):
        df = s.create_dataframe(gen_df({"a": IntGen(lo=0, hi=20),
                                        "b": IntGen()}))
        o = (F.col("a").asc(nulls_first) if asc
             else F.col("a").desc(nulls_first))
        return df.order_by(o, F.col("b").asc())
    assert_tpu_and_cpu_equal(q, ignore_order=False)


def test_sort_stability_via_tiebreak():
    def q(s):
        df = s.create_dataframe(gen_df({"a": IntGen(lo=0, hi=3),
                                        "b": IntGen()}))
        return df.order_by(F.col("a").asc(), F.col("b").asc())
    assert_tpu_and_cpu_equal(q, ignore_order=False)


def test_sort_int_min_desc():
    import pandas as pd
    import numpy as np

    def q(s):
        df = s.create_dataframe(pd.DataFrame(
            {"a": np.array([np.iinfo(np.int64).min, -1, 0, 5,
                            np.iinfo(np.int64).max], dtype=np.int64)}))
        return df.order_by(F.col("a").desc())
    assert_tpu_and_cpu_equal(q, ignore_order=False)


# ---------------------------------------------------------------------------
# Out-of-core sample sort (ref GpuOutOfCoreSortIterator GpuSortExec.scala:281)
# ---------------------------------------------------------------------------

_OOC_CONF = {"spark.rapids.tpu.sql.batchSizeBytes": 2048}


def test_out_of_core_sort_differential():
    def q(s):
        df = s.create_dataframe(gen_df(
            {"a": IntGen(lo=0, hi=1000), "b": DoubleGen(),
             "c": IntGen()}, n=4096), num_partitions=6)
        return df.order_by(F.col("a").asc(), F.col("b").desc())
    assert_tpu_and_cpu_equal(q, ignore_order=False, conf=_OOC_CONF)


def test_out_of_core_sort_nulls_and_desc():
    def q(s):
        df = s.create_dataframe(gen_df(
            {"a": IntGen(lo=0, hi=50, nullable=True),
             "b": DoubleGen(nullable=True)}, n=2048), num_partitions=4)
        return df.order_by(F.col("a").desc(), F.col("b").asc())
    assert_tpu_and_cpu_equal(q, ignore_order=False, conf=_OOC_CONF)


def test_out_of_core_sort_emits_multiple_sorted_batches():
    import pyarrow as pa
    from harness import tpu_session
    from spark_rapids_tpu.exec.sort import TpuSortExec
    s = tpu_session(_OOC_CONF)
    df = s.create_dataframe(gen_df({"a": IntGen()}, n=8192),
                            num_partitions=4).order_by(F.col("a").asc())
    phys = df._physical()
    assert isinstance(phys, TpuSortExec)
    ctx = s.exec_context()
    batches = list(phys.execute(ctx))
    assert len(batches) > 1, "expected bucketed out-of-core output"
    vals = pa.concat_tables([b.to_arrow() for b in batches])["a"]
    arr = vals.to_pandas()
    assert arr.dropna().is_monotonic_increasing


def test_out_of_core_skewed_keys():
    # heavy duplication: many splitters collapse into few distinct keys
    def q(s):
        df = s.create_dataframe(gen_df(
            {"a": IntGen(lo=0, hi=2), "b": IntGen()}, n=4096),
            num_partitions=4)
        return df.order_by(F.col("a").asc(), F.col("b").asc())
    assert_tpu_and_cpu_equal(q, ignore_order=False, conf=_OOC_CONF)


def _topn_table(parts_seed=3, n=20_000):
    import numpy as np
    import pyarrow as pa
    rng = np.random.default_rng(parts_seed)
    f = rng.integers(0, 50, n) / 7.0
    for value, share in ((np.nan, 0.05), (np.inf, 0.02), (-np.inf, 0.02),
                         (-0.0, 0.02)):
        f[rng.random(n) < share] = value
    return pa.table({
        "f": pa.array(f, mask=rng.random(n) < 0.1),
        "i": pa.array(rng.integers(-3, 3, n), mask=rng.random(n) < 0.1),
        "r": np.arange(n)})


# a float key with NaN, both infinities, -0.0 and NULLs in both directions,
# ties broken by the rows' order (20,000 rows over 300 distinct keys), a
# projection between the limit and the sort, fewer rows than the limit, the
# largest limit taken and the first one that is not
@pytest.mark.parametrize("parts", [1, 5])
@pytest.mark.parametrize("query,top", [
    ("select * from t order by f desc, i limit 10", 10),
    ("select * from t order by f asc nulls last, i desc nulls first limit 37",
     37),
    ("select r, f from t order by i, f desc nulls last limit 128", 128),
    ("select * from t where r < 5 order by f limit 10", 10),
    ("select * from t order by f limit 129", None)])
def test_limit_above_a_sort_selects_its_rows_without_sorting(query, top,
                                                            parts):
    """``LIMIT n`` over ``ORDER BY`` (n <= 128): the sort operator selects
    its first n rows (exec/sort.py:_build_topn_kernel, no ``lax.sort``) and
    hands on what the host engine's stable sort and limit give, row for
    row; above 128 it sorts as before."""
    import pandas as pd
    from harness import OPERATOR_CONF, cpu_session, tpu_session
    got = []
    for make in (tpu_session, cpu_session):
        s = make({**OPERATOR_CONF,
                  "spark.rapids.tpu.sql.batchSizeRows": 8192})
        s.create_dataframe(_topn_table(), num_partitions=parts) \
            .create_or_replace_temp_view("t")
        df = s.sql(query)
        if make is tpu_session:
            plan = df._physical().tree_string()
            assert "Cpu" not in plan, plan
            assert ("; first %s]" % top in plan) == (top is not None), plan
        got.append(df.collect_arrow().to_pandas())
    pd.testing.assert_frame_equal(got[0], got[1], check_dtype=False)


def test_the_selection_kernel_traces_no_sort():
    import jax
    import jax.numpy as jnp
    from spark_rapids_tpu.columnar import ColumnarBatch
    from spark_rapids_tpu.exec.sort import _build_topn_kernel
    from spark_rapids_tpu.plan.logical import SortOrder
    from spark_rapids_tpu.exprs.base import ColumnRef
    b = ColumnarBatch.from_arrow(_topn_table(n=2000))
    orders = [SortOrder(ColumnRef("f"), False, False),
              SortOrder(ColumnRef("i"), True, True)]
    cols = [(c.data, c.validity) for c in b.columns]
    text = str(jax.make_jaxpr(
        _build_topn_kernel(orders, b.schema, 10), static_argnums=2)(
            cols, jnp.int32(b.num_rows), b.padded_len))
    assert " sort[" not in text
    assert "while[" in text or "scan[" in text      # the n rounds, one loop


@pytest.mark.parametrize("strings", ["dictionary", "rectangle", "host"])
@pytest.mark.parametrize("parts", [1, 4])
def test_top_n_with_a_string_payload_stays_on_the_device(strings, parts,
                                                         monkeypatch):
    """``ORDER BY ... LIMIT n`` (n <= 128) with a STRING column that is no
    sort key (PR 35): the selection kernel picks rows by the keys and the
    string column is gathered by the picked rows in the form it has:
    dictionary codes, a byte rectangle, or an Arrow array on the host.
    The rows are the host engine's stable sort's, ties included (the key
    has 40 values over 6,000 rows)."""
    import numpy as np
    import pandas as pd
    import pyarrow as pa
    from harness import OPERATOR_CONF, cpu_session, tpu_session
    from spark_rapids_tpu.columnar import batch as B
    rng = np.random.default_rng(8)
    n = 6000
    card = 30 if strings == "dictionary" else n
    if strings == "host":
        # neither dictionary codes nor a rectangle: an Arrow column
        monkeypatch.setattr(B, "DICT_ENCODE_MAX_FRACTION", 0)
        from spark_rapids_tpu.columnar import strrect
        monkeypatch.setattr(strrect, "encode_string_rect",
                            lambda *a, **k: None)
    t = pa.table({
        "price": pa.array(rng.integers(0, 40, n) / 4.0,
                          mask=rng.random(n) < 0.05),
        "day": pa.array(rng.integers(8000, 8010, n).astype(np.int32)) \
        .cast(pa.date32()),
        "name": pa.array([f"Customer#{i % card:09d}" for i in range(n)],
                         mask=rng.random(n) < 0.05),
        "r": np.arange(n)})
    got = []
    for make in (tpu_session, cpu_session):
        s = make({**OPERATOR_CONF,
                  "spark.rapids.tpu.sql.batchSizeRows": 2048})
        s.create_dataframe(t, num_partitions=parts) \
            .create_or_replace_temp_view("t")
        df = s.sql("select name, r, price, day from t "
                   "order by price desc, day limit 100")
        if make is tpu_session:
            plan = df._physical().tree_string()
            assert "Cpu" not in plan and "!" not in plan, plan
            assert "; first 100]" in plan, plan
        got.append(df.collect_arrow().to_pandas())
    pd.testing.assert_frame_equal(got[0], got[1], check_dtype=False)
