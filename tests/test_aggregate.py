"""Differential tests for hash aggregation
(ref hash_aggregate_test.py)."""
import pytest

from harness import assert_tpu_and_cpu_equal, assert_all_on_tpu
from data_gen import BoolGen, DoubleGen, IntGen, LongGen, gen_df
from spark_rapids_tpu.api import functions as F


def _kv(s, key_gen=None, n=4096, seed=0):
    kg = key_gen or IntGen(lo=0, hi=50)
    return s.create_dataframe(gen_df({"k": kg, "k2": IntGen(lo=0, hi=4),
                                      "v": DoubleGen(with_special=False),
                                      "i": IntGen(lo=-1000, hi=1000)},
                                     n=n, seed=seed))


def test_global_agg():
    def q(s):
        return _kv(s).agg(F.sum(F.col("i")).with_name("s"),
                          F.count(F.col("i")).with_name("c"),
                          F.count_star().with_name("n"),
                          F.min(F.col("i")).with_name("mn"),
                          F.max(F.col("i")).with_name("mx"))
    assert_tpu_and_cpu_equal(q)


def test_global_agg_empty_input():
    def q(s):
        df = _kv(s)
        return df.filter(F.col("i") > 10**9).agg(
            F.sum(F.col("i")).with_name("s"),
            F.count_star().with_name("n"))
    assert_tpu_and_cpu_equal(q)


def test_grouped_sum_count():
    def q(s):
        return (_kv(s).group_by("k")
                .agg(F.sum(F.col("i")).with_name("s"),
                     F.count(F.col("v")).with_name("c"),
                     F.count_star().with_name("n")))
    assert_tpu_and_cpu_equal(q)


def test_grouped_min_max_avg():
    def q(s):
        return (_kv(s).group_by("k")
                .agg(F.min(F.col("i")).with_name("mn"),
                     F.max(F.col("i")).with_name("mx"),
                     F.avg(F.col("v")).with_name("a")))
    assert_tpu_and_cpu_equal(q, approximate_float=True)


def test_multi_key_grouping():
    def q(s):
        return (_kv(s).group_by("k", "k2")
                .agg(F.sum(F.col("i")).with_name("s")))
    assert_tpu_and_cpu_equal(q)


def test_group_by_expression():
    def q(s):
        return (_kv(s).group_by((F.col("k") % 7).alias("m"))
                .agg(F.sum(F.col("i")).with_name("s")))
    assert_tpu_and_cpu_equal(q)


def test_null_keys_form_group():
    def q(s):
        return (_kv(s, key_gen=IntGen(lo=0, hi=3, nullable=True))
                .group_by("k").agg(F.count_star().with_name("n")))
    assert_tpu_and_cpu_equal(q)


def test_sum_all_null_group_is_null():
    def q(s):
        df = _kv(s)
        return (df.with_column("nv", F.lit(None).cast("int"))
                .group_by("k2").agg(F.sum(F.col("nv")).with_name("s"),
                                    F.count(F.col("nv")).with_name("c")))
    assert_tpu_and_cpu_equal(q)


def test_distinct():
    def q(s):
        return _kv(s).select("k2").distinct()
    assert_tpu_and_cpu_equal(q)


def test_first_last():
    # first/last over non-null column with per-group deterministic values
    def q(s):
        df = _kv(s)
        return (df.with_column("kv", F.col("k2") * 10)
                  .group_by("k2")
                  .agg(F.first(F.col("kv")).with_name("f"),
                       F.last(F.col("kv")).with_name("l")))
    assert_tpu_and_cpu_equal(q)


def test_stddev_variance():
    def q(s):
        return (_kv(s).group_by("k2")
                .agg(F.stddev(F.col("v")).with_name("sd"),
                     F.stddev_pop(F.col("v")).with_name("sdp"),
                     F.var_samp(F.col("v")).with_name("vs"),
                     F.var_pop(F.col("v")).with_name("vp")))
    assert_tpu_and_cpu_equal(q, approximate_float=True)


def test_agg_multiple_batches():
    def q(s):
        df = s.create_dataframe(
            gen_df({"k": IntGen(lo=0, hi=20), "v": IntGen()}, n=8192),
            num_partitions=4)
        return df.group_by("k").agg(F.sum(F.col("v")).with_name("s"),
                                    F.count_star().with_name("n"))
    assert_tpu_and_cpu_equal(q)


def test_agg_on_tpu_plan():
    def q(s):
        return _kv(s).group_by("k").agg(F.sum(F.col("i")).with_name("s"))
    assert_all_on_tpu(q)


def test_count_is_never_null():
    def q(s):
        df = _kv(s)
        return (df.filter(F.col("k") < 5).group_by("k")
                .agg(F.count(F.col("v")).with_name("c")))
    assert_tpu_and_cpu_equal(q)


# ---------------------------------------------------------------------------
# Re-partition merge fallback (ref GpuAggregateExec.scala:718-780)
# ---------------------------------------------------------------------------

# the partials of 8 batches of 1,024 rows hold more rows together than one
# 1,024-row bucket: the aggregate finishes in partitions
_REPART_CONF = {"spark.rapids.tpu.sql.batchSizeRows": 1024}


def test_agg_repartition_fallback_differential():
    def q(s):
        df = s.create_dataframe(gen_df(
            {"k": IntGen(lo=0, hi=500), "v": DoubleGen(),
             "w": IntGen()}, n=8192), num_partitions=6)
        return df.group_by("k").agg(
            F.sum(F.col("v")).with_name("s"),
            F.avg(F.col("w")).with_name("a"),
            F.count_star().with_name("n"),
            F.min(F.col("v")).with_name("mn"),
            F.max(F.col("w")).with_name("mx"))
    assert_tpu_and_cpu_equal(q, approximate_float=True, conf=_REPART_CONF)


def test_agg_repartition_emits_disjoint_groups():
    import pyarrow as pa
    from harness import tpu_session
    s = tpu_session(_REPART_CONF)
    df = s.create_dataframe(gen_df(
        {"k": IntGen(lo=0, hi=600, nullable=False), "v": IntGen()},
        n=8192), num_partitions=4)
    out = df.group_by("k").agg(F.count_star().with_name("n"))
    phys = out._physical()
    batches = list(phys.execute(s.exec_context()))
    assert len(batches) > 1, "expected re-partitioned merge output"
    t = pa.concat_tables([b.to_arrow() for b in batches])
    ks = t.column("k").to_pandas()
    assert ks.nunique(dropna=False) == len(ks), "duplicate group across parts"


def test_nan_is_a_value_not_null():
    import pyarrow as pa
    from harness import tpu_session
    """Spark semantics: sum/avg/max PROPAGATE NaN, min ignores it (NaN is
    greatest), count counts it — while SQL NULL is skipped by all. Both
    engines must agree (the host oracle evaluates from Arrow, where null
    and NaN stay distinct)."""
    import math
    t = pa.table({"k": ["a", "a", "a", "b"],
                  "v": [1.0, float("nan"), None, 2.0]})
    for enabled in (True, False):
        s = tpu_session({"spark.rapids.tpu.sql.enabled": enabled})
        s.create_dataframe(t).create_or_replace_temp_view("t")
        got = s.sql("""SELECT k, sum(v) s, min(v) mn, max(v) mx, count(v) c
                       FROM t GROUP BY k ORDER BY k""").collect()
        a = got[0]
        assert math.isnan(a["s"]) and math.isnan(a["mx"]), (enabled, a)
        assert a["mn"] == 1.0 and a["c"] == 2, (enabled, a)
        assert got[1] == {"k": "b", "s": 2.0, "mn": 2.0, "mx": 2.0, "c": 1}


# ---------------------------------------------------------------------------
# Multi-batch first pass: the direct-addressed carry, or the sort kernels
# + one stacked count fetch a window (r4: per-batch int(num_groups) cost a
# device round trip each)
# ---------------------------------------------------------------------------

def test_agg_multibatch_string_keys_direct():
    """All-dict keys, small cardinality product -> the carried direct path."""
    from data_gen import StringGen

    def q(s):
        df = s.create_dataframe(gen_df(
            {"k": StringGen(alphabet="abcd", max_len=3),
             "k2": IntGen(lo=0, hi=3),  # mixed: string + int key
             "v": DoubleGen(with_special=False)}, n=8192),
            num_partitions=5)
        return df.group_by("k").agg(
            F.sum(F.col("v")).with_name("s"),
            F.count_star().with_name("n"),
            F.min(F.col("v")).with_name("mn"),
            F.avg(F.col("v")).with_name("a"))
    assert_tpu_and_cpu_equal(q, approximate_float=True)


def test_agg_multibatch_two_string_keys_with_nulls():
    from data_gen import StringGen

    def q(s):
        df = s.create_dataframe(gen_df(
            {"k": StringGen(alphabet="ab", max_len=2, nullable=0.2),
             "j": StringGen(alphabet="xy", max_len=2, nullable=0.2),
             "v": IntGen()}, n=8192), num_partitions=4)
        return df.group_by("k", "j").agg(
            F.sum(F.col("v")).with_name("s"),
            F.count(F.col("v")).with_name("c"))
    assert_tpu_and_cpu_equal(q)


# ---------------------------------------------------------------------------
# The carried first pass (ISSUE 26): on the direct-addressed path every
# batch folds into ONE running partial on the device — one dispatch a
# batch, one tail dispatch and one fetch a query
# ---------------------------------------------------------------------------

_CARRY_CONF = {"spark.rapids.tpu.sql.optimizer.enabled": False,
               "spark.rapids.tpu.sql.fusedPipeline.enabled": False,
               "spark.rapids.tpu.distributed.enabled": False}


def _letters(rng, n, alphabet, null_share=0.0):
    import numpy as np
    import pyarrow as pa
    vals = np.asarray(alphabet, dtype=object)[rng.integers(0, len(alphabet),
                                                           n)]
    mask = rng.random(n) < null_share
    return pa.array(vals, type=pa.string(), mask=mask)


def _carry_case(name):
    """(partitions, aggregates, conf, (batches, flushes) expected of the
    ``agg.carry`` counter) of one case of test_agg_carried_first_pass."""
    import numpy as np
    import pyarrow as pa
    rng = np.random.default_rng(26)
    sums = [F.sum(F.col("v")).with_name("s"),
            F.count(F.col("v")).with_name("c"),
            F.count_star().with_name("n"),
            F.avg(F.col("v")).with_name("a"),
            F.min(F.col("i")).with_name("mn"),
            F.max(F.col("i")).with_name("mx")]

    def part(n, keys, j=("x", "y"), null_keys=0.0, null_vals=0.0):
        return pa.table({
            "k": _letters(rng, n, keys, null_keys),
            "j": _letters(rng, n, j, null_keys),
            "v": pa.array(rng.normal(size=n), mask=rng.random(n) < null_vals),
            "i": pa.array(rng.integers(-1000, 1000, n),
                          mask=rng.random(n) < null_vals)})

    conf = dict(_CARRY_CONF)
    if name == "null_keys_null_values":
        # one key is all NULL in the first batch: its NULL slot moves
        # when the first value arrives
        parts = [part(700, ["a", "b"], null_keys=0.2, null_vals=0.3)
                 for _ in range(4)]
        parts[0] = parts[0].set_column(
            1, "j", pa.nulls(700, type=pa.string()))
        return parts, sums, conf, (4, 0)
    if name == "new_key_in_last_batch":
        parts = [part(500, ["a", "b"], null_keys=0.1) for _ in range(3)]
        parts.append(part(500, ["a", "b", "zz"], j=("x", "y", "w")))
        return parts, sums, conf, (4, 0)
    if name == "dictionary_crosses_bucket":
        # 3 x 3 slots fit the 16 bucket; 27 letters take the 256 bucket
        wide = [chr(ord("a") + c) for c in range(26)] + ["zz"]
        parts = [part(600, ["a", "b"]), part(600, ["a", "b"], null_keys=0.1),
                 part(600, wide, null_keys=0.1), part(600, wide)]
        return parts, sums, conf, (4, 0)
    if name == "empty_batch_in_middle":
        parts = [part(400, ["a", "b", "c"], null_vals=0.2) for _ in range(4)]
        parts.insert(2, parts[0].slice(0, 0))
        return parts, sums, conf, (5, 0)
    if name == "first_last_mix":
        # First/Last carry their GLOBAL row positions
        parts = [part(500, ["a", "b", "c"], null_vals=0.2) for _ in range(5)]
        aggs = sums[:3] + [F.first(F.col("i")).with_name("f"),
                           F.last(F.col("i")).with_name("l")]
        return parts, aggs, conf, (5, 0)
    if name == "decimal_limb_sums":
        # a decimal SUM's three limbs merge (and re-normalise) in the carry
        import decimal
        parts = []
        for _ in range(4):
            t = part(500, ["a", "b", "c"], null_keys=0.1)
            cents = rng.integers(-10**12, 10**12, 500)
            parts.append(t.append_column("d", pa.array(
                [decimal.Decimal(int(c)) / 100 for c in cents],
                type=pa.decimal128(15, 2), mask=rng.random(500) < 0.1)))
        aggs = sums[:3] + [F.sum(F.col("d")).with_name("ds"),
                           F.min(F.col("d")).with_name("dm")]
        return parts, aggs, conf, (4, 0)
    if name == "leaves_direct_path_midway":
        # the slot product passes optimisticGroups in the third batch:
        # the carry is flushed as one partial, the sort kernels go on
        wide = [chr(ord("a") + c) for c in range(26)]
        parts = [part(500, ["a", "b"]), part(500, ["a", "b"]),
                 part(500, wide, null_keys=0.1), part(500, wide)]
        conf["spark.rapids.tpu.sql.agg.optimisticGroups"] = 16
        return parts, sums[:3] + [F.first(F.col("i")).with_name("f")], \
            conf, (2, 1)
    raise AssertionError(name)


@pytest.mark.parametrize("name", [
    "null_keys_null_values", "new_key_in_last_batch",
    "dictionary_crosses_bucket", "empty_batch_in_middle", "first_last_mix",
    "decimal_limb_sums", "leaves_direct_path_midway"])
def test_agg_carried_first_pass(name):
    """The carried path equals the CPU engine AND the same rows as ONE
    batch, and says through ``agg.carry`` how many batches it folded."""
    import pyarrow as pa
    from harness import (_assert_frames_equal, _canon, cpu_session,
                         tpu_session)
    from spark_rapids_tpu.api.dataframe import DataFrame
    from spark_rapids_tpu.plan import logical as L
    from spark_rapids_tpu.trace import Tracer, install_tracer
    from spark_rapids_tpu.types import Schema, from_arrow
    parts, aggs, conf, expected = _carry_case(name)
    schema = Schema.of(**{f.name: from_arrow(f.type)
                          for f in parts[0].schema})

    def q(s, tables):
        return DataFrame(s, L.LogicalScan(tables, schema)) \
            .group_by("k", "j").agg(*aggs)

    tr = install_tracer(Tracer())
    try:
        carried = q(tpu_session(conf), parts).to_pandas()
    finally:
        install_tracer(None)
    counts = [e["args"] for e in tr.snapshot()
              if e["ph"] == "C" and e["name"] == "agg.carry"]
    assert counts == [{"batches": expected[0], "flushes": expected[1]}]
    one = q(tpu_session(conf), [pa.concat_tables(parts)]).to_pandas()
    cpu = q(cpu_session(conf), parts).to_pandas()
    _assert_frames_equal(_canon(carried, True), _canon(cpu, True), True)
    _assert_frames_equal(_canon(carried, True), _canon(one, True), True)


def test_agg_multibatch_speculation_overflow_redo():
    """Per-batch group count far above the 1024-row speculative slice:
    the stacked-count validation must re-run the overflowed batches at
    their true bucket (not silently truncate groups)."""
    def q(s):
        df = s.create_dataframe(gen_df(
            {"k": IntGen(lo=0, hi=5000, nullable=False),
             "v": IntGen()}, n=20000), num_partitions=3)
        return df.group_by("k").agg(F.sum(F.col("v")).with_name("s"),
                                    F.count_star().with_name("n"))
    assert_tpu_and_cpu_equal(q)


def test_agg_multibatch_global_no_fetch():
    def q(s):
        df = s.create_dataframe(gen_df(
            {"v": DoubleGen(with_special=False), "i": IntGen()}, n=8192),
            num_partitions=6)
        return df.agg(F.sum(F.col("v")).with_name("s"),
                      F.count_star().with_name("n"),
                      F.max(F.col("i")).with_name("mx"))
    assert_tpu_and_cpu_equal(q, approximate_float=True)


def test_agg_multibatch_string_keys_high_cardinality_sort_path():
    """Cardinality product above OPTIMISTIC_GROUPS -> the sort-based
    update kernel still carries the multi-batch path."""
    from data_gen import StringGen

    def q(s):
        df = s.create_dataframe(gen_df(
            {"k": StringGen(alphabet="abcdefgh", max_len=8),
             "v": IntGen()}, n=12000), num_partitions=3)
        return df.group_by("k").agg(F.sum(F.col("v")).with_name("s"),
                                    F.count_star().with_name("n"))
    assert_tpu_and_cpu_equal(q)


def _double_keys_table():
    """6,000 double keys four rows each, among them the ones a hash must
    not tell apart (0.0 and -0.0, NaNs) and the infinities. No NULLs: the
    host oracle groups them with NaN (the string keys below hold some)."""
    import numpy as np
    import pyarrow as pa
    rng = np.random.default_rng(5)
    k = np.repeat(rng.normal(0, 1e6, 6000), 4)
    k[:40] = np.tile([0.0, -0.0, np.nan, -np.nan, np.inf, -np.inf, 1e300,
                      -1e-300], 5)
    rng.shuffle(k)
    return pa.table({"k": k, "v": rng.integers(-50, 50, k.size),
                     "w": rng.normal(0, 1, k.size)})


def _string_keys_table():
    """12,000 string keys two rows each, every batch's keys distinct (a
    byte rectangle at that cardinality) and found again three batches on,
    the later keys longer: the batches' rectangles differ in width."""
    import numpy as np
    import pyarrow as pa
    rng = np.random.default_rng(6)
    ids = np.tile(np.arange(12_000), 2)
    k = np.asarray([f"key-{i:05d}" + "x" * (i // 2000 * 4) for i in ids],
                   dtype=object)
    k[rng.random(k.size) < 0.01] = None
    return pa.table({"k": pa.array(k), "v": rng.integers(-50, 50, k.size),
                     "w": rng.normal(0, 1, k.size)})


@pytest.mark.parametrize("table,conf,rect", [
    # a DOUBLE key: the device cannot hash it as Spark does, and need not
    (_double_keys_table,
     {"spark.rapids.tpu.sql.batchSizeRows": 2048}, False),
    # a string key held as a byte rectangle, its width a batch's own
    (_string_keys_table,
     {"spark.rapids.tpu.sql.batchSizeRows": 2048}, True),
    # few rows but wide: under batchSizeRows, over batchSizeBytes together
    (_double_keys_table,
     {"spark.rapids.tpu.sql.batchSizeBytes": 1 << 16}, False),
], ids=["double_key", "rectangle_key", "byte_cap"])
def test_partitioned_finish_takes_every_key_and_the_byte_cap(
        table, conf, rect, monkeypatch):
    """Partials that pass the cap finish in partitions whatever their key
    (at the parent of PR 35 a DOUBLE or a string rectangle kept a
    bounded tree of merges that ended in one oversized kernel), and the
    cap is ``batchSizeBytes``' where the rows are wide: the host engine's
    answer, and no merge kernel above the cap's bucket."""
    from spark_rapids_tpu.columnar.bucketing import bucket_for
    from spark_rapids_tpu.exec.aggregate import TpuHashAggregateExec
    t = table()
    seen = {"caps": [], "merged_at": [], "rect": []}
    real_cap = TpuHashAggregateExec._merge_cap
    real_run = TpuHashAggregateExec._run_kernel
    real_fin = TpuHashAggregateExec._repartitioned_merge

    def cap(self, ctx, partials):
        seen["caps"].append(real_cap(self, ctx, partials))
        return seen["caps"][-1]

    def run(self, kernel, batch, *a, **kw):
        seen["merged_at"].append(batch.padded_len)
        return real_run(self, kernel, batch, *a, **kw)

    def finish(self, ctx, partials, *a, **kw):
        seen["rect"].append(self._rect_mode)
        if self._rect_mode:
            # (the first division's: a second one takes collected pieces)
            seen.setdefault(
                "widths", {sb.get().columns[0].width for sb in partials})
        return real_fin(self, ctx, partials, *a, **kw)
    monkeypatch.setattr(TpuHashAggregateExec, "_merge_cap", cap)
    monkeypatch.setattr(TpuHashAggregateExec, "_run_kernel", run)
    monkeypatch.setattr(TpuHashAggregateExec, "_repartitioned_merge", finish)

    def q(s):
        df = s.create_dataframe(t, num_partitions=6)
        return df.group_by("k").agg(
            F.sum(F.col("v")).with_name("s"),
            F.count_star().with_name("n"),
            F.min(F.col("w")).with_name("mn"),
            F.max(F.col("w")).with_name("mx"))
    got = assert_tpu_and_cpu_equal(q, approximate_float=True, conf=conf)
    assert seen["rect"] and all(r == rect for r in seen["rect"]), seen
    assert not rect or len(seen["widths"]) > 1, seen
    assert len(got) > max(seen["caps"])
    assert max(seen["merged_at"]) <= bucket_for(max(seen["caps"])), seen
    if "spark.rapids.tpu.sql.batchSizeBytes" in conf:
        # the byte rule it was: fewer rows than batchSizeRows allows
        assert max(seen["caps"]) < 1 << 16, seen


# ---------------------------------------------------------------------------
# The partitioned finish of a high-cardinality aggregate (PR 35)
# ---------------------------------------------------------------------------

def _disjoint_keys_table(n=40_000, nulls=True):
    """Keys that come in runs of four (an order's lines): every partial
    of a scan in row order holds other keys than its neighbours, so
    merging them reduces nothing."""
    import numpy as np
    import pyarrow as pa
    rng = np.random.default_rng(11)
    k = np.repeat(np.arange(n // 4, dtype=np.int64), 4)[:n] * 7 + 3
    return pa.table({
        "k": pa.array(k, mask=rng.random(n) < 0.002 if nulls else None),
        "v": rng.integers(1, 51, n).astype(np.float64),
        "w": rng.integers(0, 1000, n).astype(np.int32)})


@pytest.mark.parametrize("cap,parts,rows,most", [
    (2048, 24, 40_000, 64),    # 64 hash buckets packed into 5 or 6
    (1024, 40, 40_000, 64),    # ... into a dozen or so
    (2048, 24, 40_000, 4),     # four buckets, each over the cap: every
                               # one divided again
])
def test_partitioned_finish_of_partials_that_pass_the_cap(cap, parts, rows,
                                                          most,
                                                          monkeypatch):
    """20+ partials of disjoint keys whose rows pass ``batchSizeRows``
    together: the answer is pandas', every key leaves in exactly one
    partition's output, the outputs add up to the whole, no merge kernel
    is built above the cap's bucket, and the counter says what ran."""
    import pandas as pd
    import pyarrow as pa
    from harness import OPERATOR_CONF, tpu_session
    from spark_rapids_tpu import trace
    from spark_rapids_tpu.columnar.bucketing import bucket_for
    from spark_rapids_tpu.exec import aggregate
    from spark_rapids_tpu.exec.aggregate import TpuHashAggregateExec
    monkeypatch.setattr(aggregate, "_HASH_BUCKETS", most)
    t = _disjoint_keys_table(rows)
    s = tpu_session({**OPERATOR_CONF,
                     "spark.rapids.tpu.sql.batchSizeRows": cap})
    s.create_dataframe(t, num_partitions=parts) \
        .create_or_replace_temp_view("t")
    merged_at = []
    real = TpuHashAggregateExec._run_kernel

    def watched(self, kernel, batch, *a, **kw):
        merged_at.append(batch.padded_len)
        return real(self, kernel, batch, *a, **kw)
    monkeypatch.setattr(TpuHashAggregateExec, "_run_kernel", watched)
    df = s.sql("select k, sum(v) s, count(*) c, max(w) m from t group by k")
    tracer = trace.Tracer(max_events=1 << 16, proc_name="t")
    trace.install_tracer(tracer)
    try:
        phys = df._physical()
        batches = [b.to_arrow() for b in phys.execute(s.exec_context())]
    finally:
        trace.install_tracer(None)
    events, _ = tracer.export_events()
    # one output a partition, keys disjoint across them
    assert len(batches) > 1
    got = pa.concat_tables(batches).to_pandas()
    assert got.k.nunique(dropna=False) == len(got)
    want = t.to_pandas().groupby("k", dropna=False).agg(
        s=("v", "sum"), c=("v", "size"), m=("w", "max")).reset_index()
    pd.testing.assert_frame_equal(
        got.sort_values("k").reset_index(drop=True).astype(
            {"c": "int64", "m": "int64"}),
        want.sort_values("k").reset_index(drop=True).astype(
            {"m": "int64"}), check_dtype=False)
    # no merge kernel above the bucket the cap has
    assert merged_at and max(merged_at) <= bucket_for(cap), merged_at
    [count] = [e["args"] for e in events
               if e.get("ph") == "C" and e["name"] == "agg.highcard"]
    merges = [e for e in events
              if e.get("ph") == "X" and e["name"] == "agg.merge_part"]
    assert count["partials"] >= 20 and count["rows_in"] == rows
    assert count["groups"] == len(want)
    assert count["partitions"] == len(merges) == len(batches)
    assert -(-len(want) // cap) <= count["partitions"] \
        <= 2 * -(-len(want) // cap) + most
    assert 0 < count["largest_partition_rows"] <= cap
    divisions = [e for e in events
                 if e.get("ph") == "X" and e["name"] == "agg.partition"]
    # partitions are packed from the buckets' counts, so none comes out
    # over the cap unless ONE bucket does: only then a second division
    assert len(divisions) == (1 + most if len(want) > most * cap else 1)
    assert all(e["args"]["cols"] == ["k", "v", "w"]
               for e in merges + divisions)


def test_partials_that_fit_one_bucket_merge_as_before():
    """Many partials of the SAME few keys: their rows fit the cap
    together, one merge kernel, one output, no partitions."""
    from harness import OPERATOR_CONF, tpu_session
    from spark_rapids_tpu import trace
    s = tpu_session({**OPERATOR_CONF,
                     "spark.rapids.tpu.sql.batchSizeRows": 1024})
    df = s.create_dataframe(gen_df(
        {"k": IntGen(lo=0, hi=20, nullable=False), "v": IntGen()}, n=8192),
        num_partitions=8).group_by("k").agg(F.count_star().with_name("n"))
    tracer = trace.Tracer(max_events=1 << 14, proc_name="t")
    trace.install_tracer(tracer)
    try:
        batches = list(df._physical().execute(s.exec_context()))
    finally:
        trace.install_tracer(None)
    events, _ = tracer.export_events()
    assert len(batches) == 1 and batches[0].num_rows == 20
    assert not [e for e in events if e["name"].startswith("agg.highcard")
                or e["name"] == "agg.merge_part"]


def test_agg_multibatch_first_last_order_dependent():
    """First/Last through the multi-batch SPLIT kernel: the original-row-
    index payload must ride the sort (needs_rank) and per-batch firsts
    must merge by position correctly."""
    def q(s):
        df = s.create_dataframe(gen_df(
            {"k": IntGen(lo=0, hi=6, nullable=False),
             "v": IntGen(nullable=False)}, n=9000), num_partitions=3)
        # per-group deterministic target: first/last of a value equal to
        # the row's position makes order bugs visible
        return df.group_by("k").agg(F.first(F.col("v")).with_name("f"),
                                    F.last(F.col("v")).with_name("l"),
                                    F.count_star().with_name("n"))
    assert_tpu_and_cpu_equal(q)


def test_agg_multibatch_decimal_key_payload_fallback():
    """Decimal group keys don't fit the reconstruct-from-operands fast
    path — the split kernel must fall back to carrying key payloads."""
    import pyarrow as pa
    from harness import assert_tpu_and_cpu_equal as chk
    import decimal
    rows = [decimal.Decimal(f"{i % 5}.25") for i in range(6000)]
    vals = list(range(6000))
    t = pa.table({"d": pa.array(rows, type=pa.decimal128(9, 2)),
                  "v": pa.array(vals, type=pa.int64())})

    def q(s):
        return (s.create_dataframe(t, num_partitions=3)
                .group_by("d").agg(F.sum(F.col("v")).with_name("s"),
                                   F.count_star().with_name("n")))
    chk(q)


def test_wide_batch_auto_ceiling_byte_gated():
    """ADVICE r5: AGG_WIDE_BATCH_ROWS=0 (auto) widens a GLOBAL agg's
    scan only while the estimated batch bytes fit half the HBM budget —
    a tiny pinned budget must keep the scan at its default width, an
    ample one still fuses the whole partition into one batch."""
    import numpy as np
    import pyarrow as pa
    from harness import tpu_session
    from spark_rapids_tpu.exec.basic import InMemoryScanExec

    n = 1 << 21                      # 2M rows x (8 B f64 + 1 B validity)
    t = pa.table({"v": pa.array(np.zeros(n))})

    def scans_of(session):
        df = session.create_dataframe(t).agg(
            F.sum(F.col("v")).with_name("sv"))
        out = []

        def walk(node):
            if isinstance(node, InMemoryScanExec):
                out.append(node)
            for c in node.children:
                walk(c)
        walk(df._physical())
        return out

    tiny = 9 * (1 << 19) * 2         # row cap (budget/2)/9 = 2**19 < n
    capped = scans_of(tpu_session(
        {"spark.rapids.tpu.memory.hbm.limitBytes": tiny}))
    assert capped and all(s.batch_rows < n for s in capped), \
        [s.batch_rows for s in capped]

    wide = scans_of(tpu_session())   # derived budget: plenty for 18 MB
    assert any(s.batch_rows >= n for s in wide), \
        [s.batch_rows for s in wide]


@pytest.mark.parametrize("n_keys", [1, 3, 9])
def test_sorted_groupby_with_fused_filter_and_null_keys(n_keys):
    """The sort-based group-by over several batches with its filter fused
    in: the keys' null ranks and the dropped rows' bit share ONE flag
    operand (exec/aggregate.py:k_prep), and a dropped row must sort behind
    every live one whatever its keys' nullness: the groups are pandas'."""
    import numpy as np
    import pandas as pd
    import pyarrow as pa
    from harness import OPERATOR_CONF, tpu_session
    rng = np.random.default_rng(n_keys)
    n = 30_000
    cols = {f"k{i}": pa.array(rng.integers(0, 4, n).astype(
        np.int64 if i % 2 else np.int32), mask=rng.random(n) < 0.25)
        for i in range(n_keys)}
    cols["v"] = pa.array(rng.integers(-1000, 1000, n))
    t = pa.table(cols)
    s = tpu_session({**OPERATOR_CONF,
                     "spark.rapids.tpu.sql.batchSizeRows": 8192})
    s.create_dataframe(t, num_partitions=4).create_or_replace_temp_view("t")
    keys = ", ".join(list(cols)[:n_keys])
    df = s.sql(f"select {keys}, sum(v) as sv, count(*) as c from t "
               f"where v > -500 group by {keys}")
    plan = df._physical().tree_string()
    assert "HashAggregate" in plan and "fused=[filter]" in plan \
        and "Cpu" not in plan, plan
    got = df.collect_arrow().to_pandas()
    pdf = t.to_pandas()
    ks = list(cols)[:n_keys]
    want = pdf[pdf.v > -500].groupby(ks, dropna=False).agg(
        sv=("v", "sum"), c=("v", "size")).reset_index()
    got = got.sort_values(ks, na_position="first").reset_index(drop=True)
    want = want.sort_values(ks, na_position="first").reset_index(drop=True)
    assert len(got) == len(want)
    assert (got.sv.to_numpy() == want.sv.to_numpy()).all()
    assert (got.c.to_numpy() == want.c.to_numpy()).all()
    for k in ks:
        assert (got[k].isna().to_numpy() == want[k].isna().to_numpy()).all()
