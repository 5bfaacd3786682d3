"""Decimal device support (ref DecimalUtils JNI 128-bit ops, SURVEY 2.12):
scaled-int64 device lanes for p<=38 with loud ingest overflow, Spark's
result type per operator, operands brought to one scale, HALF_UP, exact
limb-based SUM / AVG accumulation, TPC-H Q1 and Q6 whole against the
benchmark's integer reference, and an overflow that is never a wrapped
number."""
import decimal
import os
import re
import sys

import numpy as np
import pyarrow as pa
import pytest

from harness import assert_tpu_and_cpu_equal, cpu_session, tpu_session
from spark_rapids_tpu.api import functions as F
from spark_rapids_tpu.exprs.decimal_rules import DecimalOverflow

PERFBENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "perfbench")
#: small plans stay on the device (the cost optimizer would send them to
#: the host engine, which the differential half of each test runs anyway)
DEVICE = {"spark.rapids.tpu.sql.optimizer.enabled": False}


def _dec(x, scale=2):
    return decimal.Decimal(x).scaleb(-scale)


def _table(n=4000, seed=0, prec=15, scale=2, null_frac=0.1):
    rng = np.random.RandomState(seed)
    vals = [None if rng.rand() < null_frac
            else decimal.Decimal(int(rng.randint(-10**13, 10**13)))
            .scaleb(-scale) for _ in range(n)]
    return pa.table({"k": pa.array(rng.randint(0, 7, n)),
                     "d": pa.array(vals, pa.decimal128(prec, scale))})


def test_decimal_sum_grouped_exact():
    t = _table()

    def q(s):
        return s.create_dataframe(t).group_by("k").agg(
            F.sum(F.col("d")).with_name("sd"),
            F.count(F.col("d")).with_name("c"),
            F.min(F.col("d")).with_name("mn"),
            F.max(F.col("d")).with_name("mx"))
    got = {r["k"]: r for r in q(tpu_session()).collect()}
    exp = {}
    for k, v in zip(t.column("k").to_pylist(), t.column("d").to_pylist()):
        e = exp.setdefault(k, {"sd": decimal.Decimal(0), "c": 0,
                               "mn": None, "mx": None})
        if v is None:
            continue
        e["sd"] += v
        e["c"] += 1
        e["mn"] = v if e["mn"] is None else min(e["mn"], v)
        e["mx"] = v if e["mx"] is None else max(e["mx"], v)
    for k, e in exp.items():
        assert got[k]["sd"] == e["sd"]        # bit-exact, no float detour
        assert got[k]["c"] == e["c"]
        assert got[k]["mn"] == e["mn"]
        assert got[k]["mx"] == e["mx"]


def test_decimal_sum_output_type_widens():
    t = _table(n=100)
    s = tpu_session()
    out = s.create_dataframe(t).agg(F.sum(F.col("d")).with_name("sd")) \
        .collect_arrow()
    # Spark: sum(decimal(15,2)) -> decimal(25,2)
    assert out.schema.field("sd").type == pa.decimal128(25, 2)


def test_decimal_wide_precision_device():
    """decimal(38,2) columns are device-backed as long as values fit the
    64-bit unscaled lane."""
    t = _table(prec=38)

    def q(s):
        return s.create_dataframe(t).group_by("k").agg(
            F.sum(F.col("d")).with_name("sd"))
    assert_tpu_and_cpu_equal(q)
    s = tpu_session()
    tree = q(s)._physical().tree_string()
    assert "CpuAggregate" not in tree, tree


def test_decimal_overflowing_sum_is_null():
    """A total past 2^63 that Spark's decimal(38,2) would hold: the
    engine's loud error (the lane itself is NULL), never a number."""
    big = [_dec(9 * 10**16)] * 300        # total ~2.7e19 > int64 range
    t = pa.table({"d": pa.array(big, pa.decimal128(38, 2))})
    s = tpu_session(DEVICE)
    df = s.create_dataframe(t).agg(F.sum(F.col("d")).with_name("sd"))
    with pytest.raises(DecimalOverflow, match="64-bit unscaled"):
        df.collect()
    # the host twin holds Spark's number
    out = cpu_session().create_dataframe(t).agg(
        F.sum(F.col("d")).with_name("sd")).collect()
    assert out == [{"sd": _dec(27 * 10**18)}]


def test_decimal_ingest_overflow_is_loud():
    huge = [decimal.Decimal(2**63).scaleb(-2)]
    t = pa.table({"d": pa.array(huge, pa.decimal128(38, 2))})
    s = tpu_session()
    with pytest.raises(Exception, match="64-bit unscaled"):
        s.create_dataframe(t).select(F.col("d")).collect()


def test_decimal_tpch_q1_differential():
    """TPC-H Q1's real expressions over DECIMAL money columns, bit-exact
    between the engines (VERDICT r1 #6 'done' criterion at test scale):
    sums of bare columns, of products of three decimals with integer
    literals, averages, a count."""
    rng = np.random.RandomState(42)
    n = 20000

    def money(lo, hi):
        return pa.array([decimal.Decimal(int(x)).scaleb(-2)
                         for x in rng.randint(lo, hi, n)],
                        pa.decimal128(15, 2))
    t = pa.table({
        "rf": pa.array(rng.choice(["A", "N", "R"], n)),
        "ls": pa.array(rng.choice(["O", "F"], n)),
        "qty": money(100, 5100), "price": money(90000, 10500000),
        "disc": money(0, 11), "tax": money(0, 9),
    })

    def q(s):
        price, disc, tax = F.col("price"), F.col("disc"), F.col("tax")
        return (s.create_dataframe(t, num_partitions=3)
                .group_by("rf", "ls")
                .agg(F.sum(F.col("qty")).with_name("sum_qty"),
                     F.sum(price).with_name("sum_price"),
                     F.sum(price * (1 - disc)).with_name("sum_disc_price"),
                     F.sum(price * (1 - disc) * (1 + tax))
                     .with_name("sum_charge"),
                     F.avg(F.col("qty")).with_name("avg_qty"),
                     F.avg(disc).with_name("avg_disc"),
                     F.count_star().with_name("n")))
    assert_tpu_and_cpu_equal(q, conf=DEVICE)
    got = q(tpu_session(DEVICE)).collect_arrow()
    assert [str(f.type) for f in got.schema][2:] == [
        "decimal128(25, 2)", "decimal128(25, 2)", "decimal128(38, 4)",
        "decimal128(38, 6)", "decimal128(19, 6)", "decimal128(19, 6)",
        "int64"]


# ---------------------------------------------------------------------------
# result type and value per operator and operand pair, against
# decimal.Decimal with Spark's rules (Catalyst DecimalPrecision) written here
# ---------------------------------------------------------------------------

def _adjust(p, s):
    return (p, s) if p <= 38 else (38, max(38 - (p - s), min(s, 6)))


def _t_add(a, b):
    s = max(a[1], b[1])
    return _adjust(max(a[0] - a[1], b[0] - b[1]) + s + 1, s)


def _t_mul(a, b):
    return _adjust(a[0] + b[0] + 1, a[1] + b[1])


def _t_div(a, b):
    s = max(6, a[1] + b[0] + 1)
    return _adjust(a[0] - a[1] + b[1] + s, s)


A, B, C, INT = (15, 2), (15, 2), (10, 4), (10, 0)
_D = decimal.Decimal
_OPS_TABLE = pa.table({
    # negative values, NULLs, ties for HALF_UP at scale 2 in c (x.xx50)
    "a": pa.array([_D("33575.69"), _D("-100.10"), None, _D("0.05"),
                   _D("-0.07"), _D("24.00"), _D("9999999999999.99")],
                  pa.decimal128(*A)),
    "b": pa.array([_D("0.05"), _D("0.07"), _D("1.00"), None,
                   _D("-2.50"), _D("24.00"), _D("-0.01")],
                  pa.decimal128(*B)),
    "c": pa.array([_D("0.1250"), _D("-0.1250"), _D("3.0000"), _D("7.7777"),
                   None, _D("24.0000"), _D("-0.0050")],
                  pa.decimal128(*C)),
    "i": pa.array([3, -4, 0, None, 7, 24, 1], pa.int32()),
})


def _half_up(x, scale):
    with decimal.localcontext() as ctx:
        ctx.prec = 80
        return x.quantize(_D(1).scaleb(-scale),
                          rounding=decimal.ROUND_HALF_UP)


# (sql, Spark's result type or "bool", value from a row's Decimals)
_OPERATOR_CASES = [
    ("a + b", _t_add(A, B), lambda r: r["a"] + r["b"]),
    ("a - c", _t_add(A, C), lambda r: r["a"] - r["c"]),
    ("c - a", _t_add(C, A), lambda r: r["c"] - r["a"]),
    ("a * b", _t_mul(A, B), lambda r: r["a"] * r["b"]),
    ("a * c", _t_mul(A, C), lambda r: r["a"] * r["c"]),
    # a quotient at Spark's scale (13, 18) soon leaves a 64-bit lane: the
    # host computes it on Python ints (every row), and a device plan can
    # take the host's column back only where it fits (_DEVICE_ROWS)
    ("a / c", _t_div(A, C), lambda r: r["a"] / r["c"]),
    ("b / a", _t_div(B, A), lambda r: r["b"] / r["a"]),
    ("1 - b", _t_add((1, 0), B), lambda r: 1 - r["b"]),
    ("a * 2", _t_mul(A, (1, 0)), lambda r: r["a"] * 2),
    ("a * 0.05", _t_mul(A, (2, 2)), lambda r: r["a"] * _D("0.05")),
    ("a + 0.5", _t_add(A, (1, 1)), lambda r: r["a"] + _D("0.5")),
    ("a + i", _t_add(A, INT), lambda r: r["a"] + r["i"]),
    ("c * i", _t_mul(C, INT), lambda r: r["c"] * r["i"]),
    ("a * (1 - b) * (1 + b)", _t_mul(_t_mul(A, _t_add((1, 0), B)),
                                     _t_add((1, 0), B)),
     lambda r: r["a"] * (1 - r["b"]) * (1 + r["b"])),
    ("cast(c as decimal(10,2))", (10, 2), lambda r: r["c"]),   # the ties
    ("cast(a as decimal(20,4))", (20, 4), lambda r: r["a"]),
    ("cast(i as decimal(12,2))", (12, 2), lambda r: _D(r["i"])),
    ("a < c", "bool", lambda r: r["a"] < r["c"]),
    ("a = b", "bool", lambda r: r["a"] == r["b"]),
    ("a >= 24", "bool", lambda r: r["a"] >= 24),
    ("c <> i", "bool", lambda r: r["c"] != r["i"]),
    ("b between 0.05 and 0.07", "bool",
     lambda r: _D("0.05") <= r["b"] <= _D("0.07")),
    ("a > 0.049", "bool", lambda r: r["a"] > _D("0.049")),
]


@pytest.mark.parametrize("sql,want_type,value",
                         _OPERATOR_CASES, ids=[c[0] for c in _OPERATOR_CASES])
def test_decimal_operator_type_and_value(sql, want_type, value):
    rows = _OPERATOR_TABLE_ROWS
    expected = []
    for r in rows:
        if any(r[k] is None for k in re.findall(r"\b[abci]\b", sql)):
            expected.append(None)
            continue
        with decimal.localcontext() as ctx:
            ctx.prec = 80
            try:
                v = value(r)
            except (decimal.DivisionByZero, decimal.InvalidOperation):
                v = None                   # Spark: x / 0 is NULL
        expected.append(v if want_type == "bool" or v is None
                        else _half_up(v, want_type[1]))
    for make in (lambda: tpu_session(DEVICE), cpu_session):
        s = make()
        keep = list(range(len(rows)))
        if make is not cpu_session:
            keep = _DEVICE_ROWS.get(sql, keep)
        s.create_dataframe(_OPS_TABLE.take(keep)) \
            .create_or_replace_temp_view("t")
        got = s.sql(f"select {sql} as r from t").collect_arrow()
        if want_type == "bool":
            assert got.schema.field("r").type == pa.bool_()
        else:
            assert got.schema.field("r").type == pa.decimal128(*want_type)
        assert got.column("r").to_pylist() == [expected[i] for i in keep], \
            (sql, make)


_OPERATOR_TABLE_ROWS = _OPS_TABLE.to_pylist()
_DEVICE_ROWS = {"a / c": [0, 1, 2, 3, 4, 5], "b / a": [0, 1, 2, 3, 5],
                # 1e15 x 101 x 99 at scale 6 is past 2^63: the loud error
                "a * (1 - b) * (1 + b)": [0, 1, 2, 3, 4, 5]}


# ---------------------------------------------------------------------------
# TPC-H Q1 and Q6 whole, session.sql text, against the benchmark's integer
# reference (perfbench/references/tpch_lineitem_decimal.py) and the host twin
# ---------------------------------------------------------------------------

def _perfbench(kind, name):
    if PERFBENCH not in sys.path:
        sys.path.insert(0, PERFBENCH)
    import datagen
    return datagen.load_module(kind, name)


def _lineitem(rows=20000, seed=2**31 + 11):
    import json
    with open(os.path.join(PERFBENCH, "configs",
                           "tpch_sf10_decimal.json")) as f:
        config = json.load(f)
    tables = {k: dict(v) for k, v in config["tables"].items()}
    tables["lineitem"]["rows"] = rows
    gen = _perfbench("generators", config["generator"])
    return gen.generate("lineitem", tables, seed, 0, rows)


@pytest.mark.parametrize("query,text", [("tpch_q1_decimal", "tpch_q1"),
                                        ("tpch_q6_decimal", "tpch_q6")])
def test_decimal_tpch_query_matches_integer_reference(query, text):
    chunk = _lineitem()
    assert chunk.schema.field("l_extendedprice").type == \
        pa.decimal128(15, 2)
    with open(os.path.join(PERFBENCH, "queries", text + ".sql")) as f:
        sql = f.read()
    ref = _perfbench("references", "tpch_lineitem_decimal")
    want = ref.merge(query, [ref.partial(query, {"lineitem": chunk})])
    answers = []
    for make in (lambda: tpu_session(DEVICE), cpu_session):
        s = make()
        # several batches: Q1 folds them into the device-resident carry
        s.create_dataframe(chunk, num_partitions=4) \
            .create_or_replace_temp_view("lineitem")
        got = s.sql(sql).collect_arrow()
        answers.append(got)
        compared = ref.compare(query, got.to_pandas(), want)
        assert all(v == 0 for v in compared.values()), (make, compared)
    tpu, cpu = answers
    assert tpu.schema == cpu.schema and tpu.equals(cpu)
    if query == "tpch_q1_decimal":
        assert [str(f.type) for f in tpu.schema][2:] == [
            "decimal128(25, 2)", "decimal128(25, 2)", "decimal128(38, 4)",
            "decimal128(38, 6)", "decimal128(19, 6)", "decimal128(19, 6)",
            "decimal128(19, 6)", "int64"]
        # a float64 computation put in the engine's place fails it, at a
        # size whose sums are past 2^53 (at 400,000 rows they are not, and
        # a double holds them to the last digit on some seeds)
        big = {"lineitem": _lineitem(2_500_000, 2400000777)}
        low = ref.merge(query, [ref.partial(query, big, "double")], "double")
        exact = ref.merge(query, [ref.partial(query, big)])
        gaps = ref.compare(query, ref.answer_frame(query, low), exact)
        assert gaps["sum_unscaled_gap"] > 0 and gaps["type_mismatch"] == 0
    else:
        assert str(tpu.schema.field("revenue").type) == "decimal128(38, 4)"


def test_decimal_q1_runs_on_the_device():
    """Every operator of Q1 over decimals is placed on the device but the
    final CpuSort over the few groups (as over doubles)."""
    s = tpu_session(DEVICE)
    s.create_dataframe(_lineitem(4000), num_partitions=2) \
        .create_or_replace_temp_view("lineitem")
    with open(os.path.join(PERFBENCH, "queries", "tpch_q1.sql")) as f:
        df = s.sql(f.read())
    tree = df._physical().tree_string()
    assert "host_fallback" not in tree and "CpuAggregate" not in tree, tree
    # "!" marks an operator placed on the host
    assert [ln.strip("! ").split("[")[0] for ln in tree.splitlines()
            if ln.strip().startswith("!")] == ["CpuSort"], tree
    df.collect_arrow()
    assert s.last_placement == "device"


# ---------------------------------------------------------------------------
# a planted overflow: NULL or the loud error, never a number
# ---------------------------------------------------------------------------

def _overflow_table():
    # 9e15 x 1e2 at scales 2 + 2: the unscaled product is 9e21 > 2^63
    return pa.table({
        "k": pa.array(["x", "y", "x"]),
        "a": pa.array([_D("9000000000000000.00"), _D("1.00"), _D("2.00")],
                      pa.decimal128(18, 2)),
        "b": pa.array([_D("100.00"), _D("3.00"), _D("4.00")],
                      pa.decimal128(15, 2)),
    })


@pytest.mark.parametrize("sql", [
    "select a * b as r from t",
    "select a * b as r from t where b > 1",
    "select sum(a * b) as r from t",
    "select k, sum(a * b) as r, avg(b) as m from t group by k",
    "select cast(a as decimal(38,10)) as r from t",
])
def test_decimal_overflow_is_never_a_wrapped_number(sql):
    s = tpu_session(DEVICE)
    s.create_dataframe(_overflow_table()).create_or_replace_temp_view("t")
    try:
        got = s.sql(sql).collect_arrow().column("r").to_pylist()
    except DecimalOverflow as e:
        assert "64-bit unscaled" in str(e)
    else:
        # where a kernel has no channel for the count, the lane is NULL
        assert None in got and all(
            v is None or abs(v) < _D(10) ** 19 for v in got), got
    # the host twin computes on Python ints: Spark's own number
    c = cpu_session()
    c.create_dataframe(_overflow_table()).create_or_replace_temp_view("t")
    got = c.sql(sql).collect_arrow().column("r").to_pylist()
    assert None not in got, got
    if "sum" not in sql and "cast" not in sql:
        assert got[0] == _D("900000000000000000.0000")


def test_decimal_overflow_row_dropped_by_a_filter_is_nobodys():
    """An overflow counts for the rows that reach the operation."""
    s = tpu_session(DEVICE)
    s.create_dataframe(_overflow_table()).create_or_replace_temp_view("t")
    got = s.sql("select sum(a * b) as r from t where b < 50").collect()
    assert got == [{"r": _D("11.0000")}]


# ---------------------------------------------------------------------------
# avg(decimal): the device's long division against Python ints
# ---------------------------------------------------------------------------

_C = 4000000007          # odd, so 10^4 has an inverse modulo it
#: total / _C = x.xxxxxx499999999875: HALF_UP at scale 15 makes it ...5
#: and HALF_UP again at scale 6 rounds UP; one rounding would round down
_S_TWICE = ((_C - 1) // 2) * pow(10 ** 4, -1, _C) % _C


@pytest.mark.parametrize("total,count,ptype", [
    (_S_TWICE, _C, (15, 2)),               # Spark's two roundings differ
    (-_S_TWICE, _C, (15, 2)),
    (1, 32, (5, 0)),                       # 0.03125: a tie at scale 4
    (-1, 32, (5, 0)),
    (3_400_000_000_000_000_123, 29_000_000, (15, 2)),   # Q1's scale
    (-(10 ** 25) - 7, 3_000_000_000_001, (20, 0)),  # only limbs hold it
    (5, 1, (10, 4)),
    (0, 7, (15, 2)),
], ids=["twice", "twice-neg", "tie", "tie-neg", "q1", "limbs", "one", "zero"])
def test_decimal_average_rounds_twice_as_spark(total, count, ptype):
    import jax.numpy as jnp
    from spark_rapids_tpu.exprs import decimal_rules as D
    from spark_rapids_tpu.exprs.aggregates import (_DEC_LIMB, _dec_average,
                                                   _dec_normalize)
    from spark_rapids_tpu.types import DecimalType
    dt = DecimalType(*ptype)
    mag = abs(total)
    limbs = [mag % _DEC_LIMB, mag // _DEC_LIMB % _DEC_LIMB,
             mag // _DEC_LIMB ** 2]
    if total < 0:
        limbs = [-x for x in limbs]
    l0, l1, l2 = _dec_normalize(*(jnp.asarray([x], jnp.int64)
                                  for x in limbs))
    q, over = _dec_average(l0, l1, l2, jnp.asarray([count], jnp.int64), dt)
    want = D.average_int(total, count, dt)
    assert not bool(over[0]) and int(q[0]) == want
    # Spark's rules, written out: HALF_UP at the divide's scale, then at
    # the average's
    st, dv, res = D.avg_types(dt)
    with decimal.localcontext() as ctx:
        ctx.prec = 120
        exact = _D(total).scaleb(-st.scale) / _D(count)
        twice = _half_up(_half_up(exact, dv.scale), res.scale)
        assert _D(want).scaleb(-res.scale) == twice
        if total in (_S_TWICE, -_S_TWICE):
            assert twice != _half_up(exact, res.scale)


def test_decimal_estimated_divmod_is_exact():
    """The division the limb and digit arithmetic uses (float32 estimates
    of what is left of the quotient, then one whole step each way) equals
    Python's floor divmod over its whole contract: one round for
    quotients below 2^21, two for every int64 over a limb or a digit,
    either sign, constants and lane divisors, the edges of the lane."""
    import jax.numpy as jnp
    from spark_rapids_tpu.exprs.aggregates import _divmod_est
    rng = np.random.RandomState(7)
    edge = [0, 1, -1, 2 ** 63 - 1, -2 ** 63, 10 ** 18, -10 ** 18]
    for k, rounds, most in [
            (10 ** 12, 1, 2 ** 21 * 10 ** 12), (10 ** 12, 2, 2 ** 63),
            (10 ** 6, 1, 2 ** 21 * 10 ** 6), (10 ** 6, 2, 2 ** 62),
            (10 ** 18, 1, 2 ** 63), (10, 2, 2 ** 40),
            (999_983, 2, 2 ** 60), (4_398_046_511_103, 2, 2 ** 63)]:
        xs = [int(v) for v in rng.randint(-2 ** 62, 2 ** 62, 6000)
              .astype(object) * 2 % most - most // 2]
        xs += [q * k + r for q in (-3, -1, 0, 1, 2 ** 20)
               for r in (0, 1, k // 2, k - 1)]
        xs = [v for v in xs + edge if -most <= v < most
              and -2 ** 63 <= v < 2 ** 63]
        arr = jnp.asarray(np.array(xs, dtype=np.int64))
        for div in (k, jnp.full(len(xs), k, jnp.int64)):
            q, r = _divmod_est(arr, div, rounds)
            want = [divmod(v, k) for v in xs]
            assert [(int(a), int(b)) for a, b in zip(q, r)] == want, \
                (k, rounds)
