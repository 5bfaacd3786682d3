"""The plain reference for TPC-H Q1 and Q6 over ``decimal(15,2)`` money
columns: exact integer arithmetic on unscaled values, Spark's result
types and HALF_UP written out below. It imports nothing of the engine and
takes nothing the engine made.

Per chunk and group the partial sums are numpy ``int64`` (the widest
product is 10,494,950 x 100 x 108 = 1.13e11 at scale 6, so 1,048,576 rows
of it stay under 1.2e17); across chunks they are folded, and the averages
divided, in Python ``int``. No float anywhere.

``precision``: ``run.py`` passes the literal ``"float64"`` as "the
configuration's own precision" (``datagen.reference_answer``'s default)
and may not be edited here, so this module reads ``"float64"`` (and the
configuration's ``"decimal"``) as EXACT. Its control goes under another
name, ``--control double``: the same query computed in float64 doubles
(any other precision name is a numpy float type) and put in the program's
place, each number rounded HALF_UP to Spark's scale. At the cell's own
size a group's ``sum_charge`` is about 3.4e18 at scale 6, past 2^53, so
doubles cannot hold the sums and the control comes out ``correct: false``
(``sum_unscaled_gap``): computing in a lower precision than the
configuration states fails the comparison.

``compare`` returns ``shape_mismatch``, ``count_gap``, ``type_mismatch``
(a result column that is not a decimal of Spark's scale; the precision
does not survive ``to_pandas``, the scale does), ``sum_unscaled_gap`` and
``avg_unscaled_gap`` (largest absolute difference in units of the last
digit). Every limit is 0: the type is exact, so is the comparison.
"""
from __future__ import annotations

import decimal

import numpy as np
import pandas as pd

Q1_CUTOFF = np.datetime64("1998-12-01") - np.timedelta64(90, "D")
MONEY = ["l_quantity", "l_extendedprice", "l_discount", "l_tax"]
KEYS = ["l_returnflag", "l_linestatus"]
EXACT = ("float64", "decimal")
#: "cannot be compared"; finite, so that the result line stays valid JSON
INF = 1e300

# ---- Spark's decimal rules (Catalyst DecimalPrecision), in plain lines ----


def _adjust(p, s):                  # DecimalType.adjustPrecisionScale
    return (p, s) if p <= 38 else (38, max(38 - (p - s), min(s, 6)))


def _add(a, b):                     # a + b, a - b
    s = max(a[1], b[1])
    return _adjust(max(a[0] - a[1], b[0] - b[1]) + s + 1, s)


def _mul(a, b):                     # a * b
    return _adjust(a[0] + b[0] + 1, a[1] + b[1])


def _div(a, b):                     # a / b
    s = max(6, a[1] + b[0] + 1)
    return _adjust(a[0] - a[1] + b[1] + s, s)


def _sum(a):                        # sum(a)
    return (min(a[0] + 10, 38), a[1])


def _avg(a):                        # avg(a): sum / count, then a cast
    return (min(a[0] + 4, 38), min(a[1] + 4, 38))


COL = (15, 2)                                   # decimal(15,2)
ONE = (1, 0)                                    # the literal 1
DISC_PRICE = _mul(COL, _add(ONE, COL))          # decimal(32,4)
CHARGE = _mul(DISC_PRICE, _add(ONE, COL))       # decimal(49,6) -> (38,6)
Q1_TYPES = {"sum_qty": _sum(COL), "sum_base_price": _sum(COL),
            "sum_disc_price": _sum(DISC_PRICE), "sum_charge": _sum(CHARGE),
            "avg_qty": _avg(COL), "avg_price": _avg(COL),
            "avg_disc": _avg(COL)}
Q1_SUMS = ["sum_qty", "sum_base_price", "sum_disc_price", "sum_charge"]
Q1_AVGS = ["avg_qty", "avg_price", "avg_disc"]
Q6_TYPE = _sum(_mul(COL, COL))                  # decimal(38,4)
#: the divide inside avg: sum / cast(count as decimal(20,0)), HALF_UP at
#: ITS scale; the cast to the average's type rounds HALF_UP again
AVG_DIVIDE = _div(_sum(COL), (20, 0))           # decimal(38,15)


def half_up(n: int, d: int) -> int:
    """n / d rounded HALF_UP (ties away from zero); d > 0."""
    q, r = divmod(abs(n), d)
    q += 2 * r >= d
    return -q if n < 0 else q


def average(total: int, count: int) -> int:
    """avg over decimal(15,2) as Spark evaluates it: two roundings."""
    q = half_up(total * 10 ** (AVG_DIVIDE[1] - COL[1]), count)
    return half_up(q, 10 ** (AVG_DIVIDE[1] - _avg(COL)[1]))


# ---- the chunks ---------------------------------------------------------

def _cents(chunk, name) -> np.ndarray:
    """A decimal128 column's unscaled values: the low words of its
    16-byte buffers (the generator's cents)."""
    arr = chunk.column(name).combine_chunks()
    words = np.frombuffer(arr.buffers()[1], dtype=np.int64)
    return words[2 * arr.offset::2][:len(arr)]


def _ship(chunk) -> np.ndarray:
    return chunk.column("l_shipdate").to_numpy().astype("datetime64[D]")


def _group_codes(chunk):
    """(code per row, [(returnflag, linestatus)] per code)."""
    flag = chunk.column("l_returnflag").combine_chunks().dictionary_encode()
    stat = chunk.column("l_linestatus").combine_chunks().dictionary_encode()
    nstat = len(stat.dictionary)
    code = (flag.indices.to_numpy().astype(np.int64) * nstat
            + stat.indices.to_numpy())
    names = [(f, s) for f in flag.dictionary.to_pylist()
             for s in stat.dictionary.to_pylist()]
    return code, names


def _values(chunk, precision):
    """quantity, price, discount, tax: int64 cents (exact) or the same
    values as floats of the control's type."""
    cols = [_cents(chunk, c) for c in MONEY]
    if precision in EXACT:
        return cols
    dt = np.dtype("float64" if precision == "double" else precision)
    return [(c.astype(np.float64) / 100.0).astype(dt) for c in cols]


def _partial_q1(tables, precision):
    chunk = tables["lineitem"]
    keep = _ship(chunk) <= Q1_CUTOFF
    qty, price, disc, tax = (v[keep] for v in _values(chunk, precision))
    code, names = _group_codes(chunk)
    code = code[keep]
    one = 100 if precision in EXACT else qty.dtype.type(1.0)
    disc_price = price * (one - disc)           # scale 4
    charge = disc_price * (one + tax)           # scale 6
    out = {}
    for g in np.unique(code):
        m = code == g
        out[names[g]] = [x[m].sum().item() for x in
                         (qty, price, disc_price, charge, disc)] \
            + [int(m.sum())]
    return out


def _merge_q1(states, precision):
    total = {}
    for part in states:
        for key, vals in part.items():
            acc = total.setdefault(key, [0] * 6)
            for i, v in enumerate(vals):
                acc[i] = acc[i] + v
    rows = []
    for key in sorted(total):
        qty, price, disc_price, charge, disc, n = total[key]
        if precision in EXACT:
            avgs = [average(t, n) for t in (qty, price, disc)]
        else:
            avgs = [t / n for t in (qty, price, disc)]
        rows.append(list(key) + [qty, price, disc_price, charge] + avgs + [n])
    return pd.DataFrame(rows, columns=KEYS + Q1_SUMS + Q1_AVGS
                        + ["count_order"]).astype(
        {c: object for c in Q1_SUMS + Q1_AVGS})


def _partial_q6(tables, precision):
    chunk = tables["lineitem"]
    ship = _ship(chunk)
    qty, price, disc, _ = _values(chunk, precision)
    if precision in EXACT:
        lo, hi, most = 5, 7, 2400
    else:
        lo, hi, most = (qty.dtype.type(x) for x in (0.05, 0.07, 24.0))
    m = ((ship >= np.datetime64("1994-01-01"))
         & (ship < np.datetime64("1995-01-01"))
         & (disc >= lo) & (disc <= hi) & (qty < most))
    return (price[m] * disc[m]).sum().item(), int(m.sum())


def _merge_q6(states, precision):
    # SQL's sum over no rows is NULL
    if not sum(n for _, n in states):
        return None
    return sum(s for s, _ in states)


# ---- the shape the engine returns, and the comparison --------------------

def _decimal(value, scale: int, exact: bool):
    """An exact unscaled int, or a control's float rounded HALF_UP, as
    the decimal.Decimal of that scale."""
    with decimal.localcontext() as c:
        c.prec = 80
        if exact:
            return decimal.Decimal(int(value)).scaleb(-scale)
        return decimal.Decimal(repr(float(value))).quantize(
            decimal.Decimal(1).scaleb(-scale),
            rounding=decimal.ROUND_HALF_UP)


def _unscaled(cell, scale: int):
    """A result cell as its unscaled int, or None where it is not a
    decimal of this scale (an Arrow type that is not Spark's)."""
    if not isinstance(cell, decimal.Decimal) \
            or cell.as_tuple().exponent != -scale:
        return None
    with decimal.localcontext() as c:
        c.prec = 80
        return int(cell.scaleb(scale))


def _is_exact(want) -> bool:
    """Whether an answer holds exact ints (the reference) or a control's
    floats."""
    if isinstance(want, pd.DataFrame):
        return all(isinstance(v, int) for v in want["sum_charge"])
    return want is None or isinstance(want, int)


def answer_frame(query: str, want) -> pd.DataFrame:
    """The expected answer in the shape the engine returns it: decimal
    columns of Spark's scales (used when the control is put in the
    program's place)."""
    exact = _is_exact(want)
    if query == "tpch_q6_decimal":
        cell = None if want is None else _decimal(want, Q6_TYPE[1], exact)
        return pd.DataFrame({"revenue": [cell]}, dtype=object)
    out = want.copy()
    for c in Q1_SUMS + Q1_AVGS:
        out[c] = [_decimal(v, Q1_TYPES[c][1], exact) for v in want[c]]
    return out


def _gap(got, want, scale: int):
    """(largest |difference| in units of the last digit, cells that are
    not decimals of the scale)."""
    worst, bad = 0, 0
    for g, w in zip(got, want):
        u = _unscaled(g, scale)
        if u is None:
            bad += 1
        else:
            worst = max(worst, abs(u - int(w)))
    return float(worst), bad


def _compare_q1(got: pd.DataFrame, want: pd.DataFrame) -> dict:
    out = {"shape_mismatch": 1.0, "count_gap": INF, "type_mismatch": INF,
           "sum_unscaled_gap": INF, "avg_unscaled_gap": INF}
    if list(got.columns) != list(want.columns) or len(got) != len(want):
        return out
    # ORDER BY l_returnflag, l_linestatus: position by position
    if not all((got[k].to_numpy() == want[k].to_numpy()).all()
               for k in KEYS):
        return out
    out["shape_mismatch"] = 0.0
    counts = got["count_order"]
    bad = 0 if counts.dtype.kind == "i" else 1
    out["count_gap"] = float(np.max(np.abs(
        counts.to_numpy().astype(np.int64)
        - want["count_order"].to_numpy().astype(np.int64))))
    gaps = {}
    for c in Q1_SUMS + Q1_AVGS:
        gaps[c], n = _gap(got[c], want[c], Q1_TYPES[c][1])
        bad += n
    out["type_mismatch"] = float(bad)
    out["sum_unscaled_gap"] = max(gaps[c] for c in Q1_SUMS)
    out["avg_unscaled_gap"] = max(gaps[c] for c in Q1_AVGS)
    return out


def _compare_q6(got: pd.DataFrame, want) -> dict:
    if list(got.columns) != ["revenue"] or len(got) != 1:
        return {"shape_mismatch": 1.0, "type_mismatch": INF,
                "sum_unscaled_gap": INF}
    cell = got["revenue"].iloc[0]
    if want is None or cell is None:
        same = want is None and cell is None
        return {"shape_mismatch": 0.0 if same else 1.0,
                "type_mismatch": 0.0, "sum_unscaled_gap": 0.0}
    gap, bad = _gap([cell], [want], Q6_TYPE[1])
    return {"shape_mismatch": 0.0, "type_mismatch": float(bad),
            "sum_unscaled_gap": gap}


_QUERIES = {
    "tpch_q1_decimal": (_partial_q1, _merge_q1, _compare_q1),
    "tpch_q6_decimal": (_partial_q6, _merge_q6, _compare_q6),
}


def partial(query: str, tables: dict, precision: str = "float64"):
    """The reference's partial state over one chunk of the fact table
    (``tables``: table name -> the Arrow chunk)."""
    return _QUERIES[query][0](tables, precision)


def merge(query: str, states: list, precision: str = "float64"):
    """The expected answer from the chunks' partial states: unscaled
    Python ints (exact), or a control's floats."""
    return _QUERIES[query][1](states, precision)


def compare(query: str, got: pd.DataFrame, want) -> dict:
    """name -> number compared (each has its limit, 0, in
    perfbench/queries/<query>.json)."""
    return _QUERIES[query][2](got, want)
