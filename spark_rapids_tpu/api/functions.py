"""Column DSL + functions, PySpark-flavoured (the reference accelerates
Spark's DataFrame API; standalone we provide the same surface).

    from spark_rapids_tpu.api import functions as F
    df.select(F.col("a") + 1, F.when(F.col("b") > 0, 1).otherwise(0))
"""
from __future__ import annotations

from typing import Optional

from .. import exprs as E
from ..exprs.aggregates import (Average, Count, CountStar, First, Last, Max,
                                Min, StddevPop, StddevSamp, Sum, VariancePop,
                                VarianceSamp)
from ..types import (BOOL, DataType, FLOAT32, FLOAT64, INT8, INT16, INT32,
                     INT64, STRING, DATE, TIMESTAMP)

__all__ = ["Col", "col", "lit", "when", "coalesce", "isnan", "isnull",
           "sqrt", "exp", "log", "sin", "cos", "tan", "floor", "ceil",
           "round", "pow", "abs", "sum", "count", "count_star", "avg",
           "mean", "min", "max", "first", "last", "stddev", "stddev_pop",
           "var_samp", "var_pop", "cast", "asc", "desc"]

_builtin_abs, _builtin_sum, _builtin_min, _builtin_max, _builtin_round = \
    abs, sum, min, max, round


def _to_expr(v) -> E.Expression:
    if isinstance(v, Col):
        return v.expr
    if isinstance(v, E.Expression):
        return v
    return E.Literal(v)


class Col:
    """Wrapper giving Expression a PySpark-like operator surface."""

    def __init__(self, expr: E.Expression):
        self.expr = expr

    # arithmetic
    def __add__(self, o): return Col(E.Add(self.expr, _to_expr(o)))
    def __radd__(self, o): return Col(E.Add(_to_expr(o), self.expr))
    def __sub__(self, o): return Col(E.Subtract(self.expr, _to_expr(o)))
    def __rsub__(self, o): return Col(E.Subtract(_to_expr(o), self.expr))
    def __mul__(self, o): return Col(E.Multiply(self.expr, _to_expr(o)))
    def __rmul__(self, o): return Col(E.Multiply(_to_expr(o), self.expr))
    def __truediv__(self, o): return Col(E.Divide(self.expr, _to_expr(o)))
    def __rtruediv__(self, o): return Col(E.Divide(_to_expr(o), self.expr))
    def __mod__(self, o): return Col(E.Remainder(self.expr, _to_expr(o)))
    def __neg__(self): return Col(E.UnaryMinus(self.expr))
    def __pow__(self, o): return Col(E.Pow(self.expr, _to_expr(o)))

    # comparison
    def __eq__(self, o): return Col(E.EqualTo(self.expr, _to_expr(o)))
    def __ne__(self, o): return Col(E.NotEqual(self.expr, _to_expr(o)))
    def __lt__(self, o): return Col(E.LessThan(self.expr, _to_expr(o)))
    def __le__(self, o): return Col(E.LessThanOrEqual(self.expr, _to_expr(o)))
    def __gt__(self, o): return Col(E.GreaterThan(self.expr, _to_expr(o)))
    def __ge__(self, o): return Col(E.GreaterThanOrEqual(self.expr, _to_expr(o)))
    def eqNullSafe(self, o): return Col(E.EqualNullSafe(self.expr, _to_expr(o)))

    # logic
    def __and__(self, o): return Col(E.And(self.expr, _to_expr(o)))
    def __or__(self, o): return Col(E.Or(self.expr, _to_expr(o)))
    def __invert__(self): return Col(E.Not(self.expr))

    # misc
    def isNull(self): return Col(E.IsNull(self.expr))
    def isNotNull(self): return Col(E.IsNotNull(self.expr))

    # -- string predicates (PySpark Column parity) --
    def contains(self, s): return Col(E.Contains(self.expr, s))
    def startswith(self, s): return Col(E.StartsWith(self.expr, s))
    def endswith(self, s): return Col(E.EndsWith(self.expr, s))
    def like(self, pattern): return Col(E.Like(self.expr, pattern))
    def rlike(self, pattern): return Col(E.RLike(self.expr, pattern))
    def isin(self, *vals):
        vals = vals[0] if len(vals) == 1 and isinstance(vals[0], (list, tuple)) \
            else vals
        return Col(E.In(self.expr, vals))

    def alias(self, name: str): return Col(E.Alias(self.expr, name))
    name = alias

    def cast(self, dtype): return Col(E.Cast(self.expr, _dtype_of(dtype)))

    def asc(self, nulls_first: Optional[bool] = None):
        from ..plan.logical import SortOrder
        return SortOrder(self.expr, True, nulls_first)

    def desc(self, nulls_first: Optional[bool] = None):
        from ..plan.logical import SortOrder
        return SortOrder(self.expr, False, nulls_first)

    def __hash__(self):
        return id(self)

    def __repr__(self):
        return f"Col<{self.expr.name_hint}>"


_DTYPES = {"boolean": BOOL, "bool": BOOL, "tinyint": INT8, "byte": INT8,
           "smallint": INT16, "short": INT16, "int": INT32, "integer": INT32,
           "bigint": INT64, "long": INT64, "float": FLOAT32,
           "double": FLOAT64, "string": STRING, "date": DATE,
           "timestamp": TIMESTAMP}


def _dtype_of(d) -> DataType:
    if isinstance(d, DataType):
        return d
    name = str(d).lower().replace(" ", "")
    if name.startswith("decimal(") and name.endswith(")"):
        from ..types import DecimalType
        p, _, s = name[len("decimal("):-1].partition(",")
        return DecimalType(int(p), int(s or 0))
    return _DTYPES[name]


def col(name: str) -> Col:
    return Col(E.ColumnRef(name))


def lit(v) -> Col:
    return Col(E.Literal(v))


class _WhenBuilder:
    def __init__(self, branches):
        self.branches = branches

    def when(self, cond, value) -> "_WhenBuilder":
        return _WhenBuilder(self.branches + [(_to_expr(cond), _to_expr(value))])

    def otherwise(self, value) -> Col:
        return Col(E.CaseWhen(self.branches, _to_expr(value)))

    @property
    def col(self) -> Col:
        return Col(E.CaseWhen(self.branches, None))


def when(cond, value) -> _WhenBuilder:
    return _WhenBuilder([(_to_expr(cond), _to_expr(value))])


def coalesce(*cols) -> Col:
    return Col(E.Coalesce(*[_to_expr(c) for c in cols]))


def nullif(a, b) -> Col:
    """nullif(a, b): NULL when a == b else a (Spark semantics)."""
    return Col(E.NullIf(_to_expr(a), _to_expr(b)))


def isnan(c) -> Col: return Col(E.IsNaN(_to_expr(c)))
def isnull(c) -> Col: return Col(E.IsNull(_to_expr(c)))
def sqrt(c) -> Col: return Col(E.Sqrt(_to_expr(c)))
def exp(c) -> Col: return Col(E.Exp(_to_expr(c)))
def log(c) -> Col: return Col(E.Log(_to_expr(c)))
def sin(c) -> Col: return Col(E.Sin(_to_expr(c)))
def cos(c) -> Col: return Col(E.Cos(_to_expr(c)))
def tan(c) -> Col: return Col(E.Tan(_to_expr(c)))
def floor(c) -> Col: return Col(E.Floor(_to_expr(c)))
def ceil(c) -> Col: return Col(E.Ceil(_to_expr(c)))
def round(c, scale: int = 0) -> Col: return Col(E.Round(_to_expr(c), scale))
def pow(a, b) -> Col: return Col(E.Pow(_to_expr(a), _to_expr(b)))
def abs(c) -> Col: return Col(E.Abs(_to_expr(c)))
def cast(c, dtype) -> Col: return Col(E.Cast(_to_expr(c), _dtype_of(dtype)))


# --- datetime -------------------------------------------------------------
def year(c) -> Col: return Col(E.Year(_to_expr(c)))
def month(c) -> Col: return Col(E.Month(_to_expr(c)))
def dayofmonth(c) -> Col: return Col(E.DayOfMonth(_to_expr(c)))
def hour(c) -> Col: return Col(E.Hour(_to_expr(c)))
def minute(c) -> Col: return Col(E.Minute(_to_expr(c)))
def second(c) -> Col: return Col(E.Second(_to_expr(c)))
def dayofweek(c) -> Col: return Col(E.DayOfWeek(_to_expr(c)))
def weekday(c) -> Col: return Col(E.WeekDay(_to_expr(c)))
def dayofyear(c) -> Col: return Col(E.DayOfYear(_to_expr(c)))
def quarter(c) -> Col: return Col(E.Quarter(_to_expr(c)))
def date_add(c, days) -> Col: return Col(E.DateAdd(_to_expr(c), _to_expr(days)))
def date_sub(c, days) -> Col: return Col(E.DateSub(_to_expr(c), _to_expr(days)))
def datediff(end, start) -> Col:
    return Col(E.DateDiff(_to_expr(end), _to_expr(start)))


# --- strings ----------------------------------------------------------------
def length(c) -> Col: return Col(E.Length(_to_expr(c)))
def upper(c) -> Col: return Col(E.Upper(_to_expr(c)))
def lower(c) -> Col: return Col(E.Lower(_to_expr(c)))
def substring(c, pos, ln=None) -> Col:
    return Col(E.Substring(_to_expr(c), pos, ln))
def concat(*cols) -> Col:
    return Col(E.ConcatStrings(*[_to_expr(c) for c in cols]))
def contains(c, s) -> Col: return Col(E.Contains(_to_expr(c), s))
def startswith(c, s) -> Col: return Col(E.StartsWith(_to_expr(c), s))
def endswith(c, s) -> Col: return Col(E.EndsWith(_to_expr(c), s))
def like(c, pattern) -> Col: return Col(E.Like(_to_expr(c), pattern))
def rlike(c, pattern) -> Col: return Col(E.RLike(_to_expr(c), pattern))
def replace(c, search: str, replacement: str = "") -> Col:
    return Col(E.StringReplace(_to_expr(c), search, replacement))
def regexp_replace(c, pattern, repl) -> Col:
    return Col(E.RegExpReplace(_to_expr(c), pattern, repl))
def regexp_extract(c, pattern, group=1) -> Col:
    return Col(E.RegExpExtract(_to_expr(c), pattern, group))
def trim(c) -> Col: return Col(E.StringTrim(_to_expr(c)))
def ltrim(c) -> Col: return Col(E.StringTrimLeft(_to_expr(c)))
def rtrim(c) -> Col: return Col(E.StringTrimRight(_to_expr(c)))
def lpad(c, ln, pad=" ") -> Col: return Col(E.Lpad(_to_expr(c), ln, pad))
def rpad(c, ln, pad=" ") -> Col: return Col(E.Rpad(_to_expr(c), ln, pad))
def reverse(c) -> Col: return Col(E.Reverse(_to_expr(c)))
def repeat(c, n) -> Col: return Col(E.StringRepeat(_to_expr(c), n))
def initcap(c) -> Col: return Col(E.InitCap(_to_expr(c)))
def locate(substr, c) -> Col: return Col(E.StringLocate(substr, _to_expr(c)))
def split(c, pattern, limit=-1) -> Col:
    return Col(E.StringSplit(_to_expr(c), pattern, limit))
def parse_url(c, part, key=None) -> Col:
    return Col(E.ParseUrl(_to_expr(c), part, key))
def from_utc_timestamp(c, tz) -> Col:
    return Col(E.FromUtcTimestamp(_to_expr(c), tz))
def to_utc_timestamp(c, tz) -> Col:
    return Col(E.ToUtcTimestamp(_to_expr(c), tz))
def substring_index(c, delim, count) -> Col:
    return Col(E.SubstringIndex(_to_expr(c), delim, count))


# --- collections / complex types (ref collectionOperations.scala) -----------
def size(c) -> Col: return Col(E.Size(_to_expr(c)))
def array_contains(c, value) -> Col:
    return Col(E.ArrayContains(_to_expr(c), _to_expr(value)))
def array_position(c, value) -> Col:
    return Col(E.ArrayPosition(_to_expr(c), _to_expr(value)))
def element_at(c, extraction) -> Col:
    return Col(E.ElementAt(_to_expr(c), _to_expr(extraction)))
def get(c, index) -> Col:
    return Col(E.GetArrayItem(_to_expr(c), _to_expr(index)))
def get_field(c, name: str) -> Col:
    return Col(E.GetStructField(_to_expr(c), name))
def sort_array(c, asc: bool = True) -> Col:
    return Col(E.SortArray(_to_expr(c), E.Literal(asc)))
def array_min(c) -> Col: return Col(E.ArrayMin(_to_expr(c)))
def array_max(c) -> Col: return Col(E.ArrayMax(_to_expr(c)))
def array_join(c, delimiter, null_replacement=None) -> Col:
    rep = E.Literal(null_replacement) if null_replacement is not None else None
    return Col(E.ArrayJoin(_to_expr(c), E.Literal(delimiter), rep))
def slice(c, start, length) -> Col:
    return Col(E.Slice(_to_expr(c), _to_expr(start), _to_expr(length)))
def array_repeat(c, count) -> Col:
    return Col(E.ArrayRepeat(_to_expr(c), _to_expr(count)))
def arrays_zip(*cols) -> Col:
    names = [c.expr.name_hint if isinstance(c, Col) else str(i)
             for i, c in enumerate(cols)]
    return Col(E.ArraysZip(*[_to_expr(c) for c in cols], names=names))
def concat_arrays(*cols) -> Col:
    return Col(E.Concat(*[_to_expr(c) for c in cols]))
def flatten(c) -> Col: return Col(E.Flatten(_to_expr(c)))
def sequence(start, stop, step=None) -> Col:
    return Col(E.Sequence(_to_expr(start), _to_expr(stop),
                          _to_expr(step) if step is not None else None))
def array_distinct(c) -> Col: return Col(E.ArrayDistinct(_to_expr(c)))
def array_union(a, b) -> Col:
    return Col(E.ArrayUnion(_to_expr(a), _to_expr(b)))
def array_intersect(a, b) -> Col:
    return Col(E.ArrayIntersect(_to_expr(a), _to_expr(b)))
def array_except(a, b) -> Col:
    return Col(E.ArrayExcept(_to_expr(a), _to_expr(b)))
def array_remove(c, element) -> Col:
    return Col(E.ArrayRemove(_to_expr(c), _to_expr(element)))
def arrays_overlap(a, b) -> Col:
    return Col(E.ArraysOverlap(_to_expr(a), _to_expr(b)))
def array_reverse(c) -> Col: return Col(E.ArrayReverse(_to_expr(c)))
def map_keys(c) -> Col: return Col(E.MapKeys(_to_expr(c)))
def map_values(c) -> Col: return Col(E.MapValues(_to_expr(c)))
def map_entries(c) -> Col: return Col(E.MapEntries(_to_expr(c)))
def map_concat(*cols) -> Col:
    return Col(E.MapConcat(*[_to_expr(c) for c in cols]))
def map_from_arrays(keys, values) -> Col:
    return Col(E.MapFromArrays(_to_expr(keys), _to_expr(values)))
def str_to_map(c, pair_delim=",", kv_delim=":") -> Col:
    return Col(E.StringToMap(_to_expr(c), E.Literal(pair_delim),
                             E.Literal(kv_delim)))
def array(*cols) -> Col:
    return Col(E.CreateArray(*[_to_expr(c) for c in cols]))
def create_map(*cols) -> Col:
    return Col(E.CreateMap(*[_to_expr(c) for c in cols]))
def struct(*cols) -> Col:
    pairs = []
    for c in cols:
        pairs.append(E.Literal(c.expr.name_hint if isinstance(c, Col) else str(c)))
        pairs.append(_to_expr(c))
    return Col(E.CreateNamedStruct(*pairs))
def named_struct(*name_col_pairs) -> Col:
    return Col(E.CreateNamedStruct(*[_to_expr(p) for p in name_col_pairs]))


# --- higher-order functions (ref higherOrderFunctions.scala) ----------------
def _make_lambda(fn, hints, min_args=1):
    """Python callable over Col -> (arg vars, body expr). Arity is taken
    from the callable (like pyspark); min_args is per-function (e.g.
    zip_with and the map HOFs require exactly 2)."""
    import inspect
    n = len(inspect.signature(fn).parameters)
    if not min_args <= n <= len(hints):
        raise TypeError(
            f"lambda must take between {min_args} and {len(hints)} "
            f"arguments, got {n}")
    args = [E.NamedLambdaVariable(hints[i]) for i in range(n)]
    body = _to_expr(fn(*[Col(a) for a in args]))
    return args, body


def transform(c, fn) -> Col:
    args, body = _make_lambda(fn, ["x", "i"])
    return Col(E.ArrayTransform(_to_expr(c), args, body))
def filter(c, fn) -> Col:
    args, body = _make_lambda(fn, ["x", "i"])
    return Col(E.ArrayFilter(_to_expr(c), args, body))
def exists(c, fn) -> Col:
    args, body = _make_lambda(fn, ["x"])
    return Col(E.ArrayExists(_to_expr(c), args, body))
def forall(c, fn) -> Col:
    args, body = _make_lambda(fn, ["x"])
    return Col(E.ArrayForAll(_to_expr(c), args, body))
def aggregate(c, initial, merge, finish=None) -> Col:
    margs, mbody = _make_lambda(merge, ["acc", "x"], min_args=2)
    fargs = fbody = None
    if finish is not None:
        fargs, fbody = _make_lambda(finish, ["acc"])
    return Col(E.ArrayAggregate(_to_expr(c), _to_expr(initial), margs, mbody,
                                fargs, fbody))
def zip_with(a, b, fn) -> Col:
    args, body = _make_lambda(fn, ["x", "y"], min_args=2)
    return Col(E.ZipWith(_to_expr(a), _to_expr(b), args, body))
def transform_keys(c, fn) -> Col:
    args, body = _make_lambda(fn, ["k", "v"], min_args=2)
    return Col(E.TransformKeys(_to_expr(c), args, body))
def transform_values(c, fn) -> Col:
    args, body = _make_lambda(fn, ["k", "v"], min_args=2)
    return Col(E.TransformValues(_to_expr(c), args, body))
def map_filter(c, fn) -> Col:
    args, body = _make_lambda(fn, ["k", "v"], min_args=2)
    return Col(E.MapFilter(_to_expr(c), args, body))


# --- hashes / digests (ref HashFunctions.scala) -----------------------------
def hash(*cols) -> Col:
    return Col(E.Murmur3Hash([_to_expr(c) for c in cols]))
def xxhash64(*cols) -> Col:
    return Col(E.XxHash64([_to_expr(c) for c in cols]))
def hive_hash(*cols) -> Col:
    return Col(E.HiveHash([_to_expr(c) for c in cols]))
def md5(c) -> Col: return Col(E.Md5(_to_expr(c)))
def sha1(c) -> Col: return Col(E.Sha1(_to_expr(c)))
def sha2(c, num_bits: int = 256) -> Col:
    return Col(E.Sha2(_to_expr(c), num_bits))
def crc32(c) -> Col: return Col(E.Crc32(_to_expr(c)))


# --- JSON (ref GpuGetJsonObject / JsonToStructs / StructsToJson) ------------
def get_json_object(c, path: str) -> Col:
    return Col(E.GetJsonObject(_to_expr(c), E.Literal(path)))
def from_json(c, schema) -> Col:
    return Col(E.JsonToStructs(_to_expr(c), schema))
def to_json(c) -> Col: return Col(E.StructsToJson(_to_expr(c)))
def json_tuple(c, *fields) -> Col:
    return Col(E.JsonTuple(_to_expr(c), *fields))


# --- generators (ref GpuGenerateExec; planned via DataFrame.select) ---------
def explode(c) -> Col:
    from ..exprs.generators import Explode
    return Col(Explode(_to_expr(c)))
def explode_outer(c) -> Col:
    from ..exprs.generators import Explode
    return Col(Explode(_to_expr(c), outer=True))
def posexplode(c) -> Col:
    from ..exprs.generators import PosExplode
    return Col(PosExplode(_to_expr(c)))
def posexplode_outer(c) -> Col:
    from ..exprs.generators import PosExplode
    return Col(PosExplode(_to_expr(c), outer=True))
def stack(n: int, *cols) -> Col:
    from ..exprs.generators import Stack
    return Col(Stack(n, *[_to_expr(c) for c in cols]))


# --- task-context / non-deterministic ---------------------------------------
def monotonically_increasing_id() -> Col:
    from ..exprs.nondeterministic import MonotonicallyIncreasingID
    return Col(MonotonicallyIncreasingID())
def spark_partition_id() -> Col:
    from ..exprs.nondeterministic import SparkPartitionID
    return Col(SparkPartitionID())
def input_file_name() -> Col:
    from ..exprs.nondeterministic import InputFileName
    return Col(InputFileName())
def rand(seed: int = 0) -> Col:
    from ..exprs.nondeterministic import Rand
    return Col(Rand(seed))


# --- window -----------------------------------------------------------------
def row_number(): return E.RowNumber()
def rank(): return E.Rank()
def dense_rank(): return E.DenseRank()
def ntile(n): return E.NTile(n)
def nth_value(c, n): return E.NthValue(_to_expr(c), n)
def percent_rank(): return E.PercentRank()
def lag(c, offset=1, default=None):
    return E.Lag(_to_expr(c), offset, default)
def lead(c, offset=1, default=None):
    return E.Lead(_to_expr(c), offset, default)


def asc(name: str):
    return col(name).asc()


def desc(name: str):
    return col(name).desc()


# aggregates (return AggregateExpression, consumed by GroupedData/agg)
def sum(c): return Sum(_to_expr(c))
def count(c): return Count(_to_expr(c))
def count_star(): return CountStar()
def avg(c): return Average(_to_expr(c))
def count_distinct(c): return Count(_to_expr(c)).as_distinct()
def sum_distinct(c): return Sum(_to_expr(c)).as_distinct()
def avg_distinct(c): return Average(_to_expr(c)).as_distinct()
countDistinct = count_distinct
sumDistinct = sum_distinct
mean = avg
def min(c): return Min(_to_expr(c))
def max(c): return Max(_to_expr(c))
def first(c): return First(_to_expr(c))
def last(c): return Last(_to_expr(c))
def stddev(c): return StddevSamp(_to_expr(c))
def stddev_pop(c): return StddevPop(_to_expr(c))
def var_samp(c): return VarianceSamp(_to_expr(c))
def var_pop(c): return VariancePop(_to_expr(c))


def broadcast(df):
    """Mark a DataFrame as broadcastable for its next join (Spark's
    functions.broadcast; selects TpuBroadcastHashJoinExec in the planner)."""
    return df.hint("broadcast")


def udf(fn=None, return_type=None, compile: bool = True):
    """Python UDF: bytecode-compiled into the device plan when possible
    (ref udf-compiler), else row-based host fallback."""
    from ..udf import udf as _udf
    return _udf(fn, return_type, compile)


def columnar_udf(impl, *cols):
    """Hand-written columnar device UDF (ref RapidsUDF.java)."""
    from ..udf import ColumnarUDFExpr
    from .functions import _to_expr
    return ColumnarUDFExpr(impl, [_to_expr(c) for c in cols])


def df_udf(fn):
    """Dataframe-function UDF (ref DFUDFPlugin / sql-plugin-api
    functions.scala df_udf): the body is written in terms of Column
    expressions, so the call site inlines straight into the device plan —
    no bytecode compilation, no Python worker, full expression-level
    type checking and fusion."""
    def call(*cols):
        return fn(*[c if isinstance(c, Col) else lit(c) for c in cols])
    call.__name__ = getattr(fn, "__name__", "df_udf")
    return call


def pandas_udf(fn=None, return_type=None):
    """Vectorized pandas scalar UDF (ref GpuArrowEvalPythonExec role)."""
    if fn is None:
        return lambda f: pandas_udf(f, return_type)
    from ..udf.runtime import PandasUDF
    def call(*cols):
        return PandasUDF(fn, [_to_expr(c) for c in cols], return_type)
    call.__name__ = getattr(fn, "__name__", "pandas_udf")
    return call


# ---- round-3 breadth batch (ref GpuOverrides registry entries) -----------
def greatest(*cols) -> Col:
    return Col(E.Greatest(*[_to_expr(c) for c in cols]))
def least(*cols) -> Col:
    return Col(E.Least(*[_to_expr(c) for c in cols]))
def bitwise_not(c) -> Col: return Col(E.BitwiseNot(_to_expr(c)))
def shiftleft(c, n) -> Col:
    return Col(E.ShiftLeft(_to_expr(c), _to_expr(n)))
def shiftright(c, n) -> Col:
    return Col(E.ShiftRight(_to_expr(c), _to_expr(n)))
def shiftrightunsigned(c, n) -> Col:
    return Col(E.ShiftRightUnsigned(_to_expr(c), _to_expr(n)))
def hypot(a, b) -> Col: return Col(E.Hypot(_to_expr(a), _to_expr(b)))
def bround(c, scale: int = 0) -> Col:
    return Col(E.BRound(_to_expr(c), scale))
def asinh(c) -> Col: return Col(E.Asinh(_to_expr(c)))
def acosh(c) -> Col: return Col(E.Acosh(_to_expr(c)))
def atanh(c) -> Col: return Col(E.Atanh(_to_expr(c)))
def cot(c) -> Col: return Col(E.Cot(_to_expr(c)))
def last_day(c) -> Col: return Col(E.LastDay(_to_expr(c)))
def add_months(c, n) -> Col:
    return Col(E.AddMonths(_to_expr(c), _to_expr(n)))
def months_between(end, start, round_off: bool = True) -> Col:
    return Col(E.MonthsBetween(_to_expr(end), _to_expr(start), round_off))
def timestamp_seconds(c) -> Col:
    return Col(E.SecondsToTimestamp(_to_expr(c)))
def timestamp_millis(c) -> Col:
    return Col(E.MillisToTimestamp(_to_expr(c)))
def timestamp_micros(c) -> Col:
    return Col(E.MicrosToTimestamp(_to_expr(c)))
def to_unix_timestamp(c, fmt: str = "yyyy-MM-dd HH:mm:ss") -> Col:
    return Col(E.ToUnixTimestamp(_to_expr(c), fmt))
def unix_timestamp(c, fmt: str = "yyyy-MM-dd HH:mm:ss") -> Col:
    return Col(E.UnixTimestamp(_to_expr(c), fmt))
def from_unixtime(c, fmt: str = "yyyy-MM-dd HH:mm:ss") -> Col:
    return Col(E.FromUnixTime(_to_expr(c), fmt))
def date_format(c, fmt: str) -> Col:
    return Col(E.DateFormatClass(_to_expr(c), fmt))
def trunc(c, fmt: str) -> Col: return Col(E.TruncDate(_to_expr(c), fmt))
def ascii(c) -> Col: return Col(E.Ascii(_to_expr(c)))
def chr_(c) -> Col: return Col(E.Chr(_to_expr(c)))
def bit_length(c) -> Col: return Col(E.BitLength(_to_expr(c)))
def octet_length(c) -> Col: return Col(E.OctetLength(_to_expr(c)))
def instr(c, substr: str) -> Col:
    return Col(E.StringInstr(_to_expr(c), _to_expr(substr)))
def translate(c, src: str, dst: str) -> Col:
    return Col(E.StringTranslate(_to_expr(c), _to_expr(src),
                                 _to_expr(dst)))
def concat_ws(sep, *cols) -> Col:
    return Col(E.ConcatWs(_to_expr(sep), *[_to_expr(c) for c in cols]))
def format_number(c, d) -> Col:
    return Col(E.FormatNumber(_to_expr(c), _to_expr(d)))


def collect_list(c):
    from ..exprs.aggregates import CollectList
    return CollectList(_to_expr(c))
def collect_set(c):
    from ..exprs.aggregates import CollectSet
    return CollectSet(_to_expr(c))
def min_by(c, ordering):
    from ..exprs.aggregates import MinBy
    return MinBy(_to_expr(c), _to_expr(ordering))
def max_by(c, ordering):
    from ..exprs.aggregates import MaxBy
    return MaxBy(_to_expr(c), _to_expr(ordering))
def percentile(c, p: float):
    from ..exprs.aggregates import Percentile
    return Percentile(_to_expr(c), p)
