"""Logical data types and the type-support signature (TypeSig) machinery.

TPU-native re-design of the reference's type system:
  * Spark SQL data types  -> reference sql-plugin/.../TypeChecks.scala (TypeSig:168,543)
  * cudf DType mapping    -> reference GpuColumnVector.java:523 (toRapidsOrNull)

On TPU the physical representation is a JAX array per column plus a validity
mask. Types that XLA cannot hold natively in a dense array (strings, binary,
decimal128) are represented host-side (Arrow) and are tagged accordingly so the
planner can schedule per-expression CPU fallback — the same role TypeSig plays
in the reference's GpuOverrides tagging pass.
"""
from __future__ import annotations

import dataclasses
from typing import FrozenSet, Iterable, Optional, Tuple, Union

import numpy as np

__all__ = [
    "DataType", "IntegerType", "FractionalType", "BOOL", "INT8", "INT16",
    "INT32", "INT64", "FLOAT32", "FLOAT64", "STRING", "BINARY", "DATE",
    "TIMESTAMP", "NULLTYPE", "DECIMAL64", "DecimalType", "DecimalText",
    "ArrayType",
    "StructType", "StructField", "MapType", "TypeSig", "TypeEnum",
    "from_arrow", "to_arrow", "from_numpy_dtype",
]


class DataType:
    """Base logical type. Immutable and hashable."""

    #: name used in schemas / explain output
    name: str = "?"
    #: numpy dtype used for the device buffer, or None if host-only
    np_dtype: Optional[np.dtype] = None

    def __repr__(self) -> str:  # pragma: no cover - trivial
        return self.name

    def __eq__(self, other) -> bool:
        return type(self) is type(other) and self.name == getattr(other, "name", None)

    def __hash__(self) -> int:
        return hash((type(self).__name__, self.name))

    @property
    def device_backed(self) -> bool:
        """True if values of this type live in an HBM jax.Array."""
        return self.np_dtype is not None

    @property
    def default_value(self):
        """Fill value used for padding / invalid slots."""
        if self.np_dtype is None:
            return None
        if np.issubdtype(self.np_dtype, np.floating):
            return self.np_dtype.type(0)
        if self.np_dtype == np.bool_:
            return False
        return self.np_dtype.type(0)


class _Simple(DataType):
    def __init__(self, name: str, np_dtype):
        self.name = name
        self.np_dtype = np.dtype(np_dtype) if np_dtype is not None else None


class IntegerType(_Simple):
    pass


class FractionalType(_Simple):
    pass


BOOL = _Simple("boolean", np.bool_)
INT8 = IntegerType("tinyint", np.int8)
INT16 = IntegerType("smallint", np.int16)
INT32 = IntegerType("int", np.int32)
INT64 = IntegerType("bigint", np.int64)
FLOAT32 = FractionalType("float", np.float32)
FLOAT64 = FractionalType("double", np.float64)
#: days since epoch, int32 on device (matches Spark DateType physical rep)
DATE = _Simple("date", np.int32)
#: microseconds since epoch UTC, int64 on device (Spark TimestampType)
TIMESTAMP = _Simple("timestamp", np.int64)
#: host-only types (Arrow-backed); planner schedules CPU fallback or
#: dictionary-encodes to device
STRING = _Simple("string", None)
BINARY = _Simple("binary", None)
NULLTYPE = _Simple("void", None)


class DecimalType(DataType):
    """Decimal held as its UNSCALED value in int64 lanes on the device,
    for every declared precision up to 38.

    The reference's decimal128 path is cudf's 128-bit columns
    (DecimalUtils JNI, SURVEY.md 2.12). The TPU has no native int128, so
    the engine stores the unscaled value in int64 lanes: exact for
    magnitudes up to ~9.2e18 unscaled (19 significant digits; every TPC
    money column fits).

    What runs on the lanes (exprs/decimal_rules.py has the rules; PR 28):
    Spark's result type for every operator (``DecimalPrecision``, with
    ``adjustPrecisionScale`` beyond 38 digits); add, subtract, multiply,
    compare, ``between`` and casts with both operands brought to one
    scale and HALF_UP wherever a scale is cut; an integer or SQL decimal
    literal typed by its own digits beside a decimal operand; SUM and AVG
    accumulated in three 10^12-base limbs, so 38-digit-wide running
    totals stay exact, AVG divided and rounded on the device as Spark
    rounds it (twice). Decimal divide, remainder and pmod run on the
    host engine, on Python ints (Spark's scale of a quotient soon leaves
    a lane).

    What does not fit 63 bits is never a wrapped number: a value at
    ingest (ColumnarBatch.from_arrow's checked cast), a product, a
    rescale or a finalized total that Spark's decimal(38) would hold
    raises ``DecimalOverflow``; a value with more digits than its
    declared type is NULL, as in Spark (non-ANSI). The host engine
    computes the same expressions on Python ints where int64 cannot, so
    it returns Spark's number there. No int128 emulation, no ANSI
    mode."""

    def __init__(self, precision: int = 10, scale: int = 0):
        if precision < 1 or precision > 38:
            raise ValueError(f"bad decimal precision {precision}")
        self.precision = precision
        self.scale = scale
        self.name = f"decimal({precision},{scale})"
        self.np_dtype = np.dtype(np.int64)

    def __eq__(self, other):
        return (isinstance(other, DecimalType) and other.precision == self.precision
                and other.scale == self.scale)

    def __hash__(self):
        return hash(("decimal", self.precision, self.scale))


DECIMAL64 = DecimalType(18, 2)


class DecimalText(float):
    """A SQL numeric literal written with a point and no exponent
    (``0.05``): Spark types it ``decimal(2,2)``. It is kept as the double
    it always was here, with its text beside it, and becomes a decimal
    literal only where it meets a decimal operand
    (exprs/base.py:coerce_decimal_literals): everywhere else it behaves,
    prints and keys as the float."""

    def __new__(cls, value, text=None):
        self = float.__new__(cls, value)
        self.text = str(value) if text is None else text
        return self

    def __reduce__(self):
        return (DecimalText, (float(self), self.text))


@dataclasses.dataclass(frozen=True)
class StructField:
    name: str
    dtype: DataType
    nullable: bool = True


class StructType(DataType):
    def __init__(self, fields: Iterable[StructField]):
        self.fields: Tuple[StructField, ...] = tuple(fields)
        self.name = "struct<" + ",".join(f"{f.name}:{f.dtype.name}" for f in self.fields) + ">"
        self.np_dtype = None

    def field_names(self):
        return [f.name for f in self.fields]

    def index_of(self, name: str) -> int:
        for i, f in enumerate(self.fields):
            if f.name == name:
                return i
        raise KeyError(name)

    def __eq__(self, other):
        return isinstance(other, StructType) and other.fields == self.fields

    def __hash__(self):
        return hash(("struct", self.fields))


class ArrayType(DataType):
    def __init__(self, element: DataType, contains_null: bool = True):
        self.element = element
        self.contains_null = contains_null
        self.name = f"array<{element.name}>"
        self.np_dtype = None  # list columns carry offsets + child buffers

    def __eq__(self, other):
        return isinstance(other, ArrayType) and other.element == self.element

    def __hash__(self):
        return hash(("array", self.element))


class MapType(DataType):
    def __init__(self, key: DataType, value: DataType):
        self.key = key
        self.value = value
        self.name = f"map<{key.name},{value.name}>"
        self.np_dtype = None

    def __eq__(self, other):
        return isinstance(other, MapType) and other.key == self.key and other.value == self.value

    def __hash__(self):
        return hash(("map", self.key, self.value))


# ---------------------------------------------------------------------------
# TypeSig: declarative per-operator type-support matrix
# (reference TypeChecks.scala TypeSig:168; used by RapidsMeta tagging)
# ---------------------------------------------------------------------------

class TypeEnum:
    BOOLEAN = "BOOLEAN"
    BYTE = "BYTE"
    SHORT = "SHORT"
    INT = "INT"
    LONG = "LONG"
    FLOAT = "FLOAT"
    DOUBLE = "DOUBLE"
    DATE = "DATE"
    TIMESTAMP = "TIMESTAMP"
    STRING = "STRING"
    BINARY = "BINARY"
    DECIMAL = "DECIMAL"
    NULL = "NULL"
    ARRAY = "ARRAY"
    MAP = "MAP"
    STRUCT = "STRUCT"

    ALL = frozenset({BOOLEAN, BYTE, SHORT, INT, LONG, FLOAT, DOUBLE, DATE,
                     TIMESTAMP, STRING, BINARY, DECIMAL, NULL, ARRAY, MAP, STRUCT})


def _enum_of(dt: DataType) -> str:
    if isinstance(dt, DecimalType):
        return TypeEnum.DECIMAL
    if isinstance(dt, ArrayType):
        return TypeEnum.ARRAY
    if isinstance(dt, MapType):
        return TypeEnum.MAP
    if isinstance(dt, StructType):
        return TypeEnum.STRUCT
    return {
        "boolean": TypeEnum.BOOLEAN, "tinyint": TypeEnum.BYTE,
        "smallint": TypeEnum.SHORT, "int": TypeEnum.INT, "bigint": TypeEnum.LONG,
        "float": TypeEnum.FLOAT, "double": TypeEnum.DOUBLE, "date": TypeEnum.DATE,
        "timestamp": TypeEnum.TIMESTAMP, "string": TypeEnum.STRING,
        "binary": TypeEnum.BINARY, "void": TypeEnum.NULL,
    }[dt.name]


class TypeSig:
    """A set of supported type enums with optional nested-type set and notes.

    Mirrors reference TypeChecks.scala TypeSig (supports ``+`` union,
    ``nested()``, psNote-style notes); consumed by the planner's tagging pass
    and by the supported-ops doc generator.
    """

    def __init__(self, initial: Union[Iterable[str], FrozenSet[str]] = (),
                 nested: Union[Iterable[str], FrozenSet[str]] = (),
                 notes: Optional[dict] = None, max_decimal_precision: int = 38):
        self.types: FrozenSet[str] = frozenset(initial)
        self.nested_types: FrozenSet[str] = frozenset(nested)
        self.notes = dict(notes or {})
        self.max_decimal_precision = max_decimal_precision

    # -- constructors ------------------------------------------------------
    @staticmethod
    def none() -> "TypeSig":
        return TypeSig()

    # -- algebra -----------------------------------------------------------
    def __add__(self, other: "TypeSig") -> "TypeSig":
        return TypeSig(self.types | other.types, self.nested_types | other.nested_types,
                       {**self.notes, **other.notes},
                       max(self.max_decimal_precision, other.max_decimal_precision))

    def __sub__(self, other: "TypeSig") -> "TypeSig":
        return TypeSig(self.types - other.types, self.nested_types - other.nested_types,
                       self.notes, self.max_decimal_precision)

    def nested(self) -> "TypeSig":
        """Allow all currently-supported types to also appear nested."""
        return TypeSig(self.types, self.types | self.nested_types, self.notes,
                       self.max_decimal_precision)

    def with_psnote(self, type_enum: str, note: str) -> "TypeSig":
        new = TypeSig(self.types | {type_enum}, self.nested_types, self.notes,
                      self.max_decimal_precision)
        new.notes[type_enum] = note
        return new

    # -- checks ------------------------------------------------------------
    def _check_enum(self, enum: str, nested: bool) -> Optional[str]:
        allowed = self.nested_types if nested else self.types
        if enum not in allowed:
            where = "nested " if nested else ""
            return f"{where}{enum} is not supported"
        return None

    def reason_not_supported(self, dt: DataType, nested: bool = False) -> Optional[str]:
        enum = _enum_of(dt)
        r = self._check_enum(enum, nested)
        if r is not None:
            return r
        if isinstance(dt, DecimalType) and dt.precision > self.max_decimal_precision:
            return (f"decimal precision {dt.precision} exceeds max supported "
                    f"{self.max_decimal_precision}")
        if isinstance(dt, ArrayType):
            return self.reason_not_supported(dt.element, nested=True)
        if isinstance(dt, StructType):
            for f in dt.fields:
                r = self.reason_not_supported(f.dtype, nested=True)
                if r is not None:
                    return r
        if isinstance(dt, MapType):
            return (self.reason_not_supported(dt.key, nested=True)
                    or self.reason_not_supported(dt.value, nested=True))
        return None

    def is_supported(self, dt: DataType) -> bool:
        return self.reason_not_supported(dt) is None


# Common signatures (names follow reference TypeSig object members)
def _sig(*enums: str) -> TypeSig:
    return TypeSig(enums)


commonCudfTypes = _sig(TypeEnum.BOOLEAN, TypeEnum.BYTE, TypeEnum.SHORT, TypeEnum.INT,
                      TypeEnum.LONG, TypeEnum.FLOAT, TypeEnum.DOUBLE, TypeEnum.DATE,
                      TypeEnum.TIMESTAMP, TypeEnum.STRING)
integral = _sig(TypeEnum.BYTE, TypeEnum.SHORT, TypeEnum.INT, TypeEnum.LONG)
fp = _sig(TypeEnum.FLOAT, TypeEnum.DOUBLE)
numeric = integral + fp + _sig(TypeEnum.DECIMAL)
numericAndInterval = numeric
comparable = numeric + _sig(TypeEnum.BOOLEAN, TypeEnum.DATE, TypeEnum.TIMESTAMP,
                            TypeEnum.STRING)
orderable = comparable + _sig(TypeEnum.NULL)
all_types = TypeSig(TypeEnum.ALL, TypeEnum.ALL)
# device-resident types on TPU (dense jax arrays)
tpuNative = _sig(TypeEnum.BOOLEAN, TypeEnum.BYTE, TypeEnum.SHORT, TypeEnum.INT,
                 TypeEnum.LONG, TypeEnum.FLOAT, TypeEnum.DOUBLE, TypeEnum.DATE,
                 TypeEnum.TIMESTAMP, TypeEnum.DECIMAL)
hostOnly = _sig(TypeEnum.STRING, TypeEnum.BINARY, TypeEnum.ARRAY, TypeEnum.MAP,
                TypeEnum.STRUCT)


# ---------------------------------------------------------------------------
# Arrow / numpy interop
# ---------------------------------------------------------------------------

def from_numpy_dtype(dt) -> DataType:
    dt = np.dtype(dt)
    mapping = {
        np.dtype(np.bool_): BOOL, np.dtype(np.int8): INT8, np.dtype(np.int16): INT16,
        np.dtype(np.int32): INT32, np.dtype(np.int64): INT64,
        np.dtype(np.float32): FLOAT32, np.dtype(np.float64): FLOAT64,
    }
    if dt in mapping:
        return mapping[dt]
    if dt.kind in ("U", "S", "O"):
        return STRING
    if dt.kind == "M":  # datetime64
        return TIMESTAMP
    raise TypeError(f"unsupported numpy dtype {dt}")


def from_arrow(at) -> DataType:
    import pyarrow as pa
    if pa.types.is_boolean(at):
        return BOOL
    if pa.types.is_int8(at):
        return INT8
    if pa.types.is_int16(at):
        return INT16
    if pa.types.is_int32(at):
        return INT32
    if pa.types.is_int64(at):
        return INT64
    if pa.types.is_float32(at):
        return FLOAT32
    if pa.types.is_float64(at):
        return FLOAT64
    if pa.types.is_date32(at):
        return DATE
    if pa.types.is_timestamp(at):
        return TIMESTAMP
    if pa.types.is_string(at) or pa.types.is_large_string(at):
        return STRING
    if pa.types.is_binary(at) or pa.types.is_large_binary(at):
        return BINARY
    if pa.types.is_decimal(at):
        return DecimalType(at.precision, at.scale)
    if pa.types.is_list(at) or pa.types.is_large_list(at):
        return ArrayType(from_arrow(at.value_type))
    if pa.types.is_struct(at):
        return StructType(StructField(f.name, from_arrow(f.type), f.nullable)
                          for f in at)
    if pa.types.is_map(at):
        return MapType(from_arrow(at.key_type), from_arrow(at.item_type))
    if pa.types.is_null(at):
        return NULLTYPE
    raise TypeError(f"unsupported arrow type {at}")


def to_arrow(dt: DataType):
    import pyarrow as pa
    m = {"boolean": pa.bool_(), "tinyint": pa.int8(), "smallint": pa.int16(),
         "int": pa.int32(), "bigint": pa.int64(), "float": pa.float32(),
         "double": pa.float64(), "date": pa.date32(),
         "timestamp": pa.timestamp("us", tz="UTC"), "string": pa.string(),
         "binary": pa.binary(), "void": pa.null()}
    if dt.name in m:
        return m[dt.name]
    if isinstance(dt, DecimalType):
        return pa.decimal128(dt.precision, dt.scale)
    if isinstance(dt, ArrayType):
        return pa.list_(to_arrow(dt.element))
    if isinstance(dt, StructType):
        return pa.struct([pa.field(f.name, to_arrow(f.dtype), f.nullable)
                          for f in dt.fields])
    if isinstance(dt, MapType):
        return pa.map_(to_arrow(dt.key), to_arrow(dt.value))
    raise TypeError(f"unsupported type {dt}")


class Schema:
    """Ordered named, typed columns."""

    def __init__(self, fields: Iterable[StructField]):
        self.fields: Tuple[StructField, ...] = tuple(fields)
        self._index = {f.name: i for i, f in enumerate(self.fields)}

    @staticmethod
    def of(**kwargs) -> "Schema":
        return Schema(StructField(k, v) for k, v in kwargs.items())

    def __len__(self):
        return len(self.fields)

    def __iter__(self):
        return iter(self.fields)

    def __getitem__(self, key):
        if isinstance(key, int):
            return self.fields[key]
        return self.fields[self._index[key]]

    def index_of(self, name: str) -> int:
        return self._index[name]

    def names(self):
        return [f.name for f in self.fields]

    def types(self):
        return [f.dtype for f in self.fields]

    def __repr__(self):
        return "Schema(" + ", ".join(f"{f.name}:{f.dtype.name}" for f in self.fields) + ")"

    def __eq__(self, other):
        return isinstance(other, Schema) and self.fields == other.fields
