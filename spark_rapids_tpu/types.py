"""Spark-SQL-compatible data type system mapped onto TPU/XLA dtypes.

The reference accelerator inherits Catalyst's type system and checks per-op type
support via ``GpuOverrides.areAllSupportedTypes`` (reference:
``sql-plugin/src/main/scala/com/nvidia/spark/rapids/GpuOverrides.scala:387``).
We reproduce that surface as a small, standalone type lattice whose device
representation is explicit: every type knows the ``jnp`` dtype its column data
uses on the TPU, and whether it is fixed-width (directly vectorizable) or
variable-width (strings: offsets + byte payload, Arrow layout).

Dates are int32 days-since-epoch and timestamps int64 microseconds-since-epoch,
matching Spark's internal representation so differential tests can compare raw
values bit-for-bit.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import numpy as np


@dataclasses.dataclass(frozen=True)
class DataType:
    """Base class for all SQL data types."""

    #: Short name used in explain output and config keys.
    name: str = dataclasses.field(default="", init=False)

    @property
    def is_numeric(self) -> bool:
        return False

    @property
    def is_integral(self) -> bool:
        return False

    @property
    def is_floating(self) -> bool:
        return False

    @property
    def is_fixed_width(self) -> bool:
        """True when one value is one machine scalar on device."""
        return True

    @property
    def np_dtype(self) -> np.dtype:
        raise NotImplementedError(self)

    def __str__(self) -> str:  # pragma: no cover - trivial
        return self.name


class NullType(DataType):
    name = "null"

    @property
    def np_dtype(self) -> np.dtype:
        # Null literals are carried as int8 zeros with all-false validity.
        return np.dtype(np.int8)


class BooleanType(DataType):
    name = "boolean"

    @property
    def np_dtype(self) -> np.dtype:
        return np.dtype(np.bool_)


class NumericType(DataType):
    @property
    def is_numeric(self) -> bool:
        return True


class IntegralType(NumericType):
    @property
    def is_integral(self) -> bool:
        return True


class FractionalType(NumericType):
    @property
    def is_floating(self) -> bool:
        return True


class ByteType(IntegralType):
    name = "tinyint"

    @property
    def np_dtype(self) -> np.dtype:
        return np.dtype(np.int8)


class ShortType(IntegralType):
    name = "smallint"

    @property
    def np_dtype(self) -> np.dtype:
        return np.dtype(np.int16)


class IntegerType(IntegralType):
    name = "int"

    @property
    def np_dtype(self) -> np.dtype:
        return np.dtype(np.int32)


class LongType(IntegralType):
    name = "bigint"

    @property
    def np_dtype(self) -> np.dtype:
        return np.dtype(np.int64)


class FloatType(FractionalType):
    name = "float"

    @property
    def np_dtype(self) -> np.dtype:
        return np.dtype(np.float32)


class DoubleType(FractionalType):
    name = "double"

    @property
    def np_dtype(self) -> np.dtype:
        return np.dtype(np.float64)


class StringType(DataType):
    name = "string"

    @property
    def is_fixed_width(self) -> bool:
        return False

    @property
    def np_dtype(self) -> np.dtype:
        # Byte payload dtype; the offsets companion array is int32.
        return np.dtype(np.uint8)


class DateType(DataType):
    """Days since unix epoch, int32 — Spark's internal date representation."""

    name = "date"

    @property
    def np_dtype(self) -> np.dtype:
        return np.dtype(np.int32)


class TimestampType(DataType):
    """Microseconds since unix epoch, int64 — Spark's internal representation."""

    name = "timestamp"

    @property
    def np_dtype(self) -> np.dtype:
        return np.dtype(np.int64)


@dataclasses.dataclass(frozen=True)
class ArrayType(DataType):
    """ARRAY<element>. Device layout is padded-ragged (TPU-native): a
    ``[capacity, max_len]`` element matrix + per-element validity + an
    int32 length lane, instead of cudf's offsets+child (the reference
    reaches arrays via ``complexTypeExtractors.scala`` GetArrayItem and
    ``GpuGenerateExec.scala:101`` explode). Padding keeps every row the
    same machine shape, so gathers/filters/joins move arrays exactly like
    fixed-width scalars — no ragged re-layout inside jit."""

    element_type: "DataType" = dataclasses.field(default=None)
    contains_null: bool = True

    @property
    def name(self) -> str:  # type: ignore[override]
        return f"array<{self.element_type.name}>"

    @property
    def is_fixed_width(self) -> bool:
        return False

    @property
    def np_dtype(self) -> np.dtype:
        return self.element_type.np_dtype


@dataclasses.dataclass(frozen=True)
class StructType(DataType):
    """STRUCT<f1: t1, ...>. Device layout is column-shredded: one child
    DeviceColumn per field plus a struct-level validity lane, so struct
    columns cost nothing beyond their fields."""

    fields: tuple = dataclasses.field(default=None)  # tuple[StructField]

    def __init__(self, fields):
        object.__setattr__(self, "fields", tuple(fields))

    @property
    def name(self) -> str:  # type: ignore[override]
        inner = ",".join(f"{f.name}:{f.data_type.name}" for f in self.fields)
        return f"struct<{inner}>"

    @property
    def is_fixed_width(self) -> bool:
        return False

    def field_index(self, name: str) -> int:
        for i, f in enumerate(self.fields):
            if f.name == name:
                return i
        raise KeyError(name)


# Singletons, Spark style.
NULL = NullType()
BOOLEAN = BooleanType()
BYTE = ByteType()
SHORT = ShortType()
INT = IntegerType()
LONG = LongType()
FLOAT = FloatType()
DOUBLE = DoubleType()
STRING = StringType()
DATE = DateType()
TIMESTAMP = TimestampType()

_ALL_TYPES = [NULL, BOOLEAN, BYTE, SHORT, INT, LONG, FLOAT, DOUBLE, STRING, DATE, TIMESTAMP]
_BY_NAME = {t.name: t for t in _ALL_TYPES}

#: Types every device operator can handle unless it opts out — the analog of
#: ``GpuOverrides.isSupportedType`` (reference GpuOverrides.scala:374-385).
DEFAULT_DEVICE_TYPES = frozenset(
    [BOOLEAN, BYTE, SHORT, INT, LONG, FLOAT, DOUBLE, STRING, DATE, TIMESTAMP]
)


def device_supported(dt: DataType) -> bool:
    """Recursive device type-support check (areAllSupportedTypes analog).
    Arrays support fixed-width elements; structs support any supported
    non-nested field type."""
    if dt is NULL or dt in DEFAULT_DEVICE_TYPES:
        return True
    if isinstance(dt, ArrayType):
        return dt.element_type in DEFAULT_DEVICE_TYPES \
            and dt.element_type.is_fixed_width
    if isinstance(dt, StructType):
        return all(f.data_type in DEFAULT_DEVICE_TYPES for f in dt.fields)
    return False

_NUMERIC_ORDER = [BYTE, SHORT, INT, LONG, FLOAT, DOUBLE]


def type_by_name(name: str) -> DataType:
    return _BY_NAME[name]


def numeric_promote(a: DataType, b: DataType) -> DataType:
    """Spark's binary-arithmetic result type for two numeric inputs.

    NULL is the bottom of the lattice: a null literal (or compiled-UDF
    loop state that hasn't typed itself yet, udf/loops.py) adopts the
    other side's type, matching Spark's analyzer."""
    if a is NULL:
        return b
    if b is NULL:
        return a
    if not (a.is_numeric and b.is_numeric):
        raise TypeError(f"cannot promote {a} and {b}")
    return _NUMERIC_ORDER[max(_NUMERIC_ORDER.index(a), _NUMERIC_ORDER.index(b))]


@dataclasses.dataclass(frozen=True)
class StructField:
    name: str
    data_type: DataType
    nullable: bool = True


@dataclasses.dataclass(frozen=True)
class Schema:
    """An ordered list of named, typed, nullability-tracked columns."""

    fields: tuple

    def __init__(self, fields):
        object.__setattr__(self, "fields", tuple(fields))

    @property
    def names(self):
        return [f.name for f in self.fields]

    def __len__(self) -> int:
        return len(self.fields)

    def __iter__(self):
        return iter(self.fields)

    def __getitem__(self, key) -> StructField:
        if isinstance(key, int):
            return self.fields[key]
        for f in self.fields:
            if f.name == key:
                return f
        raise KeyError(key)

    def index_of(self, name: str) -> int:
        for i, f in enumerate(self.fields):
            if f.name == name:
                return i
        raise KeyError(name)

    def field_maybe(self, name: str) -> Optional[StructField]:
        for f in self.fields:
            if f.name == name:
                return f
        return None

    def __str__(self) -> str:
        inner = ", ".join(f"{f.name}: {f.data_type}" for f in self.fields)
        return f"[{inner}]"


def from_arrow_type(at) -> DataType:
    """Map a pyarrow DataType to ours (host interchange is Arrow throughout)."""
    import pyarrow as pa

    if pa.types.is_dictionary(at):
        # an encoding of the writer's table (a stored Arrow schema keeps
        # it), not a type of the engine: the column reads as its values
        return from_arrow_type(at.value_type)
    if pa.types.is_boolean(at):
        return BOOLEAN
    if pa.types.is_int8(at):
        return BYTE
    if pa.types.is_int16(at):
        return SHORT
    if pa.types.is_int32(at):
        return INT
    if pa.types.is_int64(at):
        return LONG
    if pa.types.is_float32(at):
        return FLOAT
    if pa.types.is_float64(at):
        return DOUBLE
    if pa.types.is_string(at) or pa.types.is_large_string(at):
        return STRING
    if pa.types.is_date32(at):
        return DATE
    if pa.types.is_timestamp(at):
        return TIMESTAMP
    if pa.types.is_null(at):
        return NULL
    if pa.types.is_list(at) or pa.types.is_large_list(at):
        return ArrayType(from_arrow_type(at.value_type),
                         at.value_field.nullable)
    if pa.types.is_struct(at):
        return StructType([StructField(f.name, from_arrow_type(f.type),
                                       f.nullable) for f in at])
    if pa.types.is_decimal(at):
        raise TypeError("decimal is not supported yet (matches reference v0.2 snapshot)")
    raise TypeError(f"unsupported arrow type {at}")


def to_arrow_type(dt: DataType):
    import pyarrow as pa

    if isinstance(dt, ArrayType):
        return pa.list_(pa.field("item", to_arrow_type(dt.element_type),
                                 dt.contains_null))
    if isinstance(dt, StructType):
        return pa.struct([pa.field(f.name, to_arrow_type(f.data_type),
                                   f.nullable) for f in dt.fields])
    mapping = {
        "null": pa.null(),
        "boolean": pa.bool_(),
        "tinyint": pa.int8(),
        "smallint": pa.int16(),
        "int": pa.int32(),
        "bigint": pa.int64(),
        "float": pa.float32(),
        "double": pa.float64(),
        "string": pa.string(),
        "date": pa.date32(),
        "timestamp": pa.timestamp("us"),
    }
    return mapping[dt.name]


def schema_from_arrow(arrow_schema) -> Schema:
    return Schema(
        [StructField(f.name, from_arrow_type(f.type), f.nullable) for f in arrow_schema]
    )


def schema_to_arrow(schema: Schema):
    import pyarrow as pa

    return pa.schema(
        [pa.field(f.name, to_arrow_type(f.data_type), f.nullable) for f in schema]
    )
