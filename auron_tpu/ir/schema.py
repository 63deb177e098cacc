"""Logical data types, fields and schemas.

Covers the Arrow-compatible type surface the reference converts from Spark
(NativeConverters.convertDataType, spark-extension/.../NativeConverters.scala:137):
null, boolean, int8/16/32/64, float32/64, decimal(p,s), utf8, binary,
date32, timestamp(us), plus nested list/map/struct.

On device (TPU), types map to:
- BOOL/INTs/FLOATs: the corresponding jnp dtype
- DECIMAL(p<=18, s): scaled int64 (unscaled value); p>18 (`is_wide_decimal`)
  is host-resident in the serial engine and two 64-bit words a value in the
  stage program (columnar/batch.py `DeviceDecimal128Column`)
- STRING/BINARY: fixed-width padded uint8 [capacity, width] + int32 lengths
- DATE32: int32 days since epoch; TIMESTAMP: int64 microseconds
- LIST/MAP/STRUCT: host-resident (hybrid execution), exploded on demand
"""

from __future__ import annotations

import enum
from dataclasses import dataclass, field
from typing import Optional, Tuple

import numpy as np


class TypeId(enum.IntEnum):
    NULL = 0
    BOOL = 1
    INT8 = 2
    INT16 = 3
    INT32 = 4
    INT64 = 5
    FLOAT32 = 6
    FLOAT64 = 7
    DECIMAL = 8
    STRING = 9
    BINARY = 10
    DATE32 = 11
    TIMESTAMP_US = 12
    LIST = 13
    MAP = 14
    STRUCT = 15


_NUMERIC = {
    TypeId.INT8, TypeId.INT16, TypeId.INT32, TypeId.INT64,
    TypeId.FLOAT32, TypeId.FLOAT64, TypeId.DECIMAL,
}
_INTEGRAL = {TypeId.INT8, TypeId.INT16, TypeId.INT32, TypeId.INT64}


@dataclass(frozen=True)
class DataType:
    id: TypeId
    precision: int = 0            # DECIMAL only
    scale: int = 0                # DECIMAL only
    children: Tuple["Field", ...] = ()   # LIST (1), MAP (2: key,value), STRUCT (n)

    # -- constructors -------------------------------------------------------
    @staticmethod
    def null() -> "DataType": return DataType(TypeId.NULL)
    @staticmethod
    def bool_() -> "DataType": return DataType(TypeId.BOOL)
    @staticmethod
    def int8() -> "DataType": return DataType(TypeId.INT8)
    @staticmethod
    def int16() -> "DataType": return DataType(TypeId.INT16)
    @staticmethod
    def int32() -> "DataType": return DataType(TypeId.INT32)
    @staticmethod
    def int64() -> "DataType": return DataType(TypeId.INT64)
    @staticmethod
    def float32() -> "DataType": return DataType(TypeId.FLOAT32)
    @staticmethod
    def float64() -> "DataType": return DataType(TypeId.FLOAT64)
    @staticmethod
    def decimal(precision: int, scale: int) -> "DataType":
        return DataType(TypeId.DECIMAL, precision=precision, scale=scale)
    @staticmethod
    def string() -> "DataType": return DataType(TypeId.STRING)
    @staticmethod
    def binary() -> "DataType": return DataType(TypeId.BINARY)
    @staticmethod
    def date32() -> "DataType": return DataType(TypeId.DATE32)
    @staticmethod
    def timestamp_us() -> "DataType": return DataType(TypeId.TIMESTAMP_US)
    @staticmethod
    def list_(value: "DataType") -> "DataType":
        return DataType(TypeId.LIST, children=(Field("item", value),))
    @staticmethod
    def map_(key: "DataType", value: "DataType") -> "DataType":
        return DataType(TypeId.MAP, children=(Field("key", key, nullable=False),
                                              Field("value", value)))
    @staticmethod
    def struct(fields: Tuple["Field", ...]) -> "DataType":
        return DataType(TypeId.STRUCT, children=tuple(fields))

    # -- predicates ---------------------------------------------------------
    @property
    def is_numeric(self) -> bool: return self.id in _NUMERIC
    @property
    def is_integral(self) -> bool: return self.id in _INTEGRAL
    @property
    def is_floating(self) -> bool:
        return self.id in (TypeId.FLOAT32, TypeId.FLOAT64)
    @property
    def is_stringlike(self) -> bool:
        return self.id in (TypeId.STRING, TypeId.BINARY)
    @property
    def is_nested(self) -> bool:
        return self.id in (TypeId.LIST, TypeId.MAP, TypeId.STRUCT)
    @property
    def is_decimal(self) -> bool: return self.id == TypeId.DECIMAL
    @property
    def is_wide_decimal(self) -> bool:
        """A decimal of 19-38 digits: its unscaled value does not fit one
        64-bit word.  THE place that says so."""
        return self.id == TypeId.DECIMAL and self.precision > 18
    @property
    def host_resident(self) -> bool:
        """The serial engine keeps it on the host (a `HostColumn`): nested
        values and wide decimals.  The stage program holds a wide decimal on
        the device (columnar/batch.py `stage_holds`)."""
        return self.is_nested or self.is_wide_decimal

    def numpy_dtype(self) -> np.dtype:
        """The host/device physical dtype for flat (non-string, non-nested)
        columns."""
        m = {
            TypeId.BOOL: np.bool_,
            TypeId.INT8: np.int8,
            TypeId.INT16: np.int16,
            TypeId.INT32: np.int32,
            TypeId.INT64: np.int64,
            TypeId.FLOAT32: np.float32,
            TypeId.FLOAT64: np.float64,
            TypeId.DECIMAL: np.int64,        # unscaled value (p<=18)
            TypeId.DATE32: np.int32,
            TypeId.TIMESTAMP_US: np.int64,
            TypeId.NULL: np.bool_,
        }
        if self.id not in m:
            raise TypeError(f"no flat physical dtype for {self}")
        return np.dtype(m[self.id])

    def __repr__(self) -> str:
        if self.id == TypeId.DECIMAL:
            return f"decimal({self.precision},{self.scale})"
        if self.id == TypeId.LIST:
            return f"list<{self.children[0].dtype!r}>"
        if self.id == TypeId.MAP:
            return f"map<{self.children[0].dtype!r},{self.children[1].dtype!r}>"
        if self.id == TypeId.STRUCT:
            inner = ", ".join(f"{f.name}:{f.dtype!r}" for f in self.children)
            return f"struct<{inner}>"
        return self.id.name.lower()


@dataclass(frozen=True)
class Field:
    name: str
    dtype: DataType
    nullable: bool = True

    def __repr__(self) -> str:
        n = "" if self.nullable else " not null"
        return f"{self.name}: {self.dtype!r}{n}"


@dataclass(frozen=True)
class Schema:
    fields: Tuple[Field, ...]

    def __post_init__(self):
        object.__setattr__(self, "fields", tuple(self.fields))

    @staticmethod
    def of(*fields: Field) -> "Schema":
        return Schema(tuple(fields))

    def __len__(self) -> int: return len(self.fields)
    def __iter__(self): return iter(self.fields)
    def __getitem__(self, i: int) -> Field: return self.fields[i]

    def names(self) -> Tuple[str, ...]:
        return tuple(f.name for f in self.fields)

    def index_of(self, name: str, case_sensitive: Optional[bool] = None) -> int:
        if case_sensitive is None:
            from auron_tpu.config import conf
            case_sensitive = conf.get("auron.case.sensitive")
        for i, f in enumerate(self.fields):
            if f.name == name or (not case_sensitive and f.name.lower() == name.lower()):
                return i
        raise KeyError(name)

    def field(self, name: str) -> Field:
        return self.fields[self.index_of(name)]

    def select(self, indices) -> "Schema":
        return Schema(tuple(self.fields[i] for i in indices))

    def rename(self, names) -> "Schema":
        assert len(names) == len(self.fields)
        return Schema(tuple(Field(n, f.dtype, f.nullable)
                            for n, f in zip(names, self.fields)))

    def concat(self, other: "Schema") -> "Schema":
        return Schema(self.fields + other.fields)

    def __repr__(self) -> str:
        return "Schema(" + ", ".join(repr(f) for f in self.fields) + ")"


# ---------------------------------------------------------------------------
# Arrow interop (pyarrow is the host-side columnar substrate).
# ---------------------------------------------------------------------------

def to_arrow_type(dt: DataType):
    import pyarrow as pa
    m = {
        TypeId.NULL: pa.null(), TypeId.BOOL: pa.bool_(),
        TypeId.INT8: pa.int8(), TypeId.INT16: pa.int16(),
        TypeId.INT32: pa.int32(), TypeId.INT64: pa.int64(),
        TypeId.FLOAT32: pa.float32(), TypeId.FLOAT64: pa.float64(),
        TypeId.STRING: pa.large_utf8(), TypeId.BINARY: pa.large_binary(),
        TypeId.DATE32: pa.date32(), TypeId.TIMESTAMP_US: pa.timestamp("us"),
    }
    if dt.id in m:
        return m[dt.id]
    if dt.id == TypeId.DECIMAL:
        return pa.decimal128(dt.precision, dt.scale)
    if dt.id == TypeId.LIST:
        return pa.large_list(to_arrow_type(dt.children[0].dtype))
    if dt.id == TypeId.MAP:
        return pa.map_(to_arrow_type(dt.children[0].dtype),
                       to_arrow_type(dt.children[1].dtype))
    if dt.id == TypeId.STRUCT:
        import pyarrow as pa
        return pa.struct([pa.field(f.name, to_arrow_type(f.dtype), f.nullable)
                          for f in dt.children])
    raise TypeError(f"cannot convert {dt} to arrow")


def from_arrow_type(t) -> DataType:
    import pyarrow as pa
    import pyarrow.types as pt
    if pt.is_null(t): return DataType.null()
    if pt.is_boolean(t): return DataType.bool_()
    if pt.is_int8(t): return DataType.int8()
    if pt.is_int16(t): return DataType.int16()
    if pt.is_int32(t): return DataType.int32()
    if pt.is_int64(t): return DataType.int64()
    if pt.is_uint8(t): return DataType.int16()
    if pt.is_uint16(t): return DataType.int32()
    if pt.is_uint32(t) or pt.is_uint64(t): return DataType.int64()
    if pt.is_float32(t): return DataType.float32()
    if pt.is_float64(t): return DataType.float64()
    if pt.is_decimal(t): return DataType.decimal(t.precision, t.scale)
    if pt.is_string(t) or pt.is_large_string(t): return DataType.string()
    if pt.is_binary(t) or pt.is_large_binary(t) or pt.is_fixed_size_binary(t):
        return DataType.binary()
    if pt.is_date32(t): return DataType.date32()
    if pt.is_date64(t): return DataType.timestamp_us()
    if pt.is_timestamp(t): return DataType.timestamp_us()
    if pt.is_list(t) or pt.is_large_list(t):
        return DataType.list_(from_arrow_type(t.value_type))
    if pt.is_map(t):
        return DataType.map_(from_arrow_type(t.key_type), from_arrow_type(t.item_type))
    if pt.is_struct(t):
        return DataType.struct(tuple(
            Field(t.field(i).name, from_arrow_type(t.field(i).type),
                  t.field(i).nullable) for i in range(t.num_fields)))
    raise TypeError(f"cannot convert arrow type {t}")


def to_arrow_schema(schema: Schema):
    import pyarrow as pa
    return pa.schema([pa.field(f.name, to_arrow_type(f.dtype), f.nullable)
                      for f in schema.fields])


def from_arrow_schema(aschema) -> Schema:
    return Schema(tuple(Field(f.name, from_arrow_type(f.type), f.nullable)
                        for f in aschema))
