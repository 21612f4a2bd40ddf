"""Columnar vectors: host SoA arrays with Arrow interop.

Reference behavior: src/datatypes/src/vectors/ — a `Vector` is a typed,
nullable column. The TPU-first design keeps the canonical host representation
as numpy arrays (object arrays for strings) plus an optional validity bitmap,
so columns move to the device with zero reshaping; Arrow is the interchange
format (Parquet, Flight, IPC/WAL payloads).
"""

from __future__ import annotations

from typing import Any, Iterable, Optional, Sequence

import numpy as np
import pyarrow as pa

from . import data_type as dt
from .data_type import ConcreteDataType, from_arrow_type


def null_column(dtype: ConcreteDataType, n: int):
    """(data, all-false validity) pair for an absent/null column — the single
    place that knows the host representation of nulls per dtype."""
    npdt = dtype.np_dtype if dtype.np_dtype is not None else object
    if npdt == object:
        data = np.full(n, None, dtype=object)
    else:
        data = np.zeros(n, dtype=npdt)
    return data, np.zeros(n, dtype=bool)


def python_values(data: np.ndarray, nulls: Optional[np.ndarray]) -> list:
    """A column's values as Python objects, None where `nulls`: the loop
    is `ndarray.tolist`'s (int, float, bool, str out, never a numpy
    scalar), not one `.item()` a value."""
    if nulls is None or not nulls.any():
        return data.tolist()
    held = data.astype(object)
    held[nulls] = None
    return held.tolist()


class Vector:
    """A typed nullable column.

    data: np.ndarray — for String/Binary this is an object array; for
          timestamps an int64 array of ticks in the type's unit.
    validity: optional boolean np.ndarray, True = valid. None = all valid.
    """

    __slots__ = ("dtype", "data", "validity")

    def __init__(self, dtype: ConcreteDataType, data: np.ndarray,
                 validity: Optional[np.ndarray] = None):
        self.dtype = dtype
        self.data = data
        if validity is not None and validity.all():
            validity = None
        self.validity = validity

    # ---- constructors ----
    @staticmethod
    def from_pylist(values: Sequence[Any], dtype: ConcreteDataType) -> "Vector":
        if isinstance(values, np.ndarray) and values.dtype != object \
                and not (dtype.is_string or dtype.is_binary):
            # numeric ndarray fast path: no per-value cast, no nulls
            return Vector(dtype,
                          np.ascontiguousarray(values, dtype=dtype.np_dtype))
        if isinstance(values, np.ndarray) and values.dtype.kind == "U" \
                and dtype.is_string:
            # fixed-width unicode arrays (np.repeat of str lists) carry
            # no nulls; store as object for Arrow interop
            return Vector(dtype, values.astype(object))
        if isinstance(values, np.ndarray) and values.dtype == object \
                and dtype.is_string:
            # string object-array fast path: vectorized null scan, cast
            # only the (rare) non-str entries
            import pandas as pd
            isnull = pd.isnull(values)
            if not isnull.any():
                if all(type(v) is str for v in values[:64]):
                    data = values
                    if not all(type(v) is str for v in values):
                        data = np.array([v if type(v) is str else
                                         dtype.cast_value(v)
                                         for v in values], dtype=object)
                    return Vector(dtype, data)
            else:
                data = np.array([dtype.default_value() if m else
                                 (v if type(v) is str
                                  else dtype.cast_value(v))
                                 for v, m in zip(values, isnull)],
                                dtype=object)
                return Vector(dtype, data, ~isnull)
        n = len(values)
        if isinstance(values, list) and n and not dtype.is_string \
                and not dtype.is_binary and dtype.np_dtype is not None \
                and not any(v is None for v in values):
            # clean numeric lists convert at C speed; np.asarray silently
            # coerces None to NaN for float dtypes (no exception), so the
            # NULL scan above is mandatory — mixed non-None content still
            # raises and falls through to the validating per-value loop
            try:
                return Vector(dtype, np.asarray(values,
                                                dtype=dtype.np_dtype))
            except (ValueError, TypeError):
                pass
        validity = np.ones(n, dtype=bool)
        if dtype.is_string or dtype.is_binary:
            data = np.empty(n, dtype=object)
            for i, v in enumerate(values):
                if v is None:
                    validity[i] = False
                    data[i] = dtype.default_value()
                else:
                    data[i] = dtype.cast_value(v)
        else:
            np_dtype = dtype.np_dtype
            data = np.zeros(n, dtype=np_dtype)
            for i, v in enumerate(values):
                if v is None:
                    validity[i] = False
                else:
                    data[i] = dtype.cast_value(v)
        return Vector(dtype, data, None if validity.all() else validity)

    @staticmethod
    def from_numpy(arr: np.ndarray, dtype: ConcreteDataType,
                   validity: Optional[np.ndarray] = None) -> "Vector":
        if not (dtype.is_string or dtype.is_binary):
            arr = np.ascontiguousarray(arr, dtype=dtype.np_dtype)
        return Vector(dtype, arr, validity)

    @staticmethod
    def constant(value: Any, n: int, dtype: ConcreteDataType) -> "Vector":
        if value is None:
            return Vector.nulls(n, dtype)
        v = dtype.cast_value(value)
        if dtype.is_string or dtype.is_binary:
            data = np.empty(n, dtype=object)
            data[:] = v
        else:
            data = np.full(n, v, dtype=dtype.np_dtype)
        return Vector(dtype, data)

    @staticmethod
    def nulls(n: int, dtype: ConcreteDataType) -> "Vector":
        if dtype.is_string or dtype.is_binary:
            data = np.empty(n, dtype=object)
            data[:] = dtype.default_value()
        else:
            data = np.zeros(n, dtype=dtype.np_dtype)
        return Vector(dtype, data, np.zeros(n, dtype=bool))

    @staticmethod
    def from_arrow(arr: pa.Array | pa.ChunkedArray) -> "Vector":
        if isinstance(arr, pa.ChunkedArray):
            arr = arr.combine_chunks()
        if pa.types.is_dictionary(arr.type):
            arr = arr.dictionary_decode()
        dtype = from_arrow_type(arr.type)
        n = len(arr)
        validity = None
        if arr.null_count:
            validity = np.asarray(arr.is_valid())
        if dtype.is_string or dtype.is_binary:
            # zero_copy_only=False yields an object ndarray with None at
            # nulls — filled vectorized (the per-value loop cost ~0.4s/2M)
            data = arr.to_numpy(zero_copy_only=False)
            if data.dtype != object:
                data = data.astype(object)
            else:
                data = data.copy()
            if validity is not None:
                data[~validity] = dtype.default_value()
        elif dtype.is_timestamp:
            data = np.asarray(arr.cast(pa.int64()).fill_null(0), dtype=np.int64)
        elif dtype is dt.DATE:
            data = np.asarray(arr.cast(pa.int32()).fill_null(0), dtype=np.int32)
        else:
            if arr.null_count:
                arr = arr.fill_null(dtype.default_value())
            data = np.asarray(arr)
            if dtype.np_dtype is not None:
                data = data.astype(dtype.np_dtype, copy=False)
        return Vector(dtype, data, validity)

    # ---- conversions ----
    def to_arrow(self) -> pa.Array:
        mask = None if self.validity is None else ~self.validity
        if self.dtype.is_string or self.dtype.is_binary:
            if isinstance(self.data, np.ndarray):
                # pa.array consumes object/<U ndarrays + mask at C speed;
                # the list() round trip costs ~0.5s per 2M rows
                return pa.array(self.data, type=self.dtype.pa_type,
                                mask=mask)
            vals = list(self.data)
            if mask is not None:
                vals = [None if m else v for v, m in zip(vals, mask)]
            return pa.array(vals, type=self.dtype.pa_type)
        if self.dtype.is_timestamp:
            base = pa.array(self.data.astype(np.int64), mask=mask)
            return base.cast(self.dtype.pa_type)
        if self.dtype is dt.DATE:
            base = pa.array(self.data.astype(np.int32), mask=mask)
            return base.cast(self.dtype.pa_type)
        return pa.array(self.data, type=self.dtype.pa_type, mask=mask)

    def to_pylist(self) -> list:
        data = self.data
        if isinstance(data, np.ndarray) and data.dtype != object and not (
                self.dtype.is_boolean and data.dtype.kind != "b"):
            return python_values(
                data, None if self.validity is None else ~self.validity)
        if self.validity is None:
            if self.dtype.is_boolean:
                return [bool(v) for v in self.data]
            return [v.item() if isinstance(v, np.generic) else v for v in self.data]
        out = []
        for v, ok in zip(self.data, self.validity):
            if not ok:
                out.append(None)
            elif isinstance(v, np.generic):
                out.append(v.item())
            else:
                out.append(v)
        return out

    # ---- access / ops ----
    def __len__(self) -> int:
        return len(self.data)

    def get(self, i: int) -> Any:
        if self.validity is not None and not self.validity[i]:
            return None
        v = self.data[i]
        return v.item() if isinstance(v, np.generic) else v

    def is_null(self, i: int) -> bool:
        return self.validity is not None and not bool(self.validity[i])

    @property
    def null_count(self) -> int:
        return 0 if self.validity is None else int((~self.validity).sum())

    def slice(self, start: int, length: int) -> "Vector":
        v = None if self.validity is None else self.validity[start:start + length]
        return Vector(self.dtype, self.data[start:start + length], v)

    def take(self, indices: np.ndarray) -> "Vector":
        v = None if self.validity is None else self.validity[indices]
        return Vector(self.dtype, self.data[indices], v)

    def filter(self, mask: np.ndarray) -> "Vector":
        v = None if self.validity is None else self.validity[mask]
        return Vector(self.dtype, self.data[mask], v)

    def cast(self, target: ConcreteDataType) -> "Vector":
        if target == self.dtype:
            return self
        if target.is_string:
            data = np.empty(len(self), dtype=object)
            for i, v in enumerate(self.to_pylist()):
                data[i] = "" if v is None else str(v)
            return Vector(target, data, self.validity)
        if self.dtype.is_string or self.dtype.is_binary:
            return Vector.from_pylist(
                [None if v is None else target.cast_value(v) for v in self.to_pylist()],
                target)
        if self.dtype.is_timestamp and target.is_timestamp:
            sf, tf = self.dtype.time_unit.factor, target.time_unit.factor
            if tf >= sf:
                data = self.data * (tf // sf)
            else:
                data = self.data // (sf // tf)
            return Vector(target, data.astype(np.int64), self.validity)
        return Vector(target, self.data.astype(target.np_dtype), self.validity)

    @staticmethod
    def concat(vectors: Iterable["Vector"]) -> "Vector":
        vs = list(vectors)
        assert vs, "cannot concat zero vectors"
        dtype = vs[0].dtype
        data = np.concatenate([v.data for v in vs])
        if any(v.validity is not None for v in vs):
            validity = np.concatenate([
                v.validity if v.validity is not None else np.ones(len(v), dtype=bool)
                for v in vs])
        else:
            validity = None
        return Vector(dtype, data, validity)

    def __repr__(self) -> str:  # pragma: no cover
        return f"Vector<{self.dtype.name}>[{len(self)}]"


def compat_column(col_schema, n: int):
    """(data, validity) for a column absent from an old run/SST: filled
    from the column's DEFAULT constraint, else nulls (reference: schema
    read-compat matrices, src/storage/src/schema/compat.rs:611 — readers
    adapt old files to the current schema by synthesizing added columns).
    Raises for a non-nullable column with no default: the file is
    genuinely incompatible."""
    vec = col_schema.create_default_vector(n)
    if vec is None:
        from ..errors import StorageError
        raise StorageError(
            f"column {col_schema.name!r} is non-nullable with no default; "
            f"cannot read data written before it was added")
    return vec.data, vec.validity
