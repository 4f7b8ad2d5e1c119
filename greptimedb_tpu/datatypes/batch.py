"""Host-side columnar batches and the host->device bridge.

The capability counterpart of the reference's `common-recordbatch` crate, but
the conversion policy is TPU-first (SURVEY.md §7 step 1):

- string/tag columns are dictionary-encoded on the host; only the int32 codes
  ship to the device,
- nulls become explicit validity masks (bool arrays), since XLA has no null
  semantics,
- batches are padded up to a bucket size so jit traces are reused across
  batches of different row counts (static shapes).
"""

from __future__ import annotations

from dataclasses import dataclass, field

import numpy as np
import pyarrow as pa

from greptimedb_tpu.datatypes.schema import Schema
from greptimedb_tpu.datatypes.types import ConcreteDataType


def bucket_size(n: int, *, minimum: int = 1024) -> int:
    """Round ``n`` up to a shape bucket (power of two) to bound the number of
    distinct compiled shapes. Mirrors the padding/bucketing policy named in
    SURVEY.md §7 hard-part (b)."""
    if n <= 0:
        return minimum
    b = minimum
    while b < n:
        b *= 2
    return b


@dataclass
class HostColumn:
    """One column: numpy values + validity. Strings stay as object arrays on
    the host; `codes`/`vocab` appear once dictionary-encoded."""

    name: str
    data_type: ConcreteDataType
    values: np.ndarray
    validity: np.ndarray | None = None  # None == all valid

    def __len__(self) -> int:
        return len(self.values)

    @property
    def valid_mask(self) -> np.ndarray:
        if self.validity is None:
            return np.ones(len(self.values), dtype=bool)
        return self.validity

    def to_arrow(self) -> pa.Array:
        mask = None if self.validity is None else ~self.validity
        if self.data_type.is_decimal():
            # float64 in memory -> exact decimal128 on the wire
            arr = pa.array(
                np.asarray(self.values, np.float64), pa.float64(), mask=mask
            )
            return arr.cast(self.data_type.to_arrow(), safe=False)
        return pa.array(self.values, type=self.data_type.to_arrow(), mask=mask)

    @staticmethod
    def from_arrow(name: str, arr: pa.Array | pa.ChunkedArray) -> "HostColumn":
        if isinstance(arr, pa.ChunkedArray):
            arr = arr.combine_chunks()
        if pa.types.is_dictionary(arr.type):
            arr = arr.cast(arr.type.value_type)
        dt = ConcreteDataType.from_arrow(arr.type)
        validity = None
        if arr.null_count:
            validity = np.asarray(arr.is_valid())
        if dt.is_string() or dt.id.value == "binary":
            # straight to an object array (a null reads None): a Python
            # list in between costs ten times the conversion
            values = arr.to_numpy(zero_copy_only=False)
        elif dt.is_decimal():
            arr = arr.cast(pa.float64())
            if arr.null_count:
                arr = arr.fill_null(0)
            values = np.asarray(arr)
        elif dt.is_timestamp():
            arr = arr.cast(pa.int64())
            if arr.null_count:
                arr = arr.fill_null(0)
            values = np.asarray(arr)
        elif dt.id.value == "date":
            arr = arr.cast(pa.int32())
            if arr.null_count:
                arr = arr.fill_null(0)
            values = np.asarray(arr).astype(np.int64)
        elif dt.is_interval():
            # normalize any duration unit to the type's int64-ms
            # representation (a duration("s") 5 must become 5000, and
            # even duration("ms") must land as int64, not timedelta64)
            arr = arr.cast(pa.duration("ms")).cast(pa.int64())
            if arr.null_count:
                arr = arr.fill_null(0)
            values = np.asarray(arr)
        else:
            if arr.null_count:
                arr = arr.fill_null(0)
            values = np.asarray(arr)
        return HostColumn(name, dt, values, validity)


class Dictionary:
    """Incremental string -> int32 code dictionary (one per tag column).

    The device never sees strings: tag values are interned here at ingest and
    group-by/series identification runs over the codes (the TPU analog of the
    reference's mcmp primary-key encoding, /root/reference/src/mito2/src/
    row_converter.rs:54)."""

    def __init__(self, values: list[str] | None = None):
        self._values: list[str] = []
        self._codes: dict[str, int] = {}
        if values:
            for v in values:
                self.intern(v)

    def __len__(self) -> int:
        return len(self._values)

    def intern(self, value: str) -> int:
        code = self._codes.get(value)
        if code is None:
            code = len(self._values)
            self._codes[value] = code
            self._values.append(value)
        return code

    def intern_array(self, values: np.ndarray) -> np.ndarray:
        """Vectorized interning: hash-based dictionary encode once per
        batch (Arrow, O(n)), dict work only on the (few) distinct values,
        then a single np.take to expand. np.unique's sort-based O(n log n)
        string compares are the fallback for non-string payloads."""
        try:
            enc = pa.array(values, type=pa.string()).dictionary_encode()
            uniq = enc.dictionary.to_pylist()
            inv = enc.indices.to_numpy(zero_copy_only=False)
        except (pa.lib.ArrowInvalid, pa.lib.ArrowTypeError):
            uniq, inv = np.unique(values, return_inverse=True)
        codes = self._codes
        uniq_codes = np.empty(len(uniq), dtype=np.int32)
        for i, v in enumerate(uniq):
            c = codes.get(v)
            if c is None:
                c = len(self._values)
                codes[v] = c
                self._values.append(v)
            uniq_codes[i] = c
        return uniq_codes[np.asarray(inv, np.int64)]

    def lookup(self, value: str) -> int | None:
        return self._codes.get(value)

    def decode(self, code: int) -> str:
        return self._values[code]

    def decode_array(self, codes: np.ndarray) -> np.ndarray:
        vals = np.asarray(self._values, dtype=object)
        return vals[codes]

    @property
    def values(self) -> list[str]:
        return self._values


@dataclass
class HostBatch:
    """A schema'd bundle of HostColumns (host-side RecordBatch)."""

    schema: Schema
    columns: list[HostColumn]
    num_rows: int = field(init=False)

    def __post_init__(self):
        self.num_rows = len(self.columns[0]) if self.columns else 0
        for c in self.columns:
            assert len(c) == self.num_rows, "ragged batch"

    def column(self, name: str) -> HostColumn:
        return self.columns[self.schema.column_index(name)]

    def to_arrow(self) -> pa.Table:
        return pa.table(
            [c.to_arrow() for c in self.columns], schema=self.schema.to_arrow()
        )

    @staticmethod
    def from_arrow(table: pa.Table, schema: Schema | None = None) -> "HostBatch":
        if schema is None:
            schema = Schema.from_arrow(table.schema)
        cols = [
            HostColumn.from_arrow(name, table.column(name))
            for name in table.column_names
        ]
        return HostBatch(schema, cols)

    def select(self, names: list[str]) -> "HostBatch":
        return HostBatch(self.schema.project(names), [self.column(n) for n in names])

    def take(self, indices: np.ndarray) -> "HostBatch":
        cols = [
            HostColumn(
                c.name,
                c.data_type,
                c.values[indices],
                None if c.validity is None else c.validity[indices],
            )
            for c in self.columns
        ]
        return HostBatch(self.schema, cols)

    @staticmethod
    def concat(batches: list["HostBatch"]) -> "HostBatch":
        assert batches, "cannot concat zero batches"
        schema = batches[0].schema
        cols = []
        for i, cs in enumerate(batches[0].columns):
            vals = np.concatenate([b.columns[i].values for b in batches])
            if any(b.columns[i].validity is not None for b in batches):
                validity = np.concatenate(
                    [b.columns[i].valid_mask for b in batches]
                )
            else:
                validity = None
            cols.append(HostColumn(cs.name, cs.data_type, vals, validity))
        return HostBatch(schema, cols)


def pad_to(values: np.ndarray, n: int, fill=0) -> np.ndarray:
    """Pad a 1-D array up to length ``n`` with ``fill``."""
    if len(values) == n:
        return values
    assert len(values) < n
    out = np.full(n, fill, dtype=values.dtype)
    out[: len(values)] = values
    return out
