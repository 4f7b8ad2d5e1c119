"""InfluxDB line protocol ingest.

Capability counterpart of /root/reference/src/servers/src/influxdb.rs +
line-protocol auto-create semantics of the operator's Inserter: each
measurement becomes a table (tags -> PRIMARY KEY strings, fields -> typed
FIELD columns, ts -> TIME INDEX), created or widened on first sight.
"""

from __future__ import annotations

import logging
import time
from collections import defaultdict
from itertools import chain

import numpy as np

from greptimedb_tpu.datatypes.schema import ColumnSchema, Schema, SemanticType
from greptimedb_tpu.datatypes.types import ConcreteDataType
from greptimedb_tpu.errors import GreptimeError, InvalidArgumentError
from greptimedb_tpu.telemetry import tracing
from greptimedb_tpu.telemetry.metrics import global_registry

# exact (numerator, denominator) ms conversion per precision: float
# scaling at epoch-scale ns values (~1.7e18) rounds the INPUT to
# float64's 2^8-ns granularity, flipping milliseconds and silently
# colliding adjacent rows into last-write-wins dedup
_PRECISION_MS = {"ns": (1, 1_000_000), "u": (1, 1_000), "us": (1, 1_000),
                 "ms": (1, 1), "s": (1_000, 1), "m": (60_000, 1),
                 "h": (3_600_000, 1)}


class LineProtocolError(InvalidArgumentError):
    pass


def _split_escaped(s: str, seps: set[str]):
    """Split on unescaped separator chars; yields (sep_char, token)."""
    out = []
    cur = []
    i = 0
    n = len(s)
    while i < n:
        c = s[i]
        if c == "\\" and i + 1 < n:
            cur.append(s[i + 1])
            i += 2
            continue
        if c in seps:
            out.append(("".join(cur), c))
            cur = []
            i += 1
            continue
        cur.append(c)
        i += 1
    out.append(("".join(cur), ""))
    return out


def parse_line(line: str):
    """One line -> (measurement, tags: dict, fields: dict, ts_raw or None).
    Field values are python bool/int/float/str."""
    # measurement+tags section ends at first unescaped space
    i = 0
    n = len(line)
    depth_quote = False
    sections = []
    cur = []
    while i < n:
        c = line[i]
        if c == "\\" and i + 1 < n:
            # escape pairs survive INSIDE quotes too: \" must not close
            # a string field value
            cur.append(c)
            cur.append(line[i + 1])
            i += 2
            continue
        if c == '"':
            depth_quote = not depth_quote
            cur.append(c)
            i += 1
            continue
        if c == " " and not depth_quote:
            sections.append("".join(cur))
            cur = []
            i += 1
            # collapse runs of spaces
            while i < n and line[i] == " ":
                i += 1
            continue
        cur.append(c)
        i += 1
    sections.append("".join(cur))
    sections = [s for s in sections if s != ""]
    if len(sections) < 2:
        raise LineProtocolError(f"invalid line: {line!r}")
    head, fields_s = sections[0], sections[1]
    ts_raw = sections[2] if len(sections) > 2 else None

    parts = _split_escaped(head, {","})
    measurement = parts[0][0]
    tags = {}
    for token, _ in parts[1:]:
        if not token:
            continue
        kv = token.split("=", 1)
        if len(kv) != 2:
            raise LineProtocolError(f"bad tag {token!r} in {line!r}")
        tags[kv[0]] = kv[1]

    fields = {}
    for token, _ in _split_field_pairs(fields_s):
        kv = token.split("=", 1)
        if len(kv) != 2:
            raise LineProtocolError(f"bad field {token!r} in {line!r}")
        fields[_unescape(kv[0])] = _parse_field_value(kv[1])
    if not fields:
        raise LineProtocolError(f"no fields in {line!r}")
    return measurement, tags, fields, ts_raw


def _split_field_pairs(s: str):
    out = []
    cur = []
    quoted = False
    i = 0
    n = len(s)
    while i < n:
        c = s[i]
        if c == "\\" and i + 1 < n:
            cur.append(c)
            cur.append(s[i + 1])
            i += 2
            continue
        if c == '"':
            quoted = not quoted
            cur.append(c)
            i += 1
            continue
        if c == "," and not quoted:
            out.append(("".join(cur), c))
            cur = []
            i += 1
            continue
        cur.append(c)
        i += 1
    out.append(("".join(cur), ""))
    return out


def _unescape(s: str) -> str:
    """Collapse backslash pairs: '\\x' -> 'x'."""
    if "\\" not in s:
        return s
    out = []
    i = 0
    while i < len(s):
        if s[i] == "\\" and i + 1 < len(s):
            out.append(s[i + 1])
            i += 2
        else:
            out.append(s[i])
            i += 1
    return "".join(out)


def _parse_field_value(v: str):
    if v.startswith('"') and v.endswith('"') and len(v) >= 2:
        return v[1:-1].replace('\\"', '"').replace("\\\\", "\\")
    low = v.lower()
    if low in ("t", "true"):
        return True
    if low in ("f", "false"):
        return False
    # '_' digit grouping is a Python-ism, not line protocol: reject it
    # so native and fallback agree on what malformed data looks like
    if "_" in v:
        raise LineProtocolError(f"bad field value {v!r}")
    if v.endswith("i") or v.endswith("u"):
        try:
            return int(v[:-1])
        except ValueError:
            raise LineProtocolError(f"bad field value {v!r}") from None
    try:
        return float(v)
    except ValueError:
        raise LineProtocolError(f"bad field value {v!r}") from None


# native tokenizer (greptimedb_tpu/native/lineproto.c, built by `make -C
# greptimedb_tpu/native`); the pure-Python parser below is the always-
# available fallback AND the behavioral spec the C version mirrors
try:
    from greptimedb_tpu.native import _lineproto as _native_lineproto
except ImportError:   # pragma: no cover - build-artifact dependent
    _native_lineproto = None
    # the .so is a git-ignored build product: say which parser serves,
    # a silent fallback reads as a slow server
    logging.getLogger("greptimedb_tpu.servers.influx").warning(
        "native line-protocol tokenizer not built (make -C "
        "greptimedb_tpu/native); using the pure-Python parser")


def parse_payload(body: str) -> list:
    """[(measurement, tags, fields, ts_raw|None)] for a whole payload."""
    if _native_lineproto is not None:
        try:
            return _native_lineproto.parse_payload(body)
        except ValueError as e:
            raise LineProtocolError(str(e)) from None
    out = []
    # split on \n only (matching the native tokenizer); stray \r is
    # stripped with the other edge whitespace
    for raw in body.split("\n"):
        line = raw.strip()
        if not line or line.startswith("#"):
            continue
        try:
            out.append(parse_line(line))
        except LineProtocolError:
            raise
        except ValueError as e:
            raise LineProtocolError(f"{e}: {line!r}") from None
    return out


def write_lines(instance, body: str, *, db: str = "public",
                precision: str = "ns") -> int:
    """Parse a line-protocol payload and write it, auto-creating/widening
    tables. Returns rows written."""
    scale = _PRECISION_MS.get(precision)
    if scale is None:
        raise LineProtocolError(f"bad precision {precision!r}")
    num, den = scale
    now_ms = int(time.time() * 1000)

    # batch rows per measurement
    per_table: dict[str, list] = defaultdict(list)
    with tracing.child_span("influx.parse"):
        parsed = parse_payload(body)
    for m, tags, fields, ts_raw in parsed:
        ts = (now_ms if ts_raw is None
              else int(ts_raw) * num // den)    # exact integer math
        per_table[m].append((tags, fields, ts))

    total = 0
    for measurement, rows in per_table.items():
        total += _write_measurement(instance, db, measurement, rows)
    return total


# how a body's field columns became arrays; both read 0 from the start
_FIELD_COLUMNS = global_registry.counter(
    "gtpu_influx_field_columns_total",
    "line-protocol field columns built, by path: typed (one array "
    "conversion of the column) or mixed (value by value, for a column "
    "that holds kinds its table type does not take)",
    labels=("path",),
)
_TYPED_COLUMNS = _FIELD_COLUMNS.labels("typed")
_MIXED_COLUMNS = _FIELD_COLUMNS.labels("mixed")

_NONE = type(None)
_FIELD_TYPES = {bool: ConcreteDataType.bool_, int: ConcreteDataType.int64,
                float: ConcreteDataType.float64}
# by the kind of a column's numpy dtype, the sets of Python types that
# one array conversion turns into what assigning value by value gives
_TYPED_KINDS = {"O": ({str},), "b": ({bool},), "f": ({int, float},),
                "i": ({int}, {float}), "u": ({int}, {float})}


def _field_type(present: list, kinds: set) -> ConcreteDataType:
    """The type a new column gets: the first value's kind stands, and
    int64 widens to float64 if a later value is a float."""
    first = type(present[0])
    if first is int and float in kinds:
        first = float
    return _FIELD_TYPES.get(first, ConcreteDataType.string)()


def _non_integral(k: str, cs: ColumnSchema, v) -> LineProtocolError:
    return LineProtocolError(
        f"field {k!r} is {cs.data_type.name} but got non-integral value {v}")


def _typed_column(k: str, cs: ColumnSchema, np_t: np.dtype, present: list,
                  kinds: set) -> np.ndarray:
    """A column whose kinds `_TYPED_KINDS` lists for its type, converted
    at once."""
    if np_t.kind in "iu" and float in kinds:
        f = np.array(present, np.float64)
        with np.errstate(invalid="ignore"):
            col = f.astype(np_t)
        # a fraction, and also a NaN, an infinity, a value out of range
        bad = col != f
        if bad.any():
            raise _non_integral(k, cs, present[int(bad.argmax())])
        return col
    return np.array(present, np_t)


def _mixed_column(k: str, cs: ColumnSchema, np_t: np.dtype,
                  present: list) -> np.ndarray:
    """Any other column (a string among floats, a bool among ints),
    assigned value by value."""
    if np_t.kind == "O":
        return np.array([str(v) for v in present], object)
    is_int = np_t.kind in "iu"
    col = np.zeros(len(present), np_t)
    for i, v in enumerate(present):
        if is_int and isinstance(v, float) and v != int(v):
            raise _non_integral(k, cs, v)
        col[i] = v
    return col


def _write_measurement(instance, db: str, measurement: str, rows) -> int:
    with tracing.child_span("influx.columns"):
        n = len(rows)
        tag_dicts, field_dicts, ts = zip(*rows)
        # keys in first-seen order over the rows
        tag_keys = list(dict.fromkeys(chain.from_iterable(tag_dicts)))
        columns = {}
        field_types = {}
        for k in dict.fromkeys(chain.from_iterable(field_dicts)):
            vals = [f.get(k) for f in field_dicts]
            kinds = set(map(type, vals))
            valid = None
            if _NONE in kinds:
                kinds.discard(_NONE)
                valid = np.array([v is not None for v in vals])
                vals = [v for v in vals if v is not None]
            columns[k] = vals, kinds, valid
            field_types[k] = _field_type(vals, kinds)
        table = ensure_table(instance, db, measurement, tag_keys,
                             field_types)

        ts = np.array(ts, np.int64)
        tag_cols = {
            k: np.asarray([t.get(k, "") for t in tag_dicts], object)
            for k in table.tag_names
        }
        fields_out = {}
        valid_out = {}
        typed = 0
        for k, (present, kinds, valid) in columns.items():
            cs = table.schema.column(k)
            np_t = cs.data_type.to_numpy()
            if any(kinds <= s for s in _TYPED_KINDS[np_t.kind]):
                typed += 1
                col = _typed_column(k, cs, np_t, present, kinds)
            else:
                col = _mixed_column(k, cs, np_t, present)
            if valid is not None:
                # a missing value reads zero, "" in a string column
                full = np.full(n, "" if np_t.kind == "O" else 0, np_t)
                full[valid] = col
                col, valid_out[k] = full, valid
            fields_out[k] = col
        _TYPED_COLUMNS.inc(typed)
        _MIXED_COLUMNS.inc(len(columns) - typed)
    table.write(tag_cols, ts, fields_out, field_valid=valid_out or None)
    data = {table.ts_name: ts, **tag_cols, **fields_out}
    instance._notify_flows(db, measurement, table, data, valid_out)
    return n


def ensure_table(instance, db: str, name: str, tag_keys: list[str],
                 field_types: dict[str, ConcreteDataType],
                 *, ts_type: ConcreteDataType | None = None,
                 ts_name: str = "ts", options: dict | None = None,
                 engine: str = "mito"):
    """Auto-create or widen a table for protocol ingest (the reference's
    auto-create/auto-alter on insert, src/operator/src/insert.rs).
    engine="metric" creates a logical table over the shared physical
    region pair (the metric engine's remote-write role)."""
    table = instance.catalog.maybe_table(db, name)
    if table is None:
        cols = [
            ColumnSchema(k, ConcreteDataType.string(), SemanticType.TAG,
                         nullable=False)
            for k in tag_keys
        ]
        for k, t in field_types.items():
            cols.append(ColumnSchema(k, t, SemanticType.FIELD))
        cols.append(ColumnSchema(
            ts_name, ts_type or ConcreteDataType.timestamp_millisecond(),
            SemanticType.TIMESTAMP, nullable=False,
        ))
        if not instance.catalog.has_database(db):
            instance.catalog.create_database(db, if_not_exists=True)
        return instance.catalog.create_table(
            db, name, Schema(cols), if_not_exists=True,
            options=options or {}, engine=engine,
        )
    # widen: add unseen tags/fields; a name clash across semantics is an
    # error, not a silent drop
    schema = table.schema
    for k in tag_keys:
        existing = schema.maybe_column(k)
        if existing is None:
            instance.catalog.alter_add_column(db, name, ColumnSchema(
                k, ConcreteDataType.string(), SemanticType.TAG,
            ), if_not_exists=True)
        elif not existing.is_tag:
            raise LineProtocolError(
                f"{name}.{k} is a {existing.semantic_type.name} column, "
                "cannot write it as a tag"
            )
    for k, t in field_types.items():
        existing = schema.maybe_column(k)
        if existing is None:
            instance.catalog.alter_add_column(db, name, ColumnSchema(
                k, t, SemanticType.FIELD,
            ), if_not_exists=True)
        elif not existing.is_field:
            raise LineProtocolError(
                f"{name}.{k} is a {existing.semantic_type.name} column, "
                "cannot write it as a field"
            )
        schema = table.schema
    return table
