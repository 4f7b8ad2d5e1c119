"""Metric engine: many logical tables over one physical storage table.

Capability counterpart of /root/reference/src/metric-engine/ (engine.rs:60-
115, engine/put.rs:36-186): thousands of small Prometheus-style metrics
share one physical region pair instead of each costing a region. The
reference synthesizes `__table_id` + a murmur3 `__tsid` per row; here the
physical table gets a `__table_id` TAG and the dense-sid series registry
plays the tsid role (a (table_id, tags...) combination IS a distinct
series). Logical tables are thin views: writes inject their table id,
scans add a `__table_id` matcher and expose only the logical columns.
"""

from __future__ import annotations

import numpy as np

from greptimedb_tpu.catalog.table import Table, TableScanData
from greptimedb_tpu.datatypes.schema import ColumnSchema, Schema, SemanticType
from greptimedb_tpu.datatypes.types import ConcreteDataType
from greptimedb_tpu.storage.memtable import OP_PUT, _slice_rows

PHYSICAL_TABLE = "greptime_physical_table"
TABLE_ID_TAG = "__table_id"


def physical_schema() -> Schema:
    return Schema([
        ColumnSchema(TABLE_ID_TAG, ConcreteDataType.string(),
                     SemanticType.TAG, nullable=False),
        ColumnSchema("greptime_value", ConcreteDataType.float64(),
                     SemanticType.FIELD),
        ColumnSchema("greptime_timestamp",
                     ConcreteDataType.timestamp_millisecond(),
                     SemanticType.TIMESTAMP, nullable=False),
    ])


class LogicalTable(Table):
    """A logical metric table backed by the shared physical table."""

    def __init__(self, info, physical: Table):
        self.info = info
        self.physical = physical

    @property
    def regions(self):  # diagnostics only; data ops go through physical
        return self.physical.regions

    @property
    def _tid(self) -> str:
        return str(self.info.table_id)

    def write(self, tag_columns, ts, fields, *, field_valid=None,
              op=OP_PUT):
        n = len(ts)
        tags = dict(tag_columns)
        tags[TABLE_ID_TAG] = np.full(n, self._tid, object)
        # map logical ts/fields onto physical columns
        return self.physical.write(
            tags, ts, fields, field_valid=field_valid, op=op,
        )

    def scan(self, *, ts_min=None, ts_max=None, field_names=None,
             matchers=None, fulltext=None) -> TableScanData:
        m = list(matchers) if matchers else []
        m.append((TABLE_ID_TAG, "eq", self._tid))
        names = (field_names if field_names is not None
                 else self.field_names)
        return self.physical.scan(
            ts_min=ts_min, ts_max=ts_max, field_names=names, matchers=m,
            fulltext=fulltext,
        )

    def scoped_sids(self, region) -> np.ndarray:
        """This table's sids on one physical region: an O(1) posting
        lookup on the __table_id tag through the secondary index —
        per-table scoping stays flat as logical tables multiply onto
        the shared region (engine.rs's tsid-prefix analog)."""
        return region.match_sids([(TABLE_ID_TAG, "eq", self._tid)])

    def _own_rows(self, region, rows):
        """Rows of the shared physical region narrowed to this table's
        series (its `__table_id`): what `appended_since` hands a grid of
        this metric. The membership mask is kept until the region's
        registry interns a series."""
        memo = getattr(self, "_own_memo", None)
        if memo is None or memo[0] != region.series.version:
            own = np.zeros(region.series.num_series, bool)
            own[self.scoped_sids(region)] = True
            memo = self._own_memo = (region.series.version, own)
        own = memo[1]
        keep = own[np.minimum(rows.sid, len(own) - 1)] & (rows.sid < len(own))
        if keep.all():
            return rows
        return _slice_rows(rows, keep) if keep.any() else None

    def flush(self):
        self.physical.flush()

    def truncate(self):
        # logical truncate: tombstone this table's rows only
        data = self.scan()
        if data.rows is None or len(data.rows) == 0:
            return
        rows = data.rows
        reg = data.registry
        # decode tag values for the DISTINCT matched series only —
        # registry-wide tag_values() gathers are O(total series) per
        # tag, which a shared physical region hosting a million
        # logical tables cannot afford per-table
        uniq, inv = np.unique(rows.sid, return_inverse=True)
        codes = reg.codes_matrix()
        tags = {}
        for t in self.physical.tag_names:
            i = reg.tag_names.index(t)
            d = reg.dicts[i]
            vals = np.asarray(
                [d.decode(int(c)) for c in codes[uniq, i]], dtype=object
            )
            tags[t] = vals[inv]
        self.physical.write(tags, rows.ts, {}, op=1)

    def row_count(self) -> int:
        return self.scan().num_rows


def ensure_physical_table(catalog, db: str) -> Table:
    t = catalog.maybe_table(db, PHYSICAL_TABLE)
    if t is not None:
        return t
    return catalog.create_table(
        db, PHYSICAL_TABLE, physical_schema(), engine="mito",
        if_not_exists=True,
    )


def widen_physical_for(catalog, db: str, physical: Table,
                       logical_schema: Schema):
    """Physical table gains any tag/field columns the logical table needs
    (the metric engine's add-columns-on-demand, engine/alter.rs)."""
    for c in logical_schema.columns:
        if c.is_time_index:
            continue
        existing = physical.schema.maybe_column(c.name)
        if existing is not None and (
            existing.semantic_type != c.semantic_type
        ):
            from greptimedb_tpu.errors import InvalidArgumentError

            raise InvalidArgumentError(
                f"column {c.name!r} already exists on the physical "
                f"metric table as a {existing.semantic_type.name}; the "
                f"logical table wants a {c.semantic_type.name} — rename "
                "the label/field"
            )
        if existing is None:
            catalog.alter_add_column(
                db, PHYSICAL_TABLE,
                ColumnSchema(
                    c.name,
                    ConcreteDataType.string() if c.is_tag else c.data_type,
                    c.semantic_type,
                ),
                if_not_exists=True,
            )
