"""Table abstraction: schema + N regions with hash partition routing.

Capability counterpart of the reference's `Table` trait + partition layer
(/root/reference/src/table/src/table.rs, src/partition/src/multi_dim.rs:37,
src/partition/src/splitter.rs): a table owns one or more storage regions;
writes are routed to regions by a stable hash of the tag tuple (the dense-sid
analog of the reference's partition-rule row split), scans fan out to every
region and merge into one table-level series space.
"""

from __future__ import annotations

import zlib
from dataclasses import dataclass

import numpy as np

from greptimedb_tpu.datatypes.schema import Schema
from greptimedb_tpu.storage.memtable import OP_PUT, ColumnarRows, _concat_rows
from greptimedb_tpu.storage.region import Region
from greptimedb_tpu.storage.series import SeriesRegistry


@dataclass
class TableScanData:
    """Merged multi-region scan output in one table-level series space."""

    rows: ColumnarRows | None
    registry: SeriesRegistry
    field_names: list[str]

    @property
    def num_rows(self) -> int:
        return 0 if self.rows is None else len(self.rows)


def _route_rows(tag_cols: list[np.ndarray], n_rows: int, n_regions: int) -> np.ndarray:
    """Stable per-row region index from the tag tuple (crc32 of the joined
    tag strings, computed once per distinct combination).

    Empty tag values are EXCLUDED from the key: a series written before an
    ALTER ADD TAG reads "" for the new tag and must keep routing to the
    same region, or overwrite dedup and deletes would split across regions.
    Collisions between different series only affect placement, never
    identity."""
    if n_regions <= 1 or not tag_cols:
        return np.zeros(n_rows, dtype=np.int32)
    stacked = np.stack([c.astype(object) for c in tag_cols], axis=1)
    uniq, inv = np.unique(stacked.astype(str), axis=0, return_inverse=True)
    dest = np.empty(len(uniq), dtype=np.int32)
    for i, row in enumerate(uniq):
        key = "\x00".join(v for v in row if v != "")
        dest[i] = zlib.crc32(key.encode()) % n_regions
    return dest[np.ravel(inv)]


class Table:
    def __init__(self, info, regions: list[Region]):
        self.info = info
        self.regions = regions
        self.partition_rule = None
        part = getattr(info, "partition", None)
        if part:
            from greptimedb_tpu.catalog.partition import PartitionRule

            self.partition_rule = PartitionRule.from_json(part)

    @property
    def name(self) -> str:
        return self.info.name

    @property
    def schema(self) -> Schema:
        return self.info.schema

    @property
    def tag_names(self) -> list[str]:
        return [c.name for c in self.info.schema.tag_columns]

    @property
    def field_names(self) -> list[str]:
        return [c.name for c in self.info.schema.field_columns]

    @property
    def ts_name(self) -> str:
        return self.info.schema.time_index.name

    # ------------------------------------------------------------------
    # write path
    # ------------------------------------------------------------------
    def write(
        self,
        tag_columns: dict[str, np.ndarray],
        ts: np.ndarray,
        fields: dict[str, np.ndarray],
        *,
        field_valid: dict[str, np.ndarray] | None = None,
        op: int = OP_PUT,
        skip_wal: bool = False,
    ) -> int:
        """Route rows to regions by tag hash; returns rows written.

        skip_wal is the bulk-load path (restore/benchmark loads — the
        reference's bulk ingest part, src/mito2/src/memtable/bulk.rs):
        rows go straight to the memtable without durability."""
        n = len(ts)
        if n == 0:
            return 0
        ts = np.asarray(ts, np.int64)
        # normalize: every schema FIELD present with its proper dtype, so
        # regions never have to guess a fill dtype (string fields stay
        # object arrays end-to-end).
        fields = dict(fields)
        field_valid = dict(field_valid) if field_valid else {}
        if op == OP_PUT:
            for c in self.info.schema.field_columns:
                if c.name in fields:
                    continue
                if c.data_type.is_string():
                    fields[c.name] = np.full(n, "", object)
                else:
                    fields[c.name] = np.zeros(n, c.data_type.to_numpy())
                field_valid[c.name] = np.zeros(n, bool)
        tag_names = self.tag_names
        tag_cols = [np.asarray(tag_columns.get(t, np.full(n, "", object)),
                               object) for t in tag_names]
        if len(self.regions) == 1:
            self._dispatch_writes(
                [(0, dict(zip(tag_names, tag_cols)), ts, fields,
                  field_valid or None)],
                op=op, skip_wal=skip_wal,
            )
            return n
        if self.partition_rule is not None:
            dest = self.partition_rule.route_rows(
                dict(zip(tag_names, tag_cols)), n
            )
            dest = np.clip(dest, 0, len(self.regions) - 1)
        else:
            dest = _route_rows(tag_cols, n, len(self.regions))
        puts = []
        for r_idx in np.unique(dest):
            sel = dest == r_idx
            puts.append((
                int(r_idx),
                {t: c[sel] for t, c in zip(tag_names, tag_cols)},
                ts[sel],
                {k: v[sel] for k, v in fields.items()},
                (
                    {k: v[sel] for k, v in field_valid.items()}
                    if field_valid else None
                ),
            ))
        self._dispatch_writes(puts, op=op, skip_wal=skip_wal)
        return n

    def _dispatch_writes(self, puts, *, op: int, skip_wal: bool):
        """Apply routed row splits; remote tables override to batch all
        of one datanode's regions into a single RPC."""
        for r_idx, tag_columns, ts, fields, field_valid in puts:
            self.regions[r_idx].write(
                tag_columns, ts, fields, field_valid=field_valid, op=op,
                skip_wal=skip_wal,
            )

    def delete(self, tag_columns: dict[str, np.ndarray], ts: np.ndarray) -> int:
        from greptimedb_tpu.storage.memtable import OP_DELETE

        return self.write(tag_columns, ts, {}, op=OP_DELETE)

    # ------------------------------------------------------------------
    # scan path
    # ------------------------------------------------------------------
    def scan(
        self,
        *,
        ts_min: int | None = None,
        ts_max: int | None = None,
        field_names: list[str] | None = None,
        matchers: list[tuple[str, str, object]] | None = None,
        fulltext: list | None = None,
    ) -> TableScanData:
        """Fan out to regions, prune series by tag matchers, merge into one
        table-level sid space. Rows stay per-series time-sorted (series are
        region-disjoint, so concatenation preserves per-series order)."""
        names = field_names if field_names is not None else self.field_names
        from greptimedb_tpu import cancellation

        cancellation.checkpoint()
        if len(self.regions) == 1:
            region = self.regions[0]
            sids = None
            if matchers:
                sids = region.match_sids(matchers)
                if len(sids) == 0:
                    return TableScanData(None, region.series, names)
            res = region.scan(ts_min=ts_min, ts_max=ts_max,
                              field_names=names, sids=sids,
                              fulltext=fulltext)
            return TableScanData(res.rows, res.registry, names)

        from greptimedb_tpu.query import stats

        scan_regions = self.pruned_regions(matchers)
        stats.add("regions_scanned", len(scan_regions))
        merged = SeriesRegistry(self.tag_names)
        chunks: list[ColumnarRows] = []
        from greptimedb_tpu import cancellation

        for region in scan_regions:
            cancellation.checkpoint()
            sids = None
            if matchers:
                sids = region.match_sids(matchers)
                if len(sids) == 0:
                    continue
            res = region.scan(ts_min=ts_min, ts_max=ts_max,
                              field_names=names, sids=sids,
                              fulltext=fulltext)
            if res.rows is None or len(res.rows) == 0:
                continue
            # region sid -> table sid: intern every region series once
            reg = res.registry
            if reg.num_series:
                remap = merged.intern_rows(
                    [reg.tag_values(t) for t in self.tag_names]
                ) if self.tag_names else merged.intern_rows([])
                if self.tag_names:
                    rows = res.rows
                    rows.sid = remap[rows.sid]
            chunks.append(res.rows)
        if not chunks:
            return TableScanData(None, merged, names)
        rows = chunks[0] if len(chunks) == 1 else _concat_rows_full(chunks, names)
        return TableScanData(rows, merged, names)

    def pruned_regions(self, matchers) -> list:
        """Regions that can match `matchers` under the partition rule
        (all of them when unpartitioned / unprunable). The ONE pruning
        implementation shared by local scans, remote scans, and the
        distributed partial fan-out."""
        if self.partition_rule is None or not matchers:
            return self.regions
        keep = self.partition_rule.prune(matchers)
        if keep is None:
            return self.regions
        from greptimedb_tpu.query import stats

        out = [self.regions[i] for i in keep if i < len(self.regions)]
        stats.add("regions_pruned", len(self.regions) - len(out))
        return out

    def flush(self):
        for r in self.regions:
            r.flush()

    def truncate(self):
        for r in self.regions:
            r.truncate()

    def data_version(self) -> tuple:
        """Logical-data version across regions + schema; device caches
        compare this to decide reuse (see query/device_range.py)."""
        return (
            tuple(r.data_version for r in self.regions),
            tuple(self.schema.column_names),
            tuple(self.tag_names),
        )

    def appended_since(self, version: tuple):
        """What was written since `version` (an earlier
        `data_version()`), if rows were put and nothing else happened
        -> (rows, appends, the version they reach, None). Otherwise
        (None, 0, None, reason): "mutation" (a truncate, a delete or a
        schema change lies between the two versions), "multi_region"
        (each region numbers its own series), "flushed" (the rows have
        left the memtable). The parts of a version compare one by one:
        schema and tags, then per region the truncate marks, then the
        sequence, which only a write moves."""
        if len(self.regions) != 1:
            return None, 0, None, "multi_region"
        region = self.regions[0]
        (then,), columns, tags = version
        if (columns != tuple(self.schema.column_names)
                or tags != tuple(self.tag_names)
                or then[1:] != region.data_version[1:]):
            return None, 0, None, "mutation"
        rows, appends, seq_now = region.rows_since(then[0])
        if rows is False:
            return None, 0, None, "flushed"
        if rows is not None and (rows.op != OP_PUT).any():
            return None, 0, None, "mutation"
        return rows, appends, (((seq_now,) + then[1:],),) + version[1:], None

    def physical_version(self) -> tuple:
        """data_version extended with each region's manifest version:
        additionally bumps on flush/compact/schema commits. The frontend
        result cache (query/result_cache.py) keys on THIS — the same
        conservative discipline as the datanode merged-scan cache."""
        return (
            tuple(r.physical_version for r in self.regions),
            tuple(self.schema.column_names),
            tuple(self.tag_names),
        )

    def row_count(self) -> int:
        """Approximate row count (memtable + SST rows, before dedup)."""
        total = 0
        for r in self.regions:
            total += r.memtable.rows
            total += sum(m.rows for m in r.manifest.state.ssts)
        return total


def _concat_rows_full(chunks: list[ColumnarRows], names: list[str]) -> ColumnarRows:
    return _concat_rows(chunks, names)
