"""A storage region: the LSM unit (WAL + memtable + SSTs + manifest).

Capability counterpart of the reference's MitoRegion + RegionWorkerLoop
write/flush/scan handlers (/root/reference/src/mito2/src/worker/handle_write.rs,
read/scan_region.rs). Writes hit the WAL first, then the memtable; scans
merge memtable + pruned SSTs and dedup by (sid, ts) keeping the highest
sequence — the last-row dedup of read/dedup.rs — then honor deletes.
"""

from __future__ import annotations

import uuid
from dataclasses import dataclass, field

import numpy as np

from greptimedb_tpu.errors import RegionReadonlyError
from greptimedb_tpu.storage import codec
from greptimedb_tpu.storage.manifest import RegionManifest
from greptimedb_tpu.storage.memtable import (
    OP_DELETE,
    OP_PUT,
    ColumnarRows,
    Memtable,
    _concat_rows,
    _slice_rows,
)
from greptimedb_tpu.storage.object_store import ObjectStore
from greptimedb_tpu.storage.series import SeriesRegistry
from greptimedb_tpu.storage.sst import (
    SstMeta,
    read_sst,
    sidecar_path,
    write_sst,
)
from greptimedb_tpu.storage.wal import RegionWal
from greptimedb_tpu.telemetry import tracing

from greptimedb_tpu import concurrency

@dataclass
class RegionOptions:
    memtable_window_ms: int | None = 2 * 3600 * 1000
    flush_rows: int = 2_000_000
    flush_bytes: int = 256 * 1024 * 1024
    wal_sync: bool = False
    compaction_window_ms: int = 2 * 3600 * 1000
    compaction_trigger_files: int = 4
    merge_mode: str = "last_row"   # or "last_non_null"
    append_mode: bool = False      # append-only tables skip dedup
    ttl_ms: int | None = None


@dataclass
class RegionMetadata:
    region_id: int
    table: str
    tag_names: list[str]
    field_names: list[str]
    ts_name: str
    options: RegionOptions = field(default_factory=RegionOptions)
    # columns with flush-time fulltext term indexes (puffin sidecars)
    fulltext_fields: list = field(default_factory=list)


@dataclass
class ScanResult:
    """Columnar scan output ready for the device bridge."""

    rows: ColumnarRows | None
    registry: SeriesRegistry
    field_names: list[str]

    @property
    def num_rows(self) -> int:
        return 0 if self.rows is None else len(self.rows)


# ----------------------------------------------------------------------
# merged-scan cache: the page-cache-hot analog. The reference answers
# repeated scans out of its SST page cache + row-group caches
# (/root/reference/src/mito2/src/cache/); here the equivalent steady
# state is the fully merged + deduped columnar row set per region, keyed
# by the region's logical data_version, so repeated full-table scans
# (row-filter queries like TSBS high-cpu-all) skip the SST read, concat
# and dedup entirely and pay only the per-query filter/projection.
_SCAN_CACHE_MIN_ROWS = 1_000_000         # below this a cold scan is cheap
_SCAN_CACHE_TOTAL_BYTES = 6 * 1024**3    # global LRU budget


class _ScanCachePool:
    """Tracks cached-scan bytes across regions; LRU-evicts over budget."""

    def __init__(self, budget: int):
        self.budget = budget
        self._lock = concurrency.Lock()
        self._entries: dict[int, tuple] = {}  # id(region) -> (region, bytes)
        self._order: list[int] = []

    def store(self, region, entry: tuple, nbytes: int):
        """Install `entry` as region._scan_cache and account it. The cache
        attribute is only ever set/cleared under this pool lock, so
        eviction can't race a concurrent install and desync accounting."""
        with self._lock:
            k = id(region)
            if k in self._entries:
                self._order.remove(k)
            region._scan_cache = entry
            self._entries[k] = (region, nbytes)
            self._order.append(k)
            total = sum(b for _, b in self._entries.values())
            while total > self.budget and len(self._order) > 1:
                ev = self._order.pop(0)
                reg, b = self._entries.pop(ev)
                reg._scan_cache = None
                total -= b

    def touch(self, region):
        with self._lock:
            k = id(region)
            if k in self._order:
                self._order.remove(k)
                self._order.append(k)

    def drop(self, region):
        with self._lock:
            region._scan_cache = None
            self._entries.pop(id(region), None)
            k = id(region)
            if k in self._order:
                self._order.remove(k)


_scan_pool = _ScanCachePool(_SCAN_CACHE_TOTAL_BYTES)


def _shallow_rows(rows: ColumnarRows, names) -> ColumnarRows:
    """New container sharing the cached arrays: callers replace attributes
    (e.g. sid remap) but never mutate the arrays in place."""
    return ColumnarRows(
        sid=rows.sid, ts=rows.ts, seq=rows.seq, op=rows.op,
        fields={n: rows.fields[n] for n in names},
        field_valid=(
            {n: rows.field_valid[n] for n in names if n in rows.field_valid}
            if rows.field_valid else None
        ),
    )


def _rows_nbytes(rows: ColumnarRows) -> int:
    n = rows.sid.nbytes + rows.ts.nbytes + rows.seq.nbytes + rows.op.nbytes
    for a in rows.fields.values():
        n += a.nbytes
    if rows.field_valid:
        for a in rows.field_valid.values():
            n += a.nbytes
    return n


class Region:
    def __init__(
        self,
        meta: RegionMetadata,
        store: ObjectStore,
        wal_dir: str,
        *,
        prefix: str | None = None,
        log_store=None,
        checkpoint_interval_edits: int | None = None,
        cold_store: ObjectStore | None = None,
    ):
        import time as _time

        from greptimedb_tpu.storage import recovery as _recovery

        self.meta = meta
        self.store = store
        # cold-tier store (compaction tiering). None = derive: the raw
        # store beneath any local read cache, so cold reads/writes
        # never evict hot objects from it
        self._cold_store = cold_store
        # compaction pool handle + engine-wide options; wired by the
        # owning engine (a bare Region compacts inline with defaults)
        self._compaction = None
        self._compaction_opts = None
        self.prefix = prefix or f"data/region_{meta.region_id}"
        # pluggable WAL backend: node-local segment files by default, or
        # any LogStore (e.g. ObjectStoreLogStore for the remote-WAL
        # topology) supplied by the engine
        self.wal = (log_store if log_store is not None
                    else RegionWal(wal_dir, sync=meta.options.wal_sync))
        # per-stage recovery wall times + replayed-entry count for this
        # open; the engine aggregates them into gtpu_recovery_* metrics
        self.recovery_stats: dict = {
            "manifest_load_ms": 0.0, "wal_replay_ms": 0.0,
            "sst_restore_ms": 0.0, "replayed_entries": 0,
        }
        t0 = _time.perf_counter()
        self.manifest = RegionManifest(
            store, f"{self.prefix}/manifest",
            checkpoint_distance=checkpoint_interval_edits,
        )
        ms = (_time.perf_counter() - t0) * 1000.0
        self.recovery_stats["manifest_load_ms"] = ms
        _recovery.record_stage("manifest_load", ms)
        self.series = (
            SeriesRegistry.restore(self.manifest.state.series_snapshot)
            if self.manifest.state.series_snapshot
            else SeriesRegistry(meta.tag_names)
        )
        # reconcile: tags added (ALTER/auto-alter) after the last snapshot
        for t in meta.tag_names:
            if t not in self.series.tag_names:
                self.series.add_tag(t)
        self.memtable = Memtable(meta.field_names,
                                 window_ms=meta.options.memtable_window_ms)
        self._frozen: list[Memtable] = []
        # intern deltas not yet on the log (skip_wal writes, failed
        # appends); the next WAL-on entry carries them, flush clears them
        self._pending_new_series: list[tuple[int, list[str]]] = []
        self._seq = self.manifest.state.committed_sequence
        # rows with a sequence at or above this are all in the active
        # memtable (`rows_since`); a flush's freeze moves it up
        self._memtable_floor_seq = self._seq
        # the memtable the last flush froze and the floor it had: its
        # rows stay readable by sequence after they reach their SST, so
        # that a grid one flush behind is brought forward, not rebuilt
        self._flushed_tail: tuple | None = None
        self._truncate_epoch = 0
        self._scan_cache: tuple | None = None  # (data_version, ColumnarRows)
        self._lock = concurrency.RLock()
        self.writable = True
        t1 = _time.perf_counter()
        self.recovery_stats["replayed_entries"] = self._replay()
        ms = (_time.perf_counter() - t1) * 1000.0
        self.recovery_stats["wal_replay_ms"] = ms
        _recovery.record_stage("wal_replay", ms)

    @property
    def data_version(self) -> tuple[int, int, int]:
        """Monotonic logical-data version: bumps with every write (sequence)
        and every truncate. Device caches key on this to know when a region's
        row set changed (the page-cache-invalidation analog of the
        reference's memtable/SST version in
        /root/reference/src/mito2/src/region/version.rs). The manifest's
        truncated_entry_id rides along so the version stays comparable
        across restarts (the in-memory epoch resets to 0 at reopen).
        Deliberately flush-stable: a flush moves rows without changing
        them, so grid snapshots restored after a clean shutdown (which
        flushes) still match."""
        return (self._seq, self._truncate_epoch,
                self.manifest.state.truncated_entry_id)

    @property
    def physical_version(self) -> tuple[int, int, int, int]:
        """data_version extended with the manifest version: additionally
        bumps on every manifest commit — flush, compaction, truncate,
        schema change. The datanode merged-scan cache
        (dist/scan_cache.py) keys on THIS, so a cached partial is never
        served across any physical mutation of the region, even ones
        that provably preserve the logical row set."""
        return self.data_version + (self.manifest.version,)

    # ------------------------------------------------------------------
    # tiered stores
    # ------------------------------------------------------------------
    @property
    def cold_store(self) -> ObjectStore:
        """The cold tier's store: the configured [storage.cold] store,
        or the raw store beneath the local read cache (cold data must
        not evict hot objects from it)."""
        if self._cold_store is not None:
            return self._cold_store
        from greptimedb_tpu.storage.object_store import CachedObjectStore

        if isinstance(self.store, CachedObjectStore):
            return self.store.inner
        return self.store

    def store_for_tier(self, tier: str) -> ObjectStore:
        from greptimedb_tpu.storage.sst import TIER_COLD

        return self.cold_store if tier == TIER_COLD else self.store

    def store_for(self, meta: SstMeta) -> ObjectStore:
        """The store holding this SST (tier-aware reads/deletes)."""
        return self.store_for_tier(getattr(meta, "tier", "hot"))

    def raw_store_for(self, meta: SstMeta) -> ObjectStore:
        """Like store_for, beneath any local read cache: compaction and
        restore reads are read-once and must not churn the cache."""
        from greptimedb_tpu.storage.object_store import CachedObjectStore

        st = self.store_for(meta)
        return st.inner if isinstance(st, CachedObjectStore) else st

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
        """Append rows. tag_columns: name -> object array of strings.
        Returns the assigned base sequence."""
        if not self.writable:
            raise RegionReadonlyError(f"region {self.meta.region_id} readonly")
        n = len(ts)
        # the lock deliberately covers base_seq assignment + sid intern +
        # WAL append + memtable insert: writers must land on the log and
        # in the memtable in one sequence order or replay diverges. Hold
        # time is bounded by the caller's batch size (a 100k-row flow
        # sink write crosses the 1s sanitizer threshold on a saturated
        # host) — never by another thread's critical section.
        with self._lock:  # gtlint: disable=GTS103
            base_seq = self._seq
            self._seq += n
            rows, new_series = self._make_rows(
                tag_columns, ts, fields, field_valid, op, base_seq
            )
            if skip_wal:
                # rows skip durability, but the intern delta must still
                # reach the log eventually or later durable entries would
                # reference unreconstructable sids — park it for the next
                # WAL-on write (flush clears it: the manifest snapshot
                # then covers the registry)
                self._pending_new_series.extend(new_series)
            else:
                # int-coded WAL payload (fmt 2): sids + numeric columns as
                # raw buffers, tag STRINGS only for series first seen in
                # this batch — the end-to-end int-coding of tags the
                # reference gets from its mcmp primary-key encoding
                # (/root/reference/src/mito2/src/row_converter.rs:54).
                # Only caller-provided fields travel; replay backfills the
                # rest exactly like _make_rows does.
                cols = {"__ts": rows.ts, "__sid": rows.sid}
                for k in fields:
                    cols[f"__f_{k}"] = rows.fields[k]
                for k, v in (field_valid or {}).items():
                    cols[f"__v_{k}"] = np.asarray(v, bool)
                delta = self._pending_new_series + list(new_series)
                payload = codec.encode_columns(cols, meta={
                    "fmt": 2, "op": op, "base_seq": base_seq,
                    "new_series": [[sid, tags] for sid, tags in delta],
                })
                try:
                    # joins the INSERT's trace when one is active (the
                    # background paths carry none, so this is free
                    # there); duration = durability cost of this batch
                    with tracing.child_span(
                            "wal.append",
                            region=self.meta.region_id,
                            bytes=len(payload)):
                        self.wal.append(payload)
                except Exception:
                    # the registry already holds the delta; make sure a
                    # future successful entry re-reports it (ensure_series
                    # is idempotent on replay)
                    self._pending_new_series.extend(new_series)
                    raise
                self._pending_new_series = []
            with tracing.child_span("memtable.append"):
                self.memtable.append(rows)
            return base_seq

    def rows_since(self, seq: int):
        """What was written from sequence `seq` on, read by sequence with
        no scan of the region: from the memtable, and across one flush
        from the memtable that flush froze -> (rows, the appends they
        came in, the sequence they reach up to). `rows` is None when
        nothing was written, and False when a second flush has moved
        some of them out of reach. Taken under the write lock, which a
        write holds from its sequence bump to its memtable insert, so
        the rows are exactly those below the sequence returned."""
        with self._lock:
            if seq >= self._memtable_floor_seq:
                rows, appends = self.memtable.rows_since(seq)
                return rows, appends, self._seq
            tail = self._flushed_tail
            if tail is None or seq < tail[1]:
                return False, 0, self._seq
            older, n_old = tail[0].rows_since(seq)
            newer, n_new = self.memtable.rows_since(seq)
            parts = [r for r in (older, newer) if r is not None]
            if len(parts) == 2:
                rows = _concat_rows(parts, self.meta.field_names)
            else:
                rows = parts[0] if parts else None
            return rows, n_old + n_new, self._seq

    def _make_rows(self, tag_columns, ts, fields, field_valid, op, base_seq):
        """Intern tags and normalize fields into sid-resolved ColumnarRows.
        Returns (rows, new_series_delta)."""
        n = len(ts)
        with tracing.child_span("write.intern"):
            sids, new_series = self.series.intern_rows_delta(
                [np.asarray(tag_columns[name], object)
                 if name in tag_columns else np.full(n, "", object)
                 for name in self.meta.tag_names],
                n=n,
            )
        full_fields, valids = self._normalize_fields(n, fields, field_valid)
        rows = ColumnarRows(
            sid=sids,
            ts=np.asarray(ts, np.int64),
            seq=np.arange(base_seq, base_seq + n, dtype=np.uint64),
            op=np.full(n, op, dtype=np.uint8),
            fields=full_fields,
            field_valid=valids or None,
        )
        return rows, new_series

    def _normalize_fields(self, n, fields, field_valid):
        """Every schema field present; absent ones zero-filled + invalid."""
        full_fields = {}
        valids = dict(field_valid) if field_valid else {}
        for name in self.meta.field_names:
            if name in fields:
                full_fields[name] = np.asarray(fields[name])
            else:
                full_fields[name] = np.zeros(n, dtype=np.float64)
                valids[name] = np.zeros(n, dtype=bool)
        return full_fields, valids

    def _apply_rows(self, tag_columns, ts, fields, field_valid, op, base_seq):
        rows, _ = self._make_rows(
            tag_columns, ts, fields, field_valid, op, base_seq
        )
        self.memtable.append(rows)

    def delete(self, tag_columns: dict[str, np.ndarray], ts: np.ndarray) -> int:
        return self.write(tag_columns, ts, {}, op=OP_DELETE)

    def _replay(self) -> int:
        """Re-apply WAL entries after the flushed id (open/catchup,
        /root/reference/src/mito2/src/worker/handle_catchup.rs analog).
        Returns the number of entries replayed."""
        from_id = self.manifest.state.flushed_entry_id + 1
        seed = getattr(self.wal, "seed_floor", None)
        if seed is not None:
            # shared-topic logs: never hand out ids below the flushed
            # watermark even if truncation erased every physical entry
            seed(self.manifest.state.flushed_entry_id)
        replayed = 0
        for entry in self.wal.replay(from_id):
            replayed += 1
            cols, meta = codec.decode_columns(entry.payload)
            ts = cols.pop("__ts")
            base_seq = meta["base_seq"]
            if meta.get("fmt") == 2:
                # int-coded payload: restore the intern delta, then feed
                # the memtable directly — no re-interning
                for sid, tag_vals in meta.get("new_series", []):
                    self.series.ensure_series(int(sid), list(tag_vals))
                n = len(ts)
                fields = {}
                valids = {}
                for k, v in cols.items():
                    if k.startswith("__f_"):
                        fields[k[4:]] = v
                    elif k.startswith("__v_"):
                        valids[k[4:]] = v
                full_fields, valids = self._normalize_fields(
                    n, fields, valids or None
                )
                rows = ColumnarRows(
                    sid=np.asarray(cols["__sid"], np.int32),
                    ts=np.asarray(ts, np.int64),
                    seq=np.arange(base_seq, base_seq + n, dtype=np.uint64),
                    op=np.full(n, meta["op"], dtype=np.uint8),
                    fields=full_fields,
                    field_valid=valids or None,
                )
                self.memtable.append(rows)
            else:
                tags = {}
                fields = {}
                valids = {}
                for k, v in cols.items():
                    if k.startswith("__tag_"):
                        tags[k[6:]] = v
                    elif k.startswith("__f_"):
                        fields[k[4:]] = v
                    elif k.startswith("__v_"):
                        valids[k[4:]] = v
                self._apply_rows(tags, ts, fields, valids or None,
                                 meta["op"], base_seq)
            self._seq = max(self._seq, base_seq + len(ts))
        return replayed

    # ------------------------------------------------------------------
    # flush
    # ------------------------------------------------------------------
    @property
    def should_flush(self) -> bool:
        o = self.meta.options
        return (self.memtable.rows >= o.flush_rows
                or self.memtable.bytes >= o.flush_bytes)

    def flush(self) -> SstMeta | None:
        """Freeze the memtable, write an SST, commit manifest, trim WAL."""
        with tracing.child_span("region.flush",
                                region=self.meta.region_id):
            return self._flush_traced()

    def _flush_traced(self) -> SstMeta | None:
        with self._lock:
            if self.memtable.is_empty:
                return None
            frozen = self.memtable
            self.memtable = Memtable(
                self.meta.field_names,
                window_ms=self.meta.options.memtable_window_ms,
            )
            self._frozen.append(frozen)
            flushed_entry_id = self.wal.next_entry_id - 1
            self._flushed_tail = (frozen, self._memtable_floor_seq)
            seq_now = self._memtable_floor_seq = self._seq
        rows = frozen.scan()
        file_id = uuid.uuid4().hex
        meta = write_sst(
            self.store, f"{self.prefix}/sst/{file_id}.parquet", file_id,
            rows, fulltext_fields=self.meta.fulltext_fields,
        )
        # GTS102/103: the manifest commit (an object-store write on
        # remote backends) happens under the region lock BY DESIGN — the
        # SST becoming visible and the frozen memtable being dropped
        # must be atomic against concurrent flush/alter/truncate; the
        # accepted I/O hold can cross the 1s wall-clock threshold on a
        # saturated host
        with self._lock:  # gtlint: disable=GTS102,GTS103
            self.manifest.commit({
                "kind": "flush",
                "add_ssts": [meta.to_json()],
                "flushed_entry_id": flushed_entry_id,
                "committed_sequence": seq_now,
                "series_snapshot": self.series.snapshot(),
            })
            # the snapshot covers every live series: replay never needs
            # pre-flush intern deltas again
            self._pending_new_series = []
            self._frozen.remove(frozen)
            self.wal.obsolete(flushed_entry_id)
        return meta

    # ------------------------------------------------------------------
    # scan
    # ------------------------------------------------------------------
    def match_sids(self, matchers) -> np.ndarray:
        """Matched sids for a tag-matcher set, routed through the
        secondary tag index (index/) — eq/in are posting lookups, re/ne
        evaluate over the distinct-value dictionary; results memoized
        per matcher set and validated against the registry version."""
        from greptimedb_tpu import index as _index

        return _index.match_sids(self.series, matchers)

    def scan(
        self,
        *,
        ts_min: int | None = None,
        ts_max: int | None = None,
        field_names: list[str] | None = None,
        sids: np.ndarray | None = None,
        raw: bool = False,
        fulltext: list | None = None,
    ) -> ScanResult:
        """Merged + deduped scan. Output rows sorted by (sid, ts)."""
        if self.meta.options.ttl_ms is not None and ts_min is None:
            import time as _time

            ts_min = int(_time.time() * 1000) - self.meta.options.ttl_ms
        names = (field_names if field_names is not None
                 else self.meta.field_names)
        # merged-scan cache: answer out of the deduped columnar row set
        # when the region's logical data hasn't changed since it was built
        if fulltext is None and not raw:
            hit = self._scan_cached(names, ts_min, ts_max, sids)
            if hit is not None:
                return hit
        chunks: list[ColumnarRows] = []
        scan_names = names
        with self._lock:
            ssts = list(self.manifest.state.ssts)
            tables = [self.memtable] + list(self._frozen)
            # version captured at snapshot time: writes landing during the
            # merge below must NOT be stamped as included in the cache
            snap_key = (self.data_version, tuple(self.meta.field_names))
            if (sids is None and fulltext is None and not raw
                    and ts_min is None and ts_max is None):
                approx = (sum(m.rows for m in ssts)
                          + sum(t.rows for t in tables))
                if (approx >= _SCAN_CACHE_MIN_ROWS
                        and set(names) != set(self.meta.field_names)):
                    # cache-build candidate: read every field once so
                    # alternating projections all hit the same entry
                    scan_names = list(self.meta.field_names)
        # fulltext row-group pruning is VALUE-based: under last-write-
        # wins dedup, skipping a group that holds a newer overwrite or
        # tombstone would resurrect the shadowed row. Append-mode
        # regions (the log-table shape fulltext serves) have no dedup,
        # so pruning is sound there; everywhere else the residual
        # filter alone does the matching.
        ft = fulltext if self.meta.options.append_mode else None
        smin = smax = None
        if sids is not None and len(sids):
            smin = int(sids.min())
            smax = int(sids.max())
        for meta in ssts:
            if smin is not None and (meta.sid_max < smin
                                     or meta.sid_min > smax):
                # manifest sid range can't intersect the matched set:
                # the whole file is skipped without touching its footer
                from greptimedb_tpu.index.tag_index import count_pruned
                from greptimedb_tpu.query import stats as _stats

                _stats.add("index_ssts_skipped", 1)
                count_pruned(bytes_=meta.size_bytes, scope="sst")
                continue
            r = read_sst(self.store_for(meta), meta,
                         ts_min=ts_min, ts_max=ts_max,
                         field_names=scan_names, sids=sids, fulltext=ft)
            if r is not None:
                chunks.append(r)
        for mt in tables:
            r = mt.scan(ts_min, ts_max, scan_names)
            if r is not None:
                if sids is not None:
                    sel = np.isin(r.sid, sids)
                    r = _slice_rows(r, sel) if not sel.all() else r
                if len(r):
                    chunks.append(r)
        if not chunks:
            return ScanResult(None, self.series, names)
        # always normalize through _concat_rows: it back-fills fields that a
        # chunk written before an ALTER ADD COLUMN does not have.
        only = chunks[0] if len(chunks) == 1 else None
        if only is not None and all(n in only.fields for n in scan_names):
            rows = only
        else:
            rows = _concat_rows(chunks, scan_names)
        if not raw and not self.meta.options.append_mode:
            rows = dedup_rows(rows, merge_mode=self.meta.options.merge_mode)
        else:
            order = np.lexsort((rows.seq, rows.ts, rows.sid))
            rows = _slice_rows(rows, order)
        if self._maybe_cache_scan(snap_key, rows, ts_min, ts_max,
                                  sids, fulltext, raw):
            # the cached object must never escape: callers mutate the
            # returned container in place (e.g. table-level sid remap)
            rows = _shallow_rows(rows, names)
        elif scan_names is not names:
            rows = _shallow_rows(rows, names)
        return ScanResult(rows, self.series, names)

    # -- merged-scan cache ---------------------------------------------
    def _scan_cached(self, names, ts_min, ts_max,
                     sids=None) -> ScanResult | None:
        cached = self._scan_cache
        if cached is None:
            return None
        key = (self.data_version, tuple(self.meta.field_names))
        if cached[0] != key:
            # stale entry can never be served again — release its arrays
            # instead of pinning gigabytes until budget pressure
            _scan_pool.drop(self)
            return None
        rows: ColumnarRows = cached[1]
        if any(n not in rows.fields for n in names):
            return None
        _scan_pool.touch(self)
        out = _shallow_rows(rows, names)
        if sids is not None:
            # cached rows are (sid, ts)-sorted: each matched series is
            # one contiguous run; runs expand vectorized (np.repeat of
            # offset deltas + cumsum), no per-sid Python even at high
            # matcher cardinality
            lo_idx = np.searchsorted(out.sid, sids, side="left")
            hi_idx = np.searchsorted(out.sid, sids, side="right")
            lens = hi_idx - lo_idx
            nz = lens > 0
            starts = lo_idx[nz].astype(np.int64)
            lens = lens[nz].astype(np.int64)
            total = int(lens.sum())
            if total:
                run_base = np.concatenate(
                    ([0], np.cumsum(lens)[:-1])
                )
                idx = (np.repeat(starts - run_base, lens)
                       + np.arange(total, dtype=np.int64))
            else:
                idx = np.zeros(0, np.int64)
            out = _slice_rows(out, idx)
        if ts_min is not None or ts_max is not None:
            lo = ts_min if ts_min is not None else -(2**63)
            hi = ts_max if ts_max is not None else 2**63 - 1
            sel = (out.ts >= lo) & (out.ts <= hi)
            if not sel.all():
                out = _slice_rows(out, sel)
        return ScanResult(out, self.series, names)

    def _maybe_cache_scan(self, snap_key, rows, ts_min, ts_max, sids,
                          fulltext, raw) -> bool:
        """Cache an unbounded scan; hits serve any field subset of it."""
        if (raw or sids is not None or fulltext is not None
                or ts_min is not None or ts_max is not None
                or len(rows) < _SCAN_CACHE_MIN_ROWS):
            return False
        nbytes = _rows_nbytes(rows)
        if nbytes > _scan_pool.budget:
            return False
        _scan_pool.store(self, (snap_key, rows), nbytes)
        return True

    # ------------------------------------------------------------------
    def compact(self, *, force: bool = False) -> bool:
        """Run triggered compactions (``force`` merges every
        multi-file window to the top level — the ADMIN semantics).
        Routes through the owning engine's bounded compaction pool
        when one is attached; a bare Region compacts inline. The
        uniform surface shared with RemoteRegion.compact()."""
        from greptimedb_tpu.storage.compaction import compact_once

        if self._compaction is not None:
            return self._compaction.compact_sync(self, force=force)
        return bool(compact_once(self, force=force))

    def invalidate_scan_cache(self):
        """Explicit invalidation for schema changes (ALTER drops/adds can
        leave data_version + field_names identical, e.g. drop+re-add of
        the trailing column with no intervening writes)."""
        _scan_pool.drop(self)

    def truncate(self):
        with self._lock:
            _scan_pool.drop(self)
            self._truncate_epoch += 1
            self._memtable_floor_seq = self._seq
            self._flushed_tail = None
            entry_id = self.wal.next_entry_id - 1
            self.memtable = Memtable(
                self.meta.field_names,
                window_ms=self.meta.options.memtable_window_ms,
            )
            self._frozen.clear()
            for s in self.manifest.state.ssts:
                st = self.store_for(s)
                st.delete(s.path)
                if s.fulltext:
                    st.delete(sidecar_path(s.path))
            self.manifest.commit({
                "kind": "truncate",
                "truncated_entry_id": entry_id,
                "series_snapshot": self.series.snapshot(),
            })
            self.wal.obsolete(entry_id)

    def close(self):
        _scan_pool.drop(self)
        self.wal.close()


def dedup_rows(rows: ColumnarRows, *, merge_mode: str = "last_row",
               drop_deletes: bool = True) -> ColumnarRows:
    """Sort by (sid, ts, seq); keep the highest-seq row per (sid, ts); drop
    rows whose winner is a delete. last_non_null additionally back-fills
    null fields from older duplicates of the same key
    (/root/reference/src/mito2/src/read/dedup.rs semantics)."""
    order = np.lexsort((rows.seq, rows.ts, rows.sid))
    r = _slice_rows(rows, order)
    n = len(r)
    if n == 0:
        return r
    key_change = np.empty(n, dtype=bool)
    key_change[0] = True
    key_change[1:] = (r.sid[1:] != r.sid[:-1]) | (r.ts[1:] != r.ts[:-1])
    # winner of each key-run = its last row (highest seq)
    last_of_run = np.empty(n, dtype=bool)
    last_of_run[:-1] = key_change[1:]
    last_of_run[-1] = True

    if merge_mode == "last_non_null" and r.field_valid is not None:
        # propagate newest-non-null per field within each key-run
        run_id = np.cumsum(key_change) - 1
        for name, vals in r.fields.items():
            valid = r.field_valid[name]
            # iterate runs only where the winner has a null (rare path)
            winners = np.nonzero(last_of_run)[0]
            for w in winners[~valid[last_of_run]]:
                rid = run_id[w]
                i = w - 1
                while i >= 0 and run_id[i] == rid:
                    if valid[i]:
                        vals[w] = vals[i]
                        valid[w] = True
                        break
                    i -= 1
    keep = last_of_run
    if drop_deletes:
        # only safe when the caller merged every file that can hold this
        # key (scan-time); compaction keeps tombstones so deletes still
        # shadow rows in files outside the merge set.
        keep = keep & (r.op != OP_DELETE)
    return _slice_rows(r, keep)
