"""Device-resident RANGE execution: the fused SQL->TPU hot path.

This is the point where the SQL engine and the device kernels meet: RANGE
queries (`agg(x) RANGE 'r' ... ALIGN 'a' BY (tags)`) lower onto
device-resident (series x time-cell) partial-state grids instead of the
host NumPy bucket machinery in executor.py.

Capability counterpart of the reference's RangeSelect physical plan + mito
scan with its page cache hot
(/root/reference/src/query/src/range_select/plan.rs:368-446,
src/mito2/src/read/scan_region.rs:59): where the reference streams
row groups out of the page cache into per-window accumulators on the CPU,
here the working set is pinned in HBM as dense per-cell aggregate states
and every query is one XLA program:

    cells (S, NB) --mask--> fold sids->groups --gather--> window combine
    (stride doubling, O(log W) passes) --strided sample--> finalize

One program, one call, one readback: the step window has to be static,
and the exact one depends on where the selected rows start and end,
which only the device knows. So the host bounds the window from the
WHERE's cell bounds before the dispatch, the program computes that
window and, beside it, the selected rows' exact extent and which series
hold any, and the host trims to the exact window after the readback (a
step's value depends on its absolute index alone).

Two programs share that body (`_selection_extent`, `_range_body`), and
one test chooses between them before the dispatch, on a number the
host already holds: `len(sids) <= _ROWS_MAX` of the series the tag
index matched.

- the rows program (`program_rows`): a selective query. One host
  argument, int32[3 + 2*Kb]: (delta, lo, hi), the K matched sids padded
  to the bucket Kb, their group ids. The program reads those rows of
  every plane ([Kb, NB] each; a dynamic slice a row up to
  `_ROW_SLICES_MAX` rows on one device, a gather beyond and on a mesh)
  and runs the body on them; a fresh window
  reads back one packed buffer (the result, the extent, Kb activity
  flags). Nothing is placed on the device ahead of a call. On a mesh
  the gathered rows are replicated and every device does one device's
  arithmetic.
- the plane programs (`program`, and on a mesh its shard_map twin): no
  matcher, or more than `_ROWS_MAX` matched series. Two host arguments,
  the (S,) group ids (unmatched series routed past g; device-resident
  from a selection's second dispatch) and int32[3]; the body masks all
  S series; three outputs in one readback.

No knob chooses: `_ROW_BUCKETS` are module constants, the bucket is the
last member of the static program spec.

Cache design:
- one `_Entry` per (table, resolution, phase); holds (S, NB) device arrays
  of per-cell partial aggregate states per field: {s, n, s2, mn, mx, vl/tl,
  vf/tf} built lazily for the ops seen, plus field-independent row-presence
  and per-cell ts min/max for exact window math;
- cell resolution = gcd(align, range, data interval) when affordable, so
  the grid *is* the data for regular series (one sample per cell) and the
  per-query device reduction does the real work;
- an entry carries the Table.data_version it holds. A query that finds it
  behind asks the table for what was written since; rows that were only
  appended (`_upkeep`) are scattered into the resident planes by one
  program (`jit_grid_append`), on the query path and under `query.grid`,
  so a body nobody queries costs nothing and several become one scatter.
  Anything else (a truncate, a delete, a new series, a row that is not
  the newest of its series, the spare cells used up) evicts and rebuilds,
  the page-cache-invalidation analog;
- the time axis has spare cells past the data (`_cell_capacity`), so an
  appended tick changes no shape and compiles nothing;
- partial states compose exactly, so results are identical to the host path
  up to f32 accumulation (the device stays in f32/int32: no x64 on TPU).

The executor falls back to the host path whenever a query shape is not
expressible over cell partials (residual row filters, non-cell-aligned time
bounds, expression-valued aggregate args, quantiles).
"""

from __future__ import annotations

import functools
import logging
import math
import threading
import time

from dataclasses import dataclass, field as dc_field

import numpy as np

from greptimedb_tpu.errors import UnsupportedError
from greptimedb_tpu.program_cache import ProgramCache
from greptimedb_tpu.sql import ast as A
from greptimedb_tpu.telemetry import tracing
from greptimedb_tpu.telemetry.metrics import global_registry

from greptimedb_tpu import concurrency
from greptimedb_tpu.query.grid_upkeep import PlaneGate as _PlaneGate
from greptimedb_tpu.query.grid_upkeep import cell_capacity as _cell_capacity
from greptimedb_tpu.query.grid_upkeep import file_unless_appended

_log = logging.getLogger("greptimedb_tpu.query.device_range")

# the program computes the window the host can bound before the
# dispatch; steps outside the rows' exact extent are trimmed after the
# readback. A deployment whose WHERE spans (or open sides) run far past
# its data shows here as trimmed="yes", and by how many steps on the
# `device.execute` span's `trimmed_steps`.
_WINDOW_TRIM = global_registry.counter(
    "gtpu_range_window_trim_total",
    "device RANGE queries by whether steps of the bound window were "
    "trimmed to the rows' exact extent after the readback",
    labels=("trimmed",),
)

# which program a query's selection took: the rows the tag index
# matched, gathered (at or under _ROWS_MAX matched series), or the whole
# plane, masked
_SELECTION = global_registry.counter(
    "gtpu_range_selection_total",
    "device RANGE queries by what the range program was called on: the "
    "rows the tag index matched, or the whole series plane",
    labels=("path",),
)
_TOOK_ROWS, _TOOK_PLANE = _SELECTION.labels("rows"), _SELECTION.labels("plane")

# what became of an entry that a query found behind its table's version:
# brought forward by `_upkeep` ("append"), or evicted and rebuilt because
# what was written since is not a plain append ("rebuild_<reason>")
_UPKEEP = global_registry.counter(
    "gtpu_grid_upkeep_total",
    "range grid entries found behind their table's version, by outcome: "
    "append (the rows written since were scattered into the resident "
    "planes) or rebuild_<reason> (evicted and built again)",
    labels=("outcome",),
)
# every outcome reads 0 from the start, so that a scrape can tell "no
# rebuild" from "no such counter"
for _reason in ("out_of_order", "new_series", "capacity", "flushed",
                "mutation", "mesh", "multi_region"):
    _UPKEEP.labels("rebuild_" + _reason)
_UPKEEP.labels("append")
_UPKEEP_ROWS = global_registry.counter(
    "gtpu_grid_upkeep_rows_total",
    "rows scattered into resident range grid planes by the upkeep",
)

DEVICE_THRESHOLD = 262_144       # min table rows before the cache pays off
_CELL_CAP = 256 * 1024 * 1024    # max S*NB cells per cached array (1GB f32)
_MAX_ENTRIES = 8                 # LRU entry-count cap across all tables
_BYTE_BUDGET = 4 * 1024**3       # LRU byte cap across all cached entries

# Timestamps ride as exact (cell index, intra-cell ms offset) int32 pairs:
# cell < nb <= _CELL_CAP and intra < res < 2^31, so both halves are exact
# where a single int32/f32 tick would lose precision on long spans.
_I32_MAX = 2**31 - 1

_DEVICE_RANGE_OPS = {
    "count", "sum", "mean", "min", "max",
    "var_pop", "var_samp", "stddev_pop", "stddev_samp",
    "first_value", "last_value",
}

# build-state keys needed per op (field-level arrays, all (S, NB))
_STATE_KEYS = {
    "count": ("n",),
    "sum": ("s", "n"),
    "mean": ("s", "n"),
    "min": ("mn", "n"),
    "max": ("mx", "n"),
    "var_pop": ("s", "s2", "n"),
    "var_samp": ("s", "s2", "n"),
    "stddev_pop": ("s", "s2", "n"),
    "stddev_samp": ("s", "s2", "n"),
    # first/last carry both directions: the window combine picks winners
    # from either half, so it needs all four arrays regardless of which op
    # the query asked for (mirrors executor.py _bucket_partials).
    # "if"/"il" are the intra-cell ms offsets of the first/last row.
    "first_value": ("vf", "if", "vl", "il", "n"),
    "last_value": ("vf", "if", "vl", "il", "n"),
}


@dataclass
class _Entry:
    version: tuple
    res: int                     # cell width, ms
    phase: int                   # cell boundary phase: boundaries ≡ phase (mod res)
    t0c: int                     # absolute ms of cell 0's left edge
    nb: int                      # cells of the time axis: its capacity
    num_series: int
    registry: object             # SeriesRegistry of the building scan
    rows_scanned: int
    # field name -> state key -> device (S, NB) array
    fields: dict = dc_field(default_factory=dict)
    # field name -> True when all data + f32 partials are finite, so
    # presence can ride inside the value plane as NaN (halves the
    # device->host result payload)
    nan_ok: dict = dc_field(default_factory=dict)
    # field-independent: row presence / per-cell ts extremes (device)
    nrow: object = None          # (S, NB) int32 rows per cell (all rows)
    imin: object = None          # (S, NB) int32 intra-cell offset of min ts
    imax: object = None          # (S, NB) int32 intra-cell offset of max ts
    # the one per-query memo, keyed by the selection (matcher key +
    # registry version, BY keys): group ids, series mask and group
    # decode, and under "windows" per (lo, hi) cell bounds what the
    # program said of them (exact extent, active groups), so a repeated
    # poll re-derives nothing and a session hit dispatches nothing
    query_memo: dict = dc_field(default_factory=dict)
    # device bytes held; stored (not recomputed) so concurrent readers
    # never iterate `fields` while a grow mutates it
    nbytes: int = 0
    # serializes in-place growth (ensure_states) across query threads
    grow_lock: object = dc_field(default_factory=concurrency.Lock)
    # host-side grid arrays retained by build_entry(keep_host=True) until
    # persist_entry writes the restart snapshot
    host_snap: dict | None = None
    # fields whose "n" state IS entry.nrow (every row valid): stored and
    # transferred once, aliased everywhere else
    n_aliased: set = dc_field(default_factory=set)
    # static program specs this entry has executed (insertion-ordered:
    # dict keys); persisted so a restart can precompile them during
    # warm (cold-start killer)
    program_specs: dict = dc_field(default_factory=dict)
    # cells [0, nb_data) reach the newest row; [nb_data, nb) are spare
    # (`_cell_capacity`) and read as cells outside the data do
    nb_data: int = 0
    # what the upkeep holds a batch of new rows against (host side):
    # the newest ts of every series, and the registry's version, which
    # a new series moves
    last_ts: np.ndarray | None = None
    registry_version: int = -1
    # the version the entry was built or restored at: the session
    # registry's stamp for its result buffers, which the upkeep purges
    # by window instead of by version
    session_version: tuple = ()
    # queries read the planes shared, the upkeep donates them exclusive
    gate: object = dc_field(default_factory=lambda: _PlaneGate())
    # batches the upkeep has applied: a query reads it with the planes
    # and files what it learned of a window (`_file`) only while it
    # stands, so nothing read from older planes outlives the purge
    appends: int = 0

    def recount_bytes(self) -> int:
        per = self.num_series * self.nb * 4
        # count UNIQUE device arrays: "__rows__" and all-valid field "n"
        # states alias entry.nrow
        seen = {id(self.nrow), id(self.imin), id(self.imax)}
        n_arr = 3
        for d in self.fields.values():
            for arr in d.values():
                if id(arr) not in seen:
                    seen.add(id(arr))
                    n_arr += 1
        self.nbytes = per * n_arr
        return self.nbytes

    def bytes(self) -> int:
        return self.nbytes


class DeviceRangeCache:
    """LRU of device grid entries, shared by a QueryEngine.

    Budgeted two ways: entry count (_MAX_ENTRIES) and total device bytes
    across entries (_BYTE_BUDGET) — an entry holds 3 + sum-of-field-state
    arrays, so byte accounting (entry.bytes()), not array-element caps,
    bounds HBM use."""

    def __init__(self, byte_budget: int = _BYTE_BUDGET):
        self._entries: dict[tuple, _Entry] = {}
        self._lock = concurrency.Lock()
        self.byte_budget = byte_budget
        self._hits = 0
        self._misses = 0
        self._evictions = 0
        from greptimedb_tpu.telemetry import memory as _memory

        _memory.register_pool(
            "range_grid", "device", self,
            stats=DeviceRangeCache._mem_stats,
            evict=DeviceRangeCache.evict_bytes,
            buffers=DeviceRangeCache._device_buffers,
        )

    def _release(self, entry: "_Entry"):
        """Drop the entry's session-resident result buffers with it
        (query/sessions.py): session keys embed id(entry), so a
        replaced/evicted grid entry's buffers could otherwise never be
        probed again — each (write, poll) cycle would strand one folded
        buffer per query shape until LRU byte pressure."""
        from greptimedb_tpu.query import sessions as _sessions

        self._evictions += 1
        _sessions.global_sessions.purge_table(("range", id(entry)))

    def lookup_compatible(self, tkey, r0: int, align_to: int
                          ) -> _Entry | None:
        """Find a live entry for `tkey` whose resolution serves a query
        with bucket gcd r0 and phase align_to; LRU-touches the hit. The
        entry may be behind the table's version: the caller brings it
        forward (`_upkeep`) or drops it (`evict`)."""
        with self._lock:
            for key in list(self._entries):
                if key[0] != tkey:
                    continue
                e = self._entries[key]
                if r0 % e.res == 0 and align_to % e.res == e.phase:
                    self._entries.pop(key)
                    self._entries[key] = e
                    self._hits += 1
                    return e
            self._misses += 1
        return None

    def evict(self, entry: _Entry) -> None:
        """Drop an entry that cannot be brought forward."""
        with self._lock:
            for key, e in list(self._entries.items()):
                if e is entry:
                    del self._entries[key]
                    self._release(e)

    def insert(self, key: tuple, entry: _Entry):
        with self._lock:
            self._insert_locked(key, entry)
        from greptimedb_tpu.telemetry import memory as _memory

        _memory.note_device_bytes()

    def _insert_locked(self, key: tuple, entry: _Entry):
        old = self._entries.pop(key, None)
        if old is not None and old is not entry:
            self._release(old)
        total = sum(e.bytes() for e in self._entries.values())
        total += entry.bytes()
        while self._entries and (
            len(self._entries) >= _MAX_ENTRIES
            or total > self.byte_budget
        ):
            victim = self._entries.pop(next(iter(self._entries)))
            self._release(victim)
            total -= victim.bytes()
        self._entries[key] = entry

    def has_table(self, tkey) -> bool:
        with self._lock:
            return any(k[0] == tkey for k in self._entries)

    def insert_if_table_absent(self, key: tuple, entry: _Entry) -> bool:
        """Insert unless ANY live entry exists for the same table —
        the warm thread must never clobber an entry a query built."""
        with self._lock:
            if any(k[0] == key[0] for k in self._entries):
                return False
            self._insert_locked(key, entry)
        # warm-start restores grow the pool like any query-path insert:
        # the global watermark applies from the first restored grid,
        # not from the first later query
        from greptimedb_tpu.telemetry import memory as _memory

        _memory.note_device_bytes()
        return True

    def total_bytes(self) -> int:
        with self._lock:
            return sum(e.bytes() for e in self._entries.values())

    def reserve_growth(self, entry: _Entry, add: int) -> bool:
        """Admit an in-place entry growth of `add` bytes against the
        AGGREGATE budget, evicting other LRU entries if needed. False ->
        the growth cannot fit (caller falls back to host)."""
        with self._lock:
            if entry.bytes() + add > self.byte_budget:
                return False
            total = sum(e.bytes() for e in self._entries.values()) + add
            for key in list(self._entries):
                if total <= self.byte_budget:
                    break
                if self._entries[key] is entry:
                    continue
                victim = self._entries.pop(key)
                self._release(victim)
                total -= victim.bytes()
            return total <= self.byte_budget

    def clear(self):
        with self._lock:
            for e in self._entries.values():
                self._release(e)
            self._entries.clear()

    # ------------------------------------------------------------------
    # memory accountant surface (telemetry/memory.py)
    # ------------------------------------------------------------------
    def _mem_stats(self) -> dict:
        with self._lock:
            total = 0
            for e in self._entries.values():
                total += e.bytes()
                # per-query-shape group-id device inputs ride the
                # entry (query_memo) but are outside recount_bytes'
                # grid contract — the watermark must still see them
                # (the census enumerates the same arrays)
                for memo in list(e.query_memo.values()):
                    arr = memo["gid"]
                    if arr is not None:
                        total += int(arr.nbytes)
            return {
                "bytes": total,
                "entries": len(self._entries),
                "budget_bytes": self.byte_budget,
                "max_entries": _MAX_ENTRIES,
                "hits": self._hits, "misses": self._misses,
                "evictions": self._evictions,
            }

    def evict_bytes(self, target: int) -> int:
        """Shed LRU grid entries until `target` bytes are freed
        (cross-pool pressure from the global device watermark)."""
        freed = 0
        with self._lock:
            while freed < target and self._entries:
                key = next(iter(self._entries))
                victim = self._entries.pop(key)
                self._release(victim)
                freed += victim.bytes()
        return freed

    def _device_buffers(self):
        out = []
        with self._lock:
            for key, e in self._entries.items():
                tag = f"range:{key[0][0]}.{key[0][1]}"
                seen = set()
                for arr in (e.nrow, e.imin, e.imax):
                    if arr is not None and id(arr) not in seen:
                        seen.add(id(arr))
                        out.append((arr, tag))
                # list() snapshots: fields/query_memo grow under the
                # entry's grow_lock / query path, not this cache lock
                for fname, d in list(e.fields.items()):
                    for arr in list(d.values()):
                        if id(arr) not in seen:
                            seen.add(id(arr))
                            out.append((arr, f"{tag}:{fname}"))
                # per-query-shape device inputs (group-id uploads) the
                # steady state keeps resident — without owner tags the
                # census would read them as leaks
                for memo in list(e.query_memo.values()):
                    arr = memo["gid"]
                    if arr is not None and id(arr) not in seen:
                        seen.add(id(arr))
                        out.append((arr, f"{tag}:query_memo"))
        return out


# ----------------------------------------------------------------------
# eligibility
# ----------------------------------------------------------------------

def plan_lowering(plan, table):
    """Return (field per item, op per item) when `plan` can lower onto cell
    partials; None -> host path. Checks everything except cell alignment of
    time bounds (needs the entry's resolution, checked later)."""
    if plan.kind != "range":
        return None
    if plan.scan.residual is not None:
        return None
    items = []
    for it in plan.range_items:
        if it.op not in _DEVICE_RANGE_OPS:
            return None
        if it.arg is None:
            if it.op != "count":
                return None
            items.append(("__rows__", it.op))
            continue
        if not isinstance(it.arg, A.Column):
            return None
        cs = table.schema.maybe_column(it.arg.name)
        if cs is None or cs.is_tag or cs.is_time_index:
            return None
        if cs.data_type.is_string():
            return None
        items.append((it.arg.name, it.op))
    for k in plan.keys:
        if not (isinstance(k.expr, A.Column) and k.expr.name in table.tag_names):
            return None
    return items


# ----------------------------------------------------------------------
# cache build (host, vectorized over the sorted scan)
# ----------------------------------------------------------------------

def _is_sid_ts_sorted(sid: np.ndarray, ts: np.ndarray) -> bool:
    if len(sid) < 2:
        return True
    d_sid = np.diff(sid.astype(np.int64))
    return bool(np.all((d_sid > 0) | ((d_sid == 0) & (np.diff(ts) >= 0))))


def _pick_res(plan, ts: np.ndarray, num_series: int) -> int | None:
    r0 = plan.align_ms
    for it in plan.range_items:
        r0 = math.gcd(r0, it.range_ms)
    # estimate the data interval from time deltas (sorted by (sid, ts))
    if len(ts) > 1:
        d = np.diff(ts)
        pos = d[d > 0]
        if len(pos):
            res = math.gcd(r0, int(pos.min()))
            span = int(ts[-1]) - int(ts[0]) + res
            if num_series * (span // res + 1) <= _CELL_CAP:
                return res
    span = int(ts[-1]) - int(ts[0]) + r0 if len(ts) else r0
    if num_series * (span // r0 + 1) > _CELL_CAP:
        return None
    return r0


def _make_put(mesh):
    """Host->device placement: single-device jnp.asarray, or series-axis
    sharding over the mesh (SURVEY.md §2.7 #1 — the region-partitioning
    analog; XLA inserts the cross-shard collectives for group folds)."""
    import jax
    import jax.numpy as jnp

    if mesh is None:
        return jnp.asarray, jnp.asarray
    from jax.sharding import NamedSharding, PartitionSpec as P

    from greptimedb_tpu.parallel.mesh import AXIS_SHARD

    s2d = NamedSharding(mesh, P(AXIS_SHARD, None))
    s1d = NamedSharding(mesh, P(AXIS_SHARD))

    def put2(x):
        return jax.device_put(np.asarray(x), s2d)

    def put1(x):
        return jax.device_put(np.asarray(x), s1d)

    return put2, put1


def _series_pad(s: int, mesh) -> int:
    """Pad the series axis to the fold-block multiple (and the shard
    count): block boundaries are part of the numeric contract — the
    blocked group fold combines per-block f32 partials in one fixed
    order, so sharded and single-device entries of the same table get
    IDENTICAL block contents and bit-identical results."""
    from greptimedb_tpu.parallel.mesh import AXIS_SHARD, FOLD_BLOCKS

    mult = FOLD_BLOCKS
    if mesh is not None:
        n = mesh.shape[AXIS_SHARD]
        mult = mult * n // math.gcd(mult, n)
    return -(-s // mult) * mult


_NO_ROW = np.iinfo(np.int64).min


def _series_last_ts(nrow, imax, t0c: int, res: int) -> np.ndarray:
    """(S,) int64: the newest ts each series holds in the host-side
    (S, NB) planes, `_NO_ROW` for a series with none."""
    has = np.asarray(nrow) > 0
    nb = has.shape[1]
    last_cell = nb - 1 - np.argmax(has[:, ::-1], axis=1)
    last = (t0c + last_cell.astype(np.int64) * res
            + np.asarray(imax)[np.arange(len(has)), last_cell])
    return np.where(has.any(axis=1), last, _NO_ROW)


def build_entry(plan, table, items, mesh=None, mesh_opts=None,
                byte_budget: int = _BYTE_BUDGET,
                keep_host: bool = False) -> _Entry | None:
    """Scan the table once and build the device cell-state grids.

    With a mesh, the replicate-vs-shard planner decides placement from
    the series count: large grids get a series-axis NamedSharding (the
    shard_map range program recombines group folds with collectives),
    small ones stay single-device. keep_host=True additionally retains
    the host-side grid arrays on entry.host_snap so persist_entry can
    write a restart snapshot without a device readback."""
    import jax.numpy as jnp

    needed: dict[str, set] = {}
    for fname, op in items:
        if fname != "__rows__":
            needed.setdefault(fname, set()).update(_STATE_KEYS[op])
    # version BEFORE the scan: a write racing the build leaves the entry
    # stamped stale, so the next query rebuilds (conservative, never mixes)
    version = table.data_version()
    data = table.scan(field_names=sorted(needed))
    rows = data.rows
    if rows is None or len(rows) == 0:
        return None
    ts = rows.ts
    sid = rows.sid
    if not _is_sid_ts_sorted(sid, ts):
        order = np.lexsort((ts, sid))
        ts = ts[order]
        sid = sid[order]
        reorder = order
    else:
        reorder = None
    S = max(data.registry.num_series, int(sid.max()) + 1 if len(sid) else 1)
    decision = None
    if mesh is not None:
        from greptimedb_tpu.query.planner import decide_mesh_execution

        decision = decide_mesh_execution(
            mesh, kind="range", series=S, ops=[op for _, op in items],
            opts=mesh_opts,
        )
        if not decision.shard:
            mesh = None
    S = _series_pad(S, mesh)
    res = _pick_res(plan, ts, S)
    if res is None or res >= _I32_MAX:
        # res >= 2^31 ms (~25-day cells) would overflow the exact int32
        # intra-cell offsets; such queries fall back to the host path.
        return None
    phase = plan.align_to % res
    data_min = int(ts.min())
    data_max = int(ts.max())
    t0c = phase + ((data_min - phase) // res) * res
    nb_data = (data_max - t0c) // res + 1
    # projected device bytes for the full entry must fit the cache budget
    n_arr = 3 + sum(len(k) for k in needed.values())

    def fits(cells):
        return (S * cells <= _CELL_CAP
                and S * cells * 4 * n_arr <= byte_budget)

    nb = _cell_capacity(nb_data, fits)
    if not fits(nb):
        return None

    cell = (ts - t0c) // res
    seg = sid.astype(np.int64) * nb + cell
    nseg = S * nb
    # exact intra-cell ms offset (0 <= intra < res < 2^31)
    intra = (ts - t0c - cell * res).astype(np.int64)

    entry = _Entry(
        version=version, res=res, phase=phase, t0c=t0c, nb=nb,
        num_series=S, registry=data.registry,
        rows_scanned=len(rows), nb_data=nb_data,
        registry_version=data.registry.version, session_version=version,
    )
    entry.mesh = mesh
    entry.mesh_decision = decision
    snap = {} if keep_host else None
    put2, _ = _make_put(mesh)
    shape = (S, nb)
    nrow = np.bincount(seg, minlength=nseg)
    nrow = nrow.reshape(shape).astype(np.int32)
    if snap is not None:
        snap["nrow"] = nrow
    entry.nrow = put2(nrow)
    # per-cell ts extremes: rows are (sid, ts)-sorted, so each seg run's
    # first/last row give the extremes directly
    change = np.empty(len(seg), bool)
    if len(seg):
        change[0] = True
        change[1:] = seg[1:] != seg[:-1]
    starts = np.nonzero(change)[0]
    ends = np.r_[starts[1:], len(seg)] - 1
    useg = seg[starts]
    imin = np.zeros(nseg, np.int64)
    imax = np.zeros(nseg, np.int64)
    imin[useg] = intra[starts]
    imax[useg] = intra[ends]
    imin = imin.reshape(shape).astype(np.int32)
    imax = imax.reshape(shape).astype(np.int32)
    if snap is not None:
        snap["imin"] = imin
        snap["imax"] = imax
    entry.imin = put2(imin)
    entry.imax = put2(imax)
    entry.last_ts = _series_last_ts(nrow, imax, t0c, res)

    for fname, keys in needed.items():
        vals = rows.fields[fname]
        if reorder is not None:
            vals = vals[reorder]
        vals = vals.astype(np.float64, copy=False)
        if rows.field_valid is not None and fname in rows.field_valid:
            valid = rows.field_valid[fname]
            if reorder is not None:
                valid = valid[reorder]
        else:
            valid = np.ones(len(vals), bool)
        states, nan_ok, n_aliased = _build_field_states(
            keys, vals, valid, seg, nseg, intra, shape, put2,
            snap=snap, snap_prefix=f"f::{fname}::",
            nrow_alias=entry.nrow,
        )
        entry.fields[fname] = states
        entry.nan_ok[fname] = nan_ok
        if n_aliased:
            entry.n_aliased.add(fname)
    _ensure_rows_pseudo(entry, items, jnp)
    entry.recount_bytes()
    if snap is not None:
        # the host copies are of this version whatever the entry's
        # planes become later (`persist_entry` writes no other)
        snap["__stamp__"] = (version, nb_data)
        entry.host_snap = snap
    return entry


def _build_field_states(keys, vals, valid, seg, nseg, intra, shape, put,
                        snap=None, snap_prefix="", nrow_alias=None):
    out = {}

    def emit(key, arr):
        if snap is not None:
            snap[snap_prefix + key] = arr
        out[key] = put(arr)

    all_valid = valid.all()
    vm = vals if all_valid else np.where(valid, vals, 0.0)
    nan_ok = bool(np.isfinite(vm).all())
    n_aliased = False
    if all_valid and nrow_alias is not None:
        # every row carries this field: its per-cell count IS the row
        # count — alias the device array (no second build/transfer)
        out["n"] = nrow_alias
        n_aliased = True
    else:
        n = (np.bincount(seg, minlength=nseg) if all_valid
             else np.bincount(seg[valid], minlength=nseg))
        emit("n", n.reshape(shape).astype(np.int32))
    if "s" in keys:
        s = np.bincount(seg, weights=vm, minlength=nseg).astype(np.float32)
        nan_ok = nan_ok and bool(np.isfinite(s).all())
        emit("s", s.reshape(shape))
    if "s2" in keys:
        s2 = np.bincount(seg, weights=vm * vm, minlength=nseg).astype(
            np.float32
        )
        nan_ok = nan_ok and bool(np.isfinite(s2).all())
        emit("s2", s2.reshape(shape))
    if keys & {"mn", "mx", "vf", "if", "vl", "il"}:
        segf = seg if all_valid else seg[valid]
        vf_ = vals if all_valid else vals[valid]
        intraf = intra if all_valid else intra[valid]
        change = np.empty(len(segf), bool)
        if len(segf):
            change[0] = True
            change[1:] = segf[1:] != segf[:-1]
        starts = np.nonzero(change)[0]
        ends = np.r_[starts[1:], len(segf)] - 1
        useg = segf[starts]
        if "mn" in keys:
            arr = np.full(nseg, np.inf)
            if len(starts):
                arr[useg] = np.minimum.reduceat(vf_, starts)
            emit("mn", arr.reshape(shape).astype(np.float32))
        if "mx" in keys:
            arr = np.full(nseg, -np.inf)
            if len(starts):
                arr[useg] = np.maximum.reduceat(vf_, starts)
            emit("mx", arr.reshape(shape).astype(np.float32))
        if "vf" in keys:
            arr = np.zeros(nseg)
            t = np.zeros(nseg, np.int64)
            arr[useg] = vf_[starts]
            t[useg] = intraf[starts]
            emit("vf", arr.reshape(shape).astype(np.float32))
            emit("if", t.reshape(shape).astype(np.int32))
        if "vl" in keys:
            arr = np.zeros(nseg)
            t = np.zeros(nseg, np.int64)
            arr[useg] = vf_[ends]
            t[useg] = intraf[ends]
            emit("vl", arr.reshape(shape).astype(np.float32))
            emit("il", t.reshape(shape).astype(np.int32))
    return out, nan_ok, n_aliased


def _ensure_rows_pseudo(entry, items, jnp):
    if any(f == "__rows__" for f, _ in items):
        entry.fields.setdefault("__rows__", {})["n"] = entry.nrow


# ----------------------------------------------------------------------
# restart snapshots: the cold-start killer. A built entry's host-side
# grids persist under the region dir; reopening the table restores them
# with puts only (no SST scan, no host aggregation), and the persistent
# XLA compilation cache (instance.py) covers the compile. Analog of the
# reference keeping its page cache warm across queries — here made
# durable across process restarts.
# ----------------------------------------------------------------------

_SNAP_DIRNAME = "device_cache"
_snapshot_io_lock = concurrency.Lock()
# per-table restore serialization: the warm thread and a racing query
# must not both decode + device-transfer the same GB-scale snapshot
_restore_locks: dict = {}


def _restore_lock(tkey) -> threading.Lock:
    with _snapshot_io_lock:
        return _restore_locks.setdefault(tkey, concurrency.Lock())

def _ver_json(version) -> str:
    import json as _json

    def norm(v):
        if isinstance(v, (list, tuple)):
            return [norm(x) for x in v]
        return int(v) if isinstance(v, (bool, np.integer)) else v

    return _json.dumps(norm(version))


_SNAP_MAGIC = b"GTDEVC1\n"
_SNAP_ALIGN = 64


def persist_entry(entry: _Entry, table) -> bool:
    """Write the entry's host grids as a restart snapshot under the
    region dir (single-region tables only). Clears entry.host_snap. A
    snapshot is read back only at the version it was taken at, so none
    is written once the table has been written to since the build: under
    steady writes that is every build, and a whole grid would go to
    disk for the next body to make stale.

    Format: magic + u64 json-meta length + meta + 64-aligned raw array
    bytes — flat on purpose, so load_entry_snapshot can memory-map each
    array and hand zero-copy views straight to the device put (no zip
    decode, no host-side copy of GB-scale grids)."""
    snap = entry.host_snap
    entry.host_snap = None
    if snap is None or len(table.regions) != 1:
        return False
    version, nb_data = snap.pop("__stamp__")
    if table.data_version() != version:
        return False
    region = table.regions[0]
    import io
    import json as _json
    import os

    names = list(snap)
    layout = []
    off = 0
    for k in names:
        arr = np.ascontiguousarray(snap[k])
        snap[k] = arr
        pad = (-off) % _SNAP_ALIGN
        off += pad
        layout.append({
            "key": k, "dtype": arr.dtype.str, "shape": list(arr.shape),
            "offset": off, "nbytes": arr.nbytes,
        })
        off += arr.nbytes
    meta = {
        "version": _ver_json(version),
        "res": entry.res, "phase": entry.phase, "t0c": entry.t0c,
        "nb": entry.nb, "nb_data": nb_data,
        "num_series": entry.num_series,
        "rows_scanned": entry.rows_scanned,
        "nan_ok": {k: bool(v) for k, v in entry.nan_ok.items()},
        "n_alias": sorted(entry.n_aliased),
        "arrays": layout,
    }
    mb = _json.dumps(meta).encode()
    header = _SNAP_MAGIC + len(mb).to_bytes(8, "little") + mb
    data_start = len(header) + ((-len(header)) % _SNAP_ALIGN)

    def _stream(f):
        f.write(header)
        f.write(b"\x00" * (data_start - len(header)))
        pos = 0
        for k, ent in zip(names, layout):
            f.write(b"\x00" * (ent["offset"] - pos))
            f.write(memoryview(snap[k]).cast("B"))
            pos = ent["offset"] + ent["nbytes"]

    path = (f"{region.prefix}/{_SNAP_DIRNAME}/"
            f"grid_{entry.res}_{entry.phase}.gtdc")
    try:
        with _snapshot_io_lock:
            try:
                lp = region.store.local_path(path)
            except NotImplementedError:
                buf = io.BytesIO()
                _stream(buf)
                region.store.write(path, buf.getvalue())
            else:
                # stream straight to disk: snapshots can be ~GB-scale
                os.makedirs(os.path.dirname(lp), exist_ok=True)
                tmp = lp + ".tmp"
                with open(tmp, "wb") as f:
                    _stream(f)
                os.replace(tmp, lp)
        return True
    except Exception:
        return False


def _snap_open(region, path):
    """-> (meta, fetch(layout_entry) -> np view). Local files memory-map
    (zero host copies); object-store bytes slice via frombuffer."""
    import json as _json

    try:
        lp = region.store.local_read_path(path)
    except (NotImplementedError, FileNotFoundError, OSError):
        raw = region.store.read(path)
        if raw[:len(_SNAP_MAGIC)] != _SNAP_MAGIC:
            raise ValueError("bad snapshot magic")
        mlen = int.from_bytes(
            raw[len(_SNAP_MAGIC):len(_SNAP_MAGIC) + 8], "little"
        )
        hdr_end = len(_SNAP_MAGIC) + 8 + mlen
        meta = _json.loads(raw[len(_SNAP_MAGIC) + 8:hdr_end])
        data_start = hdr_end + ((-hdr_end) % _SNAP_ALIGN)

        def fetch(ent):
            return np.frombuffer(
                raw, np.dtype(ent["dtype"]),
                count=ent["nbytes"] // np.dtype(ent["dtype"]).itemsize,
                offset=data_start + ent["offset"],
            ).reshape(ent["shape"])

        return meta, fetch

    with open(lp, "rb") as f:
        magic = f.read(len(_SNAP_MAGIC))
        if magic != _SNAP_MAGIC:
            raise ValueError("bad snapshot magic")
        mlen = int.from_bytes(f.read(8), "little")
        meta = _json.loads(f.read(mlen))
    hdr_end = len(_SNAP_MAGIC) + 8 + mlen
    data_start = hdr_end + ((-hdr_end) % _SNAP_ALIGN)

    def fetch(ent):
        return np.memmap(
            lp, dtype=np.dtype(ent["dtype"]), mode="r",
            offset=data_start + ent["offset"],
            shape=tuple(ent["shape"]),
        )

    return meta, fetch


def load_entry_snapshot(table, r0: int, align_to: int, mesh=None,
                        mesh_opts=None,
                        byte_budget: int = _BYTE_BUDGET) -> _Entry | None:
    """Restore a compatible snapshot for the table's CURRENT data
    version, deleting stale snapshot files as they are found."""
    if len(table.regions) != 1:
        return None
    region = table.regions[0]
    prefix = f"{region.prefix}/{_SNAP_DIRNAME}/"

    # captured ONCE: the restored entry must be stamped with the version
    # that was validated, or a racing write could stamp it newer than the
    # grids really are (same discipline as build_entry's pre-scan stamp)
    version = table.data_version()
    cur_ver = _ver_json(version)
    with _snapshot_io_lock:
        metas = region.store.list(prefix)
    for m in metas:
        # cheap pre-filter: res/phase ride in the filename
        base = m.path.rsplit("/", 1)[-1]
        if base.startswith("grid_") and base.endswith(".gtdc"):
            try:
                _, res_s, phase_s = base[:-5].split("_")
                if (r0 % int(res_s) != 0
                        or align_to % int(res_s) != int(phase_s)):
                    continue
            except ValueError:
                pass
        try:
            with _snapshot_io_lock:
                meta, fetch = _snap_open(region, m.path)
        except Exception:
            region.store.delete(m.path)
            continue
        if meta["version"] != cur_ver:
            # stale: data changed since this snapshot was written
            region.store.delete(m.path)
            continue
        res, phase = meta["res"], meta["phase"]
        if r0 % res != 0 or align_to % res != phase:
            continue
        n_arr = len(meta["arrays"])
        if meta["num_series"] * meta["nb"] * 4 * n_arr > byte_budget:
            continue
        decision = None
        if mesh is not None:
            from greptimedb_tpu.query.planner import (
                decide_mesh_execution,
            )
            from greptimedb_tpu.parallel.mesh import AXIS_SHARD

            decision = decide_mesh_execution(
                mesh, kind="range", series=meta["num_series"],
                ops=(), opts=mesh_opts,
            )
            if decision.shard and meta["num_series"] % mesh.shape[
                    AXIS_SHARD]:
                # snapshots from an unpadded/unsharded build stay
                # single-device (the series axis must split evenly)
                from greptimedb_tpu.query.planner import MeshDecision

                decision = MeshDecision("replicate", "snapshot_unaligned",
                                        devices=decision.devices)
            if not decision.shard:
                mesh = None
        put2, _ = _make_put(mesh)
        entry = _Entry(
            version=version, res=res, phase=phase,
            t0c=meta["t0c"], nb=meta["nb"],
            num_series=meta["num_series"], registry=region.series,
            rows_scanned=meta["rows_scanned"],
            # a snapshot from before the spare cells has none
            nb_data=meta.get("nb_data", meta["nb"]),
            registry_version=region.series.version,
            session_version=version,
        )
        entry.mesh = mesh
        entry.mesh_decision = decision
        by_key = {ent["key"]: ent for ent in meta["arrays"]}
        nrow, imax = fetch(by_key["nrow"]), fetch(by_key["imax"])
        entry.nrow = put2(nrow)
        entry.imin = put2(fetch(by_key["imin"]))
        entry.imax = put2(imax)
        entry.last_ts = _series_last_ts(nrow, imax, entry.t0c, res)
        for key, ent in by_key.items():
            if not key.startswith("f::"):
                continue
            _, fname, skey = key.split("::", 2)
            entry.fields.setdefault(fname, {})[skey] = put2(fetch(ent))
        for fname in meta.get("n_alias", []):
            entry.fields.setdefault(fname, {})["n"] = entry.nrow
            entry.n_aliased.add(fname)
        for fname in entry.fields:
            entry.nan_ok[fname] = bool(meta["nan_ok"].get(fname, False))
        entry.recount_bytes()
        return entry
    return None


def _program_specs_path(entry: _Entry, region) -> str:
    return (f"{region.prefix}/{_SNAP_DIRNAME}/"
            f"programs_{entry.res}_{entry.phase}.json")


# what a persisted spec list was written for: the fused programs'
# signatures and spec meaning (n_steps and g of the BOUND window and the
# MATCHED series, and the rows program's bucket: "fused-2"). A file of
# another signature names programs no query would ask for, so it is
# skipped.
_SPECS_SIGNATURE = "fused-2"


def _persist_program_specs(entry: _Entry, table) -> None:
    """Record the static jit specs this entry has served (capped), so a
    restarted process can precompile them during warm — the first query
    after restore then pays steady-state latency, not trace + XLA
    compile-cache load (VERDICT r3 cold-start task)."""
    if len(table.regions) != 1:
        return
    import json as _json

    region = table.regions[0]
    # most-RECENT 8 (insertion order): the specs a restart will actually
    # be asked for again
    specs = list(entry.program_specs)[-8:]
    doc = {"signature": _SPECS_SIGNATURE, "specs": [
        {"stride": st, "n_steps": ns, "g": g, "fold": fo,
         "nanenc": ne, "items": [list(it) for it in items], "rows": kb}
        for st, ns, g, fo, ne, items, kb in specs
    ]}
    try:
        region.store.write(
            _program_specs_path(entry, region),
            _json.dumps(doc).encode(),
        )
    except Exception as e:  # noqa: BLE001
        # advisory warm-start metadata only; queries recompile lazily
        _log.debug("program-spec snapshot write skipped: %s", e)


class _WarmScratch:
    """Device buffers pinned by the warm-start precompile pass (the
    zero sid/mask spec inputs each persisted program is re-invoked
    with). They exist only while `precompile_programs` runs, but
    without an owner tag every warm restart would read as a transient
    device leak in the census — so they register as their own pool and
    drop when the pass finishes."""

    def __init__(self):
        self._lock = concurrency.Lock()
        self._bufs: dict[int, tuple] = {}   # id -> (arr, label)
        from greptimedb_tpu.telemetry import memory as _memory

        _memory.register_pool(
            "warm_precompile", "device", self,
            stats=_WarmScratch._mem_stats,
            buffers=_WarmScratch._device_buffers,
        )

    def hold(self, arr, label: str):
        with self._lock:
            self._bufs[id(arr)] = (arr, label)
        return arr

    def drop(self, *arrs):
        with self._lock:
            for arr in arrs:
                self._bufs.pop(id(arr), None)

    def _mem_stats(self) -> dict:
        with self._lock:
            return {
                "bytes": sum(
                    int(getattr(a, "nbytes", 0))
                    for a, _ in self._bufs.values()
                ),
                "entries": len(self._bufs),
            }

    def _device_buffers(self):
        with self._lock:
            return [
                (a, f"warm_precompile:{label}")
                for a, label in self._bufs.values()
            ]


_WARM_SCRATCH = _WarmScratch()


def precompile_programs(entry: _Entry, table) -> int:
    """Re-invoke the range program for every persisted spec with the
    restored grids (values are irrelevant — static spec + array
    shapes/dtypes pin the XLA program), so the compilations land in the
    jit cache before the first real query. Returns programs compiled."""
    if len(table.regions) != 1:
        return 0
    import json as _json

    region = table.regions[0]
    try:
        raw = region.store.read(_program_specs_path(entry, region))
        doc = _json.loads(raw)
    except Exception:  # noqa: BLE001 - no specs file: nothing to do
        return 0
    if (not isinstance(doc, dict)
            or doc.get("signature") != _SPECS_SIGNATURE):
        return 0
    entry_mesh = getattr(entry, "mesh", None)
    # the kind of argument a selection's first query passes
    # (_selection_gid): a NumPy value that rides each call on one
    # device, where nothing is pinned; on a mesh a series-sharded device
    # buffer shared by every precompile invocation, pinned (and
    # owner-tagged) in the warm-scratch pool for the duration
    zero_sid = np.zeros(entry.num_series, np.int32)
    if entry_mesh is None:
        return _precompile_loop(entry, doc["specs"], entry_mesh, zero_sid)
    zero_sid = _make_put(entry_mesh)[1](zero_sid)
    label = f"{table.info.database}.{table.info.name}"
    _WARM_SCRATCH.hold(zero_sid, label)
    try:
        return _precompile_loop(entry, doc["specs"], entry_mesh, zero_sid)
    finally:
        _WARM_SCRATCH.drop(zero_sid)


def _precompile_loop(entry, doc, entry_mesh, zero_sid):
    done = 0
    for s in doc:
        try:
            items = tuple(
                (op, int(w), fname) for op, w, fname in s["items"]
            )
            arrs = {}
            usable = True
            for _op, _w, fname in items:
                if fname not in entry.fields:
                    usable = False
                    break
                d = arrs.setdefault(fname, {})
                for bk in _STATE_KEYS[_op]:
                    if bk not in entry.fields[fname]:
                        usable = False
                        break
                    d[bk] = entry.fields[fname][bk]
            if not usable:
                continue
            kb = int(s["rows"])
            spec = (int(s["stride"]), int(s["n_steps"]), int(s["g"]),
                    bool(s["fold"]), bool(s["nanenc"]), items, kb)
            # select the program the way execute_range_device will, so
            # the warm compile is the one that actually serves queries
            # (sharded entries use the shard_map twin except for
            # affordable blocked folds), and pass the kind of argument
            # a query passes
            window = np.array([0, -(2**31) + 1, 2**31 - 1], np.int32)
            inputs = (zero_sid, window)
            program = get_program()
            prog_tag = "single" if entry_mesh is None else "auto_spmd"
            if kb:
                program, prog_tag = get_rows_program(entry_mesh)
                inputs = (np.concatenate(
                    [window, np.zeros(2 * kb, np.int32)]),)
            elif entry_mesh is not None and (
                not spec[3]
                or _fold_blocks(spec[2], entry.nb,
                                entry.num_series) != 1
            ):
                program = get_sharded_program(entry_mesh)
                prog_tag = "sharded"
            # the warm dispatch rides the same device_call boundary
            # (same registry key as the query path) so the profiler
            # row attributes the compile to the program that will
            # serve queries
            from greptimedb_tpu.telemetry import device_trace

            with device_trace.device_call(
                    "range", key=("range", prog_tag, spec)) as dcall:
                out = dcall.run(
                    program,
                    arrs, entry.nrow, entry.imin, entry.imax, *inputs,
                    spec=spec,
                )
                dcall.wait(out)
            entry.program_specs[spec] = True
            done += 1
        except Exception:  # noqa: BLE001 - best-effort warm
            continue
    return done


def persist_entry_async(entry: _Entry, table) -> None:
    if entry.host_snap is None:
        return
    concurrency.Thread(
        target=persist_entry, args=(entry, table),
        daemon=True, name="device-cache-persist",
    ).start()


def force_resident(entry: _Entry) -> None:
    """Wait until every grid of a restored entry is on the device.
    device_put is asynchronous, so the warm thread waits for the
    uploads HERE, off the query path: by the time a query arrives the
    grids are HBM-resident. No program is dispatched — the restore path
    compiles nothing the serving path has not compiled already, so a
    restart adds no entry to the compile cache."""
    import jax

    arrs = [entry.nrow, entry.imin, entry.imax]
    seen = {id(a) for a in arrs}
    for d in entry.fields.values():
        for a in d.values():
            if id(a) not in seen:
                seen.add(id(a))
                arrs.append(a)
    jax.block_until_ready(arrs)


def warm_from_snapshots(engine, catalog) -> int:
    """Restore every table's snapshot into the engine's range cache
    (called in a background thread at instance open). Returns the number
    of entries restored."""
    restored = 0
    for table in catalog.all_tables():
        try:
            db, name = table.info.database, table.info.name
            if len(table.regions) != 1:
                continue
            region = table.regions[0]
            if not region.store.list(f"{region.prefix}/{_SNAP_DIRNAME}/"):
                continue
            tkey = (db, name, id(table))
            cache: DeviceRangeCache = engine.range_cache
            with _restore_lock(tkey):
                if cache.has_table(tkey):
                    continue
                entry = _load_any_snapshot(table, engine)
                inserted = entry is not None and \
                    cache.insert_if_table_absent(
                        (tkey, entry.res, entry.phase), entry
                    )
            if inserted:
                force_resident(entry)
                precompile_programs(entry, table)
                restored += 1
        except Exception:
            continue
    return restored


def _load_any_snapshot(table, engine) -> _Entry | None:
    region = table.regions[0]
    prefix = f"{region.prefix}/{_SNAP_DIRNAME}/"
    for m in region.store.list(prefix):
        base = m.path.rsplit("/", 1)[-1]
        if not base.startswith("grid_") or not base.endswith(".gtdc"):
            continue
        try:
            _, res_s, phase_s = base[:-5].split("_")
            res, phase = int(res_s), int(phase_s)
        except ValueError:
            continue
        entry = load_entry_snapshot(
            table, r0=res, align_to=phase, mesh=getattr(engine, "mesh", None),
            mesh_opts=getattr(engine, "mesh_opts", None),
            byte_budget=engine.range_cache.byte_budget,
        )
        if entry is not None:
            return entry
    return None


def ensure_states(entry: _Entry, plan, table, items,
                  cache: "DeviceRangeCache | None" = None) -> bool:
    """Add any state arrays a new query needs that the entry lacks (same
    resolution/phase, different ops). Returns False if a rescan failed."""
    import jax.numpy as jnp

    with entry.grow_lock:
        grown = _ensure_states_locked(entry, plan, table, items, cache, jnp)
    if grown:
        from greptimedb_tpu.telemetry import memory as _memory

        _memory.note_device_bytes()
    return grown is not False


def _ensure_states_locked(entry, plan, table, items, cache, jnp):
    """-> None (nothing was missing), True (grown) or False (refused)."""
    missing: dict[str, set] = {}
    for fname, op in items:
        if fname == "__rows__":
            _ensure_rows_pseudo(entry, items, jnp)
            continue
        have = entry.fields.get(fname, {})
        want = set(_STATE_KEYS[op]) - set(have)
        if want:
            missing.setdefault(fname, set()).update(want)
    if not missing:
        return None
    if table.data_version() != entry.version:
        return False  # racing write; caller falls back / rebuilds later
    # the same scan, assembly and upload as a build, for the states
    # this entry lacks
    with tracing.child_span("grid.build", grow=True):
        return _grow_states_locked(entry, table, missing, cache)


def _grow_states_locked(entry, table, missing, cache) -> bool:
    # growing the entry in place must respect the same AGGREGATE HBM
    # budget that gated its construction
    add = 0
    for fname, keys in missing.items():
        have = set(entry.fields.get(fname, {}))
        add += entry.num_series * entry.nb * 4 * len((keys | {"n"}) - have)
    if cache is not None and not cache.reserve_growth(entry, add):
        return False
    data = table.scan(field_names=sorted(missing))
    if table.data_version() != entry.version:
        # a write raced the rescan: the new states would include rows the
        # old states lack — refuse the mixed entry (caller falls back; the
        # next query rebuilds against the new version)
        return False
    rows = data.rows
    if rows is None:
        return False
    ts, sid = rows.ts, rows.sid
    order = None
    if not _is_sid_ts_sorted(sid, ts):
        order = np.lexsort((ts, sid))
        ts, sid = ts[order], sid[order]
    cell = (ts - entry.t0c) // entry.res
    seg = sid.astype(np.int64) * entry.nb + cell
    nseg = entry.num_series * entry.nb
    if len(cell) and (cell.min() < 0 or cell.max() >= entry.nb
                      or sid.max() >= entry.num_series):
        return False  # data changed shape under us; caller re-validates
    intra = (ts - entry.t0c - cell * entry.res).astype(np.int64)
    shape = (entry.num_series, entry.nb)
    for fname, keys in missing.items():
        vals = rows.fields[fname]
        valid = (rows.field_valid or {}).get(fname)
        if order is not None:
            vals = vals[order]
            valid = valid[order] if valid is not None else None
        if valid is None:
            valid = np.ones(len(vals), bool)
        put2, _ = _make_put(getattr(entry, "mesh", None))
        states, nan_ok, n_aliased = _build_field_states(
            keys | {"n"}, vals.astype(np.float64, copy=False), valid,
            seg, nseg, intra, shape, put2, nrow_alias=entry.nrow,
        )
        entry.fields.setdefault(fname, {}).update(states)
        entry.nan_ok[fname] = entry.nan_ok.get(fname, True) and nan_ok
        if n_aliased:
            entry.n_aliased.add(fname)
    entry.recount_bytes()
    return True


# ----------------------------------------------------------------------
# upkeep: an entry behind its table's version is brought forward by the
# rows written since, where they are a plain append
# ----------------------------------------------------------------------

# the cells one call of the append program updates, padded to a bucket:
# one compile a bucket and a layout of planes
_APPEND_BUCKETS = (512, 4096, 32768, 262144)
# per-field delta columns, in the order the program reads them
_APPEND_KEYS = ("n", "s", "s2", "mn", "mx", "vf", "if", "vl", "il")
# a value past this could take a cell's float32 `s2` to infinity, which
# only a readback would show: the field's NaN encoding is given up
_NAN_OK_MAX = 1e12


def _upkeep(entry: _Entry, table, cache: "DeviceRangeCache") -> str:
    """Bring `entry` forward to what its table holds now, if what was
    written since its stamp is a plain append: puts of series the entry
    knows, each strictly newer than the newest row the entry holds of
    its series, inside the spare cells, with no truncate, delete or
    schema change between, the entry not on a mesh. Returns "append"
    (also when another query did it first), or "rebuild_<reason>" with
    the entry untouched: the caller evicts it. Runs under
    `query.grid`."""
    with tracing.child_span("grid.upkeep") as span, entry.grow_lock:
        rows, appends, batch = None, 0, None
        if getattr(entry, "mesh", None) is not None:
            reason = "mesh"
        else:
            rows, appends, version, reason = table.appended_since(
                entry.version)
        if reason is None and rows is None:
            # another query brought it forward first
            entry.version = version
            span.attributes["outcome"] = "current"
            return "append"
        if reason is None:
            batch, reason = _append_batch(entry, table, rows)
        if reason is None and batch["dealias"] and not cache.reserve_growth(
                entry, len(batch["dealias"]) * entry.num_series
                * entry.nb * 4):
            reason = "capacity"
        cells_new = 0
        if reason is None:
            cells_new = -entry.nb_data
            _apply_append(entry, batch)
            cells_new += entry.nb_data
            entry.version = version
            _UPKEEP_ROWS.inc(len(rows))
        outcome = "append" if reason is None else "rebuild_" + reason
        span.attributes.update(
            outcome=outcome, bodies=appends, cells_new=cells_new,
            rows=0 if rows is None else len(rows))
        _UPKEEP.labels(outcome).inc()
        return outcome


def _forget_windows_from(entry: _Entry, c0: int) -> None:
    """What the program said of a window, and the result buffers of a
    session, hold only for windows that end at or before cell `c0`, the
    first a batch was appended to; selections (group ids, key columns,
    the series mask) depend on the registry alone and stay. Under the
    gate, exclusive."""
    from greptimedb_tpu.query import sessions as _sessions

    for memo in list(entry.query_memo.values()):
        memo["windows"] = {k: w for k, w in memo["windows"].items()
                           if k[1] <= c0}
    _sessions.global_sessions.purge_table(
        ("range", id(entry)), keep=lambda key: key[1][1] <= c0)


def _append_batch(entry: _Entry, table, rows):
    """The rows written since the entry's stamp as the append program
    takes them -> (batch, None), or (None, reason) where they are no
    plain append. The rows of a cell are reduced here, on the host and
    in float64, exactly as a build reduces them (`_build_field_states`),
    so each cell the batch touches comes with one delta a plane: the
    program's indices are unique."""
    reg = entry.registry
    if reg is not table.regions[0].series:
        return None, "multi_region"
    sid, ts = rows.sid.astype(np.int64), rows.ts
    if reg.version != entry.registry_version or int(sid.max()) >= len(
            entry.last_ts):
        return None, "new_series"
    order = None
    if not _is_sid_ts_sorted(sid, ts):
        order = np.lexsort((ts, sid))
        sid, ts = sid[order], ts[order]
    first = np.r_[True, sid[1:] != sid[:-1]]
    if ((ts[1:] <= ts[:-1]) & ~first[1:]).any() or (
            ts[first] <= entry.last_ts[sid[first]]).any():
        # a row at or before the newest its series holds: an overwrite
        # or a late arrival, which cell states cannot take back
        return None, "out_of_order"
    cell = (ts - entry.t0c) // entry.res
    if int(cell.min()) < 0:
        return None, "out_of_order"
    if int(cell.max()) >= entry.nb:
        return None, "capacity"
    intra = ts - entry.t0c - cell * entry.res
    seg = sid * entry.nb + cell
    change = np.r_[True, seg[1:] != seg[:-1]]
    starts = np.nonzero(change)[0]
    ends = np.r_[starts[1:], len(seg)] - 1
    local = np.cumsum(change) - 1          # the batch's own cell ids
    k = len(starts)
    cols = [sid[starts], cell[starts], ends - starts + 1,
            intra[starts], intra[ends]]
    layout, nan_ok, dealias = [], {}, []
    for fname in sorted(entry.fields):
        if fname == "__rows__":
            continue
        planes = entry.fields[fname]
        vals = rows.fields[fname]
        valid = (rows.field_valid or {}).get(fname)
        if order is not None:
            vals = vals[order]
            valid = valid[order] if valid is not None else None
        if valid is None:
            valid = np.ones(len(vals), bool)
        delta, ok, _ = _build_field_states(
            set(planes), vals.astype(np.float64, copy=False), valid,
            local, k, intra, (k,), lambda a: a)
        nan_ok[fname] = ok and bool(
            (np.abs(vals[valid]) <= _NAN_OK_MAX).all())
        aliased = fname in entry.n_aliased
        if aliased and not valid.all():
            # the field's count stops being the row count
            dealias.append(fname)
            aliased = False
        keys = tuple(key for key in _APPEND_KEYS if key in planes
                     and not (key == "n" and aliased))
        layout.append((fname, keys, aliased))
        cols.extend(delta[key] for key in keys)
    upd = np.stack([
        c.astype(np.float32).view(np.int32) if c.dtype.kind == "f"
        else c.astype(np.int32) for c in cols])
    run_ends = np.r_[np.nonzero(first)[0][1:] - 1, len(sid) - 1]
    return {
        "upd": upd, "layout": tuple(layout), "nan_ok": nan_ok,
        "dealias": dealias, "newest": (sid[run_ends], ts[run_ends]),
        "cells": (int(cell.min()), int(cell.max()) + 1),
    }, None


def _apply_append(entry: _Entry, batch: dict) -> None:
    """Scatter a batch into the entry's planes: one dispatch of the
    append program a bucket of cells, every plane donated and taken back
    updated. Not waited for: the query's own program queues behind it."""
    import jax.numpy as jnp

    from greptimedb_tpu.telemetry import device_trace

    for fname in batch["dealias"]:
        entry.fields[fname]["n"] = jnp.array(entry.nrow)
        entry.n_aliased.discard(fname)
    layout, upd = batch["layout"], batch["upd"]
    program = get_append_program()
    big = _APPEND_BUCKETS[-1]
    with entry.gate.exclusive():
        for at in range(0, upd.shape[1], big):
            part = upd[:, at:at + big]
            k = part.shape[1]
            kb = next(b for b in _APPEND_BUCKETS if k <= b)
            if k < kb:
                # padding: series past the axis, each its own, dropped
                pad = np.zeros((len(part), kb - k), np.int32)
                pad[0] = entry.num_series + np.arange(kb - k)
                part = np.concatenate([part, pad], axis=1)
            spec = (layout, kb)
            planes = {f: {key: entry.fields[f][key] for key in keys}
                      for f, keys, _ in layout}
            with device_trace.device_call(
                    "grid_upkeep", key=("grid_upkeep", spec),
                    cells=k) as dcall:
                planes, entry.nrow, entry.imin, entry.imax = dcall.run(
                    program, planes, entry.nrow, entry.imin, entry.imax,
                    part, spec=spec)
                dcall.transfer(part.nbytes, "upload")
                dcall.wait(dispatch_only=True)
            for f, _keys, aliased in layout:
                entry.fields[f].update(planes[f])
                if aliased:
                    entry.fields[f]["n"] = entry.nrow
            if "__rows__" in entry.fields:
                entry.fields["__rows__"]["n"] = entry.nrow
        # with the queries still shut out: what one of them files from
        # here on was read from these planes
        entry.appends += 1
        _forget_windows_from(entry, batch["cells"][0])
    for fname, ok in batch["nan_ok"].items():
        entry.nan_ok[fname] = entry.nan_ok.get(fname, True) and ok
    sids, ts = batch["newest"]
    entry.last_ts[sids] = ts
    entry.nb_data = max(entry.nb_data, batch["cells"][1])
    entry.rows_scanned += int(upd[2].sum())
    if batch["dealias"]:
        entry.recount_bytes()


# ----------------------------------------------------------------------
# device programs
# ----------------------------------------------------------------------

def _selection_extent(nrow, imin, imax, sid_mask, lo, hi):
    """Traced inside the range program, ahead of `_range_body`: which
    selected series hold a row in cells [lo, hi) (`sid_active`, (S,)
    bool) and the exact extent of those rows as one int32[4]
    (c_lo, i_lo, c_hi, i_hi): the first and last active cell and the
    intra-cell ms offsets of the earliest and latest row in them — the
    host path's `rows.ts.min()/max()`, from cell states. With no active
    cell c_hi is -1."""
    import jax.numpy as jnp

    nb = nrow.shape[1]
    cells = jnp.arange(nb, dtype=jnp.int32)
    cmask = (cells >= lo) & (cells < hi)
    act = (nrow > 0) & cmask[None, :] & sid_mask[:, None]
    sid_active = jnp.any(act, axis=1)
    colact = jnp.any(act, axis=0)
    big = jnp.int32(_I32_MAX)
    # global min ts lives in the first active cell (cells are
    # time-ordered), global max in the last: two exact int32 stages
    c_lo = jnp.min(jnp.where(colact, cells, big))
    c_hi = jnp.max(jnp.where(colact, cells, -1))
    i_lo = jnp.min(jnp.where(act & (cells[None, :] == c_lo), imin, big))
    i_hi = jnp.max(jnp.where(act & (cells[None, :] == c_hi), imax, -1))
    return sid_active, jnp.stack([c_lo, i_lo, c_hi, i_hi])


def _unpack_inputs(gid, window, spec):
    """The per-query inputs as the program's body takes them. Every
    separate host->device argument is a transfer of its own, so a query
    brings two: `gid` (S,) int32 (unmatched series routed to g: the
    series mask IS `gid < g`) and `window` int32[3] = (delta, lo, hi)."""
    return gid < spec[2], window[0], window[1], window[2]


def _clamp_i32(v: int) -> int:
    """Cell bounds from WHERE ts can land arbitrarily far outside the
    grid; clamping both directions is lossless (comparisons only see
    cells in [0, nb))."""
    return max(-(2**31) + 1, min(int(v), 2**31 - 1))


# jnp window-combine machinery (device mirror of executor.py's
# _combine_states/_shift_left/_window_combine/_finalize_window)

def _identity(key, op, jnp):
    if key == "mn" or (key == "m" and op == "min"):
        return jnp.inf
    if key == "mx" or (key == "m" and op == "max"):
        return -jnp.inf
    if key == "cl":
        return -1          # "no cell": loses every last-cell max
    if key == "cf":
        return _I32_MAX    # "no cell": loses every first-cell min
    if key in ("il", "if"):
        return 0           # intra offsets are tie-broken under cl/cf
    return 0.0


def _shift_left_j(state: dict, k: int, op, jnp):
    out = {}
    for key, v in state.items():
        pad = jnp.full(v.shape[:1] + (k,), _identity(key, op, jnp), v.dtype)
        out[key] = jnp.concatenate([v[:, k:], pad], axis=1)
    return out


def _combine_j(op, a: dict, b: dict, jnp):
    if op == "count":
        return {"n": a["n"] + b["n"]}
    if op in ("sum", "mean"):
        return {"s": a["s"] + b["s"], "n": a["n"] + b["n"]}
    if op == "min":
        return {"m": jnp.minimum(a["m"], b["m"]), "n": a["n"] + b["n"]}
    if op == "max":
        return {"m": jnp.maximum(a["m"], b["m"]), "n": a["n"] + b["n"]}
    if op in ("var_pop", "var_samp", "stddev_pop", "stddev_samp"):
        return {"s": a["s"] + b["s"], "s2": a["s2"] + b["s2"],
                "n": a["n"] + b["n"]}
    if op in ("first_value", "last_value"):
        # exact (cell, intra) lexicographic timestamp compare; within one
        # combine a and b come from distinct cells, so cl/cf ties only
        # happen between empty halves (where the value is irrelevant)
        pick_b_last = (b["cl"] > a["cl"]) | (
            (b["cl"] == a["cl"]) & (b["il"] > a["il"])
        )
        pick_a_first = (a["cf"] < b["cf"]) | (
            (a["cf"] == b["cf"]) & (a["if"] <= b["if"])
        )
        return {
            "vl": jnp.where(pick_b_last, b["vl"], a["vl"]),
            "il": jnp.where(pick_b_last, b["il"], a["il"]),
            "cl": jnp.maximum(a["cl"], b["cl"]),
            "vf": jnp.where(pick_a_first, a["vf"], b["vf"]),
            "if": jnp.where(pick_a_first, a["if"], b["if"]),
            "cf": jnp.minimum(a["cf"], b["cf"]),
            "n": a["n"] + b["n"],
        }
    raise UnsupportedError(op)


def _window_combine_j(op, state: dict, w: int, jnp):
    if w == 1:
        return state
    levels = []
    size = 1
    cur = state
    while size < w:
        nxt = _combine_j(op, cur, _shift_left_j(cur, size, op, jnp), jnp)
        levels.append((size * 2, nxt))
        cur = nxt
        size *= 2
    tables = {1: state}
    for sz, st in levels:
        tables[sz] = st
    result = None
    offset = 0
    remaining = w
    bit = 1
    parts = []
    while remaining:
        if remaining & bit:
            parts.append((offset, bit))
            offset += bit
            remaining &= ~bit
        bit <<= 1
    for off, sz in parts:
        st = tables[sz]
        piece = _shift_left_j(st, off, op, jnp) if off else st
        result = piece if result is None else _combine_j(op, result, piece, jnp)
    return result


def _finalize_j(op, state: dict, jnp):
    n = state["n"].astype(jnp.float32)
    present = state["n"] > 0
    if op == "count":
        return n, present
    if op == "sum":
        return jnp.where(present, state["s"], 0.0), present
    if op == "mean":
        return state["s"] / jnp.maximum(n, 1), present
    if op in ("min", "max"):
        return jnp.where(present, state["m"], 0.0), present
    if op in ("var_pop", "var_samp", "stddev_pop", "stddev_samp"):
        ddof = 1 if op.endswith("_samp") else 0
        mean = state["s"] / jnp.maximum(n, 1)
        var = jnp.maximum(state["s2"] / jnp.maximum(n, 1) - mean * mean, 0.0)
        if ddof:
            var = var * n / jnp.maximum(n - 1, 1)
            present = state["n"] > 1
        if op.startswith("stddev"):
            return jnp.sqrt(var), present
        return var, present
    if op == "last_value":
        return jnp.where(present, state["vl"], 0.0), present
    if op == "first_value":
        return jnp.where(present, state["vf"], 0.0), present
    raise UnsupportedError(op)


def _fold_blocks(g: int, nb: int, s: int) -> int:
    """Series-block count for the group fold. FOLD_BLOCKS when the
    (blocks, g, nb) partial tensor is affordable and the series axis is
    block-aligned; 1 degenerates to the direct fold (sharded execution
    then stays on the auto-SPMD program — see execute_range_device)."""
    from greptimedb_tpu.parallel.mesh import FOLD_BLOCKS

    if s % FOLD_BLOCKS == 0 and FOLD_BLOCKS * g * nb <= 256_000_000:
        return FOLD_BLOCKS
    return 1


def _fold_groups(op, state, gid, g, jnp, ctx):
    """Fold per-series cell states into per-group states.

    n/s/s2 fold through FOLD_BLOCKS aligned series blocks combined in
    one fixed left-fold order (bit-identical across mesh sizes); min/max
    are exactly associative and recombine with pmin/pmax; first/last
    winners resolve by exact (ts, sid) staged selection and a masked
    sum extraction (adding zeros never perturbs the winner value)."""
    import jax

    out = {}
    s_total = state["n"].shape[0] * ctx.shards
    nb = state["n"].shape[1]
    fb = _fold_blocks(g, nb, s_total)
    fb_local = fb // ctx.shards if fb >= ctx.shards else 1
    s_local = state["n"].shape[0]

    def blocked_sum(arr):
        if fb == 1:
            return ctx.psum(
                jax.ops.segment_sum(arr, gid, num_segments=g)
            )
        per = s_local // fb_local
        bid = jnp.arange(s_local, dtype=jnp.int32) // jnp.int32(per)
        seg = jnp.where(gid < g, bid * jnp.int32(g) + gid,
                        jnp.int32(fb_local * g))
        p = jax.ops.segment_sum(arr, seg, num_segments=fb_local * g + 1)
        return ctx.fold_blocks(p[:-1].reshape(fb_local, g, nb))

    out["n"] = blocked_sum(state["n"])
    if "s" in state:
        out["s"] = blocked_sum(state["s"])
    if "s2" in state:
        out["s2"] = blocked_sum(state["s2"])
    if "m" in state:
        f = jax.ops.segment_min if op == "min" else jax.ops.segment_max
        out["m"] = ctx.pext(
            f(state["m"], gid, num_segments=g), take_max=op != "min"
        )

    # first/last across sids within one cell: winner = (ts, sid)
    # lexicographic, matching the host path's deterministic rule
    # (max ts then max sid for last; min ts then min sid for first).
    # The winner is unique, so its value is extracted by a masked
    # segment_sum — exact for any float value incl. ±inf/NaN.
    def fold_extreme(v_arr, t_arr, pick_max):
        has = state["n"] > 0
        sid = ctx.sids(s_local)[:, None]
        seg_ext = jax.ops.segment_max if pick_max else jax.ops.segment_min
        t_id = -1 if pick_max else _I32_MAX
        t = jnp.where(has, t_arr, t_id)
        win_t = ctx.pext(seg_ext(t, gid, num_segments=g),
                         take_max=pick_max)
        tie = has & (t == win_t[gid])
        sid_w = ctx.pext(
            seg_ext(jnp.where(tie, sid, t_id), gid, num_segments=g),
            take_max=pick_max,
        )
        win = tie & (sid == sid_w[gid])
        v = ctx.psum(jax.ops.segment_sum(
            jnp.where(win, v_arr, 0.0), gid, num_segments=g
        ))
        return v, jnp.clip(win_t, 0, _I32_MAX - 1)

    if "il" in state:
        out["vl"], out["il"] = fold_extreme(
            state["vl"], state["il"], pick_max=True
        )
    if "if" in state:
        out["vf"], out["if"] = fold_extreme(
            state["vf"], state["if"], pick_max=False
        )
    return out


def _disjoint_reduce(op, state, n_steps, w, jnp):
    out = {}
    if op in ("first_value", "last_value"):
        G = state["n"].shape[0]
        n_r = state["n"].reshape(G, n_steps, w)
        has = n_r > 0
        pos = jnp.arange(w, dtype=jnp.int32)[None, None, :]
        # cells within a window carry distinct time ranges, so the
        # last/first present cell is the exact winner (no value ties)
        am_l = jnp.argmax(jnp.where(has, pos, -1), axis=2, keepdims=True)
        am_f = jnp.argmin(
            jnp.where(has, pos, _I32_MAX), axis=2, keepdims=True
        )
        for k, v in state.items():
            r = v.reshape(G, n_steps, w)
            if k == "n":
                out[k] = r.sum(axis=2)
            elif k in ("vl", "il", "cl"):
                out[k] = jnp.take_along_axis(r, am_l, axis=2)[..., 0]
            elif k in ("vf", "if", "cf"):
                out[k] = jnp.take_along_axis(r, am_f, axis=2)[..., 0]
        return out
    for k, v in state.items():
        r = v.reshape(v.shape[0], n_steps, w)
        if k in ("n", "s", "s2"):
            out[k] = r.sum(axis=2)
        elif k == "m":
            out[k] = (r.min(axis=2) if op == "min" else r.max(axis=2))
    return out


def _range_body(arrs, gid, sid_mask, delta, lo, hi, spec, ctx):
    """One RANGE query over (local) cell-state grids. spec =
    (stride, n_steps, g, fold, nanenc, items, rows), items (op, w,
    field_key) — everything shape-determining static; `rows` is the
    bucket of the rows program, 0 for the plane programs. Shared
    verbatim by the single-device program, each shard_map shard and the
    rows program (the fold ctx is the only difference), so sharded ==
    unsharded bit-for-bit."""
    import jax
    import jax.numpy as jnp

    stride, n_steps, g, fold, nanenc, items = spec[:6]
    vals_out = []
    pres_out = []
    nb = next(iter(next(iter(arrs.values())).values())).shape[1]
    cell_ids = jnp.arange(nb, dtype=jnp.int32)
    cmask = (cell_ids >= lo) & (cell_ids < hi)
    for op, w, fkey in items:
        raw = arrs[fkey]
        # map build-state keys to combine-state keys
        state = {}
        state["n"] = jnp.where(
            cmask[None, :] & sid_mask[:, None], raw["n"], 0
        )
        # a field's planes hold what every item on it needs: min reads
        # "mn" also where a max beside it brought "mx"
        for bk, ck in (("s", "s"), ("s2", "s2"), (_ck_to_bk("m", op), "m"),
                       ("vl", "vl"), ("il", "il"), ("vf", "vf"),
                       ("if", "if")):
            if bk in raw and ck in _STATE_COMBINE.get(op, ()):
                ident = _identity(bk, op, jnp)
                v = raw[bk]
                if ck not in ("il", "if"):
                    v = v.astype(jnp.float32)
                state[ck] = jnp.where(
                    cmask[None, :] & sid_mask[:, None], v,
                    jnp.asarray(ident, v.dtype),
                )
        if fold:
            state = _fold_groups(op, state, gid, g, jnp, ctx)
        # gather the query's cell window: nb_q cells starting at delta
        nb_q = (n_steps - 1) * stride + w
        idx = delta + jnp.arange(nb_q, dtype=jnp.int32)
        okc = (idx >= 0) & (idx < nb)
        safe = jnp.clip(idx, 0, nb - 1)
        state = {
            k: jnp.where(
                okc[None, :], v[:, safe],
                jnp.asarray(_identity(_ck_to_bk(k, op), op, jnp), v.dtype),
            )
            for k, v in state.items()
        }
        if op in ("first_value", "last_value"):
            # cell keys for the lexicographic (cell, intra) ts compare;
            # window position is monotone in absolute cell index
            pres = state["n"] > 0
            pos = jnp.arange(nb_q, dtype=jnp.int32)[None, :]
            state["cl"] = jnp.where(pres, pos, -1)
            state["cf"] = jnp.where(pres, pos, _I32_MAX)
        if w == stride and nb_q == n_steps * w:
            # disjoint windows: reshape-reduce (the TSBS double-groupby
            # shape — rides dense reductions, no stride doubling)
            combined = _disjoint_reduce(op, state, n_steps, w, jnp)
        else:
            combined = _window_combine_j(op, state, w, jnp)
            combined = {
                k: jax.lax.slice_in_dim(v, 0, (n_steps - 1) * stride + 1,
                                        stride, axis=1)
                for k, v in combined.items()
            }
        v, p = _finalize_j(op, combined, jnp)
        if nanenc:
            # presence rides inside the value plane as NaN (data is
            # known all-finite): halves the result payload
            v = jnp.where(p, v, jnp.nan)
        vals_out.append(v.astype(jnp.float32))
        pres_out.append(p)
    # ONE output array -> one device->host transfer per query (each
    # readback is its own dispatch round trip)
    if nanenc:
        return jnp.stack(vals_out)
    return jnp.concatenate(
        [jnp.stack(vals_out), jnp.stack(pres_out).astype(jnp.float32)],
        axis=0,
    )


def _make_range_program():
    import jax

    from greptimedb_tpu.parallel.dist import LocalFoldCtx

    @functools.partial(jax.jit, static_argnames=("spec",))
    def program(arrs, nrow, imin, imax, gid, window, *, spec):
        """One device RANGE query, whole: -> (result, sid_active,
        extent). The result covers the window the host could bound
        before the dispatch; sid_active and extent let it trim to the
        exact one after the one readback."""
        sid_mask, delta, lo, hi = _unpack_inputs(gid, window, spec)
        sid_active, extent = _selection_extent(nrow, imin, imax,
                                               sid_mask, lo, hi)
        out = _range_body(arrs, gid, sid_mask & sid_active, delta, lo,
                          hi, spec, LocalFoldCtx())
        return out, sid_active, extent

    return program


def _make_sharded_range_program(mesh):
    """shard_map twin of the range program: grids series-sharded over
    AXIS_SHARD, each shard runs _range_body on its slice with the
    collective fold ctx; the selection's extent is computed in the same
    jit ahead of the shard_map, under auto-SPMD. fold=True outputs
    replicate (the post-fold window combine is tiny and runs
    redundantly per shard); fold=False outputs stay series-sharded."""
    import jax
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    from greptimedb_tpu.parallel.dist import ShardFoldCtx
    from greptimedb_tpu.parallel.mesh import AXIS_SHARD

    ns = mesh.shape[AXIS_SHARD]

    @functools.partial(jax.jit, static_argnames=("spec",))
    def program(arrs, nrow, imin, imax, gid, window, *, spec):
        fold = spec[3]
        arr_specs = jax.tree_util.tree_map(
            lambda _: P(AXIS_SHARD, None), arrs
        )
        ctx = ShardFoldCtx(ns)

        def local(arrs, gid, sid_mask, delta, lo, hi):
            return _range_body(arrs, gid, sid_mask, delta, lo, hi, spec,
                               ctx)

        sid_mask, delta, lo, hi = _unpack_inputs(gid, window, spec)
        sid_active, extent = _selection_extent(nrow, imin, imax,
                                               sid_mask, lo, hi)
        out = shard_map(
            local, mesh=mesh,
            in_specs=(arr_specs, P(AXIS_SHARD), P(AXIS_SHARD),
                      P(), P(), P()),
            out_specs=P() if fold else P(None, AXIS_SHARD, None),
            check_vma=False,
        )(arrs, gid, sid_mask & sid_active, delta, lo, hi)
        return out, sid_active, extent

    return program


_SHARDED_RANGE = ProgramCache(_make_sharded_range_program)


def get_sharded_program(mesh):
    return _SHARDED_RANGE.get(mesh)


# A selective query works on the rows the tag index matched: at or
# under _ROWS_MAX matched series the rows program gathers them and runs
# the plane programs' body on [bucket, NB] rows; past it, or with no
# matcher, a plane program masks all S. len(sids) alone chooses, before
# the dispatch; one compile a bucket. Where the crossover lies on the
# chip: PERF.md section 6, PR 32.
_ROW_BUCKETS = (8, 64)
_ROWS_MAX = _ROW_BUCKETS[-1]
# up to this many rows one device reads each with a dynamic slice: the
# chip's compiler then moves K rows, where for a gather of rows it first
# prefetches every plane whole (0.15 of the program's 0.18 ms at 4,000
# series: PERF.md section 6, PR 32), as it does from 64 slices on too
_ROW_SLICES_MAX = 8


def _make_rows_program(mesh):
    """The range program over K gathered rows. One host argument: `vec`
    int32[3 + 2*Kb] = (delta, lo, hi), the matched sids ascending and
    padded to the bucket Kb, their group ids (padding routed to g). Two
    outputs: `out` (fold=False: cut to the g matched rows), which stays
    on the device for the session and for `since` polls, and `packed`,
    one flat int32 of `out`'s bits, the extent and the Kb activity
    flags: all a query of an unknown window reads back. int32, not
    float32: an extent's small numbers are denormals and its -1 a NaN
    as float bits, which no integer copy can touch.

    On a mesh the gathered rows are replicated and the body runs on
    every device with the local fold ctx: the same arithmetic as on one
    device, whatever the mesh size."""
    import jax
    import jax.numpy as jnp

    from greptimedb_tpu.parallel.dist import RowsFoldCtx

    replicated = None
    if mesh is not None:
        from jax.sharding import NamedSharding, PartitionSpec as P

        replicated = NamedSharding(mesh, P())

    @functools.partial(jax.jit, static_argnames=("spec",))
    def program_rows(arrs, nrow, imin, imax, vec, *, spec):
        g, fold, kb = spec[2], spec[3], spec[6]
        delta, lo, hi = vec[0], vec[1], vec[2]
        sids = vec[3:3 + kb]
        gid = vec[3 + kb:3 + 2 * kb]

        def take(plane):
            if replicated is None and kb <= _ROW_SLICES_MAX:
                # a dynamic slice clamps its start as mode="clip" does
                return jnp.concatenate([
                    jax.lax.dynamic_slice_in_dim(plane, sids[i], 1)
                    for i in range(kb)])
            rows = jnp.take(plane, sids, axis=0, mode="clip")
            if replicated is None:
                return rows
            # a gather it is on a mesh at any K: auto-SPMD makes it
            # masked local gathers and an all-reduce, where it would
            # all-gather every plane for the slices
            return jax.lax.with_sharding_constraint(rows, replicated)

        arrs, nrow, imin, imax = jax.tree_util.tree_map(
            take, (arrs, nrow, imin, imax))
        sid_mask = gid < g
        sid_active, extent = _selection_extent(nrow, imin, imax,
                                               sid_mask, lo, hi)
        out = _range_body(arrs, gid, sid_mask & sid_active, delta, lo,
                          hi, spec, RowsFoldCtx(sids))
        if not fold:
            out = out[:, :g]
        packed = jnp.concatenate([
            jax.lax.bitcast_convert_type(out, jnp.int32).ravel(),
            extent, sid_active.astype(jnp.int32),
        ])
        return out, packed

    return program_rows


_ROWS_RANGE = ProgramCache(_make_rows_program)


def get_rows_program(mesh=None):
    """-> (program, its tag in the registry key: mesh twins never share
    a registry row)."""
    return _ROWS_RANGE.get(mesh), "rows" if mesh is None else "rows_mesh"


def _unpack_rows(packed: np.ndarray, shape: tuple, k: int):
    """`packed` of the rows program on the host -> (out, sid_active of
    the K matched rows, extent)."""
    n = math.prod(shape)
    out = packed[:n].view(np.float32).reshape(shape)
    return out, packed[n + 4:n + 4 + k] != 0, packed[n:n + 4]


_STATE_COMBINE = {
    "count": (),
    "sum": ("s",), "mean": ("s",),
    "min": ("m",), "max": ("m",),
    "var_pop": ("s", "s2"), "var_samp": ("s", "s2"),
    "stddev_pop": ("s", "s2"), "stddev_samp": ("s", "s2"),
    "first_value": ("vl", "il", "vf", "if"),
    "last_value": ("vl", "il", "vf", "if"),
}


def _ck_to_bk(ck: str, op: str) -> str:
    if ck == "m":
        return "mn" if op == "min" else "mx"
    return ck


_PROGRAM = None


def get_program():
    global _PROGRAM
    if _PROGRAM is None:
        _PROGRAM = _make_range_program()
    return _PROGRAM


def _make_append_program():
    import jax
    import jax.numpy as jnp

    @functools.partial(jax.jit, static_argnames=("spec",),
                       donate_argnums=(0, 1, 2, 3))
    def grid_append(planes, nrow, imin, imax, upd, *, spec):
        """Every plane of an entry, donated, with the cells of one batch
        of appended rows brought up to date: `upd` int32[columns, Kb]
        holds a cell a column entry (series, cell, rows, first and last
        intra-cell offset, then for each field of `spec`'s layout the
        deltas of its planes, float32 ones as their bits), padded with
        series past the axis, which the scatters drop. The cells are
        distinct, and their rows newer than any the planes hold of
        their series, so states compose as `_build_field_states` builds
        them: counts and sums add, extremes fold, a cell's first row
        stays and its last is replaced."""
        layout, _kb = spec
        at = (upd[0], upd[1])

        def old(plane):
            return plane.at[at].get(mode="clip")

        def put(plane, value):
            return plane.at[at].set(value.astype(plane.dtype), mode="drop",
                                    unique_indices=True)

        def f32(col):
            return jax.lax.bitcast_convert_type(col, jnp.float32)

        n0 = old(nrow)
        fresh = n0 == 0
        out_imin = put(imin, jnp.where(
            fresh, upd[3], jnp.minimum(old(imin), upd[3])))
        out_imax = put(imax, jnp.where(
            fresh, upd[4], jnp.maximum(old(imax), upd[4])))
        out_nrow = put(nrow, n0 + upd[2])
        col = 5
        out = {}
        for fname, keys, aliased in layout:
            cur, new = planes[fname], {}
            delta = {}
            for key in keys:
                delta[key] = upd[col]
                col += 1
            nf0 = n0 if aliased else old(cur["n"])
            cnt = upd[2] if aliased else delta["n"]
            if not aliased:
                new["n"] = put(cur["n"], nf0 + cnt)
            for key in ("s", "s2"):
                if key in keys:
                    new[key] = put(cur[key], old(cur[key]) + f32(delta[key]))
            if "mn" in keys:
                new["mn"] = put(cur["mn"], jnp.minimum(old(cur["mn"]),
                                                      f32(delta["mn"])))
            if "mx" in keys:
                new["mx"] = put(cur["mx"], jnp.maximum(old(cur["mx"]),
                                                      f32(delta["mx"])))
            if "vf" in keys:
                first = (nf0 == 0) & (cnt > 0)
                new["vf"] = put(cur["vf"], jnp.where(
                    first, f32(delta["vf"]), old(cur["vf"])))
                new["if"] = put(cur["if"], jnp.where(
                    first, delta["if"], old(cur["if"])))
            if "vl" in keys:
                new["vl"] = put(cur["vl"], jnp.where(
                    cnt > 0, f32(delta["vl"]), old(cur["vl"])))
                new["il"] = put(cur["il"], jnp.where(
                    cnt > 0, delta["il"], old(cur["il"])))
            out[fname] = new
        return out, out_nrow, out_imin, out_imax

    return grid_append


_APPEND = None


def get_append_program():
    global _APPEND
    if _APPEND is None:
        _APPEND = _make_append_program()
    return _APPEND


# ----------------------------------------------------------------------
# orchestration
# ----------------------------------------------------------------------

def _group_ids(plan, registry, sids: np.ndarray):
    """Group ids of the matched series `sids` (ascending) by the BY
    keys' tag codes (no device result is needed: which of them hold a
    row in the query's span is known only after the readback, and
    groups with none are dropped then). Returns (gid (K,) int32, g,
    key_cols). Mirrors executor.QueryEngine._group_ids but derives
    groups from sids instead of rows (same decoded key values, possibly
    different group order — assembly sorts deterministically)."""
    from greptimedb_tpu.query.expr import Col

    if not plan.keys:
        return np.zeros(len(sids), np.int32), 1, {}
    code_cols = []
    vocabs = []
    cards = []
    for k in plan.keys:
        name = k.expr.name
        code_cols.append(registry.tag_codes(name)[sids].astype(np.int64))
        vocab = registry.dicts[registry.tag_names.index(name)].values
        vocabs.append(vocab)
        cards.append(max(len(vocab), 1))
    combined = code_cols[0].copy()
    for codes, card in zip(code_cols[1:], cards[1:]):
        combined = combined * card + codes
    uniq, inv = np.unique(combined, return_inverse=True)
    g = len(uniq)
    key_cols = {}
    rem = uniq
    for i in range(len(code_cols) - 1, -1, -1):
        card = cards[i]
        code_i = rem % card
        rem = rem // card
        # decode the g groups' values alone: the dictionary holds every
        # value of the tag, a selective query a few of them
        col = np.empty(g, dtype=object)
        col[:] = [vocabs[i][c] for c in code_i.tolist()]
        key_cols[plan.keys[i].key] = Col(col)
    return inv.astype(np.int32), g, key_cols


def _plane_selection(plan, entry: _Entry, sids) -> dict:
    """A selection as a plane program takes it: group ids over the
    entry's whole series axis, unmatched series routed to g (the
    program reads the series mask off them). `sids` None: no matcher,
    every series the registry knows (the padded tail of the series axis
    has no tags)."""
    if sids is None:
        sids = np.arange(min(entry.registry.num_series, entry.num_series))
    gid, g, key_cols = _group_ids(plan, entry.registry, sids)
    gid_full = np.full(entry.num_series, g, np.int32)
    gid_full[sids] = gid
    # identity grouping (each real series is its own group, padded tail
    # routed past g) needs no fold: the per-series state IS the group
    # state. num_series is FOLD_BLOCKS-padded, so compare the real
    # prefix, not the whole axis.
    fold = not (g <= entry.num_series
                and np.array_equal(gid_full[:g], np.arange(g))
                and (gid_full[g:] == g).all())
    return {
        "rows": 0, "gid_host": gid_full,
        # the device-resident copy, for the dispatches after the
        # selection's first
        "gid": None, "dispatched": False,
        "g": g, "key_cols": key_cols, "fold": fold, "windows": {},
    }


def _rows_selection(plan, entry: _Entry, sids) -> dict:
    """A selection as the rows program takes it: the K matched sids and
    their group ids, padded to the bucket (padding reads row 0, routed
    to g), ready to follow (delta, lo, hi) in the call's one vector.
    Nothing of it is ever placed on the device ahead of a call."""
    k = len(sids)
    kb = next(b for b in _ROW_BUCKETS if k <= b)
    gid, g, key_cols = _group_ids(plan, entry.registry, sids)
    tail = np.zeros(2 * kb, np.int32)
    tail[:k] = sids
    tail[kb:] = g
    tail[kb:kb + k] = gid
    return {
        "rows": kb, "tail": tail, "gid_host": gid, "gid": None,
        "g": g, "key_cols": key_cols,
        # each matched row its own group, in order: no fold
        "fold": not (g == k and np.array_equal(gid, np.arange(k))),
        "windows": {},
    }


_MEMO_MAX = 32      # selections an entry remembers; windows a selection does


def _memo_put(memo: dict, key, value) -> None:
    """Insert into a bounded memo, the oldest entry making room. Query
    threads share an entry's memo unlocked: two of them may pick the
    same oldest key, so the pop tolerates its absence."""
    if len(memo) >= _MEMO_MAX:
        memo.pop(next(iter(memo), None), None)
    memo[key] = value


def _window_steps(plan, ts_min: int, ts_max: int) -> tuple[int, int]:
    """(j_first, j_last): the absolute step indices whose windows meet
    rows spanning [ts_min, ts_max] — the host path's window math
    (executor._execute_range): steps t with t > ts_min - range and
    t <= ts_max."""
    align = plan.align_ms
    align_to = plan.align_to % align if plan.align_to else 0
    max_range = max(r.range_ms for r in plan.range_items)
    j_first = -((-(ts_min - max_range + 1 - align_to)) // align)
    j_last = (ts_max - align_to) // align
    return j_first, j_last


def _selection_gid(memo: dict, mesh):
    """The group ids of a memoized selection as the program takes them
    -> (gid, bytes this call uploads). On one device a selection's first
    dispatch passes them as a NumPy value: the jit's own argument path
    uploads it with the call, no separate put. From its second dispatch
    on they stay on the device. On a mesh they are placed series-sharded
    at once: a NumPy argument would be replicated to every device, and
    the program would compile a second time beside the one its resident
    copy uses."""
    if memo["gid"] is not None:
        return memo["gid"], 0
    gid = memo["gid_host"]
    if mesh is not None or memo["dispatched"]:
        gid = memo["gid"] = _make_put(mesh)[1](gid)
    memo["dispatched"] = True
    return gid, int(gid.nbytes)


def _fold_window(entry: _Entry, memo: dict, sid_active, extent) -> dict:
    """What the program said of one (lo, hi) window of a selection, as
    the host keeps it: the rows' exact extent in ms (None: no selected
    row in the span), and the groups that hold such a row — `keep`
    indexes them among the selection's g groups (None: all of them)."""
    from greptimedb_tpu.query.expr import Col

    c_lo, i_lo, c_hi, i_hi = (int(v) for v in extent)
    if c_hi < 0:
        return {"extent": None, "keep": None, "g": 0, "key_cols": {}}
    g = memo["g"]
    grp_active = np.zeros(g + 1, bool)
    grp_active[memo["gid_host"][sid_active]] = True
    keep = None
    key_cols = memo["key_cols"]
    if not grp_active[:g].all():
        keep = np.nonzero(grp_active[:g])[0]
        g = len(keep)
        key_cols = {
            k: Col(c.values[keep],
                   None if c.validity is None else c.validity[keep])
            for k, c in key_cols.items()
        }
    return {
        "extent": (entry.t0c + c_lo * entry.res + i_lo,
                   entry.t0c + c_hi * entry.res + i_hi),
        "keep": keep, "g": g, "key_cols": key_cols,
    }


def execute_range_device(engine, plan, table):
    """Try to run a RANGE plan on the device grid cache. Returns a
    QueryResult, or None to fall back to the host path."""
    if getattr(table, "remote", False):
        # distributed tables: rows live on datanode processes (each of
        # which runs its own device paths); the frontend merges on host
        return None
    items = plan_lowering(plan, table)
    if items is None:
        return None
    prefer = engine.prefer_device
    if prefer is False:
        return None
    if prefer is None and table.row_count() < DEVICE_THRESHOLD:
        return None

    align = plan.align_ms
    if align is None or align <= 0:
        return None
    r0 = align
    for it in plan.range_items:
        r0 = math.gcd(r0, it.range_ms)

    from greptimedb_tpu.query import stats

    # the grid entry of this table: looked up, restored from its
    # snapshot or built; `grid_cache` says which
    with tracing.child_span("query.grid") as grid_span:
        version = table.data_version()
        cache: DeviceRangeCache = engine.range_cache
        tkey = (table.info.database, table.info.name, id(table))

        def lookup():
            """The table's compatible entry at the table's version: an
            entry behind it is brought forward, or dropped where what
            was written since is not a plain append."""
            found = cache.lookup_compatible(tkey, r0, plan.align_to)
            if found is None or found.version == version:
                return found, "hit"
            if _upkeep(found, table, cache) == "append":
                return found, "upkeep"
            cache.evict(found)
            return None, "hit"

        entry, hit_note = lookup()
        if entry is None and getattr(engine, "persist_device_cache", True):
            with stats.timed("grid_cache_restore_ms"), _restore_lock(tkey):
                # the warm thread may have restored while we waited
                entry, hit_note = lookup()
                if entry is None:
                    entry = load_entry_snapshot(
                        table, r0, plan.align_to,
                        mesh=getattr(engine, "mesh", None),
                        mesh_opts=getattr(engine, "mesh_opts", None),
                        byte_budget=cache.byte_budget,
                    )
                    if entry is not None:
                        cache.insert((tkey, entry.res, entry.phase), entry)
                        hit_note = "miss(restored)"
        if entry is None:
            with stats.timed("grid_cache_build_ms"), \
                    tracing.child_span("grid.build"):
                entry = build_entry(
                    plan, table, items,
                    mesh=getattr(engine, "mesh", None),
                    mesh_opts=getattr(engine, "mesh_opts", None),
                    byte_budget=cache.byte_budget,
                    keep_host=getattr(engine, "persist_device_cache", True),
                )
            if entry is None:
                return None
            stats.note("grid_cache", "miss(build)")
            grid_span.attributes["grid_cache"] = "miss"
            cache.insert((tkey, entry.res, entry.phase), entry)
            persist_entry_async(entry, table)
        else:
            stats.note("grid_cache", hit_note)
            grid_span.attributes["grid_cache"] = (
                hit_note if hit_note in ("hit", "upkeep") else "restore")
            with stats.timed("grid_cache_ensure_ms"):
                ok = ensure_states(entry, plan, table, items, cache=cache)
            if not ok:
                return None
        stats.add("grid_cache_bytes", entry.bytes())
        if getattr(engine, "mesh", None) is not None:
            from greptimedb_tpu.query.planner import (
                MeshDecision, record_mesh_decision,
            )
            from greptimedb_tpu.parallel.mesh import shard_count

            dec = getattr(entry, "mesh_decision", None)
            if dec is None:
                dec = MeshDecision(
                    "shard" if getattr(entry, "mesh", None) is not None
                    else "replicate", "cached",
                    devices=shard_count(engine.mesh),
                )
            record_mesh_decision(dec, "range")

    # which series, which cells, which steps: the WHERE's ts bounds as
    # cell bounds, its matchers through the tag index as a series mask,
    # the window those bounds allow, and (memoized by selection) the
    # group ids of the matched series
    with tracing.child_span("query.select_series", memo="hit") as sel_span:
        res = entry.res
        # WHERE ts bounds must land on cell edges or partials can't honor them
        s = plan.scan
        if s.ts_min is not None and (s.ts_min - entry.t0c) % res != 0:
            return None
        if s.ts_max is not None and (s.ts_max + 1 - entry.t0c) % res != 0:
            return None
        lo = ((s.ts_min - entry.t0c) // res if s.ts_min is not None
              else -(2**31) + 1)
        hi = ((s.ts_max + 1 - entry.t0c) // res if s.ts_max is not None
              else 2**31 - 1)

        names = [nm for _, nm in plan.post_items]
        empty = engine._empty_result(names)
        # the grid's cells the WHERE admits (the grid's own extent where
        # it leaves a side open): an outer bound of the rows' extent,
        # known before any dispatch
        cell_lo, cell_hi = max(lo, 0), min(hi, entry.nb_data)
        if cell_lo >= cell_hi:
            return empty
        sids = None
        mask_key = None
        from greptimedb_tpu.query.planner import record_scan_path

        if s.matchers:
            from greptimedb_tpu import index as _index

            record_scan_path(_index.enabled())
            sids = _index.match_sids(entry.registry, s.matchers)
            if len(sids) == 0:
                return empty
            # memo on the canonical matcher key + registry version instead
            # of hashing an O(num_series) mask per query
            mask_key = (_index.matcher_key(s.matchers),
                        entry.registry.version)
        else:
            record_scan_path(False)

        # window math on the bound — identical to the host path's
        # (executor._execute_range) on the exact extent. A step's value
        # depends on its absolute index only, so the bound window's
        # steps are a superset of the exact window's, value for value:
        # the program computes these, the host trims after the readback
        align_to = plan.align_to % align if plan.align_to else 0
        if plan.grid_ts_min is not None:
            # distributed fill-grid override (see dist/dist_query.py): the
            # negotiated global extent IS the window, bound and exact
            ts_lo_b, ts_hi_b = plan.grid_ts_min, plan.grid_ts_max
        else:
            ts_lo_b = entry.t0c + cell_lo * res
            ts_hi_b = entry.t0c + cell_hi * res - 1
        j_first_b, j_last_b = _window_steps(plan, ts_lo_b, ts_hi_b)
        n_steps_b = int(j_last_b - j_first_b + 1)
        if n_steps_b <= 0:
            return empty
        stride = align // res
        delta = (align_to + j_first_b * align - entry.t0c) // res
        if not (-(2**31) < delta < 2**31):
            return None  # query window absurdly far from the data grid
        lo_c = _clamp_i32(lo)
        hi_c = _clamp_i32(hi)

        sel_key = (mask_key, tuple(k.expr.name for k in plan.keys))
        memo = entry.query_memo.get(sel_key)
        if memo is None:
            sel_span.attributes["memo"] = "miss"
            if sids is not None:
                sids = sids[sids < entry.num_series]
            if sids is not None and len(sids) <= _ROWS_MAX:
                memo = _rows_selection(plan, entry, sids)
            else:
                memo = _plane_selection(plan, entry, sids)
            _memo_put(entry.query_memo, sel_key, memo)
        kb = memo["rows"]
        (_TOOK_ROWS if kb else _TOOK_PLANE).inc()
        win_key = (lo_c, hi_c)
        win = memo["windows"].get(win_key)
        # a window the selection has not seen brings the rows' extent
        # and the active series back with the result, and folds them
        # (`_fold_window`); the key is the WHERE's own bounds, so a
        # literal that moves outside the data misses all the same
        sel_span.attributes["window"] = "miss" if win is None else "hit"
    # the program for this shape and its inputs: state planes, spec,
    # mesh variant, the session buffer of a repeated poll
    with tracing.child_span("query.plan", phase="program"):
        g = memo["g"]
        for item in plan.range_items:
            w_i = item.range_ms // res
            nb_i = (n_steps_b - 1) * stride + w_i
            if g * nb_i > 256_000_000:
                return None

        prog_items = tuple(
            (op, it.range_ms // res, fname)
            for (fname, op), it in zip(items, plan.range_items)
        )
        nanenc = all(
            entry.nan_ok.get(fname, fname == "__rows__") for fname, _ in items
        )
        program = get_program()
        prog_tag = "single"
        entry_mesh = getattr(entry, "mesh", None)
        if kb:
            # chosen by K alone, on a mesh too: the gathered rows are
            # replicated, so every mesh size does one device's arithmetic
            program, prog_tag = get_rows_program(entry_mesh)
        elif entry_mesh is not None:
            if (not memo["fold"]
                    or _fold_blocks(g, entry.nb, entry.num_series) != 1):
                # explicit-collective shard_map program with the blocked
                # exact fold (bit-identical across mesh sizes)
                program = get_sharded_program(entry_mesh)
                prog_tag = "sharded"
            else:
                # oversized blocked fold (FOLD_BLOCKS*g*nb past the partial
                # budget): stays on the auto-SPMD jit program — still
                # sharded, but XLA picks the combine order, so this is a
                # DOCUMENTED bit-identity exception; surface it
                stats.note("mesh_fold_range", "auto_spmd(oversized_fold)")
                prog_tag = "auto_spmd"
        prog_spec = (stride, n_steps_b, g, memo["fold"], nanenc, prog_items,
                     kb)
        from greptimedb_tpu.query import readback, sessions
        from greptimedb_tpu.telemetry import device_trace

        # delta-poll cursor: the first step whose __ts is past the
        # client's watermark, counted in the bound window (its steps'
        # timestamps are known before the dispatch). With FILL the full
        # grid must assemble first (PREV/LINEAR carry from pre-cursor
        # steps), so the cursor moves to cell emission; otherwise only
        # delta steps are read back.
        since = sessions.current_since()
        has_fill = plan.fill is not None or any(
            r.fill is not None for r in plan.range_items
        )
        j0_b = 0
        if since is not None and not has_fill:
            j0_b = min(max((since - align_to) // align - j_first_b + 1, 0),
                       n_steps_b)
            if j0_b >= n_steps_b:
                return empty  # the client has every step already

        # persistent query session: the folded RESULT buffer of this exact
        # query shape stays HBM-resident across polls — a repeated
        # dashboard query skips the program dispatch round trip entirely
        # (each dispatch is a host->device round trip) and the
        # delta path slices the resident buffer device-side below
        # keyed to THIS grid entry (id): two engines over the same table
        # (e.g. the sharded and single-device twins in the parity fuzz)
        # must not blindly share buffers across entries, and the cache
        # releases an entry's buffers when it drops the entry
        # (DeviceRangeCache._release — id reuse can never serve stale).
        # Tables assembled per-call (datanode partials) opt out — their
        # entry ids never repeat, so puts could only accumulate dead
        # buffers.
        use_sessions = getattr(table, "session_cacheable", True)
        session_tkey = ("range", id(entry))
        session_key = (sel_key, win_key, delta, prog_spec)
        out_dev = (sessions.global_sessions.get(
            session_tkey, session_key, entry.session_version
        ) if use_sessions else None)
        if win is None:
            # what the program said of this window went with the memo:
            # only a dispatch brings it back
            out_dev = None
        # device-time attribution: one span per query carrying compile
        # (first-call vs cache-hit), the crossing's legs (dispatch, wait,
        # readback) and transfer bytes — the transfer cost becomes a
        # named span on the trace. Attribution comes from device_trace's PROCESS-level memo,
        # matching the jit cache's scope (the entry-level program_specs
        # memo resets with every rebuilt grid entry — e.g. each datanode
        # partial builds a fresh table — and would mislabel warm programs
        # as first_call). A session hit keeps the span (execute is the
        # skipped dispatch, ~0) so traces always show the device leg.
        first_spec = prog_spec not in entry.program_specs
    # program identity carries the mesh variant (single-device vs
    # shard_map twin vs auto-SPMD fold): the profiler must never
    # cross-serve mesh twins under one registry row
    with stats.timed("device_exec_ms"), \
            device_trace.device_call(
                "range", key=("range", prog_tag, prog_spec),
                groups=g, steps=n_steps_b, rows=kb) as dcall:
        extras = ()
        dispatch = out_dev is None
        stats.note("device_session", "miss" if dispatch else "hit")
        if dispatch:
            window = np.array([delta, lo_c, hi_c], np.int32)
            if kb:
                # the call's one host argument, uploaded with it
                inputs = (np.concatenate([window, memo["tail"]]),)
                uploaded = inputs[0].nbytes
            else:
                gid_in, uploaded = _selection_gid(memo, entry_mesh)
                # the three scalars are one NumPy value too: the call
                # uploads it, in one transfer
                inputs = (gid_in, window)
            # the planes are read and handed to the dispatch with the
            # upkeep, which donates them, shut out
            with entry.gate.shared():
                seen = entry.appends
                arrs = {}
                for fname, op in items:
                    d = arrs.setdefault(fname, {})
                    for bk in _STATE_KEYS[op]:
                        d[bk] = entry.fields[fname][bk]
                outs = dcall.run(
                    program,
                    arrs, entry.nrow, entry.imin, entry.imax, *inputs,
                    spec=prog_spec,
                )
            if uploaded:
                dcall.transfer(uploaded, "upload")
            if kb:
                out_dev, packed_dev = outs
            else:
                out_dev, act_dev, extent_dev = outs
                if win is None:
                    extras = (act_dev, extent_dev)
        rows_packed = kb and win is None
        if rows_packed:
            # a window the memo does not know (so this call dispatched),
            # on the rows path: `packed` holds all of it, one array in
            # one crossing; the cursor's steps are cut on the host (K
            # rows: nothing to save)
            crossing = (packed_dev,)
        else:
            # fold=False leaves the plane programs' series axis
            # un-folded: rows [g:] are the padded/inactive tail (fold=True
            # and the rows program give exactly g rows). Both slices
            # happen on the DEVICE array, so a delta poll reads back only
            # the unseen steps, and every output of the program crosses
            # in ONE readback (readback.read_outputs feeds
            # gtpu_readback_bytes_total{mode=full|delta}).
            sliced = out_dev if kb or memo["fold"] else out_dev[:, :g]
            crossing = readback.outputs(sliced, j0_b, extras, axis=-1)
        if dispatch:
            # the copy of exactly what the read brings back, queued
            # behind the program: the read finds the bytes on their way
            dcall.start(*crossing)
            dcall.wait(out_dev)
        if dispatch and use_sessions:
            file_unless_appended(
                entry, seen, sessions.global_sessions.put, session_tkey,
                session_key, entry.session_version, out_dev,
                int(out_dev.nbytes))
        host = dcall.read(readback.read_outputs, crossing,
                          delta=not rows_packed and j0_b > 0)
        readback_bytes = sum(x.nbytes for x in host)
        if rows_packed:
            out, *extras = _unpack_rows(host[0], out_dev.shape,
                                        len(memo["gid_host"]))
            out = out[..., j0_b:]
        else:
            out, *extras = host
        if win is None:
            # (no record means this call dispatched: `seen` is its own)
            win = _fold_window(entry, memo, *extras)
            file_unless_appended(entry, seen, lambda: _memo_put(
                memo["windows"], win_key, win))
        # the exact window, from the exact extent of the selected rows:
        # the steps to keep of the bound window's
        n_steps = 0
        if win["extent"] is not None:
            ts_min_f, ts_max_f = win["extent"]
            if plan.grid_ts_min is not None:
                ts_min_f, ts_max_f = plan.grid_ts_min, plan.grid_ts_max
            j_first, j_last = _window_steps(plan, ts_min_f, ts_max_f)
            n_steps = max(int(j_last - j_first + 1), 0)
        dcall.annotate(trimmed_steps=n_steps_b - n_steps)
        _WINDOW_TRIM.labels("yes" if n_steps < n_steps_b else "no").inc()
    # host arrays -> QueryResult, from the values read back on
    with tracing.child_span("query.assemble"):
        if first_spec:
            entry.program_specs[prog_spec] = True
            concurrency.Thread(
                target=_persist_program_specs, args=(entry, table),
                daemon=True, name="program-specs-persist",
            ).start()
        # `out` holds bound steps [j0_b, n_steps_b); the exact window is
        # bound steps [trim_lo, trim_lo + n_steps); emission starts at
        # the later of the two starts
        trim_lo = j_first - j_first_b if n_steps else 0
        j0 = max(j0_b - trim_lo, 0)
        if j0 >= n_steps:
            # no selected row in the span, or the client has every step
            return empty
        out = out[..., trim_lo + j0 - j0_b:trim_lo + n_steps - j0_b]
        keep = win["keep"]
        if keep is not None:
            # groups none of whose series holds a row in the span: the
            # host path never sees them, FILL or not
            out = out[:, keep]
        g = win["g"]
        step_ts_eff = (align_to + (j_first + np.arange(j0, n_steps))
                       * align).astype(np.int64)
        n_steps_eff = n_steps - j0
        stats.add("device_readback_bytes", readback_bytes)
        stats.add("range_groups", g)
        stats.add("range_steps", n_steps)
        n_items = len(plan.range_items)
        vals = out[:n_items].astype(np.float64)
        if nanenc:
            pres = np.empty_like(vals, dtype=bool)
            for i, (fname, op) in enumerate(items):
                if op == "count":
                    pres[i] = vals[i] > 0
                else:
                    pres[i] = np.isfinite(vals[i])
        else:
            pres = out[n_items:] > 0.5

        item_vals = {}
        item_present = {}
        for i, item in enumerate(plan.range_items):
            item_vals[item.key] = vals[i]
            item_present[item.key] = pres[i]
        return engine._assemble_range_traced(
            plan, table, item_vals, item_present, win["key_cols"],
            step_ts_eff, g, n_steps_eff, since if has_fill else None,
        )
