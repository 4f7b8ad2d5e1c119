"""Cached device fast path for hot PromQL shapes.

The generic engine (promql/engine.py) rescans storage, regridifies, and
builds one Python label dict per series on every query — fine at 10k
series, hopeless at 1M. This module is the counterpart of the reference's
specialised PromQL plans (/root/reference/src/query/src/promql/planner.rs
PromPlanner + src/promql/src/extension_plan/range_manipulate.rs), rebuilt
around the device grid cache idea proven by query/device_range.py:

- a **selector grid cache**: per (table, field), the full (series x cell)
  vals/has/tsg grids for every series live in HBM, version-stamped by
  Table.data_version() and evicted under a byte budget;
- **dictionary-coded label algebra**: matchers evaluate per distinct tag
  value then broadcast through int32 code columns (SeriesRegistry.
  match_mask); group-by keys come from the cached codes matrix via one
  np.unique — no per-series Python;
- **one fused XLA program** per query shape: range function (prefix-path
  kernels from ops/window.py) + cross-series aggregation
  (ops/promql.aggregate_across_series) compile into a single jit call, so
  a query moves J*12 bytes of window indices to the device and (G, J)
  results back — independent of the series count.

Shapes handled: `agg [by/without (...)] (range_fn(sel[d]))` and
`agg [by/without (...)] (sel)` for the prefix-path range functions and the
simple aggregators. Everything else falls back to the generic engine, as
do queries whose step/range don't align with the cached grid resolution.
"""

from __future__ import annotations

import contextlib
import functools
import os

import time
from dataclasses import dataclass, field

import jax
import numpy as np

from greptimedb_tpu.promql.parser import (
    Agg,
    Binary,
    Call,
    NumberLit,
    VectorSelector,
)
from greptimedb_tpu.program_cache import ProgramCache
from greptimedb_tpu.telemetry import tracing
from greptimedb_tpu.telemetry.metrics import global_registry

from greptimedb_tpu import concurrency
from greptimedb_tpu.query.grid_upkeep import (
    PlaneGate, cell_capacity, file_unless_appended,
)

# range functions computable from per-series prefix sums: O(S*T) memory,
# no (S, J, L) window materialisation, safe at 1M series.
_PREFIX_FNS = frozenset({
    "rate", "increase", "delta", "idelta", "irate",
    "sum_over_time", "count_over_time", "avg_over_time",
    "last_over_time", "first_over_time", "present_over_time",
    "changes", "resets",
    "min_over_time", "max_over_time", "stddev_over_time",
    "stdvar_over_time", "mad_over_time", "deriv",
    "quantile_over_time", "predict_linear", "holt_winters",
})
# leading scalar-literal argument count per arg-taking range function
_FN_LEAD_ARGS = {
    "quantile_over_time": 1, "predict_linear": 0, "holt_winters": 0,
}
# trailing scalar args (after the selector)
_FN_TRAIL_ARGS = {"predict_linear": 1, "holt_winters": 2}
_SIMPLE_AGGS = frozenset(
    {"sum", "avg", "min", "max", "count", "group", "stddev", "stdvar"}
)

_FAST_HITS = global_registry.counter(
    "greptime_promql_fast_path_total",
    "PromQL queries served from the selector grid cache", ("event",),
)


_GRID_ENTRIES = global_registry.counter(
    "gtpu_promql_grid_entries_total",
    "selector grids built, and tables refused because a series' samples "
    "do not sit one to a cell or the span outgrows the byte budget",
    ("outcome",),
)


# what became of an entry that a query found behind its table's version:
# brought forward by `_upkeep` ("append"; "grow" where the time axis was
# lengthened on the device first), or evicted and rebuilt because what
# was written since is not a plain append ("rebuild_<reason>"). A family
# of its own: the range grid's `gtpu_grid_upkeep_total` is read as it is
_UPKEEP = global_registry.counter(
    "gtpu_promql_grid_upkeep_total",
    "selector grid entries found behind their table's version, by "
    "outcome: append (the samples written since were scattered into the "
    "resident planes), grow (the same, the time axis first lengthened "
    "on the device) or rebuild_<reason> (evicted and built again)",
    labels=("outcome",),
)
# every outcome reads 0 from the import, so that a scrape can tell "no
# rebuild" from "no such counter"
_REBUILD_REASONS = ("out_of_order", "new_series", "irregular", "mutation",
                    "mesh", "flushed", "capacity", "multi_region")
for _outcome in ("append", "grow") + tuple(
        "rebuild_" + r for r in _REBUILD_REASONS):
    _UPKEEP.labels(_outcome)
_UPKEEP_ROWS = global_registry.counter(
    "gtpu_promql_grid_upkeep_rows_total",
    "samples written into resident selector grid planes by the upkeep",
)
_NO_ROW = np.iinfo(np.int64).min
# samples a dispatch of the append program takes at most; a batch is
# padded to the power of two at or above its size, from this floor
_APPEND_MIN, _APPEND_MAX = 1024, 65536


def _budget_bytes() -> int:
    return int(os.environ.get(
        "GREPTIMEDB_TPU_PROMQL_CACHE_BYTES", 4 * 1024**3
    ))


def _pow2_bucket(n: int, minimum: int = 8) -> int:
    b = minimum
    while b < n:
        b *= 2
    return b


@dataclass
class _Entry:
    table: object
    fieldname: str
    version: tuple
    registry: object            # SeriesRegistry snapshot backing the grids
    spec: object                # ops.grid.GridSpec
    vals: object                # (S_pad, NC) device float32
    has: object                 # (S_pad, NC) device bool
    tsg: object                 # (S_pad, NC) device int32
    num_series: int
    s_pad: int
    nbytes: int
    last_used: float = 0.0
    mesh: object = None         # series-axis sharding mesh (None = 1 dev)
    mesh_decision: object = None  # planner MeshDecision (replicate/shard)
    # why the table has no grid ("irregular", "budget"): kept as an
    # entry so that a refused table is scanned once a data version
    refused: str = ""
    # per-entry derived caches (device-resident, so queries move no masks)
    match_cache: dict = field(default_factory=dict)
    group_cache: dict = field(default_factory=dict)
    win_cache: dict = field(default_factory=dict)
    # what the upkeep (`_upkeep`) holds a batch of samples against: the
    # newest ts of every series (`_NO_ROW` for none), and the cells that
    # reach the newest sample (the rest of the time axis is spare)
    last_ts: np.ndarray | None = None
    data_cells: int = 0
    # the version the entry was built at: the session registry's stamp
    # for its result buffers, which the upkeep purges by window instead
    session_version: tuple = ()
    # queries read the planes shared, the upkeep donates them exclusive;
    # `appends` counts the batches applied, which a query reads with the
    # planes and checks before it files a window or a buffer
    gate: object = field(default_factory=PlaneGate)
    appends: int = 0
    upkeep_lock: object = field(default_factory=concurrency.Lock)


class SelectorGridCache:
    """LRU byte-budgeted cache of full-table selector grids."""

    def __init__(self):
        self._entries: dict[tuple, _Entry] = {}
        self._lock = concurrency.Lock()
        self._hits = 0
        self._misses = 0
        self._evictions = 0
        from greptimedb_tpu.telemetry import memory as _memory

        _memory.register_pool(
            "promql_grid", "device", self,
            stats=SelectorGridCache._mem_stats,
            evict=SelectorGridCache.evict_bytes,
            buffers=SelectorGridCache._device_buffers,
        )

    def get_entry(self, table, fieldname: str, mesh=None,
                  mesh_opts=None) -> _Entry | None:
        key = (id(table), fieldname)
        version = table.data_version()
        with self._lock:
            e = self._entries.get(key)
            if e is not None and e.table is not table:
                e = None
            if e is not None and e.version == version:
                e.last_used = time.monotonic()
                self._hits += 1
                tracing.set_attr(grid_cache="hit")
                return e
        if e is not None and e.spec is not None:
            # a grid behind its table: brought forward by what was
            # written since, or dropped where that is no plain append
            if _upkeep(e, table) != "rebuild":
                with self._lock:
                    e.last_used = time.monotonic()
                    self._hits += 1
                tracing.set_attr(grid_cache="upkeep")
                return e
            with self._lock:
                if self._entries.get(key) is e:
                    self._release(self._entries.pop(key))
        with self._lock:
            self._misses += 1
        tracing.set_attr(grid_cache="miss")
        with tracing.child_span("grid.build", site="promql_grid"):
            e = _build_entry(table, fieldname, version, mesh=mesh,
                             mesh_opts=mesh_opts)
        if e is None:
            return None
        with self._lock:
            old = self._entries.get(key)
            if old is not None and old is not e:
                self._release(old)  # stale version: free its buffers
            self._entries[key] = e
            e.last_used = time.monotonic()
            self._evict_locked(keep=key)
        from greptimedb_tpu.telemetry import memory as _memory

        _memory.note_device_bytes()
        return e

    def _release(self, entry: "_Entry"):
        """Drop the entry's session-resident result buffers with it: a
        freed _Entry's id() can be reused by a new entry whose version
        coincides, and the packed buffers would otherwise pin HBM until
        unrelated LRU pressure (query/sessions.py purge contract)."""
        from greptimedb_tpu.query import sessions as _sessions

        self._evictions += 1
        _sessions.global_sessions.purge_table(("promql", id(entry)))

    def _evict_locked(self, keep):
        budget = _budget_bytes()
        total = sum(e.nbytes for e in self._entries.values())
        if total <= budget:
            return
        for key, _ in sorted(
            self._entries.items(), key=lambda kv: kv[1].last_used
        ):
            if key == keep:
                continue
            victim = self._entries.pop(key)
            self._release(victim)
            total -= victim.nbytes
            if total <= budget:
                return

    def invalidate(self):
        with self._lock:
            for e in self._entries.values():
                self._release(e)
            self._entries.clear()

    def drop_table(self, table):
        with self._lock:
            for key in [
                k for k, e in self._entries.items() if e.table is table
            ]:
                self._release(self._entries.pop(key))

    # ------------------------------------------------------------------
    # memory accountant surface (telemetry/memory.py)
    # ------------------------------------------------------------------
    def _mem_stats(self) -> dict:
        from greptimedb_tpu.telemetry.memory import iter_device_arrays

        with self._lock:
            total = 0
            seen: set[int] = set()
            for e in self._entries.values():
                total += e.nbytes
                # derived per-query device inputs (match masks, group
                # ids, window indices) pinned on the entry count too —
                # the global watermark must see every resident byte
                # (same arrays the census enumerates)
                for cname in ("match_cache", "group_cache",
                              "win_cache"):
                    for v in list((getattr(e, cname, None) or {})
                                  .values()):
                        for arr in iter_device_arrays(v):
                            if id(arr) not in seen:
                                seen.add(id(arr))
                                total += int(arr.nbytes)
            return {
                "bytes": total,
                "entries": len(self._entries),
                "budget_bytes": _budget_bytes(),
                "hits": self._hits, "misses": self._misses,
                "evictions": self._evictions,
            }

    def evict_bytes(self, target: int) -> int:
        """Shed least-recently-used grids until `target` bytes are
        freed (cross-pool pressure from the global device watermark)."""
        freed = 0
        with self._lock:
            for key, e in sorted(
                self._entries.items(), key=lambda kv: kv[1].last_used
            ):
                if freed >= target:
                    break
                self._release(self._entries.pop(key))
                freed += e.nbytes
        return freed

    def _device_buffers(self):
        from greptimedb_tpu.telemetry.memory import iter_device_arrays

        out = []
        with self._lock:
            for key, e in self._entries.items():
                tag = f"promql:{e.fieldname}"
                for arr in (e.vals, e.has, e.tsg):
                    if arr is not None:
                        out.append((arr, tag))
                # derived per-query device inputs (match masks, group
                # ids, window indices) pinned on the entry
                for cname in ("match_cache", "group_cache", "win_cache"):
                    cache = getattr(e, cname, None) or {}
                    for v in list(cache.values()):
                        for arr in iter_device_arrays(v):
                            out.append((arr, f"{tag}:{cname}"))
        return out


_CACHE = SelectorGridCache()


def _session_exec(entry: _Entry, wins, skey: tuple, dcall, run):
    """Persistent query session for a fused program's packed result: an
    identical repeated poll serves the HBM-resident buffer without
    re-dispatching the program (query/sessions.py — each dispatch is a
    host->device round trip); a miss dispatches (`run`, a call of
    `dcall.run`) and waits for the result through `dcall`. The shape
    key embeds the device-array identities of the cached masks and
    grouping inputs (match_cache/group_cache: same id => same immutable
    buffer, and an evicted input only costs a false miss) and leads with
    the windows `wins` read (`_plan_windows`' key, the last cell first):
    the upkeep purges by window the buffers of cells it wrote to, and a
    query files its buffer only if no batch was applied since it read
    the planes. Every caller reads the whole packed result: a miss
    starts its copy to the host behind the program (`dcall.start`)
    before it waits."""
    from greptimedb_tpu.query import sessions as _sessions

    tkey = ("promql", id(entry))
    wkeys = tuple(w[6] for w in wins)
    key = (max(k[0] for k in wkeys), wkeys, skey)
    buf = _sessions.global_sessions.get(tkey, key, entry.session_version)
    if buf is None:
        with entry.gate.shared():
            seen = entry.appends
            buf = run()
        dcall.start(buf)
        dcall.wait(buf)
        file_unless_appended(
            entry, seen, _sessions.global_sessions.put, tkey, key,
            entry.session_version, buf, int(buf.nbytes))
    return buf


def _series_sharding(mesh, ndim: int):
    """NamedSharding partitioning axis 0 (series) over the mesh; None
    when single-device."""
    if mesh is None:
        return None
    from jax.sharding import NamedSharding, PartitionSpec as P

    from greptimedb_tpu.parallel.mesh import AXIS_SHARD

    spec = [None] * ndim
    spec[0] = AXIS_SHARD
    return NamedSharding(mesh, P(*spec))


def _build_entry(table, fieldname: str, version, mesh=None,
                 mesh_opts=None) -> _Entry | None:
    """Scan the whole table once and gridify every series onto one
    HBM-resident grid, one sample a cell. The resolution is the series'
    own cadence and the origin follows the samples' phase (below); a
    table that cannot be held exactly comes back as a refused entry."""
    if getattr(table, "remote", False):
        return None  # distributed tables: grids live on the datanodes
    import jax.numpy as jnp

    from greptimedb_tpu.ops import grid as G

    col = next(
        (c for c in table.info.schema.field_columns if c.name == fieldname),
        None,
    )
    if col is None or col.data_type.is_string():
        return None  # no device grid for string fields; skip the scan
    t0_build = time.perf_counter()
    data = table.scan(field_names=[fieldname])
    rows = data.rows
    registry = data.registry
    if rows is None or len(rows) == 0 or registry.num_series == 0:
        return _Entry(
            table, fieldname, version, registry, None, None, None, None,
            0, 0, 0,
        )
    vals_np = rows.fields[fieldname]
    if not np.issubdtype(np.asarray(vals_np).dtype, np.number):
        return None  # string field: no device grid
    ts = np.asarray(rows.ts, np.int64)
    sid = np.asarray(rows.sid, np.int32)
    t_min = int(ts.min())
    t_max = int(ts.max())
    # the grid's resolution is the series' own cadence: the median gap
    # between consecutive samples of one series (rows come in (series,
    # ts) order), in the millisecond the samples are stamped in. A
    # Prometheus scrapes each target at its own offset inside the
    # interval, so the gcd over ALL timestamps is 1 ms where every
    # series still has exactly one sample a scrape interval
    step = np.diff(ts)
    in_series = (np.diff(sid) == 0) & (step > 0)
    gaps = step[in_series]
    res = max(int(round(float(np.median(gaps)))), 1) if len(gaps) else 1000
    # origin: where every sample shares one phase of the cadence, cell
    # boundaries stay on it (a query that starts on a sample time is
    # aligned); where the series have phases of their own, boundaries
    # go on multiples of the cadence since the epoch, where a dashboard
    # puts its steps. A window that ends on a boundary and is a whole
    # number of cells long holds the same samples whatever their phase
    # inside a cell, and the grid keeps every sample's exact tick
    phase = ts % res
    p0 = int(phase[0])
    if not bool((phase == p0).all()):
        p0 = 0
    t0 = t_min - 1 - ((t_min - 1 - p0) % res)
    s = registry.num_series
    mesh_decision = None
    if mesh is not None:
        # replicate-vs-shard: small grids stay single-device (collective
        # + launch latency dominates), large ones shard the series axis
        from greptimedb_tpu.query.planner import decide_mesh_execution

        mesh_decision = decide_mesh_execution(
            mesh, kind="promql", series=s, opts=mesh_opts,
        )
        if not mesh_decision.shard:
            mesh = None
    s_pad = _pow2_bucket(s)
    if mesh is not None:
        from greptimedb_tpu.parallel.mesh import AXIS_SHARD

        # series axis shards over the mesh; pow2 buckets >= 8 divide an
        # 8-way mesh evenly, smaller grids pad up to it
        s_pad = max(s_pad, mesh.shape[AXIS_SHARD])
    nc = int(-((-(t_max - t0)) // res)) + 1
    spec = G.GridSpec.build(t0, res, nc)
    cell = spec.cell_of(ts).astype(np.int32)
    # exactness: a grid holds one sample a cell. A table whose span
    # outgrows half the cache budget at its cadence, or a series with
    # two samples inside one cell (a cadence that is not regular), gets
    # no grid: the generic engine answers, and the refusal is kept so
    # that the table is not scanned again until its data changes
    refused = ""
    if nc > max(_budget_bytes() // 2 // (9 * s_pad), 16):
        refused = "budget"
    elif bool((in_series & (np.diff(cell) == 0)).any()):
        refused = "irregular"
    if refused:
        _GRID_ENTRIES.labels("refused_" + refused).inc()
        return _Entry(
            table, fieldname, version, registry, None, None, None, None,
            s, s_pad, 0, refused=refused,
        )

    tsrel = spec.device_ts(ts)
    mask = np.ones(len(ts), bool)
    if rows.field_valid is not None and fieldname in rows.field_valid:
        mask = np.asarray(rows.field_valid[fieldname], bool)
    gvals, ghas, gtsg = G.gridify(
        jnp.asarray(sid),
        jnp.asarray(cell),
        jnp.asarray(tsrel),
        jnp.asarray(np.asarray(vals_np, np.float32)),
        jnp.asarray(mask),
        s_pad, nc,
    )
    if mesh is not None:
        # resident grids shard over the series axis; queries then run
        # SPMD with XLA-inserted collectives for cross-shard group folds
        import jax

        sh2 = _series_sharding(mesh, 2)
        gvals = jax.device_put(gvals, sh2)
        ghas = jax.device_put(ghas, sh2)
        gtsg = jax.device_put(gtsg, sh2)
    nbytes = s_pad * nc * 9
    # the grid BUILD is the big host->device transfer of this path:
    # attribute it on the trace (a first query over a cold selector
    # pays it; steady-state queries hit the resident grid)
    with tracing.child_span("device.upload", site="promql_grid",
                            upload_bytes=nbytes):
        gvals.block_until_ready()
    _FAST_HITS.labels("grid_build").inc()
    _GRID_ENTRIES.labels("built").inc()
    global_registry.gauge(
        "greptime_promql_grid_build_seconds",
        "wall seconds of the last selector grid build",
    ).set(time.perf_counter() - t0_build)
    entry = _Entry(
        table, fieldname, version, registry, spec, gvals, ghas, gtsg,
        s, s_pad, nbytes, session_version=version, data_cells=nc,
        last_ts=_series_newest(sid, ts, s),
    )
    entry.mesh = mesh
    entry.mesh_decision = mesh_decision
    return entry


def _series_newest(sid: np.ndarray, ts: np.ndarray, s: int) -> np.ndarray:
    """(s,) int64: the newest ts of each series among the rows,
    `_NO_ROW` for a series with none. Rows come in (series, ts) order,
    so each series' run ends on its newest."""
    last = np.full(s, _NO_ROW, np.int64)
    if len(sid) and bool((np.diff(sid) >= 0).all()):
        ends = np.r_[np.nonzero(np.diff(sid))[0], len(sid) - 1]
        last[sid[ends]] = ts[ends]
    elif len(sid):
        np.maximum.at(last, sid, ts)
    return last


# ----------------------------------------------------------------------
# upkeep: the grid follows its table under writes
# ----------------------------------------------------------------------

def _upkeep(entry: _Entry, table) -> str:
    """Bring `entry` forward to what its table holds now, if what was
    written since its stamp is a plain append: samples of series the
    entry knows, each strictly newer than the newest its series holds
    and in a cell of its own, with no truncate, delete or schema change
    between, the entry not on a mesh. A sample past the time axis first
    lengthens it on the device (`cell_capacity`'s rule from the span the
    data reaches), with no scan. Returns "append" (also when another
    query did it first), "grow", or "rebuild" with the entry untouched
    and the reason counted: the caller evicts it. Runs under
    `promql.resolve`."""
    with tracing.child_span("promql.upkeep") as span, entry.upkeep_lock:
        rows, batch, reason = None, None, None
        if entry.mesh is not None:
            reason = "mesh"
        else:
            rows, _appends, version, reason = table.appended_since(
                entry.version)
        if reason is None and rows is None:
            # another query brought it forward first, or what was
            # written is another metric's on the same physical region
            entry.version = version
            span.attributes["outcome"] = "current"
            return "append"
        if reason is None:
            batch, reason = _append_batch(entry, rows)
        cells_new = 0
        if reason is None:
            outcome = ("grow" if batch["nc"] > entry.spec.num_cells
                       else "append")
            cells_new = batch["data_cells"] - entry.data_cells
            _apply_append(entry, batch)
            entry.version = version
            _UPKEEP_ROWS.inc(len(rows))
        else:
            outcome = "rebuild_" + reason
        span.attributes.update(
            outcome=outcome, cells_new=cells_new,
            samples=0 if rows is None else len(rows))
        _UPKEEP.labels(outcome).inc()
        return outcome if reason is None else "rebuild"


def _append_batch(entry: _Entry, rows):
    """The samples written since the entry's stamp as the append program
    takes them -> (batch, None), or (None, reason) where they are no
    plain append of this grid."""
    from greptimedb_tpu.ops import grid as G

    sid, ts = rows.sid.astype(np.int64), np.asarray(rows.ts, np.int64)
    if int(sid.max()) >= entry.num_series:
        return None, "new_series"
    vals = np.asarray(rows.fields[entry.fieldname])
    valid = (rows.field_valid or {}).get(entry.fieldname)
    order = np.lexsort((ts, sid))
    sid, ts, vals = sid[order], ts[order], vals[order]
    valid = (np.ones(len(ts), bool) if valid is None
             else np.asarray(valid, bool)[order])
    spec = entry.spec
    first = np.r_[True, sid[1:] != sid[:-1]]
    prev = entry.last_ts[sid[first]]
    if ((ts[1:] <= ts[:-1]) & ~first[1:]).any() or (ts[first] <= prev).any():
        # a sample at or before the newest its series holds: an
        # overwrite or a late arrival, which a cell cannot take back
        return None, "out_of_order"
    cell = spec.cell_of(ts)
    if int(cell.min()) < 1:
        return None, "out_of_order"     # at or before the grid's origin
    held = prev != _NO_ROW
    if ((cell[1:] == cell[:-1]) & ~first[1:]).any() or (
            cell[first][held] == spec.cell_of(prev[held])).any():
        return None, "irregular"        # two samples in one cell
    need = int(cell.max()) + 1
    nc = spec.num_cells
    if need > nc:
        limit = max(_budget_bytes() // 2 // (9 * entry.s_pad), 16)

        def fits(cells):
            # the build's budget, and the tick unit the planes hold
            return cells <= limit and G.GridSpec.build(
                spec.t0, spec.res, cells).unit == spec.unit

        nc = cell_capacity(need, fits)
        if not fits(nc):
            return None, "capacity"
    keep = valid
    upd = np.stack([
        sid[keep], cell[keep], spec.device_ts(ts[keep]),
        vals[keep].astype(np.float32).view(np.int32)]).astype(np.int32)
    run_ends = np.r_[np.nonzero(first)[0][1:] - 1, len(sid) - 1]
    return {
        "upd": upd, "nc": nc, "c0": int(cell.min()),
        "data_cells": max(entry.data_cells, need),
        "newest": (sid[run_ends], ts[run_ends]),
    }, None


@functools.partial(jax.jit, donate_argnums=(0, 1, 2))
def selector_grid_append(vals, has, tsg, upd):
    """The planes of a selector grid, donated, with one batch of
    appended samples written in: `upd` int32[4, Kb] holds a sample a
    column (series, cell, tick, the float32 value's bits), padded with
    series past the axis, which the scatters drop. Each sample's cell is
    empty (newer than its series' newest, one a cell), so a write is
    what a build's scatter puts there."""
    import jax.numpy as jnp

    at = (upd[0], upd[1])

    def put(plane, value):
        return plane.at[at].set(value, mode="drop", unique_indices=True)

    value = jax.lax.bitcast_convert_type(upd[3], jnp.float32)
    return put(vals, value), put(has, True), put(tsg, upd[2])


@functools.partial(jax.jit, static_argnames=("nc",))
def selector_grid_grow(vals, has, tsg, *, nc: int):
    """The planes copied into planes of `nc` cells, the new ones empty
    as a build leaves a cell no sample reaches."""
    import jax.numpy as jnp

    pad = ((0, 0), (0, nc - vals.shape[1]))
    return jnp.pad(vals, pad), jnp.pad(has, pad), jnp.pad(tsg, pad)


def _apply_append(entry: _Entry, batch: dict) -> None:
    """Lengthen the time axis where the batch needs it, then write the
    batch in: one dispatch of the append program a bucket of samples,
    the planes donated and taken back updated, not waited for (the
    query's own program queues behind it). Under the gate, exclusive,
    with what the queries filed of the windows it wrote to forgotten."""
    from greptimedb_tpu.ops import grid as G
    from greptimedb_tpu.query import sessions as _sessions
    from greptimedb_tpu.telemetry import device_trace

    nc, upd = batch["nc"], batch["upd"]
    with entry.gate.exclusive():
        if nc > entry.spec.num_cells:
            with device_trace.device_call(
                    "promql_grid_upkeep", key=("promql_grid_grow", nc),
                    cells=nc) as dcall:
                entry.vals, entry.has, entry.tsg = dcall.run(
                    selector_grid_grow, entry.vals, entry.has, entry.tsg,
                    nc=nc)
                dcall.wait(dispatch_only=True)
            spec = entry.spec
            entry.spec = G.GridSpec.build(spec.t0, spec.res, nc)
            entry.nbytes = entry.s_pad * nc * 9
            # windows were clipped to the old axis: none stands
            entry.win_cache.clear()
            _sessions.global_sessions.purge_table(("promql", id(entry)))
        for at in range(0, upd.shape[1], _APPEND_MAX):
            part = upd[:, at:at + _APPEND_MAX]
            k = part.shape[1]
            kb = _pow2_bucket(k, _APPEND_MIN)
            if k < kb:
                pad = np.zeros((4, kb - k), np.int32)
                pad[0] = entry.s_pad + np.arange(kb - k)
                part = np.concatenate([part, pad], axis=1)
            with device_trace.device_call(
                    "promql_grid_upkeep", key=("promql_grid_upkeep", kb),
                    samples=k) as dcall:
                entry.vals, entry.has, entry.tsg = dcall.run(
                    selector_grid_append, entry.vals, entry.has,
                    entry.tsg, part)
                dcall.transfer(part.nbytes, "upload")
                dcall.wait(dispatch_only=True)
        # with the queries still shut out: what one of them files from
        # here on was read from these planes
        entry.appends += 1
        _forget_windows_from(entry, batch["c0"])
    sids, ts = batch["newest"]
    entry.last_ts[sids] = ts
    entry.data_cells = batch["data_cells"]
    from greptimedb_tpu.telemetry import memory as _memory

    _memory.note_device_bytes()


def _forget_windows_from(entry: _Entry, c0: int) -> None:
    """What a window record holds (its cells, clipped to the axis) and
    what a session's buffer answers hold only for windows whose last
    cell is before `c0`, the first a batch was written to; matcher
    masks and groupings depend on the registry alone and stay. Under
    the gate, exclusive."""
    from greptimedb_tpu.query import sessions as _sessions

    entry.win_cache = {k: w for k, w in entry.win_cache.items()
                       if w[6][0] < c0}
    _sessions.global_sessions.purge_table(
        ("promql", id(entry)), keep=lambda key: key[0] < c0)


# ----------------------------------------------------------------------
# per-query planning against a cached grid
# ----------------------------------------------------------------------

@dataclass
class _WinShim:
    """Windows with traced lo/hi/t_end arrays + static scalars, shaped for
    ops/promql.eval_range_function inside jit."""

    lo: object
    hi: object
    t_end: object
    range_ticks: int
    range_seconds: float
    l_cells: int

    @property
    def num_cells_per_window(self) -> int:
        return self.l_cells


@dataclass
class _SpecShim:
    tps: float


def _eval_side(vals, has, tsg, smask, lo, hi, t_end, *, fname,
               range_ticks, range_seconds, l_cells, tps, fargs,
               lookback_ticks):
    """Instant-lookback / range-function evaluation of one masked grid —
    the shared (jit-traced) front half of every fused query."""
    from greptimedb_tpu.ops import promql as K
    from greptimedb_tpu.ops import window as W

    has = has & smask[:, None]
    if fname == "__instant__":
        return W.instant_lookback(vals, has, tsg, hi, t_end,
                                  lookback_ticks)
    win = _WinShim(lo, hi, t_end, range_ticks, range_seconds, l_cells)
    return K.eval_range_function(
        fname, vals, has, tsg, win, _SpecShim(tps), args=fargs
    )


def _plan_windows(entry: _Entry, ev, range_ms: int, offset_ms: int,
                  *, align_range: bool = True):
    """Window cell indices against the cached grid, or None if the query's
    step/range/start don't land on cell boundaries (exactness requires
    alignment; see ops/grid.py cell convention). Instant lookback compares
    exact sample ticks, so only step/start need aligning for it."""
    spec = entry.spec
    res = spec.res
    start = ev.start_ms - offset_ms
    end = ev.end_ms - offset_ms
    # (an instant query is one step: its step size aligns nothing)
    if (end > start and ev.step_ms % res) or (start - spec.t0) % res:
        return None
    if align_range and range_ms % res:
        return None
    key = (start, end, ev.step_ms, range_ms)
    hit = entry.win_cache.get(key)
    if hit is not None:
        return hit
    seen = entry.appends
    steps = np.arange(start, end + 1, ev.step_ms, dtype=np.int64)
    hi_raw = (steps - spec.t0) // res
    w = max(range_ms // res, 1)
    hi = np.clip(hi_raw, 0, spec.num_cells - 1).astype(np.int32)
    lo = np.clip(hi_raw - w, 0, spec.num_cells - 1).astype(np.int32)
    lo = np.minimum(lo, hi)
    t_end = np.clip(
        (steps - spec.t0) // spec.unit, -2**31 + 1, 2**31 - 1
    ).astype(np.int32)
    import jax.numpy as jnp

    # device-resident window indices: a repeated query uploads nothing.
    # The last element names the window: its last cell, then its key
    out = (
        jnp.asarray(lo), jnp.asarray(hi), jnp.asarray(t_end),
        int(range_ms // spec.unit), range_ms / 1000.0, int(w),
        (int(hi.max()),) + key,
    )

    def put():
        if len(entry.win_cache) >= 64:
            entry.win_cache.pop(next(iter(entry.win_cache)))
        entry.win_cache[key] = out

    # clipped to the axis as it stood: not filed if it has grown since
    file_unless_appended(entry, seen, put)
    return out


def _matcher_mask_dev(entry: _Entry, matchers):
    """Device-resident (S_pad,) bool mask for a matcher set (padded series
    are always False). Cached so repeated queries move no bytes."""
    import jax.numpy as jnp

    key = tuple(
        (name, op, value.pattern if hasattr(value, "pattern") else value)
        for name, op, value in matchers
    )
    hit = entry.match_cache.get(key)
    if hit is not None:
        return hit
    out = None
    if matchers:
        # HBM-resident label plane (index/device_plane): the mask is a
        # gather+AND over the device codes matrix — only the per-
        # distinct-value ok-tables are uploaded
        from greptimedb_tpu.index import device_plane

        out = device_plane.matcher_mask_dev(
            entry.registry, matchers, entry.s_pad,
            mesh=getattr(entry, "mesh", None),
            num_series=entry.num_series,
        )
    if out is None:
        mask = np.zeros(entry.s_pad, bool)
        if matchers:
            from greptimedb_tpu import index as _index

            mask[: entry.num_series] = _index.match_mask(
                entry.registry, matchers
            )[: entry.num_series]
        else:
            mask[: entry.num_series] = True
        any_match = bool(mask.any())
        sh = _series_sharding(getattr(entry, "mesh", None), 1)
        if sh is not None:
            import jax

            dev = jax.device_put(mask, sh)
        else:
            dev = jnp.asarray(mask)
        out = (dev, any_match)
    if len(entry.match_cache) >= 128:
        entry.match_cache.pop(next(iter(entry.match_cache)))
    entry.match_cache[key] = out
    return out


def _grouping_dev(entry: _Entry, table, grouping, without: bool):
    """(group label dicts, device gid (S_pad,), num_groups). Padded series
    map to group G (dropped after aggregation). Cached per label set."""
    import jax.numpy as jnp

    key = (tuple(sorted(grouping)), bool(without))
    hit = entry.group_cache.get(key)
    if hit is not None:
        return hit
    reg = entry.registry
    codes = reg.codes_matrix()
    visible = set(table.tag_names)
    cols = [
        i for i, nm in enumerate(reg.tag_names)
        if nm in visible and not nm.startswith("__")
        and ((nm not in grouping) if without else (nm in grouping))
    ]
    s = entry.num_series
    sh = _series_sharding(getattr(entry, "mesh", None), 1)

    def put(arr):
        if sh is not None:
            import jax

            return jax.device_put(arr, sh)
        return jnp.asarray(arr)

    if not cols or s == 0:
        labels = [{}]
        gid = np.zeros(entry.s_pad, np.int32)
        gid[s:] = 1
        out = (labels, put(gid), 1)
        entry.group_cache[key] = out
        return out
    sub = codes[:s, cols]
    uniq, inv = np.unique(sub, axis=0, return_inverse=True)
    labels = []
    for row in uniq:
        lab = {}
        for ci, code in zip(cols, row):
            v = reg.dicts[ci].decode(int(code))
            if v != "":
                lab[reg.tag_names[ci]] = v
        labels.append(lab)
    g = len(uniq)
    gid = np.full(entry.s_pad, g, np.int32)
    gid[:s] = inv.astype(np.int32)
    out = (labels, put(gid), g)
    if len(entry.group_cache) >= 128:
        entry.group_cache.pop(next(iter(entry.group_cache)))
    entry.group_cache[key] = out
    return out


@functools.partial(
    jax.jit,
    static_argnames=(
        "fname", "op", "g", "range_ticks", "range_seconds", "l_cells",
        "tps", "fargs", "lookback_ticks",
    ),
)
def _fused_query(
    vals, has, tsg, smask, gid, lo, hi, t_end, *,
    fname: str, op: str, g: int, range_ticks: int, range_seconds: float,
    l_cells: int, tps: float, fargs: tuple, lookback_ticks: int,
):
    """The whole query as one XLA program: matcher mask, range function or
    instant lookback, cross-series aggregation."""
    from greptimedb_tpu.ops import promql as K

    import jax.numpy as jnp

    out, pres = _eval_side(
        vals, has, tsg, smask, lo, hi, t_end, fname=fname,
        range_ticks=range_ticks, range_seconds=range_seconds,
        l_cells=l_cells, tps=tps, fargs=fargs,
        lookback_ticks=lookback_ticks,
    )
    # blocked fold: the same fixed combine structure the sharded twin
    # runs per shard, so mesh and single-device results agree bit-for-bit
    vals_g, pres_g = K.aggregate_across_series_blocked(
        out, pres, gid, g + 1, op, total_series=vals.shape[0],
    )
    # single packed (2G, J) buffer: one device->host transfer per query
    return jnp.concatenate([
        vals_g[:g], pres_g[:g].astype(vals_g.dtype),
    ])


def _make_sharded_fused_query(mesh):
    """shard_map twin of _fused_query: grids series-sharded over
    AXIS_SHARD, each shard evaluates its series slice (range functions
    are per-series) and the cross-series aggregation recombines with the
    SAME blocked left fold the single-device program runs — sharded ==
    unsharded bit-for-bit (the 1M-series parity contract)."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    from greptimedb_tpu.parallel.dist import ShardFoldCtx
    from greptimedb_tpu.parallel.mesh import AXIS_SHARD

    ns = mesh.shape[AXIS_SHARD]

    @functools.partial(
        jax.jit,
        static_argnames=(
            "fname", "op", "g", "range_ticks", "range_seconds",
            "l_cells", "tps", "fargs", "lookback_ticks",
        ),
    )
    def program(
        vals, has, tsg, smask, gid, lo, hi, t_end, *,
        fname: str, op: str, g: int, range_ticks: int,
        range_seconds: float, l_cells: int, tps: float, fargs: tuple,
        lookback_ticks: int,
    ):
        from greptimedb_tpu.ops import promql as K

        import jax.numpy as jnp

        def local(vals, has, tsg, smask, gid, lo, hi, t_end):
            out, pres = _eval_side(
                vals, has, tsg, smask, lo, hi, t_end, fname=fname,
                range_ticks=range_ticks, range_seconds=range_seconds,
                l_cells=l_cells, tps=tps, fargs=fargs,
                lookback_ticks=lookback_ticks,
            )
            vals_g, pres_g = K.aggregate_across_series_blocked(
                out, pres, gid, g + 1, op,
                total_series=vals.shape[0] * ns, ctx=ShardFoldCtx(ns),
            )
            return jnp.concatenate([
                vals_g[:g], pres_g[:g].astype(vals_g.dtype),
            ])

        return shard_map(
            local, mesh=mesh,
            in_specs=(P(AXIS_SHARD, None), P(AXIS_SHARD, None),
                      P(AXIS_SHARD, None), P(AXIS_SHARD), P(AXIS_SHARD),
                      P(), P(), P()),
            out_specs=P(), check_vma=False,
        )(vals, has, tsg, smask, gid, lo, hi, t_end)

    return program


_SHARDED_QUERY = ProgramCache(_make_sharded_fused_query)


def _get_sharded_query(mesh):
    return _SHARDED_QUERY.get(mesh)


# ----------------------------------------------------------------------
# entry point
# ----------------------------------------------------------------------

@functools.partial(
    jax.jit,
    static_argnames=(
        "fname", "agg_op", "g_agg", "g", "b", "range_ticks",
        "range_seconds", "l_cells", "tps", "fargs", "lookback_ticks",
    ),
)
def _fused_hist_query(
    vals, has, tsg, smask, gid, slot, le, lo, hi, t_end, phi, *,
    fname: str, agg_op: str, g_agg: int, g: int, b: int,
    range_ticks: int, range_seconds: float, l_cells: int, tps: float,
    fargs: tuple, lookback_ticks: int,
):
    """histogram_quantile(phi, [sum by (le, ...)] (range_fn(sel))) as
    ONE XLA program: per-series range function, optional cross-series
    sum, scatter into (group, bucket) slots, quantile fold — no
    per-series host work at any cardinality (the fast-path answer to
    the reference's HistogramFold plan,
    /root/reference/src/promql/src/extension_plan/histogram_fold.rs)."""
    import jax.numpy as jnp

    from greptimedb_tpu.ops import promql as K

    out, pres = _eval_side(
        vals, has, tsg, smask, lo, hi, t_end, fname=fname,
        range_ticks=range_ticks, range_seconds=range_seconds,
        l_cells=l_cells, tps=tps, fargs=fargs,
        lookback_ticks=lookback_ticks,
    )
    if agg_op:
        # inner `sum by (le, ...)`: (S_pad, J) -> (G_agg, J); slot then
        # maps the AGGREGATED series into histogram cells. An aggregated
        # series EXISTS iff any member survived the matcher (the
        # generic engine's vector membership).
        src_exists = jax.ops.segment_sum(
            smask.astype(jnp.float32), gid, num_segments=g_agg + 1,
        )[:g_agg] > 0
        out, pres = K.aggregate_across_series(
            out, pres, gid, g_agg + 1, agg_op
        )
        out = out[:g_agg]
        pres = pres[:g_agg]
        sel_mask = src_exists
    else:
        sel_mask = smask
    # -> (G, B, J) via unique (group, bucket) slots
    seg = jnp.where(sel_mask & (slot >= 0), slot, jnp.int32(g * b))
    bsum = jax.ops.segment_sum(
        jnp.where(pres, out, 0.0).astype(jnp.float32), seg,
        num_segments=g * b + 1,
    )[:-1].reshape(g, b, -1)
    bpres = jax.ops.segment_sum(
        pres.astype(jnp.float32), seg, num_segments=g * b + 1,
    )[:-1].reshape(g, b, -1) > 0
    # Prometheus: a histogram without a +Inf bucket is undefined. The
    # +Inf bound is rank b-1 of the global layout; a group qualifies
    # only if a MATCHER-SURVIVING series fills that cell (the host
    # grouping is matcher-blind, so this must fold on device)
    inf_seg = jnp.where(
        sel_mask & (slot >= 0) & (slot % b == b - 1),
        slot // b, jnp.int32(g),
    )
    has_inf = jax.ops.segment_sum(
        jnp.ones(inf_seg.shape[0], jnp.float32), inf_seg,
        num_segments=g + 1,
    )[:g] > 0
    q_out, q_ok = K.histogram_quantile(
        le, bsum.transpose(0, 2, 1), bpres.transpose(0, 2, 1), phi,
    )
    q_ok = q_ok & has_inf[:, None]
    return jnp.concatenate([q_out, q_ok.astype(q_out.dtype)])


def _hist_grouping(entry: _Entry, table):
    """(labels, slot (S_pad,) int32, le (B,) f64, G, B) — groups are the
    label sets minus `le`; bucket index = rank of the series' le bound.
    None when the layout can't serve the fast path (no le tag, unparsable
    bounds, duplicate (group, le) series, or no +Inf bucket)."""
    key = ("__hist__",)
    hit = entry.group_cache.get(key)
    if hit is not None:
        return None if hit == "unservable" else hit

    def reject():
        # negative-cache: an unservable layout must not re-pay the
        # O(series) le-parsing on every query before falling back
        entry.group_cache[key] = "unservable"
        return None

    reg = entry.registry
    if "le" not in reg.tag_names:
        return reject()
    li = reg.tag_names.index("le")
    s = entry.num_series
    codes = reg.codes_matrix()[:s]
    le_raw = reg.tag_values("le")[:s]
    le_vals = np.full(s, np.nan)
    for i, t in enumerate(le_raw):
        if t == "":
            continue
        try:
            le_vals[i] = float(t.replace("+Inf", "inf"))
        except ValueError:
            pass
    valid = np.isfinite(le_vals) | np.isposinf(le_vals)
    if not valid.any():
        return reject()
    visible = set(table.tag_names)
    gcols = [
        i for i, nm in enumerate(reg.tag_names)
        if nm != "le" and nm in visible and not nm.startswith("__")
    ]
    uniq_le = np.unique(le_vals[valid])
    if not np.isposinf(uniq_le[-1]):
        return reject()  # no +Inf bucket: undefined histogram
    b = len(uniq_le)
    bidx = np.searchsorted(uniq_le, le_vals[valid])
    if gcols:
        sub = codes[valid][:, gcols]
        uniq_g, ginv = np.unique(sub, axis=0, return_inverse=True)
        g = len(uniq_g)
    else:
        uniq_g = np.zeros((1, 0), codes.dtype)
        ginv = np.zeros(int(valid.sum()), np.int64)
        g = 1
    slots = ginv * b + bidx
    if len(np.unique(slots)) != len(slots):
        return reject()  # duplicate (group, le): conflicting buckets
    slot_full = np.full(entry.s_pad, -1, np.int32)
    slot_full[np.nonzero(valid)[0]] = slots.astype(np.int32)
    labels = []
    for row in uniq_g:
        lab = {}
        for ci, code in zip(gcols, row):
            v = reg.dicts[ci].decode(int(code))
            if v != "" and reg.tag_names[ci] != "__name__":
                lab[reg.tag_names[ci]] = v
        labels.append(lab)
    sh = _series_sharding(getattr(entry, "mesh", None), 1)
    if sh is not None:
        d_slot = jax.device_put(slot_full, sh)
    else:
        import jax.numpy as jnp

        d_slot = jnp.asarray(slot_full)
    out = (labels, d_slot, uniq_le, g, b)
    if len(entry.group_cache) >= 128:
        entry.group_cache.pop(next(iter(entry.group_cache)))
    entry.group_cache[key] = out
    return out


def _resolve_fast_selector(engine, inner, ev):
    """Shared scaffold for the fast paths: match `range_fn(sel)` /
    bare instant selector, resolve table + grid entry. Returns (entry,
    table, raw_matchers, fname, fargs, range_ms, offset_ms) on success,
    "empty" for a resolvable-but-empty selector, None to fall back."""
    fargs: tuple = ()
    if isinstance(inner, Call) and inner.name in _PREFIX_FNS:
        # scalar-literal args ride as static fargs (phi, horizon, sf/tf)
        # in their EXACT generic-path positions — a misplaced scalar must
        # fall back so the generic engine rejects it consistently
        lead = _FN_LEAD_ARGS.get(inner.name, 0)
        trail = _FN_TRAIL_ARGS.get(inner.name, 0)
        args = inner.args
        if len(args) != lead + 1 + trail:
            return None
        if not all(isinstance(a, NumberLit)
                   for a in args[:lead] + args[lead + 1:]):
            return None
        fargs = tuple(
            float(a.value) for a in args[:lead] + args[lead + 1:]
        )
        sel = args[lead]
        if not isinstance(sel, VectorSelector) or sel.range_ms is None:
            return None
        fname = inner.name
        range_ms = sel.range_ms
    elif isinstance(inner, VectorSelector) and inner.range_ms is None:
        sel = inner
        fname = "__instant__"
        range_ms = ev.lookback_ms
    else:
        return None
    if sel.at_ms is not None:
        return None
    with tracing.child_span("promql.resolve"):
        table, field_sel, raw_matchers = engine._resolve_table(sel)
        if table is None:
            return None
        try:
            fieldname = engine._value_field(table, field_sel)
        except Exception:  # noqa: BLE001 - resolution failure: generic path
            return None
        qe = getattr(engine.instance, "query_engine", None)
        mesh = getattr(qe, "mesh", None)
        entry = _CACHE.get_entry(table, fieldname, mesh=mesh,
                                 mesh_opts=getattr(qe, "mesh_opts", None))
    if entry is None or entry.refused:
        return None
    if entry.num_series == 0:
        return "empty"
    return (entry, table, raw_matchers, fname, fargs, range_ms,
            sel.offset_ms)


def _selector_windows(entry, ev, fname, range_ms, offset_ms):
    """The resolved selector's windows against its grid, or None."""
    return _plan_windows(entry, ev, range_ms, offset_ms,
                         align_range=fname != "__instant__")


def _resolve_with_windows(engine, inner, ev):
    """`_resolve_fast_selector` and the selector's windows in one step,
    for the shapes that plan nothing else before their program: returns
    (entry, table, raw_matchers, fname, fargs, win), "empty" or None."""
    resolved = _resolve_fast_selector(engine, inner, ev)
    if resolved is None or resolved == "empty":
        return resolved
    entry, table, raw_matchers, fname, fargs, range_ms, offset_ms = resolved
    with tracing.child_span("promql.plan"):
        win = _selector_windows(entry, ev, fname, range_ms, offset_ms)
    if win is None:
        return None
    return entry, table, raw_matchers, fname, fargs, win


def _note_mesh_decision(entry, *, auto_spmd_site: str | None = None):
    """Surface the entry's replicate-vs-shard decision for ONE fast-path
    query that actually EXECUTED (EXPLAIN + gtpu_mesh_*) — resolution
    alone records nothing, so queries that fall back to the generic
    engine (or resolve two operands) don't inflate the counters. Sites
    whose program runs single-device code over sharded grids (histogram
    and binary: XLA auto-SPMD picks its own combine order) tag the
    reason so the documented bit-identity exception stays visible."""
    dec = entry.mesh_decision
    if dec is None:
        return
    from greptimedb_tpu.query.planner import (
        MeshDecision, record_mesh_decision,
    )

    if auto_spmd_site is not None and dec.shard:
        dec = MeshDecision(
            dec.mode, f"{dec.reason}:auto_spmd_{auto_spmd_site}",
            dec.devices,
        )
    record_mesh_decision(dec, "promql")


def _hist_slots_from_labels(labels):
    """Histogram cells over AGGREGATED series labels (small lists):
    (out_labels, slot array, le array, G, B) or None."""
    keys, le_vals = [], []
    for lab in labels:
        le = lab.get("le")
        v = None
        if le is not None:
            try:
                v = float(str(le).replace("+Inf", "inf"))
            except ValueError:
                pass
        le_vals.append(v)
        keys.append(tuple(sorted(
            (k, val) for k, val in lab.items()
            if k not in ("le", "__name__")
        )))
    valid = [i for i, v in enumerate(le_vals) if v is not None]
    if not valid:
        return None
    uniq_le = np.unique(np.asarray([le_vals[i] for i in valid]))
    if not np.isposinf(uniq_le[-1]):
        return None
    b = len(uniq_le)
    uniq_keys = sorted({keys[i] for i in valid})
    kidx = {k: i for i, k in enumerate(uniq_keys)}
    g = len(uniq_keys)
    slot = np.full(len(labels), -1, np.int32)
    seen = set()
    for i in valid:
        s = kidx[keys[i]] * b + int(
            np.searchsorted(uniq_le, le_vals[i])
        )
        if s in seen:
            return None  # duplicate (group, le)
        seen.add(s)
        slot[i] = s
    out_labels = [dict(k) for k in uniq_keys]
    return out_labels, slot, uniq_le, g, b


def try_fast_histogram(engine, phi: float, inner, ev):
    """Serve `histogram_quantile(phi, range_fn(sel))`,
    `histogram_quantile(phi, sel)`, and
    `histogram_quantile(phi, sum by (le, ...)(range_fn(sel)))` from the
    grid cache. Returns a VectorValue, or None to fall back."""
    from greptimedb_tpu.promql.engine import VectorValue, _empty_vector

    agg = None
    if isinstance(inner, Agg) and inner.op == "sum" \
            and not inner.without and inner.grouping \
            and "le" in inner.grouping:
        agg = inner
        inner = inner.expr

    resolved = _resolve_fast_selector(engine, inner, ev)
    if resolved is None:
        _FAST_HITS.labels("fallback").inc()
        return None
    if resolved == "empty":
        _FAST_HITS.labels("hit").inc()
        return _empty_vector(ev)
    entry, table, raw_matchers, fname, fargs, range_ms, offset_ms = resolved
    import jax.numpy as jnp

    with tracing.child_span("promql.plan"):
        win = _selector_windows(entry, ev, fname, range_ms, offset_ms)
        if win is None:
            _FAST_HITS.labels("fallback").inc()
            return None
        if agg is not None:
            agg_labels, d_gid, g_agg = _grouping_dev(
                entry, table, agg.grouping, agg.without
            )
            slots = _hist_slots_from_labels(agg_labels)
            if slots is None:
                _FAST_HITS.labels("fallback").inc()
                return None
            labels, slot_np, uniq_le, g, b = slots
            d_slot = jnp.asarray(slot_np)
            agg_op = "sum"
        else:
            grouping = _hist_grouping(entry, table)
            if grouping is None:
                _FAST_HITS.labels("fallback").inc()
                return None
            labels, d_slot, uniq_le, g, b = grouping
            d_gid = jnp.zeros(entry.s_pad, jnp.int32)
            g_agg = 1
            agg_op = ""
        lo, hi, t_end, range_ticks, range_seconds, l_cells, _w = win
        matchers = engine._to_registry_matchers(raw_matchers, table)
        smask, any_match = _matcher_mask_dev(entry, matchers)
    if not any_match:
        _FAST_HITS.labels("hit").inc()
        return _empty_vector(ev)
    lookback_ticks = max(int(ev.lookback_ms // entry.spec.unit), 1)
    _note_mesh_decision(entry, auto_spmd_site="histogram")
    from greptimedb_tpu.telemetry import device_trace

    from greptimedb_tpu.query import readback as _readback

    skey = ("hist", fname, agg_op, g_agg, g, b, range_ticks,
            range_seconds, l_cells, entry.spec.tps, fargs,
            lookback_ticks, float(phi),
            np.asarray(uniq_le).tobytes(),
            id(smask), id(d_gid), id(d_slot), id(lo), id(hi), id(t_end))
    with device_trace.device_call(
            "promql_histogram", key=("hist", fname, agg_op, g_agg, g, b,
                                     range_ticks, range_seconds,
                                     l_cells, entry.spec.tps, fargs,
                                     lookback_ticks)) as dcall:
        packed = _session_exec(entry, (win,), skey, dcall, lambda: dcall.run(
            _fused_hist_query,
            entry.vals, entry.has, entry.tsg, smask, d_gid, d_slot,
            jnp.asarray(uniq_le, jnp.float32), lo, hi, t_end,
            jnp.float32(phi),
            fname=fname, agg_op=agg_op, g_agg=g_agg, g=g, b=b,
            range_ticks=range_ticks,
            range_seconds=range_seconds, l_cells=l_cells,
            tps=entry.spec.tps, fargs=fargs,
            lookback_ticks=lookback_ticks,
        ))
        packed_np = dcall.read(_readback.read_full, packed, np.float64)
    _FAST_HITS.labels("hit").inc()
    with tracing.child_span("promql.assemble"):
        vals_np = packed_np[:g]
        pres_np = packed_np[g:] != 0.0
        keep = pres_np.any(axis=1)
        if not keep.all():
            idx = np.nonzero(keep)[0]
            return VectorValue(
                [labels[i] for i in idx], vals_np[idx], pres_np[idx]
            )
        return VectorValue(list(labels), vals_np, pres_np)


def try_fast(engine, e, ev):
    """Serve `agg(range_fn(selector))` / `agg(selector)` from the grid
    cache. Returns a VectorValue, or None to fall back to the generic
    path."""
    from greptimedb_tpu.promql.engine import VectorValue, _empty_vector

    if not isinstance(e, Agg) or e.op not in _SIMPLE_AGGS:
        return None
    resolved = _resolve_fast_selector(engine, e.expr, ev)
    if resolved is None:
        _FAST_HITS.labels("fallback").inc()
        return None
    if resolved == "empty":
        _FAST_HITS.labels("hit").inc()
        return _empty_vector(ev)
    entry, table, raw_matchers, fname, fargs, range_ms, offset_ms = resolved
    with tracing.child_span("promql.plan"):
        win = _selector_windows(entry, ev, fname, range_ms, offset_ms)
        if win is None:
            _FAST_HITS.labels("fallback").inc()
            return None
        lo, hi, t_end, range_ticks, range_seconds, l_cells, _w = win
        matchers = engine._to_registry_matchers(raw_matchers, table)
        smask, any_match = _matcher_mask_dev(entry, matchers)
        if not any_match:
            _FAST_HITS.labels("hit").inc()
            return _empty_vector(ev)
        labels, gid, g = _grouping_dev(entry, table, e.grouping,
                                       e.without)
    lookback_ticks = max(int(ev.lookback_ms // entry.spec.unit), 1)
    program = (_fused_query if entry.mesh is None
               else _get_sharded_query(entry.mesh))
    _note_mesh_decision(entry)
    from greptimedb_tpu.telemetry import device_trace

    from greptimedb_tpu.query import readback as _readback

    skey = ("q", entry.mesh is None, fname, e.op, g, range_ticks,
            range_seconds, l_cells, entry.spec.tps, fargs,
            lookback_ticks, id(smask), id(gid), id(lo), id(hi),
            id(t_end))
    with device_trace.device_call(
            "promql", key=("promql", entry.mesh is None, fname, e.op,
                           g, range_ticks, range_seconds, l_cells,
                           entry.spec.tps, fargs, lookback_ticks),
            groups=g) as dcall:
        packed = _session_exec(entry, (win,), skey, dcall, lambda: dcall.run(
            program,
            entry.vals, entry.has, entry.tsg, smask, gid,
            lo, hi, t_end,
            fname=fname, op=e.op, g=g, range_ticks=range_ticks,
            range_seconds=range_seconds, l_cells=l_cells,
            tps=entry.spec.tps, fargs=fargs,
            lookback_ticks=lookback_ticks,
        ))
        packed_np = dcall.read(_readback.read_full, packed, np.float64)
    _FAST_HITS.labels("hit").inc()
    with tracing.child_span("promql.assemble"):
        vals_np = packed_np[:g]
        pres_np = packed_np[g:] != 0.0
        keep = pres_np.any(axis=1)
        if not keep.all():
            idx = np.nonzero(keep)[0]
            return VectorValue(
                [labels[i] for i in idx], vals_np[idx], pres_np[idx]
            )
        return VectorValue(list(labels), vals_np, pres_np)


# ----------------------------------------------------------------------
# per-series output labels (sid-aligned): topk and vector-vector outputs
# keep series identity, and building a million label dicts per QUERY
# would be the Python cliff the fast path exists to avoid — build them
# once per grid entry (same lifetime as the registry snapshot) instead
# ----------------------------------------------------------------------

def _series_labels(entry: _Entry, table) -> list[dict]:
    """Per-sid tag dicts (no __name__), aligned with the entry's sid
    space; built once per entry (cached on it, like group_cache)."""
    hit = entry.group_cache.get("__series_labels__")
    if hit is not None:
        return hit
    reg = entry.registry
    visible = set(table.tag_names)
    tag_names = [t for t in reg.tag_names
                 if t in visible and not t.startswith("__")]
    cols = {t: reg.tag_values(t) for t in tag_names}
    labels = []
    for s in range(entry.num_series):
        labels.append({
            t: str(cols[t][s]) for t in tag_names if cols[t][s] != ""
        })
    entry.group_cache["__series_labels__"] = labels
    return labels


def _series_labels_for(entry: _Entry, table, sids) -> list[dict]:
    """Tag dicts for just the requested sids (topk winners: O(k), not
    O(num_series)); memoized per entry alongside the bulk cache."""
    bulk = entry.group_cache.get("__series_labels__")
    if bulk is not None:
        return [dict(bulk[int(s)]) for s in sids]
    memo = entry.group_cache.setdefault("__series_labels_memo__", {})
    reg = entry.registry
    visible = set(table.tag_names)
    out = []
    for s in sids:
        s = int(s)
        lab = memo.get(s)
        if lab is None:
            lab = {
                k: str(v) for k, v in reg.series_tags(s).items()
                if v != "" and k in visible and not k.startswith("__")
            }
            memo[s] = lab
        out.append(dict(lab))
    return out


# ----------------------------------------------------------------------
# topk / bottomk over the grid cache
# ----------------------------------------------------------------------

@functools.partial(
    jax.jit,
    static_argnames=("fname", "k", "largest", "range_ticks",
                     "range_seconds", "l_cells", "tps", "fargs",
                     "lookback_ticks"),
)
def _fused_topk(
    vals, has, tsg, smask, lo, hi, t_end, *,
    fname: str, k: int, largest: bool, range_ticks: int,
    range_seconds: float, l_cells: int, tps: float, fargs: tuple,
    lookback_ticks: int,
):
    """range_fn/selector + per-step top-k as ONE XLA program; only the
    (k, J) winners cross back to the host (the extension-plan analog of
    the reference's TopK over SeriesDivide)."""
    import jax.numpy as jnp

    out, pres = _eval_side(
        vals, has, tsg, smask, lo, hi, t_end, fname=fname,
        range_ticks=range_ticks, range_seconds=range_seconds,
        l_cells=l_cells, tps=tps, fargs=fargs,
        lookback_ticks=lookback_ticks,
    )
    key = _topk_key(out, pres, largest)
    top_key, top_idx = jax.lax.top_k(key.T, k)       # (J, k)
    # presence gathered from the real mask; finite-key check drops the
    # absent fill slots when fewer than k series are present
    top_pres = (
        jnp.take_along_axis(pres.T, top_idx, axis=1)
        & jnp.isfinite(top_key)
    )
    top_vals = jnp.take_along_axis(out.T, top_idx, axis=1)
    # ONE packed (3J, k) f32 buffer = one device->host transfer (three
    # separate readbacks pay the transfer round trip three times). Winner
    # indices are exact in f32: s_pad < 2^24.
    return jnp.concatenate([
        top_vals.astype(jnp.float32),
        top_idx.astype(jnp.float32),
        top_pres.astype(jnp.float32),
    ])


def _topk_key(out, pres, largest: bool):
    """Descending sort key: present samples clamped to a finite range so
    genuine +-Inf values still rank above/below every absent slot (-inf
    fill); present NaN ranks below every real value but above absence
    (generic np.argsort puts NaN last), staying finite so the presence
    check keeps it when k exceeds the real winners."""
    import jax.numpy as jnp

    big = jnp.asarray(3.0e38, out.dtype)
    nan_key = jnp.asarray(-3.2e38, out.dtype)
    base = jnp.clip(out, -big, big)
    k_dir = base if largest else -base
    # canonicalize -0.0 -> +0.0: lax.top_k's total order ranks +0.0
    # above -0.0 where a comparison holds them equal; with one key
    # representation the tie goes to the lower series index
    k_dir = k_dir + jnp.asarray(0.0, out.dtype)
    return jnp.where(
        pres, jnp.where(jnp.isnan(out), nan_key, k_dir), -jnp.inf
    )


def _make_sharded_fused_topk(mesh):
    """shard_map twin of _fused_topk using the dist_topk pattern
    (parallel/dist.py): each shard evaluates its series slice and takes
    a LOCAL per-step top-k, the (J, k)-sized winner sets all_gather in
    shard order, and one reselect over the ns*k candidates yields the
    global winners — only k rows per shard cross the ICI instead of the
    whole (S, J) matrix. Every global winner is inside its shard's local
    top-k, and candidate order (shard, then local rank) equals ascending
    global series index among equal keys, so selection — values, winner
    indices, tie-breaks — matches the single-device program exactly."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    from greptimedb_tpu.parallel.mesh import AXIS_SHARD

    @functools.partial(
        jax.jit,
        static_argnames=("fname", "k", "largest", "range_ticks",
                         "range_seconds", "l_cells", "tps", "fargs",
                         "lookback_ticks"),
    )
    def program(
        vals, has, tsg, smask, lo, hi, t_end, *,
        fname: str, k: int, largest: bool, range_ticks: int,
        range_seconds: float, l_cells: int, tps: float, fargs: tuple,
        lookback_ticks: int,
    ):
        import jax.numpy as jnp

        def local(vals, has, tsg, smask, lo, hi, t_end):
            out, pres = _eval_side(
                vals, has, tsg, smask, lo, hi, t_end, fname=fname,
                range_ticks=range_ticks, range_seconds=range_seconds,
                l_cells=l_cells, tps=tps, fargs=fargs,
                lookback_ticks=lookback_ticks,
            )
            s_loc = out.shape[0]
            key = _topk_key(out, pres, largest)
            kl = min(k, s_loc)
            l_key, l_idx = jax.lax.top_k(key.T, kl)    # (J, kl)
            base = jax.lax.axis_index(AXIS_SHARD) * jnp.int32(s_loc)
            l_gidx = base + l_idx.astype(jnp.int32)
            l_pres = jnp.take_along_axis(pres.T, l_idx, axis=1)
            l_vals = jnp.take_along_axis(out.T, l_idx, axis=1)
            cat = lambda x: jax.lax.all_gather(  # noqa: E731
                x, AXIS_SHARD, axis=1, tiled=True
            )
            c_key = cat(l_key)                         # (J, ns*kl)
            f_key, f_pos = jax.lax.top_k(c_key, k)
            f_vals = jnp.take_along_axis(cat(l_vals), f_pos, axis=1)
            f_idx = jnp.take_along_axis(cat(l_gidx), f_pos, axis=1)
            f_pres = (jnp.take_along_axis(cat(l_pres), f_pos, axis=1)
                      & jnp.isfinite(f_key))
            return jnp.concatenate([
                f_vals.astype(jnp.float32),
                f_idx.astype(jnp.float32),
                f_pres.astype(jnp.float32),
            ])

        return shard_map(
            local, mesh=mesh,
            in_specs=(P(AXIS_SHARD, None), P(AXIS_SHARD, None),
                      P(AXIS_SHARD, None), P(AXIS_SHARD),
                      P(), P(), P()),
            out_specs=P(), check_vma=False,
        )(vals, has, tsg, smask, lo, hi, t_end)

    return program


_SHARDED_TOPK = ProgramCache(_make_sharded_fused_topk)


def _get_sharded_topk(mesh):
    return _SHARDED_TOPK.get(mesh)


def try_fast_topk(engine, e, ev):
    """Serve global `topk/bottomk(k, range_fn(sel))` from the grid
    cache; grouped topk falls back to the generic engine."""
    from greptimedb_tpu.promql.engine import VectorValue, _empty_vector

    if not isinstance(e, Agg) or e.op not in ("topk", "bottomk"):
        return None
    if e.grouping or e.without:
        return None
    if not isinstance(e.param, NumberLit):
        return None
    k = int(e.param.value)
    if k <= 0:
        return _empty_vector(ev)
    resolved = _resolve_with_windows(engine, e.expr, ev)
    if resolved is None:
        _FAST_HITS.labels("fallback").inc()
        return None
    if resolved == "empty":
        _FAST_HITS.labels("hit").inc()
        return _empty_vector(ev)
    entry, table, raw_matchers, fname, fargs, win = resolved
    lo, hi, t_end, range_ticks, range_seconds, l_cells, _w = win
    matchers = engine._to_registry_matchers(raw_matchers, table)
    smask, any_match = _matcher_mask_dev(entry, matchers)
    if not any_match:
        _FAST_HITS.labels("hit").inc()
        return _empty_vector(ev)
    if entry.s_pad >= (1 << 24):
        # packed winner indices ride as f32 (exact only below 2^24);
        # beyond that the generic engine serves correctly
        return None
    lookback_ticks = max(int(ev.lookback_ms // entry.spec.unit), 1)
    kk = min(k, entry.num_series)
    topk_prog = (_fused_topk if entry.mesh is None
                 else _get_sharded_topk(entry.mesh))
    _note_mesh_decision(entry)
    from greptimedb_tpu.telemetry import device_trace

    from greptimedb_tpu.query import readback as _readback

    skey = ("topk", entry.mesh is None, fname, kk, e.op == "topk",
            range_ticks, range_seconds, l_cells, entry.spec.tps, fargs,
            lookback_ticks, id(smask), id(lo), id(hi), id(t_end))
    with device_trace.device_call(
            "topk", key=("topk", entry.mesh is None, fname, kk,
                         e.op == "topk", range_ticks, range_seconds,
                         l_cells, entry.spec.tps, fargs,
                         lookback_ticks)) as dcall:
        packed_dev = _session_exec(
            entry, (win,), skey, dcall, lambda: dcall.run(
                topk_prog,
                entry.vals, entry.has, entry.tsg, smask, lo, hi, t_end,
                fname=fname, k=kk, largest=e.op == "topk",
                range_ticks=range_ticks, range_seconds=range_seconds,
                l_cells=l_cells, tps=entry.spec.tps, fargs=fargs,
                lookback_ticks=lookback_ticks,
            ))
        packed = dcall.read(_readback.read_full, packed_dev)
    jj = packed.shape[0] // 3
    top_vals = packed[:jj].astype(np.float64)      # (J, k)
    top_idx = packed[jj:2 * jj].astype(np.int64)
    top_pres = packed[2 * jj:] != 0.0
    j = top_vals.shape[0]
    sids = np.unique(top_idx[top_pres])
    if len(sids) == 0:
        _FAST_HITS.labels("hit").inc()
        return _empty_vector(ev)
    pos = {int(s): i for i, s in enumerate(sids)}
    vals_out = np.zeros((len(sids), j))
    pres_out = np.zeros((len(sids), j), bool)
    steps, ranks = np.nonzero(top_pres)
    rows_ = np.asarray([pos[int(s)] for s in top_idx[steps, ranks]])
    vals_out[rows_, steps] = top_vals[steps, ranks]
    pres_out[rows_, steps] = True
    labels = _series_labels_for(entry, table, sids)
    if fname == "__instant__":
        for lab in labels:
            lab["__name__"] = table.name
    _FAST_HITS.labels("hit").inc()
    return VectorValue(labels, vals_out, pres_out)


# ----------------------------------------------------------------------
# vector <op> vector over the grid cache: label matching on sid codes
# ----------------------------------------------------------------------

_BINARY_FAST_OPS = frozenset({
    "+", "-", "*", "/", "%", "^",
    ">", "<", ">=", "<=", "==", "!=",
})


def _apply_op_dev(op: str, a, b):
    import jax.numpy as jnp

    if op == "+":
        return a + b
    if op == "-":
        return a - b
    if op == "*":
        return a * b
    if op == "/":
        return a / b
    if op == "%":
        return jnp.fmod(a, b)
    if op == "^":
        return jnp.power(a, b)
    return {
        ">": a > b, "<": a < b, ">=": a >= b, "<=": a <= b,
        "==": a == b, "!=": a != b,
    }[op]


@functools.partial(
    jax.jit,
    static_argnames=(
        "fname_l", "fname_r", "op", "bool_mod", "agg_op", "g",
        "range_ticks_l", "range_ticks_r", "range_seconds_l",
        "range_seconds_r", "l_cells_l", "l_cells_r", "tps",
        "fargs_l", "fargs_r", "lookback_ticks",
    ),
)
def _fused_binary(
    vals_l, has_l, tsg_l, smask_l, lo_l, hi_l, t_end_l,
    vals_r, has_r, tsg_r, smask_r, lo_r, hi_r, t_end_r,
    gid, *,
    fname_l: str, fname_r: str, op: str, bool_mod: bool, agg_op: str,
    g: int, range_ticks_l: int, range_ticks_r: int,
    range_seconds_l: float, range_seconds_r: float,
    l_cells_l: int, l_cells_r: int, tps: float,
    fargs_l: tuple, fargs_r: tuple, lookback_ticks: int,
):
    """vector<op>vector (one-to-one, default matching) fused on device:
    both sides share the table's sid space, so label matching IS sid
    alignment — no per-series host work (the reference vectorizes this
    as a DataFusion join on label columns; here the dictionary codes are
    already the join keys). Optional trailing aggregation."""
    import jax.numpy as jnp

    from greptimedb_tpu.ops import promql as K

    out_l, pres_l = _eval_side(
        vals_l, has_l, tsg_l, smask_l, lo_l, hi_l, t_end_l,
        fname=fname_l, range_ticks=range_ticks_l,
        range_seconds=range_seconds_l, l_cells=l_cells_l, tps=tps,
        fargs=fargs_l, lookback_ticks=lookback_ticks,
    )
    out_r, pres_r = _eval_side(
        vals_r, has_r, tsg_r, smask_r, lo_r, hi_r, t_end_r,
        fname=fname_r, range_ticks=range_ticks_r,
        range_seconds=range_seconds_r, l_cells=l_cells_r, tps=tps,
        fargs=fargs_r, lookback_ticks=lookback_ticks,
    )
    pres = pres_l & pres_r
    res = _apply_op_dev(op, out_l, out_r)
    if op in (">", "<", ">=", "<=", "==", "!="):
        if bool_mod:
            out = res.astype(out_l.dtype)
        else:
            # filtering comparison keeps the LEFT operand's sample
            pres = pres & res
            out = out_l
    else:
        out = res.astype(out_l.dtype)
    if agg_op:
        vals_g, pres_g = K.aggregate_across_series(out, pres, gid,
                                                   g + 1, agg_op)
        return jnp.concatenate([
            vals_g[:g], pres_g[:g].astype(vals_g.dtype),
        ])
    return jnp.concatenate([out, pres.astype(out.dtype)])


def _operand_shape_fast(expr) -> bool:
    """Static AST pre-check, BEFORE any entry resolution: a grid build
    can scan the whole table, so reject non-fast shapes for free."""
    if isinstance(expr, VectorSelector):
        return expr.range_ms is None
    return isinstance(expr, Call) and expr.name in _PREFIX_FNS


def _resolve_binary(engine, e, ev):
    """Both operands fast-resolve over the SAME series registry ->
    (entry_l, side_l, entry_r, side_r, table) or "empty" or None."""
    if not isinstance(e, Binary) or e.op not in _BINARY_FAST_OPS:
        return None
    m = e.matching
    if m.explicit or m.labels or m.group or m.include:
        return None  # only default one-to-one matching rides sid codes
    if not (_operand_shape_fast(e.lhs) and _operand_shape_fast(e.rhs)):
        return None
    left = _resolve_with_windows(engine, e.lhs, ev)
    if left is None:
        return None
    right = _resolve_with_windows(engine, e.rhs, ev)
    if right is None:
        return None
    if left == "empty" or right == "empty":
        return "empty"
    entry_l, table_l, matchers_l, fname_l, fargs_l, win_l = left
    entry_r, table_r, matchers_r, fname_r, fargs_r, win_r = right
    if entry_l.registry is not entry_r.registry:
        return None  # different sid spaces: generic label matching
    return (left, right, table_l)


def try_fast_binary(engine, e, ev, *, agg=None):
    """Serve `vecL <op> vecR` (and `agg(...)` around it) when both sides
    live on the same table's grid cache. Returns VectorValue or None."""
    from greptimedb_tpu.promql.engine import VectorValue, _empty_vector

    if agg is not None and agg.op not in _SIMPLE_AGGS:
        return None
    resolved = _resolve_binary(engine, e, ev)
    if resolved is None:
        return None
    if resolved == "empty":
        return _empty_vector(ev)
    left, right, table = resolved
    entry_l, _tl, raw_m_l, fname_l, fargs_l, win_l = left
    entry_r, _tr, raw_m_r, fname_r, fargs_r, win_r = right
    agg_op = ""
    gid = None
    g = 1
    labels = None
    if agg is not None:
        labels, gid, g = _grouping_dev(entry_l, table, agg.grouping,
                                       agg.without)
        agg_op = agg.op
    import jax.numpy as jnp

    smask_l, any_l = _matcher_mask_dev(
        entry_l, engine._to_registry_matchers(raw_m_l, table))
    smask_r, any_r = _matcher_mask_dev(
        entry_r, engine._to_registry_matchers(raw_m_r, table))
    if not (any_l and any_r):
        _FAST_HITS.labels("hit").inc()
        return _empty_vector(ev)
    lo_l, hi_l, t_end_l, rt_l, rs_l, lc_l, _w = win_l
    lo_r, hi_r, t_end_r, rt_r, rs_r, lc_r, _w = win_r
    if gid is None:
        gid = jnp.zeros(entry_l.s_pad, jnp.int32)
    lookback_ticks = max(int(ev.lookback_ms // entry_l.spec.unit), 1)
    _note_mesh_decision(entry_l, auto_spmd_site="binary")
    from greptimedb_tpu.telemetry import device_trace

    from greptimedb_tpu.query import readback as _readback

    skey = ("binary", id(entry_r), fname_l, fname_r, e.op,
            bool(e.bool_mod), agg_op, g, rt_l, rt_r, rs_l, rs_r,
            lc_l, lc_r, entry_l.spec.tps, fargs_l, fargs_r,
            lookback_ticks, id(smask_l), id(smask_r), id(gid),
            id(lo_l), id(hi_l), id(t_end_l), id(lo_r), id(hi_r),
            id(t_end_r), entry_r.version)
    with device_trace.device_call(
            "promql_binary", key=("binary", fname_l, fname_r, e.op,
                                  bool(e.bool_mod), agg_op, g, rt_l,
                                  rt_r, rs_l, rs_r, lc_l, lc_r,
                                  entry_l.spec.tps, fargs_l, fargs_r,
                                  lookback_ticks)) as dcall:
        def run():
            # the right side's planes, where they are another entry's,
            # are read under that entry's gate too
            with (entry_r.gate.shared() if entry_r is not entry_l
                  else contextlib.nullcontext()):
                return dcall.run(
                    _fused_binary,
                    entry_l.vals, entry_l.has, entry_l.tsg, smask_l,
                    lo_l, hi_l, t_end_l,
                    entry_r.vals, entry_r.has, entry_r.tsg, smask_r,
                    lo_r, hi_r, t_end_r,
                    gid,
                    fname_l=fname_l, fname_r=fname_r, op=e.op,
                    bool_mod=bool(e.bool_mod), agg_op=agg_op, g=g,
                    range_ticks_l=rt_l, range_ticks_r=rt_r,
                    range_seconds_l=rs_l, range_seconds_r=rs_r,
                    l_cells_l=lc_l, l_cells_r=lc_r, tps=entry_l.spec.tps,
                    fargs_l=fargs_l, fargs_r=fargs_r,
                    lookback_ticks=lookback_ticks,
                )

        packed = _session_exec(entry_l, (win_l, win_r), skey, dcall, run)
        packed_np = dcall.read(_readback.read_full, packed, np.float64)
    if agg_op:
        vals_np = packed_np[:g]
        pres_np = packed_np[g:] != 0.0
        keep = pres_np.any(axis=1)
        _FAST_HITS.labels("hit").inc()
        if not keep.all():
            idx = np.nonzero(keep)[0]
            return VectorValue(
                [labels[i] for i in idx], vals_np[idx], pres_np[idx]
            )
        return VectorValue(list(labels), vals_np, pres_np)
    s = entry_l.num_series
    s_pad = entry_l.s_pad
    vals_np = packed_np[:s_pad][:s]
    pres_np = packed_np[s_pad:][:s] != 0.0
    keep = pres_np.any(axis=1)
    base = _series_labels(entry_l, table)
    _FAST_HITS.labels("hit").inc()
    if not keep.all():
        idx = np.nonzero(keep)[0]
        return VectorValue(
            [base[int(i)] for i in idx], vals_np[idx], pres_np[idx]
        )
    return VectorValue(list(base), vals_np, pres_np)


def invalidate_cache():
    _CACHE.invalidate()


def drop_table_entries(table):
    """Called by the catalog on DROP TABLE so grids don't pin dead tables."""
    _CACHE.drop_table(table)
