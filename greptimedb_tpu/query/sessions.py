"""Persistent query sessions: device-resident RESULT buffers across polls.

The grid caches (query/device_range.py, promql/fast.py) already keep the
*input* state resident in HBM; this registry keeps the *folded result*
of a query shape resident too, so a repeated dashboard poll skips the
program dispatch round trip entirely, and the `since`-cursor delta path can slice the
resident buffer device-side before reading anything back
(query/readback.read_delta).

Keyed like the scan cache: (table key, version, query-shape key). The
version is the table's data/physical version captured when the buffer
was produced, so write/flush(*)/compact(*)/truncate/ALTER invalidate by
comparison ((*) via the grid-entry version the shape key embeds);
close/drop purge explicitly (catalog/manager.py hooks). Bounded by an
LRU byte budget over HBM ([sessions] hbm_bytes).

The `since` cursor contextvar also lives here: protocol layers bind the
client's watermark (HTTP `since` param / dist ticket `since_ms` field)
and the execution paths slice their result emission to rows whose time
index is strictly greater than it.
"""

from __future__ import annotations

import contextvars

from collections import OrderedDict

from greptimedb_tpu.telemetry.metrics import global_registry

from greptimedb_tpu import concurrency

_HITS = global_registry.counter(
    "gtpu_session_hits_total",
    "query-session registry hits (device result buffer reused)",
)
_MISSES = global_registry.counter(
    "gtpu_session_misses_total",
    "query-session registry misses",
)
_EVICTIONS = global_registry.counter(
    "gtpu_session_evictions_total",
    "query-session entries evicted (budget or staleness)",
)
_BYTES = global_registry.gauge(
    "gtpu_session_bytes",
    "HBM bytes pinned by the query-session registry",
)
_ENTRIES = global_registry.gauge(
    "gtpu_session_entries",
    "entries held by the query-session registry",
)

_DEFAULT_HBM_BYTES = 1 * 1024**3
# entry-count cap on top of the byte budget: result buffers can be
# tiny, and an unbounded stream of distinct query shapes must not pin
# thousands of small HBM buffers under the byte budget's radar
_MAX_ENTRIES = 512


class SessionRegistry:
    """LRU byte-budgeted registry of device result buffers."""

    def __init__(self, max_bytes: int = _DEFAULT_HBM_BYTES,
                 enabled: bool = True):
        self.max_bytes = int(max_bytes)
        self.enabled = bool(enabled)
        self._lock = concurrency.Lock()
        # key -> (version, buffer, nbytes); key[0] is the table key so
        # purge_table can drop a dropped table's buffers eagerly
        self._entries: OrderedDict[tuple, tuple] = OrderedDict()
        self._bytes = 0
        # per-instance tallies for the memory accountant (the module
        # metric counters above are process-wide)
        self._hits = 0
        self._misses = 0
        self._evictions = 0
        from greptimedb_tpu.telemetry import memory as _memory

        _memory.register_pool(
            "sessions", "device", self,
            stats=SessionRegistry._mem_stats,
            evict=SessionRegistry.evict_bytes,
            buffers=SessionRegistry._device_buffers,
        )

    # ------------------------------------------------------------------
    def get(self, tkey, shape_key, version):
        if not self.enabled:
            return None
        from greptimedb_tpu.telemetry import stmt_stats  # cycle-safe lazy

        key = (tkey, shape_key)
        with self._lock:
            hit = self._entries.get(key)
            if hit is None:
                _MISSES.inc()
                self._misses += 1
            elif hit[0] != version:
                # the table's data changed since this buffer was folded:
                # it can never be served again — release the HBM now
                self._drop_locked(key)
                _MISSES.inc()
                self._misses += 1
                hit = None
            else:
                self._entries.move_to_end(key)
                _HITS.inc()
                self._hits += 1
        # per-statement attribution OUTSIDE the lock: the row for a
        # polled fingerprint shows its session hit rate
        stmt_stats.add("session_hits" if hit is not None
                       else "session_misses")
        return None if hit is None else hit[1]

    def put(self, tkey, shape_key, version, buf, nbytes: int):
        if not self.enabled or nbytes > self.max_bytes:
            return
        key = (tkey, shape_key)
        with self._lock:
            old = self._entries.pop(key, None)
            if old is not None:
                self._bytes -= old[2]
            self._entries[key] = (version, buf, int(nbytes))
            self._bytes += int(nbytes)
            while (self._bytes > self.max_bytes
                   or len(self._entries) > _MAX_ENTRIES) \
                    and len(self._entries) > 1:
                self._drop_locked(next(iter(self._entries)))
            self._publish_locked()
        # cross-pool pressure check OUTSIDE the lock: the global
        # watermark may evict from OTHER pools (and re-enter this one)
        from greptimedb_tpu.telemetry import memory as _memory

        _memory.note_device_bytes()

    # ------------------------------------------------------------------
    def purge_table(self, tkey, keep=None) -> None:
        """Drop every buffer for `tkey` (table drop/close: a recreated
        table could reuse the id and coincidentally match versions);
        with `keep`, those whose shape key it does not vouch for (the
        range grid's upkeep: buffers of windows that meet appended
        cells)."""
        with self._lock:
            stale = [k for k in self._entries if k[0] == tkey
                     and not (keep is not None and keep(k[1]))]
            for k in stale:
                self._drop_locked(k)
            if stale:
                self._publish_locked()

    def clear(self) -> None:
        with self._lock:
            for k in list(self._entries):
                self._drop_locked(k)
            self._publish_locked()

    def _drop_locked(self, key) -> None:
        ent = self._entries.pop(key, None)
        if ent is not None:
            self._bytes -= ent[2]
            _EVICTIONS.inc()
            self._evictions += 1
        self._publish_locked()

    # ------------------------------------------------------------------
    # memory accountant surface (telemetry/memory.py)
    # ------------------------------------------------------------------
    def _mem_stats(self) -> dict:
        with self._lock:
            return {
                "bytes": self._bytes,
                "entries": len(self._entries),
                "budget_bytes": self.max_bytes if self.enabled else 0,
                "max_entries": _MAX_ENTRIES,
                "hits": self._hits, "misses": self._misses,
                "evictions": self._evictions,
            }

    def evict_bytes(self, target: int) -> int:
        """Shed LRU entries until `target` bytes are freed (cross-pool
        pressure from the global [memory] device_budget_bytes
        watermark). Returns bytes actually freed."""
        freed = 0
        with self._lock:
            while freed < target and self._entries:
                key = next(iter(self._entries))
                freed += self._entries[key][2]
                self._drop_locked(key)
        return freed

    def set_max_bytes(self, v: int) -> None:
        """Runtime budget update (autotune/knobs.py is the sanctioned
        caller — GT021). A shrink trims LRU entries immediately so the
        freed HBM is available to whichever pool the reallocation
        controller is growing."""
        with self._lock:
            self.max_bytes = int(v)
            while self._bytes > self.max_bytes and self._entries:
                self._drop_locked(next(iter(self._entries)))
            self._publish_locked()

    def _device_buffers(self):
        with self._lock:
            return [
                (ent[1], f"sessions:{key[0]!r}")
                for key, ent in self._entries.items()
            ]

    def _publish_locked(self) -> None:
        _BYTES.set(float(self._bytes))
        _ENTRIES.set(float(len(self._entries)))

    @property
    def entry_count(self) -> int:
        with self._lock:
            return len(self._entries)

    @property
    def byte_count(self) -> int:
        with self._lock:
            return self._bytes


# process-wide registry (like promql/fast._CACHE): every QueryEngine in
# the process folds into one HBM budget
global_sessions = SessionRegistry()


def configure(options: dict | None) -> None:
    """Apply the [sessions] TOML section to this process."""
    o = options or {}
    global_sessions.enabled = bool(o.get("enable", True))
    global_sessions.max_bytes = int(
        o.get("hbm_bytes", _DEFAULT_HBM_BYTES)
    )
    if not global_sessions.enabled:
        global_sessions.clear()


# ----------------------------------------------------------------------
# `since` delta cursor: a client watermark in DATA time (epoch ms).
# Row-returning queries emit only rows whose time-index output is
# strictly greater than it — applied before ORDER BY / LIMIT, like an
# extra WHERE on the time index.
# ----------------------------------------------------------------------

_since_var: contextvars.ContextVar = contextvars.ContextVar(
    "gtpu_since_ms", default=None
)


def bind_since(since_ms):
    """Bind the delta cursor for this execution; returns a reset token.
    None binds explicitly (clearing any outer cursor)."""
    v = None if since_ms is None else int(since_ms)
    return _since_var.set(v)


def reset_since(token) -> None:
    _since_var.reset(token)


def current_since():
    """Active `since` watermark in epoch ms, or None."""
    return _since_var.get()
