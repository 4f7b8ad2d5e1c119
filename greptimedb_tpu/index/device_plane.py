"""HBM-resident dictionary-coded label plane: device matcher masks.

The PromQL/SQL device paths need an (S_pad,) bool mask per matcher set.
The host path computes it over the numpy label plane and uploads
S_pad bytes per DISTINCT matcher set; at 10M series that is a 10MB
host->device transfer before the first fused program runs. This module keeps
the label plane itself resident in HBM — the (S_pad, num_tags) int32
code matrix, sharded over the series axis like every other grid — and
computes masks on device: per query, only the per-DISTINCT-VALUE
ok-tables move (kilobytes), the gather+AND runs where the data already
lives, and the result feeds the fused programs without a host round
trip (HiFrames' columnar-pipeline locality argument, PAPERS.md).

Padded rows (sid >= num_series) carry a per-column sentinel code whose
ok-table entry is always False, so the mask is padded-False by
construction. Ok-tables are padded to powers of two to bound jit
recompiles as dictionaries grow.

Planes are version-validated against the registry (like the postings in
tag_index.py) and registered with the memory accountant as a device
pool — census-enumerable buffers, LRU eviction under cross-pool HBM
pressure.
"""

from __future__ import annotations

import functools
from collections import OrderedDict

import numpy as np

from greptimedb_tpu import concurrency
from greptimedb_tpu.storage.series import missing_tag_ok, ok_codes_for

_MAX_PLANES = 8
_MAX_MASKS = 128

_LOCK = concurrency.Lock()
_PLANES: "OrderedDict[tuple, _Plane]" = OrderedDict()
_POOL_REGISTERED = False
_HITS = 0
_MISSES = 0
_EVICTIONS = 0


class _Plane:
    __slots__ = ("registry_ref", "version", "s_pad", "num_series",
                 "dev_codes", "nbytes", "mask_cache", "tag_names")

    def __init__(self, registry, version, s_pad, dev_codes, nbytes):
        import weakref

        self.registry_ref = weakref.ref(registry)
        self.version = version
        self.s_pad = s_pad
        self.num_series = registry.num_series
        self.dev_codes = dev_codes      # (s_pad, k) int32 device
        self.nbytes = nbytes
        self.tag_names = list(registry.tag_names)
        # matcher key -> (dev mask, any_match) — same shape the promql
        # per-entry match_cache stores, computed on device here
        self.mask_cache: OrderedDict = OrderedDict()


def _pow2(n: int) -> int:
    p = 8
    while p < n:
        p <<= 1
    return p


@functools.lru_cache(maxsize=64)
def _mask_prog(ncols: int):
    """jit'd gather+AND over `ncols` referenced tag columns: each
    column's codes index its ok-table; the mask is the conjunction."""
    import jax
    import jax.numpy as jnp

    def f(cols, oks):
        m = None
        for c, ok in zip(cols, oks):
            t = jnp.take(ok, c, axis=0)
            m = t if m is None else (m & t)
        return m

    return jax.jit(f)


def _sharding(mesh):
    if mesh is None:
        return None
    from jax.sharding import NamedSharding, PartitionSpec as P

    from greptimedb_tpu.parallel.mesh import AXIS_SHARD

    return NamedSharding(mesh, P(AXIS_SHARD, None))


def _get_plane(registry, s_pad: int, mesh) -> _Plane | None:
    global _HITS, _MISSES
    version = registry.version
    key = (id(registry), s_pad, id(mesh) if mesh is not None else None)
    with _LOCK:
        p = _PLANES.get(key)
        if (p is not None and p.version == version
                and p.registry_ref() is registry):
            _PLANES.move_to_end(key)
            _HITS += 1
            return p
        _MISSES += 1
    import jax
    import jax.numpy as jnp

    codes = registry.codes_matrix()
    n, k = codes.shape
    if k == 0 or s_pad < n:
        return None
    plane = np.empty((s_pad, k), dtype=np.int32)
    plane[:n] = codes
    # padded rows get each column's sentinel code (== dict size); the
    # ok-tables below always hold False there, so padded rows never match
    for i in range(k):
        plane[n:, i] = len(registry.dicts[i])
    sh = _sharding(mesh)
    dev = (jax.device_put(plane, sh) if sh is not None
           else jnp.asarray(plane))
    p = _Plane(registry, version, s_pad, dev, int(plane.nbytes))
    with _LOCK:
        old = _PLANES.get(key)
        _PLANES[key] = p
        _PLANES.move_to_end(key)
        while len(_PLANES) > _MAX_PLANES:
            _PLANES.popitem(last=False)
        del old
    _ensure_pool()
    from greptimedb_tpu.telemetry import memory as _memory

    _memory.note_device_bytes()
    return p


def matcher_mask_dev(registry, matchers, s_pad: int, mesh=None,
                     num_series: int | None = None):
    """((s_pad,) bool device mask, any_match) for a matcher set, or
    None when the device plane can't serve it (disabled, tagless
    registry, or a constant matcher set with no indexable column —
    callers fall back to the host mask + upload path). `num_series` is
    the caller's view of the series count: a plane built over a
    registry that has since grown past it would mark rows the caller
    considers padding, so the mismatch falls back too."""
    from greptimedb_tpu.index import tag_index

    if not tag_index.device_plane_enabled():
        return None
    p = _get_plane(registry, s_pad, mesh)
    if p is None:
        return None
    if num_series is not None and p.num_series != num_series:
        return None
    key = tag_index.matcher_key(matchers)
    with _LOCK:
        hit = p.mask_cache.get(key)
        if hit is not None:
            p.mask_cache.move_to_end(key)
            return hit
    import jax.numpy as jnp

    cols: list[int] = []
    oks: list[np.ndarray] = []
    for name, op, value in matchers:
        if name not in p.tag_names:
            if not missing_tag_ok(op, value):
                zero = jnp.zeros(s_pad, dtype=bool)
                out = (zero, False)
                break
            continue
        i = p.tag_names.index(name)
        d = registry.dicts[i]
        vals = np.asarray(list(d.values), dtype=object)
        ok = ok_codes_for(vals, op, value)
        # pow2-padded with a False sentinel tail: padded plane rows
        # (code == len(d)) and future codes both read False
        padded = np.zeros(_pow2(len(ok) + 1), dtype=bool)
        padded[: len(ok)] = ok
        cols.append(i)
        oks.append(padded)
    else:
        if not cols:
            return None  # constant-true set: host path pads correctly
        prog = _mask_prog(len(cols))
        dev = prog(
            tuple(p.dev_codes[:, i] for i in cols),
            tuple(jnp.asarray(ok) for ok in oks),
        )
        out = (dev, bool(dev.any()))
    with _LOCK:
        p.mask_cache[key] = out
        p.mask_cache.move_to_end(key)
        while len(p.mask_cache) > _MAX_MASKS:
            p.mask_cache.popitem(last=False)
    return out


def invalidate() -> None:
    with _LOCK:
        _PLANES.clear()


# ---------------------------------------------------------------------
# memory accountant surface (device tier)
# ---------------------------------------------------------------------
class _PlanePool:
    def stats(self) -> dict:
        from greptimedb_tpu.telemetry.memory import iter_device_arrays

        with _LOCK:
            total = 0
            for p in _PLANES.values():
                total += int(p.dev_codes.nbytes)
                for v in list(p.mask_cache.values()):
                    for arr in iter_device_arrays(v):
                        total += int(arr.nbytes)
            return {
                "bytes": total, "entries": len(_PLANES),
                "budget_bytes": 0, "hits": _HITS, "misses": _MISSES,
                "evictions": _EVICTIONS,
            }

    def evict_bytes(self, target: int) -> int:
        global _EVICTIONS
        freed = 0
        with _LOCK:
            while _PLANES and freed < target:
                _, p = _PLANES.popitem(last=False)
                freed += int(p.dev_codes.nbytes)
                for v in list(p.mask_cache.values()):
                    from greptimedb_tpu.telemetry.memory import (
                        iter_device_arrays,
                    )

                    for arr in iter_device_arrays(v):
                        freed += int(arr.nbytes)
                _EVICTIONS += 1
        return freed

    def buffers(self):
        from greptimedb_tpu.telemetry.memory import iter_device_arrays

        out = []
        with _LOCK:
            for p in _PLANES.values():
                out.append((p.dev_codes, "tag_index:plane"))
                for v in list(p.mask_cache.values()):
                    for arr in iter_device_arrays(v):
                        out.append((arr, "tag_index:mask"))
        return out


_POOL = _PlanePool()


def _ensure_pool() -> None:
    global _POOL_REGISTERED
    with _LOCK:
        if _POOL_REGISTERED:
            return
        _POOL_REGISTERED = True
    from greptimedb_tpu.telemetry import memory as _memory

    _memory.register_pool(
        "tag_index_plane", "device", _POOL,
        stats=_PlanePool.stats, evict=_PlanePool.evict_bytes,
        buffers=_PlanePool.buffers,
    )
