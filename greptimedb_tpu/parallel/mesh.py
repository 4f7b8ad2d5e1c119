"""Device mesh lifecycle for the query engine.

One process-wide mesh, built from the visible devices at first use and
threaded cli -> instance -> QueryEngine (`[mesh]` TOML knobs). The mesh
is the engine-side analog of the reference's region partitioning: the
series axis of every large grid shards over AXIS_SHARD and the shard_map
programs in parallel/dist.py + query/reduce.py + query/device_range.py +
promql/fast.py recombine with explicit collectives.

The replicate-vs-shard decision per query lives in query/planner.py
(decide_mesh_execution); this module only owns construction and the
process-wide singleton.
"""

from __future__ import annotations

import logging
import os

from dataclasses import dataclass

import numpy as np
import jax
from jax.sharding import Mesh

from greptimedb_tpu import concurrency

AXIS_SHARD = "shard"   # series axis (region/data parallel analog)
AXIS_TIME = "time"     # time-block axis (sequence parallel analog)

# Fixed series/row fold-block count for cross-device reductions: every
# blocked partial fold (sharded OR single-device) splits the reduced
# axis into FOLD_BLOCKS aligned blocks and combines them in one fixed
# left-fold order, so results are bit-identical across mesh sizes
# 1/2/4/8 and the unsharded path (tests/fuzz/test_fuzz_mesh_parity.py).
FOLD_BLOCKS = 8

_log = logging.getLogger("greptimedb_tpu.parallel.mesh")


@dataclass(frozen=True)
class MeshOptions:
    """`[mesh]` TOML knobs (config.py DEFAULTS mirrors these)."""

    enabled: bool = False
    axis_size: int = 0              # shard-axis devices; 0 = all visible
    time_parallel: int = 1          # devices dedicated to the time axis
    # CPU simulation: force N virtual host devices BEFORE jax init
    # (XLA_FLAGS --xla_force_host_platform_device_count)
    force_host_device_count: int = 0
    # replicate-vs-shard planner thresholds (query/planner.py)
    shard_min_series: int = 4096    # grid paths: series below this replicate
    shard_min_rows: int = 262144    # row paths: rows below this replicate


def mesh_options_from(section: dict) -> MeshOptions:
    d = MeshOptions()
    return MeshOptions(
        enabled=bool(section.get("enabled", d.enabled)),
        axis_size=int(section.get("axis_size", d.axis_size)),
        time_parallel=int(section.get("time_parallel", d.time_parallel)),
        force_host_device_count=int(
            section.get("force_host_device_count",
                        d.force_host_device_count)
        ),
        shard_min_series=int(
            section.get("shard_min_series", d.shard_min_series)
        ),
        shard_min_rows=int(section.get("shard_min_rows", d.shard_min_rows)),
    )


def make_mesh(
    devices: list | None = None,
    *,
    time_parallel: int = 1,
) -> Mesh:
    """Build a (shard, time) mesh over the available devices.

    time_parallel devices are dedicated to time-block parallelism; the rest
    shard the series axis. time_parallel=1 degenerates to pure series
    sharding (the common case for aggregate-heavy workloads)."""
    if devices is None:
        devices = jax.devices()
    n = len(devices)
    assert n % time_parallel == 0, (n, time_parallel)
    grid = np.asarray(devices).reshape(n // time_parallel, time_parallel)
    return Mesh(grid, (AXIS_SHARD, AXIS_TIME))


def single_device_mesh() -> Mesh:
    return Mesh(np.asarray(jax.devices()[:1]).reshape(1, 1),
                (AXIS_SHARD, AXIS_TIME))


def shard_count(mesh) -> int:
    """Shard-axis size of a mesh (1 when mesh is None)."""
    return 1 if mesh is None else int(mesh.shape[AXIS_SHARD])


# ----------------------------------------------------------------------
# process-wide mesh
# ----------------------------------------------------------------------

_state_lock = concurrency.Lock()
_global_mesh: Mesh | None = None
_global_opts: MeshOptions | None = None
_configured = False


def _force_host_devices(n: int) -> bool:
    """Request n virtual CPU devices. Only effective before the jax
    backend initializes; returns False (with a warning) otherwise."""
    flag = f"--xla_force_host_platform_device_count={n}"
    existing = os.environ.get("XLA_FLAGS", "")
    if "--xla_force_host_platform_device_count" in existing:
        return True  # already pinned (conftest / operator)
    # ask whether a backend exists WITHOUT creating one: jax.devices()
    # would initialize every backend, which would make this check
    # self-defeating (the flag must land first)
    from greptimedb_tpu.telemetry.device_programs import backend_live

    if backend_live() and len(jax.devices()) < n:
        _log.warning(
            "[mesh] force_host_device_count=%d requested after the jax "
            "backend initialized with %d device(s); set XLA_FLAGS=%r "
            "before process start", n, len(jax.devices()), flag,
        )
        return False
    os.environ["XLA_FLAGS"] = f"{existing} {flag}".strip()
    return True


def configure(opts: MeshOptions) -> Mesh | None:
    """Build (once) and return the process-wide query mesh, or None when
    disabled / only one device is usable. Safe to call from every role
    entrypoint — first configuration wins."""
    global _global_mesh, _global_opts, _configured
    with _state_lock:
        if _configured:
            return _global_mesh
        _configured = True
        _global_opts = opts
        if not opts.enabled:
            return None
        if opts.force_host_device_count > 1:
            _force_host_devices(opts.force_host_device_count)
        devices = jax.devices()
        n = opts.axis_size * max(opts.time_parallel, 1) if opts.axis_size \
            else len(devices)
        n = min(n, len(devices))
        tp = max(opts.time_parallel, 1)
        n -= n % tp
        if n // tp <= 1:
            # covers the degenerate geometries too (1 device with
            # time_parallel=2 would otherwise build a 0-shard mesh)
            _log.info("[mesh] enabled but only %d usable device(s); "
                      "running single-device", max(n, 1))
            return None
        _global_mesh = make_mesh(devices[:n], time_parallel=tp)
        from greptimedb_tpu.telemetry.metrics import global_registry

        global_registry.gauge(
            "gtpu_mesh_devices",
            "Devices in the process-wide query mesh (shard axis)",
        ).set(shard_count(_global_mesh))
        _log.info("[mesh] query mesh %s over %d device(s)",
                  dict(_global_mesh.shape), n)
        return _global_mesh


def global_mesh() -> Mesh | None:
    """The process-wide mesh, or None when not configured/enabled."""
    with _state_lock:
        return _global_mesh


def global_mesh_opts() -> MeshOptions | None:
    """The MeshOptions configure() ran with, or None before configure.
    Sites without an engine in reach (query/window_fns.py) use this so
    the operator's `[mesh]` thresholds apply everywhere."""
    with _state_lock:
        return _global_opts


def update_shard_thresholds(*, base: MeshOptions | None = None,
                            shard_min_series: int | None = None,
                            shard_min_rows: int | None = None
                            ) -> MeshOptions:
    """Runtime update of the planner replicate/shard thresholds
    (autotune/knobs.py is the sanctioned caller — GT021). MeshOptions
    is frozen, so the process-wide object is SWAPPED, never mutated:
    sites reading via global_mesh_opts() see the new thresholds on
    their next plan; callers holding their own reference
    (QueryEngine.mesh_opts) are re-pointed by the knob's apply hook."""
    import dataclasses

    global _global_opts
    with _state_lock:
        cur = base or _global_opts or MeshOptions()
        kw = {}
        if shard_min_series is not None:
            kw["shard_min_series"] = int(shard_min_series)
        if shard_min_rows is not None:
            kw["shard_min_rows"] = int(shard_min_rows)
        new = dataclasses.replace(cur, **kw)
        # keep the no-engine-in-reach sites (global_mesh_opts readers)
        # on the same thresholds as the engine-held reference
        _global_opts = new
        return new


def reset_for_tests() -> None:
    """Drop the process-wide mesh so tests can reconfigure."""
    global _global_mesh, _global_opts, _configured
    with _state_lock:
        _global_mesh = None
        _global_opts = None
        _configured = False
