"""Device->host readback helpers: the one blessed result transfer.

Every query-path device->host result transfer goes through this module
so that (a) the bytes are attributed on /metrics
(`gtpu_readback_bytes_total{mode=full|delta}` — the host<->device
transfer, not only the kernels, is user-visible latency), and (b) delta
polls can slice ON DEVICE before materializing, shipping only the rows/
steps a `since` cursor has not seen instead of the whole buffer.

gtlint GT015 enforces the contract: a raw `np.asarray(...)` /
`jax.device_get(...)` on a device result buffer (a name
`.block_until_ready()` was called on) in query-path code is a finding —
it would read the full buffer back unattributed where these helpers
exist.
"""

from __future__ import annotations

import numpy as np

from greptimedb_tpu.telemetry import stmt_stats
from greptimedb_tpu.telemetry.metrics import global_registry

_READBACK_BYTES = global_registry.counter(
    "gtpu_readback_bytes_total",
    "device->host result readback bytes by mode "
    "(full buffer vs since-cursor delta slice)",
    labels=("mode",),
)


def _materialize(arr, dtype=None) -> np.ndarray:
    out = np.asarray(arr)
    if dtype is not None:
        out = out.astype(dtype, copy=False)
    return out


def read_full(arr, dtype=None) -> np.ndarray:
    """Materialize a whole device buffer on host (mode=full)."""
    out = _materialize(arr, dtype)
    _READBACK_BYTES.labels("full").inc(int(out.nbytes))
    stmt_stats.add("readback_full_bytes", int(out.nbytes))
    return out


def read_delta(arr, lo: int, *, axis: int = -1, dtype=None) -> np.ndarray:
    """Materialize only `arr[..., lo:]` along `axis` (mode=delta).

    The slice happens on the device array BEFORE np.asarray, so only the
    delta bytes take the device->host transfer — the point of the
    incremental-readback path (a dashboard poll with a `since` cursor
    reads back only the steps it has not seen)."""
    if lo <= 0:
        return read_full(arr, dtype)
    idx = [slice(None)] * arr.ndim
    idx[axis] = slice(lo, None)
    out = _materialize(arr[tuple(idx)], dtype)
    _READBACK_BYTES.labels("delta").inc(int(out.nbytes))
    stmt_stats.add("readback_delta_bytes", int(out.nbytes))
    return out


def readback_bytes(mode: str) -> float:
    """Current counter value (tests, bench)."""
    return _READBACK_BYTES.labels(mode).value
