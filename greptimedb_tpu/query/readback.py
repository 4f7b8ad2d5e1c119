"""Device->host readback helpers: the one blessed result transfer.

Every query-path device->host result transfer goes through this module
so that (a) the bytes are attributed on /metrics
(`gtpu_readback_bytes_total{mode=full|delta}` — the host<->device
transfer, not only the kernels, is user-visible latency), and (b) delta
polls can slice ON DEVICE before materializing, shipping only the rows/
steps a `since` cursor has not seen instead of the whole buffer.

A call that dispatched starts the copy of what it will read right
after the dispatch (`start`, through `device_call.start`): the copy is
queued behind the program, so the crossing finds the bytes on their way
instead of opening a round trip of its own. No byte crosses that the
read would not bring back.

gtlint GT015 enforces the contract: a raw `np.asarray(...)` /
`jax.device_get(...)` / `.copy_to_host_async()` on a device result
buffer (a name `.block_until_ready()` was called on) in query-path code
is a finding — it would read the full buffer back unattributed where
these helpers exist.
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


def _attribute(mode: str, nbytes: int) -> None:
    _READBACK_BYTES.labels(mode).inc(nbytes)
    stmt_stats.add(f"readback_{mode}_bytes", nbytes)


def _tail(arr, lo: int, axis: int):
    """`arr[..., lo:]` along `axis`, sliced on the device."""
    idx = [slice(None)] * arr.ndim
    idx[axis] = slice(lo, None)
    return arr[tuple(idx)]


def read_full(arr, dtype=None) -> np.ndarray:
    """Materialize a whole device buffer on host (mode=full)."""
    out = _materialize(arr, dtype)
    _attribute("full", int(out.nbytes))
    return out


def read_delta(arr, lo: int, *, axis: int = -1, dtype=None) -> np.ndarray:
    """Materialize only `arr[..., lo:]` along `axis` (mode=delta).

    The slice happens on the device array BEFORE np.asarray, so only the
    delta bytes take the device->host transfer — the point of the
    incremental-readback path (a dashboard poll with a `since` cursor
    reads back only the steps it has not seen)."""
    if lo <= 0:
        return read_full(arr, dtype)
    out = _materialize(_tail(arr, lo, axis), dtype)
    _attribute("delta", int(out.nbytes))
    return out


def start(*arrs) -> None:
    """Start the device->host copy of each of `arrs` now, queued behind
    the program that computes it. Call it with exactly the arrays a
    later crossing of this module reads (`outputs` builds them); it
    counts nothing: the read that finds the bytes does."""
    for a in arrs:
        a.copy_to_host_async()


def outputs(arr, lo: int, extras=(), *, axis: int = -1) -> tuple:
    """The device arrays of one crossing of every output of a program:
    `arr[..., lo:]` (sliced on the device, as read_delta; lo <= 0 the
    whole of it), then the small side outputs `extras`."""
    return (_tail(arr, lo, axis) if lo > 0 else arr, *extras)


def read_outputs(dev: tuple, *, delta: bool = False) -> tuple:
    """The device arrays `dev` (`outputs`) in ONE crossing:
    `jax.device_get` of the tuple starts every copy not started yet
    before it waits for any, where one `np.asarray` per output would
    block once each. -> host arrays; the bytes of all of them count
    under mode delta (a cursor's slice) or full."""
    import jax

    host = jax.device_get(dev)
    _attribute("delta" if delta else "full",
               sum(int(x.nbytes) for x in host))
    return host


def readback_bytes(mode: str) -> float:
    """Current counter value (tests)."""
    return _READBACK_BYTES.labels(mode).value
