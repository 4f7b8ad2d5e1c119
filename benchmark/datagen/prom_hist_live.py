"""`prom-100k-defbuckets` whose Prometheus keeps writing: the stream of
`prom_hist` (same metric, labels, offsets, values and restarts, made
from the seed by the same function), cut in two. The first
`held_minutes` (scrapes 0 to 4 * held_minutes) are held when the window
opens: they are the dataset's `values`, `ts` and `rows`, loaded and
counted as `prom_hist` loads them. The rest of the `minutes` ride beside
them as the live stream, for the traffic to remote-write inside the
window: every sample of every live scrape, ordered by sample time (the
twelve `le` of one scrape together, as one remote-write shard sends
them), cut into requests of a fixed number of samples. `full` is both,
what the reference answers over. A pure function of (seed, instances,
minutes, held_minutes).

The configuration guarantees `grid_kept_up`, and the counter that holds
a run to it is `gtpu_promql_grid_upkeep_total{outcome}`, which a program
that keeps its selector grid up under writes exports from start-up with
every label at 0. A program without the family cannot be held to the
guarantee (a missing counter would read as "no rebuild" while the grid
is rebuilt for every panel): `load` refuses it before a sample is sent,
so the run ends in seconds with no result line and exit code 1.
"""

from __future__ import annotations

from benchmark.datagen import prom_hist
from benchmark.datagen.prom_hist import (
    INTERVAL_MS, LE, SCRAPES_PER_MINUTE, Dataset, make_offsets, make_values,
)
from benchmark.lib.server import check

UPKEEP_FAMILY = "gtpu_promql_grid_upkeep_total"

__all__ = ["make", "load", "render", "panel_end", "acked_arrays"]


def make(np, seed: int, scale: dict) -> Dataset:
    n, minutes = int(scale["instances"]), int(scale["minutes"])
    scrapes = minutes * SCRAPES_PER_MINUTE + 1
    held = int(scale["held_minutes"]) * SCRAPES_PER_MINUTE + 1
    if not 1 < held < scrapes:
        raise ValueError("the held part is not a part of the stream")
    values = make_values(np, seed, n, scrapes)
    offsets = make_offsets(np, seed, n)
    ds = Dataset(np, values[:, :, :held], offsets, int(scale["held_minutes"]))
    ds.full = Dataset(np, values, offsets, minutes)
    # the live scrapes of every instance, in the order a shard sends
    # them: by sample time (instances have distinct offsets), then
    # instance; a pair's twelve samples follow each other
    inst = np.repeat(np.arange(n, dtype=np.int64), scrapes - held)
    scrape = np.tile(np.arange(held, scrapes, dtype=np.int64), n)
    order = np.lexsort((inst, ds.full.ts[inst, scrape]))
    ds.live_pairs = np.stack([inst[order], scrape[order]], axis=1)
    ds.rank = np.full((n, scrapes), -1, np.int64)
    ds.rank[ds.live_pairs[:, 0], ds.live_pairs[:, 1]] = np.arange(
        len(order))
    ds.live_samples = len(order) * len(LE)
    ds.held_scrapes = held
    ds.resets_live = bool((np.diff(values, axis=2)[:, :, held - 1:] < 0)
                          .any())
    ds.prefix = {}
    return ds


def load(np, srv, ds: Dataset, say) -> dict:
    check(any(k[0] == UPKEEP_FAMILY for k in srv.metrics()),
          f"the server exports no {UPKEEP_FAMILY}: this program does not "
          "keep a selector grid up under writes, so it cannot serve "
          "prom-100k-defbuckets-live, whose guarantee grid_kept_up that "
          "counter holds (it would rebuild the grid for every panel and "
          "the missing counter would read 0)")
    return prom_hist.load(np, srv, ds, say)


def _samples(np, ds: Dataset, r: int, per: int):
    """(series, values, ts) of request r: live samples [r*per, r*per +
    per) of the stream."""
    p = np.arange(r * per, min((r + 1) * per, ds.live_samples))
    pair, le = p // len(LE), p % len(LE)
    inst, k = ds.live_pairs[pair, 0], ds.live_pairs[pair, 1]
    return (inst * len(LE) + le, ds.full.values[inst, le, k].astype(
        np.float64), ds.full.ts[inst, k])


def _varints(np, t, width: int):
    """(K, width) uint8: each of `t` as a protobuf varint of `width`
    bytes (every t the same width)."""
    out = np.empty((len(t), width), np.uint8)
    for j in range(width):
        out[:, j] = (t >> (7 * j)) & 0x7F | (0x80 if j < width - 1 else 0)
    return out


def render(np, ds: Dataset, r: int, per: int) -> bytes:
    """The remote-write body of request r, byte for byte what
    `prom_hist.write_request` and `snappy_block` make of its samples,
    from the series' label bytes cached on the dataset: a `TimeSeries`
    is its series' fixed prefix, then the sample's double and varint."""
    series, values, ts = _samples(np, ds, r, per)
    width = len(prom_hist._varint(int(ts.max())))
    if width != len(prom_hist._varint(int(ts.min()))):
        return prom_hist.snappy_block(prom_hist.write_request(
            prom_hist.series_heads(ds.full), series.tolist(),
            values.tolist(), ts.tolist()))
    if width not in ds.prefix:
        size = 10 + width   # 0x09, the double, 0x10, the varint
        ds.prefix[width] = [
            b"\x0a" + prom_hist._varint(len(h) + 2 + size) + h
            + bytes([0x12, size, 0x09])
            for h in prom_hist.series_heads(ds.full)]
    prefix = ds.prefix[width]
    tail = np.concatenate([
        np.asarray(values, "<f8").view(np.uint8).reshape(-1, 8),
        np.full((len(ts), 1), 0x10, np.uint8),
        _varints(np, np.asarray(ts, np.int64), width)], axis=1)
    w = tail.shape[1]
    raw = tail.tobytes()
    body = b"".join(prefix[s] + raw[j * w:(j + 1) * w]
                    for j, s in enumerate(series.tolist()))
    return prom_hist.snappy_block(body)


def requests(ds: Dataset, per: int) -> int:
    """Requests the live stream makes at `per` samples a request."""
    return -(-ds.live_samples // per)


def newest(ds: Dataset, acked: int) -> int:
    """The newest sample time once the first `acked` live samples are
    acknowledged (the held data's newest when none is)."""
    if acked <= 0:
        return int(ds.ts[:, -1].max())
    i, k = ds.live_pairs[(acked - 1) // len(LE)]
    return int(ds.full.ts[i, k])


def panel_end(ds: Dataset, acked: int) -> int:
    """The first multiple of the interval at or after the newest
    acknowledged sample: every acknowledged sample is inside the last
    step's window."""
    return -(-newest(ds, acked) // INTERVAL_MS) * INTERVAL_MS


def acked_arrays(np, ds: Dataset, acked: int):
    """(ts, values) a series a row, (series, scrapes) and (series, 1,
    scrapes), over the held samples and the first `acked` live ones; a
    sample not acknowledged is moved past every step (the reference's
    windows never reach it). For tests at small sizes."""
    full = ds.full
    n_le = len(LE)
    ts = np.repeat(full.ts, n_le, axis=0)
    pos = np.repeat(ds.rank, n_le, axis=0) * n_le + np.tile(
        np.arange(n_le), full.instances)[:, None]
    hidden = (np.repeat(ds.rank, n_le, axis=0) >= 0) & (pos >= acked)
    ts = np.where(hidden, 2**62 + np.arange(full.scrapes)[None, :], ts)
    return ts, full.values.reshape(-1, 1, full.scrapes)
