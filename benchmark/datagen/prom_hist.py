"""One HTTP latency histogram as client_golang exports it and Prometheus
scrapes and remote-writes it: `http_request_duration_seconds_bucket`,
labels `job`, `instance`, `le`, made from the seed.

Source: client_golang `prometheus.DefBuckets` (eleven bounds and
`+Inf`); Prometheus docs "Jobs and instances" (the two labels attached
to every scraped series), the getting-started `prometheus.yml`
(`scrape_interval: 15s`), `scrape/target.go` `Target.offset` (each
target is scraped at a fixed offset inside the interval, and every
sample of one scrape carries that scrape's timestamp in milliseconds).
Assumed (the configuration file says so): the offsets (a seeded draw
without replacement from the milliseconds of one interval, instance 0
at 0, no jitter beyond it), the request rates, the latency
distribution (log-logistic: its CDF is closed-form and its tail fills
every bucket) and the restarts (1% of the instances, once, all twelve
counters back to 0). Every value is a whole number under 2**24: exact
in the float32 the device holds. A pure function of (seed, instances,
minutes).

The harness imports no jax and has no snappy and no protobuf: the
remote-write body is encoded here (a literal-only snappy block around a
hand-written `WriteRequest`).
"""

from __future__ import annotations

import struct
import time

METRIC = "http_request_duration_seconds_bucket"
JOB = "api-server"
LE = ["0.005", "0.01", "0.025", "0.05", "0.1", "0.25", "0.5", "1", "2.5",
      "5", "10", "+Inf"]
BOUNDS = [float(x.replace("+Inf", "inf")) for x in LE]
TAGS = ["instance", "job", "le"]      # remote write sorts labels by name
INTERVAL_MS = 15_000
SCRAPES_PER_MINUTE = 60_000 // INTERVAL_MS
# a multiple of the interval, so that a step of a dashboard (a multiple
# of the interval since the epoch) falls on instance 0's scrape times
BASE_MS = 1_700_000_010_000
SAMPLES_PER_SEND = 2000     # remote write's max_samples_per_send default
FIRST_BY_REMOTE_WRITE = 4   # instances whose first scrape makes the table
INSTANCES_PER_BATCH = 64    # of the bulk load: 64 x 12 series, every scrape


class Dataset:
    """values (instances, 12, scrapes) float32, whole numbers, cumulative
    in `le` and in time; offsets (instances,) int64 ms; ts(i, k) =
    BASE_MS + offsets[i] + k * INTERVAL_MS."""

    def __init__(self, np, values, offsets, minutes):
        self.values = values
        self.offsets = offsets
        self.instances = values.shape[0]
        self.scrapes = values.shape[2]
        self.minutes = minutes
        self.series = self.instances * len(LE)
        self.rows = self.series * self.scrapes
        self.names = [f"10.{i >> 16 & 255}.{i >> 8 & 255}.{i & 255}:8080"
                      for i in range(self.instances)]
        self.ts = (BASE_MS + offsets[:, None]
                   + np.arange(self.scrapes, dtype=np.int64)[None, :]
                   * INTERVAL_MS)
        self.reference = None   # the configuration's, set by the harness


def make_offsets(np, seed: int, instances: int):
    rng = np.random.default_rng([seed, 0x0FF5])
    if instances <= INTERVAL_MS:
        rest = rng.permutation(INTERVAL_MS - 1)[:instances - 1] + 1
    else:
        rest = rng.integers(0, INTERVAL_MS, instances - 1)
    return np.concatenate([[0], rest]).astype(np.int64)


def make_values(np, seed: int, instances: int, scrapes: int):
    rng = np.random.default_rng([seed, 0xB0C5])
    # requests a second and median latency (seconds) of each instance
    rate = rng.uniform(20.0, 200.0, instances)
    median = rng.uniform(0.03, 0.12, instances)
    shape = 1.6
    finite = np.asarray(BOUNDS[:-1])
    cdf = 1.0 / (1.0 + (finite[None, :] / median[:, None]) ** -shape)
    share = np.diff(np.concatenate(
        [np.zeros((instances, 1)), cdf, np.ones((instances, 1))], axis=1))
    lam = (rate * (INTERVAL_MS / 1000.0))[:, None] * share     # (n, 12)
    inc = rng.poisson(lam[:, :, None], (instances, len(LE), scrapes))
    counts = np.cumsum(np.cumsum(inc, axis=1, dtype=np.int64), axis=2)
    # a restart: from scrape r on, the counters count from 0 again
    n_restart = max(1, round(instances / 100)) if instances > 1 else 0
    who = rng.permutation(instances - 1)[:n_restart] + 1
    when = rng.integers(2, max(3, scrapes - 2), n_restart)
    for i, r in zip(who.tolist(), when.tolist()):
        counts[i, :, r:] -= counts[i, :, r - 1:r]
    if counts.max() >= 2 ** 24:
        raise ValueError("a counter passed 2**24: not exact in float32")
    return counts.astype(np.float32)


def make(np, seed: int, scale: dict) -> Dataset:
    instances, minutes = int(scale["instances"]), int(scale["minutes"])
    scrapes = minutes * SCRAPES_PER_MINUTE + 1
    return Dataset(np, make_values(np, seed, instances, scrapes),
                   make_offsets(np, seed, instances), minutes)


# -- the remote-write wire: snappy block format around a WriteRequest ----

def _varint(n: int) -> bytes:
    out = bytearray()
    while n > 0x7F:
        out.append(n & 0x7F | 0x80)
        n >>= 7
    out.append(n)
    return bytes(out)


def _field(no: int, payload: bytes) -> bytes:
    return _varint(no << 3 | 2) + _varint(len(payload)) + payload


def _label(name: str, value: str) -> bytes:
    return _field(1, _field(1, name.encode()) + _field(2, value.encode()))


def series_heads(ds: Dataset) -> list:
    """The label part of each series' `TimeSeries` message, sorted by
    label name as Prometheus sends them; index = instance * 12 + le."""
    name, job = _label("__name__", METRIC), _label("job", JOB)
    les = [_label("le", le) for le in LE]
    return [name + _label("instance", inst) + job + le
            for inst in ds.names for le in les]


def write_request(heads: list, series: list, values: list, ts: list) -> bytes:
    """`WriteRequest{timeseries: [{labels, samples: [{value, ts}]}]}`,
    one sample a series."""
    out = bytearray()
    for s, v, t in zip(series, values, ts):
        sample = b"\x09" + struct.pack("<d", v) + b"\x10" + _varint(t)
        out += _field(1, heads[s] + _field(2, sample))
    return bytes(out)


def snappy_block(data: bytes) -> bytes:
    """`data` as one snappy block of literals only (no copies): the
    uncompressed length, then literal elements of at most 2**16 bytes."""
    out = bytearray(_varint(len(data)))
    for p in range(0, len(data), 65536):
        chunk = data[p:p + 65536]
        n = len(chunk) - 1
        out += (bytes([n << 2]) if n < 60
                else bytes([61 << 2, n & 255, n >> 8]))
        out += chunk
    return bytes(out)


def scrape_bodies(np, ds: Dataset, k: int, instances, heads=None) -> list:
    """The remote-write bodies of scrape `k` of `instances` (an index
    array), SAMPLES_PER_SEND samples a request."""
    heads = heads or series_heads(ds)
    inst = np.asarray(instances, np.int64)
    series = (inst[:, None] * len(LE) + np.arange(len(LE))).reshape(-1)
    values = ds.values[inst, :, k].astype(np.float64).reshape(-1)
    ts = np.repeat(ds.ts[inst, k], len(LE))
    return [snappy_block(write_request(
        heads, series[p:p + SAMPLES_PER_SEND].tolist(),
        values[p:p + SAMPLES_PER_SEND].tolist(),
        ts[p:p + SAMPLES_PER_SEND].tolist()))
        for p in range(0, len(series), SAMPLES_PER_SEND)]


_RW_HEADERS = {"Content-Encoding": "snappy",
               "Content-Type": "application/x-protobuf",
               "X-Prometheus-Remote-Write-Version": "0.1.0"}


def load(np, srv, ds: Dataset, say) -> dict:
    """The table is the one remote write itself creates: the first
    scrape of a few instances goes by `POST /v1/prometheus/write`, the
    bulk as Arrow over Flight DoPut into that table, the last scrape of
    every instance by remote write again, 2,000 samples a request. A
    204 or the close of the DoPut stream is the acknowledgement."""
    import pyarrow as pa
    import pyarrow.flight as flight

    n, n_le, scrapes = ds.values.shape
    heads = series_heads(ds)
    t0 = time.perf_counter()
    first = np.arange(min(FIRST_BY_REMOTE_WRITE, n))
    acked_rw = 0
    for body in scrape_bodies(np, ds, 0, first, heads):
        srv.post("/v1/prometheus/write", body, _RW_HEADERS)    # 204 = ack
    acked_rw += len(first) * n_le
    # the table as remote write made it: its time index by its own name
    time_index = next(r[0] for r in srv.sql(f"DESC TABLE {METRIC}")
                      if r[-1] == "TIMESTAMP")
    schema = pa.schema([
        ("instance", pa.string()), ("job", pa.string()),
        ("le", pa.string()), (time_index, pa.timestamp("ms")),
        ("greptime_value", pa.float64())])
    inst_dict = pa.array(ds.names, pa.string())
    le_dict = pa.array(LE, pa.string())
    bulk = scrapes - 1      # the last scrape goes by remote write
    client = flight.connect(f"grpc://127.0.0.1:{srv.flight_port}")
    writer, _ = client.do_put(
        flight.FlightDescriptor.for_path(METRIC), schema)
    sent = 0
    # a backfill, target by target: a batch holds every bulk sample of
    # INSTANCES_PER_BATCH instances, series-major, so the server meets
    # each series in one batch and not in every one
    for a in range(0, n, INSTANCES_PER_BATCH):
        b = min(a + INSTANCES_PER_BATCH, n)
        inst_idx = np.repeat(np.arange(a, b, dtype=np.int32), n_le * bulk)
        le_idx = np.tile(np.repeat(np.arange(n_le, dtype=np.int32), bulk),
                         b - a)
        ts = np.repeat(ds.ts[a:b, :bulk], n_le, axis=0).reshape(-1)
        values = ds.values[a:b, :, :bulk].reshape(-1).astype(np.float64)
        keep = slice(None)
        if a < len(first):      # scrape 0 of `first` was sent above
            keep = ~((inst_idx < len(first))
                     & (np.tile(np.arange(bulk), (b - a) * n_le) == 0))
        rows = len(ts[keep])
        cols = [
            pa.DictionaryArray.from_arrays(
                pa.array(inst_idx[keep]), inst_dict).cast(pa.string()),
            pa.array([JOB] * rows, pa.string()),
            pa.DictionaryArray.from_arrays(
                pa.array(le_idx[keep]), le_dict).cast(pa.string()),
            pa.array(ts[keep], pa.timestamp("ms")),
            pa.array(values[keep]),
        ]
        writer.write_batch(pa.record_batch(cols, schema=schema))
        sent += rows
        srv.alive()
    writer.close()      # returns once every batch is applied: the ack
    client.close()
    say(f"load: {sent} rows acknowledged over Flight DoPut in "
        f"{time.perf_counter() - t0:.1f}s")
    t1 = time.perf_counter()
    bodies = scrape_bodies(np, ds, scrapes - 1, np.arange(n), heads)
    for body in bodies:
        srv.post("/v1/prometheus/write", body, _RW_HEADERS)
    acked_rw += n * n_le
    say(f"load: {acked_rw} rows acknowledged over POST "
        f"/v1/prometheus/write in {len(bodies) + 1} requests, "
        f"{time.perf_counter() - t1:.1f}s the last scrape")
    return {"acked_rows": sent + acked_rw, "flight_rows": sent,
            "remote_write_rows": acked_rw}
