"""TSBS devops `cpu-only`: table `cpu`, the source's ten tags and ten
double fields at a 10 s interval, made from the seed.

Source: upstream GreptimeDB docs/benchmarks/tsbs/README.md:40-48
(`tsbs_generate_data --use-case=cpu-only --scale=4000
--log-interval=10s`). The tag vocabularies are TSBS's own
(pkg/data/usecases/common: regions, datacenters, racks 0-99, os, arch,
team, service 0-19, service_version 0-1, service_environment). Assumed
(the configuration file says so): the VALUE generator. TSBS walks each
field randomly inside [0, 100] and prints short numbers; here every
value is an independent uniform draw from the 6400 multiples of 1/64 in
[0, 100): short in line protocol as TSBS's are, and exactly
representable in float32, so that the f64 the wire carries equals the
f32 the device keeps and selections compare exactly. A pure function of
(seed, hosts, cells).
"""

from __future__ import annotations

import time

FIELDS = [
    "usage_user", "usage_system", "usage_idle", "usage_nice",
    "usage_iowait", "usage_irq", "usage_softirq", "usage_steal",
    "usage_guest", "usage_guest_nice",
]
TAGS = [
    "hostname", "region", "datacenter", "rack", "os", "arch", "team",
    "service", "service_version", "service_environment",
]
INTERVAL_MS = 10_000
CELLS_PER_HOUR = 3_600_000 // INTERVAL_MS

_REGIONS = {
    "us-east-1": ["us-east-1a", "us-east-1b", "us-east-1c", "us-east-1e"],
    "us-west-1": ["us-west-1a", "us-west-1b"],
    "us-west-2": ["us-west-2a", "us-west-2b", "us-west-2c"],
    "eu-west-1": ["eu-west-1a", "eu-west-1b", "eu-west-1c"],
    "eu-central-1": ["eu-central-1a", "eu-central-1b"],
    "ap-southeast-1": ["ap-southeast-1a", "ap-southeast-1b"],
    "ap-southeast-2": ["ap-southeast-2a", "ap-southeast-2b"],
    "ap-northeast-1": ["ap-northeast-1a", "ap-northeast-1c"],
    "sa-east-1": ["sa-east-1a", "sa-east-1b", "sa-east-1c"],
}
_OS = ["Ubuntu16.10", "Ubuntu16.04LTS", "Ubuntu15.10"]
_ARCH = ["x64", "x86"]
_TEAM = ["SF", "NYC", "LON", "CHI"]
_ENV = ["production", "staging", "test"]


class Dataset:
    """values (F, hosts, cells) float32; tags {name: [hosts strings]}."""

    def __init__(self, values, tags, hours):
        self.values = values
        self.tags = tags
        self.hostnames = tags["hostname"]
        self.hosts = values.shape[1]
        self.cells = values.shape[2]
        self.hours = hours
        self.rows = self.hosts * self.cells
        self.reference = None   # the configuration's, set by the harness


def make_tags(np, seed: int, hosts: int) -> dict:
    rng = np.random.default_rng([seed, 0x7A65])
    regions = list(_REGIONS)
    region = [regions[i] for i in rng.integers(0, len(regions), hosts)]
    dc_pick = rng.random(hosts)
    tags = {
        "hostname": [f"host_{i}" for i in range(hosts)],
        "region": region,
        "datacenter": [
            _REGIONS[r][int(p * len(_REGIONS[r]))]
            for r, p in zip(region, dc_pick)],
        "rack": [str(v) for v in rng.integers(0, 100, hosts)],
        "os": [_OS[i] for i in rng.integers(0, len(_OS), hosts)],
        "arch": [_ARCH[i] for i in rng.integers(0, len(_ARCH), hosts)],
        "team": [_TEAM[i] for i in rng.integers(0, len(_TEAM), hosts)],
        "service": [str(v) for v in rng.integers(0, 20, hosts)],
        "service_version": [str(v) for v in rng.integers(0, 2, hosts)],
        "service_environment": [
            _ENV[i] for i in rng.integers(0, len(_ENV), hosts)],
    }
    return tags


def make_values(np, seed: int, hosts: int, cells: int):
    """(F, hosts, cells) float32: multiples of 1/64 in [0, 100), every
    one exactly representable in f32."""
    rng = np.random.default_rng([seed, 0xC9D5])
    return (rng.integers(0, 6400, (len(FIELDS), hosts, cells),
                         dtype=np.int16).astype(np.float32)
            * np.float32(1.0 / 64.0))


def make(np, seed: int, scale: dict) -> Dataset:
    hosts, hours = int(scale["hosts"]), int(scale["hours"])
    return Dataset(make_values(np, seed, hosts, hours * CELLS_PER_HOUR),
                   make_tags(np, seed, hosts), hours)


def create_table(srv):
    tags = ", ".join(f"{t} string" for t in TAGS)
    cols = ", ".join(f"{f} double" for f in FIELDS)
    srv.sql(f"create table cpu (ts timestamp time index, {tags}, {cols}, "
            f"primary key ({', '.join(TAGS)}))")


TAIL_CELLS = 6          # the last minute rides line protocol


def load(np, srv, ds: Dataset, say) -> dict:
    """Bulk rows as Arrow over Flight DoPut to the table path, the last
    minute as InfluxDB line protocol (how TSBS itself loads GreptimeDB),
    so rows acknowledged on both wire paths are counted and queried.
    Returns the acknowledged row counts."""
    import pyarrow as pa
    import pyarrow.flight as flight

    n_fields, hosts, cells = ds.values.shape
    bulk_cells = cells - TAIL_CELLS
    per_batch = max(1, 131_072 // hosts)
    schema = pa.schema(
        [(t, pa.string()) for t in TAGS] + [("ts", pa.timestamp("ms"))]
        + [(f, pa.float64()) for f in FIELDS]
    )
    tag_dicts = [pa.array(ds.tags[t], pa.string()) for t in TAGS]
    t0 = time.perf_counter()
    create_table(srv)
    client = flight.connect(f"grpc://127.0.0.1:{srv.flight_port}")
    writer, _ = client.do_put(
        flight.FlightDescriptor.for_path("cpu"), schema)
    sent = 0
    for c0 in range(0, bulk_cells, per_batch):
        c1 = min(c0 + per_batch, bulk_cells)
        w = c1 - c0
        host_idx = pa.array(
            np.repeat(np.arange(hosts, dtype=np.int32), w))
        cols = [pa.DictionaryArray.from_arrays(host_idx, d).cast(
            pa.string()) for d in tag_dicts]
        cols.append(pa.array(np.tile(
            np.arange(c0, c1, dtype=np.int64) * INTERVAL_MS, hosts
        ), pa.timestamp("ms")))
        for f in range(n_fields):
            cols.append(pa.array(
                ds.values[f, :, c0:c1].reshape(-1).astype(np.float64)))
        writer.write_batch(pa.record_batch(cols, schema=schema))
        sent += hosts * w
        srv.alive()
    # close() returns once the server has applied every batch of the
    # stream without error: that is the acknowledgement
    writer.close()
    client.close()
    flight_s = time.perf_counter() - t0
    say(f"load: {sent} rows acknowledged over Flight DoPut in "
        f"{flight_s:.1f}s")
    t1 = time.perf_counter()
    acked_influx = 0
    renderer = LineRenderer(np, ds)
    for c in range(bulk_cells, cells):
        body = "".join(renderer.cell(c)).encode()
        srv.post("/v1/influxdb/write?precision=ms", body)   # 204 = ack
        acked_influx += hosts
    say(f"load: {acked_influx} rows acknowledged over "
        f"POST /v1/influxdb/write in {time.perf_counter() - t1:.1f}s")
    return {"acked_rows": sent + acked_influx, "flight_rows": sent,
            "influx_rows": acked_influx}


class LineRenderer:
    """Full-width line protocol (10 tags, 10 fields), time-major as TSBS
    emits it. The tag part of a line is rendered once a host; repr() of
    a Python float is the shortest text that parses back to the same
    f64, so a line carries its values exactly."""

    def __init__(self, np, ds: Dataset):
        self.np, self.ds = np, ds
        self.heads = [
            "cpu," + ",".join(f"{t}={ds.tags[t][h]}" for t in TAGS)
            + " " + FIELDS[0] + "=" for h in range(ds.hosts)]
        self.seps = ["," + f + "=" for f in FIELDS[1:]]

    def cell(self, c: int) -> list:
        """One line a host, newline and all, for the cell at index c."""
        heads, s = self.heads, self.seps
        ts = f" {c * INTERVAL_MS}\n"
        block = self.ds.values[:, :, c].astype(self.np.float64).T.tolist()
        return [
            f"{heads[h]}{v[0]!r}{s[0]}{v[1]!r}{s[1]}{v[2]!r}{s[2]}{v[3]!r}"
            f"{s[3]}{v[4]!r}{s[4]}{v[5]!r}{s[5]}{v[6]!r}{s[6]}{v[7]!r}"
            f"{s[7]}{v[8]!r}{s[8]}{v[9]!r}{ts}"
            for h, v in enumerate(block)]
