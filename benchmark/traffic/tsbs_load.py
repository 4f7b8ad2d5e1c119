"""TSBS's load as upstream runs it: closed-loop workers posting bodies of
full-width line protocol (10 tags, 10 fields) to
`POST /v1/influxdb/write`, into an empty table in a fresh data home.
One traffic mix is a file of parameters:

    batch_lines   lines per body (TSBS `--batch-size`)

Body i holds rows [i*batch, (i+1)*batch) of the time-major stream TSBS
emits (every host for the first timestamp, then the next), rendered
before the window from the seed's data. After the window, on the live
server: flush, `count(*)` equals the rows acknowledged; the hourly
`avg` and `max` of all ten fields by host, through the device path, and
every row of one host drawn from the seed, all ten tags and ten fields
wide, equal the reference over exactly the acknowledged rows.
"""

from __future__ import annotations

from benchmark.datagen.tsbs_cpu import (
    FIELDS, INTERVAL_MS, TAGS, LineRenderer, create_table,
)
from benchmark.lib.compare import compare_rows
from benchmark.lib.loadgen import percentile

KIND = "ingest"
_PATH = "/v1/influxdb/write?precision=ms"
_HOUR_CELLS = 3_600_000 // INTERVAL_MS


class State:
    pass


def prepare(np, params: dict, ds, seed: int, budget: int) -> State:
    st = State()
    st.ds = ds
    st.batch = int(params["batch_lines"])
    st.n = min(int(budget), ds.rows // st.batch)
    st.bodies = _render(np, ds, st.batch, st.n)
    # the host whose rows are read back at full width
    st.row_host = int(np.random.default_rng([seed, 0x10AD]).integers(
        0, ds.hosts))
    return st


def _render(np, ds, batch: int, n: int) -> list:
    renderer = LineRenderer(np, ds)
    lines: list = []
    c = 0
    while len(lines) < n * batch:
        lines.extend(renderer.cell(c))
        c += 1
    return ["".join(lines[i * batch:(i + 1) * batch]).encode()
            for i in range(n)]


def request(st, i):
    if i >= st.n:
        raise IndexError("the data holds no more bodies")
    return "POST", _PATH, st.bodies[i], {}


def setup(np, st, srv, say):
    create_table(srv)


def shapes(st) -> dict:
    return {"batch_lines": st.batch, "hosts": st.ds.hosts}


def end_to_end(st, good, lat, window_s) -> dict:
    """`good`: the bodies acknowledged inside the window; `lat`: the
    ascending latencies (ms) of every body sent in it."""
    out = {"ingest_rows_per_s": len(good) * st.batch / window_s}
    if lat:
        out["write_p50_ms"] = percentile(lat, 0.5)
    return out


def readback_sql(agg: str) -> str:
    """Hourly `agg` of all ten fields by host over every row: the shape
    that the default gates send through the device."""
    items = ", ".join(f"{agg}({f}) RANGE '3600s'" for f in FIELDS)
    return (f"SELECT ts, hostname, {items} FROM cpu "
            "WHERE ts >= -3600000 ALIGN '3600s' BY (hostname)")


def rows_sql(st) -> str:
    return (f"SELECT ts, {', '.join(TAGS + FIELDS)} FROM cpu WHERE "
            f"hostname = '{st.ds.hostnames[st.row_host]}' ORDER BY ts")


def _acked_mask(np, st, acked):
    """(hosts, cells) bool: the rows of the bodies `acked` (indices)."""
    ds = st.ds
    mask = np.zeros(ds.hosts * ds.cells, bool)
    for i in acked:
        mask[i * st.batch:(i + 1) * st.batch] = True
    return mask.reshape(ds.cells, ds.hosts).T


def expected_readback(np, st, acked, precision: str = "float64") -> dict:
    """What the read-back has to answer when the bodies `acked` were
    acknowledged: {"avg": rows, "max": rows, "rows": [[ts, tags..,
    fields..]] of the drawn host}."""
    ds, ref = st.ds, st.ds.reference
    mask = _acked_mask(np, st, acked)
    hours = -(-(int(mask.any(axis=0).nonzero()[0].max()) + 1)
              // _HOUR_CELLS) if len(acked) else 0
    out = {}
    for op in ("avg", "max"):
        want, present = ref.range_agg(
            np, ds.values, fields=range(len(FIELDS)), hosts=None, c_lo=0,
            c_hi=hours * _HOUR_CELLS, bucket_cells=_HOUR_CELLS, op=op,
            mask=mask, precision=precision)
        out[op] = ref.as_rows(want, present, hostnames=ds.hostnames,
                              hosts=None, t_lo_ms=0, bucket_ms=3_600_000)
    h = st.row_host
    tags = [ds.tags[t][h] for t in TAGS]
    vals = ref.lower(np, ds.values[:, h, :], precision).astype(np.float64)
    out["rows"] = [[int(c) * INTERVAL_MS] + tags + vals[:, c].tolist()
                   for c in mask[h].nonzero()[0]]
    return out


def readings(np, got: dict, want: dict) -> dict:
    avg = compare_rows(np, got["avg"], want["avg"])
    mx = compare_rows(np, got["max"], want["max"])
    rows = {r[0]: r[1:] for r in got["rows"]}
    wrong = sum(1 for r in want["rows"] if rows.get(r[0]) != r[1:])
    return {"readback_rows_missing": max(avg["rows_missing"],
                                         mx["rows_missing"]),
            "readback_max_differing": mx["values_differing"],
            "readback_avg_rel_err": avg["worst_rel_err"],
            "readback_rows_differing":
                wrong + max(0, len(rows) - len(want["rows"]))}


def control(np, st, precision: str, n: int) -> dict:
    """The cell's numbers when the reference, computed in `precision`,
    stands in the program's place, the first `n` bodies acknowledged."""
    acked = list(range(min(n, st.n)))
    return readings(np, expected_readback(np, st, acked, precision),
                    expected_readback(np, st, acked))


def after_window(np, st, srv, records, run, ok_status):
    """An acknowledged row is counted and queried back."""
    acked = sorted(r.i for r in records if r.status in ok_status)
    rows = len(acked) * st.batch
    srv.sql("ADMIN flush_table('cpu')")
    counted = int(srv.sql("select count(*) from cpu")[0][0])
    run.number("rows_acked_not_counted", abs(counted - rows), 0)
    got = {"avg": {}, "max": {}, "rows": []}
    m0 = srv.metrics()
    if rows:
        for agg in ("avg", "max"):
            got[agg] = {(r[0], r[1]): tuple(r[2:])
                        for r in srv.sql(readback_sql(agg))}
    m1 = srv.metrics()
    if rows:
        got["rows"] = srv.sql(rows_sql(st))
    key = ("gtpu_query_exec_path_total",
           (("kind", "range"), ("path", "device")))
    run.number("readback_off_device",
               int(m1.get(key, 0.0) - m0.get(key, 0.0) < 2) if rows else 0,
               0)
    run.notes["readback"] = {"rows_acked": rows, "counted": counted,
                             "groups": len(got["avg"]),
                             "rows_of_host": len(got["rows"])}
    for name, value in readings(
            np, got, expected_readback(np, st, acked)).items():
        run.number(name, value, run.wl["limits"][name])
