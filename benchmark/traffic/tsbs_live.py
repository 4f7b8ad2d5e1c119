"""A dashboard over a fleet that is still reporting: one closed-loop
client on one connection sends a repeating cycle of one line-protocol
body and some last-hour panels. One traffic mix is a file of
parameters:

    batch_lines       lines a body (an aggregator's flush)
    panels_per_body   `POST /v1/sql` panels that follow each body
    span_minutes      the panels' window: the last so many minutes
    bucket_s          the panels' bucket

Body c (`POST /v1/influxdb/write?precision=ms`, full width: 10 tags, 10
fields) holds hosts [k*batch, (k+1)*batch) of live tick c // per_tick,
k = c % per_tick: the stream of `datagen/tsbs_cpu_live.py` in TSBS's
time-major order, continuing the held data with no gap. Each panel asks
`max(usage_user)` of one host by `bucket_s` over `ts >= T - span AND ts
< T`, T the end of the newest tick any body of which has been sent: a
multiple of the 10 s cell and not of the minute, so the first and the
last bucket are partial, as Grafana's "Last 1 hour" is. The first panel
of a cycle names a host of the body just acknowledged, the others any
host. Request i is a pure function of (seed, i).

One client sends in order, so what a panel has to answer is known
before the run: the held rows and the rows of every body with an index
up to its cycle's (read-your-acknowledged-writes). The records of the
window are bodies and panels; the harness counts every record as a
query in three places, which this module meets so: `parse` and
`expected` give {} for a body; there is no `EXEC_PATH`, and
`queries_off_device` is reckoned in `after_window` over the panels, from
counters read in `setup`; `end_to_end` takes its percentiles from the
panel records of `good`, not from `lat`.
"""

from __future__ import annotations

import json
import urllib.parse

from benchmark.datagen.tsbs_cpu import (
    FIELDS, INTERVAL_MS, TAGS, Dataset, LineRenderer,
)
from benchmark.lib.compare import compare_rows
from benchmark.lib.loadgen import percentile
from benchmark.readers import delta

KIND = "query"
_WRITE = "/v1/influxdb/write?precision=ms"
_HEADERS = {"Content-Type": "application/x-www-form-urlencoded"}
_HOUR_CELLS = 3_600_000 // INTERVAL_MS
_RANGE = ("gtpu_query_exec_path_total", {"kind": "range"})
_REBUILDS = ["rebuild_out_of_order", "rebuild_new_series",
             "rebuild_capacity", "rebuild_flushed", "rebuild_mutation",
             "rebuild_mesh", "rebuild_multi_region"]


class State:
    pass


def prepare(np, params: dict, ds, seed: int, budget: int) -> State:
    st = State()
    st.ds = ds
    st.batch = min(int(params["batch_lines"]), ds.hosts)
    st.per_tick = -(-ds.hosts // st.batch)          # bodies a tick
    st.cycle = 1 + int(params["panels_per_body"])
    st.span_cells = int(params["span_minutes"]) * 60_000 // INTERVAL_MS
    st.bucket_ms = int(params["bucket_s"]) * 1000
    st.bucket_cells = st.bucket_ms // INTERVAL_MS
    st.held = ds.cells
    if st.span_cells > st.held:
        raise ValueError("the panels' window is longer than the held data")
    # whole cycles, as many as the budget asks for and the live part of
    # the stream holds bodies for
    st.n = min(int(budget) // st.cycle, ds.live_cells * st.per_tick) * st.cycle
    rng = np.random.default_rng([seed, 0x11FE])
    st.draw = rng.integers(0, 2**31, st.n)
    st.row_host = int(np.random.default_rng([seed, 0x10AD]).integers(
        0, ds.hosts))
    st.bodies = _render(np, st)
    return st


def _render(np, st) -> list:
    """The bodies of the cycles, rendered before the window."""
    ds = st.ds
    renderer = LineRenderer(np, Dataset(ds.stream, ds.tags, ds.hours))
    bodies: list = []
    n = st.n // st.cycle
    for tick in range(-(-n // st.per_tick)):
        lines = renderer.cell(st.held + tick)
        bodies.extend("".join(lines[k * st.batch:(k + 1) * st.batch]).encode()
                      for k in range(st.per_tick))
    return bodies[:n]


def _body_hosts(st, c: int) -> range:
    k = c % st.per_tick
    return range(k * st.batch, min((k + 1) * st.batch, st.ds.hosts))


def _panel(st, i: int):
    """-> (cycle, host, first cell, end cell) of panel request i."""
    c, k = divmod(i, st.cycle)
    d = int(st.draw[i])
    if k == 1:
        # a host of the body just acknowledged: the guarantee is held to
        # a row that is milliseconds old in every cycle
        hosts = _body_hosts(st, c)
        host = hosts[d % len(hosts)]
    else:
        host = d % st.ds.hosts
    end = st.held + c // st.per_tick + 1
    return c, host, end - st.span_cells, end


def sql(st, i: int) -> str:
    _c, host, lo, hi = _panel(st, i)
    return _sql(st, host, lo, hi)


def _sql(st, host: int, lo: int, hi: int) -> str:
    b = st.bucket_ms // 1000
    return (f"SELECT ts, hostname, max({FIELDS[0]}) RANGE '{b}s' FROM cpu "
            f"WHERE hostname IN ('{st.ds.hostnames[host]}') AND "
            f"ts >= {lo * INTERVAL_MS} AND ts < {hi * INTERVAL_MS} "
            f"ALIGN '{b}s' BY (hostname)")


def request(st, i: int):
    if i % st.cycle == 0:
        return "POST", _WRITE, st.bodies[i // st.cycle], {}
    body = urllib.parse.urlencode({"sql": sql(st, i)}).encode()
    return "POST", "/v1/sql", body, _HEADERS


def parse(np, st, i: int, raw: bytes) -> dict:
    if i % st.cycle == 0:
        return {}
    rows = json.loads(raw)["output"][-1]["records"]["rows"]
    return {(r[0], r[1]): tuple(r[2:]) for r in rows}


def _acked_cells(st, host: int, c: int) -> int:
    """How many cells of `host` are acknowledged once body c is: the
    held ones and the live ticks whose body of this host has an index
    up to c."""
    k = host // st.batch
    return st.held + (0 if c < k else (c - k) // st.per_tick + 1)


def expected(np, st, i: int, precision: str = "float64",
             bodies_behind: int = 0) -> dict:
    """What panel i has to answer: the reference over the held rows and
    the rows of every body up to its cycle's. `bodies_behind` = 1 is
    what a grid that missed the newest body would answer."""
    if i % st.cycle == 0:
        return {}
    c, host, lo, hi = _panel(st, i)
    # whole buckets around the window; the mask cuts them to the window
    # and to the rows acknowledged
    b = st.bucket_cells
    lo_b, hi_b = lo // b * b, -(-hi // b) * b
    mask = np.zeros((1, st.ds.stream.shape[2]), bool)
    mask[0, lo:min(hi, _acked_cells(st, host, c - bodies_behind))] = True
    ref = st.ds.reference
    vals, present = ref.range_agg(
        np, st.ds.stream[:, host:host + 1], fields=[0], hosts=None,
        c_lo=lo_b, c_hi=hi_b, bucket_cells=b, op="max", mask=mask,
        precision=precision)
    return ref.as_rows(vals, present, hostnames=[st.ds.hostnames[host]],
                       hosts=None, t_lo_ms=lo_b * INTERVAL_MS,
                       bucket_ms=st.bucket_ms)


def _panels(records, cycle: int) -> list:
    return [r for r in records if r.i % cycle]


def end_to_end(st, good, lat, window_s) -> dict:
    """`good`: the records answered inside the window, bodies and
    panels; `lat` holds the bodies' latencies too and is not used. The
    percentiles are of the panels, the rate is panels answered in the
    window over its length: on one connection it carries the bodies'
    time and the grid's upkeep."""
    panels = sorted((r.t_done - r.t_send) * 1000.0
                    for r in _panels(good, st.cycle))
    if not panels or window_s <= 0:
        return {}
    return {"query_p50_ms": percentile(panels, 0.50),
            "query_p95_ms": percentile(panels, 0.95),
            "queries_per_s": len(panels) / window_s}


def _worst(np, st, n: int, got) -> dict:
    worst: dict = {"panels_differing": 0}
    for i in range(n):
        if i % st.cycle == 0:
            continue
        cmp = compare_rows(np, got(i), expected(np, st, i))
        worst["panels_differing"] += bool(
            cmp["rows_missing"] or cmp["values_differing"])
        for k, v in cmp.items():
            worst[k] = max(worst.get(k, 0), v)
    return worst


def control(np, st, precision: str, n: int) -> dict:
    """The cell's numbers when the reference, computed in `precision`,
    stands in the program's place for the first `n` requests."""
    return _worst(np, st, min(n, st.n),
                  lambda i: expected(np, st, i, precision=precision))


def control_stale(np, st, n: int) -> dict:
    """The cell's numbers when a grid that missed the newest body
    stands in the program's place: it differs only in a panel whose
    host is in that body (`panels_differing` of the panels compared)."""
    return _worst(np, st, min(n, st.n),
                  lambda i: expected(np, st, i, bodies_behind=1))


def shapes(st) -> dict:
    """What the device has to touch, for the byte models. One upkeep:
    the cells a body's rows land in, the planes of the entry that the
    configuration's `grid_warm_sql` builds (row count, first and last
    offset, `s` and `mx` of ten fields: the count of an all-valid field
    is the row count's array), the columns a cell brings (series, cell,
    rows, two offsets, a delta a plane) and the program's bucket. One
    panel: one field of one host over the window's cells, as the
    latency cell's (`tsbs_range.shapes`)."""
    planes = 3 + 2 * len(FIELDS)
    return {"batch_lines": st.batch, "hosts": st.ds.hosts,
            "grid_planes": planes, "append_columns": 5 + 2 * len(FIELDS),
            "append_bucket": 512 * -(-st.batch // 512),
            "hosts_selected": 1, "fields": 1, "span_cells": st.span_cells,
            "buckets": st.span_cells // st.bucket_cells}


def setup(np, st, srv, say):
    """A window that ends on a bucket's edge touches one bucket fewer
    than one that does not, and the step count is a static shape of the
    range program: one tick in `bucket_cells` ends so, past the
    warm-up's cycles (ten a tick). Both shapes are warmed here, over
    the held hour, ahead of the first body. Then the counters that
    `after_window` reckons from are read."""
    for end in (st.held, st.held - 1):
        srv.sql(_sql(st, 0, end - st.span_cells, end))
    st.m_setup = srv.metrics()


def readback_sql() -> str:
    """Hourly `avg` of all ten fields by host over every row, held and
    live: through the device path, over the grid that was kept up."""
    items = ", ".join(f"avg({f}) RANGE '3600s'" for f in FIELDS)
    return (f"SELECT ts, hostname, {items} FROM cpu "
            "WHERE ts >= -3600000 ALIGN '3600s' BY (hostname)")


def rows_sql(st) -> str:
    return (f"SELECT ts, {', '.join(TAGS + FIELDS)} FROM cpu WHERE "
            f"hostname = '{st.ds.hostnames[st.row_host]}' AND "
            f"ts >= {st.held * INTERVAL_MS} ORDER BY ts")


def expected_readback(np, st, bodies: int, precision: str = "float64"):
    """What the read-back has to answer once bodies [0, bodies) are
    acknowledged: (the hourly avg's rows, the live rows of the drawn
    host at full width)."""
    ds, ref = st.ds, st.ds.reference
    acked = np.array([_acked_cells(st, h, bodies - 1)
                      for h in range(ds.hosts)])
    cells = ds.stream.shape[2]
    mask = np.arange(cells)[None, :] < acked[:, None]
    hours = -(-int(acked.max()) // _HOUR_CELLS)
    pad = max(0, hours * _HOUR_CELLS - cells)
    values = ds.stream if not pad else np.concatenate(
        [ds.stream, np.zeros(ds.stream.shape[:2] + (pad,), np.float32)], 2)
    want, present = ref.range_agg(
        np, values, fields=range(len(FIELDS)), hosts=None, c_lo=0,
        c_hi=hours * _HOUR_CELLS, bucket_cells=_HOUR_CELLS, op="avg",
        mask=np.pad(mask, ((0, 0), (0, pad))), precision=precision)
    avg = ref.as_rows(want, present, hostnames=ds.hostnames, hosts=None,
                      t_lo_ms=0, bucket_ms=3_600_000)
    h = st.row_host
    tags = [ds.tags[t][h] for t in TAGS]
    vals = ds.stream[:, h, :].astype(np.float64)
    rows = [[c * INTERVAL_MS] + tags + vals[:, c].tolist()
            for c in range(st.held, int(acked[h]))]
    return avg, rows


def after_window(np, st, srv, records, run, ok_status):
    """On the live server: an acknowledged row is counted and queried
    back, the panels ran on the device, and the grid was kept up, not
    rebuilt. The limits are the cell's `after_window_limits`."""
    limits = run.wl["after_window_limits"]
    bodies = sum(1 for r in records
                 if r.i % st.cycle == 0 and r.status in ok_status)
    panels = len(_panels(records, st.cycle))
    live_rows = sum(len(_body_hosts(st, c)) for c in range(bodies))
    ctx = {"m0": st.m_setup, "m1": srv.metrics()}
    on_device = delta(ctx, _RANGE[0], {**_RANGE[1], "path": "device"}) or 0.0
    ranged = delta(ctx, *_RANGE) or 0.0
    run.number("queries_off_device",
               ranged - on_device + max(0.0, panels - ranged),
               limits["queries_off_device"])
    run.number("grid_rebuilds_in_window",
               delta(ctx, "gtpu_grid_upkeep_total",
                     {"outcome": _REBUILDS}) or 0.0,
               limits["grid_rebuilds_in_window"])
    counted = int(srv.sql("select count(*) from cpu")[0][0])
    run.number("rows_acked_not_counted",
               abs(counted - st.ds.rows - live_rows), 0)
    got_avg = {(r[0], r[1]): tuple(r[2:]) for r in srv.sql(readback_sql())}
    ctx2 = {"m0": ctx["m1"], "m1": srv.metrics()}
    run.number("readback_off_device",
               int((delta(ctx2, _RANGE[0], {**_RANGE[1], "path": "device"})
                    or 0.0) < 1), limits["readback_off_device"])
    got_rows = {r[0]: r[1:] for r in srv.sql(rows_sql(st))}
    want_avg, want_rows = expected_readback(np, st, bodies)
    avg = compare_rows(np, got_avg, want_avg)
    wrong = sum(1 for r in want_rows if got_rows.get(r[0]) != r[1:])
    run.notes["readback"] = {
        "bodies_acked": bodies, "rows_acked": live_rows, "counted": counted,
        "panels": panels, "groups": len(got_avg),
        "rows_of_host": len(got_rows)}
    for name, value in (
            ("readback_rows_missing", avg["rows_missing"]),
            ("readback_avg_rel_err", avg["worst_rel_err"]),
            ("readback_rows_differing",
             wrong + max(0, len(got_rows) - len(want_rows)))):
        run.number(name, value, limits[name])
