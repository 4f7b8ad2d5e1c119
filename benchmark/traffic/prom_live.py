"""An SLO dashboard over a service whose Prometheus keeps remote-writing:
one closed-loop client on one keep-alive connection sends a repeating
cycle of some remote-write requests and one quantile panel. One traffic
mix is a file of parameters:

    phis                 the quantiles, one a cycle, in turn
    range_s              the panel's range (`end - start`)
    step_s               the step: the scrape interval
    window_s             the range selector's window (`[5m]` = 300)
    writes_per_panel     `POST /v1/prometheus/write` requests a cycle
    samples_per_request  remote write's `max_samples_per_send`

Request w of the writes (`POST /v1/prometheus/write`, snappy-framed
protobuf, labels sorted) holds samples [w * per, (w + 1) * per) of the
live stream of `datagen/prom_hist_live.py`: every sample of the scrapes
after the held ones, by sample time, a scrape's twelve `le` together.
The panel of cycle c asks

    histogram_quantile(phi, sum by (le) (rate(<metric>[window])))

as `query_range` over `range_s` at `step_s`, phi the cycle's in turn,
`end` the first multiple of the step at or after the newest sample of
the writes of cycles up to c: every acknowledged sample is inside the
last step's window. Request i is a pure function of (seed, i).

One client sends in order, so what a panel has to answer is known
before the run: the held samples and those of every write of its cycle
and the cycles before it (read-your-acknowledged-writes). The records of
the window are writes and panels; `parse` and `expected` give {} for a
write; there is no `EXEC_PATH`, and `queries_off_device` is reckoned in
`after_window` over the panels and the read-back, from counters read in
`setup`; `end_to_end` takes its percentiles from the panel records of
`good`, not from `lat`.

The comparison's cost stays under the run's as the program gets faster:
a step before a panel's last is over samples all acknowledged, so every
panel shares one pass of the reference over the whole stream for them;
the last step holds the newest scrape of each instance acknowledged or
not, and shares, for each step that ends a panel, the rates of every
series with and without its newest sample.
"""

from __future__ import annotations

import json
import urllib.parse

from benchmark.datagen import prom_hist_live as live
from benchmark.datagen.prom_hist import (
    BASE_MS, BOUNDS, INTERVAL_MS, LE, METRIC, _RW_HEADERS,
)
from benchmark.lib.compare import compare_rows
from benchmark.lib.loadgen import percentile
from benchmark.readers import delta

KIND = "query"
_WRITE = "/v1/prometheus/write"
_QUERY = "/v1/prometheus/api/v1/query_range"
_HEADERS = {"Content-Type": "application/x-www-form-urlencoded"}
_FAST = "greptime_promql_fast_path_total"
_REBUILDS = ["rebuild_" + r for r in (
    "out_of_order", "new_series", "irregular", "mutation", "mesh",
    "flushed", "capacity", "multi_region")]
_PAST = 2 ** 62         # a sample time past every step


class State:
    pass


def prepare(np, params: dict, ds, seed: int, budget: int) -> State:
    st = State()
    st.ds = ds
    st.phis = [float(p) for p in params["phis"]]
    st.range_ms = int(params["range_s"]) * 1000
    st.step_ms = int(params["step_s"]) * 1000
    st.window_ms = int(params["window_s"]) * 1000
    st.writes = int(params["writes_per_panel"])
    st.per = int(params["samples_per_request"])
    st.cycle = st.writes + 1
    if st.step_ms != INTERVAL_MS or st.window_ms % INTERVAL_MS:
        raise ValueError("the panel steps at the scrape interval")
    # whole cycles, as many as the budget asks for and the live part of
    # the stream holds writes for
    cycles = min(int(budget) // st.cycle,
                 live.requests(ds, st.per) // st.writes)
    st.n = cycles * st.cycle
    st.bodies = [live.render(np, ds, w, st.per)
                 for w in range(cycles * st.writes)]
    ends = [panel(st, c * st.cycle + st.writes)[2] for c in (0, cycles - 1)]
    if ends[0] - st.range_ms - st.window_ms < BASE_MS:
        raise ValueError("the held data holds no whole panel")
    st.all_steps = np.arange(ends[0] - st.range_ms, ends[1] + 1, st.step_ms,
                             dtype=np.int64)
    st.memo = {}
    return st


def panel(st, i: int) -> tuple:
    """(samples acknowledged before it, phi, end_ms) of panel request i."""
    c = i // st.cycle
    acked = min((c + 1) * st.writes * st.per, st.ds.live_samples)
    return acked, st.phis[c % len(st.phis)], live.panel_end(st.ds, acked)


def promql(phi: float, window_ms: int) -> str:
    return (f"histogram_quantile({phi!r}, sum by (le) "
            f"(rate({METRIC}[{window_ms // 60_000}m])))")


def _form(query: str, start: int, end: int, step: int) -> bytes:
    return urllib.parse.urlencode({
        "query": query, "start": start // 1000, "end": end // 1000,
        "step": step // 1000}).encode()


def request(st, i: int):
    c, k = divmod(i, st.cycle)
    if k < st.writes:
        return "POST", _WRITE, st.bodies[c * st.writes + k], _RW_HEADERS
    _acked, phi, end = panel(st, i)
    return "POST", _QUERY, _form(promql(phi, st.window_ms),
                                 end - st.range_ms, end, st.step_ms), _HEADERS


def _is_write(st, i: int) -> bool:
    return i % st.cycle < st.writes


def _matrix(raw: bytes) -> dict:
    doc = json.loads(raw)
    if doc["status"] != "success":
        raise ValueError(doc)
    return {(round(float(t) * 1000), tuple(sorted(s["metric"].items()))):
            (float(v),)
            for s in doc["data"]["result"] for t, v in s["values"]}


def parse(np, st, i: int, raw: bytes) -> dict:
    return {} if _is_write(st, i) else _matrix(raw)


# -- the reference -------------------------------------------------------

def _full(np, st, precision: str):
    """`sum by (le) (rate(..))` at every step any panel asks, over the
    whole stream: what a step before a panel's last answers."""
    key = ("full", precision)
    if key not in st.memo:
        ref, full = st.ds.reference, st.ds.full
        total, held = 0.0, False
        with np.errstate(all="ignore"):
            for lo in range(0, full.instances, 256):
                rate, present = ref.extrapolated_rate(
                    np, full.ts[lo:lo + 256], full.values[lo:lo + 256],
                    st.all_steps, st.window_ms, precision)
                part, any_present = ref.sum_by_le(
                    np, rate, present, precision)
                total = ref.lower(np, total + part, precision)
                held = held | any_present
        st.memo[key] = total, held
    return st.memo[key]


def _last(np, st, t: int, precision: str):
    """At step t, each series' rate with and without its sample in
    (t - step, t], and that sample's place in the live stream (-1 for a
    held one, acknowledged before any panel)."""
    key = ("last", t, precision)
    if key not in st.memo:
        ref, full = st.ds.reference, st.ds.full
        k = (t - BASE_MS - full.offsets) // INTERVAL_MS    # (instances,)
        lo = max(0, int(k.min()) - st.window_ms // INTERVAL_MS - 2)
        hi = min(full.scrapes, int(k.max()) + 1)
        ts, values = full.ts[:, lo:hi], full.values[:, :, lo:hi]
        col = np.arange(hi - lo)[None, :]
        without = np.where(col >= (k - lo)[:, None], _PAST + col, ts)
        with np.errstate(all="ignore"):
            rw, pw = ref.extrapolated_rate(
                np, ts, values, [t], st.window_ms, precision)
            ro, po = ref.extrapolated_rate(
                np, without, values, [t], st.window_ms, precision)
        rank = st.ds.rank[np.arange(full.instances), np.minimum(
            k, full.scrapes - 1)]
        pos = np.where(rank[:, None] >= 0,
                       rank[:, None] * len(LE) + np.arange(len(LE)), -1)
        st.memo[key] = rw[:, :, 0], pw[:, :, 0], ro[:, :, 0], po[:, :, 0], pos
    return st.memo[key]


def _direct(np, st, t: int, acked: int, precision: str):
    """Each series' rate at step t over exactly the samples acknowledged
    once `acked` are: a series a row, a sample not acknowledged moved
    past the step."""
    ref, full, n_le = st.ds.reference, st.ds.full, len(LE)
    k = (t - BASE_MS - full.offsets) // INTERVAL_MS
    lo = max(0, int(k.min()) - st.window_ms // INTERVAL_MS - 2)
    hi = min(full.scrapes, int(k.max()) + 1)
    rank = np.repeat(st.ds.rank[:, lo:hi], n_le, axis=0)
    pos = rank * n_le + np.tile(np.arange(n_le), full.instances)[:, None]
    col = np.arange(hi - lo)[None, :]
    ts = np.where((rank >= 0) & (pos >= acked), _PAST + col,
                  np.repeat(full.ts[:, lo:hi], n_le, axis=0))
    with np.errstate(all="ignore"):
        rate, present = ref.extrapolated_rate(
            np, ts, full.values[:, :, lo:hi].reshape(-1, 1, hi - lo), [t],
            st.window_ms, precision)
    return rate.reshape(-1, n_le), present.reshape(-1, n_le)


def _buckets(np, st, acked: int, end: int, precision: str):
    """(12, steps) bucket sums and presence of the panel that ends at
    `end` once `acked` live samples are acknowledged. A step before the
    first sample not acknowledged reads the whole stream's; a step at
    or after it (a panel's last) takes each series' rate with or without
    its newest sample, as that sample is acknowledged or not."""
    ref = st.ds.reference
    total, held = _full(np, st, precision)
    j1 = int((end - st.all_steps[0]) // st.step_ms) + 1
    j0 = j1 - st.range_ms // st.step_ms - 1
    buckets, present = total[:, j0:j1].copy(), held[:, j0:j1].copy()
    if acked >= st.ds.live_samples:
        return buckets, present
    unacked = live.newest(st.ds, acked + 1)
    for j in range(j1 - j0):
        t = int(st.all_steps[j0 + j])
        if t < unacked:
            continue
        if t - unacked < INTERVAL_MS:
            rw, pw, ro, po, pos = _last(np, st, t, precision)
            seen = pos < acked
            rate, pres = np.where(seen, rw, ro), np.where(seen, pw, po)
        else:
            # (a control far behind: a series may miss two samples)
            rate, pres = _direct(np, st, t, acked, precision)
        with np.errstate(all="ignore"):
            b, p = ref.sum_by_le(np, rate[:, :, None], pres[:, :, None],
                                 precision)
        buckets[:, j], present[:, j] = b[:, 0], p[:, 0]
    return buckets, present


def expected(np, st, i: int, precision: str = "float64",
             requests_behind: int = 0) -> dict:
    """What panel i has to answer: the reference over the held samples
    and those of every write of its cycle and the ones before it.
    `requests_behind` = 1 is what a grid one write behind would hold."""
    if _is_write(st, i):
        return {}
    acked, phi, end = panel(st, i)
    acked = max(0, acked - requests_behind * st.per)
    buckets, present = _buckets(np, st, acked, end, precision)
    with np.errstate(all="ignore"):
        got = st.ds.reference.histogram_quantile(
            np, BOUNDS, buckets, present, phi, precision)
    first = end - st.range_ms
    return {(first + j * st.step_ms, ()): (v,) for j, v in got.items()}


def end_to_end(st, good, lat, window_s) -> dict:
    """`good`: the records answered inside the window, writes and
    panels; `lat` holds the writes' latencies too and is not used. The
    percentiles are of the panels; the rate is panels answered in the
    window over its length: on one connection it carries the writes'
    time and the grid's upkeep."""
    panels = sorted((r.t_done - r.t_send) * 1000.0 for r in good
                    if not _is_write(st, r.i))
    if not panels or window_s <= 0:
        return {}
    return {"query_p50_ms": percentile(panels, 0.50),
            "query_p95_ms": percentile(panels, 0.95),
            "queries_per_s": len(panels) / window_s}


def _worst(np, st, n: int, got) -> dict:
    worst: dict = {"panels_differing": 0}
    for i in range(min(n, st.n)):
        if _is_write(st, i):
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
    return _worst(np, st, n,
                  lambda i: expected(np, st, i, precision=precision))


def control_stale(np, st, n: int) -> dict:
    """A grid one write behind in the program's place: the panels for
    the first `n` requests, and the exact read-back after the last
    write of them. The panels move by far less than their limit: the
    read-back is what holds read-your-writes."""
    out = _worst(np, st, n,
                 lambda i: expected(np, st, i, requests_behind=1))
    writes = sum(1 for i in range(min(n, st.n)) if _is_write(st, i))
    acked = min(writes * st.per, st.ds.live_samples)
    q, _start, _end = readback(np, st, acked)
    got = expected_readback(np, st, acked - st.per, q)
    want = expected_readback(np, st, acked, q)
    cmp = compare_rows(np, got, want)
    out["readback_rows_missing"] = cmp["rows_missing"]
    out["readback_values_differing"] = cmp["values_differing"]
    return out


def shapes(st) -> dict:
    """What one upkeep makes the device touch, for the byte model: the
    samples of a cycle's writes, in the append program's bucket."""
    samples = st.writes * st.per
    bucket = 1024
    while bucket < samples:
        bucket *= 2
    return {"upkeep_samples": samples, "append_bucket": bucket}


def setup(np, st, srv, say):
    """The counters `after_window` reckons from, read before the first
    write of the warm-up."""
    st.m_setup = srv.metrics()


# -- after the window ------------------------------------------------------

def readback(np, st, acked: int) -> tuple:
    """The read-back of every live sample of the instances of the last
    acknowledged write, through the device path: (query, start, end)."""
    ds = st.ds
    w = max(0, -(-acked // st.per) - 1)
    p = np.arange(w * st.per, min(acked, (w + 1) * st.per))
    inst = np.unique(ds.live_pairs[p // len(LE), 0]) if len(p) else \
        np.zeros(0, np.int64)
    names = "|".join(ds.names[i] for i in inst.tolist())
    q = (f'sum by (instance, le) (last_over_time({METRIC}'
         f'{{instance=~"{names}"}}[{st.step_ms // 1000}s]))')
    start = BASE_MS + (ds.held_scrapes - 1) * INTERVAL_MS
    return (q, inst), start, live.panel_end(ds, acked)


def expected_readback(np, st, acked: int, query) -> dict:
    """The sample of each instance and `le` in every step's interval
    (t - step, t], from the held part to the newest step, where it is
    acknowledged once `acked` live samples are."""
    _q, inst = query
    ds, full = st.ds, st.ds.full
    _qq, start, end = readback(np, st, acked)
    out = {}
    for i in inst.tolist():
        for k in range(full.scrapes):
            t_s = int(full.ts[i, k])
            t = -(-t_s // INTERVAL_MS) * INTERVAL_MS
            if not start <= t <= end:
                continue
            r = int(ds.rank[i, k])
            for le in range(len(LE)):
                if r < 0 or r * len(LE) + le < acked:
                    out[(t, (("instance", ds.names[i]), ("le", LE[le])))] = (
                        float(full.values[i, le, k]),)
    return out


def after_window(np, st, srv, records, run, ok_status):
    """On the live server: every acknowledged sample is counted, the
    grid took every one by its upkeep and was never rebuilt, the panels
    and the read-back ran on the device, and the newest instances' live
    samples read back exactly. The limits are `after_window_limits`."""
    limits = run.wl["after_window_limits"]
    writes = sum(1 for r in records
                 if _is_write(st, r.i) and r.status in ok_status)
    acked = min(writes * st.per, st.ds.live_samples)
    panels = [r for r in records if not _is_write(st, r.i)]
    m1 = srv.metrics()
    ctx = {"m0": st.m_setup, "m1": m1}
    upkept = delta(ctx, "gtpu_promql_grid_upkeep_rows_total") or 0.0
    before_last = panel(st, max(r.i for r in panels))[0] if panels else 0
    run.number("samples_not_in_grid", abs(before_last - upkept),
               limits["samples_not_in_grid"])
    counted = int(srv.sql(f"select count(*) from {METRIC}")[0][0])
    run.number("rows_acked_not_counted",
               abs(counted - st.ds.rows - acked), 0)
    query, start, end = readback(np, st, acked)
    got = _matrix(srv.post(_QUERY, _form(query[0], start, end, st.step_ms),
                           _HEADERS))
    want = expected_readback(np, st, acked, query)
    cmp = compare_rows(np, got, want)
    ctx = {"m0": st.m_setup, "m1": srv.metrics()}
    hits = delta(ctx, _FAST, {"event": "hit"}) or 0.0
    events = delta(ctx, _FAST) or 0.0
    asked = len(panels) + 1
    run.number("queries_off_device",
               events - hits + max(0.0, asked - events),
               limits["queries_off_device"])
    run.number("promql_grid_rebuilds_in_window",
               delta(ctx, live.UPKEEP_FAMILY, {"outcome": _REBUILDS})
               or 0.0, limits["promql_grid_rebuilds_in_window"])
    run.notes["readback"] = {
        "writes_acked": writes, "samples_acked": acked, "counted": counted,
        "panels": len(panels), "upkept": upkept,
        "instances": len(query[1]), "values": len(want),
        "upkeep": {o: delta(ctx, live.UPKEEP_FAMILY, {"outcome": o})
                   for o in ["append", "grow"] + _REBUILDS}}
    run.number("readback_rows_missing", cmp["rows_missing"],
               limits["readback_rows_missing"])
    run.number("readback_values_differing", cmp["values_differing"],
               limits["readback_values_differing"])
