"""Prometheus stamps each target's samples at that target's own offset
inside the scrape interval. The selector grid takes its resolution from
the series' own cadence and puts cell boundaries where a dashboard puts
its steps, so `histogram_quantile(phi, sum by (le) (rate(..[5m])))` is
served from the device for such data; the answers are held to the plain
reference of the benchmark's configuration `prom-100k-defbuckets`
(NumPy, float64). A table with one common phase keeps the grid it had,
and a series whose cadence is not regular refuses the grid: the host
engine answers, and equals the reference too."""

import json
import os
import sys
import urllib.parse
import urllib.request

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.datagen import prom_hist as dg  # noqa: E402
from benchmark.lib.files import load_json, reference  # noqa: E402
from greptimedb_tpu.instance import Standalone  # noqa: E402
from greptimedb_tpu.promql import fast as F  # noqa: E402
from greptimedb_tpu.promql.engine import PromEngine  # noqa: E402
from greptimedb_tpu.servers import prom_store  # noqa: E402
from test_tracing import _family, _render  # noqa: E402

REF = reference(load_json(ROOT, "benchmark", "configs",
                          "prom-100k-defbuckets.json"))
PHIS = [0.5, 0.9, 0.95, 0.99]
BASE = dg.BASE_MS
STEP = dg.INTERVAL_MS
WINDOW = 300_000


def counter(family: str, **labels) -> float:
    return _family(_render(), family, **labels) or 0.0


def load(inst, ds):
    """Scrape 0 by remote write (it makes the table), the rest by the
    columnar write path, as the benchmark's load does."""
    for body in dg.scrape_bodies(np, ds, 0, np.arange(ds.instances)):
        prom_store.remote_write(inst, body)
    table = inst.catalog.table("public", dg.METRIC)
    n, n_le, scrapes = ds.values.shape
    rows = n * n_le * (scrapes - 1)
    inst._write_columns(table, {
        "instance": np.repeat(np.asarray(ds.names, object),
                              n_le * (scrapes - 1)),
        "job": np.full(rows, dg.JOB, object),
        "le": np.tile(np.repeat(np.asarray(dg.LE, object), scrapes - 1), n),
        "ts": np.repeat(ds.ts[:, 1:], n_le, axis=0).reshape(-1),
        "greptime_value": ds.values[:, :, 1:].reshape(-1).astype(np.float64),
    }, {})
    return table


def want(ds, phi, start, end):
    steps = np.arange(start, end + 1, STEP, dtype=np.int64)
    with np.errstate(all="ignore"):
        rate, present = REF.extrapolated_rate(np, ds.ts, ds.values, steps,
                                              WINDOW)
        buckets, held = REF.sum_by_le(np, rate, present)
        got = REF.histogram_quantile(np, dg.BOUNDS, buckets, held, phi)
    return {int(steps[j]): v for j, v in got.items()}


def promql(phi):
    return (f"histogram_quantile({phi}, sum by (le) "
            f"(rate({dg.METRIC}[5m])))")


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """64 instances x 12 buckets, 20 minutes of scrapes at per-instance
    millisecond offsets (instance 0 at offset 0, one instance restarts),
    behind the HTTP server."""
    from greptimedb_tpu.servers.http import HttpServer

    F.invalidate_cache()
    inst = Standalone(str(tmp_path_factory.mktemp("stagger") / "data"),
                      warm_start=False)
    srv = HttpServer(inst, port=0).start()
    ds = dg.make(np, 2**31 + 29, {"instances": 64, "minutes": 20})
    load(inst, ds)
    try:
        yield inst, srv.port, ds
    finally:
        srv.stop()
        inst.close()
        F.invalidate_cache()


def query_range(port, phi, start, end):
    body = urllib.parse.urlencode({
        "query": promql(phi), "start": start // 1000, "end": end // 1000,
        "step": STEP // 1000}).encode()
    doc = json.loads(urllib.request.urlopen(urllib.request.Request(
        f"http://127.0.0.1:{port}/v1/prometheus/api/v1/query_range",
        data=body), timeout=120).read())
    assert doc["status"] == "success"
    (series,) = doc["data"]["result"]
    assert series["metric"] == {}
    return {round(t * 1000): float(v) for t, v in series["values"]}


def test_the_data_is_what_the_test_says(served):
    _inst, _port, ds = served
    assert ds.offsets[0] == 0 and len(set(ds.offsets.tolist())) == 64
    assert ds.offsets[1:].min() > 0 and ds.offsets.max() < STEP
    # one instance restarts: its counters fall, all twelve at one scrape
    falls = (np.diff(ds.values, axis=2) < 0).any(axis=1)
    assert falls.any(axis=1).sum() == 1 and falls.sum() == 1


@pytest.mark.parametrize("phi", PHIS)
def test_served_path_equals_the_reference(served, phi):
    _inst, port, ds = served
    # the first window is (BASE, BASE + 5m]: instance 0's first sample
    # sits exactly on its open end and is not in it
    start, end = BASE + WINDOW, BASE + 20 * 60_000
    hit0 = counter("greptime_promql_fast_path_total", event="hit")
    fb0 = counter("greptime_promql_fast_path_total", event="fallback")
    got = query_range(port, phi, start, end)
    expect = want(ds, phi, start, end)
    assert sorted(got) == sorted(expect) and len(got) == 61
    for t, v in expect.items():
        assert got[t] == pytest.approx(v, rel=2e-4), (phi, t)
    assert counter("greptime_promql_fast_path_total",
                   event="hit") == hit0 + 1
    assert counter("greptime_promql_fast_path_total",
                   event="fallback") == fb0


def test_open_end_of_the_window_is_exact(served):
    """The window (BASE, BASE + 5m] and the one 15 s later differ by
    instance 0's first sample: had the grid put it inside the first
    window, the first step would read as the reference does with a
    closed window."""
    _inst, port, ds = served
    got = query_range(port, 0.5, BASE + WINDOW, BASE + WINDOW + STEP)
    expect = want(ds, 0.5, BASE + WINDOW, BASE + WINDOW + STEP)
    closed = ds.ts.copy()
    closed[0, 0] += 1       # the same data with that sample inside
    with np.errstate(all="ignore"):
        rate, present = REF.extrapolated_rate(
            np, closed, ds.values, np.asarray([BASE + WINDOW]), WINDOW)
        wrong = REF.histogram_quantile(
            np, dg.BOUNDS, *REF.sum_by_le(np, rate, present), 0.5)[0]
    first = BASE + WINDOW
    assert got[first] == pytest.approx(expect[first], rel=2e-5)
    assert abs(wrong - expect[first]) > 20 * abs(got[first] - expect[first])


def test_staggered_phases_get_a_grid_at_the_scrape_interval(served):
    inst, _port, ds = served
    table = inst.catalog.table("public", dg.METRIC)
    entry = F._CACHE.get_entry(table, "greptime_value")
    assert not entry.refused
    assert entry.spec.res == STEP
    # boundaries on multiples of the interval since the epoch: a step
    # of a dashboard lands on one
    assert entry.spec.t0 % STEP == 0 and entry.spec.t0 == BASE - STEP
    assert entry.spec.num_cells == ds.scrapes + 2
    assert entry.num_series == 64 * 12
    # one sample a cell, each with its exact tick
    has = np.asarray(entry.has)[:entry.num_series]
    assert has.sum() == ds.rows and has.sum(axis=1).max() == ds.scrapes
    ticks = np.asarray(entry.tsg)[:entry.num_series][has]
    assert sorted(set((ticks % STEP).tolist())) == sorted(
        set(ds.offsets.tolist()))


def one_phase_table(inst, *, t0, n=41, step_ms=15_000, hosts=6, skip=()):
    inst.sql("CREATE TABLE req_total (host STRING, greptime_value DOUBLE, "
             "ts TIMESTAMP TIME INDEX, PRIMARY KEY (host))")
    table = inst.catalog.table("public", "req_total")
    ts = t0 + np.arange(n) * step_ms
    keep = np.ones(n, bool)
    keep[list(skip)] = False
    for h in range(hosts):
        m = keep if h else np.ones(n, bool)     # host 0 misses nothing
        table.write({"host": np.full(int(m.sum()), f"h{h}", object)}, ts[m],
                    {"greptime_value": np.cumsum(np.ones(int(m.sum())))})
    return table, ts


@pytest.fixture()
def inst(tmp_path):
    F.invalidate_cache()
    s = Standalone(str(tmp_path / "data"), warm_start=False)
    yield s
    s.close()
    F.invalidate_cache()


@pytest.mark.parametrize("t0,step_ms,skip", [
    (1_700_000_000_000, 15_000, ()),        # a phase off the epoch's grid
    (1_700_000_010_000, 15_000, ()),        # a phase on it
    (1_700_000_007_000, 10_000, (3, 4, 17)),    # missed scrapes
    (7_000, 1_000, ()),
], ids=["off-epoch", "on-epoch", "missed-scrapes", "one-second"])
def test_one_common_phase_keeps_the_grid_it_had(inst, t0, step_ms, skip):
    """Before this PR: res = gcd of the differences of all timestamps,
    t0 = first sample - res, cells = ceil((last - t0) / res) + 1."""
    table, ts = one_phase_table(inst, t0=t0, step_ms=step_ms, skip=skip)
    built0 = counter("gtpu_promql_grid_entries_total", outcome="built")
    entry = F._CACHE.get_entry(table, "greptime_value")
    res = int(np.gcd.reduce(np.diff(ts)))
    assert (entry.spec.res, entry.spec.t0, entry.spec.num_cells) == (
        res, int(ts[0]) - res, int(-(-(int(ts[-1]) - int(ts[0]) + res)
                                     // res)) + 1)
    assert counter("gtpu_promql_grid_entries_total",
                   outcome="built") == built0 + 1
    # a query that starts on a sample time stays aligned and is served
    hit0 = counter("greptime_promql_fast_path_total", event="hit")
    PromEngine(inst).query_range("sum(rate(req_total[1m]))",
                                 int(ts[8]), int(ts[-1]), 4 * step_ms)
    assert counter("greptime_promql_fast_path_total",
                   event="hit") == hit0 + 1


def whole_second_dataset(seed, *, shift=None):
    """16 instances, 15 minutes, offsets in whole seconds (the host
    engine grids at the gcd of all timestamps: 1 s is what it can hold);
    `shift` moves one sample of instance 3 by that many ms."""
    ds = dg.make(np, seed, {"instances": 16, "minutes": 15})
    ds.offsets = (ds.offsets // 1000) * 1000
    ds.ts = (BASE + ds.offsets[:, None]
             + np.arange(ds.scrapes, dtype=np.int64)[None, :] * STEP)
    if shift:
        ds.ts[3, 10] += shift
    return ds


def engine_answer(inst, phi, start, end):
    val, ev = PromEngine(inst).query_range(promql(phi), start, end, STEP)
    assert len(val.labels) == 1
    return {int(t): float(v) for t, v, p in
            zip(ev.step_ts, val.values[0], val.present[0]) if p}


def test_an_irregular_series_refuses_the_grid_and_the_host_answers(inst):
    # instance 3's eleventh sample comes 9 s late: with the twelfth it
    # falls into one 15 s cell wherever the boundaries are
    ds = whole_second_dataset(5, shift=9_000)
    table = load(inst, ds)
    refused0 = counter("gtpu_promql_grid_entries_total",
                       outcome="refused_irregular")
    fb0 = counter("greptime_promql_fast_path_total", event="fallback")
    hit0 = counter("greptime_promql_fast_path_total", event="hit")
    start, end = BASE + WINDOW, BASE + 15 * 60_000
    got = engine_answer(inst, 0.9, start, end)
    expect = want(ds, 0.9, start, end)
    assert sorted(got) == sorted(expect) and len(got) == 41
    for t, v in expect.items():
        assert got[t] == pytest.approx(v, rel=2e-4), t
    assert F._CACHE.get_entry(table, "greptime_value").refused == "irregular"
    # histogram_quantile falls back, then its inner sum by (le) does
    fb1 = counter("greptime_promql_fast_path_total", event="fallback")
    assert fb1 > fb0
    # refused once a data version: the next query scans nothing again
    engine_answer(inst, 0.5, start, end)
    assert counter("gtpu_promql_grid_entries_total",
                   outcome="refused_irregular") == refused0 + 1
    assert counter("greptime_promql_fast_path_total",
                   event="fallback") == 2 * fb1 - fb0
    assert counter("greptime_promql_fast_path_total", event="hit") == hit0


def test_a_span_past_the_budget_refuses_the_grid(inst, monkeypatch):
    table, ts = one_phase_table(inst, t0=7_000, step_ms=1_000, n=64)
    # 9 bytes a cell, 8 series padded: 64 cells do not fit half of 1 KB
    monkeypatch.setenv("GREPTIMEDB_TPU_PROMQL_CACHE_BYTES", "1024")
    refused0 = counter("gtpu_promql_grid_entries_total",
                       outcome="refused_budget")
    assert F._CACHE.get_entry(table, "greptime_value").refused == "budget"
    assert counter("gtpu_promql_grid_entries_total",
                   outcome="refused_budget") == refused0 + 1
    val, _ev = PromEngine(inst).query_range(
        "sum(rate(req_total[10s]))", int(ts[20]), int(ts[-1]), 5_000)
    assert val.present.all()        # the host engine answered


@pytest.mark.parametrize("query,instant", [
    (f"histogram_quantile(0.9, sum by (le) (rate({dg.METRIC}[5m] "
     "offset 1m)))", False),
    (f"sum by (le) ({dg.METRIC})", False),
    (f"sum by (le) (rate({dg.METRIC}[5m]))", True),
], ids=["offset", "instant-selector", "instant-query"])
def test_offset_and_instant_paths_on_staggered_data(inst, query, instant):
    """Fast path against the generic engine on staggered data whose
    offsets the generic engine can grid (whole seconds)."""
    ds = whole_second_dataset(9)
    load(inst, ds)
    start, end = BASE + 7 * 60_000, BASE + 15 * 60_000
    hit0 = counter("greptime_promql_fast_path_total", event="hit")

    def ask():
        eng = PromEngine(inst)
        if instant:
            return eng.query_instant(query, end)[0]
        return eng.query_range(query, start, end, STEP)[0]

    fast = ask()
    assert counter("greptime_promql_fast_path_total",
                   event="hit") == hit0 + 1
    real = (F.try_fast, F.try_fast_histogram)
    F.try_fast = F.try_fast_histogram = lambda *a, **k: None
    try:
        slow = ask()
    finally:
        F.try_fast, F.try_fast_histogram = real

    def as_map(v):
        return {tuple(sorted(lab.items())): (v.values[i], v.present[i])
                for i, lab in enumerate(v.labels) if v.present[i].any()}

    fm, sm = as_map(fast), as_map(slow)
    assert set(fm) == set(sm) and fm
    for key, (fv, fp) in fm.items():
        sv, sp = sm[key]
        np.testing.assert_array_equal(fp, sp)
        np.testing.assert_allclose(np.where(fp, fv, 0), np.where(sp, sv, 0),
                                   rtol=2e-4)
