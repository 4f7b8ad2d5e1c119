"""`prom-100k-quantile-under-remote-write` on `prom-100k-defbuckets-live`:
the cell is ISSUE 39's traffic; the generator is a pure function of
(seed, i), its cycle four remote-write requests and one quantile panel,
and its bodies are byte for byte the accepted encoder's; what a panel
has to answer follows the requests acknowledged before it; the bfloat16
control fails the panels' limit and a grid one request behind fails the
exact read-back; a CPU rehearsal is `correct` and prints every new
metric; the timed path broken underneath (an upkeep that leaves the
newest request out, an acknowledged sample lost before the count) comes
out not `correct`; a program that exports no upkeep counter is refused
before a sample is sent."""

import json
import os
import urllib.parse

import numpy as np
import pytest

from test_bench_rehearsal import _check_line, _names, _rehearse

from benchmark import control
from benchmark.datagen import prom_hist as dg
from benchmark.lib.files import cell_files, module, reference

CELL = "prom-100k-quantile-under-remote-write"
SCALE = {"instances": 16, "minutes": 60, "held_minutes": 40}
REHEARSAL = ["--scale", "instances=64", "--scale", "minutes=240",
             "--scale", "held_minutes=40"]
DEVICE_ONLY = {"hist_upkeep_roofline", "hist_upkeep_device_busy_ms_per_panel"}
NEW = {"promql_upkeep_ms_per_panel", "promql_upkeep_samples_per_call",
       "promql_grid_rebuilds_in_window", "hist_upkeep_dispatch_ms_per_call",
       "hist_upkeep_device_busy_ms_per_panel", "hist_upkeep_roofline",
       "prom_write_decode_ms_per_request", "prom_write_apply_ms_per_request"}


@pytest.fixture(scope="module")
def state(tmp_path_factory):
    return str(tmp_path_factory.mktemp("bench_prom_live_state"))


def _prepare(seed, budget=100, scale=SCALE, **params):
    _manifest, _cell, wl, cfg = cell_files(CELL)
    traffic = module("traffic", wl["generator"])
    ds = module("datagen", cfg["datagen"]).make(np, seed, scale)
    ds.reference = reference(cfg)
    return traffic, traffic.prepare(
        np, {**wl["params"], **params}, ds, seed, budget)


def _decode(body):
    from greptimedb_tpu.servers import prom_store

    return prom_store.parse_write_request(prom_store.snappy.decompress(body))


def test_cell_is_the_issue_s_traffic():
    manifest, cell, wl, cfg = cell_files(CELL)
    assert (cell["config"], cell["chips"]) == ("prom-100k-defbuckets-live", 1)
    assert wl["generator"] == "prom_live"
    assert wl["params"] == {"phis": [0.5, 0.9, 0.95, 0.99], "range_s": 1800,
                            "step_s": 15, "window_s": 300,
                            "writes_per_panel": 4,
                            "samples_per_request": 2000}
    assert wl["workers"] == 1 and wl["ok_status"] == [200, 204]
    assert wl["limits"] == {"rows_missing": 0, "worst_rel_err": 1e-3}
    assert wl["after_window_limits"] == {
        "queries_off_device": 0, "promql_grid_rebuilds_in_window": 0,
        "samples_not_in_grid": 0, "readback_rows_missing": 0,
        "readback_values_differing": 0}
    # three whole cycles of warm-up
    assert wl["warm_requests"] + wl["warm_rounds"] == 15
    # about 3,000 bodies, 750 cycles, rendered
    n = int(wl["max_requests_per_s"] * manifest["run_seconds"]) + 64
    assert 740 <= n // 5 <= 750
    assert cfg["scale"] == {"instances": 8334, "minutes": 60,
                            "held_minutes": 45}
    assert cfg["reference"] == "prom-100k-defbuckets"
    assert cfg["reduced"] == ["minutes"]
    src = json.load(open(os.path.join(control.HERE, "configs",
                                      "prom-100k-defbuckets.json")))
    for k in ("schema", "precision", "table", "grid_warm_sql"):
        assert cfg[k] == src[k], k
    assert set(src["guarantees"]) < set(cfg["guarantees"])
    assert {"read_your_acknowledged_writes", "grid_kept_up"} <= set(
        cfg["guarantees"])
    assert {"writes_per_panel", "closed_loop", "panel_end", "bodies"} <= set(
        cfg["assumed"])
    assert "302.0 MB" in cfg["device_bytes"]
    for m in manifest["end_to_end"]:
        if m["name"] in ("query_p50_ms", "query_p95_ms", "queries_per_s"):
            assert m["workloads"][-1] == CELL
    assert _names("per_layer", CELL) >= NEW


def test_generator_is_a_pure_function_of_seed_and_index():
    traffic, a = _prepare(7, samples_per_request=50)
    _t, b = _prepare(7, samples_per_request=50)
    _t, c = _prepare(8, samples_per_request=50)
    ra = [traffic.request(a, i) for i in range(a.n)]
    assert ra == [traffic.request(b, i) for i in range(b.n)]
    assert ra != [traffic.request(c, i) for i in range(c.n)]
    assert a.n == 100 and a.n % 5 == 0
    # the data is too: held and live are one seeded stream, cut in two
    assert np.array_equal(a.ds.full.values, b.ds.full.values)
    assert a.ds.scrapes == 161 and a.ds.full.scrapes == 241
    assert np.array_equal(a.ds.full.values[:, :, :161], a.ds.values)
    assert a.ds.rows == 16 * 12 * 161
    assert a.ds.live_samples == 16 * 12 * 80


def test_a_body_is_byte_for_byte_the_accepted_encoder_s():
    traffic, st = _prepare(9, scale=dict(SCALE, instances=300))
    ds = st.ds
    heads = dg.series_heads(ds.full)
    for r in (0, 1, 7, 19):
        series, values, ts = traffic.live._samples(np, ds, r, 2000)
        want = dg.snappy_block(dg.write_request(
            heads, series.tolist(), values.tolist(), ts.tolist()))
        assert traffic.live.render(np, ds, r, 2000) == want


def test_a_cycle_is_four_writes_and_one_panel():
    traffic, st = _prepare(11, per=50, budget=200,
                           samples_per_request=50)
    ds = st.ds
    seen_ts = []
    for c in range(st.n // 5):
        for k in range(4):
            method, path, body, headers = traffic.request(st, 5 * c + k)
            assert (method, path) == ("POST", "/v1/prometheus/write")
            assert headers["Content-Encoding"] == "snappy"
            series = _decode(body)
            assert len(series) == 50       # a sample a series
            for labels, samples in series:
                assert list(labels) == sorted(labels)
                assert labels["__name__"] == dg.METRIC
                (v, t), = samples
                seen_ts.append(t)
        method, path, form, _h = traffic.request(st, 5 * c + 4)
        assert path == "/v1/prometheus/api/v1/query_range"
        q = urllib.parse.parse_qs(form.decode())
        acked, phi, end = traffic.panel(st, 5 * c + 4)
        assert acked == 200 * (c + 1)
        assert phi == [0.5, 0.9, 0.95, 0.99][c % 4]
        assert q["query"] == [f"histogram_quantile({phi!r}, sum by (le) "
                              f"(rate({dg.METRIC}[5m])))"]
        newest = max(seen_ts)
        assert end % 15_000 == 0 and newest <= end < newest + 15_000
        assert (int(q["end"][0]), int(q["start"][0]), q["step"]) == (
            end // 1000, (end - 1_800_000) // 1000, ["15"])
    # sample time order, a scrape's twelve `le` together
    assert seen_ts == sorted(seen_ts)
    assert len(seen_ts) == 200 * (st.n // 5)


def _by_hand(traffic, st, acked, end, phi):
    ref = st.ds.reference
    ts, values = traffic.live.acked_arrays(np, st.ds, acked)
    steps = np.arange(end - st.range_ms, end + 1, 15_000, dtype=np.int64)
    with np.errstate(all="ignore"):
        rate, present = ref.extrapolated_rate(np, ts, values, steps, 300_000)
        shape = (-1, 12, len(steps))
        got = ref.histogram_quantile(
            np, dg.BOUNDS, *ref.sum_by_le(np, rate.reshape(shape),
                                          present.reshape(shape)), phi)
    return {(int(steps[j]), ()): (v,) for j, v in got.items()}


def test_a_panel_answers_over_the_requests_acknowledged_before_it():
    traffic, st = _prepare(13, budget=400, samples_per_request=50)
    for i in (4, 9, 14, 104, 249, st.n - 1):
        acked, phi, end = traffic.panel(st, i)
        want = traffic.expected(np, st, i)
        hand = _by_hand(traffic, st, acked, end, phi)
        assert sorted(want) == sorted(hand) and len(want) == 121
        for k, v in hand.items():
            assert want[k][0] == pytest.approx(v[0], rel=1e-12, abs=1e-15)
        # one request behind: the reference over one request fewer
        behind = traffic.expected(np, st, i, requests_behind=1)
        hand = _by_hand(traffic, st, acked - 50, end, phi)
        for k, v in hand.items():
            assert behind[k][0] == pytest.approx(v[0], rel=1e-12, abs=1e-15)
        assert traffic.expected(np, st, i - 1) == {}
        assert traffic.parse(np, st, i - 1, b"") == {}


def test_both_controls_read_above_the_limits():
    scale = {"instances": 64, "minutes": 60, "held_minutes": 40}
    doc = control.read(np, CELL, 2**31 + 3905, 100, scale)
    assert doc["control_fails"] and doc["stated_precision_passes"]
    assert doc["bfloat16"]["worst_rel_err"] > 100 * 1e-3
    traffic, st = _prepare(2**31 + 3905, scale=scale)
    stale = traffic.control_stale(np, st, 100)
    # the exact read-back fails; the panels alone cannot be trusted to
    assert stale["readback_rows_missing"] > 0
    assert stale["rows_missing"] == 0
    sound = traffic.control(np, st, "float32", 100)
    assert sound["worst_rel_err"] < 1e-4


def test_rehearsal_is_correct_and_prints_every_new_metric(state):
    p, line = _rehearse(CELL, state, REHEARSAL, seconds=3, trace=1)
    assert p.returncode == 0, p.stderr[-3000:]
    _check_line(line, _names("per_layer", CELL))
    assert line["correct"] is True, line["compared"]
    assert set(line["metrics"]) == _names("per_layer", CELL) - DEVICE_ONLY
    m = {k: v["value"] for k, v in line["metrics"].items()}
    assert m["promql_grid_rebuilds_in_window"] == 0.0
    assert m["promql_upkeep_samples_per_call"] == 8000.0
    assert m["hist_readback_started_share"] == 100.0
    for k in ("promql_upkeep_ms_per_panel", "hist_upkeep_dispatch_ms_per_call",
              "prom_write_decode_ms_per_request",
              "prom_write_apply_ms_per_request"):
        assert m[k] > 0, k
    rb = line["notes"]["readback"]
    assert rb["counted"] == 64 * 12 * 161 + rb["samples_acked"]
    assert rb["samples_acked"] == 2000 * rb["writes_acked"] > 0
    assert rb["upkept"] == 8000 * rb["panels"]
    assert rb["values"] > 0 and rb["instances"] > 0
    for name in ("queries_off_device", "promql_grid_rebuilds_in_window",
                 "rows_acked_not_counted", "samples_not_in_grid",
                 "readback_rows_missing", "readback_values_differing",
                 "rows_missing"):
        assert line["compared"][name]["value"] == 0, name
    p, line = _rehearse(CELL, state, REHEARSAL, trace=0)
    assert p.returncode == 0, p.stderr[-3000:]
    assert line["correct"] is True
    assert set(line["metrics"]) == _names("end_to_end", CELL) == {
        "query_p50_ms", "query_p95_ms", "queries_per_s", "setup_s"}
    assert not os.path.exists(os.path.join(state, "home-" + CELL))


STALE_GRID = '''
import subprocess
BROKEN = """
import runpy, sys
from greptimedb_tpu.promql import fast
from greptimedb_tpu.storage.memtable import _slice_rows
_batch = fast._append_batch
def one_behind(entry, rows):
    # the upkeep stamps the entry with the table's version and leaves
    # the newest request's 2,000 samples out of the grid
    keep = rows.seq < rows.seq.max() - 1999
    return _batch(entry, _slice_rows(rows, keep) if keep.any() else rows)
fast._append_batch = one_behind
sys.argv = ["greptimedb_tpu.cli"] + sys.argv[1:]
runpy.run_module("greptimedb_tpu.cli", run_name="__main__")
"""
_popen = subprocess.Popen
class Popen(_popen):
    def __init__(self, args, **kw):
        if list(args[1:3]) == ["-m", "greptimedb_tpu.cli"]:
            args = [args[0], "-c", BROKEN] + list(args[3:])
        super().__init__(args, **kw)
subprocess.Popen = Popen
HOOKS = {}
'''

LOSE_A_SAMPLE = """
def lose(run):
    # a sample of an acknowledged request that is not there when it is
    # counted
    table = "http_request_duration_seconds_bucket"
    ts = next(r[0] for r in run.srv.sql(f"DESC TABLE {table}")
              if r[-1] == "TIMESTAMP")
    run.srv.sql(f"delete from {table} where instance = '10.0.0.3:8080' "
                f"and le = '+Inf' and {ts} = "
                + str(int(run.traffic_state.ds.full.ts[3, 170])))
HOOKS = {"before_check": lose}
"""


def test_a_grid_one_request_behind_comes_out_not_correct(state):
    p, line = _rehearse(CELL, state, REHEARSAL, hooks_code=STALE_GRID)
    assert p.returncode == 0, p.stderr[-3000:]
    assert line["correct"] is False
    c = line["compared"]
    assert (c["readback_rows_missing"]["value"]
            + c["readback_values_differing"]["value"]) > 0
    assert c["requests_failed"]["value"] == 0
    assert c["promql_grid_rebuilds_in_window"]["value"] == 0


def test_a_lost_acknowledged_sample_comes_out_not_correct(state):
    hooks = LOSE_A_SAMPLE.replace(
        "run.traffic_state.ds.full.ts[3, 170]",
        f"{_live_ts(3, 170)}")
    p, line = _rehearse(CELL, state, REHEARSAL, hooks_code=hooks)
    assert p.returncode == 0, p.stderr[-3000:]
    assert line["correct"] is False
    assert line["compared"]["rows_acked_not_counted"]["value"] == 1


def _live_ts(i, k, seed=2**31 + 77):
    ds = module("datagen", "prom_hist_live").make(
        np, seed, {"instances": 64, "minutes": 240, "held_minutes": 40})
    return int(ds.full.ts[i, k])


class _Scraped:
    """A server as far as the data module's first step sees it."""

    def __init__(self, families):
        self.families = families

    def metrics(self):
        return {(f, (("outcome", "append"),)): 0.0 for f in self.families}


@pytest.mark.parametrize("families,refused", [
    ((), True),
    (("gtpu_grid_upkeep_total",), True),
    (("gtpu_promql_grid_upkeep_total",), False),
])
def test_a_program_without_the_upkeep_counter_is_refused(
        families, refused, monkeypatch):
    """`grid_kept_up` is held by a counter: where the family is absent
    (the parent of the PR that brought the cell rebuilds for every
    panel and would read 0) the run fails cleanly, before the load."""
    from benchmark.datagen import prom_hist, prom_hist_live
    from benchmark.lib.server import BenchFailure

    loaded = []
    monkeypatch.setattr(prom_hist, "load",
                        lambda np, srv, ds, say: loaded.append(ds) or {})
    srv = _Scraped(families)
    if refused:
        with pytest.raises(BenchFailure,
                           match="gtpu_promql_grid_upkeep_total"):
            prom_hist_live.load(np, srv, "ds", print)
        assert not loaded
    else:
        assert prom_hist_live.load(np, srv, "ds", print) == {}
        assert loaded == ["ds"]


def test_the_byte_model_counts_a_cycle_s_samples():
    from benchmark.lib.bytes_model_hist_upkeep import hist_upkeep_bytes

    traffic, st = _prepare(3)
    shapes = traffic.shapes(st)
    assert shapes == {"upkeep_samples": 8000, "append_bucket": 8192}
    assert hist_upkeep_bytes(shapes) == 2 * 8000 * 9 + 8192 * 16


def test_new_readers_return_none_where_there_is_nothing_to_read():
    from benchmark.readers import trace_call_roofline, trace_program_call_ms

    metrics = os.path.join(control.HERE, "metrics")
    roof = json.load(open(os.path.join(metrics, "hist_upkeep_roofline.json")))
    busy = json.load(open(os.path.join(
        metrics, "hist_upkeep_device_busy_ms_per_panel.json")))
    shapes = {"upkeep_samples": 8000, "append_bucket": 8192}
    peaks = {"hbm_bytes_per_s": 819e9}
    for trace in (None, {}, {"programs": {"jit_grid_append": {
            "seconds": 1.0, "calls": 9}}}):
        ctx = {"trace": trace, "shapes": shapes, "peaks": peaks}
        assert trace_call_roofline.read(roof, ctx) is None
        assert trace_program_call_ms.read(busy, ctx) is None
    ctx = {"trace": {"programs": {"jit_selector_grid_append": {
        "seconds": 0.004, "calls": 8}}}, "shapes": shapes, "peaks": peaks}
    assert trace_program_call_ms.read(busy, ctx) == pytest.approx(0.5)
    assert trace_call_roofline.read(roof, ctx) == pytest.approx(
        100.0 * (275_072 * 8 / 819e9) / 0.004)
    # a parent without the families: each counter metric reads nothing
    for name in NEW - DEVICE_ONLY:
        spec = json.load(open(os.path.join(metrics, name + ".json")))
        reader = module("readers", spec["reader"])
        assert reader.read(spec, {"m0": {}, "m1": {}, "client": {}}) is None


OVERWRITE_BEFORE_THE_WINDOW = """
def scrape_again(run):
    # the last scrape of two targets sent again once the cell is warm:
    # the same samples, which the selector grid's upkeep cannot take (an
    # overwrite), so it drops the grid and the window's first query
    # builds it again: a query the device did not answer from a held
    # grid. (A new scrape is appended since PR 39, so the hook of
    # test_bench_prom.py's test_a_grid_build_inside_the_window_comes_
    # out_not_correct, which sends one, no longer forces a build.)
    import numpy as np
    from benchmark.datagen import prom_hist as dg
    ds = dg.make(np, run.args.seed, run.scale)
    for body in dg.scrape_bodies(np, ds, ds.scrapes - 1, np.arange(2)):
        run.srv.post("/v1/prometheus/write", body, dg._RW_HEADERS)
HOOKS = {"before_window": scrape_again}
"""


def test_a_grid_build_inside_the_promql_window_comes_out_not_correct(
        state):
    """The accepted PromQL cell's guarantee, held through the upkeep:
    what the upkeep cannot take rebuilds the grid inside the window,
    and the run says so."""
    p, line = _rehearse("prom-100k-histogram-quantile", state,
                        ["--scale", "instances=64", "--scale", "minutes=40"],
                        seconds=3, hooks_code=OVERWRITE_BEFORE_THE_WINDOW)
    assert p.returncode == 0, p.stderr[-3000:]
    assert line["correct"] is False
    assert line["compared"]["queries_off_device"]["value"] >= 1
    assert line["compared"]["requests_failed"]["value"] == 0
