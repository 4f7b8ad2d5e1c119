"""`tsbs-panel-under-ingest` on `tsbs-cpu-4000-live`: the generator is a
pure function of (seed, i) and its cycle one body and five panels; what
a panel has to answer follows the bodies acknowledged before it; both
controls read above the limits; a CPU rehearsal is `correct` and prints
every new metric; and the timed path broken underneath (an upkeep that
stamps the version without applying the rows, an acknowledged row lost
before the count) comes out not `correct`; a program that exports no
upkeep counter is refused before a row is sent."""

import json
import os

import numpy as np
import pytest

from test_bench_rehearsal import QUERY_SCALE, _check_line, _names, _rehearse

from benchmark import control
from benchmark.lib.files import cell_files, module, reference

CELL = "tsbs-panel-under-ingest"
SCALE = {"hosts": 64, "hours": 2, "live_minutes": 60}
DEVICE_ONLY = {"upkeep_roofline", "upkeep_device_busy_ms_per_body",
               "device_idle_share.live", "hbm_bytes_in_use.live",
               "range_roofline.live", "device_busy_ms_per_query.live"}


@pytest.fixture(scope="module")
def state(tmp_path_factory):
    return str(tmp_path_factory.mktemp("bench_live_state"))


def _prepare(seed, budget=600, scale=SCALE, **params):
    _manifest, _cell, wl, cfg = cell_files(CELL)
    traffic = module("traffic", wl["generator"])
    ds = module("datagen", cfg["datagen"]).make(np, seed, scale)
    ds.reference = reference(cfg)
    return traffic, traffic.prepare(
        np, {**wl["params"], **params}, ds, seed, budget)


def test_cell_is_the_issue_s_traffic():
    _manifest, cell, wl, cfg = cell_files(CELL)
    # four chips for steadiness alone: the program uses one of them, and
    # the cell holds the whole host (PERF.md section 6, PR 35); the
    # harness holds a rehearsal to the four too, and tests/conftest.py
    # gives every child eight CPU devices
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "tsbs-cpu-4000-live", "panel-under-ingest", 4)
    assert "steadiness" in cell["why"]
    assert wl["params"] == {"batch_lines": 400, "panels_per_body": 5,
                            "span_minutes": 60, "bucket_s": 60}
    assert wl["workers"] == 1 and wl["ok_status"] == [200, 204]
    assert set(wl["limits"]) == {"rows_missing", "values_differing"}
    assert "grid_rebuilds_in_window" in wl["after_window_limits"]
    # whole cycles of warm-up
    assert (wl["warm_requests"] + wl["warm_rounds"]) % 6 == 0
    assert cfg["scale"] == {"hosts": 4000, "hours": 4, "live_minutes": 60}
    assert cfg["reference"] == "tsbs-cpu-4000" and cfg["reduced"] == ["hours"]
    assert "read_your_acknowledged_writes" in cfg["guarantees"]


def test_generator_is_a_pure_function_of_seed_and_index():
    traffic, a = _prepare(7)
    _t, b = _prepare(7)
    _t, c = _prepare(8)
    ra = [traffic.request(a, i) for i in range(a.n)]
    assert ra == [traffic.request(b, i) for i in range(b.n)]
    assert ra != [traffic.request(c, i) for i in range(c.n)]
    assert a.n == 600 and a.n % 6 == 0
    # the data is too: held and live are one seeded stream, cut in two
    assert np.array_equal(a.ds.stream, b.ds.stream)
    assert a.ds.rows == 64 * 720 and a.ds.live_cells == 360
    assert np.array_equal(a.ds.stream[:, :, :720], a.ds.values)


def test_a_cycle_is_one_body_and_five_panels():
    # four bodies a tick, so that a tick spans cycles as the cell's does
    traffic, st = _prepare(11, batch_lines=16)
    assert (st.batch, st.per_tick, st.cycle) == (16, 4, 6)
    names = st.ds.hostnames
    for c in range(st.n // 6):
        method, path, body, _h = traffic.request(st, 6 * c)
        assert (method, path) == ("POST", "/v1/influxdb/write?precision=ms")
        lines = body.decode().splitlines()
        tick, k = divmod(c, 4)
        ts = (st.held + tick) * 10_000
        assert len(lines) == 16
        assert all(ln.endswith(f" {ts}") and ln.count(",") == 19
                   for ln in lines)
        hosts = [ln.split(",")[1].split("=")[1] for ln in lines]
        assert hosts == names[16 * k:16 * (k + 1)]
        end = ts + 10_000       # the end of the newest tick sent
        for j in range(1, 6):
            method, path, form, _h = traffic.request(st, 6 * c + j)
            assert (method, path) == ("POST", "/v1/sql")
            q = traffic.sql(st, 6 * c + j)
            assert f"ts >= {end - 3_600_000} AND ts < {end} " in q
            assert "max(usage_user) RANGE '60s'" in q
            host = q.split("hostname IN ('")[1].split("'")[0]
            if j == 1:      # a host of the body just acknowledged
                assert host in hosts
    # the other four draw from every host
    drawn = {traffic.sql(st, i).split("IN ('")[1].split("'")[0]
             for i in range(st.n) if i % 6 > 1}
    assert len(drawn) > 48


def test_a_panel_answers_over_the_bodies_acknowledged_before_it():
    traffic, st = _prepare(13, batch_lines=16)
    ds = st.ds
    for i in (1, 2, 29, 31, 143, 599):
        c, host, lo, hi = traffic._panel(st, i)
        want = traffic.expected(np, st, i)
        # by hand: minute buckets over the window's cells that the host
        # has acknowledged rows in
        acked = st.held + sum(
            1 for b in range(c + 1)
            if host in traffic._body_hosts(st, b))
        by_hand = {}
        for cell in range(lo, min(hi, acked)):
            key = (cell // 6 * 60_000, ds.hostnames[host])
            v = float(ds.stream[0, host, cell])
            by_hand[key] = (max(v, by_hand.get(key, (v,))[0]),)
        assert want == by_hand
        assert traffic.expected(np, st, 6 * c) == {}
        assert traffic.parse(np, st, 6 * c, b"") == {}
    assert (hi - lo, hi % 6 != 0) == (360, True)    # partial end buckets


def test_both_controls_read_above_the_limits():
    doc = control.read(np, CELL, 2**31 + 5, 480, SCALE)
    assert doc["control_fails"] and doc["stated_precision_passes"]
    assert doc["bfloat16"]["values_differing"] > 0
    traffic, st = _prepare(2**31 + 5)
    stale = traffic.control_stale(np, st, 480)
    # a stale grid differs only for the host of a panel that is in the
    # newest body: in some of the 400 panels, not in all
    assert 0 < stale["panels_differing"] < 400
    assert stale["values_differing"] > 0 or stale["rows_missing"] > 0
    sound = traffic.control(np, st, "float32", 480)
    assert sound["panels_differing"] == 0


def test_rehearsal_is_correct_and_prints_every_new_metric(state):
    p, line = _rehearse(CELL, state, QUERY_SCALE, trace=1)
    assert p.returncode == 0, p.stderr[-3000:]
    _check_line(line, _names("per_layer", CELL))
    assert line["correct"] is True, line["compared"]
    assert set(line["metrics"]) == _names("per_layer", CELL) - DEVICE_ONLY
    m = {k: v["value"] for k, v in line["metrics"].items()}
    assert m["grid_rebuilds_in_window"] == 0.0
    assert m["grid_upkeep_rows_per_call"] == 64.0
    assert m["compiles_per_query.live"] == 0.0
    assert 0.8 < m["dispatches_per_query.live"] <= 1.0
    assert line["notes"]["in_window"]["compiles"] == 0
    rb = line["notes"]["readback"]
    assert rb["counted"] == 64 * 720 + rb["rows_acked"]
    assert rb["rows_acked"] == 64 * rb["bodies_acked"] > 0
    assert rb["rows_of_host"] == rb["bodies_acked"] and rb["groups"] == 192
    for name in ("queries_off_device", "grid_rebuilds_in_window",
                 "rows_acked_not_counted", "readback_rows_differing",
                 "rows_missing", "values_differing"):
        assert line["compared"][name]["value"] == 0, name
    # five panels a body, one body a cycle
    assert line["attempted"] == rb["bodies_acked"] * 6 - 18 - (
        -line["attempted"] % 6)
    p, line = _rehearse(CELL, state, QUERY_SCALE, trace=0)
    assert p.returncode == 0, p.stderr[-3000:]
    assert line["correct"] is True
    assert set(line["metrics"]) == _names("end_to_end", CELL) == {
        "query_p50_ms", "query_p95_ms", "queries_per_s", "setup_s"}
    assert not os.path.exists(os.path.join(state, "home-" + CELL))


STALE_GRID = '''
import subprocess
BROKEN = """
import runpy, sys
from greptimedb_tpu.query import device_range
# the upkeep stamps the entry with the table's version and applies
# nothing: the grid a panel reads lacks the body acknowledged before it
device_range._apply_append = lambda entry, batch: None
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

LOSE_A_ROW = """
def lose(run):
    # a row of an acknowledged body that is not there when it is counted
    run.srv.sql("delete from cpu where hostname = 'host_3' and "
                "ts = 7200000")
HOOKS = {"before_check": lose}
"""


def test_a_stale_grid_comes_out_not_correct(state):
    p, line = _rehearse(CELL, state, QUERY_SCALE, hooks_code=STALE_GRID)
    assert p.returncode == 0, p.stderr[-3000:]
    assert line["correct"] is False
    c = line["compared"]
    assert c["values_differing"]["value"] + c["rows_missing"]["value"] > 0
    assert c["requests_failed"]["value"] == 0


def test_a_lost_acknowledged_row_comes_out_not_correct(state):
    p, line = _rehearse(CELL, state, QUERY_SCALE, hooks_code=LOSE_A_ROW)
    assert p.returncode == 0, p.stderr[-3000:]
    assert line["correct"] is False
    assert line["compared"]["rows_acked_not_counted"]["value"] == 1


class _Scraped:
    """A server as far as the data module's first step sees it."""

    def __init__(self, families):
        self.families = families

    def metrics(self):
        return {(f, (("outcome", "append"),)): 0.0 for f in self.families}


@pytest.mark.parametrize("families,refused", [
    ((), True),
    (("gtpu_query_exec_path_total",), True),
    (("gtpu_grid_upkeep_total",), False),
])
def test_a_program_without_the_upkeep_counter_is_refused(
        families, refused, monkeypatch):
    """`grid_kept_up` is held by a counter: where the family is absent
    (the parent of the PR that brought the cell rebuilds after every
    body and would read 0) the run fails cleanly, before the load."""
    from benchmark.datagen import tsbs_cpu, tsbs_cpu_live
    from benchmark.lib.server import BenchFailure

    loaded = []
    monkeypatch.setattr(tsbs_cpu, "load",
                        lambda np, srv, ds, say: loaded.append(ds) or {})
    srv = _Scraped(families)
    if refused:
        with pytest.raises(BenchFailure, match="gtpu_grid_upkeep_total"):
            tsbs_cpu_live.load(np, srv, "ds", print)
        assert not loaded
    else:
        assert tsbs_cpu_live.load(np, srv, "ds", print) == {}
        assert loaded == ["ds"]


def test_the_upkeep_counter_reads_zero_from_start_up():
    """What the refusal above leans on: importing the range path alone
    registers every outcome at 0, so a fresh server's scrape has them."""
    from greptimedb_tpu.query import device_range  # noqa: F401
    from greptimedb_tpu.telemetry.metrics import global_registry

    text = global_registry.render()
    for outcome in ["append"] + module("traffic", "tsbs_live")._REBUILDS:
        assert f'gtpu_grid_upkeep_total{{outcome="{outcome}"}}' in text


def test_the_byte_model_counts_a_body_s_cells_in_every_plane():
    from benchmark.lib.bytes_model_upkeep import upkeep_bytes

    _manifest, _cell, wl, cfg = cell_files(CELL)
    traffic, st = _prepare(3, budget=60, scale=dict(cfg["scale"], hours=1,
                                                    live_minutes=1))
    shapes = traffic.shapes(st)
    assert shapes["batch_lines"] == 400 and shapes["grid_planes"] == 23
    assert upkeep_bytes(shapes) == 2 * 400 * 23 * 4 + 512 * 25 * 4


def test_new_readers_return_none_where_there_is_nothing_to_read():
    from benchmark.readers import trace_program_call_ms, trace_call_roofline

    spec = json.load(open(os.path.join(
        os.path.dirname(control.HERE), "benchmark", "metrics",
        "upkeep_roofline.json")))
    shapes = {"batch_lines": 400, "grid_planes": 23, "append_columns": 25,
              "append_bucket": 512}
    peaks = {"hbm_bytes_per_s": 819e9}
    for trace in (None, {}, {"programs": {"jit_program_rows": {
            "seconds": 1.0, "calls": 9}}}):
        ctx = {"trace": trace, "shapes": shapes, "peaks": peaks}
        assert trace_call_roofline.read(spec, ctx) is None
        assert trace_program_call_ms.read(spec, ctx) is None
    ctx = {"trace": {"programs": {"jit_grid_append": {
        "seconds": 0.002, "calls": 10}}}, "shapes": shapes, "peaks": peaks}
    assert trace_program_call_ms.read(dict(spec, scale=1000.0), ctx) == 0.2
    assert trace_call_roofline.read(spec, ctx) == pytest.approx(
        100.0 * (124_800 * 10 / 819e9) / 0.002)


def test_a_panel_s_roofline_counts_the_rows_program_s_own_calls():
    """The cell's requests are bodies and panels, so the capture's
    panels are the calls of the rows program it holds, and the bytes
    are the latency cell's: one field of one host over 360 cells."""
    from benchmark.lib.bytes_model import range_query_bytes
    from benchmark.readers import trace_call_roofline, trace_program_call_ms

    _manifest, _cell, wl, cfg = cell_files(CELL)
    traffic, st = _prepare(3, budget=60, scale=dict(cfg["scale"], hours=1,
                                                    live_minutes=1))
    shapes = traffic.shapes(st)
    assert range_query_bytes(shapes) == 360 * 5 + 60 * 5
    metrics = os.path.join(os.path.dirname(control.HERE), "benchmark",
                           "metrics")
    roof = json.load(open(os.path.join(metrics, "range_roofline.live.json")))
    busy = json.load(open(os.path.join(
        metrics, "device_busy_ms_per_query.live.json")))
    ctx = {"trace": {"programs": {
        "jit_program_rows": {"seconds": 0.0021, "calls": 80},
        "jit_grid_append": {"seconds": 0.0232, "calls": 16}}},
        "shapes": shapes, "peaks": {"hbm_bytes_per_s": 819e9}}
    assert trace_program_call_ms.read(busy, ctx) == pytest.approx(0.02625)
    assert trace_call_roofline.read(roof, ctx) == pytest.approx(
        100.0 * (2100 * 80 / 819e9) / 0.0021)
