"""Device program profiler (telemetry/device_programs.py, ISSUE 14):
registry folding across every device_call site, lazy XLA cost/roofline
analysis, 3-surface agreement (information_schema ==
/debug/prof/device?format=json == gtpu_device_program_*) across ADMIN
reset, mesh twins not cross-served, on-demand trace capture, and the
statement-statistics program link."""

import functools
import json
import urllib.request

import numpy as np
import pytest

from greptimedb_tpu.instance import Standalone
from greptimedb_tpu.servers.http import HttpServer
from greptimedb_tpu.telemetry import device_programs as DP
from greptimedb_tpu.telemetry.metrics import global_registry


@pytest.fixture()
def registry():
    """A clean process-wide registry with the default config; restores
    whatever configuration the surrounding suite had."""
    old_cfg = DP.global_programs.config
    DP.global_programs.config = DP.ProfilingConfig()
    DP.global_programs.reset()
    yield DP.global_programs
    DP.global_programs.config = old_cfg
    DP.global_programs.reset()


@pytest.fixture()
def no_sessions():
    """Disable persistent query sessions so every warm poll actually
    DISPATCHES a program (a session hit deliberately does not count as
    a registry call)."""
    from greptimedb_tpu.query import sessions

    old = sessions.global_sessions.enabled
    sessions.global_sessions.enabled = False
    yield
    sessions.global_sessions.enabled = old


@pytest.fixture()
def inst(tmp_path, registry, no_sessions):
    s = Standalone(str(tmp_path / "data"), prefer_device=True,
                   warm_start=False)
    yield s
    s.close()


@pytest.fixture()
def server(inst):
    srv = HttpServer(inst, port=0).start()
    yield srv
    srv.stop()


def _get(srv, path):
    url = f"http://127.0.0.1:{srv.port}{path}"
    with urllib.request.urlopen(url, timeout=120) as r:
        return r.status, r.read().decode()


def _seed(inst, name="cpu", hosts=8, cells=360):
    inst.execute_sql(
        f"create table {name} (ts timestamp time index, "
        "host string primary key, v double)"
    )
    t = inst.catalog.table("public", name)
    rng = np.random.default_rng(3)
    ts = np.tile(np.arange(cells, dtype=np.int64) * 10_000, hosts)
    hs = np.repeat(
        np.asarray([f"h{i}" for i in range(hosts)], object), cells
    )
    t.write({"host": hs}, ts, {"v": rng.random(len(ts))}, skip_wal=True)
    t.flush()
    return t


RANGE_Q = ("SELECT ts, host, avg(v) RANGE '1h' FROM cpu "
           "ALIGN '1h' BY (host)")


# the same panel over one host: the tag index matches one series, the
# rows program runs (query/device_range.py), under its own registry key
ROWS_Q = RANGE_Q.replace("FROM cpu", "FROM cpu WHERE host = 'h1'")
RANGE_QS = pytest.mark.parametrize(
    "q,tag", [(RANGE_Q, "'single'"), (ROWS_Q, "'rows'")],
    ids=["plane", "rows"])


def _rows_by_site(registry, *, analyze=False):
    out = {}
    for d in registry.snapshot(analyze=analyze):
        out.setdefault(d["site"], []).append(d)
    return out


# ---------------------------------------------------------------------------
# registry folding across the device call sites
# ---------------------------------------------------------------------------

@RANGE_QS
def test_range_site_folds_one_row_with_calls_accumulating(inst, registry,
                                                          q, tag):
    _seed(inst)
    for _ in range(4):
        inst.sql(q)
    assert inst.query_engine.last_exec_path == "device"
    sites = _rows_by_site(registry)
    # ONE row per compiled program, calls accumulating across polls
    assert len(sites["range"]) == 1
    row = sites["range"][0]
    assert row["calls"] == 4
    # the key says which range program ran
    assert tag in row["key"]
    assert row["compile_ms"] > 0          # first call = compile
    assert row["execute_p50_ms"] > 0      # 3 steady-state samples
    assert row["readback_bytes"] > 0
    # a range query is one call of one program: no other site ran
    assert set(sites) == {"range"}


def test_groupby_and_merge_and_promql_sites_fold(inst, registry):
    _seed(inst)
    for _ in range(2):
        inst.sql("SELECT host, avg(v), max(v) FROM cpu GROUP BY host")
    sites = _rows_by_site(registry)
    assert len(sites["groupby"]) == 1
    assert sites["groupby"][0]["calls"] == 2

    # device-accelerated compaction merge registers too
    from greptimedb_tpu.storage.device_merge import merge_rows
    from greptimedb_tpu.storage.memtable import ColumnarRows

    n = 4096
    rows = ColumnarRows(
        sid=np.arange(n, dtype=np.int64) % 7,
        ts=np.arange(n, dtype=np.int64),
        seq=np.arange(n, dtype=np.uint64),
        op=np.zeros(n, np.uint8),
        fields={"v": np.arange(n, dtype=np.float64)},
        field_valid=None,
    )
    out, path = merge_rows(rows, device_min_rows=1024)
    assert path == "device" and len(out)
    sites = _rows_by_site(registry)
    assert sites["compact_merge"][0]["calls"] == 1

    # promql fast path (fused query program)
    from greptimedb_tpu.promql import fast as F
    from greptimedb_tpu.promql.engine import PromEngine

    F.invalidate_cache()
    try:
        inst.sql(
            "CREATE TABLE req_total (host STRING, greptime_value "
            "DOUBLE, ts TIMESTAMP TIME INDEX, PRIMARY KEY (host))"
        )
        t = inst.catalog.table("public", "req_total")
        ts = 1_700_000_000_000 + np.arange(41) * 15_000
        for h in range(4):
            t.write({"host": np.full(41, f"h{h}", object)}, ts,
                    {"greptime_value": np.cumsum(np.ones(41))})
        eng = PromEngine(inst)
        for _ in range(2):
            val, _ev = eng.query_range(
                "sum by (host) (rate(req_total[1m]))",
                int(ts[5]), int(ts[-1]), 30_000,
            )
        sites = _rows_by_site(registry)
        assert sites["promql"][0]["calls"] == 2
    finally:
        F.invalidate_cache()


def test_flow_sites_fold(tmp_path, registry, no_sessions):
    """Satellite: the two flow/device_state.py jit programs carry
    registry rows (they were the only device dispatches with zero
    telemetry)."""
    s = Standalone(str(tmp_path / "data"))
    try:
        s.enable_flows(tick_interval_s=3600)
        s.sql(
            "CREATE TABLE src (host STRING, v DOUBLE, ts TIMESTAMP "
            "TIME INDEX, PRIMARY KEY (host))"
        )
        s.sql(
            "CREATE FLOW f1 SINK TO out1 AS SELECT host, count(v), "
            "sum(v), avg(v) FROM src GROUP BY host"
        )
        assert s.flows._flows["f1"].device_state is not None
        t0 = 1_700_000_000_000
        for i in range(2):
            s.sql(
                "INSERT INTO src (host, v, ts) VALUES "
                + ", ".join(f"('h{j}', {j}.5, {t0 + i * 1000})"
                            for j in range(4))
            )
            s.flows.flush_all()
        sites = _rows_by_site(DP.global_programs)
        assert sites["flow_apply"][0]["calls"] >= 2
        assert sites["flow_finalize"][0]["calls"] >= 2
        # apply deliberately does not block: achieved rates suppressed
        assert sites["flow_apply"][0]["dispatch_only"] is True
        assert sites["flow_finalize"][0]["dispatch_only"] is False
        assert sites["flow_finalize"][0]["readback_bytes"] > 0
    finally:
        s.close()


@RANGE_QS
def test_session_hit_does_not_count_a_dispatch(tmp_path, registry, q, tag):
    """With sessions ON, the warm poll serves the HBM-resident buffer
    without dispatching — the registry counts real dispatches only."""
    s = Standalone(str(tmp_path / "data"), prefer_device=True,
                   warm_start=False)
    try:
        _seed(s)
        for _ in range(3):
            s.sql(q)
        row = _rows_by_site(DP.global_programs)["range"][0]
        assert row["calls"] == 1  # cold dispatch only
    finally:
        s.close()


# ---------------------------------------------------------------------------
# XLA analysis + roofline
# ---------------------------------------------------------------------------

@RANGE_QS
def test_analysis_and_roofline_verdict(inst, registry, q, tag):
    _seed(inst)
    for _ in range(3):
        inst.sql(q)
    # default CPU config: achieved-only (no peaks -> no verdict)
    docs = registry.snapshot()  # triggers the lazy analysis
    rng_row = [d for d in docs if d["site"] == "range"][0]
    assert rng_row["analysis"] == "ok"
    assert rng_row["flops"] > 0
    assert rng_row["bytes_accessed"] > 0
    assert rng_row["temp_bytes"] >= 0
    assert rng_row["output_bytes"] > 0
    assert rng_row["achieved_gflops"] > 0
    assert rng_row["bound"] == "" and rng_row["pct_of_peak"] == 0.0
    pf, pb, plat, src = registry.peaks()
    assert plat == "cpu" and src == "achieved_only"

    # explicit peaks -> roofline verdict + %-of-peak on every analyzed
    # row with steady-state samples
    registry.config = DP.ProfilingConfig(peak_tflops=0.1,
                                         peak_hbm_gbps=25.0)
    row = [d for d in registry.snapshot() if d["site"] == "range"][0]
    assert row["bound"] in ("compute", "memory")
    assert row["pct_of_peak"] > 0
    # classification is consistent with the operational intensity
    intensity = row["flops"] / row["bytes_accessed"]
    balance = (0.1 * 1e12) / (25.0 * 1e9)
    assert row["bound"] == (
        "compute" if intensity >= balance else "memory"
    )


def test_analysis_disabled_keeps_per_call_stats(inst, registry):
    registry.config = DP.ProfilingConfig(analysis=False)
    _seed(inst)
    inst.sql(RANGE_Q)
    row = _rows_by_site(registry, analyze=True)["range"][0]
    assert row["analysis"] == "off"
    assert row["flops"] == 0.0
    assert row["calls"] == 1 and row["compile_ms"] > 0


def test_lru_collapse_into_other_keeps_totals(registry):
    import jax.numpy as jnp

    from greptimedb_tpu.telemetry import device_trace

    registry.config = DP.ProfilingConfig(max_programs=2, analysis=False)

    def dispatch(i):
        with device_trace.device_call("t", key=("t", i)) as d:
            out = d.run(lambda x: x, jnp.zeros(4))
            d.wait(out)
            d.transfer(16)
        return out

    for i in range(4):
        dispatch(i)
    docs = registry.snapshot(analyze=False)
    other = [d for d in docs if d["program"] == DP.OTHER]
    assert other and other[0]["site"] == "t"
    total_calls = sum(d["calls"] for d in docs)
    assert total_calls == 4  # collapsed rows' totals never vanish
    assert sum(d["readback_bytes"] for d in docs) == 64
    assert registry.evicted_rows > 0


def test_metric_label_cap_collapses_to_other(registry):
    """Prometheus series can never be evicted, so past the first-come
    metric_programs cap churned programs export under program="_other"
    with counters SUMMED (the registry rows keep their own identity —
    only the exported label collapses)."""
    import jax.numpy as jnp

    from greptimedb_tpu.telemetry import device_trace

    registry.config = DP.ProfilingConfig(metric_programs=2,
                                         analysis=False)
    registry._metric_progs.clear()
    for i in range(4):
        with device_trace.device_call("mc", key=("mc", i)) as d:
            d.wait(d.run(lambda x: x, jnp.zeros(2)))
            d.transfer(8)
    global_registry.render()
    calls = global_registry.get("gtpu_device_program_calls_total")
    granted = [
        (key, child.value) for key, child in calls._snapshot()
        if key[0] == "mc" and child.value > 0
    ]
    by_prog = dict(granted)
    # 2 granted labels with 1 call each + _other summing the 2 extras
    assert by_prog.get(("mc", DP.OTHER)) == 2.0, granted
    assert sorted(v for (s, p), v in by_prog.items()
                  if p != DP.OTHER) == [1.0, 1.0]
    # the registry rows themselves keep per-program identity
    docs = [d for d in registry.snapshot(analyze=False)
            if d["site"] == "mc"]
    assert len(docs) == 4


# ---------------------------------------------------------------------------
# the crossing's three legs: dispatch, wait, readback (PR 37)
# ---------------------------------------------------------------------------

@functools.cache
def _crossing_prog():
    import jax

    return jax.jit(lambda x: (x * 2.0).sum(axis=0))


def _crossing(site, *, dispatch_only=False, read=True):
    """One real dispatch on the CPU through the whole call boundary."""
    import jax.numpy as jnp

    from greptimedb_tpu.query import readback
    from greptimedb_tpu.telemetry import device_trace

    with device_trace.device_call(site, key=(site,)) as d:
        out = d.run(_crossing_prog(), jnp.ones((64, 128), jnp.float32))
        d.wait(out, dispatch_only=dispatch_only)
        if read:
            host = d.read(readback.read_full, out)
            assert host.shape == (128,) and host[0] == 128.0
    return d


def test_the_three_legs_fold_into_the_row(registry):
    registry.config = DP.ProfilingConfig(analysis=False)
    # the first call is the compile: its wall time is compile_ms and it
    # stays out of all three legs, exactly as out of execute_ms
    _crossing("legs")
    (row,) = [r for r in registry._rows.values() if r.site == "legs"]
    assert row.calls == 1 and row.compile_ms > 0
    assert (row.execute_ms_total, row.dispatch_ms_total,
            row.wait_ms_total, row.readback_ms_total) == (0, 0, 0, 0)
    calls = [_crossing("legs") for _ in range(5)]
    assert row.calls == 6
    assert row.dispatch_ms_total > 0 and row.readback_ms_total > 0
    assert row.wait_ms_total >= 0
    # one clock: dispatch + wait is execute, to the float's rounding
    assert row.dispatch_ms_total + row.wait_ms_total == pytest.approx(
        row.execute_ms_total, rel=1e-9)
    # and the row holds what the calls stamped
    assert row.dispatch_ms_total == pytest.approx(
        sum(d._disp_ms for d in calls))
    assert row.readback_ms_total == pytest.approx(
        sum(d._rb_ms for d in calls))
    assert row.readback_bytes == 6 * 128 * 4
    doc = [d for d in registry.snapshot(analyze=False)
           if d["site"] == "legs"][0]
    for leg in ("dispatch", "wait", "readback"):
        assert doc[f"{leg}_ms_total"] == pytest.approx(
            getattr(row, f"{leg}_ms_total"), abs=1e-3)
    assert not doc["dispatch_only"]


def test_a_dispatch_only_call_folds_its_dispatch_and_no_wait(registry):
    """The upkeep and the flow applies do not block: the wait leg is
    what lies between the jit call's return and `wait`'s, a few
    microseconds, and the row says its timing is the dispatch's."""
    registry.config = DP.ProfilingConfig(analysis=False)
    _crossing("legs_async", dispatch_only=True, read=False)
    d = _crossing("legs_async", dispatch_only=True, read=False)
    (row,) = [r for r in registry._rows.values()
              if r.site == "legs_async"]
    assert row.dispatch_only and row.calls == 2
    assert row.dispatch_ms_total == pytest.approx(d._disp_ms) and \
        d._disp_ms > 0
    assert 0 <= row.wait_ms_total < row.dispatch_ms_total
    assert row.readback_ms_total == 0
    assert row.dispatch_ms_total + row.wait_ms_total == pytest.approx(
        row.execute_ms_total, rel=1e-9)


def _leg_series(site):
    global_registry.render()   # refresh the pull-model families
    out = {}
    for leg in ("dispatch", "wait", "readback"):
        fam = global_registry.get(f"gtpu_device_program_{leg}_ms_total")
        out[leg] = {key: child.value for key, child in fam._snapshot()
                    if key[0] == site}
    return out


def test_metrics_export_the_legs_and_admin_reset_zeroes_them(
        inst, server, registry):
    _seed(inst)
    for _ in range(3):
        inst.sql(RANGE_Q)
    (doc,) = [d for d in registry.snapshot(analyze=False)
              if d["site"] == "range"]
    legs = _leg_series("range")
    for leg in ("dispatch", "wait", "readback"):
        # labelled (site, program), equal to the row like the neighbours;
        # a program an earlier test of this process ran reads 0 since
        # the fixture's reset
        mine = legs[leg].pop(("range", doc["program"]))
        assert mine == doc[f"{leg}_ms_total"], leg
        assert set(legs[leg].values()) <= {0.0}, leg
        assert doc[f"{leg}_ms_total"] > 0 or leg == "wait"
    _status, text = _get(server, "/metrics")
    assert ('gtpu_device_program_dispatch_ms_total{site="range",'
            f'program="{doc["program"]}"}}') in text
    r = inst.sql("SELECT dispatch_ms_total, wait_ms_total, "
                 "readback_ms_total, execute_ms_total FROM "
                 "information_schema.device_programs WHERE site = 'range'")
    (d_ms, w_ms, r_ms, e_ms), = r.rows()
    assert (d_ms, w_ms, r_ms) == tuple(
        doc[f"{leg}_ms_total"] for leg in ("dispatch", "wait", "readback"))
    assert d_ms + w_ms == pytest.approx(e_ms, abs=2e-3)
    inst.sql("admin reset_device_profiler()")
    for leg, series in _leg_series("range").items():
        assert set(series.values()) == {0.0}, leg


def test_plane_gate_counts_only_a_wait_that_happened():
    """`gtpu_plane_gate_wait_seconds_total{side}`: an uncontended
    `shared()` adds nothing; one held behind an `exclusive()` adds the
    time it was held."""
    import threading
    import time

    from greptimedb_tpu.query import grid_upkeep as GU

    gate = GU.PlaneGate()
    shared0 = GU.GATE_WAIT_SHARED.value
    excl0 = GU.GATE_WAIT_EXCLUSIVE.value
    with gate.shared():
        pass
    with gate.exclusive():
        pass
    assert GU.GATE_WAIT_SHARED.value == shared0
    assert GU.GATE_WAIT_EXCLUSIVE.value == excl0
    inside = threading.Event()
    waited = []

    def reader():
        inside.wait(10)
        t0 = time.monotonic()
        with gate.shared():
            waited.append(time.monotonic() - t0)

    th = threading.Thread(target=reader)
    th.start()
    with gate.exclusive():
        inside.set()
        time.sleep(0.2)
    th.join(10)
    got = GU.GATE_WAIT_SHARED.value - shared0
    assert 0.1 <= got <= waited[0] + 1e-3
    # and the other side: the upkeep behind a query holding the planes
    release = threading.Event()
    holding = threading.Event()

    def query():
        with gate.shared():
            holding.set()
            release.wait(10)

    th = threading.Thread(target=query)
    th.start()
    holding.wait(10)
    threading.Timer(0.15, release.set).start()
    with gate.exclusive():
        pass
    th.join(10)
    assert GU.GATE_WAIT_EXCLUSIVE.value - excl0 >= 0.1
    _status_text = global_registry.render()
    for side in ("shared", "exclusive"):
        assert ('gtpu_plane_gate_wait_seconds_total{side="%s"}' % side
                in _status_text)


# ---------------------------------------------------------------------------
# surfaces: information_schema == /debug/prof/device == metrics,
# across ADMIN reset
# ---------------------------------------------------------------------------

def _surface_triple(inst, server):
    """(information_schema rows, /debug json rows, metric values) keyed
    by (site, program)."""
    info = {}
    r = inst.sql(
        "SELECT site, program, calls, bound, pct_of_peak, flops "
        "FROM information_schema.device_programs"
    )
    for row in r.rows():
        info[(row[0], row[1])] = (row[2], row[3], row[4], row[5])
    status, body = _get(server, "/debug/prof/device?format=json&top=0")
    assert status == 200
    route = {}
    doc = json.loads(body)
    for d in doc["programs"]:
        route[(d["site"], d["program"])] = (
            d["calls"], d["bound"], d["pct_of_peak"], d["flops"]
        )
    global_registry.render()  # refresh the pull-model families
    mets = {}
    calls = global_registry.get("gtpu_device_program_calls_total")
    pct = global_registry.get("gtpu_device_program_pct_of_peak")
    flops = global_registry.get("gtpu_device_program_flops")
    for key, child in calls._snapshot():
        if child.value > 0:
            mets[key] = (int(child.value),
                         pct.labels(*key).value,
                         flops.labels(*key).value)
    return info, route, mets


def test_three_surface_agreement_across_admin_reset(inst, server,
                                                    registry):
    registry.config = DP.ProfilingConfig(peak_tflops=0.1,
                                         peak_hbm_gbps=25.0)
    _seed(inst)
    for _ in range(3):
        inst.sql(RANGE_Q)
    info, route, mets = _surface_triple(inst, server)
    assert info and info == route
    for key, (calls, bound, pct_v, flops_v) in info.items():
        assert mets[key] == (calls, pct_v, flops_v), key
    rng_key = [k for k in info if k[0] == "range"][0]
    assert info[rng_key][1] in ("compute", "memory")
    assert info[rng_key][2] > 0

    # ADMIN reset drops every row; all three surfaces zero together
    r = inst.sql("admin reset_device_profiler()")
    assert r.rows()[0][0] >= 1
    info2, route2, mets2 = _surface_triple(inst, server)
    assert info2 == {} and route2 == {}
    assert mets2 == {}  # published series zeroed, not frozen

    # fresh dispatches after the reset: surfaces agree again
    inst.sql(RANGE_Q)
    info3, route3, mets3 = _surface_triple(inst, server)
    assert info3 and info3 == route3
    for key, (calls, bound, pct_v, flops_v) in info3.items():
        assert mets3[key] == (calls, pct_v, flops_v), key


def test_debug_route_text_face(inst, server, registry):
    _seed(inst)
    inst.sql(RANGE_Q)
    status, text = _get(server, "/debug/prof/device")
    assert status == 200
    assert "device programs:" in text
    assert "range" in text and "compile" in text


def test_debug_route_bad_params(server):
    with pytest.raises(urllib.request.HTTPError):
        _get(server, "/debug/prof/device?top=bogus")
    with pytest.raises(urllib.request.HTTPError):
        _get(server, "/debug/prof/device/trace?seconds=bogus")
    with pytest.raises(urllib.request.HTTPError):
        _get(server, "/debug/prof/device/trace?seconds=0")
    with pytest.raises(urllib.request.HTTPError):
        _get(server, "/debug/prof/device/trace?seconds=120")


# ---------------------------------------------------------------------------
# on-demand trace capture
# ---------------------------------------------------------------------------

def test_trace_capture_writes_loadable_trace(tmp_path, inst, registry):
    _seed(inst)
    inst.sql(RANGE_Q)
    import time

    t0 = time.monotonic()
    doc = DP.capture_trace(0.2, str(tmp_path / "traces"))
    wall = time.monotonic() - t0
    assert doc["seconds"] == 0.2
    # the capture's own legs: starting the profiler, the window asked
    # for, stopping it (the profile collected and written)
    assert doc["hold_s"] >= 0.2 and doc["start_s"] > 0 and doc["stop_s"] > 0
    assert wall - 0.05 <= (doc["start_s"] + doc["hold_s"]
                           + doc["stop_s"]) <= wall
    assert doc["trace_dir"].startswith(str(tmp_path / "traces"))
    # jax.profiler wrote a TensorBoard/perfetto-loadable capture
    assert any(f.endswith((".xplane.pb", ".trace.json.gz"))
               for f in doc["files"]), doc["files"]


def test_trace_capture_route(inst, server, registry, tmp_path):
    status, body = _get(
        server,
        "/debug/prof/device/trace?seconds=0.1"
        f"&dir={tmp_path / 'rt'}",
    )
    assert status == 200
    doc = json.loads(body)
    assert doc["files"], doc


def test_trace_capture_busy_is_typed(registry, tmp_path):
    import threading

    DP._capture_active = True
    try:
        with pytest.raises(DP.CaptureBusyError):
            DP.capture_trace(0.1, str(tmp_path))
    finally:
        DP._capture_active = False
    # sanity: flag cleanup (the finally in capture_trace) lets the next
    # capture proceed
    doc = DP.capture_trace(0.05, str(tmp_path))
    assert doc["seconds"] == 0.05
    assert not DP._capture_active
    del threading


# ---------------------------------------------------------------------------
# attribution: stmt_stats link + EXPLAIN ANALYZE roofline attrs
# ---------------------------------------------------------------------------

def test_stmt_stats_rows_link_program_ids(inst, registry):
    from greptimedb_tpu.telemetry.stmt_stats import global_stmt_stats

    _seed(inst)
    global_stmt_stats.reset()
    for _ in range(2):
        inst.sql(RANGE_Q)
    docs = [d for d in global_stmt_stats.snapshot()
            if "range" in d["query"] and d["calls"] >= 2]
    assert docs, "expected a statement row for the range poll"
    prog_ids = {d["program"] for d in registry.snapshot(analyze=False)}
    linked = set(docs[0]["program_ids"])
    assert linked and linked <= prog_ids
    # the SQL face carries the same link (JSON-encoded)
    r = inst.sql(
        "SELECT program_ids FROM information_schema."
        "statement_statistics WHERE calls >= 2"
    )
    all_linked = set()
    for row in r.rows():
        all_linked |= set(json.loads(row[0]))
    assert linked <= all_linked


def test_session_hit_still_attributes_program(tmp_path, registry):
    """With sessions ON the warm poll skips the dispatch, but EXPLAIN
    ANALYZE and traced polls must not lose the program link — the
    registry row is looked up read-only (and NOT folded: no per-call
    achieved-rate claims for a call that ran no program)."""
    from greptimedb_tpu.telemetry import tracing

    registry.config = DP.ProfilingConfig(peak_tflops=0.1,
                                         peak_hbm_gbps=25.0)
    s = Standalone(str(tmp_path / "data"), prefer_device=True,
                   warm_start=False)
    try:
        _seed(s)
        s.sql(RANGE_Q)  # cold: the one real dispatch
        registry.analyze_pending()
        row = [d for d in registry.snapshot(analyze=False)
               if d["site"] == "range"][0]
        assert row["calls"] == 1
        r = s.sql("EXPLAIN ANALYZE " + RANGE_Q)  # warm: session hit
        text = "\n".join(str(t[-1]) for t in r.rows())
        assert "device_session: hit" in text
        assert f"device_program_range: {row['program']}" in text
        assert "served from the session buffer" in text
        with tracing.span("req") as root:
            s.sql(RANGE_Q)
        dev = [sp for sp in tracing.global_traces.trace(root.trace_id)
               if sp["name"] == "device.execute"
               and sp["attributes"].get("site") == "range"]
        attrs = dev[0]["attributes"]
        assert attrs["program"] == row["program"]
        # the roofline numbers live on the registry row and in EXPLAIN
        # ANALYZE's note above, never on a span
        for k in ("flops", "roofline_bound", "pct_of_peak",
                  "achieved_gflops"):
            assert k not in attrs
        row2 = [d for d in registry.snapshot(analyze=False)
                if d["site"] == "range"][0]
        assert row2["calls"] == 1
    finally:
        s.close()


def test_explain_analyze_carries_program_and_roofline(inst, registry):
    registry.config = DP.ProfilingConfig(peak_tflops=0.1,
                                         peak_hbm_gbps=25.0)
    _seed(inst)
    for _ in range(2):
        inst.sql(RANGE_Q)
    registry.analyze_pending()  # surfaces consulted -> analysis done
    r = inst.sql("EXPLAIN ANALYZE " + RANGE_Q)
    text = "\n".join(str(row[-1]) for row in r.rows())
    assert "device_program_range" in text
    assert "roofline_range" in text
    assert "-bound" in text and "% of peak" in text


# ---------------------------------------------------------------------------
# mesh twins are not cross-served
# ---------------------------------------------------------------------------

def test_mesh_twins_get_distinct_rows(tmp_path, rng, devices, registry,
                                      no_sessions):
    from greptimedb_tpu.parallel import mesh as M
    from greptimedb_tpu.query.executor import QueryEngine
    from greptimedb_tpu.query.planner import plan_select
    from greptimedb_tpu.session import QueryContext
    from greptimedb_tpu.sql.parser import parse_sql

    del plan_select
    inst = Standalone(str(tmp_path))
    try:
        inst.execute_sql(
            "create table cpu (ts timestamp time index, host string "
            "primary key, u double)"
        )
        tab = inst.catalog.table("public", "cpu")
        n_hosts, t = 16, 120
        ts = np.tile(np.arange(t) * 10_000, n_hosts).astype(np.int64)
        hosts = np.repeat(
            [f"h{i:02d}" for i in range(n_hosts)], t
        ).astype(object)
        tab.write({"host": hosts}, ts, {"u": rng.random(n_hosts * t)})
        q = "SELECT host, sum(u), avg(u) FROM cpu GROUP BY host"
        em = QueryEngine(prefer_device=True, mesh=M.make_mesh(devices),
                         mesh_opts=M.MeshOptions(shard_min_series=1,
                                                 shard_min_rows=1))
        es = QueryEngine(prefer_device=True)

        def run(engine):
            stmt = parse_sql(q)[0]
            plan, table = inst.plan(stmt, QueryContext())
            return engine.execute(plan, table)

        run(es)
        run(es)
        run(em)
        assert em.last_exec_path == "device"
        rows = _rows_by_site(DP.global_programs)["groupby"]
        # the single-device program and the shard_map twin fold into
        # DISTINCT registry rows — never cross-served
        assert len(rows) == 2
        by_calls = sorted(r["calls"] for r in rows)
        assert by_calls == [1, 2]
        assert rows[0]["program"] != rows[1]["program"]
    finally:
        inst.close()


def test_peaks_come_from_the_device_kind_table(monkeypatch):
    """A TPU whose device_kind is not in DEVICE_PEAKS reports
    unknown_device_kind and achieved-only — never another chip's
    peaks; a known kind gets its own row; configured peaks win."""
    reg = DP.DeviceProgramRegistry()
    monkeypatch.setattr(DP, "_device_identity",
                        lambda: ("tpu", "TPU v9 hypothetical"))
    assert reg.peaks() == (0.0, 0.0, "tpu", "unknown_device_kind")
    monkeypatch.setattr(DP, "_device_identity",
                        lambda: ("tpu", "TPU v5 lite"))
    assert reg.peaks() == (197.0, 819.0, "tpu",
                           "device_kind:TPU v5 lite")
    reg.config = DP.ProfilingConfig(peak_tflops=1.0, peak_hbm_gbps=2.0)
    monkeypatch.setattr(DP, "_device_identity",
                        lambda: ("tpu", "TPU v9 hypothetical"))
    assert reg.peaks() == (1.0, 2.0, "tpu", "configured")
