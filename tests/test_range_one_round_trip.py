"""A device RANGE query is one call of one program and one readback
(query/device_range.py, ISSUE 28): the host bounds the window before
the dispatch, the program computes the rows' exact extent beside the
result, the host trims after the readback. Every case is held against
the host path (`prefer_device=False`), which sees the exact window by
construction."""

import numpy as np
import pytest

from greptimedb_tpu.instance import Standalone
from greptimedb_tpu.query import device_range as DR
from greptimedb_tpu.query.executor import QueryEngine
from greptimedb_tpu.session import QueryContext
from greptimedb_tpu.telemetry import device_programs as DP
from greptimedb_tpu.telemetry import tracing
from greptimedb_tpu.telemetry.metrics import global_registry

HOSTS, T0, T1, STEP = 8, 100_000, 500_000, 10_000
# h7 reports only here: for a span elsewhere it is a matched series
# with no row
H7_T0, H7_T1 = 300_000, 400_000


@pytest.fixture
def inst(tmp_path):
    """`cpu`: 8 hosts in 3 regions at 10 s from 100 s to 500 s (h7 only
    from 300 s to 400 s), a tenth of `u` null, on the device path."""
    pytest.importorskip("jax")
    i = Standalone(str(tmp_path), prefer_device=True, warm_start=False)
    i.execute_sql(
        "create table cpu (ts timestamp time index, host string primary "
        "key, region string primary key, u double, v double)"
    )
    rng = np.random.default_rng(28)
    ts, host = [], []
    for h in range(HOSTS):
        t = (np.arange(H7_T0, H7_T1, STEP) if h == HOSTS - 1
             else np.arange(T0, T1, STEP))
        ts.append(t)
        host.append(np.full(len(t), h))
    ts = np.concatenate(ts).astype(np.int64)
    host = np.concatenate(host)
    n = len(ts)
    i.catalog.table("public", "cpu").write(
        {"host": np.char.add("h", host.astype(str)).astype(object),
         "region": np.char.add("r", (host % 3).astype(str)).astype(object)},
        ts, {"u": rng.random(n) * 100, "v": rng.random(n) * 10},
        field_valid={"u": rng.random(n) > 0.1},
    )
    yield i
    i.close()


@pytest.fixture(params=["plane", "rows"])
def path(request, monkeypatch):
    """Which range program a small selection takes: the rows program
    (K matched series gathered, what `len(sids) <= _ROWS_MAX` chooses),
    or, every selection counting as past `_ROWS_MAX`, the plane program
    with the contract it had before the rows program existed."""
    if request.param == "plane":
        monkeypatch.setattr(DR, "_ROWS_MAX", -1)
    return request.param


def _took(path_name: str) -> float:
    return global_registry.get(
        "gtpu_range_selection_total").labels(path_name).value


def _compare(rh, rd, q):
    assert rh.names == rd.names
    assert rh.num_rows == rd.num_rows, q
    for i, name in enumerate(rh.names):
        a, b = rh.cols[i], rd.cols[i]
        assert (a.valid_mask == b.valid_mask).all(), (q, name)
        if a.values.dtype == object:
            assert (a.values == b.values).all(), (q, name)
        else:
            m = a.valid_mask
            assert np.allclose(np.asarray(a.values, float)[m],
                               np.asarray(b.values, float)[m],
                               rtol=2e-4, atol=1e-3), (q, name)


def _both(inst, q, ctx=None):
    """(host path's result, device path's result, the device call's
    span attributes) of one statement."""
    inst.query_engine = QueryEngine(prefer_device=False)
    rh = inst.sql(q, ctx)
    assert inst.query_engine.last_exec_path == "host", q
    inst.query_engine = QueryEngine(prefer_device=True)
    with tracing.span("req") as root:
        rd = inst.sql(q, ctx)
    assert inst.query_engine.last_exec_path == "device", q
    dev = [s for s in tracing.global_traces.trace(root.trace_id)
           if s["name"] == "device.execute"]
    return rh, rd, [s["attributes"] for s in dev]


def _trims():
    fam = global_registry.get("gtpu_range_window_trim_total")
    return fam.labels("yes").value, fam.labels("no").value


def _range_calls() -> int:
    return sum(d["calls"] for d in DP.global_programs.snapshot()
               if d["site"] == "range")


FILLS = ["", "FILL PREV", "FILL LINEAR", "FILL NULL", "FILL 7.5"]


@pytest.mark.parametrize("fill", FILLS)
def test_rows_inside_the_where_bounds_are_trimmed_to(inst, fill, path):
    """The WHERE admits the whole grid (100 s to 500 s), h7's rows span
    300 s to 400 s: the program's window is the wider one, the answer
    the exact one, and FILL sees the exact one."""
    q = (f"SELECT ts, host, avg(u) RANGE '10s' {fill}, "
         f"max(v) RANGE '20s' {fill} FROM cpu "
         "WHERE host = 'h7' AND ts >= 0 AND ts < 900000 ALIGN '10s' "
         "BY (host) ORDER BY ts, host")
    yes0, _ = _trims()
    took0 = _took(path)
    rh, rd, dev = _both(inst, q)
    _compare(rh, rd, q)
    assert rd.num_rows > 0
    assert [a["site"] for a in dev] == ["range"]
    assert _took(path) == took0 + 1
    assert dev[0]["rows"] == (8 if path == "rows" else 0)
    # the bound window's steps run from 90 s to 490 s (41), the exact
    # one's from 290 s to 390 s (11): twenty before and ten after go
    assert dev[0]["steps"] == 41 and dev[0]["trimmed_steps"] == 30
    assert _trims()[0] == yes0 + 1
    ts = np.asarray(rd.cols[0].values)
    assert ts.min() == H7_T0 - STEP and ts.max() == H7_T1 - STEP


@pytest.mark.parametrize("fill", ["", "FILL PREV", "FILL 0"])
@pytest.mark.parametrize("by", ["host", "region"])
def test_a_matched_series_with_no_row_in_the_span_makes_no_group(
        inst, fill, by, path):
    """h7 (region r1, with h1 and h4) has no row before 300 s: grouped
    by host it makes no group, FILL or not; grouped by region its
    region's group holds only the others' rows."""
    q = (f"SELECT ts, {by}, count(u) RANGE '10s' {fill}, "
         f"last_value(v) RANGE '10s' {fill} FROM cpu "
         "WHERE host IN ('h1', 'h2', 'h7') AND ts >= 100000 "
         f"AND ts < 200000 ALIGN '10s' BY ({by}) ORDER BY ts, {by}")
    rh, rd, dev = _both(inst, q)
    _compare(rh, rd, q)
    groups = set(rd.cols[1].values.tolist())
    assert groups == ({"h1", "h2"} if by == "host" else {"r1", "r2"})
    assert dev[0]["groups"] == (3 if by == "host" else 2)
    assert dev[0]["trimmed_steps"] == 0


@pytest.mark.parametrize("where", [
    "",                                     # both sides open
    "WHERE ts >= 200000",                   # open above
    "WHERE ts < 300000",                    # open below
    "WHERE ts >= 200000 AND ts < 300000",   # neither: bound == exact
    "WHERE host != 'h3' AND ts >= 50000",   # a bound before the grid
])
def test_open_sides_take_the_grid_s_own_extent(inst, where):
    q = ("SELECT ts, host, sum(u) RANGE '30s', first_value(v) RANGE '30s' "
         f"FROM cpu {where} ALIGN '20s' BY (host) ORDER BY ts, host")
    _, no0 = _trims()
    rh, rd, dev = _both(inst, q)
    _compare(rh, rd, q)
    assert rd.num_rows > 0
    # the grid starts and ends with the data: nothing to trim
    assert dev[0]["trimmed_steps"] == 0
    assert _trims()[1] == no0 + 1


@pytest.mark.parametrize("where,dispatches", [
    # matched series, none with a row in the span: the program says so
    ("WHERE host = 'h7' AND ts >= 100000 AND ts < 200000", 1),
    # no series matched, or no cell of the grid admitted: the host
    # knows before any dispatch
    ("WHERE host = 'nobody'", 0),
    ("WHERE ts >= 600000 AND ts < 700000", 0),
])
def test_an_empty_selection_is_empty(inst, where, dispatches, path):
    q = (f"SELECT ts, host, avg(u) RANGE '10s' FILL PREV FROM cpu {where} "
         "ALIGN '10s' BY (host)")
    DP.global_programs.reset()
    rh, rd, dev = _both(inst, q)
    assert rh.num_rows == 0 and rd.num_rows == 0
    assert rh.names == rd.names
    assert _range_calls() == dispatches
    assert len(dev) == dispatches
    if dispatches:
        # and the next poll of it asks the device nothing
        inst.sql(q)
        assert _range_calls() == dispatches


def test_twenty_fresh_literals_of_one_span_compile_once(inst, path):
    """Host and hour change with every query, the program does not: its
    spec holds the bound window's steps and the matched groups (and the
    rows program's bucket), the same for every literal of a panel. (On
    the plane the first series alone is its own group with no fold,
    another spec: h0 is left out.)"""
    inst.query_engine = QueryEngine(prefer_device=True)
    eh = QueryEngine(prefer_device=False)
    DP.global_programs.reset()
    compiles = global_registry.get(
        "gtpu_device_program_compiles_total").labels("range")
    compiles0 = compiles.value
    for k in range(20):
        lo = T0 + 10_000 * k
        q = ("SELECT ts, host, max(u) RANGE '10s' FROM cpu "
             f"WHERE host = 'h{1 + k % 6}' AND ts >= {lo} AND ts < {lo + 100000} "
             "ALIGN '10s' BY (host)")
        rd = inst.sql(q)
        assert inst.query_engine.last_exec_path == "device"
        dev_engine, inst.query_engine = inst.query_engine, eh
        _compare(inst.sql(q), rd, q)
        inst.query_engine = dev_engine
    rows = [d for d in DP.global_programs.snapshot() if d["site"] == "range"]
    assert len(rows) == 1 and rows[0]["calls"] == 20
    assert compiles.value == compiles0 + 1
    entry = next(iter(inst.query_engine.range_cache._entries.values()))
    assert len(entry.program_specs) == 1
    (spec,) = entry.program_specs
    assert spec[6] == (8 if path == "rows" else 0)
    assert _took(path) >= 20


def test_a_session_hit_dispatches_nothing_and_a_since_poll_reads_the_delta(
        inst, path):
    q = ("SELECT ts, host, avg(v) RANGE '10s' FROM cpu "
         "WHERE host = 'h7' AND ts >= 0 AND ts < 900000 ALIGN '10s' "
         "BY (host) ORDER BY ts, host")
    inst.query_engine = QueryEngine(prefer_device=True)
    DP.global_programs.reset()
    rb = global_registry.get("gtpu_readback_bytes_total")
    full0 = rb.labels("full").value
    full = inst.sql(q).rows()
    assert [r[0] for r in full] == list(range(H7_T0, H7_T1, STEP))
    assert _range_calls() == 1
    # the bound window's forty steps (100 s to 490 s) of one group, the
    # int32[4] and the activity flags, in one readback: a bool a series
    # of the plane, or an int32 a row of the bucket inside `packed`
    entry = next(iter(inst.query_engine.range_cache._entries.values()))
    flags = entry.num_series if path == "plane" else 4 * 8
    assert rb.labels("full").value - full0 == 4 * 40 + 16 + flags
    # the same poll again: the session's buffer, trimmed as before by
    # what the memo kept of the first answer
    full1 = rb.labels("full").value
    assert inst.sql(q).rows() == full
    assert _range_calls() == 1
    assert rb.labels("full").value - full1 == 4 * 40
    # a since poll slices that buffer on the device, at the cursor: the
    # ten steps past the rows' end come back with the delta and are
    # trimmed on the host
    cut = full[len(full) // 2][0]
    delta0 = rb.labels("delta").value
    ctx = QueryContext()
    ctx.extensions["since_ms"] = cut
    delta = inst.sql(q, ctx).rows()
    assert delta == [r for r in full if r[0] > cut]
    assert _range_calls() == 1
    assert rb.labels("delta").value - delta0 == 4 * (len(delta) + 10)
    # a cursor at or past the last step: nothing to send
    ctx.extensions["since_ms"] = full[-1][0]
    assert inst.sql(q, ctx).rows() == []
    assert _range_calls() == 1


def test_one_selection_s_inputs_stay_on_the_device_from_its_second_dispatch(
        inst, path):
    """A dashboard slides its hour over the same hosts: the group ids
    (the mask rides in them) are computed once; the plane program's go
    with the first call as a NumPy value and are device-resident from
    the second on, the rows program's K of them ride every call's one
    vector and nothing is ever placed on the device."""
    inst.query_engine = QueryEngine(prefer_device=True)
    eh = QueryEngine(prefer_device=False)
    results = []
    for lo in (100_000, 150_000, 200_000):
        q = ("SELECT ts, region, min(u) RANGE '10s' FROM cpu "
             f"WHERE region != 'r0' AND ts >= {lo} AND ts < {lo + 100000} "
             "ALIGN '10s' BY (region) ORDER BY ts, region")
        with tracing.span("req") as root:
            rd = inst.sql(q)
        results.append((q, rd))
        sel = [s for s in tracing.global_traces.trace(root.trace_id)
               if s["name"] == "query.select_series"]
        assert [s["attributes"]["memo"] for s in sel] == [
            "miss" if lo == 100_000 else "hit"]
    entry = next(iter(inst.query_engine.range_cache._entries.values()))
    (memo,) = entry.query_memo.values()
    assert len(memo["windows"]) == 3
    assert isinstance(memo["gid_host"], np.ndarray)
    if path == "plane":
        assert len(memo["gid_host"]) == entry.num_series
        assert memo["gid"] is not None and not isinstance(memo["gid"],
                                                          np.ndarray)
    else:
        # h1, h2, h4, h5, h7 and their two regions, padded to the bucket
        assert memo["gid"] is None and memo["rows"] == 8
        assert memo["tail"].tolist() == [1, 2, 4, 5, 7, 0, 0, 0,
                                         0, 1, 0, 1, 0, 2, 2, 2]
    inst.query_engine = eh
    for q, rd in results:
        _compare(inst.sql(q), rd, q)


@pytest.mark.parametrize("where,rows", [("", 0), ("WHERE host = 'h1'", 8)])
def test_persisted_specs_of_another_signature_are_skipped(inst, where, rows):
    """A spec file written before the fused programs, or before the
    rows program's bucket rode the spec, names programs no query asks
    for: warm-up dispatches none of them."""
    import json

    q = (f"SELECT ts, host, avg(u) RANGE '10s' FROM cpu {where} "
         "ALIGN '10s' BY (host)")
    inst.query_engine = QueryEngine(prefer_device=True)
    inst.sql(q)
    table = inst.catalog.table("public", "cpu")
    entry = next(iter(inst.query_engine.range_cache._entries.values()))
    (spec,) = entry.program_specs
    region = table.regions[0]
    path = DR._program_specs_path(entry, region)
    DR._persist_program_specs(entry, table)
    doc = json.loads(region.store.read(path))
    assert doc["signature"] == DR._SPECS_SIGNATURE == "fused-2"
    assert [s["rows"] for s in doc["specs"]] == [rows] == [spec[6]]
    DP.global_programs.reset()
    assert DR.precompile_programs(entry, table) == 1
    assert _range_calls() == 1
    # warm-up called the program a query calls, with the kind of
    # argument a query passes: the next query compiles nothing
    compiles = global_registry.get(
        "gtpu_device_program_compiles_total").labels("range")
    compiles0 = compiles.value
    inst.sql(q.replace("'h1'", "'h2'"))
    assert _range_calls() == (2 if rows else 1)   # no matcher: a session hit
    assert compiles.value == compiles0
    # the list PR 28's parent wrote (no signature) and the one this
    # PR's parent wrote (six-member specs under "fused-1")
    for old in (doc["specs"], {"signature": "fused-1", "specs": [
            {k: v for k, v in s.items() if k != "rows"}
            for s in doc["specs"]]}):
        region.store.write(path, json.dumps(old).encode())
        assert DR.precompile_programs(entry, table) == 0
    assert _range_calls() == (2 if rows else 1)


MESH_QUERIES = [
    # fold=False (a series is its own group): the sharded twin, output
    # series-sharded, h7 dropped after the readback
    "SELECT ts, host, avg(u) RANGE '20s' FILL PREV, last_value(v) RANGE '20s' "
    "FILL PREV FROM cpu WHERE ts >= 0 AND ts < 250000 ALIGN '10s' BY (host) "
    "ORDER BY ts, host",
    # fold=True: the blocked exact fold across shards
    "SELECT ts, region, sum(u) RANGE '20s', max(v) RANGE '20s' FROM cpu "
    "WHERE host != 'h3' AND ts >= 0 AND ts < 900000 ALIGN '10s' BY (region) "
    "ORDER BY ts, region",
    "SELECT ts, count(*) RANGE '30s' FROM cpu ALIGN '30s' BY () ORDER BY ts",
]


@pytest.mark.parametrize("q,took", [
    (MESH_QUERIES[0], "plane"), (MESH_QUERIES[1], "plane"),
    (MESH_QUERIES[1], "rows"), (MESH_QUERIES[2], "plane"),
])
def test_the_mesh_twin_is_one_call_too(inst, devices, q, took, monkeypatch):
    """`host != 'h3'` matches seven series: the rows program on a mesh
    too, chosen by K alone; the same statement with every selection
    past `_ROWS_MAX`, and the two with no matcher, take the sharded
    twin."""
    from greptimedb_tpu.parallel import mesh as M

    if took == "plane":
        monkeypatch.setattr(DR, "_ROWS_MAX", -1)

    opts = M.MeshOptions(shard_min_series=1, shard_min_rows=1)
    inst.query_engine = QueryEngine(prefer_device=False)
    rh = inst.sql(q)
    em = QueryEngine(prefer_device=True, mesh=M.make_mesh(devices),
                     mesh_opts=opts)
    inst.query_engine = em
    DP.global_programs.reset()
    rm = inst.sql(q)
    assert em.last_exec_path == "device"
    entry = next(iter(em.range_cache._entries.values()))
    assert len(entry.nrow.devices()) == 8
    _compare(rh, rm, q)
    rows = [d for d in DP.global_programs.snapshot() if d["site"] == "range"]
    assert len(rows) == 1 and rows[0]["calls"] == 1
    (memo,) = entry.query_memo.values()
    if took == "plane":
        # the group ids were placed series-sharded, and kept
        assert len(memo["gid"].devices()) == 8
    else:
        # seven sids in the call's vector, nothing placed anywhere
        assert memo["gid"] is None and memo["rows"] == 8
        (spec,) = entry.program_specs
        assert spec[6] == 8


def test_query_threads_share_an_entry_s_memo(inst, path):
    """More query threads than cores, a short switch interval, more
    selections than the memo holds: every answer is the host path's and
    no thread trips over another's eviction."""
    import sys
    import threading

    def q(k):
        lo = T0 + 10_000 * (k % 20)
        hosts = ", ".join(f"'h{(k + d) % 7}'" for d in range(1 + k % 3))
        return ("SELECT ts, host, max(v) RANGE '10s' FROM cpu "
                f"WHERE host IN ({hosts}) AND ts >= {lo} AND "
                f"ts < {lo + 100000} ALIGN '10s' BY (host) ORDER BY ts, host")

    n = 80
    inst.query_engine = QueryEngine(prefer_device=False)
    want = [inst.sql(q(k)).rows() for k in range(n)]
    inst.query_engine = QueryEngine(prefer_device=True)
    got, errors = [None] * n, []

    def worker(w):
        try:
            for k in range(w, n, 16):
                got[k] = inst.sql(q(k)).rows()
        except Exception as e:  # noqa: BLE001 - reported below
            errors.append(repr(e))

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    monkey_max, DR._MEMO_MAX = DR._MEMO_MAX, 4
    try:
        threads = [threading.Thread(target=worker, args=(w,))
                   for w in range(16)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(old)
        DR._MEMO_MAX = monkey_max
    assert not errors, errors[:3]
    for k in range(n):
        assert len(got[k]) == len(want[k]), q(k)
        for a, b in zip(got[k], want[k]):
            assert a[:2] == b[:2] and abs(a[2] - b[2]) < 1e-3, q(k)
