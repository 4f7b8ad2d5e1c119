"""A selective RANGE query works on the rows the tag index matched
(query/device_range.py, ISSUE 32): at or under `_ROWS_MAX` matched
series the call passes one int32 vector, the rows program gathers K
rows and runs the plane programs' body on them, and a fresh window reads
one packed buffer back. Every case is held against the host path
(`prefer_device=False`) and against the plane program on the same table
(every selection counting as past `_ROWS_MAX`)."""

import sys
import threading

import numpy as np
import pytest

from greptimedb_tpu.instance import Standalone
from greptimedb_tpu.parallel import mesh as M
from greptimedb_tpu.query import device_range as DR
from greptimedb_tpu.query.executor import QueryEngine
from greptimedb_tpu.session import QueryContext
from greptimedb_tpu.telemetry import device_programs as DP
from greptimedb_tpu.telemetry import tracing
from greptimedb_tpu.telemetry.metrics import global_registry

HOSTS, T0, T1, STEP = 80, 100_000, 500_000, 10_000
# h10 reports only here: for a span elsewhere it is a matched series
# with no row
SPARSE, SP_T0, SP_T1 = 10, 300_000, 400_000

OPS = sorted(DR._STATE_COMBINE)
SQL_OP = {"mean": "avg"}
# exact whatever the order of a fold: equal to the plane program's to
# the bit; the sums fold K rows' blocks where the plane folds S rows'
EXACT = {"count", "min", "max", "first_value", "last_value"}
# a variance is s2/n - mean^2 in float32 on the device: what cancels is
# of the size of u^2 (u < 100), whatever the variance; a standard
# deviation is held to it through its square
VAR_ATOL = 16 * float(np.finfo(np.float32).eps) * 100.0 ** 2
# both sides of each bucket and of _ROWS_MAX
KS = [1, 2, 8, 9, 64, 65]


def _hosts(k: int) -> list[int]:
    """K distinct hosts scattered over the series axis: h3 alone, h10
    (the sparse one) from K = 2 on."""
    return sorted((7 * i + 3) % HOSTS for i in range(k))


def _where(k: int) -> str:
    return "host IN (" + ", ".join(f"'h{h}'" for h in _hosts(k)) + ")"


@pytest.fixture(scope="module")
def inst(tmp_path_factory):
    """`cpu`: 80 hosts in 3 regions at 10 s from 100 s to 500 s, every
    host on the same timestamps (any two series tie on each), h10 only
    from 300 s to 400 s, a tenth of `u` null."""
    pytest.importorskip("jax")
    i = Standalone(str(tmp_path_factory.mktemp("rows")), prefer_device=True,
                   warm_start=False)
    i.execute_sql(
        "create table cpu (ts timestamp time index, host string primary "
        "key, region string primary key, u double, v double)"
    )
    rng = np.random.default_rng(32)
    ts, host = [], []
    for h in range(HOSTS):
        t = (np.arange(SP_T0, SP_T1, STEP) if h == SPARSE
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


class Engines:
    """The three answers of one statement: the host path's, the plane
    program's, the rows program's, each from an engine that keeps its
    grid entry over the module."""

    def __init__(self, inst):
        self.inst = inst
        self.host = QueryEngine(prefer_device=False)
        self.plane = QueryEngine(prefer_device=True)
        self.rows = QueryEngine(prefer_device=True)
        self.answers: dict = {}

    def run(self, engine, q, ctx=None, rows_max=None):
        """-> (result, attributes of the device call's span)."""
        old_engine, self.inst.query_engine = self.inst.query_engine, engine
        old_max = DR._ROWS_MAX
        if rows_max is not None:
            DR._ROWS_MAX = rows_max
        try:
            with tracing.span("req") as root:
                r = self.inst.sql(q, ctx)
        finally:
            DR._ROWS_MAX = old_max
            self.inst.query_engine = old_engine
        dev = [s["attributes"]
               for s in tracing.global_traces.trace(root.trace_id)
               if s["name"] == "device.execute"]
        return r, dev

    def all(self, q, ctx=None):
        rh, _ = self.run(self.host, q, ctx)
        assert self.host.last_exec_path == "host", q
        rp, devp = self.run(self.plane, q, ctx, rows_max=-1)
        rr, devr = self.run(self.rows, q, ctx)
        assert self.plane.last_exec_path == "device", q
        assert self.rows.last_exec_path == "device", q
        return rh, rp, rr, devp, devr


@pytest.fixture(scope="module")
def eng(inst):
    return Engines(inst)


def _same_shape(ra, rb, q):
    assert ra.names == rb.names
    assert ra.num_rows == rb.num_rows, q
    for a, b in zip(ra.cols, rb.cols):
        assert (a.valid_mask == b.valid_mask).all(), q
        if a.values.dtype == object:
            assert (a.values == b.values).all(), q


def _close(ra, rb, q, names=None):
    _same_shape(ra, rb, q)
    for name, a, b in zip(ra.names, ra.cols, rb.cols):
        if a.values.dtype != object and (names is None or name in names):
            m = a.valid_mask
            x = np.asarray(a.values, float)[m]
            y = np.asarray(b.values, float)[m]
            atol = 1e-3
            if "var" in name or "stddev" in name:
                atol = VAR_ATOL
            if "stddev" in name:
                x, y = x * x, y * y
            assert np.allclose(x, y, rtol=2e-4, atol=atol), (q, name)


def _equal(ra, rb, q, names=None):
    _same_shape(ra, rb, q)
    for name, a, b in zip(ra.names, ra.cols, rb.cols):
        if a.values.dtype != object and (names is None or name in names):
            m = a.valid_mask
            assert np.array_equal(np.asarray(a.values)[m],
                                  np.asarray(b.values)[m]), (q, name)


def _took(path: str) -> float:
    return global_registry.get(
        "gtpu_range_selection_total").labels(path).value


def _range_calls() -> int:
    return sum(d["calls"] for d in DP.global_programs.snapshot()
               if d["site"] == "range")


# ---------------------------------------------------------------------------
# every op x K x grouped or not: one statement a (K, by) holds every op
# as a column, each case holds its own column
# ---------------------------------------------------------------------------

def _answers(eng, k, by):
    if (k, by) not in eng.answers:
        items = ", ".join(f"{SQL_OP.get(op, op)}(u) RANGE '30s' AS c_{op}"
                          for op in OPS)
        keys = "ts, host" if by else "ts"
        q = (f"SELECT {keys}, {items} FROM cpu WHERE {_where(k)} "
             f"AND ts >= 0 AND ts < 900000 ALIGN '20s' "
             f"BY ({'host' if by else ''}) ORDER BY {keys}")
        eng.answers[k, by] = (q, *eng.all(q))
    return eng.answers[k, by]


@pytest.mark.parametrize("by", [True, False], ids=["by_host", "by_none"])
@pytest.mark.parametrize("k", KS)
@pytest.mark.parametrize("op", OPS)
def test_every_op_on_k_rows_is_the_plane_s_and_the_host_s(eng, op, k, by):
    q, rh, rp, rr, devp, devr = _answers(eng, k, by)
    col = {f"c_{op}"}
    assert rr.num_rows > 0
    _close(rh, rr, q, col)
    _close(rh, rp, q, col)
    if op in EXACT:
        _equal(rp, rr, q, col)
    # which program ran: K alone chose
    assert devp[0]["rows"] == 0
    bucket = 8 if k <= 8 else 64 if k <= 64 else 0
    assert devr[0]["rows"] == bucket
    # BY host: each matched row its own group, no fold; none: one group
    assert devr[0]["groups"] == (k if by else 1)


def test_two_series_tied_on_a_timestamp_break_by_absolute_sid(eng):
    """h3 and h17 hold a row at every timestamp: the ungrouped first and
    last value of a step is the lower and the higher sid's, whatever
    rows of the gather they are."""
    q = ("SELECT ts, first_value(v) RANGE '10s' AS f, "
         "last_value(v) RANGE '10s' AS l, first_value(u) RANGE '10s' AS fu "
         "FROM cpu WHERE host IN ('h17', 'h3') AND ts >= 200000 "
         "AND ts < 300000 ALIGN '10s' BY () ORDER BY ts")
    rh, rp, rr, _, devr = eng.all(q)
    assert devr[0]["rows"] == 8 and devr[0]["groups"] == 1
    _equal(rp, rr, q)
    _close(rh, rr, q)
    one = {}
    for h in (3, 17):
        r, _ = eng.run(eng.host, q.replace("IN ('h17', 'h3')", f"= 'h{h}'"))
        one[h] = r
    got = dict(zip(rr.names, rr.cols))
    assert np.allclose(got["f"].values, one[3].cols[1].values)
    assert np.allclose(got["l"].values, one[17].cols[2].values)


FILLS = ["", "FILL PREV", "FILL LINEAR", "FILL NULL", "FILL 7.5"]


@pytest.mark.parametrize("by", ["host", "region"])
@pytest.mark.parametrize("fill", FILLS)
def test_each_fill_sees_the_exact_window(eng, fill, by):
    """h3 and the sparse h10, whose rows span 300 s to 400 s: by host
    h10's group is filled over its own extent's steps, by region (r0 and
    r1: a fold) over the selection's."""
    q = (f"SELECT ts, {by}, avg(u) RANGE '10s' {fill}, "
         f"max(v) RANGE '20s' {fill} FROM cpu WHERE {_where(2)} "
         f"AND ts >= 0 AND ts < 900000 ALIGN '10s' BY ({by}) "
         f"ORDER BY ts, {by}")
    rh, rp, rr, devp, devr = eng.all(q)
    _close(rh, rr, q)
    _close(rp, rr, q)
    assert rr.num_rows > 0
    assert devr[0]["rows"] == 8 and devr[0]["groups"] == 2
    assert devr[0]["steps"] == devp[0]["steps"]
    assert devr[0]["trimmed_steps"] == devp[0]["trimmed_steps"]


@pytest.mark.parametrize("by", ["host", "region"])
def test_a_matched_host_with_no_row_in_the_span_makes_no_group(eng, by):
    q = (f"SELECT ts, {by}, count(u) RANGE '10s', last_value(v) RANGE '10s' "
         f"FROM cpu WHERE host IN ('h10', 'h4') AND ts >= 100000 "
         f"AND ts < 200000 ALIGN '10s' BY ({by}) ORDER BY ts, {by}")
    rh, rp, rr, _, devr = eng.all(q)
    _close(rh, rr, q)
    _equal(rp, rr, q)
    assert set(rr.cols[1].values.tolist()) == (
        {"h4"} if by == "host" else {"r1"})
    assert devr[0]["groups"] == (2 if by == "host" else 1)
    # and alone it is an empty answer the program had to give
    q1 = q.replace("IN ('h10', 'h4')", "= 'h10'")
    rh, rp, rr, _, devr = eng.all(q1)
    assert rh.num_rows == rp.num_rows == rr.num_rows == 0
    assert rh.names == rr.names and devr[0]["rows"] == 8


@pytest.mark.parametrize("where", [
    "",                                   # both sides open
    "AND ts >= 200000",                   # open above
    "AND ts < 300000",                    # open below
    "AND ts >= 200000 AND ts < 300000",   # neither: bound == exact
    "AND ts >= 50000",                    # a bound before the grid
])
def test_open_ts_sides_take_the_grid_s_own_extent(eng, where):
    q = ("SELECT ts, host, sum(u) RANGE '30s', first_value(v) RANGE '30s' "
         f"FROM cpu WHERE {_where(9)} {where} ALIGN '20s' BY (host) "
         "ORDER BY ts, host")
    rh, rp, rr, devp, devr = eng.all(q)
    _close(rh, rr, q)
    _close(rp, rr, q)
    assert rr.num_rows > 0
    assert devr[0]["rows"] == 64 and devr[0]["groups"] == 9
    assert devr[0]["trimmed_steps"] == devp[0]["trimmed_steps"]


# ---------------------------------------------------------------------------
# the crossings of a fresh literal, a session hit, a since poll
# ---------------------------------------------------------------------------

@pytest.fixture
def crossings(monkeypatch):
    """Counts, per call of a range program, the host (NumPy) arguments it
    was passed and, per `jax.device_get`, the arrays it read."""
    import jax

    seen = {"calls": [], "gets": []}

    def counting(get):
        def wrapped(*a, **kw):
            out = get(*a, **kw)
            program = out[0] if isinstance(out, tuple) else out

            def call(*args, **kwargs):
                leaves = jax.tree_util.tree_leaves(args)
                seen["calls"].append(
                    [x.shape for x in leaves if isinstance(x, np.ndarray)])
                res = program(*args, **kwargs)
                seen["outputs"] = len(res)
                return res

            return (call, *out[1:]) if isinstance(out, tuple) else call
        return wrapped

    monkeypatch.setattr(DR, "get_rows_program",
                        counting(DR.get_rows_program))
    monkeypatch.setattr(DR, "get_program", counting(DR.get_program))
    real_get = jax.device_get

    def device_get(x):
        seen["gets"].append([tuple(a.shape)
                             for a in jax.tree_util.tree_leaves(x)])
        return real_get(x)

    monkeypatch.setattr(jax, "device_get", device_get)
    return seen


def test_a_fresh_literal_is_one_host_argument_and_one_array_back(
        eng, crossings):
    """Where the plane program takes two host arguments (group ids of
    every series, the window) and gives three outputs to one
    `device_get`, the rows program takes one vector and gives one."""
    q = ("SELECT ts, host, max(u) RANGE '10s' FROM cpu "
         "WHERE host = 'h{h}' AND ts >= 150000 AND ts < 250000 ALIGN '10s' "
         "BY (host)")
    rb = global_registry.get("gtpu_readback_bytes_total").labels("full")
    rb0 = rb.value
    rr, devr = eng.run(eng.rows, q.format(h=41))
    # (delta, lo, hi), eight sids, eight group ids
    assert crossings["calls"] == [[(3 + 2 * 8,)]]
    assert crossings["outputs"] == 2
    # ten steps of one group, int32[4], eight flags: one flat array
    assert crossings["gets"] == [[(10 + 4 + 8,)]]
    assert rb.value - rb0 == 4 * (10 + 4 + 8) == devr[0]["readback_bytes"]
    assert devr[0]["upload_bytes"] == 4 * (3 + 2 * 8)
    memo = next(iter(eng.rows.range_cache._entries.values())).query_memo
    assert all(m["gid"] is None for m in memo.values())
    # the same literal on the plane program, as before this program
    crossings["calls"].clear()
    crossings["gets"].clear()
    rp, _ = eng.run(eng.plane, q.format(h=41), rows_max=-1)
    assert crossings["calls"] == [[(HOSTS,), (3,)]]
    assert crossings["outputs"] == 3
    assert crossings["gets"] == [[(1, 1, 10), (HOSTS,), (4,)]]
    _equal(rp, rr, q)


@pytest.mark.parametrize("sel", ["past", "none"])
def test_a_wide_or_unmatched_selection_takes_the_plane_program_as_before(
        eng, crossings, sel):
    """65 matched series, or no matcher: today's program with today's
    arguments, whatever `_ROWS_MAX` would allow."""
    where = f"WHERE {_where(65)} AND" if sel == "past" else "WHERE"
    q = (f"SELECT ts, region, min(v) RANGE '10s' FROM cpu {where} "
         "ts >= 150000 AND ts < 250000 ALIGN '10s' BY (region) "
         "ORDER BY ts, region")
    plane0, rows0 = _took("plane"), _took("rows")
    rr, devr = eng.run(QueryEngine(prefer_device=True), q)
    rh, _ = eng.run(eng.host, q)
    _close(rh, rr, q)
    assert (_took("plane"), _took("rows")) == (plane0 + 1, rows0)
    assert devr[0]["rows"] == 0
    assert crossings["calls"] == [[(HOSTS,), (3,)]]
    assert crossings["outputs"] == 3
    assert crossings["gets"] == [[(1, 3, 10), (HOSTS,), (4,)]]


def test_a_session_hit_dispatches_nothing_and_a_since_poll_reads_the_delta(
        eng, crossings):
    q = ("SELECT ts, host, avg(v) RANGE '10s' FROM cpu "
         "WHERE host IN ('h10', 'h55') AND ts >= 0 AND ts < 900000 "
         "ALIGN '10s' BY (host) ORDER BY ts, host")
    full_h = eng.run(eng.host, q)[0].rows()
    DP.global_programs.reset()
    full = eng.run(eng.rows, q)[0].rows()
    assert [r[:2] for r in full] == [r[:2] for r in full_h]
    assert _range_calls() == 1 and len(crossings["gets"]) == 1
    # the same poll again: the session's buffer (two groups, forty
    # steps), trimmed as before by what the memo kept
    again, dev = eng.run(eng.rows, q)
    assert again.rows() == full
    assert _range_calls() == 1 and dev[0]["rows"] == 8
    assert crossings["gets"][1:] == [[(1, 2, 40)]]
    # a since poll slices that buffer on the device, at the cursor
    cut = 350_000
    ctx = QueryContext()
    ctx.extensions["since_ms"] = cut
    delta = eng.run(eng.rows, q, ctx)[0].rows()
    assert delta == [r for r in full if r[0] > cut]
    assert _range_calls() == 1
    assert crossings["gets"][2:] == [[(1, 2, 40 - 26)]]
    # a since poll of a window the memo does not know reads `packed`,
    # one array, and cuts the cursor's steps on the host
    q2 = q.replace("ts >= 0", "ts >= 100000")
    delta2 = eng.run(eng.rows, q2, ctx)[0].rows()
    assert delta2 == delta
    assert _range_calls() == 2
    assert crossings["gets"][3:] == [[(2 * 40 + 4 + 8,)]]


def test_twenty_fresh_literals_compile_once_a_bucket(eng):
    """Ungrouped, the spec holds one group whatever K: every selection
    of a bucket is one program."""
    compiles = global_registry.get(
        "gtpu_device_program_compiles_total").labels("range")
    engine = QueryEngine(prefer_device=True)
    for ks, bucket in (((2, 3, 5, 8), 8), ((9, 17, 40, 64), 64)):
        DP.global_programs.reset()
        compiles0 = compiles.value
        for n in range(20):
            lo = T0 + 10_000 * n
            hosts = [(11 * n + 7 * i) % HOSTS for i in range(ks[n % 4])]
            q = ("SELECT ts, max(u) RANGE '10s' FROM cpu WHERE host IN ("
                 + ", ".join(f"'h{h}'" for h in hosts)
                 + f") AND ts >= {lo} AND ts < {lo + 100000} "
                 "ALIGN '10s' BY () ORDER BY ts")
            rr, dev = eng.run(engine, q)
            assert dev[0]["rows"] == bucket
            _close(eng.run(eng.host, q)[0], rr, q)
        rows = [d for d in DP.global_programs.snapshot()
                if d["site"] == "range"]
        assert len(rows) == 1 and rows[0]["calls"] == 20
        assert compiles.value == compiles0 + 1
    entry = next(iter(engine.range_cache._entries.values()))
    assert sorted(s[6] for s in entry.program_specs) == [8, 64]


# ---------------------------------------------------------------------------
# mesh twins, threads
# ---------------------------------------------------------------------------

MESH_QUERIES = [
    # no fold: nine rows of the 64 bucket
    "SELECT ts, host, avg(u) RANGE '20s' FILL PREV, last_value(v) RANGE '20s' "
    f"FILL PREV FROM cpu WHERE {_where(9)} AND ts >= 0 AND ts < 350000 "
    "ALIGN '10s' BY (host) ORDER BY ts, host",
    # a fold over sums: the order is the rows', on every mesh size
    f"SELECT ts, region, sum(u) RANGE '20s', stddev_samp(u) RANGE '30s' AS stddev, "
    f"first_value(u) RANGE '20s' FROM cpu WHERE {_where(8)} "
    "ALIGN '10s' BY (region) ORDER BY ts, region",
    f"SELECT ts, avg(v) RANGE '30s', count(*) RANGE '30s' FROM cpu "
    f"WHERE {_where(64)} ALIGN '30s' BY () ORDER BY ts",
]


@pytest.mark.parametrize("n_dev", [2, 4])
@pytest.mark.parametrize("q", MESH_QUERIES)
def test_the_mesh_twins_do_one_device_s_arithmetic(eng, devices, q, n_dev):
    opts = M.MeshOptions(shard_min_series=1, shard_min_rows=1)
    r1, dev1 = eng.run(eng.rows, q)
    em = QueryEngine(prefer_device=True, mesh=M.make_mesh(devices[:n_dev]),
                     mesh_opts=opts)
    DP.global_programs.reset()
    rm, devm = eng.run(em, q)
    assert em.last_exec_path == "device"
    entry = next(iter(em.range_cache._entries.values()))
    assert len(entry.nrow.devices()) == n_dev
    assert devm[0]["rows"] == dev1[0]["rows"] > 0
    _equal(r1, rm, q)
    _close(eng.run(eng.host, q)[0], rm, q)
    rows = [d for d in DP.global_programs.snapshot() if d["site"] == "range"]
    assert len(rows) == 1 and rows[0]["calls"] == 1
    (memo,) = entry.query_memo.values()
    assert memo["gid"] is None


def test_sixteen_threads_over_fresh_hosts(eng):
    """More query threads than cores, a short switch interval, more
    selections than the memo holds: every answer is the host path's."""
    def q(n):
        lo = T0 + 10_000 * (n % 20)
        hosts = ", ".join(f"'h{(13 * n + 5 * d) % HOSTS}'"
                          for d in range(1 + n % 3))
        return ("SELECT ts, host, max(v) RANGE '10s' FROM cpu "
                f"WHERE host IN ({hosts}) AND ts >= {lo} AND "
                f"ts < {lo + 100000} ALIGN '10s' BY (host) ORDER BY ts, host")

    n = 96
    inst = eng.inst
    want = [eng.run(eng.host, q(k))[0].rows() for k in range(n)]
    rows0 = _took("rows")
    old_engine, inst.query_engine = (inst.query_engine,
                                     QueryEngine(prefer_device=True))
    got, errors = [None] * n, []

    def worker(w):
        try:
            for k in range(w, n, 16):
                got[k] = inst.sql(q(k)).rows()
        except Exception as e:  # noqa: BLE001 - reported below
            errors.append(repr(e))

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    memo_max, DR._MEMO_MAX = DR._MEMO_MAX, 4
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
        DR._MEMO_MAX = memo_max
        inst.query_engine = old_engine
    assert not errors, errors[:3]
    assert _took("rows") == rows0 + n
    for k in range(n):
        assert len(got[k]) == len(want[k]), q(k)
        for a, b in zip(got[k], want[k]):
            assert a[:2] == b[:2] and abs(a[2] - b[2]) < 1e-3, q(k)
