"""Device RANGE execution (query/device_range.py) vs the host path.

The device path runs the same RANGE plans over HBM-resident per-cell
partial-state grids (the page-cache analog of the reference's hot datanode,
/root/reference/src/query/src/range_select/plan.rs); results must agree
with the host NumPy path up to f32 accumulation.
"""

import numpy as np
import pytest

from greptimedb_tpu.instance import Standalone
from greptimedb_tpu.query.executor import QueryEngine


@pytest.fixture
def inst(tmp_path):
    i = Standalone(str(tmp_path))
    yield i
    i.close()


@pytest.fixture
def cpu(inst, rng):
    inst.execute_sql(
        "create table cpu (ts timestamp time index, host string primary key,"
        " region string primary key, u double, v double)"
    )
    n_hosts, t = 16, 400
    tab = inst.catalog.table("public", "cpu")
    ts = np.tile(np.arange(t) * 1000, n_hosts).astype(np.int64)
    hosts = np.repeat([f"h{i}" for i in range(n_hosts)], t).astype(object)
    regions = np.repeat(
        [f"r{i % 3}" for i in range(n_hosts)], t
    ).astype(object)
    u = rng.random(n_hosts * t) * 100
    v = rng.random(n_hosts * t) * 10
    valid = rng.random(n_hosts * t) > 0.05
    tab.write({"host": hosts, "region": regions}, ts, {"u": u, "v": v},
              field_valid={"u": valid})
    return inst


QUERIES = [
    "SELECT ts, host, avg(u) RANGE '10s' FROM cpu ALIGN '10s' BY (host) "
    "ORDER BY ts, host",
    "SELECT ts, region, sum(u) RANGE '20s', max(v) RANGE '20s', "
    "min(u) RANGE '20s' FROM cpu ALIGN '10s' BY (region) "
    "ORDER BY ts, region",
    "SELECT ts, count(u) RANGE '30s', count(*) RANGE '30s' FROM cpu "
    "ALIGN '30s' BY () ORDER BY ts",
    "SELECT ts, host, last_value(u) RANGE '25s', first_value(v) RANGE '25s' "
    "FROM cpu ALIGN '5s' BY (host) ORDER BY ts, host LIMIT 400",
    "SELECT ts, host, stddev(u) RANGE '40s' FROM cpu "
    "WHERE ts >= 100000 AND ts < 300000 ALIGN '20s' BY (host) "
    "ORDER BY ts, host",
    "SELECT ts, region, avg(u) RANGE '10s' FILL PREV FROM cpu "
    "WHERE host != 'h3' ALIGN '10s' BY (region) ORDER BY ts, region",
    "SELECT ts, avg(u) RANGE '1m' FILL LINEAR FROM cpu WHERE host = 'h1' "
    "ALIGN '30s' ORDER BY ts",
    "SELECT ts, host, var_pop(u) RANGE '30s', avg(v) RANGE '30s' AS av "
    "FROM cpu ALIGN '15s' BY (host) HAVING av > 4 ORDER BY ts, host",
]


def _compare(rh, rd, q):
    assert rh.names == rd.names
    assert rh.num_rows == rd.num_rows, q
    for i in range(len(rh.names)):
        a, b = rh.cols[i], rd.cols[i]
        assert (a.valid_mask == b.valid_mask).all(), (q, rh.names[i])
        if a.values.dtype == object:
            assert (a.values == b.values).all(), (q, rh.names[i])
        else:
            m = a.valid_mask
            assert np.allclose(
                np.asarray(a.values, float)[m],
                np.asarray(b.values, float)[m],
                rtol=2e-4, atol=1e-3,
            ), (q, rh.names[i])


@pytest.mark.parametrize("q", QUERIES)
def test_device_range_matches_host(cpu, q):
    inst = cpu
    inst.query_engine = QueryEngine(prefer_device=False)
    rh = inst.sql(q)
    inst.query_engine = QueryEngine(prefer_device=True)
    rd = inst.sql(q)
    assert inst.query_engine.last_exec_path == "device", q
    _compare(rh, rd, q)


def test_device_range_cache_hit_and_invalidation(cpu):
    inst = cpu
    inst.query_engine = QueryEngine(prefer_device=True)
    q = QUERIES[0]
    r1 = inst.sql(q)
    cache = inst.query_engine.range_cache
    assert len(cache._entries) == 1
    entry = next(iter(cache._entries.values()))
    r2 = inst.sql(q)
    assert next(iter(cache._entries.values())) is entry  # reused
    assert r1.rows() == r2.rows()
    # a write bumps the data version; a row newer than any the entry
    # holds of its series is a plain append: the entry is brought
    # forward in place (query/device_range.py `_upkeep`), not evicted
    appended = _upkeep_count("append")
    inst.execute_sql(
        "insert into cpu (ts, host, region, u, v) "
        "values (400000, 'h0', 'r0', 50.0, 5.0)"
    )
    r3 = inst.sql(q)
    assert next(iter(cache._entries.values())) is entry
    assert entry.version == inst.catalog.table(
        "public", "cpu").data_version()
    assert _upkeep_count("append") == appended + 1
    assert r3.num_rows == r1.num_rows + 1
    assert r3.rows()[-1][:2] == [400000, "h0"]
    assert float(r3.rows()[-1][2]) == 50.0
    # an overwrite is not: the entry is evicted and built again
    rebuilt = _upkeep_count("rebuild_out_of_order")
    inst.execute_sql(
        "insert into cpu (ts, host, region, u, v) "
        "values (400000, 'h0', 'r0', 70.0, 5.0)"
    )
    r4 = inst.sql(q)
    assert next(iter(cache._entries.values())) is not entry
    assert _upkeep_count("rebuild_out_of_order") == rebuilt + 1
    assert float(r4.rows()[-1][2]) == 70.0


def _upkeep_count(outcome):
    from greptimedb_tpu.query.device_range import _UPKEEP

    return _UPKEEP.labels(outcome).value


def test_device_range_falls_back_on_residual(cpu):
    inst = cpu
    inst.query_engine = QueryEngine(prefer_device=True)
    # residual filter on a field value is not expressible over partials
    r = inst.sql(
        "SELECT ts, host, avg(u) RANGE '10s' FROM cpu WHERE v > 5 "
        "ALIGN '10s' BY (host) ORDER BY ts, host"
    )
    assert inst.query_engine.last_exec_path == "host"
    assert r.num_rows > 0


def test_first_last_tiebreak_matches_host(inst, rng):
    """BY coarser than series + fully aligned timestamps (typical TSBS
    shape): equal-ts ties must resolve identically on host and device
    ((ts, sid) lexicographic — ADVICE r2 medium)."""
    inst.execute_sql(
        "create table m (ts timestamp time index, host string primary key,"
        " dc string primary key, x double)"
    )
    tab = inst.catalog.table("public", "m")
    n_hosts, t = 12, 50
    ts = np.tile(np.arange(t) * 1000, n_hosts).astype(np.int64)  # aligned
    hosts = np.repeat([f"h{i:02d}" for i in range(n_hosts)], t).astype(object)
    dcs = np.repeat([f"d{i % 2}" for i in range(n_hosts)], t).astype(object)
    x = rng.random(n_hosts * t) * 100
    tab.write({"host": hosts, "dc": dcs}, ts, {"x": x})
    q = (
        "SELECT ts, dc, last_value(x) RANGE '10s', first_value(x) "
        "RANGE '10s' FROM m ALIGN '10s' BY (dc) ORDER BY ts, dc"
    )
    inst.query_engine = QueryEngine(prefer_device=False)
    rh = inst.sql(q)
    inst.query_engine = QueryEngine(prefer_device=True)
    rd = inst.sql(q)
    assert inst.query_engine.last_exec_path == "device"
    # exact equality at f32 (device value precision): the winning row must
    # be the same row, not merely a close value
    for i in range(len(rh.names)):
        if rh.cols[i].values.dtype != object:
            np.testing.assert_array_equal(
                np.asarray(rh.cols[i].values, np.float64).astype(np.float32),
                np.asarray(rd.cols[i].values, np.float64).astype(np.float32),
                err_msg=rh.names[i],
            )


def test_long_span_exact(inst, rng):
    """Spans beyond 2^31 ms stay exact on device: (cell, intra) int32
    pairs replace the lossy global tick (ADVICE r2 low)."""
    inst.execute_sql(
        "create table lng (ts timestamp time index, host string primary key,"
        " x double)"
    )
    tab = inst.catalog.table("public", "lng")
    # ~50 days at irregular offsets; interval gcd stays 1000ms
    base = np.arange(200, dtype=np.int64) * (25 * 3600 * 1000) + 13_000
    ts = np.concatenate([base, base + 1000])
    hosts = np.asarray(["a"] * 200 + ["b"] * 200, object)
    x = rng.random(400) * 10
    tab.write({"host": hosts}, ts, {"x": x})
    assert ts.max() - ts.min() > 2**31
    q = (
        "SELECT ts, last_value(x) RANGE '1d', max(x) RANGE '1d' FROM lng "
        "ALIGN '1d' BY () ORDER BY ts"
    )
    inst.query_engine = QueryEngine(prefer_device=False)
    rh = inst.sql(q)
    inst.query_engine = QueryEngine(prefer_device=True)
    rd = inst.sql(q)
    assert inst.query_engine.last_exec_path == "device"
    assert rh.num_rows == rd.num_rows
    for i in range(len(rh.names)):
        np.testing.assert_allclose(
            np.asarray(rh.cols[i].values, float),
            np.asarray(rd.cols[i].values, float), rtol=1e-6,
            err_msg=rh.names[i],
        )


def test_where_ts_far_outside_grid(inst, rng):
    """Cell-aligned WHERE ts bounds billions of cells away from the grid
    must not overflow the int32 device scalars."""
    inst.execute_sql(
        "create table tiny (ts timestamp time index, host string "
        "primary key, x double)"
    )
    tab = inst.catalog.table("public", "tiny")
    ts = np.arange(2000, dtype=np.int64)  # 1ms interval -> res=1ms
    tab.write({"host": np.asarray(["a"] * 2000, object)}, ts,
              {"x": rng.random(2000)})
    inst.query_engine = QueryEngine(prefer_device=True)
    r = inst.sql(
        "SELECT ts, max(x) RANGE '1s' FROM tiny WHERE ts >= 6000000000 "
        "ALIGN '1s' BY ()"
    )
    assert r.num_rows == 0
    r = inst.sql(
        "SELECT ts, max(x) RANGE '1s' FROM tiny WHERE ts < 6000000000 "
        "ALIGN '1s' BY () ORDER BY ts"
    )
    assert r.num_rows == 2


def test_byte_budget_gates_build_and_growth(cpu):
    """Cache HBM accounting: too-small budgets refuse the build (host
    fallback); growth of a cached entry respects the aggregate budget."""
    from greptimedb_tpu.query.device_range import DeviceRangeCache

    inst = cpu
    inst.query_engine = QueryEngine(prefer_device=True)
    inst.query_engine.range_cache = DeviceRangeCache(byte_budget=1000)
    r = inst.sql(QUERIES[0])
    assert inst.query_engine.last_exec_path == "host"  # refused: too big
    assert r.num_rows > 0

    # budget fits the avg-states build but not growth to first/last states
    inst.query_engine = QueryEngine(prefer_device=True)
    cache = inst.query_engine.range_cache
    r1 = inst.sql(QUERIES[0])
    assert inst.query_engine.last_exec_path == "device"
    entry = next(iter(cache._entries.values()))
    assert cache.total_bytes() == entry.bytes() > 0
    cache.byte_budget = entry.bytes()  # no headroom left
    inst.sql(
        "SELECT ts, host, last_value(u) RANGE '10s' FROM cpu "
        "ALIGN '10s' BY (host)"
    )
    assert inst.query_engine.last_exec_path == "host"  # growth refused
    assert cache.total_bytes() <= cache.byte_budget


def test_device_range_empty_matcher(cpu):
    inst = cpu
    inst.query_engine = QueryEngine(prefer_device=True)
    r = inst.sql(
        "SELECT ts, host, avg(u) RANGE '10s' FROM cpu WHERE host = 'nope' "
        "ALIGN '10s' BY (host)"
    )
    assert r.num_rows == 0
