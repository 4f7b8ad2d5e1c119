"""The range grid follows its table (query/device_range.py `_upkeep`).

A query that finds its table's grid entry behind the table's version
asks storage for the rows written since and, where they are a plain
append, scatters them into the resident planes with one program; after
N appends every plane is bit-equal to `build_entry` over the same rows.
Anything that is not a plain append evicts and rebuilds, counted by
reason, and answers what the host path answers.
"""

import numpy as np
import pytest

from greptimedb_tpu.instance import Standalone
from greptimedb_tpu.parallel import mesh as M
from greptimedb_tpu.query import device_range as DR
from greptimedb_tpu.query import sessions
from greptimedb_tpu.query.executor import QueryEngine

HOSTS, TICKS, STEP = 8, 60, 10_000


def _q(op="max", where=""):
    return (f"SELECT ts, host, {op}(u) RANGE '10s' FROM cpu {where} "
            "ALIGN '10s' BY (host) ORDER BY ts, host")


def _count(outcome):
    return DR._UPKEEP.labels(outcome).value


def _values(rng, n):
    """Multiples of 1/64 under 100: exact in float32, and so are a
    cell's sums of them, as the benchmark's values are."""
    return rng.integers(0, 6400, n).astype(np.float64) / 64.0


def _write(tab, rng, ticks, hosts=range(HOSTS), invalid=0.0, offsets=(0,)):
    """Rows of `hosts` at every tick of `ticks` (and `offsets` ms into
    it), a share `invalid` of `u` null."""
    ts = np.array([t * STEP + o for _h in hosts for t in ticks
                   for o in offsets], np.int64)
    names = np.array([f"h{h}" for h in hosts for _t in ticks
                      for _o in offsets], object)
    valid = rng.random(len(ts)) >= invalid
    tab.write({"host": names}, ts,
              {"u": _values(rng, len(ts)), "v": _values(rng, len(ts))},
              field_valid=None if invalid == 0.0 else {"u": valid})


@pytest.fixture
def inst(tmp_path):
    i = Standalone(str(tmp_path), prefer_device=True, warm_start=False)
    i.execute_sql(
        "create table cpu (ts timestamp time index, host string primary "
        "key, u double, v double)")
    i.query_engine.persist_device_cache = False
    yield i
    i.close()


def _entry(engine):
    (entry,) = engine.range_cache._entries.values()
    return entry


def _planes(entry):
    out = {"nrow": entry.nrow, "imin": entry.imin, "imax": entry.imax}
    for fname, d in entry.fields.items():
        for key, arr in d.items():
            out[f"{fname}.{key}"] = arr
    return {k: np.asarray(v) for k, v in out.items()}


def _assert_bit_equal(kept, fresh):
    """Every plane of the entry that was kept up against one built from
    the same rows, over the cells both hold (the data and some spare)."""
    assert (kept.t0c, kept.res, kept.nb_data) == (
        fresh.t0c, fresh.res, fresh.nb_data)
    assert kept.n_aliased == fresh.n_aliased
    assert kept.nan_ok == fresh.nan_ok
    assert np.array_equal(kept.last_ts, fresh.last_ts)
    a, b = _planes(kept), _planes(fresh)
    assert set(a) == set(b)
    m = min(kept.nb, fresh.nb)
    assert m >= kept.nb_data
    for name in a:
        assert a[name].dtype == b[name].dtype, name
        assert np.array_equal(a[name][:, :m].view(np.int32),
                              b[name][:, :m].view(np.int32)), name


@pytest.mark.parametrize("invalid", ["none", "appended", "both"])
@pytest.mark.parametrize(
    "op", ["max", "avg", "count", "last_value", "first_value"])
def test_appends_leave_planes_bit_equal_to_a_build(inst, rng, op, invalid):
    tab = inst.catalog.table("public", "cpu")
    _write(tab, rng, range(TICKS), invalid=0.2 if invalid == "both" else 0.0)
    inst.sql(_q(op))
    engine = inst.query_engine
    assert engine.last_exec_path == "device"
    entry = _entry(engine)
    appended, rows = _count("append"), DR._UPKEEP_ROWS._default().value
    tick = TICKS
    for n in range(5):
        # a body: one tick of some hosts, or several ticks of all
        hosts = range(HOSTS) if n % 2 else rng.permutation(HOSTS)[:5]
        ticks = range(tick, tick + 1 + n % 3)
        _write(tab, rng, ticks, hosts=sorted(hosts),
               invalid=0.0 if invalid == "none" else 0.3)
        tick = ticks[-1] + 1
        inst.sql(_q(op))
        assert _entry(engine) is entry
    assert _count("append") == appended + 5
    assert DR._UPKEEP_ROWS._default().value > rows
    fresh_engine = QueryEngine(prefer_device=True)
    fresh_engine.persist_device_cache = False
    inst.query_engine = fresh_engine
    r_fresh = inst.sql(_q(op))
    inst.query_engine = engine
    assert inst.sql(_q(op)).rows() == r_fresh.rows()
    _assert_bit_equal(entry, _entry(fresh_engine))


def test_a_cell_takes_rows_of_several_appends(inst, rng, monkeypatch):
    """Cells wider than the data's interval (the cell cap refuses the
    finer grid): a cell half filled at the build takes the rest from
    two appends, and composes to what a build reads."""
    monkeypatch.setattr(DR, "_CELL_CAP", HOSTS * 3 * TICKS)
    tab = inst.catalog.table("public", "cpu")
    _write(tab, rng, range(TICKS), offsets=(0, 2_000))
    q = ("SELECT ts, host, avg(u) RANGE '10s', max(v) RANGE '10s', "
         "last_value(u) RANGE '10s', first_value(v) RANGE '10s' FROM cpu "
         "ALIGN '10s' BY (host) ORDER BY ts, host")
    inst.sql(q)
    engine = inst.query_engine
    entry = _entry(engine)
    assert entry.res == STEP
    _write(tab, rng, [TICKS - 1], offsets=(4_000, 6_000), invalid=0.3)
    inst.sql(q)
    _write(tab, rng, [TICKS - 1, TICKS], offsets=(8_000,))
    r = inst.sql(q)
    assert _entry(engine) is entry
    fresh_engine = QueryEngine(prefer_device=True)
    fresh_engine.persist_device_cache = False
    inst.query_engine = fresh_engine
    assert inst.sql(q).rows() == r.rows()
    _assert_bit_equal(entry, _entry(fresh_engine))


def _older_row(inst, tab, rng):
    _write(tab, rng, [TICKS - 1], hosts=[2], offsets=(-3_000,))


def _same_row_again(inst, tab, rng):
    _write(tab, rng, [TICKS - 1], hosts=[2])


def _new_series(inst, tab, rng):
    _write(tab, rng, [TICKS], hosts=[HOSTS + 3])


def _delete(inst, tab, rng):
    tab.delete({"host": np.array(["h1"], object)},
               np.array([5 * STEP], np.int64))


def _truncate(inst, tab, rng):
    inst.execute_sql("truncate table cpu")
    _write(inst.catalog.table("public", "cpu"), rng, range(4))


def _alter(inst, tab, rng):
    inst.execute_sql("alter table cpu add column w double")
    _write(inst.catalog.table("public", "cpu"), rng, [TICKS])


def _past_the_spare_cells(inst, tab, rng):
    _write(tab, rng, [TICKS * 3])


def _two_flushes_between(inst, tab, rng):
    """One flush is followed (`test_a_flush_between_is_followed`); a
    second puts the first one's rows out of reach by sequence."""
    _write(tab, rng, [TICKS])
    tab.flush()
    _write(tab, rng, [TICKS + 1])
    tab.flush()


@pytest.mark.parametrize("change, reason", [
    (_older_row, "out_of_order"), (_same_row_again, "out_of_order"),
    (_new_series, "new_series"), (_delete, "mutation"),
    (_truncate, "mutation"), (_alter, "mutation"),
    (_past_the_spare_cells, "capacity"), (_two_flushes_between, "flushed"),
], ids=lambda v: v.__name__.strip("_") if callable(v) else v)
def test_what_is_no_plain_append_rebuilds(inst, rng, change, reason):
    tab = inst.catalog.table("public", "cpu")
    _write(tab, rng, range(TICKS))
    q = _q("max") if change is not _alter else _q("avg")
    inst.sql(q)
    engine = inst.query_engine
    entry = _entry(engine)
    before, appended = _count("rebuild_" + reason), _count("append")
    change(inst, tab, rng)
    rd = inst.sql(q)
    assert engine.last_exec_path == "device"
    assert _count("rebuild_" + reason) == before + 1
    assert _count("append") == appended
    assert entry not in engine.range_cache._entries.values()
    inst.query_engine = QueryEngine(prefer_device=False)
    assert inst.sql(q).rows() == rd.rows()


def test_an_entry_on_a_mesh_rebuilds(inst, rng, devices):
    tab = inst.catalog.table("public", "cpu")
    _write(tab, rng, range(TICKS))
    engine = inst.query_engine = QueryEngine(
        prefer_device=True, mesh=M.make_mesh(devices[:4]),
        mesh_opts=M.MeshOptions(shard_min_series=1, shard_min_rows=1))
    engine.persist_device_cache = False
    inst.sql(_q())
    entry = _entry(engine)
    assert entry.mesh is not None
    before = _count("rebuild_mesh")
    _write(tab, rng, [TICKS])
    rd = inst.sql(_q())
    assert _count("rebuild_mesh") == before + 1
    assert _entry(engine) is not entry
    inst.query_engine = QueryEngine(prefer_device=False)
    assert inst.sql(_q()).rows() == rd.rows()


def test_memo_and_sessions_hold_before_the_appended_cells(inst, rng):
    """A repeated identical query answers from its session buffer; a
    body that meets its window makes it dispatch again and answer the
    new rows; one whose window ends before the appended cells still
    hits."""
    tab = inst.catalog.table("public", "cpu")
    _write(tab, rng, range(TICKS))
    old = _q(where=f"WHERE ts >= 0 AND ts < {20 * STEP}")
    live = _q(where=f"WHERE ts >= {40 * STEP} AND ts < {(TICKS + 5) * STEP}")
    r_old, r_live = inst.sql(old), inst.sql(live)
    reg = sessions.global_sessions
    hits = reg._hits
    assert inst.sql(old).rows() == r_old.rows()
    assert inst.sql(live).rows() == r_live.rows()
    assert reg._hits == hits + 2
    entry = _entry(inst.query_engine)
    (memo,) = entry.query_memo.values()
    assert len(memo["windows"]) == 2
    _write(tab, rng, [TICKS])
    r_live2 = inst.sql(live)
    assert reg._hits == hits + 2            # dispatched again
    assert len(r_live2.rows()) == len(r_live.rows()) + HOSTS
    assert len(entry.query_memo) == 1       # the selection survived
    assert inst.sql(old).rows() == r_old.rows()
    assert reg._hits == hits + 3            # still its buffer
    inst.query_engine = QueryEngine(prefer_device=False)
    assert inst.sql(live).rows() == r_live2.rows()


def test_a_query_overtaken_by_an_append_leaves_no_stale_record(
        inst, rng, monkeypatch):
    """Query A dispatches on the planes as they are; before it files
    what it learned (the window's record, the session's buffer) a body
    is acknowledged and query B brings the grid forward and forgets the
    windows that meet the appended cells. What A files after that must
    not answer a later query: one sent after the body's 204 holds the
    body's rows."""
    from greptimedb_tpu.telemetry import device_trace

    tab = inst.catalog.table("public", "cpu")
    _write(tab, rng, range(TICKS))
    live = _q(where=f"WHERE ts >= {40 * STEP} AND ts < {(TICKS + 5) * STEP}")
    other = _q(where=f"WHERE ts >= {30 * STEP} AND ts < {(TICKS + 5) * STEP}")
    inst.sql(other)
    real = device_trace.device_call.wait
    overtaken = []

    def wait(self, *outputs, dispatch_only=False):
        if not dispatch_only and not overtaken:
            overtaken.append(True)
            _write(tab, rng, [TICKS])
            inst.sql(other)
        return real(self, *outputs, dispatch_only=dispatch_only)

    monkeypatch.setattr(device_trace.device_call, "wait", wait)
    r_a = inst.sql(live)
    monkeypatch.undo()
    assert overtaken and _count("append") >= 1
    r_after = inst.sql(live)
    assert len(r_after.rows()) == len(r_a.rows()) + HOSTS
    inst.query_engine = QueryEngine(prefer_device=False)
    assert inst.sql(live).rows() == r_after.rows()


def test_a_second_body_compiles_nothing(inst, rng):
    """Neither the append program (one bucket, one layout) nor the
    range programs (the time axis keeps its length): a panel over the
    last thirty ticks, as a dashboard under ingest asks."""
    tab = inst.catalog.table("public", "cpu")
    _write(tab, rng, range(TICKS))

    def q(tick):
        return _q("avg", f"WHERE ts >= {(tick - 29) * STEP} "
                         f"AND ts < {(tick + 1) * STEP}")

    inst.sql(q(TICKS - 1))
    _write(tab, rng, [TICKS])
    inst.sql(q(TICKS))
    program, rows_program = DR.get_append_program(), DR.get_rows_program()[0]
    compiled = program._cache_size(), rows_program._cache_size(), \
        DR.get_program()._cache_size()
    for tick in (TICKS + 1, TICKS + 2):
        _write(tab, rng, [tick], hosts=range(tick % 3, HOSTS))
        assert inst.sql(q(tick)).rows()[-1][0] == tick * STEP
    assert (program._cache_size(), rows_program._cache_size(),
            DR.get_program()._cache_size()) == compiled


def test_rows_since_reads_by_sequence(inst, rng):
    """storage: the rows above a sequence come from the memtable's
    newest chunks, and a flush between says so."""
    tab = inst.catalog.table("public", "cpu")
    region = tab.regions[0]
    _write(tab, rng, range(10))
    v0 = tab.data_version()
    assert tab.appended_since(v0) == (None, 0, v0, None)
    _write(tab, rng, [10], hosts=[1, 2])
    _write(tab, rng, [11], hosts=[3])
    rows, appends, v1, reason = tab.appended_since(v0)
    assert reason is None and appends == 2 and v1 == tab.data_version()
    assert sorted(rows.ts.tolist()) == [10 * STEP, 10 * STEP, 11 * STEP]
    assert rows.seq.min() == v0[0][0][0]
    tab.flush()
    # one flush: the frozen memtable's rows are still read by sequence
    rows2, appends2, v2, reason = tab.appended_since(v0)
    assert reason is None and (appends2, v2) == (2, v1)
    assert sorted(rows2.ts.tolist()) == sorted(rows.ts.tolist())
    assert tab.appended_since(v1) == (None, 0, v1, None)
    assert region.rows_since(v1[0][0][0])[0] is None
    # rows on both sides of the flush come back together, in order
    _write(tab, rng, [12], hosts=[4])
    rows3, appends3, _v3, reason = tab.appended_since(v0)
    assert reason is None and appends3 == 3
    assert rows3.ts.tolist()[-1] == 12 * STEP
    s0 = v0[0][0][0]
    assert sorted(rows3.seq.tolist()) == list(range(s0, s0 + 4))
    tab.flush()
    assert tab.appended_since(v0)[3] == "flushed"
    assert tab.appended_since(v1)[3] is None


@pytest.mark.parametrize("op", ["max", "avg", "last_value"])
def test_a_flush_between_is_followed(inst, rng, op):
    """A flush between the write and the query moves the rows out of
    the memtable; the upkeep reads them from the memtable the flush
    froze and appends, and the planes are those of a build."""
    tab = inst.catalog.table("public", "cpu")
    _write(tab, rng, range(TICKS))
    inst.sql(_q(op))
    engine = inst.query_engine
    entry = _entry(engine)
    appended, flushed = _count("append"), _count("rebuild_flushed")
    _write(tab, rng, [TICKS])
    tab.flush()
    _write(tab, rng, [TICKS + 1], hosts=range(3))
    r = inst.sql(_q(op))
    assert _entry(engine) is entry
    assert (_count("append"), _count("rebuild_flushed")) == (
        appended + 1, flushed)
    fresh_engine = QueryEngine(prefer_device=True)
    fresh_engine.persist_device_cache = False
    inst.query_engine = fresh_engine
    assert inst.sql(_q(op)).rows() == r.rows()
    _assert_bit_equal(entry, _entry(fresh_engine))
