"""The selector grid follows its table under remote write
(promql/fast.py `_upkeep`).

A PromQL query that finds its table's selector grid behind the table's
version asks storage for the samples written since (`appended_since`,
narrowed to the logical table's `__table_id`) and, where they are a
plain append, writes them into the resident planes with one program,
lengthening the time axis on the device first where a sample lands past
it. After every write the kept grid answers `histogram_quantile`,
`rate` and `last_over_time` bit for bit as a grid built afresh over the
same samples, and within 1e-3 of the benchmark's float64 reference;
its planes equal a build's over the cells both hold. Anything that is
not a plain append evicts and rebuilds, counted by reason, and answers
what the host engine answers.
"""

import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.datagen import prom_hist as dg  # noqa: E402
from benchmark.datagen import prom_hist_live as live  # noqa: E402
from benchmark.lib.files import load_json, reference  # noqa: E402
from greptimedb_tpu.instance import Standalone  # noqa: E402
from greptimedb_tpu.parallel import mesh as M  # noqa: E402
from greptimedb_tpu.promql import fast as F  # noqa: E402
from greptimedb_tpu.promql.engine import PromEngine  # noqa: E402
from greptimedb_tpu.query import sessions  # noqa: E402
from greptimedb_tpu.query.grid_upkeep import LANE  # noqa: E402
from greptimedb_tpu.servers import prom_store  # noqa: E402

REF = reference(load_json(ROOT, "benchmark", "configs",
                          "prom-100k-defbuckets.json"))
STEP, WINDOW = dg.INTERVAL_MS, 300_000
INSTANCES, HELD_MIN, LIVE_MIN = 16, 10, 5
PER_REQUEST = 50        # not a multiple of 12: a request splits a scrape
SCALE = {"instances": INSTANCES, "minutes": HELD_MIN + LIVE_MIN,
         "held_minutes": HELD_MIN}


def _count(outcome):
    return F._UPKEEP.labels(outcome).value


def _rows():
    return F._UPKEEP_ROWS._default().value


@pytest.fixture
def ds():
    return live.make(np, 2**31 + 39, SCALE)


@pytest.fixture
def inst(tmp_path, ds):
    i = _serve(tmp_path, ds)
    yield i
    i.close()
    F.invalidate_cache()


def _serve(tmp_path, ds):
    """The held part loaded as the benchmark loads it (scrape 0 by
    remote write, which makes the table, the rest columnar)."""
    F.invalidate_cache()
    i = Standalone(str(tmp_path / "data"), warm_start=False)
    held = ds.scrapes
    for body in dg.scrape_bodies(np, ds, 0, np.arange(ds.instances)):
        prom_store.remote_write(i, body)
    table = i.catalog.table("public", dg.METRIC)
    n, n_le = ds.instances, len(dg.LE)
    rows = n * n_le * (held - 1)
    i._write_columns(table, {
        "instance": np.repeat(np.asarray(ds.names, object),
                              n_le * (held - 1)),
        "job": np.full(rows, dg.JOB, object),
        "le": np.tile(np.repeat(np.asarray(dg.LE, object), held - 1), n),
        "ts": np.repeat(ds.ts[:, 1:], n_le, axis=0).reshape(-1),
        "greptime_value": ds.values[:, :, 1:].reshape(-1).astype(np.float64),
    }, {})
    return i


def _write(inst, ds, r0, r1, per=PER_REQUEST):
    """Requests [r0, r1) of the live stream, `per` samples each, by
    remote write -> the samples acknowledged once they are."""
    for r in range(r0, r1):
        prom_store.remote_write(inst, live.render(np, ds, r, per))
    return min(r1 * per, ds.live_samples)


def _end(ds, acked):
    return live.panel_end(ds, acked)


QUERIES = {
    "quantile": "histogram_quantile({phi}, sum by (le) (rate(" + dg.METRIC
                + "[5m])))",
    "rate": "sum by (le) (rate(" + dg.METRIC + "[5m]))",
    "last": "sum by (instance, le) (last_over_time(" + dg.METRIC
            + "[15s]))",
}


def _ask(inst, kind, end, phi=0.9, span=1_800_000):
    """{(t, labels): value} of one query_range over (end - span, end]."""
    v, ev = PromEngine(inst).query_range(
        QUERIES[kind].format(phi=phi), end - span, end, STEP)
    out = {}
    for lab, vals, pres in zip(v.labels, v.values, v.present):
        key = tuple(sorted(lab.items()))
        for j in np.nonzero(pres)[0]:
            out[(int(ev.step_ts[j]), key)] = float(vals[j])
    return out


def _fresh(inst, kind, end, **kw):
    """The same query over a grid built afresh from the same samples."""
    saved = dict(F._CACHE._entries)
    F._CACHE._entries.clear()
    try:
        return _ask(inst, kind, end, **kw), next(
            iter(F._CACHE._entries.values()))
    finally:
        for e in F._CACHE._entries.values():
            F._CACHE._release(e)
        F._CACHE._entries.clear()
        F._CACHE._entries.update(saved)


def _entry():
    (e,) = F._CACHE._entries.values()
    return e


def _want(ds, acked, phi, end, span=1_800_000):
    """The float64 reference over the held samples and the first
    `acked` live ones."""
    ts, values = live.acked_arrays(np, ds, acked)
    steps = np.arange(end - span, end + 1, STEP, dtype=np.int64)
    with np.errstate(all="ignore"):
        rate, present = REF.extrapolated_rate(np, ts, values, steps, WINDOW)
        shape = (-1, len(dg.LE), len(steps))
        rate, present = rate.reshape(shape), present.reshape(shape)
        got = REF.histogram_quantile(np, dg.BOUNDS,
                                     *REF.sum_by_le(np, rate, present), phi)
    return {int(steps[j]): v for j, v in got.items()}


def _planes_equal(kept, fresh):
    """The kept grid's planes against a build's over the cells both
    hold; the spare cells past the data are empty."""
    assert (kept.spec.t0, kept.spec.res, kept.spec.unit) == (
        fresh.spec.t0, fresh.spec.res, fresh.spec.unit)
    assert kept.data_cells == fresh.data_cells
    assert np.array_equal(kept.last_ts, fresh.last_ts)
    m = fresh.spec.num_cells
    assert kept.spec.num_cells >= m
    for name in ("vals", "has", "tsg"):
        a, b = np.asarray(getattr(kept, name)), np.asarray(
            getattr(fresh, name))
        assert np.array_equal(a[:, :m], b), name
        assert not np.asarray(a[:, m:]).any(), name


def _check_against_fresh_and_reference(inst, ds, acked, phi=0.9,
                                       planes=True):
    end = _end(ds, acked)
    kept = {k: _ask(inst, k, end, phi=phi) for k in QUERIES}
    entry = _entry()
    for kind in QUERIES:
        fresh, fresh_entry = _fresh(inst, kind, end, phi=phi)
        assert kept[kind] == fresh, kind       # bit for bit
    if planes:
        _planes_equal(entry, fresh_entry)
    want = _want(ds, acked, phi, end)
    got = {t: v for (t, _k), v in kept["quantile"].items()}
    assert sorted(got) == sorted(want)
    for t, v in want.items():
        assert got[t] == pytest.approx(v, rel=1e-3), t
    return kept


def test_the_family_reads_zero_from_the_import():
    from greptimedb_tpu.telemetry.metrics import global_registry

    text = global_registry.render()
    for outcome in ("append", "grow", "rebuild_out_of_order",
                    "rebuild_new_series", "rebuild_irregular",
                    "rebuild_mutation", "rebuild_mesh", "rebuild_flushed"):
        assert (f'gtpu_promql_grid_upkeep_total{{outcome="{outcome}"}}'
                in text), outcome
    assert "gtpu_promql_grid_upkeep_rows_total" in text


def test_each_write_is_followed_bit_for_bit(inst, ds):
    """Request after request, each query over a grid kept up equals one
    over a grid built afresh, and the reference at 1e-3: a partly
    written last interval (a request ends inside a scrape's instances,
    and inside one instance's twelve `le`) included."""
    _ask(inst, "quantile", _end(ds, 0))
    entry = _entry()
    appended, rows, grown = _count("append"), _rows(), _count("grow")
    acked, r = 0, 0
    for step, phi in zip((1, 2, 3, 5), (0.5, 0.9, 0.95, 0.99)):
        acked = _write(inst, ds, r, r + step)
        r += step
        _check_against_fresh_and_reference(inst, ds, acked, phi)
        assert _entry() is entry
    assert acked % len(dg.LE) != 0 and acked % (
        ds.instances * len(dg.LE)) != 0
    assert _count("append") + _count("grow") == appended + grown + 4
    assert _rows() == rows + acked


def test_growth_past_the_axis_keeps_the_answers(inst, ds):
    """The held grid spans less than one lane tile, so it has no spare
    cells: the first live scrape past its end lengthens the axis on the
    device (counted `grow`), by the capacity rule, once; a grid that
    sees no write keeps its shape."""
    _ask(inst, "quantile", _end(ds, 0))
    entry = _entry()
    built = entry.spec.num_cells
    assert built == entry.data_cells <= LANE   # no spare cells up front
    assert entry.nbytes == entry.s_pad * built * 9
    assert np.asarray(entry.vals).shape[1] == built
    grown = _count("grow")
    per_scrape = ds.instances * len(dg.LE)
    acked = _write(inst, ds, 0, 2 * per_scrape // PER_REQUEST + 1)
    _ask(inst, "rate", _end(ds, acked))
    assert _count("grow") == grown + 1
    assert entry.spec.num_cells == F.cell_capacity(
        entry.data_cells, lambda c: True) > built
    _check_against_fresh_and_reference(inst, ds, acked)
    assert entry.nbytes == entry.s_pad * entry.spec.num_cells * 9
    acked = _write(inst, ds, 2 * per_scrape // PER_REQUEST + 1,
                   ds.live_samples // PER_REQUEST + 1)
    assert acked == ds.live_samples
    rebuilds = sum(_count("rebuild_" + r) for r in F._REBUILD_REASONS)
    _check_against_fresh_and_reference(inst, ds, acked)
    # the quarter more that 53 cells get is 13: the rest of the live
    # part takes one growth more, and no rebuild
    assert _count("grow") == grown + 2
    assert sum(_count("rebuild_" + r) for r in F._REBUILD_REASONS) == rebuilds


def test_a_held_grid_past_one_tile_takes_the_live_scrapes_as_appends(
        tmp_path):
    """45 held minutes span 183 cells, built in two whole lane tiles
    (256): every live scrape of the next 15 minutes, up to cell 243,
    lands in the empty cells after the data, counted `append`, never
    `grow`, and the kept grid answers as a fresh build."""
    ds_h = live.make(np, 2**31 + 41, {"instances": 4, "minutes": 60,
                                      "held_minutes": 45})
    i = _serve(tmp_path, ds_h)
    try:
        _ask(i, "quantile", _end(ds_h, 0))
        entry = _entry()
        assert (entry.data_cells, entry.spec.num_cells) == (183, 256)
        grown, appended = _count("grow"), _count("append")
        per = 480
        last = live.requests(ds_h, per)
        acked, r, asked = 0, 0, 0
        while r < last:
            acked = _write(i, ds_h, r, min(r + 2, last), per=per)
            r += 2
            _ask(i, "rate", _end(ds_h, acked))
            asked += 1
        assert acked == ds_h.live_samples
        assert _entry() is entry
        assert (entry.data_cells, entry.spec.num_cells) == (243, 256)
        assert _count("grow") == grown
        assert _count("append") == appended + asked
        _check_against_fresh_and_reference(i, ds_h, acked)
    finally:
        i.close()
        F.invalidate_cache()


def test_a_flush_between_write_and_query_is_followed(inst, ds):
    _ask(inst, "quantile", _end(ds, 0))
    entry = _entry()
    flushed, appended = _count("rebuild_flushed"), _count("append")
    acked = _write(inst, ds, 0, 3)
    inst.catalog.table("public", dg.METRIC).flush()
    acked = _write(inst, ds, 3, 5)
    _check_against_fresh_and_reference(inst, ds, acked)
    assert _entry() is entry
    assert _count("rebuild_flushed") == flushed
    assert _count("append") + _count("grow") > appended


def test_a_counter_reset_in_the_live_part(tmp_path):
    """An instance that restarts inside the live part: its counters
    fall back to 0, and `rate` adds what they held."""
    seed = next(s for s in range(2**31 + 100, 2**31 + 400)
                if live.make(np, s, SCALE).resets_live)
    ds_r = live.make(np, seed, SCALE)
    i = _serve(tmp_path, ds_r)
    try:
        _ask(i, "quantile", _end(ds_r, 0))
        acked = _write(i, ds_r, 0, ds_r.live_samples // PER_REQUEST + 1)
        _check_against_fresh_and_reference(i, ds_r, acked)
    finally:
        i.close()
        F.invalidate_cache()


def test_sessions_and_windows_hold_before_the_written_cells(inst, ds):
    """A repeated panel answers from its session buffer; a write whose
    samples reach its window makes it dispatch again; a panel whose
    window ends before the written cells still hits."""
    _ask(inst, "rate", _end(ds, 0))
    acked = _write(inst, ds, 0, 4)
    _ask(inst, "rate", _end(ds, acked))     # the axis grows: all purged
    assert _entry().spec.num_cells > _entry().data_cells
    end0 = _end(ds, acked)
    old_end = end0 - 10 * STEP
    _ask(inst, "rate", old_end)
    first = _ask(inst, "rate", end0)
    reg = sessions.global_sessions
    hits = reg._hits
    assert _ask(inst, "rate", end0) == first
    assert reg._hits == hits + 1
    acked = _write(inst, ds, 4, 6)
    entry = _entry()
    assert _ask(inst, "rate", old_end)           # its buffer still stands
    assert reg._hits == hits + 2
    again = _ask(inst, "rate", end0)             # a window the write met
    assert reg._hits == hits + 2
    assert again != first
    fresh, _e = _fresh(inst, "rate", end0)
    assert again == fresh
    assert all(w[6][0] < entry.data_cells for w in entry.win_cache.values())
    assert acked > 0


def test_an_overtaken_query_leaves_no_stale_record(inst, ds, monkeypatch):
    """Query A dispatches on the planes as they are; before it files its
    session buffer, a request is acknowledged and query B brings the
    grid forward and forgets the windows the write met. What A files
    after that must not answer a later query sent after the write."""
    from greptimedb_tpu.telemetry import device_trace

    end = _end(ds, 0)
    _ask(inst, "rate", end)
    sessions.global_sessions.clear()
    real = device_trace.device_call.wait
    overtaken = []

    def wait(self, *outputs, dispatch_only=False):
        if not dispatch_only and not overtaken:
            overtaken.append(True)
            _write(inst, ds, 0, 2)
            _ask(inst, "quantile", end)
        return real(self, *outputs, dispatch_only=dispatch_only)

    monkeypatch.setattr(device_trace.device_call, "wait", wait)
    before = _ask(inst, "rate", end)
    monkeypatch.undo()
    assert overtaken
    after = _ask(inst, "rate", end)
    fresh, _e = _fresh(inst, "rate", end)
    assert after == fresh and after != before


def test_a_grid_that_sees_no_write_keeps_its_shape_and_bytes(inst, ds):
    """Built within one lane tile, the grid holds its data's cells and
    no more, and keeps that shape and its bytes while no write comes."""
    end = _end(ds, 0)
    _ask(inst, "quantile", end)
    entry = _entry()
    shape, nbytes = np.asarray(entry.vals).shape, entry.nbytes
    for phi in (0.5, 0.99):
        _ask(inst, "quantile", end - 4 * STEP, phi=phi)
    assert _entry() is entry
    assert (np.asarray(entry.vals).shape, entry.nbytes) == (shape, nbytes)
    assert shape[1] == entry.data_cells <= LANE


def test_another_metric_on_the_physical_table(inst, ds):
    """Remote write puts every metric on one physical region: a write
    to another metric moves the table's version; the grid stays right,
    whatever its outcome is named."""
    end = _end(ds, 0)
    base = _ask(inst, "rate", end)
    body = dg.snappy_block(dg.write_request(
        [dg._label("__name__", "up") + dg._label("instance", ds.names[0])
         + dg._label("job", dg.JOB)], [0], [1.0], [int(ds.ts[0, -1])]))
    prom_store.remote_write(inst, body)
    assert _ask(inst, "rate", end) == base
    acked = _write(inst, ds, 0, 3)
    # (a build now holds the other metric's series too, as an empty row)
    _check_against_fresh_and_reference(inst, ds, acked, planes=False)


def _older_sample(inst, ds):
    t = int(ds.ts[1, ds.scrapes - 3])
    return [(1, 0, 5.0, t)]


def _same_sample_again(inst, ds):
    return [(1, 0, float(ds.values[1, 0, -1]), int(ds.ts[1, -1]))]


def _second_sample_in_a_cell(inst, ds):
    return [(1, 0, float(ds.values[1, 0, -1]) + 1, int(ds.ts[1, -1]) + 5)]


def _new_series(inst, ds):
    return [(ds.instances, 0, 1.0, int(ds.ts[0, -1]) + STEP)]


@pytest.mark.parametrize("change, reason", [
    (_older_sample, "out_of_order"), (_same_sample_again, "out_of_order"),
    (_second_sample_in_a_cell, "irregular"), (_new_series, "new_series"),
    ("delete", "mutation"), ("flush_twice", "flushed"),
], ids=lambda v: v.__name__.strip("_") if callable(v) else v)
def test_what_is_no_plain_append_rebuilds(inst, ds, change, reason):
    end = _end(ds, 0)
    _ask(inst, "quantile", end)
    entry = _entry()
    before, appended = _count("rebuild_" + reason), _count("append")
    table = inst.catalog.table("public", dg.METRIC)
    if change == "delete":
        table.delete({"instance": np.array([ds.names[2]], object),
                      "job": np.array([dg.JOB], object),
                      "le": np.array([dg.LE[0]], object)},
                     np.array([int(ds.ts[2, 5])], np.int64))
    elif change == "flush_twice":
        _write(inst, ds, 0, 1)
        table.flush()
        _write(inst, ds, 1, 2)
        table.flush()
    else:
        heads = dg.series_heads(ds) + [
            dg._label("__name__", dg.METRIC)
            + dg._label("instance", "10.9.9.9:8080")
            + dg._label("job", dg.JOB) + dg._label("le", dg.LE[0])]
        samples = change(inst, ds)
        prom_store.remote_write(inst, dg.snappy_block(dg.write_request(
            heads, [i * len(dg.LE) + le if i < ds.instances else len(heads) - 1
                    for i, le, _v, _t in samples],
            [v for _i, _le, v, _t in samples],
            [t for _i, _le, _v, t in samples])))
    span = 4 * STEP     # the host engine is slow over long ranges
    got = {k: _ask(inst, k, end, span=span) for k in ("quantile", "rate")}
    assert _count("rebuild_" + reason) == before + 1
    assert _count("append") == appended
    assert _entry() is not entry
    fast = F._FAST_HITS.labels("hit").value
    host = {}
    for kind in ("quantile", "rate"):
        F.invalidate_cache()
        import unittest.mock as mock

        with mock.patch.object(F._CACHE, "get_entry", lambda *a, **k: None):
            host[kind] = _ask(inst, kind, end, span=span)
    assert F._FAST_HITS.labels("hit").value == fast
    for kind in got:
        assert set(got[kind]) == set(host[kind]), kind
        for k, v in host[kind].items():
            assert got[kind][k] == pytest.approx(v, rel=1e-4, abs=1e-9), k


def test_an_entry_on_a_mesh_rebuilds(inst, ds, devices):
    from greptimedb_tpu.query.executor import QueryEngine

    inst.query_engine = QueryEngine(
        prefer_device=True, mesh=M.make_mesh(devices[:4]),
        mesh_opts=M.MeshOptions(shard_min_series=1, shard_min_rows=1))
    end = _end(ds, 0)
    _ask(inst, "rate", end)
    entry = _entry()
    assert entry.mesh is not None
    before = _count("rebuild_mesh")
    acked = _write(inst, ds, 0, 2)
    got = _ask(inst, "rate", end)
    assert _count("rebuild_mesh") == before + 1
    assert _entry() is not entry
    assert acked > 0 and got


def test_writes_and_panels_from_many_threads(inst, ds):
    """More threads than cores, a short switch interval: one writer
    sends the live requests while the others ask panels over the last
    half hour, each of them bringing the grid forward or reading its
    planes. No query fails (no donated plane is read), and once the
    writer is done the kept grid answers as a fresh build."""
    import threading

    _ask(inst, "rate", _end(ds, 0))
    errors, done = [], threading.Event()
    n_requests = 24

    def writer():
        try:
            _write(inst, ds, 0, n_requests)
        except Exception as e:  # noqa: BLE001 - reported below
            errors.append(e)
        finally:
            done.set()

    def reader(k):
        try:
            while not done.is_set():
                _ask(inst, ("rate", "quantile")[k % 2], _end(ds, 0),
                     phi=0.5, span=10 * STEP)
        except Exception as e:  # noqa: BLE001 - reported below
            errors.append(e)

    threads = [threading.Thread(target=writer)] + [
        threading.Thread(target=reader, args=(k,))
        for k in range(2 * (os.cpu_count() or 4))]
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=300)
    finally:
        sys.setswitchinterval(old)
    assert not any(t.is_alive() for t in threads)
    assert not errors, errors[:3]
    acked = min(n_requests * PER_REQUEST, ds.live_samples)
    _check_against_fresh_and_reference(inst, ds, acked)
