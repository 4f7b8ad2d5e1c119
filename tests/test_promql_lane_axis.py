"""The selector grid's time axis in whole lane tiles (`lane_cells`).

A grid whose data spans more than one lane tile (128 cells) is built
with its planes rounded up to a whole number of tiles: the cells after
the data are empty, as a build leaves any cell no sample reaches, so
every query answers bit for bit as over planes of the exact count,
whether its windows end inside the data, in its last cell, in the empty
cells or past the axis. A count within one tile stays as it is, and so
does one whose rounded count the budget refuses.
`gtpu_promql_grid_build_cells_total{kind}` counts what was built.
"""

import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.datagen import prom_hist as dg  # noqa: E402
from greptimedb_tpu.instance import Standalone  # noqa: E402
from greptimedb_tpu.promql import fast as F  # noqa: E402
from greptimedb_tpu.promql.engine import PromEngine  # noqa: E402
from greptimedb_tpu.query.grid_upkeep import (  # noqa: E402
    LANE, cell_capacity, lane_cells,
)
from test_promql_staggered_scrapes import load, one_phase_table  # noqa: E402

STEP = dg.INTERVAL_MS
SPAN = 1_800_000
# the last data cell of a 60 minute table ends here (241 scrapes, the
# origin one interval before the first: 243 cells)
LAST = dg.BASE_MS + 241 * STEP


def _cells(kind):
    return F._BUILD_CELLS.labels(kind).value


@pytest.mark.parametrize("n, want", [
    (1, 1), (53, 53), (128, 128), (129, 256), (183, 256), (243, 256),
    (256, 256), (257, 384),
])
def test_lane_cells(n, want):
    assert lane_cells(n) == want


def test_cell_capacity_keeps_its_rule():
    """A quarter more, to whole lane tiles past one; `nb` itself where
    the rounded count does not fit."""
    for nb in range(1, 4000):
        cap = nb + max(nb // 4, 1)
        if cap > LANE:
            cap = -(-cap // LANE) * LANE
        assert cell_capacity(nb, lambda c: True) == cap, nb
        assert cell_capacity(nb, lambda c, nb=nb: c == nb) == nb


def test_the_cells_family_reads_zero_from_the_import():
    from greptimedb_tpu.telemetry.metrics import global_registry

    text = global_registry.render()
    for kind in ("data", "lane_pad"):
        assert f'gtpu_promql_grid_build_cells_total{{kind="{kind}"}}' in text


@pytest.fixture(scope="module")
def hour(tmp_path_factory):
    """16 instances x 12 buckets, 60 minutes of staggered scrapes: the
    accepted PromQL cell's 243 cells."""
    F.invalidate_cache()
    inst = Standalone(str(tmp_path_factory.mktemp("lane") / "data"),
                      warm_start=False)
    load(inst, dg.make(np, 2**31 + 43, {"instances": 16, "minutes": 60}))
    try:
        yield inst
    finally:
        inst.close()
        F.invalidate_cache()


QUERIES = {
    "quantile": f"histogram_quantile(0.9, sum by (le) (rate({dg.METRIC}"
                "[5m])))",
    "rate": f"sum by (le) (rate({dg.METRIC}[5m]))",
    "instant": f"sum by (instance, le) ({dg.METRIC})",
    "topk": f"topk(5, rate({dg.METRIC}[5m]))",
}
# where the panel's last window ends: inside the data, in its last
# cell, in the empty cells after it, past the lane-rounded axis
ENDS = {"inside": LAST - 30 * STEP, "last_cell": LAST,
        "empty_cells": LAST + 8 * STEP, "past_axis": LAST + 20 * STEP}


def _answer(inst, query, end, exact):
    """{labels: (values, present)} of a query over a grid built afresh,
    at the exact count where `exact`, and the entry it read."""
    F.invalidate_cache()
    hit0 = F._FAST_HITS.labels("hit").value
    with pytest.MonkeyPatch.context() as m:
        if exact:
            m.setattr(F, "lane_cells", lambda n: n)
        v, _ev = PromEngine(inst).query_range(query, end - SPAN, end, STEP)
    assert F._FAST_HITS.labels("hit").value == hit0 + 1     # the device's
    (entry,) = F._CACHE._entries.values()
    return {tuple(sorted(lab.items())): (np.asarray(v.values[i]),
                                         np.asarray(v.present[i]))
            for i, lab in enumerate(v.labels)}, entry


@pytest.mark.parametrize("where", list(ENDS))
@pytest.mark.parametrize("kind", list(QUERIES))
def test_lane_rounded_grid_answers_bit_for_bit(hour, kind, where):
    end = ENDS[where]
    got, entry = _answer(hour, QUERIES[kind], end, exact=False)
    want, exact = _answer(hour, QUERIES[kind], end, exact=True)
    assert (entry.spec.num_cells, entry.data_cells) == (256, 243)
    assert (exact.spec.num_cells, exact.data_cells) == (243, 243)
    assert np.asarray(entry.vals).shape[1] == 256
    assert entry.nbytes == entry.s_pad * 256 * 9
    assert (entry.spec.t0, entry.spec.res, entry.spec.unit) == (
        exact.spec.t0, exact.spec.res, exact.spec.unit)
    for name in ("vals", "has", "tsg"):
        a = np.asarray(getattr(entry, name))
        assert np.array_equal(a[:, :243], np.asarray(getattr(exact, name)))
        assert not a[:, 243:].any(), name
    assert set(got) == set(want), kind
    assert any(p.any() for _v, p in got.values())
    for key, (v, p) in want.items():
        gv, gp = got[key]
        assert np.array_equal(gp, p), (kind, key)
        assert np.array_equal(np.where(gp, gv, 0), np.where(p, v, 0),
                              equal_nan=True), (kind, key)


def _recorded_uploads(monkeypatch):
    """The attributes of every `device.upload` span the build opens."""
    seen = []
    real = F.tracing.child_span

    def child_span(name, *a, **attrs):
        if name == "device.upload":
            seen.append(attrs)
        return real(name, *a, **attrs)

    monkeypatch.setattr(F.tracing, "child_span", child_span)
    return seen


def test_the_build_counts_its_cells_and_says_them_on_its_span(
        hour, monkeypatch):
    uploads = _recorded_uploads(monkeypatch)
    data, pad = _cells("data"), _cells("lane_pad")
    _answer(hour, QUERIES["rate"], LAST, exact=False)
    assert (_cells("data"), _cells("lane_pad")) == (data + 243, pad + 13)
    _answer(hour, QUERIES["rate"], LAST, exact=True)
    assert (_cells("data"), _cells("lane_pad")) == (data + 486, pad + 13)
    assert [(u["cells"], u["cells_data"], u["upload_bytes"])
            for u in uploads] == [(256, 243, 256 * 256 * 9),
                                  (243, 243, 256 * 243 * 9)]
    assert all(u["site"] == "promql_grid" for u in uploads)


@pytest.fixture()
def inst(tmp_path):
    F.invalidate_cache()
    s = Standalone(str(tmp_path / "data"), warm_start=False)
    yield s
    s.close()
    F.invalidate_cache()


@pytest.mark.parametrize("n, budget, cells", [
    (100, None, 101),       # within one tile: as it is
    (150, None, 256),       # past it: two whole tiles
    # 8 series rows x 9 B: half of 28,800 B holds 200 cells, so the
    # rounded 256 does not fit and the build keeps its 151
    (150, 28_800, 151),
], ids=["one-tile", "rounded", "budget-keeps-nc"])
def test_the_rule_engages_past_one_tile_where_the_budget_allows(
        inst, monkeypatch, n, budget, cells):
    table, ts = one_phase_table(inst, t0=7_000, step_ms=1_000, n=n)
    if budget is not None:
        monkeypatch.setenv("GREPTIMEDB_TPU_PROMQL_CACHE_BYTES", str(budget))
    data, pad = _cells("data"), _cells("lane_pad")
    entry = F._CACHE.get_entry(table, "greptime_value")
    assert not entry.refused
    assert (entry.data_cells, entry.spec.num_cells) == (n + 1, cells)
    assert np.asarray(entry.vals).shape == (entry.s_pad, cells)
    assert entry.nbytes == entry.s_pad * cells * 9
    assert (_cells("data"), _cells("lane_pad")) == (
        data + n + 1, pad + cells - n - 1)
    hit0 = F._FAST_HITS.labels("hit").value
    val, _ev = PromEngine(inst).query_range(
        "sum(rate(req_total[10s]))", int(ts[20]), int(ts[-1]) + 30_000,
        5_000)
    assert F._FAST_HITS.labels("hit").value == hit0 + 1
    assert val.present.any()


def test_a_refused_span_counts_no_cells(inst, monkeypatch):
    table, _ts = one_phase_table(inst, t0=7_000, step_ms=1_000, n=150)
    monkeypatch.setenv("GREPTIMEDB_TPU_PROMQL_CACHE_BYTES", "1024")
    data, pad = _cells("data"), _cells("lane_pad")
    assert F._CACHE.get_entry(table, "greptime_value").refused == "budget"
    assert (_cells("data"), _cells("lane_pad")) == (data, pad)
