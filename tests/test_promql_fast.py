"""PromQL selector-grid fast path: equivalence with the generic engine,
cache invalidation, and fallback behavior (VERDICT r2 task #2)."""

import numpy as np
import pytest

from greptimedb_tpu.instance import Standalone
from greptimedb_tpu.promql import fast as F
from greptimedb_tpu.promql.engine import PromEngine, VectorValue

T0 = 1_700_000_000_000


@pytest.fixture()
def inst(tmp_path):
    F.invalidate_cache()
    s = Standalone(str(tmp_path / "data"))
    yield s
    s.close()
    F.invalidate_cache()


def setup_metrics(inst, *, n_hosts=6, n=41, step_ms=15_000):
    inst.sql(
        "CREATE TABLE req_total (host STRING, dc STRING, "
        "greptime_value DOUBLE, ts TIMESTAMP TIME INDEX, "
        "PRIMARY KEY (host, dc))"
    )
    table = inst.catalog.table("public", "req_total")
    ts = T0 + np.arange(n) * step_ms
    rng = np.random.default_rng(7)
    for h in range(n_hosts):
        vals = np.cumsum(rng.uniform(0, 5, n))
        table.write(
            {"host": np.full(n, f"h{h}", object),
             "dc": np.full(n, f"dc{h % 2}", object)},
            ts,
            {"greptime_value": vals},
        )
    return ts


def run_both(inst, promql, start, end, step):
    eng = PromEngine(inst)
    fast_val, ev = eng.query_range(promql, start, end, step)

    real = F.try_fast
    F_disabled = lambda *a, **k: None  # noqa: E731
    F.try_fast = F_disabled
    try:
        slow_val, _ = PromEngine(inst).query_range(promql, start, end, step)
    finally:
        F.try_fast = real
    return fast_val, slow_val, ev


def as_map(v: VectorValue):
    out = {}
    for i, lab in enumerate(v.labels):
        key = tuple(sorted(lab.items()))
        out[key] = (v.values[i], v.present[i])
    return out


QUERIES = [
    "sum by (host) (rate(req_total[1m]))",
    "sum(rate(req_total[1m]))",
    "avg by (dc) (increase(req_total[2m]))",
    "max by (dc) (delta(req_total[1m]))",
    "count by (dc) (req_total)",
    "sum by (host) (last_over_time(req_total[1m]))",
    "stddev by (dc) (rate(req_total[1m]))",
    'sum by (dc) (rate(req_total{host=~"h[0-2]"}[1m]))',
    'sum by (host) (rate(req_total{dc="dc0"}[1m]))',
    "sum by (host) (rate(req_total[1m] offset 1m))",
    "sum without (host) (changes(req_total[2m]))",
    "group by (dc) (req_total)",
]


@pytest.mark.parametrize("promql", QUERIES)
def test_fast_matches_generic(inst, promql):
    setup_metrics(inst)
    fast_val, slow_val, _ = run_both(
        inst, promql, T0 + 120_000, T0 + 480_000, 30_000
    )
    assert isinstance(fast_val, VectorValue)
    fm, sm = as_map(fast_val), as_map(slow_val)
    # generic path may emit all-absent series the fast path drops
    sm = {k: v for k, v in sm.items() if v[1].any()}
    assert set(fm) == set(sm), (promql, set(fm) ^ set(sm))
    for key in fm:
        fv, fp = fm[key]
        sv, sp = sm[key]
        np.testing.assert_array_equal(fp, sp, err_msg=promql)
        np.testing.assert_allclose(
            np.where(fp, fv, 0), np.where(sp, sv, 0),
            rtol=1e-5, atol=1e-6, err_msg=promql,
        )


@pytest.mark.parametrize("promql", [
    "max by (host) (max_over_time(req_total[1m]))",
    "min by (host) (min_over_time(req_total[2m]))",
    "sum by (dc) (quantile_over_time(0.5, req_total[1m]))",
    "sum by (dc) (stddev_over_time(req_total[1m]))",
])
def test_window_past_data_end_matches_generic(inst, promql):
    """Steps past the last sample: the grid clips their window END to
    its last cell, and the gather-family range functions must then see
    a SHORTER window (cells (lo, hi]), not the same length slid back
    over samples at or before t - range (chip_smoke found
    max_over_time including the sample at exactly t - range)."""
    ts = setup_metrics(inst)
    end = int(ts[-1])
    fast_val, slow_val, _ = run_both(
        inst, promql, end - 60_000, end + 90_000, 15_000
    )
    fm, sm = as_map(fast_val), as_map(slow_val)
    sm = {k: v for k, v in sm.items() if v[1].any()}
    assert set(fm) == set(sm)
    for key in fm:
        fv, fp = fm[key]
        sv, sp = sm[key]
        np.testing.assert_array_equal(fp, sp, err_msg=promql)
        np.testing.assert_allclose(
            np.where(fp, fv, 0), np.where(sp, sv, 0),
            rtol=1e-5, atol=1e-6, err_msg=promql,
        )


def test_fast_path_taken_and_invalidated(inst):
    ts = setup_metrics(inst)
    eng = PromEngine(inst)
    v1, _ = eng.query_range(
        "sum by (host) (rate(req_total[1m]))",
        T0 + 120_000, T0 + 480_000, 30_000,
    )
    # the cache now holds one entry for (req_total, greptime_value)
    assert any(
        e.num_series > 0 for e in F._CACHE._entries.values()
    ), "fast path did not build a grid entry"
    # new write must invalidate: append a big spike to h0 and re-query
    table = inst.catalog.table("public", "req_total")
    t_new = int(ts[-1]) + 15_000
    table.write(
        {"host": np.asarray(["h0"], object), "dc": np.asarray(["dc0"], object)},
        np.asarray([t_new], np.int64),
        {"greptime_value": np.asarray([1e9])},
    )
    v2, _ = eng.query_range(
        "sum by (host) (rate(req_total[1m]))",
        T0 + 120_000, t_new, 15_000,
    )
    h0 = [i for i, l in enumerate(v2.labels) if l.get("host") == "h0"][0]
    assert v2.values[h0][-1] > 1e5, "stale grid served after write"


def test_unaligned_step_falls_back(inst):
    setup_metrics(inst)
    # step 7s does not divide the 15s data interval: generic path must serve
    eng = PromEngine(inst)
    real = F._fused_query
    called = []
    F._fused_query = lambda *a, **k: called.append(1) or real(*a, **k)
    try:
        val, _ = eng.query_range(
            "sum by (host) (rate(req_total[1m]))",
            T0 + 120_000, T0 + 180_000, 7_000,
        )
    finally:
        F._fused_query = real
    assert not called
    assert isinstance(val, VectorValue) and val.num_series > 0


def test_no_match_returns_empty(inst):
    setup_metrics(inst)
    eng = PromEngine(inst)
    val, _ = eng.query_range(
        'sum by (host) (rate(req_total{host="nope"}[1m]))',
        T0 + 120_000, T0 + 180_000, 30_000,
    )
    assert val.num_series == 0


def test_matcher_mask_vectorized_semantics(inst):
    """SeriesRegistry.match_mask equals the per-series semantics of the old
    match_sids loop, including missing-tag and regex cases."""
    import re

    setup_metrics(inst)
    table = inst.catalog.table("public", "req_total")
    reg = table.regions[0].series
    cases = [
        [("host", "eq", "h1")],
        [("host", "ne", "h1")],
        [("host", "re", re.compile("h[0-2]"))],
        [("host", "nre", re.compile("h[0-2]")), ("dc", "eq", "dc1")],
        [("missing", "eq", "")],
        [("missing", "eq", "x")],
        [("host", "in", ["h1", "h3"])],
    ]
    for matchers in cases:
        mask = reg.match_mask(matchers)
        sids = reg.match_sids(matchers)
        expect = []
        for sid in range(reg.num_series):
            tags = reg.series_tags(sid)
            ok = True
            for name, op, value in matchers:
                v = tags.get(name, "")
                if op == "eq":
                    ok &= v == value
                elif op == "ne":
                    ok &= v != value
                elif op == "in":
                    ok &= v in value
                elif op == "re":
                    ok &= bool(value.fullmatch(v))
                elif op == "nre":
                    ok &= not value.fullmatch(v)
            expect.append(ok)
        np.testing.assert_array_equal(mask, np.asarray(expect), err_msg=str(matchers))
        np.testing.assert_array_equal(sids, np.nonzero(expect)[0])


def _mk_histogram(tmp_path, n_groups=6, les=("0.1", "0.5", "1", "+Inf")):
    import tempfile

    from greptimedb_tpu.instance import Standalone

    inst = Standalone(str(tmp_path), prefer_device=True,
                      warm_start=False)
    inst.execute_sql(
        "create table lat_bucket (ts timestamp time index, host string, "
        "le string, greptime_value double, primary key (host, le))"
    )
    tab = inst.catalog.table("public", "lat_bucket")
    rng = np.random.default_rng(5)
    rows_h, rows_l, rows_t, rows_v = [], [], [], []
    counts = {f"h{i}": np.zeros(len(les)) for i in range(n_groups)}
    for s in range(6):
        for h in counts:
            counts[h] = counts[h] + np.sort(
                rng.integers(0, 5, size=len(les))
            ).cumsum()
            for bi, le in enumerate(les):
                rows_h.append(h)
                rows_l.append(le)
                rows_t.append(s * 10_000)
                rows_v.append(float(counts[h][bi]))
    tab.write(
        {"host": np.asarray(rows_h, object),
         "le": np.asarray(rows_l, object)},
        np.asarray(rows_t, np.int64),
        {"greptime_value": np.asarray(rows_v)},
    )
    return inst


def _canon(v):
    order = sorted(range(len(v.labels)),
                   key=lambda i: sorted(v.labels[i].items()))
    return [
        (sorted(v.labels[i].items()),
         np.where(v.present[i], np.round(v.values[i], 6), None).tolist())
        for i in order
    ]


def test_fast_histogram_quantile_matches_generic(tmp_path):
    """histogram_quantile rides the selector-grid fast path (VERDICT r3
    missing #7) and must equal the generic engine exactly."""
    from greptimedb_tpu.promql import fast as F
    from greptimedb_tpu.promql.engine import PromEngine

    inst = _mk_histogram(tmp_path / "d")
    try:
        q = "histogram_quantile(0.9, rate(lat_bucket[30s]))"
        eng = PromEngine(inst)
        args = (30_000, 50_000, 10_000)
        F.invalidate_cache()
        orig = F.try_fast_histogram
        F.try_fast_histogram = lambda *a, **k: None
        try:
            vg, _ = eng.query_range(q, *args)
        finally:
            F.try_fast_histogram = orig
        F.invalidate_cache()
        before = F._FAST_HITS.labels("hit").value
        vf, _ = eng.query_range(q, *args)
        assert F._FAST_HITS.labels("hit").value > before, (
            "histogram did not take the fast path"
        )
        assert _canon(vg) == _canon(vf)
        # instant (no range fn) shape too
        q2 = "histogram_quantile(0.5, lat_bucket)"
        F.invalidate_cache()
        F.try_fast_histogram = lambda *a, **k: None
        try:
            vg2, _ = eng.query_range(q2, *args)
        finally:
            F.try_fast_histogram = orig
        F.invalidate_cache()
        vf2, _ = eng.query_range(q2, *args)
        assert _canon(vg2) == _canon(vf2)
    finally:
        F.invalidate_cache()
        inst.close()


def test_fast_histogram_fallbacks(tmp_path):
    """No +Inf bucket or non-le tables must fall back, not mis-answer."""
    from greptimedb_tpu.promql import fast as F
    from greptimedb_tpu.promql.engine import PromEngine

    inst = _mk_histogram(tmp_path / "d", les=("0.1", "0.5", "1"))
    try:
        q = "histogram_quantile(0.9, rate(lat_bucket[30s]))"
        F.invalidate_cache()
        v, _ = PromEngine(inst).query_range(q, 30_000, 50_000, 10_000)
        # Prometheus: histograms without +Inf are undefined -> empty
        assert v.num_series == 0
    finally:
        F.invalidate_cache()
        inst.close()


def test_fast_histogram_sum_by_matches_generic(tmp_path):
    """The at-scale shape: histogram_quantile over `sum by (le, svc)`
    of pod-level buckets — one fused program, equal to generic."""
    from greptimedb_tpu.instance import Standalone
    from greptimedb_tpu.promql import fast as F
    from greptimedb_tpu.promql.engine import PromEngine

    inst = Standalone(str(tmp_path / "d"), prefer_device=True,
                      warm_start=False)
    inst.execute_sql(
        "create table lb (ts timestamp time index, pod string, "
        "svc string, le string, greptime_value double, "
        "primary key (pod, svc, le))"
    )
    tab = inst.catalog.table("public", "lb")
    les = ["0.1", "0.5", "1", "+Inf"]
    rng = np.random.default_rng(5)
    rows = {"pod": [], "svc": [], "le": []}
    ts_l, v_l = [], []
    counts = {}
    for s in range(6):
        for p in range(12):
            pod, svc = f"p{p}", f"s{p % 3}"
            counts[pod] = counts.get(pod, np.zeros(4)) + np.sort(
                rng.integers(0, 5, 4)
            ).cumsum()
            for bi, le in enumerate(les):
                rows["pod"].append(pod)
                rows["svc"].append(svc)
                rows["le"].append(le)
                ts_l.append(s * 10_000)
                v_l.append(float(counts[pod][bi]))
    tab.write(
        {k: np.asarray(v, object) for k, v in rows.items()},
        np.asarray(ts_l, np.int64),
        {"greptime_value": np.asarray(v_l)},
    )
    try:
        q = "histogram_quantile(0.9, sum by (le, svc) (rate(lb[30s])))"
        eng = PromEngine(inst)
        args = (30_000, 50_000, 10_000)
        F.invalidate_cache()
        orig = F.try_fast_histogram
        F.try_fast_histogram = lambda *a, **k: None
        try:
            vg, _ = eng.query_range(q, *args)
        finally:
            F.try_fast_histogram = orig
        F.invalidate_cache()
        before = F._FAST_HITS.labels("hit").value
        vf, _ = eng.query_range(q, *args)
        assert F._FAST_HITS.labels("hit").value > before
        assert _canon(vg) == _canon(vf)
        assert vf.num_series == 3
    finally:
        F.invalidate_cache()
        inst.close()


# ----------------------------------------------------------------------
# round-5 fast paths: arg-taking range fns, topk/bottomk, vector<op>vector
# ----------------------------------------------------------------------

def run_both_all(inst, promql, start, end, step):
    """Query once with every fast path live, once with all of them
    disabled (resolution stubbed out) — results must agree."""
    eng = PromEngine(inst)
    fast_val, ev = eng.query_range(promql, start, end, step)
    real_resolve = F._resolve_fast_selector
    real_binary = F.try_fast_binary
    F._resolve_fast_selector = lambda *a, **k: None
    F.try_fast_binary = lambda *a, **k: None
    try:
        slow_val, _ = PromEngine(inst).query_range(promql, start, end,
                                                   step)
    finally:
        F._resolve_fast_selector = real_resolve
        F.try_fast_binary = real_binary
    return fast_val, slow_val, ev


def assert_equivalent(fast_val, slow_val, promql, *, rtol=1e-5):
    fm, sm = as_map(fast_val), as_map(slow_val)
    sm = {k: v for k, v in sm.items() if v[1].any()}
    fm = {k: v for k, v in fm.items() if v[1].any()}
    assert set(fm) == set(sm), (promql, set(fm) ^ set(sm))
    for key in fm:
        fv, fp = fm[key]
        sv, sp = sm[key]
        np.testing.assert_array_equal(fp, sp, err_msg=promql)
        np.testing.assert_allclose(
            np.where(fp, fv, 0), np.where(sp, sv, 0),
            rtol=rtol, atol=1e-5, err_msg=promql,
        )


ARG_FN_QUERIES = [
    "sum by (host) (quantile_over_time(0.9, req_total[2m]))",
    "max by (dc) (min_over_time(req_total[1m]))",
    "sum by (dc) (max_over_time(req_total[2m]))",
    "avg by (dc) (stddev_over_time(req_total[2m]))",
    "sum by (host) (deriv(req_total[2m]))",
    "sum by (host) (predict_linear(req_total[2m], 600))",
    "sum by (dc) (holt_winters(req_total[2m], 0.5, 0.5))",
    "sum by (dc) (mad_over_time(req_total[2m]))",
]


@pytest.mark.parametrize("promql", ARG_FN_QUERIES)
def test_arg_range_fns_fast_matches_generic(inst, promql):
    setup_metrics(inst)
    fast_val, slow_val, _ = run_both_all(
        inst, promql, T0 + 120_000, T0 + 480_000, 30_000
    )
    assert isinstance(fast_val, VectorValue)
    assert_equivalent(fast_val, slow_val, promql)


TOPK_QUERIES = [
    "topk(3, rate(req_total[1m]))",
    "bottomk(2, rate(req_total[1m]))",
    "topk(3, req_total)",
    "topk(100, rate(req_total[1m]))",  # k > num_series
    'topk(2, rate(req_total{dc="dc0"}[1m]))',
]


@pytest.mark.parametrize("promql", TOPK_QUERIES)
def test_topk_fast_matches_generic(inst, promql):
    setup_metrics(inst)
    fast_val, slow_val, _ = run_both_all(
        inst, promql, T0 + 120_000, T0 + 480_000, 30_000
    )
    assert isinstance(fast_val, VectorValue)
    assert_equivalent(fast_val, slow_val, promql)


def test_topk_uses_fused_kernel(inst):
    setup_metrics(inst)
    called = []
    real = F._fused_topk
    F._fused_topk = lambda *a, **k: called.append(1) or real(*a, **k)
    try:
        PromEngine(inst).query_range(
            "topk(2, rate(req_total[1m]))",
            T0 + 120_000, T0 + 240_000, 30_000,
        )
    finally:
        F._fused_topk = real
    assert called, "topk did not take the fused fast path"


BINARY_QUERIES = [
    "rate(req_total[1m]) / last_over_time(req_total[1m])",
    "rate(req_total[1m]) + rate(req_total[2m])",
    "req_total - last_over_time(req_total[1m])",
    "rate(req_total[1m]) > 0.5",                  # vector-scalar: generic
    "rate(req_total[1m]) > rate(req_total[2m])",  # filter comparison
    "rate(req_total[1m]) >= bool rate(req_total[2m])",
    'rate(req_total{dc="dc0"}[1m]) * rate(req_total[1m])',
    "sum by (dc) (rate(req_total[1m]) / last_over_time(req_total[1m]))",
    "avg by (host) (req_total + req_total)",
    "sum(rate(req_total[1m]) / last_over_time(req_total[1m]))",
]


@pytest.mark.parametrize("promql", BINARY_QUERIES)
def test_binary_fast_matches_generic(inst, promql):
    setup_metrics(inst)
    fast_val, slow_val, _ = run_both_all(
        inst, promql, T0 + 120_000, T0 + 480_000, 30_000
    )
    assert isinstance(fast_val, VectorValue)
    assert_equivalent(fast_val, slow_val, promql)


def test_binary_on_ignoring_falls_back(inst):
    """Explicit matching modifiers use the generic label matcher."""
    setup_metrics(inst)
    called = []
    real = F._fused_binary
    F._fused_binary = lambda *a, **k: called.append(1) or real(*a, **k)
    try:
        v, _ = PromEngine(inst).query_range(
            "rate(req_total[1m]) / on(host, dc) "
            "last_over_time(req_total[1m])",
            T0 + 120_000, T0 + 240_000, 30_000,
        )
    finally:
        F._fused_binary = real
    assert not called
    assert v.num_series > 0


def test_topk_keeps_infinite_samples(inst):
    """A present +Inf sample must win topk (and -Inf bottomk) rather
    than being confused with the absent-slot fill (code-review r5)."""
    inst.sql(
        "CREATE TABLE infm (host STRING PRIMARY KEY, "
        "greptime_value DOUBLE, ts TIMESTAMP TIME INDEX)"
    )
    table = inst.catalog.table("public", "infm")
    ts = T0 + np.arange(4) * 15_000
    for h, v in [("a", np.inf), ("b", 5.0), ("c", -np.inf)]:
        table.write({"host": np.full(4, h, object)}, ts,
                    {"greptime_value": np.full(4, v)})
    eng = PromEngine(inst)
    v, _ = eng.query_range("topk(1, infm)", T0 + 15_000, T0 + 45_000,
                           15_000)
    assert [l["host"] for l in v.labels] == ["a"]
    assert np.isposinf(v.values[v.present]).all()
    v, _ = eng.query_range("bottomk(1, infm)", T0 + 15_000,
                           T0 + 45_000, 15_000)
    assert [l["host"] for l in v.labels] == ["c"]
    assert np.isneginf(v.values[v.present]).all()
