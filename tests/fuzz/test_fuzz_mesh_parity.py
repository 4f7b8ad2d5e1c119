"""Sharded-vs-single-device parity fuzz (ISSUE 7 satellite): random
decomposable aggregate / RANGE / PromQL (incl. topk) queries run on a
forced 8-device CPU mesh (conftest pins
XLA_FLAGS=--xla_force_host_platform_device_count=8) and on one device,
asserting BIT-IDENTICAL results. The blocked exact folds
(parallel/mesh.FOLD_BLOCKS, parallel/dist.LocalFoldCtx/ShardFoldCtx)
promise the same f32 additions in the same order on every mesh size —
this fuzz is that contract's enforcement.

Deterministic by default (seeded); set GREPTIMEDB_TPU_FUZZ_SEED to
explore, GREPTIMEDB_TPU_FUZZ_ITERS to lengthen. Defaults generate
4 batches x 25 = 100 compared queries. The query space is sampled from
a bounded shape grid so XLA compiles amortise across iterations.
"""

import os

import numpy as np
import pytest

from greptimedb_tpu.instance import Standalone
from greptimedb_tpu.parallel import mesh as M
from greptimedb_tpu.query import stats as qstats
from greptimedb_tpu.query.executor import QueryEngine
from greptimedb_tpu.query.planner import plan_select
from greptimedb_tpu.session import QueryContext
from greptimedb_tpu.sql.parser import parse_sql

SEED = int(os.environ.get("GREPTIMEDB_TPU_FUZZ_SEED", "20260803"))
BATCHES = int(os.environ.get("GREPTIMEDB_TPU_FUZZ_ITERS", "2"))
PER_BATCH = 20

# tiny test grids: force the replicate-vs-shard planner to shard so the
# shard_map programs actually execute (prod defaults gate on 4096 series)
FORCE_SHARD = M.MeshOptions(shard_min_series=1, shard_min_rows=1)

ROW_AGGS = ["count", "sum", "min", "max", "avg",
            "first_value", "last_value"]
RANGE_AGGS = ROW_AGGS + ["stddev_samp", "var_pop"]
PROM_AGG_OPS = ["sum", "avg", "count", "min", "max", "stddev", "stdvar"]
PROM_FNS = ["rate", "increase", "delta", "sum_over_time",
            "avg_over_time", "max_over_time", "min_over_time"]


@pytest.fixture(scope="module")
def sql_setup(tmp_path_factory):
    rng = np.random.default_rng(SEED)
    inst = Standalone(str(tmp_path_factory.mktemp("mesh_parity")))
    inst.execute_sql(
        "create table fz (ts timestamp time index, host string primary "
        "key, u double, v double)"
    )
    tab = inst.catalog.table("public", "fz")
    n_hosts, t = 24, 120
    ts = np.tile(np.arange(t) * 10_000, n_hosts).astype(np.int64)
    hosts = np.repeat(
        [f"h{i:02d}" for i in range(n_hosts)], t
    ).astype(object)
    u = rng.random(n_hosts * t) * 200 - 100
    v = rng.random(n_hosts * t) * 50
    tab.write({"host": hosts}, ts, {"u": u, "v": v})
    e1 = QueryEngine(prefer_device=True)
    em = QueryEngine(prefer_device=True, mesh=M.make_mesh(),
                     mesh_opts=FORCE_SHARD)
    yield inst, e1, em
    inst.close()


def _run(engine, inst, sql):
    stmt = parse_sql(sql)[0]
    plan, table = inst.plan(stmt, QueryContext())
    return engine.execute(plan, table)


def _exact(ra, rb, q):
    assert ra.names == rb.names, q
    assert ra.num_rows == rb.num_rows, (
        f"row count differs for: {q} ({ra.num_rows} vs {rb.num_rows})"
    )
    for i, name in enumerate(ra.names):
        a, b = np.asarray(ra.cols[i].values), np.asarray(rb.cols[i].values)
        if a.dtype == object or b.dtype == object:
            ok = all(
                (x is None and y is None) or x == y
                for x, y in zip(a.tolist(), b.tolist())
            )
            assert ok, f"column {name} differs for: {q}\n{a}\nvs\n{b}"
        else:
            assert np.array_equal(a, b, equal_nan=True), (
                f"column {name} not bit-identical for: {q}\n{a}\nvs\n{b}"
            )


def _random_sql(rng) -> str:
    """Decomposable aggregate / RANGE shapes over a bounded grid of
    static program specs (ranges, aligns, group keys) so compiles
    amortise while ops and predicates stay random."""
    f = rng.choice(["u", "v"])
    if rng.random() < 0.5:
        # RANGE query: grid path, series-sharded cell states
        agg = rng.choice(RANGE_AGGS)
        rng_s, align = rng.choice([(60, 60), (120, 60), (120, 120)])
        by = rng.choice(["BY (host)", "BY ()"])
        order = "ts, host" if "host" in by else "ts"
        where = ""
        if rng.random() < 0.3:
            # cell-edge-aligned ts bound keeps the device partial valid
            lo = int(rng.integers(1, 8)) * 120_000
            where = f" WHERE ts >= {lo}"
        extra = ""
        if rng.random() < 0.4:
            agg2 = rng.choice(["count", "sum", "max"])
            extra = f", {agg2}({f}) RANGE '{rng_s}s'"
        return (
            f"SELECT ts{', host' if 'host' in by else ''}, "
            f"{agg}({f}) RANGE '{rng_s}s'{extra} FROM fz{where} "
            f"ALIGN '{align}s' {by} ORDER BY {order}"
        )
    # plain GROUP BY: row path, fused sharded reduce
    agg = rng.choice(ROW_AGGS)
    agg2 = rng.choice(["count", "sum", "avg"])
    keyed = rng.random() < 0.7
    where = ""
    if rng.random() < 0.3:
        where = f" WHERE {f} > {rng.random() * 40 - 20:.2f}"
    if keyed:
        return (
            f"SELECT host, {agg}({f}) AS a, {agg2}(v) AS b FROM fz"
            f"{where} GROUP BY host ORDER BY host"
        )
    return f"SELECT {agg}({f}) AS a, {agg2}(v) AS b FROM fz{where}"


@pytest.mark.parametrize("batch", range(BATCHES))
def test_mesh_parity_fuzz_sql(sql_setup, batch):
    inst, e1, em = sql_setup
    rng = np.random.default_rng(SEED + batch * 104729)
    sharded = 0
    for _ in range(PER_BATCH):
        q = _random_sql(rng)
        r1 = _run(e1, inst, q)
        with qstats.collect() as collected:
            rm = _run(em, inst, q)
        _exact(r1, rm, q)
        if collected.counters.get("mesh_devices", 0) > 1:
            sharded += 1
    # the fuzz must exercise the shard_map programs, not just the
    # replicate fallback
    assert sharded >= PER_BATCH * 2 // 3, sharded


# ----------------------------------------------------------------------
# PromQL: rate/aggregate + topk over the selector-grid fast path
# ----------------------------------------------------------------------


@pytest.fixture(scope="module")
def prom_setup(tmp_path_factory):
    def build(home, mesh):
        rng = np.random.default_rng(SEED)  # identical data all builds
        inst = Standalone(str(home), prefer_device=True, mesh=mesh,
                          mesh_opts=None if mesh is None else FORCE_SHARD,
                          warm_start=False)
        inst.execute_sql(
            "create table http_requests (ts timestamp time index, "
            "host string primary key, dc string primary key, "
            "greptime_value double)"
        )
        tab = inst.catalog.table("public", "http_requests")
        n_hosts, t = 24, 120
        ts = np.tile(np.arange(t) * 10_000, n_hosts).astype(np.int64)
        hosts = np.repeat(
            [f"h{k:02d}" for k in range(n_hosts)], t
        ).astype(object)
        dcs = np.repeat(
            [f"dc{k % 3}" for k in range(n_hosts)], t
        ).astype(object)
        vals = np.cumsum(rng.random(n_hosts * t), 0)
        tab.write({"host": hosts, "dc": dcs}, ts,
                  {"greptime_value": vals})
        return inst

    tmp = tmp_path_factory.mktemp("mesh_parity_prom")
    i1 = build(tmp / "single", None)
    im = build(tmp / "mesh", M.make_mesh())
    yield i1, im
    from greptimedb_tpu.promql import fast as F

    F.invalidate_cache()
    i1.close()
    im.close()


def _random_promql(rng) -> str:
    fn = rng.choice(PROM_FNS)
    sel = "http_requests[2m]"
    if rng.random() < 0.3:
        # topk/bottomk: the dist_topk per-shard select + reselect path
        op = rng.choice(["topk", "bottomk"])
        k = int(rng.choice([3, 7]))
        return f"{op}({k}, {fn}({sel}))"
    op = rng.choice(PROM_AGG_OPS)
    by = rng.choice(["by (dc) ", ""])
    return f"{op} {by}({fn}({sel}))"


def _prom_exact(queries, rs1, rs2):
    for q, r1, rm in zip(queries, rs1, rs2):
        l1 = [frozenset(lb.items()) for lb in r1.labels]
        lm = [frozenset(lb.items()) for lb in rm.labels]
        assert l1 == lm, f"labels differ for: {q}"
        assert (r1.present == rm.present).all(), \
            f"presence differs: {q}"
        a = np.where(r1.present, r1.values, 0.0)
        b = np.where(rm.present, rm.values, 0.0)
        assert np.array_equal(a, b, equal_nan=True), (
            f"values not bit-identical for: {q}\n{a}\nvs\n{b}"
        )


@pytest.mark.slow  # tier-1 budget: SQL fuzz twins keep mesh-parity gated
def test_mesh_parity_fuzz_promql(prom_setup):
    from greptimedb_tpu.promql import fast as F
    from greptimedb_tpu.promql.engine import PromEngine

    i1, im = prom_setup
    rng = np.random.default_rng(SEED + 7919)
    queries = [_random_promql(rng) for _ in range(PER_BATCH)]
    t0, t1, step = 0, 119 * 10_000, 60_000

    def run_all(inst):
        F.invalidate_cache()
        eng = PromEngine(inst)
        out = []
        for q in queries:
            r, _ = eng.query_range(q, t0, t1, step)
            out.append(r)
        return out

    rs1 = run_all(i1)
    rsm = run_all(im)
    # the mesh build's grid really is series-sharded over 8 devices
    entry = next(iter(F._CACHE._entries.values()))
    assert entry.mesh is not None
    assert len(entry.vals.devices()) == 8
    _prom_exact(queries, rs1, rsm)
