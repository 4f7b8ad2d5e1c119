"""Unified memory observability (telemetry/memory.py): the pool
ledger, the device live-buffer census, cross-pool pressure eviction,
/debug/prof/hbm + information_schema.memory_pools, and the strict
metric-registration contract (telemetry/metrics.py).
"""

import json
import threading
import urllib.request

import numpy as np
import pytest

from greptimedb_tpu.instance import Standalone
from greptimedb_tpu.servers.http import HttpServer
from greptimedb_tpu.telemetry import memory
from greptimedb_tpu.telemetry.memory import MemoryAccountant
from greptimedb_tpu.telemetry.metrics import (
    MetricRegistrationError,
    MetricsRegistry,
    global_registry,
)


class FakePool:
    """Minimal accountant client: a dict of jax buffers with an LRU
    evict."""

    def __init__(self, budget=1 << 20):
        self.entries = {}
        self.budget = budget
        self.evictions = 0

    def put(self, key, arr):
        self.entries[key] = arr
        memory.note_device_bytes()

    def stats(self):
        return {
            "bytes": sum(a.nbytes for a in self.entries.values()),
            "entries": len(self.entries),
            "budget_bytes": self.budget,
            "evictions": self.evictions,
        }

    def evict(self, target):
        freed = 0
        while freed < target and self.entries:
            _, a = self.entries.popitem()
            freed += a.nbytes
            self.evictions += 1
        return freed

    def buffers(self):
        return [(a, f"fake:{k}") for k, a in self.entries.items()]


def _jnp_buf(n_floats):
    import jax.numpy as jnp

    return jnp.zeros((n_floats,), jnp.float32)


# ---------------------------------------------------------------------
# accountant core
# ---------------------------------------------------------------------

def test_registration_aggregates_instances_and_drops_dead():
    acct = MemoryAccountant()
    a, b = FakePool(), FakePool()
    for p in (a, b):
        acct.register_pool("fake", "device", p, stats=FakePool.stats,
                           evict=FakePool.evict,
                           buffers=FakePool.buffers)
    a.entries["x"] = _jnp_buf(16)
    b.entries["y"] = _jnp_buf(8)
    snap = {s.name: s for s in acct.snapshot()}
    assert snap["fake"].instances == 2
    assert snap["fake"].bytes == 16 * 4 + 8 * 4
    assert snap["fake"].entries == 2
    # a GC'd pool silently leaves the ledger
    del b, p
    import gc

    gc.collect()
    snap = {s.name: s for s in acct.snapshot()}
    assert snap["fake"].instances == 1
    assert snap["fake"].bytes == 64


def test_census_attributes_owned_and_flags_unaccounted():
    acct = MemoryAccountant()
    pool = FakePool()
    acct.register_pool("owned", "device", pool, stats=FakePool.stats,
                       buffers=FakePool.buffers)
    owned = _jnp_buf(1024)
    pool.entries["g"] = owned
    leak = _jnp_buf(512)   # held only by this frame: no owner
    c0 = acct.census(top=50)
    assert c0["pools"]["owned"] == owned.nbytes
    assert c0["unaccounted_bytes"] >= leak.nbytes
    owners = {t["owner"] for t in c0["top"]}
    assert "fake:g" in owners
    # adopting the leak moves it from unaccounted to accounted
    pool.entries["adopted"] = leak
    c1 = acct.census()
    assert c1["unaccounted_bytes"] <= c0["unaccounted_bytes"] - leak.nbytes
    assert c1["accounted_bytes"] >= c0["accounted_bytes"] + leak.nbytes


def test_cross_pool_eviction_proportional_to_bytes():
    acct = MemoryAccountant()
    big, small = FakePool(), FakePool()
    acct.register_pool("big", "device", big, stats=FakePool.stats,
                       evict=FakePool.evict, buffers=FakePool.buffers)
    acct.register_pool("small", "device", small, stats=FakePool.stats,
                       evict=FakePool.evict, buffers=FakePool.buffers)
    for i in range(8):
        big.entries[i] = _jnp_buf(1024)     # 32 KiB total
    small.entries[0] = _jnp_buf(1024)       # 4 KiB
    total = 9 * 4096
    acct.device_budget_bytes = total - 6000  # ~6 KB overage
    freed = acct.enforce_device_budget()
    assert freed >= 6000
    assert acct.device_bytes() <= acct.device_budget_bytes
    # the big pool sheds more than the small one (proportional)
    assert big.evictions >= small.evictions
    assert big.evictions >= 1


def test_budget_unset_is_free_and_greedy_pass_covers_stuck_pools():
    acct = MemoryAccountant()
    stuck, ok = FakePool(), FakePool()

    def no_evict(pool, target):
        return 0

    acct.register_pool("stuck", "device", stuck, stats=FakePool.stats,
                       evict=no_evict)
    acct.register_pool("ok", "device", ok, stats=FakePool.stats,
                       evict=FakePool.evict)
    stuck.entries["a"] = _jnp_buf(1024)
    ok.entries["b"] = _jnp_buf(1024)
    assert acct.note_device_bytes() == 0      # no watermark configured
    acct.device_budget_bytes = 4096           # one buffer must go
    acct.enforce_device_budget()
    # the stuck pool freed nothing; the greedy second pass took the
    # whole overage out of the evictable pool
    assert not ok.entries
    assert stuck.entries


def test_eviction_delta_survives_instance_death():
    import gc

    acct = MemoryAccountant()
    a, b = FakePool(), FakePool()
    for p in (a, b):
        acct.register_pool("t_evd", "device", p, stats=FakePool.stats)
    counter = global_registry.counter(
        "gtpu_mem_evictions_total",
        "entries evicted per registered memory pool (budget, staleness "
        "or cross-pool pressure)", ("pool", "tier"),
    ).labels("t_evd", "device")
    a.evictions = 100
    b.evictions = 5
    acct.publish()
    v0 = counter.value
    # instance A dies; B keeps evicting — the counter must keep
    # advancing, not stall behind A's dead high-water mark
    del a, p
    gc.collect()
    b.evictions += 50
    acct.publish()
    assert counter.value == v0 + 50


def test_publish_zeroes_gauges_of_dead_pools():
    import gc

    acct = MemoryAccountant()
    pool = FakePool()
    acct.register_pool("t_dead_pool", "host", pool,
                       stats=FakePool.stats)
    pool.entries["x"] = _jnp_buf(256)
    acct.publish()
    gauge = global_registry.get("gtpu_mem_bytes").labels(
        "t_dead_pool", "host"
    )
    assert gauge.value == 1024.0
    del pool
    gc.collect()
    acct.publish()
    # freed memory must not keep reporting as held forever
    assert gauge.value == 0.0


def test_configure_applies_budget_immediately():
    acct = memory.global_accountant
    saved = (acct.enabled, acct.device_budget_bytes,
             acct.census_on_scrape)
    pool = FakePool()
    acct.register_pool("cfg_pool", "device", pool,
                       stats=FakePool.stats, evict=FakePool.evict)
    pool.entries["a"] = _jnp_buf(4096)
    pool.entries["b"] = _jnp_buf(4096)
    base = acct.device_bytes()
    try:
        memory.configure({"device_budget_bytes": base - 8192})
        assert acct.device_bytes() <= base - 8192
        assert pool.evictions >= 1
    finally:
        acct.enabled, acct.device_budget_bytes, acct.census_on_scrape = \
            saved


# ---------------------------------------------------------------------
# real pools end to end
# ---------------------------------------------------------------------

@pytest.fixture()
def inst(tmp_path):
    inst = Standalone(str(tmp_path / "data"), prefer_device=True,
                      warm_start=False)
    yield inst
    inst.close()


@pytest.fixture()
def server(inst):
    srv = HttpServer(inst, port=0).start()
    yield srv
    srv.stop()


def _get(srv, path):
    url = f"http://127.0.0.1:{srv.port}{path}"
    with urllib.request.urlopen(url, timeout=120) as r:
        return r.status, r.read().decode()


def _seed_device_table(inst, name="mt", hosts=4, cells=600):
    inst.execute_sql(
        f"create table {name} (ts timestamp time index, "
        "h string primary key, v double)"
    )
    t = inst.catalog.table("public", name)
    rng = np.random.default_rng(7)
    ts = np.tile(np.arange(cells, dtype=np.int64) * 1000, hosts)
    hs = np.repeat(
        np.asarray([f"h{i}" for i in range(hosts)], object), cells
    )
    t.write({"h": hs}, ts, {"v": rng.random(len(ts))}, skip_wal=True)
    return t


def _run_range(inst, name="mt"):
    out = inst.execute_sql(
        f"SELECT ts, avg(v) RANGE '1m' FROM {name} ALIGN '1m' BY ()"
    )
    assert inst.query_engine.last_exec_path == "device"
    return out


def test_hbm_route_reports_every_pool_and_census_sums(inst, server):
    _seed_device_table(inst)
    _run_range(inst)
    status, body = _get(server, "/debug/prof/hbm?format=json&top=8")
    assert status == 200
    doc = json.loads(body)
    pools = {p["pool"]: p for p in doc["pools"]}
    # the pools this workload exercises all report
    for name in ("range_grid", "sessions", "result_cache",
                 "trace_ring"):
        assert name in pools, sorted(pools)
    rg = pools["range_grid"]
    assert rg["tier"] == "device" and rg["bytes"] > 0
    assert rg["budget_bytes"] > 0
    # acceptance: per-pool census bytes sum to the census accounted
    # total (every owner-tagged buffer is claimed by exactly one pool)
    device_census_sum = sum(
        p.get("census_bytes", 0) for p in doc["pools"]
        if p["tier"] == "device"
    )
    assert device_census_sum == doc["census"]["accounted_bytes"]
    # and each device pool's REPORTED bytes equal its census bytes:
    # derived per-query inputs (query_memo gid/mask, promql match/
    # group/win caches) count in stats, not just in the census — the
    # watermark sees every resident byte
    for p in doc["pools"]:
        if p["tier"] == "device":
            assert p["bytes"] == p["census_bytes"], p
    assert doc["census"]["live_bytes"] == (
        doc["census"]["accounted_bytes"]
        + doc["census"]["unaccounted_bytes"]
    )
    # top buffers carry owner/shape/dtype attribution
    assert doc["top_buffers"]
    top = doc["top_buffers"][0]
    assert top["owner"].startswith(("range:", "sessions:", "promql:",
                                    "warm_precompile:"))
    assert "shape" in top and "dtype" in top
    # text rendering serves the same report
    status, text = _get(server, "/debug/prof/hbm")
    assert status == 200
    assert "device census:" in text and "range_grid" in text


def test_memory_pools_table_matches_hbm_report(inst, server):
    _seed_device_table(inst)
    _run_range(inst)
    res = inst.sql(
        "select pool, tier, bytes, census_bytes, budget_bytes "
        "from information_schema.memory_pools order by pool"
    )
    rows = {r[0]: r for r in res.rows()}
    assert "range_grid" in rows and "sessions" in rows
    doc = json.loads(_get(server, "/debug/prof/hbm?format=json")[1])
    hbm = {p["pool"]: p for p in doc["pools"]}
    # SQL table and /debug/prof/hbm read the same ledger
    for name, row in rows.items():
        assert row[1] == hbm[name]["tier"]
    # WHERE works (it goes through the normal planner)
    res = inst.sql(
        "select count(*) from information_schema.memory_pools "
        "where tier = 'device'"
    )
    assert res.rows()[0][0] >= 2


def test_gtpu_mem_metrics_render_and_unaccounted_gauge(inst, server):
    _seed_device_table(inst)
    _run_range(inst)
    status, text = _get(server, "/metrics")
    assert status == 200
    assert 'gtpu_mem_bytes{pool="range_grid",tier="device"}' in text
    assert 'gtpu_mem_budget_bytes{pool="sessions",tier="device"}' in text
    assert "gtpu_mem_unaccounted_device_bytes" in text
    assert "gtpu_mem_device_live_bytes" in text
    # runtime_metrics mirrors the same families
    res = inst.sql(
        "select count(*) from information_schema.runtime_metrics "
        "where metric_name = 'gtpu_mem_bytes'"
    )
    assert res.rows()[0][0] >= 2


def test_global_watermark_evicts_across_real_pools(inst):
    """A [memory] device_budget_bytes below the sum of the individual
    pool budgets is enforced by cross-pool eviction on the put path."""
    acct = memory.global_accountant
    saved = acct.device_budget_bytes
    _seed_device_table(inst, "wt1")
    _seed_device_table(inst, "wt2")
    _run_range(inst, "wt1")
    _run_range(inst, "wt2")
    base = acct.device_bytes()
    assert base > 0
    cross0 = _cross_evicted_total()
    try:
        # watermark below current residency (and far below the 4GiB +
        # 1GiB individual budgets): enforcement applies at configure,
        # and every later put re-checks
        memory.configure({"device_budget_bytes": max(base // 2, 4096)})
        assert acct.device_bytes() <= acct.device_budget_bytes
        assert _cross_evicted_total() > cross0
        # the evicted grid rebuilds on the next query and the budget
        # still holds afterwards — steady state under pressure
        _run_range(inst, "wt1")
        assert acct.device_bytes() <= acct.device_budget_bytes
    finally:
        acct.device_budget_bytes = saved


def _cross_evicted_total() -> float:
    m = global_registry.get("gtpu_mem_cross_pool_evicted_bytes_total")
    return sum(c.value for _k, c in m._snapshot())


def test_session_strand_would_be_visible_as_unaccounted(inst):
    """The leak class PR 9's reviews caught by hand: a device buffer
    that loses its owner shows up in gtpu_mem_unaccounted_device_bytes
    instead of hiding."""
    _seed_device_table(inst)
    _run_range(inst)
    c0 = memory.global_accountant.census()
    # simulate a strand: pull a buffer out of the session registry but
    # keep it alive (exactly what a purge-less eviction used to do)
    from greptimedb_tpu.query.sessions import global_sessions

    with global_sessions._lock:
        key = next(iter(global_sessions._entries))
        stranded = global_sessions._entries[key][1]
        global_sessions._drop_locked(key)
    c1 = memory.global_accountant.census()
    assert c1["unaccounted_bytes"] >= (
        c0["unaccounted_bytes"] + stranded.nbytes
    )
    del stranded


def test_device_span_carries_pool_bytes_attribution(inst):
    from greptimedb_tpu.telemetry import memory, tracing

    # expire the 0.5 s device-bytes TTL: a warm jit cache lets this
    # test reach its span before an earlier test's cached 0 ages out
    memory.global_accountant._dev_bytes_cache = (-1e18, 0)
    _seed_device_table(inst)
    _run_range(inst)
    dev_spans = [
        s for tr in tracing.global_traces.traces(limit=50)
        for s in tr["spans"] if s["name"] == "device.execute"
    ]
    assert dev_spans, "no device.execute span recorded"
    attrs = dev_spans[-1]["attributes"]
    assert attrs.get("device_pool_bytes", 0) > 0


# ---------------------------------------------------------------------
# strict metric registration (satellite: MetricsRegistry._get)
# ---------------------------------------------------------------------

def test_metric_reregistration_type_conflict_raises():
    reg = MetricsRegistry()
    reg.counter("m_total", "help")
    with pytest.raises(MetricRegistrationError) as ei:
        reg.gauge("m_total", "help")
    assert "Counter" in str(ei.value) and "Gauge" in str(ei.value)


def test_metric_reregistration_label_conflict_raises():
    reg = MetricsRegistry()
    reg.counter("m2_total", "help", labels=("mode",))
    with pytest.raises(MetricRegistrationError) as ei:
        reg.counter("m2_total", "help")
    assert "mode" in str(ei.value)
    # identical re-registration stays get-or-create
    again = reg.counter("m2_total", "different help", labels=("mode",))
    again.labels("full").inc()
    assert again.labels("full").value == 1.0


def test_metric_get_is_schema_free_lookup():
    reg = MetricsRegistry()
    with pytest.raises(KeyError):
        reg.get("absent_total")
    c = reg.counter("present_total", "h", labels=("x",))
    assert reg.get("present_total") is c


# ---------------------------------------------------------------------
# /metrics under concurrent label churn (satellite: test coverage)
# ---------------------------------------------------------------------

def test_metrics_render_survives_concurrent_label_churn(inst, server):
    """Many threads creating labelled children and observing histograms
    mid-scrape: every scrape through the real HTTP endpoint must parse,
    keep each family contiguous under one HELP/TYPE header, and show
    monotone cumulative histogram buckets with count == +Inf."""
    stop = threading.Event()
    churn_c = global_registry.counter(
        "gtpu_test_churn_total", "churn", labels=("worker", "step")
    )
    churn_h = global_registry.histogram(
        "gtpu_test_churn_seconds", "churn", labels=("worker",)
    )
    errors = []

    def churner(wid):
        import time

        i = 0
        while not stop.is_set():
            churn_c.labels(str(wid), str(i % 97)).inc()
            churn_h.labels(str(wid)).observe((i % 13) / 1000.0)
            i += 1
            if i % 50 == 0:
                # yield: hot-spinning on the 1-core CI box would starve
                # the HTTP server thread serving the scrape
                time.sleep(0.001)

    threads = [
        threading.Thread(target=churner, args=(w,), daemon=True)
        for w in range(3)
    ]
    for t in threads:
        t.start()
    try:
        for _ in range(10):
            status, text = _get(server, "/metrics")
            assert status == 200
            try:
                _assert_exposition_consistent(text)
            except AssertionError as e:
                errors.append(str(e))
                break
    finally:
        stop.set()
        for t in threads:
            t.join(timeout=10)
    assert not errors, errors[0]


def _assert_exposition_consistent(text: str):
    seen_families = set()
    current = None
    buckets: dict[str, list] = {}
    counts: dict[str, float] = {}
    for line in text.splitlines():
        if line.startswith("# HELP "):
            fam = line.split()[2]
            assert fam not in seen_families, f"family {fam} torn apart"
            seen_families.add(fam)
            current = fam
            continue
        if line.startswith("# TYPE "):
            assert line.split()[2] == current, "TYPE without its HELP"
            continue
        if not line:
            continue
        name = line.split("{")[0].split(" ")[0]
        assert current is not None and name.startswith(current), (
            f"sample {name} outside its family block"
        )
        if name.endswith("_bucket"):
            series = line.rsplit(",le=", 1)[0]
            buckets.setdefault(series, []).append(
                float(line.rsplit(" ", 1)[1])
            )
        elif name.endswith("_count"):
            counts[line.rsplit(" ", 1)[0]] = float(
                line.rsplit(" ", 1)[1]
            )
    for series, vals in buckets.items():
        assert vals == sorted(vals), (
            f"non-monotone cumulative buckets for {series}: {vals}"
        )
        cname = series.replace("_bucket{", "_count{") + "}"
        if cname in counts:
            # the count may have advanced between the bucket lines and
            # the count line of the SAME scrape only if a new
            # observation landed in between; both were read under the
            # child lock, so they must agree exactly
            assert vals[-1] == counts[cname], (
                f"+Inf bucket != count for {series}"
            )


# ---------------------------------------------------------------------
# ExportMetricsTask failure path (satellite: test coverage)
# ---------------------------------------------------------------------

def test_export_metrics_failure_path(inst, server, caplog,
                                     monkeypatch):
    """The REAL background loop under a failing sink: the failures
    counter increments (visible through the real HTTP endpoint), the
    identical repeated error logs exactly once, the thread survives,
    and a recovered sink resumes importing samples."""
    import logging
    import time

    from greptimedb_tpu.servers import prom_store
    from greptimedb_tpu.telemetry.export import ExportMetricsTask

    boom = {"on": True}
    real_apply = prom_store.apply_series

    def flaky_apply(instance, series, db="x"):
        if boom["on"]:
            raise RuntimeError("sink unavailable")
        return real_apply(instance, series, db=db)

    monkeypatch.setattr(prom_store, "apply_series", flaky_apply)
    task = ExportMetricsTask(inst, db="t_export")
    task.interval_s = 0.05  # the ctor clamps; the loop reads the attr
    with caplog.at_level(logging.WARNING,
                         logger="greptimedb_tpu.export"):
        task.start()
        try:
            deadline = time.monotonic() + 20
            while task.failures < 3 and time.monotonic() < deadline:
                time.sleep(0.05)
            assert task.failures >= 3, "loop died on the first failure"
            assert task._thread.is_alive()
            same_error_logs = [
                r for r in caplog.records
                if "sink unavailable" in r.getMessage()
            ]
            assert len(same_error_logs) == 1, (
                "identical consecutive errors must log once, got "
                f"{len(same_error_logs)}"
            )
            _status, text = _get(server, "/metrics")
            val = [
                line for line in text.splitlines() if line.startswith(
                    "greptime_export_metrics_failures_total "
                )
            ]
            assert val and float(val[0].split()[-1]) >= 3
            # recovery: the surviving loop imports samples again
            boom["on"] = False
            deadline = time.monotonic() + 20
            while (task.samples_written == 0
                   and time.monotonic() < deadline):
                time.sleep(0.05)
            assert task.samples_written > 0
            assert inst.catalog.table_names("t_export")
        finally:
            task.stop()
