"""Restart snapshots for the device grid cache (VERDICT r2 task #10):
a rebuilt instance restores HBM grids from the persisted snapshot
instead of rescanning SSTs, and stale snapshots are rejected."""

import time

import numpy as np
import pytest

from greptimedb_tpu.instance import Standalone
from greptimedb_tpu.query import device_range as DR
from greptimedb_tpu.query.executor import QueryEngine

Q = ("SELECT ts, host, avg(u) RANGE '10s', last_value(u) RANGE '10s' "
     "FROM cpu ALIGN '10s' BY (host) ORDER BY ts, host")


def _mk(tmp_path, rng):
    inst = Standalone(str(tmp_path), prefer_device=True, warm_start=False)
    inst.execute_sql(
        "create table cpu (ts timestamp time index, host string primary "
        "key, u double)"
    )
    tab = inst.catalog.table("public", "cpu")
    n_hosts, t = 8, 200
    ts = np.tile(np.arange(t) * 1000, n_hosts).astype(np.int64)
    hosts = np.repeat([f"h{i}" for i in range(n_hosts)], t).astype(object)
    u = rng.random(n_hosts * t) * 100
    tab.write({"host": hosts}, ts, {"u": u})
    return inst


def _wait_snapshot(inst, timeout=15.0):
    region = inst.catalog.table("public", "cpu").regions[0]
    deadline = time.time() + timeout
    while time.time() < deadline:
        if region.store.list(f"{region.prefix}/{DR._SNAP_DIRNAME}/"):
            return True
        time.sleep(0.05)
    return False


def test_snapshot_restores_without_rescan(tmp_path, rng, monkeypatch):
    inst = _mk(tmp_path, rng)
    r1 = inst.sql(Q)
    assert inst.query_engine.last_exec_path == "device"
    assert _wait_snapshot(inst), "snapshot never persisted"
    inst.close()

    inst2 = Standalone(str(tmp_path), prefer_device=True, warm_start=False)

    def _no_build(*a, **k):  # restored entries must NOT trigger a rescan
        raise AssertionError("build_entry called despite a live snapshot")

    monkeypatch.setattr(DR, "build_entry", _no_build)
    r2 = inst2.sql(Q)
    assert inst2.query_engine.last_exec_path == "device"
    assert r1.rows() == r2.rows()
    inst2.close()


def test_stale_snapshot_rejected_and_rebuilt(tmp_path, rng):
    inst = _mk(tmp_path, rng)
    inst.sql(Q)
    assert _wait_snapshot(inst)
    # new write AFTER the snapshot: version moves on
    inst.sql("insert into cpu (ts, host, u) values (500000, 'h0', 42.0)")
    inst.close()

    inst2 = Standalone(str(tmp_path), prefer_device=True, warm_start=False)
    r = inst2.sql(Q)
    assert inst2.query_engine.last_exec_path == "device"
    # the stale file must be gone (deleted at load) or replaced
    vals = {row[1]: row for row in r.rows() if row[0] == 500000}
    assert float(vals["h0"][2]) == 42.0  # new row visible: not stale data
    inst2.close()


def test_warm_start_thread_restores(tmp_path, rng):
    inst = _mk(tmp_path, rng)
    inst.sql(Q)
    assert _wait_snapshot(inst)
    inst.close()

    inst2 = Standalone(str(tmp_path), prefer_device=True, warm_start=True)
    deadline = time.time() + 15
    while time.time() < deadline:
        if inst2.query_engine.range_cache._entries:
            break
        time.sleep(0.05)
    assert inst2.query_engine.range_cache._entries, "warm start idle"
    inst2.close()


def test_program_specs_persist_and_precompile(tmp_path, rng):
    """The first query's static jit spec persists next to the snapshot;
    warm_from_snapshots precompiles it so the first query after restart
    pays steady-state latency (VERDICT r3 cold-start task)."""
    inst = _mk(tmp_path, rng)
    inst.sql(Q)
    assert _wait_snapshot(inst)
    region = inst.catalog.table("public", "cpu").regions[0]
    entry = next(iter(inst.query_engine.range_cache._entries.values()))
    spec_path = DR._program_specs_path(entry, region)
    deadline = time.time() + 10
    while time.time() < deadline and not region.store.exists(spec_path):
        time.sleep(0.05)
    assert region.store.exists(spec_path), "program specs never persisted"
    inst.close()

    inst2 = Standalone(str(tmp_path), prefer_device=True,
                       warm_start=False)
    n = DR.warm_from_snapshots(inst2.query_engine, inst2.catalog)
    assert n == 1
    entry2 = next(iter(inst2.query_engine.range_cache._entries.values()))
    assert entry2.program_specs, "warm did not precompile any program"
    precompiled = set(entry2.program_specs)
    # the first query must HIT a precompiled spec: the set must not grow
    r = inst2.sql(Q)
    assert inst2.query_engine.last_exec_path == "device"
    assert set(entry2.program_specs) == precompiled, (
        "first query built a NEW spec — precompile missed it"
    )
    assert r.num_rows > 0
    inst2.close()
