"""Compaction & tiered-storage dataplane (storage/compaction.py):
leveled TWCS picker, bounded pool, device-accelerated merge parity,
tombstone GC across merge sets, hot/cold tiering, orphan cleanup,
maintenance error isolation, ADMIN routing, cache invalidation."""

import threading
import time

import numpy as np
import pytest

from greptimedb_tpu.errors import TableNotFoundError
from greptimedb_tpu.storage import compaction
from greptimedb_tpu.storage.compaction import (
    CompactionOptions,
    CompactionScheduler,
    cleanup_orphan_ssts,
    compact_once,
    pick_compaction,
    pick_tasks,
    purge_expired,
    read_amplification,
)
from greptimedb_tpu.storage.device_merge import host_merge, merge_rows
from greptimedb_tpu.storage.engine import EngineConfig, TsdbEngine
from greptimedb_tpu.storage.memtable import (
    OP_DELETE,
    OP_PUT,
    ColumnarRows,
)
from greptimedb_tpu.storage.object_store import (
    FsObjectStore,
    MemoryObjectStore,
)
from greptimedb_tpu.storage.region import Region, RegionMetadata, RegionOptions
from greptimedb_tpu.storage.sst import TIER_COLD, TIER_HOT, write_sst

WINDOW = 1_000_000


def make_region(tmp_path, *, rid=1, trigger=3, window_ms=WINDOW,
                merge_mode="last_row", ttl_ms=None, append=False,
                store=None, cold_store=None, opts=None):
    meta = RegionMetadata(
        region_id=rid, table="t", tag_names=["h"], field_names=["v"],
        ts_name="ts",
        options=RegionOptions(
            compaction_trigger_files=trigger,
            compaction_window_ms=window_ms, merge_mode=merge_mode,
            ttl_ms=ttl_ms, append_mode=append,
        ),
    )
    store = store or FsObjectStore(str(tmp_path / f"data{rid}"))
    r = Region(meta, store, str(tmp_path / f"wal{rid}"),
               cold_store=cold_store)
    if opts is not None:
        r._compaction_opts = opts
    return r


def write_flush(r, hosts, ts, vals, *, op=OP_PUT):
    tags = {"h": np.asarray(hosts, object)}
    ts = np.asarray(ts, np.int64)
    if op == OP_DELETE:
        r.delete(tags, ts)
    else:
        r.write(tags, ts, {"v": np.asarray(vals, np.float64)})
    r.flush()


def levels(r):
    return sorted(m.level for m in r.manifest.state.ssts)


# ----------------------------------------------------------------------
# leveled picker
# ----------------------------------------------------------------------

def test_l0_merges_to_l1_then_l1s_to_l2(tmp_path):
    opts = CompactionOptions(l1_trigger_files=2)
    r = make_region(tmp_path, trigger=2, opts=opts)
    write_flush(r, ["a"], [100], [1.0])
    write_flush(r, ["a"], [200], [2.0])
    assert compact_once(r, opts)
    assert levels(r) == [1]
    write_flush(r, ["a"], [300], [3.0])
    write_flush(r, ["a"], [400], [4.0])
    # L0 pair merges to a second L1, then the L1 pair cascades to L2
    # inside the same compact_once call
    assert compact_once(r, opts)
    assert levels(r) == [2]
    res = r.scan()
    assert res.rows.ts.tolist() == [100, 200, 300, 400]
    r.close()


def test_l1_byte_trigger(tmp_path):
    # file-count trigger out of reach: only the byte trigger can
    # promote the accumulated L1 pair
    opts = CompactionOptions(l1_trigger_files=100, l1_trigger_bytes=1)
    r = make_region(tmp_path, trigger=2, opts=opts)
    write_flush(r, ["a"], [100], [1.0])
    write_flush(r, ["a"], [200], [2.0])
    assert compact_once(r, opts)
    write_flush(r, ["a"], [300], [3.0])
    write_flush(r, ["a"], [400], [4.0])
    assert compact_once(r, opts)
    assert levels(r) == [2]
    assert r.scan().num_rows == 4
    r.close()


def test_l2_self_merge_keeps_top_level_single(tmp_path):
    opts = CompactionOptions(l2_trigger_files=2)
    r = make_region(tmp_path, trigger=10, opts=opts)
    # install two L2 files directly (the shape left by two promoted
    # windows whose outputs later fell into one re-bucketed window)
    for i in range(2):
        rows = ColumnarRows(
            sid=np.asarray([0], np.int32),
            ts=np.asarray([100 + i], np.int64),
            seq=np.asarray([i + 1], np.uint64),
            op=np.asarray([OP_PUT], np.uint8),
            fields={"v": np.asarray([float(i)])},
        )
        m = write_sst(r.store, f"{r.prefix}/sst/l2_{i}.parquet",
                      f"l2_{i}", rows, level=2)
        with r._lock:
            r.manifest.commit({"kind": "compact", "remove_files": [],
                               "add_ssts": [m.to_json()]})
    assert compact_once(r, opts)
    assert levels(r) == [2]
    assert len(r.manifest.state.ssts) == 1
    assert r.scan().num_rows == 2
    r.close()


def test_pick_compaction_back_compat(tmp_path):
    r = make_region(tmp_path, trigger=2)
    assert pick_compaction(r) is None
    write_flush(r, ["a"], [100], [1.0])
    write_flush(r, ["a"], [200], [2.0])
    files = pick_compaction(r)
    assert files is not None and len(files) == 2
    r.close()


def test_force_merges_untriggered_window(tmp_path):
    r = make_region(tmp_path, trigger=10)
    write_flush(r, ["a"], [100], [1.0])
    write_flush(r, ["a"], [200], [2.0])
    assert not compact_once(r)             # below trigger
    assert compact_once(r, force=True)     # ADMIN semantics
    assert len(r.manifest.state.ssts) == 1
    assert r.manifest.state.ssts[0].level == 2
    assert not compact_once(r, force=True)  # single file: no-op
    r.close()


def test_read_amplification(tmp_path):
    r = make_region(tmp_path, trigger=10)
    assert read_amplification(r) == 0
    for i in range(3):
        write_flush(r, ["a"], [100 + i], [1.0])
    # a second window with one file
    write_flush(r, ["a"], [WINDOW + 100], [1.0])
    assert read_amplification(r) == 3
    assert compact_once(r, force=True)
    assert read_amplification(r) == 1
    r.close()


# ----------------------------------------------------------------------
# tombstone GC semantics
# ----------------------------------------------------------------------

def test_tombstone_gc_on_covering_merge(tmp_path):
    r = make_region(tmp_path, trigger=2)
    write_flush(r, ["a"], [100], [1.0])
    write_flush(r, ["a"], [100], None, op=OP_DELETE)
    tasks = pick_tasks(r, CompactionOptions())
    assert len(tasks) == 1 and tasks[0].drop_deletes
    assert compact_once(r)
    # put + covering delete annihilate: no output file at all
    assert r.manifest.state.ssts == []
    assert r.scan().num_rows == 0
    r.close()


def test_tombstone_kept_when_shadow_target_outside_merge_set(tmp_path):
    r = make_region(tmp_path, trigger=3)
    # the shadowed put lives in an L1 file
    for i in range(3):
        write_flush(r, ["a"], [100], [float(i)])
    assert compact_once(r)
    assert levels(r) == [1]
    # delete + fillers trigger an L0-only merge that does NOT cover
    # the L1 file's range
    write_flush(r, ["a"], [100], None, op=OP_DELETE)
    write_flush(r, ["a"], [200], [9.0])
    write_flush(r, ["a"], [201], [9.0])
    tasks = pick_tasks(r, CompactionOptions())
    assert tasks and tasks[0].kind == "l0" and not tasks[0].drop_deletes
    assert compact_once(r)
    # tombstone survived the merge and still shadows the L1 row
    merged = [m for m in r.manifest.state.ssts if m.level == 1
              and m.rows > 1]
    assert merged
    assert 100 not in r.scan().rows.ts.tolist()
    # a forced covering merge NOW drops the tombstone and the shadowed
    # row together — and the delete stays invisible afterwards
    assert compact_once(r, force=True)
    assert len(r.manifest.state.ssts) == 1
    res = r.scan()
    assert res.rows.ts.tolist() == [200, 201]
    assert not (r.manifest.state.ssts[0].rows > 2)
    r.close()


# ----------------------------------------------------------------------
# device merge parity
# ----------------------------------------------------------------------

def _random_rows(n=4000, seed=0, with_valid=True):
    rng = np.random.default_rng(seed)
    sid = rng.integers(0, 40, n).astype(np.int32)
    ts = rng.integers(1_700_000_000_000, 1_700_000_050_000, n)
    seq = np.arange(n, dtype=np.uint64)
    rng.shuffle(seq)
    op = np.where(rng.random(n) < 0.15, OP_DELETE, OP_PUT)
    f1 = rng.standard_normal(n)
    f1[rng.random(n) < 0.02] = np.nan
    valid = {"a": rng.random(n) < 0.6,
             "b": rng.random(n) < 0.95} if with_valid else None
    return ColumnarRows(
        sid=sid, ts=ts.astype(np.int64), seq=seq,
        op=op.astype(np.uint8),
        fields={"a": f1, "b": rng.standard_normal(n)},
        field_valid=valid,
    )


@pytest.mark.parametrize("merge_mode", ["last_row", "last_non_null"])
@pytest.mark.parametrize("drop_deletes", [False, True])
def test_device_merge_bit_identical(merge_mode, drop_deletes):
    rows = _random_rows()
    dev, path = merge_rows(rows, merge_mode=merge_mode,
                           drop_deletes=drop_deletes,
                           device_min_rows=1, verify=True)
    assert path == "device"
    host = host_merge(rows, merge_mode=merge_mode,
                      drop_deletes=drop_deletes)
    assert len(dev) == len(host)
    for name in ("sid", "ts", "seq", "op"):
        assert np.array_equal(getattr(dev, name), getattr(host, name))
    for name in dev.fields:
        assert np.array_equal(dev.fields[name], host.fields[name],
                              equal_nan=True)
    if host.field_valid is not None:
        for name in host.field_valid:
            assert np.array_equal(dev.field_valid[name],
                                  host.field_valid[name])


def test_device_merge_host_fallback_threshold():
    rows = _random_rows(n=100, with_valid=False)
    _out, path = merge_rows(rows, device_min_rows=10_000)
    assert path == "host"
    _out, path = merge_rows(rows, device_min_rows=0)
    assert path == "host"


def test_compaction_uses_device_merge_with_verification(tmp_path):
    from greptimedb_tpu.telemetry.metrics import global_registry

    opts = CompactionOptions(device_merge_min_rows=1,
                             verify_device_merge=True)
    r = make_region(tmp_path, trigger=2, opts=opts)
    write_flush(r, ["a", "b"], [100, 101], [1.0, 2.0])
    write_flush(r, ["a"], [100], [3.0])  # overwrite
    before = global_registry.get(
        "gtpu_compaction_merge_total"
    ).labels("device").value
    assert compact_once(r, opts)
    after = global_registry.get(
        "gtpu_compaction_merge_total"
    ).labels("device").value
    assert after == before + 1
    res = r.scan()
    assert res.rows.ts.tolist() == [100, 101]
    assert res.rows.fields["v"].tolist() == [3.0, 2.0]
    r.close()


# ----------------------------------------------------------------------
# races: concurrent write / truncate / TTL
# ----------------------------------------------------------------------

class _GatedStore(FsObjectStore):
    """Blocks the first compaction read until released, widening the
    race window between pick and commit."""

    def __init__(self, root):
        super().__init__(root)
        self.reading = threading.Event()
        self.release = threading.Event()
        self._armed = True

    def read_range(self, path, offset, length):
        if self._armed and "/sst/" in path:
            self._armed = False
            self.reading.set()
            assert self.release.wait(10)
        return super().read_range(path, offset, length)


def test_concurrent_write_during_compaction(tmp_path):
    store = _GatedStore(str(tmp_path / "data"))
    r = make_region(tmp_path, trigger=2, store=store)
    write_flush(r, ["a"], [100], [1.0])
    write_flush(r, ["a"], [200], [2.0])
    t = threading.Thread(target=compact_once, args=(r,))
    t.start()
    assert store.reading.wait(10)
    # a write + flush lands while the merge is mid-read
    write_flush(r, ["b"], [300], [3.0])
    store.release.set()
    t.join(10)
    assert not t.is_alive()
    res = r.scan()
    assert res.rows.ts.tolist() == [100, 200, 300]
    # merged output + the concurrently flushed file
    assert len(r.manifest.state.ssts) == 2
    r.close()


def test_truncate_during_compaction_aborts_cleanly(tmp_path):
    store = _GatedStore(str(tmp_path / "data"))
    r = make_region(tmp_path, trigger=2, store=store)
    write_flush(r, ["a"], [100], [1.0])
    write_flush(r, ["a"], [200], [2.0])
    result = {}
    t = threading.Thread(
        target=lambda: result.setdefault("did", compact_once(r))
    )
    t.start()
    assert store.reading.wait(10)
    r.truncate()
    store.release.set()
    t.join(10)
    assert result["did"] is False
    assert r.scan().num_rows == 0
    # the aborted merge's output was deleted, truncation left nothing
    assert store.list(r.prefix + "/sst/") == []
    r.close()


def test_ttl_purge_during_compaction_aborts_cleanly(tmp_path):
    store = _GatedStore(str(tmp_path / "data"))
    r = make_region(tmp_path, trigger=2, store=store, ttl_ms=1000)
    write_flush(r, ["a"], [100], [1.0])
    write_flush(r, ["a"], [200], [2.0])
    result = {}
    t = threading.Thread(
        target=lambda: result.setdefault("did", compact_once(r))
    )
    t.start()
    assert store.reading.wait(10)
    # TTL expiry removes both picked inputs mid-merge
    assert purge_expired(r, now_ms=10_000_000) == 2
    store.release.set()
    t.join(10)
    assert result["did"] is False
    assert r.manifest.state.ssts == []
    assert store.list(r.prefix + "/sst/") == []
    r.close()


def test_purge_expired_is_tier_aware(tmp_path):
    cold = MemoryObjectStore()
    opts = CompactionOptions(cold_horizon_ms=1)
    r = make_region(tmp_path, trigger=10, ttl_ms=1000,
                    cold_store=cold, opts=opts)
    write_flush(r, ["a"], [100], [1.0])
    # rewrite the quiesced window onto the cold tier
    assert compact_once(r, opts, now_ms=10 * WINDOW)
    m = r.manifest.state.ssts[0]
    assert m.tier == TIER_COLD
    assert cold.exists(m.path)
    assert purge_expired(r, now_ms=10_000_000) == 1
    assert not cold.exists(m.path)
    assert r.manifest.state.ssts == []
    r.close()


# ----------------------------------------------------------------------
# hot/cold tiering
# ----------------------------------------------------------------------

def test_tiering_rewrites_old_window_cold(tmp_path):
    cold = MemoryObjectStore()
    opts = CompactionOptions(cold_horizon_ms=5 * WINDOW)
    r = make_region(tmp_path, trigger=10, cold_store=cold, opts=opts)
    write_flush(r, ["a", "b"], [100, 200], [1.0, 2.0])   # old window
    now = 100 * WINDOW
    write_flush(r, ["a"], [now - 10], [3.0])             # recent window
    tasks = pick_tasks(r, opts, now_ms=now)
    assert [t.kind for t in tasks] == ["tier"]
    assert compact_once(r, opts, now_ms=now)
    tiers = {m.tier for m in r.manifest.state.ssts}
    assert tiers == {TIER_COLD, TIER_HOT}
    cold_meta = [m for m in r.manifest.state.ssts
                 if m.tier == TIER_COLD][0]
    assert cold_meta.level == 2
    assert "/cold/" in cold_meta.path
    assert cold.exists(cold_meta.path)
    # scans read through the cold store transparently (rows come back
    # (sid, ts)-sorted, so compare as sets)
    res = r.scan()
    assert sorted(res.rows.ts.tolist()) == [100, 200, now - 10]
    # the cold window does not re-pick (already cold, single file)
    assert pick_tasks(r, opts, now_ms=now) == []
    r.close()


def test_tier_survives_reopen_and_restore_skips_cold_warm(tmp_path):
    from greptimedb_tpu.storage.page_cache import global_page_cache
    from greptimedb_tpu.storage.recovery import restore_region_ssts

    cold = MemoryObjectStore()
    opts = CompactionOptions(cold_horizon_ms=1)
    store = FsObjectStore(str(tmp_path / "data1"))
    r = make_region(tmp_path, trigger=10, cold_store=cold, opts=opts,
                    store=store)
    write_flush(r, ["a"], [100], [1.0])
    assert compact_once(r, opts, now_ms=10 * WINDOW)
    r.close()
    r2 = Region(r.meta, store, str(tmp_path / "wal1"), cold_store=cold)
    assert r2.manifest.state.ssts[0].tier == TIER_COLD
    stats = restore_region_ssts(r2, prefetch_depth=2)
    # cold files fetch + verify but never warm the page cache
    assert stats["files"] == 1
    assert stats["installed_cols"] == 0
    assert not any(
        key[0] == r2.manifest.state.ssts[0].path
        for key in global_page_cache._entries
    )
    assert r2.scan().num_rows == 1
    r2.close()


# ----------------------------------------------------------------------
# orphan cleanup at open
# ----------------------------------------------------------------------

def test_orphan_sst_cleanup_on_reopen(tmp_path):
    cfg = EngineConfig(data_root=str(tmp_path), enable_background=False)
    eng = TsdbEngine(cfg)
    meta = RegionMetadata(region_id=7, table="t", tag_names=["h"],
                          field_names=["v"], ts_name="ts")
    r = eng.create_region(meta)
    r.write({"h": np.asarray(["a"], object)},
            np.asarray([100], np.int64), {"v": np.asarray([1.0])})
    r.flush()
    live = r.manifest.state.ssts[0].path
    # a crash between SST write and manifest commit leaves orphans
    eng.store.write(f"{r.prefix}/sst/deadbeef.parquet", b"orphan")
    eng.store.write(f"{r.prefix}/cold/deadcold.parquet", b"orphan")
    eng.close()
    eng2 = TsdbEngine(cfg)
    r2 = eng2.open_region(meta)
    paths = {m.path for m in eng2.store.list(r2.prefix + "/sst/")}
    assert paths == {live}
    assert eng2.store.list(r2.prefix + "/cold/") == []
    assert r2.scan().num_rows == 1
    eng2.close()


def test_cleanup_orphans_respects_live_set(tmp_path):
    r = make_region(tmp_path, trigger=10)
    write_flush(r, ["a"], [100], [1.0])
    assert cleanup_orphan_ssts(r) == 0
    r.store.write(f"{r.prefix}/sst/zzzz.parquet", b"x")
    assert cleanup_orphan_ssts(r) == 1
    assert r.scan().num_rows == 1
    r.close()


# ----------------------------------------------------------------------
# scheduler: pool, dedupe, maintenance isolation
# ----------------------------------------------------------------------

def test_scheduler_dedupes_inflight_region(tmp_path):
    store = _GatedStore(str(tmp_path / "data"))
    r = make_region(tmp_path, trigger=2, store=store)
    write_flush(r, ["a"], [100], [1.0])
    write_flush(r, ["a"], [200], [2.0])
    sched = CompactionScheduler(CompactionOptions(workers=2))
    try:
        fut = sched.schedule(r)
        assert fut is not None
        assert store.reading.wait(10)
        assert sched.schedule(r) is None     # deduped while in flight
        store.release.set()
        assert fut.result(timeout=10) is True
        assert sched.maybe_schedule(r) is False  # nothing triggered
    finally:
        sched.close()
    r.close()


def test_close_interrupts_the_running_merge(tmp_path):
    """close() does not wait a merge out: the merge sees the closed
    scheduler at its next stage boundary and gives up, its inputs
    live and nothing committed."""
    store = _GatedStore(str(tmp_path / "data"))
    r = make_region(tmp_path, trigger=2, store=store)
    write_flush(r, ["a"], [100], [1.0])
    write_flush(r, ["a"], [200], [2.0])
    before = [m.file_id for m in r.manifest.state.ssts]
    sched = CompactionScheduler(CompactionOptions())
    fut = sched.schedule(r)
    assert store.reading.wait(10)
    threading.Timer(0.2, store.release.set).start()
    t0 = time.perf_counter()
    sched.close()                           # the read stage ends in 0.2 s
    assert time.perf_counter() - t0 < 5
    assert fut.result(timeout=10) is False
    assert [m.file_id for m in r.manifest.state.ssts] == before
    assert compaction.abandoned_merges() == 0
    r.close()


def test_close_leaves_a_merge_stuck_in_a_stage(tmp_path, monkeypatch):
    """A merge that sits inside one stage past the grace (a device
    compile takes minutes) is left behind and counted, so the process
    knows not to join its thread; once it moves on it commits nothing
    and the count falls back."""
    monkeypatch.setattr(compaction, "CLOSE_GRACE_S", 0.2)
    store = _GatedStore(str(tmp_path / "data"))
    r = make_region(tmp_path, trigger=2, store=store)
    write_flush(r, ["a"], [100], [1.0])
    write_flush(r, ["a"], [200], [2.0])
    before = [m.file_id for m in r.manifest.state.ssts]
    sched = CompactionScheduler(CompactionOptions())
    fut = sched.schedule(r)
    assert store.reading.wait(10)
    t0 = time.perf_counter()
    sched.close()
    assert time.perf_counter() - t0 < 5
    assert compaction.abandoned_merges() == 1
    store.release.set()
    assert fut.result(timeout=10) is False
    assert [m.file_id for m in r.manifest.state.ssts] == before
    assert compaction.abandoned_merges() == 0
    r.close()


def test_the_process_does_not_join_an_abandoned_merge(monkeypatch, capsys):
    """After every closer ran, the server leaves at once (exit 0) if a
    merge was left behind, and returns normally if none was."""
    from concurrent.futures import Future

    from greptimedb_tpu import cli

    left = []
    monkeypatch.setattr(cli.os, "_exit", left.append)
    cli._leave_abandoned_merges()
    assert left == []
    stuck = Future()
    monkeypatch.setattr(compaction, "_abandoned", [stuck])
    cli._leave_abandoned_merges()
    assert left == [0]
    assert "1 abandoned compaction merge" in capsys.readouterr().out
    stuck.set_result(False)
    cli._leave_abandoned_merges()
    assert left == [0]


@pytest.mark.parametrize("stage", ["read", "merge", "write"])
def test_a_stopped_merge_commits_nothing(tmp_path, stage):
    """`stop` read at each stage boundary: the inputs stay the live
    files and no output is left in the store."""
    r = make_region(tmp_path, trigger=2)
    write_flush(r, ["a"], [100], [1.0])
    write_flush(r, ["a"], [200], [2.0])
    before = [m.file_id for m in r.manifest.state.ssts]
    stages = ["read", "merge", "write"]
    asked = []

    def stop():
        asked.append(1)     # the first ask is compact_once's own
        return len(asked) > stages.index(stage) + 1

    assert compact_once(r, stop=stop) is False
    assert [m.file_id for m in r.manifest.state.ssts] == before
    assert len(r.store.list(r.prefix + "/sst/")) == len(before)
    assert compact_once(r) is True          # nothing was harmed
    r.close()


def test_one_bad_window_does_not_starve_others(tmp_path):
    """A deterministically failing input in one window must not abort
    the region's OTHER windows' merges (they would otherwise
    accumulate files forever); the first error still surfaces typed
    after every window got its attempt."""
    from greptimedb_tpu.errors import SstRestoreError
    from greptimedb_tpu.telemetry.metrics import global_registry

    r = make_region(tmp_path, trigger=2)
    # window 0: two good files; window 1: one file corrupted on disk
    write_flush(r, ["a"], [100], [1.0])
    write_flush(r, ["a"], [200], [2.0])
    write_flush(r, ["a"], [WINDOW + 100], [3.0])
    write_flush(r, ["a"], [WINDOW + 200], [4.0])
    bad = [m for m in r.manifest.state.ssts
           if m.ts_max > WINDOW][0]
    r.store.write(bad.path, b"truncated")   # short vs manifest bytes
    errs0 = global_registry.get(
        "gtpu_compaction_errors_total"
    ).labels().value
    with pytest.raises(SstRestoreError):
        compact_once(r)
    # the good window merged despite the bad one
    good = [m for m in r.manifest.state.ssts if m.ts_max <= WINDOW]
    assert len(good) == 1 and good[0].level == 1
    assert global_registry.get(
        "gtpu_compaction_errors_total"
    ).labels().value == errs0 + 1
    r.close()


def test_compact_sync_after_close_is_typed(tmp_path):
    from greptimedb_tpu.errors import CompactionError

    r = make_region(tmp_path, trigger=2)
    write_flush(r, ["a"], [100], [1.0])
    write_flush(r, ["a"], [200], [2.0])
    sched = CompactionScheduler(CompactionOptions())
    sched.close()
    with pytest.raises(CompactionError):
        sched.compact_sync(r, force=True)
    # idle region with nothing picked short-circuits without the pool
    r2 = make_region(tmp_path, rid=2, trigger=2)
    sched2 = CompactionScheduler(CompactionOptions())
    try:
        assert sched2.compact_sync(r2) is False
    finally:
        sched2.close()
    r.close()
    r2.close()


def test_engine_maintenance_error_isolation(tmp_path, monkeypatch):
    """One region's failing purge/compact must not abort the other
    regions' maintenance for the tick (the old loop-level try/except
    did exactly that)."""
    cfg = EngineConfig(data_root=str(tmp_path), enable_background=False)
    cfg.compaction.workers = 1
    eng = TsdbEngine(cfg)
    metas = [
        RegionMetadata(region_id=i, table=f"t{i}", tag_names=["h"],
                       field_names=["v"], ts_name="ts",
                       options=RegionOptions(compaction_trigger_files=2))
        for i in (1, 2)
    ]
    r1, r2 = (eng.create_region(m) for m in metas)
    for r in (r1, r2):
        write_flush(r, ["a"], [100], [1.0])
        write_flush(r, ["a"], [200], [2.0])
    import greptimedb_tpu.storage.compaction as comp

    real_purge = comp.purge_expired

    def flaky_purge(region, **kw):
        if region.meta.region_id == 1:
            raise RuntimeError("boom")
        return real_purge(region, **kw)

    monkeypatch.setattr(comp, "purge_expired", flaky_purge)
    eng.run_maintenance()   # must not raise
    deadline = time.monotonic() + 10
    while time.monotonic() < deadline:
        if len(r2.manifest.state.ssts) == 1:
            break
        time.sleep(0.05)
    # region 2's compaction ran despite region 1's failing purge
    assert len(r2.manifest.state.ssts) == 1
    assert len(r1.manifest.state.ssts) == 2
    eng.close()


def test_engine_wires_scheduler_and_read_amp_gauge(tmp_path):
    from greptimedb_tpu.telemetry.metrics import global_registry

    cfg = EngineConfig(data_root=str(tmp_path), enable_background=False)
    eng = TsdbEngine(cfg)
    meta = RegionMetadata(
        region_id=3, table="t", tag_names=["h"], field_names=["v"],
        ts_name="ts",
        options=RegionOptions(compaction_trigger_files=10),
    )
    r = eng.create_region(meta)
    assert r._compaction is eng.compaction
    for i in range(3):
        write_flush(r, ["a"], [100 + i], [1.0])
    assert eng.compaction.update_read_amp([r]) == 3
    assert r.compact(force=True)            # routes through the pool
    assert eng.compaction.update_read_amp([r]) == 1
    rendered = global_registry.render()
    assert "gtpu_compaction_read_amp" in rendered
    assert "gtpu_compaction_total" in rendered
    assert "gtpu_compaction_stage_ms_total" in rendered
    assert 'gtpu_compaction_bytes_total{direction="in"}' in rendered
    eng.close()


# ----------------------------------------------------------------------
# ADMIN surface + cache invalidation (full statement path)
# ----------------------------------------------------------------------

@pytest.fixture()
def inst(tmp_path):
    from greptimedb_tpu.instance import Standalone

    inst = Standalone(str(tmp_path), prefer_device=False,
                      warm_start=False)
    yield inst
    inst.close()


def _fill(inst, n_flushes=3):
    inst.execute_sql(
        "create table cpu (ts timestamp time index, "
        "host string primary key, usage double)"
    )
    table = inst.catalog.table("public", "cpu")
    for i in range(n_flushes):
        table.write(
            {"host": np.asarray(["a", "b"], object)},
            np.asarray([1000 + i, 2000 + i], np.int64),
            {"usage": np.asarray([1.0 + i, 2.0 + i])},
        )
        table.flush()
    return table


def test_admin_compact_table_routes_through_pool(inst):
    table = _fill(inst)
    region = table.regions[0]
    assert len(region.manifest.state.ssts) == 3
    r = inst.sql("ADMIN compact_table('cpu')")
    assert r.cols[0].values[0] == 1
    assert len(region.manifest.state.ssts) == 1
    assert region.manifest.state.ssts[0].level == 2
    # count survives the merge
    res = inst.sql("select count(usage) from cpu")
    assert res.cols[0].values[0] == 6
    # idempotent second pass
    r = inst.sql("ADMIN compact_table('cpu')")
    assert r.cols[0].values[0] == 0


def test_admin_flush_and_compact_typed_errors(inst):
    with pytest.raises(TableNotFoundError):
        inst.sql("ADMIN compact_table('nope')")
    with pytest.raises(TableNotFoundError):
        inst.sql("ADMIN flush_table('nope')")


def test_compaction_metrics_in_runtime_metrics(inst):
    _fill(inst)
    inst.sql("ADMIN compact_table('cpu')")
    res = inst.sql(
        "select metric_name from information_schema.runtime_metrics"
    )
    names = set(res.cols[0].values)
    assert "gtpu_compaction_total" in names
    assert "gtpu_compaction_stage_ms_total" in names
    assert "gtpu_compaction_read_amp" in names


def test_caches_invalidate_across_gc_compaction(inst, tmp_path):
    """Result cache + merged-scan state must never serve rows a
    tombstone-GC compaction removed: physical_version bumps on the
    compact commit, and the delete itself bumps the logical version."""
    from greptimedb_tpu.query.result_cache import ResultCache

    inst.result_cache = ResultCache(enabled=True, max_bytes=1 << 20)
    inst.catalog.result_cache = inst.result_cache
    table = _fill(inst, n_flushes=2)
    q = "select count(usage) from cpu"
    assert inst.sql(q).cols[0].values[0] == 4
    assert inst.sql(q).cols[0].values[0] == 4      # cached poll
    # delete one key, flush, GC-compact everything
    table.regions[0].delete(
        {"host": np.asarray(["a", "a"], object)},
        np.asarray([1000, 1001], np.int64),
    )
    table.flush()
    v_before = table.physical_version()
    inst.sql("ADMIN compact_table('cpu')")
    assert table.physical_version() != v_before
    assert inst.sql(q).cols[0].values[0] == 2
    # tombstones were dropped by the covering merge, result stays right
    region = table.regions[0]
    assert all((m.level, m.rows) == (2, 2)
               for m in region.manifest.state.ssts)


def test_twcs_trigger_table_option(inst):
    """`compaction.twcs.trigger_file_num` (reference twcs knob) sets
    the per-table L0 trigger through CREATE ... WITH(...)."""
    inst.execute_sql(
        "create table opt (ts timestamp time index, v double) "
        "with ('compaction.twcs.trigger_file_num' = '2')"
    )
    table = inst.catalog.table("public", "opt")
    region = table.regions[0]
    assert region.meta.options.compaction_trigger_files == 2
    for i in range(2):
        table.write({}, np.asarray([1000 + i], np.int64),
                    {"v": np.asarray([float(i)])})
        table.flush()
    # two L0 files satisfy the table's trigger without force
    assert region.compact()
    assert len(region.manifest.state.ssts) == 1


def test_append_mode_compaction_keeps_all_rows(tmp_path):
    r = make_region(tmp_path, trigger=2, append=True)
    write_flush(r, ["a"], [100], [1.0])
    write_flush(r, ["a"], [100], [2.0])   # duplicate key, append mode
    assert compact_once(r)
    assert len(r.manifest.state.ssts) == 1
    res = r.scan()
    assert res.num_rows == 2
    r.close()
