"""Secondary tag index units (index/): postings vs the registry
oracle, version-validated result caching, incremental maintenance,
device-plane parity + census, SST sid pruning, matcher memoization."""

import re

import numpy as np
import pytest

from greptimedb_tpu import index as _index
from greptimedb_tpu.index import device_plane
from greptimedb_tpu.index.tag_index import TagIndex
from greptimedb_tpu.storage.series import SeriesRegistry
from greptimedb_tpu.telemetry.metrics import global_registry


def _make_registry(n=2000, hosts=16, regions=5, seed=0):
    rng = np.random.default_rng(seed)
    reg = SeriesRegistry(["host", "region"])
    cols = [
        np.asarray([f"h{v}" for v in rng.integers(0, hosts, n)], object),
        np.asarray([f"r{v}" for v in rng.integers(0, regions, n)],
                   object),
    ]
    reg.intern_rows(cols)
    return reg


CASES = [
    [("host", "eq", "h3")],
    [("host", "in", ["h1", "h5", "h7"])],
    [("host", "ne", "h0")],
    [("host", "re", re.compile(r"h1[12]?"))],
    [("host", "nre", re.compile(r"h[0-4]"))],
    [("host", "eq", "h2"), ("region", "eq", "r1")],
    [("host", "ne", ""), ("region", "in", ["r0", "r4"])],
    [("missing", "eq", "x")],          # absent tag: constant verdict
    [("missing", "eq", "")],           # matches everything (empty tag)
    [("host", "eq", "nosuchvalue")],
    [],                                # no matchers
]


def test_match_sids_bit_identical_to_registry():
    reg = _make_registry()
    ix = TagIndex(reg)
    for m in CASES:
        want = reg.match_sids(m) if m else np.arange(
            reg.num_series, dtype=np.int32)
        got = _index.match_sids(reg, m)
        np.testing.assert_array_equal(got, want), m
        assert got.dtype == want.dtype
        if m:
            np.testing.assert_array_equal(ix.match_sids(m), want)


def test_match_sids_fuzz_against_oracle():
    rng = np.random.default_rng(7)
    reg = _make_registry(n=5000, hosts=40, regions=9, seed=1)
    ix = TagIndex(reg)
    # eq/in drawn twice as often as the table ops; literals run to
    # h59/r59 over a dictionary of 40 hosts and 9 regions, so a third of
    # the host literals and most region literals are absent values
    ops = ["eq", "in", "eq", "in", "ne", "nin", "re", "nre"]

    def literal(tag):
        return f"{'r' if tag == 'region' else 'h'}{rng.integers(0, 60)}"

    for _ in range(300):
        m = []
        for _ in range(rng.integers(1, 4)):
            tag = ["host", "region", "ghost"][rng.integers(0, 3)]
            op = ops[rng.integers(0, len(ops))]
            if op in ("in", "nin"):
                val = [literal(tag) for _ in range(rng.integers(0, 9))]
            elif op in ("re", "nre"):
                val = re.compile(f"[hr]{rng.integers(0, 45)}.*")
            else:
                val = literal(tag)
            m.append((tag, op, val))
        got = ix.match_sids(m)
        np.testing.assert_array_equal(
            got, reg.match_sids(m), err_msg=repr(m))
        assert got.dtype == np.int32


# -- eq/in by dictionary code ------------------------------------------
# Every shape the code-set form of an eq/in matcher takes, each against
# the registry's full-plane oracle. A scene is how the registry and the
# index got to the lookup; the matchers run on what the scene left.

def _scene_base():
    """Built CSR, no delta; a sixth of the series leave `region` empty."""
    rng = np.random.default_rng(3)
    n = 1200
    reg = SeriesRegistry(["host", "region"])
    region = np.asarray(
        [f"r{v}" if v else "" for v in rng.integers(0, 6, n)], object)
    reg.intern_rows([
        np.asarray([f"h{v}" for v in rng.integers(0, 24, n)], object),
        region,
    ])
    ix = TagIndex(reg)
    ix.match_sids([("host", "eq", "h0")])
    return reg, ix


def _scene_delta(rows):
    """Series with values interned AFTER the CSR build: `rows` of them
    against a rebuild_threshold of 64 (below: delta tail; past: rebuild)."""
    reg, ix = _scene_base()
    reg.intern_rows([
        np.asarray([f"new{i % 3}" for i in range(rows)], object),
        np.asarray([f"rn{i}" for i in range(rows)], object),
    ])
    # one old host gains a series in the tail too
    reg.intern_rows([np.asarray(["h1"], object),
                     np.asarray(["rn0"], object)])
    return reg, ix


def _scene_alter():
    """A tag added by ALTER after the build: old series read "" for it."""
    reg, ix = _scene_base()
    reg.add_tag("dc")
    reg.intern_rows([np.asarray(["h0", "h1"], object),
                     np.asarray(["r1", ""], object),
                     np.asarray(["east", "west"], object)])
    return reg, ix


# scene -> (registry, index, CSR rebuilds the lookup must make; None: any)
_SCENES = {
    "base": lambda: (*_scene_base(), None),
    "alter": lambda: (*_scene_alter(), None),
    "delta_tail": lambda: (*_scene_delta(10), 0),
    "delta_rebuild": lambda: (*_scene_delta(200), 1),
}

_RE = re.compile(r"h1[0-9]?")
_TABLE_MATCHERS = {
    "ne": ("host", "ne", "h3"),
    "nin": ("host", "nin", ["h3", "h4", "zz"]),
    "re": ("host", "re", _RE),
    "nre": ("host", "nre", _RE),
}
_CODE_MATCHERS = {
    "eq": ("region", "eq", "r2"),
    "in": ("region", "in", ["r1", "r4", "nope"]),
}

CODE_CASES = [
    ("base", "eq-one", [("host", "eq", "h7")]),
    ("base", "in-one", [("host", "in", ["h7"])]),
    ("base", "in-eight", [("host", "in", [f"h{v}" for v in range(2, 10)])]),
    ("base", "eq-absent", [("host", "eq", "h999")]),
    ("base", "in-absent", [("host", "in", ["h999", "h998"])]),
    ("base", "in-empty-list", [("host", "in", [])]),
    ("base", "in-some-absent-repeated",
     [("host", "in", ["h5", "zz", "h5", "h11", "h5", "yy"])]),
    ("base", "eq-empty-string", [("region", "eq", "")]),
    ("base", "in-empty-string", [("region", "in", ["", "r3"])]),
    ("base", "eq-empty-string-never-left-empty", [("host", "eq", "")]),
    ("base", "eq-eq", [("host", "eq", "h2"), ("region", "eq", "r1")]),
    ("base", "in-eq-wide-seed",
     [("host", "in", [f"h{v}" for v in range(20)]), ("region", "eq", "r5")]),
    ("base", "eq-then-absent", [("host", "eq", "h2"), ("region", "eq", "zz")]),
    ("base", "missing-tag-eq", [("rack", "eq", "x")]),
    ("base", "missing-tag-eq-empty", [("rack", "eq", ""), ("host", "eq", "h2")]),
    ("base", "missing-tag-in-empty", [("rack", "in", ["", "a"])]),
    ("base", "missing-tag-in", [("host", "in", ["h1"]), ("rack", "in", ["a"])]),
    ("delta_tail", "eq-interned-after-build", [("host", "eq", "new1")]),
    ("delta_tail", "in-interned-after-build",
     [("host", "in", ["new0", "new2", "h1"])]),
    ("delta_tail", "eq-old-value-in-tail", [("host", "eq", "h1")]),
    ("delta_tail", "eq-tail-two-tags",
     [("host", "eq", "new0"), ("region", "in", ["rn0", "rn3", "r1"])]),
    ("delta_tail", "tail-mixed-ne",
     [("region", "ne", "rn0"), ("host", "in", ["new0", "h1"])]),
    ("delta_rebuild", "eq-interned-after-build", [("host", "eq", "new1")]),
    ("delta_rebuild", "in-interned-after-build",
     [("host", "in", ["new0", "new2", "h1"])]),
    ("delta_rebuild", "eq-tail-two-tags",
     [("host", "eq", "new0"), ("region", "in", ["rn0", "rn70", "r1"])]),
    ("alter", "eq-added-tag", [("dc", "eq", "east")]),
    ("alter", "eq-added-tag-empty", [("dc", "eq", "")]),
    ("alter", "in-added-tag", [("dc", "in", ["west", "", "south"])]),
    ("alter", "eq-with-added-tag-ne",
     [("host", "eq", "h0"), ("dc", "ne", "east")]),
    ("alter", "added-tag-eq-with-in",
     [("dc", "eq", "west"), ("host", "in", ["h1", "h2"])]),
] + [
    ("base", f"{c}-then-{t}", [_CODE_MATCHERS[c], _TABLE_MATCHERS[t]])
    for c in _CODE_MATCHERS for t in _TABLE_MATCHERS
] + [
    ("base", f"{t}-then-{c}", [_TABLE_MATCHERS[t], _CODE_MATCHERS[c]])
    for c in _CODE_MATCHERS for t in _TABLE_MATCHERS
]


_SELECT_NOTHING = {
    "eq-absent", "in-absent", "in-empty-list", "eq-then-absent",
    "eq-empty-string-never-left-empty", "missing-tag-eq", "missing-tag-in",
}


@pytest.mark.parametrize(
    "scene,case,matchers", CODE_CASES,
    ids=[f"{s}-{name}" for s, name, _ in CODE_CASES])
def test_code_lookup_bit_identical_to_registry(scene, case, matchers):
    _index.configure({"rebuild_threshold": 64})
    try:
        reg, ix, rebuilds = _SCENES[scene]()
        b0 = ix.stats()["builds"]
        got = ix.match_sids(matchers)
        if rebuilds is not None:
            assert ix.stats()["builds"] == b0 + rebuilds
    finally:
        _index.configure({"rebuild_threshold": 4096})
    want = reg.match_sids(matchers)
    assert np.array_equal(got, want), (matchers, got, want)
    assert got.dtype == np.int32
    assert np.all(np.diff(got) > 0)
    # a case selects nothing only where it is made to
    assert (len(want) == 0) == (case in _SELECT_NOTHING), case


def _lookups(path: str) -> float:
    return global_registry.counter(
        "gtpu_index_lookups_total", labels=("path",)
    ).labels(path).value


def test_eq_in_resolve_by_code_without_a_dictionary_pass(monkeypatch):
    from greptimedb_tpu.index import tag_index

    calls = []

    def counting(vals, op, value):
        calls.append((len(vals), op))
        return _real(vals, op, value)

    _real = tag_index.ok_codes_for
    monkeypatch.setattr(tag_index, "ok_codes_for", counting)
    n = 4000
    reg = SeriesRegistry(["hostname", "dc"])
    reg.intern_rows([
        np.asarray([f"host_{i}" for i in range(n)], object),
        np.asarray([f"dc{i % 5}" for i in range(n)], object),
    ])
    assert len(reg.dicts[0]) == n
    ix = TagIndex(reg)
    fresh = [[("hostname", "eq", "host_17")],
             [("hostname", "in", ["host_18"])],
             [("hostname", "in", ["host_19", "host_3999", "host_4000"])],
             [("hostname", "eq", "host_20"), ("dc", "in", ["dc0", "dc1"])],
             [("hostname", "eq", "host_99999")]]
    for m in fresh:
        c0, p0 = _lookups("codes"), _lookups("postings")
        got = ix.match_sids(m)
        assert np.array_equal(got, reg.match_sids(m))
        assert _lookups("codes") == c0 + 1, m
        assert _lookups("postings") == p0, m
    # the oracle above ran the registry's own module, not tag_index's name
    assert calls == []
    # one regex matcher in the set: one ok-table, counted as postings
    m = [("hostname", "in", ["host_21", "host_22"]),
         ("dc", "re", re.compile("dc[12]"))]
    c0, p0, h0 = _lookups("codes"), _lookups("postings"), _lookups("cache")
    got = ix.match_sids(m)
    assert calls == [(5, "re")]
    assert (_lookups("codes"), _lookups("postings")) == (c0, p0 + 1)
    assert got.tolist() == [21, 22]
    # a repeat of either kind is a memo hit
    assert ix.match_sids(m) is got
    ix.match_sids(fresh[0])
    assert calls == [(5, "re")]
    assert _lookups("cache") == h0 + 2
    assert (_lookups("codes"), _lookups("postings")) == (c0, p0 + 1)


def test_result_cache_hits_and_version_invalidation():
    reg = _make_registry(n=500, hosts=4)
    ix = TagIndex(reg)
    m = [("host", "eq", "h1")]
    a = ix.match_sids(m)
    h0 = ix.stats()["hits"]
    b = ix.match_sids(m)
    assert ix.stats()["hits"] == h0 + 1
    np.testing.assert_array_equal(a, b)
    v0 = reg.version
    # a new series carrying h1 must appear despite the cached result
    reg.intern_rows([np.asarray(["h1"], object),
                     np.asarray(["rz"], object)])
    assert reg.version > v0
    c = ix.match_sids(m)
    assert len(c) == len(a) + 1
    np.testing.assert_array_equal(c, reg.match_sids(m))


def test_delta_tail_avoids_rebuild_then_rebuilds():
    _index.configure({"rebuild_threshold": 64})
    try:
        reg = _make_registry(n=300, hosts=6)
        ix = TagIndex(reg)
        ix.match_sids([("host", "eq", "h1")])
        b0 = ix.stats()["builds"]
        # small delta: evaluated from the tail, no re-sort
        reg.intern_rows([np.asarray(["h1"] * 10, object),
                         np.asarray(["rd"] * 10, object)])
        np.testing.assert_array_equal(
            ix.match_sids([("host", "eq", "h1")]),
            reg.match_sids([("host", "eq", "h1")]))
        assert ix.stats()["builds"] == b0
        # past the threshold: postings rebuild
        many = np.asarray([f"x{i}" for i in range(200)], object)
        reg.intern_rows([many, np.asarray(["rd"] * 200, object)])
        np.testing.assert_array_equal(
            ix.match_sids([("host", "ne", "h1")]),
            reg.match_sids([("host", "ne", "h1")]))
        assert ix.stats()["builds"] == b0 + 1
    finally:
        _index.configure({"rebuild_threshold": 4096})


def test_add_tag_widens_and_rebuilds():
    reg = _make_registry(n=200, hosts=3)
    ix = TagIndex(reg)
    ix.match_sids([("host", "eq", "h0")])
    reg.add_tag("dc")
    reg.intern_rows([np.asarray(["h0"], object),
                     np.asarray(["r0"], object),
                     np.asarray(["east"], object)])
    for m in ([("dc", "eq", "east")], [("dc", "eq", "")],
              [("host", "eq", "h0"), ("dc", "ne", "east")]):
        np.testing.assert_array_equal(
            ix.match_sids(m), reg.match_sids(m), err_msg=repr(m))


def test_disabled_index_falls_back_to_registry():
    reg = _make_registry(n=100)
    m = [("host", "eq", "h1")]
    _index.configure({"enable": False})
    try:
        c = global_registry.counter(
            "gtpu_index_lookups_total", labels=("path",)
        ).labels("host")
        v0 = c.value
        np.testing.assert_array_equal(
            _index.match_sids(reg, m), reg.match_sids(m))
        assert c.value == v0 + 1
    finally:
        _index.configure({"enable": True})


def test_matcher_key_normalizes():
    r = re.compile("h.*")
    assert _index.matcher_key([("host", "re", r)]) == \
        _index.matcher_key([("host", "re", re.compile("h.*"))])
    assert _index.matcher_key([("host", "in", ["b", "a"])]) == \
        _index.matcher_key([("host", "in", ("a", "b"))])


def test_registry_version_bumps():
    reg = SeriesRegistry(["host"])
    v = reg.version
    reg.intern_rows([np.asarray(["a", "b"], object)])
    assert reg.version > v
    v = reg.version
    reg.intern_rows([np.asarray(["a"], object)])  # no new series
    assert reg.version == v
    reg.ensure_series(2, ["c"])
    assert reg.version > v
    v = reg.version
    reg.add_tag("dc")
    assert reg.version > v
    restored = SeriesRegistry.restore(reg.snapshot())
    assert restored.version == len(restored)


def test_compile_matcher_memoized():
    from greptimedb_tpu.query.expr import compile_matcher

    a = compile_matcher("h[0-9]+")
    b = compile_matcher("h[0-9]+")
    assert a is b
    assert a.match("h12")


# -- device plane ------------------------------------------------------

def test_device_plane_mask_parity_and_census():
    reg = _make_registry(n=700, hosts=9)
    s_pad = 1024
    for m in CASES:
        if not m:
            continue
        out = device_plane.matcher_mask_dev(reg, m, s_pad)
        if out is None:  # constant-true-only sets fall back
            continue
        mask, any_match = out
        host = np.zeros(s_pad, bool)
        sids = reg.match_sids(m)
        host[sids] = True
        np.testing.assert_array_equal(np.asarray(mask), host,
                                      err_msg=repr(m))
        assert bool(any_match) == bool(host.any())
    # census invariant: pool-reported bytes == sum of buffer nbytes
    pool = device_plane._PlanePool()
    stats = pool.stats()
    bufs = list(pool.buffers())
    assert stats["bytes"] == sum(int(a.nbytes) for a, _ in bufs)
    assert stats["bytes"] > 0


def test_device_plane_invalidates_on_registry_growth():
    reg = _make_registry(n=100, hosts=3)
    m = [("host", "eq", "h1")]
    out = device_plane.matcher_mask_dev(reg, m, 256)
    assert out is not None
    reg.intern_rows([np.asarray(["h1"], object),
                     np.asarray(["rn"], object)])
    out2 = device_plane.matcher_mask_dev(reg, m, 256)
    assert out2 is not None
    host = np.zeros(256, bool)
    host[reg.match_sids(m)] = True
    np.testing.assert_array_equal(np.asarray(out2[0]), host)


# -- SST sid pruning ---------------------------------------------------

def _pruned_rg() -> float:
    return global_registry.counter(
        "gtpu_index_pruned_row_groups_total").labels().value


def _pruned_bytes(scope: str) -> float:
    return global_registry.counter(
        "gtpu_index_pruned_bytes_total", labels=("scope",)
    ).labels(scope).value


def test_sst_meta_carries_sid_range_and_prunes_row_groups(tmp_path):
    from greptimedb_tpu.storage.memtable import ColumnarRows
    from greptimedb_tpu.storage.object_store import FsObjectStore
    from greptimedb_tpu.storage.sst import read_sst, write_sst

    store = FsObjectStore(str(tmp_path / "store"))
    n = 4000
    rows = ColumnarRows(
        sid=np.arange(n, dtype=np.int32),
        ts=np.arange(n, dtype=np.int64) + 1000,
        seq=np.arange(n, dtype=np.int64),
        op=np.zeros(n, dtype=np.int8),
        fields={"v": np.arange(n, dtype=np.float64)},
    )
    meta = write_sst(store, "t.parquet", "f1", rows, row_group_rows=512)
    assert meta.sid_min == 0 and meta.sid_max == n - 1
    rg0, by0 = _pruned_rg(), _pruned_bytes("row_group")
    out = read_sst(store, meta, sids=np.asarray([5], np.int32))
    assert out is not None and out.sid.tolist() == [5]
    assert _pruned_rg() > rg0           # 7 of 8 groups dropped
    assert _pruned_bytes("row_group") > by0


def test_region_scan_skips_disjoint_ssts(tmp_path):
    import test_compaction as tc

    r = tc.make_region(tmp_path, trigger=100)
    # two flushes; the second one's sids extend past the first's
    tc.write_flush(r, ["a", "b"], [100, 101], [1.0, 2.0])
    tc.write_flush(r, ["c", "d"], [200, 201], [3.0, 4.0])
    metas = r.manifest.state.ssts
    assert len(metas) == 2
    assert metas[1].sid_min > metas[0].sid_max or \
        metas[0].sid_min > metas[1].sid_max
    by0 = _pruned_bytes("sst")
    sids = r.match_sids([("h", "eq", "d")])
    res = r.scan(sids=sids)
    assert res.rows.fields["v"].tolist() == [4.0]
    assert _pruned_bytes("sst") > by0   # whole first SST skipped
    r.close()


def test_index_pool_registered_with_accountant():
    from greptimedb_tpu.telemetry import memory

    reg = _make_registry(n=50)
    _index.index_for(reg).match_sids([("host", "eq", "h1")])
    pools = {p.name for p in memory.global_accountant.snapshot()}
    assert "tag_index" in pools


if __name__ == "__main__":
    import sys

    sys.exit(pytest.main([__file__, "-q"]))
