"""Distributed kernels over the 8-device CPU mesh: sharded aggregates match
single-device results exactly."""

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from jax import shard_map
import pytest

from greptimedb_tpu.ops import segment as S
from greptimedb_tpu.parallel import dist, mesh as M
from greptimedb_tpu.models import tsbs


@pytest.fixture(scope="module")
def mesh8():
    return M.make_mesh(jax.devices(), time_parallel=2)  # (4 shard, 2 time)


def test_mesh_axes(mesh8):
    assert mesh8.shape == {"shard": 4, "time": 2}


def test_dist_segment_agg_matches_local(mesh8, rng):
    n, g = 1024, 37
    vals = rng.normal(size=n).astype(np.float32)
    seg = rng.integers(0, g, n).astype(np.int32)
    mask = rng.random(n) > 0.15

    sharding = dist.shard_rows_sharding(mesh8)
    dv = jax.device_put(jnp.array(vals), sharding)
    ds = jax.device_put(jnp.array(seg), sharding)
    dm = jax.device_put(jnp.array(mask), sharding)

    for op in ("sum", "count", "min", "max", "mean"):
        got = np.asarray(dist.dist_segment_agg(mesh8, op, g)(dv, ds, dm))
        if op == "sum":
            want = S.seg_sum(jnp.array(vals), jnp.array(seg), jnp.array(mask), g)
        elif op == "count":
            want = S.seg_count(jnp.array(seg), jnp.array(mask), g)
        elif op == "min":
            want = S.seg_min(jnp.array(vals), jnp.array(seg), jnp.array(mask), g)
        elif op == "max":
            want = S.seg_max(jnp.array(vals), jnp.array(seg), jnp.array(mask), g)
        else:
            want = S.seg_mean(jnp.array(vals), jnp.array(seg), jnp.array(mask), g)[0]
        np.testing.assert_allclose(got, np.asarray(want), rtol=1e-5,
                                   err_msg=op)


def test_halo_exchange_window_sum(mesh8, rng):
    s, t = 16, 64
    halo = 8
    x = rng.random((s, t)).astype(np.float32)
    dx = jax.device_put(
        jnp.array(x), NamedSharding(mesh8, P(M.AXIS_SHARD, M.AXIS_TIME))
    )

    def windowed(xl):
        xh = dist.halo_exchange_prev(xl, halo, M.AXIS_TIME)
        c = jnp.cumsum(xh, axis=1)
        return c[:, halo:] - c[:, :-halo]

    got = np.asarray(shard_map(
        windowed, mesh=mesh8,
        in_specs=P(M.AXIS_SHARD, M.AXIS_TIME),
        out_specs=P(M.AXIS_SHARD, M.AXIS_TIME),
        check_vma=False,
    )(dx))
    c = np.cumsum(np.pad(x, ((0, 0), (halo, 0))), axis=1)
    want = c[:, halo:] - c[:, :-halo]
    np.testing.assert_allclose(got, want, rtol=1e-5)


@pytest.mark.parametrize("k", [1, 8, 130])
@pytest.mark.parametrize("largest", [True, False])
def test_dist_topk(mesh8, rng, largest, k):
    """Values AND indices equal single-device lax.top_k: few distinct
    values (ties everywhere, the lower index wins), 60% of the rows
    masked (at k=130 the fill slots are reached), and at k=130 a shard
    holds 64 rows, fewer than k."""
    n = 256
    vals = rng.integers(-4, 5, n).astype(np.float32)
    mask = rng.random(n) > 0.6
    sharding = dist.shard_rows_sharding(mesh8)
    dv = jax.device_put(jnp.array(vals), sharding)
    dm = jax.device_put(jnp.array(mask), sharding)
    top_v, top_i = dist.dist_topk(mesh8, k, largest=largest)(dv, dm)
    sign = 1.0 if largest else -1.0
    want_v, want_i = jax.lax.top_k(
        jnp.where(jnp.array(mask), sign * jnp.array(vals), -jnp.inf), k)
    np.testing.assert_array_equal(np.asarray(top_v),
                                  sign * np.asarray(want_v))
    np.testing.assert_array_equal(np.asarray(top_i), np.asarray(want_i))
    assert int(mask.sum()) < 130 <= n  # k=130 does reach the fill


def _bits(a) -> np.ndarray:
    """Through the unsigned twin: -0.0 against +0.0 and NaN payloads
    compare by bit pattern."""
    return np.asarray(a).view(np.uint32)


@pytest.mark.parametrize("ns", [1, 2, 4, 8])
def test_blocked_fold_is_bit_identical_across_mesh_sizes(rng, ns):
    """The cross-shard sum seam (gather_blocks + the unrolled left
    fold) over FOLD_BLOCKS partial blocks split over ns shards equals
    the single-device fold bit for bit, on planes holding NaNs with
    payloads, -0.0, +0.0 and both infinities, and every shard holds
    the same answer."""
    g, nb = 5, 16
    x = rng.standard_normal((M.FOLD_BLOCKS, g, nb)).astype(np.float32)
    x[rng.random(x.shape) < 0.05] = -0.0
    x[rng.random(x.shape) < 0.05] = 0.0
    x[rng.random(x.shape) < 0.02] = np.inf
    x[rng.random(x.shape) < 0.02] = -np.inf
    nans = rng.random(x.shape) < 0.03
    x.view(np.uint32)[nans] = (
        0x7FC00000 | rng.integers(1, 1 << 22, int(nans.sum()))
    ).astype(np.uint32)
    x[:, 0, 0] = -0.0  # a sum of nothing but -0.0 stays -0.0
    want = np.asarray(jax.jit(dist.LocalFoldCtx().fold_blocks)(
        jnp.asarray(x)))
    mesh = M.make_mesh(jax.devices()[:ns])
    spec = P(M.AXIS_SHARD, None, None)
    got = np.asarray(shard_map(
        lambda parts: dist.ShardFoldCtx(ns).fold_blocks(parts)[None],
        mesh=mesh, in_specs=(spec,), out_specs=spec, check_vma=False,
    )(jax.device_put(jnp.asarray(x), NamedSharding(mesh, spec))))
    assert got.shape == (ns, g, nb)
    assert np.signbit(want[0, 0]) and want[0, 0] == 0.0
    assert np.isnan(want).any() and np.isinf(want).any()
    for shard in range(ns):
        assert np.array_equal(_bits(got[shard]), _bits(want)), shard


def test_distributed_double_groupby_matches_single(mesh8, rng):
    f, s, t, cpb, k = 3, 32, 48, 12, 5
    fields = rng.random((f, s, t)).astype(np.float32)
    has = rng.random((s, t)) > 0.2

    df = jax.device_put(
        jnp.array(fields),
        NamedSharding(mesh8, P(None, M.AXIS_SHARD, M.AXIS_TIME)),
    )
    dh = jax.device_put(
        jnp.array(has), NamedSharding(mesh8, P(M.AXIS_SHARD, M.AXIS_TIME))
    )
    step = tsbs.build_distributed_query_step(mesh8, t, cpb, k)
    means, top_v, top_i = step(df, dh)

    want_means, _ = tsbs.double_groupby(jnp.array(fields), jnp.array(has), cpb)
    np.testing.assert_allclose(np.asarray(means), np.asarray(want_means),
                               rtol=1e-5)
    score = np.asarray(want_means).sum(axis=(0, 2))
    want_top = np.sort(score)[::-1][:k]
    np.testing.assert_allclose(np.asarray(top_v), want_top, rtol=1e-5)


def test_lastpoint(rng):
    s, t = 10, 30
    vals = rng.random((s, t)).astype(np.float32)
    has = rng.random((s, t)) > 0.5
    tsg = np.broadcast_to(np.arange(t, dtype=np.int32) * 100, (s, t)).copy()
    v, ts, p = tsbs.lastpoint(jnp.array(vals), jnp.array(has), jnp.array(tsg))
    v, ts, p = map(np.asarray, (v, ts, p))
    for i in range(s):
        idx = np.nonzero(has[i])[0]
        if len(idx):
            assert p[i]
            assert v[i] == vals[i, idx[-1]]
            assert ts[i] == tsg[i, idx[-1]]
        else:
            assert not p[i]
