"""Pallas kernels (parallel/kernels): interpret-mode parity against
the XLA collective paths on the 8-virtual-device CPU mesh
(ISSUE 17)."""

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from jax import shard_map
import pytest

from greptimedb_tpu.parallel import dist, mesh as M
from greptimedb_tpu.parallel import kernels as K
from greptimedb_tpu.parallel.kernels import topk_merge as tm

NS = 8


@pytest.fixture(scope="module")
def mesh8():
    return M.make_mesh(jax.devices())  # shard=8, time=1


def _bits(a: np.ndarray) -> np.ndarray:
    """View through the unsigned twin so -0.0 vs +0.0 and NaN payloads
    compare by bit pattern."""
    a = np.asarray(a)
    if a.dtype.kind == "f":
        return a.view(np.dtype(f"u{a.dtype.itemsize}"))
    return a


def _smap(mesh, body, spec_in, *args):
    darg = [
        jax.device_put(jnp.asarray(a), NamedSharding(mesh, s))
        for a, s in zip(args, spec_in)
    ]
    return shard_map(
        body, mesh=mesh, in_specs=tuple(spec_in),
        out_specs=P(M.AXIS_SHARD), check_vma=False,
    )(*darg)


def test_ring_fold_bit_identical_to_gather_fold(mesh8, rng):
    fb, g, nb = 3, 5, 16
    x = rng.standard_normal((NS * fb, g, nb)).astype(np.float32)
    x[rng.random(x.shape) < 0.05] = -0.0
    x[rng.random(x.shape) < 0.05] = 0.0
    spec = [P(M.AXIS_SHARD, None, None)]

    def body_xla(parts):
        return dist.ShardFoldCtx(NS).fold_blocks(parts)[None]

    def body_ring(parts):
        return K.RingFoldCtx(NS, interpret=True).fold_blocks(parts)[None]

    a = np.asarray(_smap(mesh8, body_xla, spec, x))    # (NS, g, nb)
    b = np.asarray(_smap(mesh8, body_ring, spec, x))
    # identical on every shard, and bit-identical across paths
    for s in range(NS):
        assert np.array_equal(_bits(a[s]), _bits(a[0]))
        assert np.array_equal(_bits(b[s]), _bits(b[0]))
    assert np.array_equal(_bits(a), _bits(b))


@pytest.mark.parametrize("take_max", [False, True])
def test_ring_pext_matches_collective(mesh8, rng, take_max):
    g = 96
    # finite + ±inf payloads: the engine masks absent cells with ±inf
    # sentinels before pext, and NaN-vs-pmax semantics are backend
    # defined (the documented exception in README "Pallas kernels")
    x = rng.standard_normal((NS, g)).astype(np.float32)
    x[rng.random(x.shape) < 0.04] = np.inf
    x[rng.random(x.shape) < 0.04] = -np.inf
    x[rng.random(x.shape) < 0.04] = -0.0
    spec = [P(M.AXIS_SHARD, None)]

    def body_xla(xl):
        return dist.ShardFoldCtx(NS).pext(xl[0], take_max=take_max)[None]

    def body_ring(xl):
        ctx = K.RingFoldCtx(NS, interpret=True)
        return ctx.pext(xl[0], take_max=take_max)[None]

    a = np.asarray(_smap(mesh8, body_xla, spec, x))
    b = np.asarray(_smap(mesh8, body_ring, spec, x))
    assert np.array_equal(_bits(a), _bits(b))


def test_ring_psum_onehot_matches_psum(mesh8, rng):
    g = 128
    # masked one-nonzero payload: exactly one shard contributes per slot
    winner = rng.integers(0, NS, g)
    x = np.zeros((NS, g), np.float32)
    x[winner, np.arange(g)] = rng.standard_normal(g).astype(np.float32)
    spec = [P(M.AXIS_SHARD, None)]

    def body_xla(xl):
        return dist.ShardFoldCtx(NS).psum(xl[0])[None]

    def body_ring(xl):
        return K.RingFoldCtx(NS, interpret=True).psum(xl[0])[None]

    a = np.asarray(_smap(mesh8, body_xla, spec, x))
    b = np.asarray(_smap(mesh8, body_ring, spec, x))
    assert np.array_equal(_bits(a), _bits(b))


def test_ring_topk_merge_matches_all_gather_reselect(mesh8, rng):
    j, kl, k = 6, 5, 9
    key = rng.standard_normal((NS, j, kl)).astype(np.float32)
    # force cross-shard ties and absent (-inf) candidates
    key[rng.random(key.shape) < 0.2] = 0.5
    key[rng.random(key.shape) < 0.1] = -np.inf
    key = -np.sort(-key, axis=2)  # descending per shard, like top_k
    val = rng.standard_normal((NS, j, kl)).astype(np.float32)
    val[rng.random(val.shape) < 0.05] = -0.0
    idx = rng.integers(0, 10_000, (NS, j, kl)).astype(np.int32)
    pres = rng.random((NS, j, kl)) < 0.9
    spec = [P(M.AXIS_SHARD, None, None)] * 4

    def body_xla(ks, vs, is_, ps):
        cat = lambda x: jax.lax.all_gather(  # noqa: E731
            x[0], M.AXIS_SHARD, axis=1, tiled=True
        )
        c_key = cat(ks)
        f_key, f_pos = jax.lax.top_k(c_key, k)
        take = lambda p: jnp.take_along_axis(p, f_pos, axis=1)  # noqa: E731
        return jnp.stack([
            f_key, take(cat(vs)),
            take(cat(is_).astype(jnp.float32)),
            take(cat(ps)).astype(jnp.float32) * jnp.isfinite(f_key),
        ])[None]

    def body_ring(ks, vs, is_, ps):
        ok, ov, oi, op_ = tm.ring_topk_merge(
            ks[0], vs[0], is_[0], ps[0], k=k, ns=NS, interpret=True,
        )
        return jnp.stack([
            ok, ov, oi.astype(jnp.float32),
            (op_ & jnp.isfinite(ok)).astype(jnp.float32),
        ])[None]

    a = np.asarray(_smap(mesh8, body_xla, spec, key, val, idx, pres))
    b = np.asarray(_smap(mesh8, body_ring, spec, key, val, idx, pres))
    for s in range(NS):
        assert np.array_equal(_bits(b[s]), _bits(b[0]))
    # finite-key slots (real candidates) are bit-identical — values,
    # indices, tie-breaks; -inf fill slots are the documented exception
    fin = np.isfinite(a[0, 0])
    assert np.array_equal(fin, np.isfinite(b[0, 0]))
    for plane in range(4):
        pa, pb = a[0, plane][fin], b[0, plane][fin]
        assert np.array_equal(_bits(pa), _bits(pb)), plane


@pytest.mark.parametrize(
    "largest",
    [True, pytest.param(False, marks=pytest.mark.slow)],
)
def test_dist_topk_kernel_parity(mesh8, rng, largest):
    n, k = 256, 7
    vals = rng.standard_normal(n).astype(np.float32)  # continuous: no ties
    mask = rng.random(n) > 0.1
    sharding = dist.shard_rows_sharding(mesh8)
    dv = jax.device_put(jnp.array(vals), sharding)
    dm = jax.device_put(jnp.array(mask), sharding)
    v0, i0 = dist.dist_topk(mesh8, k, largest=largest)(dv, dm)
    v1, i1 = dist.dist_topk(mesh8, k, largest=largest,
                            kernel=True, interpret=True)(dv, dm)
    fin = np.isfinite(np.asarray(v0))
    assert np.array_equal(_bits(np.asarray(v0)[fin]),
                          _bits(np.asarray(v1)[fin]))
    assert np.array_equal(np.asarray(i0)[fin], np.asarray(i1)[fin])



def test_collective_attribution_on_program_registry():
    from greptimedb_tpu.telemetry import device_programs, device_trace
    from greptimedb_tpu.telemetry.metrics import global_registry

    fn = jax.jit(lambda x: x * 2)
    with device_trace.device_call(
            "kernel_attr_test", key=("k", 1),
            collective=True, comm_bytes=12345) as d:
        out = d.run(fn, jnp.arange(8.0))
        out.block_until_ready()
        d.executed()
    rows = [r for r in device_programs.global_programs.snapshot(
        analyze=False) if r["site"] == "kernel_attr_test"]
    assert rows and rows[0]["collective"] is True
    assert rows[0]["comm_bytes"] == 12345
    text = global_registry.render()
    assert "gtpu_device_program_comm_bytes_total" in text
    assert 'site="kernel_attr_test"' in text
