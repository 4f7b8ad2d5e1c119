"""Hash-groupby shuffle: the blocked cross-shard fold as a ring kernel.

The XLA path (parallel/dist.ShardFoldCtx) all-gathers every shard's
(fb_local, g, nb) partial blocks to every shard and left-folds the
gathered (FOLD_BLOCKS, g, nb) tensor — (ns-1) * fb_local * g * nb
elements received per device. The ring path moves only the (g, nb)
accumulator: shard 0 folds its local blocks, the accumulator walks the
ring while each shard folds its blocks on top in shard order, and the
total walks once more so every shard ends with it — 2(ns-1) hops of
g * nb elements. The fold bodies are Pallas kernels (the same
unrolled static add chain as dist.left_fold_sum, so the bit-identity
contract across mesh 1/2/4/8 is preserved by construction) — compiled
by Mosaic on a TPU backend, run by the Pallas interpreter elsewhere —
and the hops are ppermute (ICI collective-permute) on both.
"""

from __future__ import annotations

from greptimedb_tpu.parallel.dist import ShardFoldCtx
from greptimedb_tpu.parallel.kernels.base import (
    interpret_mode,
    ring_comm_bytes,
    sequential_ring,
)


# ----------------------------------------------------------------------
# kernel bodies
# ----------------------------------------------------------------------

def _fold_seed_kernel(blocks_ref, out_ref):
    """Left fold of the local partial blocks. The accumulator STARTS at
    blocks[0] — never zeros + add: x + 0.0 maps -0.0 to +0.0, which
    would break bit-identity against dist.left_fold_sum."""
    acc = blocks_ref[0]
    for i in range(1, blocks_ref.shape[0]):
        acc = acc + blocks_ref[i]
    out_ref[...] = acc


def _fold_cont_kernel(acc_ref, blocks_ref, out_ref):
    """Continue the left fold: the ring accumulator (the prefix of all
    earlier shards' blocks) plus the local blocks, in block order."""
    acc = acc_ref[...]
    for i in range(blocks_ref.shape[0]):
        acc = acc + blocks_ref[i]
    out_ref[...] = acc


def _ext_max_kernel(a_ref, b_ref, out_ref):
    import jax.numpy as jnp

    out_ref[...] = jnp.maximum(a_ref[...], b_ref[...])


def _ext_min_kernel(a_ref, b_ref, out_ref):
    import jax.numpy as jnp

    out_ref[...] = jnp.minimum(a_ref[...], b_ref[...])


def _add_kernel(a_ref, b_ref, out_ref):
    out_ref[...] = a_ref[...] + b_ref[...]


def _call1(kernel, a, *, interpret):
    import jax
    from jax.experimental import pallas as pl

    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct(a.shape[1:], a.dtype),
        interpret=interpret,
    )(a)


def _call2(kernel, a, b, *, interpret):
    import jax
    from jax.experimental import pallas as pl

    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct(a.shape, a.dtype),
        interpret=interpret,
    )(a, b)


# ----------------------------------------------------------------------
# ring programs (called from inside shard_map bodies)
# ----------------------------------------------------------------------

def ring_fold_blocks(parts, ns: int, *, interpret: bool):
    """parts: the local (fb_local, g, nb) partial blocks of one shard.
    Returns the (g, nb) global fold, identical on every shard and
    bit-identical to dist.left_fold_sum(dist.gather_blocks(parts))."""
    seed = _call1(_fold_seed_kernel, parts, interpret=interpret)

    def cont(acc):
        return _call2(_fold_cont_kernel, acc, parts, interpret=interpret)

    return sequential_ring(seed, cont, ns)


def ring_pext(x, ns: int, *, take_max: bool, interpret: bool):
    """Cross-shard elementwise extreme around the ring. min/max are
    exactly associative, so the sequential order matches pmin/pmax
    bit-for-bit (NaN propagates through jnp.minimum/maximum exactly as
    through the XLA all-reduce)."""
    kernel = _ext_max_kernel if take_max else _ext_min_kernel

    def comb(acc):
        return _call2(kernel, acc, x, interpret=interpret)

    return sequential_ring(x, comb, ns)


def ring_psum_onehot(x, ns: int, *, interpret: bool):
    """Cross-shard sum around the ring for MASKED ONE-NONZERO payloads
    (the staged first/last winner extraction: per element, exactly one
    shard contributes the winner value, every other shard contributes
    +0.0). x + 0.0 is exact for every x except -0.0 -> +0.0 — and the
    psum path normalizes -0.0 the same way — so the sequential order is
    bit-identical to jax.lax.psum for this payload shape. NOT exact for
    general summands; those go through ring_fold_blocks."""

    def comb(acc):
        return _call2(_add_kernel, acc, x, interpret=interpret)

    return sequential_ring(x, comb, ns)


def fold_comm_bytes(ns: int, g: int, nb: int, passes: int = 1) -> int:
    """Declared inter-chip traffic of `passes` ring passes over a
    (g, nb) f32 accumulator."""
    return ring_comm_bytes(ns, 4 * int(g) * int(nb)) * max(int(passes), 1)


# ----------------------------------------------------------------------
# the fold ctx the sharded query programs thread through _range_body
# ----------------------------------------------------------------------

class RingFoldCtx(ShardFoldCtx):
    """Kernel-path twin of dist.ShardFoldCtx: the same hooks the
    sharded query bodies thread (query/device_range._range_body,
    query/reduce._sharded_fused_program), with the ring kernels behind
    them. Each hook is bit-identical to its collective counterpart for
    the payload shapes those bodies produce (see the ring_* docstrings
    for the exactness argument per hook)."""

    def __init__(self, shards: int, *, interpret: bool | None = None):
        super().__init__(shards)
        self._interp = interpret_mode() if interpret is None else interpret

    def fold_blocks(self, partial):
        return ring_fold_blocks(partial, self.shards,
                                interpret=self._interp)

    def pext(self, x, take_max: bool):
        return ring_pext(x, self.shards, take_max=take_max,
                         interpret=self._interp)

    def psum(self, x):
        return ring_psum_onehot(x, self.shards, interpret=self._interp)
