"""Distributed query kernels: shard_map programs with explicit collectives.

The three distribution patterns of the reference, re-expressed over ICI
(SURVEY.md §2.7 mapping):

1. dist_segment_agg  — commutative aggregate push-down + merge: each series
   shard computes full-width partial aggregates, psum/pmin/pmax recombines
   (replaces MergeScanExec + frontend final-aggregate).
2. halo_exchange     — ring transfer of window-tail cells between adjacent
   time shards (replaces PartitionRange overlap handling; the sequence-
   parallel primitive for windows crossing block boundaries).
3. dist_topk         — per-shard top-k, all_gather, re-select (replaces
   frontend sort+limit over gathered partials).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from greptimedb_tpu.ops import segment as S
from greptimedb_tpu.parallel.mesh import AXIS_SHARD, AXIS_TIME


def dist_segment_agg(mesh: Mesh, op: str, num_segments: int):
    """Build a shard_map'd segmented aggregate: rows sharded over AXIS_SHARD,
    output replicated. op in {sum, count, min, max, mean}."""

    def local(values, seg, mask):
        if op == "sum":
            part = S.seg_sum(values, seg, mask, num_segments)
            return jax.lax.psum(part, AXIS_SHARD)
        if op == "count":
            part = S.seg_count(seg, mask, num_segments)
            return jax.lax.psum(part, AXIS_SHARD)
        if op == "min":
            part = S.seg_min(values, seg, mask, num_segments)
            return jax.lax.pmin(part, AXIS_SHARD)
        if op == "max":
            part = S.seg_max(values, seg, mask, num_segments)
            return jax.lax.pmax(part, AXIS_SHARD)
        if op == "mean":
            s = jax.lax.psum(S.seg_sum(values, seg, mask, num_segments),
                             AXIS_SHARD)
            c = jax.lax.psum(S.seg_count(seg, mask, num_segments), AXIS_SHARD)
            return s / jnp.maximum(c, 1).astype(s.dtype)
        raise ValueError(op)

    return shard_map(
        local,
        mesh=mesh,
        in_specs=(P(AXIS_SHARD), P(AXIS_SHARD), P(AXIS_SHARD)),
        out_specs=P(),
        check_vma=False,
    )


def _halo_prev(x: jax.Array, halo: int, axis_name: str, axis: int, fill):
    """Ring halo: prepend the last `halo` cells (along `axis`) of the
    PREVIOUS shard on `axis_name`; the first shard gets `fill`."""
    # jax.lax.axis_size was removed from current JAX; psum of a python
    # literal folds to the static axis size inside shard_map
    n = jax.lax.psum(1, axis_name)
    tail = jax.lax.slice_in_dim(x, x.shape[axis] - halo, x.shape[axis],
                                axis=axis)
    # ring shift: device i receives from i-1
    perm = [(i, (i + 1) % n) for i in range(n)]
    prev_tail = jax.lax.ppermute(tail, axis_name, perm)
    idx = jax.lax.axis_index(axis_name)
    prev_tail = jnp.where(idx == 0,
                          jnp.full_like(prev_tail, fill), prev_tail)
    return jnp.concatenate([prev_tail, x], axis=axis)


def halo_exchange_prev(x: jax.Array, halo: int, axis_name: str = AXIS_TIME):
    """Prepend the last `halo` cells of the previous time shard (zeros for
    the first shard). x is the local (S, T_local) block inside shard_map;
    returns (S, halo + T_local)."""
    return _halo_prev(x, halo, axis_name, axis=1, fill=0.0)


def dist_topk(mesh: Mesh, k: int, *, largest: bool = True):
    """Distributed top-k over a sharded 1-D value array: local top-k,
    all_gather the candidates, re-select. Returns (values, global_indices)."""

    def local(values, mask):
        n_local = values.shape[0]
        fill = jnp.asarray(-jnp.inf if largest else jnp.inf, values.dtype)
        v = jnp.where(mask, values, fill)
        vv = v if largest else -v
        loc_v, loc_i = jax.lax.top_k(vv, min(k, n_local))
        shard = jax.lax.axis_index(AXIS_SHARD)
        glob_i = loc_i + shard * n_local
        all_v = jax.lax.all_gather(loc_v, AXIS_SHARD).reshape(-1)
        all_i = jax.lax.all_gather(glob_i, AXIS_SHARD).reshape(-1)
        top_v, sel = jax.lax.top_k(all_v, k)
        if not largest:
            top_v = -top_v
        return top_v, all_i[sel]

    return shard_map(
        local,
        mesh=mesh,
        in_specs=(P(AXIS_SHARD), P(AXIS_SHARD)),
        out_specs=(P(), P()),
        check_vma=False,
    )


# ----------------------------------------------------------------------
# shard_map building blocks for the LIVE query path (query/reduce.py,
# query/device_range.py, promql/fast.py, query/window_fns.py). All cross
# -shard combines that touch f32 sums go through gather_blocks +
# left_fold so the addition order is identical to the single-device
# blocked fold (parallel/mesh.FOLD_BLOCKS) — sharded results match the
# unsharded path bit-for-bit for decomposable aggregates.
# ----------------------------------------------------------------------

def halo_prev_1d(x: jax.Array, halo: int, axis_name: str = AXIS_SHARD,
                 fill=0.0):
    """Prepend the last `halo` cells of the PREVIOUS shard of a 1-D
    row-sharded array (the first shard gets `fill`). The sliding-window
    primitive for frames crossing shard boundaries
    (query/window_fns.py ROWS k PRECEDING)."""
    return _halo_prev(x, halo, axis_name, axis=0, fill=fill)


def gather_blocks(partial: jax.Array, axis_name: str = AXIS_SHARD):
    """Concatenate per-shard partial blocks along axis 0 in shard order:
    (B_local, ...) -> (B_local * n_shards, ...). Pure data movement —
    exact."""
    return jax.lax.all_gather(partial, axis_name, axis=0, tiled=True)


def left_fold_sum(parts: jax.Array):
    """Sum over axis 0 as an explicit unrolled left fold. The static add
    chain is the contract: both the sharded (post-gather) and unsharded
    blocked folds run this exact sequence, so f32 results agree
    bit-for-bit across mesh sizes."""
    total = parts[0]
    for i in range(1, parts.shape[0]):
        total = total + parts[i]
    return total


def pext(x: jax.Array, axis_name: str = AXIS_SHARD, *,
         take_max: bool = True):
    """Cross-shard elementwise extreme (exact for any association)."""
    return (jax.lax.pmax if take_max else jax.lax.pmin)(x, axis_name)


class LocalFoldCtx:
    """Cross-shard hooks for blocked exact folds. This single-device
    instance is the identity; ShardFoldCtx recombines with collectives.
    Both fold the SAME per-block partials in the SAME left-fold order,
    so sharded and unsharded results agree bit-for-bit."""

    shards = 1

    def sid_base(self, s_local: int):
        return jnp.int32(0)

    def sids(self, s_local: int):
        """Absolute series id of each local row: the (ts, sid) tie-break
        of first/last folds must not depend on how the rows got here."""
        return self.sid_base(s_local) + jnp.arange(s_local, dtype=jnp.int32)

    def gather(self, partial):
        return partial

    def fold_blocks(self, partial):
        """Gather the per-shard partial blocks and run the canonical
        unrolled left fold — THE cross-shard sum seam."""
        return left_fold_sum(self.gather(partial))

    def pext(self, x, take_max: bool):
        return x

    def psum(self, x):
        return x


class RowsFoldCtx(LocalFoldCtx):
    """LocalFoldCtx over rows gathered from the plane: row i is series
    `row_sids[i]`, not series i."""

    def __init__(self, row_sids):
        self.row_sids = row_sids

    def sids(self, s_local: int):
        return self.row_sids


class ShardFoldCtx(LocalFoldCtx):
    """Collective fold hooks for code running INSIDE shard_map."""

    def __init__(self, shards: int):
        self.shards = shards

    def sid_base(self, s_local: int):
        return jax.lax.axis_index(AXIS_SHARD) * jnp.int32(s_local)

    def gather(self, partial):
        return gather_blocks(partial)

    def pext(self, x, take_max: bool):
        return pext(x, take_max=take_max)

    def psum(self, x):
        return jax.lax.psum(x, AXIS_SHARD)


def shard_rows_sharding(mesh: Mesh) -> NamedSharding:
    """Sharding for row-oriented scan outputs: rows split over AXIS_SHARD."""
    return NamedSharding(mesh, P(AXIS_SHARD))


def grid_sharding(mesh: Mesh) -> NamedSharding:
    """Sharding for (S, T) grids: series over AXIS_SHARD, time over
    AXIS_TIME."""
    return NamedSharding(mesh, P(AXIS_SHARD, AXIS_TIME))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())
