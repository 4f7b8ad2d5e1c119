"""TSBS benchmark query programs (the north-star workload, BASELINE.md).

These are the fused device pipelines the physical planner lowers recognized
query shapes onto. The reference executes the same queries through
DataFusion hash-aggregates on the datanode
(/root/reference/src/query/src/datafusion.rs); here each query is one XLA
program over (series, time) grids.

TSBS devops/cpu-only queries (docs/benchmarks/tsbs in the reference):
- double-groupby-N: mean of N cpu fields GROUP BY (hostname, hour) over 12h
- cpu-max-all-N: max of all 10 fields per hour for N hosts
- single-groupby-1-1-1: 1 field, 1 host, 5-minute buckets over 1h
- groupby-orderby-limit: max per 1-minute bucket, last 5 buckets
- high-cpu-N: rows where usage_user > 90 for N hosts
- lastpoint: latest row per host
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from greptimedb_tpu.ops import segment as S
from greptimedb_tpu.parallel.mesh import AXIS_SHARD, AXIS_TIME


@functools.partial(jax.jit, static_argnames=("cells_per_bucket",))
def groupby_time_mean(vals: jax.Array, has: jax.Array, cells_per_bucket: int):
    """mean per (series, time-bucket): (S, T) -> (S, T // cpb).

    The double-groupby kernel: with hostname already the series axis and
    hour = cells_per_bucket grid cells, GROUP BY (hostname, hour) is a
    reshape + masked mean — no hashing at all."""
    s, t = vals.shape
    nb = t // cells_per_bucket
    v = jnp.where(has, vals, 0).reshape(s, nb, cells_per_bucket)
    m = has.reshape(s, nb, cells_per_bucket)
    cnt = jnp.sum(m, axis=2)
    out = jnp.sum(v, axis=2) / jnp.maximum(cnt, 1).astype(vals.dtype)
    return out, cnt > 0


@functools.partial(jax.jit, static_argnames=("cells_per_bucket",))
def groupby_time_max(vals: jax.Array, has: jax.Array, cells_per_bucket: int):
    s, t = vals.shape
    nb = t // cells_per_bucket
    v = jnp.where(has, vals, -jnp.inf).reshape(s, nb, cells_per_bucket)
    m = has.reshape(s, nb, cells_per_bucket)
    present = jnp.any(m, axis=2)
    out = jnp.max(v, axis=2)
    return jnp.where(present, out, 0), present


@functools.partial(jax.jit, static_argnames=("cells_per_bucket",))
def double_groupby(fields: jax.Array, has: jax.Array, cells_per_bucket: int):
    """TSBS double-groupby-N: fields (F, S, T) -> (F, S, H) hourly means."""
    f, s, t = fields.shape
    nb = t // cells_per_bucket
    v = jnp.where(has[None], fields, 0).reshape(f, s, nb, cells_per_bucket)
    m = has.reshape(1, s, nb, cells_per_bucket)
    cnt = jnp.sum(m, axis=3)
    out = jnp.sum(v, axis=3) / jnp.maximum(cnt, 1).astype(fields.dtype)
    return out, (cnt > 0)[0]


@functools.partial(jax.jit, static_argnames=("threshold",))
def high_cpu_mask(gate_field: jax.Array, has: jax.Array, threshold: float):
    """high-cpu-N predicate: cells where the gate field exceeds threshold."""
    return has & (gate_field > jnp.asarray(threshold, gate_field.dtype))


@jax.jit
def lastpoint(vals: jax.Array, has: jax.Array, tsg: jax.Array):
    """Latest sample per series: (S,) values + ts + presence."""
    t = vals.shape[1]
    i = jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32), has.shape)
    li = jnp.max(jnp.where(has, i, -1), axis=1)
    present = li >= 0
    safe = jnp.maximum(li, 0)
    v = jnp.take_along_axis(vals, safe[:, None], axis=1)[:, 0]
    ts = jnp.take_along_axis(tsg, safe[:, None], axis=1)[:, 0]
    return v, ts, present


def build_distributed_query_step(
    mesh: Mesh, t_global: int, cells_per_bucket: int, k: int
):
    """The full multi-device query step used by __graft_entry__'s
    dryrun_multichip: grids sharded (series x time) over the mesh.

    Per device: partial (sum, count) per *global* time bucket via a one-hot
    matmul (rides the MXU) -> psum over the time axis (buckets crossing
    block boundaries recombine exactly) -> double-groupby means; then a
    global top-k over per-series totals: local top_k, all_gather over the
    series axis, re-select. All collectives ride ICI."""
    n_time = mesh.shape[AXIS_TIME]
    assert t_global % n_time == 0
    t_local = t_global // n_time
    nb = max(t_global // cells_per_bucket, 1)

    def local(fields, has):
        # fields: (F, S_local, T_local); has: (S_local, T_local)
        q = jax.lax.axis_index(AXIS_TIME)
        gidx = q * t_local + jnp.arange(t_local, dtype=jnp.int32)
        bucket = jnp.minimum(gidx // cells_per_bucket, nb - 1)
        onehot = jax.nn.one_hot(bucket, nb, dtype=fields.dtype)  # (T_l, NB)
        v = jnp.where(has[None], fields, 0)
        ps = jnp.einsum("fst,tb->fsb", v, onehot)
        pc = jnp.einsum("st,tb->sb", has.astype(fields.dtype), onehot)
        gs = jax.lax.psum(ps, AXIS_TIME)
        gc = jax.lax.psum(pc, AXIS_TIME)
        means = gs / jnp.maximum(gc, 1)[None]          # (F, S_l, NB)
        # per-series total across fields+buckets for the top-k stage
        series_score = jnp.sum(means, axis=(0, 2))
        n_local = series_score.shape[0]
        kk = min(k, n_local)
        loc_v, loc_i = jax.lax.top_k(series_score, kk)
        shard = jax.lax.axis_index(AXIS_SHARD)
        glob_i = loc_i + shard * n_local
        all_v = jax.lax.all_gather(loc_v, AXIS_SHARD).reshape(-1)
        all_i = jax.lax.all_gather(glob_i, AXIS_SHARD).reshape(-1)
        top_v, sel = jax.lax.top_k(all_v, kk)
        return means, top_v, all_i[sel]

    return shard_map(
        local,
        mesh=mesh,
        in_specs=(P(None, AXIS_SHARD, AXIS_TIME), P(AXIS_SHARD, AXIS_TIME)),
        out_specs=(P(None, AXIS_SHARD, None), P(), P()),
        check_vma=False,
    )
