"""PromQL function semantics over grid windows.

The TPU reimplementation of the reference's range-function kernel set
(/root/reference/src/promql/src/functions/: extrapolate_rate.rs,
aggr_over_time.rs, changes.rs, resets.rs, idelta.rs, deriv.rs,
predict_linear.rs, holt_winters.rs, quantile.rs) plus histogram_quantile
folding (/root/reference/src/promql/src/extension_plan/histogram_fold.rs).

Each function maps (vals, has, tsg) grids + Windows onto (S, J) outputs with
presence masks. Dispatch is by name so the PromQL planner stays declarative.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from greptimedb_tpu.ops import window as W

RANGE_FUNCTIONS = frozenset({
    "rate", "increase", "delta", "idelta", "irate",
    "avg_over_time", "sum_over_time", "count_over_time", "min_over_time",
    "max_over_time", "last_over_time", "first_over_time",
    "present_over_time", "absent_over_time",
    "stddev_over_time", "stdvar_over_time", "quantile_over_time",
    "mad_over_time",
    "changes", "resets", "deriv", "predict_linear", "holt_winters",
})


def eval_range_function(
    name: str, vals, has, tsg, windows: W.Windows, spec, *, args: tuple = ()
):
    """Evaluate one range function over all windows. Returns (out, present)
    shaped (S, J). `args` carries scalar parameters (quantile phi, sf/tf,
    predict_linear horizon seconds)."""
    lo = jnp.asarray(windows.lo)
    hi = jnp.asarray(windows.hi)
    t_end = jnp.asarray(windows.t_end)
    l_cells = windows.num_cells_per_window
    tps = spec.tps

    if name in ("rate", "increase", "delta"):
        return W.extrapolated_rate(
            vals, has, tsg, lo, hi, t_end, windows.range_ticks, tps,
            is_counter=name != "delta", is_rate=name == "rate",
        )
    if name == "idelta":
        return W.instant_delta(vals, has, tsg, lo, hi, tps, is_rate=False)
    if name == "irate":
        return W.instant_delta(vals, has, tsg, lo, hi, tps, is_rate=True)
    if name == "sum_over_time":
        return W.window_sum(vals, has, lo, hi)
    if name == "count_over_time":
        cnt = W.window_count(has, lo, hi)
        return cnt.astype(vals.dtype), cnt > 0
    if name == "avg_over_time":
        return W.window_avg(vals, has, lo, hi)
    if name == "min_over_time":
        return W.window_minmax(vals, has, tsg, lo, hi, l_cells, "min")
    if name == "max_over_time":
        return W.window_minmax(vals, has, tsg, lo, hi, l_cells, "max")
    if name == "last_over_time":
        v, _, p = W.window_last(vals, has, tsg, lo, hi)
        return jnp.where(p, v, 0), p
    if name == "first_over_time":
        v, _, p = W.window_first(vals, has, tsg, lo, hi)
        return jnp.where(p, v, 0), p
    if name == "present_over_time":
        cnt = W.window_count(has, lo, hi)
        p = cnt > 0
        return p.astype(vals.dtype), p
    if name == "absent_over_time":
        cnt = W.window_count(has, lo, hi)
        absent = cnt == 0
        return absent.astype(vals.dtype), absent
    if name == "stddev_over_time":
        _, sd, p = W.window_stdvar(vals, has, tsg, lo, hi, l_cells)
        return sd, p
    if name == "stdvar_over_time":
        var, _, p = W.window_stdvar(vals, has, tsg, lo, hi, l_cells)
        return var, p
    if name == "quantile_over_time":
        (phi,) = args
        return W.window_quantile(vals, has, tsg, lo, hi, l_cells, phi)
    if name == "mad_over_time":
        med, p = W.window_quantile(vals, has, tsg, lo, hi, l_cells, 0.5)
        g_vals, g_has, _ = W.gather_windows(vals, has, tsg, lo, hi, l_cells)
        dev = jnp.abs(g_vals - med[:, :, None])
        dev = jnp.where(g_has, dev, jnp.inf)
        sorted_dev = jnp.sort(dev, axis=2)
        n = jnp.sum(g_has, axis=2)
        rank = 0.5 * jnp.maximum(n - 1, 0).astype(vals.dtype)
        lo_i = jnp.clip(jnp.floor(rank).astype(jnp.int32), 0, l_cells - 1)
        hi_i = jnp.clip(jnp.ceil(rank).astype(jnp.int32), 0, l_cells - 1)
        v_lo = jnp.take_along_axis(sorted_dev, lo_i[:, :, None], axis=2)[:, :, 0]
        v_hi = jnp.take_along_axis(sorted_dev, hi_i[:, :, None], axis=2)[:, :, 0]
        out = v_lo + (v_hi - v_lo) * (rank - jnp.floor(rank))
        return jnp.where(p, out, 0), p
    if name == "changes":
        return W.window_pair_count(vals, has, lo, hi, count_changes=True)
    if name == "resets":
        return W.window_pair_count(vals, has, lo, hi, count_changes=False)
    if name == "deriv":
        slope, _, n = W.window_linear_fit(
            vals, has, tsg, lo, hi, t_end, l_cells, tps
        )
        p = n >= 2
        return jnp.where(p, slope, 0), p
    if name == "predict_linear":
        (horizon_s,) = args
        slope, intercept, n = W.window_linear_fit(
            vals, has, tsg, lo, hi, t_end, l_cells, tps
        )
        p = n >= 2
        out = intercept + slope * jnp.asarray(horizon_s, vals.dtype)
        return jnp.where(p, out, 0), p
    if name == "holt_winters":
        sf, tf = args
        return W.window_holt_winters(vals, has, tsg, lo, hi, l_cells, sf, tf)
    raise ValueError(f"unsupported range function: {name}")


# ----------------------------------------------------------------------
# histogram_quantile
# ----------------------------------------------------------------------

@jax.jit
def histogram_quantile(le: jax.Array, buckets: jax.Array, mask: jax.Array, q):
    """Prometheus histogram_quantile over pre-grouped buckets.

    le:      (B,) ascending bucket upper bounds, last must be +inf
    buckets: (..., B) cumulative bucket values (one histogram per leading
             index; typically (G, J, B) for G series-groups x J steps)
    mask:    (..., B) bucket presence
    q:       quantile in [0, 1]

    Semantics follow Prometheus bucketQuantile (monotonicity enforced via a
    running max; rank interpolated linearly within the located bucket; the
    lowest bucket interpolates from 0 when its bound is positive)."""
    dt = buckets.dtype
    q = jnp.asarray(q, dt)
    b = jnp.where(mask, buckets, 0)
    # enforce cumulative monotonicity (client-side counter skew)
    b = jax.lax.cummax(b, axis=b.ndim - 1)
    total = b[..., -1]
    ok = jnp.any(mask, axis=-1) & (total > 0)
    rank = q * total
    # first bucket index with cum >= rank
    idx = jnp.sum((b < rank[..., None]).astype(jnp.int32), axis=-1)
    nb = le.shape[0]
    idx = jnp.clip(idx, 0, nb - 1)
    # +inf bucket: clamp result to highest finite bound
    in_inf = idx >= nb - 1
    idx_lo = jnp.maximum(idx - 1, 0)
    ub = le[idx]
    lb = jnp.where(idx > 0, le[idx_lo], jnp.zeros((), dt))
    # if lowest bucket has non-positive bound, no interpolation from zero
    lb = jnp.where((idx == 0) & (le[0] <= 0), le[0], lb)
    cum_lo = jnp.where(
        idx > 0, jnp.take_along_axis(b, idx_lo[..., None], axis=-1)[..., 0], 0
    )
    cum_hi = jnp.take_along_axis(b, idx[..., None], axis=-1)[..., 0]
    width = cum_hi - cum_lo
    frac = (rank - cum_lo) / jnp.where(width == 0, 1, width)
    out = lb + (ub - lb) * frac
    highest_finite = le[jnp.maximum(nb - 2, 0)]
    out = jnp.where(in_inf, highest_finite, out)
    out = jnp.where(q < 0, -jnp.inf, out)
    out = jnp.where(q > 1, jnp.inf, out)
    return jnp.where(ok, out, jnp.zeros((), dt)), ok


# ----------------------------------------------------------------------
# cross-series aggregation (sum/avg/min/max/topk... by (...) semantics)
# ----------------------------------------------------------------------

# above this series count, linear group reductions run as one-hot matmuls
# on the MXU instead of segment scatters (TPU scatter serializes per index:
# at 1M series a segment_sum costs ~1s, the equivalent (G,S)x(S,J) matmul
# costs <1ms). Min/max are not linear and stay on the scatter path.
_MATMUL_MIN_SERIES = 4096
_MATMUL_MAX_ONEHOT_ELEMS = 1 << 28  # 1 GB f32 one-hot ceiling


def _group_matmul(x, onehot_t):
    """(G, S) @ (S, J) with full f32 accumulation (one-hot entries are
    exact in any precision; the data must not round through bf16)."""
    return jax.lax.dot(onehot_t, x, precision=jax.lax.Precision.HIGHEST)


def aggregate_across_series_blocked(
    vals, present, group_ids, num_groups: int, op: str, *,
    total_series: int, blocks: int | None = None, ctx=None,
):
    """Series aggregation with a fixed blocked-combine structure: the
    series axis splits into `blocks` aligned blocks whose partials are
    combined in one unrolled left fold. Run single-device (ctx =
    LocalFoldCtx) or per-shard inside shard_map (ctx = ShardFoldCtx) it
    performs the SAME additions in the SAME order, so the mesh fast path
    (promql/fast.py) matches the unsharded fast path bit-for-bit.
    `total_series` is the GLOBAL padded series count (local shape *
    shards inside shard_map) — it keeps the matmul-vs-scatter choice
    identical across shardings."""
    from greptimedb_tpu.parallel.dist import LocalFoldCtx, left_fold_sum
    from greptimedb_tpu.parallel.mesh import FOLD_BLOCKS

    if blocks is None:
        blocks = FOLD_BLOCKS  # the ONE cross-path fold-block contract
    if ctx is None:
        ctx = LocalFoldCtx()
    dt = vals.dtype
    gid = group_ids.astype(jnp.int32)
    s_loc = vals.shape[0]
    bl = max(blocks // ctx.shards, 1)
    aligned = (blocks % ctx.shards == 0 and s_loc % bl == 0
               and s_loc >= bl)
    linear = op in ("sum", "avg", "count", "group", "stddev", "stdvar")
    use_matmul = (
        linear
        and total_series >= _MATMUL_MIN_SERIES
        and num_groups * total_series <= _MATMUL_MAX_ONEHOT_ELEMS
    )

    def bsum(x):
        """Blocked exact-structured group sum of an (S_loc, J) matrix."""
        if not aligned:
            return ctx.psum(jax.ops.segment_sum(
                x, gid, num_segments=num_groups
            ))
        per = s_loc // bl
        if use_matmul:
            parts = []
            grange = jnp.arange(num_groups, dtype=jnp.int32)[:, None]
            for b in range(bl):
                sl = slice(b * per, (b + 1) * per)
                onehot_t = (gid[sl][None, :] == grange).astype(dt)
                parts.append(_group_matmul(x[sl], onehot_t))
            partial = jnp.stack(parts)              # (bl, G, J)
        else:
            bid = (jnp.arange(s_loc, dtype=jnp.int32)
                   // jnp.int32(per))
            seg = bid * jnp.int32(num_groups) + gid
            p = jax.ops.segment_sum(
                x, seg, num_segments=bl * num_groups
            )
            partial = p.reshape(bl, num_groups, -1)
        return left_fold_sum(ctx.gather(partial))

    cnt = bsum(present.astype(dt))
    any_present = cnt > 0
    if op in ("sum", "avg"):
        s = bsum(jnp.where(present, vals, 0))
        if op == "avg":
            s = s / jnp.maximum(cnt, 1)
        return jnp.where(any_present, s, 0), any_present
    if op == "count":
        return cnt, any_present
    if op == "group":
        return any_present.astype(dt), any_present
    if op == "min":
        v = jnp.where(present, vals, jnp.inf)
        m = ctx.pext(jax.ops.segment_min(v, gid, num_segments=num_groups),
                     take_max=False)
        return jnp.where(any_present, m, 0), any_present
    if op == "max":
        v = jnp.where(present, vals, -jnp.inf)
        m = ctx.pext(jax.ops.segment_max(v, gid, num_segments=num_groups),
                     take_max=True)
        return jnp.where(any_present, m, 0), any_present
    if op in ("stddev", "stdvar"):
        s = bsum(jnp.where(present, vals, 0))
        n = jnp.maximum(cnt, 1)
        mean = s / n
        dev = jnp.where(present, vals - mean[gid], 0)
        var = bsum(dev * dev) / n
        out = var if op == "stdvar" else jnp.sqrt(var)
        return jnp.where(any_present, out, 0), any_present
    raise ValueError(f"unsupported aggregation: {op}")


@functools.partial(jax.jit, static_argnames=("op", "num_groups"))
def aggregate_across_series(vals, present, group_ids, num_groups: int, op: str):
    """PromQL aggregation operators over the series axis of an (S, J) matrix.
    group_ids (S,) int32 maps each series to its output group (built on host
    from label sets). Returns (G, J) values + presence."""
    dt = vals.dtype
    gid = group_ids.astype(jnp.int32)
    linear = op in ("sum", "avg", "count", "group", "stddev", "stdvar")
    # the (G, S) one-hot must stay bounded: high-cardinality group-bys
    # (G ~ S) would materialize G*S floats, so those keep the scatter path
    use_matmul = (
        linear
        and vals.shape[0] >= _MATMUL_MIN_SERIES
        and num_groups * vals.shape[0] <= _MATMUL_MAX_ONEHOT_ELEMS
    )

    if use_matmul:
        onehot_t = (
            gid[None, :] == jnp.arange(num_groups, dtype=jnp.int32)[:, None]
        ).astype(dt)                                    # (G, S)
        cnt_f = _group_matmul(present.astype(dt), onehot_t)
        any_present = cnt_f > 0
        masked = jnp.where(present, vals, 0)
        if op in ("sum", "avg"):
            s = _group_matmul(masked, onehot_t)
            if op == "avg":
                s = s / jnp.maximum(cnt_f, 1)
            return jnp.where(any_present, s, 0), any_present
        if op == "count":
            return cnt_f, any_present
        if op == "group":
            return any_present.astype(dt), any_present
        # stddev / stdvar: two-pass for stability (matches the scatter path)
        n = jnp.maximum(cnt_f, 1)
        mean = _group_matmul(masked, onehot_t) / n
        dev = jnp.where(present, vals - jnp.take(mean, gid, axis=0), 0)
        var = _group_matmul(dev * dev, onehot_t) / n
        out = var if op == "stdvar" else jnp.sqrt(var)
        return jnp.where(any_present, out, 0), any_present

    cnt = jax.ops.segment_sum(
        present.astype(jnp.int32), gid, num_segments=num_groups
    )
    any_present = cnt > 0
    if op in ("sum", "avg"):
        s = jax.ops.segment_sum(
            jnp.where(present, vals, 0), gid, num_segments=num_groups
        )
        if op == "avg":
            s = s / jnp.maximum(cnt, 1).astype(dt)
        return jnp.where(any_present, s, 0), any_present
    if op == "count":
        return cnt.astype(dt), any_present
    if op == "min":
        v = jnp.where(present, vals, jnp.inf)
        m = jax.ops.segment_min(v, gid, num_segments=num_groups)
        return jnp.where(any_present, m, 0), any_present
    if op == "max":
        v = jnp.where(present, vals, -jnp.inf)
        m = jax.ops.segment_max(v, gid, num_segments=num_groups)
        return jnp.where(any_present, m, 0), any_present
    if op == "group":
        return any_present.astype(dt), any_present
    if op in ("stddev", "stdvar"):
        s = jax.ops.segment_sum(
            jnp.where(present, vals, 0), gid, num_segments=num_groups
        )
        n = jnp.maximum(cnt, 1).astype(dt)
        mean = s / n
        dev = jnp.where(present, vals - mean[gid], 0)
        var = jax.ops.segment_sum(dev * dev, gid, num_segments=num_groups) / n
        out = var if op == "stdvar" else jnp.sqrt(var)
        return jnp.where(any_present, out, 0), any_present
    raise ValueError(f"unsupported aggregation: {op}")
