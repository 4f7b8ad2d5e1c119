"""Grouped reductions with a host (numpy) and a device (TPU) path.

The executor routes every GROUP BY through here. Small row counts run
vectorized numpy on the host (device launch latency would dominate); large
row counts ship (values, segment-ids, mask) to the device and run the
jit'd segment kernels from ops/segment.py — the TPU replacement for the
reference's hash-aggregate operators (SURVEY.md §2.2 src/query).

Shapes are bucketed (rows to powers of two, segments to powers of two) so
jit traces are reused across queries.
"""

from __future__ import annotations

import functools

import numpy as np

from greptimedb_tpu.datatypes.batch import bucket_size, pad_to
from greptimedb_tpu.errors import UnsupportedError
from greptimedb_tpu.program_cache import ProgramCache

DEVICE_THRESHOLD = 262_144  # rows below this stay on host


def _pad_group_count(g: int) -> int:
    b = 1
    while b < g:
        b *= 2
    return b


def dev_block_ids(n: int, blocks: int):
    """(n,) int32 device array mapping row index -> block in [0, blocks).
    Device iota — nothing cached, nothing shipped from host."""
    import jax.numpy as jnp

    per = -(-n // blocks)
    return jnp.arange(n, dtype=jnp.int32) // jnp.int32(per)


# ----------------------------------------------------------------------
# host path
# ----------------------------------------------------------------------

def grouped_minmax_typed(op: str, values, valid, gid, g: int):
    """Per-group min/max preserving the input dtype: BIGINT/timestamp
    extremes above 2^53 never round-trip through float, strings order
    lexicographically via lexsort, floats keep numpy NaN propagation.
    Shared by the host reduce and the distributed partial merge
    (dist/dist_query.py). Returns (out_values, present_mask)."""
    present = np.zeros(g, bool)
    present[gid[valid]] = True
    if values.dtype == object or values.dtype.kind in "US":
        vv = values[valid].astype(str)
        gg = gid[valid]
        order = np.lexsort((vv, gg))
        gs = gg[order]
        edge = np.ones(len(gs), bool)
        if op == "min":
            edge[1:] = gs[1:] != gs[:-1]
        else:
            edge[:-1] = gs[:-1] != gs[1:]
        out = np.full(g, "", object)
        out[gs[edge]] = values[valid][order][edge]
        return out, present
    ufunc = np.minimum if op == "min" else np.maximum
    if values.dtype.kind in "iu":
        info = np.iinfo(values.dtype)
        init = info.max if op == "min" else info.min
        out = np.full(g, init, values.dtype)
        ufunc.at(out, gid[valid], values[valid])
        return np.where(present, out, 0), present
    v = values.astype(np.float64, copy=False)
    out = np.full(g, np.inf if op == "min" else -np.inf)
    ufunc.at(out, gid[valid], v[valid])
    return np.where(present, out, 0.0), present


def _host_reduce(op: str, values, valid, gid, g: int, q: float | None,
                 order_ts=None):
    """One aggregate over host arrays. values may be None for count(*).
    Returns (out_values, out_valid)."""
    n = len(gid)
    ones = np.ones(g)
    if op == "count":
        if values is None:
            cnt = np.bincount(gid, minlength=g)
        else:
            cnt = np.bincount(gid[valid], minlength=g)
        return cnt.astype(np.int64), None
    if op == "count_distinct":
        if n == 0:
            return np.zeros(g, np.int64), None
        vv = values[valid]
        gg = gid[valid]
        if vv.dtype == object:
            vv = vv.astype(str)
        pairs = np.unique(
            np.stack([gg.astype(np.int64),
                      np.unique(vv, return_inverse=True)[1].astype(np.int64)]),
            axis=1,
        )
        return np.bincount(pairs[0], minlength=g).astype(np.int64), None

    # dtype-preserving paths BEFORE the f64 cast: BIGINT/timestamp
    # extremes and sums above 2^53 must stay exact, and strings order
    # lexicographically (the reference's arrow kernels are typed too)
    if op in ("min", "max"):
        return grouped_minmax_typed(op, values, valid, gid, g)
    if op == "sum" and values.dtype.kind in "iu":
        present = np.zeros(g, bool)
        present[gid[valid]] = True
        vals = values[valid]
        gg = gid[valid]
        out = np.zeros(g, np.int64)
        if vals.size:
            info64 = np.iinfo(np.int64)
            infov = np.iinfo(vals.dtype)
            mag_dtype = max(abs(int(infov.max)), abs(int(infov.min)))
            # cheapest-first safety ladder, so the common case costs
            # nothing extra: (1) dtype bound — no data pass at all
            # (int8/16/32 with any realistic row count clear here);
            # (2) observed-extremes bound with size as the group-count
            # cap — one max+min pass; (3) only then the exact path
            safe = vals.size * mag_dtype <= info64.max
            if not safe:
                vmax, vmin = int(vals.max()), int(vals.min())
                safe = (vmax <= info64.max
                        and vals.size * max(abs(vmax), abs(vmin))
                        <= info64.max)
            if safe:
                np.add.at(out, gg, vals.astype(np.int64))
            else:
                # exact big-int accumulation: uint64 above 2^63 stays
                # exact (no mis-cast to negative) and true overflow is
                # DETECTED instead of silently wrapping
                from greptimedb_tpu.errors import ArithmeticOverflowError

                exact = np.zeros(g, object)
                np.add.at(exact, gg, np.asarray(vals.tolist(), object))
                hi = max(exact[present], default=0)
                lo = min(exact[present], default=0)
                if hi > info64.max or lo < info64.min:
                    raise ArithmeticOverflowError(
                        f"SUM overflows BIGINT: group total {hi if hi > info64.max else lo} "
                        f"is outside [{info64.min}, {info64.max}]"
                    )
                out[present] = exact[present].astype(np.int64)
        return out, present

    v = values.astype(np.float64, copy=False)
    vm = np.where(valid, v, 0.0)
    cnt = np.bincount(gid[valid], minlength=g)
    present = cnt > 0
    if op == "sum":
        s = np.bincount(gid, weights=vm, minlength=g)
        return s, present
    if op == "mean":
        s = np.bincount(gid, weights=vm, minlength=g)
        return s / np.maximum(cnt, 1), present
    if op in ("var_pop", "var_samp", "stddev_pop", "stddev_samp"):
        s = np.bincount(gid, weights=vm, minlength=g)
        mean = s / np.maximum(cnt, 1)
        dev = np.where(valid, v - mean[gid], 0.0)
        s2 = np.bincount(gid, weights=dev * dev, minlength=g)
        ddof = 1 if op.endswith("_samp") else 0
        var = s2 / np.maximum(cnt - ddof, 1)
        ok = cnt > ddof
        if op.startswith("stddev"):
            return np.sqrt(var), ok
        return var, ok
    if op in ("first_value", "last_value"):
        ts = order_ts if order_ts is not None else np.arange(n)
        idx = np.arange(n)
        order = np.lexsort((idx, ts))
        order = order[valid[order]]
        if op == "first_value":
            order = order[::-1]
        out = np.zeros(g, dtype=v.dtype)
        # later assignments win: for last_value ascending order leaves the
        # latest timestamp; for first_value the earliest.
        out[gid[order]] = v[order]
        return out, present
    if op == "quantile":
        assert q is not None
        order = np.lexsort((v, gid))
        order = order[valid[order]]
        gg = gid[order]
        vv = v[order]
        starts = np.zeros(g, np.int64)
        np.cumsum(np.bincount(gg, minlength=g), out=starts)
        starts = np.concatenate([[0], starts[:-1]])
        rank = q * np.maximum(cnt - 1, 0)
        lo = np.floor(rank).astype(np.int64)
        hi = np.ceil(rank).astype(np.int64)
        frac = rank - lo
        safe_take = lambda i: vv[np.minimum(starts + i, max(len(vv) - 1, 0))] if len(vv) else np.zeros(g)
        v_lo = safe_take(lo)
        v_hi = safe_take(hi)
        out = v_lo + (v_hi - v_lo) * frac
        return np.where(present, out, 0.0), present
    raise UnsupportedError(f"aggregate op: {op}")


# ----------------------------------------------------------------------
# device path: ONE fused jit program, ONE device->host transfer
# ----------------------------------------------------------------------

_DEVICE_OPS = {"count", "sum", "mean", "min", "max", "var_pop", "var_samp",
               "stddev_pop", "stddev_samp", "first_value", "last_value"}

_PROGRAM_CACHE: dict = {}


def _fused_program():
    """All aggregates of a GROUP BY in one XLA program emitting a single
    (rows, GB) f32 matrix — one transfer per query instead of one per
    aggregate (the reference streams per-operator;
    /root/reference/src/query/src/datafusion.rs:75).

    Layout (all static from `spec`):
    - per distinct validity mask: `blocks` rows of per-(group, block)
      count partials (combined in f64 on host — f32 scatter-add partials
      stay small, the blocked scheme bounds accumulation error);
    - sum/mean: `blocks` rows of value-sum partials;
    - var/stddev: `blocks` rows of squared-deviation partials (deviations
      taken against the on-device f32 mean: the correction term
      (mean - m32)^2 is O(eps^2), negligible);
    - min/max: 1 row;
    - first/last: 1 row — the winner is resolved exactly by the
      (ts_hi, ts_lo, row-index) int32 lexicographic key (epoch-ms split
      into two int31 halves survives the device without x64) and its
      value extracted by a masked segment-sum, mirroring
      device_range._fold_groups.
    """
    import jax
    import jax.numpy as jnp

    @functools.partial(jax.jit, static_argnames=("spec",))
    def program(vals, masks, gid, tshi, tslo, *, spec):
        gb, blocks, mask_rows, items = spec
        nb = gid.shape[0]
        per = -(-nb // blocks)
        block = (jnp.arange(nb, dtype=jnp.int32)
                 // jnp.int32(per))
        trash2 = jnp.int32(gb * blocks)
        rows = []

        def pseg2(v, mask):
            s2 = jnp.where(mask, gid * jnp.int32(blocks) + block, trash2)
            p = jax.ops.segment_sum(
                jnp.where(mask, v, 0.0).astype(jnp.float32),
                s2, num_segments=gb * blocks + 1,
            )
            return p[:-1].reshape(gb, blocks).T  # (blocks, gb)

        cnt32 = []
        for mi in range(mask_rows):
            cp = pseg2(jnp.ones(nb, jnp.float32), masks[mi])
            cnt32.append(jnp.sum(cp, axis=0))
            rows.append(cp)

        idx = jnp.arange(nb, dtype=jnp.int32)
        for op, vi, mi in items:
            mask = masks[mi]
            if op == "count":
                continue  # rides the mask's count rows
            v = vals[vi]
            if op in ("sum", "mean"):
                rows.append(pseg2(v, mask))
            elif op in ("var_pop", "var_samp", "stddev_pop", "stddev_samp"):
                sp = pseg2(v, mask)
                m32 = jnp.sum(sp, axis=0) / jnp.maximum(cnt32[mi], 1)
                dev = jnp.where(mask, v - m32[gid], 0.0)
                rows.append(pseg2(dev * dev, mask))
            elif op in ("min", "max"):
                ext = jax.ops.segment_max if op == "max" else (
                    jax.ops.segment_min
                )
                ident = -jnp.inf if op == "max" else jnp.inf
                sg = jnp.where(mask, gid, jnp.int32(gb))
                r = ext(
                    jnp.where(mask, v, ident).astype(jnp.float32), sg,
                    num_segments=gb + 1,
                )[:-1]
                rows.append(r[None, :])
            elif op in ("first_value", "last_value"):
                last = op == "last_value"
                ext = jax.ops.segment_max if last else jax.ops.segment_min
                sent = jnp.int32(-1 if last else _2_31M)
                sg = jnp.where(mask, gid, jnp.int32(gb))

                def stage(key, tie):
                    t = jnp.where(tie, key, sent)
                    w = ext(t, sg, num_segments=gb + 1)[:-1]
                    return tie & (key == w[sg.clip(0, gb - 1)]) & mask

                tie = mask
                tie = stage(tshi, tie)
                tie = stage(tslo, tie)
                tie = stage(idx, tie)  # row index: unique winner
                r = jax.ops.segment_sum(
                    jnp.where(tie, v, 0.0).astype(jnp.float32), sg,
                    num_segments=gb + 1,
                )[:-1]
                rows.append(r[None, :])
        return jnp.concatenate(rows, axis=0)

    return program


_2_31M = 2**31 - 1
_FUSED = None


def _pow2_floor(n: int) -> int:
    b = 1
    while b * 2 <= n:
        b *= 2
    return b


def _pick_blocks(nb: int, gb: int) -> int:
    """Power-of-two row-block count for the fused program, independent
    of mesh geometry: sharded and unsharded runs of the same query use
    the SAME block boundaries, so per-block f32 partials (and therefore
    the host f64 combine) agree bit-for-bit."""
    return max(1, min(nb, _pow2_floor(max(8, (1 << 20) // max(gb, 1)))))


def _sharded_fused_program(mesh):
    """shard_map twin of _fused_program: rows sharded over AXIS_SHARD,
    each shard computes its aligned slice of the per-(group, block)
    partials locally (identical rows, identical scatter order), blocked
    sections concatenate by output sharding, extremes recombine with
    pmin/pmax and first/last winners with staged exact selection +
    psum value extraction (the dist_segment_agg pattern from
    parallel/dist.py generalized to the fused multi-aggregate layout)."""
    import jax
    import jax.numpy as jnp
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    from greptimedb_tpu.parallel.dist import ShardFoldCtx
    from greptimedb_tpu.parallel.mesh import AXIS_SHARD

    ns = mesh.shape[AXIS_SHARD]
    ctx = ShardFoldCtx(ns)

    @functools.partial(jax.jit, static_argnames=("spec",))
    def program(vals, masks, gid, tshi, tslo, *, spec):
        gb, blocks, mask_rows, items = spec
        bl = blocks // ns  # local blocks per shard (aligned boundaries)

        def local(vals, masks, gid, tshi, tslo):
            nbl = gid.shape[0]
            per = -(-nbl // bl)
            block = (jnp.arange(nbl, dtype=jnp.int32)
                     // jnp.int32(per))
            trash2 = jnp.int32(gb * bl)
            shard = jax.lax.axis_index(AXIS_SHARD)
            blocked = []
            single = []

            def pseg2(v, mask):
                s2 = jnp.where(mask, gid * jnp.int32(bl) + block, trash2)
                p = jax.ops.segment_sum(
                    jnp.where(mask, v, 0.0).astype(jnp.float32),
                    s2, num_segments=gb * bl + 1,
                )
                return p[:-1].reshape(gb, bl).T  # (bl_local, gb)

            for mi in range(mask_rows):
                blocked.append(pseg2(jnp.ones(nbl, jnp.float32),
                                     masks[mi]))
            idx_g = shard * jnp.int32(nbl) + jnp.arange(
                nbl, dtype=jnp.int32
            )
            for op, vi, mi in items:
                mask = masks[mi]
                if op == "count":
                    continue  # rides the mask's count rows
                v = vals[vi]
                if op in ("sum", "mean"):
                    blocked.append(pseg2(v, mask))
                elif op in ("min", "max"):
                    ext = jax.ops.segment_max if op == "max" else (
                        jax.ops.segment_min
                    )
                    ident = -jnp.inf if op == "max" else jnp.inf
                    sg = jnp.where(mask, gid, jnp.int32(gb))
                    r = ext(
                        jnp.where(mask, v, ident).astype(jnp.float32),
                        sg, num_segments=gb + 1,
                    )[:-1]
                    single.append(ctx.pext(r, take_max=op == "max"))
                elif op in ("first_value", "last_value"):
                    last = op == "last_value"
                    ext = jax.ops.segment_max if last else (
                        jax.ops.segment_min
                    )
                    sent = jnp.int32(-1 if last else _2_31M)
                    sg = jnp.where(mask, gid, jnp.int32(gb))

                    def stage(key, tie, sg=sg, ext=ext, sent=sent,
                              last=last, mask=mask):
                        t = jnp.where(tie, key, sent)
                        w = ext(t, sg, num_segments=gb + 1)[:-1]
                        w = ctx.pext(w, take_max=last)
                        return tie & (key == w[sg.clip(0, gb - 1)]) & mask

                    tie = mask
                    tie = stage(tshi, tie)
                    tie = stage(tslo, tie)
                    tie = stage(idx_g, tie)  # global row idx: unique
                    r = jax.ops.segment_sum(
                        jnp.where(tie, v, 0.0).astype(jnp.float32), sg,
                        num_segments=gb + 1,
                    )[:-1]
                    single.append(ctx.psum(r))
            out_b = jnp.stack(blocked)  # (sections, bl_local, gb)
            out_s = (jnp.stack(single) if single
                     else jnp.zeros((0, gb), jnp.float32))
            return out_b, out_s

        return shard_map(
            local, mesh=mesh,
            in_specs=(P(None, AXIS_SHARD), P(None, AXIS_SHARD),
                      P(AXIS_SHARD), P(AXIS_SHARD), P(AXIS_SHARD)),
            out_specs=(P(None, AXIS_SHARD, None), P()),
            check_vma=False,
        )(vals, masks, gid, tshi, tslo)

    return program


_SHARDED_FUSED = ProgramCache(_sharded_fused_program)


def _make_row_put(mesh):
    """Host->device placement for row-axis arrays: single-device
    jnp.asarray, or row-axis sharding over the mesh (SURVEY.md §2.7 #2 —
    data-parallel GROUP BY; XLA inserts the cross-shard collectives for
    the segment folds)."""
    import jax
    import jax.numpy as jnp

    if mesh is None:
        return jnp.asarray, jnp.asarray
    from jax.sharding import NamedSharding, PartitionSpec as P

    from greptimedb_tpu.parallel.mesh import AXIS_SHARD

    s_rows = NamedSharding(mesh, P(AXIS_SHARD))
    s_stacked = NamedSharding(mesh, P(None, AXIS_SHARD))

    def put1(x):
        return jax.device_put(np.asarray(x), s_rows)

    def put2(x):
        return jax.device_put(np.asarray(x), s_stacked)

    return put2, put1


def _device_reduce_fused(specs, values: dict, gid, valid_map, g: int, ts,
                         mesh=None):
    """Single-program GROUP BY. specs: (name, op, vkey|None, q). Returns
    {name: (np values, np valid|None)}."""
    global _FUSED
    import jax.numpy as jnp

    if _FUSED is None:
        _FUSED = _fused_program()

    n = len(gid)
    nb = bucket_size(n)
    if mesh is not None:
        from greptimedb_tpu.parallel.mesh import AXIS_SHARD

        shards = mesh.shape[AXIS_SHARD]
        nb = max(nb, shards)  # bucket sizes are powers of two
    gb = _pad_group_count(g)
    blocks = _pick_blocks(nb, gb)
    if mesh is not None and (blocks % shards or nb % blocks):
        # shard boundaries must align with block boundaries for the
        # exact blocked combine; degenerate geometries run single-device
        mesh = None
    put2, put1 = _make_row_put(mesh)

    # distinct validity masks (mask 0 = all-valid)
    mask_keys = [None]
    mask_arrays = [np.ones(n, dtype=bool)]
    mask_of: dict = {None: 0}
    for name, op, vk, q in specs:
        m = valid_map.get(vk) if vk else None
        mid = id(m) if m is not None else None
        if mid not in mask_of:
            mask_of[mid] = len(mask_keys)
            mask_keys.append(mid)
            mask_arrays.append(m)
    # stacked dynamic inputs
    vkeys = sorted({vk for _, _, vk, _ in specs if vk is not None})
    vidx = {k: i for i, k in enumerate(vkeys)}
    d_vals = put2(np.stack([
        pad_to(values[k].astype(np.float32, copy=False), nb)
        for k in vkeys
    ])) if vkeys else put2(np.zeros((1, nb), np.float32))
    d_masks = put2(np.stack([
        pad_to(m, nb, fill=False) for m in mask_arrays
    ]))
    d_gid = put1(pad_to(gid.astype(np.int32), nb))
    if ts is not None and any(
        op in ("first_value", "last_value") for _, op, _, _ in specs
    ):
        rel = (ts.astype(np.int64) - int(ts.min())) if n else ts
        tshi = (rel >> 31).astype(np.int32)
        tslo = (rel & _2_31M).astype(np.int32)
    else:
        tshi = tslo = np.zeros(n, np.int32)
    d_tshi = put1(pad_to(tshi, nb))
    d_tslo = put1(pad_to(tslo, nb))

    items = tuple(
        (op, vidx[vk] if vk is not None else -1,
         mask_of[id(valid_map[vk]) if vk and vk in valid_map else None])
        for _, op, vk, _ in specs
    )
    spec = (gb, blocks, len(mask_arrays), items)
    # device-time attribution at the jit/shard_map call boundary
    # (telemetry/device_trace): compile first-call vs cache-hit, the
    # crossing's legs (dispatch, wait, readback), host<->device bytes
    from greptimedb_tpu.telemetry import device_trace

    upload = sum(int(a.nbytes) for a in (
        d_vals, d_masks, d_gid, d_tshi, d_tslo
    ) if hasattr(a, "nbytes"))
    if mesh is not None:
        prog = _SHARDED_FUSED.get(mesh)
        with device_trace.device_call(
                "groupby", key=("groupby-sharded", spec),
                groups=g) as dcall:
            dcall.transfer(upload, "upload")
            out_b, out_s = dcall.run(prog, d_vals, d_masks, d_gid,
                                     d_tshi, d_tslo, spec=spec)
            dcall.wait(out_b, out_s)
            from greptimedb_tpu.query import readback as _readback

            out_b = dcall.read(_readback.read_full, out_b, np.float64)
            out_s = dcall.read(_readback.read_full, out_s, np.float64)
        # reassemble the single-device program's row layout so the host
        # f64 combine below is shared verbatim
        pieces = []
        bi = si = 0
        for _ in mask_arrays:
            pieces.append(out_b[bi])
            bi += 1
        for op2, _vi, _mi in items:
            if op2 == "count":
                continue
            if op2 in ("sum", "mean"):
                pieces.append(out_b[bi])
                bi += 1
            else:
                pieces.append(out_s[si][None, :])
                si += 1
        out_mat = np.concatenate(pieces, axis=0)
    else:
        with device_trace.device_call(
                "groupby", key=("groupby", spec), groups=g) as dcall:
            dcall.transfer(upload, "upload")
            out_dev = dcall.run(_FUSED, d_vals, d_masks, d_gid, d_tshi,
                                d_tslo, spec=spec)
            dcall.wait(out_dev)
            from greptimedb_tpu.query import readback as _readback

            out_mat = dcall.read(_readback.read_full, out_dev, np.float64)

    # decode: host f64 combine of the blocked partials
    cnts = []
    r = 0
    for _ in mask_arrays:
        cnts.append(out_mat[r:r + blocks].sum(axis=0)[:g])
        r += blocks
    out = {}
    for (name, op, vk, q), (op2, vi, mi) in zip(specs, items):
        cnt = cnts[mi]
        present = cnt > 0
        if op == "count":
            out[name] = (cnt.astype(np.int64), None)
            continue
        if op in ("sum", "mean"):
            s = out_mat[r:r + blocks].sum(axis=0)[:g]
            r += blocks
            out[name] = ((s, present) if op == "sum"
                         else (s / np.maximum(cnt, 1), present))
        elif op in ("var_pop", "var_samp", "stddev_pop", "stddev_samp"):
            s2 = out_mat[r:r + blocks].sum(axis=0)[:g]
            r += blocks
            ddof = 1 if op.endswith("_samp") else 0
            var = np.maximum(s2, 0.0) / np.maximum(cnt - ddof, 1)
            ok = cnt > ddof
            out[name] = ((np.sqrt(var), ok) if op.startswith("stddev")
                         else (var, ok))
        else:  # min / max / first / last: one row
            vrow = out_mat[r][:g]
            r += 1
            out[name] = (np.where(present, vrow, 0.0), present)
    return out


# ----------------------------------------------------------------------
# dispatch
# ----------------------------------------------------------------------

def grouped_reduce(
    specs: list,
    values: dict,
    gid: np.ndarray,
    valid_map: dict,
    g: int,
    *,
    ts: np.ndarray | None = None,
    prefer_device: bool | None = None,
    mesh=None,
    mesh_opts=None,
) -> tuple[dict, str]:
    """specs: list of (out_name, op, value_key|None, q|None). values: key ->
    per-row array. valid_map: key -> bool array (all-valid if missing).
    Returns ({out_name: (np array len g, valid|None)}, exec_path) where
    exec_path is "device" or "host:<reason>"."""
    n = len(gid)
    all_valid = np.ones(n, dtype=bool)
    use_device = prefer_device
    if use_device is None:
        use_device = n >= DEVICE_THRESHOLD
    path = "device"
    if not use_device:
        path = "host:small" if prefer_device is None else "host:config"
    elif not all(op in _DEVICE_OPS for _, op, vk, _ in specs):
        path = "host:op"
    elif not all(
        vk is None or values[vk].dtype.kind in "iuf"
        for _, op, vk, _ in specs
    ):
        path = "host:dtype"
    if path == "device":
        use_mesh = None
        if mesh is not None:
            from greptimedb_tpu.query import planner as qplanner

            dec = qplanner.decide_mesh_execution(
                mesh, kind="aggregate", rows=n,
                ops=[op for _, op, _, _ in specs], opts=mesh_opts,
            )
            qplanner.record_mesh_decision(dec, "aggregate")
            if dec.shard:
                use_mesh = mesh
        return _device_reduce_fused(
            specs, values, gid, valid_map, g, ts, mesh=use_mesh,
        ), path
    out = {}
    for name, op, vk, q in specs:
        v = values[vk] if vk is not None else None
        mask = valid_map.get(vk) if vk else None
        if mask is None:
            mask = all_valid
        out[name] = _host_reduce(op, v, mask, gid, g, q, order_ts=ts)
    return out, path
