"""Segmented (group-by) reductions.

The TPU-native replacement for the reference's hash-aggregate operators
(DataFusion's aggregate execs reached from
/root/reference/src/query/src/datafusion.rs): group keys become dense int32
codes (tags are already dictionary-encoded, see datatypes.batch.Dictionary),
and every aggregate is a `jax.ops.segment_*` reduction — which XLA lowers to
sorted scatter-adds that tile well on TPU.

Two paths:
- dense path: when the product of key cardinalities is small enough, the
  combined code IS the segment id (num_segments = prod(cards), static).
- sort path: otherwise rows are sorted by code on device; run boundaries
  give compact per-batch segment ids with num_segments = N (static).

All kernels take a row-validity mask (padding rows and filtered rows are
masked out) and are jit-safe: shapes depend only on (N, num_segments).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

_NEG = -(2**31 - 1)
_POS = 2**31 - 1


def combine_codes(codes: list[jax.Array], cards: list[int]) -> tuple[jax.Array, int]:
    """Mixed-radix combine of per-column int32 codes into one code.

    Returns (combined_code, total_cardinality)."""
    assert len(codes) == len(cards) and codes
    out = codes[0].astype(jnp.int32)
    total = cards[0]
    for c, n in zip(codes[1:], cards[1:]):
        out = out * jnp.int32(n) + c.astype(jnp.int32)
        total *= n
    return out, total


def split_codes(code, cards: list[int]):
    """Inverse of combine_codes; works on numpy or jax arrays."""
    parts = []
    for n in reversed(cards):
        parts.append(code % n)
        code = code // n
    return list(reversed(parts))


def _masked_seg(seg: jax.Array, mask: jax.Array, num_segments: int) -> jax.Array:
    """Route masked-out rows to a trash segment (num_segments)."""
    return jnp.where(mask, seg, jnp.int32(num_segments)).astype(jnp.int32)


def seg_sum(values, seg, mask, num_segments: int):
    s = _masked_seg(seg, mask, num_segments)
    v = jnp.where(mask, values, jnp.zeros((), values.dtype))
    return jax.ops.segment_sum(v, s, num_segments=num_segments + 1)[:-1]


def seg_count(seg, mask, num_segments: int):
    s = _masked_seg(seg, mask, num_segments)
    return jax.ops.segment_sum(
        mask.astype(jnp.int32), s, num_segments=num_segments + 1
    )[:-1]


def seg_min(values, seg, mask, num_segments: int):
    s = _masked_seg(seg, mask, num_segments)
    if jnp.issubdtype(values.dtype, jnp.floating):
        fill = jnp.array(jnp.inf, values.dtype)
    else:
        fill = jnp.array(jnp.iinfo(values.dtype).max, values.dtype)
    v = jnp.where(mask, values, fill)
    return jax.ops.segment_min(v, s, num_segments=num_segments + 1)[:-1]


def seg_max(values, seg, mask, num_segments: int):
    s = _masked_seg(seg, mask, num_segments)
    if jnp.issubdtype(values.dtype, jnp.floating):
        fill = jnp.array(-jnp.inf, values.dtype)
    else:
        fill = jnp.array(jnp.iinfo(values.dtype).min, values.dtype)
    v = jnp.where(mask, values, fill)
    return jax.ops.segment_max(v, s, num_segments=num_segments + 1)[:-1]


def seg_mean(values, seg, mask, num_segments: int):
    s = seg_sum(values, seg, mask, num_segments)
    c = seg_count(seg, mask, num_segments)
    return s / jnp.maximum(c, 1).astype(s.dtype), c


def seg_var(values, seg, mask, num_segments: int, *, ddof: int = 0):
    """Population (ddof=0) or sample (ddof=1) variance per segment.

    Mean-shifted by the segment's own first value for numerical stability in
    f32 (the raw sum-of-squares formula cancels catastrophically)."""
    first_idx = seg_last_index(seg, mask, num_segments, take_first=True)
    shift = jnp.where(
        first_idx >= 0, values[jnp.maximum(first_idx, 0)], jnp.zeros((), values.dtype)
    )
    sv = values - shift[seg]
    s1 = seg_sum(sv, seg, mask, num_segments)
    s2 = seg_sum(sv * sv, seg, mask, num_segments)
    n = seg_count(seg, mask, num_segments).astype(values.dtype)
    denom = jnp.maximum(n - ddof, 1)
    var = (s2 - s1 * s1 / jnp.maximum(n, 1)) / denom
    return jnp.maximum(var, 0.0), n.astype(jnp.int32)


def seg_last_index(seg, mask, num_segments: int, *, take_first: bool = False):
    """Index of the last (or first) valid row per segment, -1 if empty.

    'last' means highest row index — callers wanting time order must feed
    time-sorted rows (the storage scan guarantees (series, ts) order)."""
    n = seg.shape[0]
    idx = jnp.arange(n, dtype=jnp.int32)
    s = _masked_seg(seg, mask, num_segments)
    if take_first:
        key = jnp.where(mask, idx, jnp.int32(_POS))
        out = jax.ops.segment_min(key, s, num_segments=num_segments + 1)[:-1]
        return jnp.where(out == _POS, jnp.int32(-1), out)
    key = jnp.where(mask, idx, jnp.int32(-1))
    return jax.ops.segment_max(key, s, num_segments=num_segments + 1)[:-1]


def seg_last(values, seg, mask, num_segments: int, *, take_first: bool = False):
    """Last (by row order) valid value per segment, plus presence mask."""
    li = seg_last_index(seg, mask, num_segments, take_first=take_first)
    present = li >= 0
    safe = jnp.maximum(li, 0)
    return values[safe], present


def seg_argmax(values, seg, mask, num_segments: int, *, argmin: bool = False):
    """Row index attaining the max (min) per segment; -1 if empty. Ties break
    to the lowest row index (matching typical SQL semantics)."""
    best = seg_min(values, seg, mask, num_segments) if argmin else seg_max(
        values, seg, mask, num_segments
    )
    hit = mask & (values == best[seg])
    return seg_last_index(seg, hit, num_segments, take_first=True)


def sort_groups(code_cols: list[jax.Array], mask: jax.Array):
    """Sort-based grouping for unbounded key spaces (cardinality product too
    large for the dense path). Lexicographically sorts rows by the int32 code
    columns — no combined code, so no overflow.

    Returns (order, seg_ids, starts, num_groups_device):
    - order: permutation putting valid rows first, sorted by keys
    - seg_ids: compact segment id per *sorted* row (0..num_groups-1);
      invalid rows get segment N (use num_segments=N+1 then drop the tail)
    - starts: bool per sorted row, True at each group's first valid row
    - num_groups: device scalar (int32)"""
    assert code_cols
    n = code_cols[0].shape[0]
    # jnp.lexsort: LAST key is primary. Significance order (most -> least):
    # !mask (so invalid rows sort after every valid row), then code_cols in
    # declaration order.
    keys = [c.astype(jnp.int32) for c in reversed(code_cols)] + [
        (~mask).astype(jnp.int32)
    ]
    order = jnp.lexsort(keys)
    smask = mask[order]
    changed = jnp.zeros((n,), dtype=bool)
    for c in code_cols:
        sc = c.astype(jnp.int32)[order]
        prev = jnp.concatenate([jnp.full((1,), _NEG, jnp.int32), sc[:-1]])
        changed = changed | (sc != prev)
    starts = smask & (changed | (jnp.arange(n) == 0))
    seg_ids = jnp.cumsum(starts.astype(jnp.int32)) - 1
    seg_ids = jnp.where(smask, jnp.maximum(seg_ids, 0), jnp.int32(n))
    num_groups = jnp.sum(starts.astype(jnp.int32))
    return order, seg_ids, starts, num_groups


@functools.partial(jax.jit, static_argnames=("num_segments", "ops"))
def multi_aggregate(values, seg, mask, num_segments: int, ops: tuple[str, ...]):
    """Run several aggregates over the same segmentation in one jit program
    (the common SELECT agg1, agg2, ... GROUP BY shape). `values` is a dict
    name -> (N,) array; ops is a tuple of (op, name) pairs flattened as
    'op:name' strings for hashability."""
    results = {}
    for spec in ops:
        op, _, name = spec.partition(":")
        v = values[name]
        if op == "sum":
            results[spec] = seg_sum(v, seg, mask, num_segments)
        elif op == "count":
            results[spec] = seg_count(seg, mask, num_segments)
        elif op == "min":
            results[spec] = seg_min(v, seg, mask, num_segments)
        elif op == "max":
            results[spec] = seg_max(v, seg, mask, num_segments)
        elif op == "mean":
            results[spec] = seg_mean(v, seg, mask, num_segments)[0]
        else:
            raise ValueError(f"unknown aggregate op: {op}")
    return results


# ----------------------------------------------------------------------
# segmented scans (window-function running frames)
# ----------------------------------------------------------------------

@jax.jit
def segmented_cumsum(values: jax.Array, reset: jax.Array) -> jax.Array:
    """Per-segment running sum: `reset[i]` marks the first row of a
    segment (partition). One associative_scan — O(log n) depth on
    device, the running-aggregate half of SQL window frames
    (ref: DataFusion WindowAggExec via src/query/src/datafusion.rs)."""
    def comb(a, b):
        av, af = a
        bv, bf = b
        return jnp.where(bf, bv, av + bv), af | bf

    v, _ = jax.lax.associative_scan(comb, (values, reset))
    return v


@functools.partial(jax.jit, static_argnames=("take_max",))
def segmented_cumextreme(values: jax.Array, reset: jax.Array,
                         *, take_max: bool) -> jax.Array:
    """Per-segment running max (or min) via one associative scan."""
    def comb(a, b):
        av, af = a
        bv, bf = b
        merged = jnp.maximum(av, bv) if take_max else jnp.minimum(av, bv)
        return jnp.where(bf, bv, merged), af | bf

    v, _ = jax.lax.associative_scan(comb, (values, reset))
    return v


@jax.jit
def segmented_cumsum_compensated_packed(v_hi: jax.Array, v_lo: jax.Array,
                                        reset: jax.Array) -> jax.Array:
    """(2, N) stacked (sum, comp): ONE device buffer = one host
    readback (every fetched buffer is its own device->host transfer)."""
    s, c = segmented_cumsum_compensated(v_hi, v_lo, reset)
    return jnp.stack([s, c])


@jax.jit
def segmented_cumsum_compensated(v_hi: jax.Array, v_lo: jax.Array,
                                 reset: jax.Array):
    """Neumaier-compensated per-segment running sum over two-float f32
    input (v_hi + v_lo ~= the f64 value): the no-x64 device path for SQL
    window running sums. Each element enters with its split low part as
    the initial compensation; the combine two-sums the high parts and
    accumulates the rounding residue, so sum+comp recovers the f64
    running sum to ~1 ulp (the pattern proven by flow/device_state.py's
    Neumaier state slots). Returns (sum, comp) f32 arrays."""
    def comb(a, b):
        a_s, a_c, a_f = a
        b_s, b_c, b_f = b
        t = a_s + b_s
        e = jnp.where(jnp.abs(a_s) >= jnp.abs(b_s),
                      (a_s - t) + b_s, (b_s - t) + a_s)
        return (jnp.where(b_f, b_s, t),
                jnp.where(b_f, b_c, a_c + b_c + e),
                a_f | b_f)

    s, c, _ = jax.lax.associative_scan(comb, (v_hi, v_lo, reset))
    return s, c


@functools.partial(jax.jit, static_argnames=("take_max",))
def segmented_cumextreme2(v_hi: jax.Array, v_lo: jax.Array,
                          reset: jax.Array, *, take_max: bool):
    """Per-segment running extreme over two-float (hi, lo) pairs:
    lexicographic compare keeps f64 ordering without x64 (values whose
    f32 roundings tie are ordered by their low parts). Returns the
    winning (hi, lo) pair arrays."""
    def comb(a, b):
        ah, al, af = a
        bh, bl, bf = b
        if take_max:
            pick_a = (ah > bh) | ((ah == bh) & (al >= bl))
        else:
            pick_a = (ah < bh) | ((ah == bh) & (al <= bl))
        mh = jnp.where(pick_a, ah, bh)
        ml = jnp.where(pick_a, al, bl)
        return (jnp.where(bf, bh, mh), jnp.where(bf, bl, ml), af | bf)

    h, l, _ = jax.lax.associative_scan(comb, (v_hi, v_lo, reset))
    return h, l
