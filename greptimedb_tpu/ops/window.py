"""Window kernels over (series x time) grids.

Replaces the reference's per-series streaming window materialization
(/root/reference/src/promql/src/extension_plan/range_manipulate.rs and the
RangeArray ragged view, /root/reference/src/promql/src/range_array.rs) with
two TPU-friendly formulations:

- prefix path: scans along the cell axis, read at the steps' columns
  (O(S*T) memory, no per-window gather, and no gather whose index
  differs by series). A window's sum or count is a difference of
  per-series prefix sums at the columns hi + 1 and lo + 1. Its last
  (first) sample is, above 128 cells, the sample carried along the cell
  axis by `_carry` and read at the column hi (lo + 1): the columns are
  the same for every series, so every fetch is `arr[:, idx]` with a (J,)
  index; up to 128 cells the sample's cell is compared with every cell
  (`_take_cells`). Used for sum/count/avg, the extrapolated rate family,
  changes/resets, first/last/idelta/irate and the instant selector.
- gather path: materialize (S, J, L) window tensors by gathering L cells per
  output step. Used for order statistics and sequential folds (min/max/
  quantile/stddev/holt_winters/deriv/predict_linear).

Window j covers grid cells [lo_j+1 .. hi_j] (samples with ts in
(t_end_j - range, t_end_j]), matching PromQL's half-open window.

All kernels return (values, present_mask) pairs shaped (S, J).
"""

from __future__ import annotations

import functools
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from greptimedb_tpu.ops.grid import GridSpec


@dataclass
class Windows:
    """Host-built window description for a range evaluation.

    Built so that every window boundary lands exactly on a cell boundary:
    res divides step, range and (start - t0)."""

    lo: np.ndarray        # (J,) int32 cell index, window = cells (lo, hi]
    hi: np.ndarray        # (J,) int32
    t_end: np.ndarray     # (J,) int32 window end, device ticks from t0
    range_ticks: int      # window length in device ticks
    range_seconds: float

    @property
    def num_steps(self) -> int:
        return len(self.hi)

    @property
    def num_cells_per_window(self) -> int:
        return int(self.hi[0] - self.lo[0])


def plan_grid_and_windows(
    start_ms: int, end_ms: int, step_ms: int, range_ms: int,
    *, max_cells: int = 4_000_000, data_interval_ms: int | None = None,
) -> tuple[GridSpec, Windows]:
    """Choose a grid resolution + origin so windows align with cells.

    res = gcd(step, range[, data_interval]) — windows then cover whole cells
    exactly. If that produces too many cells, coarsen to a divisor-free fit
    (approximation documented in ops/grid.py)."""
    step_ms = max(int(step_ms), 1)
    range_ms = max(int(range_ms), 1)
    res = int(np.gcd(step_ms, range_ms))
    if data_interval_ms and data_interval_ms > 0:
        res = int(np.gcd(res, int(data_interval_ms)))
    span = (end_ms - start_ms) + range_ms
    while span // res > max_cells:
        res *= 2  # coarsen: sacrifices exact boundary alignment on huge spans
    t0 = start_ms - range_ms
    # cells are (t0+(i-1)res, t0+i*res]; a sample at exactly end_ms maps to
    # cell span//res, so the grid needs span//res + 1 cells (cell 0 holds
    # only ts == t0, which every window's half-open lower bound excludes).
    num_cells = span // res + 1
    spec = GridSpec.build(t0, res, num_cells)
    steps = np.arange(start_ms, end_ms + 1, step_ms, dtype=np.int64)
    hi = np.minimum((steps - t0) // res, num_cells - 1).astype(np.int32)
    w_cells = max(range_ms // res, 1)
    lo = np.maximum(hi - w_cells, 0).astype(np.int32)
    t_end = ((steps - t0) // spec.unit).astype(np.int32)
    return spec, Windows(
        lo=lo, hi=hi, t_end=t_end,
        range_ticks=int(range_ms // spec.unit),
        range_seconds=range_ms / 1000.0,
    )


# ----------------------------------------------------------------------
# prefix helpers (all (S, T) -> (S, T+1) or (S, T))
# ----------------------------------------------------------------------

def _prefix(x: jax.Array) -> jax.Array:
    """P[:, i] = sum of cells < i; shape (S, T+1)."""
    c = jnp.cumsum(x, axis=1)
    return jnp.pad(c, ((0, 0), (1, 0)))


def _gather_steps(arr: jax.Array, idx: jax.Array) -> jax.Array:
    """(S, T') array at the steps' (J,) columns -> (S, J): one index for
    every series (out-of-range columns clamp)."""
    return arr[:, idx]


def _before(x: jax.Array, fill) -> jax.Array:
    """x one cell earlier: out[:, i] = x[:, i - 1], fill at cell 0."""
    return jnp.pad(x[:, :-1], ((0, 0), (1, 0)), constant_values=fill)


# A window's last (first) sample sits at a cell that differs by series.
# Fetching it with take_along_axis serialises on the TPU: 0.3 to 1.1 s a
# plane of 131,072 x 243 cells (ledger, PR 29). Two forms replace it, by
# the number of cells T, a shape (chip readings in PERF.md, PR 30):
# - T > 128: carry the sample along the cell axis (`_carry`) and read the
#   carried plane at the steps' columns, the same for every series. Its
#   scans are passes over the grid: rate() over 131,072 x 243 cells reads
#   33 ms where the gathers read 1,720.
# - T <= 128: scan for the sample's cell and compare it with every cell
#   (`_take_cells`): work grows as S x J x T, 6.3 ms at 1M series x 12
#   cells and 38.7 at 131,072 x 121, where the scans read 14.6 and 59.8:
#   XLA splits a scan into blocks of 128 cells and runs a shorter one
#   whole, at a cost that grows with T squared.
_ONE_HOT_MAX_T = 128


def _last_present_idx(has: jax.Array) -> jax.Array:
    """lastidx[:, i] = greatest cell j <= i with a sample, else -1."""
    t = has.shape[1]
    i = jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32), has.shape)
    return jax.lax.cummax(jnp.where(has, i, jnp.int32(-1)), axis=1)


def _take_cells(arr: jax.Array, idx: jax.Array) -> jax.Array:
    """take_along_axis(arr, idx, axis=1) for (S, T) arr and (S, J) idx
    over few cells: a broadcast-compare and masked sum, fused VPU work."""
    t = arr.shape[1]
    oh = idx[:, :, None] == jnp.arange(t, dtype=jnp.int32)[None, None, :]
    return jnp.sum(
        jnp.where(oh, arr[:, None, :], jnp.zeros((), arr.dtype)), axis=2
    )


def _carry(planes, has: jax.Array, rising=(), falling=()):
    """Carry each plane's present cells forward along the cell axis.
    Returns ([filled], idx), planes first: idx[:, i] is the last present
    cell at or before i, -1 where there is none, and filled[:, i] is
    x[:, idx[:, i]], the same bits, unspecified where there is none.

    A cummax selects by its key's high bits: the key of a present cell is
    (cell << p) | piece for a p-bit piece of x's bit pattern, -1 for an
    absent one, so the running maximum is the key of the last present
    cell and its low bits are that cell's piece. A plane takes
    ceil(bits / p) scans, p being what the cell index leaves of a key:
    two for 32-bit planes under 32,768 cells and for every 64-bit plane,
    four at 4M cells. An int32 plane that never falls (never rises) from
    one present cell of a series to the next, as sample ticks and cell
    indices do, is its own key: one scan."""
    t = has.shape[1]
    out = []
    for x in planes:
        nbits = 8 * x.dtype.itemsize
        # a key as wide as the plane: 64 bits only for a 64-bit plane,
        # which exists only under x64
        kt, ut = jnp.dtype(f"int{nbits}"), jnp.dtype(f"uint{nbits}")
        pbits = nbits - 1 - max((t - 1).bit_length(), 1)
        mask = jnp.asarray((1 << pbits) - 1, ut)
        rank = (jnp.arange(t, dtype=kt) << pbits)[None, :]
        bits = jax.lax.bitcast_convert_type(x, ut)
        filled = jnp.zeros(x.shape, ut)
        for sh in range(0, nbits, pbits):
            piece = ((bits >> sh) & mask).astype(kt)
            top = jax.lax.cummax(
                jnp.where(has, rank | piece, jnp.asarray(-1, kt)), axis=1)
            filled = filled | ((top.astype(ut) & mask) << sh)
        out.append(jax.lax.bitcast_convert_type(filled, x.dtype))
    i32 = jnp.iinfo(jnp.int32)
    out += [jax.lax.cummax(jnp.where(has, x, i32.min), axis=1)
            for x in rising]
    out += [jax.lax.cummin(jnp.where(has, x, i32.max), axis=1)
            for x in falling]
    # arithmetic shift: an absent key stays -1
    return out, (top >> pbits).astype(jnp.int32)


def _last_at(planes, has: jax.Array, cols: jax.Array, rising=(), falling=()):
    """Each plane at the last present cell at or before each of the (J,)
    columns, planes first, then `rising` and `falling` (int32 planes that
    never fall, never rise, from one present cell of a series to the
    next), and that cell (-1 where there is none): ([(S, J)], (S, J)).
    The values are unspecified where there is none."""
    if has.shape[1] <= _ONE_HOT_MAX_T:
        li = _gather_steps(_last_present_idx(has), cols)
        safe = jnp.maximum(li, 0)
        return [_take_cells(x, safe) for x in (*planes, *rising, *falling)], li
    filled, lastidx = _carry(planes, has, rising, falling)
    return ([_gather_steps(f, cols) for f in filled],
            _gather_steps(lastidx, cols))


def _first_at(planes, has: jax.Array, cols: jax.Array, rising=()):
    """Each plane at the first present cell at or after each column, and
    that cell (T where there is none). The scans run over the mirrored
    grid, read at the mirrored columns: the TPU takes 5.8 ms for a scan
    against the cell order where it takes 1.5 along it (131,072 x 243
    cells; PERF.md, PR 30)."""
    t = has.shape[1]
    mirrored = jnp.maximum((t - 1) - cols, 0)
    back = jnp.flip(has, axis=1)
    if t <= _ONE_HOT_MAX_T:
        fi = (t - 1) - _gather_steps(_last_present_idx(back), mirrored)
        safe = jnp.minimum(fi, t - 1)
        return [_take_cells(x, safe) for x in (*planes, *rising)], fi
    vals, li = _last_at(
        [jnp.flip(x, axis=1) for x in planes], back, mirrored,
        falling=[jnp.flip(x, axis=1) for x in rising])
    return vals, (t - 1) - li


def _sample_before(planes, has: jax.Array, rising=()):
    """Per cell, each plane at the last present cell strictly before it,
    and that cell (-1 where there is none): ([(S, T)], (S, T))."""
    if has.shape[1] <= _ONE_HOT_MAX_T:
        pl = _before(_last_present_idx(has), -1)
        safe = jnp.maximum(pl, 0)
        return [_take_cells(x, safe) for x in (*planes, *rising)], pl
    filled, lastidx = _carry(planes, has, rising)
    # a rising plane stays rising: nothing precedes a series' first sample
    fills = [0] * len(planes) + [jnp.iinfo(jnp.int32).min] * len(rising)
    return ([_before(f, fill) for f, fill in zip(filled, fills)],
            _before(lastidx, -1))


# ----------------------------------------------------------------------
# prefix-path kernels
# ----------------------------------------------------------------------

@jax.jit
def window_count(has, lo, hi):
    c = _prefix(has.astype(jnp.int32))
    return _gather_steps(c, hi + 1) - _gather_steps(c, lo + 1)


@jax.jit
def window_sum(vals, has, lo, hi):
    p = _prefix(jnp.where(has, vals, jnp.zeros((), vals.dtype)))
    s = _gather_steps(p, hi + 1) - _gather_steps(p, lo + 1)
    cnt = window_count(has, lo, hi)
    return s, cnt > 0


@jax.jit
def window_avg(vals, has, lo, hi):
    s, _ = window_sum(vals, has, lo, hi)
    cnt = window_count(has, lo, hi)
    return s / jnp.maximum(cnt, 1).astype(s.dtype), cnt > 0


@jax.jit
def window_last(vals, has, tsg, lo, hi):
    """Most recent sample in each window: (value, ts, present)."""
    (v, t), li = _last_at((vals,), has, hi, rising=(tsg,))
    return v, t, li > lo[None, :]


@jax.jit
def window_first(vals, has, tsg, lo, hi):
    (v, t), fi = _first_at((vals,), has, lo + 1, rising=(tsg,))
    return v, t, fi <= hi[None, :]


@functools.partial(jax.jit, static_argnames=("is_counter", "is_rate"))
def extrapolated_rate(
    vals, has, tsg, lo, hi, t_end, range_ticks, tps,
    *, is_counter: bool, is_rate: bool,
):
    """Prometheus rate/increase/delta with the extrapolation rules of
    functions.go (semantics per /root/reference/src/promql/src/functions/
    extrapolate_rate.rs:120-205). Returns (value, present) shaped (S, J)."""
    dt = vals.dtype
    (v_last, t_last), li = _last_at((vals,), has, hi, rising=(tsg,))
    first = (vals,)
    if is_counter:
        (prev_val,), pl = _sample_before((vals,), has)
        pair = has & (pl >= 0)
        drop = jnp.where(pair & (vals < prev_val), prev_val, jnp.zeros((), dt))
        d = _prefix(drop)
        # the drops up to and with the window's first sample: the prefix
        # just past that sample
        first += (d[:, 1:],)
    (v_first, *d_first, t_first), fi = _first_at(
        first, has, lo + 1, rising=(tsg,))
    valid = (li > lo[None, :]) & (fi <= hi[None, :]) & (fi < li)
    # ticks from the grid's origin, cast to the values' float: in
    # float32 (x64 off, as a server runs) a millisecond tick is exact
    # up to 2^24 ms = 4.66 h from t0; a grid that spans more rounds its
    # sample times here (GridSpec.build only guards int32)
    t_last = t_last.astype(dt)
    t_first = t_first.astype(dt)

    delta = v_last - v_first
    if is_counter:
        delta = delta + (_gather_steps(d, hi + 1) - d_first[0])

    cnt = window_count(has, lo, hi).astype(dt)
    t_end_f = t_end[None, :].astype(dt)
    tps = jnp.asarray(tps, dt)
    dur_start = (t_first - (t_end_f - jnp.asarray(range_ticks, dt))) / tps
    dur_end = (t_end_f - t_last) / tps
    sampled = (t_last - t_first) / tps
    avg_dur = sampled / jnp.maximum(cnt - 1, 1)

    if is_counter:
        # avoid extrapolating a counter below zero
        dur_zero = jnp.where(
            (delta > 0) & (v_first >= 0),
            sampled * (v_first / jnp.where(delta == 0, 1, delta)),
            jnp.asarray(jnp.inf, dt),
        )
        dur_start = jnp.minimum(dur_start, dur_zero)

    thresh = avg_dur * jnp.asarray(1.1, dt)
    extr = sampled
    extr = extr + jnp.where(dur_start < thresh, dur_start, avg_dur / 2)
    extr = extr + jnp.where(dur_end < thresh, dur_end, avg_dur / 2)
    factor = extr / jnp.where(sampled == 0, 1, sampled)
    out = delta * factor
    if is_rate:
        out = out / jnp.asarray(range_ticks / tps, dt)
    return jnp.where(valid, out, jnp.zeros((), dt)), valid


@functools.partial(jax.jit, static_argnames=("count_changes",))
def window_pair_count(vals, has, lo, hi, *, count_changes: bool):
    """changes() (value differs from previous) or resets() (value drops)
    over each window. Pairs are (prev sample, sample) with both inside the
    window. Returns (count_float, present)."""
    dt = vals.dtype
    (prev_val,), pl = _sample_before((vals,), has)
    pair = has & (pl >= 0)
    if count_changes:
        ind = pair & (vals != prev_val)
    else:
        ind = pair & (vals < prev_val)
    p = _prefix(ind.astype(jnp.int32))
    # pairs up to and with the window's first sample (whose own pair
    # reaches back out of the window), as in extrapolated_rate
    (p_first,), fi = _first_at((p[:, 1:],), has, lo + 1)
    in_w = fi <= hi[None, :]
    cnt = jnp.where(in_w, _gather_steps(p, hi + 1) - p_first, 0)
    return cnt.astype(dt), in_w


@functools.partial(jax.jit, static_argnames=("is_rate",))
def instant_delta(vals, has, tsg, lo, hi, tps, *, is_rate: bool):
    """idelta (last two samples' value difference) / irate (per-second,
    with counter-reset handling)."""
    dt = vals.dtype
    # the sample before each sample, fetched at a window's last sample:
    # the sample before the last
    (pv, pt), pl = _sample_before((vals,), has, rising=(tsg,))
    (v2, v1, t2, t1, pi), li = _last_at(
        (vals, pv), has, hi, rising=(tsg, pt, pl))
    valid = (li > lo[None, :]) & (pi > lo[None, :]) & (pi >= 0)
    t1 = t1.astype(dt)
    t2 = t2.astype(dt)
    if is_rate:
        dv = jnp.where(v2 < v1, v2, v2 - v1)  # counter reset: use raw value
        dtm = jnp.maximum(t2 - t1, 1) / jnp.asarray(tps, dt)
        out = dv / dtm
    else:
        out = v2 - v1
    return jnp.where(valid, out, jnp.zeros((), dt)), valid


# ----------------------------------------------------------------------
# gather-path kernels
# ----------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("num_cells",))
def gather_windows(vals, has, tsg, lo, hi, num_cells: int):
    """Materialize (S, J, L) window tensors: cell hi_j - k for k in [0, L).
    Cells are in reverse time order (k=0 is the window end). A window is
    the cells (lo_j, hi_j]: where hi_j was clipped to the grid's last
    cell (a step past the end of the data) it holds fewer than L cells,
    and the lanes at or below lo_j are masked out."""
    k = jnp.arange(num_cells, dtype=jnp.int32)
    idx = hi[None, :, None] - k[None, None, :]        # (1, J, L)
    ok = (idx >= 0) & (idx > lo[None, :, None])
    idx_s = jnp.maximum(idx, 0)
    g_vals = jnp.take(vals, idx_s[0], axis=1)          # (S, J, L)
    g_has = jnp.take(has, idx_s[0], axis=1) & ok[0]
    g_ts = jnp.take(tsg, idx_s[0], axis=1)
    return g_vals, g_has, g_ts


@functools.partial(jax.jit, static_argnames=("num_cells", "op"))
def window_minmax(vals, has, tsg, lo, hi, num_cells: int, op: str):
    g_vals, g_has, _ = gather_windows(vals, has, tsg, lo, hi, num_cells)
    dt = vals.dtype
    if op == "min":
        fill = jnp.asarray(jnp.inf, dt)
        out = jnp.min(jnp.where(g_has, g_vals, fill), axis=2)
    else:
        fill = jnp.asarray(-jnp.inf, dt)
        out = jnp.max(jnp.where(g_has, g_vals, fill), axis=2)
    present = jnp.any(g_has, axis=2)
    return jnp.where(present, out, jnp.zeros((), dt)), present


@functools.partial(jax.jit, static_argnames=("num_cells", "sample_var"))
def window_stdvar(vals, has, tsg, lo, hi, num_cells: int, *,
                  sample_var: bool = False):
    """Population stddev/stdvar over each window (Prometheus semantics).
    Returns (var, stddev, present)."""
    g_vals, g_has, _ = gather_windows(vals, has, tsg, lo, hi, num_cells)
    dt = vals.dtype
    n = jnp.sum(g_has, axis=2).astype(dt)
    n1 = jnp.maximum(n, 1)
    mean = jnp.sum(jnp.where(g_has, g_vals, 0), axis=2) / n1
    dev = jnp.where(g_has, g_vals - mean[:, :, None], 0)
    denom = jnp.maximum(n - 1, 1) if sample_var else n1
    var = jnp.sum(dev * dev, axis=2) / denom
    present = n > 0
    return var, jnp.sqrt(var), present


def _small_sort_lanes(x, length: int):
    """Ascending sort along the last axis via an odd-even transposition
    network: for the short windows quantile_over_time sees (a handful of
    cells), ~L^2/2 vectorized min/max exchanges on (S, J) planes beat
    XLA's general variadic sort by a wide margin at 1M series."""
    cols = [x[:, :, i] for i in range(length)]
    for p in range(length):
        for i in range(p % 2, length - 1, 2):
            a, b = cols[i], cols[i + 1]
            # NaN-last exchange (jnp.sort parity): a min/max pair would
            # smear one NaN into BOTH lanes
            a_first = (a <= b) | jnp.isnan(b)
            cols[i] = jnp.where(a_first, a, b)
            cols[i + 1] = jnp.where(a_first, b, a)
    return jnp.stack(cols, axis=2)


# the rank's lane differs by series AND step, so no shared column serves
# it: up to this many cells a window the lanes are compared one by one
_QUANTILE_ONE_HOT_MAX_CELLS = 128


@functools.partial(jax.jit, static_argnames=("num_cells",))
def window_quantile(vals, has, tsg, lo, hi, num_cells: int, q):
    """phi-quantile with linear interpolation (Prometheus
    quantile_over_time). q may be a scalar or (J,) array."""
    g_vals, g_has, _ = gather_windows(vals, has, tsg, lo, hi, num_cells)
    dt = vals.dtype
    fill = jnp.asarray(jnp.inf, dt)
    masked = jnp.where(g_has, g_vals, fill)
    if num_cells <= 16:
        sorted_vals = _small_sort_lanes(masked, num_cells)
    else:
        sorted_vals = jnp.sort(masked, axis=2)
    n = jnp.sum(g_has, axis=2)
    present = n > 0
    q = jnp.asarray(q, dt)
    rank = q * jnp.maximum(n - 1, 0).astype(dt)
    lo_i = jnp.clip(jnp.floor(rank).astype(jnp.int32), 0, num_cells - 1)
    hi_i = jnp.clip(jnp.ceil(rank).astype(jnp.int32), 0, num_cells - 1)
    if num_cells <= _QUANTILE_ONE_HOT_MAX_CELLS:
        # data-dependent take_along_axis lowers to a serializing
        # scatter on TPU (~250ms at 1M series); a one-hot masked
        # reduction over the tiny lane axis is fused VPU work
        lanes = jnp.arange(num_cells, dtype=jnp.int32)[None, None, :]
        z = jnp.zeros((), dt)
        v_lo = jnp.sum(jnp.where(lanes == lo_i[:, :, None],
                                 sorted_vals, z), axis=2)
        v_hi = jnp.sum(jnp.where(lanes == hi_i[:, :, None],
                                 sorted_vals, z), axis=2)
    else:
        v_lo = jnp.take_along_axis(
            sorted_vals, lo_i[:, :, None], axis=2)[:, :, 0]
        v_hi = jnp.take_along_axis(
            sorted_vals, hi_i[:, :, None], axis=2)[:, :, 0]
    frac = rank - jnp.floor(rank)
    out = v_lo + (v_hi - v_lo) * frac
    return jnp.where(present, out, jnp.zeros((), dt)), present


@functools.partial(jax.jit, static_argnames=("num_cells",))
def window_linear_fit(vals, has, tsg, lo, hi, t_end, num_cells: int, tps):
    """Least-squares line over window samples; t is seconds relative to the
    window end (small, f32-safe). Returns (slope, intercept_at_end, n)."""
    g_vals, g_has, g_ts = gather_windows(vals, has, tsg, lo, hi, num_cells)
    dt = vals.dtype
    t = (g_ts.astype(dt) - t_end[None, :, None].astype(dt)) / jnp.asarray(tps, dt)
    m = g_has.astype(dt)
    n = jnp.sum(m, axis=2)
    st = jnp.sum(t * m, axis=2)
    sv = jnp.sum(jnp.where(g_has, g_vals, 0), axis=2)
    stt = jnp.sum(t * t * m, axis=2)
    stv = jnp.sum(t * jnp.where(g_has, g_vals, 0), axis=2)
    n1 = jnp.maximum(n, 1)
    denom = n1 * stt - st * st
    slope = (n1 * stv - st * sv) / jnp.where(denom == 0, 1, denom)
    intercept = (sv - slope * st) / n1
    return slope, intercept, n


@functools.partial(jax.jit, static_argnames=("num_cells",))
def window_holt_winters(vals, has, tsg, lo, hi, num_cells: int, sf, tf):
    """Double exponential smoothing (Prometheus holt_winters semantics:
    s0 = x0, b0 = x1 - x0, then s_i = sf*x_i + (1-sf)*(s+b),
    b_i = tf*(s_i - s_prev) + (1-tf)*b). Sequential over window samples,
    vectorized over (S, J) via lax.scan along the window axis."""
    g_vals, g_has, _ = gather_windows(vals, has, tsg, lo, hi, num_cells)
    dt = vals.dtype
    # ascending time order: k = L-1 .. 0
    xs_vals = jnp.flip(g_vals, axis=2)
    xs_has = jnp.flip(g_has, axis=2)
    sf = jnp.asarray(sf, dt)
    tf = jnp.asarray(tf, dt)

    def step(carry, xs):
        s, b, x_first, cnt = carry
        x, present = xs
        # cnt: number of samples consumed so far
        new_s1 = x  # when this is the first sample
        new_b1 = jnp.zeros_like(x)
        # second sample: s = x, b = x - x_first  (Prometheus init)
        new_s2 = sf * x + (1 - sf) * (s + b)
        new_b2 = tf * (new_s2 - s) + (1 - tf) * b
        s_out = jnp.where(
            present,
            jnp.where(cnt == 0, new_s1, jnp.where(cnt == 1, x, new_s2)),
            s,
        )
        b_out = jnp.where(
            present,
            jnp.where(cnt == 0, new_b1, jnp.where(cnt == 1, x - x_first, new_b2)),
            b,
        )
        x_first = jnp.where(present & (cnt == 0), x, x_first)
        cnt = cnt + present.astype(jnp.int32)
        return (s_out, b_out, x_first, cnt), None

    shape = g_vals.shape[:2]
    init = (
        jnp.zeros(shape, dt), jnp.zeros(shape, dt), jnp.zeros(shape, dt),
        jnp.zeros(shape, jnp.int32),
    )
    (s, b, _, cnt), _ = jax.lax.scan(
        step, init,
        (jnp.moveaxis(xs_vals, 2, 0), jnp.moveaxis(xs_has, 2, 0)),
    )
    present = cnt >= 2
    return jnp.where(present, s, jnp.zeros((), dt)), present


# ----------------------------------------------------------------------
# instant (lookback) selection
# ----------------------------------------------------------------------

@jax.jit
def instant_lookback(vals, has, tsg, hi, t_end, lookback_ticks):
    """Per step, the most recent sample at or before t_end within the
    lookback delta — PromQL instant-vector selection (reference:
    /root/reference/src/promql/src/extension_plan/instant_manipulate.rs)."""
    dt = vals.dtype
    (v, t), li = _last_at((vals,), has, hi, rising=(tsg,))
    # int32-safe freshness test: ts is <= t_end by construction, so the
    # difference is small and non-positive.
    age = t_end[None, :] - t
    fresh = age < jnp.int32(lookback_ticks)
    present = (li >= 0) & fresh
    return jnp.where(present, v, jnp.zeros((), dt)), present
