"""The prefix-path window kernels fetch a window's last and first sample
by carrying values along the cell axis and reading them at the steps'
shared columns (ops/window.py:_carry). They are held here, bit for bit,
to the formulation they replaced: scan for the sample's index, then
`take_along_axis` with an index of its own in every series."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from greptimedb_tpu.ops import window as W

S = 24
CELLS = (1, 12, 121, 128, 129, 243, 481)
TPS = 1000.0


@pytest.fixture(autouse=True, scope="module")
def x64_off():
    """As a server runs: float32 values, int32 ticks."""
    saved = bool(jax.config.read("jax_enable_x64"))
    jax.config.update("jax_enable_x64", False)
    yield
    jax.config.update("jax_enable_x64", saved)


# ----------------------------------------------------------------------
# the replaced formulation: index scans + per-series gathers
# ----------------------------------------------------------------------

def _last_idx(has):
    i = jnp.broadcast_to(jnp.arange(has.shape[1], dtype=jnp.int32), has.shape)
    return jax.lax.cummax(jnp.where(has, i, jnp.int32(-1)), axis=1)


def _first_idx(has):
    t = has.shape[1]
    back = _last_idx(jnp.flip(has, axis=1))
    return jnp.flip(jnp.int32(t - 1) - back, axis=1)


def _prev_idx(lastidx):
    return jnp.pad(lastidx[:, :-1], ((0, 0), (1, 0)), constant_values=-1)


def _take(arr, idx):
    return jnp.take_along_axis(arr, idx, axis=1)


def _prefix(x):
    return jnp.pad(jnp.cumsum(x, axis=1), ((0, 0), (1, 0)))


def _ref_count(has, lo, hi):
    c = _prefix(has.astype(jnp.int32))
    return c[:, hi + 1] - c[:, lo + 1]


def _ref_prev_val(vals, has):
    pl = _prev_idx(_last_idx(has))
    return has & (pl >= 0), _take(vals, jnp.maximum(pl, 0))


@functools.partial(jax.jit, static_argnames=("is_counter", "is_rate"))
def _ref_rate(vals, has, tsg, lo, hi, t_end, range_ticks, tps, *,
              is_counter, is_rate):
    dt = vals.dtype
    li = _last_idx(has)[:, hi]
    fi = _first_idx(has)[:, lo + 1]
    li_s = jnp.maximum(li, 0)
    fi_s = jnp.minimum(fi, vals.shape[1] - 1)
    valid = (li > lo[None, :]) & (fi <= hi[None, :]) & (fi < li)
    v_last, v_first = _take(vals, li_s), _take(vals, fi_s)
    t_last = _take(tsg, li_s).astype(dt)
    t_first = _take(tsg, fi_s).astype(dt)
    delta = v_last - v_first
    if is_counter:
        pair, prev_val = _ref_prev_val(vals, has)
        drop = jnp.where(pair & (vals < prev_val), prev_val, jnp.zeros((), dt))
        d = _prefix(drop)
        delta = delta + (d[:, hi + 1] - _take(d, fi_s + 1))
    cnt = _ref_count(has, lo, hi).astype(dt)
    t_end_f = t_end[None, :].astype(dt)
    tps = jnp.asarray(tps, dt)
    dur_start = (t_first - (t_end_f - jnp.asarray(range_ticks, dt))) / tps
    dur_end = (t_end_f - t_last) / tps
    sampled = (t_last - t_first) / tps
    avg_dur = sampled / jnp.maximum(cnt - 1, 1)
    if is_counter:
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
    out = delta * (extr / jnp.where(sampled == 0, 1, sampled))
    if is_rate:
        out = out / jnp.asarray(range_ticks / tps, dt)
    return (jnp.where(valid, out, jnp.zeros((), dt)),), valid


@jax.jit
def _ref_last(vals, has, tsg, lo, hi):
    li = _last_idx(has)[:, hi]
    safe = jnp.maximum(li, 0)
    return (_take(vals, safe), _take(tsg, safe)), li > lo[None, :]


@jax.jit
def _ref_first(vals, has, tsg, lo, hi):
    fi = _first_idx(has)[:, lo + 1]
    safe = jnp.minimum(fi, vals.shape[1] - 1)
    return (_take(vals, safe), _take(tsg, safe)), fi <= hi[None, :]


@jax.jit
def _ref_lookback(vals, has, tsg, hi, t_end, lookback_ticks):
    li = _last_idx(has)[:, hi]
    safe = jnp.maximum(li, 0)
    v, t = _take(vals, safe), _take(tsg, safe)
    present = (li >= 0) & (t_end[None, :] - t < jnp.int32(lookback_ticks))
    return (jnp.where(present, v, jnp.zeros((), vals.dtype)),), present


@functools.partial(jax.jit, static_argnames=("count_changes",))
def _ref_pair_count(vals, has, lo, hi, *, count_changes):
    pair, prev_val = _ref_prev_val(vals, has)
    ind = pair & ((vals != prev_val) if count_changes else (vals < prev_val))
    p = _prefix(ind.astype(jnp.int32))
    fi = _first_idx(has)[:, lo + 1]
    fi_s = jnp.minimum(fi, vals.shape[1] - 1)
    in_w = fi <= hi[None, :]
    cnt = jnp.where(in_w, p[:, hi + 1] - _take(p, fi_s + 1), 0)
    return (cnt.astype(vals.dtype),), in_w


@functools.partial(jax.jit, static_argnames=("is_rate",))
def _ref_instant_delta(vals, has, tsg, lo, hi, tps, *, is_rate):
    dt = vals.dtype
    lastidx = _last_idx(has)
    li = lastidx[:, hi]
    li_s = jnp.maximum(li, 0)
    pi = _take(_prev_idx(lastidx), li_s)
    pi_s = jnp.maximum(pi, 0)
    valid = (li > lo[None, :]) & (pi > lo[None, :]) & (pi >= 0)
    v1, v2 = _take(vals, pi_s), _take(vals, li_s)
    t1, t2 = _take(tsg, pi_s).astype(dt), _take(tsg, li_s).astype(dt)
    if is_rate:
        dv = jnp.where(v2 < v1, v2, v2 - v1)
        out = dv / (jnp.maximum(t2 - t1, 1) / jnp.asarray(tps, dt))
    else:
        out = v2 - v1
    return (jnp.where(valid, out, jnp.zeros((), dt)),), valid


# ----------------------------------------------------------------------
# (kernel under test, replaced formulation), both -> (outputs, present)
# ----------------------------------------------------------------------

def _rate_pair(is_counter, is_rate):
    def new(g):
        return _split(W.extrapolated_rate(
            g.vals, g.has, g.tsg, g.lo, g.hi, g.t_end, g.range_ticks, TPS,
            is_counter=is_counter, is_rate=is_rate))

    def ref(g):
        return _ref_rate(g.vals, g.has, g.tsg, g.lo, g.hi, g.t_end,
                         g.range_ticks, TPS, is_counter=is_counter,
                         is_rate=is_rate)
    return new, ref


def _split(res):
    return tuple(res[:-1]), res[-1]


KERNELS = {
    "rate": _rate_pair(True, True),
    "increase": _rate_pair(True, False),
    "delta": _rate_pair(False, False),
    "window_last": (
        lambda g: _split(W.window_last(g.vals, g.has, g.tsg, g.lo, g.hi)),
        lambda g: _ref_last(g.vals, g.has, g.tsg, g.lo, g.hi),
    ),
    "window_first": (
        lambda g: _split(W.window_first(g.vals, g.has, g.tsg, g.lo, g.hi)),
        lambda g: _ref_first(g.vals, g.has, g.tsg, g.lo, g.hi),
    ),
    "instant_lookback": (
        lambda g: _split(W.instant_lookback(
            g.vals, g.has, g.tsg, g.hi, g.t_end, g.lookback_ticks)),
        lambda g: _ref_lookback(g.vals, g.has, g.tsg, g.hi, g.t_end,
                                g.lookback_ticks),
    ),
    "changes": (
        lambda g: _split(W.window_pair_count(
            g.vals, g.has, g.lo, g.hi, count_changes=True)),
        lambda g: _ref_pair_count(g.vals, g.has, g.lo, g.hi,
                                  count_changes=True),
    ),
    "resets": (
        lambda g: _split(W.window_pair_count(
            g.vals, g.has, g.lo, g.hi, count_changes=False)),
        lambda g: _ref_pair_count(g.vals, g.has, g.lo, g.hi,
                                  count_changes=False),
    ),
    "idelta": (
        lambda g: _split(W.instant_delta(
            g.vals, g.has, g.tsg, g.lo, g.hi, TPS, is_rate=False)),
        lambda g: _ref_instant_delta(g.vals, g.has, g.tsg, g.lo, g.hi, TPS,
                                     is_rate=False),
    ),
    "irate": (
        lambda g: _split(W.instant_delta(
            g.vals, g.has, g.tsg, g.lo, g.hi, TPS, is_rate=True)),
        lambda g: _ref_instant_delta(g.vals, g.has, g.tsg, g.lo, g.hi, TPS,
                                     is_rate=True),
    ),
}


class _Grid:
    """S series x t cells in float32: gaps of every density, an empty
    series, series holding only the first or only the last cell, series
    empty at both, counter resets, NaN and infinite samples; windows of
    20 cells whose steps start before the grid and end past it, clipped
    as promql/fast.py:_plan_windows clips them."""

    def __init__(self, t: int, seed: int):
        rng = np.random.default_rng(seed)
        density = rng.uniform(0.05, 0.98, size=(S, 1))
        has = rng.random((S, t)) < density
        has[0] = False                          # an empty series
        has[1] = True                           # a full one
        has[2] = False
        has[2, 0] = True                        # only the first cell
        has[3] = False
        has[3, -1] = True                       # only the last cell
        has[4:8, 0] = False                     # empty at the first cell
        has[4:8, -1] = False                    # and at the last
        vals = np.cumsum(rng.integers(0, 50, size=(S, t)), axis=1).astype(
            np.float64)
        # counter resets: from a random cell on, the count starts again
        for s in range(8, S, 2):
            at = int(rng.integers(0, t))
            vals[s, at:] -= vals[s, at] - rng.integers(0, 5)
        vals[9] = -vals[9]                      # a falling, negative series
        vals = vals.astype(np.float32) + rng.random((S, t)).astype(np.float32)
        odd = rng.random((S, t))
        vals[odd < 0.02] = np.nan
        vals[(odd >= 0.02) & (odd < 0.03)] = np.inf
        vals[(odd >= 0.03) & (odd < 0.04)] = -np.inf
        # a sample's tick lies inside its cell ((i-1) * 1000, i * 1000]
        tsg = (np.arange(t)[None, :] * 1000
               - rng.integers(0, 1000, size=(S, t))).astype(np.int32)
        w = 20
        j = min(t + 6, 121)
        hi_raw = np.unique(np.linspace(-3, t + 2, j).astype(np.int64))
        hi = np.clip(hi_raw, 0, t - 1).astype(np.int32)
        lo = np.minimum(np.clip(hi_raw - w, 0, t - 1), hi).astype(np.int32)
        self.vals, self.has = jnp.asarray(vals), jnp.asarray(has)
        self.tsg = jnp.asarray(tsg)
        self.lo, self.hi = jnp.asarray(lo), jnp.asarray(hi)
        self.t_end = jnp.asarray((hi_raw * 1000).astype(np.int32))
        self.range_ticks = w * 1000
        self.lookback_ticks = 5 * 1000


# their outputs are fetched samples, no arithmetic on them: a NaN keeps
# its very bits too. Elsewhere a NaN that arithmetic made (inf - inf, or
# a NaN sample passed on) is a NaN on both sides, its sign and payload
# being the compiler's choice of instruction and operand order
FETCH_ONLY = {"window_last", "window_first", "instant_lookback"}


def _bits(a, *, nan_bits: bool) -> np.ndarray:
    a = np.asarray(a)
    assert a.dtype.itemsize == 4, a.dtype
    bits = a.view(np.uint32)
    if nan_bits or a.dtype.kind != "f":
        return bits
    return np.where(np.isnan(a), np.uint32(0x7FC00000), bits)


@pytest.mark.parametrize("t", CELLS)
@pytest.mark.parametrize("kernel", sorted(KERNELS))
def test_carried_fetch_equals_per_series_gather(kernel, t):
    new, ref = KERNELS[kernel]
    for seed in (7, 2147483659):
        g = _Grid(t, seed)
        got, got_present = new(g)
        want, want_present = ref(g)
        want_present = np.asarray(want_present)
        assert np.array_equal(np.asarray(got_present), want_present)
        assert len(got) == len(want)
        for a, b in zip(got, want):
            assert a.dtype == b.dtype and a.dtype.itemsize == 4
            nan_bits = kernel in FETCH_ONLY
            assert np.array_equal(_bits(a, nan_bits=nan_bits)[want_present],
                                  _bits(b, nan_bits=nan_bits)[want_present])
        if t >= 121:
            # the case is not vacuous: some windows answer, some do not
            assert want_present.any() and not want_present.all()


# ----------------------------------------------------------------------
# structure: nothing is fetched by an index that differs by series
# ----------------------------------------------------------------------

def _eqns(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (tuple, list)) else (v,)):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from _eqns(inner)


def _per_series_gathers(fn, *args, series: int):
    jaxpr = jax.make_jaxpr(fn)(*args)
    return [
        eqn for eqn in _eqns(jaxpr.jaxpr)
        if eqn.primitive.name == "gather"
        and series in eqn.invars[1].aval.shape
    ]


def test_rate_has_no_gather_with_an_index_per_series():
    s, t, j = 64, 243, 121          # 64 appears in no other dimension
    vals = jnp.zeros((s, t), jnp.float32)
    has = jnp.ones((s, t), bool)
    tsg = jnp.zeros((s, t), jnp.int32)
    hi = jnp.arange(j, dtype=jnp.int32) * 2
    lo = jnp.maximum(hi - 20, 0)

    def rate(vals, has, tsg, lo, hi):
        return W.extrapolated_rate(vals, has, tsg, lo, hi, hi, 20_000, TPS,
                                   is_counter=True, is_rate=True)

    assert _per_series_gathers(rate, vals, has, tsg, lo, hi, series=s) == []
    # the walk does see such a gather where there is one
    found = _per_series_gathers(
        lambda a, i: jnp.take_along_axis(a, i, axis=1),
        vals, jnp.zeros((s, j), jnp.int32), series=s)
    assert len(found) == 1
