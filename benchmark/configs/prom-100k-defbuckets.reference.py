"""Plain reference for the panel query of `prom-100k-defbuckets`:

    histogram_quantile(phi, sum by (le) (rate(<metric>_bucket[range])))

NumPy in float64 over the arrays made from the seed. It imports nothing
of the program and takes nothing the program has made.

Semantics, and where they depart from Prometheus:
- `rate` is functions.go `extrapolatedRate` as Prometheus 2.x has it
  (the duration-to-zero cap is applied before the extrapolation
  threshold is tested; 3.x tests the threshold first, which differs only
  for a counter that would reach zero inside the window's first gap).
- A window is left-open, `(t - range, t]`, as Prometheus 3.0 has it;
  2.x included a sample at exactly `t - range`.
- Counter resets: a sample below its predecessor adds the predecessor.
- `histogram_quantile` is quantile.go `bucketQuantile`: no `+Inf`
  bucket or no observations, no answer (Prometheus answers NaN; the
  system under test leaves the step out); buckets forced monotonic by a
  running maximum (Prometheus also ignores relative deltas under 1e-12,
  which a running maximum covers); a rank in the `+Inf` bucket answers
  the highest finite bound; the first bucket interpolates from 0.
- Native histograms, staleness markers and `phi` outside [0, 1] are not
  part of the configuration.

`precision` is for the CONTROL only: the same computation with the data
and every intermediate held in a lower precision, put in the program's
place to show that the comparison fails it. The reference proper always
runs at float64.
"""

from __future__ import annotations


def lower(np, x, precision: str):
    """x rounded to `precision`: float64 (as is), float32, or bfloat16
    (float32 with the low 16 bits of the mantissa rounded away, ties to
    even: NumPy has no bfloat16 of its own)."""
    if precision == "float64":
        return np.asarray(x, np.float64)
    x32 = np.ascontiguousarray(x, dtype=np.float32)
    if precision == "float32":
        return x32
    if precision != "bfloat16":
        raise ValueError(f"unknown precision {precision!r}")
    bits = x32.view(np.uint32)
    rounded = ((bits + np.uint32(0x7FFF) + ((bits >> 16) & np.uint32(1)))
               & np.uint32(0xFFFF0000))
    return rounded.view(np.float32)


def window_bounds(np, ts, steps, range_ms: int):
    """For each series row of `ts` (rows, samples; ascending) and each
    step t: the indices of the first sample after t - range and of the
    last sample at or before t. Both (rows, steps) int64."""
    first = np.empty((ts.shape[0], len(steps)), np.int64)
    last = np.empty_like(first)
    for i in range(ts.shape[0]):
        first[i] = np.searchsorted(ts[i], steps - range_ms, side="right")
        last[i] = np.searchsorted(ts[i], steps, side="right") - 1
    return first, last


def extrapolated_rate(np, ts, values, steps, range_ms: int,
                      precision: str = "float64"):
    """Per-series `rate(v[range])` at each step. ts: (instances,
    samples) int64 ms, shared by the series of one instance; values:
    (instances, buckets, samples). Returns (rate, present), both
    (instances, buckets, steps); a window with fewer than two samples
    has no rate."""
    def r(x):
        return lower(np, x, precision)

    steps = np.asarray(steps, np.int64)
    first, last = window_bounds(np, ts, steps, range_ms)
    present = last - first >= 1
    n_samples = ts.shape[1]
    k0 = np.clip(first, 0, n_samples - 1)
    k1 = np.clip(last, 0, n_samples - 1)
    v = r(values)
    v0 = np.take_along_axis(v, k0[:, None, :], axis=2)
    v1 = np.take_along_axis(v, k1[:, None, :], axis=2)
    # a reset: the sample is below its predecessor, which is added
    drop = np.zeros_like(v)
    drop[:, :, 1:] = np.where(v[:, :, 1:] < v[:, :, :-1], v[:, :, :-1], 0)
    drops = r(np.cumsum(drop, axis=2))
    delta = r(r(v1 - v0) + r(np.take_along_axis(drops, k1[:, None, :], 2)
                             - np.take_along_axis(drops, k0[:, None, :], 2)))
    # times in seconds from the first window's open end: small numbers,
    # as a program would hold them
    origin = int(steps[0]) - range_ms
    t0 = r((np.take_along_axis(ts, k0, 1) - origin) / 1000.0)[:, None, :]
    t1 = r((np.take_along_axis(ts, k1, 1) - origin) / 1000.0)[:, None, :]
    t_end = r((steps - origin) / 1000.0)[None, None, :]
    range_s = r(np.asarray(range_ms / 1000.0))
    to_start = r(t0 - r(t_end - range_s))
    to_end = r(t_end - t1)
    sampled = r(t1 - t0)
    count = (last - first + 1)[:, None, :]
    average = r(sampled / np.maximum(count - 1, 1))
    safe = np.where(delta == 0, 1, delta)
    to_zero = np.where((delta > 0) & (v0 >= 0),
                       r(sampled * r(v0 / safe)), np.inf)
    to_start = np.minimum(to_start, to_zero)
    threshold = r(average * r(np.asarray(1.1)))
    half = r(average / 2)
    span = r(sampled + np.where(to_start < threshold, to_start, half))
    span = r(span + np.where(to_end < threshold, to_end, half))
    factor = r(span / np.where(sampled == 0, 1, sampled))
    rate = r(r(delta * factor) / range_s)
    present = np.broadcast_to(present[:, None, :], rate.shape)
    return np.where(present, rate, 0), present


def sum_by_le(np, rate, present, precision: str = "float64"):
    """`sum by (le)`: (instances, buckets, steps) -> (buckets, steps)
    and which of them hold at least one series."""
    any_present = present.any(axis=0)
    if precision == "float64":
        return rate.sum(axis=0), any_present
    # the control: a running sum held in the lower precision
    acc = lower(np, np.zeros(rate.shape[1:], np.float32), precision)
    for i in range(rate.shape[0]):
        acc = lower(np, acc + rate[i], precision)
    return acc, any_present


def histogram_quantile(np, bounds, buckets, present, phi: float,
                       precision: str = "float64"):
    """quantile.go `bucketQuantile` at each step. bounds: ascending
    upper bounds; buckets, present: (len(bounds), steps). Returns
    {step index: value} for the steps that have an answer."""
    def r(x):
        return lower(np, np.asarray(x).reshape(1), precision)[0]

    out = {}
    order = np.argsort(np.asarray(bounds, np.float64), kind="stable")
    le = [r(bounds[b]) for b in order]
    if not np.isposinf(le[-1]) or len(le) < 2:
        return out
    for j in range(buckets.shape[1]):
        held = [b for b in order if present[b, j]]
        if not held or not np.isposinf(bounds[held[-1]]):
            continue
        counts, top = [], r(0.0)
        for b in order:
            if present[b, j]:
                top = max(top, r(buckets[b, j]))    # forced monotonic
            counts.append(top)
        observations = counts[-1]
        if not observations > 0:
            continue
        rank = r(r(phi) * observations)
        b = next((i for i, c in enumerate(counts[:-1]) if c >= rank),
                 len(counts) - 1)
        if b == len(counts) - 1:
            out[j] = float(le[-2])
            continue
        if b == 0 and le[0] <= 0:
            out[j] = float(le[0])
            continue
        start, count = r(0.0), counts[b]
        if b > 0:
            start = le[b - 1]
            count = r(count - counts[b - 1])
            rank = r(rank - counts[b - 1])
        out[j] = float(r(start + r(r(le[b] - start) * r(rank / count))))
    return out
