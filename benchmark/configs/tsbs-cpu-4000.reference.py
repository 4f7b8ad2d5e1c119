"""Plain reference for the TSBS cpu-only query shapes: NumPy in float64
over the arrays made from the seed. It imports nothing of the program
and takes nothing the program has made.

`precision` is for the CONTROL only (see tests/benchmark and PERF.md):
the same computation with the data and the fold held in a lower
precision, put in the program's place to show that the comparison
fails it. The reference proper always runs at float64.
"""

from __future__ import annotations

INTERVAL_MS = 10_000


def lower(np, x, precision: str):
    """x rounded to `precision`: float64 (as is), float32, or bfloat16
    (float32 with the low 16 bits of the mantissa rounded away, ties to
    even: NumPy has no bfloat16 of its own)."""
    if precision == "float64":
        return x.astype(np.float64)
    x32 = np.ascontiguousarray(x, dtype=np.float32)
    if precision == "float32":
        return x32
    if precision != "bfloat16":
        raise ValueError(f"unknown precision {precision!r}")
    bits = x32.view(np.uint32)
    rounded = ((bits + np.uint32(0x7FFF) + ((bits >> 16) & np.uint32(1)))
               & np.uint32(0xFFFF0000))
    return rounded.view(np.float32)


def range_agg(np, values, *, fields, hosts, c_lo, c_hi, bucket_cells, op,
              mask=None, precision: str = "float64"):
    """`op(field) RANGE bucket ALIGN bucket BY (hostname)` over cells
    [c_lo, c_hi): returns (n_fields, n_hosts, n_buckets) float64 and a
    bool array of the same trailing shape saying which buckets hold at
    least one row. values: (F, hosts, cells) float32; hosts: index array
    or None for all; mask: optional (hosts, cells) bool of rows present.
    """
    x = values[:, :, c_lo:c_hi]                   # a view: no copy yet
    m = None if mask is None else mask[:, c_lo:c_hi]
    if hosts is not None:
        x = x[np.ix_(list(fields), list(hosts))]
        m = None if m is None else m[list(hosts)]
    else:
        x = x[list(fields)]
    n_f, n_h, n_c = x.shape
    if n_c % bucket_cells:
        raise ValueError("the cell range is not a whole number of buckets")
    n_b = n_c // bucket_cells
    x = lower(np, x, precision).reshape(n_f, n_h, n_b, bucket_cells)
    if m is None:
        present = np.ones((n_h, n_b), bool)
        if op == "max":
            out = x.max(axis=3)
        elif op == "avg":
            out = _mean(np, x, precision)
        else:
            raise ValueError(f"unknown op {op!r}")
        return out.astype(np.float64), present
    m = m.reshape(n_h, n_b, bucket_cells)
    cnt = m.sum(axis=2)
    present = cnt > 0
    if op == "max":
        out = np.where(m[None], x, -np.inf).max(axis=3)
    elif op == "avg":
        out = _mean(np, np.where(m[None], x, 0), precision,
                    np.maximum(cnt, 1)[None])
    else:
        raise ValueError(f"unknown op {op!r}")
    return out.astype(np.float64), present


def _mean(np, x, precision, count=None):
    """Mean over the last axis; `count` where fewer cells than the axis
    holds are present (the absent ones are zeros in x)."""
    if count is None:
        count = x.shape[3]
    if precision == "float64":
        return x.sum(axis=3) / count
    # the control: a running sum held in the lower precision
    acc = lower(np, np.zeros(x.shape[:3], np.float32), precision)
    for i in range(x.shape[3]):
        acc = lower(np, acc + x[..., i], precision)
    return lower(np, acc / np.asarray(count, np.float32), precision)


def as_rows(values, present, *, hostnames, hosts, t_lo_ms, bucket_ms):
    """{(ts_ms, hostname): (v_field0, ...)} for the buckets present."""
    n_f, n_h, n_b = values.shape
    idx = range(n_h) if hosts is None else hosts
    out = {}
    for i, h in enumerate(idx):
        name = hostnames[h]
        for b in range(n_b):
            if present[i, b]:
                out[(t_lo_ms + b * bucket_ms, name)] = tuple(
                    values[:, i, b].tolist())
    return out
