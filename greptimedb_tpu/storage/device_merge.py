"""Device-accelerated merge of sorted SST runs.

Compaction concatenates N sorted runs and must re-sort, dedup by
(sid, ts) keeping the highest sequence, optionally back-fill
last_non_null fields, and optionally drop delete tombstones — exactly
``region.dedup_rows``. That sort/scan pipeline is the data-parallel
shape the scan kernels already run on device, so the merge runs there
too: the device computes ONLY the permutation, the keep mask and (for
last_non_null) per-field fill indices; the host then gathers the
original arrays through those indices. Values never reach the device
in a lossy dtype, which makes the device output bit-identical to the
host path BY CONSTRUCTION — asserted anyway in tests and under the
``[compaction] verify_device_merge`` knob.

Device dtype contract (no x64 on TPU): int64 ``ts`` and uint64 ``seq``
are split host-side into (hi:int32|uint32, lo:uint32) pairs whose
lexicographic order equals the 64-bit order; ``jnp.lexsort`` over the
split keys reproduces ``np.lexsort`` exactly because the composite
(sid, ts, seq) key is unique per region (sequences never repeat).

Row counts pad to power-of-two buckets (padding sorts strictly after
every real key) so the jit program compiles once per bucket, not once
per merge.
"""

from __future__ import annotations

import numpy as np

from greptimedb_tpu import concurrency
from greptimedb_tpu.errors import CompactionError
from greptimedb_tpu.storage.memtable import OP_DELETE, ColumnarRows

# below this the upload+dispatch overhead beats the host sort
DEFAULT_DEVICE_MIN_ROWS = 262144
_MIN_PAD = 1024

_program = None
_program_lock = concurrency.Lock()


def _pad_to_bucket(n: int) -> int:
    p = _MIN_PAD
    while p < n:
        p <<= 1
    return p


def _build_program():
    """Compile-once builder for the merge program (jax import deferred:
    the storage layer must stay importable without a device runtime).
    The device computes the full permutation / keep mask / fill
    indices; the host reads them back and gathers."""
    import jax
    import jax.numpy as jnp

    def prog(sid, ts_hi, ts_lo, seq_hi, seq_lo, op, n_real, valids,
             *, drop_deletes):
        n = sid.shape[0]
        order = jnp.lexsort((seq_lo, seq_hi, ts_lo, ts_hi, sid))
        s_sid = sid[order]
        s_tsh = ts_hi[order]
        s_tsl = ts_lo[order]
        s_op = op[order]
        idx = jnp.arange(n, dtype=jnp.int32)
        change = jnp.concatenate([
            jnp.ones(1, bool),
            (s_sid[1:] != s_sid[:-1])
            | (s_tsh[1:] != s_tsh[:-1])
            | (s_tsl[1:] != s_tsl[:-1]),
        ])
        last_of_run = jnp.concatenate([change[1:], jnp.ones(1, bool)])
        keep = last_of_run & (idx < n_real)
        if drop_deletes:
            keep = keep & (s_op != OP_DELETE)
        fills = {}
        if valids:
            # last-valid-index forward fill, segmented at run starts:
            # a global running max of "index if valid else -1" either
            # lands inside the current run (>= its start) or there is
            # no valid value in the run yet and the row keeps itself
            run_start = jax.lax.cummax(jnp.where(change, idx, -1))
            for name, v in valids.items():
                sv = v[order]
                m = jax.lax.cummax(jnp.where(sv, idx, -1))
                fills[name] = jnp.where(m >= run_start, m, idx)
        return order.astype(jnp.int32), keep, fills

    return jax.jit(prog, static_argnames=("drop_deletes",))


def _get_program():
    global _program
    with _program_lock:
        if _program is None:
            _program = _build_program()
        return _program


def _split64(a: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """int64/uint64 -> (hi, lo) whose lexicographic order matches the
    64-bit order: hi keeps the source signedness, lo is unsigned."""
    hi = (a >> np.uint64(32) if a.dtype == np.uint64
          else a >> 32)
    lo = (a & np.uint64(0xFFFFFFFF) if a.dtype == np.uint64
          else a & 0xFFFFFFFF)
    hi_dt = np.uint32 if a.dtype == np.uint64 else np.int32
    return hi.astype(hi_dt), lo.astype(np.uint32)


def _prep_uploads(rows: ColumnarRows, *, backfill: bool):
    """Bucket-padded sort-key uploads of the merge program:
    (upload dict, valids dict, upload bytes, pad)."""
    n = len(rows)
    pad = _pad_to_bucket(n)
    ts_hi, ts_lo = _split64(np.asarray(rows.ts, np.int64))
    seq_hi, seq_lo = _split64(np.asarray(rows.seq, np.uint64))

    def padded(a: np.ndarray, fill) -> np.ndarray:
        if pad == n:
            return np.ascontiguousarray(a)
        return np.concatenate(
            [a, np.full(pad - n, fill, a.dtype)]
        )

    # padding sorts strictly after every real key: real sids are small
    # dense region-local ids, never int32 max
    up = {
        "sid": padded(np.asarray(rows.sid, np.int32), np.int32(2**31 - 1)),
        "ts_hi": padded(ts_hi, np.int32(2**31 - 1)),
        "ts_lo": padded(ts_lo, np.uint32(0xFFFFFFFF)),
        "seq_hi": padded(seq_hi, np.uint32(0xFFFFFFFF)),
        "seq_lo": padded(seq_lo, np.uint32(0xFFFFFFFF)),
        "op": padded(np.asarray(rows.op, np.uint8), np.uint8(0)),
    }
    valids = {}
    if backfill and rows.field_valid is not None:
        valids = {
            name: padded(np.asarray(v, bool), False)
            for name, v in rows.field_valid.items()
        }
    upload = sum(a.nbytes for a in up.values()) + sum(
        a.nbytes for a in valids.values()
    )
    return up, valids, upload, pad


def _device_merge_indices(rows: ColumnarRows, *, backfill: bool,
                          drop_deletes: bool):
    """Run the device program; returns (keep_row_indices, fill_src) in
    ORIGINAL row index space — fill_src maps each kept output row to
    the original row its field value/validity comes from (last_non_null
    only; None otherwise)."""
    from greptimedb_tpu.query import readback
    from greptimedb_tpu.telemetry import device_trace

    n = len(rows)
    up, valids, upload, pad = _prep_uploads(rows, backfill=backfill)
    prog = _get_program()
    key = (pad, tuple(sorted(valids)), drop_deletes)
    with device_trace.device_call("compact_merge", key=key,
                                  rows=n) as d:
        d.transfer(upload, "upload")
        order_d, keep_d, fills_d = d.run(
            prog,
            up["sid"], up["ts_hi"], up["ts_lo"], up["seq_hi"],
            up["seq_lo"], up["op"], np.int32(n), valids,
            drop_deletes=drop_deletes,
        )
        d.wait(order_d, keep_d, fills_d)
        order = d.read(readback.read_full, order_d, np.int64)
        keep = d.read(readback.read_full, keep_d)
        fills = {name: d.read(readback.read_full, f, np.int64)
                 for name, f in fills_d.items()}
    keep_idx = order[keep]
    fill_src = None
    if fills:
        fill_src = {
            name: order[f][keep] for name, f in fills.items()
        }
    return keep_idx, fill_src


def host_merge(rows: ColumnarRows, *, merge_mode: str,
               drop_deletes: bool) -> ColumnarRows:
    """The host reference path (region.dedup_rows verbatim)."""
    from greptimedb_tpu.storage.region import dedup_rows

    return dedup_rows(rows, merge_mode=merge_mode,
                      drop_deletes=drop_deletes)


def merge_rows(
    rows: ColumnarRows,
    *,
    merge_mode: str = "last_row",
    drop_deletes: bool = False,
    device_min_rows: int = DEFAULT_DEVICE_MIN_ROWS,
    verify: bool = False,
) -> tuple[ColumnarRows, str]:
    """Sort + dedup + merge-mode-fold concatenated runs.

    Returns (merged rows, path) where path is "device" or "host".
    device_min_rows <= 0 disables the device path entirely. With
    ``verify`` the device output is asserted bit-identical against the
    host path (CompactionError on divergence — diagnostic mode)."""
    n = len(rows)
    if device_min_rows <= 0 or n < device_min_rows:
        return host_merge(rows, merge_mode=merge_mode,
                          drop_deletes=drop_deletes), "host"
    backfill = merge_mode == "last_non_null" and rows.field_valid is not None
    try:
        keep_idx, fill_src = _device_merge_indices(
            rows, backfill=backfill, drop_deletes=drop_deletes
        )
    except ImportError as e:
        # no jax runtime in this process: the merge still has to happen
        import logging

        logging.getLogger(__name__).warning(
            "device merge unavailable (%s); using host path", e
        )
        return host_merge(rows, merge_mode=merge_mode,
                          drop_deletes=drop_deletes), "host"
    fields = {}
    valids = {} if rows.field_valid is not None else None
    for name, vals in rows.fields.items():
        src = keep_idx if fill_src is None else fill_src.get(name, keep_idx)
        fields[name] = vals[src]
        if valids is not None:
            v = rows.field_valid.get(name)
            if v is not None:
                valids[name] = v[src]
    out = ColumnarRows(
        sid=rows.sid[keep_idx], ts=rows.ts[keep_idx],
        seq=rows.seq[keep_idx], op=rows.op[keep_idx],
        fields=fields,
        field_valid=valids if valids else None,
    )
    if verify:
        _assert_identical(
            out,
            host_merge(rows, merge_mode=merge_mode,
                       drop_deletes=drop_deletes),
        )
    return out, "device"


def _assert_identical(dev: ColumnarRows, host: ColumnarRows) -> None:
    def bad(what: str):
        raise CompactionError(
            f"device merge diverged from host path: {what}"
        )

    if len(dev) != len(host):
        bad(f"row count {len(dev)} != {len(host)}")
    for name in ("sid", "ts", "seq", "op"):
        if not np.array_equal(getattr(dev, name), getattr(host, name)):
            bad(f"column {name}")
    if set(dev.fields) != set(host.fields):
        bad("field set")
    for name in dev.fields:
        d, h = dev.fields[name], host.fields[name]
        # bit-identical, not value-equal: NaNs compare by bit pattern
        if d.dtype != h.dtype or not np.array_equal(
            d.view(np.uint8) if d.dtype.kind == "f" else d,
            h.view(np.uint8) if h.dtype.kind == "f" else h,
        ):
            bad(f"field {name}")
    dv = dev.field_valid or {}
    hv = host.field_valid or {}
    if set(dv) != set(hv):
        bad("validity set")
    for name in dv:
        if not np.array_equal(dv[name], hv[name]):
            bad(f"validity {name}")
