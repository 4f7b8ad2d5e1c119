"""Device-resident accumulable flow state.

Capability counterpart of the reference's Hydroflow accumulable reduce
(/root/reference/src/flow/src/compute/render/reduce.rs:43-60 reduce_
accum_subgraph: per-key accumulator state updated by diff batches), laid
out TPU-first: per-group accumulators live as dense device arrays
indexed by group id, a delta batch applies as ONE jit program (segment
reductions over the batch folded into the state arrays — no scatter:
untouched segments reduce to the op identity and fold as no-ops), and a
tick finalizes EVERY group in one program, gathering the dirty slice on
device before the readback.

Supported accumulators: count, sum, mean, min, max, var_pop/var_samp/
stddev_pop/stddev_samp (s, s2, n partials), first_value/last_value over
numeric fields. Flows using count_distinct or string-valued aggregates
stay on the host path (flow/manager.py) — set-valued and string state
have no dense-array form.

Numerics are f32-safe (no jax_enable_x64 requirement, matching TPU's
native dtype): counts and presence live in exact int32 slots, running
float sums (sum/mean/var partials) carry a Neumaier compensation slot so
magnitude-driven f32 absorption is corrected at every fold, and
first/last winners order by timestamps split into two int32 halves
(hi = ts >> 20, lo = ts & 0xfffff — exact for any non-negative epoch-ms
value; negative timestamps demote the flow to the host path). Equal-
timestamp ties resolve by arrival order within a batch (segment_min /
segment_max over the row iota) and by host accumulator semantics across
batches (first_value keeps the earlier batch on a tie, last_value takes
the later one).

Group ids are interned host-side (vocabulary dicts per key column, the
same dictionary-coding the series registry uses); state arrays grow by
power-of-two capacity with a device copy, and expired groups compact by
gathering live rows into fresh arrays. The EXPIRE AFTER check is a
vectorized compare over a parallel time-key array with an O(1) min
short-circuit, not a per-key Python scan.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

# ops the device path accumulates; everything else -> host fallback
DEVICE_OPS = frozenset({
    "count", "sum", "mean", "min", "max",
    "var_pop", "var_samp", "stddev_pop", "stddev_samp",
    "first_value", "last_value",
})

_IMAX = np.int32(2**31 - 1)

# state slots per op: name -> ((kind, identity), ...); kind "f" is the
# platform float dtype, "i" is exact int32. "c" slots are Neumaier
# compensation terms paired with the float sum before them.
_SLOTS = {
    "count": (("i", 0),),
    "sum": (("f", 0.0), ("f", 0.0), ("i", 0)),          # s, comp, n
    "mean": (("f", 0.0), ("f", 0.0), ("i", 0)),         # s, comp, n
    "min": (("f", np.inf), ("i", 0)),
    "max": (("f", -np.inf), ("i", 0)),
    # k (per-group shift), s, comp, s2, comp2, n — variance accumulates
    # sum(v-k) and sum((v-k)^2) around a shift k fixed at the group's
    # first batch, so E[x^2]-E[x]^2 cancellation happens on SMALL numbers
    # and stays accurate in f32 even when |v| >> stddev
    "var_pop": (("f", 0.0), ("f", 0.0), ("f", 0.0), ("f", 0.0),
                ("f", 0.0), ("i", 0)),
    "var_samp": (("f", 0.0), ("f", 0.0), ("f", 0.0), ("f", 0.0),
                 ("f", 0.0), ("i", 0)),
    "stddev_pop": (("f", 0.0), ("f", 0.0), ("f", 0.0), ("f", 0.0),
                   ("f", 0.0), ("i", 0)),
    "stddev_samp": (("f", 0.0), ("f", 0.0), ("f", 0.0), ("f", 0.0),
                    ("f", 0.0), ("i", 0)),
    # v, ts_hi, ts_lo; sentinel hi=IMAX (first) / -1 (last) means "empty"
    "first_value": (("f", 0.0), ("i", _IMAX), ("i", _IMAX)),
    "last_value": (("f", 0.0), ("i", -1), ("i", -1)),
}


def plan_supports_device(plan) -> bool:
    return all(a.op in DEVICE_OPS for a in plan.aggs)


def _float_dtype():
    return jnp.float64 if jax.config.read("jax_enable_x64") else jnp.float32


def _ts_split(ts: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """Epoch-ms -> exact (hi, lo) int32 pair; requires ts >= 0."""
    ts64 = ts.astype(np.int64)
    return ((ts64 >> 20).astype(np.int32),
            (ts64 & 0xFFFFF).astype(np.int32))


def _kahan_fold(s, c, d):
    """Neumaier-compensated s += d: absorbs additions the raw float sum
    would round away, whichever of s and d is larger."""
    t = s + d
    c = c + jnp.where(jnp.abs(s) >= jnp.abs(d), (s - t) + d, (d - t) + s)
    return t, c


# NOTE: state is deliberately NOT donated — finalize snapshots the state
# tuple and runs outside the flow lock, so the buffers a concurrent
# apply() replaces must stay alive until that snapshot is consumed.
@functools.partial(jax.jit, static_argnames=("ops", "g"))
def _apply_program(state, gid, ts_hi, ts_lo, vals, has, *, ops: tuple,
                   g: int):
    """Fold one delta batch into the state arrays.

    state: tuple of (G,) arrays, one per slot of each agg.
    gid:   (N,) int32; ts_hi/ts_lo: (N,) int32 split timestamps.
    vals/has: per-agg (N,) value + validity arrays (stacked tuples).
    """
    out = list(state)
    si = 0

    def _nsum(ok):
        return jax.ops.segment_sum(ok.astype(jnp.int32), gid,
                                   num_segments=g)

    def _vsum(v, ok):
        return jax.ops.segment_sum(jnp.where(ok, v, 0), gid,
                                   num_segments=g)

    for j, op in enumerate(ops):
        v = vals[j]
        ok = has[j]
        if op == "count":
            out[si] = out[si] + _nsum(ok)
            si += 1
        elif op in ("sum", "mean"):
            out[si], out[si + 1] = _kahan_fold(
                out[si], out[si + 1], _vsum(v, ok)
            )
            out[si + 2] = out[si + 2] + _nsum(ok)
            si += 3
        elif op == "min":
            d = jax.ops.segment_min(
                jnp.where(ok, v, jnp.inf), gid, num_segments=g
            )
            out[si] = jnp.minimum(out[si], d)
            out[si + 1] = out[si + 1] + _nsum(ok)
            si += 2
        elif op == "max":
            d = jax.ops.segment_max(
                jnp.where(ok, v, -jnp.inf), gid, num_segments=g
            )
            out[si] = jnp.maximum(out[si], d)
            out[si + 1] = out[si + 1] + _nsum(ok)
            si += 2
        elif op in ("var_pop", "var_samp", "stddev_pop", "stddev_samp"):
            bn = _nsum(ok)
            n_old = out[si + 5]
            # pin the shift to (about) the group's first batch mean; any
            # constant near the data works, it only has to kill the
            # magnitude of the squared terms
            bmean = _vsum(v, ok) / jnp.maximum(bn, 1)
            k = jnp.where((n_old == 0) & (bn > 0), bmean, out[si])
            vk = v - k[gid]
            out[si] = k
            out[si + 1], out[si + 2] = _kahan_fold(
                out[si + 1], out[si + 2], _vsum(vk, ok)
            )
            out[si + 3], out[si + 4] = _kahan_fold(
                out[si + 3], out[si + 4], _vsum(vk * vk, ok)
            )
            out[si + 5] = n_old + bn
            si += 6
        elif op in ("first_value", "last_value"):
            last = op == "last_value"
            hi_fill = jnp.int32(-1) if last else _IMAX
            seg_ext = jax.ops.segment_max if last else jax.ops.segment_min
            # batch winner by (ts_hi, ts_lo, arrival) lexicographically,
            # one exact int32 segment reduction per component
            bh = seg_ext(jnp.where(ok, ts_hi, hi_fill), gid,
                         num_segments=g)
            c1 = ok & (ts_hi == bh[gid])
            bl = seg_ext(jnp.where(c1, ts_lo, hi_fill), gid,
                         num_segments=g)
            c2 = c1 & (ts_lo == bl[gid])
            iota = jnp.arange(ts_hi.shape[0], dtype=jnp.int32)
            widx = seg_ext(jnp.where(c2, iota, hi_fill), gid,
                           num_segments=g)
            hit = c2 & (iota == widx[gid])
            bv = jax.ops.segment_sum(
                jnp.where(hit, v, 0), gid, num_segments=g
            )
            has_cand = bh != hi_fill
            shi, slo = out[si + 1], out[si + 2]
            # cross-batch compare by timestamp with host semantics: a
            # later batch replaces at equal ts for last_value, keeps the
            # earlier arrival for first_value
            if last:
                take = has_cand & (
                    (bh > shi) | ((bh == shi) & (bl >= slo))
                )
            else:
                take = has_cand & (
                    (bh < shi) | ((bh == shi) & (bl < slo))
                )
            out[si] = jnp.where(take, bv, out[si])
            out[si + 1] = jnp.where(take, bh, shi)
            out[si + 2] = jnp.where(take, bl, slo)
            si += 3
        else:  # pragma: no cover - guarded by plan_supports_device
            raise ValueError(op)
    return tuple(out)


@functools.partial(jax.jit, static_argnames=("ops", "g"))
def _finalize_program(state, *, ops: tuple, g: int):
    """All-group finalize in one program: per-agg (values, presence)."""
    outs = []
    pres = []
    si = 0
    fdt = _float_dtype()
    for op in ops:
        if op == "count":
            n = state[si]
            outs.append(n)  # int32: exact, converted host-side
            pres.append(jnp.ones_like(n, bool))
            si += 1
        elif op in ("sum", "mean"):
            s = state[si] + state[si + 1]
            n = state[si + 2]
            ok = n > 0
            val = s / jnp.maximum(n, 1).astype(fdt) if op == "mean" else s
            outs.append(jnp.where(ok, val, 0))
            pres.append(ok)
            si += 3
        elif op in ("min", "max"):
            m, n = state[si], state[si + 1]
            ok = n > 0
            outs.append(jnp.where(ok, m, 0))
            pres.append(ok)
            si += 2
        elif op in ("var_pop", "var_samp", "stddev_pop", "stddev_samp"):
            s = state[si + 1] + state[si + 2]      # sum(v - k)
            s2 = state[si + 3] + state[si + 4]     # sum((v - k)^2)
            n = state[si + 5].astype(fdt)
            ddof = 1.0 if op.endswith("_samp") else 0.0
            ok = n > ddof
            n1 = jnp.maximum(n, 1)
            mean = s / n1                          # shift-invariant
            var = jnp.maximum(s2 / n1 - mean * mean, 0.0)
            var = var * (n1 / jnp.maximum(n1 - ddof, 1))
            out = jnp.sqrt(var) if op.startswith("stddev") else var
            outs.append(jnp.where(ok, out, 0))
            pres.append(ok)
            si += 6
        elif op in ("first_value", "last_value"):
            v, hi = state[si], state[si + 1]
            ok = hi != (jnp.int32(-1) if op == "last_value" else _IMAX)
            outs.append(jnp.where(ok, v, 0))
            pres.append(ok)
            si += 3
        else:  # pragma: no cover
            raise ValueError(op)
    return tuple(outs), tuple(pres)


_NEVER_EXPIRES = float("inf")


class DeviceFlowState:
    """Dense per-gid accumulators on device + host-side key interning."""

    def __init__(self, plan, time_key_idx: int | None = None):
        self.ops = tuple(a.op for a in plan.aggs)
        self.n_keys = len(plan.keys)
        self.time_key_idx = time_key_idx
        self._key_index: dict[tuple, int] = {}
        self._key_rows: list[tuple] = []
        self._col_dicts = None            # per-key-column Dictionary
        self._tk_vals: list[float] = []   # time-key per gid (inf: never)
        self._tk_min = _NEVER_EXPIRES
        self.capacity = 0
        self.state: tuple = ()
        self.dirty = np.zeros(0, bool)
        self.processed = 0

    # ---- group interning ----------------------------------------------
    def _append_key(self, kt: tuple) -> int:
        gid = len(self._key_rows)
        self._key_index[kt] = gid
        self._key_rows.append(kt)
        tk = self.time_key_idx
        v = _NEVER_EXPIRES
        if tk is not None and isinstance(kt[tk], (int, float)):
            v = float(kt[tk])
        self._tk_vals.append(v)
        if v < self._tk_min:
            self._tk_min = v
        return gid

    def intern_keys(self, key_cols: list[np.ndarray], n: int) -> np.ndarray:
        """Map n rows of key columns to dense gids (keyless: all gid 0).

        Per-column codes come from the incremental Dictionary interner
        (datatypes/batch.py: arrow hash-encode fast path, stable codes
        across batches), then unique composite code rows map to gids."""
        if not key_cols:
            if not self._key_rows:
                self._append_key(())
            return np.zeros(n, np.int32)
        if self._col_dicts is None:
            from greptimedb_tpu.datatypes.batch import Dictionary

            self._col_dicts = [Dictionary() for _ in key_cols]
        codes = [
            d.intern_array(_key_strings(c)).astype(np.int64)
            for d, c in zip(self._col_dicts, key_cols)
        ]
        key = codes[0]
        for d, c2 in zip(self._col_dicts[1:], codes[1:]):
            key = key * len(d) + c2
        _, first_rows, inv = np.unique(
            key, return_index=True, return_inverse=True
        )
        gids = np.empty(len(first_rows), np.int32)
        for i, row in enumerate(first_rows):
            kt = tuple(_scalar(col[row]) for col in key_cols)
            gid = self._key_index.get(kt)
            if gid is None:
                gid = self._append_key(kt)
            gids[i] = gid
        return gids[np.ravel(inv)]

    @property
    def num_groups(self) -> int:
        return len(self._key_rows)

    def key_rows(self) -> list[tuple]:
        return self._key_rows

    # ---- capacity ------------------------------------------------------
    def _slot_specs(self):
        out = []
        for op in self.ops:
            out.extend(_SLOTS[op])
        return out

    def _identity_array(self, kind, ident, cap: int):
        dt = _float_dtype() if kind == "f" else jnp.int32
        return jnp.full((cap,), ident, dt)

    def _ensure_capacity(self, g: int):
        if g <= self.capacity and self.state:
            return
        cap = max(self.capacity or 1024, 1024)
        while cap < g:
            cap *= 2
        new = []
        for i, (kind, ident) in enumerate(self._slot_specs()):
            arr = self._identity_array(kind, ident, cap)
            if self.state:
                arr = arr.at[: self.capacity].set(self.state[i])
            new.append(arr)
        self.state = tuple(new)
        nd = np.zeros(cap, bool)
        nd[: len(self.dirty)] = self.dirty
        self.dirty = nd
        self.capacity = cap

    # ---- delta application --------------------------------------------
    def apply(self, gids: np.ndarray, ts: np.ndarray,
              agg_args: list[tuple[np.ndarray | None, np.ndarray | None]]):
        """One device program folds this batch into the state."""
        n = len(gids)
        if n == 0:
            return
        if len(ts) and int(ts.min()) < 0:
            # the int32 ts split assumes epoch >= 0; manager falls back
            raise ValueError("negative timestamps: host path required")
        self._ensure_capacity(self.num_groups)
        hi, lo = _ts_split(ts)
        vals = []
        has = []
        for arr, validity in agg_args:
            if arr is None:
                vals.append(np.zeros(n, np.float32))
                has.append(np.ones(n, bool))
            else:
                vals.append(np.asarray(arr, np.float64))
                has.append(
                    np.ones(n, bool) if validity is None
                    else np.asarray(validity, bool)
                )
        # flow evals carry the same compile/execute/transfer
        # attribution (and device-program registry rows) as the query
        # path. The apply deliberately does NOT block_until_ready —
        # the delta fold overlaps host work, and the next apply's data
        # dependency orders it anyway — so the timing is flagged
        # dispatch_only and the profiler suppresses achieved-rate
        # claims for this program.
        from greptimedb_tpu.telemetry import device_trace

        d_gid = jnp.asarray(gids.astype(np.int32))
        d_hi = jnp.asarray(hi)
        d_lo = jnp.asarray(lo)
        d_vals = tuple(jnp.asarray(v) for v in vals)
        d_has = tuple(jnp.asarray(h) for h in has)
        upload = int(
            d_gid.nbytes + d_hi.nbytes + d_lo.nbytes
            + sum(int(v.nbytes) for v in d_vals)
            + sum(int(h.nbytes) for h in d_has)
        )
        with device_trace.device_call(
                "flow_apply",
                key=("flow_apply", self.ops, self.capacity),
                rows=n) as dcall:
            dcall.transfer(upload, "upload")
            self.state = dcall.run(
                _apply_program,
                self.state, d_gid, d_hi, d_lo, d_vals, d_has,
                ops=self.ops, g=self.capacity,
            )
            dcall.wait(dispatch_only=True)
        self.dirty[np.unique(gids)] = True
        self.processed += n

    # ---- finalize ------------------------------------------------------
    def snapshot_dirty(self):
        """Under the flow lock: snapshot (immutable state tuple, dirty
        gids) and clear the dirty bits. Returns None when clean."""
        g = self.num_groups
        if g == 0 or not self.dirty[:g].any():
            return None
        dirty = np.nonzero(self.dirty[:g])[0]
        self.dirty[:g] = False
        return (self.state, self.capacity, dirty)

    def finalize_snapshot(self, snap):
        """Outside the lock: one finalize program for every group; the
        dirty slice is gathered on device so only it crosses to the
        host. Returns (dirty_gids, {agg_idx: (values, present)})."""
        from greptimedb_tpu.query import readback
        from greptimedb_tpu.telemetry import device_trace

        state, cap, dirty = snap
        with device_trace.device_call(
                "flow_finalize",
                key=("flow_finalize", self.ops, cap),
                groups=int(len(dirty))) as dcall:
            outs, pres = dcall.run(
                _finalize_program, state, ops=self.ops, g=cap
            )
            dcall.wait(outs, pres)
            didx = jnp.asarray(dirty.astype(np.int32))
            k = len(self.ops)
            dirty_dev = [jnp.take(a, didx) for a in (*outs, *pres)]
            # one crossing, in the device's own widths: the host copies
            # widen to float64 below, which would double the reported
            # transfer bytes in the platform-float32 device mode
            host = dcall.read(readback.read_outputs, dirty_dev[0], 0,
                              dirty_dev[1:])
            per_agg = {j: (host[j].astype(np.float64),
                           host[k + j].astype(bool))
                       for j in range(k)}
        return dirty, per_agg

    # ---- demotion ------------------------------------------------------
    def export_host_accs(self):
        """Read back every group as host-accumulator tuples (the
        manager._accumulate format), so a flow can demote to the host
        path without losing accumulated state."""
        g = self.num_groups
        if g == 0 or not self.state:
            return [], np.zeros(0, bool)
        hs = [np.asarray(s) for s in self.state]
        rows = []
        for gid in range(g):
            accs = []
            si = 0
            for op in self.ops:
                if op == "count":
                    accs.append(int(hs[si][gid]))
                    si += 1
                elif op == "sum":
                    n = int(hs[si + 2][gid])
                    s = float(hs[si][gid]) + float(hs[si + 1][gid])
                    accs.append(s if n else None)
                    si += 3
                elif op == "mean":
                    n = int(hs[si + 2][gid])
                    s = float(hs[si][gid]) + float(hs[si + 1][gid])
                    accs.append((s, n) if n else None)
                    si += 3
                elif op in ("min", "max"):
                    n = int(hs[si + 1][gid])
                    accs.append(float(hs[si][gid]) if n else None)
                    si += 2
                elif op in ("var_pop", "var_samp", "stddev_pop",
                            "stddev_samp"):
                    n = int(hs[si + 5][gid])
                    k = float(hs[si][gid])
                    sk = float(hs[si + 1][gid]) + float(hs[si + 2][gid])
                    s2k = float(hs[si + 3][gid]) + float(hs[si + 4][gid])
                    # unshift to the host (raw s, s2) acc form in f64;
                    # precision is bounded by the f32 slots, fine for the
                    # rare demotion path
                    s = sk + n * k
                    s2 = s2k + 2 * k * sk + n * k * k
                    accs.append((s, s2, n) if n else None)
                    si += 6
                else:  # first_value / last_value
                    hi = int(hs[si + 1][gid])
                    lo = int(hs[si + 2][gid])
                    empty = hi == (-1 if op == "last_value" else int(_IMAX))
                    accs.append(
                        None if empty
                        else (float(hs[si][gid]), (hi << 20) | lo)
                    )
                    si += 3
            rows.append(accs)
        return rows, self.dirty[:g].copy()

    # ---- expiry --------------------------------------------------------
    def expire_older_than(self, horizon: float) -> bool:
        """Vectorized EXPIRE AFTER: drop groups whose time key is older
        than horizon. O(1) when nothing can expire. Groups still dirty
        (updated since the last emit) survive one more tick so their
        final state reaches the sink first."""
        if self._tk_min >= horizon or not self.num_groups:
            return False
        g = self.num_groups
        tk = np.asarray(self._tk_vals, np.float64)
        self.expire((tk >= horizon) | self.dirty[:g])
        return True

    def expire(self, keep_mask: np.ndarray):
        """Compact to the surviving groups (keep_mask over gids)."""
        g = self.num_groups
        keep = np.nonzero(keep_mask[:g])[0]
        if len(keep) == g:
            return
        rows = [self._key_rows[i] for i in keep]
        old_dirty = (self.dirty[keep] if len(self.dirty)
                     else np.zeros(0, bool))
        self._key_rows = rows
        self._key_index = {k: i for i, k in enumerate(rows)}
        self._tk_vals = [self._tk_vals[i] for i in keep]
        self._tk_min = min(self._tk_vals, default=_NEVER_EXPIRES)
        if not self.state:
            return
        idx = jnp.asarray(keep.astype(np.int32))
        gathered = tuple(jnp.take(s, idx) for s in self.state)
        cap = 1024
        while cap < len(rows):
            cap *= 2
        self.state = tuple(
            self._identity_array(kind, ident, cap)
            .at[: len(rows)].set(gv)
            for (kind, ident), gv in zip(self._slot_specs(), gathered)
        )
        nd = np.zeros(cap, bool)
        nd[: len(rows)] = old_dirty
        self.dirty = nd
        self.capacity = cap


def _scalar(v):
    return v.item() if isinstance(v, np.generic) else v


def _key_strings(c) -> np.ndarray:
    """Injective string form of a key column for interning. Numeric
    arrays stringify directly (homogeneous, so str() is injective);
    object arrays tag non-string values so NULL and the literal string
    "None" (etc.) stay distinct groups, matching the host path."""
    arr = np.asarray(c, object) if not isinstance(c, np.ndarray) else c
    if arr.dtype != object:
        return arr.astype(str)
    return np.asarray(
        [v if type(v) is str else f"\x00{type(v).__name__}:{v}"
         for v in arr],
        object,
    )
