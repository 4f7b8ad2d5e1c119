"""Device program profiler: the XLA cost registry behind every
``device_call``.

The north star is "as fast as the hardware allows", but tracing (PR 8),
memory (PR 10) and statement statistics (PR 13) all attribute per-query
— the compiled XLA programs that actually burn the device time stayed
anonymous. This module is the process-wide registry every program
dispatched through ``telemetry/device_trace.device_call.run`` folds
into, one row per compiled program (site + static program key):

- per-call stats: calls, compile_ms (wall time of the process's FIRST
  execution, which includes XLA compilation), cumulative execute_ms and
  p50/p99 from a bucketed histogram, upload/readback bytes, and the
  three legs of the crossing as `device_call` stamps them:
  dispatch_ms (the jit call's own return), wait_ms (the return of
  block_until_ready, counted from the dispatch's) and readback_ms (the
  device->host copy); execute_ms = dispatch_ms + wait_ms;
- XLA analysis (lazy, on first surface consult): ``Lowered.
  cost_analysis()`` flops + bytes accessed, and ``Compiled.
  memory_analysis()`` temp/output/argument bytes. Argument SHAPES are
  captured at first dispatch (jax.ShapeDtypeStruct — no device buffers
  pinned) so the analysis re-lowers the exact program without holding
  live data;
- roofline attribution: operational intensity I = flops / bytes
  accessed compared against the machine balance peak_flops / peak_bw
  classifies each program ``bound=compute|memory``; achieved GFLOP/s
  and HBM GB/s derive from the p50 execute time, and %-of-peak is the
  achieved fraction of the BOUNDING resource. Peaks come from the
  ``[profiling]`` knobs; on a TPU backend they default to v5e
  single-chip numbers, on CPU runs the registry reports achieved-only
  (no verdict) unless peaks are configured explicitly.

Surfaces: ``information_schema.device_programs``, ``/debug/prof/device``
(text + ?format=json, top-N by cumulative device time),
``gtpu_device_program_*`` pull-model metrics (published from the rows
at scrape time), roofline attrs on ``device.execute`` spans and EXPLAIN
ANALYZE, ``ADMIN reset_device_profiler()``, and a per-statement
``program_ids`` link from every statement_statistics row to the
programs it dispatched. Unlike the gtpu_stmt_* families (carried-base
monotone), ADMIN reset here resets the exported series too — the
3-surface agreement contract (information_schema == /debug/prof/device
== gtpu_device_program_*) is exact at every scrape, and Prometheus
consumers treat the drop as an ordinary counter reset.

On-demand trace capture (``/debug/prof/device/trace?seconds=``) wraps
``jax.profiler.start_trace``/``stop_trace`` and writes a TensorBoard/
perfetto-loadable trace under ``[profiling] trace_dir``.
"""

from __future__ import annotations

import hashlib
import itertools
import os
import time
from collections import OrderedDict

from greptimedb_tpu import concurrency
from greptimedb_tpu.telemetry import metrics
from greptimedb_tpu.telemetry.metrics import (
    global_registry,
    set_child_value as _set_value,
)

# ---------------------------------------------------------------------------
# configuration
# ---------------------------------------------------------------------------

# Single-chip roofline peaks, keyed by `jax.devices()[0].device_kind`:
# (bf16 MXU peak TFLOP/s, HBM GB/s). Source: Google Cloud TPU
# documentation, "TPU v5e" system architecture page (197 TFLOP/s bf16,
# 819 GB/s HBM per chip). Used when the backend is a TPU and the
# [profiling] knobs leave a peak at 0 (= auto). A TPU kind that is not
# in this table reports `unknown_device_kind` and achieved-only — never
# another chip's peaks; every non-TPU platform reports achieved-only
# unless both peaks are configured explicitly.
DEVICE_PEAKS: dict[str, tuple[float, float]] = {
    "TPU v5 lite": (197.0, 819.0),   # what a v5e chip reports
}


class ProfilingConfig:
    """`[profiling]` options (config.py DEFAULTS documents each)."""

    __slots__ = ("enable", "max_programs", "metric_programs",
                 "peak_tflops", "peak_hbm_gbps", "analysis",
                 "trace_dir")

    def __init__(self, *, enable: bool = True, max_programs: int = 256,
                 metric_programs: int = 128,
                 peak_tflops: float = 0.0, peak_hbm_gbps: float = 0.0,
                 analysis: bool = True, trace_dir: str = ""):
        self.enable = bool(enable)
        self.max_programs = max(1, int(max_programs))
        # /metrics label cap: prometheus series can never be evicted,
        # so real (site, program) labels are granted FIRST-COME (like
        # stmt_stats' metric_fingerprints); later programs export
        # under program="_other"
        self.metric_programs = max(0, int(metric_programs))
        self.peak_tflops = float(peak_tflops or 0.0)
        self.peak_hbm_gbps = float(peak_hbm_gbps or 0.0)
        self.analysis = bool(analysis)
        self.trace_dir = str(trace_dir or "")


# ---------------------------------------------------------------------------
# metrics — PULL-model like gtpu_stmt_*: families publish from the
# registry rows at scrape time via a MetricsRegistry collector, so the
# dispatch hot path never touches a prometheus child lock. Label
# cardinality is bounded by [profiling] max_programs (LRU rows collapse
# into a per-site "_other" row). ADMIN reset zeroes the exported
# series (an ordinary prometheus counter reset) so all three surfaces
# stay exactly equal.
# ---------------------------------------------------------------------------

_M_CALLS = global_registry.counter(
    "gtpu_device_program_calls_total",
    "device program dispatches per (site, program)",
    labels=("site", "program"),
)
_M_COMPILES = global_registry.counter(
    "gtpu_device_program_compiles_total",
    "first successful dispatches of a program row per site: the calls "
    "whose wall time is compile_ms",
    labels=("site",),
)
_M_EXEC = global_registry.counter(
    "gtpu_device_program_execute_ms_total",
    "cumulative steady-state execute ms per (site, program) "
    "(excludes the first call, whose wall time is compile_ms)",
    labels=("site", "program"),
)
_M_DISPATCH = global_registry.counter(
    "gtpu_device_program_dispatch_ms_total",
    "cumulative steady-state ms inside the jit call itself per (site, "
    "program): argument flattening, the host arguments' upload, the "
    "enqueue",
    labels=("site", "program"),
)
_M_WAIT = global_registry.counter(
    "gtpu_device_program_wait_ms_total",
    "cumulative steady-state ms from the jit call's return to the "
    "return of block_until_ready per (site, program); with "
    "dispatch_ms it sums to execute_ms",
    labels=("site", "program"),
)
_M_READBACK_MS = global_registry.counter(
    "gtpu_device_program_readback_ms_total",
    "cumulative steady-state ms of device->host result readback per "
    "(site, program)",
    labels=("site", "program"),
)
_M_UPLOAD = global_registry.counter(
    "gtpu_device_program_upload_bytes_total",
    "host->device bytes uploaded by dispatches of (site, program)",
    labels=("site", "program"),
)
_M_READBACK = global_registry.counter(
    "gtpu_device_program_readback_bytes_total",
    "device->host bytes read back by dispatches of (site, program)",
    labels=("site", "program"),
)
_M_COMPILE = global_registry.gauge(
    "gtpu_device_program_compile_ms",
    "wall time of the first execution (includes XLA compilation)",
    labels=("site", "program"),
)
_M_P50 = global_registry.gauge(
    "gtpu_device_program_execute_p50_ms",
    "p50 steady-state execute ms per (site, program)",
    labels=("site", "program"),
)
_M_P99 = global_registry.gauge(
    "gtpu_device_program_execute_p99_ms",
    "p99 steady-state execute ms per (site, program)",
    labels=("site", "program"),
)
_M_FLOPS = global_registry.gauge(
    "gtpu_device_program_flops",
    "per-call FLOPs from XLA cost_analysis (0 until analyzed)",
    labels=("site", "program"),
)
_M_BYTES = global_registry.gauge(
    "gtpu_device_program_bytes_accessed",
    "per-call HBM bytes accessed from XLA cost_analysis",
    labels=("site", "program"),
)
_M_GFLOPS = global_registry.gauge(
    "gtpu_device_program_achieved_gflops",
    "achieved GFLOP/s at the p50 execute time",
    labels=("site", "program"),
)
_M_GBPS = global_registry.gauge(
    "gtpu_device_program_achieved_hbm_gbps",
    "achieved HBM GB/s at the p50 execute time",
    labels=("site", "program"),
)
_M_PCT = global_registry.gauge(
    "gtpu_device_program_pct_of_peak",
    "achieved fraction of the roofline-bounding resource (percent; "
    "0 when peaks are unknown on this platform)",
    labels=("site", "program"),
)
_M_COUNT = global_registry.gauge(
    "gtpu_device_programs",
    "distinct program rows currently tracked by the profiler",
)

# the counter families published from the rows, each beside the key of
# the row's document it carries
_ROW_COUNTERS = (
    (_M_CALLS, "calls"),
    (_M_EXEC, "execute_ms_total"),
    (_M_DISPATCH, "dispatch_ms_total"),
    (_M_WAIT, "wait_ms_total"),
    (_M_READBACK_MS, "readback_ms_total"),
    (_M_UPLOAD, "upload_bytes"),
    (_M_READBACK, "readback_bytes"),
)

OTHER = "_other"

# execute-time histogram bounds (ms) for the per-row p50/p99; one
# OVERFLOW slot past the last bound like stmt_stats' buckets
_EXEC_BUCKETS_MS = (
    0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 25.0, 50.0, 100.0,
    250.0, 500.0, 1000.0, 2500.0, 5000.0, 10000.0, 30000.0, 60000.0,
)
_N_BUCKETS = len(_EXEC_BUCKETS_MS) + 1


def _observe(buckets: list[int], v_ms: float):
    metrics.observe_bucket(buckets, _EXEC_BUCKETS_MS, v_ms)


def _quantile(buckets: list[int], q: float) -> float:
    return metrics.bucket_quantile(buckets, _EXEC_BUCKETS_MS, q)


def backend_live() -> bool:
    """True once THIS process has created a jax backend — asked without
    creating one (jax.devices() / default_backend() / live_arrays()
    all would). A scrape or a health probe must never be what brings a
    backend up: on a one-chip host the chip belongs to the
    device-owning role process. The one place that touches the private
    xla_bridge module."""
    import sys

    if "jax" not in sys.modules:
        return False
    from jax._src import xla_bridge

    return xla_bridge.backends_are_initialized()


def _device_identity() -> tuple[str, str]:
    """(platform, device_kind) of the live jax backend, ("none", "")
    while this process has none."""
    if not backend_live():
        return "none", ""
    import jax

    dev = jax.devices()[0]
    return str(dev.platform), str(dev.device_kind)


def _prog_id(site: str, key) -> str:
    return hashlib.blake2b(
        repr((site, key)).encode(), digest_size=6
    ).hexdigest()


def _arg_spec(a):
    """Shape/dtype skeleton of one program argument: concrete arrays
    (device or host) reduce to jax.ShapeDtypeStruct so the captured
    spec pins no device memory; static values pass through unchanged."""
    shape = getattr(a, "shape", None)
    dtype = getattr(a, "dtype", None)
    if shape is not None and dtype is not None:
        import jax

        return jax.ShapeDtypeStruct(tuple(shape), dtype)
    return a


class _Program:
    """One compiled program's aggregate row."""

    __slots__ = (
        "site", "prog_id", "key_text", "calls", "compile_ms",
        "execute_ms_total", "dispatch_ms_total", "wait_ms_total",
        "readback_ms_total", "exec_buckets", "upload_bytes",
        "readback_bytes", "dispatch_only", "errors",
        "first_seen_ms", "last_seen_ms",
        "analysis", "analysis_error", "flops", "bytes_accessed",
        "temp_bytes", "output_bytes", "argument_bytes",
        "aot_compile_ms", "_spec", "_compile_done", "metric_prog",
    )

    def __init__(self, site: str, prog_id: str, key_text: str):
        self.site = site
        self.prog_id = prog_id
        self.key_text = key_text
        self.calls = 0
        self.compile_ms: float | None = None
        self.execute_ms_total = 0.0
        # the legs of the steady-state calls (those execute_ms_total
        # counts): dispatch + wait = execute
        self.dispatch_ms_total = 0.0
        self.wait_ms_total = 0.0
        self.readback_ms_total = 0.0
        self.exec_buckets = [0] * _N_BUCKETS
        self.upload_bytes = 0
        self.readback_bytes = 0
        # True when at least one fold timed only the DISPATCH (the
        # caller did not block_until_ready — flow apply): achieved
        # rates would overstate, so they are suppressed for the row
        self.dispatch_only = False
        self.errors = 0
        self.first_seen_ms = int(time.time() * 1000)
        self.last_seen_ms = self.first_seen_ms
        self.analysis = "pending"      # pending | ok | failed | off
        self.analysis_error = ""
        self.flops = 0.0
        self.bytes_accessed = 0.0
        self.temp_bytes = 0
        self.output_bytes = 0
        self.argument_bytes = 0
        self.aot_compile_ms = 0.0
        self._spec = None              # (fn, arg specs, kw specs)
        # monotonic instant the compile call finished: dispatches that
        # STARTED before it blocked on the shared XLA compile and are
        # not steady-state samples
        self._compile_done: float | None = None
        # the /metrics label this row publishes under (its own id, or
        # "_other" past the metric_programs first-come cap) — decided
        # once at row creation
        self.metric_prog = prog_id

    # -- folding -------------------------------------------------------
    def fold_call(self, execute_ms: float | None, upload: int,
                  readback: int, *, dispatch_only: bool,
                  run_start: float | None = None,
                  dispatch_ms: float = 0.0, readback_ms: float = 0.0):
        self.calls += 1
        self.last_seen_ms = int(time.time() * 1000)
        self.upload_bytes += upload
        self.readback_bytes += readback
        if execute_ms is None:
            # failed dispatch: if it was the compile attempt,
            # compile_ms stays None and the NEXT successful call (which
            # pays the compile) records it
            self.errors += 1
            return
        if self.compile_ms is None:
            # the first SUCCESSFUL execution's wall time is dominated
            # by XLA compilation (or the persistent-cache load); keep
            # it out of the steady-state percentiles
            self.compile_ms = execute_ms
            self._compile_done = time.monotonic()
            return
        if (run_start is not None and self._compile_done is not None
                and run_start < self._compile_done):
            # concurrent cold dispatch: it blocked on the creator's
            # shared XLA compile, so its wall time would poison the
            # steady-state percentiles (calls/bytes still counted)
            return
        if dispatch_only:
            self.dispatch_only = True
        self.execute_ms_total += execute_ms
        self.dispatch_ms_total += dispatch_ms
        self.wait_ms_total += execute_ms - dispatch_ms
        self.readback_ms_total += readback_ms
        _observe(self.exec_buckets, execute_ms)

    def fold_row(self, other: "_Program"):
        """Merge an LRU-evicted row into this (_other) one."""
        self.calls += other.calls
        self.errors += other.errors
        self.execute_ms_total += other.execute_ms_total
        self.dispatch_ms_total += other.dispatch_ms_total
        self.wait_ms_total += other.wait_ms_total
        self.readback_ms_total += other.readback_ms_total
        for i in range(_N_BUCKETS):
            self.exec_buckets[i] += other.exec_buckets[i]
        self.upload_bytes += other.upload_bytes
        self.readback_bytes += other.readback_bytes
        self.dispatch_only = self.dispatch_only or other.dispatch_only
        if other.compile_ms:
            self.compile_ms = (self.compile_ms or 0.0) + other.compile_ms
        self.first_seen_ms = min(self.first_seen_ms, other.first_seen_ms)
        self.last_seen_ms = max(self.last_seen_ms, other.last_seen_ms)

    # -- derived -------------------------------------------------------
    def exec_p50_ms(self) -> float:
        return _quantile(self.exec_buckets, 0.50)

    def exec_p99_ms(self) -> float:
        return _quantile(self.exec_buckets, 0.99)

    def device_ms(self) -> float:
        return (self.compile_ms or 0.0) + self.execute_ms_total

    def achieved(self) -> tuple[float, float]:
        """(GFLOP/s, HBM GB/s) at the p50 execute time; (0, 0) until
        the program is analyzed, has steady-state samples, and its
        timing covers the completed computation (not dispatch-only)."""
        p50 = self.exec_p50_ms()
        if (self.analysis != "ok" or p50 <= 0.0 or self.dispatch_only
                or sum(self.exec_buckets) == 0):
            return 0.0, 0.0
        s = p50 / 1000.0
        return self.flops / s / 1e9, self.bytes_accessed / s / 1e9

    def roofline(self, peak_tflops: float, peak_hbm_gbps: float
                 ) -> tuple[str, float]:
        """(bound, pct_of_peak). bound classifies by operational
        intensity vs the machine balance (static — no timing needed);
        pct is achieved/peak for the bounding resource, 0.0 when
        unmeasurable. ("", 0.0) when unanalyzed or peaks unknown."""
        if (self.analysis != "ok" or peak_tflops <= 0
                or peak_hbm_gbps <= 0 or self.bytes_accessed <= 0):
            return "", 0.0
        intensity = self.flops / self.bytes_accessed  # FLOP / byte
        balance = (peak_tflops * 1e12) / (peak_hbm_gbps * 1e9)
        bound = "compute" if intensity >= balance else "memory"
        gflops, gbps = self.achieved()
        if bound == "compute":
            pct = gflops / (peak_tflops * 1e3) * 100.0
        else:
            pct = gbps / peak_hbm_gbps * 100.0
        return bound, pct

    def to_doc(self, peak_tflops: float, peak_hbm_gbps: float) -> dict:
        gflops, gbps = self.achieved()
        bound, pct = self.roofline(peak_tflops, peak_hbm_gbps)
        return {
            "site": self.site,
            "program": self.prog_id,
            "key": self.key_text,
            "calls": self.calls,
            "errors": self.errors,
            "compile_ms": round(self.compile_ms or 0.0, 3),
            "execute_ms_total": round(self.execute_ms_total, 3),
            "dispatch_ms_total": round(self.dispatch_ms_total, 3),
            "wait_ms_total": round(self.wait_ms_total, 3),
            "readback_ms_total": round(self.readback_ms_total, 3),
            "execute_p50_ms": round(self.exec_p50_ms(), 3),
            "execute_p99_ms": round(self.exec_p99_ms(), 3),
            "device_ms_total": round(self.device_ms(), 3),
            "upload_bytes": int(self.upload_bytes),
            "readback_bytes": int(self.readback_bytes),
            "dispatch_only": self.dispatch_only,
            "analysis": self.analysis,
            "analysis_error": self.analysis_error,
            "flops": self.flops,
            "bytes_accessed": self.bytes_accessed,
            "temp_bytes": int(self.temp_bytes),
            "output_bytes": int(self.output_bytes),
            "argument_bytes": int(self.argument_bytes),
            "aot_compile_ms": round(self.aot_compile_ms, 3),
            "achieved_gflops": round(gflops, 3),
            "achieved_hbm_gbps": round(gbps, 3),
            "bound": bound,
            "pct_of_peak": round(pct, 3),
            "first_seen_ms": self.first_seen_ms,
            "last_seen_ms": self.last_seen_ms,
        }


class DeviceProgramRegistry:
    """Process-wide registry; one per process (``global_programs``)."""

    def __init__(self, config: ProfilingConfig | None = None):
        self.config = config or ProfilingConfig()
        self._lock = concurrency.Lock()
        self._rows: OrderedDict[tuple, _Program] = OrderedDict()
        # serializes the lazy AOT analysis passes (lower + compile can
        # take seconds for a big fused program; two surfaces consulting
        # at once must not both pay it)
        self._analysis_lock = concurrency.Lock()
        # serializes whole publish passes (snapshot + child writes):
        # two concurrent scrapes interleaving their writes could
        # expose a STALE aggregate after a newer one — a counter
        # decrease to Prometheus (same contract as stmt_stats'
        # publish lock)
        self._publish_lock = concurrency.Lock()
        # labels this process has published, so a scrape after ADMIN
        # reset (or LRU collapse) zeroes vanished series instead of
        # leaving them frozen at stale values
        self._published: set[tuple[str, str]] = set()
        # program ids granted a real /metrics label (first-come,
        # bounded by metric_programs — exported series can never be
        # evicted, so churn past the cap exports as "_other")
        self._metric_progs: set[str] = set()
        self.evicted_rows = 0

    # -- dispatch-side hot path ---------------------------------------
    def prepare(self, site: str, key, fn, args, kwargs
                ) -> tuple[_Program, bool] | None:
        """Called by device_call.run just before the dispatch. Returns
        (row, is_first_dispatch) or None when disabled. On the first
        dispatch of a program the argument shape/dtype specs are
        captured (no device buffers pinned) for the lazy analysis."""
        if not self.config.enable:
            return None
        if key is None:
            # keyless dispatch: the callable IS the identity (process-
            # local, like the jit cache itself)
            key = repr(fn)
        try:
            hkey = (site, key)
            hash(hkey)
        except TypeError:
            key = repr(key)
            hkey = (site, key)
        with self._lock:
            row = self._rows.get(hkey)
            if row is not None:
                self._rows.move_to_end(hkey)
                return row, False
            # make room INCLUDING the row about to be inserted; a
            # collapse that merely CREATED a db's _other row has not
            # shrunk anything yet, so keep collapsing until the bound
            # holds or only _other rows remain
            while len(self._rows) >= self.config.max_programs:
                if not self._collapse_lru_locked():
                    break  # only _other rows remain
            key_text = repr(key)
            if len(key_text) > 160:
                key_text = key_text[:157] + "..."
            row = _Program(site, _prog_id(site, key), key_text)
            row.metric_prog = self._metric_prog_locked(row.prog_id)
            self._rows[hkey] = row
        if self.config.analysis:
            try:
                import jax

                specs = jax.tree_util.tree_map(_arg_spec, (args, kwargs))
                row._spec = (fn, specs[0], specs[1])
            except Exception:  # noqa: BLE001 - spec capture is
                # best-effort; the row still folds per-call stats
                row.analysis = "failed"
                row.analysis_error = "argument spec capture failed"
        else:
            row.analysis = "off"
        return row, True

    def lookup(self, site: str, key) -> _Program | None:
        """Read-only row lookup for ATTRIBUTION on no-dispatch paths
        (session hits keep their device.execute span and EXPLAIN
        ANALYZE notes, but do not count a call). Never creates a row."""
        if not self.config.enable:
            return None
        if key is None:
            return None
        try:
            hkey = (site, key)
            hash(hkey)
        except TypeError:
            hkey = (site, repr(key))
        with self._lock:
            row = self._rows.get(hkey)
            if row is not None:
                # a session-served program is HOT: refresh its LRU
                # recency so the steady-state rows are the last to
                # collapse into _other, not the first
                self._rows.move_to_end(hkey)
            return row

    def finish(self, row: _Program, *,
               execute_ms: float | None, upload: int, readback: int,
               dispatch_only: bool = False,
               run_start: float | None = None,
               dispatch_ms: float = 0.0, readback_ms: float = 0.0):
        with self._lock:
            cold = row.compile_ms is None
            row.fold_call(execute_ms, upload, readback,
                          dispatch_only=dispatch_only,
                          run_start=run_start,
                          dispatch_ms=dispatch_ms,
                          readback_ms=readback_ms)
            compiled = cold and row.compile_ms is not None
        if compiled:
            # rare (once a program row): the one push-model family here
            _M_COMPILES.labels(row.site).inc()

    def _metric_prog_locked(self, prog_id: str) -> str:
        if prog_id in self._metric_progs:
            return prog_id
        if len(self._metric_progs) < self.config.metric_programs:
            self._metric_progs.add(prog_id)
            return prog_id
        return OTHER

    def _collapse_lru_locked(self) -> bool:
        """Merge the least-recently-dispatched row into its site's
        _other row. Returns False when only _other rows remain."""
        for hkey in self._rows:
            if self._rows[hkey].prog_id != OTHER:
                victim = self._rows.pop(hkey)
                break
        else:
            return False
        okey = (victim.site, OTHER)
        other = self._rows.get(okey)
        if other is None:
            other = _Program(victim.site, OTHER, OTHER)
            other.analysis = "off"
            other.metric_prog = OTHER
            self._rows[okey] = other
        else:
            self._rows.move_to_end(okey)
        other.fold_row(victim)
        self.evicted_rows += 1
        return True

    # -- peaks ---------------------------------------------------------
    def peaks(self) -> tuple[float, float, str, str]:
        """(peak_tflops, peak_hbm_gbps, platform, source). Peaks are 0
        when unknown (achieved-only reporting)."""
        pf = self.config.peak_tflops
        pb = self.config.peak_hbm_gbps
        plat, kind = _device_identity()
        if pf > 0 and pb > 0:
            return pf, pb, plat, "configured"
        if plat == "tpu":
            known = DEVICE_PEAKS.get(kind)
            if known is None:
                return 0.0, 0.0, plat, "unknown_device_kind"
            return (pf if pf > 0 else known[0],
                    pb if pb > 0 else known[1],
                    plat, f"device_kind:{kind}")
        return 0.0, 0.0, plat, "achieved_only"

    # -- lazy XLA analysis ---------------------------------------------
    def analyze_pending(self):
        """Run the XLA cost/memory analysis for every row that still
        carries its captured spec. Triggered by the consulting surfaces
        (information_schema / /debug/prof/device / snapshot), NEVER by
        the /metrics publisher — a plain scrape must not pay an AOT
        compile. One pass per program per process; artifacts are
        dropped as soon as the numbers are extracted."""
        if not self.config.analysis:
            return
        with self._lock:
            pending = [r for r in self._rows.values()
                       if r.analysis == "pending" and r._spec is not None]
        if not pending:
            return
        # contract: the analysis lock serializes whole AOT passes
        # (lower + XLA compile, potentially seconds); it is never taken
        # on the dispatch hot path and never nests another lock
        with self._analysis_lock:  # gtlint: disable=GTS103
            for row in pending:
                if row.analysis == "pending":
                    self._analyze_row(row)

    def _analyze_row(self, row: _Program):
        fn, arg_specs, kw_specs = row._spec
        try:
            lowered = fn.lower(*arg_specs, **kw_specs)
            cost = lowered.cost_analysis()
            if isinstance(cost, (list, tuple)):
                cost = cost[0] if cost else {}
            cost = cost or {}
            row.flops = float(cost.get("flops", 0.0) or 0.0)
            row.bytes_accessed = float(
                cost.get("bytes accessed", 0.0) or 0.0
            )
            t0 = time.perf_counter()
            compiled = lowered.compile()
            row.aot_compile_ms = (time.perf_counter() - t0) * 1000.0
            mem = compiled.memory_analysis()
            if mem is not None:
                row.temp_bytes = int(
                    getattr(mem, "temp_size_in_bytes", 0) or 0
                )
                row.output_bytes = int(
                    getattr(mem, "output_size_in_bytes", 0) or 0
                )
                row.argument_bytes = int(
                    getattr(mem, "argument_size_in_bytes", 0) or 0
                )
        except Exception as e:  # noqa: BLE001 - analysis is additive:
            # a program that cannot re-lower still folds call stats
            row.analysis = "failed"
            row.analysis_error = f"{type(e).__name__}: {e}"[:200]
        else:
            row.analysis = "ok"
        finally:
            row._spec = None

    # -- surfaces ------------------------------------------------------
    def snapshot(self, *, top: int = 0, analyze: bool = True
                 ) -> list[dict]:
        """Row docs ordered by cumulative device time (compile +
        execute), top-N bounded when top > 0. Triggers the lazy XLA
        analysis unless analyze=False."""
        if analyze:
            self.analyze_pending()
        pf, pb, _plat, _src = self.peaks()
        with self._lock:
            docs = [r.to_doc(pf, pb) for r in self._rows.values()]
        docs.sort(key=lambda d: d["device_ms_total"], reverse=True)
        if top > 0:
            docs = docs[:top]
        return docs

    def report(self, *, top: int = 20) -> dict:
        pf, pb, plat, src = self.peaks()
        with self._lock:
            total = len(self._rows)
        return {
            "platform": plat,
            "peak_tflops": pf,
            "peak_hbm_gbps": pb,
            "peak_source": src,
            "programs_tracked": total,
            "evicted_rows": self.evicted_rows,
            "programs": self.snapshot(top=top),
        }

    def reset(self) -> int:
        """ADMIN reset_device_profiler(): drop every row. The exported
        gtpu_device_program_* series zero at the next scrape (a plain
        prometheus counter reset) so all three surfaces stay equal."""
        with self._lock:
            n = len(self._rows)
            self._rows.clear()
            self.evicted_rows = 0
        return n

    # -- scrape-time publisher ----------------------------------------
    def _publish_metrics(self):
        """MetricsRegistry collector: refresh every
        gtpu_device_program_* family from the rows. Does NOT trigger
        the AOT analysis (a scrape stays cheap); analysis-derived
        gauges publish once a consulting surface has computed them.
        The publish lock covers snapshot AND writes: publishes
        serialize, so each scrape exposes a consistent, never-older
        aggregate (and the _published bookkeeping can't race)."""
        with self._publish_lock:
            self._publish_locked()

    def _publish_locked(self):
        pf, pb, _plat, _src = self.peaks()
        with self._lock:
            rows = [(r.to_doc(pf, pb), r.metric_prog)
                    for r in self._rows.values()]
            n_rows = len(rows)
        # aggregate by the EXPORTED label: past the metric_programs
        # first-come cap, churned programs share the per-site "_other"
        # label (counters sum; the per-program gauges publish only for
        # labels backed by their own row) — the exported series set
        # stays bounded no matter how many program shapes a
        # long-running server mints
        agg: dict[tuple[str, str], dict] = {}
        for d, mp in rows:
            lab = (d["site"], mp)
            a = agg.get(lab)
            if a is None:
                a = agg[lab] = dict.fromkeys(
                    (key for _fam, key in _ROW_COUNTERS), 0)
                a["doc"] = None
            for _fam, key in _ROW_COUNTERS:
                a[key] += d[key]
            if mp == d["program"]:
                a["doc"] = d
        live: set[tuple[str, str]] = set()
        for lab, a in agg.items():
            live.add(lab)
            for fam, key in _ROW_COUNTERS:
                _set_value(fam.labels(*lab), a[key])
            d = a["doc"]
            if d is None:
                # an over-cap aggregate label: per-program gauges are
                # meaningless for a mixed bucket
                d = {"compile_ms": 0.0, "execute_p50_ms": 0.0,
                     "execute_p99_ms": 0.0, "flops": 0.0,
                     "bytes_accessed": 0.0, "achieved_gflops": 0.0,
                     "achieved_hbm_gbps": 0.0, "pct_of_peak": 0.0}
            _M_COMPILE.labels(*lab).set(d["compile_ms"])
            _M_P50.labels(*lab).set(d["execute_p50_ms"])
            _M_P99.labels(*lab).set(d["execute_p99_ms"])
            _M_FLOPS.labels(*lab).set(d["flops"])
            _M_BYTES.labels(*lab).set(d["bytes_accessed"])
            _M_GFLOPS.labels(*lab).set(d["achieved_gflops"])
            _M_GBPS.labels(*lab).set(d["achieved_hbm_gbps"])
            _M_PCT.labels(*lab).set(d["pct_of_peak"])
        for lab in self._published - live:
            # vanished rows (ADMIN reset / LRU collapse): zero, don't
            # freeze — the surfaces must agree at every scrape
            for fam, _key in _ROW_COUNTERS:
                _set_value(fam.labels(*lab), 0)
            for fam in (_M_COMPILE, _M_P50, _M_P99, _M_FLOPS, _M_BYTES,
                        _M_GFLOPS, _M_GBPS, _M_PCT):
                fam.labels(*lab).set(0.0)
        self._published = live
        _M_COUNT.set(n_rows)


def render_text(doc: dict) -> str:
    """Human face of /debug/prof/device: top-N by device time."""
    out = [
        f"device programs: {doc['programs_tracked']} tracked "
        f"({doc['evicted_rows']} collapsed), platform "
        f"{doc['platform']}",
    ]
    if doc["peak_tflops"] > 0:
        out.append(
            f"roofline peaks [{doc['peak_source']}]: "
            f"{doc['peak_tflops']:g} TFLOP/s, "
            f"{doc['peak_hbm_gbps']:g} GB/s HBM"
        )
    else:
        out.append("roofline peaks: unknown (achieved-only; set "
                   "[profiling] peak_tflops / peak_hbm_gbps)")
    hdr = (f"{'site':<16} {'program':<13} {'calls':>7} "
           f"{'compile':>9} {'p50ms':>9} {'p99ms':>9} {'GFLOP/s':>9} "
           f"{'GB/s':>8} {'%peak':>6} {'bound':<7}")
    out.append(hdr)
    out.append("-" * len(hdr))
    for d in doc["programs"]:
        pct = f"{d['pct_of_peak']:.1f}" if d["bound"] else "-"
        bound = d["bound"] or ("dispatch" if d["dispatch_only"]
                               else d["analysis"])
        out.append(
            f"{d['site']:<16.16} {d['program']:<13.13} "
            f"{d['calls']:>7} {d['compile_ms']:>9.1f} "
            f"{d['execute_p50_ms']:>9.3f} {d['execute_p99_ms']:>9.3f} "
            f"{d['achieved_gflops']:>9.2f} "
            f"{d['achieved_hbm_gbps']:>8.2f} {pct:>6} {bound:<7}"
        )
    return "\n".join(out) + "\n"


# ---------------------------------------------------------------------------
# on-demand trace capture (jax.profiler)
# ---------------------------------------------------------------------------


class CaptureBusyError(RuntimeError):
    """A trace capture is already in progress in this process."""


_M_CAPTURE = global_registry.counter(
    "gtpu_device_trace_capture_seconds_total",
    "wall time of device trace captures by phase: start "
    "(jax.profiler.start_trace), hold (the window asked for), stop "
    "(stop_trace: the profile collected and written)",
    labels=("phase",),
)
for _phase in ("start", "hold", "stop"):
    _M_CAPTURE.labels(_phase)  # exported at 0 from start-up

_capture_seq = itertools.count(1)
_capture_lock = concurrency.Lock()
_capture_active = False


def capture_trace(seconds: float, out_dir: str | None = None) -> dict:
    """Capture `seconds` of device activity via jax.profiler into a
    TensorBoard/perfetto-loadable trace directory. One capture at a
    time per process (CaptureBusyError otherwise). The document says
    what the capture itself took: `start_s` (start_trace), `hold_s`
    (the window) and `stop_s` (stop_trace), also exported as
    `gtpu_device_trace_capture_seconds_total{phase}`. The profiler
    runs with its defaults, Python tracer included."""
    global _capture_active

    seconds = float(seconds)
    if not (0.0 < seconds <= 60.0):
        raise ValueError("seconds must be in (0, 60]")
    import tempfile

    base = (out_dir or global_programs.config.trace_dir
            or os.path.join(tempfile.gettempdir(), "gtpu_device_traces"))
    with _capture_lock:
        if _capture_active:
            raise CaptureBusyError("a trace capture is already running")
        _capture_active = True
    try:
        # dir creation AFTER the busy check: a 409'd caller must not
        # litter trace_dir with empty capture directories
        path = os.path.join(
            base, f"capture_{os.getpid()}_{next(_capture_seq)}"
        )
        os.makedirs(path, exist_ok=True)
        import jax

        from greptimedb_tpu.telemetry import tracing

        t0 = time.monotonic()
        jax.profiler.start_trace(path)
        try:
            # spans, device calls' legs, background ticks and
            # collections now also open `gtpu:<name>` events on their
            # threads' lines: the host's stages lie on the profiler's
            # clock beside the device's. On before the hold's clock
            # starts, so the annotations cover the whole window.
            tracing.set_annotating(True)
            t1 = time.monotonic()
            time.sleep(seconds)
        finally:
            t2 = time.monotonic()
            tracing.set_annotating(False)
            jax.profiler.stop_trace()
        t3 = time.monotonic()
    finally:
        with _capture_lock:
            _capture_active = False
    legs = {"start": t1 - t0, "hold": t2 - t1, "stop": t3 - t2}
    for phase, took in legs.items():
        _M_CAPTURE.labels(phase).inc(took)
    files = []
    for root, _dirs, names in os.walk(path):
        for name in names:
            files.append(os.path.relpath(os.path.join(root, name), path))
    return {
        "trace_dir": path,
        "seconds": seconds,
        "start_s": round(legs["start"], 6),
        "hold_s": round(legs["hold"], 6),
        "stop_s": round(legs["stop"], 6),
        "files": sorted(files),
    }


# ---------------------------------------------------------------------------
# process-wide instance + wiring
# ---------------------------------------------------------------------------

global_programs = DeviceProgramRegistry()
# scrape-time publisher: /metrics (and runtime_metrics, and the
# self-export loop) refresh the gtpu_device_program_* families from the
# registry rows on every render — zero prometheus work at dispatch
global_registry.register_collector(global_programs._publish_metrics)


def configure(options: dict | None) -> ProfilingConfig:
    """Apply the `[profiling]` TOML section to this process."""
    o = options or {}
    cfg = ProfilingConfig(
        enable=o.get("enable", True),
        max_programs=o.get("max_programs", 256),
        metric_programs=o.get("metric_programs", 128),
        peak_tflops=o.get("peak_tflops", 0.0),
        peak_hbm_gbps=o.get("peak_hbm_gbps", 0.0),
        analysis=o.get("analysis", True),
        trace_dir=o.get("trace_dir", ""),
    )
    with global_programs._lock:
        global_programs.config = cfg
        # the label grant set re-derives under the new cap (already-
        # exported series keep counting regardless)
        global_programs._metric_progs.clear()
    return cfg


def enabled() -> bool:
    return global_programs.config.enable
