"""Device-time attribution for traces + the program-profiler boundary.

A device query's wall time is more than its kernels, and without
this module no single query could SHOW which part it paid: XLA
compilation (first call for a program
shape), device execution (dispatch + block_until_ready), or host<->
device transfer (uploads of masks/grids, result readback). This module
wraps the jit/shard_map CALL BOUNDARY in query/device_range.py,
query/reduce.py, promql/fast.py, storage/device_merge.py and
flow/device_state.py — always from HOST scope, never inside a traced
function (gtlint GT014 flags a span or metric call inside device scope:
it is a host-sync/recompile hazard; GT018 flags a jit-produced callable
invoked OUTSIDE a device_call scope: an untracked dispatch).

Each wrapped call produces one `device.execute` span carrying:
- site: which kernel family ran (range / groupby / promql / topk / ...)
- compile: "first_call" (this process had not executed this static
  program shape before — the duration includes XLA compilation) or
  "cache_hit" (steady state)
- dispatch_ms / wait_ms / readback_ms: the three legs of the crossing,
  each stamped where it happens on one monotonic clock — the jit
  call's own return (argument flattening, the host arguments' upload,
  the enqueue), the return of block_until_ready, and the device->host
  copy through query/readback.py
- execute_ms: dispatch_ms + wait_ms, the time to completion of the
  device computation counted from the dispatch (a session hit
  dispatches nothing and reads 0)
- upload_bytes / readback_bytes: host->device and device->host traffic
  attributable to this call
- readback_started: "dispatch" where the call started the copy of the
  arrays it read right after the dispatch (`start`), so the readback leg
  holds only what the copy had left; "read" where the read opened it
- program: the program-registry id (telemetry/device_programs.py);
  the roofline numbers live on the registry row and in EXPLAIN
  ANALYZE's `roofline_<site>` note, not on the span

Dispatching THROUGH `device_call.run(fn, *args, **kw)` additionally
folds the call into the process-wide device-program registry
(telemetry/device_programs.py): calls, compile time, the three legs,
transfer bytes, and the argument shape specs the lazy XLA cost analysis
lowers against. A session hit that skips the dispatch keeps its span
but does NOT count as a program call — the registry describes real
dispatches.

While a device trace capture runs each leg also opens (`tracing.event`)
`gtpu:device.dispatch`, `gtpu:device.wait` or `gtpu:device.readback`
(with `site`) on the profiler's clock, inside
the span's `gtpu:device.execute`: beside the `XLA Modules` line one
capture shows a call's launch lag (the start of the dispatch to the
module's start) and its wake lag (the module's end to the end of the
wait). Off a capture that is one boolean test a leg.
"""

from __future__ import annotations

import time

from greptimedb_tpu import concurrency
from greptimedb_tpu.telemetry import stmt_stats, tracing
from greptimedb_tpu.telemetry.metrics import global_registry

# (site, static program key) shapes this process has already executed:
# membership decides first_call vs cache_hit attribution. Bounded the
# same way the jit caches are in practice (program shapes are few).
_SEEN_MAX = 4096
_seen: set = set()
_seen_lock = concurrency.Lock()


def note_compile(site: str, key) -> str:
    """Record one execution of (site, key); returns the compile
    attribution for THIS call."""
    k = (site, key)
    with _seen_lock:
        if k in _seen:
            return "cache_hit"
        if len(_seen) >= _SEEN_MAX:
            _seen.clear()  # rare; worst case a few re-labelled firsts
        _seen.add(k)
        return "first_call"


# every device->host crossing of a call, by where its copy was started:
# `dispatch` (the call's `start` queued it behind the program) or `read`
_CROSSINGS = global_registry.counter(
    "gtpu_readback_crossings_total",
    "device->host result crossings by site and by where the copy was "
    "started (dispatch: queued behind the program right after it was "
    "dispatched; read: opened by the read itself)",
    labels=("site", "started"),
)
for _site in ("range", "groupby", "promql", "promql_histogram",
              "promql_binary", "topk", "compact_merge", "flow_finalize"):
    for _started in ("dispatch", "read"):
        # the sites that read their results back: 0 from start-up
        _CROSSINGS.labels(_site, _started)


def _device_arrays(args):
    """The device arrays among a readback's arguments (tuples opened)."""
    for a in args:
        if isinstance(a, tuple):
            yield from _device_arrays(a)
        elif hasattr(a, "copy_to_host_async"):
            yield a


def _host_nbytes(out) -> int:
    """Bytes of what a readback returned: one host array
    (`read_full`, `read_delta`) or a tuple of them (`read_outputs`)."""
    if isinstance(out, tuple):
        return sum(int(x.nbytes) for x in out)
    return int(out.nbytes)


class device_call:
    """`with device_trace.device_call("range", key=spec) as d:` — wraps
    one jit/shard_map invocation and stamps the three legs of its
    crossing where they happen:

    - `out = d.run(fn, *args, **kw)` dispatches the program (it
      registers with the device-program profiler) and stamps the jit
      call's return: the dispatch;
    - `d.start(*arrs)` right after `run` starts the device->host copy
      of exactly the arrays the call will read, queued behind the
      program (query/readback.start);
    - `d.wait(out)` blocks until the outputs are ready and stamps that
      return: the wait (`d.wait(dispatch_only=True)` where the caller
      deliberately does not block — the grid's upkeep, the flow
      applies — so the timing covers the dispatch, not the computation);
    - `host = d.read(readback.read_full, out)` runs one crossing of
      query/readback.py, stamps its return and counts the bytes it
      brought back: the readback;
    - `d.transfer(nbytes, "upload")` for the host arguments' bytes.

    A session hit calls none of the first three: its legs read 0, and
    its read counts under started="read"."""

    __slots__ = ("_cm", "_span", "site", "_stmt", "key",
                 "_rec", "_first", "_run_t0", "_disp_t1", "_disp_ms",
                 "_wait_ms", "_rb_ms", "_exec_ms", "_up", "_rb",
                 "_dispatch_only", "_started")

    def __init__(self, site: str, *, key=None, **attrs):
        self.site = site
        self.key = key
        self._rec = None
        self._first = False
        self._run_t0 = 0.0
        self._disp_t1 = 0.0
        self._disp_ms = 0.0
        self._wait_ms = 0.0
        self._rb_ms = 0.0
        self._exec_ms = None
        self._up = 0
        self._rb = 0
        self._dispatch_only = False
        self._started = ()
        # skip the compile-memo lookup entirely when NEITHER a trace
        # nor a statement observation is active: the memo only feeds
        # attribution, and the bare hot path must stay zero-cost
        self._stmt = stmt_stats.active() is not None
        traced = tracing.enabled() and tracing.current_span() is not None
        if traced or self._stmt:
            comp = note_compile(site, key)
            if self._stmt:
                # per-statement compile-vs-program-cache attribution:
                # a repeatedly polled fingerprint shows compile=1 /
                # cache_hit=N-1 in statement_statistics
                stmt_stats.add("compile_first" if comp == "first_call"
                               else "compile_cache_hit")
        if traced:
            self._cm = tracing.child_span(
                "device.execute", site=site, compile=comp, **attrs,
            )
        else:
            self._cm = tracing.child_span("device.execute")
        self._span = None

    def __enter__(self) -> "device_call":
        self._span = self._cm.__enter__()
        return self

    def run(self, fn, *args, **kw):
        """Dispatch the program. Registers (site, key) with the
        device-program registry — first dispatch captures the argument
        shape specs for the lazy XLA cost analysis — and anchors the
        one clock of the legs at the dispatch, so session lookups
        before it never count as device time. The jit call's return
        closes the dispatch leg."""
        from greptimedb_tpu.telemetry import device_programs

        reg = device_programs.global_programs
        if reg.config.enable:
            prep = reg.prepare(self.site, self.key, fn, args, kw)
            if prep is not None:
                self._rec, self._first = prep
        with tracing.event("device.dispatch", site=self.site):
            self._run_t0 = time.monotonic()
            out = fn(*args, **kw)
            self._disp_t1 = time.monotonic()
        self._disp_ms = (self._disp_t1 - self._run_t0) * 1000.0
        return out

    def start(self, *arrs):
        """Start the device->host copy of `arrs`, the arrays this call's
        `read` will bring back, right after `run`: the copy is queued
        behind the program, and the read finds the bytes on their way
        instead of opening a round trip of its own. A crossing of these
        very arrays counts under started="dispatch"."""
        from greptimedb_tpu.query import readback

        readback.start(*arrs)
        self._started += tuple(map(id, arrs))

    def wait(self, *outputs, dispatch_only: bool = False):
        """Block until `outputs` (arrays, or trees of them) are ready
        and close the wait leg, counted from the dispatch's return: the
        device computation is complete and what is left of the span is
        readback. dispatch_only=True records that the caller does NOT
        block — the timing covers the dispatch, not the computation —
        so the profiler suppresses achieved-rate claims for this
        program."""
        if not dispatch_only:
            import jax

            with tracing.event("device.wait", site=self.site):
                jax.block_until_ready(outputs)
        now = time.monotonic()
        if not self._disp_t1:
            # no dispatch through run(): nothing to split
            self._disp_t1 = self._run_t0 = now
        self._wait_ms = (now - self._disp_t1) * 1000.0
        self._exec_ms = self._disp_ms + self._wait_ms
        self._dispatch_only = dispatch_only

    def read(self, fn, *args, **kw):
        """One device->host crossing: `fn` is a helper of
        query/readback.py (`read_outputs`, `read_full`, `read_delta`),
        called with `args`. Its return closes (one part of) the
        readback leg; the bytes of the host arrays it returned count as
        this call's readback; the crossing counts under started=
        "dispatch" where `start` began the copy of every array it read."""
        started = ("dispatch" if self._started and all(
            id(a) in self._started for a in _device_arrays(args))
            else "read")
        with tracing.event("device.readback", site=self.site):
            t0 = time.monotonic()
            out = fn(*args, **kw)
            self._rb_ms += (time.monotonic() - t0) * 1000.0
        self.transfer(_host_nbytes(out), "readback")
        _CROSSINGS.labels(self.site, started).inc()
        self._span.attributes["readback_started"] = started
        return out

    def transfer(self, nbytes: int, direction: str = "readback"):
        nbytes = int(nbytes)
        if direction == "upload":
            self._up += nbytes
        else:
            self._rb += nbytes
        key = f"{direction}_bytes"
        attrs = self._span.attributes
        attrs[key] = int(attrs.get(key, 0)) + nbytes
        if self._stmt and direction == "upload":
            # readback bytes are attributed (full vs delta) at the one
            # blessed crossing in query/readback.py; uploads only here
            stmt_stats.add("upload_bytes", int(nbytes))

    def annotate(self, **attrs):
        """Further attributes of this call's span, known only once its
        results are back (the range site's `trimmed_steps`)."""
        self._span.attributes.update(attrs)

    def _fold_program(self, sp, rec, *, dispatched: bool):
        """Fold the dispatch into the program registry (when one
        happened) + attach the program id to the span and the
        statement observation, and the program / roofline notes to
        EXPLAIN ANALYZE's stats. A no-dispatch path (session hit)
        attributes without folding — and without per-call achieved
        rates, since no compute ran."""
        from greptimedb_tpu.telemetry import device_programs

        reg = device_programs.global_programs
        if dispatched:
            reg.finish(rec, execute_ms=self._exec_ms,
                       dispatch_ms=self._disp_ms,
                       readback_ms=self._rb_ms,
                       upload=self._up, readback=self._rb,
                       dispatch_only=self._dispatch_only,
                       run_start=self._run_t0 or None)
        if self._stmt:
            # program-registry link: the statement_statistics row lists
            # the program ids its executions used (dispatched, or
            # served from the program's session buffer)
            stmt_stats.note_program(rec.prog_id)
        if sp is not None and sp.trace_id:
            sp.attributes["program"] = rec.prog_id
        from greptimedb_tpu.query import stats as qstats

        if qstats.active() is None:
            return
        # EXPLAIN ANALYZE only: the roofline note is computed for the
        # operator who asked, never for a span
        qstats.note(f"device_program_{self.site}", rec.prog_id)
        if rec.analysis != "ok":
            return
        pf, pb, _plat, _src = reg.peaks()
        bound, row_pct = rec.roofline(pf, pb)
        if not bound:
            return
        if not dispatched:
            # steady-state row numbers: this call served from the
            # session buffer, no program ran
            g, b = rec.achieved()
            qstats.note(
                f"roofline_{self.site}",
                f"{bound}-bound {row_pct:.1f}% of peak at "
                f"p50 ({g:.1f} GFLOP/s, {b:.1f} GB/s; served "
                "from the session buffer)",
            )
            return
        gflops = gbps = pct = 0.0
        if (self._exec_ms and self._exec_ms > 0
                and not self._dispatch_only and not self._first):
            s = self._exec_ms / 1000.0
            gflops = rec.flops / s / 1e9
            gbps = rec.bytes_accessed / s / 1e9
            if bound == "compute":
                pct = gflops / (pf * 1e3) * 100.0
            else:
                pct = gbps / pb * 100.0
        qstats.note(
            f"roofline_{self.site}",
            f"{bound}-bound {pct:.1f}% of peak "
            f"({gflops:.1f} GFLOP/s, {gbps:.1f} GB/s)",
        )

    def __exit__(self, exc_type, exc, tb):
        sp = self._span
        rec = self._rec
        dispatched = rec is not None
        if rec is None:
            # no dispatch happened (session hit): when someone is
            # watching (trace / statement stats / EXPLAIN ANALYZE),
            # attribute the program row read-only — the warm steady
            # state must not lose the program link
            watching = self._stmt or (sp is not None and sp.trace_id)
            if not watching:
                from greptimedb_tpu.query import stats as qstats

                watching = qstats.active() is not None
            if watching:
                from greptimedb_tpu.telemetry import device_programs

                rec = device_programs.global_programs.lookup(
                    self.site, self.key
                )
        if rec is not None:
            self._fold_program(sp, rec, dispatched=dispatched)
        if sp is not None and sp.trace_id:
            # the legs of this call, as the registry row folds them
            attrs = sp.attributes
            attrs["dispatch_ms"] = round(self._disp_ms, 3)
            attrs["wait_ms"] = round(self._wait_ms, 3)
            attrs["readback_ms"] = round(self._rb_ms, 3)
            attrs["execute_ms"] = round(self._disp_ms + self._wait_ms, 3)
            # per-query device-bytes attribution: the HBM pinned by the
            # registered device pools at the moment this call finished
            # (telemetry/memory.py ledger), so every device.* span on a
            # trace shows what the chip was holding when it ran
            from greptimedb_tpu.telemetry import memory as _memory

            acct = _memory.global_accountant
            if acct.enabled:
                # TTL-cached: a burst of traced device calls must not
                # take every pool's lock per span
                sp.attributes["device_pool_bytes"] = (
                    acct.device_bytes_cached()
                )
        return self._cm.__exit__(exc_type, exc, tb)
