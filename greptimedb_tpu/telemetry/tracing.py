"""Distributed-tracing spans with device-time attribution.

Capability counterpart of the reference's tracing stack
(/root/reference/src/common/telemetry/src/logging.rs:22-67 tracing
subscriber + OTLP export, src/common/telemetry/src/tracing_context.rs
W3C context propagation): timed spans carrying a trace id, parent links
via a context var (so nested spans form a tree across threads when the
context is passed), inbound `traceparent` parsing on every wire the
system speaks (HTTP header, Flight ticket field, DoPut app_metadata),
and an in-memory ring of finished traces served by the HTTP API
(/v1/traces) + `information_schema.traces` for inspection without an
external collector.

Cross-process stitching: a datanode executing a shipped partial plan
collects the spans it produced (`export_spans`) and ships them back in
the Arrow response metadata (`gtdb:spans`); the frontend ingests them
(`ingest_spans`) so ONE trace in its ring covers the whole distributed
query — frontend sched/plan/fan-out spans and per-datanode scan/device
spans under a shared trace_id.

Sampling is TAIL-BASED: every span records while in flight, and the
keep/drop decision happens when the process-local root span finishes —
error traces, slow traces (>= slow_ms) and explicitly marked traces
(`mark_keep`) are kept FOR CAUSE: against sampling, and in the ring
past the traces kept by chance (which are evicted first; see
`_TraceStore`); the rest keep with probability `sample_ratio`. The
slowest finished trace of each local-root name is held beside the ring
(`/v1/traces?slowest=1`). `[tracing]` TOML knobs: enable, sample_ratio,
capacity (trace ring size; 0 = unbounded: the ring then grows with
every kept trace), slow_ms.

Timestamps: `start_ms` is epoch milliseconds (display/correlation);
durations are computed on the MONOTONIC clock (an NTP slew must never
produce negative or absurd span durations — gtlint GT011).

Every finished span also exports its time BY NAME: wall time into the
histogram `gtpu_span_seconds{name}`, thread CPU time into the counter
`gtpu_span_cpu_seconds_total{name}` (folded into a per-name table on
the span's own thread, published at scrape time like the statement
statistics). Span names are code literals plus bounded f-strings
(`http <route>`, `sql.<kind>`, `dist.<stage>`, `recovery.<stage>`), so
the label set is bounded. Pauses no request owns are counted beside
them: `background_span` for periodic loops
(`gtpu_background_task_seconds{task}`; never in the ring unless slower
than `slow_ms`) and a `gc.callbacks` hook
(`gtpu_runtime_gc_pause_seconds{generation}`). While a device trace
capture runs, spans, ticks and collections also open a
`jax.profiler.TraceAnnotation("gtpu:<name>")`, so they lie in the
capture on the profiler's clock beside the device's operations.
"""

from __future__ import annotations

import bisect
import collections
import contextlib
import contextvars
import gc
import itertools
import random
import secrets

import time
from dataclasses import dataclass, field

from greptimedb_tpu import concurrency
from greptimedb_tpu.telemetry import metrics as _metrics

_current_span: contextvars.ContextVar["Span | None"] = (
    contextvars.ContextVar("gtpu_span", default=None)
)

# finished spans additionally append here when a collector is active
# (export_spans) — the cross-process export used by dist/merge.py and
# the EXPLAIN ANALYZE span-tree rendering
_collector: contextvars.ContextVar["list | None"] = (
    contextvars.ContextVar("gtpu_span_collector", default=None)
)

_MAX_TRACES = 256
_MAX_EXPORT_SPANS = 128


class TracingConfig:
    """`[tracing]` options (config.py DEFAULTS documents each knob)."""

    __slots__ = ("enabled", "sample_ratio", "capacity", "slow_ms")

    def __init__(self, *, enable: bool = True, sample_ratio: float = 1.0,
                 capacity: int = _MAX_TRACES,
                 slow_ms: float = 5000.0):
        self.enabled = bool(enable)
        self.sample_ratio = min(1.0, max(0.0, float(sample_ratio)))
        self.capacity = int(capacity)
        self.slow_ms = float(slow_ms)


_config = TracingConfig()


def configure(options: dict | None):
    """Apply the `[tracing]` TOML section to this process."""
    global _config
    o = options or {}
    _config = TracingConfig(
        enable=o.get("enable", True),
        sample_ratio=o.get("sample_ratio", 1.0),
        capacity=o.get("capacity", _MAX_TRACES),
        slow_ms=o.get("slow_ms", 5000.0),
    )
    global_traces.set_cap(_config.capacity)
    return _config


def enabled() -> bool:
    return _config.enabled


def ring_unbounded() -> bool:
    """True when the trace ring has no capacity bound (capacity <= 0):
    a misconfiguration, since memory then grows with every kept trace."""
    return global_traces.cap <= 0


# ---------------------------------------------------------------------------
# time by name — PULL-model like the statement statistics: a finished
# span folds (count, wall, thread CPU, one bucket) into a per-name row
# under one short lock; the gtpu_span_* / gtpu_background_task_* /
# gtpu_runtime_gc_pause_* families are refreshed from the rows at
# scrape time, so no span touches a prometheus child lock.
# ---------------------------------------------------------------------------

# the statement-latency bounds (stmt_stats._BUCKETS_MS), in seconds
_BOUNDS_S = (
    0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5,
    1.0, 2.5, 5.0, 10.0, 30.0, 60.0,
)

_SPAN_SECONDS = _metrics.global_registry.histogram(
    "gtpu_span_seconds",
    "wall time of finished trace spans, by span name",
    labels=("name",), buckets=_BOUNDS_S,
)
_SPAN_CPU = _metrics.global_registry.counter(
    "gtpu_span_cpu_seconds_total",
    "thread CPU time spent inside trace spans, by span name",
    labels=("name",),
)
_BACKGROUND_SECONDS = _metrics.global_registry.histogram(
    "gtpu_background_task_seconds",
    "wall time of periodic background work no request owns, by task",
    labels=("task",), buckets=_BOUNDS_S,
)
_GC_PAUSE_SECONDS = _metrics.global_registry.histogram(
    "gtpu_runtime_gc_pause_seconds",
    "wall time of Python garbage collections, by generation",
    labels=("generation",), buckets=_BOUNDS_S,
)


class _TimeRow:
    __slots__ = ("count", "wall_s", "cpu_s", "buckets")

    def __init__(self):
        self.count = 0
        self.wall_s = 0.0
        self.cpu_s = 0.0
        self.buckets = [0] * (len(_BOUNDS_S) + 1)


_rows_lock = concurrency.Lock()
_span_rows: dict[str, _TimeRow] = {}
_background_rows: dict[str, _TimeRow] = {}
# one row a generation, made up front and written WITHOUT the lock: a
# collection can start inside any allocation, also one made under
# `_rows_lock` by the same thread, and collections never overlap
_gc_rows: dict[str, _TimeRow] = {g: _TimeRow() for g in "012"}


def _fold(rows: dict, name: str, wall_s: float, cpu_s: float = 0.0):
    # first bound >= the value, or the trailing OVERFLOW slot
    # (metrics.observe_bucket's layout)
    slot = bisect.bisect_left(_BOUNDS_S, wall_s)
    with _rows_lock:
        row = rows.get(name)
        if row is None:
            row = rows[name] = _TimeRow()
        row.count += 1
        row.wall_s += wall_s
        row.cpu_s += cpu_s
        row.buckets[slot] += 1


def declare(*names: str):
    """Export a zero row for span names that finish rarely, so a
    reader of deltas finds the series before the first one does."""
    with _rows_lock:
        for name in names:
            _span_rows.setdefault(name, _TimeRow())


def _publish_rows():
    """MetricsRegistry collector: refresh the four families from the
    per-name rows."""
    with _rows_lock:
        snap = [
            (fam, name, row.count, row.wall_s, row.cpu_s,
             list(row.buckets))
            for fam, rows in ((_SPAN_SECONDS, _span_rows),
                              (_BACKGROUND_SECONDS, _background_rows),
                              (_GC_PAUSE_SECONDS, _gc_rows))
            for name, row in rows.items()
        ]
    for fam, name, count, wall_s, cpu_s, buckets in snap:
        hist = fam.labels(name)
        with hist._lock:
            cum = 0
            # the trailing OVERFLOW slot only reaches the +Inf bucket,
            # which the exposition derives from `count`
            for i in range(len(_BOUNDS_S)):
                cum += buckets[i]
                hist.counts[i] = cum
            hist.count = count
            hist.total = wall_s
        if fam is _SPAN_SECONDS:
            _metrics.set_child_value(_SPAN_CPU.labels(name), cpu_s)


_metrics.global_registry.register_collector(_publish_rows)

# Thread CPU time is read for one local root in `_CPU_EVERY` and for
# the spans beneath it, and counted `_CPU_EVERY` times over: where
# `time.thread_time()` is a real system call (6 us alone and 15 us a
# call in a serving process on the benchmark's machine, two calls a
# span, seventeen spans a query) reading it on every request cost 0.5 ms
# of a 10 ms query. The estimate is unbiased; a test sets it to 1.
_CPU_EVERY = 32
_cpu_turn = itertools.count()


# True while a device trace capture runs (device_programs.capture_trace
# sets it): spans, background ticks and collections then also open a
# profiler annotation. Off the capture this is one boolean test.
_annotating = False


def set_annotating(on: bool):
    global _annotating
    _annotating = bool(on)


def _annotate(name: str):
    """Open a `gtpu:<name>` event on this thread's line of the running
    capture; the caller closes it with `__exit__`."""
    import jax

    ann = jax.profiler.TraceAnnotation("gtpu:" + name)
    ann.__enter__()
    return ann


_NO_EVENT = contextlib.nullcontext()


def event(name: str, **meta):
    """`with tracing.event("device.wait", site=...)`: a `gtpu:<name>`
    event on the profiler's clock while a capture runs (`meta` rides it
    as the event's stats), nothing off it. For what is timed without a
    span of its own: the legs of a device call."""
    if not _annotating:
        return _NO_EVENT
    import jax

    return jax.profiler.TraceAnnotation("gtpu:" + name, **meta)


@dataclass(slots=True)
class Span:
    trace_id: str
    span_id: str
    parent_id: str | None
    name: str
    start_ms: float
    end_ms: float | None = None
    attributes: dict = field(default_factory=dict)
    # True ONLY on the placeholder parent start_remote builds from a
    # traceparent: a span whose parent carries this flag is this
    # process's LOCAL ROOT for the tail-sampling decision (the flag
    # deliberately does not propagate to descendants — a child exit
    # must never roll the sampling dice while the root is in flight)
    remote: bool = False
    # the ring's list of this trace (set when the store records the
    # span): a child appends itself there without the store's lock
    sink: list | None = field(default=None, repr=False, compare=False)
    # this request's tree is one of those whose thread CPU time is read
    # (see _CPU_EVERY); children follow their parent
    cpu: bool = field(default=False, repr=False, compare=False)

    def to_json(self) -> dict:
        return {
            "trace_id": self.trace_id,
            "span_id": self.span_id,
            "parent_id": self.parent_id,
            "name": self.name,
            "start_ms": round(self.start_ms, 3),
            "duration_ms": (
                None if self.end_ms is None
                else round(self.end_ms - self.start_ms, 3)
            ),
            # copied: a reader may serialize while __exit__ mutates
            "attributes": dict(self.attributes),
        }


class _TraceStore:
    """Bounded ring of traces. Spans record at START so /v1/traces
    shows in-flight work; the tail-sampling decision at the local
    root's finish either confirms the trace or drops it.

    What the ring keeps, and for how long: a trace kept FOR CAUSE (an
    error span, a root at or over `slow_ms`, `mark_keep`) is kept
    against sampling and is the last to be evicted — traces in flight
    or kept by chance go first, oldest first, and traces kept for cause
    go oldest first only once they fill three quarters of the ring (so
    a storm of errors cannot starve the ring of recent traffic). Beside
    the ring, outside its count, the store holds the SLOWEST finished
    trace of each local-root name (one slot a name; names are bounded,
    see the module's docstring), served by `/v1/traces?slowest=1`:
    after any run one GET names the stage that held the worst request
    of each route, however many requests came after it."""

    def __init__(self, cap: int = _MAX_TRACES):
        self._lock = concurrency.Lock()
        # in flight or kept by chance. Insertion-ordered: the oldest
        # trace is the first key, so eviction and the sampled-out drop
        # are both O(1)
        self._spans: collections.OrderedDict[str, list[Span]] = (
            collections.OrderedDict()
        )
        # kept for cause, in the order they were decided
        self._held: collections.OrderedDict[str, list[Span]] = (
            collections.OrderedDict()
        )
        self._kept: set[str] = set()
        # local-root name -> (duration_ms, trace_id, the trace's spans)
        self._slowest: dict[str, tuple[float, str, list[Span]]] = {}
        # local roots currently in flight per trace (a client may send
        # one traceparent on several concurrent requests): a sampled-
        # out sibling must never drop a trace another root is still
        # writing — the LAST root out makes the final drop decision
        self._active: dict[str, int] = {}
        self.cap = cap
        self.evicted_traces = 0
        from greptimedb_tpu.telemetry import memory as _memory

        _memory.register_pool(
            "trace_ring", "host", self, stats=_TraceStore._mem_stats
        )

    # a client/proxy bug resending one traceparent forever must not
    # grow a single trace unboundedly
    MAX_SPANS_PER_TRACE = 512

    # flat per-span host-byte estimate for the memory accountant (a
    # Span dataclass + ids + a small attribute dict; exact accounting
    # would walk every attribute on every scrape)
    SPAN_EST_BYTES = 512

    def _mem_stats(self) -> dict:
        # scrape-time work under the lock every span takes
        with background_span("trace_ring.stats"), self._lock:
            n_spans = sum(len(s) for s in self._spans.values())
            n_spans += sum(len(s) for s in self._held.values())
            return {
                "bytes": n_spans * self.SPAN_EST_BYTES,
                "entries": n_spans,
                "max_entries": max(self.cap, 0)
                * self.MAX_SPANS_PER_TRACE,
                "evictions": self.evicted_traces,
            }

    def set_cap(self, cap: int):
        with self._lock:
            self.cap = int(cap)
            self._evict_locked()

    def _evict_locked(self):
        if self.cap <= 0:
            return  # unbounded: nothing is ever evicted
        held_max = self.cap - self.cap // 4
        while len(self._spans) + len(self._held) > self.cap:
            ring = (self._held if len(self._held) > held_max
                    or not self._spans else self._spans)
            victim, _ = ring.popitem(last=False)
            self._kept.discard(victim)
            self.evicted_traces += 1

    def record(self, span: Span):
        parent_sink = span.sink
        if parent_sink is not None:
            # a child of a span recorded here: straight into its
            # trace's list (one append; the bound may be overshot by
            # the few threads of one request racing, never unboundedly).
            # A trace dropped or evicted meanwhile keeps its orphaned
            # list until its last span finishes.
            if len(parent_sink) < self.MAX_SPANS_PER_TRACE:
                parent_sink.append(span)
            return
        with self._lock:
            tid = span.trace_id
            spans = self._spans.get(tid)
            if spans is None:
                spans = self._held.get(tid)
            if spans is None:
                spans = self._spans[tid] = []
                self._evict_locked()
            if len(spans) < self.MAX_SPANS_PER_TRACE:
                spans.append(span)
            span.sink = spans

    def enter_root(self, trace_id: str):
        with self._lock:
            self._active[trace_id] = self._active.get(trace_id, 0) + 1

    def decide(self, root: Span):
        """Tail-sampling decision at a local root's finish: error spans
        anywhere in the trace, slow roots, and marked traces keep for
        cause (against sampling, and past the traces kept by chance);
        otherwise keep with probability sample_ratio. A drop only
        happens when NO other local root of the trace is in flight.
        The slowest root of each name keeps its trace in that name's
        slot whatever was decided."""
        tid = root.trace_id
        took_ms = (0.0 if root.end_ms is None
                   else root.end_ms - root.start_ms)
        with self._lock:
            remaining = self._active.get(tid, 1) - 1
            if remaining > 0:
                self._active[tid] = remaining
            else:
                self._active.pop(tid, None)
            # the root's own list: there also once the ring has turned
            # past a request still in flight (a stalled one, under load)
            spans = root.sink
            if spans is None:
                return
            slowest = self._slowest.get(root.name)
            if root.end_ms is not None and (
                    slowest is None or took_ms > slowest[0]):
                self._slowest[root.name] = (took_ms, tid, spans)
            if tid in self._held:
                return
            cause = took_ms >= _config.slow_ms and root.end_ms is not None
            if not cause:
                for s in spans:
                    if "error" in s.attributes or s.attributes.get("keep"):
                        cause = True
                        break
            if cause:
                self._spans.pop(tid, None)
                self._kept.discard(tid)     # by chance no longer
                self._held[tid] = spans
                self._evict_locked()
                return
            if tid in self._kept or tid not in self._spans:
                return
            ratio = _config.sample_ratio
            if ratio >= 1.0 or random.random() < ratio:
                self._kept.add(tid)
            elif remaining <= 0:
                # last root out and nothing remarkable: drop. With
                # siblings still writing, defer — the last one decides
                # over the COMPLETE span set (an error recorded later
                # must still be able to keep the trace).
                self._spans.pop(tid, None)

    def ingest(self, span_dicts: list, limit: int = _MAX_EXPORT_SPANS):
        """Record spans exported by ANOTHER process (gtdb:spans
        metadata) into this ring so the stitched trace lives in one
        place. No sampling decision — the local root's decision covers
        the whole trace."""
        for doc in span_dicts[:limit]:
            try:
                dur = doc.get("duration_ms")
                start = float(doc.get("start_ms") or 0.0)
                self.record(Span(
                    trace_id=str(doc["trace_id"]),
                    span_id=str(doc.get("span_id") or ""),
                    parent_id=doc.get("parent_id"),
                    name=str(doc.get("name") or "remote"),
                    start_ms=start,
                    end_ms=None if dur is None else start + float(dur),
                    attributes=dict(doc.get("attributes") or {}),
                ))
            except (KeyError, TypeError, ValueError):
                continue  # a malformed remote span must not kill a query

    def traces(self, limit: int = 50) -> list[dict]:
        """The ring's traces, newest first (by their first span)."""
        with self._lock:
            both = [*self._spans.items(), *self._held.items()]
            both.sort(key=lambda kv: kv[1][0].start_ms if kv[1] else 0.0,
                      reverse=True)
            if limit > 0:
                both = both[:int(limit)]
            return [{"trace_id": tid,
                     "spans": [s.to_json() for s in spans]}
                    for tid, spans in both]

    def trace(self, trace_id: str) -> list[dict]:
        with self._lock:
            spans = (self._spans.get(trace_id)
                     or self._held.get(trace_id) or [])
            return [s.to_json() for s in spans]

    def slowest(self, *, reset: bool = False) -> list[dict]:
        """The slowest finished trace of each local-root name, slowest
        first: held outside the ring's count, so it is there however
        many requests came after it. `reset` empties the slots once
        read: what is read next is the worst since (a warm-up's cold
        first query would otherwise hold its route's slot for good)."""
        with self._lock:
            slots = sorted(self._slowest.items(),
                           key=lambda kv: kv[1][0], reverse=True)
            if reset:
                self._slowest = {}
            return [{"name": name, "duration_ms": round(took_ms, 3),
                     "trace_id": tid,
                     "spans": [s.to_json() for s in spans]}
                    for name, (took_ms, tid, spans) in slots]

    def clear(self):
        with self._lock:
            self._spans.clear()
            self._held.clear()
            self._kept.clear()
            self._active.clear()
            self._slowest.clear()


global_traces = _TraceStore()


# span/trace ids need uniqueness, not cryptographic strength — and
# they are on the hot path of every traced statement. A per-process
# PRNG seeded from the CSPRNG is ~20x faster than secrets.token_hex
# (single C call; the GIL makes getrandbits atomic in CPython).
_idgen = random.Random(secrets.randbits(64))


def _new_id(nbytes: int) -> str:
    return f"{_idgen.getrandbits(nbytes * 8):0{nbytes * 2}x}"


class span:
    """Context manager: `with tracing.span("query.plan", sql=...)`.
    Nests under the current span; starts a new trace at the root."""

    __slots__ = ("name", "attributes", "_parent", "_span", "_token",
                 "_mono0", "_cpu0", "_ann", "_local_root")

    def __init__(self, name: str, _parent: Span | None = None,
                 **attributes):
        self.name = name
        self.attributes = attributes
        self._parent = _parent
        self._span: Span | None = None
        self._token = None
        self._mono0 = 0.0
        self._cpu0 = 0.0
        self._ann = None
        self._local_root = False

    def __enter__(self) -> Span:
        if not _config.enabled:
            # inert span: no context, no ring, no ids — zero footprint
            self._span = Span("", "", None, self.name, 0.0,
                              attributes=dict(self.attributes))
            return self._span
        parent = (self._parent if self._parent is not None
                  else _current_span.get())
        self._local_root = parent is None or parent.remote
        self._span = Span(
            trace_id=(parent.trace_id if parent else _new_id(16)),
            span_id=_new_id(8),
            parent_id=parent.span_id if parent else None,
            name=self.name,
            # epoch-ms START timestamp for display/correlation; the
            # duration below comes from the monotonic clock (GT011)
            start_ms=time.time() * 1000.0,
            # the constructor's kwargs dict is this span's own
            attributes=self.attributes,
        )
        sp = self._span
        self._token = _current_span.set(sp)
        if self._local_root:
            global_traces.enter_root(sp.trace_id)
            sp.cpu = next(_cpu_turn) % _CPU_EVERY == 0
        else:
            sp.sink = parent.sink
            sp.cpu = parent.cpu
        # recorded at START: /v1/traces shows in-flight spans (duration
        # null) and a span is never missing just because its exit races
        # a reader; __exit__ finalizes the same object in place
        global_traces.record(sp)
        if _annotating:
            self._ann = _annotate(self.name)
        # the clocks start last and stop first, so the span's own
        # bookkeeping stays out of the time it reports; the CPU reading
        # lies inside the wall reading, so CPU does not exceed wall
        self._mono0 = time.monotonic()
        if sp.cpu:
            self._cpu0 = time.thread_time()
        return sp

    def __exit__(self, exc_type, exc, tb):
        sp = self._span
        if self._token is None:
            return False  # disabled at __enter__ time
        cpu_s = ((time.thread_time() - self._cpu0) * _CPU_EVERY
                 if sp.cpu else 0.0)
        wall_s = time.monotonic() - self._mono0
        if self._ann is not None:
            self._ann.__exit__(exc_type, exc, tb)
            self._ann = None
        sp.end_ms = sp.start_ms + wall_s * 1000.0
        _fold(_span_rows, self.name, wall_s, cpu_s)
        if exc is not None:
            sp.attributes["error"] = f"{type(exc).__name__}: {exc}"
        _current_span.reset(self._token)
        self._token = None
        col = _collector.get()
        if col is not None and len(col) < _MAX_EXPORT_SPANS:
            col.append(sp)
        if self._local_root:
            # this process's outermost span: tail-sampling decision
            global_traces.decide(sp)
        return False


class _noop_span:
    """Context manager yielding an inert Span (attribute writes land
    nowhere); the zero-cost path for child_span with no active trace."""

    __slots__ = ("_span",)

    def __init__(self, name: str, attributes: dict):
        self._span = Span("", "", None, name, 0.0,
                          attributes=dict(attributes))

    def __enter__(self) -> Span:
        return self._span

    def __exit__(self, exc_type, exc, tb):
        return False


def child_span(name: str, _parent: Span | None = None, **attributes):
    """A span ONLY when it can join an existing trace: hot-path
    internals (WAL append, flush, scans, device calls) use this so
    background work with no request context never floods the ring with
    single-span root traces."""
    if not _config.enabled:
        return _noop_span(name, attributes)
    parent = _parent if _parent is not None else _current_span.get()
    if parent is None or not parent.trace_id:
        # no trace to join (or an inert parent from a disabled scope)
        return _noop_span(name, attributes)
    return span(name, _parent=parent, **attributes)


def event_span(name: str, duration_ms: float, **attributes):
    """Record an already-measured stage as a completed child span (the
    dist-query stage clock and recovery stage recorder re-publish the
    SAME numbers they export as gtpu_*_stage_ms metrics, so traces and
    metrics agree). No-op outside an active trace."""
    if not _config.enabled:
        return
    parent = _current_span.get()
    if parent is None:
        return
    now = time.time() * 1000.0
    dur = max(float(duration_ms), 0.0)
    sp = Span(
        trace_id=parent.trace_id, span_id=_new_id(8),
        parent_id=parent.span_id, name=name,
        start_ms=now - dur, end_ms=now,
        attributes=dict(attributes), sink=parent.sink,
    )
    global_traces.record(sp)
    _fold(_span_rows, name, dur / 1000.0)
    col = _collector.get()
    if col is not None and len(col) < _MAX_EXPORT_SPANS:
        col.append(sp)


class background_span:
    """`with tracing.background_span("engine.maintenance"):` around one
    round of a periodic loop that no request owns (engine maintenance,
    flow tick, heartbeat, scrape-time publishers). Its time goes to
    `gtpu_span_seconds{name}` and `gtpu_background_task_seconds{task}`
    and, during a capture, into the profile; the trace ring only ever
    sees a round slower than `[tracing] slow_ms` (a 1 Hz tick must not
    churn the query traces out). Spans opened inside it find no parent,
    so `child_span` stays a no-op there."""

    __slots__ = ("name", "_mono0", "_cpu0", "_ann")

    def __init__(self, name: str):
        self.name = name
        self._mono0 = None
        self._cpu0 = 0.0
        self._ann = None

    def __enter__(self):
        if _config.enabled:
            if _annotating:
                self._ann = _annotate(self.name)
            self._mono0 = time.monotonic()
            self._cpu0 = time.thread_time()
        return self

    def __exit__(self, exc_type, exc, tb):
        if self._mono0 is None:
            return False
        cpu_s = time.thread_time() - self._cpu0
        wall_s = time.monotonic() - self._mono0
        if self._ann is not None:
            self._ann.__exit__(exc_type, exc, tb)
            self._ann = None
        _fold(_span_rows, self.name, wall_s, cpu_s)
        _fold(_background_rows, self.name, wall_s, cpu_s)
        if wall_s * 1000.0 >= _config.slow_ms:
            now = time.time() * 1000.0
            sp = Span(
                trace_id=_new_id(16), span_id=_new_id(8), parent_id=None,
                name=self.name, start_ms=now - wall_s * 1000.0,
                end_ms=now, attributes={"background": True},
            )
            global_traces.record(sp)
            global_traces.decide(sp)
        return False


# the collection in flight: (monotonic start, annotation). One slot is
# enough, since a collection runs start -> stop on one thread and no
# other starts in between.
_gc_open: list = [None]


def _on_gc(phase: str, info: dict):
    if not _config.enabled:
        return
    if phase == "start":
        ann = (_annotate(f"gc.gen{info['generation']}")
               if _annotating else None)
        _gc_open[0] = (time.monotonic(), ann)
        return
    opened, _gc_open[0] = _gc_open[0], None
    if opened is None:
        return      # enabled between its start and its stop
    wall_s = time.monotonic() - opened[0]
    if opened[1] is not None:
        opened[1].__exit__(None, None, None)
    row = _gc_rows[str(info["generation"])]
    row.count += 1
    row.wall_s += wall_s
    row.buckets[bisect.bisect_left(_BOUNDS_S, wall_s)] += 1


if _on_gc not in gc.callbacks:
    gc.callbacks.append(_on_gc)


def current_span() -> Span | None:
    return _current_span.get()


def current_trace_id() -> str | None:
    sp = _current_span.get()
    return sp.trace_id if sp and sp.trace_id else None


def set_attr(**attributes):
    """Attach attributes to the current span (e.g. the mesh planner's
    replicate-vs-shard decision); no-op outside a span."""
    sp = _current_span.get()
    if sp is not None:
        sp.attributes.update(attributes)


def mark_keep():
    """Force-keep the current trace through tail sampling (shed and
    deadline-expired queries stay inspectable at any sample_ratio)."""
    sp = _current_span.get()
    if sp is not None:
        sp.attributes["keep"] = True


def traceparent() -> str | None:
    """W3C `traceparent` of the current span — what every outbound wire
    (Flight ticket field, DoPut app_metadata, HTTP header) carries so
    the receiving process parents its spans under ours."""
    sp = _current_span.get()
    if sp is None or not sp.trace_id:
        return None
    return f"00-{sp.trace_id}-{sp.span_id}-01"


import re as _re

# strict W3C form: lowercase hex only. The ids are CLIENT-controlled
# and get spliced into hand-built ticket JSON (dist_query.py) and
# stripped by a lowercase-hex regex on the datanode (merge.py) — a
# looser accept here would let a quote-bearing "trace id" corrupt
# tickets or an uppercase one churn the datanode decode memo.
_TRACEPARENT_RE = _re.compile(
    r"00-([0-9a-f]{32})-([0-9a-f]{16})-[0-9a-f]{2}\Z"
)


def start_remote(traceparent: str | None, name: str, **attributes):
    """Span continuing a W3C `traceparent: 00-<trace>-<parent>-<flags>`
    header when present and well-formed (strict lowercase hex); a
    fresh root otherwise. Either way the span is this process's local
    root for the tail-sampling decision."""
    parent = None
    if traceparent:
        m = _TRACEPARENT_RE.match(traceparent.strip())
        if m and m.group(1) != "0" * 32:
            parent = Span(
                trace_id=m.group(1), span_id=m.group(2),
                parent_id=None, name="remote-parent", start_ms=0.0,
                remote=True,
            )
    return span(name, _parent=parent, **attributes)


@contextlib.contextmanager
def export_spans():
    """Collect every span FINISHED inside this context (the list the
    datanode ships back as `gtdb:spans`, and EXPLAIN ANALYZE renders
    inline). Yields the live list; read it after the block."""
    spans: list[Span] = []
    token = _collector.set(spans)
    try:
        yield spans
    finally:
        _collector.reset(token)


def ingest_spans(span_dicts: list | None):
    """Record spans exported by another process into the local ring."""
    if span_dicts:
        global_traces.ingest(span_dicts)


def render_tree(span_dicts: list[dict]) -> list[str]:
    """Indented parent->child rendering of one trace's span dicts (the
    EXPLAIN ANALYZE inline view). Spans whose parent is not in the set
    (remote parents) render as roots; children sort by start time."""
    by_id = {s["span_id"]: s for s in span_dicts if s.get("span_id")}
    children: dict[str | None, list[dict]] = {}
    roots: list[dict] = []
    for s in span_dicts:
        pid = s.get("parent_id")
        if pid in by_id and pid != s.get("span_id"):
            children.setdefault(pid, []).append(s)
        else:
            roots.append(s)

    def fmt(s: dict) -> str:
        dur = s.get("duration_ms")
        dur_s = "..." if dur is None else f"{dur:.3f}ms"
        attrs = {
            k: v for k, v in (s.get("attributes") or {}).items()
            if k != "keep"
        }
        extra = ""
        if attrs:
            inner = ", ".join(f"{k}={v}" for k, v in sorted(
                attrs.items(), key=lambda kv: kv[0]
            ))
            extra = f" {{{inner}}}"
        return f"{s['name']} {dur_s}{extra}"

    lines: list[str] = []

    def walk(s: dict, depth: int):
        lines.append("  " * depth + fmt(s))
        for c in sorted(children.get(s.get("span_id"), []),
                        key=lambda x: x.get("start_ms") or 0.0):
            walk(c, depth + 1)

    for r in sorted(roots, key=lambda x: x.get("start_ms") or 0.0):
        walk(r, 0)
    return lines
