"""gtlint rules GT001-GT010.

Each rule encodes a hazard class this codebase has actually been
bitten by (see the PR log in CHANGES.md): silent exception swallows
that hid datanode failures, substring matching on error text that the
typed-error migration obsoleted, host/device sync inside jitted hot
paths that shows up only as tail latency, and locks held across
blocking Flight I/O that serialize the ingest dataplane.
"""

from __future__ import annotations

import ast

from greptimedb_tpu.tools.lint import callgraph
from greptimedb_tpu.tools.lint.core import (
    FileContext,
    Rule,
    _looks_like_device_call,
    dotted_name,
    register,
    traced_value_use,
)


def _is_swallow_body(body: list[ast.stmt]) -> bool:
    """True when a handler body does nothing: only pass/`...`."""
    for stmt in body:
        if isinstance(stmt, ast.Pass):
            continue
        if (isinstance(stmt, ast.Expr)
                and isinstance(stmt.value, ast.Constant)):
            continue
        return False
    return True


_BROAD = {"Exception", "BaseException"}


def _handler_catches_broad(node: ast.ExceptHandler) -> bool:
    if node.type is None:
        return True
    types = (node.type.elts if isinstance(node.type, ast.Tuple)
             else [node.type])
    for t in types:
        d = dotted_name(t)
        if d is not None and d.split(".")[-1] in _BROAD:
            return True
    return False


@register
class SilentSwallow(Rule):
    id = "GT001"
    name = "silent-exception-swallow"
    description = (
        "`except Exception: pass` (or a bare except) discards the "
        "error with no trace. Narrow the exception type, re-raise, or "
        "log with context."
    )

    def visit_ExceptHandler(self, node: ast.ExceptHandler,
                            ctx: FileContext):
        if node.type is None:
            ctx.report(self, node,
                       "bare `except:` also catches KeyboardInterrupt/"
                       "SystemExit; catch a concrete exception type")
            return
        if _handler_catches_broad(node) and _is_swallow_body(node.body):
            ctx.report(self, node,
                       "broad except with an empty body silently "
                       "swallows the error; narrow the type, re-raise, "
                       "or log with context")


_EXC_HINT_NAMES = {"e", "ex", "exc", "err", "error", "exception"}


def _unwrap_str_call(node: ast.AST) -> ast.AST | None:
    """For `str(x)`, `str(x).lower()`, ... return x; else None."""
    while isinstance(node, ast.Call) and isinstance(node.func,
                                                    ast.Attribute):
        node = node.func.value
    if (isinstance(node, ast.Call) and isinstance(node.func, ast.Name)
            and node.func.id == "str" and node.args):
        return node.args[0]
    return None


@register
class ErrorSubstringMatch(Rule):
    id = "GT002"
    name = "error-substring-match"
    description = (
        "Classifying an exception by substring-matching its message "
        "(`'...' in str(e)`) breaks the moment the wording changes. "
        "Use isinstance on a typed error, or the `[gtdb:<code>]` "
        "marker via errors.error_from_code."
    )

    def visit_Compare(self, node: ast.Compare, ctx: FileContext):
        if not all(isinstance(op, (ast.In, ast.NotIn))
                   for op in node.ops):
            return
        for comp in node.comparators:
            inner = _unwrap_str_call(comp)
            if inner is None or not isinstance(inner, ast.Name):
                continue
            if (inner.id in ctx.exc_names
                    or inner.id in _EXC_HINT_NAMES):
                ctx.report(self, node,
                           f"substring match on str({inner.id}) — "
                           "classify via typed errors "
                           "(errors.error_from_code / isinstance), "
                           "not message text")


@register
class UntypedRaise(Rule):
    id = "GT003"
    name = "untyped-raise"
    description = (
        "Raising a plain `Exception` defeats the errors.py taxonomy: "
        "callers cannot catch it without a broad except, and it "
        "crosses the Flight boundary as UNKNOWN. Raise a GreptimeError "
        "subclass."
    )

    def visit_Raise(self, node: ast.Raise, ctx: FileContext):
        if ctx.path.replace("\\", "/").endswith("errors.py"):
            return
        exc = node.exc
        if isinstance(exc, ast.Call):
            exc = exc.func
        d = dotted_name(exc) if exc is not None else None
        if d in ("Exception", "BaseException"):
            ctx.report(self, node,
                       f"raise {d} is untyped; raise a GreptimeError "
                       "subclass from greptimedb_tpu.errors")


_HOST_SYNC_ATTRS = {"item", "tolist"}
_HOST_SYNC_CALLS = {
    "np.asarray", "np.array", "np.fromiter", "numpy.asarray",
    "numpy.array", "onp.asarray", "onp.array", "jax.device_get",
}


@register
class HostSyncInJit(Rule):
    id = "GT004"
    name = "host-sync-in-jit"
    description = (
        "Inside a @jax.jit function or Pallas kernel, `.item()`, "
        "np.asarray(...), float(x)/int(x) on traced values force a "
        "device->host transfer (or fail to trace), stalling the "
        "pipeline. Keep host conversions outside the jitted region."
    )

    def visit_Call(self, node: ast.Call, ctx: FileContext):
        fi = ctx.device_func
        if fi is None:
            return
        if (isinstance(node.func, ast.Attribute)
                and node.func.attr in _HOST_SYNC_ATTRS):
            ctx.report(self, node,
                       f".{node.func.attr}() inside a jitted/device "
                       "function forces host sync")
            return
        d = dotted_name(node.func)
        if d in _HOST_SYNC_CALLS:
            ctx.report(self, node,
                       f"{d}(...) inside a jitted/device function "
                       "materializes on host; use jnp instead")
            return
        if (isinstance(node.func, ast.Name)
                and node.func.id in ("float", "int", "bool")
                and node.args
                and any(traced_value_use(a, fi) for a in node.args)):
            ctx.report(self, node,
                       f"{node.func.id}() on a traced value forces "
                       "host sync inside jit")
            return
        # interprocedural: a module-local helper that (transitively)
        # does .item()/.tolist()/device_get, called on a traced value
        # from inside the jitted region, syncs just the same
        s = ctx.call_summary.resolve_call(node, ctx.current_class)
        if (s is not None and s.host_sync
                and any(traced_value_use(a, fi) for a in node.args)):
            chain = " -> ".join(s.sync_chain)
            ctx.report(self, node,
                       f"{s.qualname}(...) on a traced value inside a "
                       f"jitted/device function reaches a host sync "
                       f"({chain}); hoist it out of the jitted region")


@register
class TracedPythonBranch(Rule):
    id = "GT005"
    name = "traced-python-branch"
    description = (
        "A Python `if`/`while` on a traced value inside jit forces "
        "concretization (TracerBoolConversionError at best, silent "
        "host sync at worst). Use jnp.where / lax.cond / lax.select, "
        "or mark the argument static."
    )

    def _check(self, test: ast.AST, node: ast.AST, ctx: FileContext,
               kind: str):
        fi = ctx.device_func
        if fi is None:
            return
        while isinstance(test, ast.UnaryOp) and isinstance(test.op,
                                                           ast.Not):
            test = test.operand
        if traced_value_use(test, fi):
            ctx.report(self, node,
                       f"Python {kind} on a traced value inside a "
                       "jitted/device function; use jnp.where / "
                       "lax.cond or a static arg")

    def visit_If(self, node: ast.If, ctx: FileContext):
        self._check(node.test, node, ctx, "if")

    def visit_IfExp(self, node: ast.IfExp, ctx: FileContext):
        self._check(node.test, node, ctx, "conditional expression")

    def visit_While(self, node: ast.While, ctx: FileContext):
        self._check(node.test, node, ctx, "while")


def _is_jit_call(node: ast.Call) -> bool:
    f = dotted_name(node.func)
    if f in ("jax.jit", "jit", "jax.pjit", "pjit"):
        return True
    if f in ("functools.partial", "partial") and node.args:
        return dotted_name(node.args[0]) in ("jax.jit", "jit")
    return False


@register
class RecompileHazard(Rule):
    id = "GT006"
    name = "recompile-hazard"
    description = (
        "jax.jit(...) constructed inside a loop (or over a lambda "
        "inside a function body) builds a fresh cache entry per "
        "iteration/call — every invocation recompiles. Hoist the "
        "jitted callable to module scope or cache it."
    )

    def visit_Call(self, node: ast.Call, ctx: FileContext):
        if not _is_jit_call(node):
            return
        if ctx.loop_depth > 0:
            ctx.report(self, node,
                       "jax.jit constructed inside a loop recompiles "
                       "every iteration; hoist it out")
        elif (ctx.func_stack
              and node.args
              and isinstance(node.args[-1], ast.Lambda)):
            ctx.report(self, node,
                       "jax.jit(lambda ...) inside a function creates "
                       "a new callable (and compile cache entry) per "
                       "call; define and jit it at module scope")


@register
class LockAcrossBlockingIO(Rule):
    id = "GT007"
    name = "lock-across-blocking-io"
    description = (
        "A threading.Lock held across blocking I/O (sockets, HTTP, "
        "Arrow Flight do_get/do_put/do_action, sleep) serializes every "
        "other thread on that lock for the full I/O latency — directly "
        "or through any chain of module-local helper calls. Copy the "
        "state out under the lock, do the I/O outside it."
    )

    def visit_Call(self, node: ast.Call, ctx: FileContext):
        if ctx.lock_depth == 0:
            return
        label = callgraph.blocking_label(node)
        if label is not None:
            ctx.report(self, node,
                       f"{label}(...) called while holding a lock "
                       "blocks every other waiter for the full I/O "
                       "latency; move the call outside the lock")
            return
        # interprocedural: a module-local helper that (transitively)
        # blocks is just as bad as the direct call
        s = ctx.call_summary.resolve_call(node, ctx.current_class)
        if s is not None and s.blocking:
            chain = " -> ".join(s.block_chain)
            ctx.report(self, node,
                       f"{s.qualname}(...) called while holding a "
                       f"lock reaches blocking I/O ({chain}); move "
                       "the call outside the lock")


def _assign_target_segment(ctx: FileContext) -> str | None:
    """Last name segment of the Assign target the dispatched call
    feeds, e.g. '_worker' for `self._worker = threading.Thread(...)`,
    't' for `t = Thread(...)`. None when not directly assigned."""
    parent = ctx.parent(1)
    if isinstance(parent, (ast.Assign, ast.AnnAssign)):
        tgt = (parent.targets[0] if isinstance(parent, ast.Assign)
               else parent.target)
        d = dotted_name(tgt)
        if d:
            return d.split(".")[-1]
    return None


@register
class UnjoinedThread(Rule):
    id = "GT008"
    name = "unjoined-thread"
    description = (
        "A non-daemon Thread that is never join()ed (or a "
        "ThreadPoolExecutor never shutdown and not used as a context "
        "manager) leaks and can hang interpreter exit. Pass "
        "daemon=True, join it, or shut the pool down in close()."
    )

    def _has_kw(self, node: ast.Call, name: str, value=True) -> bool:
        for kw in node.keywords:
            if (kw.arg == name and isinstance(kw.value, ast.Constant)
                    and kw.value.value is value):
                return True
        return False

    def visit_Call(self, node: ast.Call, ctx: FileContext):
        d = dotted_name(node.func)
        if d is None:
            return
        last = d.split(".")[-1]
        if last == "Thread":
            if self._has_kw(node, "daemon"):
                return
            seg = _assign_target_segment(ctx)
            scope = ctx.scope_text(cls=bool(ctx.class_stack))
            if seg is not None and f"{seg}.join(" in scope:
                return
            ctx.report(self, node,
                       "Thread without daemon=True and no matching "
                       ".join() in scope leaks on shutdown")
        elif last == "ThreadPoolExecutor":
            parent = ctx.parent(1)
            if isinstance(parent, (ast.withitem, ast.With)):
                return          # `with ThreadPoolExecutor(...) as ..`
            seg = _assign_target_segment(ctx)
            scope = (ctx.scope_text(cls=True) if ctx.class_stack
                     else ctx.source)
            # evidence the pool is torn down: either a direct
            # `<name>.shutdown(...)`, or the swap-to-local teardown
            # idiom (`pool, self._x = self._x, None` then
            # `pool.shutdown()` outside the lock) — approximated as
            # the name and a .shutdown( call both present in scope
            if seg is not None and (f"{seg}.shutdown(" in scope
                                    or f"{seg}.join(" in scope
                                    or (seg in scope
                                        and ".shutdown(" in scope)):
                return
            ctx.report(self, node,
                       "ThreadPoolExecutor with no shutdown() in "
                       "scope and not used as a context manager "
                       "leaks worker threads")


_INT64_DOTTED = {"jnp.int64", "jax.numpy.int64", "jnp.uint64",
                 "jax.numpy.uint64"}


@register
class Int64OnDevice(Rule):
    id = "GT009"
    name = "int64-on-device"
    description = (
        "jnp int64/uint64 silently downcasts to 32-bit unless x64 is "
        "enabled, and is slow on TPU where it is emulated. Use int32 "
        "(guard row counts < 2^31 on host), or gate explicitly on the "
        "x64 flag."
    )

    def visit_Attribute(self, node: ast.Attribute, ctx: FileContext):
        d = dotted_name(node)
        if d in _INT64_DOTTED:
            ctx.report(self, node,
                       f"{d} downcasts silently without x64 and is "
                       "emulated on TPU; prefer int32 or gate on x64")

    def visit_Call(self, node: ast.Call, ctx: FileContext):
        d = dotted_name(node.func)
        if not d or not (d.startswith("jnp.")
                         or d.startswith("jax.numpy.")):
            return
        for kw in node.keywords:
            if kw.arg != "dtype":
                continue
            kd = dotted_name(kw.value)
            if (kd in ("np.int64", "numpy.int64", "np.uint64")
                    or (isinstance(kw.value, ast.Constant)
                        and kw.value.value in ("int64", "uint64"))):
                ctx.report(self, node,
                           f"{d}(dtype=int64) on device; prefer int32 "
                           "or gate on x64")


def _is_walltime_call(node: ast.AST) -> bool:
    return isinstance(node, ast.Call) and dotted_name(node.func) in (
        "time.time", "_time.time")


def _contains_walltime_call(expr: ast.AST) -> bool:
    """Does `expr` contain a time.time() call in the *interval* domain?

    The exact idiom `time.time() * 1000` (either operand order) is the
    codebase's epoch-ms DATA-timestamp constructor — arithmetic on the
    result compares against row timestamps, where wall clock is the
    point — so it is exempt.  `(time.time() - t0) * 1000` is NOT: the
    subtraction happens in the time domain and stays flagged."""

    def scan(node: ast.AST) -> bool:
        if isinstance(node, ast.BinOp) and isinstance(node.op, ast.Mult):
            def ms(n):
                return (isinstance(n, ast.Constant)
                        and n.value in (1000, 1000.0))

            if ((_is_walltime_call(node.left) and ms(node.right))
                    or (_is_walltime_call(node.right) and ms(node.left))):
                return False        # epoch-ms data timestamp
        if _is_walltime_call(node):
            return True
        return any(scan(c) for c in ast.iter_child_nodes(node))

    return scan(expr)


@register
class WallClockDuration(Rule):
    id = "GT011"
    name = "wallclock-duration"
    description = (
        "Duration/deadline arithmetic on time.time() jumps with NTP "
        "slews and DST — a retry window or cooldown can silently "
        "double or go negative. Use time.monotonic() for elapsed/"
        "deadline math; time.time() is for *data* timestamps only "
        "(the epoch-ms constructor `time.time() * 1000` is exempt)."
    )

    @staticmethod
    def _scan_assigns(scope: ast.AST, *, skip_nested: bool) -> set[str]:
        """Names assigned from a wall-time expression within `scope`'s
        own statements (optionally not descending into nested function
        bodies — their bindings are a different scope)."""
        names: set[str] = set()
        stack = list(ast.iter_child_nodes(scope))
        while stack:
            node = stack.pop()
            if skip_nested and isinstance(node, (ast.FunctionDef,
                                                 ast.AsyncFunctionDef,
                                                 ast.Lambda)):
                continue
            if (isinstance(node, ast.Assign) and len(node.targets) == 1
                    and isinstance(node.targets[0], ast.Name)
                    and _contains_walltime_call(node.value)):
                names.add(node.targets[0].id)
            stack.extend(ast.iter_child_nodes(node))
        return names

    def _wall_names(self, ctx: FileContext) -> set[str]:
        """Names bound to time.time() in the CURRENT scope: the
        enclosing function's own assignments plus module-level ones.
        Scoped per function — `now = time.time()` in one function must
        not poison a monotonic `now` in another."""
        cache = getattr(ctx, "_gt011_scopes", None)
        if cache is None:
            cache = ctx._gt011_scopes = {}
        if "module" not in cache:
            cache["module"] = self._scan_assigns(ctx.tree,
                                                 skip_nested=True)
        fi = ctx.current_func
        if fi is None:
            return cache["module"]
        key = id(fi.node)
        if key not in cache:
            cache[key] = self._scan_assigns(fi.node, skip_nested=True)
        return cache[key] | cache["module"]

    def visit_BinOp(self, node: ast.BinOp, ctx: FileContext):
        if not isinstance(node.op, (ast.Add, ast.Sub)):
            return
        for side in (node.left, node.right):
            if _contains_walltime_call(side):
                ctx.report(self, node,
                           "duration/deadline arithmetic on "
                           "time.time(); use time.monotonic() (wall "
                           "clock is for data timestamps, not "
                           "intervals)")
                return
            if (isinstance(side, ast.Name)
                    and side.id in self._wall_names(ctx)):
                ctx.report(self, node,
                           f"{side.id} holds time.time() and feeds "
                           "duration/deadline arithmetic; use "
                           "time.monotonic() for interval math")
                return


_FLIGHT_CLIENT_CALLS = {"do_get", "do_put", "do_action"}
_TIMEOUT_KW_CALLS = {"urlopen", "create_connection"}


@register
class UnboundedBlockingCall(Rule):
    id = "GT012"
    name = "unbounded-blocking-call"
    description = (
        "An Arrow Flight client call (do_get/do_put/do_action) without "
        "explicit call `options`, or urlopen/socket.create_connection "
        "without a `timeout`, waits on the gRPC/socket default — "
        "i.e. forever against a blackholed peer. Every blocking call "
        "carries an explicit deadline decision at the call site "
        "(sched/deadline.call_timeout for query-path calls); "
        "intentionally unbounded long-lived streams suppress with a "
        "justification."
    )

    @staticmethod
    def _has_kw(node: ast.Call, name: str) -> bool:
        return any(kw.arg == name for kw in node.keywords)

    def visit_Call(self, node: ast.Call, ctx: FileContext):
        if not isinstance(node.func, ast.Attribute):
            # bare urlopen(...) from `from urllib.request import
            # urlopen` still needs the timeout (keyword OR positional:
            # urlopen(url, data, timeout) / create_connection(addr,
            # timeout) — same shapes the attribute branch accepts)
            if (isinstance(node.func, ast.Name)
                    and node.func.id in _TIMEOUT_KW_CALLS):
                pos_ok = (len(node.args) >= 3
                          if node.func.id == "urlopen"
                          else len(node.args) >= 2)
                if not pos_ok and not self._has_kw(node, "timeout"):
                    ctx.report(self, node,
                               f"{node.func.id}(...) without timeout= "
                               "blocks forever against a blackholed "
                               "peer; pass an explicit timeout")
            return
        attr = node.func.attr
        if attr in _FLIGHT_CLIENT_CALLS:
            # server-side handler plumbing (self._do_action and co.)
            # is not a Flight client call; the client calls go through
            # a connection object, never self/cls
            base = dotted_name(node.func.value)
            if base in ("self", "cls"):
                return
            if not self._has_kw(node, "options"):
                ctx.report(self, node,
                           f".{attr}(...) without explicit call "
                           "options carries no deadline — a "
                           "blackholed peer hangs the caller; pass "
                           "options=FlightCallOptions(timeout=...) "
                           "(None only as an explicit decision)")
        elif attr in _TIMEOUT_KW_CALLS:
            # positional timeout: urlopen(url, data, timeout) /
            # socket.create_connection(addr, timeout)
            pos_ok = (len(node.args) >= 3 if attr == "urlopen"
                      else len(node.args) >= 2)
            if not pos_ok and not self._has_kw(node, "timeout"):
                ctx.report(self, node,
                           f"{attr}(...) without timeout= blocks "
                           "forever against a blackholed peer; pass "
                           "an explicit timeout")


_MUTABLE_CTORS = {"list", "dict", "set"}


# collective -> index of its axis-name argument
_COLLECTIVES = {
    "psum": 1, "pmin": 1, "pmax": 1, "pmean": 1, "all_gather": 1,
    "ppermute": 1, "psum_scatter": 1, "all_to_all": 1, "axis_index": 0,
}


@register
class UnboundCollectiveAxis(Rule):
    id = "GT013"
    name = "unbound-collective-axis"
    description = (
        "A collective (psum/pmin/pmax/all_gather/...) inside a "
        "shard_map body references an axis name the enclosing "
        "shard_map call does not bind: it fails at trace time with an "
        "unbound-axis error, or silently reduces over the wrong axis "
        "when an outer mesh happens to share the name."
    )

    def visit_Call(self, node: ast.Call, ctx: FileContext):
        f = dotted_name(node.func)
        if not f:
            return
        short = f.split(".")[-1]
        pos = _COLLECTIVES.get(short)
        if pos is None:
            return
        # innermost enclosing shard_map kernel with a known binding
        bound = None
        for fi in reversed(ctx.func_stack):
            axes = ctx.shard_map_axes.get((fi.name, fi.node.lineno))
            if axes:
                bound = axes
                break
        if not bound:
            return
        axis_node = None
        if len(node.args) > pos:
            axis_node = node.args[pos]
        else:
            for kw in node.keywords:
                if kw.arg == "axis_name":
                    axis_node = kw.value
        if axis_node is None:
            return
        axis = ctx.axis_name_of(axis_node)
        if axis is None or axis in bound:
            return
        # only compare within one resolution space: an unresolved
        # identifier could still equal a literal axis name (and vice
        # versa), so mixed comparisons stay silent
        if axis.startswith("id:"):
            if not all(a.startswith("id:") for a in bound):
                return
        elif any(a.startswith("id:") for a in bound):
            return
        shown = sorted(a.removeprefix("id:") for a in bound)
        ctx.report(self, node,
                   f"collective {short}(...) references axis "
                   f"{axis.removeprefix('id:')!r} not bound by the "
                   f"enclosing shard_map (binds: {', '.join(shown)})")


# telemetry surfaces whose invocation inside a traced (device) scope
# is a hazard: span context managers allocate + touch contextvars and
# the ring lock; metric/stat calls take locks and read wall clocks.
# Inside jit/shard_map these either burn host work on every trace, or
# capture a Python-side value and silently stop updating after the
# first compilation — and any traced-value argument forces a host sync.
_STATS_MODULES = {"stats", "qstats"}
_STATS_FUNCS = {"add", "note", "timed"}
# mutating methods only: flagging .labels() too would double-report
# the idiomatic _METRIC.labels(x).inc(1) chain
_METRIC_METHODS = {"inc", "dec", "observe", "set"}


def _is_metric_constant(node: ast.AST) -> bool:
    """Module-level metric objects follow the ALL_CAPS constant idiom
    (`_STAGE_MS.labels(...).inc(...)`, `_REQS.inc()`)."""
    while isinstance(node, ast.Call):
        node = node.func
    if isinstance(node, ast.Attribute):
        node = node.value
        while isinstance(node, (ast.Attribute, ast.Call)):
            node = (node.value if isinstance(node, ast.Attribute)
                    else node.func)
    if not isinstance(node, ast.Name):
        return False
    name = node.id.lstrip("_")
    return bool(name) and name.isupper()


@register
class TelemetryInDeviceScope(Rule):
    id = "GT014"
    name = "telemetry-in-device-scope"
    description = (
        "A tracing span or metrics/stats call inside a jit/shard_map/"
        "Pallas device scope is a host-sync and recompile hazard: the "
        "call runs at TRACE time (so it fires once per compilation, "
        "not once per execution — metrics silently freeze), touches "
        "locks/contextvars on the host, and any traced-value argument "
        "forces a device->host transfer. Wrap the CALL boundary from "
        "host scope instead (telemetry/device_trace.py)."
    )

    def _report(self, node, ctx: FileContext, what: str):
        ctx.report(self, node,
                   f"{what} inside a jitted/device function runs at "
                   "trace time, not execution time; move the "
                   "span/metric to the host-side call boundary "
                   "(telemetry/device_trace.py)")

    def visit_Call(self, node: ast.Call, ctx: FileContext):
        if ctx.device_func is None:
            return
        f = dotted_name(node.func)
        if f:
            parts = f.split(".")
            if any(seg == "tracing" for seg in parts[:-1]) or (
                    len(parts) >= 2
                    and parts[-2] in ("tracing", "device_trace")):
                self._report(node, ctx, f"tracing call {f}(...)")
                return
            if f in ("span", "start_remote", "child_span",
                     "event_span", "device_call"):
                # bare-name telemetry entry points (from-imports)
                self._report(node, ctx, f"tracing call {f}(...)")
                return
            if (len(parts) == 2 and parts[0] in _STATS_MODULES
                    and parts[1] in _STATS_FUNCS):
                self._report(node, ctx, f"stats call {f}(...)")
                return
            if "global_registry" in parts:
                self._report(node, ctx, f"metrics call {f}(...)")
                return
        # metric-object method calls: _COUNTER.labels(x).inc(1) — the
        # receiver is a module-level ALL_CAPS metric constant
        if (isinstance(node.func, ast.Attribute)
                and node.func.attr in _METRIC_METHODS
                and _is_metric_constant(node.func)):
            self._report(
                node, ctx,
                f".{node.func.attr}() on a module-level metric"
            )


_READBACK_CALLS = {"np.asarray", "numpy.asarray", "onp.asarray",
                   "jax.device_get"}


@register
class FullBufferReadback(Rule):
    id = "GT015"
    name = "full-buffer-readback"
    description = (
        "np.asarray()/jax.device_get() on a device result buffer (a "
        "name this function called .block_until_ready() on, or waited "
        "for through a device_call's `d.wait(name)`) reads the "
        "WHOLE buffer back in one device->host transfer, "
        "unattributed. Route result readbacks through "
        "query/readback.read_full (bytes land on "
        "gtpu_readback_bytes_total) or read_delta (a since-cursor poll "
        "slices device-side and ships only the unseen rows), inside a "
        "device_call as `d.read(readback.read_full, name)`."
    )

    @staticmethod
    def _scan_blocked(scope, *, skip_nested: bool) -> set[str]:
        """Names `X` with an `X.block_until_ready()` call in `scope`'s
        own statements, or passed to the `.wait(...)` of a handle bound
        by `with device_call(...) as d` — the device-result-buffer
        idioms."""
        names: set[str] = set()
        waited: list[tuple[str, list[str]]] = []
        handles: set[str] = set()
        stack = list(ast.iter_child_nodes(scope))
        while stack:
            node = stack.pop()
            if skip_nested and isinstance(node, (ast.FunctionDef,
                                                 ast.AsyncFunctionDef,
                                                 ast.Lambda)):
                continue
            if isinstance(node, (ast.With, ast.AsyncWith)):
                handles.update(
                    item.optional_vars.id for item in node.items
                    if _looks_like_device_call(item.context_expr)
                    and isinstance(item.optional_vars, ast.Name)
                )
            if (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Attribute)
                    and isinstance(node.func.value, ast.Name)):
                if node.func.attr == "block_until_ready":
                    names.add(node.func.value.id)
                elif node.func.attr == "wait":
                    waited.append((node.func.value.id, [
                        a.id for a in node.args
                        if isinstance(a, ast.Name)
                    ]))
            stack.extend(ast.iter_child_nodes(node))
        for handle, args in waited:
            if handle in handles:
                names.update(args)
        return names

    def _blocked_names(self, ctx: FileContext) -> set[str]:
        cache = getattr(ctx, "_gt015_scopes", None)
        if cache is None:
            cache = ctx._gt015_scopes = {}
        fi = ctx.current_func
        if fi is None:
            if "module" not in cache:
                cache["module"] = self._scan_blocked(ctx.tree,
                                                     skip_nested=True)
            return cache["module"]
        key = id(fi.node)
        if key not in cache:
            cache[key] = self._scan_blocked(fi.node, skip_nested=True)
        return cache[key]

    def visit_Call(self, node: ast.Call, ctx: FileContext):
        if ctx.path.replace("\\", "/").endswith("query/readback.py"):
            return  # the helpers ARE the blessed readback point
        d = dotted_name(node.func)
        if d not in _READBACK_CALLS or not node.args:
            return
        arg = node.args[0]
        if not isinstance(arg, ast.Name):
            return
        if arg.id in self._blocked_names(ctx):
            ctx.report(self, node,
                       f"{d}({arg.id}) reads the whole device buffer "
                       "back unattributed; use query/readback."
                       "read_full (or read_delta for a since-cursor "
                       "slice) so the bytes land on "
                       "gtpu_readback_bytes_total")


# byte-budget attribute/value tokens: the LRU-with-byte-budget idiom
# assigns self.max_bytes / self.byte_budget / self.capacity =
# capacity_bytes / ... in __init__. Entry-count-only containers
# (capacity without "byte" anywhere) are not byte pools.
_GT016_BUDGET_TOKENS = ("max_bytes", "byte_budget", "budget_bytes",
                        "capacity_bytes", "hbm_bytes")
_GT016_DEVICE_PUTS = ("device_put", "asarray")


@register
class UnregisteredMemoryPool(Rule):
    id = "GT016"
    name = "unregistered-memory-pool"
    description = (
        "A byte-budgeted container (a class assigning a byte budget "
        "AND an entries dict, or a module-level dict cache holding "
        "device arrays) that never registers with the process-wide "
        "memory accountant (telemetry/memory.py register_pool) is an "
        "invisible memory pool: its bytes appear in no unified "
        "surface, the device census reads its buffers as leaks, and "
        "the global [memory] device_budget_bytes watermark cannot "
        "evict from it."
    )

    @staticmethod
    def _is_exempt(ctx: FileContext) -> bool:
        # the accountant itself is not a pool
        return ctx.path.replace("\\", "/").endswith(
            "telemetry/memory.py"
        )

    @staticmethod
    def _self_attr_target(node):
        """The attribute name of a `self.X = ...` / `self.X: T = ...`
        assignment, else None."""
        if isinstance(node, ast.Assign) and len(node.targets) == 1:
            tgt = node.targets[0]
        elif isinstance(node, ast.AnnAssign) and node.value is not None:
            tgt = node.target
        else:
            return None
        if (isinstance(tgt, ast.Attribute)
                and isinstance(tgt.value, ast.Name)
                and tgt.value.id == "self"):
            return tgt.attr
        return None

    @staticmethod
    def _is_dict_value(node) -> bool:
        value = (node.value if isinstance(node, (ast.Assign,
                                                 ast.AnnAssign))
                 else None)
        if isinstance(value, ast.Dict):
            return True
        if isinstance(value, ast.Call):
            f = dotted_name(value.func)
            return f is not None and f.split(".")[-1] in (
                "dict", "OrderedDict"
            )
        return False

    def _budget_assign(self, node) -> bool:
        attr = self._self_attr_target(node)
        if attr is None:
            return False
        low = attr.lstrip("_").lower()
        if any(tok in low for tok in _GT016_BUDGET_TOKENS):
            return True
        value = node.value
        return any(
            isinstance(n, ast.Name)
            and any(tok in n.id.lower() for tok in _GT016_BUDGET_TOKENS)
            for n in ast.walk(value)
        )

    def visit_ClassDef(self, node: ast.ClassDef, ctx: FileContext):
        if self._is_exempt(ctx):
            return
        has_budget = False
        has_container = False
        registers = False
        for sub in ast.walk(node):
            if isinstance(sub, ast.Call):
                f = dotted_name(sub.func)
                if f and f.split(".")[-1] == "register_pool":
                    registers = True
                    break
            if isinstance(sub, (ast.Assign, ast.AnnAssign)):
                if self._self_attr_target(sub) is not None:
                    if self._is_dict_value(sub):
                        has_container = True
                    if self._budget_assign(sub):
                        has_budget = True
        if has_budget and has_container and not registers:
            ctx.report(self, node,
                       f"class {node.name} holds a byte-budgeted "
                       "entries container but never calls "
                       "memory.register_pool(); register it so its "
                       "bytes land on gtpu_mem_* and the device "
                       "census/global watermark can see it")

    def visit_Module(self, node: ast.Module, ctx: FileContext):
        """Module-level dict caches holding device arrays: a
        `_GRIDS = {}` that gets `_GRIDS[k] = jax.device_put(...)` /
        `jnp.asarray(...)` somewhere in the module pins HBM outside
        any class — the accountant must know about it too."""
        if self._is_exempt(ctx):
            return
        module_dicts: set[str] = set()
        for stmt in node.body:
            name = None
            if (isinstance(stmt, ast.Assign) and len(stmt.targets) == 1
                    and isinstance(stmt.targets[0], ast.Name)):
                name = stmt.targets[0].id
            elif (isinstance(stmt, ast.AnnAssign)
                    and isinstance(stmt.target, ast.Name)
                    and stmt.value is not None):
                name = stmt.target.id
            if name is not None and self._is_dict_value(stmt):
                module_dicts.add(name)
        if not module_dicts:
            return
        registers = any(
            isinstance(sub, ast.Call)
            and (dotted_name(sub.func) or "").split(".")[-1]
            == "register_pool"
            for sub in ast.walk(node)
        )
        if registers:
            return
        for sub in ast.walk(node):
            if not (isinstance(sub, ast.Assign)
                    and len(sub.targets) == 1
                    and isinstance(sub.targets[0], ast.Subscript)):
                continue
            base = sub.targets[0].value
            if not (isinstance(base, ast.Name)
                    and base.id in module_dicts):
                continue
            holds_device = any(
                isinstance(n, ast.Call)
                and (dotted_name(n.func) or "").split(".")[-1]
                in _GT016_DEVICE_PUTS
                and (dotted_name(n.func) or "").split(".")[0]
                in ("jax", "jnp")
                for n in ast.walk(sub.value)
            )
            if holds_device:
                ctx.report(self, sub,
                           f"module-level dict {base.id} caches device "
                           "arrays but the module never calls "
                           "memory.register_pool(); the census reads "
                           "these buffers as unaccounted leaks")
                return


@register
class MutableDefaultArg(Rule):
    id = "GT010"
    name = "mutable-default-arg"
    description = (
        "A mutable default ([], {}, set()) is shared across every "
        "call of a public function — state leaks between callers. "
        "Default to None and create inside."
    )

    def _check(self, node, ctx: FileContext):
        if node.name.startswith("_"):
            return
        defaults = list(node.args.defaults) + [
            d for d in node.args.kw_defaults if d is not None
        ]
        for d in defaults:
            if isinstance(d, (ast.List, ast.Dict, ast.Set)):
                ctx.report(self, d,
                           f"mutable default argument in public "
                           f"function {node.name}(); use None")
            elif (isinstance(d, ast.Call)
                  and isinstance(d.func, ast.Name)
                  and d.func.id in _MUTABLE_CTORS and not d.args
                  and not d.keywords):
                ctx.report(self, d,
                           f"mutable default argument in public "
                           f"function {node.name}(); use None")

    visit_FunctionDef = _check
    visit_AsyncFunctionDef = _check


# metric-registration receivers GT017 inspects: the in-process
# prometheus registries (global_registry / a local `registry` /
# `self._registry` handle). Unrelated `.counter(...)` methods on other
# objects stay silent.
_GT017_KINDS = ("counter", "gauge", "histogram")
_GT017_TIME_TOKENS = ("seconds", "duration", "latency", "_time",
                      "elapsed", "_ms")


@register
class UntrackedDeviceDispatch(Rule):
    id = "GT018"
    name = "untracked-device-dispatch"
    description = (
        "Calling a jit/shard_map-produced callable outside a "
        "`device_call` scope dispatches an XLA program the device "
        "profiler (telemetry/device_programs.py) cannot see: no "
        "compile/execute attribution, no registry row, no roofline "
        "verdict. Dispatch through "
        "`with device_trace.device_call(site, key=...) as d: "
        "d.run(fn, ...)` instead. Calls INSIDE jit/shard_map/Pallas "
        "scope are inlining (tracing), not dispatches, and stay "
        "silent; so do callables the walker cannot prove jit-produced "
        "(builder-returned programs), which the registry still counts "
        "at their device_call site."
    )

    def visit_Call(self, node: ast.Call, ctx: FileContext):
        if not isinstance(node.func, ast.Name):
            return
        if node.func.id not in ctx.jit_callables:
            return
        if ctx.device_func is not None:
            return  # traced scope: inlined into the enclosing program
        if ctx.device_call_depth > 0:
            return  # tracked dispatch
        ctx.report(self, node,
                   f"jit-produced callable {node.func.id}() dispatched "
                   "outside a device_call scope — the device profiler "
                   "cannot attribute it; wrap the dispatch in `with "
                   "device_trace.device_call(site, key=...) as d: "
                   "d.run(...)`")


@register
class MetricNamingConvention(Rule):
    id = "GT017"
    name = "metric-naming-convention"
    description = (
        "Prometheus naming conventions keep the exported surface "
        "machine-readable: counter names end `_total`, a histogram "
        "measuring time carries its unit suffix (`_seconds` or `_ms`, "
        "matching what it observes), and label names are lowercase "
        "(dashboards and the self-export reingest key on exact label "
        "names)."
    )

    @staticmethod
    def _registry_receiver(node: ast.Call) -> bool:
        f = dotted_name(node.func)
        if f is None:
            return False
        parts = f.split(".")
        if parts[-1] not in _GT017_KINDS or len(parts) < 2:
            return False
        recv = parts[-2].lstrip("_").lower()
        return recv == "registry" or recv.endswith("registry")

    @staticmethod
    def _literal(node) -> str | None:
        if isinstance(node, ast.Constant) and isinstance(node.value, str):
            return node.value
        return None

    def visit_Call(self, node: ast.Call, ctx: FileContext):
        if not self._registry_receiver(node):
            return
        kind = dotted_name(node.func).split(".")[-1]
        name = self._literal(node.args[0]) if node.args else None
        if name is not None:
            if kind == "counter" and not name.endswith("_total"):
                ctx.report(self, node,
                           f"counter {name!r} must end in '_total' "
                           "(prometheus counter naming convention)")
            if kind == "histogram":
                low = name.lower()
                timeish = any(t in low for t in _GT017_TIME_TOKENS)
                if timeish and not (low.endswith("_seconds")
                                    or low.endswith("_ms")):
                    ctx.report(self, node,
                               f"time histogram {name!r} must carry "
                               "its unit suffix ('_seconds' or '_ms' "
                               "matching the observed unit)")
        # label names: the `labels=` keyword (or third positional arg)
        labels_node = None
        for kw in node.keywords:
            if kw.arg == "labels":
                labels_node = kw.value
        if labels_node is None and len(node.args) >= 3:
            labels_node = node.args[2]
        if isinstance(labels_node, (ast.Tuple, ast.List)):
            for el in labels_node.elts:
                lab = self._literal(el)
                if lab is not None and lab != lab.lower():
                    ctx.report(self, el,
                               f"label name {lab!r} must be lowercase "
                               "(exported label names are part of the "
                               "query surface)")


# scrape/heartbeat-path entry points GT019 guards: callbacks handed to
# a metrics registry's register_collector (run on EVERY /metrics
# render), the stats/buffers/evict hooks registered with the memory
# accountant (same scrape path), and the heartbeat-payload builder
# contract (telemetry/node_stats.build_node_stats rides every metasrv
# heartbeat).
_GT019_BUILDER_NAMES = {"build_node_stats"}


@register
class UnboundedScrapePathIO(Rule):
    id = "GT019"
    name = "unbounded-io-in-scrape-path"
    description = (
        "Blocking network I/O without an explicit bound inside a "
        "registered MetricsRegistry collector hook or a heartbeat-"
        "payload builder: collectors run on every /metrics render and "
        "the payload builder rides every metasrv heartbeat, so one "
        "hung peer would stall every scrape/heartbeat of this node — "
        "exactly the liveness channel that must never hang. Pass an "
        "explicit timeout/options bound, or move the I/O off the "
        "scrape path entirely (cache it from a background task)."
    )

    def _hooks(self, ctx: FileContext) -> set[str]:
        """Names of this file's scrape-path functions: anything handed
        to <registry>.register_collector(...), the named stats/evict/
        buffers callbacks of a register_pool(...) call, and the
        heartbeat-payload builder names."""
        cache = getattr(ctx, "_gt019_hooks", None)
        if cache is not None:
            return cache
        hooks = set(_GT019_BUILDER_NAMES)
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.Call):
                continue
            f = dotted_name(node.func)
            if f is None:
                continue
            short = f.split(".")[-1]
            if short == "register_collector" and node.args:
                a = node.args[0]
                if isinstance(a, ast.Name):
                    hooks.add(a.id)
            elif short == "register_pool":
                for kw in node.keywords:
                    if (kw.arg in ("stats", "buffers", "evict")
                            and isinstance(kw.value, ast.Name)):
                        hooks.add(kw.value.id)
        ctx._gt019_hooks = hooks
        return hooks

    @staticmethod
    def _has_kw(node: ast.Call, name: str) -> bool:
        return any(kw.arg == name for kw in node.keywords)

    def visit_Call(self, node: ast.Call, ctx: FileContext):
        if not ctx.func_stack:
            return
        hooks = self._hooks(ctx)
        # nested defs inside a hook are still on the scrape path
        if not any(fi.name in hooks for fi in ctx.func_stack):
            return
        if isinstance(node.func, ast.Attribute):
            attr = node.func.attr
        elif isinstance(node.func, ast.Name):
            attr = node.func.id
        else:
            return
        if attr in _FLIGHT_CLIENT_CALLS:
            # NO self/cls exemption here: inside a collector even an
            # internally-dispatched Flight call is wire I/O riding the
            # scrape path
            if not self._has_kw(node, "options"):
                ctx.report(self, node,
                           f".{attr}(...) inside a scrape/heartbeat "
                           "hook without explicit call options — a "
                           "hung peer stalls every scrape of this "
                           "node; pass options=FlightCallOptions("
                           "timeout=...) or move the I/O off the "
                           "scrape path")
        elif attr in _TIMEOUT_KW_CALLS:
            pos_ok = (len(node.args) >= 3 if attr == "urlopen"
                      else len(node.args) >= 2)
            if not pos_ok and not self._has_kw(node, "timeout"):
                ctx.report(self, node,
                           f"{attr}(...) inside a scrape/heartbeat "
                           "hook without a timeout — a hung peer "
                           "stalls every scrape/heartbeat of this "
                           "node; pass an explicit timeout")
        elif attr == "HTTPConnection":
            if not self._has_kw(node, "timeout"):
                ctx.report(self, node,
                           "HTTPConnection(...) inside a scrape/"
                           "heartbeat hook without a timeout — "
                           "requests on it block forever against a "
                           "blackholed peer; pass timeout=")


# runtime-mutable knob attributes GT021 guards (the standard knob set
# autotune/knobs.build_registry registers). The sanctioned writers:
# the autotune package (the registry's apply closures), the owning
# object's own methods (root `self`/`cls` — set_max_bytes and friends
# mutate their own field), and process-start config appliers
# (configure/from_options/__init__). GT020 is reserved.
_GT021_KNOB_ATTRS = {
    "max_concurrency", "shard_min_series", "shard_min_rows",
    "max_bytes", "workers", "l1_trigger_files", "l2_trigger_files",
}
_GT021_EXEMPT_FUNCS = {"__init__", "configure", "from_options",
                       "reset_for_tests"}


@register
class DirectKnobWrite(Rule):
    id = "GT021"
    name = "direct-knob-write"
    description = (
        "Direct assignment to a registered runtime-mutable knob "
        "attribute outside the owning object / the autotune package. "
        "Every runtime knob change must ride KnobRegistry.set (the "
        "autotune actuators and ADMIN set_config both do) so the "
        "bounds are validated, the change lands in the "
        "information_schema.autotune_decisions audit log, and the "
        "control loop stays the SINGLE writer — a second ad-hoc "
        "writer and a controller would silently fight over the knob."
    )

    def _flag(self, target: ast.expr, ctx: FileContext):
        if not isinstance(target, ast.Attribute):
            return
        if target.attr not in _GT021_KNOB_ATTRS:
            return
        root = target.value
        while isinstance(root, ast.Attribute):
            root = root.value
        if isinstance(root, ast.Name) and root.id in ("self", "cls"):
            return  # the owning object mutating its own field
        path = ctx.path.replace("\\", "/")
        if "/autotune/" in path or path.startswith("autotune/"):
            return  # the registry's apply closures ARE the write path
        if any(fi.name in _GT021_EXEMPT_FUNCS
               for fi in ctx.func_stack):
            return  # process-start config applier
        ctx.report(self, target,
                   f"direct write to runtime-mutable knob attribute "
                   f"`.{target.attr}`; route it through "
                   f"KnobRegistry.set (ADMIN set_config / the "
                   f"autotune actuators) so bounds are validated and "
                   f"the change is audited")

    def visit_Assign(self, node: ast.Assign, ctx: FileContext):
        for t in node.targets:
            if isinstance(t, ast.Tuple):
                for e in t.elts:
                    self._flag(e, ctx)
            else:
                self._flag(t, ctx)

    def visit_AugAssign(self, node: ast.AugAssign, ctx: FileContext):
        self._flag(node.target, ctx)


@register
class PallasCallHygiene(Rule):
    id = "GT022"
    name = "pallas-call-hygiene"
    description = (
        "Pallas kernel dispatch hygiene. Every pallas_call must thread "
        "`interpret=` from the kernels config (interpret_mode() or a "
        "parameter): a hard-coded literal either pins the slow "
        "interpreter onto real TPUs (True) or breaks the CPU twin the "
        "CI runs on (False, or the keyword missing entirely). And a "
        "make_async_remote_copy whose device_id names a mesh axis the "
        "enclosing shard_map does not bind fails at trace time or "
        "RDMAs around the wrong ring — the same unbound-axis hazard "
        "GT013 guards for collectives. (Kernel bodies themselves are "
        "already device scope: GT004/GT014 apply inside them.)"
    )

    def visit_Call(self, node: ast.Call, ctx: FileContext):
        f = dotted_name(node.func)
        if not f:
            return
        short = f.split(".")[-1]
        if short == "pallas_call":
            self._check_interpret(node, ctx)
        elif short == "make_async_remote_copy":
            self._check_device_id(node, ctx)

    def _check_interpret(self, node: ast.Call, ctx: FileContext):
        kw = None
        for k in node.keywords:
            if k.arg == "interpret":
                kw = k
        if kw is None:
            if any(k.arg is None for k in node.keywords):
                return  # a **kwargs splat may carry interpret=
            ctx.report(self, node,
                       "pallas_call without `interpret=` — thread it "
                       "from the kernels config (interpret_mode() or a "
                       "parameter); without it the CPU interpret twin "
                       "can never run this kernel")
        elif (isinstance(kw.value, ast.Constant)
                and isinstance(kw.value.value, bool)):
            ctx.report(self, node,
                       f"pallas_call with hard-coded interpret="
                       f"{kw.value.value} — thread it from the kernels "
                       "config (interpret_mode() or a parameter) so one "
                       "call site serves both the CPU interpret twin "
                       "and the native Mosaic path")

    def _check_device_id(self, node: ast.Call, ctx: FileContext):
        dev = None
        for k in node.keywords:
            if k.arg == "device_id":
                dev = k.value
        if dev is None:
            return
        # innermost enclosing shard_map kernel with a known binding
        # (same anchoring as GT013)
        bound = None
        for fi in reversed(ctx.func_stack):
            axes = ctx.shard_map_axes.get((fi.name, fi.node.lineno))
            if axes:
                bound = axes
                break
        if not bound:
            return
        # axis-name candidates inside the device_id expression. The
        # mesh-keyed form carries axis names as string literals (or
        # module constants resolving to them); axis_index(...) subtrees
        # are GT013's domain (it flags the call itself) and unresolved
        # bare identifiers are device-index arithmetic (`right`, `my`),
        # not axis names — both stay out of the candidate set.
        skip: set[int] = set()
        for n in ast.walk(dev):
            if isinstance(n, ast.Call):
                d = dotted_name(n.func)
                if d is not None and d.split(".")[-1] == "axis_index":
                    skip.update(id(c) for c in ast.walk(n))
                else:
                    skip.update(id(c) for c in ast.walk(n.func))
        if any(a.startswith("id:") for a in bound):
            return  # unresolved binding side: can't compare literals
        for n in ast.walk(dev):
            if id(n) in skip:
                continue
            axis = ctx.axis_name_of(n)
            if axis is None or axis in bound or axis.startswith("id:"):
                continue
            shown = sorted(bound)
            ctx.report(self, node,
                       f"make_async_remote_copy device_id references "
                       f"axis {axis!r} not bound by the enclosing "
                       f"shard_map (binds: {', '.join(shown)})")


# Registry label-plane accessors whose full-column results a matcher
# predicate must never compare directly (GT033). Gathers through them
# (decode, subscript-by-sid) are fine — only boolean verdicts over the
# whole column re-create the O(total series) scan the secondary index
# exists to kill.
_GT033_PLANE_FUNCS = {"tag_values", "codes_matrix"}
_GT033_CMP_CALLS = {"equal", "not_equal", "isin", "in1d"}


def _gt033_exempt_path(path: str) -> bool:
    p = path.replace("\\", "/")
    return ("/index/" in p or p.startswith("index/")
            or p.endswith("storage/series.py"))


def _gt033_plane_root(node: ast.AST, tracked: set[str]) -> str | None:
    """'tag_values' / 'codes_matrix' / a tracked local name when the
    expression (through any Subscript chain) roots at a label-plane
    call or a local bound to one; else None."""
    while isinstance(node, ast.Subscript):
        node = node.value
    if isinstance(node, ast.Call):
        d = dotted_name(node.func)
        if d is not None and d.split(".")[-1] in _GT033_PLANE_FUNCS:
            return d.split(".")[-1]
        return None
    if isinstance(node, ast.Name) and node.id in tracked:
        return node.id
    return None


@register
class FullLabelPlanePredicate(Rule):
    id = "GT033"
    name = "full-label-plane-predicate"
    description = (
        "A boolean compare over a series-registry label column "
        "(`tag_values()` / `codes_matrix()` results) outside the "
        "index package re-creates the O(total series) linear match "
        "the secondary tag index exists to kill: every evaluation "
        "pays the full plane even when postings answer it in O(1). "
        "Route matchers through index.match_sids / index.match_mask "
        "(posting lookups for eq/in, dictionary-domain evaluation "
        "for re/ne). Gathers — decoding values for matched sids, "
        "subscripting by a sid set — are fine; only whole-column "
        "predicates fire."
    )

    def _scopes(self, tree: ast.Module):
        """(scope node, statements owned by it) pairs: module body plus
        each def, with nested defs excluded from their enclosing
        scope's statement set (their locals shadow)."""
        defs = [n for n in ast.walk(tree)
                if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))]
        out = []
        for scope in [tree] + defs:
            owned = []
            stack = list(scope.body)
            while stack:
                n = stack.pop()
                owned.append(n)
                for child in ast.iter_child_nodes(n):
                    if isinstance(child, (ast.FunctionDef,
                                          ast.AsyncFunctionDef)):
                        continue
                    stack.append(child)
            out.append((scope, owned))
        return out

    def visit_Module(self, node: ast.Module, ctx: FileContext):
        if _gt033_exempt_path(ctx.path):
            return
        for _scope, owned in self._scopes(node):
            # names bound ONLY from label-plane calls in this scope; a
            # name also assigned from anything else is not tracked (it
            # may no longer hold the plane at the compare)
            tracked: set[str] = set()
            dirty: set[str] = set()
            for n in owned:
                if not (isinstance(n, ast.Assign)
                        and len(n.targets) == 1
                        and isinstance(n.targets[0], ast.Name)):
                    continue
                name = n.targets[0].id
                if _gt033_plane_root(n.value, set()) is not None:
                    tracked.add(name)
                else:
                    dirty.add(name)
            tracked -= dirty
            for n in owned:
                if isinstance(n, ast.Compare):
                    if not all(isinstance(op, (ast.Eq, ast.NotEq,
                                               ast.In, ast.NotIn))
                               for op in n.ops):
                        continue
                    sides = [n.left] + list(n.comparators)
                elif (isinstance(n, ast.Call)
                        and (dotted_name(n.func) or "").split(".")[-1]
                        in _GT033_CMP_CALLS):
                    sides = list(n.args)
                else:
                    continue
                for side in sides:
                    root = _gt033_plane_root(side, tracked)
                    if root is None:
                        continue
                    ctx.report(self, n,
                               f"boolean predicate over the full "
                               f"label plane (via {root!r}) — "
                               "O(total series) per evaluation; "
                               "route the matcher through "
                               "index.match_sids / index.match_mask "
                               "(postings + dictionary-domain "
                               "evaluation)")
                    break


# ----------------------------------------------------------------------
# --explain examples
# ----------------------------------------------------------------------
# Minimal firing / clean snippet pairs for `lint --explain GTxxx`,
# attached here so each rule body above stays focused on detection
# logic. The explain meta-test lints every pair under a per-rule
# select: the positive snippet must fire exactly that rule, the
# negative must stay silent.

_EXAMPLES = {
    "GT001": ('''\
try:
    x = 1
except Exception:
    pass
''', '''\
import logging
try:
    x = 1
except Exception as e:
    logging.getLogger("x").warning("boom: %s", e)
'''),
    "GT002": ('''\
def classify(e):
    return "unavailable" in str(e).lower()
''', '''\
def classify(e):
    return isinstance(e, ConnectionError)
'''),
    "GT003": ('''\
def f():
    raise Exception("boom")
''', '''\
def f():
    raise ValueError("bad arg")
'''),
    "GT004": ('''\
import jax

@jax.jit
def f(x):
    return x.item()
''', '''\
import numpy as np

def f(x):
    return float(x) + np.asarray(x).sum()
'''),
    "GT005": ('''\
import jax

@jax.jit
def f(x):
    if x > 0:
        return x
    return -x
''', '''\
import jax

@jax.jit
def f(x):
    if x.ndim == 2:
        x = x.sum(axis=1)
    return x
'''),
    "GT006": ('''\
import jax

def step(fns, x):
    for f in fns:
        x = jax.jit(f)(x)
    return x
''', '''\
import jax

def _impl(x):
    return x + 1

fast = jax.jit(_impl)
'''),
    "GT007": ('''\
import threading
import urllib.request

lock = threading.Lock()

def f():
    with lock:
        urllib.request.urlopen("http://x", timeout=5.0)
''', '''\
import threading
import urllib.request

lock = threading.Lock()

def f():
    with lock:
        snapshot = 1
    urllib.request.urlopen("http://x", timeout=5.0)
    return snapshot
'''),
    "GT008": ('''\
import threading

def fire(target):
    threading.Thread(target=target).start()
''', '''\
import threading

def ok(target):
    t = threading.Thread(target=target)
    t.start()
    t.join()
'''),
    "GT009": ('''\
import jax.numpy as jnp

def f(x):
    return jnp.asarray(x, jnp.int64)
''', '''\
import jax.numpy as jnp
import numpy as np

def f(x):
    return np.asarray(x, np.int64), jnp.asarray(x, jnp.int32)
'''),
    "GT010": ('''\
def public(a, xs=[]):
    return xs
''', '''\
def public(a, xs=None, t=()):
    return xs or t
'''),
    "GT011": ('''\
import time

def f(start):
    return time.time() - start
''', '''\
import time

def f(start):
    return time.monotonic() - start
'''),
    "GT012": ('''\
import urllib.request

def fetch(url):
    return urllib.request.urlopen(url).read()
''', '''\
import urllib.request

def fetch(url):
    return urllib.request.urlopen(url, timeout=5.0).read()
'''),
    "GT013": ('''\
import jax
from jax import shard_map
from jax.sharding import PartitionSpec as P

def run(mesh, x):
    def local(x):
        return jax.lax.psum(x, "time")

    return shard_map(local, mesh=mesh, in_specs=(P("shard"),),
                     out_specs=P())(x)
''', '''\
import jax
from jax import shard_map
from jax.sharding import PartitionSpec as P

def run(mesh, x):
    def local(x):
        return jax.lax.psum(x, "shard")

    return shard_map(local, mesh=mesh, in_specs=(P("shard"),),
                     out_specs=P())(x)
'''),
    "GT014": ('''\
import jax
from greptimedb_tpu.telemetry import tracing

@jax.jit
def kernel(x):
    with tracing.span("device.step"):
        return x + 1
''', '''\
import jax
from greptimedb_tpu.telemetry import tracing

@jax.jit
def kernel(x):
    return x + 1

def host(x):
    with tracing.span("device.execute"):
        return kernel(x)
'''),
    "GT015": ('''\
import numpy as np

def run(program, arrs):
    out = program(arrs)
    out.block_until_ready()
    return np.asarray(out)
''', '''\
from greptimedb_tpu.query import readback
from greptimedb_tpu.telemetry import device_trace

def run(program, arrs, j0):
    with device_trace.device_call("site", key=("k",)) as d:
        out = d.run(program, arrs)
        d.wait(out)
        return d.read(readback.read_delta, out, j0, axis=-1)
'''),
    "GT016": ('''\
from collections import OrderedDict

class GridCache:
    def __init__(self, max_bytes):
        self.max_bytes = int(max_bytes)
        self._entries = OrderedDict()
''', '''\
from collections import OrderedDict
from greptimedb_tpu.telemetry import memory

class GridCache:
    def __init__(self, max_bytes):
        self.max_bytes = int(max_bytes)
        self._entries = OrderedDict()
        memory.register_pool("grids", "device", self,
                             stats=GridCache._stats)

    def _stats(self):
        return {"bytes": 0}
'''),
    "GT017": ('''\
from greptimedb_tpu.telemetry.metrics import global_registry

C = global_registry.counter("gtpu_things", "things counted")
''', '''\
from greptimedb_tpu.telemetry.metrics import global_registry

C = global_registry.counter("gtpu_calls_total", "calls",
                            labels=("db", "code"))
'''),
    "GT018": ('''\
import functools
import jax

@functools.partial(jax.jit, static_argnames=("g",))
def prog(x, *, g):
    return x + g

def serve(x):
    return prog(x, g=4)
''', '''\
import jax
from greptimedb_tpu.telemetry import device_trace

@jax.jit
def prog(x):
    return x * 2

def serve(x):
    with device_trace.device_call("site", key=("k",)) as d:
        return d.run(prog, x)
'''),
    "GT019": ('''\
from urllib.request import urlopen
from greptimedb_tpu.telemetry.metrics import global_registry

def _collect():
    urlopen("http://peer:4000/metrics")

global_registry.register_collector(_collect)
''', '''\
from urllib.request import urlopen
from greptimedb_tpu.telemetry.metrics import global_registry

def _collect():
    urlopen("http://peer:4000/metrics", timeout=2.0)

global_registry.register_collector(_collect)
'''),
    "GT021": ('''\
def detune(inst):
    inst.scheduler.config.max_concurrency = 4
''', '''\
def actuate(registry):
    registry.set("scheduler.max_concurrency", 4)
'''),
    "GT022": ('''\
import jax
from jax.experimental import pallas as pl

def kernel(x_ref, o_ref):
    o_ref[...] = x_ref[...] + x_ref[...]

def run(x):
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        interpret=True,
    )(x)
''', '''\
import jax
from jax.experimental import pallas as pl

def kernel(x_ref, o_ref):
    o_ref[...] = x_ref[...] + x_ref[...]

def run(x, interpret):
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        interpret=interpret,
    )(x)
'''),
    "GT033": ('''\
import numpy as np

def match(reg, value):
    vals = reg.tag_values("host")
    return np.flatnonzero(vals == value)
''', '''\
from greptimedb_tpu import index

def match(reg, value):
    return index.match_sids(reg, [("host", "eq", value)])
'''),
}

for _cls in list(globals().values()):
    if (isinstance(_cls, type) and issubclass(_cls, Rule)
            and getattr(_cls, "id", None) in _EXAMPLES):
        _cls.example_pos, _cls.example_neg = _EXAMPLES[_cls.id]
del _cls
