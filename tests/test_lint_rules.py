"""gtlint rule fixtures: every rule has at least one positive snippet
(caught, with the right rule id and line) and one negative snippet
(not flagged), plus suppression-comment and baseline round-trips and
the CLI/JSON surface."""

from __future__ import annotations

import json
import subprocess
import sys
import textwrap

import pytest

from greptimedb_tpu.tools.lint import (
    Baseline,
    all_rules,
    lint_paths,
    lint_source,
)


def run_lint(src: str, select: str | None = None):
    act, sup = lint_source(
        "fixture.py", textwrap.dedent(src),
        select={select} if select else None,
    )
    return act, sup


def rules_hit(src: str, select: str | None = None):
    act, _ = run_lint(src, select)
    return [(f.rule, f.line) for f in act]


def test_registry_has_all_rules():
    ids = sorted(all_rules())
    # GT020 is unassigned/reserved; the registry jumps to GT021.
    assert ids == ([f"GT{n:03d}" for n in range(1, 20)]
                   + [f"GT{n:03d}" for n in range(21, 34)])
    for rule in all_rules().values():
        assert rule.name and rule.description


# ---------------------------------------------------------------------------
# GT001 silent exception swallow
# ---------------------------------------------------------------------------

def test_gt001_positive_swallow_and_bare():
    hits = rules_hit("""
        try:
            x = 1
        except Exception:
            pass
    """)
    assert ("GT001", 4) in hits

    hits = rules_hit("""
        try:
            x = 1
        except:
            x = 2
    """)
    assert ("GT001", 4) in hits


def test_gt001_negative_narrow_or_logged():
    assert rules_hit("""
        try:
            x = 1
        except ValueError:
            pass
    """) == []
    assert rules_hit("""
        import logging
        try:
            x = 1
        except Exception as e:
            logging.getLogger("x").warning("boom: %s", e)
    """) == []


# ---------------------------------------------------------------------------
# GT002 error-substring matching
# ---------------------------------------------------------------------------

def test_gt002_positive_str_e_matching():
    hits = rules_hit("""
        def classify(e):
            return "unavailable" in str(e).lower()
    """)
    assert ("GT002", 3) in hits
    hits = rules_hit("""
        try:
            x = 1
        except Exception as boom:
            if "not found" in str(boom):
                raise
    """)
    assert ("GT002", 5) in hits


def test_gt002_negative_plain_string_ops():
    # substring tests on non-exception values are fine
    assert rules_hit("""
        def f(value):
            return "," in str(value)
    """) == []
    assert rules_hit("""
        def f(e):
            return isinstance(e, ConnectionError)
    """) == []


# ---------------------------------------------------------------------------
# GT003 untyped raise
# ---------------------------------------------------------------------------

def test_gt003_positive_untyped():
    assert ("GT003", 2) in rules_hit("""
        raise Exception("boom")
    """)
    assert ("GT003", 2) in rules_hit("""
        raise BaseException("boom")
    """)


def test_gt003_negative_typed():
    assert rules_hit("""
        from greptimedb_tpu.errors import StorageError
        def f():
            raise StorageError("disk gone")
        def g():
            raise ValueError("bad arg")
    """) == []


# ---------------------------------------------------------------------------
# GT004 host sync inside jit / Pallas
# ---------------------------------------------------------------------------

def test_gt004_positive_item_float_asarray():
    hits = rules_hit("""
        import jax
        import numpy as np

        @jax.jit
        def f(x):
            a = x.item()
            b = float(x)
            c = np.asarray(x)
            return a + b + c.sum()
    """)
    assert [h[0] for h in hits] == ["GT004", "GT004", "GT004"]
    assert [h[1] for h in hits] == [7, 8, 9]


def test_gt004_positive_inside_pallas_kernel():
    hits = rules_hit("""
        from jax.experimental import pallas as pl

        def my_kernel(x_ref, o_ref):
            o_ref[0] = float(x_ref)

        def launch(x):
            return pl.pallas_call(my_kernel, out_shape=None)(x)
    """)
    assert ("GT004", 5) in hits


def test_gt004_positive_inside_shard_map_body():
    # shard_map bodies run traced on device exactly like jit/Pallas
    hits = rules_hit("""
        import jax
        import numpy as np
        from jax import shard_map
        from jax.sharding import PartitionSpec as P

        def run(mesh, x):
            def local(x):
                return np.asarray(x).sum()

            return shard_map(local, mesh=mesh, in_specs=(P("s"),),
                             out_specs=P())(x)
    """)
    assert ("GT004", 9) in hits


def test_gt005_positive_inside_shard_map_body():
    hits = rules_hit("""
        from jax import shard_map
        from jax.sharding import PartitionSpec as P

        def run(mesh, x):
            def local(x):
                if x > 0:
                    x = x - 1
                return x

            return shard_map(local, mesh=mesh, in_specs=(P("s"),),
                             out_specs=P("s"))(x)
    """)
    assert ("GT005", 7) in hits


def test_gt004_negative_host_code_and_static():
    # outside jit, all of these are normal host code
    assert rules_hit("""
        import numpy as np
        def f(x):
            return float(x) + np.asarray(x).sum() + x.item()
    """) == []
    # float() of a static (non-traced) value inside jit is fine
    assert rules_hit("""
        import functools
        import jax

        @functools.partial(jax.jit, static_argnames=("k",))
        def f(x, k):
            return x * float(k)
    """) == []


# ---------------------------------------------------------------------------
# GT005 Python branch on traced value
# ---------------------------------------------------------------------------

def test_gt005_positive_if_while_ifexp():
    hits = rules_hit("""
        import jax

        @jax.jit
        def f(x):
            if x > 0:
                x = x - 1
            while x < 3:
                x = x + 1
            return x if x > 0 else -x
    """)
    assert [h[0] for h in hits] == ["GT005", "GT005", "GT005"]
    assert [h[1] for h in hits] == [6, 8, 10]


def test_gt005_negative_static_shape_none_isinstance():
    assert rules_hit("""
        import functools
        import jax

        @functools.partial(jax.jit, static_argnames=("k",))
        def f(x, k, opt=None):
            if k > 1:
                x = x * 2
            if x.ndim == 2:
                x = x.sum(axis=1)
            if opt is None:
                x = x + 1
            if len(x.shape) == 1:
                x = x * 3
            return x
    """) == []


# ---------------------------------------------------------------------------
# GT006 recompile hazards
# ---------------------------------------------------------------------------

def test_gt006_positive_jit_in_loop_and_lambda():
    hits = rules_hit("""
        import jax

        def g(h, xs):
            for x in xs:
                f = jax.jit(h)
            f2 = jax.jit(lambda a: a + 1)
            return f, f2
    """)
    assert [h[0] for h in hits] == ["GT006", "GT006"]
    assert [h[1] for h in hits] == [6, 7]


def test_gt006_negative_module_scope_jit():
    assert rules_hit("""
        import functools
        import jax

        def _impl(x):
            return x + 1

        fast = jax.jit(_impl)
        faster = functools.partial(jax.jit, static_argnames=("k",))
    """) == []


# ---------------------------------------------------------------------------
# GT007 lock across blocking I/O
# ---------------------------------------------------------------------------

def test_gt007_positive_urlopen_flight_sleep_under_lock():
    hits = rules_hit("""
        import threading
        import time
        import urllib.request

        lock = threading.Lock()

        def f(client):
            with lock:
                urllib.request.urlopen("http://x")
            with client._lock:
                client.conn.do_get(b"t")
            with lock:
                time.sleep(1.0)
    """)
    # the same unbounded urlopen/do_get also trip GT012: filter to the
    # lock-discipline findings this test is about
    gt007 = [h for h in hits if h[0] == "GT007"]
    assert [h[1] for h in gt007] == [10, 12, 14]
    assert {h[0] for h in hits} == {"GT007", "GT012"}


def test_gt007_negative_io_outside_lock_and_condvar():
    assert rules_hit("""
        import threading
        import urllib.request

        lock = threading.Lock()
        cond = threading.Condition()

        def f():
            with lock:
                snapshot = 1
            urllib.request.urlopen("http://x", timeout=5.0)
            with cond:
                cond.wait()   # releases the lock: allowed
            return snapshot
    """) == []


# ---------------------------------------------------------------------------
# GT008 thread/pool without join/shutdown
# ---------------------------------------------------------------------------

def test_gt008_positive_leaked_thread_and_pool():
    hits = rules_hit("""
        import threading
        from concurrent.futures import ThreadPoolExecutor

        def bad(target):
            threading.Thread(target=target).start()
            pool = ThreadPoolExecutor(4)
            return pool
    """)
    assert [h[0] for h in hits] == ["GT008", "GT008"]
    assert [h[1] for h in hits] == [6, 7]


def test_gt008_negative_daemon_join_with_shutdown():
    assert rules_hit("""
        import threading
        from concurrent.futures import ThreadPoolExecutor

        def ok(target):
            threading.Thread(target=target, daemon=True).start()
            t = threading.Thread(target=target)
            t.start()
            t.join()
            with ThreadPoolExecutor(4) as p:
                p.submit(target)
            q = ThreadPoolExecutor(2)
            q.shutdown(wait=False)
    """) == []


def test_gt008_negative_swap_teardown_idiom():
    # the codebase's shutdown-outside-the-lock idiom must not flag
    assert rules_hit("""
        import threading
        from concurrent.futures import ThreadPoolExecutor

        class Server:
            def _pool(self):
                with self._lock:
                    if self._scan_pool is None:
                        self._scan_pool = ThreadPoolExecutor(4)
                    return self._scan_pool

            def close(self):
                with self._lock:
                    pool, self._scan_pool = self._scan_pool, None
                if pool is not None:
                    pool.shutdown(wait=False)
    """) == []


# ---------------------------------------------------------------------------
# GT009 int64 on device
# ---------------------------------------------------------------------------

def test_gt009_positive_jnp_int64():
    hits = rules_hit("""
        import jax.numpy as jnp
        import numpy as np

        def f(x):
            a = jnp.asarray(x, jnp.int64)
            b = jnp.zeros(3, dtype=np.int64)
            c = jnp.zeros(3, dtype="int64")
            return a, b, c
    """)
    assert [h[0] for h in hits] == ["GT009", "GT009", "GT009"]
    assert [h[1] for h in hits] == [6, 7, 8]


def test_gt009_negative_host_numpy_and_int32():
    assert rules_hit("""
        import jax.numpy as jnp
        import numpy as np

        def f(x):
            host = np.asarray(x, np.int64)      # host numpy: fine
            dev = jnp.asarray(x, jnp.int32)
            return host, dev
    """) == []


# ---------------------------------------------------------------------------
# GT010 mutable default args
# ---------------------------------------------------------------------------

def test_gt010_positive_public_mutable_defaults():
    hits = rules_hit("""
        def public(a, xs=[], m={}, s=set()):
            return a
    """)
    assert [h[0] for h in hits] == ["GT010", "GT010", "GT010"]


def test_gt010_negative_private_none_tuple():
    assert rules_hit("""
        def _private(xs=[]):
            return xs

        def public(a, xs=None, t=(), name="x"):
            return a
    """) == []


# ---------------------------------------------------------------------------
# suppressions
# ---------------------------------------------------------------------------

# ---------------------------------------------------------------------------
# GT013 collective axis not bound by the enclosing shard_map
# ---------------------------------------------------------------------------

def test_gt013_positive_unbound_literal_axis():
    hits = rules_hit("""
        import jax
        from jax import shard_map
        from jax.sharding import PartitionSpec as P

        def run(mesh, x):
            def local(x):
                return jax.lax.psum(x, "time")

            return shard_map(local, mesh=mesh, in_specs=(P("shard"),),
                             out_specs=P())(x)
    """, select="GT013")
    assert hits == [("GT013", 8)]


def test_gt013_positive_unresolved_identifier_axis():
    # both sides unresolved identifiers: compared by name
    hits = rules_hit("""
        import jax
        from jax import shard_map
        from jax.sharding import PartitionSpec as P
        from somewhere import AXIS_A, AXIS_B

        def run(mesh, x):
            def local(x):
                return jax.lax.pmax(x, AXIS_B)

            return shard_map(local, mesh=mesh, in_specs=(P(AXIS_A),),
                             out_specs=P())(x)
    """, select="GT013")
    assert hits == [("GT013", 9)]


def test_gt013_positive_module_constant_resolution():
    # module constants resolve to their string values before comparing
    hits = rules_hit("""
        import jax
        from jax import shard_map
        from jax.sharding import PartitionSpec as P

        AXIS_S = "shard"

        def run(mesh, x):
            def local(x):
                return jax.lax.all_gather(x, "ici")

            return shard_map(local, mesh=mesh, in_specs=(P(AXIS_S),),
                             out_specs=P())(x)
    """, select="GT013")
    assert hits == [("GT013", 10)]


def test_gt013_negative_bound_axis_and_mixed_spaces():
    # bound literal axis: clean
    assert rules_hit("""
        import jax
        from jax import shard_map
        from jax.sharding import PartitionSpec as P

        def run(mesh, x):
            def local(x):
                return jax.lax.psum(x, "shard")

            return shard_map(local, mesh=mesh, in_specs=(P("shard"),),
                             out_specs=P())(x)
    """, select="GT013") == []
    # module constant on both sides: resolves and matches
    assert rules_hit("""
        import jax
        from jax import shard_map
        from jax.sharding import PartitionSpec as P

        AXIS_S = "shard"

        def run(mesh, x):
            def local(x):
                return jax.lax.pmin(x, AXIS_S)

            return shard_map(local, mesh=mesh, in_specs=(P(AXIS_S),),
                             out_specs=P())(x)
    """, select="GT013") == []
    # unresolved identifier vs literal specs: can't compare, stays quiet
    assert rules_hit("""
        import jax
        from jax import shard_map
        from jax.sharding import PartitionSpec as P
        from somewhere import AXIS_T

        def run(mesh, x):
            def local(x):
                return jax.lax.psum(x, AXIS_T)

            return shard_map(local, mesh=mesh, in_specs=(P("shard"),),
                             out_specs=P())(x)
    """, select="GT013") == []
    # collective outside any shard_map body: out of scope
    assert rules_hit("""
        import jax

        def helper(x, axis_name="shard"):
            return jax.lax.psum(x, axis_name)
    """, select="GT013") == []


# ---------------------------------------------------------------------------
# GT014 tracing/metrics calls inside jit/shard_map device scope
# ---------------------------------------------------------------------------

def test_gt014_positive_tracing_span_in_jit():
    hits = rules_hit("""
        import jax
        from greptimedb_tpu.telemetry import tracing

        @jax.jit
        def kernel(x):
            with tracing.span("device.step"):
                return x + 1
    """, select="GT014")
    assert hits == [("GT014", 7)]


def test_gt014_positive_stats_and_metric_in_shard_map_body():
    hits = rules_hit("""
        import jax
        from jax import shard_map
        from jax.sharding import PartitionSpec as P
        from greptimedb_tpu.query import stats
        from greptimedb_tpu.telemetry.metrics import global_registry

        _CALLS = global_registry.counter("calls", "c", ("k",))

        def run(mesh, x):
            def local(x):
                stats.add("device_steps", 1)
                _CALLS.labels("a").inc()
                return jax.lax.psum(x, "shard")

            return shard_map(local, mesh=mesh, in_specs=(P("shard"),),
                             out_specs=P())(x)
    """, select="GT014")
    assert hits == [("GT014", 12), ("GT014", 13)]


def test_gt014_positive_nested_def_inherits_device_scope():
    # a helper nested inside a jitted function traces on device too
    hits = rules_hit("""
        import jax
        from greptimedb_tpu.telemetry import tracing

        @jax.jit
        def kernel(x):
            def inner(y):
                tracing.event_span("step", 1.0)
                return y

            return inner(x)
    """, select="GT014")
    assert hits == [("GT014", 8)]


def test_gt014_negative_host_scope_and_lowercase_receiver():
    # the same calls OUTSIDE device scope are the intended idiom
    assert rules_hit("""
        import jax
        from greptimedb_tpu.telemetry import tracing
        from greptimedb_tpu.query import stats

        @jax.jit
        def kernel(x):
            return x + 1

        def host(x):
            with tracing.span("device.execute"):
                out = kernel(x)
            stats.add("device_readback_bytes", 8)
            return out
    """, select="GT014") == []
    # lowercase method receivers inside jit are not metric constants
    assert rules_hit("""
        import jax

        @jax.jit
        def kernel(x, acc):
            y = acc.set(1)
            return x.inc() + y.observe()
    """, select="GT014") == []


# ---------------------------------------------------------------------------
# GT015 full-buffer readback on a device result buffer
# ---------------------------------------------------------------------------

def test_gt015_positive_asarray_and_device_get():
    hits = rules_hit("""
        import numpy as np

        def run(program, arrs):
            out = program(arrs)
            out.block_until_ready()
            host = np.asarray(out)
            return host
    """, select="GT015")
    assert hits == [("GT015", 7)]
    hits = rules_hit("""
        import jax

        def run(program, arrs):
            packed = program(arrs)
            packed.block_until_ready()
            return jax.device_get(packed)
    """, select="GT015")
    assert hits == [("GT015", 7)]


def test_gt015_positive_waited_through_a_device_call():
    # `d.wait(out)` is the block: the name is a device result buffer
    hits = rules_hit("""
        import numpy as np
        from greptimedb_tpu.telemetry import device_trace

        def run(program, arrs):
            with device_trace.device_call("site") as d:
                out, side = d.run(program, arrs)
                d.wait(out, side)
                return np.asarray(side)
    """, select="GT015")
    assert hits == [("GT015", 9)]


def test_gt015_negative_read_through_a_device_call():
    assert rules_hit("""
        import numpy as np
        from greptimedb_tpu.query import readback
        from greptimedb_tpu.telemetry import device_trace

        def run(program, arrs, cv, timeout):
            with device_trace.device_call("site") as d:
                out = d.run(program, arrs)
                d.wait(out)
                host = d.read(readback.read_full, out)
            # a wait that is no device_call's names no device buffer
            cv.wait(timeout)
            return host, np.asarray(timeout)
    """, select="GT015") == []


def test_gt015_positive_raw_copy_to_host_async():
    # starting the copy by hand is the crossing begun unattributed
    hits = rules_hit("""
        from greptimedb_tpu.query import readback
        from greptimedb_tpu.telemetry import device_trace

        def run(program, arrs):
            with device_trace.device_call("site") as d:
                out = d.run(program, arrs)
                out.copy_to_host_async()
                d.wait(out)
                return d.read(readback.read_full, out)
    """, select="GT015")
    assert hits == [("GT015", 8)]


def test_gt015_negative_copy_started_through_the_call():
    # `d.start(out)` is the blessed start (query/readback.start)
    assert rules_hit("""
        from greptimedb_tpu.query import readback
        from greptimedb_tpu.telemetry import device_trace

        def run(program, arrs, host_value):
            with device_trace.device_call("site") as d:
                out = d.run(program, arrs)
                d.start(out)
                d.wait(out)
                host_value.copy_to_host_async()
                return d.read(readback.read_full, out)
    """, select="GT015") == []


def test_gt015_negative_helper_and_host_arrays():
    # readback through the blessed helpers is the intended idiom
    assert rules_hit("""
        from greptimedb_tpu.query import readback

        def run(program, arrs, j0):
            out = program(arrs)
            out.block_until_ready()
            return readback.read_delta(out, j0, axis=-1)
    """, select="GT015") == []
    # np.asarray on a plain host value (no block_until_ready) is fine
    assert rules_hit("""
        import numpy as np

        def convert(vals):
            arr = np.asarray(vals)
            return arr
    """, select="GT015") == []
    # a DIFFERENT function's device buffer does not taint this one
    assert rules_hit("""
        import numpy as np

        def a(program, arrs):
            out = program(arrs)
            out.block_until_ready()
            return out

        def b(out):
            return np.asarray(out)
    """, select="GT015") == []


# ---------------------------------------------------------------------------
# GT016 byte-budgeted container not registered with the memory accountant
# ---------------------------------------------------------------------------

def test_gt016_positive_unregistered_byte_pool():
    hits = rules_hit("""
        from collections import OrderedDict

        class GridCache:
            def __init__(self, max_bytes):
                self.max_bytes = int(max_bytes)
                self._entries = OrderedDict()
                self._bytes = 0
    """, select="GT016")
    assert hits == [("GT016", 4)]
    # budget riding the VALUE name (self.capacity = capacity_bytes)
    hits = rules_hit("""
        class PageCache:
            def __init__(self, capacity_bytes):
                self.capacity = capacity_bytes
                self._entries = {}
    """, select="GT016")
    assert hits == [("GT016", 2)]


def test_gt016_positive_module_dict_of_device_arrays():
    hits = rules_hit("""
        import jax

        _GRIDS = {}

        def cache_grid(key, host_arr):
            _GRIDS[key] = jax.device_put(host_arr)
    """, select="GT016")
    assert [h[0] for h in hits] == ["GT016"]


def test_gt016_negative_registered_and_non_pools():
    # registering with the accountant silences the rule
    assert rules_hit("""
        from collections import OrderedDict
        from greptimedb_tpu.telemetry import memory

        class GridCache:
            def __init__(self, max_bytes):
                self.max_bytes = int(max_bytes)
                self._entries = OrderedDict()
                memory.register_pool(
                    "grids", "device", self, stats=GridCache._stats
                )

            def _stats(self):
                return {"bytes": 0}
    """, select="GT016") == []
    # entry-count config objects are not byte pools
    assert rules_hit("""
        class TracingConfig:
            def __init__(self, capacity):
                self.capacity = int(capacity)
                self.extra = {}
    """, select="GT016") == []
    # a budget without an entries container (a sizing constant holder)
    assert rules_hit("""
        class Sizer:
            def __init__(self, max_bytes):
                self.max_bytes = max_bytes
    """, select="GT016") == []
    # module dicts holding host-side objects are fine
    assert rules_hit("""
        _LOCKS = {}

        def lock_for(key):
            import threading
            _LOCKS[key] = threading.Lock()
            return _LOCKS[key]
    """, select="GT016") == []
    # a registering module's device-array dict is fine too
    assert rules_hit("""
        import jax
        from greptimedb_tpu.telemetry import memory

        _GRIDS = {}
        memory.register_pool("grids", "device", object(), stats=len)

        def cache_grid(key, host_arr):
            _GRIDS[key] = jax.device_put(host_arr)
    """, select="GT016") == []


def test_suppression_same_line():
    src = """
        try:
            x = 1
        except Exception:  # gtlint: disable=GT001
            pass
    """
    act, sup = run_lint(src)
    assert act == []
    assert [(f.rule, f.line) for f in sup] == [("GT001", 4)]


def test_suppression_next_line_and_multi_id():
    act, sup = run_lint("""
        import jax

        @jax.jit
        def f(x):
            # gtlint: disable-next-line=GT004,GT005
            if x > 0:
                return x
            return float(x)   # gtlint: disable=GT004
    """)
    assert act == []
    assert sorted(f.rule for f in sup) == ["GT004", "GT005"]


def test_suppression_wrong_id_does_not_cover():
    act, _ = run_lint("""
        try:
            x = 1
        except Exception:
            pass  # gtlint: disable=GT999
    """)
    assert [(f.rule, f.line) for f in act] == [("GT001", 4)]


def test_suppression_file_wide():
    act, sup = run_lint("""
        # gtlint: disable-file=GT010
        def public(xs=[]):
            return xs

        def other(m={}):
            return m
    """)
    assert act == []
    assert len(sup) == 2


# ---------------------------------------------------------------------------
# baseline round-trip
# ---------------------------------------------------------------------------

BASELINE_SRC = '''
try:
    x = 1
except Exception:
    pass

def classify(e):
    return "boom" in str(e)
'''


def test_baseline_round_trip(tmp_path):
    pkg = tmp_path / "pkg"
    pkg.mkdir()
    (pkg / "mod.py").write_text(BASELINE_SRC)

    # 1) no baseline: both findings are new
    res = lint_paths([str(pkg)], baseline=None)
    res.pop("_line_text", None)
    assert res["counts"]["new"] == 2
    assert not res["clean"]

    # 2) write those findings as the baseline; re-run: clean
    proc = subprocess.run(
        [sys.executable, "-m", "greptimedb_tpu.tools.lint", str(pkg),
         "--baseline", str(tmp_path / "base.json"), "--write-baseline"],
        capture_output=True, text=True, cwd="/root/repo",
    )
    assert proc.returncode == 0, proc.stderr
    base = Baseline.load(str(tmp_path / "base.json"))
    assert len(base.entries) == 2

    res = lint_paths([str(pkg)], baseline=base)
    res.pop("_line_text", None)
    assert res["counts"]["new"] == 0
    assert res["counts"]["baselined"] == 2
    assert res["clean"]

    # 3) fix one violation: its baseline entry goes stale (reported,
    # and the gate fails until the entry is removed)
    (pkg / "mod.py").write_text(BASELINE_SRC.replace(
        'return "boom" in str(e)', "return isinstance(e, OSError)"
    ))
    res = lint_paths([str(pkg)], baseline=base)
    res.pop("_line_text", None)
    assert res["counts"]["new"] == 0
    assert res["counts"]["baselined"] == 1
    assert res["counts"]["stale_baseline"] == 1
    assert not res["clean"]

    # 4) a NEW violation is never hidden by the baseline
    (pkg / "mod.py").write_text(
        BASELINE_SRC + "\n\ndef pub(xs=[]):\n    return xs\n"
    )
    res = lint_paths([str(pkg)], baseline=base)
    res.pop("_line_text", None)
    assert res["counts"]["new"] == 1
    assert res["findings"][0]["rule"] == "GT010"


def test_baseline_line_drift_tolerated(tmp_path):
    """Edits above a grandfathered site must not invalidate its
    baseline entry: matching is by text, not line number."""
    pkg = tmp_path / "pkg"
    pkg.mkdir()
    (pkg / "mod.py").write_text(BASELINE_SRC)
    res = lint_paths([str(pkg)], baseline=None)
    line_text = res.pop("_line_text")
    from greptimedb_tpu.tools.lint import Finding

    base = Baseline.from_findings(
        [Finding(**d) for d in res["findings"]], line_text
    )
    (pkg / "mod.py").write_text("import os\nimport sys\n" + BASELINE_SRC)
    res = lint_paths([str(pkg)], baseline=base)
    res.pop("_line_text", None)
    assert res["counts"]["new"] == 0
    assert res["counts"]["stale_baseline"] == 0
    assert res["clean"]


# ---------------------------------------------------------------------------
# CLI surface
# ---------------------------------------------------------------------------

def _run_cli(args, cwd="/root/repo"):
    return subprocess.run(
        [sys.executable, "-m", "greptimedb_tpu.tools.lint", *args],
        capture_output=True, text=True, cwd=cwd,
    )


def test_cli_json_format_and_exit_code(tmp_path):
    bad = tmp_path / "bad.py"
    bad.write_text("def pub(xs=[]):\n    return xs\n")
    proc = _run_cli([str(bad), "--format=json", "--no-baseline"])
    assert proc.returncode == 1
    doc = json.loads(proc.stdout)
    assert doc["counts"]["new"] == 1
    assert doc["findings"][0]["rule"] == "GT010"
    assert doc["findings"][0]["line"] == 1
    assert not doc["clean"]

    good = tmp_path / "good.py"
    good.write_text("def pub(xs=None):\n    return xs\n")
    proc = _run_cli([str(good), "--format=json", "--no-baseline"])
    assert proc.returncode == 0
    assert json.loads(proc.stdout)["clean"]


def test_cli_select_and_list_rules(tmp_path):
    bad = tmp_path / "bad.py"
    bad.write_text(
        "def pub(xs=[]):\n"
        "    try:\n"
        "        return xs\n"
        "    except Exception:\n"
        "        pass\n"
    )
    proc = _run_cli([str(bad), "--select=GT001", "--format=json",
                     "--no-baseline"])
    doc = json.loads(proc.stdout)
    assert [f["rule"] for f in doc["findings"]] == ["GT001"]

    proc = _run_cli(["--list-rules"])
    assert proc.returncode == 0
    for rid in ("GT001", "GT005", "GT010"):
        assert rid in proc.stdout


def test_cli_syntax_error_exit_2(tmp_path):
    bad = tmp_path / "broken.py"
    bad.write_text("def (\n")
    proc = _run_cli([str(bad), "--no-baseline"])
    assert proc.returncode == 2
    assert "error" in proc.stdout


def test_cli_nonexistent_path_exit_2(tmp_path):
    """A typo'd path must not lint 0 files and report clean."""
    proc = _run_cli([str(tmp_path / "no_such_dir"), "--no-baseline"])
    assert proc.returncode == 2
    assert "does not exist" in proc.stdout


def test_write_baseline_merges_out_of_scope_and_refuses_select(tmp_path):
    """A subdirectory --write-baseline keeps grandfathered entries for
    files outside the run's scope; --select is refused outright."""
    a = tmp_path / "a"
    b = tmp_path / "b"
    a.mkdir()
    b.mkdir()
    (a / "mod.py").write_text("def pub(xs=[]):\n    return xs\n")
    (b / "mod.py").write_text(
        "try:\n    x = 1\nexcept Exception:\n    pass\n"
    )
    base = tmp_path / "base.json"
    proc = _run_cli([str(a), str(b), "--baseline", str(base),
                     "--write-baseline"])
    assert proc.returncode == 0, proc.stderr
    assert len(Baseline.load(str(base)).entries) == 2

    # re-write scoped to only a/: b/'s entry must survive the merge
    proc = _run_cli([str(a), "--baseline", str(base),
                     "--write-baseline"])
    assert proc.returncode == 0, proc.stderr
    entries = Baseline.load(str(base)).entries
    assert sorted(e["rule"] for e in entries) == ["GT001", "GT010"]

    proc = _run_cli([str(a), "--baseline", str(base),
                     "--write-baseline", "--select=GT010"])
    assert proc.returncode == 2
    assert "--select" in proc.stderr


def test_greptimedb_tpu_cli_lint_subcommand(tmp_path):
    """`greptimedb-tpu lint` (cli.py) mirrors the module CLI."""
    bad = tmp_path / "bad.py"
    bad.write_text("def pub(xs=[]):\n    return xs\n")
    proc = subprocess.run(
        [sys.executable, "-m", "greptimedb_tpu.cli", "lint", str(bad),
         "--format=json", "--no-baseline"],
        capture_output=True, text=True, cwd="/root/repo",
    )
    assert proc.returncode == 1
    doc = json.loads(proc.stdout)
    assert doc["findings"][0]["rule"] == "GT010"


# ---------------------------------------------------------------------------
# planted multi-violation fixture: ids, files, and lines all correct
# ---------------------------------------------------------------------------

def test_planted_violations_report_correct_rule_file_line(tmp_path):
    pkg = tmp_path / "planted"
    pkg.mkdir()
    (pkg / "a.py").write_text(
        "try:\n"
        "    x = 1\n"
        "except Exception:\n"
        "    pass\n"
    )
    (pkg / "b.py").write_text(
        "import jax\n"
        "\n"
        "@jax.jit\n"
        "def f(x):\n"
        "    if x > 0:\n"
        "        return float(x)\n"
        "    return x\n"
    )
    res = lint_paths([str(pkg)], baseline=None)
    res.pop("_line_text", None)
    got = {(f["rule"], f["path"].rsplit("/", 1)[-1], f["line"])
           for f in res["findings"]}
    assert got == {
        ("GT001", "a.py", 3),
        ("GT005", "b.py", 5),
        ("GT004", "b.py", 6),
    }


def test_lint_source_on_every_rule_doc():
    """Rule descriptions render in --list-rules; ids are stable."""
    rules = all_rules()
    assert rules["GT001"].name == "silent-exception-swallow"
    assert rules["GT007"].name == "lock-across-blocking-io"
    assert rules["GT009"].name == "int64-on-device"
    assert rules["GT011"].name == "wallclock-duration"


# ---------------------------------------------------------------------------
# GT007 interprocedural: blocking taint through module-local helpers
# ---------------------------------------------------------------------------

def test_gt007_interproc_two_calls_deep():
    """lock -> helper -> helper -> do_put fires, with the chain."""
    act, _ = run_lint("""
        import threading

        lock = threading.Lock()

        class Sender:
            def _wire(self, batch):
                writer, reader = self.client.do_put(batch)

            def _send(self, batch):
                return self._wire(batch)

            def submit(self, batch):
                with lock:
                    self._send(batch)
    """)
    hits = [(f.rule, f.line) for f in act]
    assert ("GT007", 15) in hits, hits
    msg = [f.message for f in act if f.line == 15][0]
    assert "Sender._send" in msg and "do_put" in msg


def test_gt007_interproc_module_function_one_deep():
    act, _ = run_lint("""
        import threading
        import time

        lock = threading.Lock()

        def backoff():
            time.sleep(0.5)

        def retry():
            with lock:
                backoff()
    """)
    hits = [(f.rule, f.line) for f in act]
    assert ("GT007", 12) in hits, hits


def test_gt007_interproc_negative_clean_helper_and_async_def():
    # a helper with no blocking op, and a nested def handed to a
    # thread (runs asynchronously), must not taint the caller
    assert rules_hit("""
        import threading
        import time

        lock = threading.Lock()

        def compute():
            return 2 + 2

        def submit():
            def worker():
                time.sleep(5)
            t = threading.Thread(target=worker, daemon=True)
            with lock:
                compute()
            t.start()
    """) == []


def test_gt007_interproc_negative_helper_called_outside_lock():
    assert rules_hit("""
        import threading
        import time

        lock = threading.Lock()

        def backoff():
            time.sleep(0.5)

        def retry():
            with lock:
                x = 1
            backoff()
    """) == []


# ---------------------------------------------------------------------------
# GT004 interprocedural: host-sync taint through helpers in jit
# ---------------------------------------------------------------------------

def test_gt004_interproc_helper_item_on_traced_arg():
    act, _ = run_lint("""
        import jax

        def total(v):
            return v.sum().item()

        @jax.jit
        def kernel(x):
            return total(x)
    """)
    hits = [(f.rule, f.line) for f in act]
    assert ("GT004", 9) in hits, hits
    msg = [f.message for f in act if f.line == 9][0]
    assert "total" in msg and ".item()" in msg


def test_gt004_interproc_negative_static_arg_and_host_caller():
    # helper called on a NON-traced value, and the same helper called
    # from plain host code, both stay clean
    assert rules_hit("""
        import functools

        import jax

        def total(v):
            return v.sum().item()

        @functools.partial(jax.jit, static_argnames=("n",))
        def kernel(x, n):
            return x * total(n)

        def host(y):
            return total(y)
    """) == []


# ---------------------------------------------------------------------------
# GT011 wall-clock duration arithmetic
# ---------------------------------------------------------------------------

def test_gt011_positive_inline_and_named():
    hits = rules_hit("""
        import time

        def f(start):
            return time.time() - start
    """)
    assert ("GT011", 5) in hits

    hits = rules_hit("""
        import time

        def g(lease_s):
            now = time.time()
            deadline = now + lease_s
            return deadline
    """)
    assert ("GT011", 6) in hits


def test_gt011_positive_duration_then_ms_conversion():
    # (time.time() - t0) * 1000 is interval math, NOT the exempt
    # epoch-ms constructor
    hits = rules_hit("""
        import time

        def f(t0):
            return (time.time() - t0) * 1000
    """)
    assert ("GT011", 5) in hits


def test_gt011_negative_epoch_ms_and_monotonic():
    # the epoch-ms DATA-timestamp constructor is exempt, either order
    assert rules_hit("""
        import time

        def stamp(ttl_ms):
            return int(time.time() * 1000) - ttl_ms

        def stamp2():
            now_ms = int(1000 * time.time())
            return now_ms + 3
    """) == []
    # monotonic interval math is the fix, not a finding
    assert rules_hit("""
        import time

        def f(start):
            return time.monotonic() - start
    """) == []
    # bare timestamps without arithmetic are fine
    assert rules_hit("""
        import time

        def g():
            return {"created": time.time()}
    """) == []
    # name tracking is scoped per function: a wall-clock `now` in one
    # function must not poison a monotonic `now` elsewhere
    assert rules_hit("""
        import time

        def stamp():
            now = time.time()
            return {"created": now}

        def elapsed(t0):
            now = time.monotonic()
            return now - t0
    """) == []


# ---------------------------------------------------------------------------
# GT012 unbounded blocking calls
# ---------------------------------------------------------------------------

def test_gt012_positive_flight_calls_without_options():
    hits = rules_hit("""
        def scan(client, ticket):
            reader = client.do_get(ticket)
            return reader.read_all()
    """)
    assert ("GT012", 3) in hits
    hits = rules_hit("""
        def put(conn, desc, schema):
            return conn.do_put(desc, schema)
    """)
    assert ("GT012", 3) in hits
    hits = rules_hit("""
        def act(conn, action):
            return list(conn.do_action(action))
    """)
    assert ("GT012", 3) in hits


def test_gt012_positive_urlopen_and_socket_without_timeout():
    hits = rules_hit("""
        import urllib.request

        def fetch(url):
            with urllib.request.urlopen(url) as r:
                return r.read()
    """)
    assert ("GT012", 5) in hits
    hits = rules_hit("""
        from urllib.request import urlopen

        def fetch(url):
            return urlopen(url).read()
    """)
    assert ("GT012", 5) in hits
    hits = rules_hit("""
        import socket

        def dial(addr):
            return socket.create_connection(addr)
    """)
    assert ("GT012", 5) in hits


def test_gt012_negative_bounded_calls():
    assert rules_hit("""
        import urllib.request

        def fetch(url):
            with urllib.request.urlopen(url, timeout=5.0) as r:
                return r.read()
    """, "GT012") == []
    # positional timeout forms count as explicit
    assert rules_hit("""
        import socket

        def dial(addr):
            return socket.create_connection(addr, 3.0)
    """, "GT012") == []
    # ... including on bare-name imports
    assert rules_hit("""
        from urllib.request import urlopen

        def fetch(url):
            return urlopen(url, None, 5.0).read()
    """, "GT012") == []
    assert rules_hit("""
        from socket import create_connection

        def dial(addr):
            return create_connection(addr, 3.0)
    """, "GT012") == []
    assert rules_hit("""
        import pyarrow.flight as flight

        def scan(client, ticket, timeout):
            return client.do_get(
                ticket, options=flight.FlightCallOptions(timeout=timeout)
            ).read_all()
    """, "GT012") == []
    # server-side dispatch plumbing is not a Flight client call
    assert rules_hit("""
        class Server:
            def do_action(self, context, action):
                return self._do_action(action.type)

            def handle(self, context, action):
                return self.do_action(context, action)
    """, "GT012") == []


def test_gt012_suppressible():
    act, sup = run_lint("""
        def stream(conn, desc, schema):
            # long-lived by design
            # gtlint: disable-next-line=GT012
            return conn.do_put(desc, schema)
    """, "GT012")
    assert act == [] and [f.rule for f in sup] == ["GT012"]


# ---------------------------------------------------------------------------
# GT017 metric naming conventions
# ---------------------------------------------------------------------------

def test_gt017_positive_counter_without_total():
    hits = rules_hit("""
        from greptimedb_tpu.telemetry.metrics import global_registry

        C = global_registry.counter("gtpu_things", "things counted")
    """, select="GT017")
    assert hits == [("GT017", 4)]


def test_gt017_positive_time_histogram_without_unit():
    hits = rules_hit("""
        H = global_registry.histogram(
            "gtpu_query_latency", "query latency",
        )
    """, select="GT017")
    assert [h[0] for h in hits] == ["GT017"]
    # _ms is as valid a unit suffix as _seconds
    assert rules_hit("""
        H = registry.histogram("gtpu_stage_duration_ms", "stage time")
    """, select="GT017") == []


def test_gt017_positive_uppercase_label():
    hits = rules_hit("""
        C = global_registry.counter(
            "gtpu_sheds_total", "sheds",
            labels=("Tenant", "reason"),
        )
    """, select="GT017")
    assert hits == [("GT017", 4)]


def test_gt017_negative_conforming_and_foreign_receivers():
    # conforming registrations: no findings
    assert rules_hit("""
        C = global_registry.counter(
            "gtpu_calls_total", "calls", labels=("db", "code"),
        )
        G = global_registry.gauge("gtpu_depth", "queue depth")
        H = self._registry.histogram(
            "gtpu_queue_time_seconds", "sojourn",
        )
        B = registry.histogram("gtpu_batch_rows", "rows per batch")
    """, select="GT017") == []
    # .counter()/.histogram() on a NON-registry receiver is not a
    # metric registration
    assert rules_hit("""
        n = collections.Counter()
        x = stats.counter("whatever")
        y = panel.histogram("Latency")
    """, select="GT017") == []


# ---------------------------------------------------------------------------
# GT018 untracked device dispatch
# ---------------------------------------------------------------------------

def test_gt018_positive_decorated_jit_called_host_scope():
    hits = rules_hit("""
        import functools, jax

        @functools.partial(jax.jit, static_argnames=("g",))
        def prog(x, *, g):
            return x + g

        def serve(x):
            return prog(x, g=4)
    """, select="GT018")
    assert hits == [("GT018", 9)]


def test_gt018_positive_jit_assignment_called_host_scope():
    hits = rules_hit("""
        import jax

        touch = jax.jit(lambda x: x.sum())

        def warm(arrs):
            return float(touch(arrs))
    """, select="GT018")
    assert hits == [("GT018", 7)]


def test_gt018_negative_inside_device_call_scope():
    assert rules_hit("""
        import jax
        from greptimedb_tpu.telemetry import device_trace

        @jax.jit
        def prog(x):
            return x * 2

        def serve(x):
            with device_trace.device_call("site", key=("k",)) as d:
                return d.run(prog, x)

        def serve_direct(x):
            with device_trace.device_call("site") as d:
                out = prog(x)
                d.wait(out)
                return out

        def serve_chained(x, stats):
            with stats.timed("ms"), device_trace.device_call("s") as d:
                return d.run(prog, x)

        def serve_lambda(x, session_exec):
            with device_trace.device_call("s") as d:
                return session_exec(lambda: d.run(prog, x))
    """, select="GT018") == []


def test_gt018_negative_device_scope_and_unknown_callees():
    # a call INSIDE jit scope is inlining (tracing), not a dispatch;
    # builder-returned programs (name assigned from a helper call) are
    # not provably jit-produced and stay silent
    assert rules_hit("""
        import jax

        @jax.jit
        def inner(x):
            return x + 1

        @jax.jit
        def outer(x):
            return inner(x) * 2

        def get_program():
            return jax.jit(lambda v: v)

        def serve(x):
            program = get_program()
            return program(x)
    """, select="GT018") == []


def test_gt018_nested_def_does_not_inherit_device_call_scope():
    hits = rules_hit("""
        import jax
        from greptimedb_tpu.telemetry import device_trace

        @jax.jit
        def prog(x):
            return x

        def serve(x):
            with device_trace.device_call("s") as d:
                def later():
                    return prog(x)
                return d.run(prog, x), later
    """, select="GT018")
    assert hits == [("GT018", 12)]


# ---------------------------------------------------------------------------
# GT019 unbounded I/O in scrape/heartbeat paths
# ---------------------------------------------------------------------------

def test_gt019_positive_collector_urlopen_unbounded():
    hits = rules_hit("""
        from urllib.request import urlopen
        from greptimedb_tpu.telemetry.metrics import global_registry

        def _collect():
            urlopen("http://peer:4000/metrics")

        global_registry.register_collector(_collect)
    """, select="GT019")
    assert hits == [("GT019", 6)]


def test_gt019_positive_heartbeat_builder_flight_call():
    hits = rules_hit("""
        def build_node_stats(inst):
            out = {}
            out["peer"] = inst.client.do_action("region_stats")
            return out
    """, select="GT019")
    assert hits == [("GT019", 4)]


def test_gt019_positive_pool_stats_hook_httpconn():
    hits = rules_hit("""
        import http.client
        from greptimedb_tpu.telemetry import memory

        def _pool_stats(pool):
            conn = http.client.HTTPConnection("peer", 80)
            return {}

        memory.register_pool("p", "host", object(), stats=_pool_stats)
    """, select="GT019")
    assert hits == [("GT019", 6)]


def test_gt019_positive_nested_def_inherits_hook_scope():
    hits = rules_hit("""
        from urllib.request import urlopen
        from greptimedb_tpu.telemetry.metrics import global_registry

        def _collect():
            def inner():
                urlopen("http://peer:4000/metrics")
            inner()

        global_registry.register_collector(_collect)
    """, select="GT019")
    assert hits == [("GT019", 7)]


def test_gt019_negative_bounded_and_off_path():
    # bounded calls in a hook are fine; the same unbounded calls
    # OUTSIDE a registered hook are not GT019's business (GT012 covers
    # the general case)
    assert rules_hit("""
        from urllib.request import urlopen
        from greptimedb_tpu.telemetry.metrics import global_registry

        def _collect():
            urlopen("http://peer:4000/metrics", timeout=2.0)
            cli.do_action("x", options=opts)

        global_registry.register_collector(_collect)

        def not_a_hook():
            urlopen("http://peer:4000/metrics")
    """, select="GT019") == []


# ---------------------------------------------------------------------------
# --changed mode
# ---------------------------------------------------------------------------

def test_changed_mode_lints_only_differing_files(tmp_path):
    """In a fresh git repo: clean committed file + dirty violating
    file; --changed HEAD flags only the dirty one."""
    import os

    repo = tmp_path / "repo"
    repo.mkdir()

    def git(*args):
        subprocess.run(
            ["git", *args], cwd=repo, check=True, capture_output=True,
            env={**os.environ, "GIT_AUTHOR_NAME": "t",
                 "GIT_AUTHOR_EMAIL": "t@t", "GIT_COMMITTER_NAME": "t",
                 "GIT_COMMITTER_EMAIL": "t@t"},
        )

    git("init", "-q")
    clean = repo / "clean.py"
    clean.write_text("try:\n    x = 1\nexcept Exception:\n    pass\n")
    dirty = repo / "dirty.py"
    dirty.write_text("x = 1\n")
    git("add", "-A")
    git("commit", "-q", "-m", "seed")
    # clean.py keeps its committed violation (must NOT be relinted);
    # dirty.py gains one (must be flagged); untracked.py is new
    dirty.write_text("try:\n    x = 1\nexcept Exception:\n    pass\n")
    untracked = repo / "untracked.py"
    untracked.write_text("def f(xs=[]):\n    return xs\n")

    from greptimedb_tpu.tools.lint import runner

    old_root = runner._REPO_ROOT
    runner._REPO_ROOT = str(repo)
    try:
        only = runner.changed_files("HEAD")
        assert only == {str(dirty), str(untracked)}
        res = runner.lint_paths([str(repo)], only=only)
    finally:
        runner._REPO_ROOT = old_root
    flagged = {d["path"].rsplit("/", 1)[-1] for d in res["findings"]}
    assert "dirty.py" in flagged and "untracked.py" in flagged
    assert "clean.py" not in flagged
    assert res["counts"]["files"] == 2


def test_changed_mode_cli_unknown_ref_exits_2(tmp_path):
    from greptimedb_tpu.tools.lint.runner import main as lint_main

    rc = lint_main(["--changed", "no-such-ref-xyz", str(tmp_path)])
    assert rc == 2


def test_changed_run_does_not_report_foreign_stale(tmp_path):
    """A --changed run must not mark baseline entries for UNSCANNED
    files as stale; a normal (full) run still must — that is how
    entries for DELETED files get flushed out."""
    import os

    target = tmp_path / "a.py"
    target.write_text("x = 1\n")
    base = Baseline([{
        "rule": "GT001", "path": "elsewhere/b.py", "line": 3,
        "text": "except Exception:",
    }])
    # --changed semantics: `only` restricts the walk, foreign entries
    # are out of scope
    res = lint_paths([str(target)], baseline=base,
                     only={os.path.normpath(str(target))})
    assert res["stale_baseline"] == []
    assert res["clean"]
    # full-run semantics: the unmatched entry is stale (deleted file)
    res = lint_paths([str(target)], baseline=base)
    assert len(res["stale_baseline"]) == 1
    assert not res["clean"]


# ---------------------------------------------------------------------------
# GT021 direct runtime-knob write
# ---------------------------------------------------------------------------

def test_gt021_positive_direct_and_augmented_write():
    hits = rules_hit("""
        def detune(inst, opts):
            inst.scheduler.config.max_concurrency = 4
            opts.l1_trigger_files += 2
            a, inst.compaction.opts.workers = 1, 8
    """, select="GT021")
    assert hits == [("GT021", 3), ("GT021", 4), ("GT021", 5)]


def test_gt021_positive_module_scope_write():
    hits = rules_hit("""
        import somewhere
        somewhere.cache.max_bytes = 1 << 20
    """, select="GT021")
    assert hits == [("GT021", 3)]


def test_gt021_negative_registry_self_and_config_appliers():
    hits = rules_hit("""
        class Cache:
            def __init__(self, n):
                self.max_bytes = n          # owning object

            def set_max_bytes(self, v):
                self.max_bytes = int(v)     # owning object

        def configure(inst, opts):
            inst.cache.max_bytes = opts.n   # process-start applier

        def from_options(o):
            o.scheduler.max_concurrency = 8

        def actuate(registry):
            registry.set("result_cache.bytes", 1 << 20)  # sanctioned
            max_bytes = 7                   # plain Name, not an attr
    """, select="GT021")
    assert hits == []


def test_gt021_negative_autotune_package_path():
    src = textwrap.dedent("""
        def apply(inst, v):
            inst.cache.max_bytes = int(v)
    """)
    act, _ = lint_source(
        "greptimedb_tpu/autotune/knobs.py", src, select={"GT021"})
    assert act == []
    # same source outside the package IS flagged
    act, _ = lint_source("greptimedb_tpu/other.py", src,
                         select={"GT021"})
    assert [f.rule for f in act] == ["GT021"]


# ---------------------------------------------------------------------------
# GT022 pallas_call hygiene
# ---------------------------------------------------------------------------

def test_gt022_positive_hardcoded_and_missing_interpret():
    hits = rules_hit("""
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

        def run2(x):
            return pl.pallas_call(
                kernel,
                out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
            )(x)
    """, select="GT022")
    assert hits == [("GT022", 9), ("GT022", 16)]


def test_gt022_negative_threaded_interpret():
    assert rules_hit("""
        import jax
        from jax.experimental import pallas as pl
        from greptimedb_tpu.parallel.kernels import interpret_mode

        def kernel(x_ref, o_ref):
            o_ref[...] = x_ref[...] + x_ref[...]

        def run(x, interpret):
            return pl.pallas_call(
                kernel,
                out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
                interpret=interpret,
            )(x)

        def run2(x):
            return pl.pallas_call(
                kernel,
                out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
                interpret=interpret_mode(),
            )(x)

        def run3(x, **kw):
            return pl.pallas_call(
                kernel,
                out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
                **kw,
            )(x)
    """, select="GT022") == []


def test_gt022_positive_unbound_device_id_axis():
    hits = rules_hit("""
        import jax
        from jax import shard_map
        from jax.experimental.pallas import tpu as pltpu
        from jax.sharding import PartitionSpec as P

        def run(mesh, x):
            def body(ref, o_ref):
                rdma = pltpu.make_async_remote_copy(
                    src_ref=ref, dst_ref=o_ref,
                    device_id=("time", 1),
                    device_id_type=pltpu.DeviceIdType.MESH,
                )
                rdma.start()

            return shard_map(body, mesh=mesh, in_specs=(P("shard"),),
                             out_specs=P("shard"))(x)
    """, select="GT022")
    assert hits == [("GT022", 9)]


def test_gt022_negative_bound_or_computed_device_id():
    # mesh-form device_id naming the bound axis: clean
    assert rules_hit("""
        import jax
        from jax import shard_map
        from jax.experimental.pallas import tpu as pltpu
        from jax.sharding import PartitionSpec as P

        def run(mesh, x):
            def body(ref, o_ref):
                rdma = pltpu.make_async_remote_copy(
                    src_ref=ref, dst_ref=o_ref,
                    device_id=("shard", 1),
                    device_id_type=pltpu.DeviceIdType.MESH,
                )
                rdma.start()

            return shard_map(body, mesh=mesh, in_specs=(P("shard"),),
                             out_specs=P("shard"))(x)
    """, select="GT022") == []
    # computed logical device id: identifiers are index arithmetic,
    # not axis names; the axis_index subtree is GT013's domain
    assert rules_hit("""
        import jax
        from jax import shard_map
        from jax.experimental.pallas import tpu as pltpu
        from jax.sharding import PartitionSpec as P

        def run(mesh, x):
            def body(ref, o_ref):
                my = jax.lax.axis_index("shard")
                right = jax.lax.rem(my + 1, 4)
                rdma = pltpu.make_async_remote_copy(
                    src_ref=ref, dst_ref=o_ref,
                    device_id=(right,),
                    device_id_type=pltpu.DeviceIdType.LOGICAL,
                )
                rdma.start()

            return shard_map(body, mesh=mesh, in_specs=(P("shard"),),
                             out_specs=P("shard"))(x)
    """, select="GT022") == []
    # outside any shard_map body (a bare pallas kernel helper): no
    # binding to compare against, stays quiet
    assert rules_hit("""
        from jax.experimental.pallas import tpu as pltpu

        def kernel(ref, o_ref):
            rdma = pltpu.make_async_remote_copy(
                src_ref=ref, dst_ref=o_ref,
                device_id=("time", 1),
                device_id_type=pltpu.DeviceIdType.MESH,
            )
            rdma.start()
    """, select="GT022") == []


# ---------------------------------------------------------------------------
# GT033 full-label-plane predicate
# ---------------------------------------------------------------------------

def test_gt033_positive_compare_on_tag_values():
    hits = rules_hit("""
        import numpy as np

        def match(reg, value):
            vals = reg.tag_values("host")
            return np.flatnonzero(vals == value)
    """, select="GT033")
    assert ("GT033", 6) in hits


def test_gt033_positive_direct_call_and_codes_matrix():
    # compare directly on the call result, no intermediate name
    hits = rules_hit("""
        def match(reg, value):
            return reg.tag_values("host") != value
    """, select="GT033")
    assert ("GT033", 3) in hits
    # subscripted codes_matrix column through a local
    hits = rules_hit("""
        def match(reg, code, i):
            codes = reg.codes_matrix()
            return codes[:, i] == code
    """, select="GT033")
    assert ("GT033", 4) in hits


def test_gt033_positive_numpy_comparison_calls():
    hits = rules_hit("""
        import numpy as np

        def match(reg, wanted):
            vals = reg.tag_values("host")
            return np.isin(vals, wanted)
    """, select="GT033")
    assert ("GT033", 6) in hits


def test_gt033_negative_gathers_and_index_path():
    # gathering values by sid (no predicate) is the sanctioned use
    assert rules_hit("""
        def decode(reg, sids):
            return reg.tag_values("host")[sids]
    """, select="GT033") == []
    # routing through the index package is the fix, not a finding
    assert rules_hit("""
        from greptimedb_tpu import index

        def match(reg, value):
            return index.match_sids(reg, [("host", "eq", value)])
    """, select="GT033") == []
    # compares on unrelated arrays stay quiet
    assert rules_hit("""
        import numpy as np

        def f(rows, value):
            vals = rows.ts
            return np.flatnonzero(vals == value)
    """, select="GT033") == []


def test_gt033_negative_reassigned_name_untracked():
    # a name later rebound to something else is no longer the plane
    assert rules_hit("""
        def f(reg, other, value):
            vals = reg.tag_values("host")
            vals = other.column("host")
            return vals == value
    """, select="GT033") == []


def test_gt033_negative_exempt_paths():
    src = """\
def match(reg, value):
    vals = reg.tag_values("host")
    return vals == value
"""
    from greptimedb_tpu.tools.lint import lint_source
    for path in ("greptimedb_tpu/index/tag_index.py",
                 "greptimedb_tpu/storage/series.py"):
        act, _ = lint_source(path, src, select={"GT033"})
        assert act == [], path


if __name__ == "__main__":
    sys.exit(pytest.main([__file__, "-q"]))
