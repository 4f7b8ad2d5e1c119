"""Test configuration: force an 8-virtual-device CPU platform.

The driver validates multi-chip sharding the same way (see
__graft_entry__.dryrun_multichip). The platform is pinned through the
jax config as well as the env var, so a machine with an accelerator
still runs the suite on CPU.
"""

import os

# GTPU_SAN=1 turns every run into a race/deadlock audit: the gtsan
# plugin enables the concurrency sanitizer before test modules import
# the package, fails tests that leak threads/pools, and reports
# lock-order cycles + blocking-under-lock at session end
if (os.environ.get("GTPU_SAN") or "").strip().lower() in (
        "1", "true", "on", "yes"):
    pytest_plugins = ["greptimedb_tpu.tools.san.pytest_plugin"]

os.environ.setdefault("XLA_FLAGS", "")
if "--xla_force_host_platform_device_count" not in os.environ["XLA_FLAGS"]:
    os.environ["XLA_FLAGS"] += " --xla_force_host_platform_device_count=8"
os.environ["JAX_PLATFORMS"] = "cpu"
# the suite (and every server child it spawns) runs without JAX's
# persistent compilation cache: a test run must leave nothing behind in
# the checkout's .jax_cache, and results must never depend on what an
# earlier run compiled. Tests of the cache itself turn it back on in a
# child, with JAX_COMPILATION_CACHE_DIR under their tmp_path.
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
# f64 available so golden tests can check semantics at Prometheus precision;
# the engine's device path stays explicitly f32/int32.
jax.config.update("jax_enable_x64", True)

import numpy as np  # noqa: E402
import pytest  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: long-running stress tests (tier-1 runs -m 'not slow')",
    )


@pytest.fixture(scope="session")
def devices():
    devs = jax.devices()
    assert len(devs) == 8, f"expected 8 CPU devices, got {devs}"
    return devs


@pytest.fixture
def rng():
    return np.random.default_rng(42)


def _build_native():
    """Best-effort build of the C extensions so the native-parity tests
    run instead of skipping (loaders fall back to Python when absent)."""
    import glob
    import pathlib
    import subprocess

    native = pathlib.Path(__file__).parent.parent / "greptimedb_tpu" / "native"
    if glob.glob(str(native / "_lineproto*.so")):
        return
    try:
        subprocess.run(
            ["make", "-C", str(native)],
            check=False, capture_output=True, timeout=120,
        )
    except Exception:
        pass


_build_native()
