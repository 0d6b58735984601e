"""Test configuration.

Tests run on a virtual 8-device CPU mesh so multi-device sharding paths
are exercised without TPU hardware (mirrors the reference's use of
multiple mx.cpu(i) fake contexts, SURVEY.md §4). Must run before jax
import anywhere in the test process.
"""
import atexit
import os
import shutil
import tempfile

os.environ["JAX_PLATFORMS"] = "cpu"
# The compile cache is always on (programs.configure_compile_cache) and
# placed from outside: the test session gets a fresh directory, so no
# test that counts real compiles inherits a warm cache from an earlier
# run, and nothing is written into the checkout.
_cache = tempfile.mkdtemp(prefix="mxnet_tpu_test_jax_cache_")
os.environ["JAX_COMPILATION_CACHE_DIR"] = _cache
atexit.register(shutil.rmtree, _cache, ignore_errors=True)
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()
os.environ.setdefault("JAX_ENABLE_X64", "0")


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: long-running end-to-end test")
