"""Test bootstrap: force an 8-device virtual CPU mesh BEFORE jax imports.

Multi-chip sharding paths (kvstore device mode, parallel/ trainers) are
exercised on virtual CPU devices exactly as the driver's dryrun does; the
chip is checked by chip_smoke.py, not by the unit suite.
"""
import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"  # the suite never touches a chip
# nor a cache that the machine shares: JAX_COMPILATION_CACHE_DIR wins over
# MXNET_COMPILE_CACHE_DIR (compile_cache.configure), so with it inherited
# the cache tests' children would read and fill that one, not their own
os.environ.pop("JAX_COMPILATION_CACHE_DIR", None)
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: excluded from the budgeted tier-1 run (-m 'not slow'); "
        "the CI chaos jobs run slow-marked suites explicitly")
