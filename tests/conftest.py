"""Test bootstrap: force an 8-device virtual CPU mesh BEFORE jax imports.

Multi-chip sharding paths (kvstore device mode, parallel/ trainers) are
exercised on virtual CPU devices exactly as the driver's dryrun does; the
chip is checked by chip_smoke.py, not by the unit suite.
"""
import os
import sys
import tempfile

import pytest

os.environ["JAX_PLATFORMS"] = "cpu"  # the suite never touches a chip
# nor a cache that the machine shares: JAX_COMPILATION_CACHE_DIR wins over
# MXNET_COMPILE_CACHE_DIR (compile_cache.configure), so with it inherited
# the cache tests' children would read and fill that one, not their own
os.environ.pop("JAX_COMPILATION_CACHE_DIR", None)
# the suite compiles programs for the host's CPU to check what they compute,
# not to time them: LLVM's code generation at its first level in place of
# the default, about 70% of the CPU time.  The tests that hold recorded
# bytes pass under it (level 0, or LLVM's expensive passes turned off,
# change those bytes).  A compile for a described TPU is untouched by it
CPU_COMPILE_FLAGS = "--xla_backend_optimization_level=1"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_backend_optimization_level" not in flags:
    flags += " " + CPU_COMPILE_FLAGS
if "xla_force_host_platform_device_count" not in flags:
    flags += " --xla_force_host_platform_device_count=8"
os.environ["XLA_FLAGS"] = flags.strip()

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# Where no bytecode is written (PYTHONDONTWRITEBYTECODE), every process the
# suite starts (the workers, and the rehearsals, servers and children of the
# tests that run a program) compiles jax's sources and this package's anew:
# about 4 s of CPU a process.  They share one bytecode cache outside the
# tree instead; a cached file is used only while its source is unchanged.
PYCACHE = os.path.join(tempfile.gettempdir(), "mxnet_tpu_tests_pycache")
os.environ.pop("PYTHONDONTWRITEBYTECODE", None)
os.environ["PYTHONPYCACHEPREFIX"] = PYCACHE
sys.dont_write_bytecode = False
sys.pycache_prefix = PYCACHE


# The files that take longest, longest first (the per-file times of a
# whole run, ``--junitxml``).  Under ``--dist loadfile`` a file is one
# worker's serial job, and xdist starts the files with the most cases
# first: these have few, so one of them started last held the run's end on
# its own.  They start first; the rest follow as xdist would order them,
# most cases first.  Replayed on the case times of four whole runs, this
# order ends the run 10-14% sooner than xdist's own.  The runs have no
# durations of their own to order by (no cache, a fresh checkout)
LONGEST_FIRST = (
    "test_tpu_compile.py", "test_kimi_linear_faults.py",
    "test_kimi_linear_model.py", "test_joyai_llm_flash_faults.py",
    "test_nemotron_h.py", "test_joyai_llm_flash_model.py",
    "test_kimi_linear.py", "test_joyai_llm_flash.py", "test_kda_kernels.py",
    "test_serve_e2e.py", "test_qwen3_next.py", "test_sdar_moe.py",
    "test_bd_kernels.py",
    "test_mla_flash_kernels.py",
)


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: excluded from the budgeted tier-1 run (-m 'not slow'); "
        "the CI chaos jobs run slow-marked suites explicitly")
    # the order below is the one the workers are handed
    config.option.loadscopereorder = False


def pytest_collection_modifyitems(config, items):
    counts = {}
    for item in items:
        counts[item.path] = counts.get(item.path, 0) + 1

    def rank(item):
        name = item.path.name
        if name in LONGEST_FIRST:
            return (LONGEST_FIRST.index(name), 0)
        return (len(LONGEST_FIRST), -counts[item.path])
    items.sort(key=rank)        # stable: a file's cases keep their order


@pytest.fixture
def one_device_env():
    """The environment of a child process that wants one CPU device, not
    the eight of this one, its programs compiled as this one's are."""
    return dict(os.environ, JAX_PLATFORMS="cpu", XLA_FLAGS=CPU_COMPILE_FLAGS)


@pytest.fixture(scope="module")
def rehearsal_env(tmp_path_factory):
    """The environment of a module's benchmark rehearsals, each run in a
    process of its own on one CPU device: they share a compile cache
    (``MXNET_COMPILE_CACHE``), so that what the first compiles and no
    planted fault changes (the harness's draws, the reference, the
    comparisons) the next ones load."""
    return dict(os.environ, JAX_PLATFORMS="cpu", XLA_FLAGS=CPU_COMPILE_FLAGS,
                MXNET_COMPILE_CACHE=str(tmp_path_factory.mktemp("cache")))
