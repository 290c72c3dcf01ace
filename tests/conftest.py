"""Test configuration: the CPU backend with 8 virtual devices.

The reference needs no cluster because ns-3 simulates all N nodes in one
process (SURVEY.md §4); likewise these tests need no TPU — the JAX CPU backend
with a virtual 8-device mesh exercises every code path including sharding.
Tests and rehearsals run with ``JAX_PLATFORMS=cpu``; the chip is reached only
by sending ``python chip_smoke.py`` through the chip tool.

Both variables must be set before jax is imported: the platform list is read
at import and the host device count at backend init.  Children that tests
spawn inherit them through ``os.environ``.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

assert jax.default_backend() == "cpu"


def pytest_configure(config):
    # the tier-1 command deselects these with -m 'not slow' (ROADMAP.md);
    # registering the marker keeps that filter warning-free
    config.addinivalue_line(
        "markers",
        "slow: heavy end-to-end tests (bench subprocess pairs) excluded "
        "from the tier-1 870 s window via -m 'not slow'",
    )


def _mapped_regions() -> int:
    try:
        with open("/proc/self/maps") as f:
            return sum(1 for _ in f)
    except OSError:  # no /proc: nothing to count, nothing to drop
        return 0


def _map_limit() -> int:
    try:
        with open("/proc/sys/vm/max_map_count") as f:
            return int(f.read())
    except (OSError, ValueError):
        return 65530


def pytest_runtest_teardown(item, nextitem):
    """XLA:CPU maps memory for every executable it compiles (500 to 3,000
    regions a test in the engine files), jax keeps every executable, and a
    process may hold ``vm.max_map_count`` regions (65,530).  A worker that
    draws the compile-heavy files in a row passes it: LLVM fails with
    ``Cannot allocate memory`` and the worker aborts inside
    ``backend_compile_and_load`` (PR 32: ``tests/test_raft_hb.py`` in three
    whole runs of three; the parent commit aborts alike on that worker's
    order of tests).  So when a process moves on to another module with a
    quarter of the limit mapped, drop jax's executables (8,163 regions -> 691
    after ``tests/test_differential.py``; six tests of ``test_raft_hb.py`` add
    23,000), and
    past three quarters drop them between any two tests: a recompile is
    better than an abort.  Nothing a module holds is lost: a jitted function
    compiles again when called."""
    mapped, limit = _mapped_regions(), _map_limit()
    between_modules = nextitem is None or nextitem.module is not item.module
    if mapped > 3 * limit // 4 or (between_modules and mapped > limit // 4):
        import gc

        jax.clear_caches()
        gc.collect()
