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
import pickle

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()

import filelock  # noqa: E402
import jax  # noqa: E402
import pytest  # noqa: E402

assert jax.default_backend() == "cpu"


def pytest_configure(config):
    # the tier-1 command deselects these with -m 'not slow' (ROADMAP.md);
    # registering the marker keeps that filter warning-free
    config.addinivalue_line(
        "markers",
        "slow: heavy end-to-end tests (bench subprocess pairs) excluded "
        "from tier-1 (the 1,470 s command of /root/TESTS_LAST_RUN.json) via "
        "-m 'not slow'",
    )


@pytest.fixture(scope="session")
def shared(tmp_path_factory):
    """``shared(name, build)``: what ``build()`` returns, built ONCE A RUN OF
    THE SUITE.  ``--dist load`` deals a module's tests to every worker, so a
    module fixture or a ``functools.cache`` is built once a worker; here the
    first worker to ask builds under a lock file in the run's common temp
    directory and pickles the value, and the others load it (pytest-xdist's
    documented pattern for a fixture that must run once), with this
    process's own dict in front.  For plain values that several tests assert
    on and that cost a compile or a long run: metrics dicts, rows, lowered
    texts; every call hands out a copy of its own.  Never a device array or a
    jitted function.  A worker that asks while another builds waits for it:
    where all of them would ask at once, build in parts and start each
    worker on another part (tests/test_zztelemetry.py).  A ``build`` that
    raises leaves nothing behind and the next to ask builds again."""
    base = tmp_path_factory.getbasetemp()
    if os.environ.get("PYTEST_XDIST_WORKER"):
        base = base.parent  # the workers' base temps are its children
    mine = {}

    def get(name: str, build):
        if name not in mine:
            path = base / f"shared-{name}.pkl"
            with filelock.FileLock(f"{path}.lock"):
                if not path.exists():
                    path.write_bytes(pickle.dumps(build()))
                mine[name] = path.read_bytes()
        return pickle.loads(mine[name])

    return get


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
