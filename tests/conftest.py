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
