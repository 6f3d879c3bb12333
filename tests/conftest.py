"""Test harness config: force a deterministic 8-device CPU platform.

Multi-chip hardware is not available in CI; sharding tests run on a virtual
8-device CPU mesh (same XLA collectives, same GSPMD partitioner) — the driver
separately dry-run-compiles the multi-chip path via ``__graft_entry__``.

The platform is forced to cpu through both the env vars and ``jax.config``
(the latter covers a jax that was imported before this file ran).
"""
import fcntl
import os
import sys
import tempfile

import pytest

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["JAX_PLATFORM_NAME"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: minutes-long scale tests (rung 4+ of the ladder)"
    )


#: one file for every pytest process of this user's temp directory
HEAVY_LOCK = os.path.join(
    tempfile.gettempdir(), "dragonboat_tpu-heavy-multiprocess.lock"
)


@pytest.hookimpl(hookwrapper=True)
def pytest_runtest_protocol(item, nextitem):
    """A test marked ``xdist_group("heavy-multiprocess")`` holds an
    exclusive lock from before its fixtures are set up until they are
    torn down, so live multi-process clusters never run side by side,
    whatever ``--dist`` mode (or none) is in use; the mark's own effect
    exists only under ``--dist loadgroup``.  Around the whole protocol and
    not in a fixture: the wait is then charged to no test's duration, and
    a module-scoped cluster starts inside the lock too.  Nothing is taken
    for an unmarked test."""
    mark = item.get_closest_marker("xdist_group")
    if mark is None or "heavy-multiprocess" not in (
        *mark.args, mark.kwargs.get("name")
    ):
        yield
        return
    with open(HEAVY_LOCK, "w") as f:
        fcntl.flock(f, fcntl.LOCK_EX)
        yield
