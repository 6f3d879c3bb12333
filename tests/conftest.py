"""Test harness config: force a deterministic 8-device CPU platform.

Multi-chip hardware is not available in CI; sharding tests run on a virtual
8-device CPU mesh (same XLA collectives, same GSPMD partitioner) — the driver
separately dry-run-compiles the multi-chip path via ``__graft_entry__``.

The platform is forced to cpu through both the env vars and ``jax.config``
(the latter covers a jax that was imported before this file ran).
"""
import os
import sys

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
