"""The one place that decides which jax platform a process runs on.

Tests, dry runs and explicit rehearsals call :func:`force_cpu` before first
backend use; anything that reports a device result calls
:func:`require_tpu`, which raises instead of measuring whatever backend jax
happened to pick.
"""
from __future__ import annotations

import os
import re


def force_cpu() -> None:
    """Force the CPU platform.

    Safe to call before or after ``import jax`` but must run before the
    first backend init in this process.
    """
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["JAX_PLATFORM_NAME"] = "cpu"
    import jax

    jax.config.update("jax_platforms", "cpu")


def require_tpu(count: int = 1) -> list:
    """Return ``jax.devices()`` iff it holds at least ``count`` TPU devices.

    Raises ``RuntimeError`` naming what jax returned otherwise: a device
    run never quietly becomes a CPU run.  Initializes the backend, so the
    calling process owns the chip from here on.
    """
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < count:
        raise RuntimeError(
            f"need {count} TPU device(s); jax.devices() returned "
            f"{[(d.platform, d.device_kind) for d in devs]}"
        )
    return devs


def set_host_device_count(n: int) -> None:
    """Ensure XLA_FLAGS requests >= n virtual host (CPU) devices.

    Replaces any existing smaller ``--xla_force_host_platform_device_count``
    value instead of substring-checking, so a stale count from the caller's
    environment cannot survive.
    """
    flags = os.environ.get("XLA_FLAGS", "")
    m = re.search(r"--xla_force_host_platform_device_count=(\d+)", flags)
    if m:
        if int(m.group(1)) >= n:
            return
        flags = re.sub(
            r"--xla_force_host_platform_device_count=\d+",
            f"--xla_force_host_platform_device_count={n}",
            flags,
        )
    else:
        flags = (flags + f" --xla_force_host_platform_device_count={n}").strip()
    os.environ["XLA_FLAGS"] = flags


def clear_backends() -> None:
    """Reset jax's backend cache (e.g. after flag changes)."""
    import jax.extend.backend as _eb

    _eb.clear_backends()
