"""Force the CPU platform with N virtual devices, for tests and rehearsals.

``--xla_force_host_platform_device_count`` only takes effect at backend
initialization, so the env vars (and the in-process ``jax.config.update``)
must land before first backend use.  Used by ``tests/conftest.py``,
``__graft_entry__.py``'s dryrun child and multi-process test harness
children.

Lives at the repo root (NOT inside the package) on purpose: importing it must
not execute ``mpi_cuda_process_tpu/__init__``'s import chain, so env vars are
guaranteed to be set before any framework module — and hence any possible jax
backend touch — loads.  No top-level ``jax`` import either: callers control
when jax first loads.
"""

from __future__ import annotations

import os
import re

_COUNT_FLAG = "--xla_force_host_platform_device_count"


def cpu_flags(n_devices: int, flags: str = "") -> str:
    """Return ``flags`` with the virtual-device-count flag set to exactly N."""
    flags = re.sub(rf"{_COUNT_FLAG}=\d+", "", flags)
    return f"{flags} {_COUNT_FLAG}={n_devices}".strip()


def cpu_env(n_devices: int, base: dict | None = None) -> dict:
    """Environment for a child process that must run CPU-only with N devices.

    The child may also call :func:`force_cpu` before first backend use.
    """
    env = dict(os.environ if base is None else base)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = cpu_flags(n_devices, env.get("XLA_FLAGS", ""))
    return env


def force_cpu(n_devices: int | None = None) -> None:
    """In-process CPU forcing; call before any jax backend use.

    ``n_devices=None`` leaves any existing device-count flag untouched (so an
    outer harness can choose the count via ``XLA_FLAGS``).
    """
    os.environ["JAX_PLATFORMS"] = "cpu"
    if n_devices is not None:
        os.environ["XLA_FLAGS"] = cpu_flags(
            n_devices, os.environ.get("XLA_FLAGS", "")
        )
    os.environ.setdefault("JAX_ENABLE_X64", "0")
    import jax

    jax.config.update("jax_platforms", "cpu")
