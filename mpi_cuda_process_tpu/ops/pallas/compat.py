"""The Pallas-TPU symbols this package uses, pinned to the installed JAX.

Every pallas module builds its compiler params through
:func:`compiler_params`; the ``pltpu`` names the kernels touch are listed
in :data:`REQUIRED_PLTPU_SYMBOLS` so tests/test_compat.py fails as ONE
named assertion — not as collection errors — the day any of them drifts.
"""

from __future__ import annotations

from jax.experimental.pallas import tpu as pltpu

# Symbols the pallas modules reference directly off ``pltpu``; audited from
# the package source (grep ``pltpu\.``).
REQUIRED_PLTPU_SYMBOLS = (
    "roll",
    "SMEM",
    "VMEM",
    "SemaphoreType",
    "make_async_copy",
    # the in-kernel remote-DMA exchange (ops/pallas/remote.py)
    "make_async_remote_copy",
    "get_barrier_semaphore",
    "semaphore_signal",
    "semaphore_wait",
    "DeviceIdType",
)


CompilerParams = pltpu.CompilerParams


def compiler_params(**kwargs):
    """The TPU compiler-params object (``vmem_limit_bytes``,
    ``dimension_semantics``, ...)."""
    return CompilerParams(**kwargs)


def missing_pltpu_symbols():
    """Names from :data:`REQUIRED_PLTPU_SYMBOLS` absent in this JAX —
    empty on a healthy install (asserted by tests/test_compat.py)."""
    return [s for s in REQUIRED_PLTPU_SYMBOLS if not hasattr(pltpu, s)]


def interpret_remote_dma_supported() -> bool:
    """Can interpret mode discharge a REMOTE ``dma_start`` under this
    package's meshes?

    JAX 0.4.x's interpret-mode discharge rule for remote copies
    (``jax/_src/pallas/mosaic/primitives.py::dma_start_discharge_rule``)
    raises ``NotImplementedError`` whenever more than one named mesh
    axis is in scope — and every mesh this package builds carries all
    three spatial names (``parallel/mesh.SPATIAL_AXES``), so the rule
    never applies here.  The rdma transport therefore runs its
    interpret-mode path through the LOOPBACK kernel + an explicit
    ``all_gather`` ring shift (``ops/pallas/remote.py`` module
    docstring) and tags telemetry accordingly.  If a future JAX grows
    multi-axis interpret support (the 0.5.x ``InterpretParams``
    simulator), flip the decision HERE — every caller routes through
    this predicate, the version-tolerance discipline of this module.
    """
    return False
