"""API-drift smoke tier: every module imports, every pallas symbol resolves.

The round-5 seed failure mode was ``pltpu.CompilerParams`` vanishing from
the installed JAX and taking SIX test modules down as opaque collection
errors.  This module turns that class of breakage into one named test
each: (a) every package module imports, (b) the compat resolver found a
compiler-params class, (c) every other ``pltpu`` / jax symbol the package
references still exists.  Runs in milliseconds — it is the first thing to
read when a JAX upgrade lands.
"""

import importlib
import pkgutil

import pytest

import mpi_cuda_process_tpu


def _all_module_names():
    names = ["mpi_cuda_process_tpu"]
    for m in pkgutil.walk_packages(mpi_cuda_process_tpu.__path__,
                                   prefix="mpi_cuda_process_tpu."):
        names.append(m.name)
    return names


@pytest.mark.parametrize("name", _all_module_names())
def test_module_imports(name):
    importlib.import_module(name)


def test_compiler_params_resolves():
    from mpi_cuda_process_tpu.ops.pallas.compat import (
        CompilerParams, compiler_params,
    )

    from jax.experimental.pallas import tpu as pltpu

    assert CompilerParams is pltpu.CompilerParams  # the installed name
    p = compiler_params(vmem_limit_bytes=1 << 20,
                        dimension_semantics=("arbitrary",))
    assert p.vmem_limit_bytes == 1 << 20


def test_required_pltpu_symbols_present():
    from mpi_cuda_process_tpu.ops.pallas.compat import (
        REQUIRED_PLTPU_SYMBOLS, missing_pltpu_symbols,
    )

    assert missing_pltpu_symbols() == [], (
        "pltpu API drift: update ops/pallas/compat.py and the call sites")
    assert len(REQUIRED_PLTPU_SYMBOLS) >= 5


def test_shard_map_resolves():
    # the installed spelling: jax.shard_map with the check_vma kwarg
    import inspect

    import jax

    from mpi_cuda_process_tpu.parallel.stepper import shard_map

    assert callable(shard_map)
    assert "check_vma" in inspect.signature(jax.shard_map).parameters


def test_pallas_blockspec_memory_space_kwarg():
    # the SMEM/ANY BlockSpec spelling the kernels rely on
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    pl.BlockSpec(memory_space=pltpu.SMEM)
    pl.BlockSpec(memory_space=pl.ANY)
