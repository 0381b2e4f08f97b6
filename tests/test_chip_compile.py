"""Ahead-of-time compiles for a described v5e: what the chip's compiler refuses.

Interpret mode cannot see Mosaic's lowering rules, scoped-VMEM limits or
HBM fit; the TPU compiler installed here can, for a chip that is described
and not attached (jax.experimental.topologies).  Each test compiles one
main-path program at its real size in this process — nothing runs — and
asserts the kernel is present and the program fits one chip's 16 GiB.

The topology is described inside a module fixture only (never at import,
in a skipif or a parametrize): one process at a time may load libtpu, and
every xdist worker imports this file.  Keep these compiles in this one file.
"""

import os
import re

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, SingleDeviceSharding

from mpi_cuda_process_tpu import make_stencil
from mpi_cuda_process_tpu.driver import make_runner

HBM_BYTES = 16 * 1024**3  # v5e (Google Cloud documentation, "TPU v5e")


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # compiles for a described chip cannot be read back: keep them out of
    # any persistent cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, shapes):
    return jax.jit(fn).lower(*shapes).compile()


def _peak_bytes(compiled) -> int:
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            - m.alias_size_in_bytes + m.temp_size_in_bytes)


def _fields(stencil, shape, sharding):
    return tuple(jax.ShapeDtypeStruct(shape, stencil.dtype,
                                      sharding=sharding)
                 for _ in range(stencil.num_fields))


def _check(compiled, kernel="tpu_custom_call"):
    assert kernel in compiled.as_text()
    assert _peak_bytes(compiled) <= HBM_BYTES, _peak_bytes(compiled)


def test_main_path_fused_heat3d_1024(one_chip):
    """chip_smoke's main phase: the auto-fused heat3d runner at 1024^3,
    donated scan of 16 k=4 passes, exactly as maybe_auto_fuse + build
    construct it."""
    from mpi_cuda_process_tpu.ops.pallas.fused import (
        make_fused_step, prefer_padfree,
    )

    st = make_stencil("heat3d")
    grid = (1024, 1024, 1024)
    step = make_fused_step(st, grid, 4, interpret=False,
                           padfree=prefer_padfree(st, grid))
    assert step is not None
    run = make_runner(step, 16, jit=False)
    compiled = jax.jit(run, donate_argnums=0).lower(
        _fields(st, grid, one_chip)).compile()
    _check(compiled)


@pytest.mark.parametrize("n", [1, 2, 3, 4, 16])
def test_fused_heat3d_1024_runner_copies_no_state(one_chip, n):
    """The pad-free pass cannot write the buffer it reads.  Donated one
    pass per scan iteration, XLA copied the 4 GiB state into a temporary
    before every pass.  An even count now ping-pongs two passes per
    iteration between the donated buffer and one temporary, and a lone
    pass is not donated: no whole-state copy.  An odd count of 3 or more
    keeps one pass per iteration, and every count holds the 8 GiB peak
    (state plus one temporary) that one pass per iteration had."""
    from mpi_cuda_process_tpu.ops.pallas.fused import (
        make_fused_step, prefer_padfree,
    )

    st = make_stencil("heat3d")
    grid = (1024, 1024, 1024)
    step = make_fused_step(st, grid, 4, interpret=False,
                           padfree=prefer_padfree(st, grid))
    compiled = make_runner(step, n).lower(
        _fields(st, grid, one_chip)).compile()
    text = compiled.as_text()
    assert _peak_bytes(compiled) <= 8 * 1024**3 + 2**20, \
        _peak_bytes(compiled)
    state = r"f32\[1024,1024,1024\]\{[^}]*\} "
    kernels = re.findall(state + r"custom-call\(", text)
    copies = re.findall(state + r"copy(-start)?\(", text)
    if n > 1 and n % 2:
        assert len(kernels) == 1
    else:
        assert len(kernels) == min(n, 2)
        assert not copies


@pytest.mark.slow  # about 75 s: Mosaic compiles the 27-point k=4 pass
def test_fused_heat3d27_1024_runner_copies_no_state(one_chip):
    """BASELINE config 4's per-chip share, heat3d27 1024^3 f32, as
    --compute auto builds it for 16 steps a chunk: four pad-free k=4
    passes, two a scan iteration, ping-ponged between the donated state
    and one temporary.  No whole-state copy; the state and that
    temporary are the peak."""
    from mpi_cuda_process_tpu.ops.pallas.fused import (
        make_fused_step, prefer_padfree,
    )

    st = make_stencil("heat3d27")
    grid = (1024, 1024, 1024)
    assert prefer_padfree(st, grid)
    step = make_fused_step(st, grid, 4, interpret=False, padfree=True)
    assert step._carry_period == 2
    compiled = make_runner(step, 4).lower(
        _fields(st, grid, one_chip)).compile()
    text = compiled.as_text()
    assert _peak_bytes(compiled) <= 8 * 1024**3 + 2**20, \
        _peak_bytes(compiled)
    state = r"f32\[1024,1024,1024\]\{[^}]*\} "
    assert len(re.findall(state + r"custom-call\(", text)) == 2
    assert not re.findall(state + r"copy(-start)?\(", text)


def test_raw_wave3d_512(one_chip):
    from mpi_cuda_process_tpu.ops.pallas.rawstep import make_raw_step

    st = make_stencil("wave3d")
    grid = (512, 512, 512)
    step = make_raw_step(st, grid, interpret=False)
    assert step is not None
    _check(_compile(step, (_fields(st, grid, one_chip),)))


def test_wave3d_1024_auto_runner_holds_the_state_alone(one_chip):
    """BASELINE config 5's per-chip share, 1024^3 f32 wave, as --compute
    auto builds it.  Fused k=4 writes both 4 GiB fields anew: the budget
    refuses it, as the compiler does.  The in-place leapfrog runner (two
    steps per scan iteration) holds the 8 GiB state and nothing else: no
    temporaries, no whole-field copy."""
    from mpi_cuda_process_tpu.ops.pallas.fused import (
        make_fused_step, prefer_padfree,
    )
    from mpi_cuda_process_tpu.ops.pallas.rawstep import make_raw_step
    from mpi_cuda_process_tpu.utils import budget

    st = make_stencil("wave3d")
    grid = (1024, 1024, 1024)
    fields = _fields(st, grid, one_chip)
    with pytest.raises(ValueError, match="GiB per device"):
        budget.check_budget(st, grid, fuse=4, hbm_bytes=HBM_BYTES)
    fused = make_fused_step(st, grid, 4, interpret=False,
                            padfree=prefer_padfree(st, grid))
    with pytest.raises(Exception, match="hbm"):
        jax.jit(make_runner(fused, 4, jit=False),
                donate_argnums=0).lower(fields).compile()
    step = make_raw_step(st, grid, interpret=False)
    run = make_runner(step, 16, jit=False)
    compiled = jax.jit(run, donate_argnums=0).lower(fields).compile()
    _check(compiled)
    assert compiled.memory_analysis().temp_size_in_bytes < 2**20
    assert not re.search(r"f32\[1024,1024,1024\]\{[^}]*\} copy\(",
                         compiled.as_text())


def test_fused_wave3d_512(one_chip):
    from mpi_cuda_process_tpu.ops.pallas.fused import make_fused_step

    st = make_stencil("wave3d")
    grid = (512, 512, 512)
    step = make_fused_step(st, grid, 4, interpret=False)
    assert step is not None
    _check(_compile(step, (_fields(st, grid, one_chip),)))


def test_fullgrid_life_1024_k16(one_chip):
    from mpi_cuda_process_tpu.ops.pallas.fullgrid import make_fullgrid_step

    st = make_stencil("life")
    grid = (1024, 1024)
    step = make_fullgrid_step(st, grid, 16, interpret=False)
    assert step is not None
    _check(_compile(step, (_fields(st, grid, one_chip),)))


def test_fullgrid_gate_declines_what_the_compiler_refuses():
    """life 2048^2 k=16 needs 153.91 MiB of scoped VMEM (the v5e
    compiler's count): the builder's gate declines it, no chip needed."""
    from mpi_cuda_process_tpu.ops.pallas.fullgrid import make_fullgrid_step

    assert make_fullgrid_step(make_stencil("life"), (2048, 2048), 16,
                              interpret=False) is None


def test_stream_kernel_lowers(one_chip):
    """The streaming kernel with a traced (multi-strip) store offset: the
    store slice reads back through VMEM and outputs leave by DMA."""
    from mpi_cuda_process_tpu.ops.pallas.streamfused import (
        make_stream_fused_step,
    )

    st = make_stencil("heat3d")
    grid = (48, 64, 128)  # 3 z-chunks x 2 y strips
    step = make_stream_fused_step(st, grid, 4, interpret=False)
    assert step is not None
    _check(_compile(step, (_fields(st, grid, one_chip),)))


@pytest.mark.parametrize("kind", ["jnp", "fused4", "raw"])
def test_sharded_heat3d_2x2(topo, kind):
    """BASELINE config 3 on a 2x2 mesh of described chips: one
    512x512x1024 shard per chip, halos by collective-permute.  The raw
    case is the runner of the 2x2 cell, 8 sharded raw passes: two a scan
    iteration, ping-ponged between the donated shard and one temporary,
    so no shard-sized copy and at most one shard (plus the faces) of
    temporaries."""
    from mpi_cuda_process_tpu.parallel.mesh import make_mesh
    from mpi_cuda_process_tpu.parallel.stepper import (
        grid_partition_spec, make_sharded_fused_step, make_sharded_raw_step,
        make_sharded_step,
    )

    st = make_stencil("heat3d")
    grid = (1024, 1024, 1024)
    shard = 512 * 512 * 1024 * jnp.dtype(st.dtype).itemsize
    mesh = make_mesh((2, 2, 1), devices=topo.devices)
    sharding = NamedSharding(mesh, grid_partition_spec(3, mesh))
    if kind == "raw":
        step = make_sharded_raw_step(st, mesh, grid, interpret=False)
        assert step is not None
        compiled = make_runner(step, 8).lower(
            _fields(st, grid, sharding)).compile()
    else:
        if kind == "fused4":
            step = make_sharded_fused_step(st, mesh, grid, k=4,
                                           interpret=False)
            assert step is not None
        else:
            step = make_sharded_step(st, mesh, grid)
        run = make_runner(step, 4, jit=False)
        compiled = jax.jit(run, donate_argnums=0).lower(
            _fields(st, grid, sharding)).compile()
    text = compiled.as_text()
    assert "collective-permute" in text
    _check(compiled, "collective-permute" if kind == "jnp"
           else "tpu_custom_call")
    # per-device bytes: a quarter of the grid per chip
    assert compiled.memory_analysis().argument_size_in_bytes == shard
    if kind == "raw":
        assert not re.findall(
            r"f32\[512,512,1024\]\{[^}]*\} copy(-start)?\(", text)
        faces = 2 * (512 + 512) * 1024 * 4
        assert compiled.memory_analysis().temp_size_in_bytes <= \
            shard + faces
