"""The benchmark's plain heat3d reference against the program, tiny, on the CPU.

The reference (perfbench/references/heat7.py) shares no code with
ops/heat.py or driver.make_step; these pin that both compute the same
thing: the seeded initial state, the jnp step, and the interpret-mode
fused Pallas kernels (tiled and pad-free) that the one-chip cell runs.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mpi_cuda_process_tpu import make_stencil
from mpi_cuda_process_tpu.driver import make_step
from mpi_cuda_process_tpu.ops.pallas.fused import make_fused_step
from mpi_cuda_process_tpu.utils.init import init_state
from perfbench import spec

SHAPE = (32, 32, 128)
SEED = 2**31 + 21  # the driver's seeds pass 32 signed bits


@pytest.fixture(scope="module")
def heat():
    bench = spec.benchmark()
    config = dict(spec.config(bench, "heat3d-1024"))
    config["grid"] = list(SHAPE)
    return config, spec.reference(config)


@pytest.mark.parametrize("seed", [0, 7, 2**31 - 1, SEED, 2**32 - 1])
def test_reference_initial_state_is_the_programs(heat, seed):
    config, ref = heat
    st = make_stencil("heat3d")
    want = init_state(st, SHAPE, seed, config["density"], "random")[0]
    got = ref.initial_state(config, seed, jax.devices()[0])
    assert jnp.array_equal(got, want)


def test_reference_config_states_the_ops_numbers(heat):
    config, _ = heat
    st = make_stencil("heat3d")
    assert st.params == {"alpha": config["alpha"], "bc": config["bc"]}


@pytest.mark.parametrize("path", ["jnp", "fused", "fused_padfree"])
def test_reference_matches_program_steps(heat, path):
    config, ref = heat
    st = make_stencil("heat3d")
    k = 4
    u0 = ref.initial_state(config, SEED, jax.devices()[0])
    if path == "jnp":
        step = jax.jit(make_step(st, SHAPE))
        got = (u0,)
        for _ in range(k):
            got = step(got)
    else:
        fused = make_fused_step(st, SHAPE, k, interpret=True,
                                padfree=path == "fused_padfree")
        assert fused is not None
        got = jax.jit(fused)((u0,))
    want = ref.advance(jnp.copy(u0), k, config["alpha"])
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want),
                               rtol=0, atol=1e-5)


def test_reference_diagnostics_match_programs(heat):
    from mpi_cuda_process_tpu.utils.diagnostics import field_diagnostics

    config, ref = heat
    st = make_stencil("heat3d")
    u = ref.advance(ref.initial_state(config, SEED, jax.devices()[0]), 3,
                    config["alpha"])
    got = field_diagnostics(st, (u,), step_fn=make_step(st, SHAPE))
    want = ref.diagnostics(u, config, residual=True)
    assert set(got) == set(want)
    for key in want:
        assert got[key] == pytest.approx(want[key], rel=1e-5), key


@pytest.mark.parametrize("workload", ["heat3d-1024.log16",
                                      "heat3d-1024-2x2.log8"])
@pytest.mark.parametrize("seed", [7, SEED, 2**32 - 1])
def test_harness_build_is_the_clis(workload, seed):
    """The harness draws the state with the seed as an argument (one init
    program for every seed); it is the state and placement the CLI's
    ``cli.build`` gives for that seed."""
    from mpi_cuda_process_tpu import cli
    from perfbench import cell

    bench = spec.benchmark()
    w = spec.workload(bench, workload)
    config = dict(spec.config(bench, w["config"]), grid=list(SHAPE))
    cfg = cli.maybe_auto_fuse(cli.config_from_args(cell.program_argv(
        config, spec.traffic(w["traffic"]), seed)))
    _, _, want, _ = cli.build(cfg)
    _, _, got = cell.build(cfg)
    assert len(got) == len(want)
    for g, f in zip(got, want):
        assert g.sharding == f.sharding
        assert jnp.array_equal(g, f)
