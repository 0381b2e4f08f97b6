"""The cell added with the heat3d27 configuration, tiny, on the CPU: the
plain 27-point reference (perfbench/references/heat27.py) against the
program, the cell run end to end and its control, and the fused-pass work
record with the reader that the cell adds.

The reference shares no code with ops/heat.py, driver.make_step or the
Pallas kernels; these pin that all compute the same thing: the seeded
initial state, the jnp step, the raw 27-point kernel, and the pad-free
fused k=4 kernel the cell runs on the chip (interpret mode).
"""

import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mpi_cuda_process_tpu import make_stencil
from mpi_cuda_process_tpu.driver import make_runner, make_step
from mpi_cuda_process_tpu.obs import runtime
from mpi_cuda_process_tpu.ops import heat
from mpi_cuda_process_tpu.ops.pallas.fused import make_fused_step
from mpi_cuda_process_tpu.ops.pallas.rawstep import make_raw_step
from mpi_cuda_process_tpu.utils.diagnostics import field_diagnostics
from mpi_cuda_process_tpu.utils.init import init_state
from perfbench import cell, check, control, spec

SHAPE = (32, 32, 128)
SEED = 2**31 + 21  # a seed past 32 signed bits, as benchmark seeds run
WORKLOAD = "heat3d27-1024.log16"
CONFIG = "heat3d27-1024"


@pytest.fixture(scope="module")
def heat27():
    bench = spec.benchmark()
    config = dict(spec.config(bench, CONFIG), grid=list(SHAPE))
    return config, spec.reference(config)


@pytest.mark.parametrize("seed", [0, 7, 2**31 - 1, SEED, 2**32 - 1])
def test_reference_initial_state_is_the_programs(heat27, seed):
    config, ref = heat27
    st = make_stencil("heat3d27")
    want = init_state(st, SHAPE, seed, config["density"], "random")[0]
    got = ref.initial_state(config, seed, jax.devices()[0])
    assert jnp.array_equal(got, want)


def test_reference_config_states_the_ops_numbers(heat27):
    config, ref = heat27
    st = make_stencil("heat3d27")
    assert st.params == {"alpha": config["alpha"], "bc": config["bc"]}
    assert st.bc_value == (config["bc"],)
    assert tuple(w / 30.0 for w in ref._W30) == (
        heat._W_CENTER, heat._W_FACE, heat._W_EDGE, heat._W_CORNER)
    assert config["stencil"] == "heat3d27" and config["dtype"] == "float32"


# Each path sums the 27 taps in another order (the kernels by separable
# per-level partial sums, the reference tap by tap), so f32 rounding
# differs by a few ulp of the field's scale (the frame's 100): 3 ulp seen
# over 16 steps.  16 ulp leaves room for an FMA contraction another
# compiler may make; one bf16 step misses it by over a thousand times
# (test_bfloat16_step_fails_the_tolerance).
ULPS = 16


def _atol(config):
    return ULPS * float(jnp.finfo(jnp.float32).eps) * abs(config["bc"])


@pytest.mark.parametrize("path", ["jnp", "raw", "fused_padfree",
                                  "fused_padfree_16x16"])
@pytest.mark.parametrize("steps", [8, 16])
def test_reference_matches_program_steps(heat27, path, steps):
    config, ref = heat27
    st = make_stencil("heat3d27")
    k = 4 if path.startswith("fused") else 1
    if path == "jnp":
        step = make_step(st, SHAPE)
    elif path == "raw":
        step = make_raw_step(st, SHAPE, interpret=True)
    else:
        # the cell's pass; 16x16 tiles put seams between programs
        step = make_fused_step(st, SHAPE, k, interpret=True, padfree=True,
                               tiles=(16, 16) if "16x16" in path else None)
    assert step is not None
    u0 = ref.initial_state(config, SEED, jax.devices()[0])
    got = make_runner(step, steps // k)((jnp.copy(u0),))[0]
    want = ref.advance(jnp.copy(u0), steps, config["alpha"])
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=0,
                               atol=_atol(config))


def test_bfloat16_step_fails_the_tolerance(heat27):
    config, ref = heat27
    u0 = ref.initial_state(config, SEED, jax.devices()[0])
    want = ref.advance(jnp.copy(u0), 1, config["alpha"])
    low = ref.advance(u0.astype(jnp.bfloat16), 1, config["alpha"])
    gap = float(jnp.max(jnp.abs(low.astype(jnp.float32) - want)))
    assert gap > 1000 * _atol(config)


def test_reference_diagnostics_match_programs(heat27):
    config, ref = heat27
    st = make_stencil("heat3d27")
    u = ref.advance(ref.initial_state(config, SEED, jax.devices()[0]), 3,
                    config["alpha"])
    got = field_diagnostics(st, (u,), step_fn=make_step(st, SHAPE))
    want = ref.diagnostics(u, config, residual=True)
    assert set(got) == set(want) == {"mean", "min", "max", "residual"}
    for key in want:
        assert got[key] == pytest.approx(want[key], rel=1e-5), key


@pytest.mark.parametrize("seed", [7, SEED, 2**32 - 1])
def test_harness_build_is_the_clis(seed):
    from mpi_cuda_process_tpu import cli

    bench = spec.benchmark()
    w = spec.workload(bench, WORKLOAD)
    config = dict(spec.config(bench, w["config"]), grid=list(SHAPE))
    argv = cell.program_argv(config, spec.traffic(w["traffic"]), seed)
    assert argv[argv.index("--stencil") + 1] == "heat3d27"
    assert argv[argv.index("--compute") + 1] == "auto"
    assert argv[argv.index("--log-every") + 1] == "16"
    assert "--param" not in argv
    cfg = cli.maybe_auto_fuse(cli.config_from_args(argv))
    _, _, want, _ = cli.build(cfg)
    _, _, got = cell.build(cfg)
    assert len(got) == len(want) == 1
    assert got[0].sharding == want[0].sharding
    assert jnp.array_equal(got[0], want[0])


def _run(monkeypatch):
    monkeypatch.setattr(check, "FOLLOW", 2)
    bench = spec.benchmark()
    w = spec.workload(bench, WORKLOAD)
    config = dict(spec.config(bench, w["config"]), grid=list(SHAPE))
    record = cell.run(config, spec.traffic(w["traffic"]), SEED, 0.05,
                      jax.devices()[:w["chips"]], time.perf_counter(),
                      say=lambda msg: None)
    return cell.verify(record, jax.devices()[0])


def test_sound_run_is_correct(monkeypatch):
    correct, checks = _run(monkeypatch)
    assert correct, checks


def test_unchanged_state_is_not_correct(monkeypatch):
    """A runner that hands the state back unstepped fails the check."""
    from mpi_cuda_process_tpu import driver

    real = driver.make_runner
    monkeypatch.setattr(driver, "make_runner",
                        lambda step_fn, n, **kw: real(lambda f: f, n, **kw))
    correct, checks = _run(monkeypatch)
    assert not correct, checks


@pytest.mark.parametrize("residual", [False, True])
@pytest.mark.parametrize("seed", [7, 2**31 + 99, 3_000_000_001])
def test_bfloat16_control_is_not_correct(seed, residual):
    bench = spec.benchmark()
    w = spec.workload(bench, WORKLOAD)
    config = dict(spec.config(bench, w["config"]), grid=list(SHAPE))
    numbers = control.control_numbers(config, spec.traffic(w["traffic"]),
                                      seed, jax.devices()[0], residual)
    correct, checks = check.judge(numbers, config["limits"])
    assert not correct, checks


# ----------------------------------------------- the fused-pass work record

@pytest.mark.parametrize("padfree,tiles,wm", [
    (True, None, 8), (True, (16, 16), 8), (False, (8, 16), 4)])
def test_work_record_holds_the_built_tiles(padfree, tiles, wm):
    st = make_stencil("heat3d27")
    step = make_fused_step(st, SHAPE, 4, interpret=True, padfree=padfree,
                           tiles=tiles)
    assert step is not None
    rec = runtime.fused_passes()[-1]
    assert rec == {"name": "fused_padfree" if padfree else "fused_padded",
                   "tiles": tiles or (32, 32), "window_margin": wm, "k": 4,
                   "core_shape": SHAPE}


def _useful(run):
    return spec.reader("fused_kernel_useful_frac")(run)


@pytest.mark.parametrize("stencil", ["heat3d", "heat3d27"])
def test_useful_frac_reads_the_1024_pass(stencil):
    """The cells' pass: (32, 32) tiles in a window 8 rows wider a side."""
    grid = (1024, 1024, 1024)
    assert make_fused_step(make_stencil(stencil), grid, 4, interpret=False,
                           padfree=True) is not None
    run = {"fuse_k": 4, "shard_shape": grid}
    assert _useful(run) == pytest.approx(1024 / 2304)


def test_useful_frac_is_none_without_a_record(monkeypatch):
    grid = (1024, 1024, 1024)
    make_fused_step(make_stencil("heat3d27"), grid, 4, interpret=False,
                    padfree=True)
    # an unfused run, a shape no pass was built for
    assert _useful({"fuse_k": 1, "shard_shape": grid}) is None
    assert _useful({"fuse_k": 4, "shard_shape": (8, 8, 8)}) is None
    # a program that keeps no record
    monkeypatch.setitem(sys.modules, "mpi_cuda_process_tpu.obs.runtime",
                        None)
    assert _useful({"fuse_k": 4, "shard_shape": grid}) is None
