"""The observation as one compiled program (``utils/diagnostics``).

Pins:

* the jitted metric set equals the same maths staged op by op, eagerly:
  Life's population exactly, the float metrics to rtol 1e-6 — for Life,
  wave, heat with its residual, and a sharded heat3d on a 2x2 mesh of
  virtual CPU devices (overlap off and on, an ensemble of 2);
* a repeated call builds no program and compiles or loads nothing
  (``obs/runtime``'s counter and ``program_stats``);
* a new step function gets a program of its own, and a dropped one
  takes its programs with it.
"""

import gc

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mpi_cuda_process_tpu import cli, init_state, make_step, make_stencil
from mpi_cuda_process_tpu.obs import runtime
from mpi_cuda_process_tpu.utils import diagnostics


def _eager(stencil, fields, step_fn=None):
    """The metric set op by op, each op dispatched on its own."""
    f0 = fields[0]
    out = {}
    if stencil.name == "life":
        out["population"] = jnp.sum(f0)
    else:
        out["mean"] = jnp.mean(f0)
        out["min"] = jnp.min(f0)
        out["max"] = jnp.max(f0)
    if stencil.num_fields > 1:
        out["velocity_l2"] = jnp.sqrt(jnp.sum((fields[0] - fields[1]) ** 2))
    elif step_fn is not None and jnp.issubdtype(f0.dtype, jnp.inexact):
        new = step_fn(tuple(fields))
        out["residual"] = jnp.sqrt(jnp.sum(
            (new[0].astype(jnp.float32) - f0.astype(jnp.float32)) ** 2))
    return {k: float(v) for k, v in jax.device_get(out).items()}


def _assert_match(got, want):
    assert set(got) == set(want)
    for k, v in want.items():
        if k == "population":
            assert got[k] == v
        else:
            np.testing.assert_allclose(got[k], v, rtol=1e-6, err_msg=k)


def _family(name):
    st = make_stencil(name)
    shape = (16, 128)
    kind = "random" if name == "life" else "pulse"
    fields = init_state(st, shape, seed=3, density=0.4, kind=kind)
    return st, fields, make_step(st, shape)


@pytest.mark.parametrize("name,with_step", [("life", False),
                                            ("wave2d", False),
                                            ("heat2d", True)])
def test_program_matches_eager_maths(name, with_step):
    st, fields, step = _family(name)
    step_fn = step if with_step else None
    got = diagnostics.field_diagnostics(st, fields, step_fn=step_fn)
    _assert_match(got, _eager(st, fields, step_fn))
    if with_step:
        assert got["residual"] > 0


@pytest.mark.parametrize("overlap,ensemble", [(False, 0), (True, 0),
                                              (False, 2)])
def test_sharded_program_matches_eager_maths(overlap, ensemble):
    argv = ["--stencil", "heat3d", "--grid", "16,16,128", "--mesh", "2,2,1",
            "--init", "random", "--density", "0.5", "--seed", "5",
            "--iters", "4"]
    if overlap:
        argv.append("--overlap")
    if ensemble:
        argv += ["--ensemble", str(ensemble)]
    st, step_fn, fields, _ = cli.build(cli.config_from_args(argv))
    assert len(fields[0].sharding.device_set) == 4
    if ensemble:
        assert fields[0].shape[0] == ensemble
    fields = step_fn(fields)  # a state with a nonzero residual
    got = diagnostics.field_diagnostics(st, fields, step_fn=step_fn)
    _assert_match(got, _eager(st, fields, step_fn))
    assert got["residual"] > 0


def test_second_call_builds_and_compiles_nothing():
    st, fields, step = _family("heat2d")
    first = diagnostics.field_diagnostics(st, fields, step_fn=step)
    stats = diagnostics.program_stats()
    seen = runtime.compile_events_seen()
    again = diagnostics.field_diagnostics(st, fields, step_fn=step)
    assert again == first
    assert runtime.compile_events_seen() == seen
    after = diagnostics.program_stats()
    assert after["built"] == stats["built"]
    assert after["calls"] == stats["calls"] + 1


def test_new_step_fn_builds_its_own_program():
    st, fields, step = _family("heat2d")
    diagnostics.field_diagnostics(st, fields, step_fn=step)
    built = diagnostics.program_stats()["built"]
    other = make_step(st, (16, 128))
    assert other is not step
    got = diagnostics.field_diagnostics(st, fields, step_fn=other)
    assert diagnostics.program_stats()["built"] == built + 1
    _assert_match(got, _eager(st, fields, step))
    held = len(diagnostics._programs_by_step)
    del other
    gc.collect()
    assert len(diagnostics._programs_by_step) == held - 1


def test_residual_norm_shares_the_cache():
    st, fields, step = _family("heat2d")
    want = _eager(st, fields, step)["residual"]
    got = diagnostics.residual_norm(step, fields)
    built = diagnostics.program_stats()["built"]
    np.testing.assert_allclose(got, want, rtol=1e-6)
    assert diagnostics.residual_norm(step, fields) == got
    assert diagnostics.program_stats()["built"] == built
