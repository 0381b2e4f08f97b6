"""Every stencil op vs its pure-numpy golden implementation (SURVEY.md §4.1)."""

import numpy as np
import pytest

import jax.numpy as jnp

from mpi_cuda_process_tpu import make_step, make_stencil

import golden


def _rng(seed=0):
    return np.random.default_rng(seed)


def _run_steps(st, fields, n, grid_shape):
    step = make_step(st, grid_shape)
    for _ in range(n):
        fields = step(fields)
    return fields


def test_life_matches_golden():
    g = _rng(1).integers(0, 2, size=(12, 17)).astype(np.int32)
    g[0, :] = g[-1, :] = g[:, 0] = g[:, -1] = 0
    st = make_stencil("life")
    got = _run_steps(st, (jnp.asarray(g),), 4, g.shape)[0]
    want = g
    for _ in range(4):
        want = golden.life_step(want)
    np.testing.assert_array_equal(np.asarray(got), want)


def test_life_vectorized_golden_matches_loop_golden():
    # chip_smoke.py's 4096^2 Life check rests on the vectorized golden;
    # frame cells alive on purpose (both must leave them pinned)
    g = _rng(5).integers(0, 2, size=(13, 19)).astype(np.int32)
    want = g
    for _ in range(5):
        want = golden.life_step(want)
    np.testing.assert_array_equal(golden.life_steps_vec(g, 5), want)


@pytest.mark.parametrize("shape,name,alpha", [
    ((10, 14), "heat2d", 0.25),
    ((6, 7, 9), "heat3d", 1.0 / 6.0),
])
def test_heat_matches_golden(shape, name, alpha):
    g = _rng(2).random(shape).astype(np.float32) * 50
    st = make_stencil(name, alpha=alpha)
    got = _run_steps(st, (jnp.asarray(g),), 3, shape)[0]
    want = g.astype(np.float64)
    for _ in range(3):
        want = golden.heat_step(want, alpha)
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-5, atol=2e-4)


def test_heat27_matches_golden():
    shape = (6, 7, 8)
    g = _rng(3).random(shape).astype(np.float32) * 10
    st = make_stencil("heat3d27", alpha=0.15)
    got = _run_steps(st, (jnp.asarray(g),), 2, shape)[0]
    want = g.astype(np.float64)
    for _ in range(2):
        want = golden.heat27_step(want, 0.15)
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-5, atol=2e-4)


def test_wave_matches_golden():
    shape = (8, 9, 7)
    u = _rng(4).random(shape).astype(np.float32)
    up = _rng(5).random(shape).astype(np.float32)
    # pin frames so the state is self-consistent
    for a in (u, up):
        a[0], a[-1], a[:, 0], a[:, -1] = 0, 0, 0, 0
        a[:, :, 0] = a[:, :, -1] = 0
    st = make_stencil("wave3d", c2dt2=0.1)
    got = _run_steps(st, (jnp.asarray(u), jnp.asarray(up)), 3, shape)
    wu, wup = u.astype(np.float64), up.astype(np.float64)
    for _ in range(3):
        wu, wup = golden.wave_step(wu, wup, 0.1)
    np.testing.assert_allclose(np.asarray(got[0]), wu, rtol=2e-5, atol=2e-4)
    np.testing.assert_allclose(np.asarray(got[1]), wup, rtol=2e-5, atol=2e-4)


def test_frame_is_pinned():
    """Frame cells must hold their initial values forever (Dirichlet walls)."""
    shape = (9, 9)
    st = make_stencil("heat2d")
    g = np.zeros(shape, np.float32)
    g[0, :] = g[-1, :] = g[:, 0] = g[:, -1] = 100.0
    got = np.asarray(_run_steps(st, (jnp.asarray(g),), 10, shape)[0])
    np.testing.assert_array_equal(got[0, :], 100.0)
    np.testing.assert_array_equal(got[-1, :], 100.0)
    np.testing.assert_array_equal(got[:, 0], 100.0)
    np.testing.assert_array_equal(got[:, -1], 100.0)
    assert got[1:-1, 1:-1].max() > 0  # heat flowed inward


def test_odd_sizes_fully_computed():
    """Grids not divisible by any tile size still update every interior cell.

    Guards against the reference's silent coverage gap: truncating
    ``n_blocks = size/512`` leaves the last ``size mod 512`` cells never
    computed (kernel.cu:195-196, SURVEY.md C17).
    """
    shape = (13, 19)
    st = make_stencil("heat2d", alpha=0.25)
    g = np.full(shape, 1.0, np.float32)
    g[0, :] = g[-1, :] = g[:, 0] = g[:, -1] = 0.0
    got = np.asarray(_run_steps(st, (jnp.asarray(g),), 1, shape)[0])
    # every interior cell adjacent to the cold frame must have cooled
    assert got[1, 1] < 1.0 and got[-2, -2] < 1.0 and got[-2, 1] < 1.0


def test_heat4th_matches_golden():
    shape = (8, 9, 10)
    g = _rng(6).random(shape).astype(np.float32) * 10
    st = make_stencil("heat3d4th", alpha=0.05)
    got = _run_steps(st, (jnp.asarray(g),), 2, shape)[0]
    want = g.astype(np.float64)
    for _ in range(2):
        want = golden.heat4th_step(want, 0.05)
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-5, atol=2e-4)
    # halo-2 frame: outer TWO cells pinned
    np.testing.assert_array_equal(np.asarray(got)[:2], g[:2])
