"""Native host runtime tests: async .npy writer + C++ differential engines."""

import os

import numpy as np
import pytest

import jax.numpy as jnp

from mpi_cuda_process_tpu import make_step, make_stencil
from mpi_cuda_process_tpu.utils import native

pytestmark = pytest.mark.skipif(
    not native.available(), reason="native toolchain unavailable")


def test_async_npy_roundtrip(tmp_path):
    arrs = {
        "a": np.arange(24, dtype=np.float32).reshape(2, 3, 4),
        "b": np.arange(6, dtype=np.int32).reshape(3, 2),
        "c": np.random.default_rng(0).random((5,)).astype(np.float64),
    }
    for name, a in arrs.items():
        native.async_write_npy(str(tmp_path / f"{name}.npy"), a)
    native.wait_all()
    for name, a in arrs.items():
        got = np.load(tmp_path / f"{name}.npy")
        np.testing.assert_array_equal(got, a)
        assert got.dtype == a.dtype


def test_async_write_failure_surfaces():
    native.async_write_npy("/nonexistent_dir_xyz/f.npy",
                           np.zeros(3, np.float32))
    with pytest.raises(IOError):
        native.wait_all()


def test_write_failure_is_reported_once(tmp_path):
    """A failed write raises at the next wait only: later writes in the
    same process (checkpoints after a failed dump) still succeed."""
    native.async_write_npy("/nonexistent_dir_xyz/g.npy",
                           np.zeros(3, np.float32))
    with pytest.raises(IOError):
        native.wait_all()
    native.async_write_npy(str(tmp_path / "ok.npy"), np.ones(3, np.float32))
    native.wait_all()
    np.testing.assert_array_equal(np.load(tmp_path / "ok.npy"),
                                  np.ones(3, np.float32))


def test_life_differential_native_vs_jax():
    """Three independent implementations agree: C++, numpy golden, JAX."""
    rng = np.random.default_rng(5)
    g = rng.integers(0, 2, (20, 30)).astype(np.int32)
    g[0] = g[-1] = 0
    g[:, 0] = g[:, -1] = 0
    st = make_stencil("life")
    step = make_step(st, g.shape)
    jax_out, cpp_out = (jnp.asarray(g),), g
    for _ in range(5):
        jax_out = step(jax_out)
        cpp_out = native.life_step_native(cpp_out)
    np.testing.assert_array_equal(np.asarray(jax_out[0]), cpp_out)


def test_heat3d_differential_native_vs_jax():
    rng = np.random.default_rng(6)
    g = (rng.random((10, 12, 14)) * 50).astype(np.float32)
    st = make_stencil("heat3d", alpha=1 / 6)
    step = make_step(st, g.shape)
    jax_out, cpp_out = (jnp.asarray(g),), g
    for _ in range(3):
        jax_out = step(jax_out)
        cpp_out = native.heat3d_step_native(cpp_out, 1 / 6)
    np.testing.assert_allclose(
        np.asarray(jax_out[0]), cpp_out, rtol=1e-5, atol=1e-4)


def _differential_2d(name, params, native_fn, steps=3, atol=1e-4):
    rng = np.random.default_rng(7)
    g = (rng.random((12, 18)) * 40).astype(np.float32)
    st = make_stencil(name, **params)
    step = make_step(st, g.shape)
    jax_out, cpp_out = (jnp.asarray(g),), g
    for _ in range(steps):
        jax_out = step(jax_out)
        cpp_out = native_fn(cpp_out)
    np.testing.assert_allclose(
        np.asarray(jax_out[0]), cpp_out, rtol=1e-5, atol=atol)


def test_heat2d_differential_native_vs_jax():
    _differential_2d(
        "heat2d", {"alpha": 0.25},
        lambda g: native.heat2d_step_native(g, 0.25))


def test_advect2d_differential_native_vs_jax():
    _differential_2d(
        "advect2d", {"cx": 0.4, "cy": -0.3},
        lambda g: native.advect2d_step_native(g, -0.3, 0.4))


def test_sor2d_differential_native_vs_jax():
    """Gauss-Seidel semantics match between the multi-phase JAX step and the
    sequential C++ sweep (red values fresh within the step)."""
    _differential_2d(
        "sor2d", {"omega": 1.6},
        lambda g: native.sor2d_step_native(g, 1.6))


def test_wave2d_differential_native_vs_jax():
    """Two-field leapfrog carry: the C++ engine returns new u and the
    caller carries old u as the next u_prev — same contract as the scan."""
    rng = np.random.default_rng(9)
    u = (rng.random((12, 18)) * 2 - 1).astype(np.float32)
    up = (rng.random((12, 18)) * 2 - 1).astype(np.float32)
    st = make_stencil("wave2d", c2dt2=0.25)
    step = make_step(st, u.shape)
    jax_out = (jnp.asarray(u), jnp.asarray(up))
    cpp_u, cpp_up = u, up
    for _ in range(3):
        jax_out = step(jax_out)
        cpp_u, cpp_up = native.wave2d_step_native(cpp_u, cpp_up, 0.25), cpp_u
    np.testing.assert_allclose(np.asarray(jax_out[0]), cpp_u,
                               rtol=1e-5, atol=1e-4)
    np.testing.assert_allclose(np.asarray(jax_out[1]), cpp_up,
                               rtol=1e-5, atol=1e-4)


def test_grayscott2d_differential_native_vs_jax():
    """Coupled two-field reaction-diffusion, both fields halo'd."""
    rng = np.random.default_rng(10)
    u = (rng.random((12, 18)) * 0.5 + 0.5).astype(np.float32)
    v = (rng.random((12, 18)) * 0.3).astype(np.float32)
    p = dict(du=0.16, dv=0.08, f=0.035, kappa=0.06)
    st = make_stencil("grayscott2d", **p)
    step = make_step(st, u.shape)
    jax_out = (jnp.asarray(u), jnp.asarray(v))
    cpp_u, cpp_v = u, v
    for _ in range(3):
        jax_out = step(jax_out)
        cpp_u, cpp_v = native.grayscott2d_step_native(cpp_u, cpp_v, **p)
    np.testing.assert_allclose(np.asarray(jax_out[0]), cpp_u,
                               rtol=1e-5, atol=1e-4)
    np.testing.assert_allclose(np.asarray(jax_out[1]), cpp_v,
                               rtol=1e-5, atol=1e-4)


def test_heat3d27_differential_native_vs_jax():
    """Full 27-point footprint (face/edge/corner weight classes)."""
    rng = np.random.default_rng(11)
    g = (rng.random((10, 12, 14)) * 50).astype(np.float32)
    st = make_stencil("heat3d27", alpha=0.15)
    step = make_step(st, g.shape)
    jax_out, cpp_out = (jnp.asarray(g),), g
    for _ in range(3):
        jax_out = step(jax_out)
        cpp_out = native.heat3d27_step_native(cpp_out, 0.15)
    np.testing.assert_allclose(
        np.asarray(jax_out[0]), cpp_out, rtol=1e-5, atol=1e-3)


def test_library_is_built_from_the_committed_source():
    """The loaded binary is named by a hash of native/stencilhost.cpp, so
    a checkout never loads a library built from other source."""
    import hashlib

    with open(native._SRC, "rb") as fh:
        digest = hashlib.sha256(fh.read()).hexdigest()[:16]
    assert native._so_path().endswith(f"libstencilhost-{digest}.so")
    if native.available():
        assert os.path.exists(native._so_path())
