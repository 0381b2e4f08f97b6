"""Interpret-mode smoke for the HBM pipeline probes.

benchmarks/pipeline_probe.py is a chip-time experiment (can the manual
make_async_copy pipeline beat Mosaic's ~330 GB/s auto-pipeline?); these
tests prove every probe BUILDS and computes ``2*x`` correctly on CPU so
the harness never wastes a chip window on a syntax error.
"""

import importlib.util
import os

import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def probe_mod():
    spec = importlib.util.spec_from_file_location(
        "pipeline_probe_smoke",
        os.path.join(REPO, "benchmarks", "pipeline_probe.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.parametrize("name", ["jnp_copy", "auto_copy", "manual2_copy",
                                  "manual4_copy", "manual2s_copy",
                                  "manual4s_copy"])
def test_probe_builds_and_doubles(probe_mod, name):
    shape = (8, 8, 128)
    fn = probe_mod.build_probe(name, shape, bz=2, interpret=True)
    x = jnp.arange(np.prod(shape), dtype=jnp.float32).reshape(shape)
    np.testing.assert_array_equal(np.asarray(fn(x)), 2.0 * np.asarray(x))


@pytest.mark.parametrize("name", ["manual2_stencil_k4",
                                  "manual4_stencil_k4",
                                  "manual4s_stencil_k4"])
def test_stencil_probe_pair_equivalent(probe_mod, name):
    """The manual-pipeline stencil probes must compute EXACTLY what the
    auto-pipeline control computes — otherwise the measured pair would
    compare different work and the ceiling verdict would be garbage."""
    shape = (8, 8, 128)
    x = jnp.linspace(0., 1., int(np.prod(shape)),
                     dtype=jnp.float32).reshape(shape)
    auto = probe_mod.build_probe("auto4_stencil", shape, bz=2,
                                 interpret=True)
    manual = probe_mod.build_probe(name, shape, bz=2, interpret=True)
    np.testing.assert_array_equal(np.asarray(auto(x)),
                                  np.asarray(manual(x)))


def test_probe_k_parsing(probe_mod):
    assert probe_mod._probe_k("jnp_copy") == 1
    assert probe_mod._probe_k("auto4_stencil") == 4
    assert probe_mod._probe_k("manual2_stencil_k4") == 4
    # every default probe parses
    for name in probe_mod.PROBES:
        probe_mod._probe_k(name)


def test_zslab_probe_child_template_is_valid():
    """The zslab VMEM probe's child code must be syntactically valid and
    its construction path must work (interpret mode, tiny shape) — a
    chip window must never be spent on a harness bug."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "zslab_probe_smoke", os.path.join(REPO, "benchmarks",
                                          "zslab_probe.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    # template formats + compiles for every attempt row
    for label, name, dt, local, k, tiles in mod.ATTEMPTS:
        code = mod._CHILD.format(repo=REPO, name=name, dt=dt, local=local,
                                 k=k, tiles=tiles)
        compile(code, label, "exec")
    # the construction path itself, tiny, interpret mode
    import jax
    import jax.numpy as jnp

    from mpi_cuda_process_tpu import make_stencil
    from mpi_cuda_process_tpu.ops.pallas.fused import (
        build_zslab_padfree_call,
    )

    st = make_stencil("wave3d")
    local = (16, 16, 128)
    built = build_zslab_padfree_call(st, local, (128, 16, 128), 4,
                                     tiles=(8, 8), interpret=True)
    assert built is not None
    call, m, nfields = built
    key = jax.random.PRNGKey(0)
    fields = [jax.random.uniform(jax.random.fold_in(key, i), local,
                                 st.dtype) for i in range(nfields)]
    slab = jnp.zeros((m, 16, 128), st.dtype)
    origins = jnp.array([16, 0], jnp.int32)
    args = []
    for f in fields:
        args += [f] * 9 + [slab] * 3 + [slab] * 3
    out = call(origins, *args)
    assert np.isfinite(np.asarray(out[0])).all()
