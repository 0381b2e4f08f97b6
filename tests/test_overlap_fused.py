"""Communication-overlapped temporal blocking == plain temporal blocking.

``make_sharded_fused_step(overlap=True)`` / ``make_sharded_fullgrid_step
(overlap=True)`` change only the dependency structure (the width-m slab
``ppermute``s feed boundary-shell kernels instead of the whole update),
never the values: bit-exact for integer families, allclose(1e-6) for
float.  The interior kernel's independence from the exchange — the whole
point of the split — is asserted structurally: its jaxpr dependency path
contains no collective-permute.

Every equivalence case runs >= 2 consecutive steps, so the second step's
slabs come from the FIRST step's spliced outputs — a wrong-neighbor or
stale-shell bug cannot survive two exchanges.
"""

import numpy as np
import pytest

import jax
import jax.extend.core as jex_core
import jax.numpy as jnp

from mpi_cuda_process_tpu import (
    init_state,
    make_mesh,
    make_stencil,
    shard_fields,
)
from mpi_cuda_process_tpu.parallel.stepper import (
    make_sharded_fullgrid_step,
    make_sharded_fused_step,
    make_sharded_temporal_step,
)


def _pair(name, grid, mesh_shape, k, kw=None, periodic=False, kind=None,
          padfree=None):
    st = make_stencil(name, **(kw or {}))
    mesh = make_mesh(mesh_shape)
    mk = lambda ov: make_sharded_fused_step(  # noqa: E731
        st, mesh, grid, k, interpret=True, periodic=periodic, kind=kind,
        padfree=padfree, overlap=ov)
    plain, over = mk(False), mk(True)
    assert plain is not None and over is not None
    assert getattr(over, "_overlap_active", False), \
        "overlap geometry unexpectedly declined — fix the test shape"
    fields = init_state(st, grid, seed=9,
                        kind="random" if periodic else "pulse",
                        periodic=periodic)
    return st, mesh, plain, over, fields


def _run_both(st, mesh, plain, over, fields, steps=2):
    fp = fo = shard_fields(fields, mesh, st.ndim)
    jp, jo = jax.jit(plain), jax.jit(over)
    for _ in range(steps):
        fp, fo = jp(fp), jo(fo)
    return fp, fo


def _assert_equiv(fp, fo):
    for p, o in zip(fp, fo):
        if np.issubdtype(np.asarray(p).dtype, np.integer):
            np.testing.assert_array_equal(np.asarray(o), np.asarray(p))
        else:
            np.testing.assert_allclose(np.asarray(o), np.asarray(p),
                                       rtol=0, atol=1e-6)


# ---------------------------------------------------------------------------
# 3D fused (padded kind): the headline equivalences.  Two consecutive
# steps everywhere (slab-from-correct-neighbor regression).  The heavier
# compiles (extra families, 4-shard, 2-axis, periodic) ride the slow tier;
# the default tier keeps one guard-frame anchor + the carry field.
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("name,grid,mesh_shape,k,kw", [
    ("heat3d", (32, 16, 128), (2, 1, 1), 4, {}),
    pytest.param("heat3d", (64, 16, 128), (4, 1, 1), 4, {},
                 marks=pytest.mark.slow),        # 4-shard ring
    pytest.param("heat3d", (32, 32, 128), (2, 2, 1), 4, {},
                 marks=pytest.mark.slow),        # 2-axis mesh: y shells too
    ("wave3d", (32, 16, 128), (2, 1, 1), 4, {}),  # leapfrog carry field
    pytest.param("wave3d", (64, 16, 128), (4, 1, 1), 4, {},
                 marks=pytest.mark.slow),
    pytest.param("sor3d", (64, 16, 128), (2, 1, 1), 4, {},
                 marks=pytest.mark.slow),        # red-black parity, m=8
    pytest.param("sor3d", (128, 16, 128), (4, 1, 1), 4, {},
                 marks=pytest.mark.slow),
])
def test_overlap_fused_matches_plain(name, grid, mesh_shape, k, kw):
    st, mesh, plain, over, fields = _pair(name, grid, mesh_shape, k, kw)
    _assert_equiv(*_run_both(st, mesh, plain, over, fields))


@pytest.mark.parametrize("name,grid,mesh_shape,k", [
    pytest.param("heat3d", (32, 16, 128), (2, 1, 1), 4),
    pytest.param("heat3d", (32, 32, 128), (2, 2, 1), 4,
                 marks=pytest.mark.slow),
    pytest.param("sor3d", (64, 16, 128), (2, 1, 1), 4,
                 marks=pytest.mark.slow),        # wrap parity consistency
])
def test_overlap_fused_periodic_matches_plain(name, grid, mesh_shape, k):
    st, mesh, plain, over, fields = _pair(name, grid, mesh_shape, k,
                                          periodic=True)
    _assert_equiv(*_run_both(st, mesh, plain, over, fields))


# ---------------------------------------------------------------------------
# pad-free / streaming kinds: dummy-slab interiors + the same shells
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("kind,padfree,grid,periodic", [
    (None, True, (32, 16, 128), False),
    pytest.param(None, True, (32, 16, 128), True, marks=pytest.mark.slow),
    pytest.param("stream", None, (48, 32, 128), False,
                 marks=pytest.mark.slow),
])
def test_overlap_zslab_kinds_match_plain(kind, padfree, grid, periodic):
    st, mesh, plain, over, fields = _pair(
        "heat3d", grid, (2, 1, 1), 4, periodic=periodic, kind=kind,
        padfree=padfree)
    _assert_equiv(*_run_both(st, mesh, plain, over, fields))


# ---------------------------------------------------------------------------
# 2D whole-local-block kernel: bit-exact including int Life
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("name,grid,mesh_shape,k,steps", [
    ("life", (64, 128), (2,), 8, 2),             # int32: bit-exact
    pytest.param("life", (128, 128), (4,), 8, 2, marks=pytest.mark.slow),
    pytest.param("heat2d", (64, 128), (2,), 8, 2, marks=pytest.mark.slow),
])
def test_overlap_fullgrid_matches_plain(name, grid, mesh_shape, k, steps):
    st = make_stencil(name)
    mesh = make_mesh(mesh_shape)
    plain = make_sharded_fullgrid_step(st, mesh, grid, k, interpret=True)
    over = make_sharded_fullgrid_step(st, mesh, grid, k, interpret=True,
                                      overlap=True)
    assert plain is not None and over is not None
    assert getattr(over, "_overlap_active", False)
    fields = init_state(st, grid, seed=7, density=0.3,
                        kind="random" if name == "life" else "auto")
    _assert_equiv(*_run_both(st, mesh, plain, over, fields, steps=steps))


@pytest.mark.slow
def test_overlap_fullgrid_periodic_life_bitmatch():
    st = make_stencil("life")
    grid = (64, 128)
    mesh = make_mesh((2,))
    plain = make_sharded_fullgrid_step(st, mesh, grid, 8, interpret=True,
                                       periodic=True)
    over = make_sharded_fullgrid_step(st, mesh, grid, 8, interpret=True,
                                      periodic=True, overlap=True)
    assert getattr(over, "_overlap_active", False)
    fields = init_state(st, grid, seed=3, density=0.3, kind="random",
                        periodic=True)
    _assert_equiv(*_run_both(st, mesh, plain, over, fields))


# ---------------------------------------------------------------------------
# structure: the interior consumes no ppermute output
# ---------------------------------------------------------------------------


def _all_jaxprs(jaxpr):
    yield jaxpr
    for eqn in jaxpr.eqns:
        for v in eqn.params.values():
            vals = v if isinstance(v, (list, tuple)) else (v,)
            for u in vals:
                if isinstance(u, jex_core.ClosedJaxpr):
                    yield from _all_jaxprs(u.jaxpr)
                elif isinstance(u, jex_core.Jaxpr):
                    yield from _all_jaxprs(u)


def _interior_depends_on_ppermute(step, fields, local_shape):
    """Walk the full step's jaxpr: locate the interior pallas_call (the
    one producing full local-shape outputs) and flood backwards through
    its transitive producers, asserting no collective-permute feeds it."""
    closed = jax.make_jaxpr(step)(fields)
    for jx in _all_jaxprs(closed.jaxpr):
        if not any(e.primitive.name == "ppermute" for e in jx.eqns):
            continue
        producer = {}
        for eqn in jx.eqns:
            for ov in eqn.outvars:
                producer[ov] = eqn
        interior = [
            e for e in jx.eqns
            if e.primitive.name == "pallas_call"
            and any(tuple(ov.aval.shape) == tuple(local_shape)
                    for ov in e.outvars)
        ]
        assert interior, "no interior pallas_call found in the jaxpr"
        seen, stack, hit = set(), list(interior), False
        while stack:
            eqn = stack.pop()
            if id(eqn) in seen:
                continue
            seen.add(id(eqn))
            if eqn.primitive.name == "ppermute":
                hit = True
            for iv in eqn.invars:
                if isinstance(iv, jex_core.Literal):
                    continue
                p = producer.get(iv)
                if p is not None:
                    stack.append(p)
        return hit
    raise AssertionError("no ppermute anywhere — overlap step did not "
                         "exchange at all")


@pytest.mark.parametrize("kind,padfree,grid", [
    (None, None, (32, 16, 128)),                  # padded kind
    pytest.param(None, True, (32, 16, 128), marks=pytest.mark.slow),
    pytest.param("stream", None, (48, 32, 128), marks=pytest.mark.slow),
])
def test_interior_free_of_collective_permute(kind, padfree, grid):
    st = make_stencil("heat3d")
    mesh = make_mesh((2, 1, 1))
    over = make_sharded_fused_step(st, mesh, grid, 4, interpret=True,
                                   kind=kind, padfree=padfree, overlap=True)
    assert getattr(over, "_overlap_active", False)
    fields = shard_fields(init_state(st, grid, seed=9, kind="pulse"),
                          mesh, 3)
    # (a) the exported interior path traces with no collective at all
    txt = str(jax.make_jaxpr(over._interior_step)(fields))
    assert "ppermute" not in txt
    # (b) the REAL step's interior pallas_call is unreachable from any
    # ppermute output, while the step as a whole does exchange
    local = (grid[0] // 2, grid[1], grid[2])
    assert not _interior_depends_on_ppermute(over, fields, local)
    assert "ppermute" in str(jax.make_jaxpr(over)(fields))


def test_interior_free_of_collective_permute_fullgrid():
    st = make_stencil("life")
    grid = (64, 128)
    mesh = make_mesh((2,))
    over = make_sharded_fullgrid_step(st, mesh, grid, 8, interpret=True,
                                      overlap=True)
    assert getattr(over, "_overlap_active", False)
    fields = shard_fields(
        init_state(st, grid, seed=7, density=0.3, kind="random"), mesh, 2)
    assert "ppermute" not in str(
        jax.make_jaxpr(over._interior_step)(fields))
    assert not _interior_depends_on_ppermute(over, fields, (32, 128))


# ---------------------------------------------------------------------------
# graceful fallback + dispatcher passthrough
# ---------------------------------------------------------------------------


def test_overlap_falls_back_when_block_too_small():
    # local z = 8 < 3m = 12: the shell strip does not fit — the builder
    # must return the plain step (correct values), not None / garbage
    st = make_stencil("heat3d")
    mesh = make_mesh((2, 1, 1))
    grid = (16, 16, 128)
    over = make_sharded_fused_step(st, mesh, grid, 4, interpret=True,
                                   overlap=True)
    plain = make_sharded_fused_step(st, mesh, grid, 4, interpret=True)
    assert over is not None
    assert not getattr(over, "_overlap_active", False)
    fields = init_state(st, grid, seed=9, kind="pulse")
    _assert_equiv(*_run_both(st, mesh, plain, over, fields, steps=1))


def test_temporal_dispatcher_threads_overlap():
    st3 = make_stencil("heat3d")
    mesh3 = make_mesh((2, 1, 1))
    s3 = make_sharded_temporal_step(st3, mesh3, (32, 16, 128), 4,
                                    interpret=True, overlap=True)
    assert getattr(s3, "_overlap_active", False)
    st2 = make_stencil("life")
    mesh2 = make_mesh((2,))
    s2 = make_sharded_temporal_step(st2, mesh2, (64, 128), 8,
                                    interpret=True, overlap=True)
    assert getattr(s2, "_overlap_active", False)
