"""Fused temporal-blocking kernel == k applications of the plain step.

The fused kernel (ops/pallas/fused.py) advances k time steps per HBM pass;
its contract is bit-identical guard-frame semantics to ``driver.make_step``
applied k times.  Runs in Pallas interpret mode on CPU (SURVEY.md §4.4).
"""

import jax
import jax.numpy as jnp
import pytest

from mpi_cuda_process_tpu import init_state, make_step, make_stencil
from mpi_cuda_process_tpu.driver import make_runner
from mpi_cuda_process_tpu.ops.pallas.fused import (
    _pick_tiles,
    make_fused_step,
)


@pytest.mark.parametrize(
    "shape,k",
    [
        ((16, 16, 128), 4),
        ((32, 16, 128), 4),
        ((16, 32, 256), 8),
    ],
)
def test_fused_matches_plain_steps(shape, k):
    st = make_stencil("heat3d")
    fields = init_state(st, shape, seed=3, kind="random")
    step = jax.jit(make_step(st, shape))
    ref = fields
    for _ in range(k):
        ref = step(ref)
    fused = make_fused_step(st, shape, k, interpret=True)
    assert fused is not None
    out = jax.jit(fused)(fields)
    # Identical op order per cell => bit-exact, not just close.
    assert jnp.array_equal(out[0], ref[0])


@pytest.mark.parametrize(
    "name,shape,k,kw",
    [
        ("heat3d27", (16, 16, 128), 4, {"alpha": 0.1}),
        ("heat3d4th", (16, 16, 128), 2, {}),   # halo 2: margin 4, 2m=8
        ("wave3d", (16, 16, 128), 4, {}),      # two-field leapfrog carry
        ("grayscott3d", (16, 16, 128), 4, {}),  # both fields halo'd
        ("advect3d", (16, 16, 128), 4, {}),     # asymmetric upwind taps
        ("advect3d", (16, 16, 128), 4,
         {"cx": -0.3, "cy": 0.2, "cz": -0.1}),  # mixed-sign upwinding
        ("sor3d", (16, 16, 128), 4, {}),        # red-black multi-phase:
                                                # margin 2*halo per micro
    ],
)
def test_fused_families_match_plain_steps(name, shape, k, kw):
    st = make_stencil(name, **kw)
    fields = init_state(st, shape, seed=5, kind="pulse")
    step = jax.jit(make_step(st, shape))
    ref = fields
    for _ in range(k):
        ref = step(ref)
    fused = make_fused_step(st, shape, k, interpret=True)
    assert fused is not None
    out = jax.jit(fused)(fields)
    assert len(out) == len(ref)
    for o, r in zip(out, ref):
        # micro-step tap order differs from the jnp update's association
        # order, so a few-ULP tolerance (frame cells still verbatim below)
        assert jnp.allclose(o, r, rtol=0, atol=1e-4), name
    for o, r in zip(out, ref):
        for d in range(3):
            for sl in (slice(0, st.halo), slice(-st.halo, None)):
                idx = [slice(None)] * 3
                idx[d] = sl
                assert jnp.array_equal(o[tuple(idx)], r[tuple(idx)])


def test_fused_in_scan_runner(_k=4, _n=3):
    st = make_stencil("heat3d")
    shape = (16, 16, 128)
    fields = init_state(st, shape, seed=0, kind="pulse")
    fused = make_fused_step(st, shape, _k, interpret=True)
    out = make_runner(fused, _n)(fields)
    ref = make_runner(make_step(st, shape), _k * _n)(
        init_state(st, shape, seed=0, kind="pulse"))
    assert jnp.allclose(out[0], ref[0], atol=1e-5)


@pytest.mark.parametrize("n", [1, 2, 3, 4, 5])
@pytest.mark.parametrize("padfree", [False, True], ids=["padded", "padfree"])
def test_fused_runner_matches_plain_steps(padfree, n):
    """The pad-free pass writes out of place: its runner ping-pongs two
    passes per scan iteration for an even count, runs a lone pass
    undonated, and one pass per iteration otherwise.  The padded pass reads
    its pad transient and keeps the plain donated runner.  Each gives the
    state of n*k plain steps, bit for bit; only the lone pad-free pass
    leaves the caller's input alive."""
    st = make_stencil("heat3d")
    shape, k = (16, 16, 128), 4
    fields = init_state(st, shape, seed=13, kind="random")
    step = jax.jit(make_step(st, shape))
    ref = fields
    for _ in range(n * k):
        ref = step(ref)
    fused = make_fused_step(st, shape, k, interpret=True, padfree=padfree)
    assert getattr(fused, "_out_of_place", False) == padfree
    given = tuple(jnp.copy(f) for f in fields)
    out = make_runner(fused, n)(given)
    assert jnp.array_equal(out[0], ref[0])
    kept = padfree and n == 1
    assert given[0].is_deleted() != kept
    if kept:
        assert jnp.array_equal(given[0], fields[0])


def test_fused_frame_stays_pinned():
    st = make_stencil("heat3d")
    shape = (16, 16, 128)
    fields = init_state(st, shape, seed=1, kind="random")
    fused = make_fused_step(st, shape, 4, interpret=True)
    out = jax.jit(fused)(fields)[0]
    u0 = fields[0]
    for d in range(3):
        lo = [slice(None)] * 3
        hi = [slice(None)] * 3
        lo[d] = 0
        hi[d] = -1
        assert jnp.array_equal(out[tuple(lo)], u0[tuple(lo)])
        assert jnp.array_equal(out[tuple(hi)], u0[tuple(hi)])


def test_unsupported_configs_return_none():
    st = make_stencil("heat3d")
    # k with 2k % 8 != 0 (sublane alignment) is rejected
    assert make_fused_step(st, (16, 16, 128), 2, interpret=True) is None
    # shapes not tileable into aligned blocks are rejected
    assert _pick_tiles(10, 16, 128, 4, 4, 1) is None
    # 2D models have no fused kernel
    assert make_fused_step(
        make_stencil("life"), (32, 32), 4, interpret=True) is None


# ---------------------------------------------------------------------------
# sharded + fused composition: k fused steps per width-k*halo exchange
# ---------------------------------------------------------------------------

# heat3d covers the composition in the default tier; the 27-point and
# two-field variants re-compile the heaviest shard_map+interpret programs
# (~30s each on CPU) and ride the slow tier.
@pytest.mark.parametrize(
    "name,grid,mesh_shape,k,kw",
    [
        ("heat3d", (16, 16, 128), (2, 2, 1), 4, {}),
        pytest.param("heat3d27", (16, 16, 128), (2, 1, 1), 4,
                     {"alpha": 0.1}, marks=pytest.mark.slow),
        pytest.param("wave3d", (32, 16, 128), (2, 2, 1), 4, {},
                     marks=pytest.mark.slow),
        pytest.param("grayscott3d", (16, 16, 128), (2, 1, 1), 4, {},
                     marks=pytest.mark.slow),   # both fields exchanged
        pytest.param("advect3d", (16, 16, 128), (2, 1, 1), 4,
                     {"cx": -0.3, "cy": 0.2, "cz": -0.1},
                     marks=pytest.mark.slow),   # asymmetric across shards
        pytest.param("sor3d", (32, 16, 128), (2, 1, 1), 4, {},
                     marks=pytest.mark.slow),   # parity across shards
    ],
)
def test_sharded_fused_matches_unsharded(name, grid, mesh_shape, k, kw):
    from mpi_cuda_process_tpu import make_mesh, shard_fields
    from mpi_cuda_process_tpu.parallel.stepper import make_sharded_fused_step

    st = make_stencil(name, **kw)
    fields = init_state(st, grid, seed=9, kind="pulse")
    ref = fields
    step = jax.jit(make_step(st, grid))
    for _ in range(k):
        ref = step(ref)

    mesh = make_mesh(mesh_shape)
    fused = make_sharded_fused_step(st, mesh, grid, k, interpret=True)
    assert fused is not None
    got = jax.jit(fused)(shard_fields(fields, mesh, 3))
    for g, r in zip(got, ref):
        assert jnp.allclose(g, r, rtol=0, atol=1e-4), name


def test_sharded_fused_unsupported_configs():
    from mpi_cuda_process_tpu import make_mesh
    from mpi_cuda_process_tpu.parallel.stepper import make_sharded_fused_step

    st = make_stencil("heat3d")
    # sharded lane axis -> None (in-kernel lane rolls need whole rows)
    mesh = make_mesh((1, 1, 2))
    assert make_sharded_fused_step(
        st, mesh, (16, 16, 256), 4, interpret=True) is None
    # local block smaller than the k*halo margin -> None
    mesh2 = make_mesh((4, 1, 1))
    assert make_sharded_fused_step(
        st, mesh2, (16, 16, 128), 8, interpret=True) is None


def test_fused_periodic_matches_plain_steps():
    """Periodic temporal blocking: wrap-pad + no frame pin == plain wrap."""
    st = make_stencil("heat3d")
    shape = (16, 16, 128)
    fields = init_state(st, shape, seed=4, kind="random", periodic=True)
    step = jax.jit(make_step(st, shape, periodic=True))
    ref = fields
    for _ in range(4):
        ref = step(ref)
    fused = make_fused_step(st, shape, 4, interpret=True, periodic=True)
    assert fused is not None
    out = jax.jit(fused)(fields)
    assert jnp.allclose(out[0], ref[0], rtol=0, atol=1e-4)


@pytest.mark.slow
def test_sharded_fused_periodic_matches_plain():
    from mpi_cuda_process_tpu import make_mesh, shard_fields
    from mpi_cuda_process_tpu.parallel.stepper import make_sharded_fused_step

    st = make_stencil("heat3d")
    grid = (16, 16, 128)
    fields = init_state(st, grid, seed=4, kind="random", periodic=True)
    step = jax.jit(make_step(st, grid, periodic=True))
    ref = fields
    for _ in range(4):
        ref = step(ref)
    mesh = make_mesh((2, 2, 1))
    fused = make_sharded_fused_step(st, mesh, grid, 4, interpret=True,
                                    periodic=True)
    assert fused is not None
    got = jax.jit(fused)(shard_fields(fields, mesh, 3))
    assert jnp.allclose(got[0], ref[0], rtol=0, atol=1e-4)


# ---------------------------------------------------------------------------
# pad-free (9-block raw-grid) variant: no full-grid pad transient
# ---------------------------------------------------------------------------


@pytest.mark.parametrize(
    "name,shape,k,kw",
    [
        ("heat3d", (16, 16, 128), 4, {}),
        ("heat3d", (32, 16, 128), 8, {}),       # fori_loop depth
        ("heat3d4th", (16, 16, 128), 2, {}),    # halo 2
        ("wave3d", (16, 16, 128), 4, {}),       # two-field carry
        ("grayscott3d", (16, 16, 128), 4, {}),  # both fields halo'd
        ("advect3d", (16, 16, 128), 4,
         {"cx": -0.3, "cy": 0.2, "cz": -0.1}),  # mixed-sign upwinding
        ("sor3d", (16, 16, 128), 4, {}),        # parity from ghost coords
    ],
)
def test_padfree_matches_plain_steps(name, shape, k, kw):
    st = make_stencil(name, **kw)
    fields = init_state(st, shape, seed=7, kind="pulse")
    step = jax.jit(make_step(st, shape))
    ref = fields
    for _ in range(k):
        ref = step(ref)
    fused = make_fused_step(st, shape, k, interpret=True, padfree=True)
    assert fused is not None
    out = jax.jit(fused)(fields)
    assert len(out) == len(ref)
    for o, r in zip(out, ref):
        assert jnp.allclose(o, r, rtol=0, atol=1e-4), name
    # guard frame verbatim (ghost clamp garbage must never leak inward)
    for o, r in zip(out, ref):
        for d in range(3):
            for sl in (slice(0, st.halo), slice(-st.halo, None)):
                idx = [slice(None)] * 3
                idx[d] = sl
                assert jnp.array_equal(o[tuple(idx)], r[tuple(idx)])


def test_padfree_bitexact_vs_padded():
    """Same tap order as the padded fused kernel => bit-exact match."""
    st = make_stencil("heat3d")
    shape = (16, 16, 128)
    fields = init_state(st, shape, seed=11, kind="random")
    padded = make_fused_step(st, shape, 4, interpret=True)
    padfree = make_fused_step(st, shape, 4, interpret=True, padfree=True)
    assert padded is not None and padfree is not None
    a = jax.jit(padded)(fields)
    b = jax.jit(padfree)(fields)
    assert jnp.array_equal(a[0], b[0])


def test_padfree_periodic_matches_plain_steps():
    """Periodic pad-free: wrapped block indices == wrap-pad values."""
    st = make_stencil("heat3d")
    shape = (16, 16, 128)
    fields = init_state(st, shape, seed=4, kind="random", periodic=True)
    step = jax.jit(make_step(st, shape, periodic=True))
    ref = fields
    for _ in range(4):
        ref = step(ref)
    fused = make_fused_step(st, shape, 4, interpret=True, periodic=True,
                            padfree=True)
    assert fused is not None
    out = jax.jit(fused)(fields)
    assert jnp.allclose(out[0], ref[0], rtol=0, atol=1e-4)


def test_padfree_periodic_sor_parity():
    """Red-black coloring stays globally consistent across wrapped tiles."""
    st = make_stencil("sor3d")
    shape = (16, 16, 128)
    fields = init_state(st, shape, seed=6, kind="pulse", periodic=True)
    step = jax.jit(make_step(st, shape, periodic=True))
    ref = fields
    for _ in range(4):
        ref = step(ref)
    fused = make_fused_step(st, shape, 4, interpret=True, periodic=True,
                            padfree=True)
    assert fused is not None
    out = jax.jit(fused)(fields)
    assert jnp.allclose(out[0], ref[0], rtol=0, atol=1e-4)


# ---------------------------------------------------------------------------
# sharded PAD-FREE (z-slab operands, no exchange-padded transient)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize(
    "name,grid,nz,k,kw",
    [
        ("heat3d", (32, 16, 128), 2, 4, {}),
        ("wave3d", (32, 16, 128), 2, 4, {}),     # two-field slabs
        # redundant-variant rows ride the slow tier (CI budget):
        pytest.param("heat3d", (64, 16, 128), 4, 4, {},
                     marks=pytest.mark.slow),    # >2 shards: interior+walls
        pytest.param("sor3d", (32, 16, 128), 2, 4, {},
                     marks=pytest.mark.slow),    # parity via origins
        pytest.param("heat3d4th", (32, 16, 128), 2, 2, {},
                     marks=pytest.mark.slow),    # halo 2
    ],
)
def test_zslab_padfree_matches_unsharded(name, grid, nz, k, kw):
    from mpi_cuda_process_tpu import make_mesh, shard_fields
    from mpi_cuda_process_tpu.parallel.stepper import make_sharded_fused_step

    st = make_stencil(name, **kw)
    fields = init_state(st, grid, seed=13, kind="pulse")
    ref = fields
    step = jax.jit(make_step(st, grid))
    for _ in range(k):
        ref = step(ref)
    mesh = make_mesh((nz, 1, 1))
    fused = make_sharded_fused_step(st, mesh, grid, k, interpret=True,
                                    padfree=True)
    assert fused is not None
    got = jax.jit(fused)(shard_fields(fields, mesh, 3))
    for g, r in zip(got, ref):
        assert jnp.allclose(g, r, rtol=0, atol=1e-4), name


def test_zslab_padfree_periodic_matches_unsharded():
    from mpi_cuda_process_tpu import make_mesh, shard_fields
    from mpi_cuda_process_tpu.parallel.stepper import make_sharded_fused_step

    st = make_stencil("heat3d")
    grid = (32, 16, 128)
    fields = init_state(st, grid, seed=8, kind="random", periodic=True)
    ref = fields
    step = jax.jit(make_step(st, grid, periodic=True))
    for _ in range(4):
        ref = step(ref)
    mesh = make_mesh((2, 1, 1))
    fused = make_sharded_fused_step(st, mesh, grid, 4, interpret=True,
                                    padfree=True, periodic=True)
    assert fused is not None
    got = jax.jit(fused)(shard_fields(fields, mesh, 3))
    assert jnp.allclose(got[0], ref[0], rtol=0, atol=1e-4)


def test_padfree_y_sharded_mesh_takes_two_axis_kernel():
    from mpi_cuda_process_tpu import make_mesh
    from mpi_cuda_process_tpu.parallel.stepper import make_sharded_fused_step

    st = make_stencil("heat3d")
    # y sharded: padfree=True now builds the 2-AXIS slab-operand kernel
    # (y slabs + corner operands) instead of silently falling back to
    # the exchange-padded kernel (the pre-round-7 behavior; equivalence
    # is pinned by tests/test_twoaxis_padfree.py)
    mesh = make_mesh((2, 2, 1))
    step = make_sharded_fused_step(st, mesh, (32, 32, 128), 4,
                                   interpret=True, padfree=True)
    assert step is not None
    assert getattr(step, "_padfree_kind", None) == "yzslab"


# ---------------------------------------------------------------------------
# wide-X z-slab kernel (x windowed at lane-tile granularity)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize(
    "name,grid,nz,k,kw",
    [
        ("heat3d", (32, 16, 256), 2, 4, {}),     # bx=128 < X=256: 2 x-tiles
        # the wave row is slow tier (round-8 budget trim): its 90-operand
        # build is per-field replication of the heat3d row's 45-operand
        # machinery (same specs, same selects), and two-field wide-X
        # coverage stays in the default tier via the streaming x-window
        # wave test (test_streamfused::test_xwindowed_wave_two_fields)
        pytest.param("wave3d", (32, 16, 256), 2, 4, {},
                     marks=pytest.mark.slow),    # two-field, 90 operands
        # sor margin is 8 (halo x 2 phases x k=4): tiles must be
        # multiples of 16 — (8,8,128) correctly DECLINES now (see
        # test_xwin_rejects_invalid_explicit_tiles)
        pytest.param("sor3d", (32, 32, 256), 2, 4, {},
                     marks=pytest.mark.slow),    # parity incl. x offsets
    ],
)
def test_xwin_zslab_matches_unsharded(name, grid, nz, k, kw):
    from mpi_cuda_process_tpu import make_mesh, shard_fields
    from mpi_cuda_process_tpu.ops.pallas import fused as F
    from mpi_cuda_process_tpu.parallel import stepper as S

    st = make_stencil(name, **kw)
    # tiles must be multiples of 2*margin (margin doubles for the
    # red-black 2-phase micro)
    g2 = 2 * k * F._halo_per_micro(st)
    tiles = (g2, g2, 128)
    fields = init_state(st, grid, seed=21, kind="pulse")
    ref = fields
    step = jax.jit(make_step(st, grid))
    for _ in range(k):
        ref = step(ref)
    mesh = make_mesh((nz, 1, 1))
    local = (grid[0] // nz, grid[1], grid[2])
    axis_names, counts = S._resolve_mesh_axes(3, mesh)
    fused = S._make_zslab_padfree_step(
        st, mesh, grid, local, axis_names, counts, k,
        lambda *a, **kw2: F.build_zslab_xwin_call(
            *a, tiles=tiles, **kw2),
        (27, 9), True, False)
    assert fused is not None
    got = jax.jit(fused)(shard_fields(fields, mesh, 3))
    for g, r in zip(got, ref):
        assert jnp.allclose(g, r, rtol=0, atol=1e-4), name


def test_xwin_zslab_periodic_matches_unsharded():
    from mpi_cuda_process_tpu import make_mesh, shard_fields
    from mpi_cuda_process_tpu.ops.pallas import fused as F
    from mpi_cuda_process_tpu.parallel import stepper as S

    st = make_stencil("heat3d")
    grid = (32, 16, 256)
    fields = init_state(st, grid, seed=22, kind="random", periodic=True)
    ref = fields
    step = jax.jit(make_step(st, grid, periodic=True))
    for _ in range(4):
        ref = step(ref)
    mesh = make_mesh((2, 1, 1))
    axis_names, counts = S._resolve_mesh_axes(3, mesh)
    fused = S._make_zslab_padfree_step(
        st, mesh, grid, (16, 16, 256), axis_names, counts, 4,
        lambda *a, **kw2: F.build_zslab_xwin_call(
            *a, tiles=(8, 8, 128), **kw2),
        (27, 9), True, True)
    assert fused is not None
    got = jax.jit(fused)(shard_fields(fields, mesh, 3))
    assert jnp.allclose(got[0], ref[0], rtol=0, atol=1e-4)


def test_xwin_unlocks_wave_at_wide_x():
    """The config-5 gap: wave3d at 4096 lanes is untileable for the
    whole-row z-slab kernel but TILEABLE for the wide-X variant — and the
    auto pad-free path reaches it through the builder chain."""
    from mpi_cuda_process_tpu.ops.pallas.fused import (
        build_zslab_padfree_call,
        build_zslab_xwin_call,
    )

    st = make_stencil("wave3d")
    local, gshape = (64, 4096, 4096), (4096, 4096, 4096)
    assert build_zslab_padfree_call(st, local, gshape, 4,
                                    interpret=True) is None
    built = build_zslab_xwin_call(st, local, gshape, 4, interpret=True)
    assert built is not None  # picks VMEM-feasible (bz, by, bx)


def test_xwin_rejects_invalid_explicit_tiles():
    """Explicit tiles bypass the auto picker but NOT the structural
    gates: a bz that is not a multiple of 2*margin degenerated
    _tail_index_fns into silently-wrong geometry (the sor3d wide-X bug
    this test pins)."""
    from mpi_cuda_process_tpu.ops.pallas.fused import (
        build_zslab_padfree_call,
        build_zslab_xwin_call,
    )

    st = make_stencil("sor3d")  # margin 8 at k=4 (2 phases)
    local, gshape = (16, 16, 256), (32, 16, 256)
    assert build_zslab_xwin_call(st, local, gshape, 4, tiles=(8, 8, 128),
                                 interpret=True) is None
    assert build_zslab_padfree_call(st, local, gshape, 4, tiles=(8, 8),
                                    interpret=True) is None
