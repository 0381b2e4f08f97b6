"""Streaming fused kernel == k plain steps (interpret mode).

Same contract as tests/test_fused.py: ``make_stream_fused_step`` must be
semantically identical to k applications of ``driver.make_step`` —
guard-frame pinning, multi-field carries, red-black parity, halo-2
margins, and bf16 at k=4 (the streaming kernel's alignment advantage
over the tiled kernels, which require bf16 k=8).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mpi_cuda_process_tpu import init_state, make_step, make_stencil
from mpi_cuda_process_tpu.ops.pallas.streamfused import (
    make_stream_fused_step,
)


def _equiv(name, grid, k, dtype=None, tiles=None, steps=None, tol=1e-4,
           **params):
    """Same contract as tests/test_fused.py: k>1 windows accumulate in a
    different (window-local) association order, so a few-ULP atol; k=1
    (tol=0) is bit-exact."""
    kw = dict(params)
    if dtype is not None:
        kw["dtype"] = dtype
    st = make_stencil(name, **kw)
    stream = make_stream_fused_step(st, grid, k, tiles=tiles,
                                    interpret=True)
    assert stream is not None, f"stream kernel declined {name} {grid} k={k}"
    plain = make_step(st, grid)
    fields = init_state(st, grid, kind="auto", seed=7)
    ref = fields
    for _ in range(steps or k):
        ref = plain(ref)
    got = fields
    for _ in range((steps or k) // k):
        got = stream(got)
    for g, r in zip(got, ref):
        if tol:
            np.testing.assert_allclose(np.asarray(g, dtype=np.float32),
                                       np.asarray(r, dtype=np.float32),
                                       rtol=0, atol=tol)
        else:
            np.testing.assert_array_equal(np.asarray(g), np.asarray(r))


def test_heat3d_k1_bitexact():
    _equiv("heat3d", (24, 32, 128), 1, tiles=(8, 16), tol=0.0)


def test_heat3d():
    _equiv("heat3d", (24, 32, 128), 4)


def test_heat3d_two_passes():
    _equiv("heat3d", (24, 32, 128), 4, steps=8)


def test_heat3d_uneven_extents():
    # Z not a multiple of the largest chunk; Y larger than one strip
    _equiv("heat3d", (40, 64, 128), 4)


def test_heat3d_bf16_k4():
    """bf16 at k=4: impossible for the tiled kernels (sublane-16 forces
    k=8 there); the streaming kernel only needs the margin ROUNDED to the
    sublane tile, not the block offsets."""
    _equiv("heat3d", (24, 64, 128), 4, dtype=jnp.bfloat16)


def test_heat3d_explicit_tiles():
    _equiv("heat3d", (24, 32, 128), 4, tiles=(8, 16))


def test_heat3d_rejects_bad_tiles():
    st = make_stencil("heat3d")
    # 2*wm > bz
    assert make_stream_fused_step(st, (24, 32, 128), 4, tiles=(4, 16),
                                  interpret=True) is None
    # fewer than 3 chunks
    assert make_stream_fused_step(st, (16, 32, 128), 4, tiles=(8, 16),
                                  interpret=True) is None


def test_wave3d_two_fields():
    _equiv("wave3d", (24, 32, 128), 4)


@pytest.mark.slow
def test_grayscott3d_coupled_fields():
    _equiv("grayscott3d", (24, 32, 128), 4)


@pytest.mark.slow
def test_advect3d():
    _equiv("advect3d", (24, 32, 128), 4)


@pytest.mark.slow
def test_heat3d27():
    _equiv("heat3d27", (24, 32, 128), 4)


def test_heat3d4th_halo2():
    # halo 2: wm = 2k = 8 -> bz >= 16, Z >= 48
    _equiv("heat3d4th", (48, 32, 128), 4)


def test_sor3d_parity():
    # red-black: wm = 2k (phase-aware margins); parity from global coords
    _equiv("sor3d", (48, 32, 128), 4)


def test_xwindowed_strips_match():
    """Explicit (bz, by, bx) tiles window the lane axis too (the config-5
    two-field fit): clamped x shells, wrap garbage excluded by validity."""
    _equiv("heat3d", (24, 32, 768), 4, tiles=(8, 16, 256))


def test_xwindowed_wave_two_fields():
    _equiv("wave3d", (24, 32, 768), 4, tiles=(8, 16, 256))


@pytest.mark.slow
def test_xwindowed_degenerate_window_covers_whole_x():
    # wx == X exactly: every x program clamps to xlo=0 and re-reads the
    # whole row — redundant but must stay correct
    _equiv("heat3d", (24, 32, 512), 4, tiles=(8, 16, 256))


@pytest.mark.slow
def test_xwindowed_wider_lane_extent():
    _equiv("heat3d", (24, 32, 1024), 4, tiles=(8, 16, 512))


def test_xwindowed_rejects_bad_bx():
    st = make_stencil("heat3d")
    # bx not a lane-tile multiple / no room for the shells
    assert make_stream_fused_step(st, (24, 32, 768), 4, tiles=(8, 16, 200),
                                  interpret=True) is None
    assert make_stream_fused_step(st, (24, 32, 256), 4, tiles=(8, 16, 256),
                                  interpret=True) is None


def test_pick_strip_never_offers_xwindow_past_shell_margin(monkeypatch):
    """_pick_strip must never return an x-windowed strip when the window
    margin exceeds the 128-lane shell (wm > _XSHELL), because
    _stream_gates rejects that class outright instead of retrying other
    geometries (round-4 advisor).  TODAY the bz ladder (max 32) makes
    every wm > 128 candidate fail the 2*wm <= bz gate before x_options
    matters, so the filter is exercised by growing the ladder past
    2*_XSHELL — the exact future change that would make it live."""
    from mpi_cuda_process_tpu.ops.pallas import streamfused as sf

    wm = sf._XSHELL + 8  # margin one step past the shell
    wm_a = wm            # already sublane-aligned for f32
    # current ladder: no z-chunk can host 2*wm planes — no strip at all,
    # so the explicit-tiles path in _stream_gates is the only live check
    assert sf._pick_strip(4096, 4096, 32768, wm, wm_a, 4, 1) is None
    # the one configuration where the filter is load-bearing: a grown
    # ladder hosts the margin, whole-lane strips exceed the VMEM budget
    # (X very wide), and an x-window would FIT — verified: (512, 64, 256)
    # lives at ~5.55 GB vs whole-lane ~318 GB.  The picker must decline
    # rather than offer the x-window _stream_gates rejects outright.
    monkeypatch.setattr(sf, "_BZ_LADDER", (512,))
    monkeypatch.setattr(sf, "_VMEM_LIMIT", 6 * 10**9)
    assert sf._strip_live_bytes(512, 64, 256, 32768, wm, wm_a, 4, 1,
                                False) < 6 * 10**9  # x-window would fit
    assert sf._pick_strip(4096, 4096, 32768, wm, wm_a, 4, 1) is None


def test_config5_wave_constructs_via_x_windowing():
    """The config-5 gap closed: two-field wave3d at the 64-chip local
    shape (64, 4096, 4096) exceeds the whole-lane VMEM gate but tiles
    with an x-windowed strip — total read amplification ~1.9x vs the
    wide-X tiled kernel's 4.5x."""
    from mpi_cuda_process_tpu.ops.pallas.streamfused import (
        build_stream_sharded_call,
    )

    wave = make_stencil("wave3d")
    built = build_stream_sharded_call(wave, (64, 4096, 4096), (4096,) * 3,
                                      4, interpret=True)
    assert built is not None


def test_declines_2d_and_unknown():
    assert make_stream_fused_step(make_stencil("heat2d"), (64, 128), 4,
                                  interpret=True) is None
    assert make_stream_fused_step(make_stencil("life"), (64, 64), 4,
                                  interpret=True) is None


def _sharded_equiv(name, grid, mesh_shape, k, steps=None, **kw):
    from mpi_cuda_process_tpu import make_mesh, shard_fields
    from mpi_cuda_process_tpu.parallel.stepper import make_sharded_fused_step

    st = make_stencil(name, **kw)
    fields = init_state(st, grid, seed=9, kind="pulse")
    ref = fields
    step = jax.jit(make_step(st, grid))
    n = steps or k
    for _ in range(n):
        ref = step(ref)
    mesh = make_mesh(mesh_shape)
    stream = make_sharded_fused_step(st, mesh, grid, k, interpret=True,
                                     kind="stream")
    assert stream is not None, f"sharded stream declined {name} {grid}"
    got = shard_fields(fields, mesh, 3)
    run = jax.jit(stream)
    for _ in range(n // k):
        got = run(got)
    for g, r in zip(got, ref):
        np.testing.assert_allclose(np.asarray(g), np.asarray(r),
                                   rtol=0, atol=1e-4)


def test_sharded_stream_matches_unsharded():
    """z-decomposed streaming (slab operands, global-origin frame) must
    match the unsharded plain run — the config-5 execution candidate."""
    _sharded_equiv("heat3d", (48, 32, 128), (2, 1, 1), 4)


def test_sharded_stream_two_passes():
    # slab values must be re-exchanged between passes
    _sharded_equiv("heat3d", (48, 32, 128), (2, 1, 1), 4, steps=8)


@pytest.mark.slow
def test_sharded_stream_four_shards():
    _sharded_equiv("heat3d", (96, 32, 128), (4, 1, 1), 4)


@pytest.mark.slow
def test_sharded_stream_wave_two_fields():
    _sharded_equiv("wave3d", (48, 32, 128), (2, 1, 1), 4)


@pytest.mark.slow
def test_sharded_stream_sor_parity():
    # wm = 2k: global parity must stay consistent across shard origins
    _sharded_equiv("sor3d", (96, 32, 128), (2, 1, 1), 4)


@pytest.mark.slow
def test_sharded_stream_xwindowed():
    """Sharded + x-windowed: slab strips slice the lane axis too."""
    from mpi_cuda_process_tpu import make_mesh, shard_fields
    from mpi_cuda_process_tpu.ops.pallas.streamfused import (
        build_stream_sharded_call,
    )
    from mpi_cuda_process_tpu.parallel import stepper as stepper_lib

    st = make_stencil("heat3d")
    grid, mesh_shape, k = (48, 32, 768), (2, 1, 1), 4
    mesh = make_mesh(mesh_shape)
    axis_names, counts = stepper_lib._resolve_mesh_axes(3, mesh)
    local = tuple(g // c for g, c in zip(grid, counts))
    # force x-windowed tiles through the internal builder path
    step = stepper_lib._make_zslab_padfree_step(
        st, mesh, grid, local, axis_names, counts, k,
        lambda *a, **kw: build_stream_sharded_call(
            *a, tiles=(8, 16, 256), **kw),
        (1, 1), True, False)
    assert step is not None
    fields = init_state(st, grid, seed=9, kind="pulse")
    ref = fields
    plain = jax.jit(make_step(st, grid))
    for _ in range(k):
        ref = plain(ref)
    got = jax.jit(step)(shard_fields(fields, mesh, 3))
    for g, r in zip(got, ref):
        np.testing.assert_allclose(np.asarray(g), np.asarray(r),
                                   rtol=0, atol=1e-4)


def test_sharded_stream_y_mesh_builds_and_periodic_declines():
    """Round 8: a y-sharded mesh no longer declines — it routes to the
    2-axis sliding-window kernel (tests/test_twoaxis_stream.py carries
    the equivalence suite); periodic stays a hard decline on every mesh
    (the streaming kernels are guard-frame only)."""
    from mpi_cuda_process_tpu import make_mesh
    from mpi_cuda_process_tpu.parallel.stepper import make_sharded_fused_step

    st = make_stencil("heat3d")
    step = make_sharded_fused_step(
        st, make_mesh((1, 2, 1)), (48, 64, 128), 4, interpret=True,
        kind="stream")
    assert step is not None
    assert getattr(step, "_padfree_kind", None) == "stream_yz"
    assert make_sharded_fused_step(
        st, make_mesh((2, 1, 1)), (48, 32, 128), 4, interpret=True,
        kind="stream", periodic=True) is None
