"""Per-device HBM budget guard (utils/budget.py).

The reference mallocs the FULL grid on every rank with no error checking
(kernel.cu:184-191); this framework refuses an over-HBM config up front
with the arithmetic in the error.  These tests pin the BASELINE config-5
budget table documented in docs/STATE.md.
"""

import pytest

from mpi_cuda_process_tpu import make_stencil
from mpi_cuda_process_tpu.utils import budget

GiB = 2**30
V5E_HBM = 16 * GiB


def _total(name, grid, mesh=(), fuse=0, ensemble=0, **kw):
    st = make_stencil(name, **kw)
    total, parts = budget.estimate_run_bytes(
        st, grid, mesh=mesh, fuse=fuse, ensemble=ensemble)
    assert total == sum(b for _, b in parts)
    return total


def test_config5_f32_jnp_refused_with_arithmetic():
    """4096^3 wave f32 on 64 chips WITHOUT temporal blocking does not
    fit (2x4 GiB state + 4 out + ~8 GiB exchange-padded jnp copies).
    The guard must say so, with numbers."""
    st = make_stencil("wave3d")
    with pytest.raises(ValueError) as e:
        budget.check_budget(st, (4096,) * 3, mesh=(8, 8, 1),
                            hbm_bytes=V5E_HBM)
    msg = str(e.value)
    assert "GiB per device" in msg and "state: 2 field(s)" in msg
    assert "bfloat16" in msg  # the actionable lever is named


def test_config5_f32_two_axis_mesh_padfree_fits():
    """The 2-axis headline budget row (docs/STATE.md table): wave3d
    4096^3 in FULL f32 on an 8x8x1 mesh FITS at fuse 4 — the 2-axis
    pad-free kernels (y-slab + corner operands) replace the ~8 GiB
    exchange-padded transient that used to push this config past HBM,
    and the estimate follows the constructible path (the wide-X 2-axis
    builder actually tiles wave at 4096 lanes).  Pinned to the byte:
    2x4 GiB state + 4 GiB out + 0.379 GiB slab+corner operands, +10%."""
    st = make_stencil("wave3d")
    total, parts = budget.check_budget(st, (4096,) * 3, mesh=(8, 8, 1),
                                       fuse=4, hbm_bytes=V5E_HBM)
    # independent arithmetic (not the module's own constants)
    lz, ly, lx, m, item, nf = 512, 512, 4096, 4, 4, 2
    state = 2 * lz * ly * lx * item
    out = lz * ly * lx * item
    slabs = (2 * m * ly * lx            # z slabs
             + 2 * (2 * m) * lz * lx    # 2m-duplicated y-slab operands
             + 4 * m * (2 * m) * lx     # 2m-duplicated corner pieces
             ) * item * nf
    assert total == int((state + out + slabs) * 1.10) == 14_620_924_313
    assert any("pad-free" in label for label, _ in parts)
    assert not any("pad transient" in label for label, _ in parts)
    assert not any("exchange" in label for label, _ in parts)


def test_config5_bf16_fits():
    """bf16 at k=8 on the 8x8x1 mesh: ~7.0 GiB/device (state 4 + out 2 +
    0.38 GiB slab+corner operands + overhead) — the 2-axis pad-free path
    replaced the round-5 exchange-padded estimate (11.3 GiB)."""
    st = make_stencil("wave3d", dtype="bfloat16")
    total, parts = budget.check_budget(st, (4096,) * 3, mesh=(8, 8, 1),
                                       fuse=8, hbm_bytes=V5E_HBM)
    # pinned tight: a regression reinflating the estimate (e.g. the pad
    # transient coming back) must fail here, not drift in a loose range
    assert 6.8 * GiB < total < 7.3 * GiB
    assert any("pad-free" in label for label, _ in parts)


def test_1024_padfree_fits_padded_does_not_appear():
    """1024^3 f32 fused: prefer_padfree kicks in, so no pad transient is
    counted and the config fits (~8.8 GiB) — the round-4 1024^3 design."""
    st = make_stencil("heat3d")
    total, parts = budget.estimate_run_bytes(st, (1024,) * 3, fuse=4)
    assert total < 9.5 * GiB
    assert any("pad-free" in label for label, _ in parts)


def test_1024_jnp_estimate_reflects_pad_transient():
    t_jnp = _total("heat3d", (1024,) * 3)
    t_fused = _total("heat3d", (1024,) * 3, fuse=4)
    assert t_jnp > t_fused  # the pad copy is the difference


def test_ensemble_scales_estimate():
    assert _total("heat3d", (256,) * 3, ensemble=8) > \
        7 * _total("heat3d", (256,) * 3)


def test_mesh_shrinks_local_block():
    assert _total("heat3d", (512,) * 3, mesh=(2, 2, 2)) < \
        _total("heat3d", (512,) * 3) / 4


def test_small_config_passes_guard():
    st = make_stencil("heat2d")
    total, _ = budget.check_budget(st, (512, 512), hbm_bytes=V5E_HBM)
    assert total < GiB


def test_cli_flag_parses_and_cpu_backend_skips():
    from mpi_cuda_process_tpu.cli import config_from_args
    from mpi_cuda_process_tpu.plan import check_mem_budget

    cfg = config_from_args(
        ["--stencil", "wave3d", "--grid", "4096,4096,4096",
         "--mesh", "8,8,1", "--fuse", "4", "--mem-check", "error"])
    assert cfg.mem_check == "error"
    # CPU backend: the guard is a no-op (virtual-device test meshes would
    # otherwise trip on host-RAM-sized grids)
    check_mem_budget(cfg)


def test_raw_path_has_no_transient():
    """compute="raw" (whole-step kernels: the state is its own halo) must
    not be charged a pad transient — a fitting raw run was refused before
    this was threaded through (round-4 review finding)."""
    st = make_stencil("heat3d27")
    grid = (1152, 1152, 1152)
    t_raw, parts = budget.estimate_run_bytes(st, grid, compute="raw")
    t_jnp, _ = budget.estimate_run_bytes(st, grid)
    assert t_raw < t_jnp
    assert any("no pad transient" in label for label, _ in parts)
    # ~5.7 GiB state + 5.7 out + 10% — fits 16 GiB where jnp would not
    budget.check_budget(st, grid, compute="raw", hbm_bytes=16 * GiB)
    with pytest.raises(ValueError):
        budget.check_budget(st, grid, hbm_bytes=16 * GiB)


def test_stream_kind_has_no_transient_and_probes_buildability():
    """--fuse-kind stream: the ring lives in VMEM, so HBM holds state +
    output only; the estimate must probe construction so a 'fits' never
    describes an unconstructible run (the budget module's invariant)."""
    st = make_stencil("heat3d")
    total, parts = budget.estimate_run_bytes(
        st, (1024,) * 3, fuse=4, fuse_kind="stream")
    assert any("streaming fused: no pad transient" in label
               for label, _ in parts)
    # 4 GiB state + 4 out + 10% < 16 GiB: the 1024^3 f32 single-chip path
    budget.check_budget(st, (1024,) * 3, fuse=4, fuse_kind="stream",
                        hbm_bytes=16 * GiB)
    # unbuildable shape (too few z chunks): labeled, never silently 'fits'
    _, parts2 = budget.estimate_run_bytes(
        st, (16, 16, 128), fuse=4, fuse_kind="stream")
    assert any("UNBUILDABLE" in label for label, _ in parts2)
    # periodic: cli.build rejects stream (guard-frame), so the estimate
    # must label the path UNBUILDABLE rather than describe a kernel the
    # run never takes (round-4 advisor).  Ensemble runs are BUILDABLE
    # since round 15 (the batched streaming kernel) — priced, not
    # walled; pinned in tests/test_ensemble_engine.py.
    _, parts3 = budget.estimate_run_bytes(
        st, (256,) * 3, fuse=4, fuse_kind="stream", periodic=True)
    assert any("UNBUILDABLE" in label for label, _ in parts3)
    _, parts4 = budget.estimate_run_bytes(
        st, (256,) * 3, fuse=4, fuse_kind="stream", ensemble=2)
    assert not any("UNBUILDABLE" in label for label, _ in parts4)


def test_config5_stream_envelope_builder_verified():
    """Builder-verified config-5 streaming envelope (docs/STATE.md): at
    the local shape 64x4096x4096 (4096^3 on 64x1x1), single-field
    families tile whole-lane; two-field wave3d exceeds the whole-lane
    VMEM gate but tiles via an X-WINDOWED strip (~1.9x read amp vs the
    wide-X tiled kernel's 4.5x).  The picker must never admit a config
    the kernel can't host — a silent admit would compile-OOM a slice."""
    from mpi_cuda_process_tpu.ops.pallas.streamfused import (
        _stream_gates,
        build_stream_sharded_call,
    )

    local, g5 = (64, 4096, 4096), (4096, 4096, 4096)
    st = make_stencil("heat3d")
    assert build_stream_sharded_call(st, local, g5, 4,
                                     interpret=True) is not None
    wave = make_stencil("wave3d")
    assert build_stream_sharded_call(wave, local, g5, 4,
                                     interpret=True) is not None
    gates = _stream_gates(wave, 64, 4096, 4096, 4, None, sharded=True)
    assert gates[7] is not None  # wave needs the x window (bx set)
    # whole-lane tiles forced for wave at this shape must still decline
    assert build_stream_sharded_call(wave, local, g5, 4, tiles=(8, 16),
                                     interpret=True) is None


def test_sharded_stream_budget_slab_operands_only():
    """--fuse-kind stream --mesh: HBM holds state + output + slab
    operands (the VMEM ring is not HBM); config-5 wave in FULL f32 fits
    the same envelope as the zslab kernels, now on the streaming path."""
    st = make_stencil("wave3d")
    total, parts = budget.check_budget(
        st, (4096,) * 3, mesh=(64, 1, 1), fuse=4, fuse_kind="stream",
        hbm_bytes=V5E_HBM)
    assert any("sharded streaming: slab operands" in label
               for label, _ in parts)
    assert 14 * GiB < total < 15 * GiB  # 2x4 state + 4 out + 1 slabs +10%
    # unconstructible local shape: labeled, never a silent 'fits'
    _, parts2 = budget.estimate_run_bytes(
        st, (64, 64, 128), mesh=(16, 1, 1), fuse=4, fuse_kind="stream")
    assert any("UNBUILDABLE" in label for label, _ in parts2)


def test_forced_padfree_never_estimates_the_padded_transient():
    """fuse_kind='padfree' has no padded fallback in cli.build — the
    estimate must not charge padded-transient bytes the run would never
    allocate (it raises instead)."""
    st = make_stencil("heat3d")
    # a shape the padfree builder declines (odd extents)
    t_forced, parts = budget.estimate_run_bytes(
        st, (20, 20, 128), fuse=4, fuse_kind="padfree")
    assert any("pad-free fused" in label for label, _ in parts)
    assert not any("pad transient (+" in label for label, _ in parts)


def test_f32_at_4096_fits_on_z_only_mesh_padfree():
    """The round-4 headline budget row: 4096^3 in FULL f32 fits a 64-chip
    v5e on a z-only mesh with the z-slab pad-free kernel (~9.35 GiB) —
    for the single-field families, and ONLY because the builder actually
    tiles it (the estimate follows the constructible path)."""
    st = make_stencil("heat3d")
    total, parts = budget.check_budget(
        st, (4096,) * 3, mesh=(64, 1, 1), fuse=4, hbm_bytes=V5E_HBM)
    assert 9 * GiB < total < 10 * GiB
    assert any("pad-free" in label for label, _ in parts)


def test_config5_stream_budget_exact_bytes():
    """The launch-day arithmetic, pinned to the byte: config 5 (wave3d
    4096^3, 64x1x1 z-mesh, --fuse 4 --fuse-kind stream) per-device live
    bytes.  bf16: 2 fields x 2 GiB state + 2 GiB donated out + 0.5 GiB
    slab operands, +10% workspace = 7,677,254,041 B (7.150 GiB);
    f32 doubles it to 14.300 GiB.  Both fit 16 GiB v5e HBM — config 5
    is budget-clean in BOTH dtypes on the streaming path, and the
    breakdown the operator reads at launch is exactly this."""
    item = {"bfloat16": 2, "float32": 4}
    for dtype, total_expect in (("bfloat16", 7_677_254_041),
                                ("float32", 15_354_508_083)):
        st = make_stencil("wave3d", dtype=dtype)
        total, parts = budget.estimate_run_bytes(
            st, (4096,) * 3, mesh=(64, 1, 1), fuse=4, fuse_kind="stream")
        # independent arithmetic (not the module's own constants)
        lz, ly, lx = 64, 4096, 4096
        state = 2 * lz * ly * lx * item[dtype]
        out = lz * ly * lx * item[dtype]
        slabs = 2 * 4 * ly * lx * item[dtype] * 2  # 2 sides x m=4, 2 fields
        assert total == int((state + out + slabs) * 1.10) == total_expect
        assert any("slab operands only" in label for label, _ in parts)
        budget.check_budget(st, (4096,) * 3, mesh=(64, 1, 1), fuse=4,
                            fuse_kind="stream", hbm_bytes=16 * GiB)


def test_config5_stream_two_axis_budget_exact_bytes():
    """Round 8: config 5 on the BALANCED 8x8x1 mesh through the 2-AXIS
    streaming kernel — the kind x mesh matrix's last cell, pinned to the
    byte for BOTH dtypes.  HBM holds state + out + the slab/corner
    operand set (z slabs at width m; y slabs and corners at width m plus
    the call's wm_a-aligned copies — 8 for f32, 16 for bf16); the VMEM
    rings are not HBM.  Both dtypes fit 16 GiB v5e HBM, so mesh shape is
    now purely a measurement decision for the streaming kind too."""
    for dtype, item, m_a, total_expect in (
            ("float32", 4, 8, 14_770_870_681),
            ("bfloat16", 2, 16, 7_535_381_708)):
        st = make_stencil("wave3d", dtype=dtype)
        total, parts = budget.estimate_run_bytes(
            st, (4096,) * 3, mesh=(8, 8, 1), fuse=4, fuse_kind="stream")
        # independent arithmetic (not the module's own constants)
        lz, ly, lx, m, nf = 512, 512, 4096, 4, 2
        state = 2 * lz * ly * lx * item
        out = lz * ly * lx * item
        slabs = (2 * m * ly * lx                # z slabs
                 + 2 * (m + m_a) * lz * lx     # y slabs + aligned copies
                 + 4 * m * (m + m_a) * lx      # corners + aligned copies
                 ) * item * nf
        assert total == int((state + out + slabs) * 1.10) == total_expect
        assert any("2-axis stream" in label for label, _ in parts)
        assert not any("UNBUILDABLE" in label for label, _ in parts)
        assert not any("pad transient" in label for label, _ in parts)
        budget.check_budget(st, (4096,) * 3, mesh=(8, 8, 1), fuse=4,
                            fuse_kind="stream", hbm_bytes=V5E_HBM)


def test_config5_pipelined_stream_budget_exact_bytes():
    """Round 9: config 5 through the slab-carry PIPELINED exchange
    (--pipeline), pinned to the byte on BOTH mesh families and BOTH
    dtypes.  The carry adds exactly one slab set beyond the per-pass
    operands (this pass's slabs are consumed while the next pass's are
    in flight), and all four cells still fit 16 GiB v5e HBM — config 5
    stays budget-clean on the new schedule, including the VERDICT
    item-5 bf16-k4 stream rows."""
    from mpi_cuda_process_tpu.ops.pallas.fused import _sublane

    expect = {
        ("float32", (64, 1, 1)): 16_535_624_089,
        ("float32", (8, 8, 1)): 15_368_349_286,
        ("bfloat16", (64, 1, 1)): 8_267_812_044,
        ("bfloat16", (8, 8, 1)): 7_984_067_379,
    }
    for (dtype, mesh), total_expect in expect.items():
        st = make_stencil("wave3d", dtype=dtype)
        total, parts = budget.estimate_run_bytes(
            st, (4096,) * 3, mesh=mesh, fuse=4, fuse_kind="stream",
            pipeline=True)
        # independent arithmetic (not the module's own constants)
        item = {"bfloat16": 2, "float32": 4}[dtype]
        lz, ly, lx = (int(g) // c for g, c in zip((4096,) * 3, mesh))
        m, nf = 4, 2
        state = 2 * lz * ly * lx * item
        out = lz * ly * lx * item
        if mesh == (64, 1, 1):
            slab_set = 2 * m * ly * lx * item * nf
        else:
            m_a = _sublane(item)  # m=4 rounds up to one sublane tile
            slab_set = (2 * m * ly * lx
                        + 2 * (m + m_a) * lz * lx
                        + 4 * m * (m + m_a) * lx) * item * nf
        assert total == int((state + out + 2 * slab_set) * 1.10) \
            == total_expect, (dtype, mesh)
        assert any("pipelined carried slabs" in label
                   for label, _ in parts)
        budget.check_budget(st, (4096,) * 3, mesh=mesh, fuse=4,
                            fuse_kind="stream", pipeline=True,
                            hbm_bytes=V5E_HBM)


def test_pipelined_padfree_counts_carried_set_once():
    """The pad-free kinds: pipeline adds exactly ONE slab+corner set
    (the carry), on top of the per-pass operand set — and the padded
    sharded path is labeled UNSUPPORTED (cli raises; the estimate must
    describe the refusal, not a kernel the run never takes)."""
    st = make_stencil("wave3d")
    t_plain, _ = budget.estimate_run_bytes(
        st, (4096,) * 3, mesh=(8, 8, 1), fuse=4, fuse_kind="padfree")
    t_pipe, parts = budget.estimate_run_bytes(
        st, (4096,) * 3, mesh=(8, 8, 1), fuse=4, fuse_kind="padfree",
        pipeline=True)
    carried = [b for label, b in parts
               if "pipelined carried slabs" in label]
    slab = [b for label, b in parts if "sharded pad-free" in label]
    assert carried == slab  # one extra copy of the per-pass operand set
    assert t_pipe == t_plain + int(carried[0] * 1.10) or \
        abs(t_pipe - t_plain - carried[0] * 1.10) <= 1  # int rounding
    # padded sharded kind + pipeline: labeled UNSUPPORTED, zero bytes
    small = make_stencil("heat3d")
    _, parts2 = budget.estimate_run_bytes(
        small, (64, 64, 128), mesh=(2, 1, 1), fuse=4, pipeline=True)
    assert any("UNSUPPORTED" in label for label, _ in parts2)


def test_stream_two_axis_unbuildable_is_labeled():
    """An unconstructible 2-axis streaming config must be labeled, never
    a silent 'fits' (the budget module's invariant) — local z below the
    3-chunk gate here."""
    st = make_stencil("heat3d")
    _, parts = budget.estimate_run_bytes(
        st, (32, 64, 128), mesh=(2, 2, 1), fuse=4, fuse_kind="stream")
    assert any("UNBUILDABLE" in label for label, _ in parts)


def test_config5_wave_f32_fits_via_wide_x_kernel():
    """Two-field wave3d cannot tile the WHOLE-ROW z-slab window at X=4096
    (VMEM gate), but the wide-X variant windows the lane axis and tiles —
    so the budget charges slabs only and config 5 fits in FULL f32
    (~14.3 GiB/device).  The chain is builder-verified: a 'fits' row
    never describes an unconstructible execution (round-4 review)."""
    st = make_stencil("wave3d")
    total, parts = budget.check_budget(
        st, (4096,) * 3, mesh=(64, 1, 1), fuse=4, hbm_bytes=V5E_HBM)
    assert any("pad-free" in label for label, _ in parts)
    assert 13.5 * GiB < total < 15 * GiB


def test_config5_wave_bf16_k8_wide_x_headroom():
    """bf16 k=8 (margin 8, sublane-16-aligned) tiles wide-X too: config-5
    wave in bf16 with temporal blocking is ~7.7 GiB/device — deep
    headroom for larger tiles or deeper k once measured."""
    st = make_stencil("wave3d", dtype="bfloat16")
    total, parts = budget.check_budget(
        st, (4096,) * 3, mesh=(64, 1, 1), fuse=8, hbm_bytes=V5E_HBM)
    assert any("pad-free" in label for label, _ in parts)
    assert total < 8.5 * GiB


def test_2d_fuse_budget_counts_fullgrid_pad():
    t_plain = _total("life", (2048, 2048))
    t_fused = _total("life", (2048, 2048), fuse=16)
    assert t_fused > 0 and t_plain > 0  # both paths covered, no crash


def test_unsharded_fused_pass_prices_every_field_it_writes():
    """An unsharded fused pass writes both wave fields anew (its
    overlapping windows rule out aliasing): wave3d 1024^3 f32 fused k=4
    is 2x4 GiB state + 2x4 GiB out, +10%, over the chip's HBM (the v5e
    compiler: 16.00 GiB used of 15.75).  One-field heat3d keeps its
    price, and its fused pass fits."""
    wave = make_stencil("wave3d")
    field = 1024**3 * 4
    total, parts = budget.estimate_run_bytes(wave, (1024,) * 3, fuse=4)
    assert total == int(4 * field * 1.10)
    assert ("step output transient: 2 field(s) written anew "
            "(donated double buffer)", 2 * field) in parts
    with pytest.raises(ValueError, match="GiB per device"):
        budget.check_budget(wave, (1024,) * 3, fuse=4, hbm_bytes=V5E_HBM)
    heat = make_stencil("heat3d")
    total, _ = budget.check_budget(heat, (1024,) * 3, fuse=4,
                                   hbm_bytes=V5E_HBM)
    assert total == int(2 * field * 1.10)


def test_inplace_leapfrog_pass_priced_as_the_state():
    """The raw wave kernel writes u_new over u_prev: the state alone."""
    wave = make_stencil("wave3d")
    field = 1024**3 * 4
    total, parts = budget.check_budget(wave, (1024,) * 3, compute="raw",
                                       hbm_bytes=V5E_HBM)
    assert total == int(2 * field * 1.10)
    assert dict(parts)["step output transient: 0 field(s) written anew "
                       "(donated double buffer)"] == 0
    # a raw kernel that writes its fields anew is charged for them
    gs = make_stencil("grayscott3d")
    t_gs, _ = budget.estimate_run_bytes(gs, (512,) * 3, compute="raw")
    assert t_gs == int(4 * 512**3 * 4 * 1.10)


def test_sharded_raw_pass_priced_per_shard():
    """The sharded raw pass on the 2x2 cell's 512x512x1024 shard: the
    shard, one shard-sized ping-pong temporary and the four exchanged
    faces, +10%; no pad transient."""
    st = make_stencil("heat3d")
    grid, mesh = (1024,) * 3, (2, 2, 1)
    shard = 512 * 512 * 1024 * 4
    faces = 2 * (512 + 512) * 1024 * 4
    total, parts = budget.estimate_run_bytes(st, grid, mesh=mesh,
                                             compute="raw")
    assert total == int((2 * shard + faces) * 1.10)
    assert ("sharded raw whole-step kernel: exchanged faces only "
            "(2 z planes + 2 y rows), no pad transient", faces) in parts
    assert total < budget.estimate_run_bytes(st, grid, mesh=mesh)[0]
