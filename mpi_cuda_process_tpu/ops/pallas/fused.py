"""Temporal-blocking fused multi-step Pallas kernels.

The reference performs one full device pass per time step (one
``middle_kernel``+``border_kernel`` launch pair per iteration,
kernel.cu:209/221), so its throughput ceiling is memory bandwidth: every step
re-streams the whole grid.  The same is true of the XLA-fused jnp path here —
~2 HBM passes (1 read + 1 write) per step, measured ~87% of that roofline on
v5e.

This module raises that ceiling the TPU way: a Pallas kernel that advances a
tile **k time steps per HBM round-trip** (classic temporal blocking /
overlapped tiling).  Each program reads an overlapping (bz+2k, by+2k, X)
window of the grid into VMEM, applies k micro-steps entirely in VMEM
(re-pinning the global guard frame between micro-steps, so the semantics are
exactly k applications of ``driver.make_step``), and writes the (bz, by, X)
core.  HBM traffic per step drops from 2 passes to roughly
``((1+2k/bz)(1+2k/by) + 1)/k`` passes — 3-5x less for k=8 on 256^3-class
grids — at the cost of ``(1+2k/bz)(1+2k/by)`` x redundant flops, which the VPU
has headroom for on 7-point stencils.

Layout choices that matter on TPU:
  * The minor (lane) axis x is never padded or sliced: neighbor taps along x
    come from a lane **roll**; the wrapped values land only in the global x
    walls, which the per-micro-step frame mask re-pins anyway.  This keeps
    every VMEM buffer at exactly X lanes (no 264->384 lane-rounding waste) and
    avoids unaligned lane concatenation, which Mosaic cannot lower.
  * The window is assembled from four sublane-tile-aligned blocks of the
    z/y-padded input (core, y-tail, z-tail, corner) — overlapping BlockSpecs
    must start on block-aligned offsets, hence ``bz % 2m == by % 2m == 0``
    and ``2m`` (m = k*halo) a multiple of the DTYPE's sublane tile
    (``_sublane``: 8 for f32, 16 for bf16 — so bf16 halo-1 needs k >= 8).

Operates on the RAW grid (guard frame included, no halo pre-padding), so it is
a whole-step replacement (``fields -> fields after k steps``) rather than a
``compute_fn``; the CLI scans the returned ``step_k`` directly (``--fuse K``,
cli.py) with the iteration count divided by k.
"""

from __future__ import annotations

import functools
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..stencil import Fields, Stencil

from .compat import compiler_params

from .kernels import (
    _VMEM_LIMIT_BYTES,
    _W27_CENTER,
    _W27_CORNER,
    _W27_EDGE,
    _W27_FACE,
    _interpret_default,
    _roll,
)

# Scoped-VMEM cost model for auto-tiling, fit to Mosaic's reported stack
# usage: ~7 live copies of the window + ~2 of the output block.  Round 3
# raised Mosaic's scoped-vmem limit from its 16 MiB default (v5e physically
# has 128 MiB) via compiler_params — bigger tiles mean less overlap
# redundancy; the budget stays below the raised limit so Mosaic's own
# scratch still fits.
_VMEM_LIMIT = int(_VMEM_LIMIT_BYTES * 0.8)

# Micro-steps are unrolled up to this k (measured-fast at k=4); deeper
# blocking runs as a fori_loop to keep the Mosaic program size constant
# (see _fused_kernel).
_UNROLL_MAX_K = 4


# ---------------------------------------------------------------------------
# per-stencil micro-steps: (fields-of-windows, frame) -> fields-of-windows.
# Every neighbor tap is a **roll** (no shrinking slices): sublane/lane
# slicing at odd offsets forces a Mosaic relayout per tap per micro-step,
# which measured ~5x slower than the XLA path; rolls keep every operand at
# the same aligned (bz+2m, by+2m, X) layout.  Wrap-around values from the
# rolls land only in (a) the tile's outermost shell, which temporal validity
# excludes anyway — after m micro-steps only cells >= m*halo away from the
# window edge are correct, and only the inner (bz, by) core is written out —
# and (b) the global domain walls, which the frame mask re-pins every
# micro-step (the in-VMEM equivalent of the driver's per-step frame mask;
# out-of-domain ghost cells of edge tiles are pinned too, bounding their
# garbage).
# ---------------------------------------------------------------------------


def _lap(cur, ndim, interpret):
    """2*ndim+1-point Laplacian via rolls (5-point in 2D, 7-point in 3D).

    Tap order matters: left-associated roll sum, center term LAST — the
    same association as the jnp update path, preserving the fused==plain
    bit-exactness the equivalence tests assert.
    """
    acc = None
    for d in range(ndim):
        for s in (1, -1):
            r = _roll(cur, s, d, interpret)
            acc = r if acc is None else acc + r
    return acc - 2.0 * ndim * cur


# The heat / wave / advect / grayscott micro-step factories read the
# dimensionality from the stencil, so ONE definition serves both the 3D
# windowed kernels here (_MICRO) and the 2D whole-grid kernels
# (fullgrid._MICRO2D) — the 27-point/4th-order micros below stay 3D-only.


def _micro_heat(stencil, interpret):
    alpha = float(stencil.params["alpha"])
    ndim = stencil.ndim

    def micro(fields, frame):
        (cur,) = fields
        new = cur + alpha * _lap(cur, ndim, interpret)
        return (jnp.where(frame, cur, new),)

    return micro


def _micro_heat3d27(stencil, interpret):
    # Same per-z-level separable partials as rawstep._taps27: the in-plane
    # 3x3 kernel is [center', face', edge'] over {self, y/x lines,
    # diagonals}, and the dz=+-1 levels share one combination, rolled both
    # ways in z.  8 rolls per micro-step, ~5 live window buffers.
    alpha = float(stencil.params["alpha"])

    def micro(fields, frame):
        (cur,) = fields
        yl = _roll(cur, 1, 1, interpret) + _roll(cur, -1, 1, interpret)
        xl = _roll(cur, 1, 2, interpret) + _roll(cur, -1, 2, interpret)
        diag = _roll(yl, 1, 2, interpret) + _roll(yl, -1, 2, interpret)
        level0 = (_W27_CENTER * cur + _W27_FACE * (yl + xl)
                  + _W27_EDGE * diag)
        level1 = (_W27_FACE * cur + _W27_EDGE * (yl + xl)
                  + _W27_CORNER * diag)
        acc = (level0 + _roll(level1, 1, 0, interpret)
               + _roll(level1, -1, 0, interpret))
        return (jnp.where(frame, cur, cur + alpha * acc),)

    return micro


def _micro_heat3d4th(stencil, interpret):
    # 4th-order 13-point Laplacian, halo 2: taps at distance 1 and 2.
    alpha = float(stencil.params["alpha"])
    w = {1: 16.0 / 12.0, 2: -1.0 / 12.0}
    c = -30.0 / 12.0 * 3.0

    def micro(fields, frame):
        (cur,) = fields
        acc = c * cur
        for dist in (1, 2):
            for o in (-dist, dist):
                acc = acc + w[dist] * (
                    _roll(cur, -o, 0, interpret)
                    + _roll(cur, -o, 1, interpret)
                    + _roll(cur, -o, 2, interpret)
                )
        return (jnp.where(frame, cur, cur + alpha * acc),)

    return micro


def _micro_wave(stencil, interpret):
    c2dt2 = float(stencil.params["c2dt2"])
    ndim = stencil.ndim

    def micro(fields, frame):
        u, uprev = fields
        new = 2.0 * u - uprev + c2dt2 * _lap(u, ndim, interpret)
        # leapfrog carry: new u_prev is the old u, verbatim (no pin needed
        # — its frame is correct by induction, exactly carry_map's rule)
        return (jnp.where(frame, u, new), u)

    return micro


def _micro_advect(stencil, interpret):
    # First-order upwind, constant Courant numbers (ops/advection.py):
    # each axis taps ONLY the upstream neighbor — one roll per nonzero
    # component, direction chosen by the sign.
    courant = tuple(float(c) for c in stencil.params["courant"])

    def micro(fields, frame):
        (cur,) = fields
        acc = cur
        for d, c in enumerate(courant):
            if c == 0.0:
                continue
            up = _roll(cur, 1 if c > 0 else -1, d, interpret)
            acc = acc - abs(c) * (cur - up)
        return (jnp.where(frame, cur, acc),)

    return micro


def _micro_grayscott(stencil, interpret):
    # Two coupled diffusing fields, BOTH with footprints (unlike wave's
    # neighbor-free carry) — the jnp path pays 4 HBM arrays per step and
    # measured 14.4 Gcells/s at 256^3 (2026-07-29..31 chip campaign);
    # fusing k steps amortizes all of it.
    du = float(stencil.params["du"])
    dv = float(stencil.params["dv"])
    f = float(stencil.params["f"])
    kappa = float(stencil.params["kappa"])
    ndim = stencil.ndim

    def micro(fields, frame):
        u, v = fields
        uvv = u * v * v
        new_u = u + du * _lap(u, ndim, interpret) - uvv + f * (1.0 - u)
        new_v = v + dv * _lap(v, ndim, interpret) + uvv - (f + kappa) * v
        return (jnp.where(frame, u, new_u), jnp.where(frame, v, new_v))

    return micro


def _micro_sor(stencil, interpret):
    # Red-black SOR: one micro-step = red half-sweep then black half-sweep
    # reading the fresh red values (ops/sor.py phases).  ``parity`` is the
    # kernel-supplied color mask (global coordinate parity — derived from
    # program ids here, from the prelude iotas in fullgrid.py); the black
    # sweep's dependence on fresh red values is why a full micro-step
    # consumes 2*halo of validity margin (see ``_halo_per_micro``).
    omega = float(stencil.params["omega"])
    ndim = stencil.ndim

    def micro(fields, frame, parity):
        (cur,) = fields
        for color in (0, 1):
            relaxed = cur + (omega / (2 * ndim)) * _lap(cur, ndim, interpret)
            new = jnp.where(parity == color, relaxed, cur)
            cur = jnp.where(frame, fields[0], new)
        return (cur,)

    return micro


# name -> (micro factory, halo, carried fields)
_MICRO = {
    "heat3d": (_micro_heat, 1, 1),
    "heat3d27": (_micro_heat3d27, 1, 1),
    "heat3d4th": (_micro_heat3d4th, 2, 1),
    "wave3d": (_micro_wave, 1, 2),
    "grayscott3d": (_micro_grayscott, 1, 2),
    "advect3d": (_micro_advect, 1, 1),
    "sor3d": (_micro_sor, 1, 1),
}


def _halo_per_micro(stencil: Stencil) -> int:
    """Validity margin one micro-step consumes: halo cells PER PHASE."""
    micro_halo = _MICRO[stencil.name][1]
    return micro_halo * max(1, len(stencil.phases or ()))


def _assemble_window(a, b, c, d):
    top = jnp.concatenate([a[...], b[...]], axis=1)
    bot = jnp.concatenate([c[...], d[...]], axis=1)
    return jnp.concatenate([top, bot], axis=0)


def _run_micros(micro, fields, frame, extra, k):
    """Apply k micro-steps: unrolled for small k, fori_loop beyond
    (constant Mosaic program size — the bf16 k=8 compile-hang fix)."""
    if k > _UNROLL_MAX_K:
        return jax.lax.fori_loop(
            0, k, lambda _, fs: micro(fs, frame, *extra), fields)
    for _ in range(k):
        fields = micro(fields, frame, *extra)
    return fields


def _fused_kernel(micro, nfields, k, margin, halo, bz, by, shape, periodic,
                  parity, sharded, interpret, *refs):
    """k micro-steps on constant-shape VMEM windows; multi-field generic.

    ``refs`` is — when ``sharded`` — an SMEM (2,) int32 scalar ref holding
    this shard's global (z, y) origin first, then 4 window blocks per
    field (core, y-tail, z-tail, corner — overlapping BlockSpecs must
    start block-aligned, hence the assembly), then ``nfields`` output
    blocks.  ``margin = k * halo * phases`` is the temporal-validity
    margin consumed by the k micro-steps (``_halo_per_micro``); ``halo``
    is the stencil's guard-frame width.

    ``shape`` is the GLOBAL (Z, Y, X): with it the frame mask is derived
    in-kernel from program ids (+ the origin scalars when sharded) —
    a BlockSpec index_map cannot see the traced axis_index, but the
    kernel body can read it from SMEM, which is why no mask ARRAY is ever
    streamed (round 3 streamed a whole padded mask per step).

    ``periodic`` (unsharded): no guard frame — the caller wrap-pads z/y,
    and the in-window lane rolls wrap at X = the full domain width (x is
    never sharded or padded), which IS the periodic x boundary.  The
    sharded periodic caller uses ``sharded=False`` with the LOCAL shape
    (wrap halos arrive via the exchange; parity stays globally consistent
    because shard origins and extents are even by the alignment gates).
    """
    if sharded:
        origins, refs = refs[0], refs[1:]
        z_off, y_off = origins[0], origins[1]
    else:
        z_off = y_off = 0
    fields = tuple(
        _assemble_window(*refs[4 * f:4 * f + 4]) for f in range(nfields))
    like = fields[0]
    outs = refs[4 * nfields:]
    # Window origin in global coords (input pre-padded by margin in z/y).
    frame, extra = _window_frame(
        like.shape, z_off + pl.program_id(0) * bz - margin,
        y_off + pl.program_id(1) * by - margin, shape, halo, periodic,
        parity)
    # k<=4 unrolls (measured-fast); deeper k runs as a fori_loop — the
    # unrolled bf16 k=8 hung the Mosaic compile (2026-07-29..31 chip campaign,
    # heat3d_256_bf16_fused8), and a loop body keeps program size constant.
    fields = _run_micros(micro, fields, frame, extra, k)
    for o, f in zip(outs, fields):
        o[...] = f[margin:bz + margin, margin:by + margin, :]


def _window_frame(win_shape, z0, y0, shape, halo, periodic, parity, x0=0):
    """(frame mask, parity extra) for a window whose global origin is
    (z0, y0, x0).  Shared by every fused kernel variant — the single
    definition of the guard-frame predicate and the red-black coloring.
    ``x0`` is nonzero only for the wide-X kernels (x windowed too).

    Global coordinate parity: Z/Y/X are even by the tileability gates, so
    the periodic wrap keeps the coloring consistent; jnp's ``%`` is a
    floor-mod, so ghost coords (zidx < 0) color as Z+zidx — consistent
    with the wrap, and irrelevant in guard-frame mode (ghosts are pinned).
    """
    Z, Y, X = shape
    zidx = jax.lax.broadcasted_iota(jnp.int32, win_shape, 0) + z0
    yidx = jax.lax.broadcasted_iota(jnp.int32, win_shape, 1) + y0
    xidx = jax.lax.broadcasted_iota(jnp.int32, win_shape, 2) + x0
    if periodic:
        frame = jnp.zeros(win_shape, jnp.bool_)
    else:
        frame = (
            (zidx < halo) | (zidx >= Z - halo)
            | (yidx < halo) | (yidx >= Y - halo)
            | (xidx < halo) | (xidx >= X - halo)
        )
    extra = ((zidx + yidx + xidx) % 2,) if parity else ()
    return frame, extra


def _assemble_window3x3(refs):
    rows = [jnp.concatenate([b[...] for b in refs[r * 3:r * 3 + 3]], axis=1)
            for r in range(3)]
    return jnp.concatenate(rows, axis=0)


def _fused_raw_kernel(micro, nfields, k, margin, halo, bz, by, shape,
                      periodic, parity, interpret, *refs):
    """Pad-free variant of ``_fused_kernel``: the window is assembled from
    NINE blocks of the RAW grid (3x3: pre/core/post in z and y, tail
    granularity ``2*margin``) instead of four blocks of a z/y-padded copy —
    so no full-grid pad transient ever materializes.  At 1024^3 f32 the
    padded path's extra ~4.3 GiB copy was the RESOURCE_EXHAUSTED
    (2026-07-29..31 campaign label heat3d_1024_f32_fused4); pad-free needs
    only the two state buffers.

    The assembled window carries margin ``2*margin`` per side (overlapping
    BlockSpecs must start block-aligned, and the window origin sits at
    ``i*bz - 2m`` which is only ``2m``-aligned) — one extra margin of
    redundant compute; temporal validity needs only ``margin``.

    Boundary semantics: non-periodic wall tiles CLAMP their pre/post specs
    to the wall block, so out-of-domain ghost cells hold in-domain garbage
    rather than pad zeros.  That is safe for exactly the reason the padded
    kernel's ghost pinning is: ghosts satisfy the frame predicate, are
    re-pinned every micro-step, and only ever feed updates of OTHER pinned
    cells (interior outputs tap at most ``halo`` past the guard frame,
    never a ghost).  Periodic tiles WRAP their pre/post block indices
    instead, which reproduces the wrap-pad values exactly.
    """
    wm = 2 * margin
    fields = tuple(
        _assemble_window3x3(refs[9 * f:9 * f + 9]) for f in range(nfields))
    like = fields[0]
    outs = refs[9 * nfields:]
    frame, extra = _window_frame(
        like.shape, pl.program_id(0) * bz - wm, pl.program_id(1) * by - wm,
        shape, halo, periodic, parity)
    fields = _run_micros(micro, fields, frame, extra, k)
    for o, f in zip(outs, fields):
        o[...] = f[wm:bz + wm, wm:by + wm, :]


def _tail_index_fns(extent, block, g, wrap):
    """(pre, post) block-index functions for one windowed axis: blocks of
    granularity ``g`` covering a tile's pre/post tails, WRAPPED (periodic)
    or CLAMPED to the walls (guard-frame / slab-selected).  The single
    definition of the wall-index convention for every 9-block kernel."""
    nb = extent // g
    r = block // g
    if wrap:
        return (lambda i: (i * r - 1) % nb,
                lambda i: ((i + 1) * r) % nb)
    return (lambda i: jnp.maximum(i * r - 1, 0),
            lambda i: jnp.minimum((i + 1) * r, nb - 1))


def _raw_window_specs(Z, Y, X, bz, by, m, wrap_z, wrap_y):
    """Nine BlockSpecs assembling one (bz+4m, by+4m, X) window from the raw
    grid.  Tail blocks have granularity g=2m (block-aligned origins); wall
    tiles clamp (guard-frame mode / slab-selected walls) or wrap
    (periodic) per axis."""
    g = 2 * m
    zp, zn = _tail_index_fns(Z, bz, g, wrap_z)
    yp, yn = _tail_index_fns(Y, by, g, wrap_y)
    return [
        pl.BlockSpec((g, g, X), lambda i, j: (zp(i), yp(j), 0)),
        pl.BlockSpec((g, by, X), lambda i, j: (zp(i), j, 0)),
        pl.BlockSpec((g, g, X), lambda i, j: (zp(i), yn(j), 0)),
        pl.BlockSpec((bz, g, X), lambda i, j: (i, yp(j), 0)),
        pl.BlockSpec((bz, by, X), lambda i, j: (i, j, 0)),
        pl.BlockSpec((bz, g, X), lambda i, j: (i, yn(j), 0)),
        pl.BlockSpec((g, g, X), lambda i, j: (zn(i), yp(j), 0)),
        pl.BlockSpec((g, by, X), lambda i, j: (zn(i), j, 0)),
        pl.BlockSpec((g, g, X), lambda i, j: (zn(i), yn(j), 0)),
    ]


def _fused_zslab_kernel(micro, nfields, k, margin, halo, bz, by, gshape,
                        periodic, parity, nz_tiles, interpret, *refs):
    """Sharded PAD-FREE kernel for z-only decompositions.

    Like ``_fused_raw_kernel`` (9 clamped/wrapped blocks of the raw LOCAL
    field), except the z-direction wall tiles select their pre/post window
    rows from exchanged neighbor SLABS instead of clamp garbage — interior
    shard faces need genuine remote values, which the clamp trick cannot
    supply.  ``refs``: an SMEM (2,) int32 global-origin scalar first, then
    per field 9 core views + 3 views of the lower-neighbor slab (m, Y, X)
    + 3 of the upper, then ``nfields`` outputs.

    Geometry: the assembled window spans local rows
    ``[i*bz - 2m, i*bz + bz + 2m)``.  At the shard's z-walls the outer
    ``2m`` rows decompose as m don't-care rows (outside even the exchange
    width; temporal validity never reads them into a surviving cell) + m
    slab rows, so ``concat([slab_row, slab_row])`` places the real slab
    values exactly where validity needs them.  The y axis is whole on
    every shard, so its walls are GLOBAL walls and the plain clamp/wrap
    of ``_raw_window_specs`` stays sound.

    Why this exists: the exchange-padded local block was the last
    full-size transient in the 4096^3 budget (8.25 GiB f32 per device on
    a 64-chip mesh) — with slabs as operands, config 5 fits in f32
    (docs/STATE.md budget table).
    """
    wm = 2 * margin
    origins, refs = refs[0], refs[1:]
    per = 15
    iz = pl.program_id(0)
    fields = []
    for f in range(nfields):
        c = refs[per * f:per * f + 9]
        zlo = refs[per * f + 9:per * f + 12]
        zhi = refs[per * f + 12:per * f + 15]
        rows_c = [
            jnp.concatenate([c[r * 3][...], c[r * 3 + 1][...],
                             c[r * 3 + 2][...]], axis=1)
            for r in range(3)
        ]
        row_lo = jnp.concatenate([z[...] for z in zlo], axis=1)
        row_hi = jnp.concatenate([z[...] for z in zhi], axis=1)
        pre = jnp.where(iz == 0,
                        jnp.concatenate([row_lo, row_lo], axis=0),
                        rows_c[0])
        post = jnp.where(iz == nz_tiles - 1,
                         jnp.concatenate([row_hi, row_hi], axis=0),
                         rows_c[2])
        fields.append(jnp.concatenate([pre, rows_c[1], post], axis=0))
    fields = tuple(fields)
    like = fields[0]
    outs = refs[per * nfields:]
    frame, extra = _window_frame(
        like.shape, origins[0] + iz * bz - wm,
        origins[1] + pl.program_id(1) * by - wm, gshape, halo, periodic,
        parity)
    fields = _run_micros(micro, fields, frame, extra, k)
    for o, f in zip(outs, fields):
        o[...] = f[wm:bz + wm, wm:by + wm, :]


def _zslab_specs(Lz, Y, X, bz, by, m, periodic):
    """Specs for the z-sharded pad-free kernel: 9 core views (z CLAMPED —
    wall values are replaced by the slab selects — y clamp/wrap) + 3 views
    of an (m, Y, X) slab covering the window's y span.  The slab's m-row
    extent is the MAJOR axis, so no sublane constraint applies to it; the
    y views reuse the core tails' aligned sizes."""
    g = 2 * m
    yp, yn = _tail_index_fns(Y, by, g, wrap=periodic)
    core = _raw_window_specs(Lz, Y, X, bz, by, m,
                             wrap_z=False, wrap_y=periodic)
    slab = [
        pl.BlockSpec((m, g, X), lambda i, j: (0, yp(j), 0)),
        pl.BlockSpec((m, by, X), lambda i, j: (0, j, 0)),
        pl.BlockSpec((m, g, X), lambda i, j: (0, yn(j), 0)),
    ]
    return core, slab


def _assemble_yz_window(blocks, iz, jy, nz_tiles, ny_tiles):
    """Assemble one (bz+4m, by+4m, X') window with slab selects on BOTH
    wall axes — the 2-axis generalization of ``_fused_zslab_kernel``'s
    z-only selects (STATE.md round-4 open avenue 5).

    ``blocks`` is 25 loaded blocks of one field at one x-position:
    9 core views (3x3 pre/core/post in z and y, BOTH axes clamped — wall
    values are replaced by the selects below), 3 y-views of the lower
    z-slab, 3 of the upper, 3 z-views of the lower y-slab (operands
    pre-DUPLICATED to 2m columns: cols [-2m, -m) land on don't-care rows,
    [-m, 0) on the genuine slab), 3 of the upper, and the 4 corner pieces
    (also 2m-duplicated; ll/lh/hl/hh in (z-side, y-side) order — the
    two-pass-composed diagonal-neighbor data, ``halo.exchange_slabs_2axis``).

    Placement argument, per wall: the window's outer 2m rows/cols at a
    shard face decompose as m don't-care (outside even the exchange
    width — temporal validity never reads them into a surviving cell)
    + m genuine slab rows/cols, so ``concat([slab_row, slab_row])`` in z
    and the 2m-duplicated operands in y put real values exactly where
    validity needs them.  At a corner program both substitutions apply:
    the z-wall row's y-tail is replaced by the corner piece (not the
    z-slab's clamped y view), so the (z±, y±) ghost quadrant holds the
    diagonal neighbor's block.  Unsharded axes receive bc-fill/wrap
    dummy slabs from the caller, which is exactly what a local pad
    would supply — one assembly serves every mesh shape.
    """
    core, zlo = blocks[:9], blocks[9:12]
    zhi, ylo = blocks[12:15], blocks[15:18]
    yhi, corners = blocks[18:21], blocks[21:25]
    c_ll, c_lh, c_hl, c_hh = corners
    at_ylo, at_yhi = jy == 0, jy == ny_tiles - 1
    rows = []
    for r in range(3):
        pre = jnp.where(at_ylo, ylo[r], core[3 * r])
        post = jnp.where(at_yhi, yhi[r], core[3 * r + 2])
        rows.append(jnp.concatenate([pre, core[3 * r + 1], post], axis=1))

    def zrow(zv, c_lo, c_hi):
        pre = jnp.where(at_ylo, c_lo, zv[0])
        post = jnp.where(at_yhi, c_hi, zv[2])
        return jnp.concatenate([pre, zv[1], post], axis=1)

    row_lo = zrow(zlo, c_ll, c_lh)
    row_hi = zrow(zhi, c_hl, c_hh)
    pre = jnp.where(iz == 0,
                    jnp.concatenate([row_lo, row_lo], axis=0), rows[0])
    post = jnp.where(iz == nz_tiles - 1,
                     jnp.concatenate([row_hi, row_hi], axis=0), rows[2])
    return jnp.concatenate([pre, rows[1], post], axis=0)


def _fused_yzslab_kernel(micro, nfields, k, margin, halo, bz, by, gshape,
                         periodic, parity, nz_tiles, ny_tiles, interpret,
                         *refs):
    """Sharded PAD-FREE kernel for (z, y)-decomposed meshes.

    Like ``_fused_zslab_kernel`` but with slab selects on BOTH wall axes
    plus the 4 two-pass-composed corner operands — 2D meshes stop paying
    the exchange-padded HBM copy (the last pad transient on 2-axis
    decompositions).  ``refs``: an SMEM (2,) int32 global-origin scalar
    first, then per field the 25 views ``_assemble_yz_window`` documents,
    then ``nfields`` outputs.  Frame/parity from origins + program ids,
    exactly the z-slab kernel's scheme (origins now carry BOTH axes'
    shard offsets).
    """
    wm = 2 * margin
    origins, refs = refs[0], refs[1:]
    per = 25
    iz, jy = pl.program_id(0), pl.program_id(1)
    fields = tuple(
        _assemble_yz_window([r[...] for r in refs[per * f:per * f + per]],
                            iz, jy, nz_tiles, ny_tiles)
        for f in range(nfields))
    like = fields[0]
    outs = refs[per * nfields:]
    frame, extra = _window_frame(
        like.shape, origins[0] + iz * bz - wm, origins[1] + jy * by - wm,
        gshape, halo, periodic, parity)
    fields = _run_micros(micro, fields, frame, extra, k)
    for o, f in zip(outs, fields):
        o[...] = f[wm:bz + wm, wm:by + wm, :]


def _yzslab_specs(Lz, Y, X, bz, by, m):
    """25 per-field specs for the 2-axis pad-free kernel: 9 core views
    (BOTH axes clamped — every wall is a slab-selected shard face or a
    frame-re-pinned global wall), 3 y-views per z-slab ((m, ·, X): the
    m-row extent is the MAJOR axis, no sublane constraint), 3 z-views
    per y-slab (operand pre-duplicated to 2m columns so the block's
    sublane extent is ``2m`` — tile-aligned by ``_tiles_valid``'s gate —
    instead of the unaligned ``m``), and 4 corner views (same 2m
    duplication)."""
    g = 2 * m
    zp, zn = _tail_index_fns(Lz, bz, g, wrap=False)
    yp, yn = _tail_index_fns(Y, by, g, wrap=False)
    core = _raw_window_specs(Lz, Y, X, bz, by, m,
                             wrap_z=False, wrap_y=False)
    zslab = [
        pl.BlockSpec((m, g, X), lambda i, j: (0, yp(j), 0)),
        pl.BlockSpec((m, by, X), lambda i, j: (0, j, 0)),
        pl.BlockSpec((m, g, X), lambda i, j: (0, yn(j), 0)),
    ]
    yslab = [
        pl.BlockSpec((g, g, X), lambda i, j: (zp(i), 0, 0)),
        pl.BlockSpec((bz, g, X), lambda i, j: (i, 0, 0)),
        pl.BlockSpec((g, g, X), lambda i, j: (zn(i), 0, 0)),
    ]
    corner = [pl.BlockSpec((m, g, X), lambda i, j: (0, 0, 0))
              for _ in range(4)]
    return core + zslab + zslab + yslab + yslab + corner


def build_yzslab_padfree_call(
    stencil: Stencil,
    local_shape: Tuple[int, int, int],
    global_shape: Tuple[int, int, int],
    k: int,
    tiles: Optional[Tuple[int, int]] = None,
    interpret: Optional[bool] = None,
    periodic: bool = False,
):
    """Sharded pad-free fused call for (z, y)-decomposed meshes.

    The call takes: origins (int32 (2,): this shard's global z AND y
    block offsets), then per field 9 views of the raw LOCAL block +
    3 views of each z-slab + 3 views of each (2m-duplicated) y-slab +
    the 4 (2m-duplicated) corner pieces, and returns ``nfields``
    local-shape arrays advanced k steps.  Returns
    ``(call, margin, nfields)`` or None.

    Why this exists: every pad-free kind was z-mesh-only, so a 2-axis
    mesh silently fell back to the exchange-padded step — forfeiting the
    communication-minimizing balanced decomposition (arXiv:2108.11076's
    surface-to-volume argument: an 8x8x1 mesh cuts config-5 face bytes
    ~8x vs 64x1x1) unless the operator accepted the pad transient.  The
    corner operands follow the portable-collective redistribution
    pattern (slabs of slabs, arXiv:2112.01075) rather than a diagonal
    ppermute.
    """
    if not fused_supported(stencil):
        return None
    if interpret is None:
        interpret = _interpret_default()
    micro_factory, halo, nfields = _MICRO[stencil.name]
    margin = k * _halo_per_micro(stencil)
    Lz, Y, X = (int(s) for s in local_shape)
    gz, gy, gx = (int(s) for s in global_shape)
    if stencil.parity_sensitive and periodic and (gx % 2 or gy % 2
                                                  or gz % 2):
        return None
    itemsize = jnp.dtype(stencil.dtype).itemsize
    if tiles is None:
        tiles = _pick_tiles(Lz, Y, X, margin, itemsize, nfields,
                            wm=2 * margin)
    if tiles is None:
        return None
    bz, by = tiles
    if not _tiles_valid(Lz, Y, bz, by, margin, itemsize):
        return None
    micro = micro_factory(stencil, interpret)
    grid = (Lz // bz, Y // by)
    per_field = _yzslab_specs(Lz, Y, X, bz, by, margin)
    out_spec = pl.BlockSpec((bz, by, X), lambda i, j: (i, j, 0))
    call = pl.pallas_call(
        functools.partial(
            _fused_yzslab_kernel, micro, nfields, k, margin, halo, bz, by,
            (gz, gy, gx), periodic, stencil.parity_sensitive, Lz // bz,
            Y // by, interpret),
        name="fused_yzslab_padfree",
        grid=grid,
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM)]
        + per_field * nfields,
        out_specs=[out_spec] * nfields,
        out_shape=[jax.ShapeDtypeStruct((Lz, Y, X), stencil.dtype)
                   for _ in range(nfields)],
        interpret=interpret,
        compiler_params=None if interpret else compiler_params(
            vmem_limit_bytes=_VMEM_LIMIT_BYTES,
            dimension_semantics=("arbitrary", "arbitrary")),
    )
    return call, margin, nfields


_XWIN_GX = 128  # x-margin/granularity: one lane tile (>= any margin m)


def _tiles_valid(Z, Y, bz, by, margin, itemsize) -> bool:
    """Structural gates for EXPLICIT tiles — the same constraints the auto
    pickers enforce.  A bz/by that is not a multiple of 2m degenerates
    ``_tail_index_fns`` (r = 0) into silently-wrong window geometry
    (found by the sor3d wide-X test: margin 8 with bz=8 tiles), so every
    builder validates caller-supplied tiles through this."""
    return not (bz % (2 * margin) or by % (2 * margin)
                or Z % bz or Y % by
                or (2 * margin) % _sublane(itemsize))


def _pick_xwin_tiles(Lz, Y, X, margin, itemsize, nfields):
    """(bz, by, bx) for the wide-X kernel — the SAME sublane gate, VMEM
    cost model, and scoring as ``_pick_tiles`` (delegated there, so a
    recalibration of the live-copy model applies to every picker), with
    the lane axis iterated over its own candidate ladder."""
    best = None
    for bx in (2048, 1024, 512, 256, 128):
        if X % bx or bx % _XWIN_GX:
            continue
        tiles = _pick_tiles(Lz, Y, bx + 2 * _XWIN_GX, margin, itemsize,
                            nfields, wm=2 * margin)
        if tiles is None:
            continue
        bz, by = tiles
        window = ((bz + 4 * margin) * (by + 4 * margin)
                  * (bx + 2 * _XWIN_GX))
        core = bz * by * bx
        score = (core / window, core)
        if best is None or score > best[0]:
            best = (score, (bz, by, bx))
    return best[1] if best else None


def build_zslab_xwin_call(
    stencil: Stencil,
    local_shape: Tuple[int, int, int],
    global_shape: Tuple[int, int, int],
    k: int,
    tiles: Optional[Tuple[int, int, int]] = None,
    interpret: Optional[bool] = None,
    periodic: bool = False,
):
    """Wide-X sharded pad-free fused call (z-only decomposition, x
    windowed at lane-tile granularity).

    The fallback when ``build_zslab_padfree_call``'s whole-row windows
    exceed VMEM (wide X x multi-field).  The call takes: origins (int32
    (2,)), then per field 27 core views + 9 views of each z-slab (pass
    the block 27x and each slab 9x), and returns ``nfields`` local-shape
    arrays advanced k steps.  Returns ``(call, margin, nfields)`` or
    None.  Read amplification is the price: (1+4m/bz)(1+4m/by)
    (1+2*128/bx) — still a large net traffic win at k steps/pass vs the
    cliff-regime jnp path, which is why this exists for config-5 wave.
    """
    if not fused_supported(stencil):
        return None
    if interpret is None:
        interpret = _interpret_default()
    micro_factory, halo, nfields = _MICRO[stencil.name]
    margin = k * _halo_per_micro(stencil)
    if _XWIN_GX < margin:
        return None  # x shell must absorb the full validity margin
    Lz, Y, X = (int(s) for s in local_shape)
    gz, gy, gxx = (int(s) for s in global_shape)
    if stencil.parity_sensitive and periodic and (gxx % 2 or gy % 2
                                                 or gz % 2):
        return None
    itemsize = jnp.dtype(stencil.dtype).itemsize
    if tiles is None:
        tiles = _pick_xwin_tiles(Lz, Y, X, margin, itemsize, nfields)
    if tiles is None:
        return None
    bz, by, bx = tiles
    if bx >= X:
        return None  # whole-row windows: use the plain z-slab kernel
    if not _tiles_valid(Lz, Y, bz, by, margin, itemsize) \
            or X % bx or bx % _XWIN_GX:
        return None
    micro = micro_factory(stencil, interpret)
    grid = (Lz // bz, Y // by, X // bx)
    core, slab = _xwin_specs(Lz, Y, X, bz, by, bx, margin, periodic)
    per_field = core + slab + slab
    out_spec = pl.BlockSpec((bz, by, bx), lambda i, j, l: (i, j, l))
    call = pl.pallas_call(
        functools.partial(
            _fused_zslab_xwin_kernel, micro, nfields, k, margin, halo,
            bz, by, bx, (gz, gy, gxx), periodic,
            stencil.parity_sensitive, Lz // bz, interpret),
        name="fused_zslab_xwin",
        grid=grid,
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM)]
        + per_field * nfields,
        out_specs=[out_spec] * nfields,
        out_shape=[jax.ShapeDtypeStruct((Lz, Y, X), stencil.dtype)
                   for _ in range(nfields)],
        interpret=interpret,
        compiler_params=None if interpret else compiler_params(
            vmem_limit_bytes=_VMEM_LIMIT_BYTES,
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary")),
    )
    return call, margin, nfields


def _fused_zslab_xwin_kernel(micro, nfields, k, margin, halo, bz, by, bx,
                             gshape, periodic, parity, nz_tiles, interpret,
                             *refs):
    """Wide-X variant of ``_fused_zslab_kernel``: the x (lane) axis is
    windowed too, at ``_XWIN_GX``-lane granularity — for grids whose full
    X extent makes whole-row windows exceed VMEM (two-field wave3d at
    X=4096 lanes, the config-5 gap in docs/STATE.md's budget table).

    Geometry per field: 27 core views (3x3x3 pre/core/post in z, y, x;
    z/y tails at 2m granularity, x tails one lane tile) + 9 views of each
    z-slab (3x3 in y, x).  The window is (bz+4m, by+4m, bx+2*GX); lane
    rolls wrap at the WINDOW extent, and the wrap garbage lands in the
    outer GX-lane x shell, which the output inset (GX >= m) excludes —
    the same temporal-validity argument as the z/y margins.  x walls are
    GLOBAL walls (x is never sharded), so the clamp/wrap spec trick is
    sound there; only the z walls need the slab selects.
    """
    wm = 2 * margin
    gx = _XWIN_GX
    origins, refs = refs[0], refs[1:]
    per = 27 + 9 + 9
    iz = pl.program_id(0)
    fields = []
    for f in range(nfields):
        base = per * f
        # three x-positions, each a z/y 3x3 of 9 refs, concatenated in x
        subs = []
        for t in range(3):
            subs.append(_assemble_window3x3(
                refs[base + 9 * t:base + 9 * t + 9]))
        win_c = jnp.concatenate(subs, axis=2)
        lo_refs = refs[base + 27:base + 36]
        hi_refs = refs[base + 36:base + 45]
        row_lo = jnp.concatenate(
            [jnp.concatenate([r[...] for r in lo_refs[3 * t:3 * t + 3]],
                             axis=1) for t in range(3)], axis=2)
        row_hi = jnp.concatenate(
            [jnp.concatenate([r[...] for r in hi_refs[3 * t:3 * t + 3]],
                             axis=1) for t in range(3)], axis=2)
        pre = jnp.where(iz == 0,
                        jnp.concatenate([row_lo, row_lo], axis=0),
                        win_c[:wm])
        post = jnp.where(iz == nz_tiles - 1,
                         jnp.concatenate([row_hi, row_hi], axis=0),
                         win_c[bz + wm:])
        fields.append(jnp.concatenate([pre, win_c[wm:bz + wm], post],
                                      axis=0))
    fields = tuple(fields)
    like = fields[0]
    outs = refs[per * nfields:]
    frame, extra = _window_frame(
        like.shape, origins[0] + iz * bz - wm,
        origins[1] + pl.program_id(1) * by - wm, gshape, halo, periodic,
        parity, x0=pl.program_id(2) * bx - gx)
    fields = _run_micros(micro, fields, frame, extra, k)
    for o, f in zip(outs, fields):
        o[...] = f[wm:bz + wm, wm:by + wm, gx:bx + gx]


def _xwin_specs(Lz, Y, X, bz, by, bx, m, periodic):
    """(27 core specs ordered x-position-major then z/y 3x3, 9 slab
    specs) for the wide-X z-slab kernel."""
    g = 2 * m
    gx = _XWIN_GX
    zp, zn = _tail_index_fns(Lz, bz, g, wrap=False)  # slab selects own walls
    yp, yn = _tail_index_fns(Y, by, g, wrap=periodic)
    xp, xn = _tail_index_fns(X, bx, gx, wrap=periodic)
    zpos = [(g, zp), (bz, lambda i: i), (g, zn)]
    ypos = [(g, yp), (by, lambda j: j), (g, yn)]
    xpos = [(gx, xp), (bx, lambda l: l), (gx, xn)]
    core = []
    for xs, xf in xpos:
        for zs, zf in zpos:
            for ys, yf in ypos:
                core.append(pl.BlockSpec(
                    (zs, ys, xs),
                    (lambda zf=zf, yf=yf, xf=xf:
                     lambda i, j, l: (zf(i), yf(j), xf(l)))()))
    slab = []
    for xs, xf in xpos:
        for ys, yf in ypos:
            slab.append(pl.BlockSpec(
                (m, ys, xs),
                (lambda yf=yf, xf=xf:
                 lambda i, j, l: (0, yf(j), xf(l)))()))
    return core, slab


def _yzslab_xwin_specs(Lz, Y, X, bz, by, bx, m, periodic):
    """Per-field specs for the wide-X 2-axis kernel: the 25-view group of
    ``_yzslab_specs`` instantiated at each of the three x-positions
    (pre/core/post, x-tails one lane tile, clamped/wrapped at the
    always-global x walls) — 75 views per field, x-position-major so the
    kernel assembles each sub-window with the SAME 2-axis select logic
    and concatenates along x."""
    g = 2 * m
    gx = _XWIN_GX
    zp, zn = _tail_index_fns(Lz, bz, g, wrap=False)
    yp, yn = _tail_index_fns(Y, by, g, wrap=False)
    xp, xn = _tail_index_fns(X, bx, gx, wrap=periodic)
    zpos = [(g, zp), (bz, lambda i: i), (g, zn)]
    ypos = [(g, yp), (by, lambda j: j), (g, yn)]
    xpos = [(gx, xp), (bx, lambda l: l), (gx, xn)]
    specs = []
    for xs, xf in xpos:
        core = []
        for zs, zf in zpos:
            for ys, yf in ypos:
                core.append(pl.BlockSpec(
                    (zs, ys, xs),
                    (lambda zf=zf, yf=yf, xf=xf:
                     lambda i, j, l: (zf(i), yf(j), xf(l)))()))
        zslab = [pl.BlockSpec(
            (m, ys, xs),
            (lambda yf=yf, xf=xf:
             lambda i, j, l: (0, yf(j), xf(l)))())
            for ys, yf in ypos]
        yslab = [pl.BlockSpec(
            (zs, g, xs),
            (lambda zf=zf, xf=xf:
             lambda i, j, l: (zf(i), 0, xf(l)))())
            for zs, zf in zpos]
        corner = [pl.BlockSpec(
            (m, g, xs),
            (lambda xf=xf: lambda i, j, l: (0, 0, xf(l)))())
            for _ in range(4)]
        specs += core + zslab + zslab + yslab + yslab + corner
    return specs


def _fused_yzslab_xwin_kernel(micro, nfields, k, margin, halo, bz, by, bx,
                              gshape, periodic, parity, nz_tiles, ny_tiles,
                              interpret, *refs):
    """Wide-X variant of ``_fused_yzslab_kernel``: the lane axis is
    windowed at ``_XWIN_GX``-lane granularity for grids whose whole-row
    windows exceed VMEM (two-field wave3d at X=4096 on an 8x8x1 mesh —
    the config-5 2-axis gap).  Each of the three x-positions is a full
    ``_assemble_yz_window`` (both-axis slab/corner selects), concatenated
    in x; lane-roll wrap garbage lands in the GX-lane x shell, which the
    output inset excludes (GX >= m, gated)."""
    wm = 2 * margin
    gx = _XWIN_GX
    origins, refs = refs[0], refs[1:]
    per = 75
    iz, jy = pl.program_id(0), pl.program_id(1)
    fields = []
    for f in range(nfields):
        base = per * f
        subs = []
        for t in range(3):
            b = refs[base + 25 * t:base + 25 * t + 25]
            subs.append(_assemble_yz_window(
                [r[...] for r in b], iz, jy, nz_tiles, ny_tiles))
        fields.append(jnp.concatenate(subs, axis=2))
    fields = tuple(fields)
    like = fields[0]
    outs = refs[per * nfields:]
    frame, extra = _window_frame(
        like.shape, origins[0] + iz * bz - wm, origins[1] + jy * by - wm,
        gshape, halo, periodic, parity, x0=pl.program_id(2) * bx - gx)
    fields = _run_micros(micro, fields, frame, extra, k)
    for o, f in zip(outs, fields):
        o[...] = f[wm:bz + wm, wm:by + wm, gx:bx + gx]


def build_yzslab_xwin_call(
    stencil: Stencil,
    local_shape: Tuple[int, int, int],
    global_shape: Tuple[int, int, int],
    k: int,
    tiles: Optional[Tuple[int, int, int]] = None,
    interpret: Optional[bool] = None,
    periodic: bool = False,
):
    """Wide-X sharded pad-free fused call for (z, y)-decomposed meshes —
    the fallback when ``build_yzslab_padfree_call``'s whole-row windows
    exceed VMEM (wide X x multi-field), symmetric to the z-only
    ``build_zslab_xwin_call``.  The call takes origins (int32 (2,)), then
    per field the 75 views of ``_yzslab_xwin_specs`` (pass the block 27x,
    each z-slab 9x, each 2m-duplicated y-slab 9x, each 2m-duplicated
    corner 3x — x-position-major 25-groups), and returns ``nfields``
    local-shape arrays advanced k steps.  Returns
    ``(call, margin, nfields)`` or None."""
    if not fused_supported(stencil):
        return None
    if interpret is None:
        interpret = _interpret_default()
    micro_factory, halo, nfields = _MICRO[stencil.name]
    margin = k * _halo_per_micro(stencil)
    if _XWIN_GX < margin:
        return None  # x shell must absorb the full validity margin
    Lz, Y, X = (int(s) for s in local_shape)
    gz, gy, gxx = (int(s) for s in global_shape)
    if stencil.parity_sensitive and periodic and (gxx % 2 or gy % 2
                                                  or gz % 2):
        return None
    itemsize = jnp.dtype(stencil.dtype).itemsize
    if tiles is None:
        tiles = _pick_xwin_tiles(Lz, Y, X, margin, itemsize, nfields)
    if tiles is None:
        return None
    bz, by, bx = tiles
    if bx >= X:
        return None  # whole-row windows: use the plain 2-axis kernel
    if not _tiles_valid(Lz, Y, bz, by, margin, itemsize) \
            or X % bx or bx % _XWIN_GX:
        return None
    micro = micro_factory(stencil, interpret)
    grid = (Lz // bz, Y // by, X // bx)
    per_field = _yzslab_xwin_specs(Lz, Y, X, bz, by, bx, margin, periodic)
    out_spec = pl.BlockSpec((bz, by, bx), lambda i, j, l: (i, j, l))
    call = pl.pallas_call(
        functools.partial(
            _fused_yzslab_xwin_kernel, micro, nfields, k, margin, halo,
            bz, by, bx, (gz, gy, gxx), periodic,
            stencil.parity_sensitive, Lz // bz, Y // by, interpret),
        name="fused_yzslab_xwin",
        grid=grid,
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM)]
        + per_field * nfields,
        out_specs=[out_spec] * nfields,
        out_shape=[jax.ShapeDtypeStruct((Lz, Y, X), stencil.dtype)
                   for _ in range(nfields)],
        interpret=interpret,
        compiler_params=None if interpret else compiler_params(
            vmem_limit_bytes=_VMEM_LIMIT_BYTES,
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary")),
    )
    return call, margin, nfields


def build_zslab_padfree_call(
    stencil: Stencil,
    local_shape: Tuple[int, int, int],
    global_shape: Tuple[int, int, int],
    k: int,
    tiles: Optional[Tuple[int, int]] = None,
    interpret: Optional[bool] = None,
    periodic: bool = False,
):
    """Sharded pad-free fused call (z-only decomposition).

    The call takes: origins (int32 (2,)), then per field 9 views of the
    raw LOCAL block + 3 views of the lower slab + 3 of the upper (pass
    the block 9x and each slab 3x), and returns ``nfields`` local-shape
    arrays advanced k steps.  Returns ``(call, margin, nfields)`` or None.

    Reference lineage: the reference stored the FULL grid replicated on
    every rank (kernel.cu:184-191) and exchanged one element per MPI
    message (kernel.cu:228-230); here per-device storage is the shard
    plus two width-m slabs, exchanged as whole ppermute transfers once
    per k steps — the two memory/traffic limits inverted.
    """
    if not fused_supported(stencil):
        return None
    if interpret is None:
        interpret = _interpret_default()
    micro_factory, halo, nfields = _MICRO[stencil.name]
    margin = k * _halo_per_micro(stencil)
    Lz, Y, X = (int(s) for s in local_shape)
    gz, gy, gx = (int(s) for s in global_shape)
    if stencil.parity_sensitive and periodic and (gx % 2 or gy % 2
                                                  or gz % 2):
        return None
    itemsize = jnp.dtype(stencil.dtype).itemsize
    if tiles is None:
        tiles = _pick_tiles(Lz, Y, X, margin, itemsize, nfields,
                            wm=2 * margin)
    if tiles is None:
        return None
    bz, by = tiles
    if not _tiles_valid(Lz, Y, bz, by, margin, itemsize):
        return None
    micro = micro_factory(stencil, interpret)
    grid = (Lz // bz, Y // by)
    core, slab = _zslab_specs(Lz, Y, X, bz, by, margin, periodic)
    per_field = core + slab + slab  # zlo and zhi share the y-view shapes
    out_spec = pl.BlockSpec((bz, by, X), lambda i, j: (i, j, 0))
    call = pl.pallas_call(
        functools.partial(
            _fused_zslab_kernel, micro, nfields, k, margin, halo, bz, by,
            (gz, gy, gx), periodic, stencil.parity_sensitive, Lz // bz,
            interpret),
        name="fused_zslab_padfree",
        grid=grid,
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM)]
        + per_field * nfields,
        out_specs=[out_spec] * nfields,
        out_shape=[jax.ShapeDtypeStruct((Lz, Y, X), stencil.dtype)
                   for _ in range(nfields)],
        interpret=interpret,
        compiler_params=None if interpret else compiler_params(
            vmem_limit_bytes=_VMEM_LIMIT_BYTES,
            dimension_semantics=("arbitrary", "arbitrary")),
    )
    return call, margin, nfields


def _lane_round(n: int) -> int:
    return -(-n // 128) * 128


def _sublane(itemsize: int) -> int:
    """TPU second-minor tile size: (8,128) f32, (16,128) bf16, (32,128) i8."""
    return 8 * max(1, 4 // itemsize)


def _pick_tiles(Z: int, Y: int, X: int, margin: int, itemsize: int,
                nfields: int, wm: Optional[int] = None):
    """Choose (bz, by) dividing (Z, Y), multiples of 2*margin, fitting VMEM.

    ``wm`` is the per-side WINDOW margin the kernel actually assembles
    (``margin`` for the padded 4-block kernel, ``2*margin`` for the
    pad-free 9-block kernel); the VMEM budget is computed from it.
    """
    if wm is None:
        wm = margin
    if (2 * margin) % _sublane(itemsize):
        # Tail blocks are (2m, by, X) / (bz, 2m, X) at offsets that are
        # multiples of 2m: both their size and their origin must be
        # sublane-tile-aligned FOR THE DTYPE.  f32 needs 2m % 8; bf16 needs
        # 2m % 16 (so k=8 for halo-1 stencils, not k=4 — the round-3 bf16
        # 512^3 "hang"/HTTP-500 was a misaligned-bf16-window Mosaic compile,
        # 2026-07-29..31 campaign label heat3d_512_bf16_fused4).
        return None
    # Sub-f32 dtypes: budget as if f32, capping tiles at the f32 picks —
    # the proven envelope.  Revisit the halved-bytes headroom with a tile
    # bisect once a bf16 fused config has a measured win (docs/STATE.md).
    itemsize = max(itemsize, 4)
    best = None
    for bz in (64, 32, 16, 8):
        for by in (64, 32, 16, 8):
            if Z % bz or Y % by or bz % (2 * margin) or by % (2 * margin):
                continue
            window = ((bz + 2 * wm) * (by + 2 * wm)
                      * _lane_round(X) * itemsize)
            core = bz * by * _lane_round(X) * itemsize
            # ~7 live window copies per field (pipeline buffers + the
            # micro-step temporaries) + the output pipeline buffers
            if (7 * window + 2 * core) * nfields > _VMEM_LIMIT:
                continue
            # prefer max core/window ratio (least redundancy), then max core
            score = (core / window, core)
            if best is None or score > best[0]:
                best = (score, (bz, by))
    return best[1] if best else None


def fused_supported(stencil: Stencil) -> bool:
    return stencil.name in _MICRO


# The padded 4-block kernel holds ~3 full grids live per field (input, z/y-
# padded transient, output) while the pad copy runs; past this many bytes
# the 9-block pad-free kernel is selected instead (v5e HBM is 16 GiB; the
# padded path's transient was the 1024^3 f32 RESOURCE_EXHAUSTED,
# 2026-07-29..31 chip campaign).  Below it the padded kernel stays the
# default — it is the measured 107 Gcells/s configuration — until the
# campaign measures pad-free at 256^3/512^3 (labels *_padfree4 in
# benchmarks/measure.py).
_PADFREE_ABOVE_BYTES = 6 * 1024**3


def prefer_padfree(stencil: Stencil, global_shape: Sequence[int],
                   batch: int = 1) -> bool:
    """Whether ``make_fused_step`` callers should pick the pad-free kernel.

    ``batch``: ensemble factor — a vmapped step_k batches the pad
    transient too, so the live-bytes estimate scales with it.
    """
    if stencil.name not in _MICRO:
        return False
    nfields = _MICRO[stencil.name][2]
    cells = max(1, int(batch))
    for s in global_shape:
        cells *= int(s)
    live = 3 * cells * jnp.dtype(stencil.dtype).itemsize * nfields
    return live > _PADFREE_ABOVE_BYTES


def build_fused_call(
    stencil: Stencil,
    core_shape: Tuple[int, int, int],
    k: int,
    tiles: Optional[Tuple[int, int]] = None,
    interpret: Optional[bool] = None,
    sharded_global: Optional[Tuple[int, int, int]] = None,
    periodic: bool = False,
    padfree: bool = False,
):
    """Construct the fused pallas_call over a (core) block of ``core_shape``.

    Returns ``(call, margin, nfields)`` or None if untileable.  The call
    takes, per field, 4 views of the z/y-padded block (pass the same padded
    array 4 times) and returns ``nfields`` arrays of ``core_shape``.

    ``sharded_global``: the GLOBAL grid shape, for callers whose block
    sits at a traced global offset (shard_map).  The call then takes an
    int32 ``(2,)`` origins array FIRST (this shard's global z/y origin of
    the unpadded block): the frame mask is derived in-kernel from the
    origin scalars (read from SMEM) + program ids, so NO mask array is
    streamed — round 3 streamed a whole padded mask per step, a full
    extra input's worth of HBM traffic and memory.

    ``padfree=True`` builds the 9-block raw-grid kernel instead (see
    ``_fused_raw_kernel``): the call takes 9 views of the UNPADDED field
    (pass it 9 times) and no pad transient is needed.  Incompatible with
    ``sharded_global`` (the sharded caller pads its local block: interior
    shard faces need genuine neighbor values, which the clamp trick
    cannot supply).
    """
    sharded = sharded_global is not None
    if not fused_supported(stencil):
        return None
    if padfree and sharded:
        return None
    if interpret is None:
        interpret = _interpret_default()
    micro_factory, halo, nfields = _MICRO[stencil.name]
    # margin per micro-step = halo per PHASE (red-black consumes 2*halo)
    margin = k * _halo_per_micro(stencil)
    Z, Y, X = (int(s) for s in core_shape)
    if stencil.parity_sensitive and periodic and (X % 2 or Y % 2 or Z % 2):
        # wrap over an odd extent makes adjacent cells share a color —
        # the tiling gates force Z/Y even but X (lane axis) is free, so
        # refuse here exactly as make_sharded_step does
        return None
    itemsize = jnp.dtype(stencil.dtype).itemsize
    if tiles is None:
        tiles = _pick_tiles(Z, Y, X, margin, itemsize, nfields,
                            wm=2 * margin if padfree else None)
    if tiles is None:
        return None
    bz, by = tiles
    if not _tiles_valid(Z, Y, bz, by, margin, itemsize):
        return None
    micro = micro_factory(stencil, interpret)

    grid = (Z // bz, Y // by)
    m = margin
    extra_specs = []
    if padfree:
        per_field_specs = _raw_window_specs(Z, Y, X, bz, by, m,
                                            wrap_z=periodic,
                                            wrap_y=periodic)
        kernel = functools.partial(
            _fused_raw_kernel, micro, nfields, k, m, halo, bz, by,
            (Z, Y, X), periodic, stencil.parity_sensitive, interpret)
    else:
        # Four aligned views of the z/y-padded input reassemble each
        # program's overlapping (bz+2m, by+2m, X) window; alignment needs
        # bz, by % 2m == 0.
        per_field_specs = [
            pl.BlockSpec((bz, by, X), lambda i, j: (i, j, 0)),
            pl.BlockSpec(
                (bz, 2 * m, X), lambda i, j: (i, (j + 1) * by // (2 * m), 0)),
            pl.BlockSpec(
                (2 * m, by, X), lambda i, j: ((i + 1) * bz // (2 * m), j, 0)),
            pl.BlockSpec(
                (2 * m, 2 * m, X),
                lambda i, j: ((i + 1) * bz // (2 * m),
                              (j + 1) * by // (2 * m), 0)),
        ]
        kernel = functools.partial(
            _fused_kernel, micro, nfields, k, m, halo, bz, by,
            sharded_global if sharded else (Z, Y, X), periodic,
            stencil.parity_sensitive, sharded, interpret)
        if sharded:
            # whole (2,) origins array into scalar memory, same for every
            # grid step
            extra_specs = [pl.BlockSpec(memory_space=pltpu.SMEM)]
    out_spec = pl.BlockSpec((bz, by, X), lambda i, j: (i, j, 0))

    call = pl.pallas_call(
        kernel,
        name="fused_padfree" if padfree else "fused_padded",
        grid=grid,
        in_specs=extra_specs + per_field_specs * nfields,
        out_specs=[out_spec] * nfields,
        out_shape=[jax.ShapeDtypeStruct((Z, Y, X), stencil.dtype)
                   for _ in range(nfields)],
        interpret=interpret,
        compiler_params=None if interpret else compiler_params(
            vmem_limit_bytes=_VMEM_LIMIT_BYTES,
            dimension_semantics=("arbitrary", "arbitrary")),
    )
    return call, margin, nfields


def build_overlap_shell_calls(
    stencil: Stencil,
    local_shape: Tuple[int, int, int],
    global_shape: Tuple[int, int, int],
    k: int,
    axes: Sequence[int],
    interpret: Optional[bool] = None,
    periodic: bool = False,
):
    """Slab-shaped fused calls for the communication-overlap boundary
    shells (``make_sharded_fused_step(overlap=True)``).

    For each sharded grid axis ``d`` in ``axes`` (subset of {0, 1} — the
    lane axis is never sharded), builds the SAME fused kernel over a
    reduced core whose axis-``d`` extent is ``2m`` (m = k*halo*phases):
    the width-``2m`` boundary shell at one face of the local block.  The
    shell call consumes the exchanged neighbor slab plus a ``3m``-deep
    local strip (padded input extent ``4m`` along ``d``), and the caller
    offsets the SMEM origin scalars by the shell's position so the
    in-kernel global frame mask (and red-black parity) stays exact —
    ``build_fused_call`` already derives both from origins + program ids,
    so no new kernel code exists here, only a reduced-extent instance.

    Shells are ``2m`` deep (temporal validity needs only ``m``) because
    the window tail BlockSpecs require block-aligned ``2m``-granularity
    origins — ``bz = 2m`` is the smallest tileable slab — and the extra
    ``m`` rows land on also-valid values, so the splice stays exact.

    Returns ``{axis: call}`` or None when the geometry cannot host the
    split (local extent < 3m on a sharded axis, or a shell untileable):
    callers fall back to the non-overlapped step.
    """
    margin = k * _halo_per_micro(stencil)
    shells = {}
    for d in axes:
        if d not in (0, 1):
            return None
        if int(local_shape[d]) < 3 * margin:
            return None  # the 3m local strip would wrap into the far slab
        core = list(int(s) for s in local_shape)
        core[d] = 2 * margin
        built = build_fused_call(
            stencil, tuple(core), k, interpret=interpret,
            sharded_global=None if periodic else tuple(global_shape),
            periodic=periodic)
        if built is None:
            return None
        call, m_shell, _ = built
        assert m_shell == margin
        shells[d] = call
    return shells


def make_fused_step(
    stencil: Stencil,
    global_shape: Sequence[int],
    k: int,
    tiles: Optional[Tuple[int, int]] = None,
    interpret: Optional[bool] = None,
    periodic: bool = False,
    padfree: bool = False,
):
    """Build ``fields -> fields`` advancing ``k`` steps in one kernel pass.

    Semantically identical to ``k`` applications of ``driver.make_step`` for
    the same stencil/shape (guard-frame semantics included) — asserted by
    tests/test_fused.py.  ``periodic=True`` wrap-pads z/y instead of
    zero-padding and drops the frame pin (the lane rolls wrap at the full
    domain width, which IS the periodic x boundary).  Returns None when
    the shape/k cannot be tiled (callers fall back to the per-step path).
    ``2 * k * halo`` must be a multiple of the dtype's sublane tile (8 for
    f32, 16 for bf16 — see ``_sublane``), i.e. f32 halo-1 needs k in
    {4, 8, ...}, bf16 halo-1 needs k in {8, 16, ...}.

    ``padfree=True`` selects the 9-block raw-grid kernel: no z/y pad
    transient is materialized (required for 1024^3-class grids, where the
    padded path's extra full-grid copy exhausts HBM), at the cost of one
    extra margin of overlap redundancy per side.
    """
    built = build_fused_call(
        stencil, tuple(int(s) for s in global_shape), k, tiles, interpret,
        periodic=periodic, padfree=padfree)
    if built is None:
        return None
    call, m, _ = built

    if padfree:
        def step_k(fields: Fields) -> Fields:
            args = [f for f in fields for _ in range(9)]
            return tuple(call(*args))

        # Its windows overlap its input, so it cannot write in place: the
        # runner ping-pongs two passes between the donated buffer and one
        # temporary (driver.make_runner).  The padded pass below reads its
        # pad transient, so its output lands in the donated buffer already.
        step_k._carry_period = 2
        step_k._out_of_place = True
        return step_k

    pad_mode = "wrap" if periodic else "constant"

    def step_k(fields: Fields) -> Fields:
        padded = [jnp.pad(f, ((m, m), (m, m), (0, 0)), mode=pad_mode)
                  for f in fields]
        args = [p for p in padded for _ in range(4)]
        return tuple(call(*args))

    return step_k
