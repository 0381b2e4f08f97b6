"""Whole-step Pallas kernels on the raw (unpadded) grid.

The production jnp step is pad -> update -> frame re-pin
(driver.make_step).  XLA fuses that to ~2 HBM passes at 256^3, but the
padded (n+2h)^3 intermediates carry lane-misaligned extents (258 -> 384-lane
rounding) and at 512^3+ the fusion breaks down entirely (measured 17.6
Gcells/s vs 82.7 at 256^3 in round 2 — the 4.7x large-grid cliff).  These
kernels replace the ENTIRE step on the raw n^3 state, in one pass:

  * The state is its own halo: frame cells are exactly the guard cells the
    reference's ``create_universe`` pins (kernel.cu:137-138,
    MDF_kernel.cu:92-93), so no ``jnp.pad`` copy ever materializes and the
    grid keeps its natural (8,128)-tile-aligned extents.
  * The grid is cut into z-chunks of ``bz`` planes.  Each program reads its
    own chunk plus ``halo`` neighbor planes on each side via two extra
    clamped BlockSpecs (at the walls they clamp to the wall chunk — the
    values feeding those taps are garbage, but they only reach z-frame
    outputs, which the in-kernel mask re-pins).  HBM traffic:
    ``1 + 2*halo/bz`` read passes + 1 write pass, vs the jnp path's pad
    copy + update + mask chain.
  * y/x neighbor taps are **rolls** (``pltpu.roll``) of the VMEM slab —
    never shrinking slices, whose odd sublane/lane offsets force a Mosaic
    relayout per tap (same lesson as ops/pallas/fused.py).  Wrap-around
    values land only in y/x frame cells, which the mask re-pins.
  * The frame mask is computed in-kernel from global coordinates
    (program_id for z, iota for y/x) — the VMEM equivalent of
    ``driver.frame_mask``.

Semantics are bit-identical to ``driver.make_step(stencil, shape)`` for the
supported stencils (asserted in tests/test_rawstep.py), replacing both the
CUDA kernels' role (kernel.cu:70-113) and the driver's pad/mask machinery in
a single launch.
"""

from __future__ import annotations

import functools
from typing import Callable, Optional, Sequence

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..stencil import Fields, Stencil
from .kernels import (
    _COMPILER_PARAMS,
    _VMEM_LIMIT_BYTES,
    _W27_CENTER,
    _W27_CORNER,
    _W27_EDGE,
    _W27_FACE,
    _interpret_default,
    _roll,
)


def _roll2(x, dy, dx, interpret):
    out = x
    if dy:
        out = _roll(out, -dy, 1, interpret)
    if dx:
        out = _roll(out, -dx, 2, interpret)
    return out


# ---------------------------------------------------------------------------
# slab tap rules: (bz + 2*halo, Y, X) VMEM slab -> new middle bz planes
# z taps are plane slices (axis 0 is tile-row indexing: free); y/x taps are
# rolls.  Each returns the un-masked update of the slab's middle bz planes.
# ---------------------------------------------------------------------------


def _slab_lap7(s, bz, interpret):
    """(interior planes, 7-point Laplacian) of a (bz+2, Y, X) slab."""
    u = s[1:bz + 1]
    return u, (
        s[0:bz] + s[2:bz + 2]
        + _roll(u, 1, 1, interpret) + _roll(u, -1, 1, interpret)
        + _roll(u, 1, 2, interpret) + _roll(u, -1, 2, interpret)
        - 6.0 * u
    )


def _taps7(alpha, interpret, s, bz):
    u, lap = _slab_lap7(s, bz, interpret)
    return u + alpha * lap


def _taps27(alpha, interpret, s, bz):
    # Per-z-level partial sums instead of 26 independent taps: each level's
    # 3x3 in-plane kernel is [center', face', edge'] over {self, y/x lines,
    # diagonals}, and the diagonal sum reuses the y-line sum (roll of a
    # roll).  12 rolls total and ~5 live bz-plane buffers — the naive tap
    # loop kept 20+ alive, which blew the scoped-VMEM limit at 512^3.
    u = s[1:bz + 1]
    acc = None
    for dz in (-1, 0, 1):
        base = s[1 + dz:1 + dz + bz]
        yl = _roll(base, 1, 1, interpret) + _roll(base, -1, 1, interpret)
        xl = _roll(base, 1, 2, interpret) + _roll(base, -1, 2, interpret)
        diag = _roll(yl, 1, 2, interpret) + _roll(yl, -1, 2, interpret)
        if dz == 0:
            part = (_W27_CENTER * base + _W27_FACE * (yl + xl)
                    + _W27_EDGE * diag)
        else:
            part = (_W27_FACE * base + _W27_EDGE * (yl + xl)
                    + _W27_CORNER * diag)
        acc = part if acc is None else acc + part
    return u + alpha * acc


def _taps13(alpha, interpret, s, bz):
    # 4th-order 13-point Laplacian, halo 2: slab is (bz+4, Y, X).
    w = {1: 16.0 / 12.0, 2: -1.0 / 12.0}
    u = s[2:bz + 2]
    acc = (-30.0 / 12.0 * 3.0) * u
    for dist in (1, 2):
        for o in (-dist, dist):
            acc = acc + w[dist] * (
                s[2 + o:2 + o + bz]
                + _roll(u, -o, 1, interpret)
                + _roll(u, -o, 2, interpret)
            )
    return u + alpha * acc


# Single-field stencils: name -> (taps factory, halo, live-factor).  The
# factory maps (stencil, interpret) to a slab-taps fn (s, bz) -> un-pinned
# update of the middle bz planes; the shared builder supplies specs, frame
# pinning, and the pallas_call.  live-factor: scoped-VMEM use is
# ~live_factor * bz * plane_bytes (pipeline buffers + slab + live tap
# intermediates), fit to the measured compile envelope on the real v5e
# (round 3): 7-pt compiles at bz=16 for 512^3 planes, 13-pt at bz=8, etc.
# Throughput is flat across compiling bz (the Mosaic DMA pipeline, not
# compute, is the bound), so the pick only has to stay inside the envelope.
_TAPS = {
    "heat3d": (lambda st, i: functools.partial(
        _taps7, float(st.params["alpha"]), i), 1, 5),
    "heat3d27": (lambda st, i: functools.partial(
        _taps27, float(st.params["alpha"]), i), 1, 8),
    "heat3d4th": (lambda st, i: functools.partial(
        _taps13, float(st.params["alpha"]), i), 2, 6),
    "advect3d": (lambda st, i: functools.partial(
        _taps_advect, tuple(float(c) for c in st.params["courant"]), i),
        1, 6),
}


def _frame_mask_chunk(bz, halo, shape, like):
    """frame-cell mask for this program's (bz, Y, X) output chunk."""
    Z, Y, X = shape
    z0 = pl.program_id(0) * bz
    zi = jax.lax.broadcasted_iota(jnp.int32, like.shape, 0) + z0
    yi = jax.lax.broadcasted_iota(jnp.int32, like.shape, 1)
    xi = jax.lax.broadcasted_iota(jnp.int32, like.shape, 2)
    return (
        (zi < halo) | (zi >= Z - halo)
        | (yi < halo) | (yi >= Y - halo)
        | (xi < halo) | (xi >= X - halo)
    )


def _heat_kernel(taps, bz, halo, shape, prev_p, cur, next_p, out):
    s = jnp.concatenate([prev_p[...], cur[...], next_p[...]], axis=0)
    u = s[halo:halo + bz]
    new = taps(s, bz)
    frame = _frame_mask_chunk(bz, halo, shape, u)
    out[...] = jnp.where(frame, u, new)


def _wave_kernel(c2dt2, bz, shape, interpret, prev_p, cur, next_p, uprev,
                 out):
    # ``out`` is ``uprev``'s buffer (input_output_aliases): each program
    # reads its uprev block before its out block is written back, and no
    # other program reads that block
    s = jnp.concatenate([prev_p[...], cur[...], next_p[...]], axis=0)
    u, lap = _slab_lap7(s, bz, interpret)
    new = 2.0 * u - uprev[...] + c2dt2 * lap
    frame = _frame_mask_chunk(bz, 1, shape, u)
    # frame keeps old u: by induction it still holds the Dirichlet value
    out[...] = jnp.where(frame, u, new)


def _taps_advect(courant, interpret, s, bz):
    # First-order upwind: each axis reads only its upstream neighbor
    # (ops/advection.py) — z taps from the slab planes, y/x taps as rolls.
    u = s[1:bz + 1]
    acc = u
    cz, cy, cx = courant
    if cz > 0:
        acc = acc - cz * (u - s[0:bz])
    elif cz < 0:
        acc = acc - cz * (s[2:bz + 2] - u)
    for c, axis in ((cy, 1), (cx, 2)):
        if c > 0:
            acc = acc - c * (u - _roll(u, 1, axis, interpret))
        elif c < 0:
            acc = acc - c * (_roll(u, -1, axis, interpret) - u)
    return acc


def _grayscott_kernel(du, dv, f, kappa, bz, shape, interpret,
                      uprev_p, ucur, unext_p, vprev_p, vcur, vnext_p,
                      out_u, out_v):
    # Two coupled diffusing fields (ops/reaction.py): both carry footprints,
    # so both arrive as halo'd slabs and both outputs are frame-pinned.
    su = jnp.concatenate([uprev_p[...], ucur[...], unext_p[...]], axis=0)
    sv = jnp.concatenate([vprev_p[...], vcur[...], vnext_p[...]], axis=0)
    u, lap_u = _slab_lap7(su, bz, interpret)
    v, lap_v = _slab_lap7(sv, bz, interpret)
    uvv = u * v * v
    new_u = u + du * lap_u - uvv + f * (1.0 - u)
    new_v = v + dv * lap_v + uvv - (f + kappa) * v
    frame = _frame_mask_chunk(bz, 1, shape, u)
    out_u[...] = jnp.where(frame, u, new_u)
    out_v[...] = jnp.where(frame, v, new_v)


def _pick_bz(Z: int, plane_bytes: int, halo: int, live_factor: int) -> int:
    """Largest z-chunk whose estimated scoped-VMEM use fits the limit."""
    budget = int(_VMEM_LIMIT_BYTES * 0.8)  # the limit _COMPILER_PARAMS sets
    for bz in (64, 32, 16, 8, 4, 2):
        if Z % bz or bz % halo:
            continue
        if live_factor * bz * plane_bytes <= budget:
            return bz
    return 0


def _zspecs(Z, Y, X, bz, halo):
    """cur chunk + clamped halo-plane specs (block shape (halo, Y, X)).

    At the walls the halo spec clamps to the wall chunk itself; the garbage
    taps feed only z-frame outputs, which the in-kernel mask re-pins.
    """
    nb = Z // halo  # halo-plane blocks in the array (Z % bz == 0, bz % halo)
    r = bz // halo
    cur = pl.BlockSpec((bz, Y, X), lambda i: (i, 0, 0))
    prev_p = pl.BlockSpec(
        (halo, Y, X), lambda i: (jnp.maximum(i * r - 1, 0), 0, 0))
    next_p = pl.BlockSpec(
        (halo, Y, X), lambda i: (jnp.minimum((i + 1) * r, nb - 1), 0, 0))
    return prev_p, cur, next_p


def raw_step_supported(stencil: Stencil) -> bool:
    return stencil.name in _TAPS or stencil.name in (
        "wave3d", "grayscott3d")


def raw_step_fresh_fields(stencil: Stencil) -> int:
    """Fields a raw step writes to new buffers: none for the in-place
    leapfrog pass, every field for the others."""
    return 0 if stencil.name == "wave3d" else stencil.num_fields


def make_raw_step(
    stencil: Stencil,
    global_shape: Sequence[int],
    interpret: Optional[bool] = None,
) -> Optional[Callable[[Fields], Fields]]:
    """Build a whole-step ``fields -> fields`` function (guard-frame mode).

    Drop-in replacement for ``driver.make_step(stencil, global_shape)`` —
    same signature, bit-identical results.  Returns None when unsupported
    (periodic runs, 2D stencils, or shapes the z-chunking cannot tile);
    callers fall back to the jnp step.
    """
    if interpret is None:
        interpret = _interpret_default()
    if len(global_shape) != 3:
        return None
    Z, Y, X = (int(s) for s in global_shape)
    itemsize = jnp.dtype(stencil.dtype).itemsize
    plane = Y * X * itemsize

    if stencil.name == "wave3d":
        # The in-place leapfrog pass: u_new is written over u_prev's buffer
        # and the new u_prev is the old u buffer, renamed (carry_map), so a
        # step holds the two-field state and nothing else.  The carry's two
        # buffers swap slots every step; ``_carry_period`` tells
        # driver.make_runner that two steps put them back in place.
        halo = 1
        bz = _pick_bz(Z, plane, halo, live_factor=8)
        if bz == 0 or Z <= 2 * halo:
            return None
        prev_p, cur, next_p = _zspecs(Z, Y, X, bz, halo)
        sprev = pl.BlockSpec((bz, Y, X), lambda i: (i, 0, 0))
        out = pl.BlockSpec((bz, Y, X), lambda i: (i, 0, 0))
        c2dt2 = float(stencil.params["c2dt2"])
        call = pl.pallas_call(
            functools.partial(
                _wave_kernel, c2dt2, bz, (Z, Y, X), interpret),
            name="rawstep_wave",
            grid=(Z // bz,),
            in_specs=[prev_p, cur, next_p, sprev],
            out_specs=out,
            out_shape=jax.ShapeDtypeStruct((Z, Y, X), stencil.dtype),
            input_output_aliases={3: 0},
            interpret=interpret,
            compiler_params=None if interpret else _COMPILER_PARAMS,
        )

        def step(fields: Fields) -> Fields:
            u, uprev = fields
            new_u = call(u, u, u, uprev)
            return (new_u, u)  # carry_map semantics: new u_prev is old u

        step._carry_period = 2
        step._out_of_place = False  # the runner must donate u_prev
        return step

    if stencil.name == "grayscott3d":
        halo = 1
        # two full slab sets + two outputs live at once
        bz = _pick_bz(Z, plane, halo, live_factor=14)
        if bz == 0 or Z <= 2 * halo:
            return None
        prev_p, cur, next_p = _zspecs(Z, Y, X, bz, halo)
        out = pl.BlockSpec((bz, Y, X), lambda i: (i, 0, 0))
        p = stencil.params
        call = pl.pallas_call(
            functools.partial(
                _grayscott_kernel, float(p["du"]), float(p["dv"]),
                float(p["f"]), float(p["kappa"]), bz, (Z, Y, X), interpret),
            name="rawstep_grayscott",
            grid=(Z // bz,),
            in_specs=[prev_p, cur, next_p, prev_p, cur, next_p],
            out_specs=[out, out],
            out_shape=[jax.ShapeDtypeStruct((Z, Y, X), stencil.dtype)] * 2,
            interpret=interpret,
            compiler_params=None if interpret else _COMPILER_PARAMS,
        )

        def step(fields: Fields) -> Fields:
            u, v = fields
            return tuple(call(u, u, u, v, v, v))

        return step

    if stencil.name not in _TAPS:
        return None
    taps_factory, halo, live = _TAPS[stencil.name]
    if Z <= 2 * halo:
        return None
    bz = _pick_bz(Z, plane, halo, live_factor=live)
    if bz == 0:
        return None
    taps = taps_factory(stencil, interpret)
    prev_p, cur, next_p = _zspecs(Z, Y, X, bz, halo)
    out = pl.BlockSpec((bz, Y, X), lambda i: (i, 0, 0))
    call = pl.pallas_call(
        functools.partial(_heat_kernel, taps, bz, halo, (Z, Y, X)),
        name="rawstep_taps",
        grid=(Z // bz,),
        in_specs=[prev_p, cur, next_p],
        out_specs=out,
        out_shape=jax.ShapeDtypeStruct((Z, Y, X), stencil.dtype),
        interpret=interpret,
        compiler_params=None if interpret else _COMPILER_PARAMS,
    )

    def step(fields: Fields) -> Fields:
        (u,) = fields
        return (call(u, u, u),)

    return step


# ---------------------------------------------------------------------------
# the sharded heat3d pass: a z/y-sharded block, its exchanged faces as
# operands
# ---------------------------------------------------------------------------


def _sharded_heat_kernel(alpha, bz, nzb, gshape, interpret, origins,
                         prev_p, cur, next_p, zlo, zhi, ylo, yhi, out):
    # The chunks at the block's z faces take their outer plane from the
    # exchanged face, not from the clamped own-block plane
    i = jnp.full(zlo.shape, pl.program_id(0), jnp.int32)
    prev = jnp.where(i == 0, zlo[...], prev_p[...])
    nxt = jnp.where(i == nzb - 1, zhi[...], next_p[...])
    s = jnp.concatenate([prev, cur[...], nxt], axis=0)
    u = s[1:bz + 1]
    yi = jax.lax.broadcasted_iota(jnp.int32, u.shape, 1)
    # y taps: rolls, whose wrapped rows 0 and Y-1 take the exchanged faces
    y_minus = jnp.where(yi == 0, ylo[...], _roll(u, 1, 1, interpret))
    y_plus = jnp.where(yi == u.shape[1] - 1, yhi[...],
                       _roll(u, -1, 1, interpret))
    # _slab_lap7's sum, in its order: bit-identical to the jnp sharded step
    lap = (s[0:bz] + s[2:bz + 2] + y_minus + y_plus
           + _roll(u, 1, 2, interpret) + _roll(u, -1, 2, interpret)
           - 6.0 * u)
    new = u + alpha * lap
    Z, Y, X = gshape
    zi = jax.lax.broadcasted_iota(jnp.int32, u.shape, 0) \
        + origins[0] + pl.program_id(0) * bz
    yi = yi + origins[1]
    xi = jax.lax.broadcasted_iota(jnp.int32, u.shape, 2)
    frame = ((zi < 1) | (zi >= Z - 1) | (yi < 1) | (yi >= Y - 1)
             | (xi < 1) | (xi >= X - 1))
    out[...] = jnp.where(frame, u, new)


def build_sharded_raw_call(
    stencil: Stencil,
    local_shape: Sequence[int],
    global_shape: Sequence[int],
    interpret: Optional[bool] = None,
):
    """One guard-frame step of a z/y-sharded block as one Pallas pass.

    The sharded twin of ``make_raw_step``'s heat3d path: the unpadded
    local block is read in z-chunks, and the exchanged faces arrive as
    separate operands, never concatenated onto the block.  The call
    takes origins (int32 (2,): the block's global z and y offsets), the
    block three times (chunk and the two clamped halo-plane views), the
    z faces (1, Ly, X) before and after the block, and the y faces
    (Lz, 1, X) before and after it; it returns the advanced block.  The
    z faces have constant block indices, so each is fetched once per
    call.  The y taps stay rolls, and rows 0 and Ly-1 take the y faces
    in place of the wrapped values; the x axis is whole, and its wrapped
    taps land on frame cells, which the mask re-pins.  Bit-identical to
    ``parallel.stepper.make_sharded_step``'s update.

    Returns None for a stencil other than heat3d (the one single-field
    face-tap stencil with halo 1 this pass carries; heat3d27 reads edges
    and corners), a dtype other than float32 (Mosaic rotates 32-bit data
    only), a sharded x axis, or a block the z-chunking cannot tile.
    """
    if stencil.name != "heat3d" or len(global_shape) != 3 \
            or jnp.dtype(stencil.dtype) != jnp.float32:
        return None
    if interpret is None:
        interpret = _interpret_default()
    Lz, Ly, X = (int(s) for s in local_shape)
    gshape = tuple(int(g) for g in global_shape)
    if X != gshape[2]:
        return None
    _, halo, live = _TAPS[stencil.name]
    bz = _pick_bz(Lz, Ly * X * jnp.dtype(stencil.dtype).itemsize, halo,
                  live)
    if bz == 0:
        return None
    prev_p, cur, next_p = _zspecs(Lz, Ly, X, bz, halo)
    zface = pl.BlockSpec((1, Ly, X), lambda i: (0, 0, 0))
    yface = pl.BlockSpec((bz, 1, X), lambda i: (i, 0, 0))
    return pl.pallas_call(
        functools.partial(_sharded_heat_kernel,
                          float(stencil.params["alpha"]), bz, Lz // bz,
                          gshape, interpret),
        name="rawstep_sharded",
        grid=(Lz // bz,),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM), prev_p, cur,
                  next_p, zface, zface, yface, yface],
        out_specs=pl.BlockSpec((bz, Ly, X), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((Lz, Ly, X), stencil.dtype),
        interpret=interpret,
        compiler_params=None if interpret else _COMPILER_PARAMS,
    )
