"""Whole-grid temporal blocking for 2D stencils: k steps per HBM round-trip.

2D state is tiny by TPU standards (512² f32 = 1 MiB, 2048² int32 = 16.8 MiB
— v5e has 128 MiB VMEM), so unlike the 3D fused kernels (ops/pallas/fused.py,
which tile overlapping windows and pay a temporal-validity margin), the 2D
grid fits in VMEM *whole*: one program loads the state once, runs k
micro-steps as a ``fori_loop`` (constant code size — no unroll blow-up, the
suspected cause of the bf16 deep-unroll compile hang), re-pins the guard
frame every micro-step from an iota mask, and stores once.

No windows → no overlap redundancy, no alignment constraints on k, and the
result is BIT-EXACT with k applications of the plain step for every k ≥ 1
(the 3D kernels' few-ULP tap-order caveat does not apply here because the
micro-steps reuse the same roll-based tap order every pass — asserted
exactly in tests/test_fullgrid.py for int Life).

Neighbor taps are rolls (shared ``_roll``): wrap-around values land only in
the guard frame, which the per-micro-step mask re-pins — the same
guard-cell isolation argument as rawstep.py/fused.py, here with zero
approximation because the whole domain is present.

Capability lineage: this is the reference's per-cell kernel pair
(kernel.cu:70-113) taken to its TPU limit — where the reference re-uploaded
the full grid every generation (kernel.cu:208, SURVEY.md §3.1), this kernel
crosses HBM once per k generations.
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..stencil import Fields, Stencil

from .compat import compiler_params

from .kernels import _VMEM_LIMIT_BYTES, _interpret_default, _roll

# The heat/wave/advect/grayscott/sor micro-steps read ndim from the
# stencil — shared with the 3D windowed kernels (one definition, two
# kernel shapes).  ``_micro_sor``'s parity arg is supplied here by the
# kernel prelude (ops/sor._parity_mask, computed once per HBM pass).
from .fused import (
    _micro_advect,
    _micro_grayscott,
    _micro_heat,
    _micro_sor,
    _micro_wave,
)


def _micro_life(stencil, interpret):
    def micro(fields, frame):
        (cur,) = fields
        n = None
        for dy in (-1, 0, 1):
            for dx in (-1, 0, 1):
                if dy == dx == 0:
                    continue
                t = _roll(_roll(cur, dy, 0, interpret), dx, 1, interpret)
                n = t if n is None else n + t
        new = ((n == 3) | ((n == 2) & (cur == 1))).astype(cur.dtype)
        return (jnp.where(frame, cur, new),)

    return micro


# name -> (micro factory, halo, nfields)
_MICRO2D = {
    "life": (_micro_life, 1, 1),
    "heat2d": (_micro_heat, 1, 1),
    "mdf": (_micro_heat, 1, 1),
    "wave2d": (_micro_wave, 1, 2),
    "advect2d": (_micro_advect, 1, 1),
    "grayscott2d": (_micro_grayscott, 1, 2),
    "sor2d": (_micro_sor, 1, 1),
}

# Estimated live VMEM copies of the grid inside the micro-loop (state +
# roll temporaries + output staging), PER FIELD, per family: the micro
# bodies hold different working sets (grayscott carries uvv + two
# Laplacians across two fields; wave's u_prev is tap-free; sor keeps the
# relaxed copy + color mask).  Measured against the full raised scoped
# limit.  Life's factor is the v5e compiler's own count: an AOT compile
# of life 2048^2 k=16 used 153.91 MiB of scoped VMEM (9.2 grid copies;
# PR 21), so 2048^2 int32 Life is declined; heat2d 2048^2 k=16 compiles
# at factor 5.  A kernel the compiler still refuses raises its own
# error — nothing falls back.
_LIVE_FACTOR = {
    "life": 10,       # 8-tap int neighbor sum (compiler-measured 9.2)
    "heat2d": 5,      # 4-tap Laplacian accumulator
    "mdf": 5,
    "advect2d": 5,    # <=2 upwind taps, but same staging floor
    "wave2d": 4,      # u_prev is tap-free (pointwise leapfrog carry)
    "grayscott2d": 6,  # uvv + per-field Laplacian live across both fields
    "sor2d": 6,       # relaxed copy + parity mask resident per sweep
}


def _live_factor(name: str) -> int:
    return _LIVE_FACTOR.get(name, 6)  # unknown families: conservative


def fullgrid_supported(stencil: Stencil) -> bool:
    return stencil.name in _MICRO2D


def _halo_per_micro_2d(stencil: Stencil) -> int:
    """Validity margin per micro-step: halo cells PER PHASE (the 2D
    registry's counterpart of fused._halo_per_micro — same rule, keyed on
    _MICRO2D)."""
    micro_halo = _MICRO2D[stencil.name][1]
    return micro_halo * max(1, len(stencil.phases or ()))


def _build_call(stencil, block_shape, m, k, interpret, sharded_global=None,
                periodic=False):
    """Shared scaffolding for both whole-grid kernels (cf. fused.py's
    single builder with a ``sharded_global`` flag).

    ``block_shape`` is the in-VMEM block: the whole grid
    (``sharded_global=None``, ``m == 0``, frame derived from iota) or the
    halo-padded local block (``sharded_global=(H, W)`` — the GLOBAL
    extents; the shard's y-origin arrives as an SMEM (1,) int32 scalar
    input, first, and the frame is derived in-kernel: a BlockSpec
    index_map cannot see the traced axis_index but the kernel body can
    read SMEM, so no mask ARRAY is streamed — same technique as
    fused._fused_kernel).  Output is the ``m``-inset core.
    ``periodic``: no guard frame exists — unsharded, the neighbor rolls'
    wrap-around IS the periodic boundary; sharded, the exchanged slabs are
    real wrapped data — so the frame mask is identically False and no
    origin input is needed.  Returns ``(call, nfields)`` or None.
    """
    sharded = sharded_global is not None
    if not fullgrid_supported(stencil) or k < 1:
        return None
    if interpret is None:
        interpret = _interpret_default()
    Hp, W = (int(s) for s in block_shape)
    Ly = Hp - 2 * m
    itemsize = jnp.dtype(stencil.dtype).itemsize
    sublane = 8 * max(1, 4 // itemsize)
    # m-aligned output slice keeps the store sublane-aligned; Ly >= m keeps
    # every halo slab single-neighbor (vacuous when m == 0).
    if W % 128 or m % sublane or Ly < m or Ly % sublane:
        return None
    micro_factory, halo, nfields = _MICRO2D[stencil.name]
    if m and not sharded and not periodic:
        return None  # an inset store without global bounds needs wrap
    if m:
        # One micro-step advances information by halo cells PER PHASE (the
        # red-black black sweep reads this micro-step's fresh red values):
        # shared accounting with the 3D windowed kernels.
        if m != k * _halo_per_micro_2d(stencil):
            return None
    if _live_factor(stencil.name) * nfields * Hp * W * itemsize \
            > _VMEM_LIMIT_BYTES:
        return None
    micro = micro_factory(stencil, interpret)
    with_origin = sharded and not periodic

    def kernel(*refs):
        if with_origin:
            y_off, refs = refs[0][0], refs[1:]
        fields = tuple(r[...] for r in refs[:nfields])
        like = fields[0]
        if periodic:
            frame = jnp.zeros(like.shape, jnp.bool_)
        elif sharded:
            H, _W = sharded_global
            gy = (jax.lax.broadcasted_iota(jnp.int32, like.shape, 0)
                  + y_off - m)
            gx = jax.lax.broadcasted_iota(jnp.int32, like.shape, 1)
            frame = ((gy < halo) | (gy >= H - halo)
                     | (gx < halo) | (gx >= W - halo))
        else:
            yi = jax.lax.broadcasted_iota(jnp.int32, like.shape, 0)
            xi = jax.lax.broadcasted_iota(jnp.int32, like.shape, 1)
            frame = ((yi < halo) | (yi >= Hp - halo)
                     | (xi < halo) | (xi >= W - halo))
        # Loop-invariant prelude: parity-sensitive models (red-black SOR)
        # get their color mask computed once per HBM pass, not per
        # micro-step (Mosaic does not reliably hoist out of fori_loop).
        # Block-local parity equals global parity because every offset in
        # play (m, Ly, shard origin) is even by the alignment gates.
        extra = ()
        if stencil.parity_sensitive:
            from ..sor import _parity_mask

            extra = (_parity_mask(like.shape, 2),)

        def body(_, fs):
            return micro(fs, frame, *extra)

        fields = jax.lax.fori_loop(0, k, body, fields)
        for o, f in zip(refs[nfields:], fields):
            o[...] = f[m:m + Ly, :] if m else f

    in_spec = pl.BlockSpec((Hp, W), lambda: (0, 0))
    out_spec = pl.BlockSpec((Ly, W), lambda: (0, 0))
    extra_specs = [pl.BlockSpec(memory_space=pltpu.SMEM)] \
        if with_origin else []
    call = pl.pallas_call(
        kernel,
        name="fused_fullgrid",
        grid=(),
        in_specs=extra_specs + [in_spec] * nfields,
        out_specs=[out_spec] * nfields,
        out_shape=[jax.ShapeDtypeStruct((Ly, W), stencil.dtype)
                   for _ in range(nfields)],
        interpret=interpret,
        compiler_params=None if interpret else compiler_params(
            vmem_limit_bytes=_VMEM_LIMIT_BYTES),
    )
    return call, nfields


def make_fullgrid_step(
    stencil: Stencil,
    global_shape: Sequence[int],
    k: int,
    interpret: Optional[bool] = None,
    periodic: bool = False,
):
    """Build ``fields -> fields`` advancing k steps in one VMEM residency.

    ``periodic=True`` drops the guard frame entirely: the in-VMEM rolls
    wrap at the domain extents, which IS the periodic boundary (for
    parity-sensitive models this additionally requires even extents,
    matching make_sharded_step's gate).  Returns None when unsupported
    (not a 2D micro family, k < 1, sublane/lane-unaligned shape, or the
    grid does not fit the VMEM budget) — callers fall back to the
    per-step path.
    """
    # (No parity/odd-extent gate needed for periodic red-black models:
    # the alignment gates in _build_call already force even extents.)
    built = _build_call(stencil, tuple(int(s) for s in global_shape),
                        0, k, interpret, periodic=periodic)
    if built is None:
        return None
    call, _ = built

    def step_k(fields: Fields) -> Fields:
        return tuple(call(*fields))

    return step_k


def build_fullgrid_masked_call(
    stencil: Stencil,
    padded_shape,
    m: int,
    k: int,
    interpret: Optional[bool] = None,
    periodic: bool = False,
    global_shape=None,
):
    """Whole-LOCAL-block variant for the sharded 2D path (shard_map).

    The caller (parallel.stepper.make_sharded_fullgrid_step) exchanges
    width-``m`` y-halos (``m = k * halo * phases``), so the input blocks
    are ``(local_y + 2m, X)``.  In guard-frame mode the call takes the
    shard's global y-origin as an SMEM (1,) int32 input FIRST and derives
    the frame in-kernel from it + ``global_shape`` — no mask array is
    streamed (same technique as the 3D path; a BlockSpec index_map cannot
    see the traced axis_index, the kernel body can).  Output is the core
    ``(local_y, X)``; rows within ``m`` of the padded edge are
    temporal-validity casualties exactly as in the windowed 3D kernels.
    Parity-sensitive models derive color from block-local coordinates,
    which matches global parity when the caller enforces even local
    extents and even ``m`` (ops/sor.py's documented sharding caveat).

    Returns ``(call, nfields)`` or None (unsupported family, unaligned
    shape, or VMEM budget exceeded).
    """
    if m < 1:
        return None
    if not periodic and global_shape is None:
        return None
    return _build_call(
        stencil, padded_shape, m, k, interpret,
        sharded_global=None if periodic
        else tuple(int(s) for s in global_shape),
        periodic=periodic)
