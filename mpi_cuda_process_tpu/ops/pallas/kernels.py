"""Pallas TPU stencil kernels.

TPU-native replacement for the reference's CUDA ``__global__`` kernels
(``middle_kernel``/``border_kernel``, kernel.cu:70-113, MDF_kernel.cu:24-70).
Where the reference hand-partitions a flat thread index space (and silently
skips the tail when ``h*w`` isn't a multiple of 512 — kernel.cu:195-196), a
``pallas_call`` grid + ``BlockSpec``s cover the index space exactly.

Layouts:
  * 3D stencils: grid over z-chunks of ``bz`` planes.  Each program reads two
    views of the halo-padded input — a ``bz``-plane block at chunk i and a
    2-plane "tail" block starting at plane ``(i+1)*bz`` — concatenates them
    in VMEM into the ``bz+2`` planes the chunk's outputs need, applies every
    tap of the stencil in one VMEM pass, and writes ``bz`` output planes.
    HBM traffic is ``(bz+2)/bz`` x read + 1 x write (~12-25% over the ideal
    single pass), with Pallas's automatic double-buffered pipeline overlapping
    the next chunk's fetch with this chunk's compute.  This matters most for
    high-arity stencils (27-point), where XLA's own fusion does several HBM
    passes.
  * 2D stencils: the whole padded grid lives in VMEM (one program) — right
    for grids up to a few Mcells; larger 2D grids use the jnp path, which XLA
    already fuses to a single HBM pass.

All kernels compute over *padded* blocks (halo already attached by
``jnp.pad`` or the mesh halo exchange), so they are drop-in ``compute_fn``
replacements for ``Stencil.update`` in both the single-device and shard_map
steppers — the decomposition machinery does not change.
"""

from __future__ import annotations

import functools
import itertools
import math
from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..stencil import Fields, Stencil
from .compat import compiler_params

# Whole-2D-grid kernels hold in+out in VMEM (~16 MB); cap well below that.
_MAX_2D_VMEM_CELLS = 2 * 1024 * 1024

# Mosaic's default scoped-vmem limit is 16 MiB — v5e physically has 128 MiB
# of VMEM, and the z-chunk kernels want big chunks (the (bz+2h)/bz halo
# re-read overhead shrinks with bz).  Raising the limit was the fix for the
# round-2 scoped-VMEM compile failures: at 256^3 the kernel's true scoped
# usage (pipeline double-buffers + the in-kernel concatenate + tap
# intermediates) was 17.3 MiB against the 16 MiB default.
_VMEM_LIMIT_BYTES = 100 * 1024 * 1024
# Constructed through ops/pallas/compat.py.
_COMPILER_PARAMS = compiler_params(
    vmem_limit_bytes=_VMEM_LIMIT_BYTES,
    dimension_semantics=("arbitrary",),
)


def _interpret_default() -> bool:
    return jax.default_backend() != "tpu"


def _roll(x, shift, axis, interpret):
    """In-VMEM roll: jnp in interpret mode, pltpu.roll on hardware.

    Single definition shared by every Pallas module (fused.py, rawstep.py)
    — neighbor taps as rolls keep operands at one aligned layout, where
    odd-offset sublane/lane slices force a Mosaic relayout per tap.
    """
    if interpret:
        return jnp.roll(x, shift, axis)
    return pltpu.roll(x, shift % x.shape[axis], axis)


# ----------------------------------------------------------------------------
# 3D: z-chunk kernels
# ----------------------------------------------------------------------------

# Isotropic 27-point Laplacian weights (x 1/30) — single source of truth for
# every Pallas variant; must match ops/heat.py's jnp op.
_W27_FACE, _W27_EDGE, _W27_CORNER = 14.0 / 30.0, 3.0 / 30.0, 1.0 / 30.0
_W27_CENTER = -128.0 / 30.0


def _slab_taps_7(alpha, s, bz):
    u = s[1:bz + 1, 1:-1, 1:-1]
    lap = (
        s[0:bz, 1:-1, 1:-1]
        + s[2:bz + 2, 1:-1, 1:-1]
        + s[1:bz + 1, :-2, 1:-1]
        + s[1:bz + 1, 2:, 1:-1]
        + s[1:bz + 1, 1:-1, :-2]
        + s[1:bz + 1, 1:-1, 2:]
        - 6.0 * u
    )
    return u + alpha * lap


def _slab_taps_27(alpha, s, bz):
    u = s[1:bz + 1, 1:-1, 1:-1]
    acc = _W27_CENTER * u
    for dz, dy, dx in itertools.product((-1, 0, 1), repeat=3):
        nz = (dz != 0) + (dy != 0) + (dx != 0)
        if nz == 0:
            continue
        w = (_W27_FACE, _W27_EDGE, _W27_CORNER)[nz - 1]
        ys = slice(1 + dy, (dy - 1) or None)
        xs = slice(1 + dx, (dx - 1) or None)
        acc = acc + w * s[1 + dz:1 + dz + bz, ys, xs]
    return u + alpha * acc


def _slab_taps_13(alpha, s, bz):
    # 4th-order 13-point Laplacian on a halo-2 slab: s is (bz+4, yp, xp).
    w = {1: 16.0 / 12.0, 2: -1.0 / 12.0}
    u = s[2:bz + 2, 2:-2, 2:-2]
    acc = (-30.0 / 12.0 * 3.0) * u
    for dist in (1, 2):
        for o in (-dist, dist):
            acc = acc + w[dist] * (
                s[2 + o:2 + o + bz, 2:-2, 2:-2]
                + s[2:bz + 2, 2 + o:(o - 2) or None, 2:-2]
                + s[2:bz + 2, 2:-2, 2 + o:(o - 2) or None]
            )
    return u + alpha * acc


def _zchunk_kernel(taps, bz, zc, ztail, out):
    s = jnp.concatenate([zc[...], ztail[...]], axis=0)  # bz + 2*halo planes
    out[...] = taps(s, bz)


def _zchunk_wave_kernel(c2dt2, bz, zc, ztail, prev, out_u):
    s = jnp.concatenate([zc[...], ztail[...]], axis=0)
    u = s[1:bz + 1, 1:-1, 1:-1]
    lap = (
        s[0:bz, 1:-1, 1:-1]
        + s[2:bz + 2, 1:-1, 1:-1]
        + s[1:bz + 1, :-2, 1:-1]
        + s[1:bz + 1, 2:, 1:-1]
        + s[1:bz + 1, 1:-1, :-2]
        + s[1:bz + 1, 1:-1, 2:]
        - 6.0 * u
    )
    out_u[...] = 2.0 * u - prev[...] + c2dt2 * lap
    # new u_prev is carried verbatim by the stepper (carry_map), not written


def _pick_bz(z: int, plane_bytes: int, extra_planes: int = 0,
             halo: int = 1) -> int:
    # Scoped-VMEM cost model, fit to Mosaic's reported stack usage: the
    # pipeline double-buffers every spec (in: bz + 2*halo planes + extras;
    # out: bz planes), the kernel materializes the concatenated
    # (bz + 2*halo)-plane slab, and the tap chain holds ~3 bz-plane
    # intermediates live.  Keep the estimate under ~80% of the raised
    # _VMEM_LIMIT_BYTES so Mosaic's own scratch still fits.
    budget = int(_VMEM_LIMIT_BYTES * 0.8)
    for bz in (64, 32, 16, 8, 4, 2):
        if z % bz or bz % (2 * halo):
            continue
        est = (2 * (bz + 2 * halo + extra_planes)   # input pipeline buffers
               + 2 * bz                             # output pipeline buffers
               + (bz + 2 * halo)                    # in-kernel concatenate
               + 3 * bz) * plane_bytes              # tap intermediates
        if est <= budget:
            return bz
    return 0


def _zchunk_specs(padded_shape, bz, halo: int = 1):
    zp_, yp, xp = padded_shape
    z, y, x = zp_ - 2 * halo, yp - 2 * halo, xp - 2 * halo
    # chunk i needs padded planes [i*bz, i*bz + bz + 2*halo): a bz-block at
    # block index i plus a 2*halo-plane tail block at element offset
    # (i+1)*bz (block-aligned because bz % 2*halo == 0).
    zc = pl.BlockSpec((bz, yp, xp), lambda i: (i, 0, 0))
    ztail = pl.BlockSpec(
        (2 * halo, yp, xp), lambda i: ((i + 1) * bz // (2 * halo), 0, 0))
    out = pl.BlockSpec((bz, y, x), lambda i: (i, 0, 0))
    return zc, ztail, out


_SLAB_TAPS = {
    "heat3d": (_slab_taps_7, 1),
    "heat3d27": (_slab_taps_27, 1),
    "heat3d4th": (_slab_taps_13, 2),
}


def _heat3d_compute(stencil: Stencil, interpret: bool):
    alpha = float(stencil.params["alpha"])
    taps_fn, halo = _SLAB_TAPS[stencil.name]
    taps = functools.partial(taps_fn, alpha)

    def compute(padded: Fields) -> Fields:
        (p,) = padded
        zp_, yp, xp = p.shape
        z, y, x = zp_ - 2 * halo, yp - 2 * halo, xp - 2 * halo
        bz = _pick_bz(z, yp * xp * p.dtype.itemsize, halo=halo)
        if bz == 0:
            return stencil.update(padded)  # shape unsuited: jnp path
        zc, ztail, so = _zchunk_specs(p.shape, bz, halo)
        res = pl.pallas_call(
            functools.partial(_zchunk_kernel, taps, bz),
            grid=(z // bz,),
            in_specs=[zc, ztail],
            out_specs=so,
            out_shape=jax.ShapeDtypeStruct((z, y, x), p.dtype),
            interpret=interpret,
            compiler_params=None if interpret else _COMPILER_PARAMS,
        )(p, p)
        return (res,)

    return compute


def _wave3d_compute(stencil: Stencil, interpret: bool):
    c2dt2 = float(stencil.params["c2dt2"])

    def compute(padded: Fields) -> Fields:
        p, prev = padded  # prev has field_halo 0: unpadded
        zp_, yp, xp = p.shape
        z, y, x = zp_ - 2, yp - 2, xp - 2
        bz = _pick_bz(z, yp * xp * p.dtype.itemsize, extra_planes=2)
        if bz == 0:
            return stencil.update(padded)
        zc, ztail, so = _zchunk_specs(p.shape, bz)
        sprev = pl.BlockSpec((bz, y, x), lambda i: (i, 0, 0))
        new_u = pl.pallas_call(
            functools.partial(_zchunk_wave_kernel, c2dt2, bz),
            grid=(z // bz,),
            in_specs=[zc, ztail, sprev],
            out_specs=so,
            out_shape=jax.ShapeDtypeStruct((z, y, x), p.dtype),
            interpret=interpret,
            compiler_params=None if interpret else _COMPILER_PARAMS,
        )(p, p, prev)
        # slot 1 is dead (carry_map=(None, 0)); prev has the right shape
        return (new_u, prev)

    return compute


# ----------------------------------------------------------------------------
# 2D: whole-grid VMEM kernels
# ----------------------------------------------------------------------------


def _heat2d_kernel(alpha, p, out):
    u = p[1:-1, 1:-1]
    lap = p[:-2, 1:-1] + p[2:, 1:-1] + p[1:-1, :-2] + p[1:-1, 2:] - 4.0 * u
    out[...] = u + alpha * lap


def _life_kernel(p, out):
    n = None
    for dy, dx in itertools.product((-1, 0, 1), repeat=2):
        if (dy, dx) == (0, 0):
            continue
        ys = slice(1 + dy, (dy - 1) or None)
        xs = slice(1 + dx, (dx - 1) or None)
        s = p[ys, xs]
        n = s if n is None else n + s
    alive = p[1:-1, 1:-1]
    out[...] = ((n == 3) | ((n == 2) & (alive == 1))).astype(alive.dtype)


def _whole2d_compute(stencil: Stencil, interpret: bool):
    if stencil.name == "heat2d":
        def body(p, out, _alpha=stencil.params["alpha"]):
            _heat2d_kernel(_alpha, p, out)
    elif stencil.name == "life":
        body = _life_kernel
    else:
        raise KeyError(stencil.name)

    def compute(padded: Fields) -> Fields:
        (p,) = padded
        out_shape = (p.shape[0] - 2, p.shape[1] - 2)
        if math.prod(p.shape) > _MAX_2D_VMEM_CELLS:
            return stencil.update(padded)  # too big for VMEM: jnp path
        res = pl.pallas_call(
            body,
            out_shape=jax.ShapeDtypeStruct(out_shape, p.dtype),
            interpret=interpret,
        )(p)
        return (res,)

    return compute


# ----------------------------------------------------------------------------
# public entry
# ----------------------------------------------------------------------------

_BUILDERS: dict = {
    "heat3d": _heat3d_compute,
    "heat3d27": _heat3d_compute,
    "heat3d4th": _heat3d_compute,
    "wave3d": _wave3d_compute,
    "heat2d": _whole2d_compute,
    "life": _whole2d_compute,
}


def has_pallas_kernel(name: str) -> bool:
    return name in _BUILDERS


def make_pallas_compute(
    stencil: Stencil, interpret: Optional[bool] = None
) -> Callable[[Fields], Fields]:
    """Drop-in Pallas replacement for ``stencil.update``.

    Returns a function (padded fields -> interior fields) usable as the
    ``compute_fn`` of ``driver.make_step`` / ``parallel.make_sharded_step``.
    ``interpret`` defaults to True off-TPU so CI runs the same kernels in
    Pallas interpret mode (SURVEY.md §4.4).
    """
    if interpret is None:
        interpret = _interpret_default()
    try:
        builder = _BUILDERS[stencil.name]
    except KeyError:
        raise KeyError(
            f"no pallas kernel for {stencil.name!r}; "
            f"available: {sorted(_BUILDERS)}"
        ) from None
    return builder(stencil, interpret)
