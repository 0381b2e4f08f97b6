"""Streaming (sliding-window) temporal-blocking kernel: manual DMA pipeline.

The tiled fused kernels (``fused.py``) pay window READ AMPLIFICATION:
every (bz, by) tile re-reads its 2*wm-wide overlap with its neighbors, a
measured (1+2wm/bz)(1+2wm/by) ~= 1.5-2.4x extra HBM traffic.  This module
removes the z-axis share of that entirely: the kernel slides a window down
the z axis and keeps the overlap planes resident in a VMEM ring, so every
input plane is DMA'd from HBM **exactly once per k-step pass**.

Traffic per pass (k steps): ``(1 + 2*wm_a/by) reads + 1 write`` of the
grid, vs the jnp path's ``2k`` and the tiled kernels' ``~2.4 + 1``.  At
the measured ~330 GB/s Mosaic DMA rate this projects ~155 Gcells/s for
heat3d 512^3 f32 k=4 (vs the tiled kernels' measured 107), independent of
whether a manual pipeline can beat the auto rate (benchmarks/
pipeline_probe.py answers that separately).

Structure (one ``pallas_call``, grid over y strips):
  * x: full lane extent, never sliced (taps are lane rolls — fused.py's
    layout rule).
  * y: tiled in ``by`` strips; each strip loads ``by + 2*wm_a`` columns
    where ``wm_a`` is the temporal margin rounded up to the dtype's
    sublane tile, so every DMA offset is tile-aligned.  This is why bf16
    works at k=4 here: the tiled kernels need block OFFSETS at 2*wm
    granularity (hence bf16 k=8), but a strip window only needs sublane
    alignment of ``ylo``, which rounding the margin provides.
  * z: sliding window.  The grid is cut into ``nc = Z/bz`` chunks; a
    4-slot VMEM ring holds the last 4 chunks of the strip.  Computing
    chunk c needs planes ``[c*bz - wm, (c+1)*bz + wm)`` (clamped at the
    walls), which with ``2*wm <= bz`` span at most chunks {c-1, c, c+1}
    — all resident.  Chunk c+2 prefetches (into the slot chunk c-2 no
    longer needs) while the k micro-steps run, overlapping DMA with
    compute; the extraction happens BEFORE the prefetch starts, so no
    read ever races an in-flight DMA.

Correctness is the same argument as the tiled kernels (fused.py): after
j micro-steps only cells >= j*halo*phases from a non-wall window edge are
valid; the clamped window keeps the stored core >= wm from every non-wall
edge, and wall-side cells are re-pinned by the frame mask each micro-step
(``_window_frame``).  Equivalence vs k plain steps is asserted by
tests/test_streamfused.py in interpret mode for every family.

Sharded variants complete the kind x mesh matrix: z-only meshes hand the
exchanged z slabs to the kernel as operands
(``build_stream_sharded_call``); meshes that shard y additionally take
the y slabs and the four two-pass-composed corner pieces
(``build_stream_2axis_call`` — edge y-strips splice slab COLUMNS into
the sliding window in place of the unsharded clamp, corners substitute
for the slab's z overhang at z-edge chunks), so the balanced
surface-to-volume decompositions (8x8x1 on 64 chips: ~8x fewer face
bytes than the z-ring) run the same lowest-traffic kernel class.
Equivalence on 2-axis meshes: tests/test_twoaxis_stream.py.

Reference anchor: this replaces the role of the reference's per-step
middle/border kernel pair (kernel.cu:209/221) the same way fused.py does —
k whole time steps per HBM round-trip — with the DMA schedule written by
hand instead of by Mosaic's auto-pipeline.
"""

from __future__ import annotations

from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..stencil import Fields, Stencil

from .compat import compiler_params

from .kernels import _VMEM_LIMIT_BYTES, _interpret_default
from .fused import (
    _MICRO,
    _XWIN_GX,
    _halo_per_micro,
    _lane_round,
    _run_micros,
    _sublane,
    _window_frame,
)

_VMEM_LIMIT = int(_VMEM_LIMIT_BYTES * 0.8)

# Ring slots.  4 = the minimum that lets chunk c+2 prefetch while chunks
# {c-1, c, c+1} stay resident for the current window.
_NSLOTS = 4
# Lane-axis shell for x-windowed strips: one lane tile per side (the
# minimum DMA-alignable x offset granularity), >= every family's temporal
# margin wm (gated) so roll-wrap garbage never reaches the stored core —
# the SAME invariant as the wide-X tiled kernel's shell, so the single
# definition is shared.
_XSHELL = _XWIN_GX

# z-chunk candidates for the strip picker, largest first.  If this ladder
# ever grows past 2*_XSHELL, the picker's wm <= _XSHELL filter on
# x-window candidates becomes load-bearing (see _pick_strip) — the
# constant exists so tests can exercise that interaction.
_BZ_LADDER = (32, 16, 8)


def _stream_body(micro, nfields, k, halo, wm, wm_a, bz, by, bx, lshape,
                 gshape, parity, origin_z, ins, outs, slabs,
                 origin_y=0, yslabs=None, corners=None, order=""):
    """One (y, x) strip: slide the z window down the local block, k
    micro-steps per chunk.

    ``lshape`` is the LOCAL (Lz, Y, X); ``gshape`` the global shape the
    frame mask is derived against, with ``origin_z`` this block's global
    z origin (0 / static for the unsharded kernel, an SMEM scalar when
    sharded).  ``slabs`` is None (unsharded: windows CLAMP at the z walls
    and the frame re-pins them) or a pair of (wm, Y, X) HBM refs per
    field holding the exchanged neighbor slabs (sharded: edge chunks
    substitute slab planes for the clamped overhang, so the window sees
    genuine neighbor values).

    ``yslabs``/``corners`` (2-axis sharded kernel, requires ``slabs``):
    per field a pair of (Lz, wm_a, X) y-slab refs — the exchanged
    neighbor columns, caller-aligned to the sublane-rounded margin
    ``wm_a`` (genuine data in the window-adjacent wm columns, edge-
    replicated filler in the rest, which temporal validity excludes) —
    and the four (wm, wm_a, X) corner refs (ll, lh, hl, hh in (z-side,
    y-side) order, same alignment).  Edge y-strips then SPLICE slab
    columns into the sliding window in place of the unsharded clamp:
    the y slab rides its own z-chunk VMEM ring (same DMA schedule as
    the core), z-edge chunks of edge strips substitute corner planes
    for the y-slab's clamped overhang, and the spliced window's origin/
    store offsets become strip-uniform (``wm_a``).  With one y strip
    (by == Y) both splices apply statically; multi-strip grids select
    per edge on the traced strip id, exactly like the tiled 2-axis
    kernels' wall selects.

    ``bx`` is None for whole-lane strips (the x axis never sliced — the
    original kernel, byte-identical) or a lane-tile multiple: windows
    then carry a ``_XSHELL``-lane x shell, clamped at the (always-global)
    x walls exactly like y; lane-roll wrap garbage lands in the shell,
    which temporal validity excludes (``_XSHELL >= wm``, gated).  This is
    what fits two-field wave at X=4096 lanes (config 5) where whole-lane
    strips exceed VMEM.
    """
    Lz, Y, X = lshape
    nc = Lz // bz
    wz = bz + 2 * wm
    two_axis = yslabs is not None
    ny = Y // by
    one_strip = two_axis and ny == 1
    # wyc: the CORE window's column extent (what the ring DMAs carry);
    # wy: the assembled window's extent (wyc + both slab flanks when the
    # single strip spans the whole local y extent).
    wyc = Y if one_strip else by + 2 * wm_a
    wy = Y + 2 * wm_a if one_strip else wyc
    # swept traversal order (policy/autotune ``order``): "rev" walks the
    # y strips high-to-low, "xy" makes the x windows the OUTER grid axis
    # — strips write disjoint output slices, so any order is bit-exact;
    # only the DMA locality pattern (what it costs) changes
    yj = pl.program_id(1 if order == "xy" else 0)
    if order == "rev":
        yj = ny - 1 - yj
    sub = _sublane(jnp.dtype(ins[0].dtype).itemsize)
    ylo = 0 if one_strip else pl.multiple_of(
        jnp.clip(yj * by - wm_a, 0, Y - wyc), sub)  # gated alignment
    if bx is None:
        wx, xlo, x_idx = X, 0, ()
        store_x, out_x = 0, ()
    else:
        wx = bx + 2 * _XSHELL
        xj = pl.program_id(0 if order == "xy" else 1)
        xlo = pl.multiple_of(jnp.clip(xj * bx - _XSHELL, 0, X - wx),
                             _XSHELL)
        x_idx = (pl.ds(xlo, wx),)
        store_x, out_x = xj * bx - xlo, (pl.ds(xj * bx, bx),)

    def body(scratch, sems, obuf, osems, slab_mem=None, slab_sems=None,
             yring=None, ysems=None, corner_mem=None, corner_sems=None,
             stage=None):
        def _slot(chunk):
            return jax.lax.rem(chunk, _NSLOTS) if _traced(chunk) \
                else chunk % _NSLOTS

        def dma(f, chunk):
            return pltpu.make_async_copy(
                ins[f].at[(pl.ds(chunk * bz, bz), pl.ds(ylo, wyc))
                          + x_idx],
                scratch.at[f, pl.ds(_slot(chunk) * bz, bz)],
                sems.at[f, _slot(chunk)])

        def slab_dma(f, side):
            return pltpu.make_async_copy(
                slabs[f][side].at[(slice(None), pl.ds(ylo, wyc)) + x_idx],
                slab_mem.at[f, side],
                slab_sems.at[f, side])

        def ydma(f, side, chunk):
            # z-chunks of the y slab ride the SAME ring schedule as the
            # core: edge strips need the slab columns of exactly the
            # window's z span
            return pltpu.make_async_copy(
                yslabs[f][side].at[(pl.ds(chunk * bz, bz), slice(None))
                                   + x_idx],
                yring.at[f, side, pl.ds(_slot(chunk) * bz, bz)],
                ysems.at[f, side, _slot(chunk)])

        def corner_dma(f, i):
            return pltpu.make_async_copy(
                corners[f][i].at[(slice(None), slice(None)) + x_idx],
                corner_mem.at[f, i],
                corner_sems.at[f, i])

        def start_all(chunk):
            for f in range(nfields):
                dma(f, chunk).start()
                if two_axis:
                    for side in (0, 1):
                        ydma(f, side, chunk).start()

        def wait_all(chunk):
            for f in range(nfields):
                dma(f, chunk).wait()
                if two_axis:
                    for side in (0, 1):
                        ydma(f, side, chunk).wait()

        if slabs is not None:
            for f in range(nfields):
                for side in (0, 1):
                    slab_dma(f, side).start()
        if two_axis:
            for f in range(nfields):
                for i in range(4):
                    corner_dma(f, i).start()
        start_all(0)
        start_all(1)  # nc >= 3 by the builder's gate
        wait_all(0)
        if slabs is not None:
            for f in range(nfields):
                for side in (0, 1):
                    slab_dma(f, side).wait()
        if two_axis:
            for f in range(nfields):
                for i in range(4):
                    corner_dma(f, i).wait()

        def process(c, is_lo, is_hi):
            """One chunk.  ``c`` is a Python int for the peeled edge
            chunks (all extraction offsets become static) and a traced
            scalar for the interior ``fori_loop``.  The slab splice
            exists only in the edge bodies — interior chunks pay zero
            select/concat overhead."""
            if is_lo:
                zlo, base = 0, 0          # clamped window [0, wz)
            elif is_hi:
                zlo, base = Lz - wz, nc - 3
            else:
                zlo, base = c * bz - wm, c - 1  # interior: never clamps
            if not is_hi:
                wait_all(c + 1)

            # Extract a window: 3 consecutive ring chunks concatenated,
            # then sliced at the window origin — which is STATIC relative
            # to the concat base in every case (interior: bz - wm).
            off = zlo - base * bz if not _traced(base) else bz - wm

            def extract(read_chunk):
                parts = [read_chunk(base + i) for i in range(3)]
                return jnp.concatenate(parts, axis=0)[off:off + wz]

            fields = []
            for f in range(nfields):
                win = extract(
                    lambda ci, f=f: scratch[f, pl.ds(_slot(ci) * bz, bz)])
                if slabs is not None and is_lo:
                    # the true window overhangs the block by wm planes:
                    # splice the exchanged slab in place of the clamped
                    # re-read (interior chunks never clamp: bz >= 2*wm)
                    win = jnp.concatenate(
                        [slab_mem[f, 0], win[:wz - wm]], axis=0)
                elif slabs is not None and is_hi:
                    win = jnp.concatenate(
                        [win[wm:], slab_mem[f, 1]], axis=0)
                if two_axis:
                    # the y flanks: slab columns of the same z span,
                    # themselves z-spliced with CORNER planes at the z
                    # edges (the two-pass-composed diagonal data)
                    ywins = []
                    for side in (0, 1):
                        yw = extract(
                            lambda ci, f=f, side=side:
                            yring[f, side, pl.ds(_slot(ci) * bz, bz)])
                        if is_lo:
                            yw = jnp.concatenate(
                                [corner_mem[f, side], yw[:wz - wm]],
                                axis=0)
                        elif is_hi:
                            yw = jnp.concatenate(
                                [yw[wm:], corner_mem[f, 2 + side]],
                                axis=0)
                        ywins.append(yw)
                    if one_strip:
                        win = jnp.concatenate(
                            [ywins[0], win, ywins[1]], axis=1)
                    else:
                        # edge strips: replace the clamp-shifted columns
                        # by the slab flank; interior strips keep the
                        # plain window (ylo never clipped: by >= wm_a,
                        # gated).  Same-shape selects on the strip id.
                        w_lo = jnp.concatenate(
                            [ywins[0], win[:, :wyc - wm_a]], axis=1)
                        w_hi = jnp.concatenate(
                            [win[:, wm_a:], ywins[1]], axis=1)
                        win = jnp.where(
                            yj == 0, w_lo,
                            jnp.where(yj == ny - 1, w_hi, win))
                fields.append(win)
            fields = tuple(fields)

            # Prefetch AFTER extraction: chunk c+2's slot held chunk c-2,
            # which the concat above never reads — no read/DMA race.
            if is_lo:
                if 2 < nc:
                    start_all(2)
            elif not is_hi:
                @pl.when(c + 2 < nc)
                def _():
                    start_all(c + 2)

            # The TRUE window origin: with slabs, edge windows really
            # start at c*bz - wm (slab planes); clamped-only windows
            # start at zlo.
            if slabs is not None:
                z0 = origin_z + c * bz - wm
                store_z = wm  # the core sits mid-window always
            else:
                z0 = origin_z + zlo
                store_z = c * bz - zlo if not _traced(c) else wm
            if two_axis:
                # spliced windows start at the strip core minus wm_a on
                # EVERY strip (edges included) — origin and store offset
                # are strip-uniform
                y0 = origin_y + yj * by - wm_a
                store_y = wm_a
            else:
                y0 = origin_y + ylo
                store_y = yj * by - ylo
            frame, extra = _window_frame((wz, wy, wx), z0, y0, gshape,
                                         halo, False, parity, x0=xlo)
            fields = _run_micros(micro, fields, frame, extra, k)
            for f in range(nfields):
                if not is_lo:
                    out_dma(f, c - 1).wait()  # obuf[f] is free again
                obuf[f] = _store_slice(
                    fields[f], stage, store_z, store_y, store_x,
                    (bz, by, bx if bx is not None else X))
                out_dma(f, c).start()

        def out_dma(f, chunk):
            # outputs live in HBM (ANY): the core leaves VMEM by DMA,
            # overlapped with the next chunk's compute
            return pltpu.make_async_copy(
                obuf.at[f],
                outs[f].at[(pl.ds(chunk * bz, bz), pl.ds(yj * by, by))
                           + out_x],
                osems.at[f])

        process(0, True, False)
        jax.lax.fori_loop(
            1, nc - 1, lambda c, _: (process(c, False, False), ())[1], ())
        process(nc - 1, False, True)
        for f in range(nfields):
            out_dma(f, nc - 1).wait()

    kwargs = dict(
        scratch=pltpu.VMEM((nfields, _NSLOTS * bz, wyc, wx), ins[0].dtype),
        sems=pltpu.SemaphoreType.DMA((nfields, _NSLOTS)),
        obuf=pltpu.VMEM((nfields, bz, by, X if bx is None else bx),
                        ins[0].dtype),
        osems=pltpu.SemaphoreType.DMA((nfields,)),
    )
    if slabs is not None:
        kwargs["slab_mem"] = pltpu.VMEM((nfields, 2, wm, wyc, wx),
                                        ins[0].dtype)
        kwargs["slab_sems"] = pltpu.SemaphoreType.DMA((nfields, 2))
    if two_axis:
        kwargs["yring"] = pltpu.VMEM(
            (nfields, 2, _NSLOTS * bz, wm_a, wx), ins[0].dtype)
        kwargs["ysems"] = pltpu.SemaphoreType.DMA((nfields, 2, _NSLOTS))
        kwargs["corner_mem"] = pltpu.VMEM(
            (nfields, 4, wm, wm_a, wx), ins[0].dtype)
        kwargs["corner_sems"] = pltpu.SemaphoreType.DMA((nfields, 4))
    if _staged(two_axis, bx):
        kwargs["stage"] = pltpu.VMEM((wz, wy, wx), ins[0].dtype)
    pl.run_scoped(body, **kwargs)


def _staged(two_axis, bx) -> bool:
    """Is a window's store offset traced (the strip id picks it)?  Then
    the store slice reads back through a VMEM staging window."""
    return not two_axis or bx is not None


def _store_slice(win, stage, z, y, x, sizes):
    """The ``sizes`` core of a processed window at offset ``(z, y, x)``.

    Static offsets slice the value.  A traced offset reads the window
    back through the ``stage`` VMEM ref with ``pl.ds``: Mosaic lowers no
    value-level ``dynamic_slice``.  Pure data movement either way."""
    bz, by, bx = sizes
    if not any(_traced(v) for v in (z, y, x)):
        return win[z:z + bz, y:y + by, x:x + bx]
    stage[...] = win
    # traced offsets are aligned by the gates: y a sublane multiple (by,
    # wm_a, Y are), x a lane multiple (bx and _XSHELL are)
    if _traced(y):
        y = pl.multiple_of(y, _sublane(jnp.dtype(win.dtype).itemsize))
    if _traced(x):
        x = pl.multiple_of(x, 128)
    return stage[pl.ds(z, bz), pl.ds(y, by), pl.ds(x, bx)]


def _traced(v) -> bool:
    return not isinstance(v, int)


def _stream_kernel(micro, nfields, k, halo, wm, wm_a, bz, by, bx, shape,
                   parity, *refs, order=""):
    """Unsharded wrapper: ``refs`` = nfields input HBM refs then nfields
    output HBM refs (whole arrays, ``memory_space=ANY``)."""
    _stream_body(micro, nfields, k, halo, wm, wm_a, bz, by, bx, shape,
                 shape, parity, 0, refs[:nfields], refs[nfields:], None,
                 order=order)


def _stream_sharded_kernel(micro, nfields, k, halo, wm, wm_a, bz, by, bx,
                           lshape, gshape, parity, *refs, order=""):
    """Sharded wrapper: ``refs`` = origins (SMEM int32 (2,)), then per
    field [core, slab_lo, slab_hi] HBM refs, then nfields outputs."""
    origins, refs = refs[0], refs[1:]
    ins = [refs[3 * f] for f in range(nfields)]
    slabs = [(refs[3 * f + 1], refs[3 * f + 2]) for f in range(nfields)]
    outs = refs[3 * nfields:]
    _stream_body(micro, nfields, k, halo, wm, wm_a, bz, by, bx, lshape,
                 gshape, parity, origins[0], ins, outs, slabs,
                 order=order)


def _stream_2axis_kernel(micro, nfields, k, halo, wm, wm_a, bz, by, bx,
                         lshape, gshape, parity, *refs, order=""):
    """2-axis sharded wrapper: ``refs`` = origins (SMEM int32 (2,)), then
    per field [core, zslab_lo, zslab_hi, yslab_lo, yslab_hi, c_ll, c_lh,
    c_hl, c_hh] HBM refs (y slabs/corners pre-aligned to ``wm_a``
    columns), then nfields outputs."""
    origins, refs = refs[0], refs[1:]
    per = 9
    ins = [refs[per * f] for f in range(nfields)]
    slabs = [(refs[per * f + 1], refs[per * f + 2])
             for f in range(nfields)]
    yslabs = [(refs[per * f + 3], refs[per * f + 4])
              for f in range(nfields)]
    corners = [tuple(refs[per * f + 5:per * f + 9])
               for f in range(nfields)]
    outs = refs[per * nfields:]
    _stream_body(micro, nfields, k, halo, wm, wm_a, bz, by, bx, lshape,
                 gshape, parity, origins[0], ins, outs, slabs,
                 origin_y=origins[1], yslabs=yslabs, corners=corners,
                 order=order)


def _pick_strip(Z, Y, X, wm, wm_a, itemsize, nfields, sharded=False,
                two_axis=False):
    """Choose (bz, by, bx): Z/Y/X divisors meeting the sliding-window
    gates and the VMEM budget.  ``bx`` is None for whole-lane strips
    (preferred: no x amplification) or a lane-tile multiple when whole
    rows exceed VMEM (two-field wave at X=4096 — config 5).  Score:
    least total read amplification, then largest z chunk (fewer ring
    warm-ups and sem ops per pass).

    ``two_axis`` (y-sharded local blocks): ``by == Y`` becomes a valid
    single-strip candidate (both slab flanks spliced statically), and
    multi-strip candidates additionally require ``by >= wm_a`` so the
    interior strips' windows never clamp-shift (the spliced window's
    origin/store offsets are strip-uniform)."""
    budget_item = max(itemsize, 4)  # bf16 budgeted at the f32 envelope
    # x-windowed strips clamp their 128-lane shells at the global x walls,
    # which is only sound while the window margin fits inside one shell
    # (wm <= _XSHELL) — the same gate _stream_gates enforces on explicit
    # tiles.  Today the bz ladder (max 32) already excludes wm > 128 via
    # the 2*wm <= bz gate, so this filter is belt-and-braces: it keeps
    # candidate generation aligned with _stream_gates if the bz ladder
    # ever grows past 2*_XSHELL (otherwise the picker could choose an
    # x-window the gate rejects outright instead of a whole-lane strip).
    x_options = [None] + ([
        c for c in (2048, 1024, 512, 256)
        if X % c == 0 and c + 2 * _XSHELL <= X] if wm <= _XSHELL else [])
    by_options = (128, 64, 32, 16, 8)
    if two_axis and Y not in by_options:
        by_options = (Y,) + by_options  # the single-strip candidate
    best = None
    for bz in _BZ_LADDER:
        if Z % bz or 2 * wm > bz or Z // bz < 3:
            continue
        for by in by_options:
            if Y % by or by % _sublane(itemsize):
                continue
            if not _by_valid(Y, by, wm_a, two_axis):
                continue
            wy = (Y if two_axis and by == Y else by) + 2 * wm_a
            for bx in x_options:
                wx = X if bx is None else bx + 2 * _XSHELL
                x_amp = 1.0 if bx is None else wx / bx
                live = _strip_live_bytes(bz, by, bx, X, wm, wm_a,
                                         budget_item, nfields, sharded,
                                         two_axis=two_axis, Y=Y)
                if live > _VMEM_LIMIT:
                    continue
                score = (-(wy / by) * x_amp, bx is None, bz, by)
                if best is None or score > best[0]:
                    best = (score, (bz, by, bx))
    return best[1] if best else None


def _by_valid(Y, by, wm_a, two_axis):
    """Single definition of the y-strip gate (picker + explicit tiles).

    Unsharded-y strips clamp at the walls, so the window must fit the
    extent (``by + 2*wm_a <= Y``).  Two-axis strips splice slab flanks
    instead: ``by == Y`` is the static single-strip case, and
    multi-strip grids keep the window-fits gate PLUS ``by >= wm_a`` so
    interior strips never clamp-shift (the splice assumes strip-uniform
    window origins)."""
    if two_axis and by == Y:
        return True
    if by + 2 * wm_a > Y:
        return False
    return not two_axis or by >= wm_a


def _strip_live_bytes(bz, by, bx, X, wm, wm_a, budget_item, nfields,
                      sharded, two_axis=False, Y=None):
    """Scoped-VMEM live-set model for one strip program — the single
    definition used by both the picker and explicit-tile validation (an
    unvalidated explicit tile was the round-4 silently-wrong-geometry
    lesson: a 'fits' must never admit a config the kernel can't host)."""
    wz = bz + 2 * wm
    one_strip = two_axis and Y is not None and by == Y
    wyc = Y if one_strip else by + 2 * wm_a      # ring/core extent
    wy = Y + 2 * wm_a if one_strip else wyc      # assembled window
    wx = X if bx is None else bx + 2 * _XSHELL
    strip = wyc * _lane_round(wx) * budget_item
    win = wy * _lane_round(wx) * budget_item
    # ring + 3-chunk concat + window with ~3 live micro temporaries +
    # the store slice + the output DMA buffer (+ the staging window a
    # traced store offset reads back through)
    obuf = bz * by * _lane_round(X if bx is None else bx) * budget_item
    live = (_NSLOTS * bz * strip + 3 * bz * strip
            + 4 * wz * win + bz * win + obuf) * nfields
    if _staged(two_axis, bx):
        live += wz * win
    if sharded:
        # the slab ring (both sides, every field) + the edge chunks'
        # splice-concat temporary
        live += (2 * 2 * wm * strip + wz * win) * nfields
    if two_axis:
        # the y-slab rings + their concat temporaries + the corner
        # planes + the two same-shape select branches of the y splice
        ystrip = wm_a * _lane_round(wx) * budget_item
        live += (2 * _NSLOTS * bz * ystrip + 2 * 3 * bz * ystrip
                 + 2 * wz * ystrip + 4 * wm * ystrip
                 + 2 * wz * win) * nfields
    return live


def stream_supported(stencil: Stencil) -> bool:
    return stencil.name in _MICRO and stencil.ndim == 3


def _stream_gates(stencil, Lz, Y, X, k, tiles, sharded=False,
                  two_axis=False, margin=0):
    """Shared builder gates; returns
    ``(micro_factory, halo, nfields, wm, wm_a, bz, by, bx)`` or None —
    ``bx`` is None for whole-lane strips, else the x-window extent.

    ``margin`` (policy/autotune ``margin``) overrides the sublane-rounded
    temporal margin ``wm_a`` with a WIDER DMA-alignable y-flank — only a
    sublane multiple covering the k-step halo ``wm`` is geometrically
    valid (the extra columns are filler temporal validity excludes, so
    any accepted margin is bit-exact; what changes is the DMA shape)."""
    micro_factory, halo, nfields = _MICRO[stencil.name]
    wm = k * _halo_per_micro(stencil)
    itemsize = jnp.dtype(stencil.dtype).itemsize
    sub = _sublane(itemsize)
    wm_a = -(-wm // sub) * sub  # margin rounded to a DMA-alignable offset
    if margin:
        if margin % sub or margin < wm:
            return None
        wm_a = int(margin)
    if tiles is None:
        tiles = _pick_strip(Lz, Y, X, wm, wm_a, itemsize, nfields,
                            sharded=sharded, two_axis=two_axis)
        if tiles is None:
            return None
    if len(tiles) == 2:
        bz, by = tiles
        bx = None
    else:
        bz, by, bx = tiles
    if (Lz % bz or Y % by or 2 * wm > bz or Lz // bz < 3
            or by % sub or not _by_valid(Y, by, wm_a, two_axis)):
        return None
    if bx is not None and (X % bx or bx % _XSHELL
                           or bx + 2 * _XSHELL > X or wm > _XSHELL):
        return None
    # explicit tiles go through the SAME live-set gate as the picker
    if _strip_live_bytes(bz, by, bx, X, wm, wm_a, max(itemsize, 4),
                         nfields, sharded, two_axis=two_axis,
                         Y=Y) > _VMEM_LIMIT:
        return None
    return micro_factory, halo, nfields, wm, wm_a, bz, by, bx


def build_stream_sharded_call(
    stencil: Stencil,
    local_shape: Tuple[int, int, int],
    global_shape: Tuple[int, int, int],
    k: int,
    tiles: Optional[Tuple[int, ...]] = None,  # (bz, by[, bx])
    interpret: Optional[bool] = None,
    periodic: bool = False,
    margin: int = 0,
    order: str = "",
):
    """Streaming kernel over a z-decomposed LOCAL block: the config-5
    execution with sliding-window traffic.

    The call takes origins (int32 (2,)), then per field
    ``[core, slab_lo, slab_hi]`` (the width-``m`` exchanged neighbor
    slabs as separate operands — no exchange-padded copy exists, same
    contract as ``fused.build_zslab_padfree_call`` with layout (1, 1)),
    and returns ``nfields`` local-shape arrays advanced k steps.
    Returns ``(call, margin, nfields)`` or None.

    Edge z-chunks substitute slab planes for the unsharded kernel's
    clamped re-read, so interior shards see genuine neighbor values; at
    the global walls the slabs hold the bc fill and the frame mask
    re-pins them (ghost planes included), exactly like the z-slab tiled
    kernels.  vs the wide-X kernel's (1+4m/bz)(1+4m/by)(1+256/bx) read
    amplification (~4.5x for config-5 wave), streaming reads each plane
    once (+ the y-strip margin ~1.13x) — the projected config-5 winner.
    Guard-frame only (periodic declines; the sharded caller falls back).
    """
    if periodic or not stream_supported(stencil):
        return None
    if interpret is None:
        interpret = _interpret_default()
    Lz, Y, X = (int(s) for s in local_shape)
    gshape = tuple(int(s) for s in global_shape)
    gates = _stream_gates(stencil, Lz, Y, X, k, tiles, sharded=True,
                          margin=margin)
    if gates is None:
        return None
    micro_factory, halo, nfields, wm, wm_a, bz, by, bx = gates
    if order not in ("", "rev") and not (order == "xy"
                                         and bx is not None):
        return None  # "xy" permutes a 2-d strip grid only
    micro = micro_factory(stencil, interpret)
    parity = bool(stencil.phases)

    def kernel(*refs):
        _stream_sharded_kernel(micro, nfields, k, halo, wm, wm_a, bz, by,
                               bx, (Lz, Y, X), gshape, parity, *refs,
                               order=order)

    grid = (Y // by,) if bx is None else (
        (X // bx, Y // by) if order == "xy" else (Y // by, X // bx))
    call = pl.pallas_call(
        kernel,
        name="fused_stream_zslab",
        grid=grid,
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM)]
        + [pl.BlockSpec(memory_space=pl.ANY)] * (3 * nfields),
        out_specs=[pl.BlockSpec(memory_space=pl.ANY)] * nfields,
        out_shape=[jax.ShapeDtypeStruct((Lz, Y, X), stencil.dtype)
                   for _ in range(nfields)],
        interpret=interpret,
        compiler_params=None if interpret else compiler_params(
            vmem_limit_bytes=_VMEM_LIMIT_BYTES,
            dimension_semantics=("arbitrary",) * len(grid)),
    )
    return call, wm, nfields


def build_stream_2axis_call(
    stencil: Stencil,
    local_shape: Tuple[int, int, int],
    global_shape: Tuple[int, int, int],
    k: int,
    tiles: Optional[Tuple[int, ...]] = None,  # (bz, by[, bx])
    interpret: Optional[bool] = None,
    periodic: bool = False,
    margin: int = 0,
    order: str = "",
):
    """Streaming kernel over a (z, y)- or y-decomposed LOCAL block — the
    2-axis generalization of ``build_stream_sharded_call``, closing the
    last kind x mesh gap (the balanced surface-to-volume meshes could
    not use the lowest-traffic kernel class).

    The call takes origins (int32 (2,): this shard's global z AND y
    block offsets), then per field ``[core, zslab_lo, zslab_hi,
    yslab_lo, yslab_hi, c_ll, c_lh, c_hl, c_hh]`` — the operand set of
    ``halo.exchange_slabs_2axis`` at their NATURAL widths (z slabs
    (m, Ly, X), y slabs (Lz, m, X), corners (m, m, X)); the returned
    call aligns the y-facing operands to the sublane-rounded margin
    ``wm_a`` internally (edge-replicated filler on the window-far side —
    the streaming analogue of the tiled kernels' 2m duplication; the
    filler lands on don't-care cells temporal validity excludes).
    Returns ``(call, margin, nfields)`` or None.

    Edge y-strips splice the slab columns into the sliding window in
    place of the unsharded clamp (the y slab rides its own z-chunk VMEM
    ring; corner planes substitute for the slab's z overhang at z-edge
    chunks), so interior shards see genuine neighbor values on BOTH
    wall axes; at global walls the slabs hold the bc fill and the frame
    re-pins.  The x-windowed strip variant is preserved (3-extent
    tiles / the picker's x ladder), which is what keeps two-field wave
    tileable at 4096 lanes on the balanced meshes.  Guard-frame only
    (periodic declines; the sharded caller falls back).  An unsharded
    axis degrades through bc-fill dummy slabs from the same exchange
    helper, so one call serves (z, y)- and y-only-sharded meshes.
    """
    if periodic or not stream_supported(stencil):
        return None
    if interpret is None:
        interpret = _interpret_default()
    Lz, Ly, X = (int(s) for s in local_shape)
    gshape = tuple(int(s) for s in global_shape)
    gates = _stream_gates(stencil, Lz, Ly, X, k, tiles, sharded=True,
                          two_axis=True, margin=margin)
    if gates is None:
        return None
    micro_factory, halo, nfields, wm, wm_a, bz, by, bx = gates
    if order not in ("", "rev") and not (order == "xy"
                                         and bx is not None):
        return None  # "xy" permutes a 2-d strip grid only
    micro = micro_factory(stencil, interpret)
    parity = bool(stencil.phases)

    def kernel(*refs):
        _stream_2axis_kernel(micro, nfields, k, halo, wm, wm_a, bz, by,
                             bx, (Lz, Ly, X), gshape, parity, *refs,
                             order=order)

    grid = (Ly // by,) if bx is None else (
        (X // bx, Ly // by) if order == "xy" else (Ly // by, X // bx))
    pallas = pl.pallas_call(
        kernel,
        name="fused_stream_yz",
        grid=grid,
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM)]
        + [pl.BlockSpec(memory_space=pl.ANY)] * (9 * nfields),
        out_specs=[pl.BlockSpec(memory_space=pl.ANY)] * nfields,
        out_shape=[jax.ShapeDtypeStruct((Lz, Ly, X), stencil.dtype)
                   for _ in range(nfields)],
        interpret=interpret,
        compiler_params=None if interpret else compiler_params(
            vmem_limit_bytes=_VMEM_LIMIT_BYTES,
            dimension_semantics=("arbitrary",) * len(grid)),
    )
    pad = wm_a - wm

    def _align(a, lo_side):
        # pad the m-wide y extent up to the DMA-alignable wm_a: the
        # filler goes on the side AWAY from the window core (lo-side
        # slabs are read as the window's leading columns, so genuine
        # data must sit in the LAST wm columns, and vice versa)
        if pad == 0:
            return a
        cfg = [(0, 0)] * 3
        cfg[1] = (pad, 0) if lo_side else (0, pad)
        return jnp.pad(a, cfg, mode="edge")

    def call(origins, *args):
        ops = []
        for f in range(nfields):
            core, zlo, zhi, ylo, yhi, c_ll, c_lh, c_hl, c_hh = \
                args[9 * f:9 * f + 9]
            ops += [core, zlo, zhi,
                    _align(ylo, True), _align(yhi, False),
                    _align(c_ll, True), _align(c_lh, False),
                    _align(c_hl, True), _align(c_hh, False)]
        return pallas(origins, *ops)

    return call, wm, nfields


def make_stream_fused_step(
    stencil: Stencil,
    global_shape: Sequence[int],
    k: int,
    tiles: Optional[Tuple[int, ...]] = None,  # (bz, by[, bx])
    interpret: Optional[bool] = None,
    batch: int = 0,
    margin: int = 0,
    order: str = "",
):
    """Build ``fields -> fields`` advancing ``k`` steps in one streaming
    pass, or None when the shape can't host the sliding window.

    Semantically identical to ``k`` applications of ``driver.make_step``
    (guard-frame semantics; tests/test_streamfused.py).  Unlike the tiled
    kernels there is NO ``2*k*halo % sublane`` gate — bf16 runs at k=4.
    Guard-frame (non-periodic) only.

    ``batch=N`` (round 15, the ensemble engine): the step takes/returns
    fields with a leading member axis and the pallas grid gains an
    EXPLICIT leading batch dimension — ``(N, *strip_grid)`` — so all N
    members stream through the same compiled kernel, one member's full
    strip sweep per batch index (the VMEM ring re-primes at each new
    batch index exactly as it does at each new strip; per-member
    equivalence and the batched grid are pinned by
    tests/test_ensemble_engine.py).  Implemented through vmap's
    ``pallas_call`` batching rule, which constructs exactly that
    batched grid; the manual-DMA schedule is untouched.
    """
    if not stream_supported(stencil):
        return None
    if interpret is None:
        interpret = _interpret_default()
    Z, Y, X = (int(s) for s in global_shape)
    gates = _stream_gates(stencil, Z, Y, X, k, tiles, margin=margin)
    if gates is None:
        return None
    micro_factory, halo, nfields, wm, wm_a, bz, by, bx = gates
    if order not in ("", "rev") and not (order == "xy"
                                         and bx is not None):
        return None  # "xy" permutes a 2-d strip grid only
    micro = micro_factory(stencil, interpret)
    parity = bool(stencil.phases)

    def kernel(*refs):
        _stream_kernel(micro, nfields, k, halo, wm, wm_a, bz, by, bx,
                       (Z, Y, X), parity, *refs, order=order)

    grid = (Y // by,) if bx is None else (
        (X // bx, Y // by) if order == "xy" else (Y // by, X // bx))
    call = pl.pallas_call(
        kernel,
        name="fused_stream",
        grid=grid,
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)] * nfields,
        out_specs=[pl.BlockSpec(memory_space=pl.ANY)] * nfields,
        out_shape=[jax.ShapeDtypeStruct((Z, Y, X), stencil.dtype)
                   for _ in range(nfields)],
        interpret=interpret,
        compiler_params=None if interpret else compiler_params(
            vmem_limit_bytes=_VMEM_LIMIT_BYTES,
            dimension_semantics=("arbitrary",) * len(grid)),
    )

    def step_k(fields: Fields) -> Fields:
        return tuple(call(*fields))

    if batch:
        batched = jax.vmap(step_k)

        def step_k_batched(fields: Fields) -> Fields:
            if fields[0].shape != (batch, Z, Y, X):
                raise ValueError(
                    f"batched streaming step wants fields "
                    f"({batch}, {Z}, {Y}, {X}), got {fields[0].shape}")
            return batched(fields)

        step_k_batched._ensemble = int(batch)
        return step_k_batched

    return step_k
