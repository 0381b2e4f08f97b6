"""Per-device HBM budget estimation: refuse with arithmetic, don't OOM.

The reference has no memory accounting at all — it mallocs the FULL grid on
every rank (storage replicated, kernel.cu:184-191) and checks no return
code, so an over-size grid dies wherever the first allocation fails.  At
this framework's north-star scale (BASELINE config 5: 4096^3 wave,
2 x 256 GiB double-buffered f32) an unchecked launch costs minutes of
compile + transfer before a RESOURCE_EXHAUSTED with no actionable
breakdown.  This module computes the peak per-device live bytes for the
run's EXECUTION STRATEGY up front and raises a ValueError that shows the
arithmetic, so a config that cannot fit fails in milliseconds with the
numbers in hand (e.g.: config 5 needs bf16 — f32 state alone is
3 x 4 GiB/device on 64 chips before exchange transients).

The estimate is deliberately coarse-but-conservative: it models the
dominant full-field buffers (state, scan double-buffer transient, pad /
exchange-pad copies, the sharded fused mask) and adds a fractional
overhead for XLA workspace + Pallas pipeline scratch.  It is an upper
bound on the framework's own allocations, not a simulator of XLA's
scheduler.
"""

from __future__ import annotations

import math
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

# Fractional slack for XLA workspace, Pallas pipeline buffers (a few
# (bz+2m, by+2m, X) VMEM-to-HBM staging copies), and allocator rounding.
_OVERHEAD_FRAC = 0.10

# The CPU test platform reports no memory limit; the estimator's
# arithmetic still runs there against this nominal size.  A TPU that
# reports no limit is an error, never this default.
_NOMINAL_HOST_BYTES = 16 * 1024**3


def device_hbm_bytes() -> int:
    """Per-device memory capacity as the backend reports it."""
    dev = jax.devices()[0]
    stats = dev.memory_stats() or {}
    if stats.get("bytes_limit"):
        return int(stats["bytes_limit"])
    if dev.platform == "tpu":
        raise RuntimeError(
            f"{dev.device_kind} reports no memory limit "
            "(memory_stats()['bytes_limit']); the HBM budget needs it")
    return _NOMINAL_HOST_BYTES


def _local_shape(grid: Sequence[int], mesh: Sequence[int]) -> Tuple[int, ...]:
    counts = tuple(mesh) + (1,) * (len(grid) - len(mesh)) if mesh else \
        (1,) * len(grid)
    return tuple(int(g) // int(c) for g, c in zip(grid, counts))


def estimate_run_bytes(
    stencil,
    grid: Sequence[int],
    mesh: Sequence[int] = (),
    fuse: int = 0,
    ensemble: int = 0,
    periodic: bool = False,
    compute: str = "auto",
    fuse_kind: str = "auto",
    overlap: bool = False,
    pipeline: bool = False,
    exchange: str = "ppermute",
    ensemble_mesh: int = 0,
) -> Tuple[int, List[Tuple[str, int]]]:
    """Peak per-device live bytes for a run, with a labeled breakdown.

    Mirrors ``plan.build``'s strategy selection coarsely: temporal blocking
    (``fuse``) on its padded / pad-free / sharded (exchange-padded,
    SMEM-origin frame) variants, the raw whole-step kernels (no
    transient: the state is its own halo), and the jnp pad -> update
    path.  Returns ``(total, [(label, bytes), ...])``.

    ``ensemble=N`` prices the batched run (round 15 — the UNBUILDABLE
    ensemble wall is gone: every kind that builds unbatched builds
    batched, the kernels gain one batch grid dimension and the slab /
    pad transients scale with the members a device actually holds).
    ``ensemble_mesh=M`` shards the member axis over M device groups, so
    every per-device term scales by ``N / M`` members instead of N.

    ``exchange="rdma"`` (streaming kind under a mesh only — every other
    combination refuses before allocating, and the estimate says so):
    the in-kernel remote-DMA exchange stages each boundary slab
    chunk-by-chunk through double-buffered VMEM rings
    (``ops/pallas/remote.py``), so the HBM slab-transient term AND the
    pipelined carried-slab term are DELETED from the breakdown — the
    exchange path's live set is a few chunk-sized VMEM slots (never
    HBM-resident full-field or slab-set buffers), absorbed by the
    workspace-overhead fraction like every other kernel's staging
    copies.  This is the model change the rdma mode exists for: the
    last slab copies leave the budget.
    """
    itemsize = jnp.dtype(stencil.dtype).itemsize
    nfields = stencil.num_fields
    ens_shards = max(1, int(ensemble_mesh))
    if ensemble and int(ensemble) % ens_shards:
        raise ValueError(
            f"ensemble={ensemble} not divisible by "
            f"ensemble_mesh={ensemble_mesh} (the run refuses before "
            "allocating)")
    # per-DEVICE members: the batch each device actually holds
    batch = max(1, int(ensemble)) // ens_shards if ensemble else 1
    local = _local_shape(grid, mesh)
    cells = batch * math.prod(local)
    field_b = cells * itemsize
    halo = stencil.halo

    sharded = bool(mesh) and math.prod(mesh) > 1
    # donated scan carry: the new state is written before the old buffers
    # are released.  An unsharded fused pass writes every field anew (its
    # overlapping windows rule out aliasing); a raw whole-step kernel
    # writes what raw_step_fresh_fields says (none for the in-place
    # leapfrog pass); otherwise one field.
    fresh = 1
    if fuse and not sharded:
        fresh = nfields
    elif compute == "raw" and not fuse:
        from ..ops.pallas.rawstep import raw_step_fresh_fields

        fresh = raw_step_fresh_fields(stencil)
    parts: List[Tuple[str, int]] = [
        (f"state: {nfields} field(s) x {field_b / 2**30:.2f} GiB "
         f"({'x'.join(str(s) for s in local)} local, {stencil.dtype})",
         nfields * field_b),
        (f"step output transient: {fresh} field(s) written anew "
         "(donated double buffer)", fresh * field_b),
    ]

    if exchange == "rdma" and not (sharded and fuse and len(local) == 3
                                   and fuse_kind == "stream"):
        # the estimate must describe the path the run actually takes:
        # off the sharded streaming kind, plan/stepper raise before any
        # allocation — never price a transport the run would refuse
        parts.append(("rdma exchange: UNSUPPORTED off the sharded "
                      "streaming kind (the run refuses before "
                      "allocating)", 0))
    if fuse and len(local) == 3:
        from ..ops.pallas.fused import (
            _halo_per_micro,
            build_zslab_padfree_call,
            build_zslab_xwin_call,
            make_fused_step,
            prefer_padfree,
        )

        m = fuse * _halo_per_micro(stencil)
        lz, ly, lx = local
        padded_b = batch * (lz + 2 * m) * (ly + 2 * m) * lx * itemsize
        z_only = all(int(c) == 1 for c in tuple(mesh)[1:])
        lane_whole = all(int(c) == 1 for c in tuple(mesh)[2:])

        def _pipeline_part(slab_set_b):
            """(label, bytes) for the slab-carry scan: the carried slab
            set is a persistent scan-carry buffer — while a pass runs,
            THIS pass's slabs (consumed) and the NEXT pass's (being
            exchanged) are live together, one extra slab set beyond the
            per-pass operands counted above."""
            return ("pipelined carried slabs (slab-carry scan: next "
                    "pass's exchange lives alongside this pass's "
                    "operands)", slab_set_b)

        def _padfree_slab_part():
            """(label, bytes, base_set_bytes) for the sharded
            slab-operand pad-free path — z-only or 2-axis — or None when
            no builder tiles this local shape (construction is pure
            Python, no compile)."""
            if not lane_whole:
                return None
            grid_t = tuple(int(g) for g in grid)
            if z_only:
                ok = (build_zslab_padfree_call(
                    stencil, local, grid_t, fuse,
                    interpret=True, periodic=periodic) is not None
                    or build_zslab_xwin_call(
                        stencil, local, grid_t, fuse,
                        interpret=True, periodic=periodic) is not None)
                if not ok:
                    return None
                slab_cells = 2 * m * ly * lx
                what = f"slab operands only (2x{m} rows"
            else:
                from ..ops.pallas.fused import (
                    build_yzslab_padfree_call,
                    build_yzslab_xwin_call,
                )

                ok = (build_yzslab_padfree_call(
                    stencil, local, grid_t, fuse,
                    interpret=True, periodic=periodic) is not None
                    or build_yzslab_xwin_call(
                        stencil, local, grid_t, fuse,
                        interpret=True, periodic=periodic) is not None)
                if not ok:
                    return None
                # z slabs (width m) + 2m-duplicated y-slab operands +
                # the four 2m-duplicated corner pieces — the whole
                # transient set; NO exchange-padded block on 2-axis
                # meshes any more
                slab_cells = (2 * m * ly * lx + 2 * (2 * m) * lz * lx
                              + 4 * m * (2 * m) * lx)
                what = f"slab+corner operands only (2-axis, width {m}"
            base_b = batch * slab_cells * itemsize * nfields
            slab_b = 2 * base_b if overlap else base_b
            # (overlap: dummy interior slabs + the shell strips live
            # alongside the exchanged slabs during the split)
            return (f"sharded pad-free: {what}"
                    f"{', x2 overlap split' if overlap else ''})",
                    slab_b, base_b)

        # The budget must describe the path the stepper will actually
        # take: a pad-free preference that the kernel builder cannot TILE
        # (the VMEM window gate at very wide X) falls back to the padded
        # kernel, and the estimate follows it (round-4 review finding:
        # "fits" must never describe an unconstructible execution).
        # Builder construction is pure Python — no compile happens here.
        if sharded and fuse_kind == "stream":
            # slab operands only (the VMEM rings are not HBM).  Probe
            # construction so a "fits" never describes an unconstructible
            # run (plan.build raises before any allocation).  z-only
            # meshes take the zslab contract; meshes that shard y take
            # the 2-axis contract (y slabs + corners at natural width m,
            # plus the call's wm_a-aligned copies of the y-facing
            # operands).
            from ..ops.pallas.fused import _sublane
            from ..ops.pallas.streamfused import (
                build_stream_2axis_call,
                build_stream_sharded_call,
            )

            grid_t = tuple(int(g) for g in grid)
            if z_only:
                ok = lane_whole and build_stream_sharded_call(
                    stencil, local, grid_t, fuse,
                    interpret=True, periodic=periodic) is not None
                slab_cells = 2 * m * ly * lx
                what = f"slab operands only (2x{m} rows"
            else:
                ok = lane_whole and build_stream_2axis_call(
                    stencil, local, grid_t, fuse,
                    interpret=True, periodic=periodic) is not None
                # z slabs (width m) + y slabs and corners at width m PLUS
                # their wm_a-aligned copies (the sublane-rounded margin
                # the streaming DMA offsets need)
                m_a = -(-m // _sublane(itemsize)) * _sublane(itemsize)
                slab_cells = (2 * m * ly * lx
                              + 2 * (m + m_a) * lz * lx
                              + 4 * m * (m + m_a) * lx)
                what = (f"slab+corner operands only (2-axis stream, "
                        f"width {m}, y-aligned {m_a}")
            base_b = batch * slab_cells * itemsize * nfields
            # overlap: dummy interior slabs + the shell strips live
            # alongside the exchanged slabs during the split
            slab_b = 2 * base_b if overlap else base_b
            if ok and exchange == "rdma":
                # the rdma mode's whole point: boundary chunks ride the
                # in-kernel VMEM rings — the HBM slab-transient term is
                # deleted, not discounted
                parts.append(
                    ("sharded streaming rdma: slabs ride the in-kernel "
                     "VMEM rings (no HBM slab transient)", 0))
                if pipeline:
                    parts.append(
                        ("pipelined carried slabs: deleted under rdma "
                         "(the carry feeds the VMEM rings, no HBM slab "
                         "set persists across passes)", 0))
            else:
                parts.append(
                    (f"sharded streaming: {what}"
                     f"{', x2 overlap split' if overlap else ''})"
                     if ok else
                     "sharded streaming: UNBUILDABLE for this mesh/shape "
                     "(the run refuses before allocating)",
                     slab_b if ok else 0))
                if pipeline and ok:
                    parts.append(_pipeline_part(base_b))
        elif sharded and fuse_kind == "padfree":
            # forced pad-free under a mesh: no padded fallback exists
            # (make_sharded_fused_step returns None and plan.build
            # raises), so never estimate the padded transient
            part = _padfree_slab_part()
            if part is not None:
                parts.append(part[:2])
                if pipeline:
                    parts.append(_pipeline_part(part[2]))
            else:
                parts.append((
                    "sharded pad-free: UNBUILDABLE for this mesh/shape — "
                    "no padded fallback under a forced kind (the run "
                    "refuses before allocating)", 0))
        elif sharded and prefer_padfree(stencil, local, batch=batch) \
                and _padfree_slab_part() is not None:
            # slab-operand pad-free (stepper._make_zslab_padfree_step /
            # _make_yzslab_padfree_step): the exchanged slabs (+ corner
            # pieces on 2-axis meshes) are the ONLY transient — no
            # padded copy
            part = _padfree_slab_part()
            parts.append(part[:2])
            if pipeline:
                parts.append(_pipeline_part(part[2]))
        elif sharded:
            # exchange-padded local block per field (stepper.py
            # local_step); the frame comes from SMEM origin scalars, so
            # no mask array exists (round 3 streamed one per step)
            if pipeline:
                # the padded kind has no slab operands for the carry to
                # feed: make_sharded_fused_step raises, so the estimate
                # must describe the refusal, never a kernel the run
                # would not take
                parts.append((
                    "pipelined sharded fused: UNSUPPORTED on the "
                    "exchange-padded kind (the run refuses — force "
                    "--fuse-kind padfree/stream)", 0))
            n_padded = 2 * nfields if overlap else nfields
            # overlap split: the exchange-padded block (shell inputs) and
            # the locally-padded block (interior input) are live together
            parts.append(
                (f"sharded fused: {n_padded} "
                 f"{'exchange+local' if overlap else 'exchange'}-padded "
                 f"block(s) (+{2 * m} z/y)", n_padded * padded_b))
        elif fuse_kind == "stream":
            # sliding-window manual-DMA kernel: the ring lives in VMEM,
            # HBM holds only state + output.  Probe construction (pure
            # Python) so a "fits" never describes an unconstructible run;
            # when unbuildable, plan.build refuses before any allocation.
            # The unsharded kernel is guard-frame only; --ensemble now
            # BATCHES it (round 15: an explicit leading batch grid
            # dimension — the old "unbatched only" wall is deleted), so
            # only periodic wrap and untileable shapes refuse.
            from ..ops.pallas.streamfused import make_stream_fused_step

            ok = (not periodic
                  and make_stream_fused_step(stencil, grid, fuse,
                                             interpret=True) is not None)
            if ok:
                label = ("streaming fused: no pad transient"
                         + (f" ({batch} members batched)"
                            if ensemble else ""))
            elif periodic:
                # name the flag, not the shape: the fix is dropping
                # --periodic, not resizing the grid
                label = ("streaming fused: UNBUILDABLE — stream is "
                         "guard-frame only (the run refuses before "
                         "allocating)")
            else:
                label = ("streaming fused: UNBUILDABLE for this shape "
                         "(the run refuses before allocating)")
            parts.append((label, 0))
        elif fuse_kind == "padfree":
            # forced pad-free: there is no padded fallback (plan.build
            # raises instead), so never estimate the padded transient
            ok = make_fused_step(stencil, grid, fuse, interpret=True,
                                 periodic=periodic, padfree=True) is not None
            parts.append(
                ("pad-free fused: no pad transient" if ok else
                 "pad-free fused: UNBUILDABLE for this shape (the run "
                 "refuses before allocating)", 0))
        elif fuse_kind == "auto" \
                and prefer_padfree(stencil, grid, batch=batch) \
                and make_fused_step(stencil, grid, fuse,
                                    interpret=True, periodic=periodic,
                                    padfree=True) is not None:
            parts.append(("pad-free fused: no pad transient", 0))
        else:
            parts.append(
                (f"fused pad transient (+{2 * m} z/y) x {nfields}",
                 nfields * padded_b))
    elif fuse and len(local) == 2:
        m = fuse * halo * max(1, len(stencil.phases or ()))
        ly, lx = local
        padded_b = batch * (ly + 2 * m) * lx * itemsize
        parts.append((f"2D fullgrid pad transient (+{2 * m} rows)",
                      nfields * padded_b))
    elif compute == "raw":
        # whole-step raw kernels: the state is its own halo — no transient
        # (callers pass compute="raw" when the run will actually take that
        # path; see plan.budget_args)
        parts.append(("raw whole-step kernel: no pad transient", 0))
    else:
        # jnp pad -> update -> re-pin: one padded copy per halo'd field
        # (exchange-padded under a mesh: +2*halo on each sharded axis)
        pad = 2 * halo
        parts.append(
            (f"pad transient (+{pad} per axis) x {nfields}",
             nfields * batch
             * math.prod(s + pad for s in local) * itemsize))

    subtotal = sum(b for _, b in parts)
    overhead = int(subtotal * _OVERHEAD_FRAC)
    parts.append((f"workspace overhead ({int(_OVERHEAD_FRAC * 100)}%)",
                  overhead))
    return subtotal + overhead, parts


def format_budget(total: int, parts: List[Tuple[str, int]],
                  hbm: int) -> str:
    lines = [f"  {b / 2**30:7.2f} GiB  {label}" for label, b in parts]
    lines.append(f"  {total / 2**30:7.2f} GiB  TOTAL per device "
                 f"(HBM capacity {hbm / 2**30:.2f} GiB)")
    return "\n".join(lines)


def check_budget(
    stencil,
    grid: Sequence[int],
    mesh: Sequence[int] = (),
    fuse: int = 0,
    ensemble: int = 0,
    periodic: bool = False,
    compute: str = "auto",
    fuse_kind: str = "auto",
    hbm_bytes: Optional[int] = None,
    overlap: bool = False,
    pipeline: bool = False,
    exchange: str = "ppermute",
    ensemble_mesh: int = 0,
) -> Tuple[int, List[Tuple[str, int]]]:
    """Raise ValueError with the arithmetic when the run cannot fit.

    Returns the estimate when it fits (callers may log it).
    """
    hbm = hbm_bytes if hbm_bytes is not None else device_hbm_bytes()
    total, parts = estimate_run_bytes(
        stencil, grid, mesh=mesh, fuse=fuse, ensemble=ensemble,
        periodic=periodic, compute=compute, fuse_kind=fuse_kind,
        overlap=overlap, pipeline=pipeline, exchange=exchange,
        ensemble_mesh=ensemble_mesh)
    if total > hbm:
        raise ValueError(
            f"config needs ~{total / 2**30:.2f} GiB per device but HBM is "
            f"{hbm / 2**30:.2f} GiB; refusing before compile. Breakdown:\n"
            + format_budget(total, parts, hbm)
            + "\nLevers: --dtype bfloat16 halves state bytes; a larger "
            "--mesh shrinks the per-device block; --fuse on a "
            f"{'pad-free eligible' if not mesh else 'sharded'} grid avoids "
            "pad transients"
            + ("; --ensemble-mesh spreads the members over more devices"
               if ensemble else "")
            + "; --mem-check warn overrides this guard.")
    return total, parts


def estimate_coupled_bytes(plans, transport: str = "") -> Tuple[int, list]:
    """Per-device HBM estimate for a coupled ``--groups`` run.

    Each group is priced as its own run (:func:`estimate_run_bytes` on
    the group's stencil / local grid / sub-mesh — the group's interior
    step IS the unmodified stepper, so the monolithic model applies
    verbatim; round 23: the group's clause mode tokens flow into
    ``fuse``/``fuse_kind``/``overlap``/``pipeline``, so a fused or
    streamed group is priced exactly like the monolithic run it
    mirrors), plus the interface transients the coupling adds on that
    group's devices.  The two transports stage different tensors:

    * ``device_put`` — the resampled band is built on the SENDER and
      landed wholesale on the receiver: staged send (resampled,
      recv-sized) + band recv per direction.
    * ``collective`` — the RAW sender rows ride the ppermute wire and
      are resampled shard-local on the receiver: raw staged rows
      (send-sized) + the wire transient (one chunk per union device,
      charged once) + band recv per direction.

    Interface transients are charged UNSHARDED per device — an upper
    bound consistent with the coarse-but-conservative contract.

    ``plans`` is a sequence of ``parallel.groups.GroupPlan``.  Returns
    ``(worst_total, [(group_name, total, parts), ...])`` — the worst
    group's devices are the ones the run OOMs on first.
    """
    from ..parallel import groups as groups_lib

    transport = transport or groups_lib.TRANSPORT_BACKEND
    collective = transport == "collective"
    traffic = groups_lib.interface_traffic(plans)
    details = []
    worst = 0
    for g, p in enumerate(plans):
        s = p.spec
        total, parts = estimate_run_bytes(
            p.stencil, p.grid, mesh=p.mesh_shape,
            fuse=s.fuse_k if s.fuse_k > 1 else 0,
            fuse_kind=s.kind or "auto",
            overlap=bool(s.overlap_mode), pipeline=bool(s.pipeline_mode))
        extra: List[Tuple[str, int]] = []

        def _iface(t, send_dir, recv_dir):
            send_b = t[send_dir]["send_bytes"]
            recv_b = t[recv_dir]["recv_bytes"]
            if collective:
                extra.append((f"interface {t['interface']}: raw staged "
                              f"rows ({send_dir})", send_b))
                # wire transient: chunk-sized buffer per union device,
                # charged once on this group's devices (upper bound)
                extra.append((f"interface {t['interface']}: collective "
                              f"wire chunk ({send_dir})", send_b))
                extra.append((f"interface {t['interface']}: band recv "
                              f"({recv_dir})", recv_b))
            else:
                extra.append((f"interface {t['interface']}: staged send "
                              f"({send_dir})", send_b))
                extra.append((f"interface {t['interface']}: band recv "
                              f"({recv_dir})", recv_b))

        if g < len(traffic):  # this group is the low side of interface g
            _iface(traffic[g], "up", "down")
        if g > 0:  # ... and the high side of interface g-1
            _iface(traffic[g - 1], "down", "up")
        parts = list(parts) + extra
        total += sum(b for _, b in extra)
        details.append((p.name, total, parts))
        worst = max(worst, total)
    return worst, details


def check_coupled_budget(plans, hbm_bytes: Optional[int] = None,
                         transport: str = "") -> Tuple[int, list]:
    """The ``check_budget`` analogue for a coupled run: raise ValueError
    with the worst group's arithmetic when any group cannot fit."""
    hbm = hbm_bytes if hbm_bytes is not None else device_hbm_bytes()
    worst, details = estimate_coupled_bytes(plans, transport=transport)
    for name, total, parts in details:
        if total > hbm:
            raise ValueError(
                f"--groups: group {name} needs ~{total / 2**30:.2f} GiB "
                f"per device but HBM is {hbm / 2**30:.2f} GiB; refusing "
                "before compile. Breakdown:\n"
                + format_budget(total, parts, hbm)
                + "\nLevers: a bf16 group dtype halves its state bytes; "
                "a larger per-group :mesh shrinks its block; a smaller "
                ":z fraction shrinks the hot region; --mem-check warn "
                "overrides this guard.")
    return worst, details


def ring_vmem_bytes(slab_shape: Sequence[int], itemsize: int,
                    nslots: int, nchunks: int) -> int:
    """VMEM live bytes of one remote-DMA ring-exchange call under a
    kernel variant's ring geometry (``ops/pallas/remote.py``).

    The kernel stages both ring directions through a send ring AND a
    recv ring of ``nslots`` chunk-sized slots each, so the live set is
    ``2 (dirs) * 2 (send+recv) * nslots * chunk_bytes``.  The variant
    autotuner (policy/autotune.py) validates every swept ring depth /
    chunk-count candidate against this figure and the kernel VMEM limit
    BEFORE any probe runs — a candidate that would overflow VMEM is
    rejected with a named reason, never compiled.
    """
    slab_bytes = math.prod(int(s) for s in slab_shape) * int(itemsize)
    chunk_bytes = slab_bytes // max(1, int(nchunks))
    return 2 * 2 * int(nslots) * chunk_bytes
