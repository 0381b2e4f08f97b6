"""Sharded time step: domain decomposition over the mesh + halo exchange.

TPU-native replacement for the reference's entire distributed layer: the fixed
2-rank, 1-axis, storage-replicated decomposition (rank guards at kernel.cu:76/81,
per-rank driver branches kernel.cu:202/236) becomes an N-D ``NamedSharding``
over an arbitrary mesh with *sharded* storage — each device holds only its
block, which is what lets 4096^3 fp32 (256 GiB) span a slice at all
(SURVEY.md §5.7).

One step = two-pass halo exchange (parallel/halo.py) + local stencil update +
global-frame re-pin.  The same code runs on every shard (single-controller
SPMD) — the reference's duplicated rank-0/rank-1 loops and their as-written
divergence bugs (SURVEY.md §3.3) have no analogue here.
"""

from __future__ import annotations

from typing import Callable, Optional, Sequence

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

def shard_map(f, mesh, in_specs, out_specs, check_vma=True):
    """``jax.shard_map`` with keyword specs.  Every stepper builds through
    this."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check_vma)

from ..driver import frame_mask
from ..ops.stencil import Fields, Stencil
from .halo import exchange_and_pad


def grid_partition_spec(ndim: int, mesh: Mesh) -> P:
    """PartitionSpec mapping grid axis d -> mesh axis named for it (or None)."""
    from .mesh import spatial_axis_names

    names = spatial_axis_names(ndim)
    return P(*[n if n in mesh.shape else None for n in names])


def ensemble_partition_spec(ndim: int, mesh: Mesh) -> P:
    """PartitionSpec for BATCHED fields ``(members, *grid)``: the leading
    member axis is sharded over the ensemble mesh axis when the mesh
    carries one (``mesh.ENSEMBLE_AXIS``), else fully local; grid axes
    exactly as :func:`grid_partition_spec`."""
    from .mesh import ENSEMBLE_AXIS

    sp = grid_partition_spec(ndim, mesh)
    lead = ENSEMBLE_AXIS if ENSEMBLE_AXIS in mesh.shape else None
    return P(lead, *sp)


def ensemble_members_local(mesh: Mesh, ensemble: int) -> int:
    """Members each device holds: ``ensemble / ens-axis shards``.

    The single validation point for the batched steppers: the member
    count must divide over the ensemble mesh axis (and an ensemble mesh
    axis is meaningless without a batched run)."""
    from .mesh import ENSEMBLE_AXIS

    n_shards = int(mesh.shape.get(ENSEMBLE_AXIS, 1))
    if not ensemble:
        if n_shards > 1:
            raise ValueError(
                f"mesh carries a {n_shards}-way ensemble axis but the "
                "run is unbatched (ensemble=0) — drop the axis or pass "
                "ensemble=N")
        return 0
    if int(ensemble) % n_shards:
        raise ValueError(
            f"ensemble={ensemble} not divisible by the ensemble mesh "
            f"axis ({n_shards} shards)")
    return int(ensemble) // n_shards


def shard_fields(fields: Fields, mesh: Mesh, ndim: int,
                 ensemble: bool = False) -> Fields:
    """Place fields on the mesh with the grid decomposition sharding.

    ``ensemble=True``: the fields carry a leading member axis, sharded
    over the ensemble mesh axis when present
    (:func:`ensemble_partition_spec`)."""
    spec = ensemble_partition_spec(ndim, mesh) if ensemble else \
        grid_partition_spec(ndim, mesh)
    sharding = NamedSharding(mesh, spec)
    return tuple(jax.device_put(f, sharding) for f in fields)


def _member_shard_map(fn, mesh, ndim, ensemble, n_in=1, n_out=1):
    """``shard_map`` a per-member local function over the mesh.

    The single batching point of every sharded stepper (round 15): with
    ``ensemble`` the local function — written for ONE member's block —
    is ``jax.vmap``ped over the device's local member axis and the specs
    gain the leading ensemble entry.  vmap's collective batching rule
    folds the member axis INTO each ppermute operand (one collective
    per exchange site regardless of N — the structural pin of
    ``utils/jaxprcheck.assert_ensemble_exchange_invariance``), and its
    ``pallas_call`` rule prepends an explicit batch grid dimension to
    every kernel, so the batched step is the same program the unbatched
    step compiles plus one grid axis — compiled ONCE for all members.
    """
    spec = grid_partition_spec(ndim, mesh)
    if ensemble:
        fn = jax.vmap(fn)
        spec = ensemble_partition_spec(ndim, mesh)
    return shard_map(fn, mesh=mesh, in_specs=(spec,) * n_in,
                     out_specs=spec if n_out == 1 else (spec,) * n_out,
                     check_vma=False)


def _resolve_mesh_axes(ndim: int, mesh: Mesh):
    """(axis_names, counts) for grid axes 0..ndim-1 over ``mesh``.

    ``axis_names[d]`` is the mesh axis decomposing grid axis d (or None);
    ``counts[d]`` its shard count.  Single source for every stepper.
    """
    from .mesh import spatial_axis_names

    names_all = spatial_axis_names(ndim)
    axis_names = tuple(n if n in mesh.shape else None for n in names_all)
    counts = tuple(mesh.shape.get(n, 1) if n else 1 for n in axis_names)
    return axis_names, counts


def _axis_slice(x, d, sl):
    """``x[..., sl, ...]`` with the slice on axis ``d``."""
    idx = [slice(None)] * x.ndim
    idx[d] = sl
    return x[tuple(idx)]


def _attach_pipeline(stepper, prologue, body, interior_step=None):
    """Mark a stepper as slab-carry pipelined and expose the scan hooks.

    ``prologue(fields) -> slabs`` is the seed exchange (run once before a
    scan); ``body(fields, slabs) -> (fields, slabs)`` is one fused pass
    that CONSUMES the carried slabs and ISSUES the next pass's exchange.
    The scan-aware runners in driver.py thread the carry; calling the
    stepper plainly (``stepper(fields)``) runs prologue + one body pass
    and drops the trailing slabs — the same values, no pipelining."""
    stepper._pipeline_active = True
    stepper._pipeline_prologue = prologue
    stepper._pipeline_body = body
    if interior_step is not None:
        stepper._overlap_active = True
        stepper._interior_step = interior_step
    return stepper


def _attach_exchange(step, exchange, transport):
    """Record which exchange transport carries a sharded fused step
    (``_exchange``: 'ppermute' | 'rdma') and, for rdma, the honest
    backend tag (``_rdma_backend``: 'pallas-rdma' | 'interpret-
    emulated') plus the transport itself (``_rdma_transport``, whose
    per-site chunk geometry the costmodel cross-checks read)."""
    step._exchange = exchange
    if transport is not None:
        step._rdma_backend = transport.backend
        step._rdma_transport = transport
    return step


def _attach_overlap(step, interior_step):
    """Wrap a shard_map'd overlap step so tests/tools can reach the
    interior-only computation (``_interior_step``) and detect that the
    split is active.  The interior step is the exact dependency path of
    the overlapped step's bulk update — asserting its jaxpr contains no
    collective-permute proves the exchange overlaps it."""

    def stepper(fields: Fields) -> Fields:
        return step(fields)

    stepper._overlap_active = True
    stepper._interior_step = interior_step
    return stepper


def make_sharded_step(
    stencil: Stencil,
    mesh: Mesh,
    global_shape: Sequence[int],
    periodic: bool = False,
    compute_fn: Optional[Callable[[Fields], Fields]] = None,
    overlap: bool = False,
    ensemble: int = 0,
):
    """Build the SPMD step function for ``stencil`` decomposed over ``mesh``.

    ``compute_fn`` overrides the local block update (padded fields -> interior
    fields); defaults to ``stencil.update``.  This is the hook through which
    Pallas kernels replace the jnp reference ops without touching any of the
    decomposition machinery.

    ``overlap=True`` selects the explicit interior/boundary split — the
    TPU-native re-design of the reference's two-CUDA-stream overlap trick
    (middle kernel on one stream concurrent with the MPI halo wait,
    kernel.cu:209-221; SURVEY.md §7.3.1 option (b)): the bulk update is
    computed from a *locally* padded block with no data dependency on the
    ``ppermute`` results, so XLA's async scheduler can run the collective
    concurrently with it; only the width-``halo`` boundary ring is computed
    from exchanged data and spliced over the bulk result.  With
    ``overlap=False`` (default, option (a)) the whole block update consumes
    the exchanged padding and overlap is left entirely to XLA.

    ``ensemble=N``: the step takes/returns fields with a leading member
    axis (N independent universes), sharded over the mesh's ensemble
    axis when present; the local update is vmapped per member
    (:func:`_member_shard_map`) — one exchange round per site regardless
    of N, one compile for the whole batch.
    """
    ndim = stencil.ndim
    halo = stencil.halo
    ensemble_members_local(mesh, ensemble)
    axis_names, counts = _resolve_mesh_axes(ndim, mesh)
    for d, c in enumerate(counts):
        if global_shape[d] % c:
            raise ValueError(
                f"grid axis {d} ({global_shape[d]}) not divisible by "
                f"mesh axis {axis_names[d]} ({c})"
            )
    local_shape = tuple(g // c for g, c in zip(global_shape, counts))
    if any(ls < halo for ls in local_shape):
        raise ValueError(
            f"local block {local_shape} smaller than halo {halo}"
        )
    if stencil.phases:
        if compute_fn is not None:
            raise ValueError(
                f"{stencil.name} is multi-phase; compute_fn unsupported")
        if overlap:
            raise ValueError(
                f"{stencil.name} is multi-phase; overlap split unsupported")
    if stencil.parity_sensitive:
        bad = [d for d, c in enumerate(counts)
               if c > 1 and local_shape[d] % 2]
        if bad:
            raise ValueError(
                f"{stencil.name} is parity-sensitive (red-black coloring): "
                f"sharded axes {bad} have odd per-shard extents "
                f"{[local_shape[d] for d in bad]}, which would flip colors "
                f"across shards — use even per-axis block sizes")
        if periodic and any(g % 2 for g in global_shape):
            raise ValueError(
                f"{stencil.name} is parity-sensitive: periodic wrap over "
                f"odd extents {tuple(global_shape)} makes the coloring "
                f"inconsistent")
    update_fns = stencil.phases or (compute_fn or stencil.update,)

    sharded_axes = [d for d, c in enumerate(counts) if c > 1]
    no_names = (None,) * ndim

    def _axis_slice(x, d, sl):
        idx = [slice(None)] * x.ndim
        idx[d] = sl
        return x[tuple(idx)]

    def _ring_update(update, padded, fields, d, lo: bool):
        """Update of the width-halo boundary ring at face (d, lo/hi)."""
        slabs = []
        for pf, f, fh in zip(padded, fields, stencil.field_halos):
            if fh == 0:
                sl = slice(0, halo) if lo else slice(f.shape[d] - halo, None)
                slabs.append(_axis_slice(f, d, sl))
            else:
                sl = slice(0, 3 * fh) if lo else slice(pf.shape[d] - 3 * fh, None)
                slabs.append(_axis_slice(pf, d, sl))
        return update(tuple(slabs))

    def one_pass(fields: Fields, update) -> Fields:
        padded = tuple(
            exchange_and_pad(f, axis_names, counts, fh, bc, periodic)
            for f, bc, fh in zip(
                fields, stencil.bc_value, stencil.field_halos)
        )
        if overlap and sharded_axes:
            # Bulk update from LOCAL padding only — independent of ppermute,
            # so XLA can overlap the exchange with it (the reference's
            # middle-stream / border-stream split, kernel.cu:209-221).
            with jax.named_scope("interior_update"):
                local_padded = tuple(
                    exchange_and_pad(f, no_names, (1,) * ndim, fh, bc,
                                     periodic)
                    for f, bc, fh in zip(
                        fields, stencil.bc_value, stencil.field_halos)
                )
                bulk = list(update(local_padded))
            with jax.named_scope("boundary_update"):
                for d in sharded_axes:
                    ring_lo = _ring_update(update, padded, fields, d, True)
                    ring_hi = _ring_update(update, padded, fields, d, False)
                    for i in range(len(bulk)):
                        if stencil.carry_map[i] is not None:
                            continue
                        n_d = bulk[i].shape[d]
                        bulk[i] = bulk[i].at[
                            (slice(None),) * d + (slice(0, halo),)
                        ].set(ring_lo[i])
                        bulk[i] = bulk[i].at[
                            (slice(None),) * d + (slice(n_d - halo, None),)
                        ].set(ring_hi[i])
            new = tuple(bulk)
        else:
            with jax.named_scope("stencil_update"):
                new = update(padded)
        mask = None
        out = []
        for i, nf in enumerate(new):
            j = stencil.carry_map[i]
            if j is not None:
                out.append(fields[j])  # verbatim carry: no compute, no copy
            elif periodic or not stencil.mask_fields[i]:
                out.append(nf)
            else:
                if mask is None:
                    offsets = tuple(
                        lax.axis_index(n) * ls if n else 0
                        for n, ls in zip(axis_names, local_shape)
                    )
                    mask = frame_mask(local_shape, global_shape, offsets, halo)
                out.append(jnp.where(mask, fields[i], nf))
        return tuple(out)

    def local_step(fields: Fields) -> Fields:
        # One time step = every phase in order, each with its own halo
        # exchange (phase k sees phase k-1's values from neighbor shards —
        # exact red-black sweeps under decomposition).
        for upd in update_fns:
            fields = one_pass(fields, upd)
        return fields

    # check_vma=False: pallas_call outputs carry no varying-mesh-axes
    # annotation, which the default vma check rejects inside shard_map.
    step = _member_shard_map(local_step, mesh, ndim, ensemble)
    step._ensemble = int(ensemble)
    return step


def make_sharded_fused_step(
    stencil: Stencil,
    mesh: Mesh,
    global_shape: Sequence[int],
    k: int,
    interpret: Optional[bool] = None,
    periodic: bool = False,
    padfree: Optional[bool] = None,
    kind: Optional[str] = None,
    overlap: bool = False,
    pipeline: bool = False,
    exchange: Optional[str] = None,
    ensemble: int = 0,
    variant=None,
):
    """Temporal blocking under domain decomposition: k steps per exchange.

    ``ensemble=N`` (round 15): the step takes/returns fields with a
    leading member axis, sharded over the mesh's ensemble axis when
    present (``mesh.ENSEMBLE_AXIS``); every local function — plain,
    overlapped, and the pipeline prologue/body — is vmapped per member
    through :func:`_member_shard_map`, so the exchange-round count per
    pass is independent of N (vmap folds the member axis into each
    ppermute operand) and every Pallas kernel gains one leading batch
    grid dimension.  Composes with overlap, pipeline, and
    ``exchange="rdma"`` on every kind this function hosts.

    The distributed analogue of ``ops.pallas.fused.make_fused_step`` — and
    the configuration the 4096^3 north star actually needs (BASELINE.json
    config 5: too big for one chip AND bandwidth-bound).  One call =

      1. width ``m = k * halo * phases`` halo exchange on the sharded z/y
         axes (phases = 2 for red-black SOR — fused._halo_per_micro; the
         two-pass axis-wise ``ppermute`` scheme, amortized over k steps —
         k x fewer exchanges than stepping singly), local bc-pad on
         unsharded axes;
      2. the fused k-micro-step Pallas kernel on the padded local block.

    The global guard frame is pinned every micro-step from a frame mask
    derived IN-KERNEL: the shard's global origin (a traced axis_index,
    invisible to BlockSpec index_maps) is handed to the kernel as an SMEM
    (2,) scalar input, and the kernel combines it with program ids + the
    static global shape.  Round 3 streamed a whole padded mask ARRAY per
    step instead — a full extra input's worth of HBM traffic and, at the
    4096^3 scale, ~4 GiB of per-device live bytes, both now gone.

    Constraints (returns None when unmet, callers fall back):
      * 3D stencil with a fused kernel (fused_supported);
      * the lane axis x (grid axis 2) unsharded — the kernel's x taps are
        lane rolls of full rows;
      * local z/y extents tileable per ``_pick_tiles`` (multiples of
        ``2*m``, itself a multiple of the dtype's sublane tile —
        8 for f32, 16 for bf16: see ``fused._sublane``).

    Every field is exchanged at width ``m`` regardless of
    ``field_halos`` — temporal blocking consumes spatial margin for ALL
    fields (wave's u_prev is read pointwise across the shrinking validity
    window), so the per-field-halo elision that applies to single steps
    does not apply here.

    ``padfree``: hand the exchanged slabs to the kernel as separate
    operands instead of materializing the exchange-padded local block —
    the padded block was the last full-size transient in the 4096^3
    budget.  z-only meshes take the measured z-slab kernels
    (``fused.build_zslab_padfree_call``, wide-X fallback); meshes that
    shard y take the 2-axis kernels (``fused.build_yzslab_padfree_call``,
    wide-X fallback): y slabs + the four two-pass-composed corner
    operands per field, selects on both wall axes — so the balanced
    (surface-to-volume-minimizing) decompositions stop paying the pad
    transient.  ``None`` = auto: pad-free when the padded copies would
    exceed the same HBM threshold the single-chip path uses
    (``prefer_padfree`` on the local block), padded (the measured
    configuration) below it.  ``kind="padfree"`` forces it with NO
    padded fallback (returns None when no pad-free builder tiles the
    shape — a forced kind must never silently run the padded kernel).

    ``kind="stream"`` forces the sliding-window streaming kernel
    (ops/pallas/streamfused.py, any z/y mesh, guard-frame): slab
    operands like the z-slab kernels, but every core plane is DMA'd once
    per pass — the projected config-5 winner, pending real-chip
    measurement (auto policy unchanged until then).  Meshes that shard
    y take the 2-axis variant (``build_stream_2axis_call``: y slabs +
    the four corner pieces spliced into the sliding window), so the
    balanced surface-to-volume decompositions use the same kernel
    class; z-only meshes keep the measured z-slab variant.

    ``overlap=True`` selects the communication-overlapped split — the
    temporal-blocked analogue of ``make_sharded_step(overlap=True)`` (the
    reference's middle/border two-stream trick, kernel.cu:209-221): the
    width-``m`` slab ``ppermute``s are issued with NO consumer feeding
    the interior kernel, which runs on a locally-padded (padded kind) or
    dummy-slab (pad-free/stream kinds) block and is valid everywhere
    ``>= m`` from a sharded face; the width-``2m`` boundary shells are
    then computed from the exchanged slabs + a ``3m``-deep local strip by
    slab-shaped instances of the same fused kernel
    (``fused.build_overlap_shell_calls``, origin scalars offset so the
    in-kernel frame/parity stay exact) and spliced over the interior.
    Values are unchanged (bit-exact int, allclose float — the micro-step
    arithmetic is elementwise rolls, invariant to the window split);
    only the dependency structure moves, so XLA can schedule the ICI
    transfer concurrently with the interior kernel.  Falls back to the
    plain exchange-then-compute step when the local geometry cannot host
    the split (local extent < 3m on a sharded axis); the returned step
    carries ``_overlap_active=True`` and an ``_interior_step`` attribute
    (the interior's exact dependency path, for jaxpr inspection) when
    the split is live.

    ``exchange="rdma"`` replaces every XLA-level ``ppermute`` of the
    exchange with the IN-KERNEL remote-DMA ring exchange
    (``ops/pallas/remote.py`` via ``halo.RdmaTransport``): each slab is
    staged chunk-by-chunk through a double-buffered VMEM ring and
    pushed into the neighbor's recv ring by ``make_async_remote_copy``
    under send/recv DMA semaphores, with a barrier semaphore at pass
    start for neighbor-readiness — exchange latency becomes per-chunk,
    no XLA collective exists in the step (gated by
    ``utils/jaxprcheck.assert_rdma_step_structure``), and the budget
    model drops the HBM slab-transient terms.  Hosted by the streaming
    kernel family only (``kind="stream"``, z-only AND 2-axis meshes,
    f32 and bf16); it COMPOSES with ``overlap=True`` and
    ``pipeline=True``; a forced mode never silently falls back — other
    kinds, periodic wrap, and 2D grids raise with the reason.  Values
    are bit-exact vs the ppermute schedule (the ring carries the same
    bytes; equivalence pinned in interpret mode by
    tests/test_rdma_exchange.py).

    ``pipeline=True`` selects the CROSS-PASS pipelined exchange — the
    slab-carry scan: instead of issuing each pass's width-``m`` exchange
    at pass start (where only that pass's own interior can hide it), the
    exchanged slabs ride the ``lax.scan`` carry, seeded by one prologue
    exchange before the scan.  Each scan body consumes the carried slabs
    and issues the NEXT pass's exchange from this pass's output borders;
    composed with ``overlap=True`` those borders are read from the
    boundary SHELL outputs — which never touch the interior kernel — so
    the ``ppermute`` feeding pass i+1 is independent of interior(i) in
    BOTH directions and XLA gets an entire interior pass to hide each
    exchange behind (the strong-scaling fix: when the interior shrinks
    faster than the faces, the shell-to-splice tail of a single pass no
    longer bounds the hideable window).  Values are unchanged (the slabs
    carry the same bytes the per-pass exchange would fetch — bit-exact
    vs ``pipeline=False``).  Only the slab-operand kinds host it (the
    slabs must be separate kernel operands to ride the carry):
    ``kind='padfree'``/``'stream'`` or an auto-pad-free local block —
    the exchange-padded kernel raises, as does ``periodic=True`` (the
    wrap slabs of an unsharded wall axis would be borders of the spliced
    output, an interior dependency); a requested pipeline NEVER silently
    falls back.  The returned stepper exposes ``_pipeline_active`` plus
    ``_pipeline_prologue``/``_pipeline_body`` (the scan hooks driver.py
    threads); the prologue runs once per scan, and the final pass's
    in-flight slabs are dropped (one epilogue exchange of waste).
    """
    from ..ops.pallas.fused import (
        build_fused_call,
        build_zslab_padfree_call,
        fused_supported,
        prefer_padfree,
    )

    ndim = stencil.ndim
    if kind not in (None, "stream", "padfree"):
        # a typo'd or unsupported kind must not silently measure the
        # auto-selected kernel under the wrong label
        raise ValueError(f"unknown sharded fused kind {kind!r} "
                         "(None=auto, 'stream', 'padfree')")
    exchange = exchange or "ppermute"
    if exchange not in ("ppermute", "rdma"):
        # same contract as a typo'd kind: never measure the default
        # transport under an unknown exchange label
        raise ValueError(f"unknown exchange mode {exchange!r} "
                         "('ppermute' or 'rdma')")
    if exchange == "rdma":
        # a forced exchange mode never silently falls back
        if periodic:
            raise ValueError(
                "exchange='rdma' is guard-frame only (the streaming "
                "kernels that host it have no periodic wrap path) — "
                "drop --periodic or use --exchange ppermute")
        if kind != "stream":
            raise ValueError(
                "exchange='rdma' rides the streaming kernel family "
                "(the VMEM-ring kernels the remote DMA feeds): force "
                "--fuse-kind stream, or use --exchange ppermute for "
                f"kind={kind!r}")
    if variant is not None:
        # Sharded kernel variants (policy/autotune.py) ride the streaming
        # kernel family only — the swept constants (ring depth, chunk
        # geometry, strip shape) are streaming/rdma kernel knobs, and a
        # forced variant never silently runs the default-constant kernel.
        if getattr(variant, "family", "") == "tiled":
            raise ValueError(
                f"kernel variant {variant.id!r} sweeps the unsharded "
                "padded-window kernel's tiles; sharded runs have no "
                "tiled kind (drop --mesh or pick a stream-family "
                "variant)")
        if kind != "stream":
            raise ValueError(
                f"kernel variant {variant.id!r} rides the streaming "
                "kernel family: force --fuse-kind stream (or drop "
                f"--kernel-variant for kind={kind!r})")
        if variant.family == "rdma" and exchange != "rdma":
            raise ValueError(
                f"kernel variant {variant.id!r} sweeps the remote-DMA "
                "ring constants and needs --exchange rdma (or pick a "
                "stream-family variant)")
    if pipeline and periodic:
        # A requested pipeline must never silently fall back (the forced-
        # kind contract): periodic cannot host the slab-carry scan — the
        # wrap slabs of an unsharded wall axis are border rows of the
        # SPLICED step output, i.e. an interior(i) dependency, so the
        # next-pass exchange could not be issued a full interior pass
        # ahead of its consumer.
        raise ValueError(
            "pipeline=True is guard-frame only: under periodic wrap the "
            "unsharded-axis slabs derive from the spliced step output "
            "(an interior dependency), which breaks the one-pass-ahead "
            "exchange the slab-carry scan promises — drop --pipeline "
            "for periodic meshes")
    if ndim != 3 or not fused_supported(stencil):
        return None
    ensemble_members_local(mesh, ensemble)
    axis_names, counts = _resolve_mesh_axes(ndim, mesh)
    if counts[2] > 1:
        return None  # lane axis must stay whole (in-kernel lane rolls)
    if any(g % c for g, c in zip(global_shape, counts)):
        return None
    local_shape = tuple(g // c for g, c in zip(global_shape, counts))

    z_only = counts[1] == 1
    if kind == "stream":
        # forced streaming (sliding-window manual DMA), guard-frame —
        # the measured-policy candidate for config 5 (the wide-X
        # kernel's 4.5x read amplification vs streaming's ~1.13x).
        # z-only meshes take the measured z-slab variant; meshes that
        # shard y take the 2-axis variant (y-slab + corner operands
        # spliced into the sliding window), so the balanced
        # surface-to-volume decompositions no longer forfeit the
        # lowest-traffic kernel class.
        from ..ops.pallas.streamfused import build_stream_sharded_call

        if not z_only:
            return _make_yzslab_padfree_step(
                stencil, mesh, global_shape, local_shape, axis_names,
                counts, k, interpret, periodic, overlap=overlap,
                stream=True, pipeline=pipeline, exchange=exchange,
                ensemble=ensemble, variant=variant)
        return _make_zslab_padfree_step(
            stencil, mesh, global_shape, local_shape, axis_names, counts,
            k, build_stream_sharded_call, (1, 1), interpret, periodic,
            overlap=overlap, pipeline=pipeline, exchange=exchange,
            ensemble=ensemble, variant=variant)
    forced_padfree = kind == "padfree"
    if forced_padfree:
        padfree = True
    if padfree is None:
        padfree = prefer_padfree(stencil, local_shape)
    if pipeline and not padfree:
        # never silently pipeline the exchange-padded kernel (it has no
        # slab operands for the carry to feed) — the caller either forces
        # a slab-operand kind or drops the pipeline
        raise ValueError(
            "pipeline=True rides the slab-operand kinds: the exchanged "
            "slabs must be separate kernel operands to travel the scan "
            "carry, and the exchange-padded kernel has none — force "
            "--fuse-kind padfree or stream (or use a pad-free-eligible "
            "local block)")
    if padfree:
        if z_only:
            step = _make_zslab_padfree_step(
                stencil, mesh, global_shape, local_shape, axis_names,
                counts, k, build_zslab_padfree_call, (9, 3), interpret,
                periodic, overlap=overlap, pipeline=pipeline,
                ensemble=ensemble)
            if step is None:
                # whole-row windows exceed VMEM (wide X x multi-field):
                # the wide-X kernel windows the lane axis too
                from ..ops.pallas.fused import build_zslab_xwin_call

                step = _make_zslab_padfree_step(
                    stencil, mesh, global_shape, local_shape, axis_names,
                    counts, k, build_zslab_xwin_call, (27, 9), interpret,
                    periodic, overlap=overlap, pipeline=pipeline,
                    ensemble=ensemble)
        else:
            # y (or y+z) sharded: the 2-axis slab-operand kernels — y
            # slabs + two-pass-composed corner operands, selects on both
            # wall axes; 2D meshes no longer pay the pad transient
            step = _make_yzslab_padfree_step(
                stencil, mesh, global_shape, local_shape, axis_names,
                counts, k, interpret, periodic, overlap=overlap,
                pipeline=pipeline, ensemble=ensemble)
        if step is not None:
            return step
        if forced_padfree:
            # a FORCED kind must never silently measure the padded
            # kernel under a pad-free label: callers (plan.build) raise
            return None
        if pipeline:
            # a requested pipeline must never silently run the padded
            # kernel either — same contract as a forced kind
            raise ValueError(
                "pipeline=True: no slab-operand kernel tiles this local "
                "block, and the exchange-padded fallback cannot host "
                "the slab-carry scan — change k/mesh/shape or drop "
                "--pipeline")
        # the pad-free builders declined: fall through to the padded
        # kernel rather than turning a previously-working config into None
    # Periodic keeps frame identically False (no origins needed): wrap
    # halos arrive via the exchange, and parity stays globally consistent
    # because shard origins/extents are even (alignment gates).  The
    # guard-frame case passes the global shape so the kernel derives the
    # frame from the origin scalars.
    gshape = tuple(int(g) for g in global_shape)
    built = build_fused_call(
        stencil, local_shape, k, interpret=interpret,
        sharded_global=None if periodic else gshape, periodic=periodic)
    if built is None:
        return None
    call, m, nfields = built
    # (one-shard-neighbor invariant — a width-m slab must come from a single
    # neighbor — is already guaranteed: _pick_tiles only accepts local z/y
    # extents divisible by tiles that are multiples of 2*m)
    sharded_axes = [d for d in (0, 1) if counts[d] > 1]

    shells = None
    if overlap and sharded_axes:
        from ..ops.pallas.fused import build_overlap_shell_calls

        shells = build_overlap_shell_calls(
            stencil, local_shape, gshape, k, sharded_axes,
            interpret=interpret, periodic=periodic)

    def _origins():
        # this shard's global (z, y) origin of the UNPADDED block —
        # the kernel derives the frame mask from these scalars
        return jnp.array([
            lax.axis_index(axis_names[d]) * local_shape[d]
            if axis_names[d] else 0
            for d in (0, 1)], dtype=jnp.int32)

    def local_step(fields: Fields) -> Fields:
        from .halo import exchange_pad_axis

        padded = []
        for f, bc in zip(fields, stencil.bc_value):
            for d in (0, 1):
                f = exchange_pad_axis(
                    f, d, axis_names[d], counts[d], m, bc,
                    periodic=periodic)
            padded.append(f)
        args = [p for p in padded for _ in range(4)]
        if not periodic:
            args = [_origins()] + args
        return tuple(call(*args))

    if shells is None:
        step = _member_shard_map(local_step, mesh, ndim, ensemble)
        step._ensemble = int(ensemble)
        return step

    def local_interior(fields: Fields):
        # LOCAL bc/wrap pad only — no ppermute anywhere on this path, so
        # XLA can run the exchange concurrently with this kernel.  Valid
        # everywhere >= m from a sharded face; the pad rows feeding the
        # rest are overwritten by the shells.
        from .halo import exchange_pad_axis

        local_padded = []
        for f, bc in zip(fields, stencil.bc_value):
            for d in (0, 1):
                f = exchange_pad_axis(f, d, None, 1, m, bc,
                                      periodic=periodic)
            local_padded.append(f)
        args = [p for p in local_padded for _ in range(4)]
        if not periodic:
            args = [_origins()] + args
        return tuple(call(*args))

    w = 2 * m

    def local_step_overlap(fields: Fields) -> Fields:
        from .halo import exchange_pad_axis

        with jax.named_scope("halo_exchange"):
            # issued first, consumed only by the shell calls below
            padded = []
            for f, bc in zip(fields, stencil.bc_value):
                for d in (0, 1):
                    f = exchange_pad_axis(
                        f, d, axis_names[d], counts[d], m, bc,
                        periodic=periodic)
                padded.append(f)
        with jax.named_scope("interior_update"):
            out = list(local_interior(fields))
        with jax.named_scope("boundary_update"):
            origins = None if periodic else _origins()
            for d in sharded_axes:
                L = local_shape[d]
                for lo in (True, False):
                    # padded strip spanning global rows [o-m, o+3m) of
                    # axis d, where o is the shell core's origin — the
                    # exchanged slab + the 3m-deep local strip, with the
                    # OTHER axis's (exchanged or local) pad attached
                    strips = [
                        _axis_slice(p, d, slice(0, 2 * w) if lo
                                    else slice(p.shape[d] - 2 * w, None))
                        for p in padded
                    ]
                    args = [s for s in strips for _ in range(4)]
                    if not periodic:
                        off = [0, 0]
                        off[d] = 0 if lo else L - w
                        args = [origins + jnp.array(off, jnp.int32)] + args
                    shell_out = shells[d](*args)
                    sl = slice(0, w) if lo else slice(L - w, None)
                    for i in range(nfields):
                        out[i] = out[i].at[
                            (slice(None),) * d + (sl,)].set(shell_out[i])
        return tuple(out)

    step = _attach_overlap(
        _member_shard_map(local_step_overlap, mesh, ndim, ensemble),
        _member_shard_map(local_interior, mesh, ndim, ensemble),
    )
    step._ensemble = int(ensemble)
    return step


def _make_zslab_padfree_step(stencil, mesh, global_shape, local_shape,
                             axis_names, counts, k, build_call, layout,
                             interpret, periodic, overlap=False,
                             pipeline=False, exchange="ppermute",
                             ensemble=0, variant=None):
    """shard_map wrapper for the z-slab pad-free fused kernels: width-m
    slab exchange (no concatenation, no padded copy), slabs handed to the
    kernel as operands, frame from SMEM origin scalars.  ``layout`` is
    (core views, slab views) per field — (9, 3) for the whole-row kernel,
    (27, 9) for the wide-X variant, (1, 1) for the streaming kernel.

    ``overlap=True``: the exchanged slabs feed ONLY the width-``2m``
    boundary-shell calls; the kernel's own slab operands are replaced by
    LOCAL dummies (bc fill / local wrap — no ppermute dependency), so its
    output is the overlap interior, valid ``>= m`` from the shard's z
    faces, and the shells are spliced over it.  No exchange-padded copy
    is materialized in either mode (the kinds exist for the 4096^3
    budget); falls back to the plain step when the shell geometry does
    not fit (local z < 3m).

    ``pipeline=True``: the slab-carry scan (make_sharded_fused_step
    docstring) — the exchanged slabs become the scan carry; the body
    consumes them and issues the next pass's exchange from this pass's
    output border rows (with ``overlap`` those rows are read from the
    SHELL outputs, never the spliced interior)."""
    from ..ops.pallas.fused import _halo_per_micro

    if pipeline and periodic:  # guarded again for direct callers
        raise ValueError("pipeline=True is guard-frame only")

    n_core, n_slab = layout
    m = k * _halo_per_micro(stencil)
    gshape = tuple(int(g) for g in global_shape)
    build_kw = {}
    if variant is not None and variant.tiles:
        # stream-family block-shape override — only the streaming builder
        # (layout (1, 1)) accepts tiles; other layouts never see variants
        # (make_sharded_fused_step rejects them before dispatch)
        build_kw["tiles"] = variant.tiles
    if variant is not None and layout == (1, 1):
        if getattr(variant, "margin", 0):
            build_kw["margin"] = variant.margin
        if getattr(variant, "order", ""):
            build_kw["order"] = variant.order
    built = build_call(stencil, local_shape, gshape, k,
                       interpret=interpret, periodic=periodic, **build_kw)
    if built is None:
        return None
    call, m_built, nfields = built
    assert m_built == m
    # introspection label for tests/tools: which slab-operand kernel
    # actually carries the step (the builders silently fall back)
    kind_name = {(9, 3): "zslab", (27, 9): "zslab_xwin",
                 (1, 1): "stream"}[layout]

    transport = None
    if exchange == "rdma":
        from ..ops.pallas.kernels import _interpret_default
        from .halo import RdmaTransport

        transport = RdmaTransport(
            mesh, _interpret_default() if interpret is None
            else bool(interpret),
            nslots=variant.nslots if variant is not None
            and variant.family == "rdma" else 0,
            prefer_nc=variant.prefer_nc if variant is not None
            and variant.family == "rdma" else 0)

    shells = None
    if overlap and counts[0] > 1:
        from ..ops.pallas.fused import build_overlap_shell_calls

        shells = build_overlap_shell_calls(
            stencil, local_shape, gshape, k, (0,),
            interpret=interpret, periodic=periodic)

    def _origins():
        return jnp.array([
            lax.axis_index(axis_names[0]) * local_shape[0]
            if axis_names[0] else 0, 0], dtype=jnp.int32)

    def local_step(fields: Fields) -> Fields:
        from .halo import exchange_slabs_axis

        args = []
        for f, bc in zip(fields, stencil.bc_value):
            lo, hi = exchange_slabs_axis(
                f, 0, axis_names[0], counts[0], m, bc, periodic=periodic,
                transport=transport)
            args += [f] * n_core + [lo] * n_slab + [hi] * n_slab
        return tuple(call(_origins(), *args))

    if shells is None and not pipeline:
        step = _member_shard_map(local_step, mesh, 3, ensemble)
        step._padfree_kind = kind_name
        step._ensemble = int(ensemble)
        step._kernel_variant = variant.id if variant is not None else ""
        return _attach_exchange(step, exchange, transport)

    Lz = local_shape[0]
    w = 2 * m

    def local_interior(fields: Fields):
        # the kernel's slab operands are LOCAL dummies (what a 1-shard
        # exchange would produce): no ppermute on this path; its edge-m
        # output rows are garbage and overwritten by the shells
        from .halo import exchange_slabs_axis

        args = []
        for f, bc in zip(fields, stencil.bc_value):
            dlo, dhi = exchange_slabs_axis(f, 0, None, 1, m, bc,
                                           periodic=periodic)
            args += [f] * n_core + [dlo] * n_slab + [dhi] * n_slab
        return tuple(call(_origins(), *args))

    if pipeline:
        # ---- slab-carry pipelined variants: the body consumes THIS
        # pass's carried slabs and issues the NEXT pass's exchange.
        from .halo import (
            exchange_pad_axis,
            exchange_slabs_axis,
            exchange_slabs_from_borders,
        )

        def local_prologue(fields: Fields):
            with jax.named_scope("pipeline_prologue_exchange"):
                return tuple(
                    exchange_slabs_axis(f, 0, axis_names[0], counts[0],
                                        m, bc, periodic=periodic,
                                        transport=transport)
                    for f, bc in zip(fields, stencil.bc_value))

        if shells is None:
            def local_body(fields: Fields, slabs):
                args = []
                for f, (lo, hi) in zip(fields, slabs):
                    args += [f] * n_core + [lo] * n_slab + [hi] * n_slab
                out = tuple(call(_origins(), *args))
                with jax.named_scope("next_pass_exchange"):
                    new_slabs = tuple(
                        exchange_slabs_axis(o, 0, axis_names[0],
                                            counts[0], m, bc,
                                            periodic=periodic,
                                            transport=transport)
                        for o, bc in zip(out, stencil.bc_value))
                return out, new_slabs
        else:
            def local_body(fields: Fields, slabs):
                with jax.named_scope("interior_update"):
                    out = list(local_interior(fields))
                with jax.named_scope("boundary_update"):
                    lo_args, hi_args = [], []
                    for (lo, hi), f, bc in zip(slabs, fields,
                                               stencil.bc_value):
                        strip_lo = jnp.concatenate(
                            [lo, _axis_slice(f, 0, slice(0, 3 * m))],
                            axis=0)
                        strip_hi = jnp.concatenate(
                            [_axis_slice(f, 0, slice(Lz - 3 * m, None)),
                             hi], axis=0)
                        strip_lo = exchange_pad_axis(
                            strip_lo, 1, None, 1, m, bc,
                            periodic=periodic)
                        strip_hi = exchange_pad_axis(
                            strip_hi, 1, None, 1, m, bc,
                            periodic=periodic)
                        lo_args += [strip_lo] * 4
                        hi_args += [strip_hi] * 4
                    org = _origins()
                    lo_out = shells[0](org, *lo_args)
                    hi_out = shells[0](
                        org + jnp.array([Lz - w, 0], jnp.int32),
                        *hi_args)
                    for i in range(nfields):
                        out[i] = out[i].at[:w].set(lo_out[i])
                        out[i] = out[i].at[Lz - w:].set(hi_out[i])
                with jax.named_scope("next_pass_exchange"):
                    # issued from the SHELL outputs only (the output's
                    # border-m rows ARE shell rows) — never from the
                    # spliced array, whose producer chain includes the
                    # interior kernel: the ppermute feeding pass i+1 is
                    # independent of interior(i), so XLA can run it
                    # across the whole next interior pass
                    new_slabs = tuple(
                        exchange_slabs_from_borders(
                            lo_out[i][:m], hi_out[i][w - m:], 0,
                            axis_names[0], counts[0], m, bc,
                            periodic=periodic, transport=transport)
                        for i, bc in enumerate(stencil.bc_value))
                return tuple(out), new_slabs

        prologue_sm = _member_shard_map(local_prologue, mesh, 3, ensemble)
        body_sm = _member_shard_map(local_body, mesh, 3, ensemble,
                                    n_in=2, n_out=2)

        def stepper(fields: Fields) -> Fields:
            return body_sm(fields, prologue_sm(fields))[0]

        interior_sm = None
        if shells is not None:
            interior_sm = _member_shard_map(local_interior, mesh, 3,
                                            ensemble)
        step = _attach_pipeline(stepper, prologue_sm, body_sm,
                                interior_step=interior_sm)
        step._padfree_kind = kind_name
        step._ensemble = int(ensemble)
        step._kernel_variant = variant.id if variant is not None else ""
        return _attach_exchange(step, exchange, transport)

    def local_step_overlap(fields: Fields) -> Fields:
        from .halo import exchange_pad_axis, exchange_slabs_axis

        with jax.named_scope("halo_exchange"):
            slabs = [
                exchange_slabs_axis(f, 0, axis_names[0], counts[0], m, bc,
                                    periodic=periodic,
                                    transport=transport)
                for f, bc in zip(fields, stencil.bc_value)
            ]
        with jax.named_scope("interior_update"):
            out = list(local_interior(fields))
        with jax.named_scope("boundary_update"):
            lo_args, hi_args = [], []
            for (lo, hi), f, bc in zip(slabs, fields, stencil.bc_value):
                strip_lo = jnp.concatenate(
                    [lo, _axis_slice(f, 0, slice(0, 3 * m))], axis=0)
                strip_hi = jnp.concatenate(
                    [_axis_slice(f, 0, slice(Lz - 3 * m, None)), hi],
                    axis=0)
                # y is whole on every shard (z-only kinds): local pad
                strip_lo = exchange_pad_axis(strip_lo, 1, None, 1, m, bc,
                                             periodic=periodic)
                strip_hi = exchange_pad_axis(strip_hi, 1, None, 1, m, bc,
                                             periodic=periodic)
                lo_args += [strip_lo] * 4
                hi_args += [strip_hi] * 4
            if periodic:
                lo_out = shells[0](*lo_args)
                hi_out = shells[0](*hi_args)
            else:
                org = _origins()
                lo_out = shells[0](org, *lo_args)
                hi_out = shells[0](
                    org + jnp.array([Lz - w, 0], jnp.int32), *hi_args)
            for i in range(nfields):
                out[i] = out[i].at[:w].set(lo_out[i])
                out[i] = out[i].at[Lz - w:].set(hi_out[i])
        return tuple(out)

    step = _attach_overlap(
        _member_shard_map(local_step_overlap, mesh, 3, ensemble),
        _member_shard_map(local_interior, mesh, 3, ensemble),
    )
    step._padfree_kind = kind_name
    step._ensemble = int(ensemble)
    step._kernel_variant = variant.id if variant is not None else ""
    return _attach_exchange(step, exchange, transport)


def _make_yzslab_padfree_step(stencil, mesh, global_shape, local_shape,
                              axis_names, counts, k, interpret, periodic,
                              overlap=False, stream=False,
                              pipeline=False, exchange="ppermute",
                              ensemble=0, variant=None):
    """shard_map wrapper for the 2-AXIS pad-free fused kernels
    (y-sharded and y+z-sharded meshes): width-m slab exchange on both
    wall axes plus the four corner pieces by two-pass composition
    (``halo.exchange_slabs_2axis``), everything handed to the kernel as
    operands — no exchange-padded copy on 2-axis meshes (the transient
    the padded fallback used to pay, ~4 GiB-class for config 5 at
    4x4x4).  Falls back whole-row -> wide-X; an unsharded axis (z on a
    (1, ny, 1) mesh) receives local bc/wrap dummy slabs from the same
    exchange helper, so one wrapper serves every non-z-only mesh shape.

    ``stream=True`` routes the SAME operand set through the 2-axis
    sliding-window streaming kernel
    (``streamfused.build_stream_2axis_call``) instead of the tiled
    pad-free kernels — slabs and corners at their natural widths, the
    call aligns them internally; no wide-X fallback chain exists (the
    streaming builder windows the lane axis itself when whole-lane
    strips exceed VMEM), and a decline returns None (a forced kind must
    never silently run a different kernel class).

    ``overlap=True``: the exchanged slabs/corners feed ONLY the
    width-``2m`` boundary-shell calls (one lo+hi pair per sharded axis,
    ``fused.build_overlap_shell_calls``); the kernel's own slab operands
    are replaced by LOCAL dummies, so its output is the overlap
    interior, and the shells — whose input strips are assembled from
    slab + 3m local strip with the OTHER axis's exchanged slab/corner
    values as padding (edge strips included: a z-shell's y tails carry
    genuine corner data) — are spliced over it.  Falls back to the
    plain step when any sharded local extent is < 3m.

    ``pipeline=True``: the slab-carry scan (make_sharded_fused_step
    docstring) on BOTH wall axes — the full slab+corner operand set
    rides the carry; the body issues the next pass's exchange from the
    output border rows (with ``overlap``, read from the z/y SHELL
    outputs), corners by the same two-pass composition
    (``halo.exchange_slabs_2axis_from_borders``)."""
    from ..ops.pallas.fused import (
        _halo_per_micro,
        build_yzslab_padfree_call,
        build_yzslab_xwin_call,
    )

    if pipeline and periodic:  # guarded again for direct callers
        raise ValueError("pipeline=True is guard-frame only")

    m = k * _halo_per_micro(stencil)
    gshape = tuple(int(g) for g in global_shape)
    xrep = 1
    if stream:
        from ..ops.pallas.streamfused import build_stream_2axis_call

        kind_name = "stream_yz"
        tiles = (variant.tiles if variant is not None and variant.tiles
                 else None)
        built = build_stream_2axis_call(
            stencil, local_shape, gshape, k, tiles=tiles,
            interpret=interpret, periodic=periodic,
            margin=getattr(variant, "margin", 0) if variant else 0,
            order=getattr(variant, "order", "") if variant else "")
    else:
        kind_name = "yzslab"
        built = build_yzslab_padfree_call(stencil, local_shape, gshape, k,
                                          interpret=interpret,
                                          periodic=periodic)
        if built is None:
            # whole-row windows exceed VMEM (wide X x multi-field):
            # window the lane axis too — each x-position repeats the
            # 25-view group
            built = build_yzslab_xwin_call(stencil, local_shape, gshape,
                                           k, interpret=interpret,
                                           periodic=periodic)
            kind_name, xrep = "yzslab_xwin", 3
    if built is None:
        return None
    call, m_built, nfields = built
    assert m_built == m
    names2 = (axis_names[0], axis_names[1])
    counts2 = (counts[0], counts[1])
    sharded_axes = [d for d in (0, 1) if counts[d] > 1]

    transport = None
    if exchange == "rdma":
        from ..ops.pallas.kernels import _interpret_default
        from .halo import RdmaTransport

        transport = RdmaTransport(
            mesh, _interpret_default() if interpret is None
            else bool(interpret),
            nslots=variant.nslots if variant is not None
            and variant.family == "rdma" else 0,
            prefer_nc=variant.prefer_nc if variant is not None
            and variant.family == "rdma" else 0)

    shells = None
    if overlap and sharded_axes:
        from ..ops.pallas.fused import build_overlap_shell_calls

        shells = build_overlap_shell_calls(
            stencil, local_shape, gshape, k, sharded_axes,
            interpret=interpret, periodic=periodic)

    def _origins():
        return jnp.array([
            lax.axis_index(axis_names[d]) * local_shape[d]
            if axis_names[d] else 0
            for d in (0, 1)], dtype=jnp.int32)

    def _dup_y(a):
        # the y-slab/corner operands' sublane extent must be the
        # tile-aligned 2m, not the unaligned m: duplicate along y — the
        # first copy lands on don't-care window cells (see
        # fused._assemble_yz_window), the second on the genuine ones
        return jnp.concatenate([a, a], axis=1)

    def _exchange(fields, names):
        from .halo import exchange_slabs_2axis

        # (the interior-dummy call passes names (None, None): the
        # transport is then never consulted — the unsharded path is a
        # local bc fill on both axes)
        return [exchange_slabs_2axis(f, names, counts2, m, bc,
                                     periodic=periodic,
                                     transport=transport)
                for f, bc in zip(fields, stencil.bc_value)]

    def _kernel_args(fields, ex):
        args = []
        for f, ((zlo, zhi), (ylo, yhi), cs) in zip(fields, ex):
            if stream:
                # natural-width operands: the streaming call aligns the
                # y-facing slabs/corners to wm_a itself
                group = [f, zlo, zhi, ylo, yhi] + list(cs)
            else:
                group = ([f] * 9 + [zlo] * 3 + [zhi] * 3
                         + [_dup_y(ylo)] * 3 + [_dup_y(yhi)] * 3
                         + [_dup_y(c) for c in cs])
            args += group * xrep
        return args

    def local_step(fields: Fields) -> Fields:
        ex = _exchange(fields, names2)
        return tuple(call(_origins(), *_kernel_args(fields, ex)))

    if shells is None and not pipeline:
        step = _member_shard_map(local_step, mesh, 3, ensemble)
        step._padfree_kind = kind_name
        step._ensemble = int(ensemble)
        step._kernel_variant = variant.id if variant is not None else ""
        return _attach_exchange(step, exchange, transport)

    Lz, Ly = local_shape[0], local_shape[1]
    w = 2 * m

    def local_interior(fields: Fields):
        # LOCAL dummy slabs on both axes: no ppermute anywhere on this
        # path; the edge-m output cells are garbage and overwritten by
        # the shells
        ex = _exchange(fields, (None, None))
        return tuple(call(_origins(), *_kernel_args(fields, ex)))

    def _shell_strip(f, ex_f, d, lo):
        """Padded input strip of the axis-``d`` lo/hi boundary shell:
        the exchanged slab + a 3m-deep local strip along ``d``, with the
        OTHER axis's exchanged slab/corner values as the m-wide padding
        (the edge strips the 2-axis split needs for exact corners)."""
        (zlo, zhi), (ylo, yhi), (c_ll, c_lh, c_hl, c_hh) = ex_f
        s3 = 3 * m
        if d == 0:
            if lo:
                mid = jnp.concatenate([zlo, f[:s3]], axis=0)
                left = jnp.concatenate([c_ll, ylo[:s3]], axis=0)
                right = jnp.concatenate([c_lh, yhi[:s3]], axis=0)
            else:
                mid = jnp.concatenate([f[Lz - s3:], zhi], axis=0)
                left = jnp.concatenate([ylo[Lz - s3:], c_hl], axis=0)
                right = jnp.concatenate([yhi[Lz - s3:], c_hh], axis=0)
            return jnp.concatenate([left, mid, right], axis=1)
        if lo:
            mid = jnp.concatenate([ylo, f[:, :s3]], axis=1)
            top = jnp.concatenate([c_ll, zlo[:, :s3]], axis=1)
            bot = jnp.concatenate([c_hl, zhi[:, :s3]], axis=1)
        else:
            mid = jnp.concatenate([f[:, Ly - s3:], yhi], axis=1)
            top = jnp.concatenate([zlo[:, Ly - s3:], c_lh], axis=1)
            bot = jnp.concatenate([zhi[:, Ly - s3:], c_hh], axis=1)
        return jnp.concatenate([top, mid, bot], axis=0)

    if pipeline:
        # ---- slab-carry pipelined variants on both wall axes: the full
        # slab+corner operand set rides the carry.
        from .halo import exchange_slabs_2axis_from_borders

        def local_prologue(fields: Fields):
            with jax.named_scope("pipeline_prologue_exchange"):
                return tuple(_exchange(fields, names2))

        if shells is None:
            def local_body(fields: Fields, slabs):
                out = tuple(call(_origins(),
                                 *_kernel_args(fields, slabs)))
                with jax.named_scope("next_pass_exchange"):
                    new_slabs = tuple(_exchange(out, names2))
                return out, new_slabs
        else:
            def _border_rows(arr_set, i, d, fields):
                """This shard's first/last m OUTPUT rows along axis d,
                read from the SHELL outputs (never the spliced array —
                its producer chain includes the interior kernel).  An
                unsharded axis returns don't-care rows: the from-borders
                exchange substitutes the bc constant without reading
                them (periodic is excluded up front)."""
                if d in sharded_axes:
                    lo = _axis_slice(arr_set[(d, True)][i], d,
                                     slice(0, m))
                    hi = _axis_slice(arr_set[(d, False)][i], d,
                                     slice(w - m, None))
                    return lo, hi
                dummy = _axis_slice(fields[i], d, slice(0, m))
                return dummy, dummy

            def local_body(fields: Fields, slabs):
                with jax.named_scope("interior_update"):
                    out = list(local_interior(fields))
                shell_outs = {}
                with jax.named_scope("boundary_update"):
                    origins = _origins()
                    for d in sharded_axes:
                        L = local_shape[d]
                        for lo in (True, False):
                            strips = [_shell_strip(f, e, d, lo)
                                      for f, e in zip(fields, slabs)]
                            args = [s for s in strips for _ in range(4)]
                            off = [0, 0]
                            off[d] = 0 if lo else L - w
                            args = [origins
                                    + jnp.array(off, jnp.int32)] + args
                            shell_out = shells[d](*args)
                            shell_outs[(d, lo)] = shell_out
                            sl = slice(0, w) if lo else slice(L - w, None)
                            for i in range(nfields):
                                out[i] = out[i].at[
                                    (slice(None),) * d + (sl,)
                                ].set(shell_out[i])
                with jax.named_scope("next_pass_exchange"):
                    new_slabs = []
                    for i, bc in enumerate(stencil.bc_value):
                        z_lo, z_hi = _border_rows(shell_outs, i, 0,
                                                  fields)
                        y_lo, y_hi = _border_rows(shell_outs, i, 1,
                                                  fields)
                        new_slabs.append(
                            exchange_slabs_2axis_from_borders(
                                z_lo, z_hi, y_lo, y_hi, names2, counts2,
                                m, bc, periodic=periodic,
                                transport=transport))
                return tuple(out), tuple(new_slabs)

        prologue_sm = _member_shard_map(local_prologue, mesh, 3, ensemble)
        body_sm = _member_shard_map(local_body, mesh, 3, ensemble,
                                    n_in=2, n_out=2)

        def stepper(fields: Fields) -> Fields:
            return body_sm(fields, prologue_sm(fields))[0]

        interior_sm = None
        if shells is not None:
            interior_sm = _member_shard_map(local_interior, mesh, 3,
                                            ensemble)
        step = _attach_pipeline(stepper, prologue_sm, body_sm,
                                interior_step=interior_sm)
        step._padfree_kind = kind_name
        step._ensemble = int(ensemble)
        step._kernel_variant = variant.id if variant is not None else ""
        return _attach_exchange(step, exchange, transport)

    def local_step_overlap(fields: Fields) -> Fields:
        with jax.named_scope("halo_exchange"):
            # issued first, consumed only by the shell calls below
            ex = _exchange(fields, names2)
        with jax.named_scope("interior_update"):
            out = list(local_interior(fields))
        with jax.named_scope("boundary_update"):
            origins = None if periodic else _origins()
            for d in sharded_axes:
                L = local_shape[d]
                for lo in (True, False):
                    strips = [_shell_strip(f, e, d, lo)
                              for f, e in zip(fields, ex)]
                    args = [s for s in strips for _ in range(4)]
                    if not periodic:
                        off = [0, 0]
                        off[d] = 0 if lo else L - w
                        args = [origins + jnp.array(off, jnp.int32)] + args
                    shell_out = shells[d](*args)
                    sl = slice(0, w) if lo else slice(L - w, None)
                    for i in range(nfields):
                        out[i] = out[i].at[
                            (slice(None),) * d + (sl,)].set(shell_out[i])
        return tuple(out)

    step = _attach_overlap(
        _member_shard_map(local_step_overlap, mesh, 3, ensemble),
        _member_shard_map(local_interior, mesh, 3, ensemble),
    )
    step._padfree_kind = kind_name
    step._ensemble = int(ensemble)
    step._kernel_variant = variant.id if variant is not None else ""
    return _attach_exchange(step, exchange, transport)


def make_sharded_fullgrid_step(
    stencil: Stencil,
    mesh: Mesh,
    global_shape: Sequence[int],
    k: int,
    interpret: Optional[bool] = None,
    periodic: bool = False,
    overlap: bool = False,
    ensemble: int = 0,
):
    """2D temporal blocking under row decomposition: k steps per exchange.

    The 2D analogue of ``make_sharded_fused_step`` — and the TPU
    generalization of the reference's own decomposition (a 1-D row split,
    kernel.cu:76/81): shard the y axis, exchange width ``k*halo`` row
    slabs, then run the whole padded LOCAL block through the
    whole-block-in-VMEM kernel (ops/pallas/fullgrid.py) for k micro-steps
    — one exchange per k generations instead of one per generation.

    Constraints (returns None when unmet): 2D fullgrid family; x (lane)
    axis unsharded; the margin ``m = k * halo * max(1, phases)`` (a full
    red-black micro-step consumes 2*halo of validity) a multiple of the
    dtype's sublane tile (aligned core store); even local extents
    (global==local parity for red-black models, ops/sor.py caveat);
    local rows >= m (halo slabs stay single-neighbor); padded block
    within the VMEM budget.

    ``overlap=True``: communication-overlapped split, exactly the 3D
    scheme of ``make_sharded_fused_step`` in one dimension fewer — the
    width-``m`` row-slab ``ppermute``s feed only two width-``2m``
    shell instances of the same whole-block kernel (origin scalar offset
    per shell), while the interior instance consumes a locally-padded
    block.  Bit-exact vs ``overlap=False`` (the 2D kernel is exact —
    int Life included).  Falls back to the plain step when local rows
    < 3m.
    """
    from ..ops.pallas.fullgrid import build_fullgrid_masked_call

    ndim = stencil.ndim
    if ndim != 2:
        return None
    ensemble_members_local(mesh, ensemble)
    axis_names, counts = _resolve_mesh_axes(ndim, mesh)
    if counts[1] > 1:
        return None  # lane axis must stay whole (in-kernel lane rolls)
    if any(g % c for g, c in zip(global_shape, counts)):
        return None
    # (No parity/odd-extent gate needed for periodic red-black models:
    # the alignment gates in the builder already force even extents.)
    local_shape = tuple(g // c for g, c in zip(global_shape, counts))
    from ..ops.pallas.fullgrid import _halo_per_micro_2d

    # margin per micro-step = halo per PHASE (red-black consumes 2*halo)
    m = k * _halo_per_micro_2d(stencil)
    built = build_fullgrid_masked_call(
        stencil, (local_shape[0] + 2 * m, local_shape[1]), m, k,
        interpret=interpret, periodic=periodic,
        global_shape=global_shape)
    if built is None:
        return None
    call, nfields = built
    assert nfields == stencil.num_fields

    shell_call = None
    if overlap and counts[0] > 1 and local_shape[0] >= 3 * m:
        # width-2m shell instances of the same whole-block kernel: padded
        # extent 4m = the exchanged slab (m) + a 3m-deep local strip
        shell_built = build_fullgrid_masked_call(
            stencil, (4 * m, local_shape[1]), m, k,
            interpret=interpret, periodic=periodic,
            global_shape=global_shape)
        if shell_built is not None:
            shell_call = shell_built[0]

    def _origin(row0):
        return jnp.array([row0], dtype=jnp.int32)

    def _y0():
        return lax.axis_index(axis_names[0]) * local_shape[0] \
            if axis_names[0] else 0

    def local_step(fields: Fields) -> Fields:
        from .halo import exchange_pad_axis

        padded = [
            exchange_pad_axis(f, 0, axis_names[0], counts[0], m, bc,
                              periodic=periodic)
            for f, bc in zip(fields, stencil.bc_value)
        ]
        if periodic:
            # wrapped slabs are real data; the x rolls wrap at the full
            # domain width (x unsharded) — nothing is pinned, no origin
            return tuple(call(*padded))
        # shard's global y-origin of the UNPADDED block, as an SMEM
        # scalar — the kernel derives the frame mask from it
        return tuple(call(_origin(_y0()), *padded))

    if shell_call is None:
        step = _member_shard_map(local_step, mesh, ndim, ensemble)
        step._ensemble = int(ensemble)
        return step

    Ly = local_shape[0]
    w = 2 * m

    def local_interior(fields: Fields):
        # local pad only: no ppermute on the interior's dependency path
        from .halo import exchange_pad_axis

        padded = [
            exchange_pad_axis(f, 0, None, 1, m, bc, periodic=periodic)
            for f, bc in zip(fields, stencil.bc_value)
        ]
        if periodic:
            return tuple(call(*padded))
        return tuple(call(_origin(_y0()), *padded))

    def local_step_overlap(fields: Fields) -> Fields:
        from .halo import exchange_slabs_axis

        with jax.named_scope("halo_exchange"):
            slabs = [
                exchange_slabs_axis(f, 0, axis_names[0], counts[0], m, bc,
                                    periodic=periodic)
                for f, bc in zip(fields, stencil.bc_value)
            ]
        with jax.named_scope("interior_update"):
            out = list(local_interior(fields))
        with jax.named_scope("boundary_update"):
            lo_in = [jnp.concatenate([lo, f[:3 * m]], axis=0)
                     for (lo, _), f in zip(slabs, fields)]
            hi_in = [jnp.concatenate([f[Ly - 3 * m:], hi], axis=0)
                     for (_, hi), f in zip(slabs, fields)]
            if periodic:
                lo_out = shell_call(*lo_in)
                hi_out = shell_call(*hi_in)
            else:
                y0 = _y0()
                lo_out = shell_call(_origin(y0), *lo_in)
                hi_out = shell_call(_origin(y0 + Ly - w), *hi_in)
            for i in range(nfields):
                out[i] = out[i].at[:w].set(lo_out[i])
                out[i] = out[i].at[Ly - w:].set(hi_out[i])
        return tuple(out)

    step = _attach_overlap(
        _member_shard_map(local_step_overlap, mesh, ndim, ensemble),
        _member_shard_map(local_interior, mesh, ndim, ensemble),
    )
    step._ensemble = int(ensemble)
    return step


def make_sharded_temporal_step(
    stencil: Stencil,
    mesh: Mesh,
    global_shape: Sequence[int],
    k: int,
    interpret: Optional[bool] = None,
    periodic: bool = False,
    kind: Optional[str] = None,
    overlap: bool = False,
    pipeline: bool = False,
    exchange: Optional[str] = None,
    ensemble: int = 0,
    variant=None,
):
    """Temporal blocking under decomposition, any dimensionality.

    Dispatches to the whole-local-block kernel for 2D stencils and the
    windowed fused kernel for 3D — the single entry point for callers
    (cli --fuse --mesh, benchmarks/scaling.py --fuse) that should not
    care which kernel shape implements the k-steps-per-exchange strategy.
    Returns None when the (stencil, mesh, shape, k) combination is
    unsupported by the applicable builder.  ``kind="stream"`` (3D, any
    z/y mesh) forces the sliding-window streaming kernel (2-axis
    meshes take the y-slab + corner-operand variant);
    ``kind="padfree"`` (3D, any z/y mesh) forces the slab-operand
    kernels with no padded fallback.
    ``overlap=True`` selects the communication-overlapped interior/
    boundary split in every kind that hosts it (falls back to the plain
    exchange-then-compute step where the geometry declines — check
    ``getattr(step, "_overlap_active", False)``).
    ``pipeline=True`` (3D slab-operand kinds only) selects the
    cross-pass slab-carry scan — a requested pipeline never silently
    falls back: unsupported hosts (2D, periodic, the padded kind)
    raise with the reason.
    ``exchange="rdma"`` (3D streaming kind only) replaces the
    ``ppermute`` exchange with the in-kernel remote-DMA ring — the
    same never-silently-falls-back contract: 2D grids, non-stream
    kinds, and periodic wrap raise with the reason.
    """
    if stencil.ndim == 2:
        if variant is not None:
            raise ValueError(
                "kernel variants are 3D-only: the 2D whole-local-block "
                "stepper has no streaming kind whose constants a "
                "variant could sweep — drop --kernel-variant for 2D "
                "grids")
        if pipeline:
            raise ValueError(
                "pipeline=True is 3D-only: the 2D whole-local-block "
                "stepper has no slab-operand kind to carry the scan — "
                "drop --pipeline for 2D grids")
        if exchange and exchange != "ppermute":
            raise ValueError(
                "exchange='rdma' is 3D-only: the 2D whole-local-block "
                "stepper has no slab-operand streaming kind for the "
                "remote-DMA ring to feed — drop --exchange rdma for "
                "2D grids")
        return None if kind else make_sharded_fullgrid_step(
            stencil, mesh, global_shape, k, interpret=interpret,
            periodic=periodic, overlap=overlap, ensemble=ensemble)
    return make_sharded_fused_step(
        stencil, mesh, global_shape, k, interpret=interpret,
        periodic=periodic, kind=kind, overlap=overlap,
        pipeline=pipeline, exchange=exchange, ensemble=ensemble,
        variant=variant)
