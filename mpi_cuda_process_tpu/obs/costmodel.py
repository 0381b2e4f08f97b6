"""Static per-step cost counters: flops, HBM bytes, ppermute rounds/bytes.

Every telemetry manifest carries a roofline prediction next to its
measurement (the attribution discipline of the TPU CFD framework,
arXiv:2108.11076 §5, and the MPMD overlap-accounting of
arXiv:2412.14374): ``scripts/obs_report.py`` renders predicted vs
measured per phase, so a run that is slower than its own static model
says WHERE (interior bandwidth, exchange, compile).

Two kinds of counter, deliberately separate:

* **jaxpr extraction** (:func:`flops_from_jaxpr`,
  :func:`comm_stats_from_jaxpr`): counts read off a traced program —
  exact for the program traced, usable wherever tracing is possible
  (tests trace small sharded steps on virtual devices).
* **analytic model** (:func:`comm_stats`, :func:`hbm_bytes_per_step`):
  closed-form counts for configurations whose device population does
  not exist on this box (config 5's 64-chip meshes).  The analytic
  exchange model is CROSS-CHECKED two ways: against the jaxpr counts on
  traceable configs, and against ``utils/budget.py``'s byte-pinned slab
  accounting (:func:`budget_crosscheck`) — tests pin both to the byte,
  so the three models (jaxpr reality, this module, the HBM budget)
  cannot drift apart silently.

Nothing here executes device code: tracing is shape-level, the analytic
paths are pure arithmetic.
"""

from __future__ import annotations

import math
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from ..utils.jaxprcheck import iter_jaxprs

# Published per-chip peaks, keyed by jax's ``device_kind``.  Source:
# Google Cloud documentation, "TPU v5e": 819 GB/s of HBM bandwidth and
# 1,600 Gbit/s of chip-to-chip interconnect per chip, priced here per
# ICI link at 45 GB/s.  A kind not in the table gets no roofline (None):
# the roofline is an upper bound for a known chip, never a guess.
DEVICE_PEAKS = {
    "TPU v5 lite": {"hbm_gbs": 819.0, "ici_gbs": 45.0},
}


def device_peaks(kind: Optional[str] = None) -> Optional[Dict[str, float]]:
    """Peaks of ``kind`` (default: this process's first device), or None
    when the table does not know it."""
    if kind is None:
        kind = jax.devices()[0].device_kind
    return DEVICE_PEAKS.get(kind)

# Elementwise primitives counted as one flop per output element.  A
# MODEL, not a lowering simulator: comparisons, selects, copies, pads,
# and layout ops are free; transcendentals count 1 (they dominate no
# stencil here).  The counter's job is a stable, pinned, comparable
# number per program — tests assert exact values so drift is loud.
_FLOP_PRIMS = frozenset({
    "add", "add_any", "sub", "mul", "div", "rem", "pow", "integer_pow",
    "max", "min", "neg", "abs", "exp", "log", "tanh", "sqrt", "rsqrt",
    "sign", "floor", "ceil", "round", "erf", "logistic", "sin", "cos",
})


def flops_from_jaxpr(closed) -> int:
    """Weighted elementwise-arithmetic count across all nested jaxprs.

    Counts each eqn once (do not feed scanned/looped programs unless
    one iteration is what you mean to count).
    """
    total = 0
    for jx in iter_jaxprs(closed.jaxpr):
        for eqn in jx.eqns:
            if eqn.primitive.name in _FLOP_PRIMS:
                total += max(
                    (math.prod(ov.aval.shape) for ov in eqn.outvars),
                    default=0)
    return total


def comm_stats_from_jaxpr(closed) -> Dict[str, int]:
    """ppermute rounds and per-device bytes read off a traced program.

    Each ``ppermute`` eqn is one exchange round; its operand aval is
    what every participating device sends (and receives) — summing aval
    bytes gives the per-device ICI payload per call of the traced
    function.
    """
    rounds = 0
    bytes_ = 0
    for jx in iter_jaxprs(closed.jaxpr):
        for eqn in jx.eqns:
            if eqn.primitive.name == "ppermute":
                rounds += 1
                aval = eqn.invars[0].aval
                bytes_ += math.prod(aval.shape) * aval.dtype.itemsize
    return {"ppermute_rounds": rounds, "ppermute_bytes": bytes_}


def step_flops(stencil, shape: Sequence[int], periodic: bool = False) -> int:
    """Flops of ONE reference jnp step on ``shape`` (trace-only).

    The counter of record for every execution strategy: the fused/raw
    kernels compute the same update (plus margin redundancy the model
    deliberately ignores), so one number is comparable across paths.
    """
    from ..driver import make_step

    step = make_step(stencil, tuple(int(s) for s in shape),
                     periodic=periodic)
    abstract = tuple(
        jax.ShapeDtypeStruct(tuple(int(s) for s in shape), stencil.dtype)
        for _ in range(stencil.num_fields))
    return flops_from_jaxpr(jax.make_jaxpr(step)(abstract))


def _local_shape(grid: Sequence[int],
                 mesh: Sequence[int]) -> Tuple[int, ...]:
    counts = tuple(mesh) + (1,) * (len(grid) - len(mesh)) if mesh else \
        (1,) * len(grid)
    return tuple(int(g) // int(c) for g, c in zip(grid, counts))


def hbm_bytes_per_step(stencil, local_shape: Sequence[int],
                       fuse: int = 0, batch: int = 1) -> int:
    """Minimum per-device HBM traffic per REAL step: one read + one
    write of every field, divided by the temporal-blocking depth (k
    steps per HBM pass is exactly what ``--fuse`` buys)."""
    cells = max(1, int(batch)) * math.prod(int(s) for s in local_shape)
    item = jnp.dtype(stencil.dtype).itemsize
    return (2 * stencil.num_fields * cells * item) // max(1, int(fuse))


def rdma_stats_from_jaxpr(closed) -> Dict[str, int]:
    """Remote-DMA exchange counters read off a traced program: remote
    ``dma_start`` eqns (each is one chunk crossing the ICI) and the
    residual ``ppermute`` count (pinned 0 for an rdma step).  The
    jaxpr-reality half of the rdma cross-check."""
    from ..utils.jaxprcheck import count_primitive, count_remote_dma

    return {
        "remote_dma": count_remote_dma(closed),
        "ppermute_rounds": count_primitive(closed, "ppermute"),
    }


def _rdma_sites(stencil, local: Sequence[int], m: int,
                counts: Sequence[int], nslots: int = 0,
                prefer_nc: int = 0) -> List[Dict[str, Any]]:
    """The per-field ring-exchange sites of one slab-kind pass under
    ``exchange="rdma"``, with their chunk geometry — read from the SAME
    ``remote.pick_chunks`` the kernel builder uses, so the analytic DMA
    counts cross-check against the kernel's actual grid by
    construction.  Mirrors ``halo.exchange_slabs_2axis``: one call per
    z-slab pair, one per y-slab pair, two per corner set (the two-pass
    composition exchanges zlo and zhi separately along y).
    ``nslots``/``prefer_nc`` (0 = kernel defaults) re-pin the table
    under an rdma kernel variant's ring geometry (policy/autotune.py),
    so kernel and model read the same constants."""
    from ..ops.pallas.remote import ring_exchange_stats

    lz, ly, lx = local
    kw = {"nslots": nslots or None, "prefer_nc": prefer_nc}
    sites = []
    if counts[0] > 1:
        sites.append(ring_exchange_stats((m, ly, lx), stencil.dtype,
                                         **kw))
    if counts[1] > 1:
        sites.append(ring_exchange_stats((lz, m, lx), stencil.dtype,
                                         **kw))
        corner = ring_exchange_stats((m, m, lx), stencil.dtype, **kw)
        sites += [corner, dict(corner)]
    return sites


def comm_stats(
    stencil,
    grid: Sequence[int],
    mesh: Sequence[int] = (),
    fuse: int = 0,
    fuse_kind: str = "auto",
    periodic: bool = False,
    exchange: str = "ppermute",
    batch: int = 1,
    variant=None,
) -> Optional[Dict[str, Any]]:
    """Analytic ppermute rounds + bytes per device, or None (unsharded).

    Mirrors the exchange the steppers actually issue (pinned against
    traced jaxprs in tests/test_obs.py):

    * slab-operand fused kinds (``padfree``/``stream``): width-``m``
      face slabs per field — z-only meshes 2 rounds/field of
      ``(m, ly, lx)``; meshes that shard y add 2 y-rounds of
      ``(lz, m, lx)`` and 4 two-pass corner rounds of ``(m, m, lx)``
      (``halo.exchange_slabs_2axis``).  ``slab_operand_bytes`` prices
      the kernel's operand STORAGE for the same set (2-axis kernels
      duplicate/align the y-facing operands) and must equal
      ``utils/budget.py``'s slab part to the byte.
    * padded fused kind / plain jnp step: the two-pass
      ``exchange_and_pad`` scheme — axis d's slabs span axes < d
      already padded; the plain step exchanges only fields with a
      nonzero ``field_halo`` at width ``halo``, the fused kinds every
      field at width ``m``.

    ``batch=N`` (the ensemble engine): the ROUND COUNT is unchanged —
    vmap folds the member axis into each collective operand, the
    structural pin of the batched steppers — while every per-device
    byte quantity (ICI payloads, slab operand storage) scales by the N
    members the device holds.

    ``exchange="rdma"`` (streaming kind): the same slab set crosses the
    ICI, but as in-kernel remote-DMA chunks instead of ppermutes — the
    counters become ``rdma_exchange_calls_per_pass`` (ring-kernel
    invocations: one per z-slab pair, one per y-slab pair, two per
    corner set) and ``rdma_dma_per_pass`` (remote ``dma_start`` count:
    2 directions x nchunks per call, chunk geometry from
    ``remote.pick_chunks`` — the SAME function the kernel builds from,
    so the count cross-checks against the kernel grid by construction;
    pinned against traced jaxprs in tests and re-checked per manifest
    by :func:`rdma_crosscheck`).  ``ppermute_rounds_per_pass`` is 0 by
    definition (the zero-collective gate), ici bytes are unchanged
    (the ring carries the same payloads), and ``slab_operand_bytes`` is
    None — the recv side stages through VMEM rings, so budget has no
    HBM slab part to compare (see utils/budget.py).
    """
    ndim = stencil.ndim
    counts = (tuple(int(c) for c in mesh) + (1,) * ndim)[:ndim]
    if math.prod(counts) <= 1:
        return None
    local = _local_shape(grid, mesh)
    item = jnp.dtype(stencil.dtype).itemsize
    nf = stencil.num_fields
    batch = max(1, int(batch))

    if fuse:
        from ..ops.pallas.fused import _halo_per_micro

        m = int(fuse) * _halo_per_micro(stencil)
        widths = (m,) * nf
        per_pass_steps = int(fuse)
    else:
        widths = tuple(stencil.field_halos)
        per_pass_steps = 1

    # slab-operand kinds exist for 3D only (2D fused runs use the
    # whole-local-block kernel behind the padded-style exchange)
    kind = fuse_kind if (fuse and ndim == 3
                         and fuse_kind in ("padfree", "stream")) \
        else ("padded" if fuse else "plain")

    rdma = exchange == "rdma" and kind == "stream"
    rounds = 0
    ici = 0
    operand: Optional[int] = None
    rdma_sites: Optional[List[Dict[str, Any]]] = None
    if kind in ("padfree", "stream"):
        lz, ly, lx = local
        m = widths[0]
        two_axis = counts[1] > 1
        z_sharded = counts[0] > 1
        z_bytes = m * ly * lx * item
        if rdma:
            # an rdma-family kernel variant (policy/autotune.py) changes
            # the ring geometry the kernel builds — the chunk table must
            # be read under the same constants or the crosscheck would
            # compare different schedules
            v_nslots = int(getattr(variant, "nslots", 0) or 0) \
                if getattr(variant, "family", "") == "rdma" else 0
            v_nc = int(getattr(variant, "prefer_nc", 0) or 0) \
                if getattr(variant, "family", "") == "rdma" else 0
            rdma_sites = _rdma_sites(stencil, local, m, counts,
                                     nslots=v_nslots, prefer_nc=v_nc)
        if z_sharded:
            rounds += nf * 2
            ici += nf * 2 * z_bytes
        if two_axis:
            y_bytes = lz * m * lx * item
            c_bytes = m * m * lx * item
            rounds += nf * (2 + 4)
            ici += nf * (2 * y_bytes + 4 * c_bytes)
            # operand storage: the 2-axis kernels carry the y-facing
            # operands duplicated (pad-free: 2m rows) or sublane-aligned
            # (stream: m + m_a) — exactly budget.py's slab accounting
            from ..ops.pallas.fused import _sublane

            if kind == "stream":
                m_a = -(-m // _sublane(item)) * _sublane(item)
                dup = m + m_a
            else:
                dup = 2 * m
            operand = nf * item * (2 * m * ly * lx
                                   + 2 * dup * lz * lx
                                   + 4 * m * dup * lx)
        else:
            operand = nf * 2 * z_bytes
    else:
        # two-pass exchange_and_pad: axis d exchanged after axes < d are
        # padded, so its slab spans the already-grown extents
        for i in range(nf):
            w = widths[i]
            if not w:
                continue
            for d in range(ndim):
                if counts[d] <= 1:
                    continue
                slab_cells = w
                for j in range(ndim):
                    if j == d:
                        continue
                    slab_cells *= local[j] + (2 * w if j < d else 0)
                rounds += 2
                ici += 2 * slab_cells * item

    out: Dict[str, Any] = {
        "kind": kind,
        "exchange": "rdma" if rdma else "ppermute",
        "per_pass_steps": per_pass_steps,
        "width_m": max(widths),
        "sharded_counts": list(counts),
        "members_per_device": batch,
        # round count is BATCH-INDEPENDENT (the vmap collective-batching
        # pin); bytes scale with the members each device holds
        "ppermute_rounds_per_pass": 0 if rdma else rounds,
        "ici_bytes_per_pass": batch * ici,
        "ici_bytes_per_step": batch * ici / per_pass_steps,
        "slab_operand_bytes": None if rdma else (
            None if operand is None else batch * operand),
    }
    if rdma:
        # one ring-kernel invocation per site PER FIELD; the DMA count
        # is what a traced step must reproduce exactly
        out["rdma_exchange_calls_per_pass"] = nf * len(rdma_sites)
        out["rdma_dma_per_pass"] = nf * sum(
            s["remote_dma_per_call"] for s in rdma_sites)
        out["rdma_chunks"] = rdma_sites
    return out


def rdma_crosscheck(
    stencil,
    grid: Sequence[int],
    mesh: Sequence[int],
    fuse: int,
    periodic: bool = False,
    variant=None,
) -> Optional[Dict[str, Any]]:
    """Analytic rdma DMA count vs a TRACED compiled rdma step.

    The rdma analogue of :func:`budget_crosscheck`: the analytic chunk
    model (``remote.pick_chunks``) against the remote ``dma_start``
    count of the actual step jaxpr (``interpret=False`` — the kernel a
    TPU run compiles; tracing is shape-level, nothing executes), plus
    the zero-ppermute pin.  Returns None when this box cannot host the
    mesh (config 5's 64-chip population) — the analytic side still
    rides the manifest via ``comm["rdma_dma_per_pass"]``; tests pin the
    match on traceable meshes.
    """
    cs = comm_stats(stencil, grid, mesh, fuse=fuse, fuse_kind="stream",
                    periodic=periodic, exchange="rdma", variant=variant)
    if cs is None or "rdma_dma_per_pass" not in cs:
        return None
    try:
        from ..parallel.mesh import make_mesh
        from ..parallel.stepper import make_sharded_fused_step

        mesh_obj = make_mesh(tuple(mesh))
        step = make_sharded_fused_step(
            stencil, mesh_obj, tuple(int(g) for g in grid), int(fuse),
            interpret=False, kind="stream", periodic=periodic,
            exchange="rdma", variant=variant)
        if step is None:
            return None
        abstract = tuple(
            jax.ShapeDtypeStruct(tuple(int(g) for g in grid),
                                 stencil.dtype)
            for _ in range(stencil.num_fields))
        traced = rdma_stats_from_jaxpr(jax.make_jaxpr(step)(abstract))
    except Exception:  # noqa: BLE001 — mesh too big for this box, or
        return None    # any trace-environment limitation: no cross-check
    return {
        "analytic_remote_dma": cs["rdma_dma_per_pass"],
        "traced_remote_dma": traced["remote_dma"],
        "traced_ppermute": traced["ppermute_rounds"],
        "match": (traced["remote_dma"] == cs["rdma_dma_per_pass"]
                  and traced["ppermute_rounds"] == 0),
    }


def budget_crosscheck(
    stencil,
    grid: Sequence[int],
    mesh: Sequence[int],
    fuse: int,
    fuse_kind: str,
    periodic: bool = False,
    ensemble: int = 0,
    ensemble_mesh: int = 0,
) -> Optional[Dict[str, Any]]:
    """Assert-by-record: this module's slab-operand bytes vs budget.py's.

    Returns ``{"slab_operand_bytes", "budget_bytes", "match"}`` for the
    slab-operand kinds, None where budget has no slab part to compare.
    The pair rides the manifest so a drift between the two byte models
    is visible in every event log, and tests pin ``match == True`` for
    config 5 on both mesh families.
    """
    members = (max(1, int(ensemble)) // max(1, int(ensemble_mesh))
               if ensemble else 1)
    cs = comm_stats(stencil, grid, mesh, fuse=fuse, fuse_kind=fuse_kind,
                    periodic=periodic, batch=members)
    if cs is None or cs.get("slab_operand_bytes") is None:
        return None
    from ..utils import budget

    _, parts = budget.estimate_run_bytes(
        stencil, grid, mesh=mesh, fuse=fuse, fuse_kind=fuse_kind,
        periodic=periodic, ensemble=ensemble,
        ensemble_mesh=ensemble_mesh)
    slab = [b for label, b in parts
            if "operands only" in label and b > 0]
    if not slab:
        return None
    return {
        "slab_operand_bytes": cs["slab_operand_bytes"],
        "budget_bytes": slab[0],
        "match": cs["slab_operand_bytes"] == slab[0],
    }


def static_cost(
    stencil,
    grid: Sequence[int],
    mesh: Sequence[int] = (),
    fuse: int = 0,
    fuse_kind: str = "auto",
    periodic: bool = False,
    ensemble: int = 0,
    hbm_gbs: Optional[float] = None,
    ici_gbs: Optional[float] = None,
    exchange: str = "ppermute",
    ensemble_mesh: int = 0,
    variant=None,
) -> Dict[str, Any]:
    """The manifest's static cost block: counters + roofline prediction.

    Per-device flops (jaxpr-counted on the local block), minimum HBM
    traffic per step, the exchange model, the budget cross-check, and
    two throughput predictions: ``overlapped`` prices the paper's core
    claim (exchange hidden behind interior compute — step time is the
    HBM bound alone) and ``serial`` the unhidden schedule; the measured
    number landing between them is the overlap win, quantified.
    ``variant`` (a ``policy.autotune.KernelVariant`` or None) re-pins
    the rdma chunk tables and the traced cross-check under that
    variant's ring geometry — model and kernel read the same constants.
    ``hbm_gbs``/``ici_gbs`` default to :func:`device_peaks`; on a device
    the table does not know, ``roofline`` is None.
    """
    if hbm_gbs is None or ici_gbs is None:
        peaks = device_peaks() or {}
        hbm_gbs = peaks.get("hbm_gbs") if hbm_gbs is None else hbm_gbs
        ici_gbs = peaks.get("ici_gbs") if ici_gbs is None else ici_gbs
    grid = tuple(int(g) for g in grid)
    local = _local_shape(grid, mesh)
    # per-DEVICE members (time-side terms) vs TOTAL members (cell
    # throughput): an ensemble mesh axis spreads the batch over device
    # groups, so a device pays for ensemble/ensemble_mesh members while
    # the machine advances all of them
    total_members = max(1, int(ensemble))
    members = (total_members // max(1, int(ensemble_mesh))
               if ensemble else 1)
    comm = comm_stats(stencil, grid, mesh, fuse=fuse, fuse_kind=fuse_kind,
                      periodic=periodic, exchange=exchange, batch=members,
                      variant=variant)
    flops = members * step_flops(stencil, local, periodic=periodic)
    hbm_b = hbm_bytes_per_step(stencil, local, fuse=fuse, batch=members)
    cells = total_members * math.prod(grid)

    out: Dict[str, Any] = {
        "grid": list(grid),
        "mesh": list(mesh),
        "local_shape": list(local),
        "batch": total_members,
        "ensemble": int(ensemble),
        "ensemble_mesh": int(ensemble_mesh),
        "members_per_device": members,
        "fuse": int(fuse),
        "fuse_kind": comm["kind"] if comm else (fuse_kind if fuse else None),
        "dtype": str(jnp.dtype(stencil.dtype)),
        "flops_per_step_per_device": int(flops),
        "hbm_bytes_per_step_per_device": int(hbm_b),
        "comm": comm,
        "roofline": _roofline(cells, hbm_b,
                              comm["ici_bytes_per_step"] if comm else 0,
                              hbm_gbs, ici_gbs),
    }
    if comm and comm.get("slab_operand_bytes") is not None:
        try:
            out["budget_crosscheck"] = budget_crosscheck(
                stencil, grid, mesh, fuse, fuse_kind, periodic=periodic,
                ensemble=ensemble, ensemble_mesh=ensemble_mesh)
        except Exception:  # noqa: BLE001 — the cross-check must never
            out["budget_crosscheck"] = None  # block a manifest write
    if comm and comm.get("exchange") == "rdma":
        try:
            # traced remote-DMA count vs the analytic chunk model —
            # rides every rdma manifest so obs_report attributes the
            # in-kernel traffic (None when this box can't host the mesh)
            out["rdma_crosscheck"] = rdma_crosscheck(
                stencil, grid, mesh, fuse, periodic=periodic,
                variant=variant)
        except Exception:  # noqa: BLE001 — never block a manifest write
            out["rdma_crosscheck"] = None
    return out


def _roofline(cells: int, hbm_b: int, ici_b: int,
              hbm_gbs: Optional[float],
              ici_gbs: Optional[float]) -> Optional[Dict[str, Any]]:
    """Throughput bounds from minimum traffic at the peaks; None without
    peaks (a device :data:`DEVICE_PEAKS` does not know)."""
    if hbm_gbs is None or ici_gbs is None:
        return None
    t_hbm_ms = hbm_b / (hbm_gbs * 1e9) * 1e3
    t_ici_ms = ici_b / (ici_gbs * 1e9) * 1e3

    def _mcells(t_ms: float) -> float:
        return cells / (t_ms * 1e-3) / 1e6 if t_ms > 0 else float("inf")

    return {
        "hbm_gbs": hbm_gbs,
        "ici_gbs": ici_gbs,
        "predicted_ms_per_step_hbm": round(t_hbm_ms, 6),
        "predicted_ms_per_step_exchange": round(t_ici_ms, 6),
        "predicted_mcells_per_s_overlapped": round(_mcells(t_hbm_ms), 1),
        "predicted_mcells_per_s_serial": round(
            _mcells(t_hbm_ms + t_ici_ms), 1),
        "basis": "minimum HBM traffic at peak bandwidth; 'overlapped'"
                 " assumes the exchange fully hidden (the paper's "
                 "claim), 'serial' adds it to the critical path",
    }


def coupled_cost(plans, hbm_gbs: Optional[float] = None,
                 ici_gbs: Optional[float] = None,
                 transport: str = "") -> Dict[str, Any]:
    """The coupled (``--groups``) run's static cost block.

    Per-group :func:`static_cost` (each group's interior step is the
    unmodified stepper on its own sub-mesh — round 23: its clause mode
    tokens flow into ``fuse``/``fuse_kind``, so a fused/stream group is
    priced exactly like the monolithic run it mirrors) plus an EXPLICIT
    interface sub-block: the cross-group band refresh is the only new
    traffic, and it is priced by name — rounds per step, bytes per
    direction, ratios and dtypes per interface — so obs_report can
    attribute the coupling cost separately from each group's own
    exchange.  ``transport`` prices the two band paths apart:
    ``device_put`` moves the RECEIVER-side resampled band
    (``bytes_per_round`` = sum of recv parts), ``collective`` moves the
    RAW sender rows over ICI and resamples on the receiver
    (``bytes_per_round`` = sum of send parts — the wire's actual
    payload).  The budget cross-check: ``interface.bytes_per_round``
    must equal the sum of ``utils/budget.py``'s matching per-group
    interface parts (tests pin it), so the cost model and the HBM
    budget cannot drift apart.
    """
    from ..parallel import groups as groups_lib

    if ici_gbs is None:
        ici_gbs = (device_peaks() or {}).get("ici_gbs")
    transport = transport or groups_lib.TRANSPORT_BACKEND
    group_costs = []
    for p in plans:
        s = p.spec
        c = static_cost(p.stencil, p.grid, mesh=p.mesh_shape,
                        fuse=s.fuse_k if s.fuse_k > 1 else 0,
                        fuse_kind=s.kind or "auto",
                        hbm_gbs=hbm_gbs, ici_gbs=ici_gbs)
        c["group"] = p.name
        c["ratio"] = p.ratio
        c["devices"] = [p.spec.dev_lo, p.spec.dev_hi]
        c["cells_per_round"] = p.cells
        c["owned_cells"] = p.owned_cells
        c["modes"] = list(s.modes)
        group_costs.append(c)
    traffic = groups_lib.interface_traffic(plans)
    recv_bytes = sum(t[d]["recv_bytes"] for t in traffic
                     for d in ("up", "down"))
    send_bytes = sum(t[d]["send_bytes"] for t in traffic
                     for d in ("up", "down"))
    wire_bytes = send_bytes if transport == "collective" else recv_bytes
    return {
        "coupled": True,
        "n_groups": len(plans),
        "cell_updates_per_round": int(sum(p.cells for p in plans)),
        "groups": group_costs,
        "interface": {
            "n_interfaces": len(traffic),
            # one wholesale band refresh per coupled round — the whole
            # coupling protocol, by construction
            "rounds_per_step": 1,
            "transport": transport,
            "bytes_per_round": int(wire_bytes),
            "staged_bytes_per_round": int(send_bytes),
            "predicted_ms_per_round": None if ici_gbs is None else round(
                wire_bytes / (ici_gbs * 1e9) * 1e3, 6),
            "interfaces": traffic,
        },
    }
