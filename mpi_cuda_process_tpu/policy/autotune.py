"""Measured kernel-variant autotuner: Pallas constant sweeps as policy.

``--auto-policy`` (policy/select.py, ISSUE 15) resolves *modes* — mesh,
overlap, pipeline, exchange — from measured ledger rows, but every
Pallas kernel still ran hand-chosen constants: the remote-DMA ring's
slot count (``ops/pallas/remote._NSLOTS``) and chunk-count ladder, the
streaming kernel's ``bz``/``by`` strip geometry.  The r03 numbers
(fused wave3d 70 vs 24 Gcells/s) say such constants are worth whole
multiples, which is exactly how the hand-tuned TPU stencil framework
(arXiv:2108.11076) and the 1→2048-core TPU linear-algebra work
(arXiv:2112.09017) reached their rooflines.  This module makes the
constants a measured policy dimension (ROADMAP item 4):

* **Sweep space** — per-kernel-family :class:`KernelVariant` records:
  ring depth + credit capacity (``nslots``) and chunk-count preference
  (``prefer_nc``) for the ``rdma`` family, ``(bz, by)`` strip geometry
  for the ``stream`` family.  Every candidate is validated against the
  kernel's own constraints (sublane alignment, strip gates, the VMEM
  ring budget via ``utils/budget.ring_vmem_bytes``) BEFORE any probe
  runs; invalid candidates are rejected with a named reason, never
  compiled.
* **Probes** — :func:`maybe_autotune` runs a short measured probe per
  (op, shape, dtype, mesh, exchange, variant) and records each result
  as an ordinary campaign-ledger row (``source="autotune"``) whose
  ``baseline_key`` carries a ``|var:<id>`` dimension (the ``|ensN``
  pattern from round 15): a variant row can never baseline a
  default-constant row, and quarantine + ``best_known`` apply
  unchanged.  The PR-6 profiler's interior-vs-collective attribution
  prioritizes which constant family to sweep first
  (:func:`prioritize_sweep`: comm-bound → ring/credit depth,
  compute-bound → block shape).
* **Resolution** — ``policy/select.py`` resolves ``kernel_variant``
  exactly like mesh: measured beats predicted, the decision lands in
  the manifest ``policy`` event, and ``perf_gate.py --policy-check``
  fails when the winning variant moves after a JAX/XLA bump (the
  variant id is part of the cli ledger label, so label equality is the
  staleness detector).

The tuneN campaign labels (``benchmarks/measure.py`` Tier-D13,
``*_tune<N>``) index :data:`STREAM_SWEEP` / :data:`RDMA_SWEEP` 1-based,
so the queued TPU campaign seeds variant rows the moment a session
sees real chips.
"""

from __future__ import annotations

import dataclasses
import logging
import math
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from .. import plan
from ..config import RunConfig
from ..obs import ledger as ledger_lib

log = logging.getLogger("mpi_cuda_process_tpu.autotune")


@dataclasses.dataclass(frozen=True)
class KernelVariant:
    """One swept constant assignment for one kernel family.

    ``family="rdma"``: ``nslots`` is the VMEM ring depth per direction
    AND the credit capacity (``ops/pallas/remote.py`` derives its
    flow-control window, scratch shapes and drained-semaphore epilogue
    from it); ``prefer_nc`` steers ``pick_chunks``'s ladder (0 = the
    depth-scaled default ladder).  ``family="stream"``: ``(bz, by)`` is
    the explicit strip geometry handed to the streaming builders'
    ``tiles=`` (validated through the same ``_stream_gates`` as the
    picker); ``margin`` overrides the kernel's sublane-rounded temporal
    halo margin ``wm_a`` (a wider DMA-alignable y-flank — must be a
    sublane multiple covering the k-step halo ``wm``); ``order``
    permutes the strip-grid traversal (``"rev"`` walks the y strips
    high-to-low, ``"xy"`` makes the x windows the outer grid axis —
    x-windowed strips only).  ``family="tiled"`` (round 23 — the last
    ROADMAP item-4 residue): ``(bz, by)`` is the explicit window-tile
    geometry handed to the UNSHARDED padded 4-block kernel
    (``ops/pallas/fused.build_fused_call``'s ``tiles=``, hosted by
    ``--fuse-kind tiled``), validated through the builder's own
    ``_tiles_valid`` gate plus ``_pick_tiles``'s VMEM cost model —
    the sweep explores dimensions the auto picker's {8..64} grid never
    scores (128-row strips, deep z columns).  Zero fields are "not
    overridden": a variant with every constant zero compiles the
    byte-identical default kernel.
    """
    id: str
    family: str            # "rdma" | "stream" | "tiled"
    nslots: int = 0
    prefer_nc: int = 0
    bz: int = 0
    by: int = 0
    margin: int = 0
    order: str = ""

    @property
    def tiles(self) -> Optional[Tuple[int, int]]:
        return (self.bz, self.by) if self.bz else None


#: The sweep registry.  Order within a family tuple is the campaign's
#: ``tuneN`` index (1-based) — append only, never reorder, or the
#: Tier-D13 labels change meaning.
VARIANTS: Dict[str, KernelVariant] = {v.id: v for v in (
    # rdma family: ring depth (= credit capacity) and chunk preference
    KernelVariant(id="ring3", family="rdma", nslots=3),
    KernelVariant(id="ring4", family="rdma", nslots=4),
    KernelVariant(id="nc8", family="rdma", prefer_nc=8),
    # stream family: strip geometry (bz planes x by rows)
    KernelVariant(id="bz16y16", family="stream", bz=16, by=16),
    KernelVariant(id="bz8y8", family="stream", bz=8, by=8),
    KernelVariant(id="bz16y32", family="stream", bz=16, by=32),
    # stream family, round 18: halo-margin widening (picker-chosen
    # strips, wider DMA-alignable y-flank) and strip traversal order
    KernelVariant(id="mg16", family="stream", margin=16),
    KernelVariant(id="mg32", family="stream", margin=32),
    KernelVariant(id="orev", family="stream", order="rev"),
    KernelVariant(id="oxy", family="stream", order="xy"),
    # tiled family, round 23: explicit window tiles for the unsharded
    # padded kernel — shapes OUTSIDE the auto picker's {8..64} scan
    # (the picker maximizes core/window ratio; these trade it for
    # longer sublane runs / fewer tail-window reassemblies)
    KernelVariant(id="tz8y128", family="tiled", bz=8, by=128),
    KernelVariant(id="tz32y128", family="tiled", bz=32, by=128),
    KernelVariant(id="tz128y32", family="tiled", bz=128, by=32),
)}

STREAM_SWEEP: Tuple[str, ...] = ("bz16y16", "bz8y8", "bz16y32",
                                 "mg16", "mg32", "orev", "oxy")
RDMA_SWEEP: Tuple[str, ...] = ("ring3", "ring4", "nc8")
TILED_SWEEP: Tuple[str, ...] = ("tz8y128", "tz32y128", "tz128y32")

_SWEEPS: Dict[str, Tuple[str, ...]] = {
    "stream": STREAM_SWEEP, "rdma": RDMA_SWEEP, "tiled": TILED_SWEEP}


def tune_variant(family: str, n: int) -> KernelVariant:
    """The campaign's ``tune<n>`` (1-based) variant of ``family`` —
    the label contract between measure.py and this registry."""
    sweep = _SWEEPS.get(family)
    if sweep is None:
        raise ValueError(f"unknown variant family {family!r} "
                         f"(known: stream, rdma, tiled)")
    if not 1 <= n <= len(sweep):
        raise ValueError(f"tune{n}: family {family!r} has "
                         f"{len(sweep)} swept variants")
    return VARIANTS[sweep[n - 1]]


# ---------------------------------------------------------- validation

def _mesh_counts(cfg: RunConfig) -> Tuple[int, ...]:
    return (tuple(int(c) for c in cfg.mesh) + (1,) * 3)[:3]


def _config_reason(cfg: RunConfig, v: KernelVariant) -> Optional[str]:
    """Why ``cfg`` cannot host ``v`` at all (family prerequisites) —
    None when the config is variant-eligible."""
    if len(cfg.grid) != 3:
        return "kernel variants cover the 3D fused kernel families only"
    if not cfg.fuse:
        return ("kernel variants tune the temporal-blocking kernels: "
                "needs an explicit --fuse K")
    if v.family == "tiled":
        # the padded window kernel is unsharded-only (plan.build rejects
        # --fuse-kind tiled under --mesh): the opposite prerequisites
        # of the streaming families
        if cfg.fuse_kind != "tiled":
            return (f"variant {v.id} sweeps the padded window kernel's "
                    "explicit tiles: force --fuse-kind tiled")
        if cfg.mesh and math.prod(cfg.mesh) > 1:
            return ("the tiled window kernel is unsharded-only (sharded "
                    "runs ride the stream/padfree kinds): drop --mesh")
        return None
    if cfg.fuse_kind != "stream":
        return ("stream/rdma kernel variants ride the streaming kernel "
                "family: force --fuse-kind stream")
    if not cfg.mesh or math.prod(cfg.mesh) <= 1:
        return ("kernel variants tune the sharded exchange/strip "
                "schedule: needs --mesh")
    counts = _mesh_counts(cfg)
    if counts[2] > 1:
        return "x-sharded meshes have no streaming kernel to tune"
    if v.family == "rdma" and cfg.exchange != "rdma":
        return (f"variant {v.id} tunes the remote-DMA ring: needs "
                "--exchange rdma")
    return None


def validate_variant(v: KernelVariant, cfg: RunConfig,
                     st: Any = None) -> Tuple[bool, Optional[str]]:
    """``(ok, named_reason)`` for sweeping ``v`` under ``cfg``.

    Checks the family prerequisites, then the kernel's own geometry
    constraints — sublane alignment, strip gates, the VMEM budget
    (``utils/budget.ring_vmem_bytes`` against the kernel VMEM limit)
    — so an invalid candidate is rejected with its reason BEFORE any
    compile or probe.
    """
    reason = _config_reason(cfg, v)
    if reason:
        return False, reason
    if st is None:
        try:
            st = plan.make_cfg_stencil(cfg)
        except Exception as e:  # unknown stencil: nothing to validate
            return False, f"no stencil to validate against: {e}"
    from ..ops.pallas.fused import _halo_per_micro, _sublane
    from ..ops.pallas.kernels import _VMEM_LIMIT_BYTES
    from ..ops.pallas import streamfused

    counts = _mesh_counts(cfg)
    local = tuple(int(g) // c for g, c in zip(cfg.grid, counts))
    lz, ly, lx = local
    itemsize = jnp.dtype(st.dtype).itemsize
    sub = _sublane(itemsize)
    two_axis = counts[1] > 1
    k = int(cfg.fuse)

    if v.family == "tiled":
        # Explicit window tiles for the unsharded padded 4-block kernel:
        # the builder's own _tiles_valid gate, itemized first with named
        # reasons, then _pick_tiles's VMEM cost model (window margin =
        # the raw k-step margin m — the padded kernel assembles
        # (bz+2m, by+2m, X) windows, not the pad-free 2m).
        from ..ops.pallas import fused as fused_lib

        if not fused_lib.fused_supported(st):
            return False, f"{st.name} has no fused micro family"
        if not v.bz:
            return False, (f"variant {v.id} carries no tiles: the tiled "
                           "family sweeps explicit (bz, by) window "
                           "geometry only")
        wm = k * _halo_per_micro(st)
        bz, by = v.bz, v.by
        Z, Y, X = local  # unsharded (gated above): local IS the grid
        if (2 * wm) % sub:
            return False, (f"sublane-misaligned: 2*margin={2 * wm} is "
                           f"not a multiple of the dtype's sublane tile "
                           f"({sub} for itemsize {itemsize}) — no tile "
                           "choice can fix k for this dtype")
        if bz % (2 * wm) or by % (2 * wm):
            return False, (f"tiles ({bz}, {by}) are not multiples of "
                           f"2*margin={2 * wm}: the window-tail "
                           "BlockSpecs degenerate into silently-wrong "
                           "geometry (the _tiles_valid gate)")
        if Z % bz:
            return False, f"bz={bz} does not divide Z={Z}"
        if Y % by:
            return False, f"by={by} does not divide Y={Y}"
        if not fused_lib._tiles_valid(Z, Y, bz, by, wm, itemsize):
            return False, (f"tile gates reject variant {v.id} for grid "
                           f"{local} at margin {wm}")
        isz = max(itemsize, 4)  # sub-f32 budgets as f32 (_pick_tiles)
        lx_r = fused_lib._lane_round(X)
        window = (bz + 2 * wm) * (by + 2 * wm) * lx_r * isz
        core = bz * by * lx_r * isz
        nfields = fused_lib._MICRO[st.name][2]
        live = (7 * window + 2 * core) * nfields
        if live > fused_lib._VMEM_LIMIT:
            return False, (f"VMEM overflow: window live set {live} B > "
                           f"limit {fused_lib._VMEM_LIMIT} B for tiles "
                           f"({bz}, {by})")
        return True, None

    if not streamfused.stream_supported(st):
        return False, f"{st.name} has no streaming micro family"
    wm = k * _halo_per_micro(st)
    wm_a = -(-wm // sub) * sub

    if v.family == "stream":
        if v.order and v.order not in ("rev", "xy"):
            return False, (f"unknown strip order {v.order!r} "
                           f"(swept orders: rev, xy)")
        wm_eff = wm_a
        if v.margin:
            if v.margin % sub:
                return False, (f"sublane-misaligned: margin={v.margin} "
                               f"is not a multiple of the dtype's "
                               f"sublane tile ({sub} for itemsize "
                               f"{itemsize})")
            if v.margin < wm:
                return False, (f"margin={v.margin} does not cover the "
                               f"k-step temporal halo wm={wm}: the "
                               f"window would treat roll-wrap garbage "
                               f"as genuine data")
            wm_eff = v.margin
        if v.bz:
            bz, by = v.bz, v.by
            if by % sub:
                return False, (f"sublane-misaligned: by={by} is not a "
                               f"multiple of the dtype's sublane tile "
                               f"({sub} for itemsize {itemsize})")
            if lz % bz:
                return False, f"bz={bz} does not divide local Z={lz}"
            if lz // bz < 3:
                return False, (f"bz={bz} yields {lz // bz} z-chunks of "
                               f"local Z={lz}; the stream needs >= 3")
            if 2 * wm > bz:
                return False, (f"bz={bz} cannot host the 2*wm={2 * wm} "
                               f"k-step window")
            if ly % by:
                return False, f"by={by} does not divide local Y={ly}"
            if not streamfused._by_valid(ly, by, wm_eff, two_axis):
                return False, (f"by={by} y-strip window does not fit "
                               f"local Y={ly} (margin wm_a={wm_eff}"
                               + (", two-axis splice" if two_axis
                                  else "") + ")")
            live = streamfused._strip_live_bytes(
                bz, by, None, lx, wm, wm_eff, max(itemsize, 4),
                streamfused._MICRO[st.name][2], True, two_axis=two_axis,
                Y=ly)
            if live > streamfused._VMEM_LIMIT:
                return False, (f"VMEM overflow: strip live set "
                               f"{live} B > limit "
                               f"{streamfused._VMEM_LIMIT}"
                               f" B for tiles ({bz}, {by})")
        # the authoritative gate set (the same function the builder
        # runs, margin threaded identically) — anything the itemized
        # checks above missed, and the strip picker for margin/order
        # variants that carry no explicit tiles
        gates = streamfused._stream_gates(st, lz, ly, lx, k, v.tiles,
                                          sharded=True,
                                          two_axis=two_axis,
                                          margin=v.margin)
        if gates is None:
            return False, (f"streaming gates reject variant {v.id} for "
                           f"local shape {local}"
                           + (f" at margin {v.margin}" if v.margin
                              else ""))
        if v.order == "xy" and gates[7] is None:
            return False, ("order=xy permutes the (y, x) strip grid; "
                           "this config's strips are whole-lane (1-d y "
                           "grid) — nothing to reorder")
        return True, None

    if v.family == "rdma":
        from ..ops.pallas.remote import pick_chunks
        from ..utils.budget import ring_vmem_bytes

        nslots = v.nslots or 2
        if nslots < 2:
            return False, (f"ring depth {nslots} < 2: a single slot "
                           "cannot overlap send with drain")
        # the same slab sites costmodel._rdma_sites enumerates
        sites = [(wm, ly, lx)] if counts[0] > 1 else []
        if two_axis:
            sites += [(lz, wm, lx), (wm, wm, lx)]
        for slab in sites:
            axis, nc = pick_chunks(slab, itemsize, nslots=nslots,
                                   prefer_nc=v.prefer_nc)
            if v.prefer_nc and nc != v.prefer_nc:
                return False, (f"prefer_nc={v.prefer_nc} does not "
                               f"divide any chunkable axis of slab "
                               f"{slab} (sublane tile {sub}) — the "
                               f"variant would silently run the "
                               f"default geometry")
            ring = ring_vmem_bytes(slab, itemsize, nslots, nc)
            if ring > _VMEM_LIMIT_BYTES:
                return False, (f"VMEM overflow: ring live set {ring} B "
                               f"(nslots={nslots}, nchunks={nc}, slab "
                               f"{slab}) > limit {_VMEM_LIMIT_BYTES} B")
        return True, None

    return False, f"unknown variant family {v.family!r}"


def variant_for_config(cfg: RunConfig) -> Optional[KernelVariant]:
    """``cfg.kernel_variant``'s record when it is valid under ``cfg``,
    else None — the predicate ``policy/select._valid`` uses to prune
    enumerated candidates (never raises)."""
    v = VARIANTS.get(cfg.kernel_variant)
    if v is None:
        return None
    try:
        ok, _ = validate_variant(v, cfg)
    except Exception as e:  # noqa: BLE001 — a pruning predicate
        log.debug("autotune: validation error for %s: %s",
                  cfg.kernel_variant, e)
        return None
    return v if ok else None


def resolve_variant(cfg: RunConfig, st: Any = None) -> KernelVariant:
    """``cfg.kernel_variant``'s record, or ValueError with the named
    reason — the forced-flag contract for ``--kernel-variant``: an
    unsupported combination raises BEFORE any build work, never a
    silent fallback to the default constants."""
    if cfg.kernel_variant not in VARIANTS:
        raise ValueError(
            f"--kernel-variant {cfg.kernel_variant!r} unknown; swept "
            f"variants: {', '.join(sorted(VARIANTS))}")
    v = VARIANTS[cfg.kernel_variant]
    ok, reason = validate_variant(v, cfg, st=st)
    if not ok:
        raise ValueError(f"--kernel-variant {v.id}: {reason}")
    return v


# -------------------------------------------------------------- sweeps

def prioritize_sweep(attribution: Optional[Dict[str, Any]],
                     families: Sequence[str]) -> List[str]:
    """Order the family sweep by the profiler's attribution verdict.

    ``attribution`` is a PR-6 ``profile`` event record
    (``obs/profile.py``): when it attributes ok and the exposed
    collective time is a material fraction of the step (> 25% of
    compute + exposed comm), the run is comm-bound and the ring/credit
    depth family sweeps first; compute-bound runs sweep the block
    shape first.  Without a usable attribution the given order is
    kept (the caller lists the config's own family first).
    """
    fams = [f for f in families if f in _SWEEPS]
    if len(fams) < 2:
        return fams
    att = attribution or {}
    if att.get("attribution") != "ok":
        return fams
    compute = float(att.get("compute_us") or 0.0)
    exposed = float(att.get("exposed_comm_us") or 0.0)
    total = compute + exposed
    comm_bound = total > 0 and exposed / total > 0.25
    order = ("rdma", "stream") if comm_bound else ("stream", "rdma")
    # attribution only arbitrates the transport-vs-block-shape pair;
    # any other family (tiled) keeps its given position at the tail
    return ([f for f in order if f in fams]
            + [f for f in fams if f not in order])


def sweep_ids(cfg: RunConfig,
              attribution: Optional[Dict[str, Any]] = None) -> List[str]:
    """The variant ids eligible for ``cfg``, family-prioritized."""
    # the config's own kernel family leads by default; a usable
    # profiler attribution (when available) overrides the order
    if cfg.fuse_kind == "tiled":
        families = ["tiled"]
    else:
        families = (["rdma", "stream"] if cfg.exchange == "rdma"
                    else ["stream"])
    out: List[str] = []
    for fam in prioritize_sweep(attribution, families) or families:
        out += list(_SWEEPS[fam])
    return out


def _probe_mcells(cfg: RunConfig, calls: int) -> float:
    """Short measured probe: Mcells/s of ``cfg`` over a scanned step
    window, warm-timed as t(4N) - t(N) so compile and ramp cost cancel
    (the ``measure.py`` discipline, miniaturized)."""
    _, step_fn, fields, _ = plan.build(cfg)

    def scan(fs, n):
        def body(c, _):
            return step_fn(c), None
        return jax.lax.scan(body, fs, None, length=n)[0]

    run = jax.jit(scan, static_argnums=1)
    jax.block_until_ready(run(fields, calls))       # compile + warm
    t0 = time.perf_counter()
    jax.block_until_ready(run(fields, calls))
    t1 = time.perf_counter()
    jax.block_until_ready(run(fields, 4 * calls))
    t2 = time.perf_counter()
    dt = max(1e-9, (t2 - t1) - (t1 - t0))
    steps = 3 * calls * max(1, cfg.fuse)
    cells = math.prod(cfg.grid) * max(1, cfg.ensemble or 1)
    return cells * steps / dt / 1e6


def maybe_autotune(cfg: RunConfig,
                   backend: Optional[str] = None,
                   ledger_path: Optional[str] = None,
                   probe_calls: int = 2,
                   ids: Optional[Sequence[str]] = None,
                   attribution: Optional[Dict[str, Any]] = None,
                   ) -> Dict[str, Any]:
    """Sweep the eligible kernel variants under ``cfg`` and record each
    probe as a campaign-ledger row.

    The default constants probe first (their row refreshes the
    baseline the variants are ranked against), then every validated
    variant in :func:`prioritize_sweep` order.  Rows land under the
    cli label identity a real run of that config would carry —
    ``|var:<id>`` baseline keys — so ``policy/select.resolve`` ranks
    them with zero special-casing and ``perf_gate`` gates them like
    any other measurement.  Returns the sweep summary (swept, skipped
    with named reasons, winner) for the ``autotune`` manifest event.

    The probe cost rule (EXECUTION.md): each probe is ``4N + 2N``
    scanned step-calls plus one compile — size the grid so one probe
    stays under seconds, and re-sweep only when the JAX/XLA stack or
    the (op, shape, dtype, mesh, exchange) tuple changes; winners are
    durable ledger rows, not per-run state.
    """
    probe_family = TILED_SWEEP if cfg.fuse_kind == "tiled" else STREAM_SWEEP
    reason = _config_reason(
        cfg, VARIANTS[probe_family[0]])  # the config's own family prereqs
    if reason:
        raise ValueError(f"--autotune: {reason}")
    backend = backend or jax.default_backend()
    ledger_path = ledger_path or ledger_lib.default_ledger_path()
    st = plan.make_cfg_stencil(cfg)
    todo = [""] + [i for i in sweep_ids(cfg, attribution)
                   if ids is None or i in ids]
    rows: List[Dict[str, Any]] = []
    swept: List[Dict[str, Any]] = []
    skipped: List[Dict[str, Any]] = []
    for vid in todo:
        if vid:
            ok, why = validate_variant(VARIANTS[vid], cfg, st=st)
            if not ok:
                skipped.append({"id": vid, "reason": why})
                continue
        probe_cfg = dataclasses.replace(
            cfg, kernel_variant=vid, autotune=False, auto_policy=False,
            policy_recheck=0, telemetry=None, serve_port=None,
            profile=None, profile_dir=None, checkpoint_every=0,
            checkpoint_dir=None, resume=False, render=False,
            dump_every=0, log_every=0, check_finite=0, health=False,
            halo_audit=0, tol=0.0, supervise=False)
        d = dataclasses.asdict(probe_cfg)
        label = ledger_lib._cli_label(d)
        flags = ledger_lib._flags(d)
        try:
            mcps = _probe_mcells(probe_cfg, probe_calls)
        except Exception as e:  # noqa: BLE001 — a failed candidate is a
            # sweep result (named), never a sweep abort
            skipped.append({"id": vid or "default",
                            "reason": f"probe failed: {e}"})
            continue
        rows.append(ledger_lib.make_row(
            label, round(mcps, 3), source="autotune",
            measured_at=time.time(), backend=backend,
            grid=cfg.grid, mesh=cfg.mesh, kind=cfg.fuse_kind,
            dtype=str(jnp.dtype(st.dtype)), flags=flags or None,
            detail={"variant": vid or "default",
                    "probe_calls": probe_calls}))
        swept.append({"id": vid or "default", "label": label,
                      "value": round(mcps, 3)})
        log.info("autotune: %s -> %.3f Mcells/s (%s)",
                 vid or "default", mcps, label)
    n = ledger_lib.append_rows(rows, ledger_path) if rows else 0
    winner = max(swept, key=lambda s: s["value"])["id"] if swept else None
    return {"backend": backend, "ledger": ledger_path, "rows": n,
            "order": [t for t in todo if t],
            "swept": swept, "skipped": skipped, "winner": winner}
