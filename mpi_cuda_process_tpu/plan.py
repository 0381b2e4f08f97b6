"""The execution plan: which step a run takes, and building it.

For a :class:`~.config.RunConfig` this module decides the execution path
— the jnp step, a Pallas ``compute_fn``, the whole-step raw kernel, a
fused pass (its depth k and kernel kind) or a sharded stepper —, asks
the HBM budget whether it fits, and constructs the step function and the
initial state; :func:`enable_compile_cache` places the persistent
compilation cache its programs land in.  The ``--compute auto`` tables
below are the only copy of the decision: the CLI, the serving scheduler
and admission, the policy ranker (``policy/select.py``) and
``benchmarks/mktable.py`` all read them here.  Nothing here imports the
CLI or the serving layer.
"""

from __future__ import annotations

import dataclasses
import functools
import logging
import math
import os
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from . import driver
from .config import RunConfig
from .obs.spans import region
from .ops import stencil as stencil_lib
# imported to populate the stencil registry
from .ops import advection, heat, life, reaction, sor, wave  # noqa: F401
from .parallel import mesh as mesh_lib
from .parallel import stepper as stepper_lib
from .utils import budget, checkpointing
from .utils.init import init_state, init_state_sharded

log = logging.getLogger("mpi_cuda_process_tpu")

# The --compute auto tables.  Each entry names the benchmark cell
# (BENCHMARK.json, PERF_LEDGER.jsonl) that measures it on the chip, or
# says "no chip record".  An entry decides which kernel runs: changing
# one is a measured decision, not a refactor.

# Whole-step raw Pallas kernels (ops/pallas/rawstep.py) instead of XLA's
# fusion at every size.  The raw kernel is also the single-step path of
# the fused families below when cadences, the shape or the HBM budget
# rule a fused pass out.
#   wave3d: wave3d-1024.log16 (the in-place leapfrog pass at 1024^3)
#   heat3d27, grayscott3d: no chip record
_RAW_WINS = {"heat3d27", "wave3d", "grayscott3d"}
# The raw kernel at or above _CLIFF_CELLS cells, the jnp step below.
#   heat3d, heat3d4th: no chip record (heat3d at 1024^3 takes the fused
#   pass of _AUTO_FUSE_K instead)
_RAW_ABOVE_CLIFF = {"heat3d", "heat3d4th"}
_CLIFF_CELLS = 100_000_000
# An unfused mesh run takes the sharded raw pass (rawstep.
# build_sharded_raw_call) at every size wherever it builds: bit-identical
# to the jnp sharded step, with no pad and no whole-shard copies.
#   heat3d f32: heat3d-1024-2x2.log8

# Temporal blocking (ops/pallas/fused.py), k steps per HBM pass, f32;
# taken when step accounting and the HBM budget allow (maybe_auto_fuse).
#   heat3d: heat3d-1024.log16 and heat3d-1024.log4 (pad-free, k=4)
#   wave3d: no chip record of the fused pass (at 1024^3 the budget
#           declines it and wave3d-1024.log16 runs the raw pass)
#   heat3d27: no chip record
_AUTO_FUSE_K = {"heat3d": 4, "heat3d27": 4, "wave3d": 4}
# bf16 temporal blocking: bf16's sublane tile (16) needs k=8 for halo-1
# stencils (fused._sublane).  Empty, no chip record: bf16 runs keep the
# single-step path unless --fuse is explicit.
_AUTO_FUSE_K_BF16: dict = {}
# 2D whole-grid-in-VMEM temporal blocking (ops/pallas/fullgrid.py), k
# generations per HBM residency.  Empty, no chip record.
_AUTO_FULL_K: dict = {}
# Fused kernel kind per 3D family ("stream": the sliding-window kernel
# of ops/pallas/streamfused.py).  Empty, no chip record: the streaming
# kernel runs only when --fuse-kind stream is forced.
_AUTO_FUSE_KIND: dict = {}


def uses_mesh(cfg: RunConfig) -> bool:
    """Whether this run decomposes over a device mesh (sharded step_fn).

    True for a spatial decomposition (--mesh) AND for a pure
    data-parallel ensemble (--ensemble-mesh with no spatial axes): both
    run the shard_map steppers; --ensemble alone (one device, N members
    batched) stays on the vmapped single-device path.
    """
    return (bool(cfg.mesh) and math.prod(cfg.mesh) > 1) \
        or cfg.ensemble_mesh > 1


def make_cfg_stencil(cfg: RunConfig):
    """The run's stencil: its registered op, ``--param``s and ``--dtype``."""
    params = dict(cfg.params)
    if cfg.dtype:
        params.setdefault("dtype", jnp.dtype(cfg.dtype))
    return stencil_lib.make_stencil(cfg.stencil, **params)


def table_fuse_k(stencil: str, ndim: int, dtype=None) -> Optional[int]:
    """The tables' temporal-blocking depth for a family, or None."""
    if ndim == 2:
        # 2D: whole-grid-in-VMEM temporal blocking (dtype-agnostic — the
        # kernel is exact, incl. the bit-exact int32 Life path)
        return _AUTO_FULL_K.get(stencil)
    if dtype is None or jnp.dtype(dtype) == jnp.float32:
        return _AUTO_FUSE_K.get(stencil)
    if jnp.dtype(dtype) == jnp.bfloat16:
        return _AUTO_FUSE_K_BF16.get(stencil)
    return None  # int/other dtypes: no fused 3D families


def raw_wins(stencil: str, grid) -> bool:
    """Whether the tables take the whole-step raw kernel for this grid."""
    return stencil in _RAW_WINS or (
        stencil in _RAW_ABOVE_CLIFF and math.prod(grid) >= _CLIFF_CELLS)


def auto_path(stencil: str, grid, dtype=None) -> str:
    """The path the tables give an unsharded ``--compute auto`` TPU run:
    ``"fused<k>"``, ``"raw"`` or ``"jnp"``.

    Builds nothing.  At run time the cadences, the kernel probe and the
    HBM budget may still decline a fused pass (wave3d 1024^3 f32 takes
    the raw pass), and a shape the raw kernel cannot tile runs jnp.
    """
    k = table_fuse_k(stencil, len(grid), dtype)
    if k:
        return f"fused{k}"
    return "raw" if raw_wins(stencil, grid) else "jnp"


def auto_fuse_k(cfg: RunConfig, backend: str) -> Optional[int]:
    """The fused depth ``--compute auto`` may give ``cfg`` on ``backend``:
    the table's k when nothing about the run observes single steps and
    every cadence (iters, log/checkpoint/dump/check-finite intervals) is a
    multiple of it, else None."""
    if cfg.compute != "auto" or backend != "tpu":
        return None
    k = table_fuse_k(cfg.stencil, len(cfg.grid),
                     cfg.dtype or dict(cfg.params).get("dtype"))
    if k is None:
        return None
    if (cfg.periodic or cfg.tol > 0 or cfg.debug_checks or cfg.ensemble
            or cfg.resume):
        return None
    cadences = [cfg.iters, cfg.log_every, cfg.checkpoint_every,
                cfg.check_finite, cfg.dump_every]
    if any(v % k for v in cadences if v):
        return None
    return k


def maybe_auto_fuse(cfg: RunConfig) -> RunConfig:
    """Upgrade an eligible ``--compute auto`` run to ``--fuse k``.

    Applies to the fused-kernel families of the tables (:func:`auto_fuse_k`)
    on an unsharded run with no mode flag set.  Bit-for-bit: k fused
    steps == k plain steps (tests/test_fused.py), so this is purely an
    execution-strategy choice.  Only taken when the grid is tileable (the
    kernel constructor returns None when it is not) and the HBM budget
    admits the fused run.  A kernel that then fails to compile or run
    raises its own error.
    """
    if cfg.compute != "auto" or cfg.fuse:
        return cfg
    if cfg.groups:
        # a coupled run's per-group steppers are built by the coupled
        # runner, not build(); the monolithic fuse upgrade has no step
        # to upgrade
        return cfg
    if cfg.fuse_kind != "auto":
        # a user-forced kind without --fuse must reach build()'s
        # "--fuse-kind requires an explicit --fuse K" guard, not be
        # upgraded into a kernel the auto probe never checked
        return cfg
    k = auto_fuse_k(cfg, jax.default_backend())
    if k is None:
        return cfg
    if (cfg.overlap or cfg.pipeline or cfg.exchange != "ppermute"
            or uses_mesh(cfg) or cfg.mesh):
        return cfg
    with region("sim.auto_fuse_probe"):
        return _probe_fuse(cfg, k)


def _probe_fuse(cfg: RunConfig, k: int) -> RunConfig:
    """``cfg`` upgraded to ``--fuse k`` when the kernel ``build`` would
    construct for it builds and the HBM budget admits it, else ``cfg``."""
    st = make_cfg_stencil(cfg)
    upgraded = None
    if len(cfg.grid) == 2:
        from .ops.pallas.fullgrid import make_fullgrid_step

        if make_fullgrid_step(st, cfg.grid, k) is None:
            return cfg  # unaligned extents / over the VMEM budget
        what = "whole-grid VMEM kernel"
    else:
        kind = _AUTO_FUSE_KIND.get(cfg.stencil)
        if kind == "stream":
            from .ops.pallas.streamfused import make_stream_fused_step

            # probe the exact kernel build() will construct for the
            # forced kind (no fallback there — an unprobed upgrade would
            # turn auto into a hard error)
            if make_stream_fused_step(st, cfg.grid, k) is not None:
                what = "streaming Pallas kernel"
                upgraded = dataclasses.replace(cfg, fuse=k,
                                               fuse_kind="stream")
            # stream untileable for this shape: fall through to the
            # tiled probes below (auto never hard-errors)
        if upgraded is None:
            from .ops.pallas.fused import make_fused_step, prefer_padfree

            # probe the same variants build() will construct (pad-free
            # above the HBM threshold — the 1024^3 path — with a padded
            # fallback)
            if make_fused_step(st, cfg.grid, k,
                               padfree=prefer_padfree(st, cfg.grid)) \
                    is None and make_fused_step(st, cfg.grid, k) is None:
                return cfg  # untileable shape
            what = "fused Pallas kernel"
    upgraded = upgraded or dataclasses.replace(cfg, fuse=k)
    if not _fused_fits(upgraded, st):
        return cfg
    log.info("auto: temporal blocking k=%d (%s)", k, what)
    return upgraded


def budget_args(cfg: RunConfig, st) -> dict:
    """``budget.estimate_run_bytes``'s keyword arguments for ``cfg``.

    The raw whole-step kernels carry no pad transient (the sharded one
    only its exchanged faces); the estimator is told when the run will
    actually take that path (the kernel constructor only constructs — no
    compile happens here).
    """
    compute = cfg.compute
    if not cfg.fuse and (resolve_raw_step(cfg, st) is not None
                         or _sharded_raw_builds(cfg, st)):
        compute = "raw"
    return dict(mesh=cfg.mesh, fuse=cfg.fuse, ensemble=cfg.ensemble,
                periodic=cfg.periodic, compute=compute,
                fuse_kind=cfg.fuse_kind, overlap=cfg.overlap,
                pipeline=cfg.pipeline, exchange=cfg.exchange,
                ensemble_mesh=cfg.ensemble_mesh)


def _fused_fits(cfg: RunConfig, st) -> bool:
    """Whether the HBM budget admits the fused run ``cfg``; when it does
    not, one log line gives the arithmetic and the run keeps the
    single-step path."""
    total, parts = budget.estimate_run_bytes(st, cfg.grid,
                                             **budget_args(cfg, st))
    hbm = budget.device_hbm_bytes()
    if total <= hbm:
        return True
    log.info("auto: temporal blocking k=%d declined: ~%.2f GiB per device "
             "(%s) over %.2f GiB of HBM; the single-step path runs",
             cfg.fuse, total / 2**30,
             " + ".join(f"{b / 2**30:.2f} GiB {label}"
                        for label, b in parts if b), hbm / 2**30)
    return False


def _raw_eligible(cfg: RunConfig, name: str) -> bool:
    """Structural eligibility of the unsharded whole-step raw Pallas
    kernel (a mesh run's is :func:`_sharded_raw_eligible`)."""
    if cfg.periodic or cfg.ensemble or uses_mesh(cfg) or cfg.fuse:
        return False
    if cfg.compute == "jnp" or jax.default_backend() != "tpu":
        return False
    if cfg.compute == "pallas":
        return True
    return raw_wins(name, cfg.grid)


def resolve_raw_step(cfg: RunConfig, st):
    """Whole-step raw Pallas kernel for eligible unsharded TPU runs, or None.

    Replaces step construction entirely (state is its own halo — see
    ops/pallas/rawstep.py); selected by the tables (:func:`raw_wins`), or
    always under explicit ``--compute pallas`` where supported.
    """
    from .ops.pallas import rawstep

    if not _raw_eligible(cfg, st.name):
        return None
    if not rawstep.raw_step_supported(st):
        return None
    return rawstep.make_raw_step(st, cfg.grid)


def _sharded_raw_eligible(cfg: RunConfig) -> bool:
    """Whether a mesh run may take the sharded whole-step raw kernel: an
    unfused ``--compute auto`` run on a TPU, guard-frame, unbatched, with
    the plain ppermute exchange and no overlap split.  The kernel builder
    decides the rest (stencil, dtype, an unsharded x axis, tiling)."""
    return (uses_mesh(cfg) and cfg.compute == "auto" and not cfg.fuse
            and jax.default_backend() == "tpu"
            and not (cfg.periodic or cfg.ensemble or cfg.overlap
                     or cfg.pipeline)
            and cfg.exchange == "ppermute")


def _sharded_raw_builds(cfg: RunConfig, st) -> bool:
    """Whether :func:`_build` will take the sharded whole-step raw kernel
    for ``cfg``, without a mesh (the kernel constructor only constructs)."""
    from .ops.pallas.rawstep import build_sharded_raw_call

    if not _sharded_raw_eligible(cfg) or len(cfg.mesh) != len(cfg.grid) \
            or any(g % c for g, c in zip(cfg.grid, cfg.mesh)):
        return False
    local = tuple(g // c for g, c in zip(cfg.grid, cfg.mesh))
    return build_sharded_raw_call(st, local, cfg.grid) is not None


def resolve_compute_fn(cfg: RunConfig, st):
    """The Pallas ``compute_fn`` (run inside the pad-based step) under
    ``--compute pallas``, else None: auto's Pallas paths are
    :func:`resolve_raw_step` and :func:`maybe_auto_fuse`."""
    from .ops.pallas import has_pallas_kernel, make_pallas_compute

    if cfg.compute != "pallas":
        return None
    if not has_pallas_kernel(st.name):
        raise ValueError(f"no pallas kernel for {st.name!r}")
    return make_pallas_compute(st)


def _abstract_fields(st, cfg: RunConfig, sharding):
    """ShapeDtypeStruct targets for a resume — nothing is materialized."""
    shape = (cfg.ensemble, *cfg.grid) if cfg.ensemble else tuple(cfg.grid)
    return tuple(jax.ShapeDtypeStruct(shape, st.dtype, sharding=sharding)
                 for _ in range(st.num_fields))


def _validate_ensemble(cfg: RunConfig) -> None:
    """Fail-fast checks for the batched-run flags (before any build)."""
    if cfg.ensemble_mesh > 1:
        if not cfg.ensemble:
            raise ValueError(
                "--ensemble-mesh shards the member axis of a batched "
                "run; it needs --ensemble N")
        if cfg.ensemble % cfg.ensemble_mesh:
            raise ValueError(
                f"--ensemble {cfg.ensemble} not divisible by "
                f"--ensemble-mesh {cfg.ensemble_mesh}")
    if cfg.ensemble_perturb and not cfg.ensemble:
        raise ValueError(
            "--ensemble-perturb perturbs ensemble members; it needs "
            "--ensemble N")


def _resume(cfg: RunConfig, targets):
    """Load the latest checkpoint (format auto-detected) onto ``targets``.

    ``targets`` are abstract ShapeDtypeStructs carrying the run's shardings:
    an Orbax restore lands per-shard directly onto them (re-sharding across
    meshes, no host gather); an npy restore is re-placed onto the same
    shardings.  Returns ``(fields, start_step)``.
    """
    loaded, start_step, _ = checkpointing.load_any(
        cfg.checkpoint_dir, target_fields=targets)
    out = []
    for tgt, new in zip(targets, loaded):
        if isinstance(new, np.ndarray):
            new = jnp.asarray(new)
            if tgt.sharding is not None:
                new = jax.device_put(new, tgt.sharding)
        out.append(new)
    log.info("resumed from %s at step %d", cfg.checkpoint_dir, start_step)
    return tuple(out), start_step


def build(cfg: RunConfig):
    """Materialize (stencil, step_fn, fields, start_step) from a config,
    inside the ``sim.build`` region."""
    with region("sim.build"):
        return _build(cfg)


def _build(cfg: RunConfig):
    st = make_cfg_stencil(cfg)

    start_step = 0
    _validate_ensemble(cfg)
    use_mesh = uses_mesh(cfg)
    m = mesh_lib.make_mesh(cfg.mesh, ensemble=cfg.ensemble_mesh or 1) \
        if use_mesh else None
    resuming = (cfg.resume and cfg.checkpoint_dir
                and checkpointing.checkpoint_format(cfg.checkpoint_dir))
    if resuming:
        # Only shapes/dtypes/shardings are needed: the checkpoint supplies
        # the values, so no initial state is computed at all.  Unsharded
        # runs still carry a concrete single-device sharding so an orbax
        # restore re-shards onto THIS run's placement (never the on-disk
        # mesh, which may not exist here).
        from jax.sharding import NamedSharding, SingleDeviceSharding

        if m is not None:
            spec = stepper_lib.ensemble_partition_spec(st.ndim, m) \
                if cfg.ensemble else \
                stepper_lib.grid_partition_spec(st.ndim, m)
            sharding = NamedSharding(m, spec)
        else:
            sharding = SingleDeviceSharding(jax.devices()[0])
        fields = _abstract_fields(st, cfg, sharding)
    elif m is not None:
        # Shard-native init: each device computes its own block(s); no
        # process materializes the full grid (init_state_sharded) — the
        # member axis lands directly on the ensemble mesh axis when one
        # exists.
        fields = init_state_sharded(
            st, cfg.grid, m, cfg.seed, cfg.density, cfg.init,
            periodic=cfg.periodic, ensemble=cfg.ensemble,
            perturb=cfg.ensemble_perturb)
    else:
        # one jitted program: XLA fuses draw, cast and frame pin into one
        # pass — eagerly each op holds a full-grid temporary, and 1024^3
        # f32 ran out of the chip's 16 GiB
        fields = jax.jit(functools.partial(
            init_state, st, cfg.grid, cfg.seed, cfg.density, cfg.init,
            periodic=cfg.periodic, ensemble=cfg.ensemble,
            perturb=cfg.ensemble_perturb))()
    if cfg.fuse_kind != "auto" and not cfg.fuse:
        # a forced kind with auto-selected fuse would route maybe_auto_fuse
        # upgrades into a kernel that was never probed (and silently no-op
        # off-TPU) — require the explicit pairing
        raise ValueError("--fuse-kind requires an explicit --fuse K")
    if cfg.exchange == "rdma":
        # a forced exchange mode is never silently ignored (the same
        # contract as a forced kind): every unsupported combination
        # raises with the reason BEFORE any build work
        if not cfg.fuse:
            raise ValueError(
                "--exchange rdma requires an explicit --fuse K (the "
                "in-kernel remote-DMA exchange feeds the streaming "
                "temporal-blocking kernels)")
        if not use_mesh:
            raise ValueError(
                "--exchange rdma needs --mesh: an unsharded run has no "
                "halo exchange for the remote-DMA ring to carry")
        if cfg.fuse_kind != "stream":
            raise ValueError(
                "--exchange rdma rides the streaming kernel family: "
                "force --fuse-kind stream (the VMEM-ring kernels the "
                "remote DMA feeds) or drop --exchange rdma")
        if cfg.periodic:
            raise ValueError(
                "--exchange rdma is guard-frame only (the streaming "
                "kernels have no periodic wrap path)")
    variant = None
    if cfg.kernel_variant:
        # a forced kernel variant follows the forced-flag contract: an
        # unknown id or an infeasible (shape, dtype, mesh) combination
        # raises with the named reason before any build work — the
        # default-constant kernel is never silently measured under a
        # variant label.  Deferred: policy imports this module.
        from .policy import autotune as autotune_lib

        variant = autotune_lib.resolve_variant(cfg, st)
    if cfg.pipeline and not cfg.fuse:
        # a requested pipeline must never be silently ignored (the
        # forced-flag contract): without temporal blocking there are no
        # fused passes for the slab carry to span
        raise ValueError("--pipeline requires an explicit --fuse K "
                         "(the slab-carry scan pipelines the exchange "
                         "across fused passes)")
    if cfg.fuse:
        if cfg.compute == "pallas":
            raise ValueError("--fuse replaces the whole step; it excludes "
                             "--compute pallas")
        if cfg.overlap and not use_mesh:
            raise ValueError(
                "--overlap with --fuse needs --mesh: the split overlaps "
                "the halo exchange with the interior kernel, and an "
                "unsharded run has no exchange to overlap")
        if cfg.pipeline and not use_mesh:
            raise ValueError(
                "--pipeline needs --mesh: the slab-carry scan pipelines "
                "the width-m halo exchange across fused passes, and an "
                "unsharded run has no exchange to pipeline")
        if cfg.fuse_kind != "auto" and (
                st.ndim == 2
                or (use_mesh and cfg.fuse_kind not in ("stream",
                                                       "padfree"))):
            raise ValueError(
                "--fuse-kind selects the 3D kernel variant; 2D grids use "
                "the whole-grid VMEM kernel, and sharded runs support "
                "'stream' and 'padfree' on any z-only or 2-axis z/y "
                "mesh (the slab-operand kernels); the "
                "exchange-composed tiled kernels are 'auto'")
        if use_mesh:
            # k fused steps per width-k*halo exchange (the 4096^3-class
            # configuration: decomposition AND temporal blocking); 2D
            # grids use the whole-local-block VMEM kernel under a row
            # decomposition (the reference's own 1-D split, k-amortized)
            kind = cfg.fuse_kind if cfg.fuse_kind in ("stream",
                                                      "padfree") else None
            fused = stepper_lib.make_sharded_temporal_step(
                st, m, cfg.grid, cfg.fuse, periodic=cfg.periodic,
                kind=kind, overlap=cfg.overlap, pipeline=cfg.pipeline,
                exchange=cfg.exchange, ensemble=cfg.ensemble,
                variant=variant)
            if cfg.overlap and fused is not None and \
                    not getattr(fused, "_overlap_active", False):
                log.warning(
                    "--overlap: block geometry cannot host the interior/"
                    "boundary split (local extent < 3*k*halo*phases on a "
                    "sharded axis); running the plain exchange-then-"
                    "compute fused step"
                    + (" (the slab-carry pipeline stays active on the "
                       "non-split body)" if cfg.pipeline else ""))
            if fused is None:
                raise ValueError(
                    f"--fuse {cfg.fuse} + --mesh {cfg.mesh}"
                    + (f" --fuse-kind {kind}" if kind else "")
                    + (" --pipeline" if cfg.pipeline else "")
                    + (" --exchange rdma" if cfg.exchange == "rdma"
                       else "")
                    + f" unsupported for {st.name} on {cfg.grid}: needs a "
                    f"fused kernel, an unsharded lane axis"
                    + (", guard-frame BCs, local z >= 3 chunks of >= "
                       "2*k*halo planes (any z/y mesh)"
                       if kind == "stream" else "")
                    + (", a slab-operand kernel that tiles the local "
                       "block (no padded fallback under a forced kind)"
                       if kind == "padfree" else "")
                    + ", aligned per-shard extents, and blocks >= the "
                    "k-step margin")
        elif st.ndim == 2:
            # 2D grids fit VMEM whole: k steps per HBM residency, exact
            # (no windows, no alignment constraint on k)
            from .ops.pallas.fullgrid import make_fullgrid_step
            fused = make_fullgrid_step(st, cfg.grid, cfg.fuse,
                                       periodic=cfg.periodic)
            if fused is None:
                raise ValueError(
                    f"--fuse {cfg.fuse} unsupported for {st.name} on grid "
                    f"{cfg.grid} (needs a 2D micro family, sublane/lane-"
                    f"aligned extents, and a grid within the VMEM budget)")
        elif cfg.fuse_kind == "stream":
            from .ops.pallas.streamfused import make_stream_fused_step

            if cfg.periodic:
                raise ValueError(
                    "--fuse-kind stream is guard-frame only (the "
                    "manual-DMA kernel has no periodic wrap path)")
            # --ensemble N batches the streaming kernel with an EXPLICIT
            # leading batch grid dimension (round 15 — the old
            # 'unbatched only' wall is gone); the returned step is
            # already batched, so the vmap wrap below is skipped
            fused = make_stream_fused_step(st, cfg.grid, cfg.fuse,
                                           batch=cfg.ensemble)
            if fused is None:
                raise ValueError(
                    f"--fuse {cfg.fuse} --fuse-kind stream unsupported for "
                    f"{st.name} on {cfg.grid}: needs a 3D fused family, "
                    f"Z >= 3 z-chunks of >= 2*k*halo planes, and a y strip "
                    f"within the VMEM budget")
        else:
            from .ops.pallas.fused import make_fused_step, prefer_padfree
            # pad-free (9-block raw-grid) kernel for 1024^3-class grids,
            # where the padded path's full-grid pad transient exhausts HBM
            if cfg.fuse_kind == "auto":
                padfree = prefer_padfree(st, cfg.grid,
                                         batch=cfg.ensemble or 1)
            else:
                padfree = cfg.fuse_kind == "padfree"
            # tiled-family variants (policy/autotune.py round 23) carry
            # explicit window tiles for the padded kernel; resolve_variant
            # already pinned fuse_kind == "tiled" (so padfree is False)
            # and pre-validated the geometry through _tiles_valid
            tiles = (variant.tiles if variant is not None
                     and variant.family == "tiled" else None)
            fused = make_fused_step(st, cfg.grid, cfg.fuse, tiles=tiles,
                                    periodic=cfg.periodic, padfree=padfree)
            if fused is not None and tiles is not None:
                # same introspection tag the sharded steppers carry
                fused._kernel_variant = variant.id
            if fused is None and padfree and cfg.fuse_kind == "auto":
                # pad-free untileable (VMEM window gate): padded fallback
                fused = make_fused_step(st, cfg.grid, cfg.fuse,
                                        periodic=cfg.periodic)
            if fused is None:
                raise ValueError(
                    f"--fuse {cfg.fuse} unsupported for {st.name} on grid "
                    f"{cfg.grid} (need a fused kernel, 2*k*halo a multiple "
                    f"of the dtype's sublane tile — 8 for f32, 16 for bf16 "
                    f"— and an aligned tiling)")
        if cfg.ensemble and getattr(fused, "_ensemble", 0) != \
                cfg.ensemble:
            # N independent universes, each advancing k steps per kernel
            # pass: vmap adds a leading batch grid dimension to the
            # pallas_call (per-universe equivalence for both the 2D
            # whole-grid and 3D windowed kernels —
            # tests/test_cli.py::test_ensemble_composes_with_fuse{,_3d}).
            # The sharded and streaming constructors return ALREADY-batched
            # steps (they tag _ensemble); only the unsharded tiled /
            # 2D kinds take the plain vmap wrap here.
            fused = driver.make_ensemble_step(fused)
        if resuming:
            fields, start_step = _resume(cfg, fields)
        # fused step_fn advances cfg.fuse steps per call; run() accounts.
        return st, fused, fields, start_step
    raw_step = resolve_raw_step(cfg, st)
    compute_fn = None if raw_step is not None else resolve_compute_fn(cfg, st)
    if cfg.ensemble and not use_mesh:
        step_fn = driver.make_ensemble_step(driver.make_step(
            st, cfg.grid, periodic=cfg.periodic, compute_fn=compute_fn))
        if resuming:
            fields, start_step = _resume(cfg, fields)
        return st, step_fn, fields, start_step
    if use_mesh:
        step_fn = stepper_lib.make_sharded_raw_step(st, m, cfg.grid) \
            if _sharded_raw_eligible(cfg) else None
        if step_fn is not None:
            log.info("compute: sharded whole-step raw Pallas kernel (%s)",
                     st.name)
        else:
            step_fn = stepper_lib.make_sharded_step(
                st, m, cfg.grid, periodic=cfg.periodic,
                compute_fn=compute_fn, overlap=cfg.overlap,
                ensemble=cfg.ensemble)
    elif raw_step is not None:
        log.info("compute: whole-step raw Pallas kernel (%s)", st.name)
        step_fn = raw_step
    else:
        step_fn = driver.make_step(
            st, cfg.grid, periodic=cfg.periodic, compute_fn=compute_fn)
    # Resume AFTER sharding so the restore lands on the target sharding
    # (orbax: per-shard reads, no host gather).
    if resuming:
        fields, start_step = _resume(cfg, fields)
    return st, step_fn, fields, start_step


def check_mem_budget(cfg: RunConfig, groups=None) -> None:
    """Refuse-with-arithmetic HBM guard (TPU backends; utils/budget.py).

    ``groups``: a coupled run's group plans (``parallel/groups.py``),
    each priced on its own devices; the worst group decides.
    """
    if cfg.mem_check == "off" or jax.default_backend() != "tpu":
        return
    try:
        if groups is not None:
            total, _ = budget.check_coupled_budget(
                groups, transport=cfg.group_transport)
        else:
            st = make_cfg_stencil(cfg)
            total, _ = budget.check_budget(st, cfg.grid,
                                           **budget_args(cfg, st))
    except ValueError:
        if cfg.mem_check == "error":
            raise
        log.warning("HBM budget exceeded (--mem-check warn): proceeding "
                    "anyway; expect RESOURCE_EXHAUSTED", exc_info=True)
    else:
        log.debug("HBM budget (coupled): worst group ~%.2f GiB/device"
                  if groups is not None else
                  "HBM budget: ~%.2f GiB/device estimated", total / 2**30)


# The checkout-local default of the persistent compilation cache: a fixed
# path (git-ignored), since the path is part of the cache's key.
DEFAULT_COMPILE_CACHE = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    ".jax_cache")


def enable_compile_cache(directory=None):
    """Turn on jax's persistent compilation cache; returns its directory.

    Placement: ``JAX_COMPILATION_CACHE_DIR`` when set (jax reads it
    itself, and no other directory is set here); else ``directory``
    (``--compile-cache DIR``); else :data:`DEFAULT_COMPILE_CACHE`.  Off
    (returns None) when ``jax_enable_compilation_cache`` is false, as
    tests/conftest.py sets it.  The min-compile-time / min-entry-size
    floors are zeroed so every program lands in the cache.
    """
    from jax.experimental.compilation_cache import compilation_cache

    if not jax.config.jax_enable_compilation_cache:
        return None
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    chosen = env_dir or str(directory or DEFAULT_COMPILE_CACHE)
    if not env_dir:
        jax.config.update("jax_compilation_cache_dir", chosen)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    # jax latches "no cache" at the first compile of the process; a
    # long-lived engine enabling the cache after an earlier compile must
    # re-initialize it or the directory is ignored
    compilation_cache.reset_cache()
    return chosen
