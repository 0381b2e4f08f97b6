"""Single-chip measurement campaign for the BASELINE.md perf table.

Runs the full config matrix on the real TPU and appends each result to
``benchmarks/results_r05.json`` IMMEDIATELY after it is measured, so a
failure mid-campaign loses only the in-flight config.  Errored configs
(timeouts included) are retried on the next invocation; only successes
and structural declines are skip-cached.  The parent process never
touches a jax backend in subprocess mode: each label's child holds the
chip.

Timing method (same as bench.py): scan N steps and 4N steps, take the
difference / 3N — cancels the dispatch + readback overhead.

Usage:  python benchmarks/measure.py [--out FILE] [--only NAME ...]
"""

import argparse
import json
import math
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp

from mpi_cuda_process_tpu import init_state, make_step, make_stencil
from mpi_cuda_process_tpu.driver import make_runner
from mpi_cuda_process_tpu.ops.pallas import has_pallas_kernel, make_pallas_compute


def _fence(fields) -> float:
    # device->host scalar read: the completion fence
    return float(jnp.sum(fields[0].astype(jnp.float32)))


def _parse_kspec(spec):
    """``"4"`` -> (4, None); ``"4@16x16"`` -> (4, (16, 16)).

    Explicit tiles are the compile-complexity hedge: the auto-picked
    (64, 32) padfree window at 512^3 hung the Mosaic compile past the
    subprocess budget (2026-07-31, heat3d_512_f32_padfree4) — smaller
    explicit windows compile a strictly smaller program for the same
    kernel class.
    """
    if "@" in spec:
        k, t = spec.split("@", 1)
        # 2-tuple (bz, by) for the tiled/padfree kernels; the streaming
        # kernel also accepts a 3rd x-window extent (streamK@BZxBYxBX).
        # Arity is validated HERE so a malformed spec fails at the input
        # boundary, not as an unpack error deep in a kernel builder.
        tiles = tuple(int(v) for v in t.split("x"))
        if len(tiles) not in (2, 3):
            raise ValueError(f"tile spec {t!r}: want BZxBY or BZxBYxBX")
        return int(k), tiles
    return int(spec), None


def _parse_tune(spec):
    """Strip a trailing ``_tuneN`` token: ``"4_shard_tune2"`` ->
    ("4_shard", 2); absent -> 0.

    The kernel-variant sweep label family (Tier-D13, ISSUE 16): N is a
    1-BASED index into the autotuner's per-family campaign order
    (``policy.autotune.STREAM_SWEEP`` / ``RDMA_SWEEP``, append-only),
    resolved through :func:`policy.autotune.tune_variant` — so the
    labels stay stable while the registry grows, and the A/B against
    the same-shape default-constant row prices exactly one swept
    constant set."""
    if "_tune" not in spec:
        return spec, 0
    head, _, num = spec.rpartition("_tune")
    if not num.isdigit():
        raise ValueError(f"malformed _tune token in spec {spec!r}")
    return head, int(num)


def _parse_ens(spec):
    """Strip an ``_ensN`` token: ``"4_ens8"`` -> ("4", 8); absent -> 0.

    The batched-engine label family (round 15): N members advance
    through ONE compiled batched step and the row reports AGGREGATE
    Mcells/s across members — the A/B against the single-sim row with
    the same kernel class prices the per-pass fixed-cost amortization.
    """
    if "_ens" not in spec:
        return spec, 0
    head, _, tail = spec.partition("_ens")
    num = ""
    while tail and tail[0].isdigit():
        num, tail = num + tail[0], tail[1:]
    if not num:
        raise ValueError(f"malformed _ens token in spec {spec!r}")
    return head + tail, int(num)


def measure(name, grid, steps, dtype=None, compute="jnp", reps=3,
            params=None):
    """compute: jnp | pallas (compute_fn inside the pad step) |
    raw (whole-step raw kernel) | fusedK (3D windowed temporal blocking,
    K steps/pass; ``fusedK@BZxBY`` pins explicit tiles) | fullK (2D
    whole-grid-in-VMEM temporal blocking) | shfusedK / overlapK (sharded
    fused step over a z-only mesh of ALL devices, K steps per width-m
    exchange — overlapK adds the communication-overlapped interior/
    boundary split; needs >= 2 devices; a ``_meshZxY`` suffix pins a
    2-axis (Z, Y, 1) mesh instead — the two-axis pad-free A/B against
    the z-ring, needs Z*Y devices) | pipeK / pipeK_meshZxY (overlapK
    PLUS the cross-pass pipelined exchange: the slab-carry scan issues
    pass i+1's exchange from pass i's shell outputs, a full interior
    pass ahead of its consumer; forced pad-free on BOTH mesh families
    so the A/B against overlapK_* prices the pipeline, not a kind
    change) | streamK_shard / streamK_meshZxY
    (the STREAMING kernel sharded: z-only mesh of all devices /
    a pinned 2-axis mesh via the round-8 y-slab+corner splice — the
    kind x mesh A/B rows; an ``_ensN`` token — ``streamK_ensN_shard``,
    ``streamK_ensN_meshZxY``, also on shfused/overlap and unsharded
    stream specs — batches N members through ONE compiled step and
    reports AGGREGATE Mcells/s across members, the round-15 ensemble
    A/B) | rdmaK / rdmaK_meshZxY (the sharded
    STREAMING kernel with the IN-KERNEL remote-DMA exchange,
    stepper exchange='rdma': boundary slabs ride double-buffered VMEM
    rings into the neighbor via make_async_remote_copy, zero XLA
    ppermute in the step — the A/B against streamK_shard /
    streamK_meshZxY prices the exchange transport, same kernel class
    on both rows; a trailing ``_tuneN`` token on sharded stream and
    rdma specs — ``streamK_shard_tuneN``, ``rdmaK_tuneN`` — runs the
    same step under the autotuner registry's Nth campaign variant for
    the family (policy/autotune.py, Tier-D13): bit-exact schedule
    sweeps, keyed ``|var:<id>`` in the ledger) | grp2 / grp2het (the
    COUPLED 2-group split, parallel/groups.py: the device slice
    partitioned into two contiguous mesh groups coupled at interface
    ghost bands, each group running the unmodified sharded stepper on
    its own sub-mesh.  grp2 = same-physics equal split — the A/B
    against the monolithic sharded row prices exactly the host-
    orchestrated coupling; grp2het = the MPMD row, the named op
    2x-refined over the first z quarter plus a base-resolution heat3d
    far-field, reporting aggregate OWNED-cell Mcells/s) | copy
    (harness-calibration 1R+1W elementwise scan).
    """
    kw = dict(params or {})
    if dtype is not None:
        kw["dtype"] = dtype
    step_unit = 1
    if compute == "copy":
        # Harness calibration: a pure 1R+1W elementwise scan.  Converts to
        # GB/s as cells * 2 * itemsize / t — an absolute HBM-bandwidth
        # anchor for sanity-checking stencil Gcells/s numbers against the
        # roofline (a stencil can't beat this by more than its fusion
        # saves).
        dt = jnp.dtype(dtype or "float32")
        c = jnp.asarray(1.000001, dt)

        def step(fields):
            return (fields[0] * c,)

        mk = lambda: (jnp.zeros(grid, dt),)  # noqa: E731
        return _time_scan(step, mk, grid, steps, reps, 1)
    st = make_stencil(name, **kw)
    if compute == "raw":
        from mpi_cuda_process_tpu.ops.pallas.rawstep import make_raw_step
        step = make_raw_step(st, grid)  # interpret mode off-TPU (smoke)
        if step is None:
            raise ValueError(f"no raw step for {name} on {grid}")
    elif compute.startswith("padfree"):
        # pad-free 9-block raw-grid temporal blocking (no pad transient)
        from mpi_cuda_process_tpu.ops.pallas.fused import make_fused_step
        step_unit, tiles = _parse_kspec(compute[len("padfree"):])
        if tiles is not None and len(tiles) != 2:
            raise ValueError("tiled kernels take 2 tile extents (BZxBY)")
        step = make_fused_step(st, grid, step_unit, tiles=tiles,
                               padfree=True)
        if step is None:
            raise ValueError(f"untileable padfree k={step_unit} for {grid}")
    elif compute.startswith("stream"):
        # sliding-window manual-DMA temporal blocking: every input plane
        # loaded ONCE per k-step pass (ops/pallas/streamfused.py).
        # ``streamK_shard`` runs it SHARDED over a z-only mesh of all
        # devices (slab operands); ``streamK_meshZxY`` pins a 2-axis
        # (Z, Y, 1) mesh — the round-8 kernel class (y-slab + corner
        # operands spliced into the sliding window), the A/B against the
        # z-ring for the lowest-traffic kind.
        spec = compute[len("stream"):]
        spec, tune = _parse_tune(spec)
        mesh_zy = shard_all = None
        if "_mesh" in spec:
            spec, meshspec = spec.split("_mesh", 1)
            mz, my = meshspec.split("x", 1)
            mesh_zy = (int(mz), int(my))
        elif spec.endswith("_shard"):
            spec, shard_all = spec[:-len("_shard")], True
        spec, ens = _parse_ens(spec)
        step_unit, tiles = _parse_kspec(spec)
        variant = None
        if tune:
            if not (mesh_zy or shard_all):
                raise ValueError(
                    "_tune labels are sharded-only (the variant plumbing "
                    "rides make_sharded_fused_step)")
            if tiles is not None:
                raise ValueError(
                    "_tune labels take no tile spec (the variant IS the "
                    "tile geometry)")
            from mpi_cuda_process_tpu.policy.autotune import tune_variant
            variant = tune_variant("stream", tune)
        if mesh_zy or shard_all:
            if tiles is not None:
                raise ValueError("sharded stream labels take no tile spec")
            from mpi_cuda_process_tpu import make_mesh, shard_fields
            from mpi_cuda_process_tpu.parallel.stepper import (
                make_sharded_fused_step,
            )

            n_dev = len(jax.devices())
            need = mesh_zy[0] * mesh_zy[1] if mesh_zy else 2
            if n_dev < need:
                # environmental, not structural: retried on every run
                raise ValueError(
                    f"sharded stream labels need >= {need} devices "
                    f"(have {n_dev})")
            mesh = make_mesh((mesh_zy[0], mesh_zy[1], 1) if mesh_zy
                             else (n_dev, 1, 1))
            step = make_sharded_fused_step(st, mesh, grid, step_unit,
                                           kind="stream", ensemble=ens,
                                           variant=variant)
            if step is None:
                raise ValueError(
                    f"untileable sharded stream k={step_unit} for {grid} "
                    f"on mesh {tuple(mesh.shape.values())}"
                    + (f" under variant {variant.id}" if variant else ""))
            if not str(getattr(step, "_padfree_kind", "")).startswith(
                    "stream"):
                raise ValueError(
                    "sharded stream label did not build the streaming "
                    f"kernel (got {getattr(step, '_padfree_kind', None)!r})"
                    " — must not price a different kernel under this "
                    "label")
            if variant and getattr(step, "_kernel_variant", "") \
                    != variant.id:
                raise ValueError(
                    "_tune label did not build the swept variant (got "
                    f"{getattr(step, '_kernel_variant', None)!r}, want "
                    f"{variant.id!r}) — must not price the default "
                    "constants under a variant label")
            if ens and getattr(step, "_ensemble", 0) != ens:
                raise ValueError(
                    "ens label did not build the batched step — must "
                    "not price a single-sim step under an ens label")
            mk = lambda: shard_fields(  # noqa: E731
                init_state(st, grid, kind="auto", ensemble=ens), mesh,
                st.ndim, ensemble=bool(ens))
            return _time_scan(step, mk, grid, steps, reps, step_unit,
                              members=ens)
        from mpi_cuda_process_tpu.ops.pallas.streamfused import (
            make_stream_fused_step,
        )
        step = make_stream_fused_step(st, grid, step_unit, tiles=tiles,
                                      batch=ens)
        if step is None:
            raise ValueError(f"untileable stream k={step_unit} for {grid}")
        if ens:
            mk = lambda: init_state(st, grid, kind="auto",  # noqa: E731
                                    ensemble=ens)
            return _time_scan(step, mk, grid, steps, reps, step_unit,
                              members=ens)
    elif compute.startswith("rdma"):
        # sharded STREAMING kernel with the in-kernel remote-DMA
        # exchange (stepper exchange="rdma"): same kernel class as the
        # streamK_shard/_mesh rows, only the transport changes — the
        # A/B pair prices ppermute-on-HBM-slabs vs device-initiated
        # VMEM-ring RDMA.  The built step must really carry rdma (and
        # the streaming kernel) or the label refuses: a transport
        # fallback must never be priced under this label.
        from mpi_cuda_process_tpu import make_mesh, shard_fields
        from mpi_cuda_process_tpu.parallel.stepper import (
            make_sharded_fused_step,
        )

        spec = compute[len("rdma"):]
        spec, tune = _parse_tune(spec)
        mesh_zy = None
        if "_mesh" in spec:
            spec, meshspec = spec.split("_mesh", 1)
            mz, my = meshspec.split("x", 1)
            mesh_zy = (int(mz), int(my))
        step_unit, tiles = _parse_kspec(spec)
        if tiles is not None:
            raise ValueError("rdma labels take no tile spec")
        variant = None
        if tune:
            from mpi_cuda_process_tpu.policy.autotune import tune_variant
            variant = tune_variant("rdma", tune)
        n_dev = len(jax.devices())
        need = mesh_zy[0] * mesh_zy[1] if mesh_zy else 2
        if n_dev < need:
            # environmental, not structural: retried on every run
            raise ValueError(
                f"rdma labels need >= {need} devices (have {n_dev})")
        mesh = make_mesh((mesh_zy[0], mesh_zy[1], 1) if mesh_zy
                         else (n_dev, 1, 1))
        step = make_sharded_fused_step(st, mesh, grid, step_unit,
                                       kind="stream", exchange="rdma",
                                       variant=variant)
        if step is None:
            raise ValueError(
                f"untileable rdma stream k={step_unit} for {grid} on "
                f"mesh {tuple(mesh.shape.values())}"
                + (f" under variant {variant.id}" if variant else ""))
        if getattr(step, "_exchange", None) != "rdma" or not str(
                getattr(step, "_padfree_kind", "")).startswith("stream"):
            raise ValueError(
                "rdma label did not build the remote-DMA streaming "
                f"step (kind={getattr(step, '_padfree_kind', None)!r}, "
                f"exchange={getattr(step, '_exchange', None)!r}) — "
                "must not price a different path under this label")
        if getattr(step, "_rdma_backend", None) != "pallas-rdma":
            # the interpret-emulated path is a CPU test vehicle, never
            # a measurement — the same honesty rule as bench.py's
            # backend-tagged fallbacks
            raise ValueError(
                "rdma label built the interpret-emulated exchange "
                f"({getattr(step, '_rdma_backend', None)!r}) — a "
                "measurement row needs the compiled pallas-rdma path")
        if variant and getattr(step, "_kernel_variant", "") != variant.id:
            raise ValueError(
                "_tune label did not build the swept variant (got "
                f"{getattr(step, '_kernel_variant', None)!r}, want "
                f"{variant.id!r}) — must not price the default ring "
                "under a variant label")
        mk = lambda: shard_fields(  # noqa: E731
            init_state(st, grid, kind="auto"), mesh, st.ndim)
        return _time_scan(step, mk, grid, steps, reps, step_unit)
    elif compute.startswith("grp2"):
        # COUPLED 2-group split (parallel/groups.py, Tier-D14): two
        # contiguous device groups, each its own sub-mesh + unmodified
        # sharded stepper, coupled ONLY at the interface ghost bands.
        # The built runner must really carry >= 2 groups or the label
        # refuses: a monolithic fallback must never be priced here.
        from mpi_cuda_process_tpu.parallel import groups as groups_lib

        n_dev = len(jax.devices())
        if n_dev < 2:
            # environmental, not structural: retried on every run
            raise ValueError(
                f"grp2 labels need >= 2 devices (have {n_dev})")
        # y-sharded group meshes (mesh1xH): the ghost band makes each
        # group's local z extent odd (owned + band), which no z-sharded
        # sub-mesh divides — sharding y keeps the groups' z rows whole
        h = n_dev // 2
        m0, m1 = f":mesh1x{h}", f":mesh1x{n_dev - h}"
        transport = "device_put"
        if compute == "grp2":
            gspec = (f"{name}@0-{h - 1}{m0},"
                     f"{name}@{h}-{n_dev - 1}{m1}")
        elif compute == "grp2het":
            gspec = (f"{name}:fine@0-{h - 1}:z1/4{m0},"
                     f"heat3d:coarse@{h}-{n_dev - 1}{m1}")
        elif compute == "grp2ici":
            # round 23: the SAME equal split as grp2, bands moved as
            # ppermute rounds over the union mesh — the A/B against the
            # grp2 row prices exactly the transport swap
            transport = "collective"
            gspec = (f"{name}@0-{h - 1}{m0},"
                     f"{name}@{h}-{n_dev - 1}{m1}")
        elif compute == "grp2modes":
            # round 23: per-group execution modes — group 0 routed
            # through the overlap stepper, group 1 plain, same split as
            # grp2 so the A/B prices the mode routing alone
            gspec = (f"{name}@0-{h - 1}{m0}:overlap,"
                     f"{name}@{h}-{n_dev - 1}{m1}")
        else:
            raise ValueError(f"unknown grp2 spec {compute!r}")
        plans = groups_lib.plans_from_config(
            gspec, grid, default_dtype=dtype or "float32",
            n_devices=n_dev)
        runner = groups_lib.CoupledRunner(plans, transport=transport)
        if getattr(runner, "n_groups", 1) < 2:
            raise ValueError(
                "grp2 label built a monolithic runner (n_groups="
                f"{getattr(runner, 'n_groups', 1)}) — must not price a "
                "monolithic build under a group label")
        if transport == "collective" and \
                getattr(runner, "transport", "") != "collective":
            raise ValueError(
                "grp2ici label built the device_put transport — must "
                "not price the host path under a collective label")
        rec = _time_coupled(runner, steps, reps)
        rec.setdefault("groups", gspec)
        rec.setdefault("group_transport", transport)
        return rec
    elif compute.startswith("pipe"):
        # CROSS-PASS pipelined sharded temporal blocking: overlap split
        # + the slab-carry scan (pass i+1's exchange issued from pass
        # i's shell outputs).  Forced pad-free on the z-ring AND the
        # pinned 2-axis mesh — the pipeline rides the slab-operand
        # kinds only, and the A/B against the overlapK_* rows must
        # price the pipeline, not a silent kind change (the overlap
        # _mesh rows are forced pad-free already; the z-ring overlap
        # rows are auto — read the pair with that caveat).
        from mpi_cuda_process_tpu import make_mesh, shard_fields
        from mpi_cuda_process_tpu.parallel.stepper import (
            make_sharded_fused_step,
        )

        spec = compute[len("pipe"):]
        mesh_zy = None
        if "_mesh" in spec:
            spec, meshspec = spec.split("_mesh", 1)
            mz, my = meshspec.split("x", 1)
            mesh_zy = (int(mz), int(my))
        step_unit, tiles = _parse_kspec(spec)
        if tiles is not None:
            raise ValueError("pipelined labels take no tile spec")
        n_dev = len(jax.devices())
        need = mesh_zy[0] * mesh_zy[1] if mesh_zy else 2
        if n_dev < need:
            # environmental, not structural: retried on every run
            raise ValueError(
                f"pipelined labels need >= {need} devices (have {n_dev})")
        mesh = make_mesh((mesh_zy[0], mesh_zy[1], 1) if mesh_zy
                         else (n_dev, 1, 1))
        step = make_sharded_fused_step(st, mesh, grid, step_unit,
                                       overlap=True, padfree=True,
                                       pipeline=True)
        if step is None:
            raise ValueError(
                f"untileable pipelined k={step_unit} for {grid} on "
                f"mesh {tuple(mesh.shape.values())}")
        if not getattr(step, "_pipeline_active", False):
            raise ValueError(
                "pipelined label did not build the slab-carry scan — "
                "must not price a different schedule under this label")
        if not getattr(step, "_overlap_active", False):
            raise ValueError(
                "untileable overlap split under a pipelined label "
                "(local extent < 3m) — must not price the non-split "
                "body under this label")
        mk = lambda: shard_fields(  # noqa: E731
            init_state(st, grid, kind="auto"), mesh, st.ndim)
        # make_runner (inside _time_scan) threads the slab carry
        return _time_scan(step, mk, grid, steps, reps, step_unit)
    elif compute.startswith("overlap") or compute.startswith("shfused"):
        # sharded temporal blocking over a z-only mesh of ALL devices:
        # shfusedK = exchange-then-compute (the A row), overlapK = the
        # communication-overlapped interior/boundary split (the B row).
        # The A/B pair prices exactly the ~7%-class serial exchange gap
        # of docs/STATE.md item 6.
        from mpi_cuda_process_tpu import make_mesh, shard_fields
        from mpi_cuda_process_tpu.parallel.stepper import (
            make_sharded_fused_step,
        )

        ov = compute.startswith("overlap")
        spec = compute[len("overlap" if ov else "shfused"):]
        mesh_zy = None
        if "_mesh" in spec:
            # _meshZxY: a pinned 2-axis (Z, Y, 1) mesh — the A/B row
            # against the all-devices z-ring (surface-to-volume cuts
            # face bytes; the 2-axis pad-free kernels keep the path
            # transient-free)
            spec, meshspec = spec.split("_mesh", 1)
            mz, my = meshspec.split("x", 1)
            mesh_zy = (int(mz), int(my))
        spec, ens = _parse_ens(spec)
        step_unit, tiles = _parse_kspec(spec)
        if tiles is not None:
            raise ValueError("sharded fused labels take no tile spec")
        n_dev = len(jax.devices())
        need = mesh_zy[0] * mesh_zy[1] if mesh_zy else 2
        if n_dev < need:
            # environmental, not structural: retried on every run so the
            # first healthy multi-chip session prices these labels
            raise ValueError(
                f"sharded fused labels need >= {need} devices "
                f"(have {n_dev})")
        mesh = make_mesh((mesh_zy[0], mesh_zy[1], 1) if mesh_zy
                         else (n_dev, 1, 1))
        # 2-axis rows force the pad-free slab-operand kernels: at 512^3
        # the local block is below the auto pad-free threshold, and the
        # point of the _mesh labels is to price the NEW kernel class
        # (y-slab + corner operands) on a real chip, not the padded
        # kernel on a different topology
        step = make_sharded_fused_step(st, mesh, grid, step_unit,
                                       overlap=ov,
                                       padfree=True if mesh_zy else None,
                                       ensemble=ens)
        if mesh_zy and step is not None and \
                not str(getattr(step, "_padfree_kind", "")).startswith(
                    "yzslab"):
            raise ValueError(
                "2-axis label did not build the yz-slab pad-free kernel "
                f"(got {getattr(step, '_padfree_kind', None)!r}) — must "
                "not price a different kernel under this label")
        if step is None:
            raise ValueError(
                f"untileable sharded fused k={step_unit} for {grid} on "
                f"{n_dev} devices")
        if ov and not getattr(step, "_overlap_active", False):
            raise ValueError(
                f"untileable overlap split for {grid} on {n_dev} devices "
                "(local z < 3m) — must not price the plain step under an "
                "overlap label")
        mk = lambda: shard_fields(  # noqa: E731
            init_state(st, grid, kind="auto", ensemble=ens), mesh,
            st.ndim, ensemble=bool(ens))
        return _time_scan(step, mk, grid, steps, reps, step_unit,
                          members=ens)
    elif compute.startswith("fused"):
        from mpi_cuda_process_tpu.ops.pallas.fused import make_fused_step
        step_unit, tiles = _parse_kspec(compute[len("fused"):])
        if tiles is not None and len(tiles) != 2:
            raise ValueError("tiled kernels take 2 tile extents (BZxBY)")
        step = make_fused_step(st, grid, step_unit, tiles=tiles)
        if step is None:
            raise ValueError(f"untileable fused k={step_unit} for {grid}")
    elif compute.startswith("full"):
        # whole-grid VMEM temporal blocking (2D families)
        from mpi_cuda_process_tpu.ops.pallas.fullgrid import (
            make_fullgrid_step,
        )
        step_unit = int(compute[len("full"):])
        step = make_fullgrid_step(st, grid, step_unit)
        if step is None:
            raise ValueError(f"untileable fullgrid k={step_unit} for {grid}")
    else:
        compute_fn = None
        if compute == "pallas":
            if not has_pallas_kernel(name):
                raise ValueError(f"no pallas kernel for {name}")
            compute_fn = make_pallas_compute(st, interpret=False)
        step = make_step(st, grid, compute_fn=compute_fn)
    mk = lambda: init_state(st, grid, kind="auto")  # noqa: E731
    return _time_scan(step, mk, grid, steps, reps, step_unit)


def _time_scan(step, mk, grid, steps, reps, step_unit, members=0):
    run_a = make_runner(step, steps)
    run_b = make_runner(step, 4 * steps)
    _fence(run_a(mk()))  # compile + warm
    _fence(run_b(mk()))

    def best(run):
        b = math.inf
        for _ in range(reps):
            f = mk()
            _fence(f)
            t0 = time.perf_counter()
            _fence(run(f))
            b = min(b, time.perf_counter() - t0)
        return b

    t_a, t_b = best(run_a), best(run_b)
    from bench import NOISE_FLOOR_FRAC  # repo root is on sys.path (top)

    if t_b - t_a <= NOISE_FLOOR_FRAC * t_a:
        # t(4N) - t(N) should be ~3x t(N)'s step content; a non-positive or
        # tiny-relative delta means noise swamped the signal: report, don't
        # fabricate a plausible-looking Mcells/s from a clamped epsilon.
        return {"error": f"step time below noise floor: t_a={t_a:.4f}s "
                         f"t_b={t_b:.4f}s (timing noise; rerun)",
                "suspect": True}
    per_step = (t_b - t_a) / (3 * steps * step_unit)
    # aggregate cells: a batched row advances every member each step
    mcells = max(1, members) * math.prod(grid) / per_step / 1e6
    rec = {"ms_per_step": round(per_step * 1e3, 4),
           "mcells_per_s": round(mcells, 1)}
    if members:
        rec["ensemble"] = members
        rec["mcells_per_s_per_member"] = round(mcells / members, 1)
    return rec


def _time_coupled(runner, steps, reps):
    """Timing harness for the coupled group rounds (grp2 labels).

    Same N vs 4N differencing as ``_time_scan``, on ONE warmed runner
    (each CoupledRunner builds fresh jitted transfer closures, so a
    fresh runner per rep would re-pay tracing inside the timed region).
    The fence reads a scalar from EVERY group — the groups dispatch on
    disjoint devices as independent async streams, and fencing one
    would leave the others' work unmeasured.  Mcells/s counts OWNED
    cell updates only, aggregated across groups: band rows are coupling
    overhead, never throughput, so the hetero row's number is the
    actual cell-update rate the A/B compares against the monolithic
    row.
    """
    cells = sum(p.owned_cells for p in runner.plans)

    def rounds(n):
        for f in runner.fields:
            _fence(f)
        t0 = time.perf_counter()
        runner.run(n)
        for f in runner.fields:
            _fence(f)
        return time.perf_counter() - t0

    rounds(1)  # compile + warm every group program and transfer fn

    def best(n):
        b = math.inf
        for _ in range(reps):
            b = min(b, rounds(n))
        return b

    t_a, t_b = best(steps), best(4 * steps)
    from bench import NOISE_FLOOR_FRAC  # repo root is on sys.path (top)

    if t_b - t_a <= NOISE_FLOOR_FRAC * t_a:
        return {"error": f"step time below noise floor: t_a={t_a:.4f}s "
                         f"t_b={t_b:.4f}s (timing noise; rerun)",
                "suspect": True}
    per_round = (t_b - t_a) / (3 * steps)
    mcells = cells / per_round / 1e6
    return {"ms_per_step": round(per_round * 1e3, 4),
            "mcells_per_s": round(mcells, 1),
            "n_groups": runner.n_groups,
            "owned_cells_per_round": cells}


# (label, stencil, grid, steps, dtype, compute)
#
# ORDER IS EXECUTION ORDER, and it is risk-tiered (2026-07-31 lesson: the
# auto-tiled heat3d_512_f32_padfree4 compile hung past its 1200 s budget,
# and every label after it in file order was lost).  Tiers:
#   A — round-3 measured successes (cache-skipped on rerun);
#   B — safe pending: jnp references, calibration copies, fast structural
#       declines, retries of fast-failing labels;
#   C — 2D whole-grid VMEM kernels (new family on-chip, small programs);
#   D — NEW large Mosaic compiles (padfree >=512, deep k, bf16 k=8):
#       value-ordered, each gets the longer _RISKY budget, and a timeout
#       is recorded with its label.
CONFIGS = [
    # ── Tier A: BASELINE refresh + round-3 measured table ──
    ("heat2d_512_f32", "heat2d", (512, 512), 400, "float32", "jnp"),
    ("heat3d_256_f32", "heat3d", (256, 256, 256), 100, "float32", "jnp"),
    ("heat3d_256_bf16", "heat3d", (256, 256, 256), 100, "bfloat16", "jnp"),
    ("heat3d_512_f32", "heat3d", (512, 512, 512), 30, "float32", "jnp"),
    ("heat3d_512_bf16", "heat3d", (512, 512, 512), 30, "bfloat16", "jnp"),
    ("heat3d_256_f32_raw", "heat3d", (256, 256, 256), 100, "float32", "raw"),
    ("heat3d_512_f32_raw", "heat3d", (512, 512, 512), 30, "float32", "raw"),
    ("heat3d27_256_f32_raw", "heat3d27", (256, 256, 256), 50, "float32",
     "raw"),
    ("heat3d27_512_f32_raw", "heat3d27", (512, 512, 512), 20, "float32",
     "raw"),
    ("heat3d4th_256_f32_raw", "heat3d4th", (256, 256, 256), 50, "float32",
     "raw"),
    ("wave3d_256_f32_raw", "wave3d", (256, 256, 256), 50, "float32", "raw"),
    ("wave3d_512_f32_raw", "wave3d", (512, 512, 512), 20, "float32", "raw"),
    ("heat3d_256_f32_fused4", "heat3d", (256, 256, 256), 25, "float32",
     "fused4"),
    ("heat3d_512_f32_fused4", "heat3d", (512, 512, 512), 10, "float32",
     "fused4"),
    ("heat3d_256_f32_padfree4", "heat3d", (256, 256, 256), 25, "float32",
     "padfree4"),
    ("heat3d27_256_f32_fused4", "heat3d27", (256, 256, 256), 15, "float32",
     "fused4"),
    ("heat3d27_512_f32_fused4", "heat3d27", (512, 512, 512), 8, "float32",
     "fused4"),
    ("heat3d4th_256_f32_fused2", "heat3d4th", (256, 256, 256), 20, "float32",
     "fused2"),
    ("wave3d_256_f32_fused4", "wave3d", (256, 256, 256), 15, "float32",
     "fused4"),
    ("wave3d_512_f32_fused4", "wave3d", (512, 512, 512), 8, "float32",
     "fused4"),
    ("advect3d_256_f32_jnp", "advect3d", (256, 256, 256), 50, "float32",
     "jnp"),
    ("advect3d_256_f32_raw", "advect3d", (256, 256, 256), 50, "float32",
     "raw"),
    ("grayscott3d_256_f32_jnp", "grayscott3d", (256, 256, 256), 30,
     "float32", "jnp"),
    ("grayscott3d_256_f32_raw", "grayscott3d", (256, 256, 256), 30,
     "float32", "raw"),
    ("heat3d27_256_f32_jnp", "heat3d27", (256, 256, 256), 50, "float32",
     "jnp"),
    ("heat3d4th_256_f32_jnp", "heat3d4th", (256, 256, 256), 50, "float32",
     "jnp"),
    ("heat3d27_256_bf16_jnp", "heat3d27", (256, 256, 256), 50, "bfloat16",
     "jnp"),
    ("wave3d_256_f32", "wave3d", (256, 256, 256), 50, "float32", "jnp"),
    ("wave3d_256_bf16", "wave3d", (256, 256, 256), 50, "bfloat16", "jnp"),
    ("wave3d_512_bf16", "wave3d", (512, 512, 512), 20, "bfloat16", "jnp"),
    ("life_2048_i32", "life", (2048, 2048), 200, None, "jnp"),
    ("heat3d_256_f32_pallas", "heat3d", (256, 256, 256), 100, "float32",
     "pallas"),
    # ── Tier B: safe pending — no new Mosaic compile classes ──
    # harness calibration: pure 1R+1W elementwise scan (GB/s anchor)
    ("copy_256_f32", None, (256, 256, 256), 100, "float32", "copy"),
    ("copy_512_f32", None, (512, 512, 512), 30, "float32", "copy"),
    # advect3d 150 Gcells/s suspect resolution: different scan length +
    # larger grid (>1.2 TB/s implied traffic exceeds v5e HBM peak)
    ("advect3d_256_f32_jnp_n150", "advect3d", (256, 256, 256), 150,
     "float32", "jnp"),
    ("advect3d_512_f32_jnp", "advect3d", (512, 512, 512), 15, "float32",
     "jnp"),
    # large-grid jnp references (the cliff regime: does XLA's fusion
    # collapse like heat3d's 86->17.6?)
    ("heat3d27_512_f32_jnp", "heat3d27", (512, 512, 512), 15, "float32",
     "jnp"),
    ("heat3d4th_512_f32_jnp", "heat3d4th", (512, 512, 512), 15, "float32",
     "jnp"),
    ("sor2d_1024_f32_jnp", "sor2d", (1024, 1024), 100, "float32", "jnp"),
    ("sor3d_256_f32_jnp", "sor3d", (256, 256, 256), 30, "float32", "jnp"),
    # 1024^3 jnp/raw retries: r03 failures were FAST errors (OOM / HTTP
    # 500), not hangs; full head+tail stderr is captured this round
    ("heat3d_1024_bf16", "heat3d", (1024, 1024, 1024), 8, "bfloat16", "jnp"),
    ("heat3d_1024_bf16_raw", "heat3d", (1024, 1024, 1024), 8, "bfloat16",
     "raw"),
    ("heat3d_1024_f32_raw", "heat3d", (1024, 1024, 1024), 6, "float32",
     "raw"),
    # pure-Python structural declines (sublane misalignment) — instant
    ("heat3d_512_bf16_fused4", "heat3d", (512, 512, 512), 10, "bfloat16",
     "fused4"),
    ("heat3d_1024_bf16_fused4", "heat3d", (1024, 1024, 1024), 4, "bfloat16",
     "fused4"),
    # padded-fused-class compiles: the same builder/lowering measured on
    # chip at 256^3 AND 512^3 in round 3 (heat3d/heat3d27/wave3d fused4)
    ("advect3d_256_f32_fused4", "advect3d", (256, 256, 256), 13, "float32",
     "fused4"),
    ("advect3d_512_f32_fused4", "advect3d", (512, 512, 512), 6, "float32",
     "fused4"),
    ("grayscott3d_256_f32_fused4", "grayscott3d", (256, 256, 256), 10,
     "float32", "fused4"),
    ("grayscott3d_512_f32_fused4", "grayscott3d", (512, 512, 512), 5,
     "float32", "fused4"),
    ("sor3d_256_f32_fused4", "sor3d", (256, 256, 256), 10, "float32",
     "fused4"),
    ("heat3d4th_256_f32_fused4", "heat3d4th", (256, 256, 256), 12, "float32",
     "fused4"),
    ("heat3d4th_512_f32_fused2", "heat3d4th", (512, 512, 512), 8, "float32",
     "fused2"),
    # padded fused at 1024^3 f32: expected RESOURCE_EXHAUSTED (3x4.3 GiB
    # transient) — a fast allocation error, recorded for the table
    ("heat3d_1024_f32_fused4", "heat3d", (1024, 1024, 1024), 4, "float32",
     "fused4"),
    # ── Tier C: 2D whole-grid VMEM kernels (new family; small programs —
    # the whole grid is one VMEM block, no window assembly) ──
    ("life_2048_i32_full16", "life", (2048, 2048), 30, None, "full16"),
    ("life_1024_i32_full32", "life", (1024, 1024), 30, None, "full32"),
    ("heat2d_512_f32_full32", "heat2d", (512, 512), 40, "float32", "full32"),
    ("heat2d_2048_f32_full16", "heat2d", (2048, 2048), 20, "float32",
     "full16"),
    ("wave2d_1024_f32_full16", "wave2d", (1024, 1024), 20, "float32",
     "full16"),
    ("grayscott2d_1024_f32_full16", "grayscott2d", (1024, 1024), 15,
     "float32", "full16"),
    ("sor2d_1024_f32_full16", "sor2d", (1024, 1024), 15, "float32",
     "full16"),
    # ── Tier D: NEW large Mosaic compiles — value-ordered, _RISKY budget,
    # timeouts recorded.  A hang burns its label's budget, so the order
    # is (a) value rank (streams > 1024^3 > bf16 > padfree generality >
    # halo-2 > deep k) and (b) the suspect compile class (AUTO-tiled
    # padfree at >=512^3, which hung on 2026-07-31) last within its
    # group. ──
    # D1: the STREAMING kernel (ops/pallas/streamfused.py) — sliding-
    # window manual DMA, zero z read amplification: projects ~155
    # Gcells/s at 512^3 even at the 330 GB/s auto rate; decides
    # _AUTO_FUSE_KIND ("the headline question", VERDICT r4 next #2).
    # New compile class (run_scoped + make_async_copy + ANY refs at
    # scale): cheapest grid first to prove the class compiles.
    ("heat3d_256_f32_stream4", "heat3d", (256, 256, 256), 25, "float32",
     "stream4"),
    ("heat3d_512_f32_stream4", "heat3d", (512, 512, 512), 10, "float32",
     "stream4"),
    # the only bf16 k=4 temporal-blocking path (VERDICT r4 next #4)
    ("heat3d_512_bf16_stream4", "heat3d", (512, 512, 512), 10, "bfloat16",
     "stream4"),
    # config-5's family: two-field wave through the same class
    ("wave3d_512_f32_stream4", "wave3d", (512, 512, 512), 8, "float32",
     "stream4"),
    # D2: the >=1024^3 regime (VERDICT r4 next #3) — explicit (16,16)
    # tiles first (smallest window = smallest Mosaic program), then
    # stream; the AUTO-tiled padfree label LAST (the suspect class)
    ("heat3d_1024_f32_padfree4_t16", "heat3d", (1024, 1024, 1024), 4,
     "float32", "padfree4@16x16"),
    ("heat3d_1024_f32_stream4", "heat3d", (1024, 1024, 1024), 4, "float32",
     "stream4"),
    ("heat3d_1024_f32_padfree4", "heat3d", (1024, 1024, 1024), 4, "float32",
     "padfree4"),
    # D3: the bf16 story (VERDICT r4 next #4) at the proven-compile size
    # first; the fori_loop k=8 lowering is the designed fix for the
    # round-3 unrolled-compile hang
    ("heat3d_256_bf16_padfree8", "heat3d", (256, 256, 256), 13, "bfloat16",
     "padfree8"),
    ("heat3d_256_bf16_fused8", "heat3d", (256, 256, 256), 13, "bfloat16",
     "fused8"),
    ("heat3d_512_bf16_padfree8", "heat3d", (512, 512, 512), 6, "bfloat16",
     "padfree8"),
    ("heat3d_1024_bf16_padfree8", "heat3d", (1024, 1024, 1024), 4,
     "bfloat16", "padfree8"),
    # D4: padfree generality at 512^3.  The heat3d t16 hedge FIRST (it
    # discriminates the hang hypotheses in docs/STATE.md); wave/27-point
    # auto-tiled labels after it; the heat3d AUTO label last — it is the
    # exact label whose compile hung on 2026-07-31
    ("heat3d_512_f32_padfree4_t16", "heat3d", (512, 512, 512), 10,
     "float32", "padfree4@16x16"),
    ("wave3d_512_f32_padfree4", "wave3d", (512, 512, 512), 8, "float32",
     "padfree4"),
    ("heat3d27_512_f32_padfree4", "heat3d27", (512, 512, 512), 8, "float32",
     "padfree4"),
    ("heat3d_512_f32_padfree4", "heat3d", (512, 512, 512), 10, "float32",
     "padfree4"),
    # D5: the halo-2 family (VERDICT r4 next #6): fused4 (margin 8) is a
    # NEW halo-2 k=4 compile at 512^3 — Tier D so a hang gets the long
    # budget and cannot cost the safe tiers; stream4's sublane-rounded
    # margins host wm=8
    ("heat3d4th_512_f32_fused4", "heat3d4th", (512, 512, 512), 6, "float32",
     "fused4"),
    ("heat3d4th_512_f32_stream4", "heat3d4th", (512, 512, 512), 6,
     "float32", "stream4"),
    # D6: deeper ceiling probes (k=8/16 per-pass amortization, stream8,
    # 27-point stream)
    ("heat3d_512_f32_fused8", "heat3d", (512, 512, 512), 6, "float32",
     "fused8"),
    ("heat3d_512_f32_stream8", "heat3d", (512, 512, 512), 6, "float32",
     "stream8"),
    ("heat3d27_512_f32_stream4", "heat3d27", (512, 512, 512), 8, "float32",
     "stream4"),
    ("heat3d_512_f32_padfree8", "heat3d", (512, 512, 512), 6, "float32",
     "padfree8"),
    ("heat3d_512_f32_fused16", "heat3d", (512, 512, 512), 3, "float32",
     "fused16"),
    # D7: communication-overlapped temporal blocking A/B (needs a multi-
    # chip slice; on a single chip these decline fast and retry next
    # run).  shfusedK = exchange-then-compute over a z-only mesh of all
    # devices, overlapK = the interior/boundary split — the pair prices
    # the ~7%-class serial exchange gap (docs/STATE.md item 6) that the
    # split is designed to hide.  Mesh = (n_devices, 1, 1).
    ("heat3d_512_f32_shfused4", "heat3d", (512, 512, 512), 10, "float32",
     "shfused4"),
    ("heat3d_512_f32_overlap4", "heat3d", (512, 512, 512), 10, "float32",
     "overlap4"),
    ("heat3d_512_f32_overlap8", "heat3d", (512, 512, 512), 6, "float32",
     "overlap8"),
    ("wave3d_512_f32_shfused4", "wave3d", (512, 512, 512), 8, "float32",
     "shfused4"),
    ("wave3d_512_f32_overlap4", "wave3d", (512, 512, 512), 8, "float32",
     "overlap4"),
    # D8 (round 7): TWO-AXIS decomposition A/B — the same k/grid as the
    # z-ring rows above, on a pinned 8x8x1 mesh (needs a 64-chip slice;
    # fast environmental decline + retry elsewhere).  Surface-to-volume
    # cuts face bytes ~8x vs 64x1x1 (STATE.md ICI arithmetic, item 6),
    # and the 2-axis pad-free kernels (fused.build_yzslab_padfree_call:
    # y-slab + corner operands) keep the path transient-free — these
    # rows decide whether the decomposition shape is chosen by
    # measurement instead of kernel availability.
    ("heat3d_512_f32_shfused4_mesh8x8", "heat3d", (512, 512, 512), 10,
     "float32", "shfused4_mesh8x8"),
    ("heat3d_512_f32_overlap4_mesh8x8", "heat3d", (512, 512, 512), 10,
     "float32", "overlap4_mesh8x8"),
    ("wave3d_512_f32_shfused4_mesh8x8", "wave3d", (512, 512, 512), 8,
     "float32", "shfused4_mesh8x8"),
    ("wave3d_512_f32_overlap4_mesh8x8", "wave3d", (512, 512, 512), 8,
     "float32", "overlap4_mesh8x8"),
    # D9 (round 8): STREAMING x MESH — the sharded streaming kernel on
    # the z-ring (all devices) vs the pinned balanced 8x8x1 mesh (the
    # new 2-axis y-slab+corner splice class, needs a 64-chip slice;
    # fast environmental decline + retry elsewhere).  With D8 these
    # rows complete the kind x mesh measurement matrix: every kernel
    # class now exists on both mesh families, so decomposition shape
    # is chosen purely by these numbers.
    ("heat3d_512_f32_stream4_shard", "heat3d", (512, 512, 512), 10,
     "float32", "stream4_shard"),
    ("heat3d_512_f32_stream4_mesh8x8", "heat3d", (512, 512, 512), 10,
     "float32", "stream4_mesh8x8"),
    ("wave3d_512_f32_stream4_shard", "wave3d", (512, 512, 512), 8,
     "float32", "stream4_shard"),
    ("wave3d_512_f32_stream4_mesh8x8", "wave3d", (512, 512, 512), 8,
     "float32", "stream4_mesh8x8"),
    # the bf16 k=4 story on the balanced mesh (stream is the only k=4
    # bf16 temporal-blocking path; the 2-axis tiled kernels need k=8)
    ("wave3d_512_bf16_stream4_mesh8x8", "wave3d", (512, 512, 512), 8,
     "bfloat16", "stream4_mesh8x8"),
    # D10 (round 9): CROSS-PASS PIPELINED exchange A/B — the slab-carry
    # scan (pass i+1's exchange issued from pass i's shell outputs, one
    # full interior pass of hiding) against the round-6 overlap rows on
    # both mesh families.  Forced pad-free on the z-ring too (the
    # pipeline rides the slab-operand kinds), so read the z-ring pair
    # with the kind caveat in measure()'s docstring; the _mesh8x8 pair
    # is kind-clean (both forced pad-free).  The strong-scaling regime
    # (small per-chip blocks, interior shrinking faster than faces) is
    # where the gap should open — these 512^3 rows on a big slice are
    # exactly that regime.
    ("heat3d_512_f32_pipe4", "heat3d", (512, 512, 512), 10, "float32",
     "pipe4"),
    ("heat3d_512_f32_pipe4_mesh8x8", "heat3d", (512, 512, 512), 10,
     "float32", "pipe4_mesh8x8"),
    ("wave3d_512_f32_pipe4", "wave3d", (512, 512, 512), 8, "float32",
     "pipe4"),
    ("wave3d_512_f32_pipe4_mesh8x8", "wave3d", (512, 512, 512), 8,
     "float32", "pipe4_mesh8x8"),
    # D11 (round 12): IN-KERNEL REMOTE-DMA exchange A/B — the sharded
    # streaming kernel with exchange='rdma' (boundary slabs pushed into
    # the neighbor's VMEM rings by make_async_remote_copy; zero XLA
    # ppermute, no HBM slab transient) against the round-8
    # streamK_shard/_mesh8x8 rows: SAME kernel class both sides, only
    # the transport differs, so the pair prices exactly the exchange
    # path.  New compile class (collective pallas_call: remote DMA +
    # barrier/credit semaphores) — cheapest first to prove it compiles;
    # needs >= 2 devices (z-ring) / a 64-chip slice (_mesh8x8), fast
    # environmental decline + retry elsewhere.
    ("heat3d_512_f32_rdma4", "heat3d", (512, 512, 512), 10, "float32",
     "rdma4"),
    ("wave3d_512_f32_rdma4", "wave3d", (512, 512, 512), 8, "float32",
     "rdma4"),
    ("heat3d_512_f32_rdma4_mesh8x8", "heat3d", (512, 512, 512), 10,
     "float32", "rdma4_mesh8x8"),
    ("wave3d_512_f32_rdma4_mesh8x8", "wave3d", (512, 512, 512), 8,
     "float32", "rdma4_mesh8x8"),
    # ── Tier D12: batched ensemble engine (round 15) — *_ens8 rows:
    # 8 members advance through ONE compiled batched streaming step
    # (vmap folds the member axis into each exchange operand; one batch
    # grid dimension per kernel); the row reports AGGREGATE Mcells/s.
    # A/B against the single-sim stream4_shard/_mesh8x8 rows — same
    # kernel class, only the batching changes — prices the per-pass
    # fixed-cost amortization the ensemble engine claims.  The ledger
    # keys these rows by ensemble size (obs/ledger.baseline_key), so
    # an ens=8 aggregate can never baseline a single-sim row.
    ("heat3d_512_f32_stream4_ens8_shard", "heat3d", (512, 512, 512), 10,
     "float32", "stream4_ens8_shard"),
    ("wave3d_512_f32_stream4_ens8_shard", "wave3d", (512, 512, 512), 8,
     "float32", "stream4_ens8_shard"),
    ("heat3d_512_f32_stream4_ens8_mesh8x8", "heat3d", (512, 512, 512),
     10, "float32", "stream4_ens8_mesh8x8"),
    ("wave3d_512_f32_stream4_ens8_mesh8x8", "wave3d", (512, 512, 512),
     8, "float32", "stream4_ens8_mesh8x8"),
    # ── Tier D13: KERNEL-VARIANT sweeps (round 16, policy/autotune.py)
    # — *_tuneN rows: the same sharded streaming / rdma steps as the
    # D8/D11 rows, but under the autotuner registry's Nth campaign
    # variant for the family (1-based into STREAM_SWEEP / RDMA_SWEEP:
    # stream tune1=bz16y16 tune2=bz8y8; rdma tune1=ring3 tune2=ring4).
    # A/B against the same-shape default-constant row prices exactly
    # one swept constant set; the ledger keys these rows |var:<id>
    # (obs/ledger.baseline_key), so a variant row can never baseline
    # the default.  Each variant is bit-exact vs the default kernel
    # (pinned in tests/test_autotune.py) — these rows measure schedule,
    # never results.
    ("heat3d_512_f32_stream4_tune1_shard", "heat3d", (512, 512, 512),
     10, "float32", "stream4_shard_tune1"),
    ("heat3d_512_f32_stream4_tune2_shard", "heat3d", (512, 512, 512),
     10, "float32", "stream4_shard_tune2"),
    ("wave3d_512_f32_stream4_tune1_shard", "wave3d", (512, 512, 512),
     8, "float32", "stream4_shard_tune1"),
    ("wave3d_512_f32_stream4_tune2_shard", "wave3d", (512, 512, 512),
     8, "float32", "stream4_shard_tune2"),
    ("heat3d_512_f32_rdma4_tune1", "heat3d", (512, 512, 512), 10,
     "float32", "rdma4_tune1"),
    ("heat3d_512_f32_rdma4_tune2", "heat3d", (512, 512, 512), 10,
     "float32", "rdma4_tune2"),
    ("wave3d_512_f32_rdma4_tune1", "wave3d", (512, 512, 512), 8,
     "float32", "rdma4_tune1"),
    ("wave3d_512_f32_rdma4_tune2", "wave3d", (512, 512, 512), 8,
     "float32", "rdma4_tune2"),
    # ── Tier D14: COUPLED device groups (round 18, parallel/groups.py)
    # — *_grp2 rows: the slice partitioned into two contiguous mesh
    # groups coupled at interface ghost bands, every group running the
    # UNMODIFIED sharded stepper on its own sub-mesh.  grp2 = same-
    # physics equal split: the A/B against the monolithic sharded row
    # (same op, same total cells) prices exactly the host-orchestrated
    # coupling (interface transfers + per-group dispatch).  grp2het =
    # the MPMD row: the named op 2x-refined over the first z quarter +
    # a base-resolution heat3d far-field — aggregate owned-cell
    # Mcells/s, the cell-update win the groups engine claims.  The
    # ledger keys these rows |grp:<sig> (obs/ledger.baseline_key), so
    # a coupled row can never baseline a monolithic one.  Needs >= 2
    # devices (fast environmental decline + retry elsewhere).  bf16 and
    # mixed-dtype coupling are pinned bit-exactly on CPU
    # (tests/test_groups.py); no dedicated chip row — Tier D must stay
    # strictly under half the campaign (test_measure_campaign.py).
    ("heat3d_512_f32_grp2", "heat3d", (512, 512, 512), 10, "float32",
     "grp2"),
    ("wave3d_512_f32_grp2", "wave3d", (512, 512, 512), 8, "float32",
     "grp2"),
    ("wave3d_512_f32_grp2het", "wave3d", (512, 512, 512), 8, "float32",
     "grp2het"),
    # ── Tier D15: fast coupled groups (round 23).  *_grp2ici = the same
    # equal split moved over the COLLECTIVE interface transport (one
    # ppermute round per interface per direction inside a union-mesh
    # shard_map — zero host hops, gated by jaxprcheck): the A/B against
    # the *_grp2 row prices exactly the transport swap.  The ledger keys
    # these rows |gtx:collective so neither transport can baseline the
    # other.  *_grp2modes = per-group execution modes (group 0 overlap,
    # group 1 plain) under the default transport: the A/B against *_grp2
    # prices per-group mode routing alone.
    ("heat3d_512_f32_grp2ici", "heat3d", (512, 512, 512), 10, "float32",
     "grp2ici"),
    ("wave3d_512_f32_grp2ici", "wave3d", (512, 512, 512), 8, "float32",
     "grp2ici"),
    ("heat3d_512_f32_grp2modes", "heat3d", (512, 512, 512), 10,
     "float32", "grp2modes"),
]

# Tier-D labels: new large Mosaic compiles.  A hang here is plausibly a
# SLOW compile (the round-3 bf16 k=8 unrolled compile exceeded 20 min), so
# these get a longer leash before the kill.  Derived from CONFIGS order — everything
# at/after the first Tier-D row is risky, so a new Tier-D label can't
# silently get the short budget.
_RISKY_BUDGET_S = 2400
_TIER_D_START = "heat3d_256_f32_stream4"
_RISKY = frozenset(
    label for label, *_ in
    CONFIGS[[label for label, *_ in CONFIGS].index(_TIER_D_START):])


# Bumped whenever kernel-builder code changes in a way that can turn a
# previously "untileable" config tileable (new lowering, relaxed alignment
# gate, new kernel variant).  Cached untileable declines from an older
# builder are retried instead of skipped — tileability is a property of the
# CODE, not the config (round-3 advisor finding).
# rev 7: the 2-axis streaming kernel (build_stream_2axis_call) — forced
# stream on y-sharded meshes went from None to buildable.
# rev 8: the slab-carry pipelined stepper (pipeline=True) — new pipeK
# labels exist, and the pad-free builders are now constructed through
# one more wrapper layer (pipeline bodies), so older declines retry.
# rev 9: the in-kernel remote-DMA exchange (exchange='rdma') — new
# rdmaK labels exist, and the streaming steppers grew the transport
# layer (halo.RdmaTransport threading), so older declines retry.
# rev 11: kernel-variant plumbing (policy/autotune.py) — new *_tuneN
# labels exist, remote.py's ring kernel is parameterized over slot
# count / chunk preference and the streaming builders accept variant
# tiles through the sharded steppers, so older declines retry.
# rev 12: the coupled device-group engine (parallel/groups.py) — new
# *_grp2 labels exist, the streaming builders accept the round-18
# margin/order sweep constants, and the sharded stepper is now also
# constructed per-group over device subsets, so older declines retry.
# rev 13: fast coupled groups (round 23) — new *_grp2ici/*_grp2modes
# labels exist, the coupled engine grew the collective interface
# transport (union-mesh ppermute wire) and per-group mode routing
# through the fused/stream/overlap/pipeline steppers, so older coupled
# declines retry.
BUILDER_REV = 13


def _skip_cached(cached):
    """True iff a cached record needs no re-run — THE skip rule.

    Skips successes AND "untileable" structural declines (pure-Python
    ValueError, identical on every run at this BUILDER_REV).  Every
    other failure — timeouts included — is retried.  Single definition
    shared by main() and --count-runnable.
    """
    return cached is not None and (
        "error" not in cached
        or ("untileable" in cached.get("error", "")
            and cached.get("builder_rev") == BUILDER_REV))


def count_runnable(out_path):
    """How many campaign labels a plain run would still execute."""
    results = _read_results(out_path)
    return sum(1 for label, *_ in CONFIGS
               if not _skip_cached(results.get(label)))


def _read_results(out_path):
    if os.path.exists(out_path):
        with open(out_path) as fh:
            return json.load(fh)
    return {}


def _write_results(out_path, results):
    tmp = out_path + ".tmp"
    with open(tmp, "w") as fh:
        json.dump(results, fh, indent=1, sort_keys=True)
        fh.write("\n")
    os.replace(tmp, out_path)


def _merge_record(out_path, label, rec):
    """Atomically merge one label's record into the results file."""
    results = _read_results(out_path)
    results[label] = rec
    _write_results(out_path, results)
    print(f"[measure] {label}: {rec}", file=sys.stderr)


def _measure_one(out_path, label, name, grid, steps, dtype, compute):
    """Measure one config and merge its record into ``out_path``."""
    # Fault point (resilience/faults.py): label:name=LABEL:hang|sigkill
    # wedges exactly one campaign label deterministically — the CPU
    # trigger for the supervised-retry path (a wedge must cost the
    # in-flight attempt, never the label).
    from mpi_cuda_process_tpu.resilience import faults

    faults.maybe_fire("label", name=label)
    backend = jax.default_backend()
    t0 = time.time()
    try:
        rec = measure(name, grid, steps, dtype=dtype, compute=compute)
    except Exception as e:  # noqa: BLE001 — record & continue campaign
        msg = f"{type(e).__name__}: {e}"
        if len(msg) > 1200:
            # Mosaic failures bury the real error under log noise; the
            # diagnostic line is near the END of the message.
            msg = msg[:400] + " ...[snip]... " + msg[-800:]
        rec = {"error": msg}
    rec.update({"stencil": name, "grid": list(grid), "dtype": dtype,
                "compute": compute, "backend": backend,
                "builder_rev": BUILDER_REV,
                "wall_s": round(time.time() - t0, 1),
                "measured_at": time.time()})
    _merge_record(out_path, label, rec)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default=os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "results_r05.json"))
    ap.add_argument("--only", nargs="*", default=None)
    ap.add_argument("--in-process", action="store_true",
                    help="measure in this process instead of one subprocess "
                         "per config (an OOM then poisons later configs)")
    ap.add_argument("--count-runnable", action="store_true",
                    help="print how many labels a plain run would still "
                         "execute, then exit (no backend contact)")
    ap.add_argument("--label-restarts", type=int, default=1,
                    help="supervised retries per timed-out label "
                         "(resilience/supervisor.retry_subprocess): on a "
                         "subprocess timeout the child is killed and the "
                         "label retried after a backoff — a wedge costs "
                         "the in-flight ATTEMPT, not the label; the "
                         "attempt count lands in the record and the "
                         "ledger row (default 1; 0 restores the old "
                         "one-shot behavior)")
    ap.add_argument("--restart-backoff", type=float, default=2.0,
                    help="backoff base seconds between label retries "
                         "(doubles per retry, bounded)")
    ap.add_argument("--label-budget", type=float, default=None,
                    help="override the per-label subprocess budget in "
                         "seconds (default: the tier-derived 1200/2400 "
                         "split; test hook for the fault-injection "
                         "suite)")
    ap.add_argument("--telemetry", default=None, metavar="PATH",
                    help="write a JSONL telemetry event log (obs/ "
                         "schema, same manifest as cli --telemetry): "
                         "one 'label' event per campaign config with "
                         "its outcome, plus a stall-detecting heartbeat "
                         "whose STALLED verdicts land in the log while "
                         "a label is still hanging.  Render with "
                         "scripts/obs_report.py")
    ap.add_argument("--serve", type=int, default=None, metavar="PORT",
                    help="campaign live console (obs/serve.py "
                         "serve_campaign): an HTTP aggregator over the "
                         "telemetry directory — /status.json exposes "
                         "per-label progress (status, Mcells/s, "
                         "attempts) while the campaign runs, /metrics "
                         "the Prometheus counters, /events the "
                         "incremental NDJSON tail.  PORT 0 = ephemeral "
                         "(bound address printed + recorded as a "
                         "'serve' event).  Implies --telemetry (a "
                         "default path is derived when unset); watch "
                         "with scripts/obs_top.py URL")
    args = ap.parse_args()

    if args.count_runnable:
        print(count_runnable(args.out))
        return

    known = {label for label, *_ in CONFIGS}
    unknown = set(args.only or ()) - known
    if unknown:
        ap.error(f"unknown --only labels {sorted(unknown)}; "
                 f"choose from {sorted(known)}")

    results = _read_results(args.out)

    subprocess_mode = not args.in_process

    if args.serve is not None and not args.telemetry:
        # the console aggregates label events from the telemetry log;
        # --serve without one would be a blind server
        from mpi_cuda_process_tpu.obs import trace as _trace

        args.telemetry = os.path.join(
            _trace.default_telemetry_dir(),
            f"measure-{os.getpid()}-{int(time.time())}.jsonl")

    session = None
    if args.telemetry:
        try:
            from mpi_cuda_process_tpu import obs

            session = obs.open_session(
                args.telemetry, tool="measure",
                run={"out": os.path.abspath(args.out),
                     "only": args.only, "in_process": args.in_process,
                     "builder_rev": BUILDER_REV,
                     "n_configs": len(CONFIGS),
                     "runnable": count_runnable(args.out)},
                stall_after_s=420.0,
                # the label children hold the chip: the parent's
                # manifest leaves the device identity unset
                launcher=subprocess_mode)
        except Exception as e:  # noqa: BLE001 — never block the campaign
            print(f"[measure] telemetry disabled ({type(e).__name__}: {e})",
                  file=sys.stderr)
            session = None

    server = None
    if args.serve is not None:
        # Campaign aggregator (obs/serve.py): watches the telemetry
        # DIRECTORY (new manifests picked up between polls — child runs
        # that drop logs there appear live) plus this harness's own log
        # for the per-label progress table in /status.json.
        try:
            from mpi_cuda_process_tpu.obs import serve as serve_lib

            server = serve_lib.serve_campaign(
                os.path.dirname(os.path.abspath(args.telemetry)),
                port=args.serve)
            server.console.watch(os.path.abspath(args.telemetry))
            print(f"[measure] campaign console at {server.url} "
                  "(/status.json has the per-label table)",
                  file=sys.stderr)
            if session is not None:
                session.event("serve", url=server.url, port=server.port,
                              endpoints=["/metrics", "/status.json",
                                         "/events"])
        except Exception as e:  # noqa: BLE001 — never block the campaign
            print(f"[measure] --serve disabled ({type(e).__name__}: {e})",
                  file=sys.stderr)
            server = None

    def _tel_label(label, status=None, wall_s=None, attempts=None):
        if session is None:
            return
        rec = _read_results(args.out).get(label) or {}
        if status is None:
            status = "error" if rec.get("error") else \
                ("ok" if rec else "missing")
        payload = {"label": label, "status": status,
                   "compute": rec.get("compute"),
                   "mcells_per_s": rec.get("mcells_per_s"),
                   "error": (rec.get("error") or "")[:300] or None}
        if wall_s is not None:
            payload["wall_s"] = round(wall_s, 1)
        if attempts is not None and attempts > 1:
            # the restart trail: a value measured after a supervised
            # retry is honest but flagged (perf_gate reads this via the
            # ledger row detail)
            payload["attempts"] = attempts
        session.event("label", **payload)

    n_run = 0
    for label, name, grid, steps, dtype, compute in CONFIGS:
        if args.only and label not in args.only:
            continue
        # _skip_cached holds the skip rule (and its rationale); --only
        # bypasses it — the retry path for recorded declines.
        if not args.only and _skip_cached(results.get(label)):
            print(f"[measure] {label}: cached, skip", file=sys.stderr)
            _tel_label(label, "cached")
            continue
        n_run += 1
        t_label = time.time()
        if args.in_process:
            _measure_one(args.out, label, name, grid, steps, dtype, compute)
            _tel_label(label, wall_s=time.time() - t_label)
        else:
            # Subprocess isolation: a RESOURCE_EXHAUSTED on one config must
            # not leave the TPU arena poisoned for every config after it
            # (observed in the round-3 campaign: a 1024^3 OOM turned the
            # rest of the matrix into cascade failures).
            # Supervised retries (resilience/supervisor.retry_subprocess):
            # a timed-out attempt is killed (whole process group) and the
            # SAME label retried after a backoff, so a transient hang
            # costs the in-flight attempt, not the label.  Each attempt
            # exports FAULT_ATTEMPT so the fault harness can wedge attempt
            # 0 deterministically and prove the retry completes the label.
            from mpi_cuda_process_tpu.resilience import (
                supervisor as sup_lib,
            )

            budget = args.label_budget or (
                _RISKY_BUDGET_S if label in _RISKY else 1200)
            pre_rec = results.get(label)  # snapshot before the spawn
            res = sup_lib.retry_subprocess(
                [sys.executable, os.path.abspath(__file__),
                 "--only", label, "--in-process",
                 "--out", os.path.abspath(args.out)],
                timeout_s=budget,
                max_restarts=args.label_restarts,
                backoff_base_s=args.restart_backoff,
                cwd=os.path.dirname(
                    os.path.dirname(os.path.abspath(__file__))))
            if not res["timed_out"]:
                if res["rc"] != 0:
                    print(f"[measure] {label}: subprocess rc={res['rc']}",
                          file=sys.stderr)
                if res["attempts"] > 1:
                    # the wedge cost an attempt, not the label: the
                    # restart count rides the record into the results
                    # table and (via ingest) the ledger row, so the
                    # value stays honest-but-flagged downstream
                    child_rec = _read_results(args.out).get(label)
                    if child_rec is not None and child_rec != pre_rec:
                        child_rec["restart_attempts"] = res["attempts"] - 1
                        _merge_record(args.out, label, child_rec)
                _tel_label(label, wall_s=time.time() - t_label,
                           attempts=res["attempts"])
            else:
                # Every attempt burned its budget: recorded as an error
                # (retried by the next campaign run) — UNLESS the killed
                # child already merged a record (success OR a real error
                # diagnosis) before the kill: never clobber what the
                # child actually learned.
                print(f"[measure] {label}: supervised give-up after "
                      f"{res['attempts']} attempt(s) of {budget}s",
                      file=sys.stderr)
                if _read_results(args.out).get(label) == pre_rec:
                    _merge_record(args.out, label, {
                        "error": (f"supervised give-up: {res['attempts']} "
                                  f"attempt(s) timed out ({budget}s "
                                  "each)"),
                        "timeout": True, "stencil": name,
                        "grid": list(grid), "dtype": dtype,
                        "compute": compute, "builder_rev": BUILDER_REV,
                        "attempts": res["attempts"],
                        "wall_s": float(budget) * res["attempts"],
                        "measured_at": time.time()})
                _tel_label(label, "timeout", wall_s=time.time() - t_label,
                           attempts=res["attempts"])

    if session is not None:
        session.finish(labels_run=n_run,
                       runnable_after=count_runnable(args.out))
        session.close()
    if server is not None:
        server.close()  # final drain happens inside close()

    # Every FULL campaign run updates the durable cross-round ledger from
    # its results table (idempotent append; errored/suspect labels land
    # quarantined).  --only invocations skip it: they are the per-label
    # children (and the surgical manual retry path) — the parent ingests
    # once at campaign end, AFTER annotating supervised-retry records
    # with their attempt counts, so the ledger row carries the restart
    # trail instead of a pre-annotation duplicate winning the dedupe.
    # Never load-bearing for the campaign itself.
    if not args.only:
        try:
            from mpi_cuda_process_tpu.obs import ledger as _ledger

            _ledger.ingest_results(args.out)
        except Exception:  # noqa: BLE001
            pass

    if not args.only and os.path.exists(args.out):
        with open(args.out) as fh:
            print(fh.read())


if __name__ == "__main__":
    main()
