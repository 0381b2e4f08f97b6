"""Weak/strong-scaling benchmark harness (BASELINE.json north star).

The reference has no benchmark instrumentation at all (SURVEY.md §6); its
scaling story is fixed at exactly 2 ranks.  This harness measures the
framework's domain-decomposition scaling on any device population:

  * **weak scaling**: per-device block held fixed while the mesh grows; the
    headline metric is Mcells/s/device and efficiency vs the 1-device run
    (target >=90% at 64 chips, BASELINE.md).
  * **strong scaling**: global grid held fixed while the mesh grows.
  * **halo overhead**: per-step cost of the exchange, isolated by timing the
    same local block with and without the sharded exchange path.

Runs identically on a real TPU slice and on virtual CPU devices
(``--virtual N`` forces ``xla_force_host_platform_device_count`` — the
numbers are then only relative, but the harness and its efficiency
accounting are what ship).  Results print as a table plus one JSON line per
config for machine consumption.

Usage::

    python benchmarks/scaling.py --mode weak --stencil heat3d \
        --block 64,64,64 --steps 20 --virtual 8
    python benchmarks/scaling.py --mode strong --stencil heat3d \
        --grid 128,128,128 --steps 20 --virtual 8
"""

from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _setup_devices(virtual: int):
    if virtual:
        # Shared CPU-forcing recipe (repo-root cpuforce.py); only
        # effective if the jax backend is not yet initialized.
        from cpuforce import force_cpu

        force_cpu(virtual)
    import jax

    return jax


def _mesh_ladder(n_devices: int, ndim: int):
    """Mesh shapes 1, 2, 4, ... n_devices, factored over ndim axes."""
    from mpi_cuda_process_tpu.parallel.mesh import factor_mesh

    n = 1
    out = []
    while n <= n_devices:
        out.append(factor_mesh(n, ndim))
        n *= 2
    return out


def _time_run(run, fields, reps: int) -> float:
    import jax
    import jax.numpy as jnp

    def fence(fs):
        return float(jnp.sum(fs[0]))

    best = math.inf
    for _ in range(reps):
        t0 = time.perf_counter()
        fence(run(fields))
        best = min(best, time.perf_counter() - t0)
    return best


def bench_config(st, mesh_shape, global_shape, steps, reps=3, overlap=False,
                 fuse=0, fuse_kind=None, pipeline=False,
                 exchange="ppermute", ensemble=0):
    import jax

    from mpi_cuda_process_tpu import (
        init_state, make_mesh, make_sharded_step, make_step, shard_fields,
    )
    from mpi_cuda_process_tpu.driver import make_ensemble_step, make_runner

    n_dev = math.prod(mesh_shape)
    step_unit = 1
    kernel_kind = None  # which slab-operand kernel carried the rung
    if pipeline and n_dev == 1:
        return None  # no exchange to pipeline on the 1-device rung
    if exchange == "rdma" and n_dev == 1:
        return None  # no exchange for the remote-DMA ring to carry
    if n_dev > 1:
        mesh = make_mesh(mesh_shape)
        if fuse > 1:
            # temporal blocking UNDER decomposition: k micro-steps per
            # width-k exchange — the 4096^3-class execution strategy
            # (3D windowed kernel / 2D whole-local-block kernel).  With
            # ``overlap`` the width-m exchange is scheduled concurrently
            # with the interior kernel (interior/boundary split) — the
            # A/B rows this harness emits price exactly that split.
            from mpi_cuda_process_tpu.parallel.stepper import (
                make_sharded_temporal_step,
            )

            step = make_sharded_temporal_step(st, mesh, global_shape, fuse,
                                              kind=fuse_kind,
                                              overlap=overlap,
                                              pipeline=pipeline,
                                              exchange=exchange,
                                              ensemble=ensemble)
            if step is None:
                return None
            if exchange == "rdma" and \
                    getattr(step, "_exchange", None) != "rdma":
                # a row labeled exchange=rdma must not silently price
                # the ppermute transport
                return None
            if overlap and not getattr(step, "_overlap_active", False):
                # a row labeled overlap=true must not silently price the
                # plain step (geometry declined the split)
                return None
            if pipeline and not getattr(step, "_pipeline_active", False):
                # a row labeled pipeline=true must not silently price the
                # per-pass exchange schedule
                return None
            if fuse_kind == "stream" and not str(
                    getattr(step, "_padfree_kind", "")).startswith(
                        "stream"):
                # a stream-labeled rung must not silently price another
                # kernel class
                return None
            if fuse_kind == "padfree" and not str(
                    getattr(step, "_padfree_kind", "")).startswith(
                        ("zslab", "yzslab")):
                # same contract for forced pad-free rungs
                return None
            kernel_kind = getattr(step, "_padfree_kind", None)
            step_unit = fuse
        else:
            step = make_sharded_step(st, mesh, global_shape, overlap=overlap,
                                     ensemble=ensemble)
    elif fuse > 1:
        if st.ndim == 2:
            from mpi_cuda_process_tpu.ops.pallas.fullgrid import (
                make_fullgrid_step,
            )

            step = make_fullgrid_step(st, global_shape, fuse)
        elif fuse_kind == "stream":
            from mpi_cuda_process_tpu.ops.pallas.streamfused import (
                make_stream_fused_step,
            )

            step = make_stream_fused_step(st, global_shape, fuse,
                                          batch=ensemble)
        else:
            from mpi_cuda_process_tpu.ops.pallas.fused import make_fused_step

            step = make_fused_step(st, global_shape, fuse,
                                   padfree=fuse_kind == "padfree")
        if step is None:
            return None
        step_unit = fuse
    else:
        step = make_step(st, global_shape)
    if ensemble and n_dev == 1 and \
            getattr(step, "_ensemble", 0) != ensemble:
        # 1-device rungs: batch the plain/tiled step by vmap (the
        # streaming builder and the sharded steppers arrive batched)
        step = make_ensemble_step(step)
    fields = init_state(st, global_shape, kind="auto", ensemble=ensemble)
    if n_dev > 1:
        fields = shard_fields(fields, mesh, st.ndim,
                              ensemble=bool(ensemble))
    # No donation: the same input fields are reused across timing reps.
    run_nodonate = make_runner(step, steps, jit=False)
    run = jax.jit(run_nodonate)
    import jax.numpy as jnp

    float(jnp.sum(run(fields)[0]))  # compile + warm
    t = _time_run(run, fields, reps)
    # aggregate cells: a batched rung advances every member each step
    cells = max(1, ensemble) * math.prod(global_shape)
    return (cells * steps * step_unit / t / 1e6, t / (steps * step_unit),
            kernel_kind)


def bench_groups(name, n_dev, n_groups, global_shape, steps, reps=3,
                 transport="device_put"):
    """Coupled device-group rung (--groups): N same-physics groups.

    The rung's devices split into N contiguous equal groups, each on a
    y-sharded (1, H) sub-mesh running the unmodified plain sharded
    stepper, coupled at the interface ghost bands
    (parallel/groups.py).  Mcells/s counts OWNED cell updates only,
    aggregated across groups, and the fence reads a scalar from every
    group — they dispatch on disjoint devices as independent async
    streams.  Returns None when the geometry cannot host the split
    (the caller skips the rung, never silently runs monolithic).
    """
    import jax.numpy as jnp

    from mpi_cuda_process_tpu.parallel import groups as groups_lib

    if n_dev < n_groups or n_dev % n_groups:
        return None
    h = n_dev // n_groups
    gspec = ",".join(
        f"{name}@{g * h}-{(g + 1) * h - 1}:mesh1x{h}"
        for g in range(n_groups))
    try:
        plans = groups_lib.plans_from_config(gspec, global_shape,
                                             n_devices=n_dev)
        runner = groups_lib.CoupledRunner(plans, transport=transport)
    except ValueError:
        # structural decline (z share / y sharding indivisible, or a
        # geometry the collective wire rejects by name)
        return None
    if getattr(runner, "n_groups", 1) != n_groups:
        return None  # must not price a different split under this rung
    if getattr(runner, "transport", "device_put") != transport:
        return None  # must not price one transport under the other's row

    def rounds(n):
        for fs in runner.fields:
            float(jnp.sum(fs[0].astype(jnp.float32)))
        t0 = time.perf_counter()
        runner.run(n)
        for fs in runner.fields:
            float(jnp.sum(fs[0].astype(jnp.float32)))
        return time.perf_counter() - t0

    rounds(1)  # compile + warm every group program and transfer fn
    best = math.inf
    for _ in range(reps):
        best = min(best, rounds(steps))
    cells = sum(pl.owned_cells for pl in runner.plans)
    return cells * steps / best / 1e6, best / steps, gspec


def bench_halo_overhead(st, mesh_shape, global_shape, steps, reps=3):
    """Per-step halo-exchange cost, isolated (SURVEY.md §5.1 attribution).

    Times the sharded step (exchange + update) against an exchange-free
    variant of the same local block update (the BCs-only padding path), on
    the same mesh.  The difference per step is the exchange + boundary-splice
    cost the decomposition adds.
    """
    import jax
    import jax.numpy as jnp

    from mpi_cuda_process_tpu import (
        init_state, make_mesh, make_sharded_step, shard_fields,
    )
    from mpi_cuda_process_tpu.driver import make_runner
    from mpi_cuda_process_tpu.parallel.halo import exchange_and_pad
    from mpi_cuda_process_tpu.parallel.stepper import (
        grid_partition_spec, shard_map,
    )

    mesh = make_mesh(mesh_shape)
    step = make_sharded_step(st, mesh, global_shape)

    # exchange-free control: same local compute, halo from BC constants only
    ndim = st.ndim

    def local_only(fields):
        padded = tuple(
            exchange_and_pad(f, (None,) * ndim, (1,) * ndim, fh, bc)
            for f, bc, fh in zip(fields, st.bc_value, st.field_halos))
        return st.update(padded)

    spec = grid_partition_spec(ndim, mesh)
    nostep = shard_map(local_only, mesh=mesh, in_specs=(spec,),
                       out_specs=spec, check_vma=False)

    fields = shard_fields(
        init_state(st, global_shape, kind="auto"), mesh, ndim)
    r_full = jax.jit(make_runner(step, steps, jit=False))
    r_local = jax.jit(make_runner(nostep, steps, jit=False))
    for r in (r_full, r_local):
        float(jnp.sum(r(fields)[0]))
    t_full = _time_run(r_full, fields, reps) / steps
    t_local = _time_run(r_local, fields, reps) / steps
    return t_full, t_local


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--mode", choices=["weak", "strong", "halo"],
                   default="weak")
    p.add_argument("--stencil", default="heat3d")
    p.add_argument("--block", default="64,64,64",
                   help="per-device block (weak mode)")
    p.add_argument("--grid", default="128,128,128",
                   help="global grid (strong mode)")
    p.add_argument("--steps", type=int, default=20)
    p.add_argument("--reps", type=int, default=3)
    p.add_argument("--virtual", type=int, default=0,
                   help="force N virtual CPU devices (0 = real devices)")
    p.add_argument("--overlap", action="store_true",
                   help="use the explicit interior/boundary overlap stepper "
                        "(weak/strong modes) — compare against the default "
                        "XLA-scheduled exchange; composes with --fuse to "
                        "emit the overlap A/B ladder for the temporal-"
                        "blocked steppers (rungs that cannot host the "
                        "split are skipped, not silently run plain)")
    p.add_argument("--fuse-kind", default=None,
                   choices=["stream", "padfree"],
                   help="force the streaming (sliding-window manual-DMA) "
                        "or pad-free slab-operand kernels for --fuse "
                        "rungs — A/B vs the default zslab/windowed "
                        "kernels (virtual meshes: relative "
                        "evidence only).  Composes with --mesh-axes 1|2: "
                        "the 1-axis ladder runs the z-slab streaming "
                        "kernel, the 2-axis ladder the round-8 "
                        "y-slab+corner splice variant — run both for the "
                        "kind x mesh A/B pair; rungs that would price a "
                        "different kernel class are skipped")
    p.add_argument("--pipeline", action="store_true",
                   help="cross-pass pipelined exchange rungs (slab-carry "
                        "scan, stepper pipeline=True): pass i+1's "
                        "exchange issued from pass i's shell outputs — "
                        "the A/B against the same ladder without "
                        "--pipeline prices the cross-pass hiding.  Needs "
                        "--fuse; composes with --overlap and --mesh-axes "
                        "1|2; defaults --fuse-kind to padfree (the "
                        "pipeline rides the slab-operand kinds only); "
                        "1-device rungs and rungs that cannot host the "
                        "slab-carry scan are skipped, never silently "
                        "priced as per-pass rows.  Every emitted row "
                        "stamps the pipeline flag, so relative CPU "
                        "evidence and future real-slice rows stay "
                        "distinguishable")
    p.add_argument("--exchange", default="ppermute",
                   choices=["ppermute", "rdma"],
                   help="halo-exchange transport for the --fuse rungs: "
                        "ppermute (default, XLA collective on HBM slabs) "
                        "or rdma — the in-kernel remote-DMA ring "
                        "(ops/pallas/remote.py: boundary slabs through "
                        "double-buffered VMEM rings via "
                        "make_async_remote_copy, zero XLA ppermute in "
                        "the step).  The A/B against the same ladder "
                        "with --exchange ppermute prices the transport. "
                        "Needs --fuse; forces --fuse-kind stream (the "
                        "only rdma host — an explicit different kind "
                        "errors rather than silently re-labeling); "
                        "composes with --overlap/--pipeline and "
                        "--mesh-axes 1|2; 1-device rungs and rungs that "
                        "cannot host the streaming kernel are skipped, "
                        "never silently priced as ppermute rows.  Every "
                        "emitted row stamps the mode, so relative CPU "
                        "evidence (interpret-emulated) and future "
                        "real-slice rows stay distinguishable")
    p.add_argument("--fuse", type=int, default=0,
                   help="temporal blocking: k fused micro-steps per "
                        "width-k exchange (weak/strong modes; meshes keep "
                        "the lane axis whole — untileable rungs are "
                        "skipped)")
    p.add_argument("--ensemble", type=int, default=0, metavar="N",
                   help="batched-engine ladder arm (round 15): every "
                        "rung advances N members through ONE compiled "
                        "batched step (vmapped local update; one "
                        "exchange round per site regardless of N) and "
                        "reports AGGREGATE Mcells/s across members — "
                        "the A/B against the same ladder without "
                        "--ensemble prices the per-pass fixed-cost "
                        "amortization.  Every emitted row stamps the "
                        "ensemble size, so batched rows are never "
                        "confused with single-sim rows (the ledger "
                        "keys them apart)")
    p.add_argument("--groups", type=int, default=0, metavar="N",
                   help="coupled device-group ladder arm (round 18, "
                        "parallel/groups.py): every rung partitions its "
                        "devices into N contiguous same-physics groups "
                        "(y-sharded sub-meshes) coupled at interface "
                        "ghost bands, each group running the UNMODIFIED "
                        "plain sharded stepper — the A/B against the "
                        "same ladder without --groups prices exactly the "
                        "host-orchestrated coupling (interface transfers "
                        "+ per-group dispatch).  Rungs whose device "
                        "count cannot host the split (fewer than N, or "
                        "N does not divide it) are skipped, never "
                        "silently run monolithic; every emitted row "
                        "stamps the groups spec, so coupled rows are "
                        "never confused with monolithic rows (the "
                        "ledger keys them apart |grp:<sig>)")
    p.add_argument("--group-transport", default="device_put",
                   choices=["device_put", "collective"],
                   help="interface-band transport for the --groups "
                        "rungs (round 23, parallel/groups.py): "
                        "device_put (default, host-mediated receiver-"
                        "side band landing) or collective — raw sender "
                        "rows as one ppermute round per interface per "
                        "direction inside a union-mesh shard_map, "
                        "resampled shard-local on the receiver (zero "
                        "host hops; jaxpr-gated by utils/jaxprcheck)."
                        "  The A/B against the same --groups ladder "
                        "under device_put prices exactly the transport "
                        "swap; every emitted row stamps the transport, "
                        "and the ledger keys collective rows apart "
                        "(|gtx:collective), so neither transport can "
                        "baseline the other.  Needs --groups")
    p.add_argument("--telemetry", default=None, metavar="PATH",
                   help="write a JSONL telemetry event log (obs/ "
                        "schema, same manifest as cli --telemetry): "
                        "one 'rung' event per emitted ladder row, "
                        "'skip' events for declined rungs, heartbeat "
                        "verdicts if a rung stalls.  Render with "
                        "scripts/obs_report.py")
    p.add_argument("--mesh-axes", type=int, default=2, choices=[1, 2],
                   help="sharded-fused rung mesh arity (3D --fuse "
                        "ladders): 2 = balanced (z, y, 1) rungs "
                        "(default — the surface-to-volume-minimizing "
                        "decomposition, now pad-free via the 2-axis "
                        "slab-operand kernels); 1 = z-only (n, 1, 1) "
                        "rungs — run both for the decomposition-shape "
                        "A/B against the same grid")
    a = p.parse_args(argv)
    if a.exchange == "rdma":
        # resolved BEFORE the pipeline default below: an rdma ladder
        # must never be silently re-labeled onto the pad-free kind
        if not (a.fuse > 1):
            p.error("--exchange rdma needs --fuse K (the remote-DMA "
                    "ring feeds the streaming temporal-blocking "
                    "kernels)")
        if a.fuse_kind not in (None, "stream"):
            p.error("--exchange rdma rides the streaming kernel family "
                    "only; drop --fuse-kind or set it to stream")
        # pin the kernel class so every rung prices the same kernel
        a.fuse_kind = "stream"
    if a.groups:
        if a.groups < 2:
            p.error("--groups needs N >= 2 (a 1-group run is monolithic "
                    "— run the plain ladder instead)")
        bad = [flag for flag, on in (
            ("--fuse", a.fuse > 1), ("--overlap", a.overlap),
            ("--pipeline", a.pipeline), ("--ensemble", a.ensemble > 0),
            ("--exchange rdma", a.exchange == "rdma"),
            ("--fuse-kind", a.fuse_kind is not None)) if on]
        if bad:
            p.error(f"--groups conflicts with {', '.join(bad)}: coupled "
                    "rungs run each group's plain sharded stepper, so "
                    "the A/B against the monolithic ladder prices the "
                    "coupling and nothing else")
    if a.group_transport != "device_put" and not a.groups:
        p.error("--group-transport prices the coupled interface "
                "transport; it needs --groups N")
    if a.pipeline:
        if not (a.fuse > 1):
            p.error("--pipeline needs --fuse K (the slab-carry scan "
                    "pipelines the fused passes)")
        if a.fuse_kind is None:
            # the pipeline rides the slab-operand kinds; pin the kernel
            # class so every rung of the ladder prices the same kernel
            a.fuse_kind = "padfree"
    # --fuse + --overlap now composes: the temporal-blocked steppers carry
    # their own interior/boundary split (stepper.make_sharded_fused_step
    # overlap=True), so the pair emits the overlap A/B ladder for the
    # fused kind.  Rungs whose geometry declines the split are skipped
    # (never silently priced as plain rows).

    jax = _setup_devices(a.virtual)
    from mpi_cuda_process_tpu.ops.stencil import make_stencil

    st = make_stencil(a.stencil)
    if a.groups and st.ndim != 3:
        p.error("--groups partitions the z axis of a 3-d stencil; "
                f"{a.stencil} is {st.ndim}-d")
    n_devices = len(jax.devices())

    session = None
    if a.telemetry:
        try:
            from mpi_cuda_process_tpu import obs

            session = obs.open_session(
                a.telemetry, tool="scaling",
                run={k: v for k, v in vars(a).items()},
                stall_after_s=600.0)
        except Exception as e:  # noqa: BLE001 — never block the harness
            print(f"[scaling] telemetry disabled "
                  f"({type(e).__name__}: {e})", file=sys.stderr)
            session = None

    def _tel(kind, **payload):
        if session is not None:
            session.event(kind, **payload)

    try:
        rc = _ladder(a, p, jax, st, n_devices, _tel)
    finally:
        if session is not None:
            session.finish()
            session.close()
    if session is not None:
        # rung rows enter the durable cross-round ledger (idempotent;
        # quarantine rules on ingest; never load-bearing)
        try:
            from mpi_cuda_process_tpu.obs import ledger as _ledger

            _ledger.ingest_log(session.path)
        except Exception:  # noqa: BLE001
            pass
    return rc


def _ladder(a, p, jax, st, n_devices, _tel) -> int:
    from mpi_cuda_process_tpu.config import parse_int_tuple

    if a.mode == "halo":
        ladder = _mesh_ladder(n_devices, st.ndim)[1:]
        if not ladder:
            p.error("halo mode needs >= 2 devices (try --virtual 8)")
        for mesh_shape in ladder:
            block = parse_int_tuple(a.block)
            global_shape = tuple(b * m for b, m in zip(block, mesh_shape))
            t_full, t_local = bench_halo_overhead(
                st, mesh_shape, global_shape, a.steps, a.reps)
            overhead = max(t_full - t_local, 0.0)
            rec = {
                "mode": "halo", "stencil": a.stencil,
                "mesh": list(mesh_shape), "grid": list(global_shape),
                "ms_per_step_full": round(t_full * 1e3, 3),
                "ms_per_step_no_exchange": round(t_local * 1e3, 3),
                "halo_overhead_ms": round(overhead * 1e3, 3),
                "halo_overhead_frac": round(overhead / t_full, 4),
            }
            print(json.dumps(rec))
            _tel("rung", **rec)
        return 0

    base = None
    rows = []
    ladder = _mesh_ladder(n_devices, st.ndim)
    if a.fuse > 1 and st.ndim == 3:
        # sharded-fused keeps the lane axis whole: decompose z/y only
        # (--mesh-axes 1 pins the z-ring for the decomposition-shape A/B)
        if a.mesh_axes == 1:
            ladder = [(m1[0], 1, 1) for m1 in _mesh_ladder(n_devices, 1)]
        else:
            ladder = [(*m2, 1) for m2 in _mesh_ladder(n_devices, 2)]
    elif a.fuse > 1 and st.ndim == 2:
        # 2D whole-local-block kernel: row decomposition only
        ladder = _mesh_ladder(n_devices, 1)
    for mesh_shape in ladder:
        n_dev = math.prod(mesh_shape)
        if a.mode == "weak":
            block = parse_int_tuple(a.block)[:st.ndim]
            if len(block) < st.ndim:
                p.error(f"--block needs {st.ndim} extents for {a.stencil}")
            # mesh tuples may be shorter than ndim (trailing axes unsharded)
            counts = (tuple(mesh_shape) + (1,) * st.ndim)[:st.ndim]
            global_shape = tuple(b * m for b, m in zip(block, counts))
        else:
            global_shape = parse_int_tuple(a.grid)
            if any(g % m for g, m in zip(global_shape, mesh_shape)):
                continue
        gspec = None
        if a.groups:
            got = bench_groups(a.stencil, n_dev, a.groups, global_shape,
                               a.steps, a.reps,
                               transport=a.group_transport)
            if got is None:
                print(f"[scaling] skip {mesh_shape}: {n_dev} device(s) "
                      f"cannot host {a.groups} coupled groups "
                      f"({a.group_transport})", file=sys.stderr)
                _tel("skip", mesh=list(mesh_shape),
                     grid=list(global_shape), groups=a.groups,
                     group_transport=a.group_transport,
                     reason="device count or geometry cannot host the "
                            "coupled group split under this transport")
                continue
            mcells, per_step, gspec = got
            kernel_kind = None
        else:
            got = bench_config(
                st, mesh_shape, global_shape, a.steps, a.reps,
                overlap=a.overlap, fuse=a.fuse, fuse_kind=a.fuse_kind,
                pipeline=a.pipeline, exchange=a.exchange,
                ensemble=a.ensemble)
            if got is None:
                print(f"[scaling] skip {mesh_shape}: untileable fused "
                      f"k={a.fuse}"
                      + (" (or cannot host --pipeline)" if a.pipeline
                         else "")
                      + (" (or cannot host --exchange rdma)"
                         if a.exchange == "rdma" else ""),
                      file=sys.stderr)
                _tel("skip", mesh=list(mesh_shape),
                     grid=list(global_shape), fuse=a.fuse,
                     pipeline=a.pipeline, exchange=a.exchange,
                     reason="untileable or cannot host the requested "
                            "overlap/pipeline/kind/exchange contract")
                continue
            mcells, per_step, kernel_kind = got
        per_dev = mcells / n_dev
        if base is None:
            base = per_dev if a.mode == "weak" else mcells
        eff = (per_dev / base if a.mode == "weak"
               else mcells / (base * n_dev))
        rows.append((mesh_shape, global_shape, mcells, per_dev, eff))
        rec = {
            "mode": a.mode, "stencil": a.stencil,
            "overlap": a.overlap, "fuse": a.fuse,
            "pipeline": a.pipeline,
            "fuse_kind": a.fuse_kind,
            "exchange": a.exchange,
            "ensemble": a.ensemble,
            "kernel_kind": kernel_kind,
            "mesh_axes": a.mesh_axes,
            "n_groups": a.groups,
            "groups": gspec,
            "group_transport": a.group_transport if a.groups else None,
            "mesh": list(mesh_shape), "grid": list(global_shape),
            "mcells_per_s": round(mcells, 1),
            "mcells_per_s_per_device": round(per_dev, 1),
            "efficiency": round(eff, 4),
            "ms_per_step": round(per_step * 1e3, 3),
        }
        print(json.dumps(rec))
        _tel("rung", **rec)

    print(f"\n{a.mode} scaling — {a.stencil}"
          f" ({n_devices} devices, {jax.default_backend()})", file=sys.stderr)
    print(f"{'mesh':>12} {'grid':>16} {'Mcells/s':>10}"
          f" {'/device':>10} {'eff':>6}", file=sys.stderr)
    for mesh_shape, g, mc, pd, eff in rows:
        print(f"{'x'.join(map(str, mesh_shape)):>12}"
              f" {'x'.join(map(str, g)):>16}"
              f" {mc:>10.0f} {pd:>10.0f} {eff:>6.1%}", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
