"""Command-line entry point.

Replaces the reference's L5 layer (``main`` + interactive scanf,
kernel.cu:148-284) with an argparse CLI: every BASELINE.json config is one
command line, e.g.::

    python -m mpi_cuda_process_tpu --stencil heat2d --grid 512,512 --iters 1000
    python -m mpi_cuda_process_tpu --stencil heat3d --grid 1024,1024,1024 \
        --iters 100 --mesh 2,2
    python -m mpi_cuda_process_tpu --stencil life --grid 256,256 --iters 100 \
        --render
"""

from __future__ import annotations

import argparse
import dataclasses
import functools
import logging
import math
import sys
import time
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import cancellation, driver
from . import plan as plan_lib
from .config import RunConfig, parse_int_tuple, parse_params
from .ops import stencil as stencil_lib
from .parallel import mesh as mesh_lib
import os

from .resilience import faults
from .utils import checkpointing, diagnostics, native, render
# Re-exported under their old names: perfbench/, chip_smoke.py and the
# tests reach ``build``, the auto-fuse upgrade and the compile cache
# through this module.
from .plan import build, enable_compile_cache, maybe_auto_fuse

log = logging.getLogger("mpi_cuda_process_tpu")


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="mpi_cuda_process_tpu",
        description="TPU-native distributed stencil / finite-difference framework",
    )
    p.add_argument("--stencil", default="heat2d",
                   choices=stencil_lib.available_stencils())
    p.add_argument("--grid", type=parse_int_tuple, default=(512, 512),
                   help="grid shape, e.g. 512,512 or 256x256x256")
    p.add_argument("--iters", type=int, default=1000)
    p.add_argument("--dtype", default=None,
                   help="float32|bfloat16|int32|... (default: stencil's own)")
    p.add_argument("--mesh", type=parse_int_tuple, default=(),
                   help="per-grid-axis shard counts, e.g. 2,2 (default: no sharding)")
    p.add_argument("--groups", default="",
                   help="MPMD device groups (parallel/groups.py): "
                        "partition the slice into contiguous sub-meshes "
                        "along grid axis 0, each running its OWN op / "
                        "refinement ratio / dtype / mesh, coupled only "
                        "at interface faces — e.g. "
                        "\"wave3d:fine@0-3:z1/4,heat3d:coarse@4-7\" runs "
                        "a 2x-refined wave3d hot region over the first "
                        "quarter of z on devices 0-3 inside a coarse "
                        "heat3d far-field on devices 4-7.  Clause "
                        "grammar: <op>[:fine[R]|:coarse][:<dtype>]@"
                        "<d0>-<d1>[:z<num>/<den>][:mesh<m0>x<m1>...]"
                        "[:<mode>+<mode>...].  Each group's interior "
                        "step runs on its own sub-mesh; a trailing "
                        "'+'-joined mode token (fuse<K>/stream/padfree/"
                        "overlap/pipeline/plain, e.g. "
                        ":fuse2+stream+overlap) routes it through the "
                        "matching fused/overlapped stepper UNMODIFIED "
                        "(fuse<K> must agree across groups; 'plain' "
                        "locks the default; no token = unset, "
                        "--auto-policy may resolve it per group).  The "
                        "ghost-band interface refresh is the only "
                        "cross-group traffic (jaxprcheck."
                        "assert_coupled_structure pins it).  A 2-group "
                        "same-physics split is bit-exact vs the "
                        "monolithic run under every legal mode combo.  "
                        "Excludes the monolithic mode flags (--mesh/"
                        "--fuse/--ensemble/--overlap/--pipeline/...): "
                        "per-group behavior lives in the clauses")
    p.add_argument("--group-transport", default="device_put",
                   choices=["device_put", "collective"],
                   help="interface transport for --groups: device_put "
                        "(host-ordered buffer moves between the group "
                        "meshes — correct on any backend) | collective "
                        "(one union-mesh shard_map whose per-interface "
                        "ppermutes move the raw edge rows chip to chip; "
                        "resample/cast shard-local on the receiver — "
                        "bit-identical to device_put, zero host hops, "
                        "jaxprcheck.assert_group_transport_structure "
                        "pins exactly 2 ppermutes per interface and "
                        "zero device_put)")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--density", type=float, default=0.15,
                   help="alive probability for random init (reference: 0.15)")
    p.add_argument("--init", default="auto",
                   choices=["auto", "random", "zero", "pulse", "patch"])
    p.add_argument("--periodic", action="store_true",
                   help="periodic BCs instead of guard-cell frame")
    p.add_argument("--param", action="append", default=[],
                   help="stencil parameter override, key=value (repeatable)")
    p.add_argument("--log-every", type=int, default=0)
    p.add_argument("--checkpoint-every", type=int, default=0)
    p.add_argument("--checkpoint-dir", default=None)
    p.add_argument("--checkpoint-backend", default="npy",
                   choices=["npy", "orbax"],
                   help="npy: host-gathered .npy files (single-host); "
                        "orbax: per-shard sharded checkpointing (the only "
                        "option when the state exceeds host memory)")
    p.add_argument("--resume", action="store_true")
    p.add_argument("--render", action="store_true",
                   help="ASCII-render the final grid")
    p.add_argument("--profile-dir", default=None,
                   help="write a jax.profiler trace for the WHOLE run "
                        "(compile included; raw trace only — for "
                        "chunk-scoped attribution use --profile)")
    p.add_argument("--profile", default=None, metavar="DIR",
                   help="device-trace attribution (obs/profile.py): "
                        "scope a jax.profiler trace to ONE steady-state "
                        "chunk (the first post-compile chunk; start/"
                        "stop strictly at chunk boundaries — the jitted "
                        "step is untouched), then parse the trace into "
                        "interior-compute / ppermute / exposed-ICI "
                        "buckets and a measured overlap efficiency "
                        "(1 - exposed/total comm), logged and — with "
                        "--telemetry — recorded as a 'profile' event "
                        "next to the costmodel roofline so predicted-"
                        "vs-measured hiding is one obs_report line.  On "
                        "CPU (or a trace with no device events) the "
                        "record says 'attribution: unavailable' rather "
                        "than fabricating zeros")
    p.add_argument("--telemetry", default=None, metavar="PATH",
                   help="write a JSONL telemetry event log: a "
                        "provenance-stamped run manifest (config, mesh, "
                        "git sha, backend, jax version — one schema "
                        "shared with bench.py and the benchmark "
                        "harnesses), per-chunk runtime stats (compile "
                        "vs steady-state, recompile detection, device "
                        "memory peaks), static cost counters with a "
                        "roofline prediction (flops, HBM bytes, "
                        "ppermute rounds/bytes, cross-checked against "
                        "the --mem-check budget model), and a stall-"
                        "detecting heartbeat (STALLED/WEDGED verdicts). "
                        "Recorded only at chunk boundaries — zero ops "
                        "inside the jitted step.  Render with "
                        "scripts/obs_report.py PATH")
    p.add_argument("--overlap", action="store_true",
                   help="explicit interior/boundary split so the halo "
                        "exchange overlaps bulk compute (vs trusting XLA); "
                        "composes with --fuse under --mesh (the width-m "
                        "slab exchange then overlaps the interior fused "
                        "kernel, boundary shells spliced after)")
    p.add_argument("--pipeline", action="store_true",
                   help="cross-pass pipelined halo exchange (slab-carry "
                        "scan): the exchanged slabs ride the scan carry, "
                        "so pass i+1's width-m exchange is issued from "
                        "pass i's boundary-shell outputs — one FULL "
                        "interior pass ahead of its consumer instead of "
                        "the shell-to-splice tail (the strong-scaling "
                        "regime where the interior shrinks faster than "
                        "the faces).  Needs --fuse + --mesh and a "
                        "slab-operand kind (--fuse-kind padfree|stream "
                        "or an auto-pad-free block); composes with "
                        "--overlap (the combination that makes the "
                        "exchange independent of the interior in both "
                        "directions).  Never silently falls back: "
                        "periodic meshes, 2D grids, and the padded kind "
                        "raise with the reason")
    p.add_argument("--dump-every", type=int, default=0,
                   help="async-dump field0 snapshots every N steps (.npy, "
                        "non-blocking via the native writer pool)")
    p.add_argument("--dump-dir", default=None)
    p.add_argument("--ensemble", type=int, default=0,
                   help="run N independent universes batched through ONE "
                        "compiled step (seeds seed..seed+N-1; a leading "
                        "member axis rides init -> stepper -> "
                        "diagnostics).  Composes with --mesh: the "
                        "batched sharded steppers vmap the local update "
                        "per member, so the halo exchange stays ONE "
                        "round per site regardless of N and every Pallas "
                        "kernel gains one batch grid dimension — the "
                        "per-step fixed costs (exchange rounds, kernel "
                        "launches, compile, telemetry cadence) are paid "
                        "once per BATCH.  Composes with --fuse (every "
                        "kind incl. stream), --overlap, --pipeline, and "
                        "--exchange rdma")
    p.add_argument("--ensemble-mesh", type=int, default=0, metavar="M",
                   help="shard the member axis over M device groups — "
                        "the ensemble becomes a THIRD mesh axis "
                        "(ensemble x y x z, e.g. a v5e-64 as 8x8 "
                        "spatial x M-way ensemble; each group is an "
                        "independent spatial mesh, so halo ppermutes "
                        "never cross members).  Needs --ensemble N with "
                        "N %% M == 0 and M x prod(--mesh) devices; "
                        "0/1 = every device holds all N members")
    p.add_argument("--ensemble-perturb", type=float, default=0.0,
                   metavar="EPS",
                   help="per-member init perturbation: member i's "
                        "inexact fields scaled by 1 + EPS * u_i with "
                        "u_i ~ U(-1,1) drawn from (seed, i) — "
                        "deterministic parameter diversity for ensemble "
                        "studies beyond the per-member seeds (guard "
                        "frames re-pinned; integer fields untouched)")
    p.add_argument("--compute", default="auto",
                   choices=["auto", "jnp", "pallas"],
                   help="execution strategy (auto: the measured-fastest "
                        "path per stencil/size — temporal-blocking or raw "
                        "whole-step Pallas kernels where they beat XLA's "
                        "fusion, jnp elsewhere; an auto-selected kernel "
                        "that fails raises its own error)")
    p.add_argument("--check-finite", type=int, default=0,
                   help="every N steps, verify all fields are finite and "
                        "abort with the failing step range if not (debug "
                        "sanitizer for blow-ups: NaN/Inf from unstable "
                        "parameters)")
    p.add_argument("--debug-checks", action="store_true",
                   help="checkify debug mode: every step asserts all fields "
                        "finite inside the jitted scan (the error names the "
                        "exact failing step) plus index bounds checks; "
                        "slower — complements --check-finite's polling")
    p.add_argument("--health", action="store_true",
                   help="numerics sentinel (obs/health.py): at every "
                        "chunk boundary, a separately-jitted fully "
                        "sharded health reduction computes per-field "
                        "global min/max/mean and NaN/Inf counts plus "
                        "the op's REGISTERED conservation invariant "
                        "(heat: total heat; wave: the leapfrog "
                        "scheme's exactly-conserved discrete energy; "
                        "sor: the decreasing residual norm) — one "
                        "device_get per boundary, no host gather of "
                        "field state, zero ops in the jitted step.  A "
                        "trend detector (relative drift vs the "
                        "chunk-0 baseline, per-op tolerances) turns "
                        "the stats into 'health' events and a "
                        "DIVERGED verdict that aborts the run and "
                        "flows everywhere WEDGED does: the supervisor "
                        "gives up WITHOUT a checkpoint-restart loop "
                        "(resuming into the same blow-up is waste), "
                        "ledger ingest quarantines the row with "
                        "reason 'diverged', /status.json and obs_top "
                        "render it.  With no logging cadence a "
                        "~8-chunk boundary cadence is synthesized")
    p.add_argument("--anomaly", action="store_true",
                   help="run doctor (obs/anomaly.py): continuous "
                        "performance-anomaly detection at chunk "
                        "boundaries — same zero-ops-in-the-jitted-step "
                        "discipline as --health, consuming only the "
                        "chunk records the recorder already writes.  "
                        "Flags throughput collapse vs the run's own "
                        "rolling steady-state baseline AND vs the "
                        "campaign ledger's best_known band, recompiles "
                        "after chunk 0, device-memory creep, growing "
                        "chunk-time variance, and straggler "
                        "attribution naming the slowest host/group "
                        "with its lag ratio.  Findings land as "
                        "'anomaly' events and a DEGRADED verdict that "
                        "flows everywhere WEDGED does (/status.json, "
                        "obs_top, the engine, the supervisor via "
                        "--degraded-action, ledger degraded=N flags, "
                        "perf_gate) — but a slow run is not a dead "
                        "run: nothing aborts unless you ask.  On a "
                        "terminal verdict the session's flight "
                        "recorder drops a self-contained post-mortem "
                        "bundle next to the telemetry log "
                        "(scripts/obs_bundle.py makes one on demand)")
    p.add_argument("--degraded-action", default="warn",
                   choices=["warn", "restart", "abort"],
                   help="what --supervise does about a DEGRADED child "
                        "(anomaly events in its telemetry): warn = log "
                        "and keep watching (default — a slow run is "
                        "not a dead run), restart = kill and resume "
                        "from the latest checkpoint (transient host "
                        "trouble), abort = give up immediately with "
                        "the flight-recorder bundle")
    p.add_argument("--halo-audit", type=int, default=0, metavar="K",
                   help="opt-in exchange audit (obs/health.py), every "
                        "K chunks: re-exchange the ghost slabs "
                        "through the run's transport (--exchange "
                        "ppermute|rdma, any mesh family) and "
                        "bit-compare every received slab against the "
                        "neighbor interior it must equal (computed "
                        "independently from the global array view — "
                        "the two sides share no exchange code).  A "
                        "mismatch aborts with the exact (field, axis, "
                        "direction, ring-shard) site — the tool that "
                        "localizes an exchange bug in minutes.  "
                        "Needs a spatially sharded --mesh; costs one "
                        "extra exchange round per audited chunk, so "
                        "keep K coarse on production runs")
    p.add_argument("--tol", type=float, default=0.0,
                   help="stop when the residual max|u - u_prev_check| over a "
                        "--tol-check-every-step interval drops below TOL "
                        "(solver-style convergence; --iters is the step cap)")
    p.add_argument("--tol-check-every", type=int, default=10,
                   help="steps between residual checks for --tol")
    p.add_argument("--fuse", type=int, default=0,
                   help="temporal blocking: advance K steps per HBM pass "
                        "(3D windowed / 2D whole-grid Pallas kernels — the "
                        "measured-fastest path for heat3d/heat3d27/wave3d, "
                        "auto-selected there; composes with --mesh, "
                        "--periodic, and --tol)")
    p.add_argument("--fuse-kind", default="auto",
                   choices=["auto", "tiled", "padfree", "stream"],
                   help="which 3D fused kernel carries --fuse: tiled = "
                        "padded 4-block windows (unsharded); padfree = "
                        "9-block raw-grid, no pad transient (unsharded "
                        "1024^3-class grids; under --mesh, the "
                        "slab-operand kernels on z-only AND 2-axis z/y "
                        "meshes — exchanged slabs + corner pieces as "
                        "operands); stream = sliding-window manual-DMA "
                        "pipeline (every plane read once per pass; bf16 "
                        "works at k=4; under --mesh, any z/y mesh — "
                        "2-axis meshes splice y-slab + corner operands "
                        "into the sliding window); auto = the "
                        "measured default (padfree above the HBM "
                        "threshold, else tiled)")
    p.add_argument("--exchange", default="ppermute",
                   choices=["ppermute", "rdma"],
                   help="halo-exchange transport for sharded --fuse runs: "
                        "ppermute = XLA collective-permute on HBM slabs "
                        "(the default every other mode uses); rdma = "
                        "IN-KERNEL remote DMA (ops/pallas/remote.py): "
                        "each boundary slab is staged chunk-by-chunk "
                        "through a double-buffered VMEM ring and pushed "
                        "into the neighbor's recv ring by "
                        "make_async_remote_copy under send/recv DMA "
                        "semaphores (barrier at pass start for neighbor-"
                        "readiness) — no XLA collective in the step, no "
                        "HBM slab transient in the budget, exchange "
                        "latency per-chunk.  Needs --fuse + --mesh + "
                        "--fuse-kind stream (the streaming kernel family "
                        "hosts it, both mesh families, f32 and bf16); "
                        "composes with --overlap and --pipeline; never "
                        "silently falls back — unsupported combos raise "
                        "with the reason.  Bit-exact vs ppermute")
    p.add_argument("--supervise", action="store_true",
                   help="fault-tolerant run supervisor (resilience/): "
                        "run the simulation in a child subprocess with "
                        "--checkpoint-every/--telemetry forced on "
                        "(defaults derived when unset), watch its "
                        "heartbeat/manifest events, and on a WEDGED/"
                        "STALLED verdict, child death, or a wall-clock "
                        "stall with no events, kill the child, back off "
                        "exponentially, and relaunch with --resume from "
                        "the latest surviving checkpoint.  The resumed "
                        "run bit-matches an uninterrupted one (the "
                        "checkpoint contract); restart/resume events "
                        "land in a .supervisor.jsonl telemetry log.  "
                        "Gives up (exit 1) after --max-restarts")
    p.add_argument("--max-restarts", type=int, default=2,
                   help="supervised relaunches before giving up "
                        "(default 2; a supervisor must never spin "
                        "forever against a dead backend)")
    p.add_argument("--restart-backoff", type=float, default=5.0,
                   help="supervised restart backoff base seconds "
                        "(doubles per restart, bounded; default 5)")
    p.add_argument("--supervise-stall-s", type=float, default=600.0,
                   help="supervisor wall-clock kill threshold: seconds "
                        "with NO child telemetry events (covers the "
                        "compile-hang case where the in-process "
                        "heartbeat may be hung too; default 600 — set "
                        "above your longest silent phase)")
    p.add_argument("--serve", type=int, default=None, metavar="PORT",
                   dest="serve_port",
                   help="live run console (obs/serve.py): start an HTTP "
                        "service over this run's telemetry log exposing "
                        "/metrics (Prometheus text: steps/s, Gcells/s, "
                        "compile vs steady split, recompiles, memory "
                        "peak, heartbeat verdict, roofline gap), "
                        "/status.json (manifest provenance + latest "
                        "chunk + heartbeat verdict + restart trail — "
                        "the remote answer to 'is it wedged?'), and "
                        "/events?after=SEQ (incremental NDJSON tail, "
                        "bounded long-poll).  PORT 0 binds an ephemeral "
                        "port; the bound address is printed and written "
                        "into the manifest as a 'serve' event.  Implies "
                        "--telemetry (a default path is derived when "
                        "unset).  The server only tails the log the run "
                        "was writing anyway: zero ops in the jitted "
                        "step, and endpoint handlers never touch the "
                        "run loop.  Shuts down with the run")
    p.add_argument("--compile-cache", default=None, metavar="DIR",
                   help="JAX persistent compilation cache directory "
                        "(default <checkout>/.jax_cache).  Ignored when "
                        "JAX_COMPILATION_CACHE_DIR is set: that "
                        "directory wins.  The cache changes when a run "
                        "compiles, never what it computes")
    p.add_argument("--serve-engine", type=int, default=None,
                   metavar="PORT",
                   help="resident serving engine (serving/): run this "
                        "config as a job on a continuous-batching "
                        "ServingEngine — size-classed resident compiled "
                        "steps, budget-priced admission, weighted-FIFO "
                        "fairness — with the scheduler console "
                        "(/metrics /status.json /events: queue depth, "
                        "slot occupancy, admission/evict/preempt "
                        "counters) on PORT (0 = ephemeral).  One config "
                        "is a degenerate workload; the flag exists as "
                        "the quickstart face of the scheduler — "
                        "multi-tenant traffic submits through "
                        "serving.ServingEngine in-process")
    p.add_argument("--serve-router", type=int, default=None,
                   metavar="PORT",
                   help="fleet front door (serving/router.py): run this "
                        "config as a job on a ServingRouter of "
                        "--router-replicas supervised ServingEngine "
                        "replicas — admission by AGGREGATE budget, "
                        "size-class affinity routing (a class's later "
                        "jobs hit its warm replica: zero backend "
                        "compiles), zero-lost-jobs rebalance + "
                        "supervised restart on replica death — with "
                        "the aggregate fleet console (/status.json "
                        "hosts table: one row per replica) on PORT "
                        "(0 = ephemeral)")
    p.add_argument("--router-replicas", type=int, default=3,
                   metavar="N",
                   help="engine replica count behind --serve-router "
                        "(each a full scheduler with its own budget "
                        "slice and telemetry log)")
    p.add_argument("--shrink-after", type=int, default=64,
                   metavar="K",
                   help="serving ladder shrink policy: a resident size "
                        "class that spends K consecutive scheduler "
                        "rounds at occupancy <= the previous ladder "
                        "rung with nobody waiting live-repacks its "
                        "members down that rung (bit-exact, no "
                        "checkpoint round-trip, never a host gather) "
                        "and admission re-prices the freed budget; "
                        "0 disables shrinking")
    p.add_argument("--mem-check", default="error",
                   choices=["error", "warn", "off"],
                   help="per-device HBM budget guard (TPU runs): estimate "
                        "peak live bytes for the execution strategy and "
                        "refuse with the arithmetic instead of OOMing "
                        "minutes later (utils/budget.py); warn logs the "
                        "breakdown and proceeds")
    p.add_argument("--auto-policy", action="store_true",
                   help="measurement-driven execution policy "
                        "(policy/select.py): resolve every mode flag "
                        "NOT explicitly passed (--mesh/--ensemble-mesh/"
                        "--fuse/--fuse-kind/--overlap/--pipeline/"
                        "--exchange) from the campaign ledger's "
                        "best_known winner for this label x backend "
                        "(OBS_LEDGER_PATH-aware), falling back to the "
                        "costmodel roofline where nothing is measured.  "
                        "Explicit flags always win and are recorded as "
                        "overrides; the decision, its provenance "
                        "(measured vs predicted) and the runner-up "
                        "table land in the manifest as a 'policy' event")
    p.add_argument("--kernel-variant", default="", metavar="ID",
                   help="force a kernel-constant variant from the "
                        "autotuner registry (policy/autotune.py: e.g. "
                        "ring3/ring4/nc8 sweep the remote-DMA ring "
                        "depth/chunk geometry, bz16y16/bz8y8/bz16y32 "
                        "the streaming strip shape).  Schedule-only: "
                        "every variant is bit-exact vs the default "
                        "constants.  Needs --fuse-kind stream (+ "
                        "--exchange rdma for the ring family); an "
                        "infeasible variant refuses with the named "
                        "reason instead of silently running the "
                        "default kernel")
    p.add_argument("--autotune", action="store_true",
                   help="measured kernel-constant sweep before the run "
                        "(policy/autotune.py): probe every feasible "
                        "variant for this config/backend with short "
                        "scans and record the winners as ordinary "
                        "campaign-ledger rows under |var:<id> baseline "
                        "keys — --auto-policy then resolves the "
                        "measured winner like any other mode "
                        "dimension.  Probe order is attribution-"
                        "driven: comm-bound sweeps ring constants "
                        "first, compute-bound strip shapes first")
    p.add_argument("--policy-recheck", type=int, default=0, metavar="K",
                   help="with --auto-policy: re-resolve the policy "
                        "every K chunk boundaries and live-migrate the "
                        "run to the new winner when its adoptable mode "
                        "fields changed — collective redistribution "
                        "between mesh shapes (parallel/reshard.py), "
                        "never a host gather, bit-exact — emitting a "
                        "'migrate' event per adoption.  0 = decide "
                        "once at launch")
    return p


def config_from_args(argv=None) -> RunConfig:
    a = build_parser().parse_args(argv)
    return RunConfig(
        stencil=a.stencil, grid=a.grid, iters=a.iters, dtype=a.dtype,
        mesh=a.mesh, groups=a.groups, group_transport=a.group_transport,
        seed=a.seed, density=a.density,
        init=a.init,
        periodic=a.periodic, log_every=a.log_every,
        checkpoint_every=a.checkpoint_every, checkpoint_dir=a.checkpoint_dir,
        checkpoint_backend=a.checkpoint_backend,
        resume=a.resume, render=a.render, profile_dir=a.profile_dir,
        profile=a.profile, telemetry=a.telemetry,
        compute=a.compute, overlap=a.overlap, pipeline=a.pipeline,
        ensemble=a.ensemble, ensemble_mesh=a.ensemble_mesh,
        ensemble_perturb=a.ensemble_perturb,
        fuse=a.fuse, fuse_kind=a.fuse_kind, exchange=a.exchange,
        tol=a.tol, tol_check_every=a.tol_check_every,
        check_finite=a.check_finite, debug_checks=a.debug_checks,
        health=a.health, halo_audit=a.halo_audit,
        anomaly=a.anomaly, degraded_action=a.degraded_action,
        dump_every=a.dump_every, dump_dir=a.dump_dir,
        mem_check=a.mem_check,
        auto_policy=a.auto_policy, policy_recheck=a.policy_recheck,
        kernel_variant=a.kernel_variant, autotune=a.autotune,
        supervise=a.supervise, max_restarts=a.max_restarts,
        restart_backoff=a.restart_backoff,
        supervise_stall_s=a.supervise_stall_s,
        serve_port=a.serve_port,
        compile_cache=a.compile_cache,
        serve_engine=a.serve_engine,
        serve_router=a.serve_router, router_replicas=a.router_replicas,
        shrink_after=a.shrink_after,
        params=parse_params(a.param),
    )


def _profiled(cfg: RunConfig):
    """jax.profiler trace context for --profile-dir (no-op context otherwise)."""
    import contextlib

    if cfg.profile_dir:
        return jax.profiler.trace(cfg.profile_dir)
    return contextlib.nullcontext()


def _save_ckpt(cfg: RunConfig, fields, step: int):
    if cfg.checkpoint_backend == "orbax":
        checkpointing.orbax_save_checkpoint(
            cfg.checkpoint_dir, fields, step, dataclasses.asdict(cfg))
    else:
        checkpointing.save_checkpoint(
            cfg.checkpoint_dir, fields, step, dataclasses.asdict(cfg))


def _session_span(session, name: str, **attrs):
    """A span on the session's emitter, or a null context without one
    (spans are never load-bearing — obs/spans.py)."""
    from .obs import spans as spans_lib

    return spans_lib.maybe_span(
        getattr(session, "spans", None), name, **attrs)


def _epilogue(cfg: RunConfig, fields, final_step: int, save_ckpt: bool,
              session=None):
    """Shared run tail: final checkpoint + optional ASCII render."""
    if save_ckpt and cfg.checkpoint_dir:
        with _session_span(session, "checkpoint", step=final_step,
                           final=True):
            _save_ckpt(cfg, fields, final_step)
    if cfg.render:
        print(render.ascii_render(np.asarray(fields[0])))


def run(cfg: RunConfig) -> Tuple:
    """Execute a configured run; returns (final_fields, mcells_per_s).

    An auto-selected Pallas path that fails to compile or run raises its
    own error: no config is silently re-run on another path.
    """
    if cfg.serve_port is not None and not cfg.telemetry:
        # --serve tails the telemetry log; without one there is nothing
        # to serve, so derive a default path (same discipline as the
        # supervisor's forced telemetry)
        from .obs import trace as trace_lib

        cfg = dataclasses.replace(cfg, telemetry=os.path.join(
            trace_lib.default_telemetry_dir(),
            f"serve-{os.getpid()}-{int(time.time())}.jsonl"))
    if cfg.autotune:
        # measured kernel-constant sweep BEFORE policy resolution: the
        # probes land as ordinary ledger rows under |var:<id> baseline
        # keys, so the --auto-policy resolve below (and every later
        # run against the same ledger) ranks the measured variants
        # like any other mode dimension.
        from .policy import autotune as autotune_lib

        summary = autotune_lib.maybe_autotune(cfg)
        log.info(
            "autotune: swept %d variant(s) (%s) -> %s; winner %s",
            len(summary["swept"]), ",".join(summary["order"]) or "-",
            summary["ledger"], summary["winner"] or "none")
        for s in summary["skipped"]:
            log.info("autotune: skipped %s: %s", s["id"], s["reason"])
        cfg = dataclasses.replace(cfg, autotune=False)
    decision = None
    if cfg.auto_policy:
        # measurement-driven execution policy: resolve the unset mode
        # flags from the ledger winner (costmodel fallback) BEFORE the
        # fuse auto-upgrade — the policy's candidate space already
        # includes the fused variants, so a resolved decision is final
        # and maybe_auto_fuse must not silently amend it.
        from . import policy as policy_lib

        decision = policy_lib.resolve(cfg)
        cfg = decision.config
        log.info("policy: %s winner %s (%s)", decision.provenance,
                 decision.label,
                 f"{decision.value} {decision.unit}"
                 if decision.value is not None else "no ranked candidate")
    fused_cfg = cfg if decision is not None else maybe_auto_fuse(cfg)
    return _run_once(fused_cfg, decision=decision)


def _open_telemetry(cfg: RunConfig):
    """Telemetry session for ``--telemetry PATH`` (obs/), or None.

    The manifest is written up front (a run that dies mid-compile still
    leaves its provenance), the heartbeat starts immediately, and the
    recorder becomes ``run_simulation``'s chunk-boundary observer.
    """
    from . import obs

    try:
        # the heartbeat stall threshold is env-tunable (OBS_STALL_AFTER_S)
        # so a supervisor/test can make the in-process verdict land
        # before its own wall-clock kill; default unchanged (600 s)
        stall_after_s = float(os.environ.get("OBS_STALL_AFTER_S", "600")
                              or 600)
    except ValueError:
        stall_after_s = 600.0
    extra = {}
    if cfg.groups:
        # the manifest's `groups` block: one resolved entry per group
        # (op/ratio/dtype/devices/mesh/grid) so a log reader never
        # re-parses the --groups grammar.  Best-effort: a malformed
        # spec raises properly in _run_coupled WITH a session open to
        # record the error, so plan failures stay silent here.
        try:
            from .parallel import groups as groups_lib

            extra["groups"] = [
                dict(p.describe(), transport=cfg.group_transport)
                for p in groups_lib.plans_from_config(
                    cfg.groups, cfg.grid,
                    default_dtype=cfg.dtype or None)]
        except Exception:  # noqa: BLE001 — see above
            pass
    return obs.open_session(
        cfg.telemetry, tool="cli", run=dataclasses.asdict(cfg),
        step_unit=max(1, cfg.fuse), stall_after_s=stall_after_s,
        ensemble=cfg.ensemble, **extra)


def _emit_static_cost(cfg: RunConfig, st, session) -> None:
    """Best-effort static cost counters + roofline into the trace."""
    try:
        from .obs import costmodel

        variant = None
        if cfg.kernel_variant:
            from .policy import autotune as autotune_lib

            variant = autotune_lib.VARIANTS.get(cfg.kernel_variant)
        session.event("costmodel", **costmodel.static_cost(
            st, cfg.grid, mesh=cfg.mesh, fuse=cfg.fuse,
            fuse_kind=cfg.fuse_kind, periodic=cfg.periodic,
            ensemble=cfg.ensemble, exchange=cfg.exchange,
            ensemble_mesh=cfg.ensemble_mesh, variant=variant))
    except Exception:  # noqa: BLE001 — telemetry is never load-bearing
        log.debug("static cost model failed; trace goes without it",
                  exc_info=True)


def _open_serve(cfg: RunConfig, session):
    """Live console for ``--serve PORT`` (obs/serve.py), or None.

    The server tails the session's log — the run loop never sees it.
    The bound address is printed AND recorded as a ``serve`` event so a
    remote monitor (scripts/obs_top.py) can discover the URL from the
    manifest log alone.  Never load-bearing: a bind failure logs and
    the run proceeds unserved.
    """
    if cfg.serve_port is None:
        return None
    try:
        from .obs import serve as serve_lib

        server = serve_lib.serve_run(session.path, port=cfg.serve_port)
        log.info("obs live console serving at %s "
                 "(/metrics /status.json /events)", server.url)
        session.event("serve", url=server.url, port=server.port,
                      endpoints=["/metrics", "/status.json", "/events"])
        return server
    except Exception as e:  # noqa: BLE001 — telemetry never load-bearing
        log.warning("--serve disabled (%s: %s)", type(e).__name__, e)
        return None


def _make_anomaly_monitor(cfg: RunConfig, session, cells: int):
    """Run doctor (obs/anomaly.py) for ``--anomaly``: the chunk-boundary
    detector, seeded with the campaign ledger's ``best_known`` row for
    this label x backend so the roofline-gap band has a reference.  The
    ledger lookup is best-effort (no ledger, no matching baseline key →
    own-baseline detection only)."""
    from .obs import anomaly as anomaly_lib

    best = None
    try:
        from .obs import ledger as ledger_lib

        rows = ledger_lib.read_rows(ledger_lib.default_ledger_path())
        if rows:
            run = dataclasses.asdict(cfg)
            probe = ledger_lib.make_row(
                ledger_lib._cli_label(run), 1.0, source="anomaly-probe",
                expected_backend=jax.default_backend(),
                flags=ledger_lib._flags(run) or None)
            best = ledger_lib.best_known(rows).get(
                ledger_lib.baseline_key(probe))
    except Exception:  # noqa: BLE001 — the band is optional evidence
        best = None
    try:
        import socket

        ident = f"{socket.gethostname()}|p{int(jax.process_index())}"
    except Exception:  # noqa: BLE001
        ident = "?|p?"
    return anomaly_lib.AnomalyMonitor(
        trace=session.trace, spans=session.spans, ident=ident,
        cells=cells, best_known=best)


def _attach_anomaly(cfg: RunConfig, session, cells: int) -> None:
    """Hang the run doctor off the session recorder (never load-bearing:
    a construction failure leaves the run undoctored, not dead)."""
    if not cfg.anomaly or session is None:
        return
    try:
        session.recorder.anomaly = _make_anomaly_monitor(cfg, session, cells)
    except Exception:  # noqa: BLE001
        log.debug("--anomaly monitor construction failed; run proceeds "
                  "undoctored", exc_info=True)


def _maybe_bundle(session, reason: str, verdict=None) -> None:
    """Terminal-verdict flight-recorder bundle (obs/flightrec.py).

    Called on the paths where a run ends with something to explain —
    an error/DIVERGED abort, or a clean exit that accumulated anomaly
    findings.  ``bundle_from_session`` swallows every failure."""
    from .obs import flightrec as flightrec_lib

    path = flightrec_lib.bundle_from_session(session, reason,
                                             verdict=verdict)
    if path:
        log.info("flight-recorder bundle: %s", path)


def _run_once(cfg: RunConfig, decision=None) -> Tuple:
    if not cfg.telemetry:
        return _run_measured(cfg, None, decision=decision)
    session = _open_telemetry(cfg)
    server = _open_serve(cfg, session)
    try:
        if decision is not None:
            # the decision and its provenance become part of the run's
            # manifest trail — perf_gate --policy-check replays exactly
            # this event against the current ledger.  A coupled
            # resolution additionally records one policy_group event
            # per group FIRST (obs_report/metrics read them by group
            # name), then the main event whose group_decisions list is
            # what the policy check replays.
            for gd in getattr(decision, "group_decisions", None) or []:
                session.event("policy_group", **gd)
            session.event("policy", **decision.as_event())
        result = _run_measured(cfg, session, decision=decision)
        mon = getattr(session.recorder, "anomaly", None)
        if mon is not None and mon.count:
            # a run that finished slow finished DEGRADED: leave the
            # post-mortem bundle even though nothing aborted
            _maybe_bundle(session, "degraded", verdict="DEGRADED")
        return result
    except cancellation.RunCancelled as e:
        # a cancel is a third terminal outcome, not an error: the log
        # records a 'cancelled' event (ledger quarantines with reason
        # 'cancelled'; the supervisor reads it as fatal-no-restart)
        session.event("cancelled", step=e.step)
        raise
    except BaseException as e:
        session.error(e)
        verdict = None
        try:
            from .obs import health as health_lib

            if isinstance(e, health_lib.SimulationDiverged):
                verdict = "DIVERGED"
        except Exception:  # noqa: BLE001
            pass
        _maybe_bundle(session, f"error:{type(e).__name__}",
                      verdict=verdict)
        raise
    finally:
        session.close()
        if server is not None:
            # after session.close() so the final summary event is on
            # disk for the server's last drain; then the console goes
            # away with the run (no leaked thread — tier-1 pins it)
            server.close()


# Monolithic mode flags that do not compose with --groups: each group's
# clause is its own config, so a slice-wide mode flag has no single run
# to configure.  (name, predicate) — the forced-flag contract: every
# conflict raises with the reason, never a silent ignore.
_GROUP_CONFLICTS = (
    ("--mesh", lambda c: bool(c.mesh)),
    ("--ensemble/--ensemble-mesh/--ensemble-perturb",
     lambda c: bool(c.ensemble or c.ensemble_mesh or c.ensemble_perturb)),
    ("--fuse", lambda c: bool(c.fuse)),
    ("--fuse-kind", lambda c: c.fuse_kind != "auto"),
    ("--overlap", lambda c: c.overlap),
    ("--pipeline", lambda c: c.pipeline),
    ("--exchange rdma", lambda c: c.exchange == "rdma"),
    ("--periodic", lambda c: c.periodic),
    ("--tol", lambda c: c.tol > 0),
    ("--profile/--profile-dir",
     lambda c: bool(c.profile or c.profile_dir)),
    ("--halo-audit", lambda c: bool(c.halo_audit)),
    ("--debug-checks", lambda c: c.debug_checks),
    ("--policy-recheck", lambda c: bool(c.policy_recheck)),
    ("--compute pallas", lambda c: c.compute == "pallas"),
    ("--kernel-variant", lambda c: bool(c.kernel_variant)),
    ("--dump-every", lambda c: bool(c.dump_every)),
)


def _run_coupled(cfg: RunConfig, session, decision=None) -> Tuple:
    """The ``--groups`` run loop: N device groups, coupled at faces.

    The coupled analogue of the `_run_measured` tail: per-group budget
    guard, per-group costmodel event, a chunked host round loop with
    per-group "group_chunk" telemetry, per-group health sentinels (a
    DIVERGED verdict names the group), and coupled checkpoint/resume
    (per-group subdirs, one agreed step).
    """
    from .parallel import groups as groups_lib

    for flag, fired in _GROUP_CONFLICTS:
        if fired(cfg):
            raise ValueError(
                f"--groups partitions the slice into per-group sub-"
                f"meshes with their own per-group configs; {flag} "
                "configures the monolithic run and does not compose "
                "with --groups (put per-group behavior in the group "
                "clauses: <op>[:fine[R]|:coarse][:<dtype>]@<d0>-<d1>"
                "[:z<num>/<den>][:mesh<m0>x<m1>...])")
    plans = groups_lib.plans_from_config(
        cfg.groups, cfg.grid, default_dtype=cfg.dtype or None,
        n_devices=jax.device_count())
    plan_lib.check_mem_budget(cfg, groups=plans)
    enable_compile_cache(cfg.compile_cache)
    mesh_lib.bootstrap_distributed()
    runner = groups_lib.CoupledRunner(
        plans, seed=cfg.seed, density=cfg.density, init_kind=cfg.init,
        transport=cfg.group_transport)

    start_round = 0
    if cfg.resume and cfg.checkpoint_dir and os.path.isdir(
            os.path.join(cfg.checkpoint_dir, "group0")):
        start_round = runner.load_checkpoint(cfg.checkpoint_dir)
        log.info("resumed coupled run from %s at round %d",
                 cfg.checkpoint_dir, start_round)
    if session is not None:
        try:
            from .obs import costmodel

            session.event("costmodel", **costmodel.coupled_cost(
                plans, transport=cfg.group_transport))
        except Exception:  # noqa: BLE001 — telemetry never load-bearing
            log.debug("coupled cost model failed; trace goes without it",
                      exc_info=True)
        if start_round:
            session.event("resume", resumed_from_step=start_round)

    remaining = cfg.iters - start_round
    if remaining <= 0:
        log.info("coupled checkpoint already at round %d >= iters",
                 start_round)
        if session is not None:
            session.finish(steps=0, mcells_per_s=0.0,
                           note="checkpoint already at/past iters")
        return runner.assemble(), 0.0

    monitors = None
    if cfg.health:
        from .obs import health as health_lib

        # per-group sentinels, trace=None: the coupled loop emits the
        # "health" events itself so every record carries its group name.
        # open_system: a coupled group exchanges its invariant quantity
        # through the interface bands by construction, so the op's
        # conservation-drift rule is informational here — NaN/Inf and a
        # non-finite invariant stay hard triggers
        monitors = [health_lib.HealthMonitor(p.stencil, open_system=True)
                    for p in plans]

    intervals = [v for v in (cfg.log_every, cfg.checkpoint_every,
                             cfg.check_finite) if v]
    interval = math.gcd(*intervals) if len(intervals) > 1 else (
        intervals[0] if intervals else 0)
    if (cfg.health or cfg.anomaly) and not interval and remaining >= 2:
        interval = max(1, remaining // 8)

    cells_round = runner.cell_updates_per_round()
    _attach_anomaly(cfg, session, cells_round)
    done = 0
    chunk = 0
    t0 = time.perf_counter()
    while done < remaining:
        n = min(interval or remaining, remaining - done)
        tc = time.perf_counter()
        runner.run(n)
        # block per group IN ORDER and timestamp each ready horizon:
        # the groups' device programs overlap on disjoint devices, so a
        # group's horizon approximates its own duration (an early slow
        # group masks later fast ones — the masked groups then read the
        # same horizon, which the straggler detector's peer-median
        # comparison treats as "no single suspect": conservative)
        group_ready_ms = []
        for fs in runner.fields:
            for f in fs:
                f.block_until_ready()
            group_ready_ms.append(
                round((time.perf_counter() - tc) * 1e3 / n, 6))
        dtc = time.perf_counter() - tc
        done += n
        step = start_round + done
        cancellation.check(step)
        faults.maybe_fire("exchange", step=step)
        if session is not None:
            session.recorder.record_chunk(n, dtc)
            for p, ready_ms in zip(plans, group_ready_ms):
                session.event(
                    "group_chunk", step=step, group=p.name, op=p.spec.op,
                    ratio=p.ratio,
                    dtype=str(np.dtype(p.stencil.dtype)),
                    steps=n, wall_s=round(dtc, 4),
                    ready_ms_per_step=ready_ms,
                    mcells_per_s=round(p.cells * n / dtc / 1e6, 3))
            mon = getattr(session.recorder, "anomaly", None)
            if mon is not None:
                try:
                    mon.observe_members(step, [
                        {"name": p.name, "ms_per_step": ready_ms}
                        for p, ready_ms in zip(plans, group_ready_ms)],
                        kind="group")
                except Exception:  # noqa: BLE001 — never load-bearing
                    pass
        poison = faults.injected_numeric_poison(step)
        if poison is not None:
            from .obs import health as health_lib

            runner.fields[0] = health_lib.apply_nan_poison(
                runner.fields[0])
        if cfg.check_finite and step % cfg.check_finite == 0:
            for p, fs in zip(plans, runner.fields):
                for i, f in enumerate(fs):
                    if not jnp.issubdtype(f.dtype, jnp.inexact):
                        continue
                    if not bool(jnp.isfinite(f).all()):
                        raise RuntimeError(
                            f"group {p.name} field {i} became non-"
                            f"finite by step {step} (NaN/Inf blow-up "
                            "— check stability parameters)")
        if monitors is not None:
            from .obs import health as health_lib

            for p, mon, fs in zip(plans, monitors, runner.fields):
                rec = mon.check(step, fs, chunk=chunk)
                rec["group"] = p.name
                if session is not None:
                    session.event("health", **rec)
                if rec["verdict"] == health_lib.VERDICT_DIVERGED:
                    # the group is named FIRST — the eviction verdict
                    # the engine/supervisor read must say which group's
                    # physics blew up, not just that something did
                    raise health_lib.SimulationDiverged(
                        f"group {p.name} DIVERGED at step {step}: "
                        f"{rec['reason']}", record=rec)
        if cfg.log_every and step % cfg.log_every == 0:
            log.info("round %d  %s", step, "  ".join(
                f"{p.name}:{p.cells * n / dtc / 1e6:.1f}Mc/s"
                for p in plans))
        if cfg.checkpoint_every and cfg.checkpoint_dir and \
                step % cfg.checkpoint_every == 0:
            with _session_span(session, "checkpoint", step=step):
                runner.save_checkpoint(cfg.checkpoint_dir)
        chunk += 1
    dt = time.perf_counter() - t0

    if monitors is not None and monitors[0].checks == 0:
        from .obs import health as health_lib

        for p, mon, fs in zip(plans, monitors, runner.fields):
            rec = mon.check(cfg.iters, fs)
            rec["group"] = p.name
            if session is not None:
                session.event("health", **rec)
            if rec["verdict"] == health_lib.VERDICT_DIVERGED:
                raise health_lib.SimulationDiverged(
                    f"group {p.name} DIVERGED at step {cfg.iters}: "
                    f"{rec['reason']}", record=rec)

    mcells = cells_round * remaining / dt / 1e6
    log.info("%d coupled rounds x %d groups (%d cell-updates/round) in "
             "%.3fs  (%.1f Mcells/s)", remaining, len(plans),
             cells_round, dt, mcells)
    if session is not None:
        session.finish(steps=remaining, wall_s=round(dt, 4),
                       mcells_per_s=round(mcells, 3), coupled=True,
                       n_groups=len(plans),
                       cell_updates_per_round=cells_round)
    if cfg.checkpoint_dir and (cfg.checkpoint_every or cfg.resume):
        with _session_span(session, "checkpoint", step=cfg.iters,
                           final=True):
            runner.save_checkpoint(cfg.checkpoint_dir)
    fields = runner.assemble()
    if cfg.render:
        print(render.ascii_render(np.asarray(fields[0])))
    return fields, mcells


def _run_measured(cfg: RunConfig, session, decision=None) -> Tuple:
    if cfg.groups:
        return _run_coupled(cfg, session, decision=decision)
    if cfg.group_transport not in ("", "device_put"):
        raise ValueError(
            "--group-transport selects the --groups interface "
            "transport; a monolithic run has no interfaces to move — "
            "drop the flag or pass --groups")
    if cfg.debug_checks and cfg.fuse:
        raise ValueError("--debug-checks excludes --fuse (the fused "
                         "kernel replaces the step being instrumented)")
    if cfg.profile and cfg.profile_dir:
        raise ValueError("--profile and --profile-dir both open a "
                         "jax.profiler session and jax forbids nesting "
                         "them; pick the chunk-scoped (--profile) or "
                         "whole-run (--profile-dir) trace")
    if cfg.profile and cfg.tol > 0:
        raise ValueError("--profile scopes one steady-state chunk; "
                         "--tol runs inside a single while_loop with no "
                         "chunk boundary to scope")
    if cfg.halo_audit < 0:
        raise ValueError("--halo-audit takes a positive chunk cadence K")
    if cfg.halo_audit and not (cfg.mesh and any(c > 1 for c in cfg.mesh)):
        raise ValueError(
            "--halo-audit re-exchanges ghost slabs across a device "
            "mesh; it needs a spatially sharded --mesh (an unsharded "
            "run has no exchange to audit)")
    if cfg.halo_audit and cfg.tol > 0:
        raise ValueError(
            "--halo-audit runs at chunk boundaries; --tol runs inside "
            "one while_loop with no boundary to audit at")
    if cfg.policy_recheck:
        if not cfg.auto_policy:
            raise ValueError("--policy-recheck re-resolves the auto "
                             "policy; it needs --auto-policy")
        if cfg.tol > 0:
            raise ValueError(
                "--policy-recheck adopts at chunk boundaries; --tol "
                "runs inside one while_loop with no boundary to "
                "migrate at")
        if cfg.halo_audit:
            raise ValueError(
                "--policy-recheck can live-migrate the mesh out from "
                "under the halo auditor's compiled exchange; run the "
                "audit or the elastic policy, not both")
    plan_lib.check_mem_budget(cfg)
    enable_compile_cache(cfg.compile_cache)
    mesh_lib.bootstrap_distributed()
    build_t0, build_m0 = time.time(), time.perf_counter()
    st, step_fn, fields, start_step = build(cfg)
    build_s = time.perf_counter() - build_m0
    if session is not None:
        _emit_static_cost(cfg, st, session)
        if start_step:
            # the restart trail: a resumed run names its resume point in
            # its own manifest log (the supervisor mirrors this in its
            # launch events; the ledger carries it into the row detail)
            session.event("resume", resumed_from_step=start_step)
            if session.spans is not None:
                # the resume SPAN: the checkpoint restore dominates a
                # resuming build, so its bracket on the causal timeline
                # is the build itself, attrs carrying the resume point
                session.spans.emit("resume", start=build_t0,
                                   dur_s=build_s,
                                   resumed_from_step=start_step)
        if cfg.exchange == "rdma":
            # honest mode tag: which execution path actually carries the
            # remote-DMA exchange (the compiled Pallas collective kernel,
            # or the interpret-mode loopback emulation on CPU) — a CPU
            # run must never read as a measured rdma path
            session.event(
                "exchange", mode="rdma",
                backend=getattr(step_fn, "_rdma_backend", "unknown"))
    remaining = cfg.iters - start_step
    if remaining <= 0:
        log.info("checkpoint already at step %d >= iters", start_step)
        if session is not None:
            session.finish(steps=0, mcells_per_s=0.0,
                           note="checkpoint already at/past iters")
        return fields, 0.0

    cells = math.prod(cfg.grid) * max(1, cfg.ensemble)

    # Numerics sentinel + halo audit (obs/health.py): both are strictly
    # chunk-boundary observers — a separately-jitted reduction (health)
    # and a separately-jitted exchange-compare (audit), never ops in the
    # step program (the jaxpr-invariance pin extends to --health).
    monitor = auditor = None
    if cfg.health:
        from .obs import health as health_lib

        monitor = health_lib.HealthMonitor(
            st, trace=session.trace if session is not None else None,
            ensemble=cfg.ensemble,
            spans=session.spans if session is not None else None)
    if cfg.halo_audit:
        from .obs import health as health_lib

        auditor = health_lib.HaloAuditor(
            st, mesh_lib.make_mesh(cfg.mesh,
                                   ensemble=cfg.ensemble_mesh or 1),
            cfg.grid, exchange=cfg.exchange, periodic=cfg.periodic,
            ensemble=cfg.ensemble,
            trace=session.trace if session is not None else None)
    _attach_anomaly(cfg, session, cells)

    if cfg.tol > 0:
        if cfg.log_every or cfg.checkpoint_every or \
                cfg.dump_every or cfg.check_finite or cfg.debug_checks:
            raise ValueError(
                "--tol runs inside one while_loop; it excludes "
                "--debug-checks and periodic log/checkpoint/dump/"
                "check-finite (a non-finite state never converges: the "
                "residual stays NaN>tol and the loop exits at the "
                "--iters cap)")
        # --tol composes with --fuse: each while_loop body call advances
        # `unit` real steps, so caps and cadences are converted to call
        # units (the residual is then measured across unit*check_every
        # real steps — the same chunked-residual semantics, coarser).
        unit = max(1, cfg.fuse)
        if unit > 1 and remaining % unit:
            raise ValueError(
                f"--tol with --fuse {unit} needs remaining iters "
                f"({remaining}) to be a multiple of {unit}")
        if unit > 1 and cfg.tol_check_every % unit:
            # refuse rather than silently coarsen the residual chunk (the
            # convergence criterion is defined over tol_check_every steps)
            raise ValueError(
                f"--tol with --fuse {unit} needs --tol-check-every "
                f"({cfg.tol_check_every}) to be a multiple of {unit}")
        t0 = time.perf_counter()
        with _profiled(cfg):
            fields, n_calls, res = driver.run_until(
                step_fn, fields, cfg.tol, remaining // unit,
                check_every=cfg.tol_check_every // unit if unit > 1
                else cfg.tol_check_every)
        dt = time.perf_counter() - t0
        n_done = n_calls * unit
        if monitor is not None:
            # one while_loop = one chunk: the sentinel checks the final
            # state (a non-finite state never converges — the verdict
            # names why the loop ran to its cap)
            monitor.check_or_raise(start_step + n_done, fields, chunk=0)
        mcells = cells * n_done / dt / 1e6 if n_done else 0.0
        log.info(
            "converged=%s after %d steps (residual %.3e, tol %.1e) in %.3fs"
            "  (%.1f Mcells/s)",
            res <= cfg.tol, n_done, res, cfg.tol, dt, mcells)
        if session is not None:
            # one while_loop = one chunk (compile + run, inseparable here)
            session.recorder.record_chunk(n_calls, dt)
            session.finish(phase="tol_loop", steps=n_done, wall_s=dt,
                           mcells_per_s=round(mcells, 3),
                           converged=bool(res <= cfg.tol),
                           residual=float(res))
        _epilogue(cfg, fields, start_step + n_done, save_ckpt=True,
                  session=session)
        return fields, mcells

    if cfg.dump_every and cfg.dump_dir:
        os.makedirs(cfg.dump_dir, exist_ok=True)

    last_ok = [start_step]
    chunk_count = [0]
    audits_run = [0]

    def callback(done_in_run, fs):
        step = start_step + done_in_run * max(1, cfg.fuse)
        # Cooperative cancellation point (cancellation.py): the chunk
        # boundary is the one place state is materialized and
        # consistent, so a cancel lands here — before this boundary's
        # checkpoint/diagnostics, ending the run as cleanly as reaching
        # --iters would have.
        cancellation.check(step)
        # Fault point (resilience/faults.py): the first chunk boundary
        # at/past the spec's step, BEFORE this boundary's checkpoint
        # save — a kill "at step 40" leaves step 30 as the newest
        # surviving checkpoint, which is what a real mid-exchange death
        # looks like to the resume path.
        faults.maybe_fire("exchange", step=step)
        replaced = None
        if faults.injected_numeric_poison(step) is not None:
            # numerics fault site: one NaN cell, host-side, into the
            # state that CONTINUES (the driver adopts the returned
            # fields) — the deterministic stand-in for a real bit flip
            # that makes the DIVERGED path provable end to end
            from .obs import health as health_lib

            fs = replaced = health_lib.apply_nan_poison(fs)
        if cfg.check_finite and step % cfg.check_finite == 0:
            for i, f in enumerate(fs):
                if not jnp.issubdtype(f.dtype, jnp.inexact):
                    continue  # int grids cannot hold NaN/Inf
                if not bool(jnp.isfinite(f).all()):
                    raise RuntimeError(
                        f"field {i} became non-finite between steps "
                        f"{last_ok[0]} and {step} (NaN/Inf blow-up — "
                        f"check stability parameters)")
            last_ok[0] = step
        if cfg.log_every and step % cfg.log_every == 0:
            # step_fn gives diffusion models a Jacobi residual in the log
            # (skip fused step_fns: they advance K steps, not one).
            d = diagnostics.field_diagnostics(
                st, fs, step_fn=None if cfg.fuse else step_fn)
            log.info("step %d  %s", step, diagnostics.format_diagnostics(d))
        # Health sentinel + halo audit: BEFORE this boundary's
        # checkpoint save, so a diverged (or poisoned) state is never
        # checkpointed — the supervisor must give up, not resume into
        # the blow-up.
        chunk = chunk_count[0]
        chunk_count[0] += 1
        if monitor is not None:
            monitor.check_or_raise(step, fs, chunk=chunk)
        if auditor is not None and (chunk + 1) % cfg.halo_audit == 0:
            audits_run[0] += 1
            auditor.audit_or_raise(fs, step, chunk=chunk)
        if cfg.checkpoint_every and cfg.checkpoint_dir and \
                step % cfg.checkpoint_every == 0:
            with _session_span(session, "checkpoint", step=step):
                _save_ckpt(cfg, fs, step)
        if cfg.dump_every and cfg.dump_dir and \
                step % cfg.dump_every == 0:
            native.async_write_npy(
                os.path.join(cfg.dump_dir, f"step_{step:08d}.npy"),
                np.asarray(fs[0]))
        return replaced

    intervals = [v for v in (cfg.log_every, cfg.checkpoint_every,
                             cfg.check_finite,
                             cfg.dump_every if cfg.dump_dir else 0) if v]
    interval = math.gcd(*intervals) if len(intervals) > 1 else (
        intervals[0] if intervals else 0)
    if (cfg.health or cfg.halo_audit or cfg.anomaly) and not interval:
        # no logging cadence: synthesize ~8 chunk boundaries so the
        # sentinel/audit/doctor have boundaries to run at (the --profile
        # trick, coarser); multiples of the fused step unit so the
        # cadence accounting below holds unchanged
        unit = max(1, cfg.fuse)
        if remaining >= 2 * unit:
            interval = max(1, (remaining // unit) // 8) * unit

    # With temporal blocking the step_fn advances cfg.fuse steps per call:
    # scan over remaining/K calls, and run the callback cadence in K-units.
    step_unit = max(1, cfg.fuse)
    if step_unit > 1:
        if remaining % step_unit:
            raise ValueError(
                f"iters remaining ({remaining}) must be a multiple of "
                f"--fuse {step_unit}")
        if interval % step_unit:
            raise ValueError(
                f"log/checkpoint/dump intervals must be multiples of "
                f"--fuse {step_unit}")
        if start_step % step_unit:
            raise ValueError(
                f"resume step {start_step} not a multiple of "
                f"--fuse {step_unit}")
        interval //= step_unit

    runner_factory = None
    if cfg.debug_checks:
        # checkify cannot thread its error state through shard_map inside a
        # scan; sharded runs use the carry-based tracker instead (same error).
        runner_factory = functools.partial(
            driver.make_checked_runner,
            use_checkify=not plan_lib.uses_mesh(cfg))

    observer = session.recorder if session is not None else None
    prof = None
    if cfg.profile:
        from .obs import profile as profile_lib
        from .obs import runtime as runtime_lib

        calls = remaining // step_unit
        if interval == 0 and calls >= 2:
            # no logging cadence: synthesize one chunk boundary so a
            # steady-state chunk (post compile+warmup) exists to scope
            interval = (calls + 1) // 2
        n_chunks = -(-calls // interval) if interval else 1
        # chunk 1 = first post-compile chunk; a single-chunk run scopes
        # chunk 0 (compile included — give the run more iters to split)
        prof = profile_lib.ChunkProfiler(
            cfg.profile, target_chunk=1 if n_chunks >= 2 else 0)
        if observer is None:
            observer = runtime_lib.RuntimeRecorder(step_unit=step_unit)
        observer.profiler = prof

    migrator = None
    if cfg.auto_policy and cfg.policy_recheck > 0 and interval:
        from . import policy as policy_lib
        from .parallel import reshard as reshard_lib

        # The launch-time locked set: the decision recorded it; a
        # direct call without one derives it from cfg (no resolution
        # happened, so non-default mode fields ARE the explicit ones).
        launch_locked = (frozenset(decision.overrides)
                         if decision is not None
                         else policy_lib.locked_fields(cfg))
        mig_state = {"cfg": cfg, "boundaries": 0, "count": 0}

        def migrator(done_calls, fs):
            nonlocal step_fn
            step = (start_step // step_unit + done_calls) * step_unit
            mig_state["boundaries"] += 1
            if mig_state["boundaries"] % cfg.policy_recheck:
                return None
            cur = mig_state["cfg"]
            policy_lib.maybe_inject(step)
            try:
                dec = policy_lib.resolve(cur, locked=launch_locked,
                                         adoptable=True)
            except Exception as e:  # noqa: BLE001 — a recheck must
                # never kill a healthy run; the current layout stands
                log.warning("policy recheck failed at step %d: %s",
                            step, e)
                return None
            new_cfg = dec.config
            if all(getattr(new_cfg, f) == getattr(cur, f)
                   for f in policy_lib.MODE_FIELDS):
                return None
            if plan_lib.uses_mesh(cur) and not plan_lib.uses_mesh(new_cfg):
                # adopting an unsharded layout would be the host gather
                # the reshard contract forbids; stay put
                return None
            ndim = len(cur.grid)
            try:
                _st2, new_step_fn, _discard, _ = build(
                    dataclasses.replace(new_cfg, resume=False))
                src = mesh_lib.make_mesh(
                    cur.mesh, ensemble=cur.ensemble_mesh or 1) \
                    if plan_lib.uses_mesh(cur) else None
                dst = mesh_lib.make_mesh(
                    new_cfg.mesh, ensemble=new_cfg.ensemble_mesh or 1) \
                    if plan_lib.uses_mesh(new_cfg) else None
                plan = (reshard_lib.plan_reshard(
                    tuple(fs[0].shape), src, dst, ndim,
                    ensemble=cur.ensemble)
                    if src is not None and dst is not None else None)
                new_fields = reshard_lib.reshard_fields(
                    tuple(fs), src, dst, ndim, ensemble=cur.ensemble)
            except Exception as e:  # noqa: BLE001
                log.warning(
                    "migration to %s failed at step %d: %s (run "
                    "continues on the current layout)",
                    dec.label, step, e)
                return None
            mig_state["cfg"] = new_cfg
            mig_state["count"] += 1
            log.info("policy: migrating to %s at step %d (%s winner, "
                     "%d comm rounds)", dec.label, step, dec.provenance,
                     plan.n_comm_rounds if plan is not None else 0)
            if session is not None:
                session.event(
                    "migrate", step=step, n=mig_state["count"],
                    label=dec.label, provenance=dec.provenance,
                    value=dec.value,
                    rounds=(plan.n_comm_rounds if plan is not None
                            else 0),
                    src={f: policy_lib.select._json_val(getattr(cur, f))
                         for f in policy_lib.MODE_FIELDS},
                    dst={f: policy_lib.select._json_val(
                        getattr(new_cfg, f))
                        for f in policy_lib.MODE_FIELDS})
            # rebind the enclosing step_fn so the diagnostics path in
            # callback() sees the program that matches the new layout
            step_fn = new_step_fn
            return new_step_fn, tuple(new_fields)

    t0 = time.perf_counter()
    try:
        with _profiled(cfg):
            fields = driver.run_simulation(
                st, fields, remaining // step_unit, step_fn=step_fn,
                log_every=interval, callback=callback,
                start_step=start_step // step_unit,
                runner_factory=runner_factory,
                observer=observer, migrator=migrator)
            fields = jax.block_until_ready(fields)
    finally:
        if prof is not None:
            prof.close()  # never leave a trace session open (jax
            # refuses nesting; the error path must not poison the next run)
    dt = time.perf_counter() - t0

    # Single-chunk runs (no boundaries): the sentinel/audit still judge
    # the FINAL state once, so `--health` without any cadence cannot
    # silently observe nothing.
    if monitor is not None and monitor.checks == 0:
        monitor.check_or_raise(cfg.iters, fields)
    if auditor is not None and audits_run[0] == 0:
        auditor.audit_or_raise(fields, cfg.iters)

    if prof is not None:
        from .obs import profile as profile_lib

        att = profile_lib.attribution_record(
            cfg.profile, profiled_chunk=prof.profiled_chunk,
            error=prof.error)
        log.info("profile: %s", profile_lib.format_attribution(att))
        if session is not None:
            session.event("profile", **att)
    if cfg.dump_every and cfg.dump_dir:
        native.wait_all()  # drain the async dump queue; surfaces IO errors
    mcells = cells * remaining / dt / 1e6

    log.info("%d steps on %s grid in %.3fs  (%.1f Mcells/s)",
             remaining, "x".join(map(str, cfg.grid)), dt, mcells)
    if session is not None:
        # 3 decimals: a CPU smoke run's honest fraction of an Mcell/s
        # must not round to a zero that reads as "no throughput"
        session.finish(steps=remaining, wall_s=round(dt, 4),
                       mcells_per_s=round(mcells, 3))
    _epilogue(cfg, fields, cfg.iters, save_ckpt=bool(cfg.checkpoint_every),
              session=session)
    return fields, mcells


def main(argv=None) -> int:
    logging.basicConfig(
        level=logging.INFO,
        format="%(asctime)s %(levelname)s %(name)s: %(message)s")
    cfg = config_from_args(argv)
    if cfg.supervise:
        from .resilience import supervisor as supervisor_lib

        return supervisor_lib.run_supervised(cfg)
    if cfg.serve_router is not None:
        from . import serving

        return serving.serve_router_main(cfg)
    if cfg.serve_engine is not None:
        from . import serving

        return serving.serve_engine_main(cfg)
    run(cfg)
    return 0


if __name__ == "__main__":
    sys.exit(main())
