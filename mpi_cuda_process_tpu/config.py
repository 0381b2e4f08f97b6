"""Run configuration.

Replaces the reference's interactive ``scanf`` of three ints (g, h, w —
kernel.cu:152-159, run *before* MPI_Init on every rank, which only works if
stdin is forwarded to all ranks) and its scattering of hard-coded constants
(``NUM_THREADS 512`` kernel.cu:6, density 0.15 kernel.cu:193, Dirichlet 100.0
MDF_kernel.cu:93, split factor 2 everywhere) with one frozen dataclass,
serialized into checkpoints and benchmark records (SURVEY.md §5.6).
"""

from __future__ import annotations

import dataclasses
import json
from typing import Any, Dict, Optional, Tuple


@dataclasses.dataclass(frozen=True)
class RunConfig:
    stencil: str = "heat2d"
    grid: Tuple[int, ...] = (512, 512)
    iters: int = 1000
    dtype: Optional[str] = None  # None = the stencil's own default dtype
    mesh: Tuple[int, ...] = ()  # per-grid-axis shard counts; () = unsharded
    seed: int = 0
    density: float = 0.15
    init: str = "auto"
    periodic: bool = False
    log_every: int = 0
    checkpoint_every: int = 0
    checkpoint_dir: Optional[str] = None
    checkpoint_backend: str = "npy"  # npy (host gather) | orbax (per-shard)
    resume: bool = False
    render: bool = False
    profile_dir: Optional[str] = None  # whole-run jax.profiler trace
    # chunk-scoped jax.profiler trace + device-trace attribution
    # (obs/profile.py): the profiler brackets ONE steady-state chunk and
    # the parsed trace yields a measured overlap efficiency; None = off
    profile: Optional[str] = None
    compute: str = "auto"  # auto | jnp | pallas
    overlap: bool = False  # explicit interior/boundary split for comm overlap
    # cross-pass pipelined halo exchange (slab-carry scan): pass i+1's
    # exchange issued from pass i's shell outputs, one interior pass ahead
    # of its consumer; needs --fuse + --mesh + a slab-operand kind
    pipeline: bool = False
    ensemble: int = 0  # >0: batch of N independent universes (leading
    # member axis through init -> stepper -> diagnostics; composes with
    # --mesh — the batched sharded steppers compile ONCE for all N)
    # ensemble-axis device shards (round 15): the member axis becomes a
    # THIRD mesh dimension of that many shards (ensemble x y x z, e.g.
    # v5e-64 as 8x8 spatial x N-way ensemble); 0/1 = every device holds
    # all N members' local blocks.  Needs --ensemble, N % M == 0.
    ensemble_mesh: int = 0
    # per-member init perturbation: member i's inexact fields scaled by
    # 1 + eps * u_i, u_i ~ U(-1,1) from (seed, i) — deterministic
    # parameter diversity beyond the per-member seeds (utils/init.py)
    ensemble_perturb: float = 0.0
    fuse: int = 0  # >0: temporal blocking, k steps per HBM pass (experimental)
    # which fused kernel carries --fuse (3D unsharded only; auto = measured
    # default): tiled (padded 4-block) | padfree (9-block raw-grid) |
    # stream (sliding-window manual DMA, ops/pallas/streamfused.py)
    fuse_kind: str = "auto"
    # halo-exchange transport for sharded fused runs: ppermute (XLA
    # collective on HBM slabs) | rdma (in-kernel remote DMA through VMEM
    # rings, ops/pallas/remote.py — streaming kind only, never a silent
    # fallback)
    exchange: str = "ppermute"
    # MPMD device groups (parallel/groups.py): partition the slice into
    # contiguous sub-meshes along grid axis 0, each running its own
    # op/resolution/dtype, coupled ONLY at interface faces — e.g.
    # "wave3d:fine@0-3:z1/4,heat3d:coarse@4-7".  "" = monolithic SPMD.
    # SIM field: the group layout picks the compiled programs.
    groups: str = ""
    # interface transport for --groups (parallel/groups.py round 23):
    # device_put (host-ordered buffer moves between group meshes —
    # correct on any backend) | collective (one union-mesh shard_map
    # whose per-interface ppermutes carry the raw edge rows chip to
    # chip; resample/cast shard-local on the receiver, bit-identical
    # to device_put).  SIM field: it picks the compiled exchange
    # programs (the computed trajectory is identical by the pinned
    # transport-equivalence invariant, but identity stays honest —
    # the ledger prices the two transports apart via |gtx:).
    group_transport: str = "device_put"
    # measurement-driven execution policy (policy/select.py): resolve
    # every mode flag NOT explicitly passed (--mesh/--ensemble-mesh/
    # --fuse/--fuse-kind/--overlap/--pipeline/--exchange) from the
    # campaign ledger's best_known winner for this label x backend,
    # falling back to the costmodel roofline where nothing is measured.
    # Explicit flags always win and are recorded as overrides in the
    # manifest 'policy' event.
    auto_policy: bool = False
    # forced kernel variant (policy/autotune.py registry id, e.g.
    # 'ring4' or 'bz16y16'): run the streaming/rdma kernels under that
    # variant's swept constants — schedule changes, results never do;
    # an infeasible variant raises with the named reason (forced-flag
    # contract).  "" = default constants.
    kernel_variant: str = ""
    # measured kernel-constant sweep (policy/autotune.py): before the
    # run, probe every feasible variant for this config into ordinary
    # ledger rows under |var:<id> baseline keys, so --auto-policy can
    # resolve the measured winner.  The run itself then proceeds
    # normally.
    autotune: bool = False
    # >0 with --auto-policy: re-resolve the policy every K chunk
    # boundaries and, when the winner's ADOPTABLE mode fields changed,
    # live-migrate the run to it (parallel/reshard.py collective
    # redistribution — no host gather, bit-exact) and emit a 'migrate'
    # event.  0 = decide once at launch.
    policy_recheck: int = 0
    check_finite: int = 0  # >0: assert all fields finite every N steps
    debug_checks: bool = False  # checkify NaN/bounds checks, step-localized
    # numerics sentinel (obs/health.py): a separately-jitted sharded
    # health reduction at every chunk boundary — per-field min/max/mean
    # + NaN/Inf counts + the op's registered conservation invariant —
    # with a trend detector whose DIVERGED verdict flows everywhere
    # WEDGED does (supervisor gives up without restart, ledger
    # quarantines, /status.json//obs_top render it)
    health: bool = False
    # opt-in halo-exchange audit (obs/health.py): every K chunks,
    # re-exchange the ghost slabs through the run's transport and
    # bit-compare every received slab against the neighbor interior it
    # must equal; 0 = off.  Needs a spatially sharded --mesh.
    halo_audit: int = 0
    # run doctor (obs/anomaly.py): a chunk-boundary performance-anomaly
    # detector — throughput collapse vs the run's own rolling baseline
    # and the ledger's best_known band, post-warmup recompiles, memory
    # creep, variance growth, straggler attribution — whose findings
    # are 'anomaly' events and turn the run's verdict DEGRADED.  Host
    # Python at chunk boundaries only: the step jaxpr is byte-identical
    # on vs off (the --health invariant).
    anomaly: bool = False
    # supervisor policy for a DEGRADED child (resilience/supervisor.py):
    # warn (default — a slow run is not a dead run; the verdict flows
    # to /status.json and the ledger but nothing is killed) | restart
    # (kill + resume-relaunch like WEDGED) | abort (give up like
    # DIVERGED).  Parent-side only, like the other supervisor knobs.
    degraded_action: str = "warn"
    tol: float = 0.0  # >0: stop when residual < tol (lax.while_loop runner)
    tol_check_every: int = 10  # residual check cadence for --tol
    dump_every: int = 0  # >0: async .npy snapshots of field0 every N steps
    dump_dir: Optional[str] = None
    # JSONL telemetry event log (obs/): run manifest + per-chunk runtime
    # stats + static cost counters + heartbeat verdicts; None = no trace
    telemetry: Optional[str] = None
    mem_check: str = "error"  # error | warn | off: per-device HBM budget guard
    # fault-tolerant supervision (resilience/supervisor.py): run in a
    # child subprocess with checkpointing+telemetry forced on; kill and
    # resume-relaunch on WEDGED/STALLED verdicts, child death, or a
    # wall-clock event stall, with bounded exponential backoff
    supervise: bool = False
    max_restarts: int = 2  # relaunches before the supervisor gives up
    restart_backoff: float = 5.0  # backoff base seconds (doubles per restart)
    supervise_stall_s: float = 600.0  # no-telemetry-events kill threshold
    # live run console (obs/serve.py): --serve PORT starts an HTTP
    # service over the telemetry log (/metrics, /status.json,
    # /events?after=SEQ); 0 = ephemeral port (bound address printed and
    # recorded as a 'serve' event).  Launcher-only: a supervised child
    # must never try to bind the parent's port, so to_argv drops it.
    serve_port: Optional[int] = None
    # JAX persistent compilation cache directory (--compile-cache DIR;
    # JAX_COMPILATION_CACHE_DIR wins, <checkout>/.jax_cache is the
    # default — plan.enable_compile_cache).  Lifecycle: the cache changes
    # when a program compiles, never what it computes.
    compile_cache: Optional[str] = None
    # resident serving engine (serving/): --serve-engine PORT runs this
    # config as a job on a continuous-batching ServingEngine with the
    # scheduler console (queue depth, slot occupancy, admission/evict
    # counters) served over HTTP on PORT (0 = ephemeral).  Launcher-
    # only, like serve_port: a scheduler-launched child must run the
    # one ordinary CLI path, never nest another scheduler.
    serve_engine: Optional[int] = None
    # fleet front door (serving/router.py): --serve-router PORT runs
    # this config as a job on a ServingRouter of --router-replicas
    # supervised engine replicas (aggregate-budget admission, size-
    # class affinity, zero-lost-jobs rebalance on replica death) with
    # the PR-11 aggregate fleet console on PORT.  Launcher-only, like
    # serve_engine.
    serve_router: Optional[int] = None
    router_replicas: int = 3
    # ladder shrink policy (serving/scheduler.py): after this many
    # consecutive boundary rounds at occupancy <= the previous ladder
    # rung with nobody waiting, a resident class live-repacks its
    # members down a rung and the freed budget is re-priced by
    # admission.  0 disables.  Lifecycle: migration is bit-exact by
    # the reshard contract, so it never changes a computed value.
    shrink_after: int = 64
    params: Dict[str, Any] = dataclasses.field(default_factory=dict)

    def to_json(self) -> str:
        return json.dumps(dataclasses.asdict(self), sort_keys=True)

    @classmethod
    def from_dict(cls, d: Dict[str, Any]) -> "RunConfig":
        d = dict(d)
        for k in ("grid", "mesh"):
            if k in d and d[k] is not None:
                d[k] = tuple(d[k])
        known = {f.name for f in dataclasses.fields(cls)}
        return cls(**{k: v for k, v in d.items() if k in known})


# Launcher-only fields: the supervisor consumes these in the PARENT and
# must never hand them to the child (a child that re-supervises forks a
# supervision tree; the whole point of to_argv is a child that runs the
# one ordinary CLI path).  serve_port is launcher-only for the same
# reason: the parent's console serves the child's log, and a child that
# re-served would race the parent for the port.
_ARGV_SKIP = frozenset({"supervise", "max_restarts", "restart_backoff",
                        "supervise_stall_s", "serve_port", "serve_engine",
                        "serve_router", "router_replicas", "shrink_after",
                        "degraded_action"})


# --------------------------------------------------------------------------
# Simulation-state vs request-lifecycle split (round 15, the ensemble
# engine's submit/handle API).  SIMULATION fields determine WHAT is
# computed — the compiled program and its numerics: two configs equal on
# these produce bit-identical trajectories.  LIFECYCLE fields determine
# how a request is watched, persisted, instrumented, and served — they
# may differ between two submissions of the same simulation without
# changing a single computed value (telemetry is zero-ops-in-the-step by
# the obs/ invariant; checkpoint/resume is bit-exact by the checkpoint
# contract; debug instrumentation only adds checks).  The two sets
# PARTITION RunConfig — a new field must be classified here or
# tests/test_ensemble_engine.py fails, so the split cannot rot silently.

LIFECYCLE_FIELDS = frozenset({
    "log_every", "checkpoint_every", "checkpoint_dir",
    "checkpoint_backend", "resume", "render", "profile_dir", "profile",
    "check_finite", "debug_checks", "health", "halo_audit",
    "anomaly", "degraded_action",
    "dump_every", "dump_dir",
    "telemetry", "mem_check", "supervise", "max_restarts",
    "restart_backoff", "supervise_stall_s", "serve_port",
    "compile_cache", "serve_engine", "serve_router", "router_replicas",
    "shrink_after",
    # policy_recheck is WHEN mid-flight adoption is reconsidered, not
    # what is computed — migration is bit-exact by the reshard
    # contract, so two submissions differing only here share a
    # trajectory.  auto_policy stays a SIM field: it picks the
    # compiled program (the serving engine resolves it away before
    # computing a class signature).
    "policy_recheck",
})

SIM_FIELDS = frozenset(
    f.name for f in dataclasses.fields(RunConfig)
) - LIFECYCLE_FIELDS


def sim_config_dict(cfg: RunConfig) -> Dict[str, Any]:
    """The simulation-state fields of ``cfg`` alone, as a plain dict."""
    return {k: v for k, v in dataclasses.asdict(cfg).items()
            if k in SIM_FIELDS}


def sim_signature(cfg: RunConfig) -> str:
    """Canonical JSON of the simulation state — the engine's identity
    key: two requests with equal signatures compute the same
    trajectory (and can share a compile cache entry)."""
    return json.dumps(sim_config_dict(cfg), sort_keys=True)


def to_argv(cfg: RunConfig) -> list:
    """The canonical CLI argv reproducing ``cfg`` (supervisor fields
    excluded).

    The supervisor's child-launch path: every non-default field becomes
    its ``--flag`` (field underscores map 1:1 to flag dashes — a
    property ``tests/test_supervisor.py`` round-trips through the real
    parser, so a new RunConfig field that forgets its CLI flag fails a
    test instead of silently vanishing from supervised children).  Known
    lossiness, inherited from the CLI itself: a *string* param value
    that parses as a number comes back numeric (``parse_params``).
    """
    out: list = []
    defaults = RunConfig()
    for f in dataclasses.fields(RunConfig):
        if f.name in _ARGV_SKIP:
            continue
        v = getattr(cfg, f.name)
        if v == getattr(defaults, f.name):
            continue
        flag = "--" + f.name.replace("_", "-")
        if f.name == "params":
            for k, pv in v.items():
                out += ["--param", f"{k}={pv}"]
        elif isinstance(v, bool):
            out.append(flag)
        elif isinstance(v, tuple):
            out += [flag, ",".join(map(str, v))]
        else:
            out += [flag, str(v)]
    return out


def groups_signature(groups: str) -> str:
    """Short stable signature of a ``--groups`` string.

    Whitespace-normalized, so cosmetically different spellings of the
    same split share a signature; structurally different splits never
    do (within the hash).  The ledger's ``|grp:<sig>`` baseline-key
    tail and the coupled label tag both hang off this — kept here (not
    in ``parallel/groups.py``) so the pure-python obs/ledger path never
    imports the jax-heavy builder.
    """
    import hashlib

    canon = ",".join(p.strip() for p in (groups or "").split(",")
                     if p.strip())
    return hashlib.sha1(canon.encode()).hexdigest()[:10]


def parse_int_tuple(s: str) -> Tuple[int, ...]:
    s = s.strip()
    if not s:
        return ()
    return tuple(int(p) for p in s.replace("x", ",").split(",") if p.strip())


def parse_params(pairs) -> Dict[str, Any]:
    """Parse repeated ``--param key=value`` flags (values as float/int/str)."""
    out: Dict[str, Any] = {}
    for p in pairs or ():
        k, _, v = p.partition("=")
        if not _:
            raise ValueError(f"--param expects key=value, got {p!r}")
        try:
            out[k] = int(v)
        except ValueError:
            try:
                out[k] = float(v)
            except ValueError:
                out[k] = v
    return out
