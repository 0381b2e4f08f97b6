"""parallel/groups.py: MPMD device groups coupled at interface faces.

The coupling contract, pinned (ISSUE 18):

* **bit-exactness** — a same-physics 2-group split (any group meshes,
  any dtype) assembles to EXACTLY the monolithic run's state after any
  number of coupled rounds: the ghost band absorbs one round's
  staleness, the band refresh is a wholesale overwrite from the
  neighbor's owned rows, and every owned row stays exact;
* **conservation** — face resampling round-trips bitwise
  (``restrict(interpolate(x)) == x``), so a fine|coarse interface
  neither creates nor destroys what the coarse side handed over;
* **isolation** — interface faces are the ONLY cross-group
  communication (the jaxpr gate: zero collectives in the transfers,
  intra-group ppermutes only where a sub-mesh actually shards);
* **identity** — a coupled row's ledger key carries ``|grp:<sig>``, so
  perf_gate reports NO_BASELINE (never REGRESSED) across group
  signatures, and policy replay is deterministic (the group layout IS
  the execution strategy);
* **observability** — the manifest carries a resolved ``groups`` block,
  budget/costmodel price the split per group with explicit interface
  transients, a DIVERGED verdict names the group, and the engine admits
  a coupled config like any tenant.
"""

import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from mpi_cuda_process_tpu import cli, init_state, make_runner, make_step, \
    make_stencil
from mpi_cuda_process_tpu.config import RunConfig, groups_signature
from mpi_cuda_process_tpu.parallel import groups as groups_lib

HET_GROUPS = "wave3d:fine@0-3:z1/4:mesh1x4,heat3d:coarse@4-7:mesh1x4"
HET_GRID = (24, 16, 16)


# ------------------------------------------------------------ parsing

def test_parse_groups_named_rejections():
    """Every malformed clause is rejected with the reason, never a
    silently-monolithic run."""
    pg = groups_lib.parse_groups
    with pytest.raises(ValueError, match="at least 2"):
        pg("heat3d@0-7")
    with pytest.raises(ValueError, match="does not match"):
        pg("heat3d,wave3d@4-7")
    with pytest.raises(ValueError, match="unknown qualifier"):
        pg("heat3d:fast@0-3,heat3d@4-7")
    with pytest.raises(ValueError, match="power of two"):
        pg("heat3d:fine3@0-3,heat3d@4-7")
    with pytest.raises(ValueError, match="descending"):
        pg("heat3d@3-0,heat3d@4-7")
    with pytest.raises(ValueError, match="contiguous"):
        pg("heat3d@0-2,heat3d@4-7")
    with pytest.raises(ValueError, match="start at device 0"):
        pg("heat3d@1-3,heat3d@4-7")
    with pytest.raises(ValueError, match="z-fraction"):
        pg("heat3d@0-3:z3/2,heat3d@4-7")
    with pytest.raises(ValueError, match="mesh .* needs"):
        pg("heat3d@0-3:mesh2x4,heat3d@4-7")
    with pytest.raises(ValueError, match="only 8 device"):
        pg("heat3d@0-3,heat3d@4-11", n_devices=8)


def test_plan_groups_geometry_and_describe():
    plans = groups_lib.plans_from_config(HET_GROUPS, HET_GRID,
                                         n_devices=8)
    fine, coarse = plans
    assert fine.spec.ratio == 2 and coarse.spec.ratio == 1
    # z1/4 of 24 base rows = 6, refined 2x = 12 owned + one hi band
    assert (fine.base_z0, fine.base_z1) == (0, 6)
    assert fine.grid[1:] == (32, 32)  # every axis refined
    assert fine.band_lo == 0 and fine.band_hi > 0
    assert coarse.band_lo > 0 and coarse.band_hi == 0
    d = fine.describe()
    for key in ("group", "op", "ratio", "dtype", "devices", "mesh",
                "grid", "base_z", "band"):
        assert key in d
    assert d["devices"] == [0, 3]
    # a sliver group that can't even hold its own ghost bands is
    # rejected by name, with the fix (a larger :z fraction) spelled out
    with pytest.raises(ValueError, match="fewer than its own ghost"):
        groups_lib.plan_groups(
            groups_lib.parse_groups(
                "heat3d@0-3:z1/16,heat3d:fine8@4-7"), (16, 16, 16))


# ------------------------------------------------- face resampling pins

def test_restrict_interpolate_conservation_pin():
    """``restrict(interpolate(x)) == x`` BITWISE — the interface
    conservation pin, for every swept factor and dtype."""
    rng = np.random.default_rng(7)
    for dtype in ("float32", "bfloat16"):
        x = jnp.asarray(rng.standard_normal((6, 8, 8)), dtype)
        for factor in (2, 4):
            back = groups_lib.restrict(
                groups_lib.interpolate(x, factor), factor)
            np.testing.assert_array_equal(np.asarray(back),
                                          np.asarray(x))
    with pytest.raises(ValueError, match="power of two"):
        groups_lib.restrict(jnp.zeros((6, 6)), 3)


def test_interface_dtype_roundtrip_pin():
    """A bf16 band cast to f32 and back is bitwise-identical: f32
    holds every bf16 value exactly, so a mixed-precision interface
    loses nothing on the cast itself."""
    rng = np.random.default_rng(11)
    x = jnp.asarray(rng.standard_normal((4, 8, 8)), "bfloat16")
    back = x.astype("float32").astype("bfloat16")
    np.testing.assert_array_equal(np.asarray(back), np.asarray(x))


# -------------------------------------------------------- bit-exactness

def _assert_coupled_bit_exact(op, gspec, grid, rounds=6, dtype=None,
                              steps_per_round=1,
                              transport=groups_lib.TRANSPORT_BACKEND):
    """Coupled same-physics split vs the jitted monolithic reference.

    The reference is ``make_runner(step, 1)`` — the same jitted scan
    body the coupled groups run — NOT the eager step (XLA contracts
    FMAs differently under jit, so an eager reference differs in the
    last ulp and would mask real coupling bugs behind a tolerance).

    ``steps_per_round``: the groups' shared ``fuseK`` factor (round 23
    mode tokens) — one coupled round advances K monolithic steps.
    ``transport``: the interface transport under test; both transports
    must hit the SAME bits.
    """
    plans = groups_lib.plans_from_config(
        gspec, grid, default_dtype=dtype, n_devices=8)
    runner = groups_lib.CoupledRunner(plans, transport=transport)
    runner.run(rounds)
    got = runner.assemble()

    kw = {"dtype": dtype} if dtype else {}
    st = make_stencil(op, **kw)
    # make_runner donates its inputs: copy so init stays comparable
    ref = tuple(jnp.copy(f) for f in init_state(st, grid, kind="auto"))
    step1 = make_runner(make_step(st, grid), 1)
    for _ in range(rounds * steps_per_round):
        ref = step1(ref)
    assert len(got) == len(ref)
    for g, r in zip(got, ref):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(r))


def test_coupled_bit_exact_zonly_f32():
    _assert_coupled_bit_exact(
        "heat3d", "heat3d@0-3,heat3d@4-7", (30, 16, 16))


@pytest.mark.slow
def test_coupled_bit_exact_matrix():
    """z-only AND 2-axis group meshes, f32 AND bf16, one- and
    two-field ops — the full same-physics exactness matrix."""
    _assert_coupled_bit_exact(
        "wave3d", "wave3d@0-3,wave3d@4-7", (30, 16, 16))
    _assert_coupled_bit_exact(
        "heat3d", "heat3d:bf16@0-3,heat3d:bf16@4-7", (30, 16, 16),
        dtype="bfloat16")
    _assert_coupled_bit_exact(
        "heat3d", "heat3d@0-3:mesh2x2,heat3d@4-7:mesh2x2", (30, 16, 16))
    _assert_coupled_bit_exact(
        "wave3d", "wave3d:bf16@0-3:mesh2x2,wave3d:bf16@4-7:mesh2x2",
        (30, 16, 16), dtype="bfloat16")


def test_coupled_three_groups_bit_exact():
    """The band math generalizes past one interface: a middle group
    with bands on BOTH sides stays exact."""
    _assert_coupled_bit_exact(
        "heat3d",
        "heat3d@0-1:mesh1x2,heat3d@2-5:mesh1x4,heat3d@6-7:mesh1x2",
        (30, 16, 16), rounds=4)


# ------------------------------------- mode tokens (round 23, ISSUE 19)

def test_parse_mode_tokens_named_rejections():
    pg = groups_lib.parse_groups
    with pytest.raises(ValueError, match="unknown mode word"):
        pg("heat3d@0-3:stream+warp,heat3d@4-7")
    with pytest.raises(ValueError, match="fuse1 is the plain stepper"):
        pg("heat3d@0-3:fuse1,heat3d@4-7")
    with pytest.raises(ValueError, match="bad fuse token"):
        pg("heat3d@0-3:fusex,heat3d@4-7")
    with pytest.raises(ValueError, match="mutually exclusive"):
        pg("heat3d@0-3:stream+padfree,heat3d@4-7")
    with pytest.raises(ValueError, match="cannot combine"):
        pg("heat3d@0-3:plain+overlap,heat3d@4-7")
    with pytest.raises(ValueError, match="pipeline needs fuse"):
        pg("heat3d@0-3:pipeline,heat3d@4-7")
    with pytest.raises(ValueError, match="duplicate mode word"):
        pg("heat3d@0-3:overlap+overlap,heat3d@4-7")
    # the uniform-K contract is enforced at plan time, by name
    with pytest.raises(ValueError, match="fuse factors .* differ"):
        groups_lib.plans_from_config(
            "heat3d@0-3:fuse4,heat3d@4-7:fuse2", (56, 16, 16),
            n_devices=8)
    # a forced mode the builder declines raises, never degrades
    # (local z = 12 is under the streaming kernel's 3-chunk floor)
    with pytest.raises(ValueError, match="forced modes never fall back"):
        groups_lib.CoupledRunner(groups_lib.plans_from_config(
            "heat3d@0-3:fuse4+stream:mesh2x2,"
            "heat3d@4-7:fuse4+stream:mesh2x2", (40, 32, 128),
            n_devices=8))


def test_mode_tokens_canonical_and_views():
    pg = groups_lib.parse_groups
    s = pg("heat3d@0-3:overlap+stream+fuse4,heat3d@4-7")[0]
    assert s.modes == ("fuse4", "stream", "overlap")  # canonical order
    assert s.fuse_k == 4 and s.kind == "stream" and s.overlap_mode
    assert not s.pipeline_mode
    assert s.canonical() == "heat3d@0-3:fuse4+stream+overlap"
    # canonical text reparses to the same spec (the replay contract)
    assert pg(s.canonical() + ",heat3d@4-7")[0] == s
    # with_modes canonicalizes; modes fold into the groups signature
    t = pg("heat3d@0-3,heat3d@4-7")[0].with_modes(("overlap", "stream"))
    assert t.modes == ("stream", "overlap")
    assert groups_signature("heat3d@0-3:overlap,heat3d@4-7") != \
        groups_signature("heat3d@0-3,heat3d@4-7")
    # the hash itself is spelling-sensitive (pure string, no parser);
    # order-insensitivity comes from re-spelling through canonical()
    def canon_sig(raw):
        return groups_signature(
            ",".join(g.canonical() for g in pg(raw)))
    assert canon_sig("heat3d@0-3:stream+overlap,heat3d@4-7") == \
        canon_sig("heat3d@0-3:overlap+stream,heat3d@4-7")
    # plans carry the clause + modes into describe() (the manifest seed)
    d = groups_lib.plans_from_config(
        "heat3d@0-3:overlap,heat3d@4-7", (30, 16, 16),
        n_devices=8)[0].describe()
    assert d["modes"] == ["overlap"]
    assert d["clause"] == "heat3d@0-3:overlap"


def test_mode_routed_group_bit_exact_overlap():
    """An ``:overlap`` group (interior/boundary split stepper) computes
    the exact monolithic bits — the light leg of the mode matrix."""
    _assert_coupled_bit_exact(
        "heat3d", "heat3d@0-3:overlap,heat3d@4-7", (30, 16, 16))


@pytest.mark.slow
def test_mode_routed_groups_bit_exact_fused_matrix():
    """fuseK / stream mode tokens route groups through the temporal-
    blocking steppers: K micro-steps per coupled round, bit-exact
    against K monolithic steps per round."""
    # fuse4: the padded tiled kernels per group, y-sharded sub-meshes
    _assert_coupled_bit_exact(
        "heat3d",
        "heat3d@0-3:z1/2:fuse4:mesh1x4,heat3d@4-7:fuse4:mesh1x4",
        (56, 32, 16), rounds=2, steps_per_round=4)
    # fuse4+stream: the manual-DMA streaming kernels on 2-axis meshes
    _assert_coupled_bit_exact(
        "heat3d",
        "heat3d@0-3:fuse4+stream:mesh2x2,heat3d@4-7:fuse4+stream:mesh2x2",
        (88, 32, 128), rounds=2, steps_per_round=4)


# --------------------------- collective transport (round 23, ISSUE 19)

def test_collective_transport_bit_exact_zonly_f32():
    _assert_coupled_bit_exact(
        "heat3d", "heat3d@0-3,heat3d@4-7", (30, 16, 16),
        transport="collective")


@pytest.mark.slow
def test_collective_transport_bit_exact_matrix():
    """ppermute interface rounds hit the same bits as the device_put
    path: 2 and 3 groups, f32 and bf16, z-only and 2-axis meshes."""
    _assert_coupled_bit_exact(
        "wave3d", "wave3d@0-3,wave3d@4-7", (30, 16, 16),
        transport="collective")
    _assert_coupled_bit_exact(
        "heat3d", "heat3d:bf16@0-3,heat3d:bf16@4-7", (30, 16, 16),
        dtype="bfloat16", transport="collective")
    _assert_coupled_bit_exact(
        "heat3d", "heat3d@0-3:mesh2x2,heat3d@4-7:mesh2x2", (30, 16, 16),
        transport="collective")
    _assert_coupled_bit_exact(
        "heat3d",
        "heat3d@0-1:mesh1x2,heat3d@2-5:mesh2x2,heat3d@6-7:mesh1x2",
        (30, 16, 16), rounds=4, transport="collective")


def test_collective_matches_device_put_hetero():
    """A ratio'd mixed-physics interface (no monolithic reference
    exists) advances to IDENTICAL per-group state under both
    transports — the transports are interchangeable, not just both
    plausible."""
    runners = []
    for transport in groups_lib.TRANSPORTS:
        plans = groups_lib.plans_from_config(HET_GROUPS, HET_GRID,
                                             n_devices=8)
        r = groups_lib.CoupledRunner(plans, transport=transport)
        r.run(4)
        runners.append(r)
    a, b = runners
    assert a.n_groups == b.n_groups == 2
    for ga, gb in zip(a.fields, b.fields):
        for fa, fb in zip(ga, gb):
            np.testing.assert_array_equal(np.asarray(fa),
                                          np.asarray(fb))


def test_unknown_transport_rejected_by_name():
    plans = groups_lib.plans_from_config(
        "heat3d@0-3,heat3d@4-7", (30, 16, 16), n_devices=8)
    with pytest.raises(ValueError, match="--group-transport 'bogus'"):
        groups_lib.CoupledRunner(plans, transport="bogus")


def test_collective_jaxpr_transport_gate():
    """The tier-1 gate as a default-tier test: zero device_put, exactly
    2*interfaces ppermutes, nothing else collective — 2 and 3 groups."""
    from mpi_cuda_process_tpu.utils import jaxprcheck

    rep = jaxprcheck.check_group_transport_structure(
        "heat3d@0-3,heat3d@4-7", (30, 16, 16))
    assert rep["transport"] == "collective"
    assert rep["n_ppermute"] == 2 and rep["n_device_put"] == 0
    rep = jaxprcheck.check_group_transport_structure(
        "heat3d@0-1:mesh1x2,heat3d@2-5:mesh2x2,heat3d@6-7:mesh1x2",
        (30, 16, 16))
    assert rep["n_ppermute"] == 4 and rep["n_device_put"] == 0
    # mismatched y-shard counts across an interface are rejected by
    # name — the collective wire pairs edge shards y-by-y
    plans = groups_lib.plans_from_config(
        "heat3d@0-3:mesh1x4,heat3d@4-7:mesh2x2", (30, 16, 16),
        n_devices=8)
    with pytest.raises(ValueError, match="SAME y-shard count"):
        groups_lib.CoupledRunner(plans, transport="collective")


def test_coupled_checkpoint_resume_bitmatch_collective(tmp_path):
    """Checkpoint/resume under the collective transport: same per-group
    subdirs, resumed state bit-matches the uninterrupted collective
    run (bands rebuilt by the first ppermute round)."""
    ck = str(tmp_path / "ckpt")
    base = dict(stencil="heat3d", grid=(30, 16, 16), iters=8,
                groups="heat3d@0-3,heat3d@4-7",
                group_transport="collective")
    full, _ = cli.run(RunConfig(**base))
    cli.run(RunConfig(**{**base, "iters": 4}, checkpoint_every=4,
                      checkpoint_dir=ck))
    assert os.path.isdir(os.path.join(ck, "group0"))
    resumed, _ = cli.run(RunConfig(**base, checkpoint_dir=ck,
                                   resume=True))
    for a, b in zip(full, resumed):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_diverged_verdict_names_the_group_collective(tmp_path,
                                                     monkeypatch):
    """Fault injection under the collective transport still names the
    poisoned group — the union-mesh ppermutes don't smear the blame."""
    from mpi_cuda_process_tpu.obs import health as health_lib
    from mpi_cuda_process_tpu.resilience import faults

    monkeypatch.setenv("FAULT_INJECT", "numerics:step=2:nan")
    monkeypatch.setenv("FAULT_ATTEMPT", "0")
    faults.reset()
    tel = str(tmp_path / "div.jsonl")
    with pytest.raises(health_lib.SimulationDiverged,
                       match=r"^group g0:heat3d DIVERGED"):
        cli.run(RunConfig(stencil="heat3d", grid=(30, 16, 16), iters=8,
                          groups="heat3d@0-3,heat3d@4-7",
                          group_transport="collective", health=True,
                          log_every=2, telemetry=tel))
    faults.reset()
    hv = [e for e in _read_events(tel) if e.get("kind") == "health"]
    div = [e for e in hv if e["verdict"] == "DIVERGED"]
    assert div and div[0]["group"] == "g0:heat3d"


# ----------------------------------------------------------- jaxpr gate

def test_jaxpr_coupling_gate():
    from mpi_cuda_process_tpu.utils import jaxprcheck

    report = jaxprcheck.check_coupled_structure(
        groups="heat3d@0-3,heat3d@4-7", grid=(30, 16, 16))
    assert report["groups"] == ["g0:heat3d", "g1:heat3d"]
    # hetero split through the same gate: still zero cross-group ops
    report = jaxprcheck.check_coupled_structure(
        groups=HET_GROUPS, grid=HET_GRID)
    assert len(report["groups"]) == 2


# ------------------------------------------------- pricing / admission

def test_interface_traffic_budget_and_costmodel():
    from mpi_cuda_process_tpu.obs import costmodel
    from mpi_cuda_process_tpu.utils import budget

    plans = groups_lib.plans_from_config(HET_GROUPS, HET_GRID,
                                         n_devices=8)
    traffic = groups_lib.interface_traffic(plans)
    assert len(traffic) == 1
    up, dn = traffic[0]["up"], traffic[0]["down"]
    assert up["recv_bytes"] > 0 and dn["recv_bytes"] > 0
    worst, details = budget.estimate_coupled_bytes(plans)
    assert worst > 0 and len(details) == 2
    cost = costmodel.coupled_cost(plans)
    assert cost["coupled"] is True and cost["n_groups"] == 2
    assert len(cost["groups"]) == 2
    iface = cost["interface"]
    assert iface["transport"] == groups_lib.TRANSPORT_BACKEND
    # documented cross-check: bytes_per_round == the budget's interface
    # recv transients, so cost model and HBM budget cannot drift apart
    recv = sum(t[d]["recv_bytes"] for t in traffic
               for d in ("up", "down"))
    assert iface["bytes_per_round"] == recv


def test_admission_prices_coupled_config():
    from mpi_cuda_process_tpu.serving import admission

    cfg = RunConfig(stencil="wave3d", grid=HET_GRID, iters=4,
                    groups=HET_GROUPS)
    price = admission.AdmissionController().price(cfg)
    assert price["total_bytes"] > 0
    names = [g["group"] for g in price["coupled_groups"]]
    assert names == ["g0:wave3d", "g1:heat3d"]
    assert price["worst_group"] in names


# ------------------------------------------------------ hetero demo

def _read_events(path):
    with open(path) as fh:
        return [json.loads(ln) for ln in fh if ln.strip()]


def test_hetero_demo_cli_end_to_end(tmp_path):
    """Fine wave3d + coarse heat3d on 8 virtual devices, end-to-end
    through the CLI: >= 2x fewer cell-updates than uniformly-fine,
    manifest groups block + per-group chunk telemetry + coupled
    costmodel all land in the log."""
    tel = str(tmp_path / "het.jsonl")
    # 8 iters on purpose: the fine wave group's energy drifts past its
    # conservation tolerance by then — an OPEN system fed by the coarse
    # heat side — and the open_system monitors must not false-trigger
    cfg = RunConfig(stencil="wave3d", grid=HET_GRID, iters=8,
                    groups=HET_GROUPS, log_every=2, health=True,
                    telemetry=tel)
    fields, mcells = cli.run(cfg)
    assert np.asarray(fields[0]).shape == HET_GRID
    assert mcells > 0

    plans = groups_lib.plans_from_config(HET_GROUPS, HET_GRID,
                                         n_devices=8)
    coupled_cells = sum(p.cells for p in plans)
    fine_everywhere = 8 * int(np.prod(HET_GRID))  # ratio 2 on 3 axes
    assert fine_everywhere >= 2 * coupled_cells

    evs = _read_events(tel)
    man = next(e for e in evs if e.get("kind") == "manifest")
    grp_block = man["groups"]
    assert [g["group"] for g in grp_block] == ["g0:wave3d", "g1:heat3d"]
    assert [g["ratio"] for g in grp_block] == [2, 1]
    cm = next(e for e in evs if e.get("kind") == "costmodel")
    assert cm["coupled"] is True and cm["n_groups"] == 2
    # manifest cross-check: the costmodel prices the SAME resolved split
    assert [g["group"] for g in cm["groups"]] == \
        [g["group"] for g in grp_block]
    gc = [e for e in evs if e.get("kind") == "group_chunk"]
    assert {e["group"] for e in gc} == {"g0:wave3d", "g1:heat3d"}
    hv = [e for e in evs if e.get("kind") == "health"]
    assert hv and all(e.get("group") for e in hv)
    assert all(e["verdict"] == "HEALTHY" for e in hv)
    wave_inv = [e["invariant"] for e in hv
                if e["group"] == "g0:wave3d" and e.get("invariant")]
    assert wave_inv and all(b.get("open_system") for b in wave_inv)
    fin = next(e for e in evs if e.get("kind") == "summary")
    assert fin["coupled"] is True and fin["n_groups"] == 2


def test_hetero_demo_engine_submit(tmp_path):
    """The same coupled config through engine.submit: admitted,
    executed on the cli.run path, per-group stream on the handle."""
    from mpi_cuda_process_tpu import cli
    from mpi_cuda_process_tpu.engine import SimulationEngine

    eng = SimulationEngine(cli.run, telemetry_dir=str(tmp_path))
    cfg = RunConfig(stencil="wave3d", grid=HET_GRID, iters=4,
                    groups=HET_GROUPS, health=True, log_every=2)
    handle = eng.submit(cfg)
    fields, mcells = handle.result(timeout=300)
    assert np.asarray(fields[0]).shape == HET_GRID and mcells > 0
    assert handle.health_verdict() == "HEALTHY"
    kinds = {e.get("kind") for e in handle.events()}
    assert "group_chunk" in kinds


# --------------------------------------------- checkpoint / divergence

def test_coupled_checkpoint_resume_bitmatch(tmp_path):
    """A resumed coupled run bit-matches an uninterrupted one: per-group
    checkpoint subdirs, one agreed round, exact band state rebuilt by
    the first exchange of the resumed loop."""
    ck = str(tmp_path / "ckpt")
    base = dict(stencil="heat3d", grid=(30, 16, 16), iters=8,
                groups="heat3d@0-3,heat3d@4-7")
    full, _ = cli.run(RunConfig(**base))

    cli.run(RunConfig(**{**base, "iters": 4}, checkpoint_every=4,
                      checkpoint_dir=ck))
    assert os.path.isdir(os.path.join(ck, "group0"))
    resumed, _ = cli.run(RunConfig(**base, checkpoint_dir=ck,
                                   resume=True))
    for a, b in zip(full, resumed):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_diverged_verdict_names_the_group(tmp_path, monkeypatch):
    """Numeric poison in group 0 -> the eviction verdict names the
    group FIRST, and the health record carries it."""
    from mpi_cuda_process_tpu.obs import health as health_lib
    from mpi_cuda_process_tpu.resilience import faults

    monkeypatch.setenv("FAULT_INJECT", "numerics:step=2:nan")
    monkeypatch.setenv("FAULT_ATTEMPT", "0")
    faults.reset()
    tel = str(tmp_path / "div.jsonl")
    with pytest.raises(health_lib.SimulationDiverged,
                       match=r"^group g0:heat3d DIVERGED"):
        cli.run(RunConfig(stencil="heat3d", grid=(30, 16, 16), iters=8,
                          groups="heat3d@0-3,heat3d@4-7", health=True,
                          log_every=2, telemetry=tel))
    faults.reset()
    hv = [e for e in _read_events(tel) if e.get("kind") == "health"]
    div = [e for e in hv if e["verdict"] == "DIVERGED"]
    assert div and div[0]["group"] == "g0:heat3d"


def test_group_conflicts_are_named():
    with pytest.raises(ValueError, match="--overlap .*does not compose"):
        cli.run(RunConfig(stencil="heat3d", grid=(30, 16, 16), iters=2,
                          groups="heat3d@0-3,heat3d@4-7", overlap=True))
    with pytest.raises(ValueError, match="--mesh .*does not compose"):
        cli.run(RunConfig(stencil="heat3d", grid=(30, 16, 16), iters=2,
                          groups="heat3d@0-3,heat3d@4-7", mesh=(2,)))


# ------------------------------------------------- ledger / policy

def test_grp_signature_and_baseline_key_tail(tmp_path):
    """Two coupled runs with DIFFERENT splits share a label but never a
    baseline: the |grp:<sig> tail keeps them apart, so the gate says
    NO_BASELINE — a split change must never read as a regression."""
    import importlib.util

    from mpi_cuda_process_tpu.obs import ledger as ledger_lib

    split_b = "heat3d@0-3:z1/3:mesh1x4,heat3d@4-7:mesh1x4"
    sig_a = groups_signature("heat3d@0-3,heat3d@4-7")
    sig_b = groups_signature(split_b)
    assert sig_a and sig_a != sig_b
    # signature is canonical: whitespace/case never split identities
    assert groups_signature(" heat3d@0-3 , heat3d@4-7 ") == sig_a

    ledger = str(tmp_path / "ledger.jsonl")
    logs = {}
    for tag, gspec in (("a", "heat3d@0-3,heat3d@4-7"),
                       ("a2", "heat3d@0-3,heat3d@4-7"),
                       ("b", split_b)):
        tel = str(tmp_path / f"run_{tag}.jsonl")
        cli.run(RunConfig(stencil="heat3d", grid=(30, 16, 16), iters=4,
                          groups=gspec, log_every=2, telemetry=tel))
        logs[tag] = tel
    rows_a = ledger_lib.rows_from_log(logs["a"])
    rows_b = ledger_lib.rows_from_log(logs["b"])
    assert rows_a and rows_b
    # the run-level row (per-group cli_grp_ rows ride alongside since
    # round 23 — they carry the single-clause signature instead)
    run_a = next(r for r in rows_a
                 if not r["label"].startswith("cli_grp_"))
    run_b = next(r for r in rows_b
                 if not r["label"].startswith("cli_grp_"))
    assert run_a["label"] == run_b["label"]  # same grp2 label
    key_a = ledger_lib.baseline_key(run_a)
    key_b = ledger_lib.baseline_key(run_b)
    assert f"|grp:{sig_a}" in key_a and f"|grp:{sig_b}" in key_b
    assert key_a != key_b

    ledger_lib.append_rows(rows_a, ledger)
    spec = importlib.util.spec_from_file_location(
        "perf_gate", os.path.join(os.path.dirname(
            os.path.dirname(os.path.abspath(__file__))),
            "scripts", "perf_gate.py"))
    gate_mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(gate_mod)
    verdicts, _ = gate_mod.gate(logs["b"], ledger, 0.10)
    vb = next(v for v in verdicts if v["label"] == run_b["label"])
    assert vb["verdict"] == "NO_BASELINE"  # never REGRESSED
    # same split IS a baseline: a twin run (distinct source, identical
    # |grp: signature) gets judged against run a's row, not NO_BASELINE
    verdicts, _ = gate_mod.gate(logs["a2"], ledger, 0.10)
    va = next(v for v in verdicts if v["label"] == run_a["label"])
    assert va["verdict"] in ("OK", "IMPROVED", "REGRESSED")


def test_policy_treats_group_layout_as_identity(tmp_path):
    """candidates() never enumerates modes OVER a coupled config and
    the roofline never predicts one; --auto-policy instead resolves
    WITHIN it, per group (measured-beats-default across
    MODE_CANDIDATES), records one group_decisions entry per clause,
    and perf_gate --policy-check replays that resolution — exiting 1
    exactly when some group's ledger winner has moved."""
    import copy
    import importlib.util

    from mpi_cuda_process_tpu.obs import ledger as ledger_lib
    from mpi_cuda_process_tpu.policy import select as policy_select

    gspec = "heat3d@0-3,heat3d@4-7"
    cfg = RunConfig(stencil="heat3d", grid=(30, 16, 16), iters=4,
                    groups=gspec)
    cands = policy_select.candidates(cfg, "cpu", frozenset())
    assert cands == [cfg]
    assert policy_select._predict(cfg, make_stencil("heat3d"),
                                  "cpu") is None

    tel = str(tmp_path / "pol.jsonl")
    cli.run(RunConfig(stencil="heat3d", grid=(30, 16, 16), iters=4,
                      groups=gspec, auto_policy=True,
                      log_every=2, telemetry=tel))
    evs = _read_events(tel)
    pol = [e for e in evs if e.get("kind") == "policy"]
    assert pol
    ev = pol[-1]
    assert ev["requested_groups"] == gspec
    gds = ev["group_decisions"]
    assert [d["group"] for d in gds] == ["g0:heat3d", "g1:heat3d"]
    # empty ledger: nothing measured, every clause keeps its request
    assert all(d["provenance"] == "requested" and not d["locked"]
               and d["modes"] == [] for d in gds)
    pg = [e for e in evs if e.get("kind") == "policy_group"]
    assert [e["group"] for e in pg] == ["g0:heat3d", "g1:heat3d"]

    spec = importlib.util.spec_from_file_location(
        "perf_gate", os.path.join(os.path.dirname(
            os.path.dirname(os.path.abspath(__file__))),
            "scripts", "perf_gate.py"))
    gate_mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(gate_mod)
    assert gate_mod.policy_check(
        tel, str(tmp_path / "empty_ledger.jsonl")) == 0
    # the run's OWN rows can only confirm the decision — the measured
    # winner IS the clause that just ran
    own = str(tmp_path / "own.jsonl")
    ledger_lib.append_rows(ledger_lib.rows_from_log(tel), own)
    assert gate_mod.policy_check(tel, own) == 0
    # seed a faster measured row for group 0's :stream candidate: the
    # replayed per-group winner moves, so the check must trip even
    # though the run-level label is unchanged
    rows = ledger_lib.read_rows(own)
    grp = next(r for r in rows if r["label"] == "cli_grp_heat3d")
    seed = copy.deepcopy(grp)
    stream_clause = groups_lib.parse_groups(gspec)[0] \
        .with_modes(("stream",)).canonical()
    seed["key"]["flags"] = ledger_lib.group_flags(stream_clause)
    seed["key_id"] = ledger_lib.key_id(seed["key"])
    seed["value"] = float(grp["value"]) * 10.0
    seed["measured_at"] = float(grp.get("measured_at") or 1.0) + 60.0
    flipped = str(tmp_path / "flipped.jsonl")
    ledger_lib.append_rows(rows + [seed], flipped)
    assert gate_mod.policy_check(tel, flipped) == 1


def test_group_transport_splits_the_baseline(tmp_path):
    """Twin coupled runs that differ ONLY in --group-transport share a
    label but never a baseline: the |gtx:collective key tail keeps the
    ppermute wire from being judged against the device_put staging
    path (and vice versa), so the gate says NO_BASELINE."""
    import importlib.util

    from mpi_cuda_process_tpu.obs import ledger as ledger_lib

    gspec = "heat3d@0-3,heat3d@4-7"
    logs = {}
    for transport in groups_lib.TRANSPORTS:
        tel = str(tmp_path / f"run_{transport}.jsonl")
        cli.run(RunConfig(stencil="heat3d", grid=(30, 16, 16), iters=4,
                          groups=gspec, group_transport=transport,
                          log_every=2, telemetry=tel))
        logs[transport] = tel
    rows_d = ledger_lib.rows_from_log(logs["device_put"])
    rows_c = ledger_lib.rows_from_log(logs["collective"])
    assert rows_d and rows_c
    assert rows_d[0]["label"] == rows_c[0]["label"]
    key_d = ledger_lib.baseline_key(rows_d[0])
    key_c = ledger_lib.baseline_key(rows_c[0])
    assert "|gtx:" not in key_d          # the default stays tail-free
    assert "|gtx:collective" in key_c
    assert key_d != key_c
    # per-group rows split the same way
    gd = next(r for r in rows_d if r["label"].startswith("cli_grp_"))
    gc = next(r for r in rows_c if r["label"].startswith("cli_grp_"))
    assert "|gtx:collective" in ledger_lib.baseline_key(gc)
    assert ledger_lib.baseline_key(gd) != ledger_lib.baseline_key(gc)

    ledger = str(tmp_path / "ledger.jsonl")
    ledger_lib.append_rows(rows_d, ledger)
    spec = importlib.util.spec_from_file_location(
        "perf_gate", os.path.join(os.path.dirname(
            os.path.dirname(os.path.abspath(__file__))),
            "scripts", "perf_gate.py"))
    gate_mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(gate_mod)
    verdicts, _ = gate_mod.gate(logs["collective"], ledger, 0.10)
    vc = next(v for v in verdicts if v["label"] == rows_c[0]["label"])
    assert vc["verdict"] == "NO_BASELINE"  # never REGRESSED


# ------------------------------------------------------ observability

def _group_manifest():
    from mpi_cuda_process_tpu.obs import trace

    return trace.build_manifest(
        "cli", {"grid": [24, 16, 16], "groups": HET_GROUPS},
        groups=[{"group": "g0:wave3d", "op": "wave3d", "ratio": 2,
                 "dtype": "float32", "devices": [0, 3],
                 "grid": [14, 32, 32]},
                {"group": "g1:heat3d", "op": "heat3d", "ratio": 1,
                 "dtype": "float32", "devices": [4, 7],
                 "grid": [19, 16, 16]}])


def test_metrics_group_rows_and_worst_verdict():
    from mpi_cuda_process_tpu.obs.metrics import RunMetrics

    rm = RunMetrics()
    rm.ingest(_group_manifest())
    rm.ingest({"kind": "group_chunk", "step": 2, "group": "g0:wave3d",
               "op": "wave3d", "ratio": 2, "dtype": "float32",
               "steps": 2, "wall_s": 0.1, "mcells_per_s": 123.0})
    rm.ingest({"kind": "group_chunk", "step": 2, "group": "g1:heat3d",
               "op": "heat3d", "ratio": 1, "dtype": "float32",
               "steps": 2, "wall_s": 0.1, "mcells_per_s": 45.0})
    rm.ingest({"kind": "health", "verdict": "HEALTHY", "step": 2,
               "group": "g0:wave3d"})
    rm.ingest({"kind": "health", "verdict": "DIVERGED", "step": 2,
               "reason": "nonfinite", "group": "g1:heat3d"})
    st = rm.status()
    grp = st["groups"]
    assert grp["n_groups"] == 2
    assert grp["worst_verdict"] == "DIVERGED"
    # worst-first ranking: the diverged group leads the panel
    assert grp["rows"][0]["group"] == "g1:heat3d"
    assert grp["rows"][0]["verdict"] == "DIVERGED"
    assert grp["rows"][1]["mcells_per_s"] == 123.0
    assert grp["rows"][0]["devices"] == [4, 7]
    # a diverged GROUP dominates the run verdict, like run-level health
    assert st["verdict"] == "DIVERGED"
    snap = rm.registry.snapshot()
    assert snap["obs_group_chunks_total"]["value"] == 2.0


def test_obs_top_renders_group_panel():
    import importlib.util

    from mpi_cuda_process_tpu.obs.metrics import RunMetrics

    spec = importlib.util.spec_from_file_location(
        "obs_top", os.path.join(os.path.dirname(
            os.path.dirname(os.path.abspath(__file__))),
            "scripts", "obs_top.py"))
    obs_top = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(obs_top)

    rm = RunMetrics()
    rm.ingest(_group_manifest())
    rm.ingest({"kind": "group_chunk", "step": 2, "group": "g0:wave3d",
               "op": "wave3d", "ratio": 2, "dtype": "float32",
               "steps": 2, "wall_s": 0.1, "mcells_per_s": 123.0})
    rm.ingest({"kind": "health", "verdict": "HEALTHY", "step": 2,
               "group": "g0:wave3d"})
    body = obs_top.run_frame({**rm.status(), "manifest": None},
                             "/nonexistent")
    assert "2 device groups coupled at interface faces" in body
    assert "g0:wave3d" in body and "fine x2" in body
    assert "0-3" in body  # device range rendering
