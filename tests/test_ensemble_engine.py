"""Batched ensemble engine (round 15): one mesh, N simulations per step.

The contract under test, layer by layer:

* **Bit-exactness** — the batched step (``ensemble=N`` on every sharded
  stepper kind, the batched streaming builder, and the CLI composition
  ``--ensemble + --mesh``) equals N independent single-sim runs per
  member, for each kind x mesh family x dtype x overlap/pipeline/rdma
  where supported.
* **Structure** — the exchange-round count of the batched step is
  INDEPENDENT of N (vmap folds the member axis into each collective
  operand; ``jaxprcheck.assert_ensemble_exchange_invariance``), and the
  batched streaming kernel carries an explicit leading batch grid
  dimension.
* **Walls** — unsupported combinations raise explicitly (forced modes
  never silently fall back), and the OLD walls are gone: budget accepts
  ensemble configs (streaming included), cli accepts --ensemble+--mesh.
* **Money paths** — budget prices ensemble rows to the byte on both
  mesh families, cross-checked against obs/costmodel; the ledger keys
  ensemble rows apart (an ens=8 row can never baseline a single-sim
  row — perf_gate reports NO_BASELINE across ensemble sizes); the
  engine's submit/handle API streams per-member chunk telemetry.
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mpi_cuda_process_tpu import (
    init_state,
    make_mesh,
    make_stencil,
    shard_fields,
)
from mpi_cuda_process_tpu.config import (
    LIFECYCLE_FIELDS,
    RunConfig,
    SIM_FIELDS,
    sim_signature,
)
from mpi_cuda_process_tpu.driver import make_runner
from mpi_cuda_process_tpu.parallel import stepper as stepper_lib
from mpi_cuda_process_tpu.parallel.mesh import ENSEMBLE_AXIS
from mpi_cuda_process_tpu.parallel.stepper import (
    ensemble_members_local,
    ensemble_partition_spec,
    make_sharded_fused_step,
    make_sharded_step,
)
from mpi_cuda_process_tpu.utils import jaxprcheck


def _assert_members_match(batched_out, single_steps, fields, mesh, calls,
                          ensemble, atol=0.0):
    """Run each member independently and compare against the batch."""
    for i in range(ensemble):
        solo = shard_fields(tuple(f[i] for f in fields), mesh, 3)
        ref = make_runner(single_steps, calls)(solo)
        for b, r in zip(batched_out, ref):
            if atol:
                np.testing.assert_allclose(
                    np.asarray(b[i], np.float32),
                    np.asarray(r, np.float32), rtol=0, atol=atol)
            else:
                np.testing.assert_array_equal(np.asarray(b[i]),
                                              np.asarray(r))


# ---------------------------------------------------------------- mesh


def test_ensemble_mesh_axis_layout():
    mesh = make_mesh((2, 2, 1), ensemble=2)
    assert dict(mesh.shape) == {ENSEMBLE_AXIS: 2, "sx": 2, "sy": 2,
                                "sz": 1}
    spec = ensemble_partition_spec(3, mesh)
    assert spec[0] == ENSEMBLE_AXIS
    # without the axis the leading entry is unsharded
    plain = make_mesh((2, 1, 1))
    assert ensemble_partition_spec(3, plain)[0] is None


def test_ensemble_members_local_validation():
    mesh = make_mesh((2, 1, 1), ensemble=2)
    assert ensemble_members_local(mesh, 4) == 2
    with pytest.raises(ValueError, match="not divisible"):
        ensemble_members_local(mesh, 3)
    with pytest.raises(ValueError, match="unbatched"):
        ensemble_members_local(mesh, 0)
    assert ensemble_members_local(make_mesh((2, 1, 1)), 0) == 0


def test_mesh_needs_enough_devices_for_ensemble_axis():
    with pytest.raises(ValueError, match="ensemble"):
        make_mesh((2, 2, 1), ensemble=4)  # 16 > 8 virtual devices


# ------------------------------------------------- batched sharded step


def test_batched_plain_sharded_step_matches_independent():
    st = make_stencil("heat3d")
    grid, N = (32, 16, 128), 3
    mesh = make_mesh((2, 1, 1))
    batched = make_sharded_step(st, mesh, grid, ensemble=N)
    single = make_sharded_step(st, mesh, grid)
    fields = init_state(st, grid, seed=4, ensemble=N)
    out = make_runner(batched, 2)(shard_fields(fields, mesh, 3,
                                               ensemble=True))
    _assert_members_match(out, single, fields, mesh, 2, N)


def test_batched_step_on_ensemble_mesh_axis_matches_independent():
    """The headline topology: ensemble x y x z — members sharded over
    the third mesh axis, spatial exchange within each member group."""
    st = make_stencil("heat3d")
    grid, N = (32, 16, 128), 4
    mesh_e = make_mesh((2, 2, 1), ensemble=2)
    batched = make_sharded_step(st, mesh_e, grid, ensemble=N)
    fields = init_state(st, grid, seed=1, ensemble=N)
    out = make_runner(batched, 2)(shard_fields(fields, mesh_e, 3,
                                               ensemble=True))
    mesh_s = make_mesh((2, 2, 1))
    single = make_sharded_step(st, mesh_s, grid)
    _assert_members_match(out, single, fields, mesh_s, 2, N)


@pytest.mark.parametrize("name,grid,mesh_shape,kind,dtype,atol", [
    ("heat3d", (32, 16, 128), (2, 1, 1), "padfree", None, 0),
    ("heat3d", (32, 32, 128), (2, 2, 1), "padfree", None, 0),
    # a few ulp of |u| <= 2: near the shard face the batched and unbatched
    # interpreted programs round the leapfrog update differently (1 ulp,
    # 1.19e-7, on 4.4% of cells; neither is the plain step bit for bit)
    ("wave3d", (32, 16, 128), (2, 1, 1), "padfree", None, 5e-7),
    ("heat3d", (96, 32, 128), (2, 1, 1), "stream", None, 0),
    ("heat3d", (48, 64, 128), (2, 2, 1), "stream", None, 0),
    ("heat3d", (64, 32, 128), (2, 1, 1), "padfree", "bfloat16", 0),
])
def test_batched_fused_kinds_match_independent(name, grid, mesh_shape,
                                               kind, dtype, atol):
    params = {"dtype": jnp.dtype(dtype)} if dtype else {}
    st = make_stencil(name, **params)
    k = 8 if dtype == "bfloat16" else 4
    N = 2
    mesh = make_mesh(mesh_shape)
    batched = make_sharded_fused_step(st, mesh, grid, k, interpret=True,
                                      kind=kind, ensemble=N)
    single = make_sharded_fused_step(st, mesh, grid, k, interpret=True,
                                     kind=kind)
    assert batched is not None and single is not None
    assert batched._ensemble == N
    assert batched._padfree_kind == single._padfree_kind
    fields = init_state(st, grid, seed=7, ensemble=N)
    out = make_runner(batched, 2)(shard_fields(fields, mesh, 3,
                                               ensemble=True))
    _assert_members_match(out, single, fields, mesh, 2, N, atol=atol)


@pytest.mark.parametrize("overlap,pipeline", [
    (True, False), (True, True), (False, True)])
def test_batched_overlap_pipeline_match_independent(overlap, pipeline):
    st = make_stencil("heat3d")
    grid, N = (32, 16, 128), 2
    mesh = make_mesh((2, 1, 1))
    mk = lambda ens: make_sharded_fused_step(  # noqa: E731
        st, mesh, grid, 4, interpret=True, padfree=True, overlap=overlap,
        pipeline=pipeline, ensemble=ens)
    batched, single = mk(N), mk(0)
    if pipeline:
        assert batched._pipeline_active
    if overlap:
        assert batched._overlap_active
    fields = init_state(st, grid, seed=5, ensemble=N)
    out = make_runner(batched, 3)(shard_fields(fields, mesh, 3,
                                               ensemble=True))
    _assert_members_match(out, single, fields, mesh, 3, N)


def test_batched_rdma_stream_matches_independent():
    st = make_stencil("heat3d")
    grid, N = (96, 32, 128), 2
    mesh = make_mesh((2, 1, 1))
    batched = make_sharded_fused_step(st, mesh, grid, 4, interpret=True,
                                      kind="stream", exchange="rdma",
                                      ensemble=N)
    single = make_sharded_fused_step(st, mesh, grid, 4, interpret=True,
                                     kind="stream", exchange="rdma")
    assert batched._exchange == "rdma"
    fields = init_state(st, grid, seed=2, ensemble=N)
    out = make_runner(batched, 2)(shard_fields(fields, mesh, 3,
                                               ensemble=True))
    _assert_members_match(out, single, fields, mesh, 2, N)


# ------------------------------------------------------------ structure


@pytest.mark.parametrize("mesh_shape,grid,exchange", [
    ((2, 1, 1), (32, 16, 128), "ppermute"),
    ((2, 2, 1), (32, 32, 128), "ppermute"),
    ((2, 1, 1), (96, 32, 128), "rdma"),
])
def test_exchange_rounds_independent_of_ensemble(mesh_shape, grid,
                                                 exchange):
    """The headline structural pin: one exchange round per site at ANY
    N — and the count is invariant between N=2 and N=4 too."""
    rep = jaxprcheck.check_ensemble_structure(
        grid=grid, mesh_shape=mesh_shape, ensemble=2, exchange=exchange)
    rep4 = jaxprcheck.check_ensemble_structure(
        grid=grid, mesh_shape=mesh_shape, ensemble=4, exchange=exchange)
    assert rep["n_exchange_batched"] == rep4["n_exchange_batched"]


def test_batched_stream_kernel_has_leading_batch_grid_dim():
    """The vmapped streaming pallas_call must carry an EXPLICIT leading
    batch grid dimension of size N (the 'batch grid dimension' claim,
    checked against the traced grid_mapping, not inferred)."""
    from mpi_cuda_process_tpu.ops.pallas.streamfused import (
        make_stream_fused_step,
    )

    st = make_stencil("heat3d")
    grid, N = (96, 32, 128), 3
    single = make_stream_fused_step(st, grid, 4, interpret=True)
    batched = make_stream_fused_step(st, grid, 4, interpret=True, batch=N)
    assert batched._ensemble == N
    fields = init_state(st, grid, seed=3, ensemble=N)
    closed = jax.make_jaxpr(batched)(fields)

    grids = []
    for jx in jaxprcheck.iter_jaxprs(closed.jaxpr):
        for eqn in jx.eqns:
            if eqn.primitive.name == "pallas_call":
                gm = eqn.params.get("grid_mapping")
                grids.append(tuple(getattr(gm, "grid", ())))
    assert grids, "no pallas_call in the batched streaming step"
    single_grids = []
    closed_s = jax.make_jaxpr(single)(
        tuple(f[0] for f in fields))
    for jx in jaxprcheck.iter_jaxprs(closed_s.jaxpr):
        for eqn in jx.eqns:
            if eqn.primitive.name == "pallas_call":
                gm = eqn.params.get("grid_mapping")
                single_grids.append(tuple(getattr(gm, "grid", ())))
    assert grids[0][0] == N and grids[0][1:] == single_grids[0]
    # and the batched step equals per-member runs
    out = batched(fields)
    for i in range(N):
        ref = single(tuple(f[i] for f in fields))
        np.testing.assert_array_equal(np.asarray(out[0][i]),
                                      np.asarray(ref[0]))


def test_ensemble_invariance_rejects_exchange_free_program():
    st = make_stencil("heat3d")
    fields = tuple(
        jax.ShapeDtypeStruct((16, 16, 128), st.dtype)
        for _ in range(st.num_fields))
    ident = jax.make_jaxpr(lambda fs: fs)(fields)
    with pytest.raises(AssertionError, match="no exchange"):
        jaxprcheck.assert_ensemble_exchange_invariance(ident, ident)


# ------------------------------------------------------- explicit walls


def test_unsupported_combos_raise_explicitly():
    from mpi_cuda_process_tpu.cli import build

    base = dict(stencil="heat3d", grid=(96, 32, 128), iters=8)
    # periodic stream stays walled (guard-frame kernel), batched or not
    with pytest.raises(ValueError, match="guard-frame"):
        build(RunConfig(**base, fuse=4, fuse_kind="stream", periodic=True,
                        ensemble=2))
    # ensemble-mesh without ensemble
    with pytest.raises(ValueError, match="needs --ensemble"):
        build(RunConfig(**base, ensemble_mesh=2))
    # non-divisible member count
    with pytest.raises(ValueError, match="divisible"):
        build(RunConfig(**base, ensemble=3, ensemble_mesh=2))
    # perturbation without an ensemble
    with pytest.raises(ValueError, match="perturb"):
        build(RunConfig(**base, ensemble_perturb=0.1))
    # an ensemble mesh axis on an unbatched stepper build
    mesh = make_mesh((2, 1, 1), ensemble=2)
    st = make_stencil("heat3d")
    with pytest.raises(ValueError, match="unbatched"):
        make_sharded_step(st, mesh, (32, 16, 128))


def test_batched_stream_builder_rejects_wrong_shape():
    from mpi_cuda_process_tpu.ops.pallas.streamfused import (
        make_stream_fused_step,
    )

    st = make_stencil("heat3d")
    step = make_stream_fused_step(st, (96, 32, 128), 4, interpret=True,
                                  batch=2)
    bad = init_state(st, (96, 32, 128), ensemble=3)
    with pytest.raises(ValueError, match="batched streaming step"):
        step(bad)


# ------------------------------------------------------------------ cli


def test_cli_ensemble_composes_with_mesh():
    """The round-15 headline: --ensemble + --mesh builds (the old
    exclusion raise is gone) and matches independent runs."""
    from mpi_cuda_process_tpu.cli import run

    base = dict(stencil="life", grid=(16, 16), iters=5)
    ens, _ = run(RunConfig(**base, seed=4, ensemble=3, mesh=(2, 1)))
    assert np.asarray(ens[0]).shape == (3, 16, 16)
    for i in range(3):
        solo, _ = run(RunConfig(**base, seed=4 + i))
        np.testing.assert_array_equal(np.asarray(ens[0])[i],
                                      np.asarray(solo[0]))


def test_cli_ensemble_mesh_third_axis():
    from mpi_cuda_process_tpu.cli import run

    base = dict(stencil="heat3d", grid=(32, 16, 128), iters=4)
    ens, _ = run(RunConfig(**base, seed=1, ensemble=4, ensemble_mesh=2,
                           mesh=(2, 2, 1)))
    assert np.asarray(ens[0]).shape == (4, 32, 16, 128)
    for i in range(4):
        solo, _ = run(RunConfig(**base, seed=1 + i))
        np.testing.assert_array_equal(np.asarray(ens[0])[i],
                                      np.asarray(solo[0]))


def test_cli_pure_data_parallel_ensemble():
    """--ensemble-mesh with NO spatial mesh: the member axis alone is
    the device decomposition (zero exchange — each group independent)."""
    from mpi_cuda_process_tpu.cli import run

    base = dict(stencil="life", grid=(16, 16), iters=5)
    ens, _ = run(RunConfig(**base, seed=4, ensemble=4, ensemble_mesh=2))
    for i in range(4):
        solo, _ = run(RunConfig(**base, seed=4 + i))
        np.testing.assert_array_equal(np.asarray(ens[0])[i],
                                      np.asarray(solo[0]))


def test_cli_stream_ensemble_wall_deleted():
    from mpi_cuda_process_tpu.cli import run

    base = dict(stencil="heat3d", grid=(96, 32, 128), iters=8, seed=2)
    ens, _ = run(RunConfig(**base, ensemble=2, fuse=4,
                           fuse_kind="stream"))
    solo, _ = run(RunConfig(**base, fuse=4, fuse_kind="stream"))
    np.testing.assert_array_equal(np.asarray(ens[0])[0],
                                  np.asarray(solo[0]))


def test_cli_sharded_fused_ensemble_matches_single():
    from mpi_cuda_process_tpu.cli import run

    base = dict(stencil="heat3d", grid=(32, 16, 128), iters=8, seed=3,
                fuse=4, fuse_kind="padfree", mesh=(2, 1, 1))
    ens, _ = run(RunConfig(**base, ensemble=2, overlap=True,
                           pipeline=True))
    solo, _ = run(RunConfig(**base, overlap=True, pipeline=True))
    np.testing.assert_array_equal(np.asarray(ens[0])[0],
                                  np.asarray(solo[0]))


def test_ensemble_perturb_deterministic_and_distinct():
    st = make_stencil("wave3d")
    a = init_state(st, (16, 16, 128), seed=9, ensemble=3, perturb=0.1)
    b = init_state(st, (16, 16, 128), seed=9, ensemble=3, perturb=0.1)
    for fa, fb in zip(a, b):
        np.testing.assert_array_equal(np.asarray(fa), np.asarray(fb))
    plain = init_state(st, (16, 16, 128), seed=9, ensemble=3)
    # members differ from their unperturbed selves in the interior
    assert not np.array_equal(np.asarray(a[0]), np.asarray(plain[0]))
    # frame stays pinned exactly
    halo = st.halo
    np.testing.assert_array_equal(
        np.asarray(a[0])[:, :halo, :], np.asarray(plain[0])[:, :halo, :])


# ------------------------------------------------------ budget/costmodel


GiB = 2**30

# Config-5-derived ensemble rows (wave3d 2048^3 — one-eighth of config
# 5's cells per member — streaming k=4, 4 members over a 4-way ensemble
# mesh axis, 64 chips): pinned to the byte on BOTH mesh families, and
# cross-checked against obs/costmodel's independently-derived operand
# bytes below.  Re-pin deliberately on any budget-model change.
_ENSEMBLE_ROWS = {
    ("float32", (16, 1, 1)): 7_381_975_040,
    ("float32", (4, 4, 1)): 7_385_435_340,
    ("bfloat16", (16, 1, 1)): 3_690_987_520,
    ("bfloat16", (4, 4, 1)): 3_767_690_854,
}


@pytest.mark.parametrize("dtype,mesh", sorted(
    _ENSEMBLE_ROWS, key=str))
def test_budget_ensemble_rows_pinned_to_the_byte(dtype, mesh):
    from mpi_cuda_process_tpu.obs import costmodel
    from mpi_cuda_process_tpu.utils import budget

    st = make_stencil("wave3d", dtype=jnp.dtype(dtype))
    total, parts = budget.estimate_run_bytes(
        st, (2048,) * 3, mesh=mesh, fuse=4, fuse_kind="stream",
        ensemble=4, ensemble_mesh=4)
    assert total == _ENSEMBLE_ROWS[(dtype, mesh)]
    assert total < 16 * GiB  # fits a v5e chip
    cc = costmodel.budget_crosscheck(
        st, (2048,) * 3, mesh, 4, "stream", ensemble=4, ensemble_mesh=4)
    assert cc is not None and cc["match"], cc


def test_budget_stream_ensemble_wall_deleted():
    from mpi_cuda_process_tpu.utils import budget

    st = make_stencil("heat3d")
    # buildable batched streaming: priced, not walled
    total, parts = budget.estimate_run_bytes(
        st, (256,) * 3, fuse=4, fuse_kind="stream", ensemble=2)
    labels = [label for label, _ in parts]
    assert not any("UNBUILDABLE" in label for label in labels)
    assert any("members batched" in label for label in labels)
    # the state term scales with the members
    t1, _ = budget.estimate_run_bytes(st, (256,) * 3, fuse=4,
                                      fuse_kind="stream")
    assert total > 1.9 * t1
    # periodic stays walled
    _, pp = budget.estimate_run_bytes(
        st, (256,) * 3, fuse=4, fuse_kind="stream", periodic=True)
    assert any("UNBUILDABLE" in label for label, _ in pp)


def test_budget_ensemble_mesh_divides_members():
    from mpi_cuda_process_tpu.utils import budget

    st = make_stencil("heat3d")
    t_all, _ = budget.estimate_run_bytes(st, (256,) * 3, ensemble=8)
    t_split, _ = budget.estimate_run_bytes(st, (256,) * 3, ensemble=8,
                                           ensemble_mesh=4)
    assert t_all > 3.9 * t_split
    with pytest.raises(ValueError, match="divisible"):
        budget.estimate_run_bytes(st, (256,) * 3, ensemble=3,
                                  ensemble_mesh=2)


def test_costmodel_ensemble_rounds_invariant_bytes_scale():
    from mpi_cuda_process_tpu.obs import costmodel

    st = make_stencil("heat3d")
    one = costmodel.comm_stats(st, (64, 64, 128), (2, 2, 1), fuse=4,
                               fuse_kind="stream")
    four = costmodel.comm_stats(st, (64, 64, 128), (2, 2, 1), fuse=4,
                                fuse_kind="stream", batch=4)
    assert four["ppermute_rounds_per_pass"] == \
        one["ppermute_rounds_per_pass"]
    assert four["ici_bytes_per_pass"] == 4 * one["ici_bytes_per_pass"]
    assert four["slab_operand_bytes"] == 4 * one["slab_operand_bytes"]
    sc = costmodel.static_cost(st, (64, 64, 128), (2, 2, 1), fuse=4,
                               fuse_kind="stream", ensemble=8,
                               ensemble_mesh=2)
    assert sc["ensemble"] == 8 and sc["members_per_device"] == 4
    assert sc["comm"]["members_per_device"] == 4


# --------------------------------------------------------------- ledger


def test_ledger_keys_ensemble_rows_apart(tmp_path):
    from mpi_cuda_process_tpu.obs import ledger

    run_single = {"stencil": "heat3d", "grid": [64, 64, 128],
                  "fuse": 4, "fuse_kind": "stream"}
    run_ens = dict(run_single, ensemble=8)
    # flags: ensemble only when set — single-sim flags byte-identical to
    # the historical set
    assert "ensemble" not in ledger._flags(run_single)
    assert ledger._flags(run_ens)["ensemble"] == 8
    row_s = ledger.make_row("lbl", 10.0, source="t", backend="tpu",
                            flags=ledger._flags(run_single))
    row_e = ledger.make_row("lbl", 80.0, source="t", backend="tpu",
                            flags=ledger._flags(run_ens))
    assert ledger.baseline_key(row_s) != ledger.baseline_key(row_e)
    assert ledger.baseline_key(row_e).endswith("|ens8")
    # an ens=8 value can never become the single-sim baseline
    best = ledger.best_known([row_s, row_e])
    assert best[ledger.baseline_key(row_s)]["value"] == 10.0
    # cli labels name the size
    assert ledger._cli_label(run_ens).endswith("_ens8")


def test_perf_gate_no_baseline_across_ensemble_sizes(tmp_path):
    """An ens=8 manifest gated against a single-sim-only ledger must be
    NO_BASELINE, never REGRESSED/IMPROVED."""
    import sys

    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                    "scripts"))
    import perf_gate

    from mpi_cuda_process_tpu.obs import ledger, trace

    ledger_path = str(tmp_path / "ledger.jsonl")
    run_single = {"stencil": "heat3d", "grid": [64, 64, 128], "fuse": 4}
    ledger.append_rows([ledger.make_row(
        ledger._cli_label(run_single), 100.0, source="hist",
        backend="cpu", flags=ledger._flags(run_single),
        measured_at=1.0)], ledger_path)

    log = str(tmp_path / "run.jsonl")
    tw = trace.TraceWriter(log)
    tw.write_manifest(trace.build_manifest(
        "cli", dict(run_single, ensemble=8)))
    tw.event("summary", steps=8, mcells_per_s=12.5)
    tw.close()
    verdicts, _ = perf_gate.gate(log, ledger_path, 0.10)
    assert len(verdicts) == 1
    assert verdicts[0]["verdict"] == "NO_BASELINE"


# ----------------------------------------------------- metrics / status


def test_metrics_report_ensemble_and_per_member_throughput():
    from mpi_cuda_process_tpu.obs.metrics import RunMetrics

    rm = RunMetrics()
    rm.ingest({"kind": "manifest", "schema": 2, "tool": "cli",
               "run": {"stencil": "heat3d", "grid": [64, 64, 128],
                       "ensemble": 8},
               "provenance": {"backend": "cpu"}})
    rm.ingest({"kind": "chunk", "chunk": 0, "steps": 10, "wall_s": 1.0,
               "ms_per_step": 100.0, "members": 8})
    rm.ingest({"kind": "chunk", "chunk": 1, "steps": 10, "wall_s": 1.0,
               "ms_per_step": 100.0, "members": 8})
    snap = rm.registry.snapshot()
    assert snap["obs_ensemble_size"]["value"] == 8
    agg = snap["obs_gcells_per_s"]["value"]
    assert snap["obs_member_gcells_per_s"]["value"] == \
        pytest.approx(agg / 8)
    tp = rm.status()["throughput"]
    assert tp["ensemble"] == 8
    assert tp["gcells_per_s_per_member"] == \
        pytest.approx(tp["gcells_per_s"] / 8, abs=1e-4)


def test_chunk_records_carry_member_count(tmp_path):
    from mpi_cuda_process_tpu.cli import run

    log = str(tmp_path / "t.jsonl")
    run(RunConfig(stencil="life", grid=(16, 16), iters=4, ensemble=2,
                  log_every=2, telemetry=log))
    chunks = [json.loads(line) for line in open(log)
              if '"chunk"' in line]
    chunks = [c for c in chunks if c.get("kind") == "chunk"]
    assert chunks and all(c.get("members") == 2 for c in chunks)


# --------------------------------------------------------------- engine


def test_config_partition_is_total_and_disjoint():
    import dataclasses as dc

    names = {f.name for f in dc.fields(RunConfig)}
    assert SIM_FIELDS | LIFECYCLE_FIELDS == names
    assert not (SIM_FIELDS & LIFECYCLE_FIELDS)
    # lifecycle knobs never move the signature; simulation knobs do
    base = RunConfig(stencil="heat2d", grid=(32, 128), iters=4)
    assert sim_signature(base) == sim_signature(
        dc.replace(base, telemetry="/tmp/x.jsonl", log_every=2))
    assert sim_signature(base) != sim_signature(
        dc.replace(base, ensemble=4))


def test_engine_submit_handle_streams_member_telemetry(tmp_path):
    from mpi_cuda_process_tpu import cli
    from mpi_cuda_process_tpu.engine import SimulationEngine

    eng = SimulationEngine(cli.run, telemetry_dir=str(tmp_path))
    h = eng.submit(RunConfig(stencil="heat3d", grid=(16, 16, 64),
                             iters=8, ensemble=2, mesh=(2, 1, 1),
                             log_every=2))
    fields, mcells = h.result(timeout=300)
    assert np.asarray(fields[0]).shape == (2, 16, 16, 64)
    status = h.status()
    assert status["verdict"] == "DONE"
    assert status["request"]["phase"] == "done"
    assert status["throughput"]["ensemble"] == 2
    assert "gcells_per_s_per_member" in status["throughput"]
    # the event stream is the obs vocabulary, seq-cursored
    evs = h.events(after=0)
    kinds = [e["kind"] for e in evs]
    assert kinds[0] == "manifest" and "summary" in kinds
    later = h.events(after=evs[0]["_seq"])
    assert later[0]["_seq"] == evs[1]["_seq"]
    # same simulation, different lifecycle -> same signature
    h2 = eng.submit(RunConfig(stencil="heat3d", grid=(16, 16, 64),
                              iters=8, ensemble=2, mesh=(2, 1, 1)))
    h2.result(timeout=300)
    assert h2.sim_signature == h.sim_signature
    assert eng.status()["pending"] == 0


def test_engine_rejects_supervised_requests(tmp_path):
    from mpi_cuda_process_tpu import cli
    from mpi_cuda_process_tpu.engine import SimulationEngine

    eng = SimulationEngine(cli.run, telemetry_dir=str(tmp_path))
    with pytest.raises(ValueError, match="supervise"):
        eng.submit(RunConfig(stencil="life", grid=(16, 16), iters=2,
                             supervise=True))


def test_engine_delivers_run_errors(tmp_path):
    from mpi_cuda_process_tpu import cli
    from mpi_cuda_process_tpu.engine import SimulationEngine

    eng = SimulationEngine(cli.run, telemetry_dir=str(tmp_path))
    h = eng.submit(RunConfig(stencil="heat3d", grid=(96, 32, 128),
                             iters=8, fuse=4, fuse_kind="stream",
                             periodic=True))
    with pytest.raises(ValueError, match="guard-frame"):
        h.result(timeout=300)
    assert h.status()["request"]["phase"] == "failed"


# -------------------------------------------------------------- resume


def test_batched_sharded_checkpoint_resume_bitmatch(tmp_path):
    from mpi_cuda_process_tpu.cli import run

    base = dict(stencil="heat3d", grid=(32, 16, 128), seed=6, ensemble=2,
                mesh=(2, 1, 1), checkpoint_dir=str(tmp_path / "ck"))
    full, _ = run(RunConfig(**base, iters=6, checkpoint_every=3))
    resumed, _ = run(RunConfig(**base, iters=6, resume=True,
                               checkpoint_every=3))
    for f, r in zip(full, resumed):
        np.testing.assert_array_equal(np.asarray(f), np.asarray(r))
