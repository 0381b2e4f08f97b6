"""CLI / config / checkpoint-resume integration tests (SURVEY.md §5.4, §5.6)."""

import numpy as np

import jax.numpy as jnp
import pytest

from mpi_cuda_process_tpu.cli import build, config_from_args, run
from mpi_cuda_process_tpu.config import RunConfig, parse_int_tuple, parse_params
from mpi_cuda_process_tpu.utils import checkpointing


def test_parse_helpers():
    assert parse_int_tuple("512,512") == (512, 512)
    assert parse_int_tuple("256x256x256") == (256, 256, 256)
    assert parse_params(["alpha=0.2", "bc=5", "mode=fast"]) == {
        "alpha": 0.2, "bc": 5, "mode": "fast"}


def test_config_roundtrip():
    cfg = RunConfig(stencil="heat3d", grid=(8, 8, 8), mesh=(2, 2))
    import json
    back = RunConfig.from_dict(json.loads(cfg.to_json()))
    assert back == cfg


def test_cli_args_to_config():
    cfg = config_from_args([
        "--stencil", "life", "--grid", "32,32", "--iters", "3",
        "--mesh", "2,2", "--param", "dtype=int32", "--seed", "5"])
    assert cfg.stencil == "life" and cfg.mesh == (2, 2) and cfg.seed == 5


def test_run_end_to_end_unsharded():
    cfg = RunConfig(stencil="heat2d", grid=(16, 16), iters=5)
    fields, mcells = run(cfg)
    assert np.asarray(fields[0]).shape == (16, 16)
    assert mcells > 0


def test_run_end_to_end_sharded():
    cfg = RunConfig(stencil="life", grid=(16, 16), iters=4, mesh=(2, 2),
                    params={"dtype": "int32"})
    fields, _ = run(cfg)
    ref = run(RunConfig(stencil="life", grid=(16, 16), iters=4,
                        params={"dtype": "int32"}))[0]
    np.testing.assert_array_equal(np.asarray(fields[0]), np.asarray(ref[0]))


def test_checkpoint_resume_bitmatch(tmp_path):
    """A resumed run must bit-match an uninterrupted one (SURVEY.md §5.4)."""
    ck = str(tmp_path / "ckpt")
    base = dict(stencil="life", grid=(16, 16), iters=10, seed=3,
                params={"dtype": "int32"})
    full, _ = run(RunConfig(**base))

    # interrupted at step 6 (checkpoint_every=3 -> checkpoints at 3, 6, 9, 10)
    run(RunConfig(**{**base, "iters": 6},
                  checkpoint_every=3, checkpoint_dir=ck))
    assert checkpointing.latest_step(ck) == 6
    resumed, _ = run(RunConfig(**base, checkpoint_dir=ck, resume=True,
                               checkpoint_every=3))
    np.testing.assert_array_equal(
        np.asarray(resumed[0]), np.asarray(full[0]))


def test_checkpoint_atomic_roundtrip(tmp_path):
    p = str(tmp_path / "c")
    f = (jnp.arange(12, dtype=jnp.float32).reshape(3, 4),)
    checkpointing.save_checkpoint(p, f, 7, {"a": 1})
    fields, step, cfg = checkpointing.load_checkpoint(p)
    assert step == 7 and cfg == {"a": 1}
    np.testing.assert_array_equal(fields[0], np.asarray(f[0]))
    # overwrite is atomic (directory replaced, not merged)
    checkpointing.save_checkpoint(p, f, 9)
    assert checkpointing.latest_step(p) == 9


def test_resume_from_nonmultiple_step_keeps_checkpointing(tmp_path):
    """Resumed runs must keep the absolute checkpoint cadence (not stall)."""
    ck = str(tmp_path / "ck2")
    base = dict(stencil="heat2d", grid=(16, 16), params={})
    # First run ends at step 10 (not a multiple of 4), checkpoints at 4, 8, 10.
    run(RunConfig(**base, iters=10, checkpoint_every=4, checkpoint_dir=ck))
    assert checkpointing.latest_step(ck) == 10
    # Resume to 20: periodic checkpoints must fire again (12, 16, 20).
    seen = []
    orig = checkpointing.save_checkpoint

    def spy(path, fields, step, config=None):
        seen.append(step)
        return orig(path, fields, step, config)

    import mpi_cuda_process_tpu.cli as cli_mod
    old = cli_mod.checkpointing.save_checkpoint
    cli_mod.checkpointing.save_checkpoint = spy
    try:
        run(RunConfig(**base, iters=20, checkpoint_every=4,
                      checkpoint_dir=ck, resume=True))
    finally:
        cli_mod.checkpointing.save_checkpoint = old
    assert 12 in seen and 16 in seen and checkpointing.latest_step(ck) == 20


def test_orbax_checkpoint_resume_bitmatch(tmp_path):
    """Orbax backend: resumed sharded run bit-matches an uninterrupted one."""
    ck = str(tmp_path / "ock")
    base = dict(stencil="life", grid=(16, 16), iters=10, seed=3,
                mesh=(2, 2), params={"dtype": "int32"},
                checkpoint_backend="orbax")
    full, _ = run(RunConfig(**{k: v for k, v in base.items()
                               if k != "checkpoint_backend"}))
    run(RunConfig(**{**base, "iters": 6},
                  checkpoint_every=3, checkpoint_dir=ck))
    assert checkpointing.latest_step(ck) == 6
    resumed, _ = run(RunConfig(**base, checkpoint_dir=ck, resume=True,
                               checkpoint_every=3))
    np.testing.assert_array_equal(
        np.asarray(resumed[0]), np.asarray(full[0]))


def test_resume_autodetects_checkpoint_format(tmp_path):
    """Resume trusts the on-disk format, not the --checkpoint-backend flag."""
    ck = str(tmp_path / "mix")
    base = dict(stencil="life", grid=(16, 16), iters=10, seed=3,
                params={"dtype": "int32"})
    full, _ = run(RunConfig(**base))
    # write with orbax, resume with the default (npy) flag
    run(RunConfig(**{**base, "iters": 6}, checkpoint_every=3,
                  checkpoint_dir=ck, checkpoint_backend="orbax"))
    resumed, _ = run(RunConfig(**base, checkpoint_dir=ck, resume=True,
                               checkpoint_every=3))
    np.testing.assert_array_equal(
        np.asarray(resumed[0]), np.asarray(full[0]))


def test_orbax_sharded_roundtrip(tmp_path):
    """Orbax save/restore of sharded fields preserves values + sharding."""
    import jax

    from mpi_cuda_process_tpu import (
        init_state, make_mesh, make_stencil, shard_fields)

    st = make_stencil("heat3d")
    mesh = make_mesh((2, 2, 2))
    fields = shard_fields(init_state(st, (8, 8, 8), kind="zero"), mesh, 3)
    p = str(tmp_path / "oc")
    checkpointing.orbax_save_checkpoint(p, fields, 5, {"x": 2})
    out, step, cfg = checkpointing.orbax_load_checkpoint(
        p, target_fields=fields)
    assert step == 5 and cfg == {"x": 2}
    assert len(out[0].sharding.device_set) == 8
    np.testing.assert_array_equal(np.asarray(out[0]), np.asarray(fields[0]))


def test_orbax_restore_reshards_across_meshes(tmp_path):
    """Restore onto a DIFFERENT mesh must land on the target sharding."""
    import jax

    from mpi_cuda_process_tpu import (
        init_state, make_mesh, make_stencil, shard_fields)

    st = make_stencil("heat3d")
    grid = (8, 8, 8)
    mesh8 = make_mesh((2, 2, 2))
    fields8 = shard_fields(init_state(st, grid, kind="zero"), mesh8, 3)
    p = str(tmp_path / "xmesh")
    checkpointing.orbax_save_checkpoint(p, fields8, 3)

    mesh4 = make_mesh((2, 2))
    target = shard_fields(init_state(st, grid, kind="zero"), mesh4, 3)
    out, step, _ = checkpointing.orbax_load_checkpoint(
        p, target_fields=target)
    assert step == 3
    assert out[0].sharding == target[0].sharding  # 4-device target, not 8
    np.testing.assert_array_equal(np.asarray(out[0]), np.asarray(fields8[0]))


def test_checkpoint_format_prefers_newest_step(tmp_path):
    """Backend switch mid-run: the format holding the newest step wins."""
    import jax.numpy as _jnp

    p = str(tmp_path / "both")
    f = (_jnp.zeros((4, 4), _jnp.float32),)
    checkpointing.save_checkpoint(p, f, 6)          # npy at step 6
    checkpointing.orbax_save_checkpoint(p, f, 12)   # orbax at step 12
    assert checkpointing.checkpoint_format(p) == "orbax"
    assert checkpointing.latest_step(p) == 12
    _, step, _ = checkpointing.load_any(p)
    assert step == 12
    checkpointing.save_checkpoint(p, f, 20)         # npy pulls ahead
    assert checkpointing.checkpoint_format(p) == "npy"
    assert checkpointing.latest_step(p) == 20


def test_npy_save_preserves_newer_orbax_steps(tmp_path):
    """An npy save must not destroy co-located (possibly NEWER) orbax steps.

    Scenario: an orbax run checkpointed to step 12; a rerun with the default
    npy backend saves step 6 into the same dir.  The newest-step-wins
    contract of checkpoint_format requires the orbax step to survive.
    """
    import jax.numpy as _jnp

    p = str(tmp_path / "both2")
    f = (_jnp.zeros((4, 4), _jnp.float32),)
    checkpointing.orbax_save_checkpoint(p, f, 12)
    checkpointing.save_checkpoint(p, f, 6)  # older npy into the same dir
    assert checkpointing.orbax_latest_step(p) == 12
    assert checkpointing.checkpoint_format(p) == "orbax"
    _, step, _ = checkpointing.load_any(p)
    assert step == 12
    # Once the npy stream pulls AHEAD, the now-stale orbax step must be
    # dropped (retention: exactly one checkpoint, never re-preserved).
    checkpointing.save_checkpoint(p, f, 20)
    assert checkpointing.orbax_latest_step(p) is None
    assert checkpointing.checkpoint_format(p) == "npy"
    assert checkpointing.latest_step(p) == 20
    # ...and symmetrically: an orbax save past the npy step drops the npy.
    checkpointing.orbax_save_checkpoint(p, f, 30)
    assert checkpointing._npy_step(p) is None
    assert checkpointing.checkpoint_format(p) == "orbax"
    assert checkpointing.latest_step(p) == 30


def test_ensemble_matches_independent_runs():
    """vmapped ensemble == N independent runs with seeds seed..seed+N-1."""
    base = dict(stencil="life", grid=(16, 16), iters=5)
    ens, _ = run(RunConfig(**base, seed=4, ensemble=3))
    assert np.asarray(ens[0]).shape == (3, 16, 16)
    for i in range(3):
        solo, _ = run(RunConfig(**base, seed=4 + i))
        np.testing.assert_array_equal(
            np.asarray(ens[0])[i], np.asarray(solo[0]))


def test_ensemble_plus_mesh_composes():
    """Round 15 deleted the exclusion wall: --ensemble + --mesh builds
    the batched sharded stepper (full equivalence coverage lives in
    tests/test_ensemble_engine.py; this pins that the old raise stays
    gone)."""
    from mpi_cuda_process_tpu.cli import build
    st, step_fn, fields, start = build(
        RunConfig(stencil="life", grid=(16, 16), iters=1,
                  ensemble=2, mesh=(2, 2)))
    assert fields[0].shape == (2, 16, 16)


def test_fuse_matches_plain_run():
    """--fuse K (temporal blocking) must not change results."""
    base = dict(stencil="heat3d", grid=(16, 16, 128), iters=8, init="random",
                seed=2)
    plain, _ = run(RunConfig(**base))
    fused, _ = run(RunConfig(**base, fuse=4))
    np.testing.assert_array_equal(
        np.asarray(fused[0]), np.asarray(plain[0]))


def test_fuse_plus_mesh_matches_plain_run():
    """--fuse K + --mesh: k fused steps per width-k exchange, same results."""
    base = dict(stencil="heat3d", grid=(16, 16, 128), iters=8, init="random",
                seed=2)
    plain, _ = run(RunConfig(**base))
    fused, _ = run(RunConfig(**base, fuse=4, mesh=(2, 2, 1)))
    np.testing.assert_allclose(
        np.asarray(fused[0]), np.asarray(plain[0]), rtol=0, atol=1e-4)


def test_fuse_rejects_bad_configs():
    import pytest
    with pytest.raises(ValueError, match="fuse"):
        # sharded lane axis: in-kernel lane rolls need whole x rows
        build(RunConfig(stencil="heat3d", grid=(16, 16, 256), iters=8,
                        fuse=4, mesh=(1, 1, 2)))
    with pytest.raises(ValueError, match="fuse"):
        build(RunConfig(stencil="life", grid=(16, 16), iters=8, fuse=4))


def test_fuse_overlap_mesh_matches_plain_run():
    """--fuse K + --mesh + --overlap: the communication-overlapped split
    composes at the CLI layer and changes no values."""
    base = dict(stencil="heat3d", grid=(32, 16, 128), iters=8,
                init="random", seed=2)
    plain, _ = run(RunConfig(**base))
    over, _ = run(RunConfig(**base, fuse=4, mesh=(2, 1, 1), overlap=True))
    np.testing.assert_allclose(
        np.asarray(over[0]), np.asarray(plain[0]), rtol=0, atol=1e-4)


def test_fuse_overlap_requires_mesh():
    with pytest.raises(ValueError, match="overlap"):
        build(RunConfig(stencil="heat3d", grid=(32, 16, 128), iters=8,
                        fuse=4, overlap=True))


def test_pipeline_cli_matches_plain_run():
    """--pipeline --overlap --fuse K --fuse-kind padfree --mesh: the
    slab-carry scan through the whole CLI stack (build -> run's
    pipeline-aware scan runner) changes no values."""
    base = dict(stencil="heat3d", grid=(32, 16, 128), iters=12,
                init="random", seed=2)
    plain, _ = run(RunConfig(**base))
    st, step_fn, _, _ = build(RunConfig(**base, fuse=4,
                                        fuse_kind="padfree",
                                        mesh=(2, 1, 1), overlap=True,
                                        pipeline=True))
    assert getattr(step_fn, "_pipeline_active", False)
    assert getattr(step_fn, "_overlap_active", False)
    pipe, _ = run(RunConfig(**base, fuse=4, fuse_kind="padfree",
                            mesh=(2, 1, 1), overlap=True, pipeline=True))
    np.testing.assert_allclose(
        np.asarray(pipe[0]), np.asarray(plain[0]), rtol=0, atol=1e-4)


def test_pipeline_cli_chunked_cadence_matches_unchunked():
    """--pipeline + --log-every (cli's scan-over-remaining/K chunking):
    every chunk re-seeds the carry with its own prologue exchange; the
    final state must match the single-scan run bit-for-bit."""
    base = dict(stencil="heat3d", grid=(32, 16, 128), iters=16,
                init="random", seed=2, fuse=4, fuse_kind="padfree",
                mesh=(2, 1, 1), pipeline=True)
    whole, _ = run(RunConfig(**base))
    chunked, _ = run(RunConfig(**base, log_every=8))
    np.testing.assert_array_equal(
        np.asarray(chunked[0]), np.asarray(whole[0]))


def test_pipeline_cli_flag_parses():
    cfg = config_from_args([
        "--stencil", "heat3d", "--grid", "32,16,128", "--iters", "8",
        "--mesh", "2,1,1", "--fuse", "4", "--fuse-kind", "padfree",
        "--overlap", "--pipeline"])
    assert cfg.pipeline and cfg.overlap and cfg.fuse == 4


def test_pipeline_cli_never_silently_falls_back():
    """A forced --pipeline raises with the reason on every host that
    cannot carry it — no silent fallback anywhere in the chain."""
    base = dict(stencil="heat3d", grid=(32, 16, 128), iters=8)
    with pytest.raises(ValueError, match="pipeline"):
        build(RunConfig(**base, pipeline=True))  # no --fuse
    with pytest.raises(ValueError, match="pipeline"):
        build(RunConfig(**base, fuse=4, pipeline=True))  # no --mesh
    with pytest.raises(ValueError, match="guard-frame"):
        build(RunConfig(**base, fuse=4, mesh=(2, 1, 1),
                        fuse_kind="padfree", periodic=True,
                        pipeline=True))
    with pytest.raises(ValueError, match="slab-operand"):
        # auto kind resolving to the exchange-padded kernel
        build(RunConfig(**base, fuse=4, mesh=(2, 1, 1), pipeline=True))
    with pytest.raises(ValueError, match="3D-only"):
        build(RunConfig(stencil="life", grid=(64, 128), iters=8, fuse=8,
                        mesh=(2,), params={"dtype": "int32"},
                        pipeline=True))
    with pytest.raises(ValueError, match="pipeline"):
        # forced stream on a geometry stream cannot tile: the None from
        # the builder must surface as the --pipeline-aware error
        build(RunConfig(**{**base, "grid": (16, 32, 128)}, fuse=4,
                        fuse_kind="stream", mesh=(2, 2, 1),
                        pipeline=True))


def test_fuse_kind_stream_matches_plain_run():
    """--fuse K --fuse-kind stream (sliding-window manual-DMA kernel) must
    agree with the plain run to the fused-window tolerance."""
    base = dict(stencil="heat3d", grid=(24, 32, 128), iters=8,
                init="random", seed=2)
    plain, _ = run(RunConfig(**base))
    stream, _ = run(RunConfig(**base, fuse=4, fuse_kind="stream"))
    np.testing.assert_allclose(
        np.asarray(stream[0]), np.asarray(plain[0]), rtol=0, atol=1e-4)


def test_fuse_kind_padfree_matches_plain_run():
    base = dict(stencil="heat3d", grid=(16, 16, 128), iters=8,
                init="random", seed=2)
    plain, _ = run(RunConfig(**base))
    pf, _ = run(RunConfig(**base, fuse=4, fuse_kind="padfree"))
    np.testing.assert_allclose(
        np.asarray(pf[0]), np.asarray(plain[0]), rtol=0, atol=1e-4)


def test_fuse_kind_stream_with_mesh_matches_plain_run():
    """--fuse K --fuse-kind stream --mesh (z-only): the sharded streaming
    kernel through the CLI — the config-5 command shape."""
    base = dict(stencil="heat3d", grid=(48, 32, 128), iters=8,
                init="random", seed=2)
    plain, _ = run(RunConfig(**base))
    stream, _ = run(RunConfig(**base, fuse=4, fuse_kind="stream",
                              mesh=(2, 1, 1)))
    np.testing.assert_allclose(
        np.asarray(stream[0]), np.asarray(plain[0]), rtol=0, atol=1e-4)


def test_fuse_kind_padfree_with_two_axis_mesh_matches_plain_run():
    """--fuse K --fuse-kind padfree --mesh z,y: the 2-axis slab-operand
    kernels through the CLI — the lifted z-only gate (round 7).  The
    forced kind must actually run pad-free (builder introspection), not
    silently fall back to the exchange-padded kernel."""
    base = dict(stencil="heat3d", grid=(16, 32, 128), iters=8,
                init="random", seed=2)
    plain, _ = run(RunConfig(**base))
    st, step_fn, _, _ = build(RunConfig(**base, fuse=4,
                                        fuse_kind="padfree",
                                        mesh=(1, 2, 1)))
    assert getattr(step_fn, "_padfree_kind", None) == "yzslab"
    pf, _ = run(RunConfig(**base, fuse=4, fuse_kind="padfree",
                          mesh=(1, 2, 1)))
    np.testing.assert_allclose(
        np.asarray(pf[0]), np.asarray(plain[0]), rtol=0, atol=1e-4)


def test_config5_rehearsal_reduced_scale():
    """BASELINE config 5's exact command SHAPE at 1/64 scale: two-field
    wave3d, bf16, z-only 8-way mesh, --fuse 4 --fuse-kind stream,
    --mem-check on — the v5e-64 launch in docs/EXECUTION.md is this
    command with the grid swapped to 4096^3 and the mesh to 64,1,1.
    Pins (a) the command executes end-to-end through the sharded
    streaming kernel on the dryrun-class mesh and (b) equals the plain
    unsharded run (so the rehearsal is a correctness statement, not just
    a smoke)."""
    args = ["--stencil", "wave3d", "--grid", "192,64,128", "--iters", "8",
            "--mesh", "8,1,1", "--fuse", "4", "--fuse-kind", "stream",
            "--dtype", "bfloat16", "--mem-check", "error"]
    fields, mcells = run(config_from_args(args))
    assert mcells > 0
    plain, _ = run(config_from_args(
        ["--stencil", "wave3d", "--grid", "192,64,128", "--iters", "8",
         "--dtype", "bfloat16"]))
    np.testing.assert_allclose(
        np.asarray(fields[0], np.float32), np.asarray(plain[0], np.float32),
        rtol=0, atol=1e-3)


def test_fuse_kind_rejects_bad_configs():
    import pytest

    # stream: guard-frame only (round 15: --ensemble now batches it —
    # the "unbatched only" wall is gone, pinned in
    # tests/test_ensemble_engine.py)
    with pytest.raises(ValueError, match="stream"):
        build(RunConfig(stencil="heat3d", grid=(24, 32, 128), iters=8,
                        fuse=4, fuse_kind="stream", periodic=True))
    # sharded stream is allowed ONLY where the builder can host it: a
    # local block too small for the sliding window raises with the
    # constraint list
    with pytest.raises(ValueError, match="stream"):
        build(RunConfig(stencil="heat3d", grid=(16, 16, 128), iters=8,
                        fuse=4, fuse_kind="stream", mesh=(2, 1, 1)))
    # y-sharded mesh (round 8): stream now BUILDS via the 2-axis
    # sliding-window kernel — the forced kind must actually run it
    # (builder introspection), never silently fall back
    st_y, step_y, _, _ = build(
        RunConfig(stencil="heat3d", grid=(48, 64, 128), iters=8,
                  fuse=4, fuse_kind="stream", mesh=(1, 2, 1)))
    assert getattr(step_y, "_padfree_kind", None) == "stream_yz"
    # ... but stays guard-frame on 2-axis meshes too
    with pytest.raises(ValueError, match="stream"):
        build(RunConfig(stencil="heat3d", grid=(48, 64, 128), iters=8,
                        fuse=4, fuse_kind="stream", mesh=(2, 2, 1),
                        periodic=True))
    # forced padfree under a mesh builds the slab-operand kernels with
    # NO padded fallback: an untileable local block raises (local z = 4
    # is below the 2m=8 tile granularity)
    with pytest.raises(ValueError, match="padfree"):
        build(RunConfig(stencil="heat3d", grid=(8, 16, 128), iters=8,
                        fuse=4, fuse_kind="padfree", mesh=(2, 1, 1)))
    # the padded tiled kind stays unsharded-only
    with pytest.raises(ValueError, match="fuse-kind"):
        build(RunConfig(stencil="heat3d", grid=(48, 32, 128), iters=8,
                        fuse=4, fuse_kind="tiled", mesh=(2, 1, 1)))
    with pytest.raises(ValueError, match="fuse-kind"):
        build(RunConfig(stencil="heat2d", grid=(64, 128), iters=8,
                        fuse=4, fuse_kind="tiled"))
    # too few z chunks for the sliding window
    with pytest.raises(ValueError, match="stream"):
        build(RunConfig(stencil="heat3d", grid=(16, 16, 128), iters=8,
                        fuse=4, fuse_kind="stream"))
    # forced kind without an explicit k: maybe_auto_fuse upgrades must
    # never be routed into a kernel that was never probed
    with pytest.raises(ValueError, match="fuse-kind"):
        build(RunConfig(stencil="heat3d", grid=(24, 32, 128), iters=8,
                        fuse_kind="stream"))


def test_dump_every_writes_snapshots(tmp_path):
    d = str(tmp_path / "dumps")
    run(RunConfig(stencil="heat2d", grid=(16, 16), iters=10,
                  dump_every=4, dump_dir=d))
    import os
    files = sorted(os.listdir(d))
    assert files == ["step_00000004.npy", "step_00000008.npy",
                     "step_00000010.npy"] or files == [
        "step_00000004.npy", "step_00000008.npy"]
    a = np.load(os.path.join(d, files[0]))
    assert a.shape == (16, 16)


def test_auto_fuse_policy_table(monkeypatch):
    """maybe_auto_fuse upgrades exactly the measured fused winners on TPU."""
    from mpi_cuda_process_tpu import plan
    from mpi_cuda_process_tpu.ops.pallas import fused

    # Patching the shared jax module makes _interpret_default() think it is
    # on TPU too — pin interpret mode explicitly (in fused's namespace,
    # where the name is bound) so the tileability probe never constructs a
    # real TPU pallas_call on the CPU test backend.
    monkeypatch.setattr(plan.jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(fused, "_interpret_default", lambda: True)
    base = dict(grid=(16, 16, 128), iters=8)
    # winners upgrade (the builder still validates tileability)
    for name in ("heat3d", "heat3d27", "wave3d"):
        assert plan.maybe_auto_fuse(RunConfig(stencil=name, **base)).fuse == 4
    # non-winners and explicit modes never upgrade
    assert plan.maybe_auto_fuse(
        RunConfig(stencil="advect3d", **base)).fuse == 0
    assert plan.maybe_auto_fuse(
        RunConfig(stencil="heat3d", compute="jnp", **base)).fuse == 0
    # bf16 gated until the k=8 win is measured on the real chip
    assert plan.maybe_auto_fuse(
        RunConfig(stencil="heat3d", dtype="bfloat16", **base)).fuse == 0
    # cadence misalignment blocks the upgrade
    assert plan.maybe_auto_fuse(
        RunConfig(stencil="heat3d", grid=(16, 16, 128), iters=6)).fuse == 0


def test_auto_fuse_kind_table(monkeypatch):
    """A family flipped into _AUTO_FUSE_KIND routes its auto upgrade
    through the streaming kernel — probing the EXACT kernel build() will
    construct, with a tiled fallback when stream declines the shape."""
    from mpi_cuda_process_tpu import plan
    from mpi_cuda_process_tpu.ops.pallas import fused, streamfused

    monkeypatch.setattr(plan.jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(fused, "_interpret_default", lambda: True)
    monkeypatch.setattr(streamfused, "_interpret_default", lambda: True)
    monkeypatch.setattr(plan, "_AUTO_FUSE_KIND", {"heat3d": "stream"})
    # streamable shape: upgrade carries the kind
    got = plan.maybe_auto_fuse(
        RunConfig(stencil="heat3d", grid=(24, 32, 128), iters=8))
    assert (got.fuse, got.fuse_kind) == (4, "stream")
    # stream-untileable shape (two z chunks): falls back to the tiled
    # upgrade instead of hard-erroring in build()
    got = plan.maybe_auto_fuse(
        RunConfig(stencil="heat3d", grid=(16, 16, 128), iters=8))
    assert (got.fuse, got.fuse_kind) == (4, "auto")
    # empty table (the shipped default): kind never set by auto
    monkeypatch.setattr(plan, "_AUTO_FUSE_KIND", {})
    got = plan.maybe_auto_fuse(
        RunConfig(stencil="heat3d", grid=(24, 32, 128), iters=8))
    assert (got.fuse, got.fuse_kind) == (4, "auto")
    # a user-forced kind WITHOUT --fuse is never auto-upgraded: it must
    # reach build()'s "--fuse-kind requires an explicit --fuse K" guard
    got = plan.maybe_auto_fuse(RunConfig(
        stencil="heat3d", grid=(24, 32, 128), iters=8,
        fuse_kind="stream"))
    assert (got.fuse, got.fuse_kind) == (0, "stream")


def test_tol_composes_with_fuse():
    """--tol + --fuse: convergence inside the while_loop, k steps per call."""
    base = dict(stencil="sor2d", grid=(16, 128), init="zero")
    plain, _ = run(RunConfig(**base, iters=4000, tol=1e-3,
                             tol_check_every=40))
    fused, _ = run(RunConfig(**base, iters=4000, tol=1e-3,
                             tol_check_every=40, fuse=8))
    # Both must land on the same converged Laplace solution (hot walls).
    np.testing.assert_allclose(
        np.asarray(fused[0]), np.asarray(plain[0]), rtol=0, atol=5e-3)


def test_ensemble_composes_with_fuse():
    """--ensemble N + --fuse K: vmapped temporal blocking, bit-exact per
    universe against independent unfused runs."""
    base = dict(stencil="life", grid=(16, 128), iters=8, seed=3,
                init="random")
    fused, _ = run(RunConfig(**base, ensemble=3, fuse=4))
    plain, _ = run(RunConfig(**base, ensemble=3))
    np.testing.assert_array_equal(np.asarray(fused[0]), np.asarray(plain[0]))


def test_ensemble_composes_with_fuse_3d():
    """The 3D windowed fused kernel under vmap (batched pallas_call grid)."""
    base = dict(stencil="heat3d", grid=(16, 16, 128), iters=4, seed=1,
                init="pulse")
    fused, _ = run(RunConfig(**base, ensemble=2, fuse=4))
    plain, _ = run(RunConfig(**base, ensemble=2))
    np.testing.assert_allclose(
        np.asarray(fused[0]), np.asarray(plain[0]), rtol=0, atol=1e-4)


def test_auto_selected_kernel_failure_raises_its_own_error(monkeypatch):
    """No hidden fallback: when the auto-selected fused kernel fails, the
    run raises that error — it is never re-run on the jnp path."""
    from mpi_cuda_process_tpu import cli
    from mpi_cuda_process_tpu.ops.pallas import fused

    monkeypatch.setattr(cli.jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(fused, "_interpret_default", lambda: True)
    calls = []

    def failing_run_once(cfg, decision=None):
        calls.append(cfg)
        raise RuntimeError("Mosaic failed to compile TPU kernel")

    monkeypatch.setattr(cli, "_run_once", failing_run_once)
    with pytest.raises(RuntimeError, match="Mosaic"):
        cli.run(RunConfig(stencil="heat3d", grid=(16, 16, 128), iters=8))
    assert [c.fuse for c in calls] == [4]  # the fused run, once, only
    assert not hasattr(cli, "_looks_like_pallas_failure")


def test_explicit_fuse_over_fullgrid_vmem_raises_by_name():
    """life 2048^2 k=16 needs more scoped VMEM than the chip has (the v5e
    compiler's count): the builder declines and an explicit --fuse
    raises the named refusal."""
    with pytest.raises(ValueError, match="VMEM budget"):
        run(RunConfig(stencil="life", grid=(2048, 2048), iters=16,
                      fuse=16))


def test_auto_full_2d_policy_table(monkeypatch):
    """2D families upgrade via _AUTO_FULL_K (whole-grid VMEM kernel) once
    a family is flipped in; the table ships empty until measured."""
    from mpi_cuda_process_tpu import plan
    from mpi_cuda_process_tpu.ops.pallas import fullgrid

    monkeypatch.setattr(plan.jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(fullgrid, "_interpret_default", lambda: True)
    base = dict(grid=(64, 128), iters=16)
    # empty table: no upgrade
    assert plan.maybe_auto_fuse(RunConfig(stencil="life", **base)).fuse == 0
    # flipped family upgrades (builder still validates alignment/VMEM)
    monkeypatch.setitem(plan._AUTO_FULL_K, "life", 8)
    assert plan.maybe_auto_fuse(RunConfig(stencil="life", **base)).fuse == 8
    # cadence misalignment still blocks
    assert plan.maybe_auto_fuse(
        RunConfig(stencil="life", grid=(64, 128), iters=12)).fuse == 0
    # unaligned width declines at the builder
    assert plan.maybe_auto_fuse(
        RunConfig(stencil="life", grid=(64, 100), iters=16)).fuse == 0


@pytest.mark.parametrize(
    "label,args",
    [
        # Scaled-down analogues of the five BASELINE.json configs: same
        # stencil/mesh STRUCTURE, tiny extents, run end-to-end through the
        # CLI on the virtual device mesh.  What this pins: every north-star
        # config is expressible as one command line and actually executes
        # (SURVEY.md §5.6 'every BASELINE.json config expressible').
        ("config1_2d5pt", ["--stencil", "heat2d", "--grid", "64,128",
                           "--iters", "20"]),
        ("config2_3d7pt_single", ["--stencil", "heat3d",
                                  "--grid", "16,16,128", "--iters", "10"]),
        ("config3_3d7pt_2x2", ["--stencil", "heat3d", "--grid", "16,16,128",
                               "--iters", "10", "--mesh", "2,2,1"]),
        ("config4_27pt_8chip", ["--stencil", "heat3d27",
                                "--grid", "16,16,128", "--iters", "6",
                                "--mesh", "4,2,1"]),
        # bf16's sublane tile (16) requires k=8 temporal blocking
        ("config5_wave_fused_sharded", [
            "--stencil", "wave3d", "--grid", "32,32,128", "--iters", "16",
            "--mesh", "2,1,1", "--fuse", "8", "--dtype", "bfloat16"]),
    ],
)
def test_baseline_config_analogues_run_end_to_end(label, args):
    fields, mcells = run(config_from_args(args))
    arr = np.asarray(fields[0], dtype=np.float32)
    assert np.isfinite(arr).all(), label
    assert mcells > 0, label


def test_tol_composes_with_sharded_fuse():
    """Convergence mode + temporal blocking + decomposition in ONE run:
    the while_loop body advances k fused steps on the sharded state."""
    args = ["--stencil", "heat3d", "--grid", "16,16,128", "--iters", "40",
            "--mesh", "2,1,1", "--fuse", "4", "--tol", "1e-7",
            "--tol-check-every", "8"]
    fields, _ = run(config_from_args(args))
    arr = np.asarray(fields[0])
    assert np.isfinite(arr).all()
    # hot walls diffused inward: interior is strictly above the zero init
    assert arr[1:-1, 1:-1, 1:-1].mean() > 0


def test_auto_fuse_at_1024_probes_padfree_variant(monkeypatch):
    """At 1024^3 the auto-fuse probe must construct the PAD-FREE kernel
    (the padded transient is the measured RESOURCE_EXHAUSTED) — pin that
    maybe_auto_fuse upgrades, i.e. the probe chain doesn't decline."""
    from mpi_cuda_process_tpu import plan
    from mpi_cuda_process_tpu.ops.pallas import fused

    monkeypatch.setattr(plan.jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(fused, "_interpret_default", lambda: True)
    built = {}
    orig = fused.make_fused_step

    def spy(st, grid, k, **kw):
        built.setdefault("padfree", kw.get("padfree"))
        return orig(st, grid, k, **kw)

    monkeypatch.setattr(fused, "make_fused_step", spy)
    # plan imports make_fused_step by name inside the function: patch the
    # module it resolves from (it does a local import of fused each call)
    cfg = RunConfig(stencil="heat3d", grid=(1024, 1024, 1024), iters=8)
    out = plan.maybe_auto_fuse(cfg)
    assert out.fuse == 4
    assert built.get("padfree") is True  # the 1024^3 path, not the padded


def test_auto_fuse_declines_what_the_budget_refuses(monkeypatch, caplog):
    """wave3d 1024^3 f32: fused k=4 writes both 4 GiB fields anew and does
    not fit one chip, so auto keeps the single-step path (the in-place
    raw kernel) and says why in one line; heat3d 1024^3 still fuses."""
    import logging

    from mpi_cuda_process_tpu import plan
    from mpi_cuda_process_tpu.ops.pallas import fused

    monkeypatch.setattr(plan.jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(fused, "_interpret_default", lambda: True)
    base = dict(grid=(1024, 1024, 1024), iters=64, log_every=16)
    with caplog.at_level(logging.INFO, logger="mpi_cuda_process_tpu"):
        out = plan.maybe_auto_fuse(RunConfig(stencil="wave3d", **base))
    assert out.fuse == 0
    said = [r.getMessage() for r in caplog.records if "declined" in
            r.getMessage()]
    assert len(said) == 1 and "17.60 GiB" in said[0], said
    assert plan.maybe_auto_fuse(RunConfig(stencil="heat3d", **base)).fuse \
        == 4
