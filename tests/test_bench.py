"""Benchmark-harness smoke tests (SURVEY.md §4.6).

The round-gate ``bench.py`` and the weak/strong/halo harness
``benchmarks/scaling.py`` are exactly the scripts with no other CI coverage —
a regression in either would ship silently and surface only in the driver's
round-end run.  These tests execute both in tiny configs and assert a finite,
positive throughput comes out, and pin that bench.py measures only on a
chip.
"""

import importlib.util
import json
import math
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def bench():
    # Plain import: bench.py's __main__ guard keeps main() from running;
    # conftest already forced the CPU platform.
    sys.path.insert(0, REPO)
    import bench as mod

    return mod


@pytest.fixture(scope="module")
def scaling():
    spec = importlib.util.spec_from_file_location(
        "scaling_smoke", os.path.join(REPO, "benchmarks", "scaling.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_bench_stencil_smoke(bench):
    mcells, per_step, compute, _suspect = bench.bench_stencil(
        "heat3d", (16, 16, 16), {}, 2, reps=1)
    assert math.isfinite(mcells) and mcells > 0
    assert math.isfinite(per_step) and per_step > 0
    assert compute == "jnp"  # fuse=0 default


def test_bench_stencil_fused_accounting(bench):
    # fused path must report per REAL step (k steps per fused call)
    mcells, per_step, compute, _suspect = bench.bench_stencil(
        "heat3d", (32, 32, 128), {}, 2, reps=1, fuse=4)
    assert compute in ("jnp", "pallas_fused_k4")  # jnp if untileable
    assert math.isfinite(mcells) and mcells > 0


def test_scaling_weak_smoke(scaling, capsys):
    # --virtual is a no-op here (the backend is already initialized by
    # conftest), so derive the expected mesh ladder from the live count.
    import jax

    n = len(jax.devices())
    rc = scaling.main([
        "--mode", "weak", "--stencil", "heat2d", "--block", "16,16",
        "--steps", "2", "--reps", "1", "--virtual", str(n),
    ])
    assert rc == 0
    recs = [json.loads(l) for l in capsys.readouterr().out.splitlines() if l]
    assert len(recs) == int(math.log2(n)) + 1  # ladder 1, 2, 4, ... n
    for rec in recs:
        assert rec["mcells_per_s"] > 0
        assert math.isfinite(rec["efficiency"])
    assert recs[0]["efficiency"] == 1.0


@pytest.mark.slow
def test_scaling_halo_smoke(scaling, capsys):
    rc = scaling.main([
        "--mode", "halo", "--stencil", "heat2d", "--block", "16,16",
        "--steps", "2", "--reps", "1", "--virtual", "8",
    ])
    assert rc == 0
    recs = [json.loads(l) for l in capsys.readouterr().out.splitlines() if l]
    assert recs, "halo mode emitted no records"
    for rec in recs:
        assert rec["ms_per_step_full"] > 0
        assert 0.0 <= rec["halo_overhead_frac"] <= 1.0


@pytest.mark.slow
def test_scaling_fused_smoke(scaling, capsys):
    """--fuse K: z/y-only mesh ladder, untileable rungs skipped, k-step
    accounting (mcells uses steps*k real steps)."""
    import jax

    n = len(jax.devices())
    rc = scaling.main([
        "--mode", "weak", "--stencil", "heat3d", "--block", "16,16,128",
        "--steps", "2", "--reps", "1", "--fuse", "4", "--virtual", str(n),
    ])
    assert rc == 0
    recs = [json.loads(l) for l in capsys.readouterr().out.splitlines() if l]
    assert recs, "fused weak mode emitted no records"
    for rec in recs:
        assert rec["fuse"] == 4
        assert rec["mesh"][2] == 1  # lane axis never sharded
        assert rec["mcells_per_s"] > 0


@pytest.mark.slow
def test_scaling_fused_overlap_ab_rows(scaling, capsys):
    """--fuse K --overlap: the communication-overlap A/B ladder — rows
    carry overlap=true and price the split stepper (rungs whose geometry
    declines the split are skipped, never silently run plain)."""
    import jax

    n = len(jax.devices())
    rc = scaling.main([
        "--mode", "weak", "--stencil", "heat3d", "--block", "32,16,128",
        "--steps", "2", "--reps", "1", "--fuse", "4", "--overlap",
        "--virtual", str(n),
    ])
    assert rc == 0
    recs = [json.loads(l) for l in capsys.readouterr().out.splitlines() if l]
    sharded = [r for r in recs if max(r["mesh"]) > 1]
    assert sharded, "overlap A/B mode emitted no sharded rows"
    for rec in sharded:
        assert rec["fuse"] == 4 and rec["overlap"] is True
        assert rec["mcells_per_s"] > 0


def test_bench_refuses_to_measure_off_tpu(bench, capsys):
    """No chip, no number: bench.py exits non-zero and prints no result
    line (there is no CPU fallback and no stale replay)."""
    assert bench.main() != 0
    assert capsys.readouterr().out == ""


def test_mktable_regenerates_from_campaign(tmp_path):
    """benchmarks/mktable.py renders the measured table from a results
    file with the LIVE auto-policy picks bolded — the mechanism that
    keeps BASELINE.md and plan.py from silently disagreeing."""
    import subprocess
    import sys as _sys

    results = {
        "heat3d_256_f32_fused4": {
            "backend": "tpu", "compute": "fused4", "dtype": "float32",
            "grid": [256, 256, 256], "mcells_per_s": 1000.0,
            "ms_per_step": 16.7, "stencil": "heat3d"},
        "heat3d_256_f32_jnp": {
            "backend": "tpu", "compute": "jnp", "dtype": "float32",
            "grid": [256, 256, 256], "mcells_per_s": 900.0,
            "ms_per_step": 18.6, "stencil": "heat3d"},
        "heat3d_1024_bf16": {
            "backend": "tpu", "compute": "jnp", "dtype": "bfloat16",
            "grid": [1024, 1024, 1024], "stencil": "heat3d",
            "error": "JaxRuntimeError: RESOURCE_EXHAUSTED: synthetic"},
    }
    path = tmp_path / "results.json"
    path.write_text(json.dumps(results))
    out = subprocess.run(
        [_sys.executable, os.path.join(REPO, "benchmarks", "mktable.py"),
         "--in", str(path)],
        capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr[-500:]
    body = out.stdout
    assert "| Config | compute | Mcells/s | ms/step |" in body
    # the auto winner appears bolded per the live policy tables
    assert "**fused4**" in body and "**1,000**" in body
    assert "| jnp | 900 |" in body
    # errored labels surface as pending, not silently dropped
    assert "Pending / errored / suspect" in body
    assert "heat3d_1024_bf16" in body
