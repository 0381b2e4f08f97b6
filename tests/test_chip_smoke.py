"""CPU rehearsal of chip_smoke.py: its refusal off-TPU and its phase logic.

The chip runs themselves are the chip tool's (``python chip_smoke.py``);
here the phases run at tiny grids on the virtual CPU devices, with the
CLI's backend check steered to "tpu" so ``--compute auto`` takes the fused
path (Pallas interpret mode) exactly as it does on the chip.
"""

import logging
import os
import shutil
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402


@pytest.fixture
def tap():
    t = chip_smoke._LogTap()
    logger = logging.getLogger("mpi_cuda_process_tpu")
    old_level = logger.level
    logger.setLevel(logging.INFO)
    logger.addHandler(t)
    yield t
    logger.removeHandler(t)
    logger.setLevel(old_level)


def test_refuses_off_tpu_and_prints_no_result(capsys):
    assert chip_smoke.main([]) != 0
    assert chip_smoke.main(["--four-chips"]) != 0
    assert capsys.readouterr().out == ""


def test_fails_alone_without_the_repo(tmp_path):
    """In a directory holding chip_smoke.py and nothing else of the repo
    it exits non-zero and prints no result line."""
    shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
    out = subprocess.run(
        [sys.executable, "chip_smoke.py"], cwd=tmp_path,
        capture_output=True, text=True, timeout=300,
        env=dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=""))
    assert out.returncode != 0
    assert '"ok"' not in out.stdout


def test_main_and_life_phases_rehearsal(tap, monkeypatch):
    from mpi_cuda_process_tpu import cli
    from mpi_cuda_process_tpu.ops.pallas import fused

    monkeypatch.setattr(cli.jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(fused, "_interpret_default", lambda: True)
    got = chip_smoke.phase_main(tap, grid=(16, 16, 128), iters=8)
    assert got["path"] == "fused"
    assert got["max_abs_err"] <= chip_smoke.HEAT_ATOL
    monkeypatch.undo()
    assert chip_smoke.phase_life(tap, grid=(32, 128), iters=8) == \
        {"cells_differing": 0}


def test_main_phase_refuses_an_unfused_auto_path(tap):
    # on the CPU auto stays on jnp: the chip check must fail loudly
    with pytest.raises(AssertionError, match="fused"):
        chip_smoke.phase_main(tap, grid=(16, 16, 128), iters=8)


def test_mesh_phase_rehearsal_on_four_virtual_devices(tap):
    got = chip_smoke.phase_mesh(tap, grid=(16, 16, 8), iters=8)
    assert got["shards"] == 4
    assert got["max_abs_err"] <= chip_smoke.HEAT_ATOL
