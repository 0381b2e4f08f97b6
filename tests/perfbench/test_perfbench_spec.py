"""The benchmark finds every part of a cell by name, and refuses what it
cannot run: a device that is not a TPU, too few chips, an unknown kind."""

import json
import os
import shutil

import pytest

from perfbench import run as run_lib
from perfbench import spec


class _Dev:
    def __init__(self, platform="tpu", kind="TPU v5 lite"):
        self.platform = platform
        self.device_kind = kind


@pytest.fixture(scope="module")
def bench():
    return spec.benchmark()


def test_every_entry_has_its_files(bench):
    names = {c["name"] for c in bench["configs"]}
    for w in bench["workloads"]:
        assert w["config"] in names
        config = spec.config(bench, w["config"])
        assert config["name"] == w["config"]
        assert set(config["limits"]) >= {"state_plane_gap",
                                          "state_colsum_gap", "diag_rel_gap"}
        spec.reference(config)
        traffic = spec.traffic(w["traffic"])
        assert traffic["steps_per_observation"] > 0
    for group in ("end_to_end", "per_layer"):
        for m in bench[group]:
            assert callable(spec.reader(m["name"])), m["name"]


def test_per_layer_metric_lists_its_cells(bench):
    cells = [w["name"] for w in bench["workloads"]]
    exposed = spec.metrics(bench, "heat3d-1024.log16", per_layer=True)
    assert "exchange_exposed_frac" not in [m["name"] for m in exposed]
    on_mesh = spec.metrics(bench, "heat3d-1024-2x2.log8", per_layer=True)
    assert "exchange_exposed_frac" in [m["name"] for m in on_mesh]
    for cell in cells:
        e2e = [m["name"] for m in spec.metrics(bench, cell, per_layer=False)]
        assert "setup_s" in e2e and len(e2e) >= 2


def test_a_new_cell_needs_only_new_files(tmp_path, monkeypatch, bench):
    """A cell from new files and BENCHMARK.json entries: no code edited."""
    here = tmp_path / "perfbench"
    shutil.copytree(spec.HERE, here,
                    ignore=shutil.ignore_patterns("__pycache__"))
    (here / "traffic" / "log4.json").write_text(json.dumps(
        {"steps_per_observation": 4, "why": "one fused pass"}))
    (here / "metrics" / "chunks_n.py").write_text(
        "def read(run):\n    return len(run['chunk_s'])\n")
    new = dict(bench)
    new["workloads"] = bench["workloads"] + [
        {"name": "heat3d-1024.log4", "config": "heat3d-1024",
         "traffic": "log4", "chips": 1, "why": "every pass observed"}]
    new["per_layer"] = bench["per_layer"] + [
        {"name": "chunks_n", "unit": "chunks", "better": "higher",
         "source": "host_clock", "layer": "runner", "moves": "setup_s",
         "workloads": ["heat3d-1024.log4"]}]
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(new))
    for c in bench["configs"]:
        os.makedirs(tmp_path / os.path.dirname(c["file"]), exist_ok=True)
        shutil.copy(os.path.join(spec.ROOT, c["file"]), tmp_path / c["file"])
    monkeypatch.setattr(spec, "HERE", str(here))
    loaded = spec.benchmark(str(tmp_path))
    cell = spec.workload(loaded, "heat3d-1024.log4")
    assert spec.config(loaded, cell["config"], root=str(tmp_path))["grid"]
    assert spec.traffic(cell["traffic"])["steps_per_observation"] == 4
    names = [m["name"] for m in spec.metrics(loaded, cell["name"], True)]
    assert "chunks_n" in names
    assert spec.reader("chunks_n")({"chunk_s": [0.1, 0.2]}) == 2


def test_unknown_names_are_refused(bench):
    with pytest.raises(spec.SpecError):
        spec.workload(bench, "nope.log1")
    with pytest.raises(spec.SpecError):
        spec.traffic("nope")
    with pytest.raises(spec.SpecError):
        spec.reader("nope")


@pytest.mark.parametrize("devices,chips,why", [
    ([_Dev("cpu", "cpu")], 1, "needs a TPU"),
    ([], 1, "needs a TPU"),
    ([_Dev()], 4, "needs 4 chips"),
    ([_Dev(kind="TPU v9 imaginary")], 1, "not in perfbench/peaks.json"),
])
def test_device_refusals(devices, chips, why):
    with pytest.raises(spec.SpecError, match=why):
        spec.check_devices(devices, chips)
    assert spec.check_devices([_Dev()] * 4, 4)


def test_run_on_the_cpu_exits_nonzero_with_no_result(capsys, monkeypatch):
    for name in ("JAX_COMPILATION_CACHE_DIR", "TPU_LOG_DIR"):
        monkeypatch.delenv(name, raising=False)  # restored after the test
    rc = run_lib.main(["--workload", "heat3d-1024.log16", "--seed",
                       str(2**31 + 3), "--seconds", "1", "--trace", "0"])
    out = capsys.readouterr()
    assert rc != 0
    assert out.out == ""
    assert "needs a TPU" in out.err
