"""policy/select.py: measurement-driven auto-policy + live adoption.

The ``--auto-policy`` contract, pinned (ISSUE 15):

* **measured beats predicted, categorically** — one modest ledger row
  outranks every roofline prediction; with no measured candidate the
  roofline ranks the field; a ledger that says nothing applicable
  leaves the requested config in place.
* **explicit flags always win** — a non-default mode flag is locked
  through resolution and recorded in ``overrides``.
* **determinism** — ties rank on ``(-value, label)``, and the ledger
  side (``best_known``) has a total tie-order: same winner from any
  row permutation (satellite 1's pin).
* **the decision is a record** — the CLI emits a ``policy`` manifest
  event carrying decision/provenance/n_devices, the serving scheduler
  resolves at admission (resolved == explicit submission, same class),
  and ``perf_gate --policy-check`` replays the record against the
  current ledger.
* **live migration** — ``--policy-recheck`` + ``POLICY_INJECT`` flips
  the measured winner mid-run: a ``migrate`` event fires at a chunk
  boundary and the final fields bit-match the uninterrupted run under
  the target mesh.

Runs on 8 virtual CPU devices (conftest.py).
"""

import dataclasses
import itertools
import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from mpi_cuda_process_tpu import cli  # noqa: E402
from mpi_cuda_process_tpu import serving  # noqa: E402
from mpi_cuda_process_tpu.config import RunConfig  # noqa: E402
from mpi_cuda_process_tpu.obs import ledger as ledger_lib  # noqa: E402
from mpi_cuda_process_tpu.policy import select as ps  # noqa: E402

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _clean_policy_env(monkeypatch):
    monkeypatch.delenv("POLICY_INJECT", raising=False)
    ps._INJECT_FIRED.clear()
    yield
    ps._INJECT_FIRED.clear()


def _seed(ledger_path, cfg, value, backend="cpu", source="seed",
          measured_at=None):
    """One measured ``ok`` row whose identity matches ``cfg`` exactly."""
    label, _ = ps._ledger_identity(cfg, backend)
    row = ledger_lib.make_row(
        label, value, source=source,
        measured_at=measured_at if measured_at is not None else time.time(),
        backend=backend,
        flags=ledger_lib._flags(dataclasses.asdict(cfg)))
    ledger_lib.append_rows([row], ledger_path)
    return label


def _events(path):
    with open(path) as fh:
        return [json.loads(ln) for ln in fh if ln.strip()]


def _cfg(**kw):
    kw.setdefault("stencil", "heat3d")
    kw.setdefault("grid", (16, 16, 16))
    kw.setdefault("iters", 40)
    kw.setdefault("log_every", 10)
    return RunConfig(**kw)


# ------------------------------------------------------------ satellite 1

def test_best_known_tiebreak_total_order():
    """Equal-value rows: winner is the max (measured_at, key_id,
    source) — identical from every permutation of the row list."""
    c = _cfg()
    label, _ = ps._ledger_identity(c, "cpu")
    flags = ledger_lib._flags(dataclasses.asdict(c))
    rows = [ledger_lib.make_row(label, 100.0, source=s, measured_at=t,
                                backend="cpu", flags=flags)
            for s, t in (("run-b", 100.0), ("run-a", 200.0),
                         ("run-b", 200.0))]
    winners = set()
    for perm in itertools.permutations(rows):
        best = ledger_lib.best_known(list(perm))
        assert len(best) == 1
        (w,) = best.values()
        winners.add((w["measured_at"], w["source"]))
    assert winners == {(200.0, "run-b")}


# ------------------------------------------------------------- resolve

def test_measured_beats_predicted(tmp_path):
    led = str(tmp_path / "ledger.jsonl")
    winner = dataclasses.replace(_cfg(), mesh=(1, 1, 8))
    # 1 Mcell/s: far below every roofline prediction — measured must
    # still win categorically
    _seed(led, winner, 1.0)
    d = ps.resolve(_cfg(), backend="cpu", ledger_path=led)
    assert d.provenance == "measured"
    assert d.config.mesh == (1, 1, 8)
    assert d.value == 1.0
    assert d.n_devices == 8
    assert d.overrides == {}


def test_predicted_fallback_on_empty_ledger(tmp_path, monkeypatch):
    # the roofline exists only for a device kind costmodel knows: rank
    # as on a v5e (on the CPU backend there is no prediction to fall to)
    from mpi_cuda_process_tpu.obs import costmodel

    monkeypatch.setattr(costmodel, "device_peaks", lambda kind=None:
                        costmodel.DEVICE_PEAKS["TPU v5 lite"])
    led = str(tmp_path / "none.jsonl")
    d = ps.resolve(_cfg(), backend="cpu", ledger_path=led)
    assert d.provenance == "predicted"
    assert d.value is not None and d.value > 0
    assert d.table and d.table[0]["label"] == d.label


def test_explicit_flags_always_win(tmp_path):
    led = str(tmp_path / "ledger.jsonl")
    _seed(led, dataclasses.replace(_cfg(), mesh=(1, 1, 8)), 900.0)
    d = ps.resolve(_cfg(mesh=(2, 2, 2)), backend="cpu", ledger_path=led)
    assert d.config.mesh == (2, 2, 2)
    assert "mesh" in d.overrides and d.overrides["mesh"] == [2, 2, 2]


def test_tie_ranks_on_label(tmp_path):
    led = str(tmp_path / "ledger.jsonl")
    la = _seed(led, dataclasses.replace(_cfg(), mesh=(1, 1, 8)), 700.0)
    lb = _seed(led, dataclasses.replace(_cfg(), mesh=(8, 1, 1)), 700.0)
    assert la < lb  # mesh1x1x8 sorts before mesh8x1x1
    d1 = ps.resolve(_cfg(), backend="cpu", ledger_path=led)
    d2 = ps.resolve(_cfg(), backend="cpu", ledger_path=led)
    assert d1.label == d2.label == la
    assert d1.config.mesh == (1, 1, 8)


def test_adoptable_never_changes_fuse(tmp_path):
    led = str(tmp_path / "none.jsonl")
    c = _cfg(fuse=3, iters=39, log_every=39)
    d = ps.resolve(c, backend="cpu", ledger_path=led,
                   locked=frozenset(), adoptable=True)
    assert "fuse" not in ps.ADOPTABLE_FIELDS
    assert d.config.fuse == 3


# ----------------------------------------------------------- cli wiring

def test_cli_records_policy_event(tmp_path, monkeypatch):
    led = str(tmp_path / "ledger.jsonl")
    tel = str(tmp_path / "run.jsonl")
    monkeypatch.setenv("OBS_LEDGER_PATH", led)
    _seed(led, dataclasses.replace(_cfg(), mesh=(8, 1, 1)), 500.0)
    cli.run(_cfg(auto_policy=True, telemetry=tel))
    evs = _events(tel)
    pol = [e for e in evs if e["kind"] == "policy"]
    assert len(pol) == 1
    ev = pol[0]
    assert ev["decision"]["mesh"] == [8, 1, 1]
    assert ev["provenance"] == "measured"
    assert ev["n_devices"] == 8
    assert ev["requested"]["mesh"] == []
    assert ev["overrides"] == {}
    # the manifest records the RESOLVED config — the run that happened
    assert evs[0]["kind"] == "manifest"
    assert list(evs[0]["run"]["mesh"]) == [8, 1, 1]


def test_policy_recheck_requires_auto_policy():
    with pytest.raises(ValueError, match="auto.policy|auto_policy"):
        cli.run(_cfg(policy_recheck=1))


def test_perf_gate_policy_check(tmp_path, monkeypatch):
    """--policy-check: 0 while the decision matches the ledger winner,
    1 after the ledger moves (replayed with the RECORDED n_devices —
    the subprocess itself only sees one CPU device)."""
    led = str(tmp_path / "ledger.jsonl")
    tel = str(tmp_path / "run.jsonl")
    monkeypatch.setenv("OBS_LEDGER_PATH", led)
    _seed(led, dataclasses.replace(_cfg(), mesh=(8, 1, 1)), 500.0)
    cli.run(_cfg(auto_policy=True, telemetry=tel))

    gate = os.path.join(_REPO, "scripts", "perf_gate.py")
    r = subprocess.run([sys.executable, gate, tel, "--policy-check",
                        "--ledger", led], capture_output=True, text=True)
    assert r.returncode == 0, r.stdout + r.stderr

    _seed(led, dataclasses.replace(_cfg(), mesh=(1, 1, 8)), 900.0)
    r = subprocess.run([sys.executable, gate, tel, "--policy-check",
                        "--ledger", led], capture_output=True, text=True)
    assert r.returncode == 1, r.stdout + r.stderr
    assert "STALE" in r.stdout + r.stderr


# ------------------------------------------------------- live migration

@pytest.mark.slow
def test_injected_winner_migrates_bitexact(tmp_path, monkeypatch):
    """POLICY_INJECT flips the measured winner at step 20: the run
    launches on (8,1,1), migrates at the step-20 boundary, and the
    final fields bit-match an uninterrupted (1,1,8) run."""
    led = str(tmp_path / "ledger.jsonl")
    tel = str(tmp_path / "run.jsonl")
    inj = str(tmp_path / "inject.jsonl")
    monkeypatch.setenv("OBS_LEDGER_PATH", led)
    _seed(led, dataclasses.replace(_cfg(), mesh=(8, 1, 1)), 500.0)
    target = dataclasses.replace(_cfg(), mesh=(1, 1, 8))
    label2, _ = ps._ledger_identity(target, "cpu")
    ledger_lib.append_rows([ledger_lib.make_row(
        label2, 900.0, source="inject", measured_at=time.time(),
        backend="cpu",
        flags=ledger_lib._flags(dataclasses.asdict(target)))], inj)
    monkeypatch.setenv("POLICY_INJECT", f"step=20:{inj}")

    fields, _ = cli.run(_cfg(auto_policy=True, policy_recheck=1,
                             telemetry=tel))
    evs = _events(tel)
    mig = [e for e in evs if e["kind"] == "migrate"]
    assert len(mig) == 1
    assert mig[0]["step"] == 20
    assert mig[0]["dst"]["mesh"] == [1, 1, 8]
    assert mig[0]["rounds"] > 0

    want, _ = cli.run(_cfg(mesh=(1, 1, 8)))
    for g, w in zip(fields, want):
        assert np.array_equal(np.asarray(g), np.asarray(w)), \
            "migrated run != uninterrupted target-mesh run"


# ------------------------------------------------------------- serving

@pytest.mark.slow
def test_serving_resolves_at_admission(tmp_path, monkeypatch):
    """An auto-policy submission resolves BEFORE the class signature:
    it shares the resident class with the equivalent explicit job, and
    the job log records the policy event."""
    led = str(tmp_path / "ledger.jsonl")
    monkeypatch.setenv("OBS_LEDGER_PATH", led)
    base = RunConfig(stencil="heat2d", grid=(32, 32), iters=8)
    _seed(led, dataclasses.replace(base, mesh=(2, 4)), 250.0)

    eng = serving.ServingEngine(telemetry_dir=str(tmp_path / "serve"))
    ha = eng.submit(dataclasses.replace(base, auto_policy=True))
    hb = eng.submit(dataclasses.replace(base, mesh=(2, 4), seed=5))
    got, _ = ha.result(timeout=300)
    hb.result(timeout=300)
    stats = eng.close()
    assert stats["classes"] == 1, \
        "resolved submission must share the explicit job's size class"

    pol = []
    for name in os.listdir(str(tmp_path / "serve")):
        if name.endswith(".jsonl"):
            pol += [e for e in _events(str(tmp_path / "serve" / name))
                    if e.get("kind") == "policy"]
    assert len(pol) == 1 and pol[0]["decision"]["mesh"] == [2, 4]

    want, _ = cli.run(dataclasses.replace(base, mesh=(2, 4)))
    for g, w in zip(got, want):
        assert np.array_equal(np.asarray(g), np.asarray(w))
