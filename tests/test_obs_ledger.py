"""Tests for the campaign ledger + perf regression gate.

Synthetic manifests only — no TPU required.  Pins:

* **quarantine rules** — 0.0/missing values, ``stale`` replays (flagged
  OR note-marked), noise-floor suspects, errored labels, backend
  mismatches, and WEDGED/STALLED heartbeats all land quarantined with
  a reason, and :func:`best_known` can never surface one as a baseline;
* **backfill idempotence** — the one-shot historical ingest of
  BENCH_r0*/results_r0* files (small synthetic copies of the historical
  shapes) appends once and never again;
* **gate verdicts** — IMPROVED/OK/REGRESSED/NO_BASELINE/QUARANTINED
  against a backfilled ledger, nonzero exit on an injected synthetic
  regression, ``--dry`` always 0 (the acceptance criteria);
"""

import importlib.util
import json
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from mpi_cuda_process_tpu.obs import ledger, trace  # noqa: E402


def _load_script(name, rel):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(REPO, rel))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _bench_log(tmp_path, rec, name="bench.jsonl"):
    """A schema-valid bench-tool telemetry log with one result event."""
    path = str(tmp_path / name)
    with trace.TraceWriter(path) as w:
        w.write_manifest(trace.build_manifest("bench", {"grid": [16] * 3}))
        w.event("result", **rec)
    return path


# ------------------------------------------------------ quarantine rules

def test_classify_quarantines_every_bad_shape():
    assert ledger.classify(100.0) == ("ok", None)
    for kw, frag in (
        (dict(value=0.0), "zero/missing"),
        (dict(value=None), "zero/missing"),
        (dict(value=100.0, stale=True), "stale"),
        (dict(value=100.0, suspect=True), "suspect"),
        (dict(value=100.0, error="OOM"), "errored"),
        (dict(value=100.0, backend="tpu", expected_backend="cpu"),
         "backend mismatch"),
        (dict(value=100.0, heartbeat="WEDGED"), "WEDGED"),
        (dict(value=100.0, heartbeat="STALLED"), "STALLED"),
    ):
        kw = dict(kw)
        value = kw.pop("value")
        status, reason = ledger.classify(value, **kw)
        assert status == "quarantined", kw
        assert frag in reason, (kw, reason)


def test_best_known_structurally_excludes_quarantined():
    rows = [
        ledger.make_row("lab", 50.0, source="a", backend="tpu",
                        expected_backend="tpu", measured_at=1.0),
        ledger.make_row("lab", 80.0, source="b", backend="tpu",
                        expected_backend="tpu", measured_at=2.0),
        # bigger but stale: must never win
        ledger.make_row("lab", 999.0, source="c", backend="tpu",
                        expected_backend="tpu", stale=True,
                        measured_at=3.0),
        # bigger but 0.0-style wedge on another label
        ledger.make_row("lab2", 0.0, source="d", backend="tpu",
                        expected_backend="tpu", measured_at=4.0),
    ]
    best = ledger.best_known(rows)
    assert set(best) == {"lab|tpu"}
    assert best["lab|tpu"]["value"] == 80.0
    assert best["lab|tpu"]["source"] == "b"  # provenance rides along


def test_cpu_and_tpu_rows_never_share_a_baseline():
    rows = [ledger.make_row("lab", 10.0, source="cpu-run", backend="cpu",
                            expected_backend="cpu"),
            ledger.make_row("lab", 90.0, source="tpu-run", backend="tpu",
                            expected_backend="tpu")]
    best = ledger.best_known(rows)
    assert best["lab|cpu"]["value"] == 10.0
    assert best["lab|tpu"]["value"] == 90.0


def test_bench_note_only_replay_is_quarantined(tmp_path):
    """BENCH_r01's cached replay predates the ``stale`` flag — the note
    prose is the only marker, and it must still quarantine."""
    log = _bench_log(tmp_path, {
        "metric": "heat3d_7pt_256cubed_single_chip_throughput",
        "value": 88859.1, "unit": "Mcells/s", "backend": "tpu",
        "note": "cached tpu-backend result: backend unresponsive this "
                "run"})
    rows = ledger.rows_from_log(log)
    assert len(rows) == 1
    assert rows[0]["status"] == "quarantined"
    assert "stale" in rows[0]["quarantine"]


def test_append_rows_idempotent_and_validating(tmp_path):
    path = str(tmp_path / "ledger.jsonl")
    row = ledger.make_row("lab", 10.0, source="s", backend="cpu",
                          expected_backend="cpu", measured_at=1.5)
    assert ledger.append_rows([row], path) == 1
    assert ledger.append_rows([row], path) == 0  # same uid: skipped
    assert len(ledger.read_rows(path)) == 1
    with pytest.raises(ValueError, match="status"):
        ledger.append_rows([dict(row, status="great")], path)
    # a corrupt line is loud, with its line number
    with open(path, "a") as fh:
        fh.write('{"kind": "ledger_row"}\n')
    with pytest.raises(ValueError, match=":2"):
        ledger.read_rows(path)


# ------------------------------------------------------------- backfill

_HEADLINE = "heat3d_7pt_256cubed_single_chip_throughput"


@pytest.fixture
def history_repo(tmp_path):
    """A checkout-shaped directory holding small synthetic copies of the
    historical record shapes: a note-marked cached replay (r01), a fresh
    measurement (r02), a stale-flagged replay (r03), two 0.0 unmeasured
    rounds (r04/r05), and a campaign table with ok/suspect/errored rows."""
    repo = tmp_path / "repo"
    (repo / "benchmarks").mkdir(parents=True)
    parsed = {
        1: {"metric": _HEADLINE, "value": 88.0, "unit": "Mcells/s",
            "backend": "tpu",
            "note": "cached tpu-backend result: backend unresponsive "
                    "this run"},
        2: {"metric": _HEADLINE, "value": 85.0, "unit": "Mcells/s"},
        3: {"metric": _HEADLINE + "_cached", "value": 106.0,
            "unit": "Mcells/s", "stale": True, "note": "STALE: replayed"},
        4: {"metric": "stencil_throughput_unmeasured", "value": 0.0,
            "unit": "Mcells/s", "stale": True, "note": "unmeasured"},
        5: {"metric": "stencil_throughput_unmeasured", "value": 0.0,
            "unit": "Mcells/s", "stale": True, "note": "unmeasured"},
    }
    for n, rec in parsed.items():
        (repo / f"BENCH_r0{n}.json").write_text(json.dumps(
            {"n": n, "cmd": "python bench.py", "rc": 0, "tail": "",
             "parsed": rec}))
    table = {
        "heat3d_256_f32_fused4": {
            "backend": "tpu", "compute": "fused4", "dtype": "float32",
            "grid": [256, 256, 256], "mcells_per_s": 107.0,
            "measured_at": 1785386000.5, "ms_per_step": 0.157,
            "stencil": "heat3d"},
        "heat3d_256_f32_jnp": {
            "backend": "tpu", "compute": "jnp", "dtype": "float32",
            "grid": [256, 256, 256], "mcells_per_s": 86.0,
            "measured_at": 1785386100.5, "ms_per_step": 0.195,
            "stencil": "heat3d", "suspect": True},
        "heat3d_1024_bf16": {
            "backend": "tpu", "compute": "jnp", "dtype": "bfloat16",
            "error": "JaxRuntimeError: RESOURCE_EXHAUSTED",
            "grid": [1024, 1024, 1024], "measured_at": 1785386200.5,
            "stencil": "heat3d"},
    }
    (repo / "benchmarks" / "results_r03.json").write_text(json.dumps(table))
    return str(repo)


def test_backfill_is_idempotent_and_quarantines_wedged_rounds(
        tmp_path, history_repo):
    """BENCH_r04/r05 (0.0 stale) and every suspect/errored campaign label
    land quarantined; fresh measurements land ok; a second backfill
    appends nothing."""
    path = str(tmp_path / "ledger.jsonl")
    out = ledger.backfill(repo=history_repo, ledger_path=path)
    assert out["appended"] == out["found"] > 0
    again = ledger.backfill(repo=history_repo, ledger_path=path)
    assert again["appended"] == 0  # idempotent

    rows = ledger.read_rows(path)
    by_src = {}
    for r in rows:
        by_src.setdefault(r["source"], []).append(r)
    # the replay/wedge scoreboards: r01 (note-marked cached replay), r03
    # (stale flag), r04/r05 (0.0 unmeasured) — all quarantined; r02 was
    # a genuine fresh round-2 measurement and must survive as ok
    for src in ("BENCH_r01.json", "BENCH_r03.json", "BENCH_r04.json",
                "BENCH_r05.json"):
        assert all(r["status"] == "quarantined" for r in by_src[src]), src
    assert all(r["status"] == "ok" for r in by_src["BENCH_r02.json"])
    # the campaign tables carry real measurements that survive as ok
    ok_rows = [r for r in rows if r["status"] == "ok"]
    assert any(r["source"].startswith("results_r0") for r in ok_rows)
    assert all((r["value"] or 0) > 0 for r in ok_rows)
    # and no 0.0 anywhere in the baseline view
    best = ledger.best_known(rows)
    assert best
    assert all(r["status"] == "ok" and r["value"] > 0
               for r in best.values())


# ---------------------------------------------------------- gate verdicts

@pytest.fixture()
def gate_mod():
    return _load_script("perf_gate_t", "scripts/perf_gate.py")


def _seed_baseline(tmp_path, label, value, backend="cpu"):
    path = str(tmp_path / "ledger.jsonl")
    ledger.append_rows([ledger.make_row(
        label, value, source="seeded-baseline", backend=backend,
        expected_backend=backend, measured_at=100.0)], path)
    return path


def test_gate_all_verdicts(tmp_path, gate_mod, capsys):
    # fresh manifest: one ok row (value 100), one quarantined (stale)
    log = _bench_log(tmp_path, {
        "metric": "m_ok", "value": 100.0, "unit": "Mcells/s",
        "backend": "cpu", "value_512cubed": 100.0,
        "suspect_512cubed": True})
    lpath = str(tmp_path / "ledger.jsonl")
    # baseline equal to fresh -> OK; the stale sibling -> QUARANTINED
    _seed_baseline(tmp_path, "m_ok", 100.0)
    assert gate_mod.main([log, "--ledger", lpath]) == 0
    out = capsys.readouterr().out
    assert "OK" in out and "QUARANTINED" in out

    # IMPROVED: baseline far below
    l2 = str(tmp_path / "l2.jsonl")
    ledger.append_rows([ledger.make_row(
        "m_ok", 10.0, source="old", backend="cpu",
        expected_backend="cpu", measured_at=1.0)], l2)
    assert gate_mod.main([log, "--ledger", l2]) == 0
    assert "IMPROVED" in capsys.readouterr().out

    # NO_BASELINE: empty ledger
    l3 = str(tmp_path / "l3.jsonl")
    assert gate_mod.main([log, "--ledger", l3]) == 0
    assert "NO_BASELINE" in capsys.readouterr().out

    # REGRESSED: baseline far above -> nonzero exit; --dry forces 0
    l4 = str(tmp_path / "l4.jsonl")
    ledger.append_rows([ledger.make_row(
        "m_ok", 1000.0, source="good-old-days", backend="cpu",
        expected_backend="cpu", measured_at=1.0)], l4)
    assert gate_mod.main([log, "--ledger", l4]) == 1
    assert "REGRESSED" in capsys.readouterr().out
    assert gate_mod.main([log, "--ledger", l4, "--dry"]) == 0


def test_gate_noise_band_boundaries(tmp_path, gate_mod, capsys):
    log = _bench_log(tmp_path, {"metric": "m", "value": 91.0,
                                "unit": "Mcells/s", "backend": "cpu"})
    lpath = _seed_baseline(tmp_path, "m", 100.0)
    # 91 vs 100 inside a 10% band -> OK; inside 5% -> REGRESSED
    assert gate_mod.main([log, "--ledger", lpath, "--noise", "0.10"]) == 0
    capsys.readouterr()
    assert gate_mod.main([log, "--ledger", lpath, "--noise", "0.05"]) == 1


def test_gate_quarantined_ledger_rows_never_baseline(tmp_path, gate_mod,
                                                     capsys):
    """Acceptance pin: a ledger full of stale/0.0 rows gives
    NO_BASELINE, not a comparison against garbage."""
    log = _bench_log(tmp_path, {"metric": "m", "value": 5.0,
                                "unit": "Mcells/s", "backend": "cpu"})
    lpath = str(tmp_path / "ledger.jsonl")
    ledger.append_rows([
        ledger.make_row("m", 0.0, source="wedge-r04", backend="cpu",
                        expected_backend="cpu", measured_at=1.0),
        ledger.make_row("m", 9999.0, source="stale-replay", stale=True,
                        backend="cpu", expected_backend="cpu",
                        measured_at=2.0),
    ], lpath)
    assert gate_mod.main([log, "--ledger", lpath]) == 0
    out = capsys.readouterr().out
    assert "NO_BASELINE" in out and "REGRESSED=0" in out


def test_gate_update_ledger_and_self_baseline_exclusion(tmp_path,
                                                        gate_mod, capsys):
    log = _bench_log(tmp_path, {"metric": "m", "value": 50.0,
                                "unit": "Mcells/s", "backend": "cpu"})
    lpath = str(tmp_path / "ledger.jsonl")
    # first gate ingests the run; rows from the SAME manifest are never
    # their own baseline on a re-gate
    assert gate_mod.main([log, "--ledger", lpath, "--update-ledger"]) == 0
    assert any(r["label"] == "m" for r in ledger.read_rows(lpath))
    assert gate_mod.main([log, "--ledger", lpath]) == 0
    assert "NO_BASELINE" in capsys.readouterr().out


def test_restart_trail_rides_rows_and_flags_the_gate(tmp_path, gate_mod,
                                                     capsys):
    """Round-13 satellite: a value measured after a supervised restart
    (measure 'label' events with attempts > 1, cli 'resume' events) is
    judged normally — honest — but carries the trail in the row detail
    and is flagged [after-restart] by the gate, never quarantined."""
    # measure log: one label measured on its second attempt
    mlog = str(tmp_path / "measure.jsonl")
    with trace.TraceWriter(mlog) as w:
        w.write_manifest(trace.build_manifest(
            "measure", {"out": "r.json", "builder_rev": 9}))
        w.event("label", label="lab_retry", status="ok",
                mcells_per_s=50.0, compute="jnp", attempts=2)
    rows = ledger.rows_from_log(mlog)
    assert rows[0]["status"] == "ok"
    assert rows[0]["detail"]["attempts"] == 2
    # cli log: a resumed run names its resume point
    clog = str(tmp_path / "cli.jsonl")
    with trace.TraceWriter(clog) as w:
        w.write_manifest(trace.build_manifest(
            "cli", {"stencil": "life", "grid": [64, 64], "resume": True}))
        w.event("resume", resumed_from_step=30)
        w.event("summary", mcells_per_s=12.0)
    crows = ledger.rows_from_log(clog)
    assert crows[0]["detail"]["resumed_from_step"] == 30

    lpath = _seed_baseline(tmp_path, "lab_retry", 50.0)
    assert gate_mod.main([mlog, "--ledger", lpath]) == 0
    out = capsys.readouterr().out
    assert "[after-restart]" in out and "restarted=1" in out
    assert "QUARANTINED" not in out.split("summary:")[0].split(
        "lab_retry")[1].split("\n")[0]


def test_gate_backfill_mode(tmp_path, gate_mod, capsys, monkeypatch,
                           history_repo):
    monkeypatch.setenv("OBS_LEDGER_PATH", str(tmp_path / "l.jsonl"))
    monkeypatch.setattr(ledger, "_REPO", history_repo)
    assert gate_mod.main(["--backfill"]) == 0
    assert "appended" in capsys.readouterr().out
    assert ledger.read_rows(str(tmp_path / "l.jsonl"))


# ------------------------------------------- telemetry ingestion shapes

def test_ingest_cli_and_scaling_logs(tmp_path):
    lpath = str(tmp_path / "ledger.jsonl")
    cli_log = str(tmp_path / "cli.jsonl")
    with trace.TraceWriter(cli_log) as w:
        w.write_manifest(trace.build_manifest(
            "cli", {"stencil": "heat3d", "grid": [64, 64, 128],
                    "mesh": [2, 1, 1], "fuse": 4, "fuse_kind": "stream",
                    "overlap": True, "pipeline": False}))
        w.event("summary", mcells_per_s=123.4)
    assert ledger.ingest_log(cli_log, lpath) == 1
    row = ledger.read_rows(lpath)[0]
    assert row["status"] == "ok" and row["value"] == 123.4
    assert row["label"] == "cli_heat3d_64x64x128_fuse4_stream_mesh2x1x1_overlap"
    assert row["key"]["flags"]["overlap"] is True

    scal_log = str(tmp_path / "scaling.jsonl")
    with trace.TraceWriter(scal_log) as w:
        w.write_manifest(trace.build_manifest("scaling", {"mode": "weak"}))
        w.event("rung", mode="weak", stencil="heat3d", mesh=[2, 1, 1],
                grid=[64, 64, 128], fuse=4, pipeline=True,
                kernel_kind="zslab", mcells_per_s=77.0)
        w.event("skip", mesh=[4, 1, 1], reason="untileable")
        w.event("summary")
    assert ledger.ingest_log(scal_log, lpath) == 1  # skip events ignored
    rows = ledger.read_rows(lpath)
    srow = [r for r in rows if r["label"].startswith("scaling_")][0]
    assert srow["value"] == 77.0 and srow["key"]["kind"] == "zslab"
    assert "pipeline" in srow["label"]


def test_ingest_measure_log_quarantines_errors(tmp_path):
    lpath = str(tmp_path / "ledger.jsonl")
    log = str(tmp_path / "measure.jsonl")
    with trace.TraceWriter(log) as w:
        w.write_manifest(trace.build_manifest(
            "measure", {"builder_rev": 8}))
        w.event("label", label="good", status="ok", compute="fused4",
                mcells_per_s=55.0, error=None)
        w.event("label", label="hung", status="timeout", compute="padfree4",
                mcells_per_s=None,
                error="subprocess timeout (2400s)")
        w.event("summary", labels_run=2)
    assert ledger.ingest_log(log, lpath) == 2
    rows = {r["label"]: r for r in ledger.read_rows(lpath)}
    assert rows["good"]["status"] == "ok"
    assert rows["good"]["key"]["builder_rev"] == 8
    assert rows["hung"]["status"] == "quarantined"
    assert "errored" in rows["hung"]["quarantine"]
    best = ledger.best_known(rows.values())
    assert [r["label"] for r in best.values()] == ["good"]


def test_wedged_log_heartbeat_quarantines_its_rows(tmp_path):
    lpath = str(tmp_path / "ledger.jsonl")
    log = str(tmp_path / "wedged.jsonl")
    with trace.TraceWriter(log) as w:
        w.write_manifest(trace.build_manifest(
            "cli", {"stencil": "heat3d", "grid": [64, 64, 64]}))
        w.event("heartbeat", verdict="WEDGED", detail="no progress")
        w.event("summary", mcells_per_s=42.0)
    ledger.ingest_log(log, lpath)
    row = ledger.read_rows(lpath)[0]
    assert row["status"] == "quarantined"
    assert "WEDGED" in row["quarantine"]
    assert row["heartbeat"] == "WEDGED"


def test_obs_report_ledger_summary_mode(tmp_path, capsys):
    """Satellite: `obs_report.py --ledger PATH` prints the best_known
    table per label x backend with quarantine counts + reasons — the
    campaign state in one command."""
    import time as _time

    report = _load_script("obs_report_ledger_t", "scripts/obs_report.py")
    lpath = str(tmp_path / "ledger.jsonl")
    now = _time.time()
    rows = [
        ledger.make_row("heat3d_256_f32_fused4", 107.0, source="r03",
                        measured_at=now, backend="tpu"),
        ledger.make_row("heat3d_256_f32_fused4", 99.0, source="r02",
                        measured_at=now - 10, backend="tpu"),
        ledger.make_row("heat3d_256_f32_fused4", 0.0, source="r04",
                        measured_at=now - 5, backend="tpu"),
        ledger.make_row("wave3d_512", 70.0, source="r03",
                        measured_at=now, backend="tpu",
                        heartbeat="WEDGED"),
    ]
    ledger.append_rows(rows, lpath)
    assert report.main(["--ledger", lpath]) == 0
    out = capsys.readouterr().out
    assert "2 quarantined" in out and "1 best-known baselines" in out
    assert "heat3d_256_f32_fused4|tpu" in out and "107.0" in out
    # the wedged label has NO baseline row (structurally excluded)
    assert "wave3d_512|tpu" not in out
    assert "quarantine reasons:" in out
    assert "zero/missing value" in out
    assert "heartbeat verdict WEDGED" in out

    # a missing positional without --ledger is a usage error
    with pytest.raises(SystemExit):
        report.main([])
