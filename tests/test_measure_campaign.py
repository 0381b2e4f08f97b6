"""Campaign-hardening logic in benchmarks/measure.py.

A regression in any of these rules costs real hardware time (a label
retried or skipped wrongly, a hang that costs the labels after it).
Everything here is pure-Python / CPU-backend; no label is measured on TPU.
"""

import importlib.util
import json
import os
import sys

import pytest

_BENCH_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks")


@pytest.fixture()
def M():
    """A fresh measure module (CONFIGS edits must not leak across tests)."""
    spec = importlib.util.spec_from_file_location(
        "measure_under_test", os.path.join(_BENCH_DIR, "measure.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_parse_kspec(M):
    assert M._parse_kspec("4") == (4, None)
    assert M._parse_kspec("16") == (16, None)
    assert M._parse_kspec("4@16x16") == (4, (16, 16))
    assert M._parse_kspec("8@32x16") == (8, (32, 16))
    # streaming kernels take an optional 3rd x-window extent
    assert M._parse_kspec("4@8x16x256") == (4, (8, 16, 256))


def test_labels_unique_and_risky_derived(M):
    labels = [label for label, *_ in M.CONFIGS]
    assert len(labels) == len(set(labels))
    # the risky set is positional: everything at/after the Tier-D marker
    start = labels.index(M._TIER_D_START)
    assert M._RISKY == frozenset(labels[start:])
    # Tier D must be non-empty and must not swallow the safe tiers.
    # The risky tail has grown a sub-tier per perf round (D9..D15),
    # so the bound is 2/3 rather than the original half — the safe
    # jnp/raw/copy prefix must stay a substantial minority.
    assert 0 < len(M._RISKY) < len(labels) * 2 / 3
    assert start > 0


def test_risky_labels_are_new_large_compiles(M):
    # every risky label is a fused/padfree/stream variant (the classes
    # with hang history or no on-chip compile history); jnp/raw/copy/full
    # never hang.  rdma joined in round 12: the collective pallas_call
    # class (remote DMA + barrier/credit semaphores) has NO on-chip
    # compile history at all, so it belongs in Tier D by definition.
    # grp2 joined in round 22: each group compiles only the plain
    # sharded stepper (never hangs), but the multi-sub-mesh build +
    # cross-group device_put interface transport has no on-chip history.
    for label, name, grid, steps, dtype, compute in M.CONFIGS:
        if label in M._RISKY:
            assert compute.startswith(
                ("fused", "padfree", "stream", "shfused", "overlap",
                 "pipe", "rdma", "grp2")), label


def _run_single_label(M, out, label="heat2d_512_f32"):
    M.CONFIGS = [c for c in M.CONFIGS if c[0] == label]
    argv = sys.argv
    sys.argv = ["measure.py", "--out", out, "--in-process"]
    try:
        M.main()
    finally:
        sys.argv = argv


def test_recorded_timeout_retried_at_current_rev(M, tmp_path):
    """A recorded timeout is an error like any other: the next run
    retries it (no skip rule for timeouts)."""
    out = str(tmp_path / "r.json")
    rec = {"error": "subprocess timeout (2400s)", "timeout": True,
           "builder_rev": M.BUILDER_REV}
    (tmp_path / "r.json").write_text(json.dumps({"heat2d_512_f32": rec}))
    _run_single_label(M, out)
    got = json.loads((tmp_path / "r.json").read_text())["heat2d_512_f32"]
    assert "mcells_per_s" in got


def test_recorded_timeout_retried_after_builder_bump(M, tmp_path):
    out = str(tmp_path / "r.json")
    (tmp_path / "r.json").write_text(json.dumps({"heat2d_512_f32": {
        "error": "subprocess timeout (2400s)", "timeout": True,
        "builder_rev": M.BUILDER_REV - 1}}))
    _run_single_label(M, out)
    got = json.loads((tmp_path / "r.json").read_text())["heat2d_512_f32"]
    assert "mcells_per_s" in got  # re-measured under the newer builder


def test_suspect_timeout_retried(M, tmp_path):
    """An older record's suspect timeout is retried like any error."""
    out = str(tmp_path / "r.json")
    (tmp_path / "r.json").write_text(json.dumps({"heat2d_512_f32": {
        "error": "subprocess timeout (2400s) ... SUSPECT", "timeout": True,
        "suspect": True, "builder_rev": M.BUILDER_REV}}))
    _run_single_label(M, out)
    got = json.loads((tmp_path / "r.json").read_text())["heat2d_512_f32"]
    assert "mcells_per_s" in got


def test_transient_error_still_retried(M, tmp_path):
    out = str(tmp_path / "r.json")
    (tmp_path / "r.json").write_text(json.dumps({"heat2d_512_f32": {
        "error": "RESOURCE_EXHAUSTED: ...", "builder_rev": M.BUILDER_REV}}))
    _run_single_label(M, out)
    got = json.loads((tmp_path / "r.json").read_text())["heat2d_512_f32"]
    assert "mcells_per_s" in got


def test_untileable_decline_skipped_at_current_rev(M, tmp_path):
    out = str(tmp_path / "r.json")
    rec = {"error": "ValueError: untileable fused k=4 for (512, 512, 512)",
           "builder_rev": M.BUILDER_REV}
    (tmp_path / "r.json").write_text(json.dumps({"heat2d_512_f32": rec}))
    _run_single_label(M, out)
    assert json.loads((tmp_path / "r.json").read_text())[
        "heat2d_512_f32"] == rec


def test_count_runnable_matches_skip_rule(M, tmp_path):
    """--count-runnable and main() must share one skip-rule definition
    (round-4 advisor: the recovery watcher used to re-derive the rule by
    regex-scraping measure.py and could loop forever on drift)."""
    labels = [label for label, *_ in M.CONFIGS]
    out = str(tmp_path / "r.json")
    rev = M.BUILDER_REV
    (tmp_path / "r.json").write_text(json.dumps({
        labels[0]: {"mcells_per_s": 1.0},                      # success
        labels[1]: {"error": "untileable fused k=4",
                    "builder_rev": rev},                       # decline
        labels[2]: {"error": "subprocess timeout (2400s)",
                    "timeout": True, "builder_rev": rev},      # hang
        labels[3]: {"error": "subprocess timeout (2400s)", "timeout": True,
                    "suspect": True, "builder_rev": rev},      # ambiguous
        labels[4]: {"error": "RESOURCE_EXHAUSTED"},            # transient
    }))
    # skipped: success, current-rev decline; runnable: timeouts,
    # transient error, every unrecorded label
    assert M.count_runnable(out) == len(labels) - 2
    assert not M._skip_cached(None)
    assert not M._skip_cached({"error": "untileable",
                               "builder_rev": rev - 1})


def test_count_runnable_cli_prints_count(M, tmp_path, capsys):
    out = str(tmp_path / "r.json")
    (tmp_path / "r.json").write_text(json.dumps(
        {label: {"mcells_per_s": 1.0} for label, *_ in M.CONFIGS}))
    argv = sys.argv
    sys.argv = ["measure.py", "--out", out, "--count-runnable"]
    try:
        M.main()
    finally:
        sys.argv = argv
    assert capsys.readouterr().out.strip() == "0"


def test_merge_record_preserves_other_labels(M, tmp_path):
    out = str(tmp_path / "r.json")
    (tmp_path / "r.json").write_text(json.dumps({"other": {"x": 1}}))
    M._merge_record(out, "new", {"y": 2})
    got = json.loads((tmp_path / "r.json").read_text())
    assert got == {"other": {"x": 1}, "new": {"y": 2}}


def test_campaign_survives_one_wedged_label(M, tmp_path, monkeypatch):
    """The round-13 acceptance pin: a campaign with ONE injected wedged
    label (FAULT_INJECT=label:name=...:hang) completes every other
    label, retries the wedged one (the wedge costs the in-flight
    ATTEMPT, not the label), records the restart in the results record
    AND the ledger row, and a re-run re-executes nothing."""
    import time as _time

    from mpi_cuda_process_tpu.obs import ledger as ledger_lib

    wedged = "heat2d_512_f32"
    other = "sor2d_1024_f32_jnp"
    M.CONFIGS = [c for c in M.CONFIGS if c[0] in (wedged, other)]
    assert len(M.CONFIGS) == 2
    # attempt 0 of the wedged label hangs (killed at the budget);
    # attempt 1 — FAULT_ATTEMPT=1 in the retried child — runs clean
    monkeypatch.setenv("FAULT_INJECT", f"label:name={wedged}:hang")
    monkeypatch.setenv("FAULT_HANG_S", "120")
    # Budget the WEDGED label only (12s kills the hang fast); the clean
    # label keeps the default budget — a global 12s budget sat ~1s above
    # sor2d's honest wall time on a loaded box and flaked the "other
    # label untouched" pin with a spurious restart.
    monkeypatch.setattr(M, "_RISKY", frozenset({wedged}))
    monkeypatch.setattr(M, "_RISKY_BUDGET_S", 12)
    ledger = str(tmp_path / "ledger.jsonl")
    monkeypatch.setenv("OBS_LEDGER_PATH", ledger)

    out = str(tmp_path / "r.json")
    argv = sys.argv
    sys.argv = ["measure.py", "--out", out, "--restart-backoff", "0.1"]
    try:
        M.main()
    finally:
        sys.argv = argv

    results = json.loads((tmp_path / "r.json").read_text())
    assert "mcells_per_s" in results[other], results[other]
    assert "mcells_per_s" in results[wedged], results[wedged]
    assert results[wedged]["restart_attempts"] == 1
    assert "restart_attempts" not in results[other]

    # the ledger row carries the restart trail (attempt count)
    rows = [r for r in ledger_lib.read_rows(ledger)
            if r["label"] == wedged and r["status"] == "ok"]
    assert rows and rows[-1]["detail"]["restart_attempts"] == 1

    # campaign-level resume: a re-run skips every completed label
    # (identical records — nothing was re-measured)
    before = json.loads((tmp_path / "r.json").read_text())
    assert M.count_runnable(out) == 0
    t0 = _time.time()
    sys.argv = ["measure.py", "--out", out]
    try:
        M.main()
    finally:
        sys.argv = argv
    assert json.loads((tmp_path / "r.json").read_text()) == before
    assert _time.time() - t0 < 10, "cached re-run must spawn no children"


def test_explicit_tile_labels_construct(M):
    """The @BZxBY hedge labels must build a real kernel (interpret mode):
    a typo'd tile pair would otherwise surface only on the real chip."""
    from mpi_cuda_process_tpu import make_stencil
    from mpi_cuda_process_tpu.ops.pallas.fused import make_fused_step

    for label, name, grid, steps, dtype, compute in M.CONFIGS:
        if "@" not in compute:
            continue
        k, tiles = M._parse_kspec(
            compute[len("padfree" if compute.startswith("padfree")
                        else "fused"):])
        # tiles must divide a shard-sized proxy of the grid and pass the
        # builder's own validation on the REAL grid shape
        st = make_stencil(name, dtype=dtype) if dtype else make_stencil(name)
        step = make_fused_step(st, grid, k, tiles=tiles,
                               padfree=compute.startswith("padfree"))
        assert step is not None, f"{label}: hedge tiles rejected"
