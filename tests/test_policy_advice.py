"""benchmarks/policy_advice.py: the campaign-results -> policy-flip
advisor.  A wrong recommendation here costs a wrong one-line edit in
plan.py's auto tables at the end-of-round crunch, so each decision branch
is pinned against synthetic results with known winners.  Pure file
reading — no backend, no kernels."""

import importlib.util
import json
import os

import pytest

_BENCH_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks")


@pytest.fixture()
def P():
    spec = importlib.util.spec_from_file_location(
        "policy_advice_under_test",
        os.path.join(_BENCH_DIR, "policy_advice.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _rec(mc, **kw):
    return dict({"mcells_per_s": mc}, **kw)


def _advice(P, tmp_path, results):
    p = tmp_path / "r.json"
    p.write_text(json.dumps(results))
    return {d: (r, e) for d, r, e in P.advise(P.load(str(p)))}


def test_label_parse(P):
    assert P._parse_label("heat3d_512_f32_stream4") == {
        "family": "heat3d", "size": 512, "dtype": "f32",
        "compute": "stream4"}
    assert P._parse_label("advect3d_256_f32_jnp_n150")["compute"] == \
        "jnp_n150"
    assert P._parse_label("heat3d_512_f32_padfree4_t16")["compute"] == \
        "padfree4_t16"
    assert P._parse_label("life_2048_i32_full16")["dtype"] == "i32"
    assert P._parse_label("heat3d_256_f32")["compute"] == "jnp"
    assert P._parse_label("not_a_label") is None


def test_stream_win_flips_fuse_kind(P, tmp_path):
    adv = _advice(P, tmp_path, {
        "heat3d_512_f32_fused4": _rec(107000),
        "heat3d_512_f32_stream4": _rec(155000),
    })
    r, e = adv["_AUTO_FUSE_KIND"]
    assert r == "heat3d: stream"
    assert "155000" in e and "107000" in e


def test_stream_loss_keeps_tiled(P, tmp_path):
    adv = _advice(P, tmp_path, {
        "heat3d_512_f32_fused4": _rec(107000),
        "heat3d_512_f32_stream4": _rec(90000),
    })
    assert adv["_AUTO_FUSE_KIND"][0] == "heat3d: keep tiled"


def test_suspect_measurements_never_count(P, tmp_path):
    adv = _advice(P, tmp_path, {
        "heat3d_512_f32_fused4": _rec(107000),
        "heat3d_512_f32_stream4": _rec(900000, suspect=True),
    })
    # no measured stream survives -> the explicit per-family pending
    # row, never a flip recommendation built on a suspect number
    r, e = adv["_AUTO_FUSE_KIND"]
    assert r == "heat3d: no measured comparison yet"
    assert "stream" in e


def test_family_flip_requires_winning_every_measured_size(P, tmp_path):
    adv = _advice(P, tmp_path, {
        "heat3d_256_f32_fused4": _rec(107000),
        "heat3d_256_f32_stream4": _rec(120000),   # wins at 256
        "heat3d_512_f32_fused4": _rec(107000),
        "heat3d_512_f32_stream4": _rec(90000),    # loses at 512
    })
    r, e = adv["_AUTO_FUSE_KIND"]
    assert r.startswith("heat3d: MIXED")
    assert "256^3" in e and "512^3" in e  # both sizes cited


def test_no_data_rows_name_pending_labels(P, tmp_path):
    adv = _advice(P, tmp_path, {})
    for decision in ("_AUTO_FUSE_K", "_AUTO_FUSE_KIND",
                     "_AUTO_FUSE_K_BF16", "_PADFREE_ABOVE_BYTES",
                     "_AUTO_FULL_K", "advect3d suspect",
                     "copy calibration"):
        r, e = adv[decision]
        assert r == "no measured data yet"
        assert "pending" in e


def test_bf16_blocking_win_names_k_and_kind(P, tmp_path):
    adv = _advice(P, tmp_path, {
        "heat3d_512_bf16": _rec(35700),
        "heat3d_512_bf16_padfree8": _rec(80000),
    })
    r, _ = adv["_AUTO_FUSE_K_BF16"]
    assert r == "heat3d: k=8 via tiled/padfree"
    adv2 = _advice(P, tmp_path, {
        "heat3d_512_bf16": _rec(35700),
        "heat3d_512_bf16_stream4": _rec(80000),
    })
    assert adv2["_AUTO_FUSE_K_BF16"][0] == "heat3d: k=4 via stream"


def test_bf16_mixed_kind_across_sizes_never_names_one_kind(P, tmp_path):
    """Blocking wins at both sizes with the same k, but via padfree8 at
    256^3 and stream8 at 512^3 — the advice must flag the kind as MIXED
    instead of naming the largest-size winner family-wide (the old
    rows[-1]-only derivation)."""
    adv = _advice(P, tmp_path, {
        "heat3d_256_bf16": _rec(35700),
        "heat3d_256_bf16_padfree8": _rec(80000),
        "heat3d_512_bf16": _rec(35700),
        "heat3d_512_bf16_stream8": _rec(80000),
    })
    r, e = adv["_AUTO_FUSE_K_BF16"]
    assert "MIXED" in r and "k=8" in r
    assert "256^3" in e and "512^3" in e


def test_bf16_loss_keeps_jnp(P, tmp_path):
    adv = _advice(P, tmp_path, {
        "heat3d_512_bf16": _rec(35700),
        "heat3d_512_bf16_fused8": _rec(20000),
    })
    assert adv["_AUTO_FUSE_K_BF16"][0] == "heat3d: keep jnp"


def test_padfree_threshold_drop_needs_every_size(P, tmp_path):
    base = {
        "heat3d_256_f32_fused4": _rec(106978),
        "heat3d_256_f32_padfree4": _rec(106000),  # within 3%
        "heat3d_512_f32_fused4": _rec(107300),
    }
    adv = _advice(P, tmp_path, dict(
        base, heat3d_512_f32_padfree4=_rec(120000)))
    assert adv["_PADFREE_ABOVE_BYTES"][0].startswith("drop to 0")
    adv2 = _advice(P, tmp_path, dict(
        base, heat3d_512_f32_padfree4=_rec(80000)))
    assert adv2["_PADFREE_ABOVE_BYTES"][0].startswith("keep")


def test_fullgrid_win_flips_family(P, tmp_path):
    adv = _advice(P, tmp_path, {
        "life_2048_i32": _rec(53831),
        "life_2048_i32_full16": _rec(90000),
    })
    assert adv["_AUTO_FULL_K"][0] == "life: k=16"
    adv2 = _advice(P, tmp_path, {
        "life_2048_i32": _rec(53831),
        "life_2048_i32_full16": _rec(40000),
    })
    assert adv2["_AUTO_FULL_K"][0] == "life: keep jnp"


def test_auto_fuse_k_win_and_keep(P, tmp_path):
    adv = _advice(P, tmp_path, {
        "grayscott3d_256_f32_jnp": _rec(14400),
        "grayscott3d_256_f32_raw": _rec(22700),
        "grayscott3d_256_f32_fused4": _rec(45000),
    })
    r, e = adv["_AUTO_FUSE_K"]
    assert r == "grayscott3d: fused k=4"
    assert "22700" in e  # compared against the best single-step (raw)
    adv2 = _advice(P, tmp_path, {
        "heat3d4th_256_f32_jnp": _rec(62775),
        "heat3d4th_256_f32_fused2": _rec(52300),
    })
    assert adv2["_AUTO_FUSE_K"][0] == "heat3d4th: keep single-step"


def test_load_prefers_record_fields(P, tmp_path):
    # a label the regex cannot parse still lands via the record's own
    # stencil/grid/dtype/compute fields (the campaign always writes them)
    p = tmp_path / "r.json"
    p.write_text(json.dumps({"WEIRD-Label.v2": {
        "mcells_per_s": 155000, "stencil": "heat3d", "grid": [512] * 3,
        "dtype": "float32", "compute": "stream4"},
        "heat3d_512_f32_fused4": _rec(107000)}))
    table = P.load(str(p))
    assert ("heat3d", 512, "f32") in table
    assert "stream4" in table[("heat3d", 512, "f32")]
    adv = {d: r for d, r, _ in P.advise(table)}
    assert adv["_AUTO_FUSE_KIND"] == "heat3d: stream"


def test_advect_suspect_flagged_and_resolved(P, tmp_path):
    # 150 Gcells/s f32 1R+1W implies >1.2 TB/s: flagged
    adv = _advice(P, tmp_path, {"advect3d_256_f32_jnp": _rec(150454)})
    assert adv["advect3d suspect"][0].startswith("STILL")
    # a disagreeing rerun resolves it (the outlier was noise)
    adv2 = _advice(P, tmp_path, {
        "advect3d_256_f32_jnp": _rec(150454),
        "advect3d_256_f32_jnp_n150": _rec(60000),
    })
    assert adv2["advect3d suspect"][0].startswith("resolved")
    # within-roofline reading was never suspect
    adv3 = _advice(P, tmp_path, {"advect3d_256_f32_jnp": _rec(60000)})
    assert adv3["advect3d suspect"][0].startswith("resolved")
    # a rerun that disagrees but is ITSELF above the roofline resolves
    # nothing (120 Gcells/s f32 -> 960 GB/s implied > 819) — and with a
    # fused label in the table, NEITHER jnp entry may keep serving as
    # the single-step baseline: jnp_n150 also matches the baseline
    # prefix in _best, so leaving it produced a 'keep single-step'
    # verdict cited against a physically impossible number (ADVICE.md
    # r5 medium).  The correct outcome is the explicit pending row.
    adv4 = _advice(P, tmp_path, {
        "advect3d_256_f32_jnp": _rec(150454),
        "advect3d_256_f32_jnp_n150": _rec(120000),
        "advect3d_256_f32_fused4": _rec(45000),
    })
    assert adv4["advect3d suspect"][0].startswith("STILL")
    r, e = adv4["_AUTO_FUSE_K"]
    assert r == "advect3d: no measured comparison yet"
    assert "single-step baseline" in e


def test_copy_calibration_reports_rate(P, tmp_path):
    adv = _advice(P, tmp_path, {"copy_512_f32": _rec(80000)})
    r, _ = adv["copy calibration"]
    assert "640 GB/s" in r  # 80e9 cells/s * 8 B
    # an errored 512 row must not suppress the measured 256 fallback
    adv2 = _advice(P, tmp_path, {
        "copy_512_f32": {"error": "subprocess timeout"},
        "copy_256_f32": _rec(80000),
    })
    assert "256^3" in adv2["copy calibration"][1]


def test_runs_on_a_mixed_results_file(P, tmp_path):
    # a campaign-shaped table must parse without raising, whatever its
    # mix of successes/errors/timeouts
    path = tmp_path / "results.json"
    path.write_text(json.dumps({
        "heat3d_256_f32_fused4": _rec(107000.0),
        "heat3d_256_f32_jnp": _rec(86000.0, suspect=True),
        "heat3d_1024_bf16": {"error": "RESOURCE_EXHAUSTED"},
        "copy_512_f32": {"error": "subprocess timeout"},
    }))
    rows = list(P.advise(P.load(str(path))))
    assert isinstance(rows, list)
