"""Run doctor (obs/anomaly.py + obs/flightrec.py): the performance half
of obs.

Layers, matching the modules' design:

* **detectors** — :class:`AnomalyMonitor`'s own-baseline throughput
  collapse, post-warmup recompile, memory creep, variance growth,
  roofline-gap band against the ledger's ``best_known``, boundary
  stall, and the zero-findings contract on a clean constant-throughput
  stream;
* **attribution** — per-member own-baseline straggler naming
  (:meth:`observe_members`: heterogeneous-but-stable members never
  flag) and the homogeneous peer-median :func:`attribute_straggler`;
* **verdict flow** — DEGRADED everywhere WEDGED/DIVERGED flow: the
  RunMetrics status verdict (outranking DONE, dominated by everything
  harder), the aggregate worst-verdict lattice
  (DIVERGED > WEDGED > STALLED > DEGRADED), the supervisor's
  ``--degraded-action`` policy, ledger rows flagged ``degraded=N``
  (honest, never quarantined), perf_gate's ``[degraded]``, obs_top's
  panel + nonzero ``--once``;
* **flight recorder** — the session ring mirror, self-validating
  bundle round-trips, verdict replay, and obs_report rendering a
  bundle with no telemetry dir;
* **invariance** — the jitted step jaxpr is byte-identical with
  ``--anomaly`` on vs off (the zero-ops acceptance pin).
"""

import copy
import importlib.util
import json
import os
import sys
import time

import jax
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from mpi_cuda_process_tpu import cli, driver  # noqa: E402
from mpi_cuda_process_tpu import config as config_lib  # noqa: E402
from mpi_cuda_process_tpu.obs import aggregate as aggregate_lib  # noqa: E402
from mpi_cuda_process_tpu.obs import anomaly as anomaly_lib  # noqa: E402
from mpi_cuda_process_tpu.obs import flightrec as flightrec_lib  # noqa: E402
from mpi_cuda_process_tpu.obs import ledger as ledger_lib  # noqa: E402
from mpi_cuda_process_tpu.obs import metrics as metrics_lib  # noqa: E402
from mpi_cuda_process_tpu.obs import trace as trace_lib  # noqa: E402
from mpi_cuda_process_tpu.ops.stencil import make_stencil  # noqa: E402
from mpi_cuda_process_tpu.resilience import faults  # noqa: E402
from mpi_cuda_process_tpu.resilience import supervisor as sup  # noqa: E402
from mpi_cuda_process_tpu.utils.init import init_state  # noqa: E402


@pytest.fixture(autouse=True)
def _fresh_faults():
    faults.reset()
    yield
    faults.reset()


def _load_script(name, rel):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(REPO, rel))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def obs_top():
    return _load_script("obs_top_anomaly_t", "scripts/obs_top.py")


@pytest.fixture(scope="module")
def obs_report():
    return _load_script("obs_report_anomaly_t", "scripts/obs_report.py")


def _events(path):
    return [json.loads(line) for line in open(path) if line.strip()]


def _anomaly_events(path):
    return [e for e in _events(path) if e.get("kind") == "anomaly"]


def _chunk(n, ms, steps=20, recompiled=False, mem=None):
    """A RuntimeRecorder-shaped chunk record (obs/runtime.py)."""
    rec = {"chunk": n, "steps": steps, "ms_per_step": float(ms),
           "wall_s": round(float(ms) * steps / 1e3, 6),
           "recompiled": recompiled}
    if mem is not None:
        rec["memory"] = {"bytes_in_use": int(mem)}
    return rec


def _mon(**kw):
    """Monitor with a frozen clock: the boundary-stall detector reads
    real wall time, which a synthetic-record unit test must not."""
    kw.setdefault("clock", lambda: 0.0)
    return anomaly_lib.AnomalyMonitor(**kw)


# ---------------------------------------------------------- detectors

def test_clean_constant_throughput_zero_findings():
    """THE acceptance contract: a clean steady run produces nothing."""
    mon = _mon(cells=10**6, best_known=100.0)
    for n in range(40):
        mon.observe_chunk(_chunk(n, 10.0, recompiled=(n == 0),
                                 mem=10**9))
    assert mon.count == 0
    assert mon.findings == []


def test_throughput_collapse_flagged_at_the_slow_chunk():
    mon = _mon()
    for n in range(5):
        mon.observe_chunk(_chunk(n, 10.0))
    found = mon.observe_chunk(_chunk(5, 45.0))
    assert [f["anomaly"] for f in found] == ["throughput_collapse"]
    f = found[0]
    assert f["severity"] == "critical"
    assert f["chunk"] == 5
    assert f["evidence"]["ratio"] == pytest.approx(4.5)
    assert f["suspect"]["kind"] == "host"
    # a flagged chunk never poisons the baseline: the NEXT slow chunk
    # still measures against the healthy median
    again = mon.observe_chunk(_chunk(6, 45.0))
    assert [f["anomaly"] for f in again] == ["throughput_collapse"]


def test_collapse_needs_absolute_excess_not_just_ratio():
    """Microsecond chunks tripling is noise, not an anomaly."""
    mon = _mon()
    for n in range(5):
        mon.observe_chunk(_chunk(n, 0.001, steps=2))
    assert mon.observe_chunk(_chunk(5, 0.004, steps=2)) == []


def test_recompile_after_warmup_flagged_chunk0_not():
    mon = _mon()
    mon.observe_chunk(_chunk(0, 10.0, recompiled=True))
    assert mon.count == 0  # chunk 0 compiles are warmup
    found = mon.observe_chunk(_chunk(1, 10.0, recompiled=True))
    assert [f["anomaly"] for f in found] == ["recompile"]


def test_memory_creep_flagged_once_plateau_never():
    mon = _mon()
    base = 10**9
    for n in range(6):
        mon.observe_chunk(_chunk(n, 10.0, mem=base + n * base // 10))
    assert mon.counts.get("memory_creep") == 1  # one-shot
    flat = _mon()
    for n in range(10):
        flat.observe_chunk(_chunk(n, 10.0, mem=base))
    assert flat.count == 0


def test_variance_growth_flagged():
    mon = _mon()
    for n in range(1, 9):
        mon.observe_chunk(_chunk(n, 10.0))
    jitter = [6.0, 22.0] * 4
    for i, ms in enumerate(jitter):
        mon.observe_chunk(_chunk(9 + i, ms))
    assert mon.counts.get("variance_growth") == 1
    assert mon.findings[-1]["evidence"]["cv_recent"] > 0.35


def test_roofline_gap_two_steady_chunks_one_shot():
    # 1e6 cells, 20 steps, wall = ms*steps/1e3 -> tp = 1e3/ms Mcells/s;
    # ms=100 -> 10 Mcells/s, far below 0.25 * best_known=100
    mon = _mon(cells=10**6, best_known={"value": 100.0,
                                        "source": "ledger:r1"})
    for n in range(6):
        mon.observe_chunk(_chunk(n, 100.0))
    assert mon.counts.get("roofline_gap") == 1  # at the 2nd bad chunk
    f = [x for x in mon.findings if x["anomaly"] == "roofline_gap"][0]
    assert f["evidence"]["vs_best_known"] == pytest.approx(0.1)
    assert f["evidence"]["best_known_source"] == "ledger:r1"


def test_roofline_never_fires_without_ledger_or_cells():
    mon = _mon()  # no best_known, no cells
    for n in range(10):
        mon.observe_chunk(_chunk(n, 1000.0))
    assert mon.counts.get("roofline_gap") is None


def test_boundary_stall_detector_sees_untimed_host_gap():
    """The injected-sleep seam: faults fire OUTSIDE the fenced device
    window, so the stall shows up between records, not inside wall_s."""
    t = [0.0]
    mon = anomaly_lib.AnomalyMonitor(clock=lambda: t[0])
    for n in range(4):
        t[0] += 0.21  # chunk wall 0.2s + 10ms honest boundary overhead
        mon.observe_chunk(_chunk(n, 10.0))
    assert mon.count == 0
    t[0] += 0.2 + 0.5  # a 500ms host stall lands before this record
    found = mon.observe_chunk(_chunk(4, 10.0))
    assert [f["anomaly"] for f in found] == ["boundary_stall"]
    assert found[0]["evidence"]["stall_s"] == pytest.approx(0.51, abs=0.02)


def test_max_findings_bounds_the_list_not_the_counts():
    mon = _mon(max_findings=3)
    for n in range(5):
        mon.observe_chunk(_chunk(n, 10.0))
    for n in range(5, 15):
        mon.observe_chunk(_chunk(n, 60.0))
    assert len(mon.findings) == 3
    assert mon.count == 10


# -------------------------------------------------------- attribution

def test_observe_members_heterogeneous_stable_never_flags():
    mon = _mon()
    for _ in range(6):  # g1 is 5x slower than g0 every round: that's
        assert mon.observe_members(  # its physics, not a straggle
            None, [{"name": "g0", "ms_per_step": 10.0},
                   {"name": "g1", "ms_per_step": 50.0}]) is None
    assert mon.count == 0


def test_observe_members_own_baseline_straggler_named_once():
    mon = _mon()
    for step in range(4):
        mon.observe_members(step, [{"name": "g0", "ms_per_step": 10.0},
                                   {"name": "g1", "ms_per_step": 50.0}])
    f = mon.observe_members(9, [{"name": "g0", "ms_per_step": 32.0},
                                {"name": "g1", "ms_per_step": 50.0}])
    assert f is not None
    assert f["suspect"] == {"kind": "group", "name": "g0",
                            "lag_ratio": pytest.approx(3.2)}
    assert f["step"] == 9
    # once per name per run
    assert mon.observe_members(10, [{"name": "g0", "ms_per_step": 40.0},
                                    {"name": "g1", "ms_per_step": 50.0}]) \
        is None


def test_attribute_straggler_peer_median():
    entries = [{"name": "hostA", "slowness": 10.0},
               {"name": "hostB", "slowness": 10.0},
               {"name": "hostC", "slowness": 25.0}]
    s = anomaly_lib.attribute_straggler(entries)
    assert s == {"kind": "host", "name": "hostC", "lag_ratio": 2.5}
    assert anomaly_lib.attribute_straggler(entries[:1]) is None
    assert anomaly_lib.attribute_straggler(
        [{"name": "a", "slowness": 10.0},
         {"name": "b", "slowness": 11.0}]) is None


def test_findings_land_as_schema_valid_trace_events(tmp_path):
    path = str(tmp_path / "t.jsonl")
    with trace_lib.TraceWriter(path) as w:
        w.write_manifest(trace_lib.build_manifest("cli", {}))
        mon = _mon(trace=w)
        for n in range(5):
            mon.observe_chunk(_chunk(n, 10.0))
        mon.observe_chunk(_chunk(5, 60.0))
    _, events = trace_lib.validate_log(path)  # schema gate
    anomalies = [e for e in events if e.get("kind") == "anomaly"]
    assert len(anomalies) == 1
    assert anomalies[0]["anomaly"] == "throughput_collapse"
    assert anomalies[0]["suspect"]["kind"] == "host"


# ------------------------------------------------------- verdict flow

def _manifest_for(host):
    m = copy.deepcopy(trace_lib.build_manifest("cli", {}))
    m["provenance"]["hostname"] = host
    return m


_ANOMALY_EV = {"kind": "anomaly", "anomaly": "throughput_collapse",
               "severity": "critical", "chunk": 3, "t": time.time(),
               "suspect": {"kind": "host", "name": "h|p0"},
               "evidence": {"ratio": 4.0}}


def test_metrics_degraded_verdict_and_payload():
    rm = metrics_lib.RunMetrics()
    rm.ingest(trace_lib.build_manifest("cli", {}))
    rm.ingest(dict(_ANOMALY_EV))
    st = rm.status()
    assert st["verdict"] == "DEGRADED"
    assert st["anomalies"]["count"] == 1
    assert st["anomalies"]["kinds"] == {"throughput_collapse": 1}
    assert st["anomalies"]["suspect"]["name"] == "h|p0"


def test_metrics_degraded_outranks_done_but_nothing_harder():
    rm = metrics_lib.RunMetrics()
    rm.ingest(trace_lib.build_manifest("cli", {}))
    rm.ingest(dict(_ANOMALY_EV))
    rm.ingest({"kind": "summary", "t": time.time(), "steps": 8,
               "mcells_per_s": 1.0})
    assert rm.status()["verdict"] == "DEGRADED"  # finished slow, not DONE
    rm.ingest({"kind": "heartbeat", "t": time.time(),
               "verdict": "WEDGED", "detail": "no progress"})
    assert rm.status()["verdict"] == "WEDGED"


_VERDICT_EVENTS = {
    "DIVERGED": {"kind": "health", "verdict": "DIVERGED",
                 "reason": "boom", "step": 4, "t": time.time()},
    "WEDGED": {"kind": "heartbeat", "verdict": "WEDGED",
               "detail": "stuck", "t": time.time()},
    "STALLED": {"kind": "heartbeat", "verdict": "STALLED",
                "detail": "slow", "t": time.time()},
    "DEGRADED": dict(_ANOMALY_EV),
    "DONE": {"kind": "summary", "t": time.time(), "steps": 8,
             "mcells_per_s": 1.0},
}


@pytest.mark.parametrize("winner,loser", [
    ("DIVERGED", "WEDGED"), ("DIVERGED", "DEGRADED"),
    ("WEDGED", "STALLED"), ("WEDGED", "DEGRADED"),
    ("STALLED", "DEGRADED"), ("DEGRADED", "DONE")])
def test_aggregate_worst_verdict_pairwise_dominance(winner, loser):
    agg = aggregate_lib.HostAggregator()
    for i, verdict in enumerate((winner, loser)):
        src = f"{verdict.lower()}.jsonl"
        agg.ingest(src, _manifest_for(f"h{i}"))
        agg.ingest(src, dict(_VERDICT_EVENTS[verdict]))
    assert agg.status()["aggregate"]["verdict"] == winner


def test_aggregate_counts_anomalies_and_names_fleet_straggler():
    agg = aggregate_lib.HostAggregator()
    for i, ms in enumerate([10.0, 10.0, 30.0]):
        src = f"h{i}.jsonl"
        agg.ingest(src, _manifest_for(f"h{i}"))
        agg.ingest(src, {"kind": "chunk", "chunk": 1, "steps": 4,
                         "ms_per_step": ms, "wall_s": ms * 4 / 1e3,
                         "recompiled": False, "t": time.time()})
    agg.ingest("h2.jsonl", dict(_VERDICT_EVENTS["DEGRADED"]))
    st = agg.status()
    assert st["aggregate"]["anomalies"] == 1
    assert st["aggregate"]["straggler"]["kind"] == "host"
    assert st["aggregate"]["straggler"]["name"].startswith("h2")
    assert st["aggregate"]["straggler"]["lag_ratio"] == 3.0


@pytest.mark.parametrize("action,expected", [
    ("warn", None),
    ("restart", ("verdict", "DEGRADED")),
    ("abort", ("fatal", "DEGRADED"))])
def test_supervisor_degraded_action_policy(action, expected):
    hit = sup._classify_event(dict(_ANOMALY_EV), sup.KILL_VERDICTS,
                              sup.FATAL_VERDICTS,
                              degraded_action=action)
    if expected is None:
        assert hit is None
    else:
        assert (hit[0], hit[1]) == expected
        assert "throughput_collapse" in hit[2]
        assert "h|p0" in hit[2]


def test_supervisor_classify_event_default_stays_compatible():
    """Old 3-positional-arg callers (and old behavior) still work."""
    e = {"kind": "heartbeat", "verdict": "WEDGED", "detail": "x"}
    assert sup._classify_event(e, sup.KILL_VERDICTS,
                               sup.FATAL_VERDICTS)[1] == "WEDGED"
    assert sup._classify_event(dict(_ANOMALY_EV), sup.KILL_VERDICTS,
                               sup.FATAL_VERDICTS) is None


def test_config_anomaly_flows_to_children_degraded_action_does_not():
    cfg = cli.config_from_args(
        ["--stencil", "heat2d", "--grid", "16,64", "--iters", "8",
         "--anomaly", "--degraded-action", "abort"])
    assert cfg.anomaly is True
    assert cfg.degraded_action == "abort"
    argv = config_lib.to_argv(cfg)
    # the child must run the doctor (its anomaly events are what the
    # parent's policy watches); the POLICY itself is parent-side only
    assert "--anomaly" in argv
    assert "--degraded-action" not in argv
    assert {"anomaly", "degraded_action"} <= config_lib.LIFECYCLE_FIELDS


# ----------------------------------------------------- faults (sleep)

def test_fault_sleep_grammar():
    spec, = faults.parse_specs("exchange:step=40:sleep:500")
    assert (spec.site, spec.action, spec.step, spec.sleep_ms) == \
        ("exchange", "sleep", 40, 500)
    for bad in ("exchange:sleep",          # no duration
                "exchange:sleep:0",        # not positive
                "heartbeat:sleep:100",     # not a sleep site
                "numerics:sleep:100"):
        with pytest.raises(ValueError):
            faults.parse_specs(bad)


def test_fault_sleep_fires_once_and_returns(monkeypatch):
    monkeypatch.setenv("FAULT_INJECT", "exchange:step=4:sleep:30")
    t0 = time.perf_counter()
    faults.maybe_fire("exchange", step=2)      # below the gate
    assert time.perf_counter() - t0 < 0.02
    t0 = time.perf_counter()
    faults.maybe_fire("exchange", step=4)      # fires, sleeps, RETURNS
    assert time.perf_counter() - t0 >= 0.03
    t0 = time.perf_counter()
    faults.maybe_fire("exchange", step=6)      # one-shot
    assert time.perf_counter() - t0 < 0.02


# --------------------------------------------------- flight recorder

def test_flight_ring_mirrors_every_session_record(tmp_path):
    from mpi_cuda_process_tpu import obs as obs_lib

    path = str(tmp_path / "ring.jsonl")
    with obs_lib.open_session(path, "cli", {"stencil": "heat2d"}) as s:
        assert s.flight is not None
        s.event("chunk", chunk=0, steps=2, ms_per_step=1.0,
                wall_s=0.002, recompiled=False)
        s.event("anomaly", **{k: v for k, v in _ANOMALY_EV.items()
                              if k not in ("kind", "t")})
    assert s.flight.manifest["tool"] == "cli"
    kinds = [r.get("kind") for r in s.flight.ring]
    assert "chunk" in kinds and "anomaly" in kinds
    assert s.flight.events_seen == len(s.flight.ring)


def test_bundle_roundtrip_and_verdict_replay(tmp_path, monkeypatch):
    monkeypatch.delenv("OBS_BUNDLE_DIR", raising=False)
    path = str(tmp_path / "run.jsonl")
    with trace_lib.TraceWriter(path) as w:
        w.write_manifest(trace_lib.build_manifest("cli", {}))
        w.event("chunk", chunk=0, steps=2, ms_per_step=1.0,
                wall_s=0.002, recompiled=False)
        w.event("anomaly", **{k: v for k, v in _ANOMALY_EV.items()
                              if k not in ("kind", "t")})
    out = flightrec_lib.bundle_from_log(path, reason="unit")
    assert out == str(tmp_path / "run.bundle.json")
    assert flightrec_lib.is_bundle_file(out)
    assert not flightrec_lib.is_bundle_file(path)
    b = flightrec_lib.read_bundle(out)  # read implies validate
    assert b["reason"] == "unit"
    # verdict=None replays the events through RunMetrics: the anomaly
    # event makes the post-mortem verdict DEGRADED — one definition
    assert b["verdict"] == "DEGRADED"
    assert b["anomalies"][0]["anomaly"] == "throughput_collapse"
    assert set(b) >= {"manifest", "events", "env"}
    assert b["events_seen"] == 2


def test_bundle_validate_lists_problems():
    with pytest.raises(ValueError, match="schema"):
        flightrec_lib.validate_bundle({"kind": "flight_bundle"})
    with pytest.raises(ValueError, match="reason"):
        flightrec_lib.validate_bundle({
            "schema": flightrec_lib.BUNDLE_SCHEMA,
            "kind": "flight_bundle", "created_at": time.time(),
            "reason": "", "events": [], "events_seen": 0,
            "open_spans": [], "anomalies": [], "env": {}})


def test_bundle_from_session_swallows_fake_sessions():
    class _Fake:
        pass
    assert flightrec_lib.bundle_from_session(_Fake(), "x") is None


def test_obs_bundle_script_and_report_render_without_telemetry_dir(
        tmp_path, obs_report, capsys):
    """The acceptance pin: a fresh session reads the post-mortem from
    the bundle alone, original telemetry dir deleted."""
    import shutil

    tel = tmp_path / "tel"
    tel.mkdir()
    path = str(tel / "run.jsonl")
    with trace_lib.TraceWriter(path) as w:
        w.write_manifest(trace_lib.build_manifest("cli", {}))
        w.event("anomaly", **{k: v for k, v in _ANOMALY_EV.items()
                              if k not in ("kind", "t")})
        w.event("error", error="RuntimeError: boom")
    obs_bundle = _load_script("obs_bundle_t", "scripts/obs_bundle.py")
    out = str(tmp_path / "post.bundle.json")
    assert obs_bundle.main([path, "-o", out]) == 0
    shutil.rmtree(tel)  # the log is GONE; the bundle must suffice
    assert obs_report.main([out, "--check"]) == 0
    printed = capsys.readouterr().out
    assert "flight bundle" in printed
    assert "DEGRADED" in printed
    assert "throughput_collapse" in printed
    assert "RuntimeError: boom" in printed
    assert "obs_report --check: ok (flight bundle" in printed


def test_obs_report_check_rejects_tampered_bundle(tmp_path, obs_report,
                                                 capsys):
    path = str(tmp_path / "run.jsonl")
    with trace_lib.TraceWriter(path) as w:
        w.write_manifest(trace_lib.build_manifest("cli", {}))
        w.event("chunk", chunk=0, steps=2, ms_per_step=1.0,
                wall_s=0.002, recompiled=False)
    out = flightrec_lib.bundle_from_log(path, reason="unit")
    b = json.load(open(out))
    b["events"] = [{"kind": "chunk"}]  # schema-invalid event
    json.dump(b, open(out, "w"))
    assert obs_report.main([out, "--check"]) == 1
    capsys.readouterr()


# ------------------------------------------------------------ CLI e2e

_CLEAN_ARGS = ["--stencil", "heat2d", "--grid", "16,64", "--iters", "16",
               "--log-every", "2", "--anomaly"]


def test_cli_clean_run_zero_findings_no_bundle(tmp_path):
    path = str(tmp_path / "clean.jsonl")
    cli.run(cli.config_from_args(_CLEAN_ARGS + ["--telemetry", path]))
    assert _anomaly_events(path) == []
    assert not os.path.exists(str(tmp_path / "clean.bundle.json"))
    rows = ledger_lib.rows_from_log(path)
    assert rows and rows[0]["status"] == "ok"
    assert "degraded" not in (rows[0].get("detail") or {})


@pytest.mark.parametrize("op,grid", [
    ("heat3d", "8,8,128"), ("heat3d27", "8,8,128"),
    ("heat3d4th", "12,8,128"), ("wave2d", "16,64"),
    ("wave3d", "8,8,128"), ("advect2d", "16,64"),
    ("advect3d", "8,8,128"), ("grayscott2d", "16,64"),
    ("grayscott3d", "8,8,128"), ("sor2d", "16,64"),
    ("sor3d", "8,8,128"), ("life", "16,64"), ("mdf", "16,64")])
def test_cli_clean_run_every_op_zero_findings(op, grid, tmp_path):
    """The acceptance contract is per-op: no op's natural chunk-time
    profile (first-boundary setup, per-op compile shape) may read as
    an anomaly.  heat2d is pinned by the test above."""
    path = str(tmp_path / f"{op}.jsonl")
    cli.run(cli.config_from_args(
        ["--stencil", op, "--grid", grid, "--iters", "16",
         "--log-every", "2", "--anomaly", "--telemetry", path]))
    assert _anomaly_events(path) == []


def test_cli_injected_slowdown_flagged_with_bundle_and_ledger_flag(
        tmp_path, monkeypatch):
    """The acceptance chain, in-process: injected sleep -> anomaly
    event within 2 boundaries -> DEGRADED bundle on exit -> ledger row
    flagged degraded=N (NOT quarantined) -> perf_gate [degraded]."""
    monkeypatch.setenv("FAULT_INJECT", "exchange:step=8:sleep:500")
    path = str(tmp_path / "slow.jsonl")
    cli.run(cli.config_from_args(_CLEAN_ARGS + ["--telemetry", path]))
    anomalies = _anomaly_events(path)
    assert anomalies, "the 500ms injected stall must be flagged"
    flagged_steps = [e.get("step") for e in anomalies
                     if e.get("step") is not None]
    assert flagged_steps and min(flagged_steps) <= 12  # within 2 chunks
    assert all(e["suspect"]["name"] for e in anomalies)
    # the run FINISHED (a slow run is not a dead run) with a summary...
    assert any(e.get("kind") == "summary" for e in _events(path))
    # ...and left the post-mortem bundle even though nothing aborted
    bundle_path = str(tmp_path / "slow.bundle.json")
    assert os.path.exists(bundle_path)
    b = flightrec_lib.read_bundle(bundle_path)
    assert b["verdict"] == "DEGRADED"
    assert b["reason"] == "degraded"
    assert b["anomalies"]
    # ledger: honest but flagged, still scoreable, still a baseline
    rows = ledger_lib.rows_from_log(path)
    main_rows = [r for r in rows if r.get("value")]
    assert main_rows[0]["status"] == "ok"
    assert main_rows[0]["detail"]["degraded"] == len(anomalies)
    assert ledger_lib.best_known(main_rows)
    perf_gate = _load_script("perf_gate_anomaly_t", "scripts/perf_gate.py")
    ledger = str(tmp_path / "ledger.jsonl")
    verdicts, _ = perf_gate.gate(path, ledger, 0.10)
    assert any(v.get("degraded") for v in verdicts)
    assert "[degraded]" in perf_gate._table(verdicts)


def test_anomaly_jaxpr_invariance_on_vs_off(tmp_path):
    """Acceptance pin: the jitted step jaxpr is byte-identical with
    --anomaly on vs off — the doctor is host Python at chunk
    boundaries, never ops in the step."""
    st = make_stencil("heat2d")
    fields = init_state(st, (16, 64), seed=0, kind="pulse")
    step = driver.make_step(st, (16, 64))
    abstract = tuple(jax.ShapeDtypeStruct(f.shape, f.dtype)
                     for f in fields)
    jaxpr_before = str(jax.make_jaxpr(step)(abstract))
    runner_before = str(jax.make_jaxpr(
        driver.make_runner(step, 4, jit=False))(abstract))
    cli.run(cli.config_from_args(
        _CLEAN_ARGS + ["--telemetry", str(tmp_path / "jx.jsonl")]))
    assert str(jax.make_jaxpr(step)(abstract)) == jaxpr_before
    assert str(jax.make_jaxpr(
        driver.make_runner(step, 4, jit=False))(abstract)) == \
        runner_before


def test_engine_handle_surfaces_anomalies(tmp_path):
    from mpi_cuda_process_tpu import cli
    from mpi_cuda_process_tpu.engine import SimulationEngine

    eng = SimulationEngine(cli.run, telemetry_dir=str(tmp_path))
    h = eng.submit(cli.config_from_args(_CLEAN_ARGS))
    h.result(timeout=120)
    assert h.anomalies() == []  # clean run: the doctor stays silent


# ------------------------------------------------------------ obs_top

def test_obs_top_health_rc_degraded_nonzero(obs_top):
    assert obs_top.health_rc({"verdict": "DEGRADED"}) == 1
    assert obs_top.health_rc({"verdict": "DONE"}) == 0


def test_obs_top_anomaly_panel(obs_top):
    lines = obs_top._anomaly_lines({"anomalies": {
        "count": 3, "kinds": {"straggler": 1, "recompile": 2},
        "last": {"anomaly": "straggler", "severity": "warn",
                 "suspect": {"kind": "group", "name": "g1:wave3d",
                             "lag_ratio": 2.4}},
        "suspect": {"kind": "group", "name": "g1:wave3d",
                    "lag_ratio": 2.4}}})
    body = "\n".join(lines)
    assert "3 anomaly finding(s)" in body
    assert "recompile=2" in body
    assert "suspect=group:g1:wave3d (x2.4)" in body
    assert obs_top._anomaly_lines({}) == []  # clean run: no panel


def test_obs_top_ledger_frame_flags_stale_baselines(tmp_path, obs_top):
    now = time.time()
    rows = [ledger_lib.make_row("old|cpu:x", 5.0, source="r1",
                                measured_at=now - 40 * 86400,
                                expected_backend="cpu"),
            ledger_lib.make_row("mid|cpu:x", 6.0, source="r2",
                                measured_at=now - 86400,
                                expected_backend="cpu"),
            ledger_lib.make_row("new|cpu:x", 7.0, source="r3",
                                measured_at=now,
                                expected_backend="cpu")]
    path = str(tmp_path / "ledger.jsonl")
    ledger_lib.append_rows(rows, path)
    body = obs_top.ledger_frame(path)
    assert "age_d" in body and "stale?" in body
    stale_lines = [ln for ln in body.splitlines() if "stale?" in ln]
    assert len(stale_lines) == 1  # only the 40-day row: latest 2
    assert "old|cpu" in stale_lines[0]  # measurement days stay fresh


# --------------------------------------------------------- obs_report

def test_obs_report_renders_anomaly_block_from_log(tmp_path, obs_report):
    path = str(tmp_path / "r.jsonl")
    with trace_lib.TraceWriter(path) as w:
        w.write_manifest(trace_lib.build_manifest("cli", {}))
        w.event("anomaly", **{k: v for k, v in _ANOMALY_EV.items()
                              if k not in ("kind", "t")})
    body = obs_report.render(path)
    assert "run-doctor findings (1)" in body
    assert "throughput_collapse" in body
    assert "host:h|p0" in body


# ------------------------------------------------------ trace export

def test_trace_export_group_tracks_and_anomaly_instants(tmp_path):
    exp = _load_script("obs_trace_export_anomaly_t",
                       "scripts/obs_trace_export.py")
    path = str(tmp_path / "g.jsonl")
    with trace_lib.TraceWriter(path) as w:
        w.write_manifest(trace_lib.build_manifest("cli", {}))
        w.event("policy_group", group="g0:heat2d", clause="heat2d",
                modes=["exchange=collective"], locked=False,
                provenance="measured")
        for grp in ("g0:heat2d", "g1:wave3d"):
            w.event("group_chunk", step=4, group=grp, op=grp.split(":")[1],
                    steps=4, wall_s=0.02, ready_ms_per_step=3.1,
                    mcells_per_s=12.5)
        w.event("health", group="g1:wave3d", verdict="HEALTHY",
                reason=None, step=4)
        w.event("migrate", step=8, n=2, label="x", provenance="measured")
        w.event("anomaly", **{k: v for k, v in _ANOMALY_EV.items()
                              if k not in ("kind", "t")})
    obj = exp.build_trace([path])
    assert exp.validate_export(obj) == []
    evs = obj["traceEvents"]
    names = [e["name"] for e in evs]
    # one synthetic track per group, named thread:group
    gtracks = [e for e in evs if e["ph"] == "M"
               and e["name"] == "thread_name"
               and ":" in (e["args"].get("name") or "")]
    assert {e["args"]["name"].split(":", 1)[1] for e in gtracks} == \
        {"g0:heat2d", "g1:wave3d"}  # track name = "<thread>:<group>"
    gslices = [e for e in evs if e.get("cat") == "group_chunk"]
    assert len(gslices) == 2
    assert {e["args"]["group"] for e in gslices} == \
        {"g0:heat2d", "g1:wave3d"}
    assert all(e["args"]["ready_ms_per_step"] == 3.1 for e in gslices)
    assert len({e["tid"] for e in gslices}) == 2  # distinct tracks
    assert "policy_group g0:heat2d" in names
    assert "health g1:wave3d HEALTHY" in names
    assert "migrate@8" in names
    anom = [e for e in evs if e.get("cat") == "anomaly"]
    assert anom[0]["name"] == "anomaly throughput_collapse"
    assert anom[0]["args"]["suspect"] == "host:h|p0"
