"""Numerics sentinel (obs/health.py): the correctness half of obs.

Layers, matching the module's design:

* **reduction** — ``make_health_fn``'s per-field stats + NaN/Inf
  counts and the per-op REGISTERED invariants (heat total heat with
  the wall-scale drift floor, wave's exactly-conserved leapfrog
  energy, SOR's one-sided decreasing residual, Life's track-only
  population);
* **trend detector** — ``HealthMonitor``'s chunk-0 baseline + drift
  rules, the hard NaN trigger, per-member divergence for ensembles;
* **fault site** — ``FAULT_INJECT=numerics:step=N:nan`` poisons one
  cell deterministically (gating, once-only, the driver's
  callback-replacement hook carries the corruption forward);
* **verdict flow** — DIVERGED everywhere WEDGED already flows: the
  CLI aborts, the supervisor gives up WITHOUT a restart (unit fake +
  real-subprocess e2e), the ledger quarantines with reason
  ``diverged`` (so perf_gate reports QUARANTINED and best_known can
  never baseline it), /status.json + obs_top render and exit nonzero,
  the engine handle surfaces the verdict, the root span carries the
  ``health`` attribute;
* **invariance** — the jitted step jaxpr is byte-identical with
  ``--health`` on vs off (the zero-ops acceptance pin).
"""

import importlib.util
import json
import math
import os
import sys

import jax
import jax.numpy as jnp
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from mpi_cuda_process_tpu import cli, driver  # noqa: E402
from mpi_cuda_process_tpu.obs import health as health_lib  # noqa: E402
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
    return _load_script("obs_top_health_t", "scripts/obs_top.py")


def _events(path):
    return [json.loads(line) for line in open(path) if line.strip()]


def _health_events(path):
    return [e for e in _events(path) if e.get("kind") == "health"]


# ---------------------------------------------------------- reduction

def test_health_fn_stats_and_nonfinite_counts():
    st = make_stencil("heat2d")
    fields = init_state(st, (16, 32), seed=0, kind="pulse")
    fn = health_lib.make_health_fn(st)
    vals = jax.device_get(fn(fields))
    assert vals["field0_nonfinite"] == 0
    assert float(vals["field0_max"]) == pytest.approx(100.0)  # frame
    poisoned = (fields[0].at[(8, 16)].set(jnp.nan),)
    vals = jax.device_get(fn(poisoned))
    assert int(vals["field0_nonfinite"]) == 1
    assert math.isnan(float(vals["invariant"]))  # mean over a NaN cell


def test_registered_invariants_per_op():
    """The invariant is registered PER OP in ops/, never in obs."""
    assert make_stencil("heat3d").invariant.name == "total_heat"
    assert make_stencil("heat3d").invariant.scale == 100.0
    assert make_stencil("heat3d27").invariant.name == "total_heat"
    wave = make_stencil("wave3d").invariant
    assert wave.name == "discrete_energy" and wave.mode == "conserve"
    sor = make_stencil("sor3d").invariant
    assert sor.name == "residual_norm" and sor.mode == "decrease"
    life = make_stencil("life").invariant
    assert life.name == "population" and life.rtol is None
    # an invalid mode is rejected at registration time
    from mpi_cuda_process_tpu.ops.stencil import HealthInvariant

    with pytest.raises(ValueError):
        HealthInvariant("x", lambda f: 0.0, mode="sideways")


def test_wave_discrete_energy_is_exactly_conserved():
    """The registered wave invariant is the leapfrog scheme's conserved
    energy: 30 real steps move it by fp roundoff only."""
    st = make_stencil("wave2d")
    fields = init_state(st, (32, 64), seed=1, kind="pulse")
    step = driver.make_step(st, (32, 64))
    e0 = float(st.invariant.fn(fields))
    for _ in range(30):
        fields = step(fields)
    e1 = float(st.invariant.fn(tuple(jax.device_get(fields))))
    assert e0 > 0
    assert abs(e1 - e0) / e0 < 1e-4


def test_drift_modes_and_scale_floor():
    d = health_lib.drift
    assert d(1.0, 1.0, None, "conserve") == 0.0
    assert d(3.0, 1.0, None, "conserve") == pytest.approx(2.0)
    # decrease: shrinking is progress, never drift
    assert d(0.1, 1.0, None, "decrease") == 0.0
    assert d(2.0, 1.0, None, "decrease") == pytest.approx(1.0)
    # the scale floor: Dirichlet heat saturating toward bc=100 from a
    # near-zero baseline reads as drift < 1, a blow-up as huge drift
    assert d(90.0, 1.0, 100.0, "conserve") < 1.0
    assert d(1e6, 1.0, 100.0, "conserve") > 1e3
    assert d(float("nan"), 1.0, None, "conserve") == float("inf")


# ----------------------------------------------------- trend detector

def test_monitor_clean_then_nan_diverges():
    st = make_stencil("heat2d")
    fields = init_state(st, (16, 32), seed=0, kind="pulse")
    mon = health_lib.HealthMonitor(st)
    rec = mon.check(0, fields, chunk=0)
    assert rec["verdict"] == "HEALTHY" and rec["baseline_step"] == 0
    poisoned = (fields[0].at[(8, 16)].set(jnp.inf),)
    with pytest.raises(health_lib.SimulationDiverged) as exc:
        mon.check_or_raise(10, poisoned, chunk=1)
    assert "non-finite" in str(exc.value)
    assert exc.value.record["nonfinite_total"] == 1
    assert mon.verdict == "DIVERGED"


def test_monitor_diverges_on_invariant_drift_without_nan():
    """Finite-but-wrong state: a x10 scale jump blows the conserved
    wave energy far past its 5% tolerance with zero NaNs."""
    st = make_stencil("wave2d")
    fields = init_state(st, (16, 32), seed=0, kind="pulse")
    mon = health_lib.HealthMonitor(st)
    assert mon.check(0, fields)["verdict"] == "HEALTHY"
    scaled = (fields[0] * 10.0, fields[1])
    rec = mon.check(10, scaled)
    assert rec["verdict"] == "DIVERGED"
    assert rec["nonfinite_total"] == 0
    assert "discrete_energy" in rec["reason"]
    assert rec["invariant"]["drift"] > st.invariant.rtol


def test_monitor_track_only_invariant_never_diverges_on_drift():
    st = make_stencil("life")
    fields = init_state(st, (16, 32), seed=0, kind="random")
    mon = health_lib.HealthMonitor(st)
    assert mon.check(0, fields)["verdict"] == "HEALTHY"
    # population collapses to zero: tracked, never a verdict
    rec = mon.check(10, (jnp.zeros_like(fields[0]),))
    assert rec["verdict"] == "HEALTHY"
    assert rec["invariant"]["value"] == 0.0


def test_monitor_stamps_root_span_health_attr():
    class _Spans:
        root_attrs = {}

    st = make_stencil("heat2d")
    fields = init_state(st, (16, 32), seed=0, kind="pulse")
    mon = health_lib.HealthMonitor(st, spans=_Spans())
    mon.check(0, fields)
    assert mon.spans.root_attrs["health"] == "HEALTHY"
    mon.check(1, (fields[0].at[(8, 16)].set(jnp.nan),))
    assert mon.spans.root_attrs["health"] == "DIVERGED"


def test_monitor_ensemble_per_member_stats_and_spread():
    st = make_stencil("heat2d")
    fields = init_state(st, (16, 32), seed=0, kind="pulse", ensemble=3)
    mon = health_lib.HealthMonitor(st, ensemble=3)
    rec = mon.check(0, fields, chunk=0)
    assert rec["verdict"] == "HEALTHY"
    assert len(rec["invariant"]["value"]) == 3
    assert rec["ensemble"]["members"] == 3
    assert rec["ensemble"]["nonfinite_members"] == 0
    # poison ONE member: the run diverges and the record names it
    poisoned = (fields[0].at[(1, 8, 16)].set(jnp.nan),) + fields[1:]
    rec = mon.check(5, poisoned, chunk=1)
    assert rec["verdict"] == "DIVERGED"
    assert rec["ensemble"]["nonfinite_members"] == 1
    assert rec["fields"][0]["nonfinite"] == [0, 1, 0]


# ----------------------------------------------------------- poisoning

def test_apply_nan_poison_center_cell_and_int_raises():
    st = make_stencil("heat2d")
    fields = init_state(st, (16, 32), seed=0, kind="pulse")
    out = health_lib.apply_nan_poison(fields)
    assert bool(jnp.isnan(out[0][8, 16]))
    assert int(jnp.sum(~jnp.isfinite(out[0]))) == 1
    life = init_state(make_stencil("life"), (16, 32), seed=0,
                      kind="random")
    with pytest.raises(ValueError):
        health_lib.apply_nan_poison(life)


def test_fault_spec_numerics_parsing():
    specs = faults.parse_specs("numerics:step=40:nan")
    assert specs[0].site == "numerics" and specs[0].action == "nan"
    assert specs[0].step == 40
    for bad in ("numerics:sigkill", "exchange:nan", "numerics:wedge"):
        with pytest.raises(ValueError):
            faults.parse_specs(bad)


def test_injected_numeric_poison_gating(monkeypatch):
    assert faults.injected_numeric_poison(100) is None
    monkeypatch.setenv("FAULT_INJECT", "numerics:step=40:nan")
    monkeypatch.setenv("FAULT_ATTEMPT", "1")
    assert faults.injected_numeric_poison(100) is None  # wrong attempt
    monkeypatch.setenv("FAULT_ATTEMPT", "0")
    assert faults.injected_numeric_poison(39) is None  # below the gate
    spec = faults.injected_numeric_poison(45)
    assert spec is not None and spec.raw == "numerics:step=40:nan"
    assert faults.injected_numeric_poison(50) is None  # one-shot


# ------------------------------------------------------------ CLI e2e

def test_cli_health_clean_run_emits_healthy_stream(tmp_path):
    path = str(tmp_path / "clean.jsonl")
    cli.run(cli.config_from_args(
        ["--stencil", "heat2d", "--grid", "16,64", "--iters", "8",
         "--log-every", "2", "--health", "--telemetry", path]))
    hs = _health_events(path)
    assert len(hs) == 4
    assert all(h["verdict"] == "HEALTHY" for h in hs)
    assert hs[0]["invariant"]["name"] == "total_heat"
    # a clean run's row is scoreable (health never quarantines HEALTHY)
    rows = ledger_lib.rows_from_log(path)
    assert rows and rows[0]["status"] == "ok"
    assert rows[0].get("health") == "HEALTHY"


def test_cli_health_synthesizes_cadence_without_log_every(tmp_path):
    """--health with no logging cadence must still observe boundaries
    (the synthesized ~8-chunk cadence), not silently check nothing."""
    path = str(tmp_path / "nocad.jsonl")
    cli.run(cli.config_from_args(
        ["--stencil", "heat2d", "--grid", "16,64", "--iters", "16",
         "--health", "--telemetry", path]))
    assert len(_health_events(path)) >= 2


def test_cli_health_diverged_e2e_poison_to_quarantine(tmp_path,
                                                      monkeypatch):
    """The acceptance chain, in-process: numerics poison -> DIVERGED
    health event -> run aborts -> ledger row quarantined 'diverged' ->
    best_known structurally excludes it."""
    monkeypatch.setenv("FAULT_INJECT", "numerics:step=4:nan")
    path = str(tmp_path / "div.jsonl")
    with pytest.raises(health_lib.SimulationDiverged):
        cli.run(cli.config_from_args(
            ["--stencil", "heat2d", "--grid", "16,64", "--iters", "8",
             "--log-every", "2", "--health", "--telemetry", path]))
    hs = _health_events(path)
    assert hs[-1]["verdict"] == "DIVERGED"
    assert hs[-1]["step"] == 4
    assert hs[-1]["nonfinite_total"] == 1
    # the error event landed too (the run recorded how it ended)
    kinds = [e.get("kind") for e in _events(path)]
    assert "error" in kinds and "summary" not in kinds
    # ledger: quarantined with reason 'diverged', never a baseline
    rows = ledger_lib.rows_from_log(path)
    assert len(rows) == 1
    assert rows[0]["status"] == "quarantined"
    assert rows[0]["quarantine"] == "diverged"
    assert rows[0]["health"] == "DIVERGED"
    assert ledger_lib.best_known(rows) == {}


def test_cli_health_diverged_without_poison_events_still_summarized(
        tmp_path, monkeypatch):
    """perf_gate's view: the diverged row is QUARANTINED, not scored."""
    monkeypatch.setenv("FAULT_INJECT", "numerics:step=2:nan")
    path = str(tmp_path / "gate.jsonl")
    with pytest.raises(health_lib.SimulationDiverged):
        cli.run(cli.config_from_args(
            ["--stencil", "heat2d", "--grid", "16,64", "--iters", "8",
             "--log-every", "2", "--health", "--telemetry", path]))
    perf_gate = _load_script("perf_gate_health_t", "scripts/perf_gate.py")
    ledger = str(tmp_path / "ledger.jsonl")
    verdicts, fresh = perf_gate.gate(path, ledger, 0.10)
    assert len(verdicts) == 1
    assert verdicts[0]["verdict"] == "QUARANTINED"
    assert verdicts[0]["quarantine"] == "diverged"


def test_health_jaxpr_invariance_on_vs_off(tmp_path):
    """Acceptance pin: the jitted step jaxpr is byte-identical with
    --health on vs off — the sentinel is a separately-jitted reduction
    at chunk boundaries, never ops in the step."""
    st = make_stencil("heat2d")
    fields = init_state(st, (16, 64), seed=0, kind="pulse")
    step = driver.make_step(st, (16, 64))
    abstract = tuple(jax.ShapeDtypeStruct(f.shape, f.dtype)
                     for f in fields)
    jaxpr_before = str(jax.make_jaxpr(step)(abstract))
    runner_before = str(jax.make_jaxpr(
        driver.make_runner(step, 4, jit=False))(abstract))
    cli.run(cli.config_from_args(
        ["--stencil", "heat2d", "--grid", "16,64", "--iters", "8",
         "--log-every", "2", "--health",
         "--telemetry", str(tmp_path / "jx.jsonl")]))
    assert str(jax.make_jaxpr(step)(abstract)) == jaxpr_before
    assert str(jax.make_jaxpr(
        driver.make_runner(step, 4, jit=False))(abstract)) == \
        runner_before


def test_driver_callback_replacement_carries_state_forward():
    """The numerics fault's transport: a callback returning fields
    replaces the carried state (None keeps it)."""
    st = make_stencil("heat2d")
    fields = init_state(st, (16, 32), seed=0, kind="pulse")
    step = driver.make_step(st, (16, 32))

    def poison_once(done, fs):
        if done == 2:
            return (fs[0].at[(8, 16)].set(jnp.nan),)
        return None

    out = driver.run_simulation(st, fields, 4, step_fn=step,
                                log_every=2, callback=poison_once)
    # the NaN spread from the poisoned cell: the replacement CONTINUED
    assert int(jnp.sum(~jnp.isfinite(out[0]))) > 1


# ------------------------------------------------------- verdict flow

def _health_event(verdict, reason=None, **extra):
    return {"kind": "health", "verdict": verdict, "reason": reason,
            "t": 1.0, "step": 40, **extra}


def test_watch_child_returns_fatal_on_diverged():
    class _Handle:
        def poll(self):
            return None

        def kill(self):
            pass

        def wait(self, timeout_s=30.0):
            return None

    class _Tail:
        def __init__(self):
            self._batches = [[], [_health_event("HEALTHY"),
                                  _health_event("DIVERGED",
                                                reason="nan blow-up")]]

        def poll(self):
            return self._batches.pop(0) if self._batches else []

    outcome, value, detail = sup.watch_child(
        _Handle(), [_Tail()], stall_timeout_s=60.0, poll_s=0.0,
        clock=lambda: 0.0, sleep=lambda s: None)
    assert outcome == "fatal" and value == "DIVERGED"
    assert "nan" in detail


def test_supervise_gives_up_without_restart_on_diverged(tmp_path):
    """The non-restartable contract: one attempt, zero restarts, a
    give_up event carrying the verdict — never a resume into the same
    blow-up."""
    ck = tmp_path / "ck"
    ck.mkdir()
    (ck / "meta.json").write_text(json.dumps(
        {"step": 30, "num_fields": 0, "config": {}}))

    class _Handle:
        def __init__(self):
            self.killed = False

        def poll(self):
            return None

        def kill(self):
            self.killed = True

        def wait(self, timeout_s=30.0):
            return None

    class _Tail:
        def __init__(self):
            self._batches = [[_health_event("DIVERGED", reason="boom")]]

        def poll(self):
            return self._batches.pop(0) if self._batches else []

    class _Session:
        path = "fake.supervisor.jsonl"

        def __init__(self):
            self.events = []

        def event(self, kind, **payload):
            self.events.append({"kind": kind, **payload})

    session = _Session()
    handles = []

    def launcher(attempt, resume):
        h = _Handle()
        handles.append(h)
        return h, [_Tail()]

    res = sup.supervise(launcher, str(ck), max_restarts=2,
                        backoff_base_s=0.0, stall_timeout_s=60.0,
                        poll_s=0.0, session=session,
                        sleep=lambda s: None, clock=lambda: 0.0)
    assert not res.ok and res.gave_up
    assert res.attempts == 1 and res.restarts == []
    assert len(handles) == 1 and handles[0].killed
    kinds = [e["kind"] for e in session.events]
    assert "restart" not in kinds
    gu = [e for e in session.events if e["kind"] == "give_up"][0]
    assert gu["verdict"] == "DIVERGED"
    assert "non-restartable" in gu["reason"]


def test_supervised_diverged_e2e_gives_up_without_restart(tmp_path,
                                                          monkeypatch):
    """Real subprocess e2e: an injected numerics:step=40:nan under
    --supervise --health ends with supervisor give-up (rc 1) after ONE
    attempt — the DIVERGED half of the tier-1 acceptance pin."""
    monkeypatch.setenv("FAULT_INJECT", "numerics:step=40:nan")
    tel = str(tmp_path / "run.jsonl")
    cfg = cli.config_from_args(
        ["--stencil", "heat2d", "--grid", "48,48", "--iters", "100",
         "--seed", "7", "--checkpoint-every", "10",
         "--checkpoint-dir", str(tmp_path / "ck"),
         "--telemetry", tel, "--health",
         "--supervise", "--max-restarts", "2",
         "--restart-backoff", "0.2", "--supervise-stall-s", "120"])
    rc = sup.run_supervised(cfg)
    assert rc == 1
    evs = _events(sup.sibling_path(tel, "supervisor"))
    kinds = [e.get("kind") for e in evs]
    assert "restart" not in kinds
    assert len([e for e in evs if e.get("kind") == "launch"]) == 1
    gu = [e for e in evs if e.get("kind") == "give_up"]
    assert gu and gu[0]["verdict"] == "DIVERGED"
    child = _health_events(sup.sibling_path(tel, "attempt0"))
    assert child[-1]["verdict"] == "DIVERGED"
    assert child[-1]["step"] == 40


def test_status_verdict_and_obs_top_probe(tmp_path, obs_top):
    rm = metrics_lib.RunMetrics()
    rm.ingest(_health_event("HEALTHY"))
    assert rm.status()["verdict"] == "ALIVE"
    assert rm.status()["health"]["verdict"] == "HEALTHY"
    rm.ingest(_health_event(
        "DIVERGED", reason="boom", nonfinite_total=3,
        invariant={"name": "total_heat", "drift": 9.0, "rtol": 2.0},
        worst_field={"field": 0, "drift": 9.0}))
    st = rm.status()
    assert st["verdict"] == "DIVERGED"
    snap = rm.registry.snapshot()
    assert snap["obs_health_diverged"]["value"] == 1.0
    assert snap["obs_health_nonfinite_values"]["value"] == 3
    assert snap["obs_health_invariant_drift"]["value"] == 9.0
    assert obs_top.health_rc(st) == 1
    # the rendered frame names the sentinel state
    body = obs_top.run_frame({**st, "manifest": None}, "/nonexistent")
    assert "DIVERGED" in body and "total_heat" in body


def test_obs_top_once_exits_nonzero_on_diverged_log(tmp_path, obs_top,
                                                    capsys):
    path = str(tmp_path / "div.jsonl")
    with trace_lib.TraceWriter(path) as w:
        w.write_manifest(trace_lib.build_manifest("cli", {}))
        w.event("health", verdict="DIVERGED", reason="boom", step=40,
                nonfinite_total=1)
    assert obs_top.main([path, "--once"]) == 1
    capsys.readouterr()


def test_aggregate_worst_verdict_includes_diverged(tmp_path):
    from mpi_cuda_process_tpu.obs import aggregate

    agg = aggregate.HostAggregator()
    m = trace_lib.build_manifest("cli", {})
    agg.ingest("a.jsonl", m)
    agg.ingest("a.jsonl", _health_event("DIVERGED", reason="boom"))
    st = agg.status()
    assert st["aggregate"]["verdict"] == "DIVERGED"


def test_engine_handle_surfaces_health_verdict(tmp_path, monkeypatch):
    """ROADMAP item-1 contract: a scheduler evicts diverged members
    from handle.status() alone — no log parsing."""
    from mpi_cuda_process_tpu import cli
    from mpi_cuda_process_tpu.engine import SimulationEngine

    monkeypatch.setenv("FAULT_INJECT", "numerics:step=4:nan")
    eng = SimulationEngine(cli.run, telemetry_dir=str(tmp_path))
    h = eng.submit(cli.config_from_args(
        ["--stencil", "heat2d", "--grid", "16,64", "--iters", "8",
         "--log-every", "2", "--health"]))
    with pytest.raises(health_lib.SimulationDiverged):
        h.result(timeout=120)
    st = h.status()
    assert st["verdict"] == "DIVERGED"
    assert st["health"]["verdict"] == "DIVERGED"
    assert st["request"]["phase"] == "failed"
    assert h.health_verdict() == "DIVERGED"
