"""Continuous-batching scheduler tests (serving/).

What the serving layer promises, pinned:

* **size-class identity** — jobs differing only in per-job fields
  (seed/density/init/iters) share a class; any class field splits it.
* **admission** — over-budget classes are refused BEFORE any build,
  with the pricing arithmetic attached; unsupported lifecycle modes
  are refused with the offending field named.
* **near-zero cold-compile** — the second job of an already-resident
  size class triggers ZERO backend compiles, asserted through the
  jax.monitoring compile listener (``obs/runtime.compile_events_seen``)
  (the persistent cache's placement is tests/test_compile_cache.py's).
* **isolation + bit-exactness** — a slot's result is bit-identical to
  the job's solo ``cli.run``, including across a checkpoint preemption
  round-trip, and a co-tenant's NaN divergence (injected via the
  ``numerics`` fault site) evicts only the poisoned slot.
* **third terminal outcome** — cancel ends a run with a ``cancelled``
  event / phase / quarantine reason, never an error row, and the
  supervisor treats it as fatal-no-restart.
* **fairness** — weighted FIFO with a starvation bound: a low-priority
  job completes while higher-priority work keeps arriving.
"""

import json
import os
import sys
import time

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from mpi_cuda_process_tpu import cli  # noqa: E402
from mpi_cuda_process_tpu.cancellation import RunCancelled  # noqa: E402
from mpi_cuda_process_tpu.config import RunConfig  # noqa: E402
from mpi_cuda_process_tpu.engine import SimulationEngine  # noqa: E402
from mpi_cuda_process_tpu.obs import ledger as ledger_lib  # noqa: E402
from mpi_cuda_process_tpu.obs import metrics as metrics_lib  # noqa: E402
from mpi_cuda_process_tpu.obs import runtime as runtime_lib  # noqa: E402
from mpi_cuda_process_tpu.obs.health import SimulationDiverged  # noqa: E402
from mpi_cuda_process_tpu.resilience import faults  # noqa: E402
from mpi_cuda_process_tpu.resilience import supervisor as sup  # noqa: E402
from mpi_cuda_process_tpu import serving  # noqa: E402
from mpi_cuda_process_tpu.serving import (  # noqa: E402
    AdmissionController, AdmissionError, class_config, class_signature)


@pytest.fixture(autouse=True)
def _fresh_faults():
    faults.reset()
    yield
    faults.reset()


def _events(path):
    out = []
    with open(path) as fh:
        for line in fh:
            line = line.strip()
            if line:
                out.append(json.loads(line))
    return out


def _solo(cfg):
    fields, _ = cli.run(cfg)
    return tuple(np.asarray(f) for f in fields)


def _assert_bit_exact(got, cfg):
    want = _solo(cfg)
    assert len(got) == len(want)
    for a, b in zip(got, want):
        assert np.array_equal(np.asarray(a), b), \
            "slot result differs from the job's solo run"


# ------------------------------------------------------- size classes

def test_class_signature_per_job_fields_do_not_split():
    a = RunConfig(stencil="heat2d", grid=(16, 16), iters=8, seed=0)
    b = RunConfig(stencil="heat2d", grid=(16, 16), iters=640, seed=9,
                  density=0.5, init="pulse", telemetry="/tmp/x.jsonl")
    assert class_signature(a) == class_signature(b)
    for variant in (dict(grid=(16, 32)), dict(stencil="life"),
                    dict(dtype="bfloat16"), dict(periodic=True),
                    dict(fuse=2)):
        c = RunConfig(**{**dict(stencil="heat2d", grid=(16, 16)),
                         **variant})
        assert class_signature(c) != class_signature(a), variant


def test_class_config_resets_per_job_and_opens_member_axis():
    j = RunConfig(stencil="heat2d", grid=(16, 16), iters=640, seed=9,
                  density=0.5, supervise=True, telemetry="/tmp/x.jsonl")
    bc = class_config(j, 4)
    assert bc.ensemble == 4
    assert bc.grid == (16, 16) and bc.stencil == "heat2d"
    d = RunConfig()
    assert (bc.seed, bc.density, bc.iters) == (d.seed, d.density, d.iters)
    assert not bc.supervise and bc.telemetry is None


# --------------------------------------------------------- admission

def test_admission_over_budget_rejects_with_arithmetic():
    ctl = AdmissionController(hbm_bytes=10_000)
    cfg = class_config(RunConfig(stencil="heat2d", grid=(256, 256)), 8)
    with pytest.raises(AdmissionError) as ei:
        ctl.admit_or_raise(cfg)
    e = ei.value
    assert e.reason == "over_budget"
    assert e.detail["total_bytes"] > e.detail["hbm_bytes"] == 10_000
    assert "parts" in e.detail and "GiB" in str(e)


def test_engine_rejects_over_budget_with_event(tmp_path):
    eng = serving.ServingEngine(telemetry_dir=str(tmp_path),
                                hbm_bytes=10_000)
    with pytest.raises(AdmissionError) as ei:
        eng.submit(RunConfig(stencil="heat2d", grid=(256, 256), iters=8),
                   tenant="greedy")
    assert ei.value.reason == "over_budget"
    stats = eng.close()
    assert stats["rejects"] == 1 and stats["jobs_submitted"] == 0
    rejects = [e for e in _events(eng.telemetry_path)
               if e.get("kind") == "scheduler" and e.get("op") == "reject"]
    assert len(rejects) == 1
    assert rejects[0]["reason"] == "over_budget"
    assert rejects[0]["tenant"] == "greedy"


def test_engine_rejects_unsupported_fields(tmp_path):
    eng = serving.ServingEngine(telemetry_dir=str(tmp_path))
    for bad in (dict(supervise=True), dict(tol=1e-6), dict(ensemble=4),
                dict(resume=True), dict(profile="/tmp/p"),
                dict(iters=0), dict(fuse=2, iters=9)):
        with pytest.raises(AdmissionError) as ei:
            eng.submit(RunConfig(stencil="heat2d", grid=(16, 16),
                                 iters=bad.pop("iters", 8), **bad))
        assert ei.value.reason == "unsupported"
    stats = eng.close()
    assert stats["rejects"] == 7


# ------------------------------------------ residency / zero compiles

def test_second_job_of_resident_class_compiles_nothing(tmp_path):
    """THE perf pin: a size class compiles when first built; the next
    job of the class rides the resident step — zero backend compiles,
    counted by the jax.monitoring listener the recorder registers."""
    eng = serving.ServingEngine(telemetry_dir=str(tmp_path),
                                ladder=(2,), cadence=8)
    base = dict(stencil="heat2d", grid=(16, 48), iters=8)
    before_a = runtime_lib.compile_events_seen()
    ha = eng.submit(RunConfig(seed=1, **base), tenant="a")
    ha.result(timeout=300)
    after_a = runtime_lib.compile_events_seen()
    assert after_a > before_a, \
        "the first build of a class must register backend compiles " \
        "(the listener is live — this assertion gives the zero below teeth)"
    hb = eng.submit(RunConfig(seed=2, density=0.4, **base), tenant="b")
    hb.result(timeout=300)
    assert runtime_lib.compile_events_seen() == after_a, \
        "second job of a resident size class must compile NOTHING"
    stats = eng.close()
    assert stats["jobs_done"] == 2
    assert len(stats["class_table"]) == 1


# ------------------------------------------------- results / isolation

def test_results_bit_exact_vs_solo_and_batched_together(tmp_path):
    eng = serving.ServingEngine(telemetry_dir=str(tmp_path),
                                ladder=(4,), cadence=8)
    base = dict(stencil="heat2d", grid=(16, 16), iters=16)
    cfgs = [RunConfig(seed=s, **base) for s in (3, 5, 8)]
    handles = [eng.submit(c, tenant=f"t{i}") for i, c in enumerate(cfgs)]
    results = [h.result(timeout=300)[0] for h in handles]
    stats = eng.close()
    assert stats["jobs_done"] == 3
    for got, cfg in zip(results, cfgs):
        _assert_bit_exact(got, cfg)


def test_diverged_slot_evicted_others_unharmed(tmp_path, monkeypatch):
    """PR 12's verdict as the eviction signal: poison one member slot
    (numerics fault site) — that job ends DIVERGED with a real health
    record; its co-tenant finishes bit-exact."""
    monkeypatch.setenv("FAULT_INJECT", "numerics:step=4:nan")
    eng = serving.ServingEngine(telemetry_dir=str(tmp_path),
                                ladder=(2,), cadence=8)
    base = dict(stencil="heat2d", grid=(16, 16), iters=16)
    victim = eng.submit(RunConfig(seed=1, **base), tenant="victim")
    survivor = eng.submit(RunConfig(seed=2, **base), tenant="survivor")
    got, _ = survivor.result(timeout=300)
    with pytest.raises(SimulationDiverged):
        victim.result(timeout=300)
    assert victim._phase() == "evicted"
    assert victim.health_verdict() == "DIVERGED"
    assert victim.status()["verdict"] == "DIVERGED"
    stats = eng.close()
    assert stats["jobs_evicted"] == 1 and stats["jobs_done"] == 1
    evs = [e for e in _events(eng.telemetry_path)
           if e.get("kind") == "scheduler" and e.get("op") == "evict"]
    assert len(evs) == 1 and evs[0]["tenant"] == "victim"
    faults.reset()  # the one-shot fired; solo replay must stay clean
    _assert_bit_exact(got, RunConfig(seed=2, **base))


def test_preemption_checkpoints_victim_and_resumes_bit_exact(tmp_path):
    """A higher-priority arrival preempts the lowest-priority runner
    through a checkpoint; the victim resumes and still finishes
    bit-identical to its solo run (no completed chunk lost)."""
    eng = serving.ServingEngine(telemetry_dir=str(tmp_path),
                                ladder=(1,), cadence=8)
    low_cfg = RunConfig(stencil="heat2d", grid=(64, 64), iters=4096,
                        seed=4)
    low = eng.submit(low_cfg, tenant="low", priority=0)
    deadline = time.time() + 120
    while low.steps_done == 0 and time.time() < deadline:
        time.sleep(0.01)
    assert low.steps_done > 0, "low-priority job never started"
    high = eng.submit(RunConfig(stencil="heat2d", grid=(64, 64),
                                iters=8, seed=5), tenant="high",
                      priority=5)
    high.result(timeout=300)
    got_low, _ = low.result(timeout=600)
    stats = eng.close()
    assert stats["preemptions"] >= 1
    assert low.preempt_count >= 1
    assert high.finished_at < low.finished_at
    _assert_bit_exact(got_low, low_cfg)


def test_starvation_bound_low_priority_completes(tmp_path):
    """Weighted FIFO would starve priority 0 behind a deep priority-5
    queue; the starvation bound serves it FIFO once it has waited
    ``starvation_rounds`` boundaries."""
    eng = serving.ServingEngine(telemetry_dir=str(tmp_path),
                                ladder=(1,), cadence=8,
                                starvation_rounds=3)
    base = dict(stencil="heat2d", grid=(16, 16), iters=8)
    low = eng.submit(RunConfig(seed=0, **base), tenant="low", priority=0)
    highs = [eng.submit(RunConfig(seed=10 + i, **base), tenant="high",
                        priority=5) for i in range(6)]
    low.result(timeout=300)
    for h in highs:
        h.result(timeout=300)
    stats = eng.close()
    assert stats["jobs_done"] == 7
    assert low.finished_at < max(h.finished_at for h in highs), \
        "the starvation bound must serve the low-priority job before " \
        "the high-priority queue drains"


# ----------------------------------------------------------- cancel

def test_serving_cancel_queued_and_running(tmp_path):
    # starvation promotion off: the queued job must still be queued
    # when its cancel lands (otherwise this would race the scheduler)
    eng = serving.ServingEngine(telemetry_dir=str(tmp_path),
                                ladder=(1,), cadence=8,
                                starvation_rounds=10**9)
    running = eng.submit(RunConfig(stencil="heat2d", grid=(64, 64),
                                   iters=65536), tenant="a")
    queued = eng.submit(RunConfig(stencil="heat2d", grid=(64, 64),
                                  iters=8, seed=2), tenant="b",
                        priority=0)
    deadline = time.time() + 120
    while running.steps_done == 0 and time.time() < deadline:
        time.sleep(0.01)
    assert queued.cancel() and running.cancel()
    for h in (queued, running):
        h.wait(120)
        assert h.cancelled() and h._phase() == "cancelled"
        with pytest.raises(RunCancelled):
            h.result(timeout=1)
        kinds = [e.get("kind") for e in _events(h.telemetry_path)]
        assert "cancelled" in kinds and "error" not in kinds
    assert running._error.step > 0 and queued._error.step == 0
    stats = eng.close()
    assert stats["jobs_cancelled"] == 2 and stats["jobs_done"] == 0


def test_engine_cancel_is_third_outcome_everywhere(tmp_path):
    """RunHandle.cancel through the PR-10 engine: phase 'cancelled',
    a ``cancelled`` event (never ``error``), verdict CANCELLED on
    /status.json, quarantined 'cancelled' in the ledger."""
    eng = SimulationEngine(cli.run, telemetry_dir=str(tmp_path))
    h = eng.submit(RunConfig(stencil="heat2d", grid=(64, 64),
                             iters=262144, log_every=64))
    deadline = time.time() + 120
    while time.time() < deadline and not any(
            e.get("kind") == "chunk" for e in h.events()):
        time.sleep(0.02)
    assert h.cancel()
    assert h.wait(120)
    assert h.cancelled() and h._phase() == "cancelled"
    with pytest.raises(RunCancelled):
        h.result(timeout=1)
    kinds = [e.get("kind") for e in h.events()]
    assert "cancelled" in kinds and "error" not in kinds \
        and "summary" not in kinds
    st = h.status()
    assert st["verdict"] == "CANCELLED"
    assert st["cancelled"]["step"] > 0
    rows = ledger_lib.rows_from_log(h.telemetry_path)
    assert len(rows) == 1
    assert rows[0]["status"] == "quarantined"
    assert rows[0]["quarantine"] == "cancelled"
    assert eng.metrics.snapshot()[
        "engine_requests_cancelled_total"]["value"] == 1


def test_cancel_after_done_returns_false(tmp_path):
    eng = SimulationEngine(cli.run, telemetry_dir=str(tmp_path))
    h = eng.submit(RunConfig(stencil="heat2d", grid=(16, 16), iters=4))
    h.result(timeout=120)
    assert h.cancel() is False
    assert h._phase() == "done"


def test_supervisor_classifies_cancelled_fatal():
    kind, reason, detail = sup._classify_event(
        {"kind": "cancelled", "step": 40},
        ("WEDGED",), ("DIVERGED",))
    assert kind == "fatal" and reason == "CANCELLED"
    assert "40" in detail


# ------------------------------------------------- observability

def test_scheduler_events_fold_into_status(tmp_path):
    eng = serving.ServingEngine(telemetry_dir=str(tmp_path),
                                ladder=(1, 2), cadence=8)
    h = eng.submit(RunConfig(stencil="heat2d", grid=(16, 16), iters=8),
                   tenant="t0")
    h.result(timeout=300)
    eng.close()
    rm = metrics_lib.RunMetrics()
    for rec in _events(eng.telemetry_path):
        rm.ingest(rec)
    st = rm.status()
    sched = st["scheduler"]
    assert sched["counts"]["submit"] == 1
    assert sched["counts"]["retire"] == 1
    assert sched["tenants"]["t0"]["join"] == 1
    assert sched["queue_depth"] == 0
    prom = rm.registry.to_prometheus()
    assert "obs_sched_submit_total" in prom
    assert "obs_sched_tenant_ops" in prom
    # the scheduler session's summary carries the SLO numbers
    summary = [e for e in _events(eng.telemetry_path)
               if e.get("kind") == "summary"][-1]
    assert summary["jobs_done"] == 1
    assert summary["ttfc_p50_s"] is not None


def test_obs_top_renders_scheduler_panel():
    import importlib.util

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        "obs_top_serving_t", os.path.join(repo, "scripts/obs_top.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    status = {"scheduler": {
        "queue_depth": 3, "slots_total": 8, "slots_busy": 5,
        "classes": 2, "counts": {"submit": 9, "reject": 1, "evict": 1},
        "tenants": {"a": {"submit": 5, "join": 4}},
        "last_event": {"op": "join", "tenant": "a", "job": "job-1",
                       "size_class": "abc12345", "t": time.time()},
        "last_reject": {"tenant": "b", "reason": "over_budget",
                        "size_class": "abc12345"}}}
    lines = mod._scheduler_lines(status)
    text = "\n".join(lines)
    assert "queue_depth=3" in text and "slots_busy=5" in text
    assert "reject" in text and "over_budget" in text
    assert "tenant" in text
    assert mod._scheduler_lines({}) == []


def test_serve_engine_cli_flags_roundtrip(tmp_path):
    cfg = cli.config_from_args(
        ["--stencil", "heat2d", "--grid", "16,16", "--iters", "8",
         "--serve-engine", "0", "--compile-cache",
         str(tmp_path / "cache")])
    assert cfg.serve_engine == 0
    assert cfg.compile_cache == str(tmp_path / "cache")
    # compile_cache round-trips through to_argv (the supervisor child
    # re-launch path); serve_engine is launcher-only and must not
    from mpi_cuda_process_tpu.config import to_argv

    argv = to_argv(cfg)
    assert "--compile-cache" in argv
    assert "--serve-engine" not in argv
