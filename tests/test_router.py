"""serving/router.py: the fleet front door (ISSUE 17).

The multi-replica contract in five pins:

1. **Affinity** — the second job of a size class lands on the class's
   affine replica and triggers zero backend compiles there (the
   single-engine resident-step pin, lifted through the router).
2. **Zero lost jobs through a kill** — a replica killed mid-stream
   loses nothing: its unresolved jobs rebalance to survivors from
   their ORIGINAL configs (deterministic rerun => bit-exact result),
   the supervised restart brings the replica back as generation+1,
   and the router's final stats say so in numbers.
3. **Aggregate admission** — a job is rejected only when EVERY live
   replica's admission controller refuses; the reject carries the
   aggregate arithmetic.  ``unsupported`` refusals never fall through.
4. **One fleet status** — the router log + N replica-tagged scheduler
   logs roll up into per-replica rows (the ``obs_top`` fleet panel's
   source) under schema-validated manifests.
5. **SLO hygiene** — a cancelled request (the rebalance mechanism)
   rides its own counter and never lands in the engine's
   ttfc/latency histograms.

Plus the elastic-ladder shrink seam (scheduler side): a class that
outlives its peak live-repacks down the ladder — the ``shrink`` event
fires, gauges reconcile, and the surviving tenant's result stays
bit-exact vs its solo run.
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
from mpi_cuda_process_tpu.obs import aggregate as agg_lib  # noqa: E402
from mpi_cuda_process_tpu.obs import runtime as runtime_lib  # noqa: E402
from mpi_cuda_process_tpu.obs import trace as trace_lib  # noqa: E402
from mpi_cuda_process_tpu import serving  # noqa: E402
from mpi_cuda_process_tpu.serving import (  # noqa: E402
    AdmissionError, ServingRouter)


def _cfg(seed=0, grid=(16, 16), iters=16, **kw):
    return RunConfig(stencil="heat2d", grid=grid, iters=iters,
                     seed=seed, **kw)


def _solo(cfg):
    fields, _ = cli.run(cfg)
    return tuple(np.asarray(f) for f in fields)


def _wait_first_chunk(h, timeout=60.0):
    deadline = time.time() + timeout
    while time.time() < deadline and not h.done():
        inner = h._inner
        if inner is not None and \
                inner.timings.get("time_to_first_chunk_s") is not None:
            return True
        time.sleep(0.01)
    return False


# ------------------------------------------------------------ routing

def test_affinity_second_job_zero_compiles(tmp_path):
    """Pin 1: the class's second job hits its warm affine replica."""
    r = ServingRouter(replicas=2, ladder=(2,), cadence=8,
                      telemetry_dir=str(tmp_path))
    try:
        ha = r.submit(_cfg(seed=1), tenant="a")
        ha.result(300)
        seen = runtime_lib.compile_events_seen()
        hb = r.submit(_cfg(seed=2), tenant="b")
        hb.result(300)
        assert hb.replica == ha.replica, \
            "second job of a class must route to its affine replica"
        assert runtime_lib.compile_events_seen() == seen, \
            "second job of a class must compile NOTHING anywhere"
    finally:
        stats = r.close()
    assert stats["jobs_done"] == 2 and stats["lost_jobs"] == 0


def test_kill_rebalances_and_restarts_zero_lost(tmp_path):
    """Pin 2: SIGKILL mid-stream -> rebalance + supervised restart,
    zero lost jobs, and the rerun's bytes match the solo run."""
    r = ServingRouter(replicas=3, ladder=(1, 2), cadence=8,
                      restart_backoff=0.05, telemetry_dir=str(tmp_path))
    try:
        warm = r.submit(_cfg(seed=3))
        warm.result(300)
        victim_cfg = _cfg(seed=4, iters=60000)
        h = r.submit(victim_cfg)
        target = h.replica
        assert _wait_first_chunk(h), "victim never started computing"
        assert not h.done(), "victim finished before the kill"
        assert r.kill_replica(target)
        fields, _ = h.result(600)
        assert h.resubmits >= 1 and h.replica != target
        want = _solo(victim_cfg)
        for a, b in zip(fields, want):
            assert np.array_equal(np.asarray(a), b), \
                "rebalanced rerun must be bit-exact vs the solo run"
        deadline = time.time() + 15
        while time.time() < deadline and \
                not r.replicas()[target]["alive"]:
            time.sleep(0.05)
        rep = r.replicas()[target]
        assert rep["alive"] and rep["generation"] == 1, \
            "supervised restart must bring the replica back"
        after = r.submit(_cfg(seed=5))
        after.result(300)
    finally:
        stats = r.close()
    assert stats["lost_jobs"] == 0
    assert stats["jobs_done"] == 3
    assert stats["rebalanced"] >= 1
    assert stats["restarts"] == 1


def test_kill_dead_or_unknown_replica_is_false(tmp_path):
    r = ServingRouter(replicas=1, max_restarts=0,
                      telemetry_dir=str(tmp_path))
    try:
        assert not r.kill_replica("nope")
        assert r.kill_replica("r0")
        assert not r.kill_replica("r0"), "already dead"
    finally:
        r.close(drain=False, timeout=10)


# ---------------------------------------------------------- admission

def test_aggregate_admission_rejects_only_when_all_refuse(tmp_path):
    """Pin 3: the reject is the AGGREGATE verdict."""
    r = ServingRouter(replicas=2, ladder=(2,), hbm_bytes=1,
                      telemetry_dir=str(tmp_path))
    try:
        with pytest.raises(AdmissionError) as ei:
            r.submit(_cfg(seed=1))
        assert ei.value.reason == "over_budget"
        assert "aggregate" in str(ei.value)
    finally:
        stats = r.close()
    assert stats["rejects"] == 1 and stats["jobs_done"] == 0


def test_unsupported_never_falls_through(tmp_path):
    """A categorical refusal re-raises from the FIRST replica: trying
    the others would just repeat it."""
    r = ServingRouter(replicas=2, telemetry_dir=str(tmp_path))
    try:
        with pytest.raises(AdmissionError) as ei:
            r.submit(_cfg(seed=1, resume="/nonexistent"))
        assert ei.value.reason == "unsupported"
    finally:
        r.close()


# ------------------------------------------------------- fleet status

def test_aggregate_status_has_replica_rows(tmp_path):
    """Pin 4: router + replica logs roll into one hosts table with a
    row per replica, under schema-valid manifests."""
    r = ServingRouter(replicas=3, ladder=(2,), cadence=8,
                      telemetry_dir=str(tmp_path))
    try:
        hs = [r.submit(_cfg(seed=s, grid=(16, 16 + 16 * (s % 2))))
              for s in range(4)]
        for h in hs:
            h.result(300)
        paths = [r.telemetry_path] + [
            rep["telemetry"] for rep in r.replicas().values()]
    finally:
        r.close()
    for p in paths[1:]:
        with open(p) as fh:
            manifest = json.loads(fh.readline())
        trace_lib.validate_manifest(manifest)
        assert manifest["replica"] in ("r0", "r1", "r2")
    status = agg_lib.aggregate_logs(paths)
    rows = [row for row in status["hosts"] if row.get("replica")]
    assert len(rows) == 3, \
        f"one fleet row per replica, got {[r.get('key') for r in rows]}"
    busy = [row for row in rows if row.get("scheduler")]
    assert busy, "replica rows must carry the folded scheduler block"
    sched = busy[0]["scheduler"]
    assert sched.get("size_classes"), \
        "fleet rows must carry the per-class table for the obs_top panel"


def test_router_events_fold_into_status(tmp_path):
    """The router's own log folds: route counters + liveness gauges +
    the last death, rendered by the obs_top fleet panel."""
    from mpi_cuda_process_tpu.obs import metrics as metrics_lib

    r = ServingRouter(replicas=2, ladder=(1, 2), cadence=8,
                      restart_backoff=0.05, max_restarts=0,
                      telemetry_dir=str(tmp_path))
    try:
        h = r.submit(_cfg(seed=7, iters=60000))
        assert _wait_first_chunk(h)
        r.kill_replica(h.replica)
        h.result(600)
    finally:
        r.close()
    rm = metrics_lib.RunMetrics()
    for rec in agg_lib.iter_records(r.telemetry_path):
        rm.ingest(rec)
    rt = rm.status().get("router")
    assert rt, "router events must fold into status()['router']"
    assert rt["counts"].get("route", 0) >= 1
    assert rt["counts"].get("rebalance", 0) >= 1
    assert rt["counts"].get("replica_dead", 0) == 1
    assert rt["last_death"]["replica"] == "r0" or \
        rt["last_death"]["replica"] == "r1"
    assert rt["replicas_total"] == 2


# ------------------------------------------------------- SLO hygiene

def test_cancelled_requests_excluded_from_latency_histograms(tmp_path):
    """Pin 5 (engine level): cancel rides its own counter; the
    ttfc/latency histograms only ever see non-cancelled requests."""
    eng = SimulationEngine(cli.run, telemetry_dir=str(tmp_path))
    done = eng.submit(_cfg(seed=1, iters=4))
    done.result(timeout=300)
    with eng.metrics.lock:
        lat = eng.metrics.histogram("engine_request_latency_s", "")
        base_lat, base_count = lat.count, \
            eng.metrics.counter("engine_requests_total", "").value
    victim = eng.submit(_cfg(seed=2, iters=200000, log_every=8))
    while victim.started_at is None and not victim.done():
        time.sleep(0.01)
    victim.cancel()
    with pytest.raises(RunCancelled):
        victim.result(timeout=300)
    with eng.metrics.lock:
        assert eng.metrics.counter(
            "engine_requests_cancelled_total", "").value == 1
        assert eng.metrics.counter(
            "engine_requests_total", "").value == base_count + 1
        assert eng.metrics.histogram(
            "engine_request_latency_s", "").count == base_lat, \
            "a cancelled request must NOT land in the latency histogram"
        assert eng.metrics.histogram(
            "engine_time_to_first_chunk_s", "").count <= base_lat


# ------------------------------------------------------ ladder shrink

def test_ladder_shrink_fires_and_survivor_stays_bit_exact(tmp_path):
    """Shrink seam: a class grown for a burst repacks down the ladder
    once occupancy falls — the ``shrink`` event lands, gauges
    reconcile, and the long-lived survivor's bytes never notice."""
    eng = serving.ServingEngine(telemetry_dir=str(tmp_path),
                                ladder=(1, 2, 4), cadence=4,
                                shrink_after_rounds=3)
    burst = [eng.submit(_cfg(seed=s, iters=24), tenant=f"b{s}")
             for s in (1, 2, 3)]
    survivor_cfg = _cfg(seed=9, iters=40000)
    survivor = eng.submit(survivor_cfg, tenant="long")
    for h in burst:
        h.result(timeout=300)
    fields, _ = survivor.result(timeout=600)
    stats = eng.close()
    assert stats["shrinks"] >= 1, \
        f"occupancy fell to 1 of 4 with nobody waiting: {stats}"
    assert stats["jobs_done"] == 4
    [cls] = stats["class_table"]
    assert cls["capacity"] < 4, "the ladder must have come back down"
    assert cls["occupied"] == 0
    want = _solo(survivor_cfg)
    for a, b in zip(fields, want):
        assert np.array_equal(np.asarray(a), b), \
            "survivor of a live shrink must stay bit-exact vs solo"
    ops = [e for e in agg_lib.iter_records(eng.telemetry_path)
           if e.get("kind") == "scheduler" and e.get("op") == "shrink"]
    assert ops and all(op.get("capacity") < 4 for op in ops)


def test_shrink_disabled_at_zero(tmp_path):
    eng = serving.ServingEngine(telemetry_dir=str(tmp_path),
                                ladder=(1, 2), cadence=4,
                                shrink_after_rounds=0)
    hs = [eng.submit(_cfg(seed=s, iters=24)) for s in (1, 2)]
    long = eng.submit(_cfg(seed=3, iters=20000))
    for h in hs:
        h.result(timeout=300)
    long.result(timeout=600)
    stats = eng.close()
    assert stats["shrinks"] == 0
    [cls] = stats["class_table"]
    assert cls["capacity"] == 2, "shrink_after_rounds=0 must disable"
