"""Tests for the live run console (obs/metrics.py + obs/serve.py).

Pins the layer's contracts:

* **registry math** — bounded-reservoir histogram quantiles are exact
  on known data; count/sum/min/max stay exact past the bound;
  Prometheus rendering is well-formed.
* **snapshot consistency** — concurrent ingest never lets a scrape see
  half of a multi-metric update (one event's metrics land atomically).
* **HTTP surface** — /metrics, /status.json, and /events?after=SEQ
  answer over stdlib urllib against a real log; /events ordering is
  the log's, the long-poll timeout is bounded, and a new record wakes
  a parked long-poll.
* **supervised status** — /status.json on a supervised run with an
  injected FAULT_INJECT wedge shows the WEDGED verdict, the restart,
  and ``resumed_from_step`` — scraped MID-RUN, remotely, without
  reading any log file (the acceptance criterion).
* **clean shutdown** — close() leaks no ``obs-serve*`` thread and the
  port stops answering.
* **obs_top** — renders a live URL, a telemetry path, and the
  committed campaign ledger without error.
"""

import importlib.util
import json
import os
import sys
import threading
import time
import urllib.error
import urllib.request

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from mpi_cuda_process_tpu.config import RunConfig, to_argv  # noqa: E402
from mpi_cuda_process_tpu.obs import metrics, serve, trace  # noqa: E402


def _get(url, timeout=10):
    with urllib.request.urlopen(url, timeout=timeout) as r:
        return r.read().decode()


def _get_json(url, timeout=10):
    return json.loads(_get(url, timeout=timeout))


def _event(kind, **payload):
    return {"schema": trace.SCHEMA_VERSION, "kind": kind,
            "t": time.time(), **payload}


def _wait_for(pred, timeout=10.0):
    t0 = time.monotonic()
    while time.monotonic() - t0 < timeout:
        if pred():
            return True
        time.sleep(0.02)
    return False


# ------------------------------------------------------------- registry

def test_histogram_quantiles_and_bounded_reservoir():
    h = metrics.Histogram("ms", bound=1000)
    for v in range(1, 101):
        h.observe(float(v))
    q = h.quantiles()
    assert q[0.5] == pytest.approx(51.0, abs=1.0)
    assert q[0.9] == pytest.approx(90.0, abs=1.0)
    assert q[0.99] == pytest.approx(99.0, abs=1.0)
    assert h.count == 100 and h.sum == pytest.approx(5050.0)
    assert h.min == 1.0 and h.max == 100.0

    # past the bound: the reservoir slides, the exact stats do not
    small = metrics.Histogram("ms2", bound=10)
    for v in range(1, 101):
        small.observe(float(v))
    assert small.count == 100 and small.sum == pytest.approx(5050.0)
    assert small.min == 1.0 and small.max == 100.0
    assert len(small.reservoir) == 10
    # quantiles reflect the newest window (91..100), not the lifetime
    assert small.quantiles()[0.5] >= 91.0


def test_registry_prometheus_rendering_and_type_conflicts():
    reg = metrics.MetricsRegistry()
    reg.counter("steps_total", "steps done").inc(5)
    reg.gauge("rate").set(2.5)
    g = reg.gauge("peak")
    g.set_max(10)
    g.set_max(3)  # lower: peak keeps 10
    reg.info("run_info").set(tool="cli", note='quo"te\nnl', skipped=None)
    reg.histogram("ms", bound=8).observe(1.5)
    text = reg.to_prometheus()
    assert "# TYPE steps_total counter\nsteps_total 5" in text
    assert "rate 2.5" in text
    assert "peak 10" in text
    assert 'note="quo\\"te\\nnl"' in text and "skipped" not in text
    assert 'ms{quantile="0.5"} 1.5' in text
    assert "ms_count 1" in text
    # a name cannot change metric class mid-run
    with pytest.raises(ValueError):
        reg.counter("rate")
    snap = reg.snapshot()
    assert snap["steps_total"]["value"] == 5
    assert snap["ms"]["count"] == 1


def test_snapshot_consistent_under_concurrent_ingest():
    """Each chunk event bumps chunks_total AND steps_total (steps=5)
    under one lock hold — a concurrent snapshot must never observe the
    pair out of step."""
    rm = metrics.RunMetrics()
    rm.ingest(trace.build_manifest("cli", {"grid": [16, 16]}))
    n_threads, per_thread, steps = 4, 150, 5
    stop = threading.Event()
    bad = []

    def reader():
        while not stop.is_set():
            snap = rm.registry.snapshot()
            chunks = snap.get("obs_chunks_total", {}).get("value", 0)
            total = snap.get("obs_steps_total", {}).get("value", 0)
            if total != chunks * steps:
                bad.append((chunks, total))

    def writer():
        for i in range(per_thread):
            rm.ingest(_event("chunk", chunk=i + 1, steps=steps,
                             wall_s=0.01, ms_per_step=2.0,
                             recompiled=False))

    readers = [threading.Thread(target=reader) for _ in range(2)]
    writers = [threading.Thread(target=writer) for _ in range(n_threads)]
    for t in readers + writers:
        t.start()
    for t in writers:
        t.join()
    stop.set()
    for t in readers:
        t.join()
    assert not bad, f"inconsistent snapshots: {bad[:3]}"
    snap = rm.registry.snapshot()
    assert snap["obs_chunks_total"]["value"] == n_threads * per_thread
    assert snap["obs_steps_total"]["value"] == n_threads * per_thread * steps


def test_run_metrics_full_vocabulary_status():
    rm = metrics.RunMetrics()
    rm.ingest(trace.build_manifest(
        "cli", {"stencil": "heat3d", "grid": [64, 64, 64], "iters": 40}))
    rm.ingest(_event("costmodel", roofline={
        "predicted_ms_per_step_hbm": 1.0,
        "predicted_ms_per_step_exchange": 0.25}))
    rm.ingest(_event("exchange", mode="rdma", backend="pallas-rdma"))
    rm.ingest(_event("chunk", chunk=0, steps=10, wall_s=1.0,
                     ms_per_step=100.0, recompiled=False))
    rm.ingest(_event("chunk", chunk=1, steps=10, wall_s=0.02,
                     ms_per_step=2.0, recompiled=False,
                     memory={"peak_bytes_in_use": 1234}))
    rm.ingest(_event("chunk", chunk=2, steps=10, wall_s=0.5,
                     ms_per_step=50.0, recompiled=True))
    rm.ingest(_event("heartbeat", verdict="STALLED", detail="slow"))
    rm.ingest(_event("heartbeat", verdict="WEDGED", detail="probe hung"))
    rm.ingest(_event("launch", attempt=0, resume=False,
                     resumed_from_step=None))
    rm.ingest(_event("restart", attempt=0, reason="heartbeat verdict "
                     "WEDGED", backoff_s=0.2, checkpoint_step=30))
    rm.ingest(_event("launch", attempt=1, resume=True,
                     resumed_from_step=30))
    rm.ingest(_event("summary", mcells_per_s=3.5, runtime={}))

    st = rm.status()
    assert st["manifest"]["tool"] == "cli"
    assert st["verdict"] == "WEDGED"  # latest heartbeat wins
    assert st["latest_chunk"]["chunk"] == 2
    assert len(st["chunks_recent"]) == 3
    assert len(st["restarts"]) == 1 and len(st["launches"]) == 2
    assert st["resumed_from_step"] == 30
    assert st["exchange"]["mode"] == "rdma"
    assert st["summary"]["mcells_per_s"] == 3.5
    # steady p50 over non-first, non-recompiled chunks only
    assert st["throughput"]["steady_ms_per_step_p50"] == 2.0
    # gcells from the manifest grid: 64^3 cells * 10 steps / 0.5 s
    # (the payload rounds to 4 decimals)
    assert st["throughput"]["gcells_per_s"] == \
        round(64 ** 3 * 10 / 0.5 / 1e9, 4)

    snap = rm.registry.snapshot()
    assert snap["obs_recompiles_total"]["value"] == 1
    assert snap["obs_supervisor_restarts_total"]["value"] == 1
    assert snap["obs_resumed_from_step"]["value"] == 30
    assert snap["obs_device_memory_peak_bytes"]["value"] == 1234
    assert snap["obs_first_chunk_ms_per_step"]["value"] == 100.0
    # roofline gap: steady p50 2.0 over overlapped prediction 1.0
    assert snap["obs_roofline_gap_ratio"]["value"] == pytest.approx(2.0)
    assert snap["obs_heartbeat_verdict"]["labels"]["verdict"] == "WEDGED"
    # a malformed record is swallowed, never raises
    rm.ingest(_event("chunk", chunk="x", steps="y"))
    assert rm.registry.snapshot()["obs_ingest_errors_total"]["value"] >= 1


# ------------------------------------------------------------- endpoints

@pytest.fixture()
def served_log(tmp_path):
    path = str(tmp_path / "run.jsonl")
    with trace.TraceWriter(path) as w:
        w.write_manifest(trace.build_manifest(
            "cli", {"stencil": "heat2d", "grid": [32, 128], "iters": 8}))
        w.event("costmodel", roofline={"predicted_ms_per_step_hbm": 0.1})
        w.event("chunk", chunk=0, steps=2, wall_s=0.5, ms_per_step=250.0,
                recompiled=False)
        w.event("chunk", chunk=1, steps=2, wall_s=0.01, ms_per_step=5.0,
                recompiled=False)
        w.event("heartbeat", verdict="STALLED", detail="x")
    server = serve.serve_run(path, port=0, poll_s=0.05)
    try:
        yield server, path
    finally:
        server.close()


def test_http_metrics_status_and_routes(served_log):
    server, _ = served_log
    assert _wait_for(lambda: server.console.seq >= 5)
    text = _get(server.url + "/metrics")
    assert "obs_run_info" in text and "obs_steps_total 4" in text
    assert 'obs_chunk_ms_per_step{quantile="0.5"} 5' in text

    st = _get_json(server.url + "/status.json")
    trace.validate_manifest(st["manifest"])  # provenance rides status
    assert st["manifest"]["tool"] == "cli"
    assert st["verdict"] == "STALLED"
    assert st["latest_chunk"]["chunk"] == 1
    assert st["throughput"]["steady_ms_per_step_p50"] == 5.0

    assert "status.json" in _get(server.url + "/")  # index names routes
    with pytest.raises(urllib.error.HTTPError) as ei:
        _get(server.url + "/nope")
    assert ei.value.code == 404


def test_events_ordering_incremental_and_longpoll(served_log):
    server, path = served_log
    assert _wait_for(lambda: server.console.seq >= 5)
    lines = _get(server.url + "/events?after=0").strip().splitlines()
    recs = [json.loads(line) for line in lines]
    assert [r["_seq"] for r in recs] == list(range(1, len(recs) + 1))
    assert recs[0]["kind"] == "manifest"  # file order preserved
    assert [r["kind"] for r in recs[1:]] == \
        ["costmodel", "chunk", "chunk", "heartbeat"]

    # incremental: after=N yields exactly the tail
    tail = _get(server.url + f"/events?after={recs[-2]['_seq']}")
    tail_recs = [json.loads(line) for line in tail.strip().splitlines()]
    assert [r["_seq"] for r in tail_recs] == [recs[-1]["_seq"]]

    # bounded long-poll timeout: no new events -> empty after ~wait
    t0 = time.monotonic()
    body = _get(server.url + f"/events?after={server.console.seq}&wait=0.4")
    elapsed = time.monotonic() - t0
    assert body == "" and 0.3 <= elapsed < 5.0

    # a record landing mid-poll wakes the parked request
    result = {}

    def parked():
        result["body"] = _get(
            server.url + f"/events?after={server.console.seq}&wait=10")

    t = threading.Thread(target=parked)
    t.start()
    time.sleep(0.2)
    with open(path, "a") as fh:
        fh.write(json.dumps(_event("chunk", chunk=2, steps=2,
                                   wall_s=0.01, ms_per_step=5.0,
                                   recompiled=False)) + "\n")
    t.join(timeout=8)
    assert not t.is_alive(), "long-poll never woke"
    woke = [json.loads(line)
            for line in result["body"].strip().splitlines()]
    assert len(woke) == 1 and woke[0]["kind"] == "chunk"


def test_server_close_is_clean_and_idempotent(tmp_path):
    path = str(tmp_path / "r.jsonl")
    with trace.TraceWriter(path) as w:
        w.write_manifest(trace.build_manifest("cli", {}))
    server = serve.serve_run(path, port=0, poll_s=0.05)
    url = server.url
    assert _get_json(url + "/status.json")["manifest"]["tool"] == "cli"
    server.close()
    server.close()  # idempotent
    leaked = [t.name for t in threading.enumerate()
              if t.name.startswith("obs-serve")]
    assert not leaked, leaked
    with pytest.raises((urllib.error.URLError, OSError)):
        _get(url + "/status.json", timeout=2)


def test_campaign_console_rescans_directory(tmp_path):
    d = str(tmp_path)
    first = os.path.join(d, "a.jsonl")
    with trace.TraceWriter(first) as w:
        w.write_manifest(trace.build_manifest("measure", {"out": "x"}))
        w.event("label", label="heat2d_tiny", status="ok",
                mcells_per_s=12.5)
    server = serve.serve_campaign(d, port=0, poll_s=0.05)
    try:
        assert _wait_for(lambda: server.console.seq >= 2)
        st = _get_json(server.url + "/status.json")
        assert st["campaign"]["labels"]["heat2d_tiny"]["status"] == "ok"
        assert st["campaign"]["counts"] == {"ok": 1}
        # a log dropped AFTER the server started is picked up live
        second = os.path.join(d, "b.jsonl")
        with trace.TraceWriter(second) as w:
            w.write_manifest(trace.build_manifest("cli", {}))
            w.event("label", label="late_label", status="timeout")
        assert _wait_for(
            lambda: "late_label" in (_get_json(
                server.url + "/status.json").get("campaign") or
                {}).get("labels", {}))
        st = _get_json(server.url + "/status.json")
        assert st["campaign"]["counts"] == {"ok": 1, "timeout": 1}
        assert st["manifests_seen"] == 2
    finally:
        server.close()


# ------------------------------------------- supervised /status.json e2e

def test_supervised_status_shows_wedge_restart_and_resume(
        tmp_path, monkeypatch):
    """THE acceptance pin, live: an injected wedge (FAULT_INJECT) on a
    supervised run with --serve must be visible REMOTELY mid-run —
    /status.json shows the WEDGED verdict, the restart, and
    resumed_from_step, without reading any log file; and the console
    shuts down with the supervisor (no leaked thread)."""
    from mpi_cuda_process_tpu.resilience import supervisor as sup

    monkeypatch.setenv("FAULT_INJECT",
                       "exchange:step=40:hang,heartbeat:wedge")
    monkeypatch.setenv("FAULT_HANG_S", "60")
    # the child's in-process heartbeat must verdict BEFORE the
    # supervisor's wall-clock fallback so the kill reason is the
    # verdict (env inherited by the spawned child)
    monkeypatch.setenv("OBS_STALL_AFTER_S", "3")
    sup_log = str(tmp_path / "run.supervisor.jsonl")
    res = {}

    def scrape():
        url = None
        deadline = time.monotonic() + 90
        while time.monotonic() < deadline and url is None:
            try:
                for line in open(sup_log):
                    rec = json.loads(line)
                    if rec.get("kind") == "serve":
                        url = rec["url"]
            except (OSError, ValueError):
                pass
            time.sleep(0.1)
        if url is None:
            res["err"] = "no serve event in the supervisor log"
            return
        st = None
        while time.monotonic() < deadline:
            try:
                st = _get_json(url + "/status.json", timeout=5)
            except OSError:
                time.sleep(0.2)
                continue
            if st.get("restarts") and st.get("resumed_from_step") == 30 \
                    and (st.get("heartbeat") or {}).get("verdict") == \
                    "WEDGED":
                res["status"] = st
                res["url"] = url
                return
            time.sleep(0.2)
        res["err"] = f"condition never met; last status: {st}"

    t = threading.Thread(target=scrape)
    t.start()
    rc = sup.run_supervised(RunConfig(
        stencil="life", grid=(64, 64), iters=100, seed=7,
        checkpoint_every=10, checkpoint_dir=str(tmp_path / "ck"),
        telemetry=str(tmp_path / "run.jsonl"), supervise=True,
        max_restarts=2, restart_backoff=0.2, supervise_stall_s=30.0,
        serve_port=0))
    t.join()
    assert rc == 0
    assert "err" not in res, res["err"]
    st = res["status"]
    # the remote answer to "is it wedged?": verdict + restart + resume
    assert st["heartbeat"]["verdict"] == "WEDGED"
    assert len(st["restarts"]) >= 1
    assert "heartbeat verdict" in st["restarts"][0]["reason"]
    assert st["resumed_from_step"] == 30
    launches = [ln for ln in st["launches"] if ln.get("resume")]
    assert launches and launches[0]["resumed_from_step"] == 30
    # supervisor manifest is the primary; children counted as sources
    assert st["manifest"]["tool"] == "supervisor"
    assert st["manifests_seen"] >= 2
    # console gone with the run
    leaked = [th.name for th in threading.enumerate()
              if th.name.startswith("obs-serve")]
    assert not leaked, leaked
    with pytest.raises((urllib.error.URLError, OSError)):
        _get(res["url"] + "/status.json", timeout=2)


# -------------------------------------------------------------- wiring

def test_cli_serve_flag_and_launcher_only_config():
    from mpi_cuda_process_tpu.cli import config_from_args

    cfg = config_from_args(["--serve", "0"])
    assert cfg.serve_port == 0
    assert config_from_args([]).serve_port is None
    # launcher-only: a supervised child must never inherit --serve
    argv = to_argv(RunConfig(serve_port=8123, iters=7))
    assert "--serve" not in argv and "8123" not in argv
    assert config_from_args(argv) == RunConfig(iters=7)


# -------------------------------------------------------------- obs_top

def _load_script(name, rel):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(REPO, rel))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def obs_top():
    return _load_script("obs_top_t", "scripts/obs_top.py")


def test_obs_top_renders_telemetry_path(tmp_path, capsys, obs_top):
    path = str(tmp_path / "run.jsonl")
    with trace.TraceWriter(path) as w:
        w.write_manifest(trace.build_manifest(
            "cli", {"stencil": "heat2d", "grid": [32, 128], "iters": 8}))
        w.event("costmodel", roofline={"predicted_ms_per_step_hbm": 0.1})
        w.event("chunk", chunk=0, steps=2, wall_s=0.5, ms_per_step=250.0,
                recompiled=False)
        w.event("chunk", chunk=1, steps=2, wall_s=0.01, ms_per_step=5.0,
                recompiled=False)
        w.event("heartbeat", verdict="STALLED", detail="slow")
        w.event("summary", mcells_per_s=1.0, runtime={})
    # --once is a health probe (round 16): the latest heartbeat verdict
    # is STALLED, so the exit code is nonzero — CI/campaign scripts
    # gate on it (the frame still renders in full)
    assert obs_top.main([path, "--once"]) == 1
    out = capsys.readouterr().out
    assert "tool=cli" in out and "stencil=heat2d" in out
    assert "rate" in out and "roof" in out
    assert "verdict=STALLED" in out
    assert "mcells_per_s=1.0" in out


def test_obs_top_renders_live_url_and_campaign_deltas(
        tmp_path, capsys, obs_top, monkeypatch):
    from mpi_cuda_process_tpu.obs import ledger as ledger_lib

    # a ledger baseline the campaign view computes deltas against
    ledger_path = str(tmp_path / "ledger.jsonl")
    row = ledger_lib.make_row(
        "heat2d_tiny", 10.0, source="test", measured_at=time.time(),
        backend="cpu")
    ledger_lib.append_rows([row], ledger_path)

    path = str(tmp_path / "m.jsonl")
    with trace.TraceWriter(path) as w:
        w.write_manifest(trace.build_manifest("measure", {"out": "x"}))
        w.event("label", label="heat2d_tiny", status="ok",
                mcells_per_s=12.5)
    server = serve.serve_run(path, port=0, poll_s=0.05)
    try:
        assert _wait_for(lambda: server.console.seq >= 2)
        assert obs_top.main([server.url, "--once",
                             "--ledger", ledger_path]) == 0
    finally:
        server.close()
    out = capsys.readouterr().out
    assert "tool=measure" in out
    assert "heat2d_tiny" in out and "+25.0%" in out


def test_obs_top_renders_a_campaign_ledger(tmp_path, capsys, obs_top):
    """Acceptance: a campaign ledger (baselines + quarantined rows)
    renders without error."""
    from mpi_cuda_process_tpu.obs import ledger as ledger_lib

    path = str(tmp_path / "ledger.jsonl")
    ledger_lib.append_rows([
        ledger_lib.make_row("heat3d_256_f32", 100.0, source="s",
                            backend="tpu", expected_backend="tpu",
                            measured_at=1.0),
        ledger_lib.make_row("heat3d_512_f32", 0.0, source="s",
                            backend="tpu", expected_backend="tpu",
                            measured_at=2.0)], path)
    assert obs_top.main([path, "--once"]) == 0
    out = capsys.readouterr().out
    assert "baselines" in out and "quarantine reasons" in out


def test_obs_top_sparkline():
    obs_top = _load_script("obs_top_spark", "scripts/obs_top.py")
    assert obs_top.sparkline([]) == "(no samples yet)"
    assert len(obs_top.sparkline([1.0] * 5)) == 5  # flat, no div-by-0
    s = obs_top.sparkline([0, 1, 2, 3])
    assert s[0] == "▁" and s[-1] == "█"
