"""Tests for distributed span tracing + the multi-process roll-up.

Pins the round-16 contracts:

* **span model** — records validate against the obs schema, nest with
  correct parent ids, share one trace_id per session, and carry wall
  start + monotonic-measured duration.
* **cross-process propagation** — ``OBS_TRACE_CONTEXT`` round-trips;
  a session opened under an exported context adopts the trace_id and
  parents its root under the exporter's span; thread-local propagation
  (the engine's in-process path) wins over the environment.
* **supervisor timeline** — attempt/kill/restart/backoff spans land in
  the supervisor log in causal order, the restart span names the next
  attempt's ``resumed_from_step``, and the launcher's ``env_extra``
  exports the attempt span (fake-launcher units; the real-subprocess
  chain is pinned by the tier-1 span smoke).
* **jaxpr invariance** — spans on vs off change NOTHING about the
  jitted step (the telemetry zero-ops pin extended).
* **export** — ``obs_trace_export.py`` folds N logs into one
  schema-valid Chrome trace: hosts/processes as tracks, spans + chunk
  slices + instant markers, trace ids collected.
* **aggregation** — ``obs/aggregate.py`` merges per-process logs
  (distinct ``process_index``) into a per-host table + fleet
  aggregate, served on ``/status.json``.
* **engine request accounting** — submit() opens a request span;
  ``time_to_first_chunk`` lands in handle.status() AND /metrics; the
  engine keeps per-request latency histograms.
* **satellites** — LogTail truncation/rotation reset; obs_top --once
  health exit; ledger best_known gauges on /metrics; CampaignConsole
  complete-lines-only under a racing writer.
"""

import importlib.util
import json
import os
import sys
import threading
import time
import urllib.request

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from mpi_cuda_process_tpu.config import RunConfig  # noqa: E402
from mpi_cuda_process_tpu.obs import aggregate, metrics, serve  # noqa: E402
from mpi_cuda_process_tpu.obs import spans, trace  # noqa: E402


def _load_script(name, rel):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(REPO, rel))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def trace_export():
    return _load_script("obs_trace_export_t", "scripts/obs_trace_export.py")


@pytest.fixture(scope="module")
def obs_top():
    return _load_script("obs_top_spans_t", "scripts/obs_top.py")


def _manifest(tool="cli", process_index=0, hostname="boxA",
              process_count=1, trace_block=None, **run):
    """A hand-built schema-2 manifest (no jax provenance probe)."""
    m = {
        "schema": trace.SCHEMA_VERSION, "kind": "manifest", "tool": tool,
        "created_at": time.time(), "run": dict(run),
        "provenance": {
            "git_sha": "deadbeef", "jax_version": "0.0-test",
            "backend": "cpu", "device_kind": "cpu", "device_count": 1,
            "framework_version": "test",
            "process_index": process_index,
            "process_count": process_count, "hostname": hostname,
        },
    }
    if trace_block is not None:
        m["trace"] = trace_block
    return trace.validate_manifest(m)


def _read(path):
    return [json.loads(l) for l in open(path) if l.strip()]


# ------------------------------------------------------------ span model

def test_span_records_validate_nest_and_share_trace(tmp_path):
    path = str(tmp_path / "s.jsonl")
    w = trace.TraceWriter(path)
    em = spans.SpanEmitter(w, root_name="cli")
    w.write_manifest(_manifest(trace_block=em.manifest_block()))
    with em.span("outer", step=1) as outer:
        with em.span("inner") as inner:
            assert inner.trace_id == em.trace_id
            assert em.current().span_id == inner.span_id
    em.emit("manual", start=time.time() - 0.5, dur_s=0.5, tag="x")
    em.close()
    em.close()  # idempotent
    w.close()

    manifest, events = trace.validate_log(path)  # every span validates
    recs = {r["name"]: r for r in events if r["kind"] == "span"}
    assert set(recs) == {"outer", "inner", "manual", "cli"}
    assert len({r["trace_id"] for r in recs.values()}) == 1
    assert recs["inner"]["parent_id"] == recs["outer"]["span_id"]
    assert recs["outer"]["parent_id"] == recs["cli"]["span_id"]
    assert recs["manual"]["parent_id"] == recs["cli"]["span_id"]
    assert recs["cli"]["parent_id"] is None  # trace root
    assert manifest["trace"]["trace_id"] == recs["cli"]["trace_id"]
    assert manifest["trace"]["root_span_id"] == recs["cli"]["span_id"]
    for r in recs.values():
        assert r["dur_s"] >= 0 and r["start"] > 0
    assert recs["outer"]["attrs"] == {"step": 1}
    assert recs["manual"]["attrs"] == {"tag": "x"}
    # root emitted LAST (after its children) but starts first
    assert recs["cli"]["start"] <= recs["outer"]["start"]


def test_context_encode_decode_and_resolution(monkeypatch):
    ctx = spans.SpanContext("abc", "def")
    assert spans.SpanContext.decode(ctx.encode()).span_id == "def"
    assert spans.SpanContext.decode("garbage") is None
    assert spans.SpanContext.decode(":x") is None

    monkeypatch.delenv(spans.ENV_VAR, raising=False)
    assert spans.resolve_context() is None
    monkeypatch.setenv(spans.ENV_VAR, "t1:s1")
    assert spans.resolve_context().trace_id == "t1"
    # thread-local (the engine's in-process path) wins over the env
    spans.push_thread_context(spans.SpanContext("t2", "s2"))
    try:
        assert spans.resolve_context().trace_id == "t2"
    finally:
        spans.pop_thread_context()
    assert spans.resolve_context().trace_id == "t1"


def test_session_adopts_env_context_and_disable_gate(
        tmp_path, monkeypatch):
    from mpi_cuda_process_tpu import obs

    monkeypatch.setenv(spans.ENV_VAR, "parenttrace:parentspan")
    path = str(tmp_path / "child.jsonl")
    s = obs.open_session(path, tool="cli", run={}, with_heartbeat=False)
    assert s.spans.trace_id == "parenttrace"
    with s.spans.span("work"):
        pass
    s.close()
    recs = _read(path)
    assert recs[0]["trace"] == {"trace_id": "parenttrace",
                                "root_span_id": s.spans.root_id,
                                "parent_span_id": "parentspan"}
    sp = {r["name"]: r for r in recs if r["kind"] == "span"}
    assert sp["cli"]["parent_id"] == "parentspan"
    assert sp["work"]["trace_id"] == "parenttrace"

    # OBS_SPANS=0: events keep flowing, spans stop
    monkeypatch.setenv("OBS_SPANS", "0")
    path2 = str(tmp_path / "off.jsonl")
    s2 = obs.open_session(path2, tool="cli", run={}, with_heartbeat=False)
    with s2.spans.span("work"):
        pass
    s2.event("chunk", chunk=0, steps=1, wall_s=0.1, ms_per_step=100.0)
    s2.close()
    kinds = [r["kind"] for r in _read(path2)]
    assert "span" not in kinds and "chunk" in kinds


def test_jitted_step_identical_spans_on_vs_off(tmp_path, monkeypatch):
    """Acceptance criterion: the step jaxpr is byte-identical with spans
    on vs off — spans are host-side wall clocks only — and the step
    jaxpr and the runner's HLO are identical traced inside the program's
    regions (``spans.region``: profiler annotations, no ops) or not."""
    import jax

    from mpi_cuda_process_tpu import driver, obs
    from mpi_cuda_process_tpu.ops.stencil import make_stencil
    from mpi_cuda_process_tpu.utils.init import init_state

    st = make_stencil("heat2d")
    step = driver.make_step(st, (16, 128))
    abstract = tuple(jax.ShapeDtypeStruct(f.shape, f.dtype) for f in
                     init_state(st, (16, 128), seed=0, kind="pulse"))
    jaxprs = {}
    for flag in ("1", "0"):
        monkeypatch.setenv("OBS_SPANS", flag)
        s = obs.open_session(str(tmp_path / f"sp{flag}.jsonl"),
                             tool="cli", run={}, with_heartbeat=False)
        # fresh state per leg: the scanned runners donate their buffers
        fields = init_state(st, (16, 128), seed=0, kind="pulse")
        driver.run_simulation(st, fields, 4, step_fn=step, log_every=2,
                              callback=lambda d, fs: None,
                              observer=s.recorder)
        s.close()
        jaxprs[flag] = (str(jax.make_jaxpr(step)(abstract)),
                        str(jax.make_jaxpr(
                            driver.make_runner(step, 4, jit=False))(
                            abstract)))
    assert jaxprs["1"] == jaxprs["0"]

    def programs():
        runner = jax.jit(driver.make_runner(step, 4, jit=False))
        return (str(jax.make_jaxpr(step)(abstract)),
                runner.lower(abstract).as_text())

    plain = programs()
    with spans.region("sim.chunk", step_num=0), spans.region("sim.runner"):
        inside = programs()
    assert inside == plain
    assert plain[0] == jaxprs["0"][0]
    # spans-on really did emit (the comparison is not vacuous)
    on = _read(str(tmp_path / "sp1.jsonl"))
    assert any(r["kind"] == "span" and r["name"] == "compile"
               for r in on)
    off = _read(str(tmp_path / "sp0.jsonl"))
    assert not any(r["kind"] == "span" for r in off)


# ------------------------------------------------ supervisor timeline

def test_supervise_emits_causal_spans_with_fake_launcher(tmp_path):
    """attempt/kill/restart/backoff spans in causal order, the restart
    naming the resume step, and env_extra exporting the attempt span —
    all without a subprocess (injected launcher/clock/sleep)."""
    from mpi_cuda_process_tpu import obs
    from mpi_cuda_process_tpu.resilience import supervisor as sup

    session = obs.open_session(str(tmp_path / "sup.jsonl"),
                               tool="supervisor", run={},
                               with_heartbeat=False)
    ck = tmp_path / "ck"

    class FakeHandle:
        def __init__(self, rc_sequence):
            self._rcs = rc_sequence

        def poll(self):
            return self._rcs.pop(0) if self._rcs else None

        def kill(self):
            pass

        def wait(self, timeout_s=30.0):
            return 0

    class FakeTail:
        def __init__(self, events):
            self._events = events

        def poll(self):
            ev, self._events = self._events, []
            return ev

    exported = []

    def launcher(attempt, resume):
        exported.append(spans.env_extra(session).get(spans.ENV_VAR))
        if attempt == 0:
            # a WEDGED verdict: the supervisor must kill + restart;
            # fake a surviving npy checkpoint for the resume pointer
            ck.mkdir(parents=True, exist_ok=True)
            (ck / "meta.json").write_text(json.dumps({"step": 30}))
            return FakeHandle([None, None]), [FakeTail(
                [{"kind": "heartbeat", "verdict": "WEDGED"}])]
        return FakeHandle([0]), [FakeTail([])]

    res = sup.supervise(launcher, str(ck), max_restarts=2,
                        backoff_base_s=0.01, stall_timeout_s=60,
                        poll_s=0.0, session=session,
                        sleep=lambda s: None)
    session.close()
    assert res.ok and res.attempts == 2

    recs = _read(str(tmp_path / "sup.jsonl"))
    sp = [r for r in recs if r["kind"] == "span"]
    names = [r["name"] for r in sp]
    for needed in ("attempt", "kill", "restart", "backoff",
                   "supervisor"):
        assert needed in names, names
    assert len({r["trace_id"] for r in sp}) == 1
    attempts = sorted((r for r in sp if r["name"] == "attempt"),
                      key=lambda r: r["start"])
    assert len(attempts) == 2
    restart = next(r for r in sp if r["name"] == "restart")
    assert restart["attrs"]["resumed_from_step"] == 30
    # causal ordering: attempt0 ends <= restart <= attempt1 start
    assert attempts[0]["start"] + attempts[0]["dur_s"] <= \
        restart["start"] + 1e-6
    assert restart["start"] + restart["dur_s"] <= \
        attempts[1]["start"] + 1e-6
    kill = next(r for r in sp if r["name"] == "kill")
    assert kill["parent_id"] == attempts[0]["span_id"]
    backoff = next(r for r in sp if r["name"] == "backoff")
    assert backoff["parent_id"] == restart["span_id"]
    # the launcher ran INSIDE each attempt span: the exported context
    # names the attempt spans, in order
    assert exported == [f"{attempts[0]['trace_id']}:"
                        f"{attempts[0]['span_id']}",
                        f"{attempts[1]['trace_id']}:"
                        f"{attempts[1]['span_id']}"]


# --------------------------------------------------------------- export

def test_trace_export_builds_valid_chrome_trace(tmp_path, trace_export):
    base = str(tmp_path / "run.jsonl")
    suppath = str(tmp_path / "run.supervisor.jsonl")
    childpath = str(tmp_path / "run.attempt0.jsonl")

    w = trace.TraceWriter(suppath)
    em = spans.SpanEmitter(w, root_name="supervisor")
    w.write_manifest(_manifest(tool="supervisor",
                               trace_block=em.manifest_block()))
    w.event("launch", attempt=0, resume=False)
    with em.span("attempt", attempt=0):
        child_ctx = em.current().encode()
    em.close()
    w.close()

    w2 = trace.TraceWriter(childpath)
    em2 = spans.SpanEmitter(w2, context=spans.SpanContext.decode(
        child_ctx), root_name="cli")
    w2.write_manifest(_manifest(trace_block=em2.manifest_block()))
    w2.event("chunk", chunk=0, steps=4, wall_s=0.25, ms_per_step=62.5,
             recompiled=False)
    w2.event("heartbeat", verdict="WEDGED", detail="probe hang")
    em2.close()
    w2.close()

    out = str(tmp_path / "trace.json")
    # the base path never existed: sibling discovery must find both
    assert trace_export.main([base, "-o", out]) == 0
    obj = json.load(open(out))
    assert trace_export.validate_export(obj) == []
    evs = obj["traceEvents"]
    sp = [e for e in evs if e.get("cat") == "span"]
    assert {e["name"] for e in sp} == {"attempt", "supervisor", "cli"}
    assert len({e["args"]["trace_id"] for e in sp}) == 1
    assert obj["otherData"]["trace_ids"] == [em.trace_id]
    # chunk slice synthesized from the event (ts = t - wall_s)
    chunk = next(e for e in evs if e.get("cat") == "chunk")
    assert chunk["ph"] == "X" and chunk["dur"] == pytest.approx(
        0.25e6, rel=1e-3)
    # instant markers: heartbeat verdict + launch
    inames = {e["name"] for e in evs if e["ph"] == "i"}
    assert "heartbeat WEDGED" in inames and "launch attempt 0" in inames
    # both logs on the same host|process track, distinct threads
    assert len({e["pid"] for e in evs}) == 1
    assert len({e["tid"] for e in evs if e["ph"] != "M"}) == 2
    # the child root parents under the exporter's attempt span
    att = next(e for e in sp if e["name"] == "attempt")
    cli_root = next(e for e in sp if e["name"] == "cli")
    assert cli_root["args"]["parent_id"] == att["args"]["span_id"]

    assert trace_export.main([str(tmp_path / "absent.jsonl")]) == 2


# ---------------------------------------------------------- aggregation

def _process_log(tmp_path, idx, gcells_steps=4, wall=0.5,
                 verdict=None, hostname="boxA"):
    path = str(tmp_path / f"proc{idx}.jsonl")
    w = trace.TraceWriter(path)
    em = spans.SpanEmitter(w, root_name="cli")
    w.write_manifest(_manifest(
        process_index=idx, process_count=2, hostname=hostname,
        trace_block=em.manifest_block(),
        stencil="heat2d", grid=[100, 1000], iters=8))
    w.event("chunk", chunk=0, steps=gcells_steps, wall_s=wall,
            ms_per_step=wall * 1e3 / gcells_steps, recompiled=False)
    if verdict:
        w.event("heartbeat", verdict=verdict, detail="t")
    em.close()
    w.close()
    return path


def test_aggregate_merges_processes_into_host_table(tmp_path):
    """Acceptance criterion: >=2 per-process logs (distinct
    process_index) merge into one payload with a per-host table."""
    p0 = _process_log(tmp_path, 0)
    p1 = _process_log(tmp_path, 1, verdict="WEDGED")
    roll = aggregate.aggregate_logs([p0, p1])
    rows = roll["hosts"]
    assert [r["process_index"] for r in rows] == [0, 1]
    assert all(r["hostname"] == "boxA" for r in rows)
    agg = roll["aggregate"]
    assert agg["processes"] == 2 and agg["hosts"] == 1
    assert agg["verdict"] == "WEDGED"  # worst verdict wins
    # fleet throughput = sum of per-process rates (0.1 Mcells * 8/s)
    per = rows[0]["throughput"]["gcells_per_s"]
    assert agg["gcells_per_s"] == pytest.approx(2 * per, rel=1e-6)
    assert len(agg["trace_ids"]) == 2  # independent runs: two traces
    assert rows[0]["time_to_first_chunk_s"] is not None


def test_serve_aggregate_status_json_per_host(tmp_path):
    p0 = _process_log(tmp_path, 0)
    p1 = _process_log(tmp_path, 1)
    server = serve.serve_aggregate([p0, p1], port=0, poll_s=0.05)
    try:
        deadline = time.monotonic() + 10
        status = None
        while time.monotonic() < deadline:
            with urllib.request.urlopen(server.url + "/status.json",
                                        timeout=5) as r:
                status = json.load(r)
            if len(status.get("hosts") or ()) == 2:
                break
            time.sleep(0.05)
        assert status and len(status["hosts"]) == 2
        assert status["aggregate"]["processes"] == 2
        # the merged single-stream payload is still there
        assert "verdict" in status and "throughput" in status
    finally:
        server.close()


# ------------------------------------------------- engine request path

def test_engine_request_span_ttfc_and_latency_histograms(tmp_path):
    from mpi_cuda_process_tpu import cli
    from mpi_cuda_process_tpu.engine import SimulationEngine

    eng = SimulationEngine(cli.run, telemetry_dir=str(tmp_path))
    h = eng.submit(RunConfig(stencil="heat2d", grid=(32, 128), iters=8,
                             log_every=2))
    h.result(timeout=120)
    assert h.timings["queue_wait_s"] >= 0
    assert h.timings["time_to_first_chunk_s"] > 0
    assert h.timings["latency_s"] >= h.timings["time_to_first_chunk_s"]

    st = h.status()
    assert st["request"]["time_to_first_chunk_s"] == \
        h.timings["time_to_first_chunk_s"]
    assert st["request"]["trace_id"] == h.trace_id
    # the log-derived ttfc (manifest -> first chunk) also lands, and
    # the Prometheus rendering of the same stream carries the gauge +
    # the per-request latency histogram
    assert st["time_to_first_chunk_s"] > 0
    rm = metrics.RunMetrics()
    for rec in _read(h.telemetry_path):
        rm.ingest(rec)
    prom = rm.registry.to_prometheus()
    assert "obs_time_to_first_chunk_s" in prom
    assert "obs_span_request_seconds" in prom

    # request span tree in the log: request root + queue_wait/result
    # children, the run's own root parented under the request
    sp = {r["name"]: r for r in _read(h.telemetry_path)
          if r["kind"] == "span"}
    assert sp["request"]["span_id"] == h.request_span_id
    assert sp["request"]["parent_id"] is None
    assert sp["queue_wait"]["parent_id"] == h.request_span_id
    assert sp["cli"]["parent_id"] == h.request_span_id
    assert sp["request"]["attrs"]["ok"] is True

    # engine-level histograms (the scheduler's admission numbers)
    snap = eng.metrics.snapshot()
    assert snap["engine_requests_total"]["value"] == 1
    assert snap["engine_time_to_first_chunk_s"]["count"] == 1
    assert snap["engine_request_latency_s"]["count"] == 1
    assert "engine_time_to_first_chunk_s" in eng.metrics.to_prometheus()
    assert eng.status()["metrics"]["engine_requests_total"]["value"] == 1


def test_engine_failed_request_still_accounted(tmp_path):
    from mpi_cuda_process_tpu import cli
    from mpi_cuda_process_tpu.engine import SimulationEngine

    eng = SimulationEngine(cli.run, telemetry_dir=str(tmp_path))
    h = eng.submit(RunConfig(stencil="heat2d", grid=(32, 128), iters=8,
                             log_every=2, fuse=3))  # 8 % 3 != 0: raises
    with pytest.raises(ValueError):
        h.result(timeout=120)
    assert h.timings["latency_s"] >= 0
    snap = eng.metrics.snapshot()
    assert snap["engine_requests_failed_total"]["value"] == 1
    sp = {r["name"]: r for r in _read(h.telemetry_path)
          if r.get("kind") == "span"}
    assert sp["request"]["attrs"]["ok"] is False


# ------------------------------------------------------------ satellites

def test_logtail_detects_truncation_and_rotation(tmp_path):
    """Satellite: a supervisor restart that reuses a telemetry path
    (TraceWriter opens 'w') must not leave the tail stuck at the old
    offset."""
    path = str(tmp_path / "t.jsonl")
    tail = trace.LogTail(path)
    with open(path, "w") as fh:
        fh.write('{"kind": "a"}\n{"kind": "b"}\n')
    assert [r["kind"] for r in tail.poll()] == ["a", "b"]
    assert tail.poll() == []

    # rotation: the path is rewritten from scratch, shorter than the
    # consumed offset — the tail must reset and read the new content
    with open(path, "w") as fh:
        fh.write('{"kind": "c"}\n')
    assert [r["kind"] for r in tail.poll()] == ["c"]
    assert tail.truncations == 1

    # an append after the reset flows normally
    with open(path, "a") as fh:
        fh.write('{"kind": "d"}\n')
    assert [r["kind"] for r in tail.poll()] == ["d"]

    # truncate-to-empty also resets (pos > size == 0)
    open(path, "w").close()
    assert tail.poll() == []
    with open(path, "a") as fh:
        fh.write('{"kind": "e"}\n')
    assert [r["kind"] for r in tail.poll()] == ["e"]
    assert tail.truncations == 2


def test_obs_top_once_is_a_health_probe(tmp_path, capsys, obs_top):
    """Satellite: --once exits nonzero on WEDGED/STALLED or give-up."""
    def log_with(events):
        path = str(tmp_path / f"h{len(os.listdir(tmp_path))}.jsonl")
        w = trace.TraceWriter(path)
        w.write_manifest(_manifest())
        for kind, payload in events:
            w.event(kind, **payload)
        w.close()
        return path

    healthy = log_with([("chunk", {"chunk": 0, "steps": 2,
                                   "wall_s": 0.1, "ms_per_step": 50.0,
                                   "recompiled": False}),
                        ("summary", {"mcells_per_s": 1.0,
                                     "runtime": {}})])
    assert obs_top.main([healthy, "--once"]) == 0

    wedged = log_with([("heartbeat", {"verdict": "WEDGED",
                                      "detail": "probe hang"})])
    assert obs_top.main([wedged, "--once"]) == 1

    gave_up = log_with([("launch", {"attempt": 0, "resume": False}),
                        ("give_up", {"attempts": 3,
                                     "reason": "wall-clock stall"})])
    assert obs_top.main([gave_up, "--once"]) == 1
    capsys.readouterr()
    # ledger sources have no run health: always 0 (the CI ledger leg)
    from mpi_cuda_process_tpu.obs import ledger as ledger_lib

    path = str(tmp_path / "ledger.jsonl")
    ledger_lib.append_rows([ledger_lib.make_row(
        "heat3d_256_f32", 100.0, source="s", backend="tpu",
        expected_backend="tpu", measured_at=1.0)], path)
    assert obs_top.main([path, "--once"]) == 0
    capsys.readouterr()


def test_ledger_best_known_exported_as_prometheus_gauges(tmp_path):
    """Satellite: the ledger and the live console are one surface."""
    from mpi_cuda_process_tpu.obs import ledger as ledger_lib

    lpath = str(tmp_path / "ledger.jsonl")
    rows = [
        ledger_lib.make_row("heat3d_512_fused4", 107.3, source="t",
                            measured_at=time.time(), backend="tpu"),
        ledger_lib.make_row("wave3d_512", 70.0, source="t",
                            measured_at=time.time(), backend="tpu"),
        # quarantined rows must never surface as gauges
        ledger_lib.make_row("dead_label", 0.0, source="t",
                            measured_at=time.time(), backend="tpu"),
    ]
    ledger_lib.append_rows(rows, lpath)

    console = serve.RunConsole()
    assert console.load_ledger(lpath) == 2
    prom = console.metrics.registry.to_prometheus()
    assert 'obs_ledger_best_known{backend="tpu",' \
           'label="heat3d_512_fused4",unit="Mcells/s"} 107.3' in prom
    assert 'label="wave3d_512"' in prom
    assert "dead_label" not in prom
    # missing ledger: served console degrades to zero baselines
    assert serve.RunConsole().load_ledger(
        str(tmp_path / "absent.jsonl")) == 0


def test_campaign_console_complete_lines_only_under_racing_writer(
        tmp_path):
    """Satellite: the directory rescan racing a writer mid-append must
    hold the complete-lines-only invariant — a torn line is never
    ingested, and it IS ingested once its terminator lands."""
    console = serve.CampaignConsole(str(tmp_path))

    # deterministic torn write: half a record, no newline
    p1 = tmp_path / "a.jsonl"
    manifest_line = json.dumps(_manifest(tool="measure")) + "\n"
    event_line = json.dumps({"schema": trace.SCHEMA_VERSION,
                             "kind": "label", "t": time.time(),
                             "label": "L0", "status": "ok"}) + "\n"
    with open(p1, "w") as fh:
        fh.write(manifest_line)
        fh.write(event_line[:len(event_line) // 2])
        fh.flush()
    console.poll()
    assert console.seq == 1  # the manifest only; the torn tail waits
    assert console.metrics.labels == {}
    with open(p1, "a") as fh:
        fh.write(event_line[len(event_line) // 2:])
    console.poll()
    assert console.seq == 2 and "L0" in console.metrics.labels

    # stress: a writer starting NEW label files (concurrent label
    # starts) while appending records byte-by-byte, racing the rescan
    n_files, per_file = 3, 20
    stop = threading.Event()

    def writer():
        for i in range(n_files):
            path = tmp_path / f"w{i}.jsonl"
            with open(path, "w") as fh:
                fh.write(json.dumps(_manifest(tool="measure")) + "\n")
                for j in range(per_file):
                    line = json.dumps(
                        {"schema": trace.SCHEMA_VERSION, "kind": "label",
                         "t": time.time(), "label": f"w{i}-{j}",
                         "status": "ok"}) + "\n"
                    mid = len(line) // 2
                    fh.write(line[:mid])
                    fh.flush()
                    time.sleep(0.001)
                    fh.write(line[mid:])
                    fh.flush()
        stop.set()

    t = threading.Thread(target=writer)
    t.start()
    while not stop.is_set():
        console.poll()
        time.sleep(0.002)
    t.join()
    console.poll()
    expected = 2 + n_files * (per_file + 1)
    assert console.seq == expected
    # every ingested record arrived whole (no half-line ever parsed):
    # all label events are present and every tail stayed well-formed
    assert sum(1 for lbl in console.metrics.labels
               if lbl.startswith("w")) == n_files * per_file
    assert all(tail.malformed == 0 for _p, tail in console._tails)
