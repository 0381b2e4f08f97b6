"""Tests for the unified telemetry layer (mpi_cuda_process_tpu/obs).

Pins the subsystem's four contracts:

* **schema** — manifest round-trip through the writer + validator;
  rejection cases name every problem; all four entry points (cli,
  bench, measure, scaling) emit logs passing ONE validator.
* **runtime** — per-chunk stats recorded at chunk boundaries only, with
  the jitted step jaxpr byte-identical with and without telemetry
  (zero ops in the hot scan — the acceptance criterion).
* **cost model** — static ppermute round/byte counters equal to what a
  TRACED sharded step actually issues (jaxpr cross-check on virtual
  devices) and, for config 5 on both mesh families, equal to
  utils/budget.py's byte-pinned slab accounting to the byte.
* **heartbeat** — an injected hang yields STALLED, the injected wedge fault
  escalates to WEDGED, resumed progress yields RECOVERED.
"""

import importlib.util
import json
import math
import os
import sys
import time

import jax
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from mpi_cuda_process_tpu import (  # noqa: E402
    driver, init_state, make_mesh, make_step, make_stencil, shard_fields,
)
from mpi_cuda_process_tpu import cli, obs  # noqa: E402
from mpi_cuda_process_tpu.obs import (  # noqa: E402
    costmodel, heartbeat, runtime, trace,
)
from mpi_cuda_process_tpu.utils import budget  # noqa: E402


# ---------------------------------------------------------------- schema

def test_manifest_roundtrip_and_latest_lookup(tmp_path, monkeypatch):
    monkeypatch.setenv("OBS_TELEMETRY_DIR", str(tmp_path))
    path = str(tmp_path / "run.jsonl")
    with trace.TraceWriter(path) as w:
        m = trace.build_manifest("cli", {"stencil": "heat2d",
                                         "grid": [32, 128]})
        w.write_manifest(m)
        w.event("chunk", chunk=0, steps=4, wall_s=0.1)
        w.event("summary", mcells_per_s=1.0)
    manifest, events = trace.validate_log(path)
    assert manifest == json.loads(json.dumps(m))  # json round-trip clean
    assert [e["kind"] for e in events] == ["chunk", "summary"]
    prov = manifest["provenance"]
    assert prov["backend"] == jax.default_backend()
    assert prov["device_count"] == len(jax.devices())
    assert isinstance(prov["jax_version"], str)


def test_validator_rejects_and_names_every_problem(tmp_path):
    good = trace.build_manifest("bench", {"grid": [16, 16]})
    trace.validate_manifest(good)

    bad = dict(good, schema=99, kind="event")
    with pytest.raises(ValueError) as ei:
        trace.validate_manifest(bad)
    msg = str(ei.value)
    assert "schema" in msg and "kind" in msg  # ALL problems, not first

    for mutate in (
        lambda m: m.pop("tool"),
        lambda m: m.__setitem__("run", "not-a-dict"),
        lambda m: m.__setitem__("created_at", None),
        lambda m: m["provenance"].pop("git_sha"),
        lambda m: m["provenance"].__setitem__("device_count", 0),
        lambda m: m["provenance"].__setitem__("builder_rev", "eight"),
    ):
        m = json.loads(json.dumps(good))
        mutate(m)
        with pytest.raises(ValueError):
            trace.validate_manifest(m)

    with pytest.raises(ValueError):  # events may not masquerade
        trace.validate_event({"schema": 1, "kind": "manifest",
                              "t": time.time()})
    # the writer enforces ordering: manifest first, exactly once
    w = trace.TraceWriter(str(tmp_path / "order.jsonl"))
    with pytest.raises(ValueError):
        w.event("chunk")
    w.write_manifest(good)
    with pytest.raises(ValueError):
        w.write_manifest(good)
    w.close()


def test_manifest_schema2_carries_multihost_provenance():
    """Satellite: schema rev 2 adds process_index / process_count /
    hostname — the multi-host prep a per-host aggregator needs."""
    m = trace.build_manifest("cli", {"grid": [16, 16]})
    assert m["schema"] == 2
    prov = m["provenance"]
    assert isinstance(prov["process_index"], int)
    assert isinstance(prov["process_count"], int) \
        and prov["process_count"] >= 1
    assert isinstance(prov["hostname"], str) and prov["hostname"]

    # the new fields are REQUIRED at schema 2 and type-checked
    for mutate in (
        lambda d: d["provenance"].pop("hostname"),
        lambda d: d["provenance"].__setitem__("process_index", "zero"),
        lambda d: d["provenance"].__setitem__("process_count", 0),
    ):
        bad = json.loads(json.dumps(m))
        mutate(bad)
        with pytest.raises(ValueError):
            trace.validate_manifest(bad)


def test_old_schema1_manifests_still_parse():
    """Satellite: the validator accepts BOTH revisions — a pre-rev log
    (schema 1, no host fields) must keep parsing."""
    old = trace.build_manifest("cli", {"grid": [16, 16]})
    old = json.loads(json.dumps(old))
    old["schema"] = 1
    for k in ("process_index", "process_count", "hostname"):
        old["provenance"].pop(k)
    trace.validate_manifest(old)  # no raise: old manifests still parse
    # schema-1 events validate too (an old log's tail)
    trace.validate_event({"schema": 1, "kind": "chunk", "t": time.time()})
    # but a schema-1 writer that DID include the fields gets them typed
    old["provenance"]["hostname"] = 42
    with pytest.raises(ValueError, match="hostname"):
        trace.validate_manifest(old)


def test_validate_log_rejects_corrupt_event(tmp_path):
    path = str(tmp_path / "bad.jsonl")
    with trace.TraceWriter(path) as w:
        w.write_manifest(trace.build_manifest("cli", {}))
    with open(path, "a") as fh:
        fh.write(json.dumps({"kind": "chunk"}) + "\n")  # no schema/t
    with pytest.raises(ValueError, match="event 0"):
        trace.validate_log(path)


# ----------------------------------------------------- entry-point logs

def _load_script(name, rel):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(REPO, rel))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def cli_log(tmp_path_factory):
    """A real CLI run with --telemetry: the canonical event log."""
    path = str(tmp_path_factory.mktemp("obs") / "cli.jsonl")
    cfg = cli.config_from_args([
        "--stencil", "heat2d", "--grid", "32,128", "--iters", "8",
        "--log-every", "2", "--telemetry", path])
    cli.run(cfg)
    return path


def test_cli_log_valid_with_chunks_cost_and_summary(cli_log):
    manifest, events = trace.validate_log(cli_log)
    assert manifest["tool"] == "cli"
    assert manifest["run"]["stencil"] == "heat2d"
    kinds = [e["kind"] for e in events]
    assert kinds.count("chunk") == 4  # 8 iters / log-every 2
    assert "costmodel" in kinds
    # the session ROOT SPAN closes every log (round 16 — its duration
    # covers the whole session, so it must be emitted last); the
    # summary is the final non-span record
    assert events[-1]["kind"] == "span" and events[-1]["name"] == "cli"
    non_span = [e for e in events if e["kind"] != "span"]
    assert non_span[-1]["kind"] == "summary"
    summary = non_span[-1]
    assert summary["runtime"]["n_chunks"] == 4
    assert summary["runtime"]["steps"] == 8
    assert summary["runtime"]["steady"]["ms_per_step_p50"] > 0
    # compile separated from steady state: first chunk strictly slower
    assert summary["runtime"]["first_chunk_ms_per_step"] > \
        summary["runtime"]["steady"]["ms_per_step_p50"]
    assert summary["mcells_per_s"] > 0


def test_scaling_emits_same_schema(tmp_path):
    scaling = _load_script("scaling_obs", "benchmarks/scaling.py")
    path = str(tmp_path / "scaling.jsonl")
    rc = scaling.main([
        "--mode", "weak", "--stencil", "heat2d", "--block", "16,16",
        "--steps", "2", "--reps", "1",
        "--virtual", str(len(jax.devices())), "--telemetry", path])
    assert rc == 0
    manifest, events = trace.validate_log(path)
    assert manifest["tool"] == "scaling"
    rungs = [e for e in events if e["kind"] == "rung"]
    assert len(rungs) == int(math.log2(len(jax.devices()))) + 1
    non_span = [e for e in events if e["kind"] != "span"]
    assert non_span[-1]["kind"] == "summary"


def test_measure_emits_same_schema(tmp_path, monkeypatch):
    measure = _load_script("measure_obs", "benchmarks/measure.py")
    monkeypatch.setattr(measure, "CONFIGS", [
        ("heat2d_tiny", "heat2d", (16, 128), 2, "float32", "jnp")])
    out = str(tmp_path / "results.json")
    path = str(tmp_path / "measure.jsonl")
    monkeypatch.setattr(sys, "argv", [
        "measure.py", "--in-process", "--out", out, "--telemetry", path])
    measure.main()
    manifest, events = trace.validate_log(path)
    assert manifest["tool"] == "measure"
    labels = [e for e in events if e["kind"] == "label"]
    assert [e["label"] for e in labels] == ["heat2d_tiny"]
    assert labels[0]["status"] in ("ok", "error")  # noise floor may trip
    non_span = [e for e in events if e["kind"] != "span"]
    assert non_span[-1]["kind"] == "summary"
    assert non_span[-1]["labels_run"] == 1


def test_bench_telemetry_manifest(tmp_path, monkeypatch):
    """The round-gate bench is itself provenance-stamped evidence: its
    headline record lands as the one result event of a bench manifest."""
    monkeypatch.setenv("OBS_TELEMETRY_DIR", str(tmp_path))
    import bench

    rec = {"metric": "m", "value": 1.0}
    tel = bench._write_bench_telemetry(rec, (16, 16, 16), 2, 0, "cpu")
    assert tel is not None
    manifest, events = trace.validate_log(tel)
    assert manifest["tool"] == "bench"
    assert events[0]["kind"] == "result" and events[0]["value"] == 1.0
    json.dumps(rec)  # driver-visible record stays one JSON line


# ------------------------------------------------------------- runtime

def test_telemetry_adds_zero_ops_to_jitted_step(tmp_path):
    """Acceptance criterion: the jitted step/scan is byte-identical with
    and without telemetry — events exist only at chunk boundaries."""
    st = make_stencil("heat2d")
    fields = init_state(st, (16, 128), seed=0, kind="pulse")
    step = make_step(st, (16, 128))
    abstract = tuple(jax.ShapeDtypeStruct(f.shape, f.dtype) for f in fields)
    jaxpr_before = str(jax.make_jaxpr(step)(abstract))
    runner_jaxpr_before = str(
        jax.make_jaxpr(driver.make_runner(step, 4, jit=False))(abstract))

    path = str(tmp_path / "zero.jsonl")
    with trace.TraceWriter(path) as w:
        w.write_manifest(trace.build_manifest("cli", {}))
        rec = runtime.RuntimeRecorder(trace=w)
        out = driver.run_simulation(
            st, fields, 8, step_fn=step, log_every=2,
            callback=lambda done, fs: None, observer=rec)
        assert len(rec.chunks) == 4

    # telemetry active changed NOTHING about the traced program
    assert str(jax.make_jaxpr(step)(abstract)) == jaxpr_before
    runner_jaxpr_after = str(
        jax.make_jaxpr(driver.make_runner(step, 4, jit=False))(abstract))
    assert runner_jaxpr_after == runner_jaxpr_before
    # and no host-callback primitive anywhere in the executed program
    for prim in ("pure_callback", "io_callback", "debug_callback",
                 "outside_call"):
        assert prim not in runner_jaxpr_after
    assert out[0].shape == fields[0].shape


def test_serve_zero_ops_and_scrape_mid_run(tmp_path):
    """Acceptance criterion: --serve adds zero ops to the jitted step
    (the telemetry-invariance pin extended) and the server never blocks
    the run loop — /metrics and /status.json answer MID-RUN, from a
    chunk-boundary callback, while the scan is in flight."""
    import urllib.request

    from mpi_cuda_process_tpu.obs import serve as serve_lib

    st = make_stencil("heat2d")
    fields = init_state(st, (16, 128), seed=0, kind="pulse")
    step = make_step(st, (16, 128))
    abstract = tuple(jax.ShapeDtypeStruct(f.shape, f.dtype) for f in fields)
    jaxpr_before = str(jax.make_jaxpr(step)(abstract))
    runner_jaxpr_before = str(
        jax.make_jaxpr(driver.make_runner(step, 4, jit=False))(abstract))

    path = str(tmp_path / "served.jsonl")
    session = obs.open_session(path, "cli", {"grid": [16, 128]},
                               with_heartbeat=False)
    server = serve_lib.serve_run(path, port=0, poll_s=0.05)
    scraped = {}

    def callback(done, fs):
        if done != 4 or scraped:
            return  # scrape once, mid-run (2 of 4 chunks left)
        deadline = time.time() + 10
        while time.time() < deadline and "metrics" not in scraped:
            try:
                with urllib.request.urlopen(server.url + "/metrics",
                                            timeout=5) as r:
                    scraped["metrics"] = r.read().decode()
                with urllib.request.urlopen(server.url + "/status.json",
                                            timeout=5) as r:
                    scraped["status"] = json.loads(r.read().decode())
            except OSError:
                time.sleep(0.1)

    try:
        driver.run_simulation(st, fields, 8, step_fn=step, log_every=2,
                              callback=callback, observer=session.recorder)
        session.finish()
    finally:
        session.close()
        server.close()

    assert "metrics" in scraped, "mid-run scrape never succeeded"
    assert "obs_run_info" in scraped["metrics"]
    assert scraped["status"]["manifest"]["tool"] == "cli"
    # the served run traced the SAME program: zero ops added
    assert str(jax.make_jaxpr(step)(abstract)) == jaxpr_before
    assert str(jax.make_jaxpr(
        driver.make_runner(step, 4, jit=False))(abstract)) == \
        runner_jaxpr_before


def test_recorder_separates_compile_flags_recompiles_and_percentiles():
    rec = runtime.RuntimeRecorder(step_unit=4)
    rec.begin_chunk()
    rec.record_chunk(2, 1.0)  # compile chunk: 8 real steps
    for s in (0.08, 0.10, 0.12, 0.10):
        rec.begin_chunk()
        rec.record_chunk(2, s)
    s = rec.summary()
    assert s["n_chunks"] == 5 and s["steps"] == 40
    assert s["first_chunk_s"] == 1.0
    assert s["steady"]["chunks"] == 4
    assert s["steady"]["ms_per_step_best"] == pytest.approx(10.0)
    assert s["steady"]["ms_per_step_p50"] == pytest.approx(12.5)
    assert s["recompiles"] == 0
    # an injected compile event mid-steady-state flags that chunk and
    # excludes it from the percentiles
    rec.begin_chunk()
    for _ in range(3):
        runtime._on_duration(runtime.BACKEND_COMPILE_EVENT, 0.0)
    chunk = rec.record_chunk(2, 5.0)
    assert chunk["recompiled"] is True
    s2 = rec.summary()
    assert s2["recompiles"] == 3
    assert s2["steady"]["chunks"] == 4  # the recompiled chunk excluded


# ------------------------------------------------------------ heartbeat

class _ListTrace:
    def __init__(self):
        self.events = []

    def event(self, kind, **payload):
        self.events.append(dict(kind=kind, **payload))


def _wait_for(pred, timeout=5.0):
    t0 = time.monotonic()
    while time.monotonic() - t0 < timeout:
        if pred():
            return True
        time.sleep(0.01)
    return False


def test_heartbeat_stall_escalation_and_recovery(monkeypatch):
    """Injected hang -> STALLED -> (injected wedge) -> WEDGED; progress
    resumes -> RECOVERED; one verdict per episode, no event spam."""
    monkeypatch.setenv("FAULT_INJECT", "heartbeat:wedge")
    progress = [time.monotonic()]
    tr = _ListTrace()
    hb = heartbeat.Heartbeat(lambda: progress[0], trace=tr,
                             stall_after_s=0.15, poll_s=0.03)
    hb.start()
    try:
        assert _wait_for(lambda: any(
            e["verdict"] == "WEDGED" for e in tr.events))
        verdicts = [e["verdict"] for e in tr.events]
        assert verdicts[0] == "STALLED"  # stall first, then escalation
        assert hb.last_verdict["verdict"] == "WEDGED"
        n_after_episode = len(tr.events)
        time.sleep(0.2)  # still stalled: same episode, no new events
        assert len(tr.events) == n_after_episode
        progress[0] = time.monotonic()  # inject recovery
        assert _wait_for(lambda: any(
            e["verdict"] == "RECOVERED" for e in tr.events))
    finally:
        hb.stop()


def test_heartbeat_stall_without_wedge_stays_stalled(monkeypatch):
    """No subprocess probe: a stall is reported in-process as STALLED and
    is never escalated without the fault seam."""
    monkeypatch.delenv("FAULT_INJECT", raising=False)
    progress = [time.monotonic() - 100.0]  # born stalled
    tr = _ListTrace()
    hb = heartbeat.Heartbeat(
        lambda: progress[0], trace=tr, stall_after_s=0.1, poll_s=0.03)
    hb.start()
    try:
        assert _wait_for(lambda: bool(tr.events))
        time.sleep(0.1)
        assert [e["verdict"] for e in tr.events] == ["STALLED"]
        assert hb.last_verdict["verdict"] == "STALLED"  # not WEDGED
    finally:
        hb.stop()


# ------------------------------------------------------------ costmodel

def test_config5_counters_match_budget_to_the_byte():
    """Acceptance criterion: static ppermute/byte counters for config 5
    (wave3d 4096^3, k=4) equal budget.py's slab accounting exactly, on
    the z-ring AND the balanced mesh, for the stream and padfree kinds."""
    st = make_stencil("wave3d")
    grid = (4096,) * 3
    # (mesh, kind) -> (rounds/pass, ici bytes/pass, operand bytes)
    expect = {
        ((64, 1, 1), "stream"): (4, 1_073_741_824, 1_073_741_824),
        ((64, 1, 1), "padfree"): (4, 1_073_741_824, 1_073_741_824),
        ((8, 8, 1), "stream"): (16, 270_532_608, 543_162_368),
        ((8, 8, 1), "padfree"): (16, 270_532_608, 406_847_488),
    }
    for (mesh, kind), (rounds, ici, operand) in expect.items():
        cs = costmodel.comm_stats(st, grid, mesh, fuse=4, fuse_kind=kind)
        assert cs["ppermute_rounds_per_pass"] == rounds, (mesh, kind)
        assert cs["ici_bytes_per_pass"] == ici, (mesh, kind)
        assert cs["slab_operand_bytes"] == operand, (mesh, kind)
        # equal to budget.py's own arithmetic, extracted from its parts
        _, parts = budget.estimate_run_bytes(
            st, grid, mesh=mesh, fuse=4, fuse_kind=kind)
        slab = [b for label, b in parts if "operands only" in label]
        assert slab == [operand], (mesh, kind)
        cc = costmodel.budget_crosscheck(st, grid, mesh, 4, kind)
        assert cc == {"slab_operand_bytes": operand,
                      "budget_bytes": operand, "match": True}


def _traced_comm(name, grid, mesh_shape, k=0, **kw):
    st = make_stencil(name)
    mesh = make_mesh(mesh_shape)
    if k:
        from mpi_cuda_process_tpu.parallel.stepper import (
            make_sharded_fused_step,
        )

        step = make_sharded_fused_step(st, mesh, grid, k, interpret=True,
                                       **kw)
    else:
        from mpi_cuda_process_tpu.parallel.stepper import make_sharded_step

        step = make_sharded_step(st, mesh, grid)
    assert step is not None, (name, grid, mesh_shape, kw)
    fields = shard_fields(init_state(st, grid, seed=1, kind="pulse"),
                          mesh, st.ndim)
    return costmodel.comm_stats_from_jaxpr(jax.make_jaxpr(step)(fields))


@pytest.mark.parametrize("name,grid,mesh,k,kw,kind", [
    # z-only slab kernels: 2 rounds per exchanged field of (m, ly, lx)
    ("heat3d", (32, 16, 128), (2, 1, 1), 4, {"padfree": True}, "padfree"),
    ("wave3d", (32, 16, 128), (2, 1, 1), 4, {"padfree": True}, "padfree"),
    # 2-axis slab kernels: + 2 y-rounds and 4 two-pass corner rounds
    ("heat3d", (32, 32, 128), (2, 2, 1), 4, {"padfree": True}, "padfree"),
    ("heat3d", (48, 32, 128), (2, 2, 1), 4, {"kind": "stream"}, "stream"),
    # padded sharded fused: two-pass exchange_and_pad at width m
    ("heat3d", (32, 32, 128), (2, 2, 1), 4, {}, "auto"),
])
def test_comm_model_matches_traced_sharded_fused_step(
        name, grid, mesh, k, kw, kind):
    """The analytic exchange model equals what the built stepper
    actually issues — rounds AND bytes, read off the traced jaxpr."""
    st = make_stencil(name)
    got = _traced_comm(name, grid, mesh, k, **kw)
    want = costmodel.comm_stats(st, grid, mesh, fuse=k, fuse_kind=kind)
    assert got["ppermute_rounds"] == want["ppermute_rounds_per_pass"]
    assert got["ppermute_bytes"] == want["ici_bytes_per_pass"]


def test_comm_model_matches_traced_plain_sharded_step():
    """fuse=0: per-field halo widths (wave's u_prev has halo 0 and must
    not be priced) through the two-pass exchange_and_pad scheme."""
    for name, grid, mesh in (("heat3d", (16, 16, 128), (2, 2, 1)),
                             ("wave3d", (16, 16, 128), (2, 2, 1)),
                             ("heat3d", (16, 16, 128), (2, 1, 1))):
        st = make_stencil(name)
        got = _traced_comm(name, grid, mesh)
        want = costmodel.comm_stats(st, grid, mesh)
        assert got["ppermute_rounds"] == \
            want["ppermute_rounds_per_pass"], (name, mesh)
        assert got["ppermute_bytes"] == want["ici_bytes_per_pass"], \
            (name, mesh)


def test_step_flops_counter_pinned():
    """The flop counter is a pinned model: exact values, linear scaling."""
    h3 = make_stencil("heat3d")
    assert costmodel.step_flops(h3, (8, 8, 128)) == 98_304
    assert costmodel.step_flops(h3, (16, 16, 128)) == 393_216  # 4x cells
    assert costmodel.step_flops(make_stencil("life"), (16, 128)) == 18_432
    # flops land in static_cost per-device (local block), with roofline
    sc = costmodel.static_cost(h3, (16, 16, 128), mesh=(2, 1, 1))
    assert sc["flops_per_step_per_device"] == \
        costmodel.step_flops(h3, (8, 16, 128))
    assert sc["hbm_bytes_per_step_per_device"] == 2 * 8 * 16 * 128 * 4
    # no roofline for a device the peaks table does not know (the CPU)
    assert sc["roofline"] is None
    v5e = costmodel.DEVICE_PEAKS["TPU v5 lite"]
    sc_v5e = costmodel.static_cost(h3, (16, 16, 128), mesh=(2, 1, 1),
                                   **v5e)
    assert sc_v5e["roofline"]["predicted_mcells_per_s_overlapped"] > 0
    assert sc["comm"]["ppermute_rounds_per_pass"] == 2


def test_static_cost_fuse_divides_hbm_traffic():
    st = make_stencil("heat3d")
    plain = costmodel.static_cost(st, (32, 32, 128))
    fused = costmodel.static_cost(st, (32, 32, 128), fuse=4)
    assert plain["hbm_bytes_per_step_per_device"] == \
        4 * fused["hbm_bytes_per_step_per_device"]


# ----------------------------------------------------- session & report

def test_session_error_event_and_finish_idempotent(tmp_path):
    path = str(tmp_path / "err.jsonl")
    with pytest.raises(RuntimeError):
        with obs.open_session(path, "cli", {"x": 1},
                              with_heartbeat=False):
            raise RuntimeError("boom")
    manifest, events = trace.validate_log(path)
    non_span = [e for e in events if e["kind"] != "span"]
    assert non_span[-1]["kind"] == "error"
    assert "boom" in non_span[-1]["error"]

    path2 = str(tmp_path / "fin.jsonl")
    s = obs.open_session(path2, "cli", {}, with_heartbeat=False)
    s.finish(mcells_per_s=1.0)
    s.finish(mcells_per_s=2.0)  # idempotent: second call is a no-op
    s.close()
    _, events = trace.validate_log(path2)
    # exactly one summary, then the root span (round 16) closes the log
    assert [e["kind"] for e in events] == ["summary", "span"]
    assert events[0]["mcells_per_s"] == 1.0


def test_obs_report_renders_attribution_and_checks(cli_log, tmp_path,
                                                   capsys):
    report = _load_script("obs_report_t", "scripts/obs_report.py")
    assert report.main([cli_log, "--check"]) == 0
    out = capsys.readouterr().out
    assert "obs_report --check: ok" in out
    assert "manifest  tool=cli" in out
    assert "attribution (predicted vs measured)" in out
    assert "TOTAL overlapped" in out
    assert "steady" in out
    # an invalid log fails --check with a nonzero rc
    bad = tmp_path / "bad.jsonl"
    bad.write_text('{"kind": "manifest"}\n')
    assert report.main([str(bad), "--check"]) == 1


def test_obs_report_renders_supervisor_trail(tmp_path, capsys):
    """Satellite: a tool="supervisor" log renders its launch/restart/
    give-up trail (with resumed_from_step) instead of the empty and
    misleading chunk-attribution table."""
    report = _load_script("obs_report_sup_t", "scripts/obs_report.py")
    path = str(tmp_path / "sup.supervisor.jsonl")
    with trace.TraceWriter(path) as w:
        w.write_manifest(trace.build_manifest(
            "supervisor", {"stencil": "life", "grid": [64, 64]}))
        w.event("launch", attempt=0, resume=False, resumed_from_step=None)
        w.event("restart", attempt=0, reason="heartbeat verdict WEDGED",
                detail="injected", backoff_s=0.2, checkpoint_step=30)
        w.event("launch", attempt=1, resume=True, resumed_from_step=30)
        w.event("summary", ok=True, attempts=2, restarts=1,
                resumed_from_step=30)
    assert report.main([path]) == 0
    out = capsys.readouterr().out
    assert "supervisor trail (2 launch(es), 1 restart(s))" in out
    assert "heartbeat verdict WEDGED" in out
    assert "resume" in out and "30" in out
    assert "supervisor summary: ok=True" in out
    # the misleading blocks are gone: no empty attribution table
    assert "attribution (predicted vs measured)" not in out
    assert "runtime  chunks=" not in out

    # a give-up trail renders too (the other way a supervisor ends)
    path2 = str(tmp_path / "gu.supervisor.jsonl")
    with trace.TraceWriter(path2) as w:
        w.write_manifest(trace.build_manifest("supervisor", {}))
        w.event("launch", attempt=0, resume=False, resumed_from_step=None)
        w.event("give_up", attempts=1, reason="wall-clock stall",
                restarts=0)
    assert report.main([path2]) == 0
    assert "GIVE UP" in capsys.readouterr().out
