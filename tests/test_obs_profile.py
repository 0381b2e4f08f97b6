"""Tests for device-trace attribution (mpi_cuda_process_tpu/obs/profile).

All on synthetic fixtures (trace events, and one ``.xplane.pb`` built
from a text proto) — no TPU required.  Pins:

* **parser buckets** — device-lane selection (host lanes never counted),
  comm-vs-compute classification, interval-union math with nested and
  overlapping events;
* **overlap-efficiency arithmetic** — 1 - exposed/total over constructed
  interval layouts (fully hidden, fully exposed, partial, no-comm);
* **honest degradation** — CPU/host-only traces and empty profile dirs
  yield ``attribution: unavailable`` with a reason, never zeros;
* **chunk scoping** — the profiler starts/stops exactly once, at the
  target chunk's boundaries, through the driver's observer hook; and
  the telemetry invariant extends to it: the step/runner jaxpr is
  byte-identical with a profiler attached (zero ops in the scan);
* **CLI wiring** — ``--profile`` composes with ``--telemetry`` (a
  ``profile`` event lands in the log) and refuses ``--tol`` /
  ``--profile-dir`` combinations.
"""

import os
import sys

import jax
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from mpi_cuda_process_tpu import (  # noqa: E402
    cli, driver, init_state, make_step, make_stencil,
)
from mpi_cuda_process_tpu.obs import profile, runtime, trace  # noqa: E402


def _meta(pid, name):
    return {"ph": "M", "pid": pid, "name": "process_name",
            "args": {"name": name}}


def _ev(pid, name, ts, dur, tid=0):
    return {"ph": "X", "pid": pid, "tid": tid, "name": name,
            "ts": float(ts), "dur": float(dur)}


def _trace(events):
    """A minimal two-process trace: pid 1 = TPU device, pid 9 = host."""
    return [_meta(1, "/device:TPU:0"), _meta(9, "/host:CPU")] + events


# ------------------------------------------------------- parser buckets

def test_device_pid_selection_excludes_host_and_cpu_devices():
    events = [_meta(1, "/device:TPU:0"), _meta(2, "/device:CPU:0"),
              _meta(9, "/host:CPU"), _meta(3, "python")]
    assert profile.device_pids(events) == [1]


def test_comm_classification():
    for name in ("ppermute", "collective-permute.1", "fusion.all-reduce",
                 "send-done.2", "recv.3", "all-to-all"):
        assert profile.is_comm_event(name), name
    for name in ("fusion.17", "add.3", "copy.1", "while", "scan_body"):
        assert not profile.is_comm_event(name), name


def test_attribution_buckets_and_union_math():
    # compute lanes: [0,10) and a NESTED sub-event [2,6) (must not
    # double-count) plus a second lane [8,14) overlapping the first
    events = _trace([
        _ev(1, "fusion.1", 0, 10, tid=0),
        _ev(1, "fusion.1.inner", 2, 4, tid=0),
        _ev(1, "fusion.2", 8, 6, tid=1),
        # comm: [4,9) hidden under compute, [14,18) fully exposed
        _ev(1, "collective-permute.1", 4, 5, tid=2),
        _ev(1, "collective-permute.2", 14, 4, tid=2),
        # host noise that must not be attributed
        _ev(9, "python collective-permute wrapper", 0, 100),
    ])
    att = profile.attribute_events(events)
    assert att["attribution"] == "ok"
    assert att["n_device_events"] == 5
    assert att["compute_us"] == pytest.approx(14.0)   # [0,14)
    assert att["comm_us"] == pytest.approx(9.0)       # [4,9) + [14,18)
    assert att["exposed_comm_us"] == pytest.approx(4.0)
    assert att["device_busy_us"] == pytest.approx(18.0)
    assert att["overlap_efficiency"] == pytest.approx(1 - 4 / 9, abs=1e-4)


def test_overlap_efficiency_extremes():
    fully_hidden = _trace([
        _ev(1, "fusion", 0, 10),
        _ev(1, "ppermute", 2, 3, tid=1),
    ])
    att = profile.attribute_events(fully_hidden)
    assert att["overlap_efficiency"] == pytest.approx(1.0)
    assert att["exposed_comm_us"] == 0.0

    fully_serial = _trace([
        _ev(1, "fusion", 0, 10),
        _ev(1, "ppermute", 10, 5, tid=1),
    ])
    att = profile.attribute_events(fully_serial)
    assert att["overlap_efficiency"] == pytest.approx(0.0)
    assert att["exposed_comm_us"] == pytest.approx(5.0)


def test_no_comm_yields_none_not_perfect_hiding():
    att = profile.attribute_events(_trace([_ev(1, "fusion", 0, 10)]))
    assert att["attribution"] == "ok"
    assert att["overlap_efficiency"] is None
    assert att["comm_us"] == 0.0


def test_host_only_trace_is_unavailable():
    events = [_meta(9, "/host:CPU"), _ev(9, "python stuff", 0, 100)]
    att = profile.attribute_events(events)
    assert att["attribution"] == "unavailable"
    assert "no device lanes" in att["reason"]


def test_device_lane_without_events_is_unavailable():
    att = profile.attribute_events(_trace([]))
    assert att["attribution"] == "unavailable"
    assert "no complete events" in att["reason"]


# -------------------------------------------------------------- file IO

_XSPACE = """
planes {
  id: 1
  name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 10000000 }
    events { metadata_id: 2 offset_ps: 0 duration_ps: 4000000 }
    events { metadata_id: 3 offset_ps: 4000000 duration_ps: 6000000 } }
  lines { id: 2 name: "Async XLA Ops" timestamp_ns: 0
    events { metadata_id: 4 offset_ps: 8000000 duration_ps: 4000000 } }
  event_metadata { key: 1 value { id: 1
    name: "%while.1 = (f32[8]) while(f32[8] %p), body=%b" } }
  event_metadata { key: 2 value { id: 2
    name: "%fusion.1 = f32[8] fusion(f32[8] %p)" } }
  event_metadata { key: 3 value { id: 3
    name: "%fusion.2 = f32[8] fusion(f32[8] %collective-permute-done.1)" } }
  event_metadata { key: 4 value { id: 4
    name: "%collective-permute-start.1 = (f32[8]) collective-permute-start(f32[8] %fusion.2)" } }
}
planes { id: 2 name: "/host:CPU"
  lines { id: 1 name: "python" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 50000000 } }
  event_metadata { key: 1 value { id: 1 name: "sim.chunk" } }
}
"""


def test_load_trace_events_gz_roundtrip(tmp_path):
    """The newest .xplane.pb read back: leaf ops only (the while op
    holding the fusions drops out), collectives judged by their own
    name (a fusion reading a collective's output is compute)."""
    from jax.profiler import ProfileData

    run_dir = tmp_path / "plugins" / "profile" / "2026_08_04"
    run_dir.mkdir(parents=True)
    (run_dir / "host.xplane.pb").write_bytes(
        ProfileData.text_proto_to_serialized_xspace(_XSPACE))
    events = profile.load_trace_events(str(tmp_path))
    ops = [e for e in events if e["ph"] == "X"]
    assert [e["name"].split(" ")[0] for e in ops] == [
        "%fusion.1", "%fusion.2", "%collective-permute-start.1"]
    att = profile.attribution_record(str(tmp_path), profiled_chunk=1)
    assert att["attribution"] == "ok" and att["profiled_chunk"] == 1
    # compute 0-10 us, the collective 8-12 us: 2 us of it exposed
    assert att["compute_us"] == pytest.approx(10.0)
    assert att["comm_us"] == pytest.approx(4.0)
    assert att["exposed_comm_us"] == pytest.approx(2.0)


def test_attribution_record_degradations(tmp_path):
    empty = profile.attribution_record(str(tmp_path), profiled_chunk=1)
    assert empty["attribution"] == "unavailable"
    assert "no .xplane.pb" in empty["reason"]

    never = profile.attribution_record(str(tmp_path), profiled_chunk=None)
    assert never["attribution"] == "unavailable"
    assert "no chunk" in never["reason"]

    err = profile.attribution_record(str(tmp_path), profiled_chunk=0,
                                     error="RuntimeError: boom")
    assert err["attribution"] == "unavailable"
    assert "profiler error" in err["reason"]
    # every degradation formats without raising
    for rec in (empty, never, err):
        assert "unavailable" in profile.format_attribution(rec)


# --------------------------------------------------------- chunk scoping

class _StubProfiler(profile.ChunkProfiler):
    """ChunkProfiler with recorded start/stop calls (no jax.profiler)."""

    def __init__(self, outdir, target_chunk=1):
        self.calls = []
        super().__init__(
            outdir, target_chunk,
            start=lambda d: self.calls.append(("start", d)),
            stop=lambda: self.calls.append(("stop",)))


def test_chunk_profiler_scopes_exactly_the_target_chunk(tmp_path):
    prof = _StubProfiler(str(tmp_path / "prof"), target_chunk=1)
    rec = runtime.RuntimeRecorder(profiler=prof)
    for i in range(4):
        rec.begin_chunk()
        rec.record_chunk(2, 0.01)
    starts = [c for c in prof.calls if c[0] == "start"]
    stops = [c for c in prof.calls if c[0] == "stop"]
    assert len(starts) == 1 and len(stops) == 1
    assert prof.profiled_chunk == 1
    assert rec.chunks[1]["profiled"] is True
    assert all("profiled" not in rec.chunks[i] for i in (0, 2, 3))


def test_chunk_profiler_close_stops_an_open_trace(tmp_path):
    prof = _StubProfiler(str(tmp_path / "prof"), target_chunk=0)
    prof.begin_chunk(0)
    assert prof.active
    prof.close()
    assert not prof.active
    assert prof.calls[-1] == ("stop",)
    prof.close()  # idempotent
    assert prof.calls.count(("stop",)) == 1


def test_profiler_failure_is_recorded_never_raised(tmp_path):
    def boom(_d):
        raise RuntimeError("profiler exploded")

    prof = profile.ChunkProfiler(str(tmp_path), target_chunk=0,
                                 start=boom, stop=lambda: None)
    assert prof.begin_chunk(0) is False
    assert "profiler exploded" in prof.error
    rec = profile.attribution_record(str(tmp_path), profiled_chunk=None,
                                     error=prof.error)
    assert rec["attribution"] == "unavailable"


def test_profiled_run_keeps_step_jaxpr_byte_identical(tmp_path):
    """The telemetry zero-ops invariant extends to --profile: with a
    profiler attached (observer-only chunking, no callback), the traced
    step and runner programs are unchanged."""
    st = make_stencil("heat2d")
    fields = init_state(st, (16, 128), seed=0, kind="pulse")
    step = make_step(st, (16, 128))
    abstract = tuple(jax.ShapeDtypeStruct(f.shape, f.dtype) for f in fields)
    jaxpr_before = str(jax.make_jaxpr(step)(abstract))
    runner_before = str(
        jax.make_jaxpr(driver.make_runner(step, 2, jit=False))(abstract))

    prof = _StubProfiler(str(tmp_path / "prof"), target_chunk=1)
    rec = runtime.RuntimeRecorder(profiler=prof)
    out = driver.run_simulation(st, fields, 8, step_fn=step,
                                log_every=2, observer=rec)
    assert len(rec.chunks) == 4  # observer alone chunks the run
    assert prof.profiled_chunk == 1

    assert str(jax.make_jaxpr(step)(abstract)) == jaxpr_before
    assert str(jax.make_jaxpr(
        driver.make_runner(step, 2, jit=False))(abstract)) == runner_before
    assert out[0].shape == fields[0].shape


# ------------------------------------------------------------ CLI wiring

def test_cli_profile_composes_with_telemetry(tmp_path):
    log = str(tmp_path / "run.jsonl")
    prof_dir = str(tmp_path / "prof")
    cfg = cli.config_from_args([
        "--stencil", "heat2d", "--grid", "32,128", "--iters", "8",
        "--telemetry", log, "--profile", prof_dir])
    cli.run(cfg)
    manifest, events = trace.validate_log(log)
    assert manifest["run"]["profile"] == prof_dir
    profs = [e for e in events if e["kind"] == "profile"]
    assert len(profs) == 1
    p = profs[0]
    # a chunk was scoped even with no --log-every (synthesized boundary)
    assert p["profiled_chunk"] == 1
    chunks = [e for e in events if e["kind"] == "chunk"]
    assert len(chunks) == 2 and chunks[1].get("profiled") is True
    # CPU backend: host-only trace (or none) => explicit degradation,
    # never fabricated zeros
    assert p["attribution"] == "unavailable"
    assert p["reason"]
    non_span = [e for e in events if e["kind"] != "span"]
    assert non_span[-1]["kind"] == "summary"


def test_cli_profile_without_telemetry_still_runs(tmp_path):
    cfg = cli.config_from_args([
        "--stencil", "heat2d", "--grid", "32,128", "--iters", "4",
        "--profile", str(tmp_path / "prof")])
    fields, mcells = cli.run(cfg)
    assert mcells > 0


def test_cli_profile_exclusions():
    with pytest.raises(ValueError, match="while_loop"):
        cli.run(cli.config_from_args([
            "--stencil", "heat2d", "--grid", "32,128", "--iters", "4",
            "--tol", "1e-9", "--profile", "/tmp/x"]))
    with pytest.raises(ValueError, match="nesting"):
        cli.run(cli.config_from_args([
            "--stencil", "heat2d", "--grid", "32,128", "--iters", "4",
            "--profile", "/tmp/x", "--profile-dir", "/tmp/y"]))
