"""The run supervisor (resilience/): restart, backoff, give-up, bit-match.

Two layers, matching the supervisor's design:

* **unit** — the restart loop (``supervise``/``watch_child``) against
  fake children and injected clocks/sleeps: backoff sequencing,
  kill-on-verdict, kill-on-stall, give-up-after-max-restarts, resume
  flag threading.  No subprocesses, no sleeps — each decision is a pure
  function of the fakes.
* **end-to-end** — a real supervised CLI run with an injected mid-run
  wedge (``FAULT_INJECT=exchange:step=40:hang``): the wedge is
  detected, the child killed, the run resumed from the surviving
  checkpoint, and the FINAL FIELDS BIT-MATCH an uninterrupted run of
  the same config/seed — the acceptance criterion, pinned here in the
  default tier.

Plus the satellites that ride the same machinery: the fault-spec
parser, ``Heartbeat.stop()``'s SUPERVISOR_KILL contract, the
``to_argv`` round-trip (a RunConfig field that forgets its CLI flag
would silently vanish from supervised children), and the LogTail
partial-line discipline (a child SIGKILLed mid-write must not feed the
watcher garbage).
"""

import dataclasses
import json
import os
import sys

import numpy as np
import pytest

from mpi_cuda_process_tpu.config import RunConfig, to_argv
from mpi_cuda_process_tpu.obs import trace as trace_lib
from mpi_cuda_process_tpu.resilience import faults
from mpi_cuda_process_tpu.resilience import supervisor as sup


@pytest.fixture(autouse=True)
def _fresh_faults():
    faults.reset()
    yield
    faults.reset()


# ------------------------------------------------------------- faults

def test_fault_spec_parsing_rejects_malformed():
    for bad in ("exchange", "nosite:sigkill", "exchange:noaction",
                "exchange:bogus=1:sigkill", "exchange:wedge",
                "heartbeat:sigkill:wedge:extra=1"):
        with pytest.raises(ValueError):
            faults.parse_specs(bad)
    assert faults.parse_specs("") == []


def test_fault_attempt_gating(monkeypatch):
    monkeypatch.setenv("FAULT_INJECT", "exchange:step=5:raise")
    monkeypatch.setenv("FAULT_ATTEMPT", "1")
    faults.maybe_fire("exchange", step=50)  # attempt 1: spec inactive
    monkeypatch.setenv("FAULT_ATTEMPT", "0")
    faults.maybe_fire("exchange", step=4)  # below the step gate
    with pytest.raises(faults.FaultInjected):
        faults.maybe_fire("exchange", step=5)
    faults.maybe_fire("exchange", step=500)  # one-shot: already fired


def test_fault_always_and_phase_and_name(monkeypatch):
    monkeypatch.setenv(
        "FAULT_INJECT",
        "checkpoint:during_write:always:raise,label:name=tgt:raise")
    monkeypatch.setenv("FAULT_ATTEMPT", "7")  # 'always' ignores attempts
    faults.maybe_fire("checkpoint", step=10, phase="before_write")
    with pytest.raises(faults.FaultInjected):
        faults.maybe_fire("checkpoint", step=10, phase="during_write")
    faults.maybe_fire("label", name="other")
    monkeypatch.setenv("FAULT_ATTEMPT", "0")
    with pytest.raises(faults.FaultInjected):
        faults.maybe_fire("label", name="tgt")


def test_fault_injected_heartbeat_verdict(monkeypatch):
    assert faults.injected_heartbeat_verdict() is None
    monkeypatch.setenv("FAULT_INJECT", "heartbeat:wedge")
    v = faults.injected_heartbeat_verdict()
    assert v["verdict"] == "WEDGED"
    monkeypatch.setenv("FAULT_ATTEMPT", "1")  # gated off the relaunch
    assert faults.injected_heartbeat_verdict() is None


# ----------------------------------------------------------- heartbeat

class _Trace:
    def __init__(self, raise_on_event=False):
        self.events = []
        self.raise_on_event = raise_on_event

    def event(self, kind, **payload):
        if self.raise_on_event:
            raise OSError("writer closed")
        self.events.append({"kind": kind, **payload})


def test_heartbeat_stop_closes_open_episode_with_supervisor_kill():
    from mpi_cuda_process_tpu.obs.heartbeat import Heartbeat

    tr = _Trace()
    hb = Heartbeat(lambda: 0.0, trace=tr, stall_after_s=9999)
    hb._stalled_episode = True  # mid-episode, as on the kill path
    hb.stop()
    assert hb.last_verdict["verdict"] == "SUPERVISOR_KILL"
    assert [e["verdict"] for e in tr.events
            if e["kind"] == "heartbeat"] == ["SUPERVISOR_KILL"]
    # idempotent: a second stop must not re-emit
    hb.stop()
    assert len(tr.events) == 1


def test_heartbeat_stop_never_raises():
    from mpi_cuda_process_tpu.obs.heartbeat import Heartbeat

    hb = Heartbeat(lambda: 0.0, trace=_Trace(raise_on_event=True),
                   stall_after_s=9999)
    hb._stalled_episode = True
    hb.stop()  # the raising trace must be swallowed, not propagated
    assert not hb._stalled_episode


def test_heartbeat_uses_injected_wedge_verdict(monkeypatch):
    from mpi_cuda_process_tpu.obs.heartbeat import Heartbeat

    monkeypatch.setenv("FAULT_INJECT", "heartbeat:wedge")
    tr = _Trace()
    hb = Heartbeat(lambda: 0.0, trace=tr, stall_after_s=0.01, poll_s=0.01)
    hb.start()
    try:
        import time

        deadline = time.monotonic() + 5.0
        while time.monotonic() < deadline and \
                hb.last_verdict["verdict"] != "WEDGED":
            time.sleep(0.01)
        seen = hb.last_verdict["verdict"]
    finally:
        hb.stop()  # closes the open episode with SUPERVISOR_KILL
    assert seen == "WEDGED"
    verdicts = [e["verdict"] for e in tr.events if e["kind"] == "heartbeat"]
    assert "WEDGED" in verdicts and verdicts[-1] == "SUPERVISOR_KILL"


# ------------------------------------------------------------- to_argv

def test_to_argv_roundtrips_through_the_real_parser():
    from mpi_cuda_process_tpu.cli import config_from_args

    cfgs = [
        RunConfig(),
        RunConfig(stencil="life", grid=(64, 64), iters=100, seed=7,
                  checkpoint_every=10, checkpoint_dir="/tmp/ck",
                  telemetry="/tmp/t.jsonl", resume=True),
        RunConfig(stencil="heat3d", grid=(32, 32, 128), iters=8,
                  mesh=(2, 1, 1), fuse=4, fuse_kind="stream",
                  exchange="rdma", overlap=True, pipeline=True,
                  dtype="bfloat16", mem_check="warn", periodic=True,
                  params={"alpha": 0.25, "n": 3}),
    ]
    for cfg in cfgs:
        assert config_from_args(to_argv(cfg)) == cfg, cfg
    # launcher-only fields never reach the child argv (a child that
    # re-supervised would fork a supervision tree)
    sup_cfg = RunConfig(supervise=True, max_restarts=9,
                        restart_backoff=0.1, supervise_stall_s=1.0)
    argv = to_argv(sup_cfg)
    assert "--supervise" not in argv and "--max-restarts" not in argv
    assert config_from_args(argv) == RunConfig()


def test_to_argv_covers_every_runconfig_field():
    """A new RunConfig field must either be a launcher-only field or map
    to a real CLI flag — otherwise supervised children silently drop it."""
    from mpi_cuda_process_tpu.cli import build_parser

    known_flags = {a.dest for a in build_parser()._actions}
    for f in dataclasses.fields(RunConfig):
        if f.name in ("params",):  # repeated --param k=v
            continue
        assert f.name in known_flags, \
            f"RunConfig.{f.name} has no CLI flag (to_argv would drop it)"


# ------------------------------------------------------------- LogTail

def test_logtail_consumes_only_complete_lines(tmp_path):
    p = tmp_path / "log.jsonl"
    tail = trace_lib.LogTail(str(p))
    assert tail.poll() == []  # missing file: no records, no raise
    with open(p, "w") as fh:
        fh.write(json.dumps({"kind": "a"}) + "\n")
        fh.write('{"kind": "b", "trunca')  # killed mid-write
    assert [e["kind"] for e in tail.poll()] == ["a"]
    assert tail.poll() == []  # the partial line stays unconsumed
    with open(p, "a") as fh:
        fh.write('ted": 1}\n' + "not json\n"
                 + json.dumps({"kind": "c"}) + "\n")
    got = tail.poll()
    assert [e["kind"] for e in got] == ["b", "c"]
    assert tail.malformed == 1


# ------------------------------------------------- supervise (unit)

class _FakeHandle:
    """Scripted child: a list of poll() results; records kills."""

    def __init__(self, polls):
        self._polls = list(polls)
        self.killed = False

    def poll(self):
        return self._polls.pop(0) if self._polls else 0

    def kill(self):
        self.killed = True

    def wait(self, timeout_s=30.0):
        return None


class _FakeTail:
    def __init__(self, batches=()):
        self._batches = list(batches)

    def poll(self):
        return self._batches.pop(0) if self._batches else []


class _Session:
    path = "fake.supervisor.jsonl"

    def __init__(self):
        self.events = []

    def event(self, kind, **payload):
        self.events.append({"kind": kind, **payload})


def _npy_checkpoint(tmp_path, step):
    ck = tmp_path / "ck"
    ck.mkdir(exist_ok=True)
    (ck / "meta.json").write_text(json.dumps(
        {"step": step, "num_fields": 0, "config": {}}))
    return str(ck)


def test_supervise_backoff_sequencing_and_resume(tmp_path):
    """Two failures then success: backoffs must follow base*2^n, every
    relaunch must resume from the recorded checkpoint, and the launch
    events must carry resumed_from_step."""
    ck = _npy_checkpoint(tmp_path, 30)
    session = _Session()
    sleeps = []
    launches = []

    def launcher(attempt, resume):
        launches.append((attempt, resume))
        rc = 1 if attempt < 2 else 0
        return _FakeHandle([rc]), [_FakeTail()]

    res = sup.supervise(
        launcher, ck, max_restarts=3, backoff_base_s=0.5,
        backoff_max_s=100.0, stall_timeout_s=60.0, poll_s=0.0,
        session=session, sleep=sleeps.append, clock=lambda: 0.0)
    assert res.ok and res.attempts == 3 and not res.gave_up
    assert sleeps == [0.5, 1.0]  # exponential sequencing
    assert launches == [(0, False), (1, True), (2, True)]
    assert res.resumed_from_step == 30
    resumed = [e.get("resumed_from_step") for e in session.events
               if e["kind"] == "launch" and e.get("resume")]
    assert resumed == [30, 30]
    kinds = [e["kind"] for e in session.events]
    assert kinds == ["launch", "restart", "launch", "restart", "launch",
                     "summary"]
    assert session.events[-1]["ok"] is True


def test_supervise_gives_up_after_max_restarts(tmp_path):
    ck = _npy_checkpoint(tmp_path, 10)
    session = _Session()
    sleeps = []
    res = sup.supervise(
        lambda attempt, resume: (_FakeHandle([3]), [_FakeTail()]),
        ck, max_restarts=2, backoff_base_s=0.25, stall_timeout_s=60.0,
        poll_s=0.0, session=session, sleep=sleeps.append,
        clock=lambda: 0.0)
    assert not res.ok and res.gave_up and res.attempts == 3
    assert res.final_rc == 3
    assert sleeps == [0.25, 0.5]  # backoff between failures, none after
    assert [e["kind"] for e in session.events].count("give_up") == 1
    assert session.events[-1]["kind"] == "summary"
    assert session.events[-1]["ok"] is False


def test_supervise_kills_on_wedged_verdict(tmp_path):
    ck = _npy_checkpoint(tmp_path, 20)
    session = _Session()
    handles = []

    def launcher(attempt, resume):
        if attempt == 0:
            h = _FakeHandle([None, None])  # alive while the verdict lands
            tails = [_FakeTail([[], [{"kind": "heartbeat",
                                      "verdict": "WEDGED",
                                      "detail": "injected"}]])]
        else:
            h = _FakeHandle([0])
            tails = [_FakeTail()]
        handles.append(h)
        return h, tails

    res = sup.supervise(launcher, ck, max_restarts=1, backoff_base_s=0.0,
                        stall_timeout_s=60.0, poll_s=0.0, session=session,
                        sleep=lambda s: None, clock=lambda: 0.0)
    assert res.ok and res.attempts == 2
    assert handles[0].killed and not handles[1].killed
    restart = [e for e in session.events if e["kind"] == "restart"][0]
    assert "WEDGED" in restart["reason"]


def test_supervise_kills_on_wall_clock_stall(tmp_path):
    """No events at all (the compile-hang case): the wall-clock watchdog
    must kill even though the child never wrote a verdict."""
    ck = _npy_checkpoint(tmp_path, 20)
    t = [0.0]

    def clock():
        t[0] += 2.0
        return t[0]

    handles = []

    def launcher(attempt, resume):
        h = _FakeHandle([None] * 50 if attempt == 0 else [0])
        handles.append(h)
        return h, [_FakeTail()]

    res = sup.supervise(launcher, ck, max_restarts=1, backoff_base_s=0.0,
                        stall_timeout_s=5.0, poll_s=0.0,
                        sleep=lambda s: None, clock=clock)
    assert res.ok and res.attempts == 2
    assert handles[0].killed
    assert res.restarts[0]["reason"] == "wall-clock stall"


def test_watch_child_reports_verdict_over_exit_on_final_drain():
    """A child that dies right after writing its WEDGED verdict: the
    richer reason (the verdict) must win over the bare exit code."""
    h = _FakeHandle([1])
    tail = _FakeTail([[{"kind": "heartbeat", "verdict": "WEDGED",
                        "detail": "d"}]])
    # first poll drains nothing (the batch list starts at the exit
    # check), so seed the tail to deliver on the post-exit drain
    outcome, value, _ = sup.watch_child(
        h, [tail], stall_timeout_s=60.0, poll_s=0.0,
        clock=lambda: 0.0, sleep=lambda s: None)
    assert (outcome, value) == ("verdict", "WEDGED")


def test_retry_subprocess_retries_past_a_first_attempt_hang():
    """The campaign-label contract: attempt 0 hangs (killed at the
    budget), attempt 1 — gated by FAULT_ATTEMPT — completes."""
    import sys as _sys

    code = ("import os, time, sys; "
            "time.sleep(60) if os.environ.get('FAULT_ATTEMPT') == '0' "
            "else sys.exit(0)")
    res = sup.retry_subprocess(
        [_sys.executable, "-c", code], timeout_s=2.0, max_restarts=1,
        backoff_base_s=0.05, sleep=lambda s: None)
    assert res["rc"] == 0 and not res["timed_out"]
    assert res["attempts"] == 2
    assert res["history"][0]["outcome"] == "timeout"


def test_retry_subprocess_stops_when_unhealthy():
    import sys as _sys

    res = sup.retry_subprocess(
        [_sys.executable, "-c", "import time; time.sleep(60)"],
        timeout_s=1.0, max_restarts=3, backoff_base_s=0.05,
        healthy=lambda: False, sleep=lambda s: None)
    assert res["timed_out"] and not res["healthy_after"]
    assert res["attempts"] == 1  # environmental: stop burning attempts


# ------------------------------------------------- supervise (e2e)

def _read_events(path):
    with open(path) as fh:
        return [json.loads(line) for line in fh if line.strip()]


def test_supervisor_restarts_injected_wedge_and_bitmatches(
        tmp_path, monkeypatch):
    """THE acceptance pin: an injected mid-run wedge (CPU, FAULT_INJECT)
    is detected, the child killed and relaunched with --resume, the run
    completes, restart + resumed_from_step land in the supervisor's obs
    log, and the final fields bit-match an uninterrupted run of the
    same config/seed."""
    from mpi_cuda_process_tpu.cli import run
    from mpi_cuda_process_tpu.utils import checkpointing

    base = dict(stencil="life", grid=(64, 64), iters=100, seed=7)
    # the uninterrupted reference FIRST — before the fault env exists in
    # this process (the in-process run hits the same fault points)
    full, _ = run(RunConfig(**base))

    monkeypatch.setenv("FAULT_INJECT", "exchange:step=40:hang")
    monkeypatch.setenv("FAULT_HANG_S", "120")
    ck = str(tmp_path / "ck")
    tel = str(tmp_path / "run.jsonl")
    rc = sup.run_supervised(RunConfig(
        **base, checkpoint_every=10, checkpoint_dir=ck, telemetry=tel,
        supervise=True, max_restarts=2, restart_backoff=0.2,
        supervise_stall_s=6.0))
    assert rc == 0

    events = _read_events(str(tmp_path / "run.supervisor.jsonl"))
    kinds = [e.get("kind") for e in events]
    assert "restart" in kinds and "give_up" not in kinds
    resumed = [e["resumed_from_step"] for e in events
               if e.get("kind") == "launch" and e.get("resume")]
    assert resumed and all(s == 30 for s in resumed)  # hang at 40 -> 30
    summary = [e for e in events if e.get("kind") == "summary"][-1]
    assert summary["ok"] is True and summary["restarts"] >= 1

    # the resumed child also names its resume point in ITS manifest log
    child1 = _read_events(str(tmp_path / "run.attempt1.jsonl"))
    assert any(e.get("kind") == "resume"
               and e.get("resumed_from_step") == 30 for e in child1)

    # bit-exact final state: the supervised run's final checkpoint vs
    # the uninterrupted in-process run
    fields, step, _ = checkpointing.load_any(ck)
    assert step == 100
    for a, b in zip(fields, full):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


_PARENT_CODE = """
import json, sys
from mpi_cuda_process_tpu import cli
rc = cli.main(sys.argv[1:])
from jax._src import xla_bridge
print(json.dumps({"rc": rc, "backends": sorted(xla_bridge._backends)}))
"""


def test_supervisor_parent_never_initializes_a_backend(tmp_path):
    """One process per chip: the --supervise launcher starts the CLI
    child that needs the chip, so it must never touch a jax backend
    itself — its manifest leaves the device identity unset and the
    child's manifest carries it."""
    import subprocess

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    tel = str(tmp_path / "run.jsonl")
    out = subprocess.run(
        [sys.executable, "-c", _PARENT_CODE, "--stencil", "heat2d",
         "--grid", "16,16", "--iters", "8", "--supervise",
         "--telemetry", tel, "--checkpoint-dir", str(tmp_path / "ck")],
        cwd=repo, capture_output=True, text=True, timeout=300,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert out.returncode == 0, out.stderr[-2000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res == {"rc": 0, "backends": []}
    sup_manifest = _read_events(str(tmp_path / "run.supervisor.jsonl"))[0]
    assert sup_manifest["provenance"]["backend"] == "unset"
    child = _read_events(str(tmp_path / "run.attempt0.jsonl"))[0]
    assert child["provenance"]["backend"] == "cpu"


@pytest.mark.slow
def test_supervisor_restarts_on_child_death(tmp_path, monkeypatch):
    """The child-death branch with real processes: a SIGKILLed child
    (exit path, no verdict, no stall wait) is relaunched and resumes."""
    monkeypatch.setenv("FAULT_INJECT", "exchange:step=40:sigkill")
    ck = str(tmp_path / "ck")
    rc = sup.run_supervised(RunConfig(
        stencil="life", grid=(64, 64), iters=100, seed=7,
        checkpoint_every=10, checkpoint_dir=ck,
        telemetry=str(tmp_path / "run.jsonl"), supervise=True,
        max_restarts=2, restart_backoff=0.2, supervise_stall_s=60.0))
    assert rc == 0
    events = _read_events(str(tmp_path / "run.supervisor.jsonl"))
    restart = [e for e in events if e.get("kind") == "restart"][0]
    assert "exited" in restart["reason"]
    from mpi_cuda_process_tpu.utils import checkpointing

    assert checkpointing.latest_step(ck) == 100


@pytest.mark.slow
def test_supervisor_gives_up_against_a_permanent_wedge(
        tmp_path, monkeypatch):
    """always-hang: every attempt wedges, the supervisor must give up
    loudly (exit 1, give_up event) after max_restarts, never spin."""
    monkeypatch.setenv("FAULT_INJECT", "exchange:step=20:always:hang")
    monkeypatch.setenv("FAULT_HANG_S", "120")
    rc = sup.run_supervised(RunConfig(
        stencil="life", grid=(64, 64), iters=100, seed=7,
        checkpoint_every=10, checkpoint_dir=str(tmp_path / "ck"),
        telemetry=str(tmp_path / "run.jsonl"), supervise=True,
        max_restarts=1, restart_backoff=0.1, supervise_stall_s=5.0))
    assert rc == 1
    events = _read_events(str(tmp_path / "run.supervisor.jsonl"))
    assert any(e.get("kind") == "give_up" for e in events)
