"""Run supervisor: heartbeat-driven checkpoint-restart-resume.

The missing connection between three working parts: the heartbeat
writes WEDGED/STALLED verdicts (``obs/heartbeat.py``), checkpointing
resumes bit-exactly (``utils/checkpointing.py``), and the ledger
quarantines dead runs (``obs/ledger.py``) — but until now a wedge still
cost the whole run, because nobody acted on the verdict.  The
supervisor acts:

1. the simulation runs in a **child subprocess** (its own process
   group) with ``--checkpoint-every`` and ``--telemetry`` forced on;
2. the parent **tails the child's telemetry JSONL** (manifest, chunk,
   heartbeat events) — the same file a human would read post-mortem —
   and kills the child on a WEDGED/STALLED heartbeat verdict, on child
   death (nonzero exit), or on a wall-clock stall with **no events at
   all** (the compile-hang case, where the in-process heartbeat may be
   hung too);
3. after a bounded **exponential backoff** it relaunches with
   ``--resume`` from the latest surviving checkpoint, exporting
   ``FAULT_ATTEMPT`` so the deterministic fault harness
   (:mod:`.faults`) can prove every path on CPU;
4. after ``max_restarts`` failed relaunches it **gives up loudly**
   (nonzero exit, a ``give_up`` event) — a supervisor must never spin
   forever against a dead backend.

Correctness contract: the resumed-run-bit-matches-uninterrupted
invariant of ``tests/test_fault_injection.py``, extended across
automatic restarts (pinned by ``tests/test_supervisor.py``: an injected
mid-run wedge is detected, restarted, resumed, and the final fields
bit-match an uninterrupted run of the same config/seed).

Every decision lands in the supervisor's own telemetry log (the obs/
schema, tool ``"supervisor"``): ``launch`` events carry the attempt
number and ``resumed_from_step``, ``restart`` events the reason and
backoff, ``give_up``/``summary`` how it ended.

:func:`retry_subprocess` is the non-resumable sibling for measurement-
campaign labels (``benchmarks/measure.py``): a label is a timing run
with nothing to resume, so a wedge there costs the in-flight *attempt*
— kill, backoff, relaunch the same label — never the label.
"""

from __future__ import annotations

import contextlib
import dataclasses
import json
import os
import signal
import subprocess
import sys
import time
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

from . import faults

_REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

# Heartbeat verdicts that kill the child.  STALLED is included by
# design: the supervisor's job is to trade a (bounded, resumable)
# restart for an unbounded wait — a run that stalls past the child
# heartbeat's threshold AND keeps stalling past the supervisor's
# wall-clock window was not coming back.
KILL_VERDICTS = ("WEDGED", "STALLED")

# Health verdicts (obs/health.py 'health' events) that end the run
# WITHOUT a restart: a DIVERGED state is deterministic — the newest
# checkpoint precedes (or contains) the blow-up, so checkpoint-restart
# would loop into the same divergence, burning every restart budget on
# a run that can never finish.  The supervisor gives up loudly with the
# verdict instead.
FATAL_VERDICTS = ("DIVERGED",)


@dataclasses.dataclass
class SuperviseResult:
    ok: bool
    attempts: int
    restarts: List[Dict[str, Any]]
    gave_up: bool
    final_rc: Optional[int]
    resumed_from_step: Optional[int]  # the last launch's resume point
    checkpoint_dir: Optional[str]
    telemetry: Optional[str]  # the supervisor's own event log


def sibling_path(base: str, tag: str) -> str:
    """``run.jsonl`` + ``attempt0`` -> ``run.attempt0.jsonl``."""
    if base.endswith(".jsonl"):
        return f"{base[:-len('.jsonl')]}.{tag}.jsonl"
    return f"{base}.{tag}.jsonl"


def backoff_s(attempt: int, base_s: float, max_s: float) -> float:
    """Exponential backoff before relaunch ``attempt + 1``: base * 2^n,
    bounded (a supervisor that backs off for hours has given up without
    saying so)."""
    return min(float(base_s) * (2.0 ** attempt), float(max_s))


def latest_checkpoint_step(path: Optional[str]) -> Optional[int]:
    """Newest checkpoint step under ``path`` (either backend), or None.

    File-system only — delegates to ``utils.checkpointing.latest_step``
    (which touches no device), so the supervisor can read the resume
    pointer while the backend is wedged.
    """
    if not path:
        return None
    from ..utils import checkpointing

    try:
        return checkpointing.latest_step(path)
    except Exception:  # noqa: BLE001 — a corrupt dir means "no resume"
        return None


# --------------------------------------------------------------- child

class ProcHandle:
    """A supervised child: its own process group, SIGKILL-cleanable.

    The kill must take the whole group — the child may have spawned
    subprocesses of its own, and an orphaned grandchild holding the chip
    would keep the next attempt off it (one process per chip).
    """

    def __init__(self, proc: subprocess.Popen):
        self.proc = proc

    def poll(self) -> Optional[int]:
        return self.proc.poll()

    def kill(self) -> None:
        try:
            os.killpg(self.proc.pid, signal.SIGKILL)
        except (OSError, ProcessLookupError):
            try:
                self.proc.kill()
            except OSError:
                pass

    def wait(self, timeout_s: float = 30.0) -> Optional[int]:
        try:
            return self.proc.wait(timeout=timeout_s)
        except subprocess.TimeoutExpired:
            return None


def spawn_child(cmd: Sequence[str], *, attempt: int,
                cwd: Optional[str] = None,
                env_extra: Optional[Dict[str, str]] = None) -> ProcHandle:
    """Launch one supervised attempt (new session = killable group).

    ``FAULT_ATTEMPT`` is exported so the deterministic fault harness
    gates per-attempt: the injected wedge fires on attempt 0, the
    relaunch runs clean — recovery is provable, not probabilistic.
    """
    env = dict(os.environ)
    env[faults.ATTEMPT_VAR] = str(attempt)
    env["PYTHONPATH"] = _REPO + os.pathsep + env.get("PYTHONPATH", "")
    if env_extra:
        env.update(env_extra)
    proc = subprocess.Popen(
        list(cmd), cwd=cwd or _REPO, env=env, start_new_session=True)
    return ProcHandle(proc)


# --------------------------------------------------------------- watch

def _classify_event(e, kill_verdicts, fatal_verdicts,
                    degraded_action: str = "warn"):
    """One tailed record -> ("verdict"|"fatal", value, detail) or None."""
    if e.get("kind") == "heartbeat" and e.get("verdict") in kill_verdicts:
        return ("verdict", e.get("verdict"), str(e.get("detail", ""))[:300])
    if e.get("kind") == "health" and e.get("verdict") in fatal_verdicts:
        return ("fatal", e.get("verdict"), str(e.get("reason", ""))[:300])
    if e.get("kind") == "anomaly":
        # run-doctor finding (obs/anomaly.py): the child is SLOW, not
        # dead.  The default is warn-only — a degraded run still makes
        # progress, and killing it trades real work for a maybe.
        # restart treats it as transient host trouble (checkpoint-
        # resume, same as a wedge); abort gives up with the evidence.
        suspect = e.get("suspect") or {}
        detail = (f"{e.get('anomaly')} "
                  f"(suspect {suspect.get('kind')}:{suspect.get('name')})"
                  )[:300]
        if degraded_action == "restart":
            return ("verdict", "DEGRADED", detail)
        if degraded_action == "abort":
            return ("fatal", "DEGRADED", detail)
        return None
    if e.get("kind") == "cancelled":
        # cooperative cancel (cancellation.py): a deliberately stopped
        # child is not a crash — never restart it into the work someone
        # just cancelled
        return ("fatal", "CANCELLED", f"cancelled at step {e.get('step')}")
    return None


def watch_child(handle, tails, *, stall_timeout_s: float,
                poll_s: float = 0.5,
                kill_verdicts: Sequence[str] = KILL_VERDICTS,
                fatal_verdicts: Sequence[str] = FATAL_VERDICTS,
                degraded_action: str = "warn",
                clock: Callable[[], float] = time.monotonic,
                sleep: Callable[[float], None] = time.sleep,
                ) -> Tuple[str, Optional[Any], Optional[str]]:
    """Watch one attempt until it ends or must be killed.

    Returns ``(outcome, value, detail)`` with outcome one of:

    * ``"exit"``    — the child exited on its own (value = return code);
    * ``"verdict"`` — a kill-listed heartbeat verdict landed in the
      child's telemetry (value = the verdict);
    * ``"fatal"``   — a NON-restartable health verdict (DIVERGED,
      obs/health.py) landed: the caller must give up, not relaunch
      (value = the verdict);
    * ``"stall"``   — no telemetry event for ``stall_timeout_s`` wall
      seconds (the no-evidence wedge: a hung compile, a dead writer).

    The caller kills the child for the middle two; this function never
    kills anything itself (testable with fakes, no subprocesses).
    """
    last_event = clock()
    while True:
        events = [e for t in tails for e in t.poll()]
        if events:
            last_event = clock()
            for e in events:
                hit = _classify_event(e, kill_verdicts, fatal_verdicts,
                                      degraded_action)
                if hit is not None:
                    return hit
        rc = handle.poll()
        if rc is not None:
            # one final drain: the death may have been preceded by a
            # verdict the tail had not consumed yet (report the richer
            # reason when both are true).  A fatal health verdict wins
            # over the bare exit code — the rc is a symptom, the
            # DIVERGED record is the diagnosis.
            for e in (e for t in tails for e in t.poll()):
                hit = _classify_event(e, kill_verdicts, fatal_verdicts,
                                      degraded_action)
                if hit is not None:
                    return hit
            return ("exit", int(rc), None)
        if clock() - last_event > stall_timeout_s:
            return ("stall", None,
                    f"no telemetry events for {stall_timeout_s:.1f}s "
                    "(wall-clock stall — hung compile or dead event "
                    "writer)")
        sleep(poll_s)


# ----------------------------------------------------------- supervise

def supervise(launcher, checkpoint_dir: Optional[str], *,
              max_restarts: int = 2, backoff_base_s: float = 5.0,
              backoff_max_s: float = 300.0, stall_timeout_s: float = 600.0,
              poll_s: float = 0.5,
              kill_verdicts: Sequence[str] = KILL_VERDICTS,
              fatal_verdicts: Sequence[str] = FATAL_VERDICTS,
              degraded_action: str = "warn",
              session=None,
              sleep: Callable[[float], None] = time.sleep,
              clock: Callable[[], float] = time.monotonic,
              ) -> SuperviseResult:
    """The restart loop: launch, watch, kill, back off, resume, bound.

    ``launcher(attempt, resume)`` returns ``(handle, tails)`` — a
    child handle (``poll``/``kill``/``wait``) plus the telemetry tails
    to watch (``obs.trace.LogTail``-shaped).  Tests inject fakes; the
    CLI path uses :func:`spawn_child` + real tails.

    ``session`` (an obs Session, optional) receives ``launch`` /
    ``restart`` / ``give_up`` events and the final ``summary`` — the
    obs-manifest trail the acceptance criteria read
    (``resumed_from_step`` rides every resuming launch event).

    A ``fatal_verdicts`` health verdict (DIVERGED) short-circuits the
    whole loop: kill, ``give_up`` carrying the verdict, nonzero exit —
    never a restart, because resuming a deterministic blow-up from a
    checkpoint at/under the blow-up reproduces it exactly.
    """
    def _event(kind: str, **payload: Any) -> None:
        if session is not None:
            try:
                session.event(kind, **payload)
            except Exception:  # noqa: BLE001 — telemetry never load-bearing
                pass

    last_tails: List[Any] = []

    def _give_up_bundle(reason: str, verdict: Optional[str]) -> None:
        """Flight-recorder bundle at give-up (obs/flightrec.py): the
        supervisor's own ring (launch/restart/give_up trail) plus the
        tail of the final attempt's child log — the child was just
        SIGKILLed, so its log on disk is all the evidence there is.
        Best-effort on every path; a fake session in tests simply
        yields no bundle."""
        if session is None:
            return
        try:
            from ..obs import aggregate as aggregate_lib
            from ..obs import flightrec as flightrec_lib

            extra: Dict[str, List[Dict[str, Any]]] = {}
            for t in last_tails:
                p = getattr(t, "path", None)
                if not p:
                    continue
                recs = list(aggregate_lib.iter_records(p))[-80:]
                extra[os.path.basename(p)] = [
                    r for r in recs if r.get("kind") != "manifest"]
            path = flightrec_lib.bundle_from_session(
                session, reason, verdict=verdict,
                extra_events=extra or None)
            if path:
                _event("bundle", path=path, reason=reason)
        except Exception:  # noqa: BLE001 — post-mortems never load-bearing
            pass

    # span emitter (obs/spans.py): the supervisor owns the RUN-LEVEL
    # trace — every attempt is an "attempt" span, every kill/backoff a
    # span between them, and the launcher exports OBS_TRACE_CONTEXT (via
    # spans.env_extra, called INSIDE the attempt span) so each child's
    # own spans join this one trace under its attempt.
    spans = getattr(session, "spans", None)

    def _span(name: str, **attrs: Any):
        if spans is not None:
            return spans.span(name, **attrs)
        return contextlib.nullcontext()

    restarts: List[Dict[str, Any]] = []
    resumed_from: Optional[int] = None
    for attempt in range(max_restarts + 1):
        step = latest_checkpoint_step(checkpoint_dir)
        resume = attempt > 0 and step is not None
        resumed_from = step if resume else None
        _event("launch", attempt=attempt, resume=resume,
               resumed_from_step=resumed_from)
        with _span("attempt", attempt=attempt, resume=resume,
                   resumed_from_step=resumed_from):
            handle, tails = launcher(attempt, resume)
            last_tails[:] = list(tails)
            outcome, value, detail = watch_child(
                handle, tails, stall_timeout_s=stall_timeout_s,
                poll_s=poll_s, kill_verdicts=kill_verdicts,
                fatal_verdicts=fatal_verdicts,
                degraded_action=degraded_action, clock=clock,
                sleep=sleep)
            if outcome != "exit":
                # verdict/fatal/stall: the child is alive but lost —
                # kill the whole group and reap it so the relaunch (or
                # the exit path) never races a half-dead predecessor
                # for the checkpoint dir
                with _span("kill", attempt=attempt, reason=outcome,
                           verdict=value
                           if outcome in ("verdict", "fatal") else None):
                    handle.kill()
                    handle.wait()
        if outcome == "fatal":
            # non-restartable: give up WITH the verdict, zero restarts
            # spent on a deterministic blow-up (the DIVERGED contract)
            reason = f"health verdict {value} (non-restartable)"
            if value == "DEGRADED":
                reason = "degraded (anomaly findings, " \
                         "--degraded-action abort)"
            _event("give_up", attempts=attempt + 1, reason=reason,
                   detail=detail, verdict=value, restarts=len(restarts))
            _give_up_bundle(reason, value)
            _event("summary", ok=False, attempts=attempt + 1,
                   restarts=len(restarts), gave_up=True, verdict=value)
            return SuperviseResult(
                ok=False, attempts=attempt + 1, restarts=restarts,
                gave_up=True, final_rc=None,
                resumed_from_step=resumed_from,
                checkpoint_dir=checkpoint_dir,
                telemetry=getattr(session, "path", None))
        if outcome == "exit" and value == 0:
            _event("summary", ok=True, attempts=attempt + 1,
                   restarts=len(restarts), resumed_from_step=resumed_from)
            return SuperviseResult(
                ok=True, attempts=attempt + 1, restarts=restarts,
                gave_up=False, final_rc=0, resumed_from_step=resumed_from,
                checkpoint_dir=checkpoint_dir,
                telemetry=getattr(session, "path", None))
        reason = {"exit": f"child exited rc={value}",
                  "verdict": ("degraded child (anomaly findings, "
                              "--degraded-action restart)"
                              if value == "DEGRADED"
                              else f"heartbeat verdict {value}"),
                  "stall": "wall-clock stall"}[outcome]
        if attempt >= max_restarts:
            _event("give_up", attempts=attempt + 1, reason=reason,
                   detail=detail, restarts=len(restarts))
            _give_up_bundle(reason, value if outcome == "verdict" else None)
            _event("summary", ok=False, attempts=attempt + 1,
                   restarts=len(restarts), gave_up=True)
            return SuperviseResult(
                ok=False, attempts=attempt + 1, restarts=restarts,
                gave_up=True, final_rc=value if outcome == "exit" else None,
                resumed_from_step=resumed_from,
                checkpoint_dir=checkpoint_dir,
                telemetry=getattr(session, "path", None))
        wait = backoff_s(attempt, backoff_base_s, backoff_max_s)
        rec = {"attempt": attempt, "reason": reason, "detail": detail,
               "backoff_s": wait,
               "checkpoint_step": latest_checkpoint_step(checkpoint_dir)}
        restarts.append(rec)
        _event("restart", **rec)
        # the restart span sits causally BETWEEN the two attempt spans
        # and names the step the next attempt will resume from — the
        # one-line answer to "what did the restart cost, and from where
        # did we come back?" on the exported timeline
        with _span("restart", attempt=attempt, reason=reason,
                   backoff_s=wait,
                   resumed_from_step=rec["checkpoint_step"]):
            with _span("backoff", backoff_s=wait):
                sleep(wait)
    raise AssertionError("unreachable: the loop returns on every path")


# ----------------------------------------------------------- CLI entry

def _default_checkpoint_every(cfg) -> int:
    """~10 checkpoints per run, rounded to the fused step unit."""
    every = max(1, cfg.iters // 10)
    if cfg.fuse:
        every = max(cfg.fuse, (every // cfg.fuse) * cfg.fuse)
    return every


def run_supervised(cfg) -> int:
    """``cli --supervise``: supervise a RunConfig end to end; returns rc.

    Checkpointing and telemetry are forced on (defaults derived when the
    config has none): a supervisor without a checkpoint has nothing to
    resume, and without telemetry it is blind.  The child is the
    ordinary ``python -m mpi_cuda_process_tpu`` CLI — the supervisor
    adds no second execution path to keep bit-exact.
    """
    import logging

    from ..config import to_argv
    from ..obs import spans as spans_lib
    from ..obs import trace as trace_lib

    log = logging.getLogger("mpi_cuda_process_tpu.supervisor")

    tag = f"{os.getpid()}-{int(time.time())}"
    checkpoint_dir = cfg.checkpoint_dir or os.path.join(
        trace_lib.default_telemetry_dir(), f"supervise-{tag}", "ckpt")
    checkpoint_every = cfg.checkpoint_every or _default_checkpoint_every(cfg)
    telemetry_base = cfg.telemetry or os.path.join(
        trace_lib.default_telemetry_dir(), f"supervise-{tag}.jsonl")
    child_cfg = dataclasses.replace(
        cfg, supervise=False, checkpoint_dir=checkpoint_dir,
        checkpoint_every=checkpoint_every, serve_port=None)

    session = None
    try:
        from .. import obs

        session = obs.open_session(
            sibling_path(telemetry_base, "supervisor"), tool="supervisor",
            run=dataclasses.asdict(child_cfg), with_heartbeat=False,
            launcher=True,
            supervisor={"max_restarts": cfg.max_restarts,
                        "restart_backoff_s": cfg.restart_backoff,
                        "stall_timeout_s": cfg.supervise_stall_s})
    except Exception as e:  # noqa: BLE001 — supervise even when blind
        log.warning("supervisor telemetry disabled (%s: %s)",
                    type(e).__name__, e)

    # Live console (--serve, obs/serve.py): ONE address for the whole
    # supervised run.  The console watches the supervisor's own log
    # (launch/restart trail) plus every attempt's child log as it is
    # launched, so /status.json answers "is it wedged?" ACROSS restarts
    # — the restart trail, the child's heartbeat verdict, and
    # resumed_from_step through a single port.  The child itself never
    # serves (serve_port is launcher-only and to_argv drops it).
    server = None
    if cfg.serve_port is not None:
        try:
            from ..obs import aggregate as aggregate_lib
            from ..obs import serve as serve_lib

            # the aggregate console (round 16): /status.json carries a
            # per-host/process table next to the merged stream, so one
            # address answers for supervisor + every attempt (and, once
            # the multi-host launch path lands, every host's log)
            console = aggregate_lib.make_console(
                [sibling_path(telemetry_base, "supervisor")])
            server = serve_lib.ObsServer(console, port=cfg.serve_port)
            log.info("supervisor obs console serving at %s", server.url)
            if session is not None:
                session.event("serve", url=server.url, port=server.port,
                              endpoints=["/metrics", "/status.json",
                                         "/events"])
        except Exception as e:  # noqa: BLE001 — never load-bearing
            log.warning("supervisor --serve disabled (%s: %s)",
                        type(e).__name__, e)
            server = None

    def launcher(attempt: int, resume: bool):
        tel = sibling_path(telemetry_base, f"attempt{attempt}")
        argv = to_argv(dataclasses.replace(
            child_cfg, telemetry=tel,
            resume=resume or (attempt == 0 and cfg.resume)))
        log.info("supervisor: launching attempt %d%s", attempt,
                 f" (resume from step "
                 f"{latest_checkpoint_step(checkpoint_dir)})"
                 if resume else "")
        if server is not None:
            # the console follows the child across restarts: each
            # attempt's log joins the merged stream before the spawn
            server.console.watch(tel)
        # cross-process trace propagation (obs/spans.py): the launcher
        # runs inside supervise()'s "attempt" span, so the exported
        # OBS_TRACE_CONTEXT parents the child's whole span tree under
        # this attempt — one trace_id across supervisor and every child
        handle = spawn_child(
            [sys.executable, "-m", "mpi_cuda_process_tpu", *argv],
            attempt=attempt, env_extra=spans_lib.env_extra(session))
        return handle, [trace_lib.LogTail(tel)]

    try:
        res = supervise(
            launcher, checkpoint_dir,
            max_restarts=cfg.max_restarts,
            backoff_base_s=cfg.restart_backoff,
            stall_timeout_s=cfg.supervise_stall_s,
            degraded_action=getattr(cfg, "degraded_action", "warn"),
            session=session)
    finally:
        if session is not None:
            session.close()
        if server is not None:
            server.close()
    if res.ok:
        log.info("supervisor: run completed after %d attempt(s)%s",
                 res.attempts,
                 f" (last resumed from step {res.resumed_from_step})"
                 if res.resumed_from_step is not None else "")
        return 0
    log.error("supervisor: giving up after %d attempt(s); latest "
              "checkpoint %r step %s — rerun with --resume to continue "
              "by hand", res.attempts, checkpoint_dir,
              latest_checkpoint_step(checkpoint_dir))
    return 1


# ----------------------------------------------- campaign-label retries

def retry_subprocess(cmd: Sequence[str], *, timeout_s: float,
                     max_restarts: int = 1, backoff_base_s: float = 2.0,
                     backoff_max_s: float = 60.0,
                     healthy: Optional[Callable[[], bool]] = None,
                     cwd: Optional[str] = None,
                     env_extra: Optional[Dict[str, str]] = None,
                     sleep: Callable[[float], None] = time.sleep,
                     ) -> Dict[str, Any]:
    """Bounded-retry runner for non-resumable work units (campaign labels).

    A measurement label has nothing to checkpoint, so the recovery unit
    is the whole attempt: on timeout the child (whole process group) is
    SIGKILLed and the unit retried after an exponential backoff — a
    wedge costs the in-flight *attempt*, never the label.  ``healthy()``
    gates each retry: False after a kill means the wedge is
    environmental (retrying would blame an innocent label), so the
    runner stops and reports it.  ``FAULT_ATTEMPT`` is exported per
    attempt (deterministic injection, same contract as the supervisor).

    Returns ``{"rc", "attempts", "timed_out", "healthy_after",
    "history"}`` — ``rc`` is the last attempt's return code (None when
    it timed out), ``history`` one record per attempt.
    """
    history: List[Dict[str, Any]] = []
    healthy_after = True
    rc: Optional[int] = None
    timed_out = False
    attempts = 0
    for attempt in range(max_restarts + 1):
        attempts = attempt + 1
        t0 = time.monotonic()
        handle = spawn_child(cmd, attempt=attempt, cwd=cwd,
                             env_extra=env_extra)
        try:
            rc = handle.proc.wait(timeout=timeout_s)
            timed_out = False
        except subprocess.TimeoutExpired:
            handle.kill()
            handle.wait()
            rc, timed_out = None, True
        history.append({"attempt": attempt,
                        "outcome": "timeout" if timed_out else f"rc={rc}",
                        "wall_s": round(time.monotonic() - t0, 1)})
        if not timed_out:
            return {"rc": rc, "attempts": attempts, "timed_out": False,
                    "healthy_after": True, "history": history}
        if healthy is not None:
            healthy_after = bool(healthy())
            if not healthy_after:
                break  # environmental: stop burning attempts
        if attempt < max_restarts:
            sleep(backoff_s(attempt, backoff_base_s, backoff_max_s))
    return {"rc": rc, "attempts": attempts, "timed_out": timed_out,
            "healthy_after": healthy_after, "history": history}
