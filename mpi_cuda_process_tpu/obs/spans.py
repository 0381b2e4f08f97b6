"""Distributed span tracing: one causal timeline across processes.

The obs stack before this module recorded flat per-process events: a
supervised, restarted, multi-host run left N disjoint JSONL logs whose
only ordering was wall-clock guesswork.  This module adds the missing
causal spine — a **span** (trace_id / span_id / parent_id, wall start,
monotonic-measured duration, attributes) emitted as an ordinary
schema-compatible event into the telemetry stream the tools already
write — plus **cross-process propagation**: a parent (the supervisor,
the engine, a future multi-host launcher) exports ``OBS_TRACE_CONTEXT``
into a child's environment, and the child's obs session adopts that
trace_id and parents its spans under the exporter's span.  Every
attempt of a restarted run, and every process of a multi-host run,
then shares ONE trace_id — ``scripts/obs_trace_export.py`` folds the
logs into a single Chrome-trace/Perfetto timeline.

The span vocabulary (the contract the ROADMAP item-1 scheduler and the
item-5 multi-host launch path will emit into):

=============  =====================================================
name           emitted by
=============  =====================================================
``run``/tool   the session root span (``Session.close``; named after
               the emitting tool — ``cli``, ``supervisor``, ...)
``compile``    ``RuntimeRecorder`` around chunk 0 (compile + warmup)
``checkpoint`` the CLI around every checkpoint save
``resume``     the CLI around a resuming build (attrs carry
               ``resumed_from_step``)
``attempt``    the supervisor around one child's whole life
``kill``       the supervisor around killpg + reap
``restart``    the supervisor between two attempts (attrs carry the
               ``resumed_from_step`` the next attempt will use)
``backoff``    the supervisor's exponential-backoff sleep (nested in
               ``restart``)
``request``    the engine around one submitted run (children:
               ``queue_wait``, ``result``)
=============  =====================================================

Every span also goes through :func:`region`, the one primitive that
puts a name on the profiler's clock: a ``jax.profiler.TraceAnnotation``
(so a ``--profile-dir`` trace holds the span beside the device ops), a
per-thread stack of open regions (``obs/runtime``'s compile counter
keys each compile by the innermost), and a bounded record of closed
regions on ``time.perf_counter`` (:func:`closed_regions`, what an
in-process reader such as the benchmark reads).  The program's own
regions are named ``sim.*``:

==========================  ==========================================
region                      opened by
==========================  ==========================================
``sim.build``               ``plan.build``
``sim.auto_fuse_probe``     ``plan.maybe_auto_fuse``'s kernel probe
``sim.chunk``               ``driver.run_simulation``, one per chunk
                            (a ``StepTraceAnnotation``, ``step_num`` =
                            the chunk's first absolute step)
``sim.runner``              the chunk's runner call (fenced when an
                            observer times it)
``sim.observe``             the chunk's callback
``sim.diagnostics``         ``utils/diagnostics.field_diagnostics``
``sim.diagnostics.stage``   the one dispatch of its compiled
                            observation program (reductions and
                            residual together)
``sim.diagnostics.fetch``   its one ``jax.device_get``: the wait for
                            the device
==========================  ==========================================

Design constraints, inherited from the obs layer:

* **Zero ops in the jitted step** — spans are host-side wall clocks at
  the same boundaries events already fire; the step jaxpr is
  byte-identical with spans on vs off (pinned by test).
* **Never load-bearing** — emission failures are swallowed; a closed
  trace drops late spans silently.
* **Pure stdlib** — importable by the supervisor parent on a wedged
  box without dragging a jax backend in; :func:`region` annotates only
  when the process has already imported jax.
* Disable with ``OBS_SPANS=0`` (events keep flowing; only spans stop).
"""

from __future__ import annotations

import collections
import contextlib
import json
import os
import sys
import threading
import time
import uuid
from typing import Any, Dict, Iterator, List, Optional, Tuple

ENV_VAR = "OBS_TRACE_CONTEXT"
SPAN_KIND = "span"

_tls = threading.local()


# ------------------------------------------------------------ regions

# closed regions, oldest first: (name, start, end) on time.perf_counter;
# bounded so a long run keeps the last few thousand chunks' worth
_CLOSED_KEEP = 16384
_closed: "collections.deque[Tuple[str, float, float]]" = \
    collections.deque(maxlen=_CLOSED_KEEP)


def _open_regions() -> List[str]:
    stack = getattr(_tls, "regions", None)
    if stack is None:
        stack = _tls.regions = []
    return stack


class region:
    """``with region(name):`` — a named stretch of host work.

    Opens ``jax.profiler.TraceAnnotation(name)`` (a
    ``StepTraceAnnotation`` when ``step_num`` is given) if jax is
    already imported — with no profiler session active that costs one
    TraceMe check — pushes ``name`` on this thread's stack of open
    regions, and on exit records ``(name, start, end)`` in
    :func:`closed_regions`.  A class, not a generator, to keep the
    per-chunk cost at about a microsecond.
    """

    __slots__ = ("name", "step_num", "_annotation", "_t0")

    def __init__(self, name: str, step_num: Optional[int] = None):
        self.name = name
        self.step_num = step_num

    def __enter__(self) -> "region":
        _open_regions().append(self.name)
        jax = sys.modules.get("jax")
        if jax is None:
            self._annotation = None
        elif self.step_num is None:
            self._annotation = jax.profiler.TraceAnnotation(self.name)
        else:
            self._annotation = jax.profiler.StepTraceAnnotation(
                self.name, step_num=self.step_num)
        if self._annotation is not None:
            self._annotation.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc: Any) -> None:
        t1 = time.perf_counter()
        if self._annotation is not None:
            self._annotation.__exit__(*exc)
        stack = _open_regions()
        if stack:
            stack.pop()
        _closed.append((self.name, self._t0, t1))


def current_region() -> Optional[str]:
    """This thread's innermost open region, or None."""
    stack = getattr(_tls, "regions", None)
    return stack[-1] if stack else None


def closed_regions(name: Optional[str] = None
                   ) -> List[Tuple[str, float, float]]:
    """The last closed regions (all, or those called ``name``), oldest
    first, as ``(name, start, end)`` in ``time.perf_counter`` seconds."""
    return [r for r in list(_closed) if name is None or r[0] == name]


def new_id() -> str:
    """A 16-hex-char random id (span ids; trace ids use the same)."""
    return uuid.uuid4().hex[:16]


def spans_enabled() -> bool:
    """Span emission gate: ``OBS_SPANS=0`` turns spans off (events keep
    flowing — the gate exists so the on-vs-off jaxpr pin is testable)."""
    return os.environ.get("OBS_SPANS", "1") != "0"


class SpanContext:
    """Where in the one causal timeline we are: (trace_id, span_id)."""

    __slots__ = ("trace_id", "span_id")

    def __init__(self, trace_id: str, span_id: str):
        self.trace_id = str(trace_id)
        self.span_id = str(span_id)

    def encode(self) -> str:
        return f"{self.trace_id}:{self.span_id}"

    @classmethod
    def decode(cls, s: str) -> Optional["SpanContext"]:
        parts = str(s).split(":")
        if len(parts) != 2 or not parts[0] or not parts[1]:
            return None
        return cls(parts[0], parts[1])

    def __repr__(self) -> str:  # pragma: no cover — debugging aid
        return f"SpanContext({self.encode()})"


# ------------------------------------------------------- propagation

def from_env() -> Optional[SpanContext]:
    """The context a parent process exported, or None."""
    raw = os.environ.get(ENV_VAR)
    return SpanContext.decode(raw) if raw else None


def push_thread_context(ctx: SpanContext) -> None:
    """Set THIS thread's pending context (the in-process analogue of the
    env var — the engine sets it on a handle's thread before the run
    opens its session, so the session parents under the request span
    without any environment mutation)."""
    stack = getattr(_tls, "pending", None)
    if stack is None:
        stack = _tls.pending = []
    stack.append(ctx)


def pop_thread_context() -> None:
    stack = getattr(_tls, "pending", None)
    if stack:
        stack.pop()


def thread_context() -> Optional[SpanContext]:
    stack = getattr(_tls, "pending", None)
    return stack[-1] if stack else None


def resolve_context() -> Optional[SpanContext]:
    """The inherited context for a new session: this thread's pending
    context first (in-process parent, e.g. the engine), then the
    environment (cross-process parent, e.g. the supervisor)."""
    return thread_context() or from_env()


def env_extra(session: Any) -> Dict[str, str]:
    """The env block a launcher passes to a child so the child's spans
    join this session's trace under the CURRENT span (call inside the
    span that brackets the child's life — the supervisor's ``attempt``
    span).  Empty when the session has no live emitter."""
    emitter = getattr(session, "spans", None)
    if emitter is None or not emitter.enabled:
        return {}
    return {ENV_VAR: emitter.current().encode()}


# ------------------------------------------------------------ records

def make_span_record(name: str, trace_id: str, span_id: str,
                     parent_id: Optional[str], start: float, dur_s: float,
                     attrs: Optional[Dict[str, Any]] = None,
                     t: Optional[float] = None) -> Dict[str, Any]:
    """One span as an obs event record (the single schema definition —
    the emitter and the engine's post-run appender both build these)."""
    from . import trace as trace_lib

    rec: Dict[str, Any] = {
        "schema": trace_lib.SCHEMA_VERSION,
        "kind": SPAN_KIND,
        "t": float(t) if t is not None else float(start) + float(dur_s),
        "name": str(name),
        "trace_id": str(trace_id),
        "span_id": str(span_id),
        "parent_id": str(parent_id) if parent_id else None,
        "start": float(start),
        "dur_s": float(dur_s),
    }
    if attrs:
        rec["attrs"] = dict(attrs)
    return rec


def append_span_records(path: str, records: List[Dict[str, Any]]) -> int:
    """Append finished span records to an existing (closed) telemetry
    log — the engine's post-run request accounting.  Never raises; the
    return value is the number of lines written."""
    try:
        with open(path, "a") as fh:
            for rec in records:
                fh.write(json.dumps(rec, default=str) + "\n")
        return len(records)
    except OSError:
        return 0


# ------------------------------------------------------------ emitter

class SpanEmitter:
    """Per-session span factory bound to one TraceWriter.

    The emitter owns the session's trace identity: a fresh ``trace_id``
    when no context was inherited (this session is a trace root), the
    parent's ``trace_id`` otherwise.  A long-lived **root span** (named
    after the tool) brackets the whole session; it is emitted by
    :meth:`close` — exporters see it last in the log but its ``start``
    is the session open.  :meth:`span` is the context manager for
    everything else; nesting is tracked per thread (a span opened on
    the heartbeat thread parents to the root, not to whatever the main
    thread happens to be inside).
    """

    def __init__(self, trace: Any, context: Optional[SpanContext] = None,
                 root_name: str = "run",
                 root_attrs: Optional[Dict[str, Any]] = None,
                 enabled: Optional[bool] = None):
        self.trace = trace
        self.enabled = spans_enabled() if enabled is None else bool(enabled)
        self.inherited = context
        self.trace_id = context.trace_id if context else new_id()
        self.root_id = new_id()
        self.root_name = str(root_name)
        self.root_attrs = dict(root_attrs) if root_attrs else {}
        self._root_start = time.time()
        self._root_t0 = time.monotonic()
        self._root_emitted = False
        self._stacks = threading.local()

    # -- context ------------------------------------------------------

    def _stack(self) -> List[SpanContext]:
        stack = getattr(self._stacks, "stack", None)
        if stack is None:
            stack = self._stacks.stack = []
        return stack

    def current(self) -> SpanContext:
        """This thread's innermost open span (the root when none is)."""
        stack = self._stack()
        return stack[-1] if stack else SpanContext(self.trace_id,
                                                   self.root_id)

    def manifest_block(self) -> Dict[str, Any]:
        """The ``trace`` block stamped into the session manifest: how a
        reader joins this log to its parents without parsing spans."""
        return {
            "trace_id": self.trace_id,
            "root_span_id": self.root_id,
            "parent_span_id": (self.inherited.span_id
                               if self.inherited else None),
        }

    # -- emission -----------------------------------------------------

    def _write(self, rec: Dict[str, Any]) -> None:
        # TraceWriter.event() rebuilds schema/t; write through it so the
        # manifest-first rule and thread-safe locking apply unchanged.
        try:
            payload = {k: v for k, v in rec.items()
                       if k not in ("schema", "kind", "t")}
            self.trace.event(SPAN_KIND, **payload)
        except Exception:  # noqa: BLE001 — never load-bearing
            pass

    def emit(self, name: str, start: float, dur_s: float,
             parent_id: Optional[str] = None, span_id: Optional[str] = None,
             **attrs: Any) -> Optional[str]:
        """Record an already-measured span (no context manager — the
        caller timed it; e.g. the recorder's compile span, the CLI's
        resume span).  Parents to this thread's current span unless an
        explicit ``parent_id`` is given.  Returns the span id."""
        if not self.enabled or self.trace is None:
            return None
        sid = span_id or new_id()
        rec = make_span_record(
            name, self.trace_id, sid,
            parent_id if parent_id is not None else self.current().span_id,
            start, dur_s, attrs or None)
        self._write(rec)
        return sid

    @contextlib.contextmanager
    def span(self, name: str, **attrs: Any) -> Iterator[Optional[SpanContext]]:
        """Open a span around a code block; emitted at exit with the
        measured duration.  Yields the span's context (what a launcher
        encodes into a child's ``OBS_TRACE_CONTEXT``).  The block runs
        inside :func:`region` of the same name, emitted or not."""
        with region(name):
            if not self.enabled or self.trace is None:
                yield None
                return
            parent = self.current().span_id
            ctx = SpanContext(self.trace_id, new_id())
            stack = self._stack()
            stack.append(ctx)
            start = time.time()
            t0 = time.monotonic()
            try:
                yield ctx
            finally:
                if stack and stack[-1] is ctx:
                    stack.pop()
                rec = make_span_record(name, self.trace_id, ctx.span_id,
                                       parent, start,
                                       time.monotonic() - t0, attrs or None)
                self._write(rec)

    def close(self, **attrs: Any) -> None:
        """Emit the root span (idempotent).  Call BEFORE the trace
        writer closes — a post-close emission is dropped silently."""
        if self._root_emitted or not self.enabled or self.trace is None:
            return
        self._root_emitted = True
        merged = dict(self.root_attrs)
        merged.update(attrs)
        rec = make_span_record(
            self.root_name, self.trace_id, self.root_id,
            self.inherited.span_id if self.inherited else None,
            self._root_start, time.monotonic() - self._root_t0,
            merged or None)
        self._write(rec)


def maybe_span(emitter: Optional[SpanEmitter], name: str, **attrs: Any):
    """``emitter.span(...)`` or a null context when there is no emitter
    — the one-liner call sites (cli, supervisor) use."""
    if emitter is not None:
        return emitter.span(name, **attrs)
    return contextlib.nullcontext()
