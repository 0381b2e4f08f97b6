"""Unified telemetry layer: manifests, chunk stats, cost counters, heartbeat.

One subsystem shared by every entry point — ``cli --telemetry PATH``,
``bench.py``, ``benchmarks/measure.py``, ``benchmarks/scaling.py`` —
so all four emit the SAME versioned manifest schema (``trace.py``'s
validator is the single definition) and the same event vocabulary:

* ``manifest``   — provenance-stamped run record (first line, always)
* ``costmodel``  — static flop/HBM/ppermute counters + roofline
* ``chunk``      — per-chunk wall time, recompile flag, memory peaks
* ``heartbeat``  — STALLED/WEDGED/RECOVERED verdicts from the watcher
* ``profile``    — device-trace attribution of one profiled chunk
  (``profile.py``: measured overlap efficiency, or an explicit
  ``attribution: unavailable`` — never fabricated zeros)
* ``label`` / ``rung`` — benchmark-harness progress records
* ``span``       — one finished span of the causal timeline
  (``spans.py``: trace_id/span_id/parent_id + wall start + duration;
  the root span closes every log)
* ``health``     — one numerics-sentinel check (``health.py``:
  per-field min/max/mean + NaN/Inf counts, the op's registered
  conservation invariant, and the HEALTHY/DIVERGED verdict that flows
  through supervisor, ledger quarantine, and ``/status.json``)
* ``halo_audit`` — one bit-exact ghost-slab audit pass (``health.py``
  ``--halo-audit``: received slabs vs neighbor interiors, localized
  to (field, axis, direction, ring-shard) on mismatch)
* ``policy``     — the auto-policy decision (``policy/select.py``):
  chosen mode fields, measured-vs-predicted provenance, explicit-flag
  overrides, and the ranked runner-up table
* ``migrate``    — one live mesh migration (``parallel/reshard.py``):
  src/dst mode fields, the adopting step, and the collective round
  count (never a host gather)
* ``scheduler``  — one serving-scheduler decision
  (``serving/scheduler.py``: submit/join/retire/evict/preempt/cancel/
  reject plus the elastic ladder ops ``grow``/``shrink`` — a shrink is
  a live member-repack down a rung, with occupancy gauges riding every
  record)
* ``router``     — one fleet-router decision (``serving/router.py``:
  route/rebalance/reject/replica_up/replica_dead, with replica
  liveness and in-flight gauges riding every record)
* ``anomaly``    — one run-doctor finding (``anomaly.py``: throughput
  collapse vs own baseline or the ledger roofline band, post-warmup
  recompiles, device-memory creep, chunk-time variance growth,
  straggler attribution naming the slowest host/group with its lag
  ratio — the evidence behind the DEGRADED verdict)
* ``error`` / ``summary`` — how the run ended

Sibling stores complete the layer: ``profile.py`` wraps a
``jax.profiler`` session scoped to one steady-state chunk and parses
the emitted trace into interior-compute / exchange / exposed-ICI
buckets; ``ledger.py`` is the append-only cross-round campaign ledger
(every manifest ingested, 0.0/stale/suspect values quarantined with
their heartbeat verdict, best-known-value-with-provenance per label —
what ``scripts/perf_gate.py`` gates against); ``metrics.py`` folds the
event stream into an in-process registry (counters, gauges,
bounded-reservoir histograms) and ``serve.py`` puts the live HTTP face
on it (``--serve``: /metrics, /status.json, /events — rendered by
``scripts/obs_top.py``).

:func:`open_session` is the one-call wiring: trace writer + manifest +
runtime recorder + heartbeat, bundled in a :class:`Session`.  Telemetry
is an observer, never load-bearing: events record only at chunk/label
boundaries (the jitted step is untouched — pinned by jaxpr inspection
in tests), and callers guard session setup so a telemetry failure
cannot kill the run it watches.
"""

from __future__ import annotations

from typing import Any, Dict, Optional

from . import flightrec as flightrec_lib
from . import heartbeat as heartbeat_lib
from . import runtime as runtime_lib
from . import spans as spans_lib
from . import trace as trace_lib


class Session:
    """A live telemetry session: trace + recorder + optional heartbeat.

    ``recorder`` is the driver-facing observer
    (``record_chunk(steps, seconds)`` at chunk boundaries);
    ``event``/``finish``/``error`` write to the trace.  ``spans`` is
    the session's :class:`~.spans.SpanEmitter` (one causal timeline:
    the trace context is inherited from ``OBS_TRACE_CONTEXT`` — or the
    spawning thread — when a parent exported one).  ``finish`` and
    ``close`` are idempotent, and ``close`` always stops the heartbeat
    first so no verdict thread outlives its run, then emits the root
    span before the trace writer closes.
    """

    def __init__(self, trace: trace_lib.TraceWriter,
                 recorder: runtime_lib.RuntimeRecorder,
                 heartbeat: Optional[heartbeat_lib.Heartbeat],
                 spans: Optional[spans_lib.SpanEmitter] = None,
                 flight: Optional[flightrec_lib.FlightRecorder] = None):
        self.trace = trace
        self.recorder = recorder
        self.heartbeat = heartbeat
        self.spans = spans
        # the post-mortem ring (obs/flightrec.py): mirrors every trace
        # record in memory so a terminal verdict can emit a bundle even
        # after the telemetry dir is gone
        self.flight = flight
        self._finished = False

    @property
    def path(self) -> str:
        return self.trace.path

    def event(self, kind: str, **payload: Any) -> None:
        self.trace.event(kind, **payload)
        self.recorder.mark()

    def progress(self) -> None:
        """Liveness tick without an event (harness inner loops)."""
        self.recorder.mark()

    def finish(self, **payload: Any) -> None:
        """Write the summary event (once): runtime stats + caller extras."""
        if self._finished:
            return
        self._finished = True
        hb = (self.heartbeat.last_verdict if self.heartbeat is not None
              else None)
        self.trace.event("summary", runtime=self.recorder.summary(),
                         heartbeat=hb, **payload)

    def error(self, exc: BaseException) -> None:
        try:
            self.trace.event(
                "error", error=f"{type(exc).__name__}: {exc}"[:1200],
                runtime=self.recorder.summary())
        except Exception:  # noqa: BLE001 — already failing; don't mask
            pass

    def close(self) -> None:
        if self.heartbeat is not None:
            self.heartbeat.stop()
        if self.spans is not None:
            self.spans.close()  # root span: before the writer closes
        self.trace.close()

    def __enter__(self) -> "Session":
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        if exc is not None:
            self.error(exc)
        else:
            self.finish()
        self.close()


def open_session(
    path: str,
    tool: str,
    run: Dict[str, Any],
    step_unit: int = 1,
    stall_after_s: float = 600.0,
    with_heartbeat: bool = True,
    ensemble: int = 0,
    launcher: bool = False,
    **manifest_extra: Any,
) -> Session:
    """Open a trace at ``path``, write the manifest, start the heartbeat.

    The shared constructor all four tools call — the mechanism by which
    "same schema" is a property of the code rather than a convention.
    The session's span emitter adopts an inherited ``OBS_TRACE_CONTEXT``
    (or the spawning thread's pending context) so a supervised child's
    — or an engine request's — spans share the parent's trace_id; the
    manifest carries the ``trace`` identity block either way.
    ``launcher=True`` (a process that starts chip children) writes a
    manifest without touching any jax backend.
    """
    trace = trace_lib.TraceWriter(path)
    flight = flightrec_lib.FlightRecorder()
    trace.mirrors.append(flight.note)
    spans = spans_lib.SpanEmitter(trace, context=spans_lib.resolve_context(),
                                  root_name=tool)
    manifest_extra.setdefault("trace", spans.manifest_block())
    trace.write_manifest(trace_lib.build_manifest(
        tool, run, launcher=launcher, **manifest_extra))
    recorder = runtime_lib.RuntimeRecorder(trace=trace, step_unit=step_unit,
                                           ensemble=ensemble, spans=spans)
    hb = None
    if with_heartbeat:
        hb = heartbeat_lib.Heartbeat(recorder, trace=trace,
                                     stall_after_s=stall_after_s)
        hb.start()
    return Session(trace, recorder, hb, spans=spans, flight=flight)


__all__ = ["Session", "open_session"]
