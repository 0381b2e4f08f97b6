"""Per-chunk runtime stats: compile vs steady state, recorded host-side.

``driver.run_simulation`` calls :meth:`RuntimeRecorder.record_chunk`
once per chunk boundary with the chunk's wall time (measured around the
already-materializing runner call).  Everything here is host Python —
no jax primitive, no callback, no extra op inside the jitted
``lax.scan`` (tests/test_obs.py pins the step jaxpr byte-identical with
and without a recorder attached).  The cost of observation is one
``block_until_ready`` per chunk boundary, where the driver's callback
was about to materialize state anyway.

What a chunk record carries:

* wall seconds and ms/step (in REAL steps: the recorder knows the
  ``--fuse`` step unit);
* a recompile flag — read from the process's compile counter (below),
  so a chunk that triggered a compile AFTER the first chunk (shape
  drift, cache invalidation, a second chunk size) is marked instead of
  silently polluting the steady-state percentiles;
* ``device.memory_stats()`` peaks when the backend reports them (TPU
  does; CPU returns None and the field is omitted).

:meth:`summary` separates the first chunk (compile + warmup) from the
steady tail and reports p50/p90/best ms/step — the numbers
``scripts/obs_report.py`` renders next to the static cost model's
roofline prediction.
"""

from __future__ import annotations

import collections
import threading
import time
from typing import Any, Dict, List, Optional, Tuple

import jax

from .spans import current_region

# ------------------------------------------------------ compile counter
#
# One jax.monitoring listener for the whole program, registered when
# this module is imported (``obs/__init__`` imports it, so any import
# of the obs layer — the driver and the diagnostics import
# ``obs.spans`` — registers it before the program's first compile).
# The duration events it reads, as JAX 0.9 fires them:
#
# * ``/jax/core/compile/backend_compile_duration`` — once per backend
#   compile request, a persistent-cache hit included (the hit is
#   served inside it);
# * ``/jax/compilation_cache/cache_retrieval_time_sec`` — before that
#   event, on the same thread, when the request was a cache load.
#
# So each backend event is one compile or one load, and the two never
# double count.  Each is keyed by the innermost ``spans.region`` open
# on the compiling thread (``"(none)"`` outside any).
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_LOAD_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"
NO_REGION = "(none)"
_COMPILE_LOG_KEEP = 16384

_counter_lock = threading.Lock()
_load_pending = threading.local()
_totals: Dict[str, List[float]] = {}  # region -> [compiles, loads, s]
# (perf_counter at the end of the event, region, is_load, seconds)
_compile_log: "collections.deque[Tuple[float, str, bool, float]]" = \
    collections.deque(maxlen=_COMPILE_LOG_KEEP)


def _on_duration(event: str, duration: float, **_kw: Any) -> None:
    if event == CACHE_LOAD_EVENT:
        _load_pending.flag = True
        return
    if event != BACKEND_COMPILE_EVENT:
        return
    is_load = getattr(_load_pending, "flag", False)
    _load_pending.flag = False
    where = current_region() or NO_REGION
    with _counter_lock:
        tot = _totals.setdefault(where, [0, 0, 0.0])
        tot[1 if is_load else 0] += 1
        tot[2] += float(duration)
        _compile_log.append((time.perf_counter(), where, is_load,
                             float(duration)))


jax.monitoring.register_event_duration_secs_listener(_on_duration)


def compile_counts(since: Optional[float] = None,
                   until: Optional[float] = None
                   ) -> Dict[str, Dict[str, float]]:
    """``{region: {"compiles", "loads", "seconds"}}`` for this process.

    With ``since``/``until`` (``time.perf_counter`` seconds), only the
    events that ended in ``[since, until)``, from the last
    ``_COMPILE_LOG_KEEP`` events."""
    with _counter_lock:
        if since is None and until is None:
            rows = {k: tuple(v) for k, v in _totals.items()}
        else:
            lo = float("-inf") if since is None else since
            hi = float("inf") if until is None else until
            acc: Dict[str, List[float]] = {}
            for t, where, is_load, s in _compile_log:
                if lo <= t < hi:
                    row = acc.setdefault(where, [0, 0, 0.0])
                    row[1 if is_load else 0] += 1
                    row[2] += s
            rows = {k: tuple(v) for k, v in acc.items()}
    return {k: {"compiles": int(c), "loads": int(n), "seconds": s}
            for k, (c, n, s) in rows.items()}


def compile_events_seen() -> int:
    """Backend compiles and cache loads observed in this process."""
    with _counter_lock:
        return int(sum(c + n for c, n, _ in _totals.values()))


def device_memory_stats() -> Dict[str, int]:
    """Whitelisted ``memory_stats()`` of device 0, or {} when unreported."""
    try:
        stats = jax.devices()[0].memory_stats()
    except Exception:  # noqa: BLE001
        return {}
    if not stats:
        return {}
    return {k: int(stats[k])
            for k in ("bytes_in_use", "peak_bytes_in_use", "bytes_limit")
            if k in stats}


def _percentile(sorted_vals: List[float], q: float) -> float:
    """Nearest-rank percentile of an already-sorted list."""
    if not sorted_vals:
        return float("nan")
    idx = min(len(sorted_vals) - 1,
              max(0, int(round(q * (len(sorted_vals) - 1)))))
    return sorted_vals[idx]


class RuntimeRecorder:
    """Collects per-chunk wall times; optionally mirrors them to a trace.

    ``step_unit`` converts the driver's call-unit chunk sizes into real
    steps (``--fuse K`` advances K steps per call).  ``last_progress``
    (monotonic seconds) is the liveness signal the heartbeat watches.

    ``profiler`` (an :class:`~.profile.ChunkProfiler`, optional) rides
    the same chunk boundaries: its ``begin_chunk``/``end_chunk`` run
    strictly host-side where the driver already calls this recorder, so
    a ``--profile`` run scopes its ``jax.profiler`` trace to exactly
    one chunk without touching the jitted step (the zero-ops invariant
    extends to the profiler — pinned by tests/test_obs_profile.py).
    """

    def __init__(self, trace=None, step_unit: int = 1, profiler=None,
                 ensemble: int = 0, spans=None):
        self.trace = trace
        self.profiler = profiler
        # span emitter (obs/spans.py, optional): chunk 0 — the compile +
        # warmup chunk — is emitted as a "compile" span so the causal
        # timeline names the compile explicitly; steady chunks stay
        # events only (the exporter derives their slices from t/wall_s,
        # no event-volume doubling)
        self.spans = spans
        self.step_unit = max(1, int(step_unit))
        # batched runs: member count stamped on every chunk record so a
        # batched run is distinguishable from a fast single run in the
        # raw stream (aggregate vs per-member throughput is then one
        # division away — obs/metrics.RunMetrics does it)
        self.ensemble = max(0, int(ensemble))
        self.chunks: List[Dict[str, Any]] = []
        # run doctor (obs/anomaly.AnomalyMonitor, optional): consumes
        # each finished chunk record at the boundary the driver already
        # crossed — the zero-ops-in-the-jitted-step invariant extends to
        # the detector because it never sees anything but this dict
        self.anomaly = None
        self.recompiles = 0
        self.last_progress = time.monotonic()
        self._chunk_begin_compiles: Optional[int] = None

    def mark(self) -> None:
        """Record liveness without a chunk (benchmark harness loops)."""
        self.last_progress = time.monotonic()

    def begin_chunk(self) -> None:
        """Snapshot the compile counter as a chunk starts.

        Compiles landing BETWEEN chunks (the logging callback tracing
        its diagnostics reductions, a checkpoint save) are legitimate
        and must not read as hot-loop recompiles; only compiles between
        ``begin_chunk`` and ``record_chunk`` implicate the scan itself.
        """
        self.mark()
        if self.profiler is not None:
            self.profiler.begin_chunk(len(self.chunks))
        self._chunk_begin_compiles = compile_events_seen()

    def record_chunk(self, steps: int, seconds: float) -> Dict[str, Any]:
        """One chunk finished: ``steps`` call-units in ``seconds`` wall.

        The ONLY driver-facing entry point (with :meth:`begin_chunk`);
        called strictly at chunk boundaries, never from traced code.
        """
        self.mark()
        real_steps = int(steps) * self.step_unit
        n = len(self.chunks)
        profiled = (self.profiler is not None
                    and self.profiler.end_chunk(n))
        recompiled = False
        if self._chunk_begin_compiles is not None:
            during = compile_events_seen() - self._chunk_begin_compiles
            self._chunk_begin_compiles = None
            # first chunk: compiles are the expected warmup, not drift
            if n > 0 and during > 0:
                recompiled = True
                self.recompiles += during
        rec: Dict[str, Any] = {
            "chunk": n,
            "steps": real_steps,
            "wall_s": round(float(seconds), 6),
            "ms_per_step": round(seconds * 1e3 / max(1, real_steps), 6),
            "recompiled": recompiled,
        }
        if self.ensemble:
            # every member advanced the same real_steps this chunk —
            # the batched step is one program over all N
            rec["members"] = self.ensemble
        if profiled:
            rec["profiled"] = True
        mem = device_memory_stats()
        if mem:
            rec["memory"] = mem
        self.chunks.append(rec)
        if self.trace is not None:
            self.trace.event("chunk", **rec)
        if self.anomaly is not None:
            try:
                self.anomaly.observe_chunk(rec)
            except Exception:  # noqa: BLE001 — diagnosis never kills the run
                pass
        if n == 0 and self.spans is not None:
            self.spans.emit("compile", time.time() - float(seconds),
                            float(seconds), steps=real_steps,
                            ms_per_step=rec["ms_per_step"])
        return rec

    def summary(self) -> Dict[str, Any]:
        """Compile-separated aggregate: first chunk vs steady percentiles."""
        out: Dict[str, Any] = {
            "n_chunks": len(self.chunks),
            "steps": sum(c["steps"] for c in self.chunks),
            "recompiles": self.recompiles,
        }
        if not self.chunks:
            return out
        out["first_chunk_s"] = self.chunks[0]["wall_s"]
        out["first_chunk_ms_per_step"] = self.chunks[0]["ms_per_step"]
        # steady state = everything after the compile+warmup chunk; a
        # single-chunk run has no steady sample and says so rather than
        # passing compile time off as throughput
        steady = [c for c in self.chunks[1:] if not c["recompiled"]]
        if steady:
            per = sorted(c["ms_per_step"] for c in steady)
            out["steady"] = {
                "chunks": len(per),
                "ms_per_step_best": per[0],
                "ms_per_step_p50": _percentile(per, 0.50),
                "ms_per_step_p90": _percentile(per, 0.90),
            }
        peaks = [c["memory"].get("peak_bytes_in_use")
                 for c in self.chunks if "memory" in c]
        peaks = [p for p in peaks if p is not None]
        if peaks:
            out["memory_peak_bytes"] = max(peaks)
        return out
