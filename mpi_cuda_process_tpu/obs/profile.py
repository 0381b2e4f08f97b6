"""Device-trace attribution: what the device ACTUALLY did with a chunk.

Everything else in obs/ predicts or book-keeps: costmodel counts what a
step *should* cost, runtime.py times what the host *saw*.  Whether
``--overlap``/``--pipeline`` really hide the exchange was, until this
module, only a roofline prediction.  This module measures it:

* :class:`ChunkProfiler` — a ``jax.profiler`` session wrapper scoped to
  ONE chunk (by default the first steady-state chunk, after the
  compile+warmup chunk), attached to the
  :class:`~.runtime.RuntimeRecorder` the driver already calls at chunk
  boundaries.  With ``--profile`` off, nothing here is constructed and
  the jitted step jaxpr stays byte-identical (the telemetry invariant,
  extended by tests/test_obs_profile.py); with it on, ``start_trace``/
  ``stop_trace`` run strictly at chunk boundaries — never inside the
  scan.
* a reader of the profiler's newest ``.xplane.pb``
  (:func:`load_trace_events`, through ``jax.profiler.ProfileData``:
  the leaf ops of every TPU plane, as Chrome-trace-shaped events) and
  an attribution pass
  (:func:`attribute_events`) that buckets device time into
  interior-compute vs ppermute/collective (the exchange) and computes
  the **measured overlap efficiency**::

      overlap_efficiency = 1 - exposed_comm / total_comm

  where exposed comm is exchange time NOT covered by concurrent
  compute (interval arithmetic over the device lanes).  Recorded in
  the telemetry log as a ``profile`` event next to costmodel's
  ``overlapped`` vs ``serial`` roofline predictions, so predicted-vs-
  measured hiding is one line in ``scripts/obs_report.py``.

Honesty rule: on CPU (the profiler emits host lanes only) or when the
trace yields no device events, the record says ``attribution:
unavailable`` with the reason — never fabricated zeros.
"""

from __future__ import annotations

import glob
import os
import re
from typing import Any, Dict, List, Optional, Sequence, Tuple

# Event-name classification for the exchange bucket.  ppermute lowers to
# collective-permute on TPU; the rest cover the collectives any future
# stepper might issue.  Lowercased substring match on the op's own name.
_COMM_MARKERS = (
    "ppermute", "collective-permute", "collective_permute",
    "all-reduce", "all_reduce", "all-gather", "all_gather",
    "all-to-all", "all_to_all", "reduce-scatter", "reduce_scatter",
    "send", "recv",
)
# a TPU op event is named by its whole HLO instruction
# (``%fusion.3 = f32[8]{0} fusion(... %collective-permute-done.2)``):
# the instruction's own name and kind, never its operands
_HLO = re.compile(r"%?([\w.\-]+) = .*?\s([a-z][\w\-]*)\(")
_DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
_OP_LINES = ("XLA Ops", "Async XLA Ops")


def is_comm_event(name: str) -> bool:
    """A collective, judged by the op's own name and kind."""
    m = _HLO.match(str(name))
    own = f"{m.group(1)} {m.group(2)}" if m else str(name)
    low = own.lower()
    return any(mk in low for mk in _COMM_MARKERS)


# ------------------------------------------------------------ trace IO

def find_trace_files(profile_dir: str) -> List[str]:
    """``.xplane.pb`` files under a ``jax.profiler`` output dir, oldest
    first (the profiler writes ``plugins/profile/<run>/<host>.xplane
    .pb``)."""
    found = glob.glob(os.path.join(profile_dir, "**", "*.xplane.pb"),
                      recursive=True)
    return sorted(set(found), key=lambda p: (os.path.getmtime(p), p))


def _leaves(ops: List[Tuple[str, float, float]]
            ) -> List[Tuple[str, float, float]]:
    """The ops that hold no other op: a ``while`` op spans its body's
    ops, collectives included, and must not count as compute beside
    them."""
    ops = sorted(ops, key=lambda o: (o[1], -o[2]))
    return [o for o, nxt in zip(ops, ops[1:] + [None])
            if nxt is None or not (nxt[1] < o[2] and nxt[2] <= o[2])]


def load_trace_events(profile_dir: str) -> List[Dict[str, Any]]:
    """Events of the NEWEST ``.xplane.pb`` under ``profile_dir``.

    Read with ``jax.profiler.ProfileData``: every plane becomes one pid
    with a ``process_name`` record; a TPU plane's leaf ops of ``XLA
    Ops`` and its ``Async XLA Ops`` become complete (``"X"``) events in
    microseconds.  Returns ``[]`` when no trace file exists — the caller
    degrades to ``attribution: unavailable`` rather than guessing.
    """
    files = find_trace_files(profile_dir)
    if not files:
        return []
    from jax.profiler import ProfileData

    data = ProfileData.from_file(files[-1])
    events: List[Dict[str, Any]] = []
    for pid, plane in enumerate(data.planes):
        events.append({"ph": "M", "pid": pid, "name": "process_name",
                       "args": {"name": plane.name}})
        if not _DEVICE_PLANE.match(plane.name):
            continue
        for line in plane.lines:
            if line.name not in _OP_LINES:
                continue
            ops = [(e.name, e.start_ns, e.end_ns) for e in line.events]
            if line.name == "XLA Ops":
                ops = _leaves(ops)
            events.extend({"ph": "X", "pid": pid, "tid": line.name,
                           "name": n, "ts": s * 1e-3,
                           "dur": (e - s) * 1e-3} for n, s, e in ops)
    return events


# -------------------------------------------------- interval arithmetic

def _merge(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """Sorted union of half-open intervals."""
    out: List[Tuple[float, float]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def _total(merged: Sequence[Tuple[float, float]]) -> float:
    return sum(e - s for s, e in merged)


def _intersection_total(a: Sequence[Tuple[float, float]],
                        b: Sequence[Tuple[float, float]]) -> float:
    """Total overlap between two MERGED interval lists (two-pointer)."""
    i = j = 0
    tot = 0.0
    while i < len(a) and j < len(b):
        s = max(a[i][0], b[j][0])
        e = min(a[i][1], b[j][1])
        if e > s:
            tot += e - s
        if a[i][1] <= b[j][1]:
            i += 1
        else:
            j += 1
    return tot


# ---------------------------------------------------------- attribution

def device_pids(events: Sequence[Dict[str, Any]]) -> List[int]:
    """pids whose ``process_name`` marks a device lane group.

    The TF profiler names processes ``/device:TPU:0`` (device) vs
    ``/host:CPU`` (host python/runtime threads).  Host lanes carry
    python frames and must never be attributed as device compute.
    """
    pids = []
    for e in events:
        if e.get("ph") != "M" or e.get("name") != "process_name":
            continue
        name = str((e.get("args") or {}).get("name", ""))
        _, sep, dev = name.partition("/device:")
        if sep and not dev.upper().startswith("CPU"):
            pids.append(e.get("pid"))
    return sorted({p for p in pids if p is not None})


def attribute_events(events: Sequence[Dict[str, Any]]) -> Dict[str, Any]:
    """Bucket device-lane time: interior compute / exchange / exposed.

    Complete events (``ph == "X"``) on device pids only.  ``comm`` is
    the union of collective-op intervals, ``compute`` the union of
    everything else on the device lanes; ``exposed_comm`` is comm time
    with no concurrent compute — the part of the exchange the schedule
    failed to hide.  All durations in trace microseconds.
    """
    pids = set(device_pids(events))
    if not pids:
        return {"attribution": "unavailable",
                "reason": "no device lanes in the trace (CPU backend, or "
                          "a profiler run that captured host events only)"}
    comm: List[Tuple[float, float]] = []
    compute: List[Tuple[float, float]] = []
    n = 0
    for e in events:
        if e.get("ph") != "X" or e.get("pid") not in pids:
            continue
        try:
            s = float(e["ts"])
            d = float(e.get("dur", 0.0))
        except (KeyError, TypeError, ValueError):
            continue
        if d <= 0:
            continue
        n += 1
        (comm if is_comm_event(e.get("name", "")) else compute).append(
            (s, s + d))
    if n == 0:
        return {"attribution": "unavailable",
                "reason": "device lanes present but carry no complete "
                          "events"}
    comm_m, compute_m = _merge(comm), _merge(compute)
    comm_us = _total(comm_m)
    compute_us = _total(compute_m)
    hidden_us = _intersection_total(comm_m, compute_m)
    exposed_us = comm_us - hidden_us
    busy_us = _total(_merge(list(comm_m) + list(compute_m)))
    out: Dict[str, Any] = {
        "attribution": "ok",
        "n_device_events": n,
        "device_busy_us": round(busy_us, 3),
        "compute_us": round(compute_us, 3),
        "comm_us": round(comm_us, 3),
        "exposed_comm_us": round(exposed_us, 3),
        # 1 - exposed/total: 1.0 = exchange fully hidden behind compute,
        # 0.0 = fully serial.  None when the trace carries no exchange
        # at all (an unsharded run) — "no comm" is not "perfect hiding".
        "overlap_efficiency": (round(1.0 - exposed_us / comm_us, 4)
                               if comm_us > 0 else None),
    }
    return out


def attribution_record(profile_dir: str,
                       profiled_chunk: Optional[int] = None,
                       error: Optional[str] = None) -> Dict[str, Any]:
    """The ``profile`` telemetry event payload for a finished run."""
    rec: Dict[str, Any] = {
        "profile_dir": os.path.abspath(profile_dir),
        "profiled_chunk": profiled_chunk,
    }
    if error:
        rec.update(attribution="unavailable",
                   reason=f"profiler error: {error}")
        return rec
    if profiled_chunk is None:
        rec.update(attribution="unavailable",
                   reason="no chunk reached the profile scope (run ended "
                          "before the target chunk)")
        return rec
    try:
        events = load_trace_events(profile_dir)
    except Exception as e:  # noqa: BLE001 — a corrupt trace must not
        rec.update(attribution="unavailable",  # kill the run epilogue
                   reason=f"trace parse failed: {type(e).__name__}: {e}")
        return rec
    if not events:
        rec.update(attribution="unavailable",
                   reason="no .xplane.pb emitted under the profile dir")
        return rec
    rec.update(attribute_events(events))
    return rec


def format_attribution(rec: Dict[str, Any]) -> str:
    """One human line for logs/obs_report."""
    if rec.get("attribution") != "ok":
        return f"attribution unavailable ({rec.get('reason')})"
    eff = rec.get("overlap_efficiency")
    parts = [
        f"compute {rec['compute_us'] / 1e3:.3f} ms",
        f"comm {rec['comm_us'] / 1e3:.3f} ms",
        f"exposed {rec['exposed_comm_us'] / 1e3:.3f} ms",
    ]
    parts.append("no exchange in trace" if eff is None
                 else f"measured overlap efficiency {eff:.2%}")
    return "  ".join(parts)


# ------------------------------------------------------- chunk profiler

class ChunkProfiler:
    """Scope one ``jax.profiler`` trace to one chunk of a run.

    Attached as ``recorder.profiler``; the
    :class:`~.runtime.RuntimeRecorder` calls :meth:`begin_chunk` /
    :meth:`end_chunk` with the chunk index at the boundaries the driver
    already observes.  ``target_chunk`` defaults to 1 — the first
    chunk after compile+warmup, i.e. steady state.  One trace per run:
    after the target chunk is captured, later chunks are ignored.

    ``start``/``stop`` are injectable for tests; production uses
    ``jax.profiler.start_trace``/``stop_trace``.  A profiler failure is
    recorded in ``self.error`` and never propagates — observation must
    not kill the run it observes.
    """

    def __init__(self, outdir: str, target_chunk: int = 1,
                 start=None, stop=None):
        if start is None or stop is None:
            import jax

            start = start or jax.profiler.start_trace
            stop = stop or jax.profiler.stop_trace
        self.outdir = outdir
        self.target_chunk = int(target_chunk)
        self._start = start
        self._stop = stop
        self.active = False
        self.profiled_chunk: Optional[int] = None
        self.error: Optional[str] = None

    def begin_chunk(self, chunk_index: int) -> bool:
        """Start the trace iff this is the target chunk (once per run)."""
        if self.active or self.profiled_chunk is not None:
            return False
        if int(chunk_index) != self.target_chunk:
            return False
        try:
            os.makedirs(self.outdir, exist_ok=True)
            self._start(self.outdir)
            self.active = True
        except Exception as e:  # noqa: BLE001
            self.error = f"{type(e).__name__}: {e}"
        return self.active

    def end_chunk(self, chunk_index: int) -> bool:
        """Stop the trace if running; True iff this chunk was captured."""
        if not self.active:
            return False
        try:
            self._stop()
        except Exception as e:  # noqa: BLE001
            self.error = f"{type(e).__name__}: {e}"
        self.active = False
        self.profiled_chunk = int(chunk_index)
        return True

    def close(self) -> None:
        """Abort path: stop a still-open trace so the next run can start
        one (jax refuses nested sessions).  Idempotent."""
        if self.active:
            try:
                self._stop()
            except Exception as e:  # noqa: BLE001
                self.error = f"{type(e).__name__}: {e}"
            self.active = False
