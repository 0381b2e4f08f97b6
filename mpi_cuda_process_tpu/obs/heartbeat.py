"""Stall-detecting heartbeat: in-process STALLED / RECOVERED verdicts.

A daemon thread watches a progress signal (a
:class:`~..obs.runtime.RuntimeRecorder`'s ``last_progress`` or any
monotonic-time callable) and, when no progress lands for
``stall_after_s``, writes a STALLED verdict event to the trace.  One
verdict per stall episode (no event spam); progress landing again emits
RECOVERED and re-arms.

The heartbeat never touches the device from another process: the run
holds the chip, and a probe child could only fail there.  A WEDGED
verdict comes only from the ``heartbeat:wedge`` fault seam
(resilience/faults.py), the supervisor's deterministic kill trigger.
"""

from __future__ import annotations

import threading
import time
from typing import Any, Callable, Dict, Optional, Union

from ..resilience import faults

# Progress source: an object exposing ``last_progress`` (monotonic
# seconds, RuntimeRecorder) or a zero-arg callable returning the same.
ProgressSource = Union[Callable[[], float], Any]


def _last_progress(source: ProgressSource) -> float:
    if callable(source):
        return float(source())
    return float(source.last_progress)


class Heartbeat(threading.Thread):
    """Watch a progress source; write STALLED/RECOVERED verdicts to a trace.

    ``trace`` receives ``heartbeat`` events; ``last_verdict`` always
    holds the newest one.
    """

    def __init__(self, source: ProgressSource, trace=None,
                 stall_after_s: float = 300.0,
                 poll_s: Optional[float] = None,
                 clock: Callable[[], float] = time.monotonic):
        super().__init__(daemon=True, name="obs-heartbeat")
        self.source = source
        self.trace = trace
        self.stall_after_s = float(stall_after_s)
        self.poll_s = poll_s if poll_s is not None else \
            min(30.0, max(0.05, self.stall_after_s / 4.0))
        self.clock = clock
        self.last_verdict: Dict[str, Any] = {"verdict": "ALIVE",
                                             "detail": "no stall observed"}
        self._stop_evt = threading.Event()
        self._stalled_episode = False

    def _emit(self, verdict: str, detail: str, **payload: Any) -> None:
        self.last_verdict = {"verdict": verdict, "detail": detail, **payload}
        if self.trace is not None:
            try:
                self.trace.event("heartbeat", verdict=verdict,
                                 detail=detail, **payload)
            except Exception:  # noqa: BLE001 — observer, never load-bearing
                pass

    def run(self) -> None:
        while not self._stop_evt.wait(self.poll_s):
            idle = self.clock() - _last_progress(self.source)
            if idle < self.stall_after_s:
                if self._stalled_episode:
                    self._stalled_episode = False
                    self._emit("RECOVERED",
                               "progress resumed after a stall",
                               idle_s=round(idle, 2))
                continue
            if self._stalled_episode:
                continue  # one verdict per episode, no event spam
            self._stalled_episode = True
            self._emit("STALLED",
                       f"no progress for {idle:.1f}s "
                       f"(threshold {self.stall_after_s:.1f}s)",
                       idle_s=round(idle, 2))
            # Fault point (resilience/faults.py): heartbeat:wedge turns
            # the stall into a deterministic WEDGED verdict — the
            # supervisor's kill-on-verdict path gets a CPU trigger.
            wedge = faults.injected_heartbeat_verdict()
            if wedge is not None:
                self._emit("WEDGED", wedge.get("detail", ""),
                           idle_s=round(self.clock()
                                        - _last_progress(self.source), 2))

    def stop(self, join_timeout_s: float = 5.0,
             final_verdict: str = "SUPERVISOR_KILL") -> None:
        """Stop the watcher.  NEVER raises — the supervisor kill path
        runs this while tearing down a wedged run, where a secondary
        exception would mask the wedge it is reporting.

        An open stall episode is CLOSED with a final ``final_verdict``
        event (default ``SUPERVISOR_KILL``: the run was stopped from
        outside while stalled) instead of being left dangling — a trace
        ending mid-episode is indistinguishable from a writer that died.
        The thread cannot outlive a closed trace: ``_emit`` swallows
        writer errors and ``TraceWriter`` drops post-close writes, so
        even a join timeout leaves nothing that can raise into the
        closing run.
        """
        try:
            if self._stalled_episode:
                self._stalled_episode = False
                self._emit(final_verdict,
                           "watcher stopped while a stall episode was "
                           "open (supervisor kill / teardown path)")
        except Exception:  # noqa: BLE001 — teardown must not raise
            pass
        try:
            self._stop_evt.set()
            if self.is_alive():
                self.join(join_timeout_s)
        except Exception:  # noqa: BLE001
            pass
