"""Read the program's own regions and compile counter over a run's window.

The program records, in the process that ran the cell, its closed
regions (``obs/spans.closed_regions``: name, start, end on
``time.perf_counter``, the harness's clock) and its backend compiles and
cache loads by region (``obs/runtime.compile_counts``).  The readers run
in that process after the window, so they read both directly.

The window is found from the program's side: the window's last chunk
ends with its ``field_diagnostics`` call, and nothing after the window
calls it (the reference check runs its own), so the last
``sim.diagnostics`` region ends the window, and the window began
``window_s`` before.  A program without these records (one that predates
them) gives None, never an error.
"""

from __future__ import annotations

import statistics

DIAGNOSTICS = "sim.diagnostics"


def closed_regions(name):
    """The program's closed regions called ``name``, or None when the
    program keeps no such record."""
    try:
        from mpi_cuda_process_tpu.obs.spans import closed_regions as read
    except ImportError:
        return None
    return read(name)


def compile_counts(since=None, until=None):
    """The program's compile counter over ``[since, until)``, or None."""
    try:
        from mpi_cuda_process_tpu.obs.runtime import compile_counts as read
    except ImportError:
        return None
    return read(since=since, until=until)


def window(run):
    """``(t0, t1)`` of the window on ``time.perf_counter``, or None."""
    diag = closed_regions(DIAGNOSTICS)
    if not diag:
        return None
    t1 = diag[-1][2]
    t0 = t1 - run["window_s"]
    # one observation per chunk: anything else is not this window
    if sum(1 for _, s, _ in diag if s >= t0) != len(run["chunk_s"]):
        return None
    return t0, t1


def region_ms_p50(run, name):
    """Median duration of the window's ``name`` regions, in ms."""
    w = window(run)
    if w is None:
        return None
    ms = [(e - s) * 1e3 for _, s, e in closed_regions(name) or ()
          if w[0] <= s and e <= w[1]]
    if len(ms) != len(run["chunk_s"]):
        return None
    return statistics.median(ms)
