"""Reduce the profiler's trace of a window to the numbers the readers take.

``load`` reads the newest ``.xplane.pb`` under a trace directory with
``jax.profiler.ProfileData``: for every TPU device plane the ops of its
``XLA Ops`` and ``Async XLA Ops`` lines and the program executions of its
``XLA Modules`` line,
and from the host planes the harness's own ``perfbench.*`` spans.
``reduce`` is plain interval arithmetic on those lists, so a test can feed
it a synthetic trace.  The interval union and the exposed-collective
arithmetic are copied from ``obs/profile.attribute_events``.
"""

from __future__ import annotations

import bisect
import collections
import glob
import os
import re

_DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
# copied from obs/profile._COMM_MARKERS: collectives, lowercased substrings
_COMM_MARKERS = (
    "ppermute", "collective-permute", "collective_permute",
    "all-reduce", "all_reduce", "all-gather", "all_gather",
    "all-to-all", "all_to_all", "reduce-scatter", "reduce_scatter",
    "send", "recv",
)
_SPAN_PREFIX = "perfbench."
_TOP = 10


_HLO = re.compile(r"%?([\w.\-]+) = .*?\s([a-z][\w\-]*)\(")


def op_name(name):
    """``%copy.11 = f32[...] copy(...)`` -> ``copy.11 (copy)``: an op event
    of a TPU trace is named by its whole HLO instruction."""
    m = _HLO.match(name)
    return f"{m.group(1)} ({m.group(2)})" if m else name


def is_collective(name):
    """Judged by the op's own name and kind, not by its operands'."""
    low = op_name(name).lower()
    return any(m in low for m in _COMM_MARKERS)


def load(trace_dir):
    """{"devices": {plane: {"ops", "async", "modules": [(name, s, e)]}},
    "spans": [(name, s, e)]} in seconds, from the newest trace."""
    from jax.profiler import ProfileData

    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise RuntimeError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(max(files, key=os.path.getmtime))
    devices, spans = {}, []
    for plane in data.planes:
        if _DEVICE_PLANE.match(plane.name):
            lines = {ln.name: ln for ln in plane.lines}
            devices[plane.name] = {
                key: [(e.name, e.start_ns * 1e-9, e.end_ns * 1e-9)
                      for e in lines[line].events] if line in lines else []
                for key, line in (("ops", "XLA Ops"),
                                  ("async", "Async XLA Ops"),
                                  ("modules", "XLA Modules"))}
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                spans.extend((e.name, e.start_ns * 1e-9, e.end_ns * 1e-9)
                             for e in ln.events
                             if e.name.startswith(_SPAN_PREFIX))
    return {"devices": devices, "spans": spans}


# ------------------------------------------- interval arithmetic (copied)

def merge(intervals):
    """Sorted union of half-open intervals."""
    out = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def total(merged):
    return sum(e - s for s, e in merged)


def intersection_total(a, b):
    """Total overlap of two merged interval lists (two-pointer)."""
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


def clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def gaps(merged, lo, hi):
    """The idle intervals of ``[lo, hi)`` between merged busy intervals."""
    out, t = [], lo
    for s, e in merged:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


# ------------------------------------------------------------ reduction

def _module_of(name):
    """``jit_run(12)`` -> ``jit_run``: the XLA module of an execution."""
    return name.split("(", 1)[0]


def leaves(ops):
    """The ops that hold no other op: a ``while`` op spans its body's ops,
    collectives included, and must not count as compute beside them."""
    ops = sorted(ops, key=lambda o: (o[1], -o[2]))
    return [o for o, nxt in zip(ops, ops[1:] + [None])
            if nxt is None or not (nxt[1] < o[2] and nxt[2] <= o[2])]


def _label(span_index, t):
    """The innermost harness span around time ``t``."""
    best = None
    for name, s, e in span_index:
        if s <= t < e and (best is None or e - s < best[2] - best[1]):
            best = (name, s, e)
    return f"host in {best[0]}" if best else "host outside the chunk loop"


def reduce(trace, runner_module):
    """Per-chip busy, idle, exchange and runner numbers over the window,
    and the breakdown: the longest-running device ops and idle gaps."""
    chunks = [(s, e) for n, s, e in trace["spans"]
              if n == _SPAN_PREFIX + "chunk"]
    if not chunks:
        raise RuntimeError("the trace holds no perfbench.chunk span")
    lo, hi = min(s for s, _ in chunks), max(e for _, e in chunks)
    window = hi - lo
    devices = []
    op_time = collections.Counter()
    idle = []
    for name in sorted(trace["devices"]):
        dev = trace["devices"][name]
        ops = [(n, s, e) for n, s, e in leaves(dev["ops"])
               + dev.get("async", []) if e > lo and s < hi]
        comm = merge(clip([(s, e) for n, s, e in ops if is_collective(n)],
                          lo, hi))
        compute = merge(clip([(s, e) for n, s, e in ops
                              if not is_collective(n)], lo, hi))
        busy = merge(comm + compute)
        runs = sorted((s, e) for n, s, e in dev["modules"]
                      if _module_of(n) == runner_module and s >= lo
                      and e <= hi)
        starts = [s for s, _ in runs]
        runner_ops = []
        for n, s, e in ops:
            op_time[op_name(n)] += e - s
            i = bisect.bisect_right(starts, s) - 1
            if not is_collective(n) and i >= 0 and s < runs[i][1]:
                runner_ops.append((s, e))
        comm_s = total(comm)
        devices.append({
            "name": name, "busy_s": total(busy),
            "idle_frac": 1.0 - total(busy) / window,
            "comm_s": comm_s,
            "exposed_comm_s": comm_s - intersection_total(comm, compute),
            # ops nest (a while op holds the kernel calls): take the union
            "runner_execs": len(runs),
            "runner_compute_s": total(merge(runner_ops)),
        })
        idle.extend(gaps(busy, lo, hi))
    n_dev = max(1, len(devices))
    top_ops = [[n, t / n_dev] for n, t in op_time.most_common(_TOP)]
    span_index = [(n, s, e) for n, s, e in trace["spans"]]
    idle.sort(key=lambda g: g[1] - g[0], reverse=True)
    top_gaps = [[_label(span_index, (s + e) / 2), e - s]
                for s, e in idle[:_TOP]]
    return {
        "window_s": window,
        "busy_s": sum(d["busy_s"] for d in devices) / n_dev,
        "devices": devices,
        "breakdown": {"device_ops": top_ops, "idle_gaps": top_gaps},
    }
