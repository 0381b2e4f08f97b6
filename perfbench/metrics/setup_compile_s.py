"""Seconds of backend compiles and persistent-cache loads before the window.

From the program's compile counter (``obs/runtime.compile_counts``):
every compile or load from process start to the start of the window
(``perfbench/program.py``), whatever region it fell in: the auto-fuse
probe, the build and seeded init, the runner, the first diagnostics, the
harness's digest.  None where the program keeps no counter.
"""

from perfbench import program


def read(run):
    w = program.window(run)
    if w is None:
        return None
    counts = program.compile_counts(until=w[0])
    if counts is None:
        return None
    return sum(c["seconds"] for c in counts.values())
