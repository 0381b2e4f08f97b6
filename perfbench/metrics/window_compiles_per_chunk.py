"""Backend compiles plus persistent-cache loads in the window, per chunk.

From the program's compile counter (``obs/runtime.compile_counts``, one
``jax.monitoring`` listener, each event keyed by the program region that
triggered it), over the window that ``perfbench/program.py`` finds.  The
harness prints its own count of the same events to stderr ("compiles in
window"); this reads the program's.  None where the program keeps no
counter.
"""

from perfbench import program


def read(run):
    w = program.window(run)
    if w is None:
        return None
    counts = program.compile_counts(since=w[0], until=w[1])
    if counts is None:
        return None
    n = sum(c["compiles"] + c["loads"] for c in counts.values())
    return n / len(run["chunk_s"])
