"""Cell updates completed in the window per second per chip, in millions.

All the work over all the time: advancing steps of every window chunk
times the global cell count, over the window's host-clock seconds (the
chunks tile the window), over the chips.  The residual step that the
diagnostics of the jnp path run advances nothing and is not counted.
"""


def read(run):
    cells = len(run["chunk_s"]) * run["steps_per_chunk"] * run["cells"]
    return cells / run["window_s"] / run["chips"] / 1e6
