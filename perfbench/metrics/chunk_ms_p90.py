"""90th percentile of the chunk wall time over all chunks of the window.

A chunk runs from the dispatch of its steps to its diagnostics landing on
the host (host clock); the chunks tile the window, Python loop included.
"""

import statistics


def read(run):
    ms = [s * 1e3 for s in run["chunk_s"]]
    if len(ms) < 2:
        return None
    return statistics.quantiles(ms, n=10, method="inclusive")[-1]
