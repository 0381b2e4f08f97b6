"""Median host time of the window's ``field_diagnostics`` calls, from the
call (the chunk's state already ready) to the fetched scalars: the
``perfbench.diagnostics`` span of every chunk."""

import statistics


def read(run):
    if not run["observe_s"]:
        return None
    return statistics.median(run["observe_s"]) * 1e3
