"""Median host time of the window's ``sim.diagnostics.fetch`` regions, ms.

The program's own region around ``field_diagnostics``'s one
``jax.device_get``: the wait for the device to finish the dispatched
reductions and hand over the scalars.  Read from the program's closed
regions (``perfbench/program.py``), one per window chunk; None where the
program records no regions.
"""

from perfbench import program


def read(run):
    return program.region_ms_p50(run, "sim.diagnostics.fetch")
