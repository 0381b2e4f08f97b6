"""Median host time of the window's ``sim.diagnostics.stage`` regions, ms.

The program's own region (``utils/diagnostics.field_diagnostics``):
building and dispatching the reductions and, on the jnp path, the
residual step, with every compile or cache load that dispatch triggers.
Read from the program's closed regions (``perfbench/program.py``), one
per window chunk; None where the program records no regions.
"""

from perfbench import program


def read(run):
    return program.region_ms_p50(run, "sim.diagnostics.stage")
