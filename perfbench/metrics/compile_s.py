"""Host clock of the runner's lower+compile (or persistent-cache load) plus
the first ``field_diagnostics`` call, which compiles or loads its
reductions (and, on the jnp path, the residual step) and runs them once."""


def read(run):
    return run["compile_s"]
