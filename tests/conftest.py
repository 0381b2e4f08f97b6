"""Test configuration: run everything on 8 virtual CPU devices.

The TPU-world equivalent of testing MPI code without mpirun (SURVEY.md §4.4):
``--xla_force_host_platform_device_count=8`` gives every mesh / sharding /
ppermute test 8 fake devices on one host.  The recipe lives in repo-root
``cpuforce.py``, which deliberately does NOT import the package, so env vars
are set before any framework (and hence jax-backend) code runs.  The chip
runs are ``chip_smoke.py``'s, not tests.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from cpuforce import force_cpu  # noqa: E402


def pytest_configure(config):
    # The benchmark drivers auto-ingest into the campaign ledger
    # (obs/ledger.py); tests that exercise them must never append to the
    # repo's committed benchmarks/ledger.jsonl.  An explicit pre-set
    # path (a test harness choosing its own) is left untouched.
    if "OBS_LEDGER_PATH" not in os.environ:
        import tempfile

        os.environ["OBS_LEDGER_PATH"] = os.path.join(
            tempfile.mkdtemp(prefix="obs-ledger-test-"), "ledger.jsonl")
    # Leave an explicit pre-set device count untouched so an outer harness
    # can choose its own count via XLA_FLAGS.
    _n = (
        None
        if "xla_force_host_platform_device_count"
        in os.environ.get("XLA_FLAGS", "")
        else 8
    )
    # Tests never write the checkout's compile cache (cli.
    # enable_compile_cache is a no-op while this is off; the env var
    # carries it into child processes); the cache's own tests turn it
    # back on around themselves.
    os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "0"
    force_cpu(_n)
    import jax

    jax.config.update("jax_enable_compilation_cache", False)
