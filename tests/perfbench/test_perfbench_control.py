"""The control of the correctness check, at a size a test run holds: the
plain reference in bfloat16 put in the program's place is not correct, on
three seeds, under each configuration's limits.  (On the chip, at the
cells' own size: ``python3 perfbench/control.py``; readings in PERF.md.)"""

import jax
import pytest

from perfbench import check, control, spec

TINY = [32, 32, 128]


@pytest.mark.parametrize("workload,residual", [
    ("heat3d-1024.log16", False), ("heat3d-1024-2x2.log8", True)])
@pytest.mark.parametrize("seed", [7, 2**31 + 99, 3_000_000_001])
def test_bfloat16_control_is_not_correct(workload, residual, seed):
    bench = spec.benchmark()
    w = spec.workload(bench, workload)
    config = dict(spec.config(bench, w["config"]), grid=TINY)
    traffic = spec.traffic(w["traffic"])
    numbers = control.control_numbers(config, traffic, seed,
                                      jax.devices()[0], residual)
    correct, checks = check.judge(numbers, config["limits"])
    assert not correct, checks


def test_reference_against_itself_is_correct():
    bench = spec.benchmark()
    config = dict(spec.config(bench, "heat3d-1024"), grid=TINY)
    traffic = spec.traffic("log16")
    numbers = control.control_numbers(config, traffic, 5, jax.devices()[0],
                                      False, dtype="float32")
    assert numbers == {k: 0.0 for k in check.NAMES}
