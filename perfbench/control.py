"""The control of the correctness check: the plain reference computed in
bfloat16 (the precision below the configuration's float32), put in the
program's place and judged by the same comparison.  It has to come out not
correct; its readings set the upper end of each limit (PERF.md).

    python3 perfbench/control.py --workload <cell> --seeds 1,2,3

Not part of a benchmark run.  Prints one JSON line per seed.
"""

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def control_numbers(config, traffic, seed, device, residual,
                    dtype="bfloat16"):
    """The compared numbers of the reference at ``dtype`` against the
    reference at the configuration's precision."""
    import jax.numpy as jnp

    from perfbench import check, spec

    ref = spec.reference(config)
    planes = check.plane_indices(config, seed)
    steps = traffic["steps_per_observation"]
    low = check.reference_answers(config, ref, seed, steps, check.FOLLOW,
                                  residual, planes, device,
                                  dtype=jnp.dtype(dtype))
    want = check.reference_answers(config, ref, seed, steps, check.FOLLOW,
                                   residual, planes, device)
    return check.gaps(low[0], low[1], want[0], want[1])


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True)
    a = p.parse_args(argv)
    sys.path.insert(0, ROOT)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
    import jax

    from perfbench import cell as cell_lib, check, spec
    from mpi_cuda_process_tpu import cli

    bench = spec.benchmark()
    w = spec.workload(bench, a.workload)
    config = spec.config(bench, w["config"])
    traffic = spec.traffic(w["traffic"])
    cli.enable_compile_cache()
    # the diagnostics the program reports: a residual on the unfused path
    cfg = cli.maybe_auto_fuse(cli.config_from_args(
        cell_lib.program_argv(config, traffic, 0)))
    for seed in (int(s) for s in a.seeds.split(",")):
        numbers = control_numbers(config, traffic, seed, jax.devices()[0],
                                  residual=not cfg.fuse)
        correct, checks = check.judge(numbers, config["limits"])
        print(json.dumps({"workload": a.workload, "seed": seed,
                          "correct": correct, "checks": checks}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
