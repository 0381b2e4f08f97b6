"""Bisect the bf16 temporal-blocking Mosaic compile hang.

Round-3 finding (docs/STATE.md): bf16 fused k=4 was structurally
misaligned (sublane tile 16 vs 8 — fixed, now declines cleanly), but the
aligned k=8 variant HANGS the Mosaic compile (>20 min at 256^3 with the
auto-picked 64x64 tiles).  This script walks the candidate ladder —
smaller tiles first (less code after unrolling the 8 micro-steps), then
grid sizes — each attempt in its own subprocess with a hard timeout, so a
hang costs one attempt and the results name the exact frontier.

Run it ONLY when the chip is free and nothing else is using the
chip (a hung compile costs its budget).

Usage: python benchmarks/bisect_bf16_fused.py [--timeout 600]
"""

import argparse
import json
import os
import subprocess
import sys
import time

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# (label, grid, k, tiles, padfree) — smallest/cheapest first so the first
# hang gives the tightest bound.  k=8 now lowers as a fori_loop (constant
# program size — the candidate fix for the round-3 unrolled-compile hang);
# the padfree rungs cover the 9-block kernel's compile too.
ATTEMPTS = [
    ("256_k8_t16", (256, 256, 256), 8, (16, 16), False),
    ("256_k8_t32", (256, 256, 256), 8, (32, 32), False),
    ("256_k8_t64", (256, 256, 256), 8, (64, 64), False),  # the known ~hang
    ("256_k8_t32_padfree", (256, 256, 256), 8, (32, 32), True),
    ("512_k8_t32", (512, 512, 512), 8, (32, 32), False),
    ("512_k8_t32_padfree", (512, 512, 512), 8, (32, 32), True),
]

_CHILD = """\
import sys, time, math
sys.path.insert(0, {repo!r})
import jax, jax.numpy as jnp
from mpi_cuda_process_tpu import init_state, make_stencil
from mpi_cuda_process_tpu.driver import make_runner
from mpi_cuda_process_tpu.ops.pallas.fused import make_fused_step

grid, k, tiles, padfree = {grid!r}, {k!r}, {tiles!r}, {padfree!r}
st = make_stencil("heat3d", dtype=jnp.bfloat16)
step = make_fused_step(st, grid, k, tiles=tiles, padfree=padfree)
assert step is not None, "untileable"
f = init_state(st, grid, kind="pulse")
t0 = time.time()
out = step(f)
s = float(jnp.sum(out[0].astype(jnp.float32)))
t_compile = time.time() - t0
# quick throughput probe: one scanned pass of 4 calls (32 steps)
run = make_runner(step, 4)
float(jnp.sum(run(init_state(st, grid, kind="pulse"))[0].astype(jnp.float32)))
t0 = time.time()
float(jnp.sum(run(init_state(st, grid, kind="pulse"))[0].astype(jnp.float32)))
dt = time.time() - t0
print("RESULT", t_compile, math.prod(grid) * 4 * k / dt / 1e6, flush=True)
"""


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--timeout", type=int, default=600)
    ap.add_argument("--out", default=os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "bisect_bf16.json"))
    args = ap.parse_args()

    results = {}
    for label, grid, k, tiles, padfree in ATTEMPTS:
        code = _CHILD.format(repo=_REPO, grid=grid, k=k, tiles=tiles,
                             padfree=padfree)
        t0 = time.time()
        try:
            p = subprocess.run([sys.executable, "-c", code], cwd=_REPO,
                               capture_output=True, text=True,
                               timeout=args.timeout)
            out = p.stdout.strip().splitlines()
            if p.returncode == 0 and out and out[-1].startswith("RESULT"):
                _, t_compile, mcells = out[-1].split()
                results[label] = {"ok": True,
                                  "compile_s": round(float(t_compile), 1),
                                  "mcells_per_s": round(float(mcells), 1)}
            else:
                tail = (p.stderr or "")[-600:]
                results[label] = {"ok": False, "rc": p.returncode,
                                  "stderr_tail": tail}
        except subprocess.TimeoutExpired:
            results[label] = {"ok": False,
                              "error": f"timeout {args.timeout}s (hang)"}
            # a killed compile costs the chip call; stop the ladder
            results["_aborted"] = ("stopped after first hang to protect "
                                   "the chip call")
            break
        results[label]["wall_s"] = round(time.time() - t0, 1)
        print(f"[bisect] {label}: {results[label]}", file=sys.stderr)
    with open(args.out, "w") as fh:
        json.dump(results, fh, indent=1, sort_keys=True)
        fh.write("\n")
    print(json.dumps(results))


if __name__ == "__main__":
    main()
