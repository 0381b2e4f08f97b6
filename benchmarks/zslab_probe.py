"""Single-chip probe of the z-slab kernel's REAL VMEM envelope.

The z-slab pad-free sharded kernel is the config-5 memory design, but
two-field wave3d fails `_pick_tiles`' ~7-live-copies-per-field VMEM
estimate at X=4096 lanes on every legal tile (docs/STATE.md).  That
estimate was fit to single-field kernels; wave's ``u_prev`` window has NO
roll temporaries, so the true envelope may be smaller.  This script
answers the question empirically WITHOUT a 64-chip slice: the pallas_call
a shard would run is built here with EXPLICIT tiles (bypassing the
estimate) at a shard-local shape that fits one chip — (64, 2048, 4096):
the VMEM cost depends on (tile x X-lane) geometry, not the Y extent, so
halving Y changes nothing about the question while fitting HBM — and fed
synthetic slab operands + a zero origin.  Mosaic either compiles it (the
model is pessimistic -> recalibrate `_pick_tiles` and unlock config-5
wave temporal blocking) or rejects it with the scoped-vmem error text
(the model is right -> the x-windowed variant or bf16-plain stays the
plan).

Each attempt runs in its own subprocess with a hard timeout (a killed
Mosaic compile can hang — run this on an idle chip
only, AFTER the main campaign).  Results merge into
``benchmarks/zslab_probe.json``.

Usage: python benchmarks/zslab_probe.py [--timeout 600]
"""

import argparse
import json
import os
import subprocess
import sys
import time

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# (label, stencil, dtype, local_shape, k, tiles)
# 2-tuple tiles -> whole-row z-slab kernel; 3-tuple -> wide-X variant.
# Ordered cheapest-question-first; heat3d rungs calibrate the estimate's
# accuracy against a config it PASSES, so a wave-only failure is
# attributable to the second field rather than to the probe harness.
ATTEMPTS = [
    ("heat3d_f32_k4_t8", "heat3d", None, (64, 2048, 4096), 4, (8, 8)),
    ("wave3d_f32_k4_t8", "wave3d", None, (64, 2048, 4096), 4, (8, 8)),
    ("wave3d_f32_k4_t16", "wave3d", None, (64, 2048, 4096), 4, (16, 16)),
    ("wave3d_bf16_k8_t16", "wave3d", "bfloat16", (64, 2048, 4096), 8,
     (16, 16)),
    # wide-X variants: the picker's actual choices for the config-5 local
    # shapes — these measure the 4.5x-amplification kernel's REAL rate
    ("wave3d_f32_k4_xwin", "wave3d", None, (64, 2048, 4096), 4,
     (32, 16, 512)),
    ("wave3d_bf16_k8_xwin", "wave3d", "bfloat16", (64, 2048, 4096), 8,
     (16, 16, 256)),
    ("heat3d_f32_k4_xwin", "heat3d", None, (64, 2048, 4096), 4,
     (32, 32, 512)),
]

_CHILD = """\
import sys, time, math
sys.path.insert(0, {repo!r})
import jax, jax.numpy as jnp
from mpi_cuda_process_tpu import make_stencil
from mpi_cuda_process_tpu.ops.pallas.fused import build_zslab_padfree_call

name, dt, local, k, tiles = {name!r}, {dt!r}, {local!r}, {k!r}, {tiles!r}
kw = dict(dtype=jnp.bfloat16) if dt == "bfloat16" else {{}}
st = make_stencil(name, **kw)
gshape = (local[0] * 8, local[1], local[2])  # as if one of 8 z-shards
if len(tiles) == 3:
    from mpi_cuda_process_tpu.ops.pallas.fused import build_zslab_xwin_call
    built = build_zslab_xwin_call(st, local, gshape, k, tiles=tiles,
                                  interpret=False)
    n_core, n_slab = 27, 9
else:
    built = build_zslab_padfree_call(st, local, gshape, k, tiles=tiles,
                                     interpret=False)
    n_core, n_slab = 9, 3
assert built is not None, "builder declined explicit tiles"
call, m, nfields = built
key = jax.random.PRNGKey(0)
fields = [jax.random.uniform(jax.random.fold_in(key, i), local, st.dtype)
          for i in range(nfields)]
slab = jnp.zeros((m, local[1], local[2]), st.dtype)
origins = jnp.array([local[0], 0], jnp.int32)  # pretend shard 1 (interior)
args = []
for f in fields:
    args += [f] * n_core + [slab] * n_slab + [slab] * n_slab
t0 = time.time()
out = call(origins, *args)
s = float(jnp.sum(out[0].astype(jnp.float32)))
t_compile = time.time() - t0
assert math.isfinite(s)
# one timed repeat (compiled): per-pass wall time -> Mcells/s over k steps
t0 = time.time()
float(jnp.sum(call(origins, *args)[0].astype(jnp.float32)))
dt_run = time.time() - t0
print("RESULT", t_compile,
      math.prod(local) * k / dt_run / 1e6, flush=True)
"""


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--timeout", type=int, default=600)
    ap.add_argument("--out", default=os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "zslab_probe.json"))
    args = ap.parse_args()
    results = {}
    if os.path.exists(args.out):
        with open(args.out) as fh:
            results = json.load(fh)
    for label, name, dt, local, k, tiles in ATTEMPTS:
        if results.get(label, {}).get("ok"):
            print(f"[zslab] {label}: cached, skip", file=sys.stderr)
            continue
        code = _CHILD.format(repo=_REPO, name=name, dt=dt, local=local,
                             k=k, tiles=tiles)
        t0 = time.time()
        try:
            p = subprocess.run([sys.executable, "-c", code], cwd=_REPO,
                               capture_output=True, text=True,
                               timeout=args.timeout)
            out_lines = p.stdout.strip().splitlines()
            if p.returncode == 0 and out_lines and \
                    out_lines[-1].startswith("RESULT"):
                _, t_compile, mcells = out_lines[-1].split()
                results[label] = {"ok": True,
                                  "compile_s": round(float(t_compile), 1),
                                  "mcells_per_s": round(float(mcells), 1)}
            else:
                tail = (p.stderr or "")
                if len(tail) > 900:
                    tail = tail[:200] + " ...[snip]... " + tail[-600:]
                results[label] = {"ok": False, "rc": p.returncode,
                                  "stderr_tail": tail}
        except subprocess.TimeoutExpired:
            results[label] = {"ok": False,
                              "error": f"timeout {args.timeout}s (hang)"}
            results["_aborted"] = ("stopped after first hang to protect "
                                   "the chip call")
            print(json.dumps(results, indent=1, sort_keys=True))
            break
        results[label]["wall_s"] = round(time.time() - t0, 1)
        print(f"[zslab] {label}: {results[label]}", file=sys.stderr)
        with open(args.out + ".tmp", "w") as fh:
            json.dump(results, fh, indent=1, sort_keys=True)
        os.replace(args.out + ".tmp", args.out)
    print(json.dumps(results, indent=1, sort_keys=True))


if __name__ == "__main__":
    main()
