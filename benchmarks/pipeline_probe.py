"""HBM streaming-rate probes: can anything beat Mosaic's auto-pipeline?

Round-3 finding (docs/STATE.md §4): a pure copy kernel (out = 2*in)
through ``pallas_call``'s automatic pipeline tops out at ~330 GB/s on this
v5e, independent of block shape, grid arity, and dimension_semantics —
while the XLA-fused jnp path streams 640-710 GB/s on the same chip.  That
pipeline rate bounds every single-step Pallas kernel (~40 Gcells/s) and
sets the fused kernels' ceiling.  ``pl.Buffered(buffer_count > 2)`` is not
supported by this toolchain, so the remaining lever is a MANUAL pipeline:
whole-array ANY-memory-space refs + ``pltpu.make_async_copy`` chunk DMAs
with N rotating VMEM slots (the double-buffering pattern in the public
Pallas TPU docs).

Probes (each its own label; run on a HEALTHY, otherwise-idle chip):
  auto_copy      pallas_call auto-pipeline baseline (reproduces the 330)
  manual2_copy   manual pipeline, 2 VMEM slots
  manual4_copy   manual pipeline, 4 slots (deeper DMA overlap)
  jnp_copy       XLA's own fused stream (the 640-710 reference point)
  manualNs_copy  store-pipelined variant: rotating OUT slots with async
      VMEM->HBM copies too (the plain manual store is a direct write; if
      Mosaic serializes it against the next chunk's compute, the "s"
      variants measure faster — diagnosing whether the streaming kernel
      needs store rotation).  Chunk auto-halved: 2N slots must fit VMEM.
  autoK_stencil / manualN[s]_stencil_kK — the DECISIVE set for the fused
      ceiling (VERDICT r3 item 5): identical k-micro-step 5-point stencil
      compute per chunk (the fused kernels' arithmetic intensity), auto
      vs manual pipeline.  If manual streams faster AT THIS INTENSITY, a
      manual-pipeline fused kernel is worth building; if both sit at the
      auto rate, the 330 GB/s is the DMA engine, not the scheduler, and
      the writeup closes the avenue.

Usage: python benchmarks/pipeline_probe.py [--probe NAME ...] [--out F]
Writes/merges JSON records (GB/s) into benchmarks/pipeline_probe.json.
Interpret-mode smoke: tests/test_pipeline_probe.py runs every probe tiny
on CPU, so the harness itself is CI-covered before it ever costs chip
time.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from mpi_cuda_process_tpu.ops.pallas.kernels import (
    _VMEM_LIMIT_BYTES,
    _interpret_default,
)


def _double(block):
    return block * 2.0


def _stencil_transform(k, roll):
    """k micro-steps of an in-chunk 5-point y/x stencil (rolls on the
    minor axes only, so chunking on z stays embarrassingly parallel).
    NOT a correct heat step across chunk boundaries — this is a traffic
    probe at the fused kernels' arithmetic intensity, not a solver.

    ``roll`` is ``pltpu.roll`` on hardware and ``jnp.roll`` in interpret
    mode (pltpu.roll does not lower on the CPU interpreter; for this
    symmetric Laplacian the two are bit-identical), injected so the CI
    equivalence test exercises the SAME body the chip measures.
    """

    def transform(block):
        def micro(_, u):
            lap = (roll(u, 1, 1) + roll(u, -1, 1)
                   + roll(u, 1, 2) + roll(u, -1, 2) - 4.0 * u)
            return u + 0.25 * lap

        return jax.lax.fori_loop(0, k, micro, block)

    return transform


def _auto_pipeline(shape, dtype, bz, interpret, transform):
    """pallas_call auto-pipeline: the measured-330 baseline."""
    Z, Y, X = shape

    def kernel(i_ref, o_ref):
        o_ref[...] = transform(i_ref[...])

    return pl.pallas_call(
        kernel,
        grid=(Z // bz,),
        in_specs=[pl.BlockSpec((bz, Y, X), lambda i: (i, 0, 0))],
        out_specs=pl.BlockSpec((bz, Y, X), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct(shape, dtype),
        interpret=interpret,
        compiler_params=None if interpret else pltpu.CompilerParams(
            vmem_limit_bytes=_VMEM_LIMIT_BYTES),
    )


def _manual_pipeline_kernel(nslots, bz, nchunks, transform, i_hbm, o_hbm):
    """N-slot rotating DMA pipeline over z-chunks of a whole-array ref.

    Loads overlap compute/stores: slot s starts its load up to nslots-1
    chunks ahead of consumption.  The store is a plain HBM write from
    VMEM (Mosaic lowers it as a DMA); a deeper variant could rotate
    output slots too, but the load path is where the round-3 measured
    pipeline stalled.
    """

    def body(scratch, sems):
        def dma(slot, chunk):
            return pltpu.make_async_copy(
                i_hbm.at[pl.ds(chunk * bz, bz)],
                scratch.at[slot],
                sems.at[slot],
            )

        for s in range(min(nslots - 1, nchunks)):  # warm-up (bounded:
            dma(s, s).start()  # tiny grids must not read past the array)

        def loop(chunk, _):
            slot = jax.lax.rem(chunk, nslots)
            nxt = chunk + nslots - 1

            @pl.when(nxt < nchunks)
            def _():
                dma(jax.lax.rem(nxt, nslots), nxt).start()

            dma(slot, chunk).wait()
            o_hbm[pl.ds(chunk * bz, bz)] = transform(scratch[slot])
            return ()

        jax.lax.fori_loop(0, nchunks, loop, ())

    pl.run_scoped(
        body,
        scratch=pltpu.VMEM(
            (nslots, bz) + tuple(i_hbm.shape[1:]), i_hbm.dtype),
        sems=pltpu.SemaphoreType.DMA((nslots,)),
    )


def _wrap_manual(shape, dtype, interpret, body_fn):
    """The one pallas_call wrapper both manual variants share — identical
    specs/limits so the store-pipelined vs direct-store comparison always
    measures the same conditions."""

    return pl.pallas_call(
        body_fn,
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        out_shape=jax.ShapeDtypeStruct(shape, dtype),
        interpret=interpret,
        compiler_params=None if interpret else pltpu.CompilerParams(
            vmem_limit_bytes=_VMEM_LIMIT_BYTES),
    )


def _manual_pipeline(shape, dtype, bz, nslots, interpret, transform):
    nchunks = shape[0] // bz

    def kernel(i_hbm, o_hbm):
        _manual_pipeline_kernel(nslots, bz, nchunks, transform, i_hbm,
                                o_hbm)

    return _wrap_manual(shape, dtype, interpret, kernel)


def _manual_store_pipeline_kernel(nslots, bz, nchunks, transform, i_hbm,
                                  o_hbm):
    """Both directions pipelined: rotating load slots AND rotating store
    slots with async VMEM->HBM copies (waited ``nslots`` chunks later).

    The plain manual probes store via a direct ``o_hbm[...] = value``
    write; if Mosaic serializes that store against the next chunk's
    compute, these variants will measure faster — diagnosing whether the
    streaming kernel needs store-slot rotation too.
    """

    def body(inbuf, insems, outbuf, outsems):
        def in_dma(slot, chunk):
            return pltpu.make_async_copy(
                i_hbm.at[pl.ds(chunk * bz, bz)], inbuf.at[slot],
                insems.at[slot])

        def out_dma(slot, chunk):
            return pltpu.make_async_copy(
                outbuf.at[slot], o_hbm.at[pl.ds(chunk * bz, bz)],
                outsems.at[slot])

        for s in range(min(nslots - 1, nchunks)):  # warm-up (bounded)
            in_dma(s, s).start()

        def loop(chunk, _):
            slot = jax.lax.rem(chunk, nslots)
            nxt = chunk + nslots - 1

            @pl.when(nxt < nchunks)
            def _():
                in_dma(jax.lax.rem(nxt, nslots), nxt).start()

            in_dma(slot, chunk).wait()

            # the store slot is reused nslots chunks later: its previous
            # copy must have left the buffer by then
            @pl.when(chunk >= nslots)
            def _():
                out_dma(slot, chunk - nslots).wait()

            outbuf[slot] = transform(inbuf[slot])
            out_dma(slot, chunk).start()
            return ()

        jax.lax.fori_loop(0, nchunks, loop, ())
        for s in range(min(nslots, nchunks)):  # drain the last stores
            chunk = nchunks - 1 - s
            out_dma(chunk % nslots, chunk).wait()

    pl.run_scoped(
        body,
        inbuf=pltpu.VMEM((nslots, bz) + tuple(i_hbm.shape[1:]),
                         i_hbm.dtype),
        insems=pltpu.SemaphoreType.DMA((nslots,)),
        outbuf=pltpu.VMEM((nslots, bz) + tuple(i_hbm.shape[1:]),
                          i_hbm.dtype),
        outsems=pltpu.SemaphoreType.DMA((nslots,)),
    )


def _manual_store_pipeline(shape, dtype, bz, nslots, interpret,
                           transform):
    nchunks = shape[0] // bz

    def kernel(i_hbm, o_hbm):
        _manual_store_pipeline_kernel(nslots, bz, nchunks, transform,
                                      i_hbm, o_hbm)

    return _wrap_manual(shape, dtype, interpret, kernel)


def build_probe(name, shape, dtype=jnp.float32, bz=16, interpret=None):
    """Return a jittable fn implementing the named strategy.

    Copy probes (``*_copy``) compute ``2*x``; stencil probes
    (``autoK_stencil`` / ``manualN_stencil_kK``) run k in-chunk 5-point
    micro-steps per pass — the fused kernels' arithmetic intensity.
    """
    if interpret is None:
        interpret = _interpret_default()
    if name == "jnp_copy":
        return lambda x: x * 2.0
    k = _probe_k(name)
    if k == 1:
        transform = _double
    else:
        roll = jnp.roll if interpret else pltpu.roll
        transform = _stencil_transform(k, roll)
    if name.startswith("auto"):
        return _auto_pipeline(shape, dtype, bz, interpret, transform)
    if name.startswith("manual"):
        nslots, store_pipe = _probe_nslots(name)
        builder = (_manual_store_pipeline if store_pipe
                   else _manual_pipeline)
        return builder(shape, dtype, bz, nslots, interpret, transform)
    raise ValueError(f"unknown probe {name!r}")


def _probe_k(name):
    """Micro-steps per pass encoded in the probe name (1 for copies)."""
    try:
        if name.endswith("_stencil"):
            return int(name[len("auto"):-len("_stencil")])
        if "_stencil_k" in name:
            return int(name[name.index("_stencil_k") + len("_stencil_k"):])
    except ValueError:
        # e.g. "manual2_stencil" / "auto_stencil": fail as a usage error,
        # not a confusing int() traceback in the results record
        raise ValueError(f"unknown probe {name!r}") from None
    return 1


def _probe_nslots(name):
    """(slot count, store-pipelined?) encoded in a manual probe's name —
    ``manual4_copy`` = 4 load slots, direct stores; ``manual4s_copy`` =
    4 load + 4 store slots (async store copies)."""
    spec = name[len("manual"):name.index("_")]
    store_pipe = spec.endswith("s")
    return int(spec.rstrip("s")), store_pipe


PROBES = ("jnp_copy", "auto_copy", "manual2_copy", "manual4_copy",
          "manual2s_copy", "manual4s_copy",
          "auto4_stencil", "manual2_stencil_k4", "manual4_stencil_k4",
          "manual4s_stencil_k4")


def measure_probe(name, shape=(512, 512, 512), bz=16, steps=30, reps=3):
    """GB/s for one probe via the N-vs-4N scan difference (bench.py's
    dispatch-cancelling method)."""
    if name.startswith("manual") and _probe_nslots(name)[1]:
        # store-pipelined variants hold 2*nslots slots: halve the chunk
        # so the scratch stays under the 100 MiB scoped-VMEM limit at
        # the default 512^3 shape (4+4 slots x 8 MiB = 64 MiB)
        bz = min(bz, 8)
    fn = build_probe(name, shape, bz=bz, interpret=False)

    def scan_n(n):
        def run(x):
            return jax.lax.fori_loop(0, n, lambda _, v: fn(v), x)

        return jax.jit(run)

    x = jnp.ones(shape, jnp.float32)
    run_a, run_b = scan_n(steps), scan_n(4 * steps)
    float(jnp.sum(run_a(x)))  # compile+warm
    float(jnp.sum(run_b(x)))

    def best(run):
        b = math.inf
        for _ in range(reps):
            t0 = time.perf_counter()
            float(jnp.sum(run(x)))
            b = min(b, time.perf_counter() - t0)
        return b

    t = (best(run_b) - best(run_a)) / (3 * steps)
    bytes_per_step = 2 * math.prod(shape) * 4  # 1R + 1W f32
    rec = {"gb_per_s": round(bytes_per_step / t / 1e9, 1),
           "ms_per_pass": round(t * 1e3, 3), "bz": bz,
           "shape": list(shape)}
    k = _probe_k(name)
    if k > 1:
        # effective cell rate if a fused kernel streamed at this rate
        rec["mcells_per_s_equiv"] = round(
            math.prod(shape) * k / t / 1e6, 1)
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--probe", nargs="*", default=list(PROBES))
    ap.add_argument("--bz", type=int, default=16)
    ap.add_argument("--out", default=os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "pipeline_probe.json"))
    args = ap.parse_args()
    results = {}
    if os.path.exists(args.out):
        with open(args.out) as fh:
            results = json.load(fh)
    for name in args.probe:
        try:
            rec = measure_probe(name, bz=args.bz)
        except Exception as e:  # noqa: BLE001 — record & continue
            rec = {"error": f"{type(e).__name__}: {str(e)[:600]}"}
        rec["measured_at"] = time.time()
        results[f"{name}_bz{args.bz}"] = rec
        print(f"[probe] {name}: {rec}", file=sys.stderr)
        with open(args.out + ".tmp", "w") as fh:
            json.dump(results, fh, indent=1, sort_keys=True)
        os.replace(args.out + ".tmp", args.out)
    print(json.dumps(results, indent=1, sort_keys=True))


if __name__ == "__main__":
    main()
