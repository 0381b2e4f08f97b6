"""One run of one cell: the program's own entry, set up, then a timed window.

The run is built as the CLI builds ``--log-every S``:
``cli.config_from_args`` -> ``cli.maybe_auto_fuse`` -> ``cli.build``, then
``driver.make_runner(step_fn, S // k)`` (k = the temporal-blocking depth the
built run uses, 1 unfused).  Each chunk of the window is one runner call,
fenced with ``block_until_ready`` as the CLI's observer fences it, then the
CLI's observation ``diagnostics.field_diagnostics`` (one host transfer).
The window is a closed loop: the next chunk starts when the previous
chunk's diagnostics reached the host.
"""

from __future__ import annotations

import contextlib
import dataclasses
import gc
import math
import statistics
import sys
import time

from . import check, spec


def program_argv(config, traffic, seed):
    steps = traffic["steps_per_observation"]
    argv = ["--stencil", config["stencil"],
            "--grid", ",".join(str(n) for n in config["grid"]),
            "--dtype", config["dtype"], "--compute", config["compute"],
            "--init", config["init"], "--density", str(config["density"]),
            "--seed", str(seed), "--iters", str(steps),
            "--log-every", str(steps)]
    if config.get("mesh"):
        argv += ["--mesh", ",".join(str(n) for n in config["mesh"])]
    return argv


class _WindowEvents:
    """Counts, while ``active``, the backend compiles (persistent-cache
    loads included: none belong in a window)."""

    def __init__(self):
        import jax

        self.active = False
        self.compiles = 0
        jax.monitoring.register_event_duration_secs_listener(self._event)

    def _event(self, event, duration, **kwargs):
        if self.active and "backend_compile" in event:
            self.compiles += 1


def _span(name, on):
    import jax

    return jax.profiler.TraceAnnotation(name) if on else \
        contextlib.nullcontext()


def build(cfg):
    """``cli.build(cfg)``, with the initial state drawn by one program for
    every seed: (stencil, step_fn, fields).

    ``cli.build`` jits its init with the seed bound as a constant, so each
    seed new to the checkout's compile cache compiles it anew (about 10 s
    at 1024^3, PERF.md §7) and set-up would read one way for a new seed and
    another for a seen one.  So ``cli.build`` runs here with the seed-free
    ``--init zero``, and the program's own ``init_state`` then draws the
    state with the seed as an argument, onto the same shardings: the fields
    the CLI builds from that seed (tests/perfbench pins it).
    """
    import jax
    import numpy as np

    from mpi_cuda_process_tpu import cli
    from mpi_cuda_process_tpu.utils.init import init_state

    st, step_fn, fields, _ = cli.build(dataclasses.replace(cfg, init="zero"))
    shardings = tuple(f.sharding for f in fields)
    del fields

    def seeded_init(seed):
        return init_state(st, cfg.grid, seed, cfg.density, cfg.init,
                          periodic=cfg.periodic, ensemble=cfg.ensemble,
                          perturb=cfg.ensemble_perturb)

    # PRNGKey of a 32-bit seed is [0, seed], as of the Python int the CLI
    # passes: the driver's seeds run past 32 signed bits
    fields = jax.jit(seeded_init, out_shardings=shardings)(
        np.uint32(cfg.seed % 2**32))
    return st, step_fn, fields


def run(config, traffic, seed, seconds, devices, t_start, trace_dir=None,
        say=None):
    """Set up, run the window, free the program's state; returns the record.

    ``trace_dir``: trace the window with the JAX profiler into it.
    """
    import jax

    from mpi_cuda_process_tpu import cli, driver
    from mpi_cuda_process_tpu.utils import diagnostics

    say = say or (lambda msg: print(msg, file=sys.stderr, flush=True))
    events = _WindowEvents()
    backend_s = time.perf_counter() - t_start
    cli.enable_compile_cache()
    steps = traffic["steps_per_observation"]
    t = time.perf_counter()
    cfg = cli.maybe_auto_fuse(cli.config_from_args(
        program_argv(config, traffic, seed)))
    auto_fuse_s = time.perf_counter() - t
    k = max(1, cfg.fuse)
    if steps % k:
        raise spec.SpecError(f"{steps} steps per observation is not a "
                             f"multiple of the fused depth {k}")

    t = time.perf_counter()
    st, step_fn, fields = build(cfg)
    fields = jax.block_until_ready(fields)
    init_s = time.perf_counter() - t

    runner = driver.make_runner(step_fn, steps // k)
    residual_step = None if cfg.fuse else step_fn

    def observe(fs):
        return diagnostics.field_diagnostics(st, fs, step_fn=residual_step)

    t = time.perf_counter()
    compiled = runner.lower(fields).compile()
    runner_compile_s = time.perf_counter() - t
    module = compiled.as_text().split(None, 2)[1].rstrip(",")
    del compiled

    # the warm chunk: its observation compiles or loads the diagnostics
    planes = check.plane_indices(config, seed)
    fields = jax.block_until_ready(runner(fields))
    t = time.perf_counter()
    diags = [observe(fields)]
    compile_s = runner_compile_s + time.perf_counter() - t
    first_digest = check.digest(fields[0], planes)
    setup_s = time.perf_counter() - t_start
    say(f"set-up {setup_s:.3f} s: start and backend {backend_s:.3f} s, "
        f"auto-fuse probe {auto_fuse_s:.3f} s, init {init_s:.3f} s, compile "
        f"{compile_s:.3f} s ({module}, k={k}, {steps} steps per chunk)")

    traced = trace_dir is not None
    if traced:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    chunk_s, observe_s = [], []
    events.active = True
    t0 = time.perf_counter()
    end = t0 + seconds
    last = t0
    while last < end or not chunk_s:
        with _span("perfbench.chunk", traced):
            with _span("perfbench.runner", traced):
                fields = jax.block_until_ready(runner(fields))
            t = time.perf_counter()
            with _span("perfbench.diagnostics", traced):
                d = observe(fields)
        now = time.perf_counter()
        observe_s.append(now - t)
        chunk_s.append(now - last)
        last = now
        if len(diags) < check.FOLLOW:
            diags.append(d)
    window_s = last - t0
    events.active = False
    if traced:
        jax.profiler.stop_trace()
    memory_peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                      for d in devices)
    shard_shape = tuple(fields[0].addressable_shards[0].data.shape)
    del fields
    gc.collect()
    slow = sorted(range(len(chunk_s)), key=chunk_s.__getitem__)[-5:][::-1]
    say(f"chunks in window: {len(chunk_s)} in {window_s:.3f} s "
        f"(median {statistics.median(chunk_s) * 1e3:.3f} ms; slowest "
        + ", ".join(f"#{i} {chunk_s[i] * 1e3:.1f}" for i in slow)
        + f" ms); compiles in window: {events.compiles}")
    return {
        "config": config, "seed": seed,
        "chips": len(devices), "fuse_k": k, "steps_per_chunk": steps,
        "cells": math.prod(config["grid"]), "shard_shape": shard_shape,
        "itemsize": jax.numpy.dtype(st.dtype).itemsize,
        "num_fields": st.num_fields, "module": module,
        "residual": residual_step is not None,
        "setup_s": setup_s, "init_s": init_s, "compile_s": compile_s,
        "chunk_s": chunk_s, "observe_s": observe_s, "window_s": window_s,
        "memory_peak_bytes": memory_peak,
        "diags": diags, "digest": first_digest, "planes": planes,
    }


def verify(record, device):
    """Run the plain reference over the followed chunks; (correct, checks)."""
    config = record["config"]
    ref = spec.reference(config)
    follow = len(record["diags"])
    ref_digest, ref_diags = check.reference_answers(
        config, ref, record["seed"], record["steps_per_chunk"], follow,
        record["residual"], record["planes"], device)
    numbers = check.gaps(record["digest"], record["diags"], ref_digest,
                         ref_diags)
    if follow < check.FOLLOW:
        numbers["diag_rel_gap"] = float("inf")
    return check.judge(numbers, config["limits"])
