"""Benchmark harness: prints ONE JSON line with the headline metric.

Headline: Mcells/s for the 3D 7-point Laplacian on a 256^3 grid, single chip
(BASELINE.json config 2), plus the 512^3 number.  The reference publishes no
numbers (BASELINE.md), so ``vs_baseline`` is measured against an
A100+NCCL-class working target of 50,000 Mcells/s (~50 Gcell/s — what tuned
7-point fp32 stencil codes reach on A100-80GB, whose HBM bandwidth bounds the
update at ~190 Gcell/s; v5e's 819 GB/s bounds it at ~100 Gcell/s with perfect
fusion), per BASELINE.md's "A100+NCCL-class Mcells/sec" north star.

A measurement needs the chip: with no TPU the harness exits non-zero and
prints no result.  Extra diagnostics go to stderr; stdout carries exactly one
JSON line.
"""

import json
import math
import os
import sys
import time

import jax
import jax.numpy as jnp

BASELINE_MCELLS = 50_000.0  # A100-class 7-point stencil throughput
# N-vs-4N noise floor: the 3N-step delta must exceed this fraction of the
# N-scan time or the measurement is flagged suspect instead of reported.
# Shared with benchmarks/measure.py (which imports it from here).
NOISE_FLOOR_FRAC = 0.05


def _fence(fields) -> float:
    """Device->host scalar read: the completion fence."""
    return float(jnp.sum(fields[0]))


def _time_run(run, mk_state, reps) -> float:
    best = math.inf
    for _ in range(reps):
        f = mk_state()
        _fence(f)
        t0 = time.perf_counter()
        _fence(run(f))
        best = min(best, time.perf_counter() - t0)
    return best


def bench_stencil(name, grid, params, timed_steps, reps=3, fuse=0):
    """Per-step throughput with fixed dispatch/readback overhead removed.

    Times scans of N and 4N steps; the difference isolates pure step time
    (dispatch and the readback cancel out).  With
    ``fuse=k`` the step is the temporal-blocking fused Pallas kernel (k
    real steps per call — the CLI's ``auto`` path on TPU); an untileable
    shape (the builder returns None) is measured on the jnp step and
    reported as such in ``compute``.
    """
    from mpi_cuda_process_tpu import init_state, make_step, make_stencil
    from mpi_cuda_process_tpu.driver import make_runner

    st = make_stencil(name, **params)
    mk_state = lambda: init_state(st, grid, kind="auto")  # noqa: E731
    step_unit, step, compute = 1, None, "jnp"
    if fuse > 1:
        from mpi_cuda_process_tpu.ops.pallas.fused import make_fused_step

        step = make_fused_step(st, grid, fuse)  # interpret off-TPU
        if step is not None:
            step_unit, compute = fuse, f"pallas_fused_k{fuse}"
    if step is None:
        step = make_step(st, grid)
    run_a = make_runner(step, timed_steps)
    run_b = make_runner(step, 4 * timed_steps)
    _fence(run_a(mk_state()))  # compile + warm
    _fence(run_b(mk_state()))
    t_a = _time_run(run_a, mk_state, reps)
    t_b = _time_run(run_b, mk_state, reps)
    delta = t_b - t_a
    # t(4N) - t(N) should be ~3x t(N)'s step content; a delta that is
    # non-positive OR tiny relative to t_a means noise swamped the signal —
    # emit it flagged rather than clamped into a plausible-looking number.
    suspect = delta <= NOISE_FLOOR_FRAC * t_a
    per_step = max(delta, 1e-9) / (3 * timed_steps * step_unit)
    cells = math.prod(grid)
    return cells / per_step / 1e6, per_step, compute, suspect


def _write_bench_telemetry(rec, grid, steps, fuse, backend):
    """Emit the round-gate's own telemetry manifest (obs/ schema).

    One small JSONL under the shared telemetry dir: the same manifest
    schema as ``cli --telemetry`` / measure.py / scaling.py, with the
    headline record as its one result event — so the round-end bench is
    itself provenance-stamped evidence.  Returns the path, or None
    (telemetry must never break the bench).
    """
    try:
        from mpi_cuda_process_tpu.obs import trace as obs_trace

        path = os.path.join(obs_trace.default_telemetry_dir(),
                            "bench.jsonl")
        with obs_trace.TraceWriter(path) as w:
            w.write_manifest(obs_trace.build_manifest(
                "bench",
                {"grid": list(grid), "timed_steps": steps, "fuse": fuse,
                 "backend": backend,
                 "baseline_mcells": BASELINE_MCELLS}))
            w.event("result", **rec)
        return path
    except Exception:
        return None


def _maybe_serve():
    """``BENCH_SERVE_PORT``: live console over the telemetry dir.

    bench.py has no CLI (the driver runs it bare), so the live-console
    opt-in is an env var: when set, a campaign aggregator
    (obs/serve.py) serves the shared telemetry directory for the
    duration of the bench — the same /metrics + /status.json +
    /events surface as ``cli --serve``, picking up the manifest this
    run writes at the end (and any concurrent run's).  Never
    load-bearing; returns the server or None.
    """
    port = os.environ.get("BENCH_SERVE_PORT")
    if not port:
        return None
    try:
        from mpi_cuda_process_tpu.obs import serve as serve_lib
        from mpi_cuda_process_tpu.obs import trace as obs_trace

        server = serve_lib.serve_campaign(
            obs_trace.default_telemetry_dir(), port=int(port))
        print(f"[bench] obs console at {server.url}", file=sys.stderr)
        return server
    except Exception as e:
        print(f"[bench] BENCH_SERVE_PORT disabled "
              f"({type(e).__name__}: {e})", file=sys.stderr)
        return None


def main():
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"[bench] needs a TPU; JAX found {dev.platform}",
              file=sys.stderr)
        return 2
    server = _maybe_serve()
    backend = jax.default_backend()
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    rec = {"unit": "Mcells/s", "backend": backend, "device": device}
    for grid, steps, suffix in (((256, 256, 256), 50, ""),
                                ((512, 512, 512), 15, "_512cubed")):
        mcells, per_step, compute, suspect = bench_stencil(
            "heat3d", grid, {}, steps, fuse=4)
        print(f"[bench] {dev.device_kind} heat3d {'x'.join(map(str, grid))}"
              f" [{compute}]: {per_step*1e3:.3f} ms/step "
              f"({mcells:.0f} Mcells/s)", file=sys.stderr)
        if not suffix:
            rec["metric"] = "heat3d_7pt_256cubed_single_chip_throughput"
        rec["value" + suffix] = round(mcells, 1)
        rec["vs_baseline" + suffix] = round(mcells / BASELINE_MCELLS, 4)
        rec["compute" + suffix] = compute
        if suspect:
            rec["suspect" + suffix] = True
    tel = _write_bench_telemetry(rec, (256, 256, 256), 50, 4, backend)
    if tel:
        rec["telemetry"] = tel
        # every headline lands in the durable cross-round ledger
        # (quarantine rules applied on ingest; never breaks the bench)
        try:
            from mpi_cuda_process_tpu.obs import ledger as _ledger

            _ledger.ingest_log(tel)
        except Exception:
            pass
    print(json.dumps(rec), flush=True)
    if server is not None:
        server.close()  # final drain picks up the manifest written above
    return 0


if __name__ == "__main__":
    sys.exit(main())
