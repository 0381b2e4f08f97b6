"""Chip smoke: drive the CLI's main path once on a TPU and check what comes out.

    python chip_smoke.py               # one chip: phases "main" and "life"
    python chip_smoke.py --four-chips  # four chips: phase "mesh" only

Phases, all in this one process (it holds the chip; it starts no child):

* main — heat3d 1024^3 f32 (the per-chip share of the 4096^3 north star)
  through ``cli.config_from_args`` + ``cli.run`` with ``--compute auto``.
  Auto must choose the fused Pallas kernel (its ``auto: temporal blocking``
  log line); the result must match ``--compute jnp`` on the same config and
  seed within ``HEAT_ATOL``.  Each result is pulled to the host before the
  other run starts, so both never sit on the chip together.
* life — Life (the reference's kernel.cu) at 4096^2 int32 for 32
  generations through the CLI, bit for bit against the numpy golden.
* mesh (``--four-chips``) — BASELINE config 3: heat3d 1024^3 on
  ``--mesh 2,2,1`` through the CLI; each of the 4 devices must hold exactly
  one 512x512x1024 shard, and the result must match the same config run
  unsharded on ``jax.devices()[0]``.

Every line but the last is information (path, seconds, Mcells/s, errors);
Mcells/s here includes nothing the benchmark would call a measurement.  The
last line of stdout is one JSON object naming the device.  Off-TPU the
script exits non-zero before any phase and prints no result.
"""

from __future__ import annotations

import argparse
import json
import logging
import os
import sys
import time

import numpy as np

_REPO = os.path.dirname(os.path.abspath(__file__))

HEAT_GRID = (1024, 1024, 1024)
HEAT_ITERS = 64          # a multiple of the auto fuse k (4)
HEAT_ATOL = 1e-5         # f32, values in [0, 1]: summation-order noise only
LIFE_GRID = (4096, 4096)
LIFE_ITERS = 32
SEED = 21


class _LogTap(logging.Handler):
    """Collects the CLI's log messages (the auto decision is one)."""

    def __init__(self):
        super().__init__(logging.INFO)
        self.messages = []

    def emit(self, record):
        self.messages.append(record.getMessage())


def _say(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def _cli_run(argv, tap: _LogTap):
    """One run through the normal CLI entry; returns (host fields, info)."""
    from mpi_cuda_process_tpu import cli

    cfg = cli.config_from_args(argv)
    first = len(tap.messages)
    t0 = time.perf_counter()
    fields, mcells = cli.run(cfg)
    out = fields[0]
    shards = [(s.device, tuple(s.data.shape)) for s in out.addressable_shards]
    host = np.asarray(out)  # to the host before the next run starts
    seconds = time.perf_counter() - t0
    del fields, out
    auto = [m for m in tap.messages[first:] if m.startswith("auto:")]
    return host, {"seconds": seconds, "mcells_per_s": mcells,
                  "auto": auto, "shards": shards}


def _heat_argv(grid, iters, compute, mesh=None):
    argv = ["--stencil", "heat3d", "--grid", ",".join(map(str, grid)),
            "--iters", str(iters), "--compute", compute,
            "--init", "random", "--density", "0.5", "--seed", str(SEED)]
    if mesh:
        argv += ["--mesh", ",".join(map(str, mesh))]
    return argv


def phase_main(tap: _LogTap, grid=HEAT_GRID, iters=HEAT_ITERS,
               require_fused: bool = True) -> dict:
    """heat3d on the auto path against --compute jnp; raises on mismatch."""
    fused, info = _cli_run(_heat_argv(grid, iters, "auto"), tap)
    path = "fused" if any("temporal blocking" in m
                          for m in info["auto"]) else "not fused"
    _say(f"main: auto -> {path} ({'; '.join(info['auto']) or 'no auto line'})"
         f", {info['seconds']:.3f} s incl. compile, "
         f"{info['mcells_per_s']} Mcells/s (information only)")
    if require_fused and path != "fused":
        raise AssertionError("auto did not choose the fused Pallas kernel")
    ref, rinfo = _cli_run(_heat_argv(grid, iters, "jnp"), tap)
    _say(f"main: --compute jnp {rinfo['seconds']:.3f} s incl. compile, "
         f"{rinfo['mcells_per_s']} Mcells/s (information only)")
    err = _max_abs_err(fused, ref)
    _say(f"main: max abs err fused vs jnp {err!r} (tolerance {HEAT_ATOL})")
    if not np.isfinite(fused).all() or not err <= HEAT_ATOL:
        raise AssertionError(f"fused vs jnp max abs err {err} > {HEAT_ATOL}")
    return {"path": path, "max_abs_err": err}


def phase_life(tap: _LogTap, grid=LIFE_GRID, iters=LIFE_ITERS) -> dict:
    """Life through the CLI, bit for bit against the numpy golden."""
    sys.path.insert(0, os.path.join(_REPO, "tests"))
    import golden
    from mpi_cuda_process_tpu import make_stencil
    from mpi_cuda_process_tpu.utils.init import init_state

    argv = ["--stencil", "life", "--grid", ",".join(map(str, grid)),
            "--iters", str(iters), "--init", "random", "--seed", str(SEED)]
    got, info = _cli_run(argv, tap)
    # the same seeded init the CLI builds, stepped by the golden
    start = np.asarray(init_state(make_stencil("life"), grid, seed=SEED,
                                  density=0.15, kind="random")[0])
    want = golden.life_steps_vec(start, iters)
    bad = int(np.count_nonzero(got != want))
    _say(f"life: {info['auto'] or 'jnp path'}, {info['seconds']:.3f} s incl. "
         f"compile, {bad} cells differ from the golden, "
         f"{int(got.sum())} alive")
    if bad:
        raise AssertionError(f"life differs from the golden in {bad} cells")
    return {"cells_differing": bad}


def phase_mesh(tap: _LogTap, grid=HEAT_GRID, iters=HEAT_ITERS,
               mesh=(2, 2, 1)) -> dict:
    """Sharded heat3d against the same config unsharded on devices()[0]."""
    import jax

    n = int(np.prod(mesh))
    sharded, info = _cli_run(_heat_argv(grid, iters, "auto", mesh), tap)
    want_shard = tuple(g // m for g, m in zip(grid, mesh))
    shapes = sorted((str(d), s) for d, s in info["shards"])
    _say(f"mesh: {mesh} shards {shapes}, {info['seconds']:.3f} s incl. "
         f"compile, {info['mcells_per_s']} Mcells/s (information only)")
    devices = {d for d, _ in info["shards"]}
    if len(info["shards"]) != n or len(devices) != n \
            or devices != set(jax.devices()[:n]) \
            or any(s != want_shard for _, s in info["shards"]):
        raise AssertionError(
            f"want one {want_shard} shard on each of {n} devices, got "
            f"{shapes}")
    ref, rinfo = _cli_run(_heat_argv(grid, iters, "auto"), tap)
    _say(f"mesh: unsharded reference {rinfo['auto'] or 'jnp path'}, "
         f"{rinfo['seconds']:.3f} s incl. compile")
    err = _max_abs_err(sharded, ref)
    _say(f"mesh: max abs err sharded vs unsharded {err!r} "
         f"(tolerance {HEAT_ATOL})")
    if not np.isfinite(sharded).all() or not err <= HEAT_ATOL:
        raise AssertionError(f"sharded vs unsharded max abs err {err}")
    return {"shards": n, "max_abs_err": err}


def _max_abs_err(a: np.ndarray, b: np.ndarray) -> float:
    if a.shape != b.shape:
        raise AssertionError(f"shapes differ: {a.shape} vs {b.shape}")
    # plane by plane: a float64 copy of a 1024^3 grid is 8 GiB of host RAM
    return max(float(np.max(np.abs(x.astype(np.float64)
                                   - y.astype(np.float64))))
               for x, y in zip(a, b))


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--four-chips", action="store_true",
                   help="run only the 2x2 mesh phase (needs 4 chips)")
    a = p.parse_args(argv)
    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found {dev.platform} "
              f"({dev.device_kind})", file=sys.stderr)
        return 2
    if a.four_chips and len(devices) < 4:
        print(f"chip_smoke: --four-chips needs 4 chips, found "
              f"{len(devices)}", file=sys.stderr)
        return 2
    logging.basicConfig(
        level=logging.INFO, stream=sys.stderr,
        format="%(asctime)s %(levelname)s %(name)s: %(message)s")
    tap = _LogTap()
    logging.getLogger("mpi_cuda_process_tpu").addHandler(tap)
    from mpi_cuda_process_tpu import cli

    _say(f"device {dev.platform} {dev.device_kind} x{len(devices)}; "
         f"compile cache {cli.enable_compile_cache()}")
    if a.four_chips:
        phase_mesh(tap)
    else:
        phase_main(tap)
        phase_life(tap)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
