"""Run one benchmark cell once:

    python3 perfbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The last line of stdout is one JSON object: ``correct``, ``attempted``
(chunks run in the window), ``failed``, ``metrics`` (the cell's end-to-end
metrics with ``--trace 0``, its per-layer metrics with ``--trace 1``),
``device``, with ``--trace 1`` a ``breakdown``, and last ``checks``: each
compared number with its limit.  The same numbers are the last lines on
stderr.  With no TPU, too few chips, or a device kind missing from
``peaks.json``, it exits 2 and prints no result.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _say(msg):
    print(f"[perfbench] {msg}", file=sys.stderr, flush=True)


def _environment():
    """The compile cache and the TPU runtime's logs inside the checkout, at
    fixed paths under the git-ignored ``.jax_cache``, before JAX loads."""
    cache = os.path.join(ROOT, ".jax_cache")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = cache
    os.environ["TPU_LOG_DIR"] = os.path.join(cache, "tpu_logs")
    os.makedirs(os.environ["TPU_LOG_DIR"], exist_ok=True)


def _metric_values(bench, cell_name, per_layer, run):
    from perfbench import spec

    out = {}
    for m in spec.metrics(bench, cell_name, per_layer):
        value = spec.reader(m["name"])(run)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    a = p.parse_args(argv)
    sys.path.insert(0, ROOT)
    from perfbench import spec

    try:
        bench = spec.benchmark()
        cell = spec.workload(bench, a.workload)
        config = spec.config(bench, cell["config"])
        traffic = spec.traffic(cell["traffic"])
        _environment()
        import jax

        devices = spec.check_devices(jax.devices(), cell["chips"])
        peaks = spec.peaks(devices[0].device_kind)
    except spec.SpecError as e:
        _say(f"cannot run {a.workload}: {e}")
        return 2
    from perfbench import cell as cell_lib

    dev = devices[0]
    _say(f"{a.workload}: {len(devices)} x {dev.device_kind}, seed {a.seed}, "
         f"{a.seconds} s window, trace {a.trace}")
    trace_dir = tempfile.mkdtemp(prefix="perfbench-trace-") if a.trace \
        else None
    try:
        record = cell_lib.run(config, traffic, a.seed, a.seconds, devices,
                              T_START, trace_dir=trace_dir, say=_say)
        record["peaks"] = peaks
        record["trace"] = None
        if trace_dir:
            from perfbench import trace as trace_lib

            t = time.perf_counter()
            record["trace"] = trace_lib.reduce(trace_lib.load(trace_dir),
                                               record["module"])
            _say(f"trace read in {time.perf_counter() - t:.3f} s")
    finally:
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)
    t = time.perf_counter()
    correct, checks = cell_lib.verify(record, dev)
    _say(f"reference check {time.perf_counter() - t:.3f} s")
    metrics = _metric_values(bench, a.workload, bool(a.trace), record)
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices),
              "memory_peak_bytes": record["memory_peak_bytes"]}
    # the followed chunks are the answers judged: all fail with the check
    result = {"correct": correct, "attempted": len(record["chunk_s"]),
              "failed": 0 if correct else len(record["diags"]),
              "metrics": metrics, "device": device}
    if a.trace:
        tr = record["trace"]
        device["busy_s"] = tr["busy_s"]
        device["window_s"] = tr["window_s"]
        result["breakdown"] = tr["breakdown"]
    # a gap that is not a number (a missing chunk) is printed as its name
    result["checks"] = {
        name: {k: v if math.isfinite(v) else repr(v) for k, v in c.items()}
        for name, c in checks.items()}
    for name, c in checks.items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
