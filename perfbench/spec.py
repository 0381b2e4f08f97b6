"""Find a cell's parts by name.

Everything that belongs to one configuration, traffic mix or metric is a
file of its own, found from ``BENCHMARK.json``:

* a configuration: the ``file`` its entry names (``configs/<name>.json``),
  whose ``reference`` names its plain reference, ``references/<ref>.py``;
* a traffic mix: ``traffic/<traffic>.json``;
* a metric, end to end or per layer: ``metrics/<name>.py``, whose
  ``read(run)`` returns the number or None when it finds nothing to read;
* the chip's peaks: ``peaks.json``, keyed by ``device_kind``.

So a new cell needs new files and new ``BENCHMARK.json`` entries only.
"""

from __future__ import annotations

import importlib.util
import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


class SpecError(Exception):
    """A cell, file or device the benchmark cannot run."""


def _load_json(path):
    try:
        with open(path) as fh:
            return json.load(fh)
    except FileNotFoundError:
        raise SpecError(f"missing {os.path.relpath(path, ROOT)}") from None


def _load_module(path, name):
    if not os.path.isfile(path):
        raise SpecError(f"missing {os.path.relpath(path, ROOT)}")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def benchmark(root=ROOT):
    return _load_json(os.path.join(root, "BENCHMARK.json"))


def workload(bench, name):
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise SpecError(f"no workload {name!r} in BENCHMARK.json")


def config(bench, name, root=ROOT):
    for c in bench["configs"]:
        if c["name"] == name:
            return _load_json(os.path.join(root, c["file"]))
    raise SpecError(f"no configuration {name!r} in BENCHMARK.json")


def traffic(name):
    return _load_json(os.path.join(HERE, "traffic", f"{name}.json"))


def reference(config):
    ref = config["reference"]
    return _load_module(os.path.join(HERE, "references", f"{ref}.py"),
                        f"perfbench_reference_{ref}")


def metrics(bench, cell_name, per_layer):
    """The metric entries this cell reports in a run with or without trace."""
    group = bench["per_layer" if per_layer else "end_to_end"]
    return [m for m in group
            if "workloads" not in m or cell_name in m["workloads"]]


def reader(metric_name):
    path = os.path.join(HERE, "metrics", f"{metric_name}.py")
    return _load_module(path, "perfbench_metric_"
                        + metric_name.replace(".", "_").replace("-", "_")).read


def peaks(device_kind):
    table = _load_json(os.path.join(HERE, "peaks.json"))["kinds"]
    if device_kind not in table:
        raise SpecError(f"device kind {device_kind!r} is not in "
                        f"perfbench/peaks.json")
    return table[device_kind]


def check_devices(devices, chips):
    """The devices a cell runs on, or SpecError: a TPU of a known kind,
    at least ``chips`` of them."""
    if not devices or devices[0].platform != "tpu":
        found = devices[0].platform if devices else "nothing"
        raise SpecError(f"needs a TPU; JAX found {found}")
    if len(devices) < chips:
        raise SpecError(f"the cell needs {chips} chips, JAX found "
                        f"{len(devices)}")
    peaks(devices[0].device_kind)
    return devices[:chips]
