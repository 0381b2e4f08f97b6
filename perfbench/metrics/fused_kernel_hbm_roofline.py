"""Share of the HBM roofline that the fused Pallas kernel reaches, %.

Least bytes as ``step_hbm_roofline`` counts them: one read and one write
of every field per fused pass, steps / k passes per runner execution,
for every runner execution in the traced window, at the chip's HBM peak
(``peaks.json``).  Time: the device time of the fused kernel alone, the
ops the trace names after a temporal-blocking ``pallas_call`` (``name``
``fused_*``, an HLO ``custom-call``), from the breakdown's longest-running
ops.  The rest of the runner (the copy before each pass) is left out,
which ``step_hbm_roofline`` counts.  None where no such op is named: an
unfused run, or kernels without names.
"""

import re

_KERNEL = re.compile(r"^fused_[a-z_]+(\.\d+)? \(custom-call\)$")


def read(run):
    tr = run.get("trace")
    if not tr or not tr["devices"]:
        return None
    # device_ops: seconds per chip, the mean over the traced chips
    kernel_s = sum(t for name, t in tr["breakdown"]["device_ops"]
                   if _KERNEL.match(name)) * len(tr["devices"])
    execs = sum(d["runner_execs"] for d in tr["devices"])
    if kernel_s <= 0 or not execs:
        return None
    local = 1
    for n in run["shard_shape"]:
        local *= n
    per_pass = 2 * run["num_fields"] * local * run["itemsize"]
    passes = run["steps_per_chunk"] // run["fuse_k"]
    least_s = execs * passes * per_pass / run["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least_s / kernel_s
