"""Share of the HBM roofline that the chunk program's compute ops reach, %.

Least bytes: one read and one write of every field per HBM pass, per chip,
with steps / k passes (k the temporal-blocking depth the built run uses,
1 unfused), for every runner execution in the traced window.  Least time:
those bytes at the chip's HBM peak (``peaks.json``).  Over the union of
the device intervals of the runner module's non-collective leaf ops.
Bounded by bandwidth alone: heat3d does about 8 flops a cell and v5e
publishes no f32 vector peak.  Real traffic can only be larger, so the
share cannot pass 100%.
"""


def read(run):
    tr = run.get("trace")
    if not tr:
        return None
    local = 1
    for n in run["shard_shape"]:
        local *= n
    per_pass = 2 * run["num_fields"] * local * run["itemsize"]
    passes = run["steps_per_chunk"] // run["fuse_k"]
    execs = sum(d["runner_execs"] for d in tr["devices"])
    busy = sum(d["runner_compute_s"] for d in tr["devices"])
    if not execs or busy <= 0:
        return None
    least_s = execs * passes * per_pass / run["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least_s / busy
