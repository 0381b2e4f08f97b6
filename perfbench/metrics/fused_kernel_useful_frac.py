"""Share of a fused pass's micro-step cells that are its output, a fraction.

Each program of a fused pass (``ops/pallas/fused.py``) writes a (bz, by)
core of z/y rows from k micro-steps over its whole window, ``wm`` rows
wider on every z/y side: it computes (bz + 2 wm)(by + 2 wm) rows a
micro-step to write bz by.  Useful over attempted is therefore
bz by / ((bz + 2 wm)(by + 2 wm)), the same at every k and every x extent;
the rest is the overlap the pass recomputes.  Read beside
``fused_kernel_hbm_roofline``, it tells more arithmetic from more
recompute.

From the program's record of the passes it built
(``obs/runtime.fused_passes``: name, tiles, window margin, k, core
shape): the last one of the run's depth k over the run's shard.  None
where the program keeps no such record (one that predates it), or the run
built no fused pass.
"""


def read(run):
    try:
        from mpi_cuda_process_tpu.obs.runtime import fused_passes
    except ImportError:
        return None
    shape = tuple(run["shard_shape"])
    built = [p for p in fused_passes()
             if p["k"] == run["fuse_k"] and tuple(p["core_shape"]) == shape]
    if run["fuse_k"] < 2 or not built:
        return None
    (bz, by), wm = built[-1]["tiles"], built[-1]["window_margin"]
    return bz * by / ((bz + 2 * wm) * (by + 2 * wm))
