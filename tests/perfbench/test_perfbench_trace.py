"""The benchmark's trace reduction on a small synthetic trace."""

import pytest

from perfbench import spec
from perfbench import trace as trace_lib

MS = 1e-3


def _trace():
    """Two chips over a 100 ms window of two 50 ms chunks.

    chip 0: runner ops 0-30 and 50-80 ms (jit_run executions 0-35 and
    50-85), a diagnostics reduce 40-45, a collective 30-38 of which 30-32
    overlaps a fusion (on the async line), and a while op 0-38 that holds
    the runner's ops.  chip 1:
    runner ops 0-40 and 50-90, no collective.
    """
    d0 = {"ops": [("%while = (f32[8]) while((f32[8]) %t), body=%b", 0,
                   38 * MS),
                  ("fusion.1", 0, 30 * MS), ("fusion.2", 50 * MS, 80 * MS),
                  ("fusion.9", 30 * MS, 32 * MS),
                  ("reduce.4", 40 * MS, 45 * MS)],
          "async": [("collective-permute-start.3", 30 * MS, 38 * MS)],
          "modules": [("jit_run(7)", 0, 35 * MS),
                      ("jit_run(7)", 50 * MS, 85 * MS),
                      ("jit__mean(8)", 40 * MS, 45 * MS)]}
    d1 = {"ops": [("fusion.1", 0, 40 * MS), ("fusion.2", 50 * MS, 90 * MS)],
          "async": [],
          "modules": [("jit_run(7)", 0, 40 * MS),
                      ("jit_run(7)", 50 * MS, 90 * MS)]}
    spans = [("perfbench.chunk", 0, 50 * MS),
             ("perfbench.chunk", 50 * MS, 100 * MS),
             ("perfbench.runner", 0, 36 * MS),
             ("perfbench.diagnostics", 36 * MS, 49 * MS),
             ("perfbench.runner", 50 * MS, 91 * MS),
             ("perfbench.diagnostics", 91 * MS, 99 * MS)]
    return {"devices": {"/device:TPU:0": d0, "/device:TPU:1": d1},
            "spans": spans}


def test_reduce_busy_idle_and_exposed_exchange():
    tr = trace_lib.reduce(_trace(), "jit_run")
    assert tr["window_s"] == pytest.approx(100 * MS)
    d0, d1 = tr["devices"]
    # chip 0 busy: 0-38, 40-45, 50-80 = 73 ms
    assert d0["busy_s"] == pytest.approx(73 * MS)
    assert d0["idle_frac"] == pytest.approx(0.27)
    assert d1["idle_frac"] == pytest.approx(0.2)
    assert tr["busy_s"] == pytest.approx((73 + 80) / 2 * MS)
    # collective 30-38, fusion.9 covers 30-32: 6 ms exposed
    assert d0["comm_s"] == pytest.approx(8 * MS)
    assert d0["exposed_comm_s"] == pytest.approx(6 * MS)
    assert d1["comm_s"] == 0
    # runner compute: fusion.1 + fusion.9 + fusion.2 (the collective and
    # the diagnostics reduce are not the runner's compute)
    assert d0["runner_execs"] == 2
    assert d0["runner_compute_s"] == pytest.approx(62 * MS)
    assert d1["runner_compute_s"] == pytest.approx(80 * MS)


def test_breakdown_names_ops_and_labels_gaps():
    tr = trace_lib.reduce(_trace(), "jit_run")
    ops = dict(tr["breakdown"]["device_ops"])
    assert ops["fusion.1"] == pytest.approx(35 * MS)  # (30 + 40) / 2 chips
    gaps = tr["breakdown"]["idle_gaps"]
    assert len(gaps) <= 10
    # chip 0 idles 80-100 ms, its midpoint inside the second runner span
    assert gaps[0] == ["host in perfbench.runner", pytest.approx(20 * MS)]
    # chip 1 idles 40-50 ms, while the host ran the diagnostics
    assert ["host in perfbench.diagnostics", pytest.approx(10 * MS)] in gaps
    assert all(g[1] >= h[1] for g, h in zip(gaps, gaps[1:]))


def _run(k, shard, chips=1):
    return {"trace": trace_lib.reduce(_trace(), "jit_run"),
            "shard_shape": shard, "num_fields": 1, "itemsize": 4,
            "steps_per_chunk": 16, "fuse_k": k, "chips": chips,
            "peaks": {"hbm_bytes_per_s": 819e9}}


@pytest.mark.parametrize("k", [1, 4])
def test_roofline_counts_one_read_and_write_per_pass(k):
    shard = (64, 64, 128)
    run = _run(k, shard)
    per_pass = 2 * 64 * 64 * 128 * 4
    passes = 16 // k
    execs = 4  # two runner executions on each of the two chips
    least = execs * passes * per_pass / 819e9
    got = spec.reader("step_hbm_roofline")(run)
    assert got == pytest.approx(100 * least / ((62 + 80) * MS))
    # k times fewer passes, k times fewer least bytes
    if k == 4:
        assert got == pytest.approx(spec.reader("step_hbm_roofline")(
            _run(1, shard)) / 4)


def test_trace_readers_and_their_silence():
    run = _run(4, (64, 64, 128))
    assert spec.reader("device_idle_frac")(run) == pytest.approx(0.27)
    assert spec.reader("exchange_exposed_frac")(run) == pytest.approx(0.06)
    silent = dict(run, trace=None)
    for name in ("device_idle_frac", "exchange_exposed_frac",
                 "step_hbm_roofline"):
        assert spec.reader(name)(silent) is None
    # no collective anywhere: no exchange to read, not a perfect hiding
    one = _trace()
    del one["devices"]["/device:TPU:0"]
    run1 = dict(run, trace=trace_lib.reduce(one, "jit_run"))
    assert spec.reader("exchange_exposed_frac")(run1) is None


def test_op_names_and_nesting():
    hlo = ("%fusion.3 = f32[8]{0} fusion(f32[8]{0} "
           "%collective-permute-done.2), kind=kLoop")
    assert trace_lib.op_name(hlo) == "fusion.3 (fusion)"
    # an operand named after a collective does not make a collective
    assert not trace_lib.is_collective(hlo)
    start = ("%collective-permute-start.1 = (f32[1,8]{1,0}) "
             "collective-permute-start(f32[1,8]{1,0} %slice.3)")
    assert trace_lib.is_collective(start)
    ops = [("while", 0, 10), ("a", 0, 4), ("b", 5, 10), ("c", 12, 13)]
    assert [n for n, _, _ in trace_lib.leaves(ops)] == ["a", "b", "c"]


def test_interval_helpers():
    assert trace_lib.merge([(3, 4), (0, 2), (1, 3), (5, 5)]) == [(0, 4)]
    assert trace_lib.gaps([(1, 2), (3, 4)], 0, 5) == [(0, 1), (2, 3), (4, 5)]
    assert trace_lib.intersection_total([(0, 2), (4, 6)], [(1, 5)]) == 2
    assert trace_lib.is_collective("all-reduce.3")
    assert not trace_lib.is_collective("fusion.12")
