"""The readers of the program's own regions, compile counter and kernel
names: on a tiny CPU run of each cell, on synthetic records, and their
silence where the program (or the trace) has nothing to read.  Program
spans in a trace leave every earlier reader's value unchanged."""

import importlib.util
import os
import time

import jax
import pytest

from mpi_cuda_process_tpu.obs import runtime, spans
from perfbench import cell, check, program, spec
from perfbench import trace as trace_lib

MS = 1e-3
TINY = [32, 32, 128]
SEED = 2**31 + 4321
PROGRAM_READERS = ("window_compiles_per_chunk", "setup_compile_s",
                   "diag_host_ms_p50", "diag_wait_ms_p50")
TRACE_READERS = ("device_idle_frac", "exchange_exposed_frac",
                 "step_hbm_roofline")


def _trace_tests():
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "test_perfbench_trace.py")
    s = importlib.util.spec_from_file_location("perfbench_trace_tests", path)
    mod = importlib.util.module_from_spec(s)
    s.loader.exec_module(mod)
    return mod


# ------------------------------------------------ a tiny run of each cell

@pytest.mark.parametrize("workload", ["heat3d-1024.log16",
                                      "heat3d-1024-2x2.log8"])
def test_program_readers_on_a_tiny_run(workload, monkeypatch):
    """A short window of the cell at a tiny size: every program reader
    finds its window; the compile count is the harness's own count of
    the same events; observation = dispatch + wait, within the call."""
    monkeypatch.setattr(check, "FOLLOW", 2)
    counters = []

    class _Counted(cell._WindowEvents):
        def __init__(self):
            super().__init__()
            counters.append(self)

    monkeypatch.setattr(cell, "_WindowEvents", _Counted)
    bench = spec.benchmark()
    w = spec.workload(bench, workload)
    config = dict(spec.config(bench, w["config"]), grid=TINY)
    record = cell.run(config, spec.traffic(w["traffic"]), SEED, 0.3,
                      jax.devices()[:w["chips"]], time.perf_counter(),
                      say=lambda msg: None)
    got = {name: spec.reader(name)(record) for name in PROGRAM_READERS}
    assert all(v is not None for v in got.values()), got
    n = len(record["chunk_s"])
    assert got["window_compiles_per_chunk"] * n == counters[0].compiles
    assert got["setup_compile_s"] > 0  # the runner compiled in set-up
    observe_ms = sorted(s * 1e3 for s in record["observe_s"])
    assert got["diag_host_ms_p50"] + got["diag_wait_ms_p50"] <= \
        observe_ms[-1]
    assert 0 < got["diag_wait_ms_p50"] <= observe_ms[-1]


# -------------------------------------------------- synthetic program

def test_program_readers_read_the_last_window():
    """Regions and compiles recorded here, in-process, as the program
    records them: a set-up observation with one compile, then a window
    of three chunks, each observation compiling twice."""
    with spans.region("sim.diagnostics"):
        with spans.region("sim.diagnostics.stage"):
            runtime._on_duration(runtime.BACKEND_COMPILE_EVENT, 7.5)
    time.sleep(0.002)
    t0 = time.perf_counter()
    chunk_s = []
    for _ in range(3):
        t = time.perf_counter()
        time.sleep(0.001)  # the runner
        with spans.region("sim.diagnostics"):
            with spans.region("sim.diagnostics.stage"):
                runtime._on_duration(runtime.CACHE_LOAD_EVENT, 0.1)
                runtime._on_duration(runtime.BACKEND_COMPILE_EVENT, 0.1)
                runtime._on_duration(runtime.BACKEND_COMPILE_EVENT, 0.2)
            with spans.region("sim.diagnostics.fetch"):
                time.sleep(0.003)
        chunk_s.append(time.perf_counter() - t)
    run = {"chunk_s": chunk_s, "window_s": time.perf_counter() - t0}
    assert spec.reader("window_compiles_per_chunk")(run) == 2.0
    assert spec.reader("setup_compile_s")(run) >= 7.5
    fetch = [e - s for _, s, e in spans.closed_regions(
        "sim.diagnostics.fetch")[-3:]]
    assert spec.reader("diag_wait_ms_p50")(run) == \
        pytest.approx(sorted(fetch)[1] * 1e3)
    assert spec.reader("diag_host_ms_p50")(run) < \
        spec.reader("diag_wait_ms_p50")(run)
    # a record that does not match the program's last window: silence
    wrong = dict(run, chunk_s=chunk_s[:2])
    assert all(spec.reader(n)(wrong) is None for n in PROGRAM_READERS)


def test_program_readers_silent_without_the_program_records(monkeypatch):
    run = {"chunk_s": [0.1], "window_s": 0.1}
    monkeypatch.setattr(program, "closed_regions", lambda name: None)
    for name in PROGRAM_READERS:
        assert spec.reader(name)(run) is None, name


# ------------------------------------------------------- the trace side

def _named_trace(kernel="%fused_padfree.3 = f32[64,64,128]{2,1,0} "
                        "custom-call(f32[64,64,128]{2,1,0} %copy.11)"):
    """The trace test's two chips, the runner's first op on each named
    ``kernel`` (chip 0: 0-30 ms, chip 1: 0-40 ms)."""
    tr = _trace_tests()._trace()
    for dev in tr["devices"].values():
        dev["ops"] = [(kernel if n.startswith("fusion.1") else n, s, e)
                      for n, s, e in dev["ops"]]
    return tr


def _with_program_spans(tr):
    tr = dict(tr, spans=list(tr["spans"]) + [
        ("sim.diagnostics", 36 * MS, 49 * MS),
        ("sim.diagnostics.stage", 36 * MS, 40 * MS),
        ("sim.diagnostics.fetch", 40 * MS, 48 * MS),
        ("sim.diagnostics", 91 * MS, 99 * MS),
        ("sim.diagnostics.stage", 91 * MS, 93 * MS),
        ("sim.diagnostics.fetch", 93 * MS, 99 * MS)])
    return tr


def test_earlier_readers_unmoved_by_program_spans():
    tests = _trace_tests()
    plain = tests._run(4, (64, 64, 128), chips=2)
    spanned = dict(plain, trace=trace_lib.reduce(
        _with_program_spans(tests._trace()), "jit_run"))
    for name in TRACE_READERS:
        assert spec.reader(name)(spanned) == spec.reader(name)(plain), name
    assert spanned["trace"]["devices"] == plain["trace"]["devices"]
    assert spanned["trace"]["window_s"] == plain["trace"]["window_s"]


def test_fused_kernel_roofline_reads_the_named_kernel():
    tests = _trace_tests()
    run = dict(tests._run(4, (64, 64, 128), chips=2),
               trace=trace_lib.reduce(_named_trace(), "jit_run"))
    ops = dict(run["trace"]["breakdown"]["device_ops"])
    assert ops["fused_padfree.3 (custom-call)"] == pytest.approx(35 * MS)
    per_pass = 2 * 64 * 64 * 128 * 4
    least = 4 * (16 // 4) * per_pass / 819e9  # 4 runner executions
    got = spec.reader("fused_kernel_hbm_roofline")(run)
    assert got == pytest.approx(100 * least / ((30 + 40) * MS))
    # the kernel is part of the runner: its share is above the runner's
    assert got > spec.reader("step_hbm_roofline")(run)
    # an unnamed kernel (a custom call named after its call site), an
    # XLA fusion, no trace: nothing to read
    for name in ("%closed_call.4 = f32[8] custom-call(f32[8] %copy.11)",
                 "%fused_x.2 = f32[8] fusion(f32[8] %p)"):
        unnamed = dict(run, trace=trace_lib.reduce(_named_trace(name),
                                                   "jit_run"))
        assert spec.reader("fused_kernel_hbm_roofline")(unnamed) is None
    assert spec.reader("fused_kernel_hbm_roofline")(
        dict(run, trace=None)) is None
