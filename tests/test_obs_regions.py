"""Program regions on the profiler's clock, the compile counter by region,
and the names of the Pallas kernels.

Pins:

* ``obs/spans.region`` lands in a ``jax.profiler`` trace: a
  ``field_diagnostics`` call holds ``sim.diagnostics`` with its two
  children, nested, and a ``run_simulation`` chunk is a ``sim.chunk``
  step with its ``step_num`` around ``sim.runner`` and ``sim.observe``;
* ``obs/spans.py`` loads as a file with no jax (the supervisor parent's
  import), and a region there still records;
* ``obs/runtime``'s one listener counts each backend compile, and each
  persistent-cache load, under the innermost open region, and a
  repeated call counts none;
* every ``pallas_call`` carries a ``name``: the name is in the kernel's
  lowered text and holds no substring the benchmark's trace reduction
  takes for a collective.
"""

import ast
import glob
import os
import subprocess
import sys
import textwrap
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from mpi_cuda_process_tpu import driver  # noqa: E402
from mpi_cuda_process_tpu.obs import runtime, spans  # noqa: E402
from mpi_cuda_process_tpu.ops.stencil import make_stencil  # noqa: E402
from mpi_cuda_process_tpu.utils import diagnostics  # noqa: E402
from mpi_cuda_process_tpu.utils.init import init_state  # noqa: E402

SPANS_PY = os.path.join(REPO, "mpi_cuda_process_tpu", "obs", "spans.py")
PALLAS_DIR = os.path.join(REPO, "mpi_cuda_process_tpu", "ops", "pallas")


# ------------------------------------------------ regions in a profile

def _host_events(trace_dir):
    """{name: [(start_ns, end_ns, stats)]} of the host planes' events."""
    from jax.profiler import ProfileData

    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    assert files, "the profiler wrote no .xplane.pb"
    out = {}
    for plane in ProfileData.from_file(max(files,
                                           key=os.path.getmtime)).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("sim."):
                    out.setdefault(e.name, []).append(
                        (e.start_ns, e.end_ns, dict(e.stats)))
    return out


def _inside(child, parent):
    return parent[0] <= child[0] and child[1] <= parent[1]


def test_regions_nest_in_a_profiler_trace(tmp_path):
    st = make_stencil("heat2d")
    shape = (16, 128)
    step = driver.make_step(st, shape)
    fields = init_state(st, shape, seed=0, kind="pulse")
    diagnostics.field_diagnostics(st, fields, step_fn=step)  # compile
    seen = []
    with jax.profiler.trace(str(tmp_path)):
        diagnostics.field_diagnostics(st, fields, step_fn=step)
        driver.run_simulation(st, init_state(st, shape, seed=0,
                                             kind="pulse"),
                              4, step_fn=step, log_every=2,
                              callback=lambda d, fs: seen.append(d))
    assert seen == [2, 4]
    ev = _host_events(str(tmp_path))
    diag = ev["sim.diagnostics"]
    assert len(diag) == 1
    (stage,), (fetch,) = ev["sim.diagnostics.stage"], \
        ev["sim.diagnostics.fetch"]
    assert _inside(stage, diag[0]) and _inside(fetch, diag[0])
    assert stage[1] <= fetch[0]  # dispatch, then the wait
    chunks = sorted(ev["sim.chunk"])
    assert [c[2]["step_num"] for c in chunks] == [0, 2]
    for kind in ("sim.runner", "sim.observe"):
        got = sorted(ev[kind])
        assert len(got) == 2
        assert all(_inside(g, c) for g, c in zip(got, chunks))


def test_region_records_closed_spans_on_perf_counter():
    with spans.region("t.outer"):
        assert spans.current_region() == "t.outer"
        with spans.region("t.inner", step_num=3):
            assert spans.current_region() == "t.inner"
        assert spans.current_region() == "t.outer"
    assert spans.current_region() is None
    (inner,) = spans.closed_regions("t.inner")[-1:]
    (outer,) = spans.closed_regions("t.outer")[-1:]
    assert outer[1] <= inner[1] <= inner[2] <= outer[2]


def test_spans_module_loads_no_jax():
    """The supervisor parent's import: spans.py as a file, stdlib only,
    and a region there records without annotating."""
    code = textwrap.dedent(f"""
        import importlib.util, sys
        spec = importlib.util.spec_from_file_location("spans_alone",
                                                      {SPANS_PY!r})
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        with mod.region("sim.alone"):
            pass
        assert mod.closed_regions("sim.alone"), "nothing recorded"
        assert "jax" not in sys.modules, "spans.py loaded jax"
        print("ok")
    """)
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    assert out.stdout.strip() == "ok"


# ------------------------------------------------- compile counter

def _counts(region):
    return runtime.compile_counts().get(
        region, {"compiles": 0, "loads": 0, "seconds": 0.0})


def test_compile_counted_once_under_its_region():
    x = np.arange(37 * 3, dtype=np.float32).reshape(37, 3)  # a new shape
    f = jax.jit(lambda a: jnp.sin(a) * 3.0 + 1.0)
    before = _counts("t.compile")
    seen = runtime.compile_events_seen()
    with spans.region("t.compile"):
        f(x).block_until_ready()
    after = _counts("t.compile")
    assert after["compiles"] - before["compiles"] == 1
    assert after["loads"] == before["loads"]
    assert after["seconds"] > before["seconds"]
    assert runtime.compile_events_seen() == seen + 1
    with spans.region("t.compile"):
        f(x).block_until_ready()  # cached: no compile
    assert _counts("t.compile") == after


def test_cache_load_counted_apart_and_windowed():
    """A cache hit fires the retrieval event, then the backend event on
    the same thread: one load, no compile.  ``since``/``until`` read
    only the events that ended in the window."""
    t0 = time.perf_counter()
    with spans.region("t.load"):
        runtime._on_duration(runtime.CACHE_LOAD_EVENT, 0.25)
        runtime._on_duration(runtime.BACKEND_COMPILE_EVENT, 0.5)
        runtime._on_duration(runtime.BACKEND_COMPILE_EVENT, 0.5)
    t1 = time.perf_counter()
    got = runtime.compile_counts(since=t0, until=t1)["t.load"]
    assert got == {"compiles": 1, "loads": 1, "seconds": 1.0}
    assert "t.load" not in runtime.compile_counts(until=t0)
    outside = runtime.compile_counts(since=t1)
    assert "t.load" not in outside


def test_program_has_one_compile_listener():
    from jax._src import monitoring

    from mpi_cuda_process_tpu import cli  # noqa: F401 — the whole program

    runtime.RuntimeRecorder()
    runtime.RuntimeRecorder()
    ours = [fn for fn in monitoring.get_event_duration_listeners()
            if getattr(fn, "__module__", "").startswith(
                "mpi_cuda_process_tpu")]
    assert ours == [runtime._on_duration]


# ---------------------------------------------------- kernel names

def _pallas_names():
    """{name: file} of every ``pallas_call(name=...)`` in ops/pallas (both
    arms of a conditional name)."""
    found = {}
    for path in sorted(glob.glob(os.path.join(PALLAS_DIR, "*.py"))):
        for node in ast.walk(ast.parse(open(path).read())):
            if not (isinstance(node, ast.Call)
                    and getattr(node.func, "attr", None) == "pallas_call"):
                continue
            kw = {k.arg: k.value for k in node.keywords}
            assert "name" in kw, f"unnamed pallas_call in {path}:" \
                                 f"{node.lineno}"
            value = kw["name"]
            arms = [value.body, value.orelse] \
                if isinstance(value, ast.IfExp) else [value]
            for arm in arms:
                assert isinstance(arm, ast.Constant), (path, node.lineno)
                assert arm.value not in found, arm.value
                found[arm.value] = os.path.basename(path)
    return found


def _abstract(st, grid):
    return (tuple(jax.ShapeDtypeStruct(tuple(grid), st.dtype)
                  for _ in range(st.num_fields)),)


def _fused(name, grid, k, **kw):
    from mpi_cuda_process_tpu.ops.pallas.fused import make_fused_step

    st = make_stencil(name)
    return make_fused_step(st, grid, k, interpret=True, **kw), \
        _abstract(st, grid)


def _sharded(name, grid, mesh_shape, k, **kw):
    from mpi_cuda_process_tpu.parallel.mesh import make_mesh
    from mpi_cuda_process_tpu.parallel.stepper import (
        make_sharded_fused_step,
    )

    st = make_stencil(name)
    step = make_sharded_fused_step(st, make_mesh(mesh_shape), grid, k,
                                   interpret=True, **kw)
    return step, _abstract(st, grid)


def _stream(name, grid, k):
    from mpi_cuda_process_tpu.ops.pallas.streamfused import (
        make_stream_fused_step,
    )

    st = make_stencil(name)
    return make_stream_fused_step(st, grid, k, interpret=True), \
        _abstract(st, grid)


def _fullgrid(name, grid, k):
    from mpi_cuda_process_tpu.ops.pallas.fullgrid import make_fullgrid_step

    st = make_stencil(name)
    return make_fullgrid_step(st, grid, k, interpret=True), \
        _abstract(st, grid)


def _raw(name, grid):
    from mpi_cuda_process_tpu.ops.pallas.rawstep import make_raw_step

    st = make_stencil(name)
    return make_raw_step(st, grid, interpret=True), _abstract(st, grid)


def _sharded_raw(name, grid, mesh_shape):
    from mpi_cuda_process_tpu.parallel.mesh import make_mesh
    from mpi_cuda_process_tpu.parallel.stepper import make_sharded_raw_step

    st = make_stencil(name)
    step = make_sharded_raw_step(st, make_mesh(mesh_shape), grid,
                                 interpret=True)
    return step, _abstract(st, grid)


def _compute(name, grid):
    from mpi_cuda_process_tpu.ops.pallas import make_pallas_compute

    st = make_stencil(name)
    return driver.make_step(st, grid, compute_fn=make_pallas_compute(
        st, interpret=True)), _abstract(st, grid)


_KERNELS = {
    "fused_padfree": lambda: _fused("heat3d", (64, 64, 128), 4,
                                    padfree=True),
    "fused_padded": lambda: _fused("heat3d", (64, 64, 128), 4),
    "fused_zslab_padfree": lambda: _sharded(
        "heat3d", (64, 32, 128), (2, 1, 1), 4, kind="padfree"),
    "fused_yzslab_padfree": lambda: _sharded(
        "heat3d", (32, 32, 128), (2, 2, 1), 4, kind="padfree"),
    "fused_zslab_xwin": lambda: _sharded(
        "wave3d", (128, 4096, 4096), (2, 1, 1), 4, kind="padfree"),
    "fused_yzslab_xwin": lambda: _sharded(
        "wave3d", (128, 8192, 4096), (2, 2, 1), 4, kind="padfree"),
    "fused_stream": lambda: _stream("heat3d", (96, 32, 128), 4),
    "fused_stream_zslab": lambda: _sharded(
        "heat3d", (48, 32, 128), (2, 1, 1), 4, kind="stream"),
    "fused_stream_yz": lambda: _sharded(
        "heat3d", (48, 64, 128), (2, 2, 1), 4, kind="stream"),
    "halo_ring_dma": lambda: _sharded(
        "heat3d", (48, 32, 128), (2, 1, 1), 4, kind="stream",
        exchange="rdma"),
    "fused_fullgrid": lambda: _fullgrid("life", (16, 128), 4),
    "rawstep_taps": lambda: _raw("heat3d", (16, 16, 128)),
    "rawstep_wave": lambda: _raw("wave3d", (16, 16, 128)),
    "rawstep_grayscott": lambda: _raw("grayscott3d", (16, 16, 128)),
    "rawstep_sharded": lambda: _sharded_raw("heat3d", (32, 32, 128),
                                            (2, 2, 1)),
    "zchunk_taps": lambda: _compute("heat3d", (16, 16, 128)),
    "zchunk_wave": lambda: _compute("wave3d", (16, 16, 128)),
    "whole2d": lambda: _compute("heat2d", (16, 128)),
}


def test_every_pallas_call_is_named_and_covered():
    assert set(_pallas_names()) == set(_KERNELS)


@pytest.mark.parametrize("kernel", sorted(_KERNELS))
def test_kernel_name_in_lowered_text(kernel):
    from perfbench import trace as trace_lib

    assert not any(m in kernel.lower() for m in trace_lib._COMM_MARKERS)
    step, args = _KERNELS[kernel]()
    assert step is not None, kernel
    text = jax.jit(step).lower(*args).as_text(debug_info=True)
    assert kernel in text
