"""Whole-step raw Pallas kernels == driver.make_step.

Interpret-mode equivalence (SURVEY.md §4.4's Pallas CI strategy): the raw
kernels replace the ENTIRE pad -> update -> frame-re-pin step, so the
invariant is stronger than the compute_fn kernels' — the whole step function
must match, frame semantics included, over multiple steps.  Tolerance is a
few ULP at the field's scale (not bit-exact: XLA may contract mul+add to FMA
differently in the two graphs), except the frame cells, which both paths
must preserve verbatim.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from mpi_cuda_process_tpu import driver
from mpi_cuda_process_tpu.ops import make_stencil
from mpi_cuda_process_tpu.ops.pallas import rawstep
from mpi_cuda_process_tpu.utils.init import init_state

CASES = [
    ("heat3d", (16, 18, 130), {}),
    ("heat3d", (8, 10, 12), {"dtype": jnp.bfloat16}),
    ("heat3d27", (16, 12, 14), {}),
    ("heat3d4th", (16, 14, 130), {}),
    ("wave3d", (16, 18, 12), {}),
    ("advect3d", (16, 10, 12), {"cx": 0.3, "cy": -0.2, "cz": 0.25}),
    ("grayscott3d", (16, 12, 130), {}),
]


@pytest.mark.parametrize("name,grid,kw", CASES,
                         ids=[f"{n}-{'x'.join(map(str, g))}"
                              for n, g, kw in CASES])
def test_raw_step_matches_driver(name, grid, kw):
    st = make_stencil(name, **kw)
    raw = rawstep.make_raw_step(st, grid, interpret=True)
    assert raw is not None, "tileable case must build"
    ref = driver.make_step(st, grid)
    a = b = init_state(st, grid, 3, 0.2, "auto")
    for _ in range(4):
        a, b = raw(a), ref(b)
    eps = float(jnp.finfo(st.dtype).eps)
    scale = max(float(jnp.max(jnp.abs(b[0]).astype(jnp.float32))), 1.0)
    for x, y in zip(a, b):
        xn = np.asarray(x, dtype=np.float32)
        yn = np.asarray(y, dtype=np.float32)
        np.testing.assert_allclose(xn, yn, rtol=0, atol=32 * eps * scale)
        # frame cells: verbatim, no tolerance
        h = st.halo
        for d in range(3):
            lo = [slice(None)] * 3
            hi = [slice(None)] * 3
            lo[d], hi[d] = slice(0, h), slice(-h, None)
            np.testing.assert_array_equal(xn[tuple(lo)], yn[tuple(lo)])
            np.testing.assert_array_equal(xn[tuple(hi)], yn[tuple(hi)])


def test_unsupported_returns_none():
    st2d = make_stencil("heat2d")
    assert rawstep.make_raw_step(st2d, (32, 32), interpret=True) is None
    life = make_stencil("life")
    assert not rawstep.raw_step_supported(life)
    st = make_stencil("heat3d")
    # untileable Z (prime) -> None, caller falls back to jnp
    assert rawstep.make_raw_step(st, (7, 16, 16), interpret=True) is None


@pytest.mark.parametrize("n", [16, 15])
def test_inplace_wave_runner_matches_plain_steps(n):
    """The leapfrog kernel writes u_new over u_prev's buffer and the runner
    advances two steps per scan iteration (an odd count runs its last step
    after the scan): the same states as n plain steps, bit for bit."""
    import jax

    st = make_stencil("wave3d")
    grid = (16, 16, 128)
    raw = rawstep.make_raw_step(st, grid, interpret=True)
    assert raw._carry_period == 2
    call = [e for e in jax.make_jaxpr(raw)(init_state(st, grid, 3, 0.5,
                                                      "random")).eqns
            if e.primitive.name == "pallas_call"]
    assert [tuple(e.params["input_output_aliases"]) for e in call] == \
        [((3, 0),)]  # u_prev in, u_new out: one buffer
    fields = init_state(st, grid, 3, 0.5, "random")
    want = driver.make_runner(driver.make_step(st, grid), n)(
        tuple(jnp.copy(f) for f in fields))
    got = driver.make_runner(raw, n)(tuple(jnp.copy(f) for f in fields))
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


@pytest.mark.parametrize("n", [0, 1, 4, 5])
def test_runner_advances_a_carry_period_per_iteration(n):
    """A step that declares a carry period p runs p steps a scan iteration
    and the rest after the scan: n steps in all, in order."""
    def step(fields):
        a, b = fields
        return (2.0 * a - b + 1.0, a)

    periodic = lambda fields: step(fields)  # noqa: E731
    periodic._carry_period = 2
    fields = (jnp.arange(4.0), jnp.ones(4))
    want = fields
    for _ in range(n):
        want = step(want)
    got = driver.make_runner(periodic, n)(tuple(jnp.copy(f)
                                                for f in fields))
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


# The sharded twin: one Pallas pass over the unpadded shard, faces as
# operands.  Local blocks of 24 or 48 planes give three z-chunks a shard,
# so the first, a middle and the last chunk all run.
SHARDED_GRID = (48, 32, 256)
SHARDED_MESHES = [(2, 2, 1), (2, 1, 1), (1, 2, 1)]


@pytest.fixture(scope="module", params=SHARDED_MESHES,
                ids=["x".join(map(str, m)) for m in SHARDED_MESHES])
def sharded_pair(request):
    """(sharded raw step, jnp sharded step, initial sharded state)."""
    from mpi_cuda_process_tpu.parallel import stepper
    from mpi_cuda_process_tpu.parallel.mesh import make_mesh

    st = make_stencil("heat3d")
    mesh = make_mesh(request.param)
    raw = stepper.make_sharded_raw_step(st, mesh, SHARDED_GRID,
                                        interpret=True)
    assert raw is not None and raw._raw_kind == "sharded"
    ref = stepper.make_sharded_step(st, mesh, SHARDED_GRID)
    fields = stepper.shard_fields(
        init_state(st, SHARDED_GRID, 3, 0.5, "random"), mesh, 3)
    return raw, ref, fields


@pytest.mark.parametrize("n", [1, 2, 3, 8])
def test_sharded_raw_step_matches_the_jnp_sharded_step(sharded_pair, n):
    """A lone pass, paired passes, an odd count and the 2x2 cell's count
    of 8, through the runner: bit for bit the jnp sharded step's state,
    and the global frame holds bc on every shard."""
    raw, ref, fields = sharded_pair
    assert raw._carry_period == 2 and raw._out_of_place
    got = driver.make_runner(raw, n)(tuple(jnp.copy(f) for f in fields))
    want = driver.make_runner(ref, n)(tuple(jnp.copy(f) for f in fields))
    g, w = np.asarray(got[0]), np.asarray(want[0])
    np.testing.assert_array_equal(g, w)
    assert not np.array_equal(g, np.asarray(fields[0]))
    bc = make_stencil("heat3d").bc_value[0]
    for d in range(3):
        for end in (0, -1):
            np.testing.assert_array_equal(np.take(g, end, axis=d), bc)


_DECLINES = [
    ("periodic", "heat3d", {}, (2, 2, 1), SHARDED_GRID,
     {"periodic": True}),
    ("x-sharded", "heat3d", {}, (2, 1, 2), SHARDED_GRID, {}),
    ("ensemble", "heat3d", {}, (2, 2, 1), SHARDED_GRID, {"ensemble": 2}),
    ("heat3d27", "heat3d27", {}, (2, 2, 1), SHARDED_GRID, {}),
    ("bf16", "heat3d", {"dtype": jnp.bfloat16}, (2, 2, 1), SHARDED_GRID,
     {}),
    # 7 planes a shard: no z-chunk of 2 or more tiles them
    ("untileable", "heat3d", {}, (2, 1, 1), (14, 32, 256), {}),
]


@pytest.mark.parametrize("name,kw,mesh_shape,grid,opts",
                         [c[1:] for c in _DECLINES],
                         ids=[c[0] for c in _DECLINES])
def test_sharded_raw_step_declines(name, kw, mesh_shape, grid, opts):
    from mpi_cuda_process_tpu.parallel import stepper
    from mpi_cuda_process_tpu.parallel.mesh import make_mesh

    st = make_stencil(name, **kw)
    assert stepper.make_sharded_raw_step(
        st, make_mesh(mesh_shape), grid, interpret=True, **opts) is None
