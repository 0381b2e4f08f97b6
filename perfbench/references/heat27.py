"""Plain reference of the heat3d27 configuration, written from the equations.

The update is BASELINE config 4's isotropic 27-point diffusion step:
every interior cell becomes ``u + alpha * sum(w * u)`` over its 3x3x3
neighbourhood, the weight of a neighbour set by how many of its three
offsets are nonzero: centre -128/30, face 14/30, edge 3/30, corner 1/30
(per axis the second moments sum to 2, the 7-point Laplacian's).  The
1-cell frame keeps the wall value ``bc``.  The initial state is
Bernoulli(``density``) occupancy drawn with ``jax.random`` from the seed
(the semantics of the CLI's ``--init random``), frame set to ``bc``.

Imports nothing of the program and takes nothing it made: the numbers
(alpha, bc, density, grid) come from the configuration file.  It runs on
one device, unsharded, after the program's state is freed.  The 27 taps
are summed one by one, in z, y, x order, as the equation reads.
"""

from __future__ import annotations

import functools
import itertools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

# weight x 30 by the number of nonzero offsets: centre, face, edge, corner
_W30 = (-128.0, 14.0, 3.0, 1.0)


def _frame(shape):
    inner = jnp.ones(tuple(n - 2 for n in shape), bool)
    return ~jnp.pad(inner, 1, constant_values=False)


@functools.partial(jax.jit, static_argnums=(1, 2, 3, 4))
def _initial_state(seed, shape, density, bc, dtype):
    occ = jax.random.bernoulli(jax.random.PRNGKey(seed), density, shape)
    return jnp.where(_frame(shape), jnp.asarray(bc, dtype), occ.astype(dtype))


def initial_state(config, seed, device, dtype=jnp.float32):
    """The seed is an argument, not a constant of the program, so one
    compiled program serves every seed; ``PRNGKey`` of a 32-bit seed is
    ``[0, seed]``, as of the Python int below 2**32 the CLI passes."""
    seed = jax.device_put(np.uint32(seed % 2**32), device)
    return _initial_state(seed, tuple(config["grid"]), config["density"],
                          config["bc"], jnp.dtype(dtype))


def step(u, alpha):
    """One 27-point step in ``u.dtype``; the frame is left as it is."""
    nz, ny, nx = u.shape
    acc = None
    for dz, dy, dx in itertools.product((-1, 0, 1), repeat=3):
        w = jnp.asarray(_W30[abs(dz) + abs(dy) + abs(dx)] / 30.0, u.dtype)
        tap = w * u[1 + dz:nz - 1 + dz, 1 + dy:ny - 1 + dy,
                    1 + dx:nx - 1 + dx]
        acc = tap if acc is None else acc + tap
    c = u[1:-1, 1:-1, 1:-1]
    return u.at[1:-1, 1:-1, 1:-1].set(c + jnp.asarray(alpha, u.dtype) * acc)


@functools.partial(jax.jit, static_argnums=(1, 2), donate_argnums=0)
def advance(u, steps, alpha):
    return lax.fori_loop(0, steps, lambda _, v: step(v, alpha), u)


@functools.partial(jax.jit, static_argnums=(1, 2))
def _diagnostics(u, alpha, residual):
    f = u.astype(jnp.float32)
    out = {"mean": jnp.mean(f), "min": jnp.min(f), "max": jnp.max(f)}
    if residual:
        out["residual"] = jnp.sqrt(jnp.sum(
            (step(u, alpha).astype(jnp.float32) - f) ** 2))
    return out


def diagnostics(u, config, residual):
    """mean, min, max (and the one-step change's L2 norm) as floats."""
    got = jax.device_get(_diagnostics(u, config["alpha"], residual))
    return {k: float(v) for k, v in got.items()}
