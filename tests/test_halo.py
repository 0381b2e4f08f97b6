"""Unit tests for the ppermute halo exchange (SURVEY.md §4.2).

Strategy: build a globally-known array, shard it over a mesh axis with
shard_map, run the exchange, and check every shard's padded block against
slices of the (constant-padded) global array.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from mpi_cuda_process_tpu.parallel.halo import exchange_and_pad
from mpi_cuda_process_tpu.parallel.mesh import make_mesh

shard_map = jax.shard_map


@pytest.mark.parametrize("n_shards,halo", [(2, 1), (4, 1), (2, 2), (4, 3)])
def test_exchange_1d_decomposition(n_shards, halo):
    bc = -7.0
    g = np.arange(16 * 5, dtype=np.float32).reshape(16, 5)
    mesh = make_mesh((n_shards,))
    local = 16 // n_shards

    def f(x):
        return exchange_and_pad(x, ("sx", None), (n_shards, 1), halo, bc)

    out = shard_map(f, mesh=mesh, in_specs=P("sx", None),
                    out_specs=P("sx", None))(jnp.asarray(g))
    # Reassemble per-shard padded blocks and compare to global padded slices.
    gp = np.pad(g, halo, constant_values=bc)
    out = np.asarray(out).reshape(n_shards, local + 2 * halo, 5 + 2 * halo)
    for i in range(n_shards):
        want = gp[i * local:i * local + local + 2 * halo, :]
        np.testing.assert_array_equal(out[i], want)


@pytest.mark.parametrize("mesh_shape", [(2, 2), (2, 4), (4, 2)])
def test_exchange_2d_corners(mesh_shape):
    """Two-pass axis-wise exchange must deliver corner data (27-point needs)."""
    halo, bc = 1, 0.0
    g = np.arange(8 * 8, dtype=np.float32).reshape(8, 8)
    mesh = make_mesh(mesh_shape)
    ly, lx = 8 // mesh_shape[0], 8 // mesh_shape[1]

    def f(x):
        return exchange_and_pad(
            x, ("sx", "sy"), mesh_shape, halo, bc)

    out = shard_map(f, mesh=mesh, in_specs=P("sx", "sy"),
                    out_specs=P("sx", "sy"))(jnp.asarray(g))
    gp = np.pad(g, halo, constant_values=bc)
    out = np.asarray(out).reshape(
        mesh_shape[0], ly + 2, mesh_shape[1], lx + 2).transpose(0, 2, 1, 3)
    for i in range(mesh_shape[0]):
        for j in range(mesh_shape[1]):
            want = gp[i * ly:i * ly + ly + 2, j * lx:j * lx + lx + 2]
            np.testing.assert_array_equal(out[i, j], want)


def test_exchange_periodic_wraps():
    g = np.arange(8, dtype=np.float32).reshape(8, 1)
    mesh = make_mesh((4,))

    def f(x):
        return exchange_and_pad(x, ("sx", None), (4, 1), 1, 0.0, periodic=True)

    out = shard_map(f, mesh=mesh, in_specs=P("sx", None),
                    out_specs=P("sx", None))(jnp.asarray(g))
    out = np.asarray(out).reshape(4, 4, 3)
    # shard 0's left halo is global row 7; shard 3's right halo is global row 0
    assert out[0, 0, 1] == 7.0
    assert out[3, -1, 1] == 0.0
