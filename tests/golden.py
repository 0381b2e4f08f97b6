"""Pure-numpy golden implementations of every stencil (SURVEY.md §4.1).

Written loop-style, independently of the JAX ops, directly from the
reference's per-cell math: the B3/S23 rule (kernel.cu:66) and the FTCS update
(MDF_kernel.cu:20).  Grids include their guard frame; frame cells never change.
"""

import itertools

import numpy as np


def _neighborhood_sum(grid, offsets, weights=None):
    out = np.zeros_like(grid, dtype=np.float64)
    nd = grid.ndim
    for k, off in enumerate(offsets):
        w = 1.0 if weights is None else weights[k]
        src = tuple(
            slice(max(0, -o), grid.shape[d] - max(0, o)) for d, o in enumerate(off)
        )
        dst = tuple(
            slice(max(0, o), grid.shape[d] - max(0, -o)) for d, o in enumerate(off)
        )
        out[dst] += w * grid[src]
    return out


def life_step(grid: np.ndarray) -> np.ndarray:
    h, w = grid.shape
    new = grid.copy()
    for y in range(1, h - 1):
        for x in range(1, w - 1):
            n = int(grid[y - 1:y + 2, x - 1:x + 2].sum()) - int(grid[y, x])
            new[y, x] = 1 if (n == 3 or (n == 2 and grid[y, x] == 1)) else 0
    return new


def life_steps_vec(grid: np.ndarray, n: int) -> np.ndarray:
    """``n`` generations of :func:`life_step`, vectorized with shifted
    slices (same B3/S23 rule, same pinned frame) for grids where the
    per-cell loop is too slow (chip_smoke.py's 4096^2 check)."""
    grid = np.asarray(grid)
    for _ in range(n):
        c = grid[1:-1, 1:-1]
        nb = sum(grid[1 + dy:grid.shape[0] - 1 + dy,
                      1 + dx:grid.shape[1] - 1 + dx].astype(np.int32)
                 for dy in (-1, 0, 1) for dx in (-1, 0, 1) if dy or dx)
        new = grid.copy()
        new[1:-1, 1:-1] = ((nb == 3) | ((nb == 2) & (c == 1))).astype(
            grid.dtype)
        grid = new
    return grid


def heat_step(grid: np.ndarray, alpha: float) -> np.ndarray:
    """FTCS axis-neighbor diffusion, any ndim; frame pinned."""
    nd = grid.ndim
    new = grid.copy()
    it = [range(1, s - 1) for s in grid.shape]
    for idx in itertools.product(*it):
        u = grid[idx]
        acc = 0.0
        for d in range(nd):
            for s in (-1, 1):
                j = list(idx)
                j[d] += s
                acc += grid[tuple(j)]
        new[idx] = u + alpha * (acc - 2 * nd * u)
    return new


def heat27_step(grid: np.ndarray, alpha: float) -> np.ndarray:
    wf, we, wc, w0 = 14.0 / 30, 3.0 / 30, 1.0 / 30, -128.0 / 30
    new = grid.copy()
    it = [range(1, s - 1) for s in grid.shape]
    for idx in itertools.product(*it):
        acc = w0 * grid[idx]
        for off in itertools.product((-1, 0, 1), repeat=3):
            nz = sum(1 for o in off if o)
            if nz == 0:
                continue
            j = tuple(i + o for i, o in zip(idx, off))
            acc += (wf, we, wc)[nz - 1] * grid[j]
        new[idx] = grid[idx] + alpha * acc
    return new


def wave_step(u: np.ndarray, u_prev: np.ndarray, c2dt2: float):
    nd = u.ndim
    new = u.copy()
    it = [range(1, s - 1) for s in u.shape]
    for idx in itertools.product(*it):
        acc = 0.0
        for d in range(nd):
            for s in (-1, 1):
                j = list(idx)
                j[d] += s
                acc += u[tuple(j)]
        lap = acc - 2 * nd * u[idx]
        new[idx] = 2 * u[idx] - u_prev[idx] + c2dt2 * lap
    return new, u.copy()


def heat4th_step(grid: np.ndarray, alpha: float) -> np.ndarray:
    """4th-order 13-point Laplacian, halo 2; 2-cell frame pinned."""
    nd = grid.ndim
    new = grid.copy()
    it = [range(2, s - 2) for s in grid.shape]
    w = {1: 16.0 / 12.0, 2: -1.0 / 12.0}
    for idx in itertools.product(*it):
        acc = -30.0 / 12.0 * nd * grid[idx]
        for d in range(nd):
            for dist in (1, 2):
                for s in (-dist, dist):
                    j = list(idx)
                    j[d] += s
                    acc += w[dist] * grid[tuple(j)]
        new[idx] = grid[idx] + alpha * acc
    return new
