"""How ``correct`` is decided: the run's answers against the plain reference.

The state evolves, as a model's weights do in training, so the reference
follows the run's first ``FOLLOW`` chunks from the seed: the warm chunk of
set-up and the first window chunks, all driven through the window's own
runner and diagnostics call.  Compared:

* ``state_plane_gap``: the largest absolute gap over whole planes of the
  state after the warm chunk (the planes next to every shard face along
  axis 0, the frame's neighbours, and planes drawn from the seed);
* ``state_colsum_gap``: the largest gap of a column sum, along axis 0 and
  along axis 2, as a share of the reference's sum: every cell of the state
  after the warm chunk lies in two such columns;
* ``diag_rel_gap``: the largest relative gap of any value that
  ``field_diagnostics`` reported for a followed chunk.

Each has its limit in the configuration file (``limits``); PERF.md gives
the readings each limit was set from.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

NAMES = ("state_plane_gap", "state_colsum_gap", "diag_rel_gap")
# chunks the reference follows: the warm chunk and the first two of the window
FOLLOW = 3


def plane_indices(config, seed, drawn=4):
    """Planes along axis 0: both sides of every shard face, the frame's
    neighbours, and ``drawn`` more from the seed (as many for every seed,
    so one compiled digest serves them all)."""
    n = config["grid"][0]
    parts = (config.get("mesh") or [1])[0]
    idx = [1, n - 2]
    for k in range(1, parts):
        idx += [k * n // parts - 1, k * n // parts]
    rng = np.random.default_rng(seed)
    idx += [int(i) for i in rng.integers(1, n - 1, size=drawn)]
    return np.asarray(idx, np.int32)


@jax.jit
def _digest(u, planes):
    f = u.astype(jnp.float32)
    return {"planes": f[planes],
            "sum0": jnp.sum(f, axis=0), "sum2": jnp.sum(f, axis=2)}


def digest(u, planes):
    """Host copies of the compared parts of a state."""
    return jax.device_get(_digest(u, planes))


def _rel(got, want):
    return float(np.max(np.abs(got - want) / np.maximum(np.abs(want), 1.0)))


def gaps(prog_digest, prog_diags, ref_digest, ref_diags):
    """The compared numbers: program (or control) against the reference."""
    plane = float(np.max(np.abs(prog_digest["planes"]
                                - ref_digest["planes"])))
    colsum = max(_rel(prog_digest[k], ref_digest[k]) for k in ("sum0", "sum2"))
    rel = [abs(got[k] - w) / max(abs(w), 1e-6)
           for got, want in zip(prog_diags, ref_diags) for k, w in want.items()
           if k in got]
    missing = len(prog_diags) != len(ref_diags) or any(
        set(got) != set(want) for got, want in zip(prog_diags, ref_diags))
    # np.max keeps a NaN, where Python's max could drop it
    diag = float("inf") if missing or not rel else float(np.max(rel))
    return {"state_plane_gap": plane, "state_colsum_gap": colsum,
            "diag_rel_gap": diag}


def reference_answers(config, ref, seed, steps, follow, residual, planes,
                      device, dtype=jnp.float32):
    """The reference's diagnostics after each of ``follow`` chunks of
    ``steps`` steps, and its digest after the first."""
    u = ref.initial_state(config, seed, device, dtype)
    diags, first = [], None
    for c in range(follow):
        u = ref.advance(u, steps, config["alpha"])
        diags.append(ref.diagnostics(u, config, residual))
        if c == 0:
            first = digest(u, planes)
    del u
    return first, diags


def judge(numbers, limits):
    """(correct, {name: {"value", "limit"}}) — NaN fails."""
    out = {k: {"value": numbers[k], "limit": limits[k]} for k in NAMES}
    ok = all(v["value"] <= v["limit"] for v in out.values())
    return ok, out
