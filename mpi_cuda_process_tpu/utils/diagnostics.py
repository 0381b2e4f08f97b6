"""Field diagnostics for logging/observability.

ABSENT in the reference beyond commented-out debug prints (kernel.cu:73, 94,
197, 232) — SURVEY.md §5.5.  Provides the per-interval quantities the CLI
logs: Game-of-Life population count, field min/max/mean, and the Jacobi
residual norm (how far the diffusion state is from its fixed point).  All
reductions are jnp-level, so on sharded arrays XLA lowers them to per-shard
reductions + a psum-style cross-device combine over ICI.

Transfer discipline: every metric used to end in its own blocking
``float()`` — one device->host round-trip per metric.  The reductions are
now staged as jnp scalars and fetched with a single ``jax.device_get``
per logging interval, so a four-metric log line pays one round-trip,
not four.
"""

from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp

from ..obs.spans import region
from ..ops.stencil import Stencil


def _staged_diagnostics(stencil: Stencil, fields, step_fn=None):
    """The metric set as UNfetched jnp scalars (device-side)."""
    f0 = fields[0]
    out = {}
    if stencil.name == "life":
        out["population"] = jnp.sum(f0)
    else:
        out["mean"] = jnp.mean(f0)
        out["min"] = jnp.min(f0)
        out["max"] = jnp.max(f0)
    if stencil.num_fields > 1:
        # wave: discrete energy proxy |u - u_prev| (velocity magnitude)
        out["velocity_l2"] = jnp.sqrt(
            jnp.sum((fields[0] - fields[1]) ** 2))
    elif step_fn is not None and jnp.issubdtype(f0.dtype, jnp.inexact):
        # diffusion-class models: how far from the Jacobi fixed point
        out["residual"] = _residual_scalar(step_fn, fields)
    return out


def field_diagnostics(stencil: Stencil, fields, step_fn=None) -> Dict[str, float]:
    """All metrics for one logging interval — ONE host transfer total.

    Regions (``obs/spans.region``): ``sim.diagnostics`` around the call,
    ``sim.diagnostics.stage`` around building and dispatching the
    reductions (and the residual step: on a mesh it runs op by op, each
    op dispatched — and compiled or loaded — here), and
    ``sim.diagnostics.fetch`` around the transfer, the wait for the
    device."""
    with region("sim.diagnostics"):
        with region("sim.diagnostics.stage"):
            staged = _staged_diagnostics(stencil, fields, step_fn=step_fn)
        with region("sim.diagnostics.fetch"):
            fetched = jax.device_get(staged)  # batched: one round-trip
        return {k: float(v) for k, v in fetched.items()}


def _residual_scalar(step_fn, fields):
    """One-step-change L2 norm as an unfetched jnp scalar."""
    new = step_fn(tuple(fields))
    return jnp.sqrt(jnp.sum(
        (new[0].astype(jnp.float32) - fields[0].astype(jnp.float32)) ** 2))


def residual_norm(step_fn, fields) -> float:
    """L2 norm of one-step change — the Jacobi convergence residual.

    Costs one extra (non-advancing) step evaluation; only run at logging
    cadence (``--log-every``), never in the hot loop.  Standalone callers
    pay one transfer; :func:`field_diagnostics` batches it with the rest.
    """
    return float(jax.device_get(_residual_scalar(step_fn, fields)))


def format_diagnostics(d: Dict[str, float]) -> str:
    return "  ".join(f"{k}={v:.6g}" for k, v in d.items())
