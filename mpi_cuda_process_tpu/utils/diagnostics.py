"""Field diagnostics for logging/observability.

ABSENT in the reference beyond commented-out debug prints (kernel.cu:73, 94,
197, 232) — SURVEY.md §5.5.  Provides the per-interval quantities the CLI
logs: Game-of-Life population count, field min/max/mean, and the Jacobi
residual norm (how far the diffusion state is from its fixed point).  All
reductions are jnp-level, so on sharded arrays XLA lowers them to per-shard
reductions + a psum-style cross-device combine over ICI.

One compiled program per observation: the metric set — the reductions,
``velocity_l2`` and the residual through ``step_fn`` — is one ``jax.jit``
program (named ``observe``), built once per (stencil family, step
function) and dispatched once per logging interval, its scalars fetched
with a single ``jax.device_get``.  Staged op by op instead, each reduction
read the field from HBM on its own, and a residual through an un-jitted
sharded step (a bare ``shard_map``) dispatched — and compiled or loaded —
every op from Python on every call.  The jit keys on what it is given
(shapes, dtypes, shardings), so one program per step function covers
ensembles, meshes and every stencil family.  No donation: the caller keeps
the fields.  :func:`program_stats` counts the programs built and the calls
that ran one.
"""

from __future__ import annotations

import weakref
from typing import Dict

import jax
import jax.numpy as jnp

from ..obs.spans import region
from ..ops.stencil import Stencil

# {step_fn: {key: program}}; a step function that is dropped (a policy
# migration swapped it) takes its programs with it
_programs_by_step: "weakref.WeakKeyDictionary" = weakref.WeakKeyDictionary()
_programs_no_step: Dict[tuple, object] = {}
_stats = {"built": 0, "calls": 0}


def _metrics(key, fields, step_fn):
    """The metric set of ``key`` as jnp scalars (traced inside the
    program)."""
    family, velocity, residual = key
    f0 = fields[0]
    out = {}
    if family == "population":
        out["population"] = jnp.sum(f0)
    elif family == "range":
        out["mean"] = jnp.mean(f0)
        out["min"] = jnp.min(f0)
        out["max"] = jnp.max(f0)
    if velocity:
        # wave: discrete energy proxy |u - u_prev| (velocity magnitude)
        out["velocity_l2"] = jnp.sqrt(
            jnp.sum((fields[0] - fields[1]) ** 2))
    if residual:
        # diffusion-class models: how far from the Jacobi fixed point,
        # the L2 norm of one (non-advancing) step's change
        new = step_fn(tuple(fields))
        out["residual"] = jnp.sqrt(jnp.sum(
            (new[0].astype(jnp.float32) - f0.astype(jnp.float32)) ** 2))
    return out


def _program(key, step_fn):
    """The cached ``jax.jit`` program of ``key`` over ``step_fn``."""
    table = _programs_no_step if step_fn is None else \
        _programs_by_step.setdefault(step_fn, {})
    program = table.get(key)
    if program is None:
        # a weak reference: the program must not keep its cache key alive
        step_ref = None if step_fn is None else weakref.ref(step_fn)

        def observe(fields):
            return _metrics(key, fields,
                            None if step_ref is None else step_ref())

        program = table[key] = jax.jit(observe)
        _stats["built"] += 1
    _stats["calls"] += 1
    return program


def program_stats() -> Dict[str, int]:
    """``{"built": programs built, "calls": calls that ran one}`` in this
    process: a repeated observation builds nothing."""
    return dict(_stats)


def field_diagnostics(stencil: Stencil, fields, step_fn=None) -> Dict[str, float]:
    """All metrics for one logging interval — ONE program, ONE host transfer.

    Regions (``obs/spans.region``): ``sim.diagnostics`` around the call,
    ``sim.diagnostics.stage`` around the one dispatch of the observation
    program (its trace and compile, or cache load, on the first call for a
    step function and input layout), and ``sim.diagnostics.fetch`` around
    the transfer, the wait for the device."""
    family = "population" if stencil.name == "life" else "range"
    velocity = stencil.num_fields > 1
    residual = not velocity and step_fn is not None and \
        jnp.issubdtype(fields[0].dtype, jnp.inexact)
    key = (family, velocity, residual)
    with region("sim.diagnostics"):
        with region("sim.diagnostics.stage"):
            staged = _program(key, step_fn)(tuple(fields))
        with region("sim.diagnostics.fetch"):
            fetched = jax.device_get(staged)  # batched: one round-trip
        return {k: float(v) for k, v in fetched.items()}


def residual_norm(step_fn, fields) -> float:
    """L2 norm of one-step change — the Jacobi convergence residual.

    Costs one extra (non-advancing) step evaluation; only run at logging
    cadence (``--log-every``), never in the hot loop.  Standalone callers
    pay one transfer; :func:`field_diagnostics` batches it with the rest.
    """
    staged = _program((None, False, True), step_fn)(tuple(fields))
    return float(jax.device_get(staged["residual"]))


def format_diagnostics(d: Dict[str, float]) -> str:
    return "  ".join(f"{k}={v:.6g}" for k, v in d.items())
