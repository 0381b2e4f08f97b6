"""Time-stepping driver: the shared replacement for the reference's per-rank loops.

The reference duplicates its driver loop inline per rank and per program
(kernel.cu:202-269, MDF_kernel.cu:155-222) and, as written, re-uploads the full
grid host->device every iteration and discards kernel results because the
double-buffer swap is commented out (kernel.cu:211/224 — SURVEY.md §3.5).  The
*intended* semantics — double-buffered Jacobi time stepping — are implemented
here the JAX way: state is device-resident across the whole run, the step is a
pure function, ``lax.scan`` carries the new state (the "swap" is the carry),
and buffer donation makes the double buffer allocation-free.

Boundary semantics: the grid INCLUDES its guard frame, exactly like the
reference (``create_universe`` pins a 1-cell frame: 0 for Life kernel.cu:137-138,
100.0 for MDF MDF_kernel.cu:92-93).  Each step updates interior cells and
re-imposes the frame, so frame cells hold their initial (Dirichlet) values for
the whole run.
"""

from __future__ import annotations

import time
from typing import Sequence

import jax
import jax.numpy as jnp
from jax import lax

from .obs.spans import region
from .ops.stencil import Fields, Stencil
from .resilience import faults


def frame_mask(
    local_shape: Sequence[int],
    global_shape: Sequence[int],
    offsets: Sequence[jax.Array | int],
    width: int,
) -> jax.Array:
    """Boolean mask of guard-frame cells for a block of a (possibly sharded) grid.

    ``offsets[d]`` is the global index of the block's first cell along axis d
    (0 when unsharded; ``axis_index * local_size`` inside shard_map).  A cell is
    frame iff its global coordinate is within ``width`` of either wall on any
    axis — the N-D generalization of the reference's 1-cell frame.
    """
    ndim = len(local_shape)
    mask = None
    for d in range(ndim):
        coord = lax.broadcasted_iota(jnp.int32, tuple(local_shape), d) + offsets[d]
        m = (coord < width) | (coord >= global_shape[d] - width)
        mask = m if mask is None else mask | m
    return mask


def make_step(
    stencil: Stencil,
    global_shape: Sequence[int],
    periodic: bool = False,
    compute_fn=None,
):
    """Single-device step function: pad -> update -> re-pin frame.

    Guard-frame mode (default): padding uses the stencil's guard-cell
    constants, so cells just inside the frame see the same neighborhood values
    they would in the reference's full-grid-with-frame layout; the frame itself
    is then restored from the old state (it never changes, making any BC value
    — including non-constant frames set by init — honored).

    Periodic mode: wrap padding, every cell updates, no frame.

    ``compute_fn`` overrides the local update (padded fields -> interior
    fields) — the hook through which Pallas kernels replace the jnp ops.
    """
    ndim = stencil.ndim
    zeros = (0,) * ndim
    if stencil.phases and compute_fn is not None:
        raise ValueError(
            f"{stencil.name} is multi-phase; compute_fn override unsupported")
    if stencil.parity_sensitive and periodic and \
            any(g % 2 for g in global_shape):
        raise ValueError(
            f"{stencil.name} is parity-sensitive: periodic wrap over odd "
            f"extents {tuple(global_shape)} makes the coloring inconsistent")
    update_fns = stencil.phases or (compute_fn or stencil.update,)

    # NOTE (measured, round 3): a "raw" variant that skips jnp.pad by using
    # the state as its own halo (frame cells ARE the guard cells) and
    # splicing the interior back with dynamic_update_slice is bit-identical
    # but ~13x SLOWER on TPU: the (n-2h)^3 intermediate is lane-misaligned
    # (254 -> 384-lane relayout) and the splice un-fuses into a full copy.
    # The pad -> update -> where chain below fuses to ~2 HBM passes at
    # 256^3; where XLA's fusion loses at larger grids the answer is the
    # Pallas whole-step kernel (ops/pallas/), not a different jnp layout.
    def one_pass(fields: Fields, update) -> Fields:
        padded = []
        for f, v, fh in zip(fields, stencil.bc_value, stencil.field_halos):
            if fh == 0:
                padded.append(f)
            elif periodic:
                padded.append(jnp.pad(f, fh, mode="wrap"))
            else:
                padded.append(
                    jnp.pad(f, fh, constant_values=jnp.asarray(v, f.dtype))
                )
        new = update(tuple(padded))
        mask = None
        out = []
        for i, nf in enumerate(new):
            j = stencil.carry_map[i]
            if j is not None:
                out.append(fields[j])  # verbatim carry: no compute, no copy
            elif periodic or not stencil.mask_fields[i]:
                out.append(nf)
            else:
                if mask is None:
                    mask = frame_mask(
                        fields[0].shape, global_shape, zeros, stencil.halo)
                out.append(jnp.where(mask, fields[i], nf))
        return tuple(out)

    def step(fields: Fields) -> Fields:
        # One time step = every phase in order, each with fresh padding
        # (single-phase stencils: exactly the old pad -> update -> re-pin).
        for upd in update_fns:
            fields = one_pass(fields, upd)
        return fields

    return step


def make_ensemble_step(step_fn):
    """Vectorize a step over a leading batch axis of independent simulations.

    The data-parallel analogue for stencil workloads (SURVEY.md §2.2): the
    reference has no batch dimension; here ``vmap`` runs N universes per
    device in one fused program (and composes with the sharded stepper for
    batch-of-sharded-grids).
    """
    return jax.vmap(step_fn)


def pipeline_hooks(step_fn):
    """``(seed, advance)`` normalizing slab-carry pipelined steppers.

    A pipelined sharded stepper (``stepper.make_sharded_fused_step
    (pipeline=True)``) exposes ``_pipeline_prologue(fields) -> slabs``
    and ``_pipeline_body(fields, slabs) -> (fields, slabs)``: the
    exchanged halo slabs ride the scan carry so each pass's exchange is
    issued one full interior pass ahead of its consumer.  For plain
    steppers the extra carry is an empty tuple, so every runner below
    threads the same ``(fields, extra)`` shape regardless.
    """
    if getattr(step_fn, "_pipeline_active", False):
        return step_fn._pipeline_prologue, step_fn._pipeline_body

    def seed(fields):
        return ()

    def advance(fields, extra):
        return step_fn(fields), ()

    return seed, advance


def make_runner(step_fn, n_steps: int, jit: bool = True):
    """Wrap ``step_fn`` in a jitted ``lax.scan`` over ``n_steps``.

    Donation of the carry means the two time levels reuse the same buffers —
    the free equivalent of the reference's (intended) d_univ/d_new_univ swap.

    Slab-carry pipelined steppers (``pipeline_hooks``) are threaded
    through the scan carry: one prologue exchange seeds the slabs before
    the scan, each body pass consumes them and emits the next pass's,
    and the final pass's in-flight slabs are dropped (the epilogue).

    A step whose carry rotates its buffers (``step_fn._carry_period`` =
    p: the in-place leapfrog pass puts each buffer back in its slot
    every p steps) advances p steps per scan iteration, so the body ends
    with every buffer where it began and XLA copies none back into the
    carry; steps past the last multiple of p run after the scan.

    A step that writes out of place (``step_fn._out_of_place``: the
    pad-free fused pass reads overlapping windows of its input while it
    writes its output) cannot write the donated buffer it reads, so XLA
    would copy the whole state into a temporary before every pass.  Its
    runner takes p passes per iteration only when ``n_steps`` is a
    multiple of p: the carry then ping-pongs between the donated buffer
    and one temporary.  A lone pass is not donated (its output beside
    the input: no copy, the same peak).  Any other count runs one pass
    per iteration, a copy each, since pairing would need a second
    temporary.
    """
    # Fault point (resilience/faults.py): the scan is about to be built
    # and jitted — the host-side stand-in for "the compile hung" (fires
    # once per process; every runner-building entry point shares it, so
    # a measurement-campaign label can be wedged here deterministically).
    faults.maybe_fire("compile")
    seed, advance = pipeline_hooks(step_fn)
    period = getattr(step_fn, "_carry_period", 1)
    donate = True
    if getattr(step_fn, "_out_of_place", False):
        donate = n_steps != 1
        if n_steps % period:
            period = 1

    def run(fields: Fields) -> Fields:
        def body(carry, _):
            for _ in range(period):
                carry = advance(*carry)
            return carry, None

        carry, _ = lax.scan(body, (fields, seed(fields)), None,
                            length=n_steps // period)
        for _ in range(n_steps % period):
            carry = advance(*carry)
        return carry[0]

    if jit:
        run = jax.jit(run, donate_argnums=0 if donate else ())
    return run


def make_checked_runner(step_fn, n_steps: int, start_step: int = 0,
                        use_checkify: bool = True):
    """Debug-mode runner (SURVEY.md §5.2's sanitizer): every step checked.

    The reference has no sanitizers at all — and contains real races and OOB
    reads (kernel.cu:224 unsynced D2H, §3.4's unsigned-wrap indexing).  JAX
    makes those structurally impossible; the remaining numerical failure mode
    is a NaN/Inf blow-up, which ``--check-finite`` only polls at interval
    boundaries.  This runner instead checks EVERY step inside one jitted
    ``lax.scan`` and reports the exact step where the state first went
    non-finite.

    Two instrumentation strategies with identical error semantics:

    * ``use_checkify=True`` (unsharded/ensemble): ``jax.experimental.checkify``
      — a user check per inexact field whose message carries the absolute
      step index (checkify keeps the FIRST failure), plus index bounds
      checks on every gather/scatter.
    * ``use_checkify=False`` (sharded steps): checkify's error-state
      threading cannot currently cross ``shard_map`` inside ``lax.scan``
      (select shape mismatch between the scalar error state and per-device
      states), so first-failure tracking rides the scan carry as two scalars
      (step, field) instead — pure jnp, composes with any sharding; index
      checks are moot on this path (the sharded stepper does no dynamic
      indexing).

    Returns a runner; call it as ``runner(fields, abs_start_step)`` — raises
    ``checkify.JaxRuntimeError`` with the step-localized message on failure,
    else returns the final fields.  No donation: debug mode keeps the input
    state alive for inspection.
    """
    from jax.experimental import checkify

    seed, advance = pipeline_hooks(step_fn)

    if use_checkify:
        def body(carry, idx):
            new, extra = advance(*carry)
            for i, f in enumerate(new):
                if jnp.issubdtype(f.dtype, jnp.inexact):
                    checkify.check(
                        jnp.isfinite(f).all(),
                        "field %d non-finite after step {step} "
                        "(NaN/Inf blow-up — check stability parameters)" % i,
                        step=idx,
                    )
            return (new, extra), None

        def run(fields: Fields, start) -> Fields:
            (out, _extra), _ = lax.scan(
                body, (fields, seed(fields)),
                start + jnp.arange(n_steps, dtype=jnp.int32))
            return out

        checked = jax.jit(checkify.checkify(
            run, errors=checkify.user_checks | checkify.index_checks))

        def runner(fields: Fields, start=None) -> Fields:
            if start is None:
                start = start_step
            err, out = checked(fields, jnp.asarray(start, jnp.int32))
            err.throw()
            return out

        return runner

    def body(carry, idx):
        fields, extra, bad_step, bad_field = carry
        new, extra = advance(fields, extra)
        for i, f in enumerate(new):
            if not jnp.issubdtype(f.dtype, jnp.inexact):
                continue
            newly = (bad_step < 0) & ~jnp.isfinite(f).all()
            bad_field = jnp.where(newly, i, bad_field)
            bad_step = jnp.where(newly, idx, bad_step)
        return (new, extra, bad_step, bad_field), None

    def run(fields: Fields, start):
        init = (fields, seed(fields),
                jnp.asarray(-1, jnp.int32), jnp.asarray(-1, jnp.int32))
        (out, _extra, bad_step, bad_field), _ = lax.scan(
            body, init, start + jnp.arange(n_steps, dtype=jnp.int32))
        return out, bad_step, bad_field

    jitted = jax.jit(run)

    def runner(fields: Fields, start=None) -> Fields:
        if start is None:
            start = start_step
        out, bad_step, bad_field = jitted(
            fields, jnp.asarray(start, jnp.int32))
        step = int(bad_step)
        if step >= 0:
            raise checkify.JaxRuntimeError(
                f"field {int(bad_field)} non-finite after step {step} "
                "(NaN/Inf blow-up — check stability parameters)")
        return out

    return runner


def run_until(
    step_fn,
    fields: Fields,
    tol: float,
    max_steps: int,
    check_every: int = 1,
    jit: bool = True,
):
    """Run until the residual drops below ``tol`` (or ``max_steps``).

    Solver-style termination the reference cannot express (its iteration
    count is fixed up front via scanf, kernel.cu:152): a ``lax.while_loop``
    whose predicate is data-dependent — the compiler-friendly TPU form of
    "iterate until converged".  The residual is ``max_f max|f_new - f_old|``
    measured across a ``check_every``-step chunk (chunking amortizes the
    extra reduction pass).  Works on sharded fields too: the max-reduction
    over a sharded array makes XLA insert the global collective.

    Returns ``(fields, steps_done, residual)``.

    Slab-carry pipelined steppers thread their carried slabs through
    BOTH loops (fori chunk and while carry), so the pipeline stays
    primed across residual checks — one prologue exchange per run, not
    per chunk.
    """
    if check_every < 1:
        raise ValueError("check_every must be >= 1")

    seed, advance = pipeline_hooks(step_fn)

    def cond(carry):
        _, _, n, res = carry
        return (res > tol) & (n < max_steps)

    def body(carry):
        fs, extra, n, _ = carry
        # clamp the last chunk so max_steps is a hard cap even when it is
        # not a multiple of check_every
        this_chunk = jnp.minimum(check_every, max_steps - n)
        new, extra = lax.fori_loop(
            0, this_chunk, lambda _, c: advance(*c), (fs, extra))
        res = jnp.asarray(0.0, jnp.float32)
        for a, b in zip(new, fs):
            d = jnp.max(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32)))
            res = jnp.maximum(res, d)
        return new, extra, n + this_chunk, res

    def run(fs):
        init = (fs, seed(fs), jnp.asarray(0, jnp.int32),
                jnp.asarray(jnp.inf, jnp.float32))
        out, _extra, n, res = lax.while_loop(cond, body, init)
        return out, n, res

    if jit:
        run = jax.jit(run, donate_argnums=0)
    out, n, res = run(fields)
    return out, int(n), float(res)


def run_simulation(
    stencil: Stencil,
    fields: Fields,
    n_steps: int,
    step_fn=None,
    log_every: int = 0,
    callback=None,
    start_step: int = 0,
    runner_factory=None,
    observer=None,
    migrator=None,
) -> Fields:
    """Run ``n_steps``, optionally surfacing state every ``log_every`` steps.

    With ``log_every == 0`` the whole run is one jitted scan (fastest).  With
    logging, the run is chunked so ``callback(steps_done, fields)`` sees
    materialized state at interval boundaries — the working replacement for
    the reference's commented-out per-iteration debug prints (kernel.cu:232,
    265).  Chunk boundaries align to *absolute* multiples of ``log_every``
    (``start_step`` is where this run resumes from), so a run resumed from a
    non-multiple step keeps logging/checkpointing on the same cadence.

    A callback may RETURN a replacement fields tuple (same structure,
    shapes, dtypes) and the run carries it forward — the deterministic
    state-corruption hook the ``numerics`` fault site uses
    (``resilience/faults.py``: a NaN poisoned at a chunk boundary must
    corrupt the state that CONTINUES, like a real bit flip would).
    ``None`` — the normal case — keeps the state; the jitted step
    program is untouched either way (the swap is host-side, between
    chunks).

    ``runner_factory(step_fn, n)`` overrides how a chunk is executed; the
    returned runner is called as ``runner(fields, abs_start_step)`` (the
    hook through which :func:`make_checked_runner` instruments debug runs —
    the absolute step makes its error messages name the true failing step
    across chunks and resumes).

    ``migrator(steps_done, fields)`` is the elastic-execution adoption
    seam (``--auto-policy --policy-recheck``): called after the
    callback at every chunk boundary, it may return a replacement
    ``(step_fn, fields)`` pair — typically the same state live-
    resharded onto a different mesh (``parallel/reshard.py``) plus the
    step program built for it.  On a swap the compiled chunk runners
    are dropped (they close over the old step_fn) and rebuilt lazily;
    with ``--compile-cache`` a shape the machine has seen before skips
    the real XLA work.  ``None`` continues unchanged.

    Each chunk runs inside ``obs/spans.region("sim.chunk",
    step_num=<its first absolute step>)`` (a ``StepTraceAnnotation``
    in a ``--profile-dir`` trace), its runner call inside
    ``sim.runner`` and the callback inside ``sim.observe``: host-side
    names on the profiler's clock, nothing inside the jitted program.

    ``observer`` (telemetry, ``obs/runtime.py``) receives
    ``begin_chunk()`` / ``record_chunk(steps, seconds)`` around each
    chunk, the wall time measured with a ``block_until_ready`` fence.
    Strictly a chunk-boundary hook: the jitted step/scan is byte-
    identical with and without an observer (pinned by jaxpr inspection
    in tests/test_obs.py), so the hot path pays nothing.  An observer
    alone (no callback) still gets chunked execution when ``log_every``
    is set — the hook a chunk-scoped profiler (``obs/profile.py``)
    needs to see a steady-state chunk boundary without any logging
    side-channel.
    """
    if step_fn is None:
        step_fn = make_step(stencil, fields[0].shape)
    if runner_factory is None:
        def runner_factory(fn, n):
            r = make_runner(fn, n)
            return lambda fs, start=0: r(fs)

    def _run_chunk(runner, fs, n, abs_step):
        if observer is None:
            with region("sim.runner"):
                return runner(fs, abs_step)
        observer.begin_chunk()
        t0 = time.perf_counter()
        with region("sim.runner"):
            out = jax.block_until_ready(runner(fs, abs_step))
        observer.record_chunk(n, time.perf_counter() - t0)
        return out

    if not log_every or (callback is None and observer is None
                         and migrator is None):
        with region("sim.chunk", step_num=start_step):
            return _run_chunk(runner_factory(step_fn, n_steps), fields,
                              n_steps, start_step)

    done = 0
    runners = {}
    while done < n_steps:
        abs_step = start_step + done
        boundary = (abs_step // log_every + 1) * log_every
        chunk = min(boundary - abs_step, n_steps - done)
        if chunk not in runners:
            runners[chunk] = runner_factory(step_fn, chunk)
        with region("sim.chunk", step_num=abs_step):
            fields = _run_chunk(runners[chunk], fields, chunk, abs_step)
            done += chunk
            if callback is not None:
                with region("sim.observe"):
                    replacement = callback(done, fields)
                if replacement is not None:
                    fields = replacement
        if migrator is not None and done < n_steps:
            swap = migrator(done, fields)
            if swap is not None:
                step_fn, fields = swap
                runners.clear()  # compiled over the old step_fn
    return fields
