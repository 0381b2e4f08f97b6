"""Jaxpr-structure regression checks for the pipelined/overlapped steps.

The overlap and pipeline steppers make PROMISES about dependency
structure, not values: the interior kernel must be schedulable
concurrently with the halo exchange.  Values regress loudly (equivalence
tests) but structure regresses silently — an innocent refactor that
routes a slab through the spliced output would keep every number
bit-identical while serializing the exchange back onto the critical
path.  This module is the single reusable implementation of the
structural assertions (grown from the inline pattern of
tests/test_overlap_fused.py): used by the test suite AND invoked from
``scripts/tier1.sh`` via ``scripts/check_pipeline_structure.py``, so the
gate a builder actually runs checks the dependency claims too.

Checked properties of a pipelined body ``(fields, slabs) -> (fields,
slabs)``:

1. **Exactly one exchange round per scan iteration** — the body's
   ``ppermute`` count equals the non-pipelined step's (the carry moves
   the exchange, it must not duplicate or drop transfers).
2. **Two-sided independence** (with ``overlap=True``): the interior
   ``pallas_call`` is unreachable from any ``ppermute`` output
   (interior(i) does not consume the exchange feeding pass i+1), and no
   ``ppermute`` is reachable from the interior's outputs (the exchange
   feeding pass i+1 does not consume interior(i)).  Both directions are
   required for XLA to schedule the transfer across the whole interior
   pass.

The ``exchange="rdma"`` steps add a THIRD structural promise — the
whole point of the in-kernel remote-DMA mode: **zero XLA collective-
permute equations anywhere in the step** (:func:`count_remote_dma` /
:func:`assert_rdma_step_structure`).  A compiled rdma step carries its
exchange as remote ``dma_start`` equations inside the collective
pallas_calls (and nothing else — no ``all_gather`` either); the
interpret-mode step carries the documented ``all_gather`` ring-shift
emulation (``ops/pallas/remote.py``), still with zero ``ppermute``.
The independence checks generalize: for rdma bodies the "exchange
equations" are the all_gathers (interpret) / the remote-DMA
pallas_calls (compiled) instead of the ppermutes.
"""

from __future__ import annotations

from typing import Dict, Sequence, Tuple

import jax
import jax.extend.core as jex_core


def iter_jaxprs(jaxpr):
    """Yield ``jaxpr`` and every sub-jaxpr reachable through eqn params."""
    yield jaxpr
    for eqn in jaxpr.eqns:
        for v in eqn.params.values():
            vals = v if isinstance(v, (list, tuple)) else (v,)
            for u in vals:
                if isinstance(u, jex_core.ClosedJaxpr):
                    yield from iter_jaxprs(u.jaxpr)
                elif isinstance(u, jex_core.Jaxpr):
                    yield from iter_jaxprs(u)


def count_primitive(closed, name: str) -> int:
    """Occurrences of primitive ``name`` across all nested jaxprs."""
    return sum(
        1
        for jx in iter_jaxprs(closed.jaxpr)
        for eqn in jx.eqns
        if eqn.primitive.name == name
    )


def _is_remote_dma(eqn) -> bool:
    """Is this ``dma_start`` a REMOTE copy (carries a device-id operand)?

    Local ``make_async_copy`` binds ``device_id=None`` (its
    ``device_id_type`` param defaults to MESH); the remote ops of
    ``ops/pallas/remote.py`` bind a real device id under LOGICAL.  The
    tree-unflatten is the ground truth; the type check is the fallback
    if the param tree layout ever drifts.
    """
    if eqn.primitive.name != "dma_start":
        return False
    try:
        from jax import tree_util

        flat = tree_util.tree_unflatten(eqn.params["tree"], eqn.invars)
        return flat[-1] is not None  # trailing leaf group = device_id
    except Exception:  # noqa: BLE001 — fall back to the type marker
        dtype = eqn.params.get("device_id_type")
        return dtype is not None and "LOGICAL" in str(dtype).upper()


def count_remote_dma(closed) -> int:
    """Remote ``dma_start`` equations across all nested jaxprs —
    including the kernel jaxprs inside every ``pallas_call`` (the
    in-kernel exchange is exactly what lives there)."""
    return sum(
        1
        for jx in iter_jaxprs(closed.jaxpr)
        for eqn in jx.eqns
        if _is_remote_dma(eqn)
    )


def _eqn_contains_remote_dma(eqn) -> bool:
    """Does this eqn (a pallas_call, scan, ...) nest a remote dma_start?"""
    for v in eqn.params.values():
        vals = v if isinstance(v, (list, tuple)) else (v,)
        for u in vals:
            jx = None
            if isinstance(u, jex_core.ClosedJaxpr):
                jx = u.jaxpr
            elif isinstance(u, jex_core.Jaxpr):
                jx = u
            if jx is None:
                continue
            for sub in iter_jaxprs(jx):
                for e in sub.eqns:
                    if _is_remote_dma(e) or _eqn_contains_remote_dma(e):
                        return True
    return False


def assert_rdma_step_structure(closed, compiled: bool) -> Dict[str, int]:
    """The rdma mode's headline gate: ZERO XLA collective-permute eqns.

    ``compiled=True`` (the step was built with ``interpret=False``)
    additionally pins the strong form: zero ``all_gather`` too (the
    exchange must live entirely inside the collective kernels) and at
    least one remote ``dma_start`` (a step with no exchange at all
    would pass the zero-collective check vacuously).  Interpret-mode
    steps carry the documented ``all_gather`` ring-shift emulation, so
    only the ppermute count is pinned there (plus that SOME emulated
    exchange exists).  Returns the counts for the caller's report.
    """
    n_pp = count_primitive(closed, "ppermute")
    n_ag = count_primitive(closed, "all_gather")
    n_rdma = count_remote_dma(closed)
    assert n_pp == 0, (
        f"rdma step contains {n_pp} XLA ppermute eqn(s) — the in-kernel "
        "remote-DMA exchange must replace every collective-permute")
    if compiled:
        assert n_ag == 0, (
            f"compiled rdma step contains {n_ag} all_gather eqn(s) — "
            "the exchange must live inside the collective kernels, not "
            "in an XLA collective")
        assert n_rdma > 0, (
            "compiled rdma step contains no remote dma_start — the step "
            "did not exchange at all")
    else:
        assert n_ag > 0, (
            "interpret rdma step contains no all_gather ring shift — "
            "the step did not exchange at all")
    return {"n_ppermute": n_pp, "n_all_gather": n_ag,
            "n_remote_dma": n_rdma}


def _exchange_eqns(jx, exchange: str):
    """The equations that ARE the halo exchange in this (sub-)jaxpr:
    ppermutes (default), or — for rdma — all_gathers (the interpret
    emulation) plus pallas_calls nesting a remote dma_start (the
    compiled collective kernels)."""
    if exchange != "rdma":
        return [e for e in jx.eqns if e.primitive.name == "ppermute"]
    out = [e for e in jx.eqns if e.primitive.name == "all_gather"]
    out += [e for e in jx.eqns
            if e.primitive.name == "pallas_call"
            and _eqn_contains_remote_dma(e)]
    return out


def _producer_map(jx):
    producer = {}
    for eqn in jx.eqns:
        for ov in eqn.outvars:
            producer[ov] = eqn
    return producer


def _ancestor_eqns(jx, seeds):
    """All eqns transitively producing the inputs of ``seeds`` (seeds
    included)."""
    producer = _producer_map(jx)
    seen, stack = set(), list(seeds)
    out = []
    while stack:
        eqn = stack.pop()
        if id(eqn) in seen:
            continue
        seen.add(id(eqn))
        out.append(eqn)
        for iv in eqn.invars:
            if isinstance(iv, jex_core.Literal):
                continue
            p = producer.get(iv)
            if p is not None:
                stack.append(p)
    return out


def interior_exchange_independence(
    closed, local_shape: Sequence[int], exchange: str = "ppermute"
) -> Dict[str, object]:
    """Two-sided reachability report between the interior ``pallas_call``
    (the one producing full ``local_shape`` outputs) and every exchange
    equation (``ppermute`` by default; the all_gather / remote-DMA
    collective calls for ``exchange="rdma"``), inside the (sub-)jaxpr
    that holds the exchange.

    Returns ``{"n_ppermute", "interior_depends_on_exchange",
    "exchange_depends_on_interior"}`` (the count key keeps its name for
    schema stability — for rdma it counts the exchange eqns); raises
    ``AssertionError`` when no exchange or no interior pallas_call
    exists anywhere (a structural check against the wrong function is
    meaningless).
    """
    local_shape = tuple(int(s) for s in local_shape)
    for jx in iter_jaxprs(closed.jaxpr):
        perms = _exchange_eqns(jx, exchange)
        if not perms:
            continue
        perm_ids = {id(e) for e in perms}
        interior = [
            e for e in jx.eqns
            if e.primitive.name == "pallas_call"
            and id(e) not in perm_ids
            and any(tuple(ov.aval.shape) == local_shape
                    for ov in e.outvars)
        ]
        assert interior, (
            "no interior pallas_call (full local-shape outputs "
            f"{local_shape}) in the jaxpr holding the exchange")
        perm_anc = _ancestor_eqns(jx, perms)
        int_anc = _ancestor_eqns(jx, interior)
        interior_ids = {id(e) for e in interior}
        return {
            "n_ppermute": len(perms),
            # any exchange eqn in the interior's producer chain?
            "interior_depends_on_exchange": any(
                id(e) in perm_ids for e in int_anc),
            # any interior call in an exchange eqn's producer chain?
            "exchange_depends_on_interior": any(
                id(e) in interior_ids for e in perm_anc),
        }
    raise AssertionError("no exchange anywhere — the step did not "
                         "exchange at all")


def assert_pipeline_body_structure(
    pipelined_step,
    plain_step,
    fields,
    local_shape: Sequence[int],
    overlap: bool,
    exchange: str = "ppermute",
) -> Dict[str, object]:
    """Assert the pipelined body's structural contract; return the report.

    ``pipelined_step`` must carry the ``_pipeline_prologue`` /
    ``_pipeline_body`` hooks; ``plain_step`` is the same configuration
    with ``pipeline=False`` (its exchange-eqn count defines "one
    exchange round" — ppermutes by default, the all_gather / remote-DMA
    collective calls for ``exchange="rdma"``, where the body and the
    whole step are additionally pinned ppermute-free).  ``overlap``
    selects whether the two-sided independence is asserted (without the
    interior/shell split there is no separate interior kernel to be
    independent OF).
    """
    prologue = pipelined_step._pipeline_prologue
    body = pipelined_step._pipeline_body
    slabs = jax.eval_shape(prologue, fields)
    closed_body = jax.make_jaxpr(body)(fields, slabs)
    closed_plain = jax.make_jaxpr(plain_step)(fields)

    def _count(closed):
        if exchange != "rdma":
            return count_primitive(closed, "ppermute")
        return sum(len(_exchange_eqns(jx, exchange))
                   for jx in iter_jaxprs(closed.jaxpr))

    n_body = _count(closed_body)
    n_plain = _count(closed_plain)
    assert n_body == n_plain > 0, (
        f"pipelined body issues {n_body} exchange round(s) per "
        f"iteration, the non-pipelined step {n_plain} — the slab carry "
        "must move the exchange, not duplicate or drop transfers")
    if exchange == "rdma":
        for closed in (closed_body, closed_plain):
            assert count_primitive(closed, "ppermute") == 0, (
                "rdma pipelined structure check found an XLA ppermute "
                "— the in-kernel exchange must replace every "
                "collective-permute")

    report: Dict[str, object] = {"n_ppermute": n_body}
    if overlap:
        rep = interior_exchange_independence(closed_body, local_shape,
                                             exchange=exchange)
        assert not rep["interior_depends_on_exchange"], (
            "interior(i) consumes an exchange output — the carried "
            "slabs must be the only exchanged data a pass reads")
        assert not rep["exchange_depends_on_interior"], (
            "the exchange feeding pass i+1 consumes interior(i) — next "
            "slabs must be read from the SHELL outputs, not the spliced "
            "array")
        report.update(rep)
    return report


def count_exchange_rounds(closed, exchange: str = "ppermute") -> int:
    """Exchange equations across all nested jaxprs: ppermutes by
    default, or (for rdma) the all_gather ring shifts plus the
    collective pallas_calls nesting a remote dma_start."""
    return sum(len(_exchange_eqns(jx, exchange))
               for jx in iter_jaxprs(closed.jaxpr))


def assert_ensemble_exchange_invariance(
    batched_closed,
    single_closed,
    exchange: str = "ppermute",
) -> Dict[str, int]:
    """The batched ensemble engine's headline structural pin: the
    exchange-round count of the N-member batched step EQUALS the
    unbatched step's — independent of N.

    vmap's collective batching rule folds the member axis INTO each
    ppermute operand (one collective per site, a bigger payload) rather
    than unrolling one collective per member; an innocent refactor that
    mapped the exchange per member would keep every value bit-identical
    while multiplying the per-pass fixed cost by N — exactly the cost
    the ensemble engine exists to amortize.  Also pins that the batched
    step gained at least one batched ``pallas_call``-or-update over
    nothing (a vacuous check against an empty program must fail).
    """
    n_batched = count_exchange_rounds(batched_closed, exchange)
    n_single = count_exchange_rounds(single_closed, exchange)
    assert n_single > 0, (
        "the unbatched step contains no exchange — an invariance check "
        "against an exchange-free program is meaningless")
    assert n_batched == n_single, (
        f"batched step issues {n_batched} exchange round(s), the "
        f"unbatched step {n_single} — the member axis must ride INSIDE "
        "each collective operand, never unroll into per-member "
        "exchanges")
    if exchange == "rdma":
        assert count_primitive(batched_closed, "ppermute") == 0, (
            "batched rdma step contains an XLA ppermute — the in-kernel "
            "exchange must replace every collective-permute at any N")
    return {"n_exchange_batched": n_batched,
            "n_exchange_single": n_single}


def check_ensemble_structure(
    stencil_name: str = "heat3d",
    grid: Tuple[int, int, int] = (32, 16, 128),
    mesh_shape: Tuple[int, int, int] = (2, 1, 1),
    k: int = 4,
    ensemble: int = 4,
    kind=None,
    padfree=True,
    exchange: str = "ppermute",
) -> Dict[str, object]:
    """Build the batched and unbatched sharded fused steps and assert
    exchange-round invariance in N — the entry point
    ``scripts/check_pipeline_structure.py --ensemble`` (and hence
    ``scripts/tier1.sh``) drives.  Trace-only: nothing executes.
    """
    from .. import make_mesh, make_stencil
    from ..parallel.stepper import make_sharded_fused_step

    if exchange == "rdma":
        kind, padfree = "stream", None
    st = make_stencil(stencil_name)
    mesh = make_mesh(mesh_shape)
    mk = lambda ens: make_sharded_fused_step(  # noqa: E731
        st, mesh, grid, k, interpret=True, kind=kind, padfree=padfree,
        exchange=exchange, ensemble=ens)
    batched, single = mk(ensemble), mk(0)
    assert batched is not None and single is not None, (
        stencil_name, grid, mesh_shape)
    assert getattr(batched, "_ensemble", 0) == ensemble
    single_fields = tuple(
        jax.ShapeDtypeStruct(tuple(grid), st.dtype)
        for _ in range(st.num_fields))
    batched_fields = tuple(
        jax.ShapeDtypeStruct((ensemble, *grid), st.dtype)
        for _ in range(st.num_fields))
    report = assert_ensemble_exchange_invariance(
        jax.make_jaxpr(batched)(batched_fields),
        jax.make_jaxpr(single)(single_fields),
        exchange=exchange)
    report["ensemble"] = ensemble
    return report


def _shard_map_body_jaxprs(closed):
    """The per-device body jaxpr of every ``shard_map`` eqn (any depth).

    Avals inside these ARE local (per-shard) shapes — the place a
    full-grid materialization would show up as an oversized aval.
    """
    for jx in iter_jaxprs(closed.jaxpr):
        for eqn in jx.eqns:
            if eqn.primitive.name != "shard_map":
                continue
            body = eqn.params.get("jaxpr")
            if isinstance(body, jex_core.ClosedJaxpr):
                yield body.jaxpr
            elif isinstance(body, jex_core.Jaxpr):
                yield body


def assert_reshard_structure(closed, plan, n_fields: int):
    """The live-migration headline gate (``parallel/reshard.py``): the
    traced relayout moves state device-to-device ONLY.

    Pins three promises:

    1. **Zero ``all_gather``** anywhere — no collective replicates the
       grid.
    2. **Exact ppermute count**: ``plan.n_comm_rounds`` collective
       rounds per field, no more (a round per matching) and no fewer (no
       silent fallback through an XLA resharding).
    3. **No full-grid local intermediate**: inside every ``shard_map``
       body (where avals are per-device shapes), every aval is strictly
       smaller than the global array — no device ever holds the whole
       state.

    Returns the counts for the caller's report.
    """
    n_ag = count_primitive(closed, "all_gather")
    assert n_ag == 0, (
        f"reshard jaxpr contains {n_ag} all_gather eqn(s) — the "
        "relayout must never replicate the grid")
    n_pp = count_primitive(closed, "ppermute")
    expected = plan.n_comm_rounds * n_fields
    assert n_pp == expected, (
        f"reshard jaxpr contains {n_pp} ppermute eqn(s), the plan "
        f"schedules {expected} ({plan.n_comm_rounds} non-identity "
        f"round(s) x {n_fields} field(s))")
    global_size = 1
    for s in plan.array_shape:
        global_size *= int(s)
    max_local = 0
    for body in _shard_map_body_jaxprs(closed):
        for jx in iter_jaxprs(body):
            for eqn in jx.eqns:
                for v in list(eqn.invars) + list(eqn.outvars):
                    aval = getattr(v, "aval", None)
                    if aval is None or not hasattr(aval, "shape"):
                        continue
                    sz = 1
                    for d in aval.shape:
                        sz *= int(d)
                    max_local = max(max_local, sz)
                    assert sz < global_size, (
                        f"reshard shard_map body holds an aval of "
                        f"{tuple(aval.shape)} ({sz} elems) >= the global "
                        f"array ({global_size} elems) — a device "
                        "materialized the full grid")
    assert max_local > 0, "reshard jaxpr has no shard_map body at all"
    return {"n_ppermute": n_pp, "n_all_gather": n_ag,
            "max_local_aval": max_local, "global_size": global_size}


def assert_member_repack_structure(closed, plan, n_fields: int,
                                   grid_shape: Tuple[int, ...] = ()):
    """The serving defrag gate (``parallel/reshard.py`` member repack):
    re-packing occupied member slots moves state device-to-device ONLY.

    Same discipline as :func:`assert_reshard_structure`, adapted to the
    member axis:

    1. **Zero ``all_gather``** — defrag never replicates the member
       axis (or the grid).
    2. **Exact ppermute count**: ``plan.n_comm_rounds`` collective
       rounds per field.  A plan whose member axis is not device-sharded
       schedules ZERO — the local-indexing degradation is pinned too.
    3. **No full-member-axis intermediate**: when the plan runs under a
       multi-device mesh, every ``shard_map`` body aval is strictly
       smaller than the larger of the two global arrays — no device
       materializes a full (members x grid) state.
    """
    n_ag = count_primitive(closed, "all_gather")
    assert n_ag == 0, (
        f"member-repack jaxpr contains {n_ag} all_gather eqn(s) — "
        "defrag must never replicate state")
    n_pp = count_primitive(closed, "ppermute")
    expected = plan.n_comm_rounds * n_fields
    assert n_pp == expected, (
        f"member-repack jaxpr contains {n_pp} ppermute eqn(s), the "
        f"plan schedules {expected} ({plan.n_comm_rounds} non-identity "
        f"round(s) x {n_fields} field(s))")
    cells = 1
    for s in grid_shape:
        cells *= int(s)
    max_global = max(plan.n_src, plan.n_dst) * cells
    max_local = 0
    if plan.mesh is not None and plan.mesh.devices.size > 1:
        for body in _shard_map_body_jaxprs(closed):
            for jx in iter_jaxprs(body):
                for eqn in jx.eqns:
                    for v in list(eqn.invars) + list(eqn.outvars):
                        aval = getattr(v, "aval", None)
                        if aval is None or not hasattr(aval, "shape"):
                            continue
                        sz = 1
                        for d in aval.shape:
                            sz *= int(d)
                        max_local = max(max_local, sz)
                        assert sz < max_global, (
                            f"member-repack shard_map body holds an "
                            f"aval of {tuple(aval.shape)} ({sz} elems) "
                            f">= the global array ({max_global} elems)")
        assert max_local > 0, \
            "member-repack jaxpr has no shard_map body at all"
    return {"n_ppermute": n_pp, "n_all_gather": n_ag,
            "max_local_aval": max_local, "global_size": max_global}


_COLLECTIVES = ("ppermute", "all_gather", "psum", "all_to_all",
                "all_reduce")


def assert_coupled_structure(step_jaxprs, transfer_jaxprs,
                             sharded_groups: Sequence[int]):
    """The MPMD coupling gate (``parallel/groups.py``): interface faces
    are the ONLY cross-group communication.

    Pins three promises:

    1. **No group step replicates or reduces across anything**: zero
       ``all_gather``/``all_to_all`` in every per-group step jaxpr.
       ``ppermute`` (the intra-group halo exchange) is permitted ONLY
       in groups listed in ``sharded_groups`` — a single-shard group's
       step must be collective-free, so the coupling cannot smuggle a
       degenerate collective in through an unsharded group.
    2. **Intra-group exchange stays intra-group by construction**: a
       sharded group's step must actually carry its ppermutes (a
       sharded group with none didn't exchange at all) — and since
       each group's mesh holds ONLY its own devices, those ppermutes
       cannot name a cross-group peer.
    3. **The interface transfers carry ZERO collectives** of any kind:
       the band moves as slice -> resample -> cast on the sender plus
       a host ``device_put`` — no collective CAN span two groups
       (their meshes are disjoint), and this pins that none pretends
       to.

    Returns the per-group/per-transfer counts for the caller's report.
    """
    sharded = set(int(i) for i in sharded_groups)
    group_pp = []
    for g, closed in enumerate(step_jaxprs):
        for prim in ("all_gather", "all_to_all"):
            n = count_primitive(closed, prim)
            assert n == 0, (
                f"coupled group {g} step contains {n} {prim} eqn(s) — "
                "a group step must never replicate state")
        n_pp = count_primitive(closed, "ppermute")
        if g in sharded:
            assert n_pp > 0, (
                f"coupled group {g} is sharded but its step carries no "
                "ppermute — the group did not exchange its own halos")
        else:
            assert n_pp == 0, (
                f"coupled group {g} is single-shard but its step "
                f"carries {n_pp} ppermute eqn(s) — an unsharded group "
                "step must be collective-free")
        group_pp.append(n_pp)
    transfer_counts = []
    for t, closed in enumerate(transfer_jaxprs):
        total = 0
        for prim in _COLLECTIVES:
            n = count_primitive(closed, prim)
            assert n == 0, (
                f"coupled interface transfer {t} contains {n} {prim} "
                "eqn(s) — interface bands move by device_put only; no "
                "collective may cross (or pretend to cross) groups")
            total += n
        transfer_counts.append(total)
    return {"group_ppermute": group_pp,
            "transfer_collectives": transfer_counts,
            "n_groups": len(group_pp), "n_transfers": len(transfer_counts)}


def check_coupled_structure(
    groups: str = "heat3d@0-3,heat3d@4-7",
    grid: Tuple[int, ...] = (30, 16, 16),
) -> Dict[str, object]:
    """Build a coupled runner on the current devices and run the full
    coupling assertion set — the tier-1 smoke's jaxpr gate.  Builds
    real (tiny) group states but never steps them."""
    from ..parallel import groups as groups_lib

    plans = groups_lib.plans_from_config(
        groups, grid, n_devices=len(jax.devices()))
    runner = groups_lib.CoupledRunner(plans)
    report = assert_coupled_structure(
        runner.step_jaxprs(), runner.transfer_jaxprs(),
        runner.sharded_group_indices())
    report["groups"] = [p.name for p in plans]
    return report


def assert_group_transport_structure(coll, n_interfaces: int = None
                                     ) -> Dict[str, object]:
    """The collective interface-transport gate (``parallel/groups.py``
    ``transport="collective"``): the coupled exchange is device-to-device
    ONLY, with the exact collective count.

    ``coll`` is ``CoupledRunner.collective_jaxprs()`` — the stage /
    transport / splice jaxprs of one exchange round.  Pins:

    1. **Zero host-mediated transfer anywhere**: no ``device_put`` eqn
       in any stage, the transport, or any splice — the only buffer
       moves between the group meshes and the union mesh are the
       zero-copy rewraps (which trace to nothing at all).
    2. **Exact ppermute count**: the transport jaxpr carries exactly
       ``2 * n_interfaces`` ppermutes — one per interface per
       direction, no more (no duplicated round) and no fewer (no
       silent fallback through an XLA resharding or a host hop).
    3. **Everything else is collective-free**: stages (slice only) and
       splices (shard-local resample + gated band write) carry zero
       collectives of any kind; the transport carries no collective
       BESIDES its ppermutes.

    Returns the counts for the caller's report.
    """
    if n_interfaces is None:
        n_interfaces = int(coll["n_interfaces"])
    all_jaxprs = (list(coll["stage"]) + [coll["transport"]]
                  + list(coll["splice"]))
    n_dput = sum(count_primitive(c, "device_put") for c in all_jaxprs)
    assert n_dput == 0, (
        f"collective group transport contains {n_dput} device_put "
        "eqn(s) — the coupled exchange must never take a host-mediated "
        "hop")
    for label, closed_list in (("stage", coll["stage"]),
                               ("splice", coll["splice"])):
        for t, closed in enumerate(closed_list):
            for prim in _COLLECTIVES:
                n = count_primitive(closed, prim)
                assert n == 0, (
                    f"collective transport {label} {t} contains {n} "
                    f"{prim} eqn(s) — only the transport shard_map may "
                    "communicate")
    n_pp = count_primitive(coll["transport"], "ppermute")
    expected = 2 * n_interfaces
    assert n_pp == expected, (
        f"collective transport jaxpr carries {n_pp} ppermute eqn(s), "
        f"expected exactly {expected} (one per interface per direction "
        f"across {n_interfaces} interface(s))")
    for prim in _COLLECTIVES:
        if prim == "ppermute":
            continue
        n = count_primitive(coll["transport"], prim)
        assert n == 0, (
            f"collective transport jaxpr contains {n} {prim} eqn(s) — "
            "the ppermutes must be its only collectives")
    return {"n_ppermute": n_pp, "n_device_put": n_dput,
            "n_interfaces": n_interfaces,
            "n_stages": len(coll["stage"]),
            "n_splices": len(coll["splice"])}


def check_group_transport_structure(
    groups: str = "heat3d@0-3,heat3d@4-7",
    grid: Tuple[int, ...] = (30, 16, 16),
) -> Dict[str, object]:
    """Build a collective-transport coupled runner on the current
    devices and run both the coupling and the transport gates — the
    tier-1 smoke's collective jaxpr gate.  Builds real (tiny) group
    states but never steps them."""
    from ..parallel import groups as groups_lib

    plans = groups_lib.plans_from_config(
        groups, grid, n_devices=len(jax.devices()))
    runner = groups_lib.CoupledRunner(plans, transport="collective")
    report = assert_coupled_structure(
        runner.step_jaxprs(), runner.transfer_jaxprs(),
        runner.sharded_group_indices())
    report.update(assert_group_transport_structure(
        runner.collective_jaxprs()))
    report["groups"] = [p.name for p in plans]
    report["transport"] = runner.transport
    return report


def check_pipeline_structure(
    stencil_name: str = "heat3d",
    grid: Tuple[int, int, int] = (32, 16, 128),
    mesh_shape: Tuple[int, int, int] = (2, 1, 1),
    k: int = 4,
    kind=None,
    padfree=True,
    exchange: str = "ppermute",
) -> Dict[str, object]:
    """Build a pipelined+overlapped step on the current devices and run
    the full assertion set — the entry point ``scripts/
    check_pipeline_structure.py`` (and hence ``scripts/tier1.sh``)
    drives.  Trace-only: nothing executes.

    ``exchange="rdma"`` forces the streaming kind (the only rdma host),
    runs the pipelined assertions against the rdma exchange eqns, and
    ADDITIONALLY pins the zero-ppermute gate on the whole step in BOTH
    build modes — interpret (what tier-1 executes) and compiled (what a
    TPU run traces to, remote dma_start and no XLA collective at all).
    """
    from .. import init_state, make_mesh, make_stencil, shard_fields
    from ..parallel.stepper import make_sharded_fused_step

    if exchange == "rdma":
        kind, padfree = "stream", None
    st = make_stencil(stencil_name)
    mesh = make_mesh(mesh_shape)
    mk = lambda pipe: make_sharded_fused_step(  # noqa: E731
        st, mesh, grid, k, interpret=True, kind=kind, padfree=padfree,
        overlap=True, pipeline=pipe, exchange=exchange)
    pipelined, plain = mk(True), mk(False)
    assert pipelined is not None and plain is not None, (
        stencil_name, grid, mesh_shape)
    assert getattr(pipelined, "_pipeline_active", False)
    assert getattr(pipelined, "_overlap_active", False), \
        "overlap geometry declined — pick a shape hosting the split"
    fields = shard_fields(init_state(st, grid, seed=3, kind="pulse"),
                          mesh, 3)
    local = tuple(g // c for g, c in
                  zip(grid, tuple(mesh_shape) + (1,) * 3))
    report = assert_pipeline_body_structure(
        pipelined, plain, fields, local, overlap=True, exchange=exchange)
    if exchange == "rdma":
        report["interpret"] = assert_rdma_step_structure(
            jax.make_jaxpr(plain)(fields), compiled=False)
        compiled = make_sharded_fused_step(
            st, mesh, grid, k, interpret=False, kind="stream",
            overlap=True, exchange="rdma")
        assert compiled is not None
        report["compiled"] = assert_rdma_step_structure(
            jax.make_jaxpr(compiled)(fields), compiled=True)
    return report
