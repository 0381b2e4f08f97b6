"""Rank candidate execution configs and resolve ``--auto-policy``.

This is the runtime promotion of ``benchmarks/policy_advice.py``: the
offline advisor reads a campaign results table and prints which plan
data-table edits the numbers support; :func:`resolve` reads the
campaign ledger's ``best_known`` table directly and *makes* the call
for one run, at launch or mid-flight.

The contract (ISSUE 15 / ROADMAP item 3):

- **Measured beats predicted, categorically.**  Every candidate whose
  exact label x backend (under the same exchange mode and ensemble
  size — :func:`obs.ledger.baseline_key`) has an ``ok`` row in
  ``best_known`` is ranked by that measured Mcells/s; quarantined rows
  are structurally excluded because ``best_known`` never sees them.
  Only when *no* candidate has a measured row does the costmodel
  roofline rank the field (``predicted_mcells_per_s_serial``, or the
  ``_overlapped`` figure for overlap candidates, whose whole point is
  hiding the exchange).
- **Explicit flags always win.**  A mode flag the user passed (any
  value differing from the RunConfig default) is locked: every
  candidate carries the user's value, and the decision records it in
  ``overrides``.  ``--auto-policy`` resolves only the *unset* mode
  flags.
- **Determinism.**  Ranking sorts on ``(-value, label)`` — two
  candidates with identical value can never flip between runs (the
  ledger side of the same guarantee is ``best_known``'s total
  tie-order).

Candidates are the full-machine decompositions of ``jax.device_count()``
devices: the unsharded baseline, every mesh factorization that divides
the grid with locals no thinner than the halo slab, ensemble-axis
repackings when ``--ensemble`` is set (member divisors of the device
count), and overlap/fused variants where legal.  ``--exchange rdma``
and ``--pipeline`` are never *proposed* (they are TPU fused-path
specials) but explicitly-passed values are respected and keyed.  Mode
combinations that host the streaming kernels additionally propose every
feasible KERNEL VARIANT from the autotuner registry
(``policy/autotune.py``): measured ``|var:<id>`` ledger rows — the
rows ``--autotune`` writes — rank against the default-constant rows
under the same categorical measured-beats-predicted rule.

Mid-flight rechecks (``--policy-recheck``) pass ``adoptable=True``:
``fuse`` is then additionally locked, because the fused step width is
the driver's step-accounting unit and cannot change under a running
chunk loop.  Everything else — mesh shape, ensemble packing, overlap,
kind — re-resolves, and the migration seam re-shards live
(``parallel/reshard.py``, no host gather).

``POLICY_INJECT=step=N:PATH`` is the test seam (same idiom as
``FAULT_INJECT``): at the first recheck at-or-after step N, the rows in
PATH are appended to the active ledger, so a tier-1 smoke can flip the
measured winner under a running simulation.

**Coupled runs (round 23).**  A ``--groups`` config resolves PER
GROUP: each clause whose mode tokens are unset is ranked against the
ledger's per-group rows (``obs/ledger._group_rows`` — label
``cli_grp_<op>``, baseline key carrying the clause signature
``|grp:<sig>`` and the interface transport ``|gtx:<transport>``) over
the ``parallel.groups.MODE_CANDIDATES`` mode combinations.  Strictly
measured-beats-default, with NO roofline fallback: a mode combination
is adopted only when this exact clause was actually measured under it
(an ``ok`` row), so an infeasible mode — one whose stepper builder
would decline this group's geometry — can never be picked, because it
could never have produced a measurement.  A clause that carries
explicit mode tokens is locked, exactly like an explicit flag.  The
decision records one entry per group (``group_decisions``) plus the
resolved canonical spec (``groups``), and ``perf_gate --policy-check``
replays both: the check trips when ANY single group's winner moves,
even though the run label does not change with mode tokens alone.
"""

from __future__ import annotations

import dataclasses
import logging
import math
import os
from typing import Any, Dict, FrozenSet, Iterable, List, Optional, Set, Tuple

import jax

from .. import plan
from ..config import RunConfig
from ..obs import costmodel
from ..obs import ledger as ledger_lib

log = logging.getLogger("mpi_cuda_process_tpu.policy")

#: The execution-mode fields ``--auto-policy`` may resolve.  Everything
#: else on RunConfig (grid, dtype, cadences, lifecycle) is the problem
#: statement, not the execution strategy.  ``kernel_variant`` (round 16,
#: policy/autotune.py) is the sub-mode dimension: the streaming/rdma
#: kernels' own swept constants, resolved exactly like mesh — measured
#: ``|var:<id>`` rows beat predictions, an explicit --kernel-variant is
#: locked.
MODE_FIELDS: Tuple[str, ...] = ("mesh", "ensemble_mesh", "fuse",
                                "fuse_kind", "overlap", "pipeline",
                                "exchange", "kernel_variant")

#: Mode fields a mid-flight recheck may change.  ``fuse`` is excluded:
#: it is the step-accounting unit (steps per runner call) fixed when
#: the chunk loop started.  ``kernel_variant`` is adoptable: it changes
#: only the compiled schedule (bit-exact by the autotuner contract),
#: never the step-accounting unit or the sharding.
ADOPTABLE_FIELDS: Tuple[str, ...] = ("mesh", "ensemble_mesh",
                                     "fuse_kind", "overlap", "pipeline",
                                     "exchange", "kernel_variant")


def _field_default(name: str) -> Any:
    f = {x.name: x for x in dataclasses.fields(RunConfig)}[name]
    if f.default is not dataclasses.MISSING:
        return f.default
    return f.default_factory()  # type: ignore[misc]


_MODE_DEFAULTS: Dict[str, Any] = {f: _field_default(f)
                                  for f in MODE_FIELDS}


def locked_fields(cfg: RunConfig) -> FrozenSet[str]:
    """Mode fields the user set explicitly (non-default).

    ``to_argv``'s round-trip guarantee makes "differs from the
    RunConfig default" exactly "was passed on the command line", so no
    parser plumbing is needed to know what must not be overridden.
    """
    return frozenset(f for f in MODE_FIELDS
                     if getattr(cfg, f) != _MODE_DEFAULTS[f])


# ------------------------------------------------------------ candidates

def _stencil_for(cfg: RunConfig):
    try:
        return plan.make_cfg_stencil(cfg)
    except Exception as e:  # unknown stencil/params: predictions degrade
        log.debug("policy: no stencil for %s: %s", cfg.stencil, e)
        return None


def _divisors(n: int) -> List[int]:
    return [d for d in range(1, n + 1) if n % d == 0]


def _mesh_shapes(n: int, ndim: int) -> List[Tuple[int, ...]]:
    """Every ndim-length factorization of n (ordered axes matter:
    (1, 8) is a y-slab decomposition, (8, 1) an x-slab one)."""
    if ndim <= 0:
        return []
    shapes: Set[Tuple[int, ...]] = set()

    def rec(prefix: List[int], rem: int) -> None:
        if len(prefix) == ndim - 1:
            shapes.add(tuple(prefix) + (rem,))
            return
        for d in _divisors(rem):
            rec(prefix + [d], rem // d)

    rec([], n)
    return sorted(shapes)


def _grid_ok(grid: Tuple[int, ...], shape: Tuple[int, ...],
             halo: int) -> bool:
    """Shardable: every axis divides and no local extent is thinner
    than the slab the neighbor exchange needs."""
    return all(g % c == 0 and (c == 1 or g // c >= max(2 * halo, 2))
               for g, c in zip(grid, shape))


def _apply(cfg: RunConfig, locked: FrozenSet[str],
           modes: Dict[str, Any]) -> RunConfig:
    """cfg with the candidate's mode fields, explicit flags held."""
    vals = {}
    for f in MODE_FIELDS:
        if f in locked:
            vals[f] = getattr(cfg, f)
        else:
            vals[f] = modes.get(f, _MODE_DEFAULTS[f])
    return dataclasses.replace(cfg, **vals)


def _valid(c: RunConfig, n_dev: int, backend: str) -> bool:
    spatial = math.prod(c.mesh) if c.mesh else 1
    em = c.ensemble_mesh or 1
    if spatial * em > n_dev:
        return False
    if c.ensemble_mesh and (not c.ensemble
                            or c.ensemble % c.ensemble_mesh):
        return False
    if c.mesh and any(g % m for g, m in zip(c.grid, c.mesh)):
        return False
    sharded = spatial > 1 or em > 1
    if c.overlap and spatial <= 1:
        return False
    if c.fuse:
        if c.compute == "jnp":
            return False
    else:
        if c.fuse_kind != "auto" or c.pipeline:
            return False
    if c.pipeline and not sharded:
        return False
    if c.exchange != "ppermute" and not (c.fuse and sharded
                                         and backend == "tpu"):
        return False
    if c.kernel_variant:
        # a variant candidate must be feasible for this exact (shape,
        # dtype, mesh, exchange) — the autotuner's validator is the
        # arbiter (sublane alignment, VMEM budget, family prereqs)
        from . import autotune as autotune_lib

        if autotune_lib.variant_for_config(c) is None:
            return False
    return True


def candidates(cfg: RunConfig, backend: str,
               locked: FrozenSet[str],
               st: Any = None,
               n_devices: Optional[int] = None) -> List[RunConfig]:
    """The candidate configs, requested-config first, deduplicated on
    mode values.  The requested config is always kept (build() is the
    arbiter of its validity); enumerated candidates must pass
    :func:`_valid` after the locked fields are overlaid."""
    n_dev = int(n_devices) if n_devices else jax.device_count()
    if cfg.groups:
        # a coupled --groups run's execution strategy IS the group
        # layout (the |grp:<sig> ledger identity): the monolithic mode
        # enumeration does not describe it, and no mode field here can
        # be adopted without changing which programs run where.  The
        # requested config is the only candidate — a measured row for
        # this exact split still ranks it (measured beats predicted),
        # the decision is recorded, and perf_gate --policy-check
        # replays it deterministically like any other.
        return [cfg]
    halo = int(getattr(st, "halo", 1) or 1) if st is not None else 1
    ndim = len(cfg.grid)
    modes_list: List[Dict[str, Any]] = [
        {f: getattr(cfg, f) for f in MODE_FIELDS},  # requested, verbatim
        {},                                         # unsharded baseline
    ]
    fuse_k = plan.auto_fuse_k(cfg, backend)
    if fuse_k:
        modes_list.append({"fuse": fuse_k})
    if cfg.ensemble:
        ens_opts = [e for e in _divisors(min(cfg.ensemble, n_dev))
                    if cfg.ensemble % e == 0 and n_dev % e == 0]
    else:
        ens_opts = [1]
    for e in ens_opts:
        spatial = n_dev // e
        for shape in _mesh_shapes(spatial, ndim):
            if not _grid_ok(cfg.grid, shape, halo):
                continue
            mesh = shape if math.prod(shape) > 1 else ()
            em = e if e > 1 else 0
            if not mesh and not em:
                continue  # the unsharded baseline, already listed
            base: Dict[str, Any] = {"mesh": mesh, "ensemble_mesh": em}
            modes_list.append(dict(base))
            if mesh:
                modes_list.append({**base, "overlap": True})
                if fuse_k and not em:
                    modes_list.append({**base, "fuse": fuse_k})
                    modes_list.append({**base, "fuse": fuse_k,
                                       "overlap": True})
    if "kernel_variant" not in locked:
        # the kernel-variant dimension (policy/autotune.py): for every
        # mode combination that hosts variants (streaming fused kernels
        # under a mesh; the unsharded tiled window kernel, round 23),
        # also propose each registry variant feasible for its family —
        # measured |var:<id> rows then outrank the default exactly like
        # a measured mesh outranks a prediction
        from . import autotune as autotune_lib

        for d in list(modes_list):
            probe = _apply(cfg, locked, d)
            hosts = probe.fuse and (
                (probe.fuse_kind == "stream" and probe.mesh)
                or (probe.fuse_kind == "tiled" and not probe.mesh))
            if not hosts or probe.kernel_variant:
                continue
            for vid in autotune_lib.sweep_ids(probe):
                modes_list.append({**d, "kernel_variant": vid})
    out: List[RunConfig] = []
    seen: Set[Tuple[Any, ...]] = set()
    for i, modes in enumerate(modes_list):
        c = _apply(cfg, locked, modes)
        key = tuple(getattr(c, f) for f in MODE_FIELDS)
        if key in seen:
            continue
        if i > 0 and not _valid(c, n_dev, backend):
            continue
        seen.add(key)
        out.append(c)
    return out


# --------------------------------------------------------------- ranking

def _ledger_identity(c: RunConfig, backend: str) -> Tuple[str, str]:
    """(cli label, baseline key) for a candidate — the exact identity
    telemetry ingestion would give a run of this config, so a measured
    row matches if and only if this config was actually measured."""
    d = dataclasses.asdict(c)
    label = ledger_lib._cli_label(d)
    flags = ledger_lib._flags(d)
    bk = ledger_lib.baseline_key({"key": {
        "label": label, "backend": backend, "flags": flags or None}})
    return label, bk


def _predict(c: RunConfig, st: Any, backend: str) -> Optional[float]:
    if st is None:
        return None
    if c.groups:
        # the monolithic roofline does not describe a coupled round
        # (per-group programs, interface traffic); without a measured
        # |grp: row the decision is honestly "requested", never a
        # prediction from the wrong model
        return None
    if c.fuse and backend != "tpu":
        return None  # Pallas temporal blocking does not run off-TPU
    variant = None
    if c.kernel_variant:
        from . import autotune as autotune_lib

        variant = autotune_lib.VARIANTS.get(c.kernel_variant)
    try:
        cost = costmodel.static_cost(
            st, c.grid, mesh=c.mesh, fuse=c.fuse, fuse_kind=c.fuse_kind,
            periodic=c.periodic, ensemble=c.ensemble,
            exchange=c.exchange, ensemble_mesh=c.ensemble_mesh,
            variant=variant)
        roof = cost["roofline"]
        key = ("predicted_mcells_per_s_overlapped" if c.overlap
               else "predicted_mcells_per_s_serial")
        v = roof.get(key) or roof.get("predicted_mcells_per_s_serial")
        return float(v) if v else None
    except Exception as e:
        log.debug("policy: costmodel skipped a candidate: %s", e)
        return None


def _json_val(v: Any) -> Any:
    return list(v) if isinstance(v, tuple) else v


def _modes_of(c: RunConfig) -> Dict[str, Any]:
    return {f: _json_val(getattr(c, f)) for f in MODE_FIELDS}


@dataclasses.dataclass
class Decision:
    """One resolved policy decision, ready to run and to record."""
    config: RunConfig                 # cfg with the winning mode fields
    provenance: str                   # "measured" | "predicted" | "requested"
    label: str                        # winner's cli ledger label
    value: Optional[float]            # winner's Mcells/s (None: requested)
    unit: str
    backend: str
    n_devices: int                    # device count the candidates spanned
    ledger_path: str
    requested: Dict[str, Any]         # mode fields before resolution
    overrides: Dict[str, Any]         # explicitly-passed (locked) fields
    table: List[Dict[str, Any]]       # ranked runner-up table
    # coupled (--groups) resolutions only — empty/"" on monolithic runs
    groups: str = ""                  # resolved CANONICAL --groups spec
    requested_groups: str = ""        # the spec as the user wrote it
    group_decisions: List[Dict[str, Any]] = dataclasses.field(
        default_factory=list)         # one entry per group, in order

    def as_event(self) -> Dict[str, Any]:
        """JSON-safe payload for the manifest ``policy`` event."""
        out = {
            "decision": _modes_of(self.config),
            "provenance": self.provenance,
            "label": self.label,
            "value": self.value,
            "unit": self.unit,
            "backend": self.backend,
            "n_devices": self.n_devices,
            "ledger": self.ledger_path,
            "requested": dict(self.requested),
            "overrides": dict(self.overrides),
            "table": list(self.table),
        }
        if self.group_decisions:
            # only on coupled resolutions: every pre-existing
            # monolithic policy event stays byte-identical
            out["groups"] = self.groups
            out["requested_groups"] = self.requested_groups
            out["group_decisions"] = [dict(d) for d in
                                      self.group_decisions]
        return out


def _group_identity(spec: Any, transport: str,
                    backend: str) -> Tuple[str, str]:
    """(label, baseline key) a per-group ledger row gets for one
    clause — must mirror ``obs/ledger._group_rows`` exactly, so a
    measured per-group row matches if and only if this clause (mode
    tokens included, via the clause signature) was actually run under
    this interface transport."""
    label = ledger_lib.group_label(spec.op)
    flags = ledger_lib.group_flags(spec.canonical(), transport)
    bk = ledger_lib.baseline_key({"key": {
        "label": label, "backend": backend, "flags": flags}})
    return label, bk


def _resolve_groups(cfg: RunConfig, backend: str, ledger_path: str,
                    base_locked: FrozenSet[str],
                    n_devices: int) -> Decision:
    """Per-group mode resolution for a coupled config (round 23).

    See the module docstring: strictly measured-beats-default over
    ``MODE_CANDIDATES`` per unset-mode clause; explicit mode tokens
    lock their clause; no roofline fallback (the monolithic model does
    not describe a coupled round, and an unmeasured mode may be
    infeasible for the group's geometry).
    """
    from ..parallel import groups as groups_lib

    specs = groups_lib.parse_groups(cfg.groups)
    transport = cfg.group_transport or groups_lib.TRANSPORT_BACKEND
    try:
        best = ledger_lib.best_known(ledger_lib.read_rows(ledger_path))
    except ValueError as e:
        log.warning("policy: unreadable ledger %s (%s) — groups keep "
                    "their requested modes", ledger_path, e)
        best = {}
    group_decisions: List[Dict[str, Any]] = []
    resolved: List[Any] = []
    for g, spec in enumerate(specs):
        name = f"g{g}:{spec.op}"
        if spec.modes:
            # explicit mode tokens are the user's call — locked, like
            # an explicitly-passed mode flag on a monolithic run
            label, bk = _group_identity(spec, transport, backend)
            row = best.get(bk)
            v = (float(row["value"]) if row is not None
                 and row.get("unit") == "Mcells/s" else None)
            group_decisions.append({
                "group": name, "clause": spec.canonical(),
                "modes": list(spec.modes), "locked": True,
                "provenance": "measured" if v is not None
                else "requested",
                "label": label,
                "value": round(v, 3) if v is not None else None,
                "table": []})
            resolved.append(spec)
            continue
        measured: List[Tuple[float, str, Tuple[str, ...], str]] = []
        for modes in groups_lib.MODE_CANDIDATES:
            cand = spec.with_modes(modes)
            label, bk = _group_identity(cand, transport, backend)
            row = best.get(bk)
            if row is not None and row.get("unit") == "Mcells/s":
                measured.append((float(row["value"]), cand.canonical(),
                                 tuple(modes), label))
        # determinism: value desc, then canonical clause — same total
        # order contract as the monolithic ranking
        measured.sort(key=lambda t: (-t[0], t[1]))
        if measured:
            value, _, modes, label = measured[0]
            chosen = spec.with_modes(modes)
            prov = "measured"
        else:
            chosen, prov, value = spec, "requested", None
            label, _ = _group_identity(spec, transport, backend)
        group_decisions.append({
            "group": name, "clause": chosen.canonical(),
            "modes": list(chosen.modes), "locked": False,
            "provenance": prov, "label": label,
            "value": round(value, 3) if value is not None else None,
            "table": [{"modes": list(m), "value": round(v, 3),
                       "clause": cl}
                      for v, cl, m, _lb in measured][:4]})
        resolved.append(chosen)
    resolved_spec = ",".join(s.canonical() for s in resolved)
    if any(tuple(ns.modes) != tuple(s.modes)
           for ns, s in zip(resolved, specs)):
        new_cfg = dataclasses.replace(cfg, groups=resolved_spec)
    else:
        new_cfg = cfg  # nothing moved: the run keeps its exact config
    label, bk = _ledger_identity(new_cfg, backend)
    row = None
    r = best.get(bk)
    if r is not None and r.get("unit") == "Mcells/s":
        row = r
    any_measured = any(d["provenance"] == "measured"
                       for d in group_decisions)
    provenance = ("measured" if row is not None or any_measured
                  else "requested")
    return Decision(
        config=new_cfg, provenance=provenance, label=label,
        value=(round(float(row["value"]), 3)
               if row is not None else None),
        unit="Mcells/s", backend=backend, n_devices=n_devices,
        ledger_path=ledger_path,
        requested={f: _json_val(getattr(cfg, f)) for f in MODE_FIELDS},
        overrides={f: _json_val(getattr(cfg, f))
                   for f in sorted(base_locked)},
        table=[], groups=resolved_spec, requested_groups=cfg.groups,
        group_decisions=group_decisions)


def resolve(cfg: RunConfig, backend: Optional[str] = None,
            ledger_path: Optional[str] = None,
            locked: Optional[Iterable[str]] = None,
            adoptable: bool = False,
            n_devices: Optional[int] = None) -> Decision:
    """Pick the execution config for ``cfg`` (see module docstring).

    ``locked`` defaults to :func:`locked_fields` — at launch that is
    exactly the explicitly-passed flags.  Mid-flight callers MUST pass
    the launch-time locked set themselves (the adopted config's fields
    are non-default by construction, so re-deriving would lock
    everything) along with ``adoptable=True``.
    """
    backend = backend or jax.default_backend()
    ledger_path = ledger_path or ledger_lib.default_ledger_path()
    base_locked = (frozenset(locked) if locked is not None
                   else locked_fields(cfg))
    eff_locked = base_locked
    if adoptable:
        eff_locked = eff_locked | frozenset(
            f for f in MODE_FIELDS if f not in ADOPTABLE_FIELDS)
    n_devices = int(n_devices) if n_devices else jax.device_count()
    if cfg.groups:
        # coupled runs resolve PER GROUP (round 23, module docstring);
        # the monolithic candidate enumeration does not describe them
        return _resolve_groups(cfg, backend, ledger_path, base_locked,
                               n_devices)
    st = _stencil_for(cfg)
    cands = candidates(cfg, backend, eff_locked, st, n_devices)
    try:
        best = ledger_lib.best_known(ledger_lib.read_rows(ledger_path))
    except ValueError as e:
        log.warning("policy: unreadable ledger %s (%s) — roofline only",
                    ledger_path, e)
        best = {}
    measured: List[Tuple[float, str, RunConfig]] = []
    predicted: List[Tuple[float, str, RunConfig]] = []
    for c in cands:
        label, bk = _ledger_identity(c, backend)
        row = best.get(bk)
        if row is not None and row.get("unit") == "Mcells/s":
            measured.append((float(row["value"]), label, c))
            continue
        v = _predict(c, st, backend)
        if v is not None:
            predicted.append((v, label, c))
    measured.sort(key=lambda t: (-t[0], t[1]))
    predicted.sort(key=lambda t: (-t[0], t[1]))
    requested = {f: _json_val(getattr(cfg, f)) for f in MODE_FIELDS}
    overrides = {f: _json_val(getattr(cfg, f))
                 for f in sorted(base_locked)}
    table = [{"label": lb, "value": round(v, 3), "provenance": prov,
              "modes": _modes_of(c)}
             for prov, pool in (("measured", measured),
                                ("predicted", predicted))
             for v, lb, c in pool][:8]
    if measured:
        value, label, chosen = measured[0]
        provenance = "measured"
    elif predicted:
        value, label, chosen = predicted[0]
        provenance = "predicted"
    else:
        chosen, provenance, value = cfg, "requested", None
        label, _ = _ledger_identity(cfg, backend)
    return Decision(config=chosen, provenance=provenance, label=label,
                    value=(round(value, 3) if value is not None else None),
                    unit="Mcells/s", backend=backend,
                    n_devices=n_devices,
                    ledger_path=ledger_path, requested=requested,
                    overrides=overrides, table=table)


# ----------------------------------------------------------- test seam

_INJECT_FIRED: Set[str] = set()


def maybe_inject(step: int) -> bool:
    """``POLICY_INJECT=step=N:PATH`` one-shot ledger injection.

    At the first call with ``step >= N``, append PATH's rows to the
    active ledger (``OBS_LEDGER_PATH``-aware) and latch.  Returns True
    exactly once per spec value.  The seam lets tests and tier-1 flip
    the measured winner under a running simulation, the same way
    ``FAULT_INJECT`` fires deterministic faults.
    """
    spec = os.environ.get("POLICY_INJECT")
    if not spec or spec in _INJECT_FIRED:
        return False
    try:
        head, path = spec.split(":", 1)
        at = int(head.split("=", 1)[1])
    except (ValueError, IndexError):
        log.warning("POLICY_INJECT=%r malformed (want step=N:PATH)", spec)
        _INJECT_FIRED.add(spec)
        return False
    if step < at:
        return False
    _INJECT_FIRED.add(spec)
    n = ledger_lib.append_rows(ledger_lib.read_rows(path))
    log.info("policy: injected %d ledger row(s) from %s at step %d",
             n, path, step)
    return True
