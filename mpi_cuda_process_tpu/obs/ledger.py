"""Append-only campaign ledger: every measurement, with provenance, forever.

Old scoreboards that read 0.0/stale could not say what the framework
HAD measured — "never measured" and "measured, then no chip" looked
alike.  This module is the durable cross-round table:

* every telemetry log (cli/bench/measure/scaling — the obs/ schema) is
  ingested into one append-only, schema-versioned JSONL ledger
  (:func:`ingest_log`; the three benchmark drivers call it
  automatically at the end of a run);
* the historical driver scoreboards (``BENCH_r0*.json``) and campaign
  tables (``benchmarks/results_r0*.json``) enter via a one-shot,
  idempotent :func:`backfill`;
* rows are keyed by label x config x mesh x kind x flags x
  BUILDER_REV (:func:`make_key`), and **quarantine** is first-class:
  0.0/stale/suspect/errored/backend-mismatched values are recorded
  with their reason and heartbeat verdict instead of being scorable —
  a quarantined row can NEVER become a baseline
  (:func:`best_known` filters on ``status == "ok"``);
* :func:`best_known` exposes best-known-value-with-provenance per
  (label, backend) — the table ``scripts/perf_gate.py`` gates against
  and ROADMAP item 4's auto-policy will read.

No jax is imported here; the ledger must be writable/readable on a
wedged box.  ``python -m mpi_cuda_process_tpu.obs.ledger`` offers
``backfill`` / ``ingest PATH`` / ``best`` subcommands (the package
import itself may pull jax; on a wedged box run it under
``JAX_PLATFORMS=cpu`` or use ``scripts/perf_gate.py`` which forces the
CPU backend first).
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import sys
import time
from typing import Any, Dict, Iterable, List, Optional, Tuple

from . import trace as trace_lib

LEDGER_SCHEMA = 1

_REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

# Note-sniffing for replayed/stale bench records: BENCH_r01's cached
# replay predates the ``stale`` flag, so the prose is the only marker.
_STALE_NOTE_MARKERS = ("stale", "cached", "backend unresponsive",
                       "not a fresh measurement")


def default_ledger_path() -> str:
    """``OBS_LEDGER_PATH`` override (tests/tier1), else the committed
    cross-round table next to the campaign results."""
    return os.environ.get("OBS_LEDGER_PATH") or \
        os.path.join(_REPO, "benchmarks", "ledger.jsonl")


# ---------------------------------------------------------------- rows

def make_key(label: str, backend: Optional[str] = None,
             grid: Any = None, mesh: Any = None,
             kind: Optional[str] = None, dtype: Optional[str] = None,
             flags: Optional[Dict[str, Any]] = None,
             builder_rev: Optional[int] = None) -> Dict[str, Any]:
    """The row identity: label x config x mesh x kind x flags x rev."""
    return {
        "label": str(label),
        "backend": backend,
        "grid": list(grid) if grid else None,
        "mesh": list(mesh) if mesh else None,
        "kind": kind,
        "dtype": dtype,
        "flags": dict(flags) if flags else None,
        "builder_rev": builder_rev,
    }


def key_id(key: Dict[str, Any]) -> str:
    return json.dumps(key, sort_keys=True, separators=(",", ":"))


def baseline_key(row: Dict[str, Any]) -> str:
    """Baseline identity for the gate: same label on the same backend
    under the same EXCHANGE MODE and the same ENSEMBLE SIZE.

    Deliberately coarser than :func:`key_id`: a BUILDER_REV bump or a
    flag change must still be COMPARED against the old number (that
    comparison is the regression gate's whole job), but a CPU smoke
    must never be judged against a TPU baseline — and a ppermute
    measurement must never be the baseline an rdma run is scored
    against (the transports are different execution paths; a label that
    exists in the ledger only under the other mode is NO_BASELINE, not
    REGRESSED).  The same rule guards the ensemble axis (round 15): an
    ``ens=8`` row aggregates 8 members' throughput, so judging it
    against a single-sim baseline (or vice versa) would read the batch
    multiplier as an 8x regression/improvement — across ensemble sizes
    the gate reports NO_BASELINE instead.  And the KERNEL VARIANT
    (round 16, policy/autotune.py): a ``|var:<id>`` row runs the same
    kernel family under swept constants, so it must never baseline a
    default-constant row (or vice versa) — a variant adoption would
    otherwise read as a regression of the default.  Mode, ensemble and
    variant ride the flags only when non-default, so every pre-existing
    row keeps its historical baseline key byte-for-byte.
    """
    k = row["key"]
    flags = k.get("flags") or {}
    mode = flags.get("exchange")
    tail = f"|{mode}" if mode else ""
    ens = flags.get("ensemble")
    if ens:
        tail += f"|ens{ens}"
    var = flags.get("kernel_variant")
    if var:
        tail += f"|var:{var}"
    grp = flags.get("groups_sig")
    if grp:
        # the GROUP SIGNATURE (round 18, parallel/groups.py): a coupled
        # --groups row times a heterogeneous multi-program round, so it
        # must never baseline a monolithic row (or vice versa, or a row
        # with a DIFFERENT split) — across group signatures the gate
        # reports NO_BASELINE, not REGRESSED
        tail += f"|grp:{grp}"
    gtx = flags.get("group_transport")
    if gtx:
        # the INTERFACE TRANSPORT (round 23): a collective-transport
        # coupled row moves its ghost bands over ICI ppermute rounds, a
        # device_put row over host-mediated transfers — different
        # execution paths, so one must never baseline the other; across
        # transports the gate reports NO_BASELINE.  Rides the flags
        # only when non-default (device_put), so every pre-existing
        # coupled row keeps its historical baseline key byte-for-byte.
        tail += f"|gtx:{gtx}"
    return f"{k['label']}|{k.get('backend')}{tail}"


def classify(value: Any, *, stale: bool = False, suspect: bool = False,
             error: Optional[str] = None,
             cancelled: bool = False,
             backend: Optional[str] = None,
             expected_backend: Optional[str] = None,
             heartbeat: Optional[str] = None,
             health: Optional[str] = None) -> Tuple[str, Optional[str]]:
    """Quarantine decision for one measurement: ``(status, reason)``.

    Order matters only for which reason is reported; ANY tripped rule
    quarantines.  A value of 0.0 (the wedged scoreboards) is never a
    measurement.  A DIVERGED health verdict (obs/health.py) quarantines
    with reason ``diverged``: the throughput of a run computing garbage
    is not a baseline candidate, however fast it looked.  A cancelled
    run (cancellation.py) quarantines with reason ``cancelled`` —
    checked before ``error`` so a deliberately stopped run can never
    read as ``errored``.
    """
    if cancelled:
        return "quarantined", "cancelled"
    if error:
        return "quarantined", f"errored: {str(error)[:120]}"
    if stale:
        return "quarantined", "stale replay — not a fresh measurement"
    if suspect:
        return "quarantined", "noise-floor suspect"
    if backend and expected_backend and backend != expected_backend:
        return "quarantined", (f"backend mismatch: record says "
                               f"{backend!r}, provenance says "
                               f"{expected_backend!r}")
    if health == "DIVERGED":
        return "quarantined", "diverged"
    if heartbeat in ("WEDGED", "STALLED"):
        return "quarantined", f"heartbeat verdict {heartbeat}"
    if not isinstance(value, (int, float)) or value <= 0.0:
        return "quarantined", f"zero/missing value ({value!r})"
    return "ok", None


def make_row(label: str, value: Any, *, source: str,
             unit: str = "Mcells/s",
             measured_at: Optional[float] = None,
             ms_per_step: Optional[float] = None,
             heartbeat: Optional[str] = None,
             health: Optional[str] = None,
             provenance: Optional[Dict[str, Any]] = None,
             detail: Optional[Dict[str, Any]] = None,
             stale: bool = False, suspect: bool = False,
             error: Optional[str] = None,
             cancelled: bool = False,
             backend: Optional[str] = None,
             expected_backend: Optional[str] = None,
             **key_kw: Any) -> Dict[str, Any]:
    status, reason = classify(
        value, stale=stale, suspect=suspect, error=error,
        cancelled=cancelled,
        backend=backend, expected_backend=expected_backend,
        heartbeat=heartbeat, health=health)
    key = make_key(label, backend=backend or expected_backend, **key_kw)
    row: Dict[str, Any] = {
        "schema": LEDGER_SCHEMA,
        "kind": "ledger_row",
        "ingested_at": time.time(),
        "label": str(label),
        "key": key,
        "key_id": key_id(key),
        "value": value if isinstance(value, (int, float)) else None,
        "unit": unit,
        "ms_per_step": ms_per_step,
        "measured_at": measured_at,
        "status": status,
        "quarantine": reason,
        "heartbeat": heartbeat,
        "source": source,
        "provenance": provenance or None,
        "detail": detail or None,
    }
    if health is not None:
        # only when a health verdict exists: every pre-existing row
        # (and its re-ingest) stays byte-identical
        row["health"] = health
    validate_row(row)
    return row


def validate_row(row: Any) -> Dict[str, Any]:
    """Raise ValueError listing every problem; return ``row`` if valid."""
    if not isinstance(row, dict):
        raise ValueError(f"ledger row must be a dict, got "
                         f"{type(row).__name__}")
    problems: List[str] = []
    if row.get("schema") != LEDGER_SCHEMA:
        problems.append(f"schema must be {LEDGER_SCHEMA} "
                        f"(got {row.get('schema')!r}); bump the reader, "
                        "never the record")
    if row.get("kind") != "ledger_row":
        problems.append(f"kind must be 'ledger_row' (got {row.get('kind')!r})")
    if not isinstance(row.get("label"), str) or not row.get("label"):
        problems.append(f"label must be a nonempty str "
                        f"(got {row.get('label')!r})")
    if not isinstance(row.get("key"), dict):
        problems.append("key must be a dict")
    if row.get("status") not in ("ok", "quarantined"):
        problems.append(f"status must be ok|quarantined "
                        f"(got {row.get('status')!r})")
    if row.get("status") == "ok":
        v = row.get("value")
        if not isinstance(v, (int, float)) or v <= 0:
            problems.append(f"an ok row needs a positive value (got {v!r})")
    elif not row.get("quarantine"):
        problems.append("a quarantined row needs a quarantine reason")
    if not isinstance(row.get("source"), str) or not row.get("source"):
        problems.append("source must be a nonempty str")
    if not isinstance(row.get("ingested_at"), (int, float)) \
            or row.get("ingested_at", 0) <= 0:
        problems.append("ingested_at must be a positive unix time")
    if problems:
        raise ValueError("invalid ledger row: " + "; ".join(problems))
    return row


# ------------------------------------------------------------- file IO

def read_rows(path: Optional[str] = None) -> List[Dict[str, Any]]:
    """Every valid row of the ledger (missing file -> []).

    A corrupt line raises with its line number — an append-only file
    that went bad must be loud, not silently shortened.
    """
    path = path or default_ledger_path()
    if not os.path.exists(path):
        return []
    rows: List[Dict[str, Any]] = []
    with open(path) as fh:
        for i, line in enumerate(fh):
            line = line.strip()
            if not line:
                continue
            try:
                rows.append(validate_row(json.loads(line)))
            except ValueError as e:
                raise ValueError(f"{path}:{i + 1}: {e}") from None
    return rows


def _row_uid(row: Dict[str, Any]) -> Tuple[str, Optional[float], str]:
    ts = row.get("measured_at")
    return (row["key_id"],
            round(float(ts), 3) if isinstance(ts, (int, float)) else None,
            row["source"])


def append_rows(rows: Iterable[Dict[str, Any]],
                path: Optional[str] = None) -> int:
    """Append rows not already present (by key x measured_at x source).

    The dedupe makes every ingest/backfill idempotent: re-running a
    backfill or re-ingesting the same log appends nothing.  Returns the
    number of rows actually appended.
    """
    path = path or default_ledger_path()
    seen = {_row_uid(r) for r in read_rows(path)}
    fresh = []
    for r in rows:
        uid = _row_uid(validate_row(r))
        if uid not in seen:
            seen.add(uid)
            fresh.append(r)
    if not fresh:
        return 0
    parent = os.path.dirname(os.path.abspath(path))
    os.makedirs(parent, exist_ok=True)
    with open(path, "a") as fh:
        for r in fresh:
            fh.write(json.dumps(r, default=str) + "\n")
    return len(fresh)


# ------------------------------------------------- telemetry ingestion

def _flags(run: Dict[str, Any]) -> Dict[str, Any]:
    out = {k: run.get(k) for k in ("fuse", "fuse_kind", "overlap",
                                   "pipeline")
           if run.get(k)}
    # exchange mode and ensemble size are part of the row identity AND
    # the baseline key (see baseline_key) — recorded only when
    # non-default so every pre-existing key (and its best_known dedupe)
    # stays byte-identical
    if run.get("exchange") and run["exchange"] != "ppermute":
        out["exchange"] = run["exchange"]
    if run.get("ensemble"):
        out["ensemble"] = run["ensemble"]
        if run.get("ensemble_mesh"):
            out["ensemble_mesh"] = run["ensemble_mesh"]
    if run.get("kernel_variant"):
        out["kernel_variant"] = run["kernel_variant"]
    if run.get("groups"):
        # a short stable signature, not the raw spec string: the flag
        # set rides every row and key, and the signature is what the
        # |grp: baseline-key tail needs (config.groups_signature)
        from ..config import groups_signature

        out["groups_sig"] = groups_signature(run["groups"])
        if run.get("group_transport") and \
                run["group_transport"] != "device_put":
            # non-default interface transport (round 23): part of the
            # identity AND the |gtx: baseline-key tail
            out["group_transport"] = run["group_transport"]
    return out


def _cli_label(run: Dict[str, Any]) -> str:
    parts = [str(run.get("stencil") or "run"),
             "x".join(map(str, run.get("grid") or ()))]
    if run.get("dtype"):
        parts.append(str(run["dtype"]))
    if run.get("fuse"):
        parts.append(f"fuse{run['fuse']}")
    if run.get("fuse_kind") and run["fuse_kind"] != "auto":
        parts.append(str(run["fuse_kind"]))
    if run.get("mesh"):
        parts.append("mesh" + "x".join(map(str, run["mesh"])))
    if run.get("overlap"):
        parts.append("overlap")
    if run.get("pipeline"):
        parts.append("pipeline")
    if run.get("exchange") and run["exchange"] != "ppermute":
        parts.append(str(run["exchange"]))
    if run.get("ensemble"):
        parts.append(f"ens{run['ensemble']}")
        if run.get("ensemble_mesh"):
            parts.append(f"ensmesh{run['ensemble_mesh']}")
    if run.get("kernel_variant"):
        parts.append(f"var{run['kernel_variant']}")
    if run.get("groups"):
        n = len([c for c in str(run["groups"]).split(",") if c.strip()])
        parts.append(f"grp{n}")
    return "cli_" + "_".join(p for p in parts if p)


def group_label(op: Any) -> str:
    """The per-group ledger row label (round 23): the op alone.

    Deliberately minimal — the clause signature in the flags
    (``groups_sig`` of the single clause's canonical form, with its
    mode tokens folded in) carries the full identity into the baseline
    key, so two clauses differing in ANYTHING (devices, z fraction,
    sub-mesh, dtype, ratio, modes) never share a baseline.  The policy
    resolver (policy/select.py) builds the same label + flags for its
    per-group candidates, so a measured row matches if and only if
    this exact clause was actually run.
    """
    return f"cli_grp_{op}"


def group_flags(clause: str, transport: Optional[str] = None
                ) -> Dict[str, Any]:
    """The per-group ledger row flags for one canonical clause."""
    from ..config import groups_signature

    out: Dict[str, Any] = {"groups_sig": groups_signature(clause)}
    if transport and transport != "device_put":
        out["group_transport"] = transport
    return out


def _group_rows(manifest: Dict[str, Any], events: List[Dict[str, Any]],
                run: Dict[str, Any], prov: Dict[str, Any], source: str,
                hb: Optional[str], health: Optional[str]
                ) -> List[Dict[str, Any]]:
    """Per-group rows for one coupled cli log (round 23).

    One row per group, valued at the group's wall-weighted mean
    Mcells/s over its ``group_chunk`` events — the per-group measured
    table ``--auto-policy --groups`` resolves each group's mode tokens
    against.  Needs the manifest ``groups`` block's ``clause`` entry
    (older logs without it, or runs that died before any chunk, add
    nothing — the main coupled row still lands as before).
    """
    rows: List[Dict[str, Any]] = []
    transport = run.get("group_transport") or None
    for meta in manifest.get("groups") or []:
        if not isinstance(meta, dict) or not meta.get("clause"):
            continue
        name = meta.get("group")
        wall = 0.0
        weighted = 0.0
        last_t = None
        for e in events:
            if e.get("kind") != "group_chunk" or e.get("group") != name:
                continue
            w = e.get("wall_s")
            v = e.get("mcells_per_s")
            if not isinstance(w, (int, float)) or w <= 0 or \
                    not isinstance(v, (int, float)):
                continue
            wall += w
            weighted += v * w
            if e.get("t") is not None:
                last_t = e["t"]
        if wall <= 0:
            continue
        rows.append(make_row(
            group_label(meta.get("op")), round(weighted / wall, 3),
            source=source, measured_at=last_t, heartbeat=hb,
            health=health, expected_backend=prov.get("backend"),
            provenance=_prov_subset(prov),
            grid=meta.get("grid"), mesh=meta.get("mesh") or None,
            dtype=meta.get("dtype"),
            flags=group_flags(meta["clause"], transport),
            builder_rev=prov.get("builder_rev"),
            detail={"group": name, "clause": meta["clause"],
                    "modes": list(meta.get("modes") or [])}))
    return rows


def _scaling_label(run: Dict[str, Any], rung: Dict[str, Any]) -> str:
    parts = ["scaling", str(rung.get("mode") or run.get("mode") or "?"),
             str(rung.get("stencil") or "?"),
             "x".join(map(str, rung.get("grid") or ())),
             "mesh" + "x".join(map(str, rung.get("mesh") or ()))]
    if rung.get("fuse"):
        parts.append(f"fuse{rung['fuse']}")
    if rung.get("fuse_kind"):
        parts.append(str(rung["fuse_kind"]))
    if rung.get("overlap"):
        parts.append("overlap")
    if rung.get("pipeline"):
        parts.append("pipeline")
    if rung.get("exchange") and rung["exchange"] != "ppermute":
        parts.append(str(rung["exchange"]))
    if rung.get("ensemble"):
        parts.append(f"ens{rung['ensemble']}")
    return "_".join(parts)


def _prov_subset(prov: Dict[str, Any]) -> Dict[str, Any]:
    return {k: prov.get(k) for k in ("git_sha", "backend", "device_kind",
                                     "device_count", "builder_rev",
                                     "jax_version")}


def _bench_rows(rec: Dict[str, Any], source: str,
                prov: Optional[Dict[str, Any]] = None,
                measured_at: Optional[float] = None,
                heartbeat: Optional[str] = None) -> List[Dict[str, Any]]:
    """Rows from one bench.py headline record (live event or BENCH_r0*).

    The wedged-path vocabulary is quarantined wholesale: ``stale`` flags,
    ``*_cached``/``*_unmeasured`` metric names, and the pre-flag cached
    replay whose only marker is the note prose.  The
    ``last_real_measurement`` pointer rides in ``detail`` so the
    quarantined row still names the last value that WAS real.
    """
    prov = prov or {}
    note = str(rec.get("note") or "").lower()
    metric = str(rec.get("metric") or "bench")
    stale = bool(rec.get("stale")) \
        or metric.endswith(("_cached", "_unmeasured")) \
        or any(m in note for m in _STALE_NOTE_MARKERS)
    hb = heartbeat
    if hb is None and isinstance(rec.get("heartbeat"), dict):
        hb = rec["heartbeat"].get("verdict")
    detail = {}
    if rec.get("last_real_measurement"):
        detail["last_real_measurement"] = rec["last_real_measurement"]
    if rec.get("note"):
        detail["note"] = rec["note"]
    rows = [make_row(
        metric, rec.get("value"), source=source,
        unit=str(rec.get("unit") or "Mcells/s"),
        measured_at=measured_at,
        stale=stale, suspect=bool(rec.get("suspect")),
        backend=rec.get("backend"),
        expected_backend=prov.get("backend"),
        heartbeat=hb, provenance=_prov_subset(prov) if prov else None,
        detail=detail or None,
        kind=rec.get("compute"), builder_rev=prov.get("builder_rev"))]
    if rec.get("value_512cubed") is not None:
        rows.append(make_row(
            metric + "_512cubed", rec.get("value_512cubed"),
            source=source, measured_at=measured_at, stale=stale,
            suspect=bool(rec.get("suspect_512cubed")),
            backend=rec.get("backend"),
            expected_backend=prov.get("backend"), heartbeat=hb,
            provenance=_prov_subset(prov) if prov else None,
            kind=rec.get("compute_512cubed"),
            builder_rev=prov.get("builder_rev")))
    return rows


def rows_from_log(log_path: str) -> List[Dict[str, Any]]:
    """Ledger rows for one telemetry JSONL (any of the four tools).

    Does NOT append — callers pair this with :func:`append_rows`
    (``ingest_log``) or use the rows directly (the perf gate's "fresh"
    side).
    """
    manifest, events = trace_lib.read_log(log_path)
    trace_lib.validate_manifest(manifest)
    tool = manifest["tool"]
    run = manifest.get("run") or {}
    prov = manifest.get("provenance") or {}
    source = f"telemetry:{os.path.abspath(log_path)}"
    # newest heartbeat verdict anywhere in the log (summary included)
    hb = None
    for e in events:
        if e.get("kind") == "heartbeat":
            hb = e.get("verdict")
        elif e.get("kind") == "summary" and isinstance(
                e.get("heartbeat"), dict):
            hb = e["heartbeat"].get("verdict") or hb
    # health sentinel verdict (obs/health.py): once DIVERGED, the run's
    # numbers are garbage-adjacent — every row of this log quarantines
    # with reason 'diverged' (a later HEALTHY check cannot un-diverge a
    # run; the CLI aborts at the first DIVERGED boundary anyway)
    health = None
    for e in events:
        if e.get("kind") == "health":
            health = e.get("verdict") if health != "DIVERGED" else health
    rows: List[Dict[str, Any]] = []
    # restart trail (resilience/): a resumed run names its resume point
    # in a 'resume' event; the row detail carries it so downstream
    # consumers (perf_gate) can flag an after-restart value as honest
    # but restarted.  Old logs never carried the event, so every
    # pre-existing row detail stays byte-identical.
    resumed_from = None
    for e in events:
        if e.get("kind") == "resume" and \
                e.get("resumed_from_step") is not None:
            resumed_from = e["resumed_from_step"]
    # cooperative cancel (cancellation.py): the run wrote a 'cancelled'
    # event instead of an error — its row must quarantine with reason
    # 'cancelled', never 'errored: ...'
    cancelled_ev = None
    for e in events:
        if e.get("kind") == "cancelled":
            cancelled_ev = e
    if tool == "cli":
        summaries = [e for e in events if e.get("kind") == "summary"]
        # run-doctor findings (obs/anomaly.py, --anomaly): a DEGRADED
        # run's value is honest — the steps ran, the number is real —
        # so the row is NOT quarantined, just flagged.  perf_gate
        # renders the flag as [degraded]; obs_report shows the
        # findings.  Clean runs (N == 0) add no detail key, so every
        # pre-existing row stays byte-identical.
        n_anomalies = sum(1 for e in events if e.get("kind") == "anomaly")
        if run.get("groups"):
            # per-group rows land ALONGSIDE the coupled headline row —
            # the policy resolver reads these, the perf gate the main
            rows.extend(_group_rows(manifest, events, run, prov,
                                    source, hb, health))
        for s in summaries:
            detail = {}
            if resumed_from is not None:
                detail["resumed_from_step"] = resumed_from
            if n_anomalies:
                detail["degraded"] = n_anomalies
            rows.append(make_row(
                _cli_label(run), s.get("mcells_per_s"), source=source,
                measured_at=s.get("t"), heartbeat=hb, health=health,
                expected_backend=prov.get("backend"),
                provenance=_prov_subset(prov),
                grid=run.get("grid"), mesh=run.get("mesh"),
                kind=run.get("fuse_kind"), dtype=run.get("dtype"),
                flags=_flags(run), builder_rev=prov.get("builder_rev"),
                detail=detail or None))
        if cancelled_ev is not None and not summaries:
            # a cancelled run ends before its summary — the row still
            # lands (value-less, quarantined 'cancelled') so the ledger
            # records a deliberate stop, distinct from a crash
            rows.append(make_row(
                _cli_label(run), None, source=source,
                measured_at=cancelled_ev.get("t"),
                heartbeat=hb, health=health, cancelled=True,
                expected_backend=prov.get("backend"),
                provenance=_prov_subset(prov),
                grid=run.get("grid"), mesh=run.get("mesh"),
                kind=run.get("fuse_kind"), dtype=run.get("dtype"),
                flags=_flags(run), builder_rev=prov.get("builder_rev"),
                detail={"cancelled_at_step": cancelled_ev.get("step")}
                if cancelled_ev.get("step") is not None else None))
        if health == "DIVERGED" and not summaries:
            # a diverged run aborts before its summary — the row still
            # lands (value-less, quarantined 'diverged') so the ledger
            # records that this config BLEW UP rather than nothing
            div = [e for e in events if e.get("kind") == "health"
                   and e.get("verdict") == "DIVERGED"]
            detail = {"health_reason": str(div[-1].get("reason"))[:200]} \
                if div and div[-1].get("reason") else None
            rows.append(make_row(
                _cli_label(run), None, source=source,
                measured_at=div[-1].get("t") if div else None,
                heartbeat=hb, health=health,
                expected_backend=prov.get("backend"),
                provenance=_prov_subset(prov),
                grid=run.get("grid"), mesh=run.get("mesh"),
                kind=run.get("fuse_kind"), dtype=run.get("dtype"),
                flags=_flags(run), builder_rev=prov.get("builder_rev"),
                detail=detail))
    elif tool == "bench":
        for e in events:
            if e.get("kind") != "result":
                continue
            rows.extend(_bench_rows(e, source, prov=prov,
                                    measured_at=e.get("t"), heartbeat=hb))
    elif tool == "measure":
        for e in events:
            if e.get("kind") != "label":
                continue
            status = e.get("status")
            detail = {}
            if status:
                detail["status"] = status
            if e.get("attempts"):
                # measured after a supervised retry: attempt count rides
                # the row so the gate can flag the value
                detail["attempts"] = e["attempts"]
            rows.append(make_row(
                str(e.get("label")), e.get("mcells_per_s"), source=source,
                measured_at=e.get("t"), heartbeat=hb, health=health,
                error=(e.get("error") or None) if status in
                      ("error", "timeout", "missing") else None,
                expected_backend=prov.get("backend"),
                provenance=_prov_subset(prov),
                kind=e.get("compute"),
                builder_rev=run.get("builder_rev")
                or prov.get("builder_rev"),
                detail=detail or None))
    elif tool == "scaling":
        for e in events:
            if e.get("kind") != "rung":
                continue
            rows.append(make_row(
                _scaling_label(run, e),
                e.get("mcells_per_s") or e.get("ms_per_step_full"),
                source=source, measured_at=e.get("t"), heartbeat=hb,
                health=health,
                expected_backend=prov.get("backend"),
                provenance=_prov_subset(prov),
                grid=e.get("grid"), mesh=e.get("mesh"),
                kind=e.get("kernel_kind") or e.get("fuse_kind"),
                # the historical flag set PLUS exchange-when-non-default:
                # re-ingesting an old log must reproduce its old key_id
                # byte-for-byte (idempotent append), so the set is only
                # ever extended by fields old logs never carried
                flags={**{k: e.get(k) for k in ("fuse", "overlap",
                                                "pipeline") if e.get(k)},
                       **({"exchange": e["exchange"]}
                          if e.get("exchange")
                          and e["exchange"] != "ppermute" else {}),
                       **({"ensemble": e["ensemble"]}
                          if e.get("ensemble") else {})},
                builder_rev=prov.get("builder_rev"),
                unit=("Mcells/s" if e.get("mcells_per_s") is not None
                      else "ms/step")))
    return rows


def ingest_log(log_path: str, ledger_path: Optional[str] = None) -> int:
    """Parse one telemetry log and append its rows; returns rows added."""
    return append_rows(rows_from_log(log_path), ledger_path)


# ------------------------------------------------------------ backfill

def _backfill_bench_files(repo: str) -> List[Dict[str, Any]]:
    rows: List[Dict[str, Any]] = []
    for path in sorted(glob.glob(os.path.join(repo, "BENCH_r0*.json"))):
        try:
            with open(path) as fh:
                doc = json.load(fh)
        except Exception:  # noqa: BLE001 — skip foreign files
            continue
        rec = doc.get("parsed") if isinstance(doc, dict) else None
        if not isinstance(rec, dict):
            continue
        rows.extend(_bench_rows(rec, source=os.path.basename(path)))
    return rows


def _backfill_results_tables(repo: str) -> List[Dict[str, Any]]:
    rows: List[Dict[str, Any]] = []
    for path in sorted(glob.glob(
            os.path.join(repo, "benchmarks", "results_r0*.json"))):
        try:
            with open(path) as fh:
                table = json.load(fh)
        except Exception:  # noqa: BLE001
            continue
        if not isinstance(table, dict):
            continue
        src = os.path.basename(path)
        for label, rec in table.items():
            if not isinstance(rec, dict):
                continue
            rows.append(make_row(
                str(label), rec.get("mcells_per_s"), source=src,
                measured_at=rec.get("measured_at")
                if isinstance(rec.get("measured_at"), (int, float))
                else None,
                ms_per_step=rec.get("ms_per_step"),
                suspect=bool(rec.get("suspect")),
                error=rec.get("error"),
                backend=rec.get("backend"),
                grid=rec.get("grid"), dtype=rec.get("dtype"),
                kind=rec.get("compute"),
                builder_rev=rec.get("builder_rev")
                if isinstance(rec.get("builder_rev"), int) else None))
    return rows


def backfill(repo: Optional[str] = None,
             ledger_path: Optional[str] = None) -> Dict[str, int]:
    """One-shot historical ingest: BENCH_r0*.json + results_r0*.json.

    Idempotent (append_rows dedupes), so running it every round is
    safe.  Returns ``{"found", "appended", "quarantined"}``.
    """
    repo = repo or _REPO
    rows = _backfill_bench_files(repo) + _backfill_results_tables(repo)
    appended = append_rows(rows, ledger_path)
    return {"found": len(rows), "appended": appended,
            "quarantined": sum(1 for r in rows
                               if r["status"] == "quarantined")}


def ingest_results(out_path: str,
                   ledger_path: Optional[str] = None) -> int:
    """measure.py's auto-update hook: ingest its results table."""
    try:
        with open(out_path) as fh:
            table = json.load(fh)
    except Exception:  # noqa: BLE001 — a missing table adds nothing
        return 0
    if not isinstance(table, dict):
        return 0
    src = os.path.basename(out_path)
    rows = []
    for label, rec in table.items():
        if not isinstance(rec, dict):
            continue
        rows.append(make_row(
            str(label), rec.get("mcells_per_s"), source=src,
            measured_at=rec.get("measured_at")
            if isinstance(rec.get("measured_at"), (int, float)) else None,
            ms_per_step=rec.get("ms_per_step"),
            suspect=bool(rec.get("suspect")), error=rec.get("error"),
            backend=rec.get("backend"), grid=rec.get("grid"),
            dtype=rec.get("dtype"), kind=rec.get("compute"),
            builder_rev=rec.get("builder_rev")
            if isinstance(rec.get("builder_rev"), int) else None,
            # the supervised-retry trail: a value measured after a
            # restart carries its attempt count into the ledger row
            detail={"restart_attempts": rec["restart_attempts"]}
            if rec.get("restart_attempts") else None))
    return append_rows(rows, ledger_path)


# ----------------------------------------------------------- baselines

def _best_order(row: Dict[str, Any]) -> Tuple[Any, ...]:
    """Total order for best_known: value, then measured_at, then the
    full key identity and source.  The trailing components never
    change WHICH measurement wins on merit — they only make ties
    impossible, so the winner is a pure function of the row SET and
    repeated policy resolution over the same ledger can never flip its
    decision with row order (the auto-policy determinism contract)."""
    return (row["value"], row.get("measured_at") or 0,
            row.get("key_id") or key_id(row["key"]),
            str(row.get("source") or ""))


def best_known(rows: Iterable[Dict[str, Any]]) -> Dict[str, Dict[str, Any]]:
    """Best ok value per (label, backend), with full row provenance.

    Quarantined rows are structurally excluded — the function reads
    ``status`` only, so no stale/0.0/wedged record can ever surface as
    a baseline (the acceptance criterion).  Ties are broken by the
    total order of :func:`_best_order`, never by file position.
    """
    best: Dict[str, Dict[str, Any]] = {}
    for r in rows:
        if r.get("status") != "ok":
            continue
        bk = baseline_key(r)
        cur = best.get(bk)
        if cur is None or _best_order(r) > _best_order(cur):
            best[bk] = r
    return best


# ----------------------------------------------------------------- CLI

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="mpi_cuda_process_tpu.obs.ledger",
        description=__doc__.split("\n")[0])
    ap.add_argument("--ledger", default=None,
                    help=f"ledger path (default {default_ledger_path()})")
    sub = ap.add_subparsers(dest="cmd", required=True)
    sub.add_parser("backfill", help="one-shot historical ingest of "
                                    "BENCH_r0*.json + results_r0*.json "
                                    "(idempotent)")
    p_in = sub.add_parser("ingest", help="ingest one telemetry JSONL")
    p_in.add_argument("log")
    sub.add_parser("best", help="print best-known-value-with-provenance "
                                "per label x backend")
    a = ap.parse_args(argv)
    path = a.ledger or default_ledger_path()
    if a.cmd == "backfill":
        out = backfill(ledger_path=path)
        print(f"ledger backfill: {out['found']} rows found, "
              f"{out['appended']} appended "
              f"({out['quarantined']} quarantined) -> {path}")
        return 0
    if a.cmd == "ingest":
        n = ingest_log(a.log, path)
        print(f"ledger ingest: {n} rows appended from {a.log} -> {path}")
        return 0
    rows = read_rows(path)
    best = best_known(rows)
    quarantined = sum(1 for r in rows if r["status"] == "quarantined")
    print(f"# {path}: {len(rows)} rows ({quarantined} quarantined), "
          f"{len(best)} baselines")
    for bk in sorted(best):
        r = best[bk]
        print(f"{bk:60s} {r['value']:>12} {r['unit']:9s} "
              f"src={r['source']}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
