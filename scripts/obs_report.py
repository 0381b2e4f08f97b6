#!/usr/bin/env python
"""Render a telemetry event log into a per-phase attribution report.

Reads a JSONL trace written by the obs/ layer (``cli --telemetry``,
bench.py, benchmarks/measure.py, benchmarks/scaling.py — one shared
manifest schema) and prints:

* the manifest (what ran, where, from which code);
* the static cost model next to the measurement — a per-phase table
  attributing the step budget to interior HBM traffic, the ppermute
  exchange, and the boundary shells, with the roofline's ``overlapped``
  vs ``serial`` predictions bracketing the measured steady-state
  ms/step (the measured number landing between them IS the overlap win,
  quantified — the attribution discipline of arXiv:2108.11076);
* runtime stats (compile vs steady chunks, recompiles, memory peaks),
  heartbeat verdicts, benchmark label/rung records, and how the run
  ended.

``--check`` validates the log against the shared schema and exits
nonzero on any invalid record — the mode ``scripts/tier1.sh`` runs, so
a tool drifting off-schema fails the gate.

A ``tool="supervisor"`` log has no chunk events (its children's logs
carry those), so instead of an empty attribution table it renders the
launch/restart/give-up trail with ``resumed_from_step``.  ``--ledger``
is the campaign-state mode: the ``best_known`` table per label x
backend plus quarantine counts and reasons, straight from
``benchmarks/ledger.jsonl`` (or a path you pass).

Group-mode logs (``--groups``, PR 18/19) get per-group blocks:
``policy_group`` clause decisions, per-group chunk rates with the
coupled ready-horizon ms/step, ``migrate`` events, and group-named
health verdicts.  ``anomaly`` events (the ``--anomaly`` run doctor)
render as a findings table — their presence means verdict DEGRADED.

When PATH is a flight-recorder bundle (``*.bundle.json``, written by
obs/flightrec.py on a terminal verdict or by ``scripts/obs_bundle.py``)
it is rendered as a self-contained post-mortem — manifest, event ring,
anomaly findings, open spans, ledger baseline — with
no need for the original telemetry dir.  ``--check`` on a bundle runs
the bundle's own self-validation instead of the log schema walk.

Safe on a wedged box: the CPU backend is forced before any jax use and
nothing here touches a device.

Usage:  python scripts/obs_report.py PATH [--check]
        python scripts/obs_report.py --ledger [PATH]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)

from cpuforce import force_cpu  # noqa: E402

force_cpu()  # before the package (and hence any jax backend) loads

from mpi_cuda_process_tpu.obs import trace as obs_trace  # noqa: E402


def _fmt_bytes(b) -> str:
    if b is None:
        return "-"
    if b >= 2**30:
        return f"{b / 2**30:.2f} GiB"
    if b >= 2**20:
        return f"{b / 2**20:.2f} MiB"
    return f"{b} B"


def _table(rows, header):
    widths = [max(len(str(r[i])) for r in [header] + rows)
              for i in range(len(header))]
    out = ["  ".join(str(h).ljust(w) for h, w in zip(header, widths))]
    out.append("  ".join("-" * w for w in widths))
    for r in rows:
        out.append("  ".join(str(c).ljust(w) for c, w in zip(r, widths)))
    return "\n".join(out)


def _manifest_block(m) -> str:
    p = m["provenance"]
    run = m.get("run", {})
    keys = [k for k in ("stencil", "grid", "mesh", "iters", "fuse",
                        "fuse_kind", "overlap", "pipeline", "dtype",
                        "mode", "out", "only", "profile") if run.get(k)]
    lines = [
        f"manifest  tool={m['tool']}  schema={m['schema']}",
        f"  backend={p['backend']} ({p['device_count']}x "
        f"{p['device_kind']})  jax={p['jax_version']}",
        f"  git={p['git_sha'][:12]}  builder_rev={p.get('builder_rev')}  "
        f"framework={p['framework_version']}",
    ]
    if keys:
        lines.append("  run: " + "  ".join(f"{k}={run[k]}" for k in keys))
    return "\n".join(lines)


def _attribution_block(cost, summary) -> str:
    # no roofline on a device the peaks table does not know: "-"
    roof = cost.get("roofline") or {}
    comm = cost.get("comm")
    t_hbm = roof.get("predicted_ms_per_step_hbm")
    t_ici = roof.get("predicted_ms_per_step_exchange")
    measured = None
    if summary:
        steady = (summary.get("runtime") or {}).get("steady") or {}
        measured = steady.get("ms_per_step_p50")

    rows = [["interior (HBM min traffic)",
             f"{t_hbm:.4f}" if t_hbm is not None else "-",
             _fmt_bytes(cost.get("hbm_bytes_per_step_per_device")),
             "(not separable)"]]
    if comm:
        if comm.get("exchange") == "rdma":
            # in-kernel remote-DMA exchange: attribute the ICI traffic
            # by its remote-DMA chunk count (zero ppermute by gate)
            what = (f"exchange ({comm.get('rdma_dma_per_pass')} "
                    f"rdma-dma/pass, width {comm.get('width_m')})")
        else:
            what = (f"exchange ({comm['ppermute_rounds_per_pass']} "
                    f"ppermute/pass, width {comm.get('width_m')})")
        rows.append([
            what,
            f"{t_ici:.4f}" if t_ici is not None else "-",
            _fmt_bytes(int(comm["ici_bytes_per_step"])) + "/step",
            "(not separable)"])
        # boundary shells: cells within 2m of a sharded wall, re-read/
        # re-spliced by the overlap path — bandwidth-priced like interior
        m2 = 2 * (comm.get("width_m") or 0)
        local = cost.get("local_shape") or []
        counts = comm.get("sharded_counts") or []
        if local and counts and t_hbm:
            inner = 1.0
            for d, (ext, cnt) in enumerate(zip(local, counts)):
                if cnt > 1 and ext > m2:
                    inner *= (ext - m2) / ext
            shell_frac = 1.0 - inner
            rows.append([
                "shell (re-splice band, "
                f"{shell_frac * 100:.1f}% of cells)",
                f"{t_hbm * shell_frac:.4f}", "-", "(not separable)"])
    total_over = roof.get("predicted_mcells_per_s_overlapped")
    total_serial = roof.get("predicted_mcells_per_s_serial")
    rows.append(["TOTAL overlapped (exchange hidden)",
                 f"{max(t_hbm or 0, t_ici or 0):.4f}" if roof else "-",
                 f"{total_over} Mcells/s",
                 f"{measured:.4f}" if measured is not None else "-"])
    if comm:
        rows.append(["TOTAL serial (exchange on critical path)",
                     f"{(t_hbm or 0) + (t_ici or 0):.4f}" if roof
                     else "-",
                     f"{total_serial} Mcells/s", ""])
    return "attribution (predicted vs measured)\n" + _table(
        rows, ["phase", "pred ms/step", "volume", "measured ms/step"])


def _profile_block(prof, cost) -> str:
    """Predicted-vs-measured hiding in one block (the --profile event).

    The roofline's ``overlapped`` prediction assumes the exchange fully
    hidden (efficiency 1.0) and ``serial`` fully exposed (0.0); the
    device trace says where the run actually landed.
    """
    head = "device-trace attribution"
    chunk = prof.get("profiled_chunk")
    if prof.get("attribution") != "ok":
        return (f"{head}: unavailable — "
                f"{prof.get('reason') or 'no reason recorded'}"
                f"  (dir {prof.get('profile_dir')})")
    lines = [f"{head} (profiled chunk {chunk}):"]
    lines.append(
        f"  device busy {prof['device_busy_us'] / 1e3:.3f} ms = "
        f"compute {prof['compute_us'] / 1e3:.3f} ms"
        f" + exchange {prof['comm_us'] / 1e3:.3f} ms"
        f" (exposed {prof['exposed_comm_us'] / 1e3:.3f} ms)"
        f"  [{prof['n_device_events']} device events]")
    eff = prof.get("overlap_efficiency")
    roof = (cost or {}).get("roofline") or {}
    if eff is None:
        lines.append("  no exchange ops in the trace (unsharded run): "
                     "nothing to hide")
    else:
        pred = (f"roofline brackets: overlapped "
                f"{roof.get('predicted_mcells_per_s_overlapped')} vs "
                f"serial {roof.get('predicted_mcells_per_s_serial')} "
                f"Mcells/s" if roof else "no costmodel event to "
                                         "compare against")
        lines.append(f"  measured overlap efficiency {eff:.1%} "
                     f"(1.0 = exchange fully hidden) — {pred}")
    return "\n".join(lines)


def _runtime_block(summary) -> str:
    rt = summary.get("runtime") or {}
    lines = [f"runtime  chunks={rt.get('n_chunks')}  "
             f"steps={rt.get('steps')}  recompiles={rt.get('recompiles')}"]
    if "first_chunk_s" in rt:
        lines.append(f"  compile+first chunk: {rt['first_chunk_s']:.3f}s "
                     f"({rt['first_chunk_ms_per_step']:.4f} ms/step)")
    steady = rt.get("steady")
    if steady:
        lines.append(
            f"  steady ({steady['chunks']} chunks): "
            f"best {steady['ms_per_step_best']:.4f}  "
            f"p50 {steady['ms_per_step_p50']:.4f}  "
            f"p90 {steady['ms_per_step_p90']:.4f} ms/step")
    if "memory_peak_bytes" in rt:
        lines.append(f"  device memory peak: "
                     f"{_fmt_bytes(rt['memory_peak_bytes'])}")
    for k in ("mcells_per_s", "steps", "wall_s", "converged", "residual",
              "labels_run", "note"):
        if k in summary:
            lines.append(f"  {k}: {summary[k]}")
    hb = summary.get("heartbeat")
    if hb:
        lines.append(f"  heartbeat at exit: {hb.get('verdict')}")
    return "\n".join(lines)


def _supervisor_trail_block(events) -> str:
    """The launch/restart/give-up trail of a ``tool="supervisor"`` log.

    A supervisor log has no chunk or costmodel events (the CHILD's logs
    carry those), so the attribution table used to render empty and
    misleading; the trail — which attempt launched when, why each was
    killed, where each resume picked up — is the story this log
    actually tells.
    """
    rows = []
    for e in events:
        kind = e.get("kind")
        if kind == "launch":
            what = "resume" if e.get("resume") else "fresh start"
            rows.append([f"{e['t']:.0f}", e.get("attempt"), "launch",
                         e.get("resumed_from_step")
                         if e.get("resumed_from_step") is not None
                         else "-", what])
        elif kind == "restart":
            rows.append([f"{e['t']:.0f}", e.get("attempt"), "restart",
                         e.get("checkpoint_step")
                         if e.get("checkpoint_step") is not None else "-",
                         f"{e.get('reason', '?')} "
                         f"(backoff {e.get('backoff_s', '?')}s)"])
        elif kind == "give_up":
            rows.append([f"{e['t']:.0f}", "-", "GIVE UP", "-",
                         f"{e.get('reason', '?')} after "
                         f"{e.get('attempts', '?')} attempt(s)"])
    launches = sum(1 for e in events if e.get("kind") == "launch")
    restarts = sum(1 for e in events if e.get("kind") == "restart")
    head = (f"supervisor trail ({launches} launch(es), "
            f"{restarts} restart(s))")
    if not rows:
        return head + ": no launch events (did the supervisor start?)"
    return head + "\n" + _table(
        rows, ["t", "attempt", "event", "ckpt/resume step", "detail"])


def _policy_groups_block(evs) -> str:
    """Per-group policy resolutions (``policy_group`` events, PR 19)."""
    rows = []
    for e in evs[:64]:
        rows.append([e.get("group") or "?",
                     (e.get("clause") or "")[:36],
                     "locked" if e.get("locked") else "resolved",
                     e.get("provenance") or "?",
                     e.get("value") if e.get("value") is not None
                     else "-"])
    return "per-group policy decisions:\n" + _table(
        rows, ["group", "clause", "how", "provenance", "Mcells/s"])


def _group_chunks_block(evs) -> str:
    """Coupled-run per-group throughput (``group_chunk`` events)."""
    by_group: dict = {}
    for e in evs:
        by_group.setdefault(e.get("group") or "?", []).append(e)
    rows = []
    for g in sorted(by_group):
        recs = by_group[g]
        last = recs[-1]
        vals = [r.get("mcells_per_s") for r in recs
                if isinstance(r.get("mcells_per_s"), (int, float))]
        ready = [r.get("ready_ms_per_step") for r in recs
                 if isinstance(r.get("ready_ms_per_step"), (int, float))]
        rows.append([
            g, last.get("op") or "-", len(recs),
            round(sum(vals) / len(vals), 3) if vals else "-",
            last.get("mcells_per_s") if last.get("mcells_per_s")
            is not None else "-",
            round(sum(ready) / len(ready), 3) if ready else "-"])
    return (f"coupled groups ({len(by_group)}):\n"
            + _table(rows, ["group", "op", "chunks", "mean Mc/s",
                            "last Mc/s", "ready ms/step"]))


def _migrate_block(evs) -> str:
    """Live-migration trail (``migrate`` events: policy adoptions)."""
    rows = []
    for e in evs[:64]:
        dst = e.get("dst") or {}
        mesh = dst.get("mesh")
        rows.append([e.get("step", "-"), e.get("n", "-"),
                     (e.get("label") or "?")[:36],
                     e.get("provenance") or "?",
                     "x".join(map(str, mesh)) if mesh else "-",
                     e.get("rounds", "-")])
    return "migrations:\n" + _table(
        rows, ["step", "n", "label", "provenance", "dst mesh", "rounds"])


def _group_health_block(evs) -> str:
    """Group-named numerics verdicts of a coupled ``--health`` run."""
    rows = [[f"{e['t']:.0f}", e.get("group") or "-", e.get("step", "-"),
             e.get("verdict"), (e.get("reason") or "")[:56]]
            for e in evs[:128]]
    return "group health verdicts:\n" + _table(
        rows, ["t", "group", "step", "verdict", "reason"])


def _anomaly_block(evs) -> str:
    """Run-doctor findings (``anomaly`` events, obs/anomaly.py)."""
    rows = []
    for e in evs[:200]:
        sus = e.get("suspect") or {}
        who = f"{sus.get('kind', '-')}:{sus.get('name', '-')}"
        if sus.get("lag_ratio"):
            who += f" x{sus['lag_ratio']}"
        rows.append([e.get("chunk", "-"), e.get("anomaly") or "?",
                     e.get("severity") or "?", who,
                     json.dumps(e.get("evidence") or {},
                                sort_keys=True)[:64]])
    return (f"run-doctor findings ({len(evs)}) — verdict DEGRADED:\n"
            + _table(rows, ["chunk", "anomaly", "severity", "suspect",
                            "evidence"]))


def render(path: str) -> str:
    manifest, events = obs_trace.read_log(path)
    by_kind: dict = {}
    for e in events:
        by_kind.setdefault(e.get("kind"), []).append(e)
    out = [_manifest_block(manifest)]

    if manifest.get("tool") == "supervisor":
        # a supervisor log has no chunks to attribute — render the
        # restart trail, then the generic summary/heartbeat blocks
        out.append(_supervisor_trail_block(events))
        summary = (by_kind.get("summary") or [None])[-1]
        if summary:
            bits = [f"{k}={summary[k]}" for k in
                    ("ok", "attempts", "restarts", "gave_up",
                     "resumed_from_step") if k in summary]
            out.append("supervisor summary: " + "  ".join(bits))
        errors = by_kind.get("error") or []
        for e in errors:
            out.append(f"ERROR: {e.get('error')}")
        if not summary and not errors:
            out.append("(no summary event — the supervisor is live or "
                       "was killed; the trail above is the state)")
        return "\n\n".join(out)

    cost = (by_kind.get("costmodel") or [None])[-1]
    summary = (by_kind.get("summary") or [None])[-1]
    if cost:
        out.append(_attribution_block(cost, summary))
        cc = cost.get("budget_crosscheck")
        if cc:
            out.append(
                f"budget cross-check: slab operands "
                f"{_fmt_bytes(cc['slab_operand_bytes'])} vs budget.py "
                f"{_fmt_bytes(cc['budget_bytes'])} — "
                + ("MATCH" if cc.get("match") else "MISMATCH (models "
                   "drifted; fix before trusting either)"))
    profs = by_kind.get("profile") or []
    if profs:
        out.append(_profile_block(profs[-1], cost))
    if summary:
        out.append(_runtime_block(summary))

    beats = by_kind.get("heartbeat") or []
    if beats:
        out.append("heartbeat verdicts:\n" + _table(
            [[f"{b['t']:.0f}", b.get("verdict"),
              (b.get("detail") or "")[:70]] for b in beats],
            ["t", "verdict", "detail"]))
    # coupled-group vocabulary (PR 18/19): per-group policy decisions,
    # per-group throughput, the migration trail, group-named health
    for kind, block in (("policy_group", _policy_groups_block),
                        ("group_chunk", _group_chunks_block),
                        ("migrate", _migrate_block)):
        evs = by_kind.get(kind) or []
        if evs:
            out.append(block(evs))
    ghealth = [h for h in (by_kind.get("health") or [])
               if h.get("group")]
    if ghealth:
        out.append(_group_health_block(ghealth))
    anomalies = by_kind.get("anomaly") or []
    if anomalies:
        out.append(_anomaly_block(anomalies))
    labels = (by_kind.get("label") or []) + (by_kind.get("rung") or [])
    if labels:
        rows = []
        for e in labels[:200]:
            rows.append([
                e.get("label") or "x".join(map(str, e.get("mesh", []))),
                e.get("status") or e.get("mode") or "",
                e.get("mcells_per_s") if e.get("mcells_per_s")
                is not None else "-",
                (e.get("error") or "")[:48]])
        out.append(f"records ({len(labels)}):\n"
                   + _table(rows, ["label/mesh", "status", "Mcells/s",
                                   "error"]))
    results = by_kind.get("result") or []  # bench.py's headline record
    for e in results:
        out.append("bench result: " + "  ".join(
            f"{k}={e[k]}" for k in ("metric", "value", "unit",
                                    "vs_baseline", "compute", "backend")
            if k in e))
    errors = by_kind.get("error") or []
    for e in errors:
        out.append(f"ERROR: {e.get('error')}")
    if not summary and not errors and not results:
        out.append("(no summary event — the run is live or died without "
                   "an epilogue; heartbeat verdicts above say which)")
    return "\n\n".join(out)


def render_bundle(bundle) -> str:
    """Render a flight-recorder bundle (obs/flightrec.py): the whole
    post-mortem from ONE self-contained file — no telemetry dir, no
    ledger, no live process needed."""
    head = (f"flight bundle  schema={bundle.get('schema')}  "
            f"reason={bundle.get('reason')}  "
            f"verdict={bundle.get('verdict')}")
    out = [head, _manifest_block(bundle["manifest"])]
    events = bundle.get("events") or []
    kinds: dict = {}
    for e in events:
        k = e.get("kind") or "?"
        kinds[k] = kinds.get(k, 0) + 1
    out.append(f"ring: last {len(events)} of "
               f"{bundle.get('events_seen')} events  "
               + "  ".join(f"{k}={v}" for k, v in sorted(kinds.items())))
    anomalies = bundle.get("anomalies") or []
    if anomalies:
        out.append(_anomaly_block(anomalies))
    spans = bundle.get("open_spans") or []
    if spans:
        out.append("open spans at capture (outermost first):\n" + _table(
            [[s.get("span_id") or "-", s.get("name") or "-",
              f"{s.get('start', 0):.0f}"] for s in spans],
            ["span", "name", "start"]))
    best = bundle.get("best_known")
    if best:
        out.append("ledger best_known for this label: "
                   f"{best.get('value')} {best.get('unit')} "
                   f"(source {best.get('source')})")
    env = bundle.get("env") or {}
    if env:
        out.append("env: " + "  ".join(f"{k}={v}" for k, v in
                                       sorted(env.items())))
    sib = bundle.get("sibling_events") or {}
    for src in sorted(sib):
        recs = [e for e in sib[src] if isinstance(e, dict)]
        skinds: dict = {}
        for e in recs:
            k = e.get("kind") or "?"
            skinds[k] = skinds.get(k, 0) + 1
        out.append(f"sibling {src} (tail): {len(recs)} events  "
                   + "  ".join(f"{k}={v}"
                               for k, v in sorted(skinds.items())))
    errors = [e for e in events if e.get("kind") == "error"]
    for e in errors:
        out.append(f"ERROR: {e.get('error')}")
    return "\n\n".join(out)


def _ledger_summary(path) -> str:
    """``--ledger``: campaign state in one command.

    The ``best_known`` table per label x backend (structurally unable
    to surface a quarantined row) plus the quarantine counts and
    reasons — what used to take hand-grepping benchmarks/ledger.jsonl.
    """
    from mpi_cuda_process_tpu.obs import ledger as ledger_lib

    path = path or ledger_lib.default_ledger_path()
    rows = ledger_lib.read_rows(path)
    best = ledger_lib.best_known(rows)
    quarantined = [r for r in rows if r.get("status") == "quarantined"]
    out = [f"ledger {path}: {len(rows)} rows "
           f"({len(quarantined)} quarantined), "
           f"{len(best)} best-known baselines"]
    trows = []
    for bk in sorted(best):
        r = best[bk]
        q = sum(1 for row in quarantined
                if ledger_lib.baseline_key(row) == bk)
        ts = r.get("measured_at")
        trows.append([bk, r["value"], r["unit"],
                      time.strftime("%Y-%m-%d",
                                    time.localtime(ts)) if ts else "-",
                      q, r["source"][:44]])
    if trows:
        out.append(_table(trows, ["label|backend", "best", "unit",
                                  "measured", "quarantined", "source"]))
    reasons: dict = {}
    for r in quarantined:
        key = str(r.get("quarantine") or "?").split(":")[0]
        reasons[key] = reasons.get(key, 0) + 1
    if reasons:
        out.append("quarantine reasons:\n" + "\n".join(
            f"  {n:4d}  {reason}"
            for reason, n in sorted(reasons.items(),
                                    key=lambda kv: -kv[1])))
    return "\n\n".join(out)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("log", nargs="?", default=None,
                    help="telemetry JSONL path (or, with --ledger, a "
                         "ledger path; defaults to the committed "
                         "benchmarks/ledger.jsonl there)")
    ap.add_argument("--check", action="store_true",
                    help="validate the manifest and every event against "
                         "the shared schema; exit nonzero on any "
                         "invalid record (the tier-1 smoke mode)")
    ap.add_argument("--ledger", action="store_true",
                    help="summary mode for a campaign ledger: the "
                         "best_known table per label x backend plus "
                         "quarantine counts + reasons")
    a = ap.parse_args(argv)
    if a.ledger:
        try:
            print(_ledger_summary(a.log))
        except (ValueError, OSError) as e:
            print(f"obs_report --ledger: {e}", file=sys.stderr)
            return 1
        return 0
    if not a.log:
        ap.error("a telemetry JSONL path is required (or use --ledger)")
    from mpi_cuda_process_tpu.obs import flightrec as flightrec_lib
    if flightrec_lib.is_bundle_file(a.log):
        # a flight-recorder bundle IS the post-mortem: render it even
        # when the telemetry dir it came from no longer exists
        try:
            bundle = flightrec_lib.read_bundle(a.log)
        except (ValueError, OSError) as e:
            print(f"obs_report: bad bundle: {e}", file=sys.stderr)
            return 1
        if a.check:
            try:
                flightrec_lib.validate_bundle(bundle)
            except ValueError as e:
                print(f"obs_report --check: INVALID: {e}",
                      file=sys.stderr)
                return 1
            print("obs_report --check: ok (flight bundle, "
                  f"reason={bundle.get('reason')}, "
                  f"{len(bundle.get('events') or [])} events)")
        print(render_bundle(bundle))
        return 0
    if a.check:
        try:
            manifest, events = obs_trace.validate_log(a.log)
        except (ValueError, OSError) as e:
            print(f"obs_report --check: INVALID: {e}", file=sys.stderr)
            return 1
        print(f"obs_report --check: ok (tool={manifest['tool']}, "
              f"schema={manifest['schema']}, {len(events)} events)")
    print(render(a.log))
    return 0


if __name__ == "__main__":
    sys.exit(main())
