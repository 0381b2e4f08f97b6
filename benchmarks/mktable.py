"""Regenerate BASELINE.md's measured table from the campaign record.

Reads ``benchmarks/results_r05.json`` (or ``--in FILE``) and prints the
markdown table body: one row per successful label, grouped by stencil
family then grid size, with the ``--compute auto`` policy pick bolded via
the live policy tables (``plan.auto_path``) — so the measured table and
the shipping policy can never silently disagree.  Errored/suspect labels
are listed beneath the table with their reasons (a pending row is
information too).

Usage: python benchmarks/mktable.py [--in FILE]
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from mpi_cuda_process_tpu import plan  # noqa: E402


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--in", dest="inp", default=os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "results_r05.json"))
    args = ap.parse_args()
    with open(args.inp) as fh:
        results = json.load(fh)

    rows, problems = [], []
    for label, rec in sorted(results.items()):
        stencil = rec.get("stencil")
        grid = tuple(rec.get("grid") or ())
        dtype = rec.get("dtype")
        compute = rec.get("compute", "?")
        if rec.get("error"):
            problems.append((label, rec["error"].splitlines()[0][:120]))
            continue
        if rec.get("suspect"):
            problems.append((label, "SUSPECT: " + rec.get(
                "error", "noise-floor / cross-check pending")[:100]))
            continue
        if stencil is None:
            # calibration rows (copy_*): report as GB/s context
            mc = rec.get("mcells_per_s")
            if mc:
                gbs = mc * 1e6 * 2 * 4 / 1e9
                rows.append((label, f"| {label} (calibration) | copy | "
                             f"{mc:,.0f} | {gbs:.0f} GB/s |"))
            continue
        mc = rec.get("mcells_per_s")
        ms = rec.get("ms_per_step")
        if mc is None:
            continue
        gstr = "×".join(str(g) for g in grid)
        dshort = {"float32": "f32", "bfloat16": "bf16",
                  None: "i32" if stencil == "life" else "f32"}.get(
            dtype, dtype)
        pick = plan.auto_path(stencil, grid, dtype)
        cstr = f"**{compute}**" if compute == pick else compute
        mcstr = f"**{mc:,.0f}**" if compute == pick else f"{mc:,.0f}"
        rows.append((label,
                     f"| {stencil} {gstr} {dshort} | {cstr} | {mcstr} | "
                     f"{ms} |"))

    print("| Config | compute | Mcells/s | ms/step |")
    print("|---|---|---:|---:|")
    for _, row in rows:
        print(row)
    if problems:
        print("\nPending / errored / suspect labels:\n")
        for label, why in problems:
            print(f"- `{label}`: {why}")


if __name__ == "__main__":
    main()
