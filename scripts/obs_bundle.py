#!/usr/bin/env python3
"""On-demand flight-recorder bundle from a telemetry log.

    python scripts/obs_bundle.py PATH.jsonl [-o OUT]

Reads one telemetry JSONL log and writes the self-contained post-mortem
bundle (``obs/flightrec.py``) next to it — manifest, last-N events,
anomaly findings, replayed verdict, ledger ``best_known`` for the
label, env snapshot.  The bundle is what you hand to a fresh session
(or attach to a round report) when the telemetry dir itself won't
survive: ``scripts/obs_report.py BUNDLE`` renders it, ``--check``
validates it.
"""

import argparse
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from mpi_cuda_process_tpu.obs import flightrec  # noqa: E402


def main(argv=None) -> int:
    p = argparse.ArgumentParser(
        description="emit a self-contained flight-recorder bundle "
                    "from a telemetry log")
    p.add_argument("path", help="telemetry JSONL log")
    p.add_argument("-o", "--out", default=None,
                   help="bundle path (default: <log>.bundle.json)")
    p.add_argument("--reason", default="on-demand",
                   help="reason recorded in the bundle")
    a = p.parse_args(argv)
    try:
        out = flightrec.bundle_from_log(
            a.path, reason=a.reason, out_path=a.out)
    except (OSError, ValueError) as e:
        print(f"obs_bundle: {e}", file=sys.stderr)
        return 2
    b = flightrec.read_bundle(out)
    print(f"wrote {out}")
    print(f"  verdict={b['verdict']} events={len(b['events'])} "
          f"anomalies={len(b['anomalies'])}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
