"""JSONL event recorder + provenance-stamped run manifests.

The repo's runtime evidence used to live as one-off ``perf_counter``
pairs scattered across cli.py/bench.py and ad-hoc wedge scripts; three
consecutive zero scoreboards (BENCH_r03-r05) could not say *why* because
no run left a machine-readable trail.  This module is the trail: every
entry point (cli ``--telemetry``, bench.py, benchmarks/measure.py,
benchmarks/scaling.py) opens a trace, writes ONE manifest line — a
versioned, validated record of what ran (config, flags), on what
(backend, device kind/count), and from which code (git sha,
BUILDER_REV, jax version) — then appends events (chunk timings, static
cost counters, heartbeat verdicts, a final summary) as JSON lines.

Design constraints:

* **Zero ops in the jitted step.**  Nothing here touches jax tracing:
  events are written host-side at chunk boundaries only (pinned by
  ``tests/test_obs.py::test_telemetry_adds_zero_ops_to_jitted_step``).
* **One schema for all four tools** — the validator below is the single
  definition; ``scripts/obs_report.py --check`` and the tier-1 smoke run
  it, so a tool drifting off-schema fails the gate, not a reader three
  rounds later.
* **Thread-safe writes** (the heartbeat thread shares the writer).
* **Never load-bearing**: telemetry failures must not kill a run;
  callers wrap session setup in try/except (the writer itself only
  raises on programmer errors like an invalid manifest).
"""

from __future__ import annotations

import json
import os
import re
import socket
import subprocess
import threading
import time
from typing import Any, Dict, List, Optional, Tuple

# Schema 2 (round 14) adds multi-host provenance — process_index /
# process_count / hostname — the fields a future aggregator needs to
# merge per-host status pages (ROADMAP item 5 prep).  The validator
# accepts BOTH revisions: new manifests are written at SCHEMA_VERSION,
# old schema-1 logs (without the host fields) still parse, and the
# "bump the reader, never the record" rule holds.
SCHEMA_VERSION = 2
ACCEPTED_SCHEMAS = (1, 2)

_REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

# provenance keys every manifest must carry, with their required types
# (builder_rev may be None on a checkout without the campaign harness)
_PROVENANCE_TYPES = {
    "git_sha": str,
    "jax_version": str,
    "backend": str,
    "device_kind": str,
    "device_count": int,
    "framework_version": str,
}

# schema-2 additions: REQUIRED on schema-2 manifests, absent on schema-1
# (type-checked when a schema-1 writer chose to include them anyway)
_PROVENANCE_V2_TYPES = {
    "process_index": int,
    "process_count": int,
    "hostname": str,
}


def default_telemetry_dir() -> str:
    """Where tools drop event logs when no path is given.

    ``OBS_TELEMETRY_DIR`` overrides (tests point it at a tmpdir); the
    default is ``<repo>/.telemetry`` next to ``.bench_cache.json``.
    """
    return os.environ.get("OBS_TELEMETRY_DIR") or \
        os.path.join(_REPO, ".telemetry")


def _git_sha() -> str:
    try:
        out = subprocess.run(
            ["git", "rev-parse", "HEAD"], cwd=_REPO,
            capture_output=True, text=True, timeout=10)
        if out.returncode == 0 and out.stdout.strip():
            return out.stdout.strip()
    except Exception:  # noqa: BLE001 — provenance is best-effort
        pass
    return "unknown"


def _builder_rev() -> Optional[int]:
    """The measurement campaign's BUILDER_REV, parsed statically.

    Parsed from benchmarks/measure.py text rather than imported: the
    campaign harness is not a package, and importing it would drag its
    jax-at-module-scope setup into every manifest write.
    """
    try:
        with open(os.path.join(_REPO, "benchmarks", "measure.py")) as fh:
            m = re.search(r"^BUILDER_REV = (\d+)", fh.read(), re.M)
        return int(m.group(1)) if m else None
    except Exception:  # noqa: BLE001
        return None


# The device identity of a launcher's manifest: a process that starts
# chip children never initializes a backend itself (the chip belongs to
# one process at a time), so it leaves the identity unset — the
# children's own manifests carry it.
UNSET = "unset"


def provenance(launcher: bool = False) -> Dict[str, Any]:
    """The code+hardware identity block stamped into every manifest.

    ``launcher=True``: touch no jax backend (device fields ``unset``)."""
    import jax

    from .. import __version__

    if launcher:
        backend, device_kind, device_count = UNSET, UNSET, 0
        process_index, process_count = 0, 1
    else:
        backend = jax.default_backend()
        devs = jax.devices()
        device_kind, device_count = devs[0].device_kind, len(devs)
        # the multi-host identity (schema 2): which process of how many
        # wrote this manifest — what lets an aggregator merge per-host
        # status pages instead of guessing from filenames
        process_index = int(jax.process_index())
        process_count = int(jax.process_count())
    try:
        hostname = socket.gethostname() or "unknown"
    except Exception:  # noqa: BLE001
        hostname = "unknown"
    return {
        "git_sha": _git_sha(),
        "builder_rev": _builder_rev(),
        "jax_version": jax.__version__,
        "backend": backend,
        "device_kind": device_kind,
        "device_count": device_count,
        "process_index": process_index,
        "process_count": process_count,
        "hostname": hostname,
        "framework_version": __version__,
    }


def build_manifest(tool: str, run: Dict[str, Any], launcher: bool = False,
                   **extra: Any) -> Dict[str, Any]:
    """Assemble and validate a manifest record.

    ``tool`` names the emitting entry point (cli/bench/measure/scaling);
    ``run`` is its config dict (the full RunConfig for the CLI, the
    harness arguments for the benchmark tools).  ``extra`` lands at the
    top level (e.g. ``mesh_devices``).  ``launcher``: see
    :func:`provenance`.
    """
    m: Dict[str, Any] = {
        "schema": SCHEMA_VERSION,
        "kind": "manifest",
        "tool": tool,
        "created_at": time.time(),
        "run": dict(run),
        "provenance": provenance(launcher),
    }
    m.update(extra)
    validate_manifest(m)
    return m


def validate_manifest(m: Any) -> Dict[str, Any]:
    """Raise ValueError listing EVERY problem; return ``m`` when valid."""
    problems: List[str] = []
    if not isinstance(m, dict):
        raise ValueError(f"manifest must be a dict, got {type(m).__name__}")
    if m.get("schema") not in ACCEPTED_SCHEMAS:
        problems.append(
            f"schema must be one of {ACCEPTED_SCHEMAS} "
            f"(got {m.get('schema')!r}); bump the reader, never the record")
    if m.get("kind") != "manifest":
        problems.append(f"kind must be 'manifest' (got {m.get('kind')!r})")
    if not isinstance(m.get("tool"), str) or not m.get("tool"):
        problems.append(f"tool must be a nonempty str (got {m.get('tool')!r})")
    if not isinstance(m.get("created_at"), (int, float)) \
            or m.get("created_at", 0) <= 0:
        problems.append(
            f"created_at must be a positive unix time "
            f"(got {m.get('created_at')!r})")
    if not isinstance(m.get("run"), dict):
        problems.append(f"run must be a dict (got {type(m.get('run')).__name__})")
    prov = m.get("provenance")
    if not isinstance(prov, dict):
        problems.append("provenance must be a dict")
    else:
        for key, typ in _PROVENANCE_TYPES.items():
            if not isinstance(prov.get(key), typ):
                problems.append(
                    f"provenance.{key} must be {typ.__name__} "
                    f"(got {prov.get(key)!r})")
        # schema 2 requires the multi-host identity; a schema-1 manifest
        # predates it (still parses), but when present the types bind
        for key, typ in _PROVENANCE_V2_TYPES.items():
            present = key in prov
            if m.get("schema") == 2 and not present:
                problems.append(
                    f"provenance.{key} is required at schema 2 "
                    f"({typ.__name__})")
            elif present and not isinstance(prov.get(key), typ):
                problems.append(
                    f"provenance.{key} must be {typ.__name__} "
                    f"(got {prov.get(key)!r})")
        if prov.get("device_count", 0) < 1 and \
                prov.get("backend") != UNSET:
            problems.append("provenance.device_count must be >= 1 "
                            "(0 only on a launcher's unset identity)")
        if "process_count" in prov and \
                isinstance(prov.get("process_count"), int) and \
                prov["process_count"] < 1:
            problems.append("provenance.process_count must be >= 1")
        br = prov.get("builder_rev", None)
        if br is not None and not isinstance(br, int):
            problems.append(
                f"provenance.builder_rev must be int or null (got {br!r})")
    if problems:
        raise ValueError("invalid manifest: " + "; ".join(problems))
    return m


def validate_event(e: Any) -> Dict[str, Any]:
    """Raise ValueError on a malformed event record; return it when valid."""
    if not isinstance(e, dict):
        raise ValueError(f"event must be a dict, got {type(e).__name__}")
    problems: List[str] = []
    if e.get("schema") not in ACCEPTED_SCHEMAS:
        problems.append(f"schema must be one of {ACCEPTED_SCHEMAS} "
                        f"(got {e.get('schema')!r})")
    kind = e.get("kind")
    if not isinstance(kind, str) or not kind:
        problems.append(f"kind must be a nonempty str (got {kind!r})")
    elif kind == "manifest":
        problems.append("'manifest' is reserved for the first record")
    if not isinstance(e.get("t"), (int, float)) or e.get("t", 0) <= 0:
        problems.append(f"t must be a positive unix time (got {e.get('t')!r})")
    if problems:
        raise ValueError("invalid event: " + "; ".join(problems))
    return e


class TraceWriter:
    """Append-only JSONL writer: one manifest first, then events.

    Thread-safe (the heartbeat thread writes verdict events while the
    main thread writes chunks).  Values that are not JSON-native are
    stringified (``default=str``) so a dtype or Path in a config dict
    never kills a run.
    """

    def __init__(self, path: str):
        self.path = path
        parent = os.path.dirname(os.path.abspath(path))
        os.makedirs(parent, exist_ok=True)
        self._fh = open(path, "w")
        self._lock = threading.Lock()
        self._wrote_manifest = False
        self.last_event_t = time.monotonic()
        # in-process taps: each callable sees every record (manifest and
        # events, heartbeat thread included) right after it hits disk.
        # The flight recorder (obs/flightrec.py) rides here so its ring
        # holds exactly what the log holds, without tailing our own file.
        # A mirror raising must never kill the write path.
        self.mirrors: List[Any] = []

    def write_manifest(self, manifest: Dict[str, Any]) -> None:
        validate_manifest(manifest)
        with self._lock:
            if self._wrote_manifest:
                raise ValueError("manifest already written")
            self._write(manifest)
            self._wrote_manifest = True

    def event(self, kind: str, **payload: Any) -> Dict[str, Any]:
        rec = {"schema": SCHEMA_VERSION, "kind": kind, "t": time.time()}
        rec.update(payload)
        validate_event(rec)
        with self._lock:
            if not self._wrote_manifest:
                raise ValueError("write the manifest before any event")
            self._write(rec)
        return rec

    def _write(self, rec: Dict[str, Any]) -> None:
        if self._fh is None:
            return  # closed: drop silently (late heartbeat verdicts)
        self._fh.write(json.dumps(rec, default=str) + "\n")
        self._fh.flush()
        self.last_event_t = time.monotonic()
        for mirror in self.mirrors:
            try:
                mirror(rec)
            except Exception:  # noqa: BLE001 — taps are never load-bearing
                pass

    def close(self) -> None:
        with self._lock:
            if self._fh is not None:
                self._fh.close()
                self._fh = None

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()


def read_log(path: str) -> Tuple[Dict[str, Any], List[Dict[str, Any]]]:
    """Parse a JSONL trace: ``(manifest, events)``.  No validation."""
    manifest: Optional[Dict[str, Any]] = None
    events: List[Dict[str, Any]] = []
    with open(path) as fh:
        for line in fh:
            line = line.strip()
            if not line:
                continue
            rec = json.loads(line)
            if manifest is None:
                manifest = rec
            else:
                events.append(rec)
    if manifest is None:
        raise ValueError(f"{path}: empty event log")
    return manifest, events


def validate_log(path: str) -> Tuple[Dict[str, Any], List[Dict[str, Any]]]:
    """``read_log`` + schema validation of the manifest and every event."""
    manifest, events = read_log(path)
    try:
        validate_manifest(manifest)
    except ValueError as e:
        raise ValueError(f"{path}: first record: {e}") from None
    for i, e in enumerate(events):
        try:
            validate_event(e)
        except ValueError as err:
            raise ValueError(f"{path}: event {i}: {err}") from None
    return manifest, events


class LogTail:
    """Incremental JSONL reader: ``poll()`` returns newly appended records.

    The supervisor's view of a live child: it tails the child's
    telemetry log between polls, consuming only COMPLETE lines (a child
    SIGKILLed mid-write leaves a partial last line, which must stay
    unconsumed until — if ever — its terminator lands, never be parsed
    as garbage).  Reads in binary and tracks a byte offset so a decode
    boundary can't desync the position.  A missing file (child not yet
    started, or dead before its first event) yields no records rather
    than raising; malformed complete lines are counted and skipped —
    the watcher must survive anything a dying process leaves behind.

    Truncation/rotation is detected by size: a file now SHORTER than
    the consumed offset was rewritten from the top (a supervisor
    restart reuses the telemetry path — TraceWriter opens ``"w"`` — or
    a log rotation swapped the inode), so the tail restarts at byte 0
    instead of sticking forever past the new EOF.  ``truncations``
    counts the resets.  A rewrite that has already grown PAST the old
    offset is indistinguishable from an append by size alone and is
    not detected — every writer in this repo starts a fresh file
    empty, so the shrink is observable at the next poll.
    """

    def __init__(self, path: str):
        self.path = path
        self.malformed = 0
        self.truncations = 0
        self._pos = 0

    def poll(self) -> List[Dict[str, Any]]:
        try:
            fh = open(self.path, "rb")
        except OSError:
            return []
        with fh:
            try:
                size = os.fstat(fh.fileno()).st_size
            except OSError:
                size = None
            if size is not None and size < self._pos:
                self._pos = 0
                self.truncations += 1
            fh.seek(self._pos)
            buf = fh.read()
        end = buf.rfind(b"\n")
        if end < 0:
            return []
        self._pos += end + 1
        out: List[Dict[str, Any]] = []
        for line in buf[:end + 1].splitlines():
            line = line.strip()
            if not line:
                continue
            try:
                rec = json.loads(line.decode("utf-8", errors="replace"))
            except ValueError:
                self.malformed += 1
                continue
            if isinstance(rec, dict):
                out.append(rec)
            else:
                self.malformed += 1
        return out
