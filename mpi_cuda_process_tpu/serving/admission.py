"""Admission control: price a size class before accepting a job.

``utils/budget.py`` already owns the arithmetic (per-device peak live
bytes for every execution strategy, halo/fuse/ensemble/exchange
transients included).  Admission calls it with the *class* config at
the *target member capacity* — the resident program the job would
actually join — and converts a ``ValueError`` breakdown into a
structured :class:`AdmissionError` instead of ever attempting a build
that would OOM the mesh.

The controller prices against the backend-reported HBM by default
(``budget.device_hbm_bytes``); tests and capacity planning pass an
explicit ``hbm_bytes`` so rejection is provable on any backend.
"""

from __future__ import annotations

from typing import Any, Dict, Optional

from .. import plan
from ..config import RunConfig

__all__ = ["AdmissionError", "AdmissionController"]


class AdmissionError(ValueError):
    """A job was refused before touching the mesh.

    ``reason`` is machine-readable (``"over_budget"`` |
    ``"unsupported"``); ``detail`` carries the budget arithmetic or
    the offending field — the structured reject the scheduler also
    emits as a ``scheduler`` event with ``op="reject"``.
    """

    def __init__(self, reason: str, message: str,
                 detail: Optional[Dict[str, Any]] = None):
        super().__init__(message)
        self.reason = reason
        self.detail = detail or {}


class AdmissionController:
    """Budget-priced yes/no for (class config, capacity) pairs."""

    def __init__(self, hbm_bytes: Optional[int] = None):
        self.hbm_bytes = hbm_bytes

    def price(self, build_cfg: RunConfig) -> Dict[str, Any]:
        """Estimated peak bytes/device for the class build config.

        Returns ``{"total_bytes", "parts", "hbm_bytes"}``; pure host
        arithmetic — nothing compiles, nothing allocates.
        """
        from ..utils import budget

        hbm = self.hbm_bytes
        if hbm is None:
            hbm = budget.device_hbm_bytes()
        if build_cfg.groups:
            # a coupled job is an admissible tenant: priced per group
            # (worst group's devices are what the admission budget must
            # cover), interface transients included — the same
            # estimate_coupled_bytes the CLI's own guard uses
            from ..parallel import groups as groups_lib

            plans = groups_lib.plans_from_config(
                build_cfg.groups, build_cfg.grid,
                default_dtype=build_cfg.dtype or None)
            worst, details = budget.estimate_coupled_bytes(plans)
            worst_name, _, worst_parts = max(details, key=lambda d: d[1])
            return {"total_bytes": int(worst),
                    "parts": worst_parts,
                    "coupled_groups": [
                        {"group": name, "total_bytes": int(t)}
                        for name, t, _ in details],
                    "worst_group": worst_name,
                    "hbm_bytes": int(hbm)}
        st = plan.make_cfg_stencil(build_cfg)
        total, parts = budget.estimate_run_bytes(
            st, build_cfg.grid, mesh=build_cfg.mesh, fuse=build_cfg.fuse,
            ensemble=build_cfg.ensemble, periodic=build_cfg.periodic,
            compute=build_cfg.compute, fuse_kind=build_cfg.fuse_kind,
            overlap=build_cfg.overlap, pipeline=build_cfg.pipeline,
            exchange=build_cfg.exchange,
            ensemble_mesh=build_cfg.ensemble_mesh)
        return {"total_bytes": int(total), "parts": parts,
                "hbm_bytes": int(hbm)}

    def admit_or_raise(self, build_cfg: RunConfig) -> Dict[str, Any]:
        """Admit the class build or raise :class:`AdmissionError`.

        The refusal carries the full arithmetic: estimated bytes, the
        per-part breakdown, and the HBM it was priced against — the
        "reject with the reason, never OOM" contract.
        """
        est = self.price(build_cfg)
        if est["total_bytes"] > est["hbm_bytes"]:
            gib = est["total_bytes"] / 2**30
            cap = est["hbm_bytes"] / 2**30
            raise AdmissionError(
                "over_budget",
                f"size class at capacity {build_cfg.ensemble} needs "
                f"~{gib:.2f} GiB/device, over the {cap:.2f} GiB budget",
                detail=est)
        return est
