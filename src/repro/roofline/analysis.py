"""Roofline ceilings of the ConvCoTM eval paths against the TPU v5e
per-chip peaks (bf16 compute 197 TFLOP/s, HBM 819 GB/s)."""

from __future__ import annotations

from typing import Any, Dict, Optional

__all__ = ["tm_path_roofline"]

PEAK_FLOPS = 197e12
HBM_BW = 819e9


def tm_path_roofline(
    config,
    path_name: str,
    batch: int = 1,
    *,
    n_active: Optional[int] = None,
    measured_cls_per_s: Optional[float] = None,
) -> Dict[str, Any]:
    """Roofline ceiling for one ConvCoTM eval-path batch on the target HW.

    Uses the analytic per-batch op/byte model from
    ``roofline.flops.tm_serve_costs`` against the v5e constants:

      ``ceiling_cls_per_s`` = batch / max(ops / peak, bytes / bw)

    Word/bit ops are charged at the bf16 peak rate — optimistic for VPU
    integer work, which makes the ceiling a true upper bound and the
    achieved fraction conservative.  With ``measured_cls_per_s`` the
    result also carries ``achieved_fraction`` (measured / ceiling) —
    the column benchmark rows report so a path's headroom is visible
    next to its throughput (EXPERIMENTS.md §Sparsity).
    """
    from repro.roofline.flops import tm_serve_costs

    costs = tm_serve_costs(config, path_name, batch, n_active=n_active)
    compute_s = costs["ops"] / PEAK_FLOPS
    memory_s = costs["bytes"] / HBM_BW
    bound_s = max(compute_s, memory_s)
    out: Dict[str, Any] = {
        "path": path_name,
        "batch": batch,
        "ops": costs["ops"],
        "bytes": costs["bytes"],
        "clauses_evaluated": costs["clauses_evaluated"],
        "compute_s": compute_s,
        "memory_s": memory_s,
        "bound": "compute" if compute_s >= memory_s else "memory",
        "ceiling_cls_per_s": batch / bound_s if bound_s > 0 else float("inf"),
    }
    if measured_cls_per_s is not None:
        out["measured_cls_per_s"] = measured_cls_per_s
        out["achieved_fraction"] = (
            measured_cls_per_s / out["ceiling_cls_per_s"]
            if out["ceiling_cls_per_s"] > 0
            else 0.0
        )
    return out
