"""The work a frame needs, fixed by the algorithm and not by the eval path
that runs it (Tunheim et al. 2025, Algorithm 1):

  * clause checks: every nonempty clause is checked against every literal
    of every patch, one multiply-accumulate each, counted as 2 int8
    operations: ``2 * P * C' * 2o``;
  * class sums: ``2 * C' * m``;
  * booleanization: one compare per pixel for a fixed threshold; for the
    adaptive one, two separable passes of ``block_size`` multiply-adds per
    pixel plus the compare.

``C'`` is the number of nonempty clauses of the served model, so a path
that skips empty clauses cannot read above its roofline.

Bytes: the raw uint8 frame in, its int32 class sums and prediction out,
and the packed model (the include bits and int8 weights) once per step.
"""

from __future__ import annotations


def booleanize_ops(cfg: dict) -> int:
    pixels = cfg["image_y"] * cfg["image_x"]
    b = cfg["booleanize"]
    if b["method"] == "threshold":
        return pixels
    if b["method"] == "adaptive":
        return pixels * (2 * 2 * b["block_size"] + 1)
    raise ValueError(f"no work count for booleanize method {b['method']!r}")


def frame_work(cfg: dict, nonempty: int) -> dict:
    p, lits, m = cfg["n_patches"], cfg["n_literals"], cfg["n_classes"]
    ops = 2 * p * nonempty * lits + 2 * nonempty * m + booleanize_ops(cfg)
    return {
        "ops_per_frame": ops,
        "bytes_per_frame": cfg["image_y"] * cfg["image_x"] + 4 * m + 4,
        "model_bytes": -(-cfg["n_clauses"] * lits // 8) + m * cfg["n_clauses"],
    }


def least_step_s(work: dict, peaks: dict, frames: int) -> float:
    """The least time one step over ``frames`` frames can take on one chip:
    the larger of its operations over the int8 peak and its bytes over
    the memory bandwidth."""
    ops = frames * work["ops_per_frame"]
    nbytes = frames * work["bytes_per_frame"] + work["model_bytes"]
    return max(ops / peaks["int8_ops_per_s"], nbytes / peaks["hbm_bytes_per_s"])
