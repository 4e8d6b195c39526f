"""Analytic op and HBM-byte costs of one ConvCoTM eval-path batch.

The formulas mirror ``kernels/ref.py`` / ``serve/paths.py`` path by path;
``roofline/analysis.py:tm_path_roofline`` turns them into a v5e ceiling.
"""

from __future__ import annotations

from typing import Dict

__all__ = ["tm_serve_costs"]

#: Paths whose clause axis is the ACTIVE pool (empty clauses pruned by
#: serve.servable.analyze_sparsity) rather than the full pool.
TM_SPARSE_PATHS = ("sparse", "fused_sparse", "matmul_sparse")

#: Paths whose clause outputs never round-trip through HBM (class sums
#: computed in-register on the last patch chunk).
TM_FUSED_PATHS = ("fused", "fused_sparse")


def tm_serve_costs(
    config, path_name: str, batch: int = 1, *, n_active=None
) -> Dict[str, float]:
    """Analytic op/byte costs of one ConvCoTM eval-path batch.

    ``config`` is a ``repro.core.cotm.CoTMConfig`` (only geometry fields
    are read); ``n_active`` is the active-clause count for the sparse
    paths (defaults to the full pool — i.e. a model with no empty
    clauses).  Returns a dict with:

      * ``ops``   — elementary operations executed: MXU flops for the
        matmul paths, word-level bit operations (and/or/not/popcount/
        compare) for the packed paths, byte-level AND/compare for dense.
        One op = one lane-element operation, the same accounting XLA's
        cost model uses for integer vectors.
      * ``bytes`` — HBM floor traffic: literal stream in, model image
        (read once per batch — it is VMEM-resident across patch chunks),
        clause-output round-trip for non-fused paths, class sums out.

    The formulas mirror ``kernels/ref.py`` / ``serve/paths.py`` 1:1 per
    path; the roofline ceilings derived from them live in
    ``roofline/analysis.py`` (``tm_path_roofline``) and annotate the
    benchmark rows in ``benchmarks/bench_serve.py``.
    """
    spec = config.patch
    b = float(batch)
    p = float(spec.n_patches)        # patches per image
    lit = float(spec.n_literals)     # 2o dense literal bits
    w = float(spec.n_words)          # packed uint32 words per patch
    c = float(config.n_clauses)
    m = float(config.n_classes)
    c_a = c if n_active is None else float(n_active)
    if path_name in TM_SPARSE_PATHS:
        c_eval = c_a
    else:
        c_eval = c

    sums_ops = 2.0 * b * c_eval * m          # Eq. (3) int8 dot
    or_ops = b * c_eval * p                  # sequential OR (Eq. 6)

    if path_name in ("dense",):
        ops = 2.0 * b * p * c * lit + or_ops + sums_ops     # AND + reduce
        lit_bytes = b * p * lit                              # uint8 stream
        model_bytes = c * lit + c + m * c
    elif path_name in ("matmul", "matmul_sparse"):
        # int8 violation-count matmul: 2*B*P*C*2o MACs + zero-compare.
        ops = 2.0 * b * p * c_eval * lit + b * p * c_eval + or_ops + sums_ops
        lit_bytes = b * p * lit
        model_bytes = c_eval * lit + m * c_eval
    elif path_name in ("bitpacked", "kernel", "fused", "sparse", "fused_sparse"):
        # Word ops per (patch, clause): not/and(+popcount)/compare ~ 3.
        ops = 3.0 * b * p * c_eval * w + or_ops + sums_ops
        lit_bytes = b * p * w * 4.0                          # uint32 stream
        model_bytes = c_eval * w * 4.0 + m * c_eval
        if path_name in ("bitpacked", "kernel"):
            model_bytes += c                                 # nonempty mask
    else:
        raise ValueError(f"no cost model for eval path {path_name!r}")

    out_bytes = b * m * 4.0                                  # int32 class sums
    fired_bytes = 0.0 if path_name in TM_FUSED_PATHS else 2.0 * b * c_eval
    return {
        "ops": ops,
        "bytes": lit_bytes + model_bytes + fired_bytes + out_bytes,
        "lit_bytes": lit_bytes,
        "model_bytes": model_bytes,
        "clauses_evaluated": c_eval,
    }
