"""ConvCoTM core: the paper's contribution as composable JAX modules."""

from repro.core.booleanize import (
    adaptive_gaussian_booleanize,
    booleanize,
    thermometer_encode,
    threshold_booleanize,
)
from repro.core.clauses import (
    argmax_predict,
    class_sums,
    clause_nonempty,
    eval_clauses_bitpacked,
    eval_clauses_dense,
    eval_clauses_matmul,
    patch_clause_outputs,
    patch_clause_outputs_matmul,
)
from repro.core.composites import CompositeConfig, CompositeModel, composite_vote
from repro.core.cotm import CoTMConfig, CoTMModel, infer, infer_packed, init_model
from repro.core.ingress import (
    IngressSpec,
    apply_booleanize,
    apply_ingress,
    device_ingress,
    raw_trailing_shape,
)
from repro.core.model_io import model_size_bytes, pack_model, unpack_model
from repro.core.patches import (
    PatchSpec,
    extract_patch_features,
    make_literals,
    pack_bits,
    unpack_bits,
)
from repro.core.train import (
    accuracy,
    batch_literals,
    update_batch,
    update_batch_literals,
)

__all__ = [
    "CoTMConfig",
    "CoTMModel",
    "CompositeConfig",
    "CompositeModel",
    "IngressSpec",
    "PatchSpec",
    "accuracy",
    "adaptive_gaussian_booleanize",
    "apply_booleanize",
    "apply_ingress",
    "argmax_predict",
    "batch_literals",
    "booleanize",
    "class_sums",
    "clause_nonempty",
    "composite_vote",
    "device_ingress",
    "eval_clauses_bitpacked",
    "eval_clauses_dense",
    "eval_clauses_matmul",
    "extract_patch_features",
    "infer",
    "infer_packed",
    "init_model",
    "make_literals",
    "model_size_bytes",
    "pack_bits",
    "pack_model",
    "patch_clause_outputs",
    "patch_clause_outputs_matmul",
    "raw_trailing_shape",
    "thermometer_encode",
    "threshold_booleanize",
    "unpack_bits",
    "unpack_model",
    "update_batch",
    "update_batch_literals",
]
