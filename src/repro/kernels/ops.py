"""Jit'd public wrappers around the Pallas kernels.

Handles the padding contract, picks block shapes, and moves the packed
operands into the kernels' layout (word-major literals ``[W, B, P]``,
mask columns ``[W, C, 1]``; see kernels/clause_eval.py).  The backend:

  * on a TPU the kernels compile to Mosaic, and a kernel that fails to
    compile raises — there is no silent fallback;
  * on any other backend the default is ``ref``, the pure-jnp oracle:
    XLA:CPU cannot run a Mosaic kernel;
  * ``backend='interpret'`` runs the Pallas body in the interpreter on
    CPU; only tests ask for it.

Padding safety (proved in tests/test_kernels.py):
  * patches pad with all-zero literal words  -> cannot fire any nonempty
    clause, and empty clauses are masked, so the OR is unchanged;
  * clauses pad with empty include masks + nonempty=0 -> output 0, sliced;
  * batch rows pad with zeros and are sliced off;
  * class-sum pads clauses with fired=0 columns and weight 0 columns.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.class_sum import class_sum_pallas
from repro.kernels.clause_eval import clause_eval_pallas, clause_eval_sparse_pallas
from repro.kernels.fused_infer import fused_infer_pallas, fused_infer_sparse_pallas
from repro.kernels.ingress import ingress_pack_pallas
from repro.kernels.shapes import clamp_block as _clamp_block
from repro.kernels.shapes import pad_axis as _pad_axis
from repro.kernels.shapes import pad_axis_ones as _pad_axis_ones
from repro.kernels.shapes import round_up as _round_up

__all__ = [
    "clause_eval",
    "class_sum",
    "fused_infer",
    "fused_infer_from_images",
    "ingress_pack",
    "clause_eval_sparse",
    "fused_infer_sparse",
    "matmul_sparse_infer",
]


_LANES = 128


def _pick_backend(backend: Optional[str]) -> str:
    """pallas on TPU, the pure-jnp reference elsewhere.

    Pallas interpret mode emulates the kernel grid step-by-step on CPU —
    orders of magnitude slower than the jnp oracle, so it is never a
    default: tests and debuggers opt in with ``backend='interpret'``.
    """
    if backend is not None:
        return backend
    return "pallas" if jax.default_backend() == "tpu" else "ref"


def _patch_block(block_p: int, p: int) -> int:
    """Patch chunk on the lane axis: a multiple of 128, at most the
    padded patch count."""
    return _clamp_block(_round_up(block_p, _LANES), p, _LANES)


def _word_major(lit_packed: jax.Array, b_to: int, p_to: int) -> jax.Array:
    """uint32 [B, P, W] -> zero-padded kernel layout [W, b_to, p_to]."""
    x = _pad_axis(_pad_axis(lit_packed, 0, b_to), 1, p_to)
    return jnp.transpose(x, (2, 0, 1))


def _mask_columns(mask: jax.Array, c_to: int, *, ones: bool = False) -> jax.Array:
    """uint32 [C, W] -> kernel layout [W, c_to, 1]; clause rows pad with
    zero words, or all-ones words for the sparse exclude masks."""
    x = (_pad_axis_ones if ones else _pad_axis)(mask, 0, c_to)
    return jnp.transpose(x)[:, :, None]


@functools.partial(
    jax.jit, static_argnames=("backend", "block_b", "block_c", "block_p", "csrf")
)
def clause_eval(
    lit_packed: jax.Array,
    include_packed: jax.Array,
    nonempty: jax.Array,
    *,
    backend: Optional[str] = None,
    block_b: int = 8,
    block_c: int = 128,
    block_p: int = 128,
    csrf: bool = True,
) -> jax.Array:
    """Sequential-OR clause outputs uint8 [B, C] from packed inputs.

    backend: 'pallas' (TPU), 'interpret' (Pallas-on-CPU, used by tests),
    'ref' (pure jnp). Default: pallas on TPU, ref everywhere else.
    """
    bk = _pick_backend(backend)
    if bk == "ref":
        return ref.clause_eval_ref(lit_packed, include_packed, nonempty)

    b, p, w = lit_packed.shape
    c = include_packed.shape[0]
    block_b = _clamp_block(block_b, b, 8)
    block_c = _clamp_block(block_c, c, 128)
    block_p = _patch_block(block_p, p)
    ne = _pad_axis(nonempty.astype(jnp.int32), 0, _round_up(c, block_c))
    out = clause_eval_pallas(
        _word_major(lit_packed, _round_up(b, block_b), _round_up(p, block_p)),
        _mask_columns(include_packed, _round_up(c, block_c)),
        ne,
        block_b=block_b,
        block_c=block_c,
        block_p=block_p,
        csrf=csrf,
        interpret=(bk == "interpret"),
    )
    return out[:b, :c]


@functools.partial(jax.jit, static_argnames=("spec", "backend", "block_b"))
def ingress_pack(
    bool_images: jax.Array,
    spec,
    *,
    backend: Optional[str] = None,
    block_b: int = 8,
) -> jax.Array:
    """Packed patch literals uint32 [B, P, W] from booleanized images.

    The ingress stage of the fused inference path: on TPU the Pallas
    kernel (kernels/ingress.py) keeps the dense [B, P, 2o] literal bits
    in VMEM and writes only packed words to HBM; the ``ref`` backend is
    the jnp composition (patch gather -> literals -> pack) the rest of
    the repo uses.  Batch padding rows are zero images -> all literal
    words describe a blank patch; callers slice them off.
    """
    bk = _pick_backend(backend)
    if bk == "ref":
        return ref.ingress_pack_ref(bool_images, spec)

    b = bool_images.shape[0]
    block_b = _clamp_block(block_b, b, 8)
    imgs = _pad_axis(bool_images, 0, _round_up(b, block_b))
    out = ingress_pack_pallas(
        imgs, spec, block_b=block_b, interpret=(bk == "interpret")
    )
    return out[:b]


@functools.partial(
    jax.jit,
    static_argnames=("spec", "backend", "block_b", "block_c", "block_p", "csrf"),
)
def fused_infer_from_images(
    bool_images: jax.Array,     # uint8 0/1 [B, Y, X]
    spec,                       # core.patches.PatchSpec
    include_packed: jax.Array,
    nonempty: jax.Array,
    weights: jax.Array,
    *,
    backend: Optional[str] = None,
    block_b: int = 8,
    block_c: int = 128,
    block_p: int = 128,
    csrf: bool = True,
) -> jax.Array:
    """Booleanized images -> class sums with no dense literals in HBM.

    Chains the ingress kernel (dense bits live only in VMEM) into the
    fused clause-eval + class-sum kernel; the only intermediate that
    touches HBM is the packed uint32 [B, P, W] word stream — the same
    discipline as the ASIC datapath, where patch bits feed the clause
    pool without a memory round trip.
    """
    lit_packed = ingress_pack(bool_images, spec, backend=backend, block_b=block_b)
    return fused_infer(
        lit_packed, include_packed, nonempty, weights,
        backend=backend, block_b=block_b, block_c=block_c, block_p=block_p,
        csrf=csrf,
    )


@functools.partial(jax.jit, static_argnames=("backend", "block_b", "block_c"))
def class_sum(
    fired: jax.Array,
    weights: jax.Array,
    *,
    backend: Optional[str] = None,
    block_b: int = 128,
    block_c: int = 128,
) -> jax.Array:
    """int32 [B, M] class sums (Eq. 3)."""
    bk = _pick_backend(backend)
    if bk == "ref":
        return ref.class_sum_ref(fired, weights)
    b, c = fired.shape
    block_b = _clamp_block(block_b, b, 8)
    block_c = _clamp_block(block_c, c, 128)
    fp = _pad_axis(_pad_axis(fired, 0, _round_up(b, block_b)), 1, _round_up(c, block_c))
    wp = _pad_axis(weights, 1, _round_up(c, block_c))
    out = class_sum_pallas(
        fp, wp, block_b=block_b, block_c=block_c, interpret=(bk == "interpret")
    )
    return out[:b]


@functools.partial(
    jax.jit, static_argnames=("backend", "block_b", "block_c", "block_p", "csrf")
)
def fused_infer(
    lit_packed: jax.Array,
    include_packed: jax.Array,
    nonempty: jax.Array,
    weights: jax.Array,
    *,
    backend: Optional[str] = None,
    block_b: int = 8,
    block_c: int = 128,
    block_p: int = 128,
    csrf: bool = True,
) -> jax.Array:
    """Single-kernel clause_eval + class_sum, returns int32 [B, M].

    The fused kernel keeps the sequential-OR register in VMEM scratch and
    reduces it against the weights in-register on the last patch chunk —
    the fired vector never touches HBM (kernels/fused_infer.py)."""
    bk = _pick_backend(backend)
    if bk == "ref":
        return ref.fused_infer_ref(lit_packed, include_packed, nonempty, weights)

    b, p, w = lit_packed.shape
    c = include_packed.shape[0]
    block_b = _clamp_block(block_b, b, 8)
    block_c = _clamp_block(block_c, c, 128)
    block_p = _patch_block(block_p, p)
    c_to = _round_up(c, block_c)
    out = fused_infer_pallas(
        _word_major(lit_packed, _round_up(b, block_b), _round_up(p, block_p)),
        _mask_columns(include_packed, c_to),
        _pad_axis(nonempty.astype(jnp.int32), 0, c_to),
        _pad_axis(weights, 1, c_to).T,
        block_b=block_b, block_c=block_c, block_p=block_p,
        csrf=csrf, interpret=(bk == "interpret"),
    )
    return out[:b]


# --- clause-sparsity fast path ---------------------------------------------
#
# Active-clause inputs come pre-gathered from
# ``serve.servable.analyze_sparsity`` (empty clauses pruned at freeze
# time).  Sparse padding contract, proved alongside the dense one in
# tests/test_kernels.py / tests/test_sparse.py:
#   * clause rows pad with ALL-ONES exclude masks -> zero violations on
#     every patch, so they fire immediately (saturating CSRF fastest) and
#     are sliced off (clause_eval_sparse) or matched with zero weight
#     columns (fused_infer_sparse);
#   * patch rows pad with all-zero literal words -> every active clause
#     (>= 1 include by construction) violates, OR unchanged;
#   * batch rows pad with zeros and are sliced off.


@functools.partial(
    jax.jit, static_argnames=("backend", "block_b", "block_c", "block_p", "csrf")
)
def clause_eval_sparse(
    lit_packed: jax.Array,
    exclude_packed: jax.Array,
    *,
    backend: Optional[str] = None,
    block_b: int = 8,
    block_c: int = 128,
    block_p: int = 128,
    csrf: bool = True,
) -> jax.Array:
    """Active-clause sequential-OR outputs uint8 [B, C_a] from packed
    literals + packed exclude masks (popcount violation counting)."""
    b, p, w = lit_packed.shape
    c = exclude_packed.shape[0]
    if c == 0:   # fully-empty clause pool: nothing can fire
        return jnp.zeros((b, 0), jnp.uint8)
    bk = _pick_backend(backend)
    if bk == "ref":
        return ref.clause_eval_sparse_ref(lit_packed, exclude_packed)

    block_b = _clamp_block(block_b, b, 8)
    block_c = _clamp_block(block_c, c, 128)
    block_p = _patch_block(block_p, p)
    out = clause_eval_sparse_pallas(
        _word_major(lit_packed, _round_up(b, block_b), _round_up(p, block_p)),
        _mask_columns(exclude_packed, _round_up(c, block_c), ones=True),
        block_b=block_b,
        block_c=block_c,
        block_p=block_p,
        csrf=csrf,
        interpret=(bk == "interpret"),
    )
    return out[:b, :c]


@functools.partial(
    jax.jit, static_argnames=("backend", "block_b", "block_c", "block_p", "csrf")
)
def fused_infer_sparse(
    lit_packed: jax.Array,
    exclude_packed: jax.Array,
    weights_active: jax.Array,
    *,
    backend: Optional[str] = None,
    block_b: int = 8,
    block_c: int = 128,
    block_p: int = 128,
    csrf: bool = True,
) -> jax.Array:
    """Single-kernel sparse clause-eval + class-sum, int32 [B, M]."""
    b, p, w = lit_packed.shape
    c = exclude_packed.shape[0]
    m = weights_active.shape[0]
    if c == 0:
        return jnp.zeros((b, m), jnp.int32)
    bk = _pick_backend(backend)
    if bk == "ref":
        return ref.sparse_infer_ref(lit_packed, exclude_packed, weights_active)

    block_b = _clamp_block(block_b, b, 8)
    block_c = _clamp_block(block_c, c, 128)
    block_p = _patch_block(block_p, p)
    c_to = _round_up(c, block_c)
    out = fused_infer_sparse_pallas(
        _word_major(lit_packed, _round_up(b, block_b), _round_up(p, block_p)),
        _mask_columns(exclude_packed, c_to, ones=True),
        _pad_axis(weights_active, 1, c_to).T,
        block_b=block_b, block_c=block_c, block_p=block_p,
        csrf=csrf, interpret=(bk == "interpret"),
    )
    return out[:b]


@jax.jit
def matmul_sparse_infer(
    literals: jax.Array,        # uint8 0/1 [B, P, 2o] dense literals
    include_active: jax.Array,  # uint8 0/1 [C_a, 2o]
    weights_active: jax.Array,  # int8 [m, C_a]
) -> jax.Array:
    """int8 matmul violation-count path over the active clause pool.

    One int8 x int8 -> int32 dot computes per-(image, patch, clause)
    violation counts (MXU int8 throughput on TPU; plain XLA everywhere —
    no Pallas body, so every backend shares this graph).  Work scales
    with C_a instead of C: at paper geometry a boundary model keeps
    ~70-95% of clauses, a trained pool typically fewer.  Returns int32
    [B, m] class sums, bit-identical to the dense reference.
    """
    return ref.matmul_sparse_infer_ref(literals, include_active, weights_active)
