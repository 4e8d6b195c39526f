"""Pallas TPU kernel: bit-packed ConvCoTM clause evaluation.

This is the accelerator's clause pool (paper Sec. IV-D) re-derived for the
TPU memory hierarchy:

  * Literals arrive bit-packed: 9 uint32 words encode the 272 literals of
    a patch (vs 272 bytes dense: an 8.5x cut in HBM traffic for the
    dominant input stream; the dense path is memory-bound).  The kernel
    takes them word-major, ``[W, B, P]``, so that patches lie on the
    128-wide lane axis: one word of one image is a ``(1, Pc)`` row.
  * The include masks (the model's TA-action registers) come as
    ``[W, C, 1]``: clauses on sublanes, so one word of the clause block is
    a ``(Cb, 1)`` column that broadcasts across the patch lanes.  Their
    BlockSpec index map ignores the patch-chunk grid axis, so the model
    block stays **resident in VMEM** across all patch chunks — the TPU
    analogue of the ASIC's "model clock stopped, actions held in DFFs".
  * Per image, the word loop ORs ``include & ~literal`` into a
    ``(Cb, Pc)`` violation tile (16 vregs at 128 x 128); a clause fires
    on a patch iff its violation word stays zero, and on the image iff
    it fires on any patch (a lane reduction).  Every ref access uses
    leading or sublane indices only; nothing is sliced dynamically
    along lanes, which Mosaic does not lower.
  * Grid = (image blocks, clause blocks, patch chunks); the patch axis is
    innermost so the output tile acts as the sequential-OR register
    (Eq. 6) accumulated in VMEM.
  * **CSRF block-skip** (the paper's clause-switching-reduction feedback,
    adapted): once every clause in the (image x clause) tile has fired,
    remaining patch-chunk iterations skip the whole tile body via
    ``@pl.when`` — monotone OR saturation means no more work can change
    the result.  On the ASIC this cuts combinational toggling ~50 %; here
    it cuts VPU issue slots for the tail chunks.  Disable with
    ``csrf=False`` (the chip has the same enable pin).

Padding contract (enforced by ops.py): patch padding uses all-zero literal
words — any nonempty clause violates on them, and empty clauses are killed
by the ``nonempty`` mask, so zero-padding never changes the OR.  Clause
padding uses zero include masks + nonempty=0; batch padding is sliced off.

Correctness on CPU is established with ``interpret=True`` (tests sweep
shapes against ref.py); on a TPU the same call compiles to Mosaic
(tests/test_tpu_compile.py compiles it for a described v5e).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.shapes import grid_blocks

__all__ = [
    "PALLAS_ORACLES",
    "clause_eval_kernel",
    "clause_eval_pallas",
    "clause_eval_sparse_pallas",
    "image_fires",
]

#: Pallas entry point -> its pure-jnp oracle in kernels/ref.py (aggregated
#: by kernels/registry.py; statically enforced by tools/tmlint TM202).
PALLAS_ORACLES = {
    "clause_eval_pallas": "clause_eval_ref",
    "clause_eval_sparse_pallas": "clause_eval_sparse_ref",
}


def image_fires(lit_ref, mask_ref, b, *, sparse: bool) -> jax.Array:
    """int32 ``(Cb, 1)``: 1 where a clause of the block fires on some
    patch of image ``b``'s chunk.

    lit_ref: uint32 ``[W, Bb, Pc]`` packed literals (word-major);
    mask_ref: uint32 ``[W, Cb, 1]`` packed include masks (dense) or
    exclude masks (sparse).  A word misses when a required literal is
    absent: ``include & ~lit`` (dense) or ``~(lit | exclude)`` (sparse),
    the same predicate.  The fori_loop over words carries only the
    ``(Cb, Pc)`` miss tile, so trace size and live VMEM stay flat in W.
    """
    n_words, _, pc = lit_ref.shape
    cb = mask_ref.shape[1]

    def word_step(w, miss):
        lw = lit_ref[w, pl.ds(b, 1), :]         # (1, Pc)  one word, all patches
        mw = mask_ref[w]                        # (Cb, 1)  one word, all clauses
        return miss | (~(lw | mw) if sparse else (mw & ~lw))

    miss = jax.lax.fori_loop(0, n_words, word_step, jnp.zeros((cb, pc), jnp.uint32))
    return jnp.max((miss == 0).astype(jnp.int32), axis=1, keepdims=True)


def clause_eval_kernel(lit_ref, mask_ref, *rest, csrf: bool, sparse: bool):
    """Kernel body for one (image-block, clause-block, patch-chunk) tile.

    Refs:
      lit_ref:      uint32 [W, Bb, Pc]   packed literals
      mask_ref:     uint32 [W, Cb, 1]    packed include (or exclude) masks
      nonempty_ref: int32  [Cb, 1]       nonempty flags (dense only)
      out_ref:      int32  [Bb, Cb, 1]   sequential-OR accumulator
    """
    if sparse:
        (out_ref,) = rest
    else:
        nonempty_ref, out_ref = rest
    ip = pl.program_id(2)

    @pl.when(ip == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    def _tile_body():
        def image(b, carry):
            hit = image_fires(lit_ref, mask_ref, b, sparse=sparse)
            if not sparse:
                hit = hit & (nonempty_ref[...] != 0).astype(jnp.int32)
            out_ref[b] = out_ref[b] | hit       # Eq. (6) accumulator
            return carry

        jax.lax.fori_loop(0, lit_ref.shape[1], image, 0)

    if csrf:
        # CSRF: skip the tile once the OR register is saturated.
        not_saturated = jnp.logical_not(jnp.all(out_ref[...] > 0))

        @pl.when(jnp.logical_or(ip == 0, not_saturated))
        def _work():
            _tile_body()
    else:
        _tile_body()


def _clause_eval_call(lit_t, mask_t, extra, *, block_b, block_c, block_p,
                      csrf, sparse, interpret):
    """The pallas_call both entry points share; returns uint8 [B, C]."""
    w, b, p = lit_t.shape
    c = mask_t.shape[1]
    grid = (
        grid_blocks(b, block_b, axis="B"),
        grid_blocks(c, block_c, axis="C"),
        grid_blocks(p, block_p, axis="P"),
    )
    in_specs = [
        # Literals: advance along image and patch axes; all words.
        pl.BlockSpec((w, block_b, block_p), lambda ib, ic, ip: (0, ib, ip)),
        # Model block: pinned across patch chunks (VMEM-resident).
        pl.BlockSpec((w, block_c, 1), lambda ib, ic, ip: (0, ic, 0)),
    ]
    if not sparse:
        in_specs.append(pl.BlockSpec((block_c, 1), lambda ib, ic, ip: (ic, 0)))
    out = pl.pallas_call(
        functools.partial(clause_eval_kernel, csrf=csrf, sparse=sparse),
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((block_b, block_c, 1), lambda ib, ic, ip: (ib, ic, 0)),
        out_shape=jax.ShapeDtypeStruct((b, c, 1), jnp.int32),
        interpret=interpret,
    )(lit_t, mask_t, *extra)
    return out[:, :, 0].astype(jnp.uint8)


@functools.partial(
    jax.jit,
    static_argnames=("block_b", "block_c", "block_p", "csrf", "interpret"),
)
def clause_eval_pallas(
    lit_t: jax.Array,           # uint32 [W, B, P]   (word-major)
    include_t: jax.Array,       # uint32 [W, C, 1]
    nonempty: jax.Array,        # bool/uint8 [C]
    *,
    block_b: int = 8,
    block_c: int = 128,
    block_p: int = 128,
    csrf: bool = True,
    interpret: bool = False,
) -> jax.Array:
    """Pallas clause evaluation; returns uint8 0/1 ``[B, C]``.

    Inputs must already be in the kernel layout and satisfy the padding
    contract (see ops.py, which pads, transposes and dispatches);
    B % block_b == 0 etc. are required here.
    """
    ne = nonempty.astype(jnp.int32).reshape(-1, 1)
    return _clause_eval_call(
        lit_t, include_t, (ne,), block_b=block_b, block_c=block_c,
        block_p=block_p, csrf=csrf, sparse=False, interpret=interpret,
    )


# --- clause-sparsity fast path ---------------------------------------------
#
# The sparse variant evaluates only the ACTIVE clause pool (empty clauses
# pruned at freeze time by serve.servable.analyze_sparsity — the software
# form of the ASIC's ``Empty`` gating, which here removes the rows
# entirely instead of masking them).  The model side is the packed
# EXCLUDE mask: a patch satisfies a clause iff every literal word covers
# it, ``~(lit | exclude) == 0`` — the zero-violation test the matmul
# formulation makes on the MXU, so the two sparse paths share semantics
# exactly.  There is no ``nonempty`` operand — clause padding uses
# all-ones exclude masks (fires everywhere) and callers slice the rows
# off / give them zero weight columns.


@functools.partial(
    jax.jit,
    static_argnames=("block_b", "block_c", "block_p", "csrf", "interpret"),
)
def clause_eval_sparse_pallas(
    lit_t: jax.Array,           # uint32 [W, B, P]   (word-major)
    exclude_t: jax.Array,       # uint32 [W, C_a, 1] (pad clauses: all ones)
    *,
    block_b: int = 8,
    block_c: int = 128,
    block_p: int = 128,
    csrf: bool = True,
    interpret: bool = False,
) -> jax.Array:
    """Sparse (active-clause) Pallas evaluation; uint8 0/1 ``[B, C_a]``.

    Padding contract (ops.py): clause rows pad with ALL-ONES exclude
    masks — they fire on every patch (zero violations by construction),
    saturating the CSRF check fastest, and are sliced off / zero-weighted
    by the caller.  Patch padding still uses all-zero literal words: any
    clause with >= 1 include violates on them, and include-free clauses
    cannot exist in the active pool.
    """
    return _clause_eval_call(
        lit_t, exclude_t, (), block_b=block_b, block_c=block_c,
        block_p=block_p, csrf=csrf, sparse=True, interpret=interpret,
    )
