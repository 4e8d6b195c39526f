"""Fused ConvCoTM inference kernel: clause evaluation + class sums in one
pallas_call (beyond-paper optimization, EXPERIMENTS.md §Perf/kernel).

The two-kernel pipeline writes the fired vector [B, C] to HBM and reads it
back for the class-sum matmul.  Fused, the OR register lives in a VMEM
scratch for the duration of the patch loop and the weighted reduction
happens in-register on the last patch chunk — exactly the ASIC's datapath,
where clause outputs feed the adder trees without leaving the chip.

Grid = (image blocks, clause chunks, patch chunks); patch axis innermost
(sequential OR), clause chunks accumulate partial class sums into the
[Bb, 1, m] output block (revisited across ic).  Operand layouts, the
per-image word loop and the CSRF block-skip are those of clause_eval.py;
the weights come transposed, ``[C, m]``, so a clause column of the OR
register scales its weight row and a sublane reduction gives the class
sums of one image.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.clause_eval import image_fires
from repro.kernels.shapes import grid_blocks

__all__ = ["PALLAS_ORACLES", "fused_infer_pallas", "fused_infer_sparse_pallas"]

#: Pallas entry point -> its pure-jnp oracle in kernels/ref.py (aggregated
#: by kernels/registry.py; statically enforced by tools/tmlint TM202).
PALLAS_ORACLES = {
    "fused_infer_pallas": "fused_infer_ref",
    "fused_infer_sparse_pallas": "sparse_infer_ref",
}


def _kernel(lit_ref, mask_ref, *rest, csrf: bool, sparse: bool):
    """Refs:
      lit_ref: uint32 [W, Bb, Pc];  mask_ref: uint32 [W, Cc, 1]
      ne_ref:  int32 [Cc, 1] (dense only);  w_ref: int32 [Cc, M]
      out_ref: int32 [Bb, 1, M]     (class sums, accumulated over ic)
      or_scratch: int32 [Bb, Cc, 1] (sequential-OR register, VMEM)
    """
    if sparse:
        w_ref, out_ref, or_scratch = rest
    else:
        ne_ref, w_ref, out_ref, or_scratch = rest
    ic = pl.program_id(1)
    ip = pl.program_id(2)
    n_ip = pl.num_programs(2)
    n_img = lit_ref.shape[1]

    @pl.when(jnp.logical_and(ic == 0, ip == 0))
    def _init_out():
        out_ref[...] = jnp.zeros_like(out_ref)

    @pl.when(ip == 0)
    def _init_or():
        or_scratch[...] = jnp.zeros_like(or_scratch)

    def _eval_tile():
        def image(b, carry):
            hit = image_fires(lit_ref, mask_ref, b, sparse=sparse)
            if not sparse:
                hit = hit & (ne_ref[...] != 0).astype(jnp.int32)
            or_scratch[b] = or_scratch[b] | hit
            return carry

        jax.lax.fori_loop(0, n_img, image, 0)

    if csrf:
        @pl.when(jnp.logical_or(ip == 0, jnp.logical_not(jnp.all(or_scratch[...] > 0))))
        def _work():
            _eval_tile()
    else:
        _eval_tile()

    @pl.when(ip == n_ip - 1)
    def _class_sums():
        w = w_ref[...].astype(jnp.float32)               # (Cc, M), int8 range

        def image(b, carry):
            fired = or_scratch[b].astype(jnp.float32)    # (Cc, 1) 0/1
            # |partial| <= 127 * Cc: exact in f32.
            part = jnp.sum(fired * w, axis=0, keepdims=True)   # (1, M)
            out_ref[b] = out_ref[b] + part.astype(jnp.int32)
            return carry

        jax.lax.fori_loop(0, n_img, image, 0)


def _fused_call(lit_t, mask_t, extra, weights_t, *, block_b, block_c, block_p,
                csrf, sparse, interpret):
    """The pallas_call both entry points share; returns int32 [B, M]."""
    w, b, p = lit_t.shape
    c = mask_t.shape[1]
    m = weights_t.shape[1]
    grid = (
        grid_blocks(b, block_b, axis="B"),
        grid_blocks(c, block_c, axis="C"),
        grid_blocks(p, block_p, axis="P"),
    )
    in_specs = [
        pl.BlockSpec((w, block_b, block_p), lambda ib, ic, ip: (0, ib, ip)),
        pl.BlockSpec((w, block_c, 1), lambda ib, ic, ip: (0, ic, 0)),
    ]
    if not sparse:
        in_specs.append(pl.BlockSpec((block_c, 1), lambda ib, ic, ip: (ic, 0)))
    in_specs.append(pl.BlockSpec((block_c, m), lambda ib, ic, ip: (ic, 0)))
    out = pl.pallas_call(
        functools.partial(_kernel, csrf=csrf, sparse=sparse),
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((block_b, 1, m), lambda ib, ic, ip: (ib, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, 1, m), jnp.int32),
        scratch_shapes=[pltpu.VMEM((block_b, block_c, 1), jnp.int32)],
        interpret=interpret,
    )(lit_t, mask_t, *extra, weights_t.astype(jnp.int32))
    return out[:, 0, :]


@functools.partial(
    jax.jit,
    static_argnames=("block_b", "block_c", "block_p", "csrf", "interpret"),
)
def fused_infer_pallas(
    lit_t: jax.Array,           # uint32 [W, B, P]   (word-major)
    include_t: jax.Array,       # uint32 [W, C, 1]
    nonempty: jax.Array,        # bool/uint8/int [C]
    weights_t: jax.Array,       # int [C, M]
    *,
    block_b: int = 8,
    block_c: int = 128,
    block_p: int = 128,
    csrf: bool = True,
    interpret: bool = False,
) -> jax.Array:
    """Returns int32 [B, M] class sums. Layout and padding as in ops.py."""
    ne = nonempty.astype(jnp.int32).reshape(-1, 1)
    return _fused_call(
        lit_t, include_t, (ne,), weights_t, block_b=block_b, block_c=block_c,
        block_p=block_p, csrf=csrf, sparse=False, interpret=interpret,
    )


# --- clause-sparsity fast path ---------------------------------------------


@functools.partial(
    jax.jit,
    static_argnames=("block_b", "block_c", "block_p", "csrf", "interpret"),
)
def fused_infer_sparse_pallas(
    lit_t: jax.Array,           # uint32 [W, B, P]   (word-major)
    exclude_t: jax.Array,       # uint32 [W, C_a, 1] (pad clauses: all ones)
    weights_active_t: jax.Array,  # int [C_a, M]     (pad rows: zero)
    *,
    block_b: int = 8,
    block_c: int = 128,
    block_p: int = 128,
    csrf: bool = True,
    interpret: bool = False,
) -> jax.Array:
    """Returns int32 [B, M] class sums over the active clause pool (see
    clause_eval.py for the sparse padding contract: pad clauses carry
    all-ones exclude masks and zero weight rows)."""
    return _fused_call(
        lit_t, exclude_t, (), weights_active_t, block_b=block_b,
        block_c=block_c, block_p=block_p, csrf=csrf, sparse=True,
        interpret=interpret,
    )
