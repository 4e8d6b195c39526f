"""Pallas TPU kernel: booleanized images -> packed patch literals.

The ingress stage of the fused inference path (the ASIC streams
booleanized pixels straight into the clause datapath, Sec. IV-C).  The
jnp ingress materializes the dense literal tensor ``uint8 [B, P, 2o]``
in HBM between patch extraction and bit packing — 8.5x the bytes of the
packed form, and at paper geometry (361 patches x 272 literals) by far
the largest intermediate of the whole inference pipeline.  This kernel
keeps the dense bits in VMEM for the lifetime of one image block and
writes only the packed words back to HBM, so the dense literals never
exist in device memory at all.

Layout decisions:

  * Grid = (image blocks,) only.  One image block's full patch set fits
    in VMEM at paper geometry, so there is nothing to win from patch
    chunking here; the consumer kernels (clause_eval / fused_infer)
    chunk the patch axis themselves.
  * Patches lie on the lane axis, and the output is word-major
    ``[W, B, P]``: the layout the consumer kernels read.
  * The window gather runs on the MXU.  Feature k = (wy, wx) of patch p
    is the flat image pixel ``origin(p) + wy*X + wx``.  The kernel rolls
    the flat image left by that offset (one lane rotate per window
    offset) and multiplies the stacked rolls by a constant one-hot
    ``[Y*X, P]`` matrix that picks each patch's origin pixel.  0/1
    operands make the bf16 x bf16 -> f32 product exact.  Mosaic lowers
    neither a gather nor the ``(Bb, By, Bx) -> (Bb, P)`` reshape a
    strided-slice gather needs; a matmul it lowers.
  * The position thermometer bits are per-patch constants (they depend
    only on the geometry), computed by the same
    ``core.patches._index_tables`` the jnp path uses — one source of
    truth for the literal order — and passed as a pinned VMEM-resident
    input (Pallas does not allow kernels to close over array constants).
  * Bits are packed in int32: the 32 shifted bits of a word are
    disjoint, so their sum never carries and equals their OR, bit 31
    included (Mosaic reduces no unsigned integers).  The wrapper
    bitcasts the words to uint32.

The one-hot matrix grows as ``Y*X*P``: about 0.7 MB at paper geometry,
and it must fit in VMEM with the rest of the block.

Correctness on CPU is established with ``interpret=True`` against the
jnp oracle (``ref.ingress_pack_ref``); shape sweeps in
``tests/test_ingress.py``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.patches import PatchSpec, _index_tables
from repro.kernels.shapes import grid_blocks, round_up

__all__ = ["PALLAS_ORACLES", "ingress_pack_kernel", "ingress_pack_pallas"]

#: Pallas entry point -> its pure-jnp oracle in kernels/ref.py (aggregated
#: by kernels/registry.py; statically enforced by tools/tmlint TM202).
PALLAS_ORACLES = {"ingress_pack_pallas": "ingress_pack_ref"}

_LANES = 128


def ingress_pack_kernel(img_ref, sel_ref, pos_ref, out_ref, *, spec: PatchSpec):
    """Kernel body for one image block.

    Refs:
      img_ref: f32 [Bb, N]             flat booleanized images (N = Y*X
                                       padded to lanes)
      sel_ref: bf16 [N, Pp]            one-hot: patch p's origin pixel
      pos_ref: int32 [max(pos,1), 1, Pp]  position-thermometer bits,
                                       pinned (padded to >= 1 row; the
                                       real count comes from ``spec``)
      out_ref: int32 [W, Bb, Pp]       packed literal words (LSB-first)
    """
    img = img_ref[...]                              # (Bb, N)
    bb, n = img.shape
    rolled = []
    # Feature order: window bits row-major (wy, wx) — matches
    # core.patches._index_tables' meshgrid order exactly.
    for wy in range(spec.window_y):
        for wx in range(spec.window_x):
            s = wy * spec.image_x + wx              # rolled[:, i] = img[:, i + s]
            rolled.append(pltpu.roll(img, n - s, 1) if s else img)
    stacked = jnp.concatenate(rolled, axis=0).astype(jnp.bfloat16)
    win = jnp.dot(stacked, sel_ref[...], preferred_element_type=jnp.float32)
    pp = win.shape[1]
    feats = [win.astype(jnp.int32).reshape(len(rolled), bb, pp)]   # (Wy*Wx, Bb, Pp)
    n_pos = spec.n_pos_y_bits + spec.n_pos_x_bits
    if n_pos:
        feats.append(jnp.broadcast_to(pos_ref[...], (n_pos, bb, pp)))
    feats = jnp.concatenate(feats, axis=0)          # (o, Bb, Pp)
    lits = [feats, 1 - feats]
    pad = spec.n_words * 32 - spec.n_literals
    if pad:
        lits.append(jnp.zeros((pad, bb, pp), jnp.int32))
    words = jnp.concatenate(lits, axis=0).reshape(spec.n_words, 32, bb, pp)
    shifts = jax.lax.broadcasted_iota(jnp.int32, words.shape, 1)
    out_ref[...] = jnp.sum(words << shifts, axis=1)


def _tables(spec: PatchSpec):
    """(one-hot origin selector [N, Pp] bf16, position bits [pos, 1, Pp])."""
    iy, ix, pos = _index_tables(spec)
    n = round_up(spec.image_y * spec.image_x, _LANES)
    pp = round_up(spec.n_patches, _LANES)
    origin = iy[:, 0] * spec.image_x + ix[:, 0]
    sel = np.zeros((n, pp), np.float32)
    sel[origin, np.arange(spec.n_patches)] = 1
    n_pos = pos.shape[1]
    posr = np.zeros((max(n_pos, 1), 1, pp), np.int32)   # whole-image window:
    posr[:n_pos, 0, : spec.n_patches] = pos.T          # one zero row
    return jnp.asarray(sel, jnp.bfloat16), jnp.asarray(posr)


@functools.partial(jax.jit, static_argnames=("spec", "block_b", "interpret"))
def ingress_pack_pallas(
    bool_images: jax.Array,     # uint8 0/1 [B, Y, X]
    spec: PatchSpec,
    *,
    block_b: int = 8,
    interpret: bool = False,
) -> jax.Array:
    """Packed literals uint32 ``[B, P, W]``; B % block_b == 0 required
    (ops.py pads and dispatches).  Z = U = 1 geometries only — the
    multi-channel / thermometer layouts take the jnp ingress."""
    if spec.channels != 1 or spec.therm_bits != 1:
        raise ValueError("ingress kernel supports Z=U=1 geometries only")
    b, y, x = bool_images.shape
    if (y, x) != (spec.image_y, spec.image_x):
        raise ValueError(
            f"image dims {(y, x)} != spec ({spec.image_y}, {spec.image_x})"
        )
    sel, posr = _tables(spec)
    n, pp = sel.shape
    flat = bool_images.reshape(b, y * x).astype(jnp.float32)
    flat = jnp.pad(flat, ((0, 0), (0, n - y * x)))
    out = pl.pallas_call(
        functools.partial(ingress_pack_kernel, spec=spec),
        grid=(grid_blocks(b, block_b, axis="B"),),
        in_specs=[
            pl.BlockSpec((block_b, n), lambda ib: (ib, 0)),
            # Geometry constants: pinned across image blocks (VMEM-resident).
            pl.BlockSpec((n, pp), lambda ib: (0, 0)),
            pl.BlockSpec(posr.shape, lambda ib: (0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((spec.n_words, block_b, pp), lambda ib: (0, ib, 0)),
        out_shape=jax.ShapeDtypeStruct((spec.n_words, b, pp), jnp.int32),
        interpret=interpret,
    )(flat, sel, posr)
    words = jax.lax.bitcast_convert_type(out[:, :, : spec.n_patches], jnp.uint32)
    return jnp.transpose(words, (1, 2, 0))
