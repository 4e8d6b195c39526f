"""Clause evaluation for the (convolutional) coalesced Tsetlin machine.

A clause j (Eq. 2) is the AND of the literals whose trained TA action is
*include*.  For convolution (Eq. 6) a clause fires for an image iff it fires
for at least one patch (the ASIC's sequential-OR register).

Four functionally identical evaluation paths are provided:

  * ``eval_clauses_dense``     — reference semantics on 0/1 uint8 literals.
  * ``eval_clauses_bitpacked`` — uint32 bitwise path (VPU-friendly); the
    Pallas kernel in ``repro.kernels.clause_eval`` implements exactly this
    with VMEM tiling + the CSRF block-skip.
  * ``eval_clauses_matmul``    — MXU formulation: a clause fires on a patch
    iff ``popcount(include & ~literals) == 0``, i.e. iff
    ``(1 - literals) @ includeᵀ == 0`` — one bf16 matmul with fp32
    accumulation (counts ≤ 2o = 272 are exact in fp32).
  * ``eval_clauses_folded``    — the same violation count from the
    booleanized frame itself, each literal folded into its negation:
    ``v = Σ I⁺ + f · (I⁻ − I⁺)``, so the window part is one convolution
    and nothing is gathered into ``[B, P, 2o]`` (the ``matmul`` path's
    raw form, ``serve/paths.py``).

The *empty clause* rule (paper Sec. IV-D): a clause with zero includes
outputs 0 during inference (the ASIC's ``Empty`` signal forces c_j^b low).
Note all four paths implement this via the ``nonempty`` mask.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.patches import PatchSpec, _index_tables, pack_bits

__all__ = [
    "clause_nonempty",
    "eval_clauses_dense",
    "eval_clauses_bitpacked",
    "eval_clauses_matmul",
    "eval_clauses_folded",
    "patch_clause_outputs",
    "patch_clause_outputs_matmul",
    "class_sums",
    "argmax_predict",
]


def clause_nonempty(include: jax.Array) -> jax.Array:
    """[C, 2o] 0/1 include mask -> [C] bool nonempty flags."""
    return jnp.any(include > 0, axis=-1)


def patch_clause_outputs(
    literals: jax.Array, include: jax.Array, training: bool = False
) -> jax.Array:
    """Per-patch clause outputs c_j^b (before the sequential OR).

    Args:
      literals: uint8 0/1 ``[B, P, 2o]``.
      include:  uint8 0/1 ``[C, 2o]`` TA-action (include) mask.
      training: TM semantics — an *empty* clause outputs 1 during learning
        (so it can receive Type Ia feedback and bootstrap includes) but 0
        during classification (the ASIC's ``Empty`` signal, Sec. IV-D).

    Returns:
      uint8 0/1 ``[B, P, C]``.
    """
    # violation: literal required (include=1) but absent (literal=0).
    viol = (include[None, None] > 0) & (literals[:, :, None, :] == 0)
    fires = ~jnp.any(viol, axis=-1)
    if not training:
        fires &= clause_nonempty(include)[None, None]
    return fires.astype(jnp.uint8)


def eval_clauses_dense(literals: jax.Array, include: jax.Array) -> jax.Array:
    """Sequential-OR clause outputs c_j (Eq. 6). [B, P, 2o] -> [B, C]."""
    return jnp.any(patch_clause_outputs(literals, include) > 0, axis=1).astype(
        jnp.uint8
    )


def eval_clauses_bitpacked(
    lit_packed: jax.Array,
    include_packed: jax.Array,
    nonempty: jax.Array,
) -> jax.Array:
    """Bit-packed clause evaluation.

    Args:
      lit_packed:     uint32 ``[B, P, W]`` packed literals.
      include_packed: uint32 ``[C, W]`` packed include masks.
      nonempty:       bool ``[C]``.

    Returns:
      uint8 0/1 ``[B, C]`` ORed over patches.
    """
    viol = include_packed[None, None] & ~lit_packed[:, :, None, :]
    fires_patch = jnp.all(viol == 0, axis=-1)            # [B, P, C]
    fired = jnp.any(fires_patch, axis=1) & nonempty[None]
    return fired.astype(jnp.uint8)


def patch_clause_outputs_matmul(
    literals: jax.Array,
    include: jax.Array,
    training: bool = False,
    *,
    dtype=jnp.bfloat16,
) -> jax.Array:
    """MXU formulation of :func:`patch_clause_outputs` (bit-identical).

    violations = (1 - literals) @ includeᵀ: a clause fires on a patch iff
    it has zero violations.  Inputs are 0/1 so bf16 operands are exact;
    accumulation is forced to fp32 (counts ≤ 2o stay exact), making the
    boolean outputs identical to the dense-broadcast reference — this is
    the training fast path (one matmul instead of a ``[P, C, 2o]``
    broadcast per sample).

    Args/returns: as :func:`patch_clause_outputs`.
    """
    neg = (1 - literals).astype(dtype)                   # [B, P, 2o]
    inc = include.astype(dtype)                          # [C, 2o]
    viol_counts = jax.lax.dot_general(
        neg,
        inc,
        (((neg.ndim - 1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )                                                    # [B, P, C]
    fires = viol_counts == 0.0
    if not training:
        fires &= clause_nonempty(include)[None, None]
    return fires.astype(jnp.uint8)


def eval_clauses_matmul(
    literals: jax.Array,
    include: jax.Array,
    nonempty: jax.Array | None = None,
    *,
    dtype=jnp.bfloat16,
) -> jax.Array:
    """MXU formulation: violations = (1 - literals) @ includeᵀ.

    A clause fires on a patch iff it has zero violations. Inputs are 0/1 so
    bf16 operands are exact; accumulation is forced to fp32 (counts ≤ 2o).
    """
    fires_patch = patch_clause_outputs_matmul(
        literals, include, training=True, dtype=dtype
    )                                                    # [B, P, C]
    fired = jnp.any(fires_patch > 0, axis=1)
    if nonempty is None:
        nonempty = clause_nonempty(include)
    return (fired & nonempty[None]).astype(jnp.uint8)


def eval_clauses_folded(
    bits: jax.Array,
    spec: PatchSpec,
    include: jax.Array,
    nonempty: jax.Array | None = None,
    *,
    dtype=jnp.bfloat16,
) -> jax.Array:
    """Clause outputs ``uint8 [B, C]`` straight from booleanized bits.

    Every literal comes with its negation, ``ℓ = [f, 1 − f]``, so the
    violation count of :func:`eval_clauses_matmul` folds to

        v[b, p, c] = Σ_k (1 − ℓ_k) I[c, k]
                   = Σ_j I⁺[c, j] + Σ_j f[b, p, j] (I⁻[c, j] − I⁺[c, j])

    with ``I⁺``/``I⁻`` the include masks of the plain and the negated
    literals.  The window features of patch p are the bits at
    ``(py + wy, px + wx, z, u)``, so their part is a VALID convolution
    of the frame, at the patch stride, with the signed kernel
    ``D[wy, wx, z, u, c] ∈ {−1, 0, 1}``; the position features depend on
    the patch alone and join ``Σ I⁺`` in a constant ``A[p, c]``.
    Nothing is gathered into ``[B, P, 2o]``, and each patch contracts
    over ``Wy·Wx·Z·U`` bits instead of ``2o`` literals.

    The convolution runs over the frame unfolded along x only,
    ``[B, Y, Bx, Wx·Z·U]`` (the Wx column shifts side by side), with a
    ``Wy x 1`` kernel: every geometry then contracts ``Wx·Z·U`` deep per
    kernel row instead of ``Z·U``, which is 1 for a grey frame and 3 for
    an RGB one.  On a TPU v5e at 256 frames this checked within 5% of
    the plain convolution's time where ``Z·U`` is 1 or 12, and 20–30%
    faster where it is 3 or 9.

    A clause fires iff ``v == 0`` on some patch and it is nonempty, as in
    the other paths; one that includes both ``x`` and ``¬x`` keeps a +1 in
    ``A`` and never fires.  Operands are bf16 (0/1 and ±1 are exact) with
    fp32 accumulation, exact while ``|v| ≤ 2o < 2²⁴``.  ``D`` and ``A``
    are derived from ``include`` inside the step, so a clause-sharded
    bank derives them from its own clauses.  The check runs under
    ``jax.named_scope("clause_conv")``, so a trace names its fusions.

    Args:
      bits: uint8 0/1 ``[B, Y, X, Z, U]`` (``core.ingress.feature_bits``).
      spec: the patch geometry.
      include: uint8 0/1 ``[C, 2o]``.
      nonempty: bool ``[C]``; derived from ``include`` when None.

    Returns:
      uint8 0/1 ``[B, C]`` ORed over patches.
    """
    with jax.named_scope("clause_conv"):
        o, nw = spec.n_features, spec.n_window_features
        b, c = bits.shape[0], include.shape[0]
        inc = (include > 0).astype(jnp.int32)
        plus = inc[:, :o]
        d = (inc[:, o:] - plus).astype(dtype)                  # [C, o]
        zu = spec.channels * spec.therm_bits
        frame = bits.reshape(b, spec.image_y, spec.image_x, zu).astype(dtype)
        span_x = (spec.bx - 1) * spec.stride_x + 1
        cols = jnp.concatenate(
            [frame[:, :, wx : wx + span_x : spec.stride_x]
             for wx in range(spec.window_x)],
            axis=-1,
        )                                                  # [B, Y, Bx, Wx·Z·U]
        kernel = d[:, :nw].T.reshape(spec.window_y, 1, spec.window_x * zu, c)
        viol = jax.lax.conv_general_dilated(
            cols,
            kernel,
            window_strides=(spec.stride_y, 1),
            padding="VALID",
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
            preferred_element_type=jnp.float32,
        ).reshape(b, spec.n_patches, c)                    # [B, P, C]
        offset = jnp.sum(plus, axis=1).astype(jnp.float32)[None]  # [1, C]
        _, _, pos = _index_tables(spec)                    # [P, o − nw]
        if pos.shape[1]:
            offset = offset + jax.lax.dot_general(
                jnp.asarray(pos, dtype),
                d[:, nw:],
                (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )                                              # [P, C]
        fired = jnp.any(viol + offset[None] == 0.0, axis=1)
    if nonempty is None:
        nonempty = clause_nonempty(include)
    return (fired & nonempty[None]).astype(jnp.uint8)


def class_sums(fired: jax.Array, weights: jax.Array) -> jax.Array:
    """Eq. (3): v_i = sum_j w_ij * c_j, as an int32 matmul.

    Args:
      fired:   uint8/int ``[B, C]`` clause outputs.
      weights: int ``[m, C]`` signed clause weights (int8 range on the ASIC).
        int16 weights (frozen from a configuration wider than 8 bits) are
        kept at int16; any other dtype is cast to int8, as it always was.

    Returns:
      int32 ``[B, m]`` class sums.
    """
    op = jnp.int16 if weights.dtype == jnp.int16 else jnp.int8
    return jax.lax.dot_general(
        fired.astype(op),
        weights.astype(op),
        (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.int32,
    )


def argmax_predict(v: jax.Array) -> jax.Array:
    """Eq. (4) with the ASIC's tie rule (Fig. 6): v1 > v0 selects v1, so
    ties resolve to the lowest class index — which is also jnp.argmax's
    first-occurrence rule."""
    return jnp.argmax(v, axis=-1).astype(jnp.int32)


def pack_include(include: jax.Array, n_words: int | None = None) -> jax.Array:
    """[C, 2o] 0/1 include mask -> uint32 [C, W] packed."""
    return pack_bits(include, n_words)
