"""Pure-jnp oracles for the Pallas kernels (the ``ref.py`` layer).

These define the exact semantics the kernels must reproduce; every kernel
test sweeps shapes/dtypes and asserts allclose (bit-equality here — all
outputs are integers) against these functions.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = [
    "clause_eval_ref",
    "class_sum_ref",
    "fused_infer_ref",
    "ingress_pack_ref",
    "clause_eval_sparse_ref",
    "sparse_infer_ref",
    "matmul_sparse_infer_ref",
    "composite_infer_ref",
]


def ingress_pack_ref(bool_images: jax.Array, spec) -> jax.Array:
    """Booleanized images [B, Y, X] -> packed literals uint32 [B, P, W].

    The jnp ingress composition itself (patch gather -> literals -> LSB
    pack); the Pallas ingress kernel must reproduce it bit for bit.
    """
    from repro.core.patches import extract_patch_features, make_literals, pack_bits

    feats = extract_patch_features(bool_images, spec)
    return pack_bits(make_literals(feats), spec.n_words)


def clause_eval_ref(
    lit_packed: jax.Array,      # uint32 [B, P, W]
    include_packed: jax.Array,  # uint32 [C, W]
    nonempty: jax.Array,        # bool/uint8 [C]
) -> jax.Array:
    """Sequential-OR clause outputs, uint8 0/1 [B, C].

    A clause fires on a patch iff every include bit is present in the
    literal word (include & ~lit == 0 for all words); it fires for the
    image iff it fires on >= 1 patch and is nonempty (Eq. 2+6).
    """
    viol = include_packed[None, None] & ~lit_packed[:, :, None, :]
    fires_patch = jnp.all(viol == 0, axis=-1)
    fired = jnp.any(fires_patch, axis=1) & (nonempty.astype(bool))[None]
    return fired.astype(jnp.uint8)


def class_sum_ref(fired: jax.Array, weights: jax.Array) -> jax.Array:
    """Eq. (3): int32 [B, m] = fired [B, C] . weights [m, C]^T."""
    return jax.lax.dot_general(
        fired.astype(jnp.int8),
        weights.astype(jnp.int8),
        (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.int32,
    )


def fused_infer_ref(
    lit_packed: jax.Array,
    include_packed: jax.Array,
    nonempty: jax.Array,
    weights: jax.Array,
) -> jax.Array:
    """Fused clause-eval + class-sum oracle: int32 [B, m] class sums."""
    fired = clause_eval_ref(lit_packed, include_packed, nonempty)
    return class_sum_ref(fired, weights)


# --- clause-sparsity fast path (active clauses only) -----------------------
#
# Inputs come from serve.servable.analyze_sparsity: empty clauses are
# pruned at freeze time, so there is no ``nonempty`` mask here, and the
# model side is the packed EXCLUDE mask (~include, pad bits set).  A
# clause is satisfied by a patch iff every literal word is covered:
# ``~(lit | exclude) == 0`` — identical to ``include & ~lit == 0``.
# Class sums over active clauses equal class sums over the full pool bit
# for bit (empty clauses contribute w * 0); asserted in tests/test_sparse.py.


def clause_eval_sparse_ref(
    lit_packed: jax.Array,      # uint32 [B, P, W]
    exclude_packed: jax.Array,  # uint32 [C_a, W] ~include of active clauses
) -> jax.Array:
    """Sequential-OR outputs of the ACTIVE clauses, uint8 0/1 [B, C_a]."""
    viol = ~(lit_packed[:, :, None, :] | exclude_packed[None, None])
    fires_patch = jnp.all(viol == 0, axis=-1)
    return jnp.any(fires_patch, axis=1).astype(jnp.uint8)


def sparse_infer_ref(
    lit_packed: jax.Array,
    exclude_packed: jax.Array,
    weights_active: jax.Array,  # int8 [m, C_a]
) -> jax.Array:
    """Sparse clause-eval + class-sum oracle: int32 [B, m] class sums."""
    fired = clause_eval_sparse_ref(lit_packed, exclude_packed)
    return class_sum_ref(fired, weights_active)


def matmul_sparse_infer_ref(
    literals: jax.Array,        # uint8 0/1 [B, P, 2o] dense literals
    include_active: jax.Array,  # uint8 0/1 [C_a, 2o]
    weights_active: jax.Array,  # int8 [m, C_a]
) -> jax.Array:
    """int8 matmul violation-count oracle over active clauses.

    violations = (1 - literals) @ include_activeᵀ as an int8 x int8 ->
    int32 dot (counts <= 2o = 272 need the 32-bit accumulator); a clause
    fires on a patch iff it has zero violations.  Returns int32 [B, m].
    """
    neg = (1 - literals).astype(jnp.int8)                    # [B, P, 2o]
    inc = include_active.astype(jnp.int8)                    # [C_a, 2o]
    viol = jax.lax.dot_general(
        neg,
        inc,
        (((neg.ndim - 1,), (1,)), ((), ())),
        preferred_element_type=jnp.int32,
    )                                                        # [B, P, C_a]
    fired = jnp.any(viol == 0, axis=1).astype(jnp.uint8)
    return class_sum_ref(fired, weights_active)


# --- TM Composites (several specialists voting on one frame) ---------------


def composite_infer_ref(literals, includes, weights, weight_bits: int = 8):
    """Plain-jnp oracle of a TM Composite's served step (Table III).

    Per specialist k, from its dense literals ``uint8 [B, P_k, 2o_k]``, its
    include mask ``[C_k, 2o_k]`` and its weights ``int [m, C_k]``: a
    clause holds on a patch iff every literal it includes is 1 (Eq. 2),
    it fires iff it is nonempty and holds on some patch (Eq. 6), and
    ``v_k = Σ_j w_jk c_jk`` in int32 with the weights clamped to
    ``±(2**(weight_bits - 1) - 1)``.  The vote is
    ``Σ_k v_k / max(max_i |v_k,i|, 1)`` in float32 and the prediction the
    first class with the largest vote.

    Returns (predictions int32 ``[B]``, per-specialist class sums int32
    ``[B, K, m]``, votes float32 ``[B, m]``).
    """
    lim = (1 << (weight_bits - 1)) - 1
    with jax.default_matmul_precision("highest"):
        sums = []
        for lits, inc, w in zip(literals, includes, weights):
            inc = jnp.asarray(inc) > 0                               # [C, 2o]
            missing = inc[None, None] & (jnp.asarray(lits)[:, :, None, :] == 0)
            holds = ~jnp.any(missing, axis=-1)                       # [B, P, C]
            fired = jnp.any(holds, axis=1) & jnp.any(inc, axis=-1)[None]
            w = jnp.clip(jnp.asarray(w).astype(jnp.int32), -lim, lim)
            sums.append(
                jnp.sum(fired[:, None, :].astype(jnp.int32) * w[None], axis=-1)
            )
        sums = jnp.stack(sums, axis=1)                               # [B, K, m]
        v = sums.astype(jnp.float32)
        scale = jnp.maximum(jnp.max(jnp.abs(v), axis=-1, keepdims=True), 1.0)
        votes = jnp.sum(v / scale, axis=1)
        return jnp.argmax(votes, axis=-1).astype(jnp.int32), sums, votes
