"""TM Composites (Granmo [17]) — the paper's envisaged scaled-up design.

Table III sketches a CIFAR-10 accelerator running four *TM Specialists*
sequentially on one configurable TM module: each specialist is a ConvCoTM
with its own booleanization and window geometry; per image the specialists'
class sums are normalized, summed, and argmax'd.

The composite is served like any other model: ``ServingEngine.register``
freezes each member (``serve/servable.py:CompositeServable``) and one
jitted step runs every member's eval path on its own ingress of the same
raw frame, then :func:`composite_vote`.  Normalization follows [17]:
v_k <- v_k / max_i |v_k,i| per specialist (scale-free vote merging).
"""

from __future__ import annotations

import dataclasses
from typing import Tuple

import jax
import jax.numpy as jnp

from repro.core.cotm import CoTMConfig, CoTMModel

__all__ = ["CompositeConfig", "CompositeModel", "composite_vote"]


@dataclasses.dataclass(frozen=True)
class CompositeConfig:
    specialists: Tuple[CoTMConfig, ...]

    @property
    def n_classes(self) -> int:
        return self.specialists[0].n_classes


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class CompositeModel:
    members: Tuple[CoTMModel, ...]


def composite_vote(sums: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """The specialists' vote on per-specialist class sums ``int32 [B, K, m]``.

    ``Σ_k v_k / max(max_i |v_k,i|, 1)`` in float32 (a specialist whose sums
    are all 0 adds nothing), then the first class with the largest total.

    Returns:
      (predictions int32 ``[B]``, votes float32 ``[B, m]``).
    """
    v = sums.astype(jnp.float32)
    denom = jnp.maximum(jnp.max(jnp.abs(v), axis=-1, keepdims=True), 1.0)
    total = jnp.sum(v / denom, axis=1)
    return jnp.argmax(total, axis=-1).astype(jnp.int32), total
