"""Collectives of the TM mesh paths: the ``shard_map`` the meshed serving
and training steps are built on, a per-leaf ``psum``, and the exact integer
delta reduction of data-parallel training.
"""

from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

__all__ = [
    "tree_psum_batch",
    "shard_map",
    "psum_tree",
]


#: ``jax.shard_map`` with the varying-manual-axes check off: the bodies
#: built on it (here and the clause-sharded serving step in
#: ``serve/mesh.py``) reduce their integer partials with explicit psums.
shard_map = functools.partial(jax.shard_map, check_vma=False)


def psum_tree(tree: Any, axis: str) -> Any:
    """``jax.lax.psum`` every leaf over a named mesh axis.

    Only meaningful inside a ``shard_map``/``pmap`` body.  Integer leaves
    reduce exactly (addition reordering is associative in int32), which is
    what keeps clause-sharded class sums bit-identical to the unsharded
    evaluation."""
    return jax.tree.map(lambda x: jax.lax.psum(x, axis), tree)


def tree_psum_batch(tree: Any, mesh: Mesh | None = None, axis: str = "data") -> Any:
    """Sum each leaf of a per-sample pytree over its leading batch axis.

    The TM data-parallel delta reduction: without a mesh this is a plain
    ``jnp.sum(x, axis=0)``; with a mesh the batch axis is sharded over the
    named ``axis``, each device reduces its local shard, and an exact
    integer ``psum`` combines the partial sums — TA/weight deltas are
    small ints, so the result is bit-identical to the single-device sum.

    Args:
      tree: pytree of arrays ``[B, ...]`` (cast int8 deltas to int32
        *before* calling, so the reduction cannot overflow).
      mesh: optional mesh whose ``axis`` shards the batch dimension (B
        must divide evenly by the axis size).

    Returns:
      pytree of ``[...]`` sums, replicated across ``axis`` when meshed.
    """
    if mesh is None:
        return jax.tree.map(lambda x: jnp.sum(x, axis=0), tree)

    flat, treedef = jax.tree.flatten(tree)
    in_specs = tuple(P(*((axis,) + (None,) * (x.ndim - 1))) for x in flat)
    out_specs = tuple(P(*((None,) * (x.ndim - 1))) for x in flat)

    def body(*leaves):
        return tuple(jax.lax.psum(jnp.sum(x, axis=0), axis) for x in leaves)

    outs = shard_map(
        body, mesh=mesh, in_specs=in_specs, out_specs=out_specs
    )(*flat)
    return jax.tree.unflatten(treedef, list(outs))
