"""Logical-axis sharding rules of the TM serving mesh.

Serving arrays declare *logical* axes; this module maps them onto the
physical axes of the ("data", "model") mesh that ``serve/mesh.py`` builds,
so the serving code never names a physical axis:

  batch   -> "data"    request batches (data parallelism).
  clause  -> "model"   the clause pool (clause-sharded evaluation).
  (None)  -> replicated.

A logical axis whose physical axis the mesh lacks resolves to replicated.
"""

from __future__ import annotations

from typing import Optional, Sequence

from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

__all__ = ["LOGICAL_RULES", "logical_to_physical", "spec", "sharding"]

LOGICAL_RULES = {"batch": "data", "clause": "model"}


def logical_to_physical(axis: Optional[str], mesh: Mesh) -> Optional[str]:
    """One logical axis -> its physical mesh axis, or None if ``mesh`` lacks it."""
    phys = LOGICAL_RULES.get(axis)
    return phys if phys in mesh.axis_names else None


def spec(logical: Sequence[Optional[str]], mesh: Mesh) -> P:
    """Logical axis tuple -> PartitionSpec for ``mesh``."""
    return P(*(logical_to_physical(a, mesh) for a in logical))


def sharding(logical: Sequence[Optional[str]], mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, spec(logical, mesh))
