"""Sharded serving across a device mesh.

The chip sustains 60.3k classifications/s because 128 clauses evaluate in
parallel every cycle; the flexible-substrate follow-up (Qin et al.)
replicates the same TM datapath across independent tiles.  The software
analogue is a :class:`ServeMesh`: each registered
:class:`~repro.serve.servable.ServableModel` is placed across a
``("data", "model")`` :class:`jax.sharding.Mesh` and request batches are
sharded along the **data** axis.  One jitted step,
:data:`classify_step_meshed`, runs every placement as an explicit
``shard_map`` (:data:`repro.distributed.collectives.shard_map`): each
device takes its batch shard through the path's ingress and evaluation,
so one program spans N devices and returns a single gathered result.
The per-shard program is required, not a style: a Pallas kernel on a TPU
(a Mosaic custom call) cannot be partitioned by GSPMD.

Two placement contracts, both **bit-identical** to the single-device
engine (asserted in ``tests/test_serve_mesh.py``):

  * **replicated** (the default): the frozen model image lives on every
    device (the 45 056-bit register file is tiny — replication costs
    ~5.6 KiB/device) and only the batch is sharded over "data".  The
    datapath has no cross-batch interaction, so each device classifies
    its batch shard independently and the gathered result equals the
    unsharded run bit for bit.
  * **clause-sharded** (``shard_clauses=True``, for large-clause
    configs): the clause axis ``C`` of ``include``/``include_packed``/
    ``nonempty`` (and the ``C`` column axis of ``weights [m, C]``) is
    additionally split over "model" via the ``"clause"`` logical rule in
    ``sharding/partition.py``.  Each device evaluates its clause shard
    and computes partial class sums with its weight slice; an exact int32
    :func:`~repro.distributed.collectives.psum_tree` over "model"
    combines them — integer addition reorders associatively, so Eq. (3)
    class sums stay bit-identical.

Batch divisibility: jit input shardings require the batch axis to divide
evenly over "data", so the engine's power-of-two buckets are clamped from
below to the data-axis size (which must itself be a power of two
<= ``max_batch``) — every bucket then splits evenly and per-device bucket
accounting is ``bucket // n_data``.

Validated on CPU with ``XLA_FLAGS=--xla_force_host_platform_device_count=8``
(see ARCHITECTURE.md §ServeMesh and the device-count scaling table in
EXPERIMENTS.md §Serve/mesh).
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import clauses as cl
from repro.core.ingress import IngressSpec
from repro.distributed.collectives import psum_tree, shard_map
from repro.serve.paths import Params, get_path, run_path, run_path_raw
from repro.serve.servable import ServableModel
from repro.sharding import partition

__all__ = ["ServeMesh", "make_serve_mesh", "classify_step_meshed"]


@dataclasses.dataclass(frozen=True)
class ServeMesh:
    """A serving placement: device mesh + sharding mode.

    Hashable (the jit static key of :data:`classify_step_meshed`).  ``mesh``
    must carry a "data" axis; ``shard_clauses=True`` additionally
    requires a "model" axis, over which every registered model's clause
    pool is split (``n_clauses`` must divide evenly — validated at
    placement).
    """

    mesh: Mesh
    shard_clauses: bool = False

    def __post_init__(self):
        names = tuple(self.mesh.axis_names)
        if "data" not in names:
            raise ValueError(f'ServeMesh requires a "data" axis; mesh has {names}')
        if self.shard_clauses and "model" not in names:
            raise ValueError(
                f'shard_clauses=True requires a "model" axis; mesh has {names}'
            )

    # --- geometry ---------------------------------------------------------

    @property
    def devices(self) -> int:
        return self.mesh.size

    @property
    def n_data(self) -> int:
        """Batch shards (the data-axis size)."""
        return self.mesh.shape["data"]

    @property
    def n_model(self) -> int:
        """Clause shards (1 when the mesh has no "model" axis)."""
        return self.mesh.shape.get("model", 1)

    def shrunk(self) -> Optional["ServeMesh"]:
        """The next-smaller placement after losing devices on the data
        axis: half the batch shards, model axis (and clause sharding)
        kept.  None when the data axis is already minimal — the caller
        (``ServingEngine.shrink_mesh``) then has nothing left to shed.
        Rebuilt through :func:`make_serve_mesh`, so the surviving grid
        comes from the same ``launch/mesh.py`` device selection as the
        original placement.
        """
        if self.n_data <= 1:
            return None
        return make_serve_mesh(
            self.n_data // 2, self.n_model, shard_clauses=self.shard_clauses
        )

    # --- placement --------------------------------------------------------

    def batch_sharding(self, ndim: int) -> NamedSharding:
        """Leading-axis-over-"data" sharding for an ``ndim``-d batch."""
        return partition.sharding(("batch",) + (None,) * (ndim - 1), self.mesh)

    def place_batch(self, arr: np.ndarray) -> jax.Array:
        """One H2D placement of a padded bucket: rows spread over "data".

        The batch size must divide by :attr:`n_data` — the engine's
        bucket clamp guarantees this for every dispatched bucket.
        """
        if arr.shape[0] % self.n_data:
            raise ValueError(
                f"batch {arr.shape[0]} does not divide over {self.n_data} "
                f"data shards"
            )
        return jax.device_put(arr, self.batch_sharding(arr.ndim))

    def place_servable(self, servable: ServableModel) -> ServableModel:
        """Place a frozen model's register image onto the mesh.

        Replicated mode puts every field on all devices — including the
        sparsity analysis, whose active-clause arrays are as replicable
        as the full register image; clause-sharded mode splits the clause
        axis over "model" (weights on their ``C`` column axis) using the
        ``"clause"`` logical rule.  The active-clause set is NOT
        shard-uniform, so clause-sharded placement drops ``sparsity``
        (sparse eval paths then resolve to their dense fallbacks inside
        the shard_map — see ``serve/paths.py``).  A ``tuned`` plan is
        static metadata and survives either placement.  The lifecycle
        ``version`` stamp is stripped: a placed image is a *dispatch*
        image, and version must never enter jit static keys (the engine
        tracks the stamp on its registry entry — ARCHITECTURE.md
        §Lifecycle).
        """
        if servable.version is not None:
            servable = dataclasses.replace(servable, version=None)
        if not self.shard_clauses:
            rep = NamedSharding(self.mesh, P())
            return dataclasses.replace(
                servable,
                include=jax.device_put(servable.include, rep),
                include_packed=jax.device_put(servable.include_packed, rep),
                nonempty=jax.device_put(servable.nonempty, rep),
                weights=jax.device_put(servable.weights, rep),
                sparsity=(
                    None if servable.sparsity is None
                    else jax.device_put(servable.sparsity, rep)
                ),
            )
        n_clauses = servable.include.shape[0]
        if n_clauses % self.n_model:
            raise ValueError(
                f"n_clauses={n_clauses} does not divide over {self.n_model} "
                f'"model" shards (clause sharding needs an even split)'
            )

        def put(x, logical):
            return jax.device_put(x, partition.sharding(logical, self.mesh))

        return dataclasses.replace(
            servable,
            include=put(servable.include, ("clause", None)),
            include_packed=put(servable.include_packed, ("clause", None)),
            nonempty=put(servable.nonempty, ("clause",)),
            weights=put(servable.weights, (None, "clause")),
            sparsity=None,
        )


def make_serve_mesh(
    data: int = 1, model: int = 1, *, shard_clauses: Optional[bool] = None
) -> ServeMesh:
    """Build a :class:`ServeMesh` over the first ``data * model`` local
    devices (``launch/mesh.py`` owns the device grid).  ``shard_clauses``
    defaults to ``model > 1`` — a mesh with a non-trivial model axis is
    only useful clause-sharded."""
    from repro.launch.mesh import make_serve_device_mesh

    if shard_clauses is None:
        shard_clauses = model > 1
    return ServeMesh(make_serve_device_mesh(data, model), shard_clauses=shard_clauses)


def _classify_meshed(
    servable: ServableModel,
    arr: jax.Array,
    smesh: ServeMesh,
    path_name: str,
    ingress: Optional[IngressSpec],
    params: Params = (),
):
    """Explicit per-shard program: each device runs its batch shard (raw
    ingress included) through the path — over its clause shard, with
    partial class sums psummed over "model", when clause-sharded."""
    # Clause-sharded servables carry no sparsity analysis (placement
    # drops it), so sparse path names resolve to their dense fallbacks
    # inside run_path.
    path = get_path(path_name)
    mesh = smesh.mesh
    if smesh.shard_clauses:
        clause = partition.spec(("clause", None), mesh)
        model_spec = dataclasses.replace(
            servable,
            include=clause,
            include_packed=clause,
            nonempty=partition.spec(("clause",), mesh),
            weights=partition.spec((None, "clause"), mesh),
        )
    else:
        model_spec = P()
    batch = partition.spec(("batch",) + (None,) * (arr.ndim - 1), mesh)

    def body(sv, x):
        if ingress is None:
            v = run_path(path, sv, x, params)
        else:
            # Raw form: the ingress runs on the device's batch shard, in
            # the evaluated path's literal form (run_path_raw adapts it).
            v = run_path_raw(path, sv, x, ingress, params)
        if smesh.shard_clauses:
            v = psum_tree(v, "model")          # [B_local, m] partials
        return cl.argmax_predict(v), v

    return shard_map(
        body,
        mesh=mesh,
        in_specs=(model_spec, batch),
        out_specs=(
            partition.spec(("batch",), mesh),
            partition.spec(("batch", None), mesh),
        ),
    )(servable, arr)


#: The meshed classify step: (placed servable, placed batch) ->
#: (predictions, class_sums), jit-cached per (bucket shape, model config,
#: path, ServeMesh, IngressSpec, params) — ``ingress=None`` is the
#: literal form, an IngressSpec the raw form.
classify_step_meshed = jax.jit(
    _classify_meshed,
    static_argnames=("smesh", "path_name", "ingress", "params"),
)
