"""ServableModel: the frozen, serving-ready image of a trained ConvCoTM.

The ASIC holds all clause weights and TA action signals resident in
registers (the 45 056-bit model image, Sec. IV-B) and streams only image
data through the datapath.  This is the software equivalent: ``freeze``
derives every model-side quantity inference needs — include bits, packed
include words, the nonempty mask, int8-clamped weights — exactly once,
so per-batch work touches literals only.  ``core.cotm.infer`` used to
recompute all of these on every call.

A ``ServableModel`` is a pytree (config is static metadata), so it jits,
shards and checkpoints like any other model state.

Clause sparsity (ARCHITECTURE.md §Sparsity)
-------------------------------------------
A trained (or boundary-initialized) clause pool is sparse in two ways
the dense paths ignore:

  * **empty clauses** — zero includes; the ASIC's ``Empty`` signal forces
    their output low (Sec. IV-D), so evaluating their literal products is
    pure waste.  Gorji et al. (clause indexing, PAPERS.md) report 13x
    inference speedups from skipping clauses that cannot match.
  * **include density** — each clause tests only its included literals;
    the packed word ops already exploit this at word granularity, and the
    per-clause include counts let the autotuner and roofline model reason
    about it.

:func:`analyze_sparsity` derives, once per model, the **active-clause
register image**: the indices of nonempty clauses, their include masks
(dense, packed, and the complementary packed *exclude* masks the sparse
kernels consume), per-clause include popcounts, and the weight columns
restricted to active clauses.  Class sums over active clauses equal
class sums over all clauses bit for bit — empty clauses contribute
``w * 0`` — so every sparse path stays bit-identical to ``kernels/ref.py``.

The analysis needs concrete values (the active count becomes an array
*shape*), so it runs eagerly — ``ServingEngine.register`` attaches it;
``freeze`` under jit leaves ``sparsity=None`` and sparse paths fall back
to their dense twins (``serve/paths.py``).

Versioning (ARCHITECTURE.md §Lifecycle)
---------------------------------------
:class:`ServableVersion` is the identity stamp of one served model
version: an engine-assigned monotonic id plus the training provenance
(epoch / step) and a content :func:`servable_digest` of the register
image.  It rides on :class:`ServableModel` as the ``version`` field so
checkpoints and hand-offs carry it, but it is **not** part of the jit
story: ``ServingEngine`` strips the stamp (``version=None``) from the
image it dispatches, so hot-swapping versions of one model never
changes the static jit key and a same-geometry swap compiles nothing.
"""

from __future__ import annotations

import dataclasses
import hashlib
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import clauses as cl
from repro.core.patches import pack_bits

__all__ = [
    "ClauseSparsity",
    "CompositeServable",
    "ServableModel",
    "ServableVersion",
    "active_pad",
    "analyze_sparsity",
    "freeze",
    "freeze_composite",
    "servable_digest",
]


@dataclasses.dataclass(frozen=True)
class ServableVersion:
    """Identity stamp of one served model version.

    ``version`` is the engine-assigned monotonic id per serving slot
    (register -> 1, every swap/rollback increments); ``epoch``/``step``
    are the training cursor the weights came from; ``digest`` is the
    content hash of the register image (:func:`servable_digest`), which
    is what identifies *weights* across rollbacks — a rollback installs
    a fresh monotonic id carrying the prior version's digest.
    """

    version: int = 0
    epoch: int = 0
    step: int = 0
    digest: str = ""

    def as_dict(self) -> Dict:
        return {
            "version": self.version,
            "epoch": self.epoch,
            "step": self.step,
            "digest": self.digest,
        }

    @classmethod
    def from_dict(cls, d) -> "ServableVersion":
        """Parse a checkpoint-manifest stamp; malformed or legacy input
        (pre-version checkpoints have no stamp at all) synthesizes the
        v0 stamp instead of crashing restore."""
        if not isinstance(d, dict):
            return cls()
        try:
            return cls(
                version=int(d.get("version", 0)),
                epoch=int(d.get("epoch", 0)),
                step=int(d.get("step", 0)),
                digest=str(d.get("digest", "")),
            )
        except (TypeError, ValueError):
            return cls()


def servable_digest(servable: "ServableModel") -> str:
    """Content hash (12 hex chars) of a frozen model's functional identity.

    Hashes the include bits, the clamped weights and the config repr —
    everything class sums depend on (``include_packed``/``nonempty``
    derive from ``include``; sparsity/tuned are derived or advisory).
    Two servables with equal digests classify bit-identically.
    """
    h = hashlib.sha256()
    h.update(repr(servable.config).encode())
    for member in getattr(servable, "members", (servable,)):
        h.update(np.asarray(member.include).tobytes())
        h.update(np.asarray(member.weights).tobytes())
    return h.hexdigest()[:12]


@dataclasses.dataclass(frozen=True)
class ClauseSparsity:
    """The active-clause register image (empty clauses pruned at freeze).

    All clause-axis arrays have ``C_a = n_active`` rows — a concrete,
    data-dependent shape, which is why this is derived eagerly and not
    under jit.  ``exclude_packed`` is the full 32-bit complement of
    ``include_packed`` (pad bits beyond 2o are set), so the sparse
    kernels' satisfied-word test ``~(lit | exclude) == 0`` needs no
    extra valid-bit masking.
    """

    active_idx: jax.Array       # int32 [C_a] indices into the full clause pool
    include: jax.Array          # uint8 0/1 [C_a, 2o] active include masks
    include_packed: jax.Array   # uint32 [C_a, W] packed include masks
    exclude_packed: jax.Array   # uint32 [C_a, W] ~include (pad bits set)
    include_counts: jax.Array   # int32 [C_a] include popcount per clause
    weights: jax.Array          # int8 [m, C_a] active weight columns

    @property
    def n_active(self) -> int:
        return self.include.shape[0]

    @property
    def include_density(self) -> float:
        """Mean include fraction over active clauses (0 when none)."""
        if self.n_active == 0 or self.include.shape[1] == 0:
            return 0.0
        return float(np.asarray(self.include_counts).sum()) / (
            self.n_active * self.include.shape[1]
        )


ClauseSparsity = jax.tree_util.register_dataclass(
    ClauseSparsity,
    data_fields=[
        "active_idx",
        "include",
        "include_packed",
        "exclude_packed",
        "include_counts",
        "weights",
    ],
    meta_fields=[],
)


@dataclasses.dataclass(frozen=True)
class ServableModel:
    """Frozen inference artifact (the register-file image).

    ``sparsity`` (optional) is the active-clause image from
    :func:`analyze_sparsity`; ``tuned`` (optional, static metadata) is the
    per-bucket kernel plan from ``serve/autotune.py`` — both ride along
    through placement, jit and checkpointing.  ``version`` (optional,
    static metadata) is the :class:`ServableVersion` lifecycle stamp;
    the serving engine strips it from the dispatched image (see the
    module docstring) so it never perturbs jit cache keys.
    """

    include: jax.Array         # uint8 0/1 [C, 2o] TA action signals
    include_packed: jax.Array  # uint32 [C, W] packed include masks
    nonempty: jax.Array        # bool [C] empty-clause mask (Sec. IV-D)
    weights: jax.Array         # int8 [m, C] clamped clause weights (int16
                               # where config.weight_bits > 8)
    config: "repro.core.cotm.CoTMConfig"
    sparsity: Optional[ClauseSparsity] = None
    tuned: Optional["repro.serve.autotune.TunedPlan"] = None
    version: Optional[ServableVersion] = None

    @property
    def n_clauses(self) -> int:
        return self.include.shape[0]

    @property
    def n_classes(self) -> int:
        return self.weights.shape[0]


ServableModel = jax.tree_util.register_dataclass(
    ServableModel,
    data_fields=["include", "include_packed", "nonempty", "weights", "sparsity"],
    meta_fields=["config", "tuned", "version"],
)


@dataclasses.dataclass(frozen=True)
class CompositeServable:
    """The frozen image of a TM Composite (``core/composites.py``): one
    :class:`ServableModel` per specialist, each with its own geometry,
    weight width and sparsity analysis, under one static
    ``CompositeConfig``.  A pytree like :class:`ServableModel`; it carries
    no tuned plan (a composite is not autotuned)."""

    members: tuple             # ServableModel per specialist
    config: "repro.core.composites.CompositeConfig"
    version: Optional[ServableVersion] = None

    tuned = None


CompositeServable = jax.tree_util.register_dataclass(
    CompositeServable,
    data_fields=["members"],
    meta_fields=["config", "version"],
)


def freeze_composite(model, config) -> CompositeServable:
    """Freeze each specialist of a ``CompositeModel`` under its own config."""
    if len(model.members) != len(config.specialists):
        raise ValueError(
            f"composite model has {len(model.members)} members, its config "
            f"{len(config.specialists)} specialists"
        )
    return CompositeServable(
        members=tuple(freeze(m, c) for m, c in zip(model.members, config.specialists)),
        config=config,
    )


def freeze(model, config) -> ServableModel:
    """Prepare a trained ``CoTMModel`` for serving (one-time, per model).

    Works under jit (``core.cotm.infer`` freezes inline at trace time) and
    eagerly (the serving engine freezes at registration and reuses the
    arrays for every batch thereafter).  Sparsity analysis requires
    concrete values; attach it eagerly with :func:`analyze_sparsity`
    (``ServingEngine.register`` does).
    """
    from repro.core.cotm import weight_dtype, weight_limit

    include = model.include
    lim = weight_limit(config.weight_bits)
    return ServableModel(
        include=include,
        include_packed=pack_bits(include),
        nonempty=cl.clause_nonempty(include),
        weights=jnp.clip(model.weights, -lim, lim).astype(
            weight_dtype(config.weight_bits)
        ),
        config=config,
    )


def active_pad(n_active: int, n_clauses: int) -> int:
    """Pow2-binned active-row count: next power of two >= ``n_active``,
    clamped to the clause-pool size (0 stays 0).

    Sparsity array shapes are part of every jit cache key the servable
    touches, so two trained versions with different active counts would
    compile fresh executables on every hot swap.  Binning the padded row
    count to powers of two bounds the distinct shapes (and with them the
    jit cache growth of a swap storm) at ``log2(n_clauses) + 1`` per
    model — ``ServingEngine.swap`` pads with this policy.
    """
    if n_active <= 0:
        return 0
    return min(1 << (n_active - 1).bit_length(), n_clauses)


def analyze_sparsity(
    servable: ServableModel, *, pad_to: Optional[int | str] = None
) -> ServableModel:
    """Attach the active-clause image to a frozen servable (eager only).

    Idempotent; returns a new :class:`ServableModel` with ``sparsity``
    filled.  A model with NO active clauses yields zero-row arrays — the
    sparse paths still produce the correct all-zero class sums (asserted
    in tests/test_sparse.py's degenerate-servable cases).

    ``pad_to`` (optional, >= the true active count, or the string
    ``"pow2"`` for the :func:`active_pad` bin) pads the analysis to a
    fixed row count with **provably inert** synthetic clauses: an
    all-zero include row packs to an all-ones exclude word (satisfied by
    every input, so it fires) carrying an all-zero weight column — its
    class-sum contribution is exactly 0 on every sparse path, so padded
    and unpadded analyses are bit-identical.  ``ServingEngine.swap``
    pads to the pow2 bins so swap storms reuse warm executables instead
    of compiling one shape per trained version.
    """
    if servable.sparsity is not None:
        return servable
    include = np.asarray(servable.include)
    nonempty = np.asarray(servable.nonempty).astype(bool)
    weights = np.asarray(servable.weights)
    active = np.flatnonzero(nonempty).astype(np.int32)
    if pad_to == "pow2":
        pad_to = active_pad(len(active), servable.n_clauses)
    inc_a = include[active]                                  # [C_a, 2o]
    # Packing is per-clause-row, so the active subset's packed words are a
    # row slice of the freeze-time packing — no second pack_bits pass
    # (the pack-once contract in tests/test_serve.py covers this).
    incp_a = np.asarray(servable.include_packed)[active]
    counts = inc_a.sum(axis=-1).astype(np.int32)
    if pad_to is not None:
        if pad_to < len(active):
            raise ValueError(
                f"pad_to={pad_to} < {len(active)} active clauses — padding "
                f"can only grow the analysis"
            )
        pad = pad_to - len(active)
        if pad:
            inc_a = np.concatenate(
                [inc_a, np.zeros((pad,) + inc_a.shape[1:], inc_a.dtype)]
            )
            incp_a = np.concatenate(
                [incp_a, np.zeros((pad,) + incp_a.shape[1:], incp_a.dtype)]
            )
            counts = np.concatenate([counts, np.zeros(pad, np.int32)])
            weights_a = np.concatenate(
                [weights[:, active], np.zeros((weights.shape[0], pad), weights.dtype)],
                axis=1,
            )
            # -1 marks synthetic rows; no kernel consumes active_idx.
            active = np.concatenate([active, np.full(pad, -1, np.int32)])
        else:
            weights_a = weights[:, active]
    else:
        weights_a = weights[:, active]
    sparsity = ClauseSparsity(
        active_idx=jnp.asarray(active),
        include=jnp.asarray(inc_a.astype(np.uint8)),
        include_packed=jnp.asarray(incp_a),
        exclude_packed=jnp.asarray(~incp_a),                 # pad bits -> 1
        include_counts=jnp.asarray(counts),
        weights=jnp.asarray(weights_a),
    )
    return dataclasses.replace(servable, sparsity=sparsity)
