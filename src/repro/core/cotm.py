"""Coalesced convolutional Tsetlin machine (ConvCoTM) model + inference.

The model is a pytree matching the ASIC's programmable state (Sec. IV-B):

  * ``ta_state``: uint8 ``[C, 2o]`` Tsetlin-automaton counters (2N states,
    N = 128).  The *TA action* (include) is ``state >= N`` — the hardware
    keeps only these action bits in its 34 816 model flops; we keep the full
    counters so the same object trains and serves.
  * ``weights``: int32 ``[m, C]`` signed clause weights, clamped to the
    ASIC's int8 range at all times (``CoTMConfig.weight_bits`` widens the
    served clamp: Table III's composites use 10-bit weights).

Inference follows Algorithm 1: booleanize -> patches/literals -> parallel
clause evaluation with sequential OR -> class sums -> argmax.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import clauses as cl
from repro.core.patches import PatchSpec, extract_patch_features, make_literals, pack_bits

__all__ = [
    "CoTMConfig",
    "CoTMModel",
    "GeometryBounds",
    "MAX_GEOMETRY",
    "init_model",
    "init_boundary_model",
    "infer",
    "infer_packed",
    "weight_dtype",
    "weight_limit",
]

TA_HALF = 128          # N: include iff state >= N (8-bit TA, Fig. 1)
WEIGHT_MAX = 127       # int8 two's-complement clamp (Sec. IV-B)
WEIGHT_MIN = -127


def weight_limit(bits: int) -> int:
    """The symmetric clamp of ``bits``-bit signed weights: 127 at 8 bits
    (the ASIC), 511 at 10 (Table III)."""
    return (1 << (bits - 1)) - 1


def weight_dtype(bits: int):
    """The narrowest integer type that holds ``bits``-bit weights."""
    return jnp.int8 if bits <= 8 else jnp.int16


@dataclasses.dataclass(frozen=True)
class GeometryBounds:
    """The maximum model geometry the integer datapath supports.

    These are the bounds the overflow proofs are carried out at:
    ``tools/tmverify`` rule TM404 runs interval analysis over the
    clause-eval / class-sum jaxprs at exactly this envelope and fails if
    any accumulator chain can exceed its dtype at these sizes — so a
    config inside the envelope is served by arithmetic that provably
    cannot overflow, and :class:`CoTMConfig` rejects configs outside it
    rather than serving silently wrong class sums.
    """

    n_clauses: int = 1024      # C   (paper: 128; Table III composites: 1000)
    n_classes: int = 64        # m   (paper: 10)
    n_literals: int = 8192     # 2o  (paper: 272; CIFAR whole-image: 6144)
    n_patches: int = 2048      # P   (paper: 361; CIFAR 3x3 window: 900)
    batch: int = 4096          # B   (engine max_batch default: 256)

    def admits(self, n_clauses: int, n_classes: int, n_literals: int,
               n_patches: int) -> bool:
        return (
            n_clauses <= self.n_clauses
            and n_classes <= self.n_classes
            and n_literals <= self.n_literals
            and n_patches <= self.n_patches
        )


#: The proven envelope (see GeometryBounds).  Growing it requires the
#: TM404 interval proofs to still pass at the new sizes — tier-1 runs
#: ``python -m tools.tmverify`` on every PR, so an envelope bump that
#: breaks an accumulator bound fails CI instead of shipping.
MAX_GEOMETRY = GeometryBounds()


@dataclasses.dataclass(frozen=True)
class CoTMConfig:
    """Static hyper-parameters of a ConvCoTM (paper values as defaults)."""

    n_clauses: int = 128
    n_classes: int = 10
    patch: PatchSpec = dataclasses.field(default_factory=PatchSpec)
    # Training hyper-parameters (TMU-compatible).
    T: int = 500                 # class-sum clip threshold
    s: float = 10.0              # specificity
    boost_true_positive: bool = True
    max_included_literals: Optional[int] = None   # literal budget [42]
    # Width of the served clause weights: 8 on the ASIC (int8 register
    # image), 10 in Table III's composites; frozen to weight_dtype(bits).
    weight_bits: int = 8
    # Any path registered in repro.serve.paths:
    # 'dense' | 'bitpacked' | 'matmul' | 'kernel' | 'fused' | plugins.
    eval_path: str = "matmul"
    # Training-time clause evaluation inside ``core.train.sample_deltas``:
    # 'matmul' (MXU violation-count fast path, bit-identical) | 'dense'
    # (the reference [P, C, 2o] broadcast, kept for equivalence tests and
    # the dense-vs-matmul training benchmark).
    train_eval: str = "matmul"

    def __post_init__(self):
        if not 2 <= self.weight_bits <= 16:
            raise ValueError(f"weight_bits={self.weight_bits} outside [2, 16]")
        if not MAX_GEOMETRY.admits(
            self.n_clauses, self.n_classes,
            self.patch.n_literals, self.patch.n_patches,
        ):
            raise ValueError(
                f"geometry (C={self.n_clauses}, m={self.n_classes}, "
                f"2o={self.patch.n_literals}, P={self.patch.n_patches}) "
                f"exceeds the proven overflow-free envelope {MAX_GEOMETRY}; "
                f"grow GeometryBounds only with the tmverify TM404 proofs "
                f"passing at the new sizes"
            )

    @property
    def n_literals(self) -> int:
        return self.patch.n_literals

    @property
    def model_bits(self) -> int:
        """Register-image size: TA actions + weights (45 056 for paper)."""
        return (
            self.n_clauses * self.n_literals
            + self.n_classes * self.n_clauses * self.weight_bits
        )


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class CoTMModel:
    """Trainable/servable ConvCoTM state (pytree)."""

    ta_state: jax.Array          # uint8 [C, 2o]
    weights: jax.Array           # int32 [m, C]

    @property
    def include(self) -> jax.Array:
        """TA action signals: uint8 0/1 [C, 2o]."""
        return (self.ta_state >= TA_HALF).astype(jnp.uint8)


def init_model(key: jax.Array, config: CoTMConfig) -> CoTMModel:
    """TMU-style init: all TAs at N-1 (weakly exclude); weights random ±1."""
    kw = key
    ta = jnp.full((config.n_clauses, config.n_literals), TA_HALF - 1, jnp.uint8)
    signs = jax.random.bernoulli(kw, 0.5, (config.n_classes, config.n_clauses))
    weights = jnp.where(signs, 1, -1).astype(jnp.int32)
    return CoTMModel(ta_state=ta, weights=weights)


def init_boundary_model(
    key: jax.Array, config: CoTMConfig, spread: int = 10
) -> CoTMModel:
    """Untrained model with TA states straddling the include boundary.

    ``init_model`` puts every TA one step below include, so no clause ever
    fires — degenerate for exercising the inference datapath.  Scattering
    states in ``[N - spread, N + spread)`` gives nondegenerate include
    masks (and, with high probability, some empty clauses) without
    training; used by benchmarks, serving demos and tests.
    """
    k_weights, k_ta = jax.random.split(key)
    model = init_model(k_weights, config)
    model.ta_state = jax.random.randint(
        k_ta, model.ta_state.shape, TA_HALF - spread, TA_HALF + spread
    ).astype(jnp.uint8)
    return model


def _literals_for(images: jax.Array, spec: PatchSpec) -> jax.Array:
    feats = extract_patch_features(images, spec)
    return make_literals(feats)


@functools.partial(jax.jit, static_argnames=("config",))
def infer(
    model: CoTMModel, images: jax.Array, config: CoTMConfig
) -> Tuple[jax.Array, jax.Array]:
    """Algorithm 1 for a batch of booleanized images.

    The evaluation path named by ``config.eval_path`` is resolved through
    the ``repro.serve.paths`` registry; the model-side quantities (include
    bits, packed include words, nonempty mask) come from a ``ServableModel``
    frozen inline at trace time.  Long-running callers should freeze once
    and serve through ``repro.serve.engine`` instead.

    Args:
      model: trained model.
      images: uint8 0/1 ``[B, Y, X]`` (or ``[B, Y, X, Z, U]``).

    Returns:
      (predictions int32 ``[B]``, class sums int32 ``[B, m]``).
    """
    from repro.serve import paths as sp
    from repro.serve.servable import freeze

    sm = freeze(model, config)
    path = sp.get_path(config.eval_path)
    lits = _literals_for(images, config.patch)
    if path.input_form == sp.PACKED:
        lits = pack_bits(lits)
    v = sp.run_path(path, sm, lits)
    return cl.argmax_predict(v), v


@functools.partial(jax.jit, static_argnames=("config", "use_kernel"))
def infer_packed(
    model: CoTMModel,
    lit_packed: jax.Array,
    config: CoTMConfig,
    use_kernel: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    """Inference from pre-packed literals (the serving fast path).

    The data pipeline packs literals once on the host / in an earlier stage;
    this step then touches only 9 uint32 words per patch.  Dispatches to
    ``config.eval_path`` if that path consumes packed literals, else to the
    ``bitpacked`` path; ``use_kernel`` forces the Pallas kernel path.
    """
    from repro.serve import paths as sp
    from repro.serve.servable import freeze

    if use_kernel:
        path = sp.get_path("kernel")
    else:
        path = sp.get_path(config.eval_path)
        if path.input_form != sp.PACKED:
            path = sp.get_path("bitpacked")
    sm = freeze(model, config)
    v = sp.run_path(path, sm, lit_packed)
    return cl.argmax_predict(v), v
