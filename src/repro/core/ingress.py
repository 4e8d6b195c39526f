"""Device-resident inference ingress: raw pixels -> literals, one graph.

The chip classifies 60.3k images/s because booleanized pixels stream
straight into the clause datapath with no intermediate memory traffic
(paper Sec. IV-C).  The software ingress used to be the opposite: the
host pipeline (``data.pipeline.preprocess_for_serving``) round-tripped
every batch host<->device at least three times (booleanize jnp->np, pack
np->jnp->np, then np->device again in classify).  This module is the
fused replacement: :func:`apply_ingress` composes

    booleanize -> patch extraction -> literals -> (optional) bit pack

as pure jnp, so it traces into the *same* jitted graph as clause
evaluation — one H2D copy of raw ``uint8 [B, H, W]`` in, one D2H copy of
predictions out.  All static decisions (method, geometry, thermometer
levels) live in the hashable :class:`IngressSpec`, which is exactly the
jit static-argument key the serving engine uses for its bounded-
recompile contract.

Bit-identity contract: every stage calls the same functions the host
pipeline calls (``core.booleanize``, ``core.patches``), so device-ingress
results equal ``preprocess_for_serving`` bit for bit — asserted across
all booleanize methods in ``tests/test_ingress.py``.

On TPU the packed route can additionally drop into the Pallas ingress
kernel (``kernels/ingress.py``), which keeps even the dense ``[B, P, 2o]``
literal bits in VMEM and writes only packed uint32 words to HBM.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.booleanize import (
    adaptive_gaussian_booleanize,
    thermometer_encode,
    threshold_booleanize,
)
from repro.core.patches import (
    PatchSpec,
    extract_patch_features,
    make_literals,
    pack_bits,
)

__all__ = [
    "IngressSpec",
    "apply_booleanize",
    "apply_ingress",
    "feature_bits",
    "device_ingress",
    "raw_trailing_shape",
]

#: Method aliases: the paper's FMNIST/KMNIST preprocessing is OpenCV's
#: adaptiveThreshold with a Gaussian window; both spellings resolve to
#: the same code path.
_METHOD_ALIASES = {"adaptive_gaussian": "adaptive"}
_METHODS = ("threshold", "adaptive", "thermometer", "none")


@dataclasses.dataclass(frozen=True)
class IngressSpec:
    """Static description of one raw->literals ingress (hashable: this is
    the jit static-argument key of the fused classify step).

    ``method``: 'threshold' (MNIST), 'adaptive'/'adaptive_gaussian'
    (FMNIST/KMNIST), 'thermometer' (scaled-up configs), 'none' (inputs
    already booleanized).  ``packed`` selects the literal form of the
    target eval path.  ``kernel_backend`` steers the packed route:
    ``None`` auto-picks (Pallas on TPU, plain jnp elsewhere), 'interpret'
    forces the Pallas ingress kernel in interpret mode (tests), 'jnp'
    forces the plain composition.
    """

    patch: PatchSpec
    method: str = "threshold"
    packed: bool = True
    threshold: int = 75
    block_size: int = 11
    c: float = 2.0
    levels: int = 1
    kernel_backend: Optional[str] = None

    def __post_init__(self):
        m = _METHOD_ALIASES.get(self.method, self.method)
        if m not in _METHODS:
            raise ValueError(
                f"unknown booleanization method {self.method!r}; "
                f"expected one of {_METHODS} (or 'adaptive_gaussian')"
            )
        if m == "thermometer" and self.levels != self.patch.therm_bits:
            raise ValueError(
                f"thermometer levels={self.levels} must equal the patch "
                f"spec's therm_bits={self.patch.therm_bits}"
            )

    @property
    def resolved_method(self) -> str:
        return _METHOD_ALIASES.get(self.method, self.method)


def raw_trailing_shape(spec: IngressSpec) -> Tuple[int, ...]:
    """Expected trailing dims of a raw input batch for this ingress.

    Grayscale single-bit specs take ``[B, Y, X]``; multi-channel specs
    append ``Z``; pre-booleanized ('none') thermometer inputs also carry
    their ``U`` axis (the thermometer *method* produces U on device, so
    its raw input does not).
    """
    p = spec.patch
    shape: Tuple[int, ...] = (p.image_y, p.image_x)
    if p.channels > 1:
        shape += (p.channels,)
    if spec.resolved_method == "none" and p.therm_bits > 1:
        shape += (p.therm_bits,)
    return shape


def apply_booleanize(spec: IngressSpec, raw: jax.Array) -> jax.Array:
    """The booleanize stage of the ingress (pure jnp, jit-side)."""
    m = spec.resolved_method
    if m == "none":
        return raw.astype(jnp.uint8)
    if m == "threshold":
        return threshold_booleanize(raw, spec.threshold)
    if m == "adaptive":
        return adaptive_gaussian_booleanize(
            raw, spec.block_size, spec.c, channels_last=spec.patch.channels > 1
        )
    # thermometer: appends the U axis (kept even for levels == 1 here;
    # _with_feature_axes normalizes against the patch spec below).
    out = thermometer_encode(raw, spec.levels)
    if spec.levels == 1:
        out = out[..., 0]
    return out


def _with_feature_axes(bits: jax.Array, patch: PatchSpec) -> jax.Array:
    """Normalize booleanized bits to the ``[B, Y, X, Z, U]`` layout
    ``extract_patch_features`` consumes, using the patch spec to
    disambiguate a trailing channel axis from a trailing thermometer
    axis."""
    if bits.ndim == 5:
        return bits
    if bits.ndim == 3:
        return bits[..., None, None]
    if bits.ndim != 4:
        raise ValueError(f"booleanized input must be 3-5D, got {bits.ndim}D")
    if patch.therm_bits > 1 and patch.channels == 1 and bits.shape[-1] == patch.therm_bits:
        return bits[..., None, :]          # [B, Y, X, U] -> [B, Y, X, 1, U]
    if patch.channels > 1 and patch.therm_bits == 1 and bits.shape[-1] == patch.channels:
        return bits[..., :, None]          # [B, Y, X, Z] -> [B, Y, X, Z, 1]
    raise ValueError(
        f"cannot map trailing dim {bits.shape[-1]} onto (Z={patch.channels}, "
        f"U={patch.therm_bits})"
    )


def feature_bits(spec: IngressSpec, raw: jax.Array) -> jax.Array:
    """Raw pixels -> booleanized bits ``uint8 [B, Y, X, Z, U]``, the layout
    the patch window reads (``extract_patch_features``, and the folded
    clause check of ``core.clauses.eval_clauses_folded``)."""
    return _with_feature_axes(apply_booleanize(spec, raw), spec.patch)


def apply_ingress(spec: IngressSpec, raw: jax.Array) -> jax.Array:
    """Raw pixels -> literals in ``spec``'s form, composable under jit.

    Returns dense uint8 ``[B, P, 2o]`` or packed uint32 ``[B, P, W]``.
    No ``np.asarray`` anywhere: the patch index tables are trace-time
    constants and every stage stays on device, so calling this inside a
    jitted classify step fuses the whole raw->predictions path into one
    executable.
    """
    bits = feature_bits(spec, raw)
    if spec.packed and spec.patch.channels == 1 and spec.patch.therm_bits == 1:
        backend = spec.kernel_backend or (
            "pallas" if jax.default_backend() == "tpu" else "jnp"
        )
        if backend != "jnp":
            from repro.kernels.ops import ingress_pack

            return ingress_pack(bits[..., 0, 0], spec.patch, backend=backend)
    feats = extract_patch_features(bits, spec.patch)
    lits = make_literals(feats)
    if spec.packed:
        return pack_bits(lits, spec.patch.n_words)
    return lits


#: Standalone jitted ingress (raw on host -> literals on device in one
#: dispatch).  The serving engine does NOT call this — it inlines
#: :func:`apply_ingress` into its classify step so literals never leave
#: the graph; this entry point serves the training engine's dataset
#: freezing and the ingress benchmarks.
device_ingress = jax.jit(apply_ingress, static_argnums=(0,))
