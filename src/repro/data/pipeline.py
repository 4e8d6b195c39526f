"""Input pipeline: booleanize -> (optionally bit-pack) -> shard -> prefetch.

Mirrors the ASIC's double-buffered image registers (Sec. IV-C): while batch
k is being classified on device, batch k+1 is already being transferred —
``DoubleBufferedLoader`` keeps one device-resident batch in flight.

For the distributed LM substrate the same loader shards the leading batch
axis over the ("pod", "data") mesh axes with ``jax.device_put`` on a
NamedSharding; for the single-host CPU runs it degenerates to one device.
Pipeline state (epoch cursor + RNG) is checkpointable so a restarted job
resumes mid-epoch (see checkpoint/).
"""

from __future__ import annotations

import dataclasses
from typing import Iterator, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.booleanize import booleanize
from repro.core.ingress import _with_feature_axes
from repro.core.patches import PatchSpec, extract_patch_features, make_literals, pack_bits

__all__ = [
    "PipelineState",
    "batches",
    "booleanize_split",
    "DoubleBufferedLoader",
    "epoch_permutation",
    "literals_host",
    "pack_literals_host",
    "preprocess_for_serving",
]


@dataclasses.dataclass
class PipelineState:
    """Checkpointable cursor: (epoch, step-within-epoch, shuffle seed)."""

    epoch: int = 0
    step: int = 0
    seed: int = 0

    def as_dict(self):
        return dataclasses.asdict(self)

    @classmethod
    def from_dict(cls, d):
        return cls(**d)


def booleanize_split(
    images: np.ndarray, method: str = "threshold", **kw
) -> np.ndarray:
    """Host-side batch booleanization (uint8 0/1)."""
    return np.asarray(booleanize(jnp.asarray(images), method=method, **kw))


def literals_host(bool_images: np.ndarray, spec: PatchSpec) -> np.ndarray:
    """Host-side dense literals uint8 ``[B, P, 2o]`` (patch + negate).

    Accepts ``[B, Y, X]``, or with trailing channel/thermometer axes
    (normalized to the ``[B, Y, X, Z, U]`` layout against ``spec`` —
    4-D thermometer batches used to be rejected here).
    """
    bits = _with_feature_axes(jnp.asarray(bool_images), spec)
    feats = extract_patch_features(bits, spec)
    return np.asarray(make_literals(feats))


def pack_literals_host(
    bool_images: np.ndarray, spec: PatchSpec
) -> np.ndarray:
    """Precompute packed literals for the serving fast path."""
    bits = _with_feature_axes(jnp.asarray(bool_images), spec)
    feats = extract_patch_features(bits, spec)
    return np.asarray(pack_bits(make_literals(feats), spec.n_words))


def preprocess_for_serving(
    raw_images: np.ndarray,
    spec: PatchSpec,
    method: str = "threshold",
    packed: bool = True,
    **booleanize_kw,
) -> np.ndarray:
    """The HOST-side serving ingress: booleanize -> patch -> literals
    [-> pack], with an np.asarray materialization between stages.

    This is the reference/baseline ingress: serving itself now runs the
    same stages fused inside the engine's jitted raw classify graph
    (``repro.core.ingress.apply_ingress`` — bit-identical, asserted in
    ``tests/test_ingress.py``).  Callers that preprocess once and submit
    ``preprocessed=True`` many times still use this path, as do the
    ingress benchmarks.

    ``method='none'`` skips booleanization (inputs already 0/1).
    ``packed`` selects the literal form the chosen eval path prefers.
    """
    x = np.asarray(raw_images)
    if method != "none":
        x = booleanize_split(
            x, method, channels_last=spec.channels > 1, **booleanize_kw
        )
    x = x.astype(np.uint8)
    if packed:
        return pack_literals_host(x, spec)
    return literals_host(x, spec)


def epoch_permutation(seed: int, epoch: int, n: int) -> np.ndarray:
    """The deterministic shuffle of epoch ``epoch`` under ``seed``.

    Seeds a ``SeedSequence`` with the *pair* ``(seed, epoch)`` so distinct
    pairs get independent streams.  (The old ``default_rng(seed + epoch)``
    collided: (seed=3, epoch=0) and (seed=2, epoch=1) replayed the same
    permutation.)  Shared by :func:`batches` and the
    ``repro.train.tm_engine`` epoch pre-batcher, so both walk the dataset
    in the same order for the same cursor.
    """
    rng = np.random.default_rng(np.random.SeedSequence([seed, epoch]))
    return rng.permutation(n)


def batches(
    x: np.ndarray,
    y: np.ndarray,
    batch_size: int,
    state: Optional[PipelineState] = None,
    drop_remainder: bool = True,
) -> Iterator[Tuple[np.ndarray, np.ndarray, PipelineState]]:
    """Shuffled epoch iterator that resumes from a PipelineState cursor.

    Each yielded ``PipelineState`` is the cursor to resume *after* that
    batch; the state yielded with the final batch rolls over to
    ``(epoch + 1, step=0)``, so resuming from it starts the next epoch
    instead of replaying an exhausted iterator.
    """
    state = state or PipelineState()
    n = x.shape[0]
    n_steps = n // batch_size if drop_remainder else (n + batch_size - 1) // batch_size
    if n_steps and state.step >= n_steps:
        # Cursor exhausted on entry (pre-fix checkpoints, or a larger
        # batch_size than the one it was saved under, leaving fewer
        # steps per epoch): start the next epoch instead of yielding
        # nothing forever.
        state = PipelineState(state.epoch + 1, 0, state.seed)
    perm = epoch_permutation(state.seed, state.epoch, n)
    for step in range(state.step, n_steps):
        idx = perm[step * batch_size : (step + 1) * batch_size]
        if step + 1 == n_steps:
            cursor = PipelineState(state.epoch + 1, 0, state.seed)
        else:
            cursor = PipelineState(state.epoch, step + 1, state.seed)
        yield x[idx], y[idx], cursor


class DoubleBufferedLoader:
    """Keeps the next device batch in flight (the ASIC's second image buffer).

    ``sharding`` may be a NamedSharding over the batch axis for multi-device
    runs; jax.device_put is async so the H2D copy of batch k+1 overlaps the
    compute of batch k.
    """

    def __init__(self, it, sharding: Optional[jax.sharding.Sharding] = None):
        self._it = iter(it)
        self._sharding = sharding
        self._next = None
        self._prime()

    def _put(self, batch):
        if self._sharding is None:
            return jax.device_put(batch)
        return jax.device_put(batch, self._sharding)

    def _prime(self):
        try:
            x, y, st = next(self._it)
            self._next = (self._put(x), self._put(y), st)
        except StopIteration:
            self._next = None

    def __iter__(self):
        return self

    def __next__(self):
        if self._next is None:
            raise StopIteration
        out = self._next
        self._prime()
        return out
