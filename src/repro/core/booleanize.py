"""Booleanization of images for Tsetlin machines.

The paper (Sec. III-D) uses:
  * MNIST:   fixed threshold — pixel > 75 -> 1 else 0 (U = 1 bit/pixel).
  * FMNIST / KMNIST: adaptive Gaussian thresholding (per-pixel local mean
    with a Gaussian window, as in the CTM paper [13] / OpenCV
    ``adaptiveThreshold``).
  * Thermometer encoding (U bits/pixel) is supported for the scaled-up
    TM-Composites configuration (Table III uses 3- and 4-bit color
    thermometers on CIFAR-10).

All functions are pure jnp and jit-compatible; batch axes lead.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

__all__ = [
    "threshold_booleanize",
    "gaussian_kernel1d",
    "gaussian_band_matrix",
    "gaussian_local_mean",
    "adaptive_gaussian_booleanize",
    "thermometer_encode",
    "thermometer_thresholds",
    "booleanize",
]


def threshold_booleanize(images: jax.Array, threshold: int = 75) -> jax.Array:
    """Fixed-threshold booleanization (paper's MNIST setting).

    Args:
      images: uint8/float array ``[..., H, W]`` (or with channel dim).
      threshold: pixels strictly greater than this become 1.

    Returns:
      uint8 array of 0/1, same shape.
    """
    return (images > threshold).astype(jnp.uint8)


def gaussian_kernel1d(size: int, sigma: Optional[float] = None) -> np.ndarray:
    """1-D Gaussian window matching OpenCV's ``getGaussianKernel`` default.

    OpenCV default sigma for a given ksize: 0.3*((ksize-1)*0.5 - 1) + 0.8.
    """
    if sigma is None or sigma <= 0:
        sigma = 0.3 * ((size - 1) * 0.5 - 1) + 0.8
    x = np.arange(size, dtype=np.float64) - (size - 1) / 2.0
    k = np.exp(-(x**2) / (2.0 * sigma**2))
    return (k / k.sum()).astype(np.float32)


def gaussian_band_matrix(n: int, block_size: int) -> np.ndarray:
    """Edge replication then the ``block_size`` Gaussian along an axis of
    length ``n``, as one ``[n, n]`` float32 matrix ``G``:
    ``G @ v == np.convolve(np.pad(v, block_size // 2, mode="edge"), k,
    "valid")`` with ``k = gaussian_kernel1d(block_size)``.

    A clipped tap adds its weight to the edge column, so each row sums to 1.
    Summed in float64, then cast.
    """
    k = gaussian_kernel1d(block_size).astype(np.float64)
    pad = block_size // 2
    rows = np.arange(n)
    g = np.zeros((n, n), np.float64)
    for t, w in enumerate(k):
        g[rows, np.clip(rows + t - pad, 0, n - 1)] += w
    return g.astype(np.float32)


@functools.partial(jax.jit, static_argnames=("block_size", "channels_last"))
def adaptive_gaussian_booleanize(
    images: jax.Array,
    block_size: int = 11,
    c: float = 2.0,
    channels_last: bool = False,
) -> jax.Array:
    """Adaptive Gaussian thresholding (paper's FMNIST/KMNIST setting).

    pixel -> 1 iff pixel > gaussian_local_mean(pixel) - c, computed with a
    separable ``block_size`` Gaussian window and edge replication, which is
    what ``cv2.adaptiveThreshold(..., ADAPTIVE_THRESH_GAUSSIAN_C,
    THRESH_BINARY, block_size, c)`` does.

    The local mean of each plane is ``G_y · x · G_xᵀ``
    (:func:`gaussian_local_mean`, :func:`gaussian_band_matrix`): two
    contractions, over Y and then X, which run on the TPU's matrix unit.
    Both run at ``Precision.HIGHEST``: at a lower precision the TPU rounds
    the float32 band weights through bfloat16, which moves pixels near
    ``local_mean - c`` across the threshold.

    Args:
      images: ``[..., H, W]`` uint8/float, or ``[..., H, W, Z]`` with
        ``channels_last``, where each channel is smoothed on its own over
        H and W (as ``adaptiveThreshold`` on each plane).
      block_size: odd window size.
      c: constant subtracted from the local mean.
      channels_last: the last axis holds channels, not columns.
    """
    if block_size % 2 != 1:
        raise ValueError(f"block_size must be odd, got {block_size}")
    x = images.astype(jnp.float32)
    return (x > (gaussian_local_mean(x, block_size, channels_last) - c)).astype(jnp.uint8)


def gaussian_local_mean(x: jax.Array, block_size: int, channels_last: bool) -> jax.Array:
    """float32 ``G_y · x · G_xᵀ`` of each plane of ``x`` (``[..., H, W]``, or
    ``[..., H, W, Z]`` with ``channels_last``), both contractions at
    ``Precision.HIGHEST``; the local mean of
    :func:`adaptive_gaussian_booleanize`."""
    z = "z" if channels_last else ""
    h, w = x.shape[-3:-1] if channels_last else x.shape[-2:]
    gy = jnp.asarray(gaussian_band_matrix(h, block_size))
    gx = jnp.asarray(gaussian_band_matrix(w, block_size))
    hi = jax.lax.Precision.HIGHEST
    rows = jnp.einsum(f"yi,...ix{z}->...yx{z}", gy, x, precision=hi)
    return jnp.einsum(f"xj,...yj{z}->...yx{z}", gx, rows, precision=hi)


def thermometer_thresholds(levels: int, lo: float = 0.0, hi: float = 255.0) -> np.ndarray:
    """Evenly spaced interior thresholds for a ``levels``-bit thermometer."""
    return np.linspace(lo, hi, levels + 2)[1:-1].astype(np.float32)


@functools.partial(jax.jit, static_argnames=("levels",))
def thermometer_encode(
    images: jax.Array, levels: int, lo: float = 0.0, hi: float = 255.0
) -> jax.Array:
    """Thermometer encoding with ``levels`` bits per value.

    Output shape: ``images.shape + (levels,)`` with bit u set iff
    value > threshold_u; monotone by construction (Buckman et al. [38]).
    For ``levels == 1`` this is a single mid-range threshold.
    """
    th = jnp.asarray(thermometer_thresholds(levels, lo, hi))
    x = images.astype(jnp.float32)[..., None]
    return (x > th).astype(jnp.uint8)


def booleanize(
    images: jax.Array,
    method: str = "threshold",
    threshold: int = 75,
    block_size: int = 11,
    c: float = 2.0,
    levels: int = 1,
    channels_last: bool = False,
) -> jax.Array:
    """Dataset-appropriate booleanization dispatch.

    ``method``: 'threshold' (MNIST), 'adaptive' (alias
    'adaptive_gaussian'; FMNIST/KMNIST), 'thermometer' (multi-bit,
    scaled-up configs).  ``channels_last`` says the last axis holds
    channels; only the adaptive method, which smooths over H and W,
    needs to know.
    Returns ``[..., H, W]`` for U=1 methods, ``[..., H, W, U]`` for
    thermometer with levels > 1.
    """
    if method == "threshold":
        return threshold_booleanize(images, threshold)
    if method in ("adaptive", "adaptive_gaussian"):
        return adaptive_gaussian_booleanize(images, block_size, c, channels_last)
    if method == "thermometer":
        out = thermometer_encode(images, levels)
        if levels == 1:
            out = out[..., 0]
        return out
    raise ValueError(f"unknown booleanization method: {method}")
