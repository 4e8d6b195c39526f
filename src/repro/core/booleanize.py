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

    Both passes run at ``Precision.HIGHEST``: at the default precision the
    TPU rounds the float32 convolution through bfloat16, which moves
    pixels near ``local_mean - c`` across the threshold.

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
    if channels_last:
        x = jnp.moveaxis(x, -1, -3)
    batch_shape = x.shape[:-2]
    h, w = x.shape[-2:]
    x2 = x.reshape((-1, h, w))

    k = jnp.asarray(gaussian_kernel1d(block_size))
    pad = block_size // 2
    conv = functools.partial(
        jnp.convolve, mode="valid", precision=jax.lax.Precision.HIGHEST
    )

    # Separable convolution with edge replication.
    xp = jnp.pad(x2, ((0, 0), (pad, pad), (0, 0)), mode="edge")
    # Convolve rows (axis 1).
    xr = jax.vmap(
        lambda img: jax.vmap(lambda col: conv(col, k), in_axes=1, out_axes=1)(img)
    )(xp)
    xp2 = jnp.pad(xr, ((0, 0), (0, 0), (pad, pad)), mode="edge")
    local_mean = jax.vmap(lambda img: jax.vmap(lambda row: conv(row, k))(img))(xp2)

    out = (x2 > (local_mean - c)).astype(jnp.uint8).reshape(batch_shape + (h, w))
    return jnp.moveaxis(out, -3, -1) if channels_last else out


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
