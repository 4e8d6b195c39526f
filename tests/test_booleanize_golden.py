"""Golden-value tests: adaptive Gaussian booleanization vs OpenCV.

``adaptive_gaussian_booleanize`` implements the paper's FMNIST/KMNIST
preprocessing (Sec. III-D): ``cv2.adaptiveThreshold(...,
ADAPTIVE_THRESH_GAUSSIAN_C, THRESH_BINARY, block_size, c)``.  The other
booleanize tests only check the JAX code against itself; here it is
pinned to real OpenCV outputs checked into ``tests/data/``
(regenerate with ``tests/data/gen_adaptive_golden.py`` — cv2 is not a
test-time dependency).

Exactness caveat: OpenCV computes the Gaussian local mean in 8-bit
fixed point (its uint8 GaussianBlur path) and rounds it to uint8 before
comparing; the JAX path keeps the local mean (two band-matrix
contractions) in float32.
The two can therefore disagree only for pixels whose value falls within
a few gray levels of the decision boundary ``local_mean - c`` —
empirically the fixed-point mean deviates by up to ~2.5 levels, so the
tests assert bit-equality outside a 3-level band plus a small bounded
mismatch rate overall.  The largest divergence class is the dark halo
around bright strokes on black backgrounds (mean ~ c, so 0-pixels sit
almost exactly on the boundary) — glyph-like images are deliberately in
the probe set to pin that behavior.
"""

import os

import numpy as np
import pytest

from repro.core.booleanize import (
    adaptive_gaussian_booleanize,
    booleanize,
    gaussian_band_matrix,
    gaussian_kernel1d,
)

GOLDEN = os.path.join(os.path.dirname(__file__), "data", "adaptive_golden.npz")


@pytest.fixture(scope="module")
def golden():
    return np.load(GOLDEN)


def _local_mean_reference(img: np.ndarray, block_size: int) -> np.ndarray:
    """Independent numpy Gaussian local mean (separable, edge-replicated)
    used to locate the decision boundary — deliberately not the JAX code
    under test."""
    k = gaussian_kernel1d(block_size).astype(np.float64)
    pad = block_size // 2
    x = img.astype(np.float64)
    x = np.pad(x, ((pad, pad), (0, 0)), mode="edge")
    x = np.apply_along_axis(lambda col: np.convolve(col, k, "valid"), 0, x)
    x = np.pad(x, ((0, 0), (pad, pad)), mode="edge")
    return np.apply_along_axis(lambda row: np.convolve(row, k, "valid"), 1, x)


def _configs(golden):
    return [(int(bs), float(c)) for bs, c in golden["configs"]]


class TestAdaptiveGolden:
    def test_matches_opencv_away_from_quantization_boundary(self, golden):
        """Bit-exact agreement with cv2.adaptiveThreshold wherever the
        pixel is not within OpenCV's fixed-point quantization band (3
        gray levels) of the threshold."""
        images = golden["images"]
        for bs, c in _configs(golden):
            refs = golden[f"ref_b{bs}_c{c:g}"]
            got = np.asarray(adaptive_gaussian_booleanize(images, bs, c))
            assert got.shape == refs.shape and got.dtype == np.uint8
            for img, ref, out in zip(images, refs, got):
                mean = _local_mean_reference(img, bs)
                boundary = np.abs(img.astype(np.float64) - (mean - c)) < 3.0
                disagree = ref != out
                assert not np.any(disagree & ~boundary), (
                    f"b{bs}/c{c}: disagreement away from the rounding "
                    f"boundary at {np.argwhere(disagree & ~boundary)[:4]}"
                )

    def test_mismatch_rate_bounded(self, golden):
        """Boundary-pixel disagreements stay rare (<3.5% per image; the
        worst case is the stroke-halo glyph image, see module doc)."""
        images = golden["images"]
        for bs, c in _configs(golden):
            refs = golden[f"ref_b{bs}_c{c:g}"]
            got = np.asarray(adaptive_gaussian_booleanize(images, bs, c))
            per_image = (refs != got).reshape(len(images), -1).mean(axis=1)
            assert per_image.max() <= 0.035, (bs, c, per_image)

    def test_flat_fields_are_exact(self, golden):
        """Constant images sit c away from the boundary: must be exact
        (all-ones for any c > 0, OpenCV semantics)."""
        images = golden["images"]
        flat = [i for i, im in enumerate(images) if im.min() == im.max()]
        assert flat, "golden set must include flat images"
        for bs, c in _configs(golden):
            refs = golden[f"ref_b{bs}_c{c:g}"]
            got = np.asarray(adaptive_gaussian_booleanize(images, bs, c))
            for i in flat:
                np.testing.assert_array_equal(got[i], refs[i])
                np.testing.assert_array_equal(refs[i], np.ones_like(refs[i]))

    def test_dispatch_method_adaptive_matches_direct(self, golden):
        """booleanize(method='adaptive') is the same code path the
        serving ingress uses for FMNIST/KMNIST entries."""
        images = golden["images"]
        bs, c = _configs(golden)[0]
        np.testing.assert_array_equal(
            np.asarray(booleanize(images, method="adaptive", block_size=bs, c=c)),
            np.asarray(adaptive_gaussian_booleanize(images, bs, c)),
        )


class TestAdaptiveChannels:
    """A channels-last frame is thresholded one channel plane at a time,
    each smoothed over Y and X only, as ``adaptiveThreshold`` on each
    plane; checked against the float64 NumPy local mean above."""

    @pytest.mark.parametrize("shape", [(3, 16, 13), (3, 16, 13, 3), (2, 32, 32, 3)],
                             ids=["gray", "rgb", "rgb-composite"])
    @pytest.mark.parametrize("bs,c", [(5, 2.0), (11, 2.0)])
    def test_each_channel_matches_numpy(self, shape, bs, c):
        rng = np.random.default_rng(bs)
        images = rng.integers(0, 256, shape).astype(np.uint8)
        rgb = len(shape) == 4
        got = np.asarray(adaptive_gaussian_booleanize(images, bs, c, channels_last=rgb))
        assert got.shape == images.shape and got.dtype == np.uint8
        planes = images if not rgb else np.moveaxis(images, -1, 1).reshape(-1, *shape[1:3])
        outs = got if not rgb else np.moveaxis(got, -1, 1).reshape(-1, *shape[1:3])
        for img, out in zip(planes, outs):
            margin = img.astype(np.float64) - (_local_mean_reference(img, bs) - c)
            decided = np.abs(margin) > 1e-3          # float32 against float64
            np.testing.assert_array_equal(out[decided], (margin > 0)[decided])

    def test_channels_do_not_mix(self):
        """A channel's bits depend on that channel alone."""
        rng = np.random.default_rng(0)
        images = rng.integers(0, 256, (2, 12, 12, 3)).astype(np.uint8)
        other = images.copy()
        other[..., 1:] = 255 - other[..., 1:]
        a = np.asarray(adaptive_gaussian_booleanize(images, 5, 2.0, channels_last=True))
        b = np.asarray(adaptive_gaussian_booleanize(other, 5, 2.0, channels_last=True))
        np.testing.assert_array_equal(a[..., 0], b[..., 0])

    def test_convolutions_run_at_highest_precision(self):
        """The local mean of a channels-last frame is two contractions with
        the band matrices, straight on ``[B, Y, X, Z]``; the TPU rounds a
        default-precision float32 contraction through bfloat16, so both ask
        for HIGHEST, and no float convolution is left."""
        import jax

        jaxpr = str(jax.make_jaxpr(
            lambda x: adaptive_gaussian_booleanize(x, 5, 2.0, channels_last=True)
        )(np.zeros((1, 8, 8, 3), np.uint8)))
        dots = jaxpr.count("dot_general[")
        assert dots == 2
        assert jaxpr.count("precision=(Precision.HIGHEST, Precision.HIGHEST)") == dots
        assert "conv_general_dilated[" not in jaxpr


class TestBandMatrix:
    """``gaussian_band_matrix`` is edge replication then the Gaussian
    window along one axis, as one matrix."""

    @pytest.mark.parametrize("n", [8, 28, 32])
    @pytest.mark.parametrize("bs", [5, 11])
    def test_matches_padded_convolution(self, n, bs):
        g = gaussian_band_matrix(n, bs)
        assert g.shape == (n, n) and g.dtype == np.float32
        k = gaussian_kernel1d(bs).astype(np.float64)
        v = np.random.default_rng(n * bs).uniform(0, 1, n)
        want = np.convolve(np.pad(v, bs // 2, mode="edge"), k, "valid")
        np.testing.assert_allclose(g.astype(np.float64) @ v, want, rtol=0, atol=1e-6)
        np.testing.assert_allclose(g.astype(np.float64).sum(axis=1), 1.0, rtol=0, atol=1e-6)
