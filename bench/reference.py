"""The plain reference: raw frames -> class sums and predictions, in
NumPy, from the configuration file and the model arrays the benchmark
made.  It imports nothing of the program.

It follows the paper's Algorithm 1 (Tunheim et al. 2025, Sec. III):

  * booleanize: ``pixel > 75`` (MNIST), or ``pixel > local mean - c``
    with a separable 11-tap Gaussian window (sigma 2.0, OpenCV's default
    for that size), edge replicated, in float64 (FMNIST/KMNIST);
  * patches: a 10x10 window at stride 1, y outer and x inner; features
    are the window bits row-major, then a y-position and an x-position
    thermometer (position p sets its lowest p bits); literals are the
    features followed by their negations;
  * a clause includes literal k iff its TA state is >= 128; it fires on
    an image iff it is nonempty and some patch holds every literal it
    includes; class sums are the fired clauses times the weights clipped
    to int8; the prediction is the first class with the largest sum.

The Gaussian's mean is real-valued, so a pixel whose margin to its
threshold is inside float32 rounding has no one right bit: frames with
such a pixel are flagged ``ambiguous`` and left out of the comparison.

``weight_bits`` below 8 gives the control: the same reference with its
weights cut to that many bits, which the comparison has to reject.
"""

from __future__ import annotations

import numpy as np

#: A pixel this close to its adaptive threshold is decided by rounding.
AMBIGUOUS_MARGIN = 1e-3


def _gauss1d(size: int) -> np.ndarray:
    sigma = 0.3 * ((size - 1) * 0.5 - 1) + 0.8
    x = np.arange(size, dtype=np.float64) - (size - 1) / 2.0
    k = np.exp(-(x ** 2) / (2.0 * sigma ** 2))
    return k / k.sum()


def _conv_edge(x: np.ndarray, k: np.ndarray, axis: int) -> np.ndarray:
    pad = len(k) // 2
    widths = [(0, 0)] * x.ndim
    widths[axis] = (pad, pad)
    xp = np.pad(x, widths, mode="edge")
    n = x.shape[axis]
    out = np.zeros_like(x)
    for t, kt in enumerate(k):
        out += kt * np.take(xp, np.arange(t, t + n), axis=axis)
    return out


def booleanize(frames: np.ndarray, b: dict):
    """(bits uint8 [n, H, W], ambiguous bool [n])."""
    x = frames.astype(np.float64)
    if b["method"] == "threshold":
        return (x > b["threshold"]).astype(np.uint8), np.zeros(len(x), bool)
    if b["method"] == "adaptive":
        k = _gauss1d(b["block_size"])
        mean = _conv_edge(_conv_edge(x, k, axis=1), k, axis=2)
        margin = x - (mean - b["c"])
        ambiguous = (np.abs(margin) < AMBIGUOUS_MARGIN).any(axis=(1, 2))
        return (margin > 0).astype(np.uint8), ambiguous
    raise ValueError(f"reference has no booleanize method {b['method']!r}")


def _tables(cfg: dict):
    wy, wx, sy, sx = cfg["window_y"], cfg["window_x"], cfg["stride_y"], cfg["stride_x"]
    by = 1 + (cfg["image_y"] - wy) // sy
    bx = 1 + (cfg["image_x"] - wx) // sx
    iy, ix, pos = [], [], []
    ny, nx = cfg["image_y"] - wy, cfg["image_x"] - wx
    for y in range(by):
        for x in range(bx):
            rows = [y * sy + a for a in range(wy) for _ in range(wx)]
            cols = [x * sx + c for _ in range(wy) for c in range(wx)]
            iy.append(rows)
            ix.append(cols)
            pos.append([1 if q < y else 0 for q in range(ny)]
                       + [1 if q < x else 0 for q in range(nx)])
    return np.array(iy), np.array(ix), np.array(pos, np.uint8)


def class_sums(frames: np.ndarray, cfg: dict, ta_state: np.ndarray,
               weights: np.ndarray, *, weight_bits: int = 8, block: int = 64):
    """(class sums int64 [n, m], predictions int64 [n], ambiguous bool [n])."""
    bits, ambiguous = booleanize(frames, cfg["booleanize"])
    iy, ix, pos = _tables(cfg)
    include = (ta_state >= 128)
    nonempty = include.any(axis=1)
    w = np.clip(weights.astype(np.int64), -127, 127)
    if weight_bits < 8:
        step = 2 ** (8 - weight_bits)
        w = np.clip(np.round(w / step), -(2 ** (weight_bits - 1)),
                    2 ** (weight_bits - 1) - 1).astype(np.int64) * step
    inc = include.T.astype(np.float32)                     # [2o, C]
    sums = np.empty((len(frames), w.shape[0]), np.int64)
    for s in range(0, len(frames), block):
        win = bits[s:s + block][:, iy, ix]                 # [b, P, Wy*Wx]
        feats = np.concatenate(
            [win, np.broadcast_to(pos, (len(win),) + pos.shape)], axis=2)
        lits = np.concatenate([feats, 1 - feats], axis=2)  # [b, P, 2o]
        neg = (1 - lits).astype(np.float32).reshape(-1, lits.shape[2])
        viol = (neg @ inc).reshape(lits.shape[0], lits.shape[1], -1)  # [b, P, C]
        fired = (viol == 0).any(axis=1) & nonempty         # [b, C]
        sums[s:s + block] = fired.astype(np.int64) @ w.T
    return sums, sums.argmax(axis=1), ambiguous
