"""The paper's own accelerator configurations (``--arch convcotm-*``).

These are CoTMConfig instances, served and trained through the
``repro.launch`` drivers with the model/inference code of ``repro.core``.
Values follow Sec. III-D / IV:

  * 28x28 booleanized images, 10x10 window, stride 1 -> 361 patches,
    272 literals; 128 clauses; 10 classes; int8 weights.
  * MNIST uses threshold-75 booleanization, FMNIST/KMNIST adaptive
    Gaussian (handled by the data pipeline, method recorded here).
  * Training hyper-parameters (T, s) follow the TMU ConvCoTM defaults the
    paper's models were trained with.
  * cifar10-composites is the envisaged Table III scale-up: 4 TM
    Specialists, 1000 clauses, literal budget 16, 10-bit weights, served
    as one composite (``ServingEngine.register`` with one booleanization
    per specialist, :data:`COMPOSITE_BOOLEANIZE`).
"""

from __future__ import annotations

import dataclasses

from repro.core.composites import CompositeConfig
from repro.core.cotm import CoTMConfig
from repro.core.patches import PatchSpec

__all__ = [
    "COTM_CONFIGS",
    "BOOLEANIZE_METHOD",
    "CIFAR10_COMPOSITES",
    "COMPOSITE_BOOLEANIZE",
    "COMPOSITE_CONFIGS",
]

_PAPER_PATCH = PatchSpec(
    image_x=28, image_y=28, window_x=10, window_y=10, stride_x=1, stride_y=1,
    channels=1, therm_bits=1,
)

CONVCOTM_MNIST = CoTMConfig(n_clauses=128, n_classes=10, patch=_PAPER_PATCH, T=500, s=10.0)
CONVCOTM_FMNIST = dataclasses.replace(CONVCOTM_MNIST)
CONVCOTM_KMNIST = dataclasses.replace(CONVCOTM_MNIST)

BOOLEANIZE_METHOD = {
    "convcotm-mnist": "threshold",
    "convcotm-fmnist": "adaptive",
    "convcotm-kmnist": "adaptive",
}

COTM_CONFIGS = {
    "convcotm-mnist": CONVCOTM_MNIST,
    "convcotm-fmnist": CONVCOTM_FMNIST,
    "convcotm-kmnist": CONVCOTM_KMNIST,
}

# --- Table III: envisaged CIFAR-10 TM-Composites accelerator -------------
# Four specialists; window sizes / booleanizations per Table III.  1000
# clauses each, literal budget 16, 10-bit weights (clamped to +-511 and
# served as int16; see core.cotm.weight_limit).

def _spec(window: int, therm_bits: int) -> PatchSpec:
    return PatchSpec(
        image_x=32, image_y=32, window_x=window, window_y=window,
        stride_x=1, stride_y=1, channels=3, therm_bits=therm_bits,
    )


def _specialist(window: int, therm_bits: int) -> CoTMConfig:
    return CoTMConfig(n_clauses=1000, n_classes=10, patch=_spec(window, therm_bits),
                      T=1500, s=10.0, max_included_literals=16, weight_bits=10)


_SPECIALISTS = (
    _specialist(4, 4),     # 4x4 window, 4-level colour thermometer
    _specialist(3, 3),     # 3x3 window, 3-level colour thermometer
    _specialist(32, 1),    # whole image (HOG-specialist stand-in)
    _specialist(10, 1),    # 10x10 adaptive-thresholding specialist
)

CIFAR10_COMPOSITES = CompositeConfig(specialists=_SPECIALISTS)

#: Composite architectures by name, and each one's booleanization per
#: specialist (the ``booleanize`` argument of ``ServingEngine.register``).
#: The whole-image specialist thresholds each channel at mid-range in
#: place of Table III's HOG features, which the repo does not compute.
COMPOSITE_CONFIGS = {"cifar10-composites": CIFAR10_COMPOSITES}
COMPOSITE_BOOLEANIZE = {
    "cifar10-composites": (
        {"method": "thermometer", "levels": 4},
        {"method": "thermometer", "levels": 3},
        {"method": "thermometer", "levels": 1},
        {"method": "adaptive", "block_size": 11, "c": 2.0},
    ),
}
