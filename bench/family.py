"""The default hooks of a configuration: the code that serves the paper's
single-bank ConvCoTM configurations (``convcotm-mnist``,
``convcotm-fmnist``), moved or wrapped from ``bench/system.py``,
``bench/reference.py`` and ``bench/work.py``.  A module beside a
configuration's file replaces any of them by a function, or a
``STEP_MODULES``, of the same name; ``harness.load_family`` makes the
lookup, and the ``bench/harness.py`` docstring lists the hooks.

``model`` is the pytree ``make_model`` returned: on the device for
``build_engine``, as NumPy arrays for ``served_work`` and ``reference``.
"""

from __future__ import annotations

import numpy as np

import reference as _reference
import system
import tracefile
import work
from harness import say

STEP_MODULES = tracefile.STEP_MODULES
make_model = system.make_model_arrays


def make_frames(rng: np.random.Generator, n: int, cfg: dict) -> np.ndarray:
    return system.make_frames(rng, n, cfg["image_y"], cfg["image_x"])


def build_engine(cfg: dict, traffic: dict, model):
    ta, weights = model
    return system.build_engine(cfg, traffic, ta, weights), cfg["arch"]


def served_work(engine, arch: str, cfg: dict, model) -> dict:
    """The frame's work over the served model's nonempty clauses, which
    have to be the nonempty clauses of the model made."""
    ta, _ = model
    nonempty = int(np.asarray(engine.servable(arch).nonempty).sum())
    made = int((ta >= 128).any(axis=1).sum())
    if nonempty != made:
        raise RuntimeError(f"served model has {nonempty} nonempty clauses, the "
                           f"model made has {made}")
    work_ = work.frame_work(cfg, nonempty)
    say(f"model: nonempty clauses={nonempty}/{cfg['n_clauses']} "
        f"ops/frame={work_['ops_per_frame']} bytes/frame={work_['bytes_per_frame']}")
    return work_


def reference(frames: np.ndarray, cfg: dict, model, weight_bits: int = 8):
    ta, weights = model
    return _reference.class_sums(frames, cfg, ta, weights, weight_bits=weight_bits)
