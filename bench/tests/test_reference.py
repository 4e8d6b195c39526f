"""The plain reference against the program on the CPU, and its control:
the same reference with int4 weights, which the comparison must reject."""

import json

import jax
import numpy as np
import pytest

import family
import harness
import reference
import system
from cell import compare
from control import control_readings

CONFIGS = ["convcotm-mnist", "convcotm-fmnist"]


def _config(name):
    with open(harness.BENCH_DIR / "configs" / f"{name}.json") as f:
        return json.load(f)


@pytest.mark.parametrize("name", CONFIGS)
def test_program_on_cpu_equals_reference(name):
    """On the CPU the program agrees with the reference for both
    booleanizations (the fmnist cell is out of the benchmark because the
    TPU's default precision does not; see PERF.md)."""
    cfg = _config(name)
    ta, w = system.make_model_arrays(jax, cfg, 2**33 + 5)
    engine = system.build_engine(cfg, {}, ta, w)
    frames = system.make_frames(np.random.default_rng(4), 300, 28, 28)
    res = engine.classify(cfg["arch"], frames)
    out = compare([(0, frames, res.class_sums, res.predictions)], cfg,
                  family, (np.asarray(ta), np.asarray(w)))
    assert out["rows_wrong"] == 0 and out["preds_wrong"] == 0
    assert out["rows"] >= 290


@pytest.mark.parametrize("name", CONFIGS)
def test_model_makes_answers_that_differ(name):
    """The model the benchmark makes fires clauses unevenly, so the class
    sums vary from frame to frame and a wrong datapath shows."""
    cfg = _config(name)
    ta, w = (np.asarray(x) for x in system.make_model_arrays(jax, cfg, 9))
    frames = system.make_frames(np.random.default_rng(9), 200, 28, 28)
    sums, preds, _ = reference.class_sums(frames, cfg, ta, w)
    assert len({tuple(r) for r in sums}) > 190
    assert len(set(preds.tolist())) >= 3
    inc = ta >= 128
    assert 0 < (~inc.any(axis=1)).sum() < 0.3 * len(inc)     # some clauses empty


def test_threshold_and_patch_layout():
    cfg = _config("convcotm-mnist")
    frame = np.zeros((1, 28, 28), np.uint8)
    frame[0, 0, 0], frame[0, 27, 27] = 76, 75
    bits, amb = reference.booleanize(frame, cfg["booleanize"])
    assert bits[0, 0, 0] == 1 and bits[0, 27, 27] == 0 and not amb.any()
    iy, ix, pos = reference._tables(cfg)
    assert iy.shape == (361, 100) and pos.shape == (361, 36)
    assert (iy[1, 0], ix[1, 0]) == (0, 1)                  # x moves fastest
    assert pos[19 * 5 + 3].tolist() == [1] * 5 + [0] * 13 + [1] * 3 + [0] * 15


@pytest.mark.parametrize("name", ["mnist-bulk", "mnist-sensors"])
def test_control_fails_the_comparison(name):
    out = control_readings(name, 2**31 + 3, 1.0)
    assert out["rows"] > 0
    assert out["rows_wrong"] > 0.9 * out["rows"]
