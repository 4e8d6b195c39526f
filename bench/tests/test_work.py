"""The counted work depends on the algorithm and the served model, never
on the eval path that runs it."""

import jax
import numpy as np

import harness
import system
import work


def _engine_nonempty(path):
    from repro.configs.convcotm import BOOLEANIZE_METHOD, COTM_CONFIGS
    from repro.core.cotm import CoTMModel
    from repro.serve import ServingEngine

    cfg = harness.load_config(harness.load_spec(), "convcotm-mnist")
    ta, w = system.make_model_arrays(jax, cfg, 3)
    engine = ServingEngine()
    arch = cfg["arch"]
    engine.register(arch, CoTMModel(ta_state=ta, weights=w), COTM_CONFIGS[arch],
                    booleanize_method=BOOLEANIZE_METHOD[arch], path=path)
    return cfg, int(np.asarray(engine.servable(arch).nonempty).sum())


def test_same_work_on_every_eval_path():
    from repro.serve.paths import available_paths

    counted = {}
    for path in available_paths():
        cfg, nonempty = _engine_nonempty(path)
        w = work.frame_work(cfg, nonempty)
        counted[path] = (w["ops_per_frame"], w["bytes_per_frame"], w["model_bytes"])
    assert len(counted) >= 2
    assert len(set(counted.values())) == 1, counted


def test_paper_geometry_counts():
    import json

    mnist = harness.load_config(harness.load_spec(), "convcotm-mnist")
    with open(harness.BENCH_DIR / "configs" / "convcotm-fmnist.json") as f:
        fmnist = json.load(f)
    full = work.frame_work(mnist, 128)
    assert full["ops_per_frame"] == 2 * 361 * 128 * 272 + 2 * 128 * 10 + 784
    assert abs(full["ops_per_frame"] - 25.1e6) < 0.1e6
    assert full["bytes_per_frame"] == 784 + 40 + 4
    assert full["model_bytes"] == 128 * 272 // 8 + 10 * 128
    gauss = work.frame_work(fmnist, 128)["ops_per_frame"] - full["ops_per_frame"]
    assert gauss == 784 * 44
    assert work.frame_work(mnist, 64)["ops_per_frame"] < full["ops_per_frame"]


def test_least_step_time_is_compute_bound_at_256():
    mnist = harness.load_config(harness.load_spec(), "convcotm-mnist")
    w = work.frame_work(mnist, 128)
    peaks = harness.peaks_for("TPU v5 lite")
    t = work.least_step_s(w, peaks, 256)
    assert t == 256 * w["ops_per_frame"] / peaks["int8_ops_per_s"]


def test_unknown_device_has_no_peaks():
    import pytest

    with pytest.raises(KeyError):
        harness.peaks_for("cpu")
