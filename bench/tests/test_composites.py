"""The ``cifar10-composites`` hooks on the CPU: the same module run through
``run_cell`` on a tiny composite of the same shape (12x12x3 frames, four
specialists with windows 3/4/12/5 at thermometer depths 3/4/1/1, 40
clauses, literal budget 16, 10-bit weights), entered in the program's
composite registry for the test; and the full-size file checked against
what the program serves."""

import copy
import json
import shutil
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import family
import harness
from cell import run_cell
from test_cell import TINY

NAME = "cifar10-composites"
CELL = "cifar10-composites-bulk"
SEED = 2**31 + 29
TINY_GEOMETRY = ((3, 3, 100, 198), (4, 4, 81, 416), (12, 1, 1, 864), (5, 1, 64, 178))


def _tiny_file(tmp_path):
    """A copy of the configuration's file and module at the tiny size."""
    cfg = harness.load_config(harness.load_spec(), NAME)
    cfg.update(arch="composites-tiny", image_y=12, image_x=12)
    for s, (w, u, p, lits) in zip(cfg["specialists"], TINY_GEOMETRY):
        s.update(window_y=w, window_x=w, therm_bits=u, n_patches=p, n_literals=lits,
                 n_clauses=40)
        if s["booleanize"]["method"] == "thermometer":
            s["booleanize"]["levels"] = u
        else:
            s["booleanize"]["block_size"] = 5
    path = tmp_path / "composites-tiny.json"
    path.write_text(json.dumps(cfg))
    shutil.copy(harness.BENCH_DIR / "configs" / f"{NAME}.py", tmp_path / "composites-tiny.py")
    return cfg, path


@pytest.fixture
def tiny(tmp_path, monkeypatch):
    """(spec, cfg): the tiny configuration and its bulk cell, entered in
    the program's composite registry as the file describes it."""
    from repro.configs import convcotm
    from repro.core.composites import CompositeConfig
    from repro.core.cotm import CoTMConfig
    from repro.core.patches import PatchSpec

    cfg, path = _tiny_file(tmp_path)
    pcfg = CompositeConfig(specialists=tuple(
        CoTMConfig(n_clauses=40, n_classes=10, max_included_literals=16, weight_bits=10,
                   patch=PatchSpec(image_x=12, image_y=12, window_x=w, window_y=w,
                                   channels=3, therm_bits=u))
        for w, u, _, _ in TINY_GEOMETRY))
    monkeypatch.setitem(convcotm.COMPOSITE_CONFIGS, "composites-tiny", pcfg)
    monkeypatch.setitem(convcotm.COMPOSITE_BOOLEANIZE, "composites-tiny",
                        tuple(s["booleanize"] for s in cfg["specialists"]))
    spec = copy.deepcopy(harness.load_spec())
    spec["configs"].append({"name": "composites-tiny", "file": str(path)})
    spec["workloads"].append({"name": "tiny-bulk", "config": "composites-tiny",
                              "traffic": "bulk-composites", "chips": 1, "why": "test"})
    for m in spec["end_to_end"] + spec["per_layer"]:
        if CELL in m.get("workloads", ()):
            m["workloads"].append("tiny-bulk")
    return spec, cfg


def _run(spec, capsys):
    traffic = harness.load_traffic("bulk-composites")
    traffic.update(TINY["engine"])
    run_cell("tiny-bulk", SEED, 1.0, False, jax.devices()[:1], time.monotonic(),
             spec=spec, traffic=traffic, peaks=harness.peaks_for("TPU v5 lite"))
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def test_configuration_overrides_every_hook():
    fam = harness.load_family(harness.load_spec(), NAME)
    assert all(getattr(fam, h) is not getattr(family, h) for h in harness.HOOKS)
    assert fam.STEP_MODULES == ("jit__classify_composite_step",)


def test_tiny_composite_runs_correct(tiny, capsys):
    spec, _ = tiny
    res = _run(spec, capsys)
    assert res["correct"] is True, res
    assert res["checks"]["no_rows_compared"]["value"] == 0
    assert res["failed"] == 0 and res["attempted"] > 0
    assert set(res["metrics"]) == {"cls_per_s", "setup_s"}


def test_tiny_composite_with_altered_step_is_not_correct(tiny, capsys, monkeypatch):
    import repro.serve.engine as eng

    step = eng.classify_composite_step

    def broken(*a, **kw):
        preds, sums = step(*a, **kw)
        return preds, sums.at[:, 3, 0].add(1)        # the last specialist's class 0

    monkeypatch.setattr(eng, "classify_composite_step", broken)
    res = _run(tiny[0], capsys)
    assert res["correct"] is False, res
    assert res["checks"]["rows_wrong"]["value"] > 0


def test_reference_tells_its_answers_apart(tiny):
    spec, cfg = tiny
    fam = harness.load_family(spec, "composites-tiny")
    model = jax.tree.map(np.asarray, fam.make_model(jax, cfg, SEED))
    assert [b["ta"].shape for b in model] == [(40, lits) for *_, lits in TINY_GEOMETRY]
    assert max(int(np.abs(b["weights"]).max()) for b in model) > 127
    frames = fam.make_frames(np.random.default_rng(1), 200, cfg)
    assert frames.shape == (200, 12, 12, 3) and frames.dtype == np.uint8
    sums, preds, ambiguous = fam.reference(frames, cfg, model)
    assert sums.shape == (200, 4, 10)
    assert len({tuple(r.ravel()) for r in sums}) > 100 and len(set(preds.tolist())) >= 3
    assert ambiguous.mean() < 0.5
    low, low_preds, _ = fam.reference(frames, cfg, model, weight_bits=4)
    assert (low != sums).reshape(200, -1).any(axis=1).mean() > 0.9
    assert (low_preds != preds).any()


def test_tiny_composite_served_work_counts_each_specialist(tiny):
    spec, cfg = tiny
    fam = harness.load_family(spec, "composites-tiny")
    model = fam.make_model(jax, cfg, SEED)
    engine, arch = fam.build_engine(cfg, {}, model)
    model = jax.tree.map(np.asarray, model)
    got = fam.served_work(engine, arch, cfg, model)
    active = engine.stats(arch).active_clauses
    assert len(active) == 4 and all(30 <= c < 40 for c in active)
    values = 12 * 12 * 3
    want = 4 * 4 * 10 + values * (3 + 4 + 1 + 2 * 2 * 5 + 1) + sum(
        2 * p * c * lits + 2 * c * 10 for (_, _, p, lits), c in zip(TINY_GEOMETRY, active))
    assert got["ops_per_frame"] == want
    assert got["bytes_per_frame"] == values + 4 * 4 * 10 + 4


@pytest.mark.parametrize("where,key,value", [
    ("top", "weight_bits", 8),
    ("top", "channels", 1),
    (0, "n_clauses", 1024),
    (1, "therm_bits", 2),
    (2, "n_literals", 6000),
    (3, "booleanize", {"method": "adaptive", "block_size": 7, "c": 2.0}),
    (3, "max_included_literals", 32),
])
def test_build_engine_refuses_a_file_that_disagrees(where, key, value):
    cfg = harness.load_config(harness.load_spec(), NAME)
    (cfg if where == "top" else cfg["specialists"][where])[key] = value
    fam = harness.load_family(harness.load_spec(), NAME)
    with pytest.raises(ValueError, match=key):
        fam.build_engine(cfg, {}, None)


def test_file_describes_what_the_program_serves():
    """The published widths, registered and checked at full size (no
    frame is classified)."""
    spec = harness.load_spec()
    cfg = harness.load_config(spec, NAME)
    assert cfg["reduced"] == [] and len(cfg["assumed"]) == 4
    fam = harness.load_family(spec, NAME)
    model = fam.make_model(jax, cfg, SEED)
    engine, arch = fam.build_engine(cfg, {}, model)
    work_ = fam.served_work(engine, arch, cfg, jax.tree.map(np.asarray, model))
    full = sum(2 * s["n_patches"] * s["n_clauses"] * s["n_literals"]
               for s in cfg["specialists"])
    assert full == 2_074_864_000                      # 2.07 G int8 ops a frame
    assert 0.8 * full < work_["ops_per_frame"] < full
    served = engine.servable(arch)
    assert [m.weights.dtype for m in served.members] == [jnp.int16] * 4
