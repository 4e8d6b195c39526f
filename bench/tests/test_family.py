"""A configuration's code as hooks found by name: the defaults draw what the
parent's functions drew, a configuration with a module of its own runs
through ``run_cell`` with none of the harness edited, a misspelled hook
fails at lookup, and the step metrics count the family's step names."""

import copy
import gzip
import hashlib
import json
import time

import jax
import numpy as np
import pytest

import family
import harness
import tracefile
import work
from cell import run_cell
from test_cell import TINY, _alter_answers

TOY = {"name": "toy-rgb", "source": "bench/tests/data/toy-rgb.json",
       "file": "bench/tests/data/toy-rgb.json", "reduced": [],
       "why": "three-channel frames and a clause bank of its own"}
#: The toy cells and the cell of the same traffic whose metrics they report.
TOY_CELLS = {"toy-bulk": ("bulk", "mnist-bulk"), "toy-sensors": ("sensors", "mnist-sensors")}
SEED = 2**31 + 17


def _toy_spec():
    spec = copy.deepcopy(harness.load_spec())
    spec["configs"].append(TOY)
    for cell, (traffic, like) in TOY_CELLS.items():
        spec["workloads"].append({"name": cell, "config": "toy-rgb", "traffic": traffic,
                                  "chips": 1, "why": "test"})
        for m in spec["end_to_end"] + spec["per_layer"]:
            if like in m.get("workloads", ()):
                m["workloads"].append(cell)
    return spec


def _run_toy(cell, capsys):
    traffic = harness.load_traffic(TOY_CELLS[cell][0])
    traffic.update(TINY[traffic["entry"]])
    run_cell(cell, SEED, 1.0, False, jax.devices()[:1], time.monotonic(),
             spec=_toy_spec(), traffic=traffic, peaks=harness.peaks_for("TPU v5 lite"))
    out = capsys.readouterr().out.strip().splitlines()
    return json.loads(out[-1])


def test_default_family_draws_the_parents_model_and_frames():
    """sha256 of the model arrays and the first bulk pool batch for seed
    2**31 + 17, as the parent's ``system.make_model_arrays`` and
    ``system.make_frames`` drew them."""
    spec = harness.load_spec()
    fam = harness.load_family(spec, "convcotm-mnist")
    assert all(getattr(fam, h) is getattr(family, h) for h in harness.HOOKS)
    cfg = harness.load_config(spec, "convcotm-mnist")
    ta, w = (np.asarray(x) for x in fam.make_model(jax, cfg, SEED))
    batch = fam.make_frames(np.random.default_rng(SEED), 4096, cfg)
    h = hashlib.sha256()
    for a in (ta, w, batch):
        h.update(str((a.shape, a.dtype.str)).encode())
        h.update(a.tobytes())
    assert h.hexdigest() == "8d168405933aacba6f82bb233924ad246424f6babaa92cf0db1d000eba54d841"


def test_toy_family_overrides_every_hook():
    fam = harness.load_family(_toy_spec(), "toy-rgb")
    assert all(getattr(fam, h) is not getattr(family, h) for h in harness.HOOKS)


@pytest.mark.parametrize("cell", sorted(TOY_CELLS))
def test_toy_configuration_runs_correct(cell, capsys):
    res = _run_toy(cell, capsys)
    assert res["correct"] is True, res
    assert res["checks"]["no_rows_compared"]["value"] == 0
    assert res["failed"] == 0 and res["attempted"] > 0
    want = {m["name"] for m in harness.cell_metrics(_toy_spec(), cell, False)}
    assert set(res["metrics"]) == want and "setup_s" in want


def test_toy_reference_tells_its_answers_apart():
    spec = _toy_spec()
    cfg = harness.load_config(spec, "toy-rgb")
    fam = harness.load_family(spec, "toy-rgb")
    model = jax.tree.map(np.asarray, fam.make_model(jax, cfg, SEED))
    frames = fam.make_frames(np.random.default_rng(1), 200, cfg)
    assert frames.shape == (200, 12, 12, 3)
    sums, preds, _ = fam.reference(frames, cfg, model)
    assert len({tuple(r) for r in sums}) > 100 and len(set(preds.tolist())) >= 3
    low, _, _ = fam.reference(frames, cfg, model, weight_bits=4)
    assert (low != sums).any(axis=1).mean() > 0.9


@pytest.mark.parametrize("cell", sorted(TOY_CELLS))
def test_toy_configuration_with_altered_step_is_not_correct(cell, capsys, monkeypatch):
    import repro.serve.engine as eng

    monkeypatch.setattr(eng, "classify_raw_step", _alter_answers(eng.classify_raw_step))
    res = _run_toy(cell, capsys)
    assert res["correct"] is False, res
    assert res["checks"]["rows_wrong"]["value"] > 0


@pytest.mark.parametrize("body", [
    "def make_frame(rng, n, cfg):\n    return None\n",      # a misspelled hook
    "STEP_MODULE = ('jit__classify_raw_step',)\n",           # a misspelled constant
    "class Reference:\n    pass\n",
])
def test_unknown_hook_fails_at_lookup(tmp_path, body):
    (tmp_path / "bad.json").write_text("{}")
    (tmp_path / "bad.py").write_text("from __future__ import annotations\n"
                                     "import numpy as np\nfrom harness import say\n\n"
                                     "def _helper():\n    pass\n\n" + body)
    spec = {"configs": [{"name": "bad", "file": str(tmp_path / "bad.json")}]}
    with pytest.raises(ValueError, match="not hooks"):
        harness.load_family(spec, "bad")


def test_helpers_and_imports_are_not_hooks(tmp_path):
    (tmp_path / "ok.json").write_text("{}")
    (tmp_path / "ok.py").write_text("from __future__ import annotations\n"
                                    "import numpy as np\nfrom harness import say\n\n"
                                    "def _helper():\n    pass\n\n"
                                    "def reference(frames, cfg, model, weight_bits=8):\n"
                                    "    return _helper()\n")
    spec = {"configs": [{"name": "ok", "file": str(tmp_path / "ok.json")}]}
    fam = harness.load_family(spec, "ok")
    assert fam.reference is not family.reference
    assert fam.make_frames is family.make_frames


RECORDED = harness.BENCH_DIR / "tests" / "data" / "v5e_bulk_probe.xplane.pb.gz"
#: Each step metric on the recorded trace, as the parent's reader (which
#: imported ``tracefile.STEP_MODULES``) read it.
PARENT_READS = {"step_roofline.bulk": 3.61215365174092, "step_mfu.bulk": 0.4038265644711055}


@pytest.fixture(scope="module")
def recorded_trace():
    from jax.profiler import ProfileData

    raw = gzip.decompress(RECORDED.read_bytes())
    return tracefile.reduce_xspace(ProfileData.from_serialized_xspace(raw), 1)


@pytest.mark.parametrize("metric", sorted(PARENT_READS))
@pytest.mark.parametrize("names,same", [(family.STEP_MODULES, True),
                                        (("jit__classify_composite",), False)])
def test_step_metrics_count_the_record_step_modules(recorded_trace, metric, names, same):
    cfg = harness.load_config(harness.load_spec(), "convcotm-mnist")
    rec = {"kind": "engine", "trace": recorded_trace, "work": work.frame_work(cfg, 115),
           "peaks": harness.peaks_for("TPU v5 lite"), "chips": 1,
           "frames_per_step_event": 256, "step_modules": names}
    got = harness.read_metric(metric, rec)
    if same:
        assert got == pytest.approx(PARENT_READS[metric], rel=1e-12)
    else:
        assert got is None
