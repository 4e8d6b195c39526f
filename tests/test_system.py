"""End-to-end behaviour tests for the paper's system (the ConvCoTM
accelerator reproduced in JAX) + the launcher drivers."""

import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.convcotm import BOOLEANIZE_METHOD, COTM_CONFIGS
from repro.core import (
    CoTMConfig,
    infer,
    infer_packed,
    init_model,
    pack_model,
    unpack_model,
    update_batch,
)
from repro.core.patches import PatchSpec, extract_patch_features, make_literals, pack_bits
from repro.data import (
    DoubleBufferedLoader,
    PipelineState,
    batches,
    booleanize_split,
    noisy_xor_2d,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class TestPaperConfiguration:
    def test_registry_has_paper_configs(self):
        assert set(COTM_CONFIGS) == {
            "convcotm-mnist", "convcotm-fmnist", "convcotm-kmnist"
        }
        cfg = COTM_CONFIGS["convcotm-mnist"]
        assert cfg.n_clauses == 128 and cfg.n_classes == 10
        assert cfg.patch.n_patches == 361 and cfg.n_literals == 272
        assert BOOLEANIZE_METHOD["convcotm-mnist"] == "threshold"
        assert BOOLEANIZE_METHOD["convcotm-fmnist"] == "adaptive"

    def test_full_inference_path_paper_scale(self):
        """Booleanize -> patches -> 128 clauses -> class sums -> argmax,
        at the exact paper dimensions, via all three eval paths."""
        cfg = COTM_CONFIGS["convcotm-mnist"]
        key = jax.random.PRNGKey(1)
        model = init_model(key, cfg)
        model.ta_state = jax.random.randint(
            key, model.ta_state.shape, 120, 136
        ).astype(jnp.uint8)
        raw = jax.random.randint(key, (16, 28, 28), 0, 256).astype(jnp.uint8)
        imgs = jnp.asarray(booleanize_split(np.asarray(raw), "threshold"))
        preds = {}
        for path in ("dense", "bitpacked", "matmul"):
            c = dataclasses.replace(cfg, eval_path=path)
            p, v = infer(model, imgs, c)
            preds[path] = (np.asarray(p), np.asarray(v))
        np.testing.assert_array_equal(preds["dense"][1], preds["bitpacked"][1])
        np.testing.assert_array_equal(preds["dense"][1], preds["matmul"][1])

    def test_serving_fast_path_packed_literals(self):
        """Host-packed literals (the AXI-stream analogue) give identical
        predictions to the image path."""
        cfg = CoTMConfig(n_clauses=32)
        key = jax.random.PRNGKey(2)
        model = init_model(key, cfg)
        model.ta_state = jax.random.randint(
            key, model.ta_state.shape, 120, 136
        ).astype(jnp.uint8)
        imgs = (jax.random.uniform(key, (4, 28, 28)) > 0.6).astype(jnp.uint8)
        p1, v1 = infer(model, imgs, cfg)
        feats = extract_patch_features(imgs, cfg.patch)
        lp = pack_bits(make_literals(feats))
        p2, v2 = infer_packed(model, lp, cfg)
        np.testing.assert_array_equal(np.asarray(v1), np.asarray(v2))

    def test_register_image_roundtrip_is_deployable(self):
        """Train -> pack to the register image -> unpack -> identical
        inference (the load-model flow of Sec. IV-A/B)."""
        tx, ty, vx, vy = noisy_xor_2d(n_train=600, n_test=100, seed=3)
        tx, vx = booleanize_split(tx), booleanize_split(vx)
        spec = PatchSpec(image_x=4, image_y=4, window_x=2, window_y=2)
        cfg = CoTMConfig(n_clauses=16, n_classes=2, patch=spec, T=15, s=3.0)
        key = jax.random.PRNGKey(4)
        model = init_model(key, cfg)
        txj, tyj = jnp.asarray(tx), jnp.asarray(ty.astype(np.int32))
        for i in range(0, 600, 100):
            key, k = jax.random.split(key)
            model = update_batch(k, model, txj[i:i+100], tyj[i:i+100], cfg)
        blob = pack_model(model, cfg)
        model2 = unpack_model(blob, cfg)
        vxj = jnp.asarray(vx)
        p1, _ = infer(model, vxj, cfg)
        p2, _ = infer(model2, vxj, cfg)
        np.testing.assert_array_equal(np.asarray(p1), np.asarray(p2))


class TestPipeline:
    def test_double_buffered_loader_order(self):
        x = np.arange(40).reshape(10, 2, 2)
        y = np.arange(10)
        it = batches(x, y, batch_size=2, state=PipelineState(seed=1))
        loader = DoubleBufferedLoader(it)
        seen = [int(np.asarray(yb)[0]) for _, yb, _ in loader]
        assert len(seen) == 5

    def test_pipeline_resume_mid_epoch(self):
        x = np.arange(80).reshape(20, 2, 2)
        y = np.arange(20)
        full = [st for _, _, st in batches(x, y, 4, PipelineState(seed=7))]
        resumed = list(batches(x, y, 4, full[1]))
        assert len(resumed) == 3
        tail = list(batches(x, y, 4, PipelineState(seed=7)))[2:]
        for (xa, _, _), (xb, _, _) in zip(resumed, tail):
            np.testing.assert_array_equal(xa, xb)

    def test_pipeline_resume_across_epoch_boundary(self):
        """The cursor yielded with an epoch's final batch must roll over to
        (epoch+1, step=0): resuming from it starts the next epoch instead
        of yielding an empty iterator forever (the old step==n_steps bug)."""
        x = np.arange(48).reshape(12, 2, 2)
        y = np.arange(12)
        states = [st for _, _, st in batches(x, y, 4, PipelineState(seed=3))]
        final = states[-1]
        assert final.epoch == 1 and final.step == 0
        resumed = list(batches(x, y, 4, final))
        assert len(resumed) == 3          # a full next epoch, not empty
        # and it is exactly epoch 1's shuffle
        fresh = list(batches(x, y, 4, PipelineState(epoch=1, seed=3)))
        for (xa, _, _), (xb, _, _) in zip(resumed, fresh):
            np.testing.assert_array_equal(xa, xb)

    def test_pipeline_stale_exhausted_cursor_rolls_forward(self):
        """A pre-fix cursor stuck at step == n_steps (or one saved under a
        larger n_steps) must start the next epoch, not yield nothing."""
        x = np.arange(48).reshape(12, 2, 2)
        y = np.arange(12)
        stale = PipelineState(epoch=0, step=3, seed=3)   # n_steps == 3
        resumed = list(batches(x, y, 4, stale))
        assert len(resumed) == 3
        fresh = list(batches(x, y, 4, PipelineState(epoch=1, seed=3)))
        np.testing.assert_array_equal(resumed[0][0], fresh[0][0])

    def test_epoch_permutations_do_not_collide(self):
        """default_rng(seed + epoch) used to replay the same permutation
        for (seed=3, epoch=0) and (seed=2, epoch=1); the SeedSequence pair
        seeding keeps distinct (seed, epoch) streams distinct."""
        from repro.data import epoch_permutation

        n = 64
        a = epoch_permutation(3, 0, n)
        b = epoch_permutation(2, 1, n)
        assert not np.array_equal(a, b)
        # successive epochs under one seed differ too
        assert not np.array_equal(epoch_permutation(3, 0, n), epoch_permutation(3, 1, n))
        # and the stream is deterministic
        np.testing.assert_array_equal(a, epoch_permutation(3, 0, n))

    def test_composite_inference(self):
        """Two specialists on one booleanized view, served through the
        engine's composite step and checked against the jnp oracle."""
        from repro.core.composites import CompositeConfig, CompositeModel
        from repro.core.cotm import CoTMModel
        from repro.core.patches import make_literals
        from repro.kernels.ref import composite_infer_ref
        from repro.serve import ServingEngine

        spec = PatchSpec(image_x=8, image_y=8, window_x=3, window_y=3)
        cfg = CoTMConfig(n_clauses=8, n_classes=3, patch=spec)
        comp = CompositeConfig(specialists=(cfg, cfg))
        def member(seed):
            # two literals a clause, so clauses fire and the sums move
            rng = np.random.default_rng(seed)
            ta = np.full((8, cfg.n_literals), 50, np.uint8)
            for j in range(8):
                ta[j, rng.choice(cfg.n_literals, 2, replace=False)] = 200
            w = rng.integers(-127, 128, (3, 8)).astype(np.int32)
            return CoTMModel(ta_state=jnp.asarray(ta), weights=jnp.asarray(w))

        m = CompositeModel(members=(member(5), member(6)))
        view = (jax.random.uniform(jax.random.PRNGKey(5), (4, 8, 8)) > 0.5).astype(jnp.uint8)
        engine = ServingEngine(max_batch=4)
        engine.register("comp", m, comp, booleanize=({"method": "none"},) * 2)
        res = engine.classify("comp", np.asarray(view))
        assert res.predictions.shape == (4,) and res.class_sums.shape == (4, 2, 3)
        lits = make_literals(extract_patch_features(view, spec))
        pred, sums, _ = composite_infer_ref(
            [lits, lits], [x.include for x in m.members], [x.weights for x in m.members]
        )
        np.testing.assert_array_equal(res.class_sums, np.asarray(sums))
        np.testing.assert_array_equal(res.predictions, np.asarray(pred))
        assert np.abs(res.class_sums).sum() > 0


class TestDrivers:
    """The TM modes of the two CLI drivers, on the synthetic glyph fallback
    of ``get_dataset`` at a small size."""

    ARCH = "convcotm-mnist"
    KW = dict(epochs=1, batch=50, n_train=100, n_test=50)

    @pytest.fixture(scope="class")
    def ckpt(self, tmp_path_factory):
        """One epoch of ``run_tm_training``, checkpointed."""
        from repro.launch.train import run_tm_training

        ckpt = str(tmp_path_factory.mktemp("tm_ckpt"))
        with pytest.MonkeyPatch.context() as mp:
            mp.setattr("repro.data.datasets.DATA_DIR", ckpt)
            out = run_tm_training(self.ARCH, ckpt_dir=ckpt, **self.KW)
        return ckpt, out

    @pytest.fixture(autouse=True)
    def _synthetic(self, tmp_path, monkeypatch):
        monkeypatch.setattr("repro.data.datasets.DATA_DIR", str(tmp_path))

    def test_training_writes_a_checkpoint(self, ckpt):
        from repro.checkpoint.checkpointer import latest_step

        ckpt_dir, out = ckpt
        assert latest_step(ckpt_dir) == 1
        assert out["epochs"] == 1.0
        assert out["samples_per_s"] > 0
        assert 0.0 <= out["accuracy"] <= 1.0

    def test_training_resumes_and_trains_no_further_epoch(self, ckpt, capsys):
        from repro.checkpoint.checkpointer import latest_step
        from repro.launch.train import run_tm_training

        ckpt_dir, _ = ckpt
        out = run_tm_training(self.ARCH, ckpt_dir=ckpt_dir, **self.KW)
        assert "resumed from epoch 1" in capsys.readouterr().out
        assert out["epochs"] == 1.0
        assert out["samples_per_s"] == 0.0          # no epoch ran
        assert latest_step(ckpt_dir) == 1

    @pytest.mark.parametrize("ingress", ["device", "host"])
    def test_serve_answers_every_frame(self, ckpt, ingress, monkeypatch):
        from repro.launch.serve import serve_tm
        from repro.serve import ServingEngine

        sent = []
        classify = ServingEngine.classify

        def spy(engine, name, frames, **kw):
            res = classify(engine, name, frames, **kw)
            sent.append((len(frames), len(res.predictions), kw.get("ingress")))
            return res

        monkeypatch.setattr(ServingEngine, "classify", spy)
        stats = serve_tm(
            self.ARCH, n_requests=3, max_batch=4, ckpt_dir=ckpt[0], ingress=ingress
        )
        assert len(sent) == 3
        assert all(n == answered and form == ingress for n, answered, form in sent)
        assert stats["requests"] == 3
        assert stats["images"] == sum(n for n, _, _ in sent)

    @pytest.mark.parametrize("driver", ["serve", "train"])
    def test_cli_refuses_a_language_model_arch(self, driver, monkeypatch, capsys):
        import importlib

        main = importlib.import_module(f"repro.launch.{driver}").main
        monkeypatch.setattr(sys, "argv", [driver, "--arch", "h2o-danube-1.8b"])
        with pytest.raises(SystemExit) as exc:
            main()
        assert exc.value.code == 2
        err = capsys.readouterr().err
        assert "h2o-danube-1.8b" in err
        for arch in COTM_CONFIGS:
            assert arch in err
