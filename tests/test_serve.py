"""Serving subsystem: path registry, ServableModel freeze-once contract,
batch bucketing, multi-dataset engine."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.cotm import CoTMConfig, infer, init_boundary_model
from repro.core.patches import PatchSpec
from repro.serve import (
    ServingEngine,
    available_paths,
    freeze,
    get_path,
    register_path,
    run_path,
)

# Edge geometry: B/P/C deliberately not multiples of the kernel block
# sizes (block_b=8, block_c=128, block_p=64): P = 7*7 = 49, C = 37.
EDGE_SPEC = PatchSpec(image_x=11, image_y=11, window_x=5, window_y=5)
EDGE_CFG = CoTMConfig(n_clauses=37, n_classes=10, patch=EDGE_SPEC)
PAPER_CFG = CoTMConfig(n_clauses=64)   # paper geometry, smaller clause pool


def _model(cfg, seed=0):
    return init_boundary_model(jax.random.PRNGKey(seed), cfg)


def _images(cfg, b, seed=0):
    key = jax.random.PRNGKey(seed + 100)
    side = cfg.patch.image_y
    return (jax.random.uniform(key, (b, side, side)) > 0.6).astype(jnp.uint8)


class TestPathRegistry:
    def test_builtin_paths_registered(self):
        assert {"dense", "bitpacked", "matmul", "kernel", "fused"} <= set(
            available_paths()
        )

    def test_unknown_path_raises(self):
        with pytest.raises(KeyError, match="registered"):
            get_path("no-such-path")

    def test_duplicate_registration_rejected(self):
        with pytest.raises(ValueError, match="already registered"):
            register_path("dense", "dense")(lambda *a: None)

    @pytest.mark.parametrize("cfg", [PAPER_CFG, EDGE_CFG], ids=["paper", "edge"])
    @pytest.mark.parametrize("batch", [1, 3])
    def test_all_paths_identical(self, cfg, batch):
        """Every registered path gives identical predictions and class sums
        (the multi-path equivalence contract, incl. padding-edge shapes)."""
        model = _model(cfg, seed=batch)
        imgs = _images(cfg, batch, seed=batch)
        want_p = want_v = None
        for name in available_paths():
            c = dataclasses.replace(cfg, eval_path=name)
            p, v = infer(model, imgs, c)
            p, v = np.asarray(p), np.asarray(v)
            if want_v is None:
                want_p, want_v = p, v
            np.testing.assert_array_equal(want_v, v, err_msg=f"path {name}")
            np.testing.assert_array_equal(want_p, p, err_msg=f"path {name}")

    def test_run_path_matches_infer(self):
        from repro.core.patches import extract_patch_features, make_literals, pack_bits

        model = _model(EDGE_CFG)
        imgs = _images(EDGE_CFG, 4)
        sm = freeze(model, EDGE_CFG)
        lits = make_literals(extract_patch_features(imgs, EDGE_CFG.patch))
        want = np.asarray(infer(model, imgs, EDGE_CFG)[1])
        for name in available_paths():
            path = get_path(name)
            arg = pack_bits(lits) if path.input_form == "packed" else lits
            v = np.asarray(run_path(path, sm, arg))
            np.testing.assert_array_equal(want, v, err_msg=f"path {name}")


class TestServableModel:
    def test_freeze_fields(self):
        model = _model(PAPER_CFG)
        sm = freeze(model, PAPER_CFG)
        np.testing.assert_array_equal(
            np.asarray(sm.include), np.asarray(model.include)
        )
        assert sm.include_packed.dtype == jnp.uint32
        assert sm.weights.dtype == jnp.int8
        assert sm.nonempty.shape == (PAPER_CFG.n_clauses,)
        assert sm.config is PAPER_CFG

    def test_freeze_clamps_weights(self):
        model = _model(PAPER_CFG)
        model.weights = model.weights.at[0, 0].set(300)
        sm = freeze(model, PAPER_CFG)
        assert int(sm.weights[0, 0]) == 127

    def test_servable_is_pytree(self):
        sm = freeze(_model(PAPER_CFG), PAPER_CFG)
        leaves = jax.tree.leaves(sm)
        assert len(leaves) == 4          # config is static metadata
        sm2 = jax.tree.map(lambda x: x, sm)
        assert sm2.config is PAPER_CFG


class TestEngine:
    def _engine(self, cfg=EDGE_CFG, path=None, max_batch=16, seed=0):
        engine = ServingEngine(max_batch=max_batch)
        model = _model(cfg, seed)
        engine.register(
            "glyphs", model, cfg, booleanize_method="none", path=path
        )
        return engine, model

    def test_bucket_for(self):
        engine = ServingEngine(max_batch=16)
        assert [engine.bucket_for(n) for n in (1, 2, 3, 5, 8, 9, 16, 40)] == [
            1, 2, 4, 8, 8, 16, 16, 16
        ]

    def test_padded_bucket_matches_direct_infer(self):
        engine, model = self._engine()
        imgs = _images(EDGE_CFG, 5)      # bucket 8 -> 3 padding rows
        res = engine.classify("glyphs", np.asarray(imgs))
        assert res.bucket == 8
        want_p, want_v = infer(model, imgs, EDGE_CFG)
        np.testing.assert_array_equal(res.predictions, np.asarray(want_p))
        np.testing.assert_array_equal(res.class_sums, np.asarray(want_v))

    def test_oversized_request_is_sliced(self):
        engine, model = self._engine(max_batch=8)
        imgs = _images(EDGE_CFG, 19)     # 8 + 8 + 3
        res = engine.classify("glyphs", np.asarray(imgs))
        assert res.predictions.shape == (19,)
        want_p, _ = infer(model, imgs, EDGE_CFG)
        np.testing.assert_array_equal(res.predictions, np.asarray(want_p))

    @pytest.mark.parametrize("form", ["raw", "literals"])
    @pytest.mark.parametrize("n", [1, 2 * 8 + 3], ids=["one", "three_chunks"])
    def test_copy_back_started_at_launch(self, form, n, monkeypatch):
        """Results are bit-identical to the per-chunk jitted step, every
        output's host copy began at launch and was read once, and a second
        ``result()`` reads nothing more."""
        from repro.serve import classify_raw_step, classify_step

        engine, _ = self._engine(max_batch=8)
        imgs = np.asarray(_images(EDGE_CFG, n, seed=n))
        arr = imgs if form == "raw" else engine.preprocess("glyphs", imgs)
        array_type = type(jnp.zeros(()))
        copy, started = array_type.copy_to_host_async, []
        monkeypatch.setattr(
            array_type, "copy_to_host_async",
            lambda a: (started.append(a.shape), copy(a))[1],
        )
        handle = engine.dispatch("glyphs", arr, preprocessed=form == "literals")
        chunks = -(-n // 8)
        assert len(started) == 2 * chunks       # before anyone asked
        res = handle.result()

        sm, spec = engine.servable("glyphs"), engine.ingress_spec("glyphs")
        want_p, want_v = [], []
        for i in range(0, n, 8):
            chunk = arr[i : i + 8]
            b = engine.bucket_for(len(chunk))
            pad = np.zeros((b - len(chunk),) + chunk.shape[1:], chunk.dtype)
            x = jnp.asarray(np.concatenate([chunk, pad]))
            path, params = engine.resolved_path("glyphs", form, b)
            if form == "raw":
                p, v = classify_raw_step(sm, x, path, spec, params)
            else:
                p, v = classify_step(sm, x, path, params=params)
            want_p.append(np.asarray(p)[: len(chunk)])
            want_v.append(np.asarray(v)[: len(chunk)])
        np.testing.assert_array_equal(res.predictions, np.concatenate(want_p))
        np.testing.assert_array_equal(res.class_sums, np.concatenate(want_v))

        st = engine.stats("glyphs")
        assert st.copies_started == st.copies_read == 2 * chunks
        assert handle.result() is res
        assert engine.stats("glyphs").copies_read == 2 * chunks

    def test_bounded_recompiles(self):
        from repro.serve import engine as engine_mod
        from tools.recompile_guard import no_recompiles

        engine, _ = self._engine()
        sizes = [1, 2, 3, 3, 5, 7, 8, 9, 13, 16, 2, 5]
        buckets = sorted({1 << (n - 1).bit_length() for n in sizes})
        for n in buckets:    # warm every pow2 bucket this traffic can hit
            engine.classify("glyphs", np.asarray(_images(EDGE_CFG, n, seed=n)))
        # every pow2 bucket is now compiled; the mixed-size traffic below
        # must hit those caches only (tools/recompile_guard)
        with no_recompiles(engine_mod.classify_step):
            for n in sizes:
                engine.classify(
                    "glyphs", np.asarray(_images(EDGE_CFG, n, seed=n))
                )
        st = engine.stats("glyphs")
        assert st.requests == 12 + len(set(st.compiled_buckets))
        assert st.images >= 74
        # mixed sizes, but only the pow2 buckets ever compiled.
        assert set(st.compiled_buckets) <= {1, 2, 4, 8, 16}
        assert st.classifications_per_s > 0

    def test_freeze_happens_once_per_model(self, monkeypatch):
        """The pack-once contract: include packing runs at registration,
        never per classify call; the cached ServableModel arrays are
        reused identically across engine calls."""
        import repro.serve.servable as servable_mod

        calls = {"n": 0}
        real = servable_mod.pack_bits

        def counting(*a, **kw):
            calls["n"] += 1
            return real(*a, **kw)

        monkeypatch.setattr(servable_mod, "pack_bits", counting)
        engine, _ = self._engine()
        assert calls["n"] == 1           # one freeze at register time
        sm0 = engine.servable("glyphs")
        for n in (3, 5, 8, 5):
            engine.classify("glyphs", np.asarray(_images(EDGE_CFG, n, seed=n)))
        assert calls["n"] == 1           # no re-freeze on the serve path
        sm1 = engine.servable("glyphs")
        assert sm1 is sm0
        assert sm1.include_packed is sm0.include_packed

    def test_multi_dataset_registry(self):
        engine = ServingEngine(max_batch=8)
        for i, name in enumerate(["mnist", "fmnist", "kmnist"]):
            engine.register(
                name, _model(EDGE_CFG, seed=i), EDGE_CFG, booleanize_method="none"
            )
        assert engine.models() == ("fmnist", "kmnist", "mnist")
        imgs = np.asarray(_images(EDGE_CFG, 4))
        preds = {n: engine.classify(n, imgs).predictions for n in engine.models()}
        # different models -> independent stats
        assert all(engine.stats(n).requests == 1 for n in engine.models())
        assert preds["mnist"].shape == (4,)

    def test_empty_request_rejected(self):
        engine, _ = self._engine()
        with pytest.raises(ValueError, match="empty request"):
            engine.classify("glyphs", np.zeros((0, 11, 11), np.uint8))
        assert engine.stats("glyphs").requests == 0   # stats untouched

    def test_warmup_compiles_without_request_stats(self):
        engine, _ = self._engine(max_batch=8)
        compiled = engine.warmup("glyphs")
        assert compiled == (1, 2, 4, 8)
        st = engine.stats("glyphs")
        assert set(st.compiled_buckets) == {1, 2, 4, 8}
        assert st.requests == 0 and st.total_latency_s == 0.0
        assert st.bucket_hits == {}
        # idempotent: already-compiled buckets are skipped
        assert engine.warmup("glyphs") == ()
        with pytest.raises(ValueError, match="max_batch"):
            engine.warmup("glyphs", buckets=[16])

    def test_warmup_normalizes_nonpow2_buckets(self):
        engine, _ = self._engine(max_batch=16)
        assert engine.warmup("glyphs", buckets=[10]) == (16,)
        st = engine.stats("glyphs")
        assert st.compiled_buckets == (16,) and st.bucket_hits == {}
        # converged: the normalized bucket is now compiled
        assert engine.warmup("glyphs", buckets=[10]) == ()

    def test_unknown_eval_path_fails_at_register(self):
        engine = ServingEngine()
        with pytest.raises(KeyError):
            engine.register(
                "x", _model(EDGE_CFG), EDGE_CFG, path="not-a-path"
            )

    def test_load_checkpoint_roundtrip(self, tmp_path):
        from repro.checkpoint.checkpointer import save_pytree

        model = _model(EDGE_CFG, seed=3)
        save_pytree(model, str(tmp_path), step=1)
        engine = ServingEngine(max_batch=8)
        engine.load_checkpoint(
            "glyphs", str(tmp_path), EDGE_CFG, booleanize_method="none"
        )
        imgs = _images(EDGE_CFG, 4, seed=9)
        res = engine.classify("glyphs", np.asarray(imgs))
        want_p, _ = infer(model, imgs, EDGE_CFG)
        np.testing.assert_array_equal(res.predictions, np.asarray(want_p))

    def test_preprocessed_literals_accepted_when_well_formed(self):
        """preprocessed=True with literals in the path's input form matches
        the raw-image ingress exactly (dense and packed paths)."""
        from repro.data.pipeline import preprocess_for_serving

        for path in ("matmul", "bitpacked"):
            engine, model = self._engine(path=path)
            imgs = np.asarray(_images(EDGE_CFG, 4))
            want = engine.classify("glyphs", imgs)
            lits = preprocess_for_serving(
                imgs, EDGE_CFG.patch, method="none",
                packed=get_path(path).input_form == "packed",
            )
            got = engine.classify("glyphs", lits, preprocessed=True)
            np.testing.assert_array_equal(want.class_sums, got.class_sums)

    def test_preprocessed_wrong_form_rejected(self):
        """Dense literals into a packed path (and vice versa) used to
        silently produce garbage predictions; now they raise."""
        from repro.data.pipeline import preprocess_for_serving

        imgs = np.asarray(_images(EDGE_CFG, 3))
        dense = preprocess_for_serving(imgs, EDGE_CFG.patch, method="none", packed=False)
        packed = preprocess_for_serving(imgs, EDGE_CFG.patch, method="none", packed=True)

        engine_packed, _ = self._engine(path="bitpacked")
        with pytest.raises(ValueError, match="packed uint32"):
            engine_packed.classify("glyphs", dense, preprocessed=True)

        engine_dense, _ = self._engine(path="matmul")
        with pytest.raises(ValueError, match="dense uint8"):
            engine_dense.classify("glyphs", packed, preprocessed=True)

    def test_preprocessed_wrong_shape_or_dtype_rejected(self):
        engine, _ = self._engine(path="matmul")
        spec = EDGE_CFG.patch
        good = np.zeros((2, spec.n_patches, spec.n_literals), np.uint8)
        # wrong trailing dim
        with pytest.raises(ValueError, match="preprocessed literals"):
            engine.classify("glyphs", good[:, :, :-1], preprocessed=True)
        # wrong rank (raw images passed with preprocessed=True)
        with pytest.raises(ValueError, match="preprocessed literals"):
            engine.classify(
                "glyphs", np.zeros((2, 11, 11), np.uint8), preprocessed=True
            )
        # wrong dtype
        with pytest.raises(ValueError, match="preprocessed literals"):
            engine.classify(
                "glyphs", good.astype(np.int32), preprocessed=True
            )
        # stats untouched by rejected requests
        assert engine.stats("glyphs").requests == 0

    def test_booleanize_method_applied(self):
        """Raw uint8 images with a 'threshold' entry match manually
        booleanized inputs through a 'none' entry."""
        from repro.data import booleanize_split

        cfg = EDGE_CFG
        engine = ServingEngine(max_batch=8)
        model = _model(cfg)
        engine.register("raw", model, cfg, booleanize_method="threshold")
        engine.register("pre", model, cfg, booleanize_method="none")
        rng = np.random.default_rng(2)
        raw = rng.integers(0, 256, (4, 11, 11)).astype(np.uint8)
        r1 = engine.classify("raw", raw)
        r2 = engine.classify("pre", booleanize_split(raw, "threshold"))
        np.testing.assert_array_equal(r1.class_sums, r2.class_sums)


class TestCotmDispatch:
    def test_cotm_has_no_eval_path_chain(self):
        """core/cotm.py must resolve paths via the registry, not if/elif."""
        import inspect

        import repro.core.cotm as cotm

        src = inspect.getsource(cotm)
        assert 'eval_path == "' not in src and "eval_path == '" not in src
        assert "get_path" in src

    def test_infer_rejects_unknown_path(self):
        cfg = dataclasses.replace(EDGE_CFG, eval_path="bogus")
        with pytest.raises(KeyError):
            infer(_model(EDGE_CFG), _images(EDGE_CFG, 1), cfg)

    def test_make_tm_serve_fn(self):
        """The serve-step building block matches infer()."""
        from repro.core.patches import extract_patch_features, make_literals, pack_bits
        from repro.train.serve_step import make_tm_serve_fn

        model = _model(EDGE_CFG)
        sm = freeze(model, EDGE_CFG)
        classify = make_tm_serve_fn(sm, path="bitpacked")
        imgs = _images(EDGE_CFG, 3)
        lp = pack_bits(make_literals(extract_patch_features(imgs, EDGE_CFG.patch)))
        p, v = classify(lp)
        want_p, want_v = infer(model, imgs, EDGE_CFG)
        np.testing.assert_array_equal(np.asarray(v), np.asarray(want_v))
        np.testing.assert_array_equal(np.asarray(p), np.asarray(want_p))


# --- matmul's raw form: the folded clause check ----------------------------

#: (geometry, booleanization) per case: each booleanize method and
#: thermometer depth, a stride-2 window and the whole-image window (P=1).
FOLD_CASES = {
    "threshold": (PatchSpec(image_x=28, image_y=28, window_x=10, window_y=10),
                  {"method": "threshold"}),
    "thermometer-1": (PatchSpec(image_x=12, image_y=12, window_x=5, window_y=5,
                                channels=3),
                      {"method": "thermometer", "levels": 1}),
    "thermometer-3": (PatchSpec(image_x=12, image_y=12, window_x=3, window_y=3,
                                channels=3, therm_bits=3),
                      {"method": "thermometer", "levels": 3}),
    "thermometer-4": (PatchSpec(image_x=10, image_y=10, window_x=4, window_y=4,
                                therm_bits=4),
                      {"method": "thermometer", "levels": 4}),
    "adaptive-rgb": (PatchSpec(image_x=12, image_y=12, window_x=5, window_y=5,
                               channels=3),
                     {"method": "adaptive", "block_size": 5, "c": 2.0}),
    "stride-2": (PatchSpec(image_x=13, image_y=13, window_x=3, window_y=3,
                           stride_x=2, stride_y=2),
                 {"method": "threshold"}),
    "whole-image": (PatchSpec(image_x=12, image_y=12, window_x=12, window_y=12,
                              channels=3, therm_bits=3),
                    {"method": "thermometer", "levels": 3}),
}


def _fold_model(cfg, seed=0):
    """Clauses of 1 to 6 features in random polarity; clause 0 is empty
    and clause 1 includes a feature and its negation (it never fires)."""
    from repro.core.cotm import CoTMModel

    rng = np.random.default_rng(seed)
    o = cfg.n_literals // 2
    include = np.zeros((cfg.n_clauses, 2 * o), bool)
    for j in range(2, cfg.n_clauses):
        f = rng.choice(o, rng.integers(1, 7), replace=False)
        include[j, np.where(rng.random(len(f)) < 0.5, f, f + o)] = True
    include[1, [0, o]] = True
    return CoTMModel(
        ta_state=jnp.asarray(np.where(include, 200, 50).astype(np.uint8)),
        weights=jnp.asarray(rng.integers(-127, 128, (cfg.n_classes, cfg.n_clauses)),
                            jnp.int32),
    )


def _fold_frames(spec, n, seed):
    shape = (n, spec.image_y, spec.image_x) + ((spec.channels,) if spec.channels > 1 else ())
    return np.random.default_rng(seed).integers(0, 256, shape).astype(np.uint8)


@pytest.mark.parametrize("bucket", [1, 256])
@pytest.mark.parametrize("case", sorted(FOLD_CASES))
def test_folded_raw_form_equals_dense_and_oracle(case, bucket):
    """The matmul path's raw form (the folded convolution) gives the
    dense path's class sums and the kernels/ref.py oracles', bit for
    bit, and counts one folded check per chunk."""
    from repro.core import clauses as cl
    from repro.core.ingress import IngressSpec, feature_bits
    from repro.data.pipeline import preprocess_for_serving
    from repro.kernels.ref import clause_eval_ref, fused_infer_ref

    spec, boolz = FOLD_CASES[case]
    cfg = CoTMConfig(n_clauses=24, n_classes=10, patch=spec)
    model = _fold_model(cfg, seed=bucket)
    frames = _fold_frames(spec, bucket, seed=bucket)
    kw = {k: v for k, v in boolz.items() if k != "method"}
    got, want = ServingEngine(max_batch=256), ServingEngine(max_batch=256)
    sv = got.register("m", model, cfg, booleanize_method=boolz["method"],
                      booleanize_kw=kw, path="matmul")
    want.register("m", model, cfg, booleanize_method=boolz["method"],
                  booleanize_kw=kw, path="dense")
    res = got.classify("m", frames)
    ref = want.classify("m", frames)
    np.testing.assert_array_equal(res.class_sums, ref.class_sums)
    np.testing.assert_array_equal(res.predictions, ref.predictions)

    lits = jnp.asarray(preprocess_for_serving(frames, spec, **boolz))
    oracle = fused_infer_ref(lits, sv.include_packed, sv.nonempty, sv.weights)
    np.testing.assert_array_equal(res.class_sums, np.asarray(oracle))
    bits = feature_bits(IngressSpec(patch=spec, **boolz), jnp.asarray(frames))
    fired = cl.eval_clauses_folded(bits, spec, sv.include, sv.nonempty)
    np.testing.assert_array_equal(
        fired, clause_eval_ref(lits, sv.include_packed, sv.nonempty))
    assert not np.asarray(fired)[:, :2].any()   # empty, and x with not-x
    assert np.asarray(fired).any()
    assert got.stats("m").folded_checks == 1
    assert got.stats("m").as_dict()["folded_checks"] == 1
    assert want.stats("m").folded_checks == 0


def test_folded_checks_count_raw_chunks_only():
    """Two chunks of a raw request count two; the literal form and
    warmup count none."""
    cfg = CoTMConfig(n_clauses=24, n_classes=10, patch=EDGE_SPEC)
    engine = ServingEngine(max_batch=8)
    engine.register("m", _fold_model(cfg), cfg, path="matmul")
    engine.warmup("m", buckets=[8])
    frames = _fold_frames(EDGE_SPEC, 11, seed=4)
    raw = engine.classify("m", frames)
    assert engine.stats("m").folded_checks == 2
    lits = engine.classify("m", engine.preprocess("m", frames), preprocessed=True)
    np.testing.assert_array_equal(lits.class_sums, raw.class_sums)
    assert engine.stats("m").folded_checks == 2
