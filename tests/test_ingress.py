"""Device-resident ingress tests.

The single-graph raw->predictions contract: the fused device ingress
(``core.ingress``) must be bit-identical to the host pipeline
(``data.pipeline.preprocess_for_serving``) across every booleanize
method and both literal forms; the Pallas ingress-pack kernel must match
the jnp oracle; the engine's raw / host-ingress / preprocessed request
forms and the service's raw submissions must all agree bit for bit.
"""

import asyncio
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.cotm import CoTMConfig, infer, init_boundary_model
from repro.core.ingress import IngressSpec, apply_booleanize, device_ingress
from repro.core.patches import PatchSpec
from repro.data.pipeline import preprocess_for_serving
from repro.kernels import ops, ref
from repro.serve import ServiceConfig, ServingEngine, ServingService

EDGE_SPEC = PatchSpec(image_x=11, image_y=11, window_x=5, window_y=5)
EDGE_CFG = CoTMConfig(n_clauses=37, n_classes=10, patch=EDGE_SPEC)
THERM_SPEC = PatchSpec(image_x=8, image_y=8, window_x=4, window_y=4, therm_bits=3)
STRIDE_SPEC = PatchSpec(
    image_x=12, image_y=12, window_x=4, window_y=4, stride_x=2, stride_y=2
)


def _raw(n, side=11, seed=0, binary=False):
    rng = np.random.default_rng(seed)
    if binary:
        return (rng.random((n, side, side)) > 0.6).astype(np.uint8)
    return rng.integers(0, 256, (n, side, side)).astype(np.uint8)


class TestDeviceIngressEquivalence:
    """apply_ingress == preprocess_for_serving, bit for bit."""

    CASES = [
        ("threshold", EDGE_SPEC, {}),
        ("adaptive", EDGE_SPEC, {"block_size": 5, "c": 2.0}),
        ("adaptive_gaussian", EDGE_SPEC, {"block_size": 5, "c": 2.0}),
        ("thermometer", THERM_SPEC, {"levels": 3}),
        ("none", EDGE_SPEC, {}),
    ]

    @pytest.mark.parametrize("packed", [False, True], ids=["dense", "packed"])
    @pytest.mark.parametrize(
        "method,spec,kw", CASES, ids=[c[0] for c in CASES]
    )
    def test_matches_host_pipeline(self, method, spec, kw, packed):
        raw = _raw(5, side=spec.image_y, seed=3, binary=(method == "none"))
        want = preprocess_for_serving(
            raw, spec, method=method, packed=packed, **kw
        )
        got = np.asarray(
            device_ingress(
                IngressSpec(patch=spec, method=method, packed=packed, **kw),
                jnp.asarray(raw),
            )
        )
        assert got.dtype == want.dtype and got.shape == want.shape
        np.testing.assert_array_equal(want, got, err_msg=f"{method}/packed={packed}")

    def test_adaptive_matches_golden_probe_images(self):
        """On the cv2-pinned golden probe set, the device booleanize stage
        equals the host adaptive path exactly (which test_booleanize_golden
        pins to OpenCV outside the fixed-point band) — so the golden
        anchoring transfers to the fused graph."""
        import os

        g = np.load(
            os.path.join(os.path.dirname(__file__), "data", "adaptive_golden.npz")
        )
        images = g["images"]
        for bs, c in [(int(b), float(c)) for b, c in g["configs"]]:
            spec = IngressSpec(
                patch=PatchSpec(), method="adaptive_gaussian",
                packed=False, block_size=bs, c=c,
            )
            from repro.core.booleanize import adaptive_gaussian_booleanize

            np.testing.assert_array_equal(
                np.asarray(adaptive_gaussian_booleanize(images, bs, c)),
                np.asarray(apply_booleanize(spec, jnp.asarray(images))),
            )
            # And end to end: full literals agree with the host pipeline.
            np.testing.assert_array_equal(
                preprocess_for_serving(
                    images, spec.patch, method="adaptive",
                    packed=False, block_size=bs, c=c,
                ),
                np.asarray(device_ingress(spec, jnp.asarray(images))),
            )

    def test_strided_geometry(self):
        raw = _raw(4, side=12, seed=9)
        spec = IngressSpec(patch=STRIDE_SPEC, method="threshold", packed=True)
        np.testing.assert_array_equal(
            preprocess_for_serving(raw, STRIDE_SPEC, method="threshold", packed=True),
            np.asarray(device_ingress(spec, jnp.asarray(raw))),
        )

    def test_spec_validation(self):
        with pytest.raises(ValueError, match="unknown booleanization"):
            IngressSpec(patch=EDGE_SPEC, method="bogus")
        with pytest.raises(ValueError, match="therm_bits"):
            IngressSpec(patch=EDGE_SPEC, method="thermometer", levels=3)


class TestIngressKernel:
    """The Pallas ingress-pack kernel vs the jnp oracle."""

    @pytest.mark.parametrize(
        "spec",
        [EDGE_SPEC, STRIDE_SPEC, PatchSpec(image_x=14, image_y=14, window_x=6, window_y=6)],
        ids=["edge", "strided", "mid"],
    )
    @pytest.mark.parametrize("b", [1, 5, 8])
    def test_interpret_matches_ref(self, spec, b):
        imgs = jnp.asarray(_raw(b, side=spec.image_y, seed=b, binary=True))
        want = ref.ingress_pack_ref(imgs, spec)
        got = ops.ingress_pack(imgs, spec, backend="interpret")
        np.testing.assert_array_equal(np.asarray(want), np.asarray(got))

    def test_kernel_backend_in_full_ingress(self):
        """IngressSpec(kernel_backend='interpret') routes the packed path
        through the Pallas kernel and still matches the jnp route."""
        raw = _raw(3, seed=2)
        jnp_spec = IngressSpec(patch=EDGE_SPEC, method="threshold", packed=True)
        pl_spec = dataclasses.replace(jnp_spec, kernel_backend="interpret")
        np.testing.assert_array_equal(
            np.asarray(device_ingress(jnp_spec, jnp.asarray(raw))),
            np.asarray(device_ingress(pl_spec, jnp.asarray(raw))),
        )

    def test_fused_infer_from_images(self):
        """The no-dense-literals-in-HBM chain (ingress kernel -> fused
        kernel) equals the oracle composition."""
        from repro.serve import freeze

        model = init_boundary_model(jax.random.PRNGKey(1), EDGE_CFG)
        sm = freeze(model, EDGE_CFG)
        imgs = jnp.asarray(_raw(4, seed=5, binary=True))
        want = ref.fused_infer_ref(
            ref.ingress_pack_ref(imgs, EDGE_SPEC),
            sm.include_packed, sm.nonempty, sm.weights,
        )
        got = ops.fused_infer_from_images(
            imgs, EDGE_SPEC, sm.include_packed, sm.nonempty, sm.weights,
            backend="interpret",
        )
        np.testing.assert_array_equal(np.asarray(want), np.asarray(got))


class TestEngineRawPath:
    def _engine(self, path=None, method="threshold", max_batch=16):
        engine = ServingEngine(max_batch=max_batch)
        model = init_boundary_model(jax.random.PRNGKey(0), EDGE_CFG)
        engine.register("m", model, EDGE_CFG, booleanize_method=method, path=path)
        return engine, model

    @pytest.mark.parametrize("path", ["matmul", "fused"])
    def test_raw_device_matches_host_and_preprocessed(self, path):
        engine, model = self._engine(path=path)
        raw = _raw(5, seed=7)
        dev = engine.classify("m", raw)                       # device ingress
        host = engine.classify("m", raw, ingress="host")      # legacy pipeline
        lits = engine.preprocess("m", raw)
        pre = engine.classify("m", lits, preprocessed=True)
        np.testing.assert_array_equal(dev.class_sums, host.class_sums)
        np.testing.assert_array_equal(dev.class_sums, pre.class_sums)
        np.testing.assert_array_equal(dev.predictions, host.predictions)
        # ... and against the reference inference on booleanized images.
        from repro.data.pipeline import booleanize_split

        want_p, want_v = infer(
            model, jnp.asarray(booleanize_split(raw, "threshold")),
            dataclasses.replace(EDGE_CFG, eval_path=path),
        )
        np.testing.assert_array_equal(dev.predictions, np.asarray(want_p))
        np.testing.assert_array_equal(dev.class_sums, np.asarray(want_v))

    def test_latency_split_recorded(self):
        engine, _ = self._engine()
        res = engine.classify("m", _raw(4, seed=1))
        stages = (res.ingress_s, res.dispatch_s, res.wait_s, res.fetch_s)
        assert res.dispatch_s > 0.0 and min(stages) >= 0.0
        assert res.latency_s == pytest.approx(sum(stages), rel=0.05)
        st = engine.stats("m")
        assert (st.dispatch.count, st.wait.count, st.fetch.count) == (1, 1, 1)
        assert st.dispatch.quantile(0.5) > 0.0
        # Host ingress dominates its split; device path keeps ingress ~free.
        host = engine.classify("m", _raw(4, seed=2), ingress="host")
        assert host.ingress_s > res.ingress_s
        assert host.latency_s == pytest.approx(
            host.ingress_s + host.dispatch_s + host.wait_s + host.fetch_s, rel=0.05
        )
        assert engine.stats("m").dispatch.count == 2

    def test_raw_shape_validated(self):
        engine, _ = self._engine()
        with pytest.raises(ValueError, match="raw images"):
            engine.classify("m", np.zeros((2, 9, 9), np.uint8))
        with pytest.raises(ValueError, match="empty request"):
            engine.classify("m", np.zeros((0, 11, 11), np.uint8))
        assert engine.stats("m").requests == 0

    def test_warmup_covers_raw_form(self):
        """After warmup, raw classifies add no new compiled buckets and
        both request forms execute."""
        engine, _ = self._engine(max_batch=8)
        assert engine.warmup("m") == (1, 2, 4, 8)
        st = engine.stats("m")
        assert set(st.compiled_buckets) == {1, 2, 4, 8}
        engine.classify("m", _raw(3, seed=4))                   # raw bucket 4
        lits = engine.preprocess("m", _raw(3, seed=4))
        engine.classify("m", lits, preprocessed=True)           # literal bucket 4
        st = engine.stats("m")
        assert set(st.compiled_buckets) == {1, 2, 4, 8}         # still warm
        assert engine.warmup("m") == ()                         # idempotent

    def test_booleanize_kw_applies_to_both_ingresses(self):
        """Custom booleanize knobs registered for the device IngressSpec
        must also drive the host baseline — a host run with default knobs
        would silently break the bit-identity contract."""
        engine = ServingEngine(max_batch=8)
        model = init_boundary_model(jax.random.PRNGKey(0), EDGE_CFG)
        engine.register(
            "hot", model, EDGE_CFG, booleanize_method="threshold",
            booleanize_kw={"threshold": 200},
        )
        engine.register("default", model, EDGE_CFG, booleanize_method="threshold")
        raw = _raw(4, seed=3)
        dev = engine.classify("hot", raw)
        host = engine.classify("hot", raw, ingress="host")
        np.testing.assert_array_equal(dev.class_sums, host.class_sums)
        # ... and the knob is real: literals differ from the default-75 entry.
        assert not np.array_equal(
            engine.preprocess("hot", raw), engine.preprocess("default", raw)
        )

    def test_dispatch_is_nonblocking_handle(self):
        """dispatch() returns an in-flight handle whose result() is
        idempotent and matches a blocking classify."""
        engine, _ = self._engine()
        raw = _raw(4, seed=11)
        handle = engine.dispatch("m", raw)
        r1 = handle.result()
        r2 = handle.result()
        assert r1 is r2
        want = engine.classify("m", raw)
        np.testing.assert_array_equal(r1.class_sums, want.class_sums)


class TestServiceRawPath:
    def _pair(self, max_batch=16):
        model = init_boundary_model(jax.random.PRNGKey(2), EDGE_CFG)
        engine = ServingEngine(max_batch=max_batch)
        engine.register("m", model, EDGE_CFG, booleanize_method="threshold")
        reference = ServingEngine(max_batch=max_batch)
        reference.register("m", model, EDGE_CFG, booleanize_method="threshold")
        return engine, reference

    def test_raw_submission_matches_preprocessed(self):
        """The service-level contract: raw-pixel submission, preprocessed
        submission and host_ingress submission all agree with each other
        and with direct engine classifies."""
        engine, reference = self._pair()
        service = ServingService(engine, ServiceConfig(max_delay_us=500.0))

        async def run():
            await service.start()
            raws = [_raw(n, seed=i) for i, n in enumerate([1, 3, 2, 5])]
            raw_res = await asyncio.gather(
                *(service.submit("m", r) for r in raws)
            )
            pre_res = await asyncio.gather(
                *(service.submit(
                    "m", reference.preprocess("m", r), preprocessed=True
                ) for r in raws)
            )
            host_res = await asyncio.gather(
                *(service.submit("m", r, host_ingress=True) for r in raws)
            )
            await service.stop(drain=True)
            return raws, raw_res, pre_res, host_res

        raws, raw_res, pre_res, host_res = asyncio.run(run())
        for r, a, b, c in zip(raws, raw_res, pre_res, host_res):
            want = reference.classify("m", r)
            for got in (a, b, c):
                np.testing.assert_array_equal(got.predictions, want.predictions)
                np.testing.assert_array_equal(got.class_sums, want.class_sums)

    def test_mixed_form_microbatch(self):
        """Raw and preprocessed requests coalesced into ONE microbatch
        execute as separate engine dispatches but resolve identically."""
        engine, reference = self._pair()
        service = ServingService(engine, ServiceConfig(max_delay_us=50_000.0))

        async def run():
            await service.start()
            raw = _raw(2, seed=0)
            lits = reference.preprocess("m", _raw(2, seed=1))
            futs = [
                service.submit_nowait("m", raw),
                service.submit_nowait("m", lits, preprocessed=True),
                service.submit_nowait("m", _raw(2, seed=2)),
            ]
            out = await asyncio.gather(*futs)
            await service.stop(drain=True)
            return out

        results = asyncio.run(run())
        assert all(r.batch_requests == 3 and r.batch_images == 6 for r in results)
        np.testing.assert_array_equal(
            results[0].predictions,
            reference.classify("m", _raw(2, seed=0)).predictions,
        )
        np.testing.assert_array_equal(
            results[1].predictions,
            reference.classify("m", _raw(2, seed=1)).predictions,
        )
        np.testing.assert_array_equal(
            results[2].predictions,
            reference.classify("m", _raw(2, seed=2)).predictions,
        )
        st = service.stats("m")
        assert st.batches == 1 and st.images == 6

    def test_service_stats_split(self):
        engine, _ = self._pair()
        service = ServingService(engine, ServiceConfig(max_delay_us=0.0))

        async def run():
            await service.start()
            await service.submit("m", _raw(3, seed=5))
            await service.stop(drain=True)

        asyncio.run(run())
        st = service.stats("m")
        assert st.queue.count == st.latency.count == 1
        assert st.dispatch.count == st.complete.count == 1
        assert st.p50_latency_us >= st.dispatch.quantile(0.5) > 0.0
        est = engine.stats("m")
        assert est.dispatch.count == est.wait.count == est.fetch.count == 1

    def test_raw_shape_error_propagates_without_enqueue(self):
        engine, _ = self._pair()
        service = ServingService(engine)

        async def run():
            await service.start()
            with pytest.raises(ValueError, match="raw images"):
                service.submit_nowait("m", np.zeros((2, 9, 9), np.uint8))
            await service.stop()

        asyncio.run(run())
        assert service.stats("m").submitted == 0


class TestTrainerIngress:
    def test_prepare_matches_host_pipeline(self):
        from repro.train.tm_engine import TrainerEngine

        cfg = dataclasses.replace(EDGE_CFG, n_clauses=16)
        eng = TrainerEngine(cfg, batch_size=4)
        raw = _raw(10, seed=6)
        labels = np.arange(10) % cfg.n_classes
        ds = eng.prepare(raw, labels, booleanize_method="threshold")
        want = preprocess_for_serving(
            raw, cfg.patch, method="threshold", packed=False
        )
        np.testing.assert_array_equal(np.asarray(ds.literals), want)

    def test_prepare_chunks_are_seamless(self, monkeypatch):
        from repro.train import tm_engine as te

        cfg = dataclasses.replace(EDGE_CFG, n_clauses=16)
        eng = te.TrainerEngine(cfg, batch_size=4)
        monkeypatch.setattr(te.TrainerEngine, "INGRESS_CHUNK", 4)
        raw = _raw(10, seed=8)
        ds = eng.prepare(raw, np.zeros(10, np.int64))
        want = preprocess_for_serving(raw, cfg.patch, method="threshold", packed=False)
        np.testing.assert_array_equal(np.asarray(ds.literals), want)
