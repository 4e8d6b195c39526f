"""Serving-robustness chaos suite (ARCHITECTURE.md §Faults).

The request-lifetime guarantee under test: every admitted future
RESOLVES — with a result or a structured error, never a hang — under
every fault ``serve/faults.py`` can inject.  Alongside it, the
per-guarantee invariants: expired requests are never dispatched,
non-poisoned batchmates of a quarantined request stay bit-identical,
degraded-path results stay bit-identical to the ``kernels/ref.py``
oracle, and a crashed worker is restarted under bounded backoff.
"""

import asyncio

import jax
import numpy as np
import pytest

from repro.core import clauses as cl
from repro.core.cotm import CoTMConfig, init_boundary_model
from repro.core.patches import PatchSpec
from repro.data.pipeline import preprocess_for_serving
from repro.kernels.ref import fused_infer_ref
from repro.serve import (
    DegradationPolicy,
    DeviceLost,
    FaultPlan,
    InjectedEngineError,
    PoisonedPayload,
    ServiceConfig,
    ServiceExpired,
    ServiceHealth,
    ServiceStopped,
    ServingEngine,
    ServingService,
    StepCompileError,
    WorkerCrashed,
    chaos_soak,
    degraded_fallback,
    make_serve_mesh,
    poisson_open_loop,
)

EDGE_SPEC = PatchSpec(image_x=11, image_y=11, window_x=5, window_y=5)
EDGE_CFG = CoTMConfig(n_clauses=37, n_classes=10, patch=EDGE_SPEC)


def _model(seed=0):
    return init_boundary_model(jax.random.PRNGKey(seed), EDGE_CFG)


def _images(n, seed=0):
    key = jax.random.PRNGKey(seed + 100)
    side = EDGE_CFG.patch.image_y
    return np.asarray(
        (jax.random.uniform(key, (n, side, side)) > 0.6)
    ).astype(np.uint8)


def _pair(
    *, faults=None, policy=None, max_batch=16, path=None, mesh=None, seed=0
):
    """A fault-injected service engine and an untouched reference engine
    over the same model — reference results never see the FaultPlan."""
    model = _model(seed=seed)
    engine = ServingEngine(max_batch=max_batch, mesh=mesh, faults=faults)
    engine.register("glyphs", model, EDGE_CFG, booleanize_method="none", path=path)
    ref = ServingEngine(max_batch=max_batch)
    ref.register("glyphs", model, EDGE_CFG, booleanize_method="none", path=path)
    return engine, ref


def _oracle_classify(ref_engine, imgs):
    """Classify through the kernels/ref.py oracle composition directly:
    host ingress -> fused_infer_ref on the frozen register image.  The
    independent ground truth degraded paths are asserted against."""
    servable = ref_engine.servable("glyphs")
    lits = preprocess_for_serving(
        imgs, EDGE_CFG.patch, method="none", packed=True
    )
    sums = np.asarray(
        fused_infer_ref(
            jax.numpy.asarray(lits),
            servable.include_packed,
            servable.nonempty,
            servable.weights,
        )
    )
    return np.asarray(cl.argmax_predict(sums)), sums


# --------------------------------------------------------------------------
# FaultPlan / DegradationPolicy / ServiceHealth units (no event loop)
# --------------------------------------------------------------------------


class TestFaultPrimitives:
    def test_fault_plan_counters_and_injection_order(self):
        p = FaultPlan(crash_at=(2,), device_loss_at=(3,), engine_error_at=(1,))
        p.on_service_dispatch("m")                      # seq 1: clean
        with pytest.raises(WorkerCrashed) as e:
            p.on_service_dispatch("m")                  # seq 2: crash
        assert e.value.kind == "worker_crash" and e.value.model == "m"
        with pytest.raises(DeviceLost):
            p.on_service_dispatch("m")                  # seq 3: device loss
        assert p.service_dispatches == 3
        with pytest.raises(InjectedEngineError):
            p.on_engine_dispatch("m")                   # engine seq 1
        p.on_engine_dispatch("m")                       # engine seq 2: clean
        assert p.engine_dispatches == 2

    def test_poison_is_payload_identity(self):
        p = FaultPlan()
        a, b = _images(1), _images(1)
        p.poison(a)
        assert p.is_poisoned(a) and not p.is_poisoned(b)
        # np.asarray of an existing ndarray is the same object, so poison
        # survives the service's validation path.
        assert p.is_poisoned(np.asarray(a))
        with pytest.raises(PoisonedPayload):
            p.check_payload(a, "m")
        p.check_payload(b, "m")

    def test_degradation_policy_backoff_doubles_and_caps(self):
        pol = DegradationPolicy(restart_backoff_s=0.1, restart_backoff_max_s=0.5)
        assert [pol.backoff_s(i) for i in (1, 2, 3, 4)] == [
            0.1, 0.2, 0.4, 0.5
        ]
        with pytest.raises(ValueError):
            DegradationPolicy(failure_threshold=0)
        with pytest.raises(ValueError):
            DegradationPolicy(max_worker_restarts=-1)

    def test_service_health_degrade_is_sticky(self):
        h = ServiceHealth()
        assert h.state == "healthy"
        h.degrade(RuntimeError("boom"))
        assert h.state == "degraded" and "boom" in h.last_fault
        h.state = "draining"
        h.degrade(RuntimeError("later"))     # degrade never un-drains
        assert h.state == "draining"

    def test_degraded_fallback_chain_ends_dense(self):
        for start in ("fused_sparse", "sparse", "matmul_sparse",
                      "fused", "kernel", "bitpacked", "matmul"):
            name, hops = start, 0
            while name is not None:
                name = degraded_fallback(name)
                hops += 1
                assert hops < 10
        assert degraded_fallback("dense") is None


# --------------------------------------------------------------------------
# Deadlines: expired requests are shed before dispatch
# --------------------------------------------------------------------------


class TestDeadlines:
    def test_expired_request_sheds_without_dispatch(self):
        faults = FaultPlan()
        engine, _ = _pair(faults=faults)
        # Coalescing window far beyond the deadline: the request would sit
        # queued for 1 s, so the 5 ms deadline must win.
        service = ServingService(engine, ServiceConfig(max_delay_us=1e6))

        async def run():
            await service.start()
            fut = service.submit_nowait("glyphs", _images(2), deadline_s=0.005)
            with pytest.raises(ServiceExpired) as e:
                await fut
            await service.stop(drain=True)
            return e.value

        err = asyncio.run(run())
        assert err.model == "glyphs"
        assert err.waited_s >= err.deadline_s == pytest.approx(0.005)
        # The acceptance invariant: it never reached a dispatch seam.
        assert faults.service_dispatches == 0
        st = service.stats("glyphs")
        assert st.expired == 1 and st.completed == 0
        assert st.health["expired"] == 1

    def test_unexpired_batchmate_completes_bit_identical(self):
        engine, ref = _pair()
        service = ServingService(engine, ServiceConfig(max_delay_us=40_000.0))
        imgs = _images(3, seed=7)

        async def run():
            await service.start()
            doomed = service.submit_nowait(
                "glyphs", _images(2, seed=1), deadline_s=0.004
            )
            ok = service.submit_nowait("glyphs", imgs, deadline_s=5.0)
            with pytest.raises(ServiceExpired):
                await doomed
            res = await ok
            await service.stop(drain=True)
            return res

        res = asyncio.run(run())
        want = ref.classify("glyphs", imgs)
        np.testing.assert_array_equal(res.predictions, want.predictions)
        np.testing.assert_array_equal(res.class_sums, want.class_sums)
        st = service.stats("glyphs")
        assert st.expired == 1 and st.completed == 1

    def test_deadline_validation(self):
        engine, _ = _pair()
        service = ServingService(engine)

        async def run():
            await service.start()
            with pytest.raises(ValueError, match="deadline_s"):
                service.submit_nowait("glyphs", _images(1), deadline_s=0.0)
            await service.stop(drain=False)

        asyncio.run(run())


# --------------------------------------------------------------------------
# Worker supervision: crash -> structured failure -> bounded restart
# --------------------------------------------------------------------------


class TestWorkerCrash:
    def test_crash_fails_inflight_and_restarts(self):
        faults = FaultPlan(crash_at=(1,))
        engine, ref = _pair(faults=faults)
        service = ServingService(
            engine,
            ServiceConfig(max_delay_us=100.0),
            faults=faults,
            policy=DegradationPolicy(restart_backoff_s=0.001),
        )
        imgs = _images(4, seed=3)

        async def run():
            await service.start()
            with pytest.raises(WorkerCrashed) as e:
                await service.submit("glyphs", _images(2))
            # The replaced worker serves the next request normally.
            res = await service.submit("glyphs", imgs)
            await service.stop(drain=True)
            return e.value, res

        err, res = asyncio.run(run())
        assert err.kind == "worker_crash"
        want = ref.classify("glyphs", imgs)
        np.testing.assert_array_equal(res.predictions, want.predictions)
        np.testing.assert_array_equal(res.class_sums, want.class_sums)
        h = service.health()
        assert h.worker_restarts == 1
        assert h.state == "draining"        # stop() was called at the end
        assert "WorkerCrashed" in h.last_fault

    def test_restart_budget_exhaustion_drains(self):
        faults = FaultPlan(crash_at=(1,))
        engine, _ = _pair(faults=faults)
        service = ServingService(
            engine,
            ServiceConfig(max_delay_us=100.0),
            faults=faults,
            policy=DegradationPolicy(max_worker_restarts=0),
        )

        async def run():
            await service.start()
            with pytest.raises(WorkerCrashed):
                await service.submit("glyphs", _images(1))
            # Budget (0) exhausted: the service stopped accepting.
            with pytest.raises(ServiceStopped):
                service.submit_nowait("glyphs", _images(1))
            await service.stop(drain=False)

        asyncio.run(run())
        assert service.health().state == "draining"


# --------------------------------------------------------------------------
# Quarantine: a poisoned member fails alone, batchmates bit-identical
# --------------------------------------------------------------------------


class TestQuarantine:
    def test_poisoned_member_isolated_from_coalesced_batch(self):
        faults = FaultPlan()
        engine, ref = _pair(faults=faults)
        # Wide-open window so all three submissions coalesce into one
        # microbatch before the first dispatch.
        service = ServingService(
            engine, ServiceConfig(max_delay_us=30_000.0), faults=faults
        )
        batches = [_images(2, seed=i) for i in range(3)]
        faults.poison(batches[1])

        async def run():
            await service.start()
            futs = [service.submit_nowait("glyphs", b) for b in batches]
            out = await asyncio.gather(*futs, return_exceptions=True)
            await service.stop(drain=True)
            return out

        out = asyncio.run(run())
        assert isinstance(out[1], PoisonedPayload)
        for i in (0, 2):
            want = ref.classify("glyphs", batches[i])
            np.testing.assert_array_equal(out[i].predictions, want.predictions)
            np.testing.assert_array_equal(out[i].class_sums, want.class_sums)
        st = service.stats("glyphs")
        assert st.quarantined >= 1
        assert st.completed == 2
        assert service.health().quarantined >= 1

    def test_single_poisoned_request_fails_structured(self):
        faults = FaultPlan()
        engine, _ = _pair(faults=faults)
        service = ServingService(
            engine, ServiceConfig(max_delay_us=100.0), faults=faults
        )
        bad = _images(1)
        faults.poison(bad)

        async def run():
            await service.start()
            with pytest.raises(PoisonedPayload) as e:
                await service.submit("glyphs", bad)
            await service.stop(drain=True)
            return e.value

        err = asyncio.run(run())
        assert err.kind == "poisoned_payload" and err.model == "glyphs"


# --------------------------------------------------------------------------
# Engine exceptions mid-microbatch: members all resolve
# --------------------------------------------------------------------------


class TestEngineException:
    def test_injected_engine_error_resolves_every_member(self):
        faults = FaultPlan(engine_error_at=(1,))
        engine, ref = _pair(faults=faults)
        service = ServingService(
            engine, ServiceConfig(max_delay_us=30_000.0), faults=faults
        )
        batches = [_images(2, seed=i) for i in range(2)]

        async def run():
            await service.start()
            futs = [service.submit_nowait("glyphs", b) for b in batches]
            out = await asyncio.gather(*futs, return_exceptions=True)
            await service.stop(drain=True)
            return out

        out = asyncio.run(run())
        # The first engine dispatch (the coalesced batch) raised; the
        # quarantine retried each member alone (fresh engine sequence
        # numbers — a plan is a script, not a feedback loop) and both
        # completed bit-identically.
        for b, res in zip(batches, out):
            assert not isinstance(res, Exception), res
            want = ref.classify("glyphs", b)
            np.testing.assert_array_equal(res.predictions, want.predictions)
            np.testing.assert_array_equal(res.class_sums, want.class_sums)
        assert service.health().dispatch_failures >= 1


# --------------------------------------------------------------------------
# Degraded modes: circuit breaker -> fallback path, bit-identical
# --------------------------------------------------------------------------


class TestDegradation:
    def test_engine_degrade_path_walks_chain_bit_identical(self):
        engine, ref = _pair(path="fused")
        imgs = _images(5, seed=11)
        want_preds, want_sums = _oracle_classify(ref, imgs)
        seen = ["fused"]
        while True:
            res = engine.classify("glyphs", imgs)
            np.testing.assert_array_equal(res.predictions, want_preds)
            np.testing.assert_array_equal(res.class_sums, want_sums)
            nxt = engine.degrade_path("glyphs")
            if nxt is None:
                break
            seen.append(nxt)
        assert seen[-1] == "dense"               # chain bottoms out dense
        assert seen == ["fused"] + [
            s for s in ["matmul", "dense"]
        ]
        st = engine.stats("glyphs")
        assert st.fallback_path == "dense"
        assert st.degrade_steps == len(seen) - 1

    def test_breaker_trips_to_fallback_and_serves_bit_identical(self):
        # Two consecutive engine errors (threshold=2) on single-request
        # microbatches trip the breaker; the fallback path then serves.
        faults = FaultPlan(engine_error_at=(1, 2))
        engine, ref = _pair(faults=faults, path="fused")
        service = ServingService(
            engine,
            ServiceConfig(max_delay_us=100.0),
            faults=faults,
            policy=DegradationPolicy(failure_threshold=2),
        )
        imgs = _images(3, seed=5)

        async def run():
            await service.start()
            errs = []
            for _ in range(2):
                try:
                    await service.submit("glyphs", _images(1))
                except InjectedEngineError as e:
                    errs.append(e)
            res = await service.submit("glyphs", imgs)
            state = service.health().state   # before stop() marks draining
            await service.stop(drain=True)
            return errs, res, state

        errs, res, state = asyncio.run(run())
        assert len(errs) == 2
        h = service.health()
        assert state == "degraded"
        assert h.fallback_path == degraded_fallback("fused") == "matmul"
        assert engine.stats("glyphs").fallback_path == "matmul"
        # Degraded results match the kernels/ref.py oracle bit for bit.
        want_preds, want_sums = _oracle_classify(ref, imgs)
        np.testing.assert_array_equal(res.predictions, want_preds)
        np.testing.assert_array_equal(res.class_sums, want_sums)

    def test_compile_failure_raises_and_never_degrades(self, monkeypatch):
        # A step that fails to trace/compile (a Pallas kernel Mosaic
        # refuses, on a TPU) is a defect, not a runtime fault: warmup
        # raises it, the service fails the batch with it, and the
        # breaker walks no fallback chain around it.
        from repro.serve import paths

        def _uncompilable(lits, include, include_packed, nonempty, weights):
            raise NotImplementedError("Unimplemented primitive in Mosaic")

        name = "uncompilable_test_path"
        monkeypatch.setitem(
            paths._REGISTRY, name,
            paths.EvalPath(name=name, input_form=paths.PACKED, fn=_uncompilable),
        )
        engine, _ = _pair(path=name)
        with pytest.raises(StepCompileError, match="failed to compile"):
            engine.warmup("glyphs", buckets=[1], forms=("raw",))
        service = ServingService(
            engine,
            ServiceConfig(max_delay_us=100.0),
            policy=DegradationPolicy(failure_threshold=1),
        )

        async def run():
            await service.start()
            errs = []
            for _ in range(3):
                try:
                    await service.submit("glyphs", _images(1))
                except StepCompileError as e:
                    errs.append(e)
            state = service.health().state
            await service.stop(drain=True)
            return errs, state

        errs, state = asyncio.run(run())
        assert len(errs) == 3 and errs[0].kind == "compile_error"
        assert state == "healthy"
        h = service.health()
        assert h.fallback_path is None and h.dispatch_failures == 3
        st = engine.stats("glyphs")
        assert st.fallback_path is None and st.degrade_steps == 0


# --------------------------------------------------------------------------
# Device loss: shrink the mesh, retry, keep serving
# --------------------------------------------------------------------------


class TestDeviceLoss:
    def test_unmeshed_device_loss_retries_and_resolves(self):
        faults = FaultPlan(device_loss_at=(1,))
        engine, ref = _pair(faults=faults)
        service = ServingService(
            engine, ServiceConfig(max_delay_us=100.0), faults=faults
        )
        imgs = _images(2, seed=9)

        async def run():
            await service.start()
            res = await service.submit("glyphs", imgs)
            await service.stop(drain=True)
            return res

        res = asyncio.run(run())
        want = ref.classify("glyphs", imgs)
        np.testing.assert_array_equal(res.predictions, want.predictions)
        assert service.health().device_losses == 1

    @pytest.mark.skipif(
        jax.device_count() < 2, reason="needs >= 2 devices for a data mesh"
    )
    def test_meshed_device_loss_shrinks_and_stays_bit_identical(self):
        faults = FaultPlan(device_loss_at=(1,))
        engine, ref = _pair(faults=faults, mesh=make_serve_mesh(2))
        service = ServingService(
            engine, ServiceConfig(max_delay_us=100.0), faults=faults
        )
        imgs = _images(4, seed=13)

        async def run():
            await service.start()
            res = await service.submit("glyphs", imgs)
            await service.stop(drain=True)
            return res

        assert engine.stats("glyphs").data_shards == 2
        res = asyncio.run(run())
        # The loss shrank the data axis 2 -> 1 and the retry served on
        # the shrunk mesh, bit-identically.
        assert engine.stats("glyphs").data_shards == 1
        want = ref.classify("glyphs", imgs)
        np.testing.assert_array_equal(res.predictions, want.predictions)
        np.testing.assert_array_equal(res.class_sums, want.class_sums)
        assert service.health().device_losses == 1


# --------------------------------------------------------------------------
# Loadgen adversarial knobs
# --------------------------------------------------------------------------


class TestLoadgenKnobs:
    def test_malformed_requests_rejected_at_validation(self):
        engine, _ = _pair()
        service = ServingService(engine, ServiceConfig(max_delay_us=100.0))

        async def run():
            await service.start()
            report = await poisson_open_loop(
                service, "glyphs", [_images(1) for _ in range(8)],
                rate=2000.0, malformed_frac=1.0,
            )
            await service.stop(drain=True)
            return report

        report = asyncio.run(run())
        assert report.malformed == 8
        assert report.admitted == [] and report.abandoned == []
        # Nothing poisoned the service: it served zero requests cleanly.
        assert service.stats("glyphs").completed == 0

    def test_abandoned_futures_still_resolve(self):
        engine, _ = _pair()
        service = ServingService(engine, ServiceConfig(max_delay_us=100.0))

        async def run():
            await service.start()
            report = await poisson_open_loop(
                service, "glyphs", [_images(1) for _ in range(6)],
                rate=2000.0, abandon_frac=1.0, deadline_s=5.0,
            )
            # The clients walked away; the service must still resolve
            # every abandoned future.
            out = await asyncio.gather(
                *(f for _, f in report.abandoned), return_exceptions=True
            )
            await service.stop(drain=True)
            return report, out

        report, out = asyncio.run(run())
        assert len(report.abandoned) == 6 and report.admitted == []
        assert all(not isinstance(o, Exception) for o in out)

    def test_report_unpacks_as_legacy_pair(self):
        engine, _ = _pair()
        service = ServingService(engine, ServiceConfig(max_delay_us=100.0))

        async def run():
            await service.start()
            admitted, rejected = await poisson_open_loop(
                service, "glyphs", [_images(1) for _ in range(3)], rate=2000.0
            )
            await asyncio.gather(*(f for _, f in admitted))
            await service.stop(drain=True)
            return admitted, rejected

        admitted, rejected = asyncio.run(run())
        assert len(admitted) == 3 and rejected == 0


# --------------------------------------------------------------------------
# Chaos soak: every future resolves under combined faults
# --------------------------------------------------------------------------


def _soak(requests, *, faults, policy=None, **knobs):
    engine, _ = _pair(faults=faults)
    service = ServingService(
        engine,
        ServiceConfig(max_delay_us=500.0),
        faults=faults,
        policy=policy or DegradationPolicy(restart_backoff_s=0.001),
    )

    async def run():
        await service.start()
        tally = await chaos_soak(
            service, "glyphs", requests, rate=800.0, **knobs
        )
        await service.stop(drain=True)
        return tally

    return asyncio.run(run()), service


class TestChaosSoak:
    def test_fast_soak_no_future_hangs(self):
        faults = FaultPlan(
            crash_at=(2,), engine_error_at=(3,), slow_dispatch_s=0.0005
        )
        requests = [_images(2, seed=i) for i in range(24)]
        tally, service = _soak(
            requests, faults=faults,
            deadline_s=2.0, malformed_frac=0.15, abandon_frac=0.15,
        )
        # THE invariant: zero hung futures, and every submission is
        # accounted for in exactly one bucket.
        assert tally["hung"] == 0
        resolved = (
            tally["ok"] + tally["expired"] + tally["faulted"] + tally["stopped"]
        )
        assert resolved == tally["admitted"] + tally["abandoned"]
        assert (
            tally["admitted"] + tally["abandoned"]
            + tally["rejected"] + tally["malformed"]
        ) == len(requests)
        assert tally["malformed"] > 0          # knob actually engaged
        assert tally["health"]["worker_restarts"] >= 1

    @pytest.mark.slow
    def test_long_soak_under_combined_faults(self):
        faults = FaultPlan(
            crash_at=(3, 17), device_loss_at=(9,), engine_error_at=(5, 6, 30),
            slow_dispatch_s=0.0005,
        )
        requests = [_images(1 + i % 4, seed=i) for i in range(200)]
        tally, service = _soak(
            requests, faults=faults,
            deadline_s=5.0, malformed_frac=0.1, abandon_frac=0.2,
            gather_timeout_s=60.0,
        )
        assert tally["hung"] == 0
        resolved = (
            tally["ok"] + tally["expired"] + tally["faulted"] + tally["stopped"]
        )
        assert resolved == tally["admitted"] + tally["abandoned"]
        assert tally["ok"] > 0
        assert tally["health"]["worker_restarts"] >= 2
        assert tally["health"]["device_losses"] >= 1
