"""Async serving service: microbatch scheduler policy (fake clock),
asyncio service lifecycle, bit-identical-to-engine results under
concurrent load, backpressure, round-robin fairness, graceful drain."""

import asyncio

import jax
import numpy as np
import pytest

from repro.core.cotm import CoTMConfig, init_boundary_model
from repro.core.patches import PatchSpec
from repro.serve import (
    MicrobatchScheduler,
    PendingRequest,
    QueueFull,
    SchedulerConfig,
    ServiceConfig,
    ServiceOverloaded,
    ServiceStopped,
    ServingEngine,
    ServingService,
)

EDGE_SPEC = PatchSpec(image_x=11, image_y=11, window_x=5, window_y=5)
EDGE_CFG = CoTMConfig(n_clauses=37, n_classes=10, patch=EDGE_SPEC)


def _model(cfg=EDGE_CFG, seed=0):
    return init_boundary_model(jax.random.PRNGKey(seed), cfg)


def _images(n, seed=0):
    key = jax.random.PRNGKey(seed + 100)
    side = EDGE_CFG.patch.image_y
    return np.asarray(
        (jax.random.uniform(key, (n, side, side)) > 0.6)
    ).astype(np.uint8)


def _req(model="m", n=1, t=0.0):
    return PendingRequest(
        model=model, literals=np.zeros((n, 1), np.uint8), n=n, enqueue_t=t
    )


class TestSchedulerPolicy:
    """Pure state-machine tests: all time passed in, no event loop."""

    def test_config_validation(self):
        with pytest.raises(ValueError, match="max_delay_us"):
            SchedulerConfig(max_delay_us=-1)
        with pytest.raises(ValueError, match="high_water"):
            SchedulerConfig(high_water=0)
        with pytest.raises(ValueError, match="max_coalesce"):
            MicrobatchScheduler(max_coalesce=0)

    def test_admission_high_water(self):
        s = MicrobatchScheduler(
            SchedulerConfig(high_water=8), max_coalesce=16
        )
        s.submit(_req(n=5))
        s.submit(_req(n=3))          # exactly at high water: admitted
        assert s.depth("m") == 8
        with pytest.raises(QueueFull) as e:
            s.submit(_req(n=1))
        assert e.value.depth == 8 and e.value.high_water == 8

    def test_oversized_request_admitted_when_queue_empty(self):
        """A single request above high_water must still be servable (the
        engine slices it); it is only rejected behind existing work."""
        s = MicrobatchScheduler(SchedulerConfig(high_water=4), max_coalesce=8)
        s.submit(_req(n=100))        # empty queue: admitted
        assert s.depth("m") == 100
        with pytest.raises(QueueFull):
            s.submit(_req(n=1))

    def test_deadline_dispatch(self):
        s = MicrobatchScheduler(
            SchedulerConfig(max_delay_us=100.0), max_coalesce=8
        )
        s.submit(_req(n=1, t=1.0))
        assert s.next_ready(1.0) is None             # window still open
        assert s.next_ready(1.0 + 99e-6) is None
        assert s.next_ready(1.0 + 100e-6) == "m"     # deadline hit
        assert s.earliest_deadline() == pytest.approx(1.0 + 100e-6)

    def test_full_window_dispatches_immediately(self):
        s = MicrobatchScheduler(
            SchedulerConfig(max_delay_us=1e6), max_coalesce=4
        )
        for _ in range(3):
            s.submit(_req(n=1, t=0.0))
        assert s.next_ready(0.0) is None             # 3 < 4, far deadline
        s.submit(_req(n=1, t=0.0))
        assert s.next_ready(0.0) == "m"              # window full

    def test_force_ignores_deadline(self):
        s = MicrobatchScheduler(
            SchedulerConfig(max_delay_us=1e6), max_coalesce=8
        )
        s.submit(_req(n=1, t=0.0))
        assert s.next_ready(0.0) is None
        assert s.next_ready(0.0, force=True) == "m"  # drain mode

    def test_pop_batch_fifo_and_cap(self):
        s = MicrobatchScheduler(max_coalesce=8)
        for i, n in enumerate([3, 3, 3, 2]):
            r = _req(n=n, t=float(i))
            r.payload = i
            s.submit(r)
        batch = s.pop_batch("m")                     # 3 + 3, next 3 > 8
        assert [r.payload for r in batch] == [0, 1]
        assert s.depth("m") == 5
        batch = s.pop_batch("m")
        assert [r.payload for r in batch] == [2, 3]
        assert s.depth("m") == 0
        with pytest.raises(ValueError, match="no pending"):
            s.pop_batch("m")

    def test_pop_batch_takes_oversized_single(self):
        s = MicrobatchScheduler(max_coalesce=4)
        s.submit(_req(n=100))
        assert [r.n for r in s.pop_batch("m")] == [100]

    def test_round_robin_across_models(self):
        """The hot tenant cannot starve the others: after serving a model
        the cursor moves past it."""
        s = MicrobatchScheduler(
            SchedulerConfig(max_delay_us=0.0), max_coalesce=4
        )
        for _ in range(3):
            s.submit(_req(model="a", n=1))
        s.submit(_req(model="b", n=1))
        s.submit(_req(model="c", n=1))
        order = []
        while s.total_depth():
            m = s.next_ready(now=1e9)
            order.append(m)
            s.pop_batch(m)
        # a's 3 requests coalesce into one batch (cap 4): each tenant
        # gets exactly one dispatch, in rotation order.
        assert sorted(order) == ["a", "b", "c"]
        # With coalescing capped to 1, a is revisited only after b and c.
        s2 = MicrobatchScheduler(
            SchedulerConfig(max_delay_us=0.0), max_coalesce=1
        )
        for m in ["a", "a", "b", "c"]:
            s2.submit(_req(model=m, n=1))
        order2 = []
        while s2.total_depth():
            m = s2.next_ready(now=1e9)
            order2.append(m)
            s2.pop_batch(m)
        assert order2 == ["a", "b", "c", "a"]

    def test_drain_all_clears_queues(self):
        s = MicrobatchScheduler(max_coalesce=4)
        for m in ["a", "b", "a"]:
            s.submit(_req(model=m, n=2))
        dropped = s.drain_all()
        assert len(dropped) == 3 and s.total_depth() == 0
        assert s.next_ready(1e9, force=True) is None


def _serving_pair(max_batch=16, path=None, seed=0):
    """A service-backed engine and an independent reference engine over
    the same model — reference results never touch the service."""
    model = _model(seed=seed)
    engine = ServingEngine(max_batch=max_batch)
    engine.register("glyphs", model, EDGE_CFG, booleanize_method="none", path=path)
    ref = ServingEngine(max_batch=max_batch)
    ref.register("glyphs", model, EDGE_CFG, booleanize_method="none", path=path)
    return engine, ref


class TestServingService:
    def test_bit_identical_under_concurrent_load(self):
        """The acceptance contract: results equal direct engine.classify
        no matter how the microbatcher coalesced the requests."""
        engine, ref = _serving_pair()
        service = ServingService(engine, ServiceConfig(max_delay_us=500.0))

        async def run():
            await service.start()
            sizes = [1, 3, 7, 2, 5, 1, 4, 6, 2, 1]
            batches = [_images(n, seed=i) for i, n in enumerate(sizes)]

            async def one(b, i):
                # stagger submitters so coalescing patterns vary
                await asyncio.sleep(0.0005 * (i % 3))
                return await service.submit("glyphs", b)

            results = await asyncio.gather(
                *(one(b, i) for i, b in enumerate(batches))
            )
            await service.stop(drain=True)
            return batches, results

        batches, results = asyncio.run(run())
        coalesced = 0
        for b, r in zip(batches, results):
            want = ref.classify("glyphs", b)
            np.testing.assert_array_equal(r.predictions, want.predictions)
            np.testing.assert_array_equal(r.class_sums, want.class_sums)
            coalesced = max(coalesced, r.batch_requests)
        st = service.stats("glyphs")
        assert st.completed == len(batches)
        assert st.images == sum(len(b) for b in batches)

    def test_requests_coalesce_into_one_bucket(self):
        """Back-to-back submissions under an open deadline ride one
        microbatch — and still match the reference bit for bit."""
        engine, ref = _serving_pair()
        service = ServingService(engine, ServiceConfig(max_delay_us=50_000.0))

        async def run():
            await service.start()
            futs = [
                service.submit_nowait("glyphs", _images(2, seed=i))
                for i in range(4)
            ]
            out = await asyncio.gather(*futs)
            await service.stop(drain=True)
            return out

        results = asyncio.run(run())
        assert all(r.batch_requests == 4 for r in results)
        assert all(r.batch_images == 8 for r in results)
        assert all(r.bucket == 8 for r in results)
        for i, r in enumerate(results):
            want = ref.classify("glyphs", _images(2, seed=i))
            np.testing.assert_array_equal(r.predictions, want.predictions)
        st = service.stats("glyphs")
        assert st.batches == 1
        assert st.occupancy_hist == {8: {"batches": 1, "images": 8}}
        assert st.mean_occupancy == 1.0

    def test_zero_delay_serves_lone_request_immediately(self):
        engine, _ = _serving_pair()
        service = ServingService(engine, ServiceConfig(max_delay_us=0.0))

        async def run():
            await service.start()
            r = await service.submit("glyphs", _images(1))
            await service.stop()
            return r

        r = asyncio.run(run())
        assert r.batch_requests == 1 and r.bucket == 1

    def test_backpressure_rejects_past_high_water(self):
        """With the dispatcher held off by a long deadline the queue
        fills to high_water, further submissions get ServiceOverloaded
        with a retry hint, and drain still answers everyone admitted."""
        engine, ref = _serving_pair()
        service = ServingService(
            engine, ServiceConfig(max_delay_us=10e6, high_water=6)
        )

        async def run():
            await service.start()
            futs, errors = [], []
            for i in range(10):
                try:
                    futs.append(
                        service.submit_nowait("glyphs", _images(2, seed=i))
                    )
                except ServiceOverloaded as e:
                    errors.append(e)
            results = await asyncio.gather(*futs)
            await service.stop(drain=True)
            return futs, errors, results

        futs, errors, results = asyncio.run(run())
        assert len(futs) == 3 and len(errors) == 7    # 2+2+2 <= 6, then full
        assert all(e.retry_after_s > 0 for e in errors)
        assert all(e.model == "glyphs" for e in errors)
        for i, r in enumerate(results):
            want = ref.classify("glyphs", _images(2, seed=i))
            np.testing.assert_array_equal(r.predictions, want.predictions)
        st = service.stats("glyphs")
        assert st.submitted == 10 and st.rejected == 7 and st.completed == 3
        assert st.queue_depth == 0

    def test_graceful_drain_under_load(self):
        """stop(drain=True) mid-stream: every admitted request resolves
        with correct results; later submissions are refused."""
        engine, ref = _serving_pair()
        service = ServingService(engine, ServiceConfig(max_delay_us=2000.0))

        async def run():
            await service.start()
            futs = []
            for i in range(12):
                futs.append(service.submit_nowait("glyphs", _images(3, seed=i)))
                if i % 4 == 3:
                    await asyncio.sleep(0.001)   # let some batches dispatch
            await service.stop(drain=True)       # flushes the rest
            results = await asyncio.gather(*futs)
            with pytest.raises(ServiceStopped):
                service.submit_nowait("glyphs", _images(1))
            return results

        results = asyncio.run(run())
        assert len(results) == 12
        for i, r in enumerate(results):
            want = ref.classify("glyphs", _images(3, seed=i))
            np.testing.assert_array_equal(r.predictions, want.predictions)
            np.testing.assert_array_equal(r.class_sums, want.class_sums)

    def test_stop_joins_executors_off_loop(self, monkeypatch):
        """Regression pin for the tmlint TM301 fix: stop() used to call
        executor.shutdown(wait=True) directly in the async def, joining
        worker threads ON the event loop.  The joins must run off-loop
        (asyncio.to_thread) while still waiting for in-flight work."""
        import threading
        from concurrent.futures import ThreadPoolExecutor

        engine, _ = _serving_pair()
        service = ServingService(engine, ServiceConfig(max_delay_us=100.0))
        calls = []
        real = ThreadPoolExecutor.shutdown

        def recording(self, wait=True, **kw):
            calls.append((threading.current_thread(), wait))
            return real(self, wait, **kw)

        monkeypatch.setattr(ThreadPoolExecutor, "shutdown", recording)

        async def run():
            await service.start()
            fut = service.submit_nowait("glyphs", _images(2))
            await fut
            await service.stop(drain=True)
            return threading.current_thread()

        loop_thread = asyncio.run(run())
        # dispatch, completion and ingress pools all joined (wait=True)...
        joins = [t for t, w in calls if w]
        assert len(joins) >= 3
        # ...and never from the event-loop thread itself.  (asyncio.run's
        # own loop.close() fires a wait=False shutdown on the main thread
        # after the loop exits; only the blocking joins matter here.)
        assert all(t is not loop_thread for t in joins), (
            "executor.shutdown(wait=True) ran on the event-loop thread"
        )

    def test_hard_stop_fails_queued_requests(self):
        engine, _ = _serving_pair()
        service = ServingService(engine, ServiceConfig(max_delay_us=10e6))

        async def run():
            await service.start()
            futs = [
                service.submit_nowait("glyphs", _images(1, seed=i))
                for i in range(3)
            ]
            await service.stop(drain=False)
            return await asyncio.gather(*futs, return_exceptions=True)

        out = asyncio.run(run())
        assert all(isinstance(r, ServiceStopped) for r in out)

    def test_multi_model_tenancy_and_stats_isolation(self):
        model_a, model_b = _model(seed=1), _model(seed=2)
        engine = ServingEngine(max_batch=8)
        engine.register("a", model_a, EDGE_CFG, booleanize_method="none")
        engine.register("b", model_b, EDGE_CFG, booleanize_method="none")
        ref = ServingEngine(max_batch=8)
        ref.register("a", model_a, EDGE_CFG, booleanize_method="none")
        ref.register("b", model_b, EDGE_CFG, booleanize_method="none")
        service = ServingService(engine, ServiceConfig(max_delay_us=1000.0))

        async def run():
            await service.start()
            imgs = _images(2, seed=7)
            futs = [
                service.submit_nowait(name, imgs)
                for name in ("a", "b", "a", "b")
            ]
            results = await asyncio.gather(*futs)
            await service.stop(drain=True)
            return imgs, results

        imgs, results = asyncio.run(run())
        np.testing.assert_array_equal(
            results[0].predictions, ref.classify("a", imgs).predictions
        )
        np.testing.assert_array_equal(
            results[1].predictions, ref.classify("b", imgs).predictions
        )
        # same inputs, different models -> independently computed
        np.testing.assert_array_equal(
            results[0].predictions, results[2].predictions
        )
        sa, sb = service.stats("a"), service.stats("b")
        assert sa.completed == 2 and sb.completed == 2
        assert sa.images == 4 and sb.images == 4

    def test_validation_errors_propagate_without_enqueue(self):
        engine, _ = _serving_pair()
        service = ServingService(engine)

        async def run():
            await service.start()
            with pytest.raises(KeyError):
                service.submit_nowait("nope", _images(1))
            with pytest.raises(ValueError, match="empty request"):
                service.submit_nowait(
                    "glyphs", np.zeros((0, 11, 11), np.uint8)
                )
            with pytest.raises(ValueError, match="preprocessed literals"):
                service.submit_nowait(
                    "glyphs", np.zeros((2, 3), np.uint8), preprocessed=True
                )
            await service.stop()

        asyncio.run(run())
        assert service.stats("glyphs").submitted == 0

    def test_restart_after_stop(self):
        engine, ref = _serving_pair()
        service = ServingService(engine, ServiceConfig(max_delay_us=0.0))

        async def run():
            await service.start()
            await service.submit("glyphs", _images(1))
            await service.stop()
            assert not service.running
            await service.start()        # a stopped service can restart
            r = await service.submit("glyphs", _images(2, seed=5))
            await service.stop()
            return r

        r = asyncio.run(run())
        want = ref.classify("glyphs", _images(2, seed=5))
        np.testing.assert_array_equal(r.predictions, want.predictions)

    def test_oversized_request_occupancy_accounting(self):
        """A request above max_batch executes as several engine slices;
        the occupancy histogram must reflect those buckets (occupancy
        stays a <= 1 fraction), while batches counts one dispatch."""
        engine, ref = _serving_pair(max_batch=8)
        service = ServingService(engine, ServiceConfig(max_delay_us=0.0))

        async def run():
            await service.start()
            r = await service.submit("glyphs", _images(19, seed=3))  # 8+8+3
            await service.stop()
            return r

        r = asyncio.run(run())
        want = ref.classify("glyphs", _images(19, seed=3))
        np.testing.assert_array_equal(r.predictions, want.predictions)
        st = service.stats("glyphs")
        assert st.batches == 1 and st.images == 19
        assert st.occupancy_hist == {
            4: {"batches": 1, "images": 3},
            8: {"batches": 2, "images": 16},
        }
        assert 0.0 < st.mean_occupancy <= 1.0

    def test_submit_requires_running_service(self):
        engine, _ = _serving_pair()
        service = ServingService(engine)
        with pytest.raises(ServiceStopped):
            service.submit_nowait("glyphs", _images(1))

    def test_stats_unknown_model_raises(self):
        engine, _ = _serving_pair()
        service = ServingService(engine)
        with pytest.raises(KeyError):
            service.stats("no-such-model")
        st = service.stats("glyphs")     # registered, no traffic: zeros
        assert st.completed == 0 and st.queue_depth == 0

    def test_service_config_validation(self):
        with pytest.raises(ValueError, match="max_coalesce"):
            ServiceConfig(max_coalesce=0)
        # The latency quantiles come from a histogram over the service's
        # whole life, so no window size is configured.
        with pytest.raises(TypeError, match="latency_window"):
            ServiceConfig(latency_window=0)
        engine, _ = _serving_pair()
        st = ServingService(engine).stats("glyphs")
        assert st.latency.count == 0 and st.p99_latency_us == 0.0

    def test_double_start_rejected(self):
        engine, _ = _serving_pair()
        service = ServingService(engine)

        async def run():
            await service.start()
            with pytest.raises(RuntimeError, match="already started"):
                await service.start()
            await service.stop()

        asyncio.run(run())
