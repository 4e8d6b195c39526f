"""Telemetry of the serving path: the stage histogram, the engine's and
the service's stage splits, compile counting, and the profiler spans on
the host plane of a CPU trace."""

import asyncio
import glob
import math
import os
import signal

import jax
import numpy as np
import pytest

from repro.core.cotm import CoTMConfig, init_boundary_model
from repro.core.patches import PatchSpec
from repro.serve import ServiceConfig, ServingEngine, ServingService
from repro.serve.telemetry import EDGES_US, N_FINITE, Histogram

SPEC = PatchSpec(image_x=11, image_y=11, window_x=5, window_y=5)
CFG = CoTMConfig(n_clauses=37, n_classes=10, patch=SPEC)

#: Every span the serving path opens (ARCHITECTURE.md §Telemetry).
SPANS = (
    "serve.dispatch", "serve.engine.pad", "serve.engine.put",
    "serve.engine.launch", "serve.engine.compile", "serve.complete",
    "serve.engine.wait", "serve.engine.fetch", "serve.resolve",
)


@pytest.fixture(autouse=True)
def time_limit(request):
    """Each test of this file fails after its own ``limit_s`` seconds."""
    limit = getattr(request.function, "limit_s", 60)

    def expired(signum, frame):
        raise TimeoutError(f"{request.node.name} ran past {limit} s")

    old = signal.signal(signal.SIGALRM, expired)
    signal.setitimer(signal.ITIMER_REAL, limit)
    yield
    signal.setitimer(signal.ITIMER_REAL, 0)
    signal.signal(signal.SIGALRM, old)


def limit_s(seconds):
    def mark(fn):
        fn.limit_s = seconds
        return fn
    return mark


def _frames(n, seed=0):
    rng = np.random.default_rng(seed)
    return (rng.random((n, SPEC.image_y, SPEC.image_x)) > 0.6).astype(np.uint8)


def _engine(max_batch=16):
    engine = ServingEngine(max_batch=max_batch)
    model = init_boundary_model(jax.random.PRNGKey(0), CFG)
    engine.register("m", model, CFG, booleanize_method="none")
    return engine


def _exact_rank(values, q):
    v = sorted(values)
    return v[max(math.ceil(q * len(v)), 1) - 1]


class TestHistogram:
    @limit_s(10)
    def test_bucket_edges(self):
        assert len(EDGES_US) == N_FINITE + 1 == 8 * 27 + 1
        assert EDGES_US[0] == 1.125 and EDGES_US[7] == 2.0
        assert EDGES_US[N_FINITE - 1] == 2.0 ** 27 and EDGES_US[-1] == math.inf
        widths = [b / a - 1 for a, b in zip(EDGES_US[:-2], EDGES_US[1:-1])]
        assert min(widths) > 0.05 and max(widths) <= 0.125
        # Each value lands in the bucket whose upper edge first exceeds it.
        for us, i in [(0.0, 0), (0.5, 0), (1.0, 0), (1.124, 0), (1.125, 1),
                      (2.0, 8), (3.0, 12), (1000.0, 79), (2.0 ** 27, N_FINITE),
                      (math.inf, N_FINITE), (math.nan, N_FINITE)]:
            h = Histogram()
            h.record(us)
            assert h.counts.index(1) == i, us
            if 1.0 <= us < 2.0 ** 27:
                assert (EDGES_US[i - 1] if i else 1.0) <= us < EDGES_US[i]
        h = Histogram()
        h.record(5.0, n=7)
        assert h.count == 7

    @limit_s(10)
    @pytest.mark.parametrize("q", [0.01, 0.5, 0.95, 0.99, 1.0])
    def test_quantile_within_one_bucket_of_nearest_rank(self, q):
        rng = np.random.default_rng(7)
        values = np.exp(rng.normal(7.0, 1.5, 5000)).tolist()   # ~1 us .. ~1 s
        h = Histogram()
        for v in values:
            h.record(v)
        exact = _exact_rank(values, q)
        got = h.quantile(q)
        i = EDGES_US.index(got)
        lo = EDGES_US[i - 1] if i else 0.0
        assert lo <= exact < got
        assert got / exact - 1 <= 0.125 + 1e-12
        assert Histogram().quantile(q) == 0.0

    @limit_s(10)
    def test_snapshot_subtraction(self):
        h = Histogram()
        for v in (3.0, 40.0, 40.0):
            h.record(v)
        before = h.copy()
        for v in (500.0, 500.0, 9000.0):
            h.record(v)
        assert before.count == 3                  # the copy does not alias
        window = h - before
        assert window.count == 3
        assert 500.0 < window.quantile(0.5) <= 500.0 * 1.125
        assert 9000.0 < window.quantile(1.0) <= 9000.0 * 1.125
        assert min(window.counts) == 0


class TestEngineStages:
    @limit_s(120)
    def test_compiles_count_first_dispatch_of_form_and_bucket(self):
        engine = _engine()
        assert engine.stats("m").compiles == 0
        engine.classify("m", _frames(3))                       # raw, 4
        engine.classify("m", _frames(4, seed=1))               # raw, 4: warm
        assert engine.stats("m").compiles == 1
        engine.classify("m", _frames(9))                       # raw, 16
        lits = engine.preprocess("m", _frames(3))
        engine.classify("m", lits, preprocessed=True)          # literals, 4
        assert engine.stats("m").compiles == 3
        assert engine.warmup("m", buckets=[4, 16], forms=("raw",)) == ()
        for n in (1, 3, 9, 16):
            engine.classify("m", _frames(n, seed=n))
        # Only bucket 1 was new; warm (form, bucket) pairs add nothing.
        assert engine.stats("m").compiles == 4

    @limit_s(120)
    def test_stats_snapshot_does_not_alias(self):
        engine = _engine()
        engine.classify("m", _frames(2))
        snap = engine.stats("m")
        engine.classify("m", _frames(2, seed=1))
        assert snap.dispatch.count == snap.fetch.count == 1
        assert engine.stats("m").dispatch.count == 2
        assert snap.as_dict()["fetch"]["count"] == 1
        # One chunk: preds and sums, copied at launch and read once.
        assert snap.copies_started == snap.copies_read == 2
        assert engine.stats("m").copies_read == 4
        d = snap.as_dict()
        assert d["copies_started"] == d["copies_read"] == 2


class TestServiceStages:
    @limit_s(180)
    def test_stages_partition_latency(self):
        engine = _engine()
        engine.warmup("m", forms=("raw",))
        service = ServingService(engine, ServiceConfig(max_delay_us=500.0))

        async def run():
            await service.start()
            try:
                futs = [service.submit_nowait("m", _frames(1 + i % 3, seed=i))
                        for i in range(24)]
                return await asyncio.gather(*futs)
            finally:
                await service.stop(drain=True)

        results = asyncio.run(asyncio.wait_for(run(), 120))
        for r in results:
            parts = (r.queue_s, r.slot_s, r.dispatch_s, r.complete_s)
            assert min(parts) >= 0.0
            assert abs(sum(parts) - r.latency_s) < 1e-6
        st = service.stats("m")
        assert st.queue.count == st.latency.count == len(results)
        for stage in ("slot", "dispatch", "complete", "resolve"):
            assert getattr(st, stage).count == len(results), stage
        assert st.p50_latency_us == st.latency.quantile(0.5) > 0.0
        assert st.as_dict()["latency"]["count"] == len(results)


@limit_s(240)
def test_profiler_host_plane_holds_every_span(tmp_path):
    from jax.profiler import ProfileData

    engine = _engine()
    service = ServingService(engine, ServiceConfig(max_delay_us=0.0))

    async def run():
        await service.start()
        try:
            # 3 rows pad to bucket 4: a compile on the first request, a
            # warm launch on the second.
            await service.submit("m", _frames(3))
            await service.submit("m", _frames(3, seed=1))
        finally:
            await service.stop(drain=True)

    jax.profiler.start_trace(str(tmp_path))
    try:
        asyncio.run(asyncio.wait_for(run(), 180))
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(tmp_path, "**", "*.xplane.pb"), recursive=True)
    names = {
        ev.name
        for plane in ProfileData.from_file(path).planes
        if not plane.name.startswith("/device:")
        for line in plane.lines
        for ev in line.events
    }
    assert set(SPANS) <= names, sorted(set(SPANS) - names)
