"""Async serving-service lifecycle: register -> start -> submit concurrently
-> observe stats -> graceful drain.

Two tenants (an MNIST-style model with fixed-threshold booleanization and
an FMNIST-style model with adaptive Gaussian booleanization) share one
ServingService.  Concurrent submitters fire mixed-size requests at both;
the microbatcher coalesces them into pow2 buckets under the 200 us
deadline, round-robin keeps the tenants fair, and the run ends with a
graceful drain — every in-queue request is answered before shutdown.
Also demonstrates backpressure: a burst past the high-water mark is
rejected with a retry-after hint instead of queueing unboundedly.

Run:  PYTHONPATH=src python examples/serve_service.py
"""

import asyncio
import dataclasses

import jax
import numpy as np

from repro.configs.convcotm import COTM_CONFIGS
from repro.core.cotm import init_boundary_model
from repro.data import get_dataset
from repro.serve import (
    ServiceConfig,
    ServiceOverloaded,
    ServingEngine,
    ServingService,
)


async def submitter(service, name, images, n_requests, max_n, seed):
    """One tenant's request stream: mixed-size batches, back to back."""
    rng = np.random.default_rng(seed)
    ok, res = 0, None
    for _ in range(n_requests):
        n = int(rng.integers(1, max_n + 1))
        idx = rng.integers(0, len(images), n)
        try:
            res = await service.submit(name, images[idx])
            ok += 1
        except ServiceOverloaded as e:
            await asyncio.sleep(e.retry_after_s)
            continue
        await asyncio.sleep(0)     # hand the loop to the other tenant
    return ok, res


async def main():
    cfg = dataclasses.replace(
        COTM_CONFIGS["convcotm-mnist"], n_clauses=64, eval_path="fused"
    )
    _, _, vx, _, source = get_dataset("mnist", n_test=512)
    print(f"dataset source: {source}")

    # 1. Register two tenants (independent models, booleanizers, stats).
    engine = ServingEngine(max_batch=32)
    for i, (name, method) in enumerate(
        [("mnist", "threshold"), ("fmnist", "adaptive")]
    ):
        model = init_boundary_model(jax.random.PRNGKey(i), cfg)
        engine.register(name, model, cfg, booleanize_method=method)
        engine.warmup(name)

    # 2. Start the service: bounded queue, 200 us coalescing deadline.
    service = ServingService(
        engine, ServiceConfig(max_delay_us=200.0, high_water=256)
    )
    await service.start()

    # 3. Two concurrent tenants submit mixed-size requests.
    totals = await asyncio.gather(
        submitter(service, "mnist", vx, 20, 24, seed=1),
        submitter(service, "fmnist", vx, 20, 24, seed=2),
    )
    for name, (ok, res) in zip(("mnist", "fmnist"), totals):
        last = (
            f"last rode a bucket-{res.bucket} microbatch of "
            f"{res.batch_requests} request(s)" if res else "all rejected"
        )
        print(f"{name}: {ok} requests served; {last}")

    # 4. Backpressure: a burst past high_water is rejected, not queued.
    burst = [vx[:16] for _ in range(64)]
    admitted = rejected = 0
    hint = 0.0
    futures = []
    for b in burst:
        try:
            futures.append(service.submit_nowait("mnist", b))
            admitted += 1
        except ServiceOverloaded as e:
            rejected += 1
            hint = e.retry_after_s
    await asyncio.gather(*futures)
    print(f"burst of {len(burst)}: admitted {admitted}, rejected {rejected} "
          f"(retry-after hint {hint * 1e3:.1f} ms)")

    # 5. Snapshot stats, then drain gracefully.
    for name in engine.models():
        st = service.stats(name)
        print(
            f"{name}: {st.completed} requests / {st.images} images in "
            f"{st.batches} microbatches | occupancy {st.mean_occupancy:.2f} | "
            f"p50 {st.p50_latency_us:,.0f} us p99 {st.p99_latency_us:,.0f} us | "
            f"p50 queue {st.queue.quantile(0.5):,.0f} / slot "
            f"{st.slot.quantile(0.5):,.0f} / dispatch "
            f"{st.dispatch.quantile(0.5):,.0f} / complete "
            f"{st.complete.quantile(0.5):,.0f} us (raw pixels ride the "
            f"fused device-ingress graph)"
        )
    await service.stop(drain=True)
    print("drained and stopped.")


if __name__ == "__main__":
    asyncio.run(main())
