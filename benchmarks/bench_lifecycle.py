"""Hot-swap pause benchmark: what a swap storm costs live traffic.

The lifecycle contract (ARCHITECTURE.md §Lifecycle) is *zero-downtime*:
a swap never drops or fails a request.  What it may do is add latency —
the engine lock serializes the install against microbatch dispatch, and
the candidate pays its per-version sparsity analysis before the flip.
This benchmark measures that pause directly:

  * **baseline** — open-loop Poisson load (single raw images through the
    device-resident ingress), no lifecycle events: p50/p99 latency;
  * **swap storm** — the identical load while hot swaps + a rollback
    land mid-stream (weight-variant candidates, the shape a retrained
    model actually has): p50/p99 again.  The p99 delta is the headline
    "swap pause" number (EXPERIMENTS.md §Lifecycle);
  * **install costs** — wall time of ``engine.swap`` (freeze + sparsity
    analysis + stamp + flip) and ``engine.rollback`` (an O(1) pointer
    flip) off the serving path, plus the jit cache growth across the
    storm (0 once the pow2 sparsity bin is warm — the
    compiles-only-the-delta contract, tests/test_lifecycle.py).

Run:  PYTHONPATH=src python -m benchmarks.bench_lifecycle [--tiny] [--quick]
"""

from __future__ import annotations

import argparse
import asyncio
import time
from typing import Dict, List

import jax
import numpy as np

from repro.launch.compile_cache import enable_compile_cache

__all__ = ["bench_lifecycle"]


def _setup(max_batch: int, tiny: bool):
    from repro.core.cotm import CoTMModel, init_boundary_model
    from repro.serve import ServingEngine

    if tiny:
        from benchmarks.bench_ingress import tiny_config

        cfg = tiny_config()
    else:
        from repro.configs.convcotm import COTM_CONFIGS

        cfg = COTM_CONFIGS["convcotm-mnist"]
    base = init_boundary_model(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(1)
    w = np.asarray(base.weights)
    variants = [
        CoTMModel(
            ta_state=base.ta_state,
            weights=jax.numpy.asarray(
                w + rng.integers(-3, 4, w.shape).astype(w.dtype)
            ),
        )
        for _ in range(8)
    ]
    engine = ServingEngine(max_batch=max_batch)
    engine.register("m", base, cfg, booleanize_method="threshold")
    engine.warmup("m", forms=("raw",))
    # Warm the pow2-binned sparsity shape a swapped-in image carries, so
    # the storm measures the install pause, not one-time compiles.
    engine.swap("m", variants[0], cfg)
    engine.warmup("m", forms=("raw",))
    side = cfg.patch.image_y
    imgs = rng.integers(0, 256, (64, side, side)).astype(np.uint8)
    pool = [imgs[i : i + 1] for i in range(len(imgs))]
    return engine, cfg, variants, pool


async def _run(
    engine, cfg, pool, *, rate: float, n_requests: int, seed: int,
    swaps=None,
) -> Dict:
    """One open-loop run; ``swaps`` (model list) land evenly spaced
    through the stream via the service's off-loop swap, ending with one
    rollback.  Returns latency stats + per-event install times."""
    from repro.serve import ServiceConfig, ServingService
    from repro.serve.loadgen import poisson_open_loop

    service = ServingService(engine, ServiceConfig(max_delay_us=200.0))
    await service.start()
    rng = np.random.default_rng(seed)
    pick = rng.integers(0, len(pool), n_requests)
    load = asyncio.create_task(
        poisson_open_loop(
            service, "m", [pool[i] for i in pick], rate, seed=seed
        )
    )
    swap_s: List[float] = []
    rollback_s = 0.0
    if swaps:
        gap = n_requests / rate / (len(swaps) + 2)
        for candidate in swaps:
            await asyncio.sleep(gap)
            t0 = time.perf_counter()
            await service.swap("m", candidate, cfg)
            swap_s.append(time.perf_counter() - t0)
        await asyncio.sleep(gap)
        t0 = time.perf_counter()
        await service.rollback("m")
        rollback_s = time.perf_counter() - t0
    admitted, rejected = await load
    await asyncio.gather(*(f for _, f in admitted))
    await service.stop(drain=True)
    st = service.stats("m")
    return {
        "p50_us": st.p50_latency_us,
        "p99_us": st.p99_latency_us,
        "completed": st.completed,
        "rejected": rejected,
        "swap_ms": [s * 1e3 for s in swap_s],
        "rollback_ms": rollback_s * 1e3,
    }


def bench_lifecycle(
    rate: float = 2000.0,
    n_requests: int = 400,
    n_swaps: int = 4,
    max_batch: int = 256,
    tiny: bool = False,
) -> List[Dict]:
    import repro.serve.engine as engine_mod
    from tools.recompile_guard import RecompileGuard

    engine, cfg, variants, pool = _setup(max_batch, tiny=tiny)
    base_r = asyncio.run(
        _run(engine, cfg, pool, rate=rate, n_requests=n_requests, seed=2)
    )
    guard = RecompileGuard(
        engine_mod.classify_step, (engine_mod, "_raw_step_jit"),
        allow=10**9,   # measuring, not asserting — tests own the assert
    )
    with guard:
        storm_r = asyncio.run(
            _run(
                engine, cfg, pool, rate=rate, n_requests=n_requests, seed=2,
                swaps=variants[1 : 1 + n_swaps],
            )
        )
    compiles = sum(d.grew for d in guard.deltas if d.grew > 0)
    added_p99 = storm_r["p99_us"] - base_r["p99_us"]
    swap_ms = storm_r["swap_ms"]
    rows = [
        {
            "name": "lifecycle_baseline",
            "us_per_call": round(base_r["p50_us"], 1),
            "derived": (
                f"no lifecycle events | p50 {base_r['p50_us']:,.0f} us "
                f"p99 {base_r['p99_us']:,.0f} us | "
                f"{base_r['completed']} completed, "
                f"{base_r['rejected']} rejected"
            ),
            "fields": {"kind": "lifecycle", **base_r, "rate": rate},
        },
        {
            "name": f"lifecycle_swap_storm_x{n_swaps}",
            "us_per_call": round(storm_r["p50_us"], 1),
            "derived": (
                f"{n_swaps} swaps + 1 rollback mid-stream | p50 "
                f"{storm_r['p50_us']:,.0f} us p99 {storm_r['p99_us']:,.0f} us "
                f"(added p99 {added_p99:+,.0f} us) | swap install "
                f"{np.mean(swap_ms):,.1f} ms mean, rollback "
                f"{storm_r['rollback_ms']:,.2f} ms | {compiles} compiles | "
                f"{storm_r['completed']} completed, "
                f"{storm_r['rejected']} rejected"
            ),
            "fields": {
                "kind": "lifecycle", **storm_r, "rate": rate,
                "added_p99_us": added_p99, "compiles": compiles,
            },
        },
    ]
    return rows


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true", help="fewer requests")
    ap.add_argument("--tiny", action="store_true", help="CI-smoke geometry")
    ap.add_argument("--rate", type=float, default=2000.0)
    args = ap.parse_args()
    enable_compile_cache()
    kw = dict(tiny=args.tiny, rate=args.rate)
    if args.quick:
        kw.update(n_requests=150, n_swaps=3)
    print("name,us_per_call,derived")
    for r in bench_lifecycle(**kw):
        print(f"{r['name']},{r['us_per_call']},{r['derived']}")


if __name__ == "__main__":
    main()
