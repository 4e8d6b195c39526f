"""Async ServingService benchmark: open-loop Poisson load vs the ASIC.

Drives the asyncio service (queue -> latency-aware microbatch -> pow2
bucket -> jitted classify) with an open-loop Poisson arrival process of
single-image requests — arrivals follow a precomputed exponential
schedule and never wait for earlier results, which is how independent
users actually load a service (closed-loop generators hide queueing
collapse).  Three sweeps, reported as CSV rows:

  * arrival-rate sweep at a fixed ``max_delay_us`` over a preprocessed
    request pool: throughput, p50/p99 latency and batch occupancy as
    offered load approaches and exceeds capacity, compared against the
    chip's 60.3k classifications/s and 25.4 us single-image latency
    (Table II) — isolates the service spine from any ingress;
  * ``max_delay_us`` sweep at a fixed rate: the latency/occupancy
    tradeoff of the coalescing deadline (0 = pure latency mode);
  * **raw-pixel sweep**: the same open-loop load submitted as raw uint8
    images, through the device-resident ingress (raw pixels enqueue with
    a shape check; booleanize/patch/pack fuse into the microbatch's
    classify graph) vs the legacy per-request host ingress — the
    before/after of the device-resident ingress (EXPERIMENTS.md
    §Ingress; the ISSUE-4 acceptance criterion);
  * **robustness sweep** (ARCHITECTURE.md §Faults): deadline-checked vs
    unchecked load (the healthy-path cost of the request-lifetime
    machinery — shed scans, expiry bookkeeping; acceptance is < 5%
    throughput overhead), and the tuned path vs its one-step
    ``degraded_fallback`` (what a tripped circuit breaker costs while
    the primary path is out).

Rows carry machine-readable ``fields`` for ``benchmarks/run.py
--emit-json``.  Numbers land in EXPERIMENTS.md §Serve / §Ingress /
§Faults.

Run:  PYTHONPATH=src python -m benchmarks.bench_service [--quick]
"""

from __future__ import annotations

import argparse
import asyncio
from typing import Dict, List, Optional, Sequence

import jax
import numpy as np

from repro.launch.compile_cache import enable_compile_cache

PAPER_RATE = 60_300        # classifications/s @ 27.8 MHz
PAPER_LATENCY_US = 25.4    # single-image latency incl. system overhead

__all__ = ["bench_service", "run_load"]


def _setup(path: str, max_batch: int, tiny: bool = False):
    from repro.core.cotm import init_boundary_model
    from repro.serve import ServingEngine, get_path

    if tiny:
        from benchmarks.bench_ingress import tiny_config

        cfg = tiny_config()
    else:
        from repro.configs.convcotm import COTM_CONFIGS

        cfg = COTM_CONFIGS["convcotm-mnist"]
    model = init_boundary_model(jax.random.PRNGKey(0), cfg)
    engine = ServingEngine(max_batch=max_batch)
    engine.register("mnist", model, cfg, booleanize_method="threshold", path=path)
    engine.warmup("mnist")

    # Request pools, reused across sweeps: raw single images and their
    # preprocessed literal form.
    from repro.data.pipeline import preprocess_for_serving

    rng = np.random.default_rng(0)
    side = cfg.patch.image_y
    imgs = rng.integers(0, 256, (64, side, side)).astype(np.uint8)
    pre = preprocess_for_serving(
        imgs, cfg.patch, method="threshold",
        packed=get_path(path).input_form == "packed",
    )
    raw_pool = [imgs[i : i + 1] for i in range(len(imgs))]
    pre_pool = [pre[i : i + 1] for i in range(len(pre))]
    return engine, raw_pool, pre_pool


async def run_load(
    engine, pool, *, rate: float, n_requests: int, max_delay_us: float,
    high_water: int = 4096, seed: int = 0,
    preprocessed: bool = True, host_ingress: bool = False,
    deadline_s: Optional[float] = None,
) -> Dict:
    """One open-loop Poisson run; returns the stats row.

    ``deadline_s`` rides on every request: the service then runs the
    full request-lifetime machinery (expiry scans, shed-before-dispatch)
    even when the deadline is generous enough that nothing expires —
    which is exactly what the deadline-overhead rows measure.
    """
    from repro.serve import ServiceConfig, ServingService
    from repro.serve.loadgen import poisson_open_loop

    service = ServingService(
        engine, ServiceConfig(max_delay_us=max_delay_us, high_water=high_water)
    )
    await service.start()
    rng = np.random.default_rng(seed)
    pick = rng.integers(0, len(pool), n_requests)

    loop = asyncio.get_running_loop()
    t0 = loop.time()
    admitted, rejected = await poisson_open_loop(
        service, "mnist", [pool[i] for i in pick], rate,
        seed=seed, preprocessed=preprocessed, host_ingress=host_ingress,
        deadline_s=deadline_s,
    )
    # With a deadline set, shed requests resolve with ServiceExpired —
    # still a resolution, so gather with exceptions captured.
    await asyncio.gather(
        *(f for _, f in admitted), return_exceptions=True
    )
    await service.stop(drain=True)
    wall = loop.time() - t0

    st = service.stats("mnist")
    return {
        "offered_per_s": n_requests / wall,
        "achieved_per_s": st.completed / wall,
        "rejected": rejected,
        "expired": st.expired,
        "p50_us": st.p50_latency_us,
        "p99_us": st.p99_latency_us,
        "mean_occupancy": st.mean_occupancy,
        "batches": st.batches,
        "queue_p50_us": st.queue.quantile(0.5),
        "dispatch_p50_us": st.dispatch.quantile(0.5),
    }


def _row(name: str, r: Dict, derived: str, **fields) -> Dict:
    return {
        "name": name,
        "us_per_call": round(r["p50_us"], 1),
        "derived": derived,
        "fields": {
            "achieved_per_s": r["achieved_per_s"],
            "offered_per_s": r["offered_per_s"],
            "p50_us": r["p50_us"],
            "p99_us": r["p99_us"],
            "mean_occupancy": r["mean_occupancy"],
            "rejected": r["rejected"],
            "expired": r.get("expired", 0),
            "queue_p50_us": r["queue_p50_us"],
            "dispatch_p50_us": r["dispatch_p50_us"],
            **fields,
        },
    }


def bench_service(
    rates: Sequence[float] = (500.0, 2000.0, 8000.0),
    delays_us: Sequence[float] = (0.0, 200.0, 2000.0),
    raw_rates: Sequence[float] = (2000.0,),
    fixed_rate: float = 2000.0,
    n_requests: int = 400,
    path: str = "fused",
    max_batch: int = 256,
    tiny: bool = False,
) -> List[Dict]:
    """CSV rows: one per arrival rate, one per coalescing deadline, and
    one per (raw ingress mode, rate)."""
    engine, raw_pool, pre_pool = _setup(path, max_batch, tiny=tiny)
    rows = []
    for rate in rates:
        r = asyncio.run(
            run_load(engine, pre_pool, rate=rate, n_requests=n_requests,
                     max_delay_us=200.0)
        )
        rows.append(_row(
            f"service_{path}_rate{int(rate)}", r,
            (
                f"offered {r['offered_per_s']:,.0f}/s achieved "
                f"{r['achieved_per_s']:,.0f}/s "
                f"({r['achieved_per_s'] / PAPER_RATE:.3f}x ASIC) | "
                f"p50 {r['p50_us']:,.0f} us p99 {r['p99_us']:,.0f} us "
                f"(chip {PAPER_LATENCY_US} us) | occupancy "
                f"{r['mean_occupancy']:.2f} | rejected {r['rejected']}"
            ),
            kind="rate_sweep", rate=rate, path=path,
        ))
    for delay in delays_us:
        r = asyncio.run(
            run_load(engine, pre_pool, rate=fixed_rate, n_requests=n_requests,
                     max_delay_us=delay)
        )
        rows.append(_row(
            f"service_{path}_delay{int(delay)}us", r,
            (
                f"rate {fixed_rate:,.0f}/s | p50 {r['p50_us']:,.0f} us "
                f"p99 {r['p99_us']:,.0f} us | occupancy "
                f"{r['mean_occupancy']:.2f} over {r['batches']} batches"
            ),
            kind="delay_sweep", delay_us=delay, path=path,
        ))
    # Raw-pixel path: device-resident ingress vs the per-request host
    # pipeline, same open-loop load.  The ISSUE-4 acceptance comparison.
    for rate in raw_rates:
        raw_rows = {}
        for mode, host in (("device", False), ("host", True)):
            r = asyncio.run(
                run_load(engine, raw_pool, rate=rate, n_requests=n_requests,
                         max_delay_us=200.0,
                         preprocessed=False, host_ingress=host)
            )
            raw_rows[mode] = r
            rows.append(_row(
                f"service_{path}_raw_{mode}_rate{int(rate)}", r,
                (
                    f"RAW pixels, {mode} ingress | offered "
                    f"{r['offered_per_s']:,.0f}/s achieved "
                    f"{r['achieved_per_s']:,.0f}/s "
                    f"({r['achieved_per_s'] / PAPER_RATE:.3f}x ASIC) | "
                    f"p50 {r['p50_us']:,.0f} us p99 {r['p99_us']:,.0f} us | "
                    f"p50 queue {r['queue_p50_us']:,.0f} / dispatch "
                    f"{r['dispatch_p50_us']:,.0f} us"
                ),
                kind="raw_ingress", ingress=mode, rate=rate, path=path,
            ))
        speedup = (
            raw_rows["device"]["achieved_per_s"]
            / raw_rows["host"]["achieved_per_s"]
            if raw_rows["host"]["achieved_per_s"]
            else float("inf")
        )
        rows.append({
            "name": f"service_{path}_raw_speedup_rate{int(rate)}",
            "us_per_call": 0,
            "derived": (
                f"device-resident ingress {speedup:.1f}x host-ingress "
                f"baseline on the raw-pixel path"
            ),
            "fields": {"kind": "raw_speedup", "rate": rate, "speedup": speedup},
        })
    # Robustness rows (ARCHITECTURE.md §Faults).  First the price of the
    # request-lifetime machinery on a healthy service: identical load
    # with no deadline vs a generous one (nothing expires; the service
    # still runs every expiry scan).  Acceptance: < 5% throughput loss.
    r_unchecked = asyncio.run(
        run_load(engine, pre_pool, rate=fixed_rate, n_requests=n_requests,
                 max_delay_us=200.0)
    )
    r_checked = asyncio.run(
        run_load(engine, pre_pool, rate=fixed_rate, n_requests=n_requests,
                 max_delay_us=200.0, deadline_s=30.0)
    )
    overhead_pct = (
        100.0 * (1.0 - r_checked["achieved_per_s"]
                 / r_unchecked["achieved_per_s"])
        if r_unchecked["achieved_per_s"] else 0.0
    )
    for mode, r in (("unchecked", r_unchecked), ("checked", r_checked)):
        rows.append(_row(
            f"service_{path}_deadline_{mode}", r,
            (
                f"deadline {mode} | achieved {r['achieved_per_s']:,.0f}/s | "
                f"p50 {r['p50_us']:,.0f} us p99 {r['p99_us']:,.0f} us | "
                f"expired {r['expired']}"
            ),
            kind="deadline_overhead", mode=mode, path=path,
        ))
    rows.append({
        "name": f"service_{path}_deadline_overhead",
        "us_per_call": 0,
        "derived": (
            f"deadline-checked vs unchecked: {overhead_pct:+.1f}% "
            f"throughput overhead (acceptance < 5%)"
        ),
        "fields": {"kind": "deadline_overhead_pct", "path": path,
                   "overhead_pct": overhead_pct},
    })
    # Then the degraded mode: one circuit-breaker step down the fallback
    # chain (tuned plan dropped, ingress rebuilt for the fallback's input
    # form) vs the tuned path under the same raw-pixel load — raw pixels
    # because preprocessed pools are form-coupled to the path they were
    # packed for, while degradation's ingress rebuild makes raw
    # submissions path-agnostic (that IS the degraded contract).
    r_tuned_raw = asyncio.run(
        run_load(engine, raw_pool, rate=fixed_rate, n_requests=n_requests,
                 max_delay_us=200.0, preprocessed=False)
    )
    fallback = engine.degrade_path("mnist")
    if fallback is not None:
        engine.warmup("mnist")
        r_deg = asyncio.run(
            run_load(engine, raw_pool, rate=fixed_rate, n_requests=n_requests,
                     max_delay_us=200.0, preprocessed=False)
        )
        ratio = (
            r_deg["achieved_per_s"] / r_tuned_raw["achieved_per_s"]
            if r_tuned_raw["achieved_per_s"] else 0.0
        )
        rows.append(_row(
            f"service_{path}_degraded_{fallback}", r_deg,
            (
                f"degraded {path} -> {fallback} | achieved "
                f"{r_deg['achieved_per_s']:,.0f}/s "
                f"({ratio:.2f}x tuned {path}) | p50 {r_deg['p50_us']:,.0f} us "
                f"p99 {r_deg['p99_us']:,.0f} us"
            ),
            kind="degraded_path", path=path, fallback=fallback,
            vs_tuned_ratio=ratio,
        ))
    return rows


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true", help="fewer rates/requests")
    ap.add_argument("--tiny", action="store_true", help="CI-smoke geometry")
    ap.add_argument("--path", default="fused")
    args = ap.parse_args()
    enable_compile_cache()
    kw = dict(tiny=args.tiny)
    if args.quick:
        kw.update(rates=(500.0, 2000.0), delays_us=(0.0, 200.0),
                  raw_rates=(2000.0,), n_requests=150)
    print("name,us_per_call,derived")
    for r in bench_service(path=args.path, **kw):
        print(f"{r['name']},{r['us_per_call']},{r['derived']}")


if __name__ == "__main__":
    main()
