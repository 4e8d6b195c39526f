"""Knee sweep for an open-loop cell: the cell's system, set up once, offered
each rate in turn for a short window, on the chip:

    python bench/sweep.py --workload mnist-sensors --seed 7 --seconds 5 \
        --rates 2000,4000,8000,16000

One line per rate: p50, p95 and p99 latency from due time to result (refused
and failed requests count as +inf), the requests refused and failed, how
late the generator ran, and frames per microbatch.  The knee is the
highest rate at which p99 holds steady and nothing is refused; a cell
runs at about 4/5 of it.
"""

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import asyncio  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT / "src"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--rates", required=True, help="comma-separated requests/s")
    args = ap.parse_args(argv)

    import jax
    import numpy as np

    import harness
    import load

    spec = harness.load_spec()
    wl = harness.find_workload(spec, args.workload)
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < wl["chips"]:
        print(f"sweep: needs {wl['chips']} TPU chips, JAX found {len(devices)} "
              f"{devices[0].platform}; nothing run", file=sys.stderr)
        return 2
    cfg = harness.load_config(spec, wl["config"])
    traffic = harness.load_traffic(wl["traffic"])
    if traffic["entry"] != "service":
        print("sweep: only open-loop (service) traffic has a knee", file=sys.stderr)
        return 2
    harness.enable_compile_cache(jax)
    rng = np.random.default_rng(args.seed)
    family = harness.load_family(spec, wl["config"])
    engine, arch = family.build_engine(cfg, traffic, family.make_model(jax, cfg, args.seed))
    pool = family.make_frames(rng, traffic["pool_frames"], cfg)
    engine.warmup(arch, forms=("raw",))
    print(f"device: platform={devices[0].platform} kind={devices[0].device_kind} "
          f"count={len(devices)}; set-up {time.monotonic() - T_START:.3f} s", flush=True)

    from repro.serve import ServiceConfig, ServingService

    async def one(rate):
        service = ServingService(engine, ServiceConfig())
        await service.start()
        try:
            await asyncio.gather(
                *(service.submit_nowait(arch, pool[j:j + 1]) for j in range(256)))
            before = service.stats(arch)
            rec = await load.open_loop(service, arch, pool, dict(traffic, rate_per_s=rate),
                                       rng, args.seconds, cfg["n_classes"], False)
            after = service.stats(arch)
        finally:
            await service.stop(drain=True)
        batches = after.batches - before.batches
        return {
            "rate_per_s": rate,
            "due": rec["due"],
            "refused": rec["refused"],
            "failed": rec["unanswered"],
            "p50_latency_us": harness.percentile(rec["latency_s"], 50) * 1e6,
            "p95_latency_us": harness.percentile(rec["latency_s"], 95) * 1e6,
            "p99_latency_us": harness.percentile(rec["latency_s"], 99) * 1e6,
            "gen_lag_p99_us": harness.percentile(rec["lateness_s"], 99) * 1e6,
            "frames_per_batch": (after.images - before.images) / batches if batches else None,
        }

    for rate in (float(r) for r in args.rates.split(",")):
        row = asyncio.run(one(rate))
        print(json.dumps({k: (v if v is None or np.isfinite(v) else "inf")
                          for k, v in row.items()}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
