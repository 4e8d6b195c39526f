"""Ingress benchmark: host pipeline vs the device-resident fused graph.

Measures the cost of getting raw pixels into the clause datapath, the
stage the ASIC gets "for free" (booleanized pixels stream straight into
the clause pool, Sec. IV-C) and the stage that dominated the serving
stack before the device-resident ingress:

  * **host**   — ``data.pipeline.preprocess_for_serving``: booleanize
    (jnp -> np), patch/literals/pack (np -> jnp -> np), literals back on
    the host.  At least three host<->device round trips per request.
  * **device** — ``core.ingress.device_ingress``: the same stages fused
    into one jitted dispatch; one H2D copy of raw uint8 in.
  * **e2e**    — the serving engine's full raw->predictions step
    (``classify``), device vs host ingress modes, isolating how much of
    request latency the ingress split explains.

Rows carry machine-readable ``fields`` (consumed by
``benchmarks/run.py --emit-json`` -> ``BENCH_ingress.json``) on top of
the repo's ``name,us_per_call,derived`` CSV contract.  Numbers land in
EXPERIMENTS.md §Ingress.

Run:  PYTHONPATH=src python -m benchmarks.bench_ingress [--quick] [--tiny]
"""

from __future__ import annotations

import argparse
import time
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from repro.launch.compile_cache import enable_compile_cache

__all__ = ["bench_ingress", "tiny_config"]


def tiny_config():
    """A CI-smoke geometry: small clause pool, 7x7 patches."""
    from repro.core.cotm import CoTMConfig
    from repro.core.patches import PatchSpec

    return CoTMConfig(
        n_clauses=32,
        n_classes=10,
        patch=PatchSpec(image_x=11, image_y=11, window_x=5, window_y=5),
    )


def _paper_config():
    from repro.configs.convcotm import COTM_CONFIGS

    return COTM_CONFIGS["convcotm-mnist"]


def _time(fn, n_iter: int) -> float:
    """Median-of-runs microseconds per call (fn must block internally)."""
    ts = []
    for _ in range(n_iter):
        t0 = time.perf_counter()
        fn()
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts) * 1e6)


def bench_ingress(
    methods=("threshold", "adaptive", "none"),
    buckets=(1, 64),
    n_iter: int = 10,
    tiny: bool = False,
    path: str = "fused",
) -> List[Dict]:
    """One row per (method, bucket): host vs device ingress microseconds,
    plus end-to-end engine rows (device vs host raw classify)."""
    from repro.core.cotm import init_boundary_model
    from repro.core.ingress import IngressSpec, device_ingress
    from repro.data.pipeline import preprocess_for_serving
    from repro.serve import ServingEngine, get_path

    cfg = tiny_config() if tiny else _paper_config()
    spec = cfg.patch
    packed = get_path(path).input_form == "packed"
    rng = np.random.default_rng(0)
    rows: List[Dict] = []

    for method in methods:
        ispec = IngressSpec(patch=spec, method=method, packed=packed)
        for b in buckets:
            raw = rng.integers(0, 256, (b, spec.image_y, spec.image_x))
            raw = (raw > 128).astype(np.uint8) if method == "none" else raw.astype(np.uint8)

            def host():
                preprocess_for_serving(raw, spec, method=method, packed=packed)

            def device():
                jax.block_until_ready(device_ingress(ispec, jnp.asarray(raw)))

            host()      # trace/compile warmup
            device()
            host_us = _time(host, n_iter)
            dev_us = _time(device, n_iter)
            rows.append(
                {
                    "name": f"ingress_{method}_b{b}",
                    "us_per_call": round(dev_us, 1),
                    "derived": (
                        f"device {dev_us:,.0f} us vs host {host_us:,.0f} us "
                        f"({host_us / dev_us:.1f}x) | "
                        f"{b / dev_us * 1e6:,.0f} img/s device ingress"
                    ),
                    "fields": {
                        "kind": "ingress",
                        "method": method,
                        "bucket": b,
                        "host_us": host_us,
                        "device_us": dev_us,
                        "speedup": host_us / dev_us,
                    },
                }
            )

    # End to end: the engine's raw path, device vs host ingress modes.
    engine = ServingEngine(max_batch=max(buckets))
    model = init_boundary_model(jax.random.PRNGKey(0), cfg)
    engine.register("m", model, cfg, booleanize_method="threshold", path=path)
    engine.warmup("m", buckets=buckets)
    for b in buckets:
        raw = rng.integers(0, 256, (b, spec.image_y, spec.image_x)).astype(np.uint8)
        for mode in ("device", "host"):
            engine.classify("m", raw, ingress=mode)   # warm ingress caches
            us = _time(
                lambda m=mode: engine.classify("m", raw, ingress=m), n_iter
            )
            st = engine.stats("m")
            rows.append(
                {
                    "name": f"classify_raw_{mode}_{path}_b{b}",
                    "us_per_call": round(us, 1),
                    "derived": (
                        f"{b / us * 1e6:,.0f} cls/s end-to-end raw ({mode} "
                        f"ingress) | p50 so far: dispatch "
                        f"{st.dispatch.quantile(0.5):,.0f} / wait "
                        f"{st.wait.quantile(0.5):,.0f} / fetch "
                        f"{st.fetch.quantile(0.5):,.0f} us per request"
                    ),
                    "fields": {
                        "kind": "classify_raw",
                        "ingress": mode,
                        "path": path,
                        "bucket": b,
                        "us_per_request": us,
                        "cls_per_s": b / us * 1e6,
                    },
                }
            )
    return rows


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true", help="fewer methods/reps")
    ap.add_argument("--tiny", action="store_true", help="CI-smoke geometry")
    ap.add_argument("--path", default="fused")
    args = ap.parse_args()
    enable_compile_cache()
    kw = dict(tiny=args.tiny, path=args.path)
    if args.quick:
        kw.update(methods=("threshold",), buckets=(1, 8), n_iter=3)
    print("name,us_per_call,derived")
    for r in bench_ingress(**kw):
        print(f"{r['name']},{r['us_per_call']},{r['derived']}")


if __name__ == "__main__":
    main()
