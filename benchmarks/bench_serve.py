"""Serving-engine throughput benchmark vs. the paper's ASIC figures.

Measures end-to-end classifications/s of the batched ``repro.serve``
engine at the paper's exact model scale (128 clauses, 361 patches, 272
literals), across several power-of-two batch buckets, and compares
against the chip's 60.3k classifications/s and 25.4 us single-image
latency (Table II, 27.8 MHz point).

Two raw-request ingress modes are measured:

  * ``device`` (default) — the fused raw->predictions graph: one jitted
    step per bucket, single H2D copy (``core.ingress``);
  * ``host`` — the legacy per-request host pipeline (booleanize ->
    patch -> pack on the host, three round trips), kept as the baseline.

Rows carry machine-readable ``fields`` for ``benchmarks/run.py
--emit-json`` (-> ``BENCH_serve.json``); per-request latency is split
into the engine's ingress, dispatch, wait and fetch stages
(``ClassifyResult``; EXPERIMENTS.md §Ingress).  Every
``serve_engine`` row also carries the analytic roofline columns from
``roofline.analysis.tm_path_roofline`` — the v5e ceiling for the path
that actually ran (``resolved_path``: the autotuned winner, or a sparse
path's dense fallback) and the achieved fraction against it
(EXPERIMENTS.md §Sparsity).

``bench_serve`` sweeps one or more eval paths (``paths=``, CLI
``--paths fused,fused_sparse``); ``--autotune`` registers under the
per-bucket autotuner so rows report the tuned winner per (form, bucket).

``bench_sparsity_sweep`` measures the sparse-vs-dense crossover: for a
range of active-clause fractions (empty clauses forced by zeroing TA
rows — no include => empty, Sec. IV-D) it times each dense path against
its sparse twin and reports the step's speedup per fraction
(EXPERIMENTS.md §Sparsity).

``bench_serve_mesh`` adds per-device-count rows (the ``serve_mesh``
kind): the same raw-pixel workload served by a :class:`ServeMesh`-backed
engine at 1/2/8 data shards — each row records the devices the batch was
actually spread over (EXPERIMENTS.md §Serve/mesh).  Run it with
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` on CPU;
``benchmarks/run.py --emit-json`` sweeps it in-process over the devices
the process has.

Runs on CPU with the ``ref`` kernel backend (the non-TPU default).

Run:  PYTHONPATH=src python -m benchmarks.bench_serve [--quick] [--tiny]
          [--paths fused,fused_sparse] [--autotune] [--sparsity]
      XLA_FLAGS=--xla_force_host_platform_device_count=8 \\
      PYTHONPATH=src python -m benchmarks.bench_serve --mesh [--tiny]
"""

from __future__ import annotations

import argparse
from typing import Dict, List, Optional, Sequence

import jax
import numpy as np

from repro.launch.compile_cache import enable_compile_cache

PAPER_RATE = 60_300        # classifications/s @ 27.8 MHz
PAPER_LATENCY_US = 25.4    # single-image latency incl. system overhead

__all__ = ["bench_serve", "bench_serve_mesh", "bench_sparsity_sweep"]


def _config(tiny: bool):
    if tiny:
        from benchmarks.bench_ingress import tiny_config

        return tiny_config()
    from repro.configs.convcotm import COTM_CONFIGS

    return COTM_CONFIGS["convcotm-mnist"]


def _engine(
    path: str,
    max_batch: int,
    tiny: bool = False,
    mesh=None,
    *,
    autotune: bool = False,
    model=None,
):
    from repro.core.cotm import init_boundary_model
    from repro.serve import ServingEngine

    cfg = _config(tiny)
    if model is None:
        model = init_boundary_model(jax.random.PRNGKey(0), cfg)
    engine = ServingEngine(max_batch=max_batch, mesh=mesh, autotune=autotune)
    engine.register("mnist", model, cfg, booleanize_method="threshold", path=path)
    return engine, cfg


def _roofline_fields(engine, cfg, form: str, bucket: int) -> Dict:
    """The analytic-ceiling columns for the path a (form, bucket)
    dispatch actually evaluates (tuned winner / fallback-resolved)."""
    from repro.roofline.analysis import tm_path_roofline

    resolved, params = engine.resolved_path("mnist", form, bucket)
    sp = engine.servable("mnist").sparsity
    rl = tm_path_roofline(
        cfg,
        resolved,
        engine.bucket_for(bucket),
        n_active=None if sp is None else sp.n_active,
    )
    return {
        "resolved_path": resolved,
        "tuned_params": [list(kv) for kv in params],
        "roofline_bound": rl["bound"],
        "roofline_ceiling_cls_per_s": rl["ceiling_cls_per_s"],
    }


def bench_serve(
    buckets=(1, 8, 64, 256),
    n_requests: int = 10,
    path: str = "fused",
    ingress_modes=("device", "host"),
    tiny: bool = False,
    paths: Optional[Sequence[str]] = None,
    autotune: bool = False,
) -> List[Dict]:
    """One CSV row per (path, ingress mode, batch bucket): us/request +
    classifications/s + the ingress/dispatch/wait/fetch split + the roofline
    ceiling/fraction for the path that actually ran."""
    rows = []
    for p in paths if paths is not None else (path,):
        rows += _bench_serve_one(
            p, buckets, n_requests, ingress_modes, tiny, autotune
        )
    return rows


def _bench_serve_one(
    path: str, buckets, n_requests, ingress_modes, tiny, autotune
) -> List[Dict]:
    engine, cfg = _engine(path, max_batch=max(buckets), tiny=tiny, autotune=autotune)
    if autotune:
        # Tune every measured bucket (not just min/max) so each row's
        # resolved_path is that bucket's winner, then warm the winners.
        engine.autotune("mnist", buckets=buckets)
    engine.warmup("mnist", buckets=buckets)
    rng = np.random.default_rng(0)
    side = cfg.patch.image_y
    rows = []
    for mode in ingress_modes:
        form = "raw" if mode == "device" else "literals"
        for bucket in buckets:
            imgs = rng.integers(0, 256, (bucket, side, side)).astype(np.uint8)
            # One untimed request: warms the host-side trace caches for
            # this shape; the jitted classify step itself was compiled by
            # engine.warmup above.
            engine.classify("mnist", imgs, ingress=mode)
            t = t_in = t_disp = t_wait = t_fetch = 0.0
            for _ in range(n_requests):
                res = engine.classify("mnist", imgs, ingress=mode)
                t += res.latency_s
                t_in += res.ingress_s
                t_disp += res.dispatch_s
                t_wait += res.wait_s
                t_fetch += res.fetch_s
            n = n_requests * bucket
            rate = n / t
            us = t / n_requests * 1e6
            rl = _roofline_fields(engine, cfg, form, bucket)
            rl["roofline_fraction"] = (
                rate / rl["roofline_ceiling_cls_per_s"]
                if rl["roofline_ceiling_cls_per_s"] > 0
                else 0.0
            )
            rows.append(
                {
                    "name": f"serve_engine_{path}_{mode}_b{bucket}",
                    "us_per_call": round(us, 1),
                    "derived": (
                        f"{rate:,.0f} class/s = {rate / PAPER_RATE:.3f}x ASIC "
                        f"({PAPER_RATE}/s); per-image {us / bucket:.1f} us "
                        f"vs chip {PAPER_LATENCY_US} us | split ingress "
                        f"{t_in / n_requests * 1e6:,.0f} / dispatch "
                        f"{t_disp / n_requests * 1e6:,.0f} / wait "
                        f"{t_wait / n_requests * 1e6:,.0f} / fetch "
                        f"{t_fetch / n_requests * 1e6:,.0f} us | "
                        f"ran {rl['resolved_path']} at "
                        f"{rl['roofline_fraction']:.1e} of "
                        f"{rl['roofline_bound']}-bound ceiling"
                    ),
                    "fields": {
                        "kind": "serve_engine",
                        "path": path,
                        "ingress": mode,
                        "bucket": bucket,
                        "us_per_request": us,
                        "cls_per_s": rate,
                        "x_asic": rate / PAPER_RATE,
                        "ingress_us": t_in / n_requests * 1e6,
                        "dispatch_us": t_disp / n_requests * 1e6,
                        "wait_us": t_wait / n_requests * 1e6,
                        "fetch_us": t_fetch / n_requests * 1e6,
                        "autotuned": autotune,
                        **rl,
                    },
                }
            )
    st = engine.stats("mnist")
    rows.append(
        {
            "name": f"serve_engine_{path}_compiles",
            "us_per_call": 0,
            "derived": (
                f"{len(st.compiled_buckets)} bucket compiles for "
                f"{st.requests} requests (bounded-recompile contract)"
                + (
                    f"; autotune {st.autotune.get('total_s', 0):.1f}s over "
                    f"{len(st.autotune.get('plan', []))} plan entries"
                    if st.autotune
                    else ""
                )
            ),
            "fields": {
                "kind": "compiles",
                "path": path,
                "compiled_buckets": list(st.compiled_buckets),
                "requests": st.requests,
                **(
                    {
                        "autotune_total_s": st.autotune.get("total_s"),
                        "autotune_plan": st.autotune.get("plan"),
                    }
                    if st.autotune
                    else {}
                ),
            },
        }
    )
    return rows


def _model_with_active_fraction(cfg, fraction: float, key: int = 0):
    """A boundary-initialised model whose trailing clauses are forced
    empty: zeroed TA rows sit below TA_HALF, so every literal is
    excluded and the clause can never fire (the Sec. IV-D empty-clause
    rule) — ``analyze_sparsity`` then drops them from the active set."""
    import dataclasses

    import jax.numpy as jnp

    from repro.core.cotm import TA_HALF, init_boundary_model

    model = init_boundary_model(jax.random.PRNGKey(key), cfg)
    n_clauses = model.ta_state.shape[0]
    n_active = int(round(n_clauses * fraction))
    ta = np.asarray(model.ta_state).copy()
    ta[n_active:] = 0
    if n_active:                      # keep survivors provably non-empty
        ta[:n_active, 0] = np.maximum(ta[:n_active, 0], TA_HALF)
    return dataclasses.replace(model, ta_state=jnp.asarray(ta)), n_active


def bench_sparsity_sweep(
    active_fractions=(0.0625, 0.25, 0.5, 1.0),
    pairs=(
        ("bitpacked", "sparse"),
        ("matmul", "matmul_sparse"),
        ("fused", "fused_sparse"),
    ),
    bucket: int = 64,
    n_requests: int = 5,
    tiny: bool = False,
) -> List[Dict]:
    """Sparse-vs-dense crossover: per active-clause fraction, time each
    dense path against its sparse twin on the same model and report the
    speedup of the step (launch, wait and copy back).  The crossover
    point (where the sparse win exceeds its gather overhead) is what the
    autotuner discovers empirically per (bucket, geometry)."""
    cfg = _config(tiny)
    side = cfg.patch.image_y
    rng = np.random.default_rng(0)
    rows = []
    for fraction in active_fractions:
        model, n_active = _model_with_active_fraction(cfg, fraction)
        imgs = rng.integers(0, 256, (bucket, side, side)).astype(np.uint8)
        dense_step_us: Dict[str, float] = {}
        for dense_name, sparse_name in pairs:
            for p in (dense_name, sparse_name):
                engine, _ = _engine(p, max_batch=bucket, tiny=tiny, model=model)
                engine.warmup("mnist", buckets=(bucket,), forms=("raw",))
                engine.classify("mnist", imgs)      # host-cache warmup
                t = t_step = 0.0
                for _ in range(n_requests):
                    res = engine.classify("mnist", imgs)
                    t += res.latency_s
                    # Everything after validation: the step's launch,
                    # the wait on it and the copy back.
                    t_step += res.latency_s - res.ingress_s
                rate = n_requests * bucket / t
                step_us = t_step / n_requests * 1e6
                if p == dense_name:
                    dense_step_us[dense_name] = step_us
                speedup = (
                    dense_step_us[dense_name] / step_us if p == sparse_name else 1.0
                )
                rl = _roofline_fields(engine, cfg, "raw", bucket)
                rows.append(
                    {
                        "name": f"sparsity_{p}_a{fraction:g}_b{bucket}",
                        "us_per_call": round(step_us, 1),
                        "derived": (
                            f"{n_active} active clauses ({fraction:.0%}): "
                            f"{rate:,.0f} class/s, step {step_us:,.0f} us"
                            + (
                                f" = {speedup:.2f}x vs {dense_name}"
                                if p == sparse_name
                                else ""
                            )
                        ),
                        "fields": {
                            "kind": "sparsity_sweep",
                            "path": p,
                            "dense_twin": dense_name,
                            "active_fraction": fraction,
                            "n_active": n_active,
                            "bucket": bucket,
                            "cls_per_s": rate,
                            "step_us": step_us,
                            "speedup_vs_dense": speedup,
                            **rl,
                        },
                    }
                )
    return rows


def bench_serve_mesh(
    device_counts=(1, 2, 8),
    buckets=(8, 64),
    n_requests: int = 5,
    path: str = "fused",
    tiny: bool = False,
) -> List[Dict]:
    """Per-device-count serving rows: the raw-pixel path on a data-
    parallel :class:`ServeMesh` at each device count (skipping counts the
    process does not have; set
    ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` on CPU).

    Each row's ``fields`` carry ``devices`` (mesh size),
    ``devices_used`` (devices the dispatched batch actually spread over
    — asserted == mesh size by the multidevice CI job's tests) and
    ``per_device_bucket`` alongside the usual throughput numbers.
    """
    from repro.serve import make_serve_mesh

    rows = []
    avail = jax.device_count()
    rng = np.random.default_rng(0)
    for nd in device_counts:
        if nd > avail:
            continue
        smesh = make_serve_mesh(nd, 1)
        engine, cfg = _engine(path, max_batch=max(buckets), tiny=tiny, mesh=smesh)
        side = cfg.patch.image_y
        engine.warmup("mnist", buckets=[b for b in buckets if b >= nd], forms=("raw",))
        for bucket in buckets:
            if bucket < nd:
                continue  # smaller than one image per shard
            imgs = rng.integers(0, 256, (bucket, side, side)).astype(np.uint8)
            devices_used = len(
                {s.device for s in smesh.place_batch(imgs).addressable_shards}
            )
            engine.classify("mnist", imgs)   # untimed host-cache warmup
            t = 0.0
            for _ in range(n_requests):
                t += engine.classify("mnist", imgs).latency_s
            rate = n_requests * bucket / t
            us = t / n_requests * 1e6
            rows.append(
                {
                    "name": f"serve_mesh_{path}_d{nd}_b{bucket}",
                    "us_per_call": round(us, 1),
                    "derived": (
                        f"{rate:,.0f} class/s on {nd} device(s) "
                        f"({bucket // nd}/device of bucket {bucket}) = "
                        f"{rate / PAPER_RATE:.3f}x ASIC; batch spread over "
                        f"{devices_used} devices"
                    ),
                    "fields": {
                        "kind": "serve_mesh",
                        "path": path,
                        "devices": nd,
                        "devices_used": devices_used,
                        "bucket": bucket,
                        "per_device_bucket": bucket // nd,
                        "us_per_request": us,
                        "cls_per_s": rate,
                        "x_asic": rate / PAPER_RATE,
                    },
                }
            )
    return rows


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true", help="two buckets, fewer reps")
    ap.add_argument("--tiny", action="store_true", help="CI-smoke geometry")
    ap.add_argument("--path", default="fused")
    ap.add_argument("--paths", default=None,
                    help="comma-separated eval paths to sweep (overrides --path)")
    ap.add_argument("--autotune", action="store_true",
                    help="register under the per-bucket autotuner; rows "
                         "report the tuned winner per (form, bucket)")
    ap.add_argument("--sparsity", action="store_true",
                    help="sparse-vs-dense crossover sweep over active-"
                         "clause fractions instead of the bucket sweep")
    ap.add_argument("--mesh", action="store_true",
                    help="per-device-count ServeMesh rows instead of the "
                         "single-device sweep (wants 8 virtual devices)")
    args = ap.parse_args()
    enable_compile_cache()
    buckets = (8, 64) if args.quick else (1, 8, 64, 256)
    reps = 3 if args.quick else 10
    print("name,us_per_call,derived")
    if args.mesh:
        for r in bench_serve_mesh(
            n_requests=reps, path=args.path, tiny=args.tiny
        ):
            print(f"{r['name']},{r['us_per_call']},{r['derived']}")
        return
    if args.sparsity:
        for r in bench_sparsity_sweep(
            bucket=8 if args.quick or args.tiny else 64,
            n_requests=reps,
            tiny=args.tiny,
        ):
            print(f"{r['name']},{r['us_per_call']},{r['derived']}")
        return
    for r in bench_serve(
        buckets=buckets,
        n_requests=reps,
        path=args.path,
        paths=args.paths.split(",") if args.paths else None,
        ingress_modes=("device", "host"),
        tiny=args.tiny,
        autotune=args.autotune,
    ):
        print(f"{r['name']},{r['us_per_call']},{r['derived']}")


if __name__ == "__main__":
    main()
