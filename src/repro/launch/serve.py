"""Serving driver.

ConvCoTM archs (the paper's accelerator) are served through the batched
``repro.serve`` engine — model frozen once to a :class:`ServableModel`,
raw pixel requests padded to power-of-two buckets and classified by the
fused device-resident ingress graph (``--ingress host`` replays the
legacy host pipeline):

    PYTHONPATH=src python -m repro.launch.serve \
        --arch convcotm-mnist --requests 64 --max-batch 256

``--mesh DATA[xMODEL]`` (with ``--shard batch|clause``) serves sharded
across a device mesh — request batches split over the "data" axis,
optionally the clause pool over "model" (``repro.serve.mesh``); on CPU
prefix with ``XLA_FLAGS=--xla_force_host_platform_device_count=8``:

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    PYTHONPATH=src python -m repro.launch.serve \
        --arch convcotm-mnist --mesh 8 --requests 64

``--service`` runs the same arch behind the asyncio ``ServingService``
(bounded queue, latency-aware microbatching, graceful drain) under an
open-loop Poisson arrival stream — the online-serving counterpart of the
one-shot request loop (see ``repro.serve.service``; rate sweeps live in
``benchmarks/bench_service.py``):

    PYTHONPATH=src python -m repro.launch.serve \
        --arch convcotm-mnist --service --rate 2000 --requests 512 \
        --max-delay-us 200

``--arch`` takes the ``repro.configs.convcotm.COTM_CONFIGS`` names.
"""

from __future__ import annotations

import argparse
import asyncio

import jax
import numpy as np

from repro.launch.compile_cache import enable_compile_cache

__all__ = ["parse_serve_mesh", "serve_tm", "serve_tm_service"]


def parse_serve_mesh(spec: str | None, shard: str = "batch"):
    """``--mesh``/``--shard`` -> :class:`~repro.serve.mesh.ServeMesh`.

    ``spec`` is ``"DATA"`` or ``"DATAxMODEL"`` (e.g. ``8`` or ``4x2``);
    a bare count lands on the axis ``shard`` selects — ``batch`` (the
    data axis) or ``clause`` (the model axis, clause-sharded eval).
    ``None`` means single-device (no mesh).
    """
    if spec is None:
        return None
    from repro.serve.mesh import make_serve_mesh

    if "x" in spec:
        data, model = (int(p) for p in spec.split("x", 1))
    elif shard == "clause":
        data, model = 1, int(spec)
    else:
        data, model = int(spec), 1
    return make_serve_mesh(data, model, shard_clauses=shard == "clause" or model > 1)


def _tm_engine(
    arch: str,
    *,
    max_batch: int,
    eval_path: str | None,
    ckpt_dir: str | None,
    seed: int,
    mesh=None,
    autotune: bool = False,
):
    """Shared TM-serving setup: dataset, registered (or restored) model.

    Returns ``(engine, vx, vy, source)``; used by both the one-shot
    request loop and the async ``--service`` mode.  ``mesh`` (a
    :class:`~repro.serve.mesh.ServeMesh`) serves the model sharded
    across a device mesh.  ``autotune`` measures eval-path candidates
    per (form, bucket) during warmup and serves each from its winner
    (ARCHITECTURE.md §Autotune).
    """
    from repro.configs.convcotm import BOOLEANIZE_METHOD, COTM_CONFIGS
    from repro.core.cotm import init_boundary_model
    from repro.data import get_dataset
    from repro.serve import ServingEngine

    cfg = COTM_CONFIGS[arch]
    method = BOOLEANIZE_METHOD[arch]
    dataset = arch.split("-", 1)[1]               # convcotm-mnist -> mnist
    _, _, vx, vy, source = get_dataset(dataset, n_test=1024)

    engine = ServingEngine(max_batch=max_batch, mesh=mesh, autotune=autotune)
    if mesh is not None:
        print(
            f"{arch}: serving on a {mesh.n_data}x{mesh.n_model} "
            f'("data","model") mesh '
            f"({'clause-sharded' if mesh.shard_clauses else 'replicated'})"
        )
    if ckpt_dir is not None:
        engine.load_checkpoint(
            arch, ckpt_dir, cfg, booleanize_method=method, path=eval_path
        )
        print(f"{arch}: restored model from {ckpt_dir}")
    else:
        model = init_boundary_model(jax.random.PRNGKey(seed), cfg)
        engine.register(arch, model, cfg, booleanize_method=method, path=eval_path)
        print(f"{arch}: serving a randomly initialized model ({source} data)")
    return engine, vx, vy, source


def serve_tm(
    arch: str,
    *,
    n_requests: int = 32,
    max_batch: int = 256,
    eval_path: str | None = None,
    ckpt_dir: str | None = None,
    seed: int = 0,
    ingress: str = "device",
    mesh=None,
    autotune: bool = False,
) -> dict:
    """Drive the batched TM engine with a mixed-size request stream.

    The model comes from ``ckpt_dir`` (a ``repro.checkpoint`` directory of
    a trained CoTMModel) when given, else a randomly initialized model —
    enough to exercise the full raw->predictions spine (device-resident
    ingress fused into the bucketed jit classify; ``ingress='host'``
    replays the legacy host pipeline) and measure throughput; accuracy is
    reported when the dataset has labels.  ``mesh`` serves sharded across
    a device mesh (``--mesh``/``--shard``, see ``repro.serve.mesh``).
    """
    engine, vx, vy, source = _tm_engine(
        arch, max_batch=max_batch, eval_path=eval_path,
        ckpt_dir=ckpt_dir, seed=seed, mesh=mesh, autotune=autotune,
    )
    compiled = engine.warmup(arch)
    print(f"{arch}: warmed buckets {list(compiled)} (compiles excluded from stats)")
    if autotune:
        at = engine.stats(arch).autotune
        print(
            f"{arch}: autotuned in {at.get('total_s', 0.0):.1f}s -> "
            f"plan {at.get('plan')}"
        )

    rng = np.random.default_rng(seed)
    correct = total = 0
    for _ in range(n_requests):
        n = int(rng.integers(1, max_batch + 1))
        idx = rng.integers(0, len(vx), n)
        res = engine.classify(arch, vx[idx], ingress=ingress)
        correct += int((res.predictions == vy[idx].astype(np.int64)).sum())
        total += n
    st = engine.stats(arch)
    print(
        f"{arch}: {st.images} images in {st.requests} requests | "
        f"{st.classifications_per_s:,.0f} classifications/s | "
        f"mean latency {st.mean_latency_us:,.0f} us | p50 dispatch "
        f"{st.dispatch.quantile(0.5):,.0f} / wait {st.wait.quantile(0.5):,.0f} / "
        f"fetch {st.fetch.quantile(0.5):,.0f} us | "
        f"buckets compiled {sorted(st.compiled_buckets)} "
        f"hits {dict(sorted(st.bucket_hits.items()))}"
    )
    if ckpt_dir is not None:
        print(f"{arch}: accuracy {correct / total:.4f} on {source} test data")
    return st.as_dict()


async def serve_tm_service(
    arch: str,
    *,
    n_requests: int = 256,
    rate: float = 2000.0,
    max_batch: int = 256,
    max_delay_us: float = 200.0,
    high_water: int = 4096,
    eval_path: str | None = None,
    ckpt_dir: str | None = None,
    seed: int = 0,
    submit_form: str = "raw",
    mesh=None,
    autotune: bool = False,
    deadline_s: float | None = None,
    malformed_frac: float = 0.0,
    abandon_frac: float = 0.0,
) -> dict:
    """Drive the async ServingService with open-loop Poisson arrivals.

    Single-image requests arrive at ``rate`` req/s on a precomputed
    exponential schedule (``repro.serve.loadgen.poisson_open_loop``),
    coalesce in the microbatcher under ``max_delay_us``, and the run
    ends with a graceful drain.  ``submit_form`` picks the request form:

      * ``'raw'`` (default) — raw pixels; the booleanize/patch/pack
        ingress runs device-side inside each microbatch's fused classify
        graph (amortized over the coalesced requests);
      * ``'preprocessed'`` — the pool is preprocessed once up front, so
        the run measures only the service spine (queue -> microbatch ->
        bucket -> classify);
      * ``'host'`` — raw pixels through the legacy per-request host
        ingress (the pre-device-ingress baseline).

    Prints the per-model ServiceStats snapshot (p50/p99 latency,
    stage p50s, batch-occupancy histogram, rejections).

    The adversarial knobs (ARCHITECTURE.md §Faults) ride the same load:
    ``deadline_s`` stamps every request (past it, requests shed with
    ``ServiceExpired`` before dispatch), ``malformed_frac`` corrupts
    that fraction of submissions (rejected at validation),
    ``abandon_frac`` simulates clients that stop waiting — the service
    must still resolve their futures.
    """
    from repro.serve import ServiceConfig, ServingService
    from repro.serve.loadgen import poisson_open_loop

    if submit_form not in ("raw", "preprocessed", "host"):
        raise ValueError(f"unknown submit_form {submit_form!r}")
    engine, vx, vy, source = _tm_engine(
        arch, max_batch=max_batch, eval_path=eval_path,
        ckpt_dir=ckpt_dir, seed=seed, mesh=mesh, autotune=autotune,
    )
    engine.warmup(arch)
    if submit_form == "preprocessed":
        pool = engine.preprocess(arch, vx)   # the host ingress, run once
    else:
        pool = np.asarray(vx)

    service = ServingService(
        engine,
        ServiceConfig(max_delay_us=max_delay_us, high_water=high_water),
    )
    await service.start()
    rng = np.random.default_rng(seed)
    idx = rng.integers(0, len(vx), n_requests)

    loop = asyncio.get_running_loop()
    t0 = loop.time()
    report = await poisson_open_loop(
        service, arch, [pool[j : j + 1] for j in idx], rate,
        seed=seed,
        preprocessed=submit_form == "preprocessed",
        host_ingress=submit_form == "host",
        deadline_s=deadline_s,
        malformed_frac=malformed_frac,
        abandon_frac=abandon_frac,
    )
    admitted, rejected = report.admitted, report.rejected
    # Abandoned futures are gathered too — the request-lifetime
    # guarantee says they resolve whether or not the client waits; with
    # a deadline set, some resolutions are ServiceExpired exceptions.
    outcomes = await asyncio.gather(
        *(f for _, f in admitted + report.abandoned), return_exceptions=True
    )
    await service.stop(drain=True)
    wall = loop.time() - t0

    st = service.stats(arch)
    offered = n_requests / wall
    print(
        f"{arch}: offered {offered:,.0f} req/s | completed {st.completed} "
        f"({st.completed / wall:,.0f}/s), rejected {rejected} | "
        f"p50 {st.p50_latency_us:,.0f} us p99 {st.p99_latency_us:,.0f} us | "
        f"p50 queue {st.queue.quantile(0.5):,.0f} / slot "
        f"{st.slot.quantile(0.5):,.0f} / dispatch {st.dispatch.quantile(0.5):,.0f} / "
        f"complete {st.complete.quantile(0.5):,.0f} us | "
        f"mean occupancy {st.mean_occupancy:.2f} | "
        f"occupancy hist {st.occupancy_hist}"
    )
    if deadline_s is not None or malformed_frac or abandon_frac:
        health = service.health()
        print(
            f"{arch}: faults — expired {st.expired}, malformed "
            f"{report.malformed}, abandoned {len(report.abandoned)} "
            f"(all resolved), health {health.state}"
        )
    results = [
        (i, r) for (i, _), r in zip(admitted, outcomes)
        if not isinstance(r, BaseException)
    ]
    if ckpt_dir is not None and results:
        # each surviving result pairs with its request index i -> label
        # vy[idx[i]]; rejections/expiries therefore cannot shift the
        # pairing.
        correct = sum(
            int(r.predictions[0]) == int(vy[idx[i]]) for i, r in results
        )
        print(f"{arch}: accuracy {correct / len(results):.4f} on {source} test data")
    return st.as_dict()


def main():
    from repro.configs.convcotm import COTM_CONFIGS

    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=sorted(COTM_CONFIGS))
    ap.add_argument("--requests", type=int, default=32)
    ap.add_argument("--max-batch", type=int, default=256)
    ap.add_argument("--eval-path", default=None)
    ap.add_argument("--autotune", action="store_true",
                    help="measure eval-path candidates per (form, bucket) "
                         "at warmup and serve each from its winner")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ingress", default="device", choices=["device", "host"],
                    help="raw-request ingress: fused device graph or the "
                         "legacy host pipeline")
    ap.add_argument("--mesh", default=None, metavar="DATA[xMODEL]",
                    help="serve across a device mesh, e.g. 8 (data-"
                         "parallel) or 4x2 (batch over 4, clauses over "
                         "2); on CPU set XLA_FLAGS="
                         "--xla_force_host_platform_device_count=N first")
    ap.add_argument("--shard", default="batch", choices=["batch", "clause"],
                    help="which axis a bare --mesh count shards: request "
                         "batches over \"data\" or the clause pool over "
                         "\"model\" (psum-reduced class sums)")
    ap.add_argument("--submit-form", default="raw",
                    choices=["raw", "preprocessed", "host"],
                    help="request form for --service submissions")
    # async service mode
    ap.add_argument("--service", action="store_true",
                    help="serve through the asyncio ServingService")
    ap.add_argument("--rate", type=float, default=2000.0,
                    help="Poisson arrival rate, requests/s (--service)")
    ap.add_argument("--max-delay-us", type=float, default=200.0,
                    help="microbatch coalescing deadline (--service)")
    ap.add_argument("--high-water", type=int, default=4096,
                    help="queued-image admission limit (--service)")
    ap.add_argument("--deadline-s", type=float, default=None,
                    help="per-request deadline in seconds (--service); "
                         "requests past it shed with ServiceExpired "
                         "before dispatch")
    ap.add_argument("--malformed-frac", type=float, default=0.0,
                    help="fraction of submissions shape-corrupted, to be "
                         "rejected at validation (--service)")
    ap.add_argument("--abandon-frac", type=float, default=0.0,
                    help="fraction of admitted requests whose client "
                         "walks away; their futures must still resolve "
                         "(--service)")
    args = ap.parse_args()
    enable_compile_cache()

    mesh = parse_serve_mesh(args.mesh, args.shard)
    if args.service:
        asyncio.run(
            serve_tm_service(
                args.arch,
                n_requests=args.requests,
                rate=args.rate,
                max_batch=args.max_batch,
                max_delay_us=args.max_delay_us,
                high_water=args.high_water,
                eval_path=args.eval_path,
                ckpt_dir=args.ckpt_dir,
                submit_form=args.submit_form,
                autotune=args.autotune,
                mesh=mesh,
                deadline_s=args.deadline_s,
                malformed_frac=args.malformed_frac,
                abandon_frac=args.abandon_frac,
            )
        )
        return
    serve_tm(
        args.arch,
        n_requests=args.requests,
        max_batch=args.max_batch,
        eval_path=args.eval_path,
        ckpt_dir=args.ckpt_dir,
        ingress=args.ingress,
        autotune=args.autotune,
        mesh=mesh,
    )


if __name__ == "__main__":
    main()
