#!/usr/bin/env python3
"""Smoke test of the ConvCoTM serving and training paths on a TPU.

Run from the root of a checkout, on a machine with a TPU:

    python chip_smoke.py              # one chip: engine, service, train
    python chip_smoke.py --chips 4    # four chips: the mesh phase only

It drives the system through the entry points a user calls, at the
paper geometry (``convcotm-mnist``: 28x28 frames, 10x10 window, 361
patches, 272 literals, 128 clauses, 10 classes), on a boundary model
built from ``--seed`` and the built-in synthetic glyphs (no download):

  * **engine** — the ``ServingEngine`` as ``launch/serve.py`` sets it
    up, serving raw frames through every registered eval path.  The
    default ``matmul`` path and the ``fused`` Pallas path run buckets 1,
    8 and 256; every other path one bucket.  Class sums must equal the
    ``kernels/ref.py`` oracle exactly.  Each Pallas path's executable
    must hold a ``tpu_custom_call`` (compiled Mosaic, not the
    interpreter), and no path may have degraded.
  * **service** — single-frame raw requests, open-loop Poisson at
    2000 req/s, into a ``ServingService`` on the ``fused`` path, then a
    graceful drain: every request admitted and completed, none
    rejected, expired or quarantined, the service healthy, and each
    result equal to a direct ``engine.classify``.
  * **train** — ``TrainerEngine.prepare`` and a one-epoch ``fit`` over
    a few batches, bit-identical to the plain ``update_batch`` loop.
  * **mesh** (``--chips 4`` only) — the same requests on a 4x1
    data-sharded ``ServeMesh``, a 1x4 clause-sharded one and a
    single-device engine, bit-identical to each other and the oracle.

Each phase prints one line: its wall-clock seconds on the host clock
(everything in it blocks on the device), the seconds XLA spent
compiling, and the persistent compile cache's hits and misses (the
cache is placed by ``repro.launch.compile_cache``).  The last line is
one JSON object naming the device.  Any failed check raises, and the
script then exits non-zero without that line; so does a run that finds
no TPU.
"""

from __future__ import annotations

import argparse
import asyncio
import dataclasses
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

ARCH = "convcotm-mnist"
#: Eval paths that run buckets 1, 8 and 256; the others run one bucket.
FULL_SWEEP_PATHS = ("matmul", "fused")
FULL_BUCKETS = (1, 8, 256)
ONE_BUCKET = (8,)
#: Paths whose step must contain a compiled Pallas kernel.
PALLAS_PATHS = ("fused", "kernel", "sparse", "fused_sparse")
SERVICE_REQUESTS = 384
SERVICE_RATE = 2000.0


class CompileMeter:
    """Sums XLA compile seconds and persistent-cache hits/misses from
    JAX's monitoring events, so each phase can report its own."""

    def __init__(self, jax):
        self.compile_s = 0.0
        self.hits = 0
        self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compile_s += duration

    def _event(self, event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def snapshot(self):
        return self.compile_s, self.hits, self.misses


def run_phase(name, meter, fn, *args):
    """Run one phase and print its line; a failed check propagates."""
    c0, h0, m0 = meter.snapshot()
    t0 = time.perf_counter()
    detail = fn(*args)
    wall = time.perf_counter() - t0
    c1, h1, m1 = meter.snapshot()
    print(
        f"[{name}] ok wall_s={wall:.3f} xla_compile_s={c1 - c0:.3f} "
        f"cache_hits={h1 - h0} cache_misses={m1 - m0} {detail}",
        flush=True,
    )


def oracle_device(jax):
    """Where the oracle runs: the host CPU when the process has it (an
    independent compiler), else the default device (plain XLA, still
    independent of the Pallas kernels)."""
    try:
        return jax.devices("cpu")[0]
    except RuntimeError:
        return jax.devices()[0]


def make_oracle(servable, cfg, method):
    """frames -> int32 class sums via kernels/ref.py: the host ingress
    (data.pipeline) into ``fused_infer_ref`` on the frozen model."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.data.pipeline import preprocess_for_serving
    from repro.kernels import ref

    dev = oracle_device(jax)
    model = [
        jax.device_put(x, dev)
        for x in (servable.include_packed, servable.nonempty, servable.weights)
    ]

    def oracle(frames):
        with jax.default_device(dev):
            lits = preprocess_for_serving(frames, cfg.patch, method=method, packed=True)
            return np.asarray(ref.fused_infer_ref(jnp.asarray(lits), *model))

    return oracle


def engine_phase(state, seed):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs.convcotm import BOOLEANIZE_METHOD, COTM_CONFIGS
    from repro.launch.serve import _tm_engine
    from repro.serve.engine import raw_step_jit
    from repro.serve.paths import available_paths

    cfg, method = COTM_CONFIGS[ARCH], BOOLEANIZE_METHOD[ARCH]
    engine, vx, _, _ = _tm_engine(
        ARCH, max_batch=256, eval_path=None, ckpt_dir=None, seed=seed
    )
    base = engine.servable(ARCH)
    names = {cfg.eval_path: ARCH}
    for path in available_paths():
        if path not in names:
            names[path] = f"{ARCH}/{path}"
            engine.register(names[path], base, booleanize_method=method, path=path)
    oracle = make_oracle(base, cfg, method)
    rng = np.random.default_rng(seed)
    served = []
    for path, name in sorted(names.items()):
        buckets = FULL_BUCKETS if path in FULL_SWEEP_PATHS else ONE_BUCKET
        engine.warmup(name, buckets=list(buckets), forms=("raw",))
        for b in buckets:
            frames = vx[rng.choice(len(vx), b, replace=False)]
            res = engine.classify(name, frames)
            np.testing.assert_array_equal(res.class_sums, oracle(frames))
            served.append(f"{path}@{b}:{res.latency_s * 1e3:.3f}ms")
        if path in PALLAS_PATHS:
            image = dataclasses.replace(engine.servable(name), version=None)
            text = raw_step_jit().lower(
                image, jnp.zeros((8,) + vx.shape[1:], jnp.uint8),
                path_name=path, ingress=engine.ingress_spec(name), params=(),
            ).compile().as_text()
            assert "tpu_custom_call" in text, f"{path}: no compiled kernel"
        st = engine.stats(name)
        assert st.fallback_path is None and st.degrade_steps == 0, (
            f"{path} degraded to {st.fallback_path}"
        )
    state.update(engine=engine, name=names["fused"], vx=vx)
    return (
        f"paths={len(names)} class_sums==ref exact; tpu_custom_call in "
        f"{','.join(PALLAS_PATHS)}; degrade_steps=0; warm classify latency "
        f"{' '.join(served)}"
    )


def service_phase(state, seed):
    import numpy as np

    from repro.serve import ServiceConfig, ServingService
    from repro.serve.loadgen import poisson_open_loop

    engine, name, vx = state["engine"], state["name"], state["vx"]
    engine.warmup(name, forms=("raw",))     # every bucket a microbatch can hit
    rng = np.random.default_rng(seed + 1)
    frames = vx[rng.integers(0, len(vx), SERVICE_REQUESTS)]

    async def run():
        service = ServingService(
            engine, ServiceConfig(max_delay_us=200.0, high_water=4096)
        )
        await service.start()
        t0 = time.perf_counter()
        report = await poisson_open_loop(
            service, name, [frames[i : i + 1] for i in range(len(frames))],
            SERVICE_RATE, seed=seed,
        )
        outcomes = await asyncio.gather(
            *(f for _, f in report.admitted), return_exceptions=True
        )
        served_s = time.perf_counter() - t0
        health = service.health().state
        await service.stop(drain=True)
        return report, outcomes, health, service.stats(name), served_s

    report, outcomes, health, st, served_s = asyncio.run(run())
    errors = [o for o in outcomes if isinstance(o, BaseException)]
    assert not errors, f"{len(errors)} requests failed: {errors[0]!r}"
    assert len(report.admitted) == SERVICE_REQUESTS and report.rejected == 0
    assert st.completed == SERVICE_REQUESTS, st.completed
    assert st.rejected == st.expired == st.quarantined == 0, st
    assert health == "healthy", health
    direct = engine.classify(name, frames)
    for (i, _), res in zip(report.admitted, outcomes):
        np.testing.assert_array_equal(res.class_sums[0], direct.class_sums[i])
        assert res.predictions[0] == direct.predictions[i]
    return (
        f"requests={SERVICE_REQUESTS} offered_rate={SERVICE_RATE:.0f}/s "
        f"completed={st.completed} rejected=0 expired=0 quarantined=0 "
        f"health={health} results==engine.classify; served_s={served_s:.3f} "
        f"p50_us={st.p50_latency_us:.1f} p99_us={st.p99_latency_us:.1f} "
        f"mean_occupancy={st.mean_occupancy:.3f}"
    )


def train_phase(seed):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs.convcotm import COTM_CONFIGS
    from repro.core.cotm import init_model
    from repro.core.train import update_batch
    from repro.data import PipelineState, batches, booleanize_split, synthetic_glyphs
    from repro.train.tm_engine import TrainerEngine

    cfg, batch = COTM_CONFIGS[ARCH], 64
    tx, ty, _, _ = synthetic_glyphs(n_train=4 * batch, n_test=1, seed=seed)
    x = booleanize_split(tx, "threshold")
    key = jax.random.PRNGKey(seed)

    want = init_model(key, cfg)
    k = key
    for xb, yb, cursor in batches(x, ty, batch, PipelineState()):
        k, sub = jax.random.split(k)
        want = update_batch(sub, want, jnp.asarray(xb), jnp.asarray(yb), cfg)

    trainer = TrainerEngine(cfg, batch_size=batch)
    ds = trainer.prepare(x, ty, booleanize_method="none")
    _, got, got_cursor, reports = trainer.fit(
        key, trainer.init_model(key), ds, epochs=1
    )
    np.testing.assert_array_equal(np.asarray(got.ta_state), np.asarray(want.ta_state))
    np.testing.assert_array_equal(np.asarray(got.weights), np.asarray(want.weights))
    assert got_cursor == cursor, (got_cursor, cursor)
    return (
        f"samples={reports[0].samples} batches={reports[0].samples // batch} "
        f"model==update_batch loop bit-identical; epoch_s={reports[0].seconds:.3f}"
    )


def mesh_phase(seed):
    import jax
    import numpy as np

    from repro.configs.convcotm import BOOLEANIZE_METHOD, COTM_CONFIGS
    from repro.launch.serve import _tm_engine, parse_serve_mesh

    meshes = {
        "single": None,
        "data4x1": parse_serve_mesh("4", "batch"),
        "clause1x4": parse_serve_mesh("4", "clause"),
    }
    sizes = (1, 8, 256)
    engines = {}
    for label, mesh in meshes.items():
        engine, vx, _, _ = _tm_engine(
            ARCH, max_batch=256, eval_path="fused", ckpt_dir=None, seed=seed,
            mesh=mesh,
        )
        engine.warmup(ARCH, buckets=list(sizes), forms=("raw",))
        engines[label] = engine
        if mesh is not None:
            placed = {
                d for leaf in jax.tree.leaves(engine.servable(ARCH))
                for d in leaf.devices()
            }
            assert len(placed) == 4, f"{label}: servable on {len(placed)} devices"
            bucket = np.zeros((engine.bucket_for(1),) + vx.shape[1:], np.uint8)
            spread = mesh.place_batch(bucket).devices()
            assert len(spread) == 4, f"{label}: bucket on {len(spread)} devices"
    oracle = make_oracle(
        engines["single"].servable(ARCH), COTM_CONFIGS[ARCH], BOOLEANIZE_METHOD[ARCH]
    )
    rng = np.random.default_rng(seed)
    for n in sizes:
        frames = vx[rng.choice(len(vx), n, replace=False)]
        want = oracle(frames)
        for label, engine in engines.items():
            res = engine.classify(ARCH, frames)
            np.testing.assert_array_equal(res.class_sums, want, err_msg=label)
        for label, engine in engines.items():
            st = engine.stats(ARCH)
            assert st.fallback_path is None and st.degrade_steps == 0, label
    return (
        f"sizes={','.join(map(str, sizes))} data4x1==clause1x4==single==ref "
        f"bit-identical; servable and buckets span 4 devices; degrade_steps=0"
    )


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs the mesh phase alone, on four chips")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: no TPU (JAX found {dev.platform}); nothing run")
    if len(devices) < args.chips:
        sys.exit(f"chip_smoke: --chips {args.chips} but {len(devices)} devices")
    print(
        f"device: platform={dev.platform} kind={dev.device_kind} "
        f"count={len(devices)}",
        flush=True,
    )
    from repro.launch.compile_cache import enable_compile_cache

    print(f"compile cache: {enable_compile_cache()}", flush=True)
    meter = CompileMeter(jax)
    t0 = time.perf_counter()
    if args.chips == 4:
        run_phase("mesh", meter, mesh_phase, args.seed)
    else:
        state = {}
        run_phase("engine", meter, engine_phase, state, args.seed)
        run_phase("service", meter, service_phase, state, args.seed)
        run_phase("train", meter, train_phase, args.seed)
    c, h, m = meter.snapshot()
    print(
        f"[total] ok wall_s={time.perf_counter() - t0:.3f} xla_compile_s={c:.3f} "
        f"cache_hits={h} cache_misses={m}",
        flush=True,
    )
    print(json.dumps({
        "ok": True,
        "device": {
            "platform": dev.platform, "kind": dev.device_kind,
            "count": len(devices),
        },
    }))


if __name__ == "__main__":
    main()
