"""ConvCoTM training throughput (the FPGA in [12] reports 40 k samples/s;
the paper estimates 22.2 k/s for an ASIC at 27.8 MHz — here we measure the
JAX twin on CPU for completeness).

Two comparisons at paper geometry (28x28, 128 clauses):

  * dense-vs-matmul training eval — ``update_batch`` with
    ``config.train_eval='dense'`` (the reference ``[P, C, 2o]`` boolean
    broadcast, ~12.6M intermediate elements per image) against
    ``'matmul'`` (the MXU violation-count fast path, bit-identical);
  * engine-vs-naive epoch loops — a hand-written per-batch python loop
    (literal extraction per step, one dispatch per batch) against
    ``TrainerEngine`` (literals frozen once, one jitted ``lax.scan`` per
    epoch with donated model buffers).

Run:  PYTHONPATH=src python -m benchmarks.bench_train
"""

from __future__ import annotations

import time
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import CoTMConfig, init_model, update_batch

__all__ = ["bench_tm_train", "bench_train_eval_paths", "bench_epoch_loops"]


def _paper_cfg(train_eval: str) -> CoTMConfig:
    return CoTMConfig(
        n_clauses=128, n_classes=10, T=500, s=10.0, train_eval=train_eval
    )


def bench_train_eval_paths(batch: int = 64, iters: int = 3) -> List[Dict]:
    """update_batch samples/s, dense-broadcast vs matmul training eval."""
    key = jax.random.PRNGKey(0)
    imgs = (jax.random.uniform(key, (batch, 28, 28)) > 0.6).astype(jnp.uint8)
    labels = jax.random.randint(key, (batch,), 0, 10)
    out, rate = [], {}
    for train_eval in ("dense", "matmul"):
        cfg = _paper_cfg(train_eval)
        model = init_model(key, cfg)
        model = update_batch(key, model, imgs, labels, cfg)  # compile
        jax.block_until_ready(model.ta_state)
        t0 = time.perf_counter()
        for _ in range(iters):
            model = update_batch(key, model, imgs, labels, cfg)
        jax.block_until_ready(model.ta_state)
        us = (time.perf_counter() - t0) / iters * 1e6
        rate[train_eval] = batch / us * 1e6
        out.append(
            {
                "name": f"convcotm_train_step_{train_eval}_batch{batch}",
                "us_per_call": round(us, 1),
                "derived": f"{rate[train_eval]:.0f} samples/s (paper-scale model)",
            }
        )
    out.append(
        {
            "name": "convcotm_train_eval_speedup",
            "us_per_call": 0,
            "derived": f"matmul {rate['matmul'] / rate['dense']:.1f}x over "
            f"dense broadcast",
        }
    )
    return out


def bench_epoch_loops(
    n: int = 1024, batch: int = 64, epochs: int = 2
) -> List[Dict]:
    """Full-epoch samples/s: naive per-batch python loop vs TrainerEngine.

    Both use the matmul training eval; the comparison isolates the engine
    mechanics (literals frozen once + one jitted scan per epoch + donated
    buffers) from the clause-eval speedup measured above.  The first
    engine epoch (compile) is excluded from both timings.
    """
    from repro.data import PipelineState, batches
    from repro.train.tm_engine import TrainerEngine

    cfg = _paper_cfg("matmul")
    key = jax.random.PRNGKey(0)
    rng = np.random.default_rng(0)
    imgs = (rng.random((n, 28, 28)) > 0.6).astype(np.uint8)
    labels = rng.integers(0, 10, n).astype(np.int32)

    # --- naive loop: re-extract + dispatch per batch ----------------------
    model = init_model(key, cfg)
    state = PipelineState(seed=0)
    k = key
    # warm the compile outside the timed region
    model = update_batch(k, model, jnp.asarray(imgs[:batch]), jnp.asarray(labels[:batch]), cfg)
    jax.block_until_ready(model.ta_state)
    t0 = time.perf_counter()
    for _ in range(epochs):
        for xb, yb, state in batches(imgs, labels, batch, state):
            k, kk = jax.random.split(k)
            model = update_batch(kk, model, jnp.asarray(xb), jnp.asarray(yb), cfg)
    jax.block_until_ready(model.ta_state)
    naive_s = time.perf_counter() - t0

    # --- engine: frozen literals + jitted scan per epoch ------------------
    engine = TrainerEngine(cfg, batch_size=batch)
    ds = engine.prepare(imgs, labels, booleanize_method="none")
    model = engine.init_model(key)
    key, model, st, _ = engine.fit(key, model, ds, epochs=1)  # compile epoch
    t0 = time.perf_counter()
    key, model, st, _ = engine.fit(key, model, ds, epochs=epochs, state=st)
    jax.block_until_ready(model.ta_state)
    engine_s = time.perf_counter() - t0

    total = epochs * (n // batch) * batch
    return [
        {
            "name": f"convcotm_epoch_naive_n{n}",
            "us_per_call": round(naive_s / epochs * 1e6, 1),
            "derived": f"{total / naive_s:.0f} samples/s (per-batch dispatch)",
        },
        {
            "name": f"convcotm_epoch_engine_n{n}",
            "us_per_call": round(engine_s / epochs * 1e6, 1),
            "derived": f"{total / engine_s:.0f} samples/s "
            f"({naive_s / engine_s:.1f}x over naive loop)",
        },
    ]


def bench_tm_train(batch: int = 64, iters: int = 3) -> List[Dict]:
    """The full training benchmark suite (run.py entry point)."""
    return bench_train_eval_paths(batch, iters) + bench_epoch_loops(batch=batch)


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    print("name,us_per_call,derived")
    for r in bench_tm_train():
        print(f"{r['name']},{r['us_per_call']},{r['derived']}")
