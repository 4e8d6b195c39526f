"""End-to-end training driver (LM archs + the ConvCoTM itself).

CPU-scale examples:

    PYTHONPATH=src python -m repro.launch.train \
        --arch h2o-danube-1.8b --reduced --steps 20 --batch 8 --seq 128

    PYTHONPATH=src python -m repro.launch.train \
        --arch convcotm-mnist --epochs 5 --batch 100

LM archs: build mesh -> shard state -> jit train_step with NamedShardings
-> run with checkpoint/restart and straggler monitoring
(distributed/fault_tolerance).  ConvCoTM archs (the paper's accelerator)
train through ``repro.train.tm_engine.TrainerEngine`` — dataset literals
frozen once, jitted lax.scan epochs, checkpointed model + pipeline cursor.
"""

from __future__ import annotations

import argparse
import time
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint.checkpointer import Checkpointer, latest_step
from repro.configs import TrainConfig, get_config, reduced_config
from repro.distributed.fault_tolerance import StragglerPolicy
from repro.launch import specs as S
from repro.launch.compile_cache import enable_compile_cache
from repro.models.base import init_params, param_count
from repro.train.train_step import init_train_state, make_train_step

__all__ = ["run_training", "run_tm_training", "synthetic_lm_batch"]


def _token_stream(rng, batch: int, seq: int, vocab: int, noise: float = 0.05):
    """LEARNABLE synthetic stream: ascending runs (successor rule with
    random restarts) plus noise — uniform-random tokens would put the loss
    floor at ln(V) and nothing could train."""
    starts = rng.integers(0, vocab, batch)
    ramp = starts[:, None] + np.arange(seq)[None, :]
    restart = rng.random((batch, seq)) < 0.02
    offsets = np.cumsum(restart * rng.integers(1, vocab, (batch, seq)), axis=1)
    toks = (ramp + offsets) % vocab
    flip = rng.random((batch, seq)) < noise
    toks = np.where(flip, rng.integers(0, vocab, (batch, seq)), toks)
    return jnp.asarray(toks, jnp.int32)


def synthetic_lm_batch(cfg, batch: int, seq: int, step: int) -> Dict[str, Any]:
    """Deterministic synthetic batch (offline container)."""
    rng = np.random.default_rng(1234 + step)
    if cfg.is_encoder_decoder:
        return {
            "frontend_embeds": jnp.asarray(
                rng.standard_normal((batch, seq, cfg.d_model)), cfg.dtype
            ),
            "dec_tokens": _token_stream(rng, batch, max(seq // 4, 16), cfg.vocab_size),
        }
    out = {"tokens": _token_stream(rng, batch, seq, cfg.vocab_size)}
    if cfg.modality == "vision":
        nv = max(seq // 4, 4)
        out["tokens"] = out["tokens"][:, : seq - nv]
        out["frontend_embeds"] = jnp.asarray(
            rng.standard_normal((batch, nv, cfg.d_model)), cfg.dtype
        )
    return out


def run_training(
    cfg,
    tcfg: TrainConfig,
    mesh,
    *,
    batch: int,
    seq: int,
    steps: int,
    ckpt_dir: str | None = None,
    log_every: int = 5,
    batch_fn=None,
) -> Dict[str, float]:
    """Train loop with checkpoint/resume + straggler policy. Returns final
    metrics."""
    batch_fn = batch_fn or (lambda step: synthetic_lm_batch(cfg, batch, seq, step))
    key = jax.random.PRNGKey(tcfg.seed)
    decls = S.model_decls(cfg)
    with mesh:
        params = init_params(decls, key)
        state = init_train_state(params, tcfg)
        step_fn = jax.jit(make_train_step(cfg, tcfg, mesh=mesh), donate_argnums=(0,))

        start = 0
        ckpt = Checkpointer(ckpt_dir) if ckpt_dir else None
        if ckpt and latest_step(ckpt_dir) is not None:
            state, start, extra = ckpt.restore(state)
            print(f"resumed from step {start}")

        policy = StragglerPolicy()
        metrics = {}
        first_loss = None
        for step in range(start, steps):
            t0 = time.time()
            state, metrics = step_fn(state, batch_fn(step))
            jax.block_until_ready(metrics["loss"])
            if first_loss is None:
                first_loss = float(metrics["loss"])
            dt = time.time() - t0
            verdict = policy.observe(dt)
            if verdict != "ok":
                print(f"[straggler-policy] step {step}: {verdict} ({dt:.2f}s)")
            if step % log_every == 0 or step == steps - 1:
                print(
                    f"step {step:5d} loss {float(metrics['loss']):.4f} "
                    f"gnorm {float(metrics['grad_norm']):.3f} "
                    f"lr {float(metrics['lr']):.2e} {dt:.2f}s"
                )
            if ckpt and (step + 1) % tcfg.checkpoint_every == 0:
                ckpt.save(state, step + 1)
        if ckpt:
            ckpt.save(state, steps)
            ckpt.wait()
    out = {k: float(v) for k, v in metrics.items()}
    out["first_loss"] = first_loss if first_loss is not None else float("nan")
    return out


def run_tm_training(
    arch: str,
    *,
    epochs: int = 5,
    batch: int = 100,
    mode: str = "batch",
    n_train: int = 4000,
    n_test: int = 800,
    ckpt_dir: str | None = None,
    seed: int = 0,
) -> Dict[str, float]:
    """Train a ConvCoTM arch through the TrainerEngine (checkpoint/resume).

    The same driver shape as ``run_training``: restore (model + pipeline
    cursor + PRNG key) if a checkpoint exists, run jitted epochs up to the
    requested ``epochs`` total, checkpoint after every epoch, report
    accuracy and samples/s.  A restarted job finishes the run — it does
    not train ``epochs`` additional epochs — and continues the exact key
    chain an uninterrupted run would have used.
    """
    from repro.configs.convcotm import BOOLEANIZE_METHOD, COTM_CONFIGS
    from repro.data import PipelineState, get_dataset
    from repro.train.tm_engine import TrainerEngine

    cfg = COTM_CONFIGS[arch]
    method = BOOLEANIZE_METHOD[arch]
    dataset = arch.split("-", 1)[1]               # convcotm-mnist -> mnist
    tx, ty, vx, vy, source = get_dataset(dataset, n_train=n_train, n_test=n_test)
    print(f"{arch}: dataset source {source} ({len(tx)} train / {len(vx)} test)")

    engine = TrainerEngine(cfg, batch_size=batch, mode=mode)
    train_ds = engine.prepare(tx, ty, booleanize_method=method)
    eval_ds = engine.prepare(vx, vy, booleanize_method=method)

    key = jax.random.PRNGKey(seed)
    model = engine.init_model(key)
    state = PipelineState(seed=seed)
    trainer_meta = {"batch_size": batch, "mode": mode, "seed": seed}
    if ckpt_dir and latest_step(ckpt_dir) is not None:
        from repro.checkpoint.checkpointer import restore_pytree

        model, step, extra = restore_pytree(model, ckpt_dir)
        # Missing metadata is unknown provenance, not a match — default
        # to None so such checkpoints fail the guard rather than pass it.
        saved = extra.get("trainer")
        if saved != trainer_meta:
            # Different batch/mode/seed changes steps-per-epoch and the
            # per-step key chain — the run would no longer be equivalent
            # to any uninterrupted run.
            raise ValueError(
                f"checkpoint at {ckpt_dir} was trained with {saved}; "
                f"resuming with {trainer_meta} would break the key-chain "
                f"contract — restart with matching flags or a fresh dir"
            )
        state = PipelineState.from_dict(extra["pipeline"])
        key = jnp.asarray(np.asarray(extra["key"], np.uint32))
        print(f"{arch}: resumed from epoch {state.epoch} (step {step})")

    ckpt = Checkpointer(ckpt_dir) if ckpt_dir else None
    reports = []
    while state.epoch < epochs:
        key, model, state, reps = engine.fit(
            key, model, train_ds, epochs=1, eval_ds=eval_ds, state=state,
            log=lambda s: print(f"{arch}: {s}"),
        )
        reports.extend(reps)
        if ckpt:
            ckpt.save(
                model,
                state.epoch,
                extra={
                    "pipeline": state.as_dict(),
                    "key": np.asarray(key).tolist(),
                    "trainer": trainer_meta,
                },
            )
    if ckpt:
        ckpt.wait()
    if not reports:
        print(f"{arch}: checkpoint already at epoch {state.epoch} >= {epochs}")
        return {
            "accuracy": engine.evaluate(model, eval_ds),
            "samples_per_s": 0.0,
            "epochs": float(state.epoch),
        }
    last = reports[-1]
    return {
        "accuracy": last.accuracy if last.accuracy is not None else float("nan"),
        "samples_per_s": last.samples_per_s,
        "epochs": float(state.epoch),
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--steps", type=int, default=20)
    # per-arch default resolved after parsing: 8 for LM, 100 for ConvCoTM
    ap.add_argument("--batch", type=int, default=None)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--grad-compression", action="store_true")
    # ConvCoTM (TrainerEngine) flags
    ap.add_argument("--epochs", type=int, default=5)
    ap.add_argument("--mode", default="batch", choices=["batch", "scan"])
    args = ap.parse_args()
    enable_compile_cache()

    from repro.configs.convcotm import COTM_CONFIGS

    if args.arch in COTM_CONFIGS:
        out = run_tm_training(
            args.arch,
            epochs=args.epochs,
            batch=args.batch if args.batch is not None else 100,
            mode=args.mode,
            ckpt_dir=args.ckpt_dir,
        )
        print(
            f"final: acc {out['accuracy']:.4f} "
            f"{out['samples_per_s']:,.0f} samples/s"
        )
        return

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduced_config(cfg)
    tcfg = TrainConfig(
        learning_rate=args.lr,
        total_steps=args.steps,
        warmup_steps=max(args.steps // 10, 1),
        microbatches=args.microbatches,
        grad_compression=args.grad_compression,
        checkpoint_every=max(args.steps // 2, 1),
    )
    from repro.sharding.partition import single_device_mesh

    mesh = single_device_mesh()
    n = param_count(S.model_decls(cfg))
    print(f"arch={cfg.name} params={n/1e6:.1f}M devices={mesh.size}")
    run_training(
        cfg, tcfg, mesh,
        batch=args.batch if args.batch is not None else 8,
        seq=args.seq, steps=args.steps,
        ckpt_dir=args.ckpt_dir,
    )


if __name__ == "__main__":
    main()
