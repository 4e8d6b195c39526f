"""Training driver for the ConvCoTM archs (the paper's accelerator).

CPU-scale example:

    PYTHONPATH=src python -m repro.launch.train \
        --arch convcotm-mnist --epochs 5 --batch 100

Trains through ``repro.train.tm_engine.TrainerEngine`` — dataset
literals frozen once, jitted lax.scan epochs, checkpointed model +
pipeline cursor.  ``--arch`` takes the
``repro.configs.convcotm.COTM_CONFIGS`` names.
"""

from __future__ import annotations

import argparse
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint.checkpointer import Checkpointer, latest_step
from repro.launch.compile_cache import enable_compile_cache

__all__ = ["run_tm_training"]


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

    Restore (model + pipeline cursor + PRNG key) if a checkpoint exists,
    run jitted epochs up to the requested ``epochs`` total, checkpoint
    after every epoch, report accuracy and samples/s.  A restarted job
    finishes the run — it does not train ``epochs`` additional epochs —
    and continues the exact key chain an uninterrupted run would have
    used.
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
    from repro.configs.convcotm import COTM_CONFIGS

    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=sorted(COTM_CONFIGS))
    ap.add_argument("--batch", type=int, default=100)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--epochs", type=int, default=5)
    ap.add_argument("--mode", default="batch", choices=["batch", "scan"])
    args = ap.parse_args()
    enable_compile_cache()

    out = run_tm_training(
        args.arch,
        epochs=args.epochs,
        batch=args.batch,
        mode=args.mode,
        ckpt_dir=args.ckpt_dir,
    )
    print(
        f"final: acc {out['accuracy']:.4f} "
        f"{out['samples_per_s']:,.0f} samples/s"
    )


if __name__ == "__main__":
    main()
