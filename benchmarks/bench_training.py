"""Fig 9: training vs validation loss curve for the predictor.

Trains the bench-scale CAPSim predictor and records the MAPE trajectory on
train batches and a held-out validation split — the paper's convergence
evidence (its run stops near epoch 128; ours is step-scaled).
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp

from benchmarks.common import bench_cfg, eval_mape, get_mixed_dataset
from repro.core import predictor
from repro.data.dataset import batches, split_dataset
from repro.training.train_loop import (TrainConfig, init_train_state,
                                       make_train_step)

STEPS = 50
BATCH = 8
EVAL_EVERY = 20


def run(emit) -> None:
    cfg = bench_cfg()
    ds = get_mixed_dataset()
    train, val, _ = split_dataset(ds)

    tcfg = TrainConfig(optimizer="sgdm", base_lr=1e-3,
                       warmup_steps=STEPS // 10, total_steps=STEPS)
    params = predictor.init_params(cfg, jax.random.PRNGKey(0))
    state = init_train_state(params, tcfg)
    step = jax.jit(make_train_step(
        lambda p, b: predictor.mape_loss(p, b, cfg), tcfg))
    pred_fn = jax.jit(lambda p, b: predictor.predict_step(p, b, cfg))

    curve = []
    it = batches(train, BATCH, epochs=100_000)
    t0 = time.time()
    for i in range(1, STEPS + 1):
        b = {k: jnp.asarray(v) for k, v in next(it).items()}
        state, m = step(state, b)
        if i % EVAL_EVERY == 0 or i == 1:
            vl = eval_mape(pred_fn, state["params"], val)
            curve.append((i, float(m["loss"]), vl))
    us = (time.time() - t0) * 1e6 / STEPS

    pts = " ".join(f"s{i}:tr={tr:.3f}/va={va:.3f}" for i, tr, va in curve)
    emit.emit("training.loss_curve", us, pts)
    gap = curve[-1][2] - curve[-1][1]
    emit.emit("training.generalization_gap", us,
              f"final val-train gap {gap:+.3f} (no-overfit check, Fig 9)")


if __name__ == "__main__":
    from benchmarks.common import CsvEmitter
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    run(CsvEmitter())
