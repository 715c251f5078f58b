"""Fig 10: CAPSim vs the Ithemal-style LSTM vs the no-context ablation.

Method 1 (§VI-B): mixed clips from many benchmarks, 80/10/10 split; train
each model with the paper recipe (SGD momentum 0.9, lr 1e-3, MAPE) and
compare test MAPE.  Paper: CAPSim beats LSTM by 15.8% accuracy on average
and beats its own no-context ablation by 6.2%.
"""
from __future__ import annotations

import time

import jax

from benchmarks.common import (bench_cfg, eval_mape, get_mixed_dataset,
                               train_model)
from repro.core import lstm_baseline, predictor
from repro.data.dataset import split_dataset

STEPS = 200
BATCH = 8


def run(emit) -> None:
    cfg = bench_cfg()
    ds = get_mixed_dataset()
    train, _, test = split_dataset(ds)
    print(f"# Fig 10: {len(train)} train / {len(test)} test clips")

    results = {}
    for label, loss_fn, pred_fn, init_fn in [
        ("capsim",
         lambda p, b: predictor.mape_loss(p, b, cfg),
         lambda p, b: predictor.predict_step(p, b, cfg),
         predictor.init_params),
        ("capsim_noctx",
         lambda p, b: predictor.mape_loss(p, b, cfg, use_context=False),
         lambda p, b: predictor.predict_step(p, b, cfg,
                                             use_context=False),
         predictor.init_params),
        ("lstm_ithemal",
         lambda p, b: lstm_baseline.mape_loss(p, b, cfg),
         lambda p, b: lstm_baseline.forward(p, b, cfg),
         lstm_baseline.init_params),
    ]:
        t0 = time.time()
        params = init_fn(cfg, jax.random.PRNGKey(0))
        state, tr_loss = train_model(loss_fn, params, train, steps=STEPS,
                                     batch_size=BATCH)
        mape = eval_mape(jax.jit(pred_fn), state["params"], test)
        secs = time.time() - t0
        results[label] = mape
        emit.emit(f"accuracy.{label}", secs * 1e6 / STEPS,
                  f"test MAPE {mape:.4f} (train loss {tr_loss:.4f}, "
                  f"{STEPS} steps)")

    d_lstm = 100 * (results["lstm_ithemal"] - results["capsim"])
    d_ctx = 100 * (results["capsim_noctx"] - results["capsim"])
    emit.emit("accuracy.delta_vs_lstm", 0.0,
              f"CAPSim better than LSTM by {d_lstm:.1f} MAPE pts "
              "(paper: avg 15.8)")
    emit.emit("accuracy.delta_vs_noctx", 0.0,
              f"context improves MAPE by {d_ctx:.1f} pts (paper: avg 6.2)")


if __name__ == "__main__":
    from benchmarks.common import CsvEmitter
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    run(CsvEmitter())
