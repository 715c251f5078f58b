"""Fig 11: 6x6 train/test generalization matrix over the Table-II sets.

Train one predictor per benchmark set, evaluate on all six sets: the
diagonal is in-distribution accuracy, off-diagonal is the unseen-benchmark
scenario (the simulator's real use case).  Paper: 91.3% on the training
set, 88.3% average accuracy (MAPE-based accuracy = 100% - MAPE).
"""
from __future__ import annotations

import time

import jax
import numpy as np

from benchmarks.common import bench_cfg, eval_mape, get_set_dataset, \
    train_model
from repro.core import predictor
from repro.isa.progen import SET_NUMBERS

STEPS = 40
BATCH = 8


def run(emit) -> None:
    cfg = bench_cfg()
    sets = {s: get_set_dataset(s) for s in SET_NUMBERS}
    for s, d in sets.items():
        print(f"# set {s}: {len(d)} clips "
              f"({', '.join(sorted(set(d.bench_names)))})")

    pred_fn = jax.jit(lambda p, b: predictor.predict_step(p, b, cfg))
    matrix = np.zeros((len(SET_NUMBERS), len(SET_NUMBERS)))
    for i, s_train in enumerate(SET_NUMBERS):
        t0 = time.time()
        params = predictor.init_params(cfg, jax.random.PRNGKey(s_train))
        state, _ = train_model(
            lambda p, b: predictor.mape_loss(p, b, cfg), params,
            sets[s_train], steps=STEPS, batch_size=BATCH)
        secs = time.time() - t0
        for j, s_test in enumerate(SET_NUMBERS):
            matrix[i, j] = eval_mape(pred_fn, state["params"], sets[s_test])
        emit.emit(f"generalization.train_set{s_train}", secs * 1e6 / STEPS,
                  "test MAPE per set: " +
                  " ".join(f"{m:.3f}" for m in matrix[i]))

    diag = float(np.mean(np.diag(matrix)))
    off = float((matrix.sum() - np.trace(matrix)) /
                (matrix.size - len(SET_NUMBERS)))
    emit.emit("generalization.in_set", 0.0,
              f"avg in-set accuracy {100*(1-diag):.1f}% (paper 91.3%)")
    emit.emit("generalization.cross_set", 0.0,
              f"avg unseen-set accuracy {100*(1-off):.1f}% (paper 88.3%)")


if __name__ == "__main__":
    from benchmarks.common import CsvEmitter
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    run(CsvEmitter())
