"""Table III: generalization across microarchitecture parameters.

The timing oracle is re-parameterized (FetchWidth / IssueWidth /
CommitWidth / ROBEntry — the paper's five rows); a baseline predictor is
pre-trained on the default configuration, then *fine-tuned* briefly per
variant (the paper's accelerated-training protocol) and evaluated on that
variant's re-timed clips.
"""
from __future__ import annotations

import time

import jax

from benchmarks.common import (BENCH_BCFG, DATA_DIR, VOCAB, bench_cfg,
                               eval_mape, train_model)
from repro.core import predictor
from repro.data.dataset import BuildConfig, build_dataset, split_dataset
from repro.isa.timing import TimingParams

# Table III rows: (fetch, issue, commit, rob)
CONFIGS = [
    ("base_8_8_8_192", dict()),
    ("fetch4", dict(fetch_width=4)),
    ("issue4", dict(issue_width=4)),
    ("commit4", dict(commit_width=4)),
    ("rob128", dict(rob_entries=128)),
]
BENCHES = ["503.bwaves", "505.mcf", "525.x264", "541.leela"]
PRETRAIN_STEPS = 40
FINETUNE_STEPS = 30
BATCH = 8


def _dataset(tag: str, tp: TimingParams):
    path = DATA_DIR / f"params_{tag}.npz"
    if path.exists():
        from repro.data.dataset import ClipDataset
        return ClipDataset.load(path)
    bcfg = BuildConfig(
        interval_size=BENCH_BCFG.interval_size, warmup=BENCH_BCFG.warmup,
        max_checkpoints=BENCH_BCFG.max_checkpoints, l_min=BENCH_BCFG.l_min,
        l_clip=BENCH_BCFG.l_clip, l_token=BENCH_BCFG.l_token,
        threshold=BENCH_BCFG.threshold, coef=BENCH_BCFG.coef,
        timing_params=tp)
    ds = build_dataset(BENCHES, bcfg, VOCAB)
    DATA_DIR.mkdir(parents=True, exist_ok=True)
    ds.save(path)
    return ds


def run(emit) -> None:
    cfg = bench_cfg()
    pred_fn = jax.jit(lambda p, b: predictor.predict_step(p, b, cfg))
    loss_fn = lambda p, b: predictor.mape_loss(p, b, cfg)  # noqa: E731

    base_state = None
    for tag, kw in CONFIGS:
        tp = TimingParams().replace(**kw)
        ds = _dataset(tag, tp)
        train, _, test = split_dataset(ds)
        t0 = time.time()
        if base_state is None:                  # pre-train the baseline
            params = predictor.init_params(cfg, jax.random.PRNGKey(0))
            base_state, _ = train_model(loss_fn, params, train,
                                        steps=PRETRAIN_STEPS,
                                        batch_size=BATCH)
            state = base_state
            steps = PRETRAIN_STEPS
        else:                                   # fine-tune from baseline
            state, _ = train_model(loss_fn, base_state["params"], train,
                                   steps=FINETUNE_STEPS, batch_size=BATCH)
            steps = FINETUNE_STEPS
        mape = eval_mape(pred_fn, state["params"], test)
        emit.emit(f"params.{tag}", (time.time() - t0) * 1e6 / steps,
                  f"test MAPE {mape:.4f} ({steps} steps; paper row "
                  f"~12-13% error)")


if __name__ == "__main__":
    from benchmarks.common import CsvEmitter
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    run(CsvEmitter())
