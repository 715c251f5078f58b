"""Batched multi-benchmark SimulationEngine invariants.

The engine's contract: pooling clips from many programs into shared
device batches changes *throughput only* — per-benchmark predicted
cycles are bitwise identical to the sequential single-benchmark path,
and the bucketed batcher neither drops nor double-counts clips.
"""
import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import context as ctx_mod
from repro.core import predictor
from repro.core.engine import (BatchedPredictor, SimulationEngine,
                               bucket_sizes, predict_fn, reference_fn)
from repro.core.engine_config import EngineConfig
from repro.core.simulate import capsim_simulate
from repro.core.standardize import ClipEncoder, build_vocab, encode_clip
from repro.isa import progen

VOCAB = build_vocab()
SMALL_CFG = get_config("capsim").replace(
    d_model=32, head_dim=8, d_ff=64, dtype="float32")

# three mixed-size benchmarks: different ckp_num caps and interval sizes
# exercise full batches, bucketed remainders, and cross-bench boundaries
MIX = ["503.bwaves", "541.leela", "525.x264"]
SIM_EC = EngineConfig(interval_size=1_500, warmup=200, max_checkpoints=3,
                      l_min=32, l_clip=32, l_token=16, batch_size=16,
                      with_oracle=False)


@pytest.fixture(scope="module")
def params():
    return predictor.init_params(SMALL_CFG, jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def engine_results(params):
    engine = SimulationEngine(params, SMALL_CFG, VOCAB, SIM_EC)
    engine.submit_names(MIX)
    return engine.run(), engine.last_stats


def test_engine_matches_capsim_simulate_bitwise(params, engine_results):
    """(a) pooled multi-benchmark run == per-benchmark sequential wrapper,
    bit for bit, on a fixed seed."""
    results, _ = engine_results
    for name, r in zip(MIX, results):
        solo = capsim_simulate(progen.build_benchmark(name), params,
                               SMALL_CFG, VOCAB, SIM_EC)
        assert r.name == solo.name == name
        assert r.n_clips == solo.n_clips
        assert r.n_instructions == solo.n_instructions
        assert r.predicted_cycles == solo.predicted_cycles  # bitwise


def test_bucketing_conserves_clips(engine_results):
    """(b) across 3 mixed-size benchmarks, every clip is predicted exactly
    once: pool totals, per-benchmark demux spans, and dispatched batch
    shapes all agree."""
    results, stats = engine_results
    per_bench = sum(r.n_clips for r in results)
    assert per_bench == stats.n_clips == stats.n_predicted
    # dispatched rows = real clips + padding, in bucket-shaped batches only
    dispatched = sum(shape * n for shape, n in stats.batch_shapes.items())
    assert dispatched == stats.n_clips + stats.n_pad
    assert set(stats.batch_shapes) <= set(bucket_sizes(16))
    # the mix is deliberately not batch-aligned
    assert stats.n_clips % 16 != 0 and stats.n_pad > 0


def test_batched_predictor_order_and_remainder(params):
    """Predictions come back in submission order with padding stripped,
    regardless of how adds straddle batch boundaries."""
    rng = np.random.RandomState(7)
    n = 23                                       # 16 + bucketed remainder
    tok = rng.randint(1, VOCAB.size, (n, 32, 16)).astype(np.int32)
    ctx = rng.randint(1, VOCAB.size,
                      (n, ctx_mod.CONTEXT_LEN)).astype(np.int32)
    mask = np.ones((n, 32), np.float32)

    whole = BatchedPredictor(params, SMALL_CFG,
                             config=EngineConfig(batch_size=16))
    whole.add(tok, ctx, mask)
    ref = whole.drain()

    split = BatchedPredictor(params, SMALL_CFG,
                             config=EngineConfig(batch_size=16))
    for lo, hi in ((0, 5), (5, 17), (17, 23)):
        split.add(tok[lo:hi], ctx[lo:hi], mask[lo:hi])
    out = split.drain()

    assert ref.shape == out.shape == (n,)
    np.testing.assert_array_equal(ref, out)
    assert split.stats.n_predicted == n
    assert split.stats.n_pad == 8 - 7            # remainder 7 -> bucket 8


def test_bucket_sizes():
    assert bucket_sizes(256) == (256, 128, 64, 32, 16, 8)
    assert bucket_sizes(8) == (8,)
    assert bucket_sizes(12) == (12, 8)


def test_predict_fn_cached():
    assert predict_fn(SMALL_CFG, True) is predict_fn(SMALL_CFG, True)
    assert predict_fn(SMALL_CFG, True) is not predict_fn(SMALL_CFG, False)


def test_encode_clips_matches_encode_clip():
    bench = progen.build_benchmark("505.mcf")
    insts = bench.program[:90]
    clips = [insts[0:30], insts[30:55], insts[55:90]]
    enc = ClipEncoder(VOCAB, 32, 16)
    toks, mask = enc.encode(clips)
    assert toks.shape == (3, 32, 16) and mask.shape == (3, 32)
    for i, c in enumerate(clips):
        t_ref, m_ref = encode_clip(c, VOCAB, 32, 16)
        np.testing.assert_array_equal(toks[i], t_ref)
        np.testing.assert_array_equal(mask[i], m_ref)
    # memo hit rate: loopy traces collapse onto few standardized shapes
    assert len(enc._memo) < sum(len(c) for c in clips)


def test_reference_step_is_f32_xla_attention_at_highest(params):
    """The accuracy yardstick: even from a bf16 Pallas config it runs the
    f32 XLA forward at highest matmul precision; on the CPU that is
    bitwise the monolithic fp32 step."""
    rng = np.random.RandomState(0)
    batch = {"clip_tokens": rng.randint(0, VOCAB.size, (3, 32, 16)
                                        ).astype(np.int32),
             "context_tokens": rng.randint(0, VOCAB.size, (3, 360)
                                           ).astype(np.int32),
             "clip_mask": np.ones((3, 32), np.float32)}
    ref = reference_fn(SMALL_CFG.replace(dtype="bfloat16",
                                         attn_impl="pallas"))
    text = ref.lower(params, batch).as_text()
    assert "HIGHEST" in text and "DEFAULT" not in text
    assert "tpu_custom_call" not in text
    np.testing.assert_array_equal(np.asarray(ref(params, batch)),
                                  np.asarray(predict_fn(SMALL_CFG)(params,
                                                                   batch)))
