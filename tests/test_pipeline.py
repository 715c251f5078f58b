"""Dataset pipeline, intervals, serving engine, end-to-end CAPSim."""
import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import context as ctx_mod
from repro.core import predictor
from repro.core.engine import BatchedPredictor
from repro.core.engine_config import EngineConfig
from repro.core.intervals import basic_block_leaders, pick_intervals
from repro.core.simulate import capsim_simulate
from repro.core.standardize import build_vocab
from repro.data.dataset import (BuildConfig, batches, build_dataset,
                                shard_range, split_dataset)
from repro.isa import progen
from repro.isa.isa import Instruction
from repro.serving.engine import Request
from repro.serving.service import (ServiceSLA, SimulationService,
                                   build_ladder)

VOCAB = build_vocab()
TINY_BCFG = BuildConfig(interval_size=2_000, warmup=200, max_checkpoints=2,
                        l_min=16, l_clip=32, l_token=16, threshold=20,
                        coef=0.2)
SMALL_CFG = get_config("capsim").replace(
    d_model=32, head_dim=8, d_ff=64, dtype="float32")


@pytest.fixture(scope="module")
def tiny_ds():
    return build_dataset(["503.bwaves", "541.leela"], TINY_BCFG, VOCAB)


def test_build_dataset_shapes(tiny_ds):
    ds = tiny_ds
    assert len(ds) > 10
    assert ds.clip_tokens.shape[1:] == (32, 16)
    assert ds.context_tokens.shape[1:] == (ctx_mod.CONTEXT_LEN,)
    assert (ds.time > 0).all()
    assert (ds.clip_mask.sum(-1) >= TINY_BCFG.l_min).all()
    assert set(ds.bench_names) == {"503.bwaves", "541.leela"}
    # token ids live inside the real vocab
    assert ds.clip_tokens.max() < VOCAB.size
    assert ds.context_tokens.max() < VOCAB.size


def test_split_and_batches(tiny_ds):
    tr, va, te = split_dataset(tiny_ds, seed=3)
    assert len(tr) + len(va) + len(te) == len(tiny_ds)
    b = next(batches(tr, 4))
    assert b["clip_tokens"].shape == (4, 32, 16)
    assert b["time"].shape == (4,)


def test_save_load_roundtrip(tiny_ds, tmp_path):
    p = tmp_path / "ds.npz"
    tiny_ds.save(p)
    from repro.data.dataset import ClipDataset
    ds2 = ClipDataset.load(p)
    np.testing.assert_array_equal(tiny_ds.clip_tokens, ds2.clip_tokens)
    np.testing.assert_array_equal(tiny_ds.time, ds2.time)
    assert ds2.bench_names == tiny_ds.bench_names


def test_shard_range_partitions():
    marks = np.zeros(103, int)
    for h in range(8):
        lo, hi = shard_range(103, h, 8)
        marks[lo:hi] += 1
    assert (marks == 1).all()


def test_pick_intervals_weights():
    b = progen.build_benchmark("505.mcf")
    ivals = pick_intervals(b.program, 8_000, 1_000, k=3)
    assert 1 <= len(ivals) <= 3
    assert abs(sum(i.weight for i in ivals) - 1.0) < 1e-6
    assert all(i.start == i.index * 1_000 for i in ivals)


def test_basic_block_leaders():
    prog = [Instruction("addi", dsts=("R1",), imm=1),
            Instruction("bc", imm=0, target=3),
            Instruction("nop"),
            Instruction("nop"),
            Instruction("b", target=0)]
    leaders = basic_block_leaders(prog)
    # 0: entry; 2: falls after bc@1; 3: bc target; 4: not a leader (pc 3 is
    # not a branch; b@4's own successor is out of range)
    assert leaders.tolist() == [True, False, True, True, False]


def test_serving_engine_multi_request(tiny_ds):
    """Two requests in one service window share device batches and
    scatter back to their own totals; the rt rung's RT table persists
    across flushes; and the monolithic path agrees bitwise."""
    params = predictor.init_params(SMALL_CFG, jax.random.PRNGKey(0))
    config = EngineConfig(batch_size=8, l_clip=TINY_BCFG.l_clip)
    rt = [name for name, _ in build_ladder(config)].index("rt")
    svc = SimulationService(params, SMALL_CFG, config, start_tier=rt,
                            sla=ServiceSLA(check_every=0,
                                           promote_after=1_000,
                                           watchdog_s=120.0))

    def req(i, lo, hi):
        return Request(i, tiny_ds.clip_tokens[lo:hi],
                       tiny_ds.context_tokens[lo:hi],
                       tiny_ds.clip_mask[lo:hi])

    n1, n2 = 5, 9
    # admit both before the worker runs, so one window serves them
    svc._running = True
    tickets = [svc.submit(req(1, 0, n1)), svc.submit(req(2, n1, n1 + n2))]
    svc._running = False
    with svc:
        results = [t.result(timeout=300) for t in tickets]
        assert svc.stats()["tiers"]["rt"]["flushes"] == 1
        assert [r.request_id for r in results] == [1, 2]
        assert results[0].n_clips == n1 and results[1].n_clips == n2
        assert all(r.status == "degraded" and r.tier == "rt"
                   for r in results)
        assert all(r.total_cycles > 0 for r in results)
        # the RT cache persists across flushes: replaying request 1
        # alone encodes zero new static rows and reproduces the pooled
        # result bitwise (batching across requests == each alone)
        stats = svc._tiers[rt].cache.stats
        encoded_before = stats.n_rows_encoded
        assert encoded_before > 0
        replay = svc.submit(req(4, 0, n1)).result(timeout=300)
        assert stats.n_rows_encoded == encoded_before
    assert replay.total_cycles == results[0].total_cycles
    # and the monolithic reference path agrees
    mono = BatchedPredictor(params, SMALL_CFG,
                            config=config.replace(rt_cache=False))
    r = req(5, 0, n1)
    mono.add(r.clip_tokens, r.context_tokens, r.clip_mask)
    assert float(mono.drain().sum()) == replay.total_cycles


def test_capsim_simulate_end_to_end():
    bench = progen.build_benchmark("525.x264")
    params = predictor.init_params(SMALL_CFG, jax.random.PRNGKey(0))
    r = capsim_simulate(bench, params, SMALL_CFG, VOCAB,
                        EngineConfig(interval_size=2_000, warmup=200,
                                     max_checkpoints=2, l_min=32,
                                     l_clip=32, batch_size=16))
    assert r.n_intervals == 2
    assert r.n_instructions == 4_000
    assert r.predicted_cycles > 0
    assert r.oracle_cycles > 0
    assert r.rel_error is not None and r.speedup is not None
