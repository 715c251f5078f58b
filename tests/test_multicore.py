"""Multi-core trace simulation subsystem invariants.

The tentpole contracts:

  * ``run_multicore`` is deterministic, and at N=1 degenerates bitwise
    to plain ``run_compiled`` (trace AND snapshots);
  * sharded (disjoint-memory) cores are invariant under core count and
    scheduling order, while shared-memory writes ARE visible across
    cores under the deterministic interleave;
  * ``timing.simulate_multicore`` at N=1 is bitwise equal to
    ``simulate_columnar`` (the shared LLC / bus penalties key on
    cross-core interference only);
  * the engine's (benchmark, core) shards through the pooled predictor
    demux to per-core cycles bitwise equal to the per-core sequential
    path, and the RT cache is shared across cores of one program.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import context as ctx_mod
from repro.core import predictor
from repro.core import standardize as std_mod
from repro.core.engine import SimulationEngine
from repro.core.engine_config import EngineConfig, SamplingConfig
from repro.core.standardize import CORE, build_vocab
from repro.isa import funcsim, multicore, progen, timing
from repro.isa.compiled import IREG_SLOT, compile_program
from repro.isa.funcsim import CompiledState
from repro.isa.isa import Instruction

I = Instruction
VOCAB = build_vocab()
SMALL_CFG = get_config("capsim").replace(
    d_model=32, head_dim=8, d_ff=64, dtype="float32")
SIM_KW = dict(interval_size=1_200, warmup=150, max_checkpoints=2,
              l_min=32, l_clip=32, l_token=16, batch_size=16,
              with_oracle=True)
SIM_EC = EngineConfig(**SIM_KW)


@pytest.fixture(scope="module")
def params():
    return predictor.init_params(SMALL_CFG, jax.random.PRNGKey(0))


def _traces_equal(a, b):
    return (np.array_equal(a.pc, b.pc) and np.array_equal(a.ea, b.ea)
            and np.array_equal(a.taken, b.taken)
            and np.array_equal(a.snapshots, b.snapshots))


# --------------------------------------------------------------------------- #
# run_multicore: determinism, N=1 anchor, permutation invariance
# --------------------------------------------------------------------------- #

def test_n1_bitwise_equals_run_compiled():
    """One core through the quantum scheduler == one plain run_compiled
    call: pc/ea/taken columns and snapshot rows, bit for bit — across
    resumed quanta and a non-dividing snapshot stride."""
    for kind in multicore.MULTICORE_NAMES:
        mb = multicore.build_multicore_benchmark(kind, 1)
        mt = multicore.run_multicore(mb.compiled(), 2_000,
                                     mb.fresh_states(),
                                     snapshot_every=100, quantum=64)
        ref, _ = funcsim.run_compiled(
            multicore.build_multicore_benchmark(kind, 1).compiled()[0],
            2_000, mb.fresh_states()[0], snapshot_every=100)
        assert _traces_equal(mt.cores[0], ref), kind
        assert sum(n for _, n in mt.schedule) == len(ref)


def test_interleave_deterministic_across_runs():
    """Same inputs -> identical per-core traces and schedule, including
    the contention kernel whose loads see other cores' stores."""
    mb = multicore.build_multicore_benchmark("mt.counter", 4)
    a = multicore.run_multicore(mb.compiled(), 1_500, mb.fresh_states(),
                                snapshot_every=50)
    b = multicore.run_multicore(mb.compiled(), 1_500, mb.fresh_states(),
                                snapshot_every=50)
    assert a.schedule == b.schedule
    for ta, tb in zip(a.cores, b.cores):
        assert _traces_equal(ta, tb)


def test_sharded_traces_invariant_under_core_count_and_order():
    """Sharded stream/chase cores touch disjoint memory, so core i's
    trace must not change when (a) more cores join or (b) the round-robin
    visit order is permuted."""
    for kind in ("mt.stream", "mt.chase"):
        mb2 = multicore.build_multicore_benchmark(kind, 2)
        mb4 = multicore.build_multicore_benchmark(kind, 4)
        t2 = multicore.run_multicore(mb2.compiled(), 1_200,
                                     mb2.fresh_states(),
                                     snapshot_every=50)
        t4 = multicore.run_multicore(mb4.compiled(), 1_200,
                                     mb4.fresh_states(),
                                     snapshot_every=50)
        for c in range(2):
            assert _traces_equal(t2.cores[c], t4.cores[c]), (kind, c)
        perm = multicore.run_multicore(mb4.compiled(), 1_200,
                                       mb4.fresh_states(),
                                       snapshot_every=50,
                                       core_order=[3, 1, 0, 2])
        for c in range(4):
            assert _traces_equal(t4.cores[c], perm.cores[c]), (kind, c)


def test_shared_memory_conflict_visibility():
    """A store committed in core 0's quantum is architecturally visible
    to core 1's load in the SAME round (order [0, 1]), and to core 0 in
    the NEXT round when the order is reversed."""
    addr = 0x9000
    writer = compile_program([
        I("addi", dsts=("R3",), imm=addr),
        I("addi", dsts=("R4",), imm=42),
        I("std", srcs=("R4",), mem_base="R3", mem_offset=0),
        I("nop"),
        I("b", target=3),                  # spin
    ])
    reader = compile_program([
        I("addi", dsts=("R3",), imm=addr),
        I("ld", dsts=("R5",), mem_base="R3", mem_offset=0),
        I("b", target=1),                  # keep re-loading
    ])
    mem = {}
    states = [CompiledState(iregs=[0] * 40, fregs=[0.0] * 32, mem=mem)
              for _ in range(2)]
    multicore.run_multicore([writer, reader], 8, states, quantum=4)
    # writer ran its quantum first: reader's very first ld sees the store
    assert states[1].iregs[IREG_SLOT["R5"]] == 42
    assert mem[addr >> 3] == 42

    # reversed order: the reader's first quantum predates the store,
    # its later quanta observe it — visibility is by commit interleave
    mem2 = {}
    states2 = [CompiledState(iregs=[0] * 40, fregs=[0.0] * 32, mem=mem2)
               for _ in range(2)]
    mt = multicore.run_multicore([writer, reader], 8, states2, quantum=4,
                                 core_order=[1, 0])
    reader_tr = mt.cores[1]
    assert states2[1].iregs[IREG_SLOT["R5"]] == 42
    assert len(reader_tr) == 8


def test_shared_counter_increments_accumulate():
    """All cores hammer MT_COUNTER_EA: the final counter must exceed
    anything a single core could have produced alone (cross-core writes
    visible), yet stay <= the total increments committed."""
    mb = multicore.build_multicore_benchmark("mt.counter", 4)
    states = mb.fresh_states()
    multicore.run_multicore(mb.compiled(), 2_000, states)
    counter = states[0].mem[progen.MT_COUNTER_EA >> 3]
    mb1 = multicore.build_multicore_benchmark("mt.counter", 1)
    states1 = mb1.fresh_states()
    multicore.run_multicore(mb1.compiled(), 2_000, states1)
    solo = states1[0].mem[progen.MT_COUNTER_EA >> 3]
    assert counter > solo
    assert counter <= 4 * 2_000


# --------------------------------------------------------------------------- #
# Multicore timing oracle
# --------------------------------------------------------------------------- #

def test_oracle_n1_bitwise_equals_simulate_columnar():
    """The shared LLC / bus penalties key on OTHER cores, so at N=1 the
    multicore oracle must reproduce simulate_columnar bit for bit."""
    for kind in multicore.MULTICORE_NAMES:
        mb = multicore.build_multicore_benchmark(kind, 1)
        mt = multicore.run_multicore(mb.compiled(), 2_000,
                                     mb.fresh_states(), quantum=48)
        ref, _ = funcsim.run_compiled(
            multicore.build_multicore_benchmark(kind, 1).compiled()[0],
            2_000, mb.fresh_states()[0])
        got = timing.simulate_multicore(mt.cores, mt.schedule)[0]
        want = timing.simulate_columnar(ref)
        np.testing.assert_array_equal(got, want, err_msg=kind)


def test_oracle_cross_core_contention_slows_cores():
    """Chase cores at N=4 share LLC slots and the bus: at least one core
    must commit strictly later than the same core running alone."""
    mb4 = multicore.build_multicore_benchmark("mt.chase", 4)
    mt4 = multicore.run_multicore(mb4.compiled(), 1_500,
                                  mb4.fresh_states())
    tot4 = timing.total_cycles_multicore(mt4.cores, mt4.schedule)
    mb1 = multicore.build_multicore_benchmark("mt.chase", 1)
    mt1 = multicore.run_multicore(mb1.compiled(), 1_500,
                                  mb1.fresh_states())
    tot1 = timing.total_cycles_multicore(mt1.cores, mt1.schedule)
    assert max(tot4) > tot1[0]


def test_oracle_rejects_overrunning_schedule():
    mb = multicore.build_multicore_benchmark("mt.stream", 2)
    mt = multicore.run_multicore(mb.compiled(), 500, mb.fresh_states())
    bad = mt.schedule + [(0, 1)]
    with pytest.raises(AssertionError):
        timing.simulate_multicore(mt.cores, bad)


# --------------------------------------------------------------------------- #
# Core-id context channel
# --------------------------------------------------------------------------- #

def test_core_id_context_channel():
    snaps = np.arange(8 * 40, dtype=np.uint64).reshape(8, 40)
    base = ctx_mod.context_tokens_from_matrix(snaps, VOCAB)
    assert base.shape == (8, ctx_mod.CONTEXT_LEN)
    tagged = ctx_mod.context_tokens_from_matrix(snaps, VOCAB, core_id=3)
    assert tagged.shape == (8, ctx_mod.MULTICORE_CONTEXT_LEN)
    # prefix unchanged bit for bit; channel = <CORE> + big-endian bytes
    np.testing.assert_array_equal(tagged[:, :ctx_mod.CONTEXT_LEN], base)
    chan = tagged[0, ctx_mod.CONTEXT_LEN:]
    assert chan[0] == VOCAB[CORE]
    np.testing.assert_array_equal(
        chan, ctx_mod.core_id_tokens(3, VOCAB))
    assert chan[-1] == VOCAB[std_mod.BYTE_TOKENS[3]]
    # different cores -> different contexts (only the channel differs)
    other = ctx_mod.context_tokens_from_matrix(snaps, VOCAB, core_id=1)
    assert not np.array_equal(tagged, other)
    np.testing.assert_array_equal(other[:, :ctx_mod.CONTEXT_LEN], base)


# --------------------------------------------------------------------------- #
# Engine: (benchmark, core) shard demux + RT-cache sharing
# --------------------------------------------------------------------------- #

def _sequential_core_reference(mb, params, *, interval_size,
                               max_checkpoints, l_min, l_clip, l_token,
                               batch_size, warmup, with_oracle):
    """Per-(core, checkpoint) monolithic predict loops over the same
    interleaved front-end — the engine demux's bitwise reference."""
    predict = jax.jit(
        lambda p, b: predictor.predict_step(p, b, SMALL_CFG))
    cprogs = mb.compiled()
    tables = [cp.token_table(VOCAB, l_token) for cp in cprogs]
    states = mb.fresh_states()
    if warmup:
        multicore.run_multicore(cprogs, warmup, states)
    totals = [0.0] * mb.n_cores
    clips = [0] * mb.n_cores
    for _ in range(min(mb.ckp_num, max_checkpoints)):
        mtrace = multicore.run_multicore(
            cprogs, interval_size, states, snapshot_every=l_min)
        if len(mtrace) == 0:
            break
        for c, trace in enumerate(mtrace.cores):
            if not len(trace):
                continue
            tok, mask = std_mod.encode_fixed_clips(
                tables[c], trace.pc, l_min, l_clip)
            ctx_all = ctx_mod.context_tokens_from_matrix(
                trace.snapshots, VOCAB, core_id=c)
            rows = np.minimum(np.arange(tok.shape[0]), len(ctx_all) - 1)
            ctx = ctx_all[rows]
            k = tok.shape[0]
            pad = (-k) % batch_size
            if pad:
                tok = np.concatenate(
                    [tok, np.zeros((pad,) + tok.shape[1:], tok.dtype)])
                ctx = np.concatenate(
                    [ctx, np.zeros((pad,) + ctx.shape[1:], ctx.dtype)])
                mask = np.concatenate(
                    [mask, np.zeros((pad,) + mask.shape[1:],
                                    mask.dtype)])
            preds = []
            for lo in range(0, tok.shape[0], batch_size):
                batch = {
                    "clip_tokens": jnp.asarray(tok[lo:lo + batch_size]),
                    "context_tokens":
                        jnp.asarray(ctx[lo:lo + batch_size]),
                    "clip_mask": jnp.asarray(mask[lo:lo + batch_size])}
                preds.append(np.asarray(predict(params, batch)))
            totals[c] += float(np.concatenate(preds)[:k].sum())
            clips[c] += k
    return totals, clips


@pytest.fixture(scope="module")
def mc_engine_results(params):
    mbenches = [multicore.build_multicore_benchmark("mt.mix", 2),
                multicore.build_multicore_benchmark("mt.chase", 3)]
    engine = SimulationEngine(params, SMALL_CFG, VOCAB, SIM_EC)
    return mbenches, engine.run_multicore(mbenches), engine


def test_engine_demux_bitwise_equals_sequential(params, mc_engine_results):
    """(benchmark, core) shards pooled into shared (remainder-padded)
    device batches demux back to per-core and summed cycles bitwise equal
    to the per-core sequential loops."""
    mbenches, results, engine = mc_engine_results
    assert engine.last_stats.n_pad > 0        # remainder padding exercised
    for mb, r in zip(mbenches, results):
        ref, ref_clips = _sequential_core_reference(
            mb, params, **SIM_KW)
        assert r.n_cores == mb.n_cores == len(r.cores)
        for c, cr in enumerate(r.cores):
            assert cr.n_clips == ref_clips[c]
            assert cr.predicted_cycles == ref[c], (cr.name, c)
        summed = 0.0
        for v in ref:
            summed += v
        assert r.predicted_cycles == summed


def test_engine_clip_conservation(mc_engine_results):
    mbenches, results, engine = mc_engine_results
    total = sum(cr.n_clips for r in results for cr in r.cores)
    assert engine.last_stats.n_clips == total
    assert engine.last_stats.n_predicted == total
    for r in results:
        assert r.n_clips == sum(cr.n_clips for cr in r.cores)
        assert r.oracle_cycles == sum(cr.oracle_cycles for cr in r.cores)
        for cr in r.cores:
            assert cr.oracle_cycles > 0


def test_engine_sampling_fraction_one_gives_unsampled_core_totals(
        params, mc_engine_results):
    """Core-tagged ``run_multicore`` under ``fraction=1.0`` feeds every
    core's clips as one sample after the benchmark's checkpoints: the
    per-core totals are the unsampled ones (one sum over the core's
    rows instead of one per checkpoint, so to float32 rounding)."""
    mbenches, results, engine = mc_engine_results
    sampled = SimulationEngine(params, SMALL_CFG, VOCAB, SIM_EC.replace(
        sampling=SamplingConfig(fraction=1.0)))
    got = sampled.run_multicore(mbenches)
    assert sampled.last_stats.n_predicted == engine.last_stats.n_predicted
    for r, g in zip(results, got):
        assert [c.name for c in g.cores] == [c.name for c in r.cores]
        for rc, gc in zip(r.cores, g.cores):
            assert gc.n_clips == gc.clips_predicted == rc.n_clips
            assert gc.clips_extrapolated == 0
            assert gc.oracle_cycles == rc.oracle_cycles
            assert gc.predicted_cycles == pytest.approx(
                rc.predicted_cycles, rel=1e-6)


def test_rt_cache_shared_across_cores(params):
    """All cores of one multi-threaded program share a token table
    (immediates collapse to <CONST>), so adding cores must not add RT
    rows — and a 4-core run encodes exactly what a 1-core run does."""
    ec = SIM_EC.replace(with_oracle=False)
    e1 = SimulationEngine(params, SMALL_CFG, VOCAB, ec)
    e1.run_multicore([multicore.build_multicore_benchmark("mt.mix", 1)])
    rows1 = e1.last_rt_stats.n_rows_encoded
    e4 = SimulationEngine(params, SMALL_CFG, VOCAB, ec)
    e4.run_multicore([multicore.build_multicore_benchmark("mt.mix", 4)])
    rows4 = e4.last_rt_stats.n_rows_encoded
    assert rows1 == rows4
    assert e4.last_rt_stats.n_rows_served > \
        e1.last_rt_stats.n_rows_served


def test_multicore_benchmark_shared_state():
    mb = multicore.build_multicore_benchmark("mt.mix", 3)
    states = mb.fresh_states()
    assert len(states) == 3
    assert all(st.mem is states[0].mem for st in states)
    assert states[0].mem[progen.MT_COUNTER_EA >> 3] == 0
    with pytest.raises(ValueError):
        multicore.build_multicore_benchmark("mt.nope", 2)
    with pytest.raises(ValueError):
        multicore.build_multicore_benchmark("mt.mix", 0)
