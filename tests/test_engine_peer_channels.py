"""Peer-channel contexts on the engine's multicore path.

``EngineConfig(multicore=N, peer_channels=True)`` makes
``SimulationEngine.run_multicore`` feed every clip the peer-channel
layout (``context.peer_context_tokens``, M = N x 369): the clip's own
core-tagged register block, then every other core's block as of the
quantum's start.  These cases hold the served rows to the multicore
training build's rows, the answers to the float32 ``highest``
reference, the peer-off layout to what it was, and the sampled path to
the full one, at a small size on the CPU.
"""
import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import context as ctx_mod
from repro.core import predictor
from repro.core import standardize as std_mod
from repro.core.engine import BatchedPredictor, SimulationEngine, \
    reference_fn
from repro.core.engine_config import (EngineConfig, ObservabilityConfig,
                                      SamplingConfig)
from repro.data import multicore_dataset as mcd
from repro.isa import multicore
from repro.obs import REGISTRY

N_CORES = 3
SMALL_CFG = get_config("capsim").replace(d_model=32, head_dim=8, d_ff=64,
                                         dtype="float32")
EC = EngineConfig(interval_size=1_000, warmup=100, max_checkpoints=1,
                  batch_size=16, with_oracle=False, multicore=N_CORES)
PEER = EC.replace(peer_channels=True)
PROGRAMS = ["mt.counter", "mt.mix"]
# The engine's rt step (RT-table gather, block encoder over batches of
# 16) and the monolithic reference step (instruction encoder inside the
# batch, ``highest``) are both float32 XLA on the CPU and compute the
# same sums; on an x86 CPU they agree to the bit.  The tolerance is not
# zero because nothing promises XLA picks the same reduction order for
# other batch shapes: 1e-5 is a few float32 roundings of a 1,107-row
# mean.  Row order inside a context does not change an answer (no
# positions, a mean over rows), which is why the rows themselves are
# compared with the training build's, exactly, above.
REL_TOL = 1e-5


@pytest.fixture(scope="module")
def vocab():
    return std_mod.build_vocab()


@pytest.fixture(scope="module")
def params():
    return predictor.init_params(SMALL_CFG, jax.random.PRNGKey(0))


def _run(params, vocab, config, name, monkeypatch, drained=None):
    """One ``run_multicore`` pass over ``name``; returns its result and
    every (tok, ctx, mask) the predictor pool took (and appends the
    pool's drained per-clip answers to ``drained``)."""
    fed = []
    buffer, drain = BatchedPredictor._buffer, BatchedPredictor.drain

    def _buffer(self, tok, ctx, mask):
        fed.append((tok, ctx, mask))
        return buffer(self, tok, ctx, mask)

    def _drain(self):
        out = drain(self)
        if drained is not None:
            drained.append(out)
        return out
    monkeypatch.setattr(BatchedPredictor, "_buffer", _buffer)
    monkeypatch.setattr(BatchedPredictor, "drain", _drain)
    mb = multicore.build_multicore_benchmark(name, N_CORES)
    res = SimulationEngine.from_config(params, SMALL_CFG, vocab,
                                       config).run_multicore([mb])[0]
    monkeypatch.undo()
    return res, fed


def _peer_clips() -> float:
    return sum(v for _, v in
               REGISTRY.collect("capsim_peer_context_clips_total"))


def _fixed_slicer(commits, l_min, include_tail=False):
    """The engine's fixed partition (a clip every ``l_min`` dynamic
    instructions, the last one shorter) in the slicer's format."""
    out = []
    for col in commits:
        n = len(col)
        starts = np.arange(0, n, l_min, dtype=np.int64)
        bounds = np.stack([starts, np.minimum(starts + l_min, n)], axis=1)
        out.append((bounds, np.zeros(len(starts), np.float32)))
    return out


@pytest.mark.parametrize("name", PROGRAMS)
def test_peer_rows_equal_the_training_build(params, vocab, monkeypatch,
                                            name):
    """Train/serve parity: at the same clip starts the engine feeds
    exactly the rows ``build_multicore_bench_clips`` packs with peer
    channels on.  The build cuts clips at Algorithm-1 commit boundaries
    and the engine at fixed ``l_min`` windows, so the build's slicer is
    pinned to the engine's partition here; every other step (warm-up,
    interval, replay with whole-machine captures, layout) is the
    build's own."""
    res, fed = _run(params, vocab, PEER, name, monkeypatch)
    served = np.concatenate([f[1] for f in fed])
    assert served.shape == (res.n_clips, N_CORES * 369)

    monkeypatch.setattr(mcd.slicer_mod, "slice_multicore_columnar",
                        _fixed_slicer)
    bcfg = mcd.MulticoreBuildConfig(
        interval_size=EC.interval_size, warmup=EC.warmup,
        max_checkpoints=EC.max_checkpoints, l_min=EC.l_min,
        l_clip=EC.l_clip, l_token=EC.l_token, sample=False,
        n_cores=N_CORES, quantum=multicore.DEFAULT_QUANTUM,
        peer_channels=True)
    ds = mcd.build_multicore_bench_clips(
        multicore.build_multicore_benchmark(name, N_CORES), bcfg, vocab)
    assert ds.context_len == bcfg.context_len == served.shape[1]
    np.testing.assert_array_equal(ds.context_tokens, served)
    # the peer blocks are not the own block: a clip sees other cores
    own = served[:, :369]
    assert all(not np.array_equal(own, served[:, k * 369:(k + 1) * 369])
               for k in range(1, N_CORES))


@pytest.mark.parametrize("name", PROGRAMS)
def test_peer_answers_match_the_highest_reference(params, vocab,
                                                  monkeypatch, name):
    """Per-clip cycles of the RT-cache engine and of the monolithic one
    against ``reference_fn`` (float32, ``highest``) on the clips the
    monolithic engine fed: tokens, peer contexts and masks."""
    drained = []
    res, fed = _run(params, vocab, PEER, name, monkeypatch, drained)
    tok = np.concatenate([f[0] for f in fed])
    ctx = np.concatenate([f[1] for f in fed])
    mask = np.concatenate([f[2] for f in fed])
    assert tok.ndim == 2                       # the RT path feeds indices
    mono, fed_mono = _run(params, vocab,
                          PEER.replace(rt_cache=False), name, monkeypatch)
    tok = np.concatenate([f[0] for f in fed_mono])
    np.testing.assert_array_equal(
        np.concatenate([f[1] for f in fed_mono]), ctx)
    want = np.asarray(reference_fn(SMALL_CFG)(
        params, {"clip_tokens": tok, "context_tokens": ctx,
                 "clip_mask": mask}), np.float64)
    for r in (res, mono):
        got = np.array([c.predicted_cycles for c in r.cores])
        per_core, off = [], 0
        for c in r.cores:
            per_core.append(want[off:off + c.n_clips].sum())
            off += c.n_clips
        assert off == len(want)
        np.testing.assert_allclose(got, per_core, rtol=REL_TOL)
    # per clip, in the order the RT-cache engine's pool drained them
    np.testing.assert_allclose(drained[0], want, rtol=REL_TOL)


def test_peer_off_keeps_the_core_tagged_layout_and_answers(params, vocab,
                                                          monkeypatch):
    """With peer channels off the rows are the core-tagged layout
    (M 369) built from a plain ``run_multicore`` trace, the peer counter
    does not move, and the answers are bitwise those of an engine with
    no multicore setting at all."""
    name = "mt.mix"
    before = _peer_clips()
    res, fed = _run(params, vocab, EC, name, monkeypatch)
    assert _peer_clips() == before
    served = np.concatenate([f[1] for f in fed])
    mb = multicore.build_multicore_benchmark(name, N_CORES)
    states = mb.fresh_states()
    cprogs = mb.compiled()
    multicore.run_multicore(cprogs, EC.warmup, states)
    mt = multicore.run_multicore(cprogs, EC.interval_size, states,
                                 snapshot_every=EC.l_min)
    assert mt.peer_snapshots is None
    want = np.concatenate([
        ctx_mod.context_tokens_from_matrix(t.snapshots, vocab, core_id=c)
        for c, t in enumerate(mt.cores)])
    np.testing.assert_array_equal(served, want)
    plain, _ = _run(params, vocab, EC.replace(multicore=0), name,
                    monkeypatch)
    assert [c.predicted_cycles for c in res.cores] \
        == [c.predicted_cycles for c in plain.cores]


def test_sampled_path_serves_peer_channels(params, vocab, monkeypatch):
    """The fusion path shares the interval body: at fraction 1.0 it
    feeds each core the same peer rows as the full path and gives the
    same per-core totals; at 0.5 it feeds a subset of them."""
    name = "mt.counter"
    full, fed_full = _run(params, vocab, PEER, name, monkeypatch)
    rows_full = np.concatenate([f[1] for f in fed_full])
    every, fed_every = _run(
        params, vocab,
        PEER.replace(sampling=SamplingConfig(fraction=1.0,
                                             bootstrap_resamples=0)),
        name, monkeypatch)
    rows_every = np.concatenate([f[1] for f in fed_every])
    # the full path interleaves cores interval by interval, the sampled
    # one feeds core by core; with one interval the orders coincide
    np.testing.assert_array_equal(rows_every, rows_full)
    np.testing.assert_allclose(
        [c.predicted_cycles for c in every.cores],
        [c.predicted_cycles for c in full.cores], rtol=1e-6)
    half, fed_half = _run(
        params, vocab,
        PEER.replace(sampling=SamplingConfig(fraction=0.5,
                                             bootstrap_resamples=0)),
        name, monkeypatch)
    rows_half = np.concatenate([f[1] for f in fed_half])
    assert rows_half.shape[1] == N_CORES * 369
    assert 0 < len(rows_half) < len(rows_full)
    known = {r.tobytes() for r in rows_full}
    assert all(r.tobytes() in known for r in rows_half)
    assert sum(c.clips_extrapolated for c in half.cores) > 0


def test_peer_context_span_and_counter(params, vocab, tmp_path,
                                      monkeypatch):
    """``engine.peer_context`` is a child of ``engine.context`` and
    carries the clip count; ``capsim_peer_context_clips_total`` counts
    every clip fed with the peer layout; both reach the profiler while a
    session records."""
    from jax.profiler import TraceAnnotation

    from repro.obs import trace as obs_trace
    built = []

    class Counted(TraceAnnotation):
        def __init__(self, name, **meta):
            built.append((name, meta))
            super().__init__(name, **meta)

    monkeypatch.setattr(obs_trace, "_TRACE_ANNOTATION", Counted)
    eng = SimulationEngine.from_config(
        params, SMALL_CFG, vocab,
        PEER.replace(observability=ObservabilityConfig(trace=True)))
    before = eng.obs.metrics.value("capsim_peer_context_clips_total",
                                   instance=eng.instance)
    mb = multicore.build_multicore_benchmark("mt.mix", N_CORES)
    jax.profiler.start_trace(str(tmp_path))
    try:
        res = eng.run_multicore([mb])[0]
    finally:
        jax.profiler.stop_trace()
    n = eng.obs.metrics.value("capsim_peer_context_clips_total",
                              instance=eng.instance) - before
    assert n == res.n_clips > 0
    recs = eng.obs.tracer.spans()
    by_id = {r.span_id: r for r in recs}
    peer = [r for r in recs if r.name == "engine.peer_context"]
    assert len(peer) == N_CORES
    assert all(by_id[r.parent_id].name == "engine.context" for r in peer)
    assert sum(r.args["clips"] for r in peer) == n
    events = [(nm, m) for nm, m in built if nm == "engine.peer_context"]
    assert len(events) == N_CORES
    assert sum(m["clips"] for _, m in events) == n
