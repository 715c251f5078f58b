"""The peer-channel cell ``mt16.engine`` at tiny sizes on the CPU: correct
as it stands, not correct with a peer block out of place or an answer
altered, its query-blocked reference equal to the plain one, and its
host-front-end reader on hand-made and traced readings."""
import time

import jax.numpy as jnp
import numpy as np
import pytest
from _tiny import patch, tiny
from harness import faults, ref_model, ref_peer, runner, weights
from harness import reading as rd
from harness import trace as tr

CELL = "mt16.engine"
N_CORES = 3
SEED = 2**33 + 15


def _tiny_peer():
    """``_tiny.tiny`` at 3 cores: M = 3 x 369."""
    bench, conf, cfg = tiny(CELL)
    conf["suite"]["n_cores"] = N_CORES
    conf["engine"]["multicore"] = N_CORES
    conf["model"]["context_rows"] = N_CORES * 369
    return bench, conf, cfg


def _run(trace=False, keep_trace=None):
    bench, conf, cfg = _tiny_peer()
    return runner.run_cell(CELL, SEED, 1.0, trace, time.time(), bench=bench,
                           conf=conf, cfg=cfg, keep_trace=keep_trace)


def test_tiny_peer_cell_is_correct():
    res = _run()
    assert res["correct"], res["checks"]
    assert res["checks"]["context_mismatch_rows"]["value"] == 0
    assert res["failed"] == 0 and res["attempted"] > 0
    assert set(res["metrics"]) == {"sim_instr_per_s", "setup_s"}


def _swap_own_and_first_peer(monkeypatch):
    """The peer layout with the own-core block and the first peer block
    trading places: the same rows in another order."""
    from repro.core import context as ctx_mod
    orig = ctx_mod.peer_context_tokens
    width = ctx_mod.MULTICORE_CONTEXT_LEN

    def swapped(snapshots, peer_snapshots, core_id, vocab):
        out = orig(snapshots, peer_snapshots, core_id, vocab)
        out[:, :2 * width] = np.concatenate(
            [out[:, width:2 * width], out[:, :width]], axis=1)
        return out
    monkeypatch.setattr(ctx_mod, "peer_context_tokens", swapped)


def test_tiny_peer_cell_catches_a_swapped_peer_block(monkeypatch):
    """Rows out of place leave every answer where it was (a context has
    no positions; the head averages its rows), so only the row check
    can see the fault."""
    _swap_own_and_first_peer(monkeypatch)
    res = _run()
    assert not res["correct"], res["checks"]
    chk = res["checks"]
    assert chk["context_mismatch_rows"]["value"] > 0
    assert chk["answer_rel_err"]["value"] <= chk["answer_rel_err"]["limit"]


@pytest.mark.parametrize("fault", sorted(faults.FAULTS))
def test_tiny_peer_cell_catches_an_altered_answer(monkeypatch, fault):
    patch(monkeypatch, fault)
    res = _run()
    assert not res["correct"], res["checks"]
    assert res["checks"]["context_mismatch_rows"]["value"] == 0


def test_query_blocked_reference_equals_the_plain_one():
    """``ref_peer.clip_cycles`` (self-attention 32 queries at a time,
    M not a multiple of 32) against ``ref_model.clip_cycles``, which
    holds every score at once, at ``highest``."""
    m = dict(d_model=32, num_heads=2, head_dim=16, d_ff=64, vocab_size=512,
             l_token=16, n_inst_layers=1, n_block_layers=2)
    params = weights.make_params(5, m)
    rng = np.random.default_rng(5)
    b, l, rows = 3, 8, 100
    rt = jnp.asarray(rng.normal(size=(b, l, 32)), jnp.float32)
    ctx = jnp.asarray(rng.integers(0, 512, (b, rows)), jnp.int32)
    mask = jnp.asarray(rng.integers(0, 2, (b, l)), jnp.float32)
    hi = ref_model.PRECISIONS["highest"]
    want = ref_model.clip_cycles(params, rt, ctx, mask, 2, hi)
    got = ref_peer.clip_cycles(params, rt, ctx, mask, 2, hi, q_block=32)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5)


def _reading(span_s=None, clips=None):
    before, after = {rd.SPAN_SECONDS: {}}, {rd.SPAN_SECONDS: {}}
    if span_s is not None:
        key = (("instance", "engine0"), ("span", "engine.peer_context"))
        before[rd.SPAN_SECONDS][key] = 2.0
        after[rd.SPAN_SECONDS][key] = 2.0 + span_s
    if clips is not None:
        key = (("instance", "engine0"),)
        before["capsim_peer_context_clips_total"] = {key: 50.0}
        after["capsim_peer_context_clips_total"] = {key: 50.0 + clips}
    return rd.Reading(window_s=50.0, before=before, after=after, extra={},
                      model={}, peak={})


def test_peer_context_reader():
    from harness import spec
    read = spec.reader("fe.peer_context_us_per_clip").read
    assert read(_reading(0.5, 10_000)) == pytest.approx(50.0)
    # a program without the span or the counter reads None, not 0
    assert read(_reading()) is None
    assert read(_reading(clips=10_000)) is None
    assert read(_reading(0.5)) is None
    assert read(_reading(0.5, 0)) is None


def test_traced_peer_run_reports_the_peer_context(tmp_path):
    """A tiny traced run: the result line has the new metric, and the
    profiler trace holds ``engine.peer_context`` inside the window, each
    a child of an ``engine.context`` event and carrying its clips."""
    from jax.profiler import ProfileData
    out = _run(trace=True, keep_trace=str(tmp_path))
    assert out["correct"], out["checks"]
    assert out["metrics"]["fe.peer_context_us_per_clip"]["value"] > 0
    path = tr.find_xplane(str(tmp_path))
    lo, hi = tr.window_of(tr.extract(path))
    stats = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in tr._events(line, True):
                    if lo <= e.start_ns < hi:
                        stats.setdefault(e.name, []).append(dict(e.stats))
    context_ids = {s["id"] for s in stats["engine.context"]}
    peer = stats["engine.peer_context"]
    assert peer and all(s["parent"] in context_ids and s["clips"] > 0
                        for s in peer)
