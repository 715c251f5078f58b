"""SimulationService: typed results, admission, watchdog, degradation.

The service contract: every submitted request ends in exactly one typed
terminal state (ok / degraded / overloaded / deadline_exceeded / failed
/ cancelled) — never a hang — and a degraded answer is still within the
rung's rel-err gate vs the monolithic reference.
"""
import time

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import predictor
from repro.core.engine_config import EngineConfig
from repro.core.standardize import build_vocab
from repro.serving.engine import PredictorEngine, Request
from repro.serving.faults import FaultInjector
from repro.serving.service import (STATUSES, DegradationController,
                                   ServiceSLA, SimulationService,
                                   build_ladder)

VOCAB = build_vocab()
SMALL_CFG = get_config("capsim").replace(
    d_model=32, head_dim=8, d_ff=64, dtype="float32")
BASE = EngineConfig(batch_size=8)


@pytest.fixture(scope="module")
def params():
    return predictor.init_params(SMALL_CFG, jax.random.PRNGKey(0))


def _req(i, n=4, seed=None):
    rng = np.random.RandomState(i if seed is None else seed)
    tok = rng.randint(0, VOCAB.size, (n, 128, SMALL_CFG.clip_tokens)
                      ).astype(np.int32)
    ctx = rng.randint(0, VOCAB.size, (n, SMALL_CFG.context_tokens)
                      ).astype(np.int32)
    return Request(i, tok, ctx, np.ones((n, 128), np.float32))


def _sla(**kw):
    kw.setdefault("watchdog_s", 120.0)       # compile-safe on slow CI
    kw.setdefault("promote_after", 1)
    return ServiceSLA(**kw)


# --------------------------------------------------------------------------- #
# Ladder + controller units
# --------------------------------------------------------------------------- #

def test_build_ladder_respects_structural_axes():
    assert [n for n, _ in build_ladder(BASE)] == [
        "fused_int8", "fused", "rt", "monolithic"]
    assert [n for n, _ in build_ladder(BASE.replace(rt_cache=False))] == [
        "monolithic"]
    assert [n for n, _ in build_ladder(BASE.replace(use_context=False))
            ] == ["rt", "monolithic"]
    for name, cfg in build_ladder(BASE):
        cfg.validate()                        # every rung is launchable
    mono = dict(build_ladder(BASE))["monolithic"]
    assert not mono.rt_cache and mono.rt_store_dir is None


def test_degradation_controller_backoff():
    ctrl = DegradationController(4, ServiceSLA(promote_after=2,
                                               backoff_max=8))
    assert ctrl.on_trip() == 1                # demote, backoff 2 -> 4
    assert ctrl.on_trip() == 2                # backoff 4 -> 8
    assert ctrl.backoff == 8
    # climbing back needs a full backoff streak per rung
    for _ in range(7):
        assert ctrl.on_healthy() is None
    assert ctrl.on_healthy() == 1
    for _ in range(7):
        assert ctrl.on_healthy() is None
    assert ctrl.on_healthy() == 0
    # stable at the top for promote_after more -> backoff forgiven
    ctrl.on_healthy()
    ctrl.on_healthy()
    assert ctrl.backoff == 2
    # at the floor a trip demotes nowhere but still backs off
    ctrl.idx = 3
    assert ctrl.on_trip() is None


# --------------------------------------------------------------------------- #
# Request validation + persistent engine backend
# --------------------------------------------------------------------------- #

def test_submit_validates_shapes_and_dtypes(params):
    eng = PredictorEngine(params, SMALL_CFG, BASE)
    good = _req(0)
    eng.submit(good)
    bad_rank = Request(1, good.clip_tokens[:, 0], good.context_tokens,
                       good.clip_mask)
    with pytest.raises(ValueError, match="clip_tokens"):
        eng.submit(bad_rank)
    with pytest.raises(ValueError, match="l_clip"):
        eng.submit(Request(2, good.clip_tokens[:, :7], good.context_tokens,
                           good.clip_mask[:, :7]))
    with pytest.raises(ValueError, match="dtype"):
        eng.submit(Request(3, good.clip_tokens.astype(np.float32),
                           good.context_tokens, good.clip_mask))
    with pytest.raises(ValueError, match="context_tokens"):
        eng.submit(Request(4, good.clip_tokens,
                           good.context_tokens[:2], good.clip_mask))
    with pytest.raises(ValueError, match="clip_mask"):
        eng.submit(Request(5, good.clip_tokens, good.context_tokens,
                           good.clip_mask.astype(np.int32)))
    with pytest.raises(ValueError, match="context"):
        eng.submit(Request(6, good.clip_tokens,
                           good.context_tokens[:, :13], good.clip_mask))


def test_engine_backend_persists_across_flushes(params):
    eng = PredictorEngine(params, SMALL_CFG, BASE)
    eng.submit(_req(0))
    r1 = eng.flush()[0]
    backend = eng.backend()
    eng.submit(_req(1, n=3))
    eng.submit(_req(0))
    r2 = eng.flush()
    assert eng.backend() is backend           # ONE backend, reused
    assert [r.n_clips for r in r2] == [3, 4]
    assert r2[1].total_cycles == r1.total_cycles   # replay is bitwise
    # the RT table persisted across flushes: replay encoded nothing new
    assert eng.rt_stats.n_rows_encoded > 0


# --------------------------------------------------------------------------- #
# Service behavior
# --------------------------------------------------------------------------- #

def test_service_healthy_top_tier(params):
    with SimulationService(params, SMALL_CFG, BASE, sla=_sla()) as svc:
        tickets = [svc.submit(_req(i)) for i in range(3)]
        results = [t.result(timeout=300) for t in tickets]
    assert all(r.status == "ok" and r.ok for r in results)
    assert all(r.tier == "fused_int8" for r in results)
    assert all(r.total_cycles and np.isfinite(r.total_cycles)
               for r in results)
    # against the plain engine at the same rung: identical numbers
    eng = PredictorEngine(params, SMALL_CFG, BASE.replace(
        fused_serving=True, precision="int8"))
    eng.submit(_req(0))
    assert eng.flush()[0].total_cycles == pytest.approx(
        results[0].total_cycles, rel=1e-6)


def test_service_sheds_when_queue_full(params):
    sla = _sla(queue_limit=1)
    svc = SimulationService(params, SMALL_CFG, BASE, sla=sla)
    # not started: the worker never drains, so the 2nd+ submissions see
    # a full queue and must be shed IMMEDIATELY with a typed result
    svc._running = True
    t1 = svc.submit(_req(0))
    t2 = svc.submit(_req(1))
    assert not t1.done()
    assert t2.done() and t2.result().status == "overloaded"
    assert "queue full" in t2.result().error
    svc.stop(drain=False)
    assert t1.result(timeout=5).status == "cancelled"


def test_service_rejects_after_stop_and_validates(params):
    svc = SimulationService(params, SMALL_CFG, BASE, sla=_sla())
    t = svc.submit(_req(0))
    assert t.result().status == "overloaded"   # never started
    with pytest.raises(ValueError):
        svc.submit(Request(1, np.zeros((2, 3), np.int32),
                           np.zeros((2, 4), np.int32),
                           np.zeros((2, 3), np.float32)))


def test_service_deadline_exceeded_is_typed(params):
    with SimulationService(params, SMALL_CFG, BASE, sla=_sla()) as svc:
        # a deadline that already passed: the window collector resolves
        # it typed without burning a flush
        t = svc.submit(_req(0), deadline_s=-1.0)
        res = t.result(timeout=60)
    assert res.status == "deadline_exceeded"
    assert res.total_cycles is None and not res.ok


def test_service_nan_demotes_then_repromotes(params):
    inj = FaultInjector({"nan_output": 1.0})
    sla = _sla(check_every=0, backoff_max=2)
    with SimulationService(params, SMALL_CFG, BASE, sla=sla,
                           fault_injector=inj) as svc:
        top = svc.tier_stats[0].name
        # int8 tier returns NaN -> guard demotes; every tier is equally
        # poisoned, so the ladder exhausts into a typed failure
        res = svc.submit(_req(0)).result(timeout=600)
        assert res.status == "failed"
        assert "non-finite" in res.error or "tiers failed" in res.error
        assert svc.current_tier != top
        assert sum(t.nan_trips for t in svc.tier_stats) > 0
        demoted_to = svc.current_tier

        # faults stop -> healthy traffic climbs the ladder back
        inj.set_enabled(False)
        for i in range(1, 12):
            r = svc.submit(_req(i)).result(timeout=600)
            assert r.ok
            if svc.current_tier == top:
                break
        assert svc.current_tier == top
        assert svc.current_tier != demoted_to
        assert sum(t.promotions for t in svc.tier_stats) > 0
        stats = svc.stats()
    assert stats["statuses"]["failed"] == 1
    assert set(stats["statuses"]) == set(STATUSES)


def test_service_watchdog_aborts_stuck_flush(params):
    inj = FaultInjector({"slow_flush": 1.0}, slow_seconds=30.0)
    sla = _sla(watchdog_s=0.5, check_every=0)
    t0 = time.time()
    with SimulationService(params, SMALL_CFG, BASE, sla=sla,
                           fault_injector=inj) as svc:
        res = svc.submit(_req(0)).result(timeout=120)
        # stuck on EVERY rung -> typed failure, and the watchdog cut
        # each attempt at ~0.5s instead of 30s
        assert res.status == "failed"
        assert "watchdog" in res.error
        assert sum(t.watchdog_trips for t in svc.tier_stats) > 0
        assert time.time() - t0 < 30.0
        # faults stop: the service recovers without a restart (backends
        # were rebuilt after the abandoned flushes)
        inj.set_enabled(False)
        assert svc.submit(_req(1)).result(timeout=600).ok


def test_service_degraded_results_stay_gated(params):
    # poison ONLY the top tier via the spot check: int8's own rel err is
    # within gate, so serving continues at the top; a non-finite check
    # (nan fault) must demote.  Served-degraded answers then match the
    # monolithic reference exactly (rt tier is bitwise).
    inj = FaultInjector({"nan_output": 0.6}, seed=3)
    sla = _sla(check_every=0)
    with SimulationService(params, SMALL_CFG, BASE, sla=sla,
                           fault_injector=inj) as svc:
        results = [svc.submit(_req(i)).result(timeout=600)
                   for i in range(6)]
    ref = PredictorEngine(params, SMALL_CFG, BASE.replace(rt_cache=False))
    for i, r in enumerate(results):
        assert r.status in ("ok", "degraded", "failed")
        if not r.ok:
            continue
        ref.submit(_req(i))
        want = ref.flush()[0].total_cycles
        tol = 0.05 if r.tier == "fused_int8" else 1e-3
        assert abs(r.total_cycles - want) / abs(want) <= tol


def test_service_stats_shape(params):
    with SimulationService(params, SMALL_CFG, BASE, sla=_sla()) as svc:
        svc.submit(_req(0)).result(timeout=300)
        st = svc.stats()
    assert st["submitted"] == 1 and st["statuses"]["ok"] == 1
    assert list(st["tiers"]) == ["fused_int8", "fused", "rt",
                                 "monolithic"]
    assert st["tiers"]["fused_int8"]["clips"] == 4
    assert st["current_tier"] == "fused_int8"


def test_service_watchdog_excludes_compile_time(params):
    # a width no other test compiles: the first flush traces and compiles
    # every program of the top rung, which outlasts this watchdog budget
    cfg = SMALL_CFG.replace(d_ff=96)
    p = predictor.init_params(cfg, jax.random.PRNGKey(1))
    sla = _sla(watchdog_s=0.5, check_every=0)
    with SimulationService(p, cfg, BASE, sla=sla) as svc:
        res = svc.submit(_req(0)).result(timeout=300)
        st = svc.stats()
    assert res.service_seconds > sla.watchdog_s    # a cold, slow flush...
    assert res.status == "ok" and res.tier == "fused_int8"   # ...served
    assert all(t["watchdog_trips"] == 0 for t in st["tiers"].values())


def test_service_spot_checks_against_reference(params):
    sla = _sla(check_every=1)
    with SimulationService(params, SMALL_CFG, BASE, sla=sla) as svc:
        results = [svc.submit(_req(i)).result(timeout=300)
                   for i in range(2)]
        top = svc.tier_stats[0]
        assert top.spot_checks == 2 and top.relerr_trips == 0
    assert all(r.status == "ok" for r in results)


def test_service_audit_holds_every_tier_to_its_tolerance(params):
    svc = SimulationService(params, SMALL_CFG, BASE, sla=_sla())
    errs = svc.audit(_req(0))
    assert list(errs) == ["fused_int8", "fused", "rt", "monolithic"]
    for tier, err in errs.items():
        assert 0.0 <= err <= svc.sla.tier_tolerances[tier], (tier, err)
    assert errs["monolithic"] < 1e-6   # the reference's own numerics
    with svc:
        with pytest.raises(RuntimeError, match="stopped"):
            svc.audit(_req(0))
