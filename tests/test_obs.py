"""Observability layer: metrics registry, tracer, flight recorder.

Covers the tentpole's own contracts (thread-safe counters, Prometheus
exposition format, ring wraparound, disabled-mode zero cost) and the
integration path that matters most: an injected NaN demotion in the
real ``SimulationService`` must produce a postmortem JSON whose
tier-transition ledger agrees with the service snapshot's counters.
"""
import json
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import predictor
from repro.core.engine_config import EngineConfig, ObservabilityConfig
from repro.core.standardize import build_vocab
from repro.launch import compile_cache
from repro.obs import MetricsRegistry, Observability, Tracer
from repro.obs import trace as obs_trace
from repro.obs.compiles import compile_monitor
from repro.obs.exporter import serve_metrics
from repro.serving.engine import Request
from repro.serving.faults import FaultInjector
from repro.serving.service import (ServiceSLA, ServiceSnapshot,
                                   SimulationService)

VOCAB = build_vocab()
SMALL_CFG = get_config("capsim").replace(
    d_model=32, head_dim=8, d_ff=64, dtype="float32")


@pytest.fixture(scope="module")
def params():
    return predictor.init_params(SMALL_CFG, jax.random.PRNGKey(0))


def _req(i, n=4):
    rng = np.random.RandomState(i)
    tok = rng.randint(0, VOCAB.size, (n, 128, SMALL_CFG.clip_tokens)
                      ).astype(np.int32)
    ctx = rng.randint(0, VOCAB.size, (n, SMALL_CFG.context_tokens)
                      ).astype(np.int32)
    return Request(i, tok, ctx, np.ones((n, 128), np.float32))


# --------------------------------------------------------------------------- #
# Metrics registry
# --------------------------------------------------------------------------- #

def test_counter_gauge_histogram_basics():
    m = MetricsRegistry()
    c = m.counter("c_total", "c", ("k",)).labels(k="a")
    c.inc()
    c.inc(2.5)
    assert m.value("c_total", k="a") == 3.5
    assert m.value("c_total", k="missing") == 0.0
    g = m.gauge("g", "g", ()).labels()
    g.set(7)
    g.dec(3)
    assert m.value("g") == 4
    h = m.histogram("h_seconds", "h", (), buckets=(1.0, 10.0)).labels()
    h.observe(0.5)
    h.observe(5.0)
    h.observe(50.0)
    [(labels, (total, count))] = m.collect("h_seconds")
    assert count == 3 and total == 55.5


def test_counter_negative_inc_rejected():
    m = MetricsRegistry()
    c = m.counter("n_total", "n", ()).labels()
    with pytest.raises(ValueError):
        c.inc(-1)


def test_registration_idempotent_but_kind_checked():
    m = MetricsRegistry()
    f1 = m.counter("x_total", "x", ("a",))
    f2 = m.counter("x_total", "x", ("a",))
    assert f1 is f2
    with pytest.raises(ValueError):
        m.gauge("x_total", "x", ("a",))
    with pytest.raises(ValueError):
        m.counter("x_total", "x", ("b",))


def test_registry_thread_safety():
    """N writers hammering one counter and one histogram concurrently:
    the final totals must be exact (the registry lock is real)."""
    m = MetricsRegistry()
    c = m.counter("race_total", "r", ("w",))
    h = m.histogram("race_seconds", "r", ())
    n_threads, n_iter = 8, 2_000
    barrier = threading.Barrier(n_threads)

    def work(w):
        handle = c.labels(w=str(w % 2))       # two shared series
        hh = h.labels()
        barrier.wait()
        for _ in range(n_iter):
            handle.inc()
            hh.observe(1.0)

    threads = [threading.Thread(target=work, args=(w,))
               for w in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    total = (m.value("race_total", w="0")
             + m.value("race_total", w="1"))
    assert total == n_threads * n_iter
    [(_, (hsum, hcount))] = m.collect("race_seconds")
    assert hcount == n_threads * n_iter and hsum == float(hcount)


def test_prometheus_exposition_golden():
    """Exact text-format golden: HELP/TYPE lines, escaped label values,
    cumulative histogram buckets with +Inf, _sum and _count."""
    m = MetricsRegistry()
    m.counter("req_total", 'requests with "quotes"\nand newline',
              ("tier",)).labels(tier="fused").inc(3)
    m.gauge("depth", "queue depth", ()).labels().set(2.5)
    h = m.histogram("lat_seconds", "latency", (),
                    buckets=(0.1, 1.0)).labels()
    h.observe(0.05)
    h.observe(0.5)
    h.observe(5.0)
    got = m.render_prometheus()
    want = "\n".join([
        '# HELP depth queue depth',
        '# TYPE depth gauge',
        'depth 2.5',
        '# HELP lat_seconds latency',
        '# TYPE lat_seconds histogram',
        'lat_seconds_bucket{le="0.1"} 1',
        'lat_seconds_bucket{le="1"} 2',
        'lat_seconds_bucket{le="+Inf"} 3',
        'lat_seconds_sum 5.55',
        'lat_seconds_count 3',
        '# HELP req_total requests with "quotes"\\nand newline',
        '# TYPE req_total counter',
        'req_total{tier="fused"} 3',
    ]) + "\n"
    assert got == want


def test_snapshot_is_json_roundtrippable():
    m = MetricsRegistry()
    m.counter("a_total", "a", ("x",)).labels(x="1").inc()
    m.histogram("b_seconds", "b", ()).labels().observe(0.2)
    snap = m.snapshot()
    assert json.loads(json.dumps(snap)) == snap


def test_exporter_serves_registry():
    m = MetricsRegistry()
    m.counter("served_total", "s", ()).labels().inc(5)
    server = serve_metrics(m, port=0)
    try:
        import urllib.request
        port = server.server_address[1]
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/metrics", timeout=10) as r:
            body = r.read().decode()
            assert r.headers["Content-Type"].startswith("text/plain")
        assert "served_total 5" in body
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/healthz", timeout=10) as r:
            assert r.read() == b"ok\n"
    finally:
        server.shutdown()


# --------------------------------------------------------------------------- #
# Tracer
# --------------------------------------------------------------------------- #

def test_disabled_tracer_is_free():
    """Spans on a disabled tracer still time into the registry, but
    nothing reaches the ring — spans, instants or pre-timed records."""
    m = MetricsRegistry()
    tr = Tracer(enabled=False)
    obs = Observability(metrics=m, tracer=tr)
    with obs.span("x"):
        pass
    with obs.span("y", args={"a": 1}) as sp:
        pass
    assert sp.seconds > 0 and sp.id > 0
    obs.event("ev")
    obs.record_span("pre", 0, 100)
    tr.record("pre", 0, 100)
    tr.instant("ev")
    assert tr.spans() == []
    assert m.value("capsim_span_seconds_total", span="y", instance="") \
        == pytest.approx(sp.seconds)
    assert m.value("capsim_span_seconds_total", span="pre", instance="") \
        == pytest.approx(1e-7)


def test_ring_wraparound_keeps_last_n():
    tr = Tracer(ring_size=8, enabled=True)
    for i in range(20):
        tr.record(f"s{i}", start_ns=i * 1000, dur_ns=10)
    spans = tr.spans()
    assert len(spans) == 8
    assert [s.name for s in spans] == [f"s{i}" for i in range(12, 20)]


def test_chrome_export_shape():
    obs = Observability(metrics=MetricsRegistry(),
                        tracer=Tracer(enabled=True))
    with obs.span("outer", args={"k": "v"}) as outer_sp:
        with obs.span("inner") as inner_sp:
            obs.event("mark")
    doc = obs.tracer.export_chrome()
    events = doc["traceEvents"]
    names = [e["name"] for e in events]
    assert names == ["mark", "inner", "outer"]   # inner closes first
    mark, inner, outer = events
    assert outer["ph"] == "X" and outer["args"]["k"] == "v"
    assert inner["args"]["depth"] == 1           # nested under outer
    assert inner["args"]["parent"] == outer["args"]["id"] == outer_sp.id
    assert outer["args"]["parent"] == 0
    assert mark["ph"] == "i" and mark["args"]["parent"] == inner_sp.id
    json.dumps(doc)                              # must be serializable


def test_nested_spans_carry_parent_ids():
    obs = Observability(metrics=MetricsRegistry(),
                        tracer=Tracer(enabled=True))
    with obs.span("a") as a:
        with obs.span("b") as b:
            with obs.span("c") as c:
                pass
        with obs.span("d") as d:
            pass
    with obs.span("e") as e:
        pass
    assert len({a.id, b.id, c.id, d.id, e.id}) == 5
    assert (a.parent_id, b.parent_id, c.parent_id, d.parent_id,
            e.parent_id) == (0, a.id, b.id, a.id, 0)
    assert (a.depth, b.depth, c.depth, d.depth) == (0, 1, 2, 1)
    recs = {r.name: r for r in obs.tracer.spans()}
    assert recs["c"].span_id == c.id and recs["c"].parent_id == b.id
    assert recs["c"].depth == 2


def test_explicit_parent_crosses_threads():
    """A span opened on another thread with ``parent=`` hangs under the
    given span, and its own children hang under it."""
    obs = Observability(metrics=MetricsRegistry(),
                        tracer=Tracer(enabled=True))
    got = {}

    def child(parent):
        with obs.span("worker.child", parent=parent) as ch:
            with obs.span("worker.grandchild") as gc:
                pass
        with obs.span("worker.orphan") as orphan:
            pass
        got.update(ch=ch, gc=gc, orphan=orphan)

    with obs.span("main.parent") as parent:
        th = threading.Thread(target=child, args=(parent,))
        th.start()
        th.join()
    assert got["ch"].parent_id == parent.id and got["ch"].depth == 1
    assert got["gc"].parent_id == got["ch"].id and got["gc"].depth == 2
    assert got["orphan"].parent_id == 0      # the thread's own stack
    recs = {r.name: r for r in obs.tracer.spans()}
    assert recs["worker.child"].tid != recs["main.parent"].tid


def test_record_span_has_an_id_and_no_parent():
    """A span timed elsewhere did not run inside the span open when it
    is recorded."""
    obs = Observability(metrics=MetricsRegistry(),
                        tracer=Tracer(enabled=True))
    with obs.span("open") as sp:
        obs.record_span("timed", 10, 5, args={"request": 3})
    recs = {r.name: r for r in obs.tracer.spans()}
    assert recs["timed"].span_id > sp.id and recs["timed"].parent_id == 0
    assert recs["timed"].args == {"request": 3}
    assert (recs["timed"].start_ns, recs["timed"].dur_ns) == (10, 5)
    assert obs.metrics.value("capsim_span_seconds_total",
                             span="timed", instance="") \
        == pytest.approx(5e-9)


@pytest.fixture
def counted_annotations(monkeypatch):
    """Counts the profiler annotations the span primitive builds."""
    from jax.profiler import TraceAnnotation
    built = []

    class Counted(TraceAnnotation):
        def __init__(self, name, **meta):
            built.append((name, meta))
            super().__init__(name, **meta)

    monkeypatch.setattr(obs_trace, "_TRACE_ANNOTATION", Counted)
    return built


def test_no_profiler_session_builds_no_annotation(counted_annotations):
    m = MetricsRegistry()
    obs = Observability(metrics=m, tracer=Tracer(enabled=False))
    assert not obs_trace.profiler_active()
    for _ in range(3):
        with obs.span("quiet.work", instance="q0", args={"n": 1}) as sp:
            pass
    assert counted_annotations == []
    assert sp.seconds > 0
    [(_, (total, count))] = m.collect("capsim_span_seconds")
    assert count == 3 and total > 0
    assert m.value("capsim_span_seconds_total", span="quiet.work",
                   instance="q0") == pytest.approx(total)


def test_profiler_session_gets_one_annotation_per_span(counted_annotations,
                                                       tmp_path):
    obs = Observability(metrics=MetricsRegistry(),
                        tracer=Tracer(enabled=False))
    jax.profiler.start_trace(str(tmp_path))
    try:
        assert obs_trace.profiler_active()
        with obs.span("svc.flush", args={"flush": 3, "name": "a,b",
                                         "rate": 0.5}) as outer:
            with obs.span("rt.index"):
                pass
    finally:
        jax.profiler.stop_trace()
    assert not obs_trace.profiler_active()
    assert [n for n, _ in counted_annotations] == ["svc.flush", "rt.index"]
    # integers only: the profiler cuts strings at a comma
    assert counted_annotations[0][1] == {"id": outer.id, "parent": 0,
                                         "flush": 3}
    inner_meta = counted_annotations[1][1]
    assert set(inner_meta) == {"id", "parent"}
    assert inner_meta["parent"] == outer.id < inner_meta["id"]


def test_rt_wait_lies_inside_rt_build(params):
    from repro.core.rt_cache import RTCache
    obs = Observability(metrics=MetricsRegistry(),
                        tracer=Tracer(enabled=True))
    cache = RTCache(params, SMALL_CFG, SMALL_CFG.clip_tokens, obs=obs)
    rows = np.random.RandomState(0).randint(
        0, VOCAB.size, (5, SMALL_CFG.clip_tokens)).astype(np.int32)
    cache.ensure_rows(rows)
    recs = obs.tracer.spans()
    [build] = [r for r in recs if r.name == "rt.build"]
    [wait] = [r for r in recs if r.name == "rt.wait"]
    assert wait.parent_id == build.span_id
    assert build.start_ns <= wait.start_ns
    assert wait.start_ns + wait.dur_ns <= build.start_ns + build.dur_ns
    assert obs.metrics.value("capsim_span_seconds_total", span="rt.wait",
                             instance=cache.instance) > 0


def test_bucket_occupancy_family_is_gone(params):
    from repro.core.engine import BatchedPredictor
    m = MetricsRegistry()
    pred = BatchedPredictor(params, SMALL_CFG,
                            config=EngineConfig(batch_size=8,
                                                rt_cache=False),
                            obs=Observability(metrics=m))
    r = _req(0, n=3)
    pred.add(r.clip_tokens, r.context_tokens, r.clip_mask)
    assert pred.drain().shape == (3,)
    families = set(m.snapshot())
    assert "capsim_predictor_pad_rows_total" in families
    assert "capsim_predictor_batches_total" in families
    assert not any("occupancy" in f for f in families)


def test_service_flush_spans_share_flush_and_request_ids(params):
    """One flush of one request: every span of the request path is
    recorded, and the ids tie them to their flush and request."""
    config = EngineConfig(batch_size=8, observability=ObservabilityConfig(
        trace=True, trace_ring=4096))
    sla = ServiceSLA(watchdog_s=300.0, check_every=1)
    with SimulationService(params, SMALL_CFG, config, sla=sla) as svc:
        tickets = [svc.submit(_req(10, n=3))]
        assert tickets[0].result(timeout=300).status == "ok"
    recs = svc.obs.tracer.spans()
    by_id = {r.span_id: r for r in recs}
    names = {r.name for r in recs}
    assert {"svc.submit", "svc.queue", "svc.wait", "svc.flush",
            "svc.attempt", "rt.index", "predict.dedup", "svc.resolve",
            "svc.spot_check", "rt.build", "predict.dispatch"} <= names

    def ancestors(rec):
        while rec.parent_id:
            rec = by_id[rec.parent_id]
            yield rec

    [flush] = [r for r in recs if r.name == "svc.flush"]
    fid = flush.args["flush"]
    assert flush.args["requests"] == 1 and flush.args["clips"] == 3
    [attempt] = [r for r in recs if r.name == "svc.attempt"]
    assert attempt.parent_id == flush.span_id
    assert attempt.tid != flush.tid            # the watchdog's thread
    assert attempt.args == {"flush": fid, "attempt": 1, "tier": 0,
                            "instance": svc.instance}
    for name in ("rt.index", "predict.dedup"):
        # the attempt's own and the spot check's re-run on the worker
        under = [list(ancestors(r)) for r in recs if r.name == name]
        assert all(flush in up for up in under), name
        assert any(attempt in up for up in under), name
    for name in ("svc.resolve", "svc.spot_check"):
        [rec] = [r for r in recs if r.name == name]
        assert rec.args["flush"] == fid and rec.parent_id == flush.span_id
    rids = {t.request_id for t in tickets}
    submits = [r for r in recs if r.name == "svc.submit"]
    queues = [r for r in recs if r.name == "svc.queue"]
    assert {r.args["request"] for r in submits} == rids
    assert {r.args["request"] for r in queues} == rids
    assert all(r.args["flush"] == fid and r.parent_id == 0 for r in queues)
    assert all(r.dur_ns > 0 for r in queues)


def test_obs_span_records_metrics_and_trace(tmp_path):
    obs = Observability.from_config(
        ObservabilityConfig(trace=True, trace_ring=16))
    with obs.span("unit.work", instance="t0") as sp:
        x = sum(range(100))
    assert x == 4950 and sp.seconds > 0
    assert obs.metrics.value("capsim_span_seconds_total",
                             span="unit.work", instance="t0") \
        == pytest.approx(sp.seconds)
    [rec] = [r for r in obs.tracer.spans() if r.name == "unit.work"]
    assert rec.args["instance"] == "t0"
    out = tmp_path / "trace.json"
    obs.tracer.dump(str(out))
    assert json.loads(out.read_text())["traceEvents"]


# --------------------------------------------------------------------------- #
# ServiceSnapshot
# --------------------------------------------------------------------------- #

def test_service_snapshot_roundtrip_and_stable_keys(params):
    svc = SimulationService(params, SMALL_CFG, EngineConfig(batch_size=8),
                            sla=ServiceSLA())
    snap = svc.snapshot()
    d = snap.to_dict()
    # the frozen key set benches and the CI chaos leg parse
    assert list(d) == [
        "submitted", "statuses", "current_tier", "backoff",
        "healthy_streak", "queued", "queued_clips", "clips_per_s_ewma",
        "n_flushes", "tiers", "faults_fired",
        "abandoned_flush_threads", "abandoned_flush_threads_total"]
    assert list(d["tiers"]) == ["fused_int8", "fused", "rt", "monolithic"]
    assert list(d["tiers"]["rt"]) == [
        "name", "flushes", "clips", "demotions", "promotions",
        "nan_trips", "relerr_trips", "fault_trips", "watchdog_trips",
        "persist_failures", "spot_checks"]
    back = ServiceSnapshot.from_dict(json.loads(json.dumps(d)))
    assert back.to_dict() == d
    with pytest.raises(ValueError):
        ServiceSnapshot.from_dict({**d, "bogus": 1})
    # stats() is the thin compat wrapper over the same snapshot
    assert svc.stats() == svc.snapshot().to_dict()


# --------------------------------------------------------------------------- #
# Flight recorder on the real degradation path
# --------------------------------------------------------------------------- #

def test_nan_demotion_writes_consistent_postmortem(params, tmp_path):
    """A forced NaN on the top tier must demote AND dump a postmortem
    whose event ring agrees with the snapshot counters it embeds."""
    flight_dir = tmp_path / "flight"
    config = EngineConfig(
        batch_size=8, faults={"nan_output": 1.0},
        observability=ObservabilityConfig(flight_dir=str(flight_dir)))
    inj = FaultInjector({"nan_output": 1.0}, seed=3)
    inj.set_enabled(False)
    sla = ServiceSLA(watchdog_s=120.0, promote_after=1, check_every=0)
    with SimulationService(params, SMALL_CFG, config, sla=sla,
                           fault_injector=inj) as svc:
        svc.prewarm(_req(0, n=2))
        assert svc.submit(_req(1)).result(timeout=300).status == "ok"
        inj.set_enabled(True)                 # every retire goes NaN now
        res = svc.submit(_req(2)).result(timeout=300)
        inj.set_enabled(False)
        assert res.status in ("degraded", "failed")
        snap = svc.snapshot()
    fl = svc.obs.flight
    assert fl is not None and fl.postmortems
    post = json.loads(open(fl.postmortems[-1]).read())
    assert post["schema_version"] == 1
    assert post["reason"].startswith("demote_")
    assert post["metrics"] is not None
    # ledger consistency: transition events vs embedded snapshot counters
    tiers = post["state"]["tiers"]
    names = list(tiers)
    exp_demote = sum(tiers[n]["demotions"] for n in names[:-1])
    ev = [e for e in post["events"] if e["kind"] == "tier_transition"]
    got_demote = sum(1 for e in ev if e["reason"] != "promotion")
    assert got_demote == exp_demote > 0
    # the nan reason made it into both ledgers
    assert any(e["reason"] == "nan" for e in ev)
    assert sum(t["nan_trips"] for t in tiers.values()) > 0
    # the final live snapshot counts at least as many demotions
    live = sum(t["demotions"] for t in snap.tiers.values())
    assert live >= exp_demote


def test_faults_counter_lands_in_registry():
    from repro.obs import REGISTRY
    from repro.serving.faults import FAULTS_INJECTED_TOTAL
    before = REGISTRY.value(FAULTS_INJECTED_TOTAL, kind="device_error")
    inj = FaultInjector({"device_error": 1.0}, seed=0)
    assert inj.maybe("device_error")
    after = REGISTRY.value(FAULTS_INJECTED_TOTAL, kind="device_error")
    assert after == before + 1


# --------------------------------------------------------------------------- #
# Compile accounting + the persistent compile cache
# --------------------------------------------------------------------------- #

def test_compile_monitor_clocks_this_thread_only():
    mon = compile_monitor()
    assert compile_monitor() is mon            # one listener set per process
    x = jnp.ones((17, 3))
    c0 = mon.counts()["compiles"]
    with mon.attach() as acct:
        f = jax.jit(lambda a: jnp.tanh(a) * 2.0 + a.sum())
        f(x).block_until_ready()
        first = acct.seconds()
        f(x).block_until_ready()               # cached: no trace, no compile
        assert acct.seconds() == first
        # another thread's compile is not charged to this thread
        th = threading.Thread(target=lambda: jax.jit(
            lambda a: a * 5.0 - 2.0)(x).block_until_ready())
        th.start()
        th.join()
        assert acct.seconds() == first
    assert first > 0
    assert mon.counts()["compiles"] > c0


@pytest.fixture
def restore_cache_config():
    from jax.experimental.compilation_cache import compilation_cache as cc
    keys = ("jax_compilation_cache_dir", "jax_enable_compilation_cache",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")
    saved = {k: getattr(jax.config, k) for k in keys}
    cc.reset_cache()
    yield
    for k, v in saved.items():
        jax.config.update(k, v)
    cc.reset_cache()


def test_compile_cache_fixed_dir_and_repeat_hits(tmp_path, monkeypatch,
                                                 restore_cache_config):
    fixed = tmp_path / "fixed"
    monkeypatch.delenv(compile_cache.CACHE_ENV, raising=False)
    monkeypatch.setattr(compile_cache, "DEFAULT_CACHE_DIR", fixed)
    assert compile_cache.enable_compile_cache() == str(fixed)
    assert jax.config.jax_compilation_cache_dir == str(fixed)
    mon = compile_monitor()
    x = jnp.ones(11)
    jax.jit(lambda a: jnp.cos(a) * 3.0 - 1.5)(x).block_until_ready()
    assert any(fixed.iterdir())
    hits = mon.counts()["cache_hits"]
    # the same program from a fresh jit is read back, not recompiled
    jax.jit(lambda a: jnp.cos(a) * 3.0 - 1.5)(x).block_until_ready()
    assert mon.counts()["cache_hits"] > hits


def test_compile_cache_leaves_env_dir_to_jax(tmp_path, monkeypatch,
                                             restore_cache_config):
    monkeypatch.setenv(compile_cache.CACHE_ENV, str(tmp_path / "env"))
    monkeypatch.setattr(compile_cache, "DEFAULT_CACHE_DIR",
                        tmp_path / "fixed")
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable_compile_cache() == str(tmp_path / "env")
    # JAX reads the variable itself at start-up; the helper sets no dir
    assert jax.config.jax_compilation_cache_dir == before
    assert not (tmp_path / "fixed").exists()
