"""The readers of the program's spans on hand-made readings, the idle time
no program span explains on hand-made trace events, and a tiny traced
service run whose profiler trace holds the program's spans by name."""
import time

import pytest
from _tiny import tiny
from harness import reading as rd
from harness import runner, spec, spans
from harness import trace as tr

EVENTS = spans.TIER_EVENTS


def _reading(span_s=None, clips=None, window_s=50.0):
    """A reading whose window saw ``span_s`` {span: seconds} and
    ``clips`` clips of healthy flushes; a span of 3 s before the window
    is only subtracted."""
    before, after = {rd.SPAN_SECONDS: {}}, {rd.SPAN_SECONDS: {}}
    for name, s in (span_s or {}).items():
        key = (("instance", "x0"), ("span", name))
        before[rd.SPAN_SECONDS][key] = 3.0
        after[rd.SPAN_SECONDS][key] = 3.0 + s
    if clips is not None:
        key = (("event", "clips"), ("instance", "svc0"),
               ("tier", "fused_int8"))
        before[EVENTS] = {key: 100.0}
        after[EVENTS] = {key: 100.0 + clips}
    return rd.Reading(window_s=window_s, before=before, after=after,
                      extra={}, model={}, peak={})


def _read(metric, r):
    return spec.reader(metric).read(r)


@pytest.mark.parametrize("metric,span,want", [
    ("svc.wait_share.closed", "svc.wait", 100.0 * 20.0 / 50.0),
    ("rt.wait_share.engine", "rt.wait", 100.0 * 20.0 / 50.0),
    ("svc.queue_depth.closed", "svc.queue", 20.0 / 50.0),
])
def test_span_share_readers(metric, span, want):
    assert _read(metric, _reading({span: 20.0})) == pytest.approx(want)
    # another span's seconds do not count, and an absent span is None
    assert _read(metric, _reading({"rt.build": 20.0})) is None
    assert _read(metric, _reading()) is None
    # a span the registry knows but the window never entered reads 0
    assert _read(metric, _reading({span: 0.0})) == 0.0


@pytest.mark.parametrize("metric,span", [
    ("rt.index_us_per_clip.svc", "rt.index"),
    ("batch.dedup_us_per_clip.svc", "predict.dedup"),
])
def test_per_clip_readers(metric, span):
    r = _reading({span: 0.5}, clips=10_000)
    assert _read(metric, r) == pytest.approx(50.0)
    assert _read(metric, _reading(clips=10_000)) is None
    assert _read(metric, _reading({span: 0.5})) is None       # no clips
    assert _read(metric, _reading({span: 0.5}, clips=0)) is None


def _summary(host, devices=None):
    ev = tr.Event
    if devices is None:
        devices = {"/device:TPU:0": [ev(0, 10, "fusion"),
                                     ev(50, 60, "while")]}
    return tr.Summary(lo=0.0, hi=100.0, devices=devices, host=host)


@pytest.mark.parametrize("metric", ["device.idle_unattributed.engine",
                                    "device.idle_unattributed.svc_closed"])
def test_idle_unattributed_readers(metric):
    ev = tr.Event
    # device busy [0,10) and [50,60): gaps [10,50) (midpoint 30) and
    # [60,100) (midpoint 80), 80 ns idle in all
    host = [ev(0, 100, tr.WINDOW_SPAN), ev(0, 100, "bench.pass"),
            ev(20, 40, "engine.interpret"), ev(90, 100, "svc.wait"),
            ev(70, 90, "DeviceToHost")]
    r = _reading()
    r.trace = _summary(host)
    assert _read(metric, r) == pytest.approx(50.0)
    # a program span open across the second gap's midpoint covers it,
    # whichever thread it ran on
    r.trace = _summary(host + [ev(75, 85, "rt.index")])
    assert _read(metric, r) == 0.0
    # no program span at all (a program that does not record them)
    r.trace = _summary(host[:2] + host[4:])
    assert _read(metric, r) is None
    # nothing on the device, or no trace
    r.trace = _summary(host, devices={})
    assert _read(metric, r) is None
    r.trace = None
    assert _read(metric, r) is None
    # a device busy the whole window leaves no idle time
    r.trace = _summary(host, devices={"/device:TPU:0": [ev(0, 100, "x")]})
    assert _read(metric, r) == 0.0


def test_idle_gaps_of_busy_intervals():
    assert spans.idle_gaps([(0, 10), (50, 60)], 0, 100) == [(10, 50),
                                                             (60, 100)]
    assert spans.idle_gaps([(5, 10)], 0, 10) == [(0, 5)]
    assert spans.idle_gaps([], 0, 10) == [(0, 10)]


def test_traced_service_run_holds_the_program_spans(tmp_path):
    """A tiny ``spec17.svc_closed`` run under the CPU profiler: the
    request path's spans sit in the trace under their exact names inside
    the window, with their ids as stats, and the span readers report."""
    from jax.profiler import ProfileData
    cell = "spec17.svc_closed"
    bench, conf, cfg = tiny(cell)
    out = runner.run_cell(cell, 2**33 + 11, 1.0, True, time.time(),
                          bench=bench, conf=conf, cfg=cfg,
                          keep_trace=str(tmp_path))
    assert out["correct"], out["checks"]
    for metric in ("svc.wait_share.closed", "svc.queue_depth.closed",
                   "rt.index_us_per_clip.svc",
                   "batch.dedup_us_per_clip.svc"):
        assert out["metrics"][metric]["value"] >= 0, metric
    assert out["metrics"]["rt.index_us_per_clip.svc"]["value"] > 0

    path = tr.find_xplane(str(tmp_path))
    ex = tr.extract(path)
    lo, hi = tr.window_of(ex)
    inside = {e.name for e in ex.host if lo <= e.start_ns < hi}
    assert {"svc.flush", "rt.index", "predict.dedup", "svc.attempt",
            "svc.wait"} <= inside
    stats = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in tr._events(line, True):
                    if lo <= e.start_ns < hi:
                        stats.setdefault(e.name, []).append(dict(e.stats))
    for name in ("svc.flush", "rt.index", "predict.dedup"):
        assert all(s["id"] > 0 and "parent" in s for s in stats[name])
    flush_ids = {s["id"] for s in stats["svc.flush"]}
    assert all(s["flush"] > 0 and s["requests"] > 0 and s["clips"] > 0
               for s in stats["svc.flush"])
    assert all(s["parent"] in flush_ids for s in stats["svc.attempt"]
               if s["parent"])
