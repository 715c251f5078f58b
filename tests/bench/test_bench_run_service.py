"""The service cells run end to end on the CPU at tiny sizes, with the
cells' own check samples: correct as they stand, not correct with the
served path broken underneath or with the int4 control served; the
closed loop keeps every client's request in flight."""
import copy
import random
import threading
import time

import pytest
from _tiny import control_tool, patch, run, tiny
from harness import faults, spec

SERVICE_CELLS = [w["name"] for w in spec.benchmark()["workloads"]
                 if spec.traffic(w["traffic"])["kind"].startswith("service")]


@pytest.mark.parametrize("cell", SERVICE_CELLS)
def test_tiny_service_cell_is_correct(cell):
    res = run(cell)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert list(res)[-1] == "checks"


@pytest.mark.parametrize("fault", sorted(faults.FAULTS))
def test_tiny_service_cell_catches_fault(monkeypatch, fault):
    patch(monkeypatch, fault)
    res = run(SERVICE_CELLS[0])
    assert not res["correct"], res["checks"]


def test_a_cell_added_as_entries_runs_without_harness_edits():
    """A second configuration entry and a cell over it, added to
    BENCHMARK.json only: the harness finds both by name."""
    bench = copy.deepcopy(spec.benchmark())
    base = spec.cell(bench, SERVICE_CELLS[0])
    entry = next(c for c in bench["configs"] if c["name"] == base["config"])
    bench["configs"].append(dict(entry, name="capsim_spec17_b"))
    bench["workloads"].append(dict(base, name="spec17_b.svc_closed",
                                   config="capsim_spec17_b"))
    for m in bench["end_to_end"] + bench["per_layer"]:
        if base["name"] in m.get("workloads", ()):
            m["workloads"].append("spec17_b.svc_closed")
    res = run("spec17_b.svc_closed", bench=bench)
    assert res["correct"], res["checks"]
    assert set(res["metrics"]) == {"svc_clips_per_s", "setup_s"}
    assert res["attempted"] > 0 and res["failed"] == 0


def test_int4_control_fails_the_limit():
    """The control: the service serves the weights on the int4 grid, and
    the cell's own check compares its answers with the reference of the
    stated int8 weights."""
    bench, conf, cfg = tiny(SERVICE_CELLS[0])
    tool = control_tool()
    checks = {c.name: c for c in tool.service_reading(
        SERVICE_CELLS[0], conf, 5, bench, "int4", 1.0, cfg=cfg)}
    assert not all(c.ok for c in checks.values()), checks
    assert checks["req_rel_err"].value > conf["service_check"]["limit"]


class _Ticket:
    def __init__(self):
        self._event = threading.Event()
        self._result = None

    def result(self, timeout=None):
        assert self._event.wait(timeout)
        return self._result


class _Result:
    ok = True
    n_clips = 1


class _SlowService:
    """Answers each request after a random delay, out of order."""

    def __init__(self):
        self.lock = threading.Lock()
        self.in_flight = 0
        self.samples = []

    def submit(self, req):
        ticket = _Ticket()
        with self.lock:
            self.in_flight += 1

        def answer(delay):
            time.sleep(delay)
            with self.lock:
                self.in_flight -= 1
            ticket._result = _Result()
            ticket._event.set()
        threading.Thread(target=answer,
                         args=(random.uniform(0.02, 0.06),)).start()
        return ticket

    def watch(self, seconds):
        end = time.time() + seconds
        while time.time() < end:
            with self.lock:
                self.samples.append(self.in_flight)
            time.sleep(0.0005)


def test_closed_loop_keeps_every_client_in_flight():
    import numpy as np
    drv = spec.driver("service_closed")

    class Reqs:
        sent = {}

        def draws(self, rng):
            while True:
                yield 0

        def make(self, i):
            class R:
                request_id = len(self.sent)
            self.sent[R.request_id] = i
            return R

        def served(self, done):
            return {}

    svc = _SlowService()
    ctx = type("Ctx", (), {"rng": np.random.default_rng(0),
                           "traffic": {"clients": 16}})
    watcher = threading.Thread(target=svc.watch, args=(1.0,))
    watcher.start()
    win = drv.window({"ctx": ctx, "svc": svc, "reqs": Reqs()}, 1.2)
    watcher.join()
    assert win.attempted > 100
    assert max(svc.samples) == 16
    assert np.mean(svc.samples[len(svc.samples) // 4:]) > 14.5
