"""Closed loop against ``SimulationService``: ``clients`` callers, one
thread each, each keeping one request in flight and sending its next as
soon as its answer comes.  Requests go through seeded permutations of
the pool, one after another, so every seed sends the same work in its
own order.

End-to-end: clips of requests answered ``ok`` by the end of the window,
over the window.  ``correct``: see ``harness.service.check``.
"""
from __future__ import annotations

import threading
import time
from typing import Dict, List

from harness import runner, service


def setup(ctx: runner.Context) -> Dict:
    return service.start(ctx)


def window(state: Dict, seconds: float) -> runner.Window:
    ctx, svc, reqs = state["ctx"], state["svc"], state["reqs"]
    draws = reqs.draws(ctx.rng)
    lock = threading.Lock()
    done: List = []                # (request id, result) answered in time

    def client(end: float) -> None:
        while time.time() < end:
            with lock:
                req = reqs.make(next(draws))
            res = svc.submit(req).result(timeout=600)
            if time.time() <= end:
                done.append((req.request_id, res))

    t0 = time.time()
    threads = [threading.Thread(target=client, args=(t0 + seconds,),
                                name=f"bench-client-{i}")
               for i in range(ctx.traffic["clients"])]
    for t in threads:
        t.start()
    for t in threads:              # each ends on its first answer after
        t.join()                   # the window, which is not counted
    wall = seconds
    failed = sum(1 for _, res in done if not res.ok)
    clips = sum(res.n_clips for _, res in done if res.ok)
    state["done"] = done
    return runner.Window(seconds=wall, e2e={"svc_clips_per_s": clips / wall},
                         attempted=len(done), failed=failed,
                         extra=reqs.served(done))


def check(state: Dict, win: runner.Window) -> List[runner.Check]:
    return service.check(state)
