"""What the service cells share: the request pool, the service at its
default SLA and ladder, a warm-up that compiles every shape the traffic
can reach, and the comparison of answered requests with the reference.

A request is one 20,000-instruction interval of a Table II program (200
clips), tokenized by the benchmark's own front end in set-up.
"""
from __future__ import annotations

import itertools
from typing import Dict, List, Tuple

import numpy as np

from harness import programs, ref_model, reference, runner

TOP_TIER = "fused_int8"


def build_pool(conf: Dict) -> Tuple[List[reference.Clips], List[int]]:
    """The pool of requests and the index of each program's first."""
    eng = dict(conf["engine"], max_checkpoints=conf["max_checkpoints"])
    pool, firsts = [], []
    for name in conf["suite"]["programs"]:
        firsts.append(len(pool))
        pool.extend(reference.single_core_intervals(
            programs.build_benchmark(name), eng,
            conf["service"]["intervals_per_program"]))
    return pool, firsts


class Requests:
    """Program ``Request`` objects over pool entries, with fresh ids."""

    def __init__(self, pool: List[reference.Clips]):
        from repro.serving.engine import Request
        self._Request = Request
        self.pool = pool
        self._ids = itertools.count()
        self.sent: Dict[int, int] = {}          # request id -> pool index
        # unique context tokens of each pool clip (the weighted-attention
        # kernel's key count), summed per pool entry: (sum u, sum u^2)
        self.unique = [(float(u.sum()), float((u * u).sum())) for u in
                       (_unique_counts(p[1]).astype(float) for p in pool)]

    def served(self, done) -> Dict[str, float]:
        """Clips and unique-token sums of the requests answered ok."""
        ok = [self.sent[rid] for rid, res in done if res.ok]
        return {"clips": float(sum(len(self.pool[i][0]) for i in ok)),
                "sum_u": sum(self.unique[i][0] for i in ok),
                "sum_u2": sum(self.unique[i][1] for i in ok)}

    def draws(self, rng: np.random.Generator):
        """Pool indices in seeded permutations of the whole pool, one
        after another: every seed sends the same requests in each stretch
        of ``len(pool)``, in its own order."""
        while True:
            yield from (int(i) for i in rng.permutation(len(self.pool)))

    def make(self, i: int, clips: reference.Clips = None):
        rid = next(self._ids)
        tok, ctx, mask = clips if clips is not None else self.pool[i]
        self.sent[rid] = i
        return self._Request(rid, tok, ctx, mask)


def _unique_counts(ctx: np.ndarray) -> np.ndarray:
    srt = np.sort(ctx, axis=1)
    return 1 + (srt[:, 1:] != srt[:, :-1]).sum(1)


def _wait(tickets) -> None:
    for t in tickets:
        t.result(timeout=600)


def warm(svc, reqs: Requests, firsts: List[int], clients: int) -> None:
    """Serve each program's first interval (fills the RT table with the
    programs' static rows), then, for each context-dedup bucket the
    pool's rows reach, flushes of 1 to k requests built from rows of that
    bucket, k the most one flush can hold with ``clients`` in flight.
    Together these compile every (batch bucket, dedup bucket) pair and
    the spot check's shapes that traffic over this pool can produce."""
    from repro.core.standardize import dedup_bucket
    _wait([svc.submit(reqs.make(i)) for i in firsts])
    tok = np.concatenate([p[0] for p in reqs.pool])
    ctx = np.concatenate([p[1] for p in reqs.pool])
    mask = np.concatenate([p[2] for p in reqs.pool])
    n = len(reqs.pool[0][0])
    k_max = min(clients, -(-svc.sla.max_flush_clips // n))
    buckets = np.array([dedup_bucket(int(u), ctx.shape[1])
                        for u in _unique_counts(ctx)])
    for b in sorted(set(buckets.tolist())):
        rows = np.resize(np.flatnonzero(buckets == b), k_max * n)
        group = [(tok[r], ctx[r], mask[r]) for r in np.split(rows, k_max)]
        for k in range(1, k_max + 1):
            blocker = svc.submit(reqs.make(-1, group[0]))
            batch = [svc.submit(reqs.make(-1, group[j])) for j in range(k)]
            _wait([blocker] + batch)


def start(ctx: runner.Context) -> Dict:
    from repro.core.engine_config import EngineConfig
    from repro.serving.service import SimulationService
    conf = ctx.conf
    with ctx.annotate("bench.pool"):
        pool, firsts = build_pool(conf)
    reqs = Requests(pool)
    config = EngineConfig(**conf["engine"],
                          max_checkpoints=conf["max_checkpoints"])
    svc = SimulationService(ctx.params, ctx.cfg, config).start()
    if svc.current_tier != TOP_TIER:
        raise RuntimeError(f"service starts at {svc.current_tier}")
    with ctx.annotate("bench.warm"):
        warm(svc, reqs, firsts, ctx.traffic["clients"])
    return {"ctx": ctx, "svc": svc, "reqs": reqs, "done": []}


def check(state: Dict) -> List[runner.Check]:
    """A sample, drawn from the seed, of the requests answered in the
    window: each request's total cycles against the reference model with
    the served tier's int8 weights; plus answers from a lower tier."""
    ctx, reqs = state["ctx"], state["reqs"]
    svc = state.pop("svc")
    svc.stop(drain=True)
    snap = svc.snapshot()
    trips = {k: sum(t[k] for t in snap.tiers.values())
             for k in ("demotions", "promotions", "relerr_trips",
                       "nan_trips", "fault_trips", "watchdog_trips")}
    runner.log(f"[service] tier={snap.current_tier} statuses="
               f"{snap.statuses} events={trips}")
    del svc
    done: List[Tuple[int, object]] = state.pop("done")
    ok = [(rid, res) for rid, res in done if res.ok]
    not_top = sum(1 for _, res in ok if res.tier != TOP_TIER)
    k = min(ctx.conf["service_check"]["requests"], len(ok))
    rng = np.random.default_rng([ctx.seed, 1])
    pick = rng.choice(len(ok), size=k, replace=False) if ok else []
    params = ref_model.fake_quant(ctx.params,
                                  ctx.conf["service"]["weight_bits"])
    worst = 0.0 if ok else float("inf")
    for j in sorted(pick):
        rid, res = ok[j]
        tok, c, m = reqs.pool[reqs.sent[rid]]
        want = float(ref_model.predict(params, tok, c, m,
                                       ctx.model["num_heads"]).sum())
        err = abs(res.total_cycles - want) / max(abs(want), 1.0)
        runner.log(f"[check] request {rid} pool={reqs.sent[rid]} "
                   f"tier={res.tier} total={res.total_cycles!r} "
                   f"reference={want!r} rel_err={err!r}")
        worst = max(worst, err)
    return [runner.Check("req_rel_err", worst,
                         ctx.conf["service_check"]["limit"]),
            runner.Check("answers_below_top_tier", float(not_top), 0.0)]
