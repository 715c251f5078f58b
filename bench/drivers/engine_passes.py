"""Back-to-back simulation passes: each pass is a fresh
``SimulationEngine`` over the configuration's whole program suite, as one
user's process simulating it, in the configuration's order.  The seed
makes the weights and draws the programs that are checked; every seed
simulates the same programs and intervals.

Single-core suites go through ``SimulationEngine.run``; a configuration
with ``n_cores`` > 1 goes through ``run_multicore``.  The window runs to
the end of the pass in progress when ``seconds`` have passed.

``correct``: the answers of the last pass for a sample of programs drawn
from the seed -- every clip's predicted cycles, as the pass's predictor
drains them, and each program's (each core's) predicted total -- against
the benchmark's reference derivation of the same clips and its plain
reference model, with the weights of the tier the configuration states,
at the matmul precision its check states.  The number is the worst
relative error over those answers.
"""
from __future__ import annotations

import time
from typing import Dict, List, Tuple

import numpy as np

from harness import programs, ref_model, reference, runner


def _engine_config(conf: Dict):
    from repro.core.engine_config import EngineConfig
    return EngineConfig(**conf["engine"],
                        precision=conf["engine_tier"]["precision"],
                        max_checkpoints=conf["max_checkpoints"])


def setup(ctx: runner.Context) -> Dict:
    from repro.core.standardize import build_vocab
    from repro.isa import multicore, progen

    suite = ctx.conf["suite"]
    order = list(suite["programs"])
    n_cores = suite.get("n_cores", 1)
    if n_cores > 1:
        benches = [multicore.MulticoreBenchmark(
            name=n, kind=n.split(".", 1)[1], n_cores=n_cores,
            ckp_num=suite["ckp_num"], seed=programs.MT_SEEDS[n],
            programs=programs.build_multicore(n, n_cores)) for n in order]
    else:
        benches = []
        for n in order:
            p = programs.build_benchmark(n)
            benches.append(progen.Benchmark(
                name=p.name, tags=p.tags, set_no=p.set_no,
                ckp_num=p.ckp_num, program=p.program, setup=p.setup))
    state = {"ctx": ctx, "benches": benches, "n_cores": n_cores,
             "config": _engine_config(ctx.conf), "vocab": build_vocab()}
    _record_drains(state)
    with ctx.annotate("bench.warm_pass"):
        _pass(state)
    return state


def _record_drains(state: Dict) -> None:
    """Keep what each pass's predictor drains: every clip's predicted
    cycles in the order the pass fed them (one drain per pass).  The
    program's method runs unchanged; ``state["restore"]`` undoes this."""
    from repro.core.engine import BatchedPredictor
    orig = BatchedPredictor.drain

    def drain(self):
        out = orig(self)
        state["drained"] = out
        return out
    BatchedPredictor.drain = drain
    state["restore"] = lambda: setattr(BatchedPredictor, "drain", orig)


def _pass(state: Dict):
    from repro.core.engine import SimulationEngine
    ctx = state["ctx"]
    eng = SimulationEngine.from_config(ctx.params, ctx.cfg, state["vocab"],
                                       state["config"])
    if state["n_cores"] > 1:
        res = eng.run_multicore(state["benches"],
                                quantum=ctx.conf["suite"]["quantum"])
        out = {f"{r.name}#c{k}": c for r in res
               for k, c in enumerate(r.cores)}
    else:
        out = {r.name: r for r in eng.run(state["benches"])}
    return {k: (r.n_instructions, r.n_clips, float(r.predicted_cycles))
            for k, r in out.items()}, state.pop("drained")


def window(state: Dict, seconds: float) -> runner.Window:
    ctx = state["ctx"]
    t0 = time.time()
    passes, instr, clips, last, failed = 0, 0, 0, None, 0
    while True:
        with ctx.annotate("bench.pass"):
            last, drained = _pass(state)
        passes += 1
        instr += sum(v[0] for v in last.values())
        clips += sum(v[1] for v in last.values())
        failed += sum(1 for v in last.values() if not np.isfinite(v[2]))
        if time.time() - t0 >= seconds:
            break
    wall = time.time() - t0
    state["last"], state["answers"] = last, drained
    return runner.Window(seconds=wall, e2e={"sim_instr_per_s": instr / wall},
                         attempted=passes * len(last), failed=failed,
                         extra={"clips": clips})


def _reference_clips(state: Dict, name: str) -> List[Tuple[str, tuple]]:
    """(key, clips) of one program in the order the pass fed them: one
    entry for a single-core program, one per interval and core for a
    multicore one (key ``<name>#c<core>``)."""
    conf = state["ctx"].conf
    eng = dict(conf["engine"], max_checkpoints=conf["max_checkpoints"])
    suite = conf["suite"]
    if state["n_cores"] == 1:
        return [(name, reference.single_core(
            programs.build_benchmark(name), eng))]
    return [(f"{name}#c{c}", clips) for c, clips in
            reference.multicore_segments(name, state["n_cores"],
                                         suite["ckp_num"], suite["quantum"],
                                         eng)]


def answers(state: Dict, precision: str) -> List[Tuple[str, np.ndarray,
                                                        np.ndarray]]:
    """(key, program's answers, reference's answers) for the programs the
    seed draws: per clip, then each program's (core's) total."""
    ctx, last, drained = state["ctx"], state["last"], state["answers"]
    order = list(ctx.conf["suite"]["programs"])
    n_clips = {n: sum(v[1] for k, v in last.items()
                      if k.split("#")[0] == n) for n in order}
    offset = dict(zip(order, np.cumsum([0] + [n_clips[n]
                                               for n in order])))
    pick = np.random.default_rng([ctx.seed, 1]).choice(
        len(order), size=min(ctx.conf["check"]["programs"], len(order)),
        replace=False)
    heads = ctx.model["num_heads"]
    params = ref_model.fake_quant(ctx.params,
                                  ctx.conf["engine_tier"]["weight_bits"])
    out = []
    for name in sorted(order[i] for i in pick):
        segs = _reference_clips(state, name)
        want = [ref_model.predict(params, *clips, heads, precision)
                for _, clips in segs]
        n_ref = sum(len(w) for w in want)
        got = drained[offset[name]:offset[name] + n_clips[name]]
        if len(got) != n_ref:
            runner.log(f"[check] {name} clips={len(got)} reference has "
                       f"{n_ref}")
            out.append((name, np.array([np.inf]), np.array([1.0])))
            continue
        got, want_all = np.asarray(got, np.float64), np.concatenate(want)
        out.append((name, got, want_all))
        totals: Dict[str, float] = {}
        for (key, _), w in zip(segs, want):
            totals[key] = totals.get(key, 0.0) + float(w.sum())
        keys = sorted(totals)
        out.append((name + " totals",
                    np.array([last[k][2] for k in keys]),
                    np.array([totals[k] for k in keys])))
    return out


def rel_errors(got: np.ndarray, want: np.ndarray) -> np.ndarray:
    return np.abs(got - want) / np.maximum(np.abs(want), 1.0)


def check(state: Dict, win: runner.Window) -> List[runner.Check]:
    state.pop("restore")()
    conf = state["ctx"].conf["check"]
    worst = 0.0
    for key, got, want in answers(state, conf["precision"]):
        err = rel_errors(got, want)
        e = float(err.max()) if np.isfinite(err).all() else float("inf")
        runner.log(f"[check] {key} answers={len(got)} "
                   f"program_sum={float(got.sum())!r} "
                   f"reference_sum={float(want.sum())!r} worst_rel_err={e!r}")
        worst = max(worst, e)
    return [runner.Check("answer_rel_err", worst, conf["limit"])]
