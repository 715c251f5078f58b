"""Back-to-back peer-channel multicore passes: ``engine_passes``' set-up
and window (each pass a fresh ``SimulationEngine`` running
``run_multicore`` over the configuration's suite), with a configuration
whose ``engine`` turns peer channels on.  Every clip's context is then
its own core's register block and every other core's (M = N x 369).

``correct`` takes two checks of the last pass:

- ``answer_rel_err``: every clip's predicted cycles as the pass's
  predictor drains them, and each core's total, for the programs the
  seed draws, against ``ref_peer``'s derivation of the same clips and
  its query-blocked reference predictor, with the weights of the tier
  the configuration states, at the matmul precision its check states;
  the worst relative error.
- ``context_mismatch_rows``: the context rows the pass fed
  ``BatchedPredictor`` that differ from ``ref_peer``'s derivation, over
  every program.  Exact: a context has no positions and the head takes
  a mean over its rows, so a peer block in the wrong place or from the
  wrong moment can leave the answers within their limit, but not here.
"""
from __future__ import annotations

from typing import Dict, List, Tuple

import numpy as np

from harness import ref_model, ref_peer, runner, spec

_passes = spec.driver("engine_passes")
window = _passes.window
rel_errors = _passes.rel_errors


def setup(ctx: runner.Context) -> Dict:
    """``engine_passes.setup``, keeping the context rows each pass feeds
    the predictor pool (``BatchedPredictor._buffer``, wrapped as
    ``engine_passes`` wraps ``drain``; only the last pass is held)."""
    from repro.core.engine import BatchedPredictor
    buffer, drain = BatchedPredictor._buffer, BatchedPredictor.drain
    fed: Dict[str, List[np.ndarray]] = {"pass": [], "last": []}

    def _buffer(self, tok, rows, mask):
        fed["pass"].append(rows)
        return buffer(self, tok, rows, mask)

    def _drain(self):
        out = drain(self)
        fed["last"], fed["pass"] = fed["pass"], []
        return out

    def restore():
        BatchedPredictor._buffer = buffer
        BatchedPredictor.drain = drain

    BatchedPredictor._buffer, BatchedPredictor.drain = _buffer, _drain
    try:
        state = _passes.setup(ctx)
    except BaseException:
        restore()
        raise
    state.update(fed=fed, restore_fed=restore, segments={}, reference={})
    return state


def _segments(state: Dict, name: str) -> List[Tuple[int, tuple]]:
    if name not in state["segments"]:
        conf = state["ctx"].conf
        suite = conf["suite"]
        state["segments"][name] = ref_peer.peer_segments(
            name, suite["n_cores"], suite["ckp_num"], suite["quantum"],
            dict(conf["engine"], max_checkpoints=conf["max_checkpoints"]))
    return state["segments"][name]


def _reference(state: Dict, name: str, precision: str) -> List[np.ndarray]:
    """The reference's cycles per segment of ``name`` at ``precision``,
    with the weights of the stated tier."""
    key = (name, precision)
    if key not in state["reference"]:
        ctx = state["ctx"]
        params = ref_model.fake_quant(
            ctx.params, ctx.conf["engine_tier"]["weight_bits"])
        state["reference"][key] = [
            ref_peer.predict(params, *clips, ctx.model["num_heads"],
                             precision)
            for _, clips in _segments(state, name)]
    return state["reference"][key]


def answers(state: Dict, precision: str) -> List[Tuple[str, np.ndarray,
                                                        np.ndarray]]:
    """(key, program's answers, reference's answers) for the programs the
    seed draws: per clip, then each core's total."""
    ctx, last, drained = state["ctx"], state["last"], state["answers"]
    order = list(ctx.conf["suite"]["programs"])
    n_clips = {n: sum(v[1] for k, v in last.items()
                      if k.split("#")[0] == n) for n in order}
    offset = dict(zip(order, np.cumsum([0] + [n_clips[n]
                                               for n in order])))
    pick = np.random.default_rng([ctx.seed, 1]).choice(
        len(order), size=min(ctx.conf["check"]["programs"], len(order)),
        replace=False)
    out = []
    for name in sorted(order[i] for i in pick):
        segs = _segments(state, name)
        want = _reference(state, name, precision)
        got = drained[offset[name]:offset[name] + n_clips[name]]
        if len(got) != sum(len(w) for w in want):
            runner.log(f"[check] {name} clips={len(got)} reference has "
                       f"{sum(len(w) for w in want)}")
            out.append((name, np.array([np.inf]), np.array([1.0])))
            continue
        out.append((name, np.asarray(got, np.float64),
                    np.concatenate(want)))
        totals: Dict[str, float] = {}
        for (core, _), w in zip(segs, want):
            k = f"{name}#c{core}"
            totals[k] = totals.get(k, 0.0) + float(w.sum())
        keys = sorted(totals)
        out.append((name + " totals",
                    np.array([last[k][2] for k in keys]),
                    np.array([totals[k] for k in keys])))
    return out


def context_mismatches(state: Dict) -> int:
    """Rows of the last pass's fed contexts that differ from the
    reference's, in feed order over every program; a row missing on
    either side counts."""
    order = state["ctx"].conf["suite"]["programs"]
    want = np.concatenate([clips[1] for name in order
                           for _, clips in _segments(state, name)])
    fed = state["fed"]["last"]
    got = np.concatenate(fed) if fed else np.zeros((0, want.shape[1]),
                                                   want.dtype)
    if got.shape[1] != want.shape[1]:
        return max(len(got), len(want))
    n = min(len(got), len(want))
    return (int(np.any(got[:n] != want[:n], axis=1).sum())
            + abs(len(got) - len(want)))


def check(state: Dict, win: runner.Window) -> List[runner.Check]:
    state.pop("restore")()
    state.pop("restore_fed")()
    conf = state["ctx"].conf["check"]
    worst = 0.0
    for key, got, want in answers(state, conf["precision"]):
        err = rel_errors(got, want)
        e = float(err.max()) if np.isfinite(err).all() else float("inf")
        runner.log(f"[check] {key} answers={len(got)} "
                   f"program_sum={float(got.sum())!r} "
                   f"reference_sum={float(want.sum())!r} worst_rel_err={e!r}")
        worst = max(worst, e)
    rows = context_mismatches(state)
    fed = sum(len(f) for f in state["fed"]["last"])
    runner.log(f"[check] context rows fed={fed} mismatched={rows}")
    return [runner.Check("answer_rel_err", worst, conf["limit"]),
            runner.Check("context_mismatch_rows", float(rows),
                         float(conf["context_limit"]))]
