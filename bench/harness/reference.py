"""Reference derivation of the clips a simulation feeds the predictor,
from the benchmark's own programs, interpreter and front end."""
from __future__ import annotations

from typing import Dict, List, Tuple

import numpy as np

from harness import programs, ref_frontend as rf, ref_isa

Clips = Tuple[np.ndarray, np.ndarray, np.ndarray]   # tokens, context, mask


def _cat(parts: List[Clips]) -> Clips:
    return tuple(np.concatenate([p[i] for p in parts]) for i in range(3))


def single_core_intervals(spec: programs.ProgramSpec, eng: Dict,
                          n_intervals: int) -> List[Clips]:
    """Warm-up, then up to ``n_intervals`` intervals, each restarting at
    pc 0 on the carried state; one context row per clip."""
    st = ref_isa.MachineState.fresh()
    spec.setup(st)
    ref_isa.run(spec.program, eng["warmup"], st)
    table = rf.token_table(spec.program, eng["l_token"])
    parts = []
    for _ in range(min(spec.ckp_num, n_intervals)):
        pcs, snaps = ref_isa.run(spec.program, eng["interval_size"], st,
                                 snapshot_every=eng["l_min"])
        if not pcs:
            break
        pcs, snaps = ref_isa.as_arrays(pcs, snaps)
        tok, mask = rf.clip_tokens(table, pcs, eng["l_min"], eng["l_clip"])
        parts.append((tok, rf.clip_contexts(snaps, len(tok)), mask))
    return parts


def single_core(spec: programs.ProgramSpec, eng: Dict) -> Clips:
    """All clips the engine cuts from one program."""
    return _cat(single_core_intervals(spec, eng, eng["max_checkpoints"]))


def multicore_segments(name: str, n_cores: int, ckp_num: int, quantum: int,
                       eng: Dict) -> List[Tuple[int, Clips]]:
    """(core, clips) of ``mt.<kind>`` per interval and core, interval by
    interval, under the round-robin schedule, with core-tagged contexts:
    the order in which a multicore pass feeds the predictor."""
    progs = programs.build_multicore(name, n_cores)
    mem: Dict[int, int] = {}
    programs.mt_setup_memory(mem, n_cores, programs.MT_SEEDS[name])
    states = [ref_isa.MachineState.fresh(mem) for _ in range(n_cores)]
    if eng["warmup"]:
        ref_isa.run_multicore(progs, eng["warmup"], states, quantum)
    tables = [rf.token_table(p, eng["l_token"]) for p in progs]
    out: List[Tuple[int, Clips]] = []
    for _ in range(min(ckp_num, eng["max_checkpoints"])):
        pcs, snaps = ref_isa.run_multicore(progs, eng["interval_size"],
                                           states, quantum,
                                           snapshot_every=eng["l_min"])
        for c in range(n_cores):
            if not pcs[c]:
                continue
            p, s = ref_isa.as_arrays(pcs[c], snaps[c])
            tok, mask = rf.clip_tokens(tables[c], p, eng["l_min"],
                                       eng["l_clip"])
            out.append((c, (tok, rf.clip_contexts(s, len(tok), c), mask)))
    return out

