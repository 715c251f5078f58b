"""Reference of a peer-channel multicore pass: the clips, derived from the
benchmark's own programs, interpreter and front end, and the predictor
evaluated over their long contexts.

Peer-channel layout, as the repository defines it: a clip's context is
its own core's register block tagged ``<CORE> c`` (the state before the
clip's first instruction), then, for every other core in ascending
order, that core's block tagged with its id, as it stood when the
clip's quantum began.  Inside a quantum only the running core commits,
so the peers' rows are exact anywhere in it.  N cores give M = N x 369
rows.  The schedule here is the benchmark's own round-robin loop, which
captures every core's registers at the start of each quantum that
snapshots a clip start.

The predictor is ``ref_model``'s equations with one change of order:
the block encoder's self-attention over the M context rows runs in
blocks of ``Q_BLOCK`` queries, each block's softmax over all M keys.
Each query row's softmax and weighted sum are the same arithmetic as in
``ref_model.clip_cycles``; only the scores of the other blocks are not
held at the same time.  This is exact, not an approximation: at
M = 5,904 the unblocked scores of 64 clips would take ~36 GB.
"""
from __future__ import annotations

import math
from functools import lru_cache
from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from harness import programs, ref_frontend as rf, ref_isa, ref_model
from harness.reference import Clips

Q_BLOCK = 512
BLOCK_CLIPS = 16


def _round_robin(progs: Sequence[list], max_per_core: int,
                 states: List[ref_isa.MachineState], quantum: int,
                 snapshot_every: int = 0):
    """``ref_isa.run_multicore``'s schedule, and for every snapshot a
    core takes, the whole machine's registers at its quantum's start:
    per core (pcs, snapshots, captures (n, n_cores, 40))."""
    n = len(progs)
    pcs: List[List[int]] = [[] for _ in range(n)]
    snaps: List[List[List[int]]] = [[] for _ in range(n)]
    caps: List[List[List[List[int]]]] = [[] for _ in range(n)]
    done, pc, active = [0] * n, [0] * n, [True] * n
    while True:
        progressed = False
        for c in range(n):
            if not active[c] or done[c] >= max_per_core:
                continue
            q = min(quantum, max_per_core - done[c])
            machine = None
            if snapshot_every and (-done[c]) % snapshot_every < q:
                machine = [st.snapshot() for st in states]
            p, s = ref_isa.run(progs[c], q, states[c], snapshot_every,
                               start_pc=pc[c], done=done[c])
            if p:
                pcs[c].extend(p)
                snaps[c].extend(s)
                caps[c].extend([machine] * len(s))
                done[c] += len(p)
                pc[c] = states[c].regs["NIA"]
                progressed = True
            if len(p) < q:
                active[c] = False
        if not progressed:
            return pcs, snaps, caps


def peer_contexts(own: np.ndarray, machine: np.ndarray, core: int,
                  n_clips: int) -> np.ndarray:
    """(n_clips, n_cores * 369) ids: ``own`` (n, 40) snapshots of
    ``core``, ``machine`` (n, n_cores, 40) quantum-start captures."""
    blocks = [rf.context_ids(own, core)]
    blocks += [rf.context_ids(machine[:, p], p)
               for p in range(machine.shape[1]) if p != core]
    rows = np.concatenate(blocks, axis=1)
    return rows[np.minimum(np.arange(n_clips), len(rows) - 1)]


def peer_segments(name: str, n_cores: int, ckp_num: int, quantum: int,
                  eng: Dict) -> List[Tuple[int, Clips]]:
    """(core, clips) of ``mt.<kind>`` per interval and core, in the order
    a peer-channel multicore pass feeds the predictor."""
    progs = programs.build_multicore(name, n_cores)
    mem: Dict[int, int] = {}
    programs.mt_setup_memory(mem, n_cores, programs.MT_SEEDS[name])
    states = [ref_isa.MachineState.fresh(mem) for _ in range(n_cores)]
    if eng["warmup"]:
        _round_robin(progs, eng["warmup"], states, quantum)
    tables = [rf.token_table(p, eng["l_token"]) for p in progs]
    out: List[Tuple[int, Clips]] = []
    for _ in range(min(ckp_num, eng["max_checkpoints"])):
        pcs, snaps, caps = _round_robin(progs, eng["interval_size"], states,
                                        quantum, eng["l_min"])
        for c in range(n_cores):
            if not pcs[c]:
                continue
            p, s = ref_isa.as_arrays(pcs[c], snaps[c])
            tok, mask = rf.clip_tokens(tables[c], p, eng["l_min"],
                                       eng["l_clip"])
            machine = np.asarray(caps[c], np.uint64)
            out.append((c, (tok, peer_contexts(s, machine, c, len(tok)),
                            mask)))
    return out


def self_attention(p, h, heads: int, hi, q_block: int = Q_BLOCK):
    """The block encoder's context self-attention, ``ref_model._mha`` with
    the queries taken ``q_block`` at a time; (B, M, E) -> (B, M, E)."""
    B, M, _ = h.shape
    hd = p["self_wq"].shape[-1] // heads
    q = jnp.matmul(h, p["self_wq"], precision=hi).reshape(B, M, heads, hd)
    k = jnp.matmul(h, p["self_wk"], precision=hi).reshape(B, M, heads, hd)
    v = jnp.matmul(h, p["self_wv"], precision=hi).reshape(B, M, heads, hd)
    nb = -(-M // q_block)
    q = jnp.pad(q, ((0, 0), (0, nb * q_block - M), (0, 0), (0, 0)))
    q = jnp.moveaxis(q.reshape(B, nb, q_block, heads, hd), 1, 0)

    def block(qb):
        s = jnp.einsum("bqhd,bkhd->bhqk", qb, k, precision=hi) \
            / math.sqrt(hd)
        a = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", a, v, precision=hi)

    o = jnp.moveaxis(jax.lax.map(block, q), 0, 1)
    o = o.reshape(B, nb * q_block, heads * hd)[:, :M]
    return jnp.matmul(o, p["self_wo"], precision=hi)


def clip_cycles(params, rt, ctx, clip_mask, heads: int, hi,
                q_block: int = Q_BLOCK):
    """``ref_model.clip_cycles`` with the context self-attention in query
    blocks: rt (B, L, E), ctx (B, M) ids, clip_mask (B, L) -> (B,)."""
    rt = rt + ref_model._sinusoidal(rt.shape[1], rt.shape[2])[None]
    x = params["embed"][ctx]
    for i in range(params["block"]["self_wq"].shape[0]):
        p = ref_model._layer(params["block"], i)
        x = x + self_attention(p, ref_model._norm(x, p["norm1"]), heads,
                               hi, q_block)
        x = x + ref_model._mha(p, "cross_", ref_model._norm(x, p["norm2"]),
                               rt, heads, hi, clip_mask)
        x = x + ref_model._ffn(p, ref_model._norm(x, p["norm3"]), hi)
    h = ref_model._norm(x, params["final_norm"])
    hw = params["head"]
    h = jax.nn.gelu(jnp.matmul(h, hw["w1"], precision=hi) + hw["b1"])
    y = (jnp.matmul(h, hw["w2"], precision=hi) + hw["b2"])[..., 0]
    n_inst = jnp.maximum(clip_mask.sum(-1), 1.0)
    return jax.nn.softplus(jnp.mean(y, axis=-1)) * n_inst


@lru_cache(maxsize=None)
def _jitted(heads: int, precision: str):
    hi = ref_model.PRECISIONS[precision]
    return (jax.jit(lambda p, r: ref_model.encode_rows(p, r, heads, hi)),
            jax.jit(lambda p, rt, c, m: clip_cycles(p, rt, c, m, heads,
                                                    hi)))


def predict(params, clip_tokens: np.ndarray, ctx: np.ndarray,
            clip_mask: np.ndarray, heads: int,
            precision: str = "highest") -> np.ndarray:
    """Per-clip cycles of the reference, ``BLOCK_CLIPS`` clips a call."""
    enc, blk = _jitted(heads, precision)
    n, L, T = clip_tokens.shape
    rows, inv = np.unique(clip_tokens.reshape(n * L, T), axis=0,
                          return_inverse=True)
    pad = np.zeros((ref_model._bucket(len(rows)) - len(rows), T),
                   rows.dtype)
    table = enc(params, jnp.asarray(np.concatenate([rows, pad])))
    inv = inv.reshape(n, L)
    out = []
    for lo in range(0, n, BLOCK_CLIPS):
        hi = min(lo + BLOCK_CLIPS, n)
        k = BLOCK_CLIPS - (hi - lo)
        idx = np.concatenate([inv[lo:hi], np.zeros((k, L), inv.dtype)])
        c = np.concatenate([ctx[lo:hi],
                            np.zeros((k,) + ctx.shape[1:], ctx.dtype)])
        m = np.concatenate([clip_mask[lo:hi],
                            np.zeros((k, L), clip_mask.dtype)])
        out.append(blk(params, table[jnp.asarray(idx)], jnp.asarray(c),
                       jnp.asarray(m))[:hi - lo])
    return np.concatenate([np.asarray(y) for y in out]).astype(np.float64)
