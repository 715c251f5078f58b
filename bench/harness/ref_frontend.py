"""Reference front end: vocabulary, Fig-5 standardization, fixed clip
slicing and the Table-I context matrix, written plainly from the paper's
rules and kept with the benchmark.

It turns a reference trace (``ref_isa``) into exactly the tensors the
predictor consumes: ``(n, l_clip, l_token)`` token ids, ``(n, M)``
context ids and ``(n, l_clip)`` masks.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Tuple

import numpy as np

from harness.ref_isa import CONTEXT_REGS

INT, MUL, DIV, FP, FDIV, LSU, BR = "int", "mul", "div", "fp", "fdiv", "lsu", "br"


@dataclasses.dataclass(frozen=True)
class OpInfo:
    fu: str
    latency: int
    is_load: bool = False
    is_store: bool = False
    is_branch: bool = False
    writes_cr: bool = False
    writes_lr: bool = False
    uses_ctr: bool = False


OPCODES = {
    # integer ALU
    "addi":   OpInfo(INT, 1),
    "add":    OpInfo(INT, 1),
    "subf":   OpInfo(INT, 1),
    "neg":    OpInfo(INT, 1),
    "and":    OpInfo(INT, 1),
    "or":     OpInfo(INT, 1),
    "xor":    OpInfo(INT, 1),
    "rldicl": OpInfo(INT, 1),   # rotate-left + clear (shift family)
    "sld":    OpInfo(INT, 1),
    "srd":    OpInfo(INT, 1),
    "extsw":  OpInfo(INT, 1),
    # integer mul/div
    "mulld":  OpInfo(MUL, 5),
    "mulhd":  OpInfo(MUL, 5),
    "divd":   OpInfo(DIV, 20),
    "modsd":  OpInfo(DIV, 22),
    # compares (write CR)
    "cmpi":   OpInfo(INT, 1, writes_cr=True),
    "cmpl":   OpInfo(INT, 1, writes_cr=True),
    "cmpd":   OpInfo(INT, 1, writes_cr=True),
    # loads
    "ld":     OpInfo(LSU, 2, is_load=True),
    "lwz":    OpInfo(LSU, 2, is_load=True),
    "lbz":    OpInfo(LSU, 2, is_load=True),
    "lfd":    OpInfo(LSU, 3, is_load=True),
    # stores
    "std":    OpInfo(LSU, 1, is_store=True),
    "stw":    OpInfo(LSU, 1, is_store=True),
    "stb":    OpInfo(LSU, 1, is_store=True),
    "stfd":   OpInfo(LSU, 1, is_store=True),
    # floating point (VSR)
    "fadd":   OpInfo(FP, 4),
    "fsub":   OpInfo(FP, 4),
    "fmul":   OpInfo(FP, 4),
    "fmadd":  OpInfo(FP, 5),
    "fdiv":   OpInfo(FDIV, 25),
    "fsqrt":  OpInfo(FDIV, 30),
    "fcmpu":  OpInfo(FP, 2, writes_cr=True),
    "fmr":    OpInfo(FP, 1),
    # branches
    "b":      OpInfo(BR, 1, is_branch=True),
    "bc":     OpInfo(BR, 1, is_branch=True),           # conditional on CR
    "bl":     OpInfo(BR, 1, is_branch=True, writes_lr=True),
    "blr":    OpInfo(BR, 1, is_branch=True),
    "bdnz":   OpInfo(BR, 1, is_branch=True, uses_ctr=True),
    # move to/from special regs
    "mtctr":  OpInfo(INT, 1),
    "mtlr":   OpInfo(INT, 1),
    "mflr":   OpInfo(INT, 1),
    "nop":    OpInfo(INT, 1),
}

REGS = (tuple(f"R{i}" for i in range(32)) + tuple(f"F{i}" for i in range(32))
        + ("CR", "LR", "CTR", "XER", "FPSCR", "VSCR", "CIA", "NIA"))

PAD = "<PAD>"
REP = "<REP>"
END = "<END>"
OPCODE = "<OPCODE>"
DSTS, DSTS_E = "<DSTS>", "</DSTS>"
SRCS, SRCS_E = "<SRCS>", "</SRCS>"
MEM, MEM_E = "<MEM>", "</MEM>"
CONST = "<CONST>"
SPECIAL_TOKENS = (PAD, REP, END, OPCODE, DSTS, DSTS_E, SRCS, SRCS_E,
                  MEM, MEM_E, CONST)
BYTE_TOKENS = tuple(f"<B{b:02X}>" for b in range(256))
CORE = "<CORE>"

VOCAB: Dict[str, int] = {t: i for i, t in enumerate(
    SPECIAL_TOKENS + tuple(sorted(OPCODES)) + REGS + BYTE_TOKENS + (CORE,))}
TOKENS_PER_REG = 9


def standardize(inst) -> List[str]:
    """Fig 5 transformation with implicit-register insertion (Fig 5c)."""
    info = OPCODES[inst.op]
    toks = [REP, OPCODE, inst.op]

    dsts = list(inst.dsts)
    if info.writes_cr and "CR" not in dsts:
        dsts.append("CR")
    if info.writes_lr and "LR" not in dsts:
        dsts.append("LR")
    if info.uses_ctr and "CTR" not in dsts:
        dsts.append("CTR")
    if info.is_branch and "NIA" not in dsts:
        dsts.append("NIA")
    if dsts:
        toks.append(DSTS)
        toks.extend(dsts)
        toks.append(DSTS_E)

    srcs = list(inst.srcs)
    if inst.op == "bc" and "CR" not in srcs:
        srcs.append("CR")
    if info.uses_ctr and "CTR" not in srcs:
        srcs.append("CTR")
    if inst.op == "blr" and "LR" not in srcs:
        srcs.append("LR")
    if info.is_branch and "CIA" not in srcs:
        srcs.append("CIA")
    has_const = inst.imm is not None or (info.is_branch and
                                         inst.target is not None)
    if srcs or has_const:
        toks.append(SRCS)
        toks.extend(srcs)
        if has_const:
            toks.append(CONST)
        toks.append(SRCS_E)

    if inst.mem_base is not None:
        toks.append(MEM)
        toks.append(inst.mem_base)
        toks.append(CONST)
        toks.append(MEM_E)

    toks.append(END)
    return toks


def token_row(inst, l_token: int) -> np.ndarray:
    """(l_token,) int32 ids of ``standardize(inst)``, <PAD>(=0)-padded."""
    ids = [VOCAB[t] for t in standardize(inst)]
    assert len(ids) <= l_token, standardize(inst)
    out = np.zeros(l_token, np.int32)
    out[:len(ids)] = ids
    return out


def token_table(program, l_token: int) -> np.ndarray:
    """(n_static, l_token) token rows of a program's instructions."""
    return np.stack([token_row(i, l_token) for i in program])


def clip_tokens(table: np.ndarray, pcs: np.ndarray, l_min: int,
                l_clip: int) -> Tuple[np.ndarray, np.ndarray]:
    """Fixed slicing: consecutive windows of ``l_min`` dynamic
    instructions (the last one shorter), each truncated/padded to
    ``l_clip`` rows.  Returns (tokens (n, l_clip, l_token), mask)."""
    n = len(pcs)
    n_clips = -(-n // l_min)
    tok = np.zeros((n_clips, l_clip, table.shape[1]), np.int32)
    mask = np.zeros((n_clips, l_clip), np.float32)
    for c in range(n_clips):
        body = pcs[c * l_min:min((c + 1) * l_min, n)][:l_clip]
        tok[c, :len(body)] = table[body]
        mask[c, :len(body)] = 1.0
    return tok, mask


def context_ids(snaps: np.ndarray, core_id: Optional[int] = None
                ) -> np.ndarray:
    """Table I context matrix ids for (n, 40) register snapshots: per
    register its name token then the 8 value bytes, most significant
    first; a core-tagged layout appends a ``<CORE>`` row carrying the
    core id.  Returns (n, M) int32."""
    snaps = np.asarray(snaps, np.uint64).reshape(-1, len(CONTEXT_REGS))
    names = list(CONTEXT_REGS)
    if core_id is not None:
        snaps = np.concatenate(
            [snaps, np.full((len(snaps), 1), core_id, np.uint64)], axis=1)
        names.append(CORE)
    shifts = np.arange(56, -8, -8, dtype=np.uint64)
    vals = ((snaps[:, :, None] >> shifts) & np.uint64(0xFF)).astype(np.int64)
    out = np.empty(vals.shape[:2] + (TOKENS_PER_REG,), np.int32)
    out[:, :, 0] = [VOCAB[r] for r in names]
    out[:, :, 1:] = vals + VOCAB[BYTE_TOKENS[0]]
    return out.reshape(len(snaps), -1)


def clip_contexts(snaps: np.ndarray, n_clips: int,
                  core_id: Optional[int] = None) -> np.ndarray:
    """One context row per clip: the snapshot before the clip's first
    instruction (the last one for clips past the final snapshot)."""
    rows = context_ids(snaps, core_id)
    return rows[np.minimum(np.arange(n_clips), len(rows) - 1)]
