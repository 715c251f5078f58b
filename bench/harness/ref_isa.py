"""Reference functional simulator: a plain object interpreter over the
program's ``Instruction`` objects, one ``step`` per instruction, and the
round-robin quantum schedule of the multicore runner.

A copy of the semantics the simulator states (the golden model it keeps
for differential tests), so the benchmark's reference derivation of clips
shares no code with the engine's table-dispatched interpreter.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

GPRS = tuple(f"R{i}" for i in range(32))
SPECIALS = ("CR", "LR", "CTR", "XER", "FPSCR", "VSCR", "CIA", "NIA")
CONTEXT_REGS = GPRS + SPECIALS

MASK64 = (1 << 64) - 1


@dataclasses.dataclass
class MachineState:
    regs: Dict[str, int]
    fregs: Dict[str, float]
    mem: Dict[int, int]

    @classmethod
    def fresh(cls, mem: Optional[Dict[int, int]] = None) -> "MachineState":
        regs = {r: 0 for r in CONTEXT_REGS}
        fregs = {f"F{i}": 0.0 for i in range(32)}
        return cls(regs=regs, fregs=fregs, mem={} if mem is None else mem)

    def snapshot(self) -> List[int]:
        return [self.regs[r] & MASK64 for r in CONTEXT_REGS]


def _val(st: MachineState, name: str):
    if name.startswith("F"):
        return st.fregs[name]
    return st.regs[name]


def _setval(st: MachineState, name: str, v):
    if name.startswith("F"):
        st.fregs[name] = float(v)
    else:
        st.regs[name] = int(v) & MASK64


def _sext(v: int) -> int:
    v &= MASK64
    return v - (1 << 64) if v >> 63 else v


def step(st: MachineState, pc: int, inst
         ) -> Tuple[int, Optional[int], Optional[bool]]:
    """Execute one instruction; returns (next_pc, effective_addr, taken)."""
    op = inst.op
    s = inst.srcs
    ea = None
    taken = None
    next_pc = pc + 1
    st.regs["CIA"] = pc

    if op == "addi":
        _setval(st, inst.dsts[0], _val(st, s[0]) + inst.imm if s
                else inst.imm)
    elif op == "add":
        _setval(st, inst.dsts[0], _val(st, s[0]) + _val(st, s[1]))
    elif op == "subf":
        _setval(st, inst.dsts[0], _val(st, s[1]) - _val(st, s[0]))
    elif op == "neg":
        _setval(st, inst.dsts[0], -_val(st, s[0]))
    elif op == "and":
        _setval(st, inst.dsts[0], _val(st, s[0]) & _val(st, s[1]))
    elif op == "or":
        _setval(st, inst.dsts[0], _val(st, s[0]) | _val(st, s[1]))
    elif op == "xor":
        _setval(st, inst.dsts[0], _val(st, s[0]) ^ _val(st, s[1]))
    elif op in ("rldicl", "sld"):
        sh = inst.imm if inst.imm is not None else (_val(st, s[1]) & 63)
        _setval(st, inst.dsts[0], (_val(st, s[0]) << sh) & MASK64)
    elif op == "srd":
        sh = inst.imm if inst.imm is not None else (_val(st, s[1]) & 63)
        _setval(st, inst.dsts[0], (_val(st, s[0]) & MASK64) >> sh)
    elif op == "extsw":
        v = _val(st, s[0]) & 0xFFFFFFFF
        _setval(st, inst.dsts[0], v - (1 << 32) if v >> 31 else v)
    elif op in ("mulld", "mulhd"):
        prod = _sext(_val(st, s[0])) * _sext(_val(st, s[1]))
        _setval(st, inst.dsts[0],
                prod if op == "mulld" else (prod >> 64))
    elif op in ("divd", "modsd"):
        a, b = _sext(_val(st, s[0])), _sext(_val(st, s[1]))
        b = b if b != 0 else 1
        q, r = abs(a) // abs(b), abs(a) % abs(b)
        if (a < 0) != (b < 0):
            q = -q
        _setval(st, inst.dsts[0], q if op == "divd" else r)
    elif op in ("cmpi", "cmpl", "cmpd"):
        a = _sext(_val(st, s[0]))
        b = inst.imm if op == "cmpi" else _sext(_val(st, s[1]))
        st.regs["CR"] = (4 if a < b else (2 if a > b else 1))
    elif op == "fcmpu":
        a, b = _val(st, s[0]), _val(st, s[1])
        st.regs["CR"] = (4 if a < b else (2 if a > b else 1))
    elif op in ("ld", "lwz", "lbz"):
        ea = (_val(st, inst.mem_base) + inst.mem_offset) & MASK64
        v = st.mem.get(ea >> 3, 0)
        if op == "lwz":
            v &= 0xFFFFFFFF
        elif op == "lbz":
            v &= 0xFF
        _setval(st, inst.dsts[0], v)
    elif op == "lfd":
        ea = (_val(st, inst.mem_base) + inst.mem_offset) & MASK64
        raw = st.mem.get(ea >> 3, 0)
        st.fregs[inst.dsts[0]] = float(_sext(raw)) * 2.0 ** -16
    elif op in ("std", "stw", "stb"):
        ea = (_val(st, inst.mem_base) + inst.mem_offset) & MASK64
        st.mem[ea >> 3] = _val(st, s[0]) & MASK64
    elif op == "stfd":
        ea = (_val(st, inst.mem_base) + inst.mem_offset) & MASK64
        st.mem[ea >> 3] = int(st.fregs[s[0]] * 2 ** 16) & MASK64
    elif op in ("fadd", "fsub", "fmul", "fmadd", "fdiv", "fsqrt", "fmr"):
        a = st.fregs[s[0]]
        if op == "fadd":
            r = a + st.fregs[s[1]]
        elif op == "fsub":
            r = a - st.fregs[s[1]]
        elif op == "fmul":
            r = a * st.fregs[s[1]]
        elif op == "fmadd":
            r = a * st.fregs[s[1]] + st.fregs[s[2]]
        elif op == "fdiv":
            d = st.fregs[s[1]]
            r = a / d if abs(d) > 1e-30 else 0.0
        elif op == "fsqrt":
            r = abs(a) ** 0.5
        else:
            r = a
        if abs(r) > 1e30:
            r = 0.0
        st.fregs[inst.dsts[0]] = r
    elif op == "b":
        next_pc = inst.target
        taken = True
    elif op == "bc":
        # branch if CR bit set per imm: 0 -> lt(4), 1 -> gt(2), 2 -> eq(1),
        # 3 -> not-eq
        cr = st.regs["CR"]
        cond = {0: cr & 4, 1: cr & 2, 2: cr & 1, 3: (cr & 1) == 0}[
            inst.imm or 0]
        taken = bool(cond)
        if taken:
            next_pc = inst.target
    elif op == "bl":
        st.regs["LR"] = pc + 1
        next_pc = inst.target
        taken = True
    elif op == "blr":
        next_pc = st.regs["LR"]
        taken = True
    elif op == "bdnz":
        st.regs["CTR"] = (st.regs["CTR"] - 1) & MASK64
        taken = st.regs["CTR"] != 0
        if taken:
            next_pc = inst.target
    elif op == "mtctr":
        st.regs["CTR"] = _val(st, s[0])
    elif op == "mtlr":
        st.regs["LR"] = _val(st, s[0])
    elif op == "mflr":
        _setval(st, inst.dsts[0], st.regs["LR"])
    elif op == "nop":
        pass
    else:
        raise ValueError(f"unimplemented opcode {op}")

    st.regs["NIA"] = next_pc
    return next_pc, ea, taken



def run(program, max_instructions: int, st: MachineState,
        snapshot_every: int = 0, start_pc: int = 0,
        done: int = 0) -> Tuple[List[int], List[List[int]]]:
    """Execute from ``start_pc`` until the program leaves its text or
    ``max_instructions`` retire.  Returns (pcs, snapshots): one snapshot
    of the 40 context registers BEFORE every trace position whose count
    (``done`` + position) is a multiple of ``snapshot_every``."""
    pcs: List[int] = []
    snaps: List[List[int]] = []
    pc, n = start_pc, 0
    while 0 <= pc < len(program) and n < max_instructions:
        if snapshot_every and (done + n) % snapshot_every == 0:
            snaps.append(st.snapshot())
        pc_next, _, _ = step(st, pc, program[pc])
        pcs.append(pc)
        pc = pc_next
        n += 1
    return pcs, snaps


def run_multicore(programs: Sequence[list], max_per_core: int,
                  states: Sequence[MachineState], quantum: int,
                  snapshot_every: int = 0
                  ) -> Tuple[List[List[int]], List[List[List[int]]]]:
    """Round-robin quantum schedule over shared memory: each round visits
    cores 0..N-1, each visit resumes the core and retires up to
    ``quantum`` instructions; every call starts all cores at pc 0 (one
    call is one interval).  Returns per-core (pcs, snapshots)."""
    n = len(programs)
    pcs: List[List[int]] = [[] for _ in range(n)]
    snaps: List[List[List[int]]] = [[] for _ in range(n)]
    done = [0] * n
    pc = [0] * n
    active = [True] * n
    while True:
        progressed = False
        for c in range(n):
            if not active[c] or done[c] >= max_per_core:
                continue
            q = min(quantum, max_per_core - done[c])
            p, s = run(programs[c], q, states[c], snapshot_every,
                       start_pc=pc[c], done=done[c])
            if p:
                pcs[c].extend(p)
                snaps[c].extend(s)
                done[c] += len(p)
                pc[c] = states[c].regs["NIA"]
                progressed = True
            if len(p) < q:
                active[c] = False
        if not progressed:
            return pcs, snaps


def as_arrays(pcs: List[int], snaps: List[List[int]]
              ) -> Tuple[np.ndarray, np.ndarray]:
    return (np.asarray(pcs, np.int64),
            np.asarray(snaps, np.uint64).reshape(len(snaps),
                                                 len(CONTEXT_REGS)))
