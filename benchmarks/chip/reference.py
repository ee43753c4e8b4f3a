"""Plain reference for the RTL cells: a netlist interpreter over many stimuli.

It follows the semantics of a full-cycle RTL simulation (Manticore paper,
§2.1): each cycle evaluates the combinational nodes in topological order
from the current state, then commits every register and memory write at
once. An EXPECT whose operands differ raises its exception id; a stimulus
stops after the first cycle that raised one, and that cycle counts.

It imports nothing of the program under test. It reads a circuit by
attribute only (``nodes`` with ``nid``/``op``/``args``/``width``/``params``,
``reg_init``, ``reg_next``, ``reg_names``, ``mems``, ``input_values``) and
each operation by its name, so it stays what it is when the program
changes. Every stimulus of a batch shares one structural netlist and
differs only in initial register and memory values, so one pass over the
nodes evaluates all of them, as vectors of exact integers (uint64: every
node of these designs is at most 64 bits wide).

``arith="float32"`` is the control: ADD, SUB and MUL are rounded through
float32, as a datapath on the chip's float units would compute them. It
breaks the bit-exactness that the configuration states, so a comparison
that accepts it is too weak.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence

import numpy as np

ARITH = ("exact", "float32")


@dataclass
class Outcome:
    """Per-stimulus end state of a reference run."""

    cycles: np.ndarray                      # [B] cycles run, raising cycle included
    exceptions: List[frozenset]             # [B] ids raised in the stopping cycle
    registers: Dict[str, np.ndarray]        # name -> [B] value at stop time

    @property
    def batch(self) -> int:
        return len(self.exceptions)


def _topo_order(nodes) -> list:
    """Combinational nodes after their operands (REG/INPUT/CONST are
    leaves; MEMRD reads the current memory, so only its address is an
    operand)."""
    order, state = [], [0] * len(nodes)      # 0 new, 1 on stack, 2 done
    for root in range(len(nodes)):
        stack = [(root, 0)]
        while stack:
            nid, ai = stack.pop()
            node = nodes[nid]
            if ai == 0:
                if state[nid] == 2:
                    continue
                if state[nid] == 1:
                    raise ValueError("combinational loop in netlist")
                state[nid] = 1
            if ai < len(node.args):
                stack.append((nid, ai + 1))
                arg = node.args[ai]
                if state[arg] == 1:
                    raise ValueError("combinational loop in netlist")
                if state[arg] == 0:
                    stack.append((arg, 0))
            else:
                state[nid] = 2
                order.append(node)
    return order


def _mem_entries(words: Sequence[int], width: int) -> List[int]:
    """Entries of a memory from its image as 16-bit words, low word first."""
    stride = (width + 15) // 16
    return [sum(int(words[i * stride + w]) << (16 * w) for w in range(stride))
            for i in range(len(words) // stride)]


class _Arith:
    """Element-wise operations on [B] uint64 vectors at a node's width."""

    def __init__(self, mode: str):
        if mode not in ARITH:
            raise ValueError(f"unknown arithmetic {mode!r}")
        self.mode = mode

    @staticmethod
    def mask(v, width: int):
        return v & np.uint64((1 << width) - 1)

    @staticmethod
    def shl(v, k: int, width: int):
        if k >= 64:
            return np.zeros_like(v)
        return _Arith.mask(v << np.uint64(k), width)

    @staticmethod
    def shr(v, k: int):
        if k >= 64:
            return np.zeros_like(v)
        return v >> np.uint64(k)

    def arith(self, op: str, a, b, width: int):
        if self.mode == "float32":
            fa, fb = a.astype(np.float32), b.astype(np.float32)
            r = {"ADD": fa + fb, "SUB": fa - fb, "MUL": fa * fb}[op]
            return np.mod(r.astype(np.float64), 2.0 ** width).astype(np.uint64)
        return self.mask({"ADD": a + b, "SUB": a - b, "MUL": a * b}[op], width)


def simulate(circuit, max_cycles: int,
             reg_inits: Optional[Sequence[Dict[str, int]]] = None,
             mem_inits: Optional[Sequence[Dict[str, Sequence[int]]]] = None,
             arith: str = "exact") -> Outcome:
    """Run ``circuit`` for up to ``max_cycles`` cycles on every stimulus.

    Stimulus ``b`` starts from the circuit's own initial values, overlaid
    by ``reg_inits[b]`` (register name -> value) and ``mem_inits[b]``
    (memory name -> 16-bit-word image). With neither, the circuit's own
    stimulus runs once."""
    nodes = circuit.nodes
    B = len(reg_inits) if reg_inits is not None else (
        len(mem_inits) if mem_inits is not None else 1)
    widest = max((n.width for n in nodes), default=1)
    if widest > 64:
        raise NotImplementedError(f"a {widest}-bit node: values are uint64")
    A = _Arith(arith)

    def vec(v: int):
        return np.full(B, v, dtype=np.uint64)

    names = {nm: rid for rid, nm in circuit.reg_names.items()}
    regs = {rid: vec(v) for rid, v in circuit.reg_init.items()}
    mem_width = {nm: m.width for nm, m in circuit.mems.items()}
    mems = {nm: np.array([list(m.init)] * B, dtype=np.uint64).reshape(
        B, len(m.init)) for nm, m in circuit.mems.items()}
    for b in range(B):
        for nm, v in (reg_inits[b] if reg_inits is not None else {}).items():
            regs[names[nm]][b] = v
        for nm, words in (mem_inits[b] if mem_inits is not None
                          else {}).items():
            ent = _mem_entries(words, mem_width[nm])
            mems[nm][b, :len(ent)] = ent

    order = _topo_order(nodes)
    inputs = {nid: vec(v) for nid, v in circuit.input_values.items()}
    consts = {n.nid: vec(n.params["value"]) for n in nodes
              if n.op.name == "CONST"}
    ar = np.arange(B)
    active = np.ones(B, dtype=bool)
    cycles = np.zeros(B, dtype=np.int64)
    raised: List[set] = [set() for _ in range(B)]
    zero = np.uint64(0)

    for _ in range(max_cycles):
        if not active.any():
            break
        val: List = [None] * len(nodes)
        fired = np.zeros(B, dtype=bool)
        writes = []
        for n in order:
            op, a, w = n.op.name, n.args, n.width
            if op == "CONST":
                v = consts[n.nid]
            elif op == "INPUT":
                v = inputs[n.nid]
            elif op == "REG":
                v = regs[n.nid]
            elif op == "AND":
                v = val[a[0]] & val[a[1]]
            elif op == "OR":
                v = val[a[0]] | val[a[1]]
            elif op == "XOR":
                v = val[a[0]] ^ val[a[1]]
            elif op == "NOT":
                v = A.mask(~val[a[0]], w)
            elif op in ("ADD", "SUB", "MUL"):
                v = A.arith(op, val[a[0]], val[a[1]], w)
            elif op in ("EQ", "NE", "LTU"):
                x, y = val[a[0]], val[a[1]]
                t = x == y if op == "EQ" else (x != y if op == "NE" else x < y)
                v = t.astype(np.uint64)
            elif op == "SHL":
                v = A.shl(val[a[0]], n.params["amount"], w)
            elif op == "SHR":
                v = A.shr(val[a[0]], n.params["amount"])
            elif op == "SRA":
                sw = nodes[a[0]].width
                k = min(n.params["amount"], sw)
                x = val[a[0]]
                fill = np.uint64(((1 << w) - 1) & ~((1 << max(sw - k, 0)) - 1))
                sign = A.shr(x, sw - 1) != zero
                v = A.mask(np.where(sign, A.shr(x, k) | fill, A.shr(x, k)), w)
            elif op == "MUX":
                v = np.where(val[a[0]] != zero, val[a[1]], val[a[2]])
            elif op == "SLICE":
                v = A.mask(A.shr(val[a[0]], n.params["off"]), w)
            elif op == "CAT":
                v = A.shl(val[a[0]], nodes[a[1]].width, w) | val[a[1]]
            elif op == "MEMRD":
                m = mems[n.params["mem"]]
                idx = (val[a[0]] % np.uint64(m.shape[1])).astype(np.int64)
                v = m[ar, idx]
            elif op == "MEMWR":
                writes.append((n.params["mem"], val[a[0]], val[a[1]],
                               val[a[2]] != zero))
                continue
            elif op == "EXPECT":
                hit = (val[a[0]] != val[a[1]]) & active
                fired |= hit
                for b in np.flatnonzero(hit):
                    raised[b].add(n.params["eid"])
                continue
            elif op == "OUTPUT":
                continue
            else:
                raise NotImplementedError(op)
            val[n.nid] = v
        # commit at the end of the cycle, for the stimuli still running
        for rid, nxt in circuit.reg_next.items():
            regs[rid] = np.where(active, val[nxt], regs[rid])
        for nm, addr, data, en in writes:
            m = mems[nm]
            idx = (addr % np.uint64(m.shape[1])).astype(np.int64)
            m[ar, idx] = np.where(en & active, data, m[ar, idx])
        cycles += active
        active &= ~fired

    return Outcome(
        cycles=cycles,
        exceptions=[frozenset(r) for r in raised],
        registers={nm: regs[rid] for nm, rid in names.items()})
