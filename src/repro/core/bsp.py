"""Static BSP executor — vectorized lockstep interpretation of a Program.

TPU adaptation of the Manticore grid: core *c* of the paper's
MIMD grid becomes lane *c* of ``[C]``-wide vectors. Every slot, all lanes
execute their own instruction simultaneously (compute-all-select over the
opcode — NOp lanes are masked), which is exactly the paper's lockstep
guarantee expressed as SIMD. One Vcycle is:

    lax.scan over the slot stream  ->  BSP exchange (deferred register
    updates from SENDs land at the Vcycle boundary)  ->  commit done.

The engine is **partially evaluated against the program's own static code
stream** — the paper's thesis (everything about the schedule is known at
compile time) applied to the simulator itself:

  * ``make_slot_step`` emits only the opcode branches the program actually
    contains (``Program.op_set()``): a LUT-free program never pays the
    16-pattern loop, a program with no off-chip traffic skips the cache
    model entirely;
  * the per-slot trace is gone — SEND values are scattered through the
    static ``Program.send_capture`` index table into a compact
    ``[n_sends + 1]`` buffer (last entry sacrificial), so the Vcycle
    exchange reads ``n_sends`` words instead of ``T*C``;
  * slots execute in **pipeline windows** of ``hw.raw_latency``: the
    scheduler guarantees a result is not readable for ``raw_latency``
    slots (the hardware's 4-stage exec pipeline, §5.1), so reads and ALU
    work for a whole window batch into one [W, C] tensor op — register
    writes, stores and the cache model stay slot-ordered within the
    window;
  * Vcycles run in **chunks** of K under one ``lax.scan`` with per-Vcycle
    freeze predication; the host checks exceptions once per chunk instead
    of dispatching (and recompiling for) every ``num_cycles`` value.

The privileged core's off-chip traffic (GLD/GST) is modeled with the paper's
direct-mapped cache + global-stall cost model: stalls do not change
simulation *results* (the whole machine freezes together), so the engine
executes them inline and accumulates the stall cycles performance counters
(§7.7 / Fig. 8).

``Machine(..., specialize=False)`` keeps the seed behaviour (compute-all
branches, full [T, C] trace, per-Vcycle ``while_loop``) as the baseline arm
for ``benchmarks/bench_engine.py``.
"""
from __future__ import annotations

from typing import Dict, FrozenSet, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..distributed.compat import shard_map
from .compile import Program
from .isa import Op

U32 = jnp.uint32

# Vcycles per chunked dispatch: one XLA launch simulates up to K RTL cycles;
# the host looks at the exception flags once per chunk.
DEFAULT_CHUNK = 32

# unrolling the window loop (full per-window specialization) is bounded by
# slot count to keep trace/compile time sane on very deep schedules
UNROLL_SLOTS = 4096

# deep-schedule fallback: the window stream is segmented into runs of
# windows sharing an opcode set, one specialized lax.scan per run; the
# segment count is bounded so a wildly heterogeneous schedule cannot blow
# up trace time (short neighbouring runs merge, unioning their op sets)
MAX_SCAN_SEGMENTS = 32

# opcodes with no register result (SEND's value goes to the exchange only)
_NO_WRITE_OPS = (Op.NOP, Op.ST, Op.GST, Op.EXPECT, Op.SEND)

# per-element cycle counter value that marks a batch-padding element: it is
# >= any real budget, so the element's freeze predicate is never active —
# padding executes nothing, raises nothing, and costs nothing beyond the
# dead lanes of its shard's vectorized ops
PAD_FROZEN_CYC = np.int32(1 << 30)


def _is_stacked(images) -> bool:
    """True for the stacked ``([B, C, R], [B, C, S], [B, G])`` image form
    (``Program.init_images_batch``) as opposed to a per-stimulus list of
    ``(reg, spad, gmem)`` tuples. Shape-driven, not type-driven: a
    per-stimulus sequence holds tuples (no ``ndim``), never 3-D arrays."""
    return (len(images) == 3
            and getattr(images[0], "ndim", 0) == 3
            and getattr(images[1], "ndim", 0) == 3
            and getattr(images[2], "ndim", 0) == 2)


class MachineState(NamedTuple):
    regs: jax.Array      # [C, R] uint32 (values are 16-bit)
    spads: jax.Array     # [C, S] uint32
    gmem: jax.Array      # [G] uint32
    flags: jax.Array     # [C] uint32 — first exception id per core (0 = none)
    cache_tags: jax.Array  # [LINES] int32 (-1 = invalid)
    counters: jax.Array  # [4] uint32: vcycles, ghits, gmisses, stall_cycles


def _alu_branches(ops, v1, v2, v3, v4, imm, lut_tt=None, ld_val=None,
                  gld_val=None):
    """(op, value) branch list for every result-producing opcode in ``ops``
    — the single definition of the ALU semantics, shared by the scan/window
    engines and the unrolled fast path. Operand shapes propagate ([C] or
    [W, C]); ``lut_tt(p)`` returns the pre-gathered truth-table word of
    pattern ``p``, ``ld_val``/``gld_val`` are the pre-gathered memory reads
    (required iff LUT/LD/GLD is in ``ops``)."""
    branches = []

    def b(o, thunk):
        if o in ops:
            branches.append((o, thunk()))

    b(Op.MOV, lambda: v1)
    b(Op.MOVI, lambda: imm & 0xFFFF)
    b(Op.ADD, lambda: (v1 + v2) & 0xFFFF)
    b(Op.ADDC, lambda: (v1 + v2 + v3) & 0xFFFF)
    b(Op.CARRY, lambda: ((v1 + v2 + v3) >> 16) & 0xFFFF)
    b(Op.SUB, lambda: (v1 - v2) & 0xFFFF)
    b(Op.SUBB, lambda: (v1 - v2 - v3) & 0xFFFF)
    b(Op.BORROW, lambda: (v1 < v2 + v3).astype(U32))
    b(Op.MUL, lambda: (v1 * v2) & 0xFFFF)
    b(Op.MULH, lambda: ((v1 * v2) >> 16) & 0xFFFF)
    b(Op.AND, lambda: v1 & v2)
    b(Op.OR, lambda: v1 | v2)
    b(Op.XOR, lambda: v1 ^ v2)
    b(Op.NOT, lambda: (~v1) & 0xFFFF)
    b(Op.MUX, lambda: jnp.where(v1 != 0, v2, v3))
    b(Op.SEQ, lambda: (v1 == v2).astype(U32))
    b(Op.SNE, lambda: (v1 != v2).astype(U32))
    b(Op.SLTU, lambda: (v1 < v2).astype(U32))
    b(Op.SLL, lambda: (v1 << (imm & 15)) & 0xFFFF)
    b(Op.SRL, lambda: v1 >> (imm & 15))
    b(Op.SRA, lambda: ((((v1 ^ 0x8000) - 0x8000).astype(jnp.int32)
                        >> (imm & 15)).astype(U32)) & 0xFFFF)
    b(Op.SLLV, lambda: (v1 << (v2 & 15)) & 0xFFFF)
    b(Op.SRLV, lambda: v1 >> (v2 & 15))
    b(Op.SLICE, lambda: (v1 >> (imm >> 5)) & ((1 << (imm & 31)) - 1))

    if Op.LUT in ops:
        # LUT: 16-pattern compute-all-select (per-bit-lane 4-input fn);
        # pattern bit i corresponds to LUT input i (s1 -> bit 0)
        lut_out = jnp.zeros_like(v1)
        nv = [(~x) & 0xFFFF for x in (v1, v2, v3, v4)]
        for p in range(16):
            m = (v1 if p & 1 else nv[0]) & (v2 if p & 2 else nv[1]) \
                & (v3 if p & 4 else nv[2]) & (v4 if p & 8 else nv[3])
            lut_out = lut_out | (m & lut_tt(p))
        branches.append((Op.LUT, lut_out))
    if Op.LD in ops:
        branches.append((Op.LD, ld_val))
    if Op.GLD in ops:
        branches.append((Op.GLD, gld_val))
    b(Op.SEND, lambda: v1)
    return branches


def make_slot_step(luts, spad_words, gmem_words, cache_lines, line_words,
                   hit_stall, miss_stall,
                   op_set: Optional[FrozenSet[Op]] = None):
    """Build the per-slot executor, specialized to ``op_set``.

    The returned ``step(carry, xs)`` is a ``lax.scan`` body with
    ``carry = (regs, spads, gmem, flags, tags, counters, sbuf)`` and
    ``xs = (instr [C, 7] int32, cap [C] int32)`` where ``cap`` maps each
    lane to its compact SEND-buffer slot (or the sacrificial last slot).
    Only branches for opcodes in ``op_set`` are traced; ``op_set=None``
    emits everything (the unspecialized compute-all form).
    """
    win = make_window_step(luts, spad_words, gmem_words, cache_lines,
                           line_words, hit_stall, miss_stall,
                           op_set=op_set, window=1)

    def step(carry, xs):
        instr, cap = xs
        return win(carry, (instr[None], cap[None]))

    return step


def make_window_step(luts, spad_words, gmem_words, cache_lines, line_words,
                     hit_stall, miss_stall,
                     op_set: Optional[FrozenSet[Op]] = None,
                     window: int = 1):
    """Build the pipeline-window executor, specialized to ``op_set``.

    Executes ``window`` consecutive slots per call: all register/memory
    *reads* and the ALU run batched over a [W, C] tensor — sound because
    the scheduler spaces every RAW def->use pair by ``hw.raw_latency``
    slots (use ``window <= raw_latency``) and orders all loads of a memory
    before its stores — while register writes, stores and the cache model
    are applied slot-by-slot to preserve WAW/memory order.

    ``step(carry, xs)`` with ``carry = (regs, spads, gmem, flags, tags,
    counters, sbuf)`` and ``xs = (instr [W, C, 7], cap [W, C])``.
    """
    W = window
    ops = frozenset(Op) if op_set is None else frozenset(op_set)
    need_v3 = bool(ops & {Op.ADDC, Op.CARRY, Op.SUBB, Op.BORROW,
                          Op.MUX, Op.ST, Op.GST, Op.LUT})
    need_v4 = bool(ops & {Op.LUT, Op.GST})
    has_global = bool(ops & {Op.GLD, Op.GST})
    writes = bool(ops - set(_NO_WRITE_OPS))

    def step(carry, xs):
        regs, spads, gmem, flags, tags, counters, sbuf = carry
        instr, cap = xs
        C = regs.shape[0]
        ar = jnp.arange(C)
        col = jnp.broadcast_to(ar[None, :], (W, C))

        op = instr[..., 0]
        dst = instr[..., 1]
        imm = instr[..., 6].astype(U32)
        zero = jnp.zeros((W, C), U32)
        v1 = regs[col, instr[..., 2]]
        v2 = regs[col, instr[..., 3]]
        v3 = regs[col, instr[..., 4]] if need_v3 else zero
        v4 = regs[col, instr[..., 5]] if need_v4 else zero

        lut_tt = None
        if Op.LUT in ops:
            tt = luts[col, jnp.minimum(imm, luts.shape[1] - 1)]  # [W, C, 16]
            lut_tt = lambda p: tt[..., p]
        ld_val = spads[col, v1 % spad_words] if Op.LD in ops else None
        if has_global:
            g_addr = ((v1 << 16) | v2) % gmem_words
        gld_val = gmem[g_addr] if Op.GLD in ops else None
        branches = _alu_branches(ops, v1, v2, v3, v4, imm,
                                 lut_tt, ld_val, gld_val)

        result = zero
        for code_op, val in branches:
            result = jnp.where(op == int(code_op), val, result)

        # ---- register writes (slot-ordered; never r0) ----
        if writes:
            no_write = dst == 0
            for o in _NO_WRITE_OPS:
                if o in ops:
                    no_write = no_write | (op == int(o))
            wdst = jnp.where(no_write, 0, dst)
            for w in range(W):
                wval = jnp.where(no_write[w], regs[ar, 0], result[w])
                regs = regs.at[ar, wdst[w]].set(wval)

        # ---- scratchpad stores (predicated, slot-ordered) ----
        if Op.ST in ops:
            st_mask = (op == int(Op.ST)) & (v3 != 0)
            st_addr = v1 % spad_words
            for w in range(W):
                spads = spads.at[ar, st_addr[w]].set(
                    jnp.where(st_mask[w], v2[w], spads[ar, st_addr[w]]))

        # ---- global stores + cache/stall model (privileged lanes) ----
        if has_global:
            gst_mask = (op == int(Op.GST)) & (v4 != 0)
            for w in range(W):
                if Op.GST in ops:
                    w_addr = jnp.where(gst_mask[w], g_addr[w], 0)
                    gmem = gmem.at[w_addr].set(
                        jnp.where(gst_mask[w], v3[w], gmem[w_addr]))
                g_access = (op[w] == int(Op.GLD)) | gst_mask[w]
                any_g = jnp.any(g_access)
                # model the (single) privileged access through the cache
                lane = jnp.argmax(g_access)
                line = (g_addr[w, lane] // line_words).astype(jnp.int32)
                idx = line % cache_lines
                hit = (tags[idx] == line) & any_g
                miss = (~hit) & any_g
                tags = tags.at[idx].set(jnp.where(any_g, line, tags[idx]))
                counters = counters.at[1].add(hit.astype(jnp.uint32))
                counters = counters.at[2].add(miss.astype(jnp.uint32))
                counters = counters.at[3].add(
                    jnp.where(hit, jnp.uint32(hit_stall),
                              jnp.where(miss, jnp.uint32(miss_stall),
                                        jnp.uint32(0))))

        # ---- exceptions (EXPECT raises when operands differ) ----
        if Op.EXPECT in ops:
            exc = (op == int(Op.EXPECT)) & (v1 != v2)     # [W, C]
            any_exc = exc.any(axis=0)
            first_w = jnp.argmax(exc, axis=0)             # earliest slot wins
            imm_sel = imm[first_w, ar]
            flags = jnp.where((flags == 0) & any_exc, imm_sel, flags)

        # ---- compact SEND capture (non-senders hit the sacrificial slot) --
        sbuf = sbuf.at[cap.reshape(-1)].set(
            (result & 0xFFFF).reshape(-1))
        return (regs, spads, gmem, flags, tags, counters, sbuf), None

    return step


def dispatch_chunks(run_chunk, cyc, carry, chunk: int, num_cycles: int,
                    done):
    """Host side of the chunked K-Vcycle dispatch, shared by the single,
    batched and multi-device engines: launch ceil(num_cycles/chunk)
    chunks, reading the exception flags once per chunk (the only host
    sync point) and stopping early when ``done(flags)``."""
    budget = jnp.int32(num_cycles)
    n_launch = -(-num_cycles // chunk) if num_cycles > 0 else 0
    for _ in range(n_launch):
        cyc, carry = run_chunk(cyc, budget, carry)
        if done(np.asarray(carry[3])):
            break
    return carry


class Machine:
    """Executable instance of a compiled Program (single host/device).

    ``specialize=True`` (default) runs the partially-evaluated fast path:
    opcode-set-specialized pipeline-window step, compact SEND capture and
    chunked K-Vcycle dispatch. ``specialize=False`` reproduces the seed
    engine (full ISA select, [T, C] trace, per-Vcycle while_loop) and
    exists so the perf trajectory can be measured against it.
    """

    def __init__(self, program: Program, backend: str = "jnp",
                 compact: bool = True, interpret: Optional[bool] = None,
                 specialize: bool = True, chunk: int = DEFAULT_CHUNK):
        self.p = program
        self.backend = backend
        self.specialize = specialize
        self.chunk = max(1, int(chunk))
        hw = program.hw
        # active-core / active-register compaction: the FPGA burns idle
        # cores and its 2048-entry register file for free, the interpreter
        # need not simulate them (beyond-paper optimization).
        C = program.used_cores if compact else program.code.shape[0]
        C = max(C, 1)
        self.C = C
        R = program.used_reg_count() if compact else hw.num_regs
        self.R = R
        self.code = jnp.asarray(
            np.ascontiguousarray(program.code[:C].transpose(1, 0, 2)),
            dtype=jnp.int32)                                    # [T, C, 7]
        self.luts = jnp.asarray(program.luts[:C], dtype=U32)    # [C, 32, 16]
        self.reg0 = jnp.asarray(program.reg_init[:C, :R], dtype=U32)
        self.spad0 = jnp.asarray(program.spad_init[:C], dtype=U32)
        self.gmem0 = jnp.asarray(program.gmem_init, dtype=U32)
        self.xchg = tuple(jnp.asarray(a) for a in (
            program.xchg_src_slot, program.xchg_src_core,
            program.xchg_dst_core, program.xchg_dst_reg))
        self.n_sends = program.n_sends
        self.cache_lines = hw.cache_words // hw.cache_line_words
        self.op_set = program.op_set() if specialize else None
        if not specialize:
            # seed engine: unspecialized compute-all step + full trace
            self._step = make_slot_step(
                self.luts, max(self.spad0.shape[1], 1),
                max(self.gmem0.shape[0], 1), self.cache_lines,
                hw.cache_line_words, hw.cache_hit_stall,
                hw.cache_miss_stall, op_set=None)

        # pipeline-windowed code stream: [T/W, W, C, 7] with W = the
        # hardware RAW latency (all-NOP padding rows; sacrificial capture).
        # Only the specialized jnp paths consume it — the pallas backend
        # builds its own padded capture table and the seed path scans the
        # raw code.
        T = self.code.shape[0]
        W = max(1, int(hw.raw_latency))
        self.W = W
        # rotated dispatch of a modulo-pipelined program: the combined
        # stream's first ``pipe_prologue`` slots hold the *next* Vcycle's
        # hoisted pure ops. The specialized engines split the stream there:
        # the body executes in the Vcycle, the prologue re-executes after
        # the exchange gated on "no exception this cycle" (cycle k+1's
        # in-flight prologue never commits when cycle k raises), and
        # ``init_state`` applies iteration 0's prologue once. The seed
        # engine keeps the full stream: executing the prologue rows at the
        # stream head is idempotent (pure ops whose inputs are untouched
        # since the previous epilogue recomputed them), so both dispatch
        # forms produce bit-identical register planes.
        self.Tpro = int(program.pipe_prologue) if specialize else 0
        if self.Tpro:
            head_ops = {int(o) for o in
                        np.unique(np.asarray(self.code)[:self.Tpro, :, 0])}
            illegal = head_ops & {int(o) for o in
                                  (Op.ST, Op.GST, Op.EXPECT, Op.SEND,
                                   Op.LD, Op.GLD)}
            if illegal:
                raise ValueError(
                    f"pipelined prologue contains impure opcodes {illegal}")

        def _pad_windows(rows_code, rows_cap):
            t = rows_code.shape[0]
            tp = ((t + W - 1) // W) * W
            cp = np.zeros((tp, C, 7), np.int32)
            cp[:t] = rows_code
            kp = np.full((tp, C), self.n_sends, np.int32)
            kp[:t] = rows_cap
            return cp, kp

        self._pro_windows = []
        if specialize:
            cap_full = program.send_capture(C)
            code_np = np.asarray(self.code)
            code_p, cap_p = _pad_windows(code_np[self.Tpro:],
                                         cap_full[self.Tpro:])
            Tp = code_p.shape[0]
            if self.Tpro:
                pro_p, pcap_p = _pad_windows(code_np[:self.Tpro],
                                             cap_full[:self.Tpro])
                self._pro_windows = self._build_windows(pro_p, pcap_p, hw)
        T = T - self.Tpro       # body slot count drives the unroll bound

        # static per-window metadata for the fully-unrolled fast path:
        # (instr, ops, write/store/send/expect/global sites — all constant)
        self._unrolled = (specialize and backend != "pallas"
                          and T <= UNROLL_SLOTS)
        if specialize and backend != "pallas" and not self._unrolled:
            # deep-schedule fallback: per-window specialization inside the
            # scan. Windows are grouped into consecutive runs sharing an
            # opcode set; each run gets its own window body traced with
            # only that run's branches (all-NOP windows are dropped — their
            # capture rows are all-sacrificial by construction), and the
            # Vcycle executes the runs in schedule order.
            wcode_np = code_p.reshape(Tp // W, W, C, 7)
            wcap_np = cap_p.reshape(Tp // W, W, C)
            runs = []      # [frozenset(ops), [window indices]]
            for iw in range(Tp // W):
                wops = frozenset(Op(int(o))
                                 for o in np.unique(wcode_np[iw, ..., 0])
                                 if o)
                if not wops:
                    continue                       # all-NOP window
                if runs and runs[-1][0] == wops:
                    runs[-1][1].append(iw)
                else:
                    runs.append([wops, [iw]])
            while len(runs) > MAX_SCAN_SEGMENTS:
                k = min(range(len(runs) - 1),
                        key=lambda i: len(runs[i][1]) + len(runs[i + 1][1]))
                runs[k] = [runs[k][0] | runs[k + 1][0],
                           runs[k][1] + runs[k + 1][1]]
                del runs[k + 1]
            self._segments = []
            self._segment_ops = [ops for ops, _ in runs]
            for seg_ops, idxs in runs:
                step = make_window_step(
                    self.luts, max(self.spad0.shape[1], 1),
                    max(self.gmem0.shape[0], 1), self.cache_lines,
                    hw.cache_line_words, hw.cache_hit_stall,
                    hw.cache_miss_stall,
                    op_set=seg_ops | {Op.NOP}, window=W)
                self._segments.append(
                    (step, jnp.asarray(wcode_np[idxs]),
                     jnp.asarray(wcap_np[idxs])))
        self._windows = (self._build_windows(code_p, cap_p, hw)
                         if self._unrolled else [])

        if backend == "pallas":
            from ..kernels import ops as kops
            if specialize:
                self._chunk_kernel = kops.make_vcycle_chunk(
                    program, C, self.chunk, interpret=interpret)
            else:
                self._vcycle_kernel = kops.make_vcycle(
                    program, C, interpret=interpret)
        if specialize:
            if backend == "pallas":
                self._run_chunk = jax.jit(self._chunk_kernel)
            else:
                self._run_chunk = jax.jit(self._chunk_impl)
        else:
            self._run = jax.jit(self._run_legacy,
                                static_argnames=("num_cycles",))

    def _build_windows(self, code_p, cap_p, hw):
        """Static per-window metadata for the fully-unrolled fast path
        (one entry per non-NOP window; see ``_exec_windows``)."""
        C = self.C
        W = self.W
        windows = []
        no_write_ops = {int(o) for o in _NO_WRITE_OPS}
        for iw in range(code_p.shape[0] // W):
            instr = code_p[iw * W:(iw + 1) * W]          # [W, C, 7]
            wcapn = cap_p[iw * W:(iw + 1) * W]           # [W, C]
            opw = instr[..., 0]
            if not opw.any():
                continue                                 # all-NOP window
            # flat active-lane vector: the schedule's NOP lanes are
            # known statically, so gathers/ALU run over the k non-NOP
            # (slot, core) lanes only — a low-utilization schedule
            # (e.g. mc at 13%) pays for the work it contains, not for
            # the [W, C] rectangle around it
            w_arr, c_arr = np.nonzero(opw)               # [k], w-major
            lane = instr[w_arr, c_arr]                   # [k, 7]
            opl = lane[:, 0]
            wops = frozenset(Op(int(o)) for o in np.unique(opl))
            wr_rows, st_rows, send_rows, exp_rows, glb_rows = \
                [], [], [], [], []
            for w in range(W):
                in_w = w_arr == w
                wr = np.nonzero(in_w & (lane[:, 1] != 0) &
                                ~np.isin(opl, list(no_write_ops)))[0]
                if wr.size:
                    wr_rows.append((wr, c_arr[wr], lane[wr, 1]))
                st = np.nonzero(in_w & (opl == int(Op.ST)))[0]
                if st.size:
                    st_rows.append((st, c_arr[st]))
                sn = np.nonzero(in_w & (opl == int(Op.SEND)))[0]
                if sn.size:
                    send_rows.append((sn, wcapn[w, c_arr[sn]]))
                ex = np.nonzero(in_w & (opl == int(Op.EXPECT)))[0]
                if ex.size:
                    exp_rows.append((ex, c_arr[ex]))
                for gop, is_gst in ((Op.GLD, False), (Op.GST, True)):
                    gl = np.nonzero(in_w & (opl == int(gop)))[0]
                    if gl.size:
                        glb_rows.append((gl, c_arr[gl], is_gst))
            # merge the window's register writes into one scatter when
            # no (core, reg) cell is written twice (WAW inside a RAW
            # window can only come from dead writes — regalloc never
            # emits them, but stay exact if it ever does)
            if len(wr_rows) > 1:
                sss = np.concatenate([s for (s, _, _) in wr_rows])
                css = np.concatenate([c for (_, c, _) in wr_rows])
                dss = np.concatenate([d for (_, _, d) in wr_rows])
                cells = css.astype(np.int64) * hw.num_regs + dss
                if np.unique(cells).size == cells.size:
                    wr_rows = [(sss, css, dss)]
            windows.append((lane, c_arr, wops, wr_rows, st_rows,
                            send_rows, exp_rows, glb_rows))
        return windows

    # ------------------------------------------------------------------
    def init_state(self, images=None) -> MachineState:
        """Initial machine state; ``images=(reg_init, spad_init, gmem_init)``
        (full-width arrays, e.g. from ``Program.init_images``) selects a
        different stimulus than the program's base init."""
        if images is None:
            regs, spads, gmem = self.reg0, self.spad0, self.gmem0
        else:
            ri, si, gi = images
            regs = jnp.asarray(np.asarray(ri)[:self.C, :self.R], U32)
            spads = jnp.asarray(np.asarray(si)[:self.C], U32)
            gmem = jnp.asarray(np.asarray(gi), U32)
        if self.Tpro:
            # rotated prologue dispatch: iteration 0's hoisted pure ops
            # run once, before the first Vcycle's steady-state body
            regs = self._apply_prologue(regs, spads, gmem)
        return MachineState(
            regs=regs,
            spads=spads,
            gmem=gmem,
            flags=jnp.zeros((self.C,), U32),
            cache_tags=-jnp.ones((self.cache_lines,), jnp.int32),
            counters=jnp.zeros((4,), jnp.uint32),
        )

    def _apply_prologue(self, regs, spads, gmem):
        """Execute the prologue rows (pure ops — only ``regs`` changes) on
        the given state; used for iteration 0 at init and for iteration
        k+1 at the tail of every specialized Vcycle."""
        flags = jnp.zeros((self.C,), U32)
        tags = -jnp.ones((self.cache_lines,), jnp.int32)
        counters = jnp.zeros((4,), jnp.uint32)
        return self._exec_windows(self._pro_windows, regs, spads, gmem,
                                  flags, tags, counters, None, [], [])[0]

    # ------------------------------------------------ specialized path ----
    def _vcycle(self, carry, active=None):
        """One Vcycle. ``active`` (a traced bool, used by the batched
        engine under vmap) freezes an inactive element bit-identically:
        the unrolled path gates each write site individually (no
        whole-state select); the segmented-scan fallback selects the
        state leaves once at the Vcycle boundary."""
        if self._unrolled:
            return self._vcycle_unrolled(carry, active)
        regs, spads, gmem, flags, tags, counters = carry
        sbuf = jnp.zeros((self.n_sends + 1,), U32)
        c7 = (regs, spads, gmem, flags, tags, counters, sbuf)
        for step, wcode, wcap in self._segments:
            if wcode.shape[0] == 1:
                c7, _ = step(c7, (wcode[0], wcap[0]))
            else:
                c7, _ = jax.lax.scan(step, c7, (wcode, wcap), unroll=2)
        nregs, nspads, ngmem, nflags, ntags, ncounters, sbuf = c7
        # ---- BSP exchange straight from the compact SEND buffer ----
        if self.n_sends:
            _, _, d_core, d_reg = self.xchg
            nregs = nregs.at[d_core, d_reg].set(sbuf[:self.n_sends])
        ncounters = ncounters.at[0].add(jnp.uint32(1))
        if self._pro_windows:
            # cycle k+1's prologue issues in cycle k's idle tail; its
            # register carries commit only when cycle k raised nothing
            # (``active`` freezing is handled by the leaf select below)
            nregs = self._exec_windows(
                self._pro_windows, nregs, nspads, ngmem, nflags, ntags,
                ncounters, jnp.all(nflags == 0), [], [])[0]
        new = (nregs, nspads, ngmem, nflags, ntags, ncounters)
        if active is None:
            return new
        return tuple(jnp.where(active, n, o) for n, o in zip(new, carry))

    def _vcycle_unrolled(self, carry, active=None):
        """Fully partially-evaluated Vcycle: the window loop is unrolled
        over the static code stream. Every window traces only the branches
        for *its own* opcodes (the per-slot usage metadata), every
        gather/scatter site (writes, stores, SENDs, EXPECTs, global ops) is
        emitted only where the schedule actually contains one — with
        constant index arrays — and all SEND values merge into a single
        exchange scatter. The XLA graph *is* the program.

        ``active`` gates every write site (see ``_vcycle``): the per-site
        selects touch only the written cells, so a frozen batch element
        costs nothing beyond the dead compute it discards."""
        regs, spads, gmem, flags, tags, counters = carry
        send_idx, send_parts = [], []
        regs, spads, gmem, flags, tags, counters = self._exec_windows(
            self._windows, regs, spads, gmem, flags, tags, counters,
            active, send_idx, send_parts)

        # ---- BSP exchange: one scatter from the captured SEND values ----
        if self.n_sends:
            sid = np.concatenate(send_idx)
            d_core = self.p.xchg_dst_core[sid]
            d_reg = self.p.xchg_dst_reg[sid]
            vals = (jnp.concatenate(send_parts) if len(send_parts) > 1
                    else send_parts[0])
            if active is not None:
                vals = jnp.where(active, vals, regs[d_core, d_reg])
            regs = regs.at[d_core, d_reg].set(vals)
        counters = counters.at[0].add(jnp.uint32(1) if active is None
                                      else active.astype(jnp.uint32))
        if self._pro_windows:
            # cycle k+1's prologue (pure register carries) issues in cycle
            # k's idle tail and commits only when cycle k raised nothing —
            # an in-flight prologue is dropped on exception
            pgate = jnp.all(flags == 0)
            if active is not None:
                pgate = pgate & active
            regs = self._exec_windows(
                self._pro_windows, regs, spads, gmem, flags, tags,
                counters, pgate, [], [])[0]
        return (regs, spads, gmem, flags, tags, counters)

    def _exec_windows(self, windows, regs, spads, gmem, flags, tags,
                      counters, active, send_idx, send_parts):
        """Execute a list of static unrolled windows on the given leaves;
        SEND captures are appended to ``send_idx``/``send_parts`` for the
        caller's exchange scatter. ``active`` (None, or a scalar bool per
        batch element) gates every write site individually."""
        gate = ((lambda p: p) if active is None
                else (lambda p: p & active))
        hw = self.p.hw
        S = max(self.spad0.shape[1], 1)
        G = max(self.gmem0.shape[0], 1)

        for wi in windows:
            (lane, c_arr, wops, wr_rows, st_rows, send_rows, exp_rows,
             glb_rows) = wi
            imm = lane[:, 6].astype(np.uint32)
            op = lane[:, 0]
            # ST/GST operands must also come from the window-start batch:
            # a WAR/ORDER edge lets another instruction overwrite a store's
            # predicate register as little as 1 slot after the store reads
            # it, and the register writes above are applied before the
            # store sites below
            need_v3 = bool(wops & {Op.ADDC, Op.CARRY, Op.SUBB, Op.BORROW,
                                   Op.MUX, Op.LUT, Op.ST, Op.GST})
            need_v4 = bool(wops & {Op.LUT, Op.GST})
            v1 = regs[c_arr, lane[:, 2]]
            v2 = regs[c_arr, lane[:, 3]]
            v3 = regs[c_arr, lane[:, 4]] if need_v3 else None
            v4 = regs[c_arr, lane[:, 5]] if need_v4 else None

            lut_tt = None
            if Op.LUT in wops:
                tt = self.luts[c_arr, np.minimum(imm, self.luts.shape[1] - 1)]
                lut_tt = lambda p, tt=tt: tt[..., p]
            ld_val = spads[c_arr, v1 % S] if Op.LD in wops else None
            gld_val = (gmem[((v1 << 16) | v2) % G]
                       if Op.GLD in wops else None)
            branches = _alu_branches(wops, v1, v2, v3, v4, imm,
                                     lut_tt, ld_val, gld_val)

            if len(branches) == 1:
                result = branches[0][1]
            elif branches:
                result = jnp.zeros(v1.shape, U32)
                for code_op, val in branches:
                    result = jnp.where(op == int(code_op), val, result)
            else:
                result = None                  # store/expect-only window

            # ---- register writes: static (lane, cores, dsts) sites; a
            # merged site spans the window (one scatter per window) ----
            for (sel, cores, dsts) in wr_rows:
                vals = result[..., sel] & 0xFFFF
                if active is not None:
                    vals = jnp.where(active, vals, regs[cores, dsts])
                regs = regs.at[cores, dsts].set(vals)

            # ---- predicated scratchpad stores ----
            for (sel, cores) in st_rows:
                pred = gate(v3[..., sel] != 0)
                addr = v1[..., sel] % S
                spads = spads.at[cores, addr].set(
                    jnp.where(pred, v2[..., sel], spads[cores, addr]))

            # ---- SEND capture (merged into one exchange scatter) ----
            for (sel, sid) in send_rows:
                send_idx.append(sid)
                send_parts.append(v1[..., sel] & 0xFFFF)

            # ---- exceptions ----
            for (sel, cores) in exp_rows:
                exc = gate((v1[..., sel] != v2[..., sel])
                           & (flags[cores] == 0))
                flags = flags.at[cores].set(
                    jnp.where(exc, jnp.asarray(imm[sel], U32),
                              flags[cores]))

            # ---- privileged global ops + cache/stall model ----
            for (sel, cores, is_gst) in glb_rows:
                g_addr = ((v1[..., sel] << 16) | v2[..., sel]) % G
                if is_gst:
                    pred = gate(v4[..., sel] != 0)
                    w_addr = jnp.where(pred, g_addr, 0)
                    gmem = gmem.at[w_addr].set(
                        jnp.where(pred, v3[..., sel], gmem[w_addr]))
                    any_g = pred[..., 0]
                else:
                    any_g = gate(jnp.bool_(True))
                line = (g_addr[..., 0]
                        // hw.cache_line_words).astype(jnp.int32)
                idx = line % self.cache_lines
                hit = (tags[idx] == line) & any_g
                miss = (~hit) & any_g
                tags = tags.at[idx].set(jnp.where(any_g, line, tags[idx]))
                counters = counters.at[1].add(hit.astype(jnp.uint32))
                counters = counters.at[2].add(miss.astype(jnp.uint32))
                counters = counters.at[3].add(
                    jnp.where(hit, jnp.uint32(hw.cache_hit_stall),
                              jnp.where(miss,
                                        jnp.uint32(hw.cache_miss_stall),
                                        jnp.uint32(0))))

        return regs, spads, gmem, flags, tags, counters

    def _chunk_impl(self, cyc, budget, carry):
        """K predicated Vcycles under one scan: a Vcycle whose start state
        already carries an exception (or that exceeds the budget) freezes —
        the machine stops *within* the chunk, exactly at the raising cycle."""
        def body(c, _):
            cyc, st = c
            active = (cyc < budget) & jnp.all(st[3] == 0)
            st = jax.lax.cond(active, self._vcycle, lambda s: s, st)
            return (cyc + active.astype(jnp.int32), st), None

        (cyc, carry), _ = jax.lax.scan(body, (cyc, carry), None,
                                       length=self.chunk)
        return cyc, carry

    # ------------------------------------------------ seed (baseline) ----
    def _vcycle_legacy(self, carry):
        if self.backend == "pallas":
            carry, trace = self._vcycle_kernel(carry)
        else:
            # self._step is the unspecialized (op_set=None) form here
            carry, trace = _scan_with_trace(self._step, carry, self.code)
        regs, spads, gmem, flags, tags, counters = carry
        s_slot, s_core, d_core, d_reg = self.xchg
        if s_slot.shape[0]:
            vals = trace[s_slot, s_core]
            regs = regs.at[d_core, d_reg].set(vals)
        counters = counters.at[0].add(jnp.uint32(1))
        return (regs, spads, gmem, flags, tags, counters)

    def _run_legacy(self, state: MachineState, num_cycles: int):
        def cond(c):
            cyc, st = c
            return (cyc < num_cycles) & jnp.all(st[3] == 0)

        def body(c):
            cyc, st = c
            return cyc + 1, self._vcycle_legacy(st)

        _, out = jax.lax.while_loop(cond, body, (jnp.int32(0), tuple(state)))
        return MachineState(*out)

    # ------------------------------------------------------------------
    def run(self, state: MachineState, num_cycles: int) -> MachineState:
        """Run up to ``num_cycles`` Vcycles; freezes on the first exception
        (the host services it — paper's global stall + host handshake)."""
        if not self.specialize:
            return self._run(state, num_cycles=num_cycles)
        carry = dispatch_chunks(
            self._run_chunk, jnp.int32(0), tuple(state), self.chunk,
            int(num_cycles), lambda f: f.any())
        return MachineState(*carry)

    def exceptions(self, state: MachineState) -> Dict[int, int]:
        f = np.asarray(state.flags)
        return {int(c): int(e) for c, e in enumerate(f) if e}

    def read_output(self, state: MachineState, name: str) -> int:
        core, mregs = self.p.outputs[name]
        regs = np.asarray(state.regs)
        out = 0
        for j, r in enumerate(mregs):
            out |= int(regs[core, r]) << (16 * j)
        return out

    def read_reg(self, state: MachineState, rtl_name: str) -> int:
        words = self.p.state_regs[rtl_name]
        regs = np.asarray(state.regs)
        out = 0
        for j, locs in enumerate(words):
            c, r = locs[0]
            out |= int(regs[c, r]) << (16 * j)
        return out

    def perf(self, state: MachineState) -> Dict[str, int]:
        cnt = np.asarray(state.counters)
        vcycles = int(cnt[0])
        stalls = int(cnt[3])
        return {
            "vcycles": vcycles,
            "ghits": int(cnt[1]),
            "gmisses": int(cnt[2]),
            "stall_cycles": stalls,
            "machine_cycles": vcycles * self.p.vcpl + stalls,
        }


class BatchedMachine(Machine):
    """B independent stimuli of one compiled Program per device launch.

    The compile-time pipeline (partition → schedule → regalloc →
    trace/unroll) is paid once per *design*; the accelerator's data-parallel
    axis then carries B testbenches that share ``code``/``luts`` and differ
    only in initial state (``Program.init_images`` planes). Every
    ``MachineState`` leaf gains a leading ``[B]`` axis and the specialized
    Vcycle graph (unrolled or segmented-scan) is ``jax.vmap``-ed over it.

    Exception semantics are per batch element: element ``b`` freezes at its
    raising Vcycle (its chunk iterations become no-ops via predication)
    while the other elements run on; the host syncs the exception flags
    once per K-Vcycle chunk, exactly like the single-stimulus dispatch.

    ``backend="pallas"`` runs the chunked whole-machine kernel with a grid
    axis over B, so each batch element's registers/scratchpads stay
    VMEM-resident for the whole chunk.
    """

    def __init__(self, program: Program, images=None, batch: Optional[int] = None,
                 backend: str = "jnp", interpret: Optional[bool] = None,
                 compact: bool = True, chunk: int = DEFAULT_CHUNK):
        # build the jnp machinery (windows/unroll metadata) on the base
        # Machine; the pallas backend swaps in the batched chunk kernel below
        super().__init__(program, backend="jnp", compact=compact,
                         specialize=True, chunk=chunk)
        self._set_images(images, batch)
        B = self.B
        self.backend = backend
        # B=1 pays the plain specialized graph, not a vmap wrapper around it
        self._plain = backend != "pallas" and B == 1
        if backend == "pallas":
            from ..kernels import ops as kops
            self._run_chunk = jax.jit(kops.make_vcycle_chunk(
                program, self.C, self.chunk, interpret=interpret, batch=B))
        elif self._plain:
            self._run_chunk = jax.jit(self._b1chunk_impl)
        else:
            self._run_chunk = jax.jit(self._bchunk_impl)

    # ------------------------------------------------------------------
    def _set_images(self, images, batch: Optional[int]) -> None:
        """Load the per-stimulus init images into the batched ``[B, ...]``
        layout (sets ``breg0``/``bspad0``/``bgmem0`` and ``B``)."""
        C, R = self.C, self.R
        if images is None:
            assert batch is not None and batch >= 1, \
                "BatchedMachine needs init images or an explicit batch size"
            B = int(batch)
            self.breg0 = jnp.broadcast_to(self.reg0, (B,) + self.reg0.shape)
            self.bspad0 = jnp.broadcast_to(self.spad0,
                                           (B,) + self.spad0.shape)
            self.bgmem0 = jnp.broadcast_to(self.gmem0,
                                           (B,) + self.gmem0.shape)
        elif _is_stacked(images):
            # pre-stacked [B, ...] image arrays (Program.init_images_batch /
            # Bench.images_batch): already in the batched layout, no
            # per-stimulus copies
            ri, si, gi = images
            B = int(np.asarray(ri).shape[0])
            self.breg0 = jnp.asarray(np.asarray(ri)[:, :C, :R], U32)
            self.bspad0 = jnp.asarray(np.asarray(si)[:, :C], U32)
            self.bgmem0 = jnp.asarray(np.asarray(gi), U32)
        else:
            B = len(images)
            self.breg0 = jnp.asarray(
                np.stack([np.asarray(ri)[:C, :R] for ri, _, _ in images]),
                U32)
            self.bspad0 = jnp.asarray(
                np.stack([np.asarray(si)[:C] for _, si, _ in images]), U32)
            self.bgmem0 = jnp.asarray(
                np.stack([np.asarray(gi) for _, _, gi in images]), U32)
        self.B = B
        if self.Tpro:
            # iteration 0's prologue, once per stimulus (pure — regs only)
            self.breg0 = jax.vmap(self._apply_prologue)(
                self.breg0, self.bspad0, self.bgmem0)

    def rebind_images(self, images) -> None:
        """Swap in a new batch of per-stimulus init images *in place*.

        The batch size must match — the jitted chunk dispatch is
        shape-specialized on B — so only the initial state changes and the
        traced Vcycle graph stays hot. ``init_state()`` after a rebind
        starts the new stimuli. This is what keeps a serving daemon's
        compiled Simulations device-resident: per-batch image turnover
        costs one host→device transfer, never a retrace.
        """
        if images is None:
            raise ValueError("rebind_images needs init images")
        B = (int(np.asarray(images[0]).shape[0]) if _is_stacked(images)
             else len(images))
        if B != self.B:
            raise ValueError(
                f"rebind_images: batch size changed {self.B} -> {B}; "
                "build a new machine for a different B")
        self._set_images(images, None)

    def init_state(self) -> MachineState:
        B = self.B
        return MachineState(
            regs=self.breg0,
            spads=self.bspad0,
            gmem=self.bgmem0,
            flags=jnp.zeros((B, self.C), U32),
            cache_tags=-jnp.ones((B, self.cache_lines), jnp.int32),
            counters=jnp.zeros((B, 4), jnp.uint32),
        )

    def _b1chunk_impl(self, cyc, budget, carry):
        """B=1 fast path: dispatch the plain specialized chunk on the
        squeezed state — a batch of one should not pay the vmap wrapper
        (BENCH_batch showed B=1 "batched" at ~1.2-1.4x the cost of the
        single-stimulus engine for no benefit)."""
        c1, out = self._chunk_impl(cyc[0], budget,
                                   tuple(leaf[0] for leaf in carry))
        return c1[None], tuple(leaf[None] for leaf in out)

    def _bchunk_impl(self, cyc, budget, carry):
        """K Vcycles for all B elements under one scan; element b freezes
        (its state stops advancing) from its raising Vcycle on. The freeze
        predicate rides *into* the vmapped Vcycle — per-write-site gating
        on the unrolled path (no whole-state select per Vcycle), a
        per-Vcycle leaf select on the deep-schedule fallback."""
        def body(c, _):
            cyc, st = c
            active = (cyc < budget) & jnp.all(st[3] == 0, axis=1)   # [B]
            st = jax.vmap(self._vcycle)(st, active)
            return (cyc + active.astype(jnp.int32), st), None

        (cyc, carry), _ = jax.lax.scan(body, (cyc, carry), None,
                                       length=self.chunk)
        return cyc, carry

    def run(self, state: MachineState, num_cycles: int) -> MachineState:
        # stop dispatching only once *every* element froze
        carry = dispatch_chunks(
            self._run_chunk, jnp.zeros((self.B,), jnp.int32), tuple(state),
            self.chunk, int(num_cycles), lambda f: f.any(axis=1).all())
        return MachineState(*carry)

    # ---------------------------------------------- per-element access ----
    def element(self, state: MachineState, b: int) -> MachineState:
        """Single-stimulus view of batch element ``b`` (host-side)."""
        return MachineState(*(leaf[b] for leaf in state))

    def exceptions(self, state: MachineState, b: Optional[int] = None):
        if b is not None:
            return super().exceptions(self.element(state, b))
        return [super(BatchedMachine, self).exceptions(self.element(state, i))
                for i in range(self.B)]

    def read_output(self, state: MachineState, name: str, b: int = 0) -> int:
        return super().read_output(self.element(state, b), name)

    def read_reg(self, state: MachineState, rtl_name: str, b: int = 0) -> int:
        return super().read_reg(self.element(state, b), rtl_name)

    def perf(self, state: MachineState, b: Optional[int] = None):
        if b is not None:
            return super().perf(self.element(state, b))
        cnt = np.asarray(state.counters)
        vcycles = int(cnt[:, 0].sum())
        stalls = int(cnt[:, 3].sum())
        return {
            "batch": self.B,
            "vcycles": vcycles,                 # aggregate over the batch
            "ghits": int(cnt[:, 1].sum()),
            "gmisses": int(cnt[:, 2].sum()),
            "stall_cycles": stalls,
            "machine_cycles": vcycles * self.p.vcpl + stalls,
        }


class ShardedBatchedMachine(BatchedMachine):
    """Data-parallel batched execution over a device mesh: ``[D, B/D]``.

    ``BatchedMachine`` fills one device's data-parallel axis with B
    stimuli; this engine shards *the batch axis itself* over a 1-D mesh of
    D devices (the ROADMAP's next lever past PR 2, Parendi's thousand-way
    extension of the paper's model). Each device runs the **same**
    specialized Vcycle chunk — the exact ``_bchunk_impl`` graph (or the
    grid-over-B Pallas chunk kernel) — on its own ``B/D``-element shard of
    every state leaf under ``shard_map``. There is **no cross-device
    communication at all**: stimuli are independent, so the BSP exchange
    stays device-local and the only global coordination is the host's
    once-per-chunk exception sync.

    **Padding.** B is padded up to ``Bp = ceil(B/D)*D``. Padding elements
    replicate stimulus 0's images but start their per-element cycle
    counter at ``PAD_FROZEN_CYC`` (>= any budget), so their freeze
    predicate is never active: they execute nothing, raise nothing, and
    never appear in results — every accessor indexes only the logical
    ``B`` elements.

    **Sync model.** The per-device chunk additionally returns a ``[B/D]``
    ``frozen`` mask (raised an exception, or exhausted the budget —
    padding is always frozen by construction). The host's once-per-chunk
    sync reads only the assembled ``[Bp]`` bool mask — an any-reduce over
    the per-device masks, not the ``[Bp, C]`` flag planes — and stops
    dispatching when every element froze.

    Per-element semantics (freeze at the raising Vcycle, bit-exact state,
    counters) are exactly ``BatchedMachine``'s: the same chunk body runs,
    merely on a shard.
    """

    AXIS = "batch"

    def __init__(self, program: Program, images=None,
                 batch: Optional[int] = None, devices=None,
                 backend: str = "jnp", interpret: Optional[bool] = None,
                 compact: bool = True, chunk: int = DEFAULT_CHUNK):
        super().__init__(program, images=images, batch=batch,
                         backend="jnp", interpret=interpret,
                         compact=compact, chunk=chunk)
        devices = list(devices) if devices is not None else jax.devices()
        D = len(devices)
        self.D = D
        self.backend = backend
        self.mesh = Mesh(np.asarray(devices), (self.AXIS,))
        B = self.B
        Bp = -(-B // D) * D
        self.Bp = Bp
        self._pad_images()
        # padding elements start pre-frozen (see PAD_FROZEN_CYC)
        self._cyc0 = jnp.asarray(
            np.where(np.arange(Bp) < B, 0, PAD_FROZEN_CYC).astype(np.int32))

        if backend == "pallas":
            from ..kernels import ops as kops
            local_chunk = kops.make_vcycle_chunk(
                program, self.C, self.chunk, interpret=interpret,
                batch=Bp // D)
        else:
            local_chunk = self._bchunk_impl

        lead = lambda *tail: P(self.AXIS, *tail)
        state_specs = (lead(None, None), lead(None, None), lead(None),
                       lead(None), lead(None), lead(None))

        def device_chunk(cyc, budget, *leaves):
            """One device's K-Vcycle chunk on its local [B/D] shard; the
            extra ``frozen`` output is what the host syncs on."""
            cyc, out = local_chunk(cyc, budget, tuple(leaves))
            frozen = jnp.any(out[3] != 0, axis=1) | (cyc >= budget)
            return (cyc, frozen) + out

        sharded = shard_map(
            device_chunk, self.mesh,
            in_specs=(lead(), P()) + state_specs,
            out_specs=(lead(), lead()) + state_specs)
        self._run_chunk = jax.jit(
            lambda cyc, budget, carry: sharded(cyc, budget, *carry))

    # ------------------------------------------------------------------
    def _pad_images(self) -> None:
        """Pad the ``[B, ...]`` image arrays to ``[Bp, ...]`` with replicas
        of stimulus 0 (padding elements never execute — ``_cyc0`` starts
        them pre-frozen)."""
        B, Bp = self.B, self.Bp
        if Bp > B:
            def padb(a):
                return jnp.concatenate(
                    [a, jnp.broadcast_to(a[:1], (Bp - B,) + a.shape[1:])], 0)
            self.breg0 = padb(self.breg0)
            self.bspad0 = padb(self.bspad0)
            self.bgmem0 = padb(self.bgmem0)

    def rebind_images(self, images) -> None:
        super().rebind_images(images)      # checks the logical B matches
        self._pad_images()

    def init_state(self) -> MachineState:
        """Initial state in the sharded ``[Bp, ...]`` layout: every leaf
        is placed batch-sharded over the mesh up front, so the first chunk
        launch pays no resharding."""
        sh = lambda n_tail: NamedSharding(
            self.mesh, P(self.AXIS, *([None] * n_tail)))
        Bp = self.Bp
        return MachineState(
            regs=jax.device_put(self.breg0, sh(2)),
            spads=jax.device_put(self.bspad0, sh(2)),
            gmem=jax.device_put(self.bgmem0, sh(1)),
            flags=jax.device_put(jnp.zeros((Bp, self.C), U32), sh(1)),
            cache_tags=jax.device_put(
                -jnp.ones((Bp, self.cache_lines), jnp.int32), sh(1)),
            counters=jax.device_put(jnp.zeros((Bp, 4), jnp.uint32), sh(1)),
        )

    def run(self, state: MachineState, num_cycles: int) -> MachineState:
        """Chunked dispatch over the mesh: one host sync per chunk, on the
        assembled per-device frozen masks only."""
        cyc = self._cyc0
        budget = jnp.int32(num_cycles)
        n_launch = -(-int(num_cycles) // self.chunk) if num_cycles > 0 else 0
        carry = tuple(state)
        for _ in range(n_launch):
            cyc, frozen, *carry = self._run_chunk(cyc, budget, carry)
            carry = tuple(carry)
            if np.asarray(frozen).all():
                break
        return MachineState(*carry)

    def perf(self, state: MachineState, b: Optional[int] = None):
        if b is not None:
            return super().perf(state, b)
        # aggregate over the *logical* batch only (padding rows are all
        # zero by construction, but stay out of the contract regardless)
        logical = MachineState(*(leaf[:self.B] for leaf in state))
        return BatchedMachine.perf(self, logical)


def _scan_with_trace(step, carry, code):
    """Seed-style scan: run the (compact-capture) step but also emit the
    full per-slot result trace for the legacy exchange."""
    C = code.shape[1]

    def body(sc, instr):
        # capture every lane: cap = identity into a [C+1] buffer per slot
        cap = jnp.arange(C, dtype=jnp.int32)
        regs, spads, gmem, flags, tags, counters = sc
        sbuf = jnp.zeros((C + 1,), U32)
        (regs, spads, gmem, flags, tags, counters, sbuf), _ = step(
            (regs, spads, gmem, flags, tags, counters, sbuf), (instr, cap))
        return (regs, spads, gmem, flags, tags, counters), sbuf[:C]

    return jax.lax.scan(body, carry, code)
