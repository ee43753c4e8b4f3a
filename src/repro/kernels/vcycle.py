"""Pallas TPU kernels: the Vcycle slot loop over the core grid.

This is the compute hot-spot of the whole system — the inner interpreter that
executes ``t_compute`` slots for every core, every simulated RTL cycle.

**Layout: cores on lanes.** Core *c* of the paper's MIMD grid is lane *c* of
every vector, the natural SIMD reading of lockstep execution. Inside a kernel
the state is stored transposed against the engines' ``[C, R]`` carry:

  * ``code  [T, 8, Cp]`` int32 — the 7 instruction fields of slot *t* as
    rows (padded to one 8-row sublane tile), one lane per core;
  * ``luts  [L, 16, Cp]`` int32 — LUT entry *l*, pattern *p*, per core;
  * ``regs  [Rp, Cp]``, ``spads [Sp, Cp]`` int32 — register file and
    scratchpad, one row per register/word;
  * ``flags [1, Cp]`` int32 — first exception id per core (0 = none).

``Cp`` pads the core count to whole 128-lane vregs and ``Rp``/``Sp`` pad
the row counts to whole 8-row sublane tiles; padded cores are all-NOP and
never write. Values are 16-bit words, so the int32 storage is exact and
allows the integer reductions Mosaic implements.

**Access without gathers.** Mosaic does not lower per-lane dynamic gathers
and scatters. Every register, LUT and scratchpad access is therefore a
one-hot compare-select over the row axis (a read reduces the selected rows
to one ``[1, Cp]`` row; a write selects the new value into the matching
row). The BSP exchange walks the program's static SEND table from SMEM and
moves one word per send with a dynamic row access and a lane select.

The ALU semantics are ``core.bsp._alu_branches`` — the same definition the
jnp engines use — so the kernels and the engines cannot drift apart. Tests
run the kernels in interpret mode on the CPU and compare them bit-exactly
with ``ref.vcycle_ref`` and the jnp engines; ``tests/test_tpu_compile.py``
compiles them for a described TPU v5e at full-scale shapes.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..core.bsp import _NO_WRITE_OPS, _alu_branches
from ..core.isa import Op

U32 = jnp.uint32
I32 = jnp.int32

LANES = 128          # cores per vreg row
SUBLANES = 8         # rows per vreg
CODE_ROWS = 8        # 7 instruction fields, padded to one sublane tile

# cores per grid step of the per-Vcycle kernel (``vcycle_pallas``)
DEFAULT_TILE = LANES

# opcodes the Pallas path never executes (privileged off-chip traffic stays
# in the jnp engine — ``ops.make_vcycle_chunk`` rejects such programs)
_GLOBAL_OPS = frozenset((Op.GLD, Op.GST))


def round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def _interpret(interpret: Optional[bool]) -> bool:
    from .ops import resolve_interpret
    return resolve_interpret(interpret)


def _iota(shape, axis):
    return jax.lax.broadcasted_iota(I32, shape, axis)


def _loop(lo: int, hi: int, body) -> None:
    """Run ``body(t)`` for t in [lo, hi) (no carry)."""
    if hi > lo:
        jax.lax.fori_loop(lo, hi, lambda t, c: (body(t), c)[1], 0)


def _slot(t, code, luts, regs, spads, flags, trace, *, ops, spad_words):
    """Execute slot ``t`` in place on the lane-major state refs (all 2-D:
    ``regs [Rp, Cp]``, ``spads [Sp, Cp]``, ``flags [1, Cp]``); ``trace``
    receives the slot's result row. Semantics are those of
    ``core.bsp.make_window_step`` with ``window=1``: all reads happen at the
    slot start, then register writes, stores and exceptions."""
    instr = code[t]                                   # [8, Cp]

    def field(k):
        return instr[k:k + 1, :]                      # [1, Cp]

    op = field(0)
    dst = field(1)
    imm = field(6).astype(U32)
    r = regs[...]
    rows = _iota(r.shape, 0)

    def read(k):
        sel = jnp.where(rows == field(k), r, 0)
        return jnp.sum(sel, axis=0, keepdims=True).astype(U32)

    zero = jnp.zeros(op.shape, U32)
    need_v3 = bool(ops & {Op.ADDC, Op.CARRY, Op.SUBB, Op.BORROW, Op.MUX,
                          Op.ST, Op.LUT})
    need_v4 = Op.LUT in ops
    v1, v2 = read(2), read(3)
    v3 = read(4) if need_v3 else zero
    v4 = read(5) if need_v4 else zero

    lut_tt = None
    if Op.LUT in ops:
        L = luts.shape[0]
        entry = jnp.where(imm >= L - 1, L - 1, field(6))   # min(imm, L-1)

        def gather_entry(l, tt):
            return tt + jnp.where(entry == l, luts[l], 0)

        tt = jax.lax.fori_loop(0, L, gather_entry,
                               jnp.zeros(luts.shape[1:], I32))
        lut_tt = lambda p: tt[p:p + 1, :].astype(U32)
    if ops & {Op.LD, Op.ST}:
        s = spads[...]
        srows = _iota(s.shape, 0)
    ld_val = None
    if Op.LD in ops:
        ld_sel = jnp.where(srows == (v1 % spad_words).astype(I32), s, 0)
        ld_val = jnp.sum(ld_sel, axis=0, keepdims=True).astype(U32)

    result = zero
    for code_op, val in _alu_branches(ops, v1, v2, v3, v4, imm, lut_tt,
                                      ld_val):
        result = jnp.where(op == int(code_op), val, result)

    if ops - set(_NO_WRITE_OPS):
        no_write = dst == 0
        for o in _NO_WRITE_OPS:
            if o in ops:
                no_write = no_write | (op == int(o))
        wmask = (rows == dst) & ~no_write
        regs[...] = jnp.where(wmask, result.astype(I32), r)
    if Op.ST in ops:
        st = (op == int(Op.ST)) & (v3 != 0)
        smask = (srows == (v1 % spad_words).astype(I32)) & st
        spads[...] = jnp.where(smask, v2.astype(I32), s)
    if Op.EXPECT in ops:
        exc = (op == int(Op.EXPECT)) & (v1 != v2)
        f = flags[...]
        flags[...] = jnp.where((f == 0) & exc, imm.astype(I32), f)
    trace[pl.ds(t, 1), :] = (result & 0xFFFF).astype(I32)


# ======================================================================
# Per-Vcycle kernel (seed path): one Vcycle, no exchange, full trace
# ======================================================================

def _vcycle_kernel(code_ref, luts_ref, regs_in, spads_in, flags_in,
                   regs_ref, spads_ref, flags_ref, trace_ref, *,
                   num_slots: int, spad_words: int):
    regs_ref[...] = regs_in[...]
    spads_ref[...] = spads_in[...]
    flags_ref[...] = flags_in[...]
    ops = frozenset(Op) - _GLOBAL_OPS
    _loop(0, num_slots, functools.partial(
        _slot, code=code_ref, luts=luts_ref, regs=regs_ref,
        spads=spads_ref, flags=flags_ref, trace=trace_ref, ops=ops,
        spad_words=spad_words))


def vcycle_pallas(code: jax.Array, luts: jax.Array, regs: jax.Array,
                  spads: jax.Array, flags: jax.Array,
                  tile: int = DEFAULT_TILE,
                  interpret: Optional[bool] = None,
                  ) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """One Vcycle over all cores, in the engines' layout: code [T, C, 7]
    int32, luts [C, L, 16], regs [C, R], spads [C, S], flags [C] uint32.
    Returns (regs, spads, flags, trace[T, C]).

    The grid walks the cores ``tile`` lanes at a time; on a TPU ``tile``
    must be a multiple of 128 or cover every core."""
    T, C, _ = code.shape
    R, S, L = regs.shape[1], spads.shape[1], luts.shape[1]
    tile = min(tile, C)
    Cp = round_up(C, tile)
    Rp, Sp = round_up(R, SUBLANES), round_up(S, SUBLANES)
    code_k, luts_k = lane_major_program(code, luts, Cp)
    regs_k = lane_major_state(regs[None], Rp, Cp)[0]
    spads_k = lane_major_state(spads[None], Sp, Cp)[0]
    flags_k = lane_major_state(flags[None, :, None], 1, Cp)[0]

    kernel = functools.partial(_vcycle_kernel, num_slots=T,
                               spad_words=max(S, 1))
    lanes = lambda rows: pl.BlockSpec((rows, tile), lambda i: (0, i))
    regs_o, spads_o, flags_o, trace = pl.pallas_call(
        kernel,
        grid=(Cp // tile,),
        in_specs=[
            pl.BlockSpec((T, CODE_ROWS, tile), lambda i: (0, 0, i)),
            pl.BlockSpec((L, 16, tile), lambda i: (0, 0, i)),
            lanes(Rp), lanes(Sp), lanes(1),
        ],
        out_specs=[lanes(Rp), lanes(Sp), lanes(1), lanes(T)],
        out_shape=(
            jax.ShapeDtypeStruct((Rp, Cp), I32),
            jax.ShapeDtypeStruct((Sp, Cp), I32),
            jax.ShapeDtypeStruct((1, Cp), I32),
            jax.ShapeDtypeStruct((T, Cp), I32),
        ),
        interpret=_interpret(interpret),
    )(code_k, luts_k, regs_k, spads_k, flags_k)
    return (engine_state(regs_o[None], R, C)[0],
            engine_state(spads_o[None], S, C)[0],
            engine_state(flags_o[None], 1, C)[0, :, 0],
            trace[:, :C].astype(U32))


# ======================================================================
# Layout conversion between the engines and the kernels
# ======================================================================

def lane_major_program(code, luts, Cp: int):
    """Engine-layout program tables (code [T, C, 7], luts [C, L, 16]) ->
    the kernels' lane-major ``code [T, 8, Cp]`` and ``luts [L, 16, Cp]``
    int32 (padded cores are all-NOP)."""
    T, C, F = code.shape
    code_k = jnp.pad(jnp.asarray(code, I32).transpose(0, 2, 1),
                     ((0, 0), (0, CODE_ROWS - F), (0, Cp - C)))
    luts_k = jnp.pad(jnp.asarray(luts).astype(I32).transpose(1, 2, 0),
                     ((0, 0), (0, 0), (0, Cp - C)))
    return code_k, luts_k


def lane_major_state(x, rows: int, Cp: int):
    """Engine-layout batched state ``[B, C, N]`` (uint32) -> kernel layout
    ``[B, rows, Cp]`` int32 (zero padded)."""
    B, C, N = x.shape
    return jnp.pad(x.astype(I32).transpose(0, 2, 1),
                   ((0, 0), (0, rows - N), (0, Cp - C)))


def engine_state(y, N: int, C: int):
    """Inverse of :func:`lane_major_state`: ``[B, rows, Cp]`` int32 ->
    ``[B, C, N]`` uint32."""
    return y[:, :N, :C].transpose(0, 2, 1).astype(U32)


# ======================================================================
# Chunked K-Vcycle kernel (specialized fast path)
#
# One launch simulates up to K RTL cycles for whole machines: the register
# files and scratchpads stay VMEM-resident across all K Vcycles, the BSP
# exchange happens in-kernel from the static SEND table, and each Vcycle is
# predicated on the exception flags so a program that raises mid-chunk
# freezes at the raising cycle, not at the chunk boundary.
# ======================================================================

def _chunk_kernel(cyc_ref, budget_ref, xchg_ref, code_ref, luts_ref,
                  regs_in, spads_in, flags_in, regs_out, spads_out,
                  flags_out, nexec_ref, trace_ref, *, num_slots: int,
                  K: int, n_sends: int, op_set, spad_words: int,
                  num_pro: int):
    """One grid step = one batch element. ``xchg`` is the SMEM send table
    ``[src_slot | src_core | dst_core | dst_reg]``, each ``M`` long.

    ``num_pro > 0`` marks a modulo-pipelined program: code rows
    ``[0, num_pro)`` are the *next* Vcycle's hoisted pure ops. Each Vcycle
    runs the steady-state body (rows ``[num_pro, T)``), the exchange, then
    the prologue on the post-exchange state — only if the cycle raised no
    exception, so a raising cycle never commits cycle k+1's in-flight
    prologue. Iteration 0's prologue is applied by ``Machine.init_state``."""
    b = pl.program_id(0)
    regs_out[...] = regs_in[...]
    spads_out[...] = spads_in[...]
    flags_out[...] = flags_in[...]
    regs, flags = regs_out.at[0], flags_out.at[0]
    ops = (frozenset(Op) if op_set is None else frozenset(op_set)) \
        - _GLOBAL_OPS
    slot = functools.partial(
        _slot, code=code_ref, luts=luts_ref, regs=regs,
        spads=spads_out.at[0], flags=flags, trace=trace_ref, ops=ops,
        spad_words=spad_words)
    M = xchg_ref.shape[0] // 4
    lane = _iota((1, regs.shape[1]), 1)

    def send(s):
        src = trace_ref[pl.ds(xchg_ref[s], 1), :]
        val = jnp.sum(jnp.where(lane == xchg_ref[M + s], src, 0), axis=1,
                      keepdims=True)
        row = pl.ds(xchg_ref[3 * M + s], 1)
        regs[row, :] = jnp.where(lane == xchg_ref[2 * M + s], val,
                                 regs[row, :])

    def clear():
        return jnp.max(flags[...]) == 0

    base = cyc_ref[b]
    budget = budget_ref[0]

    def vcycle(k, nexec):
        active = (base + nexec < budget) & clear()

        @pl.when(active)
        def _():
            _loop(num_pro, num_slots, slot)
            _loop(0, n_sends, send)
            if num_pro:
                @pl.when(clear())
                def _():
                    _loop(0, num_pro, slot)

        return nexec + active.astype(I32)

    nexec_ref[b] = jax.lax.fori_loop(0, K, vcycle, jnp.int32(0))


def vcycle_chunk_pallas_batched(code: jax.Array, luts: jax.Array,
                                xchg: jax.Array, regs: jax.Array,
                                spads: jax.Array, flags: jax.Array,
                                cyc: jax.Array, budget: jax.Array, *,
                                K: int, n_sends: int, spad_words: int,
                                op_set=None, num_pro: int = 0,
                                interpret: Optional[bool] = None,
                                ) -> Tuple[jax.Array, jax.Array, jax.Array,
                                           jax.Array]:
    """Up to K Vcycles for B whole machines in one launch (grid over B),
    lane-major: code [T, 8, Cp] | luts [L, 16, Cp] | xchg [4*M] int32 |
    regs [B, Rp, Cp] | spads [B, Sp, Cp] | flags [B, 1, Cp] | cyc [B] |
    budget [1]. Returns (regs, spads, flags, n_executed[B]).

    ``B`` is whatever batch the caller holds — the whole stimulus batch on
    one device, or a ``B/D`` shard when the call is traced inside
    ``shard_map`` (``core.bsp.ShardedBatchedMachine``). Nothing in the
    kernel is global-batch-aware: the grid, the block specs and the
    per-element freeze predicate all derive from the local leading axis,
    which is exactly what lets the device mesh carry the batch axis."""
    T, _, Cp = code.shape
    B, Rp, _ = regs.shape
    Sp = spads.shape[1]
    kernel = functools.partial(
        _chunk_kernel, num_slots=T, K=K, n_sends=n_sends, op_set=op_set,
        spad_words=spad_words, num_pro=num_pro)
    whole = lambda shape: pl.BlockSpec(shape, lambda b, *_: (0,) * len(shape))
    elem = lambda rows: pl.BlockSpec((1, rows, Cp), lambda b, *_: (b, 0, 0))
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,                    # cyc, budget, xchg
            grid=(B,),
            in_specs=[whole(code.shape), whole(luts.shape),
                      elem(Rp), elem(Sp), elem(1)],
            out_specs=[elem(Rp), elem(Sp), elem(1),
                       pl.BlockSpec(memory_space=pltpu.SMEM)],
            scratch_shapes=[pltpu.VMEM((T, Cp), I32)],      # slot trace
        ),
        out_shape=(
            jax.ShapeDtypeStruct((B, Rp, Cp), I32),
            jax.ShapeDtypeStruct((B, Sp, Cp), I32),
            jax.ShapeDtypeStruct((B, 1, Cp), I32),
            jax.ShapeDtypeStruct((B,), I32),
        ),
        interpret=_interpret(interpret),
    )(cyc, budget, xchg, code, luts, regs, spads, flags)


def vcycle_chunk_pallas(code: jax.Array, luts: jax.Array, xchg: jax.Array,
                        regs: jax.Array, spads: jax.Array, flags: jax.Array,
                        cyc: jax.Array, budget: jax.Array, *, K: int,
                        n_sends: int, spad_words: int, op_set=None,
                        num_pro: int = 0, interpret: Optional[bool] = None,
                        ) -> Tuple[jax.Array, jax.Array, jax.Array,
                                   jax.Array]:
    """Up to K Vcycles for one whole machine in one launch (exchange
    in-kernel): the batched kernel at B=1. regs [Rp, Cp] | spads [Sp, Cp]
    | flags [1, Cp] | cyc [1] | budget [1]. Returns (regs, spads, flags,
    n_executed[1])."""
    regs_o, spads_o, flags_o, nexec = vcycle_chunk_pallas_batched(
        code, luts, xchg, regs[None], spads[None], flags[None], cyc,
        budget, K=K, n_sends=n_sends, spad_words=spad_words, op_set=op_set,
        num_pro=num_pro, interpret=interpret)
    return regs_o[0], spads_o[0], flags_o[0], nexec
