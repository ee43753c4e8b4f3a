"""Jitted wrappers around the Pallas kernels, and the interpret-mode policy.

``resolve_interpret`` decides whether a ``pallas_call`` runs compiled or in
interpret mode: interpret exactly when JAX's backend is the CPU. Every
kernel and engine signature takes ``interpret=None`` ("resolve"); an
explicit bool is for tests and compile rehearsals.

``make_vcycle`` binds a compiled :class:`~repro.core.compile.Program` to the
per-Vcycle Pallas kernel (seed path, kept as the ``specialize=False``
baseline); ``make_vcycle_chunk`` binds it to the chunked K-Vcycle kernel —
the specialized fast path with VMEM-resident state, in-kernel exchange and
per-Vcycle exception predication. Both adapt the
(regs, spads, gmem, flags, tags, counters) carry used by ``core.bsp.Machine``
and convert it to and from the kernels' lane-major layout.
Programs with privileged off-chip traffic (GLD/GST) fall back to the jnp
engine — the privileged core is special in the paper too (§5.3).
"""
from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from .vcycle import (DEFAULT_TILE, LANES, SUBLANES, engine_state,
                     lane_major_program, lane_major_state, round_up,
                     vcycle_chunk_pallas, vcycle_chunk_pallas_batched,
                     vcycle_pallas)


def resolve_interpret(interpret: Optional[bool] = None) -> bool:
    """Pallas interpret mode exactly when JAX's default backend is the CPU;
    an explicit bool is returned unchanged."""
    if interpret is None:
        return jax.default_backend() == "cpu"
    return bool(interpret)


def _reject_global(program) -> None:
    if program.has_global:
        raise ValueError(
            "Pallas path does not execute privileged GLD/GST programs; "
            "use backend='jnp' (the paper's privileged core is also special)")


def make_vcycle(program, C: int, interpret: Optional[bool] = None,
                tile: int = DEFAULT_TILE) -> Callable:
    """Returns vcycle(carry) -> (carry, trace) on the Pallas path."""
    _reject_global(program)
    code_j = jnp.asarray(program.code[:C].transpose(1, 0, 2), jnp.int32)
    luts_j = jnp.asarray(program.luts[:C], jnp.uint32)

    @jax.jit
    def vcycle(carry):
        regs, spads, gmem, flags, tags, counters = carry
        regs, spads, flags, trace = vcycle_pallas(
            code_j, luts_j, regs, spads, flags, tile=tile,
            interpret=interpret)
        return (regs, spads, gmem, flags, tags, counters), trace

    return vcycle


def exchange_table(program) -> np.ndarray:
    """The SMEM send table of the chunk kernel: ``[src_slot | src_core |
    dst_core | dst_reg]`` flattened, each ``max(n_sends, 1)`` long."""
    n = program.n_sends
    table = np.zeros((4, max(n, 1)), np.int32)
    table[:, :n] = (program.xchg_src_slot, program.xchg_src_core,
                    program.xchg_dst_core, program.xchg_dst_reg)
    return table.reshape(-1)


def make_vcycle_chunk(program, C: int, K: int,
                      interpret: Optional[bool] = None,
                      batch: Optional[int] = None) -> Callable:
    """Bind ``program`` to the chunked K-Vcycle kernel.

    Returns ``chunk(cyc, budget, carry) -> (cyc, carry)`` compatible with
    ``Machine._run_chunk``: one call advances the machine by up to K
    Vcycles (bounded by ``budget`` and frozen by exceptions), with the BSP
    exchange performed in-kernel.

    ``batch=B`` binds the batched-stimulus kernel instead: the carry
    leaves have a leading [B] axis, ``cyc`` is ``[B]`` and the kernel runs
    one grid step per batch element (each element's state VMEM-resident
    for the whole chunk, exceptions frozen per element).

    The batched binding composes with the device mesh: under
    ``ShardedBatchedMachine(backend="pallas")`` this factory is called
    with the **device-local** batch ``B/D`` and the returned ``bchunk``
    runs inside ``shard_map`` — the kernel's grid axis then covers one
    shard, per-element freezing stays device-local (the per-element
    ``cyc``/flags predicate needs no cross-device state), and the shared
    program tables (code/luts/exchange) are closed-over constants
    replicated to every device.
    """
    _reject_global(program)
    # cores on lanes, padded to whole vregs; padded lanes are all-NOP
    Cp = round_up(C, LANES)
    code_k, luts_k = lane_major_program(
        program.code[:C].transpose(1, 0, 2), program.luts[:C], Cp)
    xchg = jnp.asarray(exchange_table(program))
    static = dict(K=K, n_sends=program.n_sends, op_set=program.op_set(),
                  num_pro=int(program.pipe_prologue), interpret=interpret)

    def to_kernel(regs, spads, flags):
        """Batched engine-layout state -> lane-major kernel state."""
        R, S = regs.shape[2], spads.shape[2]
        return (lane_major_state(regs, round_up(R, SUBLANES), Cp),
                lane_major_state(spads, round_up(S, SUBLANES), Cp),
                lane_major_state(flags[:, :, None], 1, Cp))

    def to_engine(regs_k, spads_k, flags_k, R, S):
        return (engine_state(regs_k, R, C), engine_state(spads_k, S, C),
                engine_state(flags_k, 1, C)[..., 0])

    if batch is not None:
        def bchunk(cyc, budget, carry):
            regs, spads, gmem, flags, tags, counters = carry
            R, S = regs.shape[2], spads.shape[2]
            regs_k, spads_k, flags_k, nexec = vcycle_chunk_pallas_batched(
                code_k, luts_k, xchg, *to_kernel(regs, spads, flags),
                cyc.astype(jnp.int32), jnp.full((1,), budget, jnp.int32),
                spad_words=max(S, 1), **static)
            regs, spads, flags = to_engine(regs_k, spads_k, flags_k, R, S)
            counters = counters.at[:, 0].add(nexec.astype(jnp.uint32))
            return cyc + nexec, (regs, spads, gmem, flags, tags, counters)

        return bchunk

    def chunk(cyc, budget, carry):
        regs, spads, gmem, flags, tags, counters = carry
        R, S = regs.shape[1], spads.shape[1]
        state_k = [a[0] for a in to_kernel(regs[None], spads[None],
                                           flags[None])]
        regs_k, spads_k, flags_k, nexec = vcycle_chunk_pallas(
            code_k, luts_k, xchg, *state_k,
            jnp.full((1,), cyc, jnp.int32), jnp.full((1,), budget, jnp.int32),
            spad_words=max(S, 1), **static)
        regs, spads, flags = (a[0] for a in to_engine(
            regs_k[None], spads_k[None], flags_k[None], R, S))
        counters = counters.at[0].add(nexec[0].astype(jnp.uint32))
        return cyc + nexec[0], (regs, spads, gmem, flags, tags, counters)

    return chunk
