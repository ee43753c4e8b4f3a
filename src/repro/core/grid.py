"""Multi-device static BSP execution: cores sharded over a device mesh.

This is the paper's NoC scaled past one chip: a Manticore grid too large for
one accelerator is sharded over a TPU mesh, and the Vcycle-boundary exchange
becomes **one statically-shaped ``all_to_all``** per Vcycle under
``shard_map`` — the BSP superstep's communication phase. Because the compiler
knows every SEND (source core/slot, destination core/register) at compile
time, the per-device-pair message matrix is a *static* numpy table: message
``k`` from device ``s`` to device ``d`` always carries the same SEND value
into the same (core, register) cell. No runtime routing, no dynamic shapes —
the schedule is collision-free by construction, exactly as on the paper's
deflection-free torus.

The slot loop is the same partially-evaluated step the single-device engine
scans (``core.bsp.make_slot_step``): opcode branches specialized to the
program, and SEND values scattered at trace time into a compact per-device
buffer — the ``all_to_all`` payload is gathered straight from that buffer,
never from a [T, C] trace.

Vcycles are dispatched in **chunks of K** under one ``lax.scan`` (matching
the single-device engine): each Vcycle is predicated on the exception
flags, and the host syncs the flags once per chunk instead of compiling a
``num_cycles``-static ``while_loop``.

``GridMachine(prog, mesh, images=[...])`` runs **B batched stimuli**: every
state leaf gains a leading ``[B]`` axis (still sharded over the cores
axis), the per-device slot scan is ``vmap``-ed over B, and the per-Vcycle
``all_to_all`` moves the whole ``[B, n_sends]`` payload in a single
collective. Exceptions freeze per batch element.

Per-device state (register files, scratchpads, flags) lives sharded on the
``cores`` axis; the privileged core's global memory rides along sharded per
device (only its owner mutates it).
"""
from __future__ import annotations

from typing import Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..distributed.compat import shard_map
from ..spans import to_host
from .bsp import (DEFAULT_CHUNK, MachineState, dispatch_chunks, jit_chunk,
                  make_slot_step)
from .compile import Program


class ExchangeTables(NamedTuple):
    """Static per-device message tables ([D, D, M] sharded on axis 0)."""
    snd_idx: jax.Array    # index into the local compact SEND buffer
    rcv_core: jax.Array   # local core to write (receive side)
    rcv_reg: jax.Array    # machine register to write
    rcv_valid: jax.Array  # bool


def _build_exchange(program: Program, D: int, cl: int,
                    Cp: int) -> Tuple[np.ndarray, ...]:
    """Group the compile-time SEND table by (src_dev, dst_dev).

    Returns (snd_idx, rcv_core, rcv_reg, rcv_valid, cap, L): each device
    captures its own SENDs into a compact local buffer of ``L + 1`` words
    (``cap`` is the [T, Cp] capture-index table, sacrificial index ``L``),
    and message ``k`` of pair (s, d) reads local buffer slot
    ``snd_idx[s, d, k]``.
    """
    n = program.n_sends
    T = program.code.shape[1]
    loc_li = np.zeros((n,), np.int32)        # global send -> local index
    counts = [0] * D
    for i in range(n):
        sd = int(program.xchg_src_core[i]) // cl
        loc_li[i] = counts[sd]
        counts[sd] += 1
    L = max(counts) if counts else 0

    msgs: Dict[Tuple[int, int], list] = {}
    for i in range(n):
        sc = int(program.xchg_src_core[i]); dc = int(program.xchg_dst_core[i])
        sd, dd = sc // cl, dc // cl
        msgs.setdefault((sd, dd), []).append(
            (int(loc_li[i]), dc % cl, int(program.xchg_dst_reg[i])))
    mmax = max((len(v) for v in msgs.values()), default=0)
    mmax = max(mmax, 1)
    shape = (D, D, mmax)
    snd_idx = np.full(shape, L, np.int32)    # invalid -> sacrificial slot
    rcv_core = np.zeros(shape, np.int32)
    rcv_reg = np.zeros(shape, np.int32)
    rcv_valid = np.zeros(shape, bool)
    for (sd, dd), lst in msgs.items():
        for k, (li, dcore, dreg) in enumerate(lst):
            snd_idx[sd, dd, k] = li
            # receive tables are indexed by the *receiver*: row = src device
            rcv_core[dd, sd, k] = dcore
            rcv_reg[dd, sd, k] = dreg
            rcv_valid[dd, sd, k] = True

    cap = np.full((T, Cp), L, np.int32)
    for i in range(n):
        cap[int(program.xchg_src_slot[i]),
            int(program.xchg_src_core[i])] = loc_li[i]
    return snd_idx, rcv_core, rcv_reg, rcv_valid, cap, L


class GridMachine:
    """Static BSP executor over a device mesh (axis name: 'cores').

    ``images=[(reg_init, spad_init, gmem_init), ...]`` selects batched
    mode: B stimuli of the one compiled program run together, each state
    leaf carrying a leading [B] axis.
    """

    AXIS = "cores"

    def __init__(self, program: Program, mesh: Mesh,
                 images=None, chunk: int = DEFAULT_CHUNK):
        self.p = program
        self.mesh = mesh
        self.chunk = max(1, int(chunk))
        D = int(np.prod([mesh.shape[a] for a in mesh.axis_names]))
        assert mesh.axis_names == (self.AXIS,), \
            "GridMachine expects a 1-D mesh over axis 'cores'"
        self.D = D
        hw = program.hw
        C = program.used_cores
        cl = max(1, -(-C // D))            # cores per device
        Cp = cl * D
        self.C, self.cl, self.Cp = C, cl, Cp
        self.B = len(images) if images is not None else None
        R = program.used_reg_count()       # active-register compaction
        self.R = R

        code = np.zeros((program.code.shape[1], Cp, 7), np.int32)
        code[:, :C] = program.code[:C].transpose(1, 0, 2)
        luts = np.zeros((Cp,) + program.luts.shape[1:], np.uint32)
        luts[:C] = program.luts[:C]

        def pad_cores(a, fill=0):
            out = np.full((Cp,) + a.shape[1:], fill, np.uint32)
            out[:C] = a[:C]
            return out

        if images is None:
            regs = pad_cores(program.reg_init[:, :R])
            spads = pad_cores(program.spad_init)
            gmem = np.broadcast_to(program.gmem_init.astype(np.uint32),
                                   (D,) + program.gmem_init.shape).copy()
        else:
            regs = np.stack([pad_cores(np.asarray(ri)[:, :R])
                             for ri, _, _ in images])
            spads = np.stack([pad_cores(np.asarray(si))
                              for _, si, _ in images])
            gmem = np.stack([
                np.broadcast_to(np.asarray(gi).astype(np.uint32),
                                (D,) + np.asarray(gi).shape)
                for _, _, gi in images]).copy()

        (snd_idx, rcv_core, rcv_reg, rcv_valid, cap,
         L) = _build_exchange(program, D, cl, Cp)
        self.L = L

        sh = lambda *spec: NamedSharding(mesh, P(*spec))
        bsp = (None,) if self.B is not None else ()   # leading batch axis
        # code/cap are [T, Cp(, 7)]: shard the core axis
        self.code = jax.device_put(code, sh(None, self.AXIS, None))
        self.cap = jax.device_put(cap, sh(None, self.AXIS))
        self.luts = jax.device_put(luts, sh(self.AXIS))
        self.reg0 = jax.device_put(regs, sh(*bsp, self.AXIS))
        self.spad0 = jax.device_put(spads, sh(*bsp, self.AXIS))
        self.gmem0 = jax.device_put(gmem, sh(*bsp, self.AXIS))

        self.xt = ExchangeTables(*[
            jax.device_put(a, sh(self.AXIS))
            for a in (snd_idx, rcv_core, rcv_reg, rcv_valid)])
        self.cache_lines = hw.cache_words // hw.cache_line_words
        op_set = program.op_set()

        def local_vcycle(code, cap, luts, regs, spads, gmem, flags, tags,
                         counters):
            """One device's slot scan for one stimulus (local shapes:
            code [T, cl, 7], gmem [G]); returns the 7-tuple carry whose
            last entry is the compact [L + 1] SEND buffer."""
            local_step = make_slot_step(
                luts, max(spads.shape[1], 1), max(gmem.shape[0], 1),
                self.cache_lines, hw.cache_line_words, hw.cache_hit_stall,
                hw.cache_miss_stall, op_set=op_set)
            sbuf = jnp.zeros((L + 1,), jnp.uint32)
            carry = (regs, spads, gmem, flags, tags, counters, sbuf)
            carry, _ = jax.lax.scan(local_step, carry, (code, cap))
            return carry

        def scatter_in(regs, inb, rcv_core, rcv_reg, rcv_valid):
            # masked scatter: invalid entries land in a sacrificial register
            # column appended to the register file
            pad = jnp.zeros((regs.shape[0], 1), regs.dtype)
            regs_x = jnp.concatenate([regs, pad], axis=1)
            dst_core = jnp.where(rcv_valid, rcv_core, 0).reshape(-1)
            dst_reg = jnp.where(rcv_valid, rcv_reg,
                                regs.shape[1]).reshape(-1)
            regs_x = regs_x.at[dst_core, dst_reg].set(inb.reshape(-1))
            return regs_x[:, :-1]

        if self.B is None:
            def device_vcycle(code, cap, luts, regs, spads, gmem, flags,
                              tags, counters, xt: ExchangeTables):
                # local shapes: code [T, cl, 7]; gmem [1, G]; xt [1, D, M]
                carry = local_vcycle(code, cap, luts, regs, spads, gmem[0],
                                     flags, tags[0], counters[0])
                regs, spads, gmem, flags, tags, counters, sbuf = carry
                # ---- BSP exchange: one all_to_all per Vcycle, payload
                # read straight from the compact SEND buffer ----
                out = sbuf[xt.snd_idx[0]]              # [D, M]
                inb = jax.lax.all_to_all(out, self.AXIS, 0, 0, tiled=True)
                regs = scatter_in(regs, inb, xt.rcv_core[0], xt.rcv_reg[0],
                                  xt.rcv_valid[0])
                counters = counters.at[0].add(jnp.uint32(1))
                return (regs, spads, gmem[None], flags, tags[None],
                        counters[None])
        else:
            def device_vcycle(code, cap, luts, regs, spads, gmem, flags,
                              tags, counters, xt: ExchangeTables):
                # local shapes: regs [B, cl, R]; gmem [B, 1, G]
                carry = jax.vmap(
                    lambda r, s, g, f, t, cn: local_vcycle(
                        code, cap, luts, r, s, g[0], f, t[0], cn[0])
                )(regs, spads, gmem, flags, tags, counters)
                regs, spads, gmem, flags, tags, counters, sbuf = carry
                # ---- BSP exchange: the whole [B, n_sends] payload moves
                # in ONE collective per Vcycle ----
                out = sbuf[:, xt.snd_idx[0]]           # [B, D, M]
                inb = jax.lax.all_to_all(out, self.AXIS, 1, 1, tiled=True)
                regs = jax.vmap(
                    lambda r, i: scatter_in(r, i, xt.rcv_core[0],
                                            xt.rcv_reg[0], xt.rcv_valid[0])
                )(regs, inb)
                counters = counters.at[:, 0].add(jnp.uint32(1))
                return (regs, spads, gmem[:, None], flags, tags[:, None],
                        counters[:, None])

        spec_c = P(self.AXIS)
        bspec = lambda *tail: P(*bsp, self.AXIS, *tail)
        state_specs = (bspec(None), bspec(None), bspec(None), bspec(),
                       bspec(None), bspec(None))
        self._vcycle = shard_map(
            device_vcycle, mesh=mesh,
            in_specs=(P(None, self.AXIS, None), P(None, self.AXIS), spec_c)
            + state_specs + (ExchangeTables(*([spec_c] * 4)),),
            out_specs=state_specs,
            check_vma=False)

        def step_state(st):
            out = self._vcycle(self.code, self.cap, self.luts, st[0], st[1],
                               st[2], st[3], st[4], st[5], self.xt)
            return out

        if self.B is None:
            def active_of(cyc, budget, st):
                return (cyc < budget) & jnp.all(st[3] == 0)       # scalar
        else:
            def active_of(cyc, budget, st):
                return (cyc < budget) & ~jnp.any(st[3] != 0, axis=1)  # [B]

        def run_chunk(cyc, budget, state):
            def body(c, _):
                cyc, st = c
                act = active_of(cyc, budget, st)
                new = step_state(st)
                sel = lambda n, o: jnp.where(
                    act if act.ndim == 0
                    else act.reshape((-1,) + (1,) * (n.ndim - 1)), n, o)
                st = tuple(map(sel, new, st))
                return (cyc + act.astype(jnp.int32), st), None

            (cyc, state), _ = jax.lax.scan(body, (cyc, state), None,
                                           length=self.chunk)
            return cyc, state

        self._run_chunk = jit_chunk(run_chunk)

    # ------------------------------------------------------------------
    def init_state(self) -> MachineState:
        sh = lambda *spec: NamedSharding(self.mesh, P(*spec))
        D, B = self.D, self.B
        lead = () if B is None else (B,)
        bsp = () if B is None else (None,)
        return MachineState(
            regs=self.reg0, spads=self.spad0, gmem=self.gmem0,
            flags=jax.device_put(np.zeros(lead + (self.Cp,), np.uint32),
                                 sh(*bsp, self.AXIS)),
            cache_tags=jax.device_put(
                -np.ones(lead + (D, self.cache_lines), np.int32),
                sh(*bsp, self.AXIS)),
            counters=jax.device_put(np.zeros(lead + (D, 4), np.uint32),
                                    sh(*bsp, self.AXIS)),
        )

    def run(self, state: MachineState, num_cycles: int) -> MachineState:
        cyc = (jnp.int32(0) if self.B is None
               else jnp.zeros((self.B,), jnp.int32))
        carry = dispatch_chunks(
            self._run_chunk, cyc, tuple(state), self.chunk,
            int(num_cycles), lambda f: (f != 0).any(axis=-1).all())
        return MachineState(*carry)

    def _elem(self, a, b):
        """Strip the batch axis: element ``b`` (default 0) when batched,
        the array itself when not."""
        if self.B is None:
            return a
        return a[0 if b is None else b]

    def exceptions(self, state: MachineState, b: Optional[int] = None):
        """Exceptions as {core: id}; with batched state and ``b=None``,
        one dict per batch element (mirroring BatchedMachine)."""
        if self.B is not None and b is None:
            return [self.exceptions(state, i) for i in range(self.B)]
        f = to_host(self._elem(state.flags, b))[:self.C]
        return {int(c): int(e) for c, e in enumerate(f) if e}

    def read_reg(self, state: MachineState, rtl_name: str,
                 b: Optional[int] = None) -> int:
        words = self.p.state_regs[rtl_name]
        regs = to_host(self._elem(state.regs, b))
        out = 0
        for j, locs in enumerate(words):
            c, r = locs[0]
            out |= int(regs[c, r]) << (16 * j)
        return out

    def read_output(self, state: MachineState, name: str,
                    b: Optional[int] = None) -> int:
        core, mregs = self.p.outputs[name]
        regs = to_host(self._elem(state.regs, b))
        out = 0
        for j, r in enumerate(mregs):
            out |= int(regs[core, r]) << (16 * j)
        return out

    def perf(self, state: MachineState,
             b: Optional[int] = None) -> Dict[str, int]:
        """Performance counters (device 0 holds the privileged core). With
        batched state and ``b=None``, aggregates over the batch."""
        if self.B is not None and b is None:
            cnt = to_host(state.counters)[:, 0].sum(axis=0)
        else:
            cnt = to_host(self._elem(state.counters, b))[0]
        return {
            "vcycles": int(cnt[0]),
            "ghits": int(cnt[1]),
            "gmisses": int(cnt[2]),
            "stall_cycles": int(cnt[3]),
            "machine_cycles": int(cnt[0]) * self.p.vcpl + int(cnt[3]),
        }
