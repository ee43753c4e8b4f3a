"""Pallas vcycle kernel: shape sweeps + random-program allclose vs ref.py.

The kernel runs in interpret mode on the CPU backend; equivalence is
bit-exact (uint16 semantics), checked against both the pure-jnp oracle
(kernels/ref.py) and the numpy ISA simulator on compiled programs.
"""
import numpy as np
import jax.numpy as jnp
import pytest

try:  # hypothesis is optional (offline containers) — only the property
    # test needs it; the deterministic sweeps below always run
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False

from repro.core.isa import Op
from repro.kernels.ref import vcycle_ref
from repro.kernels.vcycle import vcycle_pallas

RESULT_OPS = [Op.MOV, Op.ADD, Op.ADDC, Op.CARRY, Op.SUB, Op.SUBB, Op.BORROW,
              Op.MUL, Op.MULH, Op.AND, Op.OR, Op.XOR, Op.NOT, Op.MUX,
              Op.SEQ, Op.SNE, Op.SLTU, Op.SLL, Op.SRL, Op.SRA, Op.SLLV,
              Op.SRLV, Op.SLICE, Op.LUT, Op.LD, Op.ST, Op.SEND, Op.EXPECT,
              Op.NOP]


def random_program(rng, C, T, R, S, L=8):
    code = np.zeros((T, C, 7), np.int32)
    for t in range(T):
        for c in range(C):
            op = rng.choice(RESULT_OPS)
            dst = rng.integers(1, R)
            srcs = rng.integers(0, R, 4)
            if op in (Op.SLL, Op.SRL, Op.SRA):
                imm = rng.integers(0, 16)
            elif op == Op.SLICE:
                w = rng.integers(1, 17)
                off = rng.integers(0, 16)
                imm = off * 32 + w
            elif op == Op.LUT:
                imm = rng.integers(0, L)
            else:
                imm = rng.integers(0, 1 << 15)
            code[t, c] = (int(op), dst, *srcs, imm)
    luts = rng.integers(0, 1 << 16, (C, L, 16)).astype(np.uint32)
    regs = rng.integers(0, 1 << 16, (C, R)).astype(np.uint32)
    regs[:, 0] = 0
    spads = rng.integers(0, 1 << 16, (C, S)).astype(np.uint32)
    flags = np.zeros((C,), np.uint32)
    return code, luts, regs, spads, flags


@pytest.mark.parametrize("C,T,R,S,tile", [
    (1, 4, 8, 16, 1),
    (4, 16, 32, 64, 2),
    (8, 32, 64, 32, 8),
    (16, 8, 16, 16, 4),
    (6, 12, 24, 48, 3),
])
def test_kernel_matches_ref_sweep(C, T, R, S, tile):
    rng = np.random.default_rng(C * 1000 + T)
    code, luts, regs, spads, flags = random_program(rng, C, T, R, S)
    args = (jnp.asarray(code), jnp.asarray(luts), jnp.asarray(regs),
            jnp.asarray(spads), jnp.asarray(flags))
    r_ref = vcycle_ref(*args)
    r_pal = vcycle_pallas(*args, tile=tile, interpret=True)
    for a, b, name in zip(r_ref, r_pal, ("regs", "spads", "flags", "trace")):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b), err_msg=name)


if HAVE_HYPOTHESIS:
    @settings(max_examples=15, deadline=None)
    @given(st.integers(0, 2**31 - 1), st.sampled_from([2, 4, 8]),
           st.sampled_from([4, 16, 48]))
    def test_kernel_matches_ref_property(seed, C, T):
        rng = np.random.default_rng(seed)
        code, luts, regs, spads, flags = random_program(rng, C, T, 32, 32)
        args = (jnp.asarray(code), jnp.asarray(luts), jnp.asarray(regs),
                jnp.asarray(spads), jnp.asarray(flags))
        r_ref = vcycle_ref(*args)
        r_pal = vcycle_pallas(*args, tile=2, interpret=True)
        for a, b in zip(r_ref, r_pal):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
else:
    @pytest.mark.skip(reason="hypothesis not installed in this environment")
    def test_kernel_matches_ref_property():
        pass


def test_interpret_resolves_from_platform():
    """interpret=None means interpret mode exactly on a CPU backend; an
    explicit value wins."""
    import jax
    from repro.kernels.ops import resolve_interpret
    assert resolve_interpret() == (jax.default_backend() == "cpu")
    assert resolve_interpret(False) is False
    assert resolve_interpret(True) is True


def test_ref_matches_isasim_on_compiled_program():
    """Triangulate: compiled bench -> one Vcycle on ref.py == numpy IsaSim."""
    from repro.circuits import build
    from repro.core.compile import compile_circuit
    from repro.core.isa import HardwareConfig
    from repro.core.isasim import IsaSim

    b = build("mc", "small")
    prog = compile_circuit(b.circuit, HardwareConfig(grid_width=4,
                                                     grid_height=4))
    C = prog.used_cores
    code = np.ascontiguousarray(prog.code[:C].transpose(1, 0, 2))
    sim = IsaSim(prog)
    regs = sim.regs.copy()
    spads = sim.spads.copy()
    sim.step()
    r, s, f, trace = vcycle_ref(
        jnp.asarray(code), jnp.asarray(prog.luts[:C].astype(np.uint32)),
        jnp.asarray(regs), jnp.asarray(spads),
        jnp.zeros((C,), jnp.uint32))
    # apply the exchange like the engine does
    r = np.asarray(r).copy()
    tr = np.asarray(trace)
    for i in range(prog.xchg_src_core.shape[0]):
        r[prog.xchg_dst_core[i], prog.xchg_dst_reg[i]] = \
            tr[prog.xchg_src_slot[i], prog.xchg_src_core[i]]
    np.testing.assert_array_equal(r, sim.regs)
    np.testing.assert_array_equal(np.asarray(s), sim.spads)


@pytest.mark.parametrize("BH,S,dh,bq,bk,dtype,causal", [
    (2, 256, 64, 64, 64, "float32", True),
    (2, 256, 64, 64, 128, "float32", False),
    (4, 512, 128, 128, 256, "bfloat16", True),
    (1, 128, 32, 128, 64, "float32", True),
    (3, 384, 64, 128, 128, "bfloat16", True),
])
def test_flash_attention_matches_ref(BH, S, dh, bq, bk, dtype, causal):
    from repro.kernels.flash_attention import flash_attention
    from repro.kernels.ref import flash_ref
    import jax
    rng = jax.random.key(BH * S)
    kq, kk, kv = jax.random.split(rng, 3)
    q = (jax.random.normal(kq, (BH, S, dh), jnp.float32)).astype(dtype)
    k = (jax.random.normal(kk, (BH, S, dh), jnp.float32)).astype(dtype)
    v = (jax.random.normal(kv, (BH, S, dh), jnp.float32)).astype(dtype)
    out = flash_attention(q, k, v, causal=causal, block_q=bq, block_k=bk,
                          interpret=True)
    ref = flash_ref(q, k, v, causal=causal)
    tol = 2e-2 if dtype == "bfloat16" else 2e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=tol, atol=tol)
