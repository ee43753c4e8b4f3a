"""Batch-sharded execution (PR 5): B stimuli of one compiled Program split
``[D, B/D]`` over a device mesh (``core.bsp.ShardedBatchedMachine``).

Contracts under test:

- every element of a sharded run is bit-exact against an independent
  single-stimulus specialized run of the same stimulus (mm/mc/bc, 8 forced
  host devices);
- a non-divisible B pads to ``ceil(B/D)*D`` and the padding elements never
  execute, raise, or appear in results/exceptions/perf;
- per-element exception freezing is device-local: an element living on a
  device != 0 freezes at its own raising Vcycle, and the sharded Pallas
  chunk kernel matches the sharded jnp graph;
- facade auto-selection: multi-device mesh + batch picks
  ``ShardedBatchedEngine`` (B >= 2*D), a single device falls back to
  ``BatchedEngine``, `shard_batch=` overrides both ways;
- the B=1 batched fast path skips the vmap wrapper entirely;
- ``Program.init_images_batch`` (host-parallel, stacked) matches the
  sequential per-stimulus ``init_images``.

Multi-device tests run in a subprocess with
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` (the pattern of
``test_batched.py::test_batched_grid_machine_8dev``).
"""
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import numpy as np
import pytest

from repro.circuits import FINISH, build
from repro.core.bsp import BatchedMachine, Machine, ShardedBatchedMachine
from repro.core.compile import compile_circuit
from repro.core.isa import HardwareConfig

ROOT = Path(__file__).resolve().parents[1]
HW = HardwareConfig(grid_width=5, grid_height=5)


def _run_8dev(body: str, ok: str, timeout: int = 900) -> None:
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = str(ROOT / "src")
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(body)],
                       capture_output=True, text=True, env=env,
                       timeout=timeout)
    assert r.returncode == 0, r.stdout + r.stderr
    assert ok in r.stdout


# ----------------------------------------------------------------------
# multi-device (8 forced host devices, subprocess)
# ----------------------------------------------------------------------

def test_sharded_bit_exact_and_padding_8dev():
    """B=11 (non-divisible by D=8 -> padded to 16) on mm/mc/bc: every
    element bit-exact vs an independent single-stimulus specialized run;
    padding executes nothing and leaks nowhere."""
    body = """
        import numpy as np, jax
        from repro.circuits import build, FINISH
        from repro.core.isa import HardwareConfig
        from repro.core.compile import compile_circuit
        from repro.core.bsp import Machine, ShardedBatchedMachine

        assert len(jax.devices()) == 8
        HW = HardwareConfig(grid_width=5, grid_height=5)
        B = 11
        for nm in ("mm", "mc", "bc"):
            b = build(nm, "small", seeds=[1000 + i for i in range(B)])
            prog = compile_circuit(b.circuit, HW)
            imgs = b.images_batch(prog)            # stacked, host-parallel
            sm = ShardedBatchedMachine(prog, images=imgs)
            assert (sm.D, sm.B, sm.Bp) == (8, B, 16)
            st = sm.run(sm.init_state(), b.n_cycles + 10)
            m = Machine(prog)
            for i in range(B):
                s1 = m.run(m.init_state(
                    images=(imgs[0][i], imgs[1][i], imgs[2][i])),
                    b.n_cycles + 10)
                np.testing.assert_array_equal(np.asarray(st.regs[i]),
                                              np.asarray(s1.regs))
                np.testing.assert_array_equal(np.asarray(st.spads[i]),
                                              np.asarray(s1.spads))
                np.testing.assert_array_equal(np.asarray(st.flags[i]),
                                              np.asarray(s1.flags))
                np.testing.assert_array_equal(np.asarray(st.counters[i]),
                                              np.asarray(s1.counters))
                assert set(sm.exceptions(st, i).values()) == {FINISH}
            # padding elements never execute, never raise
            assert not np.asarray(st.flags[B:]).any()
            assert not np.asarray(st.counters[B:]).any()
            # ...and never surface: accessors cover the logical batch only
            assert len(sm.exceptions(st)) == B
            p = sm.perf(st)
            assert p["batch"] == B
            assert p["vcycles"] == B * b.n_cycles
        print("SHARDED-EXACT-OK")
    """
    _run_8dev(body, "SHARDED-EXACT-OK")


def test_sharded_freeze_on_nonzero_device_8dev():
    """Per-stimulus FINISH cycles spread over all 8 devices: each element
    (including those on devices != 0) freezes at its own raising Vcycle,
    device-locally; the sharded Pallas chunk kernel matches the sharded
    jnp graph bit-for-bit."""
    body = """
        import numpy as np, jax
        from repro.circuits import FINISH
        from repro.circuits.common import Planes, make_counter
        from repro.core.isa import HardwareConfig
        from repro.core.compile import compile_circuit
        from repro.core.netlist import Circuit
        from repro.core.bsp import Machine, ShardedBatchedMachine

        assert len(jax.devices()) == 8
        HW = HardwareConfig(grid_width=5, grid_height=5)
        stops = [5 + 4 * i for i in range(16)]   # 2 elements per device
        c = Circuit("freeze")
        planes = Planes(c, len(stops), live=True)
        ctr = make_counter(c, 16)
        stop = planes.hold(stops, 16, "stopc")
        acc = planes.reg(32, [0x1000 * (i + 1) for i in range(len(stops))],
                         "acc")
        c.set_next(acc, acc + (acc >> 3) + 1)
        c.finish_when(ctr.eq(stop), FINISH)
        prog = compile_circuit(c, HW)
        images = [prog.init_images(r, m)
                  for r, m in zip(planes.regs, planes.mems)]
        sj = ShardedBatchedMachine(prog, images=images, chunk=8)
        stj = sj.run(sj.init_state(), 100)
        sp = ShardedBatchedMachine(prog, images=images, backend="pallas",
                                   chunk=8, interpret=True)
        stp = sp.run(sp.init_state(), 100)
        for i, s in enumerate(stops):
            # element i lives on device i // 2; all must freeze locally
            assert sj.perf(stj, i)["vcycles"] == s + 1
            assert set(sj.exceptions(stj, i).values()) == {FINISH}
            m = Machine(prog, specialize=False)
            s1 = m.run(m.init_state(images=images[i]), 100)
            np.testing.assert_array_equal(np.asarray(stj.regs[i]),
                                          np.asarray(s1.regs))
            np.testing.assert_array_equal(np.asarray(stj.flags[i]),
                                          np.asarray(s1.flags))
        for lj, lp in zip(stj, stp):
            np.testing.assert_array_equal(np.asarray(lj), np.asarray(lp))
        print("SHARDED-FREEZE-OK")
    """
    _run_8dev(body, "SHARDED-FREEZE-OK")


def test_facade_auto_selection_8dev():
    """mesh + batch picks the sharded engine (B >= 2*D); small batches and
    shard_batch=False stay on the vmapped single-device engine; results
    agree between the two."""
    body = """
        import jax
        import repro.sim as sim
        from repro.sim import BatchedEngine, ShardedBatchedEngine
        from repro.core import HardwareConfig

        assert len(jax.devices()) == 8
        HW = HardwareConfig(grid_width=5, grid_height=5)
        seeds = [100 + i for i in range(16)]
        s = sim.compile("mc", HW, scale="small", seeds=seeds)
        e = s.engine("auto")
        assert isinstance(e, ShardedBatchedEngine), type(e)
        res = s.run()
        assert len(res) == 16 and all(r.finished for r in res)

        sb = sim.compile("mc", HW, scale="small", seeds=seeds,
                         shard_batch=False)
        eb = sb.engine("auto")
        assert isinstance(eb, BatchedEngine)
        assert not isinstance(eb, ShardedBatchedEngine)
        resb = sb.run()
        assert [r.registers for r in resb] == [r.registers for r in res]
        assert [r.exceptions for r in resb] == [r.exceptions for r in res]

        s4 = sim.compile("mc", HW, scale="small", seeds=seeds[:4])
        e4 = s4.engine("auto")       # B=4 < 2*D: stay vmapped
        assert isinstance(e4, BatchedEngine)
        assert not isinstance(e4, ShardedBatchedEngine)
        print("FACADE-AUTO-OK")
    """
    _run_8dev(body, "FACADE-AUTO-OK")


# ----------------------------------------------------------------------
# single-device (in-process)
# ----------------------------------------------------------------------

@pytest.fixture(scope="module")
def mc_small():
    b = build("mc", "small", seeds=[3, 11, 42])
    prog = compile_circuit(b.circuit, HW)
    return b, prog


def test_sharded_single_device_matches_batched(mc_small):
    """D=1 is the degenerate mesh: the sharded engine must reproduce the
    vmapped engine exactly (same chunk body, one shard)."""
    import jax
    b, prog = mc_small
    imgs = b.images_batch(prog)
    sm = ShardedBatchedMachine(prog, images=imgs,
                               devices=jax.devices()[:1])
    assert (sm.D, sm.Bp) == (1, sm.B)
    bm = BatchedMachine(prog, images=b.images(prog))
    st = sm.run(sm.init_state(), b.n_cycles + 10)
    sb = bm.run(bm.init_state(), b.n_cycles + 10)
    for ls, lb in zip(st, sb):
        np.testing.assert_array_equal(np.asarray(ls), np.asarray(lb))


def test_batched_b1_skips_vmap(mc_small):
    """A batch of one dispatches the plain specialized graph — no vmap
    wrapper — and stays bit-exact against the single-stimulus engine."""
    b, prog = mc_small
    images = b.images(prog)
    bm = BatchedMachine(prog, images=images[:1])
    assert bm._plain
    assert bm._run_chunk.__wrapped__.__wrapped__.__func__ is \
        BatchedMachine._b1chunk_impl
    st = bm.run(bm.init_state(), b.n_cycles + 10)
    m = Machine(prog)
    s1 = m.run(m.init_state(images=images[0]), b.n_cycles + 10)
    np.testing.assert_array_equal(np.asarray(st.regs[0]),
                                  np.asarray(s1.regs))
    np.testing.assert_array_equal(np.asarray(st.flags[0]),
                                  np.asarray(s1.flags))
    np.testing.assert_array_equal(np.asarray(st.counters[0]),
                                  np.asarray(s1.counters))
    # a real batch keeps the vmapped body
    assert not BatchedMachine(prog, images=images)._plain


def test_init_images_batch_matches_sequential(mc_small):
    """The host-parallel stacked generator is a pure layout change: each
    row equals the sequential per-stimulus init_images output, threaded or
    not."""
    b, prog = mc_small
    stacked = prog.init_images_batch(b.reg_planes, b.mem_planes)
    serial = prog.init_images_batch(b.reg_planes, b.mem_planes, workers=1)
    singles = [prog.init_images(r, m)
               for r, m in zip(b.reg_planes, b.mem_planes)]
    for k in range(3):
        np.testing.assert_array_equal(stacked[k], serial[k])
        np.testing.assert_array_equal(
            stacked[k], np.stack([im[k] for im in singles]))


def test_facade_single_device_falls_back(mc_small):
    """On one device, auto stays on the vmapped engine; shard_batch=True
    still runs (degenerate D=1 mesh) with identical results; B=1 avoids
    the batched engine entirely."""
    import repro.sim as sim
    from repro.sim import (BatchedEngine, MachineEngine,
                           ShardedBatchedEngine)
    b, prog = mc_small
    s = sim.compile(b, HW)
    e = s.engine("auto")
    assert isinstance(e, BatchedEngine)
    assert not isinstance(e, ShardedBatchedEngine)
    res = s.run()
    es = s.engine("auto", shard_batch=True)
    assert isinstance(es, ShardedBatchedEngine)
    res_s = es.run_batch(s.default_cycles())
    assert [r.registers for r in res_s] == [r.registers for r in res]
    s1 = sim.compile("mc", HW, scale="small", seeds=[7])
    assert isinstance(s1.engine("auto"), MachineEngine)
