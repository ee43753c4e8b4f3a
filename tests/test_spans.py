"""The engines' spans and counters (``repro.spans``) and the one name of
their chunk programs.

- exact counter deltas for a batched launch, so that a change that adds a
  device-to-host read shows up here;
- ``to_host`` counts device arrays only, and counting is thread-safe;
- every chunk path lowers to the XLA module ``jit_sim_chunk``.

The profiler side (the spans as trace events, under the harness's spans)
is tested with the benchmark's trace reader, in
``benchmarks/chip/tests/test_chip_bench_spans.py``.
"""
import os
import subprocess
import sys
import textwrap
import threading
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import spans
from repro.circuits import build
from repro.core.bsp import BatchedMachine, Machine
from repro.core.compile import compile_circuit
from repro.core.isa import HardwareConfig
from repro.sim.engine import BatchedEngine

ROOT = Path(__file__).resolve().parents[1]
HW = HardwareConfig(grid_width=5, grid_height=5)
SEEDS = [3, 11, 42, 7]
CHUNK = 8


@pytest.fixture(scope="module")
def mc_small():
    b = build("mc", "small", seeds=SEEDS)
    return b, compile_circuit(b.circuit, HW)


def _delta(before):
    after = spans.counters()
    return {k: after.get(k, 0) - before.get(k, 0)
            for k in set(after) | set(before)}


@pytest.mark.parametrize("cycles", [20, 200])
def test_run_batch_counts_exactly(mc_small, cycles):
    """A B=4 launch: one snapshot and three reads per stimulus (registers,
    counters, flags) plus one flag sync per chunk dispatched, and exactly
    those arrays' bytes. At 200 cycles every stimulus stops at FINISH and
    the dispatch stops at the chunk that holds it."""
    b, prog = mc_small
    eng = BatchedEngine(prog, images=b.images_batch(prog), chunk=CHUNK)
    B, C, R = eng.batch, eng.m.C, eng.m.R
    before = spans.counters()
    res = eng.run_batch(cycles)
    got = _delta(before)
    last = max(r.cycles for r in res)
    assert (cycles == 20) == (last == 20)
    chunks = -(-last // CHUNK)
    assert got["sim.snapshots"] == B
    assert got["sim.host_reads"] == 3 * B + chunks
    per_stimulus = (C * R + 4 + C) * 4          # regs[b], counters, flags
    assert got["sim.host_read_bytes"] == B * per_stimulus + chunks * B * C * 4


def test_to_host_counts_device_arrays_only():
    before = spans.counters()
    host = np.arange(6, dtype=np.uint32)
    assert spans.to_host(host) is host
    assert _delta(before).get("sim.host_reads", 0) == 0
    got = spans.to_host(jnp.arange(6, dtype=jnp.uint32))
    np.testing.assert_array_equal(got, host)
    assert _delta(before)["sim.host_reads"] == 1
    assert _delta(before)["sim.host_read_bytes"] == 24


def test_counters_are_a_copy_and_reset():
    spans.count("test.copy", 5)
    c = spans.counters()
    c["test.copy"] = 0
    assert spans.counters()["test.copy"] >= 5
    spans.reset_counters()
    assert spans.counters() == {}


def test_counting_loses_no_update_across_threads():
    switch = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        before = spans.counters().get("test.threads", 0)
        n, per = 4 * (os.cpu_count() or 1), 2000

        def work():
            for _ in range(per):
                spans.count("test.threads")

        threads = [threading.Thread(target=work) for _ in range(n)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(switch)
    assert spans.counters()["test.threads"] - before == n * per


def _module(machine, cyc, state):
    text = machine._run_chunk.lower(cyc, jnp.int32(8), tuple(state)).as_text()
    return text.split("module @", 1)[1].split(" ", 1)[0]


@pytest.mark.parametrize("kind,backend", [
    ("machine", "jnp"), ("machine", "pallas"),
    ("batched1", "jnp"), ("batched", "jnp"), ("batched", "pallas")])
def test_chunk_program_has_one_name(mc_small, kind, backend):
    """The chunk program is ``jit_sim_chunk`` on the single-stimulus, the
    B=1 and the vmapped batched paths, jnp or Pallas (interpret mode)."""
    b, prog = mc_small
    images = b.images_batch(prog)
    if kind == "machine":
        m = Machine(prog, backend=backend, chunk=CHUNK)
        cyc = jnp.int32(0)
    else:
        imgs = tuple(a[:1] for a in images) if kind == "batched1" else images
        m = BatchedMachine(prog, images=imgs, backend=backend, chunk=CHUNK)
        assert m._plain == (kind == "batched1")
        cyc = jnp.zeros((m.B,), jnp.int32)
    assert _module(m, cyc, m.init_state()) == "jit_sim_chunk"


def test_chunk_program_has_one_name_on_four_devices():
    """The sharded batched engine and the core-sharded grid engine, on four
    host devices."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=str(ROOT / "src"),
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    body = """
        import numpy as np, jax, jax.numpy as jnp
        from jax.sharding import Mesh
        from repro.circuits import build
        from repro.core.isa import HardwareConfig
        from repro.core.compile import compile_circuit
        from repro.core.bsp import ShardedBatchedMachine
        from repro.core.grid import GridMachine

        def module(m, cyc, state):
            text = m._run_chunk.lower(cyc, jnp.int32(8),
                                      tuple(state)).as_text()
            return text.split("module @", 1)[1].split(" ", 1)[0]

        b = build("mc", "small", seeds=[3, 11, 42, 7, 9])
        prog = compile_circuit(b.circuit,
                               HardwareConfig(grid_width=5, grid_height=5))
        sm = ShardedBatchedMachine(prog, images=b.images_batch(prog),
                                   devices=jax.devices()[:4])
        assert sm.D == 4
        print("sharded", module(sm, sm._cyc0, sm.init_state()))
        gm = GridMachine(prog, Mesh(np.array(jax.devices()), ("cores",)),
                         images=b.images(prog))
        print("grid", module(gm, jnp.zeros((gm.B,), jnp.int32),
                             gm.init_state()))
    """
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(body)],
                       capture_output=True, text=True, env=env, timeout=600)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "sharded jit_sim_chunk" in r.stdout
    assert "grid jit_sim_chunk" in r.stdout
