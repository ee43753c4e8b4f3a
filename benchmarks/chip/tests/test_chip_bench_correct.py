"""CPU tests of what decides ``correct``: the plain reference agrees with
the project's netlist interpreter, the float32 control fails the
comparison at the cell's own size, and a run whose timed path is broken
underneath comes out not correct, for each fault the farm cells can have.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time
from pathlib import Path

import numpy as np
import pytest

HERE = Path(__file__).resolve().parent
BENCH = HERE.parent
REPO = BENCH.parents[1]
sys.path[:0] = [str(BENCH), str(REPO / "src")]

import compare  # noqa: E402
import harness  # noqa: E402
import reference  # noqa: E402

SMALL = {"scale": "small", "params": {}, "batch": 8, "budget_vcycles": 44}


def _oracle(bench, cycles):
    """The project's own NetlistSim, as its oracle engine drives it."""
    from repro.core.interpreter import NetlistSim
    sim = NetlistSim(bench.circuit)
    raised = []
    for _ in range(cycles):
        if raised:
            break
        raised.extend(sim.step().exceptions)
    regs = {nm: sim.reg_value(nm) for nm in bench.circuit.reg_names.values()}
    return sim.cycle, frozenset(raised), regs


@pytest.mark.parametrize("design", ["bc", "mm", "mc", "cgra", "vta", "blur",
                                    "jpeg", "noc", "rv32r"])
def test_reference_agrees_with_the_netlist_interpreter(design):
    from repro.circuits import build
    bench = build(design, "small", seeds=[41])
    out = reference.simulate(bench.circuit, bench.n_cycles + 10)
    cycles, exc, regs = _oracle(bench, bench.n_cycles + 10)
    assert out.cycles[0] == cycles == bench.n_cycles
    assert out.exceptions[0] == exc == {compare.FINISH}
    assert {k: int(v[0]) for k, v in out.registers.items()} == regs


def test_reference_runs_every_stimulus_of_a_batch():
    """Batched planes: every stimulus equals a build of its seed alone."""
    from repro.circuits import build
    seeds = [3, 1234567, 2 ** 31 + 5]
    batch = build("mc", "small", seeds=seeds)
    out = reference.simulate(batch.circuit, batch.n_cycles + 10,
                             batch.reg_planes, batch.mem_planes)
    for b, s in enumerate(seeds):
        cycles, exc, regs = _oracle(build("mc", "small", seeds=[s]),
                                    batch.n_cycles + 10)
        assert (out.cycles[b], out.exceptions[b]) == (cycles, exc)
        assert {k: int(v[b]) for k, v in out.registers.items()} == regs


def test_reference_stops_a_stimulus_at_its_raising_cycle():
    from repro.circuits import build
    bench = build("mc", "small", seeds=[9])
    out = reference.simulate(bench.circuit, 5)
    assert out.cycles[0] == 5 and out.exceptions[0] == frozenset()


def test_float32_control_fails_the_comparison_at_the_cell_size():
    """The control: the reference at float32 put in the program's place,
    at the farm cells' scale, on three seeds."""
    cfg = harness.load_config("mc-farm")
    for seed in (11, 2 ** 31 + 3, 987654321):
        bench = harness.build_bench(dict(cfg, batch=64), seed)
        ref = harness.reference_of(bench, cfg["budget_vcycles"])
        low = harness.reference_of(bench, cfg["budget_vcycles"], "float32")
        sound = compare.judge([compare.answers(ref)], ref, bench.n_cycles)
        ctl = compare.judge([compare.answers(low)], ref, bench.n_cycles)
        assert sound.correct and sound.numbers == {"differ": 0,
                                                   "unfinished": 0}
        assert not ctl.correct and ctl.numbers["differ"] >= 5


def test_judge_counts_missing_extra_and_foreign_results():
    from repro.circuits import build
    bench = build("mc", "small", seeds=[1, 2, 3, 4])
    ref = harness.reference_of(bench, 44)
    ans = compare.answers(ref)
    assert compare.judge([ans, ans], ref, bench.n_cycles).attempted == 8
    missing = compare.judge([ans[:2]], ref, bench.n_cycles)
    assert missing.numbers == {"differ": 2, "unfinished": 2}
    extra = compare.judge([ans + ans[:1]], ref, bench.n_cycles)
    assert extra.numbers["differ"] == 1 and not extra.correct
    swapped = compare.judge([[ans[1], ans[0]] + ans[2:]], ref, bench.n_cycles)
    assert swapped.correct          # matched by batch index, not by order


# ------------------------------------------------------------ faults

def _run(hook=None):
    cell = harness.find_cell(harness.load_spec(), "mc-farm.1chip")
    cfg = dict(harness.load_config(cell["config"]), **SMALL)
    traffic = harness.load_traffic(cell["traffic"])
    import jax
    run, verdict = harness.execute(cell, cfg, traffic, 2 ** 31 + 17, 0.0,
                                   False, jax.devices()[:1],
                                   time.perf_counter(), lambda s: None, hook)
    return run, verdict


def _unchanged(eng):
    eng.m.run = lambda state, n: state
    return eng


def _half_left_out(eng):
    full = eng.run_batch
    eng.run_batch = lambda n: full(n)[:eng.batch // 2]
    return eng


def _exchange_left_out(eng):
    import jax
    eng.m.n_sends = 0
    eng.m._run_chunk = jax.jit(eng.m._bchunk_impl)
    return eng


def _answer_altered(eng):
    run = eng.m.run
    c, r = next(iter(eng.program.state_regs.values()))[0][0]

    def altered(state, n):
        out = run(state, n)
        b = eng.batch - 1
        return out._replace(regs=out.regs.at[b, c, r].set(
            out.regs[b, c, r] ^ 1))
    eng.m.run = altered
    return eng


def test_sound_run_is_correct():
    run, verdict = _run()
    assert verdict.correct and verdict.failed == 0
    assert verdict.attempted == 8 * len(run.launches) > 0
    assert run.window_compiles == 0


@pytest.mark.parametrize("fault", [_unchanged, _half_left_out,
                                   _exchange_left_out, _answer_altered])
def test_broken_timed_path_is_not_correct(fault):
    run, verdict = _run(fault)
    assert not verdict.correct
    assert verdict.failed >= len(run.launches) >= 1


FOUR_CHIPS = r"""
import json, sys, time
sys.path[:0] = sys.argv[1:4]
import jax, jax.numpy as jnp, harness
from batch_demux import BatchDemux
cell = harness.find_cell(harness.load_spec(), "mc-farm-4chip.4chip")
cfg = dict(harness.load_config(cell["config"]), scale="small", params={},
           batch=16, budget_vcycles=44)
traffic = harness.load_traffic(cell["traffic"])

def one_chip_read(eng):
    # every stimulus's registers are those of the stimulus at its
    # position in the first chip's shard, in the state the engine returns
    run, per = eng.m.run, eng.batch // 4

    def wrong(state, n):
        out = run(state, n)
        rows = jnp.arange(out.regs.shape[0]) % per
        return out._replace(regs=jax.device_put(out.regs[rows],
                                                out.regs.sharding))
    eng.m.run = wrong
    return eng

out = {}
for name, hook in (("sound", None), ("one_chip_read", one_chip_read),
                   ("batch_demux", BatchDemux),
                   ("one_chip_read_batch_demux",
                    lambda eng: BatchDemux(one_chip_read(eng)))):
    run, v = harness.execute(cell, cfg, traffic, 77, 0.0, False,
                             jax.devices()[:4], time.perf_counter(),
                             lambda s: None, hook)
    out[name] = [v.correct, v.numbers, run.window_compiles]
print(json.dumps(out))
"""


def test_four_chip_path_and_a_lost_cross_chip_read():
    """On four host devices: the sharded engine is correct, and a fault
    that gives every stimulus the first chip's registers is caught,
    whether the engines' demux or a batch demux reads the results."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run(
        [sys.executable, "-c", FOUR_CHIPS, str(HERE), str(BENCH),
         str(REPO / "src")],
        env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    for sound in ("sound", "batch_demux"):
        assert out[sound] == [True, {"differ": 0, "unfinished": 0}, 0]
    for fault in ("one_chip_read", "one_chip_read_batch_demux"):
        assert out[fault][0] is False
        assert out[fault][1]["differ"] > 0
