"""Engine throughput: simulated Vcycles/second per circuit and backend.

First entry in the repo's perf trajectory (PR 1): measures the partially-
evaluated fast path (``Machine(specialize=True)`` — opcode-set-specialized
slots, compact SEND capture, chunked K-Vcycle dispatch) against the seed
engine (``specialize=False`` — compute-all-select, full [T, C] trace,
per-Vcycle while_loop), plus the Pallas chunk kernel (interpret mode on a
CPU backend) and the vectorized numpy ISA simulator.

Emits ``results/bench/BENCH_engine.json`` and a copy at the repo root
(``BENCH_engine.json``) so the trajectory is easy to diff across PRs. Rows
are written incrementally and one circuit's failure cannot blank the whole
artifact (PR 2 fix: the committed artifact had been ``[]``).

  PYTHONPATH=src python -m benchmarks.bench_engine            # all circuits
  PYTHONPATH=src python -m benchmarks.bench_engine bc mm      # a subset
  PYTHONPATH=src python -m benchmarks.bench_engine bc --smoke # CI smoke
"""
from __future__ import annotations

import sys
import time

import jax
import numpy as np

from benchmarks.common import best_time, row_csv, run_rows
import repro.sim as sim
from repro.circuits import CIRCUITS
from repro.core import HardwareConfig

HW = HardwareConfig(grid_width=5, grid_height=5)
REPS = 3


def _rate_machine(m, n: int, reps: int = REPS) -> float:
    """Vcycles/sec of a raw core.bsp.Machine (timed without the facade's
    RunResult probe sweep, keeping rows comparable across PRs)."""
    def once():
        jax.block_until_ready(m.run(m.init_state(), n).regs)
    return n / best_time(once, reps)


def _rate_isasim(prog, n: int, reps: int = REPS) -> float:
    from repro.core.isasim import IsaSim
    best = float("inf")
    for _ in range(reps):
        sim = IsaSim(prog)
        t0 = time.perf_counter()
        sim.run(n)
        best = min(best, time.perf_counter() - t0)
    return n / best


def bench_circuit(nm: str, scale: str = "full", reps: int = REPS) -> dict:
    # LUT-free compile: the specialization headline the paper-style
    # engines target (no 16-pattern loop anywhere in the schedule)
    s = sim.compile(nm, HW, scale=scale, use_luts=False)
    b, prog = s.bench, s.program
    # stay below the FINISH cycle; cap the cycle count so the slow seed
    # arm keeps the whole sweep in seconds
    n = min(max(8, b.n_cycles - 2), 128)

    row = {
        "circuit": nm,
        "scale": scale,
        "t_compute": prog.t_compute,
        "used_cores": prog.used_cores,
        "n_sends": prog.n_sends,
        "n_ops": len(prog.op_set()),
        "lut_free": True,
        "vcycles": n,
    }
    new = s.engine("machine").m
    row["jnp_vcycles_per_s"] = _rate_machine(new, n, reps)
    seed = s.engine("seed").m
    row["seed_vcycles_per_s"] = _rate_machine(seed, n, reps)
    row["speedup_vs_seed"] = (row["jnp_vcycles_per_s"]
                              / row["seed_vcycles_per_s"])
    row["isasim_vcycles_per_s"] = _rate_isasim(prog, n, reps)
    if not prog.has_global:
        pal = s.engine("pallas").m
        row["pallas_vcycles_per_s"] = _rate_machine(pal, n, reps)
    else:
        row["pallas_vcycles_per_s"] = None

    # bit-exactness of the fast path against the seed engine
    st_new = new.run(new.init_state(), b.n_cycles + 10)
    st_seed = seed.run(seed.init_state(), b.n_cycles + 10)
    row["bit_exact_vs_seed"] = bool(
        np.array_equal(np.asarray(st_new.regs), np.asarray(st_seed.regs))
        and np.array_equal(np.asarray(st_new.spads),
                           np.asarray(st_seed.spads))
        and np.array_equal(np.asarray(st_new.flags),
                           np.asarray(st_seed.flags)))
    row_csv(f"engine/{nm}", 1e6 / row["jnp_vcycles_per_s"],
            f"{row['speedup_vs_seed']:.2f}x_vs_seed")
    return row


def run(names=None, smoke: bool = False) -> None:
    scale = "small" if smoke else "full"
    reps = 1 if smoke else REPS
    run_rows([nm for nm in sorted(CIRCUITS) if not names or nm in names],
             lambda nm: bench_circuit(nm, scale, reps),
             "BENCH_engine", smoke,
             lambda rows: "best jnp speedup vs seed engine: %.2fx"
             % max((r["speedup_vs_seed"] for r in rows), default=0.0))


if __name__ == "__main__":
    argv = sys.argv[1:]
    run([a for a in argv if not a.startswith("-")] or None,
        smoke="--smoke" in argv)
