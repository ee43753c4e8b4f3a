"""Run one cell of the chip benchmark and print its result line.

  python3 benchmarks/chip/run.py --workload mc-farm.1chip --seed 7 \
      --seconds 10 --trace 0

Run it from the root of the checkout, on a machine that holds the chips
the cell asks for. It exits non-zero, and prints no result, where JAX
finds no TPU. See ``harness.py`` for what a run does.
"""
import sys
import time

T_START = time.perf_counter()

from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE), str(HERE.parents[1] / "src")]

if __name__ == "__main__":
    import harness
    sys.exit(harness.main(t_start=T_START))
